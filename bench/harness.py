"""The window: a recorder handed to ``run_federated`` that times the run
from its hooks, and a phase profiler handed to the scheduler through it.

The program calls the recorder once per fused chunk (``on_sync_chunk``)
or per aggregation event (``on_async_event``), after the chunk's outputs
are on the host. Set-up ends at the ``OPEN_AT``-th hook: the first chunk
builds the programs that start the run, and the second builds what only
the steady loop calls (the sync scheduler's round indices of a later
chunk), so that nothing compiles or loads inside the window. The window
runs from that hook to the first hook at or after ``seconds`` later (and
after the first ``correct.COMPARED_ROUNDS`` rounds, which the reference
follows), and the recorder then ends the call by raising
``WindowClosed``. Rounds and time are both counted to that hook.
"""

from __future__ import annotations

import contextlib
import time

import numpy as np

from bench.correct import COMPARED_ROUNDS

OPEN_AT = 2


class WindowClosed(Exception):
    """Raised from a hook to end ``run_federated`` when the window closes."""


class Phases:
    """The part of ``repro.obs.profile.Profiler`` the schedulers call:
    host-clock intervals of their ``compile``/``dispatch``/``device_get``
    phases, each also written to the device trace as a TraceAnnotation
    while one is being taken."""

    def __init__(self):
        self.spans: list[tuple[str, float, float]] = []
        self.annotate = False

    def begin_chunk(self, t0, n):
        pass

    def end_chunk(self):
        pass

    @contextlib.contextmanager
    def phase(self, name: str):
        ctx = contextlib.nullcontext()
        if self.annotate:
            import jax

            ctx = jax.profiler.TraceAnnotation(f"bench.{name}")
        t0 = time.perf_counter()
        try:
            with ctx:
                yield
        finally:
            self.spans.append((name, t0, time.perf_counter()))


class Window:
    """Recorder for ``run_federated(recorder=...)``.

    ``started`` is the process's start on the host clock; ``on_hook(window,
    now)`` runs after every hook's accounting (the traced run starts and
    stops its trace there).
    """

    def __init__(self, seconds: float, started: float, on_hook=None):
        self.seconds = float(seconds)
        self.started = started
        self.on_hook = on_hook
        self.profiler = Phases()
        self.opened: dict = {}
        self.t_first: float | None = None
        self.t_last: float | None = None
        self.rounds = 0            # rounds or events completed in the window
        self.failed = 0            # of those, ones with a rejected update
        self.hooks: list[tuple[float, int, np.ndarray]] = []  # (time, n, sel)
        # the first hooks' outputs, and under the async scheduler its
        # landing and dispatch decisions, for the reference to follow
        self.early: list[dict] = []
        self.decisions: list[tuple] = []
        self._events = 0

    # -- recorder interface ------------------------------------------------
    def open_run(self, **kw):
        self.opened = kw

    def log(self, line: str):
        pass

    def on_async_dispatch(self, clients, t_dispatch, client_pms):
        if self._events <= COMPARED_ROUNDS:
            self.decisions.append(("dispatch", np.array(clients), np.array(client_pms)))

    def close(self, history=None):
        pass

    def on_sync_chunk(self, *, acc, sel, pms, update_norm, rejected=None, **kw):
        outs = {"acc": acc, "sel": sel, "pms": pms, "norm": update_norm}
        self._hook(outs, np.asarray(sel), rejected)

    def on_async_event(self, *, acc, sel, pms, update_norm, landed_clients,
                       rejected=0, **kw):
        self._events += 1
        if self._events <= COMPARED_ROUNDS:
            self.decisions.append(("land", np.array(landed_clients)))
        outs = {"acc": acc[None], "sel": sel[None], "pms": pms[None],
                "norm": update_norm[None]}
        self._hook(outs, np.asarray(sel)[None], np.asarray([rejected]))

    # -- window ------------------------------------------------------------
    @property
    def early_outs(self) -> dict:
        """The first compared rounds' (or events') outputs, (T, C) each."""
        return {k: np.concatenate([o[k] for o in self.early]) for k in self.early[0]}

    @property
    def setup_s(self) -> float:
        return self.t_first - self.started

    @property
    def window_s(self) -> float:
        return self.t_last - self.t_first

    def _hook(self, outs, sel, rejected):
        now = time.perf_counter()
        if sum(o["acc"].shape[0] for o in self.early) < COMPARED_ROUNDS:
            self.early.append({k: np.array(v) for k, v in outs.items()})
        self.hooks.append((now, sel.shape[0], sel))
        if len(self.hooks) == OPEN_AT:
            self.t_first = self.t_last = now
        elif len(self.hooks) > OPEN_AT:
            self.t_last = now
            self.rounds += sel.shape[0]
            if rejected is not None:
                self.failed += int((np.asarray(rejected) > 0).sum())
        if self.on_hook is not None:
            self.on_hook(self, now)
        kept = sum(o["acc"].shape[0] for o in self.early)
        if (self.t_first is not None and now - self.t_first >= self.seconds
                and kept >= COMPARED_ROUNDS):
            raise WindowClosed
