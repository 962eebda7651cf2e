"""Opt-in wall-clock profiling of the real executor loop.

Where the rest of ``repro.obs`` observes the *simulated* clock, the
``Profiler`` measures where actual host time goes while the schedulers
drive the device: per chunk (sync) or per event (async) it splits

- ``compile``    — tracing + XLA compilation of a step executable (the
                   schedulers AOT-lower each distinct chunk length through
                   ``jitted.lower(...).compile()`` when profiling, so
                   compile time is attributed separately instead of hiding
                   inside the first dispatch),
- ``dispatch``   — handing the executable its inputs until it returns
                   (on an async accelerator backend this is enqueue time;
                   on CPU it includes device compute),
- ``device_get`` — the blocking fetch of the chunk's stacked out leaves,

plus the host phases of the scheduler loop between fetching one chunk
or event and dispatching the next (``queue``, ``stage``, ``account``,
``record``; see ``repro.fl.sched``), a jit cache-miss count (one per
``compile``), and the devices' peak memory at the end of the run
(``memory_stats()["peak_bytes_in_use"]``; null where the backend keeps no
such count, as the CPU).

``jax_trace_dir`` additionally captures a ``jax.profiler`` trace
(TensorBoard/Perfetto-loadable) around the run — behind its own flag
because the capture has real overhead and writes its own artifact tree.
While it runs, every phase is also a ``TraceAnnotation`` named
``fl.<phase>`` on the trace's host clock, beside the device ops that the
round step's ``fl.*`` named scopes label.

The profiler is opt-in end to end: the schedulers hold ``None`` unless
``RunRecorder(profile=True)`` attached one, and every hook sits behind an
``is not None`` check, so the disabled path costs nothing.
"""

from __future__ import annotations

import contextlib
import time

import jax

__all__ = ["Profiler"]


def phase_timer(prof: "Profiler | None", name: str):
    """Context manager timing a phase on ``prof`` — a no-op context when
    profiling is off (the schedulers' single call site for both paths)."""
    if prof is None:
        return contextlib.nullcontext()
    return prof.phase(name)


def _device_peak_bytes() -> int | None:
    """Largest ``peak_bytes_in_use`` over the local devices, or None where
    the backend reports no memory statistics."""
    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in jax.local_devices()
    ]
    peaks = [p for p in peaks if p is not None]
    return int(max(peaks)) if peaks else None


class Profiler:
    """Accumulates per-chunk phase timings; pure host state, summarized by
    ``summary()`` into ``profile.json``."""

    def __init__(self, jax_trace_dir: str | None = None):
        self.totals: dict[str, float] = {}
        self.chunks: list[dict] = []
        self.cache_misses = 0
        self._current: dict | None = None
        self._jax_trace_dir = jax_trace_dir
        self._jax_tracing = False

    # -- lifecycle ---------------------------------------------------------
    def start(self):
        if self._jax_trace_dir:
            # a trace that was asked for and cannot start is an error: the
            # run must not silently carry on without it
            jax.profiler.start_trace(self._jax_trace_dir)
            self._jax_tracing = True

    def stop(self):
        if self._jax_tracing:
            try:
                jax.profiler.stop_trace()
            finally:
                self._jax_tracing = False

    # -- per-chunk hooks ---------------------------------------------------
    def begin_chunk(self, t0: int, n: int):
        self._current = {"t0": int(t0), "rounds": int(n)}
        self.chunks.append(self._current)

    def end_chunk(self):
        self._current = None

    @contextlib.contextmanager
    def phase(self, name: str):
        annotation = contextlib.nullcontext()
        if self._jax_tracing:
            annotation = jax.profiler.TraceAnnotation(f"fl.{name}")
        t0 = time.perf_counter()
        try:
            with annotation:
                yield
        finally:
            dt = time.perf_counter() - t0
            self.totals[name] = self.totals.get(name, 0.0) + dt
            if name == "compile":
                self.cache_misses += 1
            if self._current is not None:
                self._current[f"{name}_s"] = self._current.get(f"{name}_s", 0.0) + dt

    # -- output ------------------------------------------------------------
    def summary(self) -> dict:
        return {
            "totals_s": dict(self.totals),
            "jit_cache_misses": self.cache_misses,
            "device_peak_bytes": _device_peak_bytes(),
            "jax_trace_dir": self._jax_trace_dir,
            "chunks": self.chunks,
        }
