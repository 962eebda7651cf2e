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

plus a jit cache-miss count (one per ``compile``) and a device-memory
watermark sampled from ``jax.live_arrays()`` after each chunk — the
always-on generalization of the loop bench's one-shot donation audit.

``jax_trace_dir`` additionally captures a ``jax.profiler`` trace
(TensorBoard/Perfetto-loadable) around the run — behind its own flag
because the capture has real overhead and writes its own artifact tree.

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


class Profiler:
    """Accumulates per-chunk phase timings + memory watermark; pure host
    state, summarized by ``summary()`` into ``profile.json``."""

    def __init__(self, jax_trace_dir: str | None = None):
        self.totals: dict[str, float] = {}
        self.chunks: list[dict] = []
        self.cache_misses = 0
        self.peak_live_bytes = 0
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
        self.sample_memory()
        self._current = None

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            self.totals[name] = self.totals.get(name, 0.0) + dt
            if name == "compile":
                self.cache_misses += 1
            if self._current is not None:
                self._current[f"{name}_s"] = self._current.get(f"{name}_s", 0.0) + dt

    def sample_memory(self):
        live = sum(
            a.size * a.dtype.itemsize
            for a in jax.live_arrays()
            if not a.is_deleted()
        )
        self.peak_live_bytes = max(self.peak_live_bytes, int(live))
        if self._current is not None:
            self._current["live_bytes"] = int(live)

    # -- output ------------------------------------------------------------
    def summary(self) -> dict:
        return {
            "totals_s": dict(self.totals),
            "jit_cache_misses": self.cache_misses,
            "peak_live_bytes": self.peak_live_bytes,
            "jax_trace_dir": self._jax_trace_dir,
            "chunks": self.chunks,
        }
