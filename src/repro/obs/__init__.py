"""repro.obs — host-side observability for the federated executor.

Three layers, all opt-in and all pure host-side observation (a recorded
run's device trajectory is bit-identical to an unrecorded one):

- ``repro.obs.record`` — ``RunRecorder``: structured run records
  (manifest + per-round ``metrics.jsonl`` + progress log), fed by the
  schedulers from the chunked executor's stacked out leaves.
- ``repro.obs.trace``  — Chrome/Perfetto trace-event export on the
  *simulated* clock (per-client dispatch/train/upload lanes, aggregation
  instants, sync round/chunk spans) + the schema validator CI runs.
- ``repro.obs.profile`` — opt-in wall-clock profiling of the real loop
  (compile vs dispatch vs device_get per chunk, the scheduler's host
  phases ``queue``/``stage``/``account``/``record``, jit cache misses, the
  devices' peak memory, optional ``jax.profiler`` capture).

On the device side, every op of the round step carries a named scope in
its HLO ``op_name`` (``repro.fl.phases``): ``fl.round`` / ``fl.event`` /
``fl.chunk`` for the body, and ``fl.gather``, ``fl.personalize``,
``fl.train``, ``fl.transmit``, ``fl.aggregate``, ``fl.scatter``,
``fl.eval``, ``fl.select`` for the phase inside it. With
``RunRecorder(profile=True, jax_trace_dir=...)`` the captured trace holds
both: each host phase as an ``fl.<phase>`` annotation on the trace's
clock, beside the device ops, whose scopes TensorBoard's profile plugin
reads from the HLO the trace keeps (``bench.scopes`` splits a TPU trace's
busy time by them).

Attach a recorder through the stable entry point::

    from repro.obs import RunRecorder
    rec = RunRecorder("experiments/run0", trace=True)
    h = run_federated(ds, cfg, recorder=rec)      # writes experiments/run0/

Open ``trace.json`` at https://ui.perfetto.dev (or chrome://tracing).
"""

from repro.obs.profile import Profiler
from repro.obs.record import (
    RunRecorder,
    environment_snapshot,
    format_async_progress,
    format_sync_progress,
)
from repro.obs.trace import TraceBuilder, validate_trace, validate_trace_file

__all__ = [
    "Profiler",
    "RunRecorder",
    "TraceBuilder",
    "environment_snapshot",
    "format_async_progress",
    "format_sync_progress",
    "validate_trace",
    "validate_trace_file",
]
