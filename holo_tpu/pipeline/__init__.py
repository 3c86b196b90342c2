"""Async dispatch pipeline + per-shape engine auto-tuner (ISSUE 9).

The execution layer between the protocol actors and the device:

- :mod:`holo_tpu.pipeline.dispatch` — bounded per-backend dispatch
  queue + pipeline worker overlapping marshal / device-execute /
  readback across consecutive SPF/FRR dispatches, with strict
  per-(uid, root) ordering, what-if coalescing, breaker-open skip, and
  the DeltaPath donation ownership handoff (depth-2 double buffering,
  one in-flight entry per key).  The dispatch survivability plane
  (ISSUE 19) rides the same queue: class-aware priority admission
  (correctness > advisory > background), deadline-aware graded
  load-shedding, the hung-dispatch watchdog hooks
  (:mod:`holo_tpu.resilience.watchdog`), and supervised worker
  respawn (``Supervisor.watch_worker``).
- :mod:`holo_tpu.pipeline.tuner` — measured per-(V, E, batch, mesh)
  shape-bucket engine selection from compile-time ``cost_analysis()``
  priors + dispatch wall medians, persisted to a versioned table
  (``[pipeline] tuner-cache``) so restarts don't re-learn; the same
  table carries the auto-tuned DeltaPath ``max_delta_depth`` per
  bucket.

Both are armed from ``[pipeline]`` in holod.toml at daemon boot and
exported on the ``holo-telemetry`` state leaf; everything is off by
default and the disabled path costs one module-global check.
"""

from holo_tpu.pipeline.dispatch import (
    AsyncFrrEngine,
    AsyncSpfBackend,
    DispatchPipeline,
    LazyBackupTable,
    LazySpfResult,
    PipelineClosed,
    PipelineTicket,
    configure_process_pipeline,
    process_pipeline,
    reset_process_pipeline,
    wrap_frr_engine,
    wrap_spf_backend,
)
from holo_tpu.pipeline.tuner import (
    ENGINES,
    EngineTuner,
    active_tuner,
    configure_engine_tuner,
    reset_engine_tuner,
    shape_bucket,
)

__all__ = [
    "AsyncFrrEngine",
    "AsyncSpfBackend",
    "DispatchPipeline",
    "ENGINES",
    "EngineTuner",
    "LazyBackupTable",
    "LazySpfResult",
    "PipelineClosed",
    "PipelineTicket",
    "active_tuner",
    "configure_engine_tuner",
    "configure_process_pipeline",
    "process_pipeline",
    "reset_engine_tuner",
    "reset_process_pipeline",
    "shape_bucket",
    "wrap_frr_engine",
    "wrap_spf_backend",
]
