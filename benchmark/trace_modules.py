"""Device time of one XLA program from the profiler's ``.xplane.pb``:
the union of the intervals of its events on each device plane's
``XLA Modules`` line (one event per execution of a compiled module,
named after the jitted function, ``jit_<name>``), mean over the chips.
``trace_reduce`` tells operations apart; this tells programs apart.
The TPU's ``XLA Ops`` events carry the HLO instruction's text without
its ``op_name`` metadata and no stat that names a ``jax.named_scope``,
so a part of a program cannot be timed by its scope.
"""

from __future__ import annotations

import functools

import numpy as np

from benchmark.trace_reduce import DEVICE_PLANE, merge

#: the device line whose events are the executions of compiled programs
MODULES_LINE = "XLA Modules"


@functools.lru_cache(maxsize=8)
def module_busy_s(path: str, module: str) -> float | None:
    """Seconds the programs whose name holds ``module`` ran on the
    device, or None where the trace has no modules line or no such
    execution."""
    import jax

    profile = jax.profiler.ProfileData.from_file(path)
    busy, chips = 0.0, 0
    for plane in profile.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        chips += 1
        spans = [
            [e.start_ns, e.start_ns + e.duration_ns]
            for line in plane.lines if line.name == MODULES_LINE
            for e in line.events if module in e.name
        ]
        if spans:
            iv = merge(np.array(spans, float))
            busy += float((iv[:, 1] - iv[:, 0]).sum())
    if not chips or busy == 0.0:
        return None
    return busy / chips * 1e-9

