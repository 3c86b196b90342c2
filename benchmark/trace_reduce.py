"""From the JAX profiler's ``.xplane.pb`` to the benchmark's device
numbers: seconds in which an operation ran on the device (the union of
the operation intervals, averaged over the chips), the idle share of
the traced window, self time per operation name, and the longest idle
gaps by what the host was doing in them.

    python3 -m benchmark.trace_reduce <file.xplane.pb>    # look at one by hand

Reads planes with nothing but ``jax.profiler.ProfileData``.  A trace
with no device plane is an error, never 100% idle.
"""

from __future__ import annotations

import re
import sys
from dataclasses import dataclass, field

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = re.compile(r"^/host:")
#: the device line whose events are the operations that ran
OPS_LINE = "XLA Ops"
#: idle gaps shorter than this are summed under one name, not attributed
MIN_GAP_NS = 20_000.0
TOP = 10


class TraceError(Exception):
    """The trace does not hold what the reduction needs."""


@dataclass
class Reduced:
    busy_s: float  # union of device-operation intervals, mean over chips
    window_s: float  # the traced window
    chips: int
    op_self_s: dict = field(default_factory=dict)  # name -> self seconds
    idle_gap_s: dict = field(default_factory=dict)  # host span -> seconds

    def breakdown(self) -> dict:
        def top(table: dict) -> list:
            rows = sorted(table.items(), key=lambda kv: -kv[1])[:TOP]
            return [[name, seconds] for name, seconds in rows]

        return {
            "device_ops": top(self.op_self_s),
            "idle_gaps": top(self.idle_gap_s),
        }


def merge(intervals: np.ndarray) -> np.ndarray:
    """Union of ``[[start, end], ...]`` as disjoint sorted intervals."""
    if len(intervals) == 0:
        return np.empty((0, 2))
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    reach = np.maximum.accumulate(iv[:, 1])
    first = np.ones(len(iv), bool)
    first[1:] = iv[1:, 0] > reach[:-1]
    starts = iv[first, 0]
    ends = np.append(reach[:-1][first[1:]], reach[-1])
    return np.stack([starts, ends], axis=1)


def self_times(events: list) -> dict:
    """Self nanoseconds per name of ``(name, start, end)`` events of one
    line: an event's time less that of the events nested in it (a
    ``while`` holds its body's operations), so the sum over names is
    the union and no loop counts twice."""
    out: dict = {}
    stack: list = []  # [name, end, self]

    def close(until: float) -> None:
        while stack and stack[-1][1] <= until:
            name, _end, own = stack.pop()
            out[name] = out.get(name, 0.0) + max(own, 0.0)

    for name, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= min(end, stack[-1][1]) - start
        stack.append([name, end, end - start])
    close(float("inf"))
    return out


_HLO = re.compile(r"^(%[\w.\-]+) = (\(?[a-z0-9]+\[[0-9,]*\])")
_HLO_KIND = re.compile(r"kind=(\w+)")


def short_name(name: str) -> str:
    """``%fusion.4 = pred[972000]{...} fusion(...), kind=kCustom, ...``
    as ``%fusion.4 pred[972000] kCustom``: the trace prints whole HLO
    instructions, of which the name, the result and the kind tell a
    kernel apart.  Any other name stays as it is."""
    m = _HLO.match(name)
    if m is None:
        return name
    kind = _HLO_KIND.search(name)
    shape = m[2] + ",..)" if m[2].startswith("(") else m[2]
    return " ".join(filter(None, [m[1], shape, kind and kind[1]]))


def _events(line) -> list:
    return [(short_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def _attribute_gaps(busy: np.ndarray, host: list) -> dict:
    """Idle nanoseconds between the busy intervals, by the innermost
    host span that covers each gap's middle."""
    if len(busy) < 2:
        return {}
    gaps = np.stack([busy[:-1, 1], busy[1:, 0]], axis=1)
    length = gaps[:, 1] - gaps[:, 0]
    out = {}
    small = length < MIN_GAP_NS
    if small.any():
        out[f"(gaps under {MIN_GAP_NS / 1e3:g} us)"] = float(length[small].sum())
    gaps, length = gaps[~small], length[~small]
    if not host:
        if len(length):
            out["(no host span)"] = float(length.sum())
        return out
    names = [h[0] for h in host]
    h0 = np.array([h[1] for h in host])
    h1 = np.array([h[2] for h in host])
    hlen = h1 - h0
    for (g0, g1), ln in zip(gaps, length):
        mid = 0.5 * (g0 + g1)
        cover = np.flatnonzero((h0 <= mid) & (h1 >= mid))
        name = (
            names[cover[np.argmin(hlen[cover])]] if len(cover)
            else "(no host span)"
        )
        out[name] = out.get(name, 0.0) + float(ln)
    return out


def reduce_profile(profile, window_s: float | None = None) -> Reduced:
    """``profile``: a ``jax.profiler.ProfileData``.  ``window_s``: the
    traced window by the benchmark's clock; None takes the span of the
    device events."""
    device = [p for p in profile.planes if DEVICE_PLANE.match(p.name)]
    if not device:
        names = [p.name for p in profile.planes]
        raise TraceError(f"no device plane in the trace; planes: {names}")
    busy_ns, ops, gaps = [], {}, {}
    span = [float("inf"), float("-inf")]
    host = [
        e for p in profile.planes if HOST_PLANE.match(p.name)
        for ln in p.lines for e in _events(ln) if e[2] > e[1]
    ]
    for plane in device:
        lines = [ln for ln in plane.lines if ln.name == OPS_LINE]
        if not lines:
            raise TraceError(
                f"{plane.name} has no {OPS_LINE!r} line; lines: "
                f"{[ln.name for ln in plane.lines]}"
            )
        events = [e for ln in lines for e in _events(ln)]
        if not events:
            raise TraceError(f"no operation ran on {plane.name}")
        busy = merge(np.array([[s, e] for _n, s, e in events]))
        busy_ns.append(float((busy[:, 1] - busy[:, 0]).sum()))
        span = [min(span[0], busy[0, 0]), max(span[1], busy[-1, 1])]
        for name, ns in self_times(events).items():
            ops[name] = ops.get(name, 0.0) + ns
        for name, ns in _attribute_gaps(busy, host).items():
            gaps[name] = gaps.get(name, 0.0) + ns
    chips = len(device)
    return Reduced(
        busy_s=sum(busy_ns) / chips * 1e-9,
        window_s=float(
            window_s if window_s is not None else (span[1] - span[0]) * 1e-9
        ),
        chips=chips,
        op_self_s={n: ns / chips * 1e-9 for n, ns in ops.items()},
        idle_gap_s={n: ns / chips * 1e-9 for n, ns in gaps.items()},
    )


def reduce_file(path, window_s: float | None = None) -> Reduced:
    import jax

    return reduce_profile(
        jax.profiler.ProfileData.from_file(str(path)), window_s
    )


def describe(path) -> None:
    """Planes, lines, event counts and the first events of each line:
    what to look at by hand before trusting the reduction."""
    import jax

    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        print(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            total = sum(e.duration_ns for e in events)
            print(f"  LINE {line.name!r}: {len(events)} events, "
                  f"{total * 1e-9:.6f} s summed")
            for e in events[:8]:
                print(f"    {e.name[:90]!r} start={e.start_ns:.0f} "
                      f"dur={e.duration_ns:.0f}")


if __name__ == "__main__":
    describe(sys.argv[1])
    print(reduce_file(sys.argv[1]))
