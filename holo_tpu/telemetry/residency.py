"""Unified device-residency byte ledger (ISSUE 17 satellite).

Every subsystem that parks tensors on the device grew its own partial
accounting — the marshaled-graph cache reports per-device bytes, the
BGP table reports ``resident-bytes``, the SPF backends' retained
``_prev_one`` delta seeds and the tropical tile attachments reported
nothing.  This module is the one instrument that sums them all: a
``holo_device_resident_bytes{plane}`` gauge family plus a
``holo-telemetry/device-residency`` gNMI leaf with one row per plane —
the HBM budget ROADMAP item 1's tenant fleet will allocate against.

Planes
------
- ``spf-graph`` — ``DeviceGraphCache`` ELL entries (the marshaled
  DeviceGraph plane sets, including their device-resident buffers
  under a process mesh);
- ``spf-graph-partitioned`` — the cache's stacked per-partition
  residents (``PartResident.planes``; ISSUE 15);
- ``tropical`` — blocked min-plus tile attachments riding the cache
  entries (ISSUE 13);
- ``spf-prev`` — the SPF backends' retained previous-result tensors
  (``_prev_one`` delta/multipath seeds; weakref-registered so a
  dropped backend never leaks through the ledger);
- ``bgp-table`` — the 13-lane Adj-RIB-In planes (ISSUE 16, summed
  from each backend's own ``resident-bytes``).

Discipline: everything is sampled lazily at scrape/snapshot time via
``set_fn`` — a daemon that never dispatched device work pays nothing
(the modules are looked up in ``sys.modules``, never imported), and
nothing here runs on a dispatch path.  Byte sums walk result pytrees
generically (``.nbytes`` over tuples/dicts), so a new plane member
costs no new accounting code.
"""

from __future__ import annotations

import sys
import weakref

from holo_tpu import telemetry

#: the fixed plane rows (an open set — these are the documented ones)
PLANES = (
    "spf-graph", "spf-graph-partitioned", "tropical", "spf-prev",
    "bgp-table",
)

# Sampled at scrape time only (set_fn below): stamped=False so ledger
# bookkeeping never wakes the gNMI fan-out walk (delta.py discipline).
_RESIDENT = telemetry.gauge(
    "holo_device_resident_bytes",
    "Device-resident plane bytes by subsystem (marshaled SPF graphs, "
    "partitioned residents, tropical tiles, retained previous-result "
    "tensors, BGP table lanes)",
    ("plane",),
    stamped=False,
)

# Live SPF-backend registry (weakrefs: a backend dropped with its
# engine must not leak here — the bgp_table._BACKENDS idiom).
_SPF_BACKENDS: list = []


def register_spf_backend(backend) -> None:
    """Called once from ``TpuSpfBackend.__init__`` — the ledger then
    sees its retained ``_prev_one`` planes."""
    _SPF_BACKENDS.append(weakref.ref(backend))


def _live_backends() -> list:
    out, dead = [], []
    for ref in _SPF_BACKENDS:
        b = ref()
        (out if b is not None else dead).append(b if b is not None else ref)
    for ref in dead:
        _SPF_BACKENDS.remove(ref)
    return out


def _nbytes(obj, depth: int = 0) -> int:
    """Generic device-pytree byte walk: sum ``.nbytes`` over array
    leaves through tuples/lists/dicts (NamedTuple result planes,
    (Spf, Multipath) pairs, DeviceGraph...).  Depth-bounded: an
    unexpected self-referential container terminates, not recurses."""
    if obj is None or depth > 6:
        return 0
    if not isinstance(obj, (dict, list, tuple)):
        nb = getattr(obj, "nbytes", None)
        if nb is not None:
            try:
                return int(nb)
            except (TypeError, ValueError):
                return 0
        return 0
    if isinstance(obj, dict):
        return sum(_nbytes(v, depth + 1) for v in obj.values())
    if isinstance(obj, (list, tuple)):
        return sum(_nbytes(v, depth + 1) for v in obj)
    return 0


def _graph_cache():
    """The shared DeviceGraphCache, ONLY if the engine module is
    already loaded (scrape-time laziness: never import jax here)."""
    eng = sys.modules.get("holo_tpu.ops.spf_engine")
    return None if eng is None else eng.shared_graph_cache()


def _rows() -> dict[str, dict]:
    """{plane: {"bytes": int, "entries": int}} — one walk, all planes."""
    rows = {p: {"bytes": 0, "entries": 0} for p in PLANES}
    cache = _graph_cache()
    if cache is not None:
        # Point-in-time snapshots via the cache's own accessors (its
        # lock discipline); the walks below read plane pytrees only.
        with cache._lock:
            entries = list(cache._cache.values())
        for e in entries:
            rows["spf-graph"]["bytes"] += _nbytes(tuple(e.graph))
            rows["spf-graph"]["entries"] += 1
            if e.tropical is not None:
                rows["tropical"]["bytes"] += _nbytes(tuple(e.tropical))
                rows["tropical"]["entries"] += 1
        for res in cache.partitioned_entries().values():
            planes = getattr(res, "planes", None)
            if planes is not None:
                rows["spf-graph-partitioned"]["bytes"] += _nbytes(
                    tuple(planes)
                )
            rows["spf-graph-partitioned"]["entries"] += 1
    for backend in _live_backends():
        prev = getattr(backend, "_prev_one", None)
        if not prev:
            continue
        for out in list(prev.values()):
            rows["spf-prev"]["bytes"] += _nbytes(out)
            rows["spf-prev"]["entries"] += 1
    bgm = sys.modules.get("holo_tpu.ops.bgp_table")
    if bgm is not None:
        for st in bgm.backends_stats():
            rows["bgp-table"]["bytes"] += int(st.get("resident-bytes", 0))
            rows["bgp-table"]["entries"] += len(st.get("tables", {}))
    return rows


def _plane_bytes(plane: str) -> float:
    try:
        return float(_rows()[plane]["bytes"])
    except Exception:  # noqa: BLE001 — a scrape sampler must never
        # take the exposition (or a test teardown) down.
        return 0.0


# Scrape-time samplers, one per plane row — the gauge always reads
# live sums without any subsystem having to push updates.
for _p in PLANES:
    _RESIDENT.labels(plane=_p).set_fn(
        lambda p=_p: _plane_bytes(p)
    )
del _p


def snapshot() -> dict:
    """The ``holo-telemetry/device-residency`` gNMI leaf payload:
    per-plane bytes/entries + the total."""
    rows = _rows()
    return {
        "total-bytes": sum(r["bytes"] for r in rows.values()),
        "planes": rows,
    }
