"""Jitted SPF engine: exact int32 SSSP + ECMP next-hop extraction.

Replaces the reference's scalar Dijkstra (holo-ospf/src/spf.rs:587-729,
holo-isis/src/spf.rs:527-709) with fixed-point tensor iterations:

1. Distances: masked min-plus relaxation over the ELL in-edge layout
   (Bellman-Ford).  Each round is one gather + add + row-min on the VPU;
   rounds needed = shortest-path hop diameter.
2. Shortest-path DAG: edge (u→v) is on the DAG iff dist[u] + w == dist[v].
3. ``hops`` (router-hop count from root) via the reference's first-parent
   rule: the parent popped earliest from the candidate BTreeMap is the DAG
   parent minimizing (dist[u], u) (holo-ospf/src/spf.rs:614-622, 676-706);
   ``hops`` increments only when the target vertex is a router
   (holo-ospf/src/spf.rs:673-677).
4. ECMP next-hop sets as uint32 bitmasks over "next-hop atoms" (protocol
   layer's (interface, address) table): a DAG parent with hops==0
   contributes the edge's precomputed direct atom, any other DAG parent
   contributes its own set — exactly calc_nexthops' direct-vs-inherit split
   (holo-ospf/src/spf.rs:733-767); equal-cost parents union
   (spf.rs:710-717 `nexthops.extend`).

All int32, exact; results are bit-comparable against the scalar oracle
(:mod:`holo_tpu.spf.scalar`).
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from holo_tpu import telemetry
from holo_tpu.analysis.runtime import note_donated
from holo_tpu.ops.graph import (
    DELTA_MAX_OPS,
    INF,
    MP_SAT,
    EllGraph,
    TopologyDelta,
)

# Host-side marshal metrics: every DeviceGraph build reports how long
# the ELL expansion took and how much of the padded slot space is real
# (waste here is waste in EVERY subsequent device round).
_MARSHALS = telemetry.counter(
    "holo_spf_marshal_total", "DeviceGraph marshals (ELL expansion)"
)
_MARSHAL_SECONDS = telemetry.histogram(
    "holo_spf_marshal_seconds", "Host-side ELL -> DeviceGraph marshal time"
)
_ELL_OCCUPANCY = telemetry.gauge(
    "holo_spf_ell_occupancy",
    "Valid fraction of padded ELL in-edge slots (last marshal)",
)
_MARSHAL_CACHE = telemetry.counter(
    "holo_spf_marshal_cache_total",
    "Shared marshaled-DeviceGraph cache lookups (SPF + FRR engines)",
    ("result",),
)
_DELTA_TOTAL = telemetry.counter(
    "holo_spf_delta_total",
    "DeltaPath topology-delta dispositions: in-place device-graph "
    "updates vs full-rebuild fallbacks, by delta taxonomy",
    ("kind", "path"),
)
_CACHE_EVICTIONS = telemetry.counter(
    "holo_spf_marshal_cache_evictions_total",
    "Shared marshaled-DeviceGraph cache LRU evictions",
)


def note_delta(kind: str, path: str) -> None:
    """Count one DeltaPath disposition (cache and SPF backend share the
    ``holo_spf_delta_total{kind,path}`` series)."""
    _DELTA_TOTAL.labels(kind=kind, path=path).inc()


class DeviceGraph(NamedTuple):
    """Pure-array pytree handed to jitted SPF programs."""

    in_src: jax.Array  # int32[N, K]
    in_cost: jax.Array  # int32[N, K]
    in_valid: jax.Array  # bool[N, K]
    in_edge_id: jax.Array  # int32[N, K]
    direct_nh_words: jax.Array  # uint32[N, K, W] one-hot atom bitmask (0 if none)
    is_router: jax.Array  # bool[N]


class SpfTensors(NamedTuple):
    """Result of one SPF run (or a batch thereof, with a leading axis)."""

    dist: jax.Array  # int32[N]; INF if unreachable
    parent: jax.Array  # int32[N]; chosen first parent, N (sentinel) if none
    hops: jax.Array  # int32[N]; router hops from root (first-parent rule)
    nexthops: jax.Array  # uint32[N, W] atom bitmask


class MultipathTensors(NamedTuple):
    """Multi-parent frontier planes of one SPF run (ISSUE 10 tentpole).

    ``Kp`` is the pow2-padded parent-set width (k <= 8) and ``A`` the
    atom-lane width (``W * 32``).  Per vertex:

    - ``parents`` — up to Kp admissible parents in ascending
      ``(path cost via parent, parent id)`` order, sentinel N beyond
      the set.  Admissible = shortest-path-DAG parents (the weighted
      ECMP set, path cost == dist) followed by *loop-free diversity*
      parents: sources u of valid in-edges with ``dist[u] < dist[v]``
      strictly — every shortest root→u path then provably avoids v
      (a path through v would cost >= dist[v] > dist[u]), so the
      alternative root→u→v path is loop-free (the per-vertex downward
      criterion of RFC 5286 inequality 1 with D(u,v) collapsed; the
      k-shortest-diversity selection of arXiv:2007.03776 done as a
      dense batched computation).
    - ``pdist`` — total path cost via that parent (INF past the set);
      ``pdist == dist`` marks the equal-cost (ECMP) members.
    - ``pweight`` — saturated shortest-path count of the parent
      (``npaths[parent]``): the UCMP mass a via-parent split carries.
    - ``npaths`` — saturated shortest-path count of the vertex itself.
    - ``nh_weights`` — per next-hop atom UCMP weights: the saturated
      number of shortest root→v paths whose first hop is that atom
      (sums to ``npaths`` when every hops==0 DAG slot carries an atom).
    """

    parents: jax.Array  # int32[N, Kp]; sentinel N past the set
    pdist: jax.Array  # int32[N, Kp]; INF past the set
    pweight: jax.Array  # int32[N, Kp]; 0 past the set
    npaths: jax.Array  # int32[N]; saturated at MP_SAT
    nh_weights: jax.Array  # int32[N, A]; saturated at MP_SAT


def mp_pad(k: int) -> int:
    """The pow2-padded parent-set width for a ``max-paths`` k (<= 8).

    One compiled program per padded width: the protocol's 1..8 knob
    collapses onto {1, 2, 4, 8} shape buckets."""
    k = max(1, min(int(k), 8))
    kp = 1
    while kp < k:
        kp *= 2
    return kp


def device_graph_from_ell(ell: EllGraph) -> DeviceGraph:
    """Expand per-slot direct atoms into one-hot bitmask words (host side)."""
    t0 = time.perf_counter()
    n, k = ell.in_src.shape
    w = max((ell.n_atoms + 31) // 32, 1)
    words = np.zeros((n, k, w), np.uint32)
    atom = ell.in_direct_atom
    has = atom >= 0
    rows, cols = np.nonzero(has)
    a = atom[rows, cols]
    words[rows, cols, a // 32] = np.uint32(1) << (a % 32).astype(np.uint32)
    g = DeviceGraph(
        in_src=jnp.asarray(ell.in_src),
        in_cost=jnp.asarray(ell.in_cost),
        in_valid=jnp.asarray(ell.in_valid),
        in_edge_id=jnp.asarray(ell.in_edge_id),
        direct_nh_words=jnp.asarray(words),
        is_router=jnp.asarray(ell.is_router),
    )
    _MARSHALS.inc()
    _MARSHAL_SECONDS.observe(time.perf_counter() - t0)
    # Occupancy is sampled lazily at scrape time: the O(N*K) reduction
    # has no business inside the marshal critical section (holo-lint
    # HL105) — the gauge still reads "last marshal", and the one-shot
    # sampler drops its array reference after the first scrape.
    _ELL_OCCUPANCY.set_fn(telemetry.deferred_mean(ell.in_valid))
    return g


class _EllMirror:
    """Host-side mirror of a cached entry's ELL slot occupancy.

    apply_delta needs to resolve edge-level delta ops to (row, slot)
    scatter targets and to find padding slack for additions — without
    reading the device buffers back (the no-host-round-trip contract).
    The mirror owns COPIES of the marshal-time arrays (jnp.asarray may
    alias numpy memory on CPU backends, and the mirror mutates).
    """

    def __init__(self, ell: EllGraph):
        self.in_src = ell.in_src.copy()
        self.in_cost = ell.in_cost.copy()
        self.in_valid = ell.in_valid.copy()
        self.in_atom = ell.in_direct_atom.copy()
        self.n_atoms = int(ell.n_atoms)
        self.n_valid = int(ell.in_valid.sum())

    @property
    def occupancy(self) -> float:
        return self.n_valid / max(self.in_valid.size, 1)


@dataclass
class _CacheEntry:
    graph: DeviceGraph
    mirror: _EllMirror
    depth: int = 0  # delta-chain length since the last full marshal
    # Tropical tile attachment (ISSUE 13): the blocked min-plus planes
    # marshaled alongside the ELL resident, lazily built on the first
    # tropical dispatch and updated IN PLACE by lowered tile scatters
    # when a delta is applied.  ``trop_meta`` is the host-side tile
    # index (block size, grid) the lowering needs.  A delta the tiles
    # cannot absorb drops only the attachment (rebuilt lazily from the
    # post-delta mirror) — never the ELL resident.
    tropical: object | None = None
    trop_meta: dict | None = None
    # in_edge_id no longer matches the serving topology's edge list
    # (structural deltas shift edge indices): entries in this state can
    # serve mask-free SPF but not edge-mask consumers (what-if, FRR).
    ids_stale: bool = False
    # The dispatch mesh the planes were placed under (row-sharded over
    # its node axis, batch-replicated — parallel/mesh.py layout
    # contract), or None for single-device placement.  Entries are also
    # KEYED by the mesh identity, so a reconfigured mesh never hands a
    # stale placement to a new-mesh jit.
    mesh: object | None = None


class _DeltaUnappliable(Exception):
    def __init__(self, reason: str):
        super().__init__(reason)
        self.reason = reason


def _apply_delta_slots(g: DeviceGraph, rows, cols, src, cost, valid, words, strike):
    """Scatter a lowered TopologyDelta into the resident graph buffers.

    Jitted with the graph DONATED: the update happens in place on the
    device (no host round-trip; pad ops carry out-of-range rows and are
    dropped).  ``strike`` is the transit-strike (overload) vertex mask,
    post-masking slot validity through the updated sources.
    """
    in_src = g.in_src.at[rows, cols].set(src, mode="drop")
    in_cost = g.in_cost.at[rows, cols].set(cost, mode="drop")
    in_valid = g.in_valid.at[rows, cols].set(valid, mode="drop")
    in_valid = in_valid & ~strike[in_src]
    nh_words = g.direct_nh_words.at[rows, cols].set(words, mode="drop")
    return g._replace(
        in_src=in_src, in_cost=in_cost, in_valid=in_valid,
        direct_nh_words=nh_words,
    )


_APPLY_DELTA = jax.jit(_apply_delta_slots, donate_argnums=(0,))

# Sharded apply variants, one per process-mesh identity: out_shardings
# pins the updated planes to the entry's row-sharded layout so the
# donated in-place scatter stays per-shard (no resharding collective,
# no placement drift down a delta chain).
_APPLY_DELTA_SHARDED: dict[tuple, object] = {}


def _process_mesh_state():
    """(mesh, cache-key component) of the process dispatch mesh.

    Lazy import: parallel/mesh.py imports this module at top level, so
    the dependency must stay one-way at import time.  After the first
    call this is a sys.modules dict hit — nanoseconds on the dispatch
    path.
    """
    from holo_tpu.parallel import mesh as _pm

    m = _pm.process_mesh()
    return m, (None if m is None else _pm.mesh_cache_key(m))


def _apply_delta_for(mesh) -> object:
    """The delta-apply jit matching an entry's placement."""
    if mesh is None:
        return _APPLY_DELTA
    from holo_tpu.parallel import mesh as _pm

    key = _pm.mesh_cache_key(mesh)
    fn = _APPLY_DELTA_SHARDED.get(key)
    if fn is None:
        fn = jax.jit(
            _apply_delta_slots,
            donate_argnums=(0,),
            out_shardings=_pm.graph_sharding(mesh),
        )
        _APPLY_DELTA_SHARDED[key] = fn
    return fn


# Tile-attachment delta jits (ISSUE 13), donated like the slot apply;
# one per mesh identity (replicated placement — see parallel/mesh.py).
_APPLY_TILES: dict[tuple | None, object] = {}


def _apply_tiles_for(mesh) -> object:
    key = None
    shard_kw = {}
    if mesh is not None:
        from holo_tpu.parallel import mesh as _pm

        key = _pm.mesh_cache_key(mesh)
        shard_kw = {"out_shardings": _pm.tile_sharding(mesh)}
    fn = _APPLY_TILES.get(key)
    if fn is None:
        from holo_tpu.ops.tropical import apply_tile_delta

        fn = jax.jit(apply_tile_delta, donate_argnums=(0,), **shard_kw)
        _APPLY_TILES[key] = fn
    return fn


#: THE scatter/seed bucket: every delta pads to this many rows
#: (out-of-range sentinels drop).  It equals ``DELTA_MAX_OPS``, the
#: most operations ``diff_topologies`` puts into one delta (a delta of
#: n operations touches at most n slots and seeds at most n rows), so
#: a process compiles the apply + incremental-kernel pair ONCE per
#: graph shape for every delta the LSDB seam can link — a failed
#: degree-96 router (192 edge operations) coalesced with a fibre cut
#: rides the program a one-link flap compiled, where a 256-row floor
#: put a second pair's XLA compile into the middle of the storm.
#: Larger inputs (a caller's own ``max_ops``, an IS-IS overload strike
#: seeding many rows) still double from here.
_DELTA_PAD_FLOOR = DELTA_MAX_OPS


def _pad_pow2(n: int, floor: int = _DELTA_PAD_FLOOR) -> int:
    out = floor
    while out < n:
        out *= 2
    return out


def _lower_delta(mirror: _EllMirror, delta: TopologyDelta, n_vertices: int):
    """Resolve edge-level delta ops to padded slot-scatter arrays,
    mutating the mirror to the post-delta state.  Raises
    :class:`_DeltaUnappliable` on padding overflow / atom overflow /
    an op that does not match the mirrored occupancy."""

    def find(dst, src, cost, atom) -> int:
        m = (
            mirror.in_valid[dst]
            & (mirror.in_src[dst] == src)
            & (mirror.in_cost[dst] == cost)
            & (mirror.in_atom[dst] == atom)
        )
        hit = np.nonzero(m)[0]
        if hit.shape[0] == 0:
            raise _DeltaUnappliable("missing-edge")
        return int(hit[0])

    touched: set[tuple[int, int]] = set()
    d = delta
    # Removals first: they free the padding slack additions reuse.
    for src, dst, cost, atom in zip(d.r_src, d.r_dst, d.r_cost, d.r_atom):
        col = find(dst, src, cost, atom)
        mirror.in_valid[dst, col] = False
        mirror.in_src[dst, col] = 0
        mirror.in_cost[dst, col] = 0
        mirror.in_atom[dst, col] = -1
        mirror.n_valid -= 1
        touched.add((int(dst), col))
    for src, dst, old, new, atom in zip(
        d.w_src, d.w_dst, d.w_old, d.w_new, d.w_atom
    ):
        col = find(dst, src, old, atom)
        mirror.in_cost[dst, col] = new
        touched.add((int(dst), col))
    for src, dst, cost, atom in zip(d.a_src, d.a_dst, d.a_cost, d.a_atom):
        if atom >= mirror.n_atoms:
            raise _DeltaUnappliable("atom-overflow")
        free = np.nonzero(~mirror.in_valid[dst])[0]
        if free.shape[0] == 0:
            raise _DeltaUnappliable("padding-overflow")
        col = int(free[0])
        mirror.in_valid[dst, col] = True
        mirror.in_src[dst, col] = src
        mirror.in_cost[dst, col] = cost
        mirror.in_atom[dst, col] = atom
        mirror.n_valid += 1
        touched.add((int(dst), col))
    # Overload strikes: device-side mask through in_src; mirror keeps
    # the struck slots invalid so later deltas see the real occupancy.
    strike = np.zeros(n_vertices, bool)
    if d.overload.shape[0]:
        strike[d.overload] = True
        hit = np.isin(mirror.in_src, d.overload) & mirror.in_valid
        mirror.n_valid -= int(hit.sum())
        mirror.in_valid[hit] = False
    # One scatter op per touched slot, carrying the FINAL mirror state
    # (a freed-then-reused slot must not scatter twice).
    w = max((mirror.n_atoms + 31) // 32, 1)
    pad = _pad_pow2(len(touched))
    # Pad-op sentinel: row n_vertices is OOB (dropped) on an unpadded
    # resident; on a node-sharded resident (rows padded past N) it is
    # in-bounds but writes src=0/cost=0/valid=False/words=0 — exactly
    # the padded row's existing state, so the scatter stays a no-op.
    rows = np.full(pad, n_vertices, np.int32)
    cols = np.zeros(pad, np.int32)
    src = np.zeros(pad, np.int32)
    cost = np.zeros(pad, np.int32)
    valid = np.zeros(pad, bool)
    words = np.zeros((pad, w), np.uint32)
    for i, (r, c) in enumerate(sorted(touched)):
        rows[i], cols[i] = r, c
        src[i] = mirror.in_src[r, c]
        cost[i] = mirror.in_cost[r, c]
        valid[i] = mirror.in_valid[r, c]
        a = int(mirror.in_atom[r, c])
        if a >= 0:
            words[i, a // 32] = np.uint32(1) << np.uint32(a % 32)
    return rows, cols, src, cost, valid, words, strike


class DeviceGraphCache:
    """Process-wide LRU of marshaled DeviceGraphs, shared by every SPF
    backend and FRR engine (ROADMAP cleanup: an instance running SPF +
    FRR used to hold two private caches and marshal the same LSDB
    twice).  Keyed by ``(topology uid, generation, n_atoms)`` — the
    same identity contract as the old per-engine caches: in-place
    topology mutators must ``touch()``.

    DeltaPath (ROADMAP item 1): entries are long-lived device residents
    updated IN PLACE.  When a lookup misses but the topology carries
    delta lineage (``Topology.link_delta``) to a resident base entry,
    the delta is lowered to slot scatters and applied on device with
    buffer donation — no re-marshal, no host round-trip.  Entries track
    their delta-chain depth; chains deeper than ``max_delta_depth``,
    padding/atom overflow, or a mask-consumer asking for a
    structurally-updated entry (stale edge ids) all fall back to the
    full-rebuild path (``holo_spf_delta_total{kind,path}``).

    Compile shapes under churn (ISSUE 27).  Every delta
    ``diff_topologies`` returns pads to ONE bucket of ``DELTA_MAX_OPS``
    rows (``_DELTA_PAD_FLOOR``), so the apply + incremental pair is
    compiled once per graph shape.  A full re-marshal keeps the shapes
    of the resident it replaces: the ELL width of a (vertex count,
    root, atoms, mesh) only ever grows (``_k_pad_floor``), because
    ``build_ell`` alone would take it from the largest in-degree of
    the moment, which a failed hub lowers — a narrower resident is a
    new set of programs.  And the mask-free full-SPF program
    (``TpuSpfBackend._device_compute``) is keyed on the resident's
    shapes alone, not on the edge count, which moves with every flap.

    Thread-shared under ``[runtime] isolation=threaded`` (instance
    threads dispatch concurrently): lookups and inserts run under an
    owning lock; the expensive ELL expansion runs outside it, so two
    concurrent first-misses marshal twice and the second insert wins —
    wasted work once, never a stall or a torn entry.  The delta path
    CLAIMS its base entry (pops it under the lock) before donating the
    buffers, so the dict itself never hands out a consumed graph.
    NOTE the narrower contract donation imposes: a DeviceGraph obtained
    from an earlier get() is invalidated when a delta is later applied
    to that entry — safe today because a topology's chain is only ever
    dispatched from its owning instance's actor thread (SPF then FRR,
    sequentially); cross-thread sharing of one topology's entry would
    need a read-lease before donation could stay.
    """

    def __init__(
        self,
        capacity: int = 16,
        max_delta_depth: int = 256,
        part_capacity: int = 8,
    ):
        import threading

        self.capacity = int(capacity)
        self.max_delta_depth = int(max_delta_depth)
        self._lock = threading.Lock()
        self._cache: dict[tuple, _CacheEntry] = {}
        self._evictions = 0
        self._deltas_applied = 0
        # Widest ELL built per (n_vertices, root, n_atoms, mesh): the
        # floor of the next full marshal's width (class docstring).
        self._k_pad_floor: dict[tuple, int] = {}
        # Partitioned-SPF residents (ISSUE 15): stacked per-partition
        # plane sets (ops/partition.PartResident) ride the SAME shared
        # cache — one lock discipline, one LRU/eviction surface — in a
        # parallel keyed store (their key is the serving chain
        # (backend, root, n_atoms, mesh), not a topology generation:
        # the resident advances in place along its delta chain).  The
        # engine's in-place donation update imposes the same narrowed
        # contract as _CacheEntry: a resident obtained from an earlier
        # lookup is invalidated when a later delta donates its planes.
        self.part_capacity = int(part_capacity)
        self._part: dict[tuple, object] = {}

    def get_partitioned(self, key: tuple):
        """The partitioned resident serving ``key`` (LRU-refreshed), or
        None.  Callers validate the resident's ``topo_key`` themselves
        — chain identity lives on the resident, not the store."""
        with self._lock:
            res = self._part.get(key)
            if res is not None:
                del self._part[key]
                self._part[key] = res
        return res

    def put_partitioned(self, key: tuple, res) -> None:
        with self._lock:
            self._part[key] = res
            while len(self._part) > self.part_capacity:
                self._part.pop(next(iter(self._part)))
                self._evictions += 1
                _CACHE_EVICTIONS.inc()

    def drop_partitioned(self, key: tuple) -> None:
        with self._lock:
            self._part.pop(key, None)

    def partitioned_entries(self, namespace=None) -> dict:
        """key -> resident snapshot (optionally filtered to one
        backend's ``namespace`` — key[0] by the backend's convention)."""
        with self._lock:
            return {
                k: v
                for k, v in self._part.items()
                if namespace is None or k[0] == namespace
            }

    def _depth_cap(self, topo) -> int:
        """The chain-depth cap for this topology's shape bucket.

        PR 7 shipped ``max_delta_depth`` as a fixed knob; with the
        engine tuner armed (ISSUE 9) the cap is derived per shape
        bucket from the measured delta-stage vs full-rebuild walls the
        SPF backend feeds into the persisted tuner table — a bucket
        whose in-place delta is 40x cheaper than a re-marshal affords a
        much longer chain than one where the delta barely wins.  The
        static knob remains both the untuned default and the
        no-measurements fallback.  Lazy import: nanoseconds after the
        first call, and the pipeline package must stay optional here.
        """
        from holo_tpu.pipeline.tuner import active_tuner, shape_bucket

        t = active_tuner()
        if t is None:
            return self.max_delta_depth
        _mesh, mkey = _process_mesh_state()
        return t.max_delta_depth(
            shape_bucket(topo.n_vertices, topo.n_edges, 1, mkey),
            default=self.max_delta_depth,
        )

    def get(
        self,
        topo,
        n_atoms: int,
        need_edge_ids: bool = False,
        allow_delta: bool = True,
    ) -> tuple[DeviceGraph, str]:
        """(device graph, 'hit' | 'delta' | 'miss').  Callers invoke
        this inside their sanctioned marshal windows — the device_put /
        delta scatter below is the transfer the window exists for.

        ``need_edge_ids``: the caller gathers through ``in_edge_id``
        (edge-mask consumers: what-if batches, FRR planes) — entries
        whose edge ids went stale under a structural delta are rebuilt.

        Shard-aware (ISSUE 8): under an installed process mesh the
        planes are placed row-sharded over the mesh's node axis
        (batch-replicated) per the parallel/mesh.py layout contract,
        and the mesh identity joins the cache key.
        """
        mesh, mkey = _process_mesh_state()
        key = (*topo.cache_key, int(n_atoms), mkey)
        with self._lock:
            e = self._cache.get(key)
            if e is not None:
                if need_edge_ids and e.ids_stale:
                    # A structurally-updated resident cannot serve mask
                    # consumers: rebuild (and reset the chain) below.
                    self._cache.pop(key, None)
                    e = None
                else:
                    # Refresh LRU position (dicts preserve insert order).
                    del self._cache[key]
                    self._cache[key] = e
        if e is not None:
            _MARSHAL_CACHE.labels(result="hit").inc()
            return e.graph, "hit"
        if allow_delta:
            g = self._try_delta(topo, n_atoms, need_edge_ids)
            if g is not None:
                _MARSHAL_CACHE.labels(result="delta").inc()
                return g, "delta"
        _MARSHAL_CACHE.labels(result="miss").inc()
        ell = self._build_ell(topo, int(n_atoms), mkey)
        g = device_graph_from_ell(ell)
        if mesh is not None:
            from holo_tpu.parallel.mesh import shard_graph

            g = shard_graph(g, mesh)
        else:
            g = jax.device_put(g)
        # A 1-device mesh places exactly like no mesh (shard_graph's
        # degenerate path): record it as unsharded so apply_delta and
        # the stats leaf describe the real placement.
        entry = _CacheEntry(
            graph=g,
            mirror=_EllMirror(ell),
            mesh=mesh if (mesh is not None and mesh.size > 1) else None,
        )
        with self._lock:
            self._cache[key] = entry
            self._evict_locked()
        return g, "miss"

    def _build_ell(self, topo, n_atoms: int, mkey) -> EllGraph:
        """``build_ell`` at no less than the widest ELL this cache has
        built for the same vertex count, root, atoms and mesh: the
        resident a mid-chain re-marshal builds has the shapes of the
        one it replaces whenever the graph fits them."""
        from holo_tpu.ops.graph import build_ell

        fkey = (topo.n_vertices, int(topo.root), n_atoms, mkey)
        with self._lock:
            floor = self._k_pad_floor.get(fkey, 0)
        ell = build_ell(topo, n_atoms=n_atoms, k_min=floor)
        with self._lock:
            self._k_pad_floor.pop(fkey, None)  # newest last
            self._k_pad_floor[fkey] = ell.k_pad
            while len(self._k_pad_floor) > 4 * self.capacity:
                self._k_pad_floor.pop(next(iter(self._k_pad_floor)))
        return ell

    def _try_delta(
        self, topo, n_atoms: int, need_edge_ids: bool
    ) -> DeviceGraph | None:
        delta = getattr(topo, "delta_base", None)
        if delta is None:
            return None
        kind = delta.kind
        _mesh, mkey = _process_mesh_state()
        base_key = (*delta.base_key, int(n_atoms), mkey)
        depth_cap = self._depth_cap(topo)
        with self._lock:
            base = self._cache.get(base_key)
            if base is None:
                path = "full-no-base"
                base = None
            elif base.depth + 1 > depth_cap:
                path = "full-depth"
                base = None
            elif need_edge_ids and (base.ids_stale or not delta.ids_stable):
                path = "full-edge-ids"
                base = None
            else:
                # Claim the base: its buffers are about to be donated.
                del self._cache[base_key]
                path = "apply"
        if base is None:
            _DELTA_TOTAL.labels(kind=kind, path=path).inc()
            return None
        try:
            ops = _lower_delta(base.mirror, delta, topo.n_vertices)
        except _DeltaUnappliable as exc:
            # The mirror may be half-updated: the claimed base entry is
            # dropped and the caller re-marshals from scratch.
            _DELTA_TOTAL.labels(kind=kind, path=f"full-{exc.reason}").inc()
            return None
        tile_ops = None
        if base.tropical is not None:
            # The tile attachment rides the chain: lower the same delta
            # against the POST-delta mirror (updated by _lower_delta
            # above).  An unappliable tile delta drops ONLY the
            # attachment — rebuilt lazily from the mirror — never the
            # ELL resident.
            from holo_tpu.ops import tropical as _trop

            try:
                tile_ops = _trop.lower_tile_delta(
                    base.mirror, delta, base.trop_meta
                )
            except _trop.TileDeltaUnappliable as exc:
                base.tropical = None
                base.trop_meta = None
                _trop.note_tile_delta(f"drop-{exc.reason}")
        g = _apply_delta_for(base.mesh)(base.graph, *ops)
        # Runtime half of HL109: the claimed entry's planes were just
        # donated into the scatter — poison them under the test-mode
        # donation guard so a stale reference raises at read time.
        note_donated("spf.graph.delta", base.graph)
        tt = None
        if tile_ops is not None:
            from holo_tpu.ops import tropical as _trop

            tt = _apply_tiles_for(base.mesh)(base.tropical, *tile_ops)
            _trop.note_tile_delta("apply")
            note_donated("spf.tiles.delta", base.tropical)
        entry = _CacheEntry(
            graph=g,
            mirror=base.mirror,
            depth=base.depth + 1,
            ids_stale=base.ids_stale or not delta.ids_stable,
            mesh=base.mesh,
            tropical=tt,
            trop_meta=base.trop_meta if tt is not None else None,
        )
        with self._lock:
            self._cache[(*topo.cache_key, int(n_atoms), mkey)] = entry
            self._evict_locked()
            self._deltas_applied += 1
        _DELTA_TOTAL.labels(kind=kind, path="apply").inc()
        return g

    def get_tropical(self, topo, n_atoms: int):
        """The entry's tropical tile attachment, building (and placing)
        it from the mirrored ELL state on first use.  Call inside the
        same sanctioned marshal window as :meth:`get` — the device_put
        here is part of that transfer.  The attachment tracks the entry
        through DeltaPath updates (see ``_try_delta``), so a chain
        marshals its tiles once, not once per delta."""
        from holo_tpu.ops import tropical as _trop

        _mesh, mkey = _process_mesh_state()
        key = (*topo.cache_key, int(n_atoms), mkey)
        snap = None
        e_mesh = None
        for _ in range(2):
            with self._lock:
                e = self._cache.get(key)
                if e is not None:
                    if e.tropical is not None:
                        return e.tropical
                    # Snapshot the mutable host mirror UNDER the lock:
                    # _try_delta claims entries under this same lock
                    # before mutating their mirror in place, so an
                    # in-cache entry's mirror is only stable while we
                    # hold it — an unlocked tile build from the live
                    # mirror could tear against a concurrent delta.
                    snap = (
                        e.mirror.in_src.copy(),
                        e.mirror.in_cost.copy(),
                        e.mirror.in_valid.copy(),
                    )
                    e_mesh = e.mesh
                    break
            # Entry aged out between get() and here (or get() was never
            # called): one re-prepare restores it.
            self.get(topo, n_atoms)
        if snap is None:
            # Capacity pressure: the re-prepared entry was evicted by a
            # concurrent insert before the locked read.  Serve a
            # one-shot unattached tile build rather than raising — the
            # dispatch stays correct, only the attachment reuse is
            # lost for this call.
            from holo_tpu.ops.graph import build_ell

            ell = build_ell(topo, n_atoms=n_atoms)
            tt_host, _ = _trop.build_tiles_host(
                ell.in_src, ell.in_cost, ell.in_valid
            )
            if _mesh is not None:
                from holo_tpu.parallel.mesh import shard_tiles

                return shard_tiles(tt_host, _mesh)
            return jax.device_put(tt_host)
        tt_host, meta = _trop.build_tiles_host(*snap)
        if e_mesh is not None:
            from holo_tpu.parallel.mesh import shard_tiles

            tt = shard_tiles(tt_host, e_mesh)
        else:
            tt = jax.device_put(tt_host)
        with self._lock:
            # Re-fetch by key: same key ⇒ same topology generation ⇒
            # the snapshot content is valid for whatever entry serves
            # the key now (a claimed-and-gone entry simply loses the
            # attachment for this call).
            e2 = self._cache.get(key)
            if e2 is not None and e2.tropical is None:
                e2.tropical = tt
                e2.trop_meta = meta
        return tt

    def _evict_locked(self) -> None:
        while len(self._cache) > self.capacity:
            self._cache.pop(next(iter(self._cache)))
            self._evictions += 1
            _CACHE_EVICTIONS.inc()

    def stats(self) -> dict:
        """Eviction/occupancy summary for the holo-telemetry gNMI leaf
        (rides next to the holo_spf_marshal_cache_total hit/miss
        counters).  Under an installed process mesh the summary also
        carries per-device placement: how many resident entries touch
        each device and the rows/bytes of graph plane actually held
        there (sharded entries hold a row block per node-axis device
        and a full replica per batch-axis row) — metadata reads only,
        no device->host transfer."""
        with self._lock:
            entries = list(self._cache.values())
            evictions = self._evictions
            applied = self._deltas_applied
            part_residents = list(self._part.values())
        depths = [e.depth for e in entries]
        occ = [e.mirror.occupancy for e in entries]
        from holo_tpu.parallel import mesh as _pm

        mesh = _pm.process_mesh()
        per_dev: dict[str, dict] = {}
        sharded = 0
        for e in entries:
            if e.mesh is not None:
                sharded += 1
            try:
                devs: dict[str, dict] = {}
                for plane in e.graph:
                    shards = getattr(plane, "addressable_shards", None)
                    if not shards:
                        continue
                    for sh in shards:
                        d = devs.setdefault(
                            str(getattr(sh.device, "id", sh.device)),
                            {"bytes": 0, "rows": 0},
                        )
                        d["bytes"] += int(sh.data.nbytes)
                        if plane is e.graph.in_src:
                            d["rows"] += int(sh.data.shape[0])
            except Exception:  # noqa: BLE001 — placement introspection
                # is platform-best-effort; the leaf must never fail a
                # scrape over an exotic array type.
                continue
            for dev, d in devs.items():
                agg = per_dev.setdefault(
                    dev, {"entries": 0, "bytes": 0, "rows": 0}
                )
                agg["entries"] += 1
                agg["bytes"] += d["bytes"]
                agg["rows"] += d["rows"]
        return {
            "entries": len(entries),
            "capacity": self.capacity,
            "evictions": evictions,
            "deltas-applied": applied,
            "delta-entries": sum(1 for d in depths if d > 0),
            "max-chain-depth": max(depths, default=0),
            "stale-id-entries": sum(1 for e in entries if e.ids_stale),
            "tropical-entries": sum(
                1 for e in entries if e.tropical is not None
            ),
            "occupancy": round(sum(occ) / len(occ), 4) if occ else 0.0,
            "partitioned-residents": len(part_residents),
            "partitioned-parts": sum(
                r.plan.n_parts for r in part_residents
            ),
            "sharded-entries": sharded,
            "mesh": (
                {"batch": mesh.shape["batch"], "node": mesh.shape["node"]}
                if mesh is not None
                else None
            ),
            "per-device": per_dev,
        }

    def __len__(self) -> int:
        with self._lock:
            return len(self._cache)

    def clear(self) -> None:
        with self._lock:
            self._cache.clear()
            self._part.clear()
            self._k_pad_floor.clear()


_SHARED_GRAPH_CACHE = DeviceGraphCache()


def shared_graph_cache() -> DeviceGraphCache:
    """The process-wide marshaled-graph cache."""
    return _SHARED_GRAPH_CACHE


def _slot_mask(g: DeviceGraph, edge_mask: jax.Array | None) -> jax.Array:
    """bool[N,K]: usable in-edge slots under the scenario's edge mask."""
    ok = g.in_valid
    # An empty mask is "no mask" (shape is static under trace): no
    # gather through in_edge_id.  The mask-free full SPF passes it so
    # that its program does not depend on the edge count (ISSUE 27);
    # on an edgeless graph every slot is already invalid.
    if edge_mask is not None and edge_mask.shape[0] > 0:
        ok = ok & edge_mask[g.in_edge_id]
    return ok


def sssp_distances(
    g: DeviceGraph,
    root: jax.Array,
    edge_mask: jax.Array | None = None,
    max_iters: int | None = None,
) -> jax.Array:
    """Exact shortest-path distances from ``root`` (int32[N], INF unreachable)."""
    n = g.in_src.shape[0]
    ok = _slot_mask(g, edge_mask)
    dist0 = jnp.full((n,), INF, jnp.int32).at[root].set(0)
    limit = n if max_iters is None else max_iters

    def cond(carry):
        _, changed, it = carry
        return changed & (it < limit)

    def body(carry):
        dist, _, it = carry
        d_nbr = dist[g.in_src]  # [N, K]
        usable = ok & (d_nbr < INF)
        cand = jnp.where(usable, d_nbr + g.in_cost, INF)
        new = jnp.minimum(dist, cand.min(axis=1))
        return new, jnp.any(new != dist), it + 1

    dist, _, _ = jax.lax.while_loop(cond, body, (dist0, jnp.bool_(True), 0))
    return dist


def _sp_dag(g: DeviceGraph, dist: jax.Array, ok: jax.Array, root: jax.Array):
    """bool[N,K]: slot k is a shortest-path-DAG in-edge of vertex v."""
    d_nbr = dist[g.in_src]
    dag = (
        ok
        & (d_nbr < INF)
        & (dist < INF)[:, None]
        & (d_nbr + g.in_cost == dist[:, None])
    )
    # The root has no DAG parents (dist 0; zero-cost network→router edges
    # cannot close a zero cycle since router→network costs are >= 1).
    return dag & (jnp.arange(g.in_src.shape[0]) != root)[:, None]


def _first_parent(g: DeviceGraph, dag: jax.Array, d_nbr: jax.Array) -> jax.Array:
    """int32[N]: DAG parent minimizing (dist[u], u) — the reference's
    candidate-BTreeMap pop order (holo-ospf/src/spf.rs:614-622) — or N
    (sentinel) when the vertex has no DAG parent.  Two-stage lex argmin;
    every engine MUST use this same tie-break for bit-parity."""
    n = g.in_src.shape[0]
    dmin = jnp.where(dag, d_nbr, INF).min(axis=1)  # int32[N]
    src_cand = jnp.where(dag & (d_nbr == dmin[:, None]), g.in_src, n)
    return src_cand.min(axis=1).astype(jnp.int32)


def _nh_words_round(dag, h_nbr, direct_i32, nbr_word):
    """One Jacobi next-hop recompute: per word, OR the direct atoms of
    hops==0 DAG parents with the inherited sets of the rest
    (holo-ospf/src/spf.rs:733-767 direct-vs-inherit split).

    ``nbr_word(wi) -> int32[N, K]``: gathered neighbor values of word wi.
    Shared by the fused and hybrid engines so the split rule cannot drift.
    """
    w = direct_i32.shape[2]
    direct_slot = dag & (h_nbr == 0)
    inherit_slot = dag & (h_nbr != 0)
    words = []
    for wi in range(w):
        seed_w = jax.lax.reduce(
            jnp.where(direct_slot, direct_i32[:, :, wi], 0),
            jnp.int32(0),
            jax.lax.bitwise_or,
            dimensions=(1,),
        )
        inh_w = jax.lax.reduce(
            jnp.where(inherit_slot, nbr_word(wi), 0),
            jnp.int32(0),
            jax.lax.bitwise_or,
            dimensions=(1,),
        )
        words.append(seed_w | inh_w)
    return jnp.stack(words, axis=1)


def spf_one(
    g: DeviceGraph,
    root: jax.Array,
    edge_mask: jax.Array | None = None,
    max_iters: int | None = None,
) -> SpfTensors:
    """Full SPF: distances + first-parent + hops + ECMP next-hop bitmasks."""
    n, k = g.in_src.shape
    ok = _slot_mask(g, edge_mask)
    dist = sssp_distances(g, root, edge_mask, max_iters)
    dag = _sp_dag(g, dist, ok, root)
    d_nbr = dist[g.in_src]
    parent = _first_parent(g, dag, d_nbr)  # n = no parent

    limit = n if max_iters is None else max_iters

    # hops fixpoint along the first-parent chain.  Chase the chain through
    # the ELL slots rather than `hops[parent]`: `parent` varies per
    # scenario, and a batch-dependent-index gather hits XLA's slow path
    # under vmap, while `hops[g.in_src]` shares its indices across the
    # whole batch (measured ~6x faster per round on TPU).  All slots with
    # src == parent carry the same hops value, so a min over the masked
    # slots equals hops[parent].
    big = jnp.int32(n + 1)
    hops0 = jnp.where(jnp.arange(n) == root, 0, big).astype(jnp.int32)
    inc = g.is_router.astype(jnp.int32)
    parent_slot = g.in_src == parent[:, None]  # [N,K] elementwise, no gather

    def hcond(carry):
        _, changed, it = carry
        return changed & (it < limit)

    def hbody(carry):
        hops, _, it = carry
        gathered = hops[g.in_src]  # [N,K], shared indices across batch
        ph = jnp.where(parent_slot, gathered, big).min(axis=1)
        new = jnp.minimum(hops, jnp.where(ph < big, ph + inc, big))
        return new, jnp.any(new != hops), it + 1

    hops, _, _ = jax.lax.while_loop(hcond, hbody, (hops0, jnp.bool_(True), 0))

    # Next-hop bitmask fixpoint over the full DAG (all equal-cost parents).
    # Split the recurrence into a STATIC part and the inherited part: a DAG
    # parent with hops==0 always contributes the edge's direct atom (fixed
    # once hops is known), so those slots fold into a precomputed per-word
    # seed; the loop then only gathers through the remaining slots.  The
    # atom-word axis is unrolled in Python so every loop round works on a
    # flat [N,K] uint32 gather: the [N,K,W] formulation both gathers less
    # efficiently and overflows the TPU compiler's buffer limits at 50k
    # vertices (measured: unrolled is faster at 10k AND compiles at 50k).
    w = g.direct_nh_words.shape[2]
    use_direct = hops[g.in_src] == 0  # [N,K]
    inherit_slot = dag & ~use_direct  # [N,K]

    def ncond(carry):
        _, changed, it = carry
        return changed & (it < limit)

    words = []
    for wi in range(w):
        direct_w = jnp.where(
            dag & use_direct, g.direct_nh_words[:, :, wi], jnp.uint32(0)
        )
        seed_w = jax.lax.reduce(
            direct_w, jnp.uint32(0), jax.lax.bitwise_or, dimensions=(1,)
        )  # uint32[N]

        def nbody(carry):
            nh, _, it = carry
            inherit = jnp.where(
                inherit_slot, nh[g.in_src], jnp.uint32(0)
            )
            new = nh | jax.lax.reduce(
                inherit, jnp.uint32(0), jax.lax.bitwise_or, dimensions=(1,)
            )
            return new, jnp.any(new != nh), it + 1

        nh_w, _, _ = jax.lax.while_loop(
            ncond, nbody, (seed_w, jnp.bool_(True), 0)
        )
        words.append(nh_w)
    nh = jnp.stack(words, axis=1)

    return SpfTensors(
        dist=dist, parent=parent, hops=jnp.where(dist < INF, hops, big), nexthops=nh
    )


def spf_one_fused(
    g: DeviceGraph,
    root: jax.Array,
    edge_mask: jax.Array | None = None,
    max_iters: int | None = None,
    packed: bool = False,
) -> SpfTensors:
    """Full SPF with ALL fixpoints fused into ONE while_loop.

    The sequential formulation (:func:`spf_one`) runs 2+W loops — dist,
    hops, and one per next-hop word — each chasing ~diameter rounds with
    one [N,K] gather per round.  Here every quantity is recomputed
    Jacobi-style each round from the *same* gathered neighbor state:

    - ``dist`` keeps the monotone min-accumulate relaxation;
    - ``parent``/DAG membership are derived from the current ``dist``;
    - ``hops`` and the next-hop words are *recomputed* (not accumulated)
      from the gathered neighbor values, so values derived from stale
      intermediate DAGs wash out once ``dist`` settles.

    Termination: a state the round maps to itself satisfies every
    fixpoint equation simultaneously (dist relaxation-stable + hops/nh
    consistent along the settled, acyclic DAG), so "unchanged" == done.
    hops and next-hop values chase the dist wavefront and settle a couple
    of rounds behind it: total rounds ~= hop-diameter + small constant,
    vs (2+W) x diameter across the sequential loops.

    ``packed=False`` gathers each quantity separately (2+W gathers of a
    [N] operand per round — same memory shape as the proven sequential
    path).  ``packed=True`` stores the state as one int32[N, 2+W] array
    and performs a SINGLE row gather per round ([N,K] indices fetching
    2+W contiguous lanes each) — ~(2+W)x fewer gather index operations
    per round, the dominant cost on TPU (see memory notes) — at the risk
    of a larger [N,K,C] intermediate at 50k-vertex scale.

    Reference semantics preserved: holo-ospf/src/spf.rs:587-767.
    """
    n, k = g.in_src.shape
    w = g.direct_nh_words.shape[2]
    c = 2 + w
    ok = _slot_mask(g, edge_mask)
    # Worst case the quantities settle strictly in sequence (dist, then
    # hops, then nh), each taking up to ~n rounds on a path graph.
    limit = (3 * n + 6) if max_iters is None else max_iters

    big = jnp.int32(n + 1)
    vidx = jnp.arange(n)
    not_root = vidx != root
    inc = g.is_router.astype(jnp.int32)
    # nh words live in int32 lanes (bitwise ops are representation-exact);
    # bitcast back to uint32 on exit.
    direct_i32 = jax.lax.bitcast_convert_type(g.direct_nh_words, jnp.int32)

    dist0 = jnp.full((n,), INF, jnp.int32).at[root].set(0)
    hops0 = jnp.where(vidx == root, 0, big).astype(jnp.int32)
    nh0 = jnp.zeros((n, w), jnp.int32)

    def round_fn(dist, hops, nh):
        if packed:
            state = jnp.concatenate(
                [dist[:, None], hops[:, None], nh], axis=1
            )  # int32[N, C]
            nbr = state[g.in_src]  # [N, K, C] — ONE gather
            d_nbr = nbr[:, :, 0]
            h_nbr = nbr[:, :, 1]
            nh_nbr = [nbr[:, :, 2 + wi] for wi in range(w)]
        else:
            d_nbr = dist[g.in_src]
            h_nbr = hops[g.in_src]
            nh_nbr = [nh[:, wi][g.in_src] for wi in range(w)]

        usable = ok & (d_nbr < INF)
        cand = jnp.where(usable, d_nbr + g.in_cost, INF)
        dist_new = jnp.minimum(dist, cand.min(axis=1))

        dag = usable & (dist_new < INF)[:, None] & (
            d_nbr + g.in_cost == dist_new[:, None]
        )
        dag = dag & not_root[:, None]
        parent = _first_parent(g, dag, d_nbr)

        # hops[parent] without a batch-dependent gather: every slot whose
        # src == parent carries the same gathered hops value.
        parent_slot = g.in_src == parent[:, None]
        ph = jnp.where(parent_slot, h_nbr, big).min(axis=1)
        hops_new = jnp.where(
            vidx == root,
            0,
            jnp.where((parent < n) & (ph < big), ph + inc, big),
        ).astype(jnp.int32)

        nh_new = _nh_words_round(dag, h_nbr, direct_i32, lambda wi: nh_nbr[wi])
        return dist_new, hops_new, nh_new, parent

    def cond(carry):
        _, _, _, _, changed, it = carry
        return changed & (it < limit)

    def body(carry):
        dist, hops, nh, _, _, it = carry
        dist_new, hops_new, nh_new, parent = round_fn(dist, hops, nh)
        changed = (
            jnp.any(dist_new != dist)
            | jnp.any(hops_new != hops)
            | jnp.any(nh_new != nh)
        )
        return dist_new, hops_new, nh_new, parent, changed, it + 1

    parent0 = jnp.full((n,), n, jnp.int32)
    dist, hops, nh, parent, _, _ = jax.lax.while_loop(
        cond, body, (dist0, hops0, nh0, parent0, jnp.bool_(True), 0)
    )
    return SpfTensors(
        dist=dist,
        parent=parent,
        hops=jnp.where(dist < INF, hops, big),
        nexthops=jax.lax.bitcast_convert_type(nh, jnp.uint32),
    )


def spf_one_hybrid(
    g: DeviceGraph,
    root: jax.Array,
    edge_mask: jax.Array | None = None,
    max_iters: int | None = None,
) -> SpfTensors:
    """Full SPF in TWO fixpoint loops: dist alone, then hops+nh packed.

    Rationale (see the engine notes in :func:`spf_one_fused`): the
    sequential engine runs 2+W loops of one [N,K]-shaped gather each;
    the fused engines recompute the DAG/parent/tie-break work every
    round *while dist is still settling*.  This formulation takes the
    best half of each:

    - Phase 1 is the lean dist-only relaxation (:func:`sssp_distances`)
      — one gather + add + row-min per round, nothing else.
    - The shortest-path DAG, first parent, parent-slot mask and direct
      next-hop seeds are then computed ONCE — they depend only on the
      settled dist.
    - Phase 2 chases hops and the W next-hop words together,
      Jacobi-style, through a SINGLE packed int32[N, 1+W] row gather
      per round: (1+W)x fewer gather-index operations than the
      sequential loops over the same total bytes, with none of the
      fused engines' per-round DAG recomputation.

    Results are exact and bit-identical to :func:`spf_one` (parity-gated
    in tests/test_spf_parity.py).  Reference semantics:
    holo-ospf/src/spf.rs:587-767.
    """
    n, k = g.in_src.shape
    w = g.direct_nh_words.shape[2]
    ok = _slot_mask(g, edge_mask)
    dist = sssp_distances(g, root, edge_mask, max_iters)
    dag = _sp_dag(g, dist, ok, root)
    d_nbr = dist[g.in_src]
    # First parent is fixed from here on (the DAG depends only on dist).
    parent = _first_parent(g, dag, d_nbr)

    big = jnp.int32(n + 1)
    limit = n if max_iters is None else max_iters
    hops0 = jnp.where(jnp.arange(n) == root, 0, big).astype(jnp.int32)
    nh0 = jnp.zeros((n, w), jnp.int32)
    hops, nh = _hops_nh_fixpoint(g, root, dag, parent, hops0, nh0, limit)
    return SpfTensors(
        dist=dist,
        parent=parent,
        hops=jnp.where(dist < INF, hops, big),
        nexthops=jax.lax.bitcast_convert_type(nh, jnp.uint32),
    )


def _hops_nh_fixpoint(g, root, dag, parent, hops0, nh0, limit):
    """Packed Jacobi hops + next-hop fixpoint over a settled DAG —
    phase 2 of the hybrid engine, shared with the incremental kernel.

    The body RECOMPUTES (never accumulates) each value from the
    gathered neighbor state, and the DAG/parent chain is acyclic with a
    fixed boundary (the root), so the fixpoint equations have exactly
    one solution: ANY seed in the value domain converges to the same
    bit-exact answer.  Fresh seeds (hops0 = root-only, nh0 = 0) give
    the hybrid engine; the previous run's arrays give the incremental
    path, where convergence takes rounds proportional to the depth of
    the region the delta actually changed.
    """
    n = g.in_src.shape[0]
    big = jnp.int32(n + 1)
    is_root = jnp.arange(n) == root
    inc = g.is_router.astype(jnp.int32)
    parent_slot = g.in_src == parent[:, None]
    has_parent = parent < n
    direct_i32 = jax.lax.bitcast_convert_type(g.direct_nh_words, jnp.int32)

    def cond(carry):
        _, _, changed, it = carry
        return changed & (it < limit)

    def body(carry):
        hops, nh, _, it = carry
        state = jnp.concatenate([hops[:, None], nh], axis=1)  # int32[N, 1+W]
        nbr = state[g.in_src]  # [N, K, 1+W] — the ONE gather per round
        h_nbr = nbr[:, :, 0]

        ph = jnp.where(parent_slot, h_nbr, big).min(axis=1)
        hops_new = jnp.where(
            is_root, 0, jnp.where(has_parent & (ph < big), ph + inc, big)
        ).astype(jnp.int32)

        nh_new = _nh_words_round(
            dag, h_nbr, direct_i32, lambda wi: nbr[:, :, 1 + wi]
        )

        changed = jnp.any(hops_new != hops) | jnp.any(nh_new != nh)
        return hops_new, nh_new, changed, it + 1

    hops, nh, _, _ = jax.lax.while_loop(
        cond, body, (hops0, nh0, jnp.bool_(True), 0)
    )
    return hops, nh


def _slot_atom_onehot(g: DeviceGraph) -> jax.Array:
    """int32[N, K, A] 0/1 expansion of the per-slot direct-atom words —
    the static scatter basis of the per-atom UCMP weight recurrence."""
    n, k = g.in_src.shape
    w = g.direct_nh_words.shape[2]
    bits = jnp.arange(32, dtype=jnp.uint32)
    oh = ((g.direct_nh_words[:, :, :, None] >> bits) & jnp.uint32(1)).astype(
        jnp.int32
    )  # [N, K, W, 32]
    return oh.reshape(n, k, w * 32)


def _mp_fixpoint(g, root, dag, parent, hops0, nh0, np0, aw0, limit):
    """Packed Jacobi fixpoint over a settled DAG for the FULL multipath
    state: hops + next-hop words + saturated path counts + per-atom
    UCMP weights, ONE row gather per round (the widened analog of
    :func:`_hops_nh_fixpoint`; state lanes int32[N, 2+W+A]).

    Every lane is RECOMPUTED (never accumulated) from the gathered
    neighbor values and the DAG/parent chain is acyclic with a fixed
    boundary, so each fixpoint equation — including the clamped
    path-count recursion ``npaths[v] = min(sum npaths[u], MP_SAT)``,
    which is monotone in already-clamped parent values — has exactly
    one solution: any seed converges bit-exactly (fresh seeds give the
    full kernel, the previous run's arrays give the incremental path).
    """
    n = g.in_src.shape[0]
    w = g.direct_nh_words.shape[2]
    big = jnp.int32(n + 1)
    sat = jnp.int32(MP_SAT)
    is_root = jnp.arange(n) == root
    inc = g.is_router.astype(jnp.int32)
    parent_slot = g.in_src == parent[:, None]
    has_parent = parent < n
    direct_i32 = jax.lax.bitcast_convert_type(g.direct_nh_words, jnp.int32)
    onehot = _slot_atom_onehot(g)  # int32[N, K, A]

    def cond(carry):
        _, _, _, _, changed, it = carry
        return changed & (it < limit)

    def body(carry):
        hops, nh, npaths, aw, _, it = carry
        state = jnp.concatenate(
            [hops[:, None], npaths[:, None], nh, aw], axis=1
        )  # int32[N, 2+W+A]
        nbr = state[g.in_src]  # [N, K, C] — the ONE gather per round
        h_nbr = nbr[:, :, 0]
        np_nbr = nbr[:, :, 1]

        ph = jnp.where(parent_slot, h_nbr, big).min(axis=1)
        hops_new = jnp.where(
            is_root, 0, jnp.where(has_parent & (ph < big), ph + inc, big)
        ).astype(jnp.int32)

        nh_new = _nh_words_round(
            dag, h_nbr, direct_i32, lambda wi: nbr[:, :, 2 + wi]
        )

        # Saturated path counts: sum of (clamped) parent counts over
        # the DAG slots.  Row sums stay exact in int32 (see MP_SAT).
        np_sum = jnp.where(dag, np_nbr, 0).sum(axis=1)
        np_new = jnp.where(
            is_root, 1, jnp.minimum(np_sum, sat)
        ).astype(jnp.int32)

        # Per-atom weights: a hops==0 DAG parent contributes its path
        # count on the slot's direct atom lane; any other DAG parent
        # contributes its own weight row — the direct-vs-inherit split
        # of the next-hop rule, carrying multiplicity.
        direct_slot = (dag & (h_nbr == 0)).astype(jnp.int32)
        inherit_slot = (dag & (h_nbr != 0)).astype(jnp.int32)
        aw_nbr = nbr[:, :, 2 + w :]  # [N, K, A]
        contrib = (
            onehot * (np_nbr * direct_slot)[:, :, None]
            + aw_nbr * inherit_slot[:, :, None]
        )
        aw_new = jnp.minimum(contrib.sum(axis=1), sat).astype(jnp.int32)

        changed = (
            jnp.any(hops_new != hops)
            | jnp.any(nh_new != nh)
            | jnp.any(np_new != npaths)
            | jnp.any(aw_new != aw)
        )
        return hops_new, nh_new, np_new, aw_new, changed, it + 1

    hops, nh, npaths, aw, _, _ = jax.lax.while_loop(
        cond, body, (hops0, nh0, np0, aw0, jnp.bool_(True), 0)
    )
    return hops, nh, npaths, aw


def _mp_parent_sets(g, root, dist, ok, npaths, kp: int):
    """Closed-form parent-set extraction from settled distances:
    (parents, pdist, pweight) int32[N, Kp] planes per the
    :class:`MultipathTensors` contract.

    ``kp`` rounds of masked lexicographic min over the [N, K] slot
    planes — each round emits the best remaining (path cost, source)
    pair and retires every slot of that source, so parallel links
    collapse onto one parent entry at their cheapest cost."""
    n = g.in_src.shape[0]
    d_nbr = dist[g.in_src]
    not_root = (jnp.arange(n) != root)[:, None]
    reach = (dist < INF)[:, None]
    dag = (
        ok & (d_nbr < INF) & reach & (d_nbr + g.in_cost == dist[:, None])
        & not_root
    )
    # Loop-free diversity slots: strictly-downward sources.  Strictness
    # matters — dist[u] == dist[v] (zero-cost network→router edges)
    # could route a shortest root→u path through v.
    divers = ok & (d_nbr < INF) & reach & (d_nbr < dist[:, None]) & not_root
    adm = dag | divers
    pathcost = jnp.where(adm, d_nbr + g.in_cost, INF)
    np_nbr = npaths[g.in_src]  # [N, K]

    parents, pdists, pweights = [], [], []
    remaining = adm
    for _ in range(kp):
        cmin = jnp.where(remaining, pathcost, INF).min(axis=1)
        tie = remaining & (pathcost == cmin[:, None])
        smin = jnp.where(tie, g.in_src, n).min(axis=1)
        has = cmin < INF
        parents.append(jnp.where(has, smin, n).astype(jnp.int32))
        pdists.append(jnp.where(has, cmin, INF).astype(jnp.int32))
        sel = tie & (g.in_src == smin[:, None])
        pweights.append(
            jnp.where(has, jnp.where(sel, np_nbr, 0).max(axis=1), 0).astype(
                jnp.int32
            )
        )
        remaining = remaining & (g.in_src != smin[:, None])
    return (
        jnp.stack(parents, axis=1),
        jnp.stack(pdists, axis=1),
        jnp.stack(pweights, axis=1),
    )


def spf_one_multipath(
    g: DeviceGraph,
    root: jax.Array,
    kp: int,
    edge_mask: jax.Array | None = None,
    max_iters: int | None = None,
) -> tuple[SpfTensors, MultipathTensors]:
    """Full SPF + the multi-parent frontier in ONE jitted program.

    Phase 1 is the lean distance relaxation; the DAG, first parent and
    parent-set planes are closed-form in the settled distances; phase 2
    chases hops, next-hop words, path counts and per-atom UCMP weights
    together through a single packed row gather per round (the hybrid
    engine's schedule, widened).  ``kp`` is static (pow2, <= 8): one
    XLA program per (shape, kp) bucket.  The SpfTensors half is
    bit-identical to :func:`spf_one` (parity-gated), so arming
    multipath can never change single-path routing state.

    Memory note: the packed state carries ``A = W*32`` weight lanes —
    size batches like the 10k what-if batch, not the 50k single-SPF path.
    """
    n, k = g.in_src.shape
    w = g.direct_nh_words.shape[2]
    ok = _slot_mask(g, edge_mask)
    dist = sssp_distances(g, root, edge_mask, max_iters)
    dag = _sp_dag(g, dist, ok, root)
    parent = _first_parent(g, dag, dist[g.in_src])

    big = jnp.int32(n + 1)
    limit = n if max_iters is None else max_iters
    hops0 = jnp.where(jnp.arange(n) == root, 0, big).astype(jnp.int32)
    nh0 = jnp.zeros((n, w), jnp.int32)
    np0 = jnp.where(jnp.arange(n) == root, 1, 0).astype(jnp.int32)
    aw0 = jnp.zeros((n, w * 32), jnp.int32)
    hops, nh, npaths, aw = _mp_fixpoint(
        g, root, dag, parent, hops0, nh0, np0, aw0, limit
    )
    parents, pdist, pweight = _mp_parent_sets(g, root, dist, ok, npaths, kp)
    sp = SpfTensors(
        dist=dist,
        parent=parent,
        hops=jnp.where(dist < INF, hops, big),
        nexthops=jax.lax.bitcast_convert_type(nh, jnp.uint32),
    )
    mp = MultipathTensors(
        parents=parents,
        pdist=pdist,
        pweight=pweight,
        npaths=jnp.where(dist < INF, npaths, 0),
        nh_weights=aw,
    )
    return sp, mp


def spf_one_incremental_multipath(
    g: DeviceGraph,
    root: jax.Array,
    prev: SpfTensors,
    prev_npaths: jax.Array,
    prev_nh_weights: jax.Array,
    seed_rows: jax.Array,
    kp: int,
    max_iters: int | None = None,
) -> tuple[SpfTensors, MultipathTensors]:
    """Incremental multipath SPF: the DeltaPath recompute
    (:func:`spf_one_incremental`) with the widened phase-2 state seeded
    from the previous run's multipath planes.  Only ``npaths`` and
    ``nh_weights`` carry state between runs — the parent-set planes are
    closed-form in the settled distances, so they are recomputed (not
    taken as inputs; donating them would never realize as an alias).
    Rounds ~ changed-region depth.  Bit-identical to
    ``spf_one_multipath(g, root, kp)`` by fixpoint uniqueness."""
    n, k = g.in_src.shape
    limit = n if max_iters is None else max_iters
    big = jnp.int32(n + 1)
    ok = g.in_valid  # the incremental path never carries an edge mask

    par = prev.parent
    has_par = par < n
    par_safe = jnp.where(has_par, par, 0)
    aff0 = jnp.zeros((n,), bool).at[seed_rows].set(True, mode="drop")

    def acond(carry):
        _, changed, it = carry
        return changed & (it < limit)

    def abody(carry):
        aff, _, it = carry
        new = aff | (jnp.where(has_par, aff[par_safe], False))
        return new, jnp.any(new != aff), it + 1

    aff, _, _ = jax.lax.while_loop(acond, abody, (aff0, jnp.bool_(True), 0))
    dist0 = jnp.where(aff, INF, prev.dist).at[root].set(0)

    def rcond(carry):
        _, changed, it = carry
        return changed & (it < limit)

    def rbody(carry):
        dist, _, it = carry
        d_nbr = dist[g.in_src]
        usable = ok & (d_nbr < INF)
        cand = jnp.where(usable, d_nbr + g.in_cost, INF)
        new = jnp.minimum(dist, cand.min(axis=1))
        return new, jnp.any(new != dist), it + 1

    dist, _, _ = jax.lax.while_loop(rcond, rbody, (dist0, jnp.bool_(True), 0))

    dag = _sp_dag(g, dist, ok, root)
    parent = _first_parent(g, dag, dist[g.in_src])
    nh_prev = jax.lax.bitcast_convert_type(prev.nexthops, jnp.int32)
    hops, nh, npaths, aw = _mp_fixpoint(
        g, root, dag, parent, prev.hops, nh_prev,
        prev_npaths, prev_nh_weights, limit,
    )
    parents, pdist, pweight = _mp_parent_sets(g, root, dist, ok, npaths, kp)
    sp = SpfTensors(
        dist=dist,
        parent=parent,
        hops=jnp.where(dist < INF, hops, big),
        nexthops=jax.lax.bitcast_convert_type(nh, jnp.uint32),
    )
    mp = MultipathTensors(
        parents=parents,
        pdist=pdist,
        pweight=pweight,
        npaths=jnp.where(dist < INF, npaths, 0),
        nh_weights=aw,
    )
    return sp, mp


def spf_multipath_batch(
    g: DeviceGraph,
    root: jax.Array,
    edge_masks: jax.Array,
    kp: int,
    max_iters: int | None = None,
) -> tuple[SpfTensors, MultipathTensors]:
    """Batched multipath what-if: vmap of :func:`spf_one_multipath`
    over scenario edge masks (bool[B, E]) — ECMP/UCMP and diversity
    planes for every scenario in one dispatch."""
    fn = jax.vmap(lambda m: spf_one_multipath(g, root, kp, m, max_iters))
    return fn(edge_masks)


def spf_one_incremental(
    g: DeviceGraph,
    root: jax.Array,
    prev: SpfTensors,
    seed_rows: jax.Array,
    max_iters: int | None = None,
) -> SpfTensors:
    """Incremental full SPF: recompute only what a topology delta can
    have changed, seeded from the previous run's tensors (DeltaPath,
    arXiv:1808.06893; radius cut per Bounded Dijkstra, 1903.00436).

    ``g`` is the delta-UPDATED device graph; ``prev`` the tensors
    computed on the base graph; ``seed_rows`` (padded with
    out-of-range sentinels) the vertices whose previous distance may
    now be stale-low (:meth:`TopologyDelta.seed_rows`).

    1. Invalidate the previous-SPT descendants of the seed rows: a
       vertex whose first-parent chain avoids every seed still has its
       old shortest path intact at no greater cost, so its previous
       distance remains a valid upper bound.  Rounds ~ affected-subtree
       depth (one [N] gather each).
    2. Min-plus relaxation seeded with those upper bounds (INF inside
       the invalidated region): converges in rounds ~ the radius of
       the affected region instead of the full graph diameter.
    3. DAG/first-parent from the settled distances (closed form), then
       the shared hops/next-hop fixpoint seeded with the previous
       arrays — unique-fixpoint recompute, so stale values self-correct
       in rounds ~ changed-region depth.

    Bit-identical to ``spf_one(g, root)`` by fixpoint uniqueness
    (property-gated in tests/test_delta_spf.py).
    """
    n, k = g.in_src.shape
    limit = n if max_iters is None else max_iters
    big = jnp.int32(n + 1)
    ok = g.in_valid  # the incremental path never carries an edge mask

    # 1. affected = seeds + their previous first-parent-tree descendants.
    par = prev.parent
    has_par = par < n
    par_safe = jnp.where(has_par, par, 0)
    aff0 = jnp.zeros((n,), bool).at[seed_rows].set(True, mode="drop")

    def acond(carry):
        _, changed, it = carry
        return changed & (it < limit)

    def abody(carry):
        aff, _, it = carry
        new = aff | (jnp.where(has_par, aff[par_safe], False))
        return new, jnp.any(new != aff), it + 1

    aff, _, _ = jax.lax.while_loop(acond, abody, (aff0, jnp.bool_(True), 0))

    # 2. seeded relaxation on the updated graph.
    dist0 = jnp.where(aff, INF, prev.dist).at[root].set(0)

    def rcond(carry):
        _, changed, it = carry
        return changed & (it < limit)

    def rbody(carry):
        dist, _, it = carry
        d_nbr = dist[g.in_src]
        usable = ok & (d_nbr < INF)
        cand = jnp.where(usable, d_nbr + g.in_cost, INF)
        new = jnp.minimum(dist, cand.min(axis=1))
        return new, jnp.any(new != dist), it + 1

    dist, _, _ = jax.lax.while_loop(rcond, rbody, (dist0, jnp.bool_(True), 0))

    # 3. DAG + first parent are closed-form in dist; hops/nh reconverge
    # from the previous arrays through the shared recompute fixpoint.
    dag = _sp_dag(g, dist, ok, root)
    parent = _first_parent(g, dag, dist[g.in_src])
    nh_prev = jax.lax.bitcast_convert_type(prev.nexthops, jnp.int32)
    hops, nh = _hops_nh_fixpoint(
        g, root, dag, parent, prev.hops, nh_prev, limit
    )
    return SpfTensors(
        dist=dist,
        parent=parent,
        hops=jnp.where(dist < INF, hops, big),
        nexthops=jax.lax.bitcast_convert_type(nh, jnp.uint32),
    )


def spf_whatif_batch(
    g: DeviceGraph,
    root: jax.Array,
    edge_masks: jax.Array,
    max_iters: int | None = None,
    engine: str = "seq",
) -> SpfTensors:
    """Batched what-if SPF: vmap over scenario edge masks (bool[B, E]).

    This is the framework's data-parallel axis — e.g. 1024 concurrent
    link-failure studies over one LSDB (BASELINE.md config 5).  Remember to
    mask *both* directions of a failed link.

    ``engine``: 'seq' (default — the staged-loop formulation, fastest
    measured so far; see ADVICE round 3), 'fused' (one fixpoint loop,
    separate gathers), 'packed' (one fixpoint loop, ONE row gather per
    round), or 'hybrid' (dist loop, then one packed hops+next-hop loop).
    """
    one = _ONE_ENGINES[engine]
    fn = jax.vmap(lambda m: one(g, root, m, max_iters))
    return fn(edge_masks)


_ONE_ENGINES = {
    "seq": spf_one,
    "fused": spf_one_fused,
    "packed": lambda g, r, m, mi: spf_one_fused(g, r, m, mi, packed=True),
    "hybrid": spf_one_hybrid,
}


def spf_multiroot(
    g: DeviceGraph,
    roots: jax.Array,
    edge_mask: jax.Array | None = None,
    max_iters: int | None = None,
) -> SpfTensors:
    """SPF from many roots (int32[R]) — e.g. per-neighbor SPTs for IS-IS
    flooding reduction (holo-isis/src/flooding/manet.rs:39-97) or TI-LFA."""
    fn = jax.vmap(lambda r: spf_one(g, r, edge_mask, max_iters))
    return fn(roots)


# -- jaxpr-audit registrations (HL3xx) ----------------------------------
# Inert contract descriptors for holo_tpu.analysis.jaxpr_audit: the
# builder/spec thunks below run ONLY when the audit arms — registration
# itself is a dict write, so the dispatch path never pays for them.
from holo_tpu.analysis.kernels import register_kernel as _register_kernel  # noqa: E402

#: Canonical audit shapes: small enough to lower in milliseconds, wide
#: enough to exercise every gather/scatter lane the real shapes use.
_AUDIT_N, _AUDIT_K, _AUDIT_W, _AUDIT_E = 64, 8, 2, 128
_AUDIT_B = 8  # scenario/root batch lanes


def audit_graph_spec(n=_AUDIT_N, k=_AUDIT_K, w=_AUDIT_W) -> DeviceGraph:
    """Abstract DeviceGraph matching the marshal layout, for lowering."""
    s = jax.ShapeDtypeStruct
    return DeviceGraph(
        in_src=s((n, k), jnp.int32),
        in_cost=s((n, k), jnp.int32),
        in_valid=s((n, k), jnp.bool_),
        in_edge_id=s((n, k), jnp.int32),
        direct_nh_words=s((n, k, w), jnp.uint32),
        is_router=s((n,), jnp.bool_),
    )


def audit_spf_spec(n=_AUDIT_N, w=_AUDIT_W) -> SpfTensors:
    s = jax.ShapeDtypeStruct
    return SpfTensors(
        dist=s((n,), jnp.int32),
        parent=s((n,), jnp.int32),
        hops=s((n,), jnp.int32),
        nexthops=s((n, w), jnp.uint32),
    )


def audit_mp_spec(n=_AUDIT_N, kp=2, w=_AUDIT_W) -> MultipathTensors:
    s = jax.ShapeDtypeStruct
    return MultipathTensors(
        parents=s((n, kp), jnp.int32),
        pdist=s((n, kp), jnp.int32),
        pweight=s((n, kp), jnp.int32),
        npaths=s((n,), jnp.int32),
        nh_weights=s((n, w * 32), jnp.int32),
    )


def _audit_delta_specs() -> tuple:
    s = jax.ShapeDtypeStruct
    r = _DELTA_PAD_FLOOR
    i32, u32, b = jnp.int32, jnp.uint32, jnp.bool_
    return (
        audit_graph_spec(),
        s((r,), i32), s((r,), i32), s((r,), i32),
        s((r,), i32), s((r,), b), s((r, _AUDIT_W), u32),
        s((_AUDIT_N,), b),
    )


_register_kernel(
    "spf.delta.apply",
    builder=lambda: _APPLY_DELTA,
    specs=_audit_delta_specs,
    donate=(0,),
    buckets=16,  # pow2 delta-row pads above _DELTA_PAD_FLOOR, per shape
)
