"""Graph marshaling: LSDB-style directed graphs → padded ELL tensors.

The protocol layer (OSPF/IS-IS) lowers its LSDB into a :class:`Topology`
(vertex-indexed directed graph with int32 costs).  :func:`build_ell` packs it
into a fixed-shape ELL (in-edge) layout that JAX programs consume.  Shapes are
static per (n_vertices, max_in_degree) bucket so XLA compiles once per bucket.

Vertex ordering contract: vertex indices MUST be assigned in ascending SPF
tie-break order — the reference pops candidates from a BTreeMap keyed by
``(distance, VertexId)`` (holo-ospf/src/spf.rs:614-622) where ``VertexId``
orders Network vertices before Router vertices (holo-ospf/src/ospfv2/spf.rs:42-45).
With that contract, ``argmin(dist, index)`` on device reproduces the exact
reference tie-break.
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from typing import NamedTuple

import numpy as np

from holo_tpu import telemetry

# Distances are exact int32.  Valid path costs are bounded by
# n_vertices * 65535 < 2**30 for any topology we accept, so INF is safe from
# overflow as long as candidate sums are masked before the add (see sssp.py).
INF = np.int32(1 << 30)

# Multipath path-count saturation (UCMP weights): shortest-path counts
# explode combinatorially on dense equal-cost meshes, so every engine
# (device kernel AND scalar oracle) computes the SAME clamped recursion
#   npaths[v] = min(sum_{DAG parents u} npaths[u], MP_SAT)
# over already-clamped parent values.  The clamp keeps the per-round
# row sum exact in int32: K_pad * MP_SAT < 2**31 for K_pad <= 16384,
# far above any in-degree bucket build_ell produces in practice.
MP_SAT = np.int32(1 << 17)

_TOPOLOGY_UIDS = itertools.count()


@dataclass
class Topology:
    """Host-side directed graph in SPF vertex space.

    Vertices are routers and transit networks (pseudo-nodes), pre-sorted by
    the protocol's tie-break key (networks first; see module docstring).
    Edges are directed with int32 costs; network→router edges cost 0
    (RFC 2328 §16.1).  The builder is expected to have applied the
    mutual-link (bidirectionality) check already for static edges
    (holo-ospf/src/spf.rs:653-664); per-scenario what-if masks must mask both
    directions of a link.
    """

    n_vertices: int
    is_router: np.ndarray  # bool[N]
    edge_src: np.ndarray  # int32[E]
    edge_dst: np.ndarray  # int32[E]
    edge_cost: np.ndarray  # int32[E]
    # Direct next-hop atom id per edge, or -1.  Set by the protocol layer for
    # edges whose relaxation yields a *directly computed* next hop (parent is
    # the root, or a transit network adjacent to the root — the parent.hops==0
    # case of holo-ospf/src/spf.rs:744-767).  Atom ids index the protocol
    # layer's next-hop table (interface, address pairs); ECMP sets are
    # bitmasks over these atoms.
    edge_direct_atom: np.ndarray | None = None
    # Shared-risk link group membership per edge as a uint32 bitmask
    # (bit g = the edge belongs to SRLG g; 0 = no shared risk).  Policy
    # input to the FRR engines only — it never enters the DeviceGraph,
    # so DeltaPath residents cannot serve it stale.  The protocol layer
    # (or tests/synth) sets it; default is all-zeros (no SRLGs).
    edge_srlg: np.ndarray | None = None
    # Root vertex index (the calculating router).
    root: int = 0
    names: list = field(default_factory=list)  # optional, debugging only
    # Native partition hint (ISSUE 15): per-vertex group id stamped by
    # the protocol layer at the marshal seam (OSPF area / IS-IS level
    # membership via spf_run.apply_partition_hint) or by synth multi-
    # area builders.  ``partition_topology`` honors it verbatim; None
    # means "flat" and the deterministic BFS/greedy cut decides.  Like
    # edge_srlg it never enters the DeviceGraph planes, so DeltaPath
    # residents cannot serve it stale.
    partition_hint: np.ndarray | None = None

    def __post_init__(self) -> None:
        self.is_router = np.asarray(self.is_router, dtype=bool)
        self.edge_src = np.asarray(self.edge_src, dtype=np.int32)
        self.edge_dst = np.asarray(self.edge_dst, dtype=np.int32)
        self.edge_cost = np.asarray(self.edge_cost, dtype=np.int32)
        if self.edge_direct_atom is None:
            self.edge_direct_atom = np.full(self.edge_src.shape, -1, np.int32)
        else:
            self.edge_direct_atom = np.asarray(self.edge_direct_atom, np.int32)
        if self.edge_srlg is None:
            self.edge_srlg = np.zeros(self.edge_src.shape, np.uint32)
        else:
            self.edge_srlg = np.asarray(self.edge_srlg, np.uint32)
        if self.partition_hint is not None:
            self.partition_hint = np.asarray(self.partition_hint, np.int32)
        # Identity for device-marshaling caches: a process-unique id plus a
        # generation bumped by touch().  Callers mutating arrays in place
        # MUST call touch() or cached DeviceGraphs go stale.
        self._uid = next(_TOPOLOGY_UIDS)
        self.generation = 0
        # DeltaPath lineage: a TopologyDelta linking this topology to a
        # previously-marshaled base (set by the protocol layer at the
        # LSDB seam via link_delta()).  The device-graph cache and SPF
        # backend use it to update the resident EllGraph buffers in
        # place instead of re-marshaling from scratch.
        self.delta_base: "TopologyDelta | None" = None

    def touch(self) -> None:
        """Invalidate marshaling caches after an in-place mutation.

        Also drops any delta lineage: a delta describes the arrays as
        they were when it was diffed — applying it after a mutation
        would serve a graph that silently misses the mutation."""
        self.generation += 1
        self.delta_base = None

    @property
    def cache_key(self) -> tuple:
        return (self._uid, self.generation)

    def n_atoms(self) -> int:
        """Number of distinct next-hop atoms referenced by edges (>= 1)."""
        if self.n_edges == 0:
            return 1
        return max(int(self.edge_direct_atom.max()) + 1, 1)

    @property
    def n_edges(self) -> int:
        return int(self.edge_src.shape[0])

    def link_delta(self, delta: "TopologyDelta") -> None:
        """Attach DeltaPath lineage: this topology equals the base
        topology identified by ``delta.base_key`` with ``delta``
        applied.  Consumers (DeviceGraphCache / TpuSpfBackend) may then
        update the base's device-resident EllGraph in place instead of
        re-marshaling."""
        self.delta_base = delta

    def filter_mutual(self) -> "Topology":
        """Drop edges whose reverse edge does not exist.

        Equivalent of the reference's per-visit bidirectionality check
        (holo-ospf/src/spf.rs:653-664), hoisted to marshal time.
        """
        keep = mutual_keep_mask(self.edge_src, self.edge_dst)
        return Topology(
            n_vertices=self.n_vertices,
            is_router=self.is_router,
            edge_src=self.edge_src[keep],
            edge_dst=self.edge_dst[keep],
            edge_cost=self.edge_cost[keep],
            edge_direct_atom=self.edge_direct_atom[keep],
            edge_srlg=self.edge_srlg[keep],
            root=self.root,
            names=self.names,
            partition_hint=self.partition_hint,
        )


class EllGraph(NamedTuple):
    """Fixed-shape device layout: per-vertex padded in-edge lists.

    All arrays are numpy on build and become jnp on first device use.
    Padding slots have ``in_valid == False`` and ``in_src == 0`` (safe gather).
    """

    in_src: np.ndarray  # int32[N, K] source vertex of k-th in-edge
    in_cost: np.ndarray  # int32[N, K]
    in_valid: np.ndarray  # bool[N, K]
    in_edge_id: np.ndarray  # int32[N, K] original edge index (0 for pads)
    in_direct_atom: np.ndarray  # int32[N, K] atom id or -1
    is_router: np.ndarray  # bool[N]
    n_atoms: int  # static: number of next-hop atoms (bitmask width)

    @property
    def n_vertices(self) -> int:
        return self.in_src.shape[0]

    @property
    def k_pad(self) -> int:
        return self.in_src.shape[1]


def lookup_sorted(keys: np.ndarray, wanted: np.ndarray):
    """``dict.get`` on arrays: per element of ``wanted`` its index in the
    ascending ``keys`` (of equal keys the last, as a dict comprehension
    over them leaves it) and whether it is there at all.  The search
    runs over ``wanted`` in ascending order too: a binary search per
    random element is mostly mispredicted branches, and sorting 26,000
    int64 first takes a third of the time it saves."""
    if not len(keys):
        return np.zeros(len(wanted), np.int64), np.zeros(len(wanted), bool)
    order = np.argsort(wanted)
    at = np.empty(len(wanted), np.int64)
    at[order] = np.searchsorted(keys, wanted[order], side="right") - 1
    return at, (at >= 0) & (keys[at] == wanted)


def mutual_keep_mask(edge_src, edge_dst) -> np.ndarray:
    """bool[E]: edge has a reverse edge (the single bidirectionality rule
    shared by every protocol's marshaling path).  Vertex indices are
    int32, as :class:`Topology` holds them."""
    src = np.asarray(edge_src)
    dst = np.asarray(edge_dst)
    # One int64 key per (src, dst) pair: an edge stays when the key of
    # its reverse pair is among the forward keys.
    fwd = np.sort(_pack_i32_pairs(src, dst))
    return lookup_sorted(fwd, _pack_i32_pairs(dst, src))[1]


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def build_ell(
    topo: Topology,
    k_pad: int | None = None,
    n_atoms: int = 64,
    k_multiple: int = 8,
    k_min: int = 0,
) -> EllGraph:
    """Pack a :class:`Topology` into the ELL in-edge layout.

    ``k_pad`` defaults to max in-degree rounded up to ``k_multiple`` (shape
    bucketing keeps XLA recompiles rare under LSA churn) and to no less
    than ``k_min`` (the width of the resident a re-marshal replaces).
    """
    n = topo.n_vertices
    counts = np.bincount(topo.edge_dst, minlength=n)
    kmax = int(counts.max()) if topo.n_edges else 1
    if k_pad is None:
        k_pad = max(_round_up(max(kmax, 1), k_multiple), k_multiple, k_min)
    elif kmax > k_pad:
        raise ValueError(f"k_pad={k_pad} < max in-degree {kmax}")
    if topo.n_atoms() > n_atoms:
        raise ValueError(
            f"topology references {topo.n_atoms()} next-hop atoms, "
            f"bitmask width n_atoms={n_atoms} is too small"
        )

    in_src = np.zeros((n, k_pad), np.int32)
    in_cost = np.zeros((n, k_pad), np.int32)
    in_valid = np.zeros((n, k_pad), bool)
    in_edge_id = np.zeros((n, k_pad), np.int32)
    in_direct_atom = np.full((n, k_pad), -1, np.int32)

    if topo.n_edges:
        # Vectorized bucketing: stable-sort edges by destination, then the
        # slot of each edge is its rank within its destination group.
        order = np.argsort(topo.edge_dst, kind="stable")
        dst_sorted = topo.edge_dst[order]
        first = np.searchsorted(dst_sorted, dst_sorted, side="left")
        slots = np.arange(topo.n_edges, dtype=np.int64) - first
        rows = dst_sorted.astype(np.int64)
        in_src[rows, slots] = topo.edge_src[order]
        in_cost[rows, slots] = topo.edge_cost[order]
        in_valid[rows, slots] = True
        in_edge_id[rows, slots] = order.astype(np.int32)
        in_direct_atom[rows, slots] = topo.edge_direct_atom[order]

    return EllGraph(
        in_src=in_src,
        in_cost=in_cost,
        in_valid=in_valid,
        in_edge_id=in_edge_id,
        in_direct_atom=in_direct_atom,
        is_router=topo.is_router.copy(),
        n_atoms=n_atoms,
    )


def _i32(values) -> np.ndarray:
    return np.asarray(list(values), np.int32).reshape(-1)


@dataclass
class TopologyDelta:
    """Typed topology change set (DeltaPath, arXiv:1808.06893).

    Describes how a target topology differs from an already-marshaled
    *base* topology (identified by ``base_key = (uid, generation)``) in
    terms the device-resident EllGraph can absorb as in-place scatter
    updates:

    - **weight changes** — the same directed edge (src, dst, atom) with
      a new cost; the ELL slot is rewritten, edge indices stay valid
      (``ids_stable``).
    - **edge add/remove** — directed edges entering/leaving the graph;
      removals invalidate their slot, additions occupy padding slack in
      the destination row (overflow → full rebuild).  Edge indices
      shift, so the updated graph no longer serves edge-mask consumers
      (``ids_stable`` False).
    - **node overload bit** — ``overload`` vertices are struck from
      transit: every slot whose source is an overloaded vertex goes
      invalid (IS-IS overload semantics — still reachable as a
      destination, never used as a via).  One-way: clearing overload
      requires a full rebuild.

    ``seed_rows()`` is the Bounded-Dijkstra-style radius cut: the set
    of vertices whose previous distances may now be *too small* (edge
    removed, cost increased, via struck).  Distances elsewhere remain
    valid upper bounds, so the incremental kernel only invalidates the
    previous-SPT descendants of these rows.
    """

    base_key: tuple  # (uid, generation) of the base Topology
    # cost changes: directed edge (src, dst, atom), old -> new cost
    w_src: np.ndarray = field(default_factory=lambda: _i32(()))
    w_dst: np.ndarray = field(default_factory=lambda: _i32(()))
    w_old: np.ndarray = field(default_factory=lambda: _i32(()))
    w_new: np.ndarray = field(default_factory=lambda: _i32(()))
    w_atom: np.ndarray = field(default_factory=lambda: _i32(()))
    # removed directed edges
    r_src: np.ndarray = field(default_factory=lambda: _i32(()))
    r_dst: np.ndarray = field(default_factory=lambda: _i32(()))
    r_cost: np.ndarray = field(default_factory=lambda: _i32(()))
    r_atom: np.ndarray = field(default_factory=lambda: _i32(()))
    # added directed edges
    a_src: np.ndarray = field(default_factory=lambda: _i32(()))
    a_dst: np.ndarray = field(default_factory=lambda: _i32(()))
    a_cost: np.ndarray = field(default_factory=lambda: _i32(()))
    a_atom: np.ndarray = field(default_factory=lambda: _i32(()))
    # vertices struck from transit (overload bit set since the base)
    overload: np.ndarray = field(default_factory=lambda: _i32(()))
    # True iff the base's edge ordering (and thus in_edge_id) is still
    # valid for the target topology: pure weight-change deltas only.
    ids_stable: bool = True

    @property
    def n_ops(self) -> int:
        return (
            self.w_src.shape[0]
            + self.r_src.shape[0]
            + self.a_src.shape[0]
            + self.overload.shape[0]
        )

    @property
    def kind(self) -> str:
        """Delta taxonomy bucket (metric label): the single op class
        present, ``mixed`` when several combine, ``empty`` for a
        content-identical alias."""
        present = [
            name
            for name, n in (
                ("struct", self.r_src.shape[0] + self.a_src.shape[0]),
                ("weight", self.w_src.shape[0]),
                ("overload", self.overload.shape[0]),
            )
            if n
        ]
        if not present:
            return "empty"
        return present[0] if len(present) == 1 else "mixed"

    def seed_rows(self) -> np.ndarray:
        """int32[S] vertices whose previous distance may be stale-low:
        targets of removed edges, targets of cost increases, and the
        overloaded vertices themselves (every path transiting them
        passes through them, so SPT-descendant invalidation from the
        vertex covers every route its strike can break)."""
        rows = [
            self.r_dst,
            self.w_dst[self.w_new > self.w_old],
            self.overload,
        ]
        return np.unique(np.concatenate([_i32(r) for r in rows]))


def _undirected_adjacency(
    n: int, edge_src: np.ndarray, edge_dst: np.ndarray
) -> tuple[np.ndarray, np.ndarray]:
    """CSR (indptr, indices) of the undirected structure, neighbor
    lists sorted ascending — the shared basis of the BFS/greedy cut and
    the RCM bandwidth permutation (both must be deterministic)."""
    src = np.concatenate([edge_src, edge_dst]).astype(np.int64)
    dst = np.concatenate([edge_dst, edge_src]).astype(np.int64)
    order = np.lexsort((dst, src))
    src, dst = src[order], dst[order]
    # Dedup parallel/mirrored entries.
    if src.shape[0]:
        keep = np.ones(src.shape[0], bool)
        keep[1:] = (src[1:] != src[:-1]) | (dst[1:] != dst[:-1])
        src, dst = src[keep], dst[keep]
    indptr = np.zeros(n + 1, np.int64)
    np.add.at(indptr, src + 1, 1)
    np.cumsum(indptr, out=indptr)
    return indptr, dst.astype(np.int32)


def partition_topology(
    topo: Topology,
    n_parts: int | None = None,
    max_part: int | None = None,
) -> np.ndarray:
    """int32[N] partition assignment (ids 0..P-1, every id non-empty).

    Native structure first: a stamped ``partition_hint`` (OSPF areas /
    IS-IS levels via the protocol seams, or synth multi-area builders)
    is honored verbatim — distinct hint values map onto dense partition
    ids in ascending hint order.  Flat graphs get a deterministic
    METIS-style greedy cut: BFS-grow regions of ~``ceil(N / n_parts)``
    vertices (or ``max_part``) from the lowest-indexed unassigned
    vertex, neighbors visited in ascending id order — locality-seeking
    like a KL/METIS first pass, with none of their randomized
    refinement so every run cuts identically.
    """
    n = topo.n_vertices
    hint = topo.partition_hint
    if hint is not None:
        if hint.shape[0] != n:
            raise ValueError(
                f"partition_hint has {hint.shape[0]} entries, "
                f"topology has {n} vertices"
            )
        _, dense = np.unique(hint, return_inverse=True)
        return dense.astype(np.int32)
    if max_part is None:
        if n_parts is None or n_parts < 1:
            raise ValueError("need n_parts or max_part for a flat cut")
        max_part = -(-n // int(n_parts))
    max_part = max(int(max_part), 1)
    indptr, nbrs = _undirected_adjacency(n, topo.edge_src, topo.edge_dst)
    part = np.full(n, -1, np.int32)
    next_part = 0
    cursor = 0  # lowest possibly-unassigned vertex
    while cursor < n:
        if part[cursor] >= 0:
            cursor += 1
            continue
        # BFS-grow one region from the seed until the size target.
        frontier = [cursor]
        part[cursor] = next_part
        size = 1
        while frontier and size < max_part:
            nxt: list[int] = []
            for v in frontier:
                for u in nbrs[indptr[v]: indptr[v + 1]]:
                    if part[u] < 0:
                        part[u] = next_part
                        nxt.append(int(u))
                        size += 1
                        if size >= max_part:
                            break
                if size >= max_part:
                    break
            frontier = nxt
        next_part += 1
    # Fragment cleanup: greedy growth strands leftover vertices whose
    # neighbors were all claimed (classic first-pass artifact) as tiny
    # regions that would bloat the skeleton.  Deterministically merge
    # every undersized region into its most-connected neighbor region
    # (ties -> lowest region id), smallest regions first.
    min_size = max(2, max_part // 4)
    sizes = np.bincount(part, minlength=next_part).astype(np.int64)
    esrc_p = part[topo.edge_src]
    edst_p = part[topo.edge_dst]
    alive = sizes > 0
    for _ in range(next_part):
        small = [
            p for p in range(next_part)
            if alive[p] and sizes[p] < min_size
        ]
        if not small:
            break
        p = min(small, key=lambda q: (sizes[q], q))
        cut = esrc_p != edst_p
        touch = np.concatenate(
            [edst_p[cut & (esrc_p == p)], esrc_p[cut & (edst_p == p)]]
        )
        if touch.shape[0] == 0:
            # Isolated component: nothing to merge into — keep it.
            alive[p] = False
            continue
        counts = np.bincount(touch, minlength=next_part)
        target = int(np.argmax(counts))  # argmax: lowest id wins ties
        part[part == p] = target
        esrc_p = part[topo.edge_src]
        edst_p = part[topo.edge_dst]
        sizes[target] += sizes[p]
        sizes[p] = 0
        alive[p] = False
    # Dense ids in ascending surviving-region order.
    _, dense = np.unique(part, return_inverse=True)
    return dense.astype(np.int32)


def bandwidth_permutation(
    n: int, edge_src: np.ndarray, edge_dst: np.ndarray
) -> np.ndarray:
    """Reverse Cuthill-McKee ordering: int32[n] ``perm`` with
    ``perm[new] = old`` — relabeling vertices by it clusters each
    vertex's neighbors into nearby indices, which cuts off-diagonal
    block fill-in in blocked (tile) layouts and shrinks the butterfly
    working set of banded gathers.  Deterministic: components start at
    their minimum-degree (then lowest-id) vertex in ascending id order,
    BFS visits neighbors in ascending (degree, id) order, and the final
    order is reversed (the classic RCM profile reduction).
    """
    indptr, nbrs = _undirected_adjacency(
        n, np.asarray(edge_src), np.asarray(edge_dst)
    )
    deg = np.diff(indptr)
    seen = np.zeros(n, bool)
    chunks: list[np.ndarray] = []
    # Component seeds in ascending (degree, id) order.  BFS levels are
    # processed whole (vectorized — this runs on the tile/partition
    # marshal path at 100k+ vertices): each unseen child joins at its
    # FIRST parent's rank and a level orders by (parent rank, degree,
    # id), which is exactly the classic per-vertex FIFO expansion with
    # per-parent (degree, id)-sorted children.
    seed_rank = np.lexsort((np.arange(n), deg))
    for s in seed_rank:
        if seen[s]:
            continue
        seen[s] = True
        frontier = np.asarray([s], np.int64)
        chunks.append(frontier)
        while frontier.shape[0]:
            counts = indptr[frontier + 1] - indptr[frontier]
            total = int(counts.sum())
            if total == 0:
                break
            # Gather all frontier out-neighbors (ragged -> flat).
            flat = np.repeat(
                indptr[frontier] - np.concatenate(
                    [[0], np.cumsum(counts)[:-1]]
                ),
                counts,
            ) + np.arange(total)
            childs = nbrs[flat].astype(np.int64)
            prank = np.repeat(np.arange(frontier.shape[0]), counts)
            fresh = ~seen[childs]
            childs, prank = childs[fresh], prank[fresh]
            if childs.shape[0] == 0:
                break
            # First-parent assignment: minimal rank per child.
            first = np.lexsort((prank, childs))
            childs, prank = childs[first], prank[first]
            keep = np.ones(childs.shape[0], bool)
            keep[1:] = childs[1:] != childs[:-1]
            childs, prank = childs[keep], prank[keep]
            level = childs[np.lexsort((childs, deg[childs], prank))]
            seen[level] = True
            chunks.append(level)
            frontier = level
    order = np.concatenate(chunks) if chunks else np.empty(0, np.int64)
    return order[::-1].astype(np.int32)


#: The most edge operations one :class:`TopologyDelta` carries: a larger
#: change is refused (a full re-marshal is the cheaper path anyway).
#: The delta scatter and the incremental kernel's seed rows are padded
#: to exactly this many rows (``ops/spf_engine.py _DELTA_PAD_FLOOR``),
#: so every delta this module can return rides ONE compiled program
#: pair per graph shape.
DELTA_MAX_OPS = 512

_DIFF_TOTAL = telemetry.counter(
    "holo_spf_delta_diff_total",
    "diff_topologies calls by the path that answered: the pure-weight "
    "fast path, the general edge multiset diff, or a refusal (None)",
    ("path",),
)
_DIFF_OPS = telemetry.histogram(
    "holo_spf_delta_ops",
    "edge operations of each delta diff_topologies returned or refused "
    "for its size (the early edge-count refusal observes the gap, a "
    "lower bound; a refusal for another vertex model has no count and "
    "is not observed)",
    buckets=(0, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 4096),
)


def _pack_i32_pairs(hi: np.ndarray, lo: np.ndarray) -> np.ndarray:
    """One int64 key per (hi, lo) int32 pair whose signed order is the
    lexicographic signed order of the pairs: exact for every int32
    (``lo`` is biased into [0, 2**32), so atom -1 sorts before 0)."""
    return (hi.astype(np.int64) << 32) + (lo.astype(np.int64) + (1 << 31))


def _unpack_i32_pairs(key: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Inverse of :func:`_pack_i32_pairs`."""
    hi = (key >> 32).astype(np.int32)
    lo = ((key & 0xFFFFFFFF) - (1 << 31)).astype(np.int32)
    return hi, lo


def _edge_multiset_diff(
    base: Topology, new: Topology
) -> tuple[tuple[np.ndarray, ...], tuple[np.ndarray, ...]]:
    """``(removed, added)`` rows ``(src, dst, cost, atom)`` of the
    multiset difference of two edge lists, each side in lexicographic
    row order with multiplicities repeated.

    1-D sorts over packed keys (ISSUE 26), never a comparison sort of
    16-byte rows: ``k1 = (src, dst)`` and ``k2 = (cost, atom)`` as
    int64.  One stable argsort by ``k1`` puts a row of ``base`` next to
    its unchanged twin in ``new`` (LSDB builders emit both lists in the
    same src-grouped order, so the sort is close to a merge); such
    adjacent base/new pairs with equal keys cancel — removing one equal
    element from each multiset leaves their difference as it was.  The
    few rows left are lex-sorted on ``(k1, k2)`` and signed-counted per
    distinct row.
    """
    nb = base.n_edges
    k1 = np.concatenate([
        _pack_i32_pairs(base.edge_src, base.edge_dst),
        _pack_i32_pairs(new.edge_src, new.edge_dst),
    ])
    k2 = np.concatenate([
        _pack_i32_pairs(base.edge_cost, base.edge_direct_atom),
        _pack_i32_pairs(new.edge_cost, new.edge_direct_atom),
    ])
    order = np.argsort(k1, kind="stable")
    s1, s2, is_new = k1[order], k2[order], order >= nb
    twin = (
        ~is_new[:-1] & is_new[1:]
        & (s1[:-1] == s1[1:]) & (s2[:-1] == s2[1:])
    )
    unpaired = np.ones(order.shape[0], bool)
    unpaired[:-1] &= ~twin
    unpaired[1:] &= ~twin
    left = np.flatnonzero(unpaired)
    s1, s2, is_new = s1[left], s2[left], is_new[left]
    order = np.lexsort((s2, s1))
    s1, s2 = s1[order], s2[order]
    sign = np.where(is_new[order], -1, 1)
    first = np.ones(order.shape[0], bool)
    first[1:] = (s1[1:] != s1[:-1]) | (s2[1:] != s2[:-1])
    starts = np.flatnonzero(first)
    count = np.add.reduceat(sign, starts)

    def rows(times: np.ndarray) -> tuple[np.ndarray, ...]:
        at = np.repeat(starts, times)
        return (*_unpack_i32_pairs(s1[at]), *_unpack_i32_pairs(s2[at]))

    return rows(np.maximum(count, 0)), rows(np.maximum(-count, 0))


def diff_topologies(
    base: Topology, new: Topology, max_ops: int = DELTA_MAX_OPS
) -> TopologyDelta | None:
    """Compute a :class:`TopologyDelta` taking ``base`` to ``new``, or
    None when the change is not delta-representable (different vertex
    model, or more than ``max_ops`` edge operations — at which point a
    full re-marshal is the cheaper path anyway).

    Vertex identity is positional: callers at the LSDB seam must only
    diff topologies built over the SAME vertex ordering (same
    router/network index maps) and the same next-hop atom table —
    :func:`holo_tpu.protocols.ospf.spf_run.link_spf_delta` checks that
    before calling here.

    Every call lands once in ``holo_spf_delta_diff_total{path}``:
    ``weights`` (identical edge list, costs differ), ``edges`` (the
    general multiset diff) or ``refused`` (None returned), and its
    operation count once in the histogram ``holo_spf_delta_ops``.
    """
    delta, n_ops = _diff_topologies(base, new, max_ops)
    if n_ops is not None:
        _DIFF_OPS.observe(n_ops)
    # ids_stable is set by the fast path and by no other.
    path = (
        "refused" if delta is None
        else "weights" if delta.ids_stable else "edges"
    )
    _DIFF_TOTAL.labels(path=path).inc()
    return delta


def _diff_topologies(
    base: Topology, new: Topology, max_ops: int
) -> tuple[TopologyDelta | None, int | None]:
    """:func:`diff_topologies` without the counters: ``(delta or None,
    the operations counted, None where nothing was counted)``."""
    if (
        base.n_vertices != new.n_vertices
        or base.root != new.root
        or not np.array_equal(base.is_router, new.is_router)
    ):
        return None, None
    # A changed native partition hint changes the cut geometry the
    # partitioned-SPF resident was planned over (ISSUE 15) — not
    # delta-representable; re-marshal.
    bh, nh = base.partition_hint, new.partition_hint
    if (bh is None) != (nh is None) or (
        bh is not None and not np.array_equal(bh, nh)
    ):
        return None, None
    if base.n_edges == new.n_edges and (
        np.array_equal(base.edge_src, new.edge_src)
        and np.array_equal(base.edge_dst, new.edge_dst)
        and np.array_equal(base.edge_direct_atom, new.edge_direct_atom)
    ):
        # Fast path: identical edge list (and ordering) — a pure weight
        # delta, edge indices remain valid for mask consumers.
        changed = np.nonzero(base.edge_cost != new.edge_cost)[0]
        n_ops = int(changed.shape[0])
        if n_ops > max_ops:
            return None, n_ops
        return TopologyDelta(
            base_key=base.cache_key,
            w_src=base.edge_src[changed].copy(),
            w_dst=base.edge_dst[changed].copy(),
            w_old=base.edge_cost[changed].copy(),
            w_new=new.edge_cost[changed].copy(),
            w_atom=base.edge_direct_atom[changed].copy(),
            ids_stable=True,
        ), n_ops
    # General path: multiset difference over (src, dst, cost, atom)
    # rows.  A moved/re-costed edge shows up as one removal plus one
    # addition — the slot machinery frees then reuses the ELL slot.
    # Cheap early-out before the O(E) work: the edge-count gap is a
    # lower bound on the op count.
    gap = abs(base.n_edges - new.n_edges)
    if gap > max_ops:
        return None, gap
    # Vectorized (this runs on the per-SPF hot path for exactly the
    # large topologies DeltaPath targets — no Python loop over E), and
    # in the row order ``np.unique(axis=0)`` gave before ISSUE 26:
    # _lower_delta hands out ELL slots in the order additions arrive.
    r, a = _edge_multiset_diff(base, new)
    n_ops = int(r[0].shape[0] + a[0].shape[0])
    if n_ops > max_ops:
        return None, n_ops
    return TopologyDelta(
        base_key=base.cache_key,
        r_src=r[0], r_dst=r[1], r_cost=r[2], r_atom=r[3],
        a_src=a[0], a_dst=a[1], a_cost=a[2], a_atom=a[3],
        ids_stable=False,
    ), n_ops
