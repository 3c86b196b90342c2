"""Hierarchical partitioned SPF (ISSUE 15, ROADMAP item 2).

Instead of one monolithic padded program over the full vertex axis, the
topology is cut into P partitions (native OSPF-area / IS-IS-level
structure via ``Topology.partition_hint``, or the deterministic
BFS/greedy cut of :func:`holo_tpu.ops.graph.partition_topology` for
flat graphs) and solved in three exact phases:

1. **Boundary solve** — every partition relaxes distances from each of
   its *skeleton* vertices (endpoints of cut edges, plus the root)
   restricted to intra-partition edges: ONE batched dispatch (vmap over
   the partition axis, root axis chunked) of small shape-stable
   programs.  Halo rows (external cut-edge sources) carry no in-edge
   slots, so they stay INF and the solve is intra-partition by
   construction.
2. **Skeleton stitch** — a contracted graph over the skeleton vertices:
   intra-partition boundary-to-boundary distances become edges, cut
   edges join verbatim, and one small host Dijkstra (exact int
   arithmetic, the scalar oracle's semantics) yields the exact global
   distance of every skeleton vertex.  Exactness is the classic
   contraction argument: between consecutive cut-edge crossings a
   shortest path stays inside one partition, so it decomposes into
   skeleton hops the contracted graph represents at exactly its cost.
3. **Final solve** — each partition relaxes seeded with the exact
   skeleton distances (own skeleton rows + pinned halo rows), giving
   exact distances everywhere; parents are closed-form (lex-min over
   ``(path cost, GLOBAL id)`` so the reference tie-break survives
   relabeling); hops / next-hop words (and the ``k>1`` multipath
   npaths / UCMP planes) reconverge through the shared per-round
   recompute formulas with halo lanes PINNED to exchanged values — the
   host outer loop re-dispatches until the skeleton value table is
   stable, which (acyclic DAG, unique fixpoint) is bit-identical to
   the monolithic kernels and the scalar oracle.

DeltaPath composes (Bounded-Dijkstra radius cut): a delta's seed rows
name the touched partitions; only those re-run the boundary solve, the
skeleton re-stitches on the host, and the final solve re-dispatches
only partitions whose seeds or exchanged halo values actually changed
— pow2-bucketed partition subsets, so the re-solve is bounded by the
affected region, not the graph.

Local vertex order inside each partition is the RCM bandwidth
permutation (:func:`holo_tpu.ops.graph.bandwidth_permutation`) — the
ISSUE 15 satellite — applied and inverted entirely inside the marshal:
all external ids (results, parents, edge ids) are global and unchanged.
"""

from __future__ import annotations

import heapq
import time
from dataclasses import dataclass, field
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from holo_tpu import telemetry
from holo_tpu.analysis.runtime import note_donated, sanctioned_transfer
from holo_tpu.ops.graph import (
    INF,
    MP_SAT,
    Topology,
    TopologyDelta,
    bandwidth_permutation,
    partition_topology,
)
from holo_tpu.ops.spf_engine import _nh_words_round

_PART_STAGES = telemetry.counter(
    "holo_spf_partition_total",
    "Partitioned-SPF stage dispatches (batched partition programs, "
    "skeleton stitches, exchange rounds, delta dispositions)",
    ("stage",),
)
_PART_PARTS = telemetry.gauge(
    "holo_spf_partition_parts", "Partitions of the last partitioned solve"
)
_PART_SKEL = telemetry.gauge(
    "holo_spf_partition_skeleton",
    "Skeleton (boundary-contraction) vertices of the last solve",
)
_PART_ROUNDS = telemetry.gauge(
    "holo_spf_partition_exchange_rounds",
    "Halo-exchange outer rounds of the last partitioned phase 2",
)
_PART_RESOLVED = telemetry.gauge(
    "holo_spf_partition_resolved",
    "Partitions re-solved by the last partitioned dispatch (full solve: "
    "all of them; DeltaPath: the affected set + changed-seed closure)",
)


def note_partition(stage: str) -> None:
    _PART_STAGES.labels(stage=stage).inc()


def _pow2(n: int, floor: int = 1) -> int:
    out = max(int(floor), 1)
    while out < n:
        out *= 2
    return out


class PartPlanes(NamedTuple):
    """Stacked per-partition device planes (pure-array pytree).

    Leading axis P (pow2-padded partition count); L the common padded
    local vertex axis (own vertices in RCM order, then halo rows, then
    pads); K the common padded in-edge slot axis.  Halo and pad rows
    carry no slots.  ``gid`` maps local rows to GLOBAL vertex ids
    (sentinel N for pads) — every exported quantity (parents, exchange
    values) is in global id space, so local relabeling never leaks.
    """

    in_src: jax.Array  # int32[P, L, K] local source row of slot
    in_cost: jax.Array  # int32[P, L, K]
    in_valid: jax.Array  # bool[P, L, K]
    in_edge_id: jax.Array  # int32[P, L, K] GLOBAL edge index (0 pads)
    direct_words: jax.Array  # uint32[P, L, K, W]
    is_router: jax.Array  # bool[P, L]
    gid: jax.Array  # int32[P, L]; N for pads
    own: jax.Array  # bool[P, L] own vertex (not halo/pad)
    pinned: jax.Array  # bool[P, L] halo row (pinned lanes)
    root_local: jax.Array  # int32[P]; L sentinel = root not here
    bnd_local: jax.Array  # int32[P, Bp] own skeleton rows; L sentinel


@dataclass
class PartitionPlan:
    """Host-side partition/skeleton geometry (marshal-time product)."""

    n_vertices: int
    n_parts: int
    root: int
    part_of: np.ndarray  # int32[N]
    local_of: np.ndarray  # int32[N] local row in the owning partition
    verts: list  # [P] int32[n_own] global ids in local (RCM) order
    halo: list  # [P] int32[n_halo] global ids (ascending)
    skel: np.ndarray  # int32[S] global skeleton ids (ascending)
    skel_pos: np.ndarray  # int32[N]: index into skel, -1 otherwise
    bnd: list  # [P] int32[B_p] own skeleton ids (ascending)
    cut_src: np.ndarray  # int32[C] cut edges (global)
    cut_dst: np.ndarray
    cut_cost: np.ndarray
    cut_eid: np.ndarray  # global edge indices of cut edges
    l_pad: int = 0
    k_pad: int = 0
    b_pad: int = 0
    p_pad: int = 0
    # per-partition skeleton positions (host exchange bookkeeping)
    bnd_skel: list = field(default_factory=list)  # [P] positions in skel
    halo_skel: list = field(default_factory=list)

    @property
    def n_skel(self) -> int:
        return int(self.skel.shape[0])


def build_plan(
    topo: Topology,
    n_parts: int | None = None,
    max_part: int | None = None,
    part_of: np.ndarray | None = None,
) -> PartitionPlan:
    """Cut the topology and derive the partition/skeleton geometry.

    ``part_of`` overrides the cut (tests / fuzzing); otherwise the
    native ``partition_hint`` or the deterministic BFS/greedy cut
    decides (:func:`partition_topology`).
    """
    n = topo.n_vertices
    if part_of is None:
        part_of = partition_topology(topo, n_parts=n_parts, max_part=max_part)
    part_of = np.asarray(part_of, np.int32)
    n_p = int(part_of.max()) + 1 if n else 1

    cut = part_of[topo.edge_src] != part_of[topo.edge_dst]
    cut_idx = np.nonzero(cut)[0].astype(np.int32)
    skel = np.unique(
        np.concatenate(
            [
                topo.edge_src[cut_idx],
                topo.edge_dst[cut_idx],
                np.asarray([topo.root], np.int32),
            ]
        )
    ).astype(np.int32)
    skel_pos = np.full(n, -1, np.int32)
    skel_pos[skel] = np.arange(skel.shape[0], dtype=np.int32)

    verts: list = []
    halo: list = []
    bnd: list = []
    local_of = np.full(n, -1, np.int32)
    halo_dst_part = part_of[topo.edge_dst[cut_idx]]
    for p in range(n_p):
        own = np.nonzero(part_of == p)[0].astype(np.int32)
        # RCM local order over the intra-partition subgraph: the
        # bandwidth-reducing relabeling (ISSUE 15 satellite) — purely
        # internal, results map back through gid.
        intra = (part_of[topo.edge_src] == p) & (part_of[topo.edge_dst] == p)
        g2l = np.full(n, -1, np.int64)
        g2l[own] = np.arange(own.shape[0])
        perm = bandwidth_permutation(
            own.shape[0],
            g2l[topo.edge_src[intra]],
            g2l[topo.edge_dst[intra]],
        )
        own = own[perm]
        verts.append(own)
        local_of[own] = np.arange(own.shape[0], dtype=np.int32)
        h = np.unique(topo.edge_src[cut_idx[halo_dst_part == p]]).astype(
            np.int32
        )
        halo.append(h)
        bnd.append(skel[part_of[skel] == p])

    for p in range(n_p):
        # Every halo vertex must own a local row in its home partition
        # (the exchange tables index through it).
        if halo[p].shape[0] and (local_of[halo[p]] < 0).any():
            raise AssertionError("halo vertex without a local row")
    plan = PartitionPlan(
        n_vertices=n,
        n_parts=n_p,
        root=int(topo.root),
        part_of=part_of,
        local_of=local_of,
        verts=verts,
        halo=halo,
        skel=skel,
        skel_pos=skel_pos,
        bnd=bnd,
        cut_src=topo.edge_src[cut_idx].copy(),
        cut_dst=topo.edge_dst[cut_idx].copy(),
        cut_cost=topo.edge_cost[cut_idx].copy(),
        cut_eid=cut_idx,
    )
    plan.l_pad = _pow2(
        max((verts[p].shape[0] + halo[p].shape[0]) for p in range(n_p)),
        floor=8,
    )
    plan.b_pad = _pow2(max(max(b.shape[0] for b in bnd), 1), floor=1)
    plan.p_pad = _pow2(n_p)
    plan.bnd_skel = [skel_pos[b].astype(np.int32) for b in bnd]
    plan.halo_skel = [skel_pos[h].astype(np.int32) for h in halo]
    if any((hs < 0).any() for hs in plan.halo_skel):
        raise AssertionError("halo vertex outside the skeleton")
    return plan


class _PartMirror:
    """Host mirror of the stacked local ELL occupancy — the partition
    analog of ``spf_engine._EllMirror`` (delta lowering without device
    readbacks).  Owns copies; mutates under deltas."""

    def __init__(self, in_src, in_cost, in_valid, in_atom):
        self.in_src = in_src.copy()
        self.in_cost = in_cost.copy()
        self.in_valid = in_valid.copy()
        self.in_atom = in_atom.copy()


class _PartUnappliable(Exception):
    def __init__(self, reason: str):
        super().__init__(reason)
        self.reason = reason


def marshal_partitions(
    topo: Topology, plan: PartitionPlan, n_atoms: int
) -> tuple[PartPlanes, _PartMirror]:
    """Expand the topology into stacked per-partition ELL planes
    (numpy; the caller device-places them inside its sanctioned marshal
    window).  Every edge lands in the partition of its DESTINATION:
    intra-partition edges with local sources, cut edges with halo-row
    sources.  Shapes are common pow2 buckets so the whole partition set
    is ONE program."""
    t0 = time.perf_counter()
    n = topo.n_vertices
    n_p, P = plan.n_parts, plan.p_pad
    # Common slot width: max local in-degree over all partitions.
    dst_part = plan.part_of[topo.edge_dst]
    counts = np.zeros(n, np.int64)
    np.add.at(counts, topo.edge_dst, 1)
    kmax = int(counts.max()) if topo.n_edges else 1
    k_pad = max(((max(kmax, 1) + 7) // 8) * 8, 8)
    plan.k_pad = k_pad
    L = plan.l_pad
    w = max((n_atoms + 31) // 32, 1)

    in_src = np.zeros((P, L, k_pad), np.int32)
    in_cost = np.zeros((P, L, k_pad), np.int32)
    in_valid = np.zeros((P, L, k_pad), bool)
    in_eid = np.zeros((P, L, k_pad), np.int32)
    in_atom = np.full((P, L, k_pad), -1, np.int32)
    gid = np.full((P, L), n, np.int32)
    own = np.zeros((P, L), bool)
    pinned = np.zeros((P, L), bool)
    is_router = np.zeros((P, L), bool)
    root_local = np.full(P, L, np.int32)
    bnd_local = np.full((P, plan.b_pad), L, np.int32)

    # Global -> local row (own rows via local_of; halo rows per part).
    for p in range(n_p):
        n_own = plan.verts[p].shape[0]
        gid[p, :n_own] = plan.verts[p]
        own[p, :n_own] = True
        is_router[p, :n_own] = topo.is_router[plan.verts[p]]
        h = plan.halo[p]
        gid[p, n_own: n_own + h.shape[0]] = h
        pinned[p, n_own: n_own + h.shape[0]] = True
        is_router[p, n_own: n_own + h.shape[0]] = topo.is_router[h]
        if plan.part_of[plan.root] == p:
            root_local[p] = plan.local_of[plan.root]
        bl = plan.local_of[plan.bnd[p]]
        bnd_local[p, : bl.shape[0]] = bl

    # Edge bucketing (vectorized per partition).
    if topo.n_edges:
        eidx = np.arange(topo.n_edges, dtype=np.int64)
        for p in range(n_p):
            sel = eidx[dst_part == p]
            if sel.shape[0] == 0:
                continue
            dst_l = plan.local_of[topo.edge_dst[sel]].astype(np.int64)
            src_g = topo.edge_src[sel]
            src_part = plan.part_of[src_g]
            src_l = plan.local_of[src_g].astype(np.int64)
            # Cut-edge sources sit on halo rows.
            ext = src_part != p
            if ext.any():
                n_own = plan.verts[p].shape[0]
                hpos = np.searchsorted(plan.halo[p], src_g[ext])
                src_l[ext] = n_own + hpos
            order = np.argsort(dst_l, kind="stable")
            d_s = dst_l[order]
            first = np.searchsorted(d_s, d_s, side="left")
            slots = np.arange(sel.shape[0], dtype=np.int64) - first
            in_src[p, d_s, slots] = src_l[order]
            in_cost[p, d_s, slots] = topo.edge_cost[sel][order]
            in_valid[p, d_s, slots] = True
            in_eid[p, d_s, slots] = sel[order].astype(np.int32)
            in_atom[p, d_s, slots] = topo.edge_direct_atom[sel][order]

    words = np.zeros((P, L, k_pad, w), np.uint32)
    hasa = in_atom >= 0
    pp, rr, cc = np.nonzero(hasa)
    a = in_atom[pp, rr, cc]
    words[pp, rr, cc, a // 32] = np.uint32(1) << (a % 32).astype(np.uint32)

    planes = PartPlanes(
        in_src=in_src,
        in_cost=in_cost,
        in_valid=in_valid,
        in_edge_id=in_eid,
        direct_words=words,
        is_router=is_router,
        gid=gid,
        own=own,
        pinned=pinned,
        root_local=root_local,
        bnd_local=bnd_local,
    )
    mirror = _PartMirror(in_src, in_cost, in_valid, in_atom)
    note_partition("marshal")
    telemetry.histogram(
        "holo_spf_partition_marshal_seconds",
        "Host-side partition marshal (stacked local ELL expansion)",
    ).observe(time.perf_counter() - t0)
    return planes, mirror


def place_planes(planes: PartPlanes) -> PartPlanes:
    """Device-place the stacked planes.  Under a live process mesh the
    partition axis rides the mesh's ``batch`` axis (the same axis the
    what-if scenario batch shards over) when it divides evenly; other
    shapes stay replicated — a placement choice, never a semantic one.
    Call inside the sanctioned marshal window."""
    from holo_tpu.parallel import mesh as _pm

    m = _pm.process_mesh()
    if m is not None and m.size > 1:
        nb = m.shape["batch"]
        if planes.in_src.shape[0] % nb == 0:
            return _pm.shard_part_planes(m, planes)
        return jax.device_put(planes, _pm.replicated_sharding(m))
    return jax.device_put(planes)


# -- kernels -------------------------------------------------------------


def _slot_ok(pl: PartPlanes, edge_mask):
    ok = pl.in_valid
    if edge_mask is not None and edge_mask.shape[0] > 0:
        ok = ok & edge_mask[pl.in_edge_id]
    return ok


def _relax_one(in_src, in_cost, ok, dist0, limit):
    """Seeded min-plus relaxation over one partition's local planes
    (the monolithic ``sssp_distances`` body, locally)."""

    def cond(carry):
        _, changed, it = carry
        return changed & (it < limit)

    def body(carry):
        dist, _, it = carry
        d_nbr = dist[in_src]
        usable = ok & (d_nbr < INF)
        cand = jnp.where(usable, d_nbr + in_cost, INF)
        new = jnp.minimum(dist, cand.min(axis=1))
        return new, jnp.any(new != dist), it + 1

    dist, _, _ = jax.lax.while_loop(cond, body, (dist0, jnp.bool_(True), 0))
    return dist


def boundary_dist_kernel(pl: PartPlanes, roots, edge_mask, limit):
    """Phase 1: intra-partition distances from a chunk of skeleton
    roots.  ``roots`` int32[P, C] local row ids (L sentinel = inactive
    lane).  Returns int32[P, C, Bp]: distances AT the partition's own
    skeleton rows (the skeleton edge weights)."""
    P, L, _ = pl.in_src.shape

    def per_part(in_src, in_cost, ok, rts, bnd):
        def per_root(r):
            dist0 = jnp.full((L,), INF, jnp.int32).at[r].set(
                0, mode="drop"
            )
            return _relax_one(in_src, in_cost, ok, dist0, limit)

        dist = jax.vmap(per_root)(rts)  # [C, L]
        bsafe = jnp.minimum(bnd, L - 1)
        out = dist[:, bsafe]  # [C, Bp]
        return jnp.where((bnd < L)[None, :], out, INF)

    ok = _slot_ok(pl, edge_mask)
    return jax.vmap(per_part)(
        pl.in_src, pl.in_cost, ok, roots, pl.bnd_local
    )


def final_dist_kernel(pl: PartPlanes, seed, edge_mask, limit):
    """Phase 3a: exact local distances from the skeleton-seeded state
    (halo rows have no slots, so their exact seeds are pinned free)."""
    ok = _slot_ok(pl, edge_mask)
    return jax.vmap(lambda s, c, o, d0: _relax_one(s, c, o, d0, limit))(
        pl.in_src, pl.in_cost, ok, seed
    )


def phase2_kernel(
    pl: PartPlanes,
    dist,
    hops_pin,
    nh_pin,
    edge_mask,
    n_global: int,
    limit,
):
    """Phase 3b: hops + next-hop words over settled distances, halo
    lanes pinned to the exchanged values.  Returns the full local
    planes plus the skeleton-row exports the host outer loop stitches.
    Bit-identical to the monolithic ``_hops_nh_fixpoint`` on
    convergence (acyclic DAG, unique fixpoint)."""
    P, L, K = pl.in_src.shape
    w = pl.direct_words.shape[3]
    big = jnp.int32(n_global + 1)
    ok = _slot_ok(pl, edge_mask)

    def per_part(
        in_src, in_cost, okl, words, is_router, gid, pinned, root_l,
        bnd, d, h_pin, n_pin,
    ):
        d_nbr = d[in_src]
        gid_nbr = gid[in_src]
        vrow = jnp.arange(L)
        not_root = vrow != root_l
        dag = (
            okl
            & (d_nbr < INF)
            & (d < INF)[:, None]
            & (d_nbr + in_cost == d[:, None])
            & not_root[:, None]
        )
        # First parent by the reference pop order on GLOBAL ids.
        dmin = jnp.where(dag, d_nbr, INF).min(axis=1)
        cand = jnp.where(
            dag & (d_nbr == dmin[:, None]), gid_nbr, n_global
        )
        parent_g = cand.min(axis=1).astype(jnp.int32)
        has_parent = parent_g < n_global
        parent_slot = gid_nbr == parent_g[:, None]
        inc = is_router.astype(jnp.int32)
        is_root_row = vrow == root_l
        direct_i32 = jax.lax.bitcast_convert_type(words, jnp.int32)

        def cond(carry):
            _, _, changed, it = carry
            return changed & (it < limit)

        def body(carry):
            hops, nh, _, it = carry
            state = jnp.concatenate([hops[:, None], nh], axis=1)
            nbr = state[in_src]  # [L, K, 1+W]
            h_nbr = nbr[:, :, 0]
            ph = jnp.where(parent_slot, h_nbr, big).min(axis=1)
            hops_new = jnp.where(
                is_root_row,
                0,
                jnp.where(has_parent & (ph < big), ph + inc, big),
            ).astype(jnp.int32)
            nh_new = _nh_words_round(
                dag, h_nbr, direct_i32, lambda wi: nbr[:, :, 1 + wi]
            )
            hops_new = jnp.where(pinned, h_pin, hops_new)
            nh_new = jnp.where(pinned[:, None], n_pin, nh_new)
            changed = jnp.any(hops_new != hops) | jnp.any(nh_new != nh)
            return hops_new, nh_new, changed, it + 1

        hops0 = jnp.where(is_root_row, 0, big).astype(jnp.int32)
        hops0 = jnp.where(pinned, h_pin, hops0)
        nh0 = jnp.where(pinned[:, None], n_pin, jnp.zeros((L, w), jnp.int32))
        hops, nh, _, _ = jax.lax.while_loop(
            cond, body, (hops0, nh0, jnp.bool_(True), 0)
        )
        bsafe = jnp.minimum(bnd, L - 1)
        exp_h = jnp.where(bnd < L, hops[bsafe], big)
        exp_n = jnp.where((bnd < L)[:, None], nh[bsafe], 0)
        return hops, nh, parent_g, exp_h, exp_n

    return jax.vmap(per_part)(
        pl.in_src, pl.in_cost, ok, pl.direct_words, pl.is_router,
        pl.gid, pl.pinned, pl.root_local, pl.bnd_local,
        dist, hops_pin, nh_pin,
    )


def phase2_mp_kernel(
    pl: PartPlanes,
    dist,
    hops_pin,
    nh_pin,
    np_pin,
    aw_pin,
    edge_mask,
    n_global: int,
    limit,
):
    """The multipath widening of :func:`phase2_kernel`: the packed
    state adds the saturated path counts and per-atom UCMP weight lanes
    (the monolithic ``_mp_fixpoint`` recursion), halo lanes pinned."""
    P, L, K = pl.in_src.shape
    w = pl.direct_words.shape[3]
    a_lanes = w * 32
    big = jnp.int32(n_global + 1)
    sat = jnp.int32(MP_SAT)
    ok = _slot_ok(pl, edge_mask)

    def per_part(
        in_src, in_cost, okl, words, is_router, gid, pinned, root_l,
        bnd, d, h_pin, n_pin, p_pin, w_pin,
    ):
        d_nbr = d[in_src]
        gid_nbr = gid[in_src]
        vrow = jnp.arange(L)
        not_root = vrow != root_l
        dag = (
            okl
            & (d_nbr < INF)
            & (d < INF)[:, None]
            & (d_nbr + in_cost == d[:, None])
            & not_root[:, None]
        )
        dmin = jnp.where(dag, d_nbr, INF).min(axis=1)
        cand = jnp.where(
            dag & (d_nbr == dmin[:, None]), gid_nbr, n_global
        )
        parent_g = cand.min(axis=1).astype(jnp.int32)
        has_parent = parent_g < n_global
        parent_slot = gid_nbr == parent_g[:, None]
        inc = is_router.astype(jnp.int32)
        is_root_row = vrow == root_l
        direct_i32 = jax.lax.bitcast_convert_type(words, jnp.int32)
        bits = jnp.arange(32, dtype=jnp.uint32)
        onehot = (
            (words[:, :, :, None] >> bits) & jnp.uint32(1)
        ).astype(jnp.int32).reshape(L, K, a_lanes)

        def cond(carry):
            _, _, _, _, changed, it = carry
            return changed & (it < limit)

        def body(carry):
            hops, nh, np_, aw, _, it = carry
            state = jnp.concatenate(
                [hops[:, None], np_[:, None], nh, aw], axis=1
            )
            nbr = state[in_src]  # [L, K, 2+W+A]
            h_nbr = nbr[:, :, 0]
            np_nbr = nbr[:, :, 1]
            ph = jnp.where(parent_slot, h_nbr, big).min(axis=1)
            hops_new = jnp.where(
                is_root_row,
                0,
                jnp.where(has_parent & (ph < big), ph + inc, big),
            ).astype(jnp.int32)
            nh_new = _nh_words_round(
                dag, h_nbr, direct_i32, lambda wi: nbr[:, :, 2 + wi]
            )
            np_sum = jnp.where(dag, np_nbr, 0).sum(axis=1)
            np_new = jnp.where(
                is_root_row, 1, jnp.minimum(np_sum, sat)
            ).astype(jnp.int32)
            direct_slot = (dag & (h_nbr == 0)).astype(jnp.int32)
            inherit_slot = (dag & (h_nbr != 0)).astype(jnp.int32)
            aw_nbr = nbr[:, :, 2 + w:]
            contrib = (
                onehot * (np_nbr * direct_slot)[:, :, None]
                + aw_nbr * inherit_slot[:, :, None]
            )
            aw_new = jnp.minimum(contrib.sum(axis=1), sat).astype(
                jnp.int32
            )
            hops_new = jnp.where(pinned, h_pin, hops_new)
            nh_new = jnp.where(pinned[:, None], n_pin, nh_new)
            np_new = jnp.where(pinned, p_pin, np_new)
            aw_new = jnp.where(pinned[:, None], w_pin, aw_new)
            changed = (
                jnp.any(hops_new != hops)
                | jnp.any(nh_new != nh)
                | jnp.any(np_new != np_)
                | jnp.any(aw_new != aw)
            )
            return hops_new, nh_new, np_new, aw_new, changed, it + 1

        hops0 = jnp.where(is_root_row, 0, big).astype(jnp.int32)
        hops0 = jnp.where(pinned, h_pin, hops0)
        nh0 = jnp.where(pinned[:, None], n_pin, jnp.zeros((L, w), jnp.int32))
        np0 = jnp.where(is_root_row, 1, 0).astype(jnp.int32)
        np0 = jnp.where(pinned, p_pin, np0)
        aw0 = jnp.where(
            pinned[:, None], w_pin, jnp.zeros((L, a_lanes), jnp.int32)
        )
        hops, nh, np_, aw, _, _ = jax.lax.while_loop(
            cond, body, (hops0, nh0, np0, aw0, jnp.bool_(True), 0)
        )
        bsafe = jnp.minimum(bnd, L - 1)
        bvalid = bnd < L
        exp = (
            jnp.where(bvalid, hops[bsafe], big),
            jnp.where(bvalid[:, None], nh[bsafe], 0),
            jnp.where(bvalid, np_[bsafe], 0),
            jnp.where(bvalid[:, None], aw[bsafe], 0),
        )
        return hops, nh, np_, aw, parent_g, exp

    return jax.vmap(per_part)(
        pl.in_src, pl.in_cost, ok, pl.direct_words, pl.is_router,
        pl.gid, pl.pinned, pl.root_local, pl.bnd_local,
        dist, hops_pin, nh_pin, np_pin, aw_pin,
    )


def mp_sets_kernel(pl: PartPlanes, dist, npaths, edge_mask, n_global, kp):
    """Closed-form multipath parent-set extraction in GLOBAL id space
    (the monolithic ``_mp_parent_sets``, locally): kp rounds of masked
    lex-min over (path cost, global source id), retiring every slot of
    the emitted source."""
    ok = _slot_ok(pl, edge_mask)

    def per_part(in_src, in_cost, okl, gid, root_l, d, np_):
        L = in_src.shape[0]
        d_nbr = d[in_src]
        gid_nbr = gid[in_src]
        not_root = (jnp.arange(L) != root_l)[:, None]
        reach = (d < INF)[:, None]
        dag = (
            okl & (d_nbr < INF) & reach
            & (d_nbr + in_cost == d[:, None]) & not_root
        )
        divers = (
            okl & (d_nbr < INF) & reach & (d_nbr < d[:, None]) & not_root
        )
        adm = dag | divers
        pathcost = jnp.where(adm, d_nbr + in_cost, INF)
        np_nbr = np_[in_src]
        parents, pdists, pweights = [], [], []
        remaining = adm
        for _ in range(kp):
            cmin = jnp.where(remaining, pathcost, INF).min(axis=1)
            tie = remaining & (pathcost == cmin[:, None])
            smin = jnp.where(tie, gid_nbr, n_global).min(axis=1)
            has = cmin < INF
            parents.append(
                jnp.where(has, smin, n_global).astype(jnp.int32)
            )
            pdists.append(jnp.where(has, cmin, INF).astype(jnp.int32))
            sel = tie & (gid_nbr == smin[:, None])
            pweights.append(
                jnp.where(
                    has, jnp.where(sel, np_nbr, 0).max(axis=1), 0
                ).astype(jnp.int32)
            )
            remaining = remaining & (gid_nbr != smin[:, None])
        return (
            jnp.stack(parents, axis=1),
            jnp.stack(pdists, axis=1),
            jnp.stack(pweights, axis=1),
        )

    return jax.vmap(per_part)(
        pl.in_src, pl.in_cost, ok, pl.gid, pl.root_local, dist, npaths
    )


def gather_parts_kernel(pl: PartPlanes, idx):
    """Device gather of a pow2-padded partition subset (the DeltaPath
    bounded re-solve): lane i of the result is partition ``idx[i]``
    (repeats allowed — pad entries repeat lane 0, the caller ignores
    them)."""
    return jax.tree.map(lambda x: x[idx], pl)


def apply_part_delta_kernel(pl: PartPlanes, part, row, col, src, cost, valid, words):
    """Scatter a lowered delta into the stacked planes (jitted with the
    planes DONATED — the in-place DeltaPath update, partition edition).
    Pad ops carry an out-of-range partition index and drop."""
    in_src = pl.in_src.at[part, row, col].set(src, mode="drop")
    in_cost = pl.in_cost.at[part, row, col].set(cost, mode="drop")
    in_valid = pl.in_valid.at[part, row, col].set(valid, mode="drop")
    dw = pl.direct_words.at[part, row, col].set(words, mode="drop")
    return pl._replace(
        in_src=in_src, in_cost=in_cost, in_valid=in_valid,
        direct_words=dw,
    )


# -- skeleton stitch (host) ---------------------------------------------


def skeleton_solve(
    plan: PartitionPlan,
    btab: np.ndarray,
    cut_mask: np.ndarray | None = None,
) -> np.ndarray:
    """Exact skeleton distances from the root (host Dijkstra over the
    contracted graph).  ``btab`` int64[P, Bp, Bp]: intra-partition
    distances between each partition's own skeleton vertices (row =
    source).  Cut edges join verbatim (``cut_mask`` masks failed ones,
    the what-if arm).  Returns int64[S] (INF unreachable)."""
    S = plan.n_skel
    inf = int(INF)
    adj: list[list[tuple[int, int]]] = [[] for _ in range(S)]
    for p in range(plan.n_parts):
        pos = plan.bnd_skel[p]
        b = pos.shape[0]
        tab = btab[p, :b, :b]
        for i in range(b):
            row = tab[i]
            for j in range(b):
                wgt = int(row[j])
                if i != j and wgt < inf:
                    adj[int(pos[i])].append((int(pos[j]), wgt))
    for i in range(plan.cut_src.shape[0]):
        if cut_mask is not None and not cut_mask[i]:
            continue
        u = int(plan.skel_pos[plan.cut_src[i]])
        v = int(plan.skel_pos[plan.cut_dst[i]])
        adj[u].append((v, int(plan.cut_cost[i])))
    dist = np.full(S, inf, np.int64)
    root_pos = int(plan.skel_pos[plan.root])
    dist[root_pos] = 0
    heap = [(0, root_pos)]
    while heap:
        d, v = heapq.heappop(heap)
        if d > dist[v]:
            continue
        for u, wgt in adj[v]:
            nd = d + wgt
            if nd < dist[u]:
                dist[u] = nd
                heapq.heappush(heap, (nd, u))
    note_partition("skeleton")
    return dist


# -- orchestration -------------------------------------------------------


@dataclass
class PartResident:
    """A topology's partitioned device residency + the host solve state
    DeltaPath re-solves incrementally from."""

    plan: PartitionPlan
    planes: PartPlanes  # device
    mirror: _PartMirror
    n_atoms: int
    topo_key: tuple  # (uid, generation) the planes serve
    # Host copies of the static geometry planes (assembly/seed builds).
    gid: np.ndarray = None  # int32[P, L]
    own: np.ndarray = None  # bool[P, L]
    halo_rows: list = None  # [P] int32[n_halo] local rows of halo verts
    # Last unmasked-solve state (None until solve() ran).
    kp: int = 1
    btab: np.ndarray | None = None  # int64[P, Bp, Bp]
    skel_dist: np.ndarray | None = None  # int64[S]
    dist_loc: np.ndarray | None = None  # int32[P, L]
    hops_loc: np.ndarray | None = None
    nh_loc: np.ndarray | None = None
    parent_loc: np.ndarray | None = None
    np_loc: np.ndarray | None = None
    aw_loc: np.ndarray | None = None
    mp_sets: tuple | None = None  # (parents, pdist, pweight) [P, L, Kp]
    hops_tab: np.ndarray | None = None  # int32[S]
    nh_tab: np.ndarray | None = None  # int32[S, W]
    np_tab: np.ndarray | None = None
    aw_tab: np.ndarray | None = None
    last_resolved: int = 0
    exchange_rounds: int = 0
    delta_depth: int = 0
    # Structural deltas shift global edge ids; the stacked in_edge_id
    # planes then no longer serve mask consumers (what-if) — same
    # contract as DeviceGraphCache.ids_stale.
    ids_stale: bool = False
    # Per-phase walls of the last solve/delta.
    timings: dict = field(default_factory=dict)

    def stats(self) -> dict:
        return {
            "parts": self.plan.n_parts,
            "skeleton": self.plan.n_skel,
            "cut-edges": int(self.plan.cut_src.shape[0]),
            "l-pad": self.plan.l_pad,
            "b-pad": self.plan.b_pad,
            "resolved": self.last_resolved,
            "exchange-rounds": self.exchange_rounds,
            "delta-depth": self.delta_depth,
            "ids-stale": self.ids_stale,
        }


class PartitionedSpfEngine:
    """Partitioned-SPF orchestration: jit caches per shape bucket, the
    marshal/solve/delta entry points the backend dispatches through.

    Every device interaction runs inside the caller-visible sanctioned
    windows declared here (the partition analog of the backend's
    marshal/readback discipline); results come back as host numpy
    planes in GLOBAL vertex space, bit-identical to the monolithic
    kernels and the scalar oracle (the parity contract)."""

    #: outer-exchange hard cap multiplier (rounds are bounded by the
    #: skeleton's cut-crossing depth; the cap only guards a logic bug,
    #: and tripping it surfaces as a breaker-visible failure).
    EXCHANGE_CAP_SLACK = 4

    def __init__(self, max_iters: int | None = None, root_chunk: int = 16):
        self.max_iters = max_iters
        self.root_chunk = int(root_chunk)
        self._jits: dict[tuple, object] = {}
        self._apply_jit = None

    # -- jit plumbing ---------------------------------------------------

    def _jit(self, key: tuple, build):
        fn = self._jits.get(key)
        if fn is None:
            fn = self._jits[key] = build()
        return fn

    def _limit(self, plan: PartitionPlan) -> int:
        return plan.l_pad if self.max_iters is None else self.max_iters

    def _constrained(self, fn):
        """Wrap a kernel so its outputs are pinned to the partition-
        batch sharding under a live multi-device mesh (the what-if
        batch discipline, partition edition)."""
        from holo_tpu.parallel import mesh as _pm

        m = _pm.process_mesh()
        if m is None or m.size == 1:
            return fn

        def wrapped(*args):
            return _pm.constrain_parts(m, fn(*args))

        return wrapped

    # -- marshal --------------------------------------------------------

    def marshal(
        self,
        topo: Topology,
        n_atoms: int,
        n_parts: int | None = None,
        max_part: int | None = None,
        part_of: np.ndarray | None = None,
    ) -> PartResident:
        plan = build_plan(
            topo, n_parts=n_parts, max_part=max_part, part_of=part_of
        )
        host, mirror = marshal_partitions(topo, plan, n_atoms)
        with sanctioned_transfer("spf.partition.marshal"):
            planes = place_planes(host)
        halo_rows = [
            plan.verts[p].shape[0]
            + np.arange(plan.halo[p].shape[0], dtype=np.int32)
            for p in range(plan.n_parts)
        ]
        _PART_PARTS.set(plan.n_parts)
        _PART_SKEL.set(plan.n_skel)
        return PartResident(
            plan=plan,
            planes=planes,
            mirror=mirror,
            n_atoms=n_atoms,
            topo_key=topo.cache_key,
            gid=np.asarray(host.gid),
            own=np.asarray(host.own),
            halo_rows=halo_rows,
        )

    # -- phase helpers --------------------------------------------------

    def _root_chunks(self, plan: PartitionPlan, parts=None):
        """[(chunk int32[P|Sp, C], col0), ...] local-root chunks over
        the (sub)partition set's skeleton rows."""
        if parts is None:
            bnd = [plan.local_of[plan.bnd[p]] for p in range(plan.n_parts)]
            lanes = plan.n_parts
        else:
            bnd = [plan.local_of[plan.bnd[p]] for p in parts]
            lanes = len(parts)
        c = _pow2(min(self.root_chunk, plan.b_pad))
        chunks = []
        for col0 in range(0, plan.b_pad, c):
            arr = np.full((lanes, c), plan.l_pad, np.int32)
            any_root = False
            for i in range(lanes):
                seg = bnd[i][col0: col0 + c]
                if seg.shape[0]:
                    arr[i, : seg.shape[0]] = seg
                    any_root = True
            if any_root:
                chunks.append((arr, col0))
        return chunks, c

    def _pad_parts(self, arr: np.ndarray, lanes: int):
        """Pad a per-lane host operand's leading axis to ``lanes``."""
        if arr.shape[0] == lanes:
            return arr
        pad = np.zeros((lanes - arr.shape[0],) + arr.shape[1:], arr.dtype)
        return np.concatenate([arr, pad], axis=0)

    def _boundary_tab(
        self, res: PartResident, planes, parts, mask_dev, has_mask,
        lanes: int,
    ) -> np.ndarray:
        """Phase 1 over ``parts`` (None = all): int64[|parts|, Bp, Bp]
        intra-partition skeleton-to-skeleton distances."""
        plan = res.plan
        limit = self._limit(plan)
        chunks, c = self._root_chunks(plan, parts)
        n_lanes = plan.p_pad if parts is None else lanes
        key = (
            "bdist", n_lanes, plan.l_pad, plan.k_pad, c, has_mask,
        )
        step = self._jit(
            key,
            lambda: jax.jit(
                self._constrained(
                    lambda pl, roots, m: boundary_dist_kernel(
                        pl, roots, m, limit
                    )
                ),
                static_argnums=(),
            ),
        )
        n_rows = plan.n_parts if parts is None else len(parts)
        btab = np.full(
            (n_rows, plan.b_pad, plan.b_pad), int(INF), np.int64
        )
        for arr, col0 in chunks:
            with sanctioned_transfer("spf.partition.bdist"):
                roots = jnp.asarray(self._pad_parts(arr, n_lanes))
                out = step(planes, roots, mask_dev)
                host = np.asarray(out)  # [lanes, C, Bp]
            note_partition("bdist")
            btab[:, col0: col0 + c, :] = host[:n_rows]
        return btab

    def _seeds(
        self, res: PartResident, skel_dist: np.ndarray, parts=None
    ) -> np.ndarray:
        """Phase 3 seed plane int32[|parts|, L]: exact skeleton
        distances at own-skeleton + halo rows, INF elsewhere."""
        plan = res.plan
        idx = range(plan.n_parts) if parts is None else parts
        out = np.full((len(list(idx)), plan.l_pad), int(INF), np.int64)
        for i, p in enumerate(
            range(plan.n_parts) if parts is None else parts
        ):
            bl = plan.local_of[plan.bnd[p]]
            out[i, bl] = skel_dist[plan.bnd_skel[p]]
            out[i, res.halo_rows[p]] = skel_dist[plan.halo_skel[p]]
        return np.minimum(out, int(INF)).astype(np.int32)

    def _pins(
        self, res: PartResident, state: "_ExchangeState", parts, kp: int
    ) -> tuple[np.ndarray, ...]:
        """Halo pin planes for ``parts`` from the exchange tables."""
        plan = res.plan
        n = plan.n_vertices
        w = state.nh_tab.shape[1]
        lanes = len(parts)
        h = np.full((lanes, plan.l_pad), n + 1, np.int32)
        nh = np.zeros((lanes, plan.l_pad, w), np.int32)
        np_ = np.zeros((lanes, plan.l_pad), np.int32)
        aw = (
            np.zeros((lanes, plan.l_pad, w * 32), np.int32)
            if kp > 1
            else None
        )
        for i, p in enumerate(parts):
            rows = res.halo_rows[p]
            pos = plan.halo_skel[p]
            h[i, rows] = state.hops_tab[pos]
            nh[i, rows] = state.nh_tab[pos]
            np_[i, rows] = state.np_tab[pos]
            if kp > 1:
                aw[i, rows] = state.aw_tab[pos]
        return h, nh, np_, aw

    def _subset_planes(self, res: PartResident, parts: list):
        """Device gather of a pow2-padded partition subset."""
        plan = res.plan
        sp = _pow2(len(parts))
        idx = np.zeros(sp, np.int32)
        idx[: len(parts)] = np.asarray(parts, np.int32)
        key = ("gather", plan.p_pad, sp)
        step = self._jit(key, lambda: jax.jit(gather_parts_kernel))
        with sanctioned_transfer("spf.partition.gather"):
            sub = step(res.planes, jnp.asarray(idx))
        return sub, sp

    # -- the full solve -------------------------------------------------

    def solve(
        self,
        topo: Topology,
        res: PartResident,
        edge_mask: np.ndarray | None = None,
        kp: int = 1,
    ) -> dict:
        """Full three-phase partitioned solve.  Returns host planes in
        the SpfResult layout (global vertex space); when ``edge_mask``
        is None the resident records the solve state for DeltaPath."""
        plan = res.plan
        n = plan.n_vertices
        w = max((res.n_atoms + 31) // 32, 1)
        limit = self._limit(plan)
        has_mask = edge_mask is not None
        with sanctioned_transfer("spf.partition.marshal"):
            mask_dev = (
                jnp.asarray(np.asarray(edge_mask, bool))
                if has_mask
                else jnp.zeros((0,), bool)
            )

        # Phase 1 + 2: boundary tables and the skeleton stitch.  Each
        # phase runs under its own observatory stage sub-span (site
        # spf.partitioned), so the roofline/sentinel machinery buckets
        # partitioned phases apart from the monolithic engines.
        from holo_tpu.telemetry import profiling

        t0 = time.perf_counter()
        with profiling.stage("spf.partitioned", "bdist"):
            btab = self._boundary_tab(
                res, res.planes, None, mask_dev, has_mask, plan.p_pad
            )
        t1 = time.perf_counter()
        cut_mask = (
            np.asarray(edge_mask, bool)[plan.cut_eid] if has_mask else None
        )
        with profiling.stage("spf.partitioned", "stitch"):
            skel_dist = skeleton_solve(plan, btab, cut_mask)
        t2 = time.perf_counter()

        # Phase 3a: exact local distances.
        seeds = self._seeds(res, skel_dist)
        key = ("fdist", plan.p_pad, plan.l_pad, plan.k_pad, has_mask)
        fstep = self._jit(
            key,
            lambda: jax.jit(
                self._constrained(
                    lambda pl, s, m: final_dist_kernel(pl, s, m, limit)
                )
            ),
        )
        with profiling.stage("spf.partitioned", "dist"), sanctioned_transfer(
            "spf.partition.dist"
        ):
            dist_dev = fstep(
                res.planes,
                jnp.asarray(self._pad_parts(seeds, plan.p_pad)),
                mask_dev,
            )
            # copy(): readback views are read-only and the DeltaPath
            # driver updates rows in place.
            dist_loc = np.asarray(dist_dev)[: plan.n_parts].copy()
        note_partition("dist")
        t3 = time.perf_counter()

        # Phase 3b: pinned-halo phase 2 with host halo exchange.
        state = _ExchangeState(n, w, plan.n_skel, kp)
        parts = list(range(plan.n_parts))

        def full_lanes(_active):
            return res.planes, dist_dev, plan.p_pad

        with profiling.stage("spf.partitioned", "phase2"):
            out = self._exchange(
                res, state, parts, mask_dev, has_mask, kp, limit,
                get_lanes=full_lanes, full=True,
            )
        hops_loc, nh_loc, parent_loc, np_loc, aw_loc = out
        t4 = time.perf_counter()
        res.timings = {
            "bdist_s": t1 - t0,
            "stitch_s": t2 - t1,
            "dist_s": t3 - t2,
            "phase2_s": t4 - t3,
        }

        mp_sets = None
        if kp > 1:
            # n rides the key: the kernel bakes the global-id sentinel
            # (n_global) into its closure, and two topologies can share
            # every pow2 bucket while differing in real vertex count.
            mkey = (
                "mpsets", plan.p_pad, plan.l_pad, plan.k_pad, has_mask,
                kp, n,
            )
            mstep = self._jit(
                mkey,
                lambda: jax.jit(
                    self._constrained(
                        lambda pl, d, np_, m: mp_sets_kernel(
                            pl, d, np_, m, n, kp
                        )
                    )
                ),
            )
            with sanctioned_transfer("spf.partition.mpsets"):
                np_dev = jnp.asarray(
                    self._pad_parts(np_loc, plan.p_pad)
                )
                sets = mstep(res.planes, dist_dev, np_dev, mask_dev)
                mp_sets = tuple(
                    np.asarray(x)[: plan.n_parts].copy() for x in sets
                )
            note_partition("mpsets")

        result = self._assemble(
            res, dist_loc, hops_loc, nh_loc, parent_loc, np_loc, aw_loc,
            mp_sets, kp,
        )
        _PART_RESOLVED.set(plan.n_parts)
        _PART_ROUNDS.set(state.rounds)
        if not has_mask:
            res.kp = kp
            res.btab = btab
            res.skel_dist = skel_dist
            res.dist_loc = dist_loc
            res.hops_loc = hops_loc
            res.nh_loc = nh_loc
            res.parent_loc = parent_loc
            res.np_loc = np_loc
            res.aw_loc = aw_loc
            res.mp_sets = mp_sets
            res.hops_tab = state.hops_tab
            res.nh_tab = state.nh_tab
            res.np_tab = state.np_tab
            res.aw_tab = state.aw_tab
            res.last_resolved = plan.n_parts
            res.exchange_rounds = state.rounds
        note_partition("solve")
        return result

    def _phase2_jit(self, lanes, plan, w, has_mask, kp, n, limit):
        key = (
            "phase2", lanes, plan.l_pad, plan.k_pad, w, has_mask, kp, n,
        )
        if kp > 1:
            return self._jit(
                key,
                lambda: jax.jit(
                    self._constrained(
                        lambda pl, d, h, nh, np_, aw, m: phase2_mp_kernel(
                            pl, d, h, nh, np_, aw, m, n, limit
                        )
                    )
                ),
            )
        return self._jit(
            key,
            lambda: jax.jit(
                self._constrained(
                    lambda pl, d, h, nh, m: phase2_kernel(
                        pl, d, h, nh, m, n, limit
                    )
                )
            ),
        )

    def _exchange(
        self, res, state, parts, mask_dev, has_mask, kp, limit,
        get_lanes, full,
    ):
        """The pinned-halo outer loop.  ``get_lanes(active)`` returns
        ``(planes, dist_dev, lanes)`` for the active partition list —
        the full resident planes on a full solve, a pow2-bucketed
        device gather on a DeltaPath re-solve (re-fetched whenever the
        active set changes, so a growing affected region stays
        covered).  Mutates ``state``; returns final local host planes
        (one row per plan partition; inactive rows keep the resident's
        previous values)."""
        plan = res.plan
        n = plan.n_vertices
        w = state.nh_tab.shape[1]
        hops_loc = (
            res.hops_loc.copy()
            if res.hops_loc is not None
            else np.full((plan.n_parts, plan.l_pad), n + 1, np.int32)
        )
        nh_loc = (
            res.nh_loc.copy()
            if res.nh_loc is not None
            else np.zeros((plan.n_parts, plan.l_pad, w), np.int32)
        )
        parent_loc = (
            res.parent_loc.copy()
            if res.parent_loc is not None
            else np.full((plan.n_parts, plan.l_pad), n, np.int32)
        )
        np_loc = (
            res.np_loc.copy()
            if res.np_loc is not None
            else np.zeros((plan.n_parts, plan.l_pad), np.int32)
        )
        aw_loc = (
            res.aw_loc.copy()
            if res.aw_loc is not None
            else np.zeros((plan.n_parts, plan.l_pad, w * 32), np.int32)
        )
        cap = self.EXCHANGE_CAP_SLACK * (plan.n_skel + 2)
        active = list(parts)
        resolved: set = set(parts)
        for _round in range(cap):
            if not active:
                break
            planes, dist_dev, lanes = get_lanes(active)
            step = self._phase2_jit(
                lanes, plan, w, has_mask, kp, n, limit
            )
            pins = self._pins(res, state, active, kp)
            h_pin = self._pad_parts(pins[0], lanes)
            nh_pin = self._pad_parts(pins[1], lanes)
            with sanctioned_transfer("spf.partition.phase2"):
                if kp > 1:
                    np_pin = self._pad_parts(pins[2], lanes)
                    aw_pin = self._pad_parts(pins[3], lanes)
                    out = step(
                        planes, dist_dev, jnp.asarray(h_pin),
                        jnp.asarray(nh_pin), jnp.asarray(np_pin),
                        jnp.asarray(aw_pin), mask_dev,
                    )
                    hops, nh, np_, aw, parent_g, exp = out
                    exp_h, exp_n, exp_p, exp_w = (
                        np.asarray(x) for x in exp
                    )
                    np_h = np.asarray(np_)
                    aw_h = np.asarray(aw)
                else:
                    out = step(
                        planes, dist_dev, jnp.asarray(h_pin),
                        jnp.asarray(nh_pin), mask_dev,
                    )
                    hops, nh, parent_g, exp_h, exp_n = out
                    exp_h, exp_n = np.asarray(exp_h), np.asarray(exp_n)
                    np_h = aw_h = None
                hops_h = np.asarray(hops)
                nh_h = np.asarray(nh)
                par_h = np.asarray(parent_g)
            note_partition("phase2-round")
            state.rounds += 1
            # Fold exports into the tables; active next round = parts
            # whose HALO references a changed entry.
            changed = np.zeros(plan.n_skel, bool)

            def fold(tab, pos, exp_v):
                diff = tab[pos] != exp_v
                if diff.ndim > 1:
                    diff = diff.any(axis=tuple(range(1, diff.ndim)))
                changed[pos[diff]] = True
                tab[pos] = exp_v

            for i, p in enumerate(active):
                b = plan.bnd_skel[p].shape[0]
                pos = plan.bnd_skel[p]
                fold(state.hops_tab, pos, exp_h[i, :b])
                fold(state.nh_tab, pos, exp_n[i, :b])
                if kp > 1:
                    fold(state.np_tab, pos, exp_p[i, :b])
                    fold(state.aw_tab, pos, exp_w[i, :b])
                hops_loc[p] = hops_h[i]
                nh_loc[p] = nh_h[i]
                parent_loc[p] = par_h[i]
                if kp > 1:
                    np_loc[p] = np_h[i]
                    aw_loc[p] = aw_h[i]
            nxt = [
                p
                for p in range(plan.n_parts)
                if plan.halo_skel[p].shape[0]
                and changed[plan.halo_skel[p]].any()
            ]
            if full:
                # Full solves keep every lane hot (one program, no
                # subset gathers): iterate all until nothing changes.
                active = list(range(plan.n_parts)) if nxt else []
            else:
                active = nxt
            resolved.update(active)
        else:
            raise RuntimeError(
                "partitioned phase-2 exchange failed to settle "
                f"(cap {cap})"
            )
        state.resolved = resolved
        return hops_loc, nh_loc, parent_loc, np_loc, aw_loc

    def _assemble(
        self, res, dist_loc, hops_loc, nh_loc, parent_loc, np_loc,
        aw_loc, mp_sets, kp,
    ) -> dict:
        """Scatter per-partition local planes into global host arrays
        (the SpfResult contract: sentinel N parents, N+1 unreachable
        hops, uint32 next-hop words)."""
        plan = res.plan
        n = plan.n_vertices
        w = nh_loc.shape[2]
        ownm = res.own[: plan.n_parts]
        gids = res.gid[: plan.n_parts][ownm]
        dist = np.full(n, int(INF), np.int32)
        parent = np.full(n, n, np.int32)
        hops = np.full(n, n + 1, np.int32)
        nh = np.zeros((n, w), np.int32)
        dist[gids] = dist_loc[ownm]
        parent[gids] = parent_loc[ownm]
        hops[gids] = hops_loc[ownm]
        nh[gids] = nh_loc[ownm]
        unreach = dist >= int(INF)
        parent[unreach] = n
        hops[unreach] = n + 1
        out = {
            "dist": dist,
            "parent": parent,
            "hops": hops,
            # int32 bit lanes -> uint32 words: reinterpret, not convert
            # (the host twin of lax.bitcast_convert_type).
            "nexthop_words": nh.view(np.uint32),
        }
        if kp > 1:
            npv = np.zeros(n, np.int32)
            npv[gids] = np_loc[ownm]
            npv[unreach] = 0
            awv = np.zeros((n, aw_loc.shape[2]), np.int32)
            awv[gids] = aw_loc[ownm]
            parents = np.full((n, kp), n, np.int32)
            pdist = np.full((n, kp), int(INF), np.int32)
            pweight = np.zeros((n, kp), np.int32)
            parents[gids] = mp_sets[0][ownm]
            pdist[gids] = mp_sets[1][ownm]
            pweight[gids] = mp_sets[2][ownm]
            out.update(
                parents=parents, pdist=pdist, pweight=pweight,
                npaths=npv, nh_weights=awv,
            )
        return out

    # -- DeltaPath ------------------------------------------------------

    def _lower_delta(self, res: PartResident, delta: TopologyDelta):
        """Resolve delta ops to stacked-plane scatter targets, mutating
        the mirror (and the plan's cut-edge costs) to the post-delta
        state.  Raises :class:`_PartUnappliable` on anything the
        resident cannot absorb: structural ops on cut edges (the halo /
        skeleton geometry would change), overload strikes, padding or
        atom overflow, or an op that does not match the mirrored
        occupancy."""
        plan, mir = res.plan, res.mirror
        w = max((res.n_atoms + 31) // 32, 1)

        def src_local(p: int, src: int):
            if plan.part_of[src] == p:
                return int(plan.local_of[src])
            h = plan.halo[p]
            pos = int(np.searchsorted(h, src))
            if pos >= h.shape[0] or h[pos] != src:
                raise _PartUnappliable("halo-missing")
            return plan.verts[p].shape[0] + pos

        def find(p, dst_l, src_l, cost, atom) -> int:
            m = (
                mir.in_valid[p, dst_l]
                & (mir.in_src[p, dst_l] == src_l)
                & (mir.in_cost[p, dst_l] == cost)
                & (mir.in_atom[p, dst_l] == atom)
            )
            hit = np.nonzero(m)[0]
            if hit.shape[0] == 0:
                raise _PartUnappliable("missing-edge")
            return int(hit[0])

        if delta.overload.shape[0]:
            raise _PartUnappliable("overload")
        touched: set[tuple[int, int, int]] = set()
        affected: set[int] = set()
        d = delta
        # Removals first (they free slack additions reuse).
        for src, dst, cost, atom in zip(d.r_src, d.r_dst, d.r_cost, d.r_atom):
            if plan.part_of[src] != plan.part_of[dst]:
                raise _PartUnappliable("cut-struct")
            p = int(plan.part_of[dst])
            dst_l = int(plan.local_of[dst])
            col = find(p, dst_l, src_local(p, int(src)), cost, atom)
            mir.in_valid[p, dst_l, col] = False
            mir.in_src[p, dst_l, col] = 0
            mir.in_cost[p, dst_l, col] = 0
            mir.in_atom[p, dst_l, col] = -1
            touched.add((p, dst_l, col))
            affected.add(p)
        for src, dst, old, new, atom in zip(
            d.w_src, d.w_dst, d.w_old, d.w_new, d.w_atom
        ):
            p = int(plan.part_of[dst])
            dst_l = int(plan.local_of[dst])
            s_l = src_local(p, int(src))
            col = find(p, dst_l, s_l, old, atom)
            mir.in_cost[p, dst_l, col] = new
            touched.add((p, dst_l, col))
            affected.add(p)
            if plan.part_of[src] != p:
                # Cut-edge re-cost: the skeleton edge moves too.
                hit = np.nonzero(
                    (plan.cut_src == src)
                    & (plan.cut_dst == dst)
                    & (plan.cut_cost == old)
                )[0]
                if hit.shape[0] == 0:
                    raise _PartUnappliable("cut-missing")
                plan.cut_cost[hit[0]] = new
        for src, dst, cost, atom in zip(d.a_src, d.a_dst, d.a_cost, d.a_atom):
            if plan.part_of[src] != plan.part_of[dst]:
                raise _PartUnappliable("cut-struct")
            if atom >= res.n_atoms:
                raise _PartUnappliable("atom-overflow")
            p = int(plan.part_of[dst])
            dst_l = int(plan.local_of[dst])
            free = np.nonzero(~mir.in_valid[p, dst_l])[0]
            if free.shape[0] == 0:
                raise _PartUnappliable("padding-overflow")
            col = int(free[0])
            mir.in_valid[p, dst_l, col] = True
            mir.in_src[p, dst_l, col] = src_local(p, int(src))
            mir.in_cost[p, dst_l, col] = cost
            mir.in_atom[p, dst_l, col] = atom
            touched.add((p, dst_l, col))
            affected.add(p)
        pad = _pow2(len(touched), floor=64)
        part = np.full(pad, plan.p_pad, np.int32)  # OOB lane: dropped
        row = np.zeros(pad, np.int32)
        col_a = np.zeros(pad, np.int32)
        src_a = np.zeros(pad, np.int32)
        cost_a = np.zeros(pad, np.int32)
        valid_a = np.zeros(pad, bool)
        words_a = np.zeros((pad, w), np.uint32)
        for i, (p, r, c) in enumerate(sorted(touched)):
            part[i], row[i], col_a[i] = p, r, c
            src_a[i] = mir.in_src[p, r, c]
            cost_a[i] = mir.in_cost[p, r, c]
            valid_a[i] = mir.in_valid[p, r, c]
            a = int(mir.in_atom[p, r, c])
            if a >= 0:
                words_a[i, a // 32] = np.uint32(1) << np.uint32(a % 32)
        return (
            (part, row, col_a, src_a, cost_a, valid_a, words_a),
            sorted(affected),
        )

    def try_delta(
        self, topo: Topology, res: PartResident, kp: int = 1
    ) -> tuple[dict, dict] | None:
        """Serve a delta-linked topology from the partitioned resident:
        in-place plane update, boundary re-solve of ONLY the affected
        partitions, host skeleton re-stitch, and a final re-solve
        bounded to partitions whose seeds or exchanged halo values
        changed.  Returns ``(result, info)`` or None (caller falls back
        to the full partitioned solve); ``info['resolved']`` counts the
        re-solved partitions (the Bounded-Dijkstra radius claim the
        tests assert)."""
        delta = getattr(topo, "delta_base", None)
        plan = res.plan
        if delta is None or res.btab is None:
            return None
        if delta.base_key != res.topo_key:
            note_partition("delta-no-base")
            return None
        if kp != res.kp:
            note_partition("delta-kp-flip")
            return None
        t0 = time.perf_counter()
        try:
            arrays, affected = self._lower_delta(res, delta)
        except _PartUnappliable as exc:
            # Mirror may be half-updated: the resident can no longer
            # serve deltas (the caller re-marshals from scratch).
            res.btab = None
            note_partition(f"delta-{exc.reason}")
            return None
        n = plan.n_vertices
        limit = self._limit(plan)
        pad = arrays[0].shape[0]
        akey = ("apply", plan.p_pad, plan.l_pad, plan.k_pad, pad)
        astep = self._jit(
            akey,
            lambda: jax.jit(apply_part_delta_kernel, donate_argnums=(0,)),
        )
        with sanctioned_transfer("spf.partition.delta"):
            old = res.planes
            res.planes = astep(old, *(jnp.asarray(a) for a in arrays))
        note_donated("spf.partition.delta", old)
        res.topo_key = topo.cache_key
        res.delta_depth += 1
        res.ids_stale = res.ids_stale or not delta.ids_stable
        note_partition("delta-apply")

        with sanctioned_transfer("spf.partition.delta"):
            mask_dev = jnp.zeros((0,), bool)
        # Boundary re-solve: affected partitions only.
        if affected:
            sub, sp = self._subset_planes(res, affected)
            btab_sub = self._boundary_tab(
                res, sub, affected, mask_dev, False, sp
            )
            for i, p in enumerate(affected):
                res.btab[p] = btab_sub[i]
            note_partition("delta-bdist")
        skel_new = skeleton_solve(plan, res.btab)
        need_dist = set(affected)
        for p in range(plan.n_parts):
            pos = np.concatenate([plan.bnd_skel[p], plan.halo_skel[p]])
            if pos.shape[0] and (
                skel_new[pos] != res.skel_dist[pos]
            ).any():
                need_dist.add(p)
        res.skel_dist = skel_new

        parts_d = sorted(need_dist)
        if parts_d:
            sub, sp = self._subset_planes(res, parts_d)
            seeds = self._seeds(res, skel_new, parts_d)
            fkey = ("fdist", sp, plan.l_pad, plan.k_pad, False)
            fstep = self._jit(
                fkey,
                lambda: jax.jit(
                    self._constrained(
                        lambda pl, s, m: final_dist_kernel(
                            pl, s, m, limit
                        )
                    )
                ),
            )
            with sanctioned_transfer("spf.partition.dist"):
                dist_sub = np.asarray(
                    fstep(
                        sub,
                        jnp.asarray(self._pad_parts(seeds, sp)),
                        mask_dev,
                    )
                )[: len(parts_d)]
            note_partition("delta-dist")
            for i, p in enumerate(parts_d):
                res.dist_loc[p] = dist_sub[i]

        # Phase 2 over the affected closure (active set grows with the
        # exchanged halo values; lanes re-gathered per round).
        state = _ExchangeState.from_resident(res)

        def delta_lanes(active):
            subp, spl = self._subset_planes(res, active)
            with sanctioned_transfer("spf.partition.dist"):
                d = jnp.asarray(
                    self._pad_parts(
                        res.dist_loc[np.asarray(active, np.int64)], spl
                    )
                )
            return subp, d, spl

        out = self._exchange(
            res, state, parts_d, mask_dev, False, kp, limit,
            get_lanes=delta_lanes, full=False,
        )
        hops_loc, nh_loc, parent_loc, np_loc, aw_loc = out
        resolved = sorted(state.resolved | set(parts_d))

        if kp > 1 and resolved:
            sub, sp = self._subset_planes(res, resolved)
            mkey = ("mpsets", sp, plan.l_pad, plan.k_pad, False, kp, n)
            mstep = self._jit(
                mkey,
                lambda: jax.jit(
                    self._constrained(
                        lambda pl, dd, pp, m: mp_sets_kernel(
                            pl, dd, pp, m, n, kp
                        )
                    )
                ),
            )
            with sanctioned_transfer("spf.partition.mpsets"):
                dsub = jnp.asarray(
                    self._pad_parts(
                        res.dist_loc[np.asarray(resolved, np.int64)], sp
                    )
                )
                psub = jnp.asarray(
                    self._pad_parts(
                        np_loc[np.asarray(resolved, np.int64)], sp
                    )
                )
                sets = tuple(
                    np.asarray(x)[: len(resolved)]
                    for x in mstep(sub, dsub, psub, mask_dev)
                )
            for i, p in enumerate(resolved):
                res.mp_sets[0][p] = sets[0][i]
                res.mp_sets[1][p] = sets[1][i]
                res.mp_sets[2][p] = sets[2][i]

        res.hops_loc, res.nh_loc = hops_loc, nh_loc
        res.parent_loc = parent_loc
        res.np_loc, res.aw_loc = np_loc, aw_loc
        res.hops_tab, res.nh_tab = state.hops_tab, state.nh_tab
        res.np_tab, res.aw_tab = state.np_tab, state.aw_tab
        res.last_resolved = len(resolved)
        res.exchange_rounds = state.rounds
        _PART_RESOLVED.set(len(resolved))
        _PART_ROUNDS.set(state.rounds)
        result = self._assemble(
            res, res.dist_loc, hops_loc, nh_loc, parent_loc, np_loc,
            aw_loc, res.mp_sets, kp,
        )
        res.timings = {"delta_s": time.perf_counter() - t0}
        note_partition("delta-solve")
        return result, {
            "resolved": len(resolved),
            "parts": plan.n_parts,
            "rounds": state.rounds,
        }


class _ExchangeState:
    def __init__(self, n: int, w: int, n_skel: int, kp: int):
        self.hops_tab = np.full(n_skel, n + 1, np.int32)
        self.nh_tab = np.zeros((n_skel, w), np.int32)
        self.np_tab = np.zeros(n_skel, np.int32)
        self.aw_tab = np.zeros((n_skel, w * 32), np.int32)
        self.rounds = 0
        self.resolved: set = set()

    @classmethod
    def from_resident(cls, res: PartResident) -> "_ExchangeState":
        st = cls(
            res.plan.n_vertices,
            res.nh_tab.shape[1],
            res.plan.n_skel,
            res.kp,
        )
        st.hops_tab = res.hops_tab.copy()
        st.nh_tab = res.nh_tab.copy()
        st.np_tab = res.np_tab.copy()
        st.aw_tab = res.aw_tab.copy()
        return st


# -- jaxpr-audit registrations (HL3xx) ----------------------------------
# Inert contract descriptors for holo_tpu.analysis.jaxpr_audit; the
# builders mirror PartitionedSpfEngine._jit constructions (same kernels,
# same donations) at a fixed audit limit.  Thunks run only when the
# audit arms.
from holo_tpu.analysis.kernels import register_kernel as _register_kernel  # noqa: E402

_AUDIT_P, _AUDIT_L, _AUDIT_SK, _AUDIT_BP = 4, 32, 8, 8
_AUDIT_LIMIT = 32


def audit_part_planes_spec(
    p=_AUDIT_P, l=_AUDIT_L, k=8, w=2, bp=_AUDIT_BP
) -> PartPlanes:
    """Abstract PartPlanes matching the partition marshal layout."""
    s = jax.ShapeDtypeStruct
    i32, u32, b = jnp.int32, jnp.uint32, jnp.bool_
    return PartPlanes(
        in_src=s((p, l, k), i32),
        in_cost=s((p, l, k), i32),
        in_valid=s((p, l, k), b),
        in_edge_id=s((p, l, k), i32),
        direct_words=s((p, l, k, w), u32),
        is_router=s((p, l), b),
        gid=s((p, l), i32),
        own=s((p, l), b),
        pinned=s((p, l), b),
        root_local=s((p,), i32),
        bnd_local=s((p, bp), i32),
    )


def _audit_part_specs():
    s = jax.ShapeDtypeStruct
    i32, u32, b = jnp.int32, jnp.uint32, jnp.bool_
    p, l, w = _AUDIT_P, _AUDIT_L, 2
    return {
        "pl": audit_part_planes_spec(),
        "roots": s((p, _AUDIT_SK), i32),
        "seed": s((p, l), i32),
        "dist": s((p, l), i32),
        "hops": s((p, l), i32),
        "nh": s((p, l, w), i32),
        "mask": s((128,), b),
        "idx": s((2,), i32),
        "drow": s((256,), i32),
        "dwords": s((256, w), u32),
        "dvalid": s((256,), b),
    }


_register_kernel(
    "spf.partition.bdist",
    builder=lambda: jax.jit(
        lambda pl, roots, m: boundary_dist_kernel(pl, roots, m, _AUDIT_LIMIT)
    ),
    specs=lambda: (
        lambda a: (a["pl"], a["roots"], a["mask"])
    )(_audit_part_specs()),
    buckets=16,  # pow2 partition-lane x root-chunk buckets
)

_register_kernel(
    "spf.partition.fdist",
    builder=lambda: jax.jit(
        lambda pl, seed, m: final_dist_kernel(pl, seed, m, _AUDIT_LIMIT)
    ),
    specs=lambda: (
        lambda a: (a["pl"], a["seed"], a["mask"])
    )(_audit_part_specs()),
    buckets=16,
)

_register_kernel(
    "spf.partition.phase2",
    builder=lambda: jax.jit(
        lambda pl, d, h, nh, m: phase2_kernel(
            pl, d, h, nh, m, _AUDIT_P * _AUDIT_L, _AUDIT_LIMIT
        )
    ),
    specs=lambda: (
        lambda a: (a["pl"], a["dist"], a["hops"], a["nh"], a["mask"])
    )(_audit_part_specs()),
    buckets=16,
)

_register_kernel(
    "spf.partition.gather",
    builder=lambda: jax.jit(gather_parts_kernel),
    specs=lambda: (
        lambda a: (a["pl"], a["idx"])
    )(_audit_part_specs()),
    buckets=8,  # pow2 gather-subset lanes
)

_register_kernel(
    "spf.partition.apply_delta",
    builder=lambda: jax.jit(apply_part_delta_kernel, donate_argnums=(0,)),
    specs=lambda: (
        lambda a: (
            a["pl"], a["drow"], a["drow"], a["drow"], a["drow"],
            a["drow"], a["dvalid"], a["dwords"],
        )
    )(_audit_part_specs()),
    donate=(0,),
    buckets=16,  # pow2 delta-row pads
)
