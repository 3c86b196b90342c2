"""Full block-sparse SPF: distances + first-parent + hops + ECMP next-hops.

Extends the min-plus distance kernel (ops/blocked.py) to the complete SPF
output contract of :mod:`holo_tpu.ops.spf_engine`, replacing every
gather-bound fixpoint with dense per-block VPU work:

- distances: the existing block relax kernel (Jacobi min-plus fixpoint);
- first parent: two single-pass kernels — per-vertex min DAG-parent
  distance, then min *original id* among parents at that distance.  This
  reproduces the reference's BTreeMap pop order (holo-ospf/src/
  spf.rs:614-622, 676-706) even though compute runs in a BFS-permuted
  vertex space (see below);
- hops: first-parent chain fixpoint — a cheap [N, B] gather loop;
- next-hop bitmasks: direct contributions come only from parents with
  ``hops == 0`` (the root and root-adjacent transit networks,
  spf.rs:733-767), a *small static edge set* handled densely in XLA; the
  inherit fixpoint (spf.rs:710-717) runs as a block OR kernel with the
  (word × scenario) product riding the lane axis.

Vertex permutation: vertices are BFS-reordered from the root before
blocking, which concentrates edges into far fewer S×S blocks than the
tie-break vertex order (the kernels' cost is proportional to the nonzero
block-pair count, not to E).  Distances are permutation-invariant; the
first-parent tie-break compares ORIGINAL ids inside the kernel, so results
are bit-identical to the scalar oracle in the original space.

What-if exactness follows ops/blocked.py: kernels run on the static graph;
after every Jacobi step a tiny correction recomputes the failed edges'
destination rows from the ELL in-edge lists with the failed slots masked —
only those rows can differ, and the fixpoint is preserved.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from holo_tpu.ops.blocked import CAP, S, UNREACH
from holo_tpu.ops.graph import INF, Topology, build_ell

# "no parent" sentinel inside kernels; > any original vertex id, < CAP so
# int32 arithmetic stays exact.
PBIG = np.int32(1 << 27)


class BlockSpfGraph(NamedTuple):
    """Device planes for the full blocked SPF (all in BFS-permuted space)."""

    # block-sparse weight planes (as ops/blocked.py)
    w: jax.Array  # int32[P, S, S]
    bsrc: jax.Array  # int32[P]
    bdst: jax.Array  # int32[P]
    first: jax.Array  # int32[P]
    # ELL correction planes (permuted vertex space, original edge ids)
    in_src: jax.Array  # int32[N_pad, K]
    in_cost: jax.Array  # int32[N_pad, K]
    in_valid: jax.Array  # bool[N_pad, K]
    in_edge_id: jax.Array  # int32[N_pad, K]
    # per-vertex planes
    inc: jax.Array  # int32[N_pad] 1 if router (hops increment)
    orig_id: jax.Array  # int32[N_pad] perm -> original id (PBIG for pads)
    orig2perm: jax.Array  # int32[N_orig] original -> perm
    # direct next-hop candidate table: per vertex with in-edges from the
    # root / root-adjacent networks, its padded candidate list
    vz: jax.Array  # int32[M] destination vertex (perm)
    z_src: jax.Array  # int32[M, C] source vertex (perm)
    z_cost: jax.Array  # int32[M, C]
    z_eid: jax.Array  # int32[M, C] original edge id
    z_words: jax.Array  # int32[M, C, W] one-hot atom words
    z_valid: jax.Array  # bool[M, C]
    n_real: int  # permuted-space vertex count (== n_orig)
    n_words: int  # W
    rootp: int  # root row in permuted space (0 under BFS ordering)


def bfs_permutation(topo: Topology) -> np.ndarray:
    """perm_of[orig_id] -> new id; BFS from root over the undirected graph.

    Neighbor visit order is ascending original id so the permutation is
    deterministic.  Unreached vertices keep relative order at the end.
    """
    n = topo.n_vertices
    # Undirected CSR (vectorized — graphs can have millions of edges).
    us = np.concatenate([topo.edge_src, topo.edge_dst]).astype(np.int64)
    ud = np.concatenate([topo.edge_dst, topo.edge_src]).astype(np.int64)
    order_e = np.argsort(us, kind="stable")
    us_s, ud_s = us[order_e], ud[order_e]
    starts = np.searchsorted(us_s, np.arange(n + 1))

    seen = np.zeros(n, bool)
    seen[topo.root] = True
    frontier = np.array([topo.root], np.int64)
    chunks = [frontier]
    while frontier.size:
        lo, hi = starts[frontier], starts[frontier + 1]
        # gather all neighbors of the frontier
        counts = hi - lo
        idx = np.repeat(lo, counts) + (
            np.arange(counts.sum()) - np.repeat(np.cumsum(counts) - counts, counts)
        )
        nbrs = np.unique(ud_s[idx])
        nbrs = nbrs[~seen[nbrs]]
        seen[nbrs] = True
        frontier = nbrs  # ascending-id order within each BFS layer
        if nbrs.size:
            chunks.append(nbrs)
    rest = np.nonzero(~seen)[0]
    if rest.size:
        chunks.append(rest)
    order = np.concatenate(chunks)
    perm_of = np.empty(n, np.int64)
    perm_of[order] = np.arange(n)
    return perm_of


def _block_pair_count(psrc: np.ndarray, pdst: np.ndarray, nb: int) -> int:
    key = (pdst // S).astype(np.int64) * nb + (psrc // S)
    return len(np.unique(key))


def marshal_block_spf(
    topo: Topology, n_atoms: int = 64, permute: bool | str = "auto"
) -> BlockSpfGraph:
    """Lower a Topology to the full blocked-SPF device planes.

    ``permute="auto"`` picks whichever of {BFS order, native tie-break
    order} yields fewer nonzero block pairs — kernel cost is proportional
    to the pair count, and which ordering wins is topology-dependent
    (BFS wins on unstructured graphs; layered topologies are often already
    block-friendly).

    Same restrictions as ops/blocked.py: unique (src, dst) pairs and max
    finite distance < 2**27.
    """
    n = topo.n_vertices
    src, dst, cost = topo.edge_src, topo.edge_dst, topo.edge_cost
    pair_keys = src.astype(np.int64) * n + dst
    if len(np.unique(pair_keys)) != topo.n_edges:
        raise ValueError("parallel (src,dst) edges: merge before marshaling")
    max_cost = int(cost.max()) if topo.n_edges else 0
    if (n - 1) * max_cost >= UNREACH:
        raise ValueError(
            f"distance bound (n-1)*max_cost = {(n - 1) * max_cost} "
            f">= {UNREACH}: use the gather engine (exact to 2**30)"
        )

    if permute == "auto":
        bfs = bfs_permutation(topo)
        ident = np.arange(n, dtype=np.int64)
        nb_ = (n + S - 1) // S
        perm_of = (
            bfs
            if _block_pair_count(bfs[src], bfs[dst], nb_)
            < _block_pair_count(src, dst, nb_)
            else ident
        )
    else:
        perm_of = (
            bfs_permutation(topo) if permute else np.arange(n, dtype=np.int64)
        )
    psrc = perm_of[src].astype(np.int32)
    pdst = perm_of[dst].astype(np.int32)
    inv = np.empty(n, np.int64)  # perm -> orig
    inv[perm_of] = np.arange(n)

    nb = (n + S - 1) // S
    npad = nb * S
    bj = psrc // S
    bi = pdst // S
    key = bi.astype(np.int64) * nb + bj
    missing = sorted(set(range(nb)) - set((key // nb).tolist()))
    key_all = np.concatenate(
        [key, np.array([m * nb + m for m in missing], np.int64)]
    )
    uniq, inv_all = np.unique(key_all, return_inverse=True)
    slot = inv_all[: len(key)]
    p = len(uniq)
    bsrc = (uniq % nb).astype(np.int32)
    bdst = (uniq // nb).astype(np.int32)
    w = np.full((max(p, 1), S, S), CAP, np.int32)
    w[slot, psrc % S, pdst % S] = np.minimum(cost, CAP)
    first = np.ones(max(p, 1), np.int32)
    first[1:] = (bdst[1:] != bdst[:-1]).astype(np.int32)

    # ELL planes in permuted space (edge ids stay original).
    ptopo = Topology(
        n_vertices=n,
        is_router=topo.is_router[inv],
        edge_src=psrc,
        edge_dst=pdst,
        edge_cost=cost,
        edge_direct_atom=topo.edge_direct_atom,
        root=int(perm_of[topo.root]),
    )
    ell = build_ell(ptopo, n_atoms=max(n_atoms, topo.n_atoms()))
    in_src = np.zeros((npad, ell.k_pad), np.int32)
    in_cost = np.zeros((npad, ell.k_pad), np.int32)
    in_valid = np.zeros((npad, ell.k_pad), bool)
    in_edge_id = np.zeros((npad, ell.k_pad), np.int32)
    in_src[:n] = ell.in_src
    in_cost[:n] = ell.in_cost
    in_valid[:n] = ell.in_valid
    in_edge_id[:n] = ell.in_edge_id

    inc = np.zeros(npad, np.int32)
    inc[:n] = topo.is_router[inv].astype(np.int32)
    orig_id = np.full(npad, PBIG, np.int32)
    orig_id[:n] = inv

    # Direct-contribution candidate edges: out-edges of Z = {root} union
    # {transit networks adjacent to the root}.  Only parents with
    # hops == 0 can contribute direct atoms, and those are exactly Z
    # members (a network's hop count is 0 iff its first parent is the
    # root; routers always increment).
    nwords = max((max(n_atoms, topo.n_atoms()) + 31) // 32, 1)
    rootp = int(perm_of[topo.root])
    in_z = np.zeros(n, bool)
    in_z[rootp] = True
    root_out = psrc == rootp
    in_z[pdst[root_out & ~topo.is_router[dst]]] = True
    z_edges = np.nonzero(in_z[psrc])[0]
    by_dst: dict[int, list] = {}
    for e in z_edges.tolist():
        by_dst.setdefault(int(pdst[e]), []).append(e)
    m = max(len(by_dst), 1)
    c = max((len(v) for v in by_dst.values()), default=1)
    vz = np.zeros(m, np.int32)
    z_src = np.zeros((m, c), np.int32)
    z_cost = np.zeros((m, c), np.int32)
    z_eid = np.zeros((m, c), np.int32)
    z_words = np.zeros((m, c, nwords), np.int32)
    z_valid = np.zeros((m, c), bool)
    for i, (v, edges) in enumerate(sorted(by_dst.items())):
        vz[i] = v
        for j, e in enumerate(edges):
            z_src[i, j] = psrc[e]
            z_cost[i, j] = cost[e]
            z_eid[i, j] = e
            z_valid[i, j] = True
            a = int(topo.edge_direct_atom[e])
            if a >= 0:
                z_words[i, j, a // 32] = np.int32(
                    np.uint32(1) << np.uint32(a % 32)
                )

    return BlockSpfGraph(
        w=jnp.asarray(w),
        bsrc=jnp.asarray(bsrc),
        bdst=jnp.asarray(bdst),
        first=jnp.asarray(first),
        in_src=jnp.asarray(in_src),
        in_cost=jnp.asarray(in_cost),
        in_valid=jnp.asarray(in_valid),
        in_edge_id=jnp.asarray(in_edge_id),
        inc=jnp.asarray(inc),
        orig_id=jnp.asarray(orig_id),
        orig2perm=jnp.asarray(perm_of.astype(np.int32)),
        vz=jnp.asarray(vz),
        z_src=jnp.asarray(z_src),
        z_cost=jnp.asarray(z_cost),
        z_eid=jnp.asarray(z_eid),
        z_words=jnp.asarray(z_words),
        z_valid=jnp.asarray(z_valid),
        n_real=n,
        n_words=nwords,
        rootp=rootp,
    )


# ---------------------------------------------------------------------------
# Pallas kernels.  All follow the Mosaic-safe "row variant": per-source-row
# extract + sublane broadcast inside a plain fori_loop (see ops/blocked.py
# and the platform notes there) — no dynamic lane indexing, no unrolling.


def _relax_kernel(bsrc_ref, bdst_ref, first_ref, w_ref, dsrc_ref, ddst_ref, out_ref):
    p = pl.program_id(0)

    @pl.when(first_ref[p] == 1)
    def _():
        out_ref[:] = ddst_ref[:]

    def body(u, acc):
        contrib = w_ref[0, u, :][:, None] + dsrc_ref[u, :][None, :]
        return jnp.minimum(acc, contrib)

    out_ref[:] = jax.lax.fori_loop(0, S, body, out_ref[:])


def _dmin_kernel(bsrc_ref, bdst_ref, first_ref, w_ref, dsrc_ref, ddst_ref, out_ref):
    """out[v, b] = min over DAG parents u of dist[u, b] (CAP if none)."""
    p = pl.program_id(0)

    @pl.when(first_ref[p] == 1)
    def _():
        out_ref[:] = jnp.full_like(out_ref[:], CAP)

    def body(u, acc):
        w_row = w_ref[0, u, :][:, None]  # [S, 1]
        du = dsrc_ref[u, :][None, :]  # [1, B]
        dag = (w_row < CAP) & (w_row + du == ddst_ref[:]) & (du < CAP)
        return jnp.minimum(acc, jnp.where(dag, du, CAP))

    out_ref[:] = jax.lax.fori_loop(0, S, body, out_ref[:])


def _parent_kernel(
    bsrc_ref, bdst_ref, first_ref, w_ref, dsrc_ref, ddst_ref, dmin_ref,
    oid_ref, out_ref,
):
    """out[v, b] = min original id among DAG parents with dist == dmin."""
    p = pl.program_id(0)

    @pl.when(first_ref[p] == 1)
    def _():
        out_ref[:] = jnp.full_like(out_ref[:], PBIG)

    def body(u, acc):
        w_row = w_ref[0, u, :][:, None]
        du = dsrc_ref[u, :][None, :]
        dag = (
            (w_row < CAP)
            & (w_row + du == ddst_ref[:])
            & (du < CAP)
            & (du == dmin_ref[:])
        )
        return jnp.minimum(acc, jnp.where(dag, oid_ref[u, :][None, :], PBIG))

    out_ref[:] = jax.lax.fori_loop(0, S, body, out_ref[:])


def _nh_or_kernel(
    bsrc_ref, bdst_ref, first_ref, w_ref, dsrc_ref, ddst_ref, gate_ref,
    nhsrc_ref, direct_ref, out_ref,
):
    """out[v, l] = direct[v, l] | OR over DAG parents u with hops>0 of nh[u, l].

    The lane axis packs (word, scenario): l = word * B + b; dsrc/ddst/gate
    are pre-tiled along words so the DAG test is lane-consistent.
    """
    p = pl.program_id(0)

    @pl.when(first_ref[p] == 1)
    def _():
        out_ref[:] = direct_ref[:]

    def body(u, acc):
        w_row = w_ref[0, u, :][:, None]
        du = dsrc_ref[u, :][None, :]
        dag = (
            (w_row < CAP)
            & (w_row + du == ddst_ref[:])
            & (du < CAP)
            & (gate_ref[u, :][None, :] > 0)
        )
        return acc | jnp.where(dag, nhsrc_ref[u, :][None, :], 0)

    out_ref[:] = jax.lax.fori_loop(0, S, body, out_ref[:])


def _grid(n_pairs: int, npad: int, lanes: int, kernel, extra: str,
          interpret: bool):
    """pallas_call builder: weight block + dist src/dst + extra planes.

    ``extra`` is a string over {'s', 'd'}: one additional [N_pad, lanes]
    input per char, indexed by the source ('s') or destination ('d') block,
    in kernel-signature order after ddst.
    """
    specs = [
        pl.BlockSpec((1, S, S), lambda p, bs, bd, f: (p, 0, 0)),
        pl.BlockSpec((S, lanes), lambda p, bs, bd, f: (bs[p], 0)),
        pl.BlockSpec((S, lanes), lambda p, bs, bd, f: (bd[p], 0)),
    ]
    for kind in extra:
        if kind == "s":
            specs.append(
                pl.BlockSpec((S, lanes), lambda p, bs, bd, f: (bs[p], 0))
            )
        else:
            specs.append(
                pl.BlockSpec((S, lanes), lambda p, bs, bd, f: (bd[p], 0))
            )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_pairs,),
        in_specs=specs,
        out_specs=pl.BlockSpec((S, lanes), lambda p, bs, bd, f: (bd[p], 0)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((npad, lanes), jnp.int32),
        interpret=interpret,
    )


# ---------------------------------------------------------------------------
# Failed-edge corrections (exact repair of rows whose in-edges changed).


def _row_plan(g: BlockSpfGraph, fdst, fid):
    """Shared gather plan for one failed-destination slot column."""
    B = fdst.shape[0]
    brange = jnp.arange(B)
    v = fdst  # [B]
    v_safe = jnp.maximum(v, 0)
    idx = g.in_src[v_safe]  # [B, K]
    wcost = g.in_cost[v_safe]
    valid = g.in_valid[v_safe]
    eid = g.in_edge_id[v_safe]
    excl = (eid[:, :, None] == fid[:, None, :]) & (fid[:, None, :] >= 0)
    valid = valid & ~excl.any(axis=2)
    return v, v_safe, idx, wcost, valid, brange


def _correct_dist(g, dist_prev, acc, fdst, fid):
    B, F = fdst.shape
    for f in range(F):
        v, v_safe, idx, wcost, valid, brange = _row_plan(g, fdst[:, f], fid)
        dvals = dist_prev[idx, brange[:, None]]
        cand = jnp.where(valid & (dvals < UNREACH), dvals + wcost, CAP)
        prev_v = dist_prev[v_safe, brange]
        new_v = jnp.minimum(prev_v, cand.min(axis=1))
        cur = acc[v_safe, brange]
        acc = acc.at[v_safe, brange].set(jnp.where(v >= 0, new_v, cur))
    return acc


def _dag_slots(g, dist, idx, wcost, valid, v_safe, brange):
    """bool[B, K]: ELL slot is a DAG in-edge under the final distances."""
    dvals = dist[idx, brange[:, None]]
    dv = dist[v_safe, brange][:, None]
    return valid & (dvals < CAP) & (dv < CAP) & (dvals + wcost == dv), dvals


def _correct_dmin(g, dist, acc, fdst, fid):
    for f in range(fdst.shape[1]):
        v, v_safe, idx, wcost, valid, brange = _row_plan(g, fdst[:, f], fid)
        dag, dvals = _dag_slots(g, dist, idx, wcost, valid, v_safe, brange)
        new_v = jnp.where(dag, dvals, CAP).min(axis=1)
        cur = acc[v_safe, brange]
        acc = acc.at[v_safe, brange].set(jnp.where(v >= 0, new_v, cur))
    return acc


def _correct_parent(g, dist, dmin, acc, fdst, fid):
    for f in range(fdst.shape[1]):
        v, v_safe, idx, wcost, valid, brange = _row_plan(g, fdst[:, f], fid)
        dag, dvals = _dag_slots(g, dist, idx, wcost, valid, v_safe, brange)
        at_min = dag & (dvals == dmin[v_safe, brange][:, None])
        oid = g.orig_id[idx]  # [B, K]
        new_v = jnp.where(at_min, oid, PBIG).min(axis=1)
        cur = acc[v_safe, brange]
        acc = acc.at[v_safe, brange].set(jnp.where(v >= 0, new_v, cur))
    return acc


def _correct_nh(g, dist, hops_gate, direct, acc, fdst, fid, lanes):
    """Repair failed rows of the inherit fixpoint: recompute from ELL.

    ``hops_gate``/``direct``/``acc`` are in the lane-packed [N_pad, W*B]
    layout; dist is [N_pad, B].
    """
    B = fdst.shape[0]
    W = lanes // B
    for f in range(fdst.shape[1]):
        v, v_safe, idx, wcost, valid, brange = _row_plan(g, fdst[:, f], fid)
        dag, _ = _dag_slots(g, dist, idx, wcost, valid, v_safe, brange)
        # inherit sources: DAG parents with hops > 0
        gate = hops_gate[idx, brange[:, None]] > 0  # [B, K] (word 0 lane)
        use = dag & gate
        new_rows = []
        for wd in range(W):
            lane = wd * B + brange  # [B]
            nh_parents = acc[idx, lane[:, None]]  # [B, K]
            ored = jax.lax.reduce(
                jnp.where(use, nh_parents, 0),
                jnp.int32(0),
                jax.lax.bitwise_or,
                dimensions=(1,),
            )
            new_rows.append(direct[v_safe, lane] | ored)
        for wd in range(W):
            lane = wd * B + brange
            cur = acc[v_safe, lane]
            acc = acc.at[v_safe, lane].set(
                jnp.where(v >= 0, new_rows[wd], cur)
            )
    return acc


# ---------------------------------------------------------------------------
# Full pipeline.


class BlockedSpfOut(NamedTuple):
    """[B, N] planes in the ORIGINAL vertex space (scalar-oracle layout)."""

    dist: jax.Array  # int32[B, N], INF unreachable
    parent: jax.Array  # int32[B, N], N if none
    hops: jax.Array  # int32[B, N], N+1 unreachable
    nexthops: jax.Array  # uint32[B, N, W]


def whatif_spf_blocked(
    g: BlockSpfGraph,
    failed_dst: jax.Array,  # int32[B, F] failed edges' dst (PERMUTED space)
    failed_id: jax.Array,  # int32[B, F] original edge ids (-1 pad)
    max_iters: int | None = None,
    interpret: bool = False,
) -> BlockedSpfOut:
    """Batched full SPF on the blocked planes.  Root is permuted id 0."""
    npad = g.in_src.shape[0]
    n = g.n_real  # may be traced under jit: used only in scalar arithmetic
    B, F = failed_dst.shape
    W = int(g.z_words.shape[2])  # static (shape-derived) even under jit
    n_pairs = int(g.bsrc.shape[0])
    fdst = jnp.asarray(failed_dst, jnp.int32)
    fid = jnp.asarray(failed_id, jnp.int32)
    limit = npad if max_iters is None else max_iters
    brange = jnp.arange(B)

    relax = _grid(n_pairs, npad, B, _relax_kernel, "", interpret)
    dmin_k = _grid(n_pairs, npad, B, _dmin_kernel, "", interpret)
    parent_k = _grid(n_pairs, npad, B, _parent_kernel, "ds", interpret)
    nh_k = _grid(n_pairs, npad, W * B, _nh_or_kernel, "ssd", interpret)

    # --- 1. distances (Jacobi min-plus fixpoint + failed-row repair)
    dist0 = jnp.full((npad, B), CAP, jnp.int32).at[g.rootp].set(0)

    def dcond(carry):
        _, changed, it = carry
        return changed & (it < limit)

    def dbody(carry):
        dist, _, it = carry
        capped = jnp.minimum(dist, CAP)
        acc = relax(g.bsrc, g.bdst, g.first, g.w, capped, capped)
        acc = _correct_dist(g, capped, acc, fdst, fid)
        return acc, jnp.any(acc != dist), it + 1

    dist, _, _ = jax.lax.while_loop(dcond, dbody, (dist0, jnp.bool_(True), 0))
    dist = jnp.minimum(dist, CAP)

    # --- 2. first parent: min DAG-parent distance, then min original id
    dmin = dmin_k(g.bsrc, g.bdst, g.first, g.w, dist, dist)
    dmin = _correct_dmin(g, dist, dmin, fdst, fid)
    parent_o = parent_k(
        g.bsrc, g.bdst, g.first, g.w, dist, dist, dmin,
        jnp.broadcast_to(g.orig_id[:, None], (npad, B)),
    )
    parent_o = _correct_parent(g, dist, dmin, parent_o, fdst, fid)

    # --- 3. hops along the first-parent chain (cheap [N, B] gathers)
    has_parent = parent_o < PBIG
    pperm = jnp.where(
        has_parent, g.orig2perm[jnp.minimum(parent_o, n - 1)], 0
    )
    big = jnp.int32(n + 1)
    hops0 = jnp.full((npad, B), big, jnp.int32).at[g.rootp].set(0)
    inc = g.inc[:, None]

    def hcond(carry):
        _, changed, it = carry
        return changed & (it < limit)

    def hbody(carry):
        hops, _, it = carry
        ph = jnp.where(has_parent, hops[pperm, brange[None, :]], big)
        new = jnp.minimum(hops, jnp.where(ph < big, ph + inc, big))
        return new, jnp.any(new != hops), it + 1

    hops, _, _ = jax.lax.while_loop(hcond, hbody, (hops0, jnp.bool_(True), 0))

    # --- 4. direct next-hop contributions (hops==0 parents: Z-set edges)
    zdist_s = dist[g.z_src[:, :, None], brange[None, None, :]]  # [M, C, B]
    zdist_d = dist[g.vz[:, None, None], brange[None, None, :]]  # [M, 1, B]
    # alive[M, C, B]: candidate edge not failed in scenario b
    hit = (g.z_eid[:, :, None, None] == fid[None, None, :, :]) & (
        fid[None, None, :, :] >= 0
    )  # [M, C, B, F]
    alive = ~hit.any(axis=3)
    zgate = hops[g.z_src[:, :, None], brange[None, None, :]] == 0
    zdag = (
        g.z_valid[:, :, None]
        & alive
        & (zdist_s < CAP)
        & (zdist_s + g.z_cost[:, :, None] == zdist_d)
        & zgate
    )  # [M, C, B]
    contrib = jnp.where(
        zdag[:, :, :, None], g.z_words[:, :, None, :], 0
    )  # [M, C, B, W]
    per_v = jax.lax.reduce(
        contrib, jnp.int32(0), jax.lax.bitwise_or, dimensions=(1,)
    )  # [M, B, W]
    direct = jnp.zeros((npad, B, W), jnp.int32).at[g.vz].set(per_v)
    # lane-packed [N_pad, W*B] layouts for the OR kernel
    direct_cat = jnp.concatenate([direct[:, :, wd] for wd in range(W)], axis=1)
    dist_cat = jnp.tile(dist, (1, W))
    gate_cat = jnp.tile((hops > 0).astype(jnp.int32), (1, W))

    # --- 5. inherit fixpoint (block OR kernel + failed-row repair)
    nh0 = direct_cat
    gate_plain = (hops > 0).astype(jnp.int32)

    def ncond(carry):
        _, changed, it = carry
        return changed & (it < limit)

    def nbody(carry):
        nh, _, it = carry
        acc = nh_k(
            g.bsrc, g.bdst, g.first, g.w, dist_cat, dist_cat, gate_cat,
            nh, direct_cat,
        )
        acc = _correct_nh(g, dist, gate_plain, direct_cat, acc, fdst, fid, W * B)
        return acc, jnp.any(acc != nh), it + 1

    nh_cat, _, _ = jax.lax.while_loop(ncond, nbody, (nh0, jnp.bool_(True), 0))

    # --- 6. assemble in original vertex space
    rows = g.orig2perm  # [n]: original v -> permuted row
    dist_o = dist[rows].T  # [B, n]
    unreach = dist_o >= UNREACH
    dist_out = jnp.where(unreach, jnp.int32(INF), dist_o)
    parent_out = jnp.where(
        unreach | (parent_o[rows].T >= n), jnp.int32(n), parent_o[rows].T
    )
    hops_out = jnp.where(unreach, jnp.int32(n + 1), hops[rows].T)
    nh_words = jnp.stack(
        [nh_cat[:, wd * B : (wd + 1) * B] for wd in range(W)], axis=2
    )  # [N_pad, B, W]
    nh_out = jnp.where(
        unreach[:, :, None], 0, jnp.transpose(nh_words[rows], (1, 0, 2))
    ).astype(jnp.uint32)
    return BlockedSpfOut(
        dist=dist_out, parent=parent_out, hops=hops_out, nexthops=nh_out
    )


def failed_edges_perm(
    perm_of: np.ndarray, topo: Topology, masks: np.ndarray, f_max: int = 4
):
    """Bool edge masks [B, E] -> (failed_dst_perm, failed_id) [B, F].

    ``perm_of`` is ``np.asarray(g.orig2perm)`` for the marshaled graph.
    """
    B, E = masks.shape
    fdst = np.full((B, f_max), -1, np.int32)
    fid = np.full((B, f_max), -1, np.int32)
    for b in range(B):
        failed = np.nonzero(~masks[b])[0]
        if len(failed) > f_max:
            raise ValueError(f"scenario {b}: {len(failed)} failures > {f_max}")
        for i, e in enumerate(failed):
            fdst[b, i] = perm_of[int(topo.edge_dst[e])]
            fid[b, i] = e
    return fdst, fid
