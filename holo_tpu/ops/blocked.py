"""Block-sparse dense min-plus SSSP (Pallas TPU kernel).

The gather-based engine (ops/spf_engine.py) is exact but gather-bound on
TPU.  This module reformulates the relax step as dense min-plus over the
nonzero S×S blocks of the adjacency matrix — no gathers in the hot loop;
each block pair is a VPU-friendly broadcast-add + min reduction:

    acc[v, b] = min_u W[u, v] + dist[u, b]        (per nonzero block)

What-if link failures stay EXACT without per-scenario weights: the kernel
runs on the static graph, then a tiny XLA correction pass recomputes the
failed edges' destination rows from their ELL in-edge lists with the
failed slots masked (only those rows can differ; Jacobi fixpoint is
preserved).  Scenario batches ride the lane dimension (dist is [N, B]).

In-kernel arithmetic uses CAP = 1<<28 as infinity with inputs re-capped
every iteration, keeping sums exact in int32 (real distances must stay
below 1<<27 — validated at marshal).  Outputs restore the canonical INF.

The kernel compiles on TPU Mosaic (the "row" layout variant — per-u row
extract + sublane broadcast).  Interpret mode is off unless a caller
passes ``interpret=True`` by name (the CPU tests do); no code path
selects it from the backend it finds.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from holo_tpu.ops.graph import INF, Topology, build_ell

CAP = np.int32(1 << 28)
UNREACH = 1 << 27  # values >= this are unreachable
S = 256  # vertex block size


class BlockGraph(NamedTuple):
    w: jax.Array  # int32[P, S, S] — w[p, u_local, v_local], CAP-filled
    bsrc: jax.Array  # int32[P] source block ids (sorted by bdst)
    bdst: jax.Array  # int32[P]
    first: jax.Array  # int32[P] 1 if first pair of its dst block
    # ELL planes for the correction pass:
    in_src: jax.Array  # int32[N_pad, K]
    in_cost: jax.Array  # int32[N_pad, K]
    in_valid: jax.Array  # bool[N_pad, K]
    in_edge_id: jax.Array  # int32[N_pad, K]
    n_real: int  # actual vertex count (<= N_pad)


def marshal_blocks(topo: Topology) -> BlockGraph:
    """Lower a Topology to block-sparse W + ELL correction planes.

    Requires unique (src, dst) pairs (parallel links must be pre-merged by
    min cost for distance purposes) and max real distance < 2**27.
    """
    n = topo.n_vertices
    nb = (n + S - 1) // S
    npad = nb * S
    src, dst, cost = topo.edge_src, topo.edge_dst, topo.edge_cost
    pairs = set(zip(src.tolist(), dst.tolist()))
    if len(pairs) != topo.n_edges:
        raise ValueError("parallel (src,dst) edges: merge before marshaling")
    # Exactness bound: the worst finite distance (n-1)·max_cost must stay
    # below UNREACH or finite paths would be misreported as unreachable.
    max_cost = int(cost.max()) if topo.n_edges else 0
    if (n - 1) * max_cost >= UNREACH:
        raise ValueError(
            f"distance bound (n-1)*max_cost = {(n - 1) * max_cost} "
            f">= {UNREACH}: use the gather engine (exact to 2**30)"
        )
    bj = src // S
    bi = dst // S
    key = bi.astype(np.int64) * nb + bj
    # Every destination block needs at least one pair or the kernel never
    # initializes its output rows — add identity CAP-only pairs for blocks
    # with no in-edges (their rows then just carry the previous distances).
    missing = sorted(set(range(nb)) - set((key // nb).tolist()))
    key_all = np.concatenate(
        [key, np.array([m * nb + m for m in missing], np.int64)]
    )
    uniq, inv_all = np.unique(key_all, return_inverse=True)
    inv = inv_all[: len(key)]
    p = len(uniq)
    bsrc = (uniq % nb).astype(np.int32)
    bdst = (uniq // nb).astype(np.int32)
    w = np.full((max(p, 1), S, S), CAP, np.int32)
    w[inv, src % S, dst % S] = np.minimum(cost, CAP)
    first = np.ones(max(p, 1), np.int32)
    first[1:] = (bdst[1:] != bdst[:-1]).astype(np.int32)

    ell = build_ell(topo, n_atoms=max(topo.n_atoms(), 1))
    in_src = np.zeros((npad, ell.k_pad), np.int32)
    in_cost = np.zeros((npad, ell.k_pad), np.int32)
    in_valid = np.zeros((npad, ell.k_pad), bool)
    in_edge_id = np.zeros((npad, ell.k_pad), np.int32)
    in_src[:n] = ell.in_src
    in_cost[:n] = ell.in_cost
    in_valid[:n] = ell.in_valid
    in_edge_id[:n] = ell.in_edge_id

    return BlockGraph(
        w=jnp.asarray(w),
        bsrc=jnp.asarray(bsrc),
        bdst=jnp.asarray(bdst),
        first=jnp.asarray(first),
        in_src=jnp.asarray(in_src),
        in_cost=jnp.asarray(in_cost),
        in_valid=jnp.asarray(in_valid),
        in_edge_id=jnp.asarray(in_edge_id),
        n_real=n,
    )


def _relax_kernel(bsrc_ref, bdst_ref, first_ref, w_ref, dsrc_ref, ddst_ref, out_ref):
    p = pl.program_id(0)

    @pl.when(first_ref[p] == 1)
    def _():
        out_ref[:] = ddst_ref[:]

    def body(u, acc):
        # Row extract [S] + sublane-transpose broadcast; compiles on Mosaic.
        contrib = w_ref[0, u, :][:, None] + dsrc_ref[u, :][None, :]
        return jnp.minimum(acc, contrib)

    out_ref[:] = jax.lax.fori_loop(0, S, body, out_ref[:])


def _make_relax(n_pairs: int, npad: int, batch: int, interpret: bool):
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_pairs,),
        in_specs=[
            pl.BlockSpec((1, S, S), lambda p, bs, bd, f: (p, 0, 0)),
            pl.BlockSpec((S, batch), lambda p, bs, bd, f: (bs[p], 0)),
            pl.BlockSpec((S, batch), lambda p, bs, bd, f: (bd[p], 0)),
        ],
        out_specs=pl.BlockSpec((S, batch), lambda p, bs, bd, f: (bd[p], 0)),
    )
    return pl.pallas_call(
        _relax_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((npad, batch), jnp.int32),
        interpret=interpret,
    )


def _correct(g: BlockGraph, dist_prev, acc, fdst, fid):
    """Exact repair of failed-edge destination rows.

    fdst/fid: int32[B, F] failed directed edges per scenario (-1 pad).
    Only rows fdst[b, f] can differ from the masked relax; recompute them
    from the ELL in-edge lists excluding the scenario's failed edge ids.
    """
    B, F = fdst.shape
    brange = jnp.arange(B)
    for f in range(F):  # F is tiny (typically 2) — static unroll
        v = fdst[:, f]  # [B]
        v_safe = jnp.maximum(v, 0)
        idx = g.in_src[v_safe]  # [B, K]
        w = g.in_cost[v_safe]
        valid = g.in_valid[v_safe]
        eid = g.in_edge_id[v_safe]
        # exclude ALL failed ids of this scenario (not just slot f)
        excl = (eid[:, :, None] == fid[:, None, :]) & (fid[:, None, :] >= 0)
        valid = valid & ~excl.any(axis=2)
        dvals = dist_prev[idx, brange[:, None]]  # [B, K]
        cand = jnp.where(valid & (dvals < UNREACH), dvals + w, CAP)
        prev_v = dist_prev[v_safe, brange]
        new_v = jnp.minimum(prev_v, cand.min(axis=1))
        cur = acc[v_safe, brange]
        repaired = jnp.where(v >= 0, new_v, cur)
        acc = acc.at[v_safe, brange].set(repaired)
    return acc


def whatif_distances_blocked(
    g: BlockGraph,
    root: int,
    failed_dst: np.ndarray,  # int32[B, F]
    failed_id: np.ndarray,
    max_iters: int | None = None,
    interpret: bool = False,
):
    """Batched what-if distances: int32[B, N] with canonical INF."""
    npad = g.in_src.shape[0]
    B = failed_dst.shape[0]
    n_pairs = int(g.bsrc.shape[0])
    fdst = jnp.asarray(failed_dst, jnp.int32)
    fid = jnp.asarray(failed_id, jnp.int32)
    limit = npad if max_iters is None else max_iters

    dist0 = jnp.full((npad, B), CAP, jnp.int32).at[root].set(0)
    if g.w.shape[0] == 0 or n_pairs == 0:
        # Edge-free graph: only the root is reachable; the kernel's grid
        # would be empty and its output uninitialized.
        out = dist0[: g.n_real].T
        return jnp.where(out >= UNREACH, jnp.int32(INF), out)

    relax = _make_relax(n_pairs, npad, B, interpret)

    def cond(carry):
        _, changed, it = carry
        return changed & (it < limit)

    def body(carry):
        dist, _, it = carry
        capped = jnp.minimum(dist, CAP)
        acc = relax(g.bsrc, g.bdst, g.first, g.w, capped, capped)
        acc = _correct(g, capped, acc, fdst, fid)
        return acc, jnp.any(acc != dist), it + 1

    dist, _, _ = jax.lax.while_loop(cond, body, (dist0, jnp.bool_(True), 0))
    out = dist[: g.n_real].T  # [B, N]
    return jnp.where(out >= UNREACH, jnp.int32(INF), out)


def failed_edges_from_masks(topo: Topology, masks: np.ndarray, f_max: int = 4):
    """Convert bool edge masks [B, E] to (failed_dst, failed_id) [B, F]."""
    B, E = masks.shape
    fdst = np.full((B, f_max), -1, np.int32)
    fid = np.full((B, f_max), -1, np.int32)
    for b in range(B):
        failed = np.nonzero(~masks[b])[0]
        if len(failed) > f_max:
            raise ValueError(f"scenario {b}: {len(failed)} failures > {f_max}")
        for i, e in enumerate(failed):
            fdst[b, i] = topo.edge_dst[e]
            fid[b, i] = e
    return fdst, fid
