"""Batched FRR kernel: all-roots SPF + vectorized LFA/rLFA/TI-LFA selection.

One jitted device program per (N, K, L, A) shape bucket computes

1. ``D`` — the all-roots distance matrix int32[N, N], a single vmapped
   dispatch of the lean distance relaxation (``sssp_distances``) over
   every vertex (no per-root Python loop);
2. the post-convergence SPF per protected link (``spf_whatif_batch``
   over the per-link failure masks — dist/parent/next-hop planes);
3. the repair selection tables (all int32[L, N], ``-1`` = none):

   - **LFA** (RFC 5286): candidate ``a`` protects ``(l, d)`` iff it does
     not ride link ``l`` and ``D[nbr_a, d] < D[nbr_a, root] + D[root, d]``
     (inequality 1, loop-free).  Node protection (inequality 3,
     ``D[nbr_a, d] < D[nbr_a, far_l] + D[far_l, d]``) is preferred;
     within a class the alternate minimizing
     ``(adj_cost + D[nbr, d], nbr, a)`` wins — a total order, so the
     scalar oracle reproduces the pick bit-for-bit.
   - **Remote LFA** (RFC 7490): per link, the PQ node minimizing
     ``(D[root, pq], pq)`` over (extended P-space ∩ Q-space ∩ routers);
     a destination is covered when forwarding from PQ cannot return
     through the root (``D[pq, d] < D[pq, root] + D[root, d]``).
   - **TI-LFA**: along the post-convergence path of each destination,
     ``P`` = the last router loop-free reachable from the path's first
     router (release neighbor) and ``Q`` = the next router after ``P``
     (reached with an adjacency segment).  ``q == -1`` means the path
     beyond ``P`` holds only pseudo-nodes (single node segment).  A
     two-segment repair is emitted only when normal forwarding from
     ``Q`` cannot loop back (``D[q, d] < D[q, root] + D[root, d]`` —
     sufficient here because every failure plane cuts through the
     root).  The per-destination P/S/release values propagate down the
     post SPT Jacobi-style: one gather per round, vmap-friendly, no
     host walk.

All comparisons are exact int32 with INF-guarded sums (finite operands
are < 2**30, so a single sum cannot wrap).  Every table is bit-compared
against :mod:`holo_tpu.frr.scalar` in tests/test_frr_parity.py.

Memory note: the LFA stage materializes [L, A, N] bool intermediates and
``D`` is [N, N] int32 — size the batch like the 10k what-if batch, not
the 50k single-SPF path.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from holo_tpu.frr.inputs import FrrInputs
from holo_tpu.ops.graph import INF
from holo_tpu.ops.spf_engine import (
    DeviceGraph,
    spf_whatif_batch,
    sssp_distances,
)


class FrrTensors(NamedTuple):
    """Device-side selection tables (padded shapes)."""

    lfa_adj: jax.Array  # int32[L, N] candidate index or -1
    lfa_nodeprot: jax.Array  # int32[L, N] 1 = chosen LFA node-protects
    rlfa_pq: jax.Array  # int32[L, N] PQ vertex or -1
    tilfa_p: jax.Array  # int32[L, N] P vertex or -1
    tilfa_q: jax.Array  # int32[L, N] Q vertex or -1 (single-segment)
    post_dist: jax.Array  # int32[L, N]
    post_nh: jax.Array  # uint32[L, N, W] post-convergence atom words


def _fadd(a, b):
    """INF-guarded int32 sum: INF when either side is unreachable."""
    return jnp.where((a < INF) & (b < INF), a + b, INF)


def frr_batch(
    g: DeviceGraph,
    root,
    link_far: jax.Array,
    link_cost: jax.Array,
    link_valid: jax.Array,
    edge_masks: jax.Array,
    adj_nbr: jax.Array,
    adj_cost: jax.Array,
    adj_link: jax.Array,
    adj_valid: jax.Array,
    link_srlg: jax.Array | None = None,
    adj_srlg: jax.Array | None = None,
    require_np: jax.Array | bool = False,
    max_iters: int | None = None,
) -> FrrTensors:
    """``link_srlg``/``adj_srlg`` (uint32 SRLG bitmasks, ISSUE 10): a
    repair candidate sharing ANY risk group with the protected link is
    excluded from the usable plane — all-zero planes (the default, and
    the disarmed policy) exclude nothing, so the mask costs one
    elementwise AND.  ``require_np`` (traced bool) restricts the LFA
    pick to node-protecting candidates (RFC 5286 inequality 3 as a hard
    policy instead of a preference); destinations without one fall
    through to remote-LFA / TI-LFA exactly like uncovered ones."""
    n = g.in_src.shape[0]
    nlinks = link_far.shape[0]
    nadj = adj_nbr.shape[0]
    vidx = jnp.arange(n)

    # ---- 1. all-roots distance matrix: ONE vmapped dispatch.
    D = jax.vmap(lambda r: sssp_distances(g, r, None, max_iters))(vidx)

    # ---- 2. post-convergence SPF per protected link (one batch).
    post = spf_whatif_batch(g, root, edge_masks, max_iters)

    droot = D[root]  # int32[N] primary distances
    valid_d = (droot < INF) & (vidx != root)  # destinations worth protecting

    # ---- 3a. LFA inequalities + lexicographic selection.
    dn = D[adj_nbr]  # [A, N] from each candidate neighbor
    dn_root = dn[:, root]  # [A]
    loopfree = adj_valid[:, None] & (
        dn < _fadd(dn_root[:, None], droot[None, :])
    )  # [A, N]
    usable = (
        adj_valid[None, :]
        & link_valid[:, None]
        & (adj_link[None, :] != jnp.arange(nlinks)[:, None])
    )  # [L, A]
    if link_srlg is not None and adj_srlg is not None:
        # Shared-risk exclusion: the vectorized SRLG policy mask.
        usable = usable & (
            (link_srlg[:, None] & adj_srlg[None, :]) == jnp.uint32(0)
        )
    dfar = D[link_far]  # [L, N]
    dn_far = dn[:, link_far].T  # [L, A]: D[nbr_a, far_l]
    nodeprot = dn[None, :, :] < _fadd(
        dn_far[:, :, None], dfar[:, None, :]
    )  # [L, A, N]
    cand = usable[:, :, None] & loopfree[None, :, :] & valid_d[None, None, :]
    np_cand = cand & nodeprot
    has_np = np_cand.any(axis=1)  # [L, N]
    # Preference becomes policy under require_np: only node-protecting
    # candidates are selectable at all.
    sel = jnp.where(
        jnp.asarray(require_np),
        np_cand,
        jnp.where(has_np[:, None, :], np_cand, cand),
    )
    altdist = _fadd(adj_cost[:, None], dn)  # [A, N]
    k1 = jnp.where(sel, altdist[None, :, :], INF)
    m1 = k1.min(axis=1)  # [L, N]
    sel2 = sel & (altdist[None, :, :] == m1[:, None, :]) & (m1 < INF)[:, None, :]
    k2 = jnp.where(sel2, adj_nbr[None, :, None], n)
    m2 = k2.min(axis=1)
    sel3 = sel2 & (adj_nbr[None, :, None] == m2[:, None, :])
    k3 = jnp.where(sel3, jnp.arange(nadj)[None, :, None], nadj)
    lfa_adj = jnp.where(m1 < INF, k3.min(axis=1), -1).astype(jnp.int32)
    lfa_nodeprot = ((lfa_adj >= 0) & has_np).astype(jnp.int32)

    # ---- 3b. remote LFA: extended P-space ∩ Q-space, one PQ per link.
    pspace = droot[None, :] < _fadd(link_cost[:, None], dfar)  # [L, N]
    ext_any = (usable[:, :, None] & loopfree[None, :, :]).any(axis=1)
    extp = (pspace | ext_any) & link_valid[:, None]
    dto_far = D[:, link_far].T  # [L, N]: D[v, far_l]
    dto_root = D[:, root]  # [N]
    qspace = dto_far < _fadd(dto_root[None, :], link_cost[:, None])
    pq_cand = extp & qspace & g.is_router[None, :] & (vidx != root)[None, :]
    kq = jnp.where(pq_cand, droot[None, :], INF)
    mq = kq.min(axis=1)  # [L]
    vq = jnp.where(pq_cand & (kq == mq[:, None]), vidx[None, :], n).min(axis=1)
    pq = jnp.where(mq < INF, vq, -1).astype(jnp.int32)  # [L]
    pqc = jnp.clip(pq, 0, n - 1)
    dpq = D[pqc]  # [L, N]
    rlfa_ok = (
        (pq >= 0)[:, None]
        & (dpq < _fadd(dpq[:, root][:, None], droot[None, :]))
        & valid_d[None, :]
    )
    rlfa_pq = jnp.where(rlfa_ok, pq[:, None], -1).astype(jnp.int32)

    # ---- 3c. TI-LFA: release-neighbor (n1) + last-loop-free-router (P)
    # + successor (S) propagated down the post SPT.
    par = post.parent  # [L, N], n = no parent
    parc = jnp.clip(par, 0, n - 1)
    has_par = par < n
    is_rtr = g.is_router
    limit = (2 * n + 4) if max_iters is None else (2 * max_iters + 4)

    n1_0 = jnp.full((nlinks, n), n, jnp.int32)  # n = none yet
    p_0 = jnp.where(vidx == root, root, -1)[None, :].repeat(nlinks, 0)
    s_0 = jnp.full((nlinks, n), -1, jnp.int32)

    def cond(carry):
        _, _, _, changed, it = carry
        return changed & (it < limit)

    def body(carry):
        n1, p, s, _, it = carry
        n1_u = jnp.take_along_axis(n1, parc, axis=1)
        p_u = jnp.take_along_axis(p, parc, axis=1)
        s_u = jnp.take_along_axis(s, parc, axis=1)
        # First router on the path (the repair's release neighbor).
        n1_new = jnp.where(
            (vidx == root)[None, :] | ~has_par,
            n,
            jnp.where(
                n1_u < n, n1_u, jnp.where(is_rtr[None, :], vidx[None, :], n)
            ),
        ).astype(jnp.int32)
        # v is loop-free reachable from its release neighbor: the P mark.
        n1c = jnp.clip(n1_new, 0, n - 1)
        d_n1_v = D[n1c, vidx[None, :]]  # [L, N]
        d_n1_root = D[n1c, root]
        pmark = (
            (n1_new < n)
            & is_rtr[None, :]
            & (d_n1_v < _fadd(d_n1_root, droot[None, :]))
        )
        p_new = jnp.where(
            (vidx == root)[None, :],
            root,
            jnp.where(~has_par, -1, jnp.where(pmark, vidx[None, :], p_u)),
        ).astype(jnp.int32)
        s_new = jnp.where(
            (vidx == root)[None, :] | ~has_par,
            -1,
            jnp.where(
                ~is_rtr[None, :],
                s_u,
                jnp.where(
                    pmark, -1, jnp.where(s_u >= 0, s_u, vidx[None, :])
                ),
            ),
        ).astype(jnp.int32)
        changed = (
            jnp.any(n1_new != n1)
            | jnp.any(p_new != p)
            | jnp.any(s_new != s)
        )
        return n1_new, p_new, s_new, changed, it + 1

    _, p_fix, s_fix, _, _ = jax.lax.while_loop(
        cond, body, (n1_0, p_0, s_0, jnp.bool_(True), 0)
    )

    ok = (
        link_valid[:, None]
        & valid_d[None, :]
        & (post.dist < INF)
        & (p_fix >= 0)
    )
    sc = jnp.clip(s_fix, 0, n - 1)
    d_s = D[sc, vidx[None, :]]  # D[S, d]
    d_s_root = D[sc, root]
    tail_ok = d_s < _fadd(d_s_root, droot[None, :])
    single = s_fix < 0
    double = (s_fix >= 0) & tail_ok
    tilfa_p = jnp.where(ok & (single | double), p_fix, -1).astype(jnp.int32)
    tilfa_q = jnp.where(ok & double, s_fix, -1).astype(jnp.int32)

    return FrrTensors(
        lfa_adj=lfa_adj,
        lfa_nodeprot=lfa_nodeprot,
        rlfa_pq=rlfa_pq,
        tilfa_p=tilfa_p,
        tilfa_q=tilfa_q,
        post_dist=post.dist,
        post_nh=post.nexthops,
    )


@dataclass
class BackupTable:
    """Host-side backup tables for one topology (unpadded), produced by
    either the batched kernel or the scalar oracle — bit-identical."""

    inputs: FrrInputs
    root: int
    lfa_adj: np.ndarray  # int32[L, N]
    lfa_nodeprot: np.ndarray  # int32[L, N]
    rlfa_pq: np.ndarray  # int32[L, N]
    tilfa_p: np.ndarray  # int32[L, N]
    tilfa_q: np.ndarray  # int32[L, N]
    post_dist: np.ndarray  # int32[L, N]
    post_nh: np.ndarray  # uint32[L, N, W]

    @property
    def n_links(self) -> int:
        return self.inputs.n_links

    def link_of_atom(self, atom: int) -> int | None:
        return self.inputs.atom_link.get(atom)

    def coverage(self) -> float:
        """Fraction of (protected link, protectable destination) pairs
        with any repair — the headline operational stat."""
        protected = (
            (self.lfa_adj >= 0) | (self.rlfa_pq >= 0) | (self.tilfa_p >= 0)
        )
        # Destinations a repair could exist for: still reachable after
        # the failure (a cut destination is unprotectable by definition).
        eligible = self.post_dist < INF
        eligible[:, self.root] = False
        denom = int(eligible.sum())
        if denom == 0:
            return 1.0
        return float((protected & eligible).sum()) / denom
