"""Batched FRR kernel: the distance rows the selection reads, in blocks,
and vectorized LFA/rLFA/TI-LFA selection over them.

One jitted device program per (N, K, L, A) shape bucket computes

1. the **distance rows the selection reads**, and no others, each as a
   column of an ``int32[N, B]`` plane relaxed to its fixpoint in blocks
   of fixed width (:func:`rows_block`, over the compact
   :class:`RowsGraph` the engine builds from the topology's edges):

   - forward rows ``D[x, :]`` for the *base* sources: the root, every
     router the root reaches in one hop or through one attached
     network (the release neighbours ``n1`` of TI-LFA and the LFA
     candidates are among them) and every protected link's far end;
   - reversed-graph rows, i.e. columns ``D[:, t]``, for ``t`` = the root
     and every far end (costs may differ by direction, so a column is
     a run of its own on the reversed edges);
   - forward rows for the vertices known only after selection — each
     link's remote-LFA PQ node and every distinct TI-LFA successor
     ``S`` — in a loop of ``DYN_BLOCK``-wide blocks whose count the
     device decides (one compiled program whatever the count);

2. the post-convergence SPF per protected link, every link a column
   of one batch (:func:`post_spf_block`) on the same compact forward
   graph, its slots carrying their direct next-hop atoms: the slot
   that holds the protected edge masked in its link's column (an edge
   INTO the root never carries a path from it, so the reverse
   direction needs no mask), with ``spf_one``'s semantics bit for bit;
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
     root).  Release neighbour, P and S are each a nearest ancestor of
     a kind on the post SPT's router tree, found by pointer doubling:
     log2(depth) gathers, no host walk.

All comparisons are exact int32 with INF-guarded sums (finite operands
are < 2**30, so a single sum cannot wrap).  Every table is bit-compared
against :mod:`holo_tpu.frr.scalar` in tests/test_frr_parity.py.

The program also returns ``stats`` (int32[len(STATS)]): the rows it
relaxed by direction, the relaxation rounds summed over its blocks,
each block's valid rows times its rounds (the reads a roofline counts),
the distinct successors and dynamic blocks, two guards that must
read 0 (a protected edge not found in the compact graph, a release
neighbour without a base row) — the engine refuses a table with either
set, and the breaker serves the oracle — and the post-convergence
batch's rounds (its fixpoints' summed) and slots (the compact graph's
slots times its link columns, what one of its rounds reads).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from holo_tpu.frr.inputs import FrrInputs
from holo_tpu.ops.graph import INF
from holo_tpu.ops.spf_engine import DeviceGraph

#: width of one block of the rows known only on the device (PQ, S)
DYN_BLOCK = 128
#: names of the ``stats`` vector's entries, in order
STATS = (
    "rows_forward",
    "rows_reverse",
    "rounds",
    "row_rounds_forward",
    "row_rounds_reverse",
    "successors",
    "dyn_blocks",
    "unmatched_links",
    "missing_rows",
    "post_rounds",
    "post_slots",
)


class RowsGraph(NamedTuple):
    """One direction of the graph for the distance rows, in two ELL
    planes: the first ``Klo`` in-edges of every vertex, and the rest of
    the in-edges of the few vertices with more (``hi_vtx``; pad rows
    carry ``N``).  An empty slot's source is ``N``, the row of the
    relaxation plane that stays INF.  The forward graph also carries
    each slot's direct next-hop atom (-1 none), which the
    post-convergence SPFs read; the reversed graph has none.  Built on
    the host from the topology's edge list
    (:func:`holo_tpu.frr.inputs.rows_graphs`)."""

    lo_src: jax.Array  # int32[N, Klo]
    lo_cost: jax.Array  # int32[N, Klo]
    hi_vtx: jax.Array  # int32[H]
    hi_src: jax.Array  # int32[H, Khi]
    hi_cost: jax.Array  # int32[H, Khi]
    lo_atom: jax.Array | None = None  # int32[N, Klo]
    hi_atom: jax.Array | None = None  # int32[H, Khi]

    @property
    def slots(self) -> int:
        return int(self.lo_src.size + self.hi_src.size)


class FrrTensors(NamedTuple):
    """Device-side selection tables (padded shapes)."""

    lfa_adj: jax.Array  # int32[L, N] candidate index or -1
    lfa_nodeprot: jax.Array  # int32[L, N] 1 = chosen LFA node-protects
    rlfa_pq: jax.Array  # int32[L, N] PQ vertex or -1
    tilfa_p: jax.Array  # int32[L, N] P vertex or -1
    tilfa_q: jax.Array  # int32[L, N] Q vertex or -1 (single-segment)
    post_dist: jax.Array  # int32[L, N]
    post_nh: jax.Array  # uint32[L, N, W] post-convergence atom words
    stats: jax.Array  # int32[len(STATS)]


def _fadd(a, b):
    """INF-guarded int32 sum: INF when either side is unreachable."""
    return jnp.where((a < INF) & (b < INF), a + b, INF)


def _relax(dist, src, cost, ok=True):
    """min over each row's slots of ``dist[src] + cost`` (INF where the
    source is unreachable or the slot not ``ok``): ``[rows, B]`` from a
    ``[N + 1, B]`` plane, the gather's indices shared by the B columns."""
    d = dist[src]  # [rows, slots, B]
    return jnp.where(ok & (d < INF), d + cost[:, :, None], INF).min(axis=1)


def rows_block(rg: RowsGraph, srcs: jax.Array, limit: int):
    """Distances from each of ``srcs`` (int32[B]; a value outside
    ``[0, N)`` is no source and its column stays INF) as the columns of
    an int32[N, B] plane, relaxed until no entry moves.  Returns the
    plane and the rounds it took.  The gathers share their indices
    across the block (``dist[src]`` of whole rows), the layout the
    what-if batch runs fast in."""
    n = rg.lo_src.shape[0]
    b = srcs.shape[0]
    cols = jnp.arange(b)
    start = jnp.where((srcs >= 0) & (srcs < n), srcs, n + 1)
    dist0 = (
        jnp.full((n + 1, b), INF, jnp.int32)
        .at[start, cols]
        .set(0, mode="drop")
    )

    def cond(carry):
        _, changed, it = carry
        return changed & (it < limit)

    def body(carry):
        dist, _, it = carry
        cand = _relax(dist, rg.lo_src, rg.lo_cost)
        cand = cand.at[rg.hi_vtx].min(
            _relax(dist, rg.hi_src, rg.hi_cost), mode="drop"
        )
        new = jnp.minimum(dist[:n], cand)
        changed = jnp.any(new != dist[:n])
        return dist.at[:n].set(new), changed, it + 1

    dist, _, rounds = jax.lax.while_loop(
        cond, body, (dist0, jnp.bool_(True), jnp.int32(0))
    )
    return dist[:n], rounds


class PostTensors(NamedTuple):
    """The post-convergence SPF of every protected link
    (:func:`post_spf_block`), a row per link."""

    dist: jax.Array  # int32[L, N]; INF unreachable
    parent: jax.Array  # int32[L, N]; first parent, N if none
    nexthops: jax.Array  # uint32[L, N, W] atom bitmask
    rounds: jax.Array  # int32: every fixpoint's rounds, summed
    unmatched: jax.Array  # int32: valid links with no slot


def _fold(lo, hi, hi_vtx, op):
    """``lo`` ([N, L]) with each overflow row of ``hi`` ([H, L]) combined
    into its vertex's row by ``op`` (pad rows, vertex N, dropped)."""
    at = jnp.clip(hi_vtx, 0, lo.shape[0] - 1)
    return lo.at[hi_vtx].set(op(lo[at], hi), mode="drop")


def _or_k(x):
    """Bitwise OR over the slot axis (1) of a uint32 plane."""
    return jax.lax.reduce(x, jnp.uint32(0), jax.lax.bitwise_or, (1,))


def post_spf_block(
    pg: RowsGraph,
    is_router: jax.Array,
    root,
    link_far: jax.Array,
    link_cost: jax.Array,
    link_atom: jax.Array,
    link_valid: jax.Array,
    n_words: int,
    max_iters: int | None = None,
) -> PostTensors:
    """``spf_one`` for every protected link at once, each a column of
    ``[N + 1, L]`` planes over the compact forward graph ``pg`` (its
    atoms set), with the slot that holds link ``l``'s edge masked in
    column ``l``.  A round's gathers are ``x[src]`` of whole rows, their
    indices shared by the L columns; the overflow rows fold into their
    vertices by scatter.  Row N is the empty slot's source: INF, no
    hops, no words.

    The protected edge's slot is the first of the far end's slots (low
    plane, then overflow) with source ``root``, cost ``link_cost`` and
    atom ``link_atom`` — slots alike in all three are the same edge to
    the SPF.  A pad link masks nothing; a valid link with no such slot
    masks nothing and is counted in ``unmatched``.

    Bit for bit ``spf_one``'s result on the ELL of the same edges with
    the same slot masked: the same INF-guarded relaxation, the same
    rounds under ``max_iters``, the DAG without the root, the first
    parent minimising ``(dist[u], u)`` over both planes, hops along it
    counting routers, and per word the direct atoms of DAG slots whose
    source has no hops OR the inherited words of the rest."""
    n, klo = pg.lo_src.shape
    nh, khi = pg.hi_src.shape
    nl = link_far.shape[0]
    lanes = jnp.arange(nl)
    limit = n if max_iters is None else max_iters
    big = jnp.int32(n + 1)
    hi_vtx = pg.hi_vtx
    hv = jnp.clip(hi_vtx, 0, n - 1)  # a pad row reads a row; _fold drops it

    # -- the protected edge's slot in each link's column.
    far = jnp.clip(link_far, 0, n - 1)
    hi_row = (
        jnp.full((n,), nh, jnp.int32)
        .at[hi_vtx]
        .set(jnp.arange(nh, dtype=jnp.int32), mode="drop")[far]
    )  # [L], H: far has no overflow row
    hr = jnp.clip(hi_row, 0, nh - 1)

    def match(src, cost, atom):  # [L, K]
        return (
            (src == root)
            & (cost == link_cost[:, None])
            & (atom == link_atom[:, None])
        )

    m_lo = match(pg.lo_src[far], pg.lo_cost[far], pg.lo_atom[far])
    m_hi = match(pg.hi_src[hr], pg.hi_cost[hr], pg.hi_atom[hr]) & (
        hi_row < nh
    )[:, None]
    in_lo = m_lo.any(axis=1) & link_valid
    in_hi = m_hi.any(axis=1) & link_valid & ~in_lo
    unmatched = (link_valid & ~in_lo & ~in_hi).sum().astype(jnp.int32)
    lo_ok = (
        jnp.ones((n, klo, nl), bool)
        .at[jnp.where(in_lo, far, n), jnp.argmax(m_lo, axis=1), lanes]
        .set(False, mode="drop")
    )
    hi_ok = (
        jnp.ones((nh, khi, nl), bool)
        .at[jnp.where(in_hi, hi_row, nh), jnp.argmax(m_hi, axis=1), lanes]
        .set(False, mode="drop")
    )

    def loop(step, x0):
        """``step`` from ``x0`` ([N + 1, L]; row N stays) until no entry
        moves or ``limit`` rounds; the plane and its rounds."""

        def cond(carry):
            _, changed, it = carry
            return changed & (it < limit)

        def body(carry):
            x, _, it = carry
            new = step(x)
            return x.at[:n].set(new), jnp.any(new != x[:n]), it + 1

        x, _, it = jax.lax.while_loop(cond, body, (x0, jnp.bool_(True), 0))
        return x, it

    # -- distances.
    def dist_step(dist):
        cand = _fold(
            _relax(dist, pg.lo_src, pg.lo_cost, lo_ok),
            _relax(dist, pg.hi_src, pg.hi_cost, hi_ok), hi_vtx, jnp.minimum,
        )
        return jnp.minimum(dist[:n], cand)

    dist0 = jnp.full((n + 1, nl), INF, jnp.int32).at[root].set(0)
    dist, r_dist = loop(dist_step, dist0)

    # -- the shortest-path DAG and the first parent, over both planes.
    not_root = (jnp.arange(n + 1) != root)[:, None]
    d_v = jnp.where(not_root, dist, INF)  # the root has no DAG parents

    def dag_of(dv, src, cost, ok):  # dv: the slot's vertex, [rows, L]
        d = dist[src]
        dv = dv[:, None, :]
        return d, ok & (d < INF) & (dv < INF) & (d + cost[:, :, None] == dv)

    d_lo, dag_lo = dag_of(d_v[:n], pg.lo_src, pg.lo_cost, lo_ok)
    d_hi, dag_hi = dag_of(d_v[hi_vtx], pg.hi_src, pg.hi_cost, hi_ok)
    dmin = _fold(
        jnp.where(dag_lo, d_lo, INF).min(axis=1),
        jnp.where(dag_hi, d_hi, INF).min(axis=1), hi_vtx, jnp.minimum,
    )  # [N, L]

    def first(src, d, dag, dm):  # dm: the slot's vertex's dmin
        pick = dag & (d == dm[:, None, :])
        return jnp.where(pick, src[:, :, None], n).min(axis=1)

    parent = _fold(
        first(pg.lo_src, d_lo, dag_lo, dmin),
        first(pg.hi_src, d_hi, dag_hi, dmin[hv]), hi_vtx, jnp.minimum,
    ).astype(jnp.int32)  # [N, L], N = no parent

    # -- hops along the first parent: every slot whose source is the
    # parent carries hops[parent], so the gathers stay x[src].
    ps_lo = pg.lo_src[:, :, None] == parent[:, None, :]
    ps_hi = pg.hi_src[:, :, None] == parent[hv][:, None, :]
    inc = is_router.astype(jnp.int32)[:, None]

    def hops_step(hops):
        ph = _fold(
            jnp.where(ps_lo, hops[pg.lo_src], big).min(axis=1),
            jnp.where(ps_hi, hops[pg.hi_src], big).min(axis=1), hi_vtx,
            jnp.minimum,
        )
        return jnp.minimum(hops[:n], jnp.where(ph < big, ph + inc, big))

    hops0 = jnp.where(jnp.arange(n + 1) == root, 0, big)[:, None]
    hops, r_hops = loop(hops_step, jnp.broadcast_to(hops0, (n + 1, nl)))

    # -- next-hop words, one fixpoint per word: a DAG slot whose source
    # has no hops gives its direct atom, the others inherit.
    direct_lo = dag_lo & (hops[pg.lo_src] == 0)
    direct_hi = dag_hi & (hops[pg.hi_src] == 0)
    inh_lo = dag_lo & ~direct_lo
    inh_hi = dag_hi & ~direct_hi

    def atom_word(atom, wi):
        hit = (atom >= 0) & ((atom >> 5) == wi)
        bit = jnp.left_shift(jnp.uint32(1), (atom & 31).astype(jnp.uint32))
        return jnp.where(hit, bit, jnp.uint32(0))[:, :, None]

    def nh_step(nh):
        inherit = _fold(
            _or_k(jnp.where(inh_lo, nh[pg.lo_src], jnp.uint32(0))),
            _or_k(jnp.where(inh_hi, nh[pg.hi_src], jnp.uint32(0))), hi_vtx,
            jnp.bitwise_or,
        )
        return nh[:n] | inherit

    words, r_nh = [], jnp.int32(0)
    for wi in range(n_words):
        seed = _fold(
            _or_k(jnp.where(direct_lo, atom_word(pg.lo_atom, wi), 0)),
            _or_k(jnp.where(direct_hi, atom_word(pg.hi_atom, wi), 0)),
            hi_vtx, jnp.bitwise_or,
        )
        seed = jnp.concatenate([seed, jnp.zeros((1, nl), jnp.uint32)])
        nh_w, r_w = loop(nh_step, seed)
        words.append(nh_w[:n].T)
        r_nh = r_nh + r_w

    return PostTensors(
        dist=dist[:n].T,
        parent=parent.T,
        nexthops=jnp.stack(words, axis=-1),
        rounds=(r_dist + r_hops + r_nh).astype(jnp.int32),
        unmatched=unmatched,
    )


def frr_batch(
    g: DeviceGraph,
    root,
    fwd: RowsGraph,
    rev: RowsGraph,
    base_src: jax.Array,
    rev_src: jax.Array,
    link_far: jax.Array,
    link_cost: jax.Array,
    link_atom: jax.Array,
    link_valid: jax.Array,
    adj_nbr: jax.Array,
    adj_cost: jax.Array,
    adj_link: jax.Array,
    adj_valid: jax.Array,
    link_srlg: jax.Array | None = None,
    adj_srlg: jax.Array | None = None,
    require_np: jax.Array | bool = False,
    max_iters: int | None = None,
) -> FrrTensors:
    """``base_src``: the forward base sources (root first; pads outside
    ``[0, N)``), ``rev_src``: the reversed-graph sources (root, then the
    far end of link ``l`` at ``1 + l``).

    ``link_srlg``/``adj_srlg`` (uint32 SRLG bitmasks): a
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
    nbase = base_src.shape[0]
    vidx = jnp.arange(n)
    limit = n if max_iters is None else max_iters

    # ---- 1. base forward rows and reversed-graph columns.
    rows_f, rounds_f = rows_block(fwd, base_src, limit)  # [N, Bb]
    rows_r, rounds_r = rows_block(rev, rev_src, limit)  # [N, Br]
    base_ok = (base_src >= 0) & (base_src < n)
    col_of = (
        jnp.full((n,), nbase, jnp.int32)
        .at[jnp.where(base_ok, base_src, n)]
        .set(jnp.arange(nbase, dtype=jnp.int32), mode="drop")
    )

    def base_rows(xs):
        """D[xs, :] as [len(xs), N] (xs must be base sources)."""
        return rows_f[:, jnp.clip(col_of[xs], 0, nbase - 1)].T

    # ---- 2. post-convergence SPF per protected link (one batch).
    post = post_spf_block(
        fwd, g.is_router, root, link_far, link_cost, link_atom, link_valid,
        g.direct_nh_words.shape[2], max_iters,
    )

    droot = rows_f[:, 0]  # int32[N] primary distances
    valid_d = (droot < INF) & (vidx != root)  # destinations worth protecting

    # ---- 3a. LFA inequalities + lexicographic selection.
    dn = base_rows(adj_nbr)  # [A, N] from each candidate neighbor
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
    dfar = base_rows(link_far)  # [L, N]
    dn_far = dn[:, jnp.clip(link_far, 0, n - 1)].T  # [L, A]: D[nbr_a, far_l]
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
    dto_far = rows_r[:, 1 : 1 + nlinks].T  # [L, N]: D[v, far_l]
    dto_root = rows_r[:, 0]  # [N]: D[v, root]
    qspace = dto_far < _fadd(dto_root[None, :], link_cost[:, None])
    pq_cand = extp & qspace & g.is_router[None, :] & (vidx != root)[None, :]
    kq = jnp.where(pq_cand, droot[None, :], INF)
    mq = kq.min(axis=1)  # [L]
    vq = jnp.where(pq_cand & (kq == mq[:, None]), vidx[None, :], n).min(axis=1)
    pq = jnp.where(mq < INF, vq, -1).astype(jnp.int32)  # [L]

    # ---- 3c. TI-LFA: release-neighbor (n1) + last-loop-free-router (P)
    # + successor (S) along the post SPT, by pointer doubling over the
    # router tree (a router's router parent skips the one network a path
    # may cross): each value is a nearest ancestor of a kind, found in
    # log2(depth) gathers instead of one propagation round per hop.
    has_par = post.parent < n  # [L, N], n = no parent
    par = jnp.where(has_par, post.parent, n).astype(jnp.int32)
    is_rtr = g.is_router
    isr = is_rtr[None, :]
    at_root = (vidx == root)[None, :]
    vv = jnp.broadcast_to(vidx.astype(jnp.int32), (nlinks, n))
    rtr_ext = jnp.concatenate([is_rtr, jnp.zeros((1,), bool)])
    rpar = jnp.where(rtr_ext[par], par, _up(par, par))  # router parent

    def on_nets(x):
        """A network takes its parent's value (the parent is a router)."""
        return jnp.where(isr, x, _up(x, par))

    # First router on the path: the topmost router below the root.
    step = jnp.where(isr & has_par, jnp.where(rpar == root, vv, rpar), n)
    n1_fix = on_nets(_nearest(step))
    # v is loop-free reachable from its release neighbor: the P mark.
    c1 = jnp.clip(col_of[jnp.clip(n1_fix, 0, n - 1)], 0, nbase - 1)
    d_n1_v = rows_f[vidx[None, :], c1]  # [L, N]: D[n1, v]
    d_n1_root = rows_f[root, c1]
    pmark = (
        (n1_fix < n) & isr & has_par
        & (d_n1_v < _fadd(d_n1_root, droot[None, :]))
    )
    # P: the nearest marked router at or above v (the root counts).
    marked = pmark | at_root
    step = jnp.where(
        at_root, root, jnp.where(isr & has_par, jnp.where(pmark, vv, rpar), n)
    )
    p_near = on_nets(_nearest(step))
    p_fix = jnp.where(p_near < n, p_near, -1).astype(jnp.int32)
    # S: the topmost unmarked router of the run that ends at v.
    step = jnp.where(
        isr & has_par & ~pmark, jnp.where(_up(marked, rpar), vv, rpar), n
    )
    s_near = on_nets(_nearest(step))
    s_fix = jnp.where(s_near < n, s_near, -1).astype(jnp.int32)
    # A release neighbour is a router one hop (or one network) from the
    # root, so it has a base row; the guard says when it had none.
    n1_seen = n1_fix < n
    missing = (
        n1_seen & (col_of[jnp.clip(n1_fix, 0, n - 1)] >= nbase)
    ).sum().astype(jnp.int32)

    ok = (
        link_valid[:, None]
        & valid_d[None, :]
        & (post.dist < INF)
        & (p_fix >= 0)
    )

    # ---- 4. the rows known only now: each PQ node and every distinct
    # successor S, compacted and relaxed DYN_BLOCK at a time.
    pq_need = (pq >= 0) & link_valid
    s_need = ok & (s_fix >= 0)
    need = (
        jnp.zeros((n + 1,), bool)
        .at[jnp.where(pq_need, pq, n)]
        .set(True)
        .at[jnp.where(s_need, s_fix, n)]
        .set(True)[:n]
    )
    n_need = need.sum().astype(jnp.int32)
    slot = jnp.cumsum(need).astype(jnp.int32) - 1  # slot of v if needed
    order = (
        jnp.full((n + DYN_BLOCK,), n, jnp.int32)
        .at[jnp.where(need, slot, n + DYN_BLOCK)]
        .set(vidx.astype(jnp.int32), mode="drop")
    )
    pq_slot = jnp.where(pq_need, slot[jnp.clip(pq, 0, n - 1)], -1)
    s_slot = jnp.where(s_need, slot[jnp.clip(s_fix, 0, n - 1)], -1)

    def dcond(carry):
        return carry[0] * DYN_BLOCK < n_need

    def dbody(carry):
        b, dpq, dpq_root, d_s, d_s_root, rounds, rr = carry
        lo = b * DYN_BLOCK
        srcs = jax.lax.dynamic_slice(order, (lo,), (DYN_BLOCK,))
        rows_b, r_b = rows_block(fwd, srcs, limit)  # [N, DYN_BLOCK]
        in_pq = (pq_slot >= lo) & (pq_slot < lo + DYN_BLOCK)
        cpq = jnp.clip(pq_slot - lo, 0, DYN_BLOCK - 1)
        dpq = jnp.where(in_pq[:, None], rows_b[:, cpq].T, dpq)
        dpq_root = jnp.where(in_pq, rows_b[root, cpq], dpq_root)
        in_s = (s_slot >= lo) & (s_slot < lo + DYN_BLOCK)
        cs = jnp.clip(s_slot - lo, 0, DYN_BLOCK - 1)
        d_s = jnp.where(in_s, rows_b[vidx[None, :], cs], d_s)
        d_s_root = jnp.where(in_s, rows_b[root, cs], d_s_root)
        valid_rows = jnp.minimum(n_need - lo, DYN_BLOCK)
        return (
            b + 1, dpq, dpq_root, d_s, d_s_root, rounds + r_b,
            rr + valid_rows * r_b,
        )

    inf_ln = jnp.full((nlinks, n), INF, jnp.int32)
    inf_l = jnp.full((nlinks,), INF, jnp.int32)
    blocks, dpq, dpq_root, d_s, d_s_root, rounds_d, rr_d = jax.lax.while_loop(
        dcond, dbody,
        (jnp.int32(0), inf_ln, inf_l, inf_ln, inf_ln, jnp.int32(0),
         jnp.int32(0)),
    )

    rlfa_ok = (
        pq_need[:, None]
        & (dpq < _fadd(dpq_root[:, None], droot[None, :]))
        & valid_d[None, :]
    )
    rlfa_pq = jnp.where(rlfa_ok, pq[:, None], -1).astype(jnp.int32)

    tail_ok = d_s < _fadd(d_s_root, droot[None, :])
    single = s_fix < 0
    double = (s_fix >= 0) & tail_ok
    tilfa_p = jnp.where(ok & (single | double), p_fix, -1).astype(jnp.int32)
    tilfa_q = jnp.where(ok & double, s_fix, -1).astype(jnp.int32)

    n_base = base_ok.sum().astype(jnp.int32)
    n_rev = ((rev_src >= 0) & (rev_src < n)).sum().astype(jnp.int32)
    stats = jnp.stack([
        n_base + n_need,
        n_rev,
        rounds_f + rounds_r + rounds_d,
        n_base * rounds_f + rr_d,
        n_rev * rounds_r,
        _distinct(s_fix, s_need, n),
        blocks,
        post.unmatched,
        missing,
        post.rounds,
        fwd.slots * nlinks,
    ]).astype(jnp.int32)

    return FrrTensors(
        lfa_adj=lfa_adj,
        lfa_nodeprot=lfa_nodeprot,
        rlfa_pq=rlfa_pq,
        tilfa_p=tilfa_p,
        tilfa_q=tilfa_q,
        post_dist=post.dist,
        post_nh=post.nexthops,
        stats=stats,
    )


def _up(x, idx):
    """``x`` at each row's ``idx`` ([L, N] each; ``idx == N`` reads the
    pad: N for ints, False for masks)."""
    pad = jnp.full((x.shape[0], 1), x.shape[1] if x.dtype != bool else False,
                   x.dtype)
    return jnp.take_along_axis(jnp.concatenate([x, pad], axis=1), idx, axis=1)


def _nearest(step):
    """Pointer doubling to the fixed points of ``step`` ([L, N] in
    ``[0, N]``; a fixed point points at itself, N at nothing): every
    vertex's nearest ancestor of the kind the fixed points are."""

    def cond(carry):
        return carry[1]

    def body(carry):
        ptr, _ = carry
        nxt = _up(ptr, ptr)
        return nxt, jnp.any(nxt != ptr)

    ptr, _ = jax.lax.while_loop(cond, body, (step, jnp.bool_(True)))
    return ptr


def _distinct(values, mask, n):
    """Count of distinct ``values`` (in ``[0, n)``) where ``mask``."""
    seen = jnp.zeros((n + 1,), bool).at[jnp.where(mask, values, n)].set(True)
    return seen[:n].sum().astype(jnp.int32)


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
    # What the device kernel did (kernel.STATS by name); None from the oracle.
    stats: dict | None = None

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
