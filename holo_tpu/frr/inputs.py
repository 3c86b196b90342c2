"""FRR input marshaling: Topology → protected links + repair candidates.

Shapes are padded to a multiple of ``pad_multiple`` so XLA compiles once
per (N, L, A) bucket under LSA churn (same bucketing policy as
``ops/graph.build_ell``).  Padding rows carry ``valid == False`` and MUST
be result-neutral: the kernel and the scalar oracle both mask them out,
and the fuzz target ``frr_padding_invariants`` checks that growing the
pad never changes a table entry.

Model (shared by kernel and oracle — keep the two in lockstep):

- A *protected link* is a root out-edge: one per p2p/vlink neighbor edge
  and one per attached transit network (the interface).  Its failure
  masks the edge and its first reverse edge (both directions of the
  link, like ``whatif_link_failure_masks``); for parallel p2p links the
  reverse pick is the first matching edge — the vertex graph cannot
  distinguish siblings, so siblings share the reverse (documented
  limitation).
- A *repair candidate* (adjacency) is a direct next hop the root could
  repair through: a root out-edge to a router carrying a next-hop atom,
  or a (root-adjacent network → member router) edge with an atom.  Each
  candidate rides exactly one protected link (``adj_link``) — candidates
  on the failed interface are unusable for that link, while a parallel
  link to the same neighbor remains usable (RFC 5286 link protection).
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from holo_tpu.ops.graph import Topology


def _round_up(x: int, m: int) -> int:
    return ((max(x, 1)) + m - 1) // m * m


@dataclass
class FrrInputs:
    """Host-side padded FRR tables for one topology root."""

    # Protected links (root out-edges); padded with valid=False.
    link_edge: np.ndarray  # int32[Lp] edge id (-1 pad)
    link_far: np.ndarray  # int32[Lp] far-end vertex (0 pad)
    link_cost: np.ndarray  # int32[Lp]
    link_valid: np.ndarray  # bool[Lp]
    edge_masks: np.ndarray  # bool[Lp, E] post-convergence scenario masks
    # SRLG bitmask of the protected link's edge (uint32[Lp], 0 pad):
    # the shared-risk policy plane — candidates sharing any group bit
    # with the protected link are excluded when the policy is armed.
    link_srlg: np.ndarray
    # Repair candidates; padded with valid=False.
    adj_edge: np.ndarray  # int32[Ap] edge id of the candidate edge
    adj_nbr: np.ndarray  # int32[Ap] neighbor router vertex
    adj_cost: np.ndarray  # int32[Ap] root→neighbor cost over this candidate
    adj_link: np.ndarray  # int32[Ap] protected-link index it rides (-1 pad)
    adj_atom: np.ndarray  # int32[Ap] direct next-hop atom id
    adj_valid: np.ndarray  # bool[Ap]
    adj_srlg: np.ndarray  # uint32[Ap] SRLG bitmask of the candidate edge
    # Direct next-hop atom of the protected edge (-1: none; -1 pad): with
    # the source and cost it finds the edge's slot in the resident graph.
    link_atom: np.ndarray  # int32[Lp]
    # Sources of the kernel's distance rows (pads: n_vertices).  Forward
    # base rows: the root first, then every router one hop or one
    # network from it (the release neighbours and LFA candidates) and
    # every far end; reversed-graph rows: the root, then far end l at 1 + l.
    base_src: np.ndarray  # int32[Bb]
    rev_src: np.ndarray  # int32[round_up(1 + Lp)]
    n_links: int  # unpadded L
    n_adj: int  # unpadded A
    # next-hop atom id -> protected link index (which interface an
    # installed primary next hop rides; drives failure→destination fanout).
    atom_link: dict

    @property
    def shape_key(self) -> tuple:
        return (
            self.link_valid.shape[0],
            self.adj_valid.shape[0],
            self.base_src.shape[0],
        )


def marshal_frr(topo: Topology, pad_multiple: int = 8) -> FrrInputs:
    """Build the padded FRR tables for ``topo.root``."""
    root = int(topo.root)
    n = int(topo.n_vertices)
    e_src = topo.edge_src
    e_dst = topo.edge_dst
    e_cost = topo.edge_cost
    atom = topo.edge_direct_atom
    is_router = topo.is_router
    srlg = topo.edge_srlg
    n_edges = topo.n_edges

    # Protected links: root out-edges, in edge order.
    link_edge = np.flatnonzero(e_src == root)
    nlinks = len(link_edge)
    into_root = np.flatnonzero(e_dst == root)

    masks = np.ones((nlinks, n_edges), bool)
    for l, e in enumerate(link_edge.tolist()):
        masks[l, e] = False
        rev = into_root[e_src[into_root] == e_dst[e]]
        if len(rev):
            masks[l, int(rev[0])] = False

    # Repair candidates + atom→link map; the first routers (one hop or
    # one network from the root) are the forward base rows' sources.
    adj_edge: list[int] = []
    adj_nbr: list[int] = []
    adj_cost: list[int] = []
    adj_link: list[int] = []
    adj_atom: list[int] = []
    adj_srlg: list[int] = []
    atom_link: dict[int, int] = {}
    first: set[int] = set()
    for l, e in enumerate(link_edge.tolist()):
        far = int(e_dst[e])
        if int(atom[e]) >= 0:
            atom_link.setdefault(int(atom[e]), l)
        if is_router[far]:
            first.add(far)
            if int(atom[e]) >= 0:
                adj_edge.append(e)
                adj_nbr.append(far)
                adj_cost.append(int(e_cost[e]))
                adj_link.append(l)
                adj_atom.append(int(atom[e]))
                adj_srlg.append(int(srlg[e]))
        else:
            # LAN: members reachable through this interface are candidates
            # (and their atoms ride this link for the failure fanout).
            for e2 in np.flatnonzero(e_src == far).tolist():
                member = int(e_dst[e2])
                if member == root or not is_router[member]:
                    continue
                first.add(member)
                if int(atom[e2]) < 0:
                    continue
                atom_link.setdefault(int(atom[e2]), l)
                adj_edge.append(e2)
                adj_nbr.append(member)
                adj_cost.append(int(e_cost[e]) + int(e_cost[e2]))
                adj_link.append(l)
                adj_atom.append(int(atom[e2]))
                # The LAN repair rides our interface edge AND the
                # network→member leg: its risk set is the union.
                adj_srlg.append(int(srlg[e]) | int(srlg[e2]))
    nadj = len(adj_edge)

    lp = _round_up(nlinks, pad_multiple)
    ap = _round_up(nadj, pad_multiple)

    def pad_i32(vals, size, fill):
        out = np.full(size, fill, np.int32)
        out[: len(vals)] = np.asarray(vals, np.int32).reshape(-1)[: len(vals)]
        return out

    def pad_u32(vals, size):
        out = np.zeros(size, np.uint32)
        out[: len(vals)] = np.asarray(vals, np.uint32).reshape(-1)[: len(vals)]
        return out

    link_valid = np.zeros(lp, bool)
    link_valid[:nlinks] = True
    adj_valid = np.zeros(ap, bool)
    adj_valid[:nadj] = True
    # Pad scenarios keep every edge up: their post-SPF equals the base
    # SPF, and every output row is masked by link_valid anyway.
    masks_p = np.ones((lp, n_edges), bool)
    masks_p[:nlinks] = masks
    fars = [int(e_dst[e]) for e in link_edge.tolist()]
    base = [root, *sorted((first | set(fars)) - {root})]
    nbase = max(_round_up(1 + lp + ap, 8), _round_up(len(base), 8))

    return FrrInputs(
        link_edge=pad_i32(link_edge, lp, -1),
        link_far=pad_i32(fars, lp, 0),
        link_cost=pad_i32(e_cost[link_edge], lp, 1),
        link_valid=link_valid,
        edge_masks=masks_p,
        link_srlg=pad_u32(srlg[link_edge], lp),
        adj_edge=pad_i32(adj_edge, ap, -1),
        adj_nbr=pad_i32(adj_nbr, ap, 0),
        adj_cost=pad_i32(adj_cost, ap, 1),
        adj_link=pad_i32(adj_link, ap, -1),
        adj_atom=pad_i32(adj_atom, ap, -1),
        adj_valid=adj_valid,
        adj_srlg=pad_u32(adj_srlg, ap),
        link_atom=pad_i32(atom[link_edge], lp, -1),
        base_src=pad_i32(base, nbase, n),
        rev_src=pad_i32([root, *fars], _round_up(1 + lp, 8), n),
        n_links=nlinks,
        n_adj=nadj,
        atom_link=atom_link,
    )


#: in-edges of every vertex in the first plane of a :class:`RowsGraph`
ROWS_K_LO = 8


def rows_graph_shape(topo: Topology, k_lo: int = ROWS_K_LO) -> tuple[int, int]:
    """(heavy vertices, their extra in-edges) the two directions of
    ``topo`` need beyond ``k_lo`` slots a vertex."""
    n = int(topo.n_vertices)
    need_h, need_k = 0, 0
    for row in (topo.edge_dst, topo.edge_src):
        counts = np.bincount(row, minlength=n)
        heavy = counts > k_lo
        need_h = max(need_h, int(heavy.sum()))
        need_k = max(need_k, int(counts.max(initial=0)) - k_lo)
    return need_h, need_k


def rows_graphs(
    topo: Topology, h_pad: int, k_hi: int, k_lo: int = ROWS_K_LO
) -> tuple[tuple, tuple]:
    """The forward and the reversed :class:`~holo_tpu.frr.kernel.RowsGraph`
    planes of ``topo`` as numpy tuples ``(lo_src, lo_cost, hi_vtx,
    hi_src, hi_cost)``, the forward one followed by ``(lo_atom,
    hi_atom)``, each slot's direct next-hop atom (-1 none): a vertex's
    first ``k_lo`` in-edges (forward) or out-edges (reversed) in the low
    plane, the rest, for the at most ``h_pad`` vertices that have more,
    in rows of ``k_hi``.  An empty slot's source, and a pad row's
    vertex, is ``n_vertices``."""
    n = int(topo.n_vertices)

    def planes(row, other, atom=None):
        order = np.argsort(row, kind="stable")
        r = row[order]
        rank = np.arange(len(r)) - np.searchsorted(r, r, side="left")
        low = rank < k_lo
        heavy = np.flatnonzero(np.bincount(row, minlength=n) > k_lo)
        if len(heavy) > h_pad:
            raise ValueError(f"{len(heavy)} heavy vertices, {h_pad} rows")
        hi_vtx = np.full(h_pad, n, np.int32)
        hi_vtx[: len(heavy)] = heavy
        at = np.full(n, -1, np.int64)
        at[heavy] = np.arange(len(heavy))
        high = ~low

        def place(vals, fill):
            lo = np.full((n, k_lo), fill, np.int32)
            lo[r[low], rank[low]] = vals[order][low]
            hi = np.full((h_pad, k_hi), fill, np.int32)
            hi[at[r[high]], rank[high] - k_lo] = vals[order][high]
            return lo, hi

        lo_src, hi_src = place(other, n)
        lo_cost, hi_cost = place(topo.edge_cost, 0)
        out = (lo_src, lo_cost, hi_vtx, hi_src, hi_cost)
        return out if atom is None else (*out, *place(atom, -1))

    return (
        planes(topo.edge_dst, topo.edge_src, topo.edge_direct_atom),
        planes(topo.edge_src, topo.edge_dst),
    )
