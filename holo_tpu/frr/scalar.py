"""Scalar FRR oracle: the kernel's selection semantics in plain Python.

Independent implementation (loops + Dijkstra, no shared vectorized
code) of the exact rules documented in :mod:`holo_tpu.frr.kernel`;
tests require the two to be bit-identical.  Like the kernel it computes
only the distances its selection reads: a row ``D[x, :]`` by one
Dijkstra from ``x`` and a column ``D[:, t]`` by one Dijkstra from ``t``
over the reversed edges, each the first time it is read
(:class:`LazyDistances`), so that a 10,000-vertex table takes tens of
Dijkstra runs and not 10,000.  The per-link post-convergence runs use
``spf_reference`` — whose dist/parent planes are already bit-parity
gated against the device engines — so any divergence localizes to the
selection logic itself.
"""

from __future__ import annotations

import heapq

import numpy as np

from holo_tpu.frr.inputs import FrrInputs, marshal_frr
from holo_tpu.frr.kernel import BackupTable
from holo_tpu.ops.graph import INF, Topology
from holo_tpu.spf.scalar import spf_reference

_INF = int(INF)


def _fadd(a: int, b: int) -> int:
    return a + b if a < _INF and b < _INF else _INF


class LazyDistances:
    """``D[x, y]`` of ``topo`` (INF: unreachable), each row and each
    column computed by a distance-only Dijkstra the first time it is
    read.  ``rows`` and ``cols`` count the runs made."""

    def __init__(self, topo: Topology):
        n = topo.n_vertices
        self._out: list[list[tuple[int, int]]] = [[] for _ in range(n)]
        self._in: list[list[tuple[int, int]]] = [[] for _ in range(n)]
        for a, b, c in zip(
            topo.edge_src.tolist(), topo.edge_dst.tolist(),
            topo.edge_cost.tolist(),
        ):
            self._out[a].append((b, c))
            self._in[b].append((a, c))
        self._rows: dict[int, list[int]] = {}
        self._cols: dict[int, list[int]] = {}

    @staticmethod
    def _dijkstra(adj, src: int) -> list[int]:
        dist = [_INF] * len(adj)
        dist[src] = 0
        heap = [(0, src)]
        while heap:
            d, u = heapq.heappop(heap)
            if d != dist[u]:
                continue
            for v, c in adj[u]:
                nd = d + c
                if nd < dist[v]:
                    dist[v] = nd
                    heapq.heappush(heap, (nd, v))
        return dist

    def row(self, x: int) -> list[int]:
        """``D[x, :]``."""
        out = self._rows.get(x)
        if out is None:
            out = self._rows[x] = self._dijkstra(self._out, x)
        return out

    def col(self, t: int) -> list[int]:
        """``D[:, t]``: distances to ``t``, over the reversed edges."""
        out = self._cols.get(t)
        if out is None:
            out = self._cols[t] = self._dijkstra(self._in, t)
        return out

    @property
    def rows(self) -> int:
        return len(self._rows)

    @property
    def cols(self) -> int:
        return len(self._cols)


def frr_reference(
    topo: Topology,
    n_atoms: int = 64,
    inputs: FrrInputs | None = None,
    srlg_disjoint: bool = False,
    node_protection: bool = False,
) -> BackupTable:
    """Compute the full backup table with scalar loops.

    ``srlg_disjoint``: exclude repair candidates sharing any SRLG bit
    with the protected link (mirror of the kernel's vectorized policy
    mask).  ``node_protection``: only node-protecting LFAs are
    selectable (inequality 3 as policy, not preference)."""
    fin = inputs if inputs is not None else marshal_frr(topo)
    n = topo.n_vertices
    root = int(topo.root)
    nl, na = fin.n_links, fin.n_adj
    is_router = topo.is_router.tolist()
    dd = LazyDistances(topo)
    droot = dd.row(root)
    w = max((max(n_atoms, topo.n_atoms()) + 31) // 32, 1)

    lfa_adj = np.full((nl, n), -1, np.int32)
    lfa_nodeprot = np.zeros((nl, n), np.int32)
    rlfa_pq = np.full((nl, n), -1, np.int32)
    tilfa_p = np.full((nl, n), -1, np.int32)
    tilfa_q = np.full((nl, n), -1, np.int32)
    post_dist = np.full((nl, n), _INF, np.int32)
    post_nh = np.zeros((nl, n, w), np.uint32)

    nbr = [int(x) for x in fin.adj_nbr[:na]]
    acost = [int(x) for x in fin.adj_cost[:na]]
    alink = [int(x) for x in fin.adj_link[:na]]
    rown = [dd.row(x) for x in nbr]  # D[nbr_a, :]

    def valid_d(dst: int) -> bool:
        return dst != root and droot[dst] < _INF

    for l in range(nl):
        far = int(fin.link_far[l])
        lcost = int(fin.link_cost[l])
        post = spf_reference(topo, fin.edge_masks[l])
        post_dist[l] = post.dist
        post_nh[l] = post.nexthop_words(max(n_atoms, topo.n_atoms()))
        post_d = post.dist.tolist()
        post_par = post.parent.tolist()
        rfar = dd.row(far)

        usable = [
            alink[a] != l
            and (
                not srlg_disjoint
                or (int(fin.link_srlg[l]) & int(fin.adj_srlg[a])) == 0
            )
            for a in range(na)
        ]

        # -- LFA (RFC 5286 inequalities 1 + 3, lexicographic pick)
        for dst in range(n):
            if not valid_d(dst):
                continue
            cands = []
            for a in range(na):
                if not usable[a]:
                    continue
                dn_d = rown[a][dst]
                if not dn_d < _fadd(rown[a][root], droot[dst]):
                    continue
                nprot = dn_d < _fadd(rown[a][far], rfar[dst])
                alt = _fadd(acost[a], dn_d)
                if alt < _INF:
                    cands.append((nprot, alt, nbr[a], a))
            if node_protection:
                cands = [c for c in cands if c[0]]
            if not cands:
                continue
            if any(c[0] for c in cands):
                cands = [c for c in cands if c[0]]
                lfa_nodeprot[l, dst] = 1
            _, _, _, best = min(cands, key=lambda c: (c[1], c[2], c[3]))
            lfa_adj[l, dst] = best

        # -- remote LFA (RFC 7490 P/Q intersection)
        def in_extp(v: int) -> bool:
            if droot[v] < _fadd(lcost, rfar[v]):
                return True
            return any(
                usable[a]
                and rown[a][v]
                < _fadd(rown[a][root], droot[v])
                for a in range(na)
            )

        def in_qspace(v: int) -> bool:
            return dd.col(far)[v] < _fadd(dd.col(root)[v], lcost)

        pq = -1
        best_key = (_INF, n)
        for v in range(n):
            if v == root or not is_router[v]:
                continue
            if in_extp(v) and in_qspace(v):
                key = (droot[v], v)
                if key < best_key:
                    best_key, pq = key, v
        if pq >= 0:
            rpq = dd.row(pq)
            for dst in range(n):
                if valid_d(dst) and rpq[dst] < _fadd(rpq[root], droot[dst]):
                    rlfa_pq[l, dst] = pq

        # -- TI-LFA along the post-convergence path
        for dst in range(n):
            if not valid_d(dst) or post_d[dst] >= _INF:
                continue
            # parent walk dst → root (acyclic SPT; sentinel n = none)
            path = []
            v = dst
            while v != root:
                path.append(v)
                v = post_par[v]
                if v >= n:
                    path = None
                    break
            if path is None:
                continue
            path.reverse()  # first hop ... dst
            n1 = None
            p_node, s_node = root, -1
            for v in path:
                if n1 is None and is_router[v]:
                    n1 = v
                pmark = (
                    n1 is not None
                    and is_router[v]
                    and dd.row(n1)[v]
                    < _fadd(dd.row(n1)[root], droot[v])
                )
                if not is_router[v]:
                    pass  # pseudo-node: transparent for P and S
                elif pmark:
                    p_node, s_node = v, -1
                elif s_node < 0:
                    s_node = v
            if p_node < 0:
                continue
            if s_node < 0:
                tilfa_p[l, dst] = p_node
            elif dd.row(s_node)[dst] < _fadd(
                dd.row(s_node)[root], droot[dst]
            ):
                tilfa_p[l, dst] = p_node
                tilfa_q[l, dst] = s_node

    return BackupTable(
        inputs=fin,
        root=root,
        lfa_adj=lfa_adj,
        lfa_nodeprot=lfa_nodeprot,
        rlfa_pq=rlfa_pq,
        tilfa_p=tilfa_p,
        tilfa_q=tilfa_q,
        post_dist=post_dist,
        post_nh=post_nh,
    )
