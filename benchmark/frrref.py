"""The plain reference of the fast-reroute cell: backup tables and the
device's installed table, with its repairs, in plain Python with
``heapq`` and nothing of the program.

Two entry points:

- ``tables(graph, policy, dests)``: per protected link (the root's
  out-edges, in edge order) and per destination in ``dests``, what the
  repair selection picks — the LFA neighbour and whether it
  node-protects, the remote-LFA PQ node, the TI-LFA P and Q, and the
  post-convergence distance.  ``graph`` is a plain edge list
  (``{"n", "root", "routers", "edges": [(src, dst, cost, atom, srlgs)]}``,
  ``srlgs`` the edge's shared-risk group ids), the form the cell's
  parity check hands over from a sampled dispatch.
- ``routes(model)``: the device's table from the generator's own link
  model (``benchmark/frrnet.py FrrNet.model()``): ``{prefix: (metric,
  frozenset(primary hops), {primary hop: (kind, release hop, labels)})}``
  with a hop an ``(ifname, address)`` pair, for every prefix installed
  (reachable, with a next hop).

What is computed (RFC numbers; every departure is marked DEPARTURE):

- Dijkstra distances ``D[x, y]`` over directed edges; a column
  ``D[:, t]`` by one run over the reversed edges.  Point-to-point links
  only: the deployment has no transit network (a graph with a
  pseudo-node is refused).
- The primary and the post-convergence trees: equal-cost first-hop
  sets (RFC 2328 16.1), and one parent per vertex, the one with the
  lowest ``(D[root, u], u)`` — the program's parent tie-break (its
  vertex order is ascending router id, which is the generator's router
  number).  Post-convergence = the protected edge ``root -> far``
  removed.
- LFA, RFC 5286 section 3: candidate ``a`` (an out-edge of the root
  on another link) for ``(l, d)`` iff inequality 1,
  ``D[a, d] < D[a, root] + D[root, d]``; node-protecting iff inequality
  3, ``D[a, d] < D[a, far] + D[far, d]``.  Node-protecting candidates
  are preferred, or required when ``node_protection`` is policy; among
  them the lowest ``(cost(root, a) + D[a, d], a, edge index)`` wins.
  DEPARTURE: RFC 5286 leaves the choice among loop-free alternates to
  the implementation; this is the program's documented total order.
  ``srlg_disjoint`` (RFC 7916 section 5 manageability policy): a
  candidate that shares a risk group with the protected link is
  excluded.  DEPARTURE: two group ids count as one risk where they
  agree modulo 32 — the program keeps an interface's groups as a
  32-bit mask, bit ``id % 32``, and treats a shared bit as a shared
  risk (never the reverse).  Computed here from the id sets.
- Remote LFA, RFC 7490 section 4: extended P-space = ``v`` with
  ``D[root, v] < cost(l) + D[far, v]`` (RFC 7490 4.2 on the root) or
  loop-free from a usable candidate neighbour (4.2.1); Q-space =
  ``D[v, far] < D[v, root] + cost(l)`` (4.3, with the reversed run).
  One PQ node per link, the router ``!= root`` with the lowest
  ``(D[root, v], v)`` in both.  DEPARTURE: RFC 7490 section 4.4 picks
  among PQ nodes by policy; this is the program's.  A destination ``d``
  is covered when ``D[pq, d] < D[pq, root] + D[root, d]``.  The release
  next hop toward PQ is the LFA picked for destination PQ on the same
  link; without one the destination gets no repair at all (no fall
  through to TI-LFA) — the program's rule.
- TI-LFA (draft-ietf-rtgwg-segment-routing-ti-lfa section 5): along the
  post-convergence path root -> d, ``n1`` its first router, ``P`` the
  last router ``v`` on the path with ``D[n1, v] < D[n1, root] +
  D[root, v]`` and ``S`` the router after ``P``; the repair is ``(P)``
  when there is none, else ``(P, S)`` when ``D[S, d] < D[S, root] +
  D[root, d]``, else none.  DEPARTURE: the draft's P-space is that of
  the point of local repair and the repair list is computed from the
  post-convergence path's segments (an adjacency SID to Q where P and
  Q are not adjacent); the program's is this release-neighbour P-space
  test, with node SIDs for both segments.  The release next hop is the
  post-convergence first hop with the lowest atom id (the program
  numbers a root's next hops in the order of its Router-LSA's links,
  which list peers by router number).
- Order of preference: LFA, then remote LFA (if armed), then TI-LFA
  (if armed); a remote-LFA or TI-LFA repair carries the node-SID labels
  of its repair vertices, SRGB lower bound plus SID index (RFC 8665
  section 5), and is attached only where every label resolves.
"""

from __future__ import annotations

import heapq

INF = 1 << 30
KINDS = ("lfa", "rlfa", "ti-lfa")


def _fadd(a: int, b: int) -> int:
    return a + b if a < INF and b < INF else INF


def _shared(a, b) -> bool:
    """Whether two sets of risk-group ids share a risk (see DEPARTURE)."""
    return bool({g % 32 for g in a} & {g % 32 for g in b})


def dijkstra(
    adj: list, src: int, skip: tuple | None = None, order: list | None = None
) -> list:
    """Distances from ``src`` over ``adj`` (``[[(v, cost, edge)]]``),
    ``skip`` an edge ``(u, edge index)`` left out; ``order`` gets the
    reached vertices in increasing ``(distance, vertex)``."""
    dist = [INF] * len(adj)
    dist[src] = 0
    heap = [(0, src)]
    while heap:
        d, u = heapq.heappop(heap)
        if d != dist[u]:
            continue
        if order is not None:
            order.append(u)
        for v, c, e in adj[u]:
            if skip is not None and skip[1] == e:
                continue
            nd = d + c
            if nd < dist[v]:
                dist[v] = nd
                heapq.heappush(heap, (nd, v))
    return dist


class Graph:
    """The edge list with its forward and reversed adjacency, and the
    rows and columns of ``D`` as they are first read."""

    def __init__(self, graph: dict):
        self.n = n = graph["n"]
        self.root = graph["root"]
        self.routers = graph["routers"]
        if not all(self.routers):
            raise ValueError("the reference models point-to-point links only")
        self.edges = graph["edges"]
        self.out = [[] for _ in range(n)]
        self.inn = [[] for _ in range(n)]
        for e, (a, b, c, _atom, _srlg) in enumerate(self.edges):
            self.out[a].append((b, c, e))
            self.inn[b].append((a, c, e))
        self._rows: dict = {}
        self._cols: dict = {}
        self._trees: dict = {}

    def row(self, x: int) -> list:
        if x not in self._rows:
            self._rows[x] = dijkstra(self.out, x)
        return self._rows[x]

    def col(self, t: int) -> list:
        if t not in self._cols:
            self._cols[t] = dijkstra(self.inn, t)
        return self._cols[t]

    def tree(self, skip_edge: int | None = None):
        """(dist, parent, first-hop atom sets) from the root, with
        ``skip_edge`` removed.  Parent: the lowest ``(dist[u], u)`` of
        the equal-cost parents, -1 for none."""
        if skip_edge not in self._trees:
            self._trees[skip_edge] = self._tree(skip_edge)
        return self._trees[skip_edge]

    def _tree(self, skip_edge):
        root = self.root
        skip = None if skip_edge is None else (root, skip_edge)
        order: list = []
        dist = dijkstra(self.out, root, skip, order)
        parent = [-1] * self.n
        hops = [frozenset()] * self.n
        for v in order:
            if v == root:
                continue
            best, via = None, set()
            for u, c, e in self.inn[v]:
                if e == skip_edge or dist[u] >= INF or dist[u] + c != dist[v]:
                    continue
                if best is None or (dist[u], u) < best:
                    best = (dist[u], u)
                if u == root:
                    atom = self.edges[e][3]
                    if atom >= 0:
                        via.add(atom)
                else:
                    via |= hops[u]
            parent[v] = best[1]
            hops[v] = frozenset(via)
        return dist, parent, hops


def _links(g: Graph) -> list:
    """Protected links: the root's out-edges in edge order, as
    ``(edge, far, cost, atom, srlgs)``."""
    return [
        (e, b, c, atom, srlgs)
        for e, (a, b, c, atom, srlgs) in enumerate(g.edges) if a == g.root
    ]


def _table(g: Graph, policy: dict, dests) -> dict:
    """``{(link index, d): (lfa (neighbour, edge) or None, node-protects,
    pq, p, q, post distance)}`` over ``dests``, and per link the PQ."""
    root = g.root
    droot = g.row(root)
    links = _links(g)
    cands = [x for x in links if x[3] >= 0]  # (edge, nbr, cost, atom, srlgs)
    out = {}
    for li, (le, far, lcost, _latom, lsrlg) in enumerate(links):
        usable = [
            c for c in cands
            if c[0] != le
            and not (policy["srlg_disjoint"] and _shared(c[4], lsrlg))
        ]
        rfar = g.row(far)

        def lfa(d):
            found = []
            for e, nbr, cost, _atom, _s in usable:
                rn = g.row(nbr)
                if not rn[d] < _fadd(rn[root], droot[d]):
                    continue
                nprot = rn[d] < _fadd(rn[far], rfar[d])
                found.append((nprot, _fadd(cost, rn[d]), nbr, e))
            if policy["node_protection"]:
                found = [f for f in found if f[0]]
            if not found:
                return None, False
            nprot = any(f[0] for f in found)
            if nprot:
                found = [f for f in found if f[0]]
            best = min(found, key=lambda f: (f[1], f[2], f[3]))
            return (best[2], best[3]), nprot

        pq = -1
        if policy["remote_lfa"]:
            cfar, croot = g.col(far), g.col(root)
            for v in sorted(range(g.n), key=lambda v: (droot[v], v)):
                if droot[v] >= INF:
                    break
                if v == root or not cfar[v] < _fadd(croot[v], lcost):
                    continue
                if droot[v] < _fadd(lcost, rfar[v]) or any(
                    g.row(nbr)[v] < _fadd(g.row(nbr)[root], droot[v])
                    for _e, nbr, *_rest in usable
                ):
                    pq = v
                    break
        post, parent, _hops = g.tree(le)
        for d in dests:
            if d == root or droot[d] >= INF:
                continue
            pick, nprot = lfa(d)
            cover = -1
            if pq >= 0:
                rpq = g.row(pq)
                if rpq[d] < _fadd(rpq[root], droot[d]):
                    cover = pq
            p = q = -1
            if post[d] < INF:
                path, v = [], d
                while v != root:
                    path.append(v)
                    v = parent[v]
                path.reverse()
                n1 = path[0]
                rn1 = g.row(n1)
                p_node, s_node = root, -1
                for v in path:
                    if rn1[v] < _fadd(rn1[root], droot[v]):
                        p_node, s_node = v, -1
                    elif s_node < 0:
                        s_node = v
                rs = g.row(s_node) if s_node >= 0 else None
                if s_node < 0:
                    p = p_node
                elif rs[d] < _fadd(rs[root], droot[d]):
                    p, q = p_node, s_node
            out[(li, d)] = (pick, nprot, cover, p, q, post[d])
    return out


def tables(graph: dict, policy: dict, dests) -> dict:
    """``{(link index, d): (lfa neighbour or -1, node-protects, PQ or
    -1, P or -1, Q or -1, post-convergence distance)}``: the backup
    tables of the program's ``BackupTable`` over ``dests``."""
    g = Graph(graph)
    return {
        key: (pick[0] if pick else -1, bool(nprot and pick), pq, p, q, post)
        for key, (pick, nprot, pq, p, q, post)
        in _table(g, policy, dests).items()
    }


class Deployment:
    """The generator's link model as a graph rooted at the device, with
    the backup tables of every reachable router."""

    def __init__(self, model: dict):
        self.root = root = model["dut"]
        hop_of = model["hops"]  # {peer: (ifname, address)}
        peers = sorted(hop_of)
        atom_of = {peer: k for k, peer in enumerate(peers)}
        edges = []
        for a in range(model["n"]):
            for b, cost in sorted(model["adj"].get(a, {}).items()):
                if a not in model["adj"].get(b, {}):
                    continue  # the two-way check
                atom = atom_of[b] if a == root else -1
                srlgs = tuple(model["srlgs"].get(b, ())) if a == root else ()
                edges.append((a, b, cost, atom, srlgs))
        self.g = g = Graph({
            "n": model["n"], "root": root, "routers": [True] * model["n"],
            "edges": edges,
        })
        self.hop = {atom_of[p]: hop_of[p] for p in peers}
        self.droot, _parent, self.first = g.tree()
        self.dests = [
            r for r in range(g.n) if r != root and self.droot[r] < INF
        ]
        self.policy = model["policy"]
        self.table = _table(g, self.policy, self.dests)
        self.links = _links(g)
        self.link_of_atom = {
            atom: li for li, (_e, _f, _c, atom, _s) in enumerate(self.links)
        }

    def repair(self, li: int, d: int):
        """``(kind, release atom, repair vertices)`` or None."""
        g, policy = self.g, self.policy
        pick, _nprot, pq, p, q, _post = self.table[(li, d)]
        if pick is not None:
            return "lfa", g.edges[pick[1]][3], ()
        if policy["remote_lfa"] and pq >= 0:
            rel = self.table[(li, pq)][0]
            if rel is None:
                return None
            return "rlfa", g.edges[rel[1]][3], (pq,)
        if policy["ti_lfa"] and p >= 0:
            _post, _par, hops = g.tree(self.links[li][0])
            if not hops[d]:
                return None
            return "ti-lfa", min(hops[d]), (p,) if q < 0 else (p, q)
        return None


def routes(model: dict) -> dict:
    """The device's installed table with its repairs (see module doc)."""
    dep = Deployment(model)
    lower, upper = model["srgb"]
    sid = model["sids"]

    def label(v: int):
        index = sid.get(v)
        if index is None or not 0 <= index <= upper - lower:
            return None
        return lower + index

    out = {}
    for router, prefix, metric in model["prefixes"]:
        first = dep.first[router]
        if router == dep.root or dep.droot[router] >= INF or not first:
            continue
        backups = {}
        for a in sorted(first):
            li = dep.link_of_atom.get(a)
            got = None if li is None else dep.repair(li, router)
            if got is None:
                continue
            kind, rel, via = got
            labels = tuple(label(v) for v in via)
            if None in labels:
                continue
            backups[dep.hop[a]] = (kind, dep.hop[rel], labels)
        out[prefix] = (
            dep.droot[router] + metric,
            frozenset(dep.hop[a] for a in first),
            backups,
        )
    return out


def repairs(model: dict) -> dict:
    """``{(link index, d): (kind, release atom, repair vertices) or
    None}`` for every protected link and every destination its failure
    leaves reachable."""
    dep = Deployment(model)
    out = {}
    for li in range(len(dep.links)):
        post = dep.g.tree(dep.links[li][0])[0]
        for d in dep.dests:
            if post[d] < INF:
                out[(li, d)] = dep.repair(li, d)
    return out

