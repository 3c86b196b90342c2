"""The plain reference of the OSPFv3 multi-area cell: the route table of
the device under test (an area border router) from the generator's own
link model, in plain Python with ``heapq``.  Nothing of the program is
imported here, and nothing is read from an LSDB: the same links and
prefixes give the same routes, or one of the two is wrong.

    routes(model) -> {prefix: (cost, frozenset((ifname, link-local)))}

``model`` (what ``benchmark/areanet.py AreaNet.model()`` returns, plain
data): ``dut`` (a router id), ``areas`` ``{area: {router: {peer:
cost}}}`` with one entry per direction of every link that is up
(RFC 2328 §16.1: a link counts when both ends list it), ``first_hops``
``{area: {peer: (ifname, link-local)}}`` for the device's own
adjacencies, ``prefixes`` ``{area: [(router, prefix, metric)]}``
(Intra-Area-Prefix LSAs, RFC 5340 §4.4.3.9), ``summaries`` ``[(border
router, prefix, cost)]`` (the Inter-Area-Prefix LSAs other border
routers hold in the backbone), ``ranges`` (the device's own area
address ranges, ``{area: [prefix]}``) and ``backbone`` (the backbone's
area id).

Follows RFC 5340 §4.8 and RFC 2328 §16.1-16.2: Dijkstra per area from
the device with equal-cost first-hop sets; an intra-area route per
prefix at the advertising router's distance plus the prefix's metric,
lowest wins, equal costs join their next hops; inter-area routes from
the backbone's summaries alone (an area border router looks at no
other area's, §16.2), skipping its own and those whose border router
is unreachable, lowest ``distance to the border router + advertised
cost`` wins, equal costs join; an intra-area route beats an inter-area
one for the same prefix.

Departures from the RFCs, all of them things the deployment does not
have: no Network-LSAs (every link is point-to-point), no virtual links,
no AS-external or NSSA routes, no stub areas; a summary that equals one
of the device's own active ranges is ignored (§16.2 (3)) and no
discard route is installed for an active range; a prefix the device
itself advertises (no next hop) is not a route.
"""

from __future__ import annotations

import heapq


def spf(adj: dict, root, first_hops: dict) -> dict:
    """``{router: (distance, frozenset of first hops)}`` over ``adj``
    (``{router: {peer: cost}}``) from ``root``.  An edge counts when
    its reverse is there too.  ``first_hops``: the root's neighbour ->
    the hop that names the link to it."""
    dist = {root: 0}
    hops: dict = {root: frozenset()}
    heap = [(0, root)]
    done = set()
    while heap:
        d, u = heapq.heappop(heap)
        if u in done:
            continue
        done.add(u)
        for v, cost in adj.get(u, {}).items():
            if u not in adj.get(v, {}):
                continue  # one-way: the far end does not list us
            if u == root:
                hop = first_hops.get(v)
                if hop is None:
                    continue  # no adjacency on that link
                via = frozenset((hop,))
            else:
                via = hops[u]
            nd = d + cost
            old = dist.get(v)
            if old is None or nd < old:
                dist[v], hops[v] = nd, via
                heapq.heappush(heap, (nd, v))
            elif nd == old:
                hops[v] = hops[v] | via
    return {v: (dist[v], hops[v]) for v in done}


def _offer(table: dict, prefix, cost: int, hops: frozenset) -> None:
    cur = table.get(prefix)
    if cur is None or cost < cur[0]:
        table[prefix] = (cost, hops)
    elif cost == cur[0]:
        table[prefix] = (cost, cur[1] | hops)


def routes(model: dict) -> dict:
    dut = model["dut"]
    trees = {
        area: spf(adj, dut, model["first_hops"].get(area, {}))
        for area, adj in model["areas"].items()
    }
    intra: dict = {}
    active = set()
    for area, entries in model["prefixes"].items():
        tree = trees.get(area, {})
        mine = model["ranges"].get(area, [])
        for router, prefix, metric in entries:
            reach = tree.get(router)
            if reach is None:
                continue
            _offer(intra, prefix, reach[0] + metric, reach[1])
            for rng in mine:
                if prefix.subnet_of(rng):
                    active.add(rng)
                    break
    inter: dict = {}
    backbone = trees.get(model["backbone"], {})
    for abr, prefix, cost in model["summaries"]:
        reach = backbone.get(abr)
        if abr == dut or reach is None or prefix in active:
            continue
        _offer(inter, prefix, reach[0] + cost, reach[1])
    table = {p: r for p, r in inter.items() if p not in intra}
    table.update(intra)
    return {p: r for p, r in table.items() if r[1]}
