"""A PoP-structured ISP backbone for the storm: the shape Rocketfuel
measured (Spring et al., SIGCOMM 2002, AS7018: about 10,000 routers,
14,000 links, 110 PoPs), generated from the configuration file's
``graph_seed`` and held by the same device-under-test wiring as
``stormnet.StormNet``.

The graph (``build_graph``): PoPs of heavy-tailed size on a seeded
plane, each a small backbone core (a mesh up to four routers, a ring
above) with access routers homed to one or two routers of their PoP by
a rank-skewed choice, so that a few routers fill their ports (the
``port_cap``, which is the ELL width) and most have one or two links;
aggregation routers wherever a PoP's core runs out of ports; long-haul
links to the nearest PoPs and among the largest, their cost growing
with distance; shared-risk groups of the long-haul links that leave one
PoP in one direction (one conduit).  Every count is fixed by the seed:
the same edges, and the same ELL width, for every traffic seed.

The network (``PopNet``): ``StormNet``'s event primitives, plus a
shared-risk cut (``srlg``) and a router loss or return (``node``), each
ONE causal ``lsa`` event carrying every Router-LSA it changes.  A lost
router's own LSA stays in the LSDB; its routes leave by the two-way
check alone.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from ipaddress import IPv4Address, IPv4Network

import numpy as np

from holo_tpu.protocols.ospf.instance import (
    IfConfig,
    InstanceConfig,
    OspfInstance,
    SpfTimers,
)
from holo_tpu.protocols.ospf.interface import IfType, IsmState
from holo_tpu.protocols.ospf.neighbor import Neighbor, NsmState
from holo_tpu.protocols.ospf.packet import (
    Lsa,
    LsaRouter,
    LsaType,
    Options,
)
from holo_tpu.routing.rib import MockKernel, RibManager
from holo_tpu.telemetry import convergence
from holo_tpu.utils.ibus import Ibus
from holo_tpu.utils.runtime import EventLoop, VirtualClock

from benchmark.stormnet import (
    StormNet,
    _DiscardIo,
    _p2p,
    _rid,
    _StormActor,
    _stub,
)

BACKBONE, AGGREGATION, ACCESS = 0, 1, 2
#: the device under test is router 0, as in ``StormNet``
DUT = 0


@dataclass
class PopGraph:
    """The deployment's graph, before any instance holds it."""

    adj: dict[int, dict[int, int]]  # router -> {peer: cost}, both ways
    role: np.ndarray  # int8[n]: BACKBONE / AGGREGATION / ACCESS
    pop: np.ndarray  # int32[n]: PoP of each router
    pop_sizes: list[int]
    dut_uplinks: list[int]  # the DUT's two long-haul peers (e0, e1)
    dut_peers: list[int]  # all its neighbours, uplinks first
    srlgs: list[tuple[tuple[int, int], ...]]  # groups of links (a < b)
    stub_owners: set[int] = field(default_factory=set)

    @property
    def n_routers(self) -> int:
        return int(self.role.shape[0])

    @property
    def n_links(self) -> int:
        return sum(len(peers) for peers in self.adj.values()) // 2

    def degrees(self) -> np.ndarray:
        return np.array(
            [len(self.adj[i]) for i in range(self.n_routers)], np.int64
        )

    def hop_diameter(self) -> int:
        """Longest shortest path in hops, exactly: one breadth-first
        search per router (seconds at 10,000 routers; not run in set-up)."""
        from scipy.sparse import csr_matrix
        from scipy.sparse.csgraph import shortest_path

        n = self.n_routers
        rows = [a for a, peers in self.adj.items() for _ in peers]
        cols = [b for peers in self.adj.values() for b in peers]
        graph = csr_matrix((np.ones(len(rows), np.int8), (rows, cols)), (n, n))
        worst = 0
        for at in range(0, n, 500):
            hops = shortest_path(
                graph, unweighted=True, indices=np.arange(at, min(at + 500, n))
            )
            worst = max(worst, int(hops.max()))
        return worst


def _pop_sizes(lsdb: dict, rng: np.random.Generator) -> list[int]:
    """Heavy-tailed PoP sizes summing to the router count: a Zipf law
    with an offset, jittered, largest first."""
    law, n_pops = lsdb["pop_size_law"], lsdb["pops"]
    rank = np.arange(1, n_pops + 1)
    raw = (rank + law["offset"]) ** -law["exponent"]
    raw = raw * np.exp(rng.normal(0.0, law["jitter"], n_pops))
    raw = np.sort(raw)[::-1]
    floor = law["min_routers"]
    sizes = np.maximum(
        np.floor(raw / raw.sum() * lsdb["routers"]).astype(int), floor
    )
    sizes[0] += lsdb["routers"] - int(sizes.sum())  # the remainder
    return sizes.tolist()


def build_graph(lsdb: dict) -> PopGraph:
    """The graph of ``configs/<name>.json``'s ``lsdb`` block, from its
    ``graph_seed`` alone."""
    rng = np.random.default_rng(lsdb["graph_seed"])
    n, cap = lsdb["routers"], lsdb["port_cap"]
    sizes = _pop_sizes(lsdb, rng)
    n_pops = len(sizes)
    lo_b, hi_b = lsdb["backbone_per_pop"]
    n_back = [
        int(np.clip(round(np.sqrt(s) / lsdb["backbone_sqrt_div"]), lo_b, hi_b))
        for s in sizes
    ]
    # The DUT's PoP: the median-sized one; the DUT is its first
    # backbone router and router 0.
    dut_pop = n_pops // 2
    want = lsdb["dut_neighbours"]
    n_back[dut_pop] = want["backbone"] + 1  # a mesh: three peers

    adj: dict[int, dict[int, int]] = {i: {} for i in range(n)}
    role = np.full(n, ACCESS, np.int8)
    pop = np.zeros(n, np.int32)

    def link(a: int, b: int, cost: int) -> None:
        adj[a][b] = cost
        adj[b][a] = cost

    # -- router numbering: DUT first, then PoP by PoP (backbone first)
    backbone: list[list[int]] = [[] for _ in sizes]
    members: list[list[int]] = [[] for _ in sizes]
    nxt = 1
    for p in [dut_pop, *(q for q in range(n_pops) if q != dut_pop)]:
        ids = list(range(nxt, nxt + sizes[p] - (p == dut_pop)))
        nxt += len(ids)
        if p == dut_pop:
            ids = [DUT, *ids]
        members[p] = ids
        backbone[p] = ids[: n_back[p]]
        pop[ids] = p
        role[backbone[p]] = BACKBONE

    lo_c, hi_c = lsdb["intra_pop_cost"]

    def intra_cost() -> int:
        return int(rng.integers(lo_c, hi_c + 1))

    # -- the PoP cores: a mesh up to four routers, a ring above
    for core in backbone:
        if len(core) <= 4:
            for i, a in enumerate(core):
                for b in core[i + 1:]:
                    link(a, b, intra_cost())
        else:
            for a, b in zip(core, core[1:] + core[:1]):
                link(a, b, intra_cost())

    # -- long-haul links: each PoP to its nearest PoPs, the largest
    # PoPs also to each other; cost grows with distance
    xy = rng.random((n_pops, 2))
    dist = np.hypot(*(xy[:, None, :] - xy[None, :, :]).transpose(2, 0, 1))
    lo_n, hi_n = lsdb["pop_neighbours"]
    pairs: set[tuple[int, int]] = set()
    for p in range(n_pops):
        near = np.argsort(dist[p])[1: 1 + int(rng.integers(lo_n, hi_n + 1))]
        pairs.update((min(p, int(q)), max(p, int(q))) for q in near)
    core_pops = range(lsdb["core_pops"])  # sizes are sorted, largest first
    pairs.update((p, q) for p in core_pops for q in core_pops if p < q)
    # An island of small PoPs that are each other's nearest: one more
    # link, its shortest, to the part that holds the largest PoP.
    while True:
        comp = list(range(n_pops))
        for p, q in sorted(pairs):
            cp, cq = comp[p], comp[q]
            if cp != cq:
                comp = [cp if c == cq else c for c in comp]
        main = np.array(comp) == comp[0]
        if main.all():
            break
        apart = np.where(main[:, None] & ~main[None, :], dist, np.inf)
        p, q = np.unravel_index(int(np.argmin(apart)), apart.shape)
        pairs.add((min(int(p), int(q)), max(int(p), int(q))))
    lo_h, hi_h = lsdb["inter_pop_cost"]
    haul: dict[int, int] = {}  # long-haul links per router, so far
    far_end: dict[tuple[int, int], int] = {}  # link -> the PoP it leads to

    def gateway(p: int) -> int:
        """The backbone router of ``p`` with the fewest long-haul links
        (the DUT takes exactly its two uplinks, and takes them first)."""
        if p == dut_pop and haul.get(DUT, 0) < want["uplinks"]:
            return DUT
        pool = [r for r in backbone[p] if r != DUT]
        return min(pool, key=lambda r: (haul.get(r, 0), r))

    for p, q in sorted(pairs, key=lambda pq: (dist[pq], pq)):
        a, b = gateway(p), gateway(q)
        cost = int(np.clip(
            round(lo_h + dist[p, q] / np.sqrt(2.0) * (hi_h - lo_h)), lo_h, hi_h
        ))
        link(a, b, cost)
        haul[a] = haul.get(a, 0) + 1
        haul[b] = haul.get(b, 0) + 1
        far_end[(a, b)] = q
        far_end[(b, a)] = p
    dut_uplinks = sorted(b for (a, b) in far_end if a == DUT)
    if len(dut_uplinks) != want["uplinks"]:
        raise ValueError(
            f"the DUT's PoP has {len(dut_uplinks)} long-haul links, "
            f"{want['uplinks']} uplinks asked for"
        )

    # -- access routers: homed to one or two routers of their PoP,
    # chosen by a rank-skewed draw among those with a free port;
    # aggregation routers where the core runs out of ports
    dual_share = lsdb["dual_homed_share"]
    access_ord = 0
    stub_owners: set[int] = set()
    for p in range(n_pops):
        rest = members[p][n_back[p]:]
        # Ports the core still has, after reserving two per aggregation
        # router; each aggregation router brings cap - 2 more.
        free = sum(cap - len(adj[r]) for r in backbone[p] if r != DUT)
        if p == dut_pop:
            free += want["access"]
        n_agg = 0
        while True:
            n_acc = len(rest) - n_agg
            need = n_acc + round(dual_share * n_acc)
            if need <= free - 2 * n_agg + (cap - 2) * n_agg:
                break
            n_agg += 1
        aggs, access = rest[:n_agg], rest[n_agg:]
        role[aggs] = AGGREGATION
        for r in aggs:
            ups = sorted(
                (b for b in backbone[p] if b != DUT),
                key=lambda b: (len(adj[b]), b),
            )[:2]
            for b in ups:
                link(r, b, intra_cost())
        hubs = [r for r in backbone[p] if r != DUT] + aggs
        weight = 1.0 / np.arange(1, len(hubs) + 1)
        second = set(rng.choice(
            len(access), size=round(dual_share * len(access)), replace=False
        ).tolist())
        dut_quota = want["access"] if p == dut_pop else 0

        def home(r: int) -> None:
            open_ = np.array([
                len(adj[h]) < cap and h not in adj[r] for h in hubs
            ])
            w = weight * open_
            h = hubs[int(rng.choice(len(hubs), p=w / w.sum()))]
            link(r, h, intra_cost())

        for k, r in enumerate(access):
            if k < dut_quota:
                link(r, DUT, intra_cost())
            else:
                home(r)
            if k in second:
                home(r)
            if access_ord % lsdb["prefix_every"] == 0:
                stub_owners.add(r)
            access_ord += 1

    # -- shared-risk groups: the long-haul links leaving one PoP within
    # one angular window (one conduit), never a link of the DUT
    lo_s, hi_s = lsdb["srlg_size"]
    window = np.deg2rad(lsdb["srlg_window_deg"])
    groups: set[tuple[tuple[int, int], ...]] = set()
    for p in range(n_pops):
        out = [
            (a, b) for (a, b), q in far_end.items()
            if pop[a] == p and DUT not in (a, b)
        ]
        bearing = {
            (a, b): float(np.arctan2(*(xy[far_end[(a, b)]] - xy[p])[::-1]))
            for a, b in out
        }
        out.sort(key=lambda ab: (bearing[ab], ab))
        for i, first in enumerate(out):
            run = [first]
            for nxt_link in out[i + 1:]:
                if bearing[nxt_link] - bearing[first] > window:
                    break
                run.append(nxt_link)
            run = run[:hi_s]
            if len(run) >= lo_s:
                groups.add(tuple(sorted(
                    (min(a, b), max(a, b)) for a, b in run
                )))
    ordered = sorted(groups)
    if len(ordered) < lsdb["srlgs"]:
        raise ValueError(
            f"{len(ordered)} conduits of {lo_s}-{hi_s} links, "
            f"{lsdb['srlgs']} asked for"
        )
    keep = rng.choice(len(ordered), size=lsdb["srlgs"], replace=False)
    srlgs = [ordered[i] for i in sorted(keep.tolist())]

    dut_peers = dut_uplinks + sorted(set(adj[DUT]) - set(dut_uplinks))
    return PopGraph(
        adj=adj, role=role, pop=pop, pop_sizes=sizes,
        dut_uplinks=dut_uplinks, dut_peers=dut_peers, srlgs=srlgs,
        stub_owners=stub_owners,
    )


class PopNet(StormNet):
    """The DUT ``OspfInstance`` of ``StormNet`` (virtual-clock loop,
    ibus, ``RibManager``, ``MockKernel``) holding a ``PopGraph``: the
    DUT is router 0 with one point-to-point interface ``e<k>`` and one
    FULL neighbour per link, ``e0`` and ``e1`` its long-haul uplinks.
    ``flap``, ``bfd``, ``carrier`` and ``apply_lsas`` are the parent's;
    ``StormNet.__init__`` builds its own graph and is not called."""

    def __init__(
        self, lsdb: dict, spf_backend, spf_delay: dict, rxmt_delay: float
    ):
        graph = self.graph = build_graph(lsdb)
        self.n_routers = graph.n_routers
        self.loop = EventLoop(clock=VirtualClock())
        self.bus = Ibus(self.loop)
        self.kernel = MockKernel()
        self.rib = RibManager(self.bus, self.kernel)
        self.rib.name = "routing"
        self.loop.register(self.rib)
        self.rxmt_delay = float(rxmt_delay)
        self.inst = OspfInstance(
            name=self.DUT,
            config=InstanceConfig(
                router_id=_rid(DUT), spf=SpfTimers(**spf_delay)
            ),
            netio=_DiscardIo(),
            spf_backend=spf_backend,
        )
        self.loop.register(self.inst)
        self.inst.attach_ibus(self.bus, routing_actor="routing")
        self.loop.register(_StormActor(self), name=self.ACTOR)

        self.adj = graph.adj
        self.stub_owners = graph.stub_owners
        self.down: set[tuple[int, int]] = set()  # links a flap took down
        self._cut: dict[tuple[int, int], int] = {}  # cuts and losses over a link
        self.srlg_down: list[int] = []  # oldest first
        self.node_down: list[int] = []  # oldest first
        self._last_links: dict[int, list] = {}  # a lost router's links
        self._seq: dict[int, int] = {}
        self.most_lsas = 0  # the most Router-LSAs one event carried
        # Never touched by a flap, a cut or a loss: the DUT's own links
        # (bfd, carrier and ifconfig act on e0 / e1), and for a loss
        # the DUT's neighbours too.
        self.flappable = sorted(
            (a, b) for a, peers in graph.adj.items() for b in peers
            if DUT < a < b
        )
        near = {DUT, *graph.dut_peers}
        self.losable = {
            "access": [
                i for i in range(self.n_routers)
                if graph.role[i] == ACCESS and i not in near
            ],
            "core": [
                i for i in range(self.n_routers)
                if graph.role[i] != ACCESS and i not in near
            ],
        }

        self.g0, self.g1 = graph.dut_uplinks
        self._dut_addr: dict[int, IPv4Address] = {}
        self._e0_cost = graph.adj[DUT][self.g0]
        for k, peer in enumerate(graph.dut_peers):
            ours = IPv4Address((10 << 24) | (255 << 16) | (k << 8) | 1)
            theirs = ours + 1
            self._dut_addr[peer] = ours
            iface = self.inst.add_interface(
                f"e{k}",
                IfConfig(if_type=IfType.POINT_TO_POINT, cost=1),
                IPv4Network((int(ours) - 1, 30)),
                ours,
            )
            iface.state = IsmState.POINT_TO_POINT
            iface.neighbors[_rid(peer)] = Neighbor(
                router_id=_rid(peer), src=theirs, state=NsmState.FULL
            )
        self.g0_addr = self._dut_addr[self.g0] + 1
        self.g1_addr = self._dut_addr[self.g1] + 1
        self.area = self.inst.areas[next(iter(self.inst.areas))]
        now = self.loop.clock.now()
        for i in range(self.n_routers):
            self.area.lsdb.install(self._router_lsa(i), now)
        # First full SPF + RIB sync (set-up, outside the window).
        self.inst._schedule_spf()
        self.loop.advance(30.0)

    # -- LSA construction

    def _link_up(self, a: int, b: int) -> bool:
        edge = (min(a, b), max(a, b))
        return edge not in self.down and not self._cut.get(edge)

    def _links_of(self, i: int) -> list:
        links = [
            _p2p(
                _rid(peer),
                self._dut_addr[peer] if i == DUT else IPv4Address(0),
                metric,
            )
            for peer, metric in sorted(self.adj[i].items())
            if self._link_up(i, peer)
        ]
        if i in self.stub_owners:
            links.append(_stub(IPv4Network(((172 << 24) | (i << 8), 24)), 1))
        return links

    def _router_lsa(self, i: int) -> Lsa:
        """As the parent's, with the DUT's twelve links each on its own
        interface address, and a link left out while a flap, a cut or a
        lost router at its far end holds it down.  A lost router says
        what it said last (``flap`` re-installs both ends' LSAs)."""
        seq = self._seq.get(i, 0) + 1
        self._seq[i] = seq
        lsa = Lsa(
            age=1, options=Options(0x02), type=LsaType.ROUTER,
            lsid=_rid(i), adv_rtr=_rid(i), seq_no=seq,
            body=LsaRouter(links=self._last_links.get(i) or self._links_of(i)),
        )
        lsa.encode()  # RFC 2328 13.2 compares the encoded body
        return lsa

    # -- the two further event primitives

    def _lsa_event(self, routers, lost: bool, **attrs) -> int | None:
        """One causal ``lsa`` event carrying the Router-LSA of each of
        ``routers``; ``lost`` defers the whole arrival by ``rxmt_delay``."""
        routers = sorted(set(routers) - set(self.node_down))
        self.most_lsas = max(self.most_lsas, len(routers))
        eid = convergence.begin(convergence.TRIGGER_LSA, **attrs)
        self._deliver(
            [self._router_lsa(i) for i in routers], eid,
            delay=self.rxmt_delay if lost else 0.0,
        )
        return eid

    def _hold(self, edges, down: bool) -> None:
        for edge in edges:
            self._cut[edge] = self._cut.get(edge, 0) + (1 if down else -1)

    def srlg(self, group: int, lost: bool) -> int | None:
        """Toggle shared-risk group ``group``: every link of the
        conduit goes (or comes back) in one event."""
        links = self.graph.srlgs[group]
        down = group not in self.srlg_down
        if down:
            self.srlg_down.append(group)
        else:
            self.srlg_down.remove(group)
        self._hold(links, down)
        return self._lsa_event(
            [r for link in links for r in link], lost,
            srlg=group, state="down" if down else "up",
        )

    def node(self, router: int, lost: bool) -> int | None:
        """Toggle ``router``.  On its loss every neighbour re-originates
        without the link and the router's own LSA stays as it is (it
        ages; nobody flushes it): its routes leave by the two-way check.
        On its return the neighbours and the router itself re-originate."""
        if router == DUT or router in self.graph.dut_peers:
            raise ValueError(f"router {router} is the DUT or a neighbour of it")
        down = router not in self.node_down
        if down:
            self._last_links[router] = self._links_of(router)
            self.node_down.append(router)
        else:
            del self._last_links[router]
            self.node_down.remove(router)
        peers = sorted(self.adj[router])
        self._hold(
            [(min(router, p), max(router, p)) for p in peers], down
        )
        return self._lsa_event(
            peers if down else [router, *peers], lost,
            node=router, state="down" if down else "up",
        )

    def ifconfig_metric(self) -> None:
        """Config event on the DUT: the metric of uplink ``e0`` flips
        between its configured cost and twice that (the parent flips a
        gateway link between 1 and 2; a long-haul cost is not 1)."""
        cur = self.adj[DUT][self.g0]
        new = self._e0_cost if cur != self._e0_cost else 2 * self._e0_cost
        self.adj[DUT][self.g0] = self.adj[self.g0][DUT] = new
        eid = convergence.begin(convergence.TRIGGER_IFCONFIG, ifname="e0")
        self._deliver([self._router_lsa(DUT)], eid)
