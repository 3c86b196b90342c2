"""The benchmark's OSPFv3 multi-area network: one device under test, an
area border router (``OspfV3Instance``), attached to four halls (each a
k-ary fat-tree and an OSPF area of its own) and a backbone, with
everything around it real: LSA installs run through
``_install_and_flood`` (RFC 8405 SPF-delay FSM, full / partial
classification, the convergence tracker's origin stamps), the
instance's own Router-, Link-, Intra-Area-Prefix and Inter-Area-Prefix
LSAs are its own originations, its routes reach a ``RibManager`` and a
``MockKernel`` FIB through the daemon's route sink
(``holo_tpu/routing/sink.py``) under the SPF run's causal context, and
BFD and carrier events drive the RIB's local repair.

Layout (``build_layout``; every figure from the configuration's
``lsdb`` block): a hall is the fat-tree of Al-Fares et al. (SIGCOMM
2008, sec 3) with ``k`` pods of k/2 edge and k/2 aggregation switches
and (k/2)^2 core switches; in pod 0 of every hall ``border_routers``
edge positions are held by the region's border routers (the device
under test and its peers, each in every hall), which link to that
pod's aggregation switches.  The backbone holds the border routers in
a ring, ``wan_cores`` core routers every border router links to, and
the border routers of ``remote_halls`` halls the device is not attached
to, which exist here only as the pod ranges their border routers
advertise.  Every router advertises a /128 loopback, every edge switch
a /64 besides; every border router has one /56 range per pod.

The loop's clock is virtual: RFC 8405 holds and the retransmit penalty
of a lost LSA pass in no wall time.
"""

from __future__ import annotations

import sys
from dataclasses import dataclass, field
from ipaddress import IPv4Address, IPv6Address, IPv6Network

import numpy as np

try:
    from holo_tpu.protocols.ospf import packet_v3 as P
    from holo_tpu.protocols.ospf.instance_v3 import OspfV3Instance, V3IfConfig
    from holo_tpu.protocols.ospf.spf_run import SpfTimers
    from holo_tpu.routing.sink import RouteSink, v6_route_item
except ImportError as exc:  # a program older than this cell
    print(
        "benchmark: the files do not fit together: this program cannot "
        f"run an OSPFv3 multi-area cell ({exc})", file=sys.stderr,
    )
    raise SystemExit(2)

from benchmark import fabric
from benchmark.stormnet import _DiscardIo
from holo_tpu.protocols.ospf.neighbor import Neighbor, NsmState
from holo_tpu.routing.rib import MockKernel, RibManager
from holo_tpu.telemetry import convergence
from holo_tpu.utils.ibus import (
    TOPIC_BFD_STATE,
    TOPIC_INTERFACE_UPD,
    BfdStateUpd,
    Ibus,
)
from holo_tpu.utils.runtime import Actor, EventLoop, VirtualClock
from holo_tpu.utils.southbound import InterfaceUpdMsg, Protocol

BACKBONE = 0
REGION = 0x20010DB8 << 96  # 2001:db8::/32


def rid(kind: int, n: int) -> int:
    """Router ids 10.<kind>.x.y: kind 0 the border routers (n from 0),
    so that they sort first in every area and the device under test is
    vertex 0 everywhere; 1..4 a hall's switches (n the fat-tree
    position); 100 the WAN cores; 101 the remote border routers."""
    return (10 << 24) | (kind << 16) | (n + 1)


def _ll(router: int, ifid: int) -> IPv6Address:
    """A router's link-local address on its interface ``ifid``."""
    return IPv6Address((0xFE80 << 112) | (router << 32) | ifid)


def _net(hall: int, hextet: int, low: int, length: int) -> IPv6Network:
    return IPv6Network(
        (REGION | (hall << 80) | (hextet << 64) | low, length)
    )


def pod_range(hall: int, pod: int) -> IPv6Network:
    return _net(hall, pod << 8, 0, 56)


@dataclass
class Layout:
    """The deployment as plain data: what the generator made."""

    k: int
    halls: list[int]  # area ids of the local halls
    remote_halls: list[int]
    dut: int
    borders: list[int]  # router ids, the device under test first
    wan: list[int]
    remote_abrs: list[int]
    # area -> {router: {peer: cost}}: both directions of every link
    adj: dict = field(default_factory=dict)
    role: dict = field(default_factory=dict)  # hall router -> edge|agg|core
    pod0_aggs: dict = field(default_factory=dict)  # hall -> [router]
    # area -> [(router, prefix, metric)]
    prefixes: dict = field(default_factory=dict)
    ranges: dict = field(default_factory=dict)  # hall -> [prefix], by pod
    # (abr, area it is flooded in, prefix) -> cost: what the other
    # border routers advertise, as the generator computed it
    summaries: dict = field(default_factory=dict)

    def links(self, area: int) -> int:
        return sum(len(p) for p in self.adj[area].values()) // 2


def _hall_arrays(k: int):
    """Positions and links of one k-ary fat-tree as ``benchmark/fabric.py``
    numbers them (cores, then aggregation, then edge switches): edges
    2l and 2l + 1 of its ``Topology`` are the two directions of link l."""
    topo = fabric.fat_tree(k, 1, 1, 0)
    half = k // 2
    return half * half, k * half, topo.edge_src[0::2], topo.edge_dst[0::2]


def build_layout(lsdb: dict) -> Layout:
    k, n_border = lsdb["k"], lsdb["border_routers"]
    half = k // 2
    halls = list(range(1, lsdb["halls"] + 1))
    remote = list(range(halls[-1] + 1, halls[-1] + 1 + lsdb["remote_halls"]))
    graph_rng = np.random.default_rng(lsdb["graph_seed"])
    cost_rng = np.random.default_rng(lsdb["cost_seed"])
    borders = [rid(0, b) for b in range(n_border)]
    wan = [rid(100, i) for i in range(lsdb["wan_cores"])]
    remote_abrs = [rid(101, i) for i in range(lsdb["remote_border_routers"])]
    lay = Layout(
        k=k, halls=halls, remote_halls=remote, dut=borders[0],
        borders=borders, wan=wan, remote_abrs=remote_abrs,
    )
    n_core, n_agg, a, b = _hall_arrays(k)
    first_edge = n_core + n_agg
    # Which edge positions of pod 0 the border routers hold.
    held = sorted(graph_rng.choice(half, size=n_border, replace=False).tolist())
    lo, hi = lsdb["fabric_cost"]
    for hall in halls:
        name = np.array([rid(hall, s) for s in range(first_edge + n_agg)])
        for slot, border in zip(held, borders):
            name[first_edge + slot] = border
        ab = cost_rng.integers(lo, hi + 1, a.size)
        ba = cost_rng.integers(lo, hi + 1, a.size)
        is_border = np.isin(name[b], borders)
        ab[is_border] = ba[is_border] = lsdb["border_uplink_cost"]
        adj: dict = {int(r): {} for r in name}
        for u, v, c_uv, c_vu in zip(
            name[a].tolist(), name[b].tolist(), ab.tolist(), ba.tolist()
        ):
            adj[u][v] = c_uv
            adj[v][u] = c_vu
        lay.adj[hall] = adj
        lay.pod0_aggs[hall] = [int(name[n_core + i]) for i in range(half)]
        for s in range(first_edge + n_agg):
            lay.role[int(name[s])] = (
                "core" if s < n_core else "agg" if s < first_edge else "edge"
            )
        # Prefixes: a /128 per switch, a /64 behind every edge switch.
        entries = []
        for c in range(n_core):  # core loopbacks come out of the pod blocks
            per_pod = -(-n_core // k)
            entries.append((
                int(name[c]),
                _net(hall, ((c // per_pod) << 8) | 0xFF, 0x200 + c % per_pod, 128),
                0,
            ))
        for pod in range(k):
            for i in range(half):
                entries.append((
                    int(name[n_core + pod * half + i]),
                    _net(hall, (pod << 8) | 0xFF, 0x100 + i, 128), 0,
                ))
                sw = int(name[first_edge + pod * half + i])
                if sw in borders:
                    continue
                entries.append(
                    (sw, _net(hall, (pod << 8) | 0xFF, 1 + i, 128), 0)
                )
                entries.append(
                    (sw, _net(hall, (pod << 8) | i, 0, 64),
                     lsdb["subnet_metric"])
                )
        lay.prefixes[hall] = entries
        lay.ranges[hall] = [pod_range(hall, pod) for pod in range(k)]
    for r in borders:
        lay.role[r] = "border"

    # The backbone: ring of the local border routers, every border
    # router (local and remote) to every WAN core.
    adj = {r: {} for r in borders + wan + remote_abrs}
    for i, u in enumerate(borders):
        v = borders[(i + 1) % len(borders)]
        adj[u][v] = adj[v][u] = lsdb["ring_cost"]
    for u in borders + remote_abrs:
        for w in wan:
            adj[u][w] = adj[w][u] = lsdb["wan_cost"]
    lay.adj[BACKBONE] = adj
    lay.prefixes[BACKBONE] = [
        (r, _net(0, 0xFF00, n + 1, 128), 0)
        for n, r in enumerate(borders + wan + remote_abrs)
    ]
    _peer_summaries(lay, lsdb, graph_rng)
    return lay


def _range_costs(lay: Layout, hall: int, source: int) -> dict:
    """``{range: largest component cost}`` of ``hall`` from ``source``:
    what a border router advertises for each pod (RFC 2328 12.4.3)."""
    from benchmark import v3ref

    tree = v3ref.spf(lay.adj[hall], source, {
        peer: (peer,) for peer in lay.adj[hall][source]
    })
    width = 128 - 56
    worst: dict = {}
    for router, prefix, metric in lay.prefixes[hall]:
        reach = tree.get(router)
        if reach is None:
            continue
        key = int(prefix.network_address) >> width
        worst[key] = max(worst.get(key, 0), reach[0] + metric)
    return {
        rng: worst[int(rng.network_address) >> width]
        for rng in lay.ranges[hall]
        if int(rng.network_address) >> width in worst
    }


def _peer_summaries(lay: Layout, lsdb: dict, graph_rng) -> None:
    """What the other border routers advertise, computed once from the
    links as they are built (they stay as they are but for the
    ``summary`` events: the peers' own SPF is not simulated)."""
    from benchmark import v3ref

    lo, hi = lsdb["remote_range_cost"]
    remote = {}  # (remote abr, range) -> cost
    for abr in lay.remote_abrs:
        for hall in lay.remote_halls:
            for pod in range(lay.k):
                remote[(abr, pod_range(hall, pod))] = int(
                    graph_rng.integers(lo, hi + 1)
                )
    for (abr, rng), cost in remote.items():
        lay.summaries[(abr, BACKBONE, rng)] = cost
    peers = lay.borders[1:]
    for peer in peers:
        tree = v3ref.spf(lay.adj[BACKBONE], peer, {
            p: (p,) for p in lay.adj[BACKBONE][peer]
        })
        own = {hall: _range_costs(lay, hall, peer) for hall in lay.halls}
        # best inter-area route of the peer to each remote range
        far: dict = {}
        for (abr, rng), cost in remote.items():
            total = tree[abr][0] + cost
            far[rng] = min(far.get(rng, total), total)
        loopbacks = {
            prefix: tree[r][0] + metric
            for r, prefix, metric in lay.prefixes[BACKBONE] if r != peer
        }
        for hall in lay.halls:
            for rng, cost in own[hall].items():
                lay.summaries[(peer, BACKBONE, rng)] = cost
                for other in lay.halls:
                    if other != hall:
                        lay.summaries[(peer, other, rng)] = cost
            for prefix, cost in (far | loopbacks).items():
                lay.summaries[(peer, hall, prefix)] = cost


@dataclass
class _Deliver:
    """Storm-actor message: run ``fn`` under a causal context (the
    ``event_id`` field is what the EventLoop delivery hook activates; a
    lost arrival redelivers this same message after ``rxmt_delay``)."""

    fn: object
    event_id: tuple | None = None


class _StormActor(Actor):
    def handle(self, msg) -> None:
        if isinstance(msg, _Deliver):
            msg.fn()


class AreaNet:
    """One DUT instance + RIB over a virtual-clock loop, plus the
    generator's link model the storm mutates (and ``v3ref`` reads)."""

    DUT = "ospfv3-dut"
    ACTOR = "storm-driver"

    def __init__(
        self, lsdb: dict, spf_backend, spf_delay: dict, rxmt_delay: float,
        max_paths: int | None = None, deltas: bool = True,
    ):
        """``deltas``: the instance hands the sink only the prefixes an
        SPF run changed (as the daemon's provider has it), else its
        whole table after every run."""
        lay = self.layout = build_layout(lsdb)
        self.lsdb_spec = lsdb
        self.rxmt_delay = float(rxmt_delay)
        self.loop = EventLoop(clock=VirtualClock())
        self.bus = Ibus(self.loop)
        self.kernel = MockKernel()
        self.rib = RibManager(self.bus, self.kernel)
        self.rib.name = "routing"
        self.loop.register(self.rib)
        self.sink = RouteSink(self.rib)
        self.inst = OspfV3Instance(
            name=self.DUT, router_id=IPv4Address(lay.dut),
            netio=_DiscardIo(), spf_backend=spf_backend,
            route_cb=None if deltas else self._routes_to_rib,
            route_delta_cb=self._delta_to_rib if deltas else None,
            spf_timers=SpfTimers(**spf_delay),
        )
        self.inst.max_paths = max_paths
        self.loop.register(self.inst)
        self.loop.register(_StormActor(), name=self.ACTOR)

        # The link model's state: what a flap, a loss or a shut holds
        # down.  adj itself keeps every link and its costs.
        self.down: dict[int, set] = {a: set() for a in lay.adj}
        self._held: dict = {}  # (area, u, v) -> losses and shuts over it
        self.node_down: list = []  # (hall, router), oldest first
        self._last_links: dict = {}  # a lost router's links, as it said
        self.shut: dict[int, tuple] = {}  # hall -> the DUT link shut there
        self.withdrawn: list = []  # (abr, prefix), oldest first
        self._seq: dict = {}
        self.most_lsas = 0
        self._ifids: dict = {}
        self.flappable = {
            hall: sorted(
                (u, v) for u, peers in lay.adj[hall].items() for v in peers
                if u < v and lay.role[u] != "border"
                and lay.role[v] != "border"
            )
            for hall in lay.halls
        }
        near = {
            hall: set(lay.pod0_aggs[hall]) | set(lay.borders)
            for hall in lay.halls
        }
        self.losable = {
            hall: {
                role: [
                    r for r in lay.adj[hall]
                    if lay.role[r] == role and r not in near[hall]
                ]
                for role in ("edge", "agg", "core")
            }
            for hall in lay.halls
        }

        self._dut_ifaces()
        self._install_lsdbs()
        # The device's own LSAs, then the first full SPF, its ABR
        # originations and the RIB sync (set-up, outside the window).
        self.inst._originate_router_lsa()
        self.inst._originate_intra_area_prefix()
        self.inst._schedule_spf()
        self.loop.advance(30.0)

    # -- the device's interfaces and adjacencies

    def _ifid(self, area: int, router: int, peer: int) -> int:
        """A router's interface id on its link to ``peer``: by the
        peer's rank, apart per area; the device's own are those
        ``add_interface`` gave it."""
        if router == self.layout.dut:
            return self.dut_if[(area, peer)].iface_id
        ids = self._ifids.get((area, router))
        if ids is None:
            ids = self._ifids[(area, router)] = {
                p: (area << 8) + n + 1
                for n, p in enumerate(sorted(self.layout.adj[area][router]))
            }
        return ids[peer]

    def _dut_ifaces(self) -> None:
        lay, inst = self.layout, self.inst
        self.dut_if: dict = {}  # (area, peer) -> V3Interface
        self.dut_links: list = []  # (area, peer, ifname), every adjacency
        lo = inst.add_interface(
            "lo", V3IfConfig(area_id=IPv4Address(BACKBONE), cost=0,
                             loopback=True, passive=True),
            IPv6Address("fe80::1"), [lay.prefixes[BACKBONE][0][1]],
        )
        lo.up = True
        for area in [*lay.halls, BACKBONE]:
            for n, peer in enumerate(sorted(lay.adj[area][lay.dut])):
                ifname = f"a{area}e{n}"
                iface = inst.add_interface(
                    ifname,
                    V3IfConfig(
                        area_id=IPv4Address(area),
                        cost=lay.adj[area][lay.dut][peer],
                    ),
                    _ll(lay.dut, len(self.dut_links) + 1), [],
                )
                iface.up = True
                self.dut_if[(area, peer)] = iface
                self.dut_links.append((area, peer, ifname))
        for area, peer, _ifname in self.dut_links:
            self._adjacency(area, peer)
        for area in [*lay.halls]:
            inst.areas[IPv4Address(area)].ranges = [
                {"prefix": rng, "advertise": True, "cost": None}
                for rng in lay.ranges[area]
            ]

    def _adjacency(self, area: int, peer: int) -> None:
        """The peer FULL on its link (the ISM/NSM machinery is bypassed
        exactly as ``synth_proto`` does), and its Link-LSA."""
        iface = self.dut_if[(area, peer)]
        ifid = self._ifid(area, peer, self.layout.dut)
        iface.neighbors[IPv4Address(peer)] = Neighbor(
            router_id=IPv4Address(peer), src=_ll(peer, ifid),
            state=NsmState.FULL, iface_id=ifid,
        )
        lsa = P.Lsa(
            age=1, type=P.LsaType.LINK, lsid=IPv4Address(ifid),
            adv_rtr=IPv4Address(peer), seq_no=P.INITIAL_SEQ_NO,
            body=P.LsaLink(link_local=_ll(peer, ifid)),
        )
        lsa.encode()
        iface.link_lsdb.install(lsa, self.loop.clock.now())

    def first_hop(self, area: int, peer: int) -> tuple:
        """(ifname, link-local) the device's routes through ``peer``
        carry."""
        iface = self.dut_if[(area, peer)]
        return (
            iface.name, _ll(peer, self._ifid(area, peer, self.layout.dut))
        )

    # -- LSA construction

    def _link_up(self, area: int, u: int, v: int) -> bool:
        edge = (min(u, v), max(u, v))
        return edge not in self.down[area] and not self._held.get(
            (area, *edge)
        )

    def _links_of(self, area: int, router: int) -> list:
        return [
            P.RouterLinkV3(
                P.RouterLinkType.POINT_TO_POINT, cost,
                self._ifid(area, router, peer),
                self._ifid(area, peer, router), IPv4Address(peer),
            )
            for peer, cost in sorted(self.layout.adj[area][router].items())
            if self._link_up(area, router, peer)
        ]

    def _lsa(self, ltype, lsid: int, adv: int, body, age: int = 1):
        key = (int(ltype), lsid, adv)
        seq = self._seq[key] = self._seq.get(key, 0) + 1
        lsa = P.Lsa(
            age=age, type=ltype, lsid=IPv4Address(lsid),
            adv_rtr=IPv4Address(adv), seq_no=P.INITIAL_SEQ_NO + seq,
            body=body,
        )
        lsa.encode()  # RFC 2328 13.2 compares the encoded body
        return lsa

    def _router_lsa(self, area: int, router: int):
        """A router's Router-LSA in ``area``: a link is left out while a
        flap, a lost router at its far end or a shut holds it down; a
        lost router says what it said last.  Border routers set B."""
        links = self._last_links.get((area, router))
        if links is None:
            links = self._links_of(area, router)
        flags = (
            P.RouterFlags.B if self.layout.role.get(router) == "border"
            or router in self.layout.remote_abrs else P.RouterFlags(0)
        )
        return self._lsa(
            P.LsaType.ROUTER, 0, router,
            P.LsaRouterV3(flags=flags, links=links),
        )

    def _summary_lsa(self, abr: int, area: int, prefix, age: int = 1):
        ids = self._summary_ids.setdefault((abr, area), {})
        lsid = ids.setdefault(prefix, len(ids) + 1)
        return self._lsa(
            P.LsaType.INTER_AREA_PREFIX, lsid, abr,
            P.LsaInterAreaPrefix(
                metric=self.layout.summaries[(abr, area, prefix)],
                prefix=prefix,
            ),
            age=age,
        )

    def _install_lsdbs(self) -> None:
        lay, now = self.layout, self.loop.clock.now()
        self._summary_ids: dict = {}
        for area, adj in lay.adj.items():
            db = self.inst.areas[IPv4Address(area)].lsdb
            by_router: dict = {}
            for router, prefix, metric in lay.prefixes[area]:
                opts = P.PREFIX_OPT_LA if prefix.prefixlen == 128 else 0
                by_router.setdefault(router, []).append(
                    (prefix, metric, opts)
                )
            for router in adj:
                if router == lay.dut:
                    continue  # its own originations
                db.install(self._router_lsa(area, router), now)
                if router in by_router:
                    db.install(self._lsa(
                        P.LsaType.INTRA_AREA_PREFIX, 1, router,
                        P.LsaIntraAreaPrefix(
                            ref_type=int(P.LsaType.ROUTER),
                            ref_lsid=IPv4Address(0),
                            ref_adv_rtr=IPv4Address(router),
                            prefixes=by_router[router],
                        ),
                    ), now)
            for (abr, in_area, prefix) in lay.summaries:
                if in_area == area:
                    db.install(self._summary_lsa(abr, area, prefix), now)

    # -- routes into the RIB, as the daemon's provider puts them there

    def _routes_to_rib(self, routes: dict) -> None:
        self.sink.push(
            Protocol.OSPFV3, {p: v6_route_item(r) for p, r in routes.items()}
        )
        self._ack_all()

    def _delta_to_rib(self, changed: dict, removed) -> None:
        self.sink.push_delta(
            Protocol.OSPFV3,
            {p: v6_route_item(r) for p, r in changed.items()}, removed,
        )
        self._ack_all()

    def _ack_all(self) -> None:
        """The synthetic neighbours ack instantly: drop retransmit
        state so the storm's timer load stays bounded."""
        for iface in self.inst.interfaces.values():
            for nbr in iface.neighbors.values():
                nbr.ls_rxmt.clear()

    # -- delivery

    def _deliver(self, fn, eid, delay: float = 0.0) -> None:
        msg = _Deliver(fn, (eid,) if eid is not None else None)
        if delay > 0.0:
            self.loop.timer(self.ACTOR, lambda m=msg: m).start(delay)
        else:
            self.loop.send(self.ACTOR, msg)

    def _apply(self, lsas: list) -> None:
        """Runs inside the storm actor (causal context already active
        through the delivery hook).  ``lsas``: (area, LSA)."""
        for area, lsa in lsas:
            self.inst._install_and_flood(
                self.inst.areas[IPv4Address(area)], lsa
            )
        self._ack_all()

    def _lsa_event(self, area: int, routers, lost: bool, **attrs):
        """One causal ``lsa`` event carrying the Router-LSA of each of
        ``routers`` (a lost router says nothing new); ``lost`` defers
        the whole arrival by ``rxmt_delay``."""
        gone = {r for a, r in self.node_down if a == area}
        routers = sorted(set(routers) - gone - {self.layout.dut})
        self.most_lsas = max(self.most_lsas, len(routers))
        eid = convergence.begin(convergence.TRIGGER_LSA, **attrs)
        # The LSAs are made when they arrive: what a retransmission
        # carries 5 s later is the router's LSA of that moment, and a
        # later event on the same router may have overtaken this one
        # (an older copy installed over a newer one would leave the
        # LSDB behind the link model the reference reads).
        self._deliver(
            lambda: self._apply(
                [(area, self._router_lsa(area, r)) for r in routers]
            ),
            eid, delay=self.rxmt_delay if lost else 0.0,
        )
        return eid

    # -- the storm's event primitives

    def flap(self, area: int, edge: tuple, lost: bool):
        """Toggle a hall link: both ends re-originate, one event."""
        state = "up" if edge in self.down[area] else "down"
        (self.down[area].discard if state == "up" else self.down[area].add)(
            edge
        )
        return self._lsa_event(
            area, edge, lost, hall=area, edge=f"{edge[0]}-{edge[1]}",
            state=state,
        )

    def _hold(self, area: int, router: int, peers, down: bool) -> None:
        for p in peers:
            key = (area, min(router, p), max(router, p))
            self._held[key] = self._held.get(key, 0) + (1 if down else -1)

    def node(self, area: int, router: int, lost: bool):
        """Toggle a switch.  On its loss every neighbour re-originates
        without the link and the switch's own LSAs stay as they are
        (they age; nobody flushes them): its routes leave by the
        two-way check.  On its return all of them re-originate."""
        down = (area, router) not in self.node_down
        peers = sorted(self.layout.adj[area][router])
        if down:
            self._last_links[(area, router)] = self._links_of(area, router)
            self.node_down.append((area, router))
        else:
            self.node_down.remove((area, router))
            del self._last_links[(area, router)]
        self._hold(area, router, peers, down)
        return self._lsa_event(
            area, peers if down else [router, *peers], lost,
            hall=area, node=router, state="down" if down else "up",
        )

    def summary(self, abr: int, prefix, lost: bool):
        """A border router withdraws (MaxAge) or re-advertises one pod
        range, in every area it floods it in: Inter-Area-Prefix LSAs
        only, the prefix-scoped partial run's path."""
        gone = (abr, prefix) in self.withdrawn
        (self.withdrawn.remove if gone else self.withdrawn.append)(
            (abr, prefix)
        )
        areas = [
            area for (a, area, p) in self.layout.summaries
            if a == abr and p == prefix
        ]
        self.most_lsas = max(self.most_lsas, len(areas))
        eid = convergence.begin(
            convergence.TRIGGER_LSA, abr=abr, prefix=str(prefix),
            state="advertised" if gone else "withdrawn",
        )

        def arrive() -> None:  # as it stands when it arrives, as above
            age = P.MAX_AGE if (abr, prefix) in self.withdrawn else 1
            self._apply([
                (area, self._summary_lsa(abr, area, prefix, age=age))
                for area in areas
            ])

        self._deliver(arrive, eid, delay=self.rxmt_delay if lost else 0.0)
        return eid

    def bfd(self, link: tuple, state: str) -> None:
        area, peer, ifname = link
        eid = convergence.begin(
            convergence.TRIGGER_BFD, state=state, ifname=ifname
        )
        with convergence.activation(eid):
            self.bus.publish(TOPIC_BFD_STATE, BfdStateUpd(
                (ifname, self.first_hop(area, peer)[1]), state
            ))

    def carrier(self, link: tuple, operative: bool) -> None:
        eid = convergence.begin(
            convergence.TRIGGER_CARRIER, ifname=link[2], operative=operative
        )
        with convergence.activation(eid):
            self.bus.publish(
                TOPIC_INTERFACE_UPD,
                InterfaceUpdMsg(ifname=link[2], ifindex=0,
                                operative=operative),
            )

    def ifconfig_cost(self, link: tuple) -> None:
        """Config event on the device: an uplink's cost flips between
        what the file gives it and twice that; the device re-originates
        its Router-LSA itself (``iface_cost_update``)."""
        area, peer, ifname = link
        base = self.layout.adj[area][peer][self.layout.dut]
        adj = self.layout.adj[area][self.layout.dut]
        cost = adj[peer] = base if adj[peer] != base else 2 * base
        eid = convergence.begin(convergence.TRIGGER_IFCONFIG, ifname=ifname)
        self._deliver(
            lambda: self.inst.iface_cost_update(ifname, cost), eid
        )

    def ifconfig_shut(self, link: tuple) -> None:
        """Config event on the device: shut or no-shut of an uplink
        (the caller keeps to one shut uplink an area).  The device
        takes its interface down (its adjacency with it) or up itself;
        the switch at the far end re-originates in the same event, and
        on no-shut the adjacency comes back FULL."""
        area, peer, ifname = link
        dut = self.layout.dut
        down = self.shut.get(area) != link
        if down:
            self.shut[area] = link
        else:
            del self.shut[area]
        self._hold(area, dut, [peer], down)
        eid = convergence.begin(
            convergence.TRIGGER_IFCONFIG, ifname=ifname,
            state="shut" if down else "no-shut",
        )

        def apply() -> None:
            if down:
                self.inst.if_down(ifname)
            else:
                self.inst.if_up(ifname)
                self._adjacency(area, peer)
                self.inst._originate_router_lsa()
            self._apply([(area, self._router_lsa(area, peer))])

        self._deliver(apply, eid)

    # -- what the generator really made (the configuration file states
    # these, tests/benchmark/test_areanet.py holds it to them)

    def sizes(self) -> dict:
        lay, inst = self.layout, self.inst
        per_area = {}
        for area in lay.adj:
            db = inst.areas[IPv4Address(area)].lsdb
            kinds = [
                (e.lsa.type, e.lsa.adv_rtr == inst.router_id)
                for e in db.all() if not e.lsa.is_maxage
            ]
            st = inst._spf_delta_bases[IPv4Address(area)]
            per_area[str(area)] = {
                "routers": sum(t == P.LsaType.ROUTER for t, _m in kinds),
                "links": lay.links(area),
                "directed_edges": int(st.topo.n_edges),
                "atoms": len(st.atoms),
                "root": int(st.topo.root),
                "intra_area_prefix_lsas": sum(
                    t == P.LsaType.INTRA_AREA_PREFIX for t, _m in kinds
                ),
                "inter_area_prefix_lsas_held": sum(
                    t == P.LsaType.INTER_AREA_PREFIX and not mine
                    for t, mine in kinds
                ),
                "inter_area_prefix_lsas_originated": sum(
                    t == P.LsaType.INTER_AREA_PREFIX and mine
                    for t, mine in kinds
                ),
                "dut_neighbours": sum(
                    1 for a, _p, _i in self.dut_links if a == area
                ),
            }
        routers = set()
        for adj in lay.adj.values():
            routers |= set(adj)

        def total(key: str) -> int:
            return sum(a[key] for a in per_area.values())

        return {
            "routers": len(routers),
            "directed_edges": total("directed_edges"),
            "dut_interfaces": len(self.dut_links),
            "intra_area_prefixes": sum(
                len(v) for v in lay.prefixes.values()
            ),
            "local_ranges": sum(len(v) for v in lay.ranges.values()),
            "remote_ranges": len({
                p for (abr, _a, p) in lay.summaries
                if abr in lay.remote_abrs
            }),
            "inter_area_prefix_lsas_held": total(
                "inter_area_prefix_lsas_held"
            ),
            "inter_area_prefix_lsas_originated": total(
                "inter_area_prefix_lsas_originated"
            ),
            "rib_routes": len(self.rib.routes),
            "fib_routes": len(self.kernel.fib),
            "areas": per_area,
        }

    # -- the link model as the plain reference reads it

    def model(self) -> dict:
        lay = self.layout
        areas, hops = {}, {}
        for area, adj in lay.adj.items():
            areas[area] = {
                u: {v: c for v, c in peers.items()
                    if self._link_up(area, u, v)}
                for u, peers in adj.items()
            }
            hops[area] = {
                peer: self.first_hop(area, peer)
                for peer in areas[area][lay.dut]
            }
        return {
            "dut": lay.dut, "backbone": BACKBONE, "areas": areas,
            "first_hops": hops, "prefixes": lay.prefixes,
            "ranges": lay.ranges,
            "summaries": [
                (abr, prefix, cost)
                for (abr, area, prefix), cost in lay.summaries.items()
                if area == BACKBONE and (abr, prefix) not in self.withdrawn
            ],
        }

    def fib_table(self) -> dict:
        """The settled FIB in the reference's form: ``{prefix: (metric
        of the RIB's active route, frozenset((ifname, address)))}`` over
        what the kernel holds."""
        active = self.rib.active_routes()
        return {
            prefix: (
                active[prefix].metric,
                frozenset((nh.ifname, nh.addr) for nh in nexthops),
            )
            for prefix, (nexthops, _proto) in self.kernel.fib.items()
        }
