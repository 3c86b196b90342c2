"""The benchmark's copy of the convergence-storm network
(``holo_tpu/spf/synth_storm.py`` ``StormNet``, a sound generator: see
PERF.md's inventory): one device-under-test ``OspfInstance`` holding
Router-LSAs for ``n_routers`` synthetic routers, with everything around
it real — LSA installs run through ``_install_and_flood`` (RFC 8405
SPF-delay FSM, trigger classification, the convergence tracker's origin
stamps), routes flow over the ibus into a real ``RibManager`` and a
``MockKernel`` FIB, BFD and carrier events drive its local repair.

Kept here so that the traffic a cell offers cannot change under a PR
that claims a gain.  The dual-gateway construction (root -> g0/g1 ->
shared hubs -> the rest) gives every destination behind the hubs 2-way
ECMP, so bfd/carrier repairs always have survivors to flip to.  The
loop's clock is virtual: the RFC 8405 holds and the retransmit penalty
of a lost LSA pass in no wall time.
"""

from __future__ import annotations

from dataclasses import dataclass
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
    RouterLink,
    RouterLinkType,
)
from holo_tpu.routing.rib import MockKernel, RibManager
from holo_tpu.telemetry import convergence
from holo_tpu.utils.ibus import (
    TOPIC_BFD_STATE,
    TOPIC_INTERFACE_UPD,
    BfdStateUpd,
    Ibus,
)
from holo_tpu.utils.netio import NetIo
from holo_tpu.utils.runtime import Actor, EventLoop, VirtualClock
from holo_tpu.utils.southbound import InterfaceUpdMsg

class _DiscardIo(NetIo):
    """Flood sink: the synthetic neighbors have no receive side."""

    def send(self, ifname, src, dst, data) -> None:
        pass


def _rid(i: int) -> IPv4Address:
    """Synthetic router id for index ``i`` (root is index 0)."""
    return IPv4Address((10 << 24) | (i + 1))


def _p2p(nbr: IPv4Address, data: IPv4Address, metric: int) -> RouterLink:
    return RouterLink(RouterLinkType.POINT_TO_POINT, nbr, data, metric)


def _stub(prefix: IPv4Network, metric: int = 1) -> RouterLink:
    return RouterLink(
        RouterLinkType.STUB_NETWORK,
        prefix.network_address,
        prefix.netmask,
        metric,
    )


@dataclass
class _ApplyLsas:
    """Storm-actor message: install LSAs under a causal context (the
    ``event_id`` field is what the EventLoop delivery hook activates —
    lost arrivals redeliver this same message after ``rxmt_delay``)."""

    lsas: list
    event_id: tuple | None = None


class StormNet:
    """One DUT instance + RIB over a virtual-clock loop, plus the
    python-side link model the storm mutates."""

    DUT = "storm-dut"
    ACTOR = "storm-driver"

    def __init__(
        self,
        n_routers: int,
        seed: int,
        spf_backend,
        prefix_every: int,
        hubs: int,
        extra_link_share: float,
        max_degree: int,
        spf_delay: dict,
        rxmt_delay: float,
    ):
        """``spf_delay``: the RFC 8405 timers of the deployment (fields
        of ``SpfTimers``).  ``rxmt_delay``: virtual seconds after which
        a "lost" LSA arrival is retransmitted."""
        if n_routers < hubs + 8:
            raise ValueError("need root + 2 gateways + hubs + some leaves")
        self.n_routers = n_routers
        self.loop = EventLoop(clock=VirtualClock())
        self.bus = Ibus(self.loop)
        self.kernel = MockKernel()
        self.rib = RibManager(self.bus, self.kernel)
        self.rib.name = "routing"
        self.loop.register(self.rib)
        cfg = InstanceConfig(
            router_id=_rid(0), spf=SpfTimers(**spf_delay)
        )
        self.rxmt_delay = float(rxmt_delay)
        self.inst = OspfInstance(
            name=self.DUT,
            config=cfg,
            netio=_DiscardIo(),
            spf_backend=spf_backend,
        )
        self.loop.register(self.inst)
        self.inst.attach_ibus(self.bus, routing_actor="routing")
        self.loop.register(_StormActor(self), name=self.ACTOR)

        rng = np.random.default_rng(seed)
        # Link model: adjacency dict rid-index -> {peer-index: metric}.
        # Indices: 0 root, 1..2 gateways, 3..3+hubs-1 hubs, rest leaves.
        self.adj: dict[int, dict[int, int]] = {i: {} for i in range(n_routers)}
        self.g0, self.g1 = 1, 2
        self.hub0 = 3
        self.n_hubs = hubs

        def link(a: int, b: int, cost: int) -> None:
            self.adj[a][b] = cost
            self.adj[b][a] = cost

        link(0, self.g0, 1)
        link(0, self.g1, 1)
        for j in range(hubs):
            h = self.hub0 + j
            link(self.g0, h, 1)
            link(self.g1, h, 1)
            if j:
                link(h - 1, h, 1)
        first_leaf = self.hub0 + hubs
        leaves = np.arange(first_leaf, n_routers)
        # Unlike the program's generator, the shape is the same for
        # every seed: a fixed number of second links and a degree cap
        # (a port count) hold the edge count and the ELL width, and
        # with them every compiled program's shape.
        second = set(rng.choice(
            leaves, size=round(extra_link_share * leaves.size), replace=False
        ).tolist())

        def pick(i: int) -> int:
            """A hub or an earlier leaf with a free port, not yet a
            neighbour of ``i``."""
            while True:
                peer = int(rng.integers(self.hub0, i))
                if len(self.adj[peer]) < max_degree and peer not in self.adj[i]:
                    return peer

        for i in leaves.tolist():
            # Spanning attachment, plus a sprinkling of second links
            # for path diversity.
            link(i, pick(i), int(rng.integers(1, 5)))
            if i in second:
                link(i, pick(i), int(rng.integers(1, 8)))
        # Flappable edges: leaf/hub-side only — never the root/gateway
        # structure the ECMP construction depends on.
        self.flappable = sorted(
            (a, b)
            for a, nbrs in self.adj.items()
            for b in nbrs
            if a < b and a >= self.hub0
        )
        self.down: set[tuple[int, int]] = set()
        # Per-prefix stub owners (every prefix_every-th leaf).
        self.stub_owners = set(range(first_leaf, n_routers, prefix_every))
        self._seq: dict[int, int] = {}

        # DUT interfaces + FULL neighbors toward the gateways (next-hop
        # resolution; the ISM/NSM machinery is bypassed exactly like
        # synth_proto does for OSPFv3).
        self.g0_addr = IPv4Address("10.255.0.2")
        self.g1_addr = IPv4Address("10.255.1.2")
        for ifname, net, our, nbr_idx, nbr_addr in (
            ("e0", "10.255.0.0/30", "10.255.0.1", self.g0, self.g0_addr),
            ("e1", "10.255.1.0/30", "10.255.1.1", self.g1, self.g1_addr),
        ):
            iface = self.inst.add_interface(
                ifname,
                IfConfig(if_type=IfType.POINT_TO_POINT, cost=1),
                IPv4Network(net),
                IPv4Address(our),
            )
            iface.state = IsmState.POINT_TO_POINT
            iface.neighbors[_rid(nbr_idx)] = Neighbor(
                router_id=_rid(nbr_idx), src=nbr_addr, state=NsmState.FULL
            )
        self.area = self.inst.areas[next(iter(self.inst.areas))]
        now = self.loop.clock.now()
        for i in range(n_routers):
            self.area.lsdb.install(self._router_lsa(i), now)
        # First full SPF + RIB sync (set-up, outside the window).
        self.inst._schedule_spf()
        self.loop.advance(30.0)

    # -- LSA construction

    def _router_lsa(self, i: int) -> Lsa:
        seq = self._seq.get(i, 0) + 1
        self._seq[i] = seq
        links: list[RouterLink] = []
        if i == 0:
            links.append(
                _p2p(_rid(self.g0), IPv4Address("10.255.0.1"),
                     self.adj[0][self.g0])
            )
            links.append(
                _p2p(_rid(self.g1), IPv4Address("10.255.1.1"),
                     self.adj[0][self.g1])
            )
        else:
            for peer, metric in sorted(self.adj[i].items()):
                if (min(i, peer), max(i, peer)) in self.down:
                    continue
                links.append(_p2p(_rid(peer), IPv4Address(0), metric))
        if i and i in self.stub_owners:
            links.append(
                _stub(IPv4Network(((172 << 24) | (i << 8), 24)), 1)
            )
        lsa = Lsa(
            age=1,
            options=Options(0x02),
            type=LsaType.ROUTER,
            lsid=_rid(i),
            adv_rtr=_rid(i),
            seq_no=seq,
            body=LsaRouter(links=links),
        )
        # §13.2 change detection compares the encoded body bytes —
        # synthetic LSAs must carry a real wire image.
        lsa.encode()
        return lsa

    # -- storm event primitives (called by drivers/storm.py)

    def _deliver(self, lsas: list, eid, delay: float = 0.0) -> None:
        msg = _ApplyLsas(lsas, (eid,) if eid is not None else None)
        if delay > 0.0:
            t = self.loop.timer(self.ACTOR, lambda m=msg: m)
            t.start(delay)
        else:
            self.loop.send(self.ACTOR, msg)

    def apply_lsas(self, lsas: list) -> None:
        """Runs inside the storm actor (causal context already active
        via the delivery hook)."""
        for lsa in lsas:
            self.inst._install_and_flood(self.area, lsa)
        # The synthetic neighbors ack instantly: drop retransmit state
        # so the storm's timer load stays bounded.
        for area in self.inst.areas.values():
            for iface in area.interfaces.values():
                for nbr in iface.neighbors.values():
                    nbr.ls_rxmt.clear()

    def flap(self, edge: tuple[int, int], lost: bool) -> int | None:
        """Toggle ``edge``; both endpoint LSAs (re)install as one causal
        LSA-arrival event.  ``lost`` defers the arrival by ``rxmt_delay``."""
        if edge in self.down:
            self.down.discard(edge)
            state = "up"
        else:
            self.down.add(edge)
            state = "down"
        eid = convergence.begin(
            convergence.TRIGGER_LSA, edge=f"{edge[0]}-{edge[1]}", state=state
        )
        a, b = edge
        self._deliver(
            [self._router_lsa(a), self._router_lsa(b)],
            eid,
            delay=self.rxmt_delay if lost else 0.0,
        )
        return eid

    def bfd(self, gateway: int, state: str) -> None:
        addr = self.g0_addr if gateway == self.g0 else self.g1_addr
        ifname = "e0" if gateway == self.g0 else "e1"
        eid = convergence.begin(
            convergence.TRIGGER_BFD, state=state, ifname=ifname
        )
        with convergence.activation(eid):
            self.bus.publish(
                TOPIC_BFD_STATE, BfdStateUpd((ifname, addr), state)
            )

    def carrier(self, ifname: str, operative: bool) -> None:
        eid = convergence.begin(
            convergence.TRIGGER_CARRIER, ifname=ifname, operative=operative
        )
        with convergence.activation(eid):
            self.bus.publish(
                TOPIC_INTERFACE_UPD,
                InterfaceUpdMsg(ifname=ifname, ifindex=0,
                                operative=operative),
            )

    def ifconfig_metric(self) -> None:
        """Config event on the DUT: the e0 gateway link metric flips
        between 1 and 2 — a full-SPF-forcing change with real route
        movement (ECMP collapses to g1 and back)."""
        cur = self.adj[0][self.g0]
        self.adj[0][self.g0] = 2 if cur == 1 else 1
        self.adj[self.g0][0] = self.adj[0][self.g0]
        eid = convergence.begin(convergence.TRIGGER_IFCONFIG, ifname="e0")
        self._deliver([self._router_lsa(0)], eid)


class _StormActor(Actor):
    """Applies deferred/immediate LSA batches on the loop (the delivery
    hook re-activates each message's causal event context)."""

    def __init__(self, net: StormNet):
        self.net = net

    def handle(self, msg) -> None:
        if isinstance(msg, _ApplyLsas):
            self.net.apply_lsas(msg.lsas)


