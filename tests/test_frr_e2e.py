"""End-to-end IP fast reroute: OSPF computes backup tables, the RIB
flips to the precomputed repair on BFD-down / link-down, and normal
reconvergence later replaces the repair — plus the two r5 parity leaves
that ride this PR (RFC 6987 stub-router, mtu-ignore / transmit-delay).
"""

from ipaddress import IPv4Address as A
from ipaddress import IPv4Network as N

import pytest

from holo_tpu.frr.manager import FrrConfig
from holo_tpu.testing import no_implicit_transfers


@pytest.fixture(autouse=True)
def _transfer_sanitizer():
    """E2E repair paths run under jax.transfer_guard('disallow') too —
    a protocol-layer change that smuggles a device sync outside the
    sanctioned FRR/SPF boundaries must fail here, not on the chip."""
    with no_implicit_transfers():
        yield
from holo_tpu.protocols.ospf.instance import (
    IfConfig,
    IfUpMsg,
    InstanceConfig,
    OspfInstance,
)
from holo_tpu.protocols.ospf.interface import IfType
from holo_tpu.routing.rib import MockKernel, RibManager
from holo_tpu.utils.ibus import TOPIC_BFD_STATE, BfdStateUpd, Ibus
from holo_tpu.utils.netio import MockFabric
from holo_tpu.utils.runtime import EventLoop, VirtualClock
from holo_tpu.utils.southbound import Protocol

AREA0 = A("0.0.0.0")
DEST = N("10.0.23.0/30")  # the r2--r3 subnet, primary via r2 from r1


def triangle(frr_cfg):
    """r1--r2 (10), r2--r3 (10), r1--r3 (100): from r1 the r2--r3 subnet
    routes via r2; neighbor r3 is its loop-free alternate."""
    loop = EventLoop(clock=VirtualClock())
    fabric = MockFabric(loop)
    buses, kernels, ribs, routers = {}, {}, {}, {}
    for name, rid in [("r1", "1.1.1.1"), ("r2", "2.2.2.2"), ("r3", "3.3.3.3")]:
        bus = Ibus(loop)
        k = MockKernel()
        rib = RibManager(bus, k)
        rib.name = f"routing-{name}"
        loop.register(rib)
        inst = OspfInstance(
            name=name,
            config=InstanceConfig(
                router_id=A(rid), frr=frr_cfg if name == "r1" else None
            ),
            netio=fabric.sender_for(name),
        )
        loop.register(inst)
        inst.attach_ibus(bus, routing_actor=rib.name)
        buses[name], kernels[name], ribs[name], routers[name] = bus, k, rib, inst

    cfg = lambda c: IfConfig(if_type=IfType.POINT_TO_POINT, cost=c)
    r1, r2, r3 = routers["r1"], routers["r2"], routers["r3"]
    r1.add_interface("e0", cfg(10), N("10.0.12.0/30"), A("10.0.12.1"))
    r2.add_interface("e0", cfg(10), N("10.0.12.0/30"), A("10.0.12.2"))
    r2.add_interface("e1", cfg(10), N("10.0.23.0/30"), A("10.0.23.1"))
    r3.add_interface("e0", cfg(10), N("10.0.23.0/30"), A("10.0.23.2"))
    r1.add_interface("e1", cfg(100), N("10.0.13.0/30"), A("10.0.13.1"))
    r3.add_interface("e1", cfg(100), N("10.0.13.0/30"), A("10.0.13.2"))
    fabric.join("l12", "r1", "e0", A("10.0.12.1"))
    fabric.join("l12", "r2", "e0", A("10.0.12.2"))
    fabric.join("l23", "r2", "e1", A("10.0.23.1"))
    fabric.join("l23", "r3", "e0", A("10.0.23.2"))
    fabric.join("l13", "r1", "e1", A("10.0.13.1"))
    fabric.join("l13", "r3", "e1", A("10.0.13.2"))
    for r in routers.values():
        for area in r.areas.values():
            for ifname in area.interfaces:
                loop.send(r.name, IfUpMsg(ifname))
    loop.advance(90)
    return loop, fabric, buses, kernels, ribs, routers


def test_bfd_down_backup_flip_then_reconverge():
    """The tentpole moment: BFD-down flips the FIB to the precomputed
    backup in O(1) (no SPF), and flood/SPF reconvergence later replaces
    the repair with the real post-failure route."""
    loop, fabric, buses, kernels, ribs, routers = triangle(
        FrrConfig(enabled=True)
    )
    k1, rib1 = kernels["r1"], ribs["r1"]

    # Converged: primary via r2, and the backup via r3 rode the install.
    nhs, proto = k1.fib[DEST]
    assert proto == Protocol.OSPFV2
    assert {str(nh.addr) for nh in nhs} == {"10.0.12.2"}
    backups = k1.backups[DEST]
    [(primary, backup)] = backups.items()
    assert str(primary.addr) == "10.0.12.2" and primary.ifname == "e0"
    assert str(backup.addr) == "10.0.13.2" and backup.ifname == "e1"

    # BFD session to r2 drops: O(1) local repair, no SPF involved.
    spf_runs = routers["r1"].spf_run_count
    buses["r1"].publish(
        TOPIC_BFD_STATE, BfdStateUpd(key=("e0", A("10.0.12.2")), state="down")
    )
    loop.run_until_idle()
    nhs, _ = k1.fib[DEST]
    assert {str(nh.addr) for nh in nhs} == {"10.0.13.2"}, "flip to backup"
    assert DEST in rib1.repaired
    # The flip itself never waited for an SPF run.
    assert routers["r1"].spf_run_count == spf_runs

    # Reconvergence: the link actually dies, OSPF floods + reruns SPF,
    # and the republished route clears the repair flag.
    fabric.set_link_up("l12", False)
    loop.advance(60)  # dead interval fires, SPF reruns
    nhs, _ = k1.fib[DEST]
    assert {str(nh.addr) for nh in nhs} == {"10.0.13.2"}
    assert DEST not in rib1.repaired, "reconvergence replaced the repair"


def test_interface_down_triggers_local_repair():
    """Carrier loss (InterfaceUpd operative=False) is the second flip
    trigger: same precomputed backup, no BFD session required."""
    from holo_tpu.utils.ibus import TOPIC_INTERFACE_UPD
    from holo_tpu.utils.southbound import InterfaceUpdMsg

    loop, fabric, buses, kernels, ribs, _ = triangle(FrrConfig(enabled=True))
    k1 = kernels["r1"]
    buses["r1"].publish(
        TOPIC_INTERFACE_UPD,
        InterfaceUpdMsg(ifname="e0", ifindex=1, mtu=1500, operative=False),
    )
    loop.run_until_idle()
    nhs, _ = k1.fib[DEST]
    assert {str(nh.addr) for nh in nhs} == {"10.0.13.2"}
    assert DEST in ribs["r1"].repaired


def test_no_frr_config_no_backups_no_flip():
    """Without fast-reroute config the BFD event leaves the FIB alone
    (nothing precomputed to flip to — reconvergence is the only path)."""
    loop, fabric, buses, kernels, ribs, _ = triangle(None)
    k1 = kernels["r1"]
    assert DEST not in k1.backups
    buses["r1"].publish(
        TOPIC_BFD_STATE, BfdStateUpd(key=("e0", A("10.0.12.2")), state="down")
    )
    loop.run_until_idle()
    nhs, _ = k1.fib[DEST]
    assert {str(nh.addr) for nh in nhs} == {"10.0.12.2"}  # unchanged
    assert DEST not in ribs["r1"].repaired


def test_stub_router_max_metric():
    """RFC 6987: flipping stub-router on re-originates the router-LSA
    with MaxLinkMetric on transit links (stub links keep their cost), so
    neighbors route around us; flipping it off restores the metrics."""
    from holo_tpu.protocols.ospf.packet import (
        MAX_LINK_METRIC,
        LsaType,
        RouterLinkType,
    )

    loop, fabric, buses, kernels, ribs, routers = triangle(None)
    r2 = routers["r2"]
    # A prefix on r3 only: from r1 the cheap path transits r2
    # (10 + 10 + 10 = 30) vs the direct cost-100 link (110).
    far = N("192.168.3.0/24")
    routers["r3"].interface_address_add("e0", far)
    loop.advance(10)
    nhs, _ = kernels["r1"].fib[far]
    assert {str(nh.addr) for nh in nhs} == {"10.0.12.2"}

    r2.set_stub_router(True)
    loop.advance(10)

    def r2_links(viewer):
        area = viewer.areas[AREA0]
        for key, e in area.lsdb.entries.items():
            if key.type == LsaType.ROUTER and key.adv_rtr == A("2.2.2.2"):
                return e.lsa.body.links
        return []

    links = r2_links(routers["r1"])  # as seen by a NEIGHBOR's LSDB
    p2p = [l for l in links if l.link_type == RouterLinkType.POINT_TO_POINT]
    stub = [l for l in links if l.link_type == RouterLinkType.STUB_NETWORK]
    assert p2p and all(l.metric == MAX_LINK_METRIC for l in p2p)
    assert stub and all(l.metric < MAX_LINK_METRIC for l in stub)
    # Transit traffic now avoids r2: r1 reaches r3's prefix directly...
    nhs, _ = kernels["r1"].fib[far]
    assert {str(nh.addr) for nh in nhs} == {"10.0.13.2"}
    # ...while r2's OWN attached prefix stays reachable through r2
    # (stub links keep their real metric — the RFC 6987 point).
    nhs, _ = kernels["r1"].fib[DEST]
    assert {str(nh.addr) for nh in nhs} == {"10.0.12.2"}

    r2.set_stub_router(False)
    loop.advance(10)
    links = r2_links(routers["r1"])
    assert all(
        l.metric < MAX_LINK_METRIC
        for l in links
        if l.link_type == RouterLinkType.POINT_TO_POINT
    )
    nhs, _ = kernels["r1"].fib[far]
    assert {str(nh.addr) for nh in nhs} == {"10.0.12.2"}


def test_mtu_mismatch_blocks_adjacency_mtu_ignore_bypasses():
    """RFC 2328 §10.6: a larger peer MTU sticks the adjacency before
    Full; the mtu-ignore leaf waves the same DD through."""
    from holo_tpu.protocols.ospf.neighbor import NsmState

    def run(mtu_ignore):
        loop = EventLoop(clock=VirtualClock())
        fabric = MockFabric(loop)
        insts = {}
        for name, rid, mtu in [("a", "1.1.1.1", 1400), ("b", "2.2.2.2", 9000)]:
            inst = OspfInstance(
                name=name,
                config=InstanceConfig(router_id=A(rid)),
                netio=fabric.sender_for(name),
            )
            loop.register(inst)
            insts[name] = inst
        cfg_a = IfConfig(
            if_type=IfType.POINT_TO_POINT, mtu=1400, mtu_ignore=mtu_ignore
        )
        cfg_b = IfConfig(if_type=IfType.POINT_TO_POINT, mtu=9000)
        insts["a"].add_interface("e0", cfg_a, N("10.0.0.0/30"), A("10.0.0.1"))
        insts["b"].add_interface("e0", cfg_b, N("10.0.0.0/30"), A("10.0.0.2"))
        fabric.join("l", "a", "e0", A("10.0.0.1"))
        fabric.join("l", "b", "e0", A("10.0.0.2"))
        for inst in insts.values():
            loop.send(inst.name, IfUpMsg("e0"))
        loop.advance(60)
        area = insts["a"].areas[AREA0]
        return [
            n.state
            for i in area.interfaces.values()
            for n in i.neighbors.values()
        ]

    states = run(mtu_ignore=False)
    assert states and all(s < NsmState.FULL for s in states), (
        "MTU mismatch must stall the adjacency"
    )
    states = run(mtu_ignore=True)
    assert states == [NsmState.FULL], "mtu-ignore must bypass the check"


def test_transmit_delay_increments_lsa_age():
    """§13.3: every hop adds the outgoing interface's InfTransDelay to
    the LSA age, so a large configured delay is visible in the
    receiver's LSDB immediately after flooding."""
    loop = EventLoop(clock=VirtualClock())
    fabric = MockFabric(loop)
    insts = {}
    for name, rid, delay in [("a", "1.1.1.1", 120), ("b", "2.2.2.2", 1)]:
        inst = OspfInstance(
            name=name,
            config=InstanceConfig(router_id=A(rid)),
            netio=fabric.sender_for(name),
        )
        loop.register(inst)
        cfg = IfConfig(if_type=IfType.POINT_TO_POINT, transmit_delay=delay)
        inst.add_interface("e0", cfg, N("10.0.0.0/30"), A(f"10.0.0.{1 if name == 'a' else 2}"))
        fabric.join("l", name, "e0", A(f"10.0.0.{1 if name == 'a' else 2}"))
        insts[name] = inst
    for inst in insts.values():
        loop.send(inst.name, IfUpMsg("e0"))
    loop.advance(40)
    from holo_tpu.protocols.ospf.packet import LsaType

    # b's copy of a's router-LSA aged >= a's transmit-delay on arrival;
    # a's own copy of its LSA only aged by wall clock (< 40s here).
    now = loop.clock.now()
    for viewer, floor, ceil in [("b", 120, None), ("a", 0, 119)]:
        area = insts[viewer].areas[AREA0]
        ages = [
            e.current_age(now)
            for k, e in area.lsdb.entries.items()
            if k.type == LsaType.ROUTER and k.adv_rtr == A("1.1.1.1")
        ]
        assert ages, f"router-LSA missing in {viewer}"
        assert all(a >= floor for a in ages)
        if ceil is not None:
            assert all(a <= ceil for a in ages)


def test_repair_event_tracking_unit():
    """The RIB repair model under multiple failures and staged recovery:
    events accumulate per prefix, a second failure re-repairs, recovery
    unwinds one event at a time, duplicate events are idempotent, and an
    unrelated protocol's add/del never reverts an active repair."""
    from ipaddress import ip_network

    from holo_tpu.utils.southbound import Nexthop, RouteKeyMsg, RouteMsg

    def mk():
        loop = EventLoop(clock=VirtualClock())
        k = MockKernel()
        rib = RibManager(Ibus(loop), k)
        loop.register(rib)
        rib.attach(loop)
        return rib, k

    pfx = ip_network("10.9.9.0/24")
    nh_a = Nexthop(addr="192.0.2.1", ifname="eth0")
    nh_b = Nexthop(addr="192.0.2.2", ifname="eth1")
    bk_a = Nexthop(addr="198.51.100.1", ifname="eth2")
    bk_b = Nexthop(addr="198.51.100.2", ifname="eth3")

    rib, k = mk()
    rib.route_add(
        RouteMsg(
            protocol=Protocol.OSPFV2, prefix=pfx, distance=110, metric=10,
            nexthops=frozenset({nh_a, nh_b}),
            backups={nh_a: bk_a, nh_b: bk_b},
        )
    )
    # double failure: the second event re-repairs the repaired prefix.
    assert rib.local_repair("eth0") == 1
    assert k.fib[pfx][0] == frozenset({nh_b, bk_a})
    assert rib.local_repair("eth0") == 0, "duplicate event must be a no-op"
    assert rib.local_repair("eth1") == 1
    assert k.fib[pfx][0] == frozenset({bk_a, bk_b})
    # an unrelated (worse) protocol add/del must not revert the repair.
    other = Nexthop(addr="203.0.113.3", ifname="eth4")
    rib.route_add(
        RouteMsg(protocol=Protocol.RIPV2, prefix=pfx, distance=120,
                 metric=5, nexthops=frozenset({other}))
    )
    assert pfx in rib.repaired and k.fib[pfx][0] == frozenset({bk_a, bk_b})
    rib.route_del(RouteKeyMsg(Protocol.RIPV2, pfx))
    assert pfx in rib.repaired and k.fib[pfx][0] == frozenset({bk_a, bk_b})
    # staged recovery: one event unwinds, the other stays repaired.
    assert rib.local_restore("eth1") == 1
    assert k.fib[pfx][0] == frozenset({nh_b, bk_a}) and pfx in rib.repaired
    assert rib.local_restore("eth0") == 1
    assert k.fib[pfx][0] == frozenset({nh_a, nh_b})
    assert pfx not in rib.repaired

    # a withdrawn route takes its repair along: no resurrection later.
    rib, k = mk()
    rib.route_add(
        RouteMsg(protocol=Protocol.OSPFV2, prefix=pfx, distance=110,
                 metric=10, nexthops=frozenset({nh_a}), backups={nh_a: bk_a})
    )
    assert rib.local_repair("eth0") == 1
    rib.route_add(
        RouteMsg(protocol=Protocol.DIRECT, prefix=pfx, distance=0,
                 metric=0, nexthops=frozenset())
    )
    assert pfx not in rib.repaired
    assert rib.local_restore("eth0") == 0 and pfx not in k.fib
