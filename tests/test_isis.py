"""IS-IS: PDU codecs, 3-way adjacency, LSP flooding/sync, SPF routes."""

from ipaddress import IPv4Address as A
from ipaddress import IPv4Network as N

from holo_tpu.protocols.isis.instance import (
    AdjacencyState,
    IsisIfConfig,
    IsisIfUpMsg,
    IsisInstance,
)
from holo_tpu.protocols.isis.packet import (
    ExtIpReach,
    ExtIsReach,
    HelloP2p,
    Lsp,
    LspId,
    P2pAdjState,
    AdjState3Way,
    Snp,
    decode_pdu,
)
from holo_tpu.utils.netio import MockFabric
from holo_tpu.utils.runtime import EventLoop, VirtualClock


def sysid(n: int) -> bytes:
    return bytes((0, 0, 0, 0, 0, n))


def test_hello_roundtrip():
    h = HelloP2p(
        circuit_type=3,
        sysid=sysid(1),
        hold_time=9,
        local_circuit_id=1,
        tlvs={
            "area_addresses": [b"\x49\x00\x01"],
            "protocols_supported": [0xCC],
            "ip_addresses": [A("10.0.0.1")],
            "p2p_adj": P2pAdjState(AdjState3Way.INITIALIZING, 1, sysid(2), 1),
        },
    )
    t, out = decode_pdu(h.encode())
    assert out.sysid == sysid(1) and out.hold_time == 9
    assert out.tlvs["p2p_adj"].neighbor_sysid == sysid(2)
    assert out.tlvs["ip_addresses"] == [A("10.0.0.1")]


def test_lsp_roundtrip_and_checksum():
    lsp = Lsp(
        2, 1200, LspId(sysid(1)), 5,
        tlvs={
            "area_addresses": [b"\x49\x00\x01"],
            "ext_is_reach": [ExtIsReach(sysid(2) + b"\x00", 10)],
            "ext_ip_reach": [ExtIpReach(N("10.0.0.0/24"), 10)],
        },
    )
    raw = lsp.encode()
    t, out = decode_pdu(raw)
    assert out.lsp_id == LspId(sysid(1)) and out.seqno == 5
    assert out.tlvs["ext_is_reach"] == [ExtIsReach(sysid(2) + b"\x00", 10)]
    assert out.tlvs["ext_ip_reach"] == [ExtIpReach(N("10.0.0.0/24"), 10)]
    # corruption must be detected
    bad = bytearray(raw)
    bad[30] ^= 0xFF
    import pytest
    from holo_tpu.utils.bytesbuf import DecodeError

    with pytest.raises(DecodeError):
        decode_pdu(bytes(bad))


def test_snp_roundtrip():
    s = Snp(2, True, sysid(3), [(1200, LspId(sysid(1)), 7, 0xBEEF)])
    t, out = decode_pdu(s.encode())
    assert out.complete and out.entries == [(1200, LspId(sysid(1)), 7, 0xBEEF)]


def mk_net(n_routers=3):
    loop = EventLoop(clock=VirtualClock())
    fabric = MockFabric(loop)
    routers = []
    for i in range(n_routers):
        r = IsisInstance(f"is{i}", sysid(i + 1), netio=fabric.sender_for(f"is{i}"))
        loop.register(r)
        routers.append(r)
    return loop, fabric, routers


def link(loop, fabric, r1, i1, a1, r2, i2, a2, net, metric=10):
    cfg = IsisIfConfig(metric=metric)
    r1.add_interface(i1, cfg, A(a1), N(net))
    r2.add_interface(i2, cfg, A(a2), N(net))
    fabric.join(f"{r1.name}-{r2.name}", r1.name, i1, A(a1))
    fabric.join(f"{r1.name}-{r2.name}", r2.name, i2, A(a2))


def test_adjacency_and_routes_chain():
    loop, fabric, (r1, r2, r3) = mk_net(3)
    link(loop, fabric, r1, "e0", "10.0.12.1", r2, "e0", "10.0.12.2", "10.0.12.0/30", 10)
    link(loop, fabric, r2, "e1", "10.0.23.1", r3, "e0", "10.0.23.2", "10.0.23.0/30", 5)
    for r in (r1, r2, r3):
        for ifname in r.interfaces:
            loop.send(r.name, IsisIfUpMsg(ifname))
    loop.advance(30)

    assert r1.interfaces["e0"].adj.state == AdjacencyState.UP
    assert r2.interfaces["e0"].adj.state == AdjacencyState.UP
    assert r2.interfaces["e1"].adj.state == AdjacencyState.UP
    # LSDBs synchronized.
    assert set(r1.lsdb) == set(r2.lsdb) == set(r3.lsdb)
    # r1 routes to the far subnet through r2.
    route = r1.routes.get(N("10.0.23.0/30"))
    assert route is not None
    dist, nhs = route
    assert dist == 10 + 5
    assert {(ifname, str(addr)) for ifname, addr in nhs} == {("e0", "10.0.12.2")}


def test_link_failure_reroute_square():
    loop, fabric, (r1, r2, r3) = mk_net(3)
    # triangle: r1-r2 (1), r2-r3 (1), r1-r3 (10)
    link(loop, fabric, r1, "e0", "10.0.12.1", r2, "e0", "10.0.12.2", "10.0.12.0/30", 1)
    link(loop, fabric, r2, "e1", "10.0.23.1", r3, "e0", "10.0.23.2", "10.0.23.0/30", 1)
    link(loop, fabric, r1, "e1", "10.0.13.1", r3, "e1", "10.0.13.2", "10.0.13.0/30", 10)
    for r in (r1, r2, r3):
        for ifname in r.interfaces:
            loop.send(r.name, IsisIfUpMsg(ifname))
    loop.advance(30)
    dist, nhs = r1.routes[N("10.0.23.0/30")]
    assert dist == 2 and {ifn for ifn, _ in nhs} == {"e0"}

    fabric.set_link_up("is0-is1", False)
    loop.advance(30)  # hold time 9s -> adj down -> re-originate -> SPF
    route = r1.routes.get(N("10.0.23.0/30"))
    assert route is not None
    dist, nhs = route
    assert {ifn for ifn, _ in nhs} == {"e1"}
    assert dist == 10 + 1


def test_lan_dis_election_and_pseudonode():
    """Three routers on one LAN: DIS elected, pseudonode LSP, routes."""
    loop = EventLoop(clock=VirtualClock())
    fabric = MockFabric(loop)
    routers = []
    for i in range(3):
        r = IsisInstance(f"is{i}", sysid(i + 1),
                         netio=fabric.sender_for(f"is{i}"))
        loop.register(r)
        routers.append(r)
    from holo_tpu.protocols.isis.instance import IsisIfConfig

    for i, r in enumerate(routers):
        cfg = IsisIfConfig(metric=10, circuit_type="broadcast",
                           priority=64 + (10 if i == 2 else 0))
        r.add_interface("e0", cfg, A(f"10.0.0.{i + 1}"), N("10.0.0.0/24"))
        fabric.join("lan", r.name, "e0", A(f"10.0.0.{i + 1}"))
    # Leaf prefix on r0 via a p2p stub iface (advertised in its LSP).
    routers[0].add_interface(
        "stub", IsisIfConfig(metric=5), A("192.168.9.1"), N("192.168.9.0/24")
    )
    for r in routers:
        loop.send(r.name, IsisIfUpMsg("e0"))
    loop.advance(60)

    # Highest priority (r2) is DIS; everyone agrees on the LAN ID.
    dis_id = sysid(3) + bytes((routers[2].interfaces["e0"].circuit_id,))
    for r in routers:
        assert r.interfaces["e0"].dis_lan_id == dis_id, r.name
    # Pseudonode LSP exists and lists all three members.
    from holo_tpu.protocols.isis.packet import LspId

    pn = LspId(sysid(3), pseudonode=routers[2].interfaces["e0"].circuit_id)
    for r in routers:
        assert pn in r.lsdb, f"{r.name} missing pseudonode LSP"
    members = {x.neighbor[:6] for x in routers[0].lsdb[pn].lsp.tlvs["ext_is_reach"]}
    assert members == {sysid(1), sysid(2), sysid(3)}
    # r2 and r1 route to r0's stub prefix across the LAN.
    for r in routers[1:]:
        route = r.routes.get(N("192.168.9.0/24"))
        assert route is not None, r.name
        dist, nhs = route
        assert dist == 10 + 5
        assert {str(a) for _, a in nhs} == {"10.0.0.1"}


def test_lan_dis_failover():
    loop = EventLoop(clock=VirtualClock())
    fabric = MockFabric(loop)
    routers = []
    from holo_tpu.protocols.isis.instance import IsisIfConfig

    for i in range(3):
        r = IsisInstance(f"is{i}", sysid(i + 1),
                         netio=fabric.sender_for(f"is{i}"))
        loop.register(r)
        cfg = IsisIfConfig(metric=10, circuit_type="broadcast")
        r.add_interface("e0", cfg, A(f"10.0.0.{i + 1}"), N("10.0.0.0/24"))
        fabric.join("lan", r.name, "e0", A(f"10.0.0.{i + 1}"))
        routers.append(r)
    for r in routers:
        loop.send(r.name, IsisIfUpMsg("e0"))
    loop.advance(60)
    # Equal priority: highest sysid (r2) is DIS.
    assert routers[0].interfaces["e0"].dis_lan_id[:6] == sysid(3)
    # Kill the DIS: hold time expires, a new DIS takes over, old
    # pseudonode is no longer used for routing.
    loop.unregister("is2")
    loop.advance(60)
    assert routers[0].interfaces["e0"].dis_lan_id[:6] == sysid(2)
    assert routers[0].routes  # still have LAN routes via new pseudonode


def test_flooding_reduction_suppresses_redundant_floods():
    """Full-mesh triangle with flooding reduction: LSDBs still converge
    while redundant LSP transmissions drop measurably."""

    def build(reduction: bool):
        loop = EventLoop(clock=VirtualClock())
        fabric = MockFabric(loop)
        routers = []
        for i in range(3):
            r = IsisInstance(f"fr{i}", sysid(i + 1),
                             netio=fabric.sender_for(f"fr{i}"))
            r.flooding_reduction = reduction
            loop.register(r)
            routers.append(r)
        pairs = [(0, 1), (1, 2), (0, 2)]
        for a, b in pairs:
            octet = 10 * a + b + 1
            net = f"10.{octet}.0.0/30"
            link(loop, fabric, routers[a], f"e{a}{b}", f"10.{octet}.0.1",
                 routers[b], f"e{b}{a}", f"10.{octet}.0.2", net, 10)
        for r in routers:
            for ifname in r.interfaces:
                loop.send(r.name, IsisIfUpMsg(ifname))
        loop.advance(40)
        # topology change: metric bump re-originates and floods the mesh
        routers[0].interfaces["e01"].config.metric = 11
        routers[0]._originate_lsp()
        fabric.tx_log.clear()
        loop.advance(30)
        lsp_tx = 0
        from holo_tpu.protocols.isis.packet import PduType

        for _actor, _ifn, _dst, data in fabric.tx_log:
            if len(data) > 4 and data[4] in (
                int(PduType.LSP_L1), int(PduType.LSP_L2)
            ):
                lsp_tx += 1
        images = [sorted((lid.encode(), e.lsp.seqno) for lid, e in r.lsdb.items())
                  for r in routers]
        return lsp_tx, images

    tx_full, images_full = build(reduction=False)
    tx_red, images_red = build(reduction=True)
    assert images_red[0] == images_red[1] == images_red[2], (
        "LSDBs diverged under flooding reduction"
    )
    assert tx_red < tx_full, (tx_red, tx_full)


def test_flooding_reduction_leaf_delivery_soundness():
    """The soundness trap: X connects leaf W and triangle peers P, Q.
    W's LSPs must reach P and Q even with reduction enabled everywhere."""
    loop = EventLoop(clock=VirtualClock())
    fabric = MockFabric(loop)
    names = ["X", "P", "Q", "W"]
    routers = {}
    for i, nm in enumerate(names):
        r = IsisInstance(nm, sysid(i + 1), netio=fabric.sender_for(nm))
        r.flooding_reduction = True
        loop.register(r)
        routers[nm] = r
    X, P, Q, W = (routers[n] for n in names)
    link(loop, fabric, X, "xp", "10.1.0.1", P, "px", "10.1.0.2", "10.1.0.0/30", 10)
    link(loop, fabric, X, "xq", "10.2.0.1", Q, "qx", "10.2.0.2", "10.2.0.0/30", 10)
    link(loop, fabric, P, "pq", "10.3.0.1", Q, "qp", "10.3.0.2", "10.3.0.0/30", 10)
    link(loop, fabric, X, "xw", "10.4.0.1", W, "wx", "10.4.0.2", "10.4.0.0/30", 10)
    for r in routers.values():
        for ifname in r.interfaces:
            loop.send(r.name, IsisIfUpMsg(ifname))
    loop.advance(60)
    # W's LSP (and the whole LSDB) must be identical everywhere.
    images = {
        nm: sorted((lid.encode(), e.lsp.seqno) for lid, e in r.lsdb.items())
        for nm, r in routers.items()
    }
    assert images["P"] == images["W"] == images["Q"] == images["X"]
    # And W's prefix is routable from P and Q.
    for nm in ("P", "Q"):
        assert N("10.4.0.0/30") in dict(routers[nm].routes)


def test_lsp_retransmission_on_loss():
    loop, fabric, (r1, r2) = mk_net(2)
    link(loop, fabric, r1, "e0", "10.0.12.1", r2, "e0", "10.0.12.2", "10.0.12.0/30")
    for r in (r1, r2):
        loop.send(r.name, IsisIfUpMsg("e0"))
    loop.advance(10)
    assert set(r1.lsdb) == set(r2.lsdb)
    # Drop the next LSP flood once; retransmission must recover it.
    dropped = []

    def drop_one_lsp(linkname, dst, data):
        if data[4] in (18, 20) and not dropped:  # LSP PDU type
            dropped.append(True)
            return True
        return False

    fabric.add_drop_rule(drop_one_lsp)
    # Force a new LSP from r1 (metric change -> re-originate).
    r1.interfaces["e0"].config.metric = 99
    r1._originate_lsp()
    loop.advance(20)  # > retransmit interval
    assert dropped, "drop rule never triggered"
    e1 = r1.lsdb[list(r1.lsdb)[0]]
    lid = LspId(sysid(1))
    assert r2.lsdb[lid].lsp.seqno == r1.lsdb[lid].lsp.seqno


def test_overload_reachable_but_no_transit():
    """ISO 10589 §7.2.8.1 (reference spf.rs:563-574): an overloaded
    router's own prefixes still install, but nothing routes THROUGH it."""
    from holo_tpu.protocols.isis.instance import Adjacency, LspEntry

    loop = EventLoop(clock=VirtualClock())
    inst = IsisInstance("a", sysid(1))
    loop.register(inst)
    inst.add_interface("e0", IsisIfConfig(metric=10),
                       A("10.0.12.1"), N("10.0.12.0/24"))
    inst.interfaces["e0"].adj = Adjacency(
        sysid=sysid(2), state=AdjacencyState.UP, addr=A("10.0.12.2")
    )

    def mk(owner, nbrs, prefix, flags=0x03):
        return Lsp(
            2, 1200, LspId(sysid(owner)), 1, flags,
            tlvs={
                "ext_is_reach": [ExtIsReach(sysid(x) + b"\x00", 10)
                                 for x in nbrs],
                "ext_ip_reach": [ExtIpReach(N(prefix), 0)],
            },
        )

    for lsp in (
        mk(1, [2], "1.1.1.1/32"),
        mk(2, [1, 3], "2.2.2.2/32", flags=0x03 | 0x04),  # overloaded
        mk(3, [2], "3.3.3.3/32"),
    ):
        lsp.encode()
        inst.lsdb[lsp.lsp_id] = LspEntry(lsp, 0.0)
    inst.run_spf()
    # B itself is reachable (its loopback installs)…
    assert inst.routes[N("2.2.2.2/32")][0] == 10
    # …but C, only reachable THROUGH overloaded B, is not.
    assert N("3.3.3.3/32") not in inst.routes


def test_ipv6_reach_tlv_chunking_roundtrip():
    """15 full-length /128 entries exceed one TLV body (255B): the
    encoder must split them and the decoder must recover all of them."""
    from ipaddress import IPv6Network

    prefixes = [IPv6Network(f"2001:db8::{i:x}/128") for i in range(1, 16)]
    lsp = Lsp(
        2, 1200, LspId(sysid(1)), 1,
        tlvs={"ipv6_reach": [ExtIpReach(p, i)
                             for i, p in enumerate(prefixes)]},
    )
    raw = lsp.encode()
    t, out = decode_pdu(raw)
    assert [r.prefix for r in out.tlvs["ipv6_reach"]] == prefixes
    assert [r.metric for r in out.tlvs["ipv6_reach"]] == list(range(15))


def test_live_ipv6_origination_and_hostname():
    """Two live routers: IPv6 reachability and hostnames must flow from
    ORIGINATION (TLV 232/236/137), not just be decodable (RFC 5308/5301)."""
    from ipaddress import IPv6Address, IPv6Network

    loop = EventLoop(clock=VirtualClock())
    fabric = MockFabric(loop)
    from holo_tpu.protocols.isis.instance import IsisIfConfig

    routers = []
    for i in (1, 2):
        r = IsisInstance(f"is{i}", sysid(i),
                         netio=fabric.sender_for(f"is{i}"))
        loop.register(r)
        r.add_interface(
            "e0", IsisIfConfig(metric=10),
            A(f"10.0.0.{i}"), N("10.0.0.0/24"),
            addr6=IPv6Address(f"fe80::{i}"),
            prefix6=IPv6Network(f"2001:db8:{i}::/64"),
        )
        fabric.join("wire", r.name, "e0", A(f"10.0.0.{i}"))
        routers.append(r)
    for r in routers:
        loop.send(r.name, IsisIfUpMsg("e0"))
    loop.advance(60)
    r1, r2 = routers
    # v6 route with the neighbor's link-local as next hop.
    route = r1.routes.get(IPv6Network("2001:db8:2::/64"))
    assert route is not None, "no v6 route from live origination"
    dist, nhs = route
    assert dist == 20  # dist(r2)=10 + advertised prefix metric 10
    assert {str(a) for _, a in nhs} == {"fe80::2"}
    # Hostname learned from the neighbor's LSP.
    assert r1.hostnames.get(sysid(2)) == "is2"
    assert r2.hostnames.get(sysid(1)) == "is1"
    # protocols_supported advertises IPv6 (NLPID 0x8E).
    own = r2.lsdb[LspId(sysid(1))].lsp
    assert 0x8E in own.tlvs["protocols_supported"]


def test_isis_authentication():
    """RFC 5304/5310: authenticated adjacency + LSDB sync; key mismatch
    and tampering drop PDUs."""
    import pytest

    from holo_tpu.protocols.isis.packet import (
        AuthCtxIsis,
        Lsp,
        LspId,
        decode_pdu,
    )
    from holo_tpu.utils.bytesbuf import DecodeError

    # codec level: round-trip + tamper for both TLV families
    for algo in ("hmac-md5", "hmac-sha256"):
        auth = AuthCtxIsis(key=b"k3y", algo=algo, key_id=9)
        lsp = Lsp(2, 1200, LspId(b"\x00\x00\x00\x00\x00\x01"), 4,
                  tlvs={"hostname": "a"})
        raw = lsp.encode(auth=auth)
        t, out = decode_pdu(raw, auth=auth)
        assert out.seqno == 4
        bad = bytearray(raw)
        bad[-1] ^= 0x40
        with pytest.raises(DecodeError):
            decode_pdu(bytes(bad), auth=auth)
        with pytest.raises(DecodeError):
            decode_pdu(raw, auth=AuthCtxIsis(key=b"other", algo=algo, key_id=9))
        # unauthenticated PDU rejected when auth required
        with pytest.raises(DecodeError):
            decode_pdu(Lsp(2, 1200, LspId(b"\x00" * 6), 1).encode(), auth=auth)

    def converge(key_a, key_b):
        loop = EventLoop(clock=VirtualClock())
        fabric = MockFabric(loop)
        insts = []
        for name, sid, addr, key in (
            ("ia", b"\x00\x00\x00\x00\x00\x0a", "10.7.0.1", key_a),
            ("ib", b"\x00\x00\x00\x00\x00\x0b", "10.7.0.2", key_b),
        ):
            inst = IsisInstance(
                name=name, sysid=sid, netio=fabric.sender_for(name),
                auth=AuthCtxIsis(key=key),
            )
            loop.register(inst)
            inst.add_interface("e0", IsisIfConfig(), A(addr), N("10.7.0.0/30"))
            fabric.join("l", name, "e0", A(addr))
            insts.append(inst)
        for inst in insts:
            loop.send(inst.name, IsisIfUpMsg("e0"))
        loop.advance(60)
        a, b = insts
        up = any(
            True for i in a.interfaces.values() for _ in i.up_adjacencies()
        )
        return up and set(a.lsdb) == set(b.lsdb)

    assert converge(b"ring0", b"ring0")
    assert not converge(b"ring0", b"wrong")


def test_isis_mt_origination_end_to_end():
    """RFC 5120 originate side: with mt_enabled the v6 reach rides the MT
    TLVs (ids 229/222/237) and an MT peer still computes v6 routes."""
    from ipaddress import IPv6Address as A6
    from ipaddress import IPv6Network as N6

    loop = EventLoop(clock=VirtualClock())
    fabric = MockFabric(loop)
    insts = []
    for name, sid, a4, a6, p6 in (
        ("mta", b"\x00\x00\x00\x00\x00\x1a", "10.8.0.1", "fe80::8:1",
         "2001:db8:a::/64"),
        ("mtb", b"\x00\x00\x00\x00\x00\x1b", "10.8.0.2", "fe80::8:2",
         "2001:db8:b::/64"),
    ):
        inst = IsisInstance(
            name=name, sysid=sid, netio=fabric.sender_for(name),
            mt_enabled=True,
        )
        loop.register(inst)
        inst.add_interface(
            "e0", IsisIfConfig(), A(a4), N("10.8.0.0/30"),
            addr6=A6(a6), prefix6=N6(p6),
        )
        fabric.join("l", name, "e0", A(a4))
        insts.append(inst)
    for inst in insts:
        loop.send(inst.name, IsisIfUpMsg("e0"))
    loop.advance(60)
    a, b = insts
    # our own LSP carries MT TLVs, not plain ipv6 reach
    own = a.lsdb[LspId(a.sysid)].lsp
    assert own.tlvs.get("mt_ids"), own.tlvs.keys()
    assert own.tlvs.get("mt_ipv6_reach") and not own.tlvs.get("ipv6_reach")
    # the peer computes the v6 route from the MT topology
    r6 = b.routes.get(N6("2001:db8:a::/64"))
    assert r6 is not None, sorted(map(str, b.routes))


def test_isis_sr_prefix_sids():
    """RFC 8667: SRGB capability + prefix-SID sub-TLVs resolve to labels."""
    from holo_tpu.utils.sr import PrefixSid, SrConfig, Srgb

    loop = EventLoop(clock=VirtualClock())
    fabric = MockFabric(loop)
    insts = []
    for name, sid, addr, lo in (
        ("sa", b"\x00\x00\x00\x00\x00\x2a", "10.9.0.1", "1.1.1.1"),
        ("sb", b"\x00\x00\x00\x00\x00\x2b", "10.9.0.2", "2.2.2.2"),
    ):
        loop_pfx = N(f"{lo}/32")
        sr = SrConfig(
            enabled=True, srgb=Srgb(16000, 23999),
            prefix_sids={loop_pfx: PrefixSid(loop_pfx, int(lo[0]) * 10)},
        )
        inst = IsisInstance(
            name=name, sysid=sid, netio=fabric.sender_for(name), sr=sr
        )
        loop.register(inst)
        inst.add_interface("e0", IsisIfConfig(), A(addr), N("10.9.0.0/30"))
        inst.add_interface(
            "lo", IsisIfConfig(metric=0), A(lo), loop_pfx
        )
        fabric.join("l", name, "e0", A(addr))
        insts.append(inst)
    for inst in insts:
        loop.send(inst.name, IsisIfUpMsg("e0"))
    loop.advance(60)
    a, b = insts
    # a resolves b's loopback SID through its SRGB: 16000 + 20
    entry = a.sr_labels.get(N("2.2.2.2/32"))
    assert entry is not None, a.sr_labels
    label, route = entry
    assert label == 16000 + 20  # our SRGB base + the advertised index
    # and the capability TLV round-tripped through b's LSP
    e = a.lsdb[LspId(b.sysid)].lsp
    assert e.tlvs.get("sr_cap") == (16000, 8000)


def test_yang_notifications_adjacency_lifecycle():
    """Reference holo-isis northbound/notification.rs: adjacency up/down,
    database-overload, and auth failures reach the notif_cb sink."""
    loop, fabric, (r1, r2) = mk_net(2)
    notifs = []
    r1.notif_cb = notifs.append
    link(loop, fabric, r1, "e0", "10.0.12.1", r2, "e0", "10.0.12.2",
         "10.0.12.0/30", 10)
    for r in (r1, r2):
        loop.send(r.name, IsisIfUpMsg("e0"))
    loop.advance(30)
    assert r1.interfaces["e0"].adj.state == AdjacencyState.UP
    ups = [n for n in notifs if "ietf-isis:adjacency-state-change" in n]
    assert ups, notifs
    body = ups[-1]["ietf-isis:adjacency-state-change"]
    assert body["state"] == "up"
    assert body["interface-name"] == "e0"
    assert body["neighbor-system-id"].count(".") == 2  # dotted sysid
    # Hold-time expiry: silence r2 so r1's hold timer fires.
    notifs.clear()
    loop.unregister(r2.name)
    loop.advance(120)
    downs = [n for n in notifs if "ietf-isis:adjacency-state-change" in n
             and n["ietf-isis:adjacency-state-change"]["state"] == "down"]
    assert downs, notifs
    # Overload toggling emits database-overload and re-originates.
    notifs.clear()
    r1.set_overload(True)
    ov = [n for n in notifs if "ietf-isis:database-overload" in n]
    assert ov and ov[0]["ietf-isis:database-overload"]["overload"] == "on"
    r1.set_overload(False)
    assert any(
        n.get("ietf-isis:database-overload", {}).get("overload") == "off"
        for n in notifs
    )


def test_yang_notification_auth_failure():
    """A PDU failing digest verification raises the authentication-failure
    notification (wrong TLV type raises the -type-failure variant)."""
    from holo_tpu.protocols.isis.packet import AuthCtxIsis
    from holo_tpu.utils.netio import NetRxPacket

    loop, fabric, (r1, r2) = mk_net(2)
    notifs = []
    r1.notif_cb = notifs.append
    r1.auth = AuthCtxIsis(key=b"right-key", algo="hmac-md5")
    link(loop, fabric, r1, "e0", "10.0.12.1", r2, "e0", "10.0.12.2",
         "10.0.12.0/30", 10)
    # r2 signs with the wrong key: digest mismatch on r1's LSP path.
    r2.auth = AuthCtxIsis(key=b"wrong-key", algo="hmac-md5")
    r2._originate_lsp(force=True)
    raw = next(iter(r2.lsdb.values())).lsp.raw
    r1.handle(NetRxPacket(ifname="e0", src=b"\x02\x00\x00\x00\x00\x02",
                          dst=None, data=raw))
    fails = [n for n in notifs if "ietf-isis:authentication-failure" in n]
    assert fails, notifs
    assert "raw-pdu" in fails[0]["ietf-isis:authentication-failure"]


def test_isis_frr_attaches_the_lfa():
    """IP-FRR on IS-IS: the route to the r2-r3 subnet leaves on e0
    (cost 1 + 2); r3 is an LFA for it (D(r3, r2) = 2 < D(r3, r1) +
    D(r1, r2) = 3), so losing e0 flips to e1."""
    from holo_tpu.frr.manager import FrrConfig

    loop, fabric, (r1, r2, r3) = mk_net(3)
    link(loop, fabric, r1, "e0", "10.0.12.1", r2, "e0", "10.0.12.2", "10.0.12.0/30", 1)
    link(loop, fabric, r2, "e1", "10.0.23.1", r3, "e0", "10.0.23.2", "10.0.23.0/30", 2)
    link(loop, fabric, r1, "e1", "10.0.13.1", r3, "e1", "10.0.13.2", "10.0.13.0/30", 2)
    r1.frr = FrrConfig(enabled=True)
    for r in (r1, r2, r3):
        for ifname in r.interfaces:
            loop.send(r.name, IsisIfUpMsg(ifname))
    loop.advance(30)
    assert r1.routes[N("10.0.23.0/30")][0] == 3
    assert r1.frr_backups == {
        N("10.0.23.0/30"): {
            ("e0", A("10.0.12.2")): (("e1", A("10.0.13.2")), ()),
        },
    }
