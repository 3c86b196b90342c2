"""OSPFv3's area marshal through the kept lowering
(``spf_run.LoweredLsdbV3``) against the Python body it replaced, which
stays here as the oracle: the same ``Topology``, ``keys``, ``atoms``,
``edge_direct_atom`` and prefix LSAs, field for field, on seeded LSDBs
(point-to-point, parallel links, one LAN, one-way and dangling links,
two Router-LSAs of one router, a MaxAge entry), on a fresh lowering and
on one kept across changes."""

import dataclasses
from ipaddress import IPv4Address, IPv6Address, IPv6Network

import numpy as np
import pytest

from holo_tpu import telemetry
from holo_tpu.ops.graph import Topology, mutual_keep_mask
from holo_tpu.protocols.ospf import packet_v3 as P
from holo_tpu.protocols.ospf import spf_run
from holo_tpu.protocols.ospf.instance_v3 import OspfV3Instance, V3IfConfig
from holo_tpu.protocols.ospf.interface import IfType
from holo_tpu.protocols.ospf.neighbor import Neighbor, NsmState
from holo_tpu.protocols.ospf.spf_run import (
    LoweredLsdbV3, NexthopAtom, link_spf_delta,
)
from holo_tpu.utils.runtime import EventLoop, VirtualClock

ROOT = IPv4Address("10.0.0.1")


def old_area_marshal(inst, area, vlink_nexthops=None):
    """``OspfV3Instance._area_spf`` as it was before ISSUE 31, up to
    the dispatch: Python loops over every link of every Router-LSA."""
    now = inst.loop.clock.now()
    routers, networks, prefix_lsas = {}, {}, []
    for e in area.lsdb.all():
        if e.current_age(now) >= P.MAX_AGE:
            continue
        if e.lsa.type == P.LsaType.ROUTER:
            routers[e.lsa.adv_rtr] = e.lsa.body
        elif e.lsa.type == P.LsaType.NETWORK:
            networks[(e.lsa.adv_rtr, int(e.lsa.lsid))] = e.lsa.body
        elif e.lsa.type == P.LsaType.INTRA_AREA_PREFIX:
            prefix_lsas.append((e.lsa.adv_rtr, e.lsa.body))
    if inst.router_id not in routers:
        return None
    keys = [("N",) + k for k in sorted(networks, key=lambda k: (int(k[0]), k[1]))]
    keys += [("R", rid) for rid in sorted(routers, key=int)]
    index = {k: i for i, k in enumerate(keys)}
    n = len(keys)
    is_router = np.array([k[0] == "R" for k in keys], bool)
    src, dst, cost, edge_kind, edge_nbr_ifid = [], [], [], [], []
    for rid, body in routers.items():
        u = index[("R", rid)]
        for link in body.links:
            if link.link_type == P.RouterLinkType.TRANSIT_NETWORK:
                v = index.get(("N", link.nbr_router_id, link.nbr_iface_id))
            else:
                v = index.get(("R", link.nbr_router_id))
            if v is not None:
                src.append(u)
                dst.append(v)
                cost.append(link.metric)
                edge_kind.append(int(link.link_type))
                edge_nbr_ifid.append(link.nbr_iface_id)
    for (adv, ifid), body in networks.items():
        u = index[("N", adv, ifid)]
        for member in body.attached:
            v = index.get(("R", member))
            if v is not None:
                src.append(u)
                dst.append(v)
                cost.append(0)
                edge_kind.append(-1)
                edge_nbr_ifid.append(0)
    src_a = np.array(src, np.int32).reshape(-1)
    dst_a = np.array(dst, np.int32).reshape(-1)
    keep = mutual_keep_mask(src_a, dst_a)
    edge_kind = [k for k, kp in zip(edge_kind, keep) if kp]
    edge_nbr_ifid = [i for i, kp in zip(edge_nbr_ifid, keep) if kp]
    topo = Topology(
        n_vertices=n, is_router=is_router,
        edge_src=src_a[keep], edge_dst=dst_a[keep],
        edge_cost=np.array(cost, np.int32).reshape(-1)[keep],
        root=index[("R", inst.router_id)],
    )
    atoms = []
    atom_ids = np.full(topo.n_edges, -1, np.int32)
    nbr_hop, nbr_hop_by_ifid, lan_iface_of = {}, {}, {}
    for iface in inst._area_ifaces(area):
        for nbr in iface.neighbors.values():
            if nbr.state == NsmState.FULL and not iface.is_lan:
                nbr_hop[nbr.router_id] = (iface.name, nbr.src)
                nbr_hop_by_ifid[(nbr.router_id, nbr.iface_id)] = (
                    iface.name, nbr.src,
                )
        if iface.is_lan and inst._transit_active(iface):
            lan_iface_of[("N", iface.dr, inst._dr_iface_id(iface))] = iface
    root_lans = set()
    for e_i in range(topo.n_edges):
        if topo.edge_src[e_i] == topo.root:
            k = keys[int(topo.edge_dst[e_i])]
            if k[0] == "R":
                hop = None
                if edge_kind[e_i] == int(P.RouterLinkType.VIRTUAL_LINK):
                    borrowed = (vlink_nexthops or {}).get(k[1])
                    if borrowed:
                        hop = NexthopAtom(None, None, borrowed)
                else:
                    hop = nbr_hop_by_ifid.get(
                        (k[1], edge_nbr_ifid[e_i])
                    ) or nbr_hop.get(k[1])
                if hop is not None:
                    atom_ids[e_i] = len(atoms)
                    atoms.append(hop)
            elif k in lan_iface_of:
                root_lans.add(int(topo.edge_dst[e_i]))
                atom_ids[e_i] = len(atoms)
                atoms.append((lan_iface_of[k].name, None))
    for e_i in range(topo.n_edges):
        u = int(topo.edge_src[e_i])
        if u in root_lans:
            iface = lan_iface_of[keys[u]]
            member = keys[int(topo.edge_dst[e_i])][1]
            if member == inst.router_id:
                continue
            nbr = iface.neighbors.get(member)
            if nbr is not None:
                atom_ids[e_i] = len(atoms)
                atoms.append((iface.name, nbr.src))
    topo.edge_direct_atom = atom_ids
    return topo, keys, index, atoms, prefix_lsas


def _rid(i: int) -> IPv4Address:
    # some ids above 2**31, where a signed 64-bit key would go wrong
    return IPv4Address(int(ROOT) + i) if i % 3 else IPv4Address(
        (200 << 24) + i
    )


def _lsa(ltype, lsid, adv, body, age=1, seq=1):
    lsa = P.Lsa(age, ltype, IPv4Address(lsid), adv, P.INITIAL_SEQ_NO + seq, body)
    lsa.encode()
    return lsa


def _p2p(nbr, metric, ifid=1, nbr_ifid=1, kind=P.RouterLinkType.POINT_TO_POINT):
    return P.RouterLinkV3(kind, metric, ifid, nbr_ifid, nbr)


def build(seed: int, n: int = 40, lan: bool = False):
    """A seeded instance + area: the root with three point-to-point
    interfaces (two of them parallel links to one neighbour) and, with
    ``lan``, a broadcast interface whose DR is a neighbour."""
    rng = np.random.default_rng(seed)
    loop = EventLoop(clock=VirtualClock())
    inst = OspfV3Instance("v3-marshal", ROOT, netio=None)
    loop.register(inst)
    rids = [ROOT] + [_rid(i) for i in range(1, n)]
    links = {r: [] for r in rids}
    for v in range(1, n):
        u = int(rng.integers(0, v)) if v > 3 else 0
        c_uv, c_vu = (int(x) for x in rng.integers(1, 9, 2))
        if u == 0:
            continue  # the root's own links are made below
        links[rids[u]].append(_p2p(rids[v], c_uv, v, u))
        links[rids[v]].append(_p2p(rids[u], c_vu, u, v))
    for _ in range(n):
        u, v = (int(x) for x in rng.integers(1, n, 2))
        if u != v:
            links[rids[u]].append(_p2p(rids[v], int(rng.integers(1, 9)), 50 + v, 50 + u))
            if rng.random() < 0.8:  # else a one-way link
                links[rids[v]].append(_p2p(rids[u], int(rng.integers(1, 9)), 50 + u, 50 + v))
    links[rids[5]].append(_p2p(IPv4Address("9.9.9.9"), 3))  # dangling
    area_id = IPv4Address(1)
    # root: e0 -> r1 (ifid 11), e1 -> r1 again (ifid 12), e2 -> r2
    for name, peer, peer_ifid, cost in (
        ("e0", 1, 11, 4), ("e1", 1, 12, 4), ("e2", 2, 21, 7),
    ):
        iface = inst.add_interface(
            name, V3IfConfig(cost=cost, area_id=area_id),
            IPv6Address(f"fe80::{name[1]}"), [],
        )
        iface.up = True
        iface.neighbors[rids[peer]] = Neighbor(
            router_id=rids[peer], src=IPv6Address(f"fe80::a:{peer_ifid}"),
            state=NsmState.FULL, iface_id=peer_ifid,
        )
        links[ROOT].append(_p2p(rids[peer], cost, iface.iface_id, peer_ifid))
        links[rids[peer]].append(_p2p(ROOT, cost, peer_ifid, iface.iface_id))
    # r3 hangs off the root through a virtual link: never an adjacency
    links[ROOT].append(_p2p(rids[3], 9, 90, 91, P.RouterLinkType.VIRTUAL_LINK))
    links[rids[3]].append(_p2p(ROOT, 9, 91, 90, P.RouterLinkType.VIRTUAL_LINK))
    networks = {}
    if lan:
        dr, dr_ifid = rids[4], 44
        iface = inst.add_interface(
            "lan0", V3IfConfig(cost=2, area_id=area_id, if_type=IfType.BROADCAST),
            IPv6Address("fe80::77"), [],
        )
        iface.up, iface.dr = True, dr
        for member, ifid in ((4, dr_ifid), (6, 66), (7, 76)):
            iface.neighbors[rids[member]] = Neighbor(
                router_id=rids[member], src=IPv6Address(f"fe80::b:{member}"),
                state=NsmState.FULL, iface_id=ifid,
            )
            links[rids[member]].append(_p2p(
                dr, 2, ifid, dr_ifid, P.RouterLinkType.TRANSIT_NETWORK
            ))
        links[ROOT].append(_p2p(
            dr, 2, iface.iface_id, dr_ifid, P.RouterLinkType.TRANSIT_NETWORK
        ))
        networks[(dr, dr_ifid)] = [dr, ROOT, rids[6], rids[7], rids[8]]
        # a second LAN far from the root, its DR's id above 2**31
        far = next(r for r in rids[9:] if int(r) >= 1 << 31)
        others = [r for r in rids[9:15] if r != far]
        for r in others + [far]:
            links[r].append(_p2p(far, 1, 70, 71, P.RouterLinkType.TRANSIT_NETWORK))
        networks[(far, 71)] = [far, *others]
    area = inst.areas[area_id]
    now = loop.clock.now()
    for r in rids:
        if r == rids[10]:
            # two Router-LSAs of one router: the later one's links hold
            area.lsdb.install(_lsa(P.LsaType.ROUTER, 7, r, P.LsaRouterV3(links=links[r][:1])), now)
            area.lsdb.install(_lsa(P.LsaType.ROUTER, 0, r, P.LsaRouterV3(links=links[r])), now)
        else:
            area.lsdb.install(_lsa(P.LsaType.ROUTER, 0, r, P.LsaRouterV3(links=links[r])), now)
        area.lsdb.install(_lsa(
            P.LsaType.INTRA_AREA_PREFIX, 1, r,
            P.LsaIntraAreaPrefix(
                ref_type=int(P.LsaType.ROUTER), ref_lsid=IPv4Address(0),
                ref_adv_rtr=r,
                prefixes=[(IPv6Network((int(r) << 64 | 0x2001 << 112, 64)), 1)],
            ),
        ), now)
    for (dr, ifid), attached in networks.items():
        area.lsdb.install(_lsa(
            P.LsaType.NETWORK, ifid, dr, P.LsaNetworkV3(attached=attached)
        ), now)
        area.lsdb.install(_lsa(
            P.LsaType.INTRA_AREA_PREFIX, 0x100 + ifid, dr,
            P.LsaIntraAreaPrefix(
                ref_type=int(P.LsaType.NETWORK), ref_lsid=IPv4Address(ifid),
                ref_adv_rtr=dr, prefixes=[(IPv6Network("2001:db8:77::/64"), 0)],
            ),
        ), now)
    # an LSA of a type the graph ignores, and a router that is MaxAge
    area.lsdb.install(_lsa(
        P.LsaType.INTER_AREA_PREFIX, 9, rids[2],
        P.LsaInterAreaPrefix(metric=5, prefix=IPv6Network("2001:db8:99::/48")),
    ), now)
    area.lsdb.install(_lsa(
        P.LsaType.ROUTER, 0, IPv4Address("10.9.9.9"),
        P.LsaRouterV3(links=[_p2p(rids[6], 1)]), age=P.MAX_AGE,
    ), now)
    return inst, area, rids, links


def assert_same(inst, area, vlink=None):
    want = old_area_marshal(inst, area, vlink)
    got = inst._area_marshal(area, vlink)
    if want is None:
        assert got is None
        return None
    topo, keys, index, atoms, prefix_lsas = want
    assert got.keys == keys and got.index == index
    assert got.atoms == atoms
    assert got.prefix_lsas == prefix_lsas
    for name in ("n_vertices", "root"):
        assert getattr(got.topo, name) == getattr(topo, name), name
    for name in (
        "is_router", "edge_src", "edge_dst", "edge_cost", "edge_direct_atom",
    ):
        a, b = getattr(got.topo, name), getattr(topo, name)
        assert a.dtype == b.dtype and np.array_equal(a, b), name
    return got


@pytest.mark.parametrize("lan", [False, True], ids=["p2p", "lan"])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_fresh_lowering_equals_the_old_body(seed, lan):
    inst, area, rids, _links = build(seed, lan=lan)
    vlink = {rids[3]: frozenset({("e2", IPv6Address("fe80::a:21"))})}
    st = assert_same(inst, area, vlink)
    # parallel links are two atoms, the virtual link one borrowed set,
    # and with the LAN: the interface itself plus its members
    assert ("e0", IPv6Address("fe80::a:11")) in st.atoms
    assert ("e1", IPv6Address("fe80::a:12")) in st.atoms
    assert any(isinstance(a, NexthopAtom) for a in st.atoms)
    assert (("lan0", None) in st.atoms) == lan
    assert (("lan0", IPv6Address("fe80::b:6")) in st.atoms) == lan


@pytest.mark.parametrize("lan", [False, True], ids=["p2p", "lan"])
def test_kept_lowering_follows_every_kind_of_change(lan):
    inst, area, rids, links = build(5, lan=lan)
    now = inst.loop.clock.now
    assert_same(inst, area)
    # a flap: both ends re-originate without the link
    a, b = rids[20], next(l.nbr_router_id for l in links[rids[20]])
    for r, other in ((a, b), (b, a)):
        kept = [l for l in links[r] if l.nbr_router_id != other]
        area.lsdb.install(_lsa(P.LsaType.ROUTER, 0, r, P.LsaRouterV3(links=kept), seq=2), now())
    assert_same(inst, area)
    # a cost change alone, a new router, a router removed outright
    area.lsdb.install(_lsa(P.LsaType.ROUTER, 0, rids[30], P.LsaRouterV3(links=[
        P.RouterLinkV3(l.link_type, l.metric + 1, l.iface_id, l.nbr_iface_id, l.nbr_router_id)
        for l in links[rids[30]]
    ]), seq=2), now())
    assert_same(inst, area)
    new = IPv4Address("10.7.7.7")
    area.lsdb.install(_lsa(P.LsaType.ROUTER, 0, new, P.LsaRouterV3(links=[_p2p(rids[12], 2, 1, 99)])), now())
    area.lsdb.install(_lsa(P.LsaType.ROUTER, 0, rids[12], P.LsaRouterV3(links=links[rids[12]] + [_p2p(new, 2, 99, 1)]), seq=2), now())
    assert_same(inst, area)
    area.lsdb.remove(P.LsaKey(P.LsaType.ROUTER, IPv4Address(0), rids[25]))
    assert_same(inst, area)
    # an entry of a type the graph ignores goes: nothing moves
    area.lsdb.remove(P.LsaKey(P.LsaType.INTER_AREA_PREFIX, IPv4Address(9), rids[2]))
    assert_same(inst, area)
    # the clock takes a router to MaxAge with no install at all
    old = _lsa(P.LsaType.ROUTER, 0, rids[15], P.LsaRouterV3(links=links[rids[15]]), age=P.MAX_AGE - 5, seq=3)
    area.lsdb.install(old, now())
    st = assert_same(inst, area)
    assert ("R", rids[15]) in st.index
    inst.loop.advance(10.0)
    st = assert_same(inst, area)
    assert ("R", rids[15]) not in st.index
    # an adjacency goes: its atom with it
    del inst.interfaces["e2"].neighbors[rids[2]]
    assert_same(inst, area)


def test_no_router_lsa_of_ours_is_no_topology():
    inst, area, _rids, _links = build(2)
    area.lsdb.remove(P.LsaKey(P.LsaType.ROUTER, IPv4Address(0), ROOT))
    assert assert_same(inst, area) is None
    assert IPv4Address(1) not in inst._spf_lowerings


def test_unchanged_inputs_hand_out_the_last_object_and_anything_else_does_not():
    inst, area, rids, links = build(4, lan=True)
    now = inst.loop.clock.now
    first = inst._area_marshal(area)
    assert inst._area_marshal(area) is first
    # an LSA the result is not made from comes and goes: still the same
    other = _lsa(P.LsaType.INTER_AREA_PREFIX, 77, rids[3], P.LsaInterAreaPrefix(metric=1, prefix=IPv6Network("2001:db8:5::/48")))
    area.lsdb.install(other, now())
    assert inst._area_marshal(area) is first
    area.lsdb.remove(other.key)
    assert inst._area_marshal(area) is first
    # the same Router-LSA installed again is a new entry: a new object,
    # equal to the old body's as ever
    area.lsdb.install(_lsa(P.LsaType.ROUTER, 0, rids[9], P.LsaRouterV3(links=links[rids[9]]), seq=2), now())
    second = assert_same(inst, area)
    assert second is not first and inst._area_marshal(area) is second
    # a prefix LSA, a neighbour's address, a LAN member's address, MaxAge
    area.lsdb.install(_lsa(P.LsaType.INTRA_AREA_PREFIX, 1, rids[9], P.LsaIntraAreaPrefix(ref_adv_rtr=rids[9], prefixes=[]), seq=2), now())
    third = assert_same(inst, area)
    assert third is not second
    inst.interfaces["e0"].neighbors[rids[1]].src = IPv6Address("fe80::dead")
    fourth = assert_same(inst, area)
    assert fourth is not third
    inst.interfaces["lan0"].neighbors[rids[6]].src = IPv6Address("fe80::beef")
    fifth = assert_same(inst, area)
    assert fifth is not fourth and inst._area_marshal(area) is fifth
    area.lsdb.install(_lsa(P.LsaType.ROUTER, 0, rids[16], P.LsaRouterV3(links=links[rids[16]]), age=P.MAX_AGE - 2, seq=2), now())
    sixth = assert_same(inst, area)
    inst.loop.advance(5.0)
    assert assert_same(inst, area) is not sixth
    # off: a new object every time
    inst.reuse_unchanged_areas = False
    assert inst._area_marshal(area) is not inst._area_marshal(area)


def test_marshal_walks_no_link_in_python(monkeypatch):
    """The cost the issue names: nothing of the marshal may be a Python
    loop over the LSDB's links.  Lowered once, a second call touches no
    Router-LSA body at all."""
    inst, area, _rids, _links = build(3)
    inst.reuse_unchanged_areas = False
    inst._area_marshal(area)
    touched = []
    monkeypatch.setattr(
        P.LsaRouterV3, "__getattribute__",
        lambda self, name: (
            touched.append(name) if name == "links" else None
        ) or object.__getattribute__(self, name),
    )
    inst._area_marshal(area)
    assert not touched


# -- the kept lowering by difference (ISSUE 39): entries followed by
# identity whatever the LSDB's length does, a moved area's edges from
# the last call's resolved rows

AREA = IPv4Address(1)
TOPO_FIELDS = (
    "is_router", "edge_src", "edge_dst", "edge_cost", "edge_direct_atom",
)


def _counted(family: str, paths: tuple) -> dict:
    snap = telemetry.snapshot(family)
    return {
        path: sum(v for k, v in snap.items() if f"path={path}" in k)
        for path in paths
    }


def _moved_by(family: str, paths: tuple, call) -> dict:
    before = _counted(family, paths)
    call()
    after = _counted(family, paths)
    return {p: after[p] - before[p] for p in paths}


def _lsas_moved(call) -> tuple:
    moved = _moved_by(
        "holo_ospf_topology_lsas_total", ("lowered", "reused"), call
    )
    return moved["lowered"], moved["reused"]


def _rows_moved(call) -> tuple:
    moved = _moved_by(
        "holo_ospf_topology_rows_total", ("kept", "resolved"), call
    )
    return moved["kept"], moved["resolved"]


def assert_columns_are_a_fresh_lowerings(inst, area):
    kept, fresh = inst._spf_lowerings[area.area_id], LoweredLsdbV3()
    fresh._refresh(area.lsdb)
    for name in ("_kind", "_vid", "_n_links", "_links", "_link_off"):
        a, b = getattr(kept, name), getattr(fresh, name)
        assert a.dtype == b.dtype and np.array_equal(a, b), name
    assert len(kept._bodies) == len(fresh._bodies)
    for a, b in zip(kept._bodies, fresh._bodies):
        # the LSA's body, or for an Intra-Area-Prefix LSA its pair
        assert a is b or (a[0] == b[0] and a[1] is b[1])
    assert all(map(lambda a, b: a is b, kept.entries, fresh.entries))


def _router(area, rid, links, now, seq, lsid=0, age=1):
    area.lsdb.install(
        _lsa(P.LsaType.ROUTER, lsid, rid, P.LsaRouterV3(links=list(links)),
             age=age, seq=seq),
        now,
    )


class Walk:
    """A seeded walk over one area's LSDB: every kind of change the
    kept lowering has a path for, one a step."""

    def __init__(self, seed: int):
        self.inst, self.area, self.rids, self.links = build(seed, lan=True)
        self.rng = np.random.default_rng(seed)
        self.seq = 10
        self.gone: dict = {}  # adjacencies taken away: (ifname, rid) -> nbr
        self.others: list = []  # keys of the entries the graph ignores
        self.new = 0
        # never replaced by the walk: the root, and the two DRs
        self.fixed = {ROOT, self.rids[4]} | {
            r for r in self.rids[9:] if int(r) >= 1 << 31
        }

    def now(self):
        return self.inst.loop.clock.now()

    def pick(self):
        live = [
            r for r in self.links
            if r not in self.fixed and P.LsaKey(
                P.LsaType.ROUTER, IPv4Address(0), r
            ) in self.area.lsdb.entries
        ]
        return live[int(self.rng.integers(len(live)))]

    def install(self, rid, **kw):
        self.seq += 1
        _router(self.area, rid, self.links[rid], self.now(), self.seq, **kw)

    # the steps

    def link_less(self):
        rid = self.pick()
        if len(self.links[rid]) > 1:
            self.links[rid].pop(int(self.rng.integers(len(self.links[rid]))))
        self.install(rid)

    def cost_more(self):
        rid = self.pick()
        i = int(self.rng.integers(len(self.links[rid])))
        l = self.links[rid][i]
        self.links[rid][i] = P.RouterLinkV3(
            l.link_type, l.metric + 1, l.iface_id, l.nbr_iface_id,
            l.nbr_router_id,
        )
        self.install(rid)

    def other_comes_or_goes(self):
        # an Inter-Area-Prefix LSA: installed (a new key at the end, a
        # known one replaced in the middle) or removed from the middle
        if self.others and self.rng.random() < 0.5:
            key = self.others.pop(int(self.rng.integers(len(self.others))))
            self.area.lsdb.remove(key)
            return
        lsid = int(self.rng.integers(100, 110))
        self.seq += 1
        lsa = _lsa(
            P.LsaType.INTER_AREA_PREFIX, lsid, self.rids[2],
            P.LsaInterAreaPrefix(
                metric=lsid, prefix=IPv6Network((0x2001 << 112 | lsid << 80, 48))
            ), seq=self.seq,
        )
        self.area.lsdb.install(lsa, self.now())
        if lsa.key not in self.others:
            self.others.append(lsa.key)

    def router_goes(self):
        rid = self.pick()
        self.area.lsdb.remove(P.LsaKey(P.LsaType.ROUTER, IPv4Address(0), rid))
        self.area.lsdb.remove(
            P.LsaKey(P.LsaType.INTRA_AREA_PREFIX, IPv4Address(1), rid)
        )

    def router_comes(self):
        self.new += 1
        rid, peer = IPv4Address((77 << 24) + self.new), self.pick()
        self.links[rid] = [_p2p(peer, 2, 1, 900 + self.new)]
        self.links[peer].append(_p2p(rid, 3, 900 + self.new, 1))
        self.install(rid)
        self.install(peer)

    def second_router_lsa(self):
        rid = self.pick()
        self.seq += 1
        _router(
            self.area, rid, self.links[rid][:1], self.now(), self.seq,
            lsid=int(self.rng.integers(1, 4)),
        )

    def network_changes(self):
        nets = [
            e for e in self.area.lsdb.entries.values()
            if e.lsa.type == P.LsaType.NETWORK
        ]
        lsa = nets[int(self.rng.integers(len(nets)))].lsa
        attached = list(lsa.body.attached)
        if len(attached) > 2 and self.rng.random() < 0.5:
            attached.pop()
        else:
            attached.append(self.pick())
        self.seq += 1
        self.area.lsdb.install(_lsa(
            P.LsaType.NETWORK, int(lsa.lsid), lsa.adv_rtr,
            P.LsaNetworkV3(attached=attached), seq=self.seq,
        ), self.now())

    def max_age_by_the_clock(self):
        rid = self.pick()
        self.install(rid, age=P.MAX_AGE - 5)
        st = assert_same(self.inst, self.area)
        assert ("R", rid) in st.index
        self.inst.loop.advance(10.0)

    def adjacency_goes_or_comes(self):
        if self.gone and self.rng.random() < 0.5:
            (ifname, rid), nbr = self.gone.popitem()
            self.inst.interfaces[ifname].neighbors[rid] = nbr
            return
        held = [
            (name, rid) for name, iface in self.inst.interfaces.items()
            for rid in iface.neighbors
        ]
        if held:
            name, rid = held[int(self.rng.integers(len(held)))]
            self.gone[(name, rid)] = (
                self.inst.interfaces[name].neighbors.pop(rid)
            )

    STEPS = (
        link_less, cost_more, other_comes_or_goes, router_goes, router_comes,
        second_router_lsa, network_changes, max_age_by_the_clock,
        adjacency_goes_or_comes,
    )
    # the steps that keep the vertex model come most often, as they do
    WEIGHTS = (5, 5, 5, 1, 1, 1, 3, 1, 2)


@pytest.mark.parametrize("seed", [11, 12])
def test_a_walk_of_changes_equals_the_old_body_and_a_fresh_lowering(seed):
    walk = Walk(seed)
    assert_same(walk.inst, walk.area)
    weights = np.array(Walk.WEIGHTS) / sum(Walk.WEIGHTS)
    taken, prev = set(), None
    kept_before = _counted("holo_ospf_topology_rows_total", ("kept",))
    for _ in range(220):
        step = Walk.STEPS[int(walk.rng.choice(len(Walk.STEPS), p=weights))]
        taken.add(step.__name__)
        step(walk)
        frozen = prev and {
            f: getattr(prev.topo, f).tobytes() for f in TOPO_FIELDS
        }
        st = assert_same(walk.inst, walk.area)
        assert st is not None
        assert_columns_are_a_fresh_lowerings(walk.inst, walk.area)
        if prev is not None and prev is not st:
            # nothing handed out is written again
            assert frozen == {
                f: getattr(prev.topo, f).tobytes() for f in TOPO_FIELDS
            }
        prev = st
    assert taken == {s.__name__ for s in Walk.STEPS}
    # and the walk went by difference: rows were kept
    kept_after = _counted("holo_ospf_topology_rows_total", ("kept",))
    assert kept_after["kept"] > kept_before["kept"]


def test_a_removal_lowers_nothing_and_an_append_only_what_came():
    inst, area, rids, links = build(6, lan=True)
    now = inst.loop.clock.now
    n = len(area.lsdb.entries)

    def call():
        assert_same(inst, area)

    # (the oracle's own walk and the first lowering)
    assert _lsas_moved(lambda: inst._area_marshal(area)) == (n, 0)
    assert _lsas_moved(lambda: inst._area_marshal(area)) == (0, n)
    # a Router-LSA from the middle, outright
    area.lsdb.remove(P.LsaKey(P.LsaType.ROUTER, IPv4Address(0), rids[20]))
    assert _lsas_moved(call) == (0, n - 1)
    assert_columns_are_a_fresh_lowerings(inst, area)
    # an entry the graph ignores from the middle: nothing either
    area.lsdb.remove(
        P.LsaKey(P.LsaType.INTER_AREA_PREFIX, IPv4Address(9), rids[2])
    )
    assert _lsas_moved(call) == (0, n - 2)
    # an append lowers what was appended
    new = IPv4Address("10.7.7.7")
    _router(area, new, [_p2p(rids[12], 2, 1, 99)], now(), 2)
    assert _lsas_moved(call) == (1, n - 2)
    # a removal, a replacement behind it and an append in one call,
    # the length what it was
    area.lsdb.remove(P.LsaKey(P.LsaType.ROUTER, IPv4Address(0), rids[5]))
    _router(area, rids[30], links[rids[30]][:-1], now(), 3)
    _router(area, IPv4Address("10.7.7.8"), [_p2p(new, 1)], now(), 2)
    assert _lsas_moved(call) == (2, n - 3)
    assert_columns_are_a_fresh_lowerings(inst, area)
    # removed and installed again: gone from its place, new at the end
    key = P.LsaKey(P.LsaType.ROUTER, IPv4Address(0), rids[22])
    area.lsdb.remove(key)
    _router(area, rids[22], links[rids[22]], now(), 4)
    assert _lsas_moved(call) == (1, n - 2)
    assert_columns_are_a_fresh_lowerings(inst, area)


def test_entries_in_another_order_are_lowered_from_the_first_difference():
    """Not a dict's doing, but any way of writing ``lsdb.entries`` is
    seen: what stayed in another order is lowered again."""
    inst, area, _rids, _links = build(6)
    assert_same(inst, area)
    items = list(area.lsdb.entries.items())
    n = len(items)
    items[3], items[9] = items[9], items[3]
    area.lsdb.entries = dict(items)
    assert _lsas_moved(lambda: assert_same(inst, area)) == (n - 3, 3)
    assert_columns_are_a_fresh_lowerings(inst, area)


def _flap(area, links, a, seq, now):
    """Both ends of ``a``'s first link re-originate without it."""
    b = links[a][0].nbr_router_id
    for r, other in ((a, b), (b, a)):
        links[r] = [l for l in links[r] if l.nbr_router_id != other]
        _router(area, r, links[r], now, seq)
    return a, b


def test_one_flap_resolves_the_replaced_rows_and_keeps_the_rest(monkeypatch):
    inst, area, rids, links = build(8, n=400)
    inst._area_marshal(area)
    lowering = inst._spf_lowerings[AREA]
    total = len(lowering._rows.dst)
    assert total >= 1000
    a, b = _flap(area, links, rids[200], 2, inst.loop.clock.now())
    # the rows of the two LSAs, and the rows that end at their routers
    replaced = len(links[a]) + len(links[b])
    sizes = []
    for name in ("lookup_sorted", "mutual_keep_mask"):
        real = getattr(spf_run, name)
        monkeypatch.setattr(
            spf_run, name,
            lambda x, y, real=real, name=name: (
                sizes.append((name, len(y))) or real(x, y)
            ),
        )
    kept, resolved = _rows_moved(lambda: assert_same(inst, area))
    assert {name for name, _n in sizes} == {"lookup_sorted", "mutual_keep_mask"}
    assert max(n for _name, n in sizes) <= 4 * (replaced + 2)
    assert resolved <= 4 * (replaced + 2) and kept == total - 2 - resolved
    # a whole assembly (another vertex model) resolves every row
    _router(area, IPv4Address("10.7.7.7"), [_p2p(rids[12], 2, 1, 99)],
            inst.loop.clock.now(), 2)
    sizes.clear()
    kept, resolved = _rows_moved(lambda: assert_same(inst, area))
    assert kept == 0 and resolved == total - 2 + 1
    assert max(n for _name, n in sizes) >= resolved - 1


def test_the_previous_topology_is_never_written_and_the_delta_is_a_fresh_ones():
    inst, area, rids, links = build(9, n=120, lan=True)
    prev = inst._area_marshal(area)
    frozen = {f: getattr(prev.topo, f).tobytes() for f in TOPO_FIELDS}
    _flap(area, links, rids[60], 2, inst.loop.clock.now())
    r = rids[70]
    l = links[r][0]
    links[r][0] = P.RouterLinkV3(
        l.link_type, l.metric + 3, l.iface_id, l.nbr_iface_id, l.nbr_router_id
    )
    _router(area, r, links[r], inst.loop.clock.now(), 2)
    st = assert_same(inst, area)
    assert st is not prev
    assert {f: getattr(prev.topo, f).tobytes() for f in TOPO_FIELDS} == frozen
    for f in TOPO_FIELDS:
        assert not np.shares_memory(getattr(prev.topo, f), getattr(st.topo, f))
    kept_rows = inst._spf_lowerings[AREA]._rows
    for column in (kept_rows.src, kept_rows.dst, kept_rows.cost,
                   kept_rows.mutual):
        for f in TOPO_FIELDS:
            assert not np.shares_memory(column, getattr(st.topo, f))
    assert link_spf_delta(prev, st)
    # two fresh assemblies of the same two LSDBs
    inst2, area2, rids2, links2 = build(9, n=120, lan=True)
    prev2 = inst2._area_marshal(area2)
    del inst2._spf_lowerings[AREA]
    _flap(area2, links2, rids2[60], 2, inst2.loop.clock.now())
    links2[r][0] = links[r][0]
    _router(area2, r, links2[r], inst2.loop.clock.now(), 2)
    st2 = inst2._area_marshal(area2)
    assert link_spf_delta(prev2, st2)
    got, want = st.topo.delta_base, st2.topo.delta_base
    assert got.n_ops == want.n_ops > 0 and got.kind == want.kind
    for field in dataclasses.fields(got):
        if field.name != "base_key":
            a, b = getattr(got, field.name), getattr(want, field.name)
            assert np.array_equal(a, b), field.name
