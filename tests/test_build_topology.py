"""``build_topology`` through a kept lowering against the body it replaced
(ISSUE 30).

``build_topology`` used to lower the whole area LSDB, in Python, on every
SPF run; it is now an update of a ``LoweredLsdb`` that the area keeps
between runs, assembled on arrays.  The old body is kept here as the
oracle, verbatim but for its name and for the mutual-link filter, which
is ``ops/graph.py mutual_keep_mask``'s old body too (that function
changed in the same PR).  The contract is equality, not equivalence: for
any LSDB and any sequence of installs, removals, flushes, ageing and
``entries.clear()`` the kept lowering returns the oracle's
``SpfTopology`` field for field, edges in the same order.  No case reads
a clock.
"""

import json
import os
import subprocess
import sys
from ipaddress import IPv4Address
from pathlib import Path

import numpy as np
import pytest

from holo_tpu import telemetry
from holo_tpu.ops.graph import Topology
from holo_tpu.protocols.ospf.lsdb import Lsdb
from holo_tpu.protocols.ospf.packet import (
    Lsa,
    LsaKey,
    LsaNetwork,
    LsaRouter,
    LsaSummary,
    LsaType,
    Options,
    RouterFlags,
    RouterLink,
    RouterLinkType,
)
from holo_tpu.protocols.ospf.spf_run import (
    LoweredLsdb,
    NexthopAtom,
    RouteNexthop,
    SpfTopology,
    apply_interface_srlg,
    apply_partition_hint,
    build_topology,
    srlg_bits,
)


REPO = Path(__file__).resolve().parent.parent
FAMILY = "holo_ospf_topology_lsas_total"


def _oracle_mutual_keep_mask(edge_src, edge_dst) -> np.ndarray:
    """``mutual_keep_mask`` as it stood before ISSUE 30."""
    src = np.asarray(edge_src)
    dst = np.asarray(edge_dst)
    fwd = set(zip(src.tolist(), dst.tolist()))
    return np.array([(d, s) in fwd for s, d in zip(src, dst)], dtype=bool)


def _oracle_build_topology(
    lsdb: Lsdb,
    router_id: IPv4Address,
    now: float,
    iface_by_addr: dict[IPv4Address, str],
    iface_by_nbr: dict[IPv4Address, tuple[str, IPv4Address]],
    p2p_nbr_addr: dict[tuple, IPv4Address] | None = None,
    iface_by_ifindex: dict[int, str] | None = None,
    vlink_nexthops: dict | None = None,
    iface_srlg: dict[str, int] | None = None,
    partition_of: dict | None = None,
) -> SpfTopology | None:
    """Lower the area LSDB to the SPF vertex/edge model.

    iface_by_addr: our interface address -> ifname (for transit networks we
    attach to).  iface_by_nbr: neighbor router-id -> (ifname, nbr addr)
    for p2p adjacencies (direct next-hop resolution); with
    ``p2p_nbr_addr`` {(ifname, nbr_rid): addr} parallel p2p links each
    resolve through their own interface (the per-link link_data of our
    router LSA selects the interface).
    MaxAge LSAs are excluded (RFC 2328 §16.1 note).
    """
    routers: list[IPv4Address] = []
    networks: list[IPv4Address] = []  # keyed by DR interface address (lsid)
    rlsa: dict[IPv4Address, LsaRouter] = {}
    nlsa: dict[IPv4Address, LsaNetwork] = {}
    for e in lsdb.all():
        if e.current_age(now) >= 3600:
            continue
        lsa = e.lsa
        if lsa.type == LsaType.ROUTER:
            rlsa[lsa.adv_rtr] = lsa.body
            routers.append(lsa.adv_rtr)
        elif lsa.type == LsaType.NETWORK:
            nlsa[lsa.lsid] = lsa.body
            networks.append(lsa.lsid)

    if router_id not in rlsa:
        return None  # no self LSA yet (reference: SpfRootNotFound)

    # Vertex ordering contract: Network < Router (ospfv2/spf.rs:42-45).
    networks.sort()
    routers.sort()
    network_index = {a: i for i, a in enumerate(networks)}
    router_index = {r: len(networks) + i for i, r in enumerate(routers)}
    n = len(networks) + len(routers)
    is_router = np.zeros(n, bool)
    is_router[len(networks) :] = True

    src, dst, cost = [], [], []
    # Per-edge link_data for edges out of the root (parallel p2p links
    # each resolve to their own interface); vlink edges tracked apart.
    root_edge_data: dict[int, IPv4Address] = {}
    root_vlink_edges: dict[int, IPv4Address] = {}  # edge -> nbr router id
    for rid, body in rlsa.items():
        u = router_index[rid]
        for link in body.links:
            if link.link_type in (
                RouterLinkType.POINT_TO_POINT,
                RouterLinkType.VIRTUAL_LINK,
            ):
                # Virtual links are router-router edges whose cost is the
                # transit-area distance (§15); for SPF they behave as p2p.
                v = router_index.get(link.id)
                if v is not None:
                    if rid == router_id:
                        if link.link_type == RouterLinkType.VIRTUAL_LINK:
                            root_vlink_edges[len(src)] = link.id
                        else:
                            root_edge_data[len(src)] = link.data
                    src.append(u), dst.append(v), cost.append(link.metric)
            elif link.link_type == RouterLinkType.TRANSIT_NETWORK:
                v = network_index.get(link.id)
                if v is not None:
                    if rid == router_id:
                        root_edge_data[len(src)] = link.data
                    src.append(u), dst.append(v), cost.append(link.metric)
    for dr_addr, body in nlsa.items():
        u = network_index[dr_addr]
        for rid in body.attached:
            v = router_index.get(rid)
            if v is not None:
                src.append(u), dst.append(v), cost.append(0)

    # Mutual-link filter (bidirectionality check, spf.rs:653-664) applied
    # here with index tracking so root-edge link_data survives filtering.
    keep_mask = _oracle_mutual_keep_mask(
        np.array(src, np.int32), np.array(dst, np.int32)
    )
    keep = [i for i in range(len(src)) if keep_mask[i]]
    remap = {old: new for new, old in enumerate(keep)}
    root_edge_data = {
        remap[i]: d for i, d in root_edge_data.items() if i in remap
    }
    root_vlink_edges = {
        remap[i]: r for i, r in root_vlink_edges.items() if i in remap
    }
    topo = Topology(
        n_vertices=n,
        is_router=is_router,
        edge_src=np.array([src[i] for i in keep], np.int32).reshape(-1),
        edge_dst=np.array([dst[i] for i in keep], np.int32).reshape(-1),
        edge_cost=np.array([cost[i] for i in keep], np.int32).reshape(-1),
        root=router_index[router_id],
    )

    # Next-hop atoms: edges out of the root, and edges out of root-adjacent
    # transit networks (the hops==0 direct-calculation cases).
    atoms: list[NexthopAtom] = []
    atom_ids = np.full(topo.n_edges, -1, np.int32)
    root = topo.root
    root_nets: set[int] = set()
    self_body = rlsa[router_id]
    # Map vertex index -> transit our-iface (for root->net edges).
    net_if: dict[int, str] = {}
    for link in self_body.links:
        if link.link_type == RouterLinkType.TRANSIT_NETWORK:
            vi = network_index.get(link.id)
            if vi is not None:
                ifname = iface_by_addr.get(link.data)
                if ifname is not None:
                    net_if[vi] = ifname
    for e in range(topo.n_edges):
        if topo.edge_src[e] == root:
            v = int(topo.edge_dst[e])
            if e in root_vlink_edges:
                # Virtual link: next hops borrowed from the transit area's
                # path to the vlink neighbor (§16.1).
                nbr_rid = root_vlink_edges[e]
                expand = (vlink_nexthops or {}).get(nbr_rid)
                if expand:
                    atom_ids[e] = len(atoms)
                    atoms.append(NexthopAtom(None, None, expand))
                continue
            link_data = root_edge_data.get(e)
            if is_router[v]:
                # p2p neighbor: the link's own interface (parallel links
                # each get their own atom), neighbor addr per interface.
                # Unnumbered links carry the MIB ifIndex in link_data
                # (RFC 2328 A.4.2) instead of an address.
                rid = routers[v - len(networks)]
                ifname = (
                    iface_by_addr.get(link_data)
                    if link_data is not None
                    else None
                )
                if (
                    ifname is None
                    and link_data is not None
                    and iface_by_ifindex is not None
                    and int(link_data) < 0x1000000  # 0.x.y.z: never an addr
                ):
                    ifname = iface_by_ifindex.get(int(link_data))
                addr = None
                if ifname is not None and p2p_nbr_addr is not None:
                    addr = p2p_nbr_addr.get((ifname, rid))
                if ifname is not None and addr is not None:
                    atom_ids[e] = len(atoms)
                    atoms.append(NexthopAtom(ifname, addr))
                else:
                    hop = iface_by_nbr.get(rid)
                    if hop is not None:
                        atom_ids[e] = len(atoms)
                        atoms.append(NexthopAtom(hop[0], hop[1]))
            else:
                root_nets.add(v)
                # Directly-attached transit network: next hop is the
                # outgoing interface itself (no gateway address).
                ifname = (
                    iface_by_addr.get(link_data)
                    if link_data is not None
                    else None
                )
                if ifname is not None:
                    atom_ids[e] = len(atoms)
                    atoms.append(NexthopAtom(ifname, None))
        # second pass below needs root_nets complete
    for e in range(topo.n_edges):
        u = int(topo.edge_src[e])
        v = int(topo.edge_dst[e])
        if u in root_nets and is_router[v] and v != root:
            # Destination router's address on that network = the link.data
            # of ITS transit link pointing at this network's DR address.
            rid = routers[v - len(networks)]
            dr_addr = networks[u]
            body = rlsa.get(rid)
            ifname = net_if.get(u)
            if body is None or ifname is None:
                continue
            for link in body.links:
                if (
                    link.link_type == RouterLinkType.TRANSIT_NETWORK
                    and link.id == dr_addr
                ):
                    atom_ids[e] = len(atoms)
                    atoms.append(NexthopAtom(ifname, link.data))
                    break

    topo.edge_direct_atom = atom_ids
    if iface_srlg:
        # Interface fast-reroute SRLG config -> the edge_srlg seam the
        # FRR policy masks consume (srlg_disjoint).
        apply_interface_srlg(
            topo, [a.ifname for a in atoms], iface_srlg
        )
    if partition_of:
        # Hierarchical partition hint (ISSUE 15): per-router group
        # labels (config/topology-design groupings the operator knows —
        # PoPs, rings, sub-area clusters); a transit network rides the
        # lowest-labeled attached router so zero-cost net->rtr edges
        # stay intra-partition wherever the grouping allows.
        groups: list = []
        for dr_addr in networks:
            att = [
                partition_of[r]
                for r in nlsa[dr_addr].attached
                if r in partition_of
            ]
            groups.append(min(att) if att else None)
        for rid in routers:
            groups.append(partition_of.get(rid))
        apply_partition_hint(topo, groups)
    topo.touch()
    return SpfTopology(topo, atoms, router_index, network_index)


# -- an area that events are driven through


def _rid(i: int) -> IPv4Address:
    return IPv4Address((10 << 24) | (i + 1))


def _addr(a: int, b: int, k: int = 0) -> IPv4Address:
    """Router ``a``'s interface address on its ``k``-th link to ``b``."""
    return IPv4Address((192 << 24) | (a << 16) | (b << 8) | (k + 1))


def _dr(j: int) -> IPv4Address:
    return IPv4Address((172 << 24) | (16 << 16) | (j << 8) | 1)


ROOT = 0
UNNUMBERED_IFINDEX = 7


class Area:
    """The DUT (router 0) and its area: a ring with chords, two parallel
    p2p links and an unnumbered one at the root, a virtual link, a
    transit network the root is on and one it is not, stubs, and a
    summary-LSA between the router-LSAs.  ``links[i]`` is what router
    ``i`` says; ``install`` puts it into the LSDB."""

    def __init__(self, seed: int, n: int = 14):
        self.rng = np.random.default_rng(seed)
        self.lsdb = Lsdb()
        self.now = 1000.0
        self.seq = 0
        self.n = n
        self.links: dict[int, list[RouterLink]] = {i: [] for i in range(n)}
        self.nets: dict[int, tuple[int, list[int]]] = {}
        for i in range(n):
            self.connect(i, (i + 1) % n)
        for _ in range(n // 2):
            a, b = (int(x) for x in self.rng.choice(n, 2, replace=False))
            self.connect(a, b)
        self.connect(ROOT, 1, k=1)  # parallel to the ring's 0-1
        # Unnumbered: link data is the MIB ifIndex (RFC 2328 A.4.2).
        self.links[ROOT].append(RouterLink(
            RouterLinkType.POINT_TO_POINT, _rid(2),
            IPv4Address(UNNUMBERED_IFINDEX), 4,
        ))
        self.links[2].append(RouterLink(
            RouterLinkType.POINT_TO_POINT, _rid(ROOT), _addr(2, ROOT, 2), 4,
        ))
        for a, b in ((ROOT, 5), (5, ROOT)):
            self.links[a].append(RouterLink(
                RouterLinkType.VIRTUAL_LINK, _rid(b), _addr(a, b, 3), 9,
            ))
        self.transit(0, dr=3, attached=[ROOT, 3, 4])
        self.transit(1, dr=6, attached=[6, 7, 8])
        for i in range(0, n, 3):
            self.links[i].append(RouterLink(
                RouterLinkType.STUB_NETWORK,
                IPv4Address((10 << 24) | (1 << 16) | (i << 8)),
                IPv4Address("255.255.255.0"), 1,
            ))
        for i in range(n):
            self.install(i)
            if i == n // 2:
                self.install_summary()
        for j in self.nets:
            self.install_net(j)

    # the model
    def connect(self, a: int, b: int, k: int = 0) -> None:
        m = int(self.rng.integers(1, 20))
        self.links[a].append(RouterLink(
            RouterLinkType.POINT_TO_POINT, _rid(b), _addr(a, b, k), m))
        self.links[b].append(RouterLink(
            RouterLinkType.POINT_TO_POINT, _rid(a), _addr(b, a, k), m))

    def transit(self, j: int, dr: int, attached: list[int]) -> None:
        self.nets[j] = (dr, list(attached))
        for i in attached:
            self.links[i].append(RouterLink(
                RouterLinkType.TRANSIT_NETWORK, _dr(j),
                IPv4Address(int(_dr(j)) + 1 + i), 3,
            ))

    # the LSDB
    def router_key(self, i: int) -> LsaKey:
        return LsaKey(LsaType.ROUTER, _rid(i), _rid(i))

    def install(self, i: int, age: int = 0) -> None:
        self.seq += 1
        self.lsdb.install(Lsa(
            age, Options.E, LsaType.ROUTER, _rid(i), _rid(i), self.seq,
            LsaRouter(RouterFlags(0), list(self.links[i])),
        ), self.now)

    def install_net(self, j: int, age: int = 0, adv=None) -> None:
        dr, attached = self.nets[j]
        self.seq += 1
        self.lsdb.install(Lsa(
            age, Options.E, LsaType.NETWORK, _dr(j),
            _rid(dr) if adv is None else adv, self.seq,
            LsaNetwork(IPv4Address("255.255.255.0"),
                       [_rid(i) for i in attached]),
        ), self.now)

    def install_summary(self) -> None:
        self.seq += 1
        self.lsdb.install(Lsa(
            0, Options.E, LsaType.SUMMARY_NETWORK,
            IPv4Address("10.9.0.0"), _rid(1), self.seq,
            LsaSummary(IPv4Address("255.255.0.0"), 5),
        ), self.now)

    def flush(self, key: LsaKey) -> None:
        """As ``OspfInstance._flush_self_lsa``: a MaxAge copy."""
        import copy

        lsa = copy.copy(self.lsdb.get(key).lsa)
        lsa.age = 3600
        self.lsdb.install(lsa, self.now)

    # what the instance hands build_topology beside the LSDB
    def args(self, *, srlg: bool = False, partition: bool = False) -> dict:
        iface_by_addr, iface_by_nbr, p2p_nbr_addr = {}, {}, {}
        for k, link in enumerate(self.links[ROOT]):
            if link.link_type == RouterLinkType.STUB_NETWORK:
                continue
            ifname = f"e{k}"
            if int(link.data) >= 0x1000000:
                iface_by_addr[link.data] = ifname
            else:
                ifname = "un0"
            if link.link_type == RouterLinkType.POINT_TO_POINT:
                nbr = IPv4Address(int(link.id) + (50 << 16) + k)
                iface_by_nbr[link.id] = (ifname, nbr)
                if k % 2 == 0 or ifname == "un0":  # the others: iface_by_nbr
                    p2p_nbr_addr[(ifname, link.id)] = nbr
        return dict(
            router_id=_rid(ROOT),
            now=self.now,
            iface_by_addr=iface_by_addr,
            iface_by_nbr=iface_by_nbr,
            p2p_nbr_addr=p2p_nbr_addr,
            iface_by_ifindex={UNNUMBERED_IFINDEX: "un0"},
            vlink_nexthops={
                _rid(5): frozenset({RouteNexthop("e0", _addr(1, ROOT))})
            },
            iface_srlg=(
                {"e0": srlg_bits((1, 2)), "un0": srlg_bits((33,)),
                 "e1": srlg_bits((4,))} if srlg else None
            ),
            partition_of=(
                {_rid(i): f"pop{i % 3}" for i in range(self.n + 4)}
                if partition else None
            ),
        )


# -- events: each changes the LSDB (or the clock) the way the instance can


def reinstall_changed(a: Area) -> None:
    i = int(a.rng.integers(a.n))
    k = int(a.rng.integers(len(a.links[i])))
    old = a.links[i][k]
    a.links[i][k] = RouterLink(
        old.link_type, old.id, old.data, old.metric % 40 + 1)
    a.install(i)


def refresh(a: Area) -> None:
    a.install(int(a.rng.integers(a.n)))


def one_sided_loss(a: Area) -> None:
    i = int(a.rng.integers(1, a.n))
    if len(a.links[i]) > 1:
        a.links[i].pop(int(a.rng.integers(len(a.links[i]))))
    a.install(i)


def remove_and_install(a: Area) -> None:
    i = int(a.rng.integers(1, a.n))
    a.lsdb.remove(a.router_key(i))
    a.install(i)  # now last in the LSDB's order


def flush_router(a: Area) -> None:
    a.flush(a.router_key(int(a.rng.integers(1, a.n))))


def flush_network(a: Area) -> None:
    j = int(a.rng.integers(len(a.nets)))
    a.flush(LsaKey(LsaType.NETWORK, _dr(j), _rid(a.nets[j][0])))


def tick(a: Area) -> None:
    a.now += float(a.rng.uniform(0.0, 40.0))


def new_router(a: Area) -> None:
    i, a.n = a.n, a.n + 1
    a.links[i] = []
    for b in {int(x) for x in a.rng.integers(0, i, 2)}:
        a.connect(i, b)
        a.install(b)
    a.install(i)


def summary_comes_and_goes(a: Area) -> None:
    key = LsaKey(LsaType.SUMMARY_NETWORK, IPv4Address("10.9.0.0"), _rid(1))
    if a.lsdb.get(key) is None:
        a.install_summary()
    else:
        a.lsdb.remove(key)


BACKGROUND = (
    reinstall_changed, refresh, one_sided_loss, remove_and_install,
    flush_router, flush_network, tick, summary_comes_and_goes,
)


def _aged_out(a: Area):
    """An LSA passes 3,600 s between two runs with no install."""
    i = int(a.rng.integers(1, a.n))
    a.install(i, age=3500)
    yield
    a.now += 99.5  # 3599.5: still there
    yield
    a.now += 0.5  # 3600: gone, and nothing was installed
    yield
    a.install_net(0, age=3590)
    a.now += 30.0
    yield


def _cleared(a: Area):
    a.lsdb.entries.clear()  # as the instance's teardown writes it
    yield
    for i in range(a.n):  # and the area is learnt again
        a.install(i)
        if i % 5 == 0:
            yield
    a.install_net(0)
    yield


def _no_self_lsa(a: Area):
    a.lsdb.remove(a.router_key(ROOT))
    yield  # None
    reinstall_changed(a)
    yield  # still None, and the lowering kept up
    a.install(ROOT)
    yield  # recovers
    a.flush(a.router_key(ROOT))
    yield  # None again: a MaxAge self LSA is no self LSA
    a.install(ROOT)
    yield


def _duplicate_ids(a: Area):
    """Two live LSAs of one vertex id: a network-LSA of the same DR
    address from another advertising router, a router-LSA under a second
    link-state id.  A dict keyed by id keeps the last body at the first
    one's place, and both count as vertices."""
    a.install_net(0, adv=_rid(4))
    yield
    a.seq += 1
    a.lsdb.install(Lsa(
        0, Options.E, LsaType.ROUTER, IPv4Address("10.99.0.1"), _rid(3),
        a.seq, LsaRouter(RouterFlags(0), list(a.links[3][:2])),
    ), a.now)
    yield
    a.install(3)
    yield
    a.flush(a.router_key(3))
    yield


def _once(event):
    def steps(a: Area):
        event(a)
        yield
    return steps


CASES = {
    "reinstall-changed-body": _once(reinstall_changed),
    "refresh-same-body": _once(refresh),
    "link-lost-on-one-side": _once(one_sided_loss),
    "removed-and-installed-again": _once(remove_and_install),
    "flush-to-maxage": _once(flush_router),
    "network-lsa-flushed": _once(flush_network),
    "aged-out-with-no-install": _aged_out,
    "entries-clear": _cleared,
    "new-router": _once(new_router),
    "no-self-lsa": _no_self_lsa,
    "other-lsa-types-between": _once(summary_comes_and_goes),
    "duplicate-vertex-ids": _duplicate_ids,
}


def _same(got: SpfTopology | None, want: SpfTopology | None) -> None:
    if want is None:
        assert got is None
        return
    assert got is not None
    g, w = got.topo, want.topo
    assert (g.n_vertices, g.root) == (w.n_vertices, w.root)
    for name in (
        "is_router", "edge_src", "edge_dst", "edge_cost",
        "edge_direct_atom", "edge_srlg",
    ):
        a, b = getattr(g, name), getattr(w, name)
        assert a.dtype == b.dtype and np.array_equal(a, b), name
    if w.partition_hint is None:
        assert g.partition_hint is None
    else:
        assert g.partition_hint.dtype == w.partition_hint.dtype
        assert np.array_equal(g.partition_hint, w.partition_hint)
    assert got.atoms == want.atoms
    assert got.router_index == want.router_index
    assert got.network_index == want.network_index
    assert list(got.router_index) == list(want.router_index)
    assert list(got.network_index) == list(want.network_index)
    assert (g.generation, g.delta_base) == (w.generation, w.delta_base)


@pytest.mark.parametrize("options", ["plain", "srlg+partition"])
@pytest.mark.parametrize("case", CASES)
def test_kept_lowering_equals_the_oracle_after_every_event(case, options):
    """The named event amid seeded background events; after every one the
    kept lowering, a fresh lowering and the oracle agree."""
    policy = options != "plain"
    seen_none = seen_atoms = 0
    for seed in (1, 2, 3):
        area = Area(seed)
        kept = LoweredLsdb()

        def check():
            nonlocal seen_none, seen_atoms
            args = area.args(srlg=policy, partition=policy)
            want = _oracle_build_topology(area.lsdb, **args)
            _same(kept.build_topology(area.lsdb, **args), want)
            _same(build_topology(area.lsdb, **args), want)
            seen_none += want is None
            if want is not None:
                seen_atoms = max(seen_atoms, len(want.atoms))
                # One body per router vertex, in vertex order: of two
                # live LSAs of one router the later.
                nn = int((~want.topo.is_router).sum())
                assert len(kept.router_bodies) == want.topo.n_vertices - nn
                for rid, v in want.router_index.items():
                    assert kept.router_bodies[v - nn] is [
                        e.lsa.body
                        for e in area.lsdb.all()
                        if e.lsa.type == LsaType.ROUTER
                        and e.lsa.adv_rtr == rid
                        and e.current_age(area.now) < 3600
                    ][-1]

        check()
        for _ in range(5):
            BACKGROUND[int(area.rng.integers(len(BACKGROUND)))](area)
            check()
        for _ in CASES[case](area):
            check()
        for _ in range(5):
            BACKGROUND[int(area.rng.integers(len(BACKGROUND)))](area)
            check()
    # The area is not a trivial one: the root resolves p2p, parallel,
    # unnumbered, virtual-link and transit next hops.
    assert seen_atoms >= 6
    assert (seen_none > 0) == (case in ("entries-clear", "no-self-lsa"))


def test_first_topology_of_the_area_has_every_kind_of_atom():
    area = Area(1)
    st = build_topology(area.lsdb, **area.args())
    ifnames = {a.ifname for a in st.atoms}
    assert "un0" in ifnames and None in ifnames  # unnumbered; the vlink
    assert sum(a.addr is None and a.ifname is not None for a in st.atoms) == 1
    assert (st.topo.edge_direct_atom >= 0).sum() == len(st.atoms)
    # One-sided links never became edges; both transit networks did.
    assert len(st.network_index) == 2

    def between(st, a, b):
        u, v = st.router_index[_rid(a)], st.router_index[_rid(b)]
        t = st.topo
        return (int(((t.edge_src == u) & (t.edge_dst == v)).sum()),
                int(((t.edge_src == v) & (t.edge_dst == u)).sum()))

    assert between(st, 9, 10) >= (1, 1)
    area.links[9] = [l for l in area.links[9] if l.id != _rid(10)]
    area.install(9)  # router 10 still says it has the link
    after = build_topology(area.lsdb, **area.args())
    assert between(after, 9, 10) == (0, 0)


def test_previous_topology_is_never_written_and_indices_are_kept_objects():
    """The previous run's ``SpfTopology`` is DeltaPath's base and the
    parity reservoir's sample: the next run leaves its arrays as they
    were, byte for byte.  The index dicts are last run's objects while
    the vertex set is last run's."""
    area = Area(5)
    kept = LoweredLsdb()
    first = kept.build_topology(area.lsdb, **area.args(srlg=True))
    fields = (
        "is_router", "edge_src", "edge_dst", "edge_cost",
        "edge_direct_atom", "edge_srlg",
    )
    frozen = {f: getattr(first.topo, f).tobytes() for f in fields}
    reinstall_changed(area)
    one_sided_loss(area)
    second = kept.build_topology(area.lsdb, **area.args(srlg=True))
    assert {f: getattr(first.topo, f).tobytes() for f in fields} == frozen
    assert second.router_index is first.router_index
    assert second.network_index is first.network_index
    for f in fields:
        assert not np.shares_memory(
            getattr(first.topo, f), getattr(second.topo, f)
        ), f
    assert second.topo.n_edges < first.topo.n_edges

    new_router(area)
    frozen2 = {f: getattr(second.topo, f).tobytes() for f in fields}
    third = kept.build_topology(area.lsdb, **area.args(srlg=True))
    assert {f: getattr(second.topo, f).tobytes() for f in fields} == frozen2
    assert third.router_index is not second.router_index
    assert len(third.router_index) == len(second.router_index) + 1
    assert third.network_index == second.network_index
    flush_network(area)
    fourth = kept.build_topology(area.lsdb, **area.args(srlg=True))
    assert len(fourth.network_index) == len(third.network_index) - 1
    assert fourth.topo.is_router.sum() == third.topo.is_router.sum()


def _lsas_counted() -> dict:
    snap = telemetry.snapshot(FAMILY)
    return {
        path: sum(v for k, v in snap.items() if f"path={path}" in k)
        for path in ("lowered", "reused")
    }


def test_counter_says_how_many_entries_each_call_lowered():
    area = Area(7)
    kept = LoweredLsdb()
    n = len(area.lsdb.entries)

    def call() -> tuple[int, int]:
        before = _lsas_counted()
        kept.build_topology(area.lsdb, **area.args())
        after = _lsas_counted()
        return (after["lowered"] - before["lowered"],
                after["reused"] - before["reused"])

    assert call() == (n, 0)  # the first lowering: every entry
    assert call() == (0, n)  # nothing installed: nothing lowered
    refresh(area)
    reinstall_changed(area)
    lowered, reused = call()
    assert lowered in (1, 2) and lowered + reused == n  # one LSA twice?
    area.lsdb.remove(area.router_key(1))  # the second entry of the LSDB
    assert call() == (0, n - 1)  # a removal in the middle lowers nothing
    area.install(1)
    assert call() == (1, n - 1)  # an append lowers what was appended


# -- the per-layer metric that reads the counter


def test_metric_file_reads_the_lowered_share_of_the_counter():
    spec = json.loads(
        (REPO / "benchmark/layer_metrics/storm_topology_relower_share.json")
        .read_text()
    )
    assert spec["reader"] == "counter_ratio"
    assert spec["args"] == {
        "family": FAMILY, "label": "path=lowered", "of": {"family": FAMILY},
    }
    top = json.loads((REPO / "BENCHMARK.json").read_text())
    [entry] = [
        m for m in top["per_layer"]
        if m["name"] == "storm_topology_relower_share"
    ]
    assert entry == {
        "name": "storm_topology_relower_share", "unit": spec["unit"],
        "better": spec["better"], "source": spec["source"],
        "layer": spec["layer"], "moves": spec["moves"],
        # the two OSPFv2 storm cells; later cells are appended (PR 31)
        "workloads": [
            "backbone10k-flapstorm", "isp-zoo-storm", *entry["workloads"][2:]
        ],
    } and (spec["unit"], spec["better"], spec["source"], spec["layer"],
           spec["moves"]) == (
        "%", "lower", "program_counter", "protocol instance",
        "trigger_fib_p50_ms",
    )


def test_traced_storm_rehearsal_reads_the_relower_share():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO))
    env.pop("PYTHONHASHSEED", None)
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", "tiny-storm",
         "--seed", "2147483693", "--seconds", "2", "--trace", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 3, proc.stderr[-2000:]
    report = json.loads(proc.stderr.strip().splitlines()[-1])
    assert "storm_topology_relower_share" in report["counts"]["metrics_read"]
    assert report["metrics"] == {} and report["failed"] == 0
