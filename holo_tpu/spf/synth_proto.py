"""Protocol-marshaled benchmark topologies (BASELINE.md configs 2+3).

Unlike :mod:`holo_tpu.spf.synth` (which builds ``Topology`` objects
directly), these builders populate REAL protocol instances — an OSPFv3
multi-area LSDB of ``LsaRouterV3``/Intra-Area-Prefix LSAs, and IS-IS
L1/L2 LSP databases — and extract the benchmark topologies through each
protocol's own SPF marshaling path (``OspfV3Instance._area_spf``,
``IsisInstance.run_spf``).  What a caller then runs on the shared
engine is exactly what the protocols dispatch in production
(reference parity: the per-protocol graph/vertex-ordering rules live in
the marshal, not the engine).
"""

from __future__ import annotations

from ipaddress import IPv4Address, IPv4Network, IPv6Address, IPv6Network

import numpy as np


class _CaptureBackend:
    """Delegates compute() while recording every dispatched Topology."""

    def __init__(self, inner):
        self.inner = inner
        self.name = inner.name
        self.topos: list = []

    def compute(self, topo, multipath_k: int = 1):
        self.topos.append(topo)
        return self.inner.compute(topo, multipath_k=multipath_k)


def _spanning_edges(n: int, extra: int, rng) -> list[tuple[int, int, int]]:
    """Connected random graph: tree + ``extra`` chords, uniform-ish
    costs (the fat-tree analog at arbitrary n)."""
    edges = []
    for v in range(1, n):
        u = int(rng.integers(0, v))
        edges.append((u, v, 1 + int(rng.integers(0, 16))))
    for _ in range(extra):
        u = int(rng.integers(0, n))
        v = int(rng.integers(0, n))
        if u != v:
            edges.append((u, v, 1 + int(rng.integers(0, 16))))
    return edges


def ospfv3_multiarea_topologies(
    n_routers: int = 10_000, n_areas: int = 4, seed: int = 0
) -> list:
    """BASELINE config 2 at the parity tests' size: one ABR instance
    attached to ``n_areas`` random areas totalling ``n_routers`` routers;
    returns the per-area ``Topology`` objects produced by the instance's
    own ``_area_spf`` marshal.

    The DEPLOYMENT for ``configs[1]`` is ``benchmark/areanet.py``
    (configuration ``ospfv3-multiarea-10k``: four k = 44 fat-tree halls
    and a backbone behind shared border routers, 9,660 routers, with
    prefixes, ranges, a RIB and events).  This builder stays for
    ``tests/test_synth_proto.py``, which runs it at 1/25 of that size;
    it is not a second 10k generator and nothing measures it."""
    from holo_tpu.protocols.ospf import packet_v3 as P
    from holo_tpu.protocols.ospf.instance_v3 import (
        OspfV3Instance,
        V3IfConfig,
    )
    from holo_tpu.protocols.ospf.neighbor import Neighbor, NsmState
    from holo_tpu.spf.backend import ScalarSpfBackend
    from holo_tpu.utils.runtime import EventLoop, VirtualClock

    rng = np.random.default_rng(seed)
    loop = EventLoop(clock=VirtualClock())
    inst = OspfV3Instance(
        name="bench-v3", router_id=IPv4Address("0.0.0.1"), netio=None
    )
    loop.register(inst)
    capture = _CaptureBackend(ScalarSpfBackend())
    inst.backend = capture

    per_area = n_routers // n_areas
    now = loop.clock.now()
    for a in range(n_areas):
        area_id = IPv4Address(a)
        iface = inst.add_interface(
            f"be{a}", V3IfConfig(cost=1, area_id=area_id),
            IPv6Address(f"fe80::a:{a + 1}"), [],
        )
        iface.up = True
        area = inst.areas[area_id]
        # Router ids: root is 0.0.0.1; area routers start at base+1.
        base = (a + 1) << 16
        rids = [IPv4Address(base + i + 1) for i in range(per_area)]

        def rl(nbr_rid, metric, ifid=1, nbr_ifid=1):
            return P.RouterLinkV3(
                link_type=P.RouterLinkType.POINT_TO_POINT,
                metric=metric, iface_id=ifid, nbr_iface_id=nbr_ifid,
                nbr_router_id=nbr_rid,
            )

        links: dict[IPv4Address, list] = {rid: [] for rid in rids}
        for u, v, cost in _spanning_edges(per_area, per_area // 2, rng):
            links[rids[u]].append(rl(rids[v], cost))
            links[rids[v]].append(rl(rids[u], cost))
        # The ABR (root) attaches to the area's first router.
        root_links = [rl(rids[0], 1)]
        links[rids[0]].append(rl(inst.router_id, 1))
        # Adjacency state for the root's next-hop atom.
        iface.neighbors[rids[0]] = Neighbor(
            router_id=rids[0],
            src=IPv6Address(f"fe80::b:{a + 1}"),
            state=NsmState.FULL,
            iface_id=1,
        )

        def install(ltype, lsid, adv, body):
            lsa = P.Lsa(1, ltype, IPv4Address(lsid), adv, -1000, body)
            area.lsdb.install(lsa, now)

        install(P.LsaType.ROUTER, 0, inst.router_id,
                P.LsaRouterV3(links=root_links))
        for rid in rids:
            install(P.LsaType.ROUTER, 0, rid,
                    P.LsaRouterV3(links=links[rid]))
            install(
                P.LsaType.INTRA_AREA_PREFIX, 1, rid,
                P.LsaIntraAreaPrefix(
                    ref_type=int(P.LsaType.ROUTER), ref_lsid=IPv4Address(0),
                    ref_adv_rtr=rid,
                    prefixes=[
                        (IPv6Network((int(rid) << 64) | (0x2001 << 112),
                                     64), 1)
                    ],
                ),
            )

    for area in inst.areas.values():
        out = inst._area_spf(area)
        assert out is not None, "marshal produced no topology"
    topos = capture.topos
    assert len(topos) == n_areas
    return topos


def isis_l1l2_topologies(
    n_l2: int = 9_000, n_l1: int = 1_000, ecmp_width: int = 64,
    seed: int = 0,
) -> list:
    """BASELINE config 3: IS-IS L1/L2 at 10k nodes with a
    ``ecmp_width``-way equal-cost segment at the L2 root; returns the
    [L1, L2] ``Topology`` objects from ``IsisInstance.run_spf``'s own
    marshal, asserting the root really extracts ``ecmp_width`` distinct
    next hops."""
    from holo_tpu.ops.graph import INF
    from holo_tpu.protocols.isis.instance import (
        Adjacency,
        AdjacencyState,
        IsisIfConfig,
        IsisInstance,
        LspEntry,
    )
    from holo_tpu.protocols.isis.packet import (
        ExtIpReach,
        ExtIsReach,
        Lsp,
        LspId,
    )
    from holo_tpu.spf.backend import ScalarSpfBackend
    from holo_tpu.utils.runtime import EventLoop, VirtualClock

    rng = np.random.default_rng(seed + 1)

    def sysid(i: int) -> bytes:
        return i.to_bytes(6, "big")

    def build_level(level: int, n: int, ecmp: int) -> tuple:
        loop = EventLoop(clock=VirtualClock())
        inst = IsisInstance(
            f"bench-l{level}", sysid(1), netio=None, level=level
        )
        loop.register(inst)
        capture = _CaptureBackend(ScalarSpfBackend())
        inst.backend = capture
        now = loop.clock.now()

        # Edge list over router indices 1..n (router 1 is the root).
        # ECMP segment: root -> spines (2..ecmp+1) -> core (ecmp+2),
        # all metric 1, so everything behind the core is ecmp-way.
        edges: list[tuple[int, int, int]] = []
        core = ecmp + 2
        for s in range(2, ecmp + 2):
            edges.append((1, s, 1))
            edges.append((s, core, 1))
        for v in range(core + 1, n + 1):
            u = core if v == core + 1 else int(rng.integers(core, v))
            edges.append((u, v, 1 + int(rng.integers(0, 16))))
        nbrs: dict[int, list[tuple[int, int]]] = {}
        for u, v, c in edges:
            nbrs.setdefault(u, []).append((v, c))
            nbrs.setdefault(v, []).append((u, c))

        for i in range(1, n + 1):
            tlvs = {
                "ext_is_reach": [
                    ExtIsReach(sysid(j) + b"\x00", c)
                    for j, c in nbrs.get(i, [])
                ],
                "ext_ip_reach": [
                    ExtIpReach(IPv4Network((10 << 24) | (i << 8), 32), 1)
                ],
            }
            lsp = Lsp(level, 1200, LspId(sysid(i)), 5, tlvs=tlvs)
            inst.lsdb[lsp.lsp_id] = LspEntry(lsp, now)

        # Root adjacencies: one p2p circuit per spine (the 64-way fan).
        for s in range(2, ecmp + 2):
            ifname = f"e{s}"
            inst.add_interface(
                ifname, IsisIfConfig(metric=1),
                IPv4Address((172 << 24) | (s << 8) | 1),
                IPv4Network((172 << 24) | (s << 8), 30),
            )
            iface = inst.interfaces[ifname]
            iface.adj = Adjacency(
                sysid=sysid(s), state=AdjacencyState.UP,
                addr=IPv4Address((172 << 24) | (s << 8) | 2),
            )
        inst.run_spf()
        assert len(capture.topos) == 1
        return inst, capture.topos[0]

    l1_inst, l1_topo = build_level(1, n_l1, min(ecmp_width, 8))
    l2_inst, l2_topo = build_level(2, n_l2, ecmp_width)
    # The acceptance criterion: a destination behind the core really
    # resolves to ecmp_width distinct next hops in the instance's OWN
    # route table (64-way ECMP extraction).
    far = IPv4Network((10 << 24) | (n_l2 << 8), 32)
    route = l2_inst.routes.get(far)
    assert route is not None, "far prefix unreachable in L2"
    if l2_inst.max_paths is None or l2_inst.max_paths >= ecmp_width:
        assert len(route[1]) == ecmp_width, (
            f"expected {ecmp_width}-way ECMP, got {len(route[1])}"
        )
    return [l1_topo, l2_topo]
