"""The PoP-structured backbone of ``benchmark/popnet.py`` run as an
SR-MPLS IGP with IP fast reroute armed on the device under test.

On top of ``popnet.build_graph`` (the same graph for the same
``graph_seed``), every router gets:

- a /32 loopback stub in its Router-LSA (``loopback``), and
- an Extended Prefix Opaque LSA (RFC 7684, opaque type 7) carrying a
  Prefix-SID (RFC 8665) for that loopback with SID index equal to its
  router number; the DUT originates its own from its configuration.

The DUT's ``InstanceConfig`` takes ``sr`` (an ``SrConfig`` with the
configuration's SRGB) and ``frr`` (an ``FrrConfig``: LFA, remote LFA,
TI-LFA, the engine, node protection, SRLG-disjoint repairs) as a daemon
would; the instance builds its FRR engine from them.  Each DUT
interface carries the SRLGs of the generator's groups that hold a link
of its peer (``dut_srlgs``): the fibre to a peer enters the conduits
of that peer's long-haul links.

``FrrNet.model()`` is the link model the plain reference
(``benchmark/frrref.py``) reads, and ``generated()`` what the
configuration's ``generated`` block states.
"""

from __future__ import annotations

from ipaddress import IPv4Address, IPv4Network

from holo_tpu.frr.manager import FrrConfig
from holo_tpu.protocols.ospf.packet import (
    Lsa,
    LsaOpaque,
    LsaType,
    Options,
    encode_ext_prefix_sid,
    ext_prefix_lsid,
)
from holo_tpu.utils.sr import PrefixSid, SrConfig, Srgb

from benchmark.popnet import DUT, PopGraph, PopNet, build_graph
from benchmark.stormnet import _rid, _stub

#: 198.18.0.0/15, the benchmarking block of RFC 2544: room for 131,072
LOOPBACK_BASE = (198 << 24) | (18 << 16)
#: the metric of a loopback stub
LOOPBACK_METRIC = 1


def loopback(i: int) -> IPv4Network:
    return IPv4Network((LOOPBACK_BASE + i, 32))


def stub_prefix(i: int) -> IPv4Network:
    """The /24 behind a stub owner (``PopNet._links_of``'s)."""
    return IPv4Network(((172 << 24) | (i << 8), 24))


def dut_srlgs(graph: PopGraph) -> dict[int, tuple[int, ...]]:
    """``{peer: group ids}``: the generator's shared-risk groups that
    hold a link of each DUT peer (none for a peer with no long-haul
    link in a group)."""
    out = {}
    for peer in graph.dut_peers:
        out[peer] = tuple(
            g for g, links in enumerate(graph.srlgs)
            if any(peer in link for link in links)
        )
    return out


def frr_config(cfg: dict) -> FrrConfig:
    """The configuration's ``frr`` block as the instance takes it."""
    frr = cfg["frr"]
    return FrrConfig(
        enabled=frr["enabled"], remote_lfa=frr["remote_lfa"],
        ti_lfa=frr["ti_lfa"], engine=frr["engine"],
        node_protection=frr["node_protection"],
        srlg_disjoint=frr["srlg_disjoint"],
    )


def sr_config(cfg: dict) -> SrConfig:
    """The configuration's ``sr`` block: the SRGB, and the DUT's own
    loopback Prefix-SID (index 0, its router number)."""
    lower, upper = cfg["sr"]["srgb"]
    return SrConfig(
        enabled=True, srgb=Srgb(lower, upper),
        prefix_sids={loopback(DUT): PrefixSid(loopback(DUT), DUT)},
    )


class FrrNet(PopNet):
    """``PopNet`` with loopbacks in every Router-LSA, a Prefix-SID LSA
    per router, and the DUT's SR and FRR configuration set from the
    cell's configuration file before its first armed SPF run."""

    def __init__(self, cfg: dict, spf_backend, rxmt_delay: float):
        super().__init__(cfg["lsdb"], spf_backend, cfg["spf_delay"], rxmt_delay)
        inst = self.inst
        inst.config.sr = sr_config(cfg)
        inst.config.frr = frr_config(cfg)
        self.srlgs = dut_srlgs(self.graph) if cfg["frr"]["srlg_disjoint"] else {}
        for k, peer in enumerate(self.graph.dut_peers):
            iface = self.area.interfaces[f"e{k}"]
            iface.config.srlg = self.srlgs.get(peer, ())
        now = self.loop.clock.now()
        for i in range(self.n_routers):
            if i != DUT:
                self.area.lsdb.install(self._prefix_sid_lsa(i), now)
        inst._schedule_spf()
        self.loop.advance(30.0)

    def _links_of(self, i: int) -> list:
        return [
            *super()._links_of(i), _stub(loopback(i), LOOPBACK_METRIC)
        ]

    def _prefix_sid_lsa(self, i: int) -> Lsa:
        lsa = Lsa(
            age=1, options=Options(0x42), type=LsaType.OPAQUE_AREA,
            lsid=ext_prefix_lsid(0), adv_rtr=_rid(i), seq_no=1,
            body=LsaOpaque(encode_ext_prefix_sid(loopback(i), i, 0)),
        )
        lsa.encode()
        return lsa

    def resync(self) -> int:
        """Send again, in one batch, every Router-LSA whose copy in the
        DUT's LSDB is not what its router says now, and return how many.
        The DUT drops a copy that comes within MinLSArrival of the last
        (RFC 2328 section 13 (5)(a)) and the synthetic neighbours never
        retransmit (a real one would, until acknowledged), so after a
        storm the LSDB can lag the link model the reference reads."""
        from holo_tpu.protocols.ospf.lsdb import MIN_LS_ARRIVAL
        from holo_tpu.protocols.ospf.packet import LsaKey

        stale = []
        for i in range(self.n_routers):
            rid = _rid(i)
            entry = self.area.lsdb.get(LsaKey(LsaType.ROUTER, rid, rid))
            says = self._last_links.get(i) or self._links_of(i)
            if entry is None or list(entry.lsa.body.links) != says:
                stale.append(i)
        if stale:
            self.loop.advance(2 * MIN_LS_ARRIVAL)
            self._deliver([self._router_lsa(i) for i in stale], None)
        return len(stale)

    def hops(self) -> dict:
        """``{peer: (ifname, address)}``: the DUT's next hop to each."""
        return {
            peer: (f"e{k}", self._dut_addr[peer] + 1)
            for k, peer in enumerate(self.graph.dut_peers)
        }

    def model(self, cfg: dict) -> dict:
        """The link model as it stands (links a flap, a cut or a loss
        holds down are out, costs as the ifconfig event left them) in
        the form ``frrref.routes`` reads."""
        n = self.n_routers
        adj = {
            a: {b: c for b, c in peers.items() if self._link_up(a, b)}
            for a, peers in self.adj.items()
        }
        prefixes = [(i, loopback(i), LOOPBACK_METRIC) for i in range(n)]
        prefixes += [(i, stub_prefix(i), 1) for i in sorted(self.stub_owners)]
        frr = cfg["frr"]
        return {
            "n": n, "dut": DUT, "adj": adj, "prefixes": prefixes,
            "hops": self.hops(),
            "srlgs": dict(self.srlgs),
            "sids": {i: i for i in range(n)},
            "srgb": tuple(cfg["sr"]["srgb"]),
            "policy": {
                "remote_lfa": frr["remote_lfa"], "ti_lfa": frr["ti_lfa"],
                "node_protection": frr["node_protection"],
                "srlg_disjoint": frr["srlg_disjoint"],
            },
        }


def generated(cfg: dict) -> dict:
    """What the generator makes from the configuration alone, everything
    up (the configuration's ``generated`` block;
    tests/benchmark/test_frrnet.py holds the file to it): counts of the
    LSDB, the installed routes and their repairs by kind from the plain
    reference, the share of (protected link, protectable destination)
    pairs with a repair, those pairs' repairs by kind, and how many of
    them SRLG-disjointness moved to another repair."""
    from benchmark import frrref

    graph = build_graph(cfg["lsdb"])
    n = graph.n_routers
    net = _Model(graph)
    model = FrrNet.model(net, cfg)
    table = frrref.routes(model)
    kinds: dict = {}
    for _metric, _hops, backups in table.values():
        for kind, _rel, _labels in backups.values():
            kinds[kind] = kinds.get(kind, 0) + 1
    pairs = frrref.repairs(model)
    share = sum(r is not None for r in pairs.values()) / len(pairs)
    # What SRLG-disjointness moves: the pairs whose repair differs
    # from the one the same policy without it picks.
    bare = frrref.repairs(
        dict(model, policy=dict(model["policy"], srlg_disjoint=False))
    )
    pair_kinds: dict = {}
    for r in pairs.values():
        kind = r[0] if r is not None else "none"
        pair_kinds[kind] = pair_kinds.get(kind, 0) + 1
    return {
        "routers": n,
        "links": graph.n_links,
        "stubs": len(graph.stub_owners),
        "loopbacks": n,
        "prefix_sid_lsas": n,
        "dut_interfaces": len(graph.dut_peers),
        "dut_srlg_groups": {
            f"e{k}": list(dut_srlgs(graph)[p])
            for k, p in enumerate(graph.dut_peers)
        } if cfg["frr"]["srlg_disjoint"] else {},
        "rib_routes": len(table),
        "protected_routes": sum(1 for t in table.values() if t[2]),
        "repairs_by_kind": dict(sorted(kinds.items())),
        "protected_pair_share": round(share, 6),
        "pair_repairs_by_kind": dict(sorted(pair_kinds.items())),
        "pairs_moved_by_srlg": sum(pairs[k] != bare[k] for k in pairs),
    }


class _Model:
    """The parts of an ``FrrNet`` that ``model`` reads, built from the
    graph alone with every link up."""

    def __init__(self, graph: PopGraph):
        self.graph = graph
        self.n_routers = graph.n_routers
        self.adj = graph.adj
        self.stub_owners = graph.stub_owners
        self.srlgs = dut_srlgs(graph)
        self._dut_addr = {
            peer: IPv4Address((10 << 24) | (255 << 16) | (k << 8) | 1)
            for k, peer in enumerate(graph.dut_peers)
        }
        self.hops = lambda: FrrNet.hops(self)

    def _link_up(self, a: int, b: int) -> bool:
        return True
