"""IS-IS instance actor: p2p adjacencies, LSP flooding, SPF, routes.

Reference: holo-isis/src/{instance,adjacency,lsdb,spf}.rs.  The SPF lowers
the LSP database to the same generic Topology as OSPF (pseudonodes as
"network" vertices), so the scalar and TPU backends are shared.
"""

from __future__ import annotations

import enum
from dataclasses import dataclass, field
from ipaddress import IPv4Address, IPv4Network

import numpy as np

from holo_tpu import telemetry
from holo_tpu.ops.graph import INF
from holo_tpu.protocols.isis.packet import (
    LSP_MAX_AGE,
    LSP_REFRESH,
    MAX_NARROW_METRIC,
    PREFIX_ATTR_N,
    AdjState3Way,
    ExtIpReach,
    ExtIsReach,
    HelloP2p,
    Lsp,
    LspId,
    P2pAdjState,
    PduType,
    Snp,
    decode_pdu,
)
from holo_tpu.protocols.isis.lowering import LoweredLspdb
from holo_tpu.protocols.ospf.spf_run import (
    KeptDerive,
    SpfDelayFsm,
    SpfFsmState,
    atom_bits,
    legacy_spf_timers,
    link_spf_delta,
    srlg_bits,
)
from holo_tpu.spf.backend import ScalarSpfBackend, SpfBackend
from holo_tpu.telemetry import convergence, profiling
from holo_tpu.utils.bytesbuf import DecodeError
from holo_tpu.utils.netio import NetIo, NetRxPacket
from holo_tpu.utils.runtime import Actor

# Adjacency churn, PDU rx rate, and SPF runs per instance (L1/L2 actors
# carry distinct instance names, so levels separate naturally).
_ISIS_ADJ_TRANSITIONS = telemetry.counter(
    "holo_isis_adj_transitions_total",
    "IS-IS adjacency up/down changes",
    ("instance", "to"),
)
_ISIS_PDUS_RX = telemetry.counter(
    "holo_isis_pdus_rx_total", "IS-IS PDUs received (decoded)", ("instance",)
)
_ISIS_RX_BAD = telemetry.counter(
    "holo_isis_rx_bad_total", "IS-IS PDUs dropped in decode/auth", ("instance",)
)
_ISIS_SPF_RUNS = telemetry.counter(
    "holo_isis_spf_runs_total",
    "IS-IS SPF runs of a level by type: full (the topology lowered, "
    "dispatched or reused) or route-only (prefixes moved and the IS "
    "graph did not: the last run's SPT serves)",
    ("instance", "type"),
)
_LEVEL_SPF = telemetry.counter(
    "holo_isis_level_spf_total",
    "Full SPF runs of an IS-IS level by what the run did with its "
    "topology: dispatched to the backend, or reused from the last run "
    "because nothing the topology is made from had moved",
    ("level", "disposition"),
)
NEXTHOP_WIDTH = telemetry.histogram(
    "holo_isis_nexthop_width",
    "Next hops per route an IS-IS L1/L2 node handed to its route sink "
    "after a level's SPF run (one observation per route handed on)",
    buckets=(1, 2, 4, 8, 16, 32, 64, 128),
)
RIB_DELTA_ROUTES = telemetry.histogram(
    "holo_isis_rib_delta_routes",
    "Routes an IS-IS L1/L2 node handed to its route sink after a "
    "level's SPF run (the whole installable table through route_cb, or "
    "the changed and withdrawn prefixes through route_delta_cb)",
    buckets=(1, 4, 16, 64, 256, 1024, 4096, 16384, 65536),
)

#: where the rank of a route puts an external path: after every internal one
_EXTERNAL_SHIFT = 40
_METRIC_MASK = (1 << _EXTERNAL_SHIFT) - 1


def _less(routes: dict, connected, bare_ok) -> dict:
    """``routes`` less the CONNECTED prefixes and the routes without
    next hops (but for those in ``bare_ok``), in their order."""
    out = dict(routes)
    for prefix in [p for p, r in out.items() if not r[1]]:
        if prefix not in bare_ok:
            del out[prefix]
    for prefix in connected:
        out.pop(prefix, None)
    return out


class IsisRoute(tuple):
    """``(metric, next hops)``, the form an IS-IS route table holds, and
    beside it what ``spf_run.KeptDerive`` reads back: ``prefix``,
    ``dist`` (the rank: the metric, plus ``1 << 40`` for an external
    path, RFC 1195 §3.10.2), ``nexthops``, ``prefix_options`` (whether
    external) and ``vertex`` (the SPT vertex whose offer won; -1 for
    the attached default)."""

    def __new__(cls, prefix, dist, nexthops, options, vertex):
        route = super().__new__(cls, (dist & _METRIC_MASK, nexthops))
        route.prefix, route.dist, route.nexthops = prefix, dist, nexthops
        route.prefix_options, route.vertex = options, vertex
        return route

    def __getnewargs__(self):
        return (
            self.prefix, self.dist, self.nexthops, self.prefix_options,
            self.vertex,
        )


def _sid_flags(psid) -> int:
    """RFC 8667 §2.1 prefix-SID flags from config: no-PHP (P) and
    explicit-null (E)."""
    if psid is None:
        return 0
    flags = 0
    if getattr(psid, "no_php", False):
        flags |= 0x20
    if getattr(psid, "explicit_null", False):
        flags |= 0x10
    return flags


class _McastMac(str):
    """L2 multicast destination stand-in (AllISs); the fabric checks
    ``is_multicast`` like it does for IP groups."""

    is_multicast = True


ALL_ISS = _McastMac("01:80:c2:00:00:14")


class AdjacencyState(enum.Enum):
    DOWN = "down"
    INITIALIZING = "init"
    UP = "up"


MT_IPV6 = 2  # RFC 5120 IPv6 unicast topology id


@dataclass
class IsisIfConfig:
    metric: int = 10
    hello_interval: int = 3  # p2p default (holo uses 3x multiplier)
    hold_multiplier: int = 3
    level: int = 2
    circuit_type: str = "p2p"  # "p2p" | "broadcast"
    priority: int = 64  # DIS election priority (LAN)
    # packet.AuthCtxIsis: hello authentication on this circuit (LSPs/SNPs
    # use the instance-level area auth).
    auth: object = None
    # Passive circuits (loopbacks): prefixes are advertised but no
    # hellos are sent and no adjacencies form.
    passive: bool = False
    loopback: bool = False  # RFC 7794 N-flag eligibility
    # Per-circuit enabled address families (None = instance AFs).
    afs: object = None
    # RFC 8491 Link MSD ({msd-type: value}) from the kernel interface.
    msd: dict = None
    # RFC 7602 extended sequence numbers ("send-only"/"send-and-verify").
    esn_mode: str | None = None
    # BFD fast-failure detection on this circuit (RFC 5880 client).
    bfd_enabled: bool = False
    bfd_min_tx: int = 1000000
    bfd_min_rx: int = 1000000
    bfd_multiplier: int = 3
    # Fast-reroute SRLG membership of this circuit (ietf fast-reroute
    # config): lowered to the uint32 Topology.edge_srlg bitmask at SPT
    # marshal time (spf_run.srlg_bits semantics) — the srlg_disjoint
    # FRR policy input.  Ids fold mod 32, conservative-correct.
    srlg: tuple = ()


@dataclass
class Adjacency:
    sysid: bytes
    # RFC 8667 §2.2 adjacency SIDs ((flags, weight, label), ...).
    adj_sids: tuple = ()
    # Registered BFD session destinations (one per address family).
    bfd_sessions: tuple = ()
    state: AdjacencyState = AdjacencyState.DOWN
    hold_time: int = 9
    addr: IPv4Address | None = None
    addr6: object = None  # neighbor's link-local (RFC 5308 v6 next hop)
    priority: int = 64
    lan_id: bytes = b""  # DIS the neighbor declares
    snpa: bytes = b""  # neighbor's MAC (LAN 2-way check, hello TLV 6)
    # Last hello's TLV contents surfaced in operational state.
    area_addresses: tuple = ()
    protocols: tuple = ()
    addrs4: tuple = ()
    addrs6: tuple = ()
    # RFC 5120 topologies from the hello's MT TLV ((0,) when absent).
    topologies: tuple = (0,)


@dataclass
class IsisInterface:
    name: str
    config: IsisIfConfig
    addr_ip: IPv4Address
    prefix: IPv4Network
    addr6: object = None  # our link-local (RFC 5308 hello TLV 232)
    prefix6: object = None  # advertised global v6 prefix (TLV 236)
    # Full address lists (ip_interface objects); when empty the single
    # addr_ip/prefix (+prefix6) pair above is the effective list.
    addrs4: list = field(default_factory=list)
    addrs6: list = field(default_factory=list)  # global v6
    mac: bytes = b""  # our SNPA on this circuit
    circuit_id: int = 1
    adj: Adjacency | None = None  # p2p: single adjacency
    adjs: dict = field(default_factory=dict)  # LAN: sysid -> Adjacency
    dis_lan_id: bytes | None = None  # elected DIS (sysid + pn byte)
    srm: set = field(default_factory=set)  # LspIds pending flood on this iface
    # p2p circuits keep SRM set until the PSNP ack (§7.3.15.1); this
    # records the incarnation (seqno) already transmitted so only the
    # RETRANSMIT timer resends an unchanged LSP — inline flushes must
    # not (the reference's retransmission is a timer task, a no-op
    # under its `testing` feature).
    srm_sent: dict = field(default_factory=dict)  # lid -> seqno sent
    ssn: set = field(default_factory=set)  # LspIds pending PSNP ack
    # RFC 7602 state: last accepted (session, packet) per PDU class and
    # our transmit counter.
    esn_rx: dict = field(default_factory=dict)
    esn_tx: int = 0

    @property
    def is_lan(self) -> bool:
        return self.config.circuit_type == "broadcast"

    def v4_addresses(self) -> list:
        """[(ip, network)] — every IPv4 address on this circuit."""
        if self.addrs4:
            return [(ia.ip, ia.network) for ia in self.addrs4]
        return [(self.addr_ip, self.prefix)] if self.prefix is not None else []

    def v6_addresses(self) -> list:
        """[(ip|None, network)] — global IPv6 addresses."""
        if self.addrs6:
            return [(ia.ip, ia.network) for ia in self.addrs6]
        return [(None, self.prefix6)] if self.prefix6 is not None else []

    def all_adjacencies(self) -> list:
        """Every adjacency object regardless of state."""
        if self.is_lan:
            return list(self.adjs.values())
        return [self.adj] if self.adj is not None else []

    def up_adjacencies(self) -> list:
        if self.is_lan:
            return [a for a in self.adjs.values() if a.state == AdjacencyState.UP]
        if self.adj is not None and self.adj.state == AdjacencyState.UP:
            return [self.adj]
        return []

    def we_are_dis(self, self_sysid: bytes, circuit_id: int) -> bool:
        return self.dis_lan_id == self_sysid + bytes((circuit_id,))


@dataclass
class HelloTimerMsg:
    ifname: str


@dataclass
class HoldTimerMsg:
    ifname: str


@dataclass
class LanHoldTimerMsg:
    ifname: str
    sysid: bytes


@dataclass
class CsnpTimerMsg:
    ifname: str


@dataclass
class FloodTimerMsg:
    pass


@dataclass
class AgeTickMsg:
    pass


@dataclass
class SpfTimerMsg:
    pass


@dataclass
class SpfHoldDownMsg:
    pass


@dataclass
class IsisIfUpMsg:
    ifname: str


@dataclass
class IsisIfDownMsg:
    ifname: str


@dataclass
class LspEntry:
    lsp: Lsp
    installed_at: float
    # Provenance for operational-state rendering: received off the wire
    # (vs locally originated), and whether a database copy existed when
    # this instance was installed (a purge for an unknown LSP renders
    # without lifetime leaves; reference state.rs).
    rcvd: bool = False
    had_copy: bool = False
    # Header-only entry: a received purge for an LSP we never held
    # (§7.3.16.4) — renders as id + attributes, no lifetime leaves.
    hdr_only: bool = False

    def remaining_lifetime(self, now: float) -> int:
        return max(0, int(self.lsp.lifetime - (now - self.installed_at)))


class IsisInstance(SpfDelayFsm, Actor):
    """One IS-IS routing process (single level for now)."""

    name = "isis"

    def __init__(
        self,
        name: str,
        sysid: bytes,
        area: bytes = b"\x49\x00\x01",
        level: int = 2,
        netio: NetIo | None = None,
        spf_backend: SpfBackend | None = None,
        route_cb=None,
        notif_cb=None,
        auth=None,
        mt_enabled: bool = False,
        sr=None,
        metric_style: str = "wide",  # "wide" | "narrow" | "both"
        lsp_mtu: int | None = None,  # originate lsp-buf-size TLV when set
        te_rid4: IPv4Address | None = None,  # RFC 7794 source-rid stlvs
        te_rid6=None,
        protocols: list | None = None,  # NLPID list override ([0xCC,0x8E])
        node_flag: bool = True,  # RFC 7794 N on loopback host prefixes
    ):
        assert len(sysid) == 6
        self.name = name
        self.sysid = sysid
        self.area = area
        self.level = level
        self.notif_cb = notif_cb
        # Area/domain authentication (packet.AuthCtxIsis): signs LSPs and
        # SNPs end-to-end; hellos use it too unless the circuit overrides
        # (reference holo-isis/src/packet/auth.rs key semantics).
        self.auth = auth
        # RFC 5120 multi-topology ORIGINATION: carry IPv6 in the
        # ipv6-unicast topology (MT id 2) instead of the base topology
        # (the rx side consumes both forms regardless).
        self.mt_enabled = mt_enabled
        # Segment routing (utils.sr.SrConfig): SRGB advertised via the
        # Router Capability TLV, prefix-SIDs as sub-TLVs of the wide IP
        # reach entries (RFC 8667; reference holo-isis/src/sr.rs).
        self.sr = sr
        self.sr_labels: dict = {}
        self.metric_style = metric_style
        self.lsp_mtu = lsp_mtu
        self.te_rid4 = te_rid4
        self.te_rid6 = te_rid6
        self.protocols = protocols
        self.node_flag = node_flag
        # ISO 10589 §7.2.8.1 overload bit: advertised in our LSP flags;
        # an overloaded router stays reachable but is never transit.
        self.overload = False
        # Enabled address families gate route installation per AF.
        self.afs = {"ipv4", "ipv6"}
        # IS-type bits advertised in our LSP flags (ISO 10589 §9.9:
        # IS_TYPE1 always; IS_TYPE2 when the router runs L2).
        self.is_type = 0x03
        # Level-all coupling hooks (protocols.isis.multi): L1 queries
        # att_cb() for the ATT bit; L2 merges extra_reach_cb()'s
        # propagated L1 reachability into its LSP.
        self.att_cb = None
        self.extra_reach_cb = None
        # ISO 10589 §7.2.9.2 receive-side ATT handling can be disabled.
        self.att_ignore = False
        # sysid -> SPT distance from the last SPF (L1->L2 propagation).
        self.vertex_dist: dict = {}
        # RFC 8668-style ECMP clamp (reference spf.rs:920-929).
        self.max_paths: int | None = None
        # RFC 7981 node administrative tags (router-capability sub-TLV).
        self.node_tags: tuple = ()
        # RFC 8491 node MSD advertisement ({msd-type: value}).
        self.node_msd: dict = {}
        # RFC 6232 purge originator identification.
        self.purge_originator = False
        # Redistributed routes ({prefix: metric}) -> external reach.
        self.redist: dict = {}
        # BFD session plumbing: bfd_cb(op, ifname, dst, cfg) emits
        # register/unregister requests over the ibus ("reg"/"unreg").
        self.bfd_cb = None
        # RFC 8667 adjacency-SID label allocator (v4+v6 per adjacency).
        # A mutable box so a level-all composition can share one
        # node-wide label space across its L1/L2 instances.
        self._adj_sid_box = [16]
        # System IPv4 router id (ibus RouterIdUpdate): the router-
        # capability TLV's router-id when no TE rid overrides it.
        self.router_id: IPv4Address | None = None
        # Deferred origination (the reference's LspOriginate task model):
        # when True, non-forced origination only marks pending; the
        # conformance replay fires originate_pending() at the recorded
        # LspOriginate events so seqnos — and therefore LSP bytes and
        # checksums — match the reference's exactly.
        self.deferred_origination = False
        self._orig_pending = False
        # Purges of self-originated fragments we never originate: kept
        # out of the LSDB but flooded via SRM (events.rs:734-740).
        self._srm_phantom: dict = {}
        # lsp_id -> unauthenticated TLV bytes of our last origination
        # (content-unchanged suppression; see _originate_lsp).
        self._plain_raw: dict = {}
        self.netio = netio
        self.backend = spf_backend or ScalarSpfBackend()
        # The LSP database lowered for SPF, kept between runs, and the
        # last run's SpfTopology per MT id (the DeltaPath diff base for
        # in-place device-graph updates).
        self._lowering = LoweredLspdb()
        self._spf_delta_bases: dict = {}
        # ((backend, mt6), KeptDerive v4, KeptDerive v6): the last
        # run's routes, derived by difference.
        self._derive_kept: tuple | None = None
        # Called bare after every run: the callback's owner opens the
        # stages it runs (an L1/L2 node's interlevel and publish).
        self.route_cb = route_cb
        # Production sends an immediate hello on circuit-up and on
        # adjacency transitions (the reference's IntervalTask fires
        # immediately on start).  The conformance harness turns this off:
        # under the reference's `testing` feature hello tasks are no-ops,
        # so recorded cases never contain transmitted hellos.
        self.inline_hellos = True
        self.interfaces: dict[str, IsisInterface] = {}
        self.lsdb: dict[LspId, LspEntry] = {}
        self.routes: dict[IPv4Network, tuple] = {}
        self.connected_prefixes: frozenset = frozenset()
        self.last_installable: dict = {}
        # RFC 5301 dynamic hostnames learned from LSPs (sysid -> name).
        self.hostname = name
        self.hostnames: dict[bytes, str] = {}
        self.spf_run_count = 0
        # Convergence-observatory causal ids pending on the next run.
        self._conv_pending: list = []
        # Full-vs-RouteOnly classification (reference
        # holo-isis/src/spf.rs:150-156, lsdb.rs:1558-1612): an LSP whose
        # IS-reachability TLVs are unchanged only needs route
        # recomputation over the cached SPT, not a new Dijkstra.  Any
        # non-LSP event (adjacency churn, config) forces Full.
        self._spf_type_full = True
        self._spt_cache: dict | None = None
        # SPF run log ring (reference spf.rs log_spf_run; 32 entries).
        self.spf_log: list[dict] = []
        # RFC 8405 SPF-delay FSM (spf_run.SpfDelayFsm, shared with
        # OSPF); its state is surfaced in operational state, and the
        # conformance harness replays the Learn/HoldDown transitions.
        self.spf_timers = legacy_spf_timers()
        # Flooding reduction: per-sender coverage map rebuilt after each
        # full SPF (reference flooding/manet.rs).  _covered_by[sender
        # sysid] = iface names whose neighbor is adjacent to that sender.
        self.flooding_reduction = False
        self._covered_by: dict[bytes, set[str]] = {}
        # IP fast reroute (holo_tpu.frr.FrrConfig; None = disabled):
        # the default-topology backup table is refreshed by every full
        # SPF; frr_backups maps prefix -> {primary (if, addr) ->
        # (backup, labels)} for the RIB feed.
        self.frr = None
        self.frr_tables: dict = {}
        self.frr_backups: dict = {}
        self._frr_engine = None

    def attach(self, loop_):
        super().attach(loop_)
        self._age_timer = self.loop.timer(self.name, AgeTickMsg)
        self._age_timer.start(1.0)
        self._flood_timer = self.loop.timer(self.name, FloodTimerMsg)
        self._spf_timer = self.loop.timer(self.name, SpfTimerMsg)
        self._spf_hold_timer = self.loop.timer(self.name, SpfHoldDownMsg)

    def add_interface(self, ifname: str, cfg: IsisIfConfig, addr: IPv4Address, prefix: IPv4Network, addr6=None, prefix6=None, addrs4=None, addrs6=None, mac: bytes = b"", circuit_id: int | None = None):
        self.interfaces[ifname] = IsisInterface(
            name=ifname, config=cfg, addr_ip=addr, prefix=prefix,
            addr6=addr6, prefix6=prefix6,
            addrs4=list(addrs4 or []), addrs6=list(addrs6 or []), mac=mac,
            circuit_id=circuit_id or (len(self.interfaces) + 1),
        )

    # -- actor

    def handle(self, msg):
        if isinstance(msg, NetRxPacket):
            self._rx(msg)
        elif isinstance(msg, HelloTimerMsg):
            self._send_hello(msg.ifname)
        elif isinstance(msg, HoldTimerMsg):
            self._adj_down(msg.ifname)
        elif isinstance(msg, LanHoldTimerMsg):
            self._lan_adj_down(msg.ifname, msg.sysid)
        elif isinstance(msg, CsnpTimerMsg):
            self._send_periodic_csnp(msg.ifname)
        elif isinstance(msg, FloodTimerMsg):
            self._flush_flooding(retransmit=True)
        elif isinstance(msg, AgeTickMsg):
            self._age_tick()
        elif isinstance(msg, SpfTimerMsg):
            self.run_spf()
        elif isinstance(msg, SpfHoldDownMsg):
            self._spf_holddown_fired()
        elif isinstance(msg, IsisIfUpMsg):
            self.if_up(msg.ifname)
        elif isinstance(msg, IsisIfDownMsg):
            self.if_down(msg.ifname)

    def if_up(self, ifname: str) -> None:
        if ifname in self.interfaces:
            if self.inline_hellos:
                self._send_hello(ifname)
            self._originate_lsp()

    def if_down(self, ifname: str) -> None:
        iface = self.interfaces.pop(ifname, None)
        if iface is None:
            return
        for attr in ("_hello_timer", "_hold_timer"):
            t = getattr(iface, attr, None)
            if t is not None:
                t.cancel()
        self._adj_changed()

    # -- hellos / adjacency (RFC 5303 three-way)

    def _send_hello(self, ifname: str) -> None:
        iface = self.interfaces.get(ifname)
        if iface is None or iface.config.passive:
            return
        if iface.is_lan:
            from holo_tpu.protocols.isis.packet import HelloLan

            lan_id = iface.dis_lan_id or (
                self.sysid + bytes((iface.circuit_id,))
            )
            hello = HelloLan(
                circuit_type=3,
                sysid=self.sysid,
                hold_time=iface.config.hello_interval
                * iface.config.hold_multiplier,
                priority=iface.config.priority,
                lan_id=lan_id,
                level=self.level,
                tlvs={
                    "area_addresses": [self.area],
                    "protocols_supported": self.protocols or [0xCC],
                    "ip_addresses": [ip for ip, _ in iface.v4_addresses()],
                    "ipv6_addresses": (
                        [iface.addr6] if iface.addr6 is not None else []
                    ),
                    # Heard SNPAs: neighbor MACs when known, else the
                    # mock fabric's system-id stand-ins.
                    "is_neighbors": sorted(
                        a.snpa or a.sysid for a in iface.adjs.values()
                    ),
                },
            )
            self._esn_stamp(iface, hello.tlvs)
            self.netio.send(
                ifname, iface.addr_ip, ALL_ISS,
                hello.encode(auth=self._hello_auth(iface)),
            )
        else:
            adj = iface.adj
            if adj is None or adj.state == AdjacencyState.DOWN:
                state = AdjState3Way.DOWN
                nbr_sys = None
            elif adj.state == AdjacencyState.INITIALIZING:
                state = AdjState3Way.INITIALIZING
                nbr_sys = adj.sysid
            else:
                state = AdjState3Way.UP
                nbr_sys = adj.sysid
            hello = HelloP2p(
                circuit_type=3,
                sysid=self.sysid,
                hold_time=iface.config.hello_interval * iface.config.hold_multiplier,
                local_circuit_id=iface.circuit_id,
                tlvs={
                    "area_addresses": [self.area],
                    "protocols_supported": [0xCC],  # IPv4
                    "ip_addresses": [iface.addr_ip],
                    "ipv6_addresses": (
                        [iface.addr6] if iface.addr6 is not None else []
                    ),
                    "p2p_adj": P2pAdjState(
                        state, iface.circuit_id, nbr_sys,
                        iface.circuit_id if nbr_sys else None,
                    ),
                },
            )
            self._esn_stamp(iface, hello.tlvs)
            self.netio.send(
                ifname, iface.addr_ip, ALL_ISS,
                hello.encode(auth=self._hello_auth(iface)),
            )
        t = getattr(iface, "_hello_timer", None)
        if t is None:
            t = self.loop.timer(self.name, lambda: HelloTimerMsg(ifname))
            iface._hello_timer = t
        t.start(iface.config.hello_interval)

    @staticmethod
    def _adj_learn_tlvs(adj: Adjacency, hello) -> None:
        """Record the neighbor's hello TLVs on the adjacency (next hops
        + operational state).  Each hello is authoritative: an address
        family that disappears from the TLVs is cleared."""
        addrs = hello.tlvs.get("ip_addresses") or []
        adj.addr = addrs[0] if addrs else None
        adj.addr6 = next(
            (
                a6
                for a6 in hello.tlvs.get("ipv6_addresses") or []
                if a6.is_link_local
            ),
            None,
        )
        adj.area_addresses = tuple(hello.tlvs.get("area_addresses") or ())
        adj.protocols = tuple(hello.tlvs.get("protocols_supported") or ())
        adj.addrs4 = tuple(addrs)
        adj.addrs6 = tuple(hello.tlvs.get("ipv6_addresses") or ())
        adj.topologies = tuple(
            mt for mt, _a, _o in hello.tlvs.get("mt_ids") or ()
        ) or (0,)

    # -- LAN hellos + DIS election (ISO 10589 §8.4.5)

    def _rx_hello_lan(self, iface: IsisInterface, hello, snpa: bytes = b"") -> None:
        if hello.sysid == self.sysid:
            return
        adj = iface.adjs.get(hello.sysid)
        if adj is None:
            adj = Adjacency(sysid=hello.sysid)
            iface.adjs[hello.sysid] = adj
        adj.hold_time = hello.hold_time
        adj.priority = hello.priority
        adj.lan_id = hello.lan_id
        if snpa:
            adj.snpa = snpa
        self._adj_learn_tlvs(adj, hello)
        old = adj.state
        # ISO 10589 §8.4.2 two-way check: our SNPA in their IS-Neighbors
        # TLV.  Our SNPA is the interface MAC when known (real circuits /
        # replay), else the system id (mock fabric).
        our_snpa = iface.mac or self.sysid
        new = (
            AdjacencyState.UP
            if our_snpa in (hello.tlvs.get("is_neighbors") or [])
            else AdjacencyState.INITIALIZING
        )
        adj.state = new
        if new != old and AdjacencyState.UP in (new, old):
            self._notify_adj_change(
                iface, hello.sysid, new == AdjacencyState.UP
            )
        t = getattr(adj, "_hold_timer", None)
        if t is None:
            t = self.loop.timer(
                self.name,
                lambda s=hello.sysid: LanHoldTimerMsg(iface.name, s),
            )
            adj._hold_timer = t
        t.start(adj.hold_time)
        self._bfd_update_adj(iface, adj)
        if new != old and self.inline_hellos:
            self._send_hello(iface.name)  # accelerate 2-way
        self._run_dis_election(iface)
        if new != old and new == AdjacencyState.UP:
            self._lan_adj_up(iface, adj)

    def _run_dis_election(self, iface: IsisInterface) -> None:
        ups = iface.up_adjacencies()
        if not ups:
            # ISO 10589 §8.4.5: no adjacencies — the LAN has no DIS;
            # purge our pseudonode if we held the role.
            if iface.we_are_dis(self.sysid, iface.circuit_id):
                self._flush_pseudonode(iface)
            if iface.dis_lan_id is not None:
                iface.dis_lan_id = None
                self._adj_changed()
            return
        cands = [(iface.config.priority, self.sysid)]
        for adj in ups:
            cands.append((adj.priority, adj.sysid))
        prio, winner = max(cands)
        new_lan_id = (
            self.sysid + bytes((iface.circuit_id,))
            if winner == self.sysid
            else next(
                (
                    a.lan_id
                    for a in iface.up_adjacencies()
                    if a.sysid == winner and a.lan_id
                ),
                winner + bytes((1,)),
            )
        )
        if new_lan_id == iface.dis_lan_id:
            return
        was_dis = iface.we_are_dis(self.sysid, iface.circuit_id)
        iface.dis_lan_id = new_lan_id
        now_dis = iface.we_are_dis(self.sysid, iface.circuit_id)
        if was_dis and not now_dis:
            self._flush_pseudonode(iface)
        if now_dis:
            t = getattr(iface, "_csnp_timer", None)
            if t is None:
                t = self.loop.timer(
                    self.name, lambda: CsnpTimerMsg(iface.name)
                )
                iface._csnp_timer = t
            t.start(1.0)
        self._adj_changed()

    def _lan_adj_up(self, iface: IsisInterface, adj: Adjacency) -> None:
        self._adj_up(iface)

    def _lan_adj_down(self, ifname: str, sysid: bytes) -> None:
        iface = self.interfaces.get(ifname)
        if iface is None:
            return
        gone = iface.adjs.get(sysid)
        if gone is not None:
            self._bfd_unreg_adj(iface, gone)
            if gone.state == AdjacencyState.UP:
                self._notify_adj_change(iface, sysid, False)
        if iface.adjs.pop(sysid, None) is not None:
            self._run_dis_election(iface)
            self._adj_changed()

    def _send_periodic_csnp(self, ifname: str) -> None:
        """Periodic CSNPs: DIS duty on LANs (10s); on p2p circuits only
        while flooding reduction is enabled (30s) — the recovery net for
        stale-coverage suppression windows."""
        iface = self.interfaces.get(ifname)
        if iface is None:
            return
        if iface.is_lan:
            if not iface.we_are_dis(self.sysid, iface.circuit_id):
                return
            self._send_csnp(iface)
            iface._csnp_timer.start(10.0)
        elif self.flooding_reduction and iface.up_adjacencies():
            self._send_csnp(iface)
            iface._csnp_timer.start(30.0)

    # -- deferred-event entry points (the reference models these as
    # dedicated tasks; the conformance replay drives them directly)

    def send_psnp(self, ifname: str) -> None:
        """Flush this circuit's SSN list as one PSNP (SendPsnp task)."""
        iface = self.interfaces.get(ifname)
        if iface is not None:
            self._flush_ssn(iface)

    def _flush_ssn(self, iface: IsisInterface) -> None:
        now = self.loop.clock.now()
        entries = []
        for lid in sorted(iface.ssn):
            e = self.lsdb.get(lid)
            if e is not None:
                entries.append(
                    (e.remaining_lifetime(now), lid, e.lsp.seqno, e.lsp.cksum)
                )
            iface.ssn.discard(lid)
        if entries:
            snp = Snp(self.level, False, self.sysid, entries)
            self._esn_stamp(iface, snp.tlvs)
            self.netio.send(
                iface.name, iface.addr_ip, ALL_ISS,
                snp.encode(auth=self.auth),
            )

    def send_csnp(self, ifname: str) -> None:
        """Describe the full LSDB on this circuit (SendCsnp task)."""
        iface = self.interfaces.get(ifname)
        if iface is not None:
            self._send_csnp(iface)

    def run_dis_election(self, ifname: str) -> None:
        iface = self.interfaces.get(ifname)
        if iface is not None and iface.is_lan:
            self._run_dis_election(iface)

    def clear_adjacencies(self, ifname: str | None = None) -> None:
        """ietf-isis clear-adjacency RPC: tear down adjacencies (all, or
        one interface's) — the neighbor re-forms them from hellos."""
        for iface in self.interfaces.values():
            if ifname is not None and iface.name != ifname:
                continue
            if iface.is_lan:
                for sysid in list(iface.adjs):
                    self._lan_adj_down(iface.name, sysid)
            elif iface.adj is not None:
                self._adj_down(iface.name)

    def clear_database(self) -> None:
        """ietf-isis clear-database RPC: drop the LSDB, RESTART every
        adjacency (the reference's clear tears them down; hellos re-form
        them), and rebuild our own LSPs from scratch."""
        self.lsdb.clear()
        self._plain_raw.clear()
        for iface in self.interfaces.values():
            iface.srm.clear()
            iface.srm_sent.clear()
            iface.ssn.clear()
            for adj in iface.all_adjacencies():
                self._bfd_unreg_adj(iface, adj)
            iface.adj = None
            iface.adjs.clear()
        self._originate_lsp(force=True)
        self._schedule_spf()

    def sr_allocate_adj_sids(self) -> None:
        """Allocate v4+v6 adjacency-SID labels for every up adjacency
        that lacks them (RFC 8667 §2.2; V|L value/local label form)."""
        for iface in self.interfaces.values():
            for adj in iface.up_adjacencies():
                if not adj.adj_sids:
                    v4 = self._adj_sid_box[0]
                    v6 = v4 + 1
                    self._adj_sid_box[0] = v4 + 2
                    adj.adj_sids = (
                        (0x30, 0, v4),  # V|L
                        (0xB0, 0, v6),  # F|V|L
                    )

    def set_hostname(self, hostname: str) -> None:
        """RFC 5301: our dynamic hostname changed; re-originate."""
        if hostname != self.hostname:
            self.hostname = hostname
            self._originate_lsp()

    def refresh_lsp(self, lid: LspId) -> None:
        """Periodic refresh of one self-originated LSP (seqno bump even
        with unchanged content)."""
        if lid.sysid != self.sysid:
            return
        if lid.pseudonode == 0:
            self._originate_lsp(force=True)
        else:
            self._originate_pseudonodes(force=True)

    def purge_lsp(self, lid: LspId) -> None:
        """ISO 10589 §7.3.16.4 purge: flood a body-less zero-lifetime
        header so neighbors drop the LSP too (the reference's LspPurge
        event on expiry)."""
        e = self.lsdb.get(lid)
        if e is None:
            return
        tlvs = {}
        if self.purge_originator:
            # RFC 6232 §3: the purge carries the POI TLV naming us plus
            # our dynamic hostname.
            tlvs["purge_originator"] = [self.sysid]
            tlvs["hostname"] = self.hostname
        dead = Lsp(self.level, 0, lid, e.lsp.seqno, e.lsp.flags, tlvs)
        dead.encode(auth=self.auth)
        # §7.3.16.4: the purge advertises the original checksum.  Patch
        # the wire bytes too so SNP descriptions and the flooded PDU
        # agree (zero-lifetime LSPs skip checksum verification).
        dead.cksum = e.lsp.cksum
        raw = bytearray(dead.raw)
        raw[24:26] = e.lsp.cksum.to_bytes(2, "big")
        dead.raw = bytes(raw)
        self._install_lsp(dead, flood_from=None)

    def _flush_pseudonode(self, iface: IsisInterface) -> None:
        lsp_id = LspId(self.sysid, pseudonode=iface.circuit_id)
        e = self.lsdb.get(lsp_id)
        if e is not None and e.lsp.lifetime > 0:
            self.purge_lsp(lsp_id)
            self._plain_raw.pop(lsp_id, None)

    def _rx_hello(self, iface: IsisInterface, hello: HelloP2p) -> None:
        if hello.sysid == self.sysid:
            return
        adj = iface.adj
        if adj is None or adj.sysid != hello.sysid:
            if adj is not None and adj.state == AdjacencyState.UP:
                # A different system took over the link: the old
                # neighbor is gone even though no timer fired.
                self._notify_adj_change(iface, adj.sysid, False)
            adj = Adjacency(sysid=hello.sysid)
            iface.adj = adj
        adj.hold_time = hello.hold_time
        # The hello's circuit type drives the adjacency's rendered usage
        # on p2p links (level-1/level-2/level-all), independent of our
        # own level (reference adjacency arena).
        adj.usage_ctype = hello.circuit_type
        self._adj_learn_tlvs(adj, hello)
        p2p = hello.tlvs.get("p2p_adj")
        old = adj.state
        if p2p is None:
            # Classic ISO 10589 §8.2.4 p2p: no three-way TLV, the
            # adjacency comes up on hello receipt.
            new = AdjacencyState.UP
        elif p2p.neighbor_sysid == self.sysid:
            new = AdjacencyState.UP
        else:
            new = AdjacencyState.INITIALIZING
        adj.state = new
        t = getattr(iface, "_hold_timer", None)
        if t is None:
            t = self.loop.timer(self.name, lambda: HoldTimerMsg(iface.name))
            iface._hold_timer = t
        t.start(adj.hold_time)
        self._bfd_update_adj(iface, adj)
        if new != old:
            if AdjacencyState.UP in (new, old):
                self._notify_adj_change(
                    iface, adj.sysid, new == AdjacencyState.UP
                )
            if self.inline_hellos:
                self._send_hello(iface.name)  # accelerate the handshake
            if new == AdjacencyState.UP:
                self._adj_up(iface)
            elif old == AdjacencyState.UP:
                self._adj_changed()

    def _send_csnp(self, iface: IsisInterface) -> None:
        """Describe the whole LSDB as a CSNP on this interface."""
        now = self.loop.clock.now()
        entries = [
            (e.remaining_lifetime(now), lid, e.lsp.seqno, e.lsp.cksum)
            for lid, e in sorted(self.lsdb.items())
        ]
        snp = Snp(self.level, True, self.sysid, entries)
        self._esn_stamp(iface, snp.tlvs)
        self.netio.send(
            iface.name, iface.addr_ip, ALL_ISS, snp.encode(auth=self.auth)
        )

    def _esn_stamp(self, iface: IsisInterface, tlvs: dict) -> None:
        """RFC 7602: stamp outgoing hellos/SNPs with the next extended
        sequence number when the circuit runs ESN."""
        if iface.config.esn_mode in ("send-only", "send-and-verify"):
            iface.esn_tx += 1
            tlvs["ext_seqnum"] = (1, iface.esn_tx)

    def _bfd_dsts(self, adj: Adjacency):
        out = []
        if adj.addr is not None:
            out.append(adj.addr)
        if adj.addr6 is not None:
            out.append(adj.addr6)
        return out

    def _bfd_update_adj(self, iface: IsisInterface, adj: Adjacency, force: bool = False) -> None:
        """(Re)register this adjacency's per-AF BFD sessions (reference
        adjacency.rs bfd_update_sessions: runs on every hello while BFD
        is enabled, any adjacency state)."""
        if not iface.config.bfd_enabled or self.bfd_cb is None:
            return
        cfg = {
            "local_multiplier": iface.config.bfd_multiplier,
            "min_tx": iface.config.bfd_min_tx,
            "min_rx": iface.config.bfd_min_rx,
        }
        want = self._bfd_dsts(adj)
        have = list(adj.bfd_sessions)
        for dst in want:
            if dst not in have or force:
                self.bfd_cb("reg", iface.name, dst, cfg)
        for dst in have:
            if dst not in want:
                self.bfd_cb("unreg", iface.name, dst, None)
        adj.bfd_sessions = tuple(want)

    def _bfd_unreg_adj(self, iface: IsisInterface, adj: Adjacency) -> None:
        if self.bfd_cb is None:
            return
        for dst in adj.bfd_sessions:
            self.bfd_cb("unreg", iface.name, dst, None)
        adj.bfd_sessions = ()

    def set_bfd_config(self, ifname: str, enabled: bool, min_tx: int | None = None, min_rx: int | None = None) -> None:
        """Enable/disable/retune BFD on a circuit; sessions for current
        up adjacencies (un)register accordingly."""
        iface = self.interfaces.get(ifname)
        if iface is None:
            return
        was = iface.config.bfd_enabled
        if was and not enabled:
            for adj in iface.all_adjacencies():
                self._bfd_unreg_adj(iface, adj)
        iface.config.bfd_enabled = enabled
        if min_tx is not None:
            iface.config.bfd_min_tx = min_tx
        if min_rx is not None:
            iface.config.bfd_min_rx = min_rx
        if enabled:
            # New registration or parameter change re-registration.
            for adj in iface.all_adjacencies():
                self._bfd_update_adj(iface, adj, force=True)

    def bfd_state_down(self, ifname: str, dst) -> None:
        """BFD declared the path dead: kill the matching adjacency
        immediately (the reference's fast-failure integration)."""
        iface = self.interfaces.get(ifname)
        if iface is None:
            return
        if iface.is_lan:
            for sysid, adj in list(iface.adjs.items()):
                if dst in (adj.addr, adj.addr6):
                    self._lan_adj_down(ifname, sysid)
        elif iface.adj is not None and dst in (
            iface.adj.addr, iface.adj.addr6
        ):
            # The failed adjacency stays visible in the Down state (the
            # reference deletes it only on hello re-init or hold expiry).
            adj = iface.adj
            self._bfd_unreg_adj(iface, adj)
            if adj.state == AdjacencyState.UP:
                self._notify_adj_change(iface, adj.sysid, False)
            adj.state = AdjacencyState.DOWN
            iface.srm.clear()
            iface.srm_sent.clear()
            iface.ssn.clear()
            self._adj_changed()

    def _adj_up(self, iface: IsisInterface) -> None:
        # Sync databases: send CSNP describing our LSDB + set SRM on all
        # (ISO 10589 §7.3.17 behavior for p2p).
        self._send_csnp(iface)
        for lid in self.lsdb:
            iface.srm.add(lid)
            iface.srm_sent.pop(lid, None)
        if self.flooding_reduction and not iface.is_lan:
            t = getattr(iface, "_csnp_timer", None)
            if t is None:
                t = self.loop.timer(
                    self.name, lambda n=iface.name: CsnpTimerMsg(n)
                )
                iface._csnp_timer = t
            t.start(30.0)
        self._arm_flood()
        self._adj_changed()

    def _adj_down(self, ifname: str) -> None:
        iface = self.interfaces.get(ifname)
        if iface is None or iface.adj is None:
            return
        if iface.adj.state == AdjacencyState.UP:
            self._notify_adj_change(iface, iface.adj.sysid, False)
        self._bfd_unreg_adj(iface, iface.adj)
        iface.adj = None
        iface.srm.clear()
        iface.srm_sent.clear()
        iface.ssn.clear()
        self._adj_changed()

    # ----- YANG notifications (reference holo-isis
    # northbound/notification.rs: common leaves per notification)

    def _notify(self, kind: str, data: dict) -> None:
        if self.notif_cb is not None:
            self.notif_cb({f"ietf-isis:{kind}": data})

    def _notif_common(self, iface=None) -> dict:
        lvl = {1: "level-1", 2: "level-2"}.get(self.level, "level-all")
        d = {
            # Level-all nodes override display_name: notifications name
            # the configured protocol instance, not the per-level actor.
            "routing-protocol-name": getattr(
                self, "display_name", self.name
            ),
            "isis-level": lvl,
        }
        if iface is not None:
            d["interface-name"] = iface.name
            d["interface-level"] = lvl
        return d

    def _notify_adj_change(self, iface, sysid: bytes, up: bool) -> None:
        from holo_tpu.protocols.isis.nb_state import sysid_str

        _ISIS_ADJ_TRANSITIONS.labels(
            instance=self.name, to="up" if up else "down"
        ).inc()
        self._notify(
            "adjacency-state-change",
            self._notif_common(iface)
            | {
                "neighbor-system-id": sysid_str(sysid),
                "state": "up" if up else "down",
            },
        )

    def _notify_decode_error(self, iface, data, err, rx_auth) -> None:
        """Reference notification.rs:161-188: wrong/missing auth TLV
        type vs a failed digest are separate notifications.  Only an
        authenticated circuit alarms — garbage frames on an open circuit
        are not a security event."""
        from holo_tpu.protocols.isis.packet import AuthError, AuthTypeError

        if rx_auth is None or not isinstance(err, AuthError):
            return
        import base64

        kind = (
            "authentication-type-failure"
            if isinstance(err, AuthTypeError)
            else "authentication-failure"
        )
        self._notify(
            kind,
            self._notif_common(iface)
            | {"raw-pdu": base64.b64encode(data[:64]).decode()},
        )

    def _notify_seqno_skipped(self, iface, lsp) -> None:
        from holo_tpu.protocols.isis.nb_state import lsp_id_str

        self._notify(
            "sequence-number-skipped",
            self._notif_common(iface) | {"lsp-id": lsp_id_str(lsp.lsp_id)},
        )

    def set_overload(self, on: bool) -> None:
        """ISO 10589 §7.2.8.1 overload bit with the reference's
        database-overload notification (notification.rs:28-38)."""
        if self.overload == bool(on):
            return
        self.overload = bool(on)
        self._notify(
            "database-overload",
            self._notif_common() | {"overload": "on" if on else "off"},
        )
        self._originate_lsp(force=True)

    def _adj_changed(self) -> None:
        # No direct SPF trigger: the RFC 8405 Igp event fires from LSP
        # CONTENT changes at install (reference lsdb.rs:1606-1618) — if
        # the adjacency change altered our LSP, the re-origination below
        # schedules it; a LAN member losing an adjacency it never
        # advertised (the DIS does) waits for the pseudonode update.
        self._originate_lsp()

    # -- LSP origination

    def _originate_lsp(self, force: bool = False, min_seqno: int = 0) -> None:
        """(Re-)originate our LSP.  ``force`` bypasses the content-unchanged
        skip (periodic refresh MUST bump seqno even with identical TLVs or
        neighbors age us out); ``min_seqno`` outpaces a stale incarnation
        seen in the network (ISO 10589 §7.3.16.1)."""
        if self.deferred_origination and not force:
            self._orig_pending = True
            return
        lsp_id = LspId(self.sysid)
        old = self.lsdb.get(lsp_id)
        wide = self.metric_style in ("wide", "both")
        narrow = self.metric_style in ("narrow", "both")
        is_reach = []
        narrow_is = []
        ip4_addrs: list = []
        ip4_prefixes: dict = {}  # prefix -> metric (BTreeMap dedup)
        ip6_reach_map: dict = {}
        ip6_addrs = []
        sids = (
            self.sr.prefix_sids
            if self.sr is not None and self.sr.enabled
            else {}
        )
        for iface in self.interfaces.values():
            metric = iface.config.metric
            if_afs = (
                iface.config.afs
                if iface.config.afs is not None
                else self.afs
            )
            for ip, net in iface.v4_addresses() if "ipv4" in if_afs else []:
                if ip not in ip4_addrs:
                    ip4_addrs.append(ip)
                ip4_prefixes.setdefault(net, (metric, iface))
            for ip6, net6 in iface.v6_addresses() if "ipv6" in if_afs else []:
                if ip6 is not None and ip6 not in ip6_addrs:
                    ip6_addrs.append(ip6)
                if net6 is not None and net6 not in ip6_reach_map:
                    attr = 0
                    if (
                        self.node_flag
                        and iface.config.loopback
                        and net6.prefixlen == 128
                    ):
                        attr |= PREFIX_ATTR_N
                    psid6 = sids.get(net6)
                    ip6_reach_map[net6] = ExtIpReach(
                        net6, metric,
                        sid_index=psid6.index if psid6 is not None else None,
                        sid_flags=_sid_flags(psid6),
                        attr_flags=attr or None,
                        src_rid4=self.te_rid4,
                        src_rid6=self.te_rid6,
                    )
            if iface.addr6 is not None:
                lla = iface.addr6
                if lla not in ip6_addrs and not lla.is_link_local:
                    ip6_addrs.append(lla)
            link_msd = (
                tuple(sorted(iface.config.msd.items()))
                if iface.config.msd
                else None
            )
            sr_on = self.sr is not None and self.sr.enabled
            if iface.is_lan:
                if iface.dis_lan_id is not None and iface.up_adjacencies():
                    # LAN: advertise reach to the pseudonode.
                    if wide:
                        is_reach.append(
                            ExtIsReach(
                                iface.dis_lan_id, metric, link_msd=link_msd
                            )
                        )
                    if narrow:
                        narrow_is.append(
                            ExtIsReach(
                                iface.dis_lan_id,
                                min(metric, MAX_NARROW_METRIC),
                            )
                        )
            elif iface.adj is not None and iface.adj.state == AdjacencyState.UP:
                if wide:
                    is_reach.append(
                        ExtIsReach(
                            iface.adj.sysid + b"\x00", metric,
                            link_msd=link_msd,
                            adj_sids=(
                                iface.adj.adj_sids
                                if sr_on and iface.adj.adj_sids
                                else None
                            ),
                        )
                    )
                if narrow:
                    narrow_is.append(
                        ExtIsReach(
                            iface.adj.sysid + b"\x00",
                            min(metric, MAX_NARROW_METRIC),
                        )
                    )
        ip_reach = []
        narrow_ip = []
        for net in sorted(ip4_prefixes, key=lambda p: (int(p.network_address), p.prefixlen)):
            metric, iface = ip4_prefixes[net]
            if wide:
                attr = 0
                if (
                    self.node_flag
                    and iface.config.loopback
                    and net.prefixlen == 32
                ):
                    attr |= PREFIX_ATTR_N
                psid = sids.get(net)
                ip_reach.append(
                    ExtIpReach(
                        net, metric,
                        sid_index=psid.index if psid is not None else None,
                        sid_flags=_sid_flags(psid),
                        attr_flags=attr or None,
                        src_rid4=self.te_rid4,
                        src_rid6=self.te_rid6,
                    )
                )
            if narrow:
                narrow_ip.append(
                    ExtIpReach(net, min(metric, MAX_NARROW_METRIC))
                )
        # Redistributed routes: RFC 1195 external reach (TLV 130 narrow;
        # wide entries share TLV 135; v6 entries set the X bit).
        narrow_ext = []
        for net in sorted(
            self.redist,
            key=lambda p: (p.version, int(p.network_address), p.prefixlen),
        ):
            metric = self.redist[net]
            if net.version == 4:
                if narrow:
                    narrow_ext.append(
                        ExtIpReach(
                            net, min(metric, MAX_NARROW_METRIC),
                            external=True,
                        )
                    )
                if wide and net not in ip4_prefixes:
                    ip_reach.append(ExtIpReach(net, metric))
            elif net not in ip6_reach_map:
                ip6_reach_map[net] = ExtIpReach(net, metric, external=True)
        ip6_reach = [
            ip6_reach_map[p]
            for p in sorted(
                ip6_reach_map,
                key=lambda p: (int(p.network_address), p.prefixlen),
            )
        ]
        ip4_addrs.sort(key=int)
        ip6_addrs.sort(key=int)
        if self.protocols is not None:
            protos = list(self.protocols)
        else:
            protos = [0xCC] + ([0x8E] if (ip6_reach or ip6_addrs) else [])
        tlvs = {
            "area_addresses": [self.area],
            "protocols_supported": protos,
            "hostname": self.hostname,
            "ext_is_reach": is_reach,
            "ext_ip_reach": ip_reach,
            "narrow_is_reach": narrow_is,
            "narrow_ip_reach": narrow_ip,
            "narrow_ip_ext_reach": narrow_ext,
            "ip_addresses": ip4_addrs,
            "ipv6_reach": ip6_reach,
            "ipv6_addresses": ip6_addrs,
        }
        if self.te_rid4 is not None:
            tlvs["ipv4_router_id"] = self.te_rid4
        if self.te_rid6 is not None:
            tlvs["ipv6_router_id"] = self.te_rid6
        if self.lsp_mtu is not None:
            tlvs["lsp_buf_size"] = self.lsp_mtu
        if self.node_tags:
            tlvs["node_tags"] = tuple(self.node_tags)
        if self.node_msd:
            tlvs["node_msd"] = dict(self.node_msd)
        if (
            self.sr is not None
            and self.sr.enabled
            and getattr(self.sr, "srgb_set", True)
        ):
            tlvs["sr_cap"] = (self.sr.srgb.lower, self.sr.srgb.size)
            if self.sr.srlb:
                lo, hi = self.sr.srlb
                tlvs["srlb"] = (lo, hi - lo + 1)
        if tlvs.get("sr_cap") or tlvs.get("node_tags") or tlvs.get("node_msd"):
            tlvs["cap_router_id"] = self.te_rid4 or self.router_id
        if self.mt_enabled:
            # Membership in the base + ipv6-unicast topologies, v6 reach
            # and v6-topology adjacencies under the MT TLVs.
            tlvs["mt_ids"] = [(0, False, False), (MT_IPV6, False, False)]
            tlvs["mt_ipv6_reach"] = [(MT_IPV6, e) for e in ip6_reach]
            tlvs["ipv6_reach"] = []
            tlvs["mt_is_reach"] = [(MT_IPV6, e) for e in is_reach]
        if self.extra_reach_cb is not None:
            # Level-all L2: merge propagated L1 reachability (metric
            # already includes the L1 SPT distance; lowest wins) and
            # active summaries (lsdb.rs lsp_propagate_l1_to_l2).
            xnarrow, xwide, xv6, xnarrow_ext = self.extra_reach_cb()

            def _merge(own_list, extra):
                have = {r.prefix: i for i, r in enumerate(own_list)}
                for r in extra:
                    i = have.get(r.prefix)
                    if i is None:
                        own_list.append(r)
                    elif r.metric < own_list[i].metric:
                        own_list[i] = r
                own_list.sort(
                    key=lambda r: (
                        int(r.prefix.network_address), r.prefix.prefixlen
                    )
                )

            if narrow:
                _merge(tlvs["narrow_ip_reach"], xnarrow)
                _merge(tlvs["narrow_ip_ext_reach"], xnarrow_ext)
            if wide:
                _merge(tlvs["ext_ip_reach"], xwide)
            if self.mt_enabled:
                # MT routers carry v6 under TLV 237 (topology 2).
                have6 = {r.prefix for _mt, r in tlvs.get("mt_ipv6_reach", [])}
                tlvs.setdefault("mt_ipv6_reach", []).extend(
                    (MT_IPV6, r) for r in xv6 if r.prefix not in have6
                )
            else:
                _merge(tlvs["ipv6_reach"], xv6)
        seqno = max((old.lsp.seqno + 1) if old else 1, min_seqno)
        flags = self.is_type | (0x04 if self.overload else 0)
        if (
            self.att_cb is not None
            and not self.overload
            and self.att_cb()
        ):
            flags |= 0x40  # ATT (default-metric bit)
        lsp = Lsp(self.level, LSP_MAX_AGE, lsp_id, seqno, flags=flags, tlvs=tlvs)
        # Content comparison uses the UNauthenticated bytes: the auth
        # digest covers the seqno, so authenticated raw always differs.
        plain = lsp.encode()
        if (
            not force
            and self._plain_raw.get(lsp_id) == plain[26:]
        ):
            self._originate_pseudonodes()
            return  # content unchanged
        self._plain_raw[lsp_id] = plain[26:]
        lsp.encode(auth=self.auth)
        self._install_lsp(lsp, flood_from=None)
        self._originate_pseudonodes()

    def originate_pending(self) -> None:
        """Run a deferred origination now (recorded LspOriginate event)."""
        self._orig_pending = False
        saved = self.deferred_origination
        self.deferred_origination = False
        try:
            self._originate_lsp()
        finally:
            self.deferred_origination = saved

    def _originate_pseudonodes(self, force: bool = False) -> None:
        """DIS duty: one pseudonode LSP per LAN we are DIS of, listing all
        members (incl. ourselves) at metric 0.  ``force`` bypasses the
        content-unchanged skip for periodic refresh (same seqno-bump
        requirement as the node LSP)."""
        for iface in self.interfaces.values():
            if not iface.is_lan or not iface.we_are_dis(
                self.sysid, iface.circuit_id
            ):
                continue
            lsp_id = LspId(self.sysid, pseudonode=iface.circuit_id)
            # Reference member order (lsdb.rs lsp_build_tlvs_pseudo):
            # adjacencies in arena (first-heard) order, ourselves last.
            members = [
                a.sysid + b"\x00" for a in iface.up_adjacencies()
            ] + [self.sysid + b"\x00"]
            tlvs = {"protocols_supported": []}
            if self.metric_style in ("wide", "both"):
                tlvs["ext_is_reach"] = [ExtIsReach(m, 0) for m in members]
            if self.metric_style in ("narrow", "both"):
                tlvs["narrow_is_reach"] = [ExtIsReach(m, 0) for m in members]
            old = self.lsdb.get(lsp_id)
            seqno = (old.lsp.seqno + 1) if old else 1
            lsp = Lsp(self.level, LSP_MAX_AGE, lsp_id, seqno, tlvs=tlvs)
            plain = lsp.encode()
            if not force and self._plain_raw.get(lsp_id) == plain[26:]:
                continue
            self._plain_raw[lsp_id] = plain[26:]
            lsp.encode(auth=self.auth)
            self._install_lsp(lsp, flood_from=None)

    # -- LSDB install + flooding (SRM/SSN model)

    def _install_lsp(self, lsp: Lsp, flood_from: str | None) -> None:
        now = self.loop.clock.now()
        prev = self.lsdb.get(lsp.lsp_id)
        self.lsdb[lsp.lsp_id] = LspEntry(
            lsp, now,
            rcvd=flood_from is not None,
            # Only a LIVE copy counts (not SNP shells or prior purges).
            had_copy=prev is not None
            and prev.lsp.seqno != 0
            and prev.lsp.lifetime > 0,
        )
        # RFC 5301: learn/forget the originator's dynamic hostname.
        if lsp.lsp_id.pseudonode == 0 and lsp.lsp_id.fragment == 0:
            name = lsp.tlvs.get("hostname")
            if name and lsp.lifetime > 0:
                self.hostnames[lsp.lsp_id.sysid] = name
            else:
                self.hostnames.pop(lsp.lsp_id.sysid, None)
        # Flooding reduction: interfaces whose neighbor the SENDER also
        # covers (sound: the sender floods its own neighborhood; periodic
        # CSNPs recover stale-coverage windows).
        suppressed: set[str] = set()
        if self.flooding_reduction and flood_from is not None:
            sender_iface = self.interfaces.get(flood_from)
            if sender_iface is not None and sender_iface.adj is not None:
                suppressed = self._covered_by.get(
                    sender_iface.adj.sysid, set()
                )
        for iface in self.interfaces.values():
            if not iface.up_adjacencies():
                continue
            if iface.name == flood_from:
                iface.srm.discard(lsp.lsp_id)
                iface.srm_sent.pop(lsp.lsp_id, None)
                if not iface.is_lan:
                    iface.ssn.add(lsp.lsp_id)  # p2p ack via PSNP
            elif iface.name in suppressed:
                continue
            else:
                iface.srm.add(lsp.lsp_id)
                iface.srm_sent.pop(lsp.lsp_id, None)
        self._arm_flood()
        # SPF (and the RFC 8405 Igp event) fires only on CONTENT change —
        # a pure refresh (same TLVs/flags/liveness, new seqno) schedules
        # nothing (reference lsdb.rs:1558-1618).
        content_change = not (
            prev is not None
            and prev.lsp.is_expired == lsp.is_expired
            and prev.lsp.flags == lsp.flags
            and prev.lsp.tlvs == lsp.tlvs
        )
        if content_change and lsp.seqno != 0:
            # Full SPF only when the IS-reachability (or flags/liveness)
            # changed; a prefix-only change is a RouteOnly run over the
            # cached SPT (reference lsdb.rs:1604-1612 topology_change).
            topology_change = not (
                prev is not None
                and prev.lsp.is_expired == lsp.is_expired
                and prev.lsp.flags == lsp.flags
                and all(
                    prev.lsp.tlvs.get(k) == lsp.tlvs.get(k)
                    for k in (
                        "ext_is_reach",
                        "narrow_is_reach",
                        "mt_is_reach",
                        "mt_ids",
                    )
                )
            )
            self._schedule_spf(topology=topology_change)

    def _arm_flood(self) -> None:
        if not self._flood_timer.armed:
            self._flood_timer.start(0.05)

    def _flush_flooding(
        self, srm_only: bool = False, retransmit: bool = False
    ) -> None:
        now = self.loop.clock.now()
        for iface in self.interfaces.values():
            if iface.srm:
                for lid in list(iface.srm):
                    e = self.lsdb.get(lid)
                    if e is None:
                        ph = self._srm_phantom.get(lid)
                        if ph is None or not ph.raw:
                            iface.srm.discard(lid)
                            iface.srm_sent.pop(lid, None)
                            continue
                        if (
                            not retransmit
                            and iface.srm_sent.get(lid) == (ph.seqno, ph.is_expired)
                        ):
                            continue  # ack pending; timer resends
                        self.netio.send(
                            iface.name, iface.addr_ip, ALL_ISS, ph.raw
                        )
                        if iface.is_lan:
                            iface.srm.discard(lid)
                        else:
                            iface.srm_sent[lid] = (ph.seqno, ph.is_expired)
                        continue
                    if not e.lsp.raw:
                        continue  # zero-seqno placeholder: nothing to send
                    if (
                        not retransmit
                        and iface.srm_sent.get(lid) == (e.lsp.seqno, e.lsp.is_expired)
                    ):
                        continue  # unchanged + unacked: timer's job
                    self.netio.send(iface.name, iface.addr_ip, ALL_ISS, e.lsp.raw)
                    if iface.is_lan:
                        # §7.3.15.1: broadcast circuits clear SRM after
                        # transmit (the DIS's CSNPs recover losses);
                        # p2p keeps it until the PSNP ack.
                        iface.srm.discard(lid)
                    else:
                        iface.srm_sent[lid] = (e.lsp.seqno, e.lsp.is_expired)
            if srm_only:
                continue
            if iface.ssn:
                self._flush_ssn(iface)
        if any(i.srm for i in self.interfaces.values()):
            self._flood_timer.start(5.0)  # p2p retransmit interval

    # -- rx dispatch

    def _hello_auth(self, iface):
        return iface.config.auth or self.auth

    def _rx(self, msg: NetRxPacket) -> None:
        iface = self.interfaces.get(msg.ifname)
        if iface is None:
            return
        # Hellos authenticate with the circuit key; LSPs/SNPs carry the
        # end-to-end area key (the originator's signature is forwarded).
        hello_types = (
            PduType.HELLO_P2P, PduType.HELLO_LAN_L1, PduType.HELLO_LAN_L2
        )
        probe = msg.data[4] & 0x1F if len(msg.data) > 4 else 0
        rx_auth = (
            self._hello_auth(iface)
            if probe in tuple(int(t) for t in hello_types)
            else self.auth
        )
        try:
            pdu_type, pdu = decode_pdu(msg.data, auth=rx_auth)
        except DecodeError as e:
            _ISIS_RX_BAD.labels(instance=self.name).inc()
            self._notify_decode_error(iface, msg.data, e, rx_auth)
            return
        _ISIS_PDUS_RX.labels(instance=self.name).inc()
        snpa = msg.src if isinstance(msg.src, bytes) else b""
        self.rx_pdu(msg.ifname, pdu_type, pdu, snpa)

    def rx_pdu(self, ifname: str, pdu_type: PduType, pdu, snpa: bytes = b"") -> None:
        """Dispatch one decoded PDU (the conformance replay feeds decoded
        objects directly, like the reference's testing stub)."""
        iface = self.interfaces.get(ifname)
        if iface is None or iface.config.passive:
            return
        # Circuit-type sanity precedes everything: mismatched hello
        # kinds never advance protocol state of any sort.
        if pdu_type == PduType.HELLO_P2P and iface.is_lan:
            return
        if (
            pdu_type in (PduType.HELLO_LAN_L1, PduType.HELLO_LAN_L2)
            and not iface.is_lan
        ):
            return
        if iface.config.esn_mode == "send-and-verify" and pdu_type not in (
            PduType.LSP_L1, PduType.LSP_L2
        ):
            # RFC 7602 §3: hellos and SNPs must carry a strictly
            # increasing extended sequence number or be discarded.
            # State is per sending system per PDU type — independent
            # neighbors run independent sequence spaces.
            esn = (getattr(pdu, "tlvs", None) or {}).get("ext_seqnum")
            if esn is None:
                return
            key = (getattr(pdu, "sysid", b""), int(pdu_type))
            last = iface.esn_rx.get(key)
            if last is not None and esn <= last:
                return  # replayed or stale
            iface.esn_rx[key] = esn
        if pdu_type == PduType.HELLO_P2P:
            self._rx_hello(iface, pdu)
        elif pdu_type in (PduType.HELLO_LAN_L1, PduType.HELLO_LAN_L2):
            self._rx_hello_lan(iface, pdu, snpa)
        elif pdu_type in (PduType.LSP_L1, PduType.LSP_L2):
            self._rx_lsp(iface, pdu)
        elif pdu_type in (PduType.CSNP_L1, PduType.CSNP_L2):
            self._rx_csnp(iface, pdu)
        elif pdu_type in (PduType.PSNP_L1, PduType.PSNP_L2):
            self._rx_psnp(iface, pdu)

    def _rx_lsp(self, iface: IsisInterface, lsp: Lsp) -> None:
        if not iface.up_adjacencies():
            return
        cur = self.lsdb.get(lsp.lsp_id)
        now = self.loop.clock.now()
        if lsp.lsp_id.sysid == self.sysid and lsp.is_expired:
            # ietf-isis own-lsp-purge: we RECEIVED a purged copy of one
            # of our own LSPs (reference events.rs gates the event on
            # zero remaining lifetime, not on stale live incarnations).
            from holo_tpu.protocols.isis.nb_state import lsp_id_str

            self._notify(
                "own-lsp-purge",
                self._notif_common(iface)
                | {"lsp-id": lsp_id_str(lsp.lsp_id)},
            )
        # LSP expiration synchronization (ISO 10589 §7.3.16.4.a): an
        # expired LSP we have no copy of is never installed; on p2p
        # circuits it is acknowledged directly with a PSNP.
        if lsp.is_expired and cur is None:
            if not iface.is_lan:
                snp = Snp(
                    self.level, False, self.sysid,
                    [(0, lsp.lsp_id, lsp.seqno, lsp.cksum)],
                )
                self.netio.send(
                    iface.name, iface.addr_ip, ALL_ISS,
                    snp.encode(auth=self.auth),
                )
            return
        # Self-originated received NEWER: outpace it (§7.3.16.1) — also
        # when we hold no copy (restart case: stale incarnation in the
        # network must not outlive our fresh origination).  An EQUAL or
        # older copy flows through the generic comparison below (equal =
        # implicit ack; older = send ours back).
        if lsp.lsp_id.sysid == self.sysid:
            if cur is None:
                # A fragment we don't currently originate: purge the
                # received incarnation network-wide without installing
                # it (reference events.rs:734-740).  The LSP checksum
                # excludes the lifetime field, so zeroing it in place
                # keeps the signature valid.
                lsp.lifetime = 0
                if lsp.raw:
                    raw = bytearray(lsp.raw)
                    raw[10:12] = b"\x00\x00"
                    lsp.raw = bytes(raw)
                self._srm_phantom[lsp.lsp_id] = lsp
                for other in self.interfaces.values():
                    if other.up_adjacencies():
                        other.srm.add(lsp.lsp_id)
                        other.srm_sent.pop(lsp.lsp_id, None)
                self._arm_flood()
                return
            if lsp.compare(
                cur.remaining_lifetime(now), cur.lsp.seqno, cur.lsp.cksum
            ) > 0:
                self._notify_seqno_skipped(iface, lsp)
                self._originate_lsp(force=True, min_seqno=lsp.seqno + 1)
                return
        if cur is None:
            c = 1
        else:
            c = lsp.compare(
                cur.remaining_lifetime(now), cur.lsp.seqno, cur.lsp.cksum
            )
        if c > 0:
            if (
                lsp.is_expired
                and self.purge_originator
                and not lsp.tlvs.get("purge_originator")
            ):
                # RFC 6232 §3: a relayed purge without a POI TLV gains
                # one naming us and the system we received it from.
                if iface.is_lan:
                    # Any single up adjacency identifies the relayer on
                    # a LAN only when unambiguous.
                    ups = iface.up_adjacencies()
                    sender = ups[0].sysid if len(ups) == 1 else None
                elif iface.adj is not None:
                    sender = iface.adj.sysid
                else:
                    sender = None
                lsp.tlvs["purge_originator"] = [self.sysid] + (
                    [sender] if sender else []
                )
                lsp.tlvs["hostname"] = self.hostname
                lsp.encode(auth=self.auth)
            if (
                lsp.is_expired
                and not lsp.tlvs.get("purge_originator")
                and (cur is None or cur.lsp.seqno == 0 or cur.lsp.is_expired)
            ):
                # §7.3.16.4: a purge for an LSP we never held installs
                # as a HEADER-ONLY entry (acked and remembered, but no
                # body/lifetime state — reference state.rs renders just
                # the id and attributes).
                self._install_lsp(lsp, flood_from=iface.name)
                self.lsdb[lsp.lsp_id].hdr_only = True
                return
            self._install_lsp(lsp, flood_from=iface.name)
        elif c == 0:
            if cur is not None and cur.lsp.cksum != lsp.cksum and cur.lsp.seqno != 0:
                # LSP confusion (§7.3.16.2): same seqno, different
                # contents.  Our own LSP skips ahead a seqno; a received
                # one is treated as expired and purged.
                if lsp.lsp_id.sysid == self.sysid:
                    self._notify_seqno_skipped(iface, lsp)
                    self._originate_lsp(force=True, min_seqno=lsp.seqno + 1)
                else:
                    self.purge_lsp(lsp.lsp_id)
                return
            iface.srm.discard(lsp.lsp_id)
            iface.srm_sent.pop(lsp.lsp_id, None)
            if not iface.is_lan:
                iface.ssn.add(lsp.lsp_id)
            self._arm_flood()
        else:
            # Ours is newer: send it back — and clear any pending ack
            # for the stale instance (§7.3.16.4.c: set SRM, clear SSN).
            iface.srm.add(lsp.lsp_id)
            iface.srm_sent.pop(lsp.lsp_id, None)
            iface.ssn.discard(lsp.lsp_id)
            self._arm_flood()

    def _snp_entry_update(self, iface: IsisInterface, lid: LspId, lt: int, seq: int, ck: int) -> None:
        """Apply one SNP entry against the stored LSP (reference
        events.rs process_pdu_snp comparison block)."""
        e = self.lsdb.get(lid)
        if e is None:
            return
        c = e.lsp.compare(lt, seq)
        if c == 0:
            if e.lsp.cksum != ck and e.lsp.seqno != 0:
                # LSP confusion (ISO 10589 §7.3.16.2): a received LSP is
                # treated as expired (purge); a self-originated one
                # skips ahead a sequence number.
                if lid.sysid == self.sysid:
                    self.refresh_lsp(lid)
                else:
                    self.purge_lsp(lid)
            else:
                iface.srm.discard(lid)  # implicit ack
                iface.srm_sent.pop(lid, None)
        elif c > 0:
            iface.ssn.discard(lid)
            iface.srm.add(lid)
            iface.srm_sent.pop(lid, None)  # they have older: send ours
        else:
            # §7.3.15.2(c): they described a newer incarnation —
            # request it (SSN) and stop offering ours.
            iface.srm.discard(lid)
            iface.srm_sent.pop(lid, None)
            iface.ssn.add(lid)

    def _rx_csnp(self, iface: IsisInterface, snp: Snp) -> None:
        now = self.loop.clock.now()
        described = {lid: (lt, seq, ck) for lt, lid, seq, ck in snp.entries}
        # LSPs we have that they didn't describe (in range): set SRM.
        for lid, e in self.lsdb.items():
            if lid not in described:
                iface.srm.add(lid)
                iface.srm_sent.pop(lid, None)
            else:
                lt, seq, ck = described[lid]
                self._snp_entry_update(iface, lid, lt, seq, ck)
        # LSPs they described that we lack: request via PSNP with seqno 0.
        missing = [
            (0, lid, 0, 0) for lid in described if lid not in self.lsdb
        ]
        if missing:
            psnp = Snp(self.level, False, self.sysid, missing)
            self.netio.send(
                iface.name, iface.addr_ip, ALL_ISS,
                psnp.encode(auth=self.auth),
            )
        self._arm_flood()

    def _rx_psnp(self, iface: IsisInterface, snp: Snp) -> None:
        now = self.loop.clock.now()
        for lt, lid, seq, ck in snp.entries:
            e = self.lsdb.get(lid)
            if e is None:
                # Acknowledge outstanding phantom purges (stale
                # self-originated fragments we flooded as expired).
                if lid in self._srm_phantom:
                    iface.srm.discard(lid)
                    iface.srm_sent.pop(lid, None)
                    if not any(
                        lid in i.srm for i in self.interfaces.values()
                    ):
                        del self._srm_phantom[lid]
                    continue
                # ISO 10589 §7.3.15.2(b): an entry for an LSP we lack
                # (all of lifetime/seqno/cksum nonzero) creates a
                # zero-seqno placeholder and requests it via SSN.
                if (
                    lt and seq and ck
                    and not iface.is_lan
                    and lid.sysid != self.sysid
                ):
                    ph = Lsp(self.level, 0, lid, 0, 0)
                    self.lsdb[lid] = LspEntry(ph, now)
                    iface.ssn.add(lid)
                    self._arm_flood()
                continue
            self._snp_entry_update(iface, lid, lt, seq, ck)
        self._arm_flood()

    # -- aging

    def _age_tick(self) -> None:
        now = self.loop.clock.now()
        for lid, e in list(self.lsdb.items()):
            if (
                lid.sysid == self.sysid
                and e.lsp.seqno > 0
                and e.remaining_lifetime(now) < (LSP_MAX_AGE - LSP_REFRESH)
            ):
                # Periodic refresh: force a seqno bump even with unchanged
                # content, or neighbors age our LSP out.  Pseudonode LSPs
                # refresh on the same rule.
                if lid.pseudonode == 0:
                    self._originate_lsp(force=True)
                else:
                    self._originate_pseudonodes(force=True)
            elif e.remaining_lifetime(now) == 0:
                del self.lsdb[lid]
                self._schedule_spf()
        self._age_timer.start(1.0)

    # -- SPF (shared backend)

    def iface_metric_update(self, ifname: str, metric: int) -> None:
        """Live metric reconfiguration (reference northbound
        InterfaceUpdate): re-originate our LSP with the new
        IS-reachability metric; neighbors reconverge via flooding."""
        iface = self.interfaces.get(ifname)
        if iface is None or iface.config.metric == metric:
            return
        iface.config.metric = metric
        # Pseudonode LSPs list members at metric 0 — only our own LSP
        # carries the metric, so no pseudonode re-origination needed.
        self._originate_lsp(force=True)

    def _schedule_spf(self, topology: bool = True) -> None:
        if topology:
            self._spf_type_full = True
        # Causal origin stamp (LSP arrival/change is the IS-IS trigger
        # class; shared contract, see the OSPFv2 instance).
        convergence.pend_schedule(
            self._conv_pending, convergence.TRIGGER_LSP, instance=self.name
        )
        self._spf_delay_event(
            self.spf_timers, self.loop.clock.now(),
            self._spf_timer, self._spf_hold_timer,
        )

    @property
    def spf_delay_state(self) -> str:
        """The RFC 8405 FSM's state as operational state renders it."""
        return self.spf_state.value

    def spf_delay_event(self, event: str) -> None:
        """RFC 8405 timer transitions (LEARN/HOLDDOWN; the conformance
        harness replays them at the recorded positions)."""
        if event == "learn" and self.spf_state == SpfFsmState.SHORT_WAIT:
            self.spf_state = SpfFsmState.LONG_WAIT
        elif event == "holddown":
            self._spf_holddown_fired()

    def run_spf(self) -> None:
        with convergence.spf_run(self._conv_pending, self.name):
            with telemetry.span("isis.spf", instance=self.name):
                # Host stages (site isis.spf): run holds the whole run,
                # topology / link / derive its parts, one observation of
                # each per run (an L1/L2 node's route_cb adds interlevel
                # and publish).
                with profiling.stage("isis.spf", "run"):
                    self._run_spf_traced()

    def _root_hops(self) -> tuple[dict, dict]:
        """``({router key: [(ifname, addr4, addr6)]}, {pseudonode key:
        ifname})``: our up point-to-point adjacencies, in interface
        order, and the LANs we sit on."""
        adj_hops: dict = {}
        lans: dict = {}
        for iface in self.interfaces.values():
            for adj in iface.up_adjacencies():
                adj_hops.setdefault(adj.sysid + b"\x00", []).append(
                    (iface.name, adj.addr, adj.addr6)
                )
            if iface.is_lan and iface.dis_lan_id is not None:
                lans[iface.dis_lan_id] = iface.name
        return adj_hops, lans

    def _run_spf_traced(self) -> None:
        self.spf_run_count += 1
        now = self.loop.clock.now()
        self_key = self.sysid + b"\x00"
        full_req = self._spf_type_full
        self._spf_type_full = False
        cache = self._spt_cache
        with profiling.stage("isis.spf", "topology"):
            pos, model = self._lowering.refresh(self.lsdb, now)
            # RouteOnly (reference spf.rs:744): prefix reachability
            # changed but the IS graph did not — the cached SPTs serve
            # and routes are recomputed; no Dijkstra dispatch.  The
            # live nodes must be the last full run's, in LSDB order.
            route_only = (
                not full_req and cache is not None
                and cache["lt"].model is model
            )
            lt = cache["lt"] if route_only else None
            if lt is None:
                adj_hops, lans = self._root_hops()
                iface_srlg = {
                    i.name: srlg_bits(i.config.srlg)
                    for i in self.interfaces.values() if i.config.srlg
                }
                lt = self._lowering.build(
                    self.lsdb, self_key, now, adj_hops, lans, iface_srlg,
                    keep_unchanged=True,
                )
        spf_type = "route-only" if route_only else "full"
        _ISIS_SPF_RUNS.labels(instance=self.name, type=spf_type).inc()
        if lt is None:
            return  # no LSP of ours yet (reference: SpfRootNotFound)
        if route_only:
            res4, res6 = cache["res4"], cache["res6"]
        else:
            res4, res6 = self._level_spf(lt, cache)
        st4 = lt.spf[0]
        mt6 = MT_IPV6 in lt.spf
        st6 = lt.spf[MT_IPV6] if mt6 else st4
        lsps = self._lowering.lsps(pos)
        with profiling.stage("isis.spf", "derive"):
            routes = self._derive(lt, lsps, res4, res6, mt6)
            self._publish_level(lt, lsps, routes, res4, res6, mt6)
        self.spf_log.append(
            {
                "run": self.spf_run_count,
                "type": spf_type,
                "start-time": now,
                "end-time": self.loop.clock.now(),
                "route-count": len(routes),
            }
        )
        del self.spf_log[:-32]
        if self.route_cb is not None:
            self.route_cb(self.routes)

    def _level_spf(self, lt, cache) -> tuple:
        """The level's SPF results ``(res4, res6)``: the last run's
        where the lowering handed back the last run's object (nothing
        the topology is made from moved) and neither the backend nor a
        knob changed, else one dispatch per topology."""
        knobs = (self.backend, self.frr, self.flooding_reduction)
        if cache is not None and cache["lt"] is lt and cache["knobs"] == knobs:
            _LEVEL_SPF.labels(level=str(self.level), disposition="reused").inc()
            return cache["res4"], cache["res6"]
        # DeltaPath seam (same contract as OSPF): the same vertex model
        # and atom table → diff against the previous run's topology so
        # the resident device graph updates in place.
        with profiling.stage("isis.spf", "link"):
            for mt_id, st in lt.spf.items():
                prev = self._spf_delta_bases.get(mt_id)
                if prev is not st:
                    link_spf_delta(prev, st)
                self._spf_delta_bases[mt_id] = st
        _LEVEL_SPF.labels(level=str(self.level), disposition="dispatched").inc()
        # IS-IS max-paths stays a HOST-side clamp with the reference's
        # lowest-address semantics (spf.rs:920-929, bit-for-bit —
        # conformance replays depend on it), so the dispatch does NOT
        # arm the widened multipath kernel: its UCMP planes would be
        # computed and never read here.
        topo = lt.spf[0].topo
        res4 = self.backend.compute(topo)
        # IP-FRR: the default-topology backup batch rides the full SPF
        # (route-only runs keep the tables — the IS graph is unchanged
        # by definition of RouteOnly).
        frr_cfg = self.frr
        if frr_cfg is not None and frr_cfg.active():
            from holo_tpu.frr.manager import ensure_engine

            self._frr_engine = ensure_engine(self._frr_engine, frr_cfg)
            self.frr_tables = {0: self._frr_engine.compute(topo)}
        else:
            self.frr_tables = {}
        # IPv6 path: routers running MT (RFC 5120) keep IPv6 in topology
        # 2 — a separate graph.  Single-topology routers share the
        # default SPF.
        st6 = lt.spf.get(MT_IPV6)
        res6 = self.backend.compute(st6.topo) if st6 is not None else res4
        if self.flooding_reduction:
            self._flooding_coverage(lt)
        keys = lt.model.keys
        dist = res4.dist
        routers = np.flatnonzero(lt.model.is_router & (dist < INF))
        self.vertex_dist = dict(zip(
            [keys[v][:6] for v in routers.tolist()],
            dist[routers].tolist(),
        ))
        self._spt_cache = {"lt": lt, "knobs": knobs, "res4": res4, "res6": res6}
        return res4, res6

    def _flooding_coverage(self, lt) -> None:
        """Flooding-reduction cache rebuild (reference spf.rs:763-779):
        per-neighbor hop-count SPTs via one multi-root batch."""
        from holo_tpu.protocols.isis.flooding_reduction import (
            neighbor_coverage,
        )

        index = lt.model.index
        nbr_vertex_by_iface = {}
        iface_by_vertex = {}
        sysid_by_vertex = {}
        for iface in self.interfaces.values():
            if iface.is_lan or iface.adj is None:
                continue
            v = index.get(iface.adj.sysid + b"\x00")
            if v is not None and iface.adj.state == AdjacencyState.UP:
                nbr_vertex_by_iface[iface.name] = v
                iface_by_vertex[v] = iface.name
                sysid_by_vertex[v] = iface.adj.sysid
        self._covered_by = {}
        if len(nbr_vertex_by_iface) > 1:
            cov = neighbor_coverage(
                lt.spf[0].topo, self.backend, list(nbr_vertex_by_iface.values())
            )
            for m, others in cov.items():
                self._covered_by[sysid_by_vertex[m]] = {
                    iface_by_vertex[n] for n in others
                }

    # -- route derivation

    @staticmethod
    def _offers4(lsp) -> tuple:
        """An LSP fragment's IPv4 reachability as KeptDerive lowers it:
        its node's key and ``(prefix, rank, external)`` per entry (TLVs
        135, 128, 130 and MT 0 of 235, in that order); the rank puts
        every external path after every internal one (RFC 1195
        §3.10.2)."""
        tlvs = lsp.tlvs
        key = lsp.lsp_id.sysid + bytes((lsp.lsp_id.pseudonode,))
        out = [
            (r.prefix, r.metric + (r.external << _EXTERNAL_SHIFT), r.external)
            for name in ("ext_ip_reach", "narrow_ip_reach", "narrow_ip_ext_reach")
            for r in tlvs.get(name, ())
        ]
        for mt_id, r in tlvs.get("mt_ip_reach", ()):
            if mt_id == 0:
                out.append(
                    (r.prefix, r.metric + (r.external << _EXTERNAL_SHIFT),
                     r.external)
                )
        return key, out

    @staticmethod
    def _offers6(lsp) -> tuple:
        """IPv6 reachability of a single-topology router (TLV 236)."""
        key = lsp.lsp_id.sysid + bytes((lsp.lsp_id.pseudonode,))
        return key, [
            (r.prefix, r.metric, False) for r in lsp.tlvs.get("ipv6_reach", ())
        ]

    @staticmethod
    def _offers6mt(lsp) -> tuple:
        """IPv6 reachability in RFC 5120 topology 2 (TLV 237)."""
        key = lsp.lsp_id.sysid + bytes((lsp.lsp_id.pseudonode,))
        return key, [
            (r.prefix, r.metric, False)
            for mt_id, r in lsp.tlvs.get("mt_ipv6_reach", ())
            if mt_id == MT_IPV6
        ]

    @staticmethod
    def _expand4(words, atoms) -> frozenset:
        return frozenset(
            (atoms[a][0], atoms[a][1]) for a in atom_bits(words, len(atoms))
        )

    @staticmethod
    def _expand6(words, atoms) -> frozenset:
        return frozenset(
            (atoms[a][0], atoms[a][2]) for a in atom_bits(words, len(atoms))
        )

    def _derive(self, lt, lsps: list, res4, res6, mt6: bool) -> dict:
        """``{prefix: IsisRoute}`` of both address families, by
        difference against the last run (``spf_run.KeptDerive``, one
        per family): a prefix none of whose offers sits on a vertex
        whose distance or next-hop row moved, nor in a fragment that
        came, went or was replaced, keeps its route OBJECT.  Of a
        prefix's offers the lowest rank wins and equal ranks unite
        their next hops (spf.rs:907-909 merge_nexthops)."""
        knobs = (self.backend, mt6)
        kept = self._derive_kept
        if kept is None or kept[0] != knobs:
            kept = self._derive_kept = (
                knobs,
                KeptDerive(self._offers4, self._expand4, IsisRoute),
                KeptDerive(
                    self._offers6mt if mt6 else self._offers6,
                    self._expand6, IsisRoute,
                ),
            )
        index, keys = lt.model.index, lt.model.keys
        lsas = [(None, lsp) for lsp in lsps]
        routes: dict = {}
        if "ipv4" in self.afs:
            routes = kept[1].derive(index, keys, lt.spf[0].atoms, res4, lsas)
        if "ipv6" in self.afs:
            st6 = lt.spf.get(MT_IPV6, lt.spf[0])
            routes.update(kept[2].derive(index, keys, st6.atoms, res6, lsas))
        return routes

    def _publish_level(self, lt, lsps, routes, res4, res6, mt6) -> None:
        """The level's table as the rest of the instance reads it: the
        §7.2.9.2 default route, the max-paths clamp, CONNECTED
        prefixes, IP-FRR backups, SR labels, the installable view."""
        root = lt.root
        if self.level == 1 and not self.att_ignore:
            self._attached_default(lt, lsps, routes, res4, res6, mt6)
        if self.max_paths is not None:
            for prefix, r in routes.items():
                if len(r.nexthops) > self.max_paths:
                    routes[prefix] = IsisRoute(
                        prefix, r.dist, self._clamp(r.nexthops),
                        r.prefix_options, r.vertex,
                    )
        # Prefixes whose winning contribution comes from a zero-hop
        # vertex (ourselves): the reference marks these CONNECTED and
        # never installs them (route.rs:86-88,285-301).
        own = [
            lsps[i] for i in np.flatnonzero(lt.model.seg_vertex == root).tolist()
        ]
        connected = set()
        for lsp in own:
            for offers in (self._offers4, self._offers6, self._offers6mt):
                for prefix, _rank, _ext in offers(lsp)[1]:
                    r = routes.get(prefix)
                    if r is not None and r.vertex == root:
                        connected.add(prefix)
        self._frr_backups_of(routes, root, res4, res6, lt, mt6)
        self.routes = routes
        self.connected_prefixes = frozenset(connected)
        self.sr_labels = self._resolve_sr_labels(routes)
        # Published LAST, as one atomic assignment: cross-thread readers
        # (the daemon's marshalled route_cb) get a view built entirely
        # on this thread, never a torn routes/connected combination.
        self.last_installable = self.installable_routes()

    def _clamp(self, nhs) -> frozenset:
        # Reference spf.rs:920-929: deterministic ECMP clamp — keep the
        # lowest next-hop addresses.
        ranked = sorted(
            nhs,
            key=lambda h: (
                h[1] is None,
                h[1].packed if h[1] is not None else b"",
                h[0] or "",
            ),
        )
        return frozenset(ranked[: self.max_paths])

    def _attached_default(self, lt, lsps, routes, res4, res6, mt6) -> None:
        """Level-1 routers that are not themselves attached install a
        per-AF default route toward the nearest attached router(s),
        ECMP across equal-cost exits (ISO 10589 §7.2.9.2; ATT nibble
        0x78 — the reference emits 0x40)."""
        from ipaddress import IPv6Network

        m, root = lt.model, lt.root
        if lt.att[root] and not mt6:
            return  # we are an exit ourselves in every topology
        protos: dict = {}
        mt_bits: dict = {}
        for lsp, v in zip(lsps, m.seg_vertex.tolist()):
            tlvs = lsp.tlvs
            if tlvs.get("protocols_supported"):
                protos.setdefault(v, set()).update(tlvs["protocols_supported"])
            for mt_id, att, ovl in tlvs.get("mt_ids", ()):
                mt_bits.setdefault(v, {})[mt_id] = (att, ovl)
        for want_v6, res_, atoms_, proto, default in (
            (False, res4, lt.spf[0].atoms, 0xCC, IPv4Network("0.0.0.0/0")),
            (True, res6, lt.spf.get(MT_IPV6, lt.spf[0]).atoms, 0x8E,
             IPv6Network("::/0")),
        ):
            if ("ipv6" if want_v6 else "ipv4") not in self.afs:
                continue  # address family disabled
            mt_id = MT_IPV6 if (want_v6 and mt6) else 0
            if mt_id == 0:
                att, ovl = lt.att, lt.overload
            else:
                bits = [mt_bits.get(v, {}).get(mt_id, (False, False))
                        for v in range(len(m.keys))]
                att = np.array([b[0] for b in bits], bool)
                ovl = np.array([b[1] for b in bits], bool)
            if att[root]:
                continue  # we are an exit ourselves in this topology
            # Reference spf.rs:870-876: att && !overload — an overloaded
            # exit must not attract default traffic.
            exits = att & ~ovl & m.is_router & (res_.dist < INF)
            exits[root] = False
            best, nhs = None, frozenset()
            for v in np.flatnonzero(exits).tolist():
                if protos.get(v) and proto not in protos[v]:
                    continue  # exit must route this address family
                d = int(res_.dist[v])
                cur = (self._expand6 if want_v6 else self._expand4)(
                    res_.nexthop_words[v], atoms_
                )
                if best is None or d < best:
                    best, nhs = d, cur
                elif d == best:
                    nhs |= cur
            if best is None:
                continue
            r = routes.get(default)
            if r is None or best < r.dist:
                routes[default] = IsisRoute(default, best, nhs, False, -1)
            elif best == r.dist:
                routes[default] = IsisRoute(
                    default, best, r.nexthops | nhs, r.prefix_options,
                    r.vertex,
                )

    def _frr_backups_of(self, routes, root, res4, res6, lt, mt6) -> None:
        """IP-FRR: join the default-topology backup table onto the route
        table.  Direct LFAs only (no SR tunnel encapsulation wired for
        the repair path here); the MT-2 IPv6 overlay is a separate
        graph the default-topology table does not cover."""
        self.frr_backups = {}
        frr_cfg = self.frr
        table = self.frr_tables.get(0)
        if frr_cfg is None or not frr_cfg.active() or table is None:
            return
        from holo_tpu.frr.manager import repair_picks, repair_plan

        plan = repair_plan(table, frr_cfg)
        # per address family: vertex -> {primary hop: (its LFA's hop, ())}
        lfas: dict = {}

        def lfas_of(want_v6: bool) -> dict:
            got = lfas.get(want_v6)
            if got is not None:
                return got
            res_ = res6 if want_v6 else res4
            atoms_ = lt.spf.get(MT_IPV6, lt.spf[0]).atoms if want_v6 else (
                lt.spf[0].atoms
            )
            vs, ats, kind, release, _v0, _v1 = repair_picks(
                table, plan, res_.nexthop_words
            )
            got = lfas[want_v6] = {}
            lfa = kind == 1
            for v, a, r in zip(
                vs[lfa].tolist(), ats[lfa].tolist(), release[lfa].tolist()
            ):
                ifn, p4, p6 = atoms_[a]
                bifn, b4, b6 = atoms_[r]
                paddr, baddr = (p6, b6) if want_v6 else (p4, b4)
                if paddr is None or baddr is None:
                    continue
                got.setdefault(v, {})[(ifn, paddr)] = ((bifn, baddr), ())
            return got

        for prefix, r in routes.items():
            v = r.vertex
            if v < 0 or v == root:
                continue
            want_v6 = prefix.version == 6
            if want_v6 and mt6:
                continue
            backups = lfas_of(want_v6).get(v)
            if backups:
                self.frr_backups[prefix] = dict(backups)

    def installable_routes(self) -> dict:
        """The RIB-feed view of :attr:`routes` (route.rs:285-301):
        CONNECTED prefixes never install, and a route without nexthops
        (nexthop computation error) must leave the global RIB.  A copy
        less the few left out: a dict's copy keeps the keys' hashes,
        and an ``ip_network`` hashes in Python."""
        return _less(self.routes, self.connected_prefixes, frozenset())

    def _resolve_sr_labels(self, routes: dict) -> dict:
        """prefix -> (local label, route) for every prefix-SID heard,
        resolved through our SRGB (holo-isis/src/spf.rs:931-946)."""
        if self.sr is None or not self.sr.enabled:
            return {}
        out = {}
        for e in self.lsdb.values():
            if e.lsp.is_expired:
                continue
            entries = list(e.lsp.tlvs.get("ext_ip_reach", []))
            entries += [r for _mt, r in e.lsp.tlvs.get("mt_ip_reach", [])]
            for r in entries:
                idx = getattr(r, "sid_index", None)
                if idx is None:
                    continue
                label = self.sr.srgb.label_of(idx)
                route = routes.get(r.prefix)
                if label is not None and route is not None:
                    out[r.prefix] = (label, route)
        return out
