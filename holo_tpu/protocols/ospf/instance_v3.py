"""OSPFv3 instance actor (RFC 5340): p2p circuits, v6 routing.

Reference: holo-ospf's ospfv3 side of the Version trait.  Shares the
neighbor NSM (neighbor.py) and the DD/flooding semantics with the v2
instance; differs where the protocol differs — link-local transport,
router-id keyed hellos, LSA types with flooding scopes, prefixes carried
in Link / Intra-Area-Prefix LSAs, and the SPF topology built from router
links keyed by (router-id, interface-id).

Scope: p2p + broadcast interfaces (router-id DR election with a
Waiting/BackupSeen analog, network LSAs, network-referenced
Intra-Area-Prefix LSAs), multi-area ABR with area address ranges,
stub areas, externals; intra-area v6 routes over router AND network
vertices.  What the two versions share beyond the NSM lives in
``spf_run.py``, as the reference shares it over its ``Version`` trait:
the RFC 8405 SPF-delay FSM, the ranges, the kept LSDB lowering and the
DeltaPath seam; an SPF run has the v2 instance's host stages.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from ipaddress import IPv4Address, IPv6Address, IPv6Network

import numpy as np

from holo_tpu import telemetry
from holo_tpu.ops.graph import INF, Topology
from holo_tpu.protocols.ospf import packet_v3 as P
from holo_tpu.protocols.ospf.instance import (
    _OSPF_NBR_TRANSITIONS,
    _OSPF_PACKETS,
    _OSPF_RX_BAD,
    _OSPF_SPF_RUNS,
)
from holo_tpu.protocols.ospf.interface import ElectionView, IfType, elect_dr_bdr
from holo_tpu.protocols.ospf.lsdb import (
    LS_REFRESH_TIME,
    MIN_LS_ARRIVAL,
    AgeScan,
    Lsdb,
    next_seq_no,
)
from holo_tpu.protocols.ospf.spf_run import (
    KeptDerive,
    KeptInter,
    LoweredLsdbV3,
    NexthopAtom,
    SpfDelayFsm,
    SpfTimers,
    atom_bits,
    legacy_spf_timers,
    link_spf_delta,
    srlg_bits,
)
from holo_tpu.protocols.ospf.neighbor import (
    Neighbor,
    NsmEvent,
    NsmState,
    nsm_transition,
)
from holo_tpu.routing.sink import table_delta
from holo_tpu.spf.backend import ScalarSpfBackend, SpfBackend
from holo_tpu.telemetry import convergence, profiling
from holo_tpu.utils.ip import ALL_SPF_RTRS_V6
from holo_tpu.utils.netio import NetIo, NetRxPacket
from holo_tpu.utils.runtime import Actor

DD_CHUNK = 64
AGE_TICK = 1.0

_AREA_SPF = telemetry.counter(
    "holo_ospf_area_spf_total",
    "OSPFv3 areas of a full SPF run by what the run did with them: "
    "dispatched to the backend, or reused from the last run because "
    "none of the area's inputs had changed",
    ("disposition",),
)
_RIB_DELTA_ROUTES = telemetry.histogram(
    "holo_ospf_rib_delta_routes",
    "Routes an OSPFv3 SPF run handed to its route sink (the whole "
    "table through route_cb, or the changed and withdrawn prefixes "
    "through route_delta_cb)",
    buckets=(1, 4, 16, 64, 256, 1024, 4096, 16384, 65536),
)


@dataclass
class V3IfConfig:
    area_id: IPv4Address = IPv4Address(0)
    cost: int = 10
    hello_interval: int = 10
    dead_interval: int = 40
    rxmt_interval: int = 5
    mtu: int = 1500
    # RFC 2328 §10.6 / RFC 5340: DD Interface-MTU check bypass and the
    # §13.3 InfTransDelay LSA age increment (ietf-ospf interface leaves).
    mtu_ignore: bool = False
    transmit_delay: int = 1
    instance_id: int = 0
    if_type: IfType = IfType.POINT_TO_POINT
    priority: int = 1
    loopback: bool = False
    # Passive circuits advertise their prefixes but exchange no packets.
    passive: bool = False
    auth: object = None  # packet_v3.AuthCtxV3 or None (RFC 7166 trailer)
    # Fast-reroute SRLG membership (see IfConfig.srlg): lowered to
    # Topology.edge_srlg at SPF marshal time for the FRR policy masks.
    srlg: tuple = ()


@dataclass
class V3Interface:
    name: str
    config: V3IfConfig
    iface_id: int
    link_local: IPv6Address
    prefixes: list[IPv6Network] = field(default_factory=list)
    # Link-scope LSDB (RFC 5340 §4.4.2: Link LSAs live per circuit).
    link_lsdb: Lsdb = field(default_factory=Lsdb)
    up: bool = False
    neighbors: dict[IPv4Address, Neighbor] = field(default_factory=dict)
    # LAN state (RFC 5340 identifies DR/BDR by ROUTER-ID, not address).
    dr: IPv4Address = IPv4Address(0)
    bdr: IPv4Address = IPv4Address(0)
    # §9.4 Waiting state: no self-election until the wait timer expires
    # or a neighbor declares an existing DR/BDR (BackupSeen).
    wait_until: float = 0.0
    up_since: float = -1e9
    # RFC 7166 replay protection: highest verified seqno per neighbor.
    at_seqnos: dict = field(default_factory=dict)

    @property
    def is_lan(self) -> bool:
        return self.config.if_type == IfType.BROADCAST


@dataclass
class HelloTimerV3:
    ifname: str


@dataclass
class InactivityTimerV3:
    ifname: str
    nbr_id: IPv4Address


@dataclass
class RxmtTimerV3:
    ifname: str
    nbr_id: IPv4Address


@dataclass
class SpfTimerV3:
    pass


@dataclass
class SpfHoldDownV3:
    pass


@dataclass
class WaitTimerV3:
    ifname: str


@dataclass
class AgeTickV3:
    pass


@dataclass
class V3IfUpMsg:
    ifname: str


@dataclass
class V3IfDownMsg:
    ifname: str


@dataclass
class V6Route:
    prefix: IPv6Network
    dist: int
    nexthops: frozenset  # {(ifname, link-local addr)}
    # ietf-ospf route-type identity for the local-rib state plane.
    route_type: str = "intra-area"
    # Prefix options from the originating LSA entry (LA propagates into
    # the ABR's inter-area advertisement, like the reference).
    prefix_options: int = 0
    # Area that contributed the winning path (None for external).
    area_id: object = None
    # SPT vertex the winning path terminates at (-1 when not derived
    # from an SPT vertex) — the IP-FRR consumption key.
    vertex: int = -1
    # IP-FRR repairs: {primary (ifname, ll-addr) -> (backup, labels)}.
    backups: dict | None = None


@dataclass
class V3Area:
    """One OSPFv3 area: its LSDB plus type flags (RFC 5340 areas carry
    the same stub/NSSA semantics as v2, with v6 LSA types)."""

    area_id: IPv4Address
    lsdb: Lsdb = field(default_factory=Lsdb)
    stub: bool = False
    nssa: bool = False
    # ietf-ospf summary=false: a totally-stubby area gets ONLY the
    # default inter-area-prefix from its ABRs.
    summary: bool = True
    stub_default_cost: int = 10  # ietf-ospf default-cost default
    # RFC 2328 area address ranges (RFC 5340 keeps §12.4.3): [{prefix,
    # advertise, cost}], the v2 Area's shape; intra-area prefixes under
    # a range are advertised into other areas as the range alone.
    ranges: list = field(default_factory=list)

    @property
    def no_external(self) -> bool:
        return self.stub or self.nssa


class OspfV3Instance(SpfDelayFsm, Actor):
    """One OSPFv3 routing process: multi-area ABR (inter-area-prefix
    LSAs), stub areas, externals, LAN + p2p circuits."""

    name = "ospfv3"

    def __init__(
        self,
        name: str,
        router_id: IPv4Address,
        netio: NetIo,
        spf_backend: SpfBackend | None = None,
        route_cb=None,
        notif_cb=None,
        nvstore=None,
        spf_timers: SpfTimers | None = None,
        route_delta_cb=None,
    ):
        self.name = name
        self.router_id = router_id
        self.netio = netio
        self.backend = spf_backend or ScalarSpfBackend()
        # RFC 8405 SPF-delay timers (the FSM is spf_run.SpfDelayFsm,
        # shared with the v2 instance).
        self.spf_timers = spf_timers or legacy_spf_timers()
        # ``route_cb(routes)`` gets the whole table after every run;
        # ``route_delta_cb(changed, removed)``, when set, gets only the
        # prefixes whose route changed or went (and route_cb is not
        # called): the sink then does no whole-table compare.
        self.route_cb = route_cb
        self.route_delta_cb = route_delta_cb
        self.notif_cb = notif_cb
        self.interfaces: dict[str, V3Interface] = {}
        self.areas: dict[IPv4Address, V3Area] = {}
        self.routes: dict[IPv6Network, V6Route] = {}
        # Configured virtual links [(transit area id, peer router id)];
        # when empty, vlink peers are discovered from our backbone
        # router-LSA and the best transit area is reported.
        self.vlink_config: list = []
        # Vlink endpoint state rows (ietf-ospf virtual-links render).
        self.vlink_state: list = []
        # v6 prefixes we redistribute as AS-external LSAs (ASBR duty).
        self.redistributed: dict[IPv6Network, int] = {}  # prefix -> metric
        self.spf_run_count = 0
        # IP fast reroute (holo_tpu.frr.FrrConfig; None = disabled) and
        # the per-area backup tables the area SPF refreshes.
        self.frr = None
        self.frr_tables: dict = {}
        self._frr_engine = None
        # ietf-ospf max-paths (ISSUE 10): None = unlimited ECMP;
        # 2..8 arms the vectorized multipath dispatch (same contract
        # as the v2 instance's config.max_paths).
        self.max_paths: int | None = None
        # DeltaPath: the previous run's marshaled SpfTopologyV3 per
        # area — the diff base for in-place device-graph updates — and
        # the area's kept lowering (only LSAs installed since the last
        # run are lowered again).
        self._spf_delta_bases: dict = {}
        self._spf_lowerings: dict = {}
        # The ABR's inter-area stage by difference: what each area's
        # intra-area table advertises into the others (ranges applied),
        # the summaries wanted and our LSAs of them, between runs.
        self._inter_kept = KeptInter(
            (P.LsaType.INTER_AREA_ROUTER, P.LsaType.INTER_AREA_PREFIX),
            IPv6Network("::/0"),
            P.LsaType.ROUTER,
            self._asbr_of,
            self._asbr_distance,
            self._inter_key,
            self._inter_originate,
            lambda aid, key: self._flush_self(self.areas[aid], key),
        )
        self._active_ranges: set = set()
        # An area whose SPF inputs did not change since the last full
        # run keeps its result and its intra-area table and is not
        # dispatched (False: every area is dispatched in every run, the
        # control arm of the tests and of PERF.md section 6).
        self.reuse_unchanged_areas = True
        self._area_kept: dict = {}  # aid -> (st, backend, knobs, out, intra)
        # aid -> (backend, knobs, spf_run.KeptDerive): the area's last
        # intra-area derive, which the next one differs against
        self._derive_kept: dict = {}
        self._age_scans: dict = {}  # aid -> lsdb.AgeScan of the area's LSDB
        # Hierarchical partition hint (ISSUE 15): router-id -> group
        # label lowered through spf_run.apply_partition_hint at the
        # marshal seam (same contract as the v2 instance).
        self.spf_partition_of: dict | None = None
        # RFC 6987 stub-router: MaxLinkMetric on transit/p2p router-LSA
        # links (maintenance mode; same leaf as the v2 instance).
        self.stub_router = False
        # Full-vs-partial classification (reference ospfv3/spf.rs:97-163):
        # changed LSAs accumulate as (new, old) pairs; non-LSA events
        # force Full.  The cache keeps the last full run's SPTs + route
        # tables for prefix-scoped partial updates (route.rs:200-333).
        self._spf_triggers: list = []
        self._spf_force_full = True
        self._spf_cache: dict | None = None
        # Convergence-observatory causal ids pending on the next run.
        self._conv_pending: list = []
        # SPF run log ring (reference spf.rs:770-804).
        self.spf_log: list[dict] = []
        self._dd_seq = 0x3000
        self._next_iface_id = 1
        self._timers: dict[tuple, object] = {}
        self._inter_ids: dict = {}  # summarized prefix/asbr -> lsid
        # RFC 7166 64-bit tx sequence number: restart-safe via a durable
        # reservation ceiling (same scheme as the v2 instance).
        self._nvstore = nvstore
        self._at_key = f"ospfv3/{name}/at-seqno-ceiling"
        self._at_reserved = 0
        if nvstore is not None:
            self._at_seqno = int(nvstore.get(self._at_key, 0))
            self._reserve_at_seqnos()
        else:
            self._at_seqno = 0

    _AT_WINDOW = 1 << 16

    def _reserve_at_seqnos(self) -> None:
        self._at_reserved = self._at_seqno + self._AT_WINDOW
        self._nvstore.put(self._at_key, self._at_reserved)

    def attach(self, loop_):
        super().attach(loop_)
        self._age_timer = self.loop.timer(self.name, AgeTickV3)
        self._age_timer.start(AGE_TICK)
        self._spf_timer = self.loop.timer(self.name, SpfTimerV3)
        self._hold_timer = self.loop.timer(self.name, SpfHoldDownV3)

    def add_interface(
        self,
        ifname: str,
        cfg: V3IfConfig,
        link_local: IPv6Address,
        prefixes: list[IPv6Network],
        stub: bool = False,
        nssa: bool = False,
        stub_default_cost: int = 10,
        summary: bool = True,
    ) -> V3Interface:
        assert not (stub and nssa), "area cannot be both stub and NSSA"
        area = self.areas.get(cfg.area_id)
        if area is None:
            area = V3Area(cfg.area_id, stub=stub, nssa=nssa,
                          summary=summary,
                          stub_default_cost=stub_default_cost)
            self.areas[cfg.area_id] = area
        else:
            area.stub = stub
            area.nssa = nssa
            area.summary = summary
            area.stub_default_cost = stub_default_cost
        iface = V3Interface(
            name=ifname,
            config=cfg,
            iface_id=self._next_iface_id,
            link_local=link_local,
            prefixes=list(prefixes),
        )
        self._next_iface_id += 1
        self.interfaces[ifname] = iface
        return iface

    @property
    def is_abr(self) -> bool:
        return len(self.areas) > 1

    @property
    def lsdb(self) -> Lsdb:
        """Single-area convenience view: the backbone (or only) area."""
        backbone = self.areas.get(IPv4Address(0))
        if backbone is not None:
            return backbone.lsdb
        return next(iter(self.areas.values())).lsdb

    def _area_of(self, iface: V3Interface) -> V3Area:
        return self.areas[iface.config.area_id]

    def _area_ifaces(self, area: "V3Area"):
        return (
            i
            for i in self.interfaces.values()
            if i.config.area_id == area.area_id
        )

    # -- actor

    def handle(self, msg):
        if isinstance(msg, NetRxPacket):
            self._rx(msg)
        elif isinstance(msg, HelloTimerV3):
            self._send_hello(msg.ifname)
        elif isinstance(msg, InactivityTimerV3):
            self._nbr_event(msg.ifname, msg.nbr_id, NsmEvent.INACTIVITY_TIMER)
        elif isinstance(msg, RxmtTimerV3):
            self._rxmt(msg.ifname, msg.nbr_id)
        elif isinstance(msg, SpfTimerV3):
            self.run_spf()
        elif isinstance(msg, SpfHoldDownV3):
            self._spf_holddown_fired()
        elif isinstance(msg, WaitTimerV3):
            iface = self.interfaces.get(msg.ifname)
            if iface is not None and iface.up and iface.is_lan:
                iface.wait_until = 0.0
                self._run_dr_election(iface)
        elif isinstance(msg, AgeTickV3):
            self._age_tick()
        elif isinstance(msg, V3IfUpMsg):
            self.if_up(msg.ifname)
        elif isinstance(msg, V3IfDownMsg):
            self.if_down(msg.ifname)

    def if_up(self, ifname: str) -> None:
        iface = self.interfaces.get(ifname)
        if iface is None or iface.up:
            return
        iface.up = True
        if iface.is_lan and not iface.config.passive:
            # §9.4 Waiting: listen for an incumbent DR before claiming.
            iface.up_since = self.loop.clock.now()
            iface.wait_until = (
                self.loop.clock.now() + iface.config.dead_interval
            )
            self._timer(
                ("wait", ifname), lambda: WaitTimerV3(ifname)
            ).start(iface.config.dead_interval)
        self._send_hello(ifname)
        self._originate_router_lsa()
        self._originate_intra_area_prefix()

    def if_down(self, ifname: str) -> None:
        iface = self.interfaces.get(ifname)
        if iface is None or not iface.up:
            return
        iface.up = False  # before the kills: elections no-op on a dead iface
        for nbr_id in list(iface.neighbors):
            self._nbr_event(ifname, nbr_id, NsmEvent.KILL_NBR)
        iface.dr = IPv4Address(0)
        iface.bdr = IPv4Address(0)
        for key in (("hello", ifname),):
            t = self._timers.get(key)
            if t:
                t.cancel()
        self._originate_router_lsa()
        self._originate_intra_area_prefix()
        self._schedule_spf()

    # -- timers

    def _timer(self, key, fn):
        t = self._timers.get(key)
        if t is None:
            t = self.loop.timer(self.name, fn)
            self._timers[key] = t
        return t

    # -- hello

    def _send_hello(self, ifname: str) -> None:
        iface = self.interfaces.get(ifname)
        if iface is None or not iface.up or iface.config.passive:
            return
        opts = P.Options.V6 | P.Options.R
        if not self._area_of(iface).no_external:
            opts |= P.Options.E
        hello = P.Hello(
            iface_id=iface.iface_id,
            priority=iface.config.priority,
            options=opts,
            hello_interval=iface.config.hello_interval,
            dead_interval=iface.config.dead_interval,
            dr=iface.dr,
            bdr=iface.bdr,
            neighbors=[n.router_id for n in iface.neighbors.values()
                       if n.state >= NsmState.INIT],
        )
        self._send(iface, ALL_SPF_RTRS_V6, hello)
        self._timer(("hello", ifname), lambda: HelloTimerV3(ifname)).start(
            iface.config.hello_interval
        )

    def _rx_hello(self, iface: V3Interface, src, pkt) -> None:
        h = pkt.body
        if (
            h.hello_interval != iface.config.hello_interval
            or h.dead_interval != iface.config.dead_interval
        ):
            return
        # §10.5 E-bit agreement: both sides must agree on the area's
        # external capability (stub misconfig detection).
        want_e = not self._area_of(iface).no_external
        if bool(h.options & P.Options.E) != want_e:
            return
        nbr = iface.neighbors.get(pkt.router_id)
        if nbr is None:
            nbr = Neighbor(router_id=pkt.router_id, src=src)
            iface.neighbors[pkt.router_id] = nbr
        nbr.src = src  # link-local — the v6 next hop
        changed = (h.priority, h.dr, h.bdr) != (nbr.priority, nbr.dr, nbr.bdr)
        nbr.priority = h.priority
        nbr.iface_id = h.iface_id
        nbr.dr, nbr.bdr = h.dr, h.bdr
        self._nbr_event(iface.name, pkt.router_id, NsmEvent.HELLO_RECEIVED)
        self._timer(
            ("inactivity", iface.name, pkt.router_id),
            lambda: InactivityTimerV3(iface.name, pkt.router_id),
        ).start(iface.config.dead_interval)
        was_2way = nbr.state >= NsmState.TWO_WAY
        if self.router_id in h.neighbors:
            self._nbr_event(iface.name, pkt.router_id, NsmEvent.TWO_WAY_RECEIVED)
        else:
            self._nbr_event(iface.name, pkt.router_id, NsmEvent.ONE_WAY_RECEIVED)
        if iface.is_lan:
            now_2way = (
                pkt.router_id in iface.neighbors
                and iface.neighbors[pkt.router_id].state >= NsmState.TWO_WAY
            )
            if changed or was_2way != now_2way:
                self._run_dr_election(iface)

    # -- DR election (RFC 5340 §4.2.1.1: §9.4 with router-ids)

    def _run_dr_election(self, iface: V3Interface) -> None:
        if not iface.up or iface.config.passive:
            return
        if self.loop.clock.now() < iface.wait_until:
            # BackupSeen: an established DR/BDR declared by a 2-Way
            # neighbor ends Waiting early; otherwise keep listening.
            if any(
                n.state >= NsmState.TWO_WAY and (int(n.dr) or int(n.bdr))
                for n in iface.neighbors.values()
            ):
                iface.wait_until = 0.0
            else:
                return
        # Partial-view guard, active only in the first DeadInterval after
        # coming up: a 2-Way neighbor names an incumbent DR we have not
        # heard from yet (its hello is still in flight after our rejoin).
        # Electing now would self-promote and preempt it — defer until
        # the incumbent is in view.  Outside that window the named DR is
        # genuinely dead and elections must proceed (failover).
        if (
            self.loop.clock.now()
            < iface.up_since + iface.config.dead_interval
        ):
            twoway = {
                n.router_id: n
                for n in iface.neighbors.values()
                if n.state >= NsmState.TWO_WAY
            }
            for n in twoway.values():
                if (
                    int(n.dr)
                    and n.dr != self.router_id
                    and n.dr not in twoway
                ):
                    return
        for _ in range(2):  # §9.4 step 4: rerun when our own role changes
            views = [
                ElectionView(
                    iface.config.priority,
                    self.router_id,
                    self.router_id,  # v3 elects by router-id, not address
                    iface.dr,
                    iface.bdr,
                )
            ]
            for nbr in iface.neighbors.values():
                if nbr.state >= NsmState.TWO_WAY:
                    views.append(
                        ElectionView(
                            nbr.priority, nbr.router_id, nbr.router_id,
                            nbr.dr, nbr.bdr,
                        )
                    )
            new_dr, new_bdr = elect_dr_bdr(views)
            changed = (new_dr, new_bdr) != (iface.dr, iface.bdr)
            iface.dr, iface.bdr = new_dr, new_bdr
            if not changed:
                break
        # AdjOK? — the adjacency set depends on who is DR/BDR.
        for nbr_id in list(iface.neighbors):
            if iface.neighbors[nbr_id].state >= NsmState.TWO_WAY:
                self._nbr_event(iface.name, nbr_id, NsmEvent.ADJ_OK)
        self._originate_router_lsa()
        self._originate_network_lsa(iface)
        self._originate_intra_area_prefix()

    def _adj_ok(self, iface: V3Interface, nbr: Neighbor) -> bool:
        """p2p always; LAN only with/as the DR or BDR (§10.4)."""
        if not iface.is_lan:
            return True
        return (
            iface.dr in (self.router_id, nbr.router_id)
            or iface.bdr in (self.router_id, nbr.router_id)
        )

    # -- NSM plumbing

    def _nbr_event(self, ifname: str, nbr_id, event: NsmEvent) -> None:
        iface = self.interfaces.get(ifname)
        if iface is None:
            return
        nbr = iface.neighbors.get(nbr_id)
        if nbr is None:
            return
        old_state = nbr.state
        res = nsm_transition(nbr, event, adj_ok=self._adj_ok(iface, nbr))
        nbr.state = res.new_state
        if nbr.state != old_state:
            from holo_tpu.protocols.ospf.nb_state import _NSM_NAME

            _OSPF_NBR_TRANSITIONS.labels(
                instance=self.name, to=_NSM_NAME[nbr.state]
            ).inc()
        if nbr.state != old_state and self.notif_cb is not None:
            # Reference holo-ospf northbound/notification.rs (shared by
            # both versions): same shape as the v2 instance's notify.
            from holo_tpu.protocols.ospf.nb_state import _NSM_NAME

            self.notif_cb({
                "ietf-ospf:nbr-state-change": {
                    "routing-protocol-name": self.name,
                    "address-family": "ipv6",
                    "interface": {"interface": iface.name},
                    "neighbor-router-id": str(nbr.router_id),
                    "neighbor-ip-addr": str(nbr.src),
                    "state": _NSM_NAME[nbr.state],
                }
            })
        for act in res.actions:
            if act == "start_exstart":
                self._start_exstart(iface, nbr)
            elif act == "send_dd_summary":
                self._enter_exchange(iface, nbr)
            elif act == "send_ls_request":
                self._send_ls_request(iface, nbr)
            elif act == "clear_lists":
                nbr.ls_request.clear()
                nbr.ls_rxmt.clear()
                nbr.dd_summary.clear()
            elif act == "stop_timers":
                for key in ("inactivity", "rxmt"):
                    t = self._timers.get((key, ifname, nbr_id))
                    if t:
                        t.cancel()
            elif act == "full":
                t = self._timers.get(("rxmt", ifname, nbr_id))
                if t:
                    t.cancel()
        if nbr.state == NsmState.DOWN:
            del iface.neighbors[nbr_id]
            iface.at_seqnos.pop(nbr_id, None)
            if iface.is_lan:
                self._run_dr_election(iface)
        if (old_state >= NsmState.FULL) != (nbr.state >= NsmState.FULL) or (
            nbr.state == NsmState.DOWN
        ):
            self._originate_router_lsa()
            if iface.is_lan:
                self._originate_network_lsa(iface)
            self._originate_intra_area_prefix()

    # -- DD exchange (same semantics as v2; v3 codec)

    def _start_exstart(self, iface: V3Interface, nbr: Neighbor) -> None:
        self._dd_seq += 1
        nbr.dd_seq_no = self._dd_seq
        nbr.master = True
        dd = P.DbDesc(
            mtu=iface.config.mtu,
            options=P.Options.V6 | P.Options.E | P.Options.R,
            flags=P.DbDescFlags.I | P.DbDescFlags.M | P.DbDescFlags.MS,
            dd_seq_no=nbr.dd_seq_no,
        )
        nbr.last_sent_dd = dd
        self._send(iface, nbr.src, dd)
        self._arm_rxmt(iface, nbr)

    def _enter_exchange(self, iface: V3Interface, nbr: Neighbor) -> None:
        now = self.loop.clock.now()
        # Link-scope LSAs are excluded: they must only be exchanged with
        # neighbors on their own link (RFC 5340 §4.5; origin-link tracking
        # lands with Link-LSA origination).
        nbr.dd_summary = [
            e.lsa
            for e in self._area_of(iface).lsdb.entries.values()
            if e.current_age(now) < P.MAX_AGE
            and P.scope_of(int(e.lsa.type)) != "link"
        ]

    def _send_dd(self, iface: V3Interface, nbr: Neighbor) -> None:
        chunk = nbr.dd_summary[:DD_CHUNK]
        more = len(nbr.dd_summary) > len(chunk)
        flags = P.DbDescFlags(0)
        if nbr.master:
            flags |= P.DbDescFlags.MS
        if more:
            flags |= P.DbDescFlags.M
        dd = P.DbDesc(
            mtu=iface.config.mtu,
            options=P.Options.V6 | P.Options.E | P.Options.R,
            flags=flags,
            dd_seq_no=nbr.dd_seq_no,
            lsa_headers=chunk,
        )
        nbr.last_sent_dd = dd
        self._send(iface, nbr.src, dd)
        if nbr.master:
            self._arm_rxmt(iface, nbr)

    def _rx_db_desc(self, iface: V3Interface, src, pkt) -> None:
        dd = pkt.body
        nbr = iface.neighbors.get(pkt.router_id)
        if nbr is None or nbr.state < NsmState.EX_START:
            return
        # RFC 2328 §10.6 (per RFC 5340 §4.2.2 unchanged): reject a DD
        # whose Interface MTU exceeds ours, unless mtu-ignore is set.
        if dd.mtu > iface.config.mtu and not iface.config.mtu_ignore:
            return
        F = P.DbDescFlags
        if nbr.state == NsmState.EX_START:
            negotiated = False
            if (
                dd.flags == F.I | F.M | F.MS
                and not dd.lsa_headers
                and int(pkt.router_id) > int(self.router_id)
            ):
                nbr.master = False
                nbr.dd_seq_no = dd.dd_seq_no
                negotiated = True
            elif (
                not (dd.flags & F.I)
                and not (dd.flags & F.MS)
                and dd.dd_seq_no == nbr.dd_seq_no
                and int(pkt.router_id) < int(self.router_id)
            ):
                nbr.master = True
                negotiated = True
            if not negotiated:
                return
            self._nbr_event(iface.name, pkt.router_id, NsmEvent.NEGOTIATION_DONE)
            nbr = iface.neighbors.get(pkt.router_id)
            if nbr is None or nbr.state != NsmState.EXCHANGE:
                return
            nbr.last_dd = (dd.flags, dd.options, dd.dd_seq_no)
            self._process_dd_headers(iface, nbr, dd)
            if nbr.master:
                # Master always sends its first data DD — the slave can
                # only conclude the exchange from a master DD with M clear.
                nbr.dd_seq_no += 1
                self._send_dd(iface, nbr)
            else:
                self._slave_reply(iface, nbr, dd)
            return
        if nbr.state != NsmState.EXCHANGE:
            if (
                nbr.state in (NsmState.LOADING, NsmState.FULL)
                and not nbr.master
                and nbr.last_dd == (dd.flags, dd.options, dd.dd_seq_no)
            ):
                if nbr.last_sent_dd is not None:
                    self._send(iface, nbr.src, nbr.last_sent_dd)
                return
            if nbr.state in (NsmState.LOADING, NsmState.FULL):
                self._nbr_event(iface.name, pkt.router_id, NsmEvent.SEQ_NUMBER_MISMATCH)
            return
        if nbr.last_dd == (dd.flags, dd.options, dd.dd_seq_no):
            if not nbr.master and nbr.last_sent_dd is not None:
                self._send(iface, nbr.src, nbr.last_sent_dd)
            return
        if bool(dd.flags & F.MS) == nbr.master or dd.flags & F.I:
            self._nbr_event(iface.name, pkt.router_id, NsmEvent.SEQ_NUMBER_MISMATCH)
            return
        if nbr.master:
            if dd.dd_seq_no != nbr.dd_seq_no:
                self._nbr_event(iface.name, pkt.router_id, NsmEvent.SEQ_NUMBER_MISMATCH)
                return
            nbr.last_dd = (dd.flags, dd.options, dd.dd_seq_no)
            self._process_dd_headers(iface, nbr, dd)
            nbr.dd_summary = nbr.dd_summary[len(nbr.dd_summary[:DD_CHUNK]) :]
            nbr.dd_seq_no += 1
            if not nbr.dd_summary and not (dd.flags & F.M):
                self._nbr_event(iface.name, pkt.router_id, NsmEvent.EXCHANGE_DONE)
            else:
                self._send_dd(iface, nbr)
        else:
            nbr.last_dd = (dd.flags, dd.options, dd.dd_seq_no)
            self._process_dd_headers(iface, nbr, dd)
            self._slave_reply(iface, nbr, dd)

    def _slave_reply(self, iface: V3Interface, nbr: Neighbor, dd) -> None:
        nbr.dd_seq_no = dd.dd_seq_no
        chunk = nbr.dd_summary[:DD_CHUNK]
        nbr.dd_summary = nbr.dd_summary[len(chunk) :]
        flags = P.DbDescFlags(0)
        if nbr.dd_summary:
            flags |= P.DbDescFlags.M
        reply = P.DbDesc(
            mtu=iface.config.mtu,
            options=P.Options.V6 | P.Options.E | P.Options.R,
            flags=flags,
            dd_seq_no=nbr.dd_seq_no,
            lsa_headers=chunk,
        )
        nbr.last_sent_dd = reply
        self._send(iface, nbr.src, reply)
        if not (dd.flags & P.DbDescFlags.M) and not (flags & P.DbDescFlags.M):
            self._nbr_event(iface.name, nbr.router_id, NsmEvent.EXCHANGE_DONE)

    def _process_dd_headers(self, iface: V3Interface, nbr: Neighbor, dd) -> None:
        lsdb = self._area_of(iface).lsdb
        for hdr in dd.lsa_headers:
            cur = lsdb.get(hdr.key)
            if cur is None or hdr.compare(cur.lsa) > 0:
                nbr.ls_request[hdr.key] = hdr

    # -- request / update / ack / flooding

    def _send_ls_request(self, iface: V3Interface, nbr: Neighbor) -> None:
        keys = list(nbr.ls_request.keys())[:DD_CHUNK]
        if keys:
            self._send(iface, nbr.src, P.LsRequest(keys))
            self._arm_rxmt(iface, nbr)

    @staticmethod
    def _tx_copy(lsa, delay: int):
        """§13.3 InfTransDelay age increment (shared helper; RFC 5340
        keeps the header layout and §13.3 unchanged)."""
        from holo_tpu.protocols.ospf.packet import lsa_tx_copy

        return lsa_tx_copy(lsa, delay, P.MAX_AGE)

    def _rx_ls_request(self, iface: V3Interface, src, pkt) -> None:
        nbr = iface.neighbors.get(pkt.router_id)
        if nbr is None or nbr.state < NsmState.EXCHANGE:
            return
        lsas = []
        lsdb = self._area_of(iface).lsdb
        for key in pkt.body.entries:
            e = lsdb.get(key)
            if e is None:
                self._nbr_event(iface.name, pkt.router_id, NsmEvent.BAD_LS_REQ)
                return
            lsas.append(self._tx_copy(e.lsa, iface.config.transmit_delay))
        if lsas:
            self._send(iface, nbr.src, P.LsUpdate(lsas))

    def _any_nbr_exchanging(self) -> bool:
        return any(
            n.state in (NsmState.EXCHANGE, NsmState.LOADING)
            for i in self.interfaces.values()
            for n in i.neighbors.values()
        )

    def _rx_ls_update(self, iface: V3Interface, src, pkt) -> None:
        nbr = iface.neighbors.get(pkt.router_id)
        if nbr is None or nbr.state < NsmState.EXCHANGE:
            return
        acks = []
        now = self.loop.clock.now()
        area = self._area_of(iface)
        exchanging = self._any_nbr_exchanging()
        for lsa in pkt.body.lsas:
            cur = self._scope_db(area, lsa.type, iface).get(lsa.key)
            # §13 (4): a MaxAge LSA with no database copy (and no
            # exchange in progress) is acked directly, never installed —
            # otherwise flushes ping-pong around multi-access links.
            if lsa.is_maxage and cur is None and not exchanging:
                acks.append(lsa)
                continue
            if cur is None or lsa.compare(cur.lsa) > 0:
                if cur is not None and now - cur.rcvd_time < MIN_LS_ARRIVAL:
                    continue
                if lsa.adv_rtr == self.router_id and not lsa.is_maxage:
                    self._refresh_self_lsa(
                        area, lsa, from_iface=iface, from_nbr=nbr
                    )
                    continue
                self._install_and_flood(
                    area, lsa, from_iface=iface, from_nbr=nbr
                )
                acks.append(lsa)
            elif cur is not None and lsa.compare(cur.lsa) == 0:
                if lsa.key in nbr.ls_rxmt:
                    nbr.ls_rxmt.pop(lsa.key, None)
                else:
                    self._send(iface, nbr.src, P.LsAck([lsa]))
            else:
                self._send(
                    iface,
                    nbr.src,
                    P.LsUpdate(
                        [self._tx_copy(cur.lsa, iface.config.transmit_delay)]
                    ),
                )
            if lsa.key in nbr.ls_request:
                req = nbr.ls_request[lsa.key]
                if lsa.compare(req) >= 0:
                    del nbr.ls_request[lsa.key]
        if acks:
            self._send(iface, ALL_SPF_RTRS_V6, P.LsAck(acks))
        if nbr.state == NsmState.LOADING and not nbr.ls_request:
            self._nbr_event(iface.name, pkt.router_id, NsmEvent.LOADING_DONE)
        elif nbr.state == NsmState.LOADING:
            self._send_ls_request(iface, nbr)

    def _rx_ls_ack(self, iface: V3Interface, src, pkt) -> None:
        nbr = iface.neighbors.get(pkt.router_id)
        if nbr is None or nbr.state < NsmState.EXCHANGE:
            return
        drained = False
        for hdr in pkt.body.lsa_headers:
            cur = nbr.ls_rxmt.get(hdr.key)
            if cur is not None and hdr.compare(cur) == 0:
                del nbr.ls_rxmt[hdr.key]
                drained = cur.is_maxage or drained
        if drained:
            self._sweep_maxage()

    def _scope_db(self, area: V3Area, ltype, iface=None):
        """The database that owns LSAs of this type: the circuit's
        link-scope LSDB for Link LSAs, the area LSDB otherwise."""
        if P.scope_of(int(ltype)) == "link" and iface is not None:
            return iface.link_lsdb
        return area.lsdb

    def _install_and_flood(
        self, area: V3Area, lsa, from_iface=None, from_nbr=None
    ) -> None:
        now = self.loop.clock.now()
        if P.scope_of(int(lsa.type)) == "as":
            if area.no_external:
                return  # stub/NSSA areas refuse AS-scope LSAs outright
            # AS scope: one logical instance, installed + flooded through
            # every non-stub area (stub/NSSA areas refuse externals).
            for other in self.areas.values():
                if other.no_external:
                    continue
                if other is not area:
                    other.lsdb.install(lsa, now)
        if P.scope_of(int(lsa.type)) == "link":
            # Link scope lives in the circuit's own LSDB (§4.4.2) —
            # never the area database.
            if from_iface is None:
                return
            old = from_iface.link_lsdb.get(lsa.key)
            _, changed = from_iface.link_lsdb.install(lsa, now)
        else:
            old = area.lsdb.get(lsa.key)
            _, changed = area.lsdb.install(lsa, now)
        if changed:
            # Old body rides along: partial classification merges the
            # prefixes of both versions of an Intra-Area-Prefix LSA so
            # withdrawn prefixes drop their routes (ospfv3/spf.rs:120-131).
            self._schedule_spf(
                trigger=(lsa, old.lsa if old is not None else None)
            )
        as_scope = P.scope_of(int(lsa.type)) == "as"
        for iface in self.interfaces.values():
            if not iface.up:
                continue
            iface_area = self._area_of(iface)
            if as_scope:
                if iface_area.no_external:
                    continue
            elif iface_area is not area:
                continue
            # Link-scope LSAs only flood on their own link.
            if P.scope_of(int(lsa.type)) == "link" and iface is not from_iface:
                continue
            sent = False
            for nbr in iface.neighbors.values():
                if nbr.state < NsmState.EXCHANGE:
                    continue
                if nbr.exchange_or_loading():
                    req = nbr.ls_request.get(lsa.key)
                    if req is not None:
                        c = lsa.compare(req)
                        if c < 0:
                            continue
                        del nbr.ls_request[lsa.key]
                        if c == 0:
                            continue
                if from_nbr is not None and nbr is from_nbr:
                    continue
                nbr.ls_rxmt[lsa.key] = lsa
                sent = True
                self._arm_rxmt(iface, nbr)
            if sent:
                self._send(
                    iface,
                    ALL_SPF_RTRS_V6,
                    P.LsUpdate(
                        [self._tx_copy(lsa, iface.config.transmit_delay)]
                    ),
                )
        if lsa.is_maxage:
            # The MaxAge copy STAYS installed until every retransmission
            # list drains and no neighbor is in Exchange/Loading — the
            # RFC 2328 §14 removal condition (same as v2; the reference's
            # ospfv3 conformance expects the MaxAge copy visible in the
            # LSDB, packet-lsupd-self-orig2).
            self._sweep_maxage()

    def _sweep_maxage(self) -> None:
        """§14: drop MaxAge LSAs no rxmt list holds, unless an exchange
        is in progress (the DD summaries may still reference them)."""
        if self._any_nbr_exchanging():
            return
        held: set = set()
        for iface in self.interfaces.values():
            for nbr in iface.neighbors.values():
                held |= set(nbr.ls_rxmt)
        now = self.loop.clock.now()
        for area in self.areas.values():
            # An entry whose header says MaxAge is MaxAge on the clock
            # too: the area's kept ages name the few there are.
            for e in self._age_scan(area).at_least(
                area.lsdb, now, P.MAX_AGE
            ):
                if e.lsa.is_maxage and e.lsa.key not in held:
                    area.lsdb.remove(e.lsa.key)
        for iface in self.interfaces.values():
            db = iface.link_lsdb
            for key in [
                k
                for k, e in db.entries.items()
                if e.lsa.is_maxage and k not in held
            ]:
                db.remove(key)

    def _age_scan(self, area: V3Area) -> AgeScan:
        scan = self._age_scans.get(area.area_id)
        if scan is None:
            scan = self._age_scans[area.area_id] = AgeScan()
        return scan

    def _arm_rxmt(self, iface: V3Interface, nbr: Neighbor) -> None:
        t = self._timer(
            ("rxmt", iface.name, nbr.router_id),
            lambda: RxmtTimerV3(iface.name, nbr.router_id),
        )
        if not t.armed:
            t.start(iface.config.rxmt_interval)

    def _rxmt(self, ifname: str, nbr_id) -> None:
        iface = self.interfaces.get(ifname)
        if iface is None:
            return
        nbr = iface.neighbors.get(nbr_id)
        if nbr is None:
            return
        if nbr.state == NsmState.EX_START or (
            nbr.state == NsmState.EXCHANGE and nbr.master
        ):
            if nbr.last_sent_dd is not None:
                self._send(iface, nbr.src, nbr.last_sent_dd)
        if nbr.state == NsmState.LOADING and nbr.ls_request:
            self._send_ls_request(iface, nbr)
        if nbr.ls_rxmt:
            self._send(
                iface,
                nbr.src,
                P.LsUpdate(
                    [
                        self._tx_copy(l, iface.config.transmit_delay)
                        for l in list(nbr.ls_rxmt.values())[:20]
                    ]
                ),
            )
        if (
            nbr.state in (NsmState.EX_START, NsmState.EXCHANGE, NsmState.LOADING)
            or nbr.ls_rxmt
        ):
            self._arm_rxmt(iface, nbr)

    # -- origination

    def _originate(
        self, area: V3Area, ltype: P.LsaType, lsid: IPv4Address, body,
        iface: "V3Interface | None" = None,
    ) -> None:
        key = P.LsaKey(ltype, lsid, self.router_id)
        scope_db = (
            iface.link_lsdb
            if iface is not None and P.scope_of(int(ltype)) == "link"
            else area.lsdb
        )
        old = scope_db.get(key)
        if (
            old is not None
            and not old.lsa.is_maxage
            and old.lsa.body == body
        ):
            # The same body encodes to the same bytes: nothing to build
            # (an ABR wants a thousand summaries again in every run).
            return
        lsa = P.Lsa(
            age=0,
            type=ltype,
            lsid=lsid,
            adv_rtr=self.router_id,
            seq_no=next_seq_no(old.lsa if old else None),
            body=body,
        )
        lsa.encode()
        if (
            old is not None
            and not old.lsa.is_maxage
            and old.lsa.raw[20:] == lsa.raw[20:]
        ):
            # Unchanged content: no re-origination — but a MaxAge copy
            # (mid-flush, retained until rxmt lists drain) never
            # suppresses; wanting the LSA again needs a fresh instance.
            return
        self._install_and_flood(area, lsa, from_iface=iface)

    def _refresh_self_lsa(
        self, area: V3Area, received, from_iface=None, from_nbr=None
    ) -> None:
        """§13.4 received self-originated LSA: the newer received copy is
        first flooded on as usual (reference §13 step 5.b runs before the
        self-orig check — one LS Update per adjacency with the received
        instance), then either outpaced with a fresh re-origination or
        flushed with MaxAge (a second LS Update), exactly the two-update
        sequence the reference's ospfv3 conformance cases record
        (tests/conformance/ospfv3/packet-lsupd-self-orig{1,2})."""
        cur = self._scope_db(area, received.type, from_iface).get(
            received.key
        )
        self._install_and_flood(
            area, received, from_iface=from_iface, from_nbr=from_nbr
        )
        if cur is None or received.seq_no >= P.MAX_SEQ_NO:
            # No live incarnation of ours, or the sequence space is
            # exhausted (§12.1.6): flush the received copy — the refresh
            # machinery re-originates from INITIAL_SEQ_NO once the
            # MaxAge instance drains.
            self._flush_self(area, received.key)
            return
        lsa = P.Lsa(
            age=0,
            type=cur.lsa.type,
            lsid=cur.lsa.lsid,
            adv_rtr=cur.lsa.adv_rtr,
            seq_no=received.seq_no + 1,
            body=cur.lsa.body,
        )
        lsa.encode()
        self._install_and_flood(area, lsa)

    def _transit_active(self, iface: V3Interface) -> bool:
        """A LAN contributes a transit link once a DR exists and we are
        synchronized with it (or are it)."""
        if not iface.is_lan or int(iface.dr) == 0:
            return False
        if iface.dr == self.router_id:
            return any(
                n.state == NsmState.FULL for n in iface.neighbors.values()
            )
        dr = iface.neighbors.get(iface.dr)
        return dr is not None and dr.state == NsmState.FULL

    def _dr_iface_id(self, iface: V3Interface) -> int:
        if iface.dr == self.router_id:
            return iface.iface_id
        dr = iface.neighbors.get(iface.dr)
        return dr.iface_id if dr is not None else 0

    def _originate_router_lsa(self) -> None:
        for area in self.areas.values():
            self._originate_router_lsa_area(area)

    def set_stub_router(self, enabled: bool) -> None:
        """RFC 6987 stub-router (max-metric) maintenance mode: flip the
        leaf and re-originate every area's router-LSA."""
        if enabled == self.stub_router:
            return
        self.stub_router = enabled
        self._originate_router_lsa()

    def _originate_router_lsa_area(self, area: V3Area) -> None:
        links = []
        flags = P.RouterFlags(0)
        if self.is_abr:
            flags |= P.RouterFlags.B
        if self.redistributed and not area.no_external:
            flags |= P.RouterFlags.E
        # RFC 6987 stub-router: every router-LSA link (all v3 router
        # links are transit — prefixes live in intra-area-prefix LSAs,
        # which keep their real metric) advertises MaxLinkMetric.
        from holo_tpu.protocols.ospf.packet import MAX_LINK_METRIC

        def transit_cost(cost: int) -> int:
            return MAX_LINK_METRIC if self.stub_router else cost

        for iface in self._area_ifaces(area):
            if not iface.up:
                continue
            if iface.is_lan:
                if self._transit_active(iface):
                    # RFC 5340 §4.4.3.2: transit link names the DR's
                    # (interface id, router id) — the network vertex.
                    links.append(
                        P.RouterLinkV3(
                            P.RouterLinkType.TRANSIT_NETWORK,
                            transit_cost(iface.config.cost),
                            iface.iface_id,
                            self._dr_iface_id(iface),
                            iface.dr,
                        )
                    )
                continue
            for nbr in iface.neighbors.values():
                if nbr.state == NsmState.FULL:
                    links.append(
                        P.RouterLinkV3(
                            P.RouterLinkType.POINT_TO_POINT,
                            transit_cost(iface.config.cost),
                            iface.iface_id,
                            nbr.iface_id,
                            nbr.router_id,
                        )
                    )
        self._originate(
            area,
            P.LsaType.ROUTER,
            IPv4Address(0),
            P.LsaRouterV3(flags=flags, links=links),
        )

    def _originate_network_lsa(self, iface: V3Interface) -> None:
        """DR duty: the network LSA (lsid = DR's interface id) lists all
        fully-adjacent members plus the DR itself (RFC 5340 §4.4.3.3)."""
        area = self._area_of(iface)
        lsid = IPv4Address(iface.iface_id)
        key = P.LsaKey(P.LsaType.NETWORK, lsid, self.router_id)
        if (
            iface.up
            and iface.dr == self.router_id
            and any(n.state == NsmState.FULL for n in iface.neighbors.values())
        ):
            attached = [self.router_id] + sorted(
                (n.router_id for n in iface.neighbors.values()
                 if n.state == NsmState.FULL),
                key=int,
            )
            self._originate(
                area, P.LsaType.NETWORK, lsid, P.LsaNetworkV3(attached=attached)
            )
        else:
            self._flush_self(area, key)

    @staticmethod
    def _maxage_copy(lsa):
        """A copy of ``lsa`` with the header age pinned at MaxAge."""
        import copy

        flush = copy.copy(lsa)
        flush.age = P.MAX_AGE
        if flush.raw:
            raw = bytearray(flush.raw)
            raw[0:2] = P.MAX_AGE.to_bytes(2, "big")
            flush.raw = bytes(raw)
        return flush

    def _flush_self(self, area: V3Area, key) -> None:
        e = area.lsdb.get(key)
        if e is None or e.lsa.is_maxage:
            return
        self._install_and_flood(area, self._maxage_copy(e.lsa))

    def _originate_intra_area_prefix(self) -> None:
        for area in self.areas.values():
            self._originate_intra_area_prefix_area(area)
            self._originate_router_information(area)
        self._originate_link_lsas()

    def _originate_link_lsas(self) -> None:
        """RFC 5340 §4.4.3.8: one Link LSA per up circuit — our
        priority, options, link-local address, and the link's global
        prefixes; link-state id = interface id."""
        for iface in self.interfaces.values():
            if not iface.up:
                continue
            area = self._area_of(iface)
            self._originate(
                area,
                P.LsaType.LINK,
                IPv4Address(iface.iface_id),
                P.LsaLink(
                    priority=iface.config.priority,
                    link_local=iface.link_local,
                    prefixes=list(iface.prefixes),
                ),
                iface=iface,
            )

    def _originate_router_information(self, area: V3Area) -> None:
        """RFC 7770 Router-Information LSA, one per area (the v3 analog
        of v2's RI opaque; the reference originates GR-helper +
        stub-router capabilities at area start — both real here:
        ``set_stub_router`` implements the RFC 6987 max-metric mode)."""
        from holo_tpu.protocols.ospf.packet import (
            RI_CAP_GR_HELPER,
            RI_CAP_STUB_ROUTER,
            encode_router_info,
        )

        caps = RI_CAP_STUB_ROUTER | RI_CAP_GR_HELPER
        self._originate(
            area,
            P.LsaType.ROUTER_INFORMATION,
            IPv4Address(0),
            P.LsaRawBody(data=encode_router_info(caps)),
        )

    def _originate_intra_area_prefix_area(self, area: V3Area) -> None:
        # Router-referenced LSA: p2p prefixes plus LAN prefixes whose LAN
        # has no active network LSA yet (stub behavior, RFC 5340 §4.4.3.9).
        # Host prefixes carry the LA bit (§A.4.1.1 — local addresses).
        prefixes = []
        for iface in self._area_ifaces(area):
            if iface.up and not self._transit_active(iface):
                for p in iface.prefixes:
                    prefixes.append((
                        p,
                        iface.config.cost,
                        P.PREFIX_OPT_LA if p.prefixlen == 128 else 0,
                    ))
        body = P.LsaIntraAreaPrefix(
            ref_type=int(P.LsaType.ROUTER),
            ref_lsid=IPv4Address(0),
            ref_adv_rtr=self.router_id,
            prefixes=prefixes,
        )
        self._originate(area, P.LsaType.INTRA_AREA_PREFIX, IPv4Address(1), body)
        # Network-referenced LSAs: the DR advertises each transit LAN's
        # prefixes against the network vertex (metric 0 — the path cost
        # to the network vertex already includes the link cost).
        for iface in self._area_ifaces(area):
            lsid = IPv4Address(0x100 + iface.iface_id)
            if (
                iface.up
                and iface.is_lan
                and iface.dr == self.router_id
                and self._transit_active(iface)
            ):
                self._originate(
                    area,
                    P.LsaType.INTRA_AREA_PREFIX,
                    lsid,
                    P.LsaIntraAreaPrefix(
                        ref_type=int(P.LsaType.NETWORK),
                        ref_lsid=IPv4Address(iface.iface_id),
                        ref_adv_rtr=self.router_id,
                        prefixes=[(p, 0) for p in iface.prefixes],
                    ),
                )
            else:
                self._flush_self(
                    area,
                    P.LsaKey(P.LsaType.INTRA_AREA_PREFIX, lsid, self.router_id),
                )

    # -- aging

    def _age_tick(self) -> None:
        now = self.loop.clock.now()
        for area in self.areas.values():
            # Link-scope databases age/refresh alongside the area's.
            ifaces = [
                i for i in self.interfaces.values()
                if self._area_of(i) is area
            ]
            # The area's database through its kept ages (an area holds
            # thousands of LSAs, a circuit's link-scope database two).
            old = self._age_scan(area).at_least(
                area.lsdb, now, LS_REFRESH_TIME
            )
            dbs = [(area.lsdb, None, old)] + [
                (i.link_lsdb, i, i.link_lsdb.all()) for i in ifaces
            ]
            for db, iface, aged in dbs:
                aged = list(aged)
                for e in aged:
                    if (
                        e.lsa.adv_rtr != self.router_id
                        or e.lsa.is_maxage
                        or e.current_age(now) < LS_REFRESH_TIME
                    ):
                        continue
                    lsa = P.Lsa(
                        age=0,
                        type=e.lsa.type,
                        lsid=e.lsa.lsid,
                        adv_rtr=e.lsa.adv_rtr,
                        seq_no=next_seq_no(e.lsa),
                        body=e.lsa.body,
                    )
                    lsa.encode()
                    self._install_and_flood(area, lsa, from_iface=iface)
                for e in aged:
                    if (
                        db.get(e.lsa.key) is e  # not refreshed above
                        and e.current_age(now) >= P.MAX_AGE
                        and not e.lsa.is_maxage
                    ):
                        # Natural expiry: pin the header age at MaxAge so
                        # the flood (and the §14 sweep) see the flush.
                        self._install_and_flood(
                            area, self._maxage_copy(e.lsa),
                            from_iface=iface,
                        )
        # One §14 sweep per tick drops every drained MaxAge entry.
        self._sweep_maxage()
        self._age_timer.start(AGE_TICK)

    # -- SPF

    def _schedule_spf(self, trigger=None) -> None:
        """``trigger`` is a ``(new_lsa, old_lsa | None)`` pair for LSDB
        installs; trigger-less calls (interface/config events) force the
        next run Full (reference spf.rs:511-516)."""
        if trigger is None:
            self._spf_force_full = True
        else:
            self._spf_triggers.append(trigger)
        # Causal origin stamp (shared contract; see the v2 instance).
        convergence.pend_schedule(
            self._conv_pending,
            convergence.TRIGGER_LSA
            if trigger is not None
            else convergence.TRIGGER_IFCONFIG,
            instance=self.name,
        )
        self._spf_delay_event(
            self.spf_timers, self.loop.clock.now(),
            self._spf_timer, self._hold_timer,
        )

    def set_area_ranges(self, area_id: IPv4Address, ranges: list) -> None:
        """Configure an area's address ranges ([{prefix, advertise,
        cost}]); a config event, so the next run is a full one."""
        self.areas[area_id].ranges = list(ranges)
        self._schedule_spf()

    @staticmethod
    def _expand_atoms(words, atoms) -> frozenset:
        """Atom bits -> next-hop tuples; NexthopAtom vlink atoms expand
        to their borrowed transit-area set (§16.1), same typed design
        as the v2 marshaling (spf_run.NexthopAtom.expand)."""
        out = set()
        for a in atom_bits(words, len(atoms)):
            atom = atoms[a]
            if isinstance(atom, NexthopAtom):
                if atom.expand:
                    out |= atom.expand
            else:
                out.add(atom)
        return frozenset(out)

    def _vlink_nexthops(self, backbone: V3Area, area_results: dict) -> dict:
        """{vlink peer rid: frozenset[(ifname, ll)]} from each transit
        area's path to the peer (mirrors the v2 instance §16.1 logic;
        our backbone router-LSA names the vlink peers)."""
        from holo_tpu.ops.graph import INF

        now = self.loop.clock.now()
        peers = set()
        for e in backbone.lsdb.all():
            lsa = e.lsa
            if (
                lsa.type == P.LsaType.ROUTER
                and lsa.adv_rtr == self.router_id
                and e.current_age(now) < P.MAX_AGE
            ):
                for link in lsa.body.links:
                    if link.link_type == P.RouterLinkType.VIRTUAL_LINK:
                        peers.add(link.nbr_router_id)
        best: dict = {}
        via: dict = {}  # rid -> (transit aid, dist) for state rendering
        for rid in peers:
            for aid, (index, _k, res, atoms, _pl) in area_results.items():
                if aid == IPv4Address(0):
                    continue
                v = index.get(("R", rid))
                if v is None or res.dist[v] >= INF:
                    continue
                nhs = self._expand_atoms(res.nexthop_words[v], atoms)
                if not nhs:
                    continue
                dist = int(res.dist[v])
                cur = best.get(rid)
                if cur is None or dist < cur[0]:
                    best[rid] = (dist, nhs)
                    via[rid] = (aid, dist)
                elif dist == cur[0]:
                    # Parallel virtual links through different transit
                    # areas at equal cost: ECMP union (topo3-3 shape).
                    best[rid] = (dist, cur[1] | nhs)
        # Operational state for the vlink endpoints (ietf-ospf
        # virtual-links): peer, transit area, cost, and the peer's
        # endpoint address — the LA host prefix it advertises in the
        # transit area (RFC 5340 §4.4.3.9).
        self.vlink_state = []
        if self.vlink_config:
            rows = []
            for aid, rid in self.vlink_config:
                pair = area_results.get(aid)
                dist = None
                if pair is not None:
                    index, _k, res, atoms, _pl = pair
                    v = index.get(("R", rid))
                    if v is not None and res.dist[v] < INF:
                        dist = int(res.dist[v])
                if dist is not None:
                    rows.append((rid, aid, dist))
        else:
            rows = [
                (rid, aid, dist)
                for rid, (aid, dist) in sorted(
                    via.items(), key=lambda kv: int(kv[0])
                )
            ]
        for rid, aid, dist in rows:
            addr = None
            transit = self.areas.get(aid)
            if transit is not None:
                for e in transit.lsdb.all():
                    lsa = e.lsa
                    if (
                        lsa.type == P.LsaType.INTRA_AREA_PREFIX
                        and lsa.adv_rtr == rid
                    ):
                        for entry in lsa.body.prefixes:
                            if (
                                entry[0].prefixlen == 128
                                and lsa.body.entry_opts(entry)
                                & P.PREFIX_OPT_LA
                            ):
                                addr = entry[0].network_address
            self.vlink_state.append(
                {
                    "transit_area_id": aid,
                    "router_id": rid,
                    "cost": dist,
                    "address": addr,
                }
            )
        return {rid: nhs for rid, (_d, nhs) in best.items()}

    def iface_update(
        self,
        ifname: str,
        hello: int | None = None,
        dead: int | None = None,
        priority: int | None = None,
        passive: bool | None = None,
        mtu: int | None = None,
        mtu_ignore: bool | None = None,
        transmit_delay: int | None = None,
    ) -> None:
        """Live interface reconfiguration beyond cost (the v2
        iface_update analog): hello/dead apply from the next hello (the
        hello timer re-arms with the config value), priority is
        advertised from the next hello, and a passive flip tears
        down / revives the circuit's packet exchange while its prefixes
        stay advertised."""
        iface = self.interfaces.get(ifname)
        if iface is None:
            return
        cfg = iface.config
        if hello is not None:
            cfg.hello_interval = hello
        if dead is not None:
            cfg.dead_interval = dead
        if priority is not None:
            cfg.priority = priority
        if mtu is not None:
            # Live input to the §10.6 DD Interface-MTU check.
            cfg.mtu = mtu
        if mtu_ignore is not None:
            cfg.mtu_ignore = mtu_ignore
        if transmit_delay is not None:
            cfg.transmit_delay = transmit_delay
        if passive is not None and cfg.passive != passive:
            cfg.passive = passive
            if passive:
                for nbr_id in list(iface.neighbors):
                    self._nbr_event(ifname, nbr_id, NsmEvent.KILL_NBR)
                iface.dr = IPv4Address(0)
                iface.bdr = IPv4Address(0)
                for key in (("hello", ifname), ("wait", ifname)):
                    t = self._timers.get(key)
                    if t:
                        t.cancel()
                self._originate_router_lsa()
            elif iface.up:
                if iface.is_lan:
                    # §9.4 Waiting again before claiming DR.
                    iface.up_since = self.loop.clock.now()
                    iface.wait_until = (
                        self.loop.clock.now() + cfg.dead_interval
                    )
                    self._timer(
                        ("wait", ifname), lambda: WaitTimerV3(ifname)
                    ).start(cfg.dead_interval)
                self._send_hello(ifname)

    def iface_cost_update(self, ifname: str, cost: int) -> None:
        """Live cost reconfiguration (reference InterfaceCostUpdate):
        re-originate the router-LSA with the new metric."""
        iface = self.interfaces.get(ifname)
        if iface is None or iface.config.cost == cost:
            return
        iface.config.cost = cost
        self._originate_router_lsa()
        # The interface cost is ALSO the stub-prefix metric in the
        # intra-area-prefix LSA — without re-originating it, neighbors
        # keep routing to our prefixes at the stale cost.
        self._originate_intra_area_prefix()

    def _classify_spf(self, triggers: list) -> dict | None:
        """Full-vs-partial classification (reference ospfv3/spf.rs:97-163).
        Returns None when a full SPF is required.

        Router/Network-LSAs are topological; Link-LSAs and Router
        Information changes also force Full (next-hop resolution and SR
        state depend on them — the reference makes the same
        simplification).  Intra-Area-Prefix changes merge prefixes from
        BOTH the old and new versions so withdrawn prefixes drop."""
        intra: set = set()
        inter_network: set = set()
        inter_router: set = set()
        external: set = set()
        for new, old in triggers:
            t = new.type
            if t in (
                P.LsaType.ROUTER,
                P.LsaType.NETWORK,
                P.LsaType.LINK,
                P.LsaType.ROUTER_INFORMATION,
            ):
                return None
            if t == P.LsaType.INTRA_AREA_PREFIX:
                for lsa in (new, old):
                    if lsa is not None:
                        for entry in lsa.body.prefixes:
                            intra.add(entry[0])
            elif t == P.LsaType.INTER_AREA_PREFIX:
                for lsa in (new, old):
                    if lsa is not None:
                        inter_network.add(lsa.body.prefix)
            elif t == P.LsaType.INTER_AREA_ROUTER:
                inter_router.add(new.body.dest_router_id)
            elif t == P.LsaType.AS_EXTERNAL:
                for lsa in (new, old):
                    if lsa is not None:
                        external.add(lsa.body.prefix)
            else:
                return None  # unknown type: be safe, run full
        return {
            "intra": intra,
            "inter_network": inter_network,
            "inter_router": inter_router,
            "external": external,
        }

    def run_spf(self) -> None:
        with convergence.spf_run(self._conv_pending, self.name):
            with telemetry.span("ospfv3.spf", instance=self.name):
                # The v2 instance's host stages under its names (site
                # ospf.spf): run holds the whole run, topology / link /
                # derive / inter / publish its parts, one observation
                # of each per run whatever the number of areas.
                with profiling.stage("ospf.spf", "run"):
                    self._run_spf_traced()

    def _backbone_vlink_peers(self, backbone: V3Area) -> bool:
        """Whether our backbone Router-LSA names a virtual link (or one
        is configured): only then does the backbone's marshal wait for
        the transit areas' results."""
        if self.vlink_config:
            return True
        e = backbone.lsdb.get(
            P.LsaKey(P.LsaType.ROUTER, IPv4Address(0), self.router_id)
        )
        return e is not None and any(
            link.link_type == P.RouterLinkType.VIRTUAL_LINK
            for link in e.lsa.body.links
        )

    def _run_spf_traced(self) -> None:
        triggers = self._spf_triggers
        self._spf_triggers = []
        force_full = self._spf_force_full
        self._spf_force_full = False
        partial = None if force_full else self._classify_spf(triggers)
        if partial is not None and self._spf_cache is not None:
            _OSPF_SPF_RUNS.labels(instance=self.name, type="partial").inc()
            self._run_spf_partial(partial)
            return
        _OSPF_SPF_RUNS.labels(instance=self.name, type="full").inc()
        self.spf_run_count += 1
        start_time = self.loop.clock.now()
        area_results: dict = {}
        intra_by_area: dict[IPv4Address, dict] = {}
        # Every area in one pass; a backbone with virtual links in a
        # second one, because its SPF borrows transit-area next hops
        # (§16.1), like the v2 instance.
        backbone = self.areas.get(IPv4Address(0))
        passes = [[
            a for a in self.areas.values() if int(a.area_id) != 0
        ]]
        if backbone is not None:
            if self._backbone_vlink_peers(backbone):
                passes.append([backbone])
            else:
                self.vlink_state = []
                passes[0].append(backbone)
        for areas in passes:
            self._spf_pass(
                areas, area_results, intra_by_area,
                vlinks=areas is not passes[0],
            )
        # Area order as before: the backbone last.  A prefix is nearly
        # always one area's: the tables are joined by whole-dict
        # operations, which reuse the hashes the dicts hold (hashing an
        # ip_network anew costs a microsecond, and there are 13,000),
        # and only a prefix two areas both have is compared.
        routes: dict[IPv6Network, V6Route] = {}
        for aid in sorted(intra_by_area, key=lambda a: int(a) == 0):
            intra = intra_by_area[aid]
            both = routes.keys() & intra.keys()
            if not both:
                routes.update(intra)
                continue
            settled = {prefix: routes[prefix] for prefix in both}
            routes.update(intra)
            for prefix, cur in settled.items():
                route = intra[prefix]
                if cur.dist < route.dist:
                    routes[prefix] = cur
                elif route.dist == cur.dist:
                    # Cross-area ECMP union keeps the first contributing
                    # area's (area_id, vertex) — the FRR consumption key
                    # must stay a consistent pair.
                    routes[prefix] = V6Route(
                        prefix, route.dist, cur.nexthops | route.nexthops,
                        route_type=cur.route_type,
                        prefix_options=cur.prefix_options,
                        area_id=cur.area_id, vertex=cur.vertex,
                    )

        with profiling.stage("ospf.spf", "inter"):
            self._area_adverts(intra_by_area)
            # 2. inter-area routes from received Inter-Area-Prefix LSAs:
            #    distance = dist(advertising ABR in that area) + metric.
            #    The candidate table covers EVERY advertised prefix
            #    (intra preference applies only at install time) so a
            #    later partial run can fall back to it when an intra
            #    path withdraws.
            inter_routes: dict[IPv6Network, V6Route] = {}
            self._derive_inter_area(area_results, inter_routes)
            for prefix, route in inter_routes.items():
                if prefix not in routes:
                    routes[prefix] = route

            # 3. AS-external routes (lowest preference): RFC 5340 type
            #    0x4005.  E2 ranks on the external metric, E1 on
            #    asbr-dist + metric.
            routes.update(self._derive_external(area_results, routes))

            # 4. ABR duties: inter-area-prefix origination (each area's
            #    intra prefixes, ranges applied, into every other area;
            #    default into stub areas).
            if self.is_abr:
                self._originate_inter_area(inter_routes, area_results)

            self.spf_log.append(
                {
                    "run": self.spf_run_count,
                    "type": "full",
                    "start-time": start_time,
                    "end-time": self.loop.clock.now(),
                    "route-count": len(routes),
                }
            )
            del self.spf_log[:-32]
        # Cache the run's products for prefix-scoped partial updates
        # (reference route.rs:200-333 update_rib_partial).
        self._spf_cache = {
            "area_results": area_results,
            "intra_by_area": intra_by_area,
            "routes": routes,
            "inter_routes": inter_routes,
        }
        with profiling.stage("ospf.spf", "publish"):
            self._publish(routes, area_results)

    def _spf_pass(
        self, areas: list, area_results: dict, intra_by_area: dict,
        vlinks: bool,
    ) -> None:
        """Marshal, dispatch and derive ``areas``; every stage once for
        all of them.  ``vlinks``: this is the backbone's own pass, after
        the transit areas' results."""
        marshaled: dict = {}
        with profiling.stage("ospf.spf", "topology"):
            for area in areas:
                vlink_nexthops = None
                if vlinks:
                    vlink_nexthops = self._vlink_nexthops(
                        area, area_results
                    )
                st = self._area_marshal(area, vlink_nexthops)
                if st is not None:
                    marshaled[area.area_id] = st
        mp_k = (
            self.max_paths
            if self.max_paths is not None and self.max_paths > 1
            else 1
        )
        knobs = (self.frr, mp_k)
        fresh: dict = {}
        for aid, st in marshaled.items():
            kept = self._area_kept.get(aid)
            if (
                kept is not None and kept[0] is st
                and kept[1] is self.backend and kept[2] == knobs
            ):
                # The lowering handed out the last run's object: none
                # of the area's inputs changed.
                _AREA_SPF.labels(disposition="reused").inc()
            else:
                fresh[aid] = st
        # DeltaPath seam (same contract as the v2 instance): identical
        # vertex model + atom table → diff against the previous run's
        # topology so the device-resident graph updates in place.
        with profiling.stage("ospf.spf", "link"):
            for aid, st in fresh.items():
                prev = self._spf_delta_bases.get(aid)
                if prev is not st:
                    link_spf_delta(prev, st)
                self._spf_delta_bases[aid] = st
        outs: dict = {}
        for aid, st in fresh.items():
            _AREA_SPF.labels(disposition="dispatched").inc()
            res = self.backend.compute(st.topo, multipath_k=mp_k)
            self._area_frr(aid, st.topo)
            outs[aid] = (st.index, st.keys, res, st.atoms, st.prefix_lsas)
        with profiling.stage("ospf.spf", "derive"):
            for aid, out in outs.items():
                self._area_kept[aid] = (
                    fresh[aid], self.backend, knobs, out,
                    self._derive_intra(aid, out, knobs),
                )
        for aid in marshaled:  # in the areas' order, reused or not
            _st, _be, _kn, area_results[aid], intra_by_area[aid] = (
                self._area_kept[aid]
            )

    @staticmethod
    def _prefix_offers(body) -> tuple:
        """An Intra-Area-Prefix LSA body as :class:`KeptDerive` lowers
        it: the key of the vertex it refers to (None for a referenced
        type that is no vertex) and its entries."""
        if body.ref_type == int(P.LsaType.ROUTER):
            key = ("R", body.ref_adv_rtr)
        elif body.ref_type == int(P.LsaType.NETWORK):
            key = ("N", body.ref_adv_rtr, int(body.ref_lsid))
        else:
            key = None
        return key, [
            (entry[0], entry[1], body.entry_opts(entry))
            for entry in body.prefixes
        ]

    def _derive_intra(self, aid, out, knobs: tuple) -> dict:
        """1. intra-area routes (preferred over inter/external) of one
        area from its SPF result, by difference against the area's last
        derive (:class:`KeptDerive`): a prefix none of whose inputs
        moved keeps the route OBJECT it had.  The kept state is
        dropped, and the derive a whole one, under the guards
        ``_spf_pass`` holds ``_area_kept`` to (another backend, other
        ``(frr, mp_k)`` knobs) and while IP-FRR is active:
        ``_attach_frr_backups`` writes ``backups`` into the route
        objects, and a kept one would carry a stale repair."""
        index, keys, res, atoms, prefix_lsas = out
        kept = self._derive_kept.get(aid)
        if (
            kept is None or kept[0] is not self.backend or kept[1] != knobs
            or (self.frr is not None and self.frr.active())
        ):
            def make_route(prefix, dist, nexthops, options, vertex):
                return V6Route(
                    prefix, dist, nexthops, prefix_options=options,
                    area_id=aid, vertex=vertex,
                )

            kept = self._derive_kept[aid] = (
                self.backend, knobs,
                KeptDerive(
                    self._prefix_offers, self._expand_atoms, make_route
                ),
            )
        return kept[2].derive(index, keys, atoms, res, prefix_lsas)

    @staticmethod
    def _route_delta(old: dict, new: dict) -> tuple[dict, list]:
        """``routing.sink.table_delta`` over V6Route tables."""
        def differs(o, r) -> bool:
            return o is not r and (
                o is None
                or o.dist != r.dist
                or o.nexthops != r.nexthops
                or o.backups != r.backups
            )

        return table_delta(old, new, differs)

    def _publish(self, routes: dict, area_results: dict) -> None:
        """The end of a run, full or partial: clamp, join the repairs,
        hand the table (or what changed of it) to the route sink."""
        self._clamp_max_paths(routes, area_results)
        self._attach_frr_backups(routes, area_results)
        old, self.routes = self.routes, routes
        if self.route_delta_cb is not None:
            changed, removed = self._route_delta(old, routes)
            _RIB_DELTA_ROUTES.observe(len(changed) + len(removed))
            self.route_delta_cb(changed, removed)
        elif self.route_cb is not None:
            _RIB_DELTA_ROUTES.observe(len(routes))
            self.route_cb(routes)

    def _clamp_max_paths(self, routes: dict, area_results: dict | None = None) -> None:
        """ietf-ospf max-paths (ISSUE 10): truncate every route's ECMP
        set deterministically to the configured width.  With the
        multipath dispatch armed (max_paths > 1 → the kernel computed
        UCMP planes) the rank is weight-DESCENDING — the highest-mass
        paths survive — tie-broken by lowest link-local address (the
        reference's clamp key); without weights the address key alone
        decides."""
        m = self.max_paths
        if not m or m < 1:
            return
        from dataclasses import replace as _replace

        def weights_for(r) -> dict:
            """{(ifname, ll) -> UCMP weight} from the winning area's
            multipath planes (empty when unavailable)."""
            ar = (area_results or {}).get(r.area_id)
            if ar is None or r.vertex < 0:
                return {}
            res, atoms = ar[2], ar[3]
            nhw = getattr(res, "nh_weights", None)
            if nhw is None or r.vertex >= len(res.dist):
                return {}
            from holo_tpu.protocols.ospf.spf_run import (
                NexthopAtom,
                atom_bits,
            )

            out: dict = {}
            row = nhw[r.vertex]
            for a in atom_bits(res.nexthop_words[r.vertex], len(atoms)):
                atom = atoms[a]
                w = int(row[a]) if a < len(row) else 0
                targets = (
                    atom.expand or ()
                    if isinstance(atom, NexthopAtom)
                    else (atom,)
                )
                for nh in targets:
                    out[nh] = out.get(nh, 0) + w
            return out

        for prefix, r in list(routes.items()):
            if len(r.nexthops) <= m:
                continue
            w = weights_for(r)
            ranked = sorted(
                r.nexthops,
                key=lambda h: (
                    -w.get(h, 1),
                    h[1] is None,
                    h[1].packed if h[1] is not None else b"",
                    h[0] or "",
                ),
            )
            routes[prefix] = _replace(r, nexthops=frozenset(ranked[:m]))

    def _attach_frr_backups(self, routes: dict, area_results: dict) -> None:
        """Join the per-area backup tables onto the v6 route table.

        Direct LFAs only: OSPFv3 here has no SRv6/SRH machinery to
        encapsulate a remote-LFA or TI-LFA repair, so tunnel repairs
        stay in ``frr_tables`` (operational visibility) without a
        forwarding entry — RFC 7490 §2's encapsulation requirement."""
        cfg = self.frr
        if cfg is None or not cfg.active() or not self.frr_tables:
            return
        from holo_tpu.frr.manager import repair_picks, repair_plan
        from holo_tpu.protocols.ospf.spf_run import NexthopAtom

        # area -> vertex -> {primary atom: (its LFA's atom, ())}
        lfas: dict = {}
        for aid, table in self.frr_tables.items():
            out = area_results.get(aid)
            if out is None:
                continue
            _index, _keys, res, atoms, _pl = out
            vs, ats, kind, release, _v0, _v1 = repair_picks(
                table, repair_plan(table, cfg), res.nexthop_words
            )
            per_vertex = lfas[aid] = {}
            lfa = kind == 1
            for v, a, r in zip(
                vs[lfa].tolist(), ats[lfa].tolist(), release[lfa].tolist()
            ):
                atom, batom = atoms[a], atoms[r]
                if isinstance(atom, NexthopAtom) or isinstance(
                    batom, NexthopAtom
                ):
                    continue  # vlink bundles: no single protected link
                per_vertex.setdefault(v, {})[atom] = (batom, ())
        for route in routes.values():
            v = getattr(route, "vertex", -1)
            backups = lfas.get(route.area_id, {}).get(v) if v >= 0 else None
            if backups:
                route.backups = dict(backups)

    def _derive_inter_area(
        self, area_results: dict, inter_routes: dict, only: set | None = None
    ) -> None:
        """Accumulate inter-area candidates into ``inter_routes`` from
        received Inter-Area-Prefix LSAs (RFC 2328 §16.2 hierarchy rules).
        Shared by the full run and the prefix-scoped partial run
        (``only`` restricts to the changed prefixes)."""
        active = self._active_ranges
        for aid, (index, _k, res, atoms, _pl) in area_results.items():
            area = self.areas.get(aid)
            if area is None:
                continue
            expanded: dict = {}  # ABR vertex -> its next-hop set
            if self.is_abr and aid != IPv4Address(0):
                # §16.2 hierarchy: an ABR examines summaries from the
                # backbone only (non-ABRs use their single attached area).
                continue
            for e in area.lsdb.all():
                lsa = e.lsa
                if (
                    lsa.type != P.LsaType.INTER_AREA_PREFIX
                    or lsa.adv_rtr == self.router_id
                    or lsa.is_maxage
                ):
                    continue
                prefix = lsa.body.prefix
                if only is not None and prefix not in only:
                    continue  # partial run: out-of-scope prefix
                if prefix in active:
                    # §16.2 (3): a summary that equals one of our own
                    # active area ranges is ignored.
                    continue
                abr_v = index.get(("R", lsa.adv_rtr))
                if abr_v is None or res.dist[abr_v] >= INF:
                    continue
                dist = int(res.dist[abr_v]) + lsa.body.metric
                nhs = expanded.get(abr_v)
                if nhs is None:
                    nhs = expanded[abr_v] = self._expand_atoms(
                        res.nexthop_words[abr_v], atoms
                    )
                cur = inter_routes.get(prefix)
                if cur is None or dist < cur.dist:
                    inter_routes[prefix] = V6Route(
                        prefix, dist, nhs, route_type="inter-area",
                        prefix_options=lsa.body.prefix_options,
                        area_id=aid, vertex=abr_v,
                    )
                elif dist == cur.dist:
                    inter_routes[prefix] = V6Route(
                        prefix, dist, cur.nexthops | nhs,
                        route_type="inter-area",
                        prefix_options=cur.prefix_options,
                        area_id=cur.area_id, vertex=cur.vertex,
                    )

    def _derive_external(
        self, area_results: dict, routes: dict, only: set | None = None
    ) -> dict:
        """AS-external route derivation (E1/E2 ranking, ASBR resolution
        through Inter-Area-Router LSAs).  Returns winners for prefixes
        with no internal path; shared by the full and partial runs."""
        ext_best: dict = {}
        seen_ext = set()
        for aid, (index, _k, res, atoms, _pl) in area_results.items():
            area = self.areas.get(aid)
            if area is None or area.no_external:
                continue
            for e in area.lsdb.all():
                lsa = e.lsa
                if lsa.type != P.LsaType.AS_EXTERNAL or lsa.is_maxage:
                    continue
                if lsa.adv_rtr == self.router_id:
                    continue
                prefix = lsa.body.prefix
                if only is not None and prefix not in only:
                    continue  # partial run: out-of-scope prefix
                if (lsa.key, aid) in seen_ext:
                    continue
                seen_ext.add((lsa.key, aid))
                asbr_v = index.get(("R", lsa.adv_rtr))
                if asbr_v is not None and res.dist[asbr_v] < INF:
                    asbr_dist = int(res.dist[asbr_v])
                    nhs = self._expand_atoms(
                        res.nexthop_words[asbr_v], atoms
                    )
                else:
                    # ASBR outside this area: resolve through an ABR's
                    # Inter-Area-Router LSA (RFC 5340 type 0x2004 — the
                    # v3 analog of the v2 type-4 summary).
                    resolved = self._asbr_via_inter_router(
                        area, index, res, atoms, lsa.adv_rtr
                    )
                    if resolved is None:
                        continue
                    asbr_dist, nhs = resolved
                if prefix in routes:
                    continue  # intra/inter win
                if lsa.body.e_bit:
                    rank = (1, lsa.body.metric, asbr_dist)
                    dist = lsa.body.metric
                else:
                    rank = (0, asbr_dist + lsa.body.metric, 0)
                    dist = asbr_dist + lsa.body.metric
                cur = ext_best.get(prefix)
                if cur is None or rank < cur[0]:
                    ext_best[prefix] = (
                        rank,
                        V6Route(prefix, dist, nhs, route_type="external"),
                    )
                elif rank == cur[0]:
                    ext_best[prefix] = (
                        rank,
                        V6Route(prefix, dist, cur[1].nexthops | nhs,
                                route_type="external"),
                    )
        return {p: r for p, (_rank, r) in ext_best.items()}

    def _run_spf_partial(self, partial: dict) -> None:
        """Prefix-scoped route recomputation over the cached per-area
        SPTs — no Dijkstra runs (reference route.rs:200-333).  Prefix
        LSAs are re-read from the live LSDB; reachability and next hops
        come from the cached SPT results."""
        self.spf_run_count += 1
        start_time = now = self.loop.clock.now()
        cache = self._spf_cache
        area_results = cache["area_results"]
        intra_by_area = cache["intra_by_area"]
        routes = dict(cache["routes"])
        inter_routes = dict(cache["inter_routes"])
        intra_set = set(partial["intra"])
        inter_network = set(partial["inter_network"])
        inter_router = set(partial["inter_router"])
        external = set(partial["external"])
        origination_dirty = False

        if intra_set:
            # Drop affected intra routes, then re-derive them for exactly
            # those prefixes (route.rs:214-237).
            for prefix in intra_set:
                r = routes.get(prefix)
                if r is not None and r.route_type == "intra-area":
                    del routes[prefix]
            for intra in intra_by_area.values():
                for prefix in intra_set:
                    intra.pop(prefix, None)
            for aid, (index, _k, res, atoms, _pl) in area_results.items():
                area = self.areas.get(aid)
                if area is None:
                    continue
                intra = intra_by_area.setdefault(aid, {})
                for e in area.lsdb.all():
                    lsa = e.lsa
                    if (
                        lsa.type != P.LsaType.INTRA_AREA_PREFIX
                        # current_age, not the stored header: a wall-clock
                        # expired LSA must not resurrect a route the full
                        # run (_area_spf) would exclude.
                        or e.current_age(now) >= P.MAX_AGE
                    ):
                        continue
                    body = lsa.body
                    if body.ref_type == int(P.LsaType.ROUTER):
                        v = index.get(("R", body.ref_adv_rtr))
                    elif body.ref_type == int(P.LsaType.NETWORK):
                        v = index.get(
                            ("N", body.ref_adv_rtr, int(body.ref_lsid))
                        )
                    else:
                        continue
                    if v is None or res.dist[v] >= INF:
                        continue
                    nhs = self._expand_atoms(res.nexthop_words[v], atoms)
                    for entry in body.prefixes:
                        prefix, metric = entry[0], entry[1]
                        if prefix not in intra_set:
                            continue  # scoped
                        opts = body.entry_opts(entry)
                        total = int(res.dist[v]) + metric
                        cur = intra.get(prefix)
                        if cur is None or total < cur.dist:
                            intra[prefix] = V6Route(
                                prefix, total, nhs, prefix_options=opts,
                                area_id=aid, vertex=v,
                            )
                        elif total == cur.dist:
                            intra[prefix] = V6Route(
                                prefix, total, cur.nexthops | nhs,
                                prefix_options=cur.prefix_options,
                                area_id=aid, vertex=cur.vertex,
                            )
            # Merge the recomputed intra winners across areas (same
            # preference as the full run: lowest dist, ECMP union).
            for intra in intra_by_area.values():
                for prefix in intra_set:
                    route = intra.get(prefix)
                    if route is None:
                        continue
                    cur = routes.get(prefix)
                    if cur is not None and cur.route_type != "intra-area":
                        cur = None  # intra beats inter/external
                    if cur is None or route.dist < cur.dist:
                        routes[prefix] = route
                    elif route.dist == cur.dist:
                        # Same cross-area ECMP merge as the full run:
                        # keep the first area's FRR consumption key.
                        routes[prefix] = V6Route(
                            prefix, route.dist,
                            cur.nexthops | route.nexthops,
                            route_type=cur.route_type,
                            prefix_options=cur.prefix_options,
                            area_id=cur.area_id, vertex=cur.vertex,
                        )
            # Prefixes now without an intra path fall back to a cached
            # inter-area candidate, else to the external stage.
            for prefix in intra_set:
                if prefix not in routes and prefix in inter_routes:
                    routes[prefix] = inter_routes[prefix]
            external |= {p for p in intra_set if p not in routes}
            origination_dirty = True
        # (the tables were edited in place: the adverts follow them
        # prefix by prefix)
        self._area_adverts(intra_by_area)

        if inter_network:
            for prefix in inter_network:
                inter_routes.pop(prefix, None)
                r = routes.get(prefix)
                if r is not None and r.route_type == "inter-area":
                    del routes[prefix]
            self._derive_inter_area(
                area_results, inter_routes, only=inter_network
            )
            for prefix in inter_network:
                cand = inter_routes.get(prefix)
                if cand is None:
                    continue
                cur = routes.get(prefix)
                if cur is None or cur.route_type != "intra-area":
                    routes[prefix] = cand
            external |= {p for p in inter_network if p not in routes}
            origination_dirty = True

        if inter_router or external:
            # An Inter-Area-Router change alters ASBR reachability, which
            # can affect ANY external route (route.rs:302-306).
            reevaluate_all = bool(inter_router)
            for prefix in list(routes):
                if routes[prefix].route_type == "external" and (
                    reevaluate_all or prefix in external
                ):
                    del routes[prefix]
            routes.update(
                self._derive_external(
                    area_results,
                    routes,
                    only=None if reevaluate_all else external,
                )
            )

        if origination_dirty and self.is_abr:
            self._originate_inter_area(inter_routes, area_results)

        log_type = (
            "intra" if intra_set
            else "inter" if inter_network
            else "external"
        )
        self.spf_log.append(
            {
                "run": self.spf_run_count,
                "type": log_type,
                "start-time": start_time,
                "end-time": self.loop.clock.now(),
                "route-count": len(routes),
            }
        )
        del self.spf_log[:-32]
        cache["routes"] = routes
        cache["inter_routes"] = inter_routes
        # Rebuilt routes need their repairs re-joined like the full run,
        # or a partial run would publish them backup-less and flap the
        # kernel entries off/on their precomputed repairs.
        with profiling.stage("ospf.spf", "publish"):
            self._publish(routes, area_results)

    def _area_adverts(self, intra_by_area: dict) -> None:
        """Bring what each area's intra-area table advertises into the
        other areas up to the run's tables (``KeptInter.adverts``: every
        prefix as it is or, under the area's address ranges, RFC 2328
        §12.4.3, a range at its largest component's distance), and set
        ``_active_ranges``."""
        tables = {
            aid: intra for aid, intra in intra_by_area.items()
            if aid in self.areas
        }
        self._active_ranges = self._inter_kept.adverts(
            tables,
            {aid: self.areas[aid].ranges for aid in tables},
            {name: i.config.area_id for name, i in self.interfaces.items()},
        )

    def _originate_inter_area(
        self, inter_routes: dict, area_results: dict
    ) -> None:
        """ABR duties (reference area.rs:602-643): our intra-area routes
        summarized everywhere, inter-area routes into the non-backbone
        areas only, never back into their own area nor (split horizon,
        area.rs:628-630) into an area their next hops exit through; the
        default into stub areas; the ASBRs each area reaches into the
        others; what is no longer wanted flushed."""
        self._inter_kept.originate(
            inter_routes,
            {
                aid: out for aid, out in area_results.items()
                if aid in self.areas
            },
            {
                aid: (
                    area.summary,
                    area.stub_default_cost if area.stub else None,
                    area.no_external,
                )
                for aid, area in self.areas.items()
            },
            {aid: area.lsdb for aid, area in self.areas.items()},
            self.router_id,
        )

    def _asbr_of(self, e):
        """The router id a Router-LSA's entry names as an ASBR (live,
        another router's, the E bit set), else None."""
        lsa = e.lsa
        if (
            lsa.is_maxage or P.RouterFlags.E not in lsa.body.flags
            or lsa.adv_rtr == self.router_id
        ):
            return None
        return lsa.adv_rtr

    @staticmethod
    def _asbr_distance(out, rid):
        index, _keys, res, _atoms, _pl = out
        v = index.get(("R", rid))
        if v is None or res.dist[v] >= INF:
            return None
        return int(res.dist[v])

    def _inter_key(self, aid, kind, dest) -> P.LsaKey:
        if kind == P.LsaType.INTER_AREA_ROUTER:
            dest = ("asbr", dest)
        return P.LsaKey(kind, self._inter_lsid(aid, dest), self.router_id)

    def _inter_originate(self, aid, key, dest, value) -> None:
        if key.type == P.LsaType.INTER_AREA_ROUTER:
            body = P.LsaInterAreaRouter(metric=value, dest_router_id=dest)
        else:
            body = P.LsaInterAreaPrefix(
                metric=value[0], prefix=dest, prefix_options=value[1]
            )
        self._originate(self.areas[aid], key.type, key.lsid, body)

    def _asbr_via_inter_router(self, area, index, res, atoms, asbr_rid):
        """(dist, nexthops) toward an out-of-area ASBR via the best ABR's
        Inter-Area-Router LSA in this area, or None."""
        best = None
        for e in area.lsdb.all():
            lsa = e.lsa
            if (
                lsa.type != P.LsaType.INTER_AREA_ROUTER
                or lsa.is_maxage
                or lsa.adv_rtr == self.router_id
                or lsa.body.dest_router_id != asbr_rid
            ):
                continue
            abr_v = index.get(("R", lsa.adv_rtr))
            if abr_v is None or res.dist[abr_v] >= INF:
                continue
            dist = int(res.dist[abr_v]) + lsa.body.metric
            nhs = self._expand_atoms(res.nexthop_words[abr_v], atoms)
            if best is None or dist < best[0]:
                best = (dist, nhs)
            elif dist == best[0]:
                best = (dist, best[1] | nhs)
        return best

    def _inter_lsid(self, area_id, prefix) -> IPv4Address:
        """v3 link-state ids are opaque; allocate one per (area,
        summarized prefix) — the reference numbers them per area, and a
        prefix summarized into two areas gets independent ids."""
        ids = self._inter_ids
        key = (area_id, prefix)
        lsid = ids.get(key)
        if lsid is None:
            # Gap-safe: next id after the highest in this area (seeded
            # sets may be sparse after completed flushes).
            top = max(
                (int(l) for (a, _p), l in ids.items() if a == area_id),
                default=0x0FFF,
            )
            lsid = IPv4Address(top + 1)
            ids[key] = lsid
        return lsid

    def redistribute(self, prefix: IPv6Network, metric: int = 20) -> None:
        """ASBR: inject a v6 external as an AS-external LSA (AS scope)."""
        was_asbr = bool(self.redistributed)
        self.redistributed[prefix] = metric
        lsid = self._inter_lsid(None, prefix)  # AS scope: one id space
        for area in self.areas.values():
            if area.no_external:
                continue
            self._originate(
                area,
                P.LsaType.AS_EXTERNAL,
                lsid,
                P.LsaAsExternalV3(metric=metric, e_bit=True, prefix=prefix),
            )
            break  # AS scope: one origination floods everywhere eligible
        if not was_asbr:
            self._originate_router_lsa()

    def _area_marshal(self, area: V3Area, vlink_nexthops: dict | None = None):
        """The area's LSDB as an ``SpfTopologyV3`` through the lowering
        the area keeps between runs, or None when we have no router LSA
        in the area.  Only the interface maps are built here, per
        interface; nothing loops over the LSDB's links."""
        # Per-link hop resolution: parallel p2p links to the same
        # neighbor are distinct atoms, matched by the neighbor's
        # interface id carried in its hellos (and in our router-LSA's
        # link entries) so each link's atom rides the right interface.
        nbr_hop = {}  # rid -> (ifname, src) — any one link (fallback)
        nbr_hop_by_ifid = {}  # (rid, nbr iface id) -> (ifname, src)
        lan_iface_of = {}  # network vertex key -> our iface on that LAN
        iface_srlg = {}
        for iface in self._area_ifaces(area):
            for nbr in iface.neighbors.values():
                if nbr.state == NsmState.FULL and not iface.is_lan:
                    nbr_hop[nbr.router_id] = (iface.name, nbr.src)
                    nbr_hop_by_ifid[(nbr.router_id, nbr.iface_id)] = (
                        iface.name,
                        nbr.src,
                    )
            if iface.is_lan and self._transit_active(iface):
                lan_iface_of[
                    ("N", iface.dr, self._dr_iface_id(iface))
                ] = iface
            if iface.config.srlg:
                iface_srlg[iface.name] = srlg_bits(iface.config.srlg)
        lowering = self._spf_lowerings.get(area.area_id)
        if lowering is None:
            lowering = self._spf_lowerings[area.area_id] = LoweredLsdbV3()
        st = lowering.build_topology(
            area.lsdb, self.router_id, self.loop.clock.now(), nbr_hop,
            nbr_hop_by_ifid, lan_iface_of, vlink_nexthops,
            iface_srlg=iface_srlg, partition_of=self.spf_partition_of,
            keep_unchanged=self.reuse_unchanged_areas,
        )
        if st is None:
            self._spf_delta_bases.pop(area.area_id, None)
            self._spf_lowerings.pop(area.area_id, None)
            self._area_kept.pop(area.area_id, None)
            self._derive_kept.pop(area.area_id, None)
        return st

    def _area_frr(self, area_id, topo) -> None:
        """IP-FRR: the area's backup-table batch rides the same SPF
        moment (distance rows + per-link post-convergence planes)."""
        cfg = self.frr
        if cfg is not None and cfg.active():
            from holo_tpu.frr.manager import ensure_engine

            self._frr_engine = ensure_engine(self._frr_engine, cfg)
            self.frr_tables[area_id] = self._frr_engine.compute(topo)
        else:
            self.frr_tables.pop(area_id, None)

    def _area_spf(self, area: V3Area, vlink_nexthops: dict | None = None):
        """One area's marshal and SPF outside a run (the protocol's own
        marshal as ``spf/synth_proto.py`` extracts it): (index, keys,
        result, atoms, prefix_lsas), or None when we have no router LSA
        in the area."""
        st = self._area_marshal(area, vlink_nexthops)
        if st is None:
            return None
        prev = self._spf_delta_bases.get(area.area_id)
        if prev is not st:
            link_spf_delta(prev, st)
        self._spf_delta_bases[area.area_id] = st
        mp_k = (
            self.max_paths
            if self.max_paths is not None and self.max_paths > 1
            else 1
        )
        res = self.backend.compute(st.topo, multipath_k=mp_k)
        self._area_frr(area.area_id, st.topo)
        return st.index, st.keys, res, st.atoms, st.prefix_lsas

    # -- rx/tx

    def _rx(self, msg: NetRxPacket) -> None:
        iface = self.interfaces.get(msg.ifname)
        if iface is None or not iface.up or iface.config.passive:
            # Passive circuits neither send NOR process OSPF packets.
            return
        try:
            pkt = P.Packet.decode(
                msg.data, src=msg.src, dst=msg.dst, auth=iface.config.auth
            )
        except Exception:
            _OSPF_RX_BAD.labels(instance=self.name).inc()
            return
        _OSPF_PACKETS.labels(instance=self.name, dir="rx").inc()
        if pkt.router_id == self.router_id:
            return
        if iface.config.auth is not None:
            # RFC 7166 §4.1 replay protection: per-neighbor monotonic
            # sequence numbers.
            last = iface.at_seqnos.get(pkt.router_id, -1)
            if pkt.auth_seqno <= last:
                return
            iface.at_seqnos[pkt.router_id] = pkt.auth_seqno
        # RFC 5340 §4.1.2: area and instance-id must match the interface.
        if (
            pkt.area_id != iface.config.area_id
            or pkt.instance_id != iface.config.instance_id
        ):
            return
        t = pkt.body.TYPE
        if t == P.PacketType.HELLO:
            self._rx_hello(iface, msg.src, pkt)
        elif t == P.PacketType.DB_DESC:
            self._rx_db_desc(iface, msg.src, pkt)
        elif t == P.PacketType.LS_REQUEST:
            self._rx_ls_request(iface, msg.src, pkt)
        elif t == P.PacketType.LS_UPDATE:
            self._rx_ls_update(iface, msg.src, pkt)
        elif t == P.PacketType.LS_ACK:
            self._rx_ls_ack(iface, msg.src, pkt)

    def _send(self, iface: V3Interface, dst, body) -> None:
        pkt = P.Packet(router_id=self.router_id,
                       area_id=iface.config.area_id, body=body,
                       instance_id=iface.config.instance_id)
        auth = iface.config.auth
        if auth is not None:
            # One keychain consultation per packet: SA id and digest
            # must come from the same key (resolve_send; no active key
            # sends unauthenticated, like the v2/IS-IS paths).
            auth = auth.resolve_send()
        if auth is not None:
            self._at_seqno += 1
            if self._nvstore is not None and self._at_seqno >= self._at_reserved:
                self._reserve_at_seqnos()
            auth.seqno = self._at_seqno
        _OSPF_PACKETS.labels(instance=self.name, dir="tx").inc()
        self.netio.send(
            iface.name,
            iface.link_local,
            dst,
            pkt.encode(iface.link_local, dst, auth=auth),
        )
