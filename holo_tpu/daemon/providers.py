"""Base system providers: interface, system, keychain, policy, routing.

Reference: SURVEY.md §2.2 — each is an actor + northbound provider + ibus
server.  The routing provider owns the RIB manager and spawns/stops
protocol instances from configuration (the reference does this in
holo-routing/src/northbound/configuration.rs:1228-1301).
"""

from __future__ import annotations

import logging

log = logging.getLogger("holo_tpu.providers")

from dataclasses import dataclass, field
from ipaddress import IPv4Address, ip_interface

from holo_tpu.northbound.provider import CommitPhase, Provider
from holo_tpu.protocols.ospf.instance import (
    IfConfig,
    IfUpMsg,
    InstanceConfig,
    OspfInstance,
    SpfTimers,
)
from holo_tpu.protocols.ospf.interface import IfType
from holo_tpu.routing.rib import Kernel, MockKernel, RibManager
from holo_tpu.spf.backend import ScalarSpfBackend, TpuSpfBackend
from holo_tpu.utils.ibus import (
    TOPIC_ADDRESS_ADD,
    TOPIC_HOSTNAME,
    TOPIC_INTERFACE_UPD,
    TOPIC_KEYCHAIN_UPD,
    TOPIC_POLICY_UPD,
    TOPIC_ROUTER_ID,
    Ibus,
)
from holo_tpu.utils.netio import NetIo
from holo_tpu.utils.runtime import Actor, EventLoop
from holo_tpu.utils.southbound import InterfaceUpdMsg


@dataclass
class IfaceState:
    name: str
    ifindex: int
    mtu: int = 1500
    enabled: bool = True
    operative: bool = True
    addresses: list = field(default_factory=list)
    # (parent, vlan-id) of the kernel 802.1Q device we actuated for this
    # interface; None = no vlan device created by us.
    vlan_actuated: tuple | None = None


class InterfaceProvider(Provider, Actor):
    """Interface table owner.  In the daemon this mirrors the OS via
    netlink (holo-interface/src/netlink.rs); under test it is driven by
    config + synthetic link events."""

    name = "interface"
    subtree_prefixes = ("interfaces",)

    def __init__(self, ibus: Ibus):
        self.ibus = ibus
        self.interfaces: dict[str, IfaceState] = {}
        self._next_ifindex = 1
        # Set by the daemon: where connected (direct) routes are sent.
        self.routing_actor: str | None = None
        self._direct: set = set()  # prefixes currently installed as direct
        # Set by the daemon when kernel actuation is available: config
        # admin-status/MTU changes then apply via netlink (reference
        # holo-interface/src/netlink.rs:242-270).
        self.link_mgr = None

    def handle(self, msg):
        pass

    def validate(self, new_tree) -> None:
        # Fail-closed at commit time (same pattern as the keychain
        # lifetime validation): a bad vlan-id or a vlan interface
        # without its parent must reject the commit, not silently skip
        # device creation at apply time.
        from holo_tpu.northbound.provider import CommitError

        for name, entry in (
            new_tree.get("interfaces/interface", {}) or {}
        ).items():
            if entry.get("type") != "vlan":
                continue
            vid = entry.get("vlan-id")
            if vid is not None and not 1 <= vid <= 4094:
                raise CommitError(
                    f"interface {name}: vlan-id must be 1-4094, got {vid}"
                )
            if (vid is None) != (not entry.get("parent-interface")):
                raise CommitError(
                    f"interface {name}: vlan interfaces need BOTH "
                    f"parent-interface and vlan-id"
                )

    def _sync_direct_routes(self) -> None:
        """Connected prefixes go into the RIB as protocol 'direct' at
        distance 0 with an empty next-hop set — they win over any IGP copy
        of the same prefix and the empty set keeps them out of the kernel
        FIB (which already has them)."""
        from holo_tpu.utils.southbound import Protocol, RouteKeyMsg, RouteMsg

        if self.routing_actor is None:
            return
        wanted = {
            a.network
            for st in self.interfaces.values()
            if st.operative
            for a in st.addresses
        }
        for prefix in self._direct - wanted:
            self.ibus.request(
                self.routing_actor,
                RouteKeyMsg(Protocol.DIRECT, prefix),
                sender=self.name,
            )
        for prefix in wanted - self._direct:
            self.ibus.request(
                self.routing_actor,
                RouteMsg(Protocol.DIRECT, prefix, 0, 0, frozenset()),
                sender=self.name,
            )
        self._direct = wanted

    def commit(self, phase, old, new, changes):
        if phase != CommitPhase.APPLY:
            return
        conf = new.get("interfaces/interface", {}) or {}
        for name, entry in conf.items():
            st = self.interfaces.get(name)
            if st is None:
                st = IfaceState(name=name, ifindex=self._next_ifindex)
                self._next_ifindex += 1
                self.interfaces[name] = st
            # 802.1Q subinterface actuation is CHANGE-driven (reference
            # configuration.rs:122-131,354-365 Event::VlanCreate fires
            # on the config change, not on map appearance): whenever the
            # wanted (parent, vlan-id) differs from what we actuated,
            # tear the old device down and create the new one.
            want_vlan = (
                (entry.get("parent-interface"), entry.get("vlan-id"))
                if entry.get("type") == "vlan"
                and entry.get("parent-interface")
                and entry.get("vlan-id") is not None
                else None
            )
            if self.link_mgr is not None and want_vlan != st.vlan_actuated:
                try:
                    if st.vlan_actuated is not None:
                        self.link_mgr.delete_link(name)
                        st.vlan_actuated = None
                    if want_vlan is not None:
                        self.link_mgr.create_vlan(
                            want_vlan[0], name, want_vlan[1]
                        )
                        st.vlan_actuated = want_vlan
                except (OSError, ValueError) as e:
                    log.error("vlan actuation failed for %s: %s", name, e)
            new_mtu = entry.get("mtu", 1500)
            new_enabled = entry.get("enabled", True)
            if self.link_mgr is not None and (
                new_mtu != st.mtu or new_enabled != st.enabled
            ):
                try:
                    self.link_mgr.set_link(
                        name,
                        up=new_enabled if new_enabled != st.enabled else None,
                        mtu=new_mtu if new_mtu != st.mtu else None,
                    )
                except OSError as e:
                    log.error("link apply failed for %s: %s", name, e)
            st.mtu = new_mtu
            st.enabled = new_enabled
            st.addresses = [ip_interface(a) for a in entry.get("address", [])]
            # Causal origin: an interface config change is a topology
            # event (convergence trigger class "ifconfig").
            from holo_tpu.telemetry import convergence

            eid = convergence.begin(
                convergence.TRIGGER_IFCONFIG, ifname=name,
                operative=st.enabled and st.operative,
            )
            with convergence.activation(eid):
                self.ibus.publish(
                    TOPIC_INTERFACE_UPD,
                    # operative = admin AND carrier: a config commit must
                    # not report a carrier-down link as up (the RIB treats
                    # operative=True as an FRR restore signal).
                    InterfaceUpdMsg(ifname=name, ifindex=st.ifindex,
                                    mtu=st.mtu,
                                    operative=st.enabled and st.operative),
                    ifname=name,
                )
            for addr in st.addresses:
                self.ibus.publish(TOPIC_ADDRESS_ADD, (name, addr), ifname=name)
        from holo_tpu.utils.ibus import TOPIC_INTERFACE_DEL

        for name in list(self.interfaces):
            if name not in conf:
                st = self.interfaces.pop(name)
                # Symmetric teardown: a vlan device WE created goes away
                # with its config entry, or the kernel link leaks and a
                # later re-add with a different id fails changelink.
                if st.vlan_actuated is not None and self.link_mgr is not None:
                    try:
                        self.link_mgr.delete_link(name)
                    except OSError as e:
                        log.error("vlan teardown failed for %s: %s", name, e)
                self.ibus.publish(TOPIC_INTERFACE_DEL, name, ifname=name)
        self._publish_router_id()
        self._sync_direct_routes()

    def _publish_router_id(self):
        """Router-ID derivation: highest interface address (reference
        holo-interface/src/interface.rs Router-ID logic)."""
        best = None
        for st in self.interfaces.values():
            for a in st.addresses:
                if a.version == 4 and (best is None or int(a.ip) > int(best)):
                    best = a.ip
        self.ibus.publish(TOPIC_ROUTER_ID, best)

    def apply_kernel_event(self, ev) -> None:
        """Feed a NetlinkMonitor LinkEvent into the provider table (the
        production path; config-driven interfaces take precedence)."""
        from holo_tpu.utils.ibus import TOPIC_INTERFACE_DEL

        if ev.kind == "link":
            st = self.interfaces.get(ev.ifname)
            if st is None:
                st = IfaceState(name=ev.ifname, ifindex=ev.ifindex)
                self.interfaces[ev.ifname] = st
            st.ifindex = ev.ifindex
            st.operative = ev.up and ev.running
            if ev.mtu:
                st.mtu = ev.mtu
            # Causal origin: a kernel link event is the carrier-loss /
            # carrier-recovery moment (convergence trigger "carrier").
            from holo_tpu.telemetry import convergence

            eid = convergence.begin(
                convergence.TRIGGER_CARRIER, ifname=ev.ifname,
                operative=st.operative,
            )
            with convergence.activation(eid):
                self.ibus.publish(
                    TOPIC_INTERFACE_UPD,
                    InterfaceUpdMsg(ifname=ev.ifname, ifindex=st.ifindex,
                                    mtu=st.mtu, operative=st.operative),
                    ifname=ev.ifname,
                )
        elif ev.kind == "link-del":
            if self.interfaces.pop(ev.ifname, None) is not None:
                self.ibus.publish(TOPIC_INTERFACE_DEL, ev.ifname,
                                  ifname=ev.ifname)
                self._publish_router_id()
        elif ev.kind in ("addr", "addr-del"):
            for st in self.interfaces.values():
                if st.ifindex == ev.ifindex:
                    if ev.kind == "addr" and ev.addr not in st.addresses:
                        st.addresses.append(ev.addr)
                        self.ibus.publish(TOPIC_ADDRESS_ADD,
                                          (st.name, ev.addr), ifname=st.name)
                    elif ev.kind == "addr-del" and ev.addr in st.addresses:
                        st.addresses.remove(ev.addr)
                    self._publish_router_id()
                    self._sync_direct_routes()
                    break

    def get_state(self, path=None):
        return {
            "interfaces": {
                "interface": {
                    name: {
                        "name": name,
                        "if-index": st.ifindex,
                        "oper-status": "up" if st.operative else "down",
                        "mtu": st.mtu,
                    }
                    for name, st in self.interfaces.items()
                }
            }
        }


class SystemProvider(Provider, Actor):
    name = "system"
    subtree_prefixes = ("system",)

    def __init__(self, ibus: Ibus):
        self.ibus = ibus
        self.hostname = ""

    def handle(self, msg):
        pass

    def commit(self, phase, old, new, changes):
        if phase != CommitPhase.APPLY:
            return
        hostname = new.get("system/hostname")
        if hostname != self.hostname:
            self.hostname = hostname or ""
            self.ibus.publish(TOPIC_HOSTNAME, self.hostname)

    def get_state(self, path=None):
        return {"system": {"hostname": self.hostname}}


class KeychainProvider(Provider, Actor):
    name = "keychain"
    subtree_prefixes = ("key-chains",)

    def __init__(self, ibus: Ibus):
        self.ibus = ibus
        self.keychains: dict = {}

    def handle(self, msg):
        pass

    def validate(self, new_tree) -> None:
        # FAIL-CLOSED on lifetimes: a malformed date-and-time must
        # reject the commit, never silently become an unbounded key.
        from holo_tpu.northbound.provider import CommitError
        from holo_tpu.utils.keychain import Keychain

        for name, chain in (
            new_tree.get("key-chains/key-chain", {}) or {}
        ).items():
            try:
                Keychain.from_config(name, chain)
            except ValueError as e:
                raise CommitError(f"key-chain {name!r}: {e}") from e

    def commit(self, phase, old, new, changes):
        from holo_tpu.utils.ibus import TOPIC_KEYCHAIN_DEL

        if phase != CommitPhase.APPLY:
            return
        prev = self.keychains
        self.keychains = new.get("key-chains/key-chain", {}) or {}
        for name in prev.keys() - self.keychains.keys():
            self.ibus.publish(TOPIC_KEYCHAIN_DEL, name)
        for name, chain in self.keychains.items():
            if prev.get(name) != chain:  # changed or new only
                self.ibus.publish(TOPIC_KEYCHAIN_UPD, name)


class PolicyProvider(Provider, Actor):
    name = "policy"
    subtree_prefixes = ("routing-policy",)

    def __init__(self, ibus: Ibus):
        from holo_tpu.utils.policy import PolicyEngine

        self.ibus = ibus
        self.engine = PolicyEngine()
        self.policies: dict = {}
        self.defined_sets: dict = {}

    def handle(self, msg):
        pass

    def commit(self, phase, old, new, changes):
        if phase != CommitPhase.APPLY:
            return
        self.policies = new.get("routing-policy/policy-definition", {}) or {}
        self.defined_sets = new.get("routing-policy/defined-sets", {}) or {}
        self.engine.load_from_config(
            {
                "defined-sets": self.defined_sets,
                "policy-definition": self.policies,
            }
        )
        for name in self.policies:
            self.ibus.publish(TOPIC_POLICY_UPD, name)


def _parse_system_id(s: str) -> bytes | None:
    """Parse an IS-IS system id: dotted-hex ('1921.6800.1001') or six
    dotted-decimal octets ('0.0.0.0.0.1').  Returns None if invalid."""
    parts = s.split(".")
    try:
        if len(parts) == 3 and all(len(p) == 4 for p in parts):
            return bytes.fromhex("".join(parts))
        if len(parts) == 6:
            vals = [int(p) for p in parts]
            if all(0 <= v <= 255 for v in vals):
                return bytes(vals)
    except ValueError:
        pass
    return None


class RoutingProvider(Provider, Actor):
    """RIB owner + protocol instance lifecycle from configuration."""

    name = "routing"
    subtree_prefixes = ("routing",)

    # Optional placement hooks (set by the daemon): with preemptive
    # isolation each protocol instance is registered on its own
    # ThreadedLoop instead of the shared loop (utils/preempt.py).
    instance_placer = None
    instance_unplacer = None

    def _place_instance(self, inst):
        """Registers the instance and returns the object the provider
        should hold: the instance itself (cooperative), or a marshalling
        handle when the daemon placed it on its own thread."""
        if self.instance_placer is not None:
            return self.instance_placer(inst) or inst
        if hasattr(inst, "attach_loop"):
            # Multi-actor node (IS-IS L1/L2): registers the per-level
            # actors plus the node's own packet entry point.
            inst.attach_loop(self.loop)
        else:
            self.loop.register(inst)
        return inst

    def _unplace_instance(self, name: str) -> None:
        if self.instance_unplacer is not None:
            self.instance_unplacer(name)
            return
        if name in self.loop.actors:
            self.loop.unregister(name)
        # Multi-actor node: its per-level actors carry "<name>-..." names.
        for sub in [a for a in self.loop.actors if a.startswith(f"{name}-")]:
            self.loop.unregister(sub)

    def validate(self, new_tree) -> None:
        from holo_tpu.northbound.provider import CommitError

        sid = new_tree.get("routing/control-plane-protocols/isis/system-id")
        if sid is not None and _parse_system_id(sid) is None:
            raise CommitError(f"invalid IS-IS system-id {sid!r}")
        # RFC 2080: RIPng relies on IPsec, it has no in-protocol auth.
        for ifname, if_conf in (
            new_tree.get("routing/control-plane-protocols/ripng/interface")
            or {}
        ).items():
            if if_conf.get("authentication"):
                raise CommitError(
                    f"ripng interface {ifname}: RIPng has no in-protocol "
                    f"authentication (RFC 2080)"
                )
        # Keychain references must resolve within the same candidate.
        chains = new_tree.get("key-chains/key-chain", {}) or {}
        areas = new_tree.get(
            "routing/control-plane-protocols/ospfv2/area", {}
        ) or {}
        for area_conf in areas.values():
            for ifname, if_conf in (area_conf.get("interface") or {}).items():
                kc = (if_conf.get("authentication") or {}).get("key-chain")
                if kc is None:
                    continue
                if kc not in chains:
                    raise CommitError(
                        f"interface {ifname}: unknown key-chain {kc!r}"
                    )
                if not (chains[kc].get("key") or {}):
                    raise CommitError(
                        f"interface {ifname}: key-chain {kc!r} has no keys"
                    )
        # Same resolution check for EVERY key-chain consumer — a typo'd
        # name must fail the commit, not silently run with the random
        # fail-closed key.
        isis_base = "routing/control-plane-protocols/isis"
        kc_refs = [
            (
                "isis authentication",
                (new_tree.get(f"{isis_base}/authentication") or {}).get(
                    "key-chain"
                ),
            )
        ]
        for ifname, if_conf in (
            new_tree.get(f"{isis_base}/interface") or {}
        ).items():
            kc_refs.append(
                (
                    f"isis interface {ifname} hello-authentication",
                    (if_conf.get("hello-authentication") or {}).get(
                        "key-chain"
                    ),
                )
            )
        for ifname, if_conf in (
            new_tree.get("routing/control-plane-protocols/ripv2/interface")
            or {}
        ).items():
            kc_refs.append(
                (
                    f"ripv2 interface {ifname}",
                    (if_conf.get("authentication") or {}).get("key-chain"),
                )
            )
        for where, kc in kc_refs:
            if kc is None:
                continue
            if kc not in chains:
                raise CommitError(f"{where}: unknown key-chain {kc!r}")
            if not (chains[kc].get("key") or {}):
                # An empty chain resolves to the fail-closed random key
                # — a silent auth outage nobody asked for.
                raise CommitError(f"{where}: key-chain {kc!r} has no keys")
        # OSPFv3 authentication is the RFC 7166 trailer (HMAC family):
        # v2-style simple/md5 types have no v3 encoding — reject them,
        # and key-chain references must resolve.
        v3_areas = new_tree.get(
            "routing/control-plane-protocols/ospfv3/area", {}
        ) or {}
        for area_conf in v3_areas.values():
            for ifname, if_conf in (area_conf.get("interface") or {}).items():
                auth = if_conf.get("authentication") or {}
                if auth.get("type") in ("simple", "md5"):
                    raise CommitError(
                        f"ospfv3 interface {ifname}: OSPFv3 uses the "
                        f"RFC 7166 authentication trailer (key + "
                        f"crypto-algorithm or key-chain), not v2-style "
                        f"{auth['type']!r}"
                    )
                kc = auth.get("key-chain")
                if kc is not None and kc not in chains:
                    raise CommitError(
                        f"ospfv3 interface {ifname}: unknown key-chain "
                        f"{kc!r}"
                    )
                if kc is not None:
                    if not (chains[kc].get("key") or {}):
                        raise CommitError(
                            f"ospfv3 interface {ifname}: key-chain {kc!r} "
                            f"has no keys"
                        )
                    # Every key must carry an RFC 7166-capable algorithm
                    # or its active window would be a silent auth outage
                    # (resolve_send -> None -> unauthenticated sends).
                    from holo_tpu.protocols.ospf.packet_v3 import (
                        _AT_KEYCHAIN_ALGO,
                    )

                    bad = [
                        kid
                        for kid, kconf in (
                            chains[kc].get("key") or {}
                        ).items()
                        if _AT_KEYCHAIN_ALGO.get(
                            kconf.get("crypto-algorithm", "md5")
                        )
                        is None
                    ]
                    if bad:
                        raise CommitError(
                            f"ospfv3 interface {ifname}: key-chain {kc!r} "
                            f"key(s) {bad} have no RFC 7166 algorithm "
                            f"(md5 is not valid for OSPFv3)"
                        )
        if new_tree.get("routing/control-plane-protocols/ospfv3/redistribute"):
            raise CommitError(
                "ospfv3 redistribution is not supported yet"
            )
        # RFC 2328: the backbone can never be a stub area (any spelling of
        # area id 0 counts).
        for proto in ("ospfv2", "ospfv3"):
            areas_conf = new_tree.get(
                f"routing/control-plane-protocols/{proto}/area", {}
            ) or {}
            for area_id, area_conf in areas_conf.items():
                try:
                    is_backbone = int(IPv4Address(area_id)) == 0
                except Exception:
                    is_backbone = area_id in ("0", "0.0.0.0")
                if is_backbone and area_conf.get("area-type") in (
                    "stub", "nssa"
                ):
                    raise CommitError(
                        "the backbone area cannot be stub or NSSA"
                    )

    def __init__(
        self,
        loop: EventLoop,
        ibus: Ibus,
        netio,
        interface_provider: InterfaceProvider,
        kernel: Kernel | None = None,
        prefix: str = "",
        policy_engine=None,
        keychains: "KeychainProvider | None" = None,
        nvstore=None,
        link_mgr=None,
        yang_notify=None,
        microloop_delay: float = 0.0,
    ):
        self.loop = loop
        self.ibus = ibus
        # Sink for protocol YANG notifications (reference notification.rs
        # -> northbound -> management clients); the daemon points this at
        # its fan-out so gRPC/gNMI Subscribe streams see them.
        self.yang_notify = yang_notify
        self.policy_engine = policy_engine
        self.keychains = keychains
        self.nvstore = nvstore
        # Link actuation (macvlans, admin/MTU): LinkManager in production,
        # MockLinkManager under test.
        if link_mgr is None:
            from holo_tpu.routing.netlink import MockLinkManager

            link_mgr = MockLinkManager()
        self.link_mgr = link_mgr
        # netio: either a NetIo (shared sender) or a callable actor->NetIo
        # (MockFabric.sender_for) so each protocol actor receives its own
        # bound transmit handle.
        self.netio_factory = netio if callable(netio) else (lambda _actor: netio)
        self.ifp = interface_provider
        self.prefix = prefix
        self.rib = RibManager(
            ibus, kernel or MockKernel(), microloop_delay=microloop_delay
        )
        self.rib.on_change = self._rib_changed
        from holo_tpu.routing.sink import RouteSink

        self._route_sink = RouteSink(self.rib)
        self.instances: dict[str, OspfInstance] = {}

    def attach(self, loop_):
        super().attach(loop_)
        loop_.register(self.rib, name=f"{self.prefix}routing-rib")
        from holo_tpu.utils.ibus import (
            TOPIC_INTERFACE_DEL,
            TOPIC_KEYCHAIN_DEL,
            TOPIC_KEYCHAIN_UPD,
        )

        from holo_tpu.utils.ibus import (
            TOPIC_REDISTRIBUTE_ADD,
            TOPIC_REDISTRIBUTE_DEL,
        )

        self.ibus.subscribe(TOPIC_INTERFACE_DEL, self.name)
        self.ibus.subscribe(TOPIC_KEYCHAIN_UPD, self.name)
        self.ibus.subscribe(TOPIC_KEYCHAIN_DEL, self.name)
        self.ibus.subscribe(TOPIC_REDISTRIBUTE_ADD, self.name)
        self.ibus.subscribe(TOPIC_REDISTRIBUTE_DEL, self.name)
        # BFD is always-on, spawned at startup inside the routing provider
        # (reference holo-routing/src/lib.rs:261-281).
        from holo_tpu.protocols.bfd import BfdInstance

        self.bfd = BfdInstance(
            self.netio_factory(f"{self.prefix}bfd"), self.ibus,
            notif_cb=self.yang_notify,
        )
        loop_.register(self.bfd, name=f"{self.prefix}bfd")

    def handle(self, msg):
        from holo_tpu.utils.ibus import (
            TOPIC_INTERFACE_DEL,
            TOPIC_KEYCHAIN_DEL,
            TOPIC_KEYCHAIN_UPD,
            IbusMsg,
        )

        from holo_tpu.utils.ibus import (
            TOPIC_REDISTRIBUTE_ADD,
            TOPIC_REDISTRIBUTE_DEL,
        )

        if isinstance(msg, IbusMsg) and msg.topic in (
            TOPIC_REDISTRIBUTE_ADD,
            TOPIC_REDISTRIBUTE_DEL,
        ):
            self._handle_redistribution(msg)
            return
        if isinstance(msg, IbusMsg) and msg.topic in (
            TOPIC_KEYCHAIN_UPD,
            TOPIC_KEYCHAIN_DEL,
        ):
            # Key rotation: re-resolve AuthCtx for interfaces referencing
            # the changed keychain (in place — adjacencies re-key live).
            self._refresh_ospf_auth()
            self._refresh_ospfv3_auth()
            self._refresh_isis_auth()
            self._refresh_rip_auth()
            return
        if isinstance(msg, IbusMsg) and msg.topic == TOPIC_INTERFACE_DEL:
            # Interface removed from the system: down it in every protocol
            # instance that uses it (stops hellos, withdraws the subnet).
            from holo_tpu.protocols.isis.instance import IsisIfDownMsg, IsisInstance
            from holo_tpu.protocols.ospf.instance import IfDownMsg
            from holo_tpu.protocols.ospf.instance_v3 import (
                OspfV3Instance,
                V3IfDownMsg,
            )

            ifname = msg.payload
            for inst in self.instances.values():
                if isinstance(inst, OspfInstance) and ifname in inst._if_area:
                    self.loop.send(inst.name, IfDownMsg(ifname))
                elif isinstance(inst, OspfV3Instance) and ifname in inst.interfaces:
                    self.loop.send(inst.name, V3IfDownMsg(ifname))
                elif isinstance(inst, IsisInstance) and ifname in inst.interfaces:
                    self.loop.send(inst.name, IsisIfDownMsg(ifname))
                elif (
                    hasattr(inst, "instances")
                    and hasattr(inst, "if_down")
                    and ifname in inst.interfaces
                ):
                    # IS-IS L1/L2 node: marshalled call downs both levels.
                    inst.if_down(ifname)

    def commit(self, phase, old, new, changes):
        if phase != CommitPhase.APPLY:
            return
        self._last_tree = new
        self._apply_ospfv2(new)
        self._apply_ospfv3(new)
        self._apply_isis(new)
        self._apply_bgp(new)
        self._apply_vrrp(new)
        self._apply_ldp(new)
        self._apply_rip(new)
        self._apply_igmp(new)
        self._apply_static(new)

    def _handle_redistribution(self, msg) -> None:
        """RIB redistribution → OSPF type-5 origination (reference:
        redistribution pub/sub, holo-routing/src/rib.rs:71)."""
        from holo_tpu.utils.ibus import TOPIC_REDISTRIBUTE_ADD
        from holo_tpu.utils.southbound import Protocol

        inst = self.instances.get("ospfv2")
        wanted = getattr(self, "_ospf_redistribute", set())
        if inst is None:
            return
        payload = msg.payload
        proto = payload.protocol
        if proto in (Protocol.OSPFV2,):
            return  # never re-inject our own routes
        if payload.prefix.version != 4:
            return
        if msg.topic == TOPIC_REDISTRIBUTE_ADD:
            if proto.value in wanted:
                inst.redistribute(payload.prefix, metric=max(payload.metric, 1))
            elif payload.prefix in inst.redistributed:
                # Best route switched to a non-redistributed protocol: the
                # type-5 must go (the RIB only publishes DEL on full
                # removal, so the ADD with the new winner is our signal).
                inst.withdraw_redistributed(payload.prefix)
        else:
            inst.withdraw_redistributed(payload.prefix)

    def _refresh_ospf_auth(self) -> None:
        tree = getattr(self, "_last_tree", None)
        inst = self.instances.get("ospfv2")
        if tree is None or inst is None:
            return
        areas = tree.get("routing/control-plane-protocols/ospfv2/area", {}) or {}
        for area_conf in areas.values():
            for ifname, if_conf in (area_conf.get("interface") or {}).items():
                ai = inst._iface(ifname)
                if ai is not None:
                    ai[1].config.auth = self._ospf_auth(
                        if_conf.get("authentication")
                    )

    # -- OSPFv2 lifecycle (holo-routing northbound/configuration.rs analog)

    def _apply_ospfv2(self, new):
        base = "routing/control-plane-protocols/ospfv2"
        conf = new.get(base)
        enabled = bool(conf) and new.get(f"{base}/enabled", True)
        inst = self.instances.get("ospfv2")
        if not enabled:
            if inst is not None:
                # Withdraw every route the instance installed before it goes
                # (reference: instance stop purges its RIB contributions).
                from holo_tpu.utils.southbound import Protocol, RouteKeyMsg

                for prefix in inst.routes:
                    self.rib.route_del(RouteKeyMsg(Protocol.OSPFV2, prefix))
                self._unplace_instance(inst.name)
                del self.instances["ospfv2"]
            return
        router_id = new.get(f"{base}/router-id")
        if router_id is None:
            return  # not ready (reference: instance waits for router-id)
        spf = new.get(f"{base}/spf-control", {}) or {}
        delay = spf.get("ietf-spf-delay", {}) or {}
        timers = SpfTimers(
            initial_delay=delay.get("initial-delay", 50) / 1000,
            short_delay=delay.get("short-delay", 200) / 1000,
            long_delay=delay.get("long-delay", 5000) / 1000,
            hold_down=delay.get("hold-down", 10000) / 1000,
            time_to_learn=delay.get("time-to-learn", 500) / 1000,
        )
        backend_name = spf.get("backend", "scalar")
        # Reuse the live backend when the engine kind is unchanged (the
        # ensure_engine pattern): a rebuilt TpuSpfBackend on every
        # commit would discard the warm jit/graph caches and mint a
        # fresh breaker metric series each time.
        want = TpuSpfBackend if backend_name == "tpu" else ScalarSpfBackend
        prev = getattr(inst, "backend", None) if inst is not None else None
        # A pipelined backend wraps the real one (AsyncSpfBackend.inner,
        # ISSUE 9): the reuse check looks through the facade, and a
        # fresh tpu backend rides the process pipeline when one is
        # armed (wrap_spf_backend is the identity otherwise).
        from holo_tpu.pipeline import wrap_spf_backend

        prev_core = getattr(prev, "inner", prev)
        backend = prev if type(prev_core) is want else wrap_spf_backend(want())
        old_redist = getattr(self, "_ospf_redistribute", set())
        self._ospf_redistribute = set(new.get(f"{base}/redistribute") or [])
        redist_changed = old_redist != self._ospf_redistribute
        if inst is None:
            inst = OspfInstance(
                name=f"{self.prefix}ospfv2",
                config=InstanceConfig(router_id=IPv4Address(router_id), spf=timers),
                netio=self.netio_factory(f"{self.prefix}ospfv2"),
                spf_backend=backend,
                notif_cb=self.yang_notify,
                nvstore=self.nvstore,
            )
            inst = self._place_instance(inst)
            inst.attach_ibus(
                self.ibus,
                routing_actor=f"{self.prefix}routing-rib",
                bfd_actor=f"{self.prefix}bfd",
            )
            self.instances["ospfv2"] = inst
        else:
            inst.config.router_id = IPv4Address(router_id)
            inst.config.spf = timers
            inst.backend = backend
        # IP fast reroute (mirrors the reference YANG fast-reroute
        # container: ietf-ospf fast-reroute/lfa plus holo's remote-lfa /
        # ti-lfa extension leaves).  A change must force a full SPF run:
        # that is what recomputes (or, on disable, drops) the backup
        # tables and republishes routes with the new repair set.
        new_frr = self._frr_config(new.get(f"{base}/fast-reroute"))
        if new_frr != inst.config.frr:
            inst.config.frr = new_frr
            inst._schedule_spf()
        # RFC 6987 stub-router maintenance mode (max-metric router-LSA).
        inst.set_stub_router(bool(new.get(f"{base}/stub-router", False)))

        areas = new.get(f"{base}/area", {}) or {}
        for area_id, area_conf in areas.items():
            area_type = area_conf.get("area-type", "normal")
            stub = area_type == "stub"
            nssa = area_type == "nssa"
            stub_cost = area_conf.get("default-cost", 1)
            for ifname, if_conf in (area_conf.get("interface") or {}).items():
                if ifname in inst._if_area:
                    # Live reconfiguration on the running circuit
                    # (reference configuration.rs InterfaceUpdate
                    # family); auth refreshes via _refresh_ospf_auth.
                    st = self.ifp.interfaces.get(ifname)
                    inst.iface_cost_update(ifname, if_conf.get("cost", 10))
                    inst.iface_update(
                        ifname,
                        hello=if_conf.get("hello-interval", 10),
                        dead=if_conf.get("dead-interval", 40),
                        priority=if_conf.get("priority", 1),
                        passive=if_conf.get("passive", False),
                        mtu=st.mtu if st is not None else None,
                        mtu_ignore=if_conf.get("mtu-ignore", False),
                        transmit_delay=if_conf.get("transmit-delay", 1),
                    )
                    continue
                st = self.ifp.interfaces.get(ifname)
                if st is None or not st.addresses:
                    continue
                addr = st.addresses[0].network
                host = st.addresses[0].ip
                cfg = IfConfig(
                    area_id=IPv4Address(area_id),
                    if_type=(
                        IfType.POINT_TO_POINT
                        if if_conf.get("interface-type") == "point-to-point"
                        else IfType.BROADCAST
                    ),
                    cost=if_conf.get("cost", 10),
                    hello_interval=if_conf.get("hello-interval", 10),
                    dead_interval=if_conf.get("dead-interval", 40),
                    rxmt_interval=if_conf.get("retransmit-interval", 5),
                    priority=if_conf.get("priority", 1),
                    passive=if_conf.get("passive", False),
                    mtu=st.mtu,
                    mtu_ignore=if_conf.get("mtu-ignore", False),
                    transmit_delay=if_conf.get("transmit-delay", 1),
                    bfd_enabled=if_conf.get("bfd", False),
                    auth=self._ospf_auth(if_conf.get("authentication")),
                )
                inst.add_interface(ifname, cfg, addr, host, stub=stub,
                                   stub_default_cost=stub_cost, nssa=nssa)
                self.loop.send(inst.name, IfUpMsg(ifname))
            # area-type reconfig on an existing area (no new interfaces):
            aid = IPv4Address(area_id)
            if aid in inst.areas and (
                inst.areas[aid].stub != stub or inst.areas[aid].nssa != nssa
            ):
                inst.set_area_type(aid, stub=stub, nssa=nssa)
        # Auth is change-driven on running circuits too: an inline key
        # change must re-key immediately, not only on keychain events
        # (_last_tree is set before the apply chain runs).
        self._refresh_ospf_auth()
        if redist_changed:
            self._reconcile_redistribution(inst)

    @staticmethod
    def _frr_config(frr_conf):
        """ietf fast-reroute container -> FrrConfig (None = disabled).

        Shape (shared by OSPFv2/v3 and IS-IS):
          fast-reroute: {lfa: true, remote-lfa: bool, ti-lfa: bool,
                         engine: scalar|tpu}
        """
        if not frr_conf:
            return None
        from holo_tpu.frr.manager import FrrConfig

        return FrrConfig(
            enabled=bool(frr_conf.get("lfa", True)),
            remote_lfa=bool(frr_conf.get("remote-lfa", False)),
            ti_lfa=bool(frr_conf.get("ti-lfa", False)),
            engine=frr_conf.get("engine", "scalar"),
        )

    def _reconcile_redistribution(self, inst) -> None:
        """Replay the RIB against a changed redistribute set: inject
        now-wanted active routes, withdraw no-longer-wanted type-5s."""
        from holo_tpu.utils.southbound import Protocol

        wanted = self._ospf_redistribute
        active = self.rib.active_routes()
        backed: set = set()
        for prefix, routemsg in active.items():
            if prefix.version != 4:
                continue
            if (
                routemsg.protocol.value in wanted
                and routemsg.protocol != Protocol.OSPFV2
            ):
                backed.add(prefix)
                inst.redistribute(prefix, metric=max(routemsg.metric, 1))
        for prefix in list(inst.redistributed.keys()):
            if prefix not in backed:
                inst.withdraw_redistributed(prefix)

    def _ospf_auth(self, auth_conf):
        """Build an AuthCtx from interface auth config, resolving keychain
        references through the keychain provider (holo-keychain analog).

        FAIL-CLOSED: an unresolvable keychain reference yields a deny-all
        context (random key nobody shares) — never an unauthenticated
        interface.  The reference likewise drops packets when the key
        cannot be resolved.
        """
        import os as _os

        from holo_tpu.protocols.ospf.packet import AuthCtx, AuthType

        if not auth_conf:
            return None
        kc_name = auth_conf.get("key-chain")
        if kc_name:
            # Lifetime-based selection (keychain.rs:42-92): the active
            # SEND key signs, received key ids validate against their
            # ACCEPT lifetimes — rollover works.
            resolved = self._resolve_keychain(kc_name)
            if resolved is not None:
                return AuthCtx(
                    AuthType.CRYPTOGRAPHIC,
                    keychain=resolved,
                    clock=lambda: self.loop.clock.now(),
                )
            return AuthCtx(AuthType.CRYPTOGRAPHIC, _os.urandom(16), key_id=0)
        atype = auth_conf.get("type", "none")
        key = (auth_conf.get("key") or "").encode()
        if atype == "simple":
            return AuthCtx(AuthType.SIMPLE, key)
        if atype == "md5":
            return AuthCtx(AuthType.CRYPTOGRAPHIC, key, key_id=1)
        return None

    def _apply_ospfv3(self, new):
        from holo_tpu.protocols.ospf.instance_v3 import (
            OspfV3Instance,
            V3IfConfig,
            V3IfUpMsg,
        )
        from holo_tpu.utils.southbound import Protocol

        base = "routing/control-plane-protocols/ospfv3"
        conf = new.get(base)
        enabled = bool(conf) and new.get(f"{base}/enabled", True)
        inst = self.instances.get("ospfv3")
        if not enabled:
            if inst is not None:
                self._drop_instance_routes(Protocol.OSPFV3, inst.routes)
                self._unplace_instance(inst.name)
                del self.instances["ospfv3"]
            return
        router_id = new.get(f"{base}/router-id")
        if router_id is None:
            return
        if inst is not None and inst.router_id != IPv4Address(router_id):
            # Router-id change: restart the instance (new LSA identity).
            self._drop_instance_routes(Protocol.OSPFV3, inst.routes)
            self._unplace_instance(inst.name)
            del self.instances["ospfv3"]
            inst = None
        if inst is None:
            actor = f"{self.prefix}ospfv3"
            inst = OspfV3Instance(
                name=actor,
                router_id=IPv4Address(router_id),
                netio=self.netio_factory(actor),
                route_delta_cb=self._ospfv3_delta_to_rib,
                notif_cb=self.yang_notify,
            )
            inst = self._place_instance(inst)
            self.instances["ospfv3"] = inst
        # IP fast reroute + RFC 6987 stub-router (same leaves as v2).  An
        # FRR change forces a full SPF so backup tables and published
        # routes follow the new policy immediately.
        new_frr = self._frr_config(new.get(f"{base}/fast-reroute"))
        if new_frr != inst.frr:
            inst.frr = new_frr
            inst._schedule_spf()
        inst.set_stub_router(bool(new.get(f"{base}/stub-router", False)))
        areas = new.get(f"{base}/area", {}) or {}
        for area_id, area_conf in areas.items():
            for ifname, if_conf in (area_conf.get("interface") or {}).items():
                if ifname in inst.interfaces:
                    # Live reconfiguration (reference InterfaceUpdate
                    # family analog); auth refreshes below.
                    st = self.ifp.interfaces.get(ifname)
                    inst.iface_cost_update(ifname, if_conf.get("cost", 10))
                    inst.iface_update(
                        ifname,
                        hello=if_conf.get("hello-interval", 10),
                        dead=if_conf.get("dead-interval", 40),
                        priority=if_conf.get("priority", 1),
                        passive=if_conf.get("passive", False),
                        mtu=st.mtu if st is not None else None,
                        mtu_ignore=if_conf.get("mtu-ignore", False),
                        transmit_delay=if_conf.get("transmit-delay", 1),
                    )
                    continue
                st = self.ifp.interfaces.get(ifname)
                if st is None:
                    continue
                v6 = [a for a in st.addresses if a.version == 6]
                if not v6:
                    continue
                link_local = next(
                    (a.ip for a in v6 if a.ip.is_link_local), v6[0].ip
                )
                prefixes = [a.network for a in v6 if not a.ip.is_link_local]
                inst.add_interface(
                    ifname,
                    V3IfConfig(
                        area_id=IPv4Address(area_id),
                        cost=if_conf.get("cost", 10),
                        hello_interval=if_conf.get("hello-interval", 10),
                        dead_interval=if_conf.get("dead-interval", 40),
                        priority=if_conf.get("priority", 1),
                        passive=if_conf.get("passive", False),
                        mtu=st.mtu,
                        mtu_ignore=if_conf.get("mtu-ignore", False),
                        transmit_delay=if_conf.get("transmit-delay", 1),
                        auth=self._ospfv3_auth(
                            if_conf.get("authentication")
                        ),
                    ),
                    link_local,
                    prefixes,
                )
                self.loop.send(inst.name, V3IfUpMsg(ifname))
        # Auth is change-driven on running circuits too.
        self._refresh_ospfv3_auth(new)

    def _ospfv3_auth(self, auth_conf):
        """RFC 7166 authentication-trailer context from interface config
        (reference configuration.rs ospfv3_key_chain + sa paths): a
        key-chain resolves by lifetime with the SA id as the key id; an
        inline key uses sa-id + crypto-algorithm.  Unknown chain names
        FAIL CLOSED with a random key nobody shares."""
        import os as _os

        from holo_tpu.protocols.ospf.packet_v3 import AuthCtxV3

        if not auth_conf:
            return None
        kc_name = auth_conf.get("key-chain")
        if kc_name:
            resolved = self._resolve_keychain(kc_name)
            if resolved is not None:
                return AuthCtxV3(
                    key=b"",
                    keychain=resolved,
                    clock=lambda: self.loop.clock.now(),
                )
            return AuthCtxV3(key=_os.urandom(16))
        key = auth_conf.get("key")
        if not key:
            return None
        return AuthCtxV3(
            key=key.encode(),
            sa_id=auth_conf.get("sa-id", 1) & 0xFFFF,
            algo=auth_conf.get("crypto-algorithm", "sha256"),
        )

    def _refresh_ospfv3_auth(self, tree=None) -> None:
        """(Re)apply v3 circuit auth — change-driven per commit AND on
        keychain store updates (the _refresh_ospf_auth analog)."""
        tree = tree if tree is not None else getattr(self, "_last_tree", None)
        inst = self.instances.get("ospfv3")
        if tree is None or inst is None:
            return
        areas = tree.get(
            "routing/control-plane-protocols/ospfv3/area", {}
        ) or {}
        for area_conf in areas.values():
            for ifname, if_conf in (
                area_conf.get("interface") or {}
            ).items():
                iface = inst.interfaces.get(ifname)
                if iface is not None:
                    iface.config.auth = self._ospfv3_auth(
                        if_conf.get("authentication")
                    )

    def _sink_routes(self, protocol, items: dict) -> None:
        """Shared delta route sink (``routing/sink.py`` RouteSink over
        this provider's RIB): items = {prefix: (metric, {(if, addr)})}
        or, with IP-FRR repairs, (metric, nhs, {primary -> (backup,
        labels)})."""
        self._route_sink.push(protocol, items)

    def _drop_instance_routes(self, protocol, inst_routes) -> None:
        self._route_sink.drop(protocol, inst_routes)

    def _ospfv3_delta_to_rib(self, changed, removed):
        """OSPFv3 hands over only what an SPF run changed of its table
        (``OspfV3Instance.route_delta_cb``)."""
        from holo_tpu.routing.sink import v6_route_item
        from holo_tpu.utils.southbound import Protocol

        self._route_sink.push_delta(
            Protocol.OSPFV3,
            {p: v6_route_item(r) for p, r in changed.items()},
            removed,
        )

    def _apply_isis(self, new):
        from holo_tpu.protocols.isis.instance import (
            IsisIfConfig,
            IsisIfUpMsg,
            IsisInstance,
        )
        from holo_tpu.utils.southbound import Protocol, RouteKeyMsg

        base = "routing/control-plane-protocols/isis"
        conf = new.get(base)
        enabled = bool(conf) and new.get(f"{base}/enabled", True)
        inst = self.instances.get("isis")
        if not enabled:
            if inst is not None:
                self._drop_instance_routes(Protocol.ISIS, inst.routes)
                self._unplace_instance(inst.name)
                del self.instances["isis"]
            return
        system_id = new.get(f"{base}/system-id")
        if system_id is None:
            return
        sysid = _parse_system_id(system_id)
        if sysid is None:
            return  # rejected in validate(); defensive here
        level_cfg = new.get(f"{base}/level", "level-all")
        if inst is not None and (
            inst.sysid != sysid
            or getattr(inst, "level_name", None) != level_cfg
        ):
            # System-id or level change requires a new incarnation:
            # withdraw and restart (mirrors disable+enable).
            from holo_tpu.utils.southbound import Protocol

            self._drop_instance_routes(Protocol.ISIS, inst.routes)
            self._unplace_instance(inst.name)
            del self.instances["isis"]
            inst = None
        if inst is None:
            actor = f"{self.prefix}isis"
            if level_cfg == "level-all":
                from holo_tpu.protocols.isis.multi import (
                    IsisLevelAllInstance,
                )

                raw = IsisLevelAllInstance(
                    actor, sysid, b"\x49\x00\x01",
                    netio=self.netio_factory(actor),
                    notif_cb=self.yang_notify,
                )
            else:
                raw = IsisInstance(
                    name=actor,
                    sysid=sysid,
                    level=1 if level_cfg == "level-1" else 2,
                    netio=self.netio_factory(actor),
                    notif_cb=self.yang_notify,
                )
                if level_cfg == "level-1":
                    raw.is_type = 0x01
                # level-2 keeps the default 0x03: ISO 10589 §9.9 requires
                # the L1-IS bit set even on L2-only systems
                # (reference lsdb.rs:202-207).
            raw.level_name = level_cfg
            # The RIB feed carries the installable view (route.rs:285-301:
            # connected prefixes stay out — the kernel owns them as
            # DIRECT).  last_installable is a snapshot the instance
            # thread published as ONE assignment after the SPF settled,
            # so this marshalled closure never sees a torn
            # routes/connected combination.
            raw.route_cb = lambda _r: self._isis_routes_to_rib(
                raw.last_installable
            )
            inst = self._place_instance(raw)
            self.instances["isis"] = inst
        # IP fast reroute (default-topology LFA; same container shape as
        # the OSPF instances).  A change schedules a topology SPF so the
        # backup tables and published routes follow the new policy.
        new_frr = self._frr_config(new.get(f"{base}/fast-reroute"))
        if new_frr != inst.frr:
            inst.frr = new_frr
            inst._schedule_spf()
        # Configured interface order for operational-state rendering: a
        # down interface leaves inst.interfaces but must still render.
        self._isis_ifnames = list(new.get(f"{base}/interface") or {})
        for ifname, if_conf in (new.get(f"{base}/interface") or {}).items():
            if ifname in inst.interfaces:
                # Live reconfiguration on the running circuit (reference
                # InterfaceUpdate): metric changes re-originate the LSP;
                # auth refreshes via _apply_isis_auth below.  Through
                # the handle so threaded marshalling holds (the L1/L2
                # node fans the call out to both levels itself).
                inst.iface_metric_update(ifname, if_conf.get("metric", 10))
                continue
            st = self.ifp.interfaces.get(ifname)
            if st is None or not st.addresses:
                continue
            inst.add_interface(
                ifname,
                IsisIfConfig(
                    metric=if_conf.get("metric", 10),
                    auth=self._isis_auth(
                        if_conf.get("hello-authentication")
                    ),
                ),
                st.addresses[0].ip,
                st.addresses[0].network,
            )
            if hasattr(inst, "instances"):
                # L1/L2 node: marshalled method call reaches both levels.
                inst.if_up(ifname)
            else:
                self.loop.send(inst.name, IsisIfUpMsg(ifname))
        # Authentication is change-driven on the RUNNING instance
        # (reference configuration.rs:531-597 reacts to the config
        # change): enabling/changing/removing auth applies immediately,
        # not only at instance creation.
        self._apply_isis_auth(inst, new)

    def _resolve_keychain(self, name):
        """Keychain object from the provider store, or None when the
        reference is unknown/empty (callers FAIL CLOSED).  Shared by the
        OSPF and IS-IS auth builders so keychain-resolution semantics
        cannot drift between protocols."""
        from holo_tpu.utils.keychain import Keychain

        kc = (
            self.keychains.keychains.get(name)
            if self.keychains is not None
            else None
        )
        if kc and kc.get("key"):
            return Keychain.from_config(name, kc)
        return None

    def _isis_auth(self, auth_conf):
        """AuthCtxIsis from IS-IS auth config: a key-chain reference
        resolves keys by lifetime (utils/keychain.py), an inline key is
        fixed (reference packet/auth.rs AuthMethod::{Keychain,ManualKey};
        config surface configuration.rs:531-597).  Unknown key-chain
        names FAIL CLOSED with a random key nobody shares."""
        import os as _os

        from holo_tpu.protocols.isis.packet import AuthCtxIsis

        if not auth_conf:
            return None
        kc_name = auth_conf.get("key-chain")
        if kc_name:
            resolved = self._resolve_keychain(kc_name)
            if resolved is not None:
                return AuthCtxIsis(
                    key=b"",
                    keychain=resolved,
                    clock=lambda: self.loop.clock.now(),
                )
            return AuthCtxIsis(key=_os.urandom(16))
        key = auth_conf.get("key")
        if not key:
            return None
        return AuthCtxIsis(
            key=key.encode(),
            # The RFC 5310 TLV carries a u16 key id: mask here so two
            # identically-configured peers agree on the wire value.
            key_id=auth_conf.get("key-id", 1) & 0xFFFF,
            algo=auth_conf.get("crypto-algorithm", "hmac-md5"),
        )

    def _apply_isis_auth(self, inst, tree) -> None:
        """(Re)apply instance + hello authentication from the isis
        config subtree — change-driven, every commit AND on keychain
        store updates (the OSPF _refresh_ospf_auth analog)."""
        base = "routing/control-plane-protocols/isis"
        auth = self._isis_auth(tree.get(f"{base}/authentication"))
        subs = (
            list(inst.instances())
            if hasattr(inst, "instances") and callable(inst.instances)
            else [inst]
        )
        for sub in subs:
            sub.auth = auth
        for ifname, if_conf in (tree.get(f"{base}/interface") or {}).items():
            for sub in subs:
                iface = sub.interfaces.get(ifname)
                if iface is not None:
                    iface.config.auth = self._isis_auth(
                        if_conf.get("hello-authentication")
                    )

    def _refresh_isis_auth(self) -> None:
        """Keychain store changed: re-resolve IS-IS auth contexts so the
        instances see the NEW key set (not the snapshot taken at the
        last config commit) — key rollover reaches IS-IS live."""
        tree = getattr(self, "_last_tree", None)
        inst = self.instances.get("isis")
        if tree is None or inst is None:
            return
        self._apply_isis_auth(inst, tree)

    def _isis_routes_to_rib(self, routes):
        from holo_tpu.utils.southbound import Protocol

        inst = self.instances.get("isis")
        frr_backups = getattr(inst, "frr_backups", None) or {}
        self._sink_routes(
            Protocol.ISIS,
            {
                p: (metric, frozenset(nhs), frr_backups.get(p))
                for p, (metric, nhs) in routes.items()
            },
        )

    def _apply_ldp(self, new):
        """LDP lifecycle from config (reference: holo-ldp spawn path).

        Egress FECs are seeded from the connected networks of the
        LDP-enabled interfaces; the LIB is surfaced in operational
        state.  label-distribution-control selects RFC 5036 §2.6
        independent vs ordered mode (a mode change restarts the LSR,
        like the reference's instance reconfiguration)."""
        from ipaddress import IPv4Address

        from holo_tpu.protocols.ldp import LdpInstance

        base = "routing/control-plane-protocols/ldp"
        conf = new.get(base)
        enabled = bool(conf) and new.get(f"{base}/enabled", True)
        lsr_id = new.get(f"{base}/lsr-id")
        inst = self.instances.get("ldp")
        if not enabled or lsr_id is None:
            if inst is not None:
                self._unplace_instance(inst.name)
                del self.instances["ldp"]
                self._uninstall_ldp_labels()
            return
        mode = new.get(
            f"{base}/label-distribution-control", "independent"
        )
        if inst is not None and (
            str(inst.lsr_id) != lsr_id or inst.control_mode != mode
        ):
            self._unplace_instance(inst.name)
            del self.instances["ldp"]
            self._uninstall_ldp_labels()
            inst = None
        if inst is None:
            actor = f"{self.prefix}ldp"
            inst = LdpInstance(
                name=actor,
                lsr_id=IPv4Address(lsr_id),
                netio=self.netio_factory(actor),
                control_mode=mode,
                lib_cb=self._ldp_lib_changed,
                notif_cb=self.yang_notify,
            )
            inst = self._place_instance(inst)
            self.instances["ldp"] = inst
        wanted = set(new.get(f"{base}/interface") or {})
        for ifname in list(inst.interfaces):
            if ifname not in wanted:
                st = self.ifp.interfaces.get(ifname)
                fec = (
                    st.addresses[0].network
                    if st is not None and st.addresses
                    else None
                )
                inst.remove_interface(ifname, fec)
        for ifname in wanted:
            if ifname in inst.interfaces:
                continue
            st = self.ifp.interfaces.get(ifname)
            if st is None or not st.addresses:
                continue
            addr = st.addresses[0]
            inst.add_interface(ifname, addr.ip)
            # Directly-attached networks are egress FECs (implicit null).
            inst.add_fec(addr.network, egress=True)

    def _apply_rip(self, new):
        """RIPv2/RIPng lifecycle from config (reference: holo-rip spawn
        path; both families share the Version-strategy instance)."""
        from holo_tpu.protocols.rip import (
            RipIfConfig,
            RipInstance,
            RipngVersion,
            RipVersion,
        )
        from holo_tpu.utils.southbound import Protocol

        for proto, version, want_v6 in (
            ("ripv2", RipVersion, False),
            ("ripng", RipngVersion, True),
        ):
            base = f"routing/control-plane-protocols/{proto}"
            conf = new.get(base)
            enabled = bool(conf) and new.get(f"{base}/enabled", True)
            inst = self.instances.get(proto)
            sink_proto = Protocol.RIPV2 if proto == "ripv2" else Protocol.RIPNG
            if not enabled:
                if inst is not None:
                    self._sink_routes(sink_proto, {})  # delta-clears RIB
                    self._unplace_instance(inst.name)
                    del self.instances[proto]
                continue
            if inst is None:
                actor = f"{self.prefix}{proto}"
                raw = RipInstance(
                    name=actor,
                    netio=self.netio_factory(actor),
                    update_interval=new.get(f"{base}/update-interval", 30),
                    timeout=new.get(f"{base}/invalid-interval", 180),
                    garbage=max(
                        new.get(f"{base}/flush-interval", 240)
                        - new.get(f"{base}/invalid-interval", 180),
                        1,
                    ),
                    version=version,
                )
                # The RIB feed installs LEARNED routes only — connected
                # prefixes stay with the kernel/DIRECT (same rule as
                # OSPF/IS-IS; the reference never installs them).
                raw.route_cb = lambda routes, rp=sink_proto: (
                    self._sink_routes(
                        rp,
                        {
                            p: (
                                r.metric,
                                frozenset({(r.ifname, r.nexthop)}),
                            )
                            for p, r in routes.items()
                            if r.route_type != "connected"
                            and r.nexthop is not None
                        },
                    )
                )
                inst = self._place_instance(raw)
                self.instances[proto] = inst
            # Timers reconfigure in place (they are read per tick).
            inst.update_interval = new.get(f"{base}/update-interval", 30)
            inst.timeout = new.get(f"{base}/invalid-interval", 180)
            inst.garbage = max(
                new.get(f"{base}/flush-interval", 240) - inst.timeout, 1
            )
            wanted = new.get(f"{base}/interface") or {}
            for ifname, if_conf in wanted.items():
                cost = if_conf.get("cost", 1)
                split = if_conf.get("split-horizon", "poison-reverse")
                akw = (
                    {}
                    if want_v6  # RFC 2080: RIPng has no in-protocol auth
                    else self._rip_auth_kwargs(if_conf.get("authentication"))
                )
                cur = inst.interfaces.get(ifname)
                if cur is not None:
                    # Live reconfiguration (reference configuration.rs
                    # InterfaceCostUpdate): metrics recompute table-wide;
                    # auth changes apply to the running circuit.
                    if cur[0].cost != cost:
                        inst.iface_cost_update(ifname, cost)
                    cur[0].split_horizon = split
                    self._set_rip_auth(cur[0], akw)
                    continue
                st = self.ifp.interfaces.get(ifname)
                if st is None:
                    continue
                addrs = [
                    a for a in st.addresses
                    if (a.ip.version == 6) == want_v6
                ]
                if not addrs:
                    continue
                a = addrs[0]
                inst.add_interface(
                    ifname,
                    RipIfConfig(cost=cost, split_horizon=split, **akw),
                    a.ip,
                    a.network,
                )
            for ifname in list(inst.interfaces):
                if ifname not in wanted:
                    inst.remove_interface(ifname)

    def _rip_auth_kwargs(self, auth_conf) -> dict:
        """RipIfConfig auth fields from interface auth config (reference
        holo-rip configuration.rs:309-339 key + crypto-algorithm; the
        key-chain option adds lifetime-resolved keys).  Unknown chain
        names FAIL CLOSED with a random key nobody shares."""
        import os as _os

        if not auth_conf:
            return {}
        kc_name = auth_conf.get("key-chain")
        if kc_name:
            resolved = self._resolve_keychain(kc_name)
            if resolved is None:
                return {"auth_key": _os.urandom(16)}
            return {
                "auth_keychain": resolved,
                "auth_clock": lambda: self.loop.clock.now(),
            }
        key = auth_conf.get("key")
        if not key:
            return {}
        if auth_conf.get("type", "md5") == "password":
            return {"auth_password": key}
        return {
            # RFC 2082 carries a u8 key id on the wire.
            "auth_key": key.encode(),
            "auth_key_id": auth_conf.get("key-id", 1) & 0xFF,
        }

    def _set_rip_auth(self, cfg, akw: dict) -> None:
        """Apply resolved auth kwargs onto a live RipIfConfig (absent
        keys clear — removing auth config really removes auth)."""
        cfg.auth_password = akw.get("auth_password")
        cfg.auth_key = akw.get("auth_key")
        cfg.auth_key_id = akw.get("auth_key_id", 1)
        cfg.auth_keychain = akw.get("auth_keychain")
        cfg.auth_clock = akw.get("auth_clock")

    def _refresh_rip_auth(self) -> None:
        """Keychain store changed: re-resolve keychain-backed RIP
        circuits (the OSPF/IS-IS refresh analog)."""
        tree = getattr(self, "_last_tree", None)
        inst = self.instances.get("ripv2")
        if tree is None or inst is None:
            return
        base = "routing/control-plane-protocols/ripv2"
        for ifname, if_conf in (tree.get(f"{base}/interface") or {}).items():
            cur = inst.interfaces.get(ifname)
            auth_conf = if_conf.get("authentication")
            if cur is not None and auth_conf and auth_conf.get("key-chain"):
                self._set_rip_auth(
                    cur[0], self._rip_auth_kwargs(auth_conf)
                )

    def _apply_igmp(self, new):
        """IGMP querier lifecycle from config (reference: holo-igmp
        spawn inside holo-routing).  Kernel VIF programming engages when
        the multicast routing socket is available (root)."""
        from holo_tpu.protocols.igmp import IgmpIfConfig, IgmpInstance

        base = "routing/control-plane-protocols/igmp"
        conf = new.get(base)
        wanted = (new.get(f"{base}/interface") or {}) if conf else {}
        inst = self.instances.get("igmp")
        if not wanted:
            if inst is not None:
                # Tear down kernel state first: del_vif per interface,
                # then release the one-per-system MRT socket so a
                # re-enable can MRT_INIT again.
                for ifname in list(inst.interfaces):
                    inst.remove_interface(ifname)
                if inst.mroute is not None:
                    inst.mroute.close()
                self._unplace_instance(inst.name)
                del self.instances["igmp"]
            return
        if inst is None:
            actor = f"{self.prefix}igmp"
            mroute = None
            import os

            if os.geteuid() == 0:
                try:
                    from holo_tpu.routing.mroute import MulticastRouting

                    mroute = MulticastRouting()
                except OSError:
                    mroute = None  # no kernel mcast socket: queried-only
            inst = self._place_instance(
                IgmpInstance(
                    name=actor,
                    netio=self.netio_factory(actor),
                    mroute=mroute,
                )
            )
            self.instances["igmp"] = inst
        for ifname, if_conf in wanted.items():
            if ifname in inst.interfaces:
                continue
            st = self.ifp.interfaces.get(ifname)
            if st is None or not st.addresses:
                continue
            v4 = [a for a in st.addresses if a.ip.version == 4]
            if not v4:
                continue
            inst.add_interface(
                ifname,
                IgmpIfConfig(
                    version=if_conf.get("version", 2),
                    query_interval=if_conf.get("query-interval", 125),
                ),
                v4[0].ip,
                ifindex=getattr(st, "ifindex", None),
            )
        for ifname in list(inst.interfaces):
            if ifname not in wanted:
                inst.remove_interface(ifname)

    def _apply_vrrp(self, new):
        """VRRP lifecycle: one instance per (interface, vrid).  The master
        owns a macvlan carrying the virtual MAC 00:00:5e:00:01:<vrid> and
        the virtual addresses (reference holo-vrrp/src/instance.rs:301-311
        macvlan programming); backup/init tears it down."""
        from ipaddress import ip_address

        from holo_tpu.protocols.vrrp import VrrpConfig, VrrpInstance

        base = "routing/control-plane-protocols/vrrp"
        wanted = {}
        for vrid_s, entry in (new.get(f"{base}/instance") or {}).items():
            vrid = int(entry.get("vrid", vrid_s))
            ifname = entry.get("interface")
            if ifname is None:
                continue
            st = self.ifp.interfaces.get(ifname)
            if st is None or not st.addresses:
                continue
            wanted[vrid] = (ifname, entry, st.addresses[0].ip)
        have = getattr(self, "vrrp_instances", {})
        self.vrrp_instances = have

        def _stop(vrid):
            inst = have.pop(vrid)
            inst.shutdown()  # on_state(INITIALIZE) removes the macvlan
            self._unplace_instance(inst.name)

        for vrid in list(have.keys() - wanted.keys()):
            _stop(vrid)
        for vrid, (ifname, entry, addr) in wanted.items():
            cfg = VrrpConfig(
                vrid=vrid,
                ifname=ifname,
                version=int(entry.get("version", 3)),
                priority=entry.get("priority", 100),
                advert_interval=entry.get("advertise-interval", 1),
                addresses=[
                    ip_address(a) for a in entry.get("virtual-address", [])
                ],
            )
            if vrid in have:
                if have[vrid].config == cfg:
                    continue
                # Config changed: restart with the new parameters (the
                # reference reconfigures the per-interface instance).
                _stop(vrid)
            actor = f"{self.prefix}vrrp-{ifname}-{vrid}"
            inst = VrrpInstance(
                name=actor,
                config=cfg,
                iface_addr=addr,
                netio=self.netio_factory(actor),
                notif_cb=self.yang_notify,
            )
            inst.vrrp_ifname = ifname
            inst.on_state = (
                lambda state, i=inst: self._vrrp_state_changed(i, state)
            )
            inst = self._place_instance(inst)
            have[vrid] = inst
            inst.startup()

    def _vrrp_macvlan(self, inst) -> str:
        # Kernel IFNAMSIZ is 16 incl. NUL; keep the vrid even when the
        # parent name gets truncated.
        return f"vrrp{inst.config.vrid}.{inst.vrrp_ifname}"[:15]

    def _vrrp_state_changed(self, inst, state) -> None:
        from ipaddress import ip_interface

        from holo_tpu.protocols.vrrp import VrrpState

        if self.link_mgr is None:
            return
        name = self._vrrp_macvlan(inst)
        if state == VrrpState.MASTER:
            # RFC 5798 §7.3 virtual MAC.
            mac = bytes((0x00, 0x00, 0x5E, 0x00, 0x01, inst.config.vrid))
            self.link_mgr.create_macvlan(inst.vrrp_ifname, name, mac)
            for addr in inst.config.addresses:
                self.link_mgr.add_address(
                    name, ip_interface(f"{addr}/{addr.max_prefixlen}")
                )
            self.link_mgr.set_link(name, up=True)
        else:
            self.link_mgr.delete_link(name)

    def _apply_bgp(self, new):
        """BGP lifecycle from config (reference: holo-bgp spawn path).

        Policies referenced by neighbors resolve through the policy
        provider's engine (set at wiring time via ``policy_engine``).
        """
        from ipaddress import ip_address

        from holo_tpu.protocols.bgp import BgpInstance, PeerConfig
        from holo_tpu.utils.southbound import Protocol

        base = "routing/control-plane-protocols/bgp"
        conf = new.get(base)
        inst = self.instances.get("bgp")
        asn = new.get(f"{base}/as")
        router_id = new.get(f"{base}/router-id")
        if not conf or asn is None or router_id is None:
            # Subtree (or its identity leaves) gone: tear down fully.
            if inst is not None:
                self._drop_instance_routes(Protocol.BGP, list(inst.loc_rib))
                self._unplace_instance(inst.name)
                del self.instances["bgp"]
                self._close_bgp_tcp()
            return
        wanted_transport = (
            new.get(f"{base}/transport", "fabric"),
            new.get(f"{base}/port", 179),
        )
        if inst is not None and (
            inst.asn != asn
            or inst.router_id != IPv4Address(router_id)
            or wanted_transport != getattr(self, "_bgp_transport", wanted_transport)
        ):
            # Speaker identity or transport change: restart (new OPENs,
            # fresh RIBs, fresh sockets).
            self._drop_instance_routes(Protocol.BGP, list(inst.loc_rib))
            self._unplace_instance(inst.name)
            del self.instances["bgp"]
            self._close_bgp_tcp()
            inst = None
        self._bgp_transport = wanted_transport
        tcp_io = getattr(self, "bgp_tcp_io", None)
        if inst is None:
            actor = f"{self.prefix}bgp"
            # Transport: real TCP sessions (production; RFC 4271 §8 over
            # holo-bgp/src/network.rs semantics) or the in-memory fabric
            # (deterministic tests).
            if new.get(f"{base}/transport") == "tcp":
                from holo_tpu.utils.tcpio import BgpTcpIo

                tcp_io = BgpTcpIo(
                    self.loop, actor, port=new.get(f"{base}/port", 179)
                )
                self.bgp_tcp_io = tcp_io
                netio = tcp_io
            else:
                netio = self.netio_factory(actor)
            inst = BgpInstance(
                name=actor,
                asn=asn,
                router_id=IPv4Address(router_id),
                netio=netio,
                route_cb=self._bgp_route_cb,
                notif_cb=self.yang_notify,
            )
            inst = self._place_instance(inst)
            self.instances["bgp"] = inst
        engine = self.policy_engine
        wanted_peers = set()
        for addr_s, n in (new.get(f"{base}/neighbor") or {}).items():
            addr = ip_address(n.get("address", addr_s))
            wanted_peers.add(addr)
            if addr in inst.peers:
                if tcp_io is not None:
                    # MD5 key rotation on a live neighbor re-keys the
                    # listeners and resets the session.
                    tcp_io.update_md5(
                        addr,
                        n["authentication-key"].encode()
                        if n.get("authentication-key")
                        else None,
                    )
                    tcp_io.update_mss(addr, n.get("tcp-mss") or None)
                continue
            # Outgoing interface: longest-prefix interface subnet
            # containing the peer (single-hop eBGP/iBGP assumption).
            ifname = None
            local = None
            best_len = -1
            for st in self.ifp.interfaces.values():
                for a in st.addresses:
                    if (
                        a.version == addr.version
                        and addr in a.network
                        and a.network.prefixlen > best_len
                    ):
                        ifname, local = st.name, a.ip
                        best_len = a.network.prefixlen
            if ifname is None:
                continue
            imp = exp = None
            if engine is not None:
                # Scope the hooks to this peer so match-neighbor-set
                # conditions see the route's source address.
                if n.get("import-policy"):
                    imp = engine.bgp_import_hook(
                        n["import-policy"], neighbor=addr
                    )
                if n.get("export-policy"):
                    exp = engine.bgp_import_hook(
                        n["export-policy"], neighbor=addr
                    )
            inst.add_peer(
                PeerConfig(
                    addr,
                    n.get("peer-as", asn),
                    ifname,
                    hold_time=n.get("hold-time", 90),
                    connect_retry=n.get("connect-retry-interval", 30),
                    import_policy=imp,
                    export_policy=exp,
                ),
                local,
            )
            if tcp_io is not None:
                try:
                    tcp_io.listen(local)  # idempotent per address
                except OSError as e:
                    log.error(
                        "BGP listen on %s:%s failed: %s (passive peers "
                        "cannot connect in)",
                        local, wanted_transport[1], e,
                    )
                tcp_io.add_peer(
                    local, addr, ifname=ifname,
                    md5_key=(
                        n["authentication-key"].encode()
                        if n.get("authentication-key")
                        else None
                    ),
                    # 0 means "not configured" (the uint8 leaf default).
                    ttl_security=n.get("ttl-security") or None,
                    tcp_mss=n.get("tcp-mss") or None,
                )
            inst.start_peer(addr)
        # Neighbors removed from config: drop the session + their routes.
        for addr in list(inst.peers.keys() - wanted_peers):
            inst.remove_peer(addr)
            if tcp_io is not None:
                tcp_io.remove_peer(addr)
        # network statements: locally originated routes (v4 or v6).
        from ipaddress import ip_network

        wanted_nets = set()
        for p_s, nconf in (new.get(f"{base}/network") or {}).items():
            prefix = ip_network(nconf.get("prefix", p_s), strict=False)
            wanted_nets.add(prefix)
            if prefix not in inst.originated:
                inst.originate(prefix)
        for prefix in list(inst.originated.keys() - wanted_nets):
            del inst.originated[prefix]
            inst._decision(prefix)

    def _rib_changed(self) -> None:
        """RIB delta: keep the LDP FEC table in lockstep (routed prefixes
        become transit FECs with real labels; reference seeds FECs from
        the RIB the same way) and refresh LFIB entries whose next hops
        may have moved."""
        ldp = self.instances.get("ldp")
        if ldp is None:
            return
        active = {
            prefix: msg
            for prefix, msg in self.rib.active_routes().items()
            if prefix.version == 4
        }
        from holo_tpu.utils.southbound import Protocol

        for prefix, msg in active.items():
            if msg.protocol == Protocol.DIRECT:
                continue  # connected nets are egress FECs (iface seeding)
            if prefix not in ldp.fec_table:
                ldp.add_fec(prefix, egress=False)
        for prefix, (label, egress) in list(ldp.fec_table.items()):
            if not egress and prefix not in active:
                ldp.remove_fec(prefix)
        # Ordered mode eligibility (§2.6.1): each FEC's downstream LSR is
        # the neighbor owning the route's next hop.
        nexthop_lsr = {}
        for prefix, msg in active.items():
            for nh in msg.nexthops:
                for lsr, nbr in ldp.neighbors.items():
                    if nbr.addr == nh.addr:
                        nexthop_lsr[prefix] = lsr
                        break
        ldp.set_nexthops(nexthop_lsr)
        self._ldp_lib_changed(ldp.lib())

    def _uninstall_ldp_labels(self) -> None:
        from holo_tpu.utils.southbound import LabelUninstallMsg, Protocol

        for label, msg in list(self.rib.mpls.items()):
            if msg.protocol == Protocol.LDP:
                self.rib.label_del(
                    LabelUninstallMsg(protocol=Protocol.LDP, label=label)
                )

    def _ldp_lib_changed(self, lib: dict) -> None:
        """Merge the LDP LIB with RIB next hops into LFIB entries
        (reference holo-routing/src/rib.rs:152-212): for every FEC with a
        real local label, the in-label swaps to the downstream peer's
        binding (implicit-null => penultimate-hop pop) along the FEC's
        routed next hops; egress FECs keep implicit-null and install
        nothing."""
        from holo_tpu.utils.mpls import IMPLICIT_NULL
        from holo_tpu.utils.southbound import (
            LabelInstallMsg,
            LabelUninstallMsg,
            Nexthop,
            Protocol,
        )

        ldp = self.instances.get("ldp")
        wanted: dict[int, LabelInstallMsg] = {}
        for fec, entry in lib.items():
            local = entry["local"]
            if entry.get("egress") or local == IMPLICIT_NULL:
                continue
            pr = self.rib.routes.get(fec)
            best = None
            if pr is not None:
                for e in pr.entries.values():
                    if e.active:
                        best = e.msg
                        break
            if best is None:
                continue
            # Downstream peer = the neighbor owning the route's next hop.
            remote = entry.get("remote", {})
            nhs = set()
            for nh in best.nexthops:
                out_label = None
                for lsr, label in remote.items():
                    nbr = ldp.neighbors.get(IPv4Address(lsr)) if ldp else None
                    if nbr is not None and nbr.addr == nh.addr:
                        out_label = label
                        break
                if out_label is None:
                    continue
                labels = () if out_label == IMPLICIT_NULL else (out_label,)
                nhs.add(
                    Nexthop(
                        addr=nh.addr,
                        ifname=nh.ifname,
                        ifindex=nh.ifindex,
                        labels=labels,
                    )
                )
            if nhs:
                wanted[local] = LabelInstallMsg(
                    protocol=Protocol.LDP,
                    label=local,
                    nexthops=frozenset(nhs),
                    route=(fec,),
                )
        current = {
            label
            for label, msg in self.rib.mpls.items()
            if msg.protocol == Protocol.LDP
        }
        for label, msg in wanted.items():
            self.rib.label_add(msg)
        for label in current - set(wanted):
            self.rib.label_del(
                LabelUninstallMsg(protocol=Protocol.LDP, label=label)
            )

    def _close_bgp_tcp(self):
        io = getattr(self, "bgp_tcp_io", None)
        if io is not None:
            io.close()
            self.bgp_tcp_io = None

    def _bgp_route_cb(self, prefix, best):
        from holo_tpu.utils.southbound import (
            DEFAULT_DISTANCE,
            Nexthop,
            Protocol,
            RouteKeyMsg,
            RouteMsg,
        )

        if best is None or best.peer is None:
            self.rib.route_del(RouteKeyMsg(Protocol.BGP, prefix))
            return
        from ipaddress import IPv6Network

        nh = (
            best.attrs.nh6
            if isinstance(prefix, IPv6Network)
            else best.attrs.next_hop
        )
        if nh is None:
            # No usable next hop for this family: never install a
            # blackhole; drop any previous entry instead.
            self.rib.route_del(RouteKeyMsg(Protocol.BGP, prefix))
            return
        self.rib.route_add(
            RouteMsg(
                protocol=Protocol.BGP,
                prefix=prefix,
                distance=DEFAULT_DISTANCE[Protocol.BGP],
                metric=best.attrs.med or 0,
                nexthops=frozenset({Nexthop(addr=nh)}),
            )
        )

    def _apply_static(self, new):
        from holo_tpu.utils.southbound import (
            Nexthop,
            Protocol,
            RouteKeyMsg,
            RouteMsg,
        )

        routes = new.get(
            "routing/control-plane-protocols/static-routes/route", {}
        ) or {}
        # Withdraw statics removed from config.
        new_prefixes = {r.get("prefix") for r in routes.values()}
        for prefix in getattr(self, "_static_prefixes", set()) - new_prefixes:
            self.rib.route_del(RouteKeyMsg(Protocol.STATIC, prefix))
        self._static_prefixes = {p for p in new_prefixes if p is not None}
        for _key, r in routes.items():
            prefix = r.get("prefix")
            if prefix is None:
                continue
            nhs = set()
            if r.get("next-hop") is not None:
                nhs.add(Nexthop(addr=r["next-hop"], ifname=r.get("interface")))
            elif r.get("interface"):
                nhs.add(Nexthop(ifname=r["interface"]))
            self.rib.route_add(
                RouteMsg(
                    protocol=Protocol.STATIC,
                    prefix=prefix,
                    distance=1,
                    metric=r.get("metric", 0),
                    nexthops=frozenset(nhs),
                )
            )

    def get_state(self, path=None):
        rib = {
            str(prefix): {
                "protocol": msg.protocol.value,
                "distance": msg.distance,
                "metric": msg.metric,
                "next-hops": sorted(
                    f"{nh.ifname or ''}:{nh.addr or ''}" for nh in msg.nexthops
                ),
            }
            for prefix, msg in self.rib.active_routes().items()
        }
        state = {"routing": {"rib": rib}}
        ospf = self.instances.get("ospfv2")
        if ospf is not None:
            now = self.loop.clock.now() if self.loop else 0.0

            def _lsdb_state(a):
                out = []
                for e in a.lsdb.all():
                    lsa = e.lsa
                    out.append(
                        {
                            "type": int(lsa.type),
                            "lsa-id": str(lsa.lsid),
                            "adv-router": str(lsa.adv_rtr),
                            "seq-num": lsa.seq_no & 0xFFFFFFFF,
                            "age": int(e.current_age(now)),
                            "length": lsa.length,
                        }
                    )
                return out

            state["routing"]["ospfv2"] = {
                "router-id": str(ospf.config.router_id),
                "spf-run-count": ospf.spf_run_count,
                "spf-log": list(ospf.spf_log),
                "is-abr": ospf.is_abr,
                "areas": {
                    str(aid): {
                        "area-type": (
                            "nssa" if a.nssa
                            else "stub" if a.stub
                            else "normal"
                        ),
                        "lsdb-count": len(a.lsdb.entries),
                        "database": _lsdb_state(a),
                        "interfaces": {
                            i.name: {
                                "state": i.state.name.lower(),
                                "type": i.config.if_type.name.lower(),
                                "cost": i.config.cost,
                                "hello-interval": (
                                    i.config.hello_interval
                                ),
                                "dead-interval": i.config.dead_interval,
                                "passive": i.config.passive,
                                "dr": str(i.dr),
                                "bdr": str(i.bdr),
                                "neighbor-count": len(i.neighbors),
                            }
                            for i in a.interfaces.values()
                        },
                    }
                    for aid, a in ospf.areas.items()
                },
                "neighbors": {
                    str(n.router_id): {
                        "state": n.state.name.lower(),
                        "iface": i.name,
                        "address": str(n.src),
                        "dr": str(n.dr),
                        "bdr": str(n.bdr),
                        "priority": n.priority,
                    }
                    for a in ospf.areas.values()
                    for i in a.interfaces.values()
                    for n in i.neighbors.values()
                },
                "local-rib": {
                    str(prefix): {
                        "metric": r.dist,
                        "route-type": getattr(r, "route_type", ""),
                        "next-hops": sorted(
                            f"{nh.ifname or ''}:{nh.addr or ''}"
                            for nh in r.nexthops
                        ),
                    }
                    for prefix, r in ospf.routes.items()
                },
                "sr-labels": {
                    str(prefix): label
                    for prefix, (label, _r) in getattr(
                        ospf, "sr_labels", {}
                    ).items()
                },
            }
            # YANG-modeled ietf-ospf tree (same renderer the conformance
            # harness diffs against the reference's recorded plane).
            try:
                from holo_tpu.protocols.ospf.nb_state import instance_state

                state["routing"]["ietf-ospf:ospf"] = instance_state(ospf)
            except Exception:  # noqa: BLE001 — ad-hoc state must survive
                log.exception("ietf-ospf state render failed")
        v3 = self.instances.get("ospfv3")
        if v3 is not None:
            # YANG-modeled ietf-ospf (v3) tree — the renderer the v3
            # conformance harness diffs 44/44 recorded routers against.
            try:
                from holo_tpu.protocols.ospf.nb_state_v3 import (
                    instance_state as v3_state,
                )

                state["routing"]["ietf-ospf:ospfv3"] = v3_state(v3)
            except Exception:  # noqa: BLE001 — ad-hoc state must survive
                log.exception("ietf-ospf v3 state render failed")
            # SPF run log ring (full/intra/inter/external types), like
            # the v2 and IS-IS blocks; list() snapshots vs the instance
            # thread's append/trim under threaded isolation.
            state["routing"]["ospfv3"] = {
                "spf-run-count": v3.spf_run_count,
                "spf-log": list(getattr(v3, "spf_log", [])),
            }
        isis = self.instances.get("isis")
        if isis is not None:
            # The YANG-modeled ietf-isis operational tree — the same
            # renderer the conformance harness diffs against the
            # reference's recorded state plane — served at the standard
            # module-qualified name alongside the ad-hoc summary below.
            # (ietf-ospf:ospf v2 is rendered in the ospf block above,
            # v3 in the ospfv3 block below.)
            try:
                from holo_tpu.protocols.isis.nb_state import (
                    instance_state as isis_state,
                )

                if hasattr(isis, "instances"):  # L1/L2 node
                    state["routing"]["ietf-isis:isis"] = isis_state(
                        list(isis.instances()),
                        node=isis._inst if hasattr(isis, "_inst") else isis,
                        ifnames=getattr(self, "_isis_ifnames", None),
                    )
                else:
                    state["routing"]["ietf-isis:isis"] = isis_state(
                        [isis],
                        ifnames=getattr(self, "_isis_ifnames", None),
                    )
            except Exception:  # noqa: BLE001 — ad-hoc state must survive
                log.exception("ietf-isis state render failed")
            isis_subs = (
                list(isis.instances())
                if hasattr(isis, "instances") and callable(isis.instances)
                else [isis]
            )
            state["routing"]["isis"] = {
                "spf-run-count": isis.spf_run_count,
                # SPF run log ring (reference state.rs spf_log events):
                # records the Full-vs-RouteOnly classification per run.
                "spf-log": [
                    {"level": sub.level} | dict(e)
                    for sub in isis_subs
                    # list() snapshot: the instance thread appends/trims
                    # the ring while this management-side render runs.
                    for e in list(getattr(sub, "spf_log", []))
                ],
                "lsdb-count": len(isis.lsdb),
                "database": [
                    {
                        "lsp-id": e.lsp.lsp_id.encode().hex(),
                        "seq-num": e.lsp.seqno,
                        "lifetime": e.remaining_lifetime(
                            self.loop.clock.now() if self.loop else 0.0
                        ),
                    }
                    for e in (
                        isis.lsdb.values()
                        if hasattr(isis.lsdb, "values")
                        else []
                    )
                ],
                "adjacencies": {
                    i.name: [
                        {"sysid": a.sysid.hex(), "state": a.state.value}
                        for a in i.up_adjacencies()
                    ]
                    for i in isis.interfaces.values()
                },
                "hostnames": {
                    k.hex() if hasattr(k, "hex") else str(k): v
                    for k, v in getattr(isis, "hostnames", {}).items()
                },
            }
        for proto in ("ripv2", "ripng"):
            rip = self.instances.get(proto)
            if rip is None:
                continue
            # dict() snapshots are GIL-atomic: under preemptive
            # isolation the instance thread mutates these containers
            # while this (management-side) render iterates.
            routes = dict(rip.routes)
            neighbors = dict(rip.neighbors)
            state["routing"][proto] = {
                "routes": {
                    str(p): {
                        "metric": r.metric,
                        "type": r.route_type,
                        "interface": r.ifname,
                        "next-hop": (
                            str(r.nexthop) if r.nexthop is not None else None
                        ),
                    }
                    for p, r in routes.items()
                },
                "neighbors": {
                    str(a): {"last-update": t}
                    for a, t in neighbors.items()
                },
            }
        igmp = self.instances.get("igmp")
        if igmp is not None:
            out_ifaces = {}
            for i in list(igmp.interfaces.values()):
                groups = dict(i.groups)
                out_ifaces[i.name] = {
                    "querier": i.querier,
                    "groups": {
                        str(g): {
                            "reporters": sorted(
                                str(r) for r in set(grp.reporters)
                            )
                        }
                        for g, grp in groups.items()
                    },
                }
            state["routing"]["igmp"] = {"interfaces": out_ifaces}
        ldp = self.instances.get("ldp")
        if ldp is not None:
            state["routing"]["ldp"] = {
                "lsr-id": str(ldp.lsr_id),
                "control-mode": ldp.control_mode,
                "neighbors": {
                    str(rid): n.state.value
                    for rid, n in ldp.neighbors.items()
                },
                "lib": {
                    str(fec): entry for fec, entry in ldp.lib().items()
                },
            }
        bgp = self.instances.get("bgp")
        if bgp is not None:
            state["routing"]["bgp"] = {
                "as": bgp.asn,
                "peers": {
                    str(a): {"state": p.state.value,
                             "prefixes-in": len(p.adj_rib_in)}
                    for a, p in bgp.peers.items()
                },
                "loc-rib-count": len(bgp.loc_rib),
            }
        return state
