"""The daemon's route sink (``holo_tpu/routing/sink.py``), which the
routing provider and the chip benchmark's OSPFv3 network both call."""

from ipaddress import IPv6Address, IPv6Network

from holo_tpu.routing.sink import RouteSink, v6_route_item
from holo_tpu.utils.southbound import Nexthop, Protocol, RouteKeyMsg

P1, P2, P3 = (IPv6Network(f"2001:db8:{n}::/48") for n in (1, 2, 3))
A, B = IPv6Address("fe80::a"), IPv6Address("fe80::b")


class _Rib:
    def __init__(self):
        self.calls = []

    def route_add(self, msg):
        self.calls.append(("add", msg.prefix, msg.metric, msg.nexthops, dict(msg.backups)))

    def route_del(self, msg):
        assert isinstance(msg, RouteKeyMsg)
        self.calls.append(("del", msg.prefix))


def _nh(ifname, addr):
    return Nexthop(addr=addr, ifname=ifname)


def test_push_installs_what_differs_and_withdraws_what_is_gone():
    rib = _Rib()
    sink = RouteSink(rib)
    sink.push(Protocol.OSPFV3, {P1: (5, frozenset({("e0", A)})), P2: (7, frozenset({("e1", B)}))})
    assert [c[:2] for c in rib.calls] == [("add", P1), ("add", P2)]
    assert rib.calls[0][3] == frozenset({_nh("e0", A)})
    rib.calls.clear()
    sink.push(Protocol.OSPFV3, {P1: (5, frozenset({("e0", A)})), P3: (1, frozenset({("e0", A)}))})
    assert rib.calls == [("del", P2), ("add", P3, 1, frozenset({_nh("e0", A)}), {})]
    rib.calls.clear()
    sink.push(Protocol.OSPFV3, {P1: (6, frozenset({("e0", A)})), P3: (1, frozenset({("e0", A)}))})
    assert [c[:3] for c in rib.calls] == [("add", P1, 6)]


def test_push_delta_touches_only_what_it_is_given():
    rib = _Rib()
    sink = RouteSink(rib)
    sink.push(Protocol.OSPFV3, {P1: (5, frozenset({("e0", A)})), P2: (7, frozenset({("e1", B)}))})
    rib.calls.clear()
    sink.push_delta(Protocol.OSPFV3, {P3: (2, frozenset({("e1", B)}))}, [P2])
    assert [c[:2] for c in rib.calls] == [("del", P2), ("add", P3)]
    rib.calls.clear()
    # the same again, and a withdrawal of what was never pushed: nothing
    sink.push_delta(Protocol.OSPFV3, {P3: (2, frozenset({("e1", B)}))}, [P2])
    assert rib.calls == []
    # a later whole-table push knows what the deltas left
    sink.push(Protocol.OSPFV3, {P1: (5, frozenset({("e0", A)})), P3: (2, frozenset({("e1", B)}))})
    assert rib.calls == []


def test_backups_ride_the_route_and_drop_forgets():
    rib = _Rib()
    sink = RouteSink(rib)
    item = (3, frozenset({("e0", A)}), {("e0", A): (("e1", B), (16001,)), ("e0", None): (("e1", B), ())})
    sink.push(Protocol.ISIS, {P1: item})
    assert rib.calls[0][4] == {
        _nh("e0", A): Nexthop(addr=B, ifname="e1", labels=(16001,))
    }
    rib.calls.clear()
    sink.drop(Protocol.ISIS, [P1])
    assert rib.calls == [("del", P1)]
    rib.calls.clear()
    sink.push(Protocol.ISIS, {P1: item})  # installed again after a drop
    assert [c[:2] for c in rib.calls] == [("add", P1)]


def test_provider_and_benchmark_network_hold_the_same_sink():
    import inspect

    from benchmark import areanet
    from holo_tpu.daemon import providers

    assert "RouteSink(self.rib)" in inspect.getsource(providers)
    assert "RouteSink(self.rib)" in inspect.getsource(areanet)
    assert "def _sink_routes" in inspect.getsource(providers)
    # the provider's own copy of the sink's body is gone
    assert "route_add(" not in inspect.getsource(
        providers.RoutingProvider._sink_routes
    )


def test_v6_route_item_is_what_the_rib_compares():
    from holo_tpu.protocols.ospf.instance_v3 import V6Route

    r = V6Route(P1, 9, frozenset({("e0", A)}))
    assert v6_route_item(r) == (9, frozenset({("e0", A)}), None)
