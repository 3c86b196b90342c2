"""Area address ranges (RFC 2328 §12.4.3, which RFC 5340 keeps) in the
OSPFv3 ABR, the RFC 8405 SPF-delay FSM it shares with OSPFv2, and the
two savings of a multi-area run (an unchanged area is not dispatched;
the sink gets only what changed), each against the run without it."""

import json
from ipaddress import IPv4Address, IPv4Network, IPv6Network
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark import v3ref
from benchmark.areanet import BACKBONE, AreaNet, pod_range
from holo_tpu import telemetry
from holo_tpu.protocols.ospf import packet_v3 as P
from holo_tpu.protocols.ospf.instance import OspfInstance
from holo_tpu.protocols.ospf.instance_v3 import OspfV3Instance
from holo_tpu.protocols.ospf.spf_run import (
    SpfDelayFsm,
    SpfFsmState,
    SpfTimers,
    aggregate_area_ranges,
)
from holo_tpu.spf.backend import ScalarSpfBackend

CONFIG = json.loads(
    (Path(__file__).parents[1] / "benchmark/configs/tiny-v3areas.json")
    .read_text()
)


def _net(**kw) -> AreaNet:
    return AreaNet(
        CONFIG["lsdb"], ScalarSpfBackend(), CONFIG["spf_delay"], 5.0, **kw
    )


def _summaries(net, area: int) -> dict:
    """{prefix: metric} of the device's own live Inter-Area-Prefix LSAs
    in ``area``."""
    db = net.inst.areas[IPv4Address(area)].lsdb
    return {
        e.lsa.body.prefix: e.lsa.body.metric
        for e in db.all()
        if e.lsa.type == P.LsaType.INTER_AREA_PREFIX
        and e.lsa.adv_rtr == net.inst.router_id and not e.lsa.is_maxage
    }


def _settle(net) -> None:
    net.loop.advance(30.0)


# -- ranges


def test_range_goes_out_at_its_largest_component_and_never_into_its_own_area():
    net = _net()
    hall = net.layout.halls[0]
    rng = pod_range(hall, 1)
    intra = net.inst._spf_cache["intra_by_area"][IPv4Address(hall)]
    worst = max(r.dist for p, r in intra.items() if p.subnet_of(rng))
    for area in net.layout.adj:
        got = _summaries(net, area)
        if area == hall:
            assert rng not in got
            continue
        assert got[rng] == worst
        # the components themselves are suppressed
        assert not any(p != rng and p.subnet_of(rng) for p in got)
    # §16.2 (3): what the peers say of our own active ranges is ignored
    assert rng not in net.inst._spf_cache["inter_routes"]
    assert rng not in net.inst.routes
    assert rng in net.inst._active_ranges


def _pod_switches(net, hall: int, pod: int) -> list:
    rng = pod_range(hall, pod)
    return sorted({
        r for r, p, _m in net.layout.prefixes[hall] if p.subnet_of(rng)
    })


def test_component_lost_largest_moved_and_last_component_lost():
    net = _net()
    hall, other = net.layout.halls[0], net.layout.halls[1]
    rng = pod_range(hall, 1)
    before = _summaries(net, other)[rng]
    # the cost of the largest moves: every link of the costliest
    # component's router gets dearer by ten
    intra = net.inst._spf_cache["intra_by_area"][IPv4Address(hall)]
    far = max(
        (r for p, r in intra.items() if p.subnet_of(rng)),
        key=lambda r: r.dist,
    )
    router = next(
        int(k[1]) for k, v in net.inst._spf_cache["area_results"][
            IPv4Address(hall)][0].items() if v == far.vertex
    )
    for peer in net.layout.adj[hall][router]:
        net.layout.adj[hall][peer][router] += 10
    net._lsa_event(hall, list(net.layout.adj[hall][router]), lost=False)
    _settle(net)
    assert _summaries(net, other)[rng] == before + 10
    assert net.fib_table() == v3ref.routes(net.model())
    # a component lost (an edge switch of the pod): the range stays,
    # at the largest of what is left
    switches = _pod_switches(net, hall, 1)
    edge = next(s for s in switches if net.layout.role[s] == "edge")
    net.node(hall, edge, lost=False)
    _settle(net)
    left = net.inst._spf_cache["intra_by_area"][IPv4Address(hall)]
    assert _summaries(net, other)[rng] == max(
        r.dist for p, r in left.items() if p.subnet_of(rng)
    )
    # the last components lost: the range is withdrawn everywhere
    for s in switches:
        if (hall, s) not in net.node_down:
            net.node(hall, s, lost=False)
    _settle(net)
    assert not any(
        p.subnet_of(rng) for p in
        net.inst._spf_cache["intra_by_area"][IPv4Address(hall)]
    )
    # into the backbone only intra-area routes go: the range is gone
    # from there.  It is no longer active either, so what the peers say
    # of it counts again (§16.2 (3) held while it was): an inter-area
    # route, passed on into the halls as any other.
    assert rng not in _summaries(net, BACKBONE)
    assert rng not in net.inst._active_ranges
    via_peers = net.inst.routes[rng]
    assert via_peers.route_type == "inter-area"
    assert _summaries(net, other)[rng] == via_peers.dist
    assert net.fib_table() == v3ref.routes(net.model())
    # and the pod back brings it back
    for s in switches:
        net.node(hall, s, lost=False)
    _settle(net)
    assert rng in _summaries(net, BACKBONE)
    assert rng in net.inst._active_ranges and rng not in net.inst.routes


def test_not_advertised_range_hides_its_components_and_a_cost_is_a_cost():
    net = _net()
    hall, other = net.layout.halls[0], net.layout.halls[1]
    area = net.inst.areas[IPv4Address(hall)]
    hidden, fixed = pod_range(hall, 0), pod_range(hall, 1)
    net.inst.set_area_ranges(IPv4Address(hall), [
        dict(r, advertise=False) if r["prefix"] == hidden
        else dict(r, cost=777) if r["prefix"] == fixed else r
        for r in area.ranges
    ])
    _settle(net)
    got = _summaries(net, other)
    assert hidden not in got and not any(p.subnet_of(hidden) for p in got)
    assert got[fixed] == 777
    assert hidden in net.inst._active_ranges


def _old_v2_body(routes, src_ranges, nh_areas_of):
    """``OspfInstance._originate_summaries``'s range block as it was
    before ISSUE 31 moved it to ``spf_run.aggregate_area_ranges``."""
    eff, range_max, range_nh_areas = {}, {}, {}
    for prefix, route in routes.items():
        matches = [r for r in src_ranges if prefix.subnet_of(r["prefix"])]
        rng = max(matches, key=lambda r: r["prefix"].prefixlen, default=None)
        if rng is None:
            eff[prefix] = route.dist
        elif rng.get("advertise", True):
            cur = range_max.get(rng["prefix"], -1)
            range_max[rng["prefix"]] = max(cur, route.dist)
            acc = range_nh_areas.setdefault(rng["prefix"], set())
            acc.update(nh_areas_of(route))
    for r in src_ranges:
        if r["prefix"] in range_max:
            eff[r["prefix"]] = (
                r["cost"] if r.get("cost") is not None
                else range_max[r["prefix"]]
            )
    return eff, range_nh_areas


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
@pytest.mark.parametrize("family", [4, 6])
def test_shared_range_helper_equals_the_v2_body_it_replaced(seed, family):
    rng = np.random.default_rng(seed)
    net_of = IPv4Network if family == 4 else IPv6Network
    top = 10 << 24 if family == 4 else 0x20010DB8 << 96
    bits = 32 if family == 4 else 128

    def prefix(length):
        host = int(rng.integers(0, 1 << 12)) << (bits - 20)
        return net_of(((top | host) >> (bits - length) << (bits - length), length))

    ranges = [
        {"prefix": prefix(int(rng.integers(10, 19))),
         "advertise": bool(rng.random() < 0.8),
         "cost": int(rng.integers(1, 99)) if rng.random() < 0.3 else None}
        for _ in range(12)
    ]
    ranges.append(dict(ranges[0], cost=5))  # one prefix twice
    routes = {}
    for _ in range(300):
        p = prefix(int(rng.integers(16, 25)))
        routes[p] = SimpleNamespace(
            dist=int(rng.integers(1, 50)),
            nexthops=frozenset({int(rng.integers(0, 4))}),
        )
    routes[ranges[3]["prefix"]] = SimpleNamespace(dist=7, nexthops=frozenset({1}))
    nh = lambda r: r.nexthops  # noqa: E731
    eff, nh_areas, active = aggregate_area_ranges(routes, ranges, nh)
    want_eff, want_nh = _old_v2_body(routes, ranges, nh)
    assert eff == want_eff and nh_areas == want_nh
    assert active == {
        r["prefix"] for r in ranges
        if any(
            p.subnet_of(r["prefix"]) and r is max(
                (q for q in ranges if p.subnet_of(q["prefix"])),
                key=lambda q: q["prefix"].prefixlen,
            )
            for p in routes
        )
    }
    assert aggregate_area_ranges(routes, [], nh) == (
        {p: r.dist for p, r in routes.items()}, {}, set()
    )


# -- the RFC 8405 FSM


def test_both_instances_run_the_one_fsm():
    assert issubclass(OspfInstance, SpfDelayFsm)
    assert issubclass(OspfV3Instance, SpfDelayFsm)
    assert OspfInstance._spf_delay_event is OspfV3Instance._spf_delay_event


def test_v3_walks_the_rfc8405_states():
    net = _net()
    inst, loop = net.inst, net.loop
    loop.advance(30.0)
    assert inst.spf_state == SpfFsmState.QUIET
    runs = inst.spf_run_count
    t0 = loop.clock.now()
    inst._schedule_spf()  # first event: INITIAL_DELAY
    assert inst.spf_state == SpfFsmState.SHORT_WAIT
    loop.advance(0.04)
    assert inst.spf_run_count == runs
    loop.advance(0.02)
    assert inst.spf_run_count == runs + 1
    inst._schedule_spf()  # inside time-to-learn: SHORT_DELAY
    assert inst.spf_state == SpfFsmState.SHORT_WAIT
    loop.advance(0.19)
    assert inst.spf_run_count == runs + 1
    loop.advance(0.02)
    assert inst.spf_run_count == runs + 2
    loop.advance(0.5 - (loop.clock.now() - t0) + 0.01)
    inst._schedule_spf()  # learn time over: LONG_DELAY
    assert inst.spf_state == SpfFsmState.LONG_WAIT
    inst._schedule_spf()  # a second event does not push the run out
    loop.advance(4.9)
    assert inst.spf_run_count == runs + 2
    loop.advance(0.2)
    assert inst.spf_run_count == runs + 3
    assert inst.spf_state == SpfFsmState.LONG_WAIT
    loop.advance(9.0)  # hold-down counts from the last event
    assert inst.spf_state == SpfFsmState.QUIET


def test_unconfigured_v3_waits_the_tenth_of_a_second_it_always_did():
    from holo_tpu.protocols.ospf.instance_v3 import legacy_spf_timers
    from holo_tpu.utils.runtime import EventLoop, VirtualClock

    inst = OspfV3Instance("v3-legacy", IPv4Address("1.1.1.1"), netio=None)
    assert inst.spf_timers == legacy_spf_timers() == SpfTimers(0.1, 0.1, 0.1)
    loop = EventLoop(clock=VirtualClock())
    loop.register(inst)
    ran = []
    inst.run_spf = lambda: ran.append(loop.clock.now())
    for at in (0.0, 0.03, 0.6, 0.65, 0.72, 3.0):
        loop.advance(at - loop.clock.now())
        inst._schedule_spf()
    loop.advance(1.0)
    # one run a tenth of a second after the first event of each burst
    assert [round(t, 2) for t in ran] == [0.1, 0.7, 0.82, 3.1]


# -- an unchanged area keeps its result; the sink gets what changed


def _storm(net, seed: int, events: int = 60) -> None:
    """Every kind of event, seeded, each batch settled."""
    rng = np.random.default_rng(seed)
    lay = net.layout
    remote = sorted({(a, p) for (a, ar, p) in lay.summaries if a in lay.remote_abrs})
    peer = sorted({(a, p) for (a, ar, p) in lay.summaries
                   if a not in lay.remote_abrs and ar == BACKBONE})
    uplinks = [l for l in net.dut_links if l[0] != BACKBONE]
    for n in range(events):
        kind = n % 8
        hall = lay.halls[int(rng.integers(len(lay.halls)))]
        if kind in (0, 1):
            links = net.flappable[hall]
            net.flap(hall, links[int(rng.integers(len(links)))], lost=bool(n % 5 == 0))
        elif kind == 2:
            pool = net.losable[hall][("edge", "agg", "core")[n % 3]]
            target = (hall, pool[int(rng.integers(len(pool)))])
            if len(net.node_down) >= 2 and target not in net.node_down:
                target = net.node_down[0]
            net.node(*target, lost=False)
        elif kind == 3:
            pool = remote if n % 2 else peer
            net.summary(*pool[int(rng.integers(len(pool)))], lost=False)
        elif kind == 4:
            link = net.dut_links[int(rng.integers(len(net.dut_links)))]
            net.bfd(link, "down")
            net.loop.advance(0.3)
            net.bfd(link, "up")
        elif kind == 5:
            link = net.dut_links[int(rng.integers(len(net.dut_links)))]
            net.carrier(link, operative=False)
            net.loop.advance(0.3)
            net.carrier(link, operative=True)
        elif kind == 6:
            net.ifconfig_cost(uplinks[int(rng.integers(len(uplinks)))])
        else:
            link = uplinks[int(rng.integers(len(uplinks)))]
            net.ifconfig_shut(net.shut.get(link[0], link))
        net.loop.advance(float(rng.choice([0.1, 0.4, 2.0, 7.0])))
    net.loop.advance(60.0)


def _own_lsas(net) -> dict:
    return {
        int(aid): sorted(
            (int(e.lsa.type), str(e.lsa.body))
            for e in area.lsdb.all()
            if e.lsa.adv_rtr == net.inst.router_id and not e.lsa.is_maxage
        )
        for aid, area in net.inst.areas.items()
    }


@pytest.mark.parametrize("seed", [3, 4])
def test_instance_equals_the_plain_reference_after_every_kind_of_event(seed):
    net = _net()
    remote = {p for (a, _ar, p) in net.layout.summaries if a in net.layout.remote_abrs}
    assert remote <= set(net.fib_table())  # the inter-area routes
    assert net.fib_table() == v3ref.routes(net.model())
    _storm(net, seed)
    table = net.fib_table()
    assert table == v3ref.routes(net.model()) and len(table) > 40
    assert any(len(hops) > 1 for _cost, hops in table.values())


@pytest.mark.parametrize("seed", [5, 6])
def test_unchanged_areas_reused_equals_every_area_dispatched(seed):
    moved0 = telemetry.snapshot("holo_ospf_area_spf_total")
    on, off = _net(), _net()
    assert on.inst.reuse_unchanged_areas is True  # the default
    off.inst.reuse_unchanged_areas = False
    for net in (on, off):
        _storm(net, seed)
    assert on.fib_table() == off.fib_table() == v3ref.routes(on.model())
    assert {p: (r.dist, r.nexthops, r.route_type) for p, r in on.inst.routes.items()} == {
        p: (r.dist, r.nexthops, r.route_type) for p, r in off.inst.routes.items()
    }
    assert _own_lsas(on) == _own_lsas(off)
    assert on.inst.spf_run_count == off.inst.spf_run_count
    moved = {
        k: v - moved0.get(k, 0)
        for k, v in telemetry.snapshot("holo_ospf_area_spf_total").items()
    }
    # the arm that reuses dispatched fewer areas than it left alone
    assert 0 < moved["holo_ospf_area_spf_total{disposition=reused}"]
    full_runs = (
        moved["holo_ospf_area_spf_total{disposition=reused}"]
        + moved["holo_ospf_area_spf_total{disposition=dispatched}"]
    ) / 10
    assert full_runs == int(full_runs)  # five areas a run, two nets


def test_reuse_sees_an_lsa_reach_maxage_on_the_clock_alone():
    net = _net()
    hall = net.layout.halls[0]
    router = net.losable[hall]["edge"][0]
    db = net.inst.areas[IPv4Address(hall)].lsdb
    key = P.LsaKey(P.LsaType.ROUTER, IPv4Address(0), IPv4Address(router))
    old = db.get(key).lsa
    aged = P.Lsa(P.MAX_AGE - 20, old.type, old.lsid, old.adv_rtr, old.seq_no + 1, old.body)
    aged.encode()
    db.install(aged, net.loop.clock.now())
    net.inst._schedule_spf()
    net.loop.advance(1.0)
    loopback = next(p for r, p, m in net.layout.prefixes[hall] if r == router)
    assert loopback in net.inst.routes
    net.inst._age_tick = lambda: None  # nobody floods the expiry
    net.loop.advance(30.0)
    net.inst._schedule_spf()  # no install since: the clock alone
    net.loop.advance(1.0)
    assert loopback not in net.inst.routes


def test_backend_swap_dispatches_every_area_again():
    net = _net()
    seen = []
    swapped = ScalarSpfBackend()
    inner = swapped.compute
    swapped.compute = lambda topo, *a, **kw: seen.append(topo) or inner(topo, *a, **kw)
    net.inst.backend = swapped
    net.inst._schedule_spf()
    net.loop.advance(1.0)
    assert len(seen) == len(net.inst.areas)
    net.inst._schedule_spf()
    net.loop.advance(1.0)
    assert len(seen) == len(net.inst.areas)  # and then none again


@pytest.mark.parametrize("seed", [7, 8])
def test_sink_fed_deltas_equals_sink_fed_whole_tables(seed):
    h0 = telemetry.snapshot("holo_ospf_rib_delta_routes")
    deltas, whole = _net(deltas=True), _net(deltas=False)
    log = {}
    for name, net in (("deltas", deltas), ("whole", whole)):
        net.kernel.log.clear()
        _storm(net, seed, events=40)
        log[name] = list(net.kernel.log)
    assert deltas.fib_table() == whole.fib_table()
    assert deltas.fib_table() == v3ref.routes(deltas.model())
    # not only the same end: the same installs and withdrawals (a run's
    # withdrawals come in the order of a set in one arm, of the table in
    # the other)
    assert sorted(log["deltas"], key=str) == sorted(log["whole"], key=str)
    assert dict(deltas.sink._caches) == dict(whole.sink._caches)
    h1 = telemetry.snapshot("holo_ospf_rib_delta_routes")
    key = "holo_ospf_rib_delta_routes"
    runs = h1[key]["count"] - h0.get(key, {"count": 0})["count"]
    handed = h1[key]["sum"] - h0.get(key, {"sum": 0})["sum"]
    assert runs == deltas.inst.spf_run_count + whole.inst.spf_run_count
    # the whole-table arm alone hands over its table in every run
    assert handed > whole.inst.spf_run_count * 60


@pytest.mark.parametrize("seed", range(6))
def test_route_delta_equals_the_lookup_per_prefix(seed):
    """``_route_delta`` walks two tables side by side while their keys
    agree; whatever the second table did (a route changed in place, a
    prefix gone from the middle, one come at the end or in the middle,
    an equal prefix that is another object) it gives what a lookup per
    prefix gives."""
    from holo_tpu.protocols.ospf.instance_v3 import V6Route

    rng = np.random.default_rng(seed)
    hop = [frozenset({("e0", "a")}), frozenset({("e1", "b")})]
    prefixes = [IPv6Network((0x20010DB8 << 96 | n << 64, 64)) for n in range(60)]
    old = {p: V6Route(p, int(rng.integers(1, 5)), hop[0]) for p in prefixes[:50]}
    new = {}
    for n, (p, r) in enumerate(old.items()):
        roll = rng.random()
        if seed and roll < 0.1:
            continue  # gone
        if roll < 0.3:
            r = V6Route(p, r.dist + int(roll < 0.2), hop[int(roll < 0.25)])
        if roll > 0.9:
            p = IPv6Network(str(p))  # equal, not the same object
        new[p] = r
        if seed > 2 and n == 20:
            new[prefixes[55]] = V6Route(prefixes[55], 1, hop[1])
    if seed % 2:
        new[prefixes[58]] = V6Route(prefixes[58], 2, hop[0])
    changed, removed = OspfV3Instance._route_delta(old, new)
    want = {
        p: r for p, r in new.items()
        if p not in old or (old[p].dist, old[p].nexthops) != (r.dist, r.nexthops)
    }
    assert changed == want
    assert sorted(removed) == sorted(p for p in old if p not in new)
    assert OspfV3Instance._route_delta(new, new) == ({}, [])
