"""OSPFv3's inter-area stage by difference (``spf_run.KeptInter``)
against the same instance with its kept state dropped before every run
(the whole stage), and both against the stage it replaced, which stays
here as the oracle: run for run the same ``_install_and_flood`` and
``_flush_self`` calls in the same order, with the same bodies and
sequence numbers, every area's LSDB equal, ``_active_ranges`` and the
route tables equal.  Seeded event sequences on the tiny multi-area
network, each event in its own run, with every kind of input the stage
reads moved at least once."""

import json
from ipaddress import IPv4Address, IPv6Network
from pathlib import Path

import numpy as np
import pytest

from benchmark import v3ref
from benchmark.areanet import BACKBONE, AreaNet, pod_range
from holo_tpu import telemetry
from holo_tpu.ops.graph import INF
from holo_tpu.protocols.ospf import packet_v3 as P
from holo_tpu.protocols.ospf.spf_run import aggregate_area_ranges
from holo_tpu.spf.backend import ScalarSpfBackend

CONFIG = json.loads(
    (Path(__file__).parents[1] / "benchmark/configs/tiny-v3areas.json")
    .read_text()
)


# -- the stage as it was before the kept state, the oracle


def old_area_adverts(inst, intra_by_area: dict) -> dict:
    interfaces = inst.interfaces

    def nh_areas(route) -> frozenset:
        return frozenset(
            interfaces[ifname].config.area_id
            for ifname, _addr in route.nexthops if ifname in interfaces
        )

    out: dict = {}
    active: set = set()
    for aid, intra in intra_by_area.items():
        area = inst.areas.get(aid)
        if area is None:
            continue
        eff, range_nh_areas, area_active = aggregate_area_ranges(
            intra, area.ranges, nh_areas
        )
        advert = {}
        for prefix, dist in eff.items():
            exits = range_nh_areas.get(prefix)
            if exits is not None:
                advert[prefix] = (dist, 0, frozenset(exits))
            else:
                r = intra[prefix]
                advert[prefix] = (dist, r.prefix_options, nh_areas(r))
        out[aid] = advert
        active |= area_active
    inst._active_ranges = active
    inst._old_adverts = out


def old_originate_inter_area(inst, inter_routes, area_results) -> None:
    adverts = inst._old_adverts
    backbone = IPv4Address(0)
    wanted = {aid: {} for aid in inst.areas}
    interfaces = inst.interfaces

    def nh_areas(route) -> frozenset:
        return frozenset(
            interfaces[ifname].config.area_id
            for ifname, _addr in route.nexthops if ifname in interfaces
        )

    candidates: dict = {}
    for src_aid, advert in adverts.items():
        for prefix, (dist, popts, exits) in advert.items():
            cur = candidates.get(prefix)
            if cur is None or dist < cur[0]:
                candidates[prefix] = (dist, popts, src_aid, True, exits)
    for prefix, route in inter_routes.items():
        if prefix not in candidates:
            candidates[prefix] = (
                route.dist, route.prefix_options, route.area_id,
                False, nh_areas(route),
            )
    for prefix, (dist, popts, src_aid, intra, exits) in candidates.items():
        for dst_aid in inst.areas:
            if src_aid == dst_aid:
                continue
            if not intra and dst_aid == backbone:
                continue
            if not inst.areas[dst_aid].summary:
                continue
            if dst_aid in exits:
                continue
            cur = wanted[dst_aid].get(prefix)
            if cur is None or dist < cur[0]:
                wanted[dst_aid][prefix] = (dist, popts)
    default = IPv6Network("::/0")
    for aid, area in inst.areas.items():
        if area.stub:
            wanted[aid][default] = (area.stub_default_cost, 0)
    asbr_wanted: dict = {aid: {} for aid in inst.areas}
    for src_aid, (index, _keys, res, _atoms, _pl) in area_results.items():
        src_area = inst.areas.get(src_aid)
        if src_area is None:
            continue
        for e in src_area.lsdb.all():
            if e.lsa.type != P.LsaType.ROUTER or e.lsa.is_maxage:
                continue
            if P.RouterFlags.E not in e.lsa.body.flags:
                continue
            if e.lsa.adv_rtr == inst.router_id:
                continue
            v = index.get(("R", e.lsa.adv_rtr))
            if v is None or res.dist[v] >= INF:
                continue
            for dst_aid in inst.areas:
                if dst_aid == src_aid or inst.areas[dst_aid].no_external:
                    continue
                cur = asbr_wanted[dst_aid].get(e.lsa.adv_rtr)
                if cur is None or int(res.dist[v]) < cur:
                    asbr_wanted[dst_aid][e.lsa.adv_rtr] = int(res.dist[v])
    rid = inst.router_id
    own = {
        aid: [k for k in area.lsdb.entries if k.adv_rtr == rid]
        for aid, area in inst.areas.items()
    }
    for aid, asbrs in asbr_wanted.items():
        area = inst.areas[aid]
        lsids = set()
        for asbr, dist in asbrs.items():
            lsid = inst._inter_lsid(aid, ("asbr", asbr))
            lsids.add(lsid)
            inst._originate(
                area, P.LsaType.INTER_AREA_ROUTER, lsid,
                P.LsaInterAreaRouter(metric=dist, dest_router_id=asbr),
            )
        for key in own[aid]:
            if key.type == P.LsaType.INTER_AREA_ROUTER and key.lsid not in lsids:
                e = area.lsdb.entries.get(key)
                if e is not None and not e.lsa.is_maxage:
                    inst._flush_self(area, key)
    for aid, prefixes in wanted.items():
        area = inst.areas[aid]
        lsids = set()
        for prefix, (dist, popts) in prefixes.items():
            lsid = inst._inter_lsid(aid, prefix)
            lsids.add(lsid)
            inst._originate(
                area, P.LsaType.INTER_AREA_PREFIX, lsid,
                P.LsaInterAreaPrefix(
                    metric=dist, prefix=prefix, prefix_options=popts
                ),
            )
        for key in own[aid]:
            if key.type == P.LsaType.INTER_AREA_PREFIX and key.lsid not in lsids:
                e = area.lsdb.entries.get(key)
                if e is not None and not e.lsa.is_maxage:
                    inst._flush_self(area, key)


# -- three arms of one network, and what each run did


def _net() -> AreaNet:
    return AreaNet(CONFIG["lsdb"], ScalarSpfBackend(), CONFIG["spf_delay"], 5.0)


class Arm:
    """A network whose instance logs, per SPF run, every install it
    floods and every flush it starts, and its state after the run."""

    def __init__(self, mode: str) -> None:
        self.net = net = _net()
        inst = self.inst = net.inst
        self.runs: list = []  # per run: (calls, state after it)
        self.originations = 0
        self._calls: list = []
        iaf, flush, orig = (
            inst._install_and_flood, inst._flush_self, inst._originate
        )

        def logged_iaf(area, lsa, from_iface=None, from_nbr=None):
            self._calls.append((
                "install", int(area.area_id), lsa.key, lsa.seq_no, lsa.age,
                lsa.raw,
                from_iface.name if from_iface is not None else None,
            ))
            return iaf(area, lsa, from_iface, from_nbr)

        def logged_flush(area, key):
            self._calls.append(("flush", int(area.area_id), key))
            return flush(area, key)

        def counted(*a, **kw):
            self.originations += 1
            return orig(*a, **kw)

        inst._install_and_flood = logged_iaf
        inst._flush_self = logged_flush
        inst._originate = counted
        if mode == "old":
            inst._area_adverts = lambda t: old_area_adverts(inst, t)
            inst._originate_inter_area = (
                lambda r, res: old_originate_inter_area(inst, r, res)
            )
        run = inst.run_spf

        def logged_run():
            if mode == "whole":
                inst._inter_kept.reset()
            self._calls = []
            run()
            self.runs.append((self._calls, self.state()))

        inst.run_spf = logged_run

    def state(self) -> dict:
        inst = self.inst
        cache = inst._spf_cache or {}

        def table(t):
            return {
                p: (r.dist, r.nexthops, r.route_type, r.prefix_options,
                    r.area_id)
                for p, r in t.items()
            }

        return {
            "lsdbs": {
                int(aid): {
                    k: (e.lsa.seq_no, e.lsa.age, e.lsa.raw, e.installed_at)
                    for k, e in area.lsdb.entries.items()
                }
                for aid, area in inst.areas.items()
            },
            "active": set(inst._active_ranges),
            "routes": table(inst.routes),
            "intra": {
                int(a): table(t)
                for a, t in cache.get("intra_by_area", {}).items()
            },
            "inter": table(cache.get("inter_routes", {})),
        }


def _live_own(inst, area, ltype) -> list:
    return [
        e for e in area.lsdb.all()
        if e.lsa.type == ltype and e.lsa.adv_rtr == inst.router_id
        and not e.lsa.is_maxage
    ]


def _settle(net, seconds: float = 11.0) -> None:
    net.loop.advance(seconds)


# Each of these moves one input of the stage on one network, the same
# way on every arm (the arms' networks are equal, so their choices are).


def ev_ranges(net, rng):
    hall = net.layout.halls[0]
    area = net.inst.areas[IPv4Address(hall)]
    hidden, fixed = pod_range(hall, 0), pod_range(hall, 1)
    net.inst.set_area_ranges(IPv4Address(hall), [
        dict(r, advertise=False) if r["prefix"] == hidden
        else dict(r, cost=777) if r["prefix"] == fixed else r
        for r in area.ranges
    ])


def ev_ranges_back(net, rng):
    hall = net.layout.halls[0]
    net.inst.set_area_ranges(IPv4Address(hall), [
        {"prefix": p, "advertise": True, "cost": None}
        for p in net.layout.ranges[hall]
    ])


def ev_stub(net, rng):
    area = net.inst.areas[IPv4Address(net.layout.halls[1])]
    area.stub = not area.stub
    net.inst._schedule_spf()


def ev_totally_stubby(net, rng):
    area = net.inst.areas[IPv4Address(net.layout.halls[2])]
    area.stub = True
    area.summary = not area.summary
    area.stub_default_cost = 5 if area.summary else 15
    net.inst._schedule_spf()


def ev_flap(net, rng):
    hall = net.layout.halls[int(rng.integers(len(net.layout.halls)))]
    links = net.flappable[hall]
    net.flap(hall, links[int(rng.integers(len(links)))], lost=False)


def ev_node(net, rng):
    hall = net.layout.halls[int(rng.integers(len(net.layout.halls)))]
    pool = net.losable[hall][("edge", "agg", "core")[int(rng.integers(3))]]
    target = (hall, pool[int(rng.integers(len(pool)))])
    if len(net.node_down) >= 2 and target not in net.node_down:
        target = net.node_down[0]
    net.node(*target, lost=False)


def ev_summary(net, rng):
    """A border router withdraws or re-advertises a pod range: the
    prefix-scoped partial run."""
    lay = net.layout
    pool = sorted({(a, p) for (a, _ar, p) in lay.summaries})
    net.summary(*pool[int(rng.integers(len(pool)))], lost=False)


def ev_asbr(net, rng):
    """A hall router's Router-LSA with the E bit flipped."""
    hall = net.layout.halls[3]
    router = net.losable[hall]["edge"][0]
    key = P.LsaKey(P.LsaType.ROUTER, IPv4Address(0), IPv4Address(router))
    e = net.inst.areas[IPv4Address(hall)].lsdb.get(key)
    flags = e.lsa.body.flags ^ P.RouterFlags.E
    lsa = net._lsa(
        P.LsaType.ROUTER, 0, router,
        P.LsaRouterV3(flags=flags, links=e.lsa.body.links),
    )
    net._apply([(hall, lsa)])


def ev_move_iface(net, rng):
    """The loopback moved into a hall, or back into the backbone."""
    inst = net.inst
    lo = inst.interfaces["lo"]
    to = (
        IPv4Address(net.layout.halls[0])
        if lo.config.area_id == IPv4Address(BACKBONE)
        else IPv4Address(BACKBONE)
    )
    lo.config.area_id = to
    inst._originate_router_lsa()
    inst._originate_intra_area_prefix()
    inst._schedule_spf()


def ev_premature_maxage(net, rng):
    """One of our summaries flushed by someone else (a MaxAge copy of it
    arrives): we want it still, so the next run originates it anew."""
    inst = net.inst
    for aid in (IPv4Address(net.layout.halls[1]), IPv4Address(BACKBONE)):
        area = inst.areas[aid]
        own = _live_own(inst, area, P.LsaType.INTER_AREA_PREFIX)
        if own:
            e = own[int(rng.integers(len(own)))]
            inst._install_and_flood(area, inst._maxage_copy(e.lsa))
            return


def _received(net, area, lsa_of) -> None:
    lsa = lsa_of()
    lsa.encode()
    net.inst._refresh_self_lsa(area, lsa)


def ev_self_copy(net, rng):
    """A newer copy of one of our summaries comes back from the network
    with another metric: we flood it, then outpace it."""
    inst = net.inst
    area = inst.areas[IPv4Address(net.layout.halls[2])]
    own = _live_own(inst, area, P.LsaType.INTER_AREA_PREFIX)
    e = own[int(rng.integers(len(own)))]
    old = e.lsa
    _received(net, area, lambda: P.Lsa(
        age=3, type=old.type, lsid=old.lsid, adv_rtr=old.adv_rtr,
        seq_no=old.seq_no + 5,
        body=P.LsaInterAreaPrefix(
            metric=old.body.metric + 3, prefix=old.body.prefix,
            prefix_options=old.body.prefix_options,
        ),
    ))


def ev_self_stranger(net, rng):
    """A summary of ours that we never originated (a former life's)
    comes back twice: first it is flushed; the second, newer copy is
    outpaced and live, and no run wants it, so the next stage must
    find and flush it with no origination of ours to remember it by."""
    inst = net.inst
    area = inst.areas[IPv4Address(net.layout.halls[3])]
    seq = getattr(net, "_stranger_seq", P.INITIAL_SEQ_NO + 10)
    net._stranger_seq = seq + 10
    for step in (0, 1):
        _received(net, area, lambda: P.Lsa(
            age=2, type=P.LsaType.INTER_AREA_PREFIX,
            lsid=IPv4Address(0x7FFF0001), adv_rtr=inst.router_id,
            seq_no=seq + step,
            body=P.LsaInterAreaPrefix(
                metric=9, prefix=IPv6Network("2001:db8:ffff::/48"),
            ),
        ))


def ev_bfd(net, rng):
    link = net.dut_links[int(rng.integers(len(net.dut_links)))]
    net.bfd(link, "down")
    _settle(net, 0.3)
    net.bfd(link, "up")


def ev_cost(net, rng):
    uplinks = [l for l in net.dut_links if l[0] != BACKBONE]
    net.ifconfig_cost(uplinks[int(rng.integers(len(uplinks)))])


def ev_shut(net, rng):
    uplinks = [l for l in net.dut_links if l[0] != BACKBONE]
    link = uplinks[int(rng.integers(len(uplinks)))]
    net.ifconfig_shut(net.shut.get(link[0], link))


SPECIAL = [
    ev_ranges, ev_stub, ev_asbr, ev_totally_stubby, ev_move_iface,
    ev_premature_maxage, ev_self_copy, ev_self_stranger, ev_ranges_back,
    ev_asbr, ev_stub, ev_move_iface, ev_totally_stubby, ev_self_stranger,
]
COMMON = [ev_flap, ev_flap, ev_node, ev_summary, ev_bfd, ev_cost, ev_shut]


def _drive(net, seed: int, events: int) -> None:
    """A seeded sequence: the common kinds, and the special ones in
    order between them; each event settled into its own run."""
    rng = np.random.default_rng(seed)
    special = list(SPECIAL)
    for n in range(events):
        if n % 3 == 1 and special:
            special.pop(0)(net, rng)
        else:
            COMMON[int(rng.integers(len(COMMON)))](net, rng)
        # past the hold-down: the next event starts its own run
        _settle(net, 11.0)
    net.loop.advance(30.0)


def _arms(seed: int, events: int) -> dict:
    arms = {mode: Arm(mode) for mode in ("kept", "whole", "old")}
    for arm in arms.values():
        _drive(arm.net, seed, events)
    return arms


def _assert_same_runs(a: Arm, b: Arm) -> None:
    assert len(a.runs) == len(b.runs)
    for n, ((calls_a, state_a), (calls_b, state_b)) in enumerate(
        zip(a.runs, b.runs)
    ):
        assert calls_a == calls_b, n
        for name in state_a:
            assert state_a[name] == state_b[name], (n, name)


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_kept_stage_equals_the_whole_stage_run_for_run(seed):
    snap0 = telemetry.snapshot("holo_ospf_inter_")
    arms = _arms(seed, events=60)
    kept, whole, old = arms["kept"], arms["whole"], arms["old"]
    assert len(kept.runs) >= 50
    _assert_same_runs(kept, whole)
    _assert_same_runs(kept, old)
    # every special event's kind of call was made: installs, flushes
    calls = [c for run, _s in kept.runs for c in run]
    assert any(c[0] == "flush" for c in calls)
    assert any(c[0] == "install" and c[2].type == P.LsaType.INTER_AREA_ROUTER
               for c in calls)
    # and the kept arm left most originations uncalled
    assert kept.originations * 4 < whole.originations
    snap = telemetry.snapshot("holo_ospf_inter_")
    moved = {k: v - snap0.get(k, 0) for k, v in snap.items()}
    assert moved["holo_ospf_inter_originations_total{path=skipped}"] > 0
    assert moved["holo_ospf_inter_adverts_total{path=kept}"] > 0
    net = kept.net
    assert net.fib_table() == v3ref.routes(net.model())


def test_a_stranger_of_ours_is_found_by_the_lsdb_index():
    """The second copy of a summary we never originated is live and
    wanted by nobody; no origination of ours knows it, and the stage
    flushes it all the same."""
    arm = Arm("kept")
    net, inst = arm.net, arm.inst
    _drive(net, 21, events=6)
    area = inst.areas[IPv4Address(net.layout.halls[3])]
    key = P.LsaKey(
        P.LsaType.INTER_AREA_PREFIX, IPv4Address(0x7FFF0001), inst.router_id
    )
    ev_self_stranger(net, None)
    assert not area.lsdb.get(key).lsa.is_maxage  # live, ours, unwanted
    runs = len(arm.runs)
    inst._schedule_spf()
    _settle(net)
    assert area.lsdb.get(key) is None or area.lsdb.get(key).lsa.is_maxage
    flushed = [c for run, _s in arm.runs[runs:] for c in run if c[0] == "flush"]
    assert flushed[0] == ("flush", int(area.area_id), key)


def test_an_unmoved_lsdb_count_leaves_every_summary_uncalled():
    """A run in which nothing the stage reads moved calls no
    origination; an install of one of ours between runs makes the next
    one look at that area's summaries again, and only there."""
    arm = Arm("kept")
    net, inst = arm.net, arm.inst
    _drive(net, 22, events=4)
    before = arm.originations
    inst._schedule_spf()
    _settle(net)
    assert arm.originations == before
    ev_premature_maxage(net, np.random.default_rng(0))
    _settle(net)
    assert arm.originations == before + 1


def test_lsdb_counts_and_orders_the_lsas_of_one_type_and_router():
    from holo_tpu.protocols.ospf.lsdb import Lsdb

    db = Lsdb()
    me, other = IPv4Address("1.1.1.1"), IPv4Address("2.2.2.2")

    def lsa(lsid, adv, seq=P.INITIAL_SEQ_NO):
        out = P.Lsa(
            age=1, type=P.LsaType.INTER_AREA_PREFIX, lsid=IPv4Address(lsid),
            adv_rtr=adv, seq_no=seq, body=P.LsaInterAreaPrefix(metric=lsid),
        )
        out.encode()
        return out

    for lsid in (3, 1, 2):
        db.install(lsa(lsid, me), 0.0)
    db.install(lsa(9, other), 0.0)
    count, keys = db.originated(me, P.LsaType.INTER_AREA_PREFIX)
    assert count == 3 and [int(k.lsid) for k in keys] == [3, 1, 2]
    db.install(lsa(1, me, P.INITIAL_SEQ_NO + 1), 1.0)  # replaced in place
    db.remove(lsa(3, me).key)
    db.install(lsa(3, me), 2.0)  # back, at the end
    count, keys = db.originated(me, P.LsaType.INTER_AREA_PREFIX)
    assert count == 6 and [int(k.lsid) for k in keys] == [1, 2, 3]
    assert list(keys) == [
        k for k in db.entries if k.adv_rtr == me
    ]
    assert db.originated(me, P.LsaType.ROUTER) == (0, {})
    db.remove(lsa(7, me).key)  # not there: nothing counted
    assert db.originated(me, P.LsaType.INTER_AREA_PREFIX)[0] == 6
    # and per type, every router's, the entries themselves
    count, entries = db.of_type(P.LsaType.INTER_AREA_PREFIX)
    assert count == 7 and list(entries) == list(db.entries)
    assert list(entries.values()) == list(db.entries.values())
    assert db.of_type(P.LsaType.ROUTER) == (0, {})


@pytest.mark.parametrize("seed", range(4))
def test_area_advert_follows_the_range_helper(seed):
    """``_AreaAdvert`` against ``aggregate_area_ranges`` over a sequence
    of tables, each made from the last as a run makes it: routes
    replaced, gone, come, a prefix equal but another object; ranges
    nested, one prefix twice with two costs, one not advertised.  A
    whole advert lists its entries in the helper's order."""
    from types import SimpleNamespace

    from holo_tpu.protocols.ospf.spf_run import _AreaAdvert

    rng = np.random.default_rng(seed)
    top = 0x20010DB8 << 96

    def prefix(length):
        host = int(rng.integers(0, 1 << 12)) << (128 - 20)
        return IPv6Network(((top | host) >> (128 - length) << (128 - length), length))

    ranges = [
        {"prefix": prefix(int(rng.integers(10, 19))),
         "advertise": bool(rng.random() < 0.8),
         "cost": int(rng.integers(1, 99)) if rng.random() < 0.3 else None}
        for _ in range(12)
    ]
    ranges.append(dict(ranges[0], cost=5))  # one prefix twice
    ranges.append(dict(ranges[1], cost=None))
    ifaces = {f"e{n}": IPv4Address(n % 3) for n in range(6)}

    def route(p):
        return SimpleNamespace(
            prefix=p, dist=int(rng.integers(1, 50)),
            prefix_options=int(rng.integers(0, 2)),
            nexthops=frozenset(
                (f"e{int(n)}", None) for n in rng.integers(0, 7, 2)
            ),
        )

    def exits(nexthops):
        return frozenset(ifaces[n] for n, _a in nexthops if n in ifaces)

    def want(table):
        eff, range_exits, active = aggregate_area_ranges(
            table, ranges, lambda r: exits(r.nexthops)
        )
        return {
            p: (d, 0, frozenset(range_exits[p])) if p in range_exits
            else (d, table[p].prefix_options, exits(table[p].nexthops))
            for p, d in eff.items()
        }, active

    table = {}
    for _ in range(200):
        p = prefix(int(rng.integers(16, 25)))
        table[p] = route(p)
    st = _AreaAdvert(ranges)
    st.follow(table, exits)
    advert, active = want(table)
    assert list(st.advert.items()) == list(advert.items())  # and in order
    assert st.active == active
    for _step in range(12):
        table = dict(table)
        for p in list(table):
            roll = rng.random()
            if roll < 0.08:
                del table[p]
            elif roll < 0.2:
                table[p] = route(p)
            elif roll < 0.23:
                r = table.pop(p)
                table[IPv6Network(str(p))] = r  # equal, another object
        for _ in range(int(rng.integers(0, 8))):
            p = prefix(int(rng.integers(16, 25)))
            table[p] = route(p)
        if _step == 6:
            table = {}  # every route gone, then back
        st.follow(table, exits)
        advert, active = want(table)
        assert st.advert == advert
        assert st.active == active
