"""OSPFv3's intra-area derive by difference (``spf_run.KeptDerive``)
against the loop it replaced, which stays here as the oracle: after
every derive of every run the table holds the same keys IN THE SAME
ORDER and routes equal field for field.  A storm of changes on the tiny
multi-area network, then one case per way the kept state can go stale."""

from dataclasses import dataclass
from ipaddress import IPv4Address, IPv6Address, IPv6Network

import numpy as np
import pytest

from holo_tpu import telemetry
from holo_tpu.frr.manager import FrrConfig
from holo_tpu.ops.graph import INF
from holo_tpu.protocols.ospf import packet_v3 as P
from holo_tpu.protocols.ospf.instance_v3 import OspfV3Instance, V6Route
from holo_tpu.protocols.ospf.neighbor import Neighbor, NsmState
from holo_tpu.protocols.ospf.spf_run import KeptDerive
from holo_tpu.spf.backend import ScalarSpfBackend

from tests.test_v3_marshal import _lsa, build

AREA = IPv4Address(1)
FAMILY = "holo_ospf_derive_routes_total"


def old_derive_intra(inst, aid, out) -> dict:
    """``OspfV3Instance._derive_intra`` as it was before the kept
    derive: a walk over the live Intra-Area-Prefix LSAs."""
    index, _keys, res, atoms, prefix_lsas = out
    router_t, network_t = int(P.LsaType.ROUTER), int(P.LsaType.NETWORK)
    intra: dict = {}
    for _adv, body in prefix_lsas:
        if not body.prefixes:
            continue
        if body.ref_type == router_t:
            v = index.get(("R", body.ref_adv_rtr))
        elif body.ref_type == network_t:
            v = index.get(("N", body.ref_adv_rtr, int(body.ref_lsid)))
        else:
            continue
        if v is None:
            continue
        base = int(res.dist[v])
        if base >= INF:
            continue
        nhs = inst._expand_atoms(res.nexthop_words[v], atoms)
        for entry in body.prefixes:
            prefix, total = entry[0], base + entry[1]
            cur = intra.get(prefix)
            if cur is None or total < cur.dist:
                intra[prefix] = V6Route(
                    prefix, total, nhs,
                    prefix_options=body.entry_opts(entry),
                    area_id=aid, vertex=v,
                )
            elif total == cur.dist:
                intra[prefix] = V6Route(
                    prefix, total, cur.nexthops | nhs,
                    prefix_options=cur.prefix_options,
                    area_id=aid, vertex=cur.vertex,
                )
    return intra


FIELDS = ("prefix", "dist", "nexthops", "prefix_options", "area_id", "vertex")


def assert_same_table(got: dict, want: dict) -> None:
    assert list(got) == list(want)  # key for key, in the same order
    for prefix, route in want.items():
        for name in FIELDS:
            assert getattr(got[prefix], name) == getattr(route, name), (
                prefix, name,
            )


@dataclass
class Call:
    aid: IPv4Address
    table: dict
    kept: int
    rebuilt: int
    whole: bool  # the kept state was thrown away


def _counted() -> dict:
    snap = telemetry.snapshot(prefix=FAMILY)
    return {
        path: snap.get(f"{FAMILY}{{path={path}}}", 0.0)
        for path in ("kept", "rebuilt")
    }


@pytest.fixture
def calls(monkeypatch):
    """Every ``_derive_intra`` call of the test held to the oracle and
    to the counter: ``kept + rebuilt`` is the table's size."""
    seen: list[Call] = []
    resets: list = []
    real, real_reset = OspfV3Instance._derive_intra, KeptDerive._reset

    def reset(self, index, bodies):
        resets.append(self)
        return real_reset(self, index, bodies)

    def derive(self, aid, out, knobs):
        before, n_resets = _counted(), len(resets)
        got = real(self, aid, out, knobs)
        after = _counted()
        assert_same_table(got, old_derive_intra(self, aid, out))
        kept, rebuilt = (
            int(after[path] - before[path]) for path in ("kept", "rebuilt")
        )
        assert kept + rebuilt == len(got)
        whole = len(resets) > n_resets
        assert not (whole and kept)  # a whole derive counts none kept
        seen.append(Call(aid, dict(got), kept, rebuilt, whole))
        return got

    monkeypatch.setattr(OspfV3Instance, "_derive_intra", derive)
    monkeypatch.setattr(KeptDerive, "_reset", reset)
    return seen


# ---- a storm of changes on the tiny multi-area network -----------------


def test_storm_on_the_tiny_multi_area_network_equals_the_walk(calls):
    """The ``tiny-areastorm`` rehearsal in this process: link, node,
    summary (partial runs), bfd, carrier and ifconfig (cost flips, and
    shuts that change the atom table) events over five areas, every
    derive of every run held to the walk."""
    from benchmark import run

    cell = run.load_json("workloads", "tiny-areastorm")
    result, _rc = run.measure(
        cell, run.load_json("configs", cell["config"]),
        run.load_plugin("drivers", cell["driver"]), 2147484737, 4.0, False,
    )
    assert result["checks"]["parity"] and result["failed"] == 0
    counts = result["counts"]
    # (the window is wall time: some 500 derives here alone, a third of
    # that beside five other test workers)
    assert counts["injected"] >= 100
    assert counts["spf_types"].get("instance=ospfv3-dut,type=partial", 0) > 0
    assert len(calls) >= 100 and len({c.aid for c in calls}) >= 4
    # an area's first derive is whole, and its last (the parity check's
    # forced run on another backend), and no other: a shut (another
    # atom table) is followed atom for atom, and the storm never changes
    # the vertex model; some derives keep every route they had
    areas = list(dict.fromkeys(c.aid for c in calls))
    whole = [i for i, c in enumerate(calls) if c.whole]
    assert [calls[i].aid for i in whole[:len(areas)]] == areas
    assert whole[len(areas):] == list(range(len(calls) - len(areas), len(calls)))
    by_difference = [c for c in calls if not c.whole]
    assert counts["dispatches_without_lineage"] > 5  # the shuts
    assert any(not c.rebuilt for c in by_difference)
    assert any(c.rebuilt and c.kept for c in by_difference)
    assert sum(c.kept for c in calls) > sum(c.rebuilt for c in calls)


# ---- one seeded area, one way of going stale per case ------------------


def _full(inst) -> None:
    inst._spf_force_full = True
    inst.run_spf()


def _start(calls, seed: int = 5, **build_kw):
    inst, area, rids, links = build(seed, **build_kw)
    _full(inst)
    assert len(calls) == 1 and calls[0].kept == 0  # the first: whole
    assert len(calls[0].table) > 30
    return inst, area, rids, links


def _reinstall_router(inst, area, rid, links, seq) -> None:
    area.lsdb.install(
        _lsa(P.LsaType.ROUTER, 0, rid, P.LsaRouterV3(links=links), seq=seq),
        inst.loop.clock.now(),
    )


def _nothing_moves(inst, area, rids, links, calls, seq=9) -> None:
    """The same Router-LSA installed again: a new entry, so the area
    is dispatched, and a result in which no vertex moved."""
    _reinstall_router(inst, area, rids[31], links[rids[31]], seq)
    n = len(calls)
    _full(inst)
    assert len(calls) == n + 1 and calls[-1].rebuilt == 0
    assert all(r is calls[-2].table[p] for p, r in calls[-1].table.items())


def _costlier(links, by: int = 1) -> list:
    return [
        P.RouterLinkV3(
            l.link_type, l.metric + by, l.iface_id, l.nbr_iface_id,
            l.nbr_router_id,
        )
        for l in links
    ]


def _prefix_lsa(rid, prefixes, lsid=1, seq=2, age=1):
    return _lsa(
        P.LsaType.INTRA_AREA_PREFIX, lsid, rid,
        P.LsaIntraAreaPrefix(
            ref_type=int(P.LsaType.ROUTER), ref_lsid=IPv4Address(0),
            ref_adv_rtr=rid, prefixes=prefixes,
        ),
        age=age, seq=seq,
    )


def _own_prefix(rid) -> IPv6Network:
    return IPv6Network((int(rid) << 64 | 0x2001 << 112, 64))


def _is_whole(call: Call) -> bool:
    return call.whole and call.rebuilt == len(call.table)


def _by_difference(call: Call) -> bool:
    return not call.whole and call.kept > 0


def _cut_off(inst, area, rids, links, who, seq) -> None:
    """Every link of ``who`` gone from both ends; its Router-LSA stays,
    so the vertex model does too."""
    for r in rids:
        kept = [
            l for l in links[r]
            if r != who and l.nbr_router_id != who
        ]
        if len(kept) != len(links[r]):
            _reinstall_router(inst, area, r, kept, seq)


def case_link_cost(inst, area, rids, links, calls):
    _reinstall_router(inst, area, rids[30], _costlier(links[rids[30]]), 2)
    _full(inst)
    assert _by_difference(calls[-1]) and calls[-1].rebuilt > 0
    _nothing_moves(inst, area, rids, links, calls)


def case_router_lost_and_back(inst, area, rids, links, calls):
    key = P.LsaKey(P.LsaType.ROUTER, IPv4Address(0), rids[25])
    area.lsdb.remove(key)
    _full(inst)
    assert _is_whole(calls[-1])  # another vertex model: other vertex ids
    _reinstall_router(inst, area, rids[25], links[rids[25]], 2)
    _full(inst)
    assert _is_whole(calls[-1])
    _reinstall_router(inst, area, rids[20], _costlier(links[rids[20]]), 2)
    _full(inst)
    assert _by_difference(calls[-1])


def case_atom_table(inst, area, rids, links, calls):
    """An uplink shut: the adjacency goes, its atom with it, and every
    later atom's bit moves down.  The rows are compared atom for atom:
    what went through the uplink is rebuilt, the rest kept."""
    was = calls[-1].table
    through = {
        p for p, r in was.items() if any(nh[0] == "e2" for nh in r.nexthops)
    }
    assert through and len(through) < len(was)
    atoms = inst._area_kept[AREA][0].atoms
    assert atoms.index(("e2", IPv6Address("fe80::a:21"))) < len(atoms) - 1
    del inst.interfaces["e2"].neighbors[rids[2]]
    _full(inst)
    assert _by_difference(calls[-1])
    assert inst._area_kept[AREA][0].atoms != atoms
    # (both LANs offer one prefix: rebuilt when either's vertex moves)
    through.add(IPv6Network("2001:db8:77::/64"))
    for p, r in calls[-1].table.items():
        assert (r is was[p]) == (p not in through), p
    _nothing_moves(inst, area, rids, links, calls)
    # and back: the atom returns at its old place
    inst.interfaces["e2"].neighbors[rids[2]] = Neighbor(
        router_id=rids[2], src=IPv6Address("fe80::a:21"),
        state=NsmState.FULL, iface_id=21,
    )
    _full(inst)
    assert _by_difference(calls[-1])
    assert_same_table(calls[-1].table, was)
    _nothing_moves(inst, area, rids, links, calls, seq=10)


def case_prefix_lsa_replaced(inst, area, rids, links, calls):
    extra = IPv6Network("2001:db8:1234::/48")
    now = inst.loop.clock.now
    area.lsdb.install(_prefix_lsa(
        rids[9], [(_own_prefix(rids[9]), 7), (extra, 3, 0x02)]
    ), now())
    _full(inst)
    assert _by_difference(calls[-1]) and calls[-1].rebuilt <= 2
    assert calls[-1].table[extra].prefix_options == 0x02
    area.lsdb.install(_prefix_lsa(rids[9], [(extra, 4)], seq=3), now())
    _full(inst)
    assert _own_prefix(rids[9]) not in calls[-1].table
    assert _by_difference(calls[-1]) and calls[-1].rebuilt == 1


def case_prefix_lsa_withdrawn(inst, area, rids, links, calls):
    area.lsdb.remove(
        P.LsaKey(P.LsaType.INTRA_AREA_PREFIX, IPv4Address(1), rids[9])
    )
    _full(inst)
    assert _own_prefix(rids[9]) not in calls[-1].table
    assert _by_difference(calls[-1]) and calls[-1].rebuilt == 0
    area.lsdb.install(
        _prefix_lsa(rids[9], [(_own_prefix(rids[9]), 1)]),
        inst.loop.clock.now(),
    )
    _full(inst)  # back, at the end of the LSDB's order now
    assert list(calls[-1].table)[-1] == _own_prefix(rids[9])
    assert calls[-1].rebuilt == 1


def case_prefix_lsa_aged_out(inst, area, rids, links, calls):
    area.lsdb.install(
        _prefix_lsa(rids[9], [(_own_prefix(rids[9]), 1)], age=P.MAX_AGE - 5),
        inst.loop.clock.now(),
    )
    _full(inst)
    assert _own_prefix(rids[9]) in calls[-1].table
    inst.loop.advance(10.0)  # MaxAge on the clock, with no install
    _full(inst)
    assert _own_prefix(rids[9]) not in calls[-1].table
    assert _by_difference(calls[-1])


def case_two_offers_of_one_prefix(inst, area, rids, links, calls):
    """A second LSA offers a router's prefix from another vertex: at a
    higher total, at the equal one (the sets unite, the first's options
    and vertex stay), at a lower one; then only one of the two vertices
    moves, and the prefix is rebuilt from both offers."""
    _st, _be, _kn, (index, _keys, res, _atoms, _pl), _intra = (
        inst._area_kept[AREA]
    )
    dist = {r: int(res.dist[index[("R", r)]]) for r in rids}
    y = max(rids[4:], key=lambda r: dist[r] if dist[r] < INF else -1)
    x = min(
        (r for r in rids[4:] if r != y and dist[r] < dist[y]),
        key=lambda r: dist[r],
    )
    prefix = _own_prefix(y)
    equal = dist[y] + 1 - dist[x]
    now = inst.loop.clock.now
    seq = 2
    for metric in (equal + 2, equal, equal - 1, equal):
        area.lsdb.install(
            _prefix_lsa(x, [(prefix, metric, 0x08)], lsid=2, seq=seq), now()
        )
        seq += 1
        _full(inst)
        assert _by_difference(calls[-1]) and calls[-1].rebuilt == 1
    tied = calls[-1].table[prefix]
    assert tied.vertex == index[("R", y)] and tied.prefix_options == 0
    assert tied.nexthops >= calls[0].table[prefix].nexthops
    # x alone moves further away (every link to and from it costlier):
    # the prefix is rebuilt from both offers, and y's wins again
    for r in rids:
        dearer = [
            P.RouterLinkV3(
                l.link_type,
                l.metric + (3 if x in (r, l.nbr_router_id) else 0),
                l.iface_id, l.nbr_iface_id, l.nbr_router_id,
            ) for l in links[r]
        ]
        if dearer != links[r]:
            _reinstall_router(inst, area, r, dearer, 2)
    _full(inst)
    assert _by_difference(calls[-1])
    assert calls[-1].table[prefix].nexthops == calls[0].table[prefix].nexthops


def case_first_offer_unreachable(inst, area, rids, links, calls):
    """A prefix stands where its first REACHABLE offer does: cut the
    first offering router off and the prefix moves to the second
    offer's place, join it again and it moves back."""
    z, w = rids[20], rids[1]
    prefix = _own_prefix(z)
    area.lsdb.install(
        _prefix_lsa(w, [(prefix, 60)], lsid=2), inst.loop.clock.now()
    )
    _full(inst)
    place = list(calls[-1].table).index(prefix)
    assert calls[-1].table[prefix].dist < 60
    _cut_off(inst, area, rids, links, z, 2)
    _full(inst)
    assert _by_difference(calls[-1])
    assert list(calls[-1].table)[-1] == prefix
    for r in rids:
        _reinstall_router(inst, area, r, links[r], 3)
    _full(inst)
    assert list(calls[-1].table).index(prefix) == place


def case_partial_run_between_full_runs(inst, area, rids, links, calls):
    """A partial run edits the area's table in place and moves the
    prefix to its end; the next full run's table stands in LSDB order
    again, and is not a whole derive."""
    key = P.LsaKey(P.LsaType.INTRA_AREA_PREFIX, IPv4Address(1), rids[9])
    old = area.lsdb.get(key).lsa
    new = _prefix_lsa(rids[9], [(_own_prefix(rids[9]), 5)])
    area.lsdb.install(new, inst.loop.clock.now())
    inst._spf_triggers.append((new, old))
    inst.run_spf()
    assert len(calls) == 1  # partial: no derive
    table = inst._spf_cache["intra_by_area"][AREA]
    assert list(table)[-1] == _own_prefix(rids[9])
    assert table[_own_prefix(rids[9])].dist == (
        calls[0].table[_own_prefix(rids[9])].dist + 4
    )
    _reinstall_router(inst, area, rids[30], _costlier(links[rids[30]]), 2)
    _full(inst)
    assert _by_difference(calls[-1])
    assert list(calls[-1].table) == list(calls[0].table)


def case_frr_active(inst, area, rids, links, calls):
    """Under IP-FRR every derive is a whole one: ``_attach_frr_backups``
    writes into the route objects, and no object outlives its run."""
    inst.frr = FrrConfig(enabled=True)
    _full(inst)
    assert _is_whole(calls[-1])
    assert any(r.backups for r in inst.routes.values())
    _reinstall_router(inst, area, rids[2], _costlier(links[rids[2]], 9), 2)
    _full(inst)
    assert _is_whole(calls[-1])
    assert not any(
        r is calls[-2].table.get(p) for p, r in calls[-1].table.items()
    )
    # what a fresh instance over the same LSDB attaches
    fresh, fresh_area, _r, _l = build(5)
    _reinstall_router(fresh, fresh_area, rids[2], _costlier(links[rids[2]], 9), 2)
    fresh.frr = FrrConfig(enabled=True)
    _full(fresh)
    assert list(inst.routes) == list(fresh.routes)
    for prefix, route in fresh.routes.items():
        assert inst.routes[prefix].backups == route.backups, prefix
    inst.frr = None  # other knobs: whole once more, then by difference
    _full(inst)
    assert _is_whole(calls[-1])
    assert not any(r.backups for r in calls[-1].table.values())
    _nothing_moves(inst, area, rids, links, calls)


def case_reuse_unchanged_areas_off(inst, area, rids, links, calls):
    """The control arm dispatches every area in every run; the vertex
    model is kept all the same, so the derive still differs."""
    inst.reuse_unchanged_areas = False
    _full(inst)
    _full(inst)
    assert len(calls) == 3 and calls[-1].rebuilt == 0
    _reinstall_router(inst, area, rids[30], _costlier(links[rids[30]]), 2)
    _full(inst)
    assert _by_difference(calls[-1]) and calls[-1].rebuilt > 0


def case_max_paths(inst, area, rids, links, calls):
    """``max_paths`` > 1 arms the multipath dispatch: other knobs (a
    whole derive), and a result with ``nh_weights``, whose rows count
    as moved too."""
    inst.max_paths = 4
    _full(inst)
    assert _is_whole(calls[-1])
    assert inst._area_kept[AREA][3][2].nh_weights is not None
    _reinstall_router(inst, area, rids[30], _costlier(links[rids[30]]), 2)
    _full(inst)
    assert _by_difference(calls[-1])
    _nothing_moves(inst, area, rids, links, calls)
    inst.max_paths = None
    _full(inst)
    assert _is_whole(calls[-1])


def case_another_backend(inst, area, rids, links, calls):
    inst.backend = ScalarSpfBackend()
    _full(inst)
    assert _is_whole(calls[-1])
    _nothing_moves(inst, area, rids, links, calls)


CASES = [
    case_link_cost, case_router_lost_and_back, case_atom_table,
    case_prefix_lsa_replaced, case_prefix_lsa_withdrawn,
    case_prefix_lsa_aged_out, case_two_offers_of_one_prefix,
    case_first_offer_unreachable, case_partial_run_between_full_runs,
    case_frr_active, case_reuse_unchanged_areas_off, case_max_paths,
    case_another_backend,
]


@pytest.mark.parametrize(
    "case", CASES, ids=[c.__name__[len("case_"):] for c in CASES]
)
def test_kept_derive_follows(case, calls):
    inst, area, rids, links = _start(calls, lan=case is case_atom_table)
    case(inst, area, rids, links, calls)


def test_forced_empty_moved_mask_leaves_a_stale_route_the_test_sees(
    calls, monkeypatch,
):
    """The gate itself: with no vertex ever counted as moved a cost
    change leaves stale routes, and the comparison with the walk says
    so."""
    inst, area, rids, links = _start(calls)
    monkeypatch.setattr(
        KeptDerive, "_moved",
        lambda self, planes, atoms: np.zeros(len(planes[0]), bool),
    )
    _reinstall_router(inst, area, rids[2], _costlier(links[rids[2]], 9), 2)
    with pytest.raises(AssertionError):
        _full(inst)


def test_seeded_sequence_of_changes_on_one_area(calls):
    """200 seeded changes of every kind above, interleaved."""
    inst, area, rids, links = _start(calls, seed=11, lan=True)
    rng = np.random.default_rng(37)
    now = inst.loop.clock.now
    cur = {r: list(links[r]) for r in rids}
    seq = {r: 1 for r in rids}
    removed: set = set()
    for step in range(200):
        kind = rng.choice(["cost", "cut", "prefix", "withdraw", "router",
                           "partial", "age"],
                          p=[0.4, 0.1, 0.15, 0.1, 0.1, 0.1, 0.05])
        r = rids[int(rng.integers(4, len(rids)))]
        seq[r] += 1
        if kind == "cost" and cur[r]:
            i = int(rng.integers(len(cur[r])))
            l = cur[r][i]
            cur[r][i] = P.RouterLinkV3(
                l.link_type, int(rng.integers(1, 9)), l.iface_id,
                l.nbr_iface_id, l.nbr_router_id,
            )
            _reinstall_router(inst, area, r, cur[r], seq[r])
        elif kind == "cut":
            cur[r] = cur[r][:-1] if rng.random() < 0.5 else list(links[r])
            _reinstall_router(inst, area, r, cur[r], seq[r])
        elif kind in ("prefix", "partial", "age"):
            key = P.LsaKey(P.LsaType.INTRA_AREA_PREFIX, IPv4Address(1), r)
            entry = area.lsdb.get(key)
            other = rids[int(rng.integers(4, len(rids)))]
            new = _prefix_lsa(
                r,
                [(_own_prefix(r), int(rng.integers(1, 5))),
                 (_own_prefix(other), int(rng.integers(0, 12)), 0x08)],
                seq=seq[r] + 100,
                age=P.MAX_AGE - 3 if kind == "age" else 1,
            )
            area.lsdb.install(new, now())
            if kind == "partial" and entry is not None:
                inst._spf_triggers.append((new, entry.lsa))
                inst.run_spf()
                continue
        elif kind == "withdraw":
            key = P.LsaKey(P.LsaType.INTRA_AREA_PREFIX, IPv4Address(1), r)
            if area.lsdb.get(key) is not None:
                area.lsdb.remove(key)
        elif kind == "router":
            key = P.LsaKey(P.LsaType.ROUTER, IPv4Address(0), r)
            if r in removed:
                removed.discard(r)
                _reinstall_router(inst, area, r, cur[r], seq[r])
            elif r != rids[10] and area.lsdb.get(key) is not None:
                removed.add(r)
                area.lsdb.remove(key)
        if step % 7 == 0:
            inst.loop.advance(1.0)
        _full(inst)
    assert len(calls) > 150
    assert sum(_by_difference(c) for c in calls) > len(calls) // 2
    assert any(_is_whole(c) for c in calls[1:])
