"""``derive_routes``, ``atom_bits`` and ``reachable_router_flags`` against
the formulations they replaced (ISSUE 28).

The derive stage used to decode a next-hop set for every reachable
vertex, one NumPy scalar operation per atom; it now decodes once per
distinct bitmask row, and only for vertices that offer a prefix.  The
old bodies are kept here as the oracle: the routes must come out with
the same keys in the same insertion order (the FIB digest and the RIB's
publish order hang on it) and equal in every field.  No case reads a
clock.

Since ISSUE 32 both functions read a *plan* where the topology carries
one (``SpfTopology.plan``, which the area's kept ``LoweredLsdb`` makes
for every run): the offers and the router flags by vertex index, no walk
over the LSDB or the vertices.  Their bodies as they stood before it are
the second oracle (``walked_*``), and a topology without a plan still
runs them in the program.
"""

import dataclasses
import json
import os
import subprocess
import sys
from collections import Counter
from ipaddress import IPv4Address, IPv4Network
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from holo_tpu import telemetry
from holo_tpu.ops.graph import INF
from holo_tpu.protocols.ospf.lsdb import LsaEntry, Lsdb
from holo_tpu.protocols.ospf.packet import (
    MAX_AGE,
    Lsa,
    LsaKey,
    LsaNetwork,
    LsaRouter,
    LsaType,
    Options,
    RouterFlags,
    RouterLink,
    RouterLinkType,
)
from holo_tpu.protocols.ospf.spf_run import (
    IntraRoute,
    LoweredLsdb,
    NexthopAtom,
    RouteNexthop,
    SpfTopology,
    _atom_weights_of,
    _atoms_of,
    atom_bits,
    build_topology,
    clamp_multipath,
    derive_routes,
    reachable_router_flags,
)
from holo_tpu.spf.backend import ScalarSpfBackend, SpfResult
from holo_tpu.utils.ip import apply_mask
from tests import test_build_topology as tbt

REPO = Path(__file__).resolve().parents[1]
AREA = IPv4Address("0.0.0.0")
NOW = 5000.0
FAMILY = "holo_ospf_derive_nexthops_total"


# -- the oracle: the stage as it stood before ISSUE 28, verbatim but for
# -- the names


def scalar_atom_bits(words, n_atoms):
    return [
        a
        for a in range(n_atoms)
        if words[a // 32] & (np.uint32(1) << np.uint32(a % 32))
    ]


def per_vertex_atoms_of(words, atoms):
    out = set()
    for a in scalar_atom_bits(words, len(atoms)):
        atom = atoms[a]
        if atom.expand is not None:
            out |= atom.expand
        else:
            out.add(RouteNexthop(atom.ifname, atom.addr))
    return frozenset(out)


def per_vertex_atom_weights_of(words, weights_row, atoms):
    out = {}
    for a in scalar_atom_bits(words, len(atoms)):
        atom = atoms[a]
        w = int(weights_row[a]) if a < len(weights_row) else 0
        targets = (
            atom.expand
            if atom.expand is not None
            else (RouteNexthop(atom.ifname, atom.addr),)
        )
        for nh in targets:
            out[nh] = out.get(nh, 0) + w
    return out


def per_vertex_derive_routes(st, res, lsdb, now, area_id, max_paths=None):
    routes = {}

    def offer(prefix, dist, nhs, vertex=-1, weights=None):
        cur = routes.get(prefix)
        if cur is None or dist < cur.dist:
            routes[prefix] = IntraRoute(
                prefix, dist, nhs, area_id, vertex=vertex,
                nh_weights=dict(weights) if weights else None,
            )
        elif dist == cur.dist:
            merged = None
            if cur.nh_weights or weights:
                merged = dict(cur.nh_weights or {})
                for nh, w in (weights or {}).items():
                    merged[nh] = merged.get(nh, 0) + w
            routes[prefix] = IntraRoute(
                prefix, dist, cur.nexthops | nhs, area_id,
                vertex=cur.vertex, nh_weights=merged,
            )

    inv_net = {i: a for a, i in st.network_index.items()}
    inv_rtr = {i: r for r, i in st.router_index.items()}
    nlsa = {}
    rlsa = {}
    for e in lsdb.all():
        if e.current_age(now) >= 3600:
            continue
        if e.lsa.type == LsaType.NETWORK:
            nlsa[e.lsa.lsid] = e.lsa.body
        elif e.lsa.type == LsaType.ROUTER:
            rlsa[e.lsa.adv_rtr] = e.lsa.body

    nhw = getattr(res, "nh_weights", None)
    n = st.topo.n_vertices
    for v in range(n):
        if res.dist[v] >= INF:
            continue
        nhs = per_vertex_atoms_of(res.nexthop_words[v], st.atoms)
        weights = (
            per_vertex_atom_weights_of(res.nexthop_words[v], nhw[v], st.atoms)
            if nhw is not None
            else None
        )
        if v in inv_net:
            body = nlsa.get(inv_net[v])
            if body is None:
                continue
            prefix = apply_mask(inv_net[v], body.mask)
            offer(prefix, int(res.dist[v]), nhs, vertex=v, weights=weights)
        else:
            body = rlsa.get(inv_rtr[v])
            if body is None:
                continue
            for link in body.links:
                if link.link_type == RouterLinkType.STUB_NETWORK:
                    prefix = apply_mask(link.id, link.data)
                    offer(
                        prefix, int(res.dist[v]) + link.metric, nhs,
                        vertex=v, weights=weights,
                    )
    clamp_multipath(routes, max_paths)
    return routes


def comprehension_router_flags(st, res, lsdb):
    """The flags pass as it stood in ``OspfInstance._run_spf_traced``."""
    flags_now = {}
    for key, e in lsdb.entries.items():
        if key.type == LsaType.ROUTER and not e.lsa.is_maxage:
            flags_now[key.adv_rtr] = e.lsa.body.flags
    return {
        rid: flags_now.get(rid, RouterFlags(0))
        for rid, v in st.router_index.items()
        if res.dist[v] < INF
    }


# -- small areas by hand


def _rid(i):
    return IPv4Address((10 << 24) | (i + 1))


def _dr(j):
    return IPv4Address((172 << 24) | (16 << 16) | (j << 8) | 1)


def _stub(prefix, metric=1):
    net = IPv4Network(prefix)
    return RouterLink(
        RouterLinkType.STUB_NETWORK, net.network_address, net.netmask, metric
    )


def _p2p(i, metric=1):
    return RouterLink(
        RouterLinkType.POINT_TO_POINT, _rid(i), IPv4Address(i + 1), metric
    )


def _atoms(n):
    return [
        NexthopAtom(f"e{a}", IPv4Address((192 << 24) | (a << 8) | 2))
        for a in range(n)
    ]


def _words(masks, n_atoms):
    """uint32[N, W] rows from one Python int per vertex."""
    w = max(1, -(-n_atoms // 32))
    return np.array(
        [[(m >> (32 * i)) & 0xFFFFFFFF for i in range(w)] for m in masks],
        np.uint32,
    )


def _area(routers, networks=(), *, atoms, dist, masks, nhw=None):
    """``routers``: per router vertex its Router-LSA's links, or None for
    no LSA, or ``(links, age, installed_at, flags)``; ``networks``: per
    network vertex its mask, or None for no Network-LSA.  Vertices are
    the routers, then the networks."""
    lsdb = Lsdb()
    router_index, network_index = {}, {}
    for i, spec in enumerate(routers):
        router_index[_rid(i)] = i
        if spec is None:
            continue
        links, age, at, flags = spec if isinstance(spec, tuple) else (
            spec, 0, NOW, RouterFlags(0)
        )
        lsa = Lsa(age, Options(0), LsaType.ROUTER, _rid(i), _rid(i), 1,
                  LsaRouter(flags, list(links)))
        lsdb.entries[lsa.key] = LsaEntry(lsa, at)
    for j, mask in enumerate(networks):
        network_index[_dr(j)] = len(routers) + j
        if mask is None:
            continue
        lsa = Lsa(0, Options(0), LsaType.NETWORK, _dr(j), _rid(0), 1,
                  LsaNetwork(IPv4Address(mask), [_rid(0)]))
        lsdb.entries[lsa.key] = LsaEntry(lsa, NOW)
    n = len(routers) + len(networks)
    assert len(dist) == len(masks) == n
    st = SpfTopology(
        SimpleNamespace(n_vertices=n), atoms, router_index, network_index
    )
    planes = np.zeros(n, np.int32)
    res = SpfResult(
        np.array(dist, np.int32), planes, planes, _words(masks, len(atoms)),
        nh_weights=None if nhw is None else np.array(nhw, np.int32),
    )
    return st, res, lsdb


def _random_area(n_atoms, seed, n_routers=60, n_networks=6, distinct=5):
    """A random area whose vertices share ``distinct`` bitmask rows, with
    prefixes drawn from a small pool so that offers collide."""
    rng = np.random.default_rng(seed)
    pool = [f"10.{rng.integers(1, 4)}.{k}.0/24" for k in range(24)]
    routers = []
    for i in range(n_routers):
        links = [_p2p((i + 1) % n_routers)]
        for _ in range(int(rng.integers(0, 3))):
            links.append(_stub(pool[rng.integers(len(pool))],
                               int(rng.integers(1, 4))))
        routers.append(links)
    rows = [0] + [
        int.from_bytes(rng.bytes(-(-n_atoms // 8)), "little")
        & ((1 << n_atoms) - 1)
        for _ in range(distinct - 1)
    ]
    n = n_routers + n_networks
    return _area(
        routers, ["255.255.255.0"] * n_networks, atoms=_atoms(n_atoms),
        dist=[0] + rng.integers(1, 6, n - 1).tolist(),
        masks=[rows[k] for k in rng.integers(0, distinct, n)],
    )


def _two_atoms():
    return _random_area(2, seed=1, distinct=4), None


def _twelve_atoms():
    return _random_area(12, seed=2, distinct=9), None


def _two_words():
    st, res, lsdb = _area(
        [[_stub("10.0.0.0/24")], [_stub("10.0.1.0/24")],
         [_stub("10.0.2.0/24")], [_stub("10.0.1.0/24", 2)]],
        atoms=_atoms(40), dist=[0, 3, 4, 2],
        masks=[0, 1 << 35, (1 << 2) | (1 << 39), (1 << 31) | (1 << 32)],
    )
    assert res.nexthop_words.shape == (4, 2)
    return (st, res, lsdb), None


def _vlink_expand():
    bundle = frozenset({
        RouteNexthop("e7", IPv4Address("192.0.7.2")),
        RouteNexthop("e8", IPv4Address("192.0.8.2")),
    })
    atoms = _atoms(2) + [NexthopAtom(None, None, expand=bundle)]
    return _area(
        [[_stub("10.0.0.0/24")], [_stub("10.0.1.0/24")],
         [_stub("10.0.2.0/24")], [_stub("10.0.3.0/24")]],
        atoms=atoms, dist=[0, 1, 2, 3], masks=[0, 0b100, 0b101, 0b010],
    ), None


def _unreachable_missing_and_aged():
    aged = ([_stub("10.0.4.0/24")], 0, NOW - MAX_AGE - 5, RouterFlags(0))
    return _area(
        [[_stub("10.0.0.0/24")], [_stub("10.0.1.0/24")],
         [_stub("10.0.2.0/24")], None, aged, [_stub("10.0.5.0/24")]],
        ["255.255.255.0", None],
        atoms=_atoms(2), dist=[0, 1, INF, 2, 2, 3, INF, 2],
        masks=[0, 1, 2, 1, 2, 3, 1, 1],
    ), None


def _transit_networks():
    return _area(
        [[_p2p(1)], [_p2p(0), _stub("172.16.1.0/24", 7)]],
        ["255.255.255.0", "255.255.255.0", "255.255.0.0"],
        atoms=_atoms(3), dist=[0, 1, 4, 8, 2],
        masks=[0, 0b001, 0b010, 0b100, 0b011],
    ), None


def _equal_cost_union():
    # 10.9.0.0/24 at cost 5 from vertices 1, 2 and 3 (2 first, then 1 by
    # a lower cost, then 3 and 4 equal to 1): the union keeps vertex 1.
    return _area(
        [[_p2p(1)], [_stub("10.9.0.0/24", 2)], [_stub("10.9.0.0/24", 9)],
         [_stub("10.9.0.0/24", 1)], [_stub("10.9.0.0/24", 4)]],
        atoms=_atoms(4), dist=[0, 3, 1, 4, 1],
        masks=[0, 0b0001, 0b0010, 0b0100, 0b1001],
    ), None


def _ucmp_max_paths_2():
    atoms = _atoms(3)
    return _area(
        [[_p2p(1)], [_stub("10.9.0.0/24", 1), _stub("10.8.0.0/24", 1)],
         [_stub("10.9.0.0/24", 1)], [_stub("10.7.0.0/24", 1)]],
        atoms=atoms, dist=[0, 2, 2, 5],
        masks=[0, 0b011, 0b110, 0b111],
        nhw=[[0, 0, 0], [3, 1, 0], [0, 2, 5], [1, 1, 4]],
    ), 2


def _empty_bitmask_row():
    return _area(
        [[_stub("10.0.0.0/24")], [_stub("10.0.1.0/24")],
         [_stub("10.0.2.0/24")]],
        atoms=_atoms(2), dist=[0, 1, 2], masks=[0, 0, 0b01],
    ), None


CASES = {
    "two_atoms": _two_atoms,
    "twelve_atoms": _twelve_atoms,
    "two_words_a_bit_in_each": _two_words,
    "vlink_expand": _vlink_expand,
    "unreachable_missing_and_aged_out": _unreachable_missing_and_aged,
    "transit_networks": _transit_networks,
    "equal_cost_union_keeps_first_vertex": _equal_cost_union,
    "ucmp_weights_and_max_paths_2": _ucmp_max_paths_2,
    "empty_bitmask_row": _empty_bitmask_row,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_derive_routes_equals_the_per_vertex_decode(case):
    (st, res, lsdb), max_paths = CASES[case]()
    want = per_vertex_derive_routes(st, res, lsdb, NOW, AREA, max_paths)
    got = derive_routes(st, res, lsdb, NOW, AREA, max_paths=max_paths)
    assert want, "the case derives nothing"
    # dataclass equality: prefix, dist, nexthops, area, vertex, weights
    assert list(got.items()) == list(want.items())
    assert all(type(r.dist) is int for r in got.values())


def test_cases_hold_what_they_are_named_for():
    """The oracle's routes show each case's feature, so that a case
    cannot pass by deriving something else."""
    def routes(case):
        (st, res, lsdb), max_paths = CASES[case]()
        return per_vertex_derive_routes(st, res, lsdb, NOW, AREA, max_paths)

    def net(prefix):
        return IPv4Network(prefix)

    two = routes("two_words_a_bit_in_each")
    assert {nh.ifname for nh in two[net("10.0.2.0/24")].nexthops} == {
        "e2", "e39"
    }
    assert {nh.ifname for nh in two[net("10.0.1.0/24")].nexthops} == {
        "e31", "e32", "e35"  # equal cost from vertices 1 and 3
    }
    vlink = routes("vlink_expand")
    assert {nh.ifname for nh in vlink[net("10.0.2.0/24")].nexthops} == {
        "e0", "e7", "e8"
    }
    gone = routes("unreachable_missing_and_aged_out")
    assert set(gone) == {
        net("10.0.0.0/24"), net("10.0.1.0/24"), net("10.0.5.0/24"),
    }
    transit = routes("transit_networks")
    tie = transit[net("172.16.1.0/24")]  # router 1's stub, then network 3
    assert (tie.dist, tie.vertex) == (8, 1)
    assert {nh.ifname for nh in tie.nexthops} == {"e0", "e2"}
    assert transit[net("172.16.0.0/16")].vertex == 4
    union = routes("equal_cost_union_keeps_first_vertex")[net("10.9.0.0/24")]
    assert (union.dist, union.vertex) == (5, 1)
    assert {nh.ifname for nh in union.nexthops} == {"e0", "e2", "e3"}
    ucmp = routes("ucmp_weights_and_max_paths_2")
    merged = ucmp[net("10.9.0.0/24")]
    assert len(merged.nexthops) == 2 and set(merged.nh_weights) == set(
        merged.nexthops
    )
    assert sorted(merged.nh_weights.values()) == [3, 5]
    assert sorted(ucmp[net("10.7.0.0/24")].nh_weights.values()) == [1, 4]
    empty = routes("empty_bitmask_row")
    assert empty[net("10.0.1.0/24")].nexthops == frozenset()


@pytest.mark.parametrize("n_atoms", [1, 2, 12, 32, 33, 64])
def test_atom_bits_equals_the_scalar_expression(n_atoms):
    rng = np.random.default_rng(n_atoms)
    # one word more than the atoms need, bits set beyond n_atoms: both
    # formulations must ignore them
    width = -(-n_atoms // 32) + 1
    rows = rng.integers(0, 1 << 32, (200, width), dtype=np.uint64).astype(
        np.uint32
    )
    rows[0] = 0
    rows[1] = 0xFFFFFFFF
    for row in rows:
        got = atom_bits(row, n_atoms)
        assert got == scalar_atom_bits(row, n_atoms)
        assert all(type(a) is int for a in got)
    exact = rows[:, : -(-n_atoms // 32)]
    assert atom_bits(exact[1], n_atoms) == list(range(n_atoms))


FLAG_CASES = {
    # the MaxAge copy's flags are not served; the router is still listed
    "maxage_router_lsa": dict(
        routers=[[_p2p(1)], ([_p2p(0)], MAX_AGE, NOW, RouterFlags.B)],
        dist=[0, 4],
    ),
    "unreachable_router": dict(
        routers=[[_p2p(1)], ([_p2p(0)], 0, NOW, RouterFlags.E),
                 ([_p2p(0)], 0, NOW, RouterFlags.B)],
        dist=[0, INF, 2],
    ),
    "router_without_an_lsa": dict(
        routers=[[_p2p(1)], None, ([_p2p(0)], 0, NOW - 2 * MAX_AGE,
                                   RouterFlags.B | RouterFlags.E)],
        dist=[0, 1, 1],
    ),
}


@pytest.mark.parametrize("case", sorted(FLAG_CASES))
def test_reachable_router_flags_equals_the_comprehension(case):
    spec = FLAG_CASES[case]
    st, res, lsdb = _area(
        spec["routers"], atoms=_atoms(1), dist=spec["dist"],
        masks=[0] * len(spec["dist"]),
    )
    want = comprehension_router_flags(st, res, lsdb)
    got = reachable_router_flags(st, res, lsdb)
    assert list(got.items()) == list(want.items())
    assert want == {
        "maxage_router_lsa": {_rid(0): RouterFlags(0), _rid(1): RouterFlags(0)},
        "unreachable_router": {_rid(0): RouterFlags(0), _rid(2): RouterFlags.B},
        # aged by the clock, not MaxAge in the header: its flags are served
        "router_without_an_lsa": {
            _rid(0): RouterFlags(0), _rid(1): RouterFlags(0),
            _rid(2): RouterFlags.B | RouterFlags.E,
        },
    }[case]


# -- the counter, and the metric that reads it


def _moved(before, after, path, family=FAMILY):
    key = f"{family}{{path={path}}}"
    return after.get(key, 0) - before.get(key, 0)


def test_counter_counts_distinct_rows_decoded_and_offers_reused():
    (st, res, lsdb), _ = _twelve_atoms()
    want = per_vertex_derive_routes(st, res, lsdb, NOW, AREA)
    # offers and the distinct rows among offering vertices, from the LSDB
    offers, rows = 0, set()
    for rid, v in st.router_index.items():
        body = lsdb.entries[LsaKey(LsaType.ROUTER, rid, rid)].lsa.body
        stubs = sum(
            link.link_type == RouterLinkType.STUB_NETWORK
            for link in body.links
        )
        if stubs:
            offers += stubs
            rows.add(res.nexthop_words[v].tobytes())
    for v in st.network_index.values():
        offers += 1
        rows.add(res.nexthop_words[v].tobytes())
    all_rows = {row.tobytes() for row in res.nexthop_words}
    before = telemetry.snapshot(FAMILY)
    assert derive_routes(st, res, lsdb, NOW, AREA) == want
    after = telemetry.snapshot(FAMILY)
    assert _moved(before, after, "decoded") == len(rows) <= len(all_rows) == 9
    assert _moved(before, after, "decoded") + _moved(
        before, after, "reused"
    ) == offers > len(want)


def test_vertices_that_offer_nothing_decode_nothing():
    # three routers with no stub link, a network without its LSA, an
    # unreachable router: five distinct rows, none decoded
    st, res, lsdb = _area(
        [[_p2p(1)], [_p2p(2)], [_p2p(0)], [_stub("10.0.3.0/24")]], [None],
        atoms=_atoms(3), dist=[0, 1, 2, INF, 3], masks=[1, 2, 3, 4, 5],
    )
    before = telemetry.snapshot(FAMILY)
    assert derive_routes(st, res, lsdb, NOW, AREA) == {}
    after = telemetry.snapshot(FAMILY)
    assert _moved(before, after, "decoded") == 0
    assert _moved(before, after, "reused") == 0


def test_metric_file_reads_the_decoded_share_of_the_counter():
    spec = json.loads(
        (REPO / "benchmark/layer_metrics/storm_derive_decode_share.json")
        .read_text()
    )
    assert spec["reader"] == "counter_ratio"
    assert spec["args"] == {
        "family": FAMILY, "label": "path=decoded", "of": {"family": FAMILY},
    }
    assert (spec["unit"], spec["better"], spec["layer"], spec["source"],
            spec["moves"]) == (
        "%", "lower", "readback + routes", "program_counter",
        "trigger_fib_p50_ms",
    )
    top = json.loads((REPO / "BENCHMARK.json").read_text())
    [entry] = [
        m for m in top["per_layer"] if m["name"] == "storm_derive_decode_share"
    ]
    assert entry == {
        "name": "storm_derive_decode_share", "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "readback + routes",
        "moves": "trigger_fib_p50_ms",
        # the two OSPFv2 storm cells; later cells are appended (PR 31)
        "workloads": [
            "backbone10k-flapstorm", "isp-zoo-storm", *entry["workloads"][2:]
        ],
    }


@pytest.mark.parametrize("cell", ["tiny-storm", "tiny-ispstorm"])
def test_traced_storm_rehearsal_reads_the_decode_share(cell):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO))
    env.pop("PYTHONHASHSEED", None)
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", cell,
         "--seed", "2147483659", "--seconds", "2", "--trace", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 3, proc.stderr[-2000:]
    report = json.loads(proc.stderr.strip().splitlines()[-1])
    assert "storm_derive_decode_share" in report["counts"]["metrics_read"]
    assert report["metrics"] == {} and report["failed"] == 0


# == ISSUE 32: the plan ===================================================

# -- the oracle: both functions as they stood before ISSUE 32, verbatim
# -- but for the names and the counter


def walked_router_flags(st, res, lsdb):
    flags = {
        key.adv_rtr: e.lsa.body.flags
        for key, e in lsdb.entries.items()
        if key.type == LsaType.ROUTER and not e.lsa.is_maxage
    }
    dist = res.dist.tolist()
    no_flags = RouterFlags(0)
    return {
        rid: flags.get(rid, no_flags)
        for rid, v in st.router_index.items()
        if dist[v] < INF
    }


def walked_derive_routes(st, res, lsdb, now, area_id, max_paths=None):
    routes = {}

    def offer(prefix, dist, nhs, vertex=-1, weights=None):
        cur = routes.get(prefix)
        if cur is None or dist < cur.dist:
            routes[prefix] = IntraRoute(
                prefix, dist, nhs, area_id, vertex=vertex,
                nh_weights=dict(weights) if weights else None,
            )
        elif dist == cur.dist:
            merged = None
            if cur.nh_weights or weights:
                merged = dict(cur.nh_weights or {})
                for nh, w in (weights or {}).items():
                    merged[nh] = merged.get(nh, 0) + w
            routes[prefix] = IntraRoute(
                prefix, dist, cur.nexthops | nhs, area_id,
                vertex=cur.vertex, nh_weights=merged,
            )

    inv_net = {i: a for a, i in st.network_index.items()}
    inv_rtr = {i: r for r, i in st.router_index.items()}
    nlsa = {}
    rlsa = {}
    for e in lsdb.all():
        if e.current_age(now) >= 3600:
            continue
        if e.lsa.type == LsaType.NETWORK:
            nlsa[e.lsa.lsid] = e.lsa.body
        elif e.lsa.type == LsaType.ROUTER:
            rlsa[e.lsa.adv_rtr] = e.lsa.body

    nhw = getattr(res, "nh_weights", None)
    dist = res.dist.tolist()
    words = res.nexthop_words
    stride = words.shape[1] * words.itemsize
    rows = words.tobytes()
    decoded = {}
    for v in range(st.topo.n_vertices):
        if dist[v] >= INF:
            continue
        net = inv_net.get(v)
        if net is not None:
            body = nlsa.get(net)
            if body is None:
                continue
            offered = [(apply_mask(net, body.mask), dist[v])]
        else:
            body = rlsa.get(inv_rtr[v])
            if body is None:
                continue
            offered = [
                (apply_mask(link.id, link.data), dist[v] + link.metric)
                for link in body.links
                if link.link_type == RouterLinkType.STUB_NETWORK
            ]
        if not offered:
            continue
        row = rows[v * stride:(v + 1) * stride]
        nhs = decoded.get(row)
        if nhs is None:
            nhs = decoded[row] = _atoms_of(words[v], st.atoms)
        weights = (
            _atom_weights_of(words[v], nhw[v], st.atoms)
            if nhw is not None
            else None
        )
        for prefix, cost in offered:
            offer(prefix, cost, nhs, vertex=v, weights=weights)
    clamp_multipath(routes, max_paths)
    return routes


def _same_routes(got: dict, want: dict) -> None:
    """Route for route and in the dict's order: prefix, dist, nexthops,
    area, vertex, nh_weights (dataclass equality), costs Python ints."""
    assert list(got) == list(want)
    for prefix, route in want.items():
        assert got[prefix] == route, prefix
        assert type(got[prefix].dist) is int
        assert got[prefix].vertex == route.vertex


class Checked:
    """Both functions, each call held to its oracle: what the instance
    is given to call in the storm cases, and what the area cases call."""

    def __init__(self):
        self.paths = Counter()
        self.routes = self.flag_calls = self.unreached = 0
        self.weighted = self.merged = 0

    def flags(self, st, res, lsdb):
        got = reachable_router_flags(st, res, lsdb)
        want = walked_router_flags(st, res, lsdb)
        assert list(got.items()) == list(want.items())
        self.flag_calls += 1
        self.unreached += len(st.router_index) - len(want)
        return got

    def derive(self, st, res, lsdb, now, area_id, max_paths=None):
        before = telemetry.snapshot(CALLS)
        got = derive_routes(st, res, lsdb, now, area_id, max_paths=max_paths)
        after = telemetry.snapshot(CALLS)
        for path in ("planned", "walked"):
            self.paths[path] += int(_moved(before, after, path, CALLS))
        want = walked_derive_routes(st, res, lsdb, now, area_id, max_paths)
        _same_routes(got, want)
        self.routes += len(want)
        self.weighted += sum(r.nh_weights is not None for r in want.values())
        self.merged += sum(len(r.nexthops) > 1 for r in want.values())
        return got


CALLS = "holo_ospf_derive_calls_total"


# -- the storms: every SPF run of the instance, checked as it happens


def _storm_own(max_paths=None):
    from holo_tpu.spf.synth_storm import run_convergence_storm

    report, _digest, net = run_convergence_storm(
        n_routers=300, events=200, seed=32, max_paths=max_paths,
        spf_backend=ScalarSpfBackend(),
    )
    assert report["outcomes"]["converged"] > 100
    return net


def _storm_heavy_degree():
    """200 seeded events on the benchmark's heavy-degree rehearsal
    network: flaps, shared-risk cuts, lost routers whose LSA stays, and
    the loss of a hub at the port cap."""
    from benchmark.popnet import PopNet
    from holo_tpu.telemetry import convergence

    config = json.loads(
        (REPO / "benchmark/configs/tiny-isp.json").read_text()
    )
    net = PopNet(
        config["lsdb"], ScalarSpfBackend(), config["spf_delay"], 5.0
    )
    convergence.configure(4096, clock=net.loop.clock.now)
    try:
        net.loop.advance(30.0)
        rng = np.random.default_rng(32)
        degree = net.graph.degrees()
        hub = max(net.losable["core"], key=lambda i: degree[i])
        assert degree[hub] == config["lsdb"]["port_cap"]
        lost_routers = 0
        for ev in range(200):
            roll, lost = rng.random(), bool(rng.random() < 0.1)
            if ev in (40, 41, 120, 121):
                net.node(hub, lost=False)
            elif roll < 0.6:
                net.flap(
                    net.flappable[int(rng.integers(len(net.flappable)))],
                    lost=lost,
                )
            elif roll < 0.7:
                net.srlg(int(rng.integers(len(net.graph.srlgs))), lost=lost)
            elif roll < 0.85:
                pool = net.losable["access"] + net.losable["core"]
                down = [r for r in net.node_down if r != hub]
                if len(down) >= 3:
                    router = down[0]
                else:
                    router = pool[int(rng.integers(len(pool)))]
                if router != hub:
                    lost_routers += router not in net.node_down
                    net.node(router, lost=lost)
            else:
                net.ifconfig_metric()
            net.loop.advance(float(rng.uniform(0.05, 1.5)))
        net.loop.advance(60.0)
        assert lost_routers > 5
    finally:
        convergence.configure(0)
    return net


STORMS = {
    "storm-network-200-events": _storm_own,
    "storm-network-max-paths-2": lambda: _storm_own(max_paths=2),
    "heavy-degree-network-200-events": _storm_heavy_degree,
}


@pytest.mark.parametrize("case", sorted(STORMS))
def test_planned_equals_walked_in_every_run_of_a_storm(case, monkeypatch):
    from holo_tpu.protocols.ospf import instance

    check = Checked()
    monkeypatch.setattr(instance, "derive_routes", check.derive)
    monkeypatch.setattr(instance, "reachable_router_flags", check.flags)
    net = STORMS[case]()
    runs = net.inst.spf_run_count
    assert runs >= 25
    # every run was served from the plan of the area's kept lowering,
    # and the storm is not a trivial one
    assert +check.paths == {"planned": check.flag_calls}
    assert check.flag_calls >= runs
    assert check.routes > 10 * runs
    assert check.unreached > 0
    if case == "storm-network-max-paths-2":
        assert check.weighted > 0 and check.merged > 0


# -- an area that events are driven through, with offers of every kind


class OfferingArea(tbt.Area):
    """``test_build_topology``'s area (ring, chords, parallel and
    unnumbered links, a virtual link, a transit network the root is on
    and one it is not, a stub behind every third router, the root among
    them) with flags on its routers, and stubs added by the cases."""

    flags_now: dict = {}  # a case's own: router -> flags

    def install(self, i: int, age: int = 0) -> None:
        self.seq += 1
        flags = RouterFlags(0)
        if i % 3 == 1:
            flags |= RouterFlags.B
        if i % 4 == 2:
            flags |= RouterFlags.E
        flags = self.flags_now.get(i, flags)
        self.lsdb.install(Lsa(
            age, Options.E, LsaType.ROUTER, tbt._rid(i), tbt._rid(i),
            self.seq, LsaRouter(flags, list(self.links[i])),
        ), self.now)

    def stub(self, i: int, prefix: str, metric: int) -> None:
        self.links[i].append(_stub(prefix, metric))
        self.install(i)


class Runs:
    """One area, its kept lowering, and a check after every step: the
    kept lowering's plan, a fresh lowering's plan and no plan at all
    give the oracle's routes and flags."""

    def __init__(self, seed: int, max_paths=None, multipath_k: int = 1):
        self.area = OfferingArea(seed)
        self.kept = LoweredLsdb()
        self.check = Checked()
        self.max_paths, self.k = max_paths, multipath_k
        self.last = None

    def run(self):
        area, args = self.area, self.area.args()
        st = self.kept.build_topology(area.lsdb, **args)
        fresh = build_topology(area.lsdb, **args)
        if st is None:
            assert fresh is None
            self.last = None
            return None
        assert st.plan is not None and fresh.plan is not None
        res = ScalarSpfBackend().compute(st.topo, multipath_k=self.k)
        bare = dataclasses.replace(st, plan=None)
        for topo in (st, fresh, bare):
            self.flags = self.check.flags(topo, res, area.lsdb)
            routes = self.check.derive(
                topo, res, area.lsdb, area.now, AREA, self.max_paths
            )
        self.last = (st, res, routes)
        return routes


def _net(prefix):
    return IPv4Network(prefix)


def _case_transit_with_a_dr(r: Runs):
    # network 0 has the root on it, network 1 does not; both offer their
    # own prefix at the network vertex's distance
    routes = r.run()
    for j in (0, 1):
        net = apply_mask(tbt._dr(j), IPv4Address("255.255.255.0"))
        assert routes[net].vertex == r.last[0].network_index[tbt._dr(j)]
    assert routes[apply_mask(tbt._dr(0), IPv4Address("255.255.255.0"))].dist == 3
    tbt.flush_network(r.area)  # one of them leaves
    assert len(r.run()) == len(routes) - 1
    r.area.install_net(0)
    r.area.install_net(1)
    assert list(r.run()) == list(routes)


def _case_one_prefix_equal_cost(r: Runs):
    r.run()
    st, res, _ = r.last
    dist = res.dist
    a, b = 4, 9
    va, vb = st.router_index[tbt._rid(a)], st.router_index[tbt._rid(b)]
    top = int(max(dist[va], dist[vb])) + 5
    r.area.stub(a, "10.77.0.0/24", top - int(dist[va]))
    r.area.stub(b, "10.77.0.0/24", top - int(dist[vb]))
    route = r.run()[_net("10.77.0.0/24")]
    st, res, _ = r.last
    assert route.dist == top and route.vertex == min(va, vb)
    assert route.nexthops == _atoms_of(
        res.nexthop_words[va], st.atoms
    ) | _atoms_of(res.nexthop_words[vb], st.atoms)


def _case_one_prefix_unequal_cost(r: Runs):
    r.run()
    st, res, _ = r.last
    a, b = 4, 9
    va, vb = st.router_index[tbt._rid(a)], st.router_index[tbt._rid(b)]
    r.area.stub(a, "10.78.0.0/24", 1)
    r.area.stub(b, "10.78.0.0/24", 1 + abs(int(res.dist[va] - res.dist[vb])) + 3)
    route = r.run()[_net("10.78.0.0/24")]
    assert (route.dist, route.vertex) == (int(res.dist[va]) + 1, va)
    # the dearer offer first in vertex order, then the cheaper one
    r.area.stub(11, "10.79.0.0/24", 40)
    r.area.stub(12, "10.79.0.0/24", 1)
    r.run()
    st, res, routes = r.last
    v = st.router_index[tbt._rid(12)]
    assert routes[_net("10.79.0.0/24")].vertex == v


def _case_root_with_its_own_stubs(r: Runs):
    r.area.stub(tbt.ROOT, "10.80.0.0/24", 7)
    routes = r.run()
    own = routes[_net("10.80.0.0/24")]
    assert (own.dist, own.nexthops) == (7, frozenset())
    assert own.vertex == r.last[0].topo.root
    assert routes[_net("10.1.0.0/24")].nexthops == frozenset()  # the area's


def _case_unreachable_vertices(r: Runs):
    whole = r.run()
    # router 9 and whatever hangs behind it alone lose every link back
    for i in range(1, r.area.n):
        r.area.links[i] = [
            l for l in r.area.links[i]
            if not (l.link_type == RouterLinkType.POINT_TO_POINT
                    and l.id == tbt._rid(9))
        ]
        r.area.install(i)
    seen = r.check.unreached
    routes = r.run()
    assert r.check.unreached > seen
    assert _net("10.1.9.0/24") in whole and _net("10.1.9.0/24") not in routes


def _case_age_3599_and_3600_at_now(r: Runs):
    a = r.area
    a.links[9].append(_stub("10.81.0.0/24", 1))
    a.install(9, age=3500)
    a.install_net(1, age=3500)
    net1 = apply_mask(tbt._dr(1), IPv4Address("255.255.255.0"))
    a.now += 99.0  # age 3599
    routes = r.run()
    assert _net("10.81.0.0/24") in routes and net1 in routes
    a.now += 0.999  # 3599.999: current_age is still 3599
    assert _net("10.81.0.0/24") in r.run()
    a.now += 0.001  # 3600 on the second, and nothing was installed
    routes = r.run()
    assert _net("10.81.0.0/24") not in routes and net1 not in routes
    assert tbt._rid(9) not in r.last[0].router_index
    # a topology lowered at another second is not this second's plan
    st, res, _ = r.last
    before = telemetry.snapshot(CALLS)
    got = derive_routes(st, res, a.lsdb, a.now - 50.0, AREA)
    after = telemetry.snapshot(CALLS)
    assert _moved(before, after, "walked", CALLS) == 1
    _same_routes(got, walked_derive_routes(st, res, a.lsdb, a.now - 50.0, AREA))


def _case_maxage_lsa_still_in_the_lsdb(r: Runs):
    a = r.area
    routes = r.run()
    assert _net("10.1.6.0/24") in routes
    a.flush(a.router_key(6))  # the MaxAge copy stays in the LSDB
    assert a.lsdb.get(a.router_key(6)).lsa.is_maxage
    routes = r.run()
    assert _net("10.1.6.0/24") not in routes
    a.install(6)
    assert _net("10.1.6.0/24") in r.run()


def _case_flags_change_between_runs(r: Runs):
    a = r.area
    r.run()
    assert r.flags[tbt._rid(6)] == RouterFlags.E
    assert r.flags[tbt._rid(4)] == RouterFlags.B
    # the same routers, the same reached set: only a body's flags moved
    a.flags_now = {6: RouterFlags.B | RouterFlags.E, 4: RouterFlags(0)}
    a.install(6)
    r.run()
    assert r.flags[tbt._rid(6)] == RouterFlags.B | RouterFlags.E
    assert r.flags[tbt._rid(4)] == RouterFlags.B  # not installed yet
    a.install(4)
    r.run()
    assert r.flags[tbt._rid(4)] == RouterFlags(0)
    first = r.kept.build_topology(a.lsdb, **a.args())
    again = r.kept.build_topology(a.lsdb, **a.args())
    res = r.last[1]
    # nothing moved: the dict is the last run's object
    assert reachable_router_flags(again, res, a.lsdb) is (
        reachable_router_flags(first, res, a.lsdb)
    )


def _case_mask_no_network_has(r: Runs):
    """Nothing checks a mask off the wire.  One that leaves host bits
    set raises in ``derive_routes`` when a run reaches its vertex, with
    a plan as without, and costs nothing while no run does."""
    a = r.area
    bad = RouterLink(
        RouterLinkType.STUB_NETWORK, IPv4Address("10.84.3.7"),
        IPv4Address("255.0.255.0"), 1,
    )
    a.links[9].append(bad)
    a.install(9)
    st = r.kept.build_topology(a.lsdb, **a.args())  # lowers it: no error
    res = ScalarSpfBackend().compute(st.topo)
    for topo in (st, dataclasses.replace(st, plan=None)):
        with pytest.raises(ValueError, match="host bits"):
            derive_routes(topo, res, a.lsdb, a.now, AREA)
    with pytest.raises(ValueError, match="host bits"):
        walked_derive_routes(st, res, a.lsdb, a.now, AREA)
    # out of reach, it is not looked at
    a.links[9] = [bad]
    a.install(9)
    routes = r.run()
    assert tbt._rid(9) not in r.flags and routes


def _case_vertex_set_grows_and_shrinks(r: Runs):
    a = r.area
    sizes = [len(r.run())]
    for _ in range(3):
        tbt.new_router(a)
        a.stub(a.n - 1, f"10.82.{a.n}.0/24", 2)
        sizes.append(len(r.run()))
    assert sizes == [sizes[0] + k for k in range(4)]
    for i in (a.n - 1, a.n - 2):
        a.lsdb.remove(a.router_key(i))
        sizes.append(len(r.run()))
    a.flush(a.router_key(a.n - 3))
    sizes.append(len(r.run()))
    assert sizes[-3:] == [sizes[0] + 2, sizes[0] + 1, sizes[0]]
    a.lsdb.entries.clear()
    assert r.run() is None
    for i in range(a.n - 3):
        a.install(i)
    assert len(r.run()) >= 5


def _case_chain_of_50_runs(r: Runs):
    """Fifty runs on one lowering, a seeded event before each; every
    run is also held to a fresh lowering (``Runs.run``)."""
    events = (*tbt.BACKGROUND, tbt.new_router)
    derived = 0
    for _ in range(50):
        events[int(r.area.rng.integers(len(events)))](r.area)
        derived += r.run() is not None
    assert derived >= 45 and r.kept.entries


def _case_max_paths(r: Runs):
    a = r.area
    # equal costs all round the ring and its chords, and a stub behind
    # every router: real ECMP sets (the parallel links to router 1 at
    # the least)
    for i in range(a.n):
        a.links[i] = [
            RouterLink(l.link_type, l.id, l.data, 1)
            if l.link_type == RouterLinkType.POINT_TO_POINT else l
            for l in a.links[i]
        ] + [_stub(f"10.83.{i}.0/24", 1)]
        a.install(i)
    routes = r.run()
    assert r.check.weighted > 0
    widest = max(len(x.nexthops) for x in routes.values())
    assert widest <= (r.max_paths or 99)
    if r.max_paths == 1:
        assert widest == 1
    else:
        assert widest > 1
    for route in routes.values():
        if route.nh_weights is not None:
            assert set(route.nh_weights) == set(route.nexthops)


AREA_CASES = {
    "transit-networks-with-a-dr": (_case_transit_with_a_dr, {}),
    "one-prefix-from-two-routers-at-equal-cost": (
        _case_one_prefix_equal_cost, {}),
    "one-prefix-from-two-routers-at-unequal-cost": (
        _case_one_prefix_unequal_cost, {}),
    "root-with-its-own-stubs": (_case_root_with_its_own_stubs, {}),
    "unreachable-vertices": (_case_unreachable_vertices, {}),
    "lsa-at-age-3599-and-3600-at-now": (_case_age_3599_and_3600_at_now, {}),
    "maxage-lsa-still-in-the-lsdb": (_case_maxage_lsa_still_in_the_lsdb, {}),
    "max-paths-1-with-multipath-weights": (
        _case_max_paths, dict(max_paths=1, multipath_k=4)),
    "max-paths-4-with-multipath-weights": (
        _case_max_paths, dict(max_paths=4, multipath_k=4)),
    "vertex-set-grows-and-shrinks": (_case_vertex_set_grows_and_shrinks, {}),
    "mask-that-no-network-has": (_case_mask_no_network_has, {}),
    "router-flags-change-between-runs": (
        _case_flags_change_between_runs, {}),
    "chain-of-50-runs-on-one-lowering": (_case_chain_of_50_runs, {}),
}


@pytest.mark.parametrize("case", sorted(AREA_CASES))
def test_planned_equals_walked_on_an_area(case):
    steps, options = AREA_CASES[case]
    for seed in (1, 2, 3):
        runs = Runs(seed, **options)
        steps(runs)
        # two of three topologies of every run carried a plan
        paths = runs.check.paths
        assert paths["planned"] == 2 * paths["walked"] > 0


# -- what the planned derive does not do: counting stand-ins, no timing


def _ring(n: int, stubs_at: dict):
    """A ring of ``n`` routers, router 0 the root; ``stubs_at``: router
    -> prefix.  Returns the area (an ``OfferingArea`` emptied and built
    again as a ring) ready for ``build_topology``."""
    area = OfferingArea.__new__(OfferingArea)
    area.rng = np.random.default_rng(n)
    area.lsdb, area.now, area.seq, area.n = Lsdb(), 1000.0, 0, n
    area.links = {i: [] for i in range(n)}
    area.nets = {}
    for i in range(n):
        area.connect(i, (i + 1) % n)
    for i, prefix in stubs_at.items():
        area.links[i].append(_stub(prefix, 1))
    for i in range(n):
        area.install(i)
    return area


class _Calls:
    """Counts calls of ``IPv4Address.__hash__`` and ``Lsdb.all`` while
    ``on``; both still do what they did."""

    def __init__(self, monkeypatch):
        self.on = False
        self.hashes = self.alls = 0
        real_hash, real_all = IPv4Address.__hash__, Lsdb.all

        def counting_hash(addr):
            self.hashes += self.on
            return real_hash(addr)

        def counting_all(lsdb):
            self.alls += self.on
            return real_all(lsdb)

        monkeypatch.setattr(IPv4Address, "__hash__", counting_hash)
        monkeypatch.setattr(Lsdb, "all", counting_all)


def _derive_stage(area, kept, calls: _Calls, plan: bool = True):
    """One SPF run's ``derive`` stage as the instance runs it, counted."""
    st = kept.build_topology(area.lsdb, **area.args())
    if not plan:
        st = dataclasses.replace(st, plan=None)
    res = ScalarSpfBackend().compute(st.topo)
    calls.hashes = calls.alls = 0
    calls.on = True
    try:
        flags = reachable_router_flags(st, res, area.lsdb)
        routes = derive_routes(st, res, area.lsdb, area.now, AREA)
    finally:
        calls.on = False
    return flags, routes, calls.hashes, calls.alls


def test_a_run_on_a_kept_lowering_walks_no_lsdb_and_hashes_no_router_id(
    monkeypatch,
):
    calls = _Calls(monkeypatch)
    counted = {}
    for n in (40, 400):
        # the same eight prefixes, behind the root's eight nearest
        stubs = {
            i % n: f"10.90.{k}.0/24"
            for k, i in enumerate((1, 2, 3, 4, -1, -2, -3, -4))
        }
        area, kept = _ring(n, stubs), LoweredLsdb()
        _derive_stage(area, kept, calls)  # the area's first run
        # a flap far from the root, and the run after it
        far = n // 2
        for i in (far, far + 1):
            area.links[i] = [
                l for l in area.links[i]
                if l.id not in (tbt._rid(far), tbt._rid(far + 1))
            ]
            area.install(i)
        before = telemetry.snapshot(CALLS)
        flags, routes, hashes, alls = _derive_stage(area, kept, calls)
        after = telemetry.snapshot(CALLS)
        assert _moved(before, after, "planned", CALLS) == 1
        assert _moved(before, after, "walked", CALLS) == 0
        assert alls == 0
        assert len(flags) == n and len(routes) == 8
        counted[n] = hashes
        # ... and the same run with no plan walks the LSDB and hashes
        # by the router
        before = after
        w_flags, w_routes, w_hashes, w_alls = _derive_stage(
            area, kept, calls, plan=False
        )
        after = telemetry.snapshot(CALLS)
        assert _moved(before, after, "walked", CALLS) == 1
        assert _moved(before, after, "planned", CALLS) == 0
        assert w_alls == 1 and w_hashes > 4 * n
        assert list(w_flags.items()) == list(flags.items())
        _same_routes(routes, w_routes)
    # the LSDB grew tenfold with the offered prefixes held: the hashes
    # left (the next hops of the two rows decoded) did not
    assert counted[400] == counted[40] <= 8
