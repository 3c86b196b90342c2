"""``derive_routes``, ``atom_bits`` and ``reachable_router_flags`` against
the formulations they replaced (ISSUE 28).

The derive stage used to decode a next-hop set for every reachable
vertex, one NumPy scalar operation per atom; it now decodes once per
distinct bitmask row, and only for vertices that offer a prefix.  The
old bodies are kept here as the oracle: the routes must come out with
the same keys in the same insertion order (the FIB digest and the RIB's
publish order hang on it) and equal in every field.  No case reads a
clock.
"""

import json
import os
import subprocess
import sys
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
    NexthopAtom,
    RouteNexthop,
    SpfTopology,
    atom_bits,
    clamp_multipath,
    derive_routes,
    reachable_router_flags,
)
from holo_tpu.spf.backend import SpfResult
from holo_tpu.utils.ip import apply_mask

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


def _moved(before, after, path):
    key = f"{FAMILY}{{path={path}}}"
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
