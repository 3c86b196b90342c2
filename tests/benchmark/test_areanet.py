"""The OSPFv3 multi-area deployment (``benchmark/areanet.py``), its
plain reference (``benchmark/v3ref.py``) and its cell's rehearsal."""

import json
import os
import subprocess
import sys
from ipaddress import IPv4Address
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmark import v3ref
from benchmark.areanet import BACKBONE, AreaNet, build_layout, pod_range
from benchmark.readers import counter_per
from holo_tpu.spf.backend import ScalarSpfBackend, TpuSpfBackend

REPO = Path(__file__).resolve().parents[2]


def _config(name: str) -> dict:
    return json.loads((REPO / f"benchmark/configs/{name}.json").read_text())


def _net(name: str = "tiny-v3areas", backend=None) -> AreaNet:
    config = _config(name)
    return AreaNet(
        config["lsdb"], backend or ScalarSpfBackend(), config["spf_delay"],
        5.0, max_paths=config["max_paths"],
    )


def test_published_size_is_what_the_file_states():
    """The configuration file's ``generated`` block, held to the
    generator for the file's seeds: routers, links and directed edges
    per area, prefixes, Inter-Area-Prefix LSAs held and originated, the
    device's neighbours per area, the RIB."""
    config = _config("ospfv3-multiarea-10k")
    stated = config["generated"]
    net = _net("ospfv3-multiarea-10k")
    got = net.sizes()
    for key, value in got.items():
        assert stated[key] == value, key
    assert got["routers"] == 9660 and got["directed_edges"] == 340792
    assert got["dut_interfaces"] == 92 and got["rib_routes"] == 13588
    for hall in ("1", "2", "3", "4"):
        assert got["areas"][hall] == {
            "routers": 2420, "links": 42592, "directed_edges": 85184,
            "atoms": 22, "root": 0, "intra_area_prefix_lsas": 2413,
            "inter_area_prefix_lsas_held": 1617,
            "inter_area_prefix_lsas_originated": 232, "dut_neighbours": 22,
        }
    assert got["areas"]["0"]["routers"] == 12
    assert got["areas"]["0"]["links"] == 28
    assert got["areas"]["0"]["dut_neighbours"] == 4
    assert config["reduced"] == [] and config["max_paths"] is None
    # the 88 remote ranges are the inter-area routes of the FIB, and the
    # next-hop sets are as wide as the file says
    table = net.fib_table()
    remote = {p for (a, _ar, p) in net.layout.summaries
              if a in net.layout.remote_abrs}
    assert len(remote) == 88 and remote <= set(table)
    widths = {}
    for _cost, hops in table.values():
        widths[str(len(hops))] = widths.get(str(len(hops)), 0) + 1
    assert widths == stated["next_hop_set_sizes"]
    assert stated["inter_area_routes_in_fib"] == 88
    # and at that size the instance agrees with the plain reference
    assert table == v3ref.routes(net.model())


def test_same_seeds_same_graph_other_cost_seed_other_costs():
    lsdb = _config("tiny-v3areas")["lsdb"]
    a, b = build_layout(lsdb), build_layout(lsdb)
    assert a.adj == b.adj and a.summaries == b.summaries
    c = build_layout(dict(lsdb, cost_seed=lsdb["cost_seed"] + 1))
    assert {h: {u: set(p) for u, p in c.adj[h].items()} for h in c.adj} == {
        h: {u: set(p) for u, p in a.adj[h].items()} for h in a.adj
    }
    assert c.adj != a.adj


def test_every_hall_is_the_fat_tree_with_the_border_routers_in_pod_zero():
    lay = build_layout(_config("tiny-v3areas")["lsdb"])
    k, half = lay.k, lay.k // 2
    for hall in lay.halls:
        adj = lay.adj[hall]
        assert len(adj) == 5 * k * k // 4
        assert lay.links(hall) == k * half * half * 2
        for border in lay.borders:
            assert set(adj[border]) == set(lay.pod0_aggs[hall])
            assert set(adj[border].values()) == {10}
        for u, peers in adj.items():  # two-way, per-direction costs
            for v in peers:
                assert u in adj[v]
    assert set(lay.adj[BACKBONE]) == set(
        lay.borders + lay.wan + lay.remote_abrs
    )
    # every prefix of a hall lies under one of its pod ranges
    for hall in lay.halls:
        for _r, prefix, _m in lay.prefixes[hall]:
            assert sum(prefix.subnet_of(r) for r in lay.ranges[hall]) == 1
    assert pod_range(3, 2) in lay.ranges[3]


def test_device_is_vertex_zero_in_every_area_and_the_halls_share_a_shape():
    net = _net(backend=TpuSpfBackend())
    shapes = set()
    for aid, st in net.inst._spf_delta_bases.items():
        assert st.topo.root == 0 and st.keys[0] == ("R", net.inst.router_id)
        if int(aid) != BACKBONE:
            shapes.add((
                st.topo.n_vertices, st.topo.n_edges, len(st.atoms),
                net.inst.backend.prepare(st.topo).in_src.shape,
            ))
    assert len(shapes) == 1


def test_v3ref_imports_nothing_of_the_program():
    source = (REPO / "benchmark/v3ref.py").read_text()
    assert "holo_tpu" not in source.split('"""', 2)[2]
    assert "import heapq" in source


def test_reference_two_way_check_equal_costs_and_preferences():
    a, b, c, d, dut = 1, 2, 3, 4, 0
    hop = {a: ("e0", "fe80::a"), b: ("e1", "fe80::b")}
    adj = {
        dut: {a: 1, b: 1}, a: {dut: 1, c: 1}, b: {dut: 1, c: 1},
        c: {a: 1, b: 1, d: 1}, d: {},  # d does not list c: one-way
    }
    tree = v3ref.spf(adj, dut, hop)
    assert tree[c] == (2, frozenset(hop.values())) and d not in tree
    from ipaddress import IPv6Network as N

    model = {
        "dut": dut, "backbone": 0, "areas": {0: adj, 1: {dut: {}}},
        "first_hops": {0: hop},
        "prefixes": {0: [(c, N("2001:db8:1::/64"), 3), (dut, N("2001:db8::1/128"), 0)]},
        "ranges": {0: [N("2001:db8:1::/48")]},
        "summaries": [
            (a, N("2001:db8:9::/48"), 10), (b, N("2001:db8:9::/48"), 10),
            (a, N("2001:db8:8::/48"), 5), (b, N("2001:db8:8::/48"), 6),
            (a, N("2001:db8:1::/64"), 1),  # intra wins
            (a, N("2001:db8:1::/48"), 1),  # our own active range
            (d, N("2001:db8:7::/48"), 1),  # border router unreachable
            (dut, N("2001:db8:6::/48"), 1),  # our own
        ],
    }
    assert v3ref.routes(model) == {
        N("2001:db8:1::/64"): (5, frozenset(hop.values())),
        N("2001:db8:9::/48"): (11, frozenset(hop.values())),
        N("2001:db8:8::/48"): (6, frozenset({hop[a]})),
    }


def _rehearse(workload: str, trace: int, seed: int = 2147483653):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO))
    env.pop("PYTHONHASHSEED", None)
    return subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", workload,
         "--seed", str(seed), "--seconds", "2", "--trace", str(trace)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
    )


def test_traced_rehearsal_reads_every_storm_metric_and_counts_the_storm():
    proc = _rehearse("tiny-areastorm", 1)
    assert proc.returncode == 3, proc.stderr[-2000:]
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())
    report = json.loads(proc.stderr.strip().splitlines()[-1])
    assert report["correct"] is False and report["metrics"] == {}
    checks = report["checks"]
    assert checks["parity"] and checks["fallback_clean"]
    assert checks["no_compile_in_window"] and checks["nothing_failed"]
    counts = report["counts"]
    read = set(counts["metrics_read"])
    assert {
        "multiarea_spf_run_ms", "multiarea_topology_ms",
        "multiarea_delta_link_ms", "multiarea_derive_ms",
        "multiarea_interarea_ms", "multiarea_publish_ms",
        "multiarea_rib_apply_ms", "multiarea_hold_coalesce_ms",
        "multiarea_rib_fib_ms", "multiarea_dispatch_ms",
        "multiarea_local_repair_p50_ms", "multiarea_derive_decode_share",
        "multiarea_topology_relower_share",
        "storm_area_dispatches_per_run", "storm_partial_run_share",
        "storm_rib_delta_routes_mean", "window_compiles",
    } <= read
    # the OSPFv2 cells' names move another end-to-end metric
    assert not {"storm_spf_run_ms", "hold_coalesce_ms", "rib_fib_ms"} & read
    assert set(counts["injected_by_kind"]) == {
        "link", "node", "summary", "bfd", "carrier", "ifconfig",
    }
    assert set(counts["warmup_by_kind"]) == set(counts["injected_by_kind"])
    assert counts["spf_types"].get("instance=ospfv3-dut,type=full", 0) > 0
    assert counts["area_spf"]["disposition=reused"] > 0
    assert len(counts["dispatches_by_area"]) >= 4
    # every counted event is filed under the areas its run dispatched
    by_areas = counts["converged_by_areas"]
    assert sum(by_areas.values()) == counts["spf_path_converged"]
    assert set(by_areas) <= {"0", "1", "2", "3", "4", "5", "partial"}
    assert len(by_areas) >= 2
    assert counts["spf_path_converged"] > 0 and report["failed"] == 0


def test_other_storm_cells_do_not_read_the_ospfv3_metrics():
    proc = _rehearse("tiny-storm", 1, seed=7)
    assert proc.returncode == 3, proc.stderr[-2000:]
    read = json.loads(proc.stderr.strip().splitlines()[-1])["counts"][
        "metrics_read"
    ]
    assert "storm_spf_run_ms" in read
    for name in (
        "storm_area_dispatches_per_run", "storm_partial_run_share",
        "storm_rib_delta_routes_mean",
    ):
        assert name not in read


def test_program_without_the_route_sink_does_not_fit_and_says_so(tmp_path):
    """What the driver does with the parent commit: this PR's benchmark
    files over a program that lacks what the cell needs.  Exit code 2,
    at once, before JAX starts."""
    blocker = tmp_path / "sitecustomize.py"
    blocker.write_text(
        "import sys\n"
        "class _No:\n"
        "    def find_spec(self, name, path=None, target=None):\n"
        "        if name == 'holo_tpu.routing.sink':\n"
        "            raise ImportError('no module named ' + name)\n"
        "sys.meta_path.insert(0, _No())\n"
    )
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=f"{tmp_path}:{REPO}")
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload",
         "tiny-areastorm", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert "do not fit together" in proc.stderr
    assert not any(l.startswith("{") for l in proc.stdout.splitlines())


def _window(open_snap: dict, close_snap: dict):
    from benchmark.window import Window

    w = Window(1.0, None, 0.3)
    w.snap = {"open": open_snap, "close": close_snap}
    return SimpleNamespace(window=w)


RUN = "holo_profile_stage_seconds{site=ospf.spf,stage=run,device=-}"
AREAS = "holo_ospf_area_spf_total{disposition=%s}"
ARGS = json.loads(
    (REPO / "benchmark/layer_metrics/storm_area_dispatches_per_run.json")
    .read_text()
)["args"]


def test_counter_per_divides_the_counters_move_by_the_units_observed():
    ctx = _window(
        {RUN: {"count": 10, "sum": 1.0}, AREAS % "dispatched": 50.0,
         AREAS % "reused": 0.0},
        {RUN: {"count": 14, "sum": 2.0}, AREAS % "dispatched": 57.0,
         AREAS % "reused": 13.0},
    )
    assert counter_per.read(ARGS, ctx) == 7 / 4


@pytest.mark.parametrize(
    "first, last",
    [
        # a program without the counter: nothing, not zero
        ({RUN: {"count": 1, "sum": 1.0}}, {RUN: {"count": 5, "sum": 2.0}}),
        # no SPF run observed in the window
        ({RUN: {"count": 3, "sum": 1.0}, AREAS % "dispatched": 5.0},
         {RUN: {"count": 3, "sum": 1.0}, AREAS % "dispatched": 5.0}),
    ],
    ids=["no-counter", "no-unit"],
)
def test_counter_per_reads_nothing_where_there_is_nothing(first, last):
    assert counter_per.read(ARGS, _window(first, last)) is None


def test_lost_arrival_overtaken_by_a_later_event_carries_the_later_state():
    """A lost LSA arrives 5 s late.  If the same link flapped again
    meanwhile, the late arrival is the router's LSA of that moment, not
    the older copy (which, installed over the newer one, left the LSDB
    behind the link model: one prefix of 13,583 off the reference in
    one chip run of PR 31)."""
    net = _net()
    hall = net.layout.halls[1]
    whole = v3ref.routes(net.model())

    def matters(edge) -> bool:
        net.down[hall].add(edge)
        moved = v3ref.routes(net.model()) != whole
        net.down[hall].discard(edge)
        return moved

    edge = next(e for e in net.flappable[hall] if matters(e))
    net.flap(hall, edge, lost=True)  # down, arrives at +5 s
    net.loop.advance(1.0)
    net.flap(hall, edge, lost=False)  # up again, arrives at once
    net.loop.advance(30.0)
    assert edge not in net.down[hall]
    assert net.fib_table() == v3ref.routes(net.model())
    abr, prefix = sorted(
        (a, p) for (a, _ar, p) in net.layout.summaries
        if a in net.layout.remote_abrs
    )[0]
    net.summary(abr, prefix, lost=True)  # withdrawn, late
    net.loop.advance(1.0)
    net.summary(abr, prefix, lost=False)  # advertised again, at once
    net.loop.advance(30.0)
    table = net.fib_table()
    assert prefix in table and table == v3ref.routes(net.model())
