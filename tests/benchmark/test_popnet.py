"""The PoP-structured backbone of ISSUE 27 at rehearsal size
(``tiny-isp``: 160 routers, 6 PoPs, port cap 16): the graph is the
configuration file's and nothing else's, the new event kinds keep the
device backend bit-identical to the scalar reference, and the cell's
traced rehearsal reads the three metrics this PR adds."""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from benchmark import parity, popnet
from benchmark.popnet import ACCESS, DUT, PopNet, build_graph
from holo_tpu.ops.graph import INF

REPO = Path(__file__).resolve().parents[2]


def _config(name: str = "tiny-isp") -> dict:
    return json.loads((REPO / "benchmark/configs" / f"{name}.json").read_text())


def _edges(graph) -> list:
    return sorted(
        (a, b, c) for a, peers in graph.adj.items() for b, c in peers.items()
    )


@pytest.fixture(scope="module")
def graph():
    return build_graph(_config()["lsdb"])


def test_same_graph_seed_same_edges_other_seed_other_edges(graph):
    lsdb = _config()["lsdb"]
    assert _edges(build_graph(lsdb)) == _edges(graph)
    assert build_graph(lsdb).srlgs == graph.srlgs
    assert _edges(build_graph(dict(lsdb, graph_seed=1))) != _edges(graph)


@pytest.mark.parametrize("name", ["tiny-isp", "ospf-isp-pop-10k"])
def test_file_states_the_link_count_and_the_port_cap_is_reached(name):
    lsdb = _config(name)["lsdb"]
    g = build_graph(lsdb)
    degree = g.degrees()
    assert g.n_routers == lsdb["routers"] and g.n_links == lsdb["links"]
    assert degree.max() == lsdb["port_cap"] and degree.min() >= 1
    # ... by routers of several PoPs, so the ELL width outlives a loss
    full = np.flatnonzero(degree == lsdb["port_cap"])
    assert len(set(g.pop[full].tolist())) >= (6 if lsdb["routers"] > 1000 else 2)
    access = g.role == ACCESS
    dual = np.count_nonzero(access & (degree == 2)) / np.count_nonzero(access)
    assert abs(dual - lsdb["dual_homed_share"]) < 0.01
    assert set(degree[access].tolist()) <= {1, 2}
    assert min(g.pop_sizes) >= lsdb["pop_size_law"]["min_routers"]
    assert len(g.srlgs) == lsdb["srlgs"]


def test_file_states_the_hop_diameter(graph):
    assert graph.hop_diameter() == _config()["lsdb"]["hop_diameter"]


def test_every_link_is_two_way_with_one_cost(graph):
    for a, peers in graph.adj.items():
        for b, cost in peers.items():
            assert a != b and graph.adj[b][a] == cost
    lo, hi = _config()["lsdb"]["inter_pop_cost"]
    for a, b, cost in _edges(graph):
        if graph.pop[a] != graph.pop[b]:
            assert lo <= cost <= hi
        else:
            assert 1 <= cost <= 10


def test_dut_has_its_twelve_neighbours_and_nothing_drawn_touches_them(graph):
    want = _config()["lsdb"]["dut_neighbours"]
    peers = graph.dut_peers
    assert len(peers) == sum(want.values()) == len(graph.adj[DUT])
    assert [graph.pop[p] != graph.pop[DUT] for p in peers] == (
        [True] * want["uplinks"] + [False] * (len(peers) - want["uplinks"])
    )
    for group in graph.srlgs:
        assert 2 <= len(group) <= 8
        for a, b in group:
            assert DUT not in (a, b) and graph.pop[a] != graph.pop[b]


# -- the network: every traffic seed sees the deployment's shapes, and
# -- the new events keep the device on the scalar reference's bits


def _net(backend=None):
    from holo_tpu.spf.backend import TpuSpfBackend

    cfg = _config()
    return PopNet(
        cfg["lsdb"], backend or TpuSpfBackend(), cfg["spf_delay"], 5.0
    )


def test_loss_and_cut_draws_leave_the_dut_and_its_links_alone():
    net = _net()
    near = {DUT, *net.graph.dut_peers}
    assert not near & (set(net.losable["access"]) | set(net.losable["core"]))
    assert all(DUT not in edge for edge in net.flappable)
    with pytest.raises(ValueError):
        net.node(net.graph.dut_peers[3], lost=False)
    assert sorted(net.inst.areas[next(iter(net.inst.areas))].interfaces) == sorted(
        f"e{k}" for k in range(12)
    )


def _hub(net) -> int:
    degree = net.graph.degrees()
    return next(
        i for i in net.losable["core"] if degree[i] == degree.max()
    )


def _events(kind: str):
    """The events of one case, each a function of the network."""
    loss = lambda net: net.node(_hub(net), lost=False)  # noqa: E731
    cut = lambda net: net.srlg(0, lost=False)  # noqa: E731
    return {
        "link": [lambda net: net.flap(net.flappable[5], lost=False)],
        "srlg": [cut],
        "router-loss": [loss],
        "router-return": [loss, None, loss],  # None: let it converge
        "loss-with-cut": [loss, cut],  # coalesced into one SPF run
    }[kind]


@pytest.mark.parametrize(
    "kind", ["link", "srlg", "router-loss", "router-return", "loss-with-cut"]
)
def test_event_keeps_device_on_scalar_bits_and_fib(kind):
    """The device backend (JAX on the CPU) against ``ScalarSpfBackend``
    on four planes for every dispatch the events cause, and the settled
    FIB digest against the one a scalar-forced full SPF derives."""
    from holo_tpu import telemetry
    from holo_tpu.spf.backend import ScalarSpfBackend, TpuSpfBackend
    from holo_tpu.telemetry.canary import fib_digest

    backend = TpuSpfBackend()
    seen: list = []
    inner = backend.compute

    def compute(topo, edge_mask=None, **kw):
        res = inner(topo, edge_mask, **kw)
        seen.append((topo, edge_mask, parity.keep(res)))
        return res

    backend.compute = compute
    net = _net(backend)
    fib0 = dict(net.kernel.fib)
    first = len(seen)
    paths0 = telemetry.snapshot("holo_spf_delta_total")
    for event in _events(kind):
        if event is None:
            net.loop.advance(30.0)
        else:
            event(net)
    net.loop.advance(30.0)
    assert len(seen) > first, "the events ran no SPF"
    report = parity.against_scalar(seen[first:])
    assert report["checked"] and not report["mismatches"], report
    # served in place, not by a full re-marshal
    moved = {
        k: v - paths0.get(k, 0)
        for k, v in telemetry.snapshot("holo_spf_delta_total").items()
    }
    assert sum(v for k, v in moved.items() if "path=incremental" in k) > 0
    assert not any(v for k, v in moved.items() if "path=full-" in k), moved
    # Routers are vertices in router-id order, which is index order.
    hub, last = _hub(net), seen[-1][2]
    if kind in ("router-loss", "loss-with-cut"):
        # the lost router's LSA is still in the LSDB; the two-way check
        # alone takes it out of the tree
        lsids = {e.lsa.lsid for e in net.area.lsdb.all()}
        assert popnet._rid(hub) in lsids
        assert last.dist[hub] == INF and last.dist[hub + 1] < INF
    if kind == "router-return":
        assert last.dist[hub] < INF and dict(net.kernel.fib) == fib0
    before = fib_digest(net.kernel.fib)
    net.inst.backend = ScalarSpfBackend()
    net.inst._schedule_spf()
    net.loop.advance(30.0)
    assert fib_digest(net.kernel.fib) == before and len(net.kernel.fib) > 0


def test_lost_router_keeps_its_last_lsa_under_a_flap_of_its_link():
    net = _net()
    hub = _hub(net)
    peer = sorted(net.adj[hub])[0]
    said = net._links_of(hub)
    net.node(hub, lost=False)
    net.flap((min(hub, peer), max(hub, peer)), lost=False)  # re-installs both
    assert net._router_lsa(hub).body.links == said
    net.node(hub, lost=False)  # back: the flapped link stays down
    assert len(net._links_of(hub)) == len(said) - 1


# -- the cell's rehearsal


def _rehearse(seed: int, trace: int):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO))
    env.pop("PYTHONHASHSEED", None)
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", "tiny-ispstorm",
         "--seed", str(seed), "--seconds", "2", "--trace", str(trace)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 3, proc.stderr[-2000:]
    return json.loads(proc.stderr.strip().splitlines()[-1])


def test_traced_rehearsal_reads_the_three_new_metrics_and_counts_the_storm():
    report = _rehearse(2147483659, 1)
    read = set(report["counts"]["metrics_read"])
    assert {
        "storm_remarshal_share", "storm_delta_refused_share",
        "storm_delta_ops_mean", "storm_derive_ms",
    } <= read
    assert report["metrics"] == {} and report["failed"] == 0
    assert report["checks"]["parity"] and report["checks"]["no_compile_in_window"]
    counts = report["counts"]
    lsdb = _config()["lsdb"]
    assert counts["edges"] == 2 * lsdb["links"]
    assert counts["ell_width"] == lsdb["port_cap"]
    assert counts["ell_slots"] == lsdb["routers"] * lsdb["port_cap"]
    assert counts["warmup_hub_loss"]["degree"] == lsdb["port_cap"]
    assert set(counts["warmup_by_kind"]) == set(counts["injected_by_kind"]) == {
        "link", "srlg", "node", "bfd", "carrier", "ifconfig"
    }
    assert counts["most_lsas_in_one_event"] >= 8
    assert counts["routers_down_at_end"] <= 3 and counts["srlgs_down_at_end"] <= 4


def test_every_traffic_seed_sees_the_same_edge_count_and_ell_width():
    a, b = (_rehearse(seed, 0)["counts"] for seed in (3, 2147483777))
    for key in ("edges", "ell_width", "ell_slots"):
        assert a[key] == b[key]
    assert a["injected_by_kind"] != b["injected_by_kind"]
