"""Compile shapes under churn (ISSUE 27, D): one delta bucket for every
delta ``diff_topologies`` can return, a mask-free full-SPF program that
does not depend on the edge count, and a re-marshal that keeps the ELL
width of the resident it replaces — so that a storm's largest failure
compiles nothing in the middle of the storm."""

import numpy as np
import pytest

from holo_tpu import telemetry
from holo_tpu.ops.graph import DELTA_MAX_OPS, Topology, diff_topologies
from holo_tpu.ops.spf_engine import (
    _DELTA_PAD_FLOOR,
    DeviceGraphCache,
    shared_graph_cache,
)
from holo_tpu.spf.backend import ScalarSpfBackend, TpuSpfBackend
from holo_tpu.spf.synth import clone_topology, random_ospf_topology

PLANES = ("dist", "parent", "hops", "nexthop_words")


def _compiles() -> float:
    return sum(telemetry.snapshot("holo_spf_jit_compiles_total").values())


class _Programs:
    """XLA backend compiles since construction (what the benchmark's
    ``SetupClock`` counts as ``programs_in_window``)."""

    def __init__(self):
        import jax.monitoring

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


@pytest.fixture(scope="module")
def programs():
    return _Programs()


def _same_bits(got, topo):
    ref = ScalarSpfBackend().compute(topo)
    for plane in PLANES:
        assert np.array_equal(getattr(got, plane), getattr(ref, plane)), plane


def _base(seed: int = 11) -> Topology:
    return random_ospf_topology(
        n_routers=300, n_networks=0, extra_p2p=600, seed=seed
    )


def _linked(base: Topology, nxt: Topology) -> Topology:
    delta = diff_topologies(base, nxt)
    assert delta is not None
    nxt.link_delta(delta)
    return nxt


def test_pad_floor_is_the_most_operations_a_delta_carries():
    assert _DELTA_PAD_FLOOR == DELTA_MAX_OPS == 512


def test_two_edge_counts_of_one_ell_shape_share_the_full_spf_program(programs):
    a = _base()
    # b: the same vertices, four links fewer (both directions), no lineage
    pairs = {(int(s), int(d)) for s, d in zip(a.edge_src[:8], a.edge_dst[:8])}
    pairs |= {(d, s) for s, d in pairs}
    keep = np.array([
        (int(s), int(d)) not in pairs for s, d in zip(a.edge_src, a.edge_dst)
    ])
    b = clone_topology(a, keep=keep)
    assert b.n_edges < a.n_edges and b.delta_base is None
    be = TpuSpfBackend()
    _same_bits(be.compute(a), a)
    compiles, built = _compiles(), programs.n
    got = be.compute(b)
    assert _compiles() == compiles and programs.n == built
    _same_bits(got, b)
    assert be.prepare(a).in_src.shape == be.prepare(b).in_src.shape


@pytest.mark.parametrize("n_ops", [8, 257, 512])
def test_a_delta_of_any_size_rides_the_pair_the_first_one_compiled(
    programs, n_ops
):
    base = _base(seed=12)
    assert base.n_edges >= 600
    be = TpuSpfBackend()
    be.compute(base)
    # set-up: a one-operation delta compiles the apply + incremental pair
    first = _linked(base, clone_topology(base, cost={0: int(base.edge_cost[0]) + 1}))
    _same_bits(be.compute(first), first)
    compiles, built = _compiles(), programs.n
    moved = {
        e: int(first.edge_cost[e]) + 1 + e % 3 for e in range(1, n_ops + 1)
    }
    nxt = clone_topology(first, cost=moved)
    delta = diff_topologies(first, nxt)
    assert delta is not None and delta.n_ops == n_ops
    nxt.link_delta(delta)
    before = telemetry.snapshot("holo_spf_delta_total")
    got = be.compute(nxt)
    after = telemetry.snapshot("holo_spf_delta_total")
    assert _compiles() == compiles and programs.n == built
    _same_bits(got, nxt)
    served = sum(
        v - before.get(k, 0) for k, v in after.items()
        if "path=incremental" in k
    )
    assert served == 1


def test_structural_delta_of_a_lost_hub_rides_the_same_pair(programs):
    """Every link of the widest router gone in one delta (both
    directions: twice its degree in operations), then back."""
    base = _base(seed=13)
    hub = int(np.argmax(np.bincount(base.edge_dst)))
    be = TpuSpfBackend()
    be.compute(base)
    first = _linked(base, clone_topology(base, cost={0: int(base.edge_cost[0]) + 1}))
    be.compute(first)
    compiles, built = _compiles(), programs.n
    keep = (first.edge_src != hub) & (first.edge_dst != hub)
    lost = _linked(first, clone_topology(first, keep=keep))
    assert lost.delta_base.n_ops == int((~keep).sum()) > 16
    _same_bits(be.compute(lost), lost)
    back = _linked(lost, clone_topology(first))
    _same_bits(be.compute(back), back)
    assert _compiles() == compiles and programs.n == built


def test_masked_call_keeps_its_signature_and_the_mask_free_one_drops_e():
    topo = _base(seed=14)
    be = TpuSpfBackend()
    be.compute(topo)
    mask = np.ones(topo.n_edges, bool)
    mask[3] = False
    be.compute(topo, mask)
    shape = be.prepare(topo).in_src.shape
    one = sorted(
        (s for s in be._compiled_shapes if s[0] == "one"), key=lambda s: s[4]
    )
    assert [s[4] for s in one] == [0, topo.n_edges]  # mask-free, masked
    # (kind, engine, ELL shape, words, E, mesh, engine, k, tiles, repair rows)
    assert one[1] == (
        "one", be.one_engine, shape, one[1][3], topo.n_edges, None,
        be.one_engine, 1, None, None,
    )
    assert one[0][2] == shape and one[0][5:] == one[1][5:]


def test_remarshal_while_the_widest_router_is_down_keeps_the_ell_width(
    programs,
):
    n = 120
    ring = [(i, (i + 1) % n) for i in range(n)]
    spokes = [(0, j) for j in range(2, 24)]  # router 0: degree 24
    links = ring + spokes
    src = np.array([a for a, b in links] + [b for a, b in links], np.int32)
    dst = np.array([b for a, b in links] + [a for a, b in links], np.int32)

    def topo_of(keep) -> Topology:
        return Topology(
            n_vertices=n, is_router=np.ones(n, bool),
            edge_src=src[keep], edge_dst=dst[keep],
            edge_cost=np.full(int(keep.sum()), 3, np.int32),
            edge_direct_atom=np.full(int(keep.sum()), -1, np.int32), root=60,
        )

    whole = topo_of(np.ones(src.size, bool))
    hub_down = topo_of((src != 0) & (dst != 0))  # no lineage: a re-marshal
    assert int(np.bincount(hub_down.edge_dst).max()) == 2
    be = TpuSpfBackend()
    _same_bits(be.compute(whole), whole)
    assert be.prepare(whole).in_src.shape == (n, 24)
    compiles, built = _compiles(), programs.n
    _same_bits(be.compute(hub_down), hub_down)
    assert be.prepare(hub_down).in_src.shape == (n, 24)
    assert _compiles() == compiles and programs.n == built
    # ... which is the cache's doing: a cache that never held the wide
    # resident builds the narrow one
    fresh, _how = DeviceGraphCache().get(hub_down, be.n_atoms)
    assert fresh.in_src.shape == (n, 8)


def test_delta_ops_histogram_counts_returned_and_refused_deltas():
    def seen():
        snap = telemetry.snapshot("holo_spf_delta_ops")
        return sum(c["count"] for c in snap.values()), sum(
            c["sum"] for c in snap.values()
        )

    base = _base(seed=15)
    count0, sum0 = seen()
    small = clone_topology(base, cost={e: 99 for e in range(5)})
    assert diff_topologies(base, small).n_ops == 5
    large = clone_topology(base, cost={e: 99 for e in range(520)})
    assert diff_topologies(base, large) is None  # refused for its size
    other = random_ospf_topology(n_routers=40, n_networks=0, seed=1)
    assert diff_topologies(base, other) is None  # another vertex model
    count1, sum1 = seen()
    assert (count1 - count0, sum1 - sum0) == (2, 525)


def test_storm_past_the_depth_cap_compiles_nothing_in_its_window(monkeypatch):
    """ROADMAP S2: a DeltaPath chain that crosses the depth cap inside
    the window re-marshals (``full-depth``) and, E having moved with
    the flaps, used to compile a new full-SPF program there."""
    from benchmark import run

    monkeypatch.setattr(shared_graph_cache(), "max_delta_depth", 24)
    before = telemetry.snapshot("holo_spf_delta_total")
    cell = run.load_json("workloads", "tiny-storm")
    result, rc = run.measure(
        cell, run.load_json("configs", cell["config"]),
        run.load_plugin("drivers", cell["driver"]), 7, 1.5, False,
    )
    after = telemetry.snapshot("holo_spf_delta_total")
    full_depth = sum(
        v - before.get(k, 0) for k, v in after.items() if "path=full-depth" in k
    )
    assert rc == 3 and full_depth >= 1
    assert result["checks"]["no_compile_in_window"] is True
    assert result["checks"]["parity"] and result["failed"] == 0


def _areas_of_one_shape(n_areas: int = 4, k: int = 6):
    """Same-shaped areas of one ABR: the k-ary fat-tree with other
    per-direction costs in each, the root the same vertex in all."""
    from benchmark import fabric

    return [fabric.fat_tree(k, 1, 3, seed=40 + a) for a in range(n_areas)]


def test_four_areas_of_one_shape_share_every_program_and_one_width_floor(
    programs,
):
    """ISSUE 31: an ABR serves several residents in turn through one
    backend, four of them of one shape.  The first area compiles the
    full program and the apply + incremental pair; the other three, and
    every later delta on any of them, compile nothing, and their delta
    chains advance apart."""
    areas = _areas_of_one_shape()
    be = TpuSpfBackend()
    _same_bits(be.compute(areas[0]), areas[0])
    step = _linked(areas[0], clone_topology(
        areas[0], cost={0: int(areas[0].edge_cost[0]) + 1}
    ))
    _same_bits(be.compute(step), step)
    heads = [step] + areas[1:]
    compiles, built = _compiles(), programs.n
    for topo in areas[1:]:
        _same_bits(be.compute(topo), topo)
    assert {be.prepare(t).in_src.shape for t in heads} == {
        be.prepare(step).in_src.shape
    }
    # deltas in turn, two rounds: a cost change, then a lost switch
    for round_ in range(2):
        for a, head in enumerate(heads):
            if round_ == 0:
                nxt = clone_topology(
                    head, cost={5 + a: int(head.edge_cost[5 + a]) + 2}
                )
            else:
                lost = 3 + a
                nxt = clone_topology(
                    head,
                    keep=(head.edge_src != lost) & (head.edge_dst != lost),
                )
            heads[a] = _linked(head, nxt)
            _same_bits(be.compute(heads[a]), heads[a])
    assert _compiles() == compiles and programs.n == built
    delta = telemetry.snapshot("holo_spf_delta_total")
    assert sum(v for k, v in delta.items() if "path=incremental" in k) >= 9
    # one floor for the four of them (and one key: vertices, root, atoms)
    floors = [
        k for k in shared_graph_cache()._k_pad_floor
        if k[0] == areas[0].n_vertices and k[1] == int(areas[0].root)
    ]
    assert len(floors) == 1


def test_remarshal_of_one_area_keeps_the_width_its_siblings_compiled(programs):
    """One of four same-shaped areas re-marshals with no lineage while
    a switch is down (the atom table changed): the floor the four share
    keeps its ELL width, so the full program it runs is the siblings'."""
    areas = _areas_of_one_shape()
    be = TpuSpfBackend()
    for topo in areas:
        be.compute(topo)
    shape = be.prepare(areas[0]).in_src.shape
    compiles, built = _compiles(), programs.n
    widest = int(np.argmax(np.bincount(areas[2].edge_dst)))
    keep = (areas[2].edge_src != widest) & (areas[2].edge_dst != widest)
    again = clone_topology(areas[2], keep=keep)  # no link_delta: lineage-less
    assert again.delta_base is None
    _same_bits(be.compute(again), again)
    assert be.prepare(again).in_src.shape == shape
    assert _compiles() == compiles and programs.n == built
