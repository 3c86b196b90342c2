"""DeltaPath incremental SPF (ISSUE 7): property and fallback gates.

The contract: ANY delta chain served through the device-resident graph
(``DeviceGraphCache.apply_delta`` path + the seeded incremental kernel)
yields distances / parents / hops / ECMP next-hop words bit-identical
to a from-scratch marshal + full SPF of the final topology — checked
against both the full-rebuild device path and the scalar oracle.  Every
fallback trigger (chain depth, padding slack, atom width, mask
consumers needing edge ids, missing base) must land on the full-rebuild
path with the same bits.  Everything runs under the transfer-guard
sanitizer: the delta path may only move data inside its sanctioned
windows.
"""

import numpy as np
import pytest

from holo_tpu import telemetry
from holo_tpu.ops.graph import TopologyDelta, diff_topologies
from holo_tpu.ops.spf_engine import shared_graph_cache
from holo_tpu.spf.backend import ScalarSpfBackend, TpuSpfBackend
from holo_tpu.spf.synth import (
    clone_topology as clone,
    random_ospf_topology,
    whatif_link_failure_masks,
)
from holo_tpu.testing import no_implicit_transfers

N_ATOMS = 64


@pytest.fixture(autouse=True)
def _transfer_sanitizer():
    """The whole suite runs under jax.transfer_guard('disallow'): the
    delta path's scatter/seed transfers must stay inside the sanctioned
    spf.one.delta window."""
    with no_implicit_transfers():
        yield


def random_mutation(topo, rng):
    """One random storm-shaped event: metric change, link flap (both
    directions of one edge), or a fresh bidirectional edge."""
    roll = rng.random()
    if roll < 0.4 and topo.n_edges:
        e = int(rng.integers(0, topo.n_edges))
        return clone(topo, cost={e: int(rng.integers(1, 64))})
    if roll < 0.8 and topo.n_edges:
        e = int(rng.integers(0, topo.n_edges))
        s, d = int(topo.edge_src[e]), int(topo.edge_dst[e])
        keep = ~(
            ((topo.edge_src == s) & (topo.edge_dst == d))
            | ((topo.edge_src == d) & (topo.edge_dst == s))
        )
        return clone(topo, keep=keep)
    a = int(rng.integers(0, topo.n_vertices))
    b = int(rng.integers(0, topo.n_vertices))
    w = int(rng.integers(1, 32))
    return clone(topo, extra=[[a, b, w, -1], [b, a, w, -1]])


def assert_results_equal(ref, got, ctx=""):
    for f in ("dist", "parent", "hops", "nexthop_words"):
        np.testing.assert_array_equal(
            getattr(ref, f), getattr(got, f), err_msg=f"{ctx}: {f}"
        )


def delta_snapshot():
    return telemetry.snapshot(prefix="holo_spf_delta")


def count(snap, path):
    return sum(v for k, v in snap.items() if f"path={path}" in k)


@pytest.mark.parametrize("seed", range(5))
def test_random_delta_chain_bit_identical(seed):
    """THE property: a random delta chain applied via apply_delta +
    the seeded incremental kernel == from-scratch marshal + full SPF of
    the final topology, at every step, against the device full-rebuild
    path AND the scalar oracle."""
    rng = np.random.default_rng(seed)
    topo = random_ospf_topology(
        n_routers=24, n_networks=6, extra_p2p=30, seed=seed
    )
    inc_be = TpuSpfBackend(N_ATOMS)
    full_be = TpuSpfBackend(N_ATOMS, incremental=False)
    oracle = ScalarSpfBackend(N_ATOMS)
    before = delta_snapshot()
    inc_be.compute(topo)
    cur = topo
    for _step in range(10):
        nxt = random_mutation(cur, rng)
        delta = diff_topologies(cur, nxt)
        if delta is not None:
            nxt.link_delta(delta)
        got = inc_be.compute(nxt)
        fresh = full_be.compute(clone(nxt))  # distinct identity: no reuse
        ref = oracle.compute(nxt)
        assert_results_equal(ref, got, f"seed {seed} step {_step} inc")
        assert_results_equal(ref, fresh, f"seed {seed} step {_step} full")
        cur = nxt
    after = delta_snapshot()
    assert count(after, "incremental") > count(before, "incremental"), (
        "the chain must actually exercise the incremental path"
    )


def test_too_deep_delta_chain_falls_back_full_rebuild():
    cache = shared_graph_cache()
    old_depth = cache.max_delta_depth
    cache.max_delta_depth = 2
    try:
        rng = np.random.default_rng(9)
        topo = random_ospf_topology(n_routers=16, n_networks=4, seed=9)
        be = TpuSpfBackend(N_ATOMS)
        oracle = ScalarSpfBackend(N_ATOMS)
        before = delta_snapshot()
        be.compute(topo)
        cur = topo
        for _ in range(6):
            nxt = random_mutation(cur, rng)
            delta = diff_topologies(cur, nxt)
            if delta is not None:
                nxt.link_delta(delta)
            assert_results_equal(oracle.compute(nxt), be.compute(nxt))
            cur = nxt
        after = delta_snapshot()
        depth_falls = count(after, "full-depth") - count(before, "full-depth")
        assert depth_falls > 0, (
            "depth-capped chains must take the full-rebuild path"
        )
        # Accounting regression: a dispatch the cache full-rebuilt must
        # NOT also claim path="incremental" — the label means the
        # in-place-updated resident served it.
        inc_served = count(after, "incremental") - count(before, "incremental")
        assert inc_served + depth_falls <= 6
    finally:
        cache.max_delta_depth = old_depth


def test_padding_overflow_falls_back_full_rebuild():
    """Additions beyond the destination row's ELL padding slack cannot
    be absorbed in place: the delta is refused and the full rebuild
    (with a wider K bucket) serves the same bits."""
    topo = random_ospf_topology(n_routers=14, n_networks=3, seed=4)
    be = TpuSpfBackend(N_ATOMS)
    oracle = ScalarSpfBackend(N_ATOMS)
    be.compute(topo)
    # Flood one vertex with more new in-edges than any padded row holds.
    k_pad = 8 * (
        1 + int(np.bincount(topo.edge_dst, minlength=topo.n_vertices).max())
        // 8
    )
    v = int(topo.edge_dst[0])
    extra = []
    for i in range(k_pad + 4):
        peer = (v + 1 + i) % topo.n_vertices
        extra.append([peer, v, 7, -1])
    nxt = clone(topo, extra=extra)
    delta = diff_topologies(topo, nxt, max_ops=4 * k_pad + 64)
    assert delta is not None
    nxt.link_delta(delta)
    before = delta_snapshot()
    assert_results_equal(oracle.compute(nxt), be.compute(nxt))
    after = delta_snapshot()
    assert count(after, "full-padding-overflow") > count(
        before, "full-padding-overflow"
    )


def test_overload_strike_delta():
    """The node-overload delta kind: transit through the struck vertex
    dies in place (slots masked through in_src), destinations stay
    reachable — equal to a topology without the vertex's out-edges."""
    topo = random_ospf_topology(n_routers=18, n_networks=4, seed=6)
    be = TpuSpfBackend(N_ATOMS)
    oracle = ScalarSpfBackend(N_ATOMS)
    be.compute(topo)
    # Strike a non-root transit vertex.
    v = next(
        int(u) for u in np.unique(topo.edge_src) if int(u) != topo.root
    )
    nxt = clone(topo, keep=topo.edge_src != v)
    nxt.link_delta(
        TopologyDelta(
            base_key=topo.cache_key,
            overload=np.asarray([v], np.int32),
            ids_stable=False,
        )
    )
    before = delta_snapshot()
    assert_results_equal(oracle.compute(nxt), be.compute(nxt))
    after = delta_snapshot()
    assert count(after, "incremental") > count(before, "incremental")


def test_topology_without_lineage_never_enters_the_delta_path():
    """The no-delta steady state: a topology that carries no
    ``link_delta`` lineage books no DeltaPath disposition at all (not
    even a ``full-*`` fallback), and repeated dispatches of it keep ONE
    retained seed set — the same object, not a fresh one per dispatch.
    A backend with ``incremental=False`` retains none."""
    topo = random_ospf_topology(n_routers=12, n_networks=2, seed=4)
    assert topo.delta_base is None
    be = TpuSpfBackend(N_ATOMS)
    snap0 = delta_snapshot()
    first = be.compute(topo)
    [seed] = be._prev_one.values()
    for _ in range(3):
        assert_results_equal(first, be.compute(topo))
    assert delta_snapshot() == snap0
    [kept] = be._prev_one.values()
    assert kept is seed
    off = TpuSpfBackend(N_ATOMS, incremental=False)
    assert_results_equal(first, off.compute(topo))
    assert not off._prev_one and delta_snapshot() == snap0


def test_empty_delta_reuses_resident_graph_without_marshal():
    """A content-identical rebuild (LSA refresh with no topology change)
    produces an empty delta: the resident graph is aliased under the
    new key with zero marshal work."""
    topo = random_ospf_topology(n_routers=12, n_networks=2, seed=2)
    be = TpuSpfBackend(N_ATOMS)
    be.compute(topo)
    nxt = clone(topo)
    delta = diff_topologies(topo, nxt)
    assert delta is not None and delta.kind == "empty" and delta.ids_stable
    nxt.link_delta(delta)
    marshals0 = telemetry.snapshot(prefix="holo_spf_marshal_total")
    res = be.compute(nxt)
    marshals1 = telemetry.snapshot(prefix="holo_spf_marshal_total")
    assert marshals0 == marshals1, "an empty delta must not re-marshal"
    assert_results_equal(ScalarSpfBackend(N_ATOMS).compute(nxt), res)


def test_whatif_after_structural_delta_rebuilds_edge_ids():
    """Mask consumers gather through in_edge_id: a structurally-updated
    resident entry must be rebuilt for them, bit-identically."""
    topo = random_ospf_topology(n_routers=16, n_networks=4, seed=3)
    be = TpuSpfBackend(N_ATOMS)
    be.compute(topo)
    e = int(np.nonzero(topo.edge_src != topo.root)[0][0])
    s, d = int(topo.edge_src[e]), int(topo.edge_dst[e])
    keep = ~(
        ((topo.edge_src == s) & (topo.edge_dst == d))
        | ((topo.edge_src == d) & (topo.edge_dst == s))
    )
    nxt = clone(topo, keep=keep)
    delta = diff_topologies(topo, nxt)
    assert delta is not None and not delta.ids_stable
    nxt.link_delta(delta)
    be.compute(nxt)  # serve the delta chain (stale edge ids now)
    masks = whatif_link_failure_masks(nxt, n_scenarios=6, seed=3)
    scalar = ScalarSpfBackend(N_ATOMS).compute_whatif(nxt, masks)
    got = be.compute_whatif(nxt, masks)
    for sres, tres in zip(scalar, got):
        assert_results_equal(sres, tres)


def test_masked_compute_after_structural_delta_rebuilds_edge_ids():
    """Regression: compute(topo, edge_mask) gathers the scenario mask
    through in_edge_id, so it must not be served by a structurally
    delta-updated resident (stale edge ids would mask the wrong
    edges, silently)."""
    topo = random_ospf_topology(n_routers=14, n_networks=3, seed=11)
    be = TpuSpfBackend(N_ATOMS)
    be.compute(topo)
    e = int(np.nonzero(topo.edge_src != topo.root)[0][0])
    s, d = int(topo.edge_src[e]), int(topo.edge_dst[e])
    keep = ~(
        ((topo.edge_src == s) & (topo.edge_dst == d))
        | ((topo.edge_src == d) & (topo.edge_dst == s))
    )
    nxt = clone(topo, keep=keep)
    delta = diff_topologies(topo, nxt)
    assert delta is not None and not delta.ids_stable
    nxt.link_delta(delta)
    be.compute(nxt)  # mask-free: rides the delta entry (ids now stale)
    mask = np.ones(nxt.n_edges, bool)
    f = int(np.nonzero(nxt.edge_src != nxt.root)[0][-1])
    fs, fd = int(nxt.edge_src[f]), int(nxt.edge_dst[f])
    mask[
        ((nxt.edge_src == fs) & (nxt.edge_dst == fd))
        | ((nxt.edge_src == fd) & (nxt.edge_dst == fs))
    ] = False
    assert_results_equal(
        ScalarSpfBackend(N_ATOMS).compute(nxt, mask),
        be.compute(nxt, mask),
        "masked compute after struct delta",
    )


def test_frr_engine_rides_weight_delta_chain():
    """FrrEngine chooses incremental vs full rebuild: a pure metric
    delta keeps edge ids valid, so the FRR planes ride the in-place
    updated resident graph — backup tables bit-identical to the scalar
    oracle either way."""
    from holo_tpu.frr.manager import FrrEngine
    from holo_tpu.spf.synth import grid_topology

    topo = grid_topology(4, 4, seed=5)
    be = TpuSpfBackend(N_ATOMS)
    eng = FrrEngine("tpu")
    be.compute(topo)
    eng.compute(topo)
    nxt = clone(topo, cost={1: int(topo.edge_cost[1]) + 3})
    delta = diff_topologies(topo, nxt)
    assert delta is not None and delta.ids_stable
    nxt.link_delta(delta)
    be.compute(nxt)  # applies the delta; FRR below must hit the entry
    cache0 = telemetry.snapshot(prefix="holo_spf_marshal_total")
    table = eng.compute(nxt)
    assert telemetry.snapshot(prefix="holo_spf_marshal_total") == cache0, (
        "a weight-delta chain must not force an FRR re-marshal"
    )
    ref = FrrEngine("scalar").compute(nxt)
    for f in (
        "lfa_adj", "lfa_nodeprot", "rlfa_pq", "tilfa_p", "tilfa_q",
        "post_dist", "post_nh",
    ):
        np.testing.assert_array_equal(
            getattr(ref, f), getattr(table, f), err_msg=f
        )


def test_ospfv2_seam_links_deltas_in_storm():
    """LSDB-seam e2e: a real OSPFv2 instance under flap events links
    delta lineage per area and the backend serves it incrementally —
    the FIB matches a scalar-backend control run event for event."""
    from holo_tpu.spf.synth_storm import StormNet

    def run(backend):
        net = StormNet(n_routers=50, seed=13, spf_backend=backend)
        for i in range(6):
            net.flap(net.flappable[i % len(net.flappable)], lost=False)
            net.loop.advance(12.0)
        net.loop.advance(40.0)
        return dict(net.kernel.fib)

    before = delta_snapshot()
    fib_tpu = run(TpuSpfBackend(N_ATOMS))
    after = delta_snapshot()
    assert count(after, "incremental") > count(before, "incremental"), (
        "the protocol seam must link servable deltas"
    )
    fib_scalar = run(None)
    assert fib_tpu == fib_scalar


def test_cache_stats_on_gnmi_leaf():
    """Satellite: eviction/occupancy stats ride the holo-telemetry
    subtree next to the hit/miss counters."""
    from holo_tpu.telemetry.provider import TelemetryStateProvider

    topo = random_ospf_topology(n_routers=10, n_networks=2, seed=1)
    TpuSpfBackend(N_ATOMS).compute(topo)
    state = TelemetryStateProvider().get_state()
    leaf = state["holo-telemetry"]["spf-graph-cache"]
    for key in (
        "entries", "capacity", "evictions", "deltas-applied",
        "delta-entries", "max-chain-depth", "occupancy",
    ):
        assert key in leaf, key
    assert leaf["entries"] >= 1
    assert 0.0 < leaf["occupancy"] <= 1.0


# -- donation guard: the runtime half of HL109 (ISSUE 14) ---------------


def test_donation_guard_poisons_and_asserts():
    """Unit contract: disarmed note_donated is a no-op; armed, it
    deletes the donated handles, and assert_live converts a later read
    into a named DonatedBufferError at the force boundary."""
    import jax.numpy as jnp

    from holo_tpu.analysis import runtime as art
    from holo_tpu.testing import donation_guarded

    arr = jnp.arange(4)
    art.note_donated("fixture.disarmed", arr)
    assert not arr.is_deleted()
    art.assert_live("fixture.disarmed", arr)  # disarmed: no-op too
    with donation_guarded():
        arr2 = jnp.arange(8)
        art.note_donated("fixture.armed", (arr2, None))
        assert arr2.is_deleted()
        with pytest.raises(art.DonatedBufferError, match="fixture.read"):
            art.assert_live("fixture.read", arr2)
    assert art.donated_counts().get("fixture.armed", 0) >= 1


def test_donation_guard_catches_retained_prev_alias():
    """The runtime arm of the ISSUE-14 mutation proof: a reference
    that illegally outlives the DeltaPath donation (exactly the HL109
    retention bug) is poisoned by the dispatch seam, so reading it at
    test time raises instead of silently passing on the CPU platform
    (which ignores donation and would have returned stale bytes)."""
    from holo_tpu.analysis import runtime as art
    from holo_tpu.testing import donation_guarded

    with donation_guarded():
        topo = random_ospf_topology(n_routers=16, n_networks=4, seed=3)
        be = TpuSpfBackend(N_ATOMS)
        be.compute(topo)
        # The seeded bug: an alias of the retained prev tensors that
        # the next delta dispatch will donate out from under us.
        stale = next(iter(be._prev_one.values()))
        before = art.donated_counts().get("spf.one.delta", 0)
        nxt = clone(topo, cost={0: 7})
        delta = diff_topologies(topo, nxt)
        assert delta is not None
        nxt.link_delta(delta)
        be.compute(nxt)
        assert art.donated_counts().get("spf.one.delta", 0) > before, (
            "delta dispatch did not ride the incremental (donating) path"
        )
        with pytest.raises(art.DonatedBufferError):
            art.assert_live("test.readback", stale)


def test_delta_chain_parity_under_donation_guard():
    """One parity arm under the armed guard (composed with the
    transfer sanitizer via the suite's autouse fixture): poisoning
    every donated seed must not disturb bit-identity — the production
    path never reads what it donated — and both halves of the shared
    seam vocabulary must actually run."""
    from holo_tpu.analysis import runtime as art
    from holo_tpu.testing import donation_guarded

    with donation_guarded():
        rng = np.random.default_rng(11)
        topo = random_ospf_topology(
            n_routers=20, n_networks=5, extra_p2p=20, seed=11
        )
        be = TpuSpfBackend(N_ATOMS)
        oracle = ScalarSpfBackend(N_ATOMS)
        be.compute(topo)
        cur = topo
        for _step in range(6):
            nxt = random_mutation(cur, rng)
            delta = diff_topologies(cur, nxt)
            if delta is not None:
                nxt.link_delta(delta)
            assert_results_equal(
                oracle.compute(nxt), be.compute(nxt), f"step {_step}"
            )
            cur = nxt
        assert art.donated_counts().get("spf.one.delta", 0) > 0
        assert art.consumed_counts().get("spf.prev.redeposit", 0) > 0
