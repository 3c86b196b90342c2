"""Hierarchical partitioned SPF (ISSUE 15): correctness property gates.

The contract: the three-phase partitioned path (batched per-partition
boundary solves -> exact host skeleton stitch -> seeded final solves
with pinned-halo phase-2 exchange) is bit-identical to BOTH the
monolithic device path and the scalar oracle on every arm — plain,
what-if masks, multipath k ∈ {1, 2, 8}, DeltaPath chains whose events
cross partition boundaries, sharded mesh, and breaker fallback — for
random BFS/greedy cuts, adversarial random vertex->partition maps, and
native partition hints.  Everything runs under
``jax.transfer_guard("disallow")`` (the partitioned path may only move
data inside its sanctioned windows) and the delta chains additionally
under the armed HL109 runtime donation guard.
"""

import numpy as np
import pytest

from holo_tpu import telemetry
from holo_tpu.ops.graph import INF, Topology, diff_topologies, partition_topology
from holo_tpu.ops.partition import PartitionedSpfEngine, build_plan
from holo_tpu.parallel.mesh import (
    configure_process_mesh,
    reset_process_mesh,
)
from holo_tpu.resilience.breaker import CircuitBreaker
from holo_tpu.resilience.faults import FaultInjector, FaultPlan, inject
from holo_tpu.spf.backend import ScalarSpfBackend, TpuSpfBackend
from holo_tpu.spf.scalar import spf_reference
from holo_tpu.spf.synth import (
    clone_topology as clone,
    grid_topology,
    random_ospf_topology,
    whatif_link_failure_masks,
)
from holo_tpu.testing import donation_guarded, no_implicit_transfers

MP_FIELDS = ("parents", "pdist", "pweight", "npaths", "nh_weights")
ALL_FIELDS = ("dist", "parent", "hops", "nexthop_words") + MP_FIELDS


@pytest.fixture(autouse=True)
def _transfer_sanitizer():
    """The whole suite runs under jax.transfer_guard('disallow'): every
    partitioned-phase transfer must stay inside the sanctioned
    spf.partition.* windows."""
    with no_implicit_transfers():
        yield


def tied(seed, n=40, nets=6, extra=60):
    """Random topology with a tiny cost universe: real ECMP ties, and
    enough extra links that random cuts produce real cut-edge sets."""
    return random_ospf_topology(
        n, n_networks=nets, extra_p2p=extra, max_cost=4, seed=seed
    )


def assert_same(a, b, tag=""):
    for f in ALL_FIELDS:
        x, y = getattr(a, f), getattr(b, f)
        if x is None or y is None:
            assert x is None and y is None, (tag, f)
        else:
            assert np.array_equal(x, y), (tag, f)


def delta_count(path: str) -> float:
    return telemetry.snapshot(prefix="holo_spf_delta").get(
        f"holo_spf_delta_total{{kind=weight,path={path}}}", 0.0
    )


# ------------------------------------------------------------- the cut


def test_partition_cut_is_deterministic_exact_cover():
    for seed in range(4):
        topo = tied(seed)
        a = partition_topology(topo, max_part=12)
        b = partition_topology(topo, max_part=12)
        assert np.array_equal(a, b), "cut must be deterministic"
        assert a.min() == 0
        assert np.all(np.bincount(a) > 0), "dense non-empty ids"
        assert a.shape[0] == topo.n_vertices


def test_partition_hint_honored_verbatim():
    topo = tied(1)
    rng = np.random.default_rng(3)
    hint = rng.integers(0, 5, topo.n_vertices, dtype=np.int32)
    topo.partition_hint = hint
    part = partition_topology(topo)
    # Same grouping, dense ids in ascending hint order.
    _, want = np.unique(hint, return_inverse=True)
    assert np.array_equal(part, want.astype(np.int32))
    plan = build_plan(topo)
    assert plan.n_parts == len(np.unique(hint))


# ----------------------------------------------------- engine parity


def test_partitioned_solve_bit_identical_across_random_cuts():
    """Seeded property sweep: engine-level parity vs the scalar oracle
    for BFS/greedy cuts AND adversarial random vertex->partition maps
    (worst-case skeletons)."""
    eng = PartitionedSpfEngine()
    for seed in range(4):
        topo = tied(seed)
        rng = np.random.default_rng(seed)
        cuts = [
            partition_topology(topo, max_part=12),
            rng.integers(0, 4, topo.n_vertices).astype(np.int32),
        ]
        ref = spf_reference(topo)
        for ci, part_of in enumerate(cuts):
            res = eng.marshal(topo, n_atoms=8, part_of=part_of)
            out = eng.solve(topo, res, None, 1)
            for f in ("dist", "parent", "hops"):
                assert np.array_equal(out[f], getattr(ref, f)), (seed, ci, f)
            assert np.array_equal(
                out["nexthop_words"], ref.nexthop_words(8)
            ), (seed, ci)


def test_partitioned_backend_matches_monolithic_and_oracle():
    """Backend-level: a partition-armed backend, the monolithic device
    backend, and the scalar oracle agree bit-for-bit (the digest-parity
    contract)."""
    mono = TpuSpfBackend()
    part = TpuSpfBackend(partition_threshold=1, partition_max_part=12)
    oracle = ScalarSpfBackend()
    for seed in range(3):
        topo = tied(seed)
        a = part.compute(topo)
        assert_same(a, mono.compute(topo), tag=("mono", seed))
        assert_same(a, oracle.compute(topo), tag=("oracle", seed))


def test_partitioned_multipath_k_sweep():
    part = TpuSpfBackend(partition_threshold=1, partition_max_part=12)
    oracle = ScalarSpfBackend()
    for k in (1, 2, 8):
        for seed in (5, 6):
            topo = tied(seed)
            res = part.compute(topo, multipath_k=k)
            ref = oracle.compute(topo, multipath_k=k)
            assert_same(res, ref, tag=(k, seed))
            if k > 1:
                # Somebody actually has multiple equal-cost parents.
                ecmp = (res.pdist == res.dist[:, None]) & (
                    res.parents < topo.n_vertices
                )
                assert (ecmp.sum(axis=1) > 1).any()


def test_partitioned_whatif_masks_bit_identical():
    part = TpuSpfBackend(partition_threshold=1, partition_max_part=12)
    oracle = ScalarSpfBackend()
    topo = tied(7)
    masks = whatif_link_failure_masks(topo, 6, seed=7)
    got = part.compute_whatif(topo, masks)
    want = oracle.compute_whatif(topo, masks)
    for i, (g, w) in enumerate(zip(got, want)):
        assert_same(g, w, tag=("whatif", i))


# ------------------------------------------------------------ DeltaPath


def test_partitioned_delta_chain_crosses_boundaries():
    """A chain of weight deltas — intra-partition AND cut-edge
    re-costs — rides the partitioned incremental path
    (``holo_spf_delta_total{path=partitioned-incremental}``) with every
    step bit-identical to the oracle, and intra-partition steps
    re-solve a bounded partition subset (the Bounded-Dijkstra radius
    claim), all under the armed donation guard."""
    oracle = ScalarSpfBackend()
    with donation_guarded():
        part = TpuSpfBackend(partition_threshold=1, partition_max_part=12)
        topo = tied(11)
        part.compute(topo)  # roots the chain, records the solve state
        res = part.partition_residents()[0]
        n_parts = res.plan.n_parts
        assert n_parts >= 3, "cut too coarse for a bounded-radius claim"
        cutset = set(res.plan.cut_eid.tolist())
        intra = [e for e in range(topo.n_edges) if e not in cutset]
        cut = sorted(cutset)
        before = delta_count("partitioned-incremental")
        bounded_seen = False
        cur = topo
        picks = [intra[0], cut[0], intra[len(intra) // 2], cut[-1], intra[-1]]
        for step, e in enumerate(picks):
            nxt = clone(cur, cost={e: int(cur.edge_cost[e]) + 1 + step})
            delta = diff_topologies(cur, nxt)
            assert delta is not None
            nxt.link_delta(delta)
            got = part.compute(nxt)
            assert_same(got, oracle.compute(nxt), tag=("delta", step, e))
            if e in cutset:
                # Cut-edge re-cost: the skeleton moves, the affected
                # closure may grow — but the chain must stay served.
                pass
            elif res.last_resolved < n_parts:
                bounded_seen = True
            cur = nxt
        after = delta_count("partitioned-incremental")
        assert after - before >= len(picks), "chain fell off the delta path"
        assert bounded_seen, (
            "no intra-partition delta re-solved a strict partition subset"
        )


def test_partitioned_delta_structural_falls_back_to_remarshal():
    """A structural delta on a CUT edge (halo/skeleton geometry change)
    is not absorbable in place: the resident re-marshals and the next
    full partitioned solve still matches the oracle."""
    oracle = ScalarSpfBackend()
    part = TpuSpfBackend(partition_threshold=1, partition_max_part=12)
    topo = tied(13)
    part.compute(topo)
    res = part.partition_residents()[0]
    e = int(res.plan.cut_eid[0])
    s, d = int(topo.edge_src[e]), int(topo.edge_dst[e])
    keep = ~(
        ((topo.edge_src == s) & (topo.edge_dst == d))
        | ((topo.edge_src == d) & (topo.edge_dst == s))
    )
    nxt = clone(topo, keep=keep)
    delta = diff_topologies(topo, nxt)
    if delta is not None:
        nxt.link_delta(delta)
    assert_same(part.compute(nxt), oracle.compute(nxt), tag="cut-struct")


# ----------------------------------------------- fallback + mesh arms


def test_partitioned_breaker_fallback_bit_identical():
    """Forced dispatch failures serve the partitioned result from the
    scalar oracle — bit-identical, chain disposition counted."""
    topo = tied(17)
    want = ScalarSpfBackend().compute(topo, multipath_k=2)
    breaker = CircuitBreaker("part-test", failure_threshold=10)
    part = TpuSpfBackend(
        breaker=breaker, partition_threshold=1, partition_max_part=12
    )
    plan = FaultPlan(seed=1, dispatch_fail={"spf.dispatch": 2})
    with inject(FaultInjector(plan)) as inj:
        r1 = part.compute(topo, multipath_k=2)
        r2 = part.compute(topo, multipath_k=2)
    assert inj.injected["spf.dispatch"] == 2
    assert_same(r1, want, "fallback-1")
    assert_same(r2, want, "fallback-2")


def test_partitioned_sharded_mesh_bit_identical():
    """Under a forced multi-device batch mesh the partition axis rides
    the batch sharding; results stay byte-identical to the oracle."""
    oracle = ScalarSpfBackend()
    mesh = configure_process_mesh(None, 1)  # all devices on batch
    try:
        part = TpuSpfBackend(
            partition_threshold=1,
            partition_parts=int(mesh.shape["batch"]),  # divides batch
        )
        for seed in (19, 23):
            topo = tied(seed)
            assert_same(
                part.compute(topo),
                oracle.compute(topo),
                tag=("mesh", seed),
            )
    finally:
        reset_process_mesh()
    del mesh


def test_partitioned_hinted_topology_end_to_end():
    """A native partition hint (the protocol-seam contract) drives the
    cut end to end through the backend and survives mutation chains."""
    oracle = ScalarSpfBackend()
    part = TpuSpfBackend(partition_threshold=1)
    topo = grid_topology(6, 8, max_cost=6, seed=29)
    hint = (np.arange(topo.n_vertices) * 4 // topo.n_vertices).astype(
        np.int32
    )
    topo.partition_hint = hint
    assert_same(part.compute(topo), oracle.compute(topo), tag="hint")
    res = part.partition_residents()[0]
    assert res.plan.n_parts == 4
    # The hint rides mutation clones: the chain keeps its cut.
    nxt = clone(topo, cost={0: int(topo.edge_cost[0]) + 3})
    delta = diff_topologies(topo, nxt)
    assert delta is not None, "hint must not break delta linking"
    nxt.link_delta(delta)
    assert_same(part.compute(nxt), oracle.compute(nxt), tag="hint-delta")


def test_partitioned_disconnected_and_tiny_graphs():
    """Edge shapes: disconnected components (INF lanes), a partition
    with no cut edges, and graphs smaller than the partition target."""
    oracle = ScalarSpfBackend()
    part = TpuSpfBackend(partition_threshold=1, partition_max_part=4)
    # Two disconnected grids: the root's component resolves, the other
    # stays INF/unreachable — sentinel contract preserved.
    g = grid_topology(3, 4, max_cost=5, seed=31)
    n = g.n_vertices
    iso = Topology(
        n_vertices=n + 5,
        is_router=np.concatenate([g.is_router, np.ones(5, bool)]),
        edge_src=g.edge_src,
        edge_dst=g.edge_dst,
        edge_cost=g.edge_cost,
        edge_direct_atom=g.edge_direct_atom,
        root=g.root,
    )
    assert_same(part.compute(iso), oracle.compute(iso), tag="disconnected")
    tiny = grid_topology(2, 2, max_cost=3, seed=37)
    assert_same(part.compute(tiny), oracle.compute(tiny), tag="tiny")
