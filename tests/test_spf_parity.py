"""Bit-identical parity: TPU tensor SPF vs scalar reference Dijkstra.

The acceptance gate from BASELINE.md: every (distance, hops, first-parent,
ECMP next-hop set) must match the scalar reference semantics exactly, across
random OSPF-style topologies and what-if link-failure batches.
"""

import numpy as np
import pytest

from holo_tpu.spf.backend import ScalarSpfBackend, TpuSpfBackend
from holo_tpu.spf.synth import random_ospf_topology, whatif_link_failure_masks
from holo_tpu.testing import no_implicit_transfers

N_ATOMS = 64


@pytest.fixture(autouse=True)
def _transfer_sanitizer():
    """Every parity test runs under jax.transfer_guard('disallow'):
    only the backend's sanctioned marshal/unmarshal boundaries may
    move data between host and device (holo-lint runtime mode)."""
    with no_implicit_transfers():
        yield

# Every gather-path fixpoint formulation must be bit-identical: 'seq'
# the staged-loop form (production default, both here and in
# spf_whatif_batch), 'fused'/'packed' the one-loop variants, 'hybrid'
# the dist-loop + packed hops/next-hop loop.
ENGINES = ["fused", "packed", "seq", "hybrid"]


def assert_parity(topo, scalar_res, tpu_res):
    np.testing.assert_array_equal(scalar_res.dist, tpu_res.dist, err_msg="dist")
    np.testing.assert_array_equal(scalar_res.hops, tpu_res.hops, err_msg="hops")
    np.testing.assert_array_equal(scalar_res.parent, tpu_res.parent, err_msg="parent")
    np.testing.assert_array_equal(
        scalar_res.nexthop_words, tpu_res.nexthop_words, err_msg="nexthops"
    )


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("seed", range(8))
@pytest.mark.parametrize(
    "shape",
    [
        dict(n_routers=12, n_networks=0),
        dict(n_routers=10, n_networks=4),
        dict(n_routers=40, n_networks=10, extra_p2p=60),
    ],
)
def test_single_spf_parity(seed, shape, engine):
    topo = random_ospf_topology(seed=seed, **shape)
    scalar = ScalarSpfBackend(N_ATOMS).compute(topo)
    tpu = TpuSpfBackend(N_ATOMS, one_engine=engine).compute(topo)
    assert_parity(topo, scalar, tpu)


def test_lone_router_edgeless():
    """Regression: E=0 graphs must not crash the edge-mask gather."""
    from holo_tpu.ops.graph import Topology

    topo = Topology(
        n_vertices=1,
        is_router=np.ones(1, bool),
        edge_src=np.zeros(0, np.int32),
        edge_dst=np.zeros(0, np.int32),
        edge_cost=np.zeros(0, np.int32),
        root=0,
    )
    scalar = ScalarSpfBackend(N_ATOMS).compute(topo)
    tpu = TpuSpfBackend(N_ATOMS).compute(topo)
    assert_parity(topo, scalar, tpu)


def test_disconnected_component_unreachable():
    topo = random_ospf_topology(n_routers=8, n_networks=2, seed=1)
    # Fail every edge touching the root: everything except root unreachable.
    mask = np.ones(topo.n_edges, bool)
    for e in range(topo.n_edges):
        if topo.edge_src[e] == topo.root or topo.edge_dst[e] == topo.root:
            mask[e] = False
    scalar = ScalarSpfBackend(N_ATOMS).compute(topo, mask)
    tpu = TpuSpfBackend(N_ATOMS).compute(topo, mask)
    assert_parity(topo, scalar, tpu)
    from holo_tpu.ops.graph import INF

    unreachable = np.arange(topo.n_vertices) != topo.root
    assert (tpu.dist[unreachable] == INF).all()


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("seed", range(3))
def test_whatif_batch_parity(seed, engine):
    topo = random_ospf_topology(n_routers=16, n_networks=5, seed=seed)
    masks = whatif_link_failure_masks(topo, n_scenarios=8, seed=seed)
    scalar = ScalarSpfBackend(N_ATOMS).compute_whatif(topo, masks)
    tpu = TpuSpfBackend(N_ATOMS, one_engine=engine).compute_whatif(topo, masks)
    for s, t in zip(scalar, tpu):
        assert_parity(topo, s, t)


def test_ecmp_nexthop_sets_union():
    """Two equal-cost paths from the root must union their atoms."""
    from holo_tpu.ops.graph import Topology
    from holo_tpu.spf.synth import assign_direct_atoms

    # root(0) -> a(1) -> d(3), root -> b(2) -> d: both cost 2.
    src = np.array([0, 1, 0, 2, 1, 3, 2, 3], np.int32)
    dst = np.array([1, 0, 2, 0, 3, 1, 3, 2], np.int32)
    cost = np.array([1, 1, 1, 1, 1, 1, 1, 1], np.int32)
    topo = Topology(
        n_vertices=4,
        is_router=np.ones(4, bool),
        edge_src=src,
        edge_dst=dst,
        edge_cost=cost,
        root=0,
    )
    assign_direct_atoms(topo)
    scalar = ScalarSpfBackend(N_ATOMS).compute(topo)
    tpu = TpuSpfBackend(N_ATOMS).compute(topo)
    assert_parity(topo, scalar, tpu)
    # d (vertex 3) must carry both root links' atoms.
    assert bin(int(tpu.nexthop_words[3, 0])).count("1") == 2


def test_cache_invalidation_on_touch():
    """In-place cost mutation + touch() must re-marshal the device graph."""
    topo = random_ospf_topology(n_routers=10, n_networks=2, seed=5)
    be = TpuSpfBackend(N_ATOMS)
    be.compute(topo)
    topo.edge_cost[:] = 1
    topo.touch()
    tpu = be.compute(topo)
    scalar = ScalarSpfBackend(N_ATOMS).compute(topo)
    assert_parity(topo, scalar, tpu)


def test_atom_overflow_rejected():
    """More atoms than the bitmask width must raise, not corrupt."""
    from holo_tpu.ops.graph import build_ell

    topo = random_ospf_topology(n_routers=12, n_networks=4, seed=2)
    with pytest.raises(ValueError, match="atoms"):
        build_ell(topo, n_atoms=1)


def test_forced_dispatch_failure_scalar_fallback_bit_identical():
    """ISSUE 4 satellite: a forced mid-batch dispatch failure must be
    served by the breaker's scalar fallback with results byte-identical
    to an uninterrupted scalar run — the RIB cannot tell the difference.
    The next healthy dispatch runs on the device again (closed breaker,
    failure streak reset)."""
    from holo_tpu.resilience import CircuitBreaker, FaultPlan, inject

    topo = random_ospf_topology(n_routers=14, n_networks=4, seed=3)
    masks = whatif_link_failure_masks(topo, n_scenarios=6, seed=3)
    scalar = ScalarSpfBackend(N_ATOMS).compute_whatif(topo, masks)
    be = TpuSpfBackend(
        N_ATOMS, breaker=CircuitBreaker("spf-parity-fallback")
    )
    with inject(FaultPlan(dispatch_fail={"spf.dispatch": 1})) as inj:
        got = be.compute_whatif(topo, masks)
    assert inj.injected["spf.dispatch"] == 1, "the failure must have fired"
    for s, t in zip(scalar, got):
        assert_parity(topo, s, t)
    assert be.breaker.consecutive_failures == 1
    assert be.breaker.state == "closed"
    got2 = be.compute_whatif(topo, masks)  # healthy: device path again
    for s, t in zip(scalar, got2):
        assert_parity(topo, s, t)
    assert be.breaker.consecutive_failures == 0


def test_closed_breaker_costs_a_healthy_dispatch_two_clock_reads_and_no_oracle():
    """The healthy path of the guard: over N device dispatches a closed
    breaker reads its clock twice per dispatch (the deadline window)
    and nothing else — the scalar oracle is never called, no transition
    or failure is emitted, and the circuit ends closed with no streak.
    Bypassed (``enabled=False``) it reads no clock at all."""
    from holo_tpu import telemetry
    from holo_tpu.resilience import CircuitBreaker

    reads = []

    def clock():
        reads.append(1)
        return 0.0

    def poisoned(*_a, **_k):
        raise AssertionError("scalar oracle called on the healthy path")

    def breaker_series():
        return {
            k: v
            for fam in ("holo_resilience_breaker_transitions_total",
                        "holo_resilience_breaker_failures_total",
                        "holo_resilience_fallback_total")
            for k, v in telemetry.snapshot(prefix=fam).items()
        }

    topo = random_ospf_topology(n_routers=14, n_networks=4, seed=5)
    ref = ScalarSpfBackend(N_ATOMS).compute(topo)
    be = TpuSpfBackend(
        N_ATOMS, breaker=CircuitBreaker("spf-parity-healthy", clock=clock)
    )
    be._oracle.compute = poisoned
    before = breaker_series()
    for _ in range(5):
        assert_parity(topo, ref, be.compute(topo))
    assert len(reads) == 2 * 5
    assert breaker_series() == before
    assert be.breaker.state == "closed"
    assert be.breaker.consecutive_failures == 0
    assert be.breaker.last_error is None

    bypass = TpuSpfBackend(
        N_ATOMS,
        breaker=CircuitBreaker(
            "spf-parity-bypass", clock=poisoned, enabled=False
        ),
    )
    assert_parity(topo, ref, bypass.compute(topo))


def test_multiroot_matches_per_root():
    topo = random_ospf_topology(n_routers=12, n_networks=3, seed=7)
    roots = np.array(
        [i for i in range(topo.n_vertices) if topo.is_router[i]][:4], np.int32
    )
    backend = TpuSpfBackend(N_ATOMS)
    batch = backend.compute_multiroot(topo, roots)
    for i, r in enumerate(roots):
        t2 = random_ospf_topology(n_routers=12, n_networks=3, seed=7)
        t2.root = int(r)
        from holo_tpu.spf.synth import assign_direct_atoms

        assign_direct_atoms(t2)
        # Distances are root-dependent but atom tables differ per root, so
        # compare distances only (next hops are per-root-marshaled).
        single = ScalarSpfBackend(N_ATOMS).compute(t2)
        np.testing.assert_array_equal(single.dist, np.asarray(batch.dist[i]))
