"""Full blocked SPF (interpret mode): bit-identical parity with the scalar
oracle on all four output planes (dist/parent/hops/nexthop bitmasks)."""

import numpy as np
import pytest

from holo_tpu.ops.blocked_spf import (
    bfs_permutation,
    failed_edges_perm,
    marshal_block_spf,
    whatif_spf_blocked,
)
from holo_tpu.spf.backend import ScalarSpfBackend
from holo_tpu.spf.synth import random_ospf_topology, whatif_link_failure_masks


def _assert_parity(topo, masks, permute=True, n_atoms=64):
    g = marshal_block_spf(topo, n_atoms=n_atoms, permute=permute)
    perm_of = np.asarray(g.orig2perm)
    fdst, fid = failed_edges_perm(perm_of, topo, masks)
    out = whatif_spf_blocked(g, fdst, fid, interpret=True)
    dist = np.asarray(out.dist)
    parent = np.asarray(out.parent)
    hops = np.asarray(out.hops)
    nh = np.asarray(out.nexthops)
    scalar = ScalarSpfBackend(n_atoms=n_atoms).compute_whatif(topo, masks)
    for b, s in enumerate(scalar):
        np.testing.assert_array_equal(s.dist, dist[b], err_msg=f"dist b={b}")
        np.testing.assert_array_equal(
            s.parent, parent[b], err_msg=f"parent b={b}"
        )
        np.testing.assert_array_equal(s.hops, hops[b], err_msg=f"hops b={b}")
        np.testing.assert_array_equal(
            s.nexthop_words, nh[b], err_msg=f"nexthops b={b}"
        )


@pytest.mark.parametrize("seed", range(3))
def test_blocked_full_parity_whatif(seed):
    topo = random_ospf_topology(
        n_routers=260, n_networks=40, extra_p2p=400, seed=seed
    )
    masks = whatif_link_failure_masks(topo, n_scenarios=6, seed=seed + 7)
    _assert_parity(topo, masks)


def test_blocked_full_parity_unpermuted():
    topo = random_ospf_topology(n_routers=120, n_networks=30, seed=9)
    masks = whatif_link_failure_masks(topo, n_scenarios=4, seed=2)
    _assert_parity(topo, masks, permute=False)


def test_blocked_full_no_failures():
    topo = random_ospf_topology(n_routers=90, n_networks=20, seed=3)
    masks = np.ones((2, topo.n_edges), bool)
    _assert_parity(topo, masks)


def test_blocked_full_multi_failure():
    topo = random_ospf_topology(n_routers=80, n_networks=10, seed=5)
    masks = np.ones((3, topo.n_edges), bool)
    rng = np.random.default_rng(11)
    pair = {
        (int(topo.edge_src[e]), int(topo.edge_dst[e])): e
        for e in range(topo.n_edges)
    }
    for b in (1, 2):
        for _ in range(2):
            e = int(rng.integers(0, topo.n_edges))
            masks[b, e] = False
            rev = pair.get((int(topo.edge_dst[e]), int(topo.edge_src[e])))
            if rev is not None:
                masks[b, rev] = False
    _assert_parity(topo, masks)


def test_bfs_permutation_reduces_blocks():
    """The point of the BFS ordering: fewer nonzero S x S block pairs."""
    topo = random_ospf_topology(
        n_routers=1500, n_networks=200, extra_p2p=2500, seed=1
    )
    g_perm = marshal_block_spf(topo, permute=True)
    g_id = marshal_block_spf(topo, permute=False)
    assert g_perm.w.shape[0] <= g_id.w.shape[0]
    perm = bfs_permutation(topo)
    assert perm[topo.root] == 0
    assert sorted(perm.tolist()) == list(range(topo.n_vertices))


def test_backend_blocked_engine_parity_and_fallback():
    import jax

    jax.config.update("jax_platforms", "cpu")
    from holo_tpu.ops.graph import Topology
    from holo_tpu.spf.backend import TpuSpfBackend

    topo = random_ospf_topology(n_routers=150, n_networks=30, seed=4)
    masks = whatif_link_failure_masks(topo, n_scenarios=4, seed=5)
    from functools import partial

    from holo_tpu import telemetry
    from holo_tpu.ops.blocked_spf import whatif_spf_blocked

    def interpreted(backend):
        # Pallas interpret mode is asked for by name: no production
        # path selects it from the backend it happens to find.
        backend._jit_blocked = jax.jit(
            partial(whatif_spf_blocked, interpret=True)
        )
        return backend

    be = interpreted(TpuSpfBackend(engine="blocked"))
    scalar = ScalarSpfBackend().compute_whatif(topo, masks)
    for s, t in zip(scalar, be.compute_whatif(topo, masks)):
        np.testing.assert_array_equal(s.dist, t.dist)
        np.testing.assert_array_equal(s.parent, t.parent)
        np.testing.assert_array_equal(s.hops, t.hops)
        np.testing.assert_array_equal(s.nexthop_words, t.nexthop_words)
    one = be.compute(topo)
    np.testing.assert_array_equal(
        one.dist, ScalarSpfBackend().compute(topo).dist
    )
    # parallel (src,dst) edges: the blocked preconditions fail LOUDLY —
    # the device arm raises, the breaker serves the scalar oracle and
    # counts it; the gather engine never runs under the blocked name.
    par = Topology(
        n_vertices=3,
        is_router=np.ones(3, bool),
        edge_src=np.array([0, 0, 1, 1, 2, 0], np.int32),
        edge_dst=np.array([1, 1, 0, 2, 1, 2], np.int32),
        edge_cost=np.array([1, 2, 1, 1, 1, 9], np.int32),
        root=0,
    )
    import pytest

    with pytest.raises(ValueError, match="parallel"):
        TpuSpfBackend(engine="blocked").prepare_blocked(par)

    def fallbacks():
        return sum(
            telemetry.snapshot("holo_resilience_fallback_total").values()
        )

    before = fallbacks()
    bad = interpreted(TpuSpfBackend(engine="blocked"))
    got = bad.compute(par)
    np.testing.assert_array_equal(got.dist, ScalarSpfBackend().compute(par).dist)
    assert fallbacks() == before + 1
    assert "ValueError" in bad.breaker.last_error


def test_interpret_mode_is_never_chosen_from_the_backend():
    """Interpret mode is off unless a caller names it (ISSUE 21): the
    default must not depend on ``jax.default_backend()``."""
    import inspect

    from holo_tpu.ops.blocked import whatif_distances_blocked

    for fn in (whatif_spf_blocked, whatif_distances_blocked):
        assert inspect.signature(fn).parameters["interpret"].default is False
        assert "default_backend" not in inspect.getsource(fn)
