"""Sharded multi-chip SPF through the REAL dispatch path (ISSUE 8).

tests/test_parallel.py proves the mesh/layout scaffolding against the
scalar oracle; THIS suite proves the production promotion: with a
process mesh installed (`parallel.configure_process_mesh`, what the
daemon does at boot from ``[parallel]``), `TpuSpfBackend` and
`FrrEngine` dispatch sharded — and their output stays byte-identical
to both the single-device path and the scalar oracle, under
``jax.transfer_guard("disallow")``.  The suite runs on the 8-device
virtual CPU mesh the conftest forces (the same
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` shape the
acceptance criteria name).
"""

from contextlib import contextmanager

import numpy as np
import pytest

from holo_tpu import telemetry
from holo_tpu.frr.manager import FrrEngine
from holo_tpu.ops.graph import diff_topologies
from holo_tpu.ops.spf_engine import shared_graph_cache
from holo_tpu.parallel.mesh import (
    configure_process_mesh,
    process_mesh,
    reset_process_mesh,
)
from holo_tpu.spf.backend import ScalarSpfBackend, TpuSpfBackend
from holo_tpu.spf.synth import (
    clone_topology as clone,
    random_ospf_topology,
    whatif_link_failure_masks,
)
from holo_tpu.telemetry import profiling
from holo_tpu.testing import no_implicit_transfers

SPF_FIELDS = ("dist", "parent", "hops", "nexthop_words")
FRR_FIELDS = (
    "lfa_adj", "lfa_nodeprot", "rlfa_pq", "tilfa_p", "tilfa_q",
    "post_dist", "post_nh",
)


@contextmanager
def mesh_scope(n_batch=None, n_node=None, devices=None):
    """Install a process mesh for one test and ALWAYS uninstall after —
    the suite shares its process with every unsharded tier-1 test."""
    mesh = configure_process_mesh(n_batch, n_node, devices)
    try:
        yield mesh
    finally:
        reset_process_mesh()


@pytest.fixture(autouse=True)
def _no_leaked_mesh():
    yield
    assert process_mesh() is None, "a test leaked the process mesh"
    reset_process_mesh()


def assert_spf_equal(ref, got, msg=""):
    for f in SPF_FIELDS:
        np.testing.assert_array_equal(
            getattr(ref, f), getattr(got, f), err_msg=f"{msg} {f}"
        )


def _topo(seed=3, routers=24):
    return random_ospf_topology(
        n_routers=routers, n_networks=8, extra_p2p=40, seed=seed
    )


def shard_count(kind: str) -> float:
    snap = telemetry.snapshot(prefix="holo_spf_shard_dispatch_total")
    return snap.get(f"holo_spf_shard_dispatch_total{{kind={kind}}}", 0.0)


# -- the acceptance scenario: 8-scenario what-if over 8 devices ----------


@pytest.mark.parametrize("mesh_shape", [(8, 1), (4, 2), (2, 4)])
def test_sharded_whatif_bit_identical_to_plain_and_oracle(mesh_shape):
    """An 8-scenario what-if batch through the real TpuSpfBackend
    sharded path is byte-identical to the single-device dispatch AND
    the scalar oracle, for every mesh factorization, under the
    transfer guard — and it demonstrably took the sharded path (the
    shard-dispatch counter moved)."""
    topo = _topo()
    masks = whatif_link_failure_masks(topo, n_scenarios=8, seed=4)
    with no_implicit_transfers():
        oracle = ScalarSpfBackend().compute_whatif(topo, masks)
        plain = TpuSpfBackend().compute_whatif(topo, masks)
        before = shard_count("whatif")
        with mesh_scope(*mesh_shape):
            shard = TpuSpfBackend().compute_whatif(topo, masks)
    assert shard_count("whatif") == before + 1
    for i, (o, p, s) in enumerate(zip(oracle, plain, shard)):
        assert_spf_equal(o, s, f"{mesh_shape} scen {i} vs oracle")
        assert_spf_equal(p, s, f"{mesh_shape} scen {i} vs plain")


def test_row_padding_and_sentinel_renorm():
    """node=4 over a 13-vertex LSDB pads graph rows to 16: results must
    still slice back to N with the no-parent sentinel renormalized to
    N (not the padded row count) — the bit-identity load-bearing
    detail of the readback contract."""
    topo = random_ospf_topology(n_routers=11, n_networks=2, seed=9)
    assert topo.n_vertices % 4 != 0  # the padding case, by construction
    with no_implicit_transfers():
        ref = ScalarSpfBackend().compute(topo)
        with mesh_scope(2, 4):
            got = TpuSpfBackend().compute(topo)
    assert got.dist.shape == (topo.n_vertices,)
    assert got.parent.max() <= topo.n_vertices
    assert_spf_equal(ref, got)


def test_odd_scenario_batch_pads_and_slices():
    """B=5 does not divide the 8-wide batch axis: the dispatch pads
    with no-failure scenarios and hands back exactly 5 results."""
    topo = _topo(seed=7)
    masks = whatif_link_failure_masks(topo, n_scenarios=5, seed=1)
    with no_implicit_transfers():
        oracle = ScalarSpfBackend().compute_whatif(topo, masks)
        with mesh_scope(8, 1):
            got = TpuSpfBackend().compute_whatif(topo, masks)
    assert len(got) == 5
    for i, (o, s) in enumerate(zip(oracle, got)):
        assert_spf_equal(o, s, f"scen {i}")


def test_sharded_multiroot_parity():
    topo = random_ospf_topology(n_routers=11, n_networks=2, seed=9)
    roots = np.asarray([0, 1, 3], np.int32)  # odd count: batch-padded
    with no_implicit_transfers():
        ref = ScalarSpfBackend().compute_multiroot(topo, roots)
        with mesh_scope(2, 4):
            got = TpuSpfBackend().compute_multiroot(topo, roots)
    for f in ("dist", "parent", "hops"):
        assert got.dist.shape == (3, topo.n_vertices)
        np.testing.assert_array_equal(
            getattr(ref, f), getattr(got, f), err_msg=f
        )


def test_one_device_mesh_matches_plain_path():
    """A 1-device mesh (what a one-chip host boots with) runs the
    mesh-aware code path and must produce the plain path's bits."""
    import jax

    topo = _topo(seed=5)
    masks = whatif_link_failure_masks(topo, n_scenarios=4, seed=2)
    with no_implicit_transfers():
        plain = TpuSpfBackend().compute_whatif(topo, masks)
        with mesh_scope(1, 1, devices=jax.devices()[:1]):
            got = TpuSpfBackend().compute_whatif(topo, masks)
    for p, s in zip(plain, got):
        assert_spf_equal(p, s)


def test_without_a_mesh_no_sharded_program_is_built_and_the_shard_seam_is_skipped():
    """The default daemon has no ``[parallel]`` mesh: every dispatch
    kind then takes the single-device branch — no sharded jit is built,
    no shard dispatch is counted, and the ``spf.shard`` chaos seam is
    never reached (an armed failure there stays unconsumed)."""
    from holo_tpu.resilience.faults import FaultPlan, inject

    assert process_mesh() is None
    topo = _topo(seed=7)
    masks = whatif_link_failure_masks(topo, n_scenarios=4, seed=2)
    roots = np.arange(4, dtype=np.int32)
    be, oracle = TpuSpfBackend(), ScalarSpfBackend()
    counts = {k: shard_count(k) for k in ("one", "whatif", "multiroot")}
    with no_implicit_transfers():
        with inject(FaultPlan(dispatch_fail={"spf.shard": 1})) as inj:
            one = be.compute(topo)
            batch = be.compute_whatif(topo, masks)
            multi = be.compute_multiroot(topo, roots)
    assert "spf.shard" not in inj.injected
    assert be._shard_jits == {}
    assert {k: shard_count(k) for k in counts} == counts
    assert be.breaker.consecutive_failures == 0
    assert_spf_equal(oracle.compute(topo), one)
    for ref, got in zip(oracle.compute_whatif(topo, masks), batch):
        assert_spf_equal(ref, got)
    np.testing.assert_array_equal(
        oracle.compute_multiroot(topo, roots).dist, multi.dist
    )


# -- DeltaPath composes with sharding ------------------------------------


def test_delta_chain_on_sharded_resident_stays_incremental():
    """A weight-delta chain against a node-sharded resident graph is
    served by the in-place apply + seeded incremental kernel (not a
    re-marshal), bit-identical to the oracle at every step."""
    rng = np.random.default_rng(13)
    topo = _topo(seed=13)
    with no_implicit_transfers():
        with mesh_scope(4, 2):
            be = TpuSpfBackend()
            be.compute(topo)
            before = telemetry.snapshot(prefix="holo_spf_delta")
            cur = topo
            for step in range(4):
                e = int(rng.integers(0, cur.n_edges))
                nxt = clone(cur, cost={e: int(rng.integers(1, 64))})
                d = diff_topologies(cur, nxt)
                if d is not None:
                    nxt.link_delta(d)
                got = be.compute(nxt)
                assert_spf_equal(
                    ScalarSpfBackend().compute(nxt), got, f"step {step}"
                )
                cur = nxt
            after = telemetry.snapshot(prefix="holo_spf_delta")
            stats = shared_graph_cache().stats()

    def count(snap, needle):
        return sum(v for k, v in snap.items() if needle in k)

    assert (
        count(after, "path=incremental") > count(before, "path=incremental")
    ), "the sharded resident must serve the chain incrementally"
    assert stats["sharded-entries"] >= 1
    assert stats["mesh"] == {"batch": 4, "node": 2}


# -- FRR all-roots plane --------------------------------------------------


def test_sharded_frr_bit_identical_to_plain_and_oracle():
    topo = random_ospf_topology(
        n_routers=13, n_networks=3, extra_p2p=20, seed=5
    )
    with no_implicit_transfers():
        ref = FrrEngine("scalar").compute(topo)
        plain = FrrEngine("tpu").compute(topo)
        before = shard_count("frr")
        with mesh_scope(4, 2):
            shard = FrrEngine("tpu").compute(topo)
    assert shard_count("frr") == before + 1
    for f in FRR_FIELDS:
        np.testing.assert_array_equal(
            getattr(ref, f), getattr(shard, f), err_msg=f"{f} vs oracle"
        )
        np.testing.assert_array_equal(
            getattr(plain, f), getattr(shard, f), err_msg=f"{f} vs plain"
        )


# -- observability satellites --------------------------------------------


def test_per_device_stage_profiling_splits_by_device():
    """A profiled sharded dispatch emits holo_profile_stage_seconds
    device-phase rows labeled per device id — one per mesh device —
    alongside the whole-span device='-' row."""
    topo = _topo(seed=11)
    masks = whatif_link_failure_masks(topo, n_scenarios=8, seed=3)

    def device_rows():
        snap = telemetry.snapshot(prefix="holo_profile_stage_seconds")
        return {
            k: v["count"]
            for k, v in snap.items()
            if "site=spf.whatif,stage=device" in k
        }

    before = device_rows()
    profiling.set_device_profiling(True)
    try:
        with mesh_scope(4, 2):
            TpuSpfBackend().compute_whatif(topo, masks)
    finally:
        profiling.set_device_profiling(False)
    after = device_rows()
    for dev in range(8):
        key = (
            "holo_profile_stage_seconds"
            f"{{site=spf.whatif,stage=device,device={dev}}}"
        )
        assert after.get(key, 0) == before.get(key, 0) + 1, key
    whole = (
        "holo_profile_stage_seconds"
        "{site=spf.whatif,stage=device,device=-}"
    )
    assert after.get(whole, 0) == before.get(whole, 0) + 1


def test_cache_stats_per_device_placement_on_gnmi_leaf():
    """Satellite: the spf-graph-cache leaf carries mesh + per-device
    entries/rows/bytes placement for sharded residents."""
    from holo_tpu.telemetry.provider import TelemetryStateProvider

    topo = _topo(seed=17)
    with mesh_scope(2, 4):
        TpuSpfBackend().compute(topo)
        state = TelemetryStateProvider().get_state()
        leaf = state["holo-telemetry"]["spf-graph-cache"]
        assert leaf["sharded-entries"] >= 1
        assert leaf["mesh"] == {"batch": 2, "node": 4}
        per_dev = leaf["per-device"]
        assert len(per_dev) == 8  # every mesh device holds a row block
        rows_total = sum(d["rows"] for d in per_dev.values())
        for d in per_dev.values():
            assert d["entries"] >= 1
            assert d["bytes"] > 0
        # node=4 row-shards the padded rows; batch=2 replicates them.
        assert rows_total % 2 == 0
