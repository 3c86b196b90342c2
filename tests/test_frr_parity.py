"""Bit-identical parity: batched FRR kernel vs scalar oracle.

Acceptance gate for the FRR subsystem (ISSUE 1): every backup table —
LFA pick + node-protection flag, remote-LFA PQ coverage, TI-LFA (P, Q)
segments, post-convergence dist/next-hops — must match the scalar
oracle exactly over the synth topology family (ring, grid, fat-tree,
random, with and without LAN pseudo-nodes).
"""

import numpy as np
import pytest

from holo_tpu.frr.manager import (
    REPAIR_KINDS,
    FrrConfig,
    FrrEngine,
    repair_picks,
    repair_plan,
)
from holo_tpu.ops.graph import INF
from holo_tpu.spf.synth import (
    fat_tree_topology,
    grid_topology,
    hub_topology,
    random_ospf_topology,
    ring_topology,
)
from holo_tpu.testing import no_implicit_transfers

N_ATOMS = 64


@pytest.fixture(autouse=True)
def _transfer_sanitizer():
    """Every FRR parity test runs under jax.transfer_guard('disallow'):
    only the engine's sanctioned marshal/unmarshal boundary may move
    data between host and device (holo-lint runtime mode)."""
    with no_implicit_transfers():
        yield


def assert_table_parity(scalar, tpu):
    for name in (
        "lfa_adj",
        "lfa_nodeprot",
        "rlfa_pq",
        "tilfa_p",
        "tilfa_q",
        "post_dist",
        "post_nh",
    ):
        np.testing.assert_array_equal(
            getattr(scalar, name), getattr(tpu, name), err_msg=name
        )


def _topos(seed):
    return {
        "ring": ring_topology(10, seed=seed),
        "grid": grid_topology(4, 4, seed=seed),
        "fat-tree": fat_tree_topology(k=4, seed=seed),
        "random": random_ospf_topology(
            n_routers=10, n_networks=3, seed=seed
        ),
        # Hubs with 20-30 in-edges: the compact graph's overflow plane
        # holds protected edges, DAG parents and ties.
        "hub": hub_topology(seed=seed),
    }


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("shape", ["ring", "grid", "fat-tree", "random", "hub"])
def test_frr_kernel_oracle_parity(seed, shape):
    topo = _topos(seed)[shape]
    scalar = FrrEngine("scalar", N_ATOMS).compute(topo)
    tpu = FrrEngine("tpu", N_ATOMS).compute(topo)
    assert_table_parity(scalar, tpu)


def test_ring_uniform_cost_needs_remote_repair():
    """The textbook rLFA case: a uniform-cost ring has destinations with
    no per-neighbor LFA; rLFA/TI-LFA must cover (nearly) all of them."""
    topo = ring_topology(8, max_cost=1, seed=0)  # uniform costs
    table = FrrEngine("scalar", N_ATOMS).compute(topo)
    eligible = table.post_dist < INF
    eligible[:, topo.root] = False
    lfa_only = (table.lfa_adj >= 0) & eligible
    assert lfa_only.sum() < eligible.sum(), "ring should defeat plain LFA"
    assert table.coverage() == 1.0, "rLFA/TI-LFA should cover the ring"


def test_resolve_backup_policy_order():
    """``repair_plan``'s order: LFA first (released on the candidate's
    own atom), remote LFA only where no LFA exists, nothing under a
    disarmed or LFA-only policy where only remote repairs exist."""
    topo = ring_topology(8, max_cost=1, seed=0)
    table = FrrEngine("scalar", N_ATOMS).compute(topo)
    fin = table.inputs
    kind, release, via0, _via1 = repair_plan(
        table, FrrConfig(enabled=True, remote_lfa=True, ti_lfa=True)
    )
    lfa_kind = repair_plan(table, FrrConfig(enabled=True))[0]
    assert not repair_plan(table, FrrConfig())[0].any()
    got_kinds = set()
    for l in range(table.n_links):
        for d in range(topo.n_vertices):
            if d == topo.root:
                continue
            k = REPAIR_KINDS[kind[l, d]]
            if k == "none":
                continue
            got_kinds.add(k)
            assert release[l, d] >= 0
            a = int(table.lfa_adj[l, d])
            if k == "lfa":
                assert release[l, d] == fin.adj_atom[a]
                assert via0[l, d] == fin.adj_nbr[a]
            else:
                # LFA-disabled policy must yield nothing where only
                # remote repairs exist.
                assert a < 0 and lfa_kind[l, d] == 0
            if k == "rlfa":
                assert via0[l, d] == table.rlfa_pq[l, d]
    assert "rlfa" in got_kinds or "ti-lfa" in got_kinds


@pytest.mark.parametrize("shape", ["ring", "grid", "random"])
def test_repair_picks_read_the_plan(shape):
    """``repair_picks``: one entry per (vertex, primary atom on a
    protected link) that the plan gives a repair, the plan's own."""
    from holo_tpu.spf.backend import ScalarSpfBackend

    topo = _topos(0)[shape]
    table = FrrEngine("scalar", N_ATOMS).compute(topo)
    plan = repair_plan(
        table, FrrConfig(enabled=True, remote_lfa=True, ti_lfa=True)
    )
    words = ScalarSpfBackend().compute(topo).nexthop_words
    got = {
        (v, a): (k, r, x, y)
        for v, a, k, r, x, y in zip(*(p.tolist() for p in repair_picks(
            table, plan, words
        )))
    }
    want = {}
    for v in range(topo.n_vertices):
        for a in range(32 * words.shape[1]):
            link = table.link_of_atom(a)
            if not (int(words[v, a // 32]) >> (a % 32)) & 1 or link is None:
                continue
            if plan[0][link, v]:
                want[(v, a)] = tuple(int(p[link, v]) for p in plan)
    assert want and got == want


def test_padding_is_result_neutral():
    """Growing the pad bucket must not change any table entry (the fuzz
    target's invariant, pinned here deterministically)."""
    from holo_tpu.frr.inputs import marshal_frr
    from holo_tpu.frr.scalar import frr_reference

    topo = random_ospf_topology(n_routers=8, n_networks=2, seed=4)
    a = frr_reference(topo, N_ATOMS, inputs=marshal_frr(topo, pad_multiple=1))
    b = frr_reference(topo, N_ATOMS, inputs=marshal_frr(topo, pad_multiple=16))
    assert_table_parity(a, b)
    # And through the device kernel, where pads actually enter the math.
    ta = FrrEngine("tpu", N_ATOMS).compute(topo)
    assert_table_parity(a, ta)


def test_forced_frr_dispatch_failure_scalar_fallback_bit_identical():
    """ISSUE 4 satellite (FRR side): a forced kernel-dispatch failure
    falls back to the oracle over the SAME marshaled inputs — every
    backup-table plane byte-identical to an uninterrupted scalar run."""
    from holo_tpu.resilience import CircuitBreaker, FaultPlan, inject

    topo = grid_topology(4, 4, seed=1)
    scalar = FrrEngine("scalar", N_ATOMS).compute(topo)
    eng = FrrEngine(
        "tpu", N_ATOMS, breaker=CircuitBreaker("frr-parity-fallback")
    )
    with inject(FaultPlan(dispatch_fail={"frr.dispatch": 1})) as inj:
        got = eng.compute(topo)
    assert inj.injected["frr.dispatch"] == 1
    assert_table_parity(scalar, got)
    assert eng.breaker.consecutive_failures == 1
    assert eng.breaker.state == "closed"
    got2 = eng.compute(topo)  # healthy: device kernel again
    assert_table_parity(scalar, got2)
    assert eng.breaker.consecutive_failures == 0


def test_lfa_never_uses_protected_interface():
    for seed in range(3):
        topo = random_ospf_topology(n_routers=9, n_networks=3, seed=seed)
        table = FrrEngine("scalar", N_ATOMS).compute(topo)
        fin = table.inputs
        for l in range(table.n_links):
            picks = table.lfa_adj[l]
            for a in picks[picks >= 0]:
                assert int(fin.adj_link[a]) != l


# -- the blocked distance rows (the kernel computes only the rows its
# selection reads; the oracle only the Dijkstra runs it reads) ----------


def _reversed_costs(topo, seed):
    """The same links with a cost of their own in each direction."""
    from holo_tpu.spf.synth import clone_topology

    rng = np.random.default_rng(seed)
    out = clone_topology(topo)
    out.edge_cost = rng.integers(1, 20, topo.n_edges).astype(np.int32)
    out.touch()
    return out


def _srlg(topo, seed):
    rng = np.random.default_rng(seed)
    topo.edge_srlg = rng.integers(0, 6, topo.n_edges).astype(np.uint32)
    topo.touch()
    return topo


def _petals(k: int, m: int, seed: int):
    """The root on ``k`` uniform-cost cycles of ``m`` routers each: every
    protected link has destinations only a remote repair reaches, and
    its own TI-LFA successor — ``2 k`` links, as many distinct S."""
    from holo_tpu.ops.graph import Topology
    from holo_tpu.spf.synth import assign_direct_atoms

    rng = np.random.default_rng(seed)
    src, dst = [], []
    for p in range(k):
        ring = [0, *range(1 + p * m, 1 + (p + 1) * m)]
        for a, b in zip(ring, ring[1:] + ring[:1]):
            src += [a, b]
            dst += [b, a]
    topo = Topology(
        n_vertices=1 + k * m, is_router=np.ones(1 + k * m, bool),
        edge_src=np.array(src, np.int32), edge_dst=np.array(dst, np.int32),
        edge_cost=rng.integers(1, 3, len(src)).astype(np.int32), root=0,
    )
    assign_direct_atoms(topo)
    return topo


BLOCKED_CASES = {
    # case: (topology, srlg_disjoint, node_protection, dyn block width)
    "srlg-policy": (lambda: _srlg(grid_topology(5, 5, seed=2), 2),
                    True, False, None),
    "node-protection-policy": (
        lambda: random_ospf_topology(n_routers=14, n_networks=3, seed=6),
        False, True, None),
    "successors-past-one-block": (lambda: _petals(4, 9, seed=5),
                                  False, False, 2),
    "reversed-costs": (
        lambda: _reversed_costs(
            random_ospf_topology(n_routers=16, extra_p2p=14, seed=8), 8),
        False, False, None),
    "reversed-costs-lan-srlg": (
        lambda: _srlg(_reversed_costs(
            random_ospf_topology(n_routers=12, n_networks=4, seed=9), 9), 9),
        True, True, 3),
}


@pytest.mark.parametrize("case", sorted(BLOCKED_CASES))
def test_blocked_rows_tables_bit_identical_to_oracle(case, monkeypatch):
    from holo_tpu.frr import kernel
    from holo_tpu.frr.scalar import frr_reference

    make, srlg, nodeprot, block = BLOCKED_CASES[case]
    if block is not None:
        # A narrower dynamic block: the successors then span several
        # blocks of the device's loop (a fresh engine compiles anew).
        monkeypatch.setattr(kernel, "DYN_BLOCK", block)
    topo = make()
    eng = FrrEngine("tpu", N_ATOMS)
    eng.set_policy(FrrConfig(
        enabled=True, engine="tpu", srlg_disjoint=srlg,
        node_protection=nodeprot,
    ))
    got = eng.compute(topo)
    ref = frr_reference(
        topo, N_ATOMS, srlg_disjoint=srlg, node_protection=nodeprot
    )
    assert_table_parity(ref, got)
    stats = got.stats
    assert stats["unmatched_links"] == 0 and stats["missing_rows"] == 0
    # Only the rows the selection reads: far fewer than the vertices
    # once the graph is larger than its root's neighbourhood.
    assert stats["rows_forward"] <= 1 + 2 * ref.n_links + ref.inputs.n_adj + (
        stats["successors"]
    )
    if case.startswith("successors"):
        assert stats["successors"] > block and stats["dyn_blocks"] > 1


def test_stats_count_the_rows_and_the_post_batch():
    """The rows' counters read what they read before the post batch
    moved onto the compact graph (pinned on one hub topology), and the
    post batch reports its rounds and the slots one round reads."""
    from holo_tpu.frr.inputs import rows_graph_shape

    from holo_tpu import telemetry

    topo = hub_topology(seed=0)
    before = telemetry.snapshot(prefix="holo_frr_post_rounds_total")
    stats = FrrEngine("tpu", N_ATOMS).compute(topo).stats
    after = telemetry.snapshot(prefix="holo_frr_post")
    assert {k: stats[k] for k in (
        "rows_forward", "rows_reverse", "rounds", "row_rounds_forward",
        "row_rounds_reverse",
    )} == {
        "rows_forward": 35, "rows_reverse": 9, "rounds": 17,
        "row_rounds_forward": 209, "row_rounds_reverse": 54,
    }
    h, k = rows_graph_shape(topo)
    assert (h, k) == (6, 23)
    # 50 vertices x 8 slots + 64 overflow rows x 24, times 8 link columns
    assert stats["post_slots"] == (50 * 8 + 64 * 24) * 8
    # dist, hops and two atom words: each fixpoint at least one round
    assert stats["post_rounds"] >= 4
    assert after["holo_frr_post_slots"] == stats["post_slots"]
    assert after["holo_frr_post_rounds_total"] - before.get(
        "holo_frr_post_rounds_total", 0.0
    ) == stats["post_rounds"]


def test_oracle_reads_rows_not_the_matrix():
    """The oracle's Dijkstra runs are the rows and columns it reads:
    at 60 ring routers a handful, not 60."""
    from holo_tpu.frr import scalar

    topo = ring_topology(60, max_cost=3, seed=1)
    runs = []
    real = scalar.LazyDistances

    class Counting(real):
        def __init__(self, t):
            super().__init__(t)
            runs.append(self)

    scalar.LazyDistances = Counting
    try:
        scalar.frr_reference(topo, N_ATOMS)
    finally:
        scalar.LazyDistances = real
    [dd] = runs
    assert 0 < dd.rows + dd.cols < 20
