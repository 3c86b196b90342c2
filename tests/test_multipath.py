"""Vectorized multipath (ISSUE 10): device multi-parent planes
bit-identical to the scalar multipath oracle — plain, DeltaPath
incremental, sharded-mesh and breaker-fallback arms, all under
``jax.transfer_guard("disallow")`` — plus the policy/consumption seams
(FRR SRLG + node-protection masks, max-paths route clamping, weighted
RIB install, RFC 8333 delayed flip, advisory what-if batching, and the
off-critical-path FRR force).
"""

from contextlib import contextmanager

import numpy as np
import pytest

from holo_tpu import pipeline, telemetry
from holo_tpu.frr.manager import FrrConfig, FrrEngine
from holo_tpu.frr.scalar import frr_reference
from holo_tpu.ops.graph import INF, MP_SAT, diff_topologies
from holo_tpu.parallel.mesh import (
    configure_process_mesh,
    reset_process_mesh,
)
from holo_tpu.resilience.breaker import CircuitBreaker
from holo_tpu.resilience.faults import FaultInjector, FaultPlan, inject
from holo_tpu.spf.backend import ScalarSpfBackend, TpuSpfBackend
from holo_tpu.spf.synth import (
    clone_topology as clone,
    random_ospf_topology,
    whatif_link_failure_masks,
)
from holo_tpu.testing import no_implicit_transfers

MP_FIELDS = ("parents", "pdist", "pweight", "npaths", "nh_weights")
ALL_FIELDS = ("dist", "parent", "hops", "nexthop_words") + MP_FIELDS


def tied(seed, n=36, nets=7, extra=50):
    """Random topology with a tiny cost universe: real ECMP ties."""
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


@contextmanager
def mesh_scope(n_batch=None, n_node=None):
    mesh = configure_process_mesh(n_batch, n_node)
    try:
        yield mesh
    finally:
        reset_process_mesh()


# ---------------------------------------------------------------- parity


def test_multipath_device_bit_identical_to_oracle():
    """Seeded property sweep: every multipath plane (parents, per-parent
    costs/weights, path counts, per-atom UCMP weights) AND the classic
    SpfTensors half are bit-identical to the scalar multipath oracle
    across widths, with real equal-cost ties in every graph."""
    oracle = ScalarSpfBackend()
    with no_implicit_transfers():
        tpu = TpuSpfBackend()
        for seed in range(4):
            topo = tied(seed)
            for k in (2, 3, 8):  # 3 exercises the pow2 pad (-> 4)
                res = tpu.compute(topo, multipath_k=k)
                ref = oracle.compute(topo, multipath_k=k)
                assert_same(res, ref, tag=(seed, k))
                # Width contract: pow2-padded parent-set planes.
                assert res.parents.shape[1] in (2, 4, 8)
                # Somebody actually has multiple equal-cost parents.
                ecmp = (res.pdist == res.dist[:, None]) & (
                    res.parents < topo.n_vertices
                )
                assert (ecmp.sum(axis=1) > 1).any()


def test_multipath_k1_is_the_unchanged_single_parent_dispatch():
    """multipath off (k=1): no planes, and byte-identical output to the
    pre-change call shape."""
    with no_implicit_transfers():
        tpu = TpuSpfBackend()
        topo = tied(9)
        plain = tpu.compute(topo)
        k1 = tpu.compute(topo, multipath_k=1)
        for f in MP_FIELDS:
            assert getattr(plain, f) is None and getattr(k1, f) is None
        for f in ("dist", "parent", "hops", "nexthop_words"):
            assert np.array_equal(getattr(plain, f), getattr(k1, f))


def test_multipath_delta_chain_incremental_and_bit_identical():
    """DeltaPath arm: a chain of weight deltas rides the widened
    incremental kernel (donated multipath tensors) and every step stays
    bit-identical to a from-scratch oracle run."""
    oracle = ScalarSpfBackend()
    with no_implicit_transfers():
        tpu = TpuSpfBackend()
        topo = tied(21)
        before = telemetry.snapshot(prefix="holo_spf_delta").get(
            "holo_spf_delta_total{kind=weight,path=incremental}", 0.0
        )
        tpu.compute(topo, multipath_k=4)  # roots the chain
        cur = topo
        for step in range(5):
            e = (step * 3) % cur.n_edges
            nxt = clone(cur, cost={e: int(cur.edge_cost[e]) + 1 + step})
            delta = diff_topologies(cur, nxt)
            assert delta is not None
            nxt.link_delta(delta)
            res = tpu.compute(nxt, multipath_k=4)
            assert_same(res, oracle.compute(nxt, multipath_k=4), tag=step)
            cur = nxt
        after = telemetry.snapshot(prefix="holo_spf_delta").get(
            "holo_spf_delta_total{kind=weight,path=incremental}", 0.0
        )
        assert after - before >= 5.0, "chain fell off the delta path"


def test_multipath_chain_width_change_degrades_to_full_no_prev():
    """A max-paths reconfigure mid-chain must never donate wrong-width
    tensors: the next delta for that root degrades to full-no-prev."""
    with no_implicit_transfers():
        tpu = TpuSpfBackend()
        topo = tied(5)
        tpu.compute(topo, multipath_k=2)
        nxt = clone(topo, cost={0: int(topo.edge_cost[0]) + 2})
        delta = diff_topologies(topo, nxt)
        nxt.link_delta(delta)
        before = telemetry.snapshot(prefix="holo_spf_delta").get(
            "holo_spf_delta_total{kind=weight,path=full-no-prev}", 0.0
        )
        res = tpu.compute(nxt, multipath_k=8)  # width flip mid-chain
        after = telemetry.snapshot(prefix="holo_spf_delta").get(
            "holo_spf_delta_total{kind=weight,path=full-no-prev}", 0.0
        )
        assert after - before >= 1.0
        assert_same(
            res, ScalarSpfBackend().compute(nxt, multipath_k=8), "width"
        )


def test_multipath_sharded_mesh_bit_identical():
    """Sharded arm: the multipath what-if batch dispatched over the
    (batch, node) process mesh is byte-identical to the single-device
    program and the oracle; the shard counter proves the real path."""
    topo = tied(13)
    masks = whatif_link_failure_masks(topo, 6, seed=3)
    oracle = ScalarSpfBackend()
    ref = oracle.compute_whatif(topo, masks, multipath_k=4)
    with no_implicit_transfers():
        plain = TpuSpfBackend().compute_whatif(topo, masks, multipath_k=4)
        for shape in ((4, 2), (2, 4)):
            with mesh_scope(*shape):
                before = telemetry.snapshot(
                    prefix="holo_spf_shard_dispatch"
                ).get("holo_spf_shard_dispatch_total{kind=whatif}", 0.0)
                res = TpuSpfBackend().compute_whatif(
                    topo, masks, multipath_k=4
                )
                after = telemetry.snapshot(
                    prefix="holo_spf_shard_dispatch"
                ).get("holo_spf_shard_dispatch_total{kind=whatif}", 0.0)
                assert after == before + 1
            for i in range(len(masks)):
                assert_same(res[i], ref[i], tag=("shard", shape, i))
                assert_same(res[i], plain[i], tag=("plain", shape, i))


def test_multipath_breaker_fallback_bit_identical():
    """Breaker arm: forced dispatch failures serve the multipath result
    from the scalar oracle — planes included, bit-identical."""
    topo = tied(17)
    want = ScalarSpfBackend().compute(topo, multipath_k=4)
    breaker = CircuitBreaker("mp-test", failure_threshold=10)
    tpu = TpuSpfBackend(breaker=breaker)
    plan = FaultPlan(seed=1, dispatch_fail={"spf.dispatch": 2})
    with inject(FaultInjector(plan)) as inj:
        r1 = tpu.compute(topo, multipath_k=4)
        r2 = tpu.compute(topo, multipath_k=4)
    assert inj.injected["spf.dispatch"] == 2
    assert_same(r1, want, "fallback-1")
    assert_same(r2, want, "fallback-2")


def test_multipath_invariants_property_sweep():
    """The fuzz target's loop-free/weight-consistency invariants hold
    across a seeded grid (the in-tree arm of ``multipath_invariants``)."""
    from holo_tpu.tools.fuzz import multipath_invariants

    for kind in range(3):
        for size in (1, 3, 5):
            for seed in (0, 11, 200):
                for kbyte in range(4):
                    multipath_invariants(bytes([kind, size, seed, kbyte]))


def test_saturation_is_shared_and_exact():
    """Path counts clamp identically on both engines (MP_SAT contract):
    a dense tied mesh overflows the counter and stays bit-identical."""
    # Parallel equal-cost two-hop ladders double the path count per
    # stage: 2^20 paths saturate at MP_SAT = 2^17.
    n = 44  # 22 ladder stages
    src, dst, cost = [], [], []
    for i in range(0, n - 2, 2):
        for a in (i, i + 1):
            for b in (i + 2, i + 3):
                src += [a, b]
                dst += [b, a]
                cost += [1, 1]
    from holo_tpu.ops.graph import Topology

    topo = Topology(
        n_vertices=n,
        is_router=np.ones(n, bool),
        edge_src=np.array(src, np.int32),
        edge_dst=np.array(dst, np.int32),
        edge_cost=np.array(cost, np.int32),
        root=0,
    )
    from holo_tpu.spf.synth import assign_direct_atoms

    assign_direct_atoms(topo)
    ref = ScalarSpfBackend().compute(topo, multipath_k=2)
    assert int(ref.npaths.max()) == int(MP_SAT), "ladder must saturate"
    with no_implicit_transfers():
        res = TpuSpfBackend().compute(topo, multipath_k=2)
    assert_same(res, ref, "saturation")


# ------------------------------------------------- FRR policy masks


def srlg_topo(seed=3):
    topo = tied(seed, n=24, nets=4, extra=30)
    rng = np.random.default_rng(seed)
    topo.edge_srlg = rng.integers(0, 8, topo.n_edges).astype(np.uint32)
    topo.touch()
    return topo


@pytest.mark.parametrize(
    "srlg,nodeprot", [(True, False), (False, True), (True, True)]
)
def test_frr_policy_masks_device_scalar_parity(srlg, nodeprot):
    """SRLG-disjoint and node-protection policy masks: the vectorized
    kernel and the scalar oracle agree bit-for-bit under every flag
    combination."""
    topo = srlg_topo()
    policy = FrrConfig(
        enabled=True, engine="tpu",
        srlg_disjoint=srlg, node_protection=nodeprot,
    )
    eng = FrrEngine(engine="tpu")
    eng.set_policy(policy)
    with no_implicit_transfers():
        dev = eng.compute(topo)
    ref = frr_reference(
        topo, srlg_disjoint=srlg, node_protection=nodeprot
    )
    for f in (
        "lfa_adj", "lfa_nodeprot", "rlfa_pq", "tilfa_p", "tilfa_q",
        "post_dist", "post_nh",
    ):
        assert np.array_equal(getattr(dev, f), getattr(ref, f)), f


def test_frr_srlg_policy_actually_excludes():
    """Armed SRLG policy must change selections on a topology whose
    best LFA shares a risk group with its protected link (and the
    excluded candidate never shares a group when armed)."""
    topo = srlg_topo(7)
    off = frr_reference(topo)
    on = frr_reference(topo, srlg_disjoint=True)
    assert not np.array_equal(off.lfa_adj, on.lfa_adj), (
        "seed produced no SRLG conflict; pick another"
    )
    fin = on.inputs
    for l in range(fin.n_links):
        for d in range(on.lfa_adj.shape[1]):
            a = int(on.lfa_adj[l, d])
            if a >= 0:
                assert (
                    int(fin.link_srlg[l]) & int(fin.adj_srlg[a])
                ) == 0


def test_frr_node_protection_policy_restricts():
    topo = srlg_topo(11)
    on = frr_reference(topo, node_protection=True)
    sel = on.lfa_adj >= 0
    # Every selected LFA under the policy is node-protecting.
    assert np.all(on.lfa_nodeprot[sel] == 1)


def test_per_prefix_protection_filtering():
    import ipaddress

    cfg = FrrConfig(
        enabled=True,
        protected_prefixes=(ipaddress.ip_network("10.1.0.0/16"),),
    )
    assert cfg.protects_prefix(ipaddress.ip_network("10.1.2.0/24"))
    assert not cfg.protects_prefix(ipaddress.ip_network("10.2.2.0/24"))
    assert FrrConfig(enabled=True).protects_prefix(
        ipaddress.ip_network("10.2.2.0/24")
    )


# ------------------------------------------------- RIB consumption


def _mk_rib(microloop_delay=0.0):
    from holo_tpu.routing.rib import MockKernel, RibManager
    from holo_tpu.utils.ibus import Ibus
    from holo_tpu.utils.runtime import EventLoop, VirtualClock

    loop = EventLoop(clock=VirtualClock())
    bus = Ibus(loop)
    kernel = MockKernel()
    rib = RibManager(bus, kernel, microloop_delay=microloop_delay)
    loop.register(rib)
    return loop, rib, kernel


def _route(prefix, nhs, weights=None, backups=None):
    import ipaddress

    from holo_tpu.utils.southbound import Nexthop, Protocol, RouteMsg

    hops = frozenset(
        Nexthop(addr=ipaddress.ip_address(a), ifname=i) for i, a in nhs
    )
    by_addr = {
        str(nh.addr): nh for nh in hops
    }
    return RouteMsg(
        protocol=Protocol.OSPFV2,
        prefix=ipaddress.ip_network(prefix),
        distance=110,
        metric=10,
        nexthops=hops,
        nh_weights={
            by_addr[a]: w for a, w in (weights or {}).items()
        },
        backups={
            by_addr[a]: Nexthop(
                addr=ipaddress.ip_address(b[1]), ifname=b[0]
            )
            for a, b in (backups or {}).items()
        },
    )


def test_rib_weighted_multipath_install():
    import ipaddress

    loop, rib, kernel = _mk_rib()
    msg = _route(
        "10.9.0.0/24",
        [("e0", "10.0.0.2"), ("e1", "10.0.1.2")],
        weights={"10.0.0.2": 3, "10.0.1.2": 1},
    )
    rib.route_add(msg)
    prefix = ipaddress.ip_network("10.9.0.0/24")
    nhs, _proto = kernel.fib[prefix]
    assert len(nhs) == 2
    w = kernel.weights[prefix]
    assert sorted(w.values()) == [1, 3]
    assert kernel.multipath_installs >= 1
    assert kernel.weighted_installs >= 1


def test_rib_microloop_delayed_flip():
    """RFC 8333: a reconvergence install replacing an ACTIVE repair is
    delayed by the configured window (repair keeps forwarding), then
    installed when the timer fires; a second reconvergence inside the
    window supersedes the pending install."""
    import ipaddress

    loop, rib, kernel = _mk_rib(microloop_delay=5.0)
    prefix = ipaddress.ip_network("10.9.0.0/24")
    msg = _route(
        "10.9.0.0/24",
        [("e0", "10.0.0.2"), ("e1", "10.0.1.2")],
        backups={"10.0.0.2": ("e1", "10.0.1.2")},
    )
    rib.route_add(msg)
    assert rib.local_repair("e0") == 1  # flip onto the backup
    assert prefix in rib.repaired
    survivors, _ = kernel.fib[prefix]
    assert {str(nh.addr) for nh in survivors} == {"10.0.1.2"}

    # Reconvergence republishes the prefix: the flip-back is DELAYED.
    msg2 = _route("10.9.0.0/24", [("e0", "10.0.0.3")])
    rib.route_add(msg2)
    assert prefix in rib.repaired, "repair dropped inside the window"
    survivors, _ = kernel.fib[prefix]
    assert {str(nh.addr) for nh in survivors} == {"10.0.1.2"}
    snap = telemetry.snapshot(prefix="holo_rib_microloop")
    assert snap.get("holo_rib_microloop_delays_total", 0) >= 1

    loop.advance(6.0)  # window expires -> delayed install happens
    assert prefix not in rib.repaired
    survivors, _ = kernel.fib[prefix]
    assert {str(nh.addr) for nh in survivors} == {"10.0.0.3"}


def test_rib_microloop_failure_during_window_keeps_repair():
    """A NEW failure inside the microloop window re-flips against the
    held message; window expiry must keep that repair instead of
    reinstalling the raw primaries (which contain the failed hop)."""
    import ipaddress

    loop, rib, kernel = _mk_rib(microloop_delay=5.0)
    prefix = ipaddress.ip_network("10.9.0.0/24")
    rib.route_add(
        _route(
            "10.9.0.0/24",
            [("e0", "10.0.0.2")],
            backups={"10.0.0.2": ("e1", "10.0.1.2")},
        )
    )
    rib.local_repair("e0")  # first failure: repair onto e1
    # Reconvergence around the failure: new primary on e2 (held).
    msg2 = _route(
        "10.9.0.0/24",
        [("e2", "10.0.2.1")],
        backups={"10.0.2.1": ("e3", "10.0.3.1")},
    )
    rib.route_add(msg2)
    assert prefix in rib.repaired
    # SECOND failure during the window hits the held msg's primary.
    assert rib.local_repair("e2") == 1
    survivors, _ = kernel.fib[prefix]
    assert {str(nh.addr) for nh in survivors} == {"10.0.3.1"}
    loop.advance(6.0)  # window expires
    # The repair survives; the dead 10.0.2.1 primary is NOT reinstalled.
    assert prefix in rib.repaired
    survivors, _ = kernel.fib[prefix]
    assert {str(nh.addr) for nh in survivors} == {"10.0.3.1"}


def test_ospfv3_clamp_consumes_ucmp_weights():
    """The v3 max-paths clamp ranks by the multipath dispatch's UCMP
    weights (highest mass survives), tie-broken by lowest address."""
    import ipaddress
    import types

    from holo_tpu.protocols.ospf.instance_v3 import OspfV3Instance, V6Route

    atoms = [
        ("e0", ipaddress.ip_address("fe80::1")),
        ("e1", ipaddress.ip_address("fe80::2")),
        ("e2", ipaddress.ip_address("fe80::3")),
    ]
    words = np.zeros((4, 2), np.uint32)
    words[3, 0] = 0b111
    nhw = np.zeros((4, 64), np.int32)
    nhw[3, :3] = (5, 1, 9)
    res = types.SimpleNamespace(
        dist=np.zeros(4, np.int32), nexthop_words=words, nh_weights=nhw
    )
    route = V6Route(
        prefix=ipaddress.ip_network("2001:db8::/64"), dist=10,
        nexthops=frozenset(atoms), area_id="0.0.0.0", vertex=3,
    )
    routes = {route.prefix: route}
    stub = types.SimpleNamespace(max_paths=2)
    OspfV3Instance._clamp_max_paths(
        stub, routes, {"0.0.0.0": (None, None, res, atoms, None)}
    )
    assert routes[route.prefix].nexthops == frozenset(
        {atoms[0], atoms[2]}
    )  # weights 5 and 9 survive; weight-1 e1 is clamped off


def test_ospfv2_inter_and_external_routes_clamp_too():
    """max-paths applies to the whole v2 table: inter/external routes
    (raw SPF next-hop sets via their ABR vertex) clamp in _finish_spf
    exactly like intra routes."""
    import ipaddress

    from holo_tpu.protocols.ospf.spf_run import (
        IntraRoute,
        RouteNexthop,
        clamp_multipath,
    )

    nhs = frozenset(
        RouteNexthop(f"e{i}", ipaddress.ip_address(f"10.0.{i}.2"))
        for i in range(4)
    )
    routes = {
        ipaddress.ip_network("10.50.0.0/16"): IntraRoute(
            ipaddress.ip_network("10.50.0.0/16"), 20, nhs,
            ipaddress.ip_address("0.0.0.0"), rtype="inter",
        )
    }
    assert clamp_multipath(routes, 2) == 1
    kept = routes[ipaddress.ip_network("10.50.0.0/16")].nexthops
    assert len(kept) == 2
    assert {str(nh.addr) for nh in kept} == {"10.0.0.2", "10.0.1.2"}


def test_rib_microloop_zero_delay_is_immediate():
    import ipaddress

    loop, rib, kernel = _mk_rib()
    prefix = ipaddress.ip_network("10.9.0.0/24")
    rib.route_add(
        _route(
            "10.9.0.0/24",
            [("e0", "10.0.0.2")],
            backups={"10.0.0.2": ("e1", "10.0.1.2")},
        )
    )
    rib.local_repair("e0")
    rib.route_add(_route("10.9.0.0/24", [("e0", "10.0.0.3")]))
    assert prefix not in rib.repaired
    survivors, _ = kernel.fib[prefix]
    assert {str(nh.addr) for nh in survivors} == {"10.0.0.3"}


# --------------------------------------- protocol + pipeline satellites


@pytest.fixture(autouse=True)
def _clean_pipeline():
    yield
    pipeline.reset_process_pipeline()


def test_storm_multipath_arm_installs_sets_and_weights():
    """e2e: the dual-gateway storm with max-paths=2 installs REAL
    next-hop sets with UCMP weights, deterministically."""
    from holo_tpu.spf.synth_storm import run_convergence_storm

    digs = []
    for _ in range(2):
        rep, dig, _net = run_convergence_storm(
            n_routers=60, events=24, seed=17,
            spf_backend=TpuSpfBackend(), max_paths=2,
        )
        digs.append(dig)
    assert digs[0] == digs[1]
    assert rep["fib-multipath"] > 0
    assert rep["fib-weighted"] > 0


def test_whatif_advisory_rides_pipeline_and_coalesces():
    """Satellite 1 e2e: OSPF enqueues advisory what-if batches through
    the pipeline after each SPF; rapid successive SPF runs coalesce
    (newer generation supersedes the queued older batch)."""
    from holo_tpu.spf.synth_storm import StormNet

    with no_implicit_transfers():
        pipe = pipeline.configure_process_pipeline(
            depth=1, guard=no_implicit_transfers
        )
        be = pipeline.wrap_spf_backend(TpuSpfBackend())
        net = StormNet(n_routers=60, seed=33, spf_backend=be)
        net.inst.config.whatif_advisory = 4
        before = telemetry.snapshot(prefix="holo_pipeline_coalesced")
        for i in range(6):
            net.flap(net.flappable[i], lost=False)
            net.loop.advance(6.0)
        net.loop.advance(40.0)
        pipe.drain(timeout=20)
        after = telemetry.snapshot(prefix="holo_pipeline_coalesced")
        stats = net.inst._whatif_stats
        assert stats["enqueued"] >= 2
        coalesced = sum(after.values()) - sum(before.values())
        done = stats["completed"]
        # Every enqueued batch either completed or was coalesced away.
        assert done > 0
        assert coalesced + done >= stats["enqueued"]


def test_frr_force_moves_off_spf_critical_path():
    """Satellite 2 e2e: with the pipeline armed and a tpu FRR engine,
    the SPF path never forces the LazyBackupTable — the worker's
    done-callback posts FrrTablesReadyMsg, the actor attaches backups
    afterwards, and ``holo_pipeline_wait_seconds{kind=frr}`` records no
    SPF-path wait."""
    from holo_tpu.spf.synth_storm import StormNet

    with no_implicit_transfers():
        pipe = pipeline.configure_process_pipeline(
            depth=2, guard=no_implicit_transfers
        )
        be = pipeline.wrap_spf_backend(TpuSpfBackend())
        net = StormNet(n_routers=60, seed=33, spf_backend=be)
        net.inst.config.frr = FrrConfig(enabled=True, engine="tpu")
        wait_before = telemetry.snapshot(
            prefix="holo_pipeline_wait"
        ).get("holo_pipeline_wait_seconds{kind=frr}", {"count": 0})
        for i in range(3):
            net.flap(net.flappable[i], lost=False)
            net.loop.advance(12.0)
        net.loop.advance(40.0)
        pipe.drain(timeout=20)
        # Deliver the cross-thread FrrTablesReadyMsg.
        net.loop.advance(1.0)
        wait_after = telemetry.snapshot(
            prefix="holo_pipeline_wait"
        ).get("holo_pipeline_wait_seconds{kind=frr}", {"count": 0})
        assert wait_after["count"] == wait_before["count"], (
            "the SPF path paid an FRR force wait"
        )
        # The deferred attach happened: routes carry backups.
        assert any(
            getattr(r, "backups", None) for r in net.inst.routes.values()
        )
