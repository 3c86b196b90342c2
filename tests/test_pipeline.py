"""Async dispatch pipeline semantics (ISSUE 9 tentpole contract).

Covers the queue/ownership contract the pipeline promises the protocol
layer: strict result ordering per (uid, root) key, coalescing of
superseded what-if batches, donation safety under depth-2 delta chains
(one in-flight entry per key — the DeltaPath ownership handoff),
breaker-open skip of advisory batches, split-phase breaker fallback
parity, and the mid-storm ``pipeline.dispatch`` crashpoint chaos test:
forced pipelined-dispatch failures must leave the final FIB
bit-identical to a synchronous control run, under
``jax.transfer_guard("disallow")``.
"""

import threading
import time

import numpy as np
import pytest

from holo_tpu import pipeline
from holo_tpu.ops.graph import diff_topologies
from holo_tpu.pipeline.dispatch import DispatchPipeline
from holo_tpu.resilience.breaker import CircuitBreaker
from holo_tpu.resilience.faults import FaultInjector, FaultPlan, inject
from holo_tpu.spf.backend import ScalarSpfBackend, TpuSpfBackend
from holo_tpu.spf.synth import (
    clone_topology,
    random_ospf_topology,
    whatif_link_failure_masks,
)
from holo_tpu.testing import no_implicit_transfers


@pytest.fixture(autouse=True)
def _clean_process_state():
    yield
    pipeline.reset_process_pipeline()
    pipeline.reset_engine_tuner()


def _topo(seed=1, n=30):
    return random_ospf_topology(
        n_routers=n, n_networks=5, extra_p2p=n // 2, seed=seed
    )


# -- core queue semantics ----------------------------------------------


def test_per_key_ordering_and_cross_key_progress():
    """Results complete in submission order per key; independent keys
    interleave freely (only per-key order is promised)."""
    pipe = DispatchPipeline(depth=2)
    done = []
    lock = threading.Lock()

    def work(key, i, delay):
        def run():
            time.sleep(delay)
            with lock:
                done.append((key, i))
            return (key, i)

        return run

    tickets = []
    for i in range(4):
        tickets.append(
            pipe.submit(("a", 0), "one", run=work("a", i, 0.01))
        )
        tickets.append(
            pipe.submit(("b", 0), "one", run=work("b", i, 0.0))
        )
    for t in tickets:
        t.result(timeout=10)
    pipe.close()
    for key in ("a", "b"):
        seq = [i for k, i in done if k == key]
        assert seq == sorted(seq), f"per-key order violated for {key}: {seq}"


def test_split_phase_overlap_and_single_inflight_per_key():
    """Split-phase items overlap across keys (launch i+1 while i is in
    flight) but NEVER within one key — the DeltaPath donation handoff.
    The stats probe records the max concurrent in-flight per key."""
    pipe = DispatchPipeline(depth=2)
    events = []
    lock = threading.Lock()

    def mk(key, i):
        def launch():
            with lock:
                events.append(("launch", key, i))
            return (key, i)

        def finish(h):
            time.sleep(0.02)
            with lock:
                events.append(("finish", key, i))
            return h

        return launch, finish

    tickets = []
    for i in range(3):
        for key in ("k1", "k2"):
            la, fi = mk(key, i)
            tickets.append(
                pipe.submit((key,), "one", launch=la, finish=fi)
            )
    for t in tickets:
        t.result(timeout=10)
    stats = pipe.stats()
    pipe.close()
    assert stats["max-inflight-per-key"] <= 1, stats
    # Per-key phase ordering: finish(i) precedes launch(i+1) for the
    # same key (the ownership handoff), even with depth-2 overlap.
    for key in ("k1", "k2"):
        seq = [(ev, i) for ev, k, i in events if k == key]
        for i in range(2):
            assert seq.index(("finish", i)) < seq.index(("launch", i + 1))
    # And some genuine overlap happened across keys.
    assert stats["overlap-seconds"] > 0.0


def test_whatif_coalescing_shared_and_superseded():
    pipe = DispatchPipeline(depth=1)
    release = threading.Event()
    ran = []

    def blocker():
        release.wait(5)
        return "blocker"

    def batch(gen):
        def run():
            ran.append(gen)
            return f"batch-{gen}"

        return run

    # Occupy the worker so subsequent submits stay queued.
    t0 = pipe.submit(("x",), "one", run=blocker)
    t1 = pipe.submit(("w",), "whatif", run=batch(1), generation=1,
                     coalesce=True)
    # Same (key, generation): shared ticket, no duplicate work.
    t1b = pipe.submit(("w",), "whatif", run=batch(1), generation=1,
                      coalesce=True)
    assert t1b is t1
    # Newer generation supersedes the queued older batch.
    t2 = pipe.submit(("w",), "whatif", run=batch(2), generation=2,
                     coalesce=True)
    release.set()
    assert t0.result(timeout=10) == "blocker"
    assert t2.result(timeout=10) == "batch-2"
    assert t1.result(timeout=10) is None and t1.superseded
    stats = pipe.stats()
    pipe.close()
    assert ran == [2], f"superseded batch must not run: {ran}"
    assert stats["coalesced"] == 2  # one shared + one superseded


def test_breaker_open_skips_advisory_batch_entirely():
    """While the circuit is open the what-if batch is not enqueued at
    all — no scalar re-run, no queue slot, just a skipped ticket (the
    ISSUE 9 breaker-awareness contract)."""
    pipe = DispatchPipeline(depth=1)
    breaker = CircuitBreaker(
        "pipeline-skip-test", failure_threshold=1, recovery_timeout=1e9
    )
    breaker.call(
        lambda: (_ for _ in ()).throw(RuntimeError("boom")),
        lambda: None,
    )
    assert breaker.state == "open"
    ran = []
    t = pipe.submit(
        ("w",), "whatif", run=lambda: ran.append(1), generation=1,
        coalesce=True, skip_when_open=breaker,
    )
    assert t.skipped and t.result(timeout=1) is None
    stats = pipe.stats()
    pipe.close()
    assert not ran
    assert stats["breaker-skipped"] == 1 and stats["submitted"] == 0


def test_async_whatif_breaker_open_skip_via_backend():
    topo = _topo(seed=3)
    masks = whatif_link_failure_masks(topo, 4, seed=1)
    pipe = pipeline.configure_process_pipeline(depth=2)
    breaker = CircuitBreaker(
        "async-whatif-test", failure_threshold=1, recovery_timeout=1e9
    )
    be = pipeline.wrap_spf_backend(TpuSpfBackend(breaker=breaker))
    # Healthy: the advisory batch computes and matches the oracle.
    ticket = be.compute_whatif_async(topo, masks)
    res = ticket.result(timeout=30)
    ref = ScalarSpfBackend().compute_whatif(topo, masks)
    for r, s in zip(ref, res):
        assert np.array_equal(r.dist, s.dist)
    # Open circuit: skipped outright.
    breaker.call(
        lambda: (_ for _ in ()).throw(RuntimeError("boom")),
        lambda: None,
    )
    assert breaker.state == "open"
    t2 = be.compute_whatif_async(topo, masks)
    assert t2.skipped and t2.result(timeout=1) is None


def test_passthrough_exception_surfaces_at_force_time():
    """Bug-class exceptions (TypeError & friends) must not be masked by
    the fallback: they re-raise on the caller's thread when the lazy
    result is forced — the synchronous passthrough contract — and
    release the breaker's probe slot without counting a failure."""
    pipe = pipeline.configure_process_pipeline(depth=1)
    inner = TpuSpfBackend()
    be = pipeline.wrap_spf_backend(inner)
    topo = _topo(seed=11)

    def buggy_launch(t, edge_mask=None):
        raise TypeError("bug, not a device failure")

    inner.launch_one = buggy_launch
    res = be.compute(topo)
    with pytest.raises(TypeError):
        _ = res.dist
    assert be.breaker.state == "closed"  # never counted as device failure
    assert be.breaker.consecutive_failures == 0
    pipe.close()


# -- parity + donation safety ------------------------------------------


def test_async_parity_and_delta_chain_donation_safety():
    """Depth-2 delta chains through the pipeline: consecutive deltas
    for ONE key are serialized by the ownership handoff, the resident
    graph + retained tensors are donated exactly as in the synchronous
    path, and every step is bit-identical to the scalar oracle.  Runs
    under the transfer sanitizer."""
    pipe = pipeline.configure_process_pipeline(
        depth=2, guard=no_implicit_transfers
    )
    be = pipeline.wrap_spf_backend(TpuSpfBackend())
    oracle = ScalarSpfBackend()
    rng = np.random.default_rng(5)
    with no_implicit_transfers():
        topo = _topo(seed=5, n=40)
        be.compute(topo).wait()  # warm: marshal + retain seed tensors
        results = []
        chain = [topo]
        # Two consecutive deltas submitted back-to-back: the second's
        # launch must wait for the first's finish (which re-deposits
        # the retained tensors) — otherwise full-no-prev or worse, a
        # donated-buffer reuse.
        for step in range(2):
            prev = chain[-1]
            e = int(rng.integers(0, prev.n_edges))
            nxt = clone_topology(prev, cost={e: int(rng.integers(1, 64))})
            delta = diff_topologies(prev, nxt)
            assert delta is not None
            nxt.link_delta(delta)
            chain.append(nxt)
            results.append((nxt, be.compute(nxt)))
        for nxt, lazy in results:
            ref = oracle.compute(nxt)
            for f in ("dist", "parent", "hops", "nexthop_words"):
                assert np.array_equal(getattr(ref, f), getattr(lazy, f)), f
    from holo_tpu import telemetry

    snap = telemetry.snapshot(prefix="holo_spf_delta")
    incr = sum(
        v for k, v in snap.items() if "path=incremental" in k
    )
    assert incr >= 2, f"delta chain did not stay incremental: {snap}"
    assert pipe.stats()["max-inflight-per-key"] <= 1


def test_async_breaker_fallback_bit_identical():
    """Split-phase launch failure -> breaker accounting + scalar
    fallback, same output as the oracle."""
    pipe = pipeline.configure_process_pipeline(depth=2)
    breaker = CircuitBreaker(
        "async-fallback-test", failure_threshold=2, recovery_timeout=1e9
    )
    be = pipeline.wrap_spf_backend(TpuSpfBackend(breaker=breaker))
    topo = _topo(seed=7)
    ref = ScalarSpfBackend().compute(topo)
    plan = FaultPlan(seed=7, dispatch_fail={"pipeline.dispatch": 2})
    with inject(FaultInjector(plan)) as inj:
        r1 = be.compute(topo)
        assert np.array_equal(r1.dist, ref.dist)
        r2 = be.compute(topo)
        assert np.array_equal(r2.dist, ref.dist)
        assert np.array_equal(r2.nexthop_words, ref.nexthop_words)
    assert inj.injected["pipeline.dispatch"] == 2
    assert breaker.state == "open"
    # Open circuit: compute still serves (oracle, at launch admit).
    r3 = be.compute(topo)
    assert np.array_equal(r3.dist, ref.dist)


# -- chaos: mid-storm crashpoint vs synchronous control -----------------


def test_pipeline_dispatch_crashpoint_mid_storm_bit_identical_fibs():
    """ISSUE 9 chaos acceptance: forced ``pipeline.dispatch`` failures
    mid-storm open the breaker; every subsequent pipelined dispatch is
    served by the scalar fallback, and the final FIB is bit-identical
    to a SYNCHRONOUS control run of the same seeded storm.  Runs under
    ``jax.transfer_guard("disallow")`` (the pipeline worker installs
    the same sanitizer via its guard hook)."""
    from holo_tpu.spf.synth_storm import StormNet

    def run(backend, asynchronous):
        net = StormNet(n_routers=60, seed=33, spf_backend=backend)
        for i in range(8):
            net.flap(net.flappable[i], lost=False)
            net.loop.advance(12.0)
        net.ifconfig_metric()
        net.loop.advance(40.0)
        if asynchronous:
            pipeline.process_pipeline().drain(timeout=10)
        return dict(net.kernel.fib)

    with no_implicit_transfers():
        # Control: synchronous TpuSpfBackend, no chaos.
        control_fib = run(TpuSpfBackend(64), asynchronous=False)
        # Async arm under chaos: same storm, pipelined backend, two
        # forced pipeline.dispatch failures -> breaker open -> scalar.
        pipeline.configure_process_pipeline(
            depth=2, guard=no_implicit_transfers
        )
        breaker = CircuitBreaker(
            "pipeline-storm", failure_threshold=2, recovery_timeout=1e9
        )
        be = pipeline.wrap_spf_backend(TpuSpfBackend(64, breaker=breaker))
        plan = FaultPlan(seed=33, dispatch_fail={"pipeline.dispatch": 2})
        with inject(FaultInjector(plan)) as inj:
            chaos_fib = run(be, asynchronous=True)
        assert inj.injected["pipeline.dispatch"] == 2
        assert breaker.state == "open"
    assert chaos_fib == control_fib


def test_async_storm_digest_matches_sync_and_scalar():
    """Clean storm tri-parity: the async-pipelined arm's causal
    timeline digest is byte-identical to the synchronous device arm's —
    pipelining must not reorder, drop, or re-attribute a single causal
    step — and the final FIBs of all THREE arms (async / sync /
    all-scalar) are identical.  (The scalar arm's causal digest legitimately differs:
    its dispatch entries record mode=scalar, which is the point of the
    attribution.)"""
    from holo_tpu.spf.synth_storm import run_convergence_storm

    def arm(backend, asynchronous=False):
        report, digest, net = run_convergence_storm(
            n_routers=60, events=24, seed=35, spf_backend=backend,
        )
        if asynchronous:
            pipeline.process_pipeline().drain(timeout=10)
        return digest, dict(net.kernel.fib)

    d_sync, fib_sync = arm(TpuSpfBackend(64))
    _d_scalar, fib_scalar = arm(None)
    pipeline.configure_process_pipeline(depth=2)
    d_async, fib_async = arm(
        pipeline.wrap_spf_backend(TpuSpfBackend(64)), asynchronous=True
    )
    assert d_async == d_sync, "pipelining perturbed the causal timeline"
    assert fib_async == fib_sync == fib_scalar


# -- FRR through the pipeline ------------------------------------------


def test_async_frr_overlaps_and_matches_oracle():
    from holo_tpu.frr.manager import FrrEngine
    from holo_tpu.spf.synth import grid_topology

    pipe = pipeline.configure_process_pipeline(depth=2)
    topo = grid_topology(5, 5, seed=3)
    ref = FrrEngine("scalar").compute(topo)
    eng = pipeline.wrap_frr_engine(FrrEngine("tpu"))
    be = pipeline.wrap_spf_backend(TpuSpfBackend())
    # SPF + FRR for one topology ride distinct keys: both enqueue
    # without blocking, then force.
    spf_res = be.compute(topo)
    table = eng.compute(topo)
    assert spf_res.dist is not None
    for f in ("lfa_adj", "rlfa_pq", "tilfa_p", "tilfa_q", "post_nh"):
        assert np.array_equal(getattr(ref, f), getattr(table, f)), f
    assert pipe.stats()["completed"] >= 2


def test_wrap_helpers_are_identity_when_unarmed():
    be = TpuSpfBackend()
    assert pipeline.wrap_spf_backend(be) is be
    scalar = ScalarSpfBackend()
    pipeline.configure_process_pipeline(depth=1)
    assert pipeline.wrap_spf_backend(scalar) is scalar
    assert pipeline.wrap_spf_backend(be) is not be
