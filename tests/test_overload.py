"""Dispatch survivability plane (ISSUE 19 acceptance contract).

Covers the overload/robustness semantics the pipeline promises under
pressure: class-aware dequeue (correctness > advisory > background),
graded load-shedding on a full queue (worst class first, correctness
never shed and still bounded-blocking), advisory submit-time deadlines
expired at dequeue, close() waking a capacity-blocked submitter into
``PipelineClosed``, the hung-dispatch watchdog (abandon + bit-identical
scalar fallback + breaker escalation + worker respawn), chaos-born
worker kills with supervised respawn (queued tickets survive), the
transient-vs-deterministic retry taxonomy ahead of the breaker, and the
disarmed-path identity contract (a poisoned deadline clock is never
read when no ticket carries a deadline).
"""

import threading
import time

import numpy as np
import pytest

from holo_tpu import pipeline
from holo_tpu.pipeline.dispatch import (
    DispatchPipeline,
    PipelineClosed,
    _guarded_launch,
)
from holo_tpu.resilience import overload
from holo_tpu.resilience.breaker import CircuitBreaker
from holo_tpu.resilience.faults import FaultInjector, FaultPlan, inject
from holo_tpu.resilience.watchdog import (
    DispatchWatchdog,
    reset_process_watchdog,
)
from holo_tpu.spf.backend import ScalarSpfBackend, TpuSpfBackend
from holo_tpu.spf.synth import random_ospf_topology


@pytest.fixture(autouse=True)
def _clean_process_state():
    yield
    reset_process_watchdog()
    pipeline.reset_process_pipeline()
    pipeline.reset_engine_tuner()
    overload.configure_retry(None)


def _topo(seed=1, n=30):
    return random_ospf_topology(
        n_routers=n, n_networks=5, extra_p2p=n // 2, seed=seed
    )


def _occupied_pipe(**kw):
    """Pipeline whose worker is parked inside a blocker run — queued
    submissions pile up behind it until ``release`` is set."""
    pipe = DispatchPipeline(**kw)
    release = threading.Event()
    started = threading.Event()

    def blocker():
        started.set()
        release.wait(30)

    t = pipe.submit(("blocker", 0), "one", run=blocker)
    assert started.wait(5), "worker never picked up the blocker"
    return pipe, release, t


# -- priority admission -------------------------------------------------


def test_class_aware_dequeue_correctness_first_fifo_within_rank():
    """Mixed-class backlog drains correctness first, FIFO within each
    class — advisory and background never queue ahead of FIB-feeding
    work regardless of arrival order."""
    pipe, release, blocker = _occupied_pipe(depth=1, capacity=16)
    order = []

    def mk(tag):
        return lambda: order.append(tag)

    tickets = [
        pipe.submit(("bg", 0), "one", run=mk("bg"), cls="background"),
        pipe.submit(("a1", 0), "one", run=mk("a1"), cls="advisory"),
        pipe.submit(("c1", 0), "one", run=mk("c1")),
        pipe.submit(("a2", 0), "one", run=mk("a2"), cls="advisory"),
        pipe.submit(("c2", 0), "one", run=mk("c2")),
    ]
    release.set()
    for t in tickets:
        t.result(timeout=10)
    pipe.close()
    assert order == ["c1", "c2", "a1", "a2", "bg"]


def test_submit_rejects_unknown_class_and_correctness_deadline():
    pipe = DispatchPipeline(depth=1)
    with pytest.raises(ValueError, match="unknown ticket class"):
        pipe.submit(("k", 0), "one", run=lambda: None, cls="bogus")
    with pytest.raises(ValueError, match="deadline"):
        pipe.submit(("k", 0), "one", run=lambda: None, deadline=1.0)
    pipe.close()


# -- graded load-shedding -----------------------------------------------


def test_full_queue_sheds_worst_class_first():
    """Capacity pressure evicts the worst-class (oldest within it)
    queued ticket; an unsheddable incoming background ticket sheds
    itself instead of walling the submitter."""
    pipe, release, blocker = _occupied_pipe(depth=1, capacity=2)
    done = []
    bg = pipe.submit(
        ("bg", 0), "one", run=lambda: done.append("bg"), cls="background"
    )
    a1 = pipe.submit(
        ("a1", 0), "one", run=lambda: done.append("a1"), cls="advisory"
    )
    # Queue full.  Incoming advisory evicts the background victim.
    a2 = pipe.submit(
        ("a2", 0), "one", run=lambda: done.append("a2"), cls="advisory"
    )
    assert bg.shed == "capacity" and bg.skipped
    assert bg.result(timeout=1) is None
    # Queue holds [a1, a2] — an incoming background ticket outranks
    # nothing, so it sheds itself (never blocks).
    bg2 = pipe.submit(
        ("bg2", 0), "one", run=lambda: done.append("bg2"), cls="background"
    )
    assert bg2.shed == "capacity" and bg2.skipped
    # Incoming correctness evicts the OLDEST advisory instead of
    # blocking while sheddable work occupies the queue.
    c1 = pipe.submit(("c1", 0), "one", run=lambda: done.append("c1"))
    assert a1.shed == "capacity"
    release.set()
    c1.result(timeout=10)
    a2.result(timeout=10)
    pipe.close()
    st = pipe.stats()
    assert st["sheds"] == 3
    assert st["shed-by-class"] == {"background": 2, "advisory": 1}
    assert "c1" in done and "a2" in done
    assert done.count("bg") == 0 and done.count("a1") == 0


def test_correctness_blocks_bounded_when_queue_all_correctness():
    """A queue full of correctness work has no victim: the correctness
    submitter blocks (bounded backpressure, the seed contract) and
    admits as soon as the worker frees a slot — it is NEVER shed."""
    pipe, release, blocker = _occupied_pipe(depth=1, capacity=1)
    first = pipe.submit(("c0", 0), "one", run=lambda: "c0")
    admitted = threading.Event()
    out = {}

    def submitter():
        out["ticket"] = pipe.submit(("c1", 0), "one", run=lambda: "c1")
        admitted.set()

    th = threading.Thread(target=submitter, daemon=True)
    th.start()
    assert not admitted.wait(0.4), "correctness submit must block, not shed"
    release.set()
    assert admitted.wait(10), "blocked correctness submit never admitted"
    assert out["ticket"].result(timeout=10) == "c1"
    assert first.result(timeout=10) == "c0"
    pipe.close()
    assert pipe.stats()["shed-by-class"].get("correctness", 0) == 0


def test_close_wakes_capacity_blocked_submitter_with_pipeline_closed():
    """ISSUE 19 satellite: a correctness submitter walled on a full
    queue must not sleep through close() — it wakes and raises
    ``PipelineClosed`` instead of waiting out a dead pipeline."""
    pipe, release, blocker = _occupied_pipe(depth=1, capacity=1)
    pipe.submit(("c0", 0), "one", run=lambda: None)
    failed = threading.Event()
    out = {}

    def submitter():
        try:
            pipe.submit(("c1", 0), "one", run=lambda: None)
        except PipelineClosed as exc:
            out["exc"] = exc
            failed.set()

    th = threading.Thread(target=submitter, daemon=True)
    th.start()
    time.sleep(0.2)
    assert not failed.is_set()
    release.set()  # let the worker drain so close() can join it
    pipe.close(timeout=10)
    assert failed.wait(5), "blocked submitter never saw PipelineClosed"
    assert isinstance(out["exc"], PipelineClosed)


# -- deadline-aware shedding --------------------------------------------


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_advisory_deadline_expires_at_dequeue():
    """An advisory ticket whose submit-time deadline lapsed while it
    queued is shed at dequeue (reason ``expired``) — the worker never
    runs it; correctness behind it is untouched."""
    clk = _FakeClock()
    pipe = DispatchPipeline(depth=1, capacity=8, clock=clk)
    release = threading.Event()
    started = threading.Event()
    pipe.submit(
        ("blocker", 0), "one",
        run=lambda: (started.set(), release.wait(30)),
    )
    assert started.wait(5)
    done = []
    adv = pipe.submit(
        ("a", 0), "one", run=lambda: done.append("a"),
        cls="advisory", deadline=5.0,
    )
    c = pipe.submit(("c", 0), "one", run=lambda: done.append("c"))
    clk.t = 10.0  # the advisory deadline lapses while queued
    release.set()
    c.result(timeout=10)
    assert adv.result(timeout=10) is None
    assert adv.shed == "expired" and adv.skipped
    assert done == ["c"]
    pipe.close()
    assert pipe.stats()["shed-by-class"] == {"advisory": 1}


def test_pipeline_default_advisory_deadline_applies():
    """``advisory_deadline`` stamps advisory tickets that did not pass
    their own; correctness is exempt by construction."""
    clk = _FakeClock()
    pipe = DispatchPipeline(
        depth=1, capacity=8, clock=clk, advisory_deadline=2.0
    )
    release = threading.Event()
    started = threading.Event()
    pipe.submit(
        ("blocker", 0), "one",
        run=lambda: (started.set(), release.wait(30)),
    )
    assert started.wait(5)
    adv = pipe.submit(("a", 0), "one", run=lambda: "a", cls="advisory")
    c = pipe.submit(("c", 0), "one", run=lambda: "c")
    clk.t = 100.0
    release.set()
    assert c.result(timeout=10) == "c"
    assert adv.result(timeout=10) is None and adv.shed == "expired"
    pipe.close()


def test_disarmed_path_never_reads_poisoned_clock():
    """Identity contract: with no deadline-carrying ticket anywhere,
    the pipeline NEVER reads its deadline clock — a poisoned clock
    proves the disarmed path is byte-identical to the seed."""

    def poisoned():
        raise AssertionError("deadline clock read on the disarmed path")

    pipe = DispatchPipeline(depth=2, capacity=4, clock=poisoned)
    tickets = [
        pipe.submit(("k", i), "one", run=lambda i=i: i, cls=cls)
        for i, cls in enumerate(
            ("correctness", "advisory", "background", "correctness")
        )
    ]
    for i, t in enumerate(tickets):
        assert t.result(timeout=10) == i
    pipe.close()
    assert pipe.stats()["sheds"] == 0


# -- hung-dispatch watchdog ---------------------------------------------


def test_watchdog_abandons_hang_serves_bit_identical_fallback():
    """Chaos hang inside the launch phase: the watchdog abandons the
    wedged phase within its budget, the ticket is served from the
    bit-identical scalar oracle, the breaker takes the hang as a
    failure (circuit opens), and a respawned worker keeps serving the
    queue."""
    topo = _topo(seed=11)
    ref = ScalarSpfBackend().compute(topo)
    pipe = pipeline.configure_process_pipeline(depth=2)
    breaker = CircuitBreaker(
        "watchdog-hang-test", failure_threshold=1, recovery_timeout=1e9
    )
    be = pipeline.wrap_spf_backend(TpuSpfBackend(breaker=breaker))
    wd = DispatchWatchdog(pipe, interval=0.05, floor=1.0).start()
    plan = FaultPlan(seed=1, dispatch_hang={"pipeline.launch": 30.0})
    with inject(FaultInjector(plan)) as inj:
        try:
            res = be.compute(topo)
            assert np.array_equal(res.dist, ref.dist)
            assert np.array_equal(res.nexthop_words, ref.nexthop_words)
            assert inj.injected["hang:pipeline.launch"] == 1
            assert wd.hangs == 1
            assert breaker.state == "open"
            assert breaker.last_error.startswith("hang:")
            st = pipe.stats()
            assert st["hangs"] == 1
            assert st["worker-respawns"] >= 1
            # The respawned worker owns the queue: open-circuit
            # dispatches keep flowing (served from the oracle up
            # front) — the pipeline is not wedged.
            res2 = be.compute(topo)
            assert np.array_equal(res2.dist, ref.dist)
            assert pipe.stats()["max-inflight-per-key"] <= 1
        finally:
            # Free the wedged thread before teardown (it is disowned
            # and exits at its next ownership check).
            inj.release_hangs()
            wd.stop()


def test_watchdog_check_is_noop_without_overrun():
    """The sentinel declares nothing while every phase is inside its
    budget, and the floor guards cold observatory sketches."""
    pipe = DispatchPipeline(depth=1, name="wd-quiet")
    wd = DispatchWatchdog(pipe, interval=0.05, floor=5.0)
    assert wd.budget("spf.one") == 5.0  # cold: floor wins
    assert wd.check() is False  # nothing in flight
    t = pipe.submit(("k", 0), "one", run=lambda: 7)
    assert t.result(timeout=10) == 7
    assert wd.check() is False
    assert wd.hangs == 0
    pipe.close()


# -- chaos worker kills + supervised respawn ----------------------------


def test_worker_kill_respawns_and_queued_tickets_survive():
    """``FaultPlan.worker_kill`` murders the worker thread at the loop
    top (no item in hand): the unsupervised pipeline self-respawns and
    every queued ticket still completes, per-key single-inflight
    intact."""
    pipe = DispatchPipeline(depth=2, capacity=16, name="kill-test")
    plan = FaultPlan(seed=3, worker_kill={"pipeline.worker": 1})
    with inject(FaultInjector(plan)) as inj:
        tickets = [
            pipe.submit(("k", i), "one", run=lambda i=i: i * i)
            for i in range(6)
        ]
        for i, t in enumerate(tickets):
            assert t.result(timeout=15) == i * i
        assert inj.injected["kill:pipeline.worker"] == 1
    pipe.drain(timeout=10)
    st = pipe.stats()
    assert st["worker-crashes"] == 1
    assert st["worker-respawns"] >= 1
    assert st["max-inflight-per-key"] <= 1
    pipe.close()


def test_supervisor_watch_worker_respawns_killed_pipeline_worker():
    """Supervised pipeline (``Supervisor.watch_worker``): the worker's
    chaos death marshals to the home loop as a CrashNotice, the
    RestartPolicy backoff fires, and ``respawn()`` brings a fresh
    thread up over the surviving queue."""
    from holo_tpu.resilience.supervisor import RestartPolicy, Supervisor
    from holo_tpu.utils.runtime import EventLoop, VirtualClock

    home = EventLoop(clock=VirtualClock())
    sup = Supervisor(RestartPolicy(base_delay=0.5, jitter=0.0)).install(home)
    pipe = DispatchPipeline(depth=2, name="supkill")
    pname = sup.watch_worker(pipe, "supkill")
    assert pname == "worker:supkill"
    assert pipe.on_worker_crash is not None

    def wait(cond, what):
        deadline = time.monotonic() + 10
        while not cond() and time.monotonic() < deadline:
            time.sleep(0.01)
            home.run_until_idle()  # pump CrashNotice / RestartDue
        assert cond(), what

    # Spawn the worker with one completed dispatch, then kill its idle
    # loop — no submit races the death, so ONLY the supervisor path can
    # bring it back.
    assert pipe.submit(("k", 0), "one", run=lambda: 1).result(timeout=10) == 1
    plan = FaultPlan(seed=3, worker_kill={"pipeline.worker": 1})
    with inject(FaultInjector(plan)):
        wait(lambda: pipe.stats()["worker-crashes"] == 1, "worker kill seen")
        wait(lambda: sup.crashes.get(pname) == 1, "crash notice marshaled")
        home.advance(1.0)  # backoff expires -> RestartDue -> respawn()
        wait(lambda: sup.restarts.get(pname) == 1, "supervised respawn")
    assert pipe.stats()["worker-respawns"] >= 1
    # The respawned worker serves the queue.
    assert pipe.submit(("k", 1), "one", run=lambda: 2).result(timeout=10) == 2
    pipe.close()


# -- transient-retry taxonomy -------------------------------------------


def test_transient_error_retried_before_breaker_counts():
    """A transient-classified launch failure gets one jittered-backoff
    retry BEFORE the breaker sees anything; recovery leaves zero
    strikes on the circuit."""
    overload.configure_retry(
        overload.RetryPolicy(retries=1, base_delay=0.0, jitter=0.0)
    )
    br = CircuitBreaker(
        "retry-transient", failure_threshold=3, recovery_timeout=1e9
    )
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) == 1:
            raise OSError("connection reset by peer")
        return "handle"

    verdict, guard, handle = _guarded_launch(br, "test.flaky", flaky)
    assert verdict == "ok" and handle == "handle"
    assert len(calls) == 2
    assert br.consecutive_failures == 0 and br.state == "closed"
    guard.success()


def test_deterministic_error_goes_straight_to_fallback():
    """A deterministic error (shape bug: retrying is pure added
    latency) is NOT retried — one call, one breaker strike, fallback
    verdict."""
    overload.configure_retry(
        overload.RetryPolicy(retries=1, base_delay=0.0, jitter=0.0)
    )
    br = CircuitBreaker(
        "retry-deterministic", failure_threshold=3, recovery_timeout=1e9
    )
    calls = []

    def broken():
        calls.append(1)
        raise RuntimeError("dimension mismatch in gather")

    verdict, guard, handle = _guarded_launch(br, "test.broken", broken)
    assert verdict == "fallback" and handle is None
    assert len(calls) == 1
    assert br.consecutive_failures == 1


def test_transient_exhaustion_still_strikes_breaker():
    """Retries are bounded: a persistently transient error burns its
    retry then strikes the breaker exactly once."""
    overload.configure_retry(
        overload.RetryPolicy(retries=1, base_delay=0.0, jitter=0.0)
    )
    br = CircuitBreaker(
        "retry-exhausted", failure_threshold=3, recovery_timeout=1e9
    )
    calls = []

    def down():
        calls.append(1)
        raise OSError("UNAVAILABLE: runtime endpoint down")

    verdict, _guard, _handle = _guarded_launch(br, "test.down", down)
    assert verdict == "fallback"
    assert len(calls) == 2  # original + one retry
    assert br.consecutive_failures == 1


def test_is_transient_classification():
    assert overload.is_transient(OSError("boom"))
    assert overload.is_transient(RuntimeError("DEADLINE_EXCEEDED: slow"))
    assert overload.is_transient(RuntimeError("collective timed out"))
    assert not overload.is_transient(RuntimeError("bad gather shape"))
    from holo_tpu.resilience.faults import InjectedFault

    # Chaos faults carry no transient marker: injected strike counts
    # (dispatch_fail burn-downs) are preserved exactly.
    assert not overload.is_transient(InjectedFault("forced failure"))


def test_retry_backoff_is_deterministic_and_jittered():
    p = overload.RetryPolicy(retries=2, base_delay=0.1, jitter=0.5)
    a = p.backoff("spf.one", 1)
    b = p.backoff("spf.one", 1)
    c = p.backoff("spf.one", 2)
    assert a == b  # seeded by (context, attempt): reproducible
    assert 0.1 <= a <= 0.1 * 1.5
    assert c >= 0.2  # exponential base doubles per attempt


# -- chaos storms: digest parity under flood / hang ----------------------


def test_advisory_flood_storm_sheds_only_advisory_fib_parity():
    """ISSUE 19 chaos acceptance: a queue_flood advisory storm riding
    the live pipeline sheds ONLY advisory tickets; the correctness
    causal digest and final FIB are byte-identical to the flood-free
    control of the same seeded storm."""
    from holo_tpu.spf.synth_storm import run_convergence_storm

    def arm(flood):
        pipe = pipeline.configure_process_pipeline(depth=2, capacity=8)
        inj = FaultInjector(FaultPlan(seed=9))
        hook = None
        if flood:
            def hook(net, index, now):
                if index % 5 == 0:
                    inj.queue_flood(pipe, 24)
        _report, digest, net = run_convergence_storm(
            n_routers=40, events=16, seed=9,
            spf_backend=pipeline.wrap_spf_backend(TpuSpfBackend(64)),
            event_hook=hook,
        )
        pipe.drain(timeout=30)
        return digest, dict(net.kernel.fib), pipe.stats()

    d_ctl, fib_ctl, st_ctl = arm(flood=False)
    d_fld, fib_fld, st_fld = arm(flood=True)
    assert d_fld == d_ctl, "flood perturbed the correctness causal timeline"
    assert fib_fld == fib_ctl
    assert st_fld["shed-by-class"].get("advisory", 0) > 0
    assert st_fld["shed-by-class"].get("correctness", 0) == 0
    assert st_ctl["sheds"] == 0


def test_watchdog_hang_mid_storm_fib_parity():
    """A mid-storm launch hang abandoned by the watchdog leaves the
    final FIB byte-identical to the unfaulted control — the abandoned
    dispatch is served from the bit-identical oracle and the respawned
    worker finishes the storm."""
    from holo_tpu.spf.synth_storm import run_convergence_storm

    def arm(hang):
        pipe = pipeline.configure_process_pipeline(depth=2)
        breaker = CircuitBreaker(
            f"storm-hang-{hang}", failure_threshold=3,
            recovery_timeout=1e9,
        )
        wd = inj = None
        if hang:
            # The floor must clear a REAL first-compile launch wall at
            # this scale, or merely-slow dispatches get spuriously
            # abandoned mid-chain; only the injected 30s wedge may trip.
            wd = DispatchWatchdog(pipe, interval=0.1, floor=4.0).start()
            inj = FaultInjector(
                FaultPlan(seed=13, dispatch_hang={"pipeline.launch": 30.0})
            )
        cm = inject(inj) if inj is not None else None
        if cm is not None:
            cm.__enter__()
        try:
            _r, _d, net = run_convergence_storm(
                n_routers=40, events=12, seed=13,
                spf_backend=pipeline.wrap_spf_backend(
                    TpuSpfBackend(64, breaker=breaker)
                ),
            )
            pipe.drain(timeout=30)
            return dict(net.kernel.fib), pipe.stats(), wd
        finally:
            if inj is not None:
                inj.release_hangs()
            if cm is not None:
                cm.__exit__(None, None, None)
            if wd is not None:
                wd.stop()

    fib_ctl, _st_ctl, _ = arm(hang=False)
    fib_hang, st_hang, wd = arm(hang=True)
    assert fib_hang == fib_ctl
    assert wd.hangs == 1
    assert st_hang["hangs"] == 1
    assert st_hang["worker-respawns"] >= 1
    assert st_hang["max-inflight-per-key"] <= 1
