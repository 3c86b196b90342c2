"""Resilience subsystem units: circuit breaker FSM, restart policy +
supervisor (virtual clock), fault-plan determinism, txqueue drop-cause
attribution, event-recorder crash-safe flush."""

import json

import pytest

from holo_tpu import telemetry
from holo_tpu.resilience import (
    CircuitBreaker,
    FaultInjector,
    FaultPlan,
    InjectedFault,
    RestartPolicy,
    Supervisor,
    health_snapshot,
    inject,
)
from holo_tpu.resilience import faults as faults_mod
from holo_tpu.utils.runtime import (
    Actor,
    EventLoop,
    PoisonPill,
    VirtualClock,
)

# -- circuit breaker ----------------------------------------------------


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def mkbreaker(name, **kw):
    clk = FakeClock()
    kw.setdefault("failure_threshold", 3)
    kw.setdefault("recovery_timeout", 10.0)
    return CircuitBreaker(name, clock=clk, **kw), clk


def test_breaker_opens_after_consecutive_failures_and_short_circuits():
    br, clk = mkbreaker("u-open")
    calls = {"primary": 0, "fallback": 0}

    def bad():
        calls["primary"] += 1
        raise RuntimeError("xla died")

    def oracle():
        calls["fallback"] += 1
        return "scalar"

    for _ in range(3):
        assert br.call(bad, oracle) == "scalar"
    assert br.state == "open" and calls == {"primary": 3, "fallback": 3}
    # Open: the device is not even attempted.
    assert br.call(bad, oracle) == "scalar"
    assert calls["primary"] == 3 and calls["fallback"] == 4


def test_breaker_success_resets_failure_streak():
    br, _ = mkbreaker("u-streak")
    br.call(lambda: (_ for _ in ()).throw(RuntimeError()), lambda: None)
    br.call(lambda: (_ for _ in ()).throw(RuntimeError()), lambda: None)
    assert br.consecutive_failures == 2
    assert br.call(lambda: "ok", lambda: "fb") == "ok"
    assert br.consecutive_failures == 0 and br.state == "closed"


def test_breaker_half_open_probe_restores_service():
    br, clk = mkbreaker("u-probe")
    boom = lambda: (_ for _ in ()).throw(RuntimeError("x"))
    for _ in range(3):
        br.call(boom, lambda: "fb")
    assert br.state == "open"
    clk.t = 11.0  # past recovery_timeout
    calls = {"n": 0}

    def good():
        calls["n"] += 1
        return "device"

    assert br.call(good, lambda: "fb") == "device"
    assert br.state == "closed" and calls["n"] == 1
    # Healthy again: subsequent calls dispatch normally.
    assert br.call(good, lambda: "fb") == "device"


def test_breaker_failed_probe_reopens():
    br, clk = mkbreaker("u-reprobe")
    boom = lambda: (_ for _ in ()).throw(RuntimeError("x"))
    for _ in range(3):
        br.call(boom, lambda: "fb")
    clk.t = 11.0
    assert br.call(boom, lambda: "fb") == "fb"  # probe fails
    assert br.state == "open"
    # A fresh timeout applies before the next probe.
    assert br.call(lambda: "dev", lambda: "fb") == "fb"
    clk.t = 22.0
    assert br.call(lambda: "dev", lambda: "fb") == "dev"
    assert br.state == "closed"


def test_breaker_deadline_overrun_counts_but_keeps_completed_result():
    br, clk = mkbreaker("u-deadline", failure_threshold=2, deadline=1.0)

    def slow():
        clk.t += 5.0  # blows the 1s budget
        return "late-device"

    # The result is already in hand and bit-identical by contract:
    # return it, but count the failure so a degrading device opens the
    # circuit (and THEN dispatches go scalar up front).
    assert br.call(slow, lambda: "fb") == "late-device"
    assert br.consecutive_failures == 1 and br.state == "closed"
    assert "deadline" in (br.last_error or "")
    assert br.call(slow, lambda: "fb") == "late-device"
    assert br.state == "open"
    assert br.call(slow, lambda: "fb") == "fb"  # open: device not tried


def test_breaker_programming_errors_pass_through():
    """TypeError/IndexError/etc. are bugs, not device failures — the
    breaker must re-raise them, not mask them behind the oracle."""
    br, _ = mkbreaker("u-passthrough")
    with pytest.raises(TypeError):
        br.call(lambda: (_ for _ in ()).throw(TypeError("bug")), lambda: "fb")
    assert br.consecutive_failures == 0 and br.state == "closed"


def test_breaker_probe_slot_released_when_passthrough_escapes():
    """A TypeError escaping the half-open probe must not wedge the
    breaker: the probe slot is released and the NEXT call probes."""
    br, clk = mkbreaker("u-probe-abort")
    boom = lambda: (_ for _ in ()).throw(RuntimeError("x"))
    for _ in range(3):
        br.call(boom, lambda: "fb")
    clk.t = 11.0  # past recovery: next call is the probe
    with pytest.raises(TypeError):
        br.call(lambda: (_ for _ in ()).throw(TypeError("bug")), lambda: "fb")
    assert br.state == "half-open"
    # The breaker is NOT wedged: this call wins the probe slot and
    # restores service.
    assert br.call(lambda: "dev", lambda: "fb") == "dev"
    assert br.state == "closed"


def test_breaker_disabled_is_a_pure_bypass():
    br, _ = mkbreaker("u-bypass", enabled=False)
    with pytest.raises(RuntimeError):
        br.call(lambda: (_ for _ in ()).throw(RuntimeError("x")), lambda: "fb")
    assert br.state == "closed" and br.consecutive_failures == 0


def test_breaker_health_snapshot_exported():
    br, _ = mkbreaker("u-health")
    br.call(lambda: (_ for _ in ()).throw(RuntimeError("x")), lambda: None)
    snap = health_snapshot()["breakers"]["u-health"]
    assert snap["state"] == "closed" and snap["consecutive-failures"] == 1
    assert "exception" in snap["last-error"]


# -- restart policy -----------------------------------------------------


def test_restart_policy_backoff_deterministic_jittered_capped():
    p = RestartPolicy(base_delay=0.5, max_delay=8.0, multiplier=2.0, jitter=0.1)
    a = [p.delay("ospfv2", i) for i in range(8)]
    b = [p.delay("ospfv2", i) for i in range(8)]
    assert a == b, "jitter must be deterministic per (actor, attempt)"
    # Exponential envelope with +/-10% jitter, capped at max_delay * 1.1.
    for i, d in enumerate(a):
        base = min(0.5 * 2.0 ** i, 8.0)
        assert base * 0.9 <= d <= base * 1.1
    # Distinct actors de-synchronize their restarts.
    assert p.delay("ospfv2", 0) != p.delay("isis", 0)


# -- supervisor on a virtual-clock loop ---------------------------------


class Worker(Actor):
    name = "worker"

    def __init__(self):
        self.got = []
        self.restarts = 0

    def handle(self, msg):
        self.got.append(msg)

    def on_restart(self):
        self.restarts += 1


def mksupervised(policy=None):
    loop = EventLoop(clock=VirtualClock())
    sup = Supervisor(policy or RestartPolicy(base_delay=1.0, jitter=0.0)).install(loop)
    w = Worker()
    loop.register(w)
    return loop, sup, w


def test_supervisor_restarts_crashed_actor_and_redelivers_held_mail():
    loop, sup, w = mksupervised()
    before = telemetry.snapshot(prefix="holo_resilience_actor_restarts")
    loop.send("worker", PoisonPill())
    loop.run_until_idle()
    assert "worker" in loop._crashed
    # Mail sent while down is held, not dropped (supervised loop).
    assert loop.send("worker", "while-down")
    loop.run_until_idle()
    assert w.got == []  # not delivered yet: actor still crashed
    loop.advance(2.0)  # past the 1s backoff: restart fires
    assert "worker" not in loop._crashed
    assert w.restarts == 1 and w.got == ["while-down"]
    assert sup.restarts["worker"] == 1
    after = telemetry.snapshot(prefix="holo_resilience_actor_restarts")
    assert (
        after.get("holo_resilience_actor_restarts_total{actor=worker}", 0)
        > before.get("holo_resilience_actor_restarts_total{actor=worker}", 0)
    )
    # Service actually restored: new mail flows normally.
    loop.send("worker", "after")
    loop.run_until_idle()
    assert w.got == ["while-down", "after"]


def test_supervisor_crash_loop_parks_actor_degraded():
    loop, sup, w = mksupervised(
        RestartPolicy(
            base_delay=0.5, jitter=0.0, crash_loop_threshold=3,
            crash_loop_window=300.0,
        )
    )
    for _ in range(3):
        loop.send("worker", PoisonPill())
        loop.advance(60.0)  # crash -> backoff -> restart (until degraded)
    assert "worker" in sup.degraded
    assert not loop.send("worker", "dead-letter"), "degraded refuses mail"
    loop.advance(120.0)
    assert "worker" in loop._crashed, "no further restarts"
    assert sup.restarts.get("worker", 0) == 2  # third crash degraded
    health = health_snapshot()["supervision"]
    assert "worker" in health["degraded-actors"]


def test_supervisor_old_crashes_age_out_of_the_window():
    loop, sup, w = mksupervised(
        RestartPolicy(
            base_delay=0.5, jitter=0.0, crash_loop_threshold=3,
            crash_loop_window=10.0,
        )
    )
    for _ in range(5):  # spaced far beyond the window: never a crash loop
        loop.send("worker", PoisonPill())
        loop.advance(100.0)
    assert "worker" not in sup.degraded
    assert sup.restarts["worker"] == 5


def test_held_mail_is_bounded_and_drops_are_introspectable():
    loop, sup, w = mksupervised()
    loop.send("worker", PoisonPill())
    loop.run_until_idle()
    loop.held_mail_limit = 8
    accepted = sum(bool(loop.send("worker", i)) for i in range(20))
    assert accepted == 8
    # The 12 refused messages are the operator's lost-mail signal.
    snap = loop.introspect()["actors"]["worker"]
    assert snap["held-mail-dropped"] == 12 and snap["crashed"]
    loop.advance(5.0)
    assert w.got == list(range(8))


def test_supervisor_self_heals_after_its_own_crash():
    """A crashed supervisor cannot wait on its own held inbox: it
    self-heals immediately, and supervision of OTHER actors survives."""
    loop, sup, w = mksupervised()
    loop.send(sup.name, PoisonPill())
    loop.run_until_idle()
    assert sup.name not in loop._crashed, "self-healed on the spot"
    # Supervision still works end to end afterwards.
    loop.send("worker", PoisonPill())
    loop.run_until_idle()
    loop.advance(2.0)
    assert w.restarts == 1 and sup.restarts["worker"] == 1
    assert sup.crashes[sup.name] == 1  # the incident is still counted


def test_unadopt_forgets_verdicts_so_replaced_instances_are_supervised():
    """Tearing an instance down on purpose is not a crash: the SAME
    supervisor must supervise a re-created actor of the same name
    afresh — no inherited degraded verdict, no stale crash history (the
    natural remediation for a crash loop is delete + re-create).
    Mirrors the daemon shape: supervisor on the home loop, the instance
    on its own adopted loop."""
    home = EventLoop(clock=VirtualClock())
    sup = Supervisor(
        RestartPolicy(
            base_delay=0.5, jitter=0.0, crash_loop_threshold=2,
            crash_loop_window=300.0,
        )
    ).install(home)

    def spin(inst_loop):
        # Drive both cooperative loops: deliveries on each, then the
        # home clock forward so backoff/restart timers fire.
        for _ in range(4):
            inst_loop.run_until_idle()
            home.advance(10.0)
            inst_loop.run_until_idle()

    loop_a = EventLoop(clock=VirtualClock())
    sup.adopt(loop_a)
    w1 = Worker()
    loop_a.register(w1)
    for _ in range(2):  # crash loop -> degraded
        loop_a.send("worker", PoisonPill())
        spin(loop_a)
    assert "worker" in sup.degraded
    # Unplace the instance: loop dropped, verdicts cleared.
    sup.unadopt(loop_a)
    assert "worker" not in sup.degraded
    assert not any(lp is loop_a for lp, _ in sup._loops)
    # Re-placed incarnation: fresh loop, same actor name.
    loop_b = EventLoop(clock=VirtualClock())
    sup.adopt(loop_b)
    w2 = Worker()
    loop_b.register(w2)
    loop_b.send("worker", PoisonPill())
    spin(loop_b)
    assert w2.restarts == 1, "one crash on the new incarnation restarts"
    assert "worker" not in sup.degraded


def test_supervisor_restarts_threaded_loop_actor_on_its_own_thread():
    """Adopted ThreadedLoop: the crash notice marshals to the home
    loop, and the restart marshals BACK — on_restart and held-mail
    redelivery run on the instance's pump thread, never the
    supervisor's."""
    import threading
    import time as _time

    from holo_tpu.utils.preempt import ThreadedLoop

    home = EventLoop(clock=VirtualClock())
    sup = Supervisor(RestartPolicy(base_delay=0.5, jitter=0.0)).install(home)
    tl = ThreadedLoop(name="inst")
    threads = []

    class TWorker(Worker):
        def on_restart(self):
            super().on_restart()
            threads.append(threading.get_ident())

    w = TWorker()
    tl.register(w, name="worker")
    sup.adopt(tl.loop, sender=tl.send)  # before start, like the daemon
    tl.start()
    tl.send("worker", PoisonPill())

    def wait(cond, what):
        deadline = _time.monotonic() + 10
        while not cond() and _time.monotonic() < deadline:
            _time.sleep(0.01)
            home.run_until_idle()  # pump CrashNotice / RestartDone
        assert cond(), what

    wait(lambda: "worker" in tl.loop._crashed, "crash")
    assert tl.send("worker", "while-down")  # held on the adopted loop
    home.advance(1.0)  # backoff expires -> RestartDue marshals to tl
    wait(lambda: sup.restarts.get("worker") == 1, "restart counted")
    assert w.restarts == 1
    assert threads and threads[0] == tl._thread.ident, (
        "on_restart must run on the instance's pump thread"
    )
    wait(lambda: w.got == ["while-down"], "held mail redelivered")
    tl.stop()


def test_restart_runner_crash_self_heals_and_supervision_survives():
    """Chaos may kill the restart runner itself; it cannot be restarted
    through its own dead inbox, so it heals in the crash callback — and
    actors on that loop still restart afterwards."""
    import time as _time

    from holo_tpu.utils.preempt import ThreadedLoop

    home = EventLoop(clock=VirtualClock())
    sup = Supervisor(RestartPolicy(base_delay=0.5, jitter=0.0)).install(home)
    tl = ThreadedLoop(name="inst2")
    w = Worker()
    tl.register(w, name="worker")
    sup.adopt(tl.loop, sender=tl.send)
    tl.start()
    tl.send(Supervisor.RUNNER, PoisonPill())

    def wait(cond, what):
        deadline = _time.monotonic() + 10
        while not cond() and _time.monotonic() < deadline:
            _time.sleep(0.01)
            home.run_until_idle()
        assert cond(), what

    wait(lambda: sup.crashes.get(Supervisor.RUNNER) == 1, "runner crash seen")
    assert Supervisor.RUNNER not in tl.loop._crashed, "runner self-healed"
    tl.send("worker", PoisonPill())
    wait(lambda: "worker" in tl.loop._crashed, "worker crash")
    home.advance(1.0)  # backoff -> RestartDue marshals through the runner
    wait(lambda: sup.restarts.get("worker") == 1, "worker restarted")
    assert w.restarts == 1
    tl.stop()


# -- fault plans --------------------------------------------------------


def test_fault_plan_streams_deterministic_and_site_independent():
    a, b = FaultInjector(FaultPlan(seed=7)), FaultInjector(FaultPlan(seed=7))
    sa = [a._rng("fabric.drop").random() for _ in range(50)]
    sb = [b._rng("fabric.drop").random() for _ in range(50)]
    assert sa == sb, "same seed + site -> same stream"
    # Draws on another site's stream must not perturb this one.
    c = FaultInjector(FaultPlan(seed=7))
    c._rng("netio.send").random()
    sc = [c._rng("fabric.drop").random() for _ in range(50)]
    assert sc == sa
    assert [
        FaultInjector(FaultPlan(seed=8))._rng("fabric.drop").random()
        for _ in range(50)
    ] != sa


def test_forced_dispatch_failures_burn_down_exactly():
    inj = FaultInjector(FaultPlan(dispatch_fail={"spf.dispatch": 2}))
    with inject(inj):
        for _ in range(2):
            with pytest.raises(InjectedFault):
                faults_mod.crashpoint("spf.dispatch")
        faults_mod.crashpoint("spf.dispatch")  # exhausted: no-op
        faults_mod.crashpoint("frr.dispatch")  # other sites untouched
    assert inj.injected["spf.dispatch"] == 2
    faults_mod.crashpoint("spf.dispatch")  # disarmed: no-op


def test_faulty_netio_raises_per_plan_and_forwards_rest():
    sent = []

    class Sink:
        def send(self, ifname, src, dst, data):
            sent.append(data)

    inj = FaultInjector(FaultPlan(seed=3, send_error_prob=0.5))
    io = inj.wrap_netio(Sink())
    errors = 0
    for i in range(40):
        try:
            io.send("e0", None, None, i)
        except OSError:
            errors += 1
    assert errors == inj.injected["netio.send"] > 0
    assert len(sent) == 40 - errors


def test_jittered_advance_preserves_total_time():
    inj = FaultInjector(FaultPlan(seed=1, timer_jitter=0.5))
    loop = EventLoop(clock=VirtualClock())
    got = []

    class T(Actor):
        name = "t"

        def handle(self, msg):
            got.append((msg, loop.clock.now()))

    loop.register(T())
    loop.timer("t", lambda: "fire").start(10.0)
    inj.jittered_advance(loop, 30.0, steps=7)
    assert loop.clock.now() == pytest.approx(30.0)
    assert [m for m, _ in got] == ["fire"]


# -- txqueue drop-cause attribution -------------------------------------


def test_txqueue_drop_causes_attributed():
    import threading

    from holo_tpu.utils.txqueue import TxTaskNetIo

    gate = threading.Event()

    class SlowBadSink:
        def __init__(self):
            self.fail = False

        def send(self, ifname, src, dst, data):
            if ifname == "slow0":
                gate.wait(timeout=10)
            if self.fail:
                raise OSError("wire died")

    sink = SlowBadSink()

    def causes(ifname):
        snap = telemetry.snapshot(prefix="holo_txqueue_dropped")
        return {
            cause: snap.get(
                f"holo_txqueue_dropped_total{{ifname={ifname},cause={cause}}}", 0
            )
            for cause in ("overflow", "send_error", "closed")
        }

    # overflow: bounded enqueue against a gated wire times out.
    tx = TxTaskNetIo(sink, maxsize=1, put_timeout=0.05)
    base = causes("slow0")
    for i in range(4):
        tx.send("slow0", None, None, i)
    assert causes("slow0")["overflow"] > base["overflow"]
    gate.set()
    tx.close()

    # send_error: the pump's send raised — the accepted packet is gone.
    sink2 = SlowBadSink()
    sink2.fail = True
    tx2 = TxTaskNetIo(sink2)
    base = causes("bad0")
    tx2.send("bad0", None, None, b"x")
    tx2.close()
    assert causes("bad0")["send_error"] > base["send_error"]

    # closed: late send after teardown.
    base = causes("bad0")
    tx2.send("bad0", None, None, b"late")
    assert causes("bad0")["closed"] > base["closed"]


# -- event recorder crash-safe flush ------------------------------------


def test_event_recorder_flush_fsyncs_journal(tmp_path):
    from holo_tpu.utils.event_recorder import EventRecorder, read_entries

    rec = EventRecorder(tmp_path / "ev.jsonl")
    rec.record("a", 1.0, {"k": 1})
    rec.flush()  # the SIGTERM path: flush + fsync, file stays open
    entries = read_entries(tmp_path / "ev.jsonl")
    assert len(entries) == 1 and entries[0]["actor"] == "a"
    rec.record("a", 2.0, {"k": 2})
    rec.close()
    rec.close()  # idempotent
    rec.flush()  # after close: a no-op, never a crash
    assert len(read_entries(tmp_path / "ev.jsonl")) == 2
    # JSON stays one-entry-per-line greppable after fsync interleaving.
    lines = (tmp_path / "ev.jsonl").read_text().splitlines()
    assert all(json.loads(l) for l in lines)
