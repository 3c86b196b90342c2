"""What an event waits for (ISSUE 38): the exact account of a thread's
wall by innermost armed span (``holo_profile_self_seconds_total``,
``profiling.self_seconds``), the collector's pauses as the span
``runtime.gc``, and the critical-path ledger's ``coalesce_wait`` cut at
the SPF run's begin (``hold`` on every waterfall record).

The account is held under a hand-moved stage timer, where every number
is known; the collector's callback does nothing under a swapped timer
(a counter clock counts reads), so what it does is held on the real
clock with the automatic collector off and one forced collection.
"""

from __future__ import annotations

import gc
import threading
from contextlib import contextmanager

import pytest

from holo_tpu import telemetry
from holo_tpu.telemetry import convergence, critpath, profiling
from holo_tpu.telemetry.critpath import PHASES, CritPathLedger, _decompose, _Rec
from holo_tpu.utils.runtime import Actor, EventLoop, VirtualClock

FAMILY = "holo_profile_self_seconds_total"


class HandClock:
    """A stage timer that moves only when the test moves it."""

    def __init__(self):
        self.t = 100.0
        self.reads = 0

    def __call__(self) -> float:
        self.reads += 1
        return self.t


@pytest.fixture
def clock():
    hand = HandClock()
    profiling.set_stage_timer(hand)
    try:
        yield hand
    finally:
        _disarm()


@pytest.fixture
def real_clock_no_collector():
    """Armed on the real clock with the automatic collector off: the
    only collection is the one the test forces."""
    gc.collect()
    gc.disable()
    try:
        yield
    finally:
        gc.enable()
        _disarm()


def _disarm() -> None:
    profiling.set_device_profiling(False)
    profiling.set_annotation_factory()
    profiling.set_stage_timer(None)
    critpath.configure(0)
    convergence.configure(0)


def _family() -> dict:
    """``{span: seconds}`` of the counter family, as a scrape reads it."""
    return {
        key.split("span=", 1)[1][:-1]: value
        for key, value in telemetry.snapshot(FAMILY).items()
    }


def _gained(before: dict, after: dict) -> dict:
    return {
        span: after[span] - before.get(span, 0.0)
        for span in after if after[span] != before.get(span, 0.0)
    }


# -- the account -------------------------------------------------------------


def test_children_sum_to_the_interval_and_a_nested_span_counts_once(clock):
    profiling.set_device_profiling(True)
    before, first = _family(), profiling.self_seconds()
    clock.t += 1.0  # under no span
    with profiling.stage("acct", "outer"):
        clock.t += 2.0
        with profiling.stage("acct", "inner"):
            clock.t += 4.0
        clock.t += 8.0
    clock.t += 16.0
    last = profiling.self_seconds()
    want = {"-": 17.0, "acct.outer": 10.0, "acct.inner": 4.0}
    assert _gained(first, last) == want
    assert _gained(before, _family()) == want  # the family: the same account
    assert sum(want.values()) == 31.0  # the interval, nothing twice


def test_a_snapshot_inside_an_open_span_is_exact(clock):
    profiling.set_device_profiling(True)
    first = profiling.self_seconds()
    with profiling.stage("acct", "open"):
        clock.t += 3.0
        inside = profiling.self_seconds()
        clock.t += 5.0
        at, totals = profiling.account_at(clock.t)
    assert _gained(first, inside) == {"acct.open": 3.0}
    assert at == clock.t and _gained(inside, totals) == {"acct.open": 5.0}
    # a stamp the account has overtaken gets the account's own time
    assert profiling.account_at(clock.t - 1.0) == (clock.t, totals)


def test_an_exception_closes_the_span_in_the_account(clock):
    profiling.set_device_profiling(True)
    first = profiling.self_seconds()
    with pytest.raises(KeyError):
        with profiling.stage("acct", "raises"):
            clock.t += 2.0
            raise KeyError("boom")
    clock.t += 7.0
    assert _gained(first, profiling.self_seconds()) == {
        "acct.raises": 2.0, "-": 7.0,
    }


def test_the_account_is_a_threads_own(clock):
    profiling.set_device_profiling(True)
    first = profiling.self_seconds()
    seen = {}

    def other():
        seen["first"] = profiling.self_seconds()
        with profiling.stage("acct", "other-thread"):
            clock.t += 2.0
        seen["last"] = profiling.self_seconds()

    with profiling.stage("acct", "main-thread"):
        worker = threading.Thread(target=other)
        worker.start()
        worker.join(timeout=10)
        assert not worker.is_alive()
    assert _gained(seen["first"], seen["last"]) == {"acct.other-thread": 2.0}
    assert _gained(first, profiling.self_seconds()) == {"acct.main-thread": 2.0}


def test_rearming_forgets_open_spans_and_the_time_disarmed(clock):
    profiling.set_device_profiling(True)
    with profiling.stage("acct", "across"):
        clock.t += 1.0
        profiling.set_device_profiling(False)
        clock.t += 50.0  # disarmed: nobody's
        profiling.set_device_profiling(True)
        first = profiling.self_seconds()
        clock.t += 2.0
    clock.t += 3.0
    assert _gained(first, profiling.self_seconds()) == {"-": 5.0}


def test_disarmed_the_account_reads_no_clock_and_is_empty(clock):
    reads = clock.reads
    assert profiling.self_seconds() == {}
    assert profiling.account_at(7.0) == (7.0, {})
    with profiling.stage("acct", "disarmed"):
        pass
    assert clock.reads == reads


# -- the collector's pauses --------------------------------------------------


def test_the_callback_is_in_gc_callbacks_only_while_armed():
    assert profiling._on_gc not in gc.callbacks
    profiling.set_device_profiling(True)
    try:
        profiling.set_device_profiling(True)  # armed twice: there once
        assert gc.callbacks.count(profiling._on_gc) == 1
    finally:
        profiling.set_device_profiling(False)
    assert profiling._on_gc not in gc.callbacks
    profiling.set_device_profiling(False)  # disarmed twice: no error


class _Labels:
    """Annotation factory that records the labels it brackets."""

    def __init__(self, raises: str | None = None):
        self.events, self.raises = [], raises

    @contextmanager
    def __call__(self, label: str):
        if label == self.raises:
            raise RuntimeError("annotation factory failed")
        self.events.append(("enter", label))
        try:
            yield
        finally:
            self.events.append(("exit", label))


def test_a_collection_is_the_span_runtime_gc_taken_out_of_the_span_it_broke(
    real_clock_no_collector,
):
    labels = _Labels()
    profiling.set_annotation_factory(labels)
    profiling.set_device_profiling(True)
    snap0 = telemetry.snapshot("holo_runtime_gc")
    first = profiling.self_seconds()
    with profiling.stage("acct", "collects"):
        gc.collect()
    gained = _gained(first, profiling.self_seconds())
    snap1 = telemetry.snapshot("holo_runtime_gc")
    key = "holo_runtime_gc_pause_seconds{generation=2}"
    pauses = snap1[key]["count"] - snap0.get(key, {"count": 0})["count"]
    assert pauses == 1
    assert "holo_runtime_gc_collected_total{generation=2}" in snap1
    # inside the annotation of the span it interrupted, and out of its time
    assert labels.events == [
        ("enter", "acct.collects"), ("enter", "runtime.gc"),
        ("exit", "runtime.gc"), ("exit", "acct.collects"),
    ]
    stage = telemetry.snapshot("holo_profile_stage_seconds")[
        "holo_profile_stage_seconds{site=acct,stage=collects,device=-}"
    ]
    assert gained["runtime.gc"] > 0.0
    assert gained["acct.collects"] + gained["runtime.gc"] == pytest.approx(
        stage["sum"], abs=2e-6
    )


def test_a_raising_annotation_factory_does_not_reach_the_collectors_caller(
    real_clock_no_collector,
):
    profiling.set_annotation_factory(_Labels(raises="runtime.gc"))
    profiling.set_device_profiling(True)
    first = profiling.self_seconds()
    gc.collect()  # must not raise
    after = profiling.self_seconds()
    assert _gained(first, after)["runtime.gc"] > 0.0
    with profiling.stage("acct", "after-gc"):
        pass  # ... and the span is closed: the next time is not its
    assert "acct.after-gc" in _gained(after, profiling.self_seconds())
    assert "runtime.gc" not in _gained(after, profiling.self_seconds())


def test_under_a_swapped_timer_the_callback_reads_no_clock(clock):
    profiling.set_device_profiling(True)
    reads, first = clock.reads, profiling.self_seconds()
    gc.collect()
    assert clock.reads == reads + 1  # the snapshot's own read
    clock.t += 1.0
    assert _gained(first, profiling.self_seconds()) == {"-": 1.0}


# -- coalesce_wait cut at the run's begin ------------------------------------


def test_phases_are_what_they_were():
    assert PHASES == (
        "wake", "coalesce_wait", "queue_wait", "marshal", "device",
        "force_wait", "rib", "fib_commit", "unattributed", "fallback",
    )
    assert "force0" not in _Rec.__slots__ and "run0" in _Rec.__slots__


@pytest.mark.parametrize("run0", [None, 0.05, 0.3, 0.45, 0.9])
def test_the_cut_moves_no_phase(run0):
    """run0 cuts coalesce_wait in two and leaves every phase where it
    was: missing, before sched, inside, and past the marshal's begin."""
    rec = _Rec("lsa", t0=0.0)
    rec.sched, rec.marshal0, rec.marshal1, rec.t_end = 0.1, 0.5, 0.6, 0.7
    plain = _decompose(rec, 0.7, False)
    rec.run0 = run0
    assert _decompose(rec, 0.7, False) == pytest.approx(plain, abs=1e-15)


def _ledger_event(ledger, stamps: dict, t_done: float = 1.0) -> dict:
    ledger.ev_begin(1, "lsa")
    rec = ledger._recs[1]
    rec.t0 = 0.0
    for name, value in stamps.items():
        setattr(rec, name, value)
    rec.t_end = t_done
    ledger.ev_done(1, "converged", False)
    return ledger.waterfalls()[-1]


@pytest.mark.parametrize("stamps, wait, prerun, unannounced", [
    # the run's begin inside the hold
    ({"sched": 0.1, "run0": 0.3, "marshal0": 0.5}, 0.2, 0.2, 0),
    # no run0 but a marshal: the whole hold is wait, and it is counted
    ({"sched": 0.1, "marshal0": 0.5}, 0.4, 0.0, 1),
    ({"sched": 0.1, "enqueue": 0.4, "launch0": 0.5}, 0.3, 0.0, 1),
    # a stamp out of order is clamped as every cut is
    ({"sched": 0.3, "run0": 0.1, "marshal0": 0.5}, 0.0, 0.2, 0),
    ({"sched": 0.1, "run0": 0.8, "marshal0": 0.5}, 0.4, 0.0, 0),
    # an event that ran no SPF (a local repair): nothing to announce
    ({}, 0.0, 0.0, 0),
    ({"sched": 0.1}, 0.0, 0.0, 0),
], ids=["inside", "no-run0", "no-run0-pipelined", "before-sched",
        "past-marshal", "stampless", "sched-only"])
def test_hold_is_wait_plus_prerun_and_a_missing_run0_is_counted(
    stamps, wait, prerun, unannounced
):
    ledger = CritPathLedger(check_every=0)
    record = _ledger_event(ledger, stamps)
    hold = record["hold"]
    assert hold["wait"] == pytest.approx(wait, abs=1e-9)
    assert hold["prerun"] == pytest.approx(prerun, abs=1e-9)
    assert hold["wait"] + hold["prerun"] == pytest.approx(
        record["phases"]["coalesce_wait"], abs=1e-9
    )
    assert hold["by"] == {}
    assert ledger.stats()["no_run_stamp"] == unannounced
    assert tuple(record["phases"]) == PHASES


@pytest.mark.parametrize("stamps, kept", [
    ({"sched": 0.1, "run0": 0.3, "marshal0": 0.5}, True),
    # a run that dispatched nothing: no coalesce_wait to cut or explain
    ({"sched": 0.1, "run0": 0.3}, False),
    # the cut clamped to the hold's end: the account is of another span
    ({"sched": 0.1, "run0": 0.3, "marshal0": 0.2}, False),
], ids=["stood", "no-dispatch", "clamped"])
def test_the_account_is_kept_only_where_the_cut_stood(stamps, kept):
    ledger = CritPathLedger(check_every=0)
    account = {"loop.routing": 0.15, "-": 0.3 - 0.1 - 0.15}
    hold = _ledger_event(ledger, {**stamps, "by": account})["hold"]
    assert hold["by"] == (
        {k: round(v, 9) for k, v in account.items()} if kept else {}
    )
    if kept:
        assert sum(hold["by"].values()) == pytest.approx(hold["wait"], abs=1e-9)


def test_configure_installs_and_clears_the_run_hook():
    assert convergence._RUN_HOOK is None
    convergence.configure(16)
    ledger = critpath.configure(check_every=0)
    try:
        assert convergence._RUN_HOOK == ledger.run_begin
    finally:
        critpath.configure(0)
        convergence.configure(0)
    assert convergence._RUN_HOOK is None
    pending = [3]
    with convergence.spf_run(pending, "disarmed") as eids:  # one None check
        assert eids == (3,) and pending == []


class _Dut(Actor):
    """Schedules on ``sched`` and runs its SPF on ``run``, as an
    instance does on its delay timer."""

    def __init__(self):
        self.pending: list = []

    def handle(self, msg) -> None:
        if msg == "sched":
            convergence.pend_schedule(self.pending, "lsa", "acct-dut")
            return
        with convergence.spf_run(self.pending, "acct-dut") as eids:
            with profiling.stage("ospf.spf", "topology"):
                sum(range(2000))
            with profiling.stage("spf.one", "marshal"):
                pass
            with profiling.stage("spf.one", "device"):
                pass
        convergence.observe(convergence.PHASE_RIB, eids=eids)
        convergence.fib_commit(eids=eids)


class _Routing(Actor):
    def handle(self, msg) -> None:
        gc.collect()


class _Idle(Actor):
    def handle(self, msg) -> None:
        sum(range(2000))


def _scheduled_then_run_three_deliveries_later() -> tuple[dict, dict]:
    loop = EventLoop(clock=VirtualClock())
    loop.register(_Dut(), name="acct-dut")
    loop.register(_Routing(), name="acct-routing")
    loop.register(_Idle(), name="acct-idle")
    convergence.configure(64, clock=loop.clock.now)
    ledger = critpath.configure(check_every=0)
    for actor, msg in (
        ("acct-dut", "sched"), ("acct-routing", "repair"),
        ("acct-idle", "tick"), ("acct-idle", "tick"), ("acct-dut", "run"),
    ):
        loop.send(actor, msg)
    loop.run_until_idle()
    [record] = ledger.waterfalls()
    return record, ledger.stats()


def test_the_wait_is_charged_to_the_deliveries_and_the_pause_in_it(
    real_clock_no_collector,
):
    profiling.set_device_profiling(True)
    gc0 = telemetry.snapshot("holo_runtime_gc_pause_seconds")
    stage0 = telemetry.snapshot("holo_profile_stage_seconds")
    record, stats = _scheduled_then_run_three_deliveries_later()
    hold, phases = record["hold"], record["phases"]
    assert stats["no_run_stamp"] == 0 and stats["completed"] == 1
    assert hold["wait"] + hold["prerun"] == pytest.approx(
        phases["coalesce_wait"], abs=1e-9
    )
    assert hold["wait"] > 0.0 and hold["prerun"] > 0.0  # topology: prerun
    by = hold["by"]
    assert sum(by.values()) == pytest.approx(hold["wait"], abs=1e-6)
    assert {"loop.acct-routing", "loop.acct-idle", "runtime.gc"} <= set(by)
    assert "ospf.spf.topology" not in by  # the run's own work is not wait
    # the forced collection: all of it under runtime.gc ...
    gc1 = telemetry.snapshot("holo_runtime_gc_pause_seconds")
    key = "holo_runtime_gc_pause_seconds{generation=2}"
    pause = gc1[key]["sum"] - gc0.get(key, {"sum": 0.0})["sum"]
    assert by["runtime.gc"] == pytest.approx(pause, abs=2e-6)
    # ... and none of it under the delivery it interrupted
    stage1 = telemetry.snapshot("holo_profile_stage_seconds")
    key = "holo_profile_stage_seconds{site=loop,stage=acct-routing,device=-}"
    delivery = stage1[key]["sum"] - stage0.get(key, {"sum": 0.0})["sum"]
    assert by["loop.acct-routing"] + by["runtime.gc"] == pytest.approx(
        delivery, abs=2e-6
    )
    assert by["loop.acct-routing"] < by["runtime.gc"]


def test_disarmed_the_cut_is_there_and_nothing_is_charged():
    try:
        record, stats = _scheduled_then_run_three_deliveries_later()
    finally:
        _disarm()
    hold = record["hold"]
    assert hold["by"] == {} and stats["no_run_stamp"] == 0
    assert hold["wait"] > 0.0
    assert hold["wait"] + hold["prerun"] == pytest.approx(
        record["phases"]["coalesce_wait"], abs=1e-9
    )
    assert profiling._on_gc not in gc.callbacks


def test_under_the_counter_clock_the_split_repeats_to_the_byte():
    """The explain report's clock counts reads: two runs of the same
    deliveries give the same records, the new key included."""
    from holo_tpu.telemetry.observatory import DeterministicTimer

    def once() -> dict:
        profiling.set_stage_timer(DeterministicTimer())
        profiling.set_device_profiling(True)
        try:
            record, _stats = _scheduled_then_run_three_deliveries_later()
        finally:
            _disarm()
        return record

    first, second = once(), once()
    assert first == second and first["hold"]["by"]
    assert sum(first["hold"]["by"].values()) == pytest.approx(
        first["hold"]["wait"], abs=1e-9
    )
    assert "runtime.gc" not in first["hold"]["by"]


def test_the_leaf_gains_no_run_stamp_and_keeps_its_keys():
    from holo_tpu.telemetry.provider import TelemetryStateProvider

    convergence.configure(16)
    critpath.configure(check_every=0)
    try:
        eid = convergence.begin("lsa")
        convergence.fib_commit(eids=(eid,))
        leaf = TelemetryStateProvider().get_state()["holo-telemetry"][
            "critical-path"
        ]
    finally:
        critpath.configure(0)
        convergence.configure(0)
    assert leaf["no_run_stamp"] == 0
    assert {
        "open", "completed", "dropped", "sheds", "capacity", "sketches",
        "verdicts", "phases",
    } <= set(leaf)
    assert set(leaf["phases"]) <= {*PHASES, "wall"}
