"""SLO plane + synthetic canary (ISSUE 20): burn math, sentinel
latching, objective routing, canary probe attribution, and the
disarmed one-check gate.

The burn tests pin the SRE arithmetic to hand-computed fractions under
an injected clock.  The sentinel tests prove the latch contract — one
fire per excursion, re-arm on recovery, warn-only.  The canary tests
drive the REAL paths: probes through a virtual loop attribute at
``fib_commit`` with zero unattributed closes; an injected
``FaultPlan.dispatch_delay`` on the ``canary.probe`` seam trips the
fast-window sentinel exactly once while the clean arm stays silent;
and a seeded storm's production FIB digest is byte-identical with a
canary riding vs never built.  The disarmed tests poison
``profiling.clock`` and walk every seam — no clock read, no sketch
write, hook uninstalled — the same structural gate as critpath's.
"""

from __future__ import annotations

import json
import threading

import pytest

from holo_tpu import telemetry
from holo_tpu.resilience import faults
from holo_tpu.telemetry import (
    canary,
    convergence,
    observatory,
    profiling,
    slo,
)
from holo_tpu.telemetry.slo import Objective, SloEngine


@pytest.fixture(autouse=True)
def _reset_slo_state():
    yield
    from holo_tpu.pipeline import dispatch

    canary.configure(False)
    slo.configure(False)
    convergence.configure(0)
    observatory.configure(enabled=False)
    dispatch.reset_process_pipeline()
    profiling.set_device_profiling(False)
    profiling.set_stage_timer(None)


class _FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


# -- objective model ------------------------------------------------------

def test_objective_validation():
    with pytest.raises(ValueError):
        Objective("x", kind="throughput")
    with pytest.raises(ValueError):
        Objective("x", target=1.0)
    with pytest.raises(ValueError):
        Objective("x", quantile=0.0)
    with pytest.raises(ValueError):
        Objective("x", threshold_s=0.0)


def test_objective_from_config_kebab_keys():
    o = Objective.from_config({
        "name": "ospf-fib", "kind": "latency", "source": "lsa",
        "quantile": 0.95, "threshold-ms": 500.0, "target": 0.99,
    })
    assert o.name == "ospf-fib"
    assert o.source == "lsa"
    assert o.threshold_s == pytest.approx(0.5)
    assert o.target == 0.99
    # defaults fill in
    assert Objective.from_config({"name": "d"}).kind == "latency"


def test_engine_rejects_duplicates_and_bad_windows():
    with pytest.raises(ValueError):
        SloEngine(objectives=(Objective("a"), Objective("a")))
    with pytest.raises(ValueError):
        SloEngine(fast_window=600.0, slow_window=60.0)


# -- burn math ------------------------------------------------------------

def test_burn_and_budget_hand_computed():
    clk = _FakeClock(1000.0)
    eng = SloEngine(
        objectives=(Objective("o", "latency", "*", 0.99, 1.0, 0.9),),
        clock=clk, fast_window=60.0, slow_window=600.0, check_every=0,
    )
    for _ in range(19):
        eng.note_endcut("lsa", 0.5, False)  # good
    eng.note_endcut("lsa", 2.0, False)  # bad
    st = eng.objective("o")
    frac, good, bad = eng._bad_frac(st, clk.t, eng.fast_window)
    assert (good, bad) == (19, 1)
    assert frac == pytest.approx(0.05)
    # burn = bad_frac / (1 - target) = 0.05 / 0.1
    assert eng.burn(st, clk.t, eng.fast_window) == pytest.approx(0.5)
    assert eng.budget_remaining(st, clk.t) == pytest.approx(0.5)
    # Empty window -> no verdict, not a zero verdict.
    clk.t += 10_000.0
    assert eng.burn(st, clk.t, eng.fast_window) is None


def test_buckets_trim_past_slow_window():
    clk = _FakeClock(0.0)
    eng = SloEngine(
        objectives=(Objective("o", target=0.9),),
        clock=clk, fast_window=60.0, slow_window=600.0, check_every=0,
    )
    st = eng.objective("o")
    for i in range(100):
        clk.t = i * 60.0
        eng.note_endcut("lsa", 0.1, False)
    eng.checkpoint()
    floor = int((clk.t - eng.slow_window) // eng.bucket_w)
    assert all(i >= floor for i in st.buckets)


def test_sentinel_latches_once_and_rearms():
    clk = _FakeClock(50.0)
    eng = SloEngine(
        objectives=(Objective("o", "latency", "*", 0.99, 1.0, 0.5),),
        clock=clk, fast_window=60.0, slow_window=600.0,
        fast_burn=1.0, slow_burn=100.0, check_every=0,
    )
    st = eng.objective("o")
    for _ in range(3):
        eng.note_endcut("lsa", 9.0, False)  # burn 2.0 > 1.0
    assert st.fires["fast"] == 1  # latched: one fire for the excursion
    for _ in range(5):
        eng.note_endcut("lsa", 9.0, False)
    assert st.fires["fast"] == 1
    for _ in range(10):
        eng.note_endcut("lsa", 0.1, False)  # frac 8/18 -> burn 0.89
    eng.checkpoint()
    assert st.latched["fast"] is False  # re-armed on recovery
    for _ in range(30):
        eng.note_endcut("lsa", 9.0, False)
    assert st.fires["fast"] == 2  # second excursion fires once more
    # warn-only surface: the counter matches the latch tally
    fires = telemetry.snapshot(prefix="holo_slo_sentinel_fires_total")
    assert any(v >= 2 for v in fires.values())


def test_canary_endcuts_never_grade_production_objectives():
    eng = SloEngine(clock=_FakeClock(), check_every=0)
    eng.note_endcut("canary", 99.0, False)
    assert eng.objective("trigger-fib").events == 0
    assert eng.objective("canary").events == 0  # probes only, via note_probe


def test_endcut_routes_by_trigger_source():
    clk = _FakeClock(10.0)
    eng = SloEngine(
        objectives=(
            Objective("all", "latency", "*", 0.99, 1.0, 0.9),
            Objective("lsa-only", "latency", "lsa", 0.99, 1.0, 0.9),
        ),
        clock=clk, check_every=0,
    )
    eng.note_endcut("lsa", 0.1, False)
    eng.note_endcut("bfd", 0.1, True)
    assert eng.objective("all").events == 2
    assert eng.objective("lsa-only").events == 1
    assert eng.objective("all").fallbacks == 1


def test_delivery_objective_grades_served_vs_shed():
    eng = SloEngine(clock=_FakeClock(77.0), check_every=0)
    for _ in range(5):
        eng.note_served("background")
    for _ in range(5):
        eng.note_shed("background", "expired")
    st = eng.objective("background-delivery")
    frac, good, bad = eng._bad_frac(st, 77.0, eng.fast_window)
    assert (good, bad) == (5, 5)
    assert eng._sheds == {("background", "expired"): 5}
    # correctness class has no delivery objective: silently unrouted
    eng.note_served("correctness")
    assert st.events == 10


# -- wiring: hooks and feeds ---------------------------------------------

def test_configure_installs_and_uninstalls_endcut_hook():
    eng = slo.configure(check_every=0)
    assert convergence._SLO_HOOK is eng
    slo.configure(False)
    assert convergence._SLO_HOOK is None
    assert slo.active() is None


def test_fib_commit_feeds_trigger_fib_objective():
    clk = _FakeClock(5.0)
    convergence.configure(64, clock=clk)
    eng = slo.configure(check_every=0, clock=clk)
    eid = convergence.begin("lsa")
    clk.t = 5.5
    convergence.fib_commit(eids=(eid,))
    st = eng.objective("trigger-fib")
    assert st.events == 1
    assert st.sketch.count == 1
    assert eng._bad_frac(st, clk.t, eng.fast_window)[1] == 1  # good


def test_pipeline_serve_and_shed_feed_delivery_objective():
    from holo_tpu.pipeline.dispatch import DispatchPipeline

    eng = slo.configure(check_every=0)
    pipe = DispatchPipeline(depth=2, name="slo-feed")
    try:
        t = pipe.submit("k", "spf", run=lambda: "v", cls="background")
        assert t.result(5.0) == "v"
    finally:
        pipe.close()
    st = eng.objective("background-delivery")
    assert eng._bad_frac(st, eng._clock(), eng.fast_window)[1] >= 1


def test_shed_margin_histogram_carries_event_exemplar():
    from holo_tpu.pipeline.dispatch import DispatchPipeline
    from holo_tpu.telemetry.provider import _exemplar_leaf

    convergence.configure(64)
    eng = slo.configure(check_every=0)
    pipe = DispatchPipeline(depth=1, name="slo-shed")
    gate = threading.Event()
    try:
        stall = pipe.submit("hold", "spf", run=lambda: gate.wait(5.0))
        eid = convergence.begin("lsa")
        with convergence.activation((eid,)):
            bg = pipe.submit(
                "k", "spf", run=lambda: "v",
                cls="background", deadline=0.05,
            )
        import time

        time.sleep(0.2)  # worker busy: the deadline lapses in-queue
        gate.set()
        assert bg.result(5.0) is None  # shed resolves empty, not raising
        assert bg.shed is not None
    finally:
        gate.set()
        pipe.close()
    assert eng._sheds.get(("background", "expired"), 0) >= 1
    fams = {f.name: f for f in telemetry.registry().families()}
    hist = fams["holo_pipeline_shed_margin_seconds"]
    total = sum(child.count for _k, child in hist.children())
    assert total >= 1
    joined = ";".join(
        _exemplar_leaf(child) for _k, child in hist.children()
    )
    assert "event_id=" in joined


def test_checkpoint_seeds_observatory_ledger_rows():
    obs = observatory.configure(check_every=0)
    clk = _FakeClock(3.0)
    eng = slo.configure(check_every=0, clock=clk)
    eng.note_endcut("lsa", 0.2, False)
    before = obs.sentinel()["seeded"]
    eng.checkpoint()
    assert obs.sentinel()["seeded"] > before


def test_provider_leaf_carries_slo_and_canary():
    from holo_tpu.telemetry.provider import TelemetryStateProvider

    eng = slo.configure(check_every=0)
    eng.note_served("background")
    st = TelemetryStateProvider().get_state()["holo-telemetry"]
    leaf = st["slo"]
    assert leaf["objectives"]["background-delivery"]["events"] == 1
    assert leaf["objectives"]["trigger-fib"]["burn-fast"] is None


# -- canary: probe attribution -------------------------------------------

def _virtual_loop():
    from holo_tpu.utils.runtime import EventLoop, VirtualClock

    return EventLoop(clock=VirtualClock())


def test_canary_probes_attribute_through_fib_commit():
    loop = _virtual_loop()
    convergence.configure(256, clock=loop.clock.now)
    eng = slo.configure(check_every=0)
    prober = canary.CanaryProber(loop, period=2.0, warmup=10.0)
    try:
        prober.start()
        loop.advance(30.0)
    finally:
        prober.stop()
    assert prober.probes >= 10
    # A flip pair coalesced into one SPF hold cancels out (metric back
    # where it started -> no install), so a couple of probes may still
    # be open — but every CLOSED probe must balance the tallies.
    assert prober.completed == prober.probes - len(prober._open)
    assert prober.completed >= 8
    assert prober.unattributed == 0
    assert prober.unattributed_fraction() == 0.0
    st = eng.objective("canary")
    assert st.events == prober.completed
    # every probe graded good on its real wall
    assert eng._bad_frac(st, eng._clock(), eng.fast_window)[2] == 0
    # the flip is a REAL route change: the leaf prefix is installed
    from ipaddress import IPv4Network

    assert IPv4Network("198.51.100.0/24") in prober.net.kernel.fib


def test_canary_tracker_disarmed_grades_nothing():
    loop = _virtual_loop()
    slo.configure(check_every=0)
    prober = canary.CanaryProber(loop, period=2.0, warmup=10.0)
    try:
        prober.start()
        loop.advance(10.0)
    finally:
        prober.stop()
    assert prober.probes == 0  # no tracker -> no causal ids -> no probes


def test_canary_configure_requires_loop():
    with pytest.raises(ValueError):
        canary.configure(True, loop=None)
    with pytest.raises(ValueError):
        canary.CanaryProber(_virtual_loop(), period=0.0)


def test_canary_breach_trips_fast_sentinel_exactly_once():
    from holo_tpu.pipeline import dispatch

    loop = _virtual_loop()
    convergence.configure(256, clock=loop.clock.now)
    eng = slo.configure(check_every=0)
    dispatch.configure_process_pipeline(depth=2, capacity=32)
    prober = canary.CanaryProber(
        loop, period=2.0, deadline=0.25, warmup=10.0
    )
    st = eng.objective("canary")
    # The breaker registry is process-global: earlier suites leave their
    # own tripped breakers behind.  Only a breaker NEWLY opened by this
    # test would indicate the sentinel touched dispatch.
    from holo_tpu.resilience import health_snapshot

    def _open_breakers():
        return {
            name
            for name, b in health_snapshot().get("breakers", {}).items()
            if b.get("state") == "open"
        }

    open_before = _open_breakers()
    try:
        prober.start()
        # Clean arm first: probes ride the pipeline, sentinel silent.
        loop.advance(10.0)
        assert st.fires["fast"] == 0
        # Breach: the canary.probe delaypoint sleeps 0.5 s REAL per
        # dispatch — over the 0.25 s objective threshold, invisible to
        # the virtual end-cuts.
        with faults.inject(
            faults.FaultPlan(dispatch_delay={"canary.probe": 0.5})
        ):
            loop.advance(8.0)
    finally:
        prober.stop()
        dispatch.reset_process_pipeline()
    bad = eng._bad_frac(st, eng._clock(), eng.fast_window)[2]
    assert bad >= 2  # the slowed probes graded bad
    assert st.fires["fast"] == 1  # latched: exactly one fire
    assert st.latched["fast"] is True
    # warn-only: no breaker newly opened, dispatch unaffected
    assert _open_breakers() == open_before


def test_storm_fib_digest_identical_with_canary_riding():
    from holo_tpu.spf.backend import ScalarSpfBackend
    from holo_tpu.spf.synth_storm import run_convergence_storm
    from holo_tpu.telemetry.canary import fib_digest

    def run(arm: bool):
        prober = None

        def hook(net, _i, _now):
            nonlocal prober
            if arm and prober is None:
                slo.configure(check_every=0)
                prober = canary.CanaryProber(
                    net.loop, period=2.0, warmup=10.0
                )
                prober.start()

        _rep, _digest, net = run_convergence_storm(
            n_routers=24, events=12, seed=7,
            spf_backend=ScalarSpfBackend(),
            event_hook=hook,
        )
        if prober is not None:
            prober.stop()
            assert prober.completed > 0
            assert prober.unattributed_fraction() < 0.01
        d = fib_digest(net.kernel.fib)
        slo.configure(False)
        return d

    control = run(arm=False)
    armed = run(arm=True)
    # The canary's routes live in its OWN kernel: the production FIB is
    # byte-identical whether the canary rode the storm or never existed.
    assert armed == control


# -- surfaces -------------------------------------------------------------

def test_explain_slo_byte_identical(capsys):
    from holo_tpu.tools.cli import main as cli_main

    argv = [
        "explain", "--slo", "--storm", "32",
        "--events", "12", "--seed", "5",
    ]
    assert cli_main(argv) == 0
    out1 = capsys.readouterr().out
    assert cli_main(argv) == 0
    out2 = capsys.readouterr().out
    assert out1 == out2
    assert "slo — windows:" in out1
    assert "trigger-fib" in out1 and "canary" in out1
    # The CLI disarmed the plane on exit.
    assert slo.active() is None
    assert canary.active() is None


def test_explain_slo_json_has_budget_math(capsys):
    from holo_tpu.tools.cli import main as cli_main

    assert cli_main(
        ["explain", "--slo", "--storm", "32", "--events", "12",
         "--seed", "5", "--json"]
    ) == 0
    doc = json.loads(capsys.readouterr().out)
    rows = {r["objective"]: r for r in doc["slo"]["objectives"]}
    tf = rows["trigger-fib"]
    assert tf["events"] > 0
    assert tf["budget_remaining"] is not None
    cn = rows["canary"]
    assert cn["events"] > 0
    assert doc["slo"]["canary"]["completed"] == cn["events"]


# -- config ---------------------------------------------------------------

def test_config_parses_slo_and_canary_knobs(tmp_path):
    from holo_tpu.daemon.config import DaemonConfig

    p = tmp_path / "holod.toml"
    p.write_text(
        """
[telemetry]
convergence-events = 256
slo = true
slo-fast-window = 600.0
slo-slow-window = 7200.0
slo-fast-burn = 10.0
canary = true
canary-period = 2.5
canary-deadline = 0.5

[[telemetry.slo-objectives]]
name = "ospf-fib"
kind = "latency"
source = "lsa"
threshold-ms = 500.0
target = 0.99
"""
    )
    cfg = DaemonConfig.load(p)
    t = cfg.telemetry
    assert t.slo is True
    assert t.slo_fast_window == 600.0 and t.slo_slow_window == 7200.0
    assert t.slo_fast_burn == 10.0
    assert t.canary is True and t.canary_period == 2.5
    assert t.canary_deadline == 0.5
    (o,) = t.slo_objectives
    assert isinstance(o, Objective)
    assert o.source == "lsa" and o.threshold_s == pytest.approx(0.5)


def test_config_rejects_bad_slo_tables(tmp_path):
    from holo_tpu.daemon.config import DaemonConfig

    p = tmp_path / "holod.toml"
    p.write_text(
        """
[telemetry]
slo = true
slo-objectives = [{ name = "x", kind = "nope" }]
"""
    )
    with pytest.raises(ValueError, match="slo-objectives invalid"):
        DaemonConfig.load(p)
    p.write_text(
        """
[telemetry]
slo = true
slo-fast-window = 7200.0
slo-slow-window = 600.0
"""
    )
    with pytest.raises(ValueError, match="slo windows"):
        DaemonConfig.load(p)


def test_config_canary_requires_convergence_tracker(tmp_path):
    from holo_tpu.daemon.config import DaemonConfig

    p = tmp_path / "holod.toml"
    p.write_text("[telemetry]\ncanary = true\n")
    with pytest.raises(ValueError, match="convergence-events"):
        DaemonConfig.load(p)


# -- disarmed contract ----------------------------------------------------

def test_disarmed_seams_are_one_global_check(monkeypatch):
    assert slo.active() is None
    assert canary.active() is None

    def boom():
        raise AssertionError("disarmed SLO seam read the clock")

    monkeypatch.setattr(profiling, "clock", boom)
    # Every module seam returns before any clock read or sketch write.
    slo.note_probe(True, 0.01)
    slo.note_served("background")
    slo.note_shed("background", "expired")
    # The convergence end-cut hook is uninstalled: fib_commit pays one
    # None check, never an SLO clock read.
    assert convergence._SLO_HOOK is None


def test_disarmed_pipeline_path_never_reads_slo_clock(monkeypatch):
    from holo_tpu.pipeline.dispatch import DispatchPipeline

    assert slo.active() is None

    def boom():
        raise AssertionError("disarmed SLO seam read the clock")

    monkeypatch.setattr(profiling, "clock", boom)
    pipe = DispatchPipeline(depth=2, name="slo-off")
    try:
        # settle path (note_served seam) and shed path (note_shed seam)
        # both cross the disarmed seams without touching the clock
        t = pipe.submit("k", "spf", run=lambda: "v", cls="background")
        assert t.result(5.0) == "v"
    finally:
        pipe.close()
