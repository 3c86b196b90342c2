"""Critical-path ledger (ISSUE 17): cut-model invariants, chaos
phase-attribution, determinism, and the disarmed one-check gate.

The cut-model tests fuzz the telescoping invariant (phase sum ==
end-to-end wall for ANY stamp subset, clamped or missing).  The chaos
tests drive the REAL paths — ``TpuSpfBackend`` under an injected
``FaultPlan.dispatch_delay`` (must book to ``device``), a real
``DispatchPipeline`` per-key ordering stall (must book to
``queue_wait``), the scalar-fallback close (must book to ``fallback``)
— at unit scale and over the seeded storm, where the injected delay
must inflate the device phase while the causal digest stays
byte-identical.  ``explain --critical-path`` must render byte-identical
output across two same-seed runs, and the disarmed path must cost one
module-global check (no clock read), same structural gate as the
observatory's.
"""

from __future__ import annotations

import random
import threading
import time

import pytest

from holo_tpu import telemetry
from holo_tpu.resilience import faults
from holo_tpu.telemetry import convergence, critpath, observatory, profiling
from holo_tpu.telemetry.critpath import (
    PHASES,
    CritPathLedger,
    _decompose,
    _Rec,
    _verdict,
)


@pytest.fixture(autouse=True)
def _reset_critpath_state():
    yield
    critpath.configure(0)
    convergence.configure(0)
    observatory.configure(enabled=False)
    profiling.set_device_profiling(False)
    profiling.set_stage_timer(None)


# -- cut model -----------------------------------------------------------

_STAMPS = (
    "sched", "run0", "enqueue", "launch0", "marshal0", "marshal1",
    "device_end", "force1", "spf", "rib", "t_end",
)


def test_phase_sum_equals_wall_fuzzed():
    """The telescoping invariant: for ANY subset of stamps at ANY
    values (ordered, disordered, out of range), every phase is
    non-negative and the vector sums to the wall exactly."""
    rng = random.Random(17)
    for _ in range(2000):
        rec = _Rec("lsa", t0=rng.uniform(0.0, 2.0))
        for stamp in _STAMPS:
            if rng.random() < 0.7:
                setattr(rec, stamp, rng.uniform(0.0, 10.0))
        t_done = max(rng.uniform(0.0, 10.0), rec.t0)
        fallback = rng.random() < 0.3
        phases = _decompose(rec, t_done, fallback)
        assert set(phases) == set(PHASES)
        for name, v in phases.items():
            assert v >= 0.0, (name, v)
        assert abs(sum(phases.values()) - (t_done - rec.t0)) < 1e-9
        if fallback:
            assert phases["device"] == 0.0


def test_stampless_event_is_all_unattributed():
    rec = _Rec("bfd", t0=1.0)
    phases = _decompose(rec, 3.0, False)
    assert phases["unattributed"] == 2.0
    assert sum(phases.values()) == 2.0


def test_unpipelined_hold_books_as_coalesce_not_queue():
    # No enqueue stamp: sched→marshal is the delay-FSM hold.
    rec = _Rec("lsa", t0=0.0)
    rec.sched, rec.marshal0, rec.marshal1, rec.t_end = 0.1, 0.5, 0.6, 0.7
    phases = _decompose(rec, 0.7, False)
    assert phases["coalesce_wait"] == pytest.approx(0.4)
    assert phases["queue_wait"] == 0.0
    assert phases["marshal"] == pytest.approx(0.1)


def test_verdict_partition_and_tie_break():
    zero = dict.fromkeys(PHASES, 0.0)
    assert _verdict(zero) == "host"  # all-tie breaks host-ward
    q = dict(zero, queue_wait=1.0)
    assert _verdict(q) == "queue"
    d = dict(zero, device=1.0, queue_wait=0.5)
    assert _verdict(d) == "device"
    h = dict(zero, rib=2.0, device=1.0)
    assert _verdict(h) == "host"


# -- chaos attribution: unit scale ---------------------------------------

def _close(eid):
    convergence.observe(convergence.PHASE_SPF, eids=(eid,))
    convergence.observe(convergence.PHASE_RIB, eids=(eid,))
    convergence.fib_commit(eids=(eid,))


def test_injected_dispatch_delay_books_to_device_phase():
    from holo_tpu.spf.backend import TpuSpfBackend
    from holo_tpu.spf.synth import grid_topology

    convergence.configure(256)
    cp = critpath.configure(check_every=0)
    topo = grid_topology(4, 4, seed=2)
    be = TpuSpfBackend()
    be.compute(topo)  # warm: compile outside any event

    def one(plan):
        eid = convergence.begin("lsa")
        with convergence.activation((eid,)):
            with faults.inject(plan):
                be.compute(topo)
            _close(eid)
        return cp.waterfalls()[-1]

    clean = one(faults.FaultPlan())
    slow = one(faults.FaultPlan(dispatch_delay={"spf.dispatch": 0.02}))
    assert slow["phases"]["device"] >= clean["phases"]["device"] + 0.015
    # Wrong-phase attribution is a failure: the delay must NOT have
    # landed in the host/queue phases.
    for ph in ("wake", "coalesce_wait", "queue_wait", "force_wait"):
        assert slow["phases"][ph] < 0.015
    for w in (clean, slow):
        assert abs(sum(w["phases"].values()) - w["wall"]) < 1e-6


def test_per_key_ordering_stall_books_to_queue_wait():
    from holo_tpu.pipeline.dispatch import DispatchPipeline

    convergence.configure(256)
    cp = critpath.configure(check_every=0)
    pipe = DispatchPipeline(depth=2, name="cp-stall")
    gate, queued = threading.Event(), threading.Event()
    try:
        e1 = convergence.begin("lsa")
        with convergence.activation((e1,)):
            t1 = pipe.submit(
                "k", "spf",
                # e1 stays in launch until e2 is queued behind it, so the
                # worker's next scan finds e2 with its key in flight.
                launch=lambda: queued.wait(5.0) and "h",
                finish=lambda h: gate.wait(5.0) and "v1",
            )
        e2 = convergence.begin("lsa")
        with convergence.activation((e2,)):
            t2 = pipe.submit("k", "spf", run=lambda: "v2")
        queued.set()
        # Wait for the worker to latch e2 stalled (the ledger's open
        # record shows it), then hold the gate: queue_wait >= 0.1 by
        # construction, whatever the scheduler does under xdist.
        deadline = time.monotonic() + 5.0
        while cp._recs[e2].stalls < 1 and time.monotonic() < deadline:
            time.sleep(0.005)
        time.sleep(0.12)
        gate.set()
        assert t1.result(5.0) == "v1"
        assert t2.result(5.0) == "v2"
        _close(e1)
        _close(e2)
    finally:
        gate.set()
        pipe.close()
    w2 = cp.waterfalls()[-1]
    assert w2["stalls"] >= 1
    assert w2["phases"]["queue_wait"] >= 0.1
    assert abs(sum(w2["phases"].values()) - w2["wall"]) < 1e-6


def test_force_wait_books_only_the_uncovered_seam_window():
    from holo_tpu.pipeline.dispatch import DispatchPipeline

    convergence.configure(256)
    cp = critpath.configure(check_every=0)
    # Pipelined force where the wait IS the dispatch executing: the
    # window is covered by the launch/finish stamps, so it books as
    # device — force_wait keeps only the uncovered residual (≈0).
    pipe = DispatchPipeline(depth=1, name="cp-force")
    gate = threading.Event()
    try:
        eid = convergence.begin("lsa")
        with convergence.activation((eid,)):
            t = pipe.submit(
                "kf", "spf", run=lambda: gate.wait(5.0) and "v"
            )
        threading.Timer(0.12, gate.set).start()
        assert t.result(5.0) == "v"  # blocks ≥0.1s at the seam
        _close(eid)
    finally:
        gate.set()
        pipe.close()
    w = cp.waterfalls()[-1]
    assert w["phases"]["device"] >= 0.1
    assert w["phases"]["force_wait"] < 0.05
    # A force window with NO covering dispatch stamps (the readiness
    # the caller waited on was produced elsewhere) books to force_wait.
    e2 = convergence.begin("lsa")
    cp.note_force((e2,), "b")
    time.sleep(0.06)
    cp.note_force((e2,), "e")
    _close(e2)
    w2 = cp.waterfalls()[-1]
    assert w2["phases"]["force_wait"] >= 0.05
    assert w2["verdict"] == "queue"


def test_scalar_fallback_relabels_to_fallback_phase():
    convergence.configure(256)
    cp = critpath.configure(check_every=0)
    eid = convergence.begin("lsa")
    with convergence.activation((eid,)):
        convergence.note_dispatch("spf.one", "fallback")
        time.sleep(0.01)  # the oracle's compute
        convergence.observe(convergence.PHASE_SPF, eids=(eid,))
        convergence.fib_commit(eids=(eid,))
    w = cp.waterfalls()[-1]
    assert w["fallback"] is True
    assert w["phases"]["fallback"] >= 0.008
    assert w["phases"]["device"] == 0.0
    assert w["verdict"] == "device"
    assert abs(sum(w["phases"].values()) - w["wall"]) < 1e-6


# -- chaos attribution: storm scale --------------------------------------

def test_storm_delay_inflates_device_phase_digest_identical():
    from holo_tpu.spf.backend import TpuSpfBackend
    from holo_tpu.spf.synth_storm import run_convergence_storm

    def run(plan):
        cp = critpath.configure(check_every=0)
        with faults.inject(plan):
            _rep, digest, _net = run_convergence_storm(
                n_routers=40, events=16, seed=5,
                spf_backend=TpuSpfBackend(),
            )
        q = cp.phase_quantiles()
        waterfalls = cp.waterfalls()
        return q, digest, waterfalls

    q0, d0, w0 = run(faults.FaultPlan())
    q1, d1, _w1 = run(
        faults.FaultPlan(dispatch_delay={"spf.dispatch": 0.02})
    )
    # Real sleeps are invisible to the virtual clock: same causal run.
    assert d0 == d1
    dev0 = q0.get("device", {"p50": 0.0})["p50"]
    assert q1["device"]["p50"] >= dev0 + 0.01
    # Gap-free at storm scale: every waterfall telescopes to its wall
    # and the residual stays near zero.
    assert w0
    for w in w0:
        assert abs(sum(w["phases"].values()) - w["wall"]) < 1e-6
    wall0 = q0.get("wall", {"p50": 0.0})["p50"]
    un0 = q0.get("unattributed", {"p50": 0.0})["p50"]
    assert wall0 > 0.0 and un0 < 0.01 * wall0


def test_sentinel_seeds_critpath_phase_keys():
    obs = observatory.configure(check_every=0)
    convergence.configure(256)
    cp = critpath.configure(check_every=0)
    eid = convergence.begin("lsa")
    with convergence.activation((eid,)):
        _close(eid)
    before = obs.sentinel()["seeded"]
    cp.checkpoint()
    assert obs.sentinel()["seeded"] > before


# -- surfaces ------------------------------------------------------------

def test_explain_critical_path_byte_identical(capsys):
    from holo_tpu.tools.cli import main as cli_main

    argv = [
        "explain", "--critical-path", "--storm", "40",
        "--events", "16", "--seed", "5",
    ]
    assert cli_main(argv) == 0
    out1 = capsys.readouterr().out
    assert cli_main(argv) == 0
    out2 = capsys.readouterr().out
    assert out1 == out2
    assert "critical path —" in out1
    assert "phase ledger (cut order):" in out1
    # The CLI disarmed the ledger on exit.
    assert critpath.active() is None


def test_explain_critical_path_json_empty_workload(capsys):
    import json as _json

    from holo_tpu.tools.cli import main as cli_main

    assert cli_main(
        ["explain", "--critical-path", "--k", "6", "--batch", "4",
         "--reps", "4", "--json"]
    ) == 0
    doc = _json.loads(capsys.readouterr().out)
    cp = doc["critical_path"]
    assert cp["completed"] == 0  # no convergence events in the mix
    assert cp["phases"] == [] and cp["events"] == []


def test_provider_leaf_carries_critical_path():
    from holo_tpu.telemetry.provider import TelemetryStateProvider

    convergence.configure(256)
    critpath.configure(check_every=0)
    eid = convergence.begin("lsa")
    with convergence.activation((eid,)):
        _close(eid)
    st = TelemetryStateProvider().get_state()["holo-telemetry"]
    leaf = st["critical-path"]
    assert leaf["completed"] >= 1
    assert leaf["verdicts"]["host"] >= 1
    assert "phases" in leaf


def test_device_residency_ledger_sums_planes():
    from holo_tpu.spf.backend import TpuSpfBackend
    from holo_tpu.spf.synth import grid_topology
    from holo_tpu.telemetry import residency

    be = TpuSpfBackend()
    be.compute(grid_topology(4, 4, seed=2))
    snap = residency.snapshot()
    assert snap["total-bytes"] > 0
    assert snap["planes"]["spf-graph"]["entries"] >= 1
    assert snap["planes"]["spf-graph"]["bytes"] > 0
    # The gauge family samples the same sums at scrape time.
    vals = telemetry.snapshot(prefix="holo_device_resident_bytes")
    assert any(v > 0 for v in vals.values())


def test_wait_seconds_carries_event_exemplar():
    from holo_tpu.pipeline.dispatch import DispatchPipeline
    from holo_tpu.telemetry.provider import _exemplar_leaf

    convergence.configure(256)
    pipe = DispatchPipeline(depth=1, name="cp-exemplar")
    gate = threading.Event()
    try:
        eid = convergence.begin("lsa")
        with convergence.activation((eid,)):
            t = pipe.submit(
                "ke", "spf", run=lambda: gate.wait(5.0) and "v"
            )
        threading.Timer(0.05, gate.set).start()
        assert t.result(5.0) == "v"  # blocked: the wait observes
        convergence.fib_commit(eids=(eid,))
    finally:
        gate.set()
        pipe.close()
    fams = {f.name: f for f in telemetry.registry().families()}
    hist = fams["holo_pipeline_wait_seconds"]
    leaves = [_exemplar_leaf(child) for _key, child in hist.children()]
    joined = ";".join(leaves)
    assert "event_id=" in joined or "span_id=" in joined


# -- disarmed contract ---------------------------------------------------

def test_disarmed_seams_are_one_global_check(monkeypatch):
    assert critpath.active() is None

    def boom():
        raise AssertionError("disarmed seam read the clock")

    monkeypatch.setattr(profiling, "clock", boom)
    critpath.note_enqueue((1, 2))
    critpath.note_launch((1,), "b")
    critpath.note_finish((1,), "e")
    critpath.note_force((1,), "b")
    critpath.note_stall((1,))
    # The profiling phase hook and convergence hook are uninstalled.
    assert profiling._PHASE_HOOK is None
    assert convergence._CP_HOOK is None
    with profiling.stage("x.y", "marshal"):
        pass  # no hook dispatch, no clock read via the hook


def test_hooks_install_and_uninstall_with_configure():
    cp = critpath.configure(check_every=0)
    assert profiling._PHASE_HOOK is not None
    assert convergence._CP_HOOK is cp
    critpath.configure(0)
    assert profiling._PHASE_HOOK is None
    assert convergence._CP_HOOK is None


def test_capacity_bound_evicts_oldest_open_record():
    cp = CritPathLedger(capacity=4, check_every=0)
    for eid in range(8):
        cp.ev_begin(eid, "lsa")
    assert len(cp._recs) == 4
    assert set(cp._recs) == {4, 5, 6, 7}
    assert cp.stats()["dropped"] == 4
