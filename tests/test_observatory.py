"""Dispatch observatory (ISSUE 12): sketch core bounds, roofline
attribution, regression sentinel, explain CLI.

Sketch contract tests pin the DDSketch guarantees the sentinel relies
on (relative-error quantiles, merge associativity, byte-identical
serialization); the integration tests drive the REAL dispatch path —
``TpuSpfBackend`` / ``FrrEngine`` under the armed observer — including
the fault-injected dispatch delay the sentinel must flag within one
storm, and the structural "disarmed path is one global check" gate.
"""

from __future__ import annotations

import json
import math
import random

import numpy as np
import pytest

from holo_tpu import telemetry
from holo_tpu.pipeline.tuner import (
    EngineTuner,
    reset_engine_tuner,
)
from holo_tpu.resilience import faults
from holo_tpu.telemetry import flight, observatory, profiling
from holo_tpu.telemetry.observatory import (
    DDSketch,
    DeterministicTimer,
    Observatory,
    RooflinePeaks,
)


@pytest.fixture(autouse=True)
def _reset_observatory_state():
    yield
    observatory.configure(enabled=False)
    profiling.set_device_profiling(False)
    profiling.set_stage_timer(None)
    reset_engine_tuner()
    flight.configure(entries=0)


# -- sketch core ---------------------------------------------------------


def _true_quantile(vals, q):
    s = sorted(vals)
    return s[round(q * (len(s) - 1))]


def test_sketch_quantile_relative_error_bounds():
    rng = random.Random(7)
    for dist in ("uniform", "lognormal"):
        sk = DDSketch(alpha=0.01)
        vals = []
        for _ in range(5000):
            v = (
                rng.uniform(1e-4, 10.0)
                if dist == "uniform"
                else math.exp(rng.gauss(-5.0, 2.0))
            )
            vals.append(v)
            sk.observe(v)
        for q in (0.1, 0.5, 0.9, 0.99):
            true = _true_quantile(vals, q)
            est = sk.quantile(q)
            # alpha relative error on the bucket + one rank of
            # discretization slack.
            assert abs(est - true) <= 2 * sk.alpha * true + 1e-12, (
                dist, q, est, true,
            )


def test_sketch_merge_matches_combined_and_serializes_identically():
    rng = random.Random(3)
    a_vals = [rng.uniform(1e-3, 1.0) for _ in range(400)]
    b_vals = [rng.uniform(1e-2, 5.0) for _ in range(300)]
    a, b, both = DDSketch(), DDSketch(), DDSketch()
    for v in a_vals:
        a.observe(v)
        both.observe(v)
    for v in b_vals:
        b.observe(v)
        both.observe(v)
    a.merge(b)
    assert a.count == both.count
    assert a.bins == both.bins
    # Serialization is canonical up to float-sum association: compare
    # everything except the order-dependent running sum.
    da, db = a.to_doc(), both.to_doc()
    assert abs(da.pop("sum") - db.pop("sum")) < 1e-9
    assert da == db


def test_sketch_merge_associative():
    rng = random.Random(11)
    chunks = [
        [rng.uniform(1e-4, 2.0) for _ in range(150)] for _ in range(3)
    ]

    def sk(vals):
        s = DDSketch()
        for v in vals:
            s.observe(v)
        return s

    left = sk(chunks[0]).merge(sk(chunks[1])).merge(sk(chunks[2]))
    right = sk(chunks[0]).merge(sk(chunks[1]).merge(sk(chunks[2])))
    assert left.bins == right.bins
    assert left.count == right.count
    assert left.quantile(0.5) == right.quantile(0.5)


def test_sketch_bounded_bins_collapse_preserves_count_and_tail():
    sk = DDSketch(alpha=0.01, max_bins=64)
    rng = random.Random(5)
    vals = [10.0 ** rng.uniform(-9, 3) for _ in range(4000)]
    for v in vals:
        sk.observe(v)
    assert len(sk.bins) <= 64
    assert sk.count == len(vals)
    # Tail accuracy survives the low-bucket collapse.
    true99 = _true_quantile(vals, 0.99)
    assert abs(sk.quantile(0.99) - true99) <= 2 * sk.alpha * true99


def test_sketch_doc_roundtrip_and_alpha_mismatch():
    sk = DDSketch(alpha=0.02)
    for v in (0.001, 0.01, 0.1, 0.1, 1.0):
        sk.observe(v)
    back = DDSketch.from_doc(json.loads(sk.serialize()))
    assert back.serialize() == sk.serialize()
    assert back.quantile(0.5) == sk.quantile(0.5)
    with pytest.raises(ValueError):
        sk.merge(DDSketch(alpha=0.01))


def test_sketch_zero_and_negative_values():
    sk = DDSketch()
    sk.observe(0.0)
    sk.observe(-1.0)  # clock step backwards clamps to 0
    sk.observe(1.0)
    assert sk.zero == 2
    assert sk.quantile(0.0) == 0.0
    assert sk.count == 3


# -- observe path / keying ----------------------------------------------


def _spf_workload(obs_reps=4, topo_seed=1):
    from holo_tpu.spf.backend import TpuSpfBackend
    from holo_tpu.spf.synth import grid_topology

    topo = grid_topology(5, 5, seed=topo_seed)
    be = TpuSpfBackend()
    for _ in range(obs_reps):
        be.compute(topo)
    return topo, be


def test_observe_keys_carry_engine_bucket_kind():
    obs = observatory.configure(check_every=0)
    profiling.set_device_profiling(True)
    _spf_workload()
    keys = list(obs._sketches)
    sites = {k[0] for k in keys}
    assert "spf.one" in sites
    one = [k for k in keys if k[0] == "spf.one" and k[1] == "device"]
    assert one, keys
    site, stage, engine, bucket, kind = one[0]
    assert engine == "seq" and kind == "one"
    assert isinstance(bucket, tuple) and bucket[0] >= 25  # pow2(V) >= V


def test_observe_requires_no_device_profiling():
    # The observatory stays always-on even with the histogram/exemplar
    # machinery off: stage() times for the observer alone.
    obs = observatory.configure(check_every=0)
    assert not profiling.device_profiling()
    _spf_workload()
    assert any(k[0] == "spf.one" for k in obs._sketches)
    # ... and record_cost captured the roofline numerators too.
    assert obs._costs


def test_observe_skips_per_device_skew_rows():
    obs = observatory.configure(check_every=0)
    obs._observe("spf.one", "device", "3", 0.5)
    assert not obs._sketches
    obs._observe("spf.one", "device", "-", 0.5)
    assert len(obs._sketches) == 1


def test_disarmed_path_is_one_global_check():
    # Disarmed + unprofiled, stage() must return before its first
    # timer read: the disarmed cost is one global check per observe.
    assert observatory.active() is None
    assert not profiling.observing()

    def boom():
        raise AssertionError("stage timed on the disarmed path")

    profiling.set_stage_timer(boom)
    try:
        with profiling.stage("x.y", "marshal"):
            pass
    finally:
        profiling.set_stage_timer(None)
    # ... and the dispatch-context wrapper is the shared null context
    # (no per-dispatch allocation while disarmed).
    assert (
        profiling.dispatch_context(kind="one")
        is profiling.dispatch_context(kind="whatif")
    )


def test_frr_dispatch_feeds_frr_keys_and_roofline():
    from holo_tpu.frr.manager import FrrEngine
    from holo_tpu.spf.synth import grid_topology

    obs = observatory.configure(check_every=0)
    profiling.set_device_profiling(True)
    FrrEngine("tpu").compute(grid_topology(4, 4, seed=2))
    assert any(
        k[0] == "frr.batch" and k[2] == "frr" for k in obs._sketches
    )
    rows = [r for r in obs.roofline() if r["site"] == "frr.batch"]
    assert rows and rows[0]["engine"] == "frr"


# -- determinism ---------------------------------------------------------


def _deterministic_run():
    obs = observatory.configure(check_every=4)
    profiling.set_stage_timer(DeterministicTimer())
    profiling.set_device_profiling(True)
    _spf_workload(obs_reps=6)
    blob = obs.serialize()
    report = json.dumps(obs.report(), sort_keys=True)
    profiling.set_stage_timer(None)
    profiling.set_device_profiling(False)
    observatory.configure(enabled=False)
    return blob, report


def test_byte_identical_serialization_across_same_seed_runs():
    b1, r1 = _deterministic_run()
    b2, r2 = _deterministic_run()
    assert b1 == b2
    assert r1 == r2
    assert json.loads(r1)["timing"] == "deterministic"


# -- roofline attribution ------------------------------------------------


def test_roofline_verdicts_from_ridge_point():
    obs = Observatory()
    # Gather-like kernel: far more bytes than flops -> memory-bound.
    obs.note_cost("spf.one", "one", "seq", ("b",), {
        "flops": 1e6, "bytes": 1e7,
    })
    # Contraction-like kernel: AI above the CPU ridge (5 flop/B).
    obs.note_cost("spf.one", "one", "tropical", ("b",), {
        "flops": 1e9, "bytes": 1e7,
    })
    rows = {r["engine"]: r for r in obs.roofline()}
    assert rows["seq"]["verdict"] == "memory-bound"
    assert rows["tropical"]["verdict"] == "compute-bound"
    # No device sketch yet: verdict present, achieved rates absent.
    assert "achieved_flops_per_sec" not in rows["seq"]


def test_roofline_achieved_rates_join_device_sketch():
    obs = Observatory(check_every=0)
    key = ("spf.one", "device", "seq", ("b",), "one")
    for _ in range(10):
        obs._sketches.setdefault(key, DDSketch()).observe(0.01)
    obs.note_cost("spf.one", "one", "seq", ("b",), {
        "flops": 1e6, "bytes": 1e7,
    })
    row = obs.roofline()[0]
    p50 = row["device_p50_s"]
    assert row["achieved_flops_per_sec"] == pytest.approx(1e6 / p50)
    assert row["achieved_bytes_per_sec"] == pytest.approx(1e7 / p50)
    # Memory-bound bucket: the attainable ceiling is AI * peak_bytes.
    attainable = row["ai_flops_per_byte"] * obs.peaks.bytes_per_sec
    assert row["roofline_fraction"] == pytest.approx(
        (1e6 / p50) / attainable, rel=1e-6
    )


def test_roofline_peaks_config_moves_the_ridge():
    # A machine with huge bandwidth relative to flops classifies the
    # same kernel compute-bound.
    obs = Observatory(peaks={"flops": 1e9, "bytes": 1e12, "name": "hbm"})
    obs.note_cost("s", "k", "e", ("b",), {"flops": 1e6, "bytes": 1e7})
    assert obs.roofline()[0]["verdict"] == "compute-bound"
    assert obs.peaks.source == "hbm"
    # The default follows the attached device: the CPU guess here.
    assert observatory.device_peaks() is observatory.CPU_GUESS
    assert Observatory().peaks_source == "cpu-default"


def test_default_peaks_follow_the_device_kind(monkeypatch):
    """ISSUE 21: a v5e gets its published peaks with the source named;
    any other accelerator gets NO peaks — verdict ``unknown`` and no
    roofline fraction, never a CPU guess under a device's name."""
    import jax

    class _Dev:
        def __init__(self, platform, kind):
            self.platform, self.device_kind = platform, kind

    monkeypatch.setattr(jax, "devices", lambda: [_Dev("tpu", "TPU v5 lite")])
    v5e = observatory.device_peaks()
    assert (v5e.flops_per_sec, v5e.bytes_per_sec) == (1.97e14, 8.19e11)
    assert "v5e" in v5e.source and "documentation" in v5e.source
    monkeypatch.setattr(jax, "devices", lambda: [_Dev("tpu", "TPU v9")])
    assert observatory.device_peaks() is None
    obs = Observatory(check_every=0)
    obs.note_cost("s", "k", "e", ("b",), {"flops": 1e6, "bytes": 1e7})
    for _ in range(4):
        obs._sketches.setdefault(
            ("s", "device", "e", ("b",), "k"), DDSketch()
        ).observe(0.01)
    row = obs.roofline()[0]
    assert row["verdict"] == "unknown" and row["peaks"] == "unknown-device"
    assert "achieved_bytes_per_sec" in row
    assert "roofline_fraction" not in row
    assert obs.report()["peaks"]["ridge_flops_per_byte"] is None
    # [telemetry] roofline-peaks still overrides on any device.
    assert Observatory(
        peaks={"flops": 1e9, "bytes": 1e12, "name": "hbm"}
    ).peaks.source == "hbm"


def test_real_gather_dispatch_classified_memory_bound():
    obs = observatory.configure(check_every=0)
    profiling.set_device_profiling(True)
    _spf_workload()
    rows = [
        r
        for r in obs.roofline()
        if r["site"] == "spf.one" and r["engine"] == "seq"
    ]
    assert rows and rows[0]["verdict"] == "memory-bound"
    assert rows[0]["ai_flops_per_byte"] < obs.peaks.ridge


def test_cost_centers_ranked_by_total():
    obs = Observatory(check_every=0)
    k1 = ("a", "device", "e", "-", "k")
    k2 = ("b", "device", "e", "-", "k")
    for _ in range(3):
        obs._sketches.setdefault(k1, DDSketch()).observe(0.001)
    obs._sketches.setdefault(k2, DDSketch()).observe(1.0)
    rows = obs.cost_centers()
    assert rows[0]["site"] == "b" and rows[1]["site"] == "a"
    assert obs.cost_centers(top=1) == rows[:1]


# -- regression sentinel -------------------------------------------------


def _feed(obs, key, value, n):
    for _ in range(n):
        obs._observe(key[0], key[1], "-", value)


def test_sentinel_seeds_then_stays_silent(tmp_path):
    led = tmp_path / "ledger.json"
    obs = Observatory(check_every=4, ledger_path=led)
    _feed(obs, ("spf.one", "device"), 0.010, 16)
    assert obs.sentinel()["flags"] == 0
    assert obs.sentinel()["seeded"] >= 1
    # Persistence happens at checkpoint boundaries, never as a disk
    # write on the observing (dispatch) thread.
    assert not led.exists()
    obs.checkpoint()
    doc = json.loads(led.read_text())
    assert any("spf.one/device" in k for k in doc)
    # Fresh instrument over the persisted ledger, same latencies:
    # silent (the acceptance's "clean ledger-seeded run").
    obs2 = Observatory(check_every=4, ledger_path=led)
    _feed(obs2, ("spf.one", "device"), 0.010, 16)
    assert obs2.sentinel()["flags"] == 0
    assert obs2.sentinel()["seeded"] == 0


def test_sentinel_flags_drift_and_latches_once(tmp_path):
    led = tmp_path / "ledger.json"
    obs = Observatory(check_every=4, ledger_path=led)
    _feed(obs, ("spf.one", "device"), 0.010, 8)   # seed ~10ms
    _feed(obs, ("spf.one", "device"), 0.100, 32)  # 10x regression
    s = obs.sentinel()
    assert s["flags"] >= 1
    assert any("spf.one/device" in r for r in s["regressed"])
    # The latch fires on the TRANSITION, not per check.
    assert s["flags"] <= 2  # p50 + p99 at most once each


def test_sentinel_ratchets_improvements(tmp_path):
    led = tmp_path / "ledger.json"
    obs = Observatory(check_every=4, ledger_path=led)
    _feed(obs, ("spf.one", "device"), 0.100, 8)
    obs.checkpoint()
    seeded = json.loads(led.read_text())
    key, ent = next(iter(seeded.items()))
    obs2 = Observatory(check_every=4, ledger_path=led)
    _feed(obs2, ("spf.one", "device"), 0.050, 16)
    assert obs2.sentinel()["flags"] == 0
    obs2.checkpoint()
    ratcheted = json.loads(led.read_text())
    assert ratcheted[key]["p50"] < ent["p50"]
    assert obs2.sentinel()["ratcheted"] >= 1


def test_sentinel_corrupt_ledger_reseeds(tmp_path):
    led = tmp_path / "ledger.json"
    led.write_text("{not json")
    obs = Observatory(check_every=4, ledger_path=led)
    _feed(obs, ("spf.one", "device"), 0.010, 8)
    assert obs.sentinel()["seeded"] >= 1
    obs.checkpoint()
    assert json.loads(led.read_text())  # rewritten clean


def test_sentinel_flags_injected_dispatch_delay():
    """The acceptance scenario at unit scale: a clean seeded baseline,
    then a fault-injected dispatch delay (resilience/faults.py) — the
    sentinel flags the slowed bucket, emits the flight-ring event and
    the counter, while the dispatch itself keeps SUCCEEDING (warn-only:
    no breaker, no fallback)."""
    flight.configure(entries=512)
    obs = observatory.configure(check_every=4)
    profiling.set_device_profiling(True)
    topo, be = _spf_workload(obs_reps=12)
    assert obs.sentinel()["flags"] == 0
    before = telemetry.snapshot(prefix="holo_observatory_regressions")
    with faults.inject(
        faults.FaultPlan(dispatch_delay={"spf.dispatch": 0.02})
    ) as inj:
        for _ in range(12):
            res = be.compute(topo)
            assert res.dist is not None  # still succeeding
        assert inj.injected.get("delay:spf.dispatch", 0) >= 12
    s = obs.sentinel()
    assert s["flags"] >= 1
    after = telemetry.snapshot(prefix="holo_observatory_regressions")
    assert sum(after.values()) > sum(before.values())
    kinds = {e[1] for e in flight.recorder().snapshot_ring()
             if e[0] == "event"}
    assert "observatory-regression" in kinds
    assert be.breaker.snapshot()["state"] == "closed"


def test_sentinel_flags_slowed_bucket_within_one_storm(tmp_path):
    """Storm-scale acceptance: seed the ledger from a clean seeded
    storm, then re-run the same storm with a dispatch delay injected —
    the sentinel must flag within that one storm, and the clean run
    must have stayed silent."""
    from holo_tpu.spf.backend import TpuSpfBackend
    from holo_tpu.spf.synth_storm import run_convergence_storm

    led = tmp_path / "storm-ledger.json"
    obs = observatory.configure(check_every=4, ledger_path=led)
    profiling.set_device_profiling(True)
    run_convergence_storm(
        n_routers=40, events=16, seed=5, spf_backend=TpuSpfBackend()
    )
    assert obs.checkpoint()["flags"] == 0  # clean, ledger-seeded
    obs2 = observatory.configure(check_every=4, ledger_path=led)
    with faults.inject(
        faults.FaultPlan(
            drop_prob=0.10, dispatch_delay={"spf.dispatch": 0.03}
        )
    ):
        run_convergence_storm(
            n_routers=40, events=16, seed=5, spf_backend=TpuSpfBackend()
        )
    assert obs2.sentinel()["flags"] >= 1
    assert any("spf.one" in r for r in obs2.sentinel()["regressed"])


def test_delaypoint_disarmed_is_noop():
    faults.delaypoint("spf.dispatch")  # no injector armed: no-op
    with faults.inject(faults.FaultPlan()) as inj:
        faults.delaypoint("spf.dispatch")  # no delay planned: no-op
    assert not inj.injected


# -- surfaces: provider leaf, CLI, tuner ledger --------------------------


def test_provider_leaf_carries_observatory():
    from holo_tpu.telemetry.provider import TelemetryStateProvider

    obs = observatory.configure(check_every=0)
    obs._observe("spf.one", "device", "-", 0.01)
    state = TelemetryStateProvider().get_state()["holo-telemetry"]
    assert state["observatory"]["sketches"] == 1
    assert state["observatory"]["sentinel"]["flags"] == 0


def test_explain_cli_json_byte_identical(capsys):
    from holo_tpu.tools.cli import main as cli_main

    argv = ["explain", "--k", "6", "--batch", "4", "--reps", "4",
            "--json"]
    assert cli_main(argv) == 0
    out1 = capsys.readouterr().out
    assert cli_main(argv) == 0
    out2 = capsys.readouterr().out
    assert out1 == out2
    doc = json.loads(out1)
    assert doc["timing"] == "deterministic"
    assert doc["cost_centers"] and doc["roofline"]
    for row in doc["roofline"]:
        assert row["verdict"] in ("memory-bound", "compute-bound")
    # Gather engines at this scale: memory-bound, with quantiles.
    gather = [r for r in doc["roofline"] if r["site"] == "spf.one"]
    assert gather and all(
        r["verdict"] == "memory-bound" for r in gather
    )
    assert doc["tuner"], "win/loss ledger rides the report"
    # The CLI disarmed everything on exit.
    assert observatory.active() is None
    assert not profiling.device_profiling()
    assert not profiling.stage_timer_overridden()


def test_explain_cli_text_render(capsys):
    from holo_tpu.tools.cli import main as cli_main

    assert cli_main(
        ["explain", "--k", "6", "--batch", "4", "--reps", "4",
         "--top", "5"]
    ) == 0
    out = capsys.readouterr().out
    assert "top 5 cost centers" in out
    assert "memory-bound" in out
    assert "engine tuner win/loss ledger" in out
    assert "sentinel:" in out
    assert "cpu-default" in out  # the peak label names the platform


def test_shared_table_renderer_and_top(capsys):
    from holo_tpu.tools.cli import _print_table, _snapshot_cost_rows

    rows = _snapshot_cost_rows(
        {
            "fast": 1.0,
            "hist": {"count": 4, "sum": 9.5},
            "slow": 3.0,
        }
    )
    assert [r[0] for r in rows] == ["hist", "slow", "fast"]
    _print_table(("name", "count", "total"), rows, top=2)
    out = capsys.readouterr().out.splitlines()
    assert len(out) == 3  # header + top 2
    assert out[1].startswith("  hist")


def test_tuner_ledger_explains_win_basis():
    t = EngineTuner(engines=("packed", "fused"))
    bucket = (64, 128, 1, None, 1)
    t.cost_prior("one", bucket, "packed", {"flops": 2e6, "bytes": 1e6})
    t.cost_prior("one", bucket, "fused", {"flops": 1e6, "bytes": 5e6})
    for _ in range(3):
        t.observe("one", bucket, "packed", 0.001)
        t.observe("one", bucket, "fused", 0.002)
    rows = t.ledger()
    assert rows[0]["winner"] == "packed"
    assert rows[0]["basis"] == "packed beat fused on bytes"
    assert rows[0]["engines"]["fused"]["median_ms"] == 2.0


def test_tuner_ledger_mp_bucket_reports_measured_engine():
    t = EngineTuner()
    bucket = (64, 128, 1, None, 2)
    t.observe("one", bucket, "mp", 0.001)
    row = t.ledger()[0]
    assert row["winner"] == "mp"
    assert row["basis"] == "only measured engine"


def test_observatory_stats_leaf_shape():
    obs = observatory.configure(check_every=0)
    obs._observe("spf.one", "device", "-", 0.01)
    s = obs.stats()
    assert s["sketches"] == 1 and s["observations"] == 1
    assert s["peaks-source"] == "cpu-default"
    snap = telemetry.snapshot(prefix="holo_observatory_sketches")
    assert snap["holo_observatory_sketches"] == 1.0
