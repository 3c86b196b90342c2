"""The five readings of what a counted event waits for (ISSUE 38), each
with its ``multiarea_`` twin: the metric files and their
``BENCHMARK.json`` entries, the one new reader on hand-made records
(with a program that lacks the cut, and one whose account is off), the
two readers that were there on hand-made snapshots, and the storm
rehearsals reading them: traced all five, untraced the two cuts and no
share."""

import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmark import run
from benchmark.readers import counter_ratio, critpath_hold, histogram_per
from benchmark.window import Window

REPO = Path(__file__).resolve().parents[2]
TOP = json.loads((REPO / "BENCHMARK.json").read_text())
PER_LAYER = {m["name"]: m for m in TOP["per_layer"]}
POPULATION = ["lsa", "ifconfig"]
SELF = "holo_profile_self_seconds_total"
STAGE = "holo_profile_stage_seconds"
RUNS = "site=ospf.spf,stage=run,device=-"
#: original -> (layer, source, reader, args)
METRICS = {
    "storm_hold_wait_ms": (
        "protocol instance", "program_span", "critpath_hold",
        {"part": "wait", "triggers": POPULATION, "stat": "p50",
         "scale": 1000.0},
    ),
    "storm_prerun_ms": (
        "protocol instance", "program_span", "critpath_hold",
        {"part": "prerun", "triggers": POPULATION, "stat": "p50",
         "scale": 1000.0},
    ),
    "storm_hold_routing_share": (
        "readback + routes", "program_span", "critpath_hold",
        {"by": ["loop.routing"], "of": "wait", "triggers": POPULATION},
    ),
    "storm_gc_pause_ms": (
        "protocol instance", "program_counter", "histogram_per",
        {"family": "holo_runtime_gc_pause_seconds",
         "per": {"family": STAGE, "label": RUNS}, "scale": 1000.0},
    ),
    "storm_host_unspanned_share": (
        "protocol instance", "program_counter", "counter_ratio",
        {"family": SELF, "label": "span=-}", "of": {"family": SELF}},
    ),
}
TWINS = {
    "multiarea_" + name.removeprefix("storm_"): name for name in METRICS
}


@pytest.mark.parametrize("name", sorted(METRICS))
def test_metric_file_reads_its_own_span_or_counter(name):
    layer, source, reader, args = METRICS[name]
    spec = run.layer_spec(name)
    assert spec["reader"] == reader and spec["args"] == args
    assert (spec["layer"], spec["source"]) == (layer, source)
    assert spec["moves"] == "trigger_fib_p50_ms" and spec["better"] == "lower"
    assert spec["unit"] == ("ms" if name.endswith("_ms") else "%")
    assert PER_LAYER[name] == {
        "name": name, "unit": spec["unit"], "better": "lower",
        "source": source, "layer": layer, "moves": "trigger_fib_p50_ms",
        # the two OSPFv2 storm cells; a later cell is appended
        "workloads": ["backbone10k-flapstorm", "isp-zoo-storm",
                      *PER_LAYER[name]["workloads"][2:]],
    }


@pytest.mark.parametrize("twin", sorted(TWINS))
def test_the_multiarea_storm_reads_a_twin(twin):
    held = run.load_json("layer_metrics", twin)
    assert held == {
        "name": twin, "twin_of": TWINS[twin],
        "moves": "multiarea_trigger_fib_p50_ms",
    }
    first = PER_LAYER[TWINS[twin]]
    assert PER_LAYER[twin] == {
        **first, "name": twin, "moves": "multiarea_trigger_fib_p50_ms",
        "workloads": ["v3-multiarea-storm",
                      *PER_LAYER[twin]["workloads"][1:]],
    }


def test_the_ten_entries_follow_what_was_there_in_pairs():
    names = [m["name"] for m in TOP["per_layer"]]
    first = names.index("storm_hold_wait_ms")
    assert names[first:first + 10] == [
        name for original in METRICS
        for name in (original, "multiarea_" + original.removeprefix("storm_"))
    ]
    assert "multiarea_derive_kept_share" in names[:first]  # PR 37's, before


# -- critpath_hold on hand-made records ----------------------------------------


def _record(trigger, wait, prerun, by=None) -> dict:
    return {
        "trigger": trigger, "wall": wait + prerun + 0.01,
        "phases": {"coalesce_wait": wait + prerun},
        "hold": {"wait": wait, "prerun": prerun, "by": by or {}},
    }


_ARMED = [
    _record("lsa", 0.010, 0.004, {"loop.routing": 0.004, "loop.dut": 0.006}),
    _record("ifconfig", 0.030, 0.008, {"loop.routing": 0.006, "-": 0.024}),
    _record("lsa", 0.020, 0.006, {"runtime.gc": 0.020}),
    # a local repair: outside the population, whatever it holds
    _record("bfd", 9.0, 9.0, {"loop.routing": 9.0}),
]
_DISARMED = [_record(r["trigger"], r["hold"]["wait"], r["hold"]["prerun"])
             for r in _ARMED]
_PARENT = [{k: v for k, v in r.items() if k != "hold"} for r in _ARMED]
WAIT, PRERUN, SHARE = (
    METRICS[name][3] for name in
    ("storm_hold_wait_ms", "storm_prerun_ms", "storm_hold_routing_share")
)


@pytest.mark.parametrize("args, records, want", [
    (WAIT, _ARMED, 20.0),
    (PRERUN, _ARMED, 6.0),
    (SHARE, _ARMED, 100.0 * 0.010 / 0.060),
    # the account off (an untraced run): the cuts, and no share
    (WAIT, _DISARMED, 20.0),
    (PRERUN, _DISARMED, 6.0),
    (SHARE, _DISARMED, None),
    # a share over the records that hold an account only
    (SHARE, _ARMED[:1] + _DISARMED[1:], 40.0),
    # a span nobody waited in is a measured zero
    ({**SHARE, "by": ["loop.absent"]}, _ARMED, 0.0),
    ({**SHARE, "by": ["loop.routing", "runtime.gc"]}, _ARMED, 50.0),
    # a program without the cut (the parent commit): nothing to read
    (WAIT, _PARENT, None),
    (PRERUN, _PARENT, None),
    (SHARE, _PARENT, None),
    # no counted event, no record, no ledger at all
    (WAIT, _ARMED[3:], None),
    (SHARE, _ARMED[3:], None),
    (WAIT, [], None),
    (SHARE, None, None),
    # nothing waited: no share of nothing
    (SHARE, [_record("lsa", 0.0, 0.004, {"-": 0.0})], None),
], ids=[
    "wait-p50", "prerun-p50", "routing-share", "disarmed-wait",
    "disarmed-prerun", "disarmed-no-share", "share-of-the-accounted",
    "absent-span-is-zero", "two-spans", "parent-wait", "parent-prerun",
    "parent-share", "other-triggers-only", "other-triggers-share",
    "no-records", "no-ledger", "zero-wait",
])
def test_critpath_hold_gives_the_p50_the_share_or_nothing(args, records, want):
    ctx = SimpleNamespace(run={"waterfalls": records})
    got = critpath_hold.read(args, ctx)
    assert got == (want if want is None else pytest.approx(want))


def test_critpath_hold_reads_a_run_without_the_key():
    assert critpath_hold.read(WAIT, SimpleNamespace(run={})) is None


# -- the two readers that were there, on the new families ----------------------


def _window(open_snap: dict, close_snap: dict) -> SimpleNamespace:
    window = Window(1.0, None, 0.3)
    window.snap = {"open": open_snap, "close": close_snap}
    return SimpleNamespace(window=window)


def _span(label: str) -> str:
    return f"{SELF}{{span={label}}}"


def _pause(generation: int) -> str:
    return f"holo_runtime_gc_pause_seconds{{generation={generation}}}"


_RUN = f"{STAGE}{{{RUNS}}}"


@pytest.mark.parametrize("opened, closed, want", [
    # 2 s of 50 under no span; a span whose name holds "span=-" does
    # not count as unspanned
    ({_span("-"): 1.0, _span("loop.routing"): 10.0},
     {_span("-"): 3.0, _span("loop.routing"): 40.0,
      _span("loop.x-span=-y"): 18.0}, 4.0),
    ({}, {_span("loop.routing"): 5.0}, 0.0),
    # a program without the account (the parent commit), or disarmed
    ({}, {}, None),
    ({_RUN: {"count": 1, "sum": 0.1}}, {_RUN: {"count": 9, "sum": 0.9}}, None),
], ids=["share", "all-spanned", "no-family", "other-family-only"])
def test_unspanned_share_is_the_dash_child_of_the_whole_family(
    opened, closed, want
):
    args = METRICS["storm_host_unspanned_share"][3]
    assert counter_ratio.read(args, _window(opened, closed)) == want


@pytest.mark.parametrize("opened, closed, want", [
    # 0.2 + 0.4 s of pauses, every generation, over 20 runs, in ms
    ({_pause(0): {"count": 5, "sum": 0.1}, _RUN: {"count": 10, "sum": 1.0}},
     {_pause(0): {"count": 105, "sum": 0.3}, _pause(2): {"count": 3, "sum": 0.4},
      _RUN: {"count": 30, "sum": 3.0}}, 30.0),
    # no run in the window; no pause observed; a program without either
    ({_pause(0): {"count": 5, "sum": 0.1}},
     {_pause(0): {"count": 9, "sum": 0.2}}, None),
    ({_RUN: {"count": 10, "sum": 1.0}}, {_RUN: {"count": 30, "sum": 3.0}}, None),
    ({}, {}, None),
], ids=["per-run", "no-run", "no-pause", "no-family"])
def test_gc_pause_is_the_pauses_seconds_per_spf_run(opened, closed, want):
    args = METRICS["storm_gc_pause_ms"][3]
    got = histogram_per.read(args, _window(opened, closed))
    assert got == (want if want is None else pytest.approx(want))


# -- the rehearsals ------------------------------------------------------------


@pytest.mark.parametrize("workload, names", [
    ("tiny-storm", sorted(METRICS)), ("tiny-areastorm", sorted(TWINS)),
])
def test_traced_rehearsal_reads_all_five(workload, names):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO))
    env.pop("PYTHONHASHSEED", None)
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", workload,
         "--seed", "2147484803", "--seconds", "2", "--trace", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 3, proc.stderr[-2000:]
    report = json.loads(proc.stderr.strip().splitlines()[-1])
    assert report["metrics"] == {} and report["failed"] == 0
    read = set(report["counts"]["metrics_read"])
    assert set(names) <= read
    assert not read & (set(METRICS) | set(TWINS)) - set(names)


def test_untraced_storm_reads_the_two_cuts_and_no_share():
    """Device profiling off: the ledger still cuts ``coalesce_wait`` at
    the run's begin, and keeps no account."""
    cell = run.load_json("workloads", "tiny-storm")
    driver = run.load_plugin("drivers", cell["driver"]).Driver(
        run.load_json("configs", cell["config"]), cell["params"], 2147484805
    )
    window = Window(1.0, None, 0.3)
    try:
        driver.set_up()
        out = driver.run(window)
    finally:
        driver.close()
    counted = [r for r in out["waterfalls"] if r["trigger"] in POPULATION]
    assert counted and all(r["hold"]["by"] == {} for r in counted)
    for record in counted:
        hold = record["hold"]
        assert hold["wait"] + hold["prerun"] == pytest.approx(
            record["phases"]["coalesce_wait"], abs=1e-9
        )
    layers = run.read_layers(
        [run.layer_spec(name) for name in sorted(METRICS)],
        run.ReadContext(out, window, {}),
    )
    assert sorted(layers) == ["storm_hold_wait_ms", "storm_prerun_ms"]
    assert all(v["value"] > 0.0 and v["unit"] == "ms" for v in layers.values())
