"""The seven storm metrics that read the host stages (ISSUE 25): found
as files by the runner that was there, read in the storm's traced
rehearsal, and the one new reader on hand-made snapshots."""

import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmark.readers import histogram_per
from benchmark.window import Window

REPO = Path(__file__).resolve().parents[2]
STAGE_METRICS = {
    "storm_spf_run_ms": "run", "storm_topology_ms": "topology",
    "storm_delta_link_ms": "link", "storm_derive_ms": "derive",
    "storm_interarea_ms": "inter", "storm_publish_ms": "publish",
}
FAMILY = "holo_profile_stage_seconds"


def test_traced_storm_rehearsal_reads_all_seven_host_stage_metrics():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO))
    env.pop("PYTHONHASHSEED", None)
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", "tiny-storm",
         "--seed", "2147483659", "--seconds", "2", "--trace", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 3, proc.stderr[-2000:]
    report = json.loads(proc.stderr.strip().splitlines()[-1])
    read = set(report["counts"]["metrics_read"])
    assert set(STAGE_METRICS) | {"storm_rib_apply_ms"} <= read
    # ... beside the ones that time the same layers from outside
    assert {"hold_coalesce_ms", "rib_fib_ms", "storm_dispatch_ms"} <= read
    assert report["metrics"] == {} and report["failed"] == 0


@pytest.mark.parametrize("name, stage", sorted(STAGE_METRICS.items()))
def test_stage_metric_file_reads_its_own_span(name, stage):
    spec = json.loads(
        (REPO / "benchmark/layer_metrics" / f"{name}.json").read_text()
    )
    assert spec["reader"] == "histogram_delta"
    assert spec["args"]["family"] == FAMILY
    assert spec["args"]["label"] == f"site=ospf.spf,stage={stage},device=-"
    assert spec["args"]["scale"] == 1000.0 and spec["unit"] == "ms"


def _window(open_snap: dict, close_snap: dict) -> SimpleNamespace:
    window = Window(1.0, None, 0.3)
    window.snap = {"open": open_snap, "close": close_snap}
    return SimpleNamespace(window=window)


def _key(site: str, stage: str) -> str:
    return f"{FAMILY}{{site={site},stage={stage},device=-}}"


ARGS = {
    "family": FAMILY, "label": "site=loop,stage=routing,device=-",
    "per": {"family": FAMILY, "label": "site=ospf.spf,stage=run,device=-"},
    "scale": 1000.0,
}


def test_histogram_per_divides_one_sum_by_the_other_count():
    ctx = _window(
        {_key("loop", "routing"): {"count": 10, "sum": 1.0},
         _key("loop", "storm-dut"): {"count": 7, "sum": 50.0},
         _key("ospf.spf", "run"): {"count": 2, "sum": 9.0}},
        {_key("loop", "routing"): {"count": 310, "sum": 1.6},
         _key("loop", "storm-dut"): {"count": 9, "sum": 70.0},
         _key("ospf.spf", "run"): {"count": 6, "sum": 19.0}},
    )
    # 0.6 s of routing deliveries over 4 SPF runs, in ms
    assert histogram_per.read(ARGS, ctx) == pytest.approx(150.0)


_RUNS, _ROUTING = _key("ospf.spf", "run"), _key("loop", "routing")


@pytest.mark.parametrize(
    "open_snap, close_snap",
    [
        # no SPF run in the window: a zero denominator
        ({_ROUTING: {"count": 3, "sum": 0.2}, _RUNS: {"count": 2, "sum": 9.0}},
         {_ROUTING: {"count": 12, "sum": 1.1}, _RUNS: {"count": 2, "sum": 9.0}}),
        # a program without the spans (the parent commit): nothing at all
        ({}, {}),
        # runs, but no delivery observed: not a measured zero
        ({_RUNS: {"count": 2, "sum": 9.0}}, {_RUNS: {"count": 5, "sum": 9.5}}),
    ],
    ids=["zero-denominator", "no-such-span", "nothing-observed"],
)
def test_histogram_per_reads_nothing_where_nothing_was_observed(
    open_snap, close_snap
):
    assert histogram_per.read(ARGS, _window(open_snap, close_snap)) is None
