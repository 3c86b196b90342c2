"""``storm_derive_planned_share`` (ISSUE 32): the metric's file and its
``BENCHMARK.json`` entry, what the reader that was there makes of the
counter ``holo_ospf_derive_calls_total{path}``, with a program that
lacks the counter too, and the two OSPFv2 storm rehearsals reading it."""

import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmark.readers import counter_ratio
from benchmark.window import Window

REPO = Path(__file__).resolve().parents[2]
NAME = "storm_derive_planned_share"
FAMILY = "holo_ospf_derive_calls_total"
SPEC = json.loads(
    (REPO / "benchmark/layer_metrics" / f"{NAME}.json").read_text()
)


def test_metric_file_reads_the_planned_share_of_derive_calls():
    assert SPEC["reader"] == "counter_ratio"
    assert SPEC["args"] == {
        "family": FAMILY, "label": "path=planned", "of": {"family": FAMILY},
    }
    top = json.loads((REPO / "BENCHMARK.json").read_text())
    [entry] = [m for m in top["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "readback + routes",
        "moves": "trigger_fib_p50_ms",
        # the two OSPFv2 storm cells; a later cell is appended
        "workloads": [
            "backbone10k-flapstorm", "isp-zoo-storm", *entry["workloads"][2:]
        ],
    }
    for key in ("unit", "better", "source", "layer", "moves"):
        assert SPEC[key] == entry[key]


def _key(path: str) -> str:
    return f"{FAMILY}{{path={path}}}"


@pytest.mark.parametrize("opened, closed, want", [
    # every run of the window from the plan
    ({_key("planned"): 7.0}, {_key("planned"): 340.0}, 100.0),
    # a topology without a plan among them
    ({_key("planned"): 10.0, _key("walked"): 2.0},
     {_key("planned"): 40.0, _key("walked"): 12.0}, 75.0),
    # the walk alone
    ({}, {_key("walked"): 5.0}, 0.0),
    # a program without the counter (the parent commit), and a cell
    # that never calls derive_routes: nothing to read, no metric
    ({}, {}, None),
    ({"holo_ospf_derive_nexthops_total{path=decoded}": 1.0},
     {"holo_ospf_derive_nexthops_total{path=decoded}": 9.0}, None),
], ids=["planned", "mixed", "walked", "no-counter", "other-family-only"])
def test_reader_gives_the_share_or_nothing(opened, closed, want):
    window = Window(1.0, None, 0.3)
    window.snap = {"open": opened, "close": closed}
    assert counter_ratio.read(
        SPEC["args"], SimpleNamespace(window=window)
    ) == want


@pytest.mark.parametrize("cell", ["tiny-storm", "tiny-ispstorm"])
def test_traced_storm_rehearsal_reads_the_planned_share(cell):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO))
    env.pop("PYTHONHASHSEED", None)
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", cell,
         "--seed", "2147483732", "--seconds", "2", "--trace", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 3, proc.stderr[-2000:]
    report = json.loads(proc.stderr.strip().splitlines()[-1])
    assert NAME in report["counts"]["metrics_read"]
    assert report["metrics"] == {} and report["failed"] == 0
