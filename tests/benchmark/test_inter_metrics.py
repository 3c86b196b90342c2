"""``multiarea_inter_adverts_kept_share`` and
``multiarea_inter_originate_skipped_share``: each metric's file and its
``BENCHMARK.json`` entry, found by name wherever later entries are
appended, what the reader that was there makes of its counter, with a
program that lacks the counter too, and the OSPFv3 rehearsal reading
both."""

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
METRICS = {
    # name: (counter family, the share's label, the other label)
    "multiarea_inter_adverts_kept_share": (
        "holo_ospf_inter_adverts_total", "path=kept", "path=rebuilt",
    ),
    "multiarea_inter_originate_skipped_share": (
        "holo_ospf_inter_originations_total", "path=skipped", "path=called",
    ),
}


def _spec(name: str) -> dict:
    return json.loads(
        (REPO / "benchmark/layer_metrics" / f"{name}.json").read_text()
    )


@pytest.mark.parametrize("name", sorted(METRICS))
def test_metric_file_and_entry_read_the_share_of_the_counter(name):
    family, label, _other = METRICS[name]
    spec = _spec(name)
    assert spec["reader"] == "counter_ratio"
    assert spec["args"] == {
        "family": family, "label": label, "of": {"family": family},
    }
    top = json.loads((REPO / "BENCHMARK.json").read_text())
    # by name and content, wherever later entries are appended
    [entry] = [m for m in top["per_layer"] if m["name"] == name]
    assert entry == {
        "name": name, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "protocol instance",
        "moves": "multiarea_trigger_fib_p50_ms",
        # the OSPFv3 cell; a later cell is appended
        "workloads": ["v3-multiarea-storm", *entry["workloads"][1:]],
    }
    for key in ("unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key]
    assert set(spec) == {
        "name", "unit", "better", "layer", "source", "moves", "reader",
        "args", "what",
    }


CASES = {
    # a window of stages by difference
    "share": ({"a": 1000.0, "b": 500.0}, {"a": 10000.0, "b": 1500.0}, 90.0),
    # every stage a whole one
    "whole": ({"b": 10.0}, {"b": 5010.0}, 0.0),
    # nothing moved in any stage of the window
    "all": ({}, {"a": 800.0}, 100.0),
    # a program without the counter (the parent commit), and a cell that
    # never runs an OSPFv3 ABR: nothing to read, no metric
    "no-counter": ({}, {}, None),
}


@pytest.mark.parametrize("name", sorted(METRICS))
@pytest.mark.parametrize("case", sorted(CASES))
def test_reader_gives_the_share_or_nothing(name, case):
    family, label, other = METRICS[name]
    keys = {"a": f"{family}{{{label}}}", "b": f"{family}{{{other}}}"}
    opened, closed, want = CASES[case]
    window = Window(1.0, None, 0.3)
    window.snap = {
        "open": {keys[k]: v for k, v in opened.items()},
        "close": {keys[k]: v for k, v in closed.items()},
    }
    assert counter_ratio.read(
        _spec(name)["args"], SimpleNamespace(window=window)
    ) == want


def test_another_family_alone_reads_nothing():
    window = Window(1.0, None, 0.3)
    window.snap = {
        "open": {"holo_ospf_derive_routes_total{path=kept}": 1.0},
        "close": {"holo_ospf_derive_routes_total{path=kept}": 9.0},
    }
    for name in METRICS:
        assert counter_ratio.read(
            _spec(name)["args"], SimpleNamespace(window=window)
        ) is None


def test_traced_areastorm_rehearsal_reads_both_shares():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO))
    env.pop("PYTHONHASHSEED", None)
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload",
         "tiny-areastorm", "--seed", "2147484742", "--seconds", "2",
         "--trace", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 3, proc.stderr[-2000:]
    report = json.loads(proc.stderr.strip().splitlines()[-1])
    assert report["metrics"] == {} and report["failed"] == 0
    read = report["counts"]["metrics_read"]
    assert set(METRICS) <= set(read)
    assert "multiarea_interarea_ms" in read
