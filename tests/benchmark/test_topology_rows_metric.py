"""``multiarea_topology_rows_kept_share`` (ISSUE 39): the metric's file
and its ``BENCHMARK.json`` entry, what the reader that was there makes
of the counter ``holo_ospf_topology_rows_total{path}``, with a program
that lacks the counter too, and the OSPFv3 rehearsal reading it."""

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
NAME = "multiarea_topology_rows_kept_share"
FAMILY = "holo_ospf_topology_rows_total"
SPEC = json.loads(
    (REPO / "benchmark/layer_metrics" / f"{NAME}.json").read_text()
)


def test_metric_file_reads_the_kept_share_of_the_link_rows():
    assert SPEC["reader"] == "counter_ratio"
    assert SPEC["args"] == {
        "family": FAMILY, "label": "path=kept", "of": {"family": FAMILY},
    }
    top = json.loads((REPO / "BENCHMARK.json").read_text())
    # by name and content, wherever later entries are appended
    [entry] = [m for m in top["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "protocol instance",
        "moves": "multiarea_trigger_fib_p50_ms",
        # the OSPFv3 cell; a later cell is appended
        "workloads": ["v3-multiarea-storm", *entry["workloads"][1:]],
    }
    for key in ("unit", "better", "source", "layer", "moves"):
        assert SPEC[key] == entry[key]
    assert set(SPEC) == {
        "name", "unit", "better", "layer", "source", "moves", "reader",
        "args", "what",
    }


def _key(path: str) -> str:
    return f"{FAMILY}{{path={path}}}"


@pytest.mark.parametrize("opened, closed, want", [
    # a window of assemblies by difference
    ({_key("kept"): 1000.0, _key("resolved"): 500.0},
     {_key("kept"): 86000.0, _key("resolved"): 15500.0}, 85.0),
    # every assembly a whole one (the vertex model never last call's)
    ({_key("resolved"): 10.0}, {_key("resolved"): 85010.0}, 0.0),
    # assemblies in which no emitted segment was a new entry
    ({}, {_key("kept"): 85000.0}, 100.0),
    # a program without the counter (the parent commit), and a cell
    # that never assembles an OSPFv3 area: nothing to read, no metric
    ({}, {}, None),
    ({"holo_ospf_topology_lsas_total{path=lowered}": 1.0},
     {"holo_ospf_topology_lsas_total{path=lowered}": 9.0}, None),
], ids=["kept", "whole", "all-kept", "no-counter", "other-family-only"])
def test_reader_gives_the_share_or_nothing(opened, closed, want):
    window = Window(1.0, None, 0.3)
    window.snap = {"open": opened, "close": closed}
    assert counter_ratio.read(
        SPEC["args"], SimpleNamespace(window=window)
    ) == want


def test_traced_areastorm_rehearsal_reads_the_kept_share():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO))
    env.pop("PYTHONHASHSEED", None)
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload",
         "tiny-areastorm", "--seed", "2147484739", "--seconds", "2",
         "--trace", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 3, proc.stderr[-2000:]
    report = json.loads(proc.stderr.strip().splitlines()[-1])
    assert report["metrics"] == {} and report["failed"] == 0
    read = report["counts"]["metrics_read"]
    assert NAME in read and "multiarea_topology_relower_share" in read
