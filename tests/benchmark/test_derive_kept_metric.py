"""``multiarea_derive_kept_share`` (ISSUE 37): the metric's file and its
``BENCHMARK.json`` entry, what the reader that was there makes of the
counter ``holo_ospf_derive_routes_total{path}``, with a program that
lacks the counter too, and the OSPFv3 rehearsal reading it."""

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
NAME = "multiarea_derive_kept_share"
FAMILY = "holo_ospf_derive_routes_total"
SPEC = json.loads(
    (REPO / "benchmark/layer_metrics" / f"{NAME}.json").read_text()
)


def test_metric_file_reads_the_kept_share_of_derived_routes():
    assert SPEC["reader"] == "counter_ratio"
    assert SPEC["args"] == {
        "family": FAMILY, "label": "path=kept", "of": {"family": FAMILY},
    }
    top = json.loads((REPO / "BENCHMARK.json").read_text())
    [entry] = [m for m in top["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "readback + routes",
        "moves": "multiarea_trigger_fib_p50_ms",
        # the OSPFv3 cell; a later cell is appended
        "workloads": ["v3-multiarea-storm", *entry["workloads"][1:]],
    }
    for key in ("unit", "better", "source", "layer", "moves"):
        assert SPEC[key] == entry[key]
    assert top["per_layer"][-1]["name"] == NAME  # appended, nothing moved


def _key(path: str) -> str:
    return f"{FAMILY}{{path={path}}}"


@pytest.mark.parametrize("opened, closed, want", [
    # a window of derives by difference
    ({_key("kept"): 100.0, _key("rebuilt"): 50.0},
     {_key("kept"): 1000.0, _key("rebuilt"): 150.0}, 90.0),
    # every derive a whole one (IP-FRR active, say)
    ({_key("rebuilt"): 10.0}, {_key("rebuilt"): 510.0}, 0.0),
    # nothing moved in any dispatched area
    ({}, {_key("kept"): 340.0}, 100.0),
    # a program without the counter (the parent commit), and a cell
    # that never derives an OSPFv3 area: nothing to read, no metric
    ({}, {}, None),
    ({"holo_ospf_derive_nexthops_total{path=decoded}": 1.0},
     {"holo_ospf_derive_nexthops_total{path=decoded}": 9.0}, None),
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
         "tiny-areastorm", "--seed", "2147484737", "--seconds", "2",
         "--trace", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 3, proc.stderr[-2000:]
    report = json.loads(proc.stderr.strip().splitlines()[-1])
    assert report["metrics"] == {} and report["failed"] == 0
    read = report["counts"]["metrics_read"]
    assert NAME in read and "multiarea_derive_decode_share" in read
