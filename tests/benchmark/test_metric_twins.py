"""One convergence metric per kind of storm (ISSUE 36): a bound is keyed
to an end-to-end metric's name, so the multi-area storm reports its
median under a name of its own, and every layer reading it shares with
the OSPFv2 cells has a twin that moves that name.

What is pinned here is what PR 36 put there.  A later cell lists itself
under either metric, and a later twin of any family is a file, with no
edit to this one: the twins are found by their ``twin_of`` key, and each
is held to its own file and its own original."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from benchmark import run
from benchmark.drivers import areastorm, popstorm, storm

REPO = Path(__file__).resolve().parents[2]
TOP = json.loads((REPO / "BENCHMARK.json").read_text())
END_TO_END = {m["name"]: m for m in TOP["end_to_end"]}
PER_LAYER = {m["name"]: m for m in TOP["per_layer"]}
OLD, NEW = storm.Driver.METRIC, areastorm.Driver.METRIC
V3 = "v3-multiarea-storm"
TWINS = sorted(
    path.stem for path in (REPO / "benchmark/layer_metrics").glob("*.json")
    if "twin_of" in json.loads(path.read_text())
)
#: the originals PR 36 twinned for ``v3-multiarea-storm``
TWINNED_IN_PR_36 = (
    "delta_incremental_share", "generator_share", "hold_coalesce_ms",
    "local_repair_p50_ms", "rib_fib_ms", "trigger_fib_tail_ms",
    "storm_delta_link_ms", "storm_delta_ops_mean",
    "storm_delta_refused_share", "storm_derive_decode_share",
    "storm_derive_ms", "storm_device_idle_share", "storm_device_wait_ms",
    "storm_dispatch_ms", "storm_interarea_ms", "storm_marshal_ms",
    "storm_publish_ms", "storm_remarshal_share", "storm_rib_apply_ms",
    "storm_spf_device_ms", "storm_spf_run_ms", "storm_topology_ms",
    "storm_topology_relower_share",
)


def test_the_two_names_their_bounds_and_their_cells():
    assert (OLD, NEW) == ("trigger_fib_p50_ms", "multiarea_trigger_fib_p50_ms")
    assert popstorm.Driver.METRIC == OLD
    old, new = END_TO_END[OLD], END_TO_END[NEW]
    assert (old["bound"], new["bound"]) == (0.09, 0.25)
    for key in ("unit", "better", "source"):
        assert new[key] == old[key]
    assert old["workloads"][:2] == ["backbone10k-flapstorm", "isp-zoo-storm"]
    assert V3 in new["workloads"] and V3 not in old["workloads"]


@pytest.mark.parametrize("original", TWINNED_IN_PR_36)
def test_the_multiarea_storm_reads_a_twin_of(original):
    name = "multiarea_" + original.removeprefix("storm_")
    assert name in TWINS
    assert run.layer_spec(name)["twin_of"] == original
    assert V3 in PER_LAYER[name]["workloads"]
    assert V3 not in PER_LAYER[original]["workloads"]


@pytest.mark.parametrize("name", TWINS)
def test_a_twin_is_its_original_but_for_what_it_moves(name):
    held = run.load_json("layer_metrics", name)
    assert set(held) == run.TWIN_KEYS  # nothing overridden
    spec, first = run.layer_spec(name), run.layer_spec(held["twin_of"])
    assert "twin_of" not in first and first["moves"] != held["moves"]
    for key in ("unit", "better", "layer", "source", "reader", "args"):
        assert spec[key] == first[key]
    # the name and what it moves carry one prefix: multiarea_spf_run_ms,
    # a twin of storm_spf_run_ms, moves multiarea_trigger_fib_p50_ms
    prefix = held["moves"].removesuffix(first["moves"])
    assert prefix and held["moves"] == prefix + first["moves"]
    assert name == prefix + first["name"].removeprefix("storm_")
    entry, entry_of_first = PER_LAYER[name], PER_LAYER[first["name"]]
    for key in ("unit", "better", "layer", "source"):
        assert entry[key] == entry_of_first[key] == first[key]
    assert entry["moves"] == held["moves"]
    assert entry_of_first["moves"] == first["moves"]
    # each lists only cells that report the metric it moves
    for listed in (entry, entry_of_first):
        reported_in = END_TO_END[listed["moves"]].get("workloads")
        if reported_in is not None:  # else every cell reports it
            assert set(listed["workloads"]) <= set(reported_in)


def _layer_metrics_are(tmp_path, monkeypatch, files: dict) -> None:
    (tmp_path / "layer_metrics").mkdir()
    for stem, spec in files.items():
        path = tmp_path / "layer_metrics" / f"{stem}.json"
        path.write_text(json.dumps(spec))
    monkeypatch.setattr(run, "HERE", tmp_path)


_FIRST = {
    "name": "first", "unit": "ms", "better": "lower", "layer": "kernels",
    "source": "program_span", "moves": "a_ms", "reader": "clock_mean",
}
_TWIN = {"name": "second", "twin_of": "first", "moves": "b_ms"}


def test_the_loader_fills_a_twin_in_from_its_original(tmp_path, monkeypatch):
    _layer_metrics_are(
        tmp_path, monkeypatch, {"first": _FIRST, "second": _TWIN}
    )
    assert run.layer_spec("second") == {**_FIRST, **_TWIN}
    assert [s["name"] for s in run.layer_specs({"b_ms"})] == ["second"]
    assert [s["name"] for s in run.layer_specs({"a_ms"})] == ["first"]


@pytest.mark.parametrize("twin, others", [
    ({**_TWIN, "unit": "s"}, {}),
    ({**_TWIN, "reader": "histogram_delta"}, {}),
    ({**_TWIN, "args": {"scale": 1.0}}, {}),
    ({"name": "second", "twin_of": "first"}, {}),
    ({**_TWIN, "moves": "a_ms"}, {}),
    ({**_TWIN, "twin_of": "second"}, {}),
    ({**_TWIN, "twin_of": "third"},
     {"third": {"name": "third", "twin_of": "first", "moves": "c_ms"}}),
    ({**_TWIN, "twin_of": "third"}, {"third": {**_FIRST, "name": "fourth"}}),
    ({**_TWIN, "twin_of": "absent"}, {}),
], ids=[
    "overrides-unit", "overrides-reader", "overrides-args", "moves-nothing",
    "moves-what-its-original-moves", "twin-of-itself", "twin-of-a-twin",
    "original-misnamed", "original-absent",
])
def test_the_loader_refuses_a_twin_that_is_more_than_a_twin(
    tmp_path, monkeypatch, twin, others
):
    """The rule lives in the loader every run uses, and a file that
    breaks it ends a run as any other misfit does: BenchError, exit 2."""
    _layer_metrics_are(
        tmp_path, monkeypatch, {"first": _FIRST, "second": twin, **others}
    )
    with pytest.raises(run.BenchError):
        run.layer_spec("second")


@pytest.mark.parametrize("more, code", [({}, 3), ({"unit": "s"}, 2)])
def test_a_later_twin_of_another_family_is_a_file(tmp_path, more, code):
    """A traced rehearsal of a copy of the benchmark with one file added:
    a twin of a ``query_`` metric is read under its own name, and one
    that overrides its original's unit ends the run with exit code 2."""
    shutil.copytree(
        REPO / "benchmark", tmp_path / "benchmark",
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    bench = tmp_path / "benchmark"
    original = run.load_json("layer_metrics", "query_dispatch_ms")
    cell = json.loads((bench / "workloads/tiny-single.json").read_text())
    cell.update(name="added-cell", driver="added")
    (bench / "workloads/added-cell.json").write_text(json.dumps(cell))
    (bench / "drivers/added.py").write_text(
        "from benchmark.drivers import single\n\n\n"
        "class Driver(single.Driver):\n"
        "    def run(self, window):\n"
        "        out = super().run(window)\n"
        "        out['end_to_end'] = {\n"
        "            'added_' + k: v for k, v in out['end_to_end'].items()\n"
        "        }\n"
        "        return out\n"
    )
    (bench / "layer_metrics/added_dispatch_ms.json").write_text(json.dumps({
        "name": "added_dispatch_ms", "twin_of": "query_dispatch_ms",
        "moves": "added_" + original["moves"], **more,
    }))
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO))
    env.pop("PYTHONHASHSEED", None)
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", "added-cell",
         "--seed", "5", "--seconds", "1", "--trace", "1"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == code, proc.stderr[-2000:]
    if code == 2:
        assert "twin added_dispatch_ms.json holds" in proc.stderr
        return
    report = json.loads(proc.stderr.strip().splitlines()[-1])
    read = report["counts"]["metrics_read"]
    assert "added_dispatch_ms" in read and "query_dispatch_ms" not in read


def _driver_of(cell: str) -> type:
    held = run.load_json("workloads", cell)
    return run.load_plugin("drivers", held["driver"]).Driver


def _storm_cells() -> list:
    return [
        cell["name"] for cell in TOP["workloads"]
        if issubclass(_driver_of(cell["name"]), storm.Driver)
    ]


@pytest.mark.parametrize("cell", _storm_cells())
def test_a_storm_cell_is_listed_under_the_metric_its_driver_reports(cell):
    listed = [
        m["name"] for m in TOP["end_to_end"]
        if m["name"] != "setup_s" and cell in m.get("workloads", [cell])
    ]
    assert listed == [_driver_of(cell).METRIC]  # and under no other
    moved = {*listed, "setup_s"}
    for metric in TOP["per_layer"]:
        if cell in metric.get("workloads", ()):
            assert metric["moves"] in moved, metric["name"]


def test_only_what_moves_set_up_is_read_in_every_cell():
    """An entry with no ``workloads`` is held to be read in every cell
    that reports what it moves, those a later PR adds too."""
    for metric in TOP["per_layer"]:
        assert metric.get("workloads", True), metric["name"]
        if "workloads" not in metric:
            assert metric["moves"] == "setup_s", metric["name"]
    assert all(
        "workloads" not in PER_LAYER[name]
        for name in ("peak_hbm_mb", "window_compiles")
    )
