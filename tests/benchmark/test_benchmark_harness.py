"""The benchmark's runner, rehearsed on the CPU at tiny sizes: the same
control flow as on the chip, counts only, never a result."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _rehearse(cwd: Path, workload: str, trace: int, seconds: str = "1"):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO))
    env.pop("PYTHONHASHSEED", None)
    return subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", workload,
         "--seed", "5", "--seconds", seconds, "--trace", str(trace)],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def _report(proc) -> dict:
    return json.loads(proc.stderr.strip().splitlines()[-1])


@pytest.mark.parametrize(
    "workload, trace, counted",
    [("tiny-single", 0, "queries"), ("tiny-sweep", 1, "scenarios"),
     ("tiny-storm", 1, "spf_path_converged")],
)
def test_off_the_chip_a_cell_is_a_rehearsal_and_never_a_result(
    workload, trace, counted
):
    # The storm's tail wants 100 samples, also on a busy test machine.
    proc = _rehearse(REPO, workload, trace, "2" if "storm" in workload else "1")
    assert proc.returncode == 3, proc.stderr[-2000:]
    # Standard output carries no result line and no value of any metric.
    assert not any(
        line.startswith("{") or '"value"' in line
        for line in proc.stdout.splitlines()
    ), proc.stdout
    report = _report(proc)
    assert CONTRACT_KEYS <= set(report)
    assert report["correct"] is False and report["metrics"] == {}
    assert report["device"]["platform"] == "cpu"
    assert "busy_s" not in report["device"] and "breakdown" not in report
    assert report["attempted"] > 0 and report["failed"] == 0
    assert report["counts"][counted] > 0
    checks = report["checks"]
    assert checks["platform_is_tpu"] is False
    assert checks["parity"] and checks["fallback_clean"]
    assert "setup_s" in report["counts"]["metrics_read"] or trace
    if trace:  # a CPU trace has no device plane: an error, not 100% idle
        assert "no device plane" in report["trace_error"]
        assert "window_compiles" in report["counts"]["metrics_read"]
    if workload == "tiny-storm":  # the tail needs ten samples beyond it
        assert report["counts"]["tail_samples_beyond"] >= 10
        assert "trigger_fib_tail_ms" in report["counts"]["metrics_read"]


def test_unknown_workload_names_the_ones_there_are():
    proc = _rehearse(REPO, "no-such-cell", 0)
    assert proc.returncode == 2
    assert "tiny-single" in proc.stderr and proc.stdout.strip() == ""


def test_new_cell_config_and_layer_metric_are_found_as_files(tmp_path):
    """A later PR adds a workload, a configuration and a layer metric
    by adding files: no file that is there needs an edit."""
    shutil.copytree(
        REPO / "benchmark", tmp_path / "benchmark",
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    bench = tmp_path / "benchmark"
    config = json.loads((bench / "configs/tiny-fabric.json").read_text())
    config.update(name="added-fabric")
    config["topology"]["k"] = 6
    (bench / "configs/added-fabric.json").write_text(json.dumps(config))
    (bench / "workloads/added-cell.json").write_text(json.dumps({
        "name": "added-cell", "config": "added-fabric", "traffic": "single",
        "driver": "single", "chips": 1, "params": {}, "trace_seconds": 0.3,
    }))
    (bench / "layer_metrics/added_query_p90_ms.json").write_text(json.dumps({
        "name": "added_query_p90_ms", "unit": "ms", "better": "lower",
        "layer": "dispatch", "source": "host_clock",
        "moves": "spf_query_p50_ms", "reader": "bench_clock",
        "args": {"sample": "query_wall_s", "stat": "p90", "scale": 1000.0},
    }))
    proc = _rehearse(tmp_path, "added-cell", 1)
    assert proc.returncode == 3, proc.stderr[-2000:]
    read = _report(proc)["counts"]["metrics_read"]
    assert "added_query_p90_ms" in read
    assert "query_dispatch_ms" in read  # the ones that were there, too
    assert "sweep_dispatch_ms" not in read  # moves a metric not reported


def _measure(workload: str, seconds: float = 0.5):
    from benchmark import run

    cell = run.load_json("workloads", workload)
    return run.measure(
        cell, run.load_json("configs", cell["config"]),
        run.load_plugin("drivers", cell["driver"]), 5, seconds, False,
    )


@pytest.mark.parametrize("workload", ["tiny-single", "tiny-sweep", "tiny-storm"])
def test_forced_dispatch_failure_is_incorrect_though_every_bit_matches(
    workload,
):
    """The breaker serves a failed device dispatch from the scalar
    oracle with identical bits: parity holds, the witness does not."""
    from holo_tpu.resilience.faults import FaultPlan, inject

    with inject(FaultPlan(dispatch_fail={"spf.dispatch": 1})):
        result, _rc = _measure(workload)
    assert result["checks"]["parity"] is True
    assert result["checks"]["fallback_clean"] is False
    assert result["correct"] is False


def test_every_number_compared_stands_beside_its_limit_last_in_the_line():
    from holo_tpu.resilience.faults import FaultPlan, inject

    clean, _rc = _measure("tiny-storm")
    assert list(clean)[-1] == "compared"
    over = {k for k, (read, limit) in clean["compared"].items() if read > limit}
    assert over == {"chips_missing"}  # a rehearsal: no chip, all else clean
    assert all(limit == 0 for _read, limit in clean["compared"].values())
    with inject(FaultPlan(dispatch_fail={"spf.dispatch": 1})):
        broken, _rc = _measure("tiny-storm")
    read, limit = broken["compared"]["fallback_dispatches"]
    assert read >= 1 and limit == 0


@pytest.mark.parametrize("fault", [False, True])
def test_correct_is_decided_by_the_numbers_compared_and_nothing_else(fault):
    """``checks`` and ``correct`` are read off ``compared``: a check
    fails exactly where one of its numbers is over its limit."""
    from contextlib import nullcontext

    from holo_tpu.resilience.faults import FaultPlan, inject

    plan = FaultPlan(dispatch_fail={"spf.dispatch": 1})
    with inject(plan) if fault else nullcontext():
        result, _rc = _measure("tiny-single")
    over = {k for k, (read, limit) in result["compared"].items() if read > limit}
    failing = {k for k, ok in result["checks"].items() if not ok}
    assert ("fallback_dispatches" in over) is fault
    assert ("fallback_clean" in failing) is fault
    assert "platform_is_tpu" in failing and "chips_missing" in over
    assert len(failing) <= len(over) and result["correct"] is (not over)
    if not fault:
        assert failing == {"platform_is_tpu"} and over == {"chips_missing"}


def test_clean_rehearsal_in_process_fails_only_on_the_platform():
    result, rc = _measure("tiny-single")
    assert rc == 3 and result["metrics"] == {}
    failing = [k for k, ok in result["checks"].items() if not ok]
    assert failing == ["platform_is_tpu"]


@pytest.mark.parametrize("kind", ["layer_metrics", "workloads", "configs"])
def test_every_data_file_is_named_by_what_it_holds(kind):
    for path in sorted((REPO / "benchmark" / kind).glob("*.json")):
        assert json.loads(path.read_text())["name"] == path.stem


def test_benchmark_json_lists_what_the_files_hold():
    """BENCHMARK.json's cells, configurations and layer metrics are the
    files of the same names, with the same unit, layer and moves."""
    from benchmark import run

    top = json.loads((REPO / "BENCHMARK.json").read_text())
    bench = REPO / "benchmark"
    for cfg in top["configs"]:
        held = json.loads((REPO / cfg["file"]).read_text())
        assert held["name"] == cfg["name"] and held["source"] == cfg["source"]
        assert held["reduced"] == cfg["reduced"]
    for cell in top["workloads"]:
        held = json.loads((bench / f"workloads/{cell['name']}.json").read_text())
        for key in ("config", "traffic", "chips"):
            assert held[key] == cell[key]
    end_to_end = {m["name"] for m in top["end_to_end"]}
    for metric in top["per_layer"]:
        held = run.layer_spec(metric["name"])  # a twin's, filled in
        for key in ("unit", "better", "source", "layer", "moves"):
            assert held[key] == metric[key], (metric["name"], key)
        assert metric["moves"] in end_to_end
    assert {m["name"] for m in top["per_layer"]} == {
        p.stem for p in (bench / "layer_metrics").glob("*.json")
    }


def _backend_answering(alter):
    """``fabric.backend_of`` for a device backend whose every answer
    passes through ``alter`` where it is produced."""
    from holo_tpu.spf.backend import TpuSpfBackend

    class Altered(TpuSpfBackend):
        def compute(self, topo, edge_mask=None, **kw):
            return alter(super().compute(topo, edge_mask, **kw))

        def compute_whatif(self, topo, edge_masks, **kw):
            return [
                alter(res)
                for res in super().compute_whatif(topo, edge_masks, **kw)
            ]

    return lambda config: Altered(**config.get("backend", {}))


def _one_hop_further(res):
    """The farthest vertex one unit further: a wrong answer."""
    import dataclasses

    dist = res.dist.copy()
    dist[-1] += 1
    return dataclasses.replace(res, dist=dist)


@pytest.mark.parametrize(
    "workload, alter, correct",
    [("tiny-single", _one_hop_further, False),
     ("tiny-sweep", _one_hop_further, False),
     ("tiny-storm", _one_hop_further, False),
     ("tiny-single", lambda res: res, True),
     ("tiny-storm", lambda res: res, True)],
)
def test_an_answer_altered_where_it_is_produced_is_incorrect(
    monkeypatch, workload, alter, correct
):
    """The control of ``correct``: the timed path broken underneath
    the harness (every guarantee of the configuration's file rests on
    these answers) fails the parity check, and only that one; the same
    wrapper altering nothing passes it."""
    from benchmark import fabric

    monkeypatch.setattr(fabric, "backend_of", _backend_answering(alter))
    result, _rc = _measure(workload)
    assert result["checks"]["parity"] is correct
    assert result["checks"]["fallback_clean"] and result["failed"] == 0
    read, limit = result["compared"]["parity_not_ok"]
    assert (read, limit) == (int(not correct), 0)
    if not correct:
        assert result["correct"] is False
        assert result["compared"]["parity_mismatches"][0] >= 1
