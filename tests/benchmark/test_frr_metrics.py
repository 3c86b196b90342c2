"""The six per-layer metrics of ``isp-tilfa-storm``: each metric's file
and its ``BENCHMARK.json`` entry, found by name wherever later entries
are appended, and what each reader makes of a synthetic record — with a
program that lacks the spans and counters (the parent commit, or a cell
with fast reroute off) reading nothing."""

import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmark import frrcost
from benchmark.readers import (
    counter_per,
    counter_ratio,
    frr_roofline,
    histogram_per,
    trace_module,
)
from benchmark.window import Window

REPO = Path(__file__).resolve().parents[2]
STAGES = "holo_profile_stage_seconds"
RUN = "holo_profile_stage_seconds{site=ospf.spf,stage=run,device=-}"
DISPATCH = "holo_frr_dispatch_seconds{engine=tpu}"
METRICS = {
    # name: (unit, better, source, layer, reader)
    "frr_run_ms": ("ms", "lower", "program_span", "fast reroute", "histogram_per"),
    "frr_backups_ms": ("ms", "lower", "program_span", "readback + routes",
                       "histogram_per"),
    "frr_device_ms": ("ms", "lower", "device_trace", "kernels", "trace_module"),
    "frr_rows_per_dispatch": ("rows", "lower", "program_counter",
                              "fast reroute", "counter_per"),
    "frr_batch_roofline": ("%", "higher", "device_trace", "kernels",
                           "frr_roofline"),
    "frr_protected_share": ("%", "higher", "program_counter", "fast reroute",
                            "counter_ratio"),
}


def _spec(name: str) -> dict:
    return json.loads(
        (REPO / "benchmark/layer_metrics" / f"{name}.json").read_text()
    )


@pytest.mark.parametrize("name", sorted(METRICS))
def test_metric_file_and_entry(name):
    unit, better, source, layer, reader = METRICS[name]
    spec = _spec(name)
    assert spec["reader"] == reader and spec["name"] == name
    top = json.loads((REPO / "BENCHMARK.json").read_text())
    [entry] = [m for m in top["per_layer"] if m["name"] == name]
    assert entry == {
        "name": name, "unit": unit, "better": better, "source": source,
        "layer": layer, "moves": "trigger_fib_p50_ms",
        # the fast-reroute cell; a later cell is appended
        "workloads": ["isp-tilfa-storm", *entry["workloads"][1:]],
    }
    for key in ("unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key]
    assert set(spec) == {
        "name", "unit", "better", "layer", "source", "moves", "reader",
        "args", "what",
    }


def _window(opened: dict, closed: dict, **edges) -> Window:
    window = Window(1.0, None, 0.3)
    window.snap = {"open": opened, "close": closed, **edges}
    return window


def _hist(count: int, total: float) -> dict:
    return {"count": count, "sum": total}


@pytest.mark.parametrize("stage", ["frr", "backups"])
def test_stage_per_run(stage):
    name = f"frr_{'run' if stage == 'frr' else 'backups'}_ms"
    key = f"{STAGES}{{site=ospf.spf,stage={stage},device=-}}"
    args = _spec(name)["args"]
    w = _window({RUN: _hist(10, 2.0), key: _hist(10, 0.5)},
                {RUN: _hist(30, 6.0), key: _hist(28, 1.3)})
    assert histogram_per.read(args, SimpleNamespace(window=w)) == pytest.approx(40.0)
    # the parent commit: runs, no such span
    w = _window({RUN: _hist(10, 2.0)}, {RUN: _hist(30, 6.0)})
    assert histogram_per.read(args, SimpleNamespace(window=w)) is None


def test_rows_per_dispatch():
    args = _spec("frr_rows_per_dispatch")["args"]
    fam = "holo_frr_rows_total"
    w = _window(
        {f"{fam}{{dir=forward}}": 100.0, f"{fam}{{dir=reverse}}": 50.0,
         DISPATCH: _hist(4, 1.0)},
        {f"{fam}{{dir=forward}}": 300.0, f"{fam}{{dir=reverse}}": 110.0,
         DISPATCH: _hist(9, 2.0)},
    )
    assert counter_per.read(args, SimpleNamespace(window=w)) == 52.0
    w = _window({DISPATCH: _hist(4, 1.0)}, {DISPATCH: _hist(9, 2.0)})
    assert counter_per.read(args, SimpleNamespace(window=w)) is None


def test_protected_share_counts_every_kind_but_none():
    args = _spec("frr_protected_share")["args"]
    fam = "holo_frr_repairs_total"
    closed = {f"{fam}{{kind=lfa}}": 90.0, f"{fam}{{kind=rlfa}}": 4.0,
              f"{fam}{{kind=ti-lfa}}": 3.0, f"{fam}{{kind=none}}": 3.0}
    w = _window({}, closed)
    assert counter_ratio.read(args, SimpleNamespace(window=w)) == 97.0
    assert counter_ratio.read(args, SimpleNamespace(window=_window({}, {}))) is None


def _traced(monkeypatch, busy, snaps: dict):
    from benchmark import trace_modules

    monkeypatch.setattr(trace_modules, "module_busy_s", lambda path, m: busy)
    window = _window({}, {}, **snaps)
    window.trace_file = lambda: Path("/nonexistent.xplane.pb")
    return SimpleNamespace(window=window, trace=object())


def test_device_time_per_dispatch(monkeypatch):
    args = _spec("frr_device_ms")["args"]
    ctx = _traced(monkeypatch, 0.6, {
        "trace_open": {DISPATCH: _hist(2, 0.1)},
        "trace_close": {DISPATCH: _hist(6, 0.9)},
    })
    assert trace_module.read(args, ctx) == pytest.approx(150.0)
    assert trace_module.read(args, SimpleNamespace(window=ctx.window, trace=None)) is None
    ctx = _traced(monkeypatch, None, {
        "trace_open": {DISPATCH: _hist(2, 0.1)},
        "trace_close": {DISPATCH: _hist(6, 0.9)},
    })
    assert trace_module.read(args, ctx) is None


def test_rows_bytes():
    # 100 slots, two blocks: 3 rows x 10 rounds and 1 row x 4 rounds
    assert frrcost.rows_bytes(100, 14, 34) == 100 * (8 * 14 + 4 * 34)


def test_rows_roofline(monkeypatch):
    import jax

    args = _spec("frr_batch_roofline")["args"]
    assert args == {"module": "frr_batch"}
    ctx = _traced(monkeypatch, 0.002, {
        "trace_open": {"holo_frr_rounds_total": 10.0,
                       "holo_frr_row_rounds_total{dir=forward}": 100.0},
        "trace_close": {"holo_frr_rounds_total": 30.0,
                        "holo_frr_row_rounds_total{dir=forward}": 300.0,
                        "holo_frr_row_rounds_total{dir=reverse}": 40.0,
                        "holo_frr_rows_slots": 1000.0},
    })
    peaks = json.loads((REPO / "benchmark/peaks.json").read_text())
    kind = next(iter(peaks))
    monkeypatch.setattr(
        jax, "devices", lambda: [SimpleNamespace(device_kind=kind)]
    )
    want = 100.0 * frrcost.rows_bytes(1000, 20, 240) / (
        0.002 * peaks[kind]["hbm_bytes_per_s"]
    )
    assert frr_roofline.read(args, ctx) == pytest.approx(want)
    assert 0 < want < 100
    ctx.window.snap["trace_close"].pop("holo_frr_rows_slots")
    assert frr_roofline.read(args, ctx) is None



def test_module_time_is_the_union_of_the_programs_executions(monkeypatch):
    """``module_busy_s`` over a synthetic profile: two overlapping
    executions of the FRR program counted once, another program's and
    the host's left out, and operations never read."""
    import jax

    from benchmark import trace_modules

    def ev(name, start, end):
        return SimpleNamespace(
            name=name, start_ns=start, duration_ns=end - start, stats=[],
        )

    device = SimpleNamespace(name="/device:TPU:0", lines=[
        SimpleNamespace(name="XLA Modules", events=[
            ev("jit_frr_batch_step(123)", 0, 100),
            ev("jit_frr_batch_step(123)", 50, 150),
            ev("jit_spf_step(7)", 200, 900),
        ]),
        SimpleNamespace(name="XLA Ops", events=[
            ev("%fusion.1 = s32[16,10000] fusion(...)", 0, 900),
        ]),
    ])
    host = SimpleNamespace(name="/host:CPU", lines=[
        SimpleNamespace(name="XLA Modules", events=[
            ev("jit_frr_batch_step(123)", 0, 5000),
        ]),
    ])
    profile = SimpleNamespace(planes=[device, host])
    monkeypatch.setattr(
        jax.profiler.ProfileData, "from_file", staticmethod(lambda path: profile)
    )
    trace_modules.module_busy_s.cache_clear()
    assert trace_modules.module_busy_s("a.xplane.pb", "frr_batch") == (
        pytest.approx(150e-9)
    )
    assert trace_modules.module_busy_s("a.xplane.pb", "no_such_program") is None
    trace_modules.module_busy_s.cache_clear()
