"""One cell, one process, one result line.

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds ``workloads/<name>.json``, its ``configs/<config>.json``, the
driver ``drivers/<driver>.py`` the workload names and, in a traced run,
every ``layer_metrics/*.json`` that moves an end-to-end metric this
cell reports, each read by ``readers/<reader>.py``.  No name of a cell
or a configuration appears in code: a later PR adds either as files.

On the chip the last line of standard output is the result object of
BENCHMARK.json's contract.  Anywhere else the same control flow runs as
a rehearsal: counts only, on standard error, exit code 3, and no time,
rate or share under any metric's name.  No flag or variable turns a
rehearsal into a measurement.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
#: process start on the system-wide monotonic clock, carried over the
#: re-execution below so that set-up counts from the first process
_T0_ENV = "HOLO_BENCH_T0"
T0 = float(os.environ.get(_T0_ENV) or time.monotonic())

RC_OK, RC_INCORRECT, RC_USAGE, RC_NO_CHIP = 0, 1, 2, 3


class BenchError(Exception):
    """The benchmark's files or arguments do not fit together."""


def pin_hash_seed(argv: list[str]) -> None:
    """Re-execute with ``PYTHONHASHSEED=0`` before anything imports JAX:
    the FIB digest and some host iteration order depend on it (PERF.md,
    Open questions), and runs of one cell must do the same work."""
    if os.environ.get("PYTHONHASHSEED") == "0":
        return
    env = dict(os.environ, PYTHONHASHSEED="0", **{_T0_ENV: repr(T0)})
    os.execve(
        sys.executable, [sys.executable, "-m", "benchmark.run", *argv], env
    )


def load_json(kind: str, name: str) -> dict:
    path = HERE / kind / f"{name}.json"
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        known = sorted(p.stem for p in (HERE / kind).glob("*.json"))
        raise BenchError(f"no {kind}/{name}.json; there are: {known}")


def load_plugin(kind: str, name: str):
    """``drivers/<name>.py`` or ``readers/<name>.py``, by name."""
    if not (HERE / kind / f"{name}.py").is_file():
        known = sorted(
            p.stem for p in (HERE / kind).glob("*.py") if p.stem != "__init__"
        )
        raise BenchError(f"no {kind}/{name}.py; there are: {known}")
    return importlib.import_module(f"benchmark.{kind}.{name}")


#: all a twin may hold: what it takes from its original is never overridden
TWIN_KEYS = {"name", "twin_of", "moves"}


def layer_spec(name: str) -> dict:
    """``layer_metrics/<name>.json``.  A metric has one ``moves``, so
    the same reading in a cell that reports another end-to-end metric
    is a twin: a file that names its original under ``twin_of`` and
    holds only what differs, its ``name`` and its ``moves``."""
    spec = load_json("layer_metrics", name)
    if spec["name"] != name:
        raise BenchError(f"{name}.json names metric {spec['name']!r}")
    if "twin_of" not in spec:
        return spec
    if set(spec) != TWIN_KEYS:
        raise BenchError(
            f"twin {name}.json holds {sorted(spec)}, "
            f"a twin holds {sorted(TWIN_KEYS)} and no more"
        )
    original = load_json("layer_metrics", spec["twin_of"])
    if original["name"] != spec["twin_of"] or "twin_of" in original:
        raise BenchError(
            f"{name}.json is a twin of {spec['twin_of']!r}, "
            "which is a twin itself or not the file of that name"
        )
    if original["moves"] == spec["moves"]:
        raise BenchError(f"twin {name}.json moves what its original moves")
    return {**original, **spec}


def layer_specs(moved: set[str]) -> list[dict]:
    """Every layer metric that moves one of ``moved``, by name order."""
    specs = [
        layer_spec(path.stem)
        for path in sorted((HERE / "layer_metrics").glob("*.json"))
    ]
    return [spec for spec in specs if spec["moves"] in moved]


def read_layers(specs: list[dict], ctx) -> dict:
    """``{name: {"value", "unit"}}``; a reader that finds nothing to
    read returns None and its metric is left out."""
    out = {}
    for spec in specs:
        value = load_plugin("readers", spec["reader"]).read(
            spec.get("args", {}), ctx
        )
        if value is not None:
            out[spec["name"]] = {"value": float(value), "unit": spec["unit"]}
    return out


class ReadContext:
    """What a reader may read: the driver's own clocks and samples
    (``run``), the counter snapshots at the window's edges
    (``window.snap``), the device's memory statistics (``memory``) and
    the reduced profiler trace of the sub-window (``trace``; None in an
    untraced run, or where the trace held no device operation)."""

    def __init__(self, run: dict, window, memory: dict):
        self.run = run
        self.window = window
        self.memory = memory
        self.trace = None

    def reduce_trace(self) -> None:
        from benchmark.trace_reduce import TraceError, reduce_file

        path = self.window.trace_file()
        if path is None:
            raise TraceError("the traced run wrote no .xplane.pb")
        self.trace = reduce_file(path, self.window.trace_wall)


def mismatches(report) -> int:
    """Samples a parity report, however nested, found differing."""
    if not isinstance(report, dict):
        return 0
    return len(report.get("mismatches", ())) + sum(
        mismatches(part) for part in report.values()
    )


def memory_stats() -> dict:
    """Statistics of the fullest device (empty where the backend has
    none, as on the CPU)."""
    import jax

    stats = [d.memory_stats() or {} for d in jax.devices()]
    return max(stats, key=lambda s: s.get("peak_bytes_in_use", 0))


def run_cell(workload: str, seed: int, seconds: float, trace: bool):
    """One cell by name, as the command runs it.  Returns ``(result
    object, exit code)``."""
    cell = load_json("workloads", workload)
    config = load_json("configs", cell["config"])
    driver_mod = load_plugin("drivers", cell["driver"])

    import jax

    from holo_tpu.utils.compile_cache import configure_compile_cache

    # Every program is kept, also the sub-second ones JAX would skip.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    print(f"benchmark: compile cache at {configure_compile_cache()}",
          flush=True)
    return measure(cell, config, driver_mod, seed, seconds, trace)


def measure(
    cell: dict, config: dict, driver_mod, seed: int, seconds: float,
    trace: bool,
):
    """Set up, open the window, verify, read.  Touches no JAX
    configuration, so the tests call it in their own process."""
    from holo_tpu.telemetry import profiling

    from benchmark.window import Window
    from benchmark.witness import FallbackWitness, SetupClock, device_info

    workload = cell["name"]
    marks = {"imports": time.monotonic() - T0}
    dev = device_info()
    marks["device_init"] = time.monotonic() - T0
    on_chip = dev["platform"] == "tpu" and dev["count"] >= cell.get("chips", 1)
    if on_chip and dev["kind"] not in json.loads(
        (HERE / "peaks.json").read_text()
    ):
        raise BenchError(f"device kind {dev['kind']!r} is not in peaks.json")
    print(f"benchmark: cell={workload} seed={seed} seconds={seconds} "
          f"trace={int(trace)} device={json.dumps(dev)}", flush=True)

    clock, witness = SetupClock(), FallbackWitness()
    driver = driver_mod.Driver(config, cell.get("params", {}), seed)
    window = Window(
        seconds,
        HERE.parent / ".bench_trace" / workload if trace else None,
        cell.get("trace_seconds", 3.0),
    )
    try:
        driver.set_up()
        # The stage histograms put a device sync into every dispatch:
        # armed in the traced run only, where the layer metrics are read.
        profiling.set_device_profiling(trace)
        setup_s = marks["driver_set_up"] = time.monotonic() - T0
        programs0 = clock.programs
        run = driver.run(window)
        programs_in_window = clock.programs - programs0
        profiling.set_device_profiling(False)
        memory = memory_stats()
        parity = driver.verify()
    finally:
        profiling.set_device_profiling(False)
        driver.close()

    compiles = int(window.counter_delta("holo_spf_jit_compiles_total"))
    fallback = witness.check()
    notes = {
        "programs": clock.programs, "cache_hits": clock.cache_hits,
        "window_compiles": compiles, "programs_in_window": programs_in_window,
        "fallback": fallback, "parity": parity,
    }
    if on_chip:  # times are printed where they are measurements
        notes["setup_reached_s"] = marks  # since process start
        notes["setup_clock_s"] = clock.seconds
        notes["window_s"] = window.wall
        notes["timing"] = run.get("timing", {})
    print("benchmark: " + json.dumps(notes), flush=True)
    # Every number behind ``correct``, as [read, limit]: each comparison
    # is exact, so a run is correct when none is over its limit.
    compared = {
        "chips_missing": [int(not on_chip), 0],
        "fallback_dispatches": [fallback["fallbacks"], 0],
        "unclean_breakers": [len(fallback["unclean"]), 0],
        "window_compiles": [compiles, 0],
        "programs_in_window": [programs_in_window, 0],
        "parity_mismatches": [mismatches(parity), 0],
        "parity_not_ok": [int(not parity["ok"]), 0],
        "failed": [int(run["failed"]), 0],
        "nothing_attempted": [int(run["attempted"] == 0), 0],
        "end_to_end_unread": [int(len(run["end_to_end"]) == 0), 0],
    }

    def within(*names: str) -> bool:
        return all(compared[n][0] <= compared[n][1] for n in names)

    checks = {
        "platform_is_tpu": within("chips_missing"),
        "fallback_clean": within("fallback_dispatches", "unclean_breakers"),
        "no_compile_in_window": within(
            "window_compiles", "programs_in_window"
        ),
        "parity": within("parity_mismatches", "parity_not_ok"),
        "nothing_failed": within(
            "failed", "nothing_attempted", "end_to_end_unread"
        ),
    }

    result = {
        "correct": all(checks.values()),
        "attempted": int(run["attempted"]),
        "failed": int(run["failed"]),
        "metrics": {},
        "device": {
            **dev, "memory_peak_bytes": int(memory.get("peak_bytes_in_use", 0)),
        },
        "checks": checks,
        "counts": run.get("counts", {}),
    }
    end_to_end = {
        "setup_s": {"value": setup_s, "unit": "s"}, **run["end_to_end"]
    }
    layers = {}
    if trace:
        from benchmark.trace_reduce import TraceError

        ctx = ReadContext(run, window, memory)
        try:
            ctx.reduce_trace()
        except TraceError as exc:
            # No device operation in the trace: fails the run on the
            # chip, and is what a rehearsal off it must find.
            result["trace_error"] = str(exc)
            compared["trace_without_device_op"] = [1, 0]
            result["correct"] = False
        else:
            result["device"]["busy_s"] = ctx.trace.busy_s
            result["device"]["window_s"] = ctx.trace.window_s
            result["breakdown"] = ctx.trace.breakdown()
        layers = read_layers(layer_specs(set(end_to_end)), ctx)
    if not on_chip:
        # A rehearsal: which metrics would have been printed, no value.
        result["correct"] = False
        result["counts"]["metrics_read"] = sorted(layers if trace else end_to_end)
        result["device"].pop("busy_s", None)
        result["device"].pop("window_s", None)
        result.pop("breakdown", None)
        result["compared"] = compared
        return result, RC_NO_CHIP
    if trace:
        result["metrics"] = layers
        result["end_to_end_traced"] = end_to_end
    else:
        result["metrics"] = end_to_end
    result["compared"] = compared  # last in the line, and on stderr
    print(f"benchmark: compared [read, limit] {json.dumps(compared)}",
          file=sys.stderr, flush=True)
    return result, RC_OK if result["correct"] else RC_INCORRECT


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(prog="python3 -m benchmark.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    try:
        result, rc = run_cell(
            args.workload, args.seed, args.seconds, bool(args.trace)
        )
    except BenchError as exc:
        print(f"benchmark: {exc}", file=sys.stderr)
        return RC_USAGE
    line = json.dumps(result)
    if rc == RC_NO_CHIP:
        print("benchmark: no TPU with the chips this cell asks for; "
              "rehearsal only, counts follow", file=sys.stderr)
        print(line, file=sys.stderr, flush=True)
    else:
        print(line, flush=True)
    return rc


if __name__ == "__main__":
    pin_hash_seed(sys.argv[1:])
    sys.exit(main())
