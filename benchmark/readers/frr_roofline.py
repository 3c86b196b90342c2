"""Share, in percent, of the chip's HBM bandwidth that the whole FRR
program (``jit_frr_batch_step``) reaches in the traced sub-window, with
its bytes counted from below: the bytes ``benchmark/frrcost.py`` counts
for the blocked distance rows from the engine's counters, moved between
the trace's edges, over the program's device seconds there
(``benchmark/trace_modules.py``) times ``hbm_bytes_per_s`` of the
device in ``peaks.json``.  The post-convergence SPFs and the selection
are timed but not counted, so this is a floor of the program's share.

args: ``module`` (substring of the FRR program's name).  No trace, no
execution of the program, or no rows counted: nothing to read.
"""

import json
from pathlib import Path


def read(args: dict, ctx):
    import jax

    from benchmark.frrcost import rows_bytes
    from benchmark.trace_modules import module_busy_s

    path = ctx.window.trace_file() if ctx.trace is not None else None
    if path is None:
        return None
    busy = module_busy_s(str(path), args["module"])
    edges = ("trace_open", "trace_close")
    window = ctx.window
    rounds = window.counter_delta("holo_frr_rounds_total", "", edges)
    row_rounds = window.counter_delta("holo_frr_row_rounds_total", "", edges)
    slots = window.snap["trace_close"].get("holo_frr_rows_slots")
    if busy is None or not rounds or not slots:
        return None
    peaks = json.loads(
        (Path(__file__).resolve().parents[1] / "peaks.json").read_text()
    )
    peak = peaks[jax.devices()[0].device_kind]["hbm_bytes_per_s"]
    return 100.0 * rows_bytes(slots, rounds, row_rounds) / (busy * peak)
