"""Numbers of the profiler's trace of the steady sub-window
(``benchmark/trace_reduce.py``).

args: ``value``: ``idle_share`` (percent of the traced sub-window in
which no operation ran on the device) or ``busy_per`` (device-busy
seconds per observation of the histogram ``family``/``label`` made
inside the sub-window); ``scale``.
"""


def read(args: dict, ctx):
    reduced = ctx.trace
    if reduced is None:
        return None
    scale = args.get("scale", 1.0)
    if args["value"] == "idle_share":
        return 100.0 * (1.0 - reduced.busy_s / reduced.window_s)
    if args["value"] != "busy_per":
        raise ValueError(f"trace reader: unknown value {args['value']!r}")
    count, _sum = ctx.window.histogram_delta(
        args["family"], args.get("label", ""), ("trace_open", "trace_close")
    )
    if not count:
        return None
    return reduced.busy_s / count * scale
