"""The benchmark's own clocks and samples, as the driver returned them.

args: ``sample`` + ``stat`` (``p50`` ... of ``run["samples"][sample]``,
only with at least ``min_count`` samples) or ``clock`` [+ ``over``]
(``run["clocks"]``, a ratio when ``over`` is given); ``scale``.
"""

from benchmark import stats


def read(args: dict, ctx):
    scale = args.get("scale", 1.0)
    if "sample" in args:
        values = ctx.run.get("samples", {}).get(args["sample"])
        if not values or len(values) < args.get("min_count", 1):
            return None
        return stats.percentile(values, float(args["stat"][1:])) * scale
    clocks = ctx.run.get("clocks", {})
    if args["clock"] not in clocks:
        return None
    value = clocks[args["clock"]]
    if "over" in args:
        if not clocks.get(args["over"]):
            return None
        value /= clocks[args["over"]]
    return value * scale
