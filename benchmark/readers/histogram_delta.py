"""Mean of a host-clock histogram's observations inside the window:
sum over count, both as deltas of ``telemetry.snapshot``.

args: ``family``, ``label`` (substring of the label string), ``scale``.
"""


def read(args: dict, ctx):
    count, total = ctx.window.histogram_delta(
        args["family"], args.get("label", "")
    )
    if not count:
        return None
    return total / count * args.get("scale", 1.0)
