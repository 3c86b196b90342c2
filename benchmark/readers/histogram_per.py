"""Seconds one histogram gained inside the window per observation of
another: the sum of ``family``/``label`` over the count of
``per.family``/``per.label``, both as deltas of ``telemetry.snapshot``.
For work that is not done once per unit, told per unit (the routing
actor's deliveries per SPF run).

args: ``family``, ``label`` (substring of the label string), ``per``
(``{"family", "label"}``: the histogram whose observations are the
units), ``scale``.  No unit observed, or nothing observed of the
family itself (a program without the span): nothing to read.
"""


def read(args: dict, ctx):
    per = args["per"]
    units, _sum = ctx.window.histogram_delta(
        per["family"], per.get("label", "")
    )
    if not units:
        return None
    count, total = ctx.window.histogram_delta(
        args["family"], args.get("label", "")
    )
    if not count:
        return None
    return total / units * args.get("scale", 1.0)
