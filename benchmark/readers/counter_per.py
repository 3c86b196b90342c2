"""A counter's move over the window per observation of a histogram:
for work that is counted and not timed, told per unit (areas
dispatched per SPF run).

args: ``family``, ``label`` (the counter's children, by substring of
the label string); ``per`` (``{"family", "label"}``: the histogram whose
observations are the units); ``scale``.  A program without the counter
(no child of the family in the window's last snapshot) or no unit
observed: nothing to read.
"""


def read(args: dict, ctx):
    window = ctx.window
    if not any(
        key.split("{", 1)[0] == args["family"] for key in window.snap["close"]
    ):
        return None
    per = args["per"]
    units, _sum = window.histogram_delta(per["family"], per.get("label", ""))
    if not units:
        return None
    moved = window.counter_delta(args["family"], args.get("label", ""))
    return moved / units * args.get("scale", 1.0)
