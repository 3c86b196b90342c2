"""Share, in percent, of the window's observations of one family that
a counter's move accounts for.

args: ``family``, ``label`` (numerator: a counter's children); ``of``
(denominator): ``{"family", "label"}`` of a counter, or with
``"histogram": true`` the count of a histogram's observations.  Nothing
observed: nothing to read.
"""


def read(args: dict, ctx):
    of, window = args["of"], ctx.window
    if of.get("histogram"):
        whole, _sum = window.histogram_delta(of["family"], of.get("label", ""))
    else:
        whole = window.counter_delta(of["family"], of.get("label", ""))
    if not whole:
        return None
    part = window.counter_delta(args["family"], args.get("label", ""))
    return 100.0 * part / whole
