"""Device-busy seconds of one compiled program in the traced
sub-window (``benchmark/trace_modules.py``) per observation of a
histogram made in it.

args: ``module`` (substring of the program's name), ``per``
(``{"family", "label"}``: the histogram whose observations are the
units), ``scale``.  No trace, no execution of the program, or no unit
observed: nothing to read.
"""


def read(args: dict, ctx):
    from benchmark.trace_modules import module_busy_s

    path = ctx.window.trace_file() if ctx.trace is not None else None
    if path is None:
        return None
    busy = module_busy_s(str(path), args["module"])
    per = args["per"]
    count, _sum = ctx.window.histogram_delta(
        per["family"], per.get("label", ""), ("trace_open", "trace_close")
    )
    if busy is None or not count:
        return None
    return busy / count * args.get("scale", 1.0)
