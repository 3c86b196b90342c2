"""A percentile over the critical-path ledger's exact per-event records
(``critpath.CritPathLedger.waterfalls()``, kept by the driver under
``run["waterfalls"]``): the sum of some phases, or the wall.

args: ``phases`` (list; default: the event's wall), ``triggers``
(population), ``stat`` (``p50`` ...), ``scale``.
"""

from benchmark import stats


def read(args: dict, ctx):
    records = ctx.run.get("waterfalls")
    if not records:
        return None
    triggers = set(args["triggers"])
    phases = args.get("phases")
    values = [
        sum(r["phases"][p] for p in phases) if phases else r["wall"]
        for r in records if r["trigger"] in triggers
    ]
    if not values:
        return None
    return (
        stats.percentile(values, float(args["stat"][1:]))
        * args.get("scale", 1.0)
    )
