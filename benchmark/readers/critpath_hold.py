"""What an event waited for in front of its SPF run: the ``hold`` key
of the critical-path ledger's per-event records (kept by the driver
under ``run["waterfalls"]``, as ``critpath_phase`` reads them), which
cuts the ``coalesce_wait`` phase at the run's begin: ``wait`` before
it, ``prerun`` after it, and ``by``, the wait charged to the innermost
spans the host was in (``{}`` where device profiling was off).

args: ``triggers`` (population), and either ``part`` (``wait`` |
``prerun``) with ``stat`` (``p50`` ...) and ``scale``: a percentile of
that part; or ``by`` (span labels) with ``of`` (``wait``): 100 x the
sum of those spans' seconds over the sum of that part, over the records
that hold an account.  No record with ``hold`` (a program without the
cut), no account for a share, or nothing waited: nothing to read.
"""

from benchmark import stats


def read(args: dict, ctx):
    triggers = set(args["triggers"])
    holds = [
        r["hold"] for r in ctx.run.get("waterfalls") or ()
        if r["trigger"] in triggers and "hold" in r
    ]
    if "by" not in args:
        if not holds:
            return None
        values = [hold[args["part"]] for hold in holds]
        return (
            stats.percentile(values, float(args["stat"][1:]))
            * args.get("scale", 1.0)
        )
    accounted = [hold for hold in holds if hold["by"]]
    whole = sum(hold[args["of"]] for hold in accounted)
    if not whole:
        return None
    part = sum(
        hold["by"].get(span, 0.0) for hold in accounted for span in args["by"]
    )
    return 100.0 * part / whole
