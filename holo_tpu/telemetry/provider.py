"""Telemetry as operational state: a read-only northbound provider
serving the registry under the ``holo-telemetry`` subtree, so gNMI
``Get``/``Subscribe`` (and the gRPC GetState path) see live metric
leaves with no extra plumbing — the ``_RuntimeStateProvider`` pattern.

Tree shape (walks into one gNMI update per leaf under PROTO encoding):

    holo-telemetry/
      metric[<name>]/            # list keyed by exposition name
        name                     # counter/gauge: bare family name;
        value                    #   histograms expand to _count/_sum
        labels                   # "k=v,k=v" ("" when label-less)
        exemplars                # histogram _count rows only: the
                                 #   OpenMetrics bucket exemplars
                                 #   ("le=<b>:span_id=<id>:value=<v>;...")
                                 #   Prometheus renders since PR 5 —
                                 #   the gNMI surface now carries the
                                 #   same span-id join keys
      health/                    # resilience summary (ISSUE 4)
        breakers/<name>/...      # dispatch-breaker state + failure tally
        supervision/...          # degraded actors, restart counts
      flight/                    # flight recorder (ISSUE 5; only while
        entries, capacity, dumps #   armed via flight-buffer-entries)
      spf-graph-cache/           # shared marshaled-graph cache (ISSUE 7):
        entries, capacity,       #   eviction/occupancy + DeltaPath chain
        evictions, deltas-...    #   state, next to the hit/miss counters
        sharded-entries, mesh,   #   + mesh placement (ISSUE 8): resident
        per-device/...           #   entries/rows/bytes per device
      gnmi-fanout/               # shared-delta fan-out engine (ISSUE 11):
        epoch, subscribers,      #   epoch id, cursor/bucket population,
        buckets, breaker, ...    #   breaker state + failure tally
      bgp-table/                 # device BGP plane (ISSUE 16): dispatch
        dispatches, fallbacks,   #   and fallback tallies, compiled shapes,
        tables/...               #   resident rows/cols + poisoned prefixes
      observatory/               # dispatch observatory (ISSUE 12; while
        sketches, observations,  #   armed): sketch population, sentinel
        sentinel/...             #   ledger + regressed keys, peak source
"""

from __future__ import annotations

from holo_tpu.northbound.provider import Provider as NbProvider

ROOT = "holo-telemetry"


class TelemetryStateProvider(NbProvider):
    """Read-only: owns no config subtree, vetoes nothing."""

    name = "telemetry"

    def __init__(self, registry=None):
        if registry is None:
            from holo_tpu import telemetry

            registry = telemetry.registry()
        self._registry = registry

    def filter_changes(self, changes):
        return []  # state-only: never part of a commit fan-out

    def get_state(self, path: str | None = None) -> dict:
        if path and not ROOT.startswith(path.split("/")[0]):
            return {}
        metrics = []
        for fam in self._registry.families():
            for key, child in fam.children():
                labels = ",".join(
                    f"{n}={v}" for n, v in zip(fam.labelnames, key)
                )
                if fam.kind == "histogram":
                    rows = [
                        (f"{fam.name}_count", child.count),
                        (f"{fam.name}_sum", round(child.sum, 9)),
                    ]
                else:
                    rows = [(fam.name, child.value)]
                exemplars = (
                    _exemplar_leaf(child) if fam.kind == "histogram" else ""
                )
                for name, value in rows:
                    entry = {
                        "name": f"{name}{{{labels}}}" if labels else name,
                        "value": value,
                        "labels": labels,
                    }
                    if exemplars and name.endswith("_count"):
                        # One leaf per histogram child (on the _count
                        # row): the bucket exemplars Prometheus has
                        # rendered since PR 5, now on the gNMI surface.
                        entry["exemplars"] = exemplars
                    metrics.append(entry)
        out = {"metric": metrics}
        health = _resilience_health()
        if health:
            out["health"] = health
        from holo_tpu.telemetry import flight

        rec = flight.recorder()
        if rec is not None:
            out["flight"] = rec.stats()
        from holo_tpu.telemetry import convergence

        tr = convergence.tracker()
        if tr is not None:
            out["convergence"] = tr.stats()
        # Lazy: the marshal cache pulls in jax — a daemon that never
        # dispatched device work should not pay the import at scrape
        # time, so the leaf appears once the engine module is loaded.
        import sys

        eng = sys.modules.get("holo_tpu.ops.spf_engine")
        if eng is not None:
            out["spf-graph-cache"] = eng.shared_graph_cache().stats()
        # Async dispatch pipeline + engine tuner (ISSUE 9): the leaf
        # appears once the pipeline package is armed (same lazy
        # discipline — an unarmed daemon pays nothing at scrape time).
        disp = sys.modules.get("holo_tpu.pipeline.dispatch")
        if disp is not None:
            # Bind once: a concurrent reset_process_pipeline() between
            # a check and a second lookup must not crash the scrape.
            pipe = disp.process_pipeline()
            if pipe is not None:
                out["pipeline"] = pipe.stats()
        tun = sys.modules.get("holo_tpu.pipeline.tuner")
        if tun is not None:
            tuner = tun.active_tuner()
            if tuner is not None:
                out["engine-tuner"] = tuner.stats()
        # Shared-delta gNMI fan-out (ISSUE 11): epoch / bucket /
        # breaker stats, one entry per live engine (same lazy
        # discipline — a daemon that never served a stream pays
        # nothing at scrape time).  Get-only by contract: the engine
        # excludes this leaf from its own sampled store (delta.py
        # SELF_ROOT) so its epoch bookkeeping cannot feed back into
        # the change-set it is diffing.
        # Device BGP table (ISSUE 16): Adj-RIB-In plane residency and
        # dispatch/fallback tallies, one entry per live backend (same
        # lazy discipline — scalar-only daemons never import the module).
        bgm = sys.modules.get("holo_tpu.ops.bgp_table")
        if bgm is not None:
            rows = bgm.backends_stats()
            if rows:
                out["bgp-table"] = rows[0] if len(rows) == 1 else rows
        fan = sys.modules.get("holo_tpu.telemetry.delta")
        if fan is not None:
            rows = fan.engines_stats()
            if rows:
                out["gnmi-fanout"] = rows[0] if len(rows) == 1 else rows
        # Dispatch observatory (ISSUE 12): sketch population, sentinel
        # ledger state, roofline peak source — present while armed.
        obsm = sys.modules.get("holo_tpu.telemetry.observatory")
        if obsm is not None:
            ob = obsm.active()
            if ob is not None:
                out["observatory"] = ob.stats()
        # Critical-path ledger (ISSUE 17): per-phase trigger→FIB
        # quantiles, bound-verdict tally, host-fraction — while armed.
        cpm = sys.modules.get("holo_tpu.telemetry.critpath")
        if cpm is not None:
            cp = cpm.active()
            if cp is not None:
                out["critical-path"] = cp.stats()
        # SLO plane (ISSUE 20): per-objective burn/budget/sentinel
        # state — while armed; the canary prober's attribution tallies
        # ride the same leaf when one is standing.
        slm = sys.modules.get("holo_tpu.telemetry.slo")
        if slm is not None:
            sl = slm.active()
            if sl is not None:
                out["slo"] = sl.stats()
                cam = sys.modules.get("holo_tpu.telemetry.canary")
                if cam is not None:
                    pr = cam.active()
                    if pr is not None:
                        out["slo"]["canary"] = pr.stats()
        # Device-residency byte ledger (ISSUE 17 satellite): per-plane
        # resident bytes — present once any device subsystem loaded
        # (the module itself stays lazy like the leaves it sums).
        resm = sys.modules.get("holo_tpu.telemetry.residency")
        if resm is not None:
            rs = resm.snapshot()
            if rs.get("total-bytes") or any(
                r["entries"] for r in rs["planes"].values()
            ):
                out["device-residency"] = rs
        return {ROOT: out}


def _exemplar_leaf(hist) -> str:
    """Compact scalar rendering of a histogram child's OpenMetrics
    bucket exemplars: ``le=<bucket>:<k>=<v>:value=<obs>`` joined by
    ``;`` in ascending bucket order (a gNMI leaf carries one scalar —
    the span-id join key is what matters)."""
    out = []
    for le, (pairs, value) in sorted(hist.exemplars().items()):
        le_s = "+Inf" if le == float("inf") else f"{le:g}"
        kv = ":".join(f"{k}={v}" for k, v in pairs)
        out.append(f"le={le_s}:{kv}:value={value:g}")
    return ";".join(out)


def _resilience_health() -> dict:
    """Breaker + supervision summary — the health leaf an operator (or
    an alerting pipeline subscribed over gNMI) watches instead of
    deriving state from raw counters."""
    from holo_tpu.resilience import health_snapshot

    return health_snapshot()
