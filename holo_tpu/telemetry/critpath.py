"""Critical-path ledger: cross-thread trigger→FIB waterfalls (ISSUE 17).

The convergence observatory (ISSUE 6) measures the trigger→FIB path
end-to-end and the dispatch observatory (ISSUE 12) attributes the
*device* slice — but ROADMAP item 5's claim is that under flap storms
the p99 is owned by *host choreography* (actor wake, queue wait,
marshal, force-wait, RIB sync), and nothing measured which host phase
owns each millisecond.  This module is that instrument: it joins the
per-event causal ids from :mod:`holo_tpu.telemetry.convergence`, the
profiling sub-spans (marshal / device / readback) from
:mod:`holo_tpu.telemetry.profiling`, and the queue-lifecycle stamps
from :mod:`holo_tpu.pipeline.dispatch` (enqueue, launch, finish,
force-wait, per-key ordering stalls) into one per-event cross-thread
**waterfall**, then decomposes every completed event into an
exhaustive, gap-free phase vector whose sum equals the end-to-end wall
*by construction*.

Phase taxonomy (the cut model)
------------------------------
Stamps are absolute reads of :func:`profiling.clock` (perf_counter in
production, the observatory's ``DeterministicTimer`` under ``explain``
— which is what makes the rendered waterfall byte-identical).  Per
event the stamps become an ordered sequence of *cuts*, each clamped
monotonically into ``[t_begin, t_end]``; phases are the differences
between consecutive cuts, so they telescope to the wall exactly:

    begin ──wake──▶ spf-scheduled ──coalesce_wait──▶ spf-run-begin
      ──coalesce_wait──▶ enqueue
      ──queue_wait──▶ marshal-begin ──marshal──▶ marshal-end
      ──device──▶ device-end ──force_wait──▶ force-end
      ──rib──▶ spf-observed ──rib──▶ rib-observed
      ──fib_commit──▶ fib/fallback-observed
      ──unattributed──▶ event-closed (= t_done)

A missing stamp collapses its phase to zero (the cut inherits its
predecessor): an un-pipelined dispatch has no enqueue/force stamps, so
coalesce_wait absorbs the SPF delay-FSM hold and queue_wait/force_wait
read zero; a BFD local-repair event with no SPF at all lands its wall
in rib + fib_commit.  ``rib`` spans from result availability to the
last RIB op — BOTH the host route derivation (scalar next-hop
extraction from the device result, the spf-observed waypoint) and the
publish/apply slice: that is the "RIB sync" item of ROADMAP item 5's
host-choreography list.  When the breaker's scalar fallback served the
event, the device segment and the derivation slice (which then holds
the scalar oracle's compute) relabel to ``fallback`` (chaos contract:
a forced breaker trip must show up there, an injected
``FaultPlan.dispatch_delay`` in ``device``, a queue stall in
``queue_wait`` — wrong-phase attribution is a test failure).  The
residual that no stamp explains is *reported*, never hidden: the
``unattributed`` phase is the closing segment past the last stamp — an
event with NO stamps at all books its whole wall there — held under 1%
of the wall at p50 by ``tests/test_critpath.py::
test_storm_delay_inflates_device_phase_digest_identical``.

What an event waits for (ISSUE 38).  ``coalesce_wait`` holds two
things: the **wait** in front of the SPF run that drains the event
(every delivery the loop makes while the delay timer is pending, and
the collector's pauses among them) and the run's own host work before
its first dispatch, the **prerun** (``topology``, ``link``).  The
``run0`` stamp, taken where :func:`convergence.spf_run` drains the
event, cuts the phase in two; the phase is the sum of the two slices,
so ``PHASES`` and every reading of them stay what they were.  Each
completed record carries the split under ``hold``: ``wait``,
``prerun`` and ``by``, the wait charged to the innermost spans that
ran in it (the difference of two :func:`profiling.account_at`
snapshots, which give the ``sched`` and ``run0`` stamps their times:
it sums to ``wait``; ``{}`` where device profiling is off, and where
the cut did not stand as stamped: a run that dispatched nothing has no
``coalesce_wait`` to cut).

Aggregation + sentinel
----------------------
Per-phase walls stream into DDSketch quantiles keyed
``(trigger, phase, engine, shape-bucket, kind)`` — the engine/bucket
labels ride in on :func:`profiling.dispatch_ctx` exactly like the
dispatch observatory's sketches.  Every event also gets a
deterministic **bound verdict** (``host`` / ``queue`` / ``device``,
largest share wins, ties break host > queue > device — the analogue of
the roofline ridge-point verdict).  When a dispatch observatory is
armed, every ``check_every`` completions the per-phase sketches run
through ITS perf-regression sentinel (`Observatory._sentinel_check`)
under ``critpath.<trigger>/<phase>|...`` ledger keys, so phase-level
regressions latch, flag, and ratchet with the same machinery and the
same ledger file as stage-level ones.

Armed/disarmed contract: off by default; every seam costs one
module-global ``None`` check while disarmed (``tests/test_critpath.py::
test_disarmed_seams_are_one_global_check``); no locks are taken on the
dispatch thread — records are plain dicts mutated under the GIL (the
DDSketch lock-free contract, see observatory.py).
"""

from __future__ import annotations

import logging
from collections import deque

from holo_tpu import telemetry
from holo_tpu.telemetry import convergence, profiling
from holo_tpu.telemetry.observatory import DDSketch

log = logging.getLogger("holo_tpu.telemetry")

#: exhaustive phase vector, in cut order (``fallback`` is the relabel
#: of device + route-derivation under a scalar-fallback verdict)
PHASES = (
    "wake", "coalesce_wait", "queue_wait", "marshal", "device",
    "force_wait", "rib", "fib_commit", "unattributed", "fallback",
)
#: verdict partition (host > queue > device on ties)
HOST_PHASES = (
    "wake", "coalesce_wait", "marshal", "rib", "fib_commit",
    "unattributed",
)
QUEUE_PHASES = ("queue_wait", "force_wait")
DEVICE_PHASES = ("device", "fallback")

#: profiling stage names folded into the marshal / device cuts
#: (``delta`` is the in-place incremental scatter — host marshal work;
#: ``solve`` is the partitioned block solve — device work)
_MARSHAL_STAGES = frozenset(("marshal", "delta"))
_DEVICE_STAGES = frozenset(("device", "readback", "solve"))

_VERDICTS = telemetry.counter(
    "holo_critpath_verdicts_total",
    "Completed trigger→FIB events by critical-path bound verdict",
    ("verdict",),
)
# Population gauges update on completion/stats only — stamped=False so
# ledger bookkeeping never wakes the gNMI fan-out walk (delta.py
# discipline, same as the observatory's gauges).
_OPEN = telemetry.gauge(
    "holo_critpath_open_events",
    "Causal events with an open critical-path record",
    stamped=False,
)
_SKETCHES_G = telemetry.gauge(
    "holo_critpath_sketches",
    "Live (trigger, phase, engine, shape-bucket, kind) phase sketches",
    stamped=False,
)


class _Rec:
    """One open event's stamp set.  Mutated lock-free: each field is
    written by exactly one logical stage of the event's life (the GIL
    makes the attribute stores atomic; a racing duplicate stamp
    resolves min/max-wards, inside the phase's own noise floor)."""

    __slots__ = (
        "trigger", "t0", "sched", "run0", "enqueue", "launch0", "marshal0",
        "marshal1", "device_end", "force1", "spf", "rib",
        "t_end", "stalls", "engine", "kind", "bucket", "acct0", "by",
    )

    def __init__(self, trigger: str, t0: float):
        self.trigger = trigger
        self.t0 = t0
        self.sched = None
        self.run0 = None
        self.enqueue = None
        self.launch0 = None
        self.marshal0 = None
        self.marshal1 = None
        self.device_end = None
        self.force1 = None
        self.spf = None
        self.rib = None
        self.t_end = None
        self.stalls = 0
        self.engine = "-"
        self.kind = "-"
        self.bucket = "-"
        # the thread's account (profiling.account_at) at the sched
        # stamp, and what it gained by the run0 stamp ({} while device
        # profiling is off)
        self.acct0 = None
        self.by: dict = {}


#: the phase each cut closes, in cut order: ``coalesce_wait`` and
#: ``rib`` are two slices each (the wait in front of the SPF run and
#: the run's host work before its first dispatch; route derivation and
#: publish + apply)
_CUT_PHASES = (
    "wake", "coalesce_wait", "coalesce_wait", "queue_wait", "marshal",
    "device", "force_wait", "rib", "rib", "fib_commit", "unattributed",
)
_WAIT, _PRERUN, _DERIVE = 1, 2, 7  # slices of _CUT_PHASES told apart


def _slices(rec: _Rec, t_done: float) -> list:
    """The cut model: clamped-monotone cuts → their consecutive
    differences, one per entry of ``_CUT_PHASES``.

    Every cut is forced into ``[previous cut, t_done]``, so the slices
    are non-negative and sum to ``t_done - t0`` exactly (each is an
    exact float difference of consecutive cuts).  A missing stamp
    inherits its predecessor (its slice reads zero); a missing ``run0``
    inherits its successor instead, the hold's end, so that the whole
    of ``coalesce_wait`` stays the wait it was before the cut existed."""
    mb = rec.marshal0 if rec.marshal0 is not None else rec.launch0
    # No pipeline ⇒ no enqueue stamp: the sched→marshal hold is the
    # SPF delay FSM coalescing triggers, so it books as coalesce_wait
    # (queue_wait then reads zero), not vice versa.
    hold_end = rec.enqueue if rec.enqueue is not None else mb
    # run0 cuts the hold and moves no phase: kept inside the hold's end
    run0 = rec.run0
    if run0 is None or hold_end is None or run0 > hold_end:
        run0 = hold_end
    cuts = (
        rec.sched,
        run0,
        hold_end,
        mb,
        rec.marshal1,
        rec.device_end,
        rec.force1,
        # rib spans BOTH slices of RIB sync: host route derivation
        # from the ready result (…→spf-observed) and route publish +
        # apply (…→rib-observed).
        rec.spf,
        rec.rib,
        rec.t_end,
        # The closing segment past the last stamp: an event that
        # converged with NO stamps books its whole wall here — the
        # honest "no stamp explains this" residual the storm test bounds.
        t_done,
    )
    prev = rec.t0
    out = []
    for c in cuts:
        c = prev if c is None else min(max(c, prev), t_done)
        out.append(c - prev)
        prev = c
    return out


def _fold(slices: list, fallback: bool) -> dict:
    """Slices → the telescoping phase dict."""
    phases = dict.fromkeys(PHASES, 0.0)
    for name, dt in zip(_CUT_PHASES, slices):
        phases[name] += dt
    if fallback:
        # The scalar oracle served this event: the device segment
        # (absent) plus the derivation slice — which then holds the
        # oracle's compute — are its phase, not a device/rib lie.
        phases["fallback"] = phases["device"] + slices[_DERIVE]
        phases["device"] = 0.0
        phases["rib"] -= slices[_DERIVE]
    return phases


def _decompose(rec: _Rec, t_done: float, fallback: bool) -> dict:
    return _fold(_slices(rec, t_done), fallback)


def _verdict(phases: dict) -> str:
    host = sum(phases[p] for p in HOST_PHASES)
    queue = sum(phases[p] for p in QUEUE_PHASES)
    device = sum(phases[p] for p in DEVICE_PHASES)
    # Deterministic tie-break: host > queue > device (>= comparisons).
    if host >= queue and host >= device:
        return "host"
    if queue >= device:
        return "queue"
    return "device"


class CritPathLedger:
    """Process-wide critical-path instrument (module singleton via
    :func:`configure`).  Hot path = the stamp methods below, fed by
    the convergence/profiling/dispatch hooks; everything else is cold
    reporting."""

    def __init__(
        self,
        capacity: int = 1024,
        check_every: int = 64,
        alpha: float = 0.01,
        max_bins: int = 512,
        waterfalls: int = 64,
    ):
        self.capacity = int(capacity)
        self.check_every = int(check_every)
        self.alpha = float(alpha)
        self.max_bins = int(max_bins)
        # eid -> _Rec; plain insertion-ordered dict, GIL-atomic ops
        # only (no locks on the dispatch thread — ISSUE 17 contract).
        self._recs: dict[int, _Rec] = {}
        self._sketches: dict[tuple, DDSketch] = {}
        self._water: deque = deque(maxlen=int(waterfalls))
        self._verdicts = {"host": 0, "queue": 0, "device": 0}
        self._completed = 0
        self._dropped = 0
        self._sheds = 0
        self._no_run_stamp = 0

    # -- hot path: stamps -----------------------------------------------

    def ev_begin(self, eid: int, trigger: str) -> None:
        rec = _Rec(trigger, profiling.clock())
        # Lock-free bounded map: setdefault/pop are GIL-atomic; a
        # racing begin for a distinct eid interleaves cleanly.
        self._recs[eid] = rec
        while len(self._recs) > self.capacity:
            try:
                self._recs.pop(next(iter(self._recs)))
                self._dropped += 1
            except (StopIteration, KeyError):  # racing pop emptied it
                break

    def ev_sched(self, eid: int) -> None:
        rec = self._recs.get(eid)
        if rec is not None and rec.sched is None:
            rec.sched, rec.acct0 = profiling.account_at(profiling.clock())

    def run_begin(self, eids) -> None:
        """The SPF run that drains ``eids`` begins: the cut inside
        ``coalesce_wait``, and what the loop's thread did since each
        event's sched stamp (the account is a thread's own, and an
        instance schedules and runs on its loop's thread)."""
        now, acct = profiling.account_at(profiling.clock())
        for eid in eids:
            rec = self._recs.get(eid)
            if rec is None or rec.run0 is not None:
                continue
            rec.run0 = now
            acct0 = rec.acct0
            if acct and acct0:
                rec.by = {
                    span: dt for span, total in acct.items()
                    if (dt := total - acct0.get(span, 0.0)) > 0.0
                }

    def ev_phase(self, eid: int, phase: str) -> None:
        rec = self._recs.get(eid)
        if rec is None:
            return
        now = profiling.clock()
        if phase == convergence.PHASE_SPF:
            if rec.spf is None:
                rec.spf = now
        elif phase == convergence.PHASE_RIB:
            if rec.rib is None:
                rec.rib = now
        else:  # fib / fallback: the closing cut
            if rec.t_end is None:
                rec.t_end = now

    def ev_done(self, eid: int, outcome: str, fallback: bool) -> None:
        rec = self._recs.pop(eid, None)
        if rec is None:
            return
        if outcome != "converged":
            self._dropped += 1
            return
        # Wall = trigger→fib-observed, same end cut as
        # holo_convergence_seconds; the close-time read only serves as
        # the end when the fib stamp is missing — in which case the
        # whole tail books as unattributed (residual by construction).
        t_done = rec.t_end
        if t_done is None:
            t_done = profiling.clock()
        t_done = max(t_done, rec.t0)
        slices = _slices(rec, t_done)
        phases = _fold(slices, fallback)
        if rec.run0 is None and (
            rec.enqueue is not None or rec.marshal0 is not None
            or rec.launch0 is not None
        ):
            self._no_run_stamp += 1  # a run nobody announced: never hidden
        # The account explains the wait it was taken over, sched to
        # run0: where the cut did not stand as stamped (clamped, or no
        # dispatch followed and the phase reads zero) it explains
        # nothing of this record.
        by = rec.by
        if by and slices[_WAIT] != rec.run0 - rec.sched:
            by = {}
        verdict = _verdict(phases)
        self._verdicts[verdict] += 1
        _VERDICTS.labels(verdict=verdict).inc()
        key4 = (rec.trigger, rec.engine, rec.bucket, rec.kind)
        for phase in PHASES:
            self._sketch(phase, key4).observe(phases[phase])
        self._sketch("wall", key4).observe(t_done - rec.t0)
        # deque.append with maxlen is GIL-atomic; the cold reader
        # copies via list() and tolerates a torn-window snapshot.
        self._water.append({  # holo-lint: disable=HL204
            "trigger": rec.trigger,
            "wall": round(t_done - rec.t0, 9),
            "phases": {p: round(phases[p], 9) for p in PHASES},
            "verdict": verdict,
            "engine": rec.engine,
            "kind": rec.kind,
            "bucket": rec.bucket,
            "stalls": rec.stalls,
            "fallback": bool(fallback),
            # coalesce_wait cut at the run's begin: wait + prerun is
            # the phase above, by sums to wait (to the rounding)
            "hold": {
                "wait": round(slices[_WAIT], 9),
                "prerun": round(slices[_PRERUN], 9),
                "by": {k: round(v, 9) for k, v in sorted(by.items())},
            },
        })
        self._completed += 1
        _OPEN.set(len(self._recs))
        if self.check_every and self._completed % self.check_every == 0:
            self._sentinel_pass()

    def _sketch(self, phase: str, key4: tuple) -> DDSketch:
        trigger, engine, bucket, kind = key4
        key = (trigger, phase, engine, bucket, kind)
        sk = self._sketches.get(key)
        if sk is None:
            # setdefault is GIL-atomic: two racing first-observers
            # both get the one surviving sketch (observatory idiom).
            sk = self._sketches.setdefault(  # holo-lint: disable=HL204
                key, DDSketch(self.alpha, self.max_bins)
            )
        return sk

    # profiling phase hook: fed every stage() begin/end edge while
    # armed.  Reads the clock itself; device != "-" rows are the
    # per-device skew split of one already-stamped sharded span.
    def _on_stage(self, site: str, name: str, device: str, edge: str) -> None:
        if device != "-":
            return
        if name in _MARSHAL_STAGES:
            eids = convergence.current()
            if not eids:
                return
            now = profiling.clock()
            for eid in eids:
                rec = self._recs.get(eid)
                if rec is None:
                    continue
                if edge == "b":
                    if rec.marshal0 is None:
                        rec.marshal0 = now
                elif rec.marshal1 is None or now > rec.marshal1:
                    rec.marshal1 = now
        elif name in _DEVICE_STAGES:
            eids = convergence.current()
            if not eids:
                return
            now = profiling.clock()
            ctx = profiling.dispatch_ctx() if edge == "b" else None
            for eid in eids:
                rec = self._recs.get(eid)
                if rec is None:
                    continue
                if edge == "e":
                    if rec.device_end is None or now > rec.device_end:
                        rec.device_end = now
                elif ctx is not None and rec.engine == "-":
                    rec.engine = str(ctx.get("engine", "-"))
                    rec.kind = str(ctx.get("kind", "-"))
                    rec.bucket = ctx.get("bucket") or "-"

    # dispatch queue-lifecycle stamps (module seams below fan in here)
    def note_enqueue(self, eids) -> None:
        now = profiling.clock()
        for eid in eids:
            rec = self._recs.get(eid)
            if rec is not None and rec.enqueue is None:
                rec.enqueue = now

    def note_launch(self, eids, edge: str) -> None:
        if edge != "b":
            return
        now = profiling.clock()
        for eid in eids:
            rec = self._recs.get(eid)
            if rec is not None and rec.launch0 is None:
                rec.launch0 = now

    def note_finish(self, eids, edge: str) -> None:
        if edge != "e":
            return
        now = profiling.clock()
        for eid in eids:
            rec = self._recs.get(eid)
            if rec is not None and (
                rec.device_end is None or now > rec.device_end
            ):
                rec.device_end = now

    def note_force(self, eids, edge: str) -> None:
        if edge != "e":
            return
        now = profiling.clock()
        for eid in eids:
            rec = self._recs.get(eid)
            if rec is not None and (rec.force1 is None or now > rec.force1):
                rec.force1 = now

    def note_stall(self, eids) -> None:
        for eid in eids:
            rec = self._recs.get(eid)
            if rec is not None:
                rec.stalls += 1

    def note_shed(self, eids) -> None:
        """Overload shed disposition: the dispatch never ran (capacity
        shed or deadline expiry), so the open records are discarded
        rather than decomposed — a shed event has no trigger→FIB wall.
        The tally is its own ledger line: sheds are a load-management
        verdict, not a tracker overflow (``dropped``)."""
        self._sheds += 1
        for eid in eids:
            self._recs.pop(eid, None)

    # -- sentinel (reuses the dispatch observatory's machinery) ---------

    def _sentinel_pass(self) -> None:
        from holo_tpu.telemetry import observatory

        obs = observatory.active()
        if obs is None:
            return
        for (trigger, phase, engine, bucket, kind), sk in list(
            self._sketches.items()
        ):
            if phase == "wall" or not sk.count:
                continue
            try:
                obs._sentinel_check(
                    (f"critpath.{trigger}", phase, engine, bucket, kind), sk
                )
            except Exception:  # noqa: BLE001 — warn-only by contract:
                # a sentinel bug must never propagate into the
                # fib_commit path that triggered this pass.
                log.debug("critpath sentinel pass failed", exc_info=True)
        _SKETCHES_G.set(len(self._sketches))

    def checkpoint(self) -> None:
        """Force one sentinel pass NOW (the explain CLI brackets its
        runs with it, same discipline as ``Observatory.checkpoint``)."""
        self._sentinel_pass()

    # -- cold reporting -------------------------------------------------

    def _merged_phase(self, phase: str) -> DDSketch:
        out = DDSketch(self.alpha, self.max_bins)
        for (t, p, e, b, k), sk in list(self._sketches.items()):
            if p == phase and sk.count:
                out.merge(sk)
        return out

    def phase_quantiles(self) -> dict:
        """{phase: {p50, p99, mean}} merged across all sketch keys
        (plus the ``wall`` pseudo-phase), rounded canonically."""
        out = {}
        for phase in (*PHASES, "wall"):
            sk = self._merged_phase(phase)
            if not sk.count:
                continue
            out[phase] = {
                "p50": round(sk.quantile(0.5), 9),
                "p99": round(sk.quantile(0.99), 9),
                "mean": round(sk.total / sk.count, 9),
            }
        return out

    def host_fraction_p99(self) -> float | None:
        """Σ host-phase p99 / Σ all-phase p99 — the scalar ROADMAP item
        5's streaming-convergence refactor must drive down."""
        q = self.phase_quantiles()
        total = sum(q[p]["p99"] for p in PHASES if p in q)
        if total <= 0.0:
            return None
        host = sum(q[p]["p99"] for p in HOST_PHASES if p in q)
        return round(host / total, 6)

    def unattributed_frac_p50(self) -> float | None:
        """unattributed p50 as a fraction of the wall p50 — the
        gap-free figure the storm test in ``tests/test_critpath.py``
        holds under 1%."""
        q = self.phase_quantiles()
        wall = q.get("wall")
        if not wall or wall["p50"] <= 0.0:
            return None
        un = q.get("unattributed", {"p50": 0.0})
        return round(un["p50"] / wall["p50"], 6)

    def waterfalls(self) -> list[dict]:
        """Most recent completed waterfalls, oldest first."""
        return [dict(w) for w in self._water]

    def stats(self) -> dict:
        """The ``holo-telemetry/critical-path`` gNMI leaf payload."""
        out = {
            "open": len(self._recs),
            "completed": self._completed,
            "dropped": self._dropped,
            "sheds": self._sheds,
            # completed events that went through an SPF run (a marshal
            # or queue stamp) which no run0 stamp announced
            "no_run_stamp": self._no_run_stamp,
            "capacity": self.capacity,
            "sketches": len(self._sketches),
            "verdicts": dict(self._verdicts),
            "phases": self.phase_quantiles(),
        }
        hf = self.host_fraction_p99()
        if hf is not None:
            out["host-fraction-p99"] = hf
        uf = self.unattributed_frac_p50()
        if uf is not None:
            out["unattributed-frac-p50"] = uf
        return out

    def report(self, top: int = 8) -> dict:
        """Deterministic report document (the ``explain
        --critical-path`` payload): phase table in cut order, verdict
        tally, and the last ``top`` per-event waterfalls.  Events are
        numbered by completion order WITHIN this report — raw eids are
        process-global counters and would break byte-identity across
        same-process runs (the storm-digest precedent)."""
        phases = self.phase_quantiles()
        rows = [
            {"phase": p, **phases[p]} for p in PHASES if p in phases
        ]
        total_p99 = sum(r["p99"] for r in rows)
        for r in rows:
            r["share_p99"] = (
                round(r["p99"] / total_p99, 6) if total_p99 > 0 else 0.0
            )
        water = self.waterfalls()[-int(top):] if int(top) > 0 else []
        return {
            "completed": self._completed,
            "dropped": self._dropped,
            "sheds": self._sheds,
            "verdicts": dict(self._verdicts),
            "phases": rows,
            "wall": phases.get("wall"),
            "host-fraction-p99": self.host_fraction_p99(),
            "unattributed-frac-p50": self.unattributed_frac_p50(),
            "events": [
                {"n": i, **w} for i, w in enumerate(water)
            ],
        }


# -- process-wide singleton + one-global-check seams ---------------------

_CP: CritPathLedger | None = None


def configure(
    capacity: int = 1024,
    check_every: int = 64,
    waterfalls: int = 64,
) -> CritPathLedger | None:
    """Arm (``capacity`` > 0) or disarm (0) the process-wide ledger and
    (un)install the convergence + profiling hooks.  Requires an armed
    convergence tracker to see any events (the causal ids are the join
    key); the dispatch observatory is optional (without it the phase
    sketches still aggregate — only the sentinel pass is skipped)."""
    global _CP
    if capacity and int(capacity) > 0:
        _CP = CritPathLedger(
            int(capacity), check_every=check_every, waterfalls=waterfalls
        )
        profiling.set_phase_hook(_CP._on_stage)
        convergence.set_critpath_hook(_CP)
        convergence.set_run_hook(_CP.run_begin)
    else:
        _CP = None
        profiling.set_phase_hook(None)
        convergence.set_critpath_hook(None)
        convergence.set_run_hook(None)
    return _CP


def active() -> CritPathLedger | None:
    return _CP


def enabled() -> bool:
    return _CP is not None


def note_enqueue(eids) -> None:
    """Dispatch-queue admission stamp (no-op while disarmed)."""
    cp = _CP
    if cp is None or not eids:
        return
    cp.note_enqueue(eids)


def note_launch(eids, edge: str) -> None:
    """Worker launch begin/end stamp (``edge`` = 'b' | 'e')."""
    cp = _CP
    if cp is None or not eids:
        return
    cp.note_launch(eids, edge)


def note_finish(eids, edge: str) -> None:
    """Worker finish begin/end stamp (``edge`` = 'b' | 'e')."""
    cp = _CP
    if cp is None or not eids:
        return
    cp.note_finish(eids, edge)


def note_force(eids, edge: str) -> None:
    """Force-seam (ticket result) wait begin/end stamp."""
    cp = _CP
    if cp is None or not eids:
        return
    cp.note_force(eids, edge)


def note_stall(eids) -> None:
    """Per-key ordering stall: a launchable item skipped because an
    earlier generation of its key is still in flight."""
    cp = _CP
    if cp is None or not eids:
        return
    cp.note_stall(eids)


def note_shed(eids) -> None:
    """Overload shed disposition (ISSUE 19).  No ``eids`` gate: a
    synthetic flood ticket carries none, but the shed itself must
    still land in the ledger tally."""
    cp = _CP
    if cp is None:
        return
    cp.note_shed(eids)
