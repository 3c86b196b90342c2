"""SLO plane: error budgets + multi-window burn-rate sentinels (ISSUE 20).

ROADMAP items 1 and 5 both schedule "under a latency SLO", but until
this module the framework had only raw instruments — convergence
end-cuts, critpath phase vectors, shed counters, breaker/fallback
events — with no *objectives*, *budgets*, or *compliance verdicts*
attached.  This engine is that vocabulary: declared objectives grade
the existing streams into rolling good/bad counts, the counts become
error budgets, and budget spend-rate ("burn") is watched by the
classic multi-window sentinel so a breach pages once, early, and
warn-only.

Objective model
---------------
An :class:`Objective` declares WHAT is graded and HOW:

- ``kind="latency"`` — per-event grading of trigger→FIB end-cuts
  (``feed`` via :func:`note_endcut`, fed by the convergence tracker's
  ``fib_commit`` close under a one-global-check hook) or synthetic
  canary probes (:func:`note_probe`, fed by
  :mod:`holo_tpu.telemetry.canary`).  An event is *good* when its
  latency ≤ ``threshold_s`` (a fallback-served event can still be
  good: the oracle delivered — the fallback fraction is reported
  separately); the target quantile is what the threshold is meant to
  hold at (``target`` = the good-fraction objective, e.g. 0.999).
- ``kind="delivery"`` — per-ticket grading by dispatch priority class
  (:func:`note_served` / :func:`note_shed` from the pipeline's settle
  and shed paths): good = served, bad = shed.  The ``background``
  delivery objective is the canary's saturation signal — probes are
  background-class by design, so THEY are shed first and their shed
  rate is the first-class "the queue is full" indicator.

``source`` scopes the stream: a trigger class (``lsa``/``bfd``/…), a
priority class for delivery, or ``"*"``
(every trigger EXCEPT the canary's own — canary end-cuts ride the
storm's virtual clock and would dilute the production objective with
synthetic ≈0 walls; the canary grades through its own objective on
real probe walls).

Burn-rate math (the SRE standard, deterministic here)
-----------------------------------------------------
Events land in fixed-width buckets of the engine clock
(``fast_window / 60`` wide, trimmed past ``slow_window``).  For window
``W``: ``bad_frac = bad/(good+bad)`` over the buckets in ``[now−W,
now]`` and ``burn = bad_frac / (1 − target)`` — burn 1.0 spends
exactly the budget over the compliance window, burn 14.4 spends a
30-day budget in 50 hours (the classic fast-page threshold, the
default ``fast_burn``).  ``budget_remaining = 1 − bad_frac_slow /
(1 − target)`` clamped to [0, 1].  The clock is
:func:`profiling.clock` — perf_counter in production, the
observatory's ``DeterministicTimer`` under ``explain --slo``, which is
what makes the rendered report byte-identical.

The fast-window sentinel LATCHES: crossing ``fast_burn`` fires exactly
one ``holo_slo_sentinel_fires_total`` increment + one warn-only
``slo-burn`` flight event per excursion (re-arms when burn falls back
under), never a breaker, never a fallback — the observatory sentinel's
contract.  Latency sketches additionally seed ``slo.<objective>``
ledger rows through ``Observatory._sentinel_check`` at checkpoint, so
SLO latency regressions ratchet and flag with the same baseline
machinery and file as stage- and phase-level ones.

Armed/disarmed contract: off by default; every seam costs one
module-global ``None`` check while disarmed (poisoned-clock tests in
``tests/test_slo.py`` prove no clock read:
``test_disarmed_seams_are_one_global_check``,
``test_disarmed_pipeline_path_never_reads_slo_clock``).  No locks on the
feeding threads —
bucket dicts mutate under the GIL (the DDSketch lock-free contract,
see observatory.py).
"""

from __future__ import annotations

import logging
from dataclasses import dataclass

from holo_tpu import telemetry
from holo_tpu.telemetry import flight, profiling
from holo_tpu.telemetry.observatory import DDSketch

log = logging.getLogger("holo_tpu.telemetry")

#: objective kinds (closed set)
KINDS = ("latency", "delivery")
#: burn windows (names are the gauge label vocabulary)
WINDOWS = ("fast", "slow")

_BURN = telemetry.gauge(
    "holo_slo_burn_rate",
    "Error-budget burn rate per objective and window (1.0 spends the "
    "budget exactly over the compliance window)",
    ("objective", "window"),
    stamped=False,
)
_BUDGET = telemetry.gauge(
    "holo_slo_budget_remaining",
    "Fraction of the slow-window error budget left per objective",
    ("objective",),
    stamped=False,
)
_SENTINEL_FIRES = telemetry.counter(
    "holo_slo_sentinel_fires_total",
    "Burn-rate sentinel excursions per objective and window "
    "(latched: one fire per crossing, warn-only)",
    ("objective", "window"),
)


@dataclass(frozen=True)
class Objective:
    """One declared service-level objective (see module docstring)."""

    name: str
    kind: str = "latency"  # latency | delivery
    source: str = "*"  # trigger class | priority class | "*"
    quantile: float = 0.99
    threshold_s: float = 1.0
    target: float = 0.999

    def __post_init__(self):
        if self.kind not in KINDS:
            raise ValueError(f"unknown SLO kind {self.kind!r}")
        if not 0.0 < self.target < 1.0:
            raise ValueError(
                f"SLO target must be in (0, 1), got {self.target}"
            )
        if not 0.0 < self.quantile < 1.0:
            raise ValueError(
                f"SLO quantile must be in (0, 1), got {self.quantile}"
            )
        if self.threshold_s <= 0.0:
            raise ValueError(
                f"SLO threshold must be positive, got {self.threshold_s}"
            )

    @classmethod
    def from_config(cls, raw: dict) -> "Objective":
        """One ``[[telemetry.slo-objectives]]`` table (kebab keys)."""
        return cls(
            name=str(raw["name"]),
            kind=str(raw.get("kind", "latency")),
            source=str(raw.get("source", "*")),
            quantile=float(raw.get("quantile", 0.99)),
            threshold_s=float(raw.get("threshold-ms", 1000.0)) / 1e3,
            target=float(raw.get("target", 0.999)),
        )


def default_objectives() -> tuple[Objective, ...]:
    """The default set: production trigger→FIB latency, the canary,
    and the background delivery row that makes the canary's shed rate
    a budget instead of a counter."""
    return (
        # Production trigger→FIB latency: every convergence end-cut
        # (lsa/lsp/bfd/carrier/ifconfig) graded at p99.  The threshold
        # covers a full delay-FSM SPF under 10% loss (LONG_WAIT + one
        # LS-retransmit ≈ 10 s virtual) — a healthy seeded storm stays
        # in budget; deployments with FRR-flip expectations declare a
        # tighter objective in [telemetry] slo-objectives.
        Objective("trigger-fib", "latency", "*", 0.99, 15.0, 0.99),
        # The canary's own objective: black-box probe availability —
        # real (profiling-clock) trigger→FIB walls through the live
        # dispatch path, graded tighter than production.
        Objective("canary", "latency", "canary", 0.99, 0.25, 0.99),
        # Background admission: probes/advisories shed first under
        # pressure — their shed rate is the saturation budget.
        Objective("background-delivery", "delivery", "background",
                  0.99, 1.0, 0.99),
    )


class _ObjState:
    """Rolling state for one objective.  Mutated lock-free on the
    feeding threads (fib_commit path, pipeline worker, canary loop):
    bucket dict get/set and scalar adds are GIL-atomic; a racing
    increment coalescing one count is inside the budget math's own
    noise (the DDSketch argument, observatory.py)."""

    __slots__ = (
        "obj", "buckets", "sketch", "fallbacks", "events",
        "latched", "fires",
    )

    def __init__(self, obj: Objective, alpha: float, max_bins: int):
        self.obj = obj
        # bucket index -> [good, bad]
        self.buckets: dict[int, list] = {}
        self.sketch = DDSketch(alpha, max_bins)
        self.fallbacks = 0
        self.events = 0
        self.latched = {"fast": False, "slow": False}
        self.fires = {"fast": 0, "slow": 0}


class SloEngine:
    """Process-wide SLO engine (module singleton via :func:`configure`).
    Hot path = the ``note_*`` methods, fed by the convergence hook, the
    pipeline shed/settle seams, and the canary;
    everything else is cold reporting."""

    def __init__(
        self,
        objectives=None,
        clock=None,
        fast_window: float = 3600.0,
        slow_window: float = 86400.0,
        fast_burn: float = 14.4,
        slow_burn: float = 1.0,
        check_every: int = 16,
        alpha: float = 0.01,
        max_bins: int = 512,
    ):
        objs = tuple(objectives) if objectives else default_objectives()
        names = [o.name for o in objs]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate SLO objective names: {names}")
        self.fast_window = float(fast_window)
        self.slow_window = float(slow_window)
        if self.fast_window <= 0 or self.slow_window < self.fast_window:
            raise ValueError(
                "SLO windows must satisfy 0 < fast <= slow, got "
                f"{fast_window}/{slow_window}"
            )
        self.fast_burn = float(fast_burn)
        self.slow_burn = float(slow_burn)
        self.check_every = int(check_every)
        self.bucket_w = self.fast_window / 60.0
        self._clock = clock if clock is not None else profiling.clock
        self._states = {
            o.name: _ObjState(o, alpha, max_bins) for o in objs
        }
        # routing tables: feed -> matching states (computed once so the
        # hot path is a tuple walk, not a per-note objective scan)
        self._latency_any = tuple(
            s for s in self._states.values()
            if s.obj.kind == "latency" and s.obj.source == "*"
        )
        self._latency_by_src: dict[str, tuple] = {}
        for s in self._states.values():
            if s.obj.kind == "latency" and s.obj.source != "*":
                self._latency_by_src.setdefault(s.obj.source, ())
                self._latency_by_src[s.obj.source] += (s,)
        self._delivery_by_cls = {
            s.obj.source: s
            for s in self._states.values() if s.obj.kind == "delivery"
        }
        self._sheds: dict[tuple, int] = {}  # (class, reason) -> count
        self._notes = 0

    # -- hot path: grading ----------------------------------------------

    def _grade(self, st: _ObjState, good: bool, now: float) -> None:
        b = self.buckets_for(st, now)
        b[0 if good else 1] += 1
        st.events += 1
        self._notes += 1
        if not good or (
            self.check_every
            and self._notes % self.check_every == 0
        ):
            self._check(st, now)

    def buckets_for(self, st: _ObjState, now: float) -> list:
        i = int(now // self.bucket_w)
        b = st.buckets.get(i)
        if b is None:
            # setdefault is GIL-atomic: two racing first-graders both
            # land in the one surviving bucket (observatory idiom).
            b = st.buckets.setdefault(i, [0, 0])
            if len(st.buckets) > 2 * int(self.slow_window / self.bucket_w) + 4:
                self._trim(st, now)
        return b

    def _trim(self, st: _ObjState, now: float) -> None:
        floor = int((now - self.slow_window) // self.bucket_w)
        for i in [i for i in st.buckets if i < floor]:
            st.buckets.pop(i, None)

    def note_endcut(self, trigger: str, seconds: float, fallback: bool) -> None:
        """One trigger→FIB end-cut (the convergence tracker's close
        hook; latency on the TRACKER's clock — virtual in storms)."""
        if trigger == "canary":
            # Canary end-cuts ride the tracker's possibly-VIRTUAL clock
            # (a storm's 5 s SPF-delay wait would grade as a 5 s probe);
            # the canary objective grades only the real profiling-clock
            # walls note_probe delivers.
            return
        now = self._clock()
        states = self._latency_by_src.get(trigger, ()) + self._latency_any
        for st in states:
            st.sketch.observe(max(seconds, 0.0))
            if fallback:
                st.fallbacks += 1
            self._grade(st, seconds <= st.obj.threshold_s, now)

    def note_probe(self, ok: bool, seconds: float | None) -> None:
        """One synthetic canary probe verdict (canary.py's close; the
        probe latency is a REAL profiling-clock wall)."""
        now = self._clock()
        for st in self._latency_by_src.get("canary", ()):
            good = bool(ok)
            if seconds is not None:
                st.sketch.observe(max(seconds, 0.0))
                good = good and seconds <= st.obj.threshold_s
            self._grade(st, good, now)

    def note_served(self, cls: str) -> None:
        """One pipeline ticket settled successfully, by class."""
        st = self._delivery_by_cls.get(cls)
        if st is not None:
            self._grade(st, True, self._clock())

    def note_shed(self, cls: str, reason: str) -> None:
        """One pipeline ticket shed (capacity eviction or deadline
        expiry), by class — the saturation stream."""
        key = (cls, reason)
        # GIL-atomic read-add-store; a racing shed coalescing one count
        # is inside the saturation signal's noise.
        self._sheds[key] = self._sheds.get(key, 0) + 1  # holo-lint: disable=HL204
        st = self._delivery_by_cls.get(cls)
        if st is not None:
            self._grade(st, False, self._clock())

    # -- burn math ------------------------------------------------------

    def _bad_frac(self, st: _ObjState, now: float, window: float):
        """(bad_fraction, good, bad) over ``[now - window, now]``;
        ``None`` fraction when the window saw no events."""
        lo = int((now - window) // self.bucket_w)
        good = bad = 0
        for i, b in list(st.buckets.items()):
            if i >= lo:
                good += b[0]
                bad += b[1]
        if good + bad == 0:
            return None, 0, 0
        return bad / (good + bad), good, bad

    def burn(self, st: _ObjState, now: float, window: float) -> float | None:
        frac, _g, _b = self._bad_frac(st, now, window)
        if frac is None:
            return None
        return frac / max(1.0 - st.obj.target, 1e-9)

    def budget_remaining(self, st: _ObjState, now: float) -> float | None:
        frac, _g, _b = self._bad_frac(st, now, self.slow_window)
        if frac is None:
            return None
        spent = frac / max(1.0 - st.obj.target, 1e-9)
        return min(max(1.0 - spent, 0.0), 1.0)

    # -- sentinel -------------------------------------------------------

    def _check(self, st: _ObjState, now: float) -> None:
        for window, span, limit in (
            ("fast", self.fast_window, self.fast_burn),
            ("slow", self.slow_window, self.slow_burn),
        ):
            b = self.burn(st, now, span)
            if b is None:
                continue
            _BURN.labels(objective=st.obj.name, window=window).set(b)
            breached = b > limit
            if breached and not st.latched[window]:
                # Latch: one fire per excursion.  GIL-atomic bool flip
                # (single-writer per feeding path; a racing double-fire
                # window is the same one the observatory accepts).
                st.latched[window] = True
                st.fires[window] += 1
                _SENTINEL_FIRES.labels(
                    objective=st.obj.name, window=window
                ).inc()
                flight.event(
                    "slo-burn",
                    objective=st.obj.name,
                    window=window,
                    burn=round(b, 3),
                    limit=limit,
                )
                log.warning(
                    "slo: objective %r %s-window burn %.2f exceeds %.2f "
                    "— warn-only, dispatch unaffected",
                    st.obj.name, window, b, limit,
                )
            elif not breached and st.latched[window]:
                st.latched[window] = False
        rem = self.budget_remaining(st, now)
        if rem is not None:
            _BUDGET.labels(objective=st.obj.name).set(rem)

    def checkpoint(self) -> None:
        """Force one sentinel pass over every objective NOW, trim the
        bucket tails, and seed latency ``slo.<objective>`` rows through
        the dispatch observatory's baseline machinery (when armed) —
        the explain CLI's bracket, same discipline as
        ``Observatory.checkpoint``."""
        from holo_tpu.telemetry import observatory

        now = self._clock()
        obs = observatory.active()
        for st in self._states.values():
            self._trim(st, now)
            self._check(st, now)
            if obs is not None and st.sketch.count:
                try:
                    obs._sentinel_check(
                        (f"slo.{st.obj.name}", "latency", "-", "-", "-"),
                        st.sketch,
                    )
                except Exception:  # noqa: BLE001 — warn-only by
                    # contract: a ledger bug must never propagate into
                    # the path that triggered this checkpoint.
                    log.debug("slo sentinel pass failed", exc_info=True)

    # -- cold reporting -------------------------------------------------

    def _objective_row(self, st: _ObjState, now: float) -> dict:
        o = st.obj
        fast_frac, fg, fb = self._bad_frac(st, now, self.fast_window)
        slow_frac, sg, sb = self._bad_frac(st, now, self.slow_window)
        row = {
            "objective": o.name,
            "kind": o.kind,
            "source": o.source,
            "target": o.target,
            "threshold_ms": round(o.threshold_s * 1e3, 3),
            "quantile": o.quantile,
            "events": st.events,
            "good_fast": fg,
            "bad_fast": fb,
            "good_slow": sg,
            "bad_slow": sb,
            "burn_fast": (
                round(self.burn(st, now, self.fast_window), 6)
                if fast_frac is not None else None
            ),
            "burn_slow": (
                round(self.burn(st, now, self.slow_window), 6)
                if slow_frac is not None else None
            ),
            "budget_remaining": (
                round(self.budget_remaining(st, now), 6)
                if slow_frac is not None else None
            ),
            "sentinel_fires_fast": st.fires["fast"],
            "sentinel_fires_slow": st.fires["slow"],
            "latched_fast": bool(st.latched["fast"]),
        }
        if o.kind == "latency":
            row["fallbacks"] = st.fallbacks
            if st.sketch.count:
                row["measured_ms"] = {
                    "p50": round((st.sketch.quantile(0.5) or 0.0) * 1e3, 3),
                    f"p{round(o.quantile * 100)}": round(
                        (st.sketch.quantile(o.quantile) or 0.0) * 1e3, 3
                    ),
                    "p99": round((st.sketch.quantile(0.99) or 0.0) * 1e3, 3),
                }
        return row

    def report(self) -> dict:
        """Deterministic report document (the ``explain --slo``
        payload): one row per objective in declaration order, plus the
        shed-by-(class, reason) saturation tally.  Byte-identical
        across same-seed runs under the DeterministicTimer."""
        now = self._clock()
        return {
            "windows": {
                "fast_s": self.fast_window,
                "slow_s": self.slow_window,
                "fast_burn_limit": self.fast_burn,
                "slow_burn_limit": self.slow_burn,
            },
            "objectives": [
                self._objective_row(st, now)
                for st in self._states.values()
            ],
            "sheds": {
                f"{cls}/{reason}": n
                for (cls, reason), n in sorted(self._sheds.items())
            },
        }

    def stats(self) -> dict:
        """The ``holo-telemetry/slo`` gNMI leaf payload."""
        now = self._clock()
        out = {"objectives": {}, "sheds": {}}
        for st in self._states.values():
            b = self.burn(st, now, self.fast_window)
            rem = self.budget_remaining(st, now)
            out["objectives"][st.obj.name] = {
                "kind": st.obj.kind,
                "events": st.events,
                "burn-fast": round(b, 6) if b is not None else None,
                "budget-remaining": (
                    round(rem, 6) if rem is not None else None
                ),
                "sentinel-fires": st.fires["fast"] + st.fires["slow"],
            }
        for (cls, reason), n in sorted(self._sheds.items()):
            out["sheds"][f"{cls}/{reason}"] = n
        return out

    def objective(self, name: str) -> _ObjState | None:
        """Test/CLI surface: the state for one objective."""
        return self._states.get(name)


# -- process-wide singleton + one-global-check seams ---------------------

_SLO: SloEngine | None = None


def configure(enabled=True, objectives=None, **kw) -> SloEngine | None:
    """Arm (truthy ``enabled``) or disarm (falsy) the process-wide
    engine and (un)install the convergence end-cut hook.  ``kw`` passes
    through to :class:`SloEngine` (clock/windows/burn limits)."""
    global _SLO
    from holo_tpu.telemetry import convergence

    if enabled:
        _SLO = SloEngine(objectives=objectives, **kw)
        convergence.set_slo_hook(_SLO)
    else:
        _SLO = None
        convergence.set_slo_hook(None)
    return _SLO


def active() -> SloEngine | None:
    return _SLO


def enabled() -> bool:
    return _SLO is not None


def note_probe(ok: bool, seconds: float | None = None) -> None:
    """Canary probe verdict (no-op while disarmed)."""
    sl = _SLO
    if sl is None:
        return
    sl.note_probe(ok, seconds)


def note_served(cls: str) -> None:
    """Pipeline ticket served, by class (no-op while disarmed)."""
    sl = _SLO
    if sl is None:
        return
    sl.note_served(cls)


def note_shed(cls: str, reason: str) -> None:
    """Pipeline ticket shed, by class + reason (no-op while disarmed)."""
    sl = _SLO
    if sl is None:
        return
    sl.note_shed(cls, reason)
