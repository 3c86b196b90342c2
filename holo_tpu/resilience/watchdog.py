"""Hung-dispatch watchdog: budgeted walls for in-flight pipeline phases.

The breaker FSM (``resilience/breaker.py``) counts *exceptions* — a
device call that never returns (XLA compile stall, a wedged runtime
socket) produces no exception, so the single pipeline worker blocks
forever inside launch/finish while bounded-queue backpressure walls the
submitting protocol actors behind it.  This sentinel closes that gap:

- the worker stamps ``pipeline._active = (item, phase, since)`` around
  every launch/finish phase (one GIL-atomic tuple store, only when a
  watchdog is armed — the disarmed path never reads the clock);
- the watchdog compares each stamp's age against a per-site budget
  learned from the dispatch observatory's p99 sketches
  (:meth:`Observatory.site_p99` × ``multiplier``, floor-clamped; the
  floor alone when no observatory is armed or the site is cold);
- on an overrun it **abandons** the phase
  (:meth:`DispatchPipeline.abandon_active`: the wedged thread is
  disowned and exits at its next ownership check, the per-key donation
  token is released through the ``consumes_donated`` handoff seam),
  escalates the ticket's breaker via
  :meth:`CircuitBreaker.force_failure` (cause ``hang`` — a hang is a
  device-service failure even though no exception fired), serves the
  ticket from its bit-identical scalar fallback, and respawns the
  worker thread — through the installed ``on_worker_crash`` seam when
  the pipeline is supervised (``Supervisor.watch_worker``:
  RestartPolicy backoff + crash-loop degrade), directly otherwise.

The sentinel thread is itself respawnable (``respawn()`` +
``on_worker_crash``), so it rides the same ``Supervisor.watch_worker``
machinery as the pipeline worker it guards.

Chaos seam: ``FaultPlan.dispatch_hang`` wedges the worker inside the
``pipeline.launch`` / ``pipeline.finish`` hangpoints; the acceptance
contract is byte-identical correctness FIB digests versus the
unfaulted control (tests/test_overload.py::
test_watchdog_hang_mid_storm_fib_parity).
"""

from __future__ import annotations

import logging
import threading
import time

from holo_tpu import telemetry
from holo_tpu.telemetry import flight

log = logging.getLogger("holo_tpu.resilience.watchdog")

_HANGS = telemetry.counter(
    "holo_pipeline_watchdog_hangs_total",
    "In-flight pipeline phases abandoned by the hung-dispatch watchdog",
    ("phase",),
)
_BUDGET = telemetry.gauge(
    "holo_pipeline_watchdog_budget_seconds",
    "Hang budget the watchdog applied on its most recent verdict",
)


class WatchdogTimeout(RuntimeError):
    """An in-flight launch/finish phase overran its hang budget."""


class DispatchWatchdog:
    """Supervised sentinel for one :class:`DispatchPipeline`.

    ``multiplier``/``floor`` shape the budget: ``max(site_p99 *
    multiplier, floor)`` — the p99 comes from the armed dispatch
    observatory's per-(site, stage, shape-bucket) sketches (max across
    the site's keys: conservative, a hang is declared only well past
    the slowest bucket's tail), ``floor`` guards against cold sketches
    declaring hangs on the first warm-up dispatch.  ``clock`` is
    injectable for deterministic tests (the breaker precedent)."""

    def __init__(
        self,
        pipeline,
        interval: float = 0.25,
        multiplier: float = 4.0,
        floor: float = 5.0,
        clock=time.monotonic,
    ):
        self.pipeline = pipeline
        self.interval = float(interval)
        self.multiplier = float(multiplier)
        self.floor = float(floor)
        self._clock = clock
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self.hangs = 0
        # Supervision seam (Supervisor.watch_worker duck-type): set by
        # the supervisor; a sentinel-loop crash marshals through it.
        self.on_worker_crash = None

    @property
    def name(self) -> str:
        return f"watchdog:{self.pipeline.name}"

    # -- lifecycle ------------------------------------------------------

    def start(self) -> "DispatchWatchdog":
        """Arm the pipeline's phase stamps and spawn the sentinel."""
        self.pipeline.arm_watchdog(self._clock)
        self._spawn()
        return self

    def _spawn(self) -> None:
        self._thread = threading.Thread(
            target=self._sentinel, name=f"holo-{self.name}", daemon=True
        )
        self._thread.start()

    def respawn(self) -> bool:
        """Supervisor restart hook (``watch_worker`` duck-type)."""
        if self._stop.is_set():
            return False
        t = self._thread
        if t is not None and t.is_alive() and t is not threading.current_thread():
            return True
        self._spawn()
        return True

    def stop(self, timeout: float = 5.0) -> None:
        self._stop.set()
        self.pipeline.disarm_watchdog()
        t = self._thread
        if t is not None and t.is_alive():
            t.join(timeout)

    # -- sentinel -------------------------------------------------------

    def _sentinel(self) -> None:
        try:
            while not self._stop.wait(self.interval):
                self.check()
        except BaseException as exc:  # noqa: BLE001 — the sentinel must
            # never die silently: the pipeline it guards would be
            # unprotected with no signal anywhere.
            log.exception("dispatch watchdog %s crashed", self.name)
            flight.event("watchdog-crash", watchdog=self.name, error=repr(exc))
            cb = self.on_worker_crash
            if cb is not None:
                cb(exc)
            elif not self._stop.is_set():
                self._spawn()

    def budget(self, site: str | None) -> float:
        """Hang budget for ``site`` (floor-clamped observatory p99)."""
        base = None
        if site:
            from holo_tpu.telemetry import observatory

            obs = observatory.active()
            if obs is not None:
                base = obs.site_p99(site)
        if base is None:
            return self.floor
        return max(base * self.multiplier, self.floor)

    def check(self, now: float | None = None) -> bool:
        """One sentinel pass: True when a hang was declared and served.

        Tests drive this directly (no thread); the sentinel thread
        calls it every ``interval``."""
        pipe = self.pipeline
        active = pipe._active
        if active is None:
            return False
        item, phase, since = active
        if now is None:
            now = self._clock()
        budget = self.budget(item.site)
        if now - since < budget:
            return False
        return self._fire(item, phase, now - since, budget)

    def _fire(self, item, phase: str, age: float, budget: float) -> bool:
        if not self.pipeline.abandon_active(item, phase):
            return False  # the phase completed while we decided
        self.hangs += 1
        _HANGS.labels(phase=phase).inc()
        _BUDGET.set(budget)
        flight.event(
            "pipeline-hang",
            pipeline=self.pipeline.name, phase=phase,
            dispatch=item.kind, site=item.site or "-",
            age_s=round(age, 3), budget_s=round(budget, 3),
        )
        exc = WatchdogTimeout(
            f"{phase} phase for {item.key}/{item.kind} hung "
            f"{age:.3f}s (> budget {budget:.3f}s at site "
            f"{item.site or '-'})"
        )
        log.error("%s", exc)
        if item.breaker is not None:
            # A hang IS a device-service failure: strike the breaker so
            # repeated hangs open the circuit and dispatches go scalar
            # up front instead of each waiting out a budget.
            item.breaker.force_failure("hang", exc)
        # Serve the ticket NOW from the proven bit-identical fallback —
        # the protocol actor blocked on result() must not wait for the
        # respawned worker.  The wedged thread's eventual completion is
        # discarded by the ticket's first-settler claim.
        if item.fallback is not None:
            try:
                item.ticket._complete(item.fallback())
            except BaseException as fexc:  # noqa: BLE001 — marshaled to
                # the caller exactly like a worker-side failure.
                item.ticket._fail(fexc)
        else:
            item.ticket._fail(exc)
        # Fresh worker over the surviving queue: supervised pipelines
        # route through the RestartPolicy (backoff, crash-loop
        # degrade); bare ones respawn immediately.
        cb = self.pipeline.on_worker_crash
        if cb is not None:
            cb(exc)
        else:
            self.pipeline.respawn()
        return True

    def stats(self) -> dict:
        return {
            "pipeline": self.pipeline.name,
            "interval": self.interval,
            "multiplier": self.multiplier,
            "floor": self.floor,
            "hangs": self.hangs,
        }


# -- process-wide singleton (daemon boot from [pipeline] watchdog) ------

_WATCHDOG: DispatchWatchdog | None = None


def configure_process_watchdog(pipeline, **kw) -> DispatchWatchdog:
    """Arm the process-wide watchdog over ``pipeline`` (daemon boot;
    tests call directly).  Stops any previous sentinel first."""
    global _WATCHDOG
    if _WATCHDOG is not None:
        _WATCHDOG.stop()
    _WATCHDOG = DispatchWatchdog(pipeline, **kw).start()
    return _WATCHDOG


def process_watchdog() -> DispatchWatchdog | None:
    return _WATCHDOG


def reset_process_watchdog() -> None:
    """Stop + uninstall (test teardown)."""
    global _WATCHDOG
    if _WATCHDOG is not None:
        _WATCHDOG.stop()
        _WATCHDOG = None
