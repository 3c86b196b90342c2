"""TPU-dispatch circuit breaker with bit-identical scalar fallback.

The device dispatch in ``spf/backend.py`` / ``frr/manager.py`` is the
one place where an external service (the XLA runtime / the device) can
fail underneath a routing computation.  The parity contract
(BASELINE.json, ``tests/test_spf_parity.py`` / ``test_frr_parity.py``)
proves the scalar oracle produces byte-identical output, so a failed or
overdue dispatch can be re-run on the host with NO observable change to
the RIB — the breaker makes that substitution automatic and bounded:

- **closed** — dispatches run on the device; an XLA exception falls
  back to the scalar oracle, a deadline overrun keeps the completed
  (identical) result, and both count as failures;
  ``failure_threshold`` consecutive failures open the circuit.
- **open** — dispatches go straight to the oracle (no device attempt)
  until ``recovery_timeout`` elapses.
- **half-open** — exactly one probe dispatch is allowed through; success
  closes the circuit (TPU service restored), failure re-opens it.

State is exported via Prometheus (``holo_resilience_breaker_*``) and the
``holo-telemetry`` health leaf (:func:`holo_tpu.resilience.health_snapshot`).
Thread-shared (instance threads under ``[runtime] isolation=threaded``
dispatch concurrently): state mutates under an owning lock, primary /
fallback callables always run outside it.
"""

from __future__ import annotations

import logging
import threading
import time
import weakref
from typing import Callable

from holo_tpu import telemetry
from holo_tpu.telemetry import flight

log = logging.getLogger("holo_tpu.resilience.breaker")

CLOSED = "closed"
OPEN = "open"
HALF_OPEN = "half-open"
_STATE_CODE = {CLOSED: 0, OPEN: 1, HALF_OPEN: 2}

_STATE = telemetry.gauge(
    "holo_resilience_breaker_state",
    "Dispatch circuit-breaker state (0=closed, 1=open, 2=half-open)",
    ("breaker",),
)
_TRANSITIONS = telemetry.counter(
    "holo_resilience_breaker_transitions_total",
    "Breaker state transitions by target state",
    ("breaker", "to"),
)
_FAILURES = telemetry.counter(
    "holo_resilience_breaker_failures_total",
    "Guarded dispatch failures by cause",
    ("breaker", "cause"),
)
_FALLBACKS = telemetry.counter(
    "holo_resilience_fallback_total",
    "Dispatches served by the scalar oracle instead of the device",
    ("breaker", "cause"),
)

# Live breakers for the health leaf; weak values so short-lived backend
# instances (tests) do not accumulate forever.  The lock guards
# the name-uniquify + insert pair: instance threads construct engines
# (and so breakers) concurrently under [runtime] isolation=threaded.
_REGISTRY: "weakref.WeakValueDictionary[str, CircuitBreaker]" = (
    weakref.WeakValueDictionary()
)
_REGISTRY_LOCK = threading.Lock()


def breakers() -> dict[str, "CircuitBreaker"]:
    """Snapshot of live breakers by name (health leaf / debugging)."""
    return dict(_REGISTRY)


class DeadlineOverrun(RuntimeError):
    """A guarded dispatch finished but blew its deadline budget."""


# Exception types that are never how a device failure presents at
# this boundary — they are plain programming or input errors, and the
# scalar fallback would either hit the identical bug or silently mask a
# real defect behind "device down" telemetry.  These re-raise.
_PASSTHROUGH = (TypeError, AttributeError, NameError, IndexError, KeyError)


# Process-wide defaults for breakers constructed without explicit
# parameters — protocol code builds its engines (and so its breakers)
# internally, so the daemon's [resilience] section lands here at boot.
_UNSET = object()
DEFAULTS = {
    "failure_threshold": 3,
    "recovery_timeout": 30.0,
    "deadline": None,
}


def configure_defaults(
    failure_threshold: int | None = None,
    recovery_timeout: float | None = None,
    deadline=_UNSET,
) -> None:
    """Update the process-wide breaker defaults (daemon boot only;
    already-built breakers keep their parameters)."""
    if failure_threshold is not None:
        DEFAULTS["failure_threshold"] = int(failure_threshold)
    if recovery_timeout is not None:
        DEFAULTS["recovery_timeout"] = float(recovery_timeout)
    if deadline is not _UNSET:
        DEFAULTS["deadline"] = deadline


class CircuitBreaker:
    """Guard one dispatch site; see module docstring for the FSM."""

    def __init__(
        self,
        name: str,
        failure_threshold: int | None = None,
        recovery_timeout: float | None = None,
        deadline=_UNSET,
        clock: Callable[[], float] = time.monotonic,
        enabled: bool = True,
    ):
        """``clock`` is injectable so virtual-clock tests drive recovery
        deterministically (pass ``loop.clock.now``).  ``deadline`` is a
        per-dispatch wall budget in clock units (None = no budget).
        ``enabled=False`` bypasses the breaker entirely; closed and
        enabled it costs a healthy dispatch two clock reads
        (tests/test_spf_parity.py::
        test_closed_breaker_costs_a_healthy_dispatch_two_clock_reads_and_no_oracle).
        Parameters
        left unset fall back to the process-wide :data:`DEFAULTS`."""
        # Unique registry/metric identity: several protocol instances
        # each build a default-named backend breaker ("spf-dispatch");
        # without disambiguation they would overwrite each other in the
        # health leaf and flap one shared state gauge.
        with _REGISTRY_LOCK:
            base, n = name, 2
            while name in _REGISTRY:
                name = f"{base}#{n}"
                n += 1
            self.name = name
            _REGISTRY[name] = self
        self.failure_threshold = int(
            failure_threshold
            if failure_threshold is not None
            else DEFAULTS["failure_threshold"]
        )
        self.recovery_timeout = float(
            recovery_timeout
            if recovery_timeout is not None
            else DEFAULTS["recovery_timeout"]
        )
        self.deadline = DEFAULTS["deadline"] if deadline is _UNSET else deadline
        self.enabled = enabled
        self._clock = clock
        self._lock = threading.Lock()
        self.state = CLOSED
        self.consecutive_failures = 0
        self.last_error: str | None = None
        self._open_until = 0.0
        self._probing = False
        _STATE.labels(breaker=name).set(_STATE_CODE[CLOSED])
        # The metrics registry has no series-removal API: when this
        # breaker dies (its backend was replaced), reset the state gauge
        # so a breaker that was OPEN at death cannot leave a perpetual
        # false "circuit open" alert on the scrape surface.
        weakref.finalize(
            self, _STATE.labels(breaker=name).set, _STATE_CODE[CLOSED]
        )

    # -- state bookkeeping (metrics emitted by the caller, outside _lock)

    def _transition_locked(self, to: str) -> None:
        self.state = to
        if to == OPEN:
            self._open_until = self._clock() + self.recovery_timeout

    def _emit(self, to: str) -> None:
        _STATE.labels(breaker=self.name).set(_STATE_CODE[to])
        _TRANSITIONS.labels(breaker=self.name, to=to).inc()
        # Flight-recorder forensics (no-ops while disarmed): every
        # transition lands in the ring; the open transition is a
        # postmortem trigger — the moment the device service was
        # declared down is exactly when the recent-span/journal context
        # is worth freezing to disk.
        flight.event("breaker", breaker=self.name, to=to)
        if to == OPEN:
            flight.trigger(
                f"breaker-open:{self.name}",
                extra={"last-error": self.last_error or ""},
            )

    def _admit(self) -> bool:
        """Decide whether this call may try the device.  Returns True to
        dispatch (closed, or the single half-open probe)."""
        emit = None
        with self._lock:
            if self.state == OPEN and self._clock() >= self._open_until:
                self._transition_locked(HALF_OPEN)
                self._probing = False
                emit = HALF_OPEN
            if self.state == CLOSED:
                admitted = True
            elif self.state == HALF_OPEN and not self._probing:
                self._probing = True
                admitted = True
            else:
                admitted = False
        if emit:
            self._emit(emit)
        return admitted

    def _on_failure(self, cause: str, error: BaseException) -> None:
        emit = None
        with self._lock:
            self.consecutive_failures += 1
            self.last_error = f"{cause}: {error!r}"
            if self.state == HALF_OPEN:
                # The probe failed: back to open for a fresh timeout.
                self._probing = False
                self._transition_locked(OPEN)
                emit = OPEN
            elif (
                self.state == CLOSED
                and self.consecutive_failures >= self.failure_threshold
            ):
                self._transition_locked(OPEN)
                emit = OPEN
        _FAILURES.labels(breaker=self.name, cause=cause).inc()
        if emit:
            self._emit(emit)
            log.error(
                "breaker %s OPEN after %d consecutive failures (%s); "
                "dispatches fall back to the scalar oracle for %.1fs",
                self.name, self.consecutive_failures, self.last_error,
                self.recovery_timeout,
            )
        else:
            log.warning(
                "breaker %s: dispatch failure %d/%d (%s)",
                self.name, self.consecutive_failures,
                self.failure_threshold, self.last_error,
            )

    def _abort_probe(self) -> None:
        """An admitted call exited without a device verdict (escaped
        passthrough exception or interrupt): release the half-open
        probe slot so the next call may probe again."""
        with self._lock:
            self._probing = False

    def _on_success(self) -> None:
        emit = None
        with self._lock:
            self.consecutive_failures = 0
            if self.state != CLOSED:
                self._probing = False
                self._transition_locked(CLOSED)
                emit = CLOSED
        if emit:
            self._emit(emit)
            log.info(
                "breaker %s: probe dispatch succeeded — device service "
                "restored (circuit closed)", self.name,
            )

    def force_failure(self, cause: str, error: BaseException) -> None:
        """Count a failure that produced no exception through a guard —
        a hung dispatch the watchdog abandoned is a device-service
        failure even though nothing raised.  Same FSM path as a
        guarded exception (half-open probe released, open-at-threshold)
        plus the fallback tally, since the caller is about to serve the
        scalar fallback."""
        if not self.enabled:
            return
        self._on_failure(cause, error)
        _FALLBACKS.labels(breaker=self.name, cause=cause).inc()

    # -- the guard

    def call(self, primary, fallback, context: str = ""):
        """Run ``primary`` under the breaker; on exception or an open
        circuit run ``fallback`` instead (a deadline overrun keeps the
        completed result but counts as a failure).  The contract that
        makes this transparent: ``fallback`` is the proven bit-identical
        oracle for the same inputs, so callers never see a different
        result — only different latency."""
        if not self.enabled:
            return primary()
        if not self._admit():
            _FALLBACKS.labels(breaker=self.name, cause="open").inc()
            return fallback()
        t0 = self._clock()
        try:
            result = primary()
        except _PASSTHROUGH:
            # A bug, not a device failure — never mask it.  But release
            # the probe slot: an escaped exception with no recorded
            # verdict would otherwise wedge half-open forever.
            self._abort_probe()
            raise
        except Exception as exc:
            self._on_failure("exception", exc)
            _FALLBACKS.labels(breaker=self.name, cause="exception").inc()
            return fallback()
        except BaseException:
            # KeyboardInterrupt/SystemExit: same probe-slot release.
            self._abort_probe()
            raise
        elapsed = self._clock() - t0
        if self.deadline is not None and elapsed > self.deadline:
            # The device answered, too late to be trusted as a service:
            # count the failure (this is how a degrading device opens the
            # circuit and future dispatches go scalar up front).  The
            # completed result is returned as-is — it is bit-identical
            # to the oracle's by the parity contract, and re-computing
            # it would double down on latency exactly when the deadline
            # was already missed.
            self._on_failure(
                "deadline", DeadlineOverrun(f"{elapsed:.3f}s > {self.deadline}s")
            )
            return result
        self._on_success()
        return result

    # -- split-phase guard (pipelined dispatch, ISSUE 9)

    def split(self, context: str = "") -> "SplitGuard":
        """The :meth:`call` contract unbundled for two-phase (launch /
        finish) dispatch: the async pipeline admits at launch time,
        reports a failure from either phase, and records success —
        with the deadline measured across BOTH phases — at finish.
        The caller owns running the fallback when not admitted or
        after a failure; see ``pipeline/dispatch.py``."""
        return SplitGuard(self, context)

    def snapshot(self) -> dict:
        """Health-leaf view (served under holo-telemetry/health)."""
        with self._lock:
            return {
                "state": self.state,
                "consecutive-failures": self.consecutive_failures,
                "failure-threshold": self.failure_threshold,
                "recovery-timeout": self.recovery_timeout,
                "last-error": self.last_error or "",
            }


class SplitGuard:
    """One guarded dispatch split across two phases (see
    :meth:`CircuitBreaker.split`).

    Lifecycle: construct (admits or refuses), then exactly one of
    :meth:`failure` / :meth:`success` / :meth:`abort`.  ``admitted``
    False means the circuit is open — the caller must serve the
    dispatch from the fallback (the ``cause="open"`` fallback counter
    has already been bumped, matching :meth:`CircuitBreaker.call`).  A
    disabled breaker admits unconditionally and records nothing.
    """

    __slots__ = ("breaker", "context", "admitted", "_t0", "_settled")

    def __init__(self, breaker: CircuitBreaker, context: str = ""):
        self.breaker = breaker
        self.context = context
        self._settled = breaker.enabled is False
        self._t0 = breaker._clock()
        if not breaker.enabled:
            self.admitted = True
        else:
            self.admitted = breaker._admit()
            if not self.admitted:
                _FALLBACKS.labels(breaker=breaker.name, cause="open").inc()
                self._settled = True

    def failure(self, exc: BaseException, cause: str = "exception") -> None:
        """A phase failed with a device-shaped error: count it (the
        caller then runs the bit-identical fallback)."""
        if self._settled:
            return
        self._settled = True
        self.breaker._on_failure(cause, exc)
        _FALLBACKS.labels(breaker=self.breaker.name, cause=cause).inc()

    def abort(self) -> None:
        """A passthrough (bug-class) exception escaped with no device
        verdict: release the half-open probe slot, record nothing."""
        if self._settled:
            return
        self._settled = True
        self.breaker._abort_probe()

    def success(self) -> None:
        """Both phases completed.  The deadline budget spans launch
        through finish — exactly the window :meth:`CircuitBreaker.call`
        measures around its primary."""
        if self._settled:
            return
        self._settled = True
        b = self.breaker
        elapsed = b._clock() - self._t0
        if b.deadline is not None and elapsed > b.deadline:
            b._on_failure(
                "deadline",
                DeadlineOverrun(f"{elapsed:.3f}s > {b.deadline}s"),
            )
            return
        b._on_success()
