"""Convergence observatory: causal event→FIB tracing.

The quantity the ROADMAP's perf arc is graded by — how long the network
takes to converge after a topology event — was invisible before this
module: PR 2/5 instrumented individual dispatches, but nothing joined a
*cause* (an LSA/LSP arrival, a BFD session dropping, carrier loss, an
interface config change) to its *effect* (the kernel FIB reflecting the
new topology).  This module stamps every topology-changing event with a
causal ``event_id`` at its origin and rides it through the whole chain:

    origin (protocol/BFD/ibus)          convergence.begin(trigger)
      → ibus publish                    IbusMsg.event_id (captured)
      → actor processing                EventLoop delivery context hook
      → SPF-delay FSM + dispatch        instance pend/drain + observe("spf")
      → RIB route ops                   observe("rib")
      → kernel FIB install / FRR flip   fib_commit() → observe("fib")

Each phase records a ``holo_convergence_seconds{trigger,phase}``
histogram observation with an OpenMetrics exemplar (the active trace
span id when one exists, the event id otherwise), so a scrape can jump
from a latency bucket to the trace that produced it; the per-event
causal **timeline** (origin, marks, dispatch sites with their span ids
— joining the marshal/device/readback sub-spans from
:mod:`holo_tpu.telemetry.profiling` — and the closing FIB commit) lands
in the flight-recorder ring on completion, so postmortem bundles carry
the last convergence stories leading up to a failure.

Dispatch attribution: the SPF/FRR backends call :func:`note_dispatch`
with the mode that actually served the computation (``device`` /
``scalar`` / ``fallback``).  An event served by the breaker's scalar
fallback closes with ``phase="fallback"`` instead of ``"fib"`` — the
storm report splits its distributions on exactly this.

Everything is **off by default**: the hot-path cost while disarmed is
one module-global ``None`` check per seam (``[telemetry]
convergence-events`` arms it in the daemon; the benchmark and tests call
:func:`configure` directly with the loop clock, which makes every
timeline and latency deterministic under the virtual clock).
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict, deque
from contextlib import contextmanager, nullcontext

from holo_tpu import telemetry
from holo_tpu.telemetry import flight

#: trigger classes (open set — these are the documented ones)
TRIGGER_LSA = "lsa"  # OSPF LSA arrival/change
TRIGGER_LSP = "lsp"  # IS-IS LSP arrival/change
TRIGGER_BFD = "bfd"  # BFD session state change
TRIGGER_CARRIER = "carrier"  # interface operational/carrier change
TRIGGER_IFCONFIG = "ifconfig"  # interface/instance config change

#: phases observed on holo_convergence_seconds (origin → phase end)
PHASE_SPF = "spf"  # SPF/route computation finished
PHASE_RIB = "rib"  # first RIB route operation applied
PHASE_FIB = "fib"  # first kernel FIB commit (event complete)
PHASE_FALLBACK = "fallback"  # FIB commit served via scalar fallback

# Convergence latencies span one virtual-clock instant (an O(1) FRR
# flip) to tens of seconds (LONG_WAIT SPF delays + retransmits under
# loss) — the default log-spaced bucket ladder covers exactly that.
_CONV_SECONDS = telemetry.histogram(
    "holo_convergence_seconds",
    "Topology-event to FIB convergence latency, by causal phase",
    ("trigger", "phase"),
)
_CONV_EVENTS = telemetry.counter(
    "holo_convergence_events_total",
    "Causal convergence events, by trigger class and outcome",
    ("trigger", "outcome"),
)

#: per-event timeline entries kept before the tail is dropped
TIMELINE_LIMIT = 64

# Critical-path ledger hook (ISSUE 17): while armed, event lifecycle
# moments (begin / spf-scheduled / phase observed / finish) are ALSO
# stamped into holo_tpu.telemetry.critpath's cross-thread waterfall.
# One module global, installed only by critpath.configure — the
# disarmed cost at every seam is exactly this None check.  The hook
# keeps its OWN clock (profiling.clock): the tracker's clock may be a
# storm's virtual loop clock, under which host compute is invisible.
_CP_HOOK = None


def set_critpath_hook(ledger) -> None:
    """Install/remove the critical-path ledger
    (:func:`holo_tpu.telemetry.critpath.configure` is the only
    caller); ``None`` disarms."""
    global _CP_HOOK
    _CP_HOOK = ledger


# SPF-run-begin hook (ISSUE 38): the ledger's ``run0`` stamp, taken
# where spf_run drains an instance's pending ids (the seam OSPFv2,
# OSPFv3 and IS-IS share; the ``ospf.spf.run`` stage's begin edge would
# have served OSPF alone: IS-IS books no host stage).  A seam of its
# own and not a method of _CP_HOOK: the benchmark lays its own object
# over that one, which forwards the four calls it knows.  Same
# contract: one module global, installed only by critpath.configure,
# one None check when disarmed.
_RUN_HOOK = None


def set_run_hook(fn) -> None:
    """Install/remove the SPF-run-begin hook ``fn(eids)``
    (:func:`holo_tpu.telemetry.critpath.configure` is the only
    caller); ``None`` disarms."""
    global _RUN_HOOK
    _RUN_HOOK = fn


# SLO-engine hook (ISSUE 20): while armed, every fib_commit close ALSO
# grades the event's end-cut latency against the declared objectives in
# holo_tpu.telemetry.slo.  Same contract as _CP_HOOK: one module
# global, installed only by slo.configure, a single None check when
# disarmed — and the clock is read ONLY under a non-None hook, so the
# disarmed path stays byte-identical (poisoned-clock tested).
_SLO_HOOK = None


def set_slo_hook(engine) -> None:
    """Install/remove the SLO engine
    (:func:`holo_tpu.telemetry.slo.configure` is the only caller);
    ``None`` disarms."""
    global _SLO_HOOK
    _SLO_HOOK = engine


class _Event:
    """One open causal event (mutated only under the tracker lock)."""

    __slots__ = (
        "eid", "trigger", "t0", "attrs", "observed", "dispatch",
        "fallback", "timeline", "truncated",
    )

    def __init__(self, eid: int, trigger: str, t0: float, attrs: dict):
        self.eid = eid
        self.trigger = trigger
        self.t0 = t0
        self.attrs = attrs
        self.observed: set[str] = set()
        self.dispatch: dict[str, str] = {}  # site -> device|scalar|fallback
        self.fallback = False
        self.timeline: list = []
        self.truncated = 0


class ConvergenceTracker:
    """Process-wide causal event tracker (module singleton via
    :func:`configure`).

    Open events live in a bounded insertion-ordered map (an event storm
    cannot grow memory without limit: the oldest open event is closed as
    ``outcome="evicted"`` when a new one would exceed ``capacity``);
    completed timelines keep the most recent ``capacity`` entries.
    """

    def __init__(self, capacity: int = 512, clock=time.monotonic):
        self.capacity = int(capacity)
        self._clock = clock
        self._lock = threading.Lock()
        self._next = 1
        self._open: "OrderedDict[int, _Event]" = OrderedDict()
        self._done: deque = deque(maxlen=self.capacity)
        self._tls = threading.local()
        self._completed = 0

    # -- context (threadlocal active-event stack)

    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def current(self) -> tuple[int, ...]:
        st = getattr(self._tls, "stack", None)
        return st[-1] if st else ()

    @contextmanager
    def activation(self, eids: tuple[int, ...]):
        """Make ``eids`` the active causal context for the dynamic
        extent (nested activations stack; the delivery hook uses this to
        re-establish context when a message carrying event ids is
        handled on another actor/thread)."""
        st = self._stack()
        st.append(tuple(eids))
        try:
            yield
        finally:
            st.pop()

    # -- recording

    def begin(self, trigger: str, **attrs) -> int:
        """Stamp a new causal event at its origin; returns its id."""
        t = self._clock()
        clean = {str(k): str(v) for k, v in sorted(attrs.items())}
        evicted: _Event | None = None
        with self._lock:
            eid = self._next
            self._next += 1
            ev = _Event(eid, str(trigger), t, clean)
            ev.timeline.append(("origin", 0.0, clean))
            self._open[eid] = ev
            if len(self._open) > self.capacity:
                _, evicted = self._open.popitem(last=False)
        cp = _CP_HOOK
        if cp is not None:
            cp.ev_begin(eid, str(trigger))
        if evicted is not None:
            self._finish(evicted, "evicted")
        _CONV_EVENTS.labels(trigger=trigger, outcome="begun").inc()
        return eid

    def _events(self, eids) -> list[_Event]:
        with self._lock:
            return [ev for e in eids if (ev := self._open.get(e)) is not None]

    def _entry(self, ev: _Event, step: str, attrs: dict) -> None:
        """Append one timeline entry (caller holds no lock)."""
        t = round(self._clock() - ev.t0, 9)
        with self._lock:
            if len(ev.timeline) >= TIMELINE_LIMIT:
                ev.truncated += 1
                return
            ev.timeline.append((step, t, attrs))

    def mark(self, step: str, eids=None, **attrs) -> None:
        """Timeline-only entry for the active (or given) events."""
        clean = {str(k): str(v) for k, v in sorted(attrs.items())}
        for ev in self._events(eids if eids is not None else self.current()):
            self._entry(ev, step, clean)

    def note_dispatch(self, site: str, mode: str) -> None:
        """Record which engine served a dispatch for the active events
        (``device`` / ``scalar`` / ``fallback``), joining the profiling
        sub-spans via the enclosing dispatch span id."""
        eids = self.current()
        if not eids:
            return
        sid = telemetry.current_span_id()
        attrs = {"site": site, "mode": mode}
        if sid is not None:
            attrs["span_id"] = str(sid)
        for ev in self._events(eids):
            with self._lock:
                ev.dispatch[site] = mode
                if mode == "fallback":
                    ev.fallback = True
            self._entry(ev, "dispatch", attrs)

    def observe(self, phase: str, eids=None, **attrs) -> None:
        """Histogram observation ``now - origin`` for each event that
        has not seen ``phase`` yet, with a span/event exemplar."""
        now = self._clock()
        clean = {str(k): str(v) for k, v in sorted(attrs.items())}
        sid = telemetry.current_span_id()
        for ev in self._events(eids if eids is not None else self.current()):
            with self._lock:
                if phase in ev.observed:
                    fresh = False
                else:
                    ev.observed.add(phase)
                    fresh = True
            if not fresh:
                continue
            cp = _CP_HOOK
            if cp is not None:
                cp.ev_phase(ev.eid, phase)
            exemplar = (
                {"span_id": sid} if sid is not None else {"event_id": ev.eid}
            )
            _CONV_SECONDS.labels(trigger=ev.trigger, phase=phase).observe(
                max(now - ev.t0, 0.0), exemplar=exemplar
            )
            self._entry(ev, phase, clean)

    def fib_commit(self, op: str = "install", eids=None, **attrs) -> None:
        """The FIB moment: observe the event-to-FIB total (phase
        ``fib``, or ``fallback`` when a scalar fallback served the
        computation) and complete the event — its causal timeline is
        flushed to the flight-recorder ring."""
        to_close: list[_Event] = []
        use = eids if eids is not None else self.current()
        for ev in self._events(use):
            with self._lock:
                phase = PHASE_FALLBACK if ev.fallback else PHASE_FIB
            self.observe(phase, eids=(ev.eid,), op=op, **attrs)
            sl = _SLO_HOOK
            if sl is not None:
                # End-cut on the TRACKER's clock (virtual in storms) —
                # the latency the convergence histogram itself records.
                sl.note_endcut(
                    ev.trigger, max(self._clock() - ev.t0, 0.0), ev.fallback
                )
            with self._lock:
                if self._open.pop(ev.eid, None) is not None:
                    to_close.append(ev)
        for ev in to_close:
            self._finish(ev, "converged")

    def sweep(self) -> int:
        """Close every still-open event (storm settle / shutdown): no
        histogram observation — an event that never touched the FIB is
        a no-op convergence-wise — but the timeline still flushes so
        the ring shows what it did do.  Returns the count closed."""
        with self._lock:
            evs = list(self._open.values())
            self._open.clear()
        for ev in evs:
            self._finish(ev, "no-fib")
        return len(evs)

    def _finish(self, ev: _Event, outcome: str) -> None:
        with self._lock:
            record = {
                "eid": ev.eid,
                "trigger": ev.trigger,
                "outcome": outcome,
                "fallback": ev.fallback,
                "dispatch": dict(ev.dispatch),
                "timeline": list(ev.timeline),
                "truncated": ev.truncated,
            }
            self._done.append(record)
            self._completed += 1
        cp = _CP_HOOK
        if cp is not None:
            cp.ev_done(ev.eid, outcome, ev.fallback)
        _CONV_EVENTS.labels(trigger=ev.trigger, outcome=outcome).inc()
        # Ring entry outside our lock (the flight recorder locks its
        # own ring); disarmed flight makes this a no-op.
        flight.event(
            "convergence",
            eid=ev.eid,
            trigger=ev.trigger,
            outcome=outcome,
            fallback=ev.fallback,
            phases=",".join(
                f"{s}@{t}" for s, t, _ in record["timeline"][:TIMELINE_LIMIT]
            ),
        )

    # -- queries

    def timelines(self) -> list[dict]:
        """Completed event records, oldest first (storm/test surface)."""
        with self._lock:
            return [dict(r) for r in self._done]

    def stats(self) -> dict:
        """holo-telemetry state-leaf view."""
        with self._lock:
            return {
                "open": len(self._open),
                "completed": self._completed,
                "capacity": self.capacity,
            }


# -- process-wide singleton + module-level seams ------------------------

_TRACKER: ConvergenceTracker | None = None


def _delivery_context(msg):
    """EventLoop delivery hook: re-establish the causal context of a
    message stamped with ``event_id`` (ibus envelopes, marshalled
    callbacks, storm-harness messages) for the handler's extent."""
    t = _TRACKER
    if t is None:
        return None
    eids = getattr(msg, "event_id", None)
    if not eids:
        return None
    if isinstance(eids, int):
        eids = (eids,)
    return t.activation(tuple(eids))


def configure(
    capacity: int = 0, clock=None
) -> ConvergenceTracker | None:
    """Arm (``capacity`` > 0) or disarm (0) the process-wide tracker and
    (un)install the runtime delivery-context hook.  The daemon calls
    this at boot from ``[telemetry] convergence-events``; storms and
    tests pass the loop clock for deterministic timelines."""
    global _TRACKER
    from holo_tpu.utils import runtime as _runtime

    if capacity and int(capacity) > 0:
        _TRACKER = ConvergenceTracker(int(capacity), clock or time.monotonic)
        _runtime.set_delivery_context(_delivery_context)
    else:
        _TRACKER = None
        _runtime.set_delivery_context(None)
    return _TRACKER


def tracker() -> ConvergenceTracker | None:
    return _TRACKER


def enabled() -> bool:
    return _TRACKER is not None


def begin(trigger: str, **attrs) -> int | None:
    """Origin stamp (no-op while disarmed)."""
    t = _TRACKER
    if t is None:
        return None
    return t.begin(trigger, **attrs)


def current() -> tuple[int, ...]:
    t = _TRACKER
    return t.current() if t is not None else ()


def activation(eids):
    """Context manager activating ``eids`` (accepts None/empty)."""
    t = _TRACKER
    if t is None or not eids:
        return nullcontext()
    if isinstance(eids, int):
        eids = (eids,)
    return t.activation(tuple(eids))


def mark(step: str, eids=None, **attrs) -> None:
    t = _TRACKER
    if t is not None:
        t.mark(step, eids=eids, **attrs)


def note_dispatch(site: str, mode: str) -> None:
    t = _TRACKER
    if t is not None:
        t.note_dispatch(site, mode)


def observe(phase: str, eids=None, **attrs) -> None:
    t = _TRACKER
    if t is not None:
        t.observe(phase, eids=eids, **attrs)


def fib_commit(op: str = "install", eids=None, **attrs) -> None:
    t = _TRACKER
    if t is not None:
        t.fib_commit(op=op, eids=eids, **attrs)


def sweep() -> int:
    t = _TRACKER
    return t.sweep() if t is not None else 0


# -- protocol-instance helpers (the shared pend/drain contract) ---------

#: per-instance bound on causal ids pending on the next SPF run
PENDING_LIMIT = 256


def pend_schedule(pending: list, default_trigger: str, instance: str = "") -> None:
    """The SPF-schedule origin stamp every protocol instance shares:
    inherit the active causal ids (the schedule is part of a larger
    chain — a storm flap, a BFD notification) or begin a fresh event of
    ``default_trigger`` class, then park the ids on ``pending`` (the
    instance's bounded list) for the SPF run the delay FSM coalesces
    them into.  No-op while disarmed."""
    t = _TRACKER
    if t is None:
        return
    eids = t.current()
    if not eids:
        eids = (t.begin(default_trigger, instance=instance),)
    for e in eids:
        if e not in pending and len(pending) < PENDING_LIMIT:
            pending.append(e)
    t.mark("spf-scheduled", eids=eids, instance=instance)
    cp = _CP_HOOK
    if cp is not None:
        for e in eids:
            cp.ev_sched(e)


@contextmanager
def spf_run(pending: list, instance: str = ""):
    """Drain ``pending`` into an active causal context around one SPF
    run (route publishes inside capture the ids) and observe the
    ``spf`` phase on normal completion.  Yields the drained ids."""
    eids = tuple(pending)
    del pending[:]
    rh = _RUN_HOOK
    if rh is not None and eids:
        rh(eids)
    with activation(eids):
        yield eids
        if eids:
            observe(PHASE_SPF, eids=eids, instance=instance)
