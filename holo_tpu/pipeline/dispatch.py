"""Double-buffered async dispatch pipeline (ISSUE 9 tentpole, part a).

Protocol actors used to block synchronously on every SPF/FRR marshal →
device-execute → readback round trip.  This module puts a bounded
dispatch queue and one pipeline worker between the actors and the
device, in the spirit of DeltaPath's dataflow pipelining
(arXiv:1808.06893):

- actors **enqueue** work (:meth:`DispatchPipeline.submit`) and get a
  ticket back immediately; :class:`LazySpfResult` defers the block to
  the first *use* of the result, so the host work between the dispatch
  call and the first consumption (LSDB walks, route bookkeeping)
  overlaps the device execution for free;
- the worker runs the split-phase backend API
  (``TpuSpfBackend.launch_* / finish_*``): while dispatch *i* executes
  on the device, dispatch *i+1*'s host marshal proceeds — depth-bounded
  double buffering (``depth=2`` default), with the finish (device sync
  + readback) of the oldest in-flight entry interleaved;
- **ordering** is strict per ``(instance topology uid, root)`` key:
  results complete in submission order for a key, and at most ONE entry
  per key is ever in flight — the *ownership handoff* the DeltaPath
  donation contract requires (an in-flight dispatch's donated previous
  tensors / resident graph buffers must never be consumed by a queued
  delta for the same chain; the next entry launches only after the
  previous one's ``finish`` has re-deposited the retained tensors);
- superseded **what-if batches coalesce**: a queued advisory batch for
  the same key is dropped (ticket marked superseded) when a batch for a
  newer topology generation arrives, and a resubmission of the same
  generation shares the queued ticket instead of duplicating work;
- **breaker awareness**: while a dispatch breaker is OPEN, advisory
  what-if batches are skipped at the submit seam — previously each one
  paid the full scalar re-run just to produce advisory output nobody
  was owed.

The survivability plane (ISSUE 19) hardens this queue into something a
serving system can stand on:

- **priority admission** — every ticket carries a class from
  :data:`holo_tpu.resilience.overload.CLASSES` (``correctness`` >
  ``advisory`` > ``background``).  The dequeue is class-aware (lowest
  rank first, FIFO within a rank), so FIB-feeding SPF/FRR work never
  queues behind what-if/twin batches; a FULL queue sheds
  lowest-class-first instead of blocking the submitting actor.
  ``correctness`` is NEVER shed — it keeps the bounded-blocking
  contract exactly as before;
- **deadline-aware shedding** — advisory tickets may carry a
  submit-time deadline and are dropped at dequeue once expired (an
  hour-old what-if batch is not owed a dispatch).  Sheds land in
  ``holo_pipeline_shed_total{class,reason}``, a flight event, and the
  critical-path ledger's ``shed`` disposition;
- **hung-dispatch watchdog hooks** — when a
  :class:`holo_tpu.resilience.watchdog.DispatchWatchdog` is armed, the
  worker stamps each in-flight launch/finish phase
  (``_begin_phase``/``_end_phase``); the sentinel may
  :meth:`DispatchPipeline.abandon_active` an overrunning phase — the
  wedged thread is disowned (it exits at its next ownership check),
  the per-key donation token is released through the
  ``consumes_donated`` handoff seam, and the ticket is served from its
  bit-identical scalar fallback while a fresh worker respawns
  (``respawn()``, supervised via ``Supervisor.watch_worker`` parity
  with ``watch_pump``);
- **transient-retry taxonomy** — ``_guarded_launch`` grants
  transient-classified device errors
  (:func:`holo_tpu.resilience.overload.is_transient`) one
  jittered-backoff retry BEFORE the breaker counts a strike;
  deterministic errors go straight to the fallback as before.

Chaos seams: the async dispatch closures run
``faults.crashpoint("pipeline.dispatch")`` inside the breaker guard;
the worker additionally traverses ``faults.killpoint("pipeline.worker")``
(thread death → supervised respawn) and
``faults.hangpoint("pipeline.launch"/"pipeline.finish")`` (wedge →
watchdog) — every arm must keep correctness FIB digests bit-identical
to the unfaulted control (tests/test_pipeline.py, tests/test_overload.py).

Everything lands in the ``holo_pipeline_*`` metric family: queue depth,
in-flight count, per-kind dispatch counters, coalesced/skipped/shed
tallies, worker respawns, caller wait time, and the measured overlap
ratio (device-in-flight seconds that ran while the worker was free to
do other host work).
"""

from __future__ import annotations

import logging
import threading
import time
from collections import deque
from contextlib import nullcontext

from holo_tpu import telemetry
from holo_tpu.analysis.runtime import consumes_donated
from holo_tpu.resilience import faults
from holo_tpu.resilience.overload import CLASS_RANK, CLASSES
from holo_tpu.telemetry import convergence, critpath, flight, slo

log = logging.getLogger("holo_tpu.pipeline")

_QUEUE_DEPTH = telemetry.gauge(
    "holo_pipeline_queue_depth",
    "Entries waiting in the dispatch pipeline queue",
)
_INFLIGHT = telemetry.gauge(
    "holo_pipeline_inflight",
    "Launched-but-unfinished pipeline entries (device in flight)",
)
_DISPATCHES = telemetry.counter(
    "holo_pipeline_dispatch_total",
    "Pipeline entries completed, by dispatch kind",
    ("kind",),
)
_COALESCED = telemetry.counter(
    "holo_pipeline_coalesced_total",
    "Queued what-if batches coalesced (shared or superseded)",
    ("reason",),
)
_BREAKER_SKIPS = telemetry.counter(
    "holo_pipeline_breaker_skip_total",
    "Advisory batches skipped at submit because the circuit was open",
)
_WAIT_SECONDS = telemetry.histogram(
    "holo_pipeline_wait_seconds",
    "Caller-side wait from result force to completion",
    ("kind",),
)
_OVERLAP_RATIO = telemetry.gauge(
    "holo_pipeline_overlap_ratio",
    "Fraction of device-in-flight time overlapped with other host work",
)
_SHED = telemetry.counter(
    "holo_pipeline_shed_total",
    "Tickets shed by the overload plane, by ticket class and reason",
    ("class", "reason"),
)
# Margins span a just-missed dequeue (sub-millisecond past expiry) to
# an advisory that sat a whole storm behind correctness work — the
# default log ladder covers both ends.
_SHED_MARGIN = telemetry.histogram(
    "holo_pipeline_shed_margin_seconds",
    "How far past its deadline an expired ticket already was at "
    "dequeue (near-miss sheds vs hopeless ones)",
    ("class",),
)
_WORKER_RESPAWNS = telemetry.counter(
    "holo_pipeline_worker_respawns_total",
    "Pipeline worker threads respawned after a crash or abandoned hang",
)


class PipelineClosed(RuntimeError):
    """Submit against a closed pipeline."""


class PipelineTicket:
    """Completion handle for one submitted dispatch."""

    __slots__ = (
        "key", "kind", "generation", "cls", "_event", "_value", "_exc",
        "skipped", "superseded", "shed", "_done", "_pipeline", "_cbs",
        "_cb_lock", "eids",
    )

    def __init__(
        self, pipeline, key, kind: str, generation: int,
        cls: str = "correctness",
    ):
        self.key = key
        self.kind = kind
        self.generation = generation
        self.cls = cls
        self._pipeline = pipeline
        self._event = threading.Event()
        self._value = None
        self._exc: BaseException | None = None
        self.skipped = False  # breaker-open skip: never executed
        self.superseded = False  # coalesced away by a newer generation
        self.shed = None  # overload shed reason ("capacity"/"expired")
        # First-settler claim: a ticket may race two resolvers — the
        # watchdog serving the scalar fallback vs the wedged worker
        # finally unblocking — and exactly one outcome must win.
        self._done = False
        self._cbs: list = []
        self._cb_lock = threading.Lock()
        # Causal convergence ids captured at submit (the critical-path
        # ledger's cross-thread join key for the force-wait stamps).
        self.eids: tuple = ()

    def add_done_callback(self, fn) -> None:
        """Run ``fn(ticket)`` at completion (immediately when already
        done).  Callbacks fire on the COMPLETING thread — the pipeline
        worker for queued work — so receivers must hop back onto their
        own actor loop before touching instance state (the deferred
        FRR-attach seam posts itself a loop message).  Callback
        exceptions are swallowed: a consumer bug must not poison the
        worker or the other callbacks."""
        with self._cb_lock:
            if not self._event.is_set():
                self._cbs.append(fn)
                return
        self._run_cb(fn)

    def _run_cb(self, fn) -> None:
        try:
            fn(self)
        except Exception:  # noqa: BLE001 — see add_done_callback
            log.exception("pipeline ticket done-callback failed")

    def _fire_cbs(self) -> None:
        with self._cb_lock:
            cbs, self._cbs = self._cbs, []
        for fn in cbs:
            self._run_cb(fn)

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: float | None = None):
        """Block until completion; re-raises a passthrough exception on
        the caller's thread (same contract as the synchronous dispatch).
        Skipped/superseded tickets return None."""
        if not self._event.is_set():
            critpath.note_force(self.eids, "b")
            t0 = time.perf_counter()
            if not self._event.wait(timeout):
                raise TimeoutError(
                    f"pipeline result for {self.key}/{self.kind} not ready"
                )
            # Span exemplar (ISSUE 17 satellite): a p99 force-wait is
            # joinable back to its flight-recorder timeline exactly like
            # holo_profile_stage_seconds buckets — the caller's active
            # span when one exists, the causal event id otherwise.
            sid = telemetry.current_span_id()
            exemplar = (
                {"span_id": sid}
                if sid is not None
                else ({"event_id": self.eids[0]} if self.eids else None)
            )
            _WAIT_SECONDS.labels(kind=self.kind).observe(
                time.perf_counter() - t0, exemplar=exemplar
            )
            critpath.note_force(self.eids, "e")
        if self._exc is not None:
            raise self._exc
        return self._value

    # pipeline-side completion (first settler wins; later attempts —
    # e.g. a disowned wedged worker completing after the watchdog
    # already served the fallback — are silently discarded)
    def _claim(self) -> bool:
        with self._cb_lock:
            if self._done:
                return False
            self._done = True
            return True

    def _complete(self, value) -> None:
        if not self._claim():
            return
        self._value = value
        self._event.set()
        self._fire_cbs()
        # Delivery-objective feed (ISSUE 20): a value delivered — even
        # a watchdog-served fallback — is a GOOD graded event for the
        # ticket's priority class; sheds grade bad in _shed_item.  One
        # module-global check while the SLO plane is disarmed.
        slo.note_served(self.cls)

    def _fail(self, exc: BaseException) -> None:
        if not self._claim():
            return
        self._exc = exc
        self._event.set()
        self._fire_cbs()

    def _skip(self, superseded: bool = False) -> None:
        if not self._claim():
            return
        if superseded:
            self.superseded = True
        else:
            self.skipped = True
        self._event.set()
        self._fire_cbs()

    def _shed(self, reason: str) -> None:
        """Overload shed: resolved-but-never-ran, like a breaker skip
        (``skipped`` stays the consumer-facing flag; ``shed`` carries
        the why)."""
        if not self._claim():
            return
        self.shed = reason
        self.skipped = True
        self._event.set()
        self._fire_cbs()


class _Item:
    """One queued dispatch."""

    __slots__ = (
        "key", "kind", "generation", "ticket", "run", "launch", "finish",
        "coalesce", "eids", "handle", "t_launch_end", "stalled",
        "cls", "rank", "deadline", "site", "fallback", "breaker",
        "abandoned",
    )

    def __init__(
        self, ticket, run=None, launch=None, finish=None,
        coalesce=False, eids=(), site=None, fallback=None, breaker=None,
    ):
        self.ticket = ticket
        self.key = ticket.key
        self.kind = ticket.kind
        self.generation = ticket.generation
        self.cls = ticket.cls
        self.rank = CLASS_RANK[ticket.cls]
        self.run = run
        self.launch = launch
        self.finish = finish
        self.coalesce = coalesce
        self.eids = tuple(eids)
        self.handle = None
        self.t_launch_end = 0.0
        # Per-key ordering-stall latch: stamped into the critical-path
        # waterfall on the FIRST skip only (worker rescans are routine).
        self.stalled = False
        # Survivability plane (ISSUE 19): absolute expiry (pipeline
        # clock; None = no deadline), the observatory site whose p99
        # sketches calibrate the watchdog budget, the bit-identical
        # scalar fallback + breaker the watchdog serves/escalates on a
        # hang, and the abandoned latch set by abandon_active.
        self.deadline = None
        self.site = site
        self.fallback = fallback
        self.breaker = breaker
        self.abandoned = False


class DispatchPipeline:
    """Bounded dispatch queue + one pipeline worker thread.

    ``depth`` bounds the launched-but-unfinished entries (2 = classic
    double buffering); ``capacity`` bounds the queue — a full queue
    backpressures the submitting actor (bounded means bounded).
    ``guard`` is an optional zero-arg callable returning a context
    manager entered around every worker-side phase: tests pass
    ``holo_tpu.testing.no_implicit_transfers`` so the pipelined path
    runs under the same transfer sanitizer as the synchronous suites.
    """

    def __init__(
        self,
        depth: int = 2,
        capacity: int = 32,
        name: str = "pipeline",
        guard=None,
        clock=time.monotonic,
        advisory_deadline: float | None = None,
    ):
        self.depth = max(int(depth), 1)
        self.capacity = max(int(capacity), 1)
        self.name = name
        self.guard = guard
        # Deadline clock — consulted ONLY when a ticket actually
        # carries a deadline (the disarmed-path identity contract:
        # tests submit through a poisoned clock and must never trip it).
        self._clock = clock
        #: default relative deadline stamped onto advisory tickets
        #: that did not pass their own (None = advisory never expires)
        self.advisory_deadline = advisory_deadline
        self._cv = threading.Condition()
        self._queue: deque[_Item] = deque()
        self._inflight: list[_Item] = []
        self._inflight_keys: set = set()
        # Items the worker popped but has not yet parked in _inflight /
        # finalized — without this, drain() would report empty while a
        # launch (or a whole single-phase run) is still executing.
        self._working = 0
        self._closed = False
        self._thread: threading.Thread | None = None
        self._worker_spawned = False  # first spawn vs respawn tally
        # Watchdog plane: (item, phase, since) stamp of the in-flight
        # launch/finish phase — ONE tuple store/read (GIL-atomic), only
        # while armed (_watch_clock not None); the sentinel reads it
        # lock-free and abandon_active re-verifies under _cv.
        self._watch_clock = None
        self._active = None
        # Crash seam (Supervisor.watch_worker): worker death marshals
        # through this callback when supervised, else self-respawns.
        self.on_worker_crash = None
        # stats (mutated under _cv or worker-only)
        self._submitted = 0
        self._completed = 0
        self._coalesced = 0
        self._skipped = 0
        self._sheds = 0
        self._shed_by_class: dict = {}
        self._hangs = 0
        self._worker_crashes = 0
        self._worker_respawns = 0
        self._launch_seconds = 0.0
        self._finish_seconds = 0.0
        self._overlap_seconds = 0.0
        self._max_inflight_per_key = 0  # invariant probe (tests): <= 1
        _QUEUE_DEPTH.set_fn(lambda: float(len(self._queue)))
        _INFLIGHT.set_fn(lambda: float(len(self._inflight)))

    # -- submit side ----------------------------------------------------

    def submit(
        self,
        key,
        kind: str,
        run=None,
        launch=None,
        finish=None,
        generation: int = 0,
        coalesce: bool = False,
        skip_when_open=None,
        cls: str = "correctness",
        deadline: float | None = None,
        site: str | None = None,
        fallback=None,
        breaker=None,
    ) -> PipelineTicket:
        """Enqueue one dispatch and return its ticket.

        Exactly one of ``run`` (single-phase: the worker executes it
        whole) or the ``launch``/``finish`` pair (split-phase: overlap
        eligible) must be given.  ``coalesce=True`` marks an advisory
        what-if batch: same-(key, generation) resubmissions share the
        queued ticket, a newer generation supersedes a queued older
        one, and ``skip_when_open`` (a CircuitBreaker) short-circuits
        the submit entirely while the circuit is open.

        Survivability plane: ``cls`` is the priority class
        (``correctness`` keeps bounded-blocking and is never shed;
        ``advisory``/``background`` shed instead of blocking when the
        queue is full).  ``deadline`` (relative seconds; advisory-only)
        expires the ticket at dequeue — advisory tickets default to the
        pipeline's ``advisory_deadline``.  ``site`` names the
        observatory cost-center whose p99 sketches calibrate the
        watchdog hang budget; ``fallback``/``breaker`` are what the
        watchdog serves/escalates when it abandons a hung phase."""
        if cls not in CLASS_RANK:
            raise ValueError(
                f"unknown ticket class {cls!r} (one of {CLASSES})"
            )
        if (run is None) == (launch is None or finish is None):
            raise ValueError("pass run=... OR launch=.../finish=...")
        if deadline is not None and cls == "correctness":
            # Correctness work is owed a dispatch, always — an expiry
            # would be a silent FIB-feeding drop.
            raise ValueError("correctness tickets cannot carry a deadline")
        if deadline is None and cls == "advisory":
            deadline = self.advisory_deadline
        ticket = PipelineTicket(self, key, kind, int(generation), cls=cls)
        if skip_when_open is not None and skip_when_open.state == "open":
            # The breaker is already serving FIB-feeding dispatches from
            # the oracle; an advisory batch is not owed a scalar re-run.
            ticket._skip()
            self._skipped += 1
            _BREAKER_SKIPS.inc()
            return ticket
        item = _Item(
            ticket, run=run, launch=launch, finish=finish,
            coalesce=coalesce, eids=convergence.current(),
            site=site, fallback=fallback, breaker=breaker,
        )
        ticket.eids = item.eids
        if deadline is not None:
            # The ONLY clock read on the submit path — disarmed tickets
            # (no deadline) never touch it (poisoned-clock contract).
            item.deadline = self._clock() + float(deadline)
        # Admission-time stamp, BEFORE the capacity gate: a submitter
        # blocked on a full queue books that wall as ``queue_wait`` in
        # the critical-path waterfalls (overload must be attributable),
        # not silently inside the caller's frame.  note_enqueue is
        # idempotent per record, so the coalesce-shared path needs no
        # second stamp.
        critpath.note_enqueue(item.eids)
        shed_self = False
        victims: list = []
        try:
            with self._cv:
                if self._closed:
                    raise PipelineClosed(self.name)
                if coalesce:
                    for old in list(self._queue):
                        if not (
                            old.coalesce
                            and old.key == key
                            and old.kind == kind
                        ):
                            continue
                        if old.generation == item.generation:
                            # Identical work already queued: share it —
                            # the new submit's causal events ride the
                            # queued item from here on (their
                            # queue-wait started now, at THIS
                            # admission).
                            if item.eids:
                                old.eids = tuple(
                                    dict.fromkeys(old.eids + item.eids)
                                )
                                old.ticket.eids = old.eids
                            self._coalesced += 1
                            _COALESCED.labels(reason="shared").inc()
                            return old.ticket
                        if old.generation < item.generation:
                            # Stale batch nobody needs anymore.
                            self._queue.remove(old)
                            old.ticket._skip(superseded=True)
                            self._coalesced += 1
                            _COALESCED.labels(reason="superseded").inc()
                while len(self._queue) >= self.capacity and not self._closed:
                    victim = self._capacity_victim_locked(item.rank)
                    if victim is not None:
                        # Graded load-shedding: evict the worst-class
                        # (oldest within it) queued ticket instead of
                        # walling the submitter.
                        self._queue.remove(victim)
                        self._note_shed_locked(victim)
                        victims.append(victim)
                        continue
                    if item.rank > 0:
                        # Queue full of equal-or-better work and the
                        # incoming ticket is sheddable: shed IT rather
                        # than block the actor — nobody is owed a
                        # stale advisory result.
                        self._note_shed_locked(item)
                        shed_self = True
                        break
                    # Correctness: bounded means bounded — block until
                    # space frees or the pipeline closes (close() wakes
                    # this wait; the recheck below raises).
                    self._cv.wait(0.5)
                if self._closed:
                    raise PipelineClosed(self.name)
                if not shed_self:
                    self._queue.append(item)
                    self._submitted += 1
                    self._ensure_worker_locked()
                    self._cv.notify_all()
        finally:
            # Victim tickets settle OUTSIDE the lock (done-callbacks
            # must never run under _cv) — including on the
            # PipelineClosed raise above.
            for v in victims:
                self._shed_item(v, "capacity")
        if shed_self:
            self._shed_item(item, "capacity")
        return ticket

    def _capacity_victim_locked(self, incoming_rank: int):
        """Worst-class victim a full queue gives up for an incoming
        ticket of ``incoming_rank``: highest rank wins, oldest within
        that rank; ``correctness`` (rank 0) is untouchable and a victim
        must rank >= the incoming ticket (an equal-rank advisory yields
        to a fresher one).  None = nothing sheddable."""
        victim = None
        for item in self._queue:
            if item.rank == 0 or item.rank < incoming_rank:
                continue
            if victim is None or item.rank > victim.rank:
                victim = item
        return victim

    def _note_shed_locked(self, item) -> None:
        self._sheds += 1
        self._shed_by_class[item.cls] = (
            self._shed_by_class.get(item.cls, 0) + 1
        )

    def _shed_item(self, item, reason: str, margin: float | None = None) -> None:
        """Settle a shed ticket (outside _cv: fires done-callbacks).
        ``margin`` — seconds past the deadline at dequeue — only exists
        for expiry sheds; capacity evictions have no deadline frame."""
        _SHED.labels(**{"class": item.cls, "reason": reason}).inc()
        if margin is not None:
            # Exemplar-joined to the ticket's causal events exactly like
            # the force-wait histogram: a p99 margin is traceable back to
            # the flight-recorder timeline of the event that missed.
            exemplar = {"event_id": item.eids[0]} if item.eids else None
            _SHED_MARGIN.labels(**{"class": item.cls}).observe(
                margin, exemplar=exemplar
            )
        flight.event(
            "pipeline-shed", pipeline=self.name, dispatch=item.kind,
            cls=item.cls, reason=reason,
        )
        critpath.note_shed(item.eids)
        slo.note_shed(item.cls, reason)
        item.ticket._shed(reason)

    def _ensure_worker_locked(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._spawn_worker_locked()

    def _spawn_worker_locked(self) -> None:
        # Callers hold _cv; the re-acquire is reentrant (Condition's
        # default lock is an RLock) and makes the publication of
        # self._thread an explicit lock-seam write.
        with self._cv:
            if self._worker_spawned:
                # Anything after the first spawn is a respawn —
                # crashed, abandoned-as-wedged, or close()-exited then
                # resubmitted.
                self._worker_respawns += 1
                _WORKER_RESPAWNS.inc()
            self._worker_spawned = True
            self._thread = threading.Thread(
                target=self._worker_main,
                name=f"holo-pipeline-{self.name}",
                daemon=True,
            )
            self._thread.start()

    def respawn(self) -> bool:
        """Start a fresh worker over the surviving queue (supervised
        restart hook — ``Supervisor.watch_worker`` duck-type — and the
        watchdog's post-abandon revival).  No-op when a healthy owned
        worker is already running; False once closed."""
        with self._cv:
            if self._closed:
                return False
            t = self._thread
            if (
                t is not None
                and t.is_alive()
                and t is not threading.current_thread()
            ):
                return True
            self._spawn_worker_locked()
            self._cv.notify_all()
            return True

    # -- worker side ----------------------------------------------------

    def _worker_main(self) -> None:
        """Thread target: the loop plus the crash seam.  A worker death
        from ANY cause (chaos killpoint, a bookkeeping bug) must never
        strand the queued tickets — it marshals to the supervisor when
        watched (``on_worker_crash`` → CrashNotice → RestartPolicy
        backoff) and self-respawns immediately otherwise."""
        try:
            self._worker()
        except BaseException as exc:  # noqa: BLE001 — last-resort seam;
            # the per-item paths already contain their own failures.
            with self._cv:
                self._worker_crashes += 1
                if self._thread is threading.current_thread():
                    self._thread = None
                self._cv.notify_all()
            log.exception("pipeline %s worker crashed", self.name)
            flight.event(
                "pipeline-worker-crash", pipeline=self.name,
                error=repr(exc),
            )
            cb = self.on_worker_crash
            if cb is not None:
                cb(exc)
            elif not self._closed:
                self.respawn()

    def _next_launchable_locked(
        self, stalled: list, expired: list
    ) -> _Item | None:
        """Best queued launchable item: lowest class rank first (FIB-
        feeding correctness work never queues behind advisory batches),
        FIFO within a rank, per-key ownership handoff respected (never
        two launches for one key).  Expired-deadline items are removed
        into ``expired`` (shed at dequeue — the hour-old what-if batch
        is not owed a dispatch); items skipped because their key IS in
        flight land in ``stalled`` on their first skip only (the
        ``_Item.stalled`` latch) — the per-key ordering-stall stamp of
        the critical-path ledger."""
        # The worker calls this holding _cv; the re-acquire is
        # reentrant (Condition's default lock is an RLock) and makes
        # the queue mutations explicit lock-seam writes.
        with self._cv:
            best = None
            now = None
            for item in list(self._queue):
                if item.deadline is not None:
                    if now is None:
                        now = self._clock()
                    if now >= item.deadline:
                        self._queue.remove(item)
                        self._note_shed_locked(item)
                        # Carry the lateness out with the item: the
                        # margin histogram observes OUTSIDE _cv.
                        expired.append((item, now - item.deadline))
                        continue
                if item.key in self._inflight_keys:
                    if not item.stalled:
                        item.stalled = True
                        stalled.append(item)
                    continue
                if best is None or item.rank < best.rank:
                    best = item
                    if best.rank == 0:
                        break  # nothing outranks correctness
            if best is not None:
                self._queue.remove(best)
            return best

    def _worker(self) -> None:
        while True:
            # Chaos seam: thread-death injection (supervised-respawn
            # coverage).  Traversed with no item in hand, so queued
            # tickets survive the kill intact.
            faults.killpoint("pipeline.worker")
            launch_item = None
            finish_item = None
            stalled: list = []
            expired: list = []
            with self._cv:
                if self._thread is not threading.current_thread():
                    # Disowned: the watchdog abandoned this thread as
                    # wedged (or a respawn superseded it) — a
                    # replacement owns the queue now.
                    return
                if (
                    self._closed
                    and not self._queue
                    and not self._inflight
                ):
                    self._cv.notify_all()
                    return
                launch_item = (
                    self._next_launchable_locked(stalled, expired)
                    if len(self._inflight) < self.depth
                    else None
                )
                if launch_item is None:
                    if self._inflight:
                        finish_item = self._inflight.pop(0)
                        self._working += 1
                    elif not expired:
                        self._cv.wait(0.5)
                else:
                    self._working += 1
            # Stall/shed stamps run OUTSIDE the cv lock (ISSUE 17
            # contract: no new work under the queue lock on the
            # dispatch thread).
            for it in stalled:
                critpath.note_stall(it.eids)
            for it, margin in expired:
                self._shed_item(it, "expired", margin=margin)
            if launch_item is not None:
                self._do_launch(launch_item)
            elif finish_item is not None:
                self._do_finish(finish_item)

    def _ctx(self, item: _Item):
        g = self.guard() if self.guard is not None else nullcontext()
        return g, convergence.activation(item.eids)

    # -- watchdog plane -------------------------------------------------

    def arm_watchdog(self, clock) -> None:
        """Begin stamping in-flight phase walls (DispatchWatchdog)."""
        self._watch_clock = clock

    def disarm_watchdog(self) -> None:
        self._watch_clock = None
        self._active = None

    def _begin_phase(self, item: _Item, phase: str) -> None:
        wc = self._watch_clock
        if wc is None:
            return  # disarmed: zero clock reads, zero stores
        # One tuple store (GIL-atomic); the sentinel reads it lock-free
        # and abandon_active re-verifies the exact tuple under _cv.
        self._active = (item, phase, wc())

    def _end_phase(self, item: _Item) -> bool:
        """True when this thread still owns ``item`` (the common case);
        False when the watchdog abandoned the phase while we were
        wedged — the ticket was served from the fallback, the
        bookkeeping was settled by abandon_active, and this thread was
        disowned (it exits at the next loop-top ownership check)."""
        if self._watch_clock is None and not item.abandoned:
            return True
        with self._cv:
            act = self._active
            if act is not None and act[0] is item:
                self._active = None
            return not item.abandoned

    def abandon_active(self, item, phase: str) -> bool:
        """Watchdog verdict: give up on the in-flight ``phase`` of
        ``item``.  False when the phase is no longer active (it
        completed while the sentinel decided) — nothing happens then.
        On True: the worker thread is disowned as wedged, the item's
        bookkeeping is settled as completed-by-fallback, and — for a
        finish-phase hang — the per-key donation token is released
        through the audited ``consumes_donated`` seam, so a queued
        delta of the same chain may launch on the respawned worker
        without ever violating donation ownership (the disowned
        thread's late completion is discarded by the ticket's
        first-settler claim and its _end_phase result)."""
        with self._cv:
            act = self._active
            if act is None or act[0] is not item or act[1] != phase:
                return False
            item.abandoned = True
            self._active = None
            self._hangs += 1
            if (
                self._thread is not None
                and self._thread is not threading.current_thread()
            ):
                self._thread = None  # wedged: ownership check exits it
            self._working -= 1
            self._completed += 1
            self._cv.notify_all()
        if phase == "finish":
            # The wedged finish() never re-deposited the donated
            # tensors; the scalar fallback path touches no device
            # residents, so ownership of the chain transfers through
            # the same audited handoff window the healthy path uses.
            with consumes_donated("pipeline.key.handoff"):
                with self._cv:
                    self._inflight_keys.discard(item.key)
                    self._cv.notify_all()
        _DISPATCHES.labels(kind=item.kind).inc()
        return True

    # -- phases ---------------------------------------------------------

    def _do_launch(self, item: _Item) -> None:
        critpath.note_launch(item.eids, "b")
        t0 = time.perf_counter()
        try:
            guard, act = self._ctx(item)
            with guard, act:
                self._begin_phase(item, "launch")
                # Chaos seam: wedge-the-worker injection (watchdog
                # coverage) — inside the phase stamp, like a real stall.
                faults.hangpoint("pipeline.launch")
                if item.run is not None:
                    value = item.run()
                    if not self._end_phase(item):
                        return  # abandoned: watchdog settled everything
                    item.ticket._complete(value)
                    critpath.note_finish(item.eids, "e")
                    self._finalize(item, finished=True)
                    return
                item.handle = item.launch()
                if not self._end_phase(item):
                    return  # abandoned mid-launch: drop the orphan handle
        except BaseException as exc:  # noqa: BLE001 — marshaled to the
            # caller's thread by ticket.result(); the worker survives.
            if not self._end_phase(item):
                return
            item.ticket._fail(exc)
            self._finalize(item, finished=True)
            return
        finally:
            self._launch_seconds += time.perf_counter() - t0
        critpath.note_launch(item.eids, "e")
        item.t_launch_end = time.perf_counter()
        with self._cv:
            self._inflight.append(item)
            self._inflight_keys.add(item.key)
            self._working -= 1
            per_key = sum(
                1 for i in self._inflight if i.key == item.key
            )
            self._max_inflight_per_key = max(
                self._max_inflight_per_key, per_key
            )
            self._cv.notify_all()

    def _do_finish(self, item: _Item) -> None:
        critpath.note_finish(item.eids, "b")
        t_fs = time.perf_counter()
        # Device time that elapsed while the worker was busy elsewhere
        # (launching the next entry / idle-waiting): the overlap the
        # double buffer exists to create.
        self._overlap_seconds += max(t_fs - item.t_launch_end, 0.0)
        owned = True
        try:
            guard, act = self._ctx(item)
            # The pipeline's per-key ownership handoff: finish()
            # re-deposits the fresh tensors that replace the donated
            # previous set, and only then may a queued delta of the
            # same chain launch (submit() serializes on the key).
            # consumes_donated is the HL109 seam vocabulary — the
            # runtime guard counts the window so tests can pin that
            # the handoff actually ran under the async path.
            with guard, act, consumes_donated("pipeline.key.handoff"):
                self._begin_phase(item, "finish")
                faults.hangpoint("pipeline.finish")
                value = item.finish(item.handle)
                owned = self._end_phase(item)
                if owned:
                    item.ticket._complete(value)
            if owned:
                critpath.note_finish(item.eids, "e")
        except BaseException as exc:  # noqa: BLE001 — see _do_launch
            owned = self._end_phase(item)
            if owned:
                item.ticket._fail(exc)
        finally:
            self._finish_seconds += time.perf_counter() - t_fs
            if owned:
                self._finalize(item, finished=False)

    def _finalize(self, item: _Item, finished: bool) -> None:
        with self._cv:
            self._inflight_keys.discard(item.key)
            self._working -= 1
            self._completed += 1
            self._cv.notify_all()
        _DISPATCHES.labels(kind=item.kind).inc()
        denom = self._overlap_seconds + self._finish_seconds
        if denom > 0:
            _OVERLAP_RATIO.set(self._overlap_seconds / denom)

    # -- lifecycle ------------------------------------------------------

    def drain(self, timeout: float | None = None) -> bool:
        """Block until queue + in-flight are empty (True on success)."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cv:
            while self._queue or self._inflight_keys or self._working:
                wait = 0.5
                if deadline is not None:
                    wait = deadline - time.monotonic()
                    if wait <= 0:
                        return False
                self._cv.wait(min(wait, 0.5))
        return True

    def close(self, timeout: float = 10.0) -> None:
        """Refuse new submits, drain, stop the worker."""
        with self._cv:
            self._closed = True
            self._cv.notify_all()
        t = self._thread
        if t is not None and t.is_alive():
            t.join(timeout)
        # Detach the sampled gauges: a set_fn closure over self would
        # otherwise pin this closed pipeline forever and keep scraping
        # its dead queue.  Safe ordering with configure_process_pipeline
        # (old closed BEFORE the replacement's __init__ re-points them).
        _QUEUE_DEPTH.set_fn(None)
        _QUEUE_DEPTH.set(0.0)
        _INFLIGHT.set_fn(None)
        _INFLIGHT.set(0.0)

    @property
    def closed(self) -> bool:
        return self._closed

    def stats(self) -> dict:
        with self._cv:
            denom = self._overlap_seconds + self._finish_seconds
            return {
                "depth": self.depth,
                "capacity": self.capacity,
                "queued": len(self._queue),
                "inflight": len(self._inflight),
                "submitted": self._submitted,
                "completed": self._completed,
                "coalesced": self._coalesced,
                "breaker-skipped": self._skipped,
                "launch-seconds": round(self._launch_seconds, 6),
                "finish-seconds": round(self._finish_seconds, 6),
                "overlap-seconds": round(self._overlap_seconds, 6),
                "overlap-ratio": round(
                    self._overlap_seconds / denom, 4
                ) if denom > 0 else 0.0,
                "max-inflight-per-key": self._max_inflight_per_key,
                "sheds": self._sheds,
                "shed-by-class": dict(self._shed_by_class),
                "hangs": self._hangs,
                "worker-crashes": self._worker_crashes,
                "worker-respawns": self._worker_respawns,
            }


# -- lazy results -------------------------------------------------------


class LazySpfResult:
    """Duck-typed :class:`holo_tpu.spf.backend.SpfResult`: attribute
    access forces the pipeline ticket.  The protocol layer reads
    ``dist``/``parent``/``hops``/``nexthop_words`` — each blocks until
    the worker completed the dispatch, which by then has usually
    overlapped the caller's own host work."""

    __slots__ = ("_ticket",)

    _FIELDS = (
        "dist", "parent", "hops", "nexthop_words",
        "parents", "pdist", "pweight", "npaths", "nh_weights",
    )

    def __init__(self, ticket: PipelineTicket):
        self._ticket = ticket

    def _force(self):
        res = self._ticket.result()
        if res is None:
            raise RuntimeError(
                f"pipelined SPF dispatch for {self._ticket.key} was "
                f"{'skipped' if self._ticket.skipped else 'superseded'}"
            )
        return res

    def __getattr__(self, name):
        if name in self._FIELDS:
            return getattr(self._force(), name)
        raise AttributeError(name)

    def wait(self):
        """Explicit force (returns the real SpfResult)."""
        return self._force()


class LazyBackupTable:
    """Duck-typed :class:`holo_tpu.frr.kernel.BackupTable`: any
    attribute access forces the FRR pipeline ticket — the protocol
    layer stores the table at SPF time but only consumes it when a
    repair is resolved (BFD/carrier flip), so the FRR dispatch rides
    the pipeline for free."""

    __slots__ = ("_ticket",)

    def __init__(self, ticket: PipelineTicket):
        self._ticket = ticket

    def _force(self):
        res = self._ticket.result()
        if res is None:
            raise RuntimeError(
                f"pipelined FRR dispatch for {self._ticket.key} skipped"
            )
        return res

    def pending(self) -> bool:
        """True while the dispatch is still in flight — the protocol's
        defer-the-force probe (ISSUE 10: the SPF path must not pay the
        FRR force; it re-attaches from a worker done-callback)."""
        return not self._ticket.done()

    def on_done(self, fn) -> None:
        """Completion hook (fires on the pipeline worker thread)."""
        self._ticket.add_done_callback(fn)

    def __getattr__(self, name):
        if name.startswith("_"):
            raise AttributeError(name)
        return getattr(self._force(), name)

    def wait(self):
        return self._force()


# -- async backend facades ---------------------------------------------

#: exception types the breaker never masks (bugs, not device failures);
#: mirrored from resilience.breaker so the split-phase closures agree.
def _passthrough():
    from holo_tpu.resilience.breaker import _PASSTHROUGH

    return _PASSTHROUGH


def _guarded_launch(breaker, context: str, launch_fn) -> tuple:
    """Phase 1 of a split breaker-guarded dispatch — ONE implementation
    shared by the SPF and FRR facades so the breaker contract (admit →
    chaos seam → retry taxonomy → passthrough abort → failure) cannot
    drift between them.  Returns the ``(verdict, guard, handle)`` state
    :func:`_guarded_finish` completes.

    Transient-retry taxonomy (ISSUE 19): a transient-classified device
    error (:func:`overload.is_transient` — a runtime blip, UNAVAILABLE, a
    timed-out collective) gets the policy's jittered-backoff retries
    BEFORE the breaker counts a strike; deterministic errors (a shape
    bug reproduces identically — retrying is pure added latency) go
    straight to the fallback verdict as before."""
    from holo_tpu.resilience import overload

    guard = breaker.split(context)
    if not guard.admitted:
        return ("fallback", guard, None)
    policy = overload.default_retry_policy()
    attempt = 0
    while True:
        try:
            faults.crashpoint("pipeline.dispatch")
            handle = launch_fn()
        except _passthrough():
            guard.abort()
            raise
        except Exception as exc:  # noqa: BLE001 — breaker contract
            if attempt < policy.retries and overload.is_transient(exc):
                attempt += 1
                time.sleep(policy.backoff(context, attempt))
                continue
            if attempt:
                overload.note_retry("exhausted")
            guard.failure(exc)
            return ("fallback", guard, None)
        if attempt:
            overload.note_retry("recovered")
        return ("ok", guard, handle)


def _guarded_finish(state: tuple, finish_fn, fallback_fn):
    """Phase 2: complete the device dispatch or serve the bit-identical
    fallback; success records the whole launch→finish deadline span."""
    verdict, guard, handle = state
    if verdict == "fallback":
        return fallback_fn()
    try:
        res = finish_fn(handle)
    except _passthrough():
        guard.abort()
        raise
    except Exception as exc:  # noqa: BLE001 — breaker contract
        guard.failure(exc)
        return fallback_fn()
    guard.success()
    return res


class AsyncSpfBackend:
    """``SpfBackend`` facade routing dispatches through a pipeline.

    ``compute`` enqueues a split-phase (launch/finish) dispatch and
    returns a :class:`LazySpfResult`; the synchronous breaker contract
    is preserved phase by phase via ``CircuitBreaker.split`` — an XLA
    failure in either phase re-runs on the scalar oracle
    (bit-identical), repeated failures open the circuit, and
    passthrough exceptions surface on the caller's thread at force
    time.  ``compute_whatif_async`` adds the advisory-batch semantics
    (coalescing + breaker-open skip); the plain ``compute_whatif`` /
    ``compute_multiroot`` stay synchronous delegates — their callers
    (the CLI) want blocking results.
    """

    #: retained chain-root entries (one live dispatch chain per entry)
    CHAIN_CAPACITY = 512

    def __init__(self, inner, pipeline: DispatchPipeline):
        self.inner = inner
        self.pipeline = pipeline
        # Topology uid -> chain-root uid.  Every SPF run marshals a
        # FRESH Topology object (new uid), so the ordering/ownership
        # unit is the DELTA CHAIN: a topology carrying ``delta_base``
        # lineage joins its base's chain, everything else roots a new
        # one.  This is what makes "(instance, root)" concrete at the
        # backend layer — one instance area advances one chain.
        self._chains: dict = {}

    @property
    def name(self) -> str:
        return f"{self.inner.name}-async"

    def __getattr__(self, attr):
        # breaker / incremental / engine / prepare / oracle ... all
        # delegate: the facade adds scheduling, not behavior.
        return getattr(self.inner, attr)

    # -- keys ----------------------------------------------------------

    def _key(self, topo) -> tuple:
        """The strict-ordering / ownership-handoff unit: (delta-chain
        root uid, root vertex).  Consecutive generations of one
        instance area MUST serialize — an in-flight dispatch's donated
        previous tensors / resident graph buffers must never be
        consumed by a queued delta of the same chain — while unrelated
        areas/instances overlap freely."""
        uid = topo.cache_key[0]
        delta = getattr(topo, "delta_base", None)
        if delta is not None:
            base_uid = delta.base_key[0]
            chain = self._chains.get(base_uid, base_uid)
        else:
            chain = self._chains.get(uid, uid)
        self._chains[uid] = chain
        while len(self._chains) > self.CHAIN_CAPACITY:
            self._chains.pop(next(iter(self._chains)))
        return (chain, int(topo.root))

    # -- SpfBackend interface ------------------------------------------

    def compute(self, topo, edge_mask=None, multipath_k: int = 1):
        inner = self.inner
        pipe = self.pipeline
        if pipe is None or pipe.closed:
            return inner.compute(topo, edge_mask, multipath_k=multipath_k)
        if inner.breaker.state == "open":
            # Degraded mode runs on the CALLER's thread, exactly like
            # the unpipelined breaker: N threaded instances' scalar
            # fallbacks must not serialize behind the one pipeline
            # worker while the device is down.  Safe w.r.t. the
            # per-key contract: the scalar path touches no device
            # residents or retained tensors.
            return inner.compute(topo, edge_mask, multipath_k=multipath_k)
        if getattr(inner, "engine", None) == "blocked" and multipath_k <= 1:
            # The blocked-Pallas experiment has no split-phase path;
            # run it whole on the worker (actors still don't block).
            ticket = pipe.submit(
                self._key(topo), "one",
                run=lambda: inner.compute(topo, edge_mask),
                cls="correctness", site="spf.blocked",
                fallback=lambda: inner._noted_fallback(
                    lambda: inner._oracle.compute(topo, edge_mask)
                ),
                breaker=inner.breaker,
            )
            return LazySpfResult(ticket)
        use_part = getattr(inner, "_use_partitioned", None)
        if use_part is not None and use_part(topo):
            # Partitioned SPF (ISSUE 15) is a host-orchestrated
            # multi-dispatch (boundary solve -> skeleton stitch ->
            # halo-exchange rounds) with no single launch/finish seam:
            # run it whole on the worker.  Ordering still holds — the
            # per-key serialization covers the resident's donated
            # plane handoff exactly like the split-phase chains.
            fallback = lambda: inner._noted_fallback(  # noqa: E731
                lambda: inner._oracle.compute(
                    topo, edge_mask, multipath_k=multipath_k
                )
            )
            ticket = pipe.submit(
                self._key(topo), "one",
                run=lambda: inner.compute(
                    topo, edge_mask, multipath_k=multipath_k
                ),
                cls="correctness", site="spf.partitioned",
                fallback=fallback, breaker=inner.breaker,
            )
            return LazySpfResult(ticket)
        fallback = lambda: inner._noted_fallback(  # noqa: E731
            lambda: inner._oracle.compute(
                topo, edge_mask, multipath_k=multipath_k
            )
        )
        ticket = pipe.submit(
            self._key(topo), "one",
            launch=lambda: _guarded_launch(
                inner.breaker, "spf.one",
                lambda: inner.launch_one(
                    topo, edge_mask, multipath_k=multipath_k
                ),
            ),
            finish=lambda st: _guarded_finish(
                st, inner.finish_one, fallback
            ),
            cls="correctness", site="spf.one",
            fallback=fallback, breaker=inner.breaker,
        )
        return LazySpfResult(ticket)

    def compute_whatif(self, topo, edge_masks, multipath_k: int = 1):
        return self.inner.compute_whatif(
            topo, edge_masks, multipath_k=multipath_k
        )

    def compute_multiroot(self, topo, roots):
        return self.inner.compute_multiroot(topo, roots)

    # -- advisory what-if (the coalescing + breaker-skip seam) ----------

    def compute_whatif_async(
        self, topo, edge_masks, generation: int | None = None
    ) -> PipelineTicket:
        """Enqueue an advisory what-if batch.  Returns the ticket;
        ``result()`` yields the usual list of SpfResults — or None when
        the batch was skipped (circuit open) or superseded by a newer
        generation's batch for the same (uid, root).

        ``generation`` defaults to the topology's own generation, but
        protocol actors pass a monotonic per-instance stamp (their SPF
        run counter): every SPF marshals a FRESH topology whose local
        generation restarts, and without the stamp a queued batch from
        run N would be "shared" with run N+1 instead of superseded."""
        inner = self.inner
        pipe = self.pipeline
        gen = int(
            topo.cache_key[1] if generation is None else generation
        )
        if pipe is None or pipe.closed:
            t = PipelineTicket(None, self._key(topo), "whatif", gen)
            t._complete(inner.compute_whatif(topo, edge_masks))
            return t
        return pipe.submit(
            self._key(topo), "whatif",
            run=lambda: inner.compute_whatif(topo, edge_masks),
            generation=gen,
            coalesce=True,
            skip_when_open=inner.breaker,
            # Advisory class: first shed under overload, expires at the
            # pipeline's advisory_deadline.  No fallback — a hung
            # advisory batch is not owed a scalar re-run (the ticket
            # fails with WatchdogTimeout; consumers treat it like a
            # skip).
            cls="advisory", site="spf.whatif",
        )


class AsyncFrrEngine:
    """``FrrEngine`` facade: ``compute`` enqueues the batched
    backup-table dispatch (split-phase on the tpu engine) and returns a
    :class:`LazyBackupTable` — SPF and FRR dispatches for one topology
    then overlap, since the FRR planes derive from the topology, not
    the SPF result."""

    def __init__(self, inner, pipeline: DispatchPipeline):
        self.inner = inner
        self.pipeline = pipeline

    def __getattr__(self, attr):
        return getattr(self.inner, attr)

    @property
    def name(self) -> str:
        return f"{getattr(self.inner, 'engine', 'frr')}-async"

    def compute(self, topo):
        inner = self.inner
        pipe = self.pipeline
        if (
            pipe is None
            or pipe.closed
            or getattr(inner, "engine", "scalar") != "tpu"
            or inner.breaker.state == "open"  # see AsyncSpfBackend
        ):
            return inner.compute(topo)
        # Distinct ordering domain from the SPF dispatches of the same
        # topology: FRR reads the resident graph but donates nothing,
        # and the shared DeviceGraphCache serializes its own mutation
        # under its lock — so SPF(topo) and FRR(topo) may overlap.
        # Plane marshal (occupancy gauges included) rides the worker;
        # the failure path re-marshals for the oracle — paying the
        # host marshal twice on the RARE failed dispatch beats paying
        # it on the actor for every healthy one.
        key = ("frr", topo.cache_key[0], int(topo.root))
        ticket = pipe.submit(
            key, "frr",
            launch=lambda: _guarded_launch(
                inner.breaker, "frr.batch",
                lambda: inner._launch_tpu(
                    topo, inner.marshal_inputs(topo)
                ),
            ),
            finish=lambda st: _guarded_finish(
                st, inner._finish_tpu,
                lambda: inner._scalar_fallback(
                    topo, inner.marshal_inputs(topo)
                ),
            ),
            cls="correctness", site="frr.batch",
            fallback=lambda: inner._scalar_fallback(
                topo, inner.marshal_inputs(topo)
            ),
            breaker=inner.breaker,
        )
        return LazyBackupTable(ticket)


# -- process-wide singleton --------------------------------------------

_PIPELINE: DispatchPipeline | None = None
_PIPELINE_LOCK = threading.Lock()


def configure_process_pipeline(
    depth: int = 2, capacity: int = 32, guard=None,
    advisory_deadline: float | None = None,
) -> DispatchPipeline:
    """Install the process-wide dispatch pipeline (daemon boot from
    ``[pipeline]``; tests call directly).  Closes any previous
    pipeline first so its worker cannot race the replacement."""
    global _PIPELINE
    with _PIPELINE_LOCK:
        if _PIPELINE is not None:
            _PIPELINE.close()
        _PIPELINE = DispatchPipeline(
            depth=depth, capacity=capacity, name="process", guard=guard,
            advisory_deadline=advisory_deadline,
        )
        return _PIPELINE


def process_pipeline() -> DispatchPipeline | None:
    return _PIPELINE


def reset_process_pipeline() -> None:
    """Close + uninstall (test teardown)."""
    global _PIPELINE
    with _PIPELINE_LOCK:
        if _PIPELINE is not None:
            _PIPELINE.close()
        _PIPELINE = None


def wrap_spf_backend(backend):
    """Route a TpuSpfBackend through the process pipeline when one is
    armed; scalar backends and unarmed processes pass through unchanged
    (the ``[pipeline] enabled=false`` default costs nothing)."""
    pipe = _PIPELINE
    if pipe is None or pipe.closed:
        return backend
    if backend is None or getattr(backend, "name", "") != "tpu":
        return backend
    return AsyncSpfBackend(backend, pipe)


def wrap_frr_engine(engine):
    """FRR analog of :func:`wrap_spf_backend`."""
    pipe = _PIPELINE
    if pipe is None or pipe.closed:
        return engine
    if engine is None or getattr(engine, "engine", "scalar") != "tpu":
        return engine
    return AsyncFrrEngine(engine, pipe)
