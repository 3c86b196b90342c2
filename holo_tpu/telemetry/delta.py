"""Shared-delta telemetry fan-out (ISSUE 11): serve thousands of gNMI
subscribers at O(1) per-tick render cost.

Before this module every gNMI SAMPLE/ON_CHANGE subscriber independently
walked and diffed the state subtree on its own timer
(``gnmi_server._SubSampler``), so per-tick cost grew linearly with
subscriber count.  The :class:`FanoutEngine` applies the same
incremental-dataflow framing that made SPF cheap (DeltaPath): compute
ONE change-set per coalesced tick epoch, render each changed leaf once,
and fan the shared rendered notification out to every due subscriber
through the existing bounded queues.

Epoch / versioning contract
---------------------------
- The engine keeps one leaf store ``{path -> value}`` plus a per-leaf
  ``last-changed epoch``.  A tick that observes any leaf change
  advances the monotonic epoch id by one; an unchanged tick keeps it.
- Subscriptions become *epoch cursors* grouped into **interval
  buckets**: subscribers sharing (path, mode, sample interval,
  heartbeat, suppress) share one bucket, one cursor, and one rendered
  notification per fire — per-tick render cost is O(distinct buckets),
  never O(subscribers).
- suppress-redundant is an epoch comparison (``changed-epoch >
  cursor``), heartbeat is a render-cache hit keyed on the current
  epoch: neither re-walks the tree.  Suppression is therefore
  *epoch-granular*: a leaf that changed and reverted (A->B->A) across
  intermediate epochs between a slow bucket's fires is resent with its
  (correct, current) value where the legacy value diff would have
  stayed silent — gNMI suppress_redundant is best-effort, and a bucket
  firing at every epoch (the byte-identity test's arm) is provably
  value-exact.
- The registry's write-time leaf stamps
  (:func:`holo_tpu.telemetry.registry.write_stamp`) short-circuit idle
  ticks entirely: when every bucket sits under the registry-backed
  ``holo-telemetry/metric`` subtree, no callback-backed gauge is live,
  and nothing external invalidated the tree, an unchanged stamp skips
  the walk itself.

Fallback contract (same breaker discipline as the SPF plane): any
engine failure increments ``holo_gnmi_fanout_fallback_total``, N
consecutive failures open the breaker, and every stream degrades to
the per-subscriber walk path (``_SubSampler``) with byte-identical
output; a cooldown later the engine half-opens and fresh streams probe
it again.
"""

from __future__ import annotations

import logging
import threading
import time
import weakref
from collections import deque

from holo_tpu import telemetry

log = logging.getLogger("holo_tpu.telemetry.delta")

ROOT = "holo-telemetry"
# The registry-backed subtree: ONLY these leaves are provably frozen by
# an unchanged write stamp (flight/convergence/cache stats under
# holo-telemetry/ move without registry writes), so the idle
# short-circuit requires every bucket to sit strictly under it.
METRIC_ROOT = "holo-telemetry/metric"
# The engine's OWN live stats leaf (provider.py surfaces it for Get).
# It is excluded from the sampled leaf store: diffing it would make
# every epoch advance change the tree again — a self-sustaining
# change feedback loop that re-renders forever on an idle system.
# Subscribers read it via Get; the registry-backed holo_gnmi_fanout_*
# METRIC leaves still flow through sampling like any other counter.
SELF_ROOT = "holo-telemetry/gnmi-fanout"

#: consecutive tick failures before the breaker opens
BREAKER_THRESHOLD = 3
#: seconds an open breaker parks before half-opening to a probe
BREAKER_COOLDOWN = 30.0
#: per-epoch change-set window kept for O(changed) delta renders;
#: cursors older than the window fall back to a full stamp scan
RECENT_EPOCHS = 128
#: distinct covering subtree roots beyond which the scoped per-root
#: fetch costs more than one full-tree walk (every provider runs per
#: get_state call) — fall back to the single full walk instead
MAX_SCOPED_ROOTS = 4

# Every family here is stamped=False: the engine's own bookkeeping must
# not advance the registry write stamp, or serving a heartbeat would
# re-arm the next tick's walk and the idle short-circuit (and suppress
# streams over the metric subtree) would never quiesce.
_EPOCHS = telemetry.counter(
    "holo_gnmi_fanout_epochs_total",
    "Shared-delta fan-out epochs (ticks that observed a leaf change)",
    stamped=False,
)
_RENDERS = telemetry.counter(
    "holo_gnmi_fanout_shared_renders_total",
    "Notifications rendered ONCE and shared across all due subscribers",
    ("kind",),
    stamped=False,
)
_CACHE = telemetry.counter(
    "holo_gnmi_fanout_render_cache_total",
    "Shared render cache lookups keyed by (epoch, subtree)",
    ("result",),
    stamped=False,
)
_LEAVES = telemetry.histogram(
    "holo_gnmi_fanout_leaves_changed",
    "Changed-leaf count per fan-out epoch",
    buckets=(0, 1, 2, 5, 10, 25, 50, 100, 250, 1000, 10000),
    stamped=False,
)
_TICK = telemetry.histogram(
    "holo_gnmi_fanout_tick_seconds",
    "Wall seconds per coalesced fan-out tick (snapshot+diff+render+put)",
    stamped=False,
)
_FALLBACK = telemetry.counter(
    "holo_gnmi_fanout_fallback_total",
    "Delta-engine failures degrading subscribers to the walk path",
    ("reason",),
    stamped=False,
)
_SUBSCRIBERS = telemetry.gauge(
    "holo_gnmi_fanout_subscribers", "Epoch cursors attached to the engine",
    stamped=False,
)
_BUCKETS = telemetry.gauge(
    "holo_gnmi_fanout_buckets", "Distinct interval buckets in the engine",
    stamped=False,
)

# Engines register here (weakly) so the holo-telemetry provider leaf
# can surface fan-out stats without owning a reference.
_ENGINES: "weakref.WeakSet[FanoutEngine]" = weakref.WeakSet()


def register_engine(engine: "FanoutEngine") -> None:
    _ENGINES.add(engine)


def engines_stats() -> list[dict]:
    return [e.stats() for e in list(_ENGINES)]


def _pb():
    """The gNMI lite proto module + render helpers (lazy: importing the
    server pulls grpc; by render time it is always loaded)."""
    import holo_tpu.daemon.gnmi_server as gs

    return gs


def _match(base: str, path: str) -> bool:
    """Same subtree predicate as the legacy per-subscriber walk."""
    return (
        not base
        or path == base
        or path.startswith((base + "/", base + "["))
    )


class _Member:
    """One attached subscriber queue inside a bucket.  ``needs_full``
    marks a cursor that has not received its first sampled push yet —
    its first notification is a full sync (shared with every other
    member syncing at the same tick), matching the legacy sampler's
    empty ``last`` dict."""

    __slots__ = ("queue", "sid", "needs_full")

    def __init__(self, queue, sid: int, needs_full: bool) -> None:
        self.queue = queue
        self.sid = sid
        self.needs_full = needs_full


class _Bucket:
    """A shared sampler: the epoch-cursor replacement for one
    ``_SubSampler`` timer configuration, serving EVERY subscriber with
    that configuration.  Timer semantics mirror the legacy sampler
    (sample + heartbeat next-due, beat wins the mode label when both
    fire in one wake)."""

    __slots__ = (
        "path", "kind", "interval", "heartbeat", "suppress",
        "next_sample", "next_beat", "cursor", "members",
    )

    def __init__(self, spec: tuple, now: float, cursor: int) -> None:
        self.path, self.kind, self.interval, self.heartbeat, self.suppress = (
            spec
        )
        self.next_sample = now + self.interval if self.interval else None
        self.next_beat = now + self.heartbeat if self.heartbeat else None
        self.cursor = cursor
        self.members: list[_Member] = []

    def next_due(self) -> float | None:
        # All _Bucket state is guarded by the owning engine's lock.
        s, b = self.next_sample, self.next_beat
        if s is None:
            return b
        if b is None:
            return s
        return min(s, b)

    def advance_if_due(self, now: float) -> tuple[bool, bool]:
        beat = self.next_beat is not None and now >= self.next_beat
        sample = self.next_sample is not None and now >= self.next_sample
        while self.next_beat is not None and self.next_beat <= now:
            self.next_beat += self.heartbeat
        while self.next_sample is not None and self.next_sample <= now:
            self.next_sample += self.interval
        return beat, sample


def bucket_spec(sub, tick: float) -> tuple | None:
    """(path, kind, interval, heartbeat, suppress) for a
    ``pb.Subscription``, or None when it needs no engine timer.

    SAMPLE keeps its own interval (gNMI 0.8 default/floor rules);
    ON_CHANGE / TARGET_DEFINED ride the engine's base tick for real
    change delivery — an upgrade over the legacy path, where ON_CHANGE
    state subtrees only ever saw commit/yang notifications — plus
    their optional heartbeat."""
    gs = _pb()
    path = gs.path_to_str(sub.path)
    heartbeat = (
        max(sub.heartbeat_interval / 1e9, gs.MIN_SAMPLE_INTERVAL)
        if sub.heartbeat_interval
        else None
    )
    if sub.mode == gs.pb.SAMPLE:
        interval = max(
            sub.sample_interval / 1e9 or gs.DEFAULT_SAMPLE_INTERVAL,
            gs.MIN_SAMPLE_INTERVAL,
        )
        return (path, "sample", interval, heartbeat, bool(sub.suppress_redundant))
    # ON_CHANGE / TARGET_DEFINED: deltas at the base tick, suppressed
    # by construction (only changed leaves ever go out).
    interval = max(tick, gs.MIN_SAMPLE_INTERVAL) if tick else None
    if interval is None and heartbeat is None:
        return None
    return (path, "on-change", interval, heartbeat, True)


class FanoutEngine:
    """The shared-delta observatory: one snapshot + one change-set per
    coalesced tick epoch, rendered once per bucket, fanned out through
    the caller's bounded queues.

    ``fetch_state``   -> the full operational tree (one walk per tick);
    ``deliver(q, sid, notif, in_burst) -> bool``
                      -> bounded put with the caller's drop/burst
                         accounting (gnmi_server._deliver);
    ``burst_snapshot``-> set of sids currently in a drop burst;
    ``on_push(mode, n_updates)``
                      -> per-delivery metric hook (the legacy
                         holo_gnmi_sample_updates_total surface);
    ``clock``/``clock_ns``
                      -> bucket timers / notification timestamps
                         (injectable: virtual-clock storms and the
                         byte-identity test pin both).
    """

    def __init__(
        self,
        fetch_state,
        deliver,
        burst_snapshot=None,
        on_push=None,
        tick: float = 1.0,
        clock=time.monotonic,
        clock_ns=None,
        breaker_threshold: int = BREAKER_THRESHOLD,
        breaker_cooldown: float = BREAKER_COOLDOWN,
    ) -> None:
        self._fetch_state = fetch_state
        self._deliver = deliver
        self._burst_snapshot = burst_snapshot or (lambda: frozenset())
        self._on_push = on_push
        self.tick = tick
        self._clock = clock
        self._clock_ns = clock_ns or (lambda: int(time.time() * 1e9))
        self._lock = threading.Lock()
        # One tick at a time: the ticker thread and any manual
        # tick_now() driver (tests) serialize here, so the
        # store/diff path stays single-writer.
        self._tick_lock = threading.Lock()
        self._buckets: dict[tuple, _Bucket] = {}
        self._all_telemetry = True
        # Union of bucket subtree roots (None = some bucket wants the
        # whole tree): the fetch closure may scope its get_state walk
        # to these instead of snapshotting every provider per tick.
        self._roots: tuple | None = None
        # Leaf store + versioning.
        self._epoch = 0
        self._store: dict[str, object] = {}
        self._changed: dict[str, int] = {}  # path -> last-changed epoch
        self._recent: deque = deque(maxlen=RECENT_EPOCHS)  # (epoch, [paths])
        self._stamp: int | None = None  # registry stamp at last walk
        self._dirty = True  # external invalidation (commit/yang notify)
        # Shared render caches: `_rendered` memoizes one pb.Update per
        # leaf (invalidated when the leaf changes); `_cache` memoizes
        # whole notifications keyed (kind, path[, since]) and is
        # cleared on every epoch advance — a heartbeat over an
        # unchanged epoch is a pure cache hit.
        self._rendered: dict[str, object] = {}
        self._cache: dict[tuple, object] = {}
        # Breaker (SPF-plane discipline: consecutive failures open,
        # cooldown half-opens, a successful tick closes).
        self._threshold = breaker_threshold
        self._cooldown = breaker_cooldown
        self._failures = 0
        self._open_at: float | None = None
        # Ticker thread (lazy: parked until the first bucket exists).
        self._wake = threading.Event()
        self._thread: threading.Thread | None = None
        self._stopped = False

    # -- subscriber management ------------------------------------------

    def attach(self, q, sid: int, subscriptions) -> list | None:
        """Group a stream's subscriptions into interval buckets; returns
        an opaque handle for :meth:`detach`, or None when the breaker
        is open (the caller then runs the legacy walk path)."""
        if not self.healthy():
            _FALLBACK.labels(reason="breaker-open").inc()
            return None
        specs = [
            s
            for s in (bucket_spec(sub, self.tick) for sub in subscriptions)
            if s is not None
        ]
        if not specs:
            return []
        now = self._clock()
        handle = []
        with self._lock:
            for spec in specs:
                b = self._buckets.get(spec)
                if b is None:
                    b = _Bucket(spec, now, self._epoch)
                    self._buckets[spec] = b
                # EVERY new cursor owes a first full sampled push: a
                # change landing between the stream's preamble snapshot
                # and this attach would otherwise be silently lost (the
                # bucket cursor may already sit past the epoch the
                # client saw).
                m = _Member(q, sid, needs_full=True)
                b.members.append(m)
                handle.append((b, m))
            self._all_telemetry, self._roots = self._scope_of(self._buckets)
            self._update_gauges_locked()
        self._wake.set()
        return handle

    def detach(self, handle) -> None:
        if not handle:
            return
        with self._lock:
            for b, m in handle:
                try:
                    b.members.remove(m)
                except ValueError:
                    pass
                if not b.members:
                    self._buckets.pop(
                        (b.path, b.kind, b.interval, b.heartbeat, b.suppress),
                        None,
                    )
            self._all_telemetry, self._roots = self._scope_of(self._buckets)
            self._update_gauges_locked()

    @staticmethod
    def _scope_of(buckets) -> tuple:
        """(all_telemetry, roots) for a bucket table — pure, so the
        caller assigns both under its own lock hold.

        Roots are collapsed to COVERING prefixes (a bucket nested
        under another bucket's subtree adds no fetch work) and capped:
        every provider is consulted per get_state call, so past a few
        distinct roots one full-tree walk is cheaper than N scoped
        ones — the cap falls back to it."""
        all_telemetry = all(k[0].startswith(METRIC_ROOT) for k in buckets)
        paths = sorted({k[0] for k in buckets})
        if not paths or "" in paths:
            return all_telemetry, None
        covering: list[str] = []
        for p in paths:
            if not any(_match(c, p) for c in covering):
                covering.append(p)
        if len(covering) > MAX_SCOPED_ROOTS:
            return all_telemetry, None
        return all_telemetry, tuple(covering)

    def sample_roots(self) -> tuple | None:
        """Union of subscribed subtree roots, for scope-aware fetch
        closures (None = fetch the full tree)."""
        with self._lock:
            return self._roots

    def _update_gauges_locked(self) -> None:
        _SUBSCRIBERS.set(sum(len(b.members) for b in self._buckets.values()))
        _BUCKETS.set(len(self._buckets))

    def invalidate(self) -> None:
        """External state change (commit / yang notification): the next
        tick must walk even if the registry stamp is unchanged."""
        with self._lock:
            self._dirty = True
        self._wake.set()

    # -- breaker --------------------------------------------------------

    def healthy(self) -> bool:
        """False while the breaker is open; a cooldown later it
        half-opens (True) so new streams / the next tick probe it."""
        with self._lock:
            if self._open_at is None:
                return True
            if self._clock() - self._open_at >= self._cooldown:
                return True  # half-open: next failure re-opens
            return False

    def _note_failure(self, reason: str) -> None:
        _FALLBACK.labels(reason=reason).inc()
        with self._lock:
            self._failures += 1
            if self._failures >= self._threshold:
                opening = self._open_at is None
                self._open_at = self._clock()
            else:
                opening = False
        if opening:
            log.warning(
                "gNMI shared-delta fan-out breaker OPEN after %d "
                "consecutive tick failures; subscribers degrade to the "
                "per-subscriber walk path",
                self._failures,
            )

    # -- ticking --------------------------------------------------------

    def next_due(self) -> float | None:
        with self._lock:
            due = [b.next_due() for b in self._buckets.values()]
        due = [t for t in due if t is not None]
        return min(due) if due else None

    def tick_now(self, now: float | None = None, state=None) -> dict:
        """One coalesced tick: advance every due bucket against ONE
        state snapshot/epoch, render per bucket (shared cache), fan out
        to member queues.  Manual drivers (tests) may inject
        ``now`` and a pre-fetched ``state``."""
        with self._tick_lock:
            return self._tick_locked(now, state)

    def tick_guarded(self, now: float | None = None) -> dict | None:
        """The ticker's tick: any failure feeds the breaker (and the
        fallback counter) instead of propagating — subscribers degrade
        to the walk path, they never lose the stream."""
        try:
            return self.tick_now(now)
        except Exception as e:  # noqa: BLE001 — breaker + walk fallback
            log.debug("gNMI fan-out tick failed: %s", e, exc_info=True)
            self._note_failure(type(e).__name__)
            return None

    def _tick_locked(self, now, state) -> dict:
        if now is None:
            now = self._clock()
        with self._lock:
            due = []
            for b in self._buckets.values():
                nd = b.next_due()
                if nd is not None and now >= nd:
                    beat, sample = b.advance_if_due(now)
                    due.append((b, beat, sample, list(b.members), b.cursor))
        if not due:
            return {"fired": 0, "epoch": self._epoch}
        t0 = time.perf_counter()
        walked = False
        if state is not None:
            # An injected snapshot is authoritative (test drivers pin
            # the exact state both arms see): never skip it.
            self._refresh(state)
            walked = True
        elif not self._can_skip_walk():
            self._refresh(self._fetch_state())
            walked = True
        epoch = self._epoch
        t_walked = time.perf_counter() - t0
        bursts = self._burst_snapshot()
        delivered = dropped = 0
        t_render = 0.0

        def timed(render, *args):
            nonlocal t_render
            tr = time.perf_counter()
            try:
                return render(*args)
            finally:
                t_render += time.perf_counter() - tr

        for b, beat, sample, members, cursor in due:
            mode = (
                "heartbeat"
                if beat
                else ("sample" if b.kind == "sample" else "on-change")
            )
            # Lazy shared renders: each flavor's update list is
            # computed at most ONCE per bucket fire — and only when
            # some member actually needs it (a bucket of all-new
            # cursors never pays for the delta) — then wrapped in ONE
            # freshly-stamped Notification shared by every member.
            full_u = None
            full_notif = None
            delta_u = _UNSET
            delta_notif = None
            full_fire = beat or (sample and not b.suppress)
            for m in members:
                syncing = m.needs_full or full_fire
                if syncing:
                    # First sampled push is a full sync (shared: every
                    # member syncing this tick gets the same render);
                    # any full render (a beat) also settles the debt.
                    if full_u is None:
                        full_u = timed(self._render_full, b.path)
                    if full_notif is None and full_u:
                        full_notif = timed(self._notif_of, full_u)
                    out = full_notif
                else:
                    if delta_u is _UNSET:
                        delta_u = timed(
                            self._render_delta, b.path, cursor
                        )
                    if delta_notif is None and delta_u:
                        delta_notif = timed(self._notif_of, delta_u)
                    out = delta_notif
                if out is None:
                    continue
                if self._deliver(m.queue, m.sid, out, m.sid in bursts):
                    delivered += 1
                    if self._on_push is not None:
                        self._on_push(mode, len(out.update))
                    if m.needs_full:
                        # The baseline debt clears only on a CONFIRMED
                        # put: a full sync dropped on a full queue must
                        # retry at the next fire, or the cursor would
                        # serve deltas against a baseline the client
                        # never received.
                        m.needs_full = False
                else:
                    dropped += 1
            b.cursor = epoch
        with self._lock:
            self._failures = 0
            if self._open_at is not None:
                self._open_at = None
                log.info("gNMI shared-delta fan-out breaker closed")
        dt = time.perf_counter() - t0
        if walked or delivered:
            # Skipped-idle ticks stay out of the histogram AND out of
            # the write stamp: observing them would advance the stamp
            # and wake the next tick's walk for nothing.
            _TICK.observe(dt, exemplar={"epoch": epoch})
        if walked:
            # Stamp AFTER the engine's own per-tick metric observes:
            # the tick's bookkeeping must not wake the next tick's walk
            # (a feedback loop that would defeat the idle
            # short-circuit).  The price is a tick-execution-wide
            # masking window: a foreign write landing mid-tick is
            # folded into this stamp and its leaf stays stale until the
            # NEXT write anywhere — an eventually-consistent surface,
            # same as a scrape racing a write.
            with self._lock:
                self._stamp = telemetry.write_stamp()
        return {
            "fired": len(due),
            "epoch": epoch,
            "walked": walked,
            "delivered": delivered,
            "dropped": dropped,
            "tick_seconds": dt,
            # The O(1)-in-subscribers portion (snapshot+diff+render)
            # vs the O(subscribers) bounded-queue delivery floor (the
            # render share is what tests/test_gnmi_fanout.py::
            # test_bucket_shares_one_render_across_hundreds_of_cursors
            # holds constant in the subscriber count).
            "render_seconds": t_walked + t_render,
            "deliver_seconds": max(dt - t_walked - t_render, 0.0),
        }

    def _can_skip_walk(self) -> bool:
        """O(1) idle tick: every bucket under holo-telemetry, no
        callback-backed gauges live, nothing external invalidated the
        tree, and the registry write stamp unchanged since the last
        walk — the snapshot is provably byte-identical."""
        with self._lock:
            if self._dirty or self._stamp is None or not self._all_telemetry:
                return False
        return (
            telemetry.volatile_children() == 0
            and telemetry.write_stamp() == self._stamp
        )

    def _refresh(self, state) -> bool:
        """Diff one walked snapshot against the leaf store; advances the
        epoch iff anything changed."""
        gs = _pb()
        trees = state if isinstance(state, list) else [state]
        leaves = {
            p: v
            for tree in trees
            for p, v in gs._walk_leaves("", tree)
            if not p.startswith(SELF_ROOT)
        }
        store = self._store
        changed = [p for p, v in leaves.items() if store.get(p, _MISS) != v]
        removed = [p for p in store if p not in leaves]
        if not changed and not removed:
            with self._lock:
                self._dirty = False
            return False
        with self._lock:
            self._epoch += 1
            epoch = self._epoch
            for p in changed:
                store[p] = leaves[p]
                self._changed[p] = epoch
                self._rendered.pop(p, None)
            for p in removed:
                del store[p]
                self._changed.pop(p, None)
                self._rendered.pop(p, None)
            self._recent.append((epoch, changed))
            self._cache.clear()
            self._dirty = False
        _EPOCHS.inc()
        _LEAVES.observe(len(changed) + len(removed))
        return True

    # -- shared rendering -----------------------------------------------

    def _leaf_update(self, path: str):
        """One pb.Update per (leaf, value) — parsed/typed ONCE per
        change, shared by every notification that carries the leaf."""
        u = self._rendered.get(path)
        if u is None:
            gs = _pb()
            u = gs.pb.Update(
                path=gs.str_to_path(path),
                val=gs._typed_value(self._store[path]),
            )
            with self._lock:
                self._rendered[path] = u
        return u

    def _notif_of(self, updates):
        """One Notification per bucket fire: the update LIST is the
        cached/shared artifact; the timestamp is stamped fresh at push
        time so heartbeats over an unchanged epoch still read as live
        (the legacy walk path stamps every push too)."""
        gs = _pb()
        notif = gs.pb.Notification(timestamp=self._clock_ns())
        for u in updates:
            notif.update.add().CopyFrom(u)
        return notif

    def _updates(self, paths):
        return tuple(self._leaf_update(p) for p in sorted(paths))

    def _render_full(self, path: str):
        """Cached tuple of pb.Updates for the whole subtree (cleared
        only on epoch advance — a heartbeat over an unchanged epoch is
        a pure cache hit)."""
        key = ("full", path)
        if key in self._cache:
            _CACHE.labels(result="hit").inc()
            return self._cache[key]
        _CACHE.labels(result="miss").inc()
        updates = self._updates(
            [p for p in self._store if _match(path, p)]
        )
        _RENDERS.labels(kind="full").inc()
        with self._lock:
            self._cache[key] = updates
        return updates

    def _render_delta(self, path: str, since: int):
        """Updates for leaves whose last-changed epoch is newer than
        the cursor — the epoch-comparison replacement for the legacy
        value diff.  Returns None when nothing changed."""
        if since >= self._epoch:
            return None
        key = ("delta", path, since)
        if key in self._cache:
            _CACHE.labels(result="hit").inc()
            return self._cache[key]
        _CACHE.labels(result="miss").inc()
        if self._recent and self._recent[0][0] <= since + 1:
            cand: set[str] = set()
            for epoch, paths in reversed(self._recent):
                if epoch <= since:
                    break
                cand.update(paths)
            # Deletions between the cursor and now leave stale paths in
            # the window; the store lookup drops them.
            paths = [
                p for p in cand if p in self._store and _match(path, p)
            ]
        else:
            paths = [
                p
                for p, e in self._changed.items()
                if e > since and _match(path, p)
            ]
        updates = self._updates(paths) if paths else None
        if updates is not None:
            _RENDERS.labels(kind="delta").inc()
        with self._lock:
            self._cache[key] = updates
        return updates

    # -- ticker thread --------------------------------------------------

    def start(self) -> None:
        """Idempotent: spin the coalescing ticker up (parks while no
        buckets exist, so an idle service costs one blocked thread)."""
        with self._lock:
            if self._thread is not None:
                return
            self._stopped = False
            self._thread = threading.Thread(
                target=self._run, name="gnmi-fanout-ticker", daemon=True
            )
        self._thread.start()

    def stop(self) -> None:
        with self._lock:
            self._stopped = True
            t = self._thread
            self._thread = None
        self._wake.set()
        if t is not None:
            t.join(timeout=2.0)

    def _run(self) -> None:
        while not self._stopped:
            nd = self.next_due()
            if nd is None:
                self._wake.wait()
                self._wake.clear()
                continue
            now = self._clock()
            if nd > now:
                # Cap the sleep so attach()/invalidate() wakes and
                # clock skew (tests swapping clocks) resolve quickly.
                self._wake.wait(min(nd - now, 0.5))
                self._wake.clear()
                continue
            if self.tick_guarded(now) is None and not self.healthy():
                # Open: park for the cooldown (or an early wake).
                self._wake.wait(self._cooldown)
                self._wake.clear()

    # -- introspection --------------------------------------------------

    def stats(self) -> dict:
        with self._lock:
            n_members = sum(len(b.members) for b in self._buckets.values())
            state = (
                "closed"
                if self._open_at is None
                else (
                    "half-open"
                    if self._clock() - self._open_at >= self._cooldown
                    else "open"
                )
            )
            return {
                "epoch": self._epoch,
                "subscribers": n_members,
                "buckets": len(self._buckets),
                "leaves": len(self._store),
                "breaker": state,
                "consecutive-failures": self._failures,
                "all-telemetry": self._all_telemetry,
                "tick": self.tick,
            }


class _Miss:
    __slots__ = ()


_MISS = _Miss()
_UNSET = _Miss()
