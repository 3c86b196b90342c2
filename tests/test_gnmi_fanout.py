"""Shared-delta gNMI fan-out (ISSUE 11): epoch/versioning contract,
interval-bucket sharing, subscriber churn under a convergence storm,
breaker fallback to the per-subscriber walk path, and the subscriber-
lock discipline fix."""

import queue
import threading
import types

import pytest

import holo_tpu.daemon.gnmi_server as gs
from holo_tpu import telemetry
from holo_tpu.telemetry import delta, flight

# The package __init__ shadows the `registry` submodule with the
# registry() accessor function; reach the module through sys.modules.
import sys as _sys

registry_mod = _sys.modules["holo_tpu.telemetry.registry"]
from holo_tpu.telemetry.provider import TelemetryStateProvider


def _sub(path="", mode=None, interval_ns=0, suppress=False, heartbeat_ns=0):
    s = gs.pb.Subscription()
    if path:
        s.path.CopyFrom(gs.str_to_path(path))
    s.mode = mode if mode is not None else gs.pb.SAMPLE
    s.sample_interval = interval_ns
    s.suppress_redundant = suppress
    s.heartbeat_interval = heartbeat_ns
    return s


def _paths(notif):
    return [gs.path_to_str(u.path) for u in notif.update]


def _drain(q):
    out = []
    while True:
        try:
            out.append(q.get_nowait())
        except queue.Empty:
            return out


class _Harness:
    """FanoutEngine on a manual clock with injectable state trees —
    the engine without the gRPC plumbing around it."""

    def __init__(self, tick=1.0, **kw):
        self.now = 0.0
        self.state = {}
        self.dropped = []
        self.engine = delta.FanoutEngine(
            fetch_state=lambda: self.state,
            deliver=self._deliver,
            tick=tick,
            clock=lambda: self.now,
            # Timestamps carry the epoch id: monotonicity/torn-epoch
            # assertions read them straight off the wire format.
            clock_ns=lambda: self.engine._epoch,
            **kw,
        )

    def _deliver(self, q, sid, notif, in_burst):
        try:
            q.put_nowait(notif)
            return True
        except queue.Full:
            self.dropped.append(sid)
            return False

    def tick(self, advance=1.0, state=None):
        self.now += advance
        return self.engine.tick_now(self.now, state=state)


def _metric_state(**values):
    """A holo-telemetry-shaped state tree with the given metric leaves."""
    return {
        "holo-telemetry": {
            "metric": [
                {"name": k, "value": v, "labels": ""}
                for k, v in sorted(values.items())
            ]
        }
    }


# -- epoch / change-set contract -----------------------------------------


def test_epoch_advances_only_on_change_and_deltas_carry_changed_leaves():
    h = _Harness()
    h.state = _metric_state(a=1.0, b=2.0)
    q = queue.Queue(64)
    handle = h.engine.attach(
        q, 1, [_sub("holo-telemetry", interval_ns=int(1e9), suppress=True)]
    )
    assert handle
    r1 = h.tick()
    assert r1["fired"] == 1 and r1["epoch"] == 1 and r1["walked"]
    first = _drain(q)
    assert len(first) == 1  # full sync: every leaf, once
    assert "holo-telemetry/metric[a]/value" in _paths(first[0])
    assert "holo-telemetry/metric[b]/value" in _paths(first[0])
    # Unchanged tick: epoch holds, nothing is delivered.
    r2 = h.tick()
    assert r2["epoch"] == 1 and _drain(q) == []
    # One leaf moves: the delta carries exactly its changed leaves.
    h.state = _metric_state(a=1.0, b=3.0)
    r3 = h.tick()
    assert r3["epoch"] == 2
    (d,) = _drain(q)
    assert _paths(d) == ["holo-telemetry/metric[b]/value"]
    assert d.update[0].val.double_val == 3.0
    assert d.timestamp > first[0].timestamp  # monotonic epoch ids


def test_bucket_shares_one_render_across_hundreds_of_cursors():
    h = _Harness()
    h.state = _metric_state(**{f"m{i}": float(i) for i in range(50)})
    queues = [queue.Queue(8) for _ in range(300)]
    for i, q in enumerate(queues):
        h.engine.attach(
            q,
            i + 1,
            [_sub("holo-telemetry", interval_ns=int(1e9), suppress=True)],
        )
    def renders():
        snap = telemetry.snapshot(prefix="holo_gnmi_fanout_shared_renders")
        return sum(v for v in snap.values())

    r0 = renders()
    h.tick()
    notifs = [q.get_nowait() for q in queues]
    # Literally ONE shared object fanned out to all 300 queues.
    assert all(n is notifs[0] for n in notifs)
    assert renders() - r0 == 1
    # A delta tick shares the same way.
    h.state = _metric_state(
        **{f"m{i}": float(i) for i in range(49)} | {"m49": -1.0}
    )
    r1 = renders()
    h.tick()
    notifs = [q.get_nowait() for q in queues]
    assert all(n is notifs[0] for n in notifs)
    assert _paths(notifs[0]) == ["holo-telemetry/metric[m49]/value"]
    assert renders() - r1 == 1


def test_heartbeat_is_a_render_cache_hit_over_unchanged_epoch():
    h = _Harness()
    h.state = _metric_state(x=5.0)
    q = queue.Queue(64)
    h.engine.attach(
        q,
        1,
        [
            _sub(
                "holo-telemetry",
                interval_ns=int(1e9),
                suppress=True,
                heartbeat_ns=int(1e9),
            )
        ],
    )
    h.tick()  # full sync + cache fill
    _drain(q)

    def hits():
        return telemetry.snapshot(prefix="holo_gnmi_fanout_render").get(
            "holo_gnmi_fanout_render_cache_total{result=hit}", 0.0
        )

    h0 = hits()
    h.tick()  # unchanged: beat fires, full render reused from cache
    (beat,) = _drain(q)
    assert "holo-telemetry/metric[x]/value" in _paths(beat)
    assert hits() > h0


def test_late_joiner_first_notification_is_full_sync():
    h = _Harness()
    h.state = _metric_state(quiet=7.0, busy=0.0)
    q1 = queue.Queue(64)
    spec = [_sub("holo-telemetry", interval_ns=int(1e9), suppress=True)]
    h.engine.attach(q1, 1, spec)
    h.tick()
    h.state = _metric_state(quiet=7.0, busy=1.0)
    h.tick()
    _drain(q1)
    # Joiner after two epochs: its first push must be the FULL subtree
    # (including the quiet leaf that last changed at epoch 1), while
    # the veteran cursor sees only deltas.
    q2 = queue.Queue(64)
    h.engine.attach(q2, 2, spec)
    h.state = _metric_state(quiet=7.0, busy=2.0)
    h.tick()
    (vet,) = _drain(q1)
    (joiner,) = _drain(q2)
    assert _paths(vet) == ["holo-telemetry/metric[busy]/value"]
    assert "holo-telemetry/metric[quiet]/value" in _paths(joiner)
    assert "holo-telemetry/metric[busy]/value" in _paths(joiner)


# -- byte-identity vs the per-subscriber walk path -----------------------


def test_engine_output_byte_identical_to_legacy_walk_path():
    """The shared-render path and the legacy ``_SubSampler`` walk path
    stepped over the SAME state sequence at the SAME times produce
    byte-identical notification streams (the fallback contract)."""
    svc = gs.GnmiService(daemon=None, shared_fanout=False)
    svc._clock_ns = lambda: 777_000
    for suppress, heartbeat_ns in (
        (True, 0),
        (False, 0),
        (True, int(4e9)),
    ):
        h = _Harness()
        h.engine._clock_ns = lambda: 777_000
        sub = _sub(
            "holo-telemetry",
            interval_ns=int(1e9),
            suppress=suppress,
            heartbeat_ns=heartbeat_ns,
        )
        sampler = gs._SubSampler(sub, now=0.0)
        q = queue.Queue(1024)
        h.engine.attach(q, 1, [sub])
        engine_out, legacy_out = [], []
        vals = [
            {"a": 1.0, "b": 1.0},
            {"a": 1.0, "b": 2.0},
            {"a": 1.0, "b": 2.0},  # idle step
            {"a": 3.0, "b": 2.0},
            {"a": 3.0, "b": 2.0},
            {"a": 4.0, "b": 5.0},
            {"a": 4.0, "b": 5.0},
            {"a": 4.0, "b": 5.0},
            {"a": 9.0, "b": 5.0},
        ]
        for step, v in enumerate(vals, start=1):
            state = _metric_state(**v)
            h.tick(state=state)
            engine_out.extend(_drain(q))
            if sampler.advance_if_due(float(step)):
                out = svc._sample_notif(sampler, state)
                if out is not None:
                    legacy_out.append(out)
        assert [n.SerializeToString() for n in engine_out] == [
            n.SerializeToString() for n in legacy_out
        ], f"suppress={suppress} heartbeat={heartbeat_ns}"


# -- write-stamp short-circuit -------------------------------------------


def test_idle_ticks_skip_the_walk_under_an_unchanged_write_stamp():
    """Leaf-version stamping at write time (registry.py): with every
    bucket under holo-telemetry/metric and no registry writes, the
    engine proves the snapshot unchanged WITHOUT walking it."""
    probe = telemetry.counter("holo_fanout_skip_probe_total")
    probe.inc()
    provider = TelemetryStateProvider()
    walks = [0]

    def fetch():
        walks[0] += 1
        return provider.get_state(None)

    h = _Harness()
    h.engine._fetch_state = fetch
    q = queue.Queue(64)
    leaf = "holo-telemetry/metric[holo_fanout_skip_probe_total]/value"
    h.engine.attach(q, 1, [_sub(leaf, interval_ns=int(1e9), suppress=True)])
    # Callback-backed gauges registered by OTHER suites void the stamp
    # contract by design; pin the count to isolate the mechanism.
    saved = registry_mod._VOLATILE[0]
    registry_mod._VOLATILE[0] = 0
    try:
        r1 = h.tick()
        assert r1["walked"] and walks[0] == 1
        assert len(_drain(q)) == 1  # full sync
        r2 = h.tick()
        r3 = h.tick()
        assert not r2["walked"] and not r3["walked"]
        assert walks[0] == 1, "unchanged stamp must skip the walk"
        probe.inc()  # a stamped write re-arms the walk
        r4 = h.tick()
        assert r4["walked"] and walks[0] == 2
        (d,) = _drain(q)
        assert _paths(d) == [leaf]
        # External invalidation (commit/yang) also re-arms it.
        h.engine.invalidate()
        r5 = h.tick()
        assert r5["walked"] and walks[0] == 3
    finally:
        registry_mod._VOLATILE[0] = saved


def test_heartbeat_served_subscriber_quiesces_on_an_idle_system():
    """The engine's own bookkeeping (tick/cache/push counters) is
    stamped=False: serving heartbeats from the render cache must not
    re-arm the next tick's walk, or an idle system would churn
    forever (walk -> see own counters changed -> new epoch -> deliver
    -> bump -> walk ...)."""
    probe = telemetry.counter("holo_quiesce_probe_total")
    probe.inc()
    provider = TelemetryStateProvider()
    walks = [0]

    def fetch():
        walks[0] += 1
        return provider.get_state(None)

    # Service path: on_push (the stamped=False sample-updates counter)
    # fires per delivery, exactly the feedback loop under test.
    stub = types.SimpleNamespace(
        lock=threading.RLock(),
        northbound=types.SimpleNamespace(
            get_state=lambda p=None: provider.get_state(None)
        ),
    )
    svc = gs.GnmiService(stub, shared_fanout=True, fanout_tick=1.0)
    now = [0.0]
    eng = svc.fanout
    eng._clock = lambda: now[0]
    eng._fetch_state = fetch
    q = queue.Queue(64)
    leaf = "holo-telemetry/metric[holo_quiesce_probe_total]/value"
    eng.attach(
        q,
        svc._add_subscriber(q),
        [_sub(leaf, interval_ns=int(1e9), suppress=True,
              heartbeat_ns=int(1e9))],
    )
    saved = registry_mod._VOLATILE[0]
    registry_mod._VOLATILE[0] = 0
    try:
        now[0] = 1.0
        r1 = eng.tick_now(now[0])
        assert r1["walked"] and r1["delivered"] == 1 and walks[0] == 1
        for i in range(2, 6):
            now[0] = float(i)
            r = eng.tick_now(now[0])
            # Beats keep flowing (from the render cache) but the walk
            # never re-arms: the system is quiescent.
            assert r["delivered"] == 1 and not r["walked"]
        assert walks[0] == 1
        assert len(_drain(q)) == 5
    finally:
        registry_mod._VOLATILE[0] = saved


def test_fetch_scope_is_the_union_of_subscribed_roots():
    """A narrow subscription must not cost a full provider-tree walk:
    the service's fetch closure scopes get_state to the union of
    bucket roots (None only when some bucket wants the whole tree)."""
    seen = []
    stub = types.SimpleNamespace(
        lock=threading.RLock(),
        northbound=types.SimpleNamespace(
            get_state=lambda p=None: seen.append(p) or {}
        ),
    )
    svc = gs.GnmiService(stub, shared_fanout=True, fanout_tick=1.0)
    eng = svc.fanout
    assert eng.sample_roots() is None  # no buckets yet
    q1, q2 = queue.Queue(8), queue.Queue(8)
    h1 = eng.attach(
        q1, svc._add_subscriber(q1),
        [_sub("holo-telemetry/metric", interval_ns=int(1e9))],
    )
    eng.attach(
        q2, svc._add_subscriber(q2),
        [_sub("holo-runtime", interval_ns=int(1e9))],
    )
    assert eng.sample_roots() == ("holo-runtime", "holo-telemetry/metric")
    svc._fetch_state()
    assert seen == ["holo-runtime", "holo-telemetry/metric"]
    # A whole-tree subscription collapses the scope to a full walk.
    q3 = queue.Queue(8)
    h3 = eng.attach(
        q3, svc._add_subscriber(q3), [_sub("", interval_ns=int(1e9))]
    )
    assert eng.sample_roots() is None
    seen.clear()
    svc._fetch_state()
    assert seen == [None]
    eng.detach(h3)
    eng.detach(h1)
    assert eng.sample_roots() == ("holo-runtime",)
    # Nested roots collapse to their covering prefix; past the cap the
    # scope falls back to one full walk (every provider runs per
    # get_state call, so N scoped fetches can cost MORE than one).
    q4 = queue.Queue(8)
    eng.attach(
        q4, svc._add_subscriber(q4),
        [_sub("holo-runtime/main-loop", interval_ns=int(1e9))],
    )
    assert eng.sample_roots() == ("holo-runtime",)
    q5 = queue.Queue(8)
    eng.attach(
        q5, svc._add_subscriber(q5),
        [
            _sub(f"root{i}", interval_ns=int(1e9))
            for i in range(delta.MAX_SCOPED_ROOTS + 1)
        ],
    )
    assert eng.sample_roots() is None


def test_dropped_first_full_sync_retries_until_delivered():
    """The full-sync baseline debt clears only on a CONFIRMED put: a
    subscriber whose bounded queue was full at its first fire retries
    the full sync at the next fire instead of silently serving deltas
    against a baseline the client never saw."""
    h = _Harness()
    h.state = _metric_state(quiet=1.0, busy=0.0)
    slow: queue.Queue = queue.Queue(maxsize=1)
    slow.put_nowait("stuck")  # full before the first fire
    h.engine.attach(
        slow, 1,
        [_sub("holo-telemetry", interval_ns=int(1e9), suppress=True)],
    )
    r1 = h.tick()
    assert r1["dropped"] == 1 and r1["delivered"] == 0
    slow.get_nowait()  # consumer recovers
    h.state = _metric_state(quiet=1.0, busy=2.0)
    h.tick()
    (first,) = _drain(slow)
    # Retried FULL sync — not a delta missing the quiet leaf.
    assert "holo-telemetry/metric[quiet]/value" in _paths(first)
    assert "holo-telemetry/metric[busy]/value" in _paths(first)


def test_registry_write_stamp_and_volatility_accounting():
    s0 = telemetry.write_stamp()
    c = telemetry.counter("holo_stamp_unit_total")
    c.inc()
    assert telemetry.write_stamp() > s0
    assert c.labels().stamp == telemetry.write_stamp()
    g = telemetry.gauge("holo_stamp_unit_gauge")
    s1 = telemetry.write_stamp()
    g.set(4.0)
    assert telemetry.write_stamp() > s1
    v0 = telemetry.volatile_children()
    g.set_fn(lambda: 1.0)
    assert telemetry.volatile_children() == v0 + 1
    g.set_fn(None)
    assert telemetry.volatile_children() == v0


# -- breaker / fallback --------------------------------------------------


def test_breaker_opens_after_consecutive_failures_and_recovers():
    h = _Harness(breaker_threshold=3, breaker_cooldown=30.0)
    h.state = _metric_state(z=1.0)
    q = queue.Queue(8)
    spec = [_sub("holo-telemetry", interval_ns=int(1e9), suppress=True)]
    h.engine.attach(q, 1, spec)

    def boom():
        raise RuntimeError("provider exploded")

    good = h.engine._fetch_state
    h.engine._fetch_state = boom
    fb0 = sum(
        telemetry.snapshot(prefix="holo_gnmi_fanout_fallback").values()
    )
    for _ in range(3):
        h.now += 1.0
        assert h.engine.tick_guarded(h.now) is None
    assert not h.engine.healthy()
    assert h.engine.stats()["breaker"] == "open"
    # Open breaker refuses new cursors (streams run the walk path).
    assert h.engine.attach(queue.Queue(8), 2, spec) is None
    fb1 = sum(
        telemetry.snapshot(prefix="holo_gnmi_fanout_fallback").values()
    )
    assert fb1 - fb0 >= 4  # 3 tick failures + 1 refused attach
    # Cooldown elapses -> half-open; a successful tick closes.
    h.engine._fetch_state = good
    h.now += 31.0
    assert h.engine.healthy()
    assert h.engine.stats()["breaker"] == "half-open"
    assert h.engine.tick_guarded(h.now) is not None
    assert h.engine.stats()["breaker"] == "closed"


def test_stream_degrades_to_walk_path_when_breaker_opens():
    """E2E over real gRPC: a live SAMPLE stream keeps receiving pushes
    after the engine breaker opens — served by the legacy walk path,
    with the degradation counted."""
    import socket

    from holo_tpu.daemon.daemon import Daemon
    from holo_tpu.utils.runtime import EventLoop, VirtualClock

    marker = telemetry.counter("holo_degrade_probe_total")
    marker.inc(2)
    d = Daemon(loop=EventLoop(clock=VirtualClock()), name="deg")
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    server = gs.serve_gnmi(d, f"127.0.0.1:{port}")
    svc = d._gnmi_service
    try:
        cli = gs.GnmiClient(f"127.0.0.1:{port}")
        leaf = "holo-telemetry/metric[holo_degrade_probe_total]/value"
        req = gs.pb.SubscribeRequest()
        req.subscribe.mode = gs.pb.SubscriptionList.STREAM
        sub = req.subscribe.subscription.add()
        sub.path.CopyFrom(gs.str_to_path(leaf))
        sub.mode = gs.pb.SAMPLE
        sub.sample_interval = 50_000_000  # 50ms
        stream = cli.Subscribe(iter([req]))
        got = []
        done = threading.Event()
        poisoned = threading.Event()
        after = []

        def consume():
            for m in stream:
                if not (m.HasField("update") and m.update.update):
                    continue
                if not m.update.update[0].path.elem:
                    continue
                got.append(m.update)
                if poisoned.is_set():
                    after.append(m.update)
                    if len(after) >= 2:
                        done.set()
                        return

        t = threading.Thread(target=consume, daemon=True)
        t.start()
        deadline = threading.Event()
        for _ in range(100):
            if got:
                break
            deadline.wait(0.05)
        assert got, "engine path must push sampled leaves"
        # Poison the engine: the ticker's next fetches fail, the
        # breaker opens, and the stream must keep flowing on the
        # legacy samplers.
        def boom():
            raise RuntimeError("state provider down")

        # Park the ticker so its (possibly skip-path, hence successful)
        # ticks cannot reset the failure streak mid-forcing, then fail
        # deterministically: invalidate() forces a walk attempt, and a
        # future `now` keeps the bucket due each forced tick.
        svc.fanout.stop()
        svc.fanout._fetch_state = boom
        import time as time_mod

        ahead = time_mod.monotonic()
        for _ in range(svc.fanout._threshold):
            svc.fanout.invalidate()
            ahead += 1.0
            svc.fanout.tick_guarded(ahead)
        assert not svc.fanout.healthy()
        poisoned.set()
        assert done.wait(8.0), "stream must survive on the walk path"
        assert all(
            gs.path_to_str(u.path) == leaf
            for n in after
            for u in n.update
        )
        snap = telemetry.snapshot(prefix="holo_gnmi_fanout_fallback")
        assert sum(snap.values()) > 0
    finally:
        server.stop(grace=0)
        if svc.fanout is not None:
            svc.fanout.stop()


# -- lock discipline (satellite fix) -------------------------------------


def test_fanout_never_holds_subscriber_lock_during_puts():
    """HL203 surface: _fanout snapshots the copy-on-write subscriber
    tuple under the lock and performs EVERY put (and the drop path)
    after release — the Ibus._subs discipline."""
    svc = gs.GnmiService(daemon=None, shared_fanout=False)
    held = []

    class Probe:
        def __init__(self, full=False):
            self.full = full

        def put_nowait(self, item):
            held.append(svc._sub_lock.locked())
            if self.full:
                raise queue.Full

    ok_q, full_q = Probe(), Probe(full=True)
    svc._add_subscriber(ok_q)
    svc._add_subscriber(full_q)
    svc._fanout("n1")
    svc._fanout("n2")  # second round exercises the open-burst path
    assert held == [False] * 4
    svc._remove_subscriber(ok_q)
    svc._remove_subscriber(full_q)


def test_fanout_lock_hold_is_constant_in_subscriber_count():
    """The lock region is two reference reads: adding 500 subscribers
    must not change what happens under the lock (no per-queue work)."""
    svc = gs.GnmiService(daemon=None, shared_fanout=False)
    for _ in range(500):
        svc._add_subscriber(queue.Queue(maxsize=4))
    with svc._sub_lock:
        snap = svc._subscribers
        bursts = set(svc._bursts)
    assert isinstance(snap, tuple) and len(snap) == 500
    assert bursts == set()
    svc._fanout("x")
    assert all(q.qsize() == 1 for q, _ in snap)


# -- drop bursts through the shared path ---------------------------------


def test_shared_path_drop_bursts_reach_flight_ring_per_subscriber():
    """Forced slow consumer on the SHARED render path: the bounded
    queue drops, and the per-subscriber burst story lands in the
    flight ring exactly as on the legacy fanout path."""
    flight.configure(entries=1024)
    try:
        provider = TelemetryStateProvider()
        stub = types.SimpleNamespace(
            lock=threading.RLock(),
            northbound=types.SimpleNamespace(
                get_state=lambda p=None: provider.get_state(None)
            ),
        )
        svc = gs.GnmiService(stub, shared_fanout=True, fanout_tick=0.5)
        now = [0.0]
        svc.fanout._clock = lambda: now[0]
        beat = telemetry.counter("holo_burst_probe_total")
        slow: queue.Queue = queue.Queue(maxsize=1)
        sid = svc._add_subscriber(slow)
        svc.fanout.attach(
            slow,
            sid,
            [_sub("holo-telemetry/metric", interval_ns=int(5e8))],
        )
        for _ in range(4):  # 1 fills the queue, 3 drop
            beat.inc()
            now[0] += 0.5
            svc.fanout.tick_now(now[0])
        ring = flight.recorder().snapshot_ring()
        starts = [
            e
            for e in ring
            if e[0] == "event"
            and e[1] == "gnmi-drop-burst-start"
            and e[2]["subscriber"] == sid
        ]
        assert len(starts) == 1
        # Draining ends the burst on the next successful shared put.
        slow.get_nowait()
        beat.inc()
        now[0] += 0.5
        svc.fanout.tick_now(now[0])
        ring = flight.recorder().snapshot_ring()
        ends = [
            e
            for e in ring
            if e[0] == "event"
            and e[1] == "gnmi-drop-burst"
            and e[2]["subscriber"] == sid
        ]
        assert len(ends) == 1
        assert ends[0][2]["dropped"] == 3
        assert ends[0][2]["ended"] == "drained"
    finally:
        flight.configure(entries=0)


# -- churn under a convergence storm (satellite) -------------------------


def test_subscriber_churn_under_storm_never_observes_a_torn_epoch():
    """Subscribers joining/leaving mid-convergence-storm: monotonic
    epoch ids per session, first notification is a full sync, and
    correlated leaves always arrive from ONE epoch snapshot.  The
    storm's own causal digest is unaffected by the riding fleet."""
    from holo_tpu.spf.synth_storm import run_convergence_storm

    provider = TelemetryStateProvider()
    quiet = telemetry.counter("holo_churn_quiet_probe_total")
    quiet.inc(7)
    pair_a = telemetry.counter("holo_churn_pair_a_total")
    pair_b = telemetry.counter("holo_churn_pair_b_total")
    quiet_leaf = "holo-telemetry/metric[holo_churn_quiet_probe_total]/value"
    sessions: dict[int, list] = {}
    box: dict = {}

    def attach(net, sid):
        q = queue.Queue(4096)
        box["svc"].fanout.attach(
            q,
            sid,
            [_sub("holo-telemetry/metric", interval_ns=int(5e8),
                  suppress=True)],
        )
        sessions[sid] = []
        box.setdefault("queues", {})[sid] = q

    def hook(net, i, now):
        if "svc" not in box:
            stub = types.SimpleNamespace(
                lock=threading.RLock(),
                northbound=types.SimpleNamespace(
                    get_state=lambda p=None: provider.get_state(None)
                ),
            )
            svc = gs.GnmiService(stub, shared_fanout=True, fanout_tick=0.5)
            svc.fanout._clock = net.loop.clock.now
            svc.fanout._clock_ns = lambda: svc.fanout._epoch
            box["svc"] = svc
        if i == 3:
            attach(net, 1)
            attach(net, 2)
        if i == 20:
            attach(net, 3)  # joins mid-storm
        # Correlated writes BEFORE the tick: any notification carrying
        # both leaves must show them equal (one epoch snapshot).
        pair_a.inc()
        pair_b.inc()
        box["svc"].fanout.tick_now(now)
        for sid, q in box.get("queues", {}).items():
            sessions[sid].extend(_drain(q))
        if i == 35 and 2 in box["queues"]:
            handlebars = box["queues"].pop(2)  # leaves mid-storm
            box["svc"]._remove_subscriber(handlebars)

    _report, digest, _net = run_convergence_storm(
        n_routers=120, events=50, seed=11, event_hook=hook
    )
    _r2, digest_control, _n2 = run_convergence_storm(
        n_routers=120, events=50, seed=11
    )
    assert digest == digest_control, "riding fleet must not perturb the storm"
    assert set(sessions) == {1, 2, 3}
    a_leaf = "holo-telemetry/metric[holo_churn_pair_a_total]/value"
    b_leaf = "holo-telemetry/metric[holo_churn_pair_b_total]/value"
    for sid, notifs in sessions.items():
        assert notifs, f"session {sid} saw no pushes"
        # First notification is a full sync: it carries the quiet leaf
        # (which never changes during the storm); deltas never do.
        assert quiet_leaf in _paths(notifs[0])
        for later in notifs[1:]:
            assert quiet_leaf not in _paths(later)
        # Monotonic epoch ids per session (timestamps carry epochs).
        stamps = [n.timestamp for n in notifs]
        assert stamps == sorted(stamps)
        assert len(set(stamps)) == len(stamps)
        # No torn epoch: correlated counters always arrive equal.
        for n in notifs:
            vals = {
                gs.path_to_str(u.path): u.val.double_val for u in n.update
            }
            if a_leaf in vals and b_leaf in vals:
                assert vals[a_leaf] == vals[b_leaf]
        # The mid-storm joiner's first epoch is later than a founder's.
    assert sessions[3][0].timestamp > sessions[1][0].timestamp


# -- config / provider surfaces ------------------------------------------


def test_config_parses_fanout_and_device_trace_keys(tmp_path):
    from holo_tpu.daemon.config import DaemonConfig

    p = tmp_path / "holod.toml"
    p.write_text(
        """
[telemetry]
enabled = false
gnmi-shared-fanout = false
fanout-tick = 0.25
device-trace-dir = "/tmp/holo-trace"
"""
    )
    cfg = DaemonConfig.load(str(p))
    assert cfg.telemetry.gnmi_shared_fanout is False
    assert cfg.telemetry.fanout_tick == 0.25
    assert cfg.telemetry.device_trace_dir == "/tmp/holo-trace"
    # Defaults: engine on, 1s tick, no trace dir.
    dflt = DaemonConfig()
    assert dflt.telemetry.gnmi_shared_fanout is True
    assert dflt.telemetry.fanout_tick == 1.0
    assert dflt.telemetry.device_trace_dir is None


def test_provider_surfaces_fanout_stats_leaf():
    h = _Harness()
    delta.register_engine(h.engine)
    h.state = _metric_state(p=1.0)
    q = queue.Queue(8)
    h.engine.attach(
        q, 1, [_sub("holo-telemetry", interval_ns=int(1e9))]
    )
    h.tick()
    state = TelemetryStateProvider().get_state()
    rows = state["holo-telemetry"].get("gnmi-fanout")
    assert rows is not None
    row = rows if isinstance(rows, dict) else rows[0]
    found = [
        r
        for r in ([row] if isinstance(row, dict) else row)
        if r.get("subscribers", -1) >= 0
    ]
    assert found and found[0]["breaker"] in ("closed", "open", "half-open")


def test_capture_device_trace_without_tpu_is_explicit_not_captured(tmp_path):
    from holo_tpu.telemetry import profiling

    row = profiling.capture_device_trace(tmp_path / "trace")
    assert row["captured"] is False
    assert row["platform"] == "cpu"
    assert "no TPU attached" in row["reason"]


def test_daemon_boot_with_device_trace_dir_never_fails(tmp_path):
    from holo_tpu.daemon.config import DaemonConfig
    from holo_tpu.daemon.daemon import Daemon
    from holo_tpu.utils.runtime import EventLoop, VirtualClock

    cfg = DaemonConfig()
    cfg.telemetry.device_trace_dir = str(tmp_path / "trace")
    d = Daemon(config=cfg, loop=EventLoop(clock=VirtualClock()), name="dtr")
    assert d._device_trace is not None
    assert d._device_trace["captured"] is False
    assert d._device_trace["platform"] == "cpu"


def test_on_change_sessions_receive_deltas_at_the_base_tick():
    """ON_CHANGE is a first-class citizen of the delta engine: state
    subtree changes reach ON_CHANGE cursors at the base tick (the
    legacy path only ever served them commit/yang notifications and
    heartbeats)."""
    h = _Harness(tick=0.5)
    h.state = _metric_state(oc=1.0)
    q = queue.Queue(64)
    h.engine.attach(
        q, 1, [_sub("holo-telemetry", mode=gs.pb.ON_CHANGE)]
    )
    h.tick(advance=0.5)
    # ON_CHANGE join: the Subscribe preamble is the sync — the first
    # engine epoch (all leaves "changed") does flow, after which only
    # real changes do.
    _drain(q)
    h.tick(advance=0.5)
    assert _drain(q) == []  # no change, no push
    h.state = _metric_state(oc=2.0)
    h.tick(advance=0.5)
    (d,) = _drain(q)
    assert _paths(d) == ["holo-telemetry/metric[oc]/value"]
    snap = telemetry.snapshot(prefix="holo_gnmi_sample")
    # Engine-side pushes ride the same updates counter under their own
    # mode label when wired through the service; the harness has no
    # on_push -> no assertion on the label here.
    assert snap is not None
