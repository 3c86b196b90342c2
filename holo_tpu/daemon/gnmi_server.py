"""gNMI service: Capabilities / Get / Set / Subscribe over the northbound.

Reference: holo-daemon gNMI plugin (client/gnmi.rs:49-268) — Get merges
config+state, Set runs one transaction per request, Subscribe streams
notifications.  gNMI paths map to the YANG-lite tree: path elems with keys
become the bracket path segments (``interface[name=eth0]`` ->
``interface[eth0]``).

STREAM serving scale (ISSUE 11): SAMPLE / ON_CHANGE subscriptions are
normally cheap epoch cursors inside the shared-delta
:class:`holo_tpu.telemetry.delta.FanoutEngine` — one state snapshot,
one change-set, and one render per coalesced tick epoch, fanned out to
every due subscriber through the bounded per-subscriber queues.  The
per-subscriber walk path (``_SubSampler``) remains as the
byte-identical fallback when the engine is disabled or its breaker
opens.
"""

from __future__ import annotations

import json
import queue
import sys
import threading
import time
from concurrent import futures
from pathlib import Path as FsPath

import grpc

sys.path.insert(0, str(FsPath(__file__).resolve().parent))
import gnmi_lite_pb2 as pb  # noqa: E402

import holo_tpu
from holo_tpu import telemetry
from holo_tpu.northbound.provider import CommitError
from holo_tpu.telemetry import delta as fanout_delta
from holo_tpu.telemetry import flight
from holo_tpu.yang.schema import SchemaError

# Subscribe-path hardening metrics: per-subscriber queues are bounded
# (SUBSCRIBE_QUEUE_DEPTH) so a stalled consumer costs dropped updates —
# counted here — instead of unbounded daemon memory.  Delivery-side
# tallies are stamped=False: they bump WHILE the delta engine serves a
# push, and re-arming the next tick's walk with our own bookkeeping
# would keep an idle system churning forever (registry.py rationale).
_SUB_DROPS = telemetry.counter(
    "holo_gnmi_subscribe_dropped_total",
    "gNMI Subscribe updates dropped on a full subscriber queue",
    stamped=False,
)
_SUBSCRIBERS = telemetry.gauge(
    "holo_gnmi_subscribers", "Active gNMI Subscribe streams"
)
_SAMPLE_UPDATES = telemetry.counter(
    "holo_gnmi_sample_updates_total",
    "Leaf updates pushed by SAMPLE / heartbeat subscription timers",
    ("mode",),
    stamped=False,
)

SUBSCRIBE_QUEUE_DEPTH = 256
# SAMPLE subscriptions leaving sample_interval at 0 get the
# target-chosen default (gNMI spec wording); a floor keeps a hostile
# 1ns interval from spinning the stream thread.
DEFAULT_SAMPLE_INTERVAL = 1.0
MIN_SAMPLE_INTERVAL = 0.01


class _SubSampler:
    """Per-subscription STREAM timer state (gNMI 0.8 semantics) — the
    per-subscriber WALK path.

    Since ISSUE 11 this is the fallback arm: streams normally attach to
    the shared-delta :class:`holo_tpu.telemetry.delta.FanoutEngine`
    (one snapshot + one render per tick epoch, shared across every due
    subscriber) and only run these samplers when the engine is disabled
    or its breaker opened.  The semantics here are the byte-identical
    contract the engine is graded against (``tests/test_gnmi_fanout.py::
    test_engine_output_byte_identical_to_legacy_walk_path``).

    - ``SAMPLE``: push the subscribed subtree's scalar leaves every
      ``sample_interval`` (ns).  With ``suppress_redundant`` only leaves
      whose value changed since the last push go out; a non-zero
      ``heartbeat_interval`` forces a full resend at each beat so a
      quiet leaf still proves liveness.
    - ``ON_CHANGE`` / ``TARGET_DEFINED`` with ``heartbeat_interval``:
      the notification fanout carries the changes; this timer resends
      the current (unchanged) leaves at each beat.

    Samplers run on the stream's own generator thread and bypass the
    bounded fanout queue entirely — gRPC flow control is their
    backpressure, so the overflow-drop counter keeps meaning exactly
    "fanout updates lost to a stalled consumer".
    """

    def __init__(self, sub, now: float | None = None) -> None:
        if now is None:
            now = time.monotonic()
        self.path = path_to_str(sub.path)
        self.suppress = bool(sub.suppress_redundant)
        self.interval = None
        if sub.mode == pb.SAMPLE:
            self.interval = max(
                sub.sample_interval / 1e9 or DEFAULT_SAMPLE_INTERVAL,
                MIN_SAMPLE_INTERVAL,
            )
        self.heartbeat = (
            max(sub.heartbeat_interval / 1e9, MIN_SAMPLE_INTERVAL)
            if sub.heartbeat_interval
            else None
        )
        self.next_sample = now + self.interval if self.interval else None
        self.next_beat = now + self.heartbeat if self.heartbeat else None
        self.last: dict[str, object] = {}
        self.fired = (False, False)  # (beat, sample) of the last advance

    @property
    def active(self) -> bool:
        return self.next_sample is not None or self.next_beat is not None

    def next_due(self) -> float | None:
        due = [t for t in (self.next_sample, self.next_beat) if t is not None]
        return min(due) if due else None

    def advance_if_due(self, now: float) -> bool:
        """True when a beat or sample tick is due; advances the timers
        and remembers which fired (read by the renderer)."""
        beat = self.next_beat is not None and now >= self.next_beat
        sample = self.next_sample is not None and now >= self.next_sample
        if not (beat or sample):
            return False
        while self.next_beat is not None and self.next_beat <= now:
            self.next_beat += self.heartbeat
        while self.next_sample is not None and self.next_sample <= now:
            self.next_sample += self.interval
        self.fired = (beat, sample)
        return True


def path_to_str(path: pb.Path) -> str:
    segs = []
    for elem in path.elem:
        if elem.key:
            # single-key lists: the key value is the instance selector
            key = next(iter(elem.key.values()))
            segs.append(f"{elem.name}[{key}]")
        else:
            segs.append(elem.name)
    return "/".join(segs)


def str_to_path(s: str) -> pb.Path:
    from holo_tpu.yang.schema import parse_path

    p = pb.Path()
    for name, key in parse_path(s):
        e = p.elem.add()
        e.name = name
        if key is not None:
            e.key["name"] = key
    return p


class GnmiService:
    def __init__(
        self,
        daemon,
        shared_fanout: bool = True,
        fanout_tick: float = 1.0,
    ):
        self.daemon = daemon
        # Copy-on-write subscriber snapshot (ISSUE 11 lock-discipline
        # fix): an immutable tuple of (queue, ordinal) pairs rebuilt on
        # add/remove, so _fanout's lock hold is two reference reads —
        # never per-subscriber work — matching the Ibus._subs
        # snapshot-then-release discipline (HL203 surface).
        self._subscribers: tuple = ()
        self._sub_lock = threading.Lock()
        # Per-subscriber identity + drop-burst tracking (ISSUE 6
        # carry-over from PR 5): subscriber ordinal -> consecutive
        # drops in the current burst.  Burst edges land in the
        # flight-recorder ring so a postmortem bundle shows WHICH
        # subscriber was shedding and when — the aggregate counter
        # alone cannot answer that.
        self._sub_ids: dict[int, int] = {}  # id(queue) -> ordinal
        self._next_sub = 0
        self._bursts: dict[int, int] = {}  # ordinal -> burst depth
        # Injectable notification timestamp source: the byte-identity
        # test pins it so the shared-render and walk paths stamp
        # identically.
        self._clock_ns = lambda: int(time.time() * 1e9)
        # Shared-delta fan-out engine (ISSUE 11): one state snapshot +
        # one render per tick epoch, shared across all due subscribers.
        self.fanout = None
        if shared_fanout:
            self.fanout = fanout_delta.FanoutEngine(
                fetch_state=self._fetch_state,
                deliver=self._deliver,
                burst_snapshot=self._burst_snapshot,
                on_push=self._count_push,
                tick=fanout_tick,
                clock_ns=lambda: self._clock_ns(),
            )
            fanout_delta.register_engine(self.fanout)

    def _fetch_state(self):
        """Scope-aware snapshot for the delta engine: fetch only the
        union of subscribed subtree roots (ONE lock acquisition, the
        legacy wake-loop discipline) — a narrow subscription must not
        cost a full provider-tree walk per tick."""
        roots = self.fanout.sample_roots() if self.fanout else None
        with self.daemon.lock:
            nb = self.daemon.northbound
            if roots is None:
                return nb.get_state(None)
            return [nb.get_state(r or None) for r in roots]

    @staticmethod
    def _count_push(mode: str, n_updates: int) -> None:
        _SAMPLE_UPDATES.labels(mode=mode).inc(n_updates)

    def _add_subscriber(self, q: queue.Queue) -> int:
        with self._sub_lock:
            self._next_sub += 1
            sid = self._next_sub
            self._sub_ids[id(q)] = sid
            self._subscribers = self._subscribers + ((q, sid),)
            _SUBSCRIBERS.set(len(self._subscribers))
        return sid

    def _remove_subscriber(self, q: queue.Queue) -> None:
        """Idempotent removal: the stream's finally block AND any future
        notify-side eviction may both call this — a double remove must
        not raise inside a gRPC generator teardown.  The gauge updates
        under the same lock so concurrent teardowns cannot publish a
        stale count."""
        with self._sub_lock:
            self._subscribers = tuple(
                (qq, s) for qq, s in self._subscribers if qq is not q
            )
            sid = self._sub_ids.pop(id(q), None)
            burst = self._bursts.pop(sid, 0) if sid is not None else 0
            _SUBSCRIBERS.set(len(self._subscribers))
        if burst:
            # The subscriber died mid-burst: close the story in the ring.
            flight.event(
                "gnmi-drop-burst", subscriber=sid, dropped=burst,
                ended="disconnect",
            )

    def _burst_snapshot(self) -> set:
        """Ordinals currently mid-burst (O(open bursts), usually 0)."""
        with self._sub_lock:
            return set(self._bursts)

    def _deliver(self, q, sid: int, notif, in_burst: bool) -> bool:
        """Bounded best-effort put with per-subscriber drop-burst
        accounting — shared by the on-change fanout and the delta
        engine's shared-render pushes.  Burst edges (first drop; first
        successful put after drops) land in the flight ring; the
        subscriber lock is only taken ON an edge, never on the healthy
        path."""
        try:
            q.put_nowait(notif)
        except queue.Full:
            _SUB_DROPS.inc()
            with self._sub_lock:
                if id(q) not in self._sub_ids:
                    # Removed concurrently: _remove_subscriber already
                    # closed (or owns) this burst story — re-creating
                    # the entry would leak it forever.
                    depth = 0
                else:
                    depth = self._bursts.get(sid, 0) + 1
                    self._bursts[sid] = depth
            if depth == 1:
                flight.event("gnmi-drop-burst-start", subscriber=sid)
            return False
        if in_burst:
            with self._sub_lock:
                burst = self._bursts.pop(sid, 0)
            if burst:
                flight.event(
                    "gnmi-drop-burst", subscriber=sid, dropped=burst,
                    ended="drained",
                )
        return True

    def _fanout(self, notif) -> None:
        """Best-effort delivery to every subscriber: bounded queues drop
        (and count) on overflow rather than block the publisher or grow
        memory for a stalled consumer.  The lock is held for two
        reference reads (copy-on-write snapshot + open-burst set);
        every put and burst edge happens after release."""
        with self._sub_lock:
            targets = self._subscribers
            bursts = set(self._bursts)
        for q, sid in targets:
            self._deliver(q, sid, notif, sid in bursts)

    def Capabilities(self, request, context):
        resp = pb.CapabilityResponse(
            supported_encodings=["JSON_IETF", "PROTO"],
            gNMI_version="0.8.0-lite",
        )
        for name in sorted(self.daemon.northbound.schema.roots.keys()):
            resp.supported_models.add(
                name=name, organization="holo_tpu", version=holo_tpu.__version__
            )
        return resp

    def Get(self, request, context):
        with self.daemon.lock:
            nb = self.daemon.northbound
            notif = pb.Notification(timestamp=int(time.time() * 1e9))
            paths = list(request.path) or [pb.Path()]
            for path in paths:
                try:
                    self._get_one(nb, request, notif, path)
                except SchemaError as e:
                    context.abort(grpc.StatusCode.INVALID_ARGUMENT, str(e))
        return pb.GetResponse(notification=[notif])

    def _get_one(self, nb, request, notif, path):
        pstr = path_to_str(path)
        payload = {}
        if request.type in (pb.GetRequest.ALL, pb.GetRequest.CONFIG):
            val = (
                json.loads(nb.running.to_json())
                if not pstr
                else nb.running.get(pstr)
            )
            if val is not None:
                payload["config"] = val
        if request.type in (
            pb.GetRequest.ALL,
            pb.GetRequest.STATE,
            pb.GetRequest.OPERATIONAL,
        ):
            state = nb.get_state(pstr or None)
            if state:
                payload["state"] = state
        if request.encoding == pb.PROTO:
            # Proto-encoded updates: one Update per scalar leaf with a
            # native TypedValue (reference gnmi.rs gen_update_proto).
            # Leaves are rooted at the requested path (no config/state
            # wrapper segments) so returned paths round-trip into Set;
            # when both planes are requested, state wins on overlap.
            leaves: dict[str, object] = {}
            for section in ("config", "state"):
                if section in payload:
                    for leaf_path, value in _walk_leaves(
                        pstr, payload[section]
                    ):
                        leaves[leaf_path] = value
            for leaf_path, value in leaves.items():
                notif.update.add(
                    path=str_to_path(leaf_path),
                    val=_typed_value(value),
                )
            return
        notif.update.add(
            path=path,
            val=pb.TypedValue(json_ietf_val=json.dumps(payload, default=str)),
        )

    def Set(self, request, context):
        nb = self.daemon.northbound
        results = []
        try:
            with self.daemon.lock:
                cand = nb.running.copy()
                for path in request.delete:
                    cand.delete(path_to_str(path))
                    results.append(
                        pb.UpdateResult(path=path, op=pb.UpdateResult.DELETE)
                    )
                n_replace = len(request.replace)
                for i, upd in enumerate(
                    list(request.replace) + list(request.update)
                ):
                    is_replace = i < n_replace
                    pstr = path_to_str(upd.path)
                    if is_replace:
                        # gNMI Replace semantics: the subtree is replaced,
                        # not merged — leaves absent from the payload go.
                        cand.delete(pstr)
                    v = upd.val
                    which = v.WhichOneof("value")
                    if which == "json_ietf_val":
                        sub = json.loads(v.json_ietf_val)
                        _apply_json(cand, pstr, sub)
                    elif which is not None:
                        cand.set(pstr, getattr(v, which))
                    else:
                        cand.set(pstr)
                    op = (
                        pb.UpdateResult.REPLACE
                        if is_replace
                        else pb.UpdateResult.UPDATE
                    )
                    results.append(pb.UpdateResult(path=upd.path, op=op))
                txn = self.daemon.commit(cand, comment="gnmi-set")
        except (SchemaError, CommitError) as e:
            context.abort(grpc.StatusCode.INVALID_ARGUMENT, str(e))
        return pb.SetResponse(
            response=results, timestamp=int(time.time() * 1e9)
        )

    def Subscribe(self, request_iterator, context):
        q: queue.Queue = queue.Queue(maxsize=SUBSCRIBE_QUEUE_DEPTH)
        sid = self._add_subscriber(q)
        handle = None
        try:
            first = next(iter(request_iterator), None)
            # Initial sync: current state snapshot then sync_response.
            with self.daemon.lock:
                state = self.daemon.northbound.get_state(None)
            notif = pb.Notification(timestamp=self._clock_ns())
            notif.update.add(
                path=pb.Path(),
                val=pb.TypedValue(json_ietf_val=json.dumps(state, default=str)),
            )
            yield pb.SubscribeResponse(update=notif)
            yield pb.SubscribeResponse(sync_response=True)
            if (
                first is not None
                and first.subscribe.mode == pb.SubscriptionList.ONCE
            ):
                return
            # STREAM: the bounded fanout queue carries on-change
            # notifications, and — shared-delta path (ISSUE 11) — the
            # fan-out engine's shared rendered pushes: this stream is
            # then a cheap epoch cursor inside the engine's interval
            # buckets and the loop below is a pure queue drain.
            if (
                self.fanout is not None
                and first is not None
                and first.HasField("subscribe")
            ):
                handle = self.fanout.attach(
                    q, sid, first.subscribe.subscription
                )
            # Fallback contract: engine disabled or breaker open —
            # per-subscription samplers walk the subtree on this
            # stream's own timers (the pre-ISSUE-11 path, byte-
            # identical output).
            samplers = (
                self._make_samplers(first) if handle is None else []
            )
            while context.is_active():
                if handle is not None:
                    if not self.fanout.healthy():
                        # Engine breaker opened mid-stream: degrade to
                        # the walk path for the rest of this stream.
                        self.fanout.detach(handle)
                        handle = None
                        samplers = self._make_samplers(first)
                        continue
                    try:
                        notif = q.get(timeout=0.25)
                        yield pb.SubscribeResponse(update=notif)
                    except queue.Empty:
                        pass
                    continue
                wait = 1.0
                now = time.monotonic()
                for s in samplers:
                    due = s.next_due()
                    if due is not None:
                        wait = min(wait, due - now)
                try:
                    notif = q.get(timeout=max(wait, 0.005))
                    yield pb.SubscribeResponse(update=notif)
                except queue.Empty:
                    pass
                now = time.monotonic()
                due = [s for s in samplers if s.advance_if_due(now)]
                if due:
                    # One state fetch per distinct path per wake, under
                    # ONE lock acquisition: N samplers coming due
                    # together must not serialize N full provider-tree
                    # walks against the commit path.
                    states = {}
                    with self.daemon.lock:
                        for p in {s.path for s in due}:
                            states[p] = self.daemon.northbound.get_state(
                                p or None
                            )
                    for s in due:
                        out = self._sample_notif(s, states[s.path])
                        if out is not None:
                            yield pb.SubscribeResponse(update=out)
        finally:
            if handle is not None:
                self.fanout.detach(handle)
            self._remove_subscriber(q)

    @staticmethod
    def _make_samplers(first) -> list[_SubSampler]:
        if first is None or not first.HasField("subscribe"):
            return []
        return [
            s
            for s in map(_SubSampler, first.subscribe.subscription)
            if s.active
        ]

    def _sample_notif(self, s: _SubSampler, state):
        """Render one due sampler's updates from an already-fetched
        state tree (None when every leaf was suppressed as redundant)."""
        beat, sample = s.fired
        leaves = {
            p: v
            for p, v in _walk_leaves("", state)
            if not s.path
            or p == s.path
            or p.startswith((s.path + "/", s.path + "["))
        }
        # A heartbeat resends everything; a suppress-redundant sample
        # pushes only leaves whose value moved since the last push.
        out = {
            p: v
            for p, v in leaves.items()
            if beat or not (sample and s.suppress and s.last.get(p) == v)
        }
        s.last = leaves
        if not out:
            return None
        notif = pb.Notification(timestamp=self._clock_ns())
        for p, v in sorted(out.items()):
            notif.update.add(path=str_to_path(p), val=_typed_value(v))
        # A beat forcing the resend wins the label even when a sample
        # tick is due in the same wake — it is what put the unchanged
        # leaves back on the wire.
        _SAMPLE_UPDATES.labels(mode="heartbeat" if beat else "sample").inc(
            len(out)
        )
        return notif

    def _notify_yang(self, payload: dict) -> None:
        # Protocol YANG notifications ride the same update stream, one
        # update per notification keyed by its qualified name.  The
        # delta engine's stamp short-circuit is voided: protocol state
        # moved outside the metrics registry.
        if self.fanout is not None:
            self.fanout.invalidate()
        for kind, body in payload.items():
            notif = pb.Notification(timestamp=self._clock_ns())
            notif.update.add(
                path=str_to_path(kind),
                val=pb.TypedValue(
                    json_ietf_val=json.dumps(body, default=str)
                ),
            )
            self._fanout(notif)

    def _notify_commit(self, txn) -> None:
        if self.fanout is not None:
            self.fanout.invalidate()
        notif = pb.Notification(timestamp=self._clock_ns())
        notif.update.add(
            path=str_to_path("transactions"),
            val=pb.TypedValue(
                json_ietf_val=json.dumps(
                    {"transaction-id": txn.id, "comment": txn.comment}
                )
            ),
        )
        self._fanout(notif)


def _typed_value(value) -> pb.TypedValue:
    """Scalar -> native gNMI TypedValue (gnmi.rs:332-388 proto arm)."""
    if isinstance(value, bool):
        return pb.TypedValue(bool_val=value)
    if isinstance(value, int):
        if value < 0:
            return pb.TypedValue(int_val=value)
        return pb.TypedValue(uint_val=value)
    if isinstance(value, float):
        return pb.TypedValue(double_val=value)
    return pb.TypedValue(string_val=str(value))


def _walk_leaves(base: str, tree):
    """Yield (path, scalar) for every leaf under a JSON state tree.

    List entries use the value of their first key-ish member ("name",
    else the first scalar) as the gNMI path key segment.
    """
    if not isinstance(tree, (dict, list)):
        yield base, tree
        return
    if isinstance(tree, dict):
        for k, v in tree.items():
            sub = f"{base}/{k}" if base else str(k)
            yield from _walk_leaves(sub, v)
        return
    if all(not isinstance(e, dict) for e in tree):
        # Leaf-list: one update carrying the whole array (our lite
        # proto has no ScalarArray; JSON keeps the path unique).
        yield base, json.dumps(tree, default=str)
        return
    for i, entry in enumerate(tree):
        if isinstance(entry, dict):
            key = entry.get("name")
            if key is None:
                key = next(
                    (
                        v
                        for v in entry.values()
                        if not isinstance(v, (dict, list))
                    ),
                    None,
                )
            sub = f"{base}[{key}]" if key is not None else f"{base}[{i}]"
            yield from _walk_leaves(sub, entry)
        else:
            yield f"{base}[{i}]", entry


def _apply_json(tree, base: str, sub) -> None:
    """Merge a JSON subtree at base path (leaves set individually)."""
    if not isinstance(sub, dict):
        tree.set(base, sub)
        return
    for k, v in sub.items():
        p = f"{base}/{k}" if base else k
        if isinstance(v, dict):
            # list entries look like {"key": {...}} under a list node; we
            # detect by trying as a container first and falling back.
            try:
                node = tree.schema.resolve(p)
            except SchemaError:
                node = None
            from holo_tpu.yang.schema import List as SchemaList

            if isinstance(node, SchemaList):
                for key, entry in v.items():
                    _apply_json(tree, f"{p}[{key}]", entry)
            else:
                _apply_json(tree, p, v)
        elif isinstance(v, list):
            tree.set(p, v)
        else:
            tree.set(p, v)


def serve_gnmi(
    daemon,
    address: str,
    tls_cert=None,
    tls_key=None,
    shared_fanout: bool | None = None,
    fanout_tick: float | None = None,
) -> grpc.Server:
    tcfg = getattr(getattr(daemon, "config", None), "telemetry", None)
    if shared_fanout is None:
        shared_fanout = getattr(tcfg, "gnmi_shared_fanout", True)
    if fanout_tick is None:
        fanout_tick = getattr(tcfg, "fanout_tick", 1.0)
    service = GnmiService(
        daemon, shared_fanout=shared_fanout, fanout_tick=fanout_tick
    )
    if service.fanout is not None:
        # The coalescing ticker parks while no stream has a bucket, so
        # an idle service costs one blocked daemon thread.
        service.fanout.start()
    daemon.add_commit_listener(service._notify_commit)
    daemon.add_notification_listener(service._notify_yang)
    svc_desc = pb.DESCRIPTOR.services_by_name["gNMI"]
    handlers = {}
    for m in svc_desc.methods:
        req = getattr(pb, m.input_type.name)
        resp = getattr(pb, m.output_type.name)
        fn = getattr(service, m.name)
        if m.name == "Subscribe":
            handlers[m.name] = grpc.stream_stream_rpc_method_handler(
                fn, request_deserializer=req.FromString,
                response_serializer=resp.SerializeToString)
        else:
            handlers[m.name] = grpc.unary_unary_rpc_method_handler(
                fn, request_deserializer=req.FromString,
                response_serializer=resp.SerializeToString)
    server = grpc.server(futures.ThreadPoolExecutor(max_workers=4))
    server.add_generic_rpc_handlers(
        (grpc.method_handlers_generic_handler("gnmi.gNMI", handlers),)
    )
    from holo_tpu.daemon.grpc_server import _bind

    _bind(server, address, tls_cert, tls_key)
    server.start()
    daemon._gnmi_service = service
    if service.fanout is not None:
        # The pre-existing caller contract is `server.stop(grace)`:
        # fold the fan-out ticker shutdown into it so every stop path
        # (tests, Daemon.stop, operators) joins the thread instead of
        # leaking a parked engine per serve_gnmi call.
        grpc_stop = server.stop

        def _stop(grace=None):
            service.fanout.stop()
            return grpc_stop(grace)

        server.stop = _stop
    return server


class GnmiClient:
    """Minimal test client."""

    def __init__(self, address: str):
        self.channel = grpc.insecure_channel(address)
        svc = pb.DESCRIPTOR.services_by_name["gNMI"]
        for m in svc.methods:
            req = getattr(pb, m.input_type.name)
            resp = getattr(pb, m.output_type.name)
            path = f"/gnmi.gNMI/{m.name}"
            if m.name == "Subscribe":
                call = self.channel.stream_stream(
                    path, request_serializer=req.SerializeToString,
                    response_deserializer=resp.FromString)
            else:
                call = self.channel.unary_unary(
                    path, request_serializer=req.SerializeToString,
                    response_deserializer=resp.FromString)
            setattr(self, m.name, call)
