"""Unified telemetry subsystem (ISSUE 2): registry concurrency,
Prometheus exposition golden, gNMI Get/Subscribe of telemetry leaves,
SPF recompile-counter flatness, span tracing + log correlation, gNMI
subscriber overflow hardening, and event-recorder latency stamps."""

import json
import queue
import socket
import threading

import numpy as np
import pytest

from holo_tpu import telemetry
from holo_tpu.telemetry.prometheus import render_text, start_http_server
from holo_tpu.telemetry.registry import MetricsRegistry


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# -- registry core


def test_registry_concurrency_exact_totals():
    """Hammer one counter family + histogram from threads; totals must
    be exact (no lost updates)."""
    reg = MetricsRegistry()
    c = reg.counter("holo_t_hits_total", "hits", ("worker",))
    h = reg.histogram("holo_t_lat_seconds", "lat", buckets=(0.5, 1.0))
    g = reg.gauge("holo_t_depth")
    n_threads, n_iter = 8, 5000

    def work(i):
        child = c.labels(worker=str(i % 2))
        for _ in range(n_iter):
            child.inc()
            h.observe(1.0)
            g.inc()

    threads = [
        threading.Thread(target=work, args=(i,)) for i in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    total = sum(child.value for _, child in c.children())
    assert total == n_threads * n_iter
    assert c.labels(worker="0").value == n_threads * n_iter / 2
    assert h.count == n_threads * n_iter
    assert h.sum == float(n_threads * n_iter)
    assert g.value == n_threads * n_iter
    # Cumulative buckets are consistent: everything fell in le=1.0.
    cum = dict(h.cumulative())
    assert cum[1.0] == h.count and cum[float("inf")] == h.count


def test_registry_kind_conflict_and_disable():
    reg = MetricsRegistry()
    reg.counter("holo_t_x_total")
    with pytest.raises(ValueError):
        reg.gauge("holo_t_x_total")
    c = reg.counter("holo_t_y_total")
    telemetry.set_enabled(False)
    try:
        c.inc()
        assert c.value == 0.0  # disabled = no-op
    finally:
        telemetry.set_enabled(True)
    c.inc(2)
    assert c.value == 2.0


def test_prometheus_exposition_golden():
    """Exact text-format golden: HELP/TYPE blocks, label escaping,
    histogram bucket expansion with +Inf, integer formatting."""
    reg = MetricsRegistry()
    c = reg.counter("holo_g_ops_total", "operations", ("op",))
    c.labels(op="add").inc(3)
    c.labels(op='we"ird').inc()
    reg.gauge("holo_g_depth", "queue depth").set(2.5)
    h = reg.histogram("holo_g_lat_seconds", "latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(10.0)
    expected = (
        "# HELP holo_g_depth queue depth\n"
        "# TYPE holo_g_depth gauge\n"
        "holo_g_depth 2.5\n"
        "# HELP holo_g_lat_seconds latency\n"
        "# TYPE holo_g_lat_seconds histogram\n"
        'holo_g_lat_seconds_bucket{le="0.1"} 1\n'
        'holo_g_lat_seconds_bucket{le="1"} 2\n'
        'holo_g_lat_seconds_bucket{le="+Inf"} 3\n'
        "holo_g_lat_seconds_sum 10.55\n"
        "holo_g_lat_seconds_count 3\n"
        "# HELP holo_g_ops_total operations\n"
        "# TYPE holo_g_ops_total counter\n"
        'holo_g_ops_total{op="add"} 3\n'
        'holo_g_ops_total{op="we\\"ird"} 1\n'
    )
    assert render_text(reg) == expected


def test_prometheus_http_endpoint():
    import urllib.request

    reg = MetricsRegistry()
    reg.counter("holo_h_pings_total").inc(4)
    server = start_http_server(reg, "127.0.0.1:0")
    try:
        port = server.server_address[1]
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics"
        ).read().decode()
        assert "holo_h_pings_total 4" in body
        with pytest.raises(Exception):
            urllib.request.urlopen(f"http://127.0.0.1:{port}/nope")
    finally:
        server.shutdown()
        server.server_close()


# -- span tracer


def test_tracer_nesting_and_chrome_export():
    tr = telemetry.tracer()
    tr.clear()  # the ring is bounded: slicing needs headroom
    before = len(tr.spans())
    assert telemetry.current_span_id() is None
    with telemetry.span("outer", instance="ospfv2") as outer_id:
        assert telemetry.current_span_id() == outer_id
        assert telemetry.current_instance() == "ospfv2"
        with telemetry.span("inner", batch=4) as inner_id:
            assert telemetry.current_span_id() == inner_id
            assert telemetry.current_instance() == "ospfv2"  # inherited
    assert telemetry.current_span_id() is None
    spans = tr.spans()[before:]
    by_name = {s.name: s for s in spans}
    assert by_name["inner"].parent_id == by_name["outer"].span_id
    doc = tr.to_chrome_trace()
    evs = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
    assert evs["inner"]["args"]["parent_id"] == by_name["outer"].span_id
    assert evs["outer"]["args"]["instance"] == "ospfv2"
    assert evs["outer"]["dur"] >= evs["inner"]["dur"]
    json.dumps(doc)  # perfetto-loadable = valid JSON


# -- SPF dispatch instrumentation


def test_spf_dispatch_recompile_counter_flat():
    """Same-shape re-runs must NOT count as recompiles — the whole point
    of the counter is to catch silent recompile storms."""
    from holo_tpu.spf.backend import TpuSpfBackend
    from holo_tpu.spf.synth import grid_topology

    topo = grid_topology(4, 4, seed=1)
    backend = TpuSpfBackend()

    def compiles():
        snap = telemetry.snapshot(prefix="holo_spf_jit_compiles_total")
        return snap.get("holo_spf_jit_compiles_total{kind=one}", 0.0)

    base = compiles()
    r1 = backend.compute(topo)
    assert compiles() == base + 1  # first shape: one compile
    r2 = backend.compute(topo)
    r3 = backend.compute(topo)
    assert compiles() == base + 1  # flat across same-shape re-runs
    assert np.array_equal(r1.dist, r2.dist) and np.array_equal(r2.dist, r3.dist)
    hits = telemetry.snapshot(prefix="holo_spf_jit_cache_hits_total")
    assert hits.get("holo_spf_jit_cache_hits_total{kind=one}", 0.0) >= 2
    # Dispatch wall-time histogram advanced once per compute call.
    disp = telemetry.snapshot(prefix="holo_spf_dispatch_seconds")
    assert (
        disp["holo_spf_dispatch_seconds{backend=tpu,kind=one}"]["count"] >= 3
    )


def test_disabled_registry_dispatch_writes_no_metric_and_opens_no_span():
    """The kill switch on the real dispatch: a warm SPF through
    ``TpuSpfBackend`` with telemetry disabled stamps no registry write,
    moves no ``holo_spf_*`` value, records no span and never reads the
    tracer's clock — with the same bits as the enabled dispatch."""
    from holo_tpu.spf.backend import TpuSpfBackend
    from holo_tpu.spf.synth import grid_topology

    def poisoned():
        raise AssertionError("span clock read with telemetry disabled")

    topo = grid_topology(4, 4, seed=2)
    backend = TpuSpfBackend()
    ref = backend.compute(topo)  # the compile, and its counters, land here
    tracer = telemetry.tracer()
    saved_clock = tracer.clock
    telemetry.set_enabled(False)
    tracer.clock = poisoned
    try:
        stamp = telemetry.write_stamp()
        values = telemetry.snapshot(prefix="holo_spf")
        spans = len(tracer.spans())
        got = backend.compute(topo)
        assert telemetry.write_stamp() == stamp
        assert telemetry.snapshot(prefix="holo_spf") == values
        assert len(tracer.spans()) == spans
    finally:
        tracer.clock = saved_clock
        telemetry.set_enabled(True)
    assert np.array_equal(got.dist, ref.dist)
    assert np.array_equal(got.nexthop_words, ref.nexthop_words)


# -- RIB churn + FRR flip counters


def test_rib_churn_and_backup_flip_counters():
    from ipaddress import IPv4Address as A
    from ipaddress import IPv4Network as N

    from holo_tpu.routing.rib import MockKernel, RibManager
    from holo_tpu.utils.ibus import Ibus
    from holo_tpu.utils.runtime import EventLoop, VirtualClock
    from holo_tpu.utils.southbound import Nexthop, Protocol, RouteMsg

    def snap():
        return telemetry.snapshot(prefix="holo_rib")

    loop = EventLoop(clock=VirtualClock())
    rib = RibManager(Ibus(loop), MockKernel())
    loop.register(rib)
    before = snap()
    p = N("10.1.0.0/16")
    primary = Nexthop(addr=A("10.0.0.2"), ifname="e0")
    backup = Nexthop(addr=A("10.0.1.2"), ifname="e1")
    rib.route_add(
        RouteMsg(
            Protocol.OSPFV2, p, 110, 20, frozenset({primary}),
            backups={primary: backup},
        )
    )
    rib.route_add(
        RouteMsg(
            Protocol.OSPFV2, p, 110, 10, frozenset({primary}),
            backups={primary: backup},
        )
    )
    assert rib.local_repair("e0") == 1
    after = snap()

    def delta(name):
        return after.get(name, 0.0) - before.get(name, 0.0)

    assert delta("holo_rib_route_ops_total{op=add}") == 1
    assert delta("holo_rib_route_ops_total{op=replace}") == 1
    assert delta("holo_rib_backup_flips_total") == 1
    assert delta("holo_rib_kernel_installs_total{op=repair}") == 1
    assert after.get("holo_rib_prefixes") >= 1
    rib.local_restore("e0")
    assert (
        telemetry.snapshot(prefix="holo_rib").get(
            "holo_rib_backup_restores_total", 0.0
        )
        - before.get("holo_rib_backup_restores_total", 0.0)
        == 1
    )


# -- gNMI: telemetry leaves over Get/Subscribe, subscriber hardening


def test_gnmi_get_and_subscribe_telemetry_leaf():
    import holo_tpu.daemon.gnmi_server as gs
    from holo_tpu.daemon.daemon import Daemon
    from holo_tpu.utils.runtime import EventLoop, VirtualClock

    marker = telemetry.counter(
        "holo_e2e_marker_total", "end-to-end visibility marker"
    )
    marker.inc(11)
    loop = EventLoop(clock=VirtualClock())
    d = Daemon(loop=loop, name="tele")
    port = free_port()
    server = gs.serve_gnmi(d, f"127.0.0.1:{port}")
    try:
        cli = gs.GnmiClient(f"127.0.0.1:{port}")
        # Get STATE at the telemetry subtree: live metric leaves.
        get = gs.pb.GetRequest(type=gs.pb.GetRequest.STATE)
        get.path.add().CopyFrom(gs.str_to_path("holo-telemetry"))
        out = cli.Get(get)
        payload = json.loads(out.notification[0].update[0].val.json_ietf_val)
        metrics = {
            m["name"]: m["value"]
            for m in payload["state"]["holo-telemetry"]["metric"]
        }
        assert metrics["holo_e2e_marker_total"] == 11.0
        # The SPF dispatch signal set is registered (instrumented paths
        # import at module load even before traffic flows).
        assert any(n.startswith("holo_spf_") for n in metrics)
        # Subscribe: the initial sync snapshot carries the same leaves.
        sub = gs.pb.SubscribeRequest()
        sub.subscribe.mode = gs.pb.SubscriptionList.ONCE
        msgs = list(cli.Subscribe(iter([sub])))
        snap = json.loads(msgs[0].update.update[0].val.json_ietf_val)
        names = {m["name"] for m in snap["holo-telemetry"]["metric"]}
        assert "holo_e2e_marker_total" in names
    finally:
        server.stop(grace=0)


def test_gnmi_subscriber_overflow_drop_counter_and_safe_removal():
    """A stalled subscriber costs counted drops, never unbounded memory;
    removal is idempotent (a double remove must not raise)."""
    import holo_tpu.daemon.gnmi_server as gs

    svc = gs.GnmiService(daemon=None)
    q: queue.Queue = queue.Queue(maxsize=2)
    svc._add_subscriber(q)
    drops0 = telemetry.snapshot(prefix="holo_gnmi").get(
        "holo_gnmi_subscribe_dropped_total", 0.0
    )
    for i in range(5):
        svc._fanout(f"notif-{i}")
    assert q.qsize() == 2  # bounded: the stall cannot grow memory
    snap = telemetry.snapshot(prefix="holo_gnmi")
    assert snap["holo_gnmi_subscribe_dropped_total"] - drops0 == 3
    svc._remove_subscriber(q)
    svc._remove_subscriber(q)  # exception-safe double removal
    # Copy-on-write snapshot (ISSUE 11): the subscriber table is an
    # immutable tuple so _fanout's lock hold is O(1).
    assert svc._subscribers == ()
    assert snap["holo_gnmi_subscribers"] == 1.0
    assert (
        telemetry.snapshot(prefix="holo_gnmi")["holo_gnmi_subscribers"] == 0.0
    )


def test_acceptance_daemon_ospf_frr_metrics_over_both_exports():
    """ISSUE 2 acceptance: a daemon pair running OSPF (tpu backend) with
    fast-reroute converges, and the daemon exposes live metrics over
    BOTH the Prometheus endpoint and gNMI Subscribe — including SPF
    dispatch timing, jit recompile count, and padded-slot occupancy."""
    import urllib.request
    from ipaddress import ip_address

    import holo_tpu.daemon.gnmi_server as gs
    from holo_tpu.daemon.daemon import Daemon
    from holo_tpu.utils.netio import MockFabric
    from holo_tpu.utils.runtime import EventLoop, VirtualClock

    loop = EventLoop(clock=VirtualClock())
    fabric = MockFabric(loop)
    d1 = Daemon(loop=loop, netio=fabric, name="m1")
    d2 = Daemon(loop=loop, netio=fabric, name="m2")
    fabric.join("l12", "m1.ospfv2", "eth0", ip_address("10.0.12.1"))
    fabric.join("l12", "m2.ospfv2", "eth0", ip_address("10.0.12.2"))
    for d, rid, addr in [
        (d1, "1.1.1.1", "10.0.12.1/30"),
        (d2, "2.2.2.2", "10.0.12.2/30"),
    ]:
        cand = d.candidate()
        cand.set("interfaces/interface[eth0]/enabled", "true")
        cand.set("interfaces/interface[eth0]/address", [addr])
        base = "routing/control-plane-protocols/ospfv2"
        cand.set(f"{base}/router-id", rid)
        cand.set(f"{base}/spf-control/backend", "tpu")
        cand.set(f"{base}/fast-reroute/lfa", "true")
        cand.set(
            f"{base}/area[0.0.0.0]/interface[eth0]/interface-type",
            "point-to-point",
        )
        d.commit(cand)
    loop.advance(60)
    assert d1.routing.instances["ospfv2"].spf_run_count > 0

    needed = (
        "holo_spf_dispatch_seconds",  # SPF dispatch timing
        "holo_spf_jit_compiles_total",  # recompile count
        "holo_spf_ell_occupancy",  # padded-slot occupancy
        "holo_frr_dispatch_seconds",
        "holo_frr_pad_occupancy",
        "holo_ospf_packets_total",
        "holo_ospf_nbr_transitions_total",
    )
    # Export 1: Prometheus text endpoint.
    server = d1.start_telemetry("127.0.0.1:0")
    try:
        port = server.server_address[1]
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics"
        ).read().decode()
        for name in needed:
            assert f"# TYPE {name} " in body, name
    finally:
        d1.stop()
        d2.stop()
    # Export 2: gNMI Subscribe initial sync (and Get) of the state tree.
    port = free_port()
    gsrv = gs.serve_gnmi(d1, f"127.0.0.1:{port}")
    try:
        cli = gs.GnmiClient(f"127.0.0.1:{port}")
        sub = gs.pb.SubscribeRequest()
        sub.subscribe.mode = gs.pb.SubscriptionList.ONCE
        msgs = list(cli.Subscribe(iter([sub])))
        snap = json.loads(msgs[0].update.update[0].val.json_ietf_val)
        names = {m["name"] for m in snap["holo-telemetry"]["metric"]}
        assert any(n.startswith("holo_spf_dispatch_seconds") for n in names)
        assert any(
            n.startswith("holo_spf_jit_compiles_total") for n in names
        )
        assert "holo_spf_ell_occupancy" in names
        assert any(n.startswith("holo_frr_pad_occupancy") for n in names)
    finally:
        gsrv.stop(grace=0)


# -- correlated logging


def test_json_log_records_carry_instance_and_span(capsys):
    import logging

    from holo_tpu.daemon.config import DaemonConfig
    from holo_tpu.daemon.daemon import setup_logging

    cfg = DaemonConfig()
    cfg.logging.style = "json"
    root = logging.getLogger()
    old_handlers = root.handlers[:]
    old_level = root.level
    try:
        setup_logging(cfg)
        log = logging.getLogger("holo_tpu.test")
        with telemetry.span("spf.test", instance="ospfv2-a") as sid:
            log.info("inside span")
        log.info("outside span")
        err = capsys.readouterr().err
        lines = [json.loads(ln) for ln in err.strip().splitlines()]
        inside = next(l for l in lines if l["message"] == "inside span")
        outside = next(l for l in lines if l["message"] == "outside span")
        assert inside["span"] == sid
        assert inside["instance"] == "ospfv2-a"
        assert outside["span"] is None and outside["instance"] is None
    finally:
        root.handlers[:] = old_handlers
        root.setLevel(old_level)


# -- event recorder stamps


def test_event_recorder_mono_seq_stamps_and_backward_compat(tmp_path):
    from holo_tpu.utils.event_recorder import (
        EventRecorder,
        read_entries,
        replay,
    )
    from holo_tpu.utils.runtime import Actor, EventLoop, VirtualClock

    path = tmp_path / "events.jsonl"
    rec = EventRecorder(path)
    rec.record("a", 1.0, {"k": 1})
    rec.record("a", 2.0, {"k": 2})
    rec.record("b", 2.5, {"k": 3})
    rec.close()
    entries = read_entries(path)
    assert [e["seq"] for e in entries] == [0, 1, 2]
    monos = [e["mono"] for e in entries]
    assert monos == sorted(monos) and all(m >= 0 for m in monos)
    # Inter-event latency is reconstructable from the monotonic stamps.
    assert monos[2] - monos[0] >= 0

    # Backward compat: a pre-stamp recording (no mono/seq) still decodes
    # with derived defaults AND still replays.
    old = tmp_path / "old.jsonl"
    old.write_text(
        json.dumps({"actor": "x", "time": 3.0, "msg": {"k": 9}}) + "\n"
    )
    entries = read_entries(old)
    assert entries[0]["seq"] == 0 and entries[0]["mono"] == 3.0

    got = []

    class X(Actor):
        name = "x"

        def handle(self, msg):
            got.append(msg)

    loop = EventLoop(clock=VirtualClock())
    loop.register(X())
    assert replay(old, loop) == 1
    assert got == [{"k": 9}]


# -- txqueue + ibus plumbing metrics


def test_txqueue_and_ibus_metrics():
    from holo_tpu.utils.ibus import Ibus
    from holo_tpu.utils.runtime import Actor, EventLoop, VirtualClock
    from holo_tpu.utils.txqueue import TxTaskNetIo

    class SinkIo:
        def __init__(self):
            self.sent = []

        def send(self, ifname, src, dst, data):
            self.sent.append((ifname, data))

    tx = TxTaskNetIo(SinkIo())
    tx.send("eth9", None, None, b"x")
    tx.close()
    snap = telemetry.snapshot(prefix="holo_txqueue")
    assert snap.get("holo_txqueue_sent_total{ifname=eth9}", 0) >= 1
    tx.send("eth9", None, None, b"late")  # after close: counted drop
    assert (
        telemetry.snapshot(prefix="holo_txqueue")[
            "holo_txqueue_dropped_total{ifname=eth9,cause=closed}"
        ]
        >= 1
    )

    class Rx(Actor):
        name = "rx"

        def handle(self, msg):
            pass

    loop = EventLoop(clock=VirtualClock())
    ibus = Ibus(loop)
    loop.register(Rx())
    ibus.subscribe("test.topic", "rx")
    before = telemetry.snapshot(prefix="holo_ibus")
    ibus.publish("test.topic", {"x": 1})
    ibus.subscribe("test.topic", "ghost")  # never registered actor
    ibus.publish("test.topic", {"x": 2})
    after = telemetry.snapshot(prefix="holo_ibus")
    assert (
        after["holo_ibus_publish_total{topic=test.topic}"]
        - before.get("holo_ibus_publish_total{topic=test.topic}", 0)
        == 2
    )
    assert (
        after["holo_ibus_undeliverable_total{topic=test.topic}"]
        - before.get("holo_ibus_undeliverable_total{topic=test.topic}", 0)
        == 1
    )


# -- deferred occupancy sampling (holo-lint HL105 fix, PR 3) ------------


def test_deferred_mean_one_shot_release_and_kill_switch():
    """set_fn + deferred_mean: the reduction runs at scrape time (not
    on the dispatch path), the array reference is dropped after the
    first sample, and set_enabled(False) gates fn-backed gauges too."""
    import gc
    import weakref

    import numpy as np

    g = telemetry.gauge("holo_test_deferred_occupancy")
    arr = np.ones((4, 8), bool)
    arr[0, :4] = False
    ref = weakref.ref(arr)
    g.set_fn(telemetry.deferred_mean(arr))
    del arr
    gc.collect()
    assert ref() is not None  # pinned until first scrape...
    assert g.value == 1.0 - 4 / 32
    gc.collect()
    assert ref() is None  # ...released after it; value stays cached
    assert g.value == 1.0 - 4 / 32

    # Kill switch: a disabled registry must not run sampling closures.
    calls = []
    g.set_fn(lambda: calls.append(1) or 7.0)
    telemetry.set_enabled(False)
    try:
        assert g.value == 0.0 and not calls
    finally:
        telemetry.set_enabled(True)
    assert g.value == 7.0 and calls
