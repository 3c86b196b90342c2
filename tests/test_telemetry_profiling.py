"""Deep profiling + flight recorder (ISSUE 5): per-dispatch sub-span
nesting, compile-time cost-analysis capture per shape bucket, histogram
exemplars + OpenMetrics rendering, flight-recorder ring/postmortem
mechanics, and gNMI STREAM sampled-interval pushes."""

import json
import socket
import threading
import time

import pytest

from holo_tpu import telemetry
from holo_tpu.telemetry import flight, profiling
from holo_tpu.telemetry.prometheus import render_text
from holo_tpu.telemetry.registry import MetricsRegistry


@pytest.fixture
def profiled():
    """Arm device profiling for one test and always disarm after."""
    profiling.set_device_profiling(True)
    try:
        yield
    finally:
        profiling.set_device_profiling(False)


def _stage_counts():
    snap = telemetry.snapshot(prefix="holo_profile_stage_seconds")
    return {k: v["count"] for k, v in snap.items()}


# -- sub-span nesting ----------------------------------------------------


def test_dispatch_splits_into_nested_subspans(profiled):
    """A profiled SPF dispatch yields marshal/device/readback sub-spans
    nested under the spf.dispatch span, and one stage-histogram
    observation each."""
    from holo_tpu.spf.backend import TpuSpfBackend
    from holo_tpu.spf.synth import grid_topology

    topo = grid_topology(4, 4, seed=1)
    backend = TpuSpfBackend()
    tracer = telemetry.tracer()
    tracer.clear()  # the ring is bounded: slicing needs headroom
    before_spans = len(tracer.spans())
    before_counts = _stage_counts()
    backend.compute(topo)
    spans = tracer.spans()[before_spans:]
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, s)
    dispatch = by_name["spf.dispatch"]
    for stage_name in ("marshal", "device", "readback"):
        sub = by_name[f"spf.one.{stage_name}"]
        assert sub.parent_id == dispatch.span_id, stage_name
        assert sub.attrs["stage"] == stage_name
        key = (
            f"holo_profile_stage_seconds"
            f"{{site=spf.one,stage={stage_name},device=-}}"
        )
        assert _stage_counts()[key] == before_counts.get(key, 0) + 1

    # Disarmed: the same dispatch emits no sub-spans and no stage rows.
    profiling.set_device_profiling(False)
    tracer.clear()  # the ring is bounded: slicing needs headroom
    before_spans = len(tracer.spans())
    counts = _stage_counts()
    backend.compute(topo)
    names = {s.name for s in tracer.spans()[before_spans:]}
    assert names == {"spf.dispatch"}
    assert _stage_counts() == counts


def test_frr_dispatch_profiled_subspans(profiled):
    from holo_tpu.frr.manager import FrrEngine
    from holo_tpu.spf.synth import grid_topology

    topo = grid_topology(4, 4, seed=2)
    tracer = telemetry.tracer()
    tracer.clear()  # the ring is bounded: slicing needs headroom
    before = len(tracer.spans())
    FrrEngine("tpu").compute(topo)
    spans = tracer.spans()[before:]
    by_name = {s.name: s for s in spans}
    dispatch = by_name["frr.dispatch"]
    for stage_name in ("marshal", "device", "readback"):
        assert by_name[f"frr.batch.{stage_name}"].parent_id == dispatch.span_id


# -- compile-time cost analysis -----------------------------------------


def test_cost_analysis_captured_per_shape_bucket(profiled):
    """One cost-table entry per fresh (engine, shape) bucket, exactly
    mirroring the jit cache: a re-run on a seen shape adds nothing, a
    new topology shape adds one."""
    from holo_tpu.spf.backend import TpuSpfBackend
    from holo_tpu.spf.synth import grid_topology

    profiling.clear_cost_table()
    backend = TpuSpfBackend()
    t4 = grid_topology(4, 4, seed=1)
    t5 = grid_topology(5, 5, seed=1)
    backend.compute(t4)
    one_buckets = [k for k in profiling.cost_table() if k[0] == "spf.one"]
    assert len(one_buckets) == 1
    backend.compute(t4)  # same shape: jit cache hit, no new capture
    assert len([k for k in profiling.cost_table() if k[0] == "spf.one"]) == 1
    backend.compute(t5)  # fresh shape bucket
    table = profiling.cost_table()
    one_buckets = [k for k in table if k[0] == "spf.one"]
    assert len(one_buckets) == 2
    for key in one_buckets:
        assert table[key]["flops"] > 0
        assert table[key]["bytes"] > 0
    # The per-site gauges track the last-compiled bucket.
    snap = telemetry.snapshot(prefix="holo_profile_cost")
    assert snap["holo_profile_cost_flops{site=spf.one}"] > 0


def test_cost_analysis_disarmed_is_free():
    from holo_tpu.spf.backend import TpuSpfBackend
    from holo_tpu.spf.synth import grid_topology

    profiling.clear_cost_table()
    TpuSpfBackend().compute(grid_topology(4, 4, seed=3))
    assert profiling.cost_table() == {}


# -- exemplars -----------------------------------------------------------


def test_histogram_exemplar_attachment_and_rendering():
    """Exemplars land in the bucket the observation fell into and render
    in OpenMetrics syntax after the bucket count — but ONLY under the
    OpenMetrics mode: the classic 0.0.4 grammar rejects the suffix, so
    the default render must stay exemplar-free."""
    reg = MetricsRegistry()
    h = reg.histogram("holo_x_lat_seconds", "lat", buckets=(0.1, 1.0))
    h.observe(0.05, exemplar={"span_id": 7})
    h.observe(0.5)  # no exemplar: bucket renders bare
    h.observe(0.7, exemplar={"span_id": 9})
    ex = h.labels().exemplars()
    assert ex[0.1] == ((("span_id", "7"),), 0.05)
    assert ex[1.0] == ((("span_id", "9"),), 0.7)
    text = render_text(reg, openmetrics=True)
    assert 'holo_x_lat_seconds_bucket{le="0.1"} 1 # {span_id="7"} 0.05' in text
    assert 'holo_x_lat_seconds_bucket{le="1"} 3 # {span_id="9"} 0.7' in text
    assert 'le="+Inf"} 3\n' in text  # untouched buckets render bare
    assert "# {" not in render_text(reg)  # 0.0.4 scrape stays clean


def test_metrics_endpoint_negotiates_openmetrics_exemplars():
    """The HTTP endpoint serves 0.0.4 (no exemplars) by default and
    OpenMetrics (+ exemplars + # EOF) when the scraper Accepts it."""
    import urllib.request

    from holo_tpu.telemetry.prometheus import start_http_server

    reg = MetricsRegistry()
    h = reg.histogram("holo_neg_lat_seconds", buckets=(0.1,))
    h.observe(0.05, exemplar={"span_id": 3})
    server = start_http_server(reg, "127.0.0.1:0")
    try:
        port = server.server_address[1]
        url = f"http://127.0.0.1:{port}/metrics"
        plain = urllib.request.urlopen(url)
        body = plain.read().decode()
        assert "# {" not in body and "# EOF" not in body
        assert "version=0.0.4" in plain.headers["Content-Type"]
        req = urllib.request.Request(
            url, headers={"Accept": "application/openmetrics-text"}
        )
        om = urllib.request.urlopen(req)
        body = om.read().decode()
        assert '# {span_id="3"} 0.05' in body
        assert body.endswith("# EOF\n")
        assert "openmetrics-text" in om.headers["Content-Type"]
    finally:
        server.shutdown()
        server.server_close()


def test_profiled_dispatch_exemplars_link_to_subspans(profiled):
    """The stage histogram's exemplars carry span ids that exist in the
    tracer ring as the matching sub-spans — the bucket→trace join."""
    from holo_tpu.spf.backend import TpuSpfBackend
    from holo_tpu.spf.synth import grid_topology

    fam = telemetry.histogram(
        "holo_profile_stage_seconds", labelnames=("site", "stage", "device")
    )
    child = fam.labels(site="spf.one", stage="marshal", device="-")
    # Exemplars of earlier dispatches outlive their spans (the tracer
    # ring is bounded): the join is checked on what THIS dispatch set.
    stale = child.exemplars()
    backend = TpuSpfBackend()
    backend.compute(grid_topology(4, 4, seed=4))
    exemplars = {
        k: v for k, v in child.exemplars().items() if stale.get(k) != v
    }
    assert exemplars, "profiled dispatch must attach an exemplar"
    span_ids = {
        s.span_id
        for s in telemetry.tracer().spans()
        if s.name == "spf.one.marshal"
    }
    for labels, _value in exemplars.values():
        assert dict(labels).keys() == {"span_id"}
        assert int(dict(labels)["span_id"]) in span_ids
    # And the OpenMetrics scrape surface carries the join.
    assert "# {span_id=" in render_text(telemetry.registry(), openmetrics=True)


# -- flight recorder -----------------------------------------------------


def test_flight_ring_bounded_and_renumbered(tmp_path):
    """Ring stays bounded; span ids renumber relative to the first
    recorded span so seeded runs produce identical bundles; journal
    marks and events carry the injected clock's stamps."""
    t = [0.0]
    rec = flight.FlightRecorder(
        capacity=4, postmortem_dir=tmp_path, clock=lambda: t[0]
    )
    tracer = telemetry.tracer()
    tracer.on_complete = rec.note_span
    try:
        with telemetry.span("warm"):
            pass
        for i in range(6):
            t[0] = float(i)
            rec.journal_mark(i, "r1")
        ring = rec.snapshot_ring()
        assert len(ring) == 4  # bounded: oldest entries fell off
        assert ring[0][0] == "journal" and ring[0][1] == 2
        with telemetry.span("s2"):
            pass
        first_span = next(e for e in rec.snapshot_ring() if e[0] == "span")
        assert first_span[2] == 1  # renumbered: warm was span 0, s2 is 1
        rec.event("breaker", breaker="spf-dispatch#3", to="open")
        path, bundle = rec.postmortem("breaker-open:spf-dispatch#3")
        assert path is not None and path.exists()
        assert bundle["reason"] == "breaker-open:spf-dispatch"  # scrubbed
        ev = next(e for e in bundle["ring"] if e[0] == "event")
        assert ev[2]["breaker"] == "spf-dispatch"
        assert bundle["journal-tail"][-1] == [5, "r1"]
        assert json.loads(path.read_text()) == bundle
    finally:
        tracer.on_complete = None


def test_flight_metric_deltas_are_counter_counts_only():
    """The bundle metric section carries counter/histogram-count deltas
    since arm time — no gauges, no wall-time sums."""
    c = telemetry.counter("holo_fx_events_total")
    g = telemetry.gauge("holo_fx_depth")
    h = telemetry.histogram("holo_fx_lat_seconds")
    c.inc(2)
    rec = flight.FlightRecorder(capacity=8)
    c.inc(3)
    g.set(99)
    h.observe(0.25)
    deltas = rec.metric_deltas()
    assert deltas["holo_fx_events_total"] == 3  # delta, not absolute
    assert deltas["holo_fx_lat_seconds"] == 1  # count delta only
    assert not any(k.startswith("holo_fx_depth") for k in deltas)


def test_flight_postmortem_debounced_per_reason(tmp_path):
    """A flapping breaker re-opening every few seconds must not fill
    the disk: repeat dumps for one reason inside min_dump_interval are
    suppressed; a different reason (or the window expiring) dumps."""
    t = [0.0]
    rec = flight.FlightRecorder(
        capacity=16, postmortem_dir=tmp_path, clock=lambda: t[0],
        min_dump_interval=60.0,
    )
    p1, b1 = rec.postmortem("breaker-open:spf")
    assert p1 is not None and b1 is not None
    t[0] = 10.0
    assert rec.postmortem("breaker-open:spf") == (None, None)  # debounced
    p2, _ = rec.postmortem("crash-loop:r1")  # distinct reason: dumps
    assert p2 is not None
    t[0] = 75.0
    p3, _ = rec.postmortem("breaker-open:spf")  # window expired
    assert p3 is not None
    assert len(sorted(tmp_path.glob("postmortem-*.json"))) == 3


def test_flight_trigger_disarmed_is_noop(tmp_path):
    flight.configure(entries=0)
    assert flight.trigger("breaker-open:x") is None
    assert not list(tmp_path.iterdir())


# -- gNMI STREAM sampling ------------------------------------------------


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _stream(cli, gs, *subs):
    """Subscribe STREAM with the given Subscription protos; returns the
    response iterator."""
    req = gs.pb.SubscribeRequest()
    req.subscribe.mode = gs.pb.SubscriptionList.STREAM
    for s in subs:
        req.subscribe.subscription.add().CopyFrom(s)
    return cli.Subscribe(iter([req]))


def _collect(stream, n_notifs, timeout=8.0):
    """First ``n_notifs`` non-sync sampled/heartbeat notifications
    (update messages whose updates carry real paths)."""
    got = []
    done = threading.Event()

    def run():
        for m in stream:
            if (
                m.HasField("update")
                and m.update.update
                and m.update.update[0].path.elem
            ):
                got.append(m.update)
                if len(got) >= n_notifs:
                    done.set()
                    return

    t = threading.Thread(target=run, daemon=True)
    t.start()
    done.wait(timeout)
    return got


def test_gnmi_sample_stream_pushes_metric_leaves():
    """SAMPLE + sample_interval pushes periodic holo-telemetry leaf
    updates (typed, per-leaf paths) without any state change."""
    import holo_tpu.daemon.gnmi_server as gs
    from holo_tpu.daemon.daemon import Daemon
    from holo_tpu.utils.runtime import EventLoop, VirtualClock

    marker = telemetry.counter("holo_sample_seen_total")
    marker.inc(5)
    d = Daemon(loop=EventLoop(clock=VirtualClock()), name="smp")
    port = _free_port()
    server = gs.serve_gnmi(d, f"127.0.0.1:{port}")
    try:
        cli = gs.GnmiClient(f"127.0.0.1:{port}")
        sub = gs.pb.Subscription()
        sub.path.CopyFrom(gs.str_to_path("holo-telemetry"))
        sub.mode = gs.pb.SAMPLE
        sub.sample_interval = 60_000_000  # 60ms
        notifs = _collect(_stream(cli, gs, sub), 2)
        assert len(notifs) >= 2, "two sampled intervals must push"
        by_path = {
            gs.path_to_str(u.path): u.val for u in notifs[0].update
        }
        key = "holo-telemetry/metric[holo_sample_seen_total]/value"
        assert by_path[key].WhichOneof("value") == "double_val"
        assert by_path[key].double_val == 5.0
        assert all(
            p.startswith("holo-telemetry") for p in by_path
        ), "subscription path must scope the push"
        snap = telemetry.snapshot(prefix="holo_gnmi_sample")
        assert snap.get("holo_gnmi_sample_updates_total{mode=sample}", 0) > 0
    finally:
        server.stop(grace=0)


def test_gnmi_sample_suppress_redundant_with_heartbeat():
    """suppress_redundant drops unchanged leaves from sampled pushes; a
    value change resumes them; the heartbeat resends regardless."""
    import holo_tpu.daemon.gnmi_server as gs
    from holo_tpu.daemon.daemon import Daemon
    from holo_tpu.utils.runtime import EventLoop, VirtualClock

    marker = telemetry.counter("holo_suppress_probe_total")
    marker.inc()
    d = Daemon(loop=EventLoop(clock=VirtualClock()), name="sup")
    port = _free_port()
    server = gs.serve_gnmi(d, f"127.0.0.1:{port}")
    try:
        cli = gs.GnmiClient(f"127.0.0.1:{port}")
        leaf = "holo-telemetry/metric[holo_suppress_probe_total]/value"
        sub = gs.pb.Subscription()
        sub.path.CopyFrom(gs.str_to_path(leaf))
        sub.mode = gs.pb.SAMPLE
        sub.sample_interval = 50_000_000  # 50ms
        sub.suppress_redundant = True
        sub.heartbeat_interval = 1_000_000_000  # 1s

        stream = _stream(cli, gs, sub)
        first = _collect(stream, 1)
        assert len(first) == 1  # initial sample: leaf sent once
        # Unchanged: further samples are suppressed until the value
        # moves.  Poke the counter and the next sample resumes.
        time.sleep(0.2)
        marker.inc()
        more = _collect(stream, 1)
        assert more, "changed leaf must be sampled again"
        vals = [u.val.double_val for u in more[0].update]
        assert vals == [2.0]
        # Heartbeat: with no further change, the 1s beat resends the
        # unchanged leaf (sampled suppression alone would stay silent).
        beat = _collect(stream, 1, timeout=4.0)
        assert beat, "heartbeat must resend unchanged leaves"
        assert [u.val.double_val for u in beat[0].update] == [2.0]
        snap = telemetry.snapshot(prefix="holo_gnmi_sample")
        assert (
            snap.get("holo_gnmi_sample_updates_total{mode=heartbeat}", 0) > 0
        )
    finally:
        server.stop(grace=0)


def test_gnmi_on_change_heartbeat_resends_unchanged_leaves():
    """ON_CHANGE + heartbeat_interval: no state changes at all, yet the
    subscriber sees the leaf at every beat (the satellite fix — before,
    heartbeat_interval was silently ignored)."""
    import holo_tpu.daemon.gnmi_server as gs
    from holo_tpu.daemon.daemon import Daemon
    from holo_tpu.utils.runtime import EventLoop, VirtualClock

    telemetry.counter("holo_onchange_probe_total").inc(4)
    d = Daemon(loop=EventLoop(clock=VirtualClock()), name="hb")
    port = _free_port()
    server = gs.serve_gnmi(d, f"127.0.0.1:{port}")
    try:
        cli = gs.GnmiClient(f"127.0.0.1:{port}")
        leaf = "holo-telemetry/metric[holo_onchange_probe_total]/value"
        sub = gs.pb.Subscription()
        sub.path.CopyFrom(gs.str_to_path(leaf))
        sub.mode = gs.pb.ON_CHANGE
        sub.heartbeat_interval = 80_000_000  # 80ms
        notifs = _collect(_stream(cli, gs, sub), 2)
        assert len(notifs) >= 2, "two heartbeats must fire"
        for n in notifs:
            assert [gs.path_to_str(u.path) for u in n.update] == [leaf]
            assert n.update[0].val.double_val == 4.0
    finally:
        server.stop(grace=0)
