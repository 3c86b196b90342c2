"""Chaos e2e: the resilience subsystem under injected failure.

The acceptance scenario (ISSUE 4): kill a protocol actor AND force >= 3
consecutive TPU dispatch failures — the run must end with the actor
restarted (restart counter > 0), the breaker OPEN then restored via a
half-open probe, and the final RIB bit-identical to a clean
scalar-oracle run of the same topology events.

Plus the harness's own guarantee: the same FaultPlan seed produces an
identical event-recorder sequence across two runs (chaos results must
be replayable), and OSPF reconverges through packet loss.
"""

import json
from contextlib import nullcontext
from ipaddress import IPv4Address as A
from ipaddress import IPv4Network as N

from holo_tpu.protocols.ospf.instance import (
    IfConfig,
    IfUpMsg,
    InstanceConfig,
    OspfInstance,
)
from holo_tpu.protocols.ospf.interface import IfType
from holo_tpu.resilience import (
    CircuitBreaker,
    FaultInjector,
    FaultPlan,
    RestartPolicy,
    Supervisor,
    inject,
)
from holo_tpu.routing.rib import MockKernel, RibManager
from holo_tpu.utils.event_recorder import EventRecorder, instrument, read_entries
from holo_tpu.utils.ibus import Ibus
from holo_tpu.utils.netio import MockFabric
from holo_tpu.utils.runtime import EventLoop, VirtualClock
from holo_tpu.utils.southbound import Protocol

AREA0 = A("0.0.0.0")
DEST = N("10.0.23.0/30")  # the r2--r3 subnet, primary via r2 from r1


def triangle(loop, fabric, r1_backend=None):
    """r1--r2 (10), r2--r3 (10), r1--r3 (100); r1 optionally computes
    SPF on an injected (breaker-guarded TPU) backend."""
    buses, kernels, ribs, routers = {}, {}, {}, {}
    for name, rid in [("r1", "1.1.1.1"), ("r2", "2.2.2.2"), ("r3", "3.3.3.3")]:
        bus = Ibus(loop)
        k = MockKernel()
        rib = RibManager(bus, k)
        rib.name = f"routing-{name}"
        loop.register(rib)
        inst = OspfInstance(
            name=name,
            config=InstanceConfig(router_id=A(rid)),
            netio=fabric.sender_for(name),
            spf_backend=r1_backend if name == "r1" else None,
        )
        loop.register(inst)
        inst.attach_ibus(bus, routing_actor=rib.name)
        buses[name], kernels[name], ribs[name], routers[name] = bus, k, rib, inst

    cfg = lambda c: IfConfig(if_type=IfType.POINT_TO_POINT, cost=c)
    r1, r2, r3 = routers["r1"], routers["r2"], routers["r3"]
    r1.add_interface("e0", cfg(10), N("10.0.12.0/30"), A("10.0.12.1"))
    r2.add_interface("e0", cfg(10), N("10.0.12.0/30"), A("10.0.12.2"))
    r2.add_interface("e1", cfg(10), N("10.0.23.0/30"), A("10.0.23.1"))
    r3.add_interface("e0", cfg(10), N("10.0.23.0/30"), A("10.0.23.2"))
    r1.add_interface("e1", cfg(100), N("10.0.13.0/30"), A("10.0.13.1"))
    r3.add_interface("e1", cfg(100), N("10.0.13.0/30"), A("10.0.13.2"))
    fabric.join("l12", "r1", "e0", A("10.0.12.1"))
    fabric.join("l12", "r2", "e0", A("10.0.12.2"))
    fabric.join("l23", "r2", "e1", A("10.0.23.1"))
    fabric.join("l23", "r3", "e0", A("10.0.23.2"))
    fabric.join("l13", "r1", "e1", A("10.0.13.1"))
    fabric.join("l13", "r3", "e1", A("10.0.13.2"))
    for r in routers.values():
        for area in r.areas.values():
            for ifname in area.interfaces:
                loop.send(r.name, IfUpMsg(ifname))
    return buses, kernels, ribs, routers


def test_chaos_actor_kill_breaker_cycle_and_rib_parity():
    """THE acceptance scenario.  The chaos arm and the clean control arm
    see the SAME topology events; the control's r1 computes on the
    scalar oracle throughout, so final-FIB equality IS the 'RIB
    bit-identical to the scalar oracle' contract."""

    def scenario(chaos: bool):
        from holo_tpu.spf.backend import TpuSpfBackend

        loop = EventLoop(clock=VirtualClock())
        fabric = MockFabric(loop)
        breaker = sup = backend = None
        if chaos:
            breaker = CircuitBreaker(
                "spf-chaos",
                failure_threshold=3,
                recovery_timeout=30.0,
                clock=loop.clock.now,
            )
            backend = TpuSpfBackend(64, breaker=breaker)
            sup = Supervisor(
                RestartPolicy(base_delay=1.0, jitter=0.1)
            ).install(loop)
        buses, kernels, ribs, routers = triangle(loop, fabric, backend)
        loop.advance(90)  # converge

        inj = FaultInjector(
            FaultPlan(seed=11, dispatch_fail={"spf.dispatch": 3})
        )
        if chaos:
            # Kill the protocol actor: the pill crashes r1 inside its
            # handler; supervision restarts it after ~1s backoff with
            # the in-flight mail held and redelivered.
            inj.kill_actor(loop, "r1")
            loop.run_until_idle()
            assert "r1" in loop._crashed
        loop.advance(5)
        if chaos:
            assert "r1" not in loop._crashed
            assert sup.restarts["r1"] > 0, "restart counter must move"

        # Three LSDB changes -> three r1 SPF runs, each a forced TPU
        # dispatch failure served bit-identically by the scalar oracle.
        with inject(inj) if chaos else nullcontext():
            for third_octet in (110, 111, 112):
                routers["r3"].interface_address_add(
                    "e0", N(f"192.168.{third_octet}.0/24")
                )
                loop.advance(15)
            if chaos:
                assert breaker.state == "open", (
                    f"3 consecutive failures must open the circuit "
                    f"(spf runs: {routers['r1'].spf_run_count})"
                )
            # While OPEN the device is not attempted (the forced-failure
            # budget is exhausted — any attempt now would SUCCEED and
            # close the circuit early, so staying open proves the
            # short-circuit).
            routers["r3"].interface_address_add("e0", N("192.168.113.0/24"))
            loop.advance(15)
            if chaos:
                assert breaker.state == "open"
            # Recovery: past the timeout the next SPF run is the
            # half-open probe; the device is healthy again (injector
            # still armed, budget spent) so service restores.
            loop.advance(31)
            routers["r3"].interface_address_add("e0", N("192.168.114.0/24"))
            loop.advance(15)
        if chaos:
            assert breaker.state == "closed", "half-open probe must restore"
            assert inj.injected["spf.dispatch"] == 3
        loop.advance(30)  # settle
        return kernels, routers

    chaos_kernels, chaos_routers = scenario(chaos=True)
    clean_kernels, clean_routers = scenario(chaos=False)

    # The chaos run converged at all...
    fib = chaos_kernels["r1"].fib
    assert DEST in fib and fib[DEST][1] == Protocol.OSPFV2
    assert N("192.168.114.0/24") in fib
    # ...and every router's final FIB is bit-identical to the clean
    # scalar-oracle run over the same topology events.
    for name in ("r1", "r2", "r3"):
        assert chaos_kernels[name].fib == clean_kernels[name].fib, name


def _recorded_run(tmp_path, tag: str):
    """One seeded chaos run with the journal on: packet drops, delayed
    ibus deliveries, jittered time, and an actor kill + restart."""
    plan = FaultPlan(
        seed=5,
        drop_prob=0.12,
        publish_delay=0.3,
        publish_delay_prob=1.0,  # ibus traffic is sparse: defer all of it
        timer_jitter=0.4,
    )
    inj = FaultInjector(plan)
    loop = EventLoop(clock=VirtualClock())
    rec = EventRecorder(tmp_path / f"events-{tag}.jsonl")
    instrument(loop, rec)
    fabric = MockFabric(loop)
    inj.wire_fabric(fabric)
    sup = Supervisor(RestartPolicy(base_delay=1.0, jitter=0.2)).install(loop)
    buses, kernels, ribs, routers = triangle(loop, fabric)
    inj.wrap_ibus(buses["r1"])
    with inject(inj):
        inj.jittered_advance(loop, 90, steps=18)
        inj.kill_actor(loop, "r1")
        loop.run_until_idle()
        inj.jittered_advance(loop, 40, steps=8)
    rec.close()
    assert sup.restarts.get("r1", 0) == 1
    assert inj.injected.get("fabric.drop", 0) > 0, "loss must actually fire"
    assert inj.injected.get("ibus.delay", 0) > 0
    # Chaos or not, the network converged.
    assert {str(nh.addr) for nh in kernels["r1"].fib[DEST][0]} == {"10.0.12.2"}
    return [
        (e["actor"], e["time"], json.dumps(e["msg"], sort_keys=True))
        for e in read_entries(tmp_path / f"events-{tag}.jsonl")
    ], dict(inj.injected)


def test_same_fault_plan_seed_identical_event_sequence(tmp_path):
    """The harness's own determinism contract: two runs of one seeded
    plan journal byte-identical (actor, time, message) sequences —
    guarding the chaos machinery itself against nondeterminism."""
    seq_a, injected_a = _recorded_run(tmp_path, "a")
    seq_b, injected_b = _recorded_run(tmp_path, "b")
    assert injected_a == injected_b
    assert len(seq_a) > 100, "the scenario must actually exercise the loop"
    assert seq_a == seq_b


def test_breaker_open_postmortem_bundle_deterministic(tmp_path):
    """ISSUE 5 chaos satellite: a forced spf.dispatch breaker-open under
    a seeded FaultPlan produces EXACTLY ONE postmortem bundle whose
    journal-seq tail matches the event recorder — and the bundle is
    byte-identical across two runs of the same seed (modulo dump path):
    spans ride the virtual clock, ids renumber, metric deltas are
    per-run counts."""
    import gc
    import time as _time

    from holo_tpu import telemetry
    from holo_tpu.spf.backend import TpuSpfBackend
    from holo_tpu.telemetry import flight

    def run(tag: str) -> str:
        from ipaddress import IPv4Network as NN

        gc.collect()  # free the previous run's breaker weakrefs
        # Determinism isolation: eviction counts depend on how full the
        # process-wide marshal cache is when the run starts (ISSUE 7
        # makes entries long-lived), so each arm starts empty.
        from holo_tpu.ops.spf_engine import shared_graph_cache

        shared_graph_cache().clear()
        loop = EventLoop(clock=VirtualClock())
        telemetry.tracer().use_clock(loop.clock.now)
        dump_dir = tmp_path / tag
        flight.configure(
            entries=1024, postmortem_dir=dump_dir, clock=loop.clock.now
        )
        rec = EventRecorder(tmp_path / f"pm-{tag}.jsonl")
        instrument(loop, rec)
        fabric = MockFabric(loop)
        breaker = CircuitBreaker(
            "spf-postmortem",
            failure_threshold=3,
            recovery_timeout=1e9,  # stay open through the settle window
            clock=loop.clock.now,
        )
        backend = TpuSpfBackend(64, breaker=breaker)
        buses, kernels, ribs, routers = triangle(loop, fabric, backend)
        loop.advance(90)  # converge
        inj = FaultInjector(
            FaultPlan(seed=7, dispatch_fail={"spf.dispatch": 3})
        )
        with inject(inj):
            for third_octet in (120, 121, 122):
                routers["r3"].interface_address_add(
                    "e0", NN(f"192.168.{third_octet}.0/24")
                )
                loop.advance(15)
        assert breaker.state == "open"
        assert inj.injected["spf.dispatch"] == 3
        rec.close()
        flight.configure(entries=0)

        bundles = sorted(dump_dir.glob("postmortem-*.json"))
        assert len(bundles) == 1, [b.name for b in bundles]
        bundle = json.loads(bundles[0].read_text())
        assert bundle["reason"] == "breaker-open:spf-postmortem"
        # The journal-seq tail joins the bundle to the journal file:
        # every [seq, actor] marker must match the recorded entry.
        entries = read_entries(tmp_path / f"pm-{tag}.jsonl")
        tail = bundle["journal-tail"]
        assert tail, "the ring must carry journal markers"
        for seq, actor in tail:
            assert entries[seq]["seq"] == seq
            assert entries[seq]["actor"] == actor
        # The breaker-open event and the open-state health verdict made
        # it into the bundle.
        events = [e for e in bundle["ring"] if e[0] == "event"]
        assert any(
            e[1] == "breaker" and e[2]["to"] == "open" for e in events
        )
        assert (
            bundle["health"]["breakers"]["spf-postmortem"]["state"] == "open"
        )
        assert bundle["metrics"][
            "holo_resilience_breaker_failures_total"
            "{breaker=spf-postmortem,cause=exception}"
        ] == 3
        return bundles[0].read_text()

    try:
        text_a = run("a")
        text_b = run("b")
    finally:
        flight.configure(entries=0)
        telemetry.tracer().use_clock(_time.monotonic)
    assert text_a == text_b, "seeded chaos bundle must be byte-identical"


def test_breaker_open_mid_storm_tags_fallback_latencies():
    """ISSUE 6 chaos satellite: when the dispatch breaker opens in the
    middle of a convergence storm, the events served by the scalar
    fallback close under phase="fallback" — the storm report splits
    them out from the batched-device distribution."""
    from holo_tpu.resilience import faults
    from holo_tpu.spf.backend import TpuSpfBackend
    from holo_tpu.spf.synth_storm import StormNet
    from holo_tpu.telemetry import convergence

    net = StormNet(n_routers=60, seed=21, spf_backend=None)
    breaker = CircuitBreaker(
        "spf-storm",
        failure_threshold=2,
        recovery_timeout=1e9,  # stays open through the storm tail
        clock=net.loop.clock.now,
    )
    net.inst.backend = TpuSpfBackend(64, breaker=breaker)
    tracker = convergence.configure(1024, clock=net.loop.clock.now)
    try:
        plan = FaultPlan(seed=21, dispatch_fail={"spf.dispatch": 2})
        with inject(FaultInjector(plan)):
            for i in range(8):
                net.flap(net.flappable[i], lost=False)
                net.loop.advance(12.0)
        net.loop.advance(60.0)
        tracker.sweep()
        assert breaker.state == "open"
        recs = [
            r for r in tracker.timelines() if r["outcome"] == "converged"
        ]
        fallbacks = [r for r in recs if r["fallback"]]
        assert fallbacks, "breaker fallback must tag convergence events"
        assert all(
            any(step == "fallback" for step, _t, _a in r["timeline"])
            for r in fallbacks
        )
        # The histogram split the storm report reads.
        hist = telemetry_registry_hist()
        assert hist.labels(trigger="lsa", phase="fallback").count > 0
    finally:
        convergence.configure(0)


def telemetry_registry_hist():
    from holo_tpu import telemetry

    return telemetry.registry().histogram(
        "holo_convergence_seconds", labelnames=("trigger", "phase")
    )


def test_convergence_storm_survives_pump_thread_kill():
    """ISSUE 6 satellite: a ThreadedLoop pump crash mid-run is detected
    AND respawned under the RestartPolicy (the detected-but-not-
    respawned gap), and the storm network hosted on that loop keeps
    converging afterwards."""
    import time as _time

    from holo_tpu.spf.synth_storm import StormNet
    from holo_tpu.utils.preempt import ThreadedLoop
    from holo_tpu.utils.runtime import RealClock

    home = EventLoop(clock=RealClock())
    sup = Supervisor(RestartPolicy(base_delay=0.05, jitter=0.0)).install(home)
    tl = ThreadedLoop(name="storm-host")
    net = StormNet(n_routers=40, seed=9, loop=tl)
    sup.adopt(tl.loop, sender=tl.send)
    pump_name = sup.watch_pump(tl)
    tl.start()

    def settle(pred, timeout=10.0) -> bool:
        deadline = _time.monotonic() + timeout
        while _time.monotonic() < deadline:
            home.run_until_idle()
            if pred():
                return True
            _time.sleep(0.02)
        return False

    # Initial convergence on the pump thread (real clock).
    assert settle(lambda: len(net.kernel.fib) > 0), "no initial FIB"
    fib0 = dict(net.kernel.fib)

    inj = FaultInjector(FaultPlan(seed=9))
    inj.kill_pump(tl)
    assert settle(lambda: not tl.pump_alive(), 5.0), "pump must die"
    assert tl.pump_crashes == 1
    # Supervision: CrashNotice marshals home, backoff fires, respawn.
    assert settle(lambda: tl.pump_alive(), 10.0), "pump must respawn"
    assert sup.restarts.get(pump_name, 0) == 1

    # The storm keeps converging on the respawned pump: flap an edge
    # whose endpoint owns a stub prefix and watch the FIB move.
    runs0 = net.inst.spf_run_count
    net.flap(net.flappable[0], lost=False)
    assert settle(lambda: net.inst.spf_run_count > runs0), (
        "post-respawn SPF must run"
    )
    assert len(net.kernel.fib) > 0, f"FIB lost after respawn (was {fib0})"
    tl.stop()


def test_delta_chain_breaker_open_falls_back_bit_identical():
    """ISSUE 7 chaos acceptance (1/3): forced dispatch failures open
    the breaker in the middle of a DeltaPath storm — every event from
    then on is served by the scalar fallback, and the final FIB is
    bit-identical to an all-scalar control run of the same seeded
    events.  Runs under jax.transfer_guard('disallow')."""
    from holo_tpu.spf.backend import TpuSpfBackend
    from holo_tpu.spf.synth_storm import StormNet
    from holo_tpu.testing import no_implicit_transfers

    def run(backend):
        net = StormNet(n_routers=60, seed=27, spf_backend=backend)
        for i in range(8):
            net.flap(net.flappable[i], lost=False)
            net.loop.advance(12.0)
        net.ifconfig_metric()
        net.loop.advance(40.0)
        return dict(net.kernel.fib)

    with no_implicit_transfers():
        breaker = CircuitBreaker(
            "spf-delta-breaker",
            failure_threshold=2,
            recovery_timeout=1e9,  # stays open through the tail
        )
        be = TpuSpfBackend(64, breaker=breaker)
        plan = FaultPlan(seed=27, dispatch_fail={"spf.dispatch": 2})
        with inject(FaultInjector(plan)) as inj:
            chaos_fib = run(be)
        assert inj.injected["spf.dispatch"] == 2
        assert breaker.state == "open"
        control_fib = run(None)  # scalar oracle end to end
    assert chaos_fib == control_fib


def test_delta_chain_depth_cap_full_rebuild_identical_digests():
    """ISSUE 7 chaos acceptance (2/3): a depth-capped delta chain keeps
    falling back to the full-rebuild device path mid-storm — causal
    timelines AND FIB digests stay byte-identical to the uncapped
    incremental run.  Runs under jax.transfer_guard('disallow')."""
    from holo_tpu import telemetry
    from holo_tpu.ops.spf_engine import shared_graph_cache
    from holo_tpu.spf.backend import TpuSpfBackend
    from holo_tpu.spf.synth_storm import run_convergence_storm
    from holo_tpu.testing import no_implicit_transfers

    def storm():
        _report, digest, net = run_convergence_storm(
            n_routers=60, events=24, seed=29,
            spf_backend=TpuSpfBackend(64),
        )
        return digest, dict(net.kernel.fib)

    cache = shared_graph_cache()
    old_depth = cache.max_delta_depth
    with no_implicit_transfers():
        digest_inc, fib_inc = storm()
        cache.max_delta_depth = 1
        before = telemetry.snapshot(prefix="holo_spf_delta")
        try:
            digest_capped, fib_capped = storm()
        finally:
            cache.max_delta_depth = old_depth
        after = telemetry.snapshot(prefix="holo_spf_delta")
    fellback = sum(
        v for k, v in after.items() if "path=full-depth" in k
    ) - sum(v for k, v in before.items() if "path=full-depth" in k)
    assert fellback > 0, "the cap must actually force full rebuilds"
    assert digest_capped == digest_inc, "causal timelines must not move"
    assert fib_capped == fib_inc


def test_delta_padding_overflow_full_rebuild_identical():
    """ISSUE 7 chaos acceptance (3/3): a delta overflowing the ELL
    padding slack is refused in place and served by the full-rebuild
    path with bit-identical results, under the transfer guard."""
    import numpy as np

    from holo_tpu import telemetry
    from holo_tpu.ops.graph import Topology, diff_topologies
    from holo_tpu.spf.backend import ScalarSpfBackend, TpuSpfBackend
    from holo_tpu.spf.synth import random_ospf_topology
    from holo_tpu.testing import no_implicit_transfers

    with no_implicit_transfers():
        topo = random_ospf_topology(n_routers=12, n_networks=3, seed=8)
        be = TpuSpfBackend(64)
        be.compute(topo)
        v = int(topo.edge_dst[0])
        k_pad = 8 * (
            1
            + int(np.bincount(topo.edge_dst, minlength=topo.n_vertices).max())
            // 8
        )
        extra = [
            [(v + 1 + i) % topo.n_vertices, v, 5, -1]
            for i in range(k_pad + 2)
        ]
        nxt = Topology(
            n_vertices=topo.n_vertices,
            is_router=topo.is_router.copy(),
            edge_src=np.concatenate(
                [topo.edge_src, np.asarray([e[0] for e in extra], np.int32)]
            ),
            edge_dst=np.concatenate(
                [topo.edge_dst, np.asarray([e[1] for e in extra], np.int32)]
            ),
            edge_cost=np.concatenate(
                [topo.edge_cost, np.asarray([e[2] for e in extra], np.int32)]
            ),
            edge_direct_atom=np.concatenate(
                [
                    topo.edge_direct_atom,
                    np.asarray([e[3] for e in extra], np.int32),
                ]
            ),
            root=topo.root,
        )
        delta = diff_topologies(topo, nxt, max_ops=4 * k_pad + 64)
        assert delta is not None
        nxt.link_delta(delta)
        before = telemetry.snapshot(prefix="holo_spf_delta")
        got = be.compute(nxt)
        ref = ScalarSpfBackend(64).compute(nxt)
        after = telemetry.snapshot(prefix="holo_spf_delta")
    overflowed = sum(
        v for k, v in after.items() if "full-padding-overflow" in k
    ) - sum(v for k, v in before.items() if "full-padding-overflow" in k)
    assert overflowed > 0, "the overflow fallback must actually fire"
    for f in ("dist", "parent", "hops", "nexthop_words"):
        np.testing.assert_array_equal(
            getattr(ref, f), getattr(got, f), err_msg=f
        )


def test_shard_dispatch_failure_mid_storm_falls_back_bit_identical():
    """ISSUE 8 chaos satellite: with the process mesh installed (the
    real multi-chip dispatch path), forced shard-dispatch failures
    mid-storm open the breaker — every event from then on is served by
    the scalar oracle, tagged phase="fallback" on its convergence
    timeline, and the final FIB is bit-identical to an all-scalar
    control run of the same seeded events."""
    from holo_tpu.parallel.mesh import (
        configure_process_mesh,
        reset_process_mesh,
    )
    from holo_tpu.spf.backend import TpuSpfBackend
    from holo_tpu.spf.synth_storm import StormNet
    from holo_tpu.telemetry import convergence

    def run(backend, with_tracker=False):
        net = StormNet(n_routers=60, seed=31, spf_backend=backend)
        tracker = (
            convergence.configure(1024, clock=net.loop.clock.now)
            if with_tracker
            else None
        )
        for i in range(8):
            net.flap(net.flappable[i], lost=False)
            net.loop.advance(12.0)
        net.loop.advance(60.0)
        if tracker is not None:
            tracker.sweep()
        return dict(net.kernel.fib), tracker

    configure_process_mesh(4, 2)
    try:
        breaker = CircuitBreaker(
            "spf-shard-storm",
            failure_threshold=2,
            recovery_timeout=1e9,  # stays open through the storm tail
        )
        plan = FaultPlan(seed=31, dispatch_fail={"spf.shard": 2})
        with inject(FaultInjector(plan)) as inj:
            chaos_fib, tracker = run(
                TpuSpfBackend(64, breaker=breaker), with_tracker=True
            )
        assert inj.injected["spf.shard"] == 2
        assert breaker.state == "open"
        fallbacks = [
            r
            for r in tracker.timelines()
            if r["outcome"] == "converged" and r["fallback"]
        ]
        assert fallbacks, "shard failures must tag convergence events"
        assert all(
            any(step == "fallback" for step, _t, _a in r["timeline"])
            for r in fallbacks
        )
    finally:
        convergence.configure(0)
        reset_process_mesh()
    control_fib, _ = run(None)  # scalar oracle end to end
    assert chaos_fib == control_fib


def test_ospf_reconverges_through_packet_loss():
    """Convergence-under-failure, the metric that matters: with a lossy
    wire AND a link failure mid-run, retransmission machinery still
    reconverges every router onto the surviving path."""
    plan = FaultPlan(seed=9, drop_prob=0.10, timer_jitter=0.3)
    inj = FaultInjector(plan)
    loop = EventLoop(clock=VirtualClock())
    fabric = MockFabric(loop)
    inj.wire_fabric(fabric)
    buses, kernels, ribs, routers = triangle(loop, fabric)
    inj.jittered_advance(loop, 150, steps=15)
    assert {str(nh.addr) for nh in kernels["r1"].fib[DEST][0]} == {"10.0.12.2"}
    # The r1--r2 link dies under continuing loss: r1 must end on r3.
    fabric.set_link_up("l12", False)
    inj.jittered_advance(loop, 120, steps=12)
    assert {str(nh.addr) for nh in kernels["r1"].fib[DEST][0]} == {"10.0.13.2"}
