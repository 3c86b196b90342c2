"""Host stages (ISSUE 25): ``profiling.stage()`` is the one host-span
primitive, on the profiler's clock when armed; the served OSPF path
names six stages of one SPF run (site ``ospf.spf``) and the event loop
one span per delivery (site ``loop``, stage = the actor's name).

The profiler annotation is injected (a recording factory): off the chip
the platform resolves to no annotation at all, and what the tests hold
is the bracketing — entered once per armed stage, nested in call
order, never touched while disarmed.
"""

from __future__ import annotations

from contextlib import contextmanager

import pytest

from holo_tpu import telemetry
from holo_tpu.telemetry import convergence, critpath, profiling
from holo_tpu.utils import runtime
from holo_tpu.utils.runtime import Actor, EventLoop, VirtualClock

SPF_STAGES = ("run", "topology", "link", "derive", "inter", "publish")


class Recorder:
    """Annotation factory that records enter / exit by label."""

    def __init__(self):
        self.events: list[tuple[str, str]] = []

    @contextmanager
    def __call__(self, label: str):
        self.events.append(("enter", label))
        try:
            yield
        finally:
            self.events.append(("exit", label))


def _poison(*_a, **_k):
    raise AssertionError("touched on the disarmed path")


@pytest.fixture
def recorder():
    rec = Recorder()
    profiling.set_annotation_factory(rec)
    try:
        yield rec
    finally:
        profiling.set_device_profiling(False)
        profiling.set_annotation_factory()
        profiling.set_stage_timer(None)
        critpath.configure(0)
        convergence.configure(0)


def _stage_children(site: str) -> dict:
    """``{stage: (count, sum)}`` of ``holo_profile_stage_seconds{site}``."""
    out = {}
    for key, child in telemetry.snapshot("holo_profile_stage_seconds").items():
        labels = dict(
            kv.split("=", 1) for kv in key.split("{", 1)[1][:-1].split(",")
        )
        if labels["site"] == site and labels["device"] == "-":
            out[labels["stage"]] = (child["count"], child["sum"])
    return out


def _moved(before: dict, after: dict) -> dict:
    zero = (0, 0.0)
    return {
        s: (after[s][0] - before.get(s, zero)[0],
            after[s][1] - before.get(s, zero)[1])
        for s in after
        if after[s][0] != before.get(s, zero)[0]
    }


# -- the primitive ---------------------------------------------------------


def test_armed_stage_enters_its_annotation_once_and_nests(recorder):
    profiling.set_device_profiling(True)
    with profiling.stage("a", "b"):
        with profiling.stage("a", "c"):
            pass
    with profiling.stage("a", "b"):
        pass
    assert recorder.events == [
        ("enter", "a.b"), ("enter", "a.c"), ("exit", "a.c"), ("exit", "a.b"),
        ("enter", "a.b"), ("exit", "a.b"),
    ]
    assert _stage_children("a")["b"][0] >= 2


def test_annotation_leaves_on_an_exception_and_records_nothing(recorder):
    profiling.set_device_profiling(True)
    before = _stage_children("a.raise")
    with pytest.raises(KeyError):
        with profiling.stage("a.raise", "b"):
            raise KeyError("boom")
    assert recorder.events == [("enter", "a.raise.b"), ("exit", "a.raise.b")]
    assert _stage_children("a.raise") == before  # clean exits only


def test_disarmed_stage_calls_no_factory_and_reads_no_clock():
    profiling.set_device_profiling(False)
    profiling.set_annotation_factory(_poison)
    profiling.set_stage_timer(_poison)
    try:
        with profiling.stage("a", "b"):
            pass
        with profiling.stage("ospf.spf", "run"):
            pass
    finally:
        profiling.set_stage_timer(None)
        profiling.set_annotation_factory()


def test_host_sites_do_not_feed_the_observer():
    """An armed observatory is fed every dispatch stage and keys it by
    the dispatch context; a host stage has none and stays out (and,
    profiling off, reads no clock for it)."""
    seen = []
    profiling.set_observer(lambda *a: seen.append(a))
    try:
        with profiling.stage("spf.one", "marshal"):
            pass
        profiling.set_stage_timer(_poison)
        with profiling.stage("ospf.spf", "run"):
            pass
        with profiling.stage("loop", "routing"):
            pass
    finally:
        profiling.set_stage_timer(None)
        profiling.set_observer(None)
    assert [(s, n) for s, n, _d, _dt in seen] == [("spf.one", "marshal")]


def test_annotation_resolves_once_from_the_platform_and_is_none_off_tpu():
    import jax

    jax.devices()  # the backend is up, so arming can latch
    profiling.set_annotation_factory()
    assert profiling._annotation is profiling._UNRESOLVED
    try:
        profiling.set_device_profiling(True)
        assert profiling._annotation is None  # the CPU: no annotation
        with profiling.stage("a", "b"):
            pass
    finally:
        profiling.set_device_profiling(False)
        profiling.set_annotation_factory()


def test_arming_installs_the_loop_seam_and_disarming_clears_it():
    assert runtime._DELIVERY_STAGE is None
    profiling.set_device_profiling(True)
    try:
        assert runtime._DELIVERY_STAGE is profiling.stage
    finally:
        profiling.set_device_profiling(False)
    assert runtime._DELIVERY_STAGE is None


# -- one span per loop delivery --------------------------------------------


class _Echo(Actor):
    def __init__(self):
        self.got = []

    def handle(self, msg) -> None:
        self.got.append(msg)
        if msg == "boom":
            raise RuntimeError("handler crashed")


def test_loop_books_one_stage_per_delivery_when_armed_and_none_disarmed(
    recorder,
):
    loop = EventLoop(clock=VirtualClock())
    a, b = _Echo(), _Echo()
    loop.register(a, name="hs-alpha")
    loop.register(b, name="hs-beta")

    before = _stage_children("loop")
    for i in range(3):
        loop.send("hs-alpha", i)
    loop.run_until_idle()
    assert _stage_children("loop") == before and recorder.events == []

    profiling.set_device_profiling(True)
    for i in range(3):
        loop.send("hs-alpha", i)
    loop.send("hs-beta", "x")
    loop.send("hs-beta", "boom")  # a crashed delivery: no observation
    loop.run_until_idle()
    profiling.set_device_profiling(False)
    moved = _moved(before, _stage_children("loop"))
    assert {s: c for s, (c, _sum) in moved.items()} == {
        "hs-alpha": 3, "hs-beta": 1,
    }
    assert recorder.events.count(("enter", "loop.hs-alpha")) == 3
    assert recorder.events.count(("exit", "loop.hs-beta")) == 2
    assert len(a.got) == 6 and b.got == ["x", "boom"]

    loop.send("hs-alpha", 9)
    loop.run_until_idle()
    assert _moved(before, _stage_children("loop")) == moved


# -- the six stages of one SPF run -----------------------------------------


def _storm_net():
    from holo_tpu.spf.backend import TpuSpfBackend
    from holo_tpu.spf.synth_storm import StormNet

    net = StormNet(n_routers=24, seed=3, spf_backend=TpuSpfBackend())
    net.loop.advance(30.0)  # the initial LSDB's own SPF, unarmed
    return net


def test_spf_run_books_six_stages_once_per_run_and_run_bounds_them(recorder):
    net = _storm_net()
    before, runs0 = _stage_children("ospf.spf"), net.inst.spf_run_count
    profiling.set_device_profiling(True)
    for edge in net.flappable[:2]:
        net.flap(edge, lost=False)
        net.loop.advance(30.0)
    net.ifconfig_metric()  # moves routes: the RIB gets work
    net.loop.advance(30.0)
    profiling.set_device_profiling(False)
    runs = net.inst.spf_run_count - runs0
    assert runs >= 3
    moved = _moved(before, _stage_children("ospf.spf"))
    assert set(moved) == set(SPF_STAGES)
    assert {s: c for s, (c, _sum) in moved.items()} == dict.fromkeys(
        SPF_STAGES, runs
    )  # one area: every stage once per run
    run_s = moved["run"][1]
    inner = {s: moved[s][1] for s in SPF_STAGES if s != "run"}
    assert all(0.0 <= v <= run_s for v in inner.values())
    assert sum(inner.values()) <= run_s  # disjoint parts of the run

    # On the profiler's clock: the parts open inside ospf.spf.run, the
    # run inside the instance's delivery, in call order.
    ev = recorder.events
    first_run = ev.index(("enter", "ospf.spf.run"))
    last = len(ev) - 1 - ev[::-1].index(("exit", "ospf.spf.run"))
    order = [label for kind, label in ev[first_run:last] if kind == "enter"]
    assert order[:3] == [
        "ospf.spf.run", "ospf.spf.topology", "ospf.spf.link",
    ]
    assert order[3] in ("spf.one.marshal", "spf.one.delta")  # the dispatch
    assert ("enter", f"loop.{net.DUT}") in ev[:first_run]
    assert ("enter", "loop.routing") in ev  # the RIB's work, by actor


def _lsas_counted() -> dict:
    snap = telemetry.snapshot("holo_ospf_topology_lsas_total")
    return {
        path: sum(v for k, v in snap.items() if f"path={path}" in k)
        for path in ("lowered", "reused")
    }


def test_second_run_lowers_the_flapped_lsas_and_lowering_goes_with_base():
    """The ``topology`` stage keeps the area's lowered LSDB between runs
    (ISSUE 30): a flap re-installs two router-LSAs, the next run lowers
    those two and reuses the rest; the instance holds the lowering
    beside the DeltaPath base and drops the two together."""
    from holo_tpu.protocols.ospf.packet import LsaKey, LsaType

    net = _storm_net()
    inst, area = net.inst, net.area
    assert set(inst._spf_lowerings) == set(inst._spf_delta_bases) == {
        area.area_id
    }
    kept = inst._spf_lowerings[area.area_id]
    net.flap(net.flappable[0], lost=False)
    net.loop.advance(30.0)
    before, runs0 = _lsas_counted(), inst.spf_run_count
    net.flap(net.flappable[1], lost=False)
    net.loop.advance(30.0)
    assert inst.spf_run_count == runs0 + 1
    after = _lsas_counted()
    n = len(area.lsdb.entries)
    assert after["lowered"] - before["lowered"] == 2
    assert after["reused"] - before["reused"] == n - 2
    assert inst._spf_lowerings[area.area_id] is kept
    assert kept.entries == list(area.lsdb.entries.values())

    # No self LSA: no topology, and neither a base nor a lowering kept.
    rid = inst.config.router_id
    area.lsdb.remove(LsaKey(LsaType.ROUTER, rid, rid))
    inst._spf_force_full = True
    inst.run_spf()
    assert area.area_id not in inst._spf_delta_bases
    assert area.area_id not in inst._spf_lowerings


def test_every_run_of_the_instance_derives_from_the_run_plan():
    """The ``derive`` stage reads the plan the area's kept lowering made
    for the run (ISSUE 32): every ``derive_routes`` call of an instance
    counts ``planned``; the base the instance keeps carries that run's
    plan, whose offers are the routes' prefixes."""
    def calls() -> dict:
        snap = telemetry.snapshot("holo_ospf_derive_calls_total")
        return {
            path: sum(v for k, v in snap.items() if f"path={path}" in k)
            for path in ("planned", "walked")
        }

    before = calls()
    net = _storm_net()
    inst, area = net.inst, net.area
    for k in range(3):
        net.flap(net.flappable[k], lost=False)
        net.loop.advance(30.0)
    after = calls()
    assert after["walked"] == before["walked"]
    assert after["planned"] - before["planned"] == inst.spf_run_count > 3
    plan = inst._spf_delta_bases[area.area_id].plan
    assert plan.now <= net.loop.clock.now()
    offered = set(plan.prefixes)
    intra = {p for p, r in inst.routes.items() if r.rtype == "intra"}
    assert intra and intra <= offered
    reached = inst._spf_lowerings[area.area_id]._reached.reached
    assert len(inst._area_reachable_routers[area.area_id]) == reached.sum()


def test_waterfall_keeps_its_phases_and_telescopes_with_stages_armed(recorder):
    """The ledger folds only marshal / delta / device / readback /
    solve: the host stages leave every cut where it was."""

    def one_event(armed: bool) -> dict:
        net = _storm_net()
        convergence.configure(256, clock=net.loop.clock.now)
        cp = critpath.configure(check_every=0)
        profiling.set_device_profiling(armed)
        try:
            net.ifconfig_metric()  # moves routes: converges at the FIB
            net.loop.advance(30.0)
        finally:
            profiling.set_device_profiling(False)
        [w] = cp.waterfalls()
        critpath.configure(0)
        convergence.configure(0)
        return w

    plain, armed = one_event(False), one_event(True)
    assert tuple(armed["phases"]) == tuple(plain["phases"]) == critpath.PHASES
    assert abs(sum(armed["phases"].values()) - armed["wall"]) < 1e-9
    assert armed["verdict"] in ("host", "device", "queue")
    assert ("enter", "ospf.spf.run") in recorder.events
