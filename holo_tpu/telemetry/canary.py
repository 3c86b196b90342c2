"""Synthetic canary prober: black-box trigger→FIB probing (ISSUE 20).

Nothing measured the serving path while user traffic was idle: every
latency the observatory knows comes from REAL topology events, so a
quiet daemon reports nothing — and the first sign of a wedged worker
or a saturated queue is a production trigger paying for it.  This
module is the always-on model graded continuously against the live
protocol ("Advanced Models for the OSPF Routing Protocol", PAPERS.md):
a standing synthetic OSPF instance whose heartbeat topology deltas run
through the REAL actor → ibus → pipeline → RIB path, closing each
probe at ``fib_commit`` so trigger→FIB latency is measured end to end
even on an idle daemon.

Probe contract
--------------
- The canary net (:class:`_CanaryNet`) is a five-router miniature of
  the storm topology — DUT root, two ECMP gateways, a hub, one stub
  leaf — living on the HOST loop (the daemon's or a storm's) with its
  own ibus, its own :class:`RibManager`, and its own mock kernel.  It
  shares exactly two things with production work: the event loop
  (scheduling) and the process dispatch pipeline (admission).  Its FIB
  is disjoint by construction — :func:`fib_digest` over the production
  kernel is asserted unperturbed by a riding canary (the ``slo_storm``
  gate).
- Each heartbeat flips the hub→leaf link metric 1↔2 and reinstalls
  both endpoint Router-LSAs under a fresh ``canary`` causal event.
  The delta forces a real SPF and a real route-metric change, so every
  healthy probe ends in a kernel install; the canary kernel matches
  the install back to the probe's event id (``unattributed`` counts
  installs that arrived with no matching causal id — attribution
  quality, held under 1% by ``tests/test_slo.py::
  test_storm_fib_digest_identical_with_canary_riding``).
- The canary's SPF dispatch rides the process pipeline as a
  ``background``-class ticket (site ``canary.probe``) when one is
  armed: probes are shed FIRST under pressure and can never displace
  correctness work — and the canary's own shed rate is therefore a
  first-class saturation signal (the ``background-delivery`` objective
  in :mod:`holo_tpu.telemetry.slo`).  A shed or timed-out probe serves
  the previous (stale, same-shape) SPF result so the synthetic
  instance never crashes, and grades the probe bad.
- Probe latency is a REAL wall (``profiling.clock()`` — perf_counter
  in production, the deterministic timer under ``explain``), NOT the
  loop's virtual clock: a storm's virtual end-cuts are blind to host
  stalls, which are exactly what the canary exists to see
  (``FaultPlan.dispatch_delay`` breaches, wedged workers, queue
  waits).  Results feed :func:`holo_tpu.telemetry.slo.note_probe` as
  the canary's own objective.

Arming: the daemon boots one prober from ``[telemetry] canary``;
test storms arm one on the storm loop via their event hooks.
Disarmed, nothing here exists — the module seams in dispatch/slo are
the only residue, each one global check.
"""

from __future__ import annotations

import hashlib
import json
import logging
from dataclasses import dataclass
from ipaddress import IPv4Address, IPv4Network

from holo_tpu.resilience import faults
from holo_tpu.routing.rib import MockKernel, RibManager
from holo_tpu.telemetry import convergence, profiling, slo
from holo_tpu.utils.ibus import Ibus
from holo_tpu.utils.netio import NetIo
from holo_tpu.utils.runtime import Actor

log = logging.getLogger("holo_tpu.telemetry")

#: canary net indices (root DUT, dual gateways, hub, stub leaf)
_ROOT, _GW0, _GW1, _HUB, _LEAF = range(5)
#: the leaf's advertised prefix (TEST-NET-2 — never a production route)
_LEAF_PREFIX = IPv4Network("198.51.100.0/24")


def fib_digest(fib: dict) -> str:
    """Canonical digest of a kernel FIB: what the storm tests and
    ``chip_smoke.py`` compare across arms."""
    text = json.dumps(sorted((str(k), str(v)) for k, v in fib.items()))
    return hashlib.sha256(text.encode()).hexdigest()


class _DiscardIo(NetIo):
    """The synthetic neighbors have no receive side."""

    def send(self, ifname, src, dst, data) -> None:
        pass


def _rid(i: int) -> IPv4Address:
    """Canary router ids live in 192.168.0.x — disjoint from the storm
    harness's 10.x synthetic fleet and any production router id a test
    daemon uses, so a canary riding a storm can never alias."""
    return IPv4Address((192 << 24) | (168 << 16) | (i + 1))


@dataclass
class _Beat:
    """Heartbeat timer message (self-rearming via the canary actor)."""


@dataclass
class _ApplyLsas:
    """LSA batch delivered under a causal context (the loop delivery
    hook activates ``event_id`` for the handler's extent — same shape
    as the storm harness's message)."""

    lsas: list
    event_id: tuple | None = None


class _CanaryKernel(MockKernel):
    """Mock kernel that closes probes: every install is matched back to
    the open probe whose causal event id is active at commit time."""

    def __init__(self, prober: "CanaryProber"):
        super().__init__()
        self._prober = prober

    def install(self, *args, **kwargs):
        out = super().install(*args, **kwargs)
        self._prober._on_install(convergence.current())
        return out


class _ProbeBackend:
    """SPF facade for the canary instance: route the dispatch through
    the process pipeline as a background-class ticket when one is
    armed, compute inline otherwise.  Shed/timed-out dispatches serve
    the previous same-shape result (the synthetic topology never
    changes structurally — only the hub→leaf metric flips), so the
    instance's route derivation always has something to chew on."""

    name = "canary"

    def __init__(self, inner, prober: "CanaryProber"):
        self.inner = inner
        self._prober = prober
        self._stale = None
        self.sheds = 0
        self.timeouts = 0

    def __getattr__(self, attr):
        return getattr(self.inner, attr)

    def compute(self, topo, edge_mask=None, multipath_k: int = 1):
        from holo_tpu.pipeline import dispatch as pipeline

        inner = self.inner

        def run():
            # Breach seam: tests inject FaultPlan.dispatch_delay here
            # to slow ONLY the canary's dispatch (a real time.sleep —
            # visible to the probe's profiling-clock wall, invisible to
            # the storm's virtual end-cuts).
            faults.delaypoint("canary.probe")
            return inner.compute(topo, edge_mask, multipath_k=multipath_k)

        pipe = pipeline.process_pipeline()
        if pipe is None or pipe.closed:
            res = run()
            self._stale = res
            return res
        ticket = pipe.submit(
            ("canary", int(topo.root)), "canary", run=run,
            cls="background", site="canary.probe",
            deadline=self._prober.deadline,
        )
        res = None
        try:
            res = ticket.result(timeout=self._prober.overdue)
        except TimeoutError:
            self.timeouts += 1
            self._prober._probe_failed(ticket.eids, "timeout")
        except Exception:  # noqa: BLE001 — a probe dispatch error is a
            # bad probe, never a canary crash (warn-only plane).
            log.debug("canary probe dispatch failed", exc_info=True)
            self._prober._probe_failed(ticket.eids, "error")
        if res is None:
            if ticket.shed is not None:
                self.sheds += 1
                self._prober._probe_failed(ticket.eids, "shed")
            if self._stale is not None:
                return self._stale
            return run()  # first-ever dispatch: nothing stale to serve
        self._stale = res
        return res


class _CanaryActor(Actor):
    def __init__(self, prober: "CanaryProber"):
        self.prober = prober

    def handle(self, msg) -> None:
        if isinstance(msg, _ApplyLsas):
            self.prober.net.apply_lsas(msg.lsas)
        elif isinstance(msg, _Beat):
            self.prober._beat()
            self.prober._rearm()


class _CanaryNet:
    """The standing synthetic instance (see module docstring).  Names
    are ``canary-*`` so registration on a shared loop never collides
    with production actors or the storm harness."""

    DUT = "canary-dut"
    RIB = "canary-routing"
    ACTOR = "canary-driver"

    def __init__(self, loop, prober: "CanaryProber", spf_backend=None,
                 warmup: float = 30.0):
        from holo_tpu.protocols.ospf.instance import (
            IfConfig,
            InstanceConfig,
            OspfInstance,
        )
        from holo_tpu.protocols.ospf.interface import IfType, IsmState
        from holo_tpu.protocols.ospf.neighbor import Neighbor, NsmState
        from holo_tpu.spf.backend import ScalarSpfBackend

        self.loop = loop
        self.bus = Ibus(loop)
        self.kernel = _CanaryKernel(prober)
        self.rib = RibManager(self.bus, self.kernel)
        self.rib.name = self.RIB
        loop.register(self.rib)
        backend = _ProbeBackend(
            spf_backend if spf_backend is not None else ScalarSpfBackend(),
            prober,
        )
        self.inst = OspfInstance(
            name=self.DUT,
            config=InstanceConfig(router_id=_rid(_ROOT)),
            netio=_DiscardIo(),
            spf_backend=backend,
        )
        self.backend = backend
        loop.register(self.inst)
        self.inst.attach_ibus(self.bus, routing_actor=self.RIB)
        loop.register(_CanaryActor(prober), name=self.ACTOR)

        # Fixed miniature topology; only adj[_HUB][_LEAF] ever changes.
        self.adj: dict[int, dict[int, int]] = {i: {} for i in range(5)}
        for a, b in ((_ROOT, _GW0), (_ROOT, _GW1),
                     (_GW0, _HUB), (_GW1, _HUB), (_HUB, _LEAF)):
            self.adj[a][b] = self.adj[b][a] = 1
        self._seq: dict[int, int] = {}

        self.g0_addr = IPv4Address("192.168.255.2")
        self.g1_addr = IPv4Address("192.168.254.2")
        for ifname, net, our, nbr_idx, nbr_addr in (
            ("cn0", "192.168.255.0/30", "192.168.255.1", _GW0, self.g0_addr),
            ("cn1", "192.168.254.0/30", "192.168.254.1", _GW1, self.g1_addr),
        ):
            iface = self.inst.add_interface(
                ifname,
                IfConfig(if_type=IfType.POINT_TO_POINT, cost=1),
                IPv4Network(net),
                IPv4Address(our),
            )
            iface.state = IsmState.POINT_TO_POINT
            iface.neighbors[_rid(nbr_idx)] = Neighbor(
                router_id=_rid(nbr_idx), src=nbr_addr, state=NsmState.FULL
            )
        self.area = self.inst.areas[next(iter(self.inst.areas))]
        inner = getattr(loop, "loop", loop)  # ThreadedLoop hosts
        now = inner.clock.now()
        for i in range(5):
            self.area.lsdb.install(self.router_lsa(i), now)
        # Initial convergence outside any probe; a threaded host loop
        # converges on its own pump instead.
        self.inst._schedule_spf()
        if hasattr(loop, "advance"):
            loop.advance(warmup)

    def router_lsa(self, i: int):
        from holo_tpu.protocols.ospf.packet import (
            Lsa,
            LsaRouter,
            LsaType,
            Options,
            RouterLink,
            RouterLinkType,
        )

        seq = self._seq.get(i, 0) + 1
        self._seq[i] = seq
        links = []
        if i == _ROOT:
            links.append(RouterLink(
                RouterLinkType.POINT_TO_POINT, _rid(_GW0),
                IPv4Address("192.168.255.1"), self.adj[_ROOT][_GW0],
            ))
            links.append(RouterLink(
                RouterLinkType.POINT_TO_POINT, _rid(_GW1),
                IPv4Address("192.168.254.1"), self.adj[_ROOT][_GW1],
            ))
        else:
            for peer, metric in sorted(self.adj[i].items()):
                links.append(RouterLink(
                    RouterLinkType.POINT_TO_POINT, _rid(peer),
                    IPv4Address(0), metric,
                ))
        if i == _LEAF:
            links.append(RouterLink(
                RouterLinkType.STUB_NETWORK,
                _LEAF_PREFIX.network_address, _LEAF_PREFIX.netmask, 1,
            ))
        lsa = Lsa(
            age=1,
            options=Options(0x02),
            type=LsaType.ROUTER,
            lsid=_rid(i),
            adv_rtr=_rid(i),
            seq_no=seq,
            body=LsaRouter(links=links),
        )
        lsa.encode()  # §13.2 change detection needs a real wire image
        return lsa

    def flip_metric(self) -> int:
        """Toggle the hub→leaf metric 1↔2; returns the new metric.  The
        flip moves the leaf route's total cost, so every healthy probe
        ends in a kernel install."""
        m = 2 if self.adj[_HUB][_LEAF] == 1 else 1
        self.adj[_HUB][_LEAF] = self.adj[_LEAF][_HUB] = m
        return m

    def deliver(self, lsas: list, eid) -> None:
        self.loop.send(
            self.ACTOR,
            _ApplyLsas(lsas, (eid,) if eid is not None else None),
        )

    def apply_lsas(self, lsas: list) -> None:
        for lsa in lsas:
            self.inst._install_and_flood(self.area, lsa)
        for area in self.inst.areas.values():
            for iface in area.interfaces.values():
                for nbr in iface.neighbors.values():
                    nbr.ls_rxmt.clear()


class CanaryProber:
    """One standing canary (daemon boot or storm hook).  All probe
    state is touched on the host loop's thread only (beats, LSA
    applies, RIB installs all run there), so plain attributes suffice.
    """

    def __init__(
        self,
        loop,
        period: float = 5.0,
        deadline: float = 0.25,
        overdue: float = 10.0,
        spf_backend=None,
        warmup: float = 30.0,
    ):
        if period <= 0:
            raise ValueError(f"canary period must be positive, got {period}")
        self.period = float(period)
        #: pipeline deadline for the probe ticket (background class —
        #: a probe older than this is not owed a dispatch)
        self.deadline = float(deadline)
        #: real-clock budget before an unclosed probe grades bad
        self.overdue = float(overdue)
        self.loop = loop
        self._seq = 0
        self._open: dict[int, float] = {}  # probe eid -> profiling t0
        self._timer = None
        self._stopped = False
        # verdict tallies (stats surface)
        self.probes = 0
        self.completed = 0
        self.attributed = 0
        self.unattributed = 0
        self.failed = 0
        self.overdue_count = 0
        self.last_ms = None
        self.net = _CanaryNet(
            loop, self, spf_backend=spf_backend, warmup=warmup
        )

    # -- heartbeat ------------------------------------------------------

    def start(self) -> None:
        """Arm the self-rearming heartbeat timer (daemon boot; storms
        get deterministic virtual-time beats the same way since timers
        fire during ``loop.advance``)."""
        self._stopped = False
        self._rearm()

    def stop(self) -> None:
        self._stopped = True
        t = self._timer
        if t is not None and hasattr(t, "cancel"):
            t.cancel()
        self._timer = None

    def _rearm(self) -> None:
        if self._stopped:
            return
        self._timer = self.loop.timer(_CanaryNet.ACTOR, _Beat)
        self._timer.start(self.period)

    def _beat(self) -> None:
        """One heartbeat: flip the canary link, open a probe, deliver
        the endpoint LSAs under its causal event."""
        if self._stopped:
            return
        net = self.net
        m = net.flip_metric()
        eid = convergence.begin("canary", seq=self._seq, metric=m)
        self._seq += 1
        if eid is None:
            # Tracker disarmed: nothing can close a probe — still flip
            # (the canary net stays live) but grade nothing.
            net.deliver([net.router_lsa(_HUB), net.router_lsa(_LEAF)], None)
            return
        self.probes += 1
        # Single-writer by construction: _beat, _on_install and
        # _sweep_overdue all run on the canary loop's actor thread
        # (the timer fires there; the RIB handler commits there).
        self._open[eid] = profiling.clock()  # holo-lint: disable=HL204
        net.deliver([net.router_lsa(_HUB), net.router_lsa(_LEAF)], eid)
        self._sweep_overdue()

    def beat(self) -> None:
        """Manual heartbeat (storm hooks that want probes at exact
        storm indices instead of timer cadence)."""
        self._beat()

    # -- probe close paths ----------------------------------------------

    def _on_install(self, eids: tuple) -> None:
        """Canary-kernel install: close every open probe whose causal
        id is active at commit; an install with no matching id closes
        the oldest probe as ``unattributed`` (attribution quality is a
        test contract, so miscounting must be visible, not silent)."""
        t1 = profiling.clock()
        hit = False
        for e in eids:
            t0 = self._open.pop(e, None)
            if t0 is None:
                continue
            hit = True
            self._close_ok(t1 - t0)
        if not hit and self._open:
            eid = next(iter(self._open))
            t0 = self._open.pop(eid)
            self.unattributed += 1
            self._close_ok(t1 - t0)

    def _close_ok(self, latency: float) -> None:
        lat = max(latency, 0.0)
        self.completed += 1
        self.attributed = self.completed - self.unattributed
        self.last_ms = round(lat * 1e3, 3)
        slo.note_probe(True, lat)

    def _probe_failed(self, eids: tuple, why: str) -> None:
        """Dispatch-side failure (shed / timeout / error): the probe's
        FIB change is never coming — grade it bad now."""
        closed = False
        for e in eids:
            if self._open.pop(e, None) is not None:
                closed = True
        if not closed:
            return
        self.completed += 1
        self.failed += 1
        slo.note_probe(False, None)
        log.debug("canary probe failed (%s)", why)

    def _sweep_overdue(self) -> None:
        t = profiling.clock()
        for eid, t0 in list(self._open.items()):
            if t - t0 > self.overdue:
                self._open.pop(eid, None)
                self.completed += 1
                self.failed += 1
                self.overdue_count += 1
                slo.note_probe(False, None)

    # -- surfaces --------------------------------------------------------

    def unattributed_fraction(self) -> float:
        """Installs closed without a matching causal id, as a fraction
        of completed probes (tests/test_slo.py holds it under 1%)."""
        if not self.completed:
            return 0.0
        return self.unattributed / self.completed

    def stats(self) -> dict:
        """holo-telemetry/slo canary sub-leaf."""
        return {
            "probes": self.probes,
            "completed": self.completed,
            "attributed": self.attributed,
            "unattributed": self.unattributed,
            "failed": self.failed,
            "overdue": self.overdue_count,
            "sheds": self.net.backend.sheds,
            "timeouts": self.net.backend.timeouts,
            "open": len(self._open),
            "last-ms": self.last_ms,
        }


# -- process-wide singleton (daemon boot) --------------------------------

_PROBER: CanaryProber | None = None


def configure(enabled=False, loop=None, **kw) -> CanaryProber | None:
    """Arm (build + start) or disarm (stop + drop) the process-wide
    prober.  ``loop`` is required to arm; ``kw`` passes through to
    :class:`CanaryProber` (period/deadline/overdue/warmup)."""
    global _PROBER
    if _PROBER is not None:
        _PROBER.stop()
        _PROBER = None
    if enabled:
        if loop is None:
            raise ValueError("canary.configure(enabled=True) needs a loop")
        _PROBER = CanaryProber(loop, **kw)
        _PROBER.start()
    return _PROBER


def active() -> CanaryProber | None:
    return _PROBER


def enabled() -> bool:
    return _PROBER is not None
