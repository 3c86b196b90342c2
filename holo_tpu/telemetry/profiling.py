"""Per-dispatch device-time breakdown (the deep-profiling tentpole).

PR 2's dispatch telemetry measures the SPF/FRR hot path from the host
side only: one wall-clock histogram around the whole dispatch and a
readback timer.  This module splits each dispatch span into the three
phases that actually matter for the DeltaPath incremental-SPF work —

- **marshal** — host graph/plane preparation + the (async) jit call;
- **device** — device execution, measured by ``jax.block_until_ready``
  bracketing;
- **readback** — device→host materialization of the result planes.

:func:`stage` is also the one host-span primitive.  The served OSPF
path names its host work with it (site ``ospf.spf``: ``run`` /
``topology`` / ``link`` / ``derive`` / ``inter`` / ``publish``) and the
event loop names every delivery by its actor (site ``loop``).  Armed on
a real TPU, every stage also sits inside a
``jax.profiler.TraceAnnotation("<site>.<stage>")``, so a profiler
capture shows the stages on the same clock as the device operations.

Each phase records a nested trace sub-span AND a
``holo_profile_stage_seconds{site,stage,device}`` histogram observation
carrying an OpenMetrics **exemplar** ``{span_id=...}`` — a scrape can
jump from a latency bucket straight to the trace span that produced it.
``device="-"`` is the whole-dispatch span; under a process mesh the
device phase additionally splits into per-device completion sub-spans
(``device=<id>``, :func:`device_stages`) so a straggling shard is
attributable to its chip.

Compile-time cost attribution rides the same switch: when a backend
sees a fresh (engine, shape) bucket it calls :func:`record_cost`, which
runs ``jit(...).lower(...).compile().cost_analysis()`` and records the
XLA FLOP / bytes-accessed estimates per dispatch site — the denominator
that turns a measured device time into achieved-vs-peak utilization.

While armed the module also keeps an exact **account of the host's
wall by innermost span** (ISSUE 38): every stage edge charges the time
since the thread's last edge to the span on top of its stack (``-``
where none is open), so over any interval on one thread the children
of ``holo_profile_self_seconds_total{span}`` sum to the interval's wall
and a nested span's time is counted once (:func:`self_seconds` is the
same account charged up to *now*).  The collector's pauses are a span
of the program too: arming appends one callback to ``gc.callbacks``
that brackets every collection as ``runtime.gc`` — an annotation in the
capture, an edge of the account (the pause is taken *out of* the span
it interrupted) and an observation of
``holo_runtime_gc_pause_seconds{generation}`` /
``holo_runtime_gc_collected_total{generation}``.

Everything is **off by default** (``[telemetry] profile-device-time``
in holod.toml, :func:`set_device_profiling` programmatically): when
disabled, :func:`stage` costs one module-global bool check and
:func:`sync` is a no-op — no extra device synchronization is added to
the dispatch path (``tests/test_host_stages.py::
test_disarmed_stage_calls_no_factory_and_reads_no_clock``; the armed
cost was read on the chip by PR 25, PERF.md section 6).  Metric updates
here are
O(1) (a float and a small exemplar tuple) — nothing reads device
values or reduces arrays on the traced path (holo-lint HL101/HL105).
"""

from __future__ import annotations

import gc
import logging
import sys
import threading
import time
from contextlib import contextmanager, nullcontext

from holo_tpu import telemetry

log = logging.getLogger("holo_tpu.telemetry")

_STAGE_SECONDS = telemetry.histogram(
    "holo_profile_stage_seconds",
    "Per-dispatch sub-span time (marshal / device / readback) and host "
    "stages (site=ospf.spf: one SPF run's functions; site=loop: one "
    "delivery, stage=<actor>); "
    "device=<id> rows are the per-device completion split of a "
    "mesh-sharded dispatch ('-' = host-side / whole-dispatch span)",
    ("site", "stage", "device"),
)
_COST_FLOPS = telemetry.gauge(
    "holo_profile_cost_flops",
    "XLA compile-time FLOP estimate for the last-compiled shape bucket",
    ("site",),
)
_COST_BYTES = telemetry.gauge(
    "holo_profile_cost_bytes",
    "XLA compile-time bytes-accessed estimate for the last-compiled "
    "shape bucket",
    ("site",),
)

_SELF_SECONDS = telemetry.counter(
    "holo_profile_self_seconds_total",
    "Host wall by innermost armed span, exclusive of the spans nested "
    "in it (span=<site>.<stage>; '-' = under no armed span; "
    "'runtime.gc' = a collector pause, taken out of the span it "
    "interrupted); over an interval on one thread the children sum to "
    "the interval's wall",
    ("span",),
)
_GC_PAUSE = telemetry.histogram(
    "holo_runtime_gc_pause_seconds",
    "Pause of one collection of Python's cyclic collector (gc.callbacks "
    "start to stop), armed with profile-device-time",
    ("generation",),
)
_GC_COLLECTED = telemetry.counter(
    "holo_runtime_gc_collected_total",
    "Objects the cyclic collector freed, by the generation collected",
    ("generation",),
)

_enabled = False

# Dispatch-observatory feed (ISSUE 12): when armed, every stage
# observation is ALSO handed to the observer callback — the streaming
# quantile sketches in holo_tpu.telemetry.observatory.  One module
# global: the disarmed hot-path cost is exactly this None check.
_OBSERVER = None

# Critical-path feed (ISSUE 17): when armed, every stage's begin AND
# end edge is handed to the phase hook — the cross-thread waterfall in
# holo_tpu.telemetry.critpath stamps the active convergence events
# with marshal/device cuts.  Same discipline as _OBSERVER: one module
# global, the disarmed hot-path cost is exactly this None check.
_PHASE_HOOK = None

# Stage timer: time.perf_counter in production; the observatory's
# DeterministicTimer swaps it so a seeded workload produces
# byte-identical sketches (set_stage_timer).
_timer = time.perf_counter
_timer_overridden = False

# Dispatch context (thread-local): the backend labels its dispatch
# window with (kind, engine, shape-bucket) so the observatory can key
# sketches without new arguments threading through every stage() call.
# Only ever entered while an observer is armed — dispatch_context()
# returns a shared null context otherwise, so the un-observed hot path
# pays one global check and one call.
_ctx_local = threading.local()
_NULLCTX = nullcontext()

# Host sites: stages of host functions, outside any dispatch.  They
# have no dispatch context to key a sketch by, and a loop delivery is
# bimodal by nature (a timer tick or a whole SPF run), which the
# observatory's regression sentinel would flag for ever: they never
# feed it (decided here, by the site: no switch).
_HOST_SITES = frozenset({"ospf.spf", "loop"})

# Profiler-annotation factory of an armed stage: ``factory(label)`` is a
# context manager on the profiler's clock.  _UNRESOLVED until JAX is up
# (at arming, else at the first armed stage after), then latched for the
# process: ``jax.profiler.TraceAnnotation`` on a TPU, None elsewhere.
_UNRESOLVED = object()
_annotation = _UNRESOLVED

# The account of the host's wall by innermost span (armed only).  One
# per thread: ``stage()`` edges and the collector's callback move it,
# :func:`self_seconds` reads it.  ``_account_epoch`` counts armings: an
# account of an older one forgets its open spans and its last edge
# (what went by while disarmed is nobody's), and keeps its totals.
_GC_SPAN = "runtime.gc"
_account_local = threading.local()
_account_epoch = 0
_self_children: dict = {}  # span -> its child of _SELF_SECONDS


class _Account:
    """One thread's wall by innermost open span, exclusive and exact:
    every charge is ``now - last`` and moves ``last`` to ``now``, so
    the totals telescope to the wall whatever the order of the edges."""

    __slots__ = ("epoch", "last", "stack", "totals", "pauses", "gc")

    def __init__(self):
        self.epoch = 0
        self.last = 0.0
        self.stack: list[str] = []  # open armed spans, innermost last
        self.totals = {"-": 0.0}  # span -> seconds, cumulative
        # the collection under way on this thread: (begin, what its
        # begin charged, its annotation); and the finished ones the
        # registry has not been told yet: (generation, what begin and
        # end charged, seconds, collected)
        self.gc = None
        self.pauses: list = []


def _account(now: float) -> _Account:
    acct = getattr(_account_local, "acct", None)
    if acct is None:
        acct = _account_local.acct = _Account()
    if acct.epoch != _account_epoch:
        acct.epoch, acct.last = _account_epoch, now
        del acct.stack[:]
        acct.gc = None
    return acct


def _charge(acct: _Account, now: float):
    """Charge the time since the thread's last edge to its innermost
    open span; returns ``(span, seconds)`` for the registry, or None
    for an edge that was overtaken (already charged).  The collector's
    callback runs this too, between any two bytecodes of an edge on the
    same thread: so nothing here takes a lock, ``last`` moves before
    anything is called, and the book is updated by a subscript."""
    last = acct.last
    if now <= last:
        return None
    acct.last = now
    stack = acct.stack
    span = stack[-1] if stack else "-"
    dt = now - last
    totals = acct.totals
    try:
        totals[span] += dt
    except KeyError:
        totals[span] = dt
    return span, dt


def _self_child(span: str):
    child = _self_children.get(span)
    if child is None:
        child = _self_children[span] = _SELF_SECONDS.labels(span=span)
    return child


def _publish(acct: _Account, charged) -> None:
    """Tell the registry what an edge charged, and what the collector's
    callback left for it: the callback itself never does, because a
    registry child is updated under its lock, which the code the
    collector interrupted may hold."""
    if charged is not None:
        _self_child(charged[0]).inc(charged[1])
    if acct.pauses:
        pauses, acct.pauses = acct.pauses, []
        for generation, charges, seconds, collected in pauses:
            for charged in charges:  # at the pause's begin and end
                if charged is not None:
                    _self_child(charged[0]).inc(charged[1])
            _GC_PAUSE.labels(generation=generation).observe(seconds)
            _GC_COLLECTED.labels(generation=generation).inc(collected)


def _span_edge(label: str, begin: bool, now: float) -> None:
    acct = _account(now)
    charged = _charge(acct, now)
    if begin:
        acct.stack.append(label)
    elif acct.stack and acct.stack[-1] == label:
        acct.stack.pop()
    _publish(acct, charged)


def account_at(now: float) -> tuple[float, dict]:
    """The calling thread's seconds by innermost armed span, charged up
    to ``now`` first, with the time they stand at: ``now``, or the
    later edge that overtook it (a collection that began between the
    caller's clock read and this call).  For a stamp that has to agree
    with the account to the float (the critical-path ledger's
    ``sched`` and ``run0``): the difference of two such snapshots sums
    to the difference of their times.  Disarmed: ``(now, {})``."""
    if not _enabled:
        return now, {}
    acct = _account(now)
    _publish(acct, _charge(acct, now))
    return acct.last, dict(acct.totals)


def self_seconds() -> dict:
    """The calling thread's seconds by innermost armed span (the
    children of ``holo_profile_self_seconds_total`` it fed), charged up
    to *now* first, so that a snapshot inside an open span is exact:
    the difference of two snapshots sums to the wall between them.
    Disarmed: ``{}``, and no clock read."""
    return account_at(_timer())[1] if _enabled else {}


def _on_gc(phase: str, info: dict) -> None:
    """``gc.callbacks`` entry while armed: one collection is the span
    ``runtime.gc`` of the thread it runs on.  Warn-only, as
    :func:`_phase_guarded`: a bug here must never reach the code that
    happened to allocate.  Under a swapped stage timer it does nothing:
    a counter clock counts reads, and when the collector runs is no
    part of a seeded workload."""
    if _timer_overridden:
        return
    try:
        now = _timer()
        acct = _account(now)
        if phase == "start":
            before = _charge(acct, now)
            acct.stack.append(_GC_SPAN)
            acct.gc = (now, before, None)  # stands if the factory raises
            if _annotation is not None and _annotation is not _UNRESOLVED:
                ann = _annotation(_GC_SPAN)
                ann.__enter__()
                acct.gc = (now, before, ann)
        elif acct.gc is not None:  # else: armed inside a collection
            (t0, before, ann), acct.gc = acct.gc, None
            charged = (before, _charge(acct, now))
            if acct.stack and acct.stack[-1] == _GC_SPAN:
                acct.stack.pop()
            acct.pauses.append((
                str(info.get("generation", "-")), charged, now - t0,
                info.get("collected", 0),
            ))
            if ann is not None:
                ann.__exit__(None, None, None)
    except Exception:  # noqa: BLE001 — see contract above
        log.debug("gc callback failed", exc_info=True)


# (site, shape signature) -> {"flops": float, "bytes": float}; one entry
# per compiled shape bucket, exactly mirroring the backends' jit caches.
_cost_lock = threading.Lock()
_cost_table: dict[tuple, dict] = {}


def set_device_profiling(on: bool) -> None:
    """Arm/disarm the per-dispatch breakdown (daemon boot reads
    ``[telemetry] profile-device-time``; the benchmark's ``--trace 1``
    and tests flip it directly)."""
    global _enabled, _account_epoch
    on = bool(on)
    if on != _enabled:
        if on:
            _account_epoch += 1  # every thread's account starts anew
            gc.callbacks.append(_on_gc)
            _enabled = True
        elif _on_gc in gc.callbacks:
            gc.callbacks.remove(_on_gc)
        # Arming: this thread's account starts here.  Disarming: it is
        # charged up to here, and what the collector left for the
        # registry reaches it.
        self_seconds()
        _enabled = on
    if _enabled and _annotation is _UNRESOLVED:
        _resolve_annotation()
    # The event loop's per-delivery span (site "loop", stage = actor).
    from holo_tpu.utils import runtime

    runtime.set_delivery_stage(stage if _enabled else None)


def device_profiling() -> bool:
    return _enabled


def set_annotation_factory(factory=_UNRESOLVED) -> None:
    """Tests: inject the armed stages' annotation factory
    (``factory(label) -> context manager``; None = no annotation).  No
    argument: back to unresolved, latched from the platform again."""
    global _annotation
    _annotation = factory


def _resolve_annotation():
    """Latch the annotation factory from the platform, once.  Never
    brings JAX up itself (a daemon armed at boot, or one that serves
    from the scalar backend, must not take the chip for a span): while
    no backend is initialised the answer is None, unlatched."""
    global _annotation
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    try:
        from jax._src.xla_bridge import backends_are_initialized

        if not backends_are_initialized():
            return None
    except ImportError:  # the private seam moved: JAX is imported, ask it
        pass
    try:
        tpu = jax.default_backend() == "tpu"
        _annotation = jax.profiler.TraceAnnotation if tpu else None
    except Exception:  # noqa: BLE001 — best-effort on exotic backends
        log.debug("profiler annotation unavailable", exc_info=True)
        _annotation = None
    return _annotation


def set_observer(fn) -> None:
    """Install/remove the dispatch-observatory stage observer (ISSUE
    12; :func:`holo_tpu.telemetry.observatory.configure` is the only
    caller).  ``fn(site, stage, device, seconds)`` runs after every
    completed stage observation; ``None`` disarms — the stage hot path
    then pays exactly one global check for the feature."""
    global _OBSERVER
    _OBSERVER = fn


def observing() -> bool:
    """True while a stage observer (the observatory) is armed."""
    return _OBSERVER is not None


def set_phase_hook(fn) -> None:
    """Install/remove the critical-path stage-edge hook (ISSUE 17;
    :func:`holo_tpu.telemetry.critpath.configure` is the only caller).
    ``fn(site, stage, device, edge)`` runs at every stage begin
    (``edge='b'``) and clean-exit end (``edge='e'``) — the hook reads
    :func:`clock` itself, so a DeterministicTimer makes its stamps
    byte-identical too; ``None`` disarms."""
    global _PHASE_HOOK
    _PHASE_HOOK = fn


def set_stage_timer(fn) -> None:
    """Swap the stage timer (``None`` restores ``time.perf_counter``).
    The observatory's ``DeterministicTimer`` uses this for
    byte-identical seeded runs; nothing else should."""
    global _timer, _timer_overridden
    _timer = fn if fn is not None else time.perf_counter
    _timer_overridden = fn is not None


def stage_timer_overridden() -> bool:
    return _timer_overridden


def clock() -> float:
    """The stage timer — ``time.perf_counter`` unless a deterministic
    timer is installed.  Dispatch walls that feed the engine tuner read
    THIS instead of ``time.perf_counter`` directly, so a deterministic
    explain run makes deterministic tuner decisions (and the whole
    report stays byte-identical); in production the two are the same
    function."""
    return _timer()


def dispatch_ctx() -> dict | None:
    """The active dispatch context (observer keying), or None."""
    return getattr(_ctx_local, "ctx", None)


@contextmanager
def _dispatch_context(kw: dict):
    prev = getattr(_ctx_local, "ctx", None)
    _ctx_local.ctx = kw
    try:
        yield
    finally:
        _ctx_local.ctx = prev


def dispatch_context(**kw):
    """Label the enclosed dispatch for the observatory feed — the
    backends wrap each device dispatch with its (kind, engine,
    shape-bucket).  A shared null context when no observer is armed,
    so the unobserved dispatch path pays one check + one call."""
    if _OBSERVER is None:
        return _NULLCTX
    return _dispatch_context(kw)


@contextmanager
def stage(site: str, name: str, device: str = "-"):
    """One dispatch phase: a nested trace sub-span plus a
    ``holo_profile_stage_seconds`` observation whose exemplar links the
    bucket to the sub-span id.  ``site`` is the dispatch site
    (``spf.one``, ``spf.whatif``, ``frr.batch``, ...), ``name`` the
    phase (``marshal`` / ``device`` / ``readback``); ``device`` is the
    per-device split label of a sharded dispatch ('-' = whole span,
    see :func:`device_stages`).

    When the dispatch observatory is armed (:func:`set_observer`) the
    measured wall is ALSO fed to its streaming sketches — including
    with device profiling off, so the observatory can stay always-on
    without the histogram/exemplar machinery; observations keep the
    existing contract of recording only on clean exit.  Host sites
    (``_HOST_SITES``) are not dispatches and never feed it.

    Armed, the whole stage also sits inside the profiler annotation
    ``<site>.<name>`` (see ``_annotation``): a host span in the same
    capture as the device operations; and both its edges move the
    thread's account of its wall by innermost span (``_Account``)."""
    obs = _OBSERVER
    if obs is not None and site in _HOST_SITES:
        obs = None
    ph = _PHASE_HOOK
    if ph is not None:
        _phase_guarded(ph, site, name, device, "b")
    if not _enabled:
        if obs is None:
            yield None
        else:
            t0 = _timer()
            yield None
            _observe_guarded(obs, site, name, device, _timer() - t0)
        if ph is not None:
            _phase_guarded(ph, site, name, device, "e")
        return
    ann = _annotation
    if ann is _UNRESOLVED:
        ann = _resolve_annotation()
    label = f"{site}.{name}"
    t0 = _timer()
    _span_edge(label, True, t0)
    t1 = None
    try:
        with _NULLCTX if ann is None else ann(label):
            with telemetry.span(label, stage=name, device=device) as sid:
                yield sid
        t1 = _timer()
    finally:  # the account closes the span on an exception too
        _span_edge(label, False, _timer() if t1 is None else t1)
    dt = t1 - t0
    _STAGE_SECONDS.labels(site=site, stage=name, device=device).observe(
        dt, exemplar={"span_id": sid}
    )
    if obs is not None:
        _observe_guarded(obs, site, name, device, dt)
    if ph is not None:
        _phase_guarded(ph, site, name, device, "e")


def _observe_guarded(obs, site, name, device, dt) -> None:
    """The observatory is warn-only BY CONTRACT: an observer bug (e.g.
    a lock-free race losing a bin mid-quantile) must never propagate
    into the dispatch, where the circuit breaker would misread it as a
    device failure and serve the scalar fallback."""
    try:
        obs(site, name, device, dt)
    except Exception:  # noqa: BLE001 — see contract above
        log.debug("stage observer failed", exc_info=True)


def _phase_guarded(ph, site, name, device, edge) -> None:
    """Same warn-only contract as :func:`_observe_guarded`: a
    critical-path hook bug must never fail the dispatch it stamps."""
    try:
        ph(site, name, device, edge)
    except Exception:  # noqa: BLE001 — see contract above
        log.debug("stage phase hook failed", exc_info=True)


def device_stages(site: str, tree) -> bool:
    """Per-device completion split of a mesh-sharded dispatch: block on
    each device's result shards in device-id order, recording one
    ``stage(site, "device", device=<id>)`` sub-span each.

    Spans are sequential from the host's vantage point: the first
    device's span absorbs most of the wait and later spans measure the
    RESIDUAL skew after earlier devices completed — exactly the
    straggler signal worth watching on a real mesh (a healthy sharded
    dispatch shows one fat span and near-zero residuals; a slow chip
    shows up as a fat residual at its id).  Returns False — recording
    nothing — when profiling is disarmed or the result lives on fewer
    than two devices; callers then fall back to the plain :func:`sync`
    barrier, so single-device dispatch behavior is unchanged."""
    if not _enabled:
        return False
    import jax

    by_dev: dict = {}
    try:
        for leaf in jax.tree_util.tree_leaves(tree):
            shards = getattr(leaf, "addressable_shards", None)
            if not shards:
                continue
            for sh in shards:
                by_dev.setdefault(sh.device, []).append(sh.data)
    except Exception:  # noqa: BLE001 — introspection is best-effort;
        # the caller's sync barrier still bounds the device phase.
        log.debug("shard enumeration failed under profiling", exc_info=True)
        return False
    if len(by_dev) < 2:
        return False
    for dev in sorted(by_dev, key=lambda d: getattr(d, "id", 0)):
        with stage(site, "device", device=str(getattr(dev, "id", dev))):
            try:
                jax.block_until_ready(by_dev[dev])
            except Exception:  # noqa: BLE001 — same contract as sync()
                log.debug(
                    "block_until_ready failed under profiling", exc_info=True
                )
    return True


def sync(tree) -> None:
    """Completion barrier bounding the **device** phase: block until the
    jit result pytree is ready.  A no-op when profiling is off — the
    un-profiled dispatch path keeps its async overlap and pays for the
    device inside the readback materialization instead.  An armed
    observatory also needs the barrier: without it every device wall
    would hide inside the readback sketch."""
    if not _enabled and _OBSERVER is None:
        return
    import jax

    try:
        jax.block_until_ready(tree)
    except Exception:  # noqa: BLE001 — a profiler barrier must never
        # fail a dispatch the breaker would otherwise see succeed.
        log.debug("block_until_ready failed under profiling", exc_info=True)


def record_cost(site: str, jitfn, *args, shape_sig: tuple = ()) -> dict | None:
    """Compile-time FLOP/bytes estimate for a freshly-compiled shape
    bucket via ``jitfn.lower(*args).compile().cost_analysis()``.

    Called by the backends right after :meth:`_track_compile` reports a
    fresh (engine, shape) signature, so the table mirrors the jit cache
    one-to-one.  The lower+compile pair re-runs XLA compilation for the
    bucket (the AOT path does not share the jit dispatch cache), which
    is why this only runs when profiling is armed — it is compile-time
    cost on a cold bucket, never per-dispatch cost.  Never raises:
    backends without cost analysis record nothing.  The armed
    observatory needs the same capture (its roofline numerators), so
    either switch enables it."""
    if not _enabled and _OBSERVER is None:
        return None
    try:
        ca = jitfn.lower(*args).compile().cost_analysis()
    except Exception as e:  # noqa: BLE001 — platform-dependent API
        log.debug("cost analysis unavailable for %s: %r", site, e)
        return None
    if isinstance(ca, (list, tuple)):  # some jax versions: one per device
        ca = ca[0] if ca else {}
    if not isinstance(ca, dict):
        return None
    entry = {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
    }
    with _cost_lock:
        _cost_table[(site, tuple(shape_sig))] = entry
    _COST_FLOPS.labels(site=site).set(entry["flops"])
    _COST_BYTES.labels(site=site).set(entry["bytes"])
    return entry


def stage_median(
    site: str, stage: str, device: str = "-"
) -> float | None:
    """Approximate median of ``holo_profile_stage_seconds{site,stage}``
    from the histogram's cumulative bucket counts (upper bucket
    boundary of the bucket containing the median — a <=2x
    overestimate given the log-spaced ladder, which is plenty for
    ratio decisions).  None when the stage has no observations.

    This is the engine auto-tuner's GLOBAL fallback signal
    (holo_tpu/pipeline/tuner.py): its per-shape-bucket decisions use
    the dispatch walls the backends feed it directly, but a
    fresh bucket with no samples can still consult the process-wide
    stage distribution."""
    child = _STAGE_SECONDS.labels(site=site, stage=stage, device=device)
    total = child.count
    if not total:
        return None
    half = (total + 1) // 2
    for le, cum in child.cumulative():
        if cum >= half:
            return float(le)
    return None


def cost_table() -> dict[tuple, dict]:
    """Snapshot of {(site, shape signature) -> cost estimates}."""
    with _cost_lock:
        return {k: dict(v) for k, v in _cost_table.items()}


def clear_cost_table() -> None:
    """Tests only."""
    with _cost_lock:
        _cost_table.clear()


def capture_device_trace(
    trace_dir, n_routers: int = 48, seed: int = 3
) -> dict:
    """One REAL ``jax.profiler.trace()`` around a seeded SPF dispatch
    ([telemetry] device-trace-dir).

    The capture only runs when the attached platform is a TPU; any
    other platform yields a ``captured: False`` row with the platform
    and the reason, never a failure.  The compile is warmed outside the
    trace so the captured timeline is one steady-state dispatch."""
    from pathlib import Path

    import jax

    platform = jax.devices()[0].platform
    row: dict = {"platform": platform, "captured": False,
                 "trace_dir": str(trace_dir)}
    if platform != "tpu":
        row["reason"] = f"no TPU attached (platform={platform})"
        return row
    from holo_tpu.spf.backend import TpuSpfBackend
    from holo_tpu.spf.synth import random_ospf_topology

    topo = random_ospf_topology(
        n_routers=n_routers,
        n_networks=max(n_routers // 8, 4),
        extra_p2p=max(n_routers // 2, 16),
        seed=seed,
    )
    backend = TpuSpfBackend()
    backend.compute(topo)  # warm: compile + marshal outside the trace
    out = Path(trace_dir)
    out.mkdir(parents=True, exist_ok=True)
    with jax.profiler.trace(str(out)):
        backend.compute(topo)
    row.update(
        captured=True,
        n_vertices=int(topo.n_vertices),
        files=sum(1 for p in out.rglob("*") if p.is_file()),
    )
    return row
