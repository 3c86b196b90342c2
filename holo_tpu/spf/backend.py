"""Pluggable SPF backends.

``SpfBackend.compute`` is the single dispatch point the protocol layer calls
from its SPF-delay FSM (the reference's compute site: holo-ospf/src/spf.rs:428-435).
The scalar backend is the default (reference semantics, zero marshaling
latency — the right choice for small LSDBs); the TPU backend wins on large
LSDBs and on batched what-if / multi-root workloads, which the scalar path
can only do serially.
"""

from __future__ import annotations

import itertools
import time
from dataclasses import dataclass

import jax
import numpy as np

from holo_tpu import telemetry
from holo_tpu.analysis.runtime import (
    assert_live,
    consumes_donated,
    note_donated,
    sanctioned_transfer,
)
from holo_tpu.ops.graph import Topology
from holo_tpu.resilience import faults
from holo_tpu.resilience.breaker import CircuitBreaker
from holo_tpu.ops.spf_engine import (
    DeviceGraph,
    mp_pad,
    note_delta,
    shared_graph_cache,
    spf_multipath_batch,
    spf_multiroot,
    spf_one,
    spf_one_incremental,
    spf_one_incremental_multipath,
    spf_one_multipath,
    spf_whatif_batch,
)
from holo_tpu.ops.tropical import (
    repair_rows_host,
    tropical_multiroot,
    tropical_spf_one,
    tropical_spf_one_incremental,
    tropical_spf_one_incremental_multipath,
    tropical_spf_one_multipath,
    tropical_whatif_batch,
)

#: engine names that dispatch through the tropical tile planes
_TROPICAL_ENGINES = ("tropical", "mp_tropical")
#: "no scenario mask" as a jit operand: see ``TpuSpfBackend._one_mask``
_NO_MASK = np.zeros(0, bool)
from holo_tpu.spf.scalar import spf_multipath_reference, spf_reference
from holo_tpu.telemetry import convergence, profiling

# Device-dispatch observability (the tentpole signal set): wall time per
# dispatch, device->host readback time, jit recompiles vs shape-cache
# hits (a silent recompile storm is the classic invisible regression),
# and marshaled-graph cache behavior.  Shape tracking is done HERE (a
# seen-signature set per backend) rather than poking jit internals, so
# it works identically on every jax version and platform.
_DISPATCH_SECONDS = telemetry.histogram(
    "holo_spf_dispatch_seconds",
    "Wall time of one SPF dispatch (incl. readback)",
    ("backend", "kind"),
)
_TRANSFER_SECONDS = telemetry.histogram(
    "holo_spf_transfer_seconds",
    "Device->host readback time per dispatch",
    ("kind",),
)
_JIT_COMPILES = telemetry.counter(
    "holo_spf_jit_compiles_total",
    "Dispatches that hit a new (engine, shape) bucket (XLA recompile)",
    ("kind",),
)
_JIT_HITS = telemetry.counter(
    "holo_spf_jit_cache_hits_total",
    "Dispatches served from an already-compiled shape bucket",
    ("kind",),
)
_GRAPH_CACHE = telemetry.counter(
    "holo_spf_graph_cache_total",
    "Marshaled DeviceGraph cache lookups",
    ("result",),
)
_BATCH_SCENARIOS = telemetry.counter(
    "holo_spf_scenarios_total",
    "Scenario-SPFs computed (batch rows count individually)",
    ("kind",),
)
_SHARD_DISPATCHES = telemetry.counter(
    "holo_spf_shard_dispatch_total",
    "Dispatches routed through the process-mesh sharded path "
    "(parallel/mesh.py layout contract)",
    ("kind",),
)


def _mesh():
    """The process dispatch mesh (parallel/mesh.py), or None."""
    from holo_tpu.parallel.mesh import process_mesh

    return process_mesh()


def _mesh_key():
    from holo_tpu.parallel.mesh import mesh_cache_key

    return mesh_cache_key()


@dataclass
class SpfResult:
    """Backend-independent SPF output in host (numpy) space.

    The multipath planes (ISSUE 10) are present iff the dispatch asked
    for them (``multipath_k > 1``); ``None`` otherwise — the k=1 path
    is byte-for-byte the single-parent dispatch (``tests/test_multipath.py::
    test_multipath_k1_is_the_unchanged_single_parent_dispatch``)."""

    dist: np.ndarray  # int32[N]
    parent: np.ndarray  # int32[N]
    hops: np.ndarray  # int32[N]
    nexthop_words: np.ndarray  # uint32[N, W]
    parents: np.ndarray | None = None  # int32[N, Kp]; sentinel N
    pdist: np.ndarray | None = None  # int32[N, Kp]; INF past the set
    pweight: np.ndarray | None = None  # int32[N, Kp]
    npaths: np.ndarray | None = None  # int32[N]
    nh_weights: np.ndarray | None = None  # int32[N, A]


def _host_tensors(out, n: int):
    """Materialize device SPF tensors into the host contract: vertex
    axis sliced back to N and the sentinels renormalized.

    Node-sharded residents pad rows to a multiple of the mesh's node
    axis, so the device program's "no parent" sentinel is the PADDED
    row count R (and unreachable hops R+1) — map them back to N / N+1
    so sharded output is byte-identical to the single-device path.  On
    an unpadded graph every step is a no-op (slice of full extent;
    minimum against a value no tensor reaches)."""
    dist = np.asarray(out.dist)[..., :n]
    parent = np.minimum(np.asarray(out.parent)[..., :n], np.int32(n))
    hops = np.minimum(np.asarray(out.hops)[..., :n], np.int32(n + 1))
    nh = np.asarray(out.nexthops)[..., :n, :]
    return dist, parent, hops, nh


def _host_mp(mp, n: int) -> dict:
    """Multipath-plane readback under the same sharded-row contract as
    :func:`_host_tensors`: vertex axis sliced to N, the padded-row
    parent sentinel R renormalized to N.  SpfResult field kwargs."""
    return {
        "parents": np.minimum(
            np.asarray(mp.parents)[..., :n, :], np.int32(n)
        ),
        "pdist": np.asarray(mp.pdist)[..., :n, :],
        "pweight": np.asarray(mp.pweight)[..., :n, :],
        "npaths": np.asarray(mp.npaths)[..., :n],
        "nh_weights": np.asarray(mp.nh_weights)[..., :n, :],
    }


@dataclass
class MultiRootResult:
    """Multi-root SPF output: SPT shape only (see compute_multiroot)."""

    dist: np.ndarray  # int32[R, N]
    parent: np.ndarray  # int32[R, N]
    hops: np.ndarray  # int32[R, N]


@dataclass
class _InFlightOne:
    """Phase-1 state of a split (pipelined) kind=one dispatch — see
    ``TpuSpfBackend.launch_one`` / ``finish_one``."""

    out: object  # device SpfTensors, dispatch possibly still in flight
    topo: Topology
    t0: float
    engine: str
    bucket: tuple | None  # tuner bucket; None = "never feed the tuner"
    mode: str  # "full" | "delta"
    n_atoms: int
    delta_kind: str = ""
    kp: int = 1  # pow2 multipath width; 1 = single-parent kernel
    remember: bool = False
    sharded: bool = False
    remarshal: bool = False
    fresh: bool = False  # fresh XLA compile: not a tuner sample
    # Observatory shape key (ISSUE 12) — deliberately separate from
    # ``bucket`` so observing never overrides the tuner's None sentinel.
    obucket: tuple | None = None
    # Wall of the launch phase alone: tuner samples use launch_s +
    # finish wall, EXCLUDING the time the entry sat parked in the
    # pipeline's in-flight slot while the worker served other keys —
    # parked time is scheduling, not engine cost, and would bias both
    # the engine medians and the delta/full depth ratio.
    launch_s: float = 0.0


class SpfBackend:
    """Interface: one SPF run, a what-if batch, or a multi-root batch."""

    name = "abstract"

    def compute(self, topo: Topology, edge_mask: np.ndarray | None = None) -> SpfResult:
        raise NotImplementedError

    def compute_whatif(self, topo: Topology, edge_masks: np.ndarray) -> list[SpfResult]:
        raise NotImplementedError


class ScalarSpfBackend(SpfBackend):
    """Default backend: exact reference-semantics Dijkstra on the host CPU."""

    name = "scalar"

    def __init__(self, n_atoms: int = 64):
        self.n_atoms = n_atoms

    def _one(self, topo: Topology, edge_mask, kp: int = 1) -> SpfResult:
        n_atoms = max(self.n_atoms, topo.n_atoms())
        if kp > 1:
            out, omp = spf_multipath_reference(
                topo, kp, edge_mask, n_lanes=((n_atoms + 31) // 32) * 32
            )
            return SpfResult(
                dist=out.dist,
                parent=out.parent,
                hops=out.hops,
                nexthop_words=out.nexthop_words(n_atoms),
                parents=omp.parents,
                pdist=omp.pdist,
                pweight=omp.pweight,
                npaths=omp.npaths,
                nh_weights=omp.nh_weights,
            )
        out = spf_reference(topo, edge_mask)
        return SpfResult(
            dist=out.dist,
            parent=out.parent,
            hops=out.hops,
            nexthop_words=out.nexthop_words(n_atoms),
        )

    def compute(self, topo, edge_mask=None, multipath_k: int = 1):
        # Same dispatch histogram as the TPU backend (kind axis shared):
        # a default-config daemon still reports SPF timing; only the
        # transfer/recompile signals are device-specific.
        t0 = profiling.clock()
        with telemetry.span("spf.dispatch", kind="one", backend="scalar"):
            res = self._one(topo, edge_mask, mp_pad(multipath_k))
        _DISPATCH_SECONDS.labels(backend="scalar", kind="one").observe(
            profiling.clock() - t0
        )
        _BATCH_SCENARIOS.labels(kind="one").inc()
        convergence.note_dispatch("spf", "scalar")
        return res

    def compute_whatif(self, topo, edge_masks, multipath_k: int = 1):
        t0 = profiling.clock()
        kp = mp_pad(multipath_k)
        with telemetry.span(
            "spf.dispatch", kind="whatif", backend="scalar",
            batch=len(edge_masks),
        ):
            res = [self._one(topo, m, kp) for m in edge_masks]
        _DISPATCH_SECONDS.labels(backend="scalar", kind="whatif").observe(
            profiling.clock() - t0
        )
        _BATCH_SCENARIOS.labels(kind="whatif").inc(len(res))
        convergence.note_dispatch("spf", "scalar")
        return res

    def compute_multiroot(self, topo, roots: np.ndarray) -> "MultiRootResult":
        import copy

        dists, parents, hops = [], [], []
        for r in roots:
            t = copy.copy(topo)
            t.root = int(r)
            out = spf_reference(t)
            dists.append(out.dist)
            parents.append(out.parent)
            hops.append(out.hops)
        return MultiRootResult(
            dist=np.stack(dists), parent=np.stack(parents), hops=np.stack(hops)
        )


# Partitioned-resident cache namespaces (one per backend, process-wide
# unique for the process lifetime — see TpuSpfBackend._part_ns).
_PART_NS_IDS = itertools.count()


class TpuSpfBackend(SpfBackend):
    """JAX/XLA backend: jitted tensor SPF, cached per topology generation.

    Marshaling (Topology → ELL → DeviceGraph) happens once per LSDB
    generation and is reused across runs/batches; jit caches compile per
    (N, K, W) shape bucket.
    """

    name = "tpu"

    def __init__(
        self,
        n_atoms: int = 64,
        max_iters: int | None = None,
        engine: str = "gather",
        one_engine: str = "seq",
        breaker: CircuitBreaker | None = None,
        incremental: bool = True,
        prev_capacity: int = 32,
        partition_threshold: int | None = None,
        partition_parts: int | None = None,
        partition_max_part: int = 4096,
    ):
        """``engine``: 'gather' (ELL gathers; handles any topology) or
        'blocked' (block-sparse Pallas kernels for the single-path
        planes; multipath_k > 1 always rides the ``mp`` kernels).
        'blocked' requires unique (src,dst) pairs, distances < 2**27
        and at most 4 failed edges per scenario: a topology or batch
        outside that raises ``ValueError`` from the device arm, which
        the breaker counts as a failed dispatch — it never quietly
        runs the gather engine under the 'blocked' name.

        ``one_engine`` picks the gather-path fixpoint formulation
        ('fused' | 'packed' | 'seq' — see :func:`spf_one_fused`); all are
        bit-identical, differing only in TPU round/gather scheduling.
        'seq' is the default, and the only engine the ledger has read on
        the chip (all four cells); none has been A/B'd against it there
        (ROADMAP S3) — flip only once a run on the chip shows another
        engine winning.

        ``breaker`` guards every device dispatch: XLA exceptions and
        deadline overruns fall back to the scalar oracle (bit-identical
        by the parity contract), and repeated failures open the circuit
        so a dead device stops being retried on the SPF hot path.

        ``incremental`` arms the DeltaPath dispatch: topologies carrying
        delta lineage (``Topology.link_delta`` at the LSDB seam) are
        served by an in-place device-graph update plus the seeded
        incremental kernel instead of a full re-marshal + full-batch
        recompute.  False forces the full-rebuild path everywhere.
        ``prev_capacity`` bounds the retained
        previous-tensor entries — one live (topology, root) chain per
        entry, so size it >= the number of areas/MTs the instance
        computes per SPF cycle or their chains silently degrade to
        ``full-no-prev``.

        ``partition_threshold`` arms the hierarchical partitioned path
        (ISSUE 15): kind=one/whatif dispatches on topologies with at
        least that many vertices route through
        :class:`holo_tpu.ops.partition.PartitionedSpfEngine` — the
        graph is cut (natively via ``Topology.partition_hint``, else
        the deterministic BFS/greedy cut into ``partition_parts`` parts
        or parts of ≤ ``partition_max_part`` vertices), solved as one
        batched dispatch of small per-partition programs, and stitched
        exactly through the boundary-contraction skeleton.  None (the
        default) keeps every dispatch monolithic.  Bit-identical to the
        monolithic kernels and scalar oracle on every arm (the parity
        contract); breaker fallback and DeltaPath compose."""
        self.n_atoms = n_atoms
        self.max_iters = max_iters
        self.engine = engine
        self.one_engine = one_engine
        self.incremental = incremental
        self.prev_capacity = int(prev_capacity)
        self.breaker = (
            breaker if breaker is not None else CircuitBreaker("spf-dispatch")
        )
        self._oracle = ScalarSpfBackend(n_atoms)
        self._blocked_cache: dict[tuple, object] = {}
        self._jit_blocked = None  # built lazily (pallas import)
        # (kind, shape...) signatures already dispatched: a miss here is
        # a fresh XLA compile for this backend instance.
        self._compiled_shapes: set[tuple] = set()
        # Previous SpfTensors per (topology key, n_atoms, root): the
        # device-resident seed state of the incremental kernel.  The
        # entry is DONATED into the kernel that consumes it.
        self._prev_one: dict[tuple, object] = {}
        # Gather-path jits, one per fixpoint engine (lazily built):
        # the engine auto-tuner (holo_tpu.pipeline.tuner) flips the
        # formulation per shape bucket at dispatch time, so the pinned
        # ``one_engine`` is only the untuned default.  All engines are
        # bit-identical (parity-gated), so a flip is a latency choice,
        # never a semantic one.
        self._one_jits: dict[str, object] = {}
        self._batch_jits: dict[str, object] = {}
        # Multipath (ISSUE 10) jits, one per pow2 parent-set width kp:
        # the widened kernel is dispatched ONLY when a dispatch asks
        # for multipath_k > 1 — the k=1 path rides the unchanged
        # single-parent programs (tests/test_multipath.py::
        # test_multipath_k1_is_the_unchanged_single_parent_dispatch).
        self._mp_jits: dict[int, object] = {}
        self._mp_batch_jits: dict[int, object] = {}
        self._mp_incr_jits: dict[int, object] = {}
        # Tropical (ISSUE 13) jits: the blocked min-plus programs take
        # the tile planes as an extra operand, so they live in their
        # own caches; the tuner flips between the families per shape
        # bucket (all bit-identical — a flip is a latency choice).
        self._trop_jits: dict[tuple, object] = {}
        self._jit_multiroot = jax.jit(
            lambda g, rs, m: spf_multiroot(g, rs, m, self.max_iters)
        )
        self._jit_incr = jax.jit(
            lambda g, r, prev, seeds: spf_one_incremental(
                g, r, prev, seeds, self.max_iters
            ),
            donate_argnums=(2,),
        )
        # What the last prepare() actually did ('hit'/'delta'/'miss'):
        # the depth auto-tuner attributes full-rebuild walls to cache
        # misses only (a warm hit is not a re-marshal cost).
        self._last_prepare_how = ""
        # Mesh-sharded dispatch programs, built lazily per (kind,
        # engine, mesh identity): outputs pinned to the batch sharding
        # so GSPMD propagates the scenario/root split through the whole
        # program.
        self._shard_jits: dict[tuple, object] = {}
        # Partitioned-SPF state (ISSUE 15): the engine is lazy (first
        # partitioned dispatch); residents ride the process-wide
        # DeviceGraphCache as per-partition entries — one lock/LRU/
        # eviction surface with the monolithic DeltaPath residents —
        # keyed per (backend namespace, root, n_atoms, mesh) chain.
        self.partition_threshold = partition_threshold
        self.partition_parts = partition_parts
        self.partition_max_part = int(partition_max_part)
        self._part_engine = None
        # Monotonic, never reused (id(self) can be recycled after GC,
        # letting a new backend adopt a dead backend's residents).
        self._part_ns = f"part:{next(_PART_NS_IDS)}"
        # Device-residency byte ledger (ISSUE 17 satellite): weakref
        # registration only — the ledger walks _prev_one lazily at
        # scrape time, and a dropped backend never leaks through it.
        from holo_tpu.telemetry import residency

        residency.register_spf_backend(self)

    def _jit_one_for(self, engine: str):
        fn = self._one_jits.get(engine)
        if fn is None:
            from holo_tpu.ops.spf_engine import _ONE_ENGINES

            one = _ONE_ENGINES[engine]
            fn = self._one_jits[engine] = jax.jit(
                lambda g, r, m: one(g, r, m, self.max_iters)
            )
        return fn

    def _jit_batch_for(self, engine: str):
        fn = self._batch_jits.get(engine)
        if fn is None:
            fn = self._batch_jits[engine] = jax.jit(
                lambda g, r, ms: spf_whatif_batch(
                    g, r, ms, self.max_iters, engine=engine
                )
            )
        return fn

    def _jit_mp_for(self, kp: int):
        fn = self._mp_jits.get(kp)
        if fn is None:
            fn = self._mp_jits[kp] = jax.jit(
                lambda g, r, m, _kp=kp: spf_one_multipath(
                    g, r, _kp, m, self.max_iters
                )
            )
        return fn

    def _jit_mp_batch_for(self, kp: int):
        fn = self._mp_batch_jits.get(kp)
        if fn is None:
            fn = self._mp_batch_jits[kp] = jax.jit(
                lambda g, r, ms, _kp=kp: spf_multipath_batch(
                    g, r, ms, _kp, self.max_iters
                )
            )
        return fn

    def _jit_mp_incr_for(self, kp: int):
        """Incremental multipath jit: the previous SpfTensors plus the
        two multipath planes that actually carry state (``npaths``,
        ``nh_weights``) are donated — same ownership discipline as
        ``_jit_incr``, widened.  The parent-set planes are closed-form
        in the settled distances and never read by the kernel, so they
        are not passed (HL301: a donated-but-unused arg is pruned and
        its alias can never realize)."""
        fn = self._mp_incr_jits.get(kp)
        if fn is None:
            fn = self._mp_incr_jits[kp] = jax.jit(
                lambda g, r, prev, np_prev, aw_prev, seeds, _kp=kp: (
                    spf_one_incremental_multipath(
                        g, r, prev, np_prev, aw_prev, seeds,
                        _kp, self.max_iters,
                    )
                ),
                donate_argnums=(2, 3, 4),
            )
        return fn

    def _jit_trop(self, key: str, build):
        fn = self._trop_jits.get(key)
        if fn is None:
            fn = self._trop_jits[key] = build()
        return fn

    @property
    def _jit_trop_one(self):
        return self._jit_trop(
            "one",
            lambda: jax.jit(
                lambda g, tt, r, m, rr: tropical_spf_one(
                    g, tt, r, m, rr, self.max_iters
                )
            ),
        )

    @property
    def _jit_trop_batch(self):
        return self._jit_trop(
            "whatif",
            lambda: jax.jit(
                lambda g, tt, r, ms, rr: tropical_whatif_batch(
                    g, tt, r, ms, rr, self.max_iters
                )
            ),
        )

    def _jit_trop_mp_for(self, kp: int):
        return self._jit_trop(
            f"mp{kp}",
            lambda: jax.jit(
                lambda g, tt, r, m, rr, _kp=kp: tropical_spf_one_multipath(
                    g, tt, r, _kp, m, rr, self.max_iters
                )
            ),
        )

    @property
    def _jit_trop_incr(self):
        return self._jit_trop(
            "incr",
            lambda: jax.jit(
                lambda g, tt, r, prev, seeds: tropical_spf_one_incremental(
                    g, tt, r, prev, seeds, self.max_iters
                ),
                donate_argnums=(3,),
            ),
        )

    def _jit_trop_mp_incr_for(self, kp: int):
        # Donation mirrors _jit_mp_incr_for: prev plus the two live
        # multipath planes only — the parent-set planes never realize.
        return self._jit_trop(
            f"mp-incr{kp}",
            lambda: jax.jit(
                lambda g, tt, r, prev, np_prev, aw_prev, seeds, _kp=kp: (
                    tropical_spf_one_incremental_multipath(
                        g, tt, r, prev, np_prev, aw_prev, seeds,
                        _kp, self.max_iters,
                    )
                ),
                donate_argnums=(3, 4, 5),
            ),
        )

    @property
    def _jit_trop_multiroot(self):
        return self._jit_trop(
            "multiroot",
            lambda: jax.jit(
                lambda g, tt, rs, m, rr: tropical_multiroot(
                    g, tt, rs, m, rr, self.max_iters
                )
            ),
        )

    def _trop_operands(self, topo, g, mask=None):
        """(tiles, repair rows) for one tropical dispatch — call inside
        the sanctioned marshal window (the tile device_put and the
        repair-row lowering are part of that transfer).  The repair
        rows carry the destinations of masked-out edges, padded with
        the resident's PADDED row count (drop sentinel)."""
        tt = shared_graph_cache().get_tropical(
            topo, max(self.n_atoms, topo.n_atoms())
        )
        rows = int(g.in_src.shape[0])
        if mask is None:
            rr = np.zeros(0, np.int32)
        else:
            rr = repair_rows_host(
                topo.edge_dst, np.asarray(mask, bool)[None, :], rows
            )[0]
        return tt, rr

    def _one_step(self, engine: str, kp: int, g, tt, root, mask, rr):
        """(jit, args) of one single-SPF dispatch for the picked
        engine — the gather/tropical/mp/mp_tropical fan-in shared by
        the sync and split-phase paths."""
        if kp > 1:
            if engine == "mp_tropical":
                return self._jit_trop_mp_for(kp), (g, tt, root, mask, rr)
            return self._jit_mp_for(kp), (g, root, mask)
        if engine == "tropical":
            return self._jit_trop_one, (g, tt, root, mask, rr)
        return self._jit_one_for(engine), (g, root, mask)

    def _incr_step(self, topo, g, n_atoms, kp, pad, prev_key, prev, seeds_p):
        """Dispatch ONE incremental (DeltaPath) kernel — the
        gather/tropical x single/multipath fan-in shared by the sync
        and split-phase paths.  Must run inside the caller's
        ``spf.one.delta`` sanctioned window (the tile attach may
        device_put).  The previous tensors are DONATED into the
        kernel: our ``_prev_one`` reference is dropped here, before
        dispatch, so a failed dispatch can never leave a consumed
        entry behind.  Returns ``(step, out, trop, tt, sig, fresh)``."""
        trop = self._trop_incremental(topo, kp)
        tt = (
            shared_graph_cache().get_tropical(topo, n_atoms)
            if trop
            else None
        )
        sig = (
            g.in_src.shape, g.direct_nh_words.shape[2], pad,
            _mesh_key(), kp,
            None if tt is None else tt.tiles.shape,
        )
        fresh = self._track_compile("delta", "incr", *sig)
        del self._prev_one[prev_key]
        if kp > 1:
            np_prev, aw_prev = prev[1].npaths, prev[1].nh_weights
            if trop:
                step = self._jit_trop_mp_incr_for(kp)
                out = step(
                    g, tt, topo.root, prev[0], np_prev, aw_prev, seeds_p
                )
            else:
                step = self._jit_mp_incr_for(kp)
                out = step(g, topo.root, prev[0], np_prev, aw_prev, seeds_p)
        elif trop:
            step = self._jit_trop_incr
            out = step(g, tt, topo.root, prev, seeds_p)
        else:
            step = self._jit_incr
            out = step(g, topo.root, prev, seeds_p)
        # Runtime half of HL109: under the test-mode donation guard
        # the consumed previous tensors are actually poisoned, so any
        # use-after-donate the static rule missed raises at read time
        # on the CPU platform exactly as it would corrupt on device.
        # The whole previous state is poisoned — including the
        # multipath parent-set planes that are recomputed rather than
        # donated — because ownership transfers wholesale here even
        # where the jit-level donation is narrower.
        note_donated("spf.one.delta", prev)
        return step, out, trop, tt, sig, fresh

    def _incr_cost_args(self, trop, tt, g, root, out, seeds_p, kp):
        """record_cost re-trace args for a fresh incremental compile —
        the donated prev args are gone, so this run's own output
        tensors stand in (same shapes/dtypes)."""
        root_args = (g, tt, root) if trop else (g, root)
        return (
            (*root_args, out[0], out[1].npaths, out[1].nh_weights, seeds_p)
            if kp > 1
            else (*root_args, out, seeds_p)
        )

    # Kept as properties: external probes (tests, cost tooling) read
    # the pinned-engine jits.  Pinned tropical returns the tile-plane
    # jit — NOTE its call signature is (g, tt, root, mask, rr), not
    # the gather engines' (g, root, mask).
    @property
    def _jit_one(self):
        if self.one_engine == "tropical":
            return self._jit_trop_one
        return self._jit_one_for(self.one_engine)

    @property
    def _jit_batch(self):
        if self.one_engine == "tropical":
            return self._jit_trop_batch
        return self._jit_batch_for(self.one_engine)

    def _pick_engine(self, kind: str, topo, batch: int = 1, kp: int = 1):
        """(engine, shape bucket | None) for this dispatch: the
        process engine tuner's per-shape choice when one is armed, else
        the pinned ``one_engine``.  Lazy import keeps the unarmed path
        at a sys.modules hit.

        Multipath dispatches (``kp > 1``) choose between the packed
        row-gather kernel (``mp``) and its tropical DAG-tile variant
        (``mp_tropical``, kind=one only — ISSUE 13), still under a
        bucket carrying kp in the shape key (the tuner learns k as
        part of the shape: k=1 engine medians never mix with k=8
        walls)."""
        from holo_tpu.pipeline.tuner import active_tuner, shape_bucket

        t = active_tuner()
        if t is None or self.engine == "blocked":
            if kp > 1:
                pinned_trop = (
                    self.one_engine == "tropical" and kind == "one"
                )
                return ("mp_tropical" if pinned_trop else "mp"), None
            return self.one_engine, None
        bucket = shape_bucket(
            topo.n_vertices, topo.n_edges, batch, _mesh_key(), k=kp
        )
        return t.pick(kind, bucket), bucket

    @staticmethod
    def _tuner_observe(kind, bucket, engine, seconds) -> None:
        if bucket is None:
            return
        from holo_tpu.pipeline.tuner import active_tuner

        t = active_tuner()
        if t is not None:
            t.observe(kind, bucket, engine, seconds)

    @staticmethod
    def _tuner_cost(kind, bucket, engine, entry) -> None:
        if bucket is None or entry is None:
            return
        from holo_tpu.pipeline.tuner import active_tuner

        t = active_tuner()
        if t is not None:
            t.cost_prior(kind, bucket, engine, entry)

    def _obs_bucket(self, topo, batch: int, kp: int, bucket):
        """The observatory's shape key for this dispatch (ISSUE 12):
        the tuner bucket when one was computed, else the same pow2
        quantization derived directly — sketches must key on shape
        even when no tuner is armed.  Kept SEPARATE from the tuner's
        bucket variable: ``_pick_engine`` returns ``bucket=None`` as a
        deliberate "never feed the tuner" sentinel (blocked-engine
        backends, unarmed tuner), and arming a passive observability
        feature must not start mutating engine-selection state.
        Returns None while the observatory is disarmed."""
        if not profiling.observing():
            return None
        if bucket is not None:
            return bucket
        from holo_tpu.pipeline.tuner import shape_bucket

        return shape_bucket(
            topo.n_vertices, topo.n_edges, batch, _mesh_key(), k=kp
        )

    @staticmethod
    def _obs_cost(site, kind, engine, bucket, entry) -> None:
        """Forward a fresh-compile cost entry to the observatory's
        roofline join (the ``cost_prior`` twin for sketches)."""
        if entry is None or not profiling.observing():
            return
        from holo_tpu.telemetry import observatory

        observatory.note_cost(site, kind, engine, bucket, entry)

    def _depth_bucket(self, topo, kp: int = 1):
        """The DeltaPath depth-tuning bucket (kind=one, batch=1).
        ``kp`` rides the shape key: the widened kernel's delta/full
        walls must not contaminate the k=1 bucket's depth ratio."""
        from holo_tpu.pipeline.tuner import shape_bucket

        return shape_bucket(
            topo.n_vertices, topo.n_edges, 1, _mesh_key(), k=kp
        )

    def _trop_incremental(self, topo, kp: int) -> bool:
        """Route this chain's engine-fixed incremental kernel through
        the tropical tiles?  Yes when the backend is pinned tropical,
        or when the tuner's measured full-dispatch winner for this
        shape bucket is the tropical family — the incremental program
        should relax on the same representation the full program
        proved fastest at this shape."""
        if self.one_engine == "tropical":
            return True
        from holo_tpu.pipeline.tuner import active_tuner

        t = active_tuner()
        if t is None:
            return False
        return (
            t.current_winner("one", self._depth_bucket(topo, kp))
            in _TROPICAL_ENGINES
        )

    def _tuner_depth_observe(
        self, topo, arm: str, seconds: float, kp: int = 1
    ) -> None:
        """Feed a measured delta-path / full-rebuild wall into the
        persisted tuner table (the per-shape max_delta_depth input)."""
        from holo_tpu.pipeline.tuner import active_tuner

        t = active_tuner()
        if t is None:
            return
        b = self._depth_bucket(topo, kp)
        if arm == "delta":
            t.observe_delta(b, seconds)
        else:
            t.observe_full(b, seconds)

    def _sharded_whatif(self, mesh, engine: str | None = None):
        if engine is None:
            engine = self.one_engine
        if mesh.size == 1:
            # Degenerate mesh: the plain program IS the sharded program
            # (mesh.constrain_batch would be a no-op) — reuse its jit
            # cache so the 1-device mesh costs nothing but the routing.
            return self._jit_batch_for(engine)
        from holo_tpu.parallel.mesh import mesh_cache_key, sharded_whatif_jit

        key = ("whatif", engine, mesh_cache_key(mesh))
        fn = self._shard_jits.get(key)
        if fn is None:
            fn = sharded_whatif_jit(mesh, self.max_iters, engine)
            self._shard_jits[key] = fn
        return fn

    def _sharded_trop_whatif(self, mesh):
        if mesh.size == 1:  # see _sharded_whatif
            return self._jit_trop_batch
        from holo_tpu.parallel.mesh import (
            mesh_cache_key,
            sharded_tropical_whatif_jit,
        )

        key = ("whatif-tropical", mesh_cache_key(mesh))
        fn = self._shard_jits.get(key)
        if fn is None:
            fn = sharded_tropical_whatif_jit(mesh, self.max_iters)
            self._shard_jits[key] = fn
        return fn

    def _sharded_trop_multiroot(self, mesh):
        if mesh.size == 1:
            return self._jit_trop_multiroot
        from holo_tpu.parallel.mesh import (
            mesh_cache_key,
            sharded_tropical_multiroot_jit,
        )

        key = ("multiroot-tropical", mesh_cache_key(mesh))
        fn = self._shard_jits.get(key)
        if fn is None:
            fn = sharded_tropical_multiroot_jit(mesh, self.max_iters)
            self._shard_jits[key] = fn
        return fn

    def _sharded_mp_whatif(self, mesh, kp: int):
        if mesh.size == 1:  # see _sharded_whatif
            return self._jit_mp_batch_for(kp)
        from holo_tpu.parallel.mesh import (
            mesh_cache_key,
            sharded_multipath_jit,
        )

        key = ("mp-whatif", kp, mesh_cache_key(mesh))
        fn = self._shard_jits.get(key)
        if fn is None:
            fn = sharded_multipath_jit(mesh, kp, self.max_iters)
            self._shard_jits[key] = fn
        return fn

    def _sharded_multiroot(self, mesh):
        if mesh.size == 1:  # see _sharded_whatif
            return self._jit_multiroot
        from holo_tpu.parallel.mesh import constrain_batch, mesh_cache_key

        key = ("multiroot", mesh_cache_key(mesh))
        fn = self._shard_jits.get(key)
        if fn is None:

            @jax.jit
            def step(g, rs, m):
                out = spf_multiroot(g, rs, m, self.max_iters)
                return constrain_batch(mesh, out)

            fn = self._shard_jits[key] = step
        return fn

    def prepare(
        self,
        topo: Topology,
        need_edge_ids: bool = False,
        allow_delta: bool | None = None,
    ) -> DeviceGraph:
        # The process-wide shared cache (keyed by the topology's
        # (process-unique uid, generation) identity — in-place mutators
        # must topo.touch()): an instance running SPF + FRR marshals its
        # DeviceGraph once, not once per engine.  The per-engine counter
        # keeps the historical series alive alongside the shared
        # holo_spf_marshal_cache_total triple; a 'delta' result means
        # the resident graph was updated in place instead of rebuilt.
        if allow_delta is None:
            allow_delta = self.incremental
        g, how = shared_graph_cache().get(
            topo,
            max(self.n_atoms, topo.n_atoms()),
            need_edge_ids=need_edge_ids,
            allow_delta=allow_delta,
        )
        _GRAPH_CACHE.labels(result=how).inc()
        self._last_prepare_how = how
        return g

    def _remember(self, topo: Topology, n_atoms: int, out, kp: int = 1) -> None:
        """Retain this run's device tensors as the next delta's seed.

        Idempotent per key: a repeated dispatch of the same (topology
        generation, root) produces bit-identical tensors, so the
        already-stored set stays — the no-delta steady state then holds
        one buffer set instead of churning a fresh one per dispatch
        (tests/test_delta_spf.py::
        test_topology_without_lineage_never_enters_the_delta_path).

        ``kp`` joins the key: a multipath chain seeds from multipath
        tensors ((SpfTensors, MultipathTensors) pairs) and a k=1 chain
        from plain SpfTensors — a ``max-paths`` reconfigure mid-chain
        degrades that root's next delta to ``full-no-prev``, never to a
        wrong-width donation."""
        key = (
            *topo.cache_key, int(n_atoms), int(topo.root), _mesh_key(),
            int(kp),
        )
        if key in self._prev_one:
            return
        # The legitimate re-deposit seam of the donation handoff: the
        # FRESH output tensors take the consumed previous set's place.
        # consumes_donated is the shared HL109 vocabulary — the static
        # rule exempts this window, the runtime guard counts it.
        with consumes_donated("spf.prev.redeposit"):
            self._prev_one[key] = out
            while len(self._prev_one) > self.prev_capacity:
                self._prev_one.pop(next(iter(self._prev_one)))

    def _track_compile(self, kind: str, engine: str, *shape) -> bool:
        """Returns True when this (engine, shape) bucket is fresh — a
        real XLA compile, and the moment to capture its cost analysis.
        ``engine`` is the fixpoint formulation actually dispatched (the
        tuner may differ from the pinned one_engine per shape bucket).
        Callers append the process-mesh identity to ``shape``: the same
        shapes under a different sharding are a different XLA program,
        and the cost-analysis table keys on the same signature."""
        sig = (kind, engine, *shape)
        if sig in self._compiled_shapes:
            _JIT_HITS.labels(kind=kind).inc()
            return False
        self._compiled_shapes.add(sig)
        _JIT_COMPILES.labels(kind=kind).inc()
        return True

    def _full_mask(self, topo: Topology, edge_mask) -> np.ndarray:
        if edge_mask is None:
            return np.ones(topo.n_edges, bool)
        return np.asarray(edge_mask, bool)

    @staticmethod
    def _one_mask(edge_mask) -> np.ndarray:
        """The mask operand of one full single-SPF dispatch.  A real
        scenario mask is ``bool[E]`` and keys its program on E.  The
        mask-free call (the protocol instance's) passes the empty mask,
        which the engines read as "every valid slot" with no gather
        (``_slot_mask``): its program is keyed on the resident's shapes
        alone, so a full re-marshal under churn — E moves with every
        flap — reuses the program the first SPF compiled (ROADMAP S2)."""
        if edge_mask is None:
            return _NO_MASK
        return np.asarray(edge_mask, bool)

    # Public entry points run under the circuit breaker: an XLA failure
    # or deadline overrun transparently re-runs the batch on the scalar
    # oracle (RIB output unchanged by construction — the parity suites
    # pin the two backends bit-identical), and repeated failures open
    # the circuit so a dead device stops being retried per-SPF.

    @staticmethod
    def _noted_fallback(fn):
        """Run the scalar fallback and tag the active convergence
        events with ``fallback`` (AFTER the oracle's own ``scalar``
        note, so the sticky fallback verdict is what the event closes
        with — storm distributions split on it)."""
        try:
            return fn()
        finally:
            convergence.note_dispatch("spf", "fallback")

    def compute(self, topo, edge_mask=None, multipath_k: int = 1):
        kp = mp_pad(multipath_k)
        if self._use_partitioned(topo):
            return self.compute_partitioned(
                topo, edge_mask, multipath_k=kp
            )
        return self.breaker.call(
            lambda: self._device_compute(topo, edge_mask, kp),
            lambda: self._noted_fallback(
                lambda: self._oracle.compute(
                    topo, edge_mask, multipath_k=kp
                )
            ),
            context="spf.one",
        )

    def compute_whatif(self, topo, edge_masks, multipath_k: int = 1):
        kp = mp_pad(multipath_k)
        if self._use_partitioned(topo):
            return self.breaker.call(
                lambda: [
                    self._device_partitioned(topo, m, kp)
                    for m in edge_masks
                ],
                lambda: self._noted_fallback(
                    lambda: self._oracle.compute_whatif(
                        topo, edge_masks, multipath_k=kp
                    )
                ),
                context="spf.whatif",
            )
        return self.breaker.call(
            lambda: self._device_whatif(topo, edge_masks, kp),
            lambda: self._noted_fallback(
                lambda: self._oracle.compute_whatif(
                    topo, edge_masks, multipath_k=kp
                )
            ),
            context="spf.whatif",
        )

    # -- partitioned dispatch (ISSUE 15) --------------------------------

    def _use_partitioned(self, topo) -> bool:
        return (
            self.partition_threshold is not None
            and topo.n_vertices >= self.partition_threshold
            and self.engine != "blocked"
        )

    def compute_partitioned(self, topo, edge_mask=None, multipath_k: int = 1):
        """Explicit partitioned dispatch (auto-routed from ``compute``
        when ``partition_threshold`` arms it) — breaker-guarded with
        the bit-identical scalar oracle as the fallback arm, exactly
        like the monolithic paths."""
        kp = mp_pad(multipath_k)
        return self.breaker.call(
            lambda: self._device_partitioned(topo, edge_mask, kp),
            lambda: self._noted_fallback(
                lambda: self._oracle.compute(
                    topo, edge_mask, multipath_k=kp
                )
            ),
            context="spf.partitioned",
        )

    def _part_engine_for(self):
        if self._part_engine is None:
            from holo_tpu.ops.partition import PartitionedSpfEngine

            self._part_engine = PartitionedSpfEngine(
                max_iters=self.max_iters
            )
        return self._part_engine

    def _part_key(self, topo, n_atoms: int) -> tuple:
        return (self._part_ns, int(topo.root), int(n_atoms), _mesh_key())

    def partition_residents(self) -> list:
        """This backend's live partitioned residents (tests)."""
        from holo_tpu.ops.spf_engine import shared_graph_cache

        return list(
            shared_graph_cache()
            .partitioned_entries(self._part_ns)
            .values()
        )

    def _part_resident_for(self, topo, n_atoms: int, need_edge_ids: bool):
        """The partitioned resident serving this topology's chain,
        re-marshaled when the chain broke (or never existed).  Returns
        ``(resident, how)`` with how in {'hit', 'miss'} — the delta
        path claims the resident separately."""
        from holo_tpu.ops.spf_engine import shared_graph_cache

        eng = self._part_engine_for()
        key = self._part_key(topo, n_atoms)
        cache = shared_graph_cache()
        res = cache.get_partitioned(key)
        if (
            res is not None
            and res.topo_key == topo.cache_key
            and not (need_edge_ids and res.ids_stale)
        ):
            return res, "hit"
        res = eng.marshal(
            topo,
            n_atoms,
            n_parts=self.partition_parts,
            max_part=(
                None
                if self.partition_parts is not None
                else self.partition_max_part
            ),
        )
        cache.put_partitioned(key, res)
        return res, "miss"

    def _device_partitioned(self, topo, edge_mask, kp: int = 1):
        faults.crashpoint("spf.dispatch")
        mesh = _mesh()
        if mesh is not None:
            faults.crashpoint("spf.shard")
        from holo_tpu.ops.spf_engine import shared_graph_cache

        eng = self._part_engine_for()
        n_atoms = max(self.n_atoms, topo.n_atoms())
        t0 = profiling.clock()
        obucket = self._obs_bucket(topo, 1, kp, None)
        key = self._part_key(topo, n_atoms)
        result = None
        how = None
        delta = getattr(topo, "delta_base", None)
        with profiling.dispatch_context(
            kind="partitioned", engine="partitioned", bucket=obucket
        ), telemetry.span(
            "spf.dispatch", kind="partitioned", backend="tpu"
        ):
            res = shared_graph_cache().get_partitioned(key)
            if (
                edge_mask is None
                and delta is not None
                and self.incremental
                and res is not None
            ):
                # Bounded re-solve: affected partitions + skeleton.
                with profiling.stage("spf.partitioned", "delta"):
                    served = eng.try_delta(topo, res, kp)
                if served is not None:
                    result, _info = served
                    note_delta(delta.kind, "partitioned-incremental")
            if result is None:
                with profiling.stage("spf.partitioned", "marshal"):
                    with sanctioned_transfer("spf.partition.marshal"):
                        res, how = self._part_resident_for(
                            topo, n_atoms, edge_mask is not None
                        )
                with profiling.stage("spf.partitioned", "solve"):
                    result = eng.solve(topo, res, edge_mask, kp)
                if delta is not None and edge_mask is None:
                    note_delta(delta.kind, "partitioned-full")
            mpkw = {
                f: result[f]
                for f in (
                    "parents", "pdist", "pweight", "npaths", "nh_weights"
                )
                if f in result
            }
            out = SpfResult(
                dist=result["dist"],
                parent=result["parent"],
                hops=result["hops"],
                nexthop_words=result["nexthop_words"],
                **mpkw,
            )
        t1 = profiling.clock()
        _DISPATCH_SECONDS.labels(backend="tpu", kind="partitioned").observe(
            t1 - t0
        )
        kind = "one" if edge_mask is None else "whatif"
        if edge_mask is None and how == "hit":
            # Feed the tuner's partitioned rows (same shape key as the
            # kind=one monolithic walls, so partitioned_advantage
            # compares like with like) — FULL solves on a WARM resident
            # only: a per-mask what-if wall, a bounded delta re-solve,
            # or a marshal-miss dispatch (one-off re-marshal + XLA
            # compile wall) is not comparable to the kind=one
            # steady-state median, which excludes the same costs.
            from holo_tpu.pipeline.tuner import active_tuner

            tun = active_tuner()
            if tun is not None:
                tun.observe_partitioned(
                    self._depth_bucket(topo, kp), t1 - t0
                )
        _BATCH_SCENARIOS.labels(kind=kind).inc()
        if mesh is not None:
            _SHARD_DISPATCHES.labels(kind=kind).inc()
        convergence.note_dispatch("spf", "device")
        return out

    def partition_stats(self) -> dict:
        """Resident summaries for the telemetry leaf."""
        from holo_tpu.ops.spf_engine import shared_graph_cache

        return {
            str(k[1:]): r.stats()
            for k, r in shared_graph_cache()
            .partitioned_entries(self._part_ns)
            .items()
        }

    def compute_multiroot(self, topo, roots: np.ndarray) -> "MultiRootResult":
        return self.breaker.call(
            lambda: self._device_multiroot(topo, roots),
            lambda: self._noted_fallback(
                lambda: self._oracle.compute_multiroot(topo, roots)
            ),
            context="spf.multiroot",
        )

    def _device_compute(self, topo, edge_mask=None, kp: int = 1):
        faults.crashpoint("spf.dispatch")
        mesh = _mesh()
        if mesh is not None:
            # The shard-dispatch chaos seam: a device lost from the
            # mesh / an XLA failure on any shard surfaces here and the
            # breaker serves the WHOLE batch from the scalar oracle.
            faults.crashpoint("spf.shard")
        if self.engine == "blocked" and kp == 1:
            # The blocked-Pallas experiment has no multipath planes;
            # kp > 1 rides the gather-path multipath kernel below.
            return self._whatif_blocked(
                topo, self._full_mask(topo, edge_mask)[None, :]
            )[0]
        if edge_mask is None:
            res = self._try_incremental(topo, kp)
            if res is not None:
                return res
        t0 = profiling.clock()
        engine, bucket = self._pick_engine("one", topo, kp=kp)
        obucket = self._obs_bucket(topo, 1, kp, bucket)
        with profiling.dispatch_context(
            kind="one", engine=engine, bucket=obucket
        ), telemetry.span("spf.dispatch", kind="one", backend="tpu"):
            # THE sanctioned marshal boundary: host graph + root + mask
            # move to device here and nowhere else (transfer_guard
            # "disallow" everywhere outside these windows).
            with profiling.stage("spf.one", "marshal"):
                with sanctioned_transfer("spf.one.marshal"):
                    # A REAL scenario mask gathers through in_edge_id:
                    # structurally delta-updated residents must rebuild
                    # for it (the mask-free call keeps riding them).
                    g = self.prepare(
                        topo, need_edge_ids=edge_mask is not None
                    )
                    remarshal = self._last_prepare_how == "miss"
                    mask = self._one_mask(edge_mask)
                    tt = rr = None
                    if engine in _TROPICAL_ENGINES:
                        tt, rr = self._trop_operands(topo, g, edge_mask)
                    step, args = self._one_step(
                        engine, kp, g, tt, topo.root, mask, rr
                    )
                    sig = (
                        g.in_src.shape, g.direct_nh_words.shape[2],
                        mask.shape[0], _mesh_key(), engine, kp,
                        None if tt is None else tt.tiles.shape,
                        None if rr is None else rr.shape,
                    )
                    fresh = self._track_compile("one", engine, *sig)
                    out = step(*args)
            if fresh:
                entry = profiling.record_cost(
                    "spf.one", step, *args, shape_sig=sig,
                )
                self._tuner_cost("one", bucket, engine, entry)
                self._obs_cost("spf.one", "one", engine, obucket, entry)
            with profiling.stage("spf.one", "device"):
                faults.delaypoint("spf.dispatch")
                if not profiling.device_stages("spf.one", out):
                    profiling.sync(out)
            t1 = profiling.clock()
            with profiling.stage("spf.one", "readback"):
                with sanctioned_transfer("spf.one.unmarshal"):
                    sp = out[0] if kp > 1 else out
                    dist, parent, hops, nh = _host_tensors(
                        sp, topo.n_vertices
                    )
                    mpkw = _host_mp(out[1], topo.n_vertices) if kp > 1 else {}
                    res = SpfResult(
                        dist=dist, parent=parent, hops=hops,
                        nexthop_words=nh, **mpkw,
                    )
        t2 = profiling.clock()
        _TRANSFER_SECONDS.labels(kind="one").observe(t2 - t1)
        _DISPATCH_SECONDS.labels(backend="tpu", kind="one").observe(t2 - t0)
        _BATCH_SCENARIOS.labels(kind="one").inc()
        if mesh is not None:
            _SHARD_DISPATCHES.labels(kind="one").inc()
        convergence.note_dispatch("spf", "device")
        if not fresh:
            # Fresh-compile dispatches carry one-off XLA compile wall:
            # feeding them to the tuner would let compile spikes outvote
            # the steady-state cost the decision is about.
            self._tuner_observe("one", bucket, engine, t2 - t0)
        if remarshal and edge_mask is None:
            # A full re-marshal paid: the depth tuner's "full" arm (the
            # cost a deeper delta chain would have avoided).
            self._tuner_depth_observe(topo, "full", t2 - t0, kp)
        if edge_mask is None and self.incremental:
            # Disarmed backends skip retention: they could never
            # consume the tensors.
            self._remember(
                topo, max(self.n_atoms, topo.n_atoms()), out, kp
            )
        return res

    def _try_incremental(self, topo, kp: int = 1) -> SpfResult | None:
        """DeltaPath dispatch: the resident device graph absorbs the
        topology delta in place and the incremental kernel recomputes
        seeded from the previous run's tensors — O(affected) rounds and
        a delta-sized transfer instead of a full marshal.  Returns None
        (→ full-rebuild path) when the chain cannot be served; every
        disposition lands in ``holo_spf_delta_total{kind,path}``.
        ``kp > 1`` rides the widened incremental kernel, seeded from
        (and donating) the chain's retained multipath tensors."""
        delta = getattr(topo, "delta_base", None)
        if delta is None or not self.incremental:
            return None
        n_atoms = max(self.n_atoms, topo.n_atoms())
        prev_key = (
            *delta.base_key, int(n_atoms), int(topo.root), _mesh_key(),
            int(kp),
        )
        prev = self._prev_one.get(prev_key)
        if prev is None:
            note_delta(delta.kind, "full-no-prev")
            return None
        t0 = profiling.clock()
        obucket = self._obs_bucket(topo, 1, kp, None)
        with profiling.dispatch_context(
            kind="delta", engine="incr", bucket=obucket
        ), telemetry.span(
            "spf.dispatch", kind="one", backend="tpu", mode="delta"
        ):
            with profiling.stage("spf.one", "delta"):
                # The delta-sized sanctioned boundary: scatter/seed
                # rows move host->device here — the full-graph marshal
                # transfer is exactly what this path avoids.  The
                # apply (host lowering + donated scatter) runs INSIDE
                # the dispatch timer and the delta stage so the
                # full-vs-incremental _DISPATCH_SECONDS comparison
                # carries symmetric costs (the full path's timer
                # includes its marshal).
                with sanctioned_transfer("spf.one.delta"):
                    from holo_tpu.ops.spf_engine import _pad_pow2

                    g, how = shared_graph_cache().get(
                        topo, n_atoms, allow_delta=True
                    )
                    if how == "miss":
                        # The cache refused the delta (depth/overflow/
                        # missing base — reasons already counted in
                        # holo_spf_delta_total) and paid a full
                        # re-marshal: this dispatch belongs to the
                        # full-rebuild path, which now hits the fresh
                        # entry; its prepare() alone counts the
                        # per-dispatch _GRAPH_CACHE disposition.  (The
                        # rare aborted mode=delta span records the
                        # attempt; path="incremental" must mean the
                        # resident actually served it.)
                        return None
                    _GRAPH_CACHE.labels(result=how).inc()
                    seeds = delta.seed_rows()
                    pad = _pad_pow2(seeds.shape[0])
                    # Pad sentinel = the resident's PADDED row count
                    # (node-sharded residents pad rows past N): truly
                    # out of range for the aff-scatter's mode="drop".
                    seeds_p = np.full(
                        pad, int(g.in_src.shape[0]), np.int32
                    )
                    seeds_p[: seeds.shape[0]] = seeds
                    step, out, trop, tt, sig, fresh = self._incr_step(
                        topo, g, n_atoms, kp, pad, prev_key, prev,
                        seeds_p,
                    )
            if fresh:
                cost_args = self._incr_cost_args(
                    trop, tt, g, topo.root, out, seeds_p, kp
                )
                entry = profiling.record_cost(
                    "spf.delta", step, *cost_args, shape_sig=sig,
                )
                self._obs_cost("spf.one", "delta", "incr", obucket, entry)
            with profiling.stage("spf.one", "device"):
                faults.delaypoint("spf.dispatch")
                # Donation-guard force boundary: a leaked donated alias
                # in the output set fails HERE, named, not as a generic
                # deleted-array error inside the readback.
                assert_live("spf.one.readback", out)
                if not profiling.device_stages("spf.one", out):
                    profiling.sync(out)
            t1 = profiling.clock()
            with profiling.stage("spf.one", "readback"):
                with sanctioned_transfer("spf.one.unmarshal"):
                    sp = out[0] if kp > 1 else out
                    dist, parent, hops, nh = _host_tensors(
                        sp, topo.n_vertices
                    )
                    mpkw = _host_mp(out[1], topo.n_vertices) if kp > 1 else {}
                    res = SpfResult(
                        dist=dist, parent=parent, hops=hops,
                        nexthop_words=nh, **mpkw,
                    )
        t2 = profiling.clock()
        _TRANSFER_SECONDS.labels(kind="one").observe(t2 - t1)
        _DISPATCH_SECONDS.labels(backend="tpu", kind="one").observe(t2 - t0)
        _BATCH_SCENARIOS.labels(kind="one").inc()
        if _mesh() is not None:
            _SHARD_DISPATCHES.labels(kind="one").inc()
        convergence.note_dispatch("spf", "device")
        note_delta(delta.kind, "incremental")
        # The depth tuner's "delta" arm: what an in-place update +
        # seeded recompute actually costs at this shape.
        self._tuner_depth_observe(topo, "delta", t2 - t0, kp)
        self._remember(topo, n_atoms, out, kp)
        return res

    def prepare_blocked(self, topo: Topology):
        """Marshal (and cache) the blocked planes; ``ValueError`` when
        the topology is outside the kernels' preconditions.

        The cache key includes the root: unlike the gather planes, the
        blocked planes bake the root in (BFS permutation + rootp).
        """
        key = (*topo.cache_key, topo.root)
        if key in self._blocked_cache:
            return self._blocked_cache[key]
        from holo_tpu.ops.blocked_spf import marshal_block_spf

        g = marshal_block_spf(topo, n_atoms=max(self.n_atoms, topo.n_atoms()))
        self._blocked_cache[key] = g
        while len(self._blocked_cache) > 4:
            self._blocked_cache.pop(next(iter(self._blocked_cache)))
        return g

    def _whatif_blocked(self, topo, edge_masks):
        from holo_tpu.ops.blocked_spf import failed_edges_perm, whatif_spf_blocked

        with sanctioned_transfer("spf.blocked.marshal"):
            g = self.prepare_blocked(topo)
            fdst, fid = failed_edges_perm(
                np.asarray(g.orig2perm), topo,
                np.asarray(edge_masks, bool),
            )
        if self._jit_blocked is None:
            from functools import partial

            self._jit_blocked = jax.jit(
                partial(whatif_spf_blocked, max_iters=self.max_iters)
            )
        t0 = profiling.clock()
        bl_bucket = self._obs_bucket(topo, len(edge_masks), 1, None)
        with profiling.dispatch_context(
            kind="blocked", engine="blocked", bucket=bl_bucket
        ), telemetry.span(
            "spf.dispatch", kind="blocked", backend="tpu",
            batch=len(edge_masks),
        ):
            with profiling.stage("spf.blocked", "marshal"):
                fresh = self._track_compile(
                    "blocked", "blocked", fdst.shape, fid.shape
                )
                with sanctioned_transfer("spf.blocked.dispatch"):
                    out = self._jit_blocked(g, fdst, fid)
            if fresh:
                entry = profiling.record_cost(
                    "spf.blocked", self._jit_blocked, g, fdst, fid,
                    shape_sig=(fdst.shape, fid.shape),
                )
                self._obs_cost(
                    "spf.blocked", "blocked", "blocked", bl_bucket, entry
                )
            with profiling.stage("spf.blocked", "device"):
                profiling.sync(out)
            t1 = profiling.clock()
            with profiling.stage("spf.blocked", "readback"):
                with sanctioned_transfer("spf.blocked.unmarshal"):
                    dist, parent, hops, nh = (
                        np.asarray(out.dist),
                        np.asarray(out.parent),
                        np.asarray(out.hops),
                        np.asarray(out.nexthops),
                    )
        t2 = profiling.clock()
        _TRANSFER_SECONDS.labels(kind="blocked").observe(t2 - t1)
        _DISPATCH_SECONDS.labels(backend="tpu", kind="blocked").observe(t2 - t0)
        _BATCH_SCENARIOS.labels(kind="blocked").inc(dist.shape[0])
        return [
            SpfResult(dist=dist[i], parent=parent[i], hops=hops[i], nexthop_words=nh[i])
            for i in range(dist.shape[0])
        ]

    def _device_whatif(self, topo, edge_masks, kp: int = 1):
        faults.crashpoint("spf.dispatch")
        mesh = _mesh()
        if mesh is not None:
            faults.crashpoint("spf.shard")
        if self.engine == "blocked" and kp == 1:
            # The blocked-Pallas experiment marshals its own planes and
            # stays single-device; the mesh path rides the gather
            # engines (the headline since r02).
            return self._whatif_blocked(topo, edge_masks)
        B = len(edge_masks)
        t0 = profiling.clock()
        engine, bucket = self._pick_engine("whatif", topo, B, kp=kp)
        obucket = self._obs_bucket(topo, B, kp, bucket)
        with profiling.dispatch_context(
            kind="whatif", engine=engine, bucket=obucket
        ), telemetry.span(
            "spf.dispatch", kind="whatif", backend="tpu", batch=B,
        ):
            with profiling.stage("spf.whatif", "marshal"):
                with sanctioned_transfer("spf.whatif.marshal"):
                    # What-if masks gather through in_edge_id: entries
                    # whose ids went stale under a structural delta are
                    # rebuilt (need_edge_ids).
                    g = self.prepare(topo, need_edge_ids=True)
                    masks = np.asarray(edge_masks, bool)
                    tt = rr = None
                    if engine == "tropical":
                        tt = shared_graph_cache().get_tropical(
                            topo, max(self.n_atoms, topo.n_atoms())
                        )
                        rr = repair_rows_host(
                            topo.edge_dst, masks, int(g.in_src.shape[0])
                        )
                    if mesh is not None:
                        # THE sharded scenario axis: masks placed
                        # batch-sharded (padded to the axis size with
                        # no-failure rows), outputs pinned to the batch
                        # sharding — GSPMD fans the B scenarios out
                        # over the mesh's batch devices while the
                        # cache-resident graph planes ride row-sharded
                        # over node (the mesh.py layout contract).
                        from holo_tpu.parallel.mesh import (
                            shard_repair_rows,
                            shard_scenarios,
                        )

                        masks_dev = shard_scenarios(mesh, masks)
                        if engine == "tropical":
                            rr = shard_repair_rows(
                                mesh, rr, int(g.in_src.shape[0])
                            )
                            step = self._sharded_trop_whatif(mesh)
                        elif kp > 1:
                            step = self._sharded_mp_whatif(mesh, kp)
                        else:
                            step = self._sharded_whatif(mesh, engine)
                    else:
                        masks_dev = masks
                        if engine == "tropical":
                            step = self._jit_trop_batch
                        elif kp > 1:
                            step = self._jit_mp_batch_for(kp)
                        else:
                            step = self._jit_batch_for(engine)
                    args = (
                        (g, tt, topo.root, masks_dev, rr)
                        if engine == "tropical"
                        else (g, topo.root, masks_dev)
                    )
                    sig = (
                        g.in_src.shape, g.direct_nh_words.shape[2],
                        masks_dev.shape, _mesh_key(), engine, kp,
                        None if tt is None else tt.tiles.shape,
                        None if rr is None else rr.shape,
                    )
                    fresh = self._track_compile("whatif", engine, *sig)
                    out = step(*args)
            if fresh:
                entry = profiling.record_cost(
                    "spf.whatif", step, *args, shape_sig=sig,
                )
                self._tuner_cost("whatif", bucket, engine, entry)
                self._obs_cost(
                    "spf.whatif", "whatif", engine, obucket, entry
                )
            with profiling.stage("spf.whatif", "device"):
                faults.delaypoint("spf.dispatch")
                if not profiling.device_stages("spf.whatif", out):
                    profiling.sync(out)
            t1 = profiling.clock()
            # One bulk device→host transfer per plane: per-scenario slicing
            # of device arrays would pay the host round-trip B×4 times.
            with profiling.stage("spf.whatif", "readback"):
                with sanctioned_transfer("spf.whatif.unmarshal"):
                    sp = out[0] if kp > 1 else out
                    dist, parent, hops, nh = _host_tensors(
                        sp, topo.n_vertices
                    )
                    mpkw = _host_mp(out[1], topo.n_vertices) if kp > 1 else {}
        t2 = profiling.clock()
        _TRANSFER_SECONDS.labels(kind="whatif").observe(t2 - t1)
        _DISPATCH_SECONDS.labels(backend="tpu", kind="whatif").observe(t2 - t0)
        _BATCH_SCENARIOS.labels(kind="whatif").inc(B)
        if mesh is not None:
            _SHARD_DISPATCHES.labels(kind="whatif").inc()
        convergence.note_dispatch("spf", "device")
        if not fresh:  # see _device_compute: no compile-spike samples
            self._tuner_observe("whatif", bucket, engine, t2 - t0)
        # Slice off the batch-pad rows (sharded dispatch pads B up to a
        # multiple of the mesh batch axis) — [:B] is a no-op otherwise.
        return [
            SpfResult(
                dist=dist[i], parent=parent[i], hops=hops[i],
                nexthop_words=nh[i],
                **{f: plane[i] for f, plane in mpkw.items()},
            )
            for i in range(B)
        ]

    def _device_multiroot(self, topo, roots: np.ndarray) -> "MultiRootResult":
        """Distances/parents/hops from many roots (one device program).

        Next-hop bitmasks are intentionally NOT returned: direct atoms are
        marshaled relative to ``topo.root``, so they are meaningless for any
        other root.  Multi-root users (IS-IS flooding reduction, TI-LFA)
        need the SPT shape only.
        """
        faults.crashpoint("spf.dispatch")
        mesh = _mesh()
        if mesh is not None:
            faults.crashpoint("spf.shard")
        R = len(roots)
        t0 = profiling.clock()
        # The multiroot program has no tuner kind of its own: it rides
        # the tropical tiles when the backend is pinned tropical (the
        # root axis becomes the contraction's dense lanes), else the
        # proven seq formulation.
        mr_engine = "tropical" if self.one_engine == "tropical" else "seq"
        mr_bucket = self._obs_bucket(topo, R, 1, None)
        with profiling.dispatch_context(
            kind="multiroot", engine=mr_engine, bucket=mr_bucket
        ), telemetry.span(
            "spf.dispatch", kind="multiroot", backend="tpu", roots=R
        ):
            with profiling.stage("spf.multiroot", "marshal"):
                with sanctioned_transfer("spf.multiroot.marshal"):
                    g = self.prepare(topo)
                    tt = None
                    rr = np.zeros(0, np.int32)
                    if mr_engine == "tropical":
                        tt, rr = self._trop_operands(topo, g)
                    roots_i32 = np.asarray(roots, np.int32)
                    if mesh is not None:
                        # The all-roots plane rides the same batch
                        # axis: roots sharded over it (padded with
                        # root 0; pad rows sliced off below).
                        from holo_tpu.parallel.mesh import shard_roots

                        roots_dev = shard_roots(mesh, roots_i32)
                        step = (
                            self._sharded_trop_multiroot(mesh)
                            if mr_engine == "tropical"
                            else self._sharded_multiroot(mesh)
                        )
                    else:
                        roots_dev = roots_i32
                        step = (
                            self._jit_trop_multiroot
                            if mr_engine == "tropical"
                            else self._jit_multiroot
                        )
                    sig = (
                        g.in_src.shape, g.direct_nh_words.shape[2],
                        roots_dev.shape[0], topo.n_edges, _mesh_key(),
                        None if tt is None else tt.tiles.shape,
                    )
                    fresh = self._track_compile(
                        "multiroot", mr_engine, *sig
                    )
                    mask = np.ones(topo.n_edges, bool)
                    args = (
                        (g, tt, roots_dev, mask, rr)
                        if mr_engine == "tropical"
                        else (g, roots_dev, mask)
                    )
                    out = step(*args)
            if fresh:
                entry = profiling.record_cost(
                    "spf.multiroot", step, *args, shape_sig=sig,
                )
                self._obs_cost(
                    "spf.multiroot", "multiroot", mr_engine, mr_bucket,
                    entry,
                )
            with profiling.stage("spf.multiroot", "device"):
                if not profiling.device_stages("spf.multiroot", out):
                    profiling.sync(out)
            t1 = profiling.clock()
            with profiling.stage("spf.multiroot", "readback"):
                with sanctioned_transfer("spf.multiroot.unmarshal"):
                    dist, parent, hops, _nh = _host_tensors(
                        out, topo.n_vertices
                    )
                    res = MultiRootResult(
                        dist=dist[:R], parent=parent[:R], hops=hops[:R]
                    )
        t2 = profiling.clock()
        _TRANSFER_SECONDS.labels(kind="multiroot").observe(t2 - t1)
        _DISPATCH_SECONDS.labels(backend="tpu", kind="multiroot").observe(t2 - t0)
        _BATCH_SCENARIOS.labels(kind="multiroot").inc(R)
        if mesh is not None:
            _SHARD_DISPATCHES.labels(kind="multiroot").inc()
        convergence.note_dispatch("spf", "device")
        return res

    # -- split-phase dispatch (the pipeline seam, ISSUE 9) --------------
    #
    # launch_one() performs everything host-side-blocking (chaos seams,
    # marshal or DeltaPath in-place update + donation, the ASYNC jit
    # call) and returns an in-flight handle; finish_one() pays the
    # device completion + readback and the accounting.  Between the
    # two, the device executes while the pipeline worker launches the
    # next entry — the overlap the double buffer exists for.  The
    # phases emit separate `spf.launch` / `spf.finish` spans instead of
    # one enclosing `spf.dispatch` span: the worker interleaves other
    # items' phases on its one thread, and a straddling span would
    # cross the tracer's thread-local nesting.  Results are bit-
    # identical to _device_compute by construction (same jits, same
    # readback; parity-gated in tests/test_pipeline.py).

    def launch_one(self, topo, edge_mask=None, multipath_k: int = 1) -> "_InFlightOne":
        faults.crashpoint("spf.dispatch")
        mesh = _mesh()
        if mesh is not None:
            faults.crashpoint("spf.shard")
        kp = mp_pad(multipath_k)
        n_atoms = max(self.n_atoms, topo.n_atoms())
        if edge_mask is None:
            h = self._launch_incremental(topo, n_atoms, kp)
            if h is not None:
                return h
        t0 = profiling.clock()
        engine, bucket = self._pick_engine("one", topo, kp=kp)
        obucket = self._obs_bucket(topo, 1, kp, bucket)
        with profiling.dispatch_context(
            kind="one", engine=engine, bucket=obucket
        ), telemetry.span(
            "spf.launch", kind="one", backend="tpu", engine=engine
        ):
            with profiling.stage("spf.one", "marshal"):
                with sanctioned_transfer("spf.one.marshal"):
                    g = self.prepare(
                        topo, need_edge_ids=edge_mask is not None
                    )
                    remarshal = self._last_prepare_how == "miss"
                    mask = self._one_mask(edge_mask)
                    tt = rr = None
                    if engine in _TROPICAL_ENGINES:
                        tt, rr = self._trop_operands(topo, g, edge_mask)
                    step, args = self._one_step(
                        engine, kp, g, tt, topo.root, mask, rr
                    )
                    sig = (
                        g.in_src.shape, g.direct_nh_words.shape[2],
                        mask.shape[0], _mesh_key(), engine, kp,
                        None if tt is None else tt.tiles.shape,
                        None if rr is None else rr.shape,
                    )
                    fresh = self._track_compile("one", engine, *sig)
                    out = step(*args)
            if fresh:
                entry = profiling.record_cost(
                    "spf.one", step, *args, shape_sig=sig,
                )
                self._tuner_cost("one", bucket, engine, entry)
                self._obs_cost("spf.one", "one", engine, obucket, entry)
        return _InFlightOne(
            out=out, topo=topo, t0=t0, engine=engine, bucket=bucket,
            mode="full", n_atoms=n_atoms, kp=kp,
            remember=edge_mask is None and self.incremental,
            sharded=mesh is not None,
            remarshal=remarshal and edge_mask is None,
            fresh=fresh, obucket=obucket,
            launch_s=profiling.clock() - t0,
        )

    def _launch_incremental(
        self, topo, n_atoms, kp: int = 1
    ) -> "_InFlightOne | None":
        """Split-phase DeltaPath launch: same contract (and the same
        donation discipline — the previous tensors leave ``_prev_one``
        BEFORE the kernel call) as :meth:`_try_incremental`; the
        pipeline's per-key ownership handoff guarantees no queued delta
        for this chain launches until finish_one re-deposited the new
        tensors."""
        delta = getattr(topo, "delta_base", None)
        if delta is None or not self.incremental:
            return None
        prev_key = (
            *delta.base_key, int(n_atoms), int(topo.root), _mesh_key(),
            int(kp),
        )
        prev = self._prev_one.get(prev_key)
        if prev is None:
            note_delta(delta.kind, "full-no-prev")
            return None
        t0 = profiling.clock()
        obucket = self._obs_bucket(topo, 1, kp, None)
        with profiling.dispatch_context(
            kind="delta", engine="incr", bucket=obucket
        ), telemetry.span(
            "spf.launch", kind="one", backend="tpu", mode="delta"
        ):
            with profiling.stage("spf.one", "delta"):
                with sanctioned_transfer("spf.one.delta"):
                    from holo_tpu.ops.spf_engine import _pad_pow2

                    g, how = shared_graph_cache().get(
                        topo, n_atoms, allow_delta=True
                    )
                    if how == "miss":
                        # Cache refused the delta (reasons already
                        # counted): this dispatch belongs to the full
                        # path, which hits the fresh entry.
                        return None
                    _GRAPH_CACHE.labels(result=how).inc()
                    seeds = delta.seed_rows()
                    pad = _pad_pow2(seeds.shape[0])
                    seeds_p = np.full(
                        pad, int(g.in_src.shape[0]), np.int32
                    )
                    seeds_p[: seeds.shape[0]] = seeds
                    step, out, trop, tt, sig, fresh = self._incr_step(
                        topo, g, n_atoms, kp, pad, prev_key, prev,
                        seeds_p,
                    )
            if fresh:
                cost_args = self._incr_cost_args(
                    trop, tt, g, topo.root, out, seeds_p, kp
                )
                entry = profiling.record_cost(
                    "spf.delta", step, *cost_args, shape_sig=sig,
                )
                self._obs_cost("spf.one", "delta", "incr", obucket, entry)
        return _InFlightOne(
            out=out, topo=topo, t0=t0, engine="incr", bucket=None,
            mode="delta", delta_kind=delta.kind, n_atoms=n_atoms, kp=kp,
            remember=True, sharded=_mesh() is not None, obucket=obucket,
            launch_s=profiling.clock() - t0,
        )

    def finish_one(self, h: "_InFlightOne") -> SpfResult:
        t_fs = profiling.clock()
        with profiling.dispatch_context(
            kind="delta" if h.mode == "delta" else "one",
            engine=h.engine, bucket=h.obucket,
        ), telemetry.span(
            "spf.finish", kind="one", backend="tpu", mode=h.mode
        ):
            with profiling.stage("spf.one", "device"):
                faults.delaypoint("spf.dispatch")
                # Donation-guard force boundary (see _try_incremental).
                assert_live("spf.one.readback", h.out)
                if not profiling.device_stages("spf.one", h.out):
                    profiling.sync(h.out)
            t1 = profiling.clock()
            with profiling.stage("spf.one", "readback"):
                with sanctioned_transfer("spf.one.unmarshal"):
                    sp = h.out[0] if h.kp > 1 else h.out
                    dist, parent, hops, nh = _host_tensors(
                        sp, h.topo.n_vertices
                    )
                    mpkw = (
                        _host_mp(h.out[1], h.topo.n_vertices)
                        if h.kp > 1
                        else {}
                    )
                    res = SpfResult(
                        dist=dist, parent=parent, hops=hops,
                        nexthop_words=nh, **mpkw,
                    )
        t2 = profiling.clock()
        _TRANSFER_SECONDS.labels(kind="one").observe(t2 - t1)
        _DISPATCH_SECONDS.labels(backend="tpu", kind="one").observe(
            t2 - h.t0
        )
        _BATCH_SCENARIOS.labels(kind="one").inc()
        if h.sharded:
            _SHARD_DISPATCHES.labels(kind="one").inc()
        convergence.note_dispatch("spf", "device")
        # Tuner samples exclude the parked interval between the two
        # phases (see _InFlightOne.launch_s); the dispatch histogram
        # above keeps the true end-to-end wall.
        unparked = h.launch_s + (t2 - t_fs)
        if h.mode == "delta":
            note_delta(h.delta_kind, "incremental")
            self._tuner_depth_observe(h.topo, "delta", unparked, h.kp)
        else:
            if not h.fresh:  # see _device_compute: no compile spikes
                self._tuner_observe("one", h.bucket, h.engine, unparked)
            if h.remarshal:
                self._tuner_depth_observe(h.topo, "full", unparked, h.kp)
        if h.remember and self.incremental:
            self._remember(h.topo, h.n_atoms, h.out, h.kp)
        return res


# -- jaxpr-audit registrations (HL3xx) ----------------------------------
# The per-instance jit caches above (_jit_one_for/_jit_incr/_jit_mp_*)
# are the gather-path dispatch seams; each registers an equivalent
# module-level construction (same kernel fn, same arg order, same
# donate_argnums, max_iters=None) so the audit proves the contracts the
# instance jits rely on.  Thunks run only when the audit arms.
from holo_tpu.analysis.kernels import register_kernel as _register_kernel  # noqa: E402


def _audit_specs():
    from holo_tpu.ops.spf_engine import (
        _AUDIT_B,
        _AUDIT_E,
        audit_graph_spec,
        audit_mp_spec,
        audit_spf_spec,
    )
    import jax.numpy as jnp

    s = jax.ShapeDtypeStruct
    return {
        "g": audit_graph_spec(),
        "sp": audit_spf_spec(),
        "mp": audit_mp_spec(),
        "root": s((), jnp.int32),
        "roots": s((_AUDIT_B,), jnp.int32),
        "mask": s((_AUDIT_E,), jnp.bool_),
        "masks": s((_AUDIT_B, _AUDIT_E), jnp.bool_),
        "seeds": s((256,), jnp.int32),
    }


def _register_one_engines() -> None:
    from holo_tpu.ops.spf_engine import _ONE_ENGINES

    for eng in sorted(_ONE_ENGINES):
        _register_kernel(
            f"spf.one.{eng}",
            builder=(
                # The jit lives inside an inert audit thunk: it is
                # built at most once per engine, when the HL3xx audit
                # arms — never on the dispatch path this rule guards.
                # holo-lint: disable=HL103
                lambda e=eng: jax.jit(
                    lambda g, r, m, _e=e: __import__(
                        "holo_tpu.ops.spf_engine", fromlist=["_ONE_ENGINES"]
                    )._ONE_ENGINES[_e](g, r, m, None)
                )
            ),
            specs=lambda: (
                lambda a: (a["g"], a["root"], a["mask"])
            )(_audit_specs()),
            buckets=4,  # engine picked per jit; shapes ride the resident
        )


_register_one_engines()

_register_kernel(
    "spf.whatif.batch",
    builder=lambda: jax.jit(
        lambda g, r, ms: spf_whatif_batch(g, r, ms, None, engine="seq")
    ),
    specs=lambda: (
        lambda a: (a["g"], a["root"], a["masks"])
    )(_audit_specs()),
    buckets=16,  # pow2 scenario-lane pads per shape
)

_register_kernel(
    "spf.multiroot",
    builder=lambda: jax.jit(lambda g, rs, m: spf_multiroot(g, rs, m, None)),
    specs=lambda: (
        lambda a: (a["g"], a["roots"], a["mask"])
    )(_audit_specs()),
    buckets=16,
)

_register_kernel(
    "spf.one.incremental",
    builder=lambda: jax.jit(
        lambda g, r, prev, seeds: spf_one_incremental(g, r, prev, seeds, None),
        donate_argnums=(2,),
    ),
    specs=lambda: (
        lambda a: (a["g"], a["root"], a["sp"], a["seeds"])
    )(_audit_specs()),
    donate=(2,),
    buckets=16,  # pow2 seed-row pads per shape
)

_register_kernel(
    "spf.one.multipath.k2",
    builder=lambda: jax.jit(
        lambda g, r, m: spf_one_multipath(g, r, 2, m, None)
    ),
    specs=lambda: (
        lambda a: (a["g"], a["root"], a["mask"])
    )(_audit_specs()),
    buckets=4,  # kp collapses onto {1, 2, 4, 8}
)

_register_kernel(
    "spf.multipath.batch.k2",
    builder=lambda: jax.jit(
        lambda g, r, ms: spf_multipath_batch(g, r, ms, 2, None)
    ),
    specs=lambda: (
        lambda a: (a["g"], a["root"], a["masks"])
    )(_audit_specs()),
    buckets=32,  # kp x scenario-lane buckets
)

_register_kernel(
    "spf.one.incremental.multipath.k2",
    builder=lambda: jax.jit(
        lambda g, r, prev, np_p, aw_p, seeds: spf_one_incremental_multipath(
            g, r, prev, np_p, aw_p, seeds, 2, None
        ),
        donate_argnums=(2, 3, 4),
    ),
    specs=lambda: (
        lambda a: (
            a["g"], a["root"], a["sp"],
            a["mp"].npaths, a["mp"].nh_weights, a["seeds"],
        )
    )(_audit_specs()),
    donate=(2, 3, 4),
    buckets=32,
)
