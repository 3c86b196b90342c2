"""FRR engine + backup resolution policy.

``FrrEngine`` is the dispatch point the protocol layer calls right after
its primary SPF: Topology in, :class:`BackupTable` out, through either
the batched device kernel (:func:`holo_tpu.frr.kernel.frr_batch`, cached
per shape bucket like ``TpuSpfBackend``) or the scalar oracle.  Both are
bit-identical; 'scalar' is the default for the same reason it is for
SPF — zero marshaling latency on small LSDBs.

``repair_plan`` applies the configured protection policy to every
(protected link, destination vertex) at once: direct LFA first
(cheapest — no extra encapsulation), then remote-LFA PQ tunnel, then the
TI-LFA segment repair.  ``repair_picks`` reads the plan for each
(destination, primary next-hop atom) of an SPF result — the one
selection every protocol (OSPFv2/v3, IS-IS) attaches from.  The result
is symbolic (atoms + repair vertices); the protocol layer maps atoms to
(interface, address) next hops and repair vertices to SR labels via its
own SID tables.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

import numpy as np

from holo_tpu import telemetry
from holo_tpu.analysis.runtime import sanctioned_transfer
from holo_tpu.frr.inputs import marshal_frr
from holo_tpu.frr.kernel import BackupTable
from holo_tpu.ops.graph import INF, Topology
from holo_tpu.resilience import faults
from holo_tpu.resilience.breaker import CircuitBreaker
from holo_tpu.telemetry import convergence, profiling

# FRR dispatch observability, mirroring the SPF backend's signal set:
# wall time per backup-table computation, recompiles vs shape hits, and
# how much of the padded link/adjacency planes is real work.
_FRR_SECONDS = telemetry.histogram(
    "holo_frr_dispatch_seconds",
    "Wall time of one backup-table computation (marshal + dispatch + readback)",
    ("engine",),
)
_FRR_COMPILES = telemetry.counter(
    "holo_frr_jit_compiles_total",
    "FRR dispatches hitting a new shape bucket (XLA recompile)",
)
_FRR_JIT_HITS = telemetry.counter(
    "holo_frr_jit_cache_hits_total",
    "FRR dispatches served from an already-compiled shape bucket",
)
_FRR_GRAPH_CACHE = telemetry.counter(
    "holo_frr_graph_cache_total",
    "Marshaled DeviceGraph cache lookups (FRR engine)",
    ("result",),
)
_FRR_PAD_OCCUPANCY = telemetry.gauge(
    "holo_frr_pad_occupancy",
    "Valid fraction of the padded FRR plane (last dispatch)",
    ("plane",),
)
# What the blocked rows kernel did, per dispatch (kernel.STATS): the
# distance rows it relaxed by direction, its relaxation rounds summed
# over its blocks, and each block's rows times its rounds (the reads a
# roofline of the rows counts, benchmark/frrcost.py).
_FRR_ROWS = telemetry.counter(
    "holo_frr_rows_total",
    "Distance rows the FRR kernel relaxed (forward rows, reversed-graph columns)",
    ("dir",),
)
_FRR_ROUNDS = telemetry.counter(
    "holo_frr_rounds_total",
    "Relaxation rounds of the FRR kernel's row blocks, summed over blocks",
)
_FRR_ROW_ROUNDS = telemetry.counter(
    "holo_frr_row_rounds_total",
    "Sum over the FRR kernel's row blocks of valid rows times rounds",
    ("dir",),
)
_FRR_SLOTS = telemetry.gauge(
    "holo_frr_rows_slots",
    "Slots of one direction of the FRR kernel's compact rows graph (last dispatch)",
)
# The post-convergence batch (kernel.post_spf_block): its fixpoints'
# rounds, and the slots one of its rounds reads (the compact forward
# graph's slots times the batch's link columns).
_FRR_POST_ROUNDS = telemetry.counter(
    "holo_frr_post_rounds_total",
    "Rounds of the FRR kernel's post-convergence fixpoints, summed",
)
_FRR_POST_SLOTS = telemetry.gauge(
    "holo_frr_post_slots",
    "Slots one round of the FRR post-convergence batch reads (last dispatch)",
)
_FRR_REPAIRS = telemetry.counter(
    "holo_frr_repairs_total",
    "Repairs attached, per (protected link, protectable destination) per attach",
    ("kind",),
)
# Same family the SPF backend increments (registry get-or-create by
# name): one process-wide series of mesh-sharded dispatches, split by
# dispatch kind.
_FRR_SHARD_DISPATCHES = telemetry.counter(
    "holo_spf_shard_dispatch_total",
    "Dispatches routed through the process-mesh sharded path "
    "(parallel/mesh.py layout contract)",
    ("kind",),
)


def _mesh():
    from holo_tpu.parallel.mesh import process_mesh

    return process_mesh()


@dataclass
class FrrConfig:
    """Mirrors the reference YANG fast-reroute containers
    (ietf-ospf ``fast-reroute/lfa``, holo's ti-lfa extension leaves).

    Policy knobs (ISSUE 10) are applied as vectorized masks inside the
    batched kernel (and mirrored by the scalar oracle):

    - ``node_protection`` — only node-protecting LFAs are selectable
      (inequality 3 as policy); uncovered destinations fall through to
      remote-LFA / TI-LFA.
    - ``srlg_disjoint`` — repair candidates sharing any SRLG bit
      (``Topology.edge_srlg``) with the protected link are excluded.
    - ``protected_prefixes`` — per-prefix protection filter: when
      non-None, backups attach only to routes covered by one of these
      networks (RFC 7916-style protection policy scope).
    """

    enabled: bool = False  # LFA (RFC 5286)
    remote_lfa: bool = False  # RFC 7490 (requires enabled)
    ti_lfa: bool = False  # TI-LFA segment repairs (requires enabled + SR)
    engine: str = "scalar"  # 'scalar' | 'tpu'
    node_protection: bool = False  # LFA must node-protect
    srlg_disjoint: bool = False  # backup must be SRLG-disjoint
    protected_prefixes: tuple | None = None  # None = protect everything

    def active(self) -> bool:
        return self.enabled

    def protects_prefix(self, prefix) -> bool:
        """Per-prefix protection filtering: is ``prefix`` in scope?"""
        if self.protected_prefixes is None:
            return True
        for scope in self.protected_prefixes:
            try:
                if prefix == scope or prefix.subnet_of(scope):
                    return True
            except (TypeError, ValueError):
                continue  # mixed address families never match
        return False


#: the kinds of ``repair_plan``, by code, as ``holo_frr_repairs_total`` labels them
REPAIR_KINDS = ("none", "lfa", "rlfa", "ti-lfa")


def _lowest_atoms(words: np.ndarray) -> np.ndarray:
    """int32[...]: the lowest set atom id of each uint32 bitmask row of
    ``words`` ([..., W]), -1 where none (the deterministic pick)."""
    words = np.asarray(words, np.uint32)
    nz = words != 0
    wi = np.argmax(nz, axis=-1)
    w = np.take_along_axis(words, wi[..., None], axis=-1)[..., 0]
    low = (w & (~w + np.uint32(1))).astype(np.float64)
    bit = np.log2(np.where(low > 0, low, 1.0)).astype(np.int32)
    return np.where(nz.any(axis=-1), wi.astype(np.int32) * 32 + bit, -1)


def repair_plan(table: BackupTable, cfg: FrrConfig) -> tuple:
    """The repair of every (protected link, destination) under ``cfg``:
    ``(kind, release, via0, via1)``, int[L, N] each — ``kind`` the
    :data:`REPAIR_KINDS` index (0 none), ``release`` the release
    next-hop atom, ``via0``/``via1`` the repair vertices (-1 past the
    repair's own: an LFA's neighbour, a remote LFA's PQ node, TI-LFA's
    P and Q).

    Direct LFA first; else the remote-LFA PQ node, released through the
    PQ node's own LFA (the plain route to it may cross the failed link)
    — a PQ node without one protects nothing and does not fall through;
    else TI-LFA released on the lowest post-convergence next-hop atom.
    Only repairs with a release next hop are kept: nothing can forward
    on the others."""
    nl, n = table.lfa_adj.shape
    fin = table.inputs
    kind = np.zeros((nl, n), np.int8)
    release = np.full((nl, n), -1, np.int32)
    via0 = np.full((nl, n), -1, np.int32)
    via1 = np.full((nl, n), -1, np.int32)
    if not cfg.enabled or nl == 0:
        return kind, release, via0, via1
    adj_atom = np.append(fin.adj_atom[: fin.n_adj], -1).astype(np.int32)
    adj_nbr = np.append(fin.adj_nbr[: fin.n_adj], -1).astype(np.int32)
    lfa = table.lfa_adj >= 0
    kind[lfa] = 1
    release[lfa] = adj_atom[table.lfa_adj[lfa]]
    via0[lfa] = adj_nbr[table.lfa_adj[lfa]]
    open_ = ~lfa
    if cfg.remote_lfa:
        pq = table.rlfa_pq
        has = open_ & (pq >= 0)
        rel = table.lfa_adj[np.arange(nl)[:, None], np.clip(pq, 0, n - 1)]
        rel = np.where(rel >= 0, adj_atom[rel], -1)
        got = has & (rel >= 0)
        kind[got] = 2
        release[got] = rel[got]
        via0[got] = pq[got]
        open_ &= ~has  # a PQ without a release protects nothing
    if cfg.ti_lfa:
        rel = _lowest_atoms(table.post_nh)
        got = open_ & (table.tilfa_p >= 0) & (rel >= 0)
        kind[got] = 3
        release[got] = rel[got]
        via0[got] = table.tilfa_p[got]
        via1[got] = table.tilfa_q[got]
    return kind, release, via0, via1


def repair_picks(table: BackupTable, plan: tuple, words: np.ndarray) -> tuple:
    """``plan`` (:func:`repair_plan`) read for each destination vertex's
    primary next hops: every (vertex, primary atom) of ``words``
    (uint32[N, W], an SPF result's next-hop words) whose atom rides a
    protected link (``atom_link``) and has a repair there, lowest atom
    first per vertex.  Returns ``(vertex, atom, kind, release, via0,
    via1)``, int arrays of one length — the repair a router flips to
    when that next hop's link, BFD session or carrier drops."""
    kind, release, via0, via1 = plan
    n = kind.shape[1]
    words = np.ascontiguousarray(np.asarray(words, np.uint32)[:n])
    bits = np.unpackbits(
        words.view(np.uint8).reshape(n, -1, 4)[:, :, ::-1], axis=2,
    )[:, :, ::-1].reshape(n, -1)
    vs, ats = np.nonzero(bits)
    link_of = np.full(bits.shape[1], -1, np.int64)
    for a, link in table.inputs.atom_link.items():
        if a < bits.shape[1]:
            link_of[a] = link
    lk = link_of[ats]
    keep = lk >= 0
    vs, ats, lk = vs[keep], ats[keep], lk[keep]
    k = kind[lk, vs]
    got = k > 0
    vs, ats, lk = vs[got], ats[got], lk[got]
    return (
        vs, ats, k[got], release[lk, vs], via0[lk, vs], via1[lk, vs],
    )


def note_repairs(table: BackupTable, cfg: FrrConfig, plan=None) -> None:
    """Count one attach of ``table`` into ``holo_frr_repairs_total``:
    one increment per (protected link, protectable destination) — the
    root and destinations the failure cuts off are not protectable — by
    the kind :func:`repair_plan` keeps (``none`` for no repair)."""
    kind = (plan if plan is not None else repair_plan(table, cfg))[0]
    eligible = table.post_dist < INF
    eligible[:, table.root] = False
    counts = np.bincount(kind[eligible].ravel(), minlength=len(REPAIR_KINDS))
    for code, name in enumerate(REPAIR_KINDS):
        if counts[code]:
            _FRR_REPAIRS.labels(kind=name).inc(int(counts[code]))


def ensure_engine(current, cfg: FrrConfig) -> "FrrEngine":
    """Reuse ``current`` when it already runs ``cfg.engine``, else build
    a fresh engine (the graph/jit caches are per-engine).  The shared
    lazy-create step for every protocol instance holding a
    ``_frr_engine`` slot.  With the process dispatch pipeline armed
    ([pipeline] in holod.toml) a fresh tpu engine is wrapped so the
    backup-table dispatch rides the async pipeline (``current`` may
    therefore be an AsyncFrrEngine — its ``engine`` attribute
    delegates, so the reuse check is unchanged)."""
    if current is not None and current.engine == cfg.engine:
        current.set_policy(cfg)
        return current
    from holo_tpu.pipeline import wrap_frr_engine

    engine = wrap_frr_engine(FrrEngine(engine=cfg.engine))
    engine.set_policy(cfg)
    return engine


class FrrEngine:
    """Backup-table computation behind the SpfBackend-style interface."""

    def __init__(
        self,
        engine: str = "scalar",
        n_atoms: int = 64,
        max_iters: int | None = None,
        breaker: CircuitBreaker | None = None,
    ):
        """``breaker`` guards the device path like the SPF backend's: a
        failed/overdue ``frr_batch`` dispatch re-runs on the scalar
        oracle (bit-identical backup tables by the parity contract)."""
        self.engine = engine
        self.n_atoms = n_atoms
        self.max_iters = max_iters
        self.breaker = (
            breaker if breaker is not None else CircuitBreaker("frr-dispatch")
        )
        self._jit = None  # built lazily (jax import on first TPU compute)
        self._compiled_shapes: set[tuple] = set()
        # Mesh-sharded programs, one per mesh identity (outputs pinned
        # to the batch sharding over protected links).
        self._shard_jits: dict[tuple, object] = {}
        # Shapes of the compact rows graphs per vertex count: they only
        # grow (a failed hub would otherwise narrow them, and a narrower
        # graph is a new program) — DeviceGraphCache._k_pad_floor's rule.
        self._rows_floor: dict[int, tuple[int, int]] = {}
        # Protection policy (node-protection / SRLG-disjoint masks) —
        # traced kernel inputs, so a policy flip never recompiles.
        self.policy = FrrConfig()

    def set_policy(self, cfg: "FrrConfig") -> None:
        """Adopt the instance's protection policy (ensure_engine seam)."""
        self.policy = cfg

    def _step(self, g, root, *args):
        from holo_tpu.frr.kernel import frr_batch

        fwd, rev, base, rsrc, lf, lc, la, lv, an, ac, al, av, lsr, asr, rnp = args
        return frr_batch(
            g, root, fwd, rev, base, rsrc, lf, lc, la, lv, an, ac, al, av,
            link_srlg=lsr, adj_srlg=asr, require_np=rnp,
            max_iters=self.max_iters,
        )

    def _sharded_jit(self, mesh):
        if mesh.size == 1:
            # Degenerate mesh: the plain program is the sharded program
            # (built by _launch_tpu before dispatch branches).
            return self._jit
        import jax

        from holo_tpu.parallel.mesh import constrain_batch, mesh_cache_key

        key = mesh_cache_key(mesh)
        fn = self._shard_jits.get(key)
        if fn is None:

            def frr_batch_sharded(g, root, *args):
                return constrain_batch(mesh, self._step(g, root, *args))

            fn = self._shard_jits[key] = jax.jit(frr_batch_sharded)
        return fn

    def _policy_args(self, fin) -> tuple:
        """(link_srlg, adj_srlg, require_np) kernel inputs under the
        current policy.  Disarmed SRLG policy passes all-zero planes —
        the mask then excludes nothing and the table is bit-identical
        to the pre-policy kernel (parity suites run disarmed)."""
        if self.policy.srlg_disjoint:
            lsr, asr = fin.link_srlg, fin.adj_srlg
        else:
            lsr = np.zeros_like(fin.link_srlg)
            asr = np.zeros_like(fin.adj_srlg)
        return lsr, asr, np.bool_(self.policy.node_protection)

    def _rows_args(self, topo: Topology) -> tuple:
        """The forward (with its slots' atoms) and reversed compact rows
        graphs of ``topo`` (kernel.RowsGraph of host arrays), at no less
        than the widest shapes this engine built for the same vertex
        count."""
        from holo_tpu.frr.inputs import rows_graph_shape, rows_graphs
        from holo_tpu.frr.kernel import RowsGraph

        need_h, need_k = rows_graph_shape(topo)
        floor_h, floor_k = self._rows_floor.get(topo.n_vertices, (0, 0))
        h_pad = max(floor_h, -(-max(need_h, 1) // 64) * 64)
        k_hi = max(floor_k, -(-max(need_k, 1) // 8) * 8)
        self._rows_floor[topo.n_vertices] = (h_pad, k_hi)
        fwd, rev = rows_graphs(topo, h_pad, k_hi)
        return RowsGraph(*fwd), RowsGraph(*rev)

    def _kernel_args(self, topo: Topology, fin, nb: int = 1) -> tuple:
        """Host planes of one dispatch, the per-link ones padded to a
        multiple of ``nb`` (the mesh batch axis) with valid=False links."""
        fwd, rev = self._rows_args(topo)
        lf, lc, la, lv = fin.link_far, fin.link_cost, fin.link_atom, fin.link_valid
        lsr, asr, rnp = self._policy_args(fin)
        rsrc = fin.rev_src
        pad = (-lf.shape[0]) % nb
        if pad:
            lf = np.concatenate([lf, np.zeros(pad, lf.dtype)])
            lc = np.concatenate([lc, np.ones(pad, lc.dtype)])
            la = np.concatenate([la, np.full(pad, -1, la.dtype)])
            lv = np.concatenate([lv, np.zeros(pad, bool)])
            lsr = np.concatenate([lsr, np.zeros(pad, lsr.dtype)])
            want = -(-(1 + lf.shape[0]) // 8) * 8
            rsrc = np.concatenate([
                rsrc, np.full(want - rsrc.shape[0], topo.n_vertices, np.int32)
            ])
        return (
            fwd, rev, fin.base_src, rsrc, lf, lc, la, lv,
            fin.adj_nbr, fin.adj_cost, fin.adj_link, fin.adj_valid,
            lsr, asr, rnp,
        )

    def _shard_args(self, mesh, args: tuple) -> tuple:
        """Place the FRR planes per the mesh layout contract: the
        per-protected-link planes (the post-convergence batch axis)
        sharded over ``batch`` and everything else replicated."""
        if mesh.size == 1:
            # Nothing to shard: the jit commits host arrays itself
            # (see mesh.shard_scenarios).
            return args
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        link = NamedSharding(mesh, P("batch"))
        rep = NamedSharding(mesh, P())
        fwd, rev, base, rsrc, lf, lc, la, lv, an, ac, al, av, lsr, asr, rnp = args
        put = jax.device_put
        return (
            put(fwd, rep), put(rev, rep), put(base, rep), put(rsrc, rep),
            put(lf, link), put(lc, link), put(la, link), put(lv, link),
            put(an, rep), put(ac, rep), put(al, rep), put(av, rep),
            put(lsr, link), put(asr, rep), np.bool_(rnp),
        )

    # -- device path

    def _prepare(self, topo: Topology):
        # Shared with TpuSpfBackend.prepare (ROADMAP cleanup): an
        # instance running SPF + FRR marshals its DeviceGraph once —
        # the holo_spf_marshal_cache_total hit/miss/delta triple makes
        # the dedup visible, while this engine's series stays alive.
        # The kernel runs on the compact graphs it is handed from the
        # topology and reads only ``is_router`` and the atom-word width
        # here, never ``in_edge_id``, so a structurally-updated resident
        # serves it as it is: FRR never forces the SPF's DeltaPath chain
        # into a re-marshal.
        from holo_tpu.ops.spf_engine import shared_graph_cache

        g, how = shared_graph_cache().get(
            topo, max(self.n_atoms, topo.n_atoms())
        )
        _FRR_GRAPH_CACHE.labels(result=how).inc()
        return g

    def _compute_tpu(self, topo: Topology, fin) -> BackupTable:
        return self._finish_tpu(self._launch_tpu(topo, fin))

    def _launch_tpu(self, topo: Topology, fin) -> tuple:
        """Phase 1 of the (optionally pipelined) FRR dispatch: chaos
        seams, plane marshal, the ASYNC jit call.  Returns the handle
        :meth:`_finish_tpu` completes; between the two the device
        executes while the pipeline worker launches other entries
        (ISSUE 9 split-phase contract, mirroring
        ``TpuSpfBackend.launch_one``)."""
        faults.crashpoint("frr.dispatch")
        mesh = _mesh()
        if mesh is not None:
            # Shard-dispatch chaos seam: device loss / XLA failure on
            # any shard surfaces here and the breaker serves the whole
            # batch from the scalar oracle.
            faults.crashpoint("frr.shard")
        import jax

        from holo_tpu.parallel.mesh import mesh_cache_key

        if self._jit is None:

            def frr_batch_step(g, root, *args):
                return self._step(g, root, *args)

            self._jit = jax.jit(frr_batch_step)
        # The FRR analog of the SPF backend's sanctioned boundary: the
        # padded planes move host->device here, results device->host
        # in _finish_tpu, and nowhere else.
        obucket = self._obs_bucket(topo) if profiling.observing() else None
        with self._obs_ctx(obucket), profiling.stage(
            "frr.batch", "marshal"
        ):
            with sanctioned_transfer("frr.batch.marshal"):
                g = self._prepare(topo)
                if mesh is not None:
                    args = self._shard_args(
                        mesh,
                        self._kernel_args(topo, fin, mesh.shape["batch"]),
                    )
                    step = self._sharded_jit(mesh)
                else:
                    args = self._kernel_args(topo, fin)
                    step = self._jit
                _FRR_SLOTS.set(args[0].slots)
                sig = (
                    g.in_src.shape,
                    tuple(np.shape(a) for a in (*args[0], *args[2:12])),
                    mesh_cache_key(mesh),
                )
                if sig in self._compiled_shapes:
                    _FRR_JIT_HITS.inc()
                    fresh = False
                else:
                    self._compiled_shapes.add(sig)
                    _FRR_COMPILES.inc()
                    fresh = True
                out = step(g, topo.root, *args)
        if fresh:
            entry = profiling.record_cost(
                "frr.batch", step, g, topo.root, *args, shape_sig=sig
            )
            if entry is not None and obucket is not None:
                from holo_tpu.telemetry import observatory

                observatory.note_cost(
                    "frr.batch", "frr", "frr", obucket, entry
                )
        return (out, fin, topo, mesh is not None, obucket)

    @staticmethod
    def _obs_bucket(topo):
        """The observatory shape key for this FRR batch (the SPF
        tuner's quantization) — computed ONCE per dispatch at launch
        and carried through the handle."""
        from holo_tpu.parallel.mesh import mesh_cache_key
        from holo_tpu.pipeline.tuner import shape_bucket

        return shape_bucket(
            topo.n_vertices, topo.n_edges, 1, mesh_cache_key()
        )

    @staticmethod
    def _obs_ctx(obucket):
        """Dispatch-context window for the observatory feed (ISSUE 12):
        a shared null context while it is disarmed."""
        if obucket is None:
            return profiling.dispatch_context()
        return profiling.dispatch_context(
            kind="frr", engine="frr", bucket=obucket
        )

    def _finish_tpu(self, handle: tuple) -> BackupTable:
        """Phase 2: device completion + readback + accounting."""
        from holo_tpu.frr.kernel import STATS

        out, fin, topo, sharded, obucket = handle
        with self._obs_ctx(obucket), profiling.stage(
            "frr.batch", "device"
        ):
            faults.delaypoint("frr.dispatch")
            if not profiling.device_stages("frr.batch", out):
                profiling.sync(out)
        nl = fin.n_links
        n = int(topo.n_vertices)
        with self._obs_ctx(obucket), profiling.stage(
            "frr.batch", "readback"
        ):
            with sanctioned_transfer("frr.batch.unmarshal"):
                stats = dict(zip(STATS, np.asarray(out.stats).tolist()))
                if stats["unmatched_links"] or stats["missing_rows"]:
                    # The resident graph does not hold a protected edge,
                    # or a release neighbour had no row: the table would
                    # be wrong, so the breaker serves the oracle.
                    raise RuntimeError(f"frr.batch: inconsistent planes {stats}")
                # [:nl] drops the link-plane pad (marshal bucket + mesh
                # batch-axis pad); [:n] drops the node-sharded row pad
                # on the vertex axis — both no-ops single-device.
                table = BackupTable(
                    inputs=fin,
                    root=int(topo.root),
                    lfa_adj=np.asarray(out.lfa_adj)[:nl, :n],
                    lfa_nodeprot=np.asarray(out.lfa_nodeprot)[:nl, :n],
                    rlfa_pq=np.asarray(out.rlfa_pq)[:nl, :n],
                    tilfa_p=np.asarray(out.tilfa_p)[:nl, :n],
                    tilfa_q=np.asarray(out.tilfa_q)[:nl, :n],
                    post_dist=np.asarray(out.post_dist)[:nl, :n],
                    post_nh=np.asarray(out.post_nh)[:nl, :n],
                )
        if sharded:
            _FRR_SHARD_DISPATCHES.labels(kind="frr").inc()
        convergence.note_dispatch("frr", "device")
        _FRR_ROWS.labels(dir="forward").inc(stats["rows_forward"])
        _FRR_ROWS.labels(dir="reverse").inc(stats["rows_reverse"])
        _FRR_ROUNDS.inc(stats["rounds"])
        _FRR_ROW_ROUNDS.labels(dir="forward").inc(stats["row_rounds_forward"])
        _FRR_ROW_ROUNDS.labels(dir="reverse").inc(stats["row_rounds_reverse"])
        _FRR_POST_ROUNDS.inc(stats["post_rounds"])
        _FRR_POST_SLOTS.set(stats["post_slots"])
        table.stats = stats
        return table

    def marshal_inputs(self, topo: Topology):
        """Marshal the FRR planes + pad-occupancy gauges (the shared
        front half of :meth:`compute`, exposed for the pipelined
        facade)."""
        fin = marshal_frr(topo)
        lp = fin.link_valid.shape[0]
        ap = fin.adj_valid.shape[0]
        if lp:
            _FRR_PAD_OCCUPANCY.labels(plane="links").set(fin.n_links / lp)
        if ap:
            # Deferred (set_fn): see compute().
            _FRR_PAD_OCCUPANCY.labels(plane="adjs").set_fn(
                telemetry.deferred_mean(fin.adj_valid)
            )
        return fin

    def _scalar_fallback(self, topo: Topology, fin) -> BackupTable:
        """Breaker degraded path: the oracle over the SAME marshaled
        inputs and policy — bit-identical by the parity suite."""
        from holo_tpu.frr.scalar import frr_reference

        try:
            return frr_reference(
                topo, self.n_atoms, inputs=fin,
                srlg_disjoint=self.policy.srlg_disjoint,
                node_protection=self.policy.node_protection,
            )
        finally:
            convergence.note_dispatch("frr", "fallback")

    # -- dispatch

    def compute(self, topo: Topology) -> BackupTable:
        """One batched backup-table computation for ``topo.root``."""
        t0 = time.perf_counter()
        with telemetry.span("frr.dispatch", engine=self.engine):
            # Occupancy gauges ride marshal_inputs; the adj-plane mean
            # is deferred to scrape time via set_fn (holo-lint HL105).
            fin = self.marshal_inputs(topo)
            if self.engine == "tpu":
                table = self.breaker.call(
                    lambda: self._compute_tpu(topo, fin),
                    lambda: self._scalar_fallback(topo, fin),
                    context="frr.batch",
                )
            else:
                from holo_tpu.frr.scalar import frr_reference

                table = frr_reference(
                    topo, self.n_atoms, inputs=fin,
                    srlg_disjoint=self.policy.srlg_disjoint,
                    node_protection=self.policy.node_protection,
                )
                convergence.note_dispatch("frr", "scalar")
        _FRR_SECONDS.labels(engine=self.engine).observe(
            time.perf_counter() - t0
        )
        return table


# -- jaxpr-audit registrations (HL3xx) ----------------------------------
# Inert contract descriptors for holo_tpu.analysis.jaxpr_audit.  This
# module keeps jax out of its import graph, so the thunks import jax
# themselves — they only ever run when the audit arms.
from holo_tpu.analysis.kernels import register_kernel as _register_kernel  # noqa: E402

_AUDIT_LINKS, _AUDIT_ADJ, _AUDIT_HEAVY = 8, 16, 64


def _audit_frr_specs() -> tuple:
    import jax
    import jax.numpy as jnp

    from holo_tpu.frr.inputs import ROWS_K_LO
    from holo_tpu.frr.kernel import RowsGraph
    from holo_tpu.ops.spf_engine import _AUDIT_N, audit_graph_spec

    s = jax.ShapeDtypeStruct
    lk, ad, hv = _AUDIT_LINKS, _AUDIT_ADJ, _AUDIT_HEAVY
    i32 = jnp.int32
    rows = RowsGraph(
        lo_src=s((_AUDIT_N, ROWS_K_LO), i32),
        lo_cost=s((_AUDIT_N, ROWS_K_LO), i32),
        hi_vtx=s((hv,), i32),
        hi_src=s((hv, 8), i32),
        hi_cost=s((hv, 8), i32),
    )
    # The forward graph also carries the slots' direct atoms, the
    # post-convergence batch's next-hop seeds.
    fwd = rows._replace(
        lo_atom=s((_AUDIT_N, ROWS_K_LO), i32), hi_atom=s((hv, 8), i32)
    )
    return (
        audit_graph_spec(),
        s((), i32),  # root
        fwd,  # forward rows graph, with atoms
        rows,  # reversed rows graph
        s((1 + lk + ad,), i32),  # base_src (rounded to 8 by marshal_frr)
        s((16,), i32),  # rev_src
        s((lk,), i32),  # link_far
        s((lk,), i32),  # link_cost
        s((lk,), i32),  # link_atom
        s((lk,), jnp.bool_),  # link_valid
        s((ad,), i32),  # adj_nbr
        s((ad,), i32),  # adj_cost
        s((ad,), i32),  # adj_link
        s((ad,), jnp.bool_),  # adj_valid
        s((lk,), jnp.uint32),  # link_srlg
        s((ad,), jnp.uint32),  # adj_srlg
        s((), jnp.bool_),  # require_np
    )


def _audit_frr_builder():
    import jax

    return jax.jit(FrrEngine()._step)


def _audit_frr_sharded_builder(mesh):
    import jax

    from holo_tpu.parallel.mesh import constrain_batch

    step = FrrEngine()._step

    @jax.jit
    def frr_batch_sharded(g, root, *args):
        return constrain_batch(mesh, step(g, root, *args))

    return frr_batch_sharded


_register_kernel(
    "frr.batch",
    builder=_audit_frr_builder,
    specs=_audit_frr_specs,
    buckets=16,  # pow2 protected-link x adjacency pads per shape
)

_register_kernel(
    "frr.batch.sharded",
    builder=_audit_frr_sharded_builder,
    specs=_audit_frr_specs,
    fences=1,
    needs_mesh=True,
    buckets=16,
)
