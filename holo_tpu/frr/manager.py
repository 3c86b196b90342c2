"""FRR engine + backup resolution policy.

``FrrEngine`` is the dispatch point the protocol layer calls right after
its primary SPF: Topology in, :class:`BackupTable` out, through either
the batched device kernel (:func:`holo_tpu.frr.kernel.frr_batch`, cached
per shape bucket like ``TpuSpfBackend``) or the scalar oracle.  Both are
bit-identical; 'scalar' is the default for the same reason it is for
SPF — zero marshaling latency on small LSDBs.

``resolve_backup`` applies the configured protection policy to one
(protected link, destination vertex) query: direct LFA first (cheapest —
no extra encapsulation), then remote-LFA PQ tunnel, then the TI-LFA
segment repair.  The result is symbolic (atoms + repair vertices); the
protocol layer maps atoms to (interface, address) next hops and repair
vertices to SR labels via its own SID tables.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

import numpy as np

from holo_tpu import telemetry
from holo_tpu.analysis.runtime import sanctioned_transfer
from holo_tpu.frr.inputs import marshal_frr
from holo_tpu.frr.kernel import BackupTable
from holo_tpu.ops.graph import Topology
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


@dataclass(frozen=True)
class BackupEntry:
    """One resolved repair for (protected link, destination vertex)."""

    kind: str  # 'lfa' | 'rlfa' | 'ti-lfa'
    atom: int | None  # release next-hop atom (None: caller falls back
    # to its primary next hop toward via[0])
    via: tuple[int, ...] = ()  # repair vertices: () | (pq,) | (p[, q])
    node_protecting: bool = False


def first_atom(words: np.ndarray) -> int | None:
    """Lowest set atom id in a uint32 bitmask row (deterministic pick)."""
    for wi, word in enumerate(np.asarray(words, np.uint32)):
        w = int(word)
        if w:
            return wi * 32 + (w & -w).bit_length() - 1
    return None


def resolve_backup(
    table: BackupTable, cfg: FrrConfig, link: int, dest: int
) -> BackupEntry | None:
    """Pick the repair for (link, dest) under ``cfg``; None = unprotected."""
    if not cfg.enabled or link < 0 or link >= table.n_links:
        return None
    fin = table.inputs
    a = int(table.lfa_adj[link, dest])
    if a >= 0:
        return BackupEntry(
            kind="lfa",
            atom=int(fin.adj_atom[a]),
            via=(int(fin.adj_nbr[a]),),
            node_protecting=bool(table.lfa_nodeprot[link, dest]),
        )
    if cfg.remote_lfa:
        pq = int(table.rlfa_pq[link, dest])
        if pq >= 0:
            # Release toward the PQ node: its own LFA pick when the
            # plain P-space route would still cross the failed link.
            rel = int(table.lfa_adj[link, pq])
            atom = int(fin.adj_atom[rel]) if rel >= 0 else None
            return BackupEntry(kind="rlfa", atom=atom, via=(pq,))
    if cfg.ti_lfa:
        p = int(table.tilfa_p[link, dest])
        if p >= 0:
            q = int(table.tilfa_q[link, dest])
            atom = first_atom(table.post_nh[link, dest])
            via = (p,) if q < 0 else (p, q)
            return BackupEntry(kind="ti-lfa", atom=atom, via=via)
    return None


def repair_map(
    table: BackupTable | None,
    cfg: FrrConfig,
    words: np.ndarray,
    vertex: int,
) -> dict[int, BackupEntry]:
    """{primary next-hop atom id -> repair} for one destination vertex.

    The shared protocol-side consumption step (OSPFv2/v3, IS-IS): each
    primary atom rides exactly one protected link (``atom_link``), and
    the repair for (that link, this destination) is what the router
    flips to when the link's BFD session or carrier drops.  Entries
    whose repair has no release atom (an unreachable tunnel release) are
    omitted — the caller cannot build a forwarding entry from them."""
    out: dict[int, BackupEntry] = {}
    if table is None or not cfg.active():
        return out
    n_words = np.asarray(words, np.uint32)
    for wi, word in enumerate(n_words):
        w = int(word)
        while w:
            low = w & -w
            a = wi * 32 + low.bit_length() - 1
            w ^= low
            link = table.link_of_atom(a)
            if link is None:
                continue
            entry = resolve_backup(table, cfg, link, vertex)
            if entry is not None and entry.atom is not None:
                out[a] = entry
    return out


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
        # Mesh-sharded all-roots programs, one per mesh identity
        # (outputs pinned to the batch sharding over protected links).
        self._shard_jits: dict[tuple, object] = {}
        # Protection policy (node-protection / SRLG-disjoint masks) —
        # traced kernel inputs, so a policy flip never recompiles.
        self.policy = FrrConfig()

    def set_policy(self, cfg: "FrrConfig") -> None:
        """Adopt the instance's protection policy (ensure_engine seam)."""
        self.policy = cfg

    def _sharded_jit(self, mesh):
        if mesh.size == 1:
            # Degenerate mesh: the plain program is the sharded program
            # (built by _compute_tpu before dispatch branches).
            return self._jit
        import jax

        from holo_tpu.frr.kernel import frr_batch
        from holo_tpu.parallel.mesh import constrain_batch, mesh_cache_key

        key = mesh_cache_key(mesh)
        fn = self._shard_jits.get(key)
        if fn is None:

            @jax.jit
            def step(g, root, lf, lc, lv, em, an, ac, al, av, lsr, asr, rnp):
                out = frr_batch(
                    g, root, lf, lc, lv, em, an, ac, al, av,
                    link_srlg=lsr, adj_srlg=asr, require_np=rnp,
                    max_iters=self.max_iters,
                )
                return constrain_batch(mesh, out)

            fn = self._shard_jits[key] = step
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

    def _shard_args(self, mesh, fin):
        """Place the FRR planes per the mesh layout contract: the
        per-protected-link planes (the all-roots/what-if batch axis)
        sharded over ``batch`` — padded to the axis size with
        valid=False links whose scenario masks fail nothing — and the
        repair-candidate adjacency planes replicated."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        nb = mesh.shape["batch"]
        lf, lc, lv, em = (
            fin.link_far, fin.link_cost, fin.link_valid, fin.edge_masks,
        )
        lsr, asr, rnp = self._policy_args(fin)
        pad = (-lf.shape[0]) % nb
        if pad:
            lf = np.concatenate([lf, np.zeros(pad, lf.dtype)])
            lc = np.concatenate([lc, np.ones(pad, lc.dtype)])
            lv = np.concatenate([lv, np.zeros(pad, bool)])
            em = np.concatenate([em, np.ones((pad, em.shape[1]), bool)])
            lsr = np.concatenate([lsr, np.zeros(pad, lsr.dtype)])
        if mesh.size == 1:
            # Nothing to shard: the jit commits host arrays itself
            # (see mesh.shard_scenarios).
            return (
                lf, lc, lv, em,
                fin.adj_nbr, fin.adj_cost, fin.adj_link, fin.adj_valid,
                lsr, asr, rnp,
            )
        link = NamedSharding(mesh, P("batch"))
        mask = NamedSharding(mesh, P("batch", None))
        rep = NamedSharding(mesh, P())
        return (
            jax.device_put(lf, link),
            jax.device_put(lc, link),
            jax.device_put(lv, link),
            jax.device_put(em, mask),
            jax.device_put(fin.adj_nbr, rep),
            jax.device_put(fin.adj_cost, rep),
            jax.device_put(fin.adj_link, rep),
            jax.device_put(fin.adj_valid, rep),
            jax.device_put(lsr, link),
            jax.device_put(asr, rep),
            np.bool_(rnp),
        )

    # -- device path

    def _prepare(self, topo: Topology):
        # Shared with TpuSpfBackend.prepare (ROADMAP cleanup): an
        # instance running SPF + FRR now marshals its DeviceGraph once —
        # the holo_spf_marshal_cache_total hit/miss/delta triple makes
        # the dedup visible, while this engine's series stays alive.
        #
        # Incremental-vs-full choice (DeltaPath): the FRR kernel
        # gathers its per-protected-link scenario masks through
        # ``in_edge_id``, so it can ride a delta-updated resident graph
        # only while edge ids stay valid — pure weight-change chains
        # within depth/padding headroom.  ``need_edge_ids`` makes the
        # cache rebuild (full path) for structurally-updated entries;
        # every disposition lands in holo_spf_delta_total{kind,path}.
        from holo_tpu.ops.spf_engine import shared_graph_cache

        g, how = shared_graph_cache().get(
            topo, max(self.n_atoms, topo.n_atoms()), need_edge_ids=True
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

        from holo_tpu.frr.kernel import frr_batch
        from holo_tpu.parallel.mesh import mesh_cache_key

        if self._jit is None:
            self._jit = jax.jit(
                lambda g, root, lf, lc, lv, em, an, ac, al, av, lsr, asr, rnp: (
                    frr_batch(
                        g, root, lf, lc, lv, em, an, ac, al, av,
                        link_srlg=lsr, adj_srlg=asr, require_np=rnp,
                        max_iters=self.max_iters,
                    )
                )
            )
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
                    args = self._shard_args(mesh, fin)
                    step = self._sharded_jit(mesh)
                else:
                    args = (
                        fin.link_far,
                        fin.link_cost,
                        fin.link_valid,
                        fin.edge_masks,
                        fin.adj_nbr,
                        fin.adj_cost,
                        fin.adj_link,
                        fin.adj_valid,
                        *self._policy_args(fin),
                    )
                    step = self._jit
                sig = (
                    args[0].shape, args[3].shape, args[4].shape,
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
        tuner's quantization, batch = the all-roots plane) — computed
        ONCE per dispatch at launch and carried through the handle."""
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
        out, fin, topo, sharded, obucket = handle
        with self._obs_ctx(obucket), profiling.stage(
            "frr.batch", "device"
        ):
            faults.delaypoint("frr.dispatch")
            if not profiling.device_stages("frr.batch", out):
                profiling.sync(out)
        nl = fin.n_links
        n = int(topo.n_vertices)
        if sharded:
            _FRR_SHARD_DISPATCHES.labels(kind="frr").inc()
        convergence.note_dispatch("frr", "device")
        with self._obs_ctx(obucket), profiling.stage(
            "frr.batch", "readback"
        ):
            with sanctioned_transfer("frr.batch.unmarshal"):
                # [:nl] drops the link-plane pad (marshal bucket + mesh
                # batch-axis pad); [:n] drops the node-sharded row pad
                # on the vertex axis — both no-ops single-device.
                return BackupTable(
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

_AUDIT_LINKS, _AUDIT_ADJ = 8, 16


def _audit_frr_specs() -> tuple:
    import jax
    import jax.numpy as jnp

    from holo_tpu.ops.spf_engine import _AUDIT_E, audit_graph_spec

    s = jax.ShapeDtypeStruct
    lk, ad = _AUDIT_LINKS, _AUDIT_ADJ
    return (
        audit_graph_spec(),
        s((), jnp.int32),  # root
        s((lk,), jnp.int32),  # link_far
        s((lk,), jnp.int32),  # link_cost
        s((lk,), jnp.bool_),  # link_valid
        s((lk, _AUDIT_E), jnp.bool_),  # edge_masks
        s((ad,), jnp.int32),  # adj_nbr
        s((ad,), jnp.int32),  # adj_cost
        s((ad,), jnp.int32),  # adj_link
        s((ad,), jnp.bool_),  # adj_valid
        s((lk,), jnp.uint32),  # link_srlg
        s((ad,), jnp.uint32),  # adj_srlg
        s((), jnp.bool_),  # require_np
    )


def _audit_frr_builder():
    import jax

    from holo_tpu.frr.kernel import frr_batch

    return jax.jit(
        lambda g, root, lf, lc, lv, em, an, ac, al, av, lsr, asr, rnp: (
            frr_batch(
                g, root, lf, lc, lv, em, an, ac, al, av,
                link_srlg=lsr, adj_srlg=asr, require_np=rnp,
                max_iters=None,
            )
        )
    )


def _audit_frr_sharded_builder(mesh):
    import jax

    from holo_tpu.frr.kernel import frr_batch
    from holo_tpu.parallel.mesh import constrain_batch

    @jax.jit
    def step(g, root, lf, lc, lv, em, an, ac, al, av, lsr, asr, rnp):
        out = frr_batch(
            g, root, lf, lc, lv, em, an, ac, al, av,
            link_srlg=lsr, adj_srlg=asr, require_np=rnp, max_iters=None,
        )
        return constrain_batch(mesh, out)

    return step


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
