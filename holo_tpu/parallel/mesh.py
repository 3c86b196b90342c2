"""Mesh construction + sharded SPF step + the process-wide dispatch mesh.

Layout contract (see package docstring):
- graph planes (``in_src``, ``in_cost``, ``in_valid``, ``in_edge_id``,
  ``direct_nh_words``, ``is_router``): sharded on their vertex (row) axis
  over ``node``, replicated over ``batch``;
- scenario edge masks ``[B, E]``: sharded over ``batch``, replicated over
  ``node``;
- results ``[B, ...]``: sharded over ``batch``.

The distance vector inside the fixed-point loops is logically replicated on
the node axis; GSPMD turns each round's row-block update into a node-axis
all-gather, which rides ICI on real hardware.

Since ISSUE 8 this module also owns the PROCESS MESH: the daemon (or a
test harness) installs one ``(batch, node)`` mesh at startup via
:func:`configure_process_mesh` (``[parallel]`` in holod.toml; default
all-devices-on-batch per :func:`make_spf_mesh`), and the real dispatch
path — ``TpuSpfBackend``, ``FrrEngine``, and the shared
``DeviceGraphCache`` — consults :func:`process_mesh` on every dispatch.
Cache entries and jit buckets are keyed by :func:`mesh_cache_key`, so a
reconfigured mesh never serves stale-placement residents (old-mesh
entries age out of the LRU instead of being handed to a new-mesh jit).
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from holo_tpu import telemetry
from holo_tpu.ops.spf_engine import DeviceGraph, spf_whatif_batch

_MESH_SIZE = telemetry.gauge(
    "holo_parallel_mesh_size",
    "Process dispatch-mesh axis sizes (0 = no mesh: single-device path)",
    ("axis",),
)

#: The process-wide dispatch mesh (None = single-device dispatch).
_PROCESS_MESH: Mesh | None = None


def make_spf_mesh(
    n_batch: int | None = None,
    n_node: int | None = None,
    devices: list | None = None,
) -> Mesh:
    """Build a (batch, node) mesh over the available devices.

    Defaults put all devices on the batch axis — what-if batches scale
    embarrassingly, so that is the right default until a single LSDB
    outgrows one chip's HBM.
    """
    devices = devices if devices is not None else jax.devices()
    nd = len(devices)
    if n_batch is None and n_node is None:
        n_batch, n_node = nd, 1
    elif n_batch is None:
        n_batch = nd // n_node
    elif n_node is None:
        n_node = nd // n_batch
    if n_batch * n_node != nd:
        raise ValueError(f"mesh {n_batch}x{n_node} != {nd} devices")
    arr = np.array(devices).reshape(n_batch, n_node)
    return Mesh(arr, axis_names=("batch", "node"))


def configure_process_mesh(
    n_batch: int | None = None,
    n_node: int | None = None,
    devices: list | None = None,
) -> Mesh:
    """Install the process-wide dispatch mesh (daemon boot; tests).

    From here on every ``TpuSpfBackend``/``FrrEngine`` dispatch and every
    ``DeviceGraphCache`` marshal runs mesh-sharded per the layout
    contract above.  Safe to call again with a different shape: entries
    and jit buckets are keyed by :func:`mesh_cache_key`, so the switch
    costs re-marshal/re-compile on first touch, never a torn placement.
    """
    global _PROCESS_MESH
    mesh = make_spf_mesh(n_batch, n_node, devices)
    _PROCESS_MESH = mesh
    _MESH_SIZE.labels(axis="batch").set(mesh.shape["batch"])
    _MESH_SIZE.labels(axis="node").set(mesh.shape["node"])
    return mesh


def reset_process_mesh() -> None:
    """Drop the process mesh: subsequent dispatches take the
    single-device path (tests; a daemon never un-configures)."""
    global _PROCESS_MESH
    _PROCESS_MESH = None
    _MESH_SIZE.labels(axis="batch").set(0)
    _MESH_SIZE.labels(axis="node").set(0)


def process_mesh() -> Mesh | None:
    """The installed dispatch mesh, or None (single-device path)."""
    return _PROCESS_MESH


def mesh_cache_key(mesh: Mesh | None = None) -> tuple | None:
    """Hashable identity of a mesh for cache/jit-bucket keys.

    Two meshes with the same shape over the same device ids key
    identically, so toggling the SAME mesh on/off re-hits warm
    entries."""
    m = mesh if mesh is not None else _PROCESS_MESH
    if m is None:
        return None
    return (
        m.shape["batch"],
        m.shape["node"],
        tuple(int(d.id) for d in m.devices.flat),
    )


def graph_sharding(mesh: Mesh) -> DeviceGraph:
    """The layout contract as a DeviceGraph of NamedShardings (rows over
    ``node``, batch-replicated) — shared by placement and by the
    donation-preserving sharded ``apply_delta`` jit."""
    row = NamedSharding(mesh, P("node", None))
    return DeviceGraph(
        in_src=row,
        in_cost=row,
        in_valid=row,
        in_edge_id=row,
        direct_nh_words=NamedSharding(mesh, P("node", None, None)),
        is_router=NamedSharding(mesh, P("node")),
    )


def _pad_rows(a: np.ndarray, rows: int):
    pad = rows - a.shape[0]
    if pad == 0:
        return a
    width = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
    return np.pad(a, width)


def shard_graph(g: DeviceGraph, mesh: Mesh) -> DeviceGraph:
    """Place graph planes row-sharded over the node axis (batch-replicated).

    Rows are zero-padded to a multiple of the node-axis size; padded rows
    have no valid in-edges and are unreachable, so results are unaffected
    (dispatch readbacks slice back to N and renormalize the no-parent /
    unreachable sentinels from the padded row count).
    """
    if mesh.size == 1:
        # Degenerate mesh, degenerate placement: a plain single-device
        # put — NamedSharding-committed arrays take a measurably slower
        # jax dispatch path, and a 1-device mesh must stay the plain path
        # (tests/test_shard_spf.py::test_one_device_mesh_matches_plain_path).
        return jax.device_put(g, mesh.devices.flat[0])
    n_node = mesh.shape["node"]
    n = g.in_src.shape[0]
    rows = ((n + n_node - 1) // n_node) * n_node
    spec = graph_sharding(mesh)

    def put(x, sharding):
        return jax.device_put(_pad_rows(np.asarray(x), rows), sharding)

    return DeviceGraph(*(put(x, s) for x, s in zip(g, spec)))


def tile_sharding(mesh: Mesh):
    """Placement of the tropical tile planes (ISSUE 13): fully
    REPLICATED over both axes.  The tiles are the contraction's shared
    left operand — every batch shard reads all of them every round, and
    row-sharding a [T, B, B] scatter-min would put a node-axis
    collective inside the fixpoint body."""
    from holo_tpu.ops.tropical import TropicalTiles

    rep = NamedSharding(mesh, P())
    return TropicalTiles(tiles=rep, cb=rep, pos=rep, perm=rep, inv=rep)


def shard_tiles(tt, mesh: Mesh):
    """Place tropical tile planes under the mesh (replicated); the
    1-device mesh degenerates to a plain put like shard_graph."""
    if mesh.size == 1:
        return jax.device_put(tt, mesh.devices.flat[0])
    return jax.device_put(tt, tile_sharding(mesh))


def shard_repair_rows(
    mesh: Mesh, rows: np.ndarray, sentinel: int
) -> jax.Array:
    """Place a per-scenario repair-row batch sharded over ``batch``,
    padded with sentinel-only rows to match the padded scenario axis
    (a pad scenario fails nothing, so its repair set is empty)."""
    r = np.asarray(rows, np.int32)
    pad = (-r.shape[0]) % mesh.shape["batch"]
    if pad:
        r = np.concatenate(
            [r, np.full((pad, r.shape[1]), sentinel, np.int32)]
        )
    if mesh.size == 1:  # see shard_scenarios
        return r
    return jax.device_put(r, NamedSharding(mesh, P("batch", None)))


def sharded_tropical_whatif_jit(mesh: Mesh, max_iters: int | None = None):
    """Sharded tropical what-if (ISSUE 13): the scenario lanes ride the
    batch axis through the min-plus contraction; tiles replicated."""
    from holo_tpu.ops.tropical import tropical_whatif_batch

    @jax.jit
    def step(g: DeviceGraph, tt, root, edge_masks, repair_rows):
        out = tropical_whatif_batch(
            g, tt, root, edge_masks, repair_rows, max_iters
        )
        return constrain_batch(mesh, out)

    return step


def sharded_tropical_multiroot_jit(mesh: Mesh, max_iters: int | None = None):
    """Sharded tropical multiroot: roots on the batch axis, tiles
    replicated, outputs pinned to the batch sharding."""
    from holo_tpu.ops.tropical import tropical_multiroot

    @jax.jit
    def step(g: DeviceGraph, tt, roots, edge_mask, repair_rows):
        out = tropical_multiroot(
            g, tt, roots, edge_mask, repair_rows, max_iters
        )
        return constrain_batch(mesh, out)

    return step


def shard_scenarios(mesh: Mesh, edge_masks: np.ndarray) -> jax.Array:
    """Place a scenario edge-mask batch sharded over ``batch``.

    Rows are padded to a multiple of the batch-axis size with all-True
    (no-failure) scenarios — same shape bucket for every batch size up
    to the next multiple, and the caller slices results back to B.
    """
    masks = np.asarray(edge_masks, bool)
    pad = (-masks.shape[0]) % mesh.shape["batch"]
    if pad:
        masks = np.concatenate(
            [masks, np.ones((pad, masks.shape[1]), bool)]
        )
    if mesh.size == 1:
        # Nothing to shard: let the jit commit the host array itself —
        # an explicit NamedSharding put costs ~0.3ms of pure dispatch
        # machinery (a JAX-CPU figure), which the 1-device mesh must not
        # pay.
        return masks
    return jax.device_put(masks, NamedSharding(mesh, P("batch", None)))


def shard_roots(mesh: Mesh, roots: np.ndarray) -> jax.Array:
    """Place a multi-root batch sharded over ``batch`` (pad with root 0;
    padded rows are sliced off on readback)."""
    r = np.asarray(roots, np.int32)
    pad = (-r.shape[0]) % mesh.shape["batch"]
    if pad:
        r = np.concatenate([r, np.zeros(pad, np.int32)])
    if mesh.size == 1:  # see shard_scenarios: no put on a 1-device mesh
        return r
    return jax.device_put(r, NamedSharding(mesh, P("batch")))


def constrain_batch(mesh: Mesh, out):
    """Pin a result pytree's leading axis to the batch sharding (the
    annotation GSPMD propagates the whole program from).  On a
    1-device mesh the constraint is semantically a no-op — skip it so
    the degenerate program is bit-for-bit the single-device one
    (tests/test_shard_spf.py::test_one_device_mesh_matches_plain_path)."""
    if mesh.size == 1:
        return out
    spec = NamedSharding(mesh, P("batch"))
    return jax.tree.map(
        lambda x: jax.lax.with_sharding_constraint(x, spec), out
    )


def sharded_whatif_step(
    mesh: Mesh, max_iters: int | None = None, engine: str = "seq"
):
    """Jitted batched-SPF step with mesh-sharded inputs/outputs.

    This is the framework's "training step" analog: the full batched
    computation (distances, DAG, hops, ECMP next-hop masks) for a sharded
    scenario batch over a sharded graph, one XLA program, collectives
    inserted by GSPMD.  ``TpuSpfBackend`` builds its production sharded
    dispatch from the same :func:`sharded_whatif_jit` /
    :func:`shard_scenarios` pieces.
    """
    step = sharded_whatif_jit(mesh, max_iters, engine)

    def run(g: DeviceGraph, root: int, edge_masks: np.ndarray):
        return step(g, root, shard_scenarios(mesh, edge_masks))

    return run


def sharded_whatif_jit(
    mesh: Mesh, max_iters: int | None = None, engine: str = "seq"
):
    """The jitted sharded what-if program (masks already placed)."""

    @jax.jit
    def step(g: DeviceGraph, root, edge_masks):
        out = spf_whatif_batch(g, root, edge_masks, max_iters, engine=engine)
        return constrain_batch(mesh, out)

    return step


def replicated_sharding(mesh: Mesh):
    """A fully-replicated NamedSharding (the fallback placement for
    partition batches that do not divide the batch axis)."""
    return NamedSharding(mesh, P())


def shard_part_planes(mesh: Mesh, planes):
    """Place stacked partitioned-SPF planes (ISSUE 15) with the
    partition axis sharded over ``batch`` — the same axis the what-if
    scenario batch rides; every lane is an independent small program,
    so GSPMD fans the partition set across the batch devices.  The
    caller guarantees the partition axis divides the batch axis."""

    def put(x):
        spec = P(*(("batch",) + (None,) * (x.ndim - 1)))
        return jax.device_put(np.asarray(x), NamedSharding(mesh, spec))

    return jax.tree.map(put, planes)


def constrain_parts(mesh: Mesh, out):
    """Pin a partitioned-solve result pytree's leading (partition) axis
    to the batch sharding — the partition edition of
    :func:`constrain_batch` (no-op on a 1-device mesh)."""
    return constrain_batch(mesh, out)


def sharded_multipath_jit(mesh: Mesh, kp: int, max_iters: int | None = None):
    """Sharded multipath what-if (ISSUE 10): the scenario batch rides
    the same batch axis, the parent-set / weight planes ride the
    result pytree — one program per (mesh, kp)."""
    from holo_tpu.ops.spf_engine import spf_multipath_batch

    @jax.jit
    def step(g: DeviceGraph, root, edge_masks):
        sp, mp = spf_multipath_batch(g, root, edge_masks, kp, max_iters)
        return constrain_batch(mesh, sp), constrain_batch(mesh, mp)

    return step


# -- jaxpr-audit registrations (HL3xx) ----------------------------------
# The per-mesh builders above are the fence-bearing seams: under a
# multi-device mesh every output is pinned through constrain_batch, and
# HL305 proves the pin survives to the lowered jaxpr as real
# sharding_constraint eqns.  Thunks run only when the audit arms (the
# audit passes its own >=2-device CPU mesh).
from holo_tpu.analysis.kernels import register_kernel as _register_kernel  # noqa: E402


def _audit_mesh_specs():
    from holo_tpu.ops.spf_engine import audit_graph_spec
    from holo_tpu.ops.tropical import audit_tiles_spec
    import jax.numpy as jnp

    s = jax.ShapeDtypeStruct
    b, e, rr = 8, 128, 8
    return {
        "g": audit_graph_spec(),
        "tt": audit_tiles_spec(),
        "root": s((), jnp.int32),
        "roots": s((b,), jnp.int32),
        "mask": s((e,), jnp.bool_),
        "masks": s((b, e), jnp.bool_),
        "rr": s((rr,), jnp.int32),
        "rrs": s((b, rr), jnp.int32),
    }


_register_kernel(
    "spf.shard.whatif",
    builder=lambda mesh: sharded_whatif_jit(mesh, None, "seq"),
    specs=lambda: (
        lambda a: (a["g"], a["root"], a["masks"])
    )(_audit_mesh_specs()),
    fences=1,
    needs_mesh=True,
    buckets=16,  # pow2 scenario lanes x mesh identities
)

_register_kernel(
    "spf.shard.multipath.k2",
    builder=lambda mesh: sharded_multipath_jit(mesh, 2, None),
    specs=lambda: (
        lambda a: (a["g"], a["root"], a["masks"])
    )(_audit_mesh_specs()),
    fences=1,
    needs_mesh=True,
    buckets=32,
)

_register_kernel(
    "spf.shard.tropical.whatif",
    builder=lambda mesh: sharded_tropical_whatif_jit(mesh, None),
    specs=lambda: (
        lambda a: (a["g"], a["tt"], a["root"], a["masks"], a["rrs"])
    )(_audit_mesh_specs()),
    fences=1,
    needs_mesh=True,
    buckets=32,
)

_register_kernel(
    "spf.shard.tropical.multiroot",
    builder=lambda mesh: sharded_tropical_multiroot_jit(mesh, None),
    specs=lambda: (
        lambda a: (a["g"], a["tt"], a["roots"], a["mask"], a["rr"])
    )(_audit_mesh_specs()),
    fences=1,
    needs_mesh=True,
    buckets=32,
)
