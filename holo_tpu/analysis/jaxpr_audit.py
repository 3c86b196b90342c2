"""Jaxpr-level kernel-contract audit (HL3xx family).

Lowers every kernel registered in :mod:`holo_tpu.analysis.kernels`
*abstractly* — CPU platform, ``ShapeDtypeStruct`` args, transfer guard
armed, no device, no data — and proves the declared contracts on the
compiled IR:

* **HL301** donation-not-realized: declared ``donate_argnums`` leaves that
  never became ``input_output_aliases`` in the lowered module.
* **HL302** host-leak-in-kernel: host round-trip primitives
  (``pure_callback``/``io_callback``/``debug_callback``/``device_put``/
  infeed/outfeed) inside the jaxpr.
* **HL303** dtype-widening: eqn outputs outside the kernel's declared
  dtype lanes (int64 / float / weak promotion in the saturating-uint32
  plane).
* **HL304** compile-signature budget: unbounded-shape dispatch seams or
  bucket counts beyond the recompile budget.
* **HL305** fence-realized: fewer ``sharding_constraint`` eqns than the
  kernel declares for its per-mesh fences.

The audit never probes an accelerator: the platform is pinned to CPU
before JAX initializes (or forced via config if JAX is already up) and
lowering runs under ``jax.transfer_guard("disallow")`` so any attempt to
materialize a real buffer raises instead of touching a device.

Findings are ordinary :class:`~holo_tpu.analysis.core.Finding` rows that
anchor at the ``register_kernel`` call site of the owning module, so the
baseline ratchet, suppression comments, and the suppression-rot audit all
work unchanged.
"""

from __future__ import annotations

import os
import sys
import time
import warnings
from dataclasses import dataclass, field
from typing import Dict, Iterable, Iterator, List, Optional, Tuple

from holo_tpu.analysis.core import Finding, parse_suppressions
from holo_tpu.analysis.kernels import KernelSpec, registry

__all__ = [
    "AuditResult",
    "SEAM_MODULES",
    "apply_suppressions",
    "audit_entries",
    "audit_kernel",
    "load_registry",
    "run_audit",
    "spec_signature",
]

#: Modules that own jit-construction seams; importing them populates the
#: registry (each calls ``register_kernel`` at import time).  The audit cache
#: hashes this file, so editing the list invalidates cached results.
SEAM_MODULES: Tuple[str, ...] = (
    "holo_tpu.ops.spf_engine",
    "holo_tpu.ops.tropical",
    "holo_tpu.ops.partition",
    "holo_tpu.ops.bgp_table",
    "holo_tpu.parallel.mesh",
    "holo_tpu.spf.backend",
    "holo_tpu.frr.manager",
)

#: Primitive names that mean a host round-trip inside a kernel body.
HOST_PRIMITIVES = frozenset(
    {
        "pure_callback",
        "io_callback",
        "debug_callback",
        "debug_print",  # what jax.debug.print lowers to (JAX 0.9)
        "callback",
        "device_put",
        "infeed",
        "outfeed",
    }
)

#: Marker the StableHLO lowering puts on parameters whose donation was
#: realized as an input/output alias.
_ALIAS_MARKERS = ("tf.aliasing_output", "jax.buffer_donor")


@dataclass
class AuditResult:
    """Outcome of one audit pass over the kernel registry."""

    findings: List[Finding] = field(default_factory=list)
    suppressed: List[Finding] = field(default_factory=list)
    kernel_seconds: Dict[str, float] = field(default_factory=dict)
    #: Raw (pre-suppression) findings per kernel — what the cache stores.
    kernel_findings: Dict[str, List[Finding]] = field(default_factory=dict)
    kernels_checked: int = 0
    kernels_cached: int = 0
    skipped: List[str] = field(default_factory=list)
    device_count: int = 0


def _ensure_cpu() -> None:
    """Pin JAX to the host platform before anything can probe a device.

    If JAX has not been imported yet we can set the environment (platform
    + 8 virtual CPU devices so per-mesh fences are realizable); if it is
    already up we force the platform via config.  Either way the audit
    never initializes a TPU backend.
    """
    if "jax" not in sys.modules:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")


def load_registry() -> Dict[str, KernelSpec]:
    """Import every seam module (self-registering) and snapshot the registry."""
    _ensure_cpu()
    import importlib

    for mod in SEAM_MODULES:
        importlib.import_module(mod)
    return registry()


def spec_signature(entry: KernelSpec) -> str:
    """Stable signature of the canonical specs + declared contracts.

    Feeds the per-kernel cache fingerprint: changing a shape, dtype,
    donation, fence count, or bucket budget re-lowers just that kernel.
    """
    import jax

    rows = []
    for arg in entry.specs():
        leaves, treedef = jax.tree_util.tree_flatten(arg)
        rows.append(
            (
                str(treedef),
                [
                    (tuple(leaf.shape), str(leaf.dtype))
                    if hasattr(leaf, "shape") and hasattr(leaf, "dtype")
                    else repr(leaf)
                    for leaf in leaves
                ],
            )
        )
    return repr(
        (
            rows,
            entry.donate,
            entry.fences,
            entry.dtypes,
            entry.buckets,
            entry.budget,
            entry.needs_mesh,
        )
    )


def _iter_eqns(jaxpr) -> Iterator:
    """Walk every eqn, descending into scan/while/cond/pjit sub-jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in _sub_jaxprs(val):
                yield from _iter_eqns(sub)


def _sub_jaxprs(val) -> Iterator:
    inner = getattr(val, "jaxpr", None)
    if inner is not None and hasattr(inner, "eqns"):
        yield inner
    elif hasattr(val, "eqns"):
        yield val
    elif isinstance(val, (tuple, list)):
        for item in val:
            yield from _sub_jaxprs(item)


def _finding(entry: KernelSpec, rule: str, severity: str, message: str) -> Finding:
    return Finding(
        rule=rule,
        path=entry.module,
        line=entry.line,
        context=f"kernel:{entry.name}",
        message=message,
        severity=severity,
    )


def _severities() -> Dict[str, str]:
    from holo_tpu.analysis import rules_jaxpr

    return {cls.id: cls.severity for cls in rules_jaxpr.RULES}


def audit_kernel(entry: KernelSpec, mesh=None) -> Tuple[List[Finding], float]:
    """Lower one registered kernel abstractly and check HL301-HL305.

    Returns the findings plus the wall seconds the lowering took.  All JAX
    work happens under the transfer guard so a kernel that tries to
    materialize a real buffer fails loudly instead of silently probing a
    device.
    """
    import jax

    sev = _severities()
    findings: List[Finding] = []
    t0 = time.perf_counter()

    # HL304 is pure metadata — check it before spending any lowering time.
    if entry.buckets is None:
        findings.append(
            _finding(
                entry,
                "HL304",
                sev["HL304"],
                "dispatch seam declares no static shape-bucket bound "
                "(unbounded-shape args => unbounded recompiles); register "
                "buckets=<n> from the tuner/pow2 quantization",
            )
        )
    elif entry.buckets > entry.budget:
        findings.append(
            _finding(
                entry,
                "HL304",
                sev["HL304"],
                f"dispatch seam enumerates {entry.buckets} shape buckets, "
                f"over the compile-signature budget of {entry.budget}",
            )
        )

    donation_warning = False
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        with jax.transfer_guard("disallow"):
            jitted = entry.builder(mesh) if entry.needs_mesh else entry.builder()
            specs = entry.specs()
            traced = jitted.trace(*specs)
            jaxpr = traced.jaxpr
            lowered = traced.lower()
    for w in caught:
        if "donated" in str(w.message).lower():
            donation_warning = True

    # HL301: every donated leaf must surface as an input/output alias in
    # the lowered module text.
    expected = sum(
        len(jax.tree_util.tree_leaves(specs[i]))
        for i in entry.donate
        if i < len(specs)
    )
    if expected:
        text = lowered.as_text()
        realized = sum(text.count(marker) for marker in _ALIAS_MARKERS)
        if realized < expected or donation_warning:
            findings.append(
                _finding(
                    entry,
                    "HL301",
                    sev["HL301"],
                    f"declared donate_argnums={entry.donate} but only "
                    f"{realized}/{expected} donated leaves realized as "
                    "input_output_aliases in the lowered kernel (donation "
                    "is silently dropped; note_donated poison never fires)",
                )
            )

    closed = getattr(jaxpr, "jaxpr", jaxpr)
    prim_names: List[str] = []
    bad_dtypes: Dict[str, str] = {}
    fence_eqns = 0
    allowed = set(entry.dtypes)
    for eqn in _iter_eqns(closed):
        name = eqn.primitive.name
        prim_names.append(name)
        if name == "sharding_constraint":
            fence_eqns += 1
        for ov in eqn.outvars:
            aval = getattr(ov, "aval", None)
            dtype = getattr(aval, "dtype", None)
            if dtype is None:
                continue
            ds = str(dtype)
            if ds not in allowed and ds not in bad_dtypes:
                bad_dtypes[ds] = name

    # HL302: host round-trips in the kernel body.
    leaks = sorted(set(prim_names) & HOST_PRIMITIVES)
    if leaks:
        findings.append(
            _finding(
                entry,
                "HL302",
                sev["HL302"],
                "host-transfer primitive(s) inside dispatch-scope kernel: "
                + ", ".join(leaks),
            )
        )

    # HL303: widened lanes.
    if bad_dtypes:
        detail = ", ".join(
            f"{dt} (from `{prim}`)" for dt, prim in sorted(bad_dtypes.items())
        )
        findings.append(
            _finding(
                entry,
                "HL303",
                sev["HL303"],
                f"eqn output lanes outside declared dtypes {entry.dtypes}: "
                + detail,
            )
        )

    # HL305: declared fences must appear as sharding_constraint eqns.  Only
    # meaningful when the kernel was built against a real multi-device mesh
    # (the fences legitimately no-op on a 1-device mesh).
    if entry.fences and (not entry.needs_mesh or mesh is not None):
        if fence_eqns < entry.fences:
            findings.append(
                _finding(
                    entry,
                    "HL305",
                    sev["HL305"],
                    f"kernel declares {entry.fences} sharding fence(s) but "
                    f"the lowered jaxpr contains {fence_eqns} "
                    "sharding_constraint eqn(s)",
                )
            )

    return findings, time.perf_counter() - t0


def _audit_mesh():
    """Multi-device CPU mesh for fence-bearing kernels (None if 1 device)."""
    import jax

    devices = jax.devices()
    if len(devices) < 2:
        return None
    from holo_tpu.parallel.mesh import make_spf_mesh

    return make_spf_mesh(devices=devices)


def apply_suppressions(
    findings: Iterable[Finding], root: str
) -> Tuple[List[Finding], List[Finding]]:
    """Split audit findings into (live, suppressed) using the same
    ``# holo-lint: disable=`` comments (same line or line above) the AST
    rules honor.  Reads each registering module's source once."""
    cache: Dict[str, Dict[int, set]] = {}
    live: List[Finding] = []
    suppressed: List[Finding] = []
    for f in findings:
        if f.path not in cache:
            try:
                with open(os.path.join(root, f.path), encoding="utf-8") as fh:
                    cache[f.path] = parse_suppressions(fh.read())
            except OSError:
                cache[f.path] = {}
        sup = cache[f.path]
        hit = False
        for line in (f.line, f.line - 1):
            ids = sup.get(line)
            if ids and ("all" in ids or f.rule in ids):
                hit = True
                break
        (suppressed if hit else live).append(f)
    return live, suppressed


def audit_entries(
    entries: Iterable[KernelSpec], mesh=None
) -> Tuple[Dict[str, List[Finding]], Dict[str, float], List[str]]:
    """Audit an explicit entry list (no registry, no cache, no suppression
    pass) — the building block both for ``run_audit`` and for fixture tests.

    Returns (per-kernel findings, per-kernel wall seconds, skipped kernel
    names).  Mesh-needing kernels are skipped (with a note) when no
    multi-device mesh is available rather than audited against a
    fence-eliding mesh.
    """
    per_kernel: Dict[str, List[Finding]] = {}
    seconds: Dict[str, float] = {}
    skipped: List[str] = []
    for entry in entries:
        if entry.needs_mesh and mesh is None:
            skipped.append(entry.name)
            continue
        rows, dt = audit_kernel(entry, mesh=mesh)
        per_kernel[entry.name] = rows
        seconds[entry.name] = dt
    return per_kernel, seconds, skipped


def run_audit(
    root: str,
    names: Optional[Iterable[str]] = None,
    reuse: Optional[Dict[str, dict]] = None,
) -> AuditResult:
    """Arm JAX (CPU-pinned), audit every registered kernel, and apply
    suppressions.

    ``reuse`` maps kernel name -> ``{"findings": [...], "seconds": s}`` rows
    the cache layer validated by fingerprint; those kernels skip lowering
    and replay their stored findings.  Findings come back sorted the same
    way ``run_sources`` sorts AST findings so merged output is stable.
    """
    _ensure_cpu()
    import jax

    entries = load_registry()
    if names is not None:
        wanted = set(names)
        entries = {k: v for k, v in entries.items() if k in wanted}

    mesh = _audit_mesh()
    result = AuditResult(device_count=len(jax.devices()))

    fresh: List[KernelSpec] = []
    for name in sorted(entries):
        entry = entries[name]
        row = (reuse or {}).get(name)
        if row is not None:
            result.kernel_findings[name] = list(row["findings"])
            result.kernel_seconds[name] = row.get("seconds", 0.0)
            result.kernels_cached += 1
        else:
            fresh.append(entry)
    per_kernel, seconds, skipped = audit_entries(fresh, mesh=mesh)
    result.kernel_findings.update(per_kernel)
    result.kernel_seconds.update(seconds)
    result.skipped = skipped
    result.kernels_checked = len(entries) - len(skipped)

    raw: List[Finding] = []
    for name in sorted(result.kernel_findings):
        raw.extend(result.kernel_findings[name])

    live, suppressed = apply_suppressions(raw, root)
    result.findings = sorted(live, key=lambda f: (f.path, f.line, f.rule))
    result.suppressed = sorted(
        suppressed, key=lambda f: (f.path, f.line, f.rule)
    )
    return result
