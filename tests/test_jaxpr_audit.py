"""HL3xx jaxpr kernel audit (ISSUE 18): golden fixtures per rule,
seeded mutations of real seams, registry inertness, the per-kernel
cache, and the repo-wide audit-clean gate.

The fixtures build :class:`KernelSpec` rows by hand and drive
``audit_kernel``/``audit_entries`` directly — no registry, no cache —
so each rule's fire/clean/suppressed behavior is proven in isolation.
The mutation tests then take REAL registered kernels and break exactly
one declared contract (drop a donation, unfence a mesh carry, widen a
lane, unbound the bucket budget), proving the audit catches the defect
classes it was built for on the production kernels themselves.
"""

import dataclasses
import json
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from holo_tpu.analysis import gate_findings, run_audit_cached
from holo_tpu.analysis.kernels import KernelSpec, register_kernel, registry
from holo_tpu.analysis.jaxpr_audit import (
    SEAM_MODULES,
    _audit_mesh,
    apply_suppressions,
    audit_entries,
    audit_kernel,
    load_registry,
    run_audit,
    spec_signature,
)

REPO = Path(__file__).resolve().parent.parent


def _spec(shape=(64,), dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _entry(name, builder, specs, **kw):
    kw.setdefault("buckets", 1)
    kw.setdefault("module", "fixture_mod.py")
    kw.setdefault("line", 3)
    return KernelSpec(name=name, builder=builder, specs=specs, **kw)


def _rules_fired(entry, mesh=None):
    findings, wall = audit_kernel(entry, mesh=mesh)
    assert wall >= 0.0
    return {f.rule for f in findings}, findings


# -- golden fixtures: one flagged + one clean per rule ------------------


def test_clean_kernel_produces_no_findings():
    entry = _entry(
        "fix.clean",
        lambda: jax.jit(lambda x: x + 1, donate_argnums=(0,)),
        lambda: (_spec(),),
        donate=(0,),
    )
    fired, findings = _rules_fired(entry)
    assert fired == set(), [f.render() for f in findings]


def test_hl301_dropped_donation_fires():
    # Mutation shape #1: the wrapper forgets donate_argnums while the
    # registration still declares the donation.
    entry = _entry(
        "fix.donation.dropped",
        lambda: jax.jit(lambda x: x + 1),  # no donate_argnums
        lambda: (_spec(),),
        donate=(0,),
    )
    fired, findings = _rules_fired(entry)
    assert fired == {"HL301"}
    (f,) = findings
    assert f.severity == "error"
    assert "0/1" in f.message


def test_hl301_donated_but_unused_arg_fires():
    # The true-positive class this PR fixed in the incremental
    # multipath seams: a donated argument the kernel never reads is
    # pruned before XLA, so its alias can never realize — the buffer
    # is neither reused nor reclaimed.
    entry = _entry(
        "fix.donation.unused",
        lambda: jax.jit(lambda a, b: a + 1, donate_argnums=(1,)),
        lambda: (_spec(), _spec()),
        donate=(1,),
    )
    fired, _ = _rules_fired(entry)
    assert fired == {"HL301"}


def test_hl301_partial_pytree_donation_counts_leaves():
    # Two donated leaves, only one realized: the finding reports the
    # leaf count, not just the argnum.
    entry = _entry(
        "fix.donation.partial",
        lambda: jax.jit(
            lambda pair: pair[0] + 1, donate_argnums=(0,)
        ),
        lambda: ((_spec(), _spec()),),
        donate=(0,),
    )
    fired, findings = _rules_fired(entry)
    assert fired == {"HL301"}
    assert "1/2" in findings[0].message


def test_hl302_host_callback_fires():
    def kernel(x):
        jax.debug.print("leak {}", x[0])
        return x + 1

    entry = _entry("fix.hostleak", lambda: jax.jit(kernel), lambda: (_spec(),))
    fired, findings = _rules_fired(entry)
    assert fired == {"HL302"}
    assert findings[0].severity == "error"
    assert "debug_print" in findings[0].message


def test_hl302_pure_callback_fires():
    def kernel(x):
        return jax.pure_callback(
            lambda v: v, jax.ShapeDtypeStruct((64,), jnp.int32), x
        )

    entry = _entry("fix.purecb", lambda: jax.jit(kernel), lambda: (_spec(),))
    fired, _ = _rules_fired(entry)
    assert "HL302" in fired


def test_hl303_float_mean_in_uint32_plane_fires():
    # Mutation shape #3: a stray jnp.mean in the saturating-uint32
    # plane silently widens to float32.
    entry = _entry(
        "fix.widen",
        lambda: jax.jit(lambda x: (x + jnp.uint32(1), jnp.mean(x))),
        lambda: (_spec(dtype=jnp.uint32),),
    )
    fired, findings = _rules_fired(entry)
    assert fired == {"HL303"}
    (f,) = findings
    assert f.severity == "warn"
    assert "float32" in f.message


def test_hl303_respects_widened_declaration():
    # The same kernel is clean when the registration widens the
    # discipline explicitly (e.g. the FRR SRLG plane's float scoring).
    entry = _entry(
        "fix.widen.ok",
        lambda: jax.jit(lambda x: (x + jnp.uint32(1), jnp.mean(x))),
        lambda: (_spec(dtype=jnp.uint32),),
        dtypes=("int32", "uint32", "bool", "float32"),
    )
    fired, _ = _rules_fired(entry)
    assert fired == set()


def test_hl304_unbounded_buckets_fires():
    # Mutation shape #4: a dispatch seam with no declared shape-bucket
    # bound — unbounded recompiles.
    entry = _entry(
        "fix.unbounded",
        lambda: jax.jit(lambda x: x + 1),
        lambda: (_spec(),),
        buckets=None,
    )
    fired, findings = _rules_fired(entry)
    assert fired == {"HL304"}
    assert "unbounded" in findings[0].message


def test_hl304_over_budget_fires():
    entry = _entry(
        "fix.overbudget",
        lambda: jax.jit(lambda x: x + 1),
        lambda: (_spec(),),
        buckets=80,
        budget=64,
    )
    fired, findings = _rules_fired(entry)
    assert fired == {"HL304"}
    assert "80" in findings[0].message


def test_hl305_missing_fence_fires():
    entry = _entry(
        "fix.unfenced",
        lambda: jax.jit(lambda x: x + 1),
        lambda: (_spec(),),
        fences=1,
    )
    fired, findings = _rules_fired(entry)
    assert fired == {"HL305"}
    assert findings[0].severity == "warn"


def test_hl305_realized_fence_is_clean():
    import numpy as np

    devices = jax.devices()
    if len(devices) < 2:
        pytest.skip("needs >=2 CPU devices (conftest forces 8)")
    mesh = jax.sharding.Mesh(np.array(devices), ("d",))
    sharding = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec("d")
    )

    entry = _entry(
        "fix.fenced",
        lambda: jax.jit(
            lambda x: jax.lax.with_sharding_constraint(x + 1, sharding)
        ),
        lambda: (_spec((len(devices) * 8,)),),
        fences=1,
    )
    fired, _ = _rules_fired(entry)
    assert fired == set()


def test_hl305_mesh_needing_kernel_skipped_without_mesh():
    entry = _entry(
        "fix.meshonly",
        lambda mesh: jax.jit(lambda x: x + 1),
        lambda: (_spec(),),
        fences=1,
        needs_mesh=True,
    )
    per_kernel, seconds, skipped = audit_entries([entry], mesh=None)
    assert skipped == ["fix.meshonly"]
    assert per_kernel == {} and seconds == {}


# -- suppression flow ---------------------------------------------------


def test_audit_findings_honor_disable_comments(tmp_path):
    mod = tmp_path / "fixture_mod.py"
    mod.write_text(
        "# fixture seam module\n"
        "# holo-lint: disable=HL304\n"
        "register_kernel_call_site = None\n"
    )
    entry = _entry(
        "fix.suppressed",
        lambda: jax.jit(lambda x: x + 1),
        lambda: (_spec(),),
        buckets=None,  # fires HL304...
        line=3,  # ...anchored under the disable comment on line 2
    )
    findings, _ = audit_kernel(entry)
    live, suppressed = apply_suppressions(findings, str(tmp_path))
    assert live == []
    assert [f.rule for f in suppressed] == ["HL304"]

    # A different rule id on the same line stays live.
    other = dataclasses.replace(entry, name="fix.other", fences=1)
    findings, _ = audit_kernel(other)
    live, suppressed = apply_suppressions(
        [f for f in findings if f.rule == "HL305"], str(tmp_path)
    )
    assert [f.rule for f in live] == ["HL305"]
    assert suppressed == []


# -- seeded mutations of REAL registered kernels ------------------------


def test_mutation_real_incremental_kernel_without_donation():
    # Take the production incremental seam and rebuild its jit WITHOUT
    # donate_argnums: the audit must flag the dropped donation.
    from holo_tpu.ops.spf_engine import spf_one_incremental

    entry = load_registry()["spf.one.incremental"]
    mutated = dataclasses.replace(
        entry,
        builder=lambda: jax.jit(
            lambda g, r, prev, seeds: spf_one_incremental(
                g, r, prev, seeds, None
            )
        ),
    )
    findings, _ = audit_kernel(mutated)
    assert {f.rule for f in findings} == {"HL301"}


def test_mutation_real_sharded_kernel_without_fence():
    # Replace the sharded what-if builder with the UNfenced plain batch
    # kernel (the PR-13 GSPMD miscompile shape): HL305 must fire.
    from holo_tpu.ops.spf_engine import spf_whatif_batch

    mesh = _audit_mesh()
    if mesh is None:
        pytest.skip("needs a multi-device CPU mesh (conftest forces 8)")
    entry = load_registry()["spf.shard.whatif"]
    mutated = dataclasses.replace(
        entry,
        builder=lambda m: jax.jit(
            lambda g, r, ms: spf_whatif_batch(g, r, ms, None, engine="seq")
        ),
    )
    findings, _ = audit_kernel(mutated, mesh=mesh)
    assert "HL305" in {f.rule for f in findings}


def test_mutation_real_kernel_with_unbounded_buckets():
    entry = load_registry()["spf.tropical.one"]
    mutated = dataclasses.replace(entry, buckets=None)
    findings, _ = audit_kernel(mutated)
    assert {f.rule for f in findings} == {"HL304"}


# -- registry: inert outside audit mode ---------------------------------


def _restore_registry(saved):
    from holo_tpu.analysis import kernels

    kernels._REGISTRY.clear()
    kernels._REGISTRY.update(saved)


def test_registration_never_invokes_thunks():
    saved = registry()

    def boom(*a, **k):  # pragma: no cover - the assertion IS the test
        raise AssertionError("audit thunk invoked outside audit mode")

    try:
        register_kernel("test.inert", builder=boom, specs=boom, buckets=1)
        entry = registry()["test.inert"]
        assert entry.builder is boom
        assert entry.specs is boom
        # The call site anchors like an AST finding would.
        assert entry.module == "tests/test_jaxpr_audit.py"
        assert entry.line > 0
    finally:
        _restore_registry(saved)


def test_register_decorator_form_and_overwrite():
    saved = registry()
    try:

        @register_kernel("test.deco", specs=lambda: (), buckets=1)
        def build():  # pragma: no cover - never invoked
            raise AssertionError("invoked")

        assert registry()["test.deco"].builder is build
        assert registry()["test.deco"].module == "tests/test_jaxpr_audit.py"

        # Re-registration under the same name overwrites (idempotent
        # module re-imports).
        register_kernel(
            "test.deco", builder=build, specs=lambda: (), buckets=2
        )
        assert registry()["test.deco"].buckets == 2
    finally:
        _restore_registry(saved)


def test_every_seam_module_registers_kernels():
    entries = load_registry()
    assert len(entries) >= 30
    by_module = {e.module for e in entries.values()}
    for mod in SEAM_MODULES:
        rel = mod.replace(".", "/") + ".py"
        assert rel in by_module, f"no kernels registered from {rel}"
    # Every anchor points at a real line of a real file.
    for e in entries.values():
        src = (REPO / e.module).read_text().splitlines()
        assert 0 < e.line <= len(src), (e.name, e.module, e.line)


def test_spec_signature_is_stable_and_contract_sensitive():
    entries = load_registry()
    entry = entries["spf.one.incremental"]
    assert spec_signature(entry) == spec_signature(entry)
    widened = dataclasses.replace(entry, donate=())
    assert spec_signature(widened) != spec_signature(entry)
    rebudgeted = dataclasses.replace(entry, buckets=8)
    assert spec_signature(rebudgeted) != spec_signature(entry)


# -- the repo-wide gate -------------------------------------------------


def test_repo_audit_error_tier_is_clean():
    """ISSUE 18 acceptance: every registered kernel lowers and passes
    HL301/HL302 with the error-tier baseline kept empty."""
    result = run_audit_cached(REPO)
    assert result.kernels_checked >= 30
    assert result.skipped == [], result.skipped
    errors = gate_findings(result.findings)
    assert errors == [], "\n".join(f.render() for f in errors)


def test_repo_audit_currently_warn_clean():
    # Not a permanent contract (HL303/304/305 soak at warn), but today
    # the tree is fully clean — a new warn finding should be a
    # deliberate decision, not drift.
    result = run_audit_cached(REPO)
    assert result.findings == [], "\n".join(
        f.render() for f in result.findings
    )


# -- the per-kernel cache -----------------------------------------------


def test_audit_cache_cold_then_warm(tmp_path):
    cache = tmp_path / "audit_cache.json"
    cold = run_audit_cached(REPO, cache_path=cache, no_cache=False)
    assert cache.exists()
    assert cold.kernels_checked >= 30

    warm = run_audit_cached(REPO, cache_path=cache)
    assert warm.kernels_cached == warm.kernels_checked == (
        cold.kernels_checked
    )
    assert [f.render() for f in warm.findings] == [
        f.render() for f in cold.findings
    ]
    assert set(warm.kernel_seconds) == set(cold.kernel_seconds)


def test_audit_cache_no_cache_bypasses_read_and_write(tmp_path):
    cache = tmp_path / "audit_cache.json"
    run_audit_cached(REPO, cache_path=cache)
    before = cache.read_bytes()
    fresh = run_audit_cached(REPO, cache_path=cache, no_cache=True)
    assert fresh.kernels_cached == 0  # full re-lowering
    assert cache.read_bytes() == before  # and no rewrite


def test_audit_cache_per_kernel_fingerprint_reuse(tmp_path):
    """Corrupt ONE kernel's fingerprint in the cache document and break
    the fully-warm fast path: only that kernel re-lowers; the rest
    replay from their per-kernel rows."""
    cache = tmp_path / "audit_cache.json"
    run_audit_cached(REPO, cache_path=cache)
    doc = json.loads(cache.read_text())
    victim = sorted(doc["kernels"])[0]
    doc["kernels"][victim]["fingerprint"] = "stale"
    # Invalidate a recorded file stat so the warm fast path falls
    # through to the armed (fingerprint-checking) path.
    a_file = sorted(doc["files"])[0]
    doc["files"][a_file]["mtime_ns"] = 1
    doc["files"][a_file]["size"] = 1
    doc["files"][a_file]["sha256"] = "not-the-real-hash"
    cache.write_text(json.dumps(doc))

    result = run_audit_cached(REPO, cache_path=cache)
    assert result.kernels_cached == result.kernels_checked - 1


def test_warm_audit_replay_never_imports_jax():
    """The fully-warm path must stay jax-free: that is what keeps the
    warm lint gate near the AST-only wall time."""
    # Warm the default cache (what the gate itself uses).
    run_audit_cached(REPO)
    probe = (
        "import sys\n"
        "from pathlib import Path\n"
        "from holo_tpu.analysis import run_audit_cached\n"
        f"res = run_audit_cached(Path({str(REPO)!r}))\n"
        "assert res.kernels_checked >= 30, res.kernels_checked\n"
        "assert res.kernels_cached == res.kernels_checked\n"
        "assert 'jax' not in sys.modules, 'warm replay imported jax'\n"
        "print('ok')\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", probe],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "ok" in proc.stdout


def test_self_check_audit_arm_is_faithful():
    from holo_tpu.analysis import self_check

    mismatches = self_check([REPO / "holo_tpu"], root=REPO, audit=True)
    assert not mismatches, "\n".join(mismatches)
