#!/usr/bin/env python
"""Batched full-SPF throughput on the device against the scalar CPU.

Workloads (BASELINE.md):

- 10k-vertex fat-tree LSDB, 512-scenario what-if batch (configs 1/5):
  full SPF (distances + first-parent + hops + 64-way ECMP next-hop
  bitmasks) on the ELL gather engines (ops/spf_engine.py) against the
  serial C++ candidate-list Dijkstra (native/spf_baseline.cpp).
- 50k-vertex fat-tree (the BASELINE.md target scale), gather engines.
- OSPFv3 multi-area + IS-IS L1/L2 protocol-marshaled rows (configs
  2/3): topologies extracted through the real instance marshal paths
  (spf/synth_proto.py), parity-gated per area/level.
- p50 latency: small-batch gather run + C++ single-run p50.

No row in the record has run on a TPU yet (ROADMAP S1); the first
proof that the served path runs there is ``chip_smoke.py``.

Every stage runs in a SUBPROCESS with a hard timeout, one after the
other, and this parent process never imports JAX: a chip belongs to
one process at a time.  A stage child without ``--cpu`` refuses to run
unless ``jax.devices()[0].platform == "tpu"``.  Parity vs the C++
scalar is a gate on every row.

Prints exactly one JSON line (the driver records the LAST line):
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "extra": {...}}
and exits non-zero when no headline row came back ok.
"""

from __future__ import annotations

import json
import subprocess
import sys
import time

import numpy as np

_GATHER_ENGINES = ("seq", "fused", "packed", "hybrid")
# Stages that pin a virtual CPU mesh (or CPU grandchildren) by design:
# they never touch the chip and say so in their row.
_CPU_PINNED = ("shard_spf", "sharding_overhead", "audit_overhead")

STAGE_TIMEOUT = {
    "gather10k": 1500,
    "blocked10k": 900,
    "latency": 600,
    "scale50k": 1500,
    "scale50k_packed": 1200,
    "scale50k_fused": 1200,
    "scale50k_hybrid": 1200,
    "scale50k_b256": 1500,
    "whatif1024": 900,
    "cspf10k": 900,
    "cpu100": 300,
    "cpubaseline": 600,
    "ospfv3_multiarea": 1200,
    "isis_l1l2": 1200,
    "frr_batch": 900,
    "telemetry_overhead": 900,
    "fallback_overhead": 900,
    "profiling_overhead": 900,
    "convergence_storm": 1800,
    "convergence_overhead": 900,
    "delta_spf": 900,
    "incremental_overhead": 900,
    "shard_spf": 1200,
    "sharding_overhead": 900,
    "pipeline_spf": 1800,
    "pipeline_overhead": 900,
    "overload_storm": 1800,
    "overload_overhead": 900,
    "multipath_spf": 1200,
    "multipath_overhead": 900,
    "gnmi_fanout": 1500,
    "fanout_overhead": 900,
    "device_trace": 600,
    "explain_spf": 1500,
    "observatory_overhead": 900,
    "tropical_spf": 1500,
    "partitioned_spf": 1500,
    "bgp_table": 1500,
    "critical_path": 1800,
    "critpath_overhead": 900,
    "audit_overhead": 900,
    "slo_storm": 1800,
    "slo_overhead": 900,
}


def _cpu_baseline(topo, masks, runs):
    from holo_tpu.native_build import native_spf_batch_dist, spf_baseline_lib

    spf_baseline_lib()  # build/load outside the timed region
    times = []
    dists = []
    for i in range(runs):
        t0 = time.perf_counter()
        d = native_spf_batch_dist(topo, masks[i : i + 1])
        times.append(time.perf_counter() - t0)
        dists.append(d[0])
    total = sum(times)
    return np.stack(dists), runs / total, float(np.median(times) * 1e3)


def _make(k, n_scenarios, seed=0):
    from holo_tpu.spf.synth import fat_tree_topology, whatif_link_failure_masks

    topo = fat_tree_topology(k=k, seed=seed)
    masks = whatif_link_failure_masks(topo, n_scenarios, seed=1)
    return topo, masks


def _gather_run(topo, masks, cpu_runs=0, reps=3, n_atoms=64, engine="fused"):
    import jax

    from holo_tpu.ops.graph import build_ell
    from holo_tpu.ops.spf_engine import device_graph_from_ell, spf_whatif_batch

    B = masks.shape[0]
    g = jax.device_put(
        device_graph_from_ell(build_ell(topo, n_atoms=n_atoms))
    )
    masks_dev = jax.device_put(masks)
    step = jax.jit(
        lambda gr, ms: spf_whatif_batch(gr, topo.root, ms, engine=engine)
    )
    out = step(g, masks_dev)
    jax.block_until_ready(out)
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(step(g, masks_dev))
        times.append(time.perf_counter() - t0)
    dt = sum(times) / reps
    from holo_tpu import telemetry

    result = {
        "runs_per_sec": B / dt,
        "batch_ms": dt * 1e3,
        "engine": engine,
        "times_ms": [round(t * 1e3, 2) for t in times],
        # Explanatory signal riding the row: marshal cost + padded-slot
        # occupancy from the instrumented ELL path (holo_tpu.telemetry).
        "telemetry": telemetry.snapshot(prefix="holo_spf"),
    }
    if cpu_runs:
        cpu_dist, cpu_rps, cpu_p50 = _cpu_baseline(topo, masks, cpu_runs)
        check = np.asarray(out.dist[:cpu_runs])[:, : topo.n_vertices]
        result |= {
            "ok": bool(np.array_equal(check, cpu_dist)),
            "cpu_runs_per_sec": cpu_rps,
            "cpu_p50_ms": cpu_p50,
        }
    else:
        result["ok"] = True
    return result


def stage_gather10k(k, B, cpu_runs):
    """Sweep the gather-path fixpoint engines at 10k; report all,
    headline the fastest parity-ok one (compiles are cheap at this size)."""
    topo, masks = _make(k, B)
    rows = {}
    for engine in ("fused", "packed", "seq", "hybrid"):
        try:
            rows[engine] = _gather_run(topo, masks, cpu_runs, engine=engine)
        except Exception as e:  # noqa: BLE001 — report, keep sweeping
            rows[engine] = {"ok": False, "error": f"{type(e).__name__}: {e}"[:200]}
    best = max(
        (r for r in rows.values() if r.get("ok") and "runs_per_sec" in r),
        key=lambda r: r["runs_per_sec"],
        default={"ok": False, "error": "no engine succeeded"},
    )
    return best | {"sweep": rows}


def _blocked_run(topo, masks, cpu_runs=0, reps=3):
    import jax

    from holo_tpu.ops.blocked_spf import (
        failed_edges_perm,
        marshal_block_spf,
        whatif_spf_blocked,
    )

    B = masks.shape[0]
    g = marshal_block_spf(topo)
    fdst, fid = failed_edges_perm(np.asarray(g.orig2perm), topo, masks)
    step = jax.jit(lambda gr, fd, fi: whatif_spf_blocked(gr, fd, fi))
    fdst_d, fid_d = jax.device_put(fdst), jax.device_put(fid)
    out = step(g, fdst_d, fid_d)
    jax.block_until_ready(out)
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(step(g, fdst_d, fid_d))
        times.append(time.perf_counter() - t0)
    dt = sum(times) / reps
    result = {
        "runs_per_sec": B / dt,
        "batch_ms": dt * 1e3,
        "engine": "blocked",
        "batch": int(B),
        "blocks": int(g.w.shape[0]),
        "times_ms": [round(t * 1e3, 2) for t in times],
    }
    if cpu_runs:
        cpu_dist, cpu_rps, cpu_p50 = _cpu_baseline(topo, masks, cpu_runs)
        check = np.asarray(out.dist[:cpu_runs])
        result |= {
            "ok": bool(np.array_equal(check, cpu_dist)),
            "cpu_runs_per_sec": cpu_rps,
            "cpu_p50_ms": cpu_p50,
        }
    else:
        result["ok"] = True
    return result


def stage_blocked10k(k, B, cpu_runs):
    topo, masks = _make(k, B)
    return _blocked_run(topo, masks, cpu_runs)


def stage_latency(k, B):
    """Honest p50 rows: (a) time-to-answer for a B-scenario batch (every
    answer lands when the batch completes, so the batch wall IS the
    per-answer latency), (b) a true single-run (B=1) TPU SPF, and (c) the
    C++ scalar single-run p50 they compete with.
    """
    topo, masks = _make(k, B)
    r = _gather_run(topo, masks, cpu_runs=1, reps=7, engine="seq")
    single = _gather_run(topo, masks[:1], cpu_runs=0, reps=7, engine="seq")
    return {
        "ok": r["ok"],
        "p50_ms": float(np.median(r["times_ms"])),
        "amortized_per_answer_ms": float(np.median(r["times_ms"])) / B,
        "tpu_single_run_p50_ms": float(np.median(single["times_ms"])),
        "cpu_p50_ms": r["cpu_p50_ms"],
        "batch": B,
    }


def stage_whatif1024(k, cpu_runs):
    """BASELINE.md config 5 verbatim: 1024 concurrent link-failure SPFs
    vmapped over one 10k-node LSDB."""
    topo, masks = _make(k, 1024)
    return _gather_run(topo, masks, cpu_runs, engine="seq") | {"batch": 1024}


def stage_cspf10k(k, B):
    """BASELINE.md config 4: constrained SPF as masked batched SSSP —
    B TE path requests (affinity/bandwidth constraints) over the 10k
    LSDB in one device batch."""
    import numpy as np

    from holo_tpu.ops.cspf import Constraint, CspfEngine, LinkAttrs
    from holo_tpu.spf.synth import fat_tree_topology

    topo = fat_tree_topology(k=k, seed=0)
    rng = np.random.default_rng(7)
    attrs = LinkAttrs(
        affinity=rng.integers(0, 2**8, topo.n_edges, dtype=np.uint32),
        bandwidth=rng.uniform(1.0, 10.0, topo.n_edges),
    )
    eng = CspfEngine(topo, attrs)
    cons = [
        Constraint(
            exclude_any=int(rng.integers(0, 4)),
            min_bandwidth=float(rng.uniform(0.0, 2.0)),
        )
        for _ in range(B)
    ]
    dsts = [int(d) for d in rng.integers(0, topo.n_vertices, B)]
    t0 = time.perf_counter()
    paths = eng.compute(cons, dsts)  # includes host path extraction
    warm = time.perf_counter() - t0  # first call compiles
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        paths = eng.compute(cons, dsts)
        times.append(time.perf_counter() - t0)
    dt = sum(times) / len(times)
    found = sum(1 for p in paths if p.cost is not None)
    return {
        "ok": found > 0,
        "requests_per_sec": B / dt,
        "batch_ms": dt * 1e3,
        "paths_found": found,
        "batch": B,
        "compile_s": round(warm, 1),
    }


def stage_cpu100(runs=200):
    """BASELINE.md config 1: the 100-router single-area LSDB — full-SPF
    runs/sec + p50 on the scalar CPU reference (TPU only wins at scale;
    this row documents the small-LSDB floor it must not regress)."""
    from holo_tpu.spf.synth import random_ospf_topology

    topo = random_ospf_topology(
        n_routers=100, n_networks=20, extra_p2p=150, seed=3
    )
    masks = np.ones((runs, topo.n_edges), bool)
    _, cpu_rps, cpu_p50 = _cpu_baseline(topo, masks, runs)
    return {
        "ok": True,
        "cpu_runs_per_sec": cpu_rps,
        "cpu_p50_ms": cpu_p50,
        "n_vertices": int(topo.n_vertices),
    }


def stage_cpubaseline(k, runs):
    """C++ scalar baseline only (no JAX device needed)."""
    topo, masks = _make(k, runs)
    _, cpu_rps, cpu_p50 = _cpu_baseline(topo, masks, runs)
    return {
        "ok": True,
        "cpu_runs_per_sec": cpu_rps,
        "cpu_p50_ms": cpu_p50,
        "n_vertices": int(topo.n_vertices),
    }


def stage_scale50k(k, B, cpu_runs, engine="seq"):
    """BASELINE.md's target scale.  Each fixpoint engine gets its own
    subprocess stage (50k compiles run ~minutes each); 'seq' keeps the
    blocked-Pallas fallback as the insurance row."""
    topo, masks = _make(k, B)
    try:
        return _gather_run(topo, masks, cpu_runs, reps=2, n_atoms=128, engine=engine) | {
            "batch": int(B)
        }
    except Exception as e:  # noqa: BLE001 — compiler limits: fall back
        print(
            f"scale50k[{engine}]: gather engine failed ({type(e).__name__}: "
            f"{str(e)[:200]}); falling back to blocked",
            file=sys.stderr,
        )
        if engine != "seq":
            raise
        return _blocked_run(topo, masks, cpu_runs, reps=2)


def _multi_topo_run(topos, B, cpu_runs, engine="seq", n_atoms=64, reps=2):
    """One FULL SPF run = every topology computed for one scenario
    (multi-area OSPFv3: all areas; IS-IS: both levels).  Aggregates the
    per-topology batched engine runs into a full-run rate, parity-gated
    per topology against the C++ scalar baseline."""
    from holo_tpu.spf.synth import whatif_link_failure_masks

    parts = []
    tpu_time = 0.0
    cpu_time = 0.0
    ok = True
    for topo in topos:
        masks = whatif_link_failure_masks(topo, B, seed=1)
        r = _gather_run(
            topo, masks, cpu_runs, reps=reps, n_atoms=n_atoms, engine=engine
        )
        parts.append(r | {"n_vertices": int(topo.n_vertices)})
        ok = ok and r.get("ok", False)
        tpu_time += r["batch_ms"] / 1e3
        if cpu_runs and r.get("cpu_runs_per_sec"):
            cpu_time += cpu_runs / r["cpu_runs_per_sec"]
    out = {
        "ok": ok,
        "runs_per_sec": (B / tpu_time) if tpu_time else 0.0,
        "engine": engine,
        "parts": parts,
    }
    if cpu_time:
        out["cpu_runs_per_sec"] = cpu_runs / cpu_time
        out["vs_cpu"] = round(out["runs_per_sec"] / out["cpu_runs_per_sec"], 2)
    return out


def stage_ospfv3_multiarea(n_routers, n_areas, B, cpu_runs):
    """BASELINE config 2: 10k-node multi-area OSPFv3 LSDB, marshaled
    through OspfV3Instance._area_spf (one SPT per area)."""
    from holo_tpu.spf.synth_proto import ospfv3_multiarea_topologies

    topos = ospfv3_multiarea_topologies(n_routers, n_areas)
    return _multi_topo_run(topos, B, cpu_runs) | {
        "n_routers": int(n_routers),
        "n_areas": int(n_areas),
    }


def stage_isis_l1l2(n_l2, n_l1, ecmp, B, cpu_runs):
    """BASELINE config 3: 10k-node IS-IS L1/L2 with 64-way ECMP
    extraction at the L2 root, marshaled through IsisInstance.run_spf
    (the builder asserts the root's route table really fans out
    ``ecmp`` ways)."""
    from holo_tpu.spf.synth_proto import isis_l1l2_topologies

    topos = isis_l1l2_topologies(n_l2, n_l1, ecmp)
    return _multi_topo_run(topos, B, cpu_runs, n_atoms=max(64, ecmp)) | {
        "n_l2": int(n_l2),
        "n_l1": int(n_l1),
        "ecmp_width": int(ecmp),
    }


def stage_frr_batch(rows, cols, reps, parity):
    """FRR backup-table batch (ISSUE 1): ONE batched dispatch computes
    the all-roots distance matrix, the per-protected-link
    post-convergence planes, and the LFA/rLFA/TI-LFA selection tables.
    runs/sec counts whole engine.compute() calls (marshal + dispatch +
    readback — the unit the protocol layer pays per SPF).  Parity-gated
    against the scalar oracle."""
    from holo_tpu.frr.manager import FrrEngine
    from holo_tpu.spf.synth import grid_topology

    topo = grid_topology(rows, cols, seed=3)
    eng = FrrEngine("tpu")
    table = eng.compute(topo)  # warmup: compile + device-graph cache
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        eng.compute(topo)
        times.append(time.perf_counter() - t0)
    dt = sum(times) / reps
    from holo_tpu import telemetry

    result = {
        "runs_per_sec": 1.0 / dt,
        "batch_ms": dt * 1e3,
        "n_vertices": int(topo.n_vertices),
        "n_links": int(table.n_links),
        "coverage": round(table.coverage(), 4),
        "times_ms": [round(t * 1e3, 2) for t in times],
        # Recompile count / cache behavior / pad occupancy for the row.
        "telemetry": telemetry.snapshot(prefix="holo_frr"),
    }
    if parity:
        ref = FrrEngine("scalar").compute(topo)
        result["ok"] = all(
            np.array_equal(getattr(ref, f), getattr(table, f))
            for f in (
                "lfa_adj",
                "lfa_nodeprot",
                "rlfa_pq",
                "tilfa_p",
                "tilfa_q",
                "post_dist",
                "post_nh",
            )
        )
    else:
        result["ok"] = True
    return result


def stage_telemetry_overhead(k, B, reps=15):
    """ISSUE 2 acceptance row: the instrumented SPF dispatch path
    (TpuSpfBackend — counters, histograms, spans) against the SAME path
    with the registry disabled.  Reps interleave the two arms so clock
    drift hits both equally; ok requires overhead < 2% AND the jit
    recompile counter staying flat across same-shape re-runs."""
    from holo_tpu import telemetry
    from holo_tpu.spf.backend import TpuSpfBackend

    topo, masks = _make(k, B)
    backend = TpuSpfBackend()
    backend.compute_whatif(topo, masks)  # warm: compile + graph cache
    compiles0 = telemetry.snapshot(prefix="holo_spf_jit_compiles")
    on_times, off_times = [], []
    for rep in range(reps):
        # Alternate arm order per rep: cache/GC warmth from the previous
        # dispatch lands on each arm equally, not always on the same one.
        arms = ((True, on_times), (False, off_times))
        for arm, times in arms if rep % 2 == 0 else arms[::-1]:
            telemetry.set_enabled(arm)
            t0 = time.perf_counter()
            backend.compute_whatif(topo, masks)
            times.append(time.perf_counter() - t0)
    telemetry.set_enabled(True)
    compiles1 = telemetry.snapshot(prefix="holo_spf_jit_compiles")
    # Min-of-N per arm: the instrumentation cost is deterministic and
    # additive while scheduler noise is one-sided positive, so the two
    # minima isolate the true per-dispatch delta far better than means
    # (medians of ms-scale dispatches still carry multi-percent jitter).
    on_ms = float(np.min(on_times) * 1e3)
    off_ms = float(np.min(off_times) * 1e3)
    overhead_pct = (on_ms - off_ms) / off_ms * 100.0 if off_ms else 0.0
    # The disabled arm skips the _enabled counter bumps but NOT the
    # jit shape-signature tracking (that is plain set logic), so the
    # flatness check is valid across both arms.
    recompiles_flat = compiles0 == compiles1
    return {
        "ok": bool(overhead_pct < 2.0 and recompiles_flat),
        "enabled_ms": round(on_ms, 3),
        "disabled_ms": round(off_ms, 3),
        "overhead_pct": round(overhead_pct, 3),
        "recompiles_flat": recompiles_flat,
        "batch": int(B),
        "reps": reps,
        "telemetry": telemetry.snapshot(prefix="holo_spf"),
    }


def stage_fallback_overhead(k, B, reps=15):
    """ISSUE 4 acceptance row: the breaker-guarded SPF dispatch on the
    HEALTHY path (closed circuit — per-call admit check + success
    accounting) against the same backend with the breaker bypassed.
    Same interleaved min-of-N discipline as telemetry_overhead; ok
    requires <2% overhead AND the circuit still closed (a bench run
    must never trip the breaker)."""
    from holo_tpu.spf.backend import TpuSpfBackend

    topo, masks = _make(k, B)
    backend = TpuSpfBackend()
    backend.compute_whatif(topo, masks)  # warm: compile + graph cache
    guarded, bypassed = [], []
    for rep in range(reps):
        arms = ((True, guarded), (False, bypassed))
        for armed, times in arms if rep % 2 == 0 else arms[::-1]:
            backend.breaker.enabled = armed
            t0 = time.perf_counter()
            backend.compute_whatif(topo, masks)
            times.append(time.perf_counter() - t0)
    backend.breaker.enabled = True
    on_ms = float(np.min(guarded) * 1e3)
    off_ms = float(np.min(bypassed) * 1e3)
    overhead_pct = (on_ms - off_ms) / off_ms * 100.0 if off_ms else 0.0
    snap = backend.breaker.snapshot()
    return {
        "ok": bool(overhead_pct < 2.0 and snap["state"] == "closed"),
        "guarded_ms": round(on_ms, 3),
        "bypassed_ms": round(off_ms, 3),
        "overhead_pct": round(overhead_pct, 3),
        "breaker": snap,
        "batch": int(B),
        "reps": reps,
    }


def stage_profiling_overhead(k, B, reps=15):
    """ISSUE 5 acceptance row: the SPF dispatch path with the deep
    profiler armed (marshal/device/readback sub-spans + exemplars) AND
    the flight recorder ring tapping every span, against the same path
    with both off.  Same interleaved min-of-N discipline as
    telemetry_overhead; ok requires overhead < 2% and the on-arm ring
    actually capturing spans (an empty ring would gate nothing)."""
    from holo_tpu import telemetry
    from holo_tpu.spf.backend import TpuSpfBackend
    from holo_tpu.telemetry import flight, profiling

    topo, masks = _make(k, B)
    backend = TpuSpfBackend()
    # Warm with profiling ON: the compile AND its one-off cost-analysis
    # capture both land here, outside the timed region.
    profiling.set_device_profiling(True)
    flight.configure(entries=4096)
    backend.compute_whatif(topo, masks)
    on_times, off_times = [], []
    for rep in range(reps):
        arms = ((True, on_times), (False, off_times))
        for armed, times in arms if rep % 2 == 0 else arms[::-1]:
            profiling.set_device_profiling(armed)
            if not armed:
                telemetry.tracer().on_complete = None  # detach the tap
            else:
                flight.configure(entries=4096)
            t0 = time.perf_counter()
            backend.compute_whatif(topo, masks)
            times.append(time.perf_counter() - t0)
    profiling.set_device_profiling(True)
    ring_entries = flight.recorder().stats()["entries"]
    cost_sites = sorted({site for site, _ in profiling.cost_table()})
    profiling.set_device_profiling(False)
    flight.configure(entries=0)
    on_ms = float(np.min(on_times) * 1e3)
    off_ms = float(np.min(off_times) * 1e3)
    overhead_pct = (on_ms - off_ms) / off_ms * 100.0 if off_ms else 0.0
    return {
        "ok": bool(overhead_pct < 2.0 and ring_entries > 0),
        "enabled_ms": round(on_ms, 3),
        "disabled_ms": round(off_ms, 3),
        "overhead_pct": round(overhead_pct, 3),
        "flight_ring_entries": ring_entries,
        "cost_sites": cost_sites,
        "batch": int(B),
        "reps": reps,
        "telemetry": telemetry.snapshot(prefix="holo_profile"),
    }


def stage_convergence_storm(n_routers, events, reps=2):
    """ISSUE 6 acceptance row: seeded flap storm with 10% loss over a
    synthetic multi-thousand-router OSPFv2 LSDB in a real instance,
    measured end to end by the convergence observatory.  Reports
    per-trigger p50/p95/p99/max event-to-FIB distributions split by
    dispatch mode (batched-device vs scalar-fallback), and gates on the
    causal timelines being byte-identical across ``reps`` runs of the
    same seed (the virtual-clock determinism contract)."""
    from holo_tpu.spf.backend import TpuSpfBackend
    from holo_tpu.spf.synth_storm import run_convergence_storm

    t0 = time.perf_counter()
    digests, report, inc_first = [], None, None
    # ONE incremental backend across reps: rep 1 is the FIRST-ENCOUNTER
    # distribution (the two DeltaPath jits compile once), later reps
    # are warm — the digest gate proves jit warmth leaves the causal
    # run byte-identical either way.
    inc_backend = TpuSpfBackend()
    for i in range(reps):
        report, digest, _net = run_convergence_storm(
            n_routers=n_routers, events=events, seed=17,
            spf_backend=inc_backend,
        )
        if i == 0:
            inc_first = report
        digests.append(digest)
    # DeltaPath comparison arm (ISSUE 7): the SAME seeded storm with
    # incremental dispatch disabled — causal timelines and FIB digests
    # must stay byte-identical (bit-parity contract) while the REAL
    # per-trigger dispatch-wall distributions show the win.  Two runs:
    # the FIRST is how the shipped full-rebuild path actually meets a
    # storm (every novel live-edge-count re-jits the mask shape, and
    # every event re-marshals — those spikes ARE its p95), the second
    # is the fully-warm steady state for an honest like-for-like split.
    full_backend = TpuSpfBackend(incremental=False)
    full_report = full_first = None
    for i in range(2):
        full_report, full_digest, _net = run_convergence_storm(
            n_routers=n_routers, events=events, seed=17,
            spf_backend=full_backend,
        )
        if i == 0:
            full_first = full_report
        digests.append(full_digest)
    identical = len(set(digests)) == 1
    lsa = report["triggers"].get("lsa", {})
    converged = report["outcomes"].get("converged", 0)

    def split(rep):
        return rep["dispatch-wall"].get("lsa", {})

    def ratio(full_d, inc_d):
        return {
            q: round(full_d[q] / inc_d[q], 2)
            for q in ("p50", "p95", "p99")
            if inc_d.get(q) and full_d.get(q)
        }

    speedup_cold = ratio(split(full_first), split(inc_first))
    speedup_warm = ratio(split(full_report), split(report))

    # Multipath arm (ISSUE 10): the SAME seeded storm with max-paths=2
    # armed — dual-gateway ECMP flips now exercise real next-hop SETS
    # through the widened kernel.  Gated on byte-identical digests
    # across ITS two runs (virtual-clock determinism of the k>1 path)
    # and on the FIB actually carrying multipath + weighted entries;
    # its per-trigger dispatch-wall split reports the k>1 price.
    mp_backend = TpuSpfBackend()
    mp_digests, mp_report = [], None
    for _ in range(2):
        mp_report, mp_digest, mp_net = run_convergence_storm(
            n_routers=n_routers, events=events, seed=17,
            spf_backend=mp_backend, max_paths=2,
        )
        mp_digests.append(mp_digest)
    mp_identical = len(set(mp_digests)) == 1
    from holo_tpu import telemetry

    return {
        # ISSUE 7 acceptance rides the ok gate: byte-identical digests
        # AND the first-encounter lsa-trigger dispatch-wall p95
        # improving >= 2x over the full-rebuild path (both arms cold:
        # a fresh daemon meeting the storm on each path — the full
        # path's per-event marshal + mask-shape recompile churn is
        # exactly the cost DeltaPath removes; the warm steady-state
        # split rides along un-gated).
        "ok": bool(
            identical
            and converged > 0
            and lsa.get("all", {}).get("count", 0) > 0
            and speedup_cold.get("p95", 0.0) >= 2.0
            and mp_identical
            and mp_report.get("fib-multipath", 0) > 0
            and mp_report.get("fib-weighted", 0) > 0
        ),
        "identical_across_runs": identical,
        "identical_incremental_vs_full": digests[0] == full_digest,
        "digest": digests[0][:16],
        "multipath_arm": {
            "identical_across_runs": mp_identical,
            "digest": mp_digests[0][:16],
            "fib_multipath": mp_report.get("fib-multipath", 0),
            "fib_weighted": mp_report.get("fib-weighted", 0),
            "lsa_wall_k2": split(mp_report),
        },
        "lsa_wall_first_encounter": {
            "incremental": split(inc_first),
            "full_rebuild": split(full_first),
            "speedup": speedup_cold,
        },
        "lsa_wall_warm": {
            "incremental": split(report),
            "full_rebuild": split(full_report),
            "speedup": speedup_warm,
        },
        "delta_telemetry": telemetry.snapshot(prefix="holo_spf_delta"),
        "wall_s": round(time.perf_counter() - t0, 1),
        "report": report,
    }


def stage_convergence_overhead(k, B, reps=15):
    """ISSUE 6 overhead gate: the SPF dispatch path with the convergence
    tracker ARMED and an open causal event active (worst case — every
    dispatch runs the note_dispatch bookkeeping) against the same path
    disarmed.  Same interleaved min-of-N discipline as the other
    overhead gates; ok requires <2%."""
    from contextlib import nullcontext

    from holo_tpu.spf.backend import TpuSpfBackend
    from holo_tpu.telemetry import convergence

    topo, masks = _make(k, B)
    backend = TpuSpfBackend()
    backend.compute_whatif(topo, masks)  # warm: compile + graph cache
    on_times, off_times = [], []
    for rep in range(reps):
        arms = ((True, on_times), (False, off_times))
        for armed, times in arms if rep % 2 == 0 else arms[::-1]:
            if armed:
                convergence.configure(4096)
                ctx = convergence.activation(convergence.begin("lsa"))
            else:
                convergence.configure(0)
                ctx = nullcontext()
            with ctx:
                t0 = time.perf_counter()
                backend.compute_whatif(topo, masks)
                times.append(time.perf_counter() - t0)
    convergence.configure(0)
    on_ms = float(np.min(on_times) * 1e3)
    off_ms = float(np.min(off_times) * 1e3)
    overhead_pct = (on_ms - off_ms) / off_ms * 100.0 if off_ms else 0.0
    return {
        "ok": bool(overhead_pct < 2.0),
        "enabled_ms": round(on_ms, 3),
        "disabled_ms": round(off_ms, 3),
        "overhead_pct": round(overhead_pct, 3),
        "batch": int(B),
        "reps": reps,
    }


def stage_critical_path(n_routers, events):
    """ISSUE 17 acceptance row: the critical-path ledger over the
    seeded storm.  Reports the per-phase trigger→FIB split (p50/p99 ms
    in cut order), the bound-verdict tally, and the two headline
    scalars — ``host_fraction_p99`` (the fraction of the summed-phase
    p99 owned by host choreography: ROADMAP item 5's before-number)
    and ``unattributed_frac_p50`` (the gap-free gate: the residual no
    stamp explains must stay <1% of the wall at p50).  A chaos arm
    re-runs a small same-seed storm with ``FaultPlan.dispatch_delay``
    injected and gates on the delay landing in the DEVICE phase
    (wrong-phase attribution fails the row) while the causal digest
    stays byte-identical (real sleeps are invisible to the virtual
    clock).  The device-residency snapshot rides along."""
    from holo_tpu.resilience import faults
    from holo_tpu.spf.backend import TpuSpfBackend
    from holo_tpu.spf.synth_storm import run_convergence_storm
    from holo_tpu.telemetry import critpath, residency

    t0 = time.perf_counter()
    cp = critpath.configure(check_every=64)
    try:
        report, digest, _net = run_convergence_storm(
            n_routers=n_routers, events=events, seed=17,
            spf_backend=TpuSpfBackend(),
        )
        cp.checkpoint()
        clean = cp.report(top=0)
        phases_ms = {
            r["phase"]: {
                "p50_ms": round(r["p50"] * 1e3, 3),
                "p99_ms": round(r["p99"] * 1e3, 3),
                "share_p99": r["share_p99"],
            }
            for r in clean["phases"]
        }
        # Chaos arm: small same-seed storm, clean vs injected 5 ms
        # device-dispatch delay — the delta must book to `device`.
        chaos_n, chaos_ev, delay = min(n_routers, 120), min(events, 48), 0.005

        def chaos_run(plan):
            c = critpath.configure(check_every=0)
            with faults.inject(plan) as inj:
                _r, dg, _n = run_convergence_storm(
                    n_routers=chaos_n, events=chaos_ev, seed=17,
                    spf_backend=TpuSpfBackend(),
                )
            q = c.phase_quantiles()
            dev = q.get("device", {"p50": 0.0})["p50"]
            return dev, dg, dict(inj.injected)

        dev_clean, dg_clean, _ = chaos_run(faults.FaultPlan())
        dev_chaos, dg_chaos, injected = chaos_run(
            faults.FaultPlan(dispatch_delay={"spf.dispatch": delay})
        )
        chaos_attributed = bool(
            injected.get("delay:spf.dispatch", 0) > 0
            and dev_chaos >= dev_clean + 0.5 * delay
        )
        uf = clean["unattributed-frac-p50"]
        hf = clean["host-fraction-p99"]
        out = {
            "ok": bool(
                clean["completed"] > 0
                and uf is not None
                and uf < 0.01
                and chaos_attributed
                and dg_clean == dg_chaos
            ),
            "completed": clean["completed"],
            "dropped": clean["dropped"],
            "verdicts": clean["verdicts"],
            "phases": phases_ms,
            "wall_p50_ms": round((clean["wall"] or {}).get("p50", 0.0) * 1e3, 3),
            "wall_p99_ms": round((clean["wall"] or {}).get("p99", 0.0) * 1e3, 3),
            "host_fraction_p99": hf,
            "unattributed_frac_p50": uf,
            "chaos": {
                "device_p50_clean_ms": round(dev_clean * 1e3, 3),
                "device_p50_injected_ms": round(dev_chaos * 1e3, 3),
                "injected_delay_ms": delay * 1e3,
                "attributed_to_device": chaos_attributed,
                "digest_identical": dg_clean == dg_chaos,
            },
            "residency": residency.snapshot(),
            "digest": digest[:16],
            "wall_s": round(time.perf_counter() - t0, 1),
        }
        # Ledger scalars: per-phase p99 flattened to top-level keys so
        # the regression ledger (ISSUE 11 satellite) tracks each phase.
        for ph, row in phases_ms.items():
            out[f"critpath_{ph}_p99_ms"] = row["p99_ms"]
        return out
    finally:
        critpath.configure(0)


def stage_critpath_overhead(k, B, reps=15):
    """ISSUE 17 overhead gate: the SPF dispatch path with convergence
    armed AND an open causal event active in BOTH arms (the ledger's
    stamps only fire inside events — that is the worst case being
    measured), critical-path ledger armed vs disarmed.  Same
    interleaved min-of-N discipline as the other gates; ok <2%."""
    from holo_tpu.spf.backend import TpuSpfBackend
    from holo_tpu.telemetry import convergence, critpath

    topo, masks = _make(k, B)
    backend = TpuSpfBackend()
    backend.compute_whatif(topo, masks)  # warm: compile + graph cache
    on_times, off_times = [], []
    for rep in range(reps):
        arms = ((True, on_times), (False, off_times))
        for armed, times in arms if rep % 2 == 0 else arms[::-1]:
            critpath.configure(4096 if armed else 0, check_every=0)
            convergence.configure(4096)
            with convergence.activation(convergence.begin("lsa")):
                t0 = time.perf_counter()
                backend.compute_whatif(topo, masks)
                times.append(time.perf_counter() - t0)
            convergence.configure(0)
    critpath.configure(0)
    on_ms = float(np.min(on_times) * 1e3)
    off_ms = float(np.min(off_times) * 1e3)
    overhead_pct = (on_ms - off_ms) / off_ms * 100.0 if off_ms else 0.0
    return {
        "ok": bool(overhead_pct < 2.0),
        "enabled_ms": round(on_ms, 3),
        "disabled_ms": round(off_ms, 3),
        "overhead_pct": round(overhead_pct, 3),
        "batch": int(B),
        "reps": reps,
    }


def stage_audit_overhead():
    """ISSUE 18 gate cost: the HL3xx jaxpr kernel audit must ride its
    per-kernel cache.  Measures the lint gate as subprocess walls
    (interpreter + imports included — the cost a pre-commit hook pays):
    warm full gate (AST cache + audit cache) vs warm ``--no-audit``
    (the pre-audit gate shape) vs a cold ``--no-cache`` run (full
    rescan + full kernel re-lowering).  ok needs the warm full gate
    under 2x the pre-audit wall AND under the 1s absolute acceptance
    bound, with the cold re-lowering inside a fixed 120s budget."""
    import os
    import subprocess

    repo = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # the audit pins CPU anyway; be explicit
    base = [
        sys.executable, "-m", "holo_tpu.tools.cli", "lint",
        "--baseline", "holo_tpu/analysis/baseline.json",
    ]

    def wall(*flags):
        t0 = time.perf_counter()
        proc = subprocess.run(
            base + list(flags), cwd=repo, env=env,
            capture_output=True, text=True, timeout=600,
        )
        return time.perf_counter() - t0, proc.returncode

    wall()  # prime both caches (AST + per-kernel audit)
    cold_s, cold_rc = wall("--no-cache")
    no_audit_s, na_rc = wall("--no-audit")
    warm_s, warm_rc = wall()
    clean = cold_rc == 0 and na_rc == 0 and warm_rc == 0
    return {
        "ok": bool(
            clean
            and warm_s < 2.0 * no_audit_s
            and warm_s < 1.0
            and cold_s < 120.0
        ),
        "gate_clean": bool(clean),
        "warm_gate_s": round(warm_s, 3),
        "warm_no_audit_s": round(no_audit_s, 3),
        "cold_full_s": round(cold_s, 3),
        "warm_vs_no_audit_x": round(
            warm_s / no_audit_s if no_audit_s else 0.0, 3
        ),
    }


def stage_delta_spf(n_routers, steps, parity_every=8):
    """ISSUE 7 acceptance row: single-flap incremental SPF (DeltaPath
    in-place device-graph update + seeded recompute) vs the full
    re-marshal + full recompute path, on one evolving topology chain.
    Per-trigger split: pure metric changes (`weight`) vs link flaps
    (`struct`, edge pair down/up).  Parity-gated against the scalar
    oracle every ``parity_every`` steps; the chains run on distinct
    Topology objects so the two arms never share cache entries."""
    import numpy as np

    from holo_tpu import telemetry
    from holo_tpu.ops.graph import diff_topologies
    from holo_tpu.spf.backend import ScalarSpfBackend, TpuSpfBackend
    from holo_tpu.spf.synth import clone_topology as clone
    from holo_tpu.spf.synth import random_ospf_topology
    from holo_tpu.telemetry import profiling

    rng = np.random.default_rng(23)
    base = random_ospf_topology(
        n_routers=n_routers, n_networks=n_routers // 10,
        extra_p2p=n_routers // 2, seed=23,
    )

    def mutate(topo, step):
        """One storm event: a metric change or a bidirectional flap."""
        if step % 2 == 0:
            e = int(rng.integers(0, topo.n_edges))
            return clone(
                topo, cost={e: int(rng.integers(1, 64))}
            ), "weight"
        # Flap: drop both directions of a random non-root edge.
        for _ in range(32):
            e = int(rng.integers(0, topo.n_edges))
            s, d = int(topo.edge_src[e]), int(topo.edge_dst[e])
            if s == topo.root or d == topo.root:
                continue
            keep = ~(
                ((topo.edge_src == s) & (topo.edge_dst == d))
                | ((topo.edge_src == d) & (topo.edge_dst == s))
            )
            return clone(topo, keep=keep), "struct"
        return clone(topo), "weight"

    inc_be = TpuSpfBackend()
    full_be = TpuSpfBackend(incremental=False)
    oracle = ScalarSpfBackend()
    # Profiling armed for the warmup compiles only: the cost_analysis
    # table then carries the spf.delta vs spf.one FLOP/bytes split (the
    # compile-time view of the win) without taxing the timed loop.
    profiling.set_device_profiling(True)
    # Two identical chains over DISTINCT Topology objects (distinct
    # cache identities): the incremental arm carries delta lineage, the
    # full arm never does.
    inc_topo = base
    inc_be.compute(inc_topo)  # warm: compile + marshal
    full_be.compute(clone(base))
    # Warm the delta-apply + incremental kernels too (one compile per
    # (shape, seed-bucket) pair): the timed loop measures dispatches.
    warm, _ = mutate(inc_topo, 0)
    wdelta = diff_topologies(inc_topo, warm)
    if wdelta is not None:
        warm.link_delta(wdelta)
        inc_be.compute(warm)
        inc_topo = warm
        full_be.compute(clone(warm))
    profiling.set_device_profiling(False)
    times: dict = {"weight": {"inc": [], "full": []},
                   "struct": {"inc": [], "full": []}}
    ok = True
    deltas = 0
    for step in range(steps):
        nxt, kind = mutate(inc_topo, step)
        inc_next, full_next = nxt, clone(nxt)
        delta = diff_topologies(inc_topo, inc_next)
        if delta is not None:
            inc_next.link_delta(delta)
            deltas += 1
        t0 = time.perf_counter()
        r_inc = inc_be.compute(inc_next)
        times[kind]["inc"].append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        r_full = full_be.compute(full_next)
        times[kind]["full"].append(time.perf_counter() - t0)
        if step % parity_every == 0 or step == steps - 1:
            ref = oracle.compute(inc_next)
            for f in ("dist", "parent", "hops", "nexthop_words"):
                ok = ok and bool(
                    np.array_equal(getattr(ref, f), getattr(r_inc, f))
                    and np.array_equal(getattr(ref, f), getattr(r_full, f))
                )
        inc_topo = inc_next

    def dist(vals):
        if not vals:
            return {}
        arr = np.sort(np.asarray(vals)) * 1e3
        return {
            "p50_ms": round(float(arr[len(arr) // 2]), 3),
            "p95_ms": round(float(arr[min(len(arr) - 1, int(0.95 * len(arr)))]), 3),
            "count": len(arr),
        }

    rows = {}
    for kind, arms in times.items():
        inc_d, full_d = dist(arms["inc"]), dist(arms["full"])
        rows[kind] = {
            "incremental": inc_d,
            "full_rebuild": full_d,
            "speedup_p50": round(full_d["p50_ms"] / inc_d["p50_ms"], 2)
            if inc_d.get("p50_ms")
            else None,
        }
    return {
        "ok": bool(ok and deltas > 0),
        "parity": ok,
        "n_vertices": int(base.n_vertices),
        "steps": steps,
        "deltas_linked": deltas,
        "triggers": rows,
        "delta_telemetry": telemetry.snapshot(prefix="holo_spf_delta"),
        # Compile-time cost_analysis split: the delta kernel's
        # FLOP/bytes next to the full engine's, per shape bucket.
        "cost_analysis": {
            f"{site}{list(sig)}": entry
            for (site, sig), entry in sorted(
                profiling.cost_table().items(), key=lambda kv: kv[0][0]
            )
        },
    }


def stage_incremental_overhead(k, B, reps=24, inner=4):
    """ISSUE 7 overhead gate: the no-delta steady-state dispatch path
    with the DeltaPath machinery ARMED (lineage checks + previous-
    tensor retention) against the same path disarmed.  Same interleaved
    min-of-N discipline as the other overhead gates, with an INNER loop
    per sample: a single ~0.5ms kind=one dispatch sits at the
    allocator-noise floor, so each sample amortizes ``inner`` dispatches
    (the armed delta is a few host-side lookups — well under the
    per-dispatch jitter).  ok requires <2%."""
    from holo_tpu.spf.backend import TpuSpfBackend

    topo, _masks = _make(k, B)
    backend = TpuSpfBackend()
    # Warm thoroughly: compile + graph cache, then enough dispatches
    # for the allocator/CPU to reach steady state — the armed delta is
    # single-digit microseconds of host lookups, so the stage measures
    # a multi-ms dispatch (k sized up by the caller) where the 2%
    # threshold sits far above scheduler jitter.
    for _ in range(16):
        backend.compute(topo)
    on_times, off_times = [], []
    for rep in range(reps):
        arms = ((True, on_times), (False, off_times))
        for armed, times in arms if rep % 2 == 0 else arms[::-1]:
            backend.incremental = armed
            t0 = time.perf_counter()
            for _ in range(inner):
                backend.compute(topo)
            times.append((time.perf_counter() - t0) / inner)
    backend.incremental = True
    # PAIRED comparison: allocator/scheduler drift at this dispatch
    # size (~0.5ms) exceeds the 2% threshold across a whole arm, but
    # each rep's adjacent armed/disarmed samples share it — the median
    # per-pair delta isolates the true armed cost (a few host lookups).
    deltas = [a - b for a, b in zip(on_times, off_times)]
    off_ms = float(np.median(off_times) * 1e3)
    on_ms = float(np.median(on_times) * 1e3)
    delta_ms = float(np.median(deltas) * 1e3)
    overhead_pct = delta_ms / off_ms * 100.0 if off_ms else 0.0
    return {
        "ok": bool(overhead_pct < 2.0),
        "armed_ms": round(on_ms, 4),
        "disarmed_ms": round(off_ms, 4),
        "paired_delta_ms": round(delta_ms, 5),
        "overhead_pct": round(overhead_pct, 3),
        "reps": reps,
        "inner": inner,
    }


def stage_shard_spf(n_routers, reps=3):
    """ISSUE 8 acceptance row: the REAL TpuSpfBackend sharded dispatch
    path over a forced 8-device virtual CPU mesh — scenario-count
    sweep 1→2·devices per mesh shape, runs/s + compile-time
    cost_analysis, parity-gated bit-identical against the scalar
    oracle, with the shard-dispatch counter proving every timed batch
    actually took the mesh path.  The row says ``platform: cpu``:
    virtual host devices measure sharding mechanics + GSPMD
    partitioning, not chip throughput."""
    from holo_tpu.testing import force_virtual_cpu_mesh

    force_virtual_cpu_mesh(8)
    import jax

    from holo_tpu import telemetry
    from holo_tpu.parallel.mesh import (
        configure_process_mesh,
        reset_process_mesh,
    )
    from holo_tpu.spf.backend import ScalarSpfBackend, TpuSpfBackend
    from holo_tpu.spf.synth import (
        random_ospf_topology,
        whatif_link_failure_masks,
    )
    from holo_tpu.telemetry import profiling

    n_devices = len(jax.devices())
    topo = random_ospf_topology(
        n_routers=n_routers,
        n_networks=n_routers // 5,
        extra_p2p=n_routers,
        seed=8,
    )
    sweep_b = sorted({1, 2, n_devices // 2, n_devices, 2 * n_devices})
    mesh_rows: dict = {}
    ok = True

    def counter():
        snap = telemetry.snapshot(prefix="holo_spf_shard_dispatch_total")
        return snap.get("holo_spf_shard_dispatch_total{kind=whatif}", 0.0)

    oracle = ScalarSpfBackend()
    try:
        for nb, nn in ((n_devices, 1), (n_devices // 2, 2), (2, n_devices // 2)):
            configure_process_mesh(nb, nn)
            be = TpuSpfBackend()
            # Warm with profiling armed: the compiles AND their one-off
            # cost_analysis captures land here, outside the timed loop.
            profiling.set_device_profiling(True)
            for b in sweep_b:
                be.compute_whatif(
                    topo, whatif_link_failure_masks(topo, b, seed=1)
                )
            profiling.set_device_profiling(False)
            rows = {}
            for b in sweep_b:
                masks = whatif_link_failure_masks(topo, b, seed=1)
                c0 = counter()
                times = []
                for _ in range(reps):
                    t0 = time.perf_counter()
                    res = be.compute_whatif(topo, masks)
                    times.append(time.perf_counter() - t0)
                dt = sum(times) / reps
                sharded = counter() - c0
                if b == n_devices:
                    ref = oracle.compute_whatif(topo, masks)
                    parity = all(
                        np.array_equal(getattr(r, f), getattr(s, f))
                        for r, s in zip(ref, res)
                        for f in ("dist", "parent", "hops", "nexthop_words")
                    )
                    ok = ok and parity
                    rows[f"B{b}"] = {"parity_vs_oracle": parity}
                rows.setdefault(f"B{b}", {}).update(
                    {
                        "runs_per_sec": round(b / dt, 2),
                        "batch_ms": round(dt * 1e3, 3),
                        "shard_dispatches": sharded,
                    }
                )
                ok = ok and sharded == reps
            full = rows[f"B{n_devices}"]["runs_per_sec"]
            single = rows["B1"]["runs_per_sec"]
            mesh_rows[f"{nb}x{nn}"] = rows | {
                # Throughput leverage of the batch axis (informational
                # on virtual CPU devices; the gate is parity + the
                # counter — chip scaling needs real ICI).
                "batch_axis_speedup": round(full / single, 2) if single else 0.0
            }
    finally:
        reset_process_mesh()
        profiling.set_device_profiling(False)
    return {
        "ok": bool(ok),
        "devices": n_devices,
        "scenario_sweep": sweep_b,
        "meshes": mesh_rows,
        "cost_analysis": {
            # sig = (graph shape, W, mask shape, mesh identity): keep
            # the mesh axes in the key — the sweep's shapes coincide on
            # meshes whose padded dims agree, and the per-mesh split IS
            # the deliverable — but drop the device-id tuple noise.
            f"{site}{list(sig[:3])}@mesh{sig[3][0]}x{sig[3][1]}": entry
            for (site, sig), entry in sorted(
                profiling.cost_table().items(), key=lambda kv: kv[0][0]
            )
            if site == "spf.whatif" and sig[3] is not None
        },
        "telemetry": telemetry.snapshot(prefix="holo_spf_shard"),
    }


def stage_sharding_overhead(k, B, reps=24, inner=2):
    """ISSUE 8 overhead gate: the mesh-aware dispatch path on a
    1-DEVICE mesh (placement, batch padding check, sharded jit with a
    degenerate constraint) against the plain single-device path, on
    the same warm backend.  Cache entries and jits are keyed by mesh
    identity, so toggling the installed mesh between arms re-hits warm
    state — the paired-median discipline of incremental_overhead
    isolates the true per-dispatch delta.  ok requires <2%."""
    from holo_tpu.testing import force_virtual_cpu_mesh

    force_virtual_cpu_mesh(8)
    import jax

    from holo_tpu.parallel.mesh import (
        configure_process_mesh,
        reset_process_mesh,
    )
    from holo_tpu.spf.backend import TpuSpfBackend

    topo, masks = _make(k, B)
    be = TpuSpfBackend()
    one_dev = jax.devices()[:1]
    # Warm both arms: compile + marshal both cache placements.
    configure_process_mesh(1, 1, devices=one_dev)
    be.compute_whatif(topo, masks)
    reset_process_mesh()
    be.compute_whatif(topo, masks)
    on_times, off_times = [], []
    try:
        for rep in range(reps):
            arms = ((True, on_times), (False, off_times))
            for armed, times in arms if rep % 2 == 0 else arms[::-1]:
                if armed:
                    configure_process_mesh(1, 1, devices=one_dev)
                else:
                    reset_process_mesh()
                t0 = time.perf_counter()
                for _ in range(inner):
                    be.compute_whatif(topo, masks)
                times.append((time.perf_counter() - t0) / inner)
    finally:
        reset_process_mesh()
    deltas = [a - b for a, b in zip(on_times, off_times)]
    off_ms = float(np.median(off_times) * 1e3)
    on_ms = float(np.median(on_times) * 1e3)
    delta_ms = float(np.median(deltas) * 1e3)
    overhead_pct = delta_ms / off_ms * 100.0 if off_ms else 0.0
    return {
        "ok": bool(overhead_pct < 2.0),
        "meshed_ms": round(on_ms, 4),
        "plain_ms": round(off_ms, 4),
        "paired_delta_ms": round(delta_ms, 5),
        "overhead_pct": round(overhead_pct, 3),
        "batch": int(B),
        "reps": reps,
        "inner": inner,
    }


def stage_pipeline_spf(n_routers, events):
    """ISSUE 9 acceptance row: the async dispatch pipeline + engine
    auto-tuner against the synchronous path.

    Three parts: (1) the seeded convergence storm run on three arms —
    async-pipelined, synchronous device, all-scalar — gated on the
    async arm beating sync on the per-trigger lsa dispatch-wall p50
    (the time the protocol actor spends blocked INSIDE the dispatch
    call), byte-identical FIBs across all three arms, and a
    byte-identical causal digest between the two device arms (the
    scalar arm's digest legitimately differs: its dispatch entries
    record mode=scalar); the actor-side wait the lazy result still
    pays is reported honestly as blocked-wall next to it.  (2) a
    consecutive-dispatch overlap microbench: four independent LSDBs'
    SPF+FRR dispatches submitted back-to-back through the depth-2
    pipeline vs computed serially — the marshal/device overlap the
    double buffer exists for, with the measured overlap ratio.
    (3) tuner rows: the per-shape engine sweep with measured winners
    per (V, E, batch) bucket vs every pinned engine, compile-time
    cost_analysis deltas riding along, gated on a COLD tuner (fresh
    process state, table loaded from disk) reproducing the learned
    winners in pure exploit mode."""
    import tempfile
    from pathlib import Path

    from holo_tpu import pipeline, telemetry
    from holo_tpu.spf.backend import TpuSpfBackend
    from holo_tpu.spf.synth import (
        random_ospf_topology,
        whatif_link_failure_masks,
    )
    from holo_tpu.spf.synth_storm import run_convergence_storm
    from holo_tpu.telemetry import profiling

    t_start = time.perf_counter()

    # -- (1) storm arms -------------------------------------------------
    def storm_arm(backend, asynchronous=False):
        report, digest, net = run_convergence_storm(
            n_routers=n_routers, events=events, seed=17,
            spf_backend=backend,
        )
        if asynchronous:
            pipeline.process_pipeline().drain(timeout=30)
        fib = json.dumps(
            sorted((str(k), str(v)) for k, v in net.kernel.fib.items())
        )
        import hashlib

        return report, digest, hashlib.sha256(fib.encode()).hexdigest()

    sync_rep, sync_dig, sync_fib = storm_arm(TpuSpfBackend(64))
    _scalar_rep, _scalar_dig, scalar_fib = storm_arm(None)
    pipe = pipeline.configure_process_pipeline(depth=2)
    async_rep, async_dig, async_fib = storm_arm(
        pipeline.wrap_spf_backend(TpuSpfBackend(64)), asynchronous=True
    )
    pipe_stats = pipe.stats()
    wait_snap = telemetry.snapshot(prefix="holo_pipeline_wait")
    pipeline.reset_process_pipeline()

    def lsa_wall(rep):
        return rep.get("dispatch-wall", {}).get("lsa", {})

    sync_p50 = lsa_wall(sync_rep).get("p50", 0.0)
    async_p50 = lsa_wall(async_rep).get("p50", float("inf"))
    storm_row = {
        "sync_lsa_dispatch_wall": lsa_wall(sync_rep),
        "async_lsa_dispatch_wall": lsa_wall(async_rep),
        "dispatch_wall_p50_speedup": round(sync_p50 / async_p50, 2)
        if async_p50
        else None,
        # Honest companion numbers: the wait the lazy result still pays
        # (holo_pipeline_wait_seconds) and the worker's overlap ratio.
        "async_blocked_wait": wait_snap,
        "pipeline": pipe_stats,
        "fib_identical_async_sync_scalar": (
            async_fib == sync_fib == scalar_fib
        ),
        "causal_digest_async_eq_sync": async_dig == sync_dig,
    }

    # -- (2) consecutive-dispatch overlap -------------------------------
    from holo_tpu.frr.manager import FrrEngine

    topos = [
        random_ospf_topology(
            n_routers=max(n_routers // 2, 60),
            n_networks=max(n_routers // 10, 8),
            extra_p2p=max(n_routers // 2, 40),
            seed=100 + i,
        )
        for i in range(4)
    ]
    sync_be = TpuSpfBackend(64)
    sync_frr = FrrEngine("tpu")
    for t in topos:  # warm compiles + marshals for both arms
        sync_be.compute(t)
        sync_frr.compute(t)
    reps = 5
    t0 = time.perf_counter()
    for _ in range(reps):
        for t in topos:
            sync_be.compute(t)
            sync_frr.compute(t)
    sync_wall = (time.perf_counter() - t0) / reps
    pipe = pipeline.configure_process_pipeline(depth=2)
    async_be = pipeline.wrap_spf_backend(sync_be)
    async_frr = pipeline.wrap_frr_engine(sync_frr)
    # Warm the pipelined path once (thread spin-up etc.).
    [r.wait() for r in [async_be.compute(t) for t in topos]]
    t0 = time.perf_counter()
    for _ in range(reps):
        pending = []
        for t in topos:
            pending.append(async_be.compute(t))
            pending.append(async_frr.compute(t))
        for r in pending:
            r.wait()
    async_wall = (time.perf_counter() - t0) / reps
    overlap_stats = pipe.stats()
    pipeline.reset_process_pipeline()
    consecutive_row = {
        "sync_wall_ms": round(sync_wall * 1e3, 3),
        "async_wall_ms": round(async_wall * 1e3, 3),
        "speedup": round(sync_wall / async_wall, 3) if async_wall else None,
        "overlap_ratio": overlap_stats["overlap-ratio"],
        "dispatches_per_round": len(topos) * 2,
    }

    # -- (3) tuner rows -------------------------------------------------
    tdir = Path(tempfile.mkdtemp(prefix="holo-tuner-bench-"))
    table_path = tdir / "tuner.json"
    sizes = [
        ("small", random_ospf_topology(
            n_routers=60, n_networks=10, extra_p2p=40, seed=41
        ), 16),
        ("mid", random_ospf_topology(
            n_routers=max(n_routers, 300),
            n_networks=max(n_routers // 10, 30),
            extra_p2p=max(n_routers, 200),
            seed=42,
        ), 16),
    ]
    # Pinned-engine comparison rows FIRST, tuner disarmed (an armed
    # tuner overrides every backend's engine pick by design).
    tuner_rows = {}
    for label, topo, batch in sizes:
        masks = whatif_link_failure_masks(topo, batch, seed=1)
        pinned = {}
        for eng in pipeline.ENGINES:
            pb = TpuSpfBackend(64, one_engine=eng)
            pb.compute_whatif(topo, masks)  # warm
            t0 = time.perf_counter()
            for _ in range(3):
                pb.compute_whatif(topo, masks)
            pinned[eng] = round(
                batch * 3 / (time.perf_counter() - t0), 2
            )
        tuner_rows[label] = {
            "n_vertices": int(topo.n_vertices),
            "batch": batch,
            "pinned_runs_per_sec": pinned,
            "measured_best_pinned": max(pinned, key=pinned.get),
        }
    # Now arm the tuner and let it learn both shapes (cost priors ride
    # the armed profiler's cost_analysis capture).
    profiling.set_device_profiling(True)
    tuner = pipeline.configure_engine_tuner(
        path=table_path, explore_rounds=2, reprobe_every=0
    )
    for label, topo, batch in sizes:
        masks = whatif_link_failure_masks(topo, batch, seed=1)
        be = TpuSpfBackend(64)
        for _ in range(12):
            be.compute_whatif(topo, masks)
        bucket = pipeline.shape_bucket(
            topo.n_vertices, topo.n_edges, batch, None
        )
        bkey = json.dumps(["whatif", *bucket])
        tuner_rows[label]["winner"] = (
            tuner.stats()["winners"].get(bkey, {}).get("winner")
        )
    tuner.save()
    # COLD reproduction: a fresh tuner restores the table and picks the
    # winner for each bucket in pure exploit mode (zero exploration).
    cold = pipeline.EngineTuner(
        path=table_path, explore_rounds=2, reprobe_every=0
    )
    cold_ok = True
    winners_credible = True
    for label, topo, batch in sizes:
        bucket = pipeline.shape_bucket(
            topo.n_vertices, topo.n_edges, batch, None
        )
        pick = cold.pick("whatif", bucket)
        want = tuner_rows[label]["winner"]
        tuner_rows[label]["cold_pick"] = pick
        cold_ok = cold_ok and (want is not None and pick == want)
        # Credibility: the learned winner must be the measured pinned
        # best, or within 20% of it (the top engines at some shapes
        # measure within noise of each other — seq vs hybrid on small
        # jaxcpu graphs — and either pick is correct there).
        pinned = tuner_rows[label]["pinned_runs_per_sec"]
        best = max(pinned.values())
        winners_credible = winners_credible and (
            want in pinned and pinned[want] >= 0.8 * best
        )
    cost = {
        f"{site}{list(sig)[:3]}+{list(sig)[4:]}": entry
        for (site, sig), entry in sorted(
            profiling.cost_table().items(), key=lambda kv: kv[0][0]
        )
        if site == "spf.whatif" and len(sig) >= 5
    }
    profiling.set_device_profiling(False)
    pipeline.reset_engine_tuner()

    ok = bool(
        storm_row["fib_identical_async_sync_scalar"]
        and storm_row["causal_digest_async_eq_sync"]
        and async_p50 < sync_p50
        and cold_ok
        and winners_credible
    )
    return {
        "ok": ok,
        "storm": storm_row,
        "consecutive": consecutive_row,
        "tuner": tuner_rows,
        "tuner_cold_reproduces_winners": cold_ok,
        "tuner_winners_credible": winners_credible,
        "cost_analysis": cost,
        "n_routers": n_routers,
        "events": events,
        "wall_s": round(time.perf_counter() - t_start, 1),
        "telemetry": telemetry.snapshot(prefix="holo_pipeline"),
    }


def stage_pipeline_overhead(k, B, reps=24, inner=4):
    """ISSUE 9 overhead gate: the pipeline machinery must cost <2% in
    the depth-1/disabled configuration.  Two paired-median rows on the
    same warm backend (incremental_overhead discipline): (a) DISABLED —
    the wrap_spf_backend facade with no process pipeline armed (pure
    delegation, what every daemon — default config — pays for the
    feature existing): THE <2% gate.  (b) DEPTH-1 — dispatches routed
    through the worker with the caller forcing immediately (submit +
    two thread handoffs + force, nothing overlapping): reported
    honestly against the same bare baseline as the floor price of
    unblocking the actor — a fixed ~0.1-0.2ms per dispatch that is
    sub-2% at production dispatch sizes (10k-vertex ~15ms) but not at
    this stage's small-k sizing, so it informs rather than gates."""
    from holo_tpu import pipeline
    from holo_tpu.spf.backend import TpuSpfBackend

    topo, _masks = _make(k, B)
    bare = TpuSpfBackend()
    for _ in range(16):
        bare.compute(topo)  # warm: compile + graph cache + allocator
    facade = pipeline.wrap_spf_backend(bare)  # no pipeline: identity
    assert facade is bare
    pipe = pipeline.configure_process_pipeline(depth=1)
    wrapped = pipeline.wrap_spf_backend(bare)
    wrapped.compute(topo).wait()  # spin the worker up

    def sample(fn):
        t0 = time.perf_counter()
        for _ in range(inner):
            fn()
        return (time.perf_counter() - t0) / inner

    bare_times, disabled_times, depth1_times = [], [], []
    disabled = pipeline.AsyncSpfBackend(bare, None)  # facade, no pipe
    arms = (
        (lambda: bare.compute(topo), bare_times),
        (lambda: disabled.compute(topo), disabled_times),
        (lambda: wrapped.compute(topo).wait(), depth1_times),
    )
    for rep in range(reps):
        order = arms if rep % 2 == 0 else arms[::-1]
        for fn, times in order:
            times.append(sample(fn))
    pipeline.reset_process_pipeline()
    bare_ms = float(np.median(bare_times) * 1e3)
    dis_delta = float(
        np.median([a - b for a, b in zip(disabled_times, bare_times)]) * 1e3
    )
    d1_delta = float(
        np.median([a - b for a, b in zip(depth1_times, bare_times)]) * 1e3
    )
    dis_pct = dis_delta / bare_ms * 100.0 if bare_ms else 0.0
    d1_pct = d1_delta / bare_ms * 100.0 if bare_ms else 0.0
    return {
        "ok": bool(dis_pct < 2.0),
        "bare_ms": round(bare_ms, 4),
        "disabled_paired_delta_ms": round(dis_delta, 5),
        "disabled_overhead_pct": round(dis_pct, 3),
        "depth1_paired_delta_ms": round(d1_delta, 5),
        "depth1_overhead_pct": round(d1_pct, 3),
        "reps": reps,
        "inner": inner,
    }


def stage_overload_storm(n_routers, events, flood_every=5, flood_n=24):
    """ISSUE 19 acceptance row: the dispatch survivability plane under
    chaos-born pressure.

    Three arms of ONE seeded storm: (a) the flood-free pipelined
    control; (b) the same storm with ``queue_flood`` advisory storms
    injected every ``flood_every`` events against a small-capacity
    queue — gated on byte-identical causal digest + FIB versus the
    control, advisory sheds > 0, ZERO correctness sheds, and the
    correctness (lsa) dispatch-wall p99 staying bounded relative to
    the flood-free arm (priority dequeue + graded shedding must keep
    FIB-feeding work from queuing behind the flood); (c) a hung-launch
    arm — ``dispatch_hang`` wedges the worker mid-storm, the watchdog
    abandons the phase, serves the bit-identical scalar fallback, and
    a respawned worker finishes the storm — gated on FIB parity with
    the control plus at least the injected hang being declared."""
    import hashlib

    from holo_tpu import pipeline, telemetry
    from holo_tpu.resilience.breaker import CircuitBreaker
    from holo_tpu.resilience.faults import FaultInjector, FaultPlan, inject
    from holo_tpu.resilience.watchdog import DispatchWatchdog
    from holo_tpu.spf.backend import TpuSpfBackend
    from holo_tpu.spf.synth_storm import run_convergence_storm

    t0 = time.perf_counter()

    def arm(flood=False, hang=False):
        pipe = pipeline.configure_process_pipeline(depth=2, capacity=8)
        inj = FaultInjector(
            FaultPlan(
                seed=19,
                dispatch_hang=(
                    {"pipeline.launch": 30.0} if hang else {}
                ),
            )
        )
        breaker = (
            CircuitBreaker(
                f"overload-storm-{flood}-{hang}",
                failure_threshold=3, recovery_timeout=1e9,
            )
            if hang
            else None
        )
        wd = None
        if hang:
            # Floor must clear a real first-compile launch wall at this
            # scale — only the injected 30s wedge should trip; a
            # spuriously-abandoned slow launch still keeps FIB parity
            # (the fallback is bit-identical), which is what we gate.
            wd = DispatchWatchdog(pipe, interval=0.25, floor=6.0).start()
        hook = None
        if flood:
            def hook(net, index, now):
                if index % flood_every == 0:
                    inj.queue_flood(pipe, flood_n)
        try:
            with inject(inj):
                report, digest, net = run_convergence_storm(
                    n_routers=n_routers, events=events, seed=19,
                    spf_backend=pipeline.wrap_spf_backend(
                        TpuSpfBackend(64, breaker=breaker)
                        if breaker is not None
                        else TpuSpfBackend(64)
                    ),
                    event_hook=hook,
                )
                pipe.drain(timeout=60)
        finally:
            inj.release_hangs()
            if wd is not None:
                wd.stop()
        stats = pipe.stats()
        pipeline.reset_process_pipeline()
        fib = json.dumps(
            sorted((str(k), str(v)) for k, v in net.kernel.fib.items())
        )
        return {
            "report": report,
            "digest": digest,
            "fib": hashlib.sha256(fib.encode()).hexdigest(),
            "stats": stats,
            "hangs": wd.hangs if wd is not None else 0,
        }

    ctl = arm()
    fld = arm(flood=True)
    hng = arm(hang=True)

    def wall_p99(a):
        return a["report"].get("dispatch-wall", {}).get("lsa", {}).get(
            "p99", 0.0
        )

    ctl_p99, fld_p99 = wall_p99(ctl), wall_p99(fld)
    # Bounded, with a small absolute slack so a ~ms-scale control p99
    # does not turn scheduler noise into a gate failure.
    p99_bounded = fld_p99 <= max(ctl_p99 * 5.0, ctl_p99 + 0.005)
    shed_by_class = fld["stats"]["shed-by-class"]
    shed_advisory = int(shed_by_class.get("advisory", 0))
    shed_correctness = int(shed_by_class.get("correctness", 0))
    row = {
        "ok": bool(
            fld["digest"] == ctl["digest"]
            and fld["fib"] == ctl["fib"]
            and hng["fib"] == ctl["fib"]
            and shed_advisory > 0
            and shed_correctness == 0
            and hng["hangs"] >= 1
            and p99_bounded
        ),
        "flood_digest_identical": fld["digest"] == ctl["digest"],
        "flood_fib_identical": fld["fib"] == ctl["fib"],
        "watchdog_fib_identical": hng["fib"] == ctl["fib"],
        "shed_advisory_total": shed_advisory,
        "shed_correctness_total": shed_correctness,
        "flood_sheds": fld["stats"]["sheds"],
        "watchdog_hangs": int(hng["hangs"]),
        "watchdog_worker_respawns": hng["stats"]["worker-respawns"],
        "control_lsa_wall_p99_s": round(ctl_p99, 6),
        "flood_lsa_wall_p99_s": round(fld_p99, 6),
        "correctness_p99_ratio": round(fld_p99 / ctl_p99, 3)
        if ctl_p99
        else None,
        "correctness_p99_bounded": bool(p99_bounded),
        "shed_metric": telemetry.snapshot(prefix="holo_pipeline_shed"),
        "wall_s": round(time.perf_counter() - t0, 1),
    }
    return row


def stage_overload_overhead(k, B, reps=24, inner=4):
    """ISSUE 19 overhead gate: the survivability plane must cost <2%
    when armed and ~nothing when disarmed.  Paired-median rows on one
    warm depth-1 pipeline (pipeline_overhead discipline): (a) DISARMED
    — no watchdog, no deadlines: the class-aware admission/dequeue
    plumbing every pipelined dispatch now rides (zero deadline-clock
    reads, zero phase stamps); (b) ARMED — the watchdog stamping every
    launch/finish phase (two clock reads + one tuple store per phase).
    THE gate is armed-vs-disarmed < 2%: arming the sentinel must be
    free enough to leave on in production."""
    from holo_tpu import pipeline
    from holo_tpu.spf.backend import TpuSpfBackend

    topo, _masks = _make(k, B)
    bare = TpuSpfBackend()
    for _ in range(16):
        bare.compute(topo)  # warm: compile + graph cache + allocator
    pipeline.configure_process_pipeline(depth=1)
    pipe = pipeline.process_pipeline()
    wrapped = pipeline.wrap_spf_backend(bare)
    wrapped.compute(topo).wait()  # spin the worker up

    def sample(fn):
        t0 = time.perf_counter()
        for _ in range(inner):
            fn()
        return (time.perf_counter() - t0) / inner

    disarmed_times, armed_times = [], []

    def disarmed():
        pipe.disarm_watchdog()
        return sample(lambda: wrapped.compute(topo).wait())

    def armed():
        # Stamps only (no sentinel thread): the armed hot-path cost is
        # the phase stamps themselves — exactly what start() adds to
        # every dispatch; the sentinel wakes on its own interval and
        # never rides the dispatch path.
        pipe.arm_watchdog(time.monotonic)
        try:
            return sample(lambda: wrapped.compute(topo).wait())
        finally:
            pipe.disarm_watchdog()

    arms = ((disarmed, disarmed_times), (armed, armed_times))
    for rep in range(reps):
        order = arms if rep % 2 == 0 else arms[::-1]
        for fn, times in order:
            times.append(fn())
    pipeline.reset_process_pipeline()
    disarmed_ms = float(np.median(disarmed_times) * 1e3)
    armed_delta = float(
        np.median([a - b for a, b in zip(armed_times, disarmed_times)])
        * 1e3
    )
    armed_pct = armed_delta / disarmed_ms * 100.0 if disarmed_ms else 0.0
    return {
        "ok": bool(armed_pct < 2.0),
        "disarmed_ms": round(disarmed_ms, 4),
        "armed_paired_delta_ms": round(armed_delta, 5),
        "overload_overhead_pct": round(armed_pct, 3),
        "reps": reps,
        "inner": inner,
    }


def stage_slo_storm(n_routers, events, breach_routers=40, breach_events=10):
    """ISSUE 20 acceptance row: the SLO plane + synthetic canary over
    the seeded storm.

    Three arms: (a) a canary-free control — its production FIB digest
    is the identity reference; (b) the same-seed storm with the SLO
    engine armed and a canary prober riding the storm loop, its probes
    admitted as background-class pipeline tickets — gated on the
    production FIB digest being byte-identical to the control (the
    canary's routes live in its own kernel), probe attribution quality
    (unattributed fraction < 1%), and the canary burn-rate sentinel
    staying SILENT on the healthy arm; (c) a small same-seed breach
    sub-storm with ``FaultPlan.dispatch_delay`` wedging every canary
    dispatch past the probe threshold — gated on the fast-window
    sentinel firing EXACTLY once (latched) while every breaker stays
    closed (warn-only by contract).  The armed arm's budget math seeds
    the ledger: trigger→FIB budget remaining + canary probe p99."""
    from dataclasses import replace

    from holo_tpu import pipeline
    from holo_tpu.resilience import health_snapshot
    from holo_tpu.resilience.faults import FaultPlan, inject
    from holo_tpu.spf.backend import TpuSpfBackend
    from holo_tpu.spf.synth_storm import run_convergence_storm
    from holo_tpu.telemetry import slo as slo_mod
    from holo_tpu.telemetry.canary import CanaryProber, fib_digest

    t0 = time.perf_counter()

    def arm(routers, evts, canary_on=False, breach=None):
        pipe = pipeline.configure_process_pipeline(depth=2, capacity=32)
        eng = prober = None
        hook = None
        if canary_on:
            # CPU-honest canary threshold (1 s real wall): the default
            # 250 ms objective is calibrated for a warm production
            # daemon, not a storm sharing one CPU with jit compiles —
            # a loose threshold keeps the CLEAN arm's silence gate
            # about the sentinel contract, not scheduler noise.
            eng = slo_mod.configure(
                check_every=0,
                objectives=tuple(
                    replace(o, threshold_s=1.0) if o.name == "canary" else o
                    for o in slo_mod.default_objectives()
                ),
            )
            state = {}

            def hook(net, index, now):
                if "p" not in state:
                    state["p"] = CanaryProber(
                        net.loop, period=2.0, deadline=2.0, warmup=10.0
                    )
                    state["p"].start()
        plan = FaultPlan(seed=20, dispatch_delay=breach or {})
        try:
            with inject(plan):
                _report, digest, net = run_convergence_storm(
                    n_routers=routers, events=evts, seed=20,
                    spf_backend=pipeline.wrap_spf_backend(
                        TpuSpfBackend(64)
                    ),
                    event_hook=hook,
                )
                pipe.drain(timeout=60)
        finally:
            prober = None if not canary_on else state.get("p")
            if prober is not None:
                prober.stop()
        row = {
            "digest": digest,
            "fib": fib_digest(net.kernel.fib),
            "canary": prober.stats() if prober is not None else None,
            "unattributed_fraction": (
                prober.unattributed_fraction() if prober is not None
                else None
            ),
        }
        if eng is not None:
            eng.checkpoint()
            row["slo"] = eng.report()
            st = eng.objective("canary")
            row["canary_fires_fast"] = st.fires["fast"]
            slo_mod.configure(False)
        pipeline.reset_process_pipeline()
        return row

    ctl = arm(n_routers, events)
    armed = arm(n_routers, events, canary_on=True)
    # Breach: every canary dispatch sleeps past the 1 s probe
    # threshold (REAL seconds — invisible to the virtual end-cuts, so
    # the storm's causal story is untouched); small sub-storm because
    # each wedged probe pays the sleep for real.
    breach = arm(
        breach_routers, breach_events, canary_on=True,
        breach={"canary.probe": 2.5},
    )
    breakers_closed = not any(
        b.get("state") == "open"
        for b in health_snapshot().get("breakers", {}).values()
    )
    rows = {r["objective"]: r for r in armed["slo"]["objectives"]}
    budget = rows["trigger-fib"]["budget_remaining"]
    canary_p99 = (
        rows["canary"].get("measured_ms", {}).get("p99")
    )
    unattr = armed["unattributed_fraction"] or 0.0
    completed = armed["canary"]["completed"] if armed["canary"] else 0
    return {
        "ok": bool(
            armed["fib"] == ctl["fib"]
            and completed > 0
            and unattr < 0.01
            and armed["canary_fires_fast"] == 0
            and breach["canary_fires_fast"] == 1
            and breakers_closed
        ),
        "fib_identical_with_canary": armed["fib"] == ctl["fib"],
        "canary_probes_completed": completed,
        "canary_unattributed_fraction": round(unattr, 4),
        "clean_sentinel_fires": armed["canary_fires_fast"],
        "breach_sentinel_fires": breach["canary_fires_fast"],
        "breach_probes": breach["canary"],
        "breakers_closed": bool(breakers_closed),
        "slo_budget_remaining": budget,
        "canary_p99_ms": canary_p99,
        "trigger_fib_row": rows["trigger-fib"],
        "sheds": armed["slo"]["sheds"],
        "wall_s": round(time.perf_counter() - t0, 1),
    }


def stage_slo_overhead(k, B, reps=24, inner=4):
    """ISSUE 20 overhead gate: the SLO plane's hot seams — the
    convergence end-cut hook at ``fib_commit`` plus the sentinel check
    cadence — armed vs disarmed on the full begin→dispatch→commit
    cycle, with the convergence tracker armed in BOTH arms (the hook
    only fires inside events: that is the worst case being measured).
    Paired-median discipline (overload_overhead): alternate arm order,
    median of per-rep deltas; ok <2%."""
    from holo_tpu.spf.backend import TpuSpfBackend
    from holo_tpu.telemetry import convergence
    from holo_tpu.telemetry import slo as slo_mod

    topo, masks = _make(k, B)
    backend = TpuSpfBackend()
    backend.compute_whatif(topo, masks)  # warm: compile + graph cache
    convergence.configure(8192)

    def sample():
        t0 = time.perf_counter()
        for _ in range(inner):
            eid = convergence.begin("lsa")
            with convergence.activation((eid,)):
                backend.compute_whatif(topo, masks)
                convergence.fib_commit(eids=(eid,))
        return (time.perf_counter() - t0) / inner

    armed_times, disarmed_times = [], []

    def armed():
        slo_mod.configure(check_every=16)
        try:
            return sample()
        finally:
            slo_mod.configure(False)

    def disarmed():
        return sample()

    arms = ((disarmed, disarmed_times), (armed, armed_times))
    for rep in range(reps):
        order = arms if rep % 2 == 0 else arms[::-1]
        for fn, times in order:
            times.append(fn())
    convergence.configure(0)
    disarmed_ms = float(np.median(disarmed_times) * 1e3)
    armed_delta = float(
        np.median([a - b for a, b in zip(armed_times, disarmed_times)])
        * 1e3
    )
    armed_pct = armed_delta / disarmed_ms * 100.0 if disarmed_ms else 0.0
    return {
        "ok": bool(armed_pct < 2.0),
        "disarmed_ms": round(disarmed_ms, 4),
        "armed_paired_delta_ms": round(armed_delta, 5),
        "slo_overhead_pct": round(armed_pct, 3),
        "reps": reps,
        "inner": inner,
    }


def stage_multipath_spf(k, B, reps=3):
    """ISSUE 10 acceptance row: the vectorized multipath kernel swept
    over parent-set widths k ∈ {1, 2, 4, 8} on a tied-weight random
    topology.  k=1 rides the unchanged single-parent program (its row
    is the baseline the deltas compare against); every k>1 row is
    digest-gated bit-identical to the scalar multipath oracle and
    reports runs/s plus the compile-time cost_analysis deltas of the
    widened program."""
    import hashlib

    from holo_tpu import telemetry
    from holo_tpu.spf.backend import ScalarSpfBackend, TpuSpfBackend
    from holo_tpu.spf.synth import random_ospf_topology
    from holo_tpu.telemetry import profiling

    # Tied weights (small cost universe) force real ECMP sets.
    topo = random_ospf_topology(
        k * 10, n_networks=k * 2, extra_p2p=k * 20, max_cost=4, seed=11
    )
    tpu = TpuSpfBackend()
    oracle = ScalarSpfBackend()
    profiling.set_device_profiling(True)
    rows = {}
    base_runs = None
    base_cost = None
    parity_ok = True
    digests = {}
    try:
        for kk in (1, 2, 4, 8):
            res = tpu.compute(topo, multipath_k=kk)  # warm/compile
            ref = oracle.compute(topo, multipath_k=kk)
            h = hashlib.sha256()
            for f in (
                "dist", "parent", "hops", "nexthop_words",
                "parents", "pdist", "pweight", "npaths", "nh_weights",
            ):
                a, b = getattr(res, f), getattr(ref, f)
                if (a is None) != (b is None) or (
                    a is not None and not np.array_equal(a, b)
                ):
                    parity_ok = False
                if a is not None:
                    h.update(np.ascontiguousarray(a).tobytes())
            digests[kk] = h.hexdigest()[:16]
            times = []
            for _ in range(reps):
                t0 = time.perf_counter()
                for _ in range(4):
                    tpu.compute(topo, multipath_k=kk)
                times.append((time.perf_counter() - t0) / 4)
            med = float(np.median(times))
            cost = {}
            for (site, sig), ent in profiling.cost_table().items():
                if site == "spf.one" and sig and sig[-1] == kk:
                    cost = {
                        "flops": ent.get("flops"), "bytes": ent.get("bytes")
                    }
            if kk == 1:
                base_runs, base_cost = 1.0 / med, cost
            rows[f"k{kk}"] = {
                "runs_per_sec": round(1.0 / med, 2),
                "vs_k1": round((1.0 / med) / base_runs, 3)
                if base_runs
                else None,
                "cost_analysis": cost,
                "cost_bytes_vs_k1": (
                    round(cost["bytes"] / base_cost["bytes"], 2)
                    if cost.get("bytes") and (base_cost or {}).get("bytes")
                    else None
                ),
                "digest": digests[kk],
            }
    finally:
        profiling.set_device_profiling(False)
    return {
        "ok": bool(parity_ok),
        "oracle_parity": parity_ok,
        "n_vertices": topo.n_vertices,
        "n_edges": topo.n_edges,
        "rows": rows,
        "telemetry": telemetry.snapshot(prefix="holo_spf_dispatch"),
    }


def stage_multipath_overhead(k, B, reps=32, inner=4):
    """ISSUE 10 overhead gate: with multipath OFF (k=1) the dispatch
    must ride the unchanged single-parent kernel — the widened planes
    cost <2% (paired-median) vs the same backend asked without the
    multipath_k argument at all (the pre-change call shape)."""
    from holo_tpu.spf.backend import TpuSpfBackend

    topo, _masks = _make(k, B)
    be = TpuSpfBackend()
    for _ in range(12):
        be.compute(topo)  # warm both call shapes (same jit underneath)
        be.compute(topo, multipath_k=1)

    def sample(fn):
        t0 = time.perf_counter()
        for _ in range(inner):
            fn()
        return (time.perf_counter() - t0) / inner

    bare_times, mp_times = [], []
    arms = (
        (lambda: be.compute(topo), bare_times),
        (lambda: be.compute(topo, multipath_k=1), mp_times),
    )
    for rep in range(reps):
        order = arms if rep % 2 == 0 else arms[::-1]
        for fn, times in order:
            times.append(sample(fn))
    bare_ms = float(np.median(bare_times) * 1e3)
    delta = float(
        np.median([a - b for a, b in zip(mp_times, bare_times)]) * 1e3
    )
    pct = delta / bare_ms * 100.0 if bare_ms else 0.0
    return {
        "ok": bool(pct < 2.0),
        "bare_ms": round(bare_ms, 4),
        "k1_paired_delta_ms": round(delta, 5),
        "k1_overhead_pct": round(pct, 3),
        "reps": reps,
        "inner": inner,
    }


def stage_gnmi_fanout(n_routers, events, big=1000, small_fleet=10):
    """ISSUE 11 acceptance row: the shared-delta gNMI fan-out engine
    serving a subscriber fleet riding the seeded convergence storm.

    Two arms of the SAME seeded storm — a 10-subscriber fleet and a
    1000-subscriber fleet (mixed SAMPLE / SAMPLE+suppress / ON_CHANGE
    sessions over the holo-telemetry subtree) — with the engine ticked
    at deterministic virtual times via the storm's event hook.  Gates:

    - per-tick shared-render cost stays ~O(1) in subscriber count
      (p50 tick wall ratio 10 -> 1000 subscribers <= 1.5x);
    - subscriber output byte-identical to the per-subscriber-walk
      fallback path across the whole run: a legacy ``_SubSampler``
      twin steps over the exact per-tick snapshots the engine consumed
      and must produce the identical serialized notification stream;
    - p99 update-delivery latency (tick start -> consumer dequeue,
      measured by concurrent drainer threads) reported per arm.
    """
    import queue as queue_mod
    import threading
    import types

    import holo_tpu.daemon.gnmi_server as gsrv
    from holo_tpu import telemetry
    from holo_tpu.spf.synth_storm import run_convergence_storm
    from holo_tpu.telemetry.provider import TelemetryStateProvider

    provider = TelemetryStateProvider()
    TICK = 0.5  # engine base tick (virtual seconds)

    def make_sub(path, interval_s=None, suppress=False, heartbeat_s=None,
                 mode=None):
        s = gsrv.pb.Subscription()
        s.path.CopyFrom(gsrv.str_to_path(path))
        s.mode = mode if mode is not None else gsrv.pb.SAMPLE
        if interval_s:
            s.sample_interval = int(interval_s * 1e9)
        s.suppress_redundant = suppress
        if heartbeat_s:
            s.heartbeat_interval = int(heartbeat_s * 1e9)
        return s

    class _LatencyQueue(queue_mod.Queue):
        """Bounded queue recording the ENQUEUE instant per item, so a
        backlog item drained after the next tick still reports its true
        age (measuring against the latest tick's start would understate
        exactly the tail the p99 exists to expose)."""

        def __init__(self, maxsize=0):
            super().__init__(maxsize=maxsize)
            from collections import deque as _deque

            self.stamps = _deque()

        def put_nowait(self, item):
            super().put_nowait(item)  # Full propagates: no stamp
            self.stamps.append(time.perf_counter())

    def run_arm(n_subs):
        box: dict = {}
        ticks: list[float] = []
        renders: list[float] = []
        delivers: list[float] = []
        latencies: list[float] = []
        engine_seq: list[bytes] = []
        legacy_seq: list[bytes] = []
        delivered = [0]
        dropped = [0]
        stop = threading.Event()
        threads: list[threading.Thread] = []

        def hook(net, i, now):
            if "svc" not in box:
                stub = types.SimpleNamespace(
                    lock=threading.RLock(),
                    northbound=types.SimpleNamespace(
                        get_state=lambda p=None: provider.get_state(None)
                    ),
                )
                svc = gsrv.GnmiService(
                    stub, shared_fanout=True, fanout_tick=TICK
                )
                svc.fanout._clock = net.loop.clock.now
                # Deterministic timestamps (epoch ids): the engine and
                # the legacy twin stamp identically, so the identity
                # gate compares full wire bytes.
                svc._clock_ns = lambda: svc.fanout._epoch
                box["svc"] = svc
                # The identity cursor fires at EVERY engine tick (the
                # 10ms interval floor is below any storm gap): its
                # epoch cursor then always sits one epoch back, where
                # the epoch comparison and the legacy value diff are
                # provably the same set.
                ident = make_sub(
                    "holo-telemetry/metric", interval_s=0.01, suppress=True
                )
                box["ident_sub"] = ident
                box["sampler"] = gsrv._SubSampler(ident, now=now)
                # Identity subscriber (queue 0: drained in-order here,
                # never by the latency drainers) + the mixed fleet.
                qs = []
                for k in range(n_subs):
                    q = _LatencyQueue(
                        maxsize=gsrv.SUBSCRIBE_QUEUE_DEPTH
                    )
                    sid = svc._add_subscriber(q)
                    if k == 0:
                        subs = [ident]
                    elif k % 5 == 4:
                        subs = [make_sub(
                            "holo-telemetry/metric",
                            mode=gsrv.pb.ON_CHANGE,
                            heartbeat_s=TICK * 8,
                        )]
                    elif k % 5 == 3:
                        subs = [make_sub(
                            "holo-telemetry/metric", interval_s=TICK * 2
                        )]
                    else:
                        subs = [make_sub(
                            "holo-telemetry/metric", interval_s=TICK,
                            suppress=True,
                        )]
                    svc.fanout.attach(q, sid, subs)
                    qs.append(q)
                box["queues"] = qs
                box["t0"] = [0.0]
                # Concurrent drainers: delivery latency = tick start ->
                # dequeue, the consumer-side number the gate reports.
                n_drain = 4 if n_subs >= 64 else 1
                fleet = qs[1:]
                shard = max(1, (len(fleet) + n_drain - 1) // n_drain)
                for d in range(n_drain):
                    mine = fleet[d * shard:(d + 1) * shard]
                    if not mine:
                        continue

                    def drain(mine=mine):
                        while not stop.is_set():
                            got = False
                            for q in mine:
                                try:
                                    q.get_nowait()
                                except queue_mod.Empty:
                                    continue
                                got = True
                                try:
                                    t_enq = q.stamps.popleft()
                                except IndexError:
                                    # Enqueue-stamp race window (item
                                    # visible before its stamp):
                                    # fall back to the tick start.
                                    t_enq = box["t0"][0]
                                latencies.append(
                                    time.perf_counter() - t_enq
                                )
                            if not got:
                                stop.wait(0.001)

                    t = threading.Thread(target=drain, daemon=True)
                    t.start()
                    threads.append(t)
            svc = box["svc"]
            # ONE snapshot per hook: the engine tick and the legacy
            # twin both consume it, so the identity gate compares the
            # two render paths, not two racing fetches.
            state = provider.get_state(None)
            t0 = time.perf_counter()
            box["t0"][0] = t0
            summary = svc.fanout.tick_now(now, state=state)
            if summary["fired"]:
                ticks.append(time.perf_counter() - t0)
                renders.append(summary["render_seconds"])
                delivers.append(summary["deliver_seconds"])
                delivered[0] += summary["delivered"]
                dropped[0] += summary["dropped"]
            q0 = box["queues"][0]
            while True:
                try:
                    engine_seq.append(
                        q0.get_nowait().SerializeToString()
                    )
                except queue_mod.Empty:
                    break
            if box["sampler"].advance_if_due(now):
                out = svc._sample_notif(box["sampler"], state)
                if out is not None:
                    legacy_seq.append(out.SerializeToString())

        try:
            _report, _digest, _net = run_convergence_storm(
                n_routers=n_routers, events=events, seed=17,
                event_hook=hook,
            )
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=2.0)
        arr = np.sort(np.asarray(ticks, np.float64)) * 1e3
        ren = np.sort(np.asarray(renders, np.float64)) * 1e3
        dlv = np.sort(np.asarray(delivers, np.float64)) * 1e3
        lat = np.sort(np.asarray(latencies, np.float64)) * 1e3
        pick = lambda a, q: (
            float(a[min(len(a) - 1, int(q * (len(a) - 1)))]) if len(a) else None
        )
        return {
            "subscribers": n_subs,
            "ticks": len(ticks),
            "tick_p50_ms": round(pick(arr, 0.5) or 0.0, 4),
            "tick_p95_ms": round(pick(arr, 0.95) or 0.0, 4),
            # The gated quantity: snapshot+diff+render, shared across
            # every subscriber — vs the honest O(N) delivery floor.
            "render_p50_ms": round(pick(ren, 0.5) or 0.0, 4),
            "render_p95_ms": round(pick(ren, 0.95) or 0.0, 4),
            "deliver_p50_ms": round(pick(dlv, 0.5) or 0.0, 4),
            "delivered": delivered[0],
            "dropped": dropped[0],
            "deliveries_measured": len(latencies),
            "delivery_p50_ms": round(pick(lat, 0.5), 4) if len(lat) else None,
            "delivery_p99_ms": round(pick(lat, 0.99), 4) if len(lat) else None,
            "identical_to_walk_path": engine_seq == legacy_seq,
            "identity_notifs": len(engine_seq),
            "fanout": box["svc"].fanout.stats(),
        }

    t_start = time.perf_counter()
    arm_small = run_arm(small_fleet)
    snap_before_big = telemetry.snapshot(prefix="holo_gnmi_fanout_shared")
    arm_big = run_arm(big)
    snap_after_big = telemetry.snapshot(prefix="holo_gnmi_fanout_shared")
    renders_big_arm = sum(snap_after_big.values()) - sum(
        snap_before_big.values()
    )
    ratio = (
        arm_big["render_p50_ms"] / arm_small["render_p50_ms"]
        if arm_small["render_p50_ms"]
        else None
    )
    tick_ratio = (
        arm_big["tick_p50_ms"] / arm_small["tick_p50_ms"]
        if arm_small["tick_p50_ms"]
        else None
    )
    ok = bool(
        ratio is not None
        and ratio <= 1.5
        and arm_small["identical_to_walk_path"]
        and arm_big["identical_to_walk_path"]
        and arm_small["identity_notifs"] > 0
        and arm_big["delivered"] > 0
        and arm_big["deliveries_measured"] > 0
    )
    return {
        "ok": ok,
        "n_routers": n_routers,
        "events": events,
        "render_p50_ratio_big_vs_small": round(ratio, 3) if ratio else None,
        # The whole tick including the O(N) bounded-queue put floor —
        # reported honestly next to the gated shared-render ratio.
        "tick_p50_ratio_big_vs_small": (
            round(tick_ratio, 3) if tick_ratio else None
        ),
        "arm_small": arm_small,
        "arm_big": arm_big,
        # Renders in the big arm stay O(buckets): the whole point.
        "shared_renders_big_arm": renders_big_arm,
        "renders_per_delivery_big_arm": round(
            renders_big_arm / arm_big["delivered"], 5
        )
        if arm_big["delivered"]
        else None,
        "wall_s": round(time.perf_counter() - t_start, 1),
        "telemetry": telemetry.snapshot(prefix="holo_gnmi_fanout"),
    }


def stage_fanout_overhead(reps=300, warm=40):
    """ISSUE 11 overhead gate: on the 1-SUBSCRIBER arm the shared-delta
    machinery (store diff + epoch stamping + render cache + bounded-
    queue put) must cost <2% paired-median against the legacy
    per-subscriber walk (``_SubSampler`` + ``_sample_notif``) stepping
    over the SAME snapshots at the SAME times.  The registry is
    pre-populated so the walk cost is the realistic denominator, and a
    probe counter moves every tick (worst case: every tick renders)."""
    import queue as queue_mod
    import threading
    import types

    import holo_tpu.daemon.gnmi_server as gsrv
    from holo_tpu import telemetry
    from holo_tpu.telemetry.provider import TelemetryStateProvider

    fam = telemetry.counter(
        "holo_fanout_ovh_fill_total", "walk-cost filler", ("i",)
    )
    for i in range(600):
        fam.labels(i=str(i)).inc()
    probe = telemetry.counter("holo_fanout_ovh_probe_total")
    provider = TelemetryStateProvider()
    TICK = 0.5
    stub = types.SimpleNamespace(
        lock=threading.RLock(),
        northbound=types.SimpleNamespace(
            get_state=lambda p=None: provider.get_state(None)
        ),
    )
    svc = gsrv.GnmiService(stub, shared_fanout=True, fanout_tick=TICK)
    now = [0.0]
    svc.fanout._clock = lambda: now[0]
    svc._clock_ns = lambda: 7
    sub = gsrv.pb.Subscription()
    sub.path.CopyFrom(gsrv.str_to_path("holo-telemetry/metric"))
    sub.mode = gsrv.pb.SAMPLE
    sub.sample_interval = int(TICK * 1e9)
    sub.suppress_redundant = True
    q_e: queue_mod.Queue = queue_mod.Queue(maxsize=4096)
    svc.fanout.attach(q_e, svc._add_subscriber(q_e), [sub])
    sampler = gsrv._SubSampler(sub, now=0.0)

    def drain(q):
        while True:
            try:
                q.get_nowait()
            except queue_mod.Empty:
                return

    engine_t, legacy_t = [], []

    def engine_arm(state):
        svc.fanout.tick_now(now[0], state=state)
        drain(q_e)

    def legacy_arm(state):
        if sampler.advance_if_due(now[0]):
            svc._sample_notif(sampler, state)

    for rep in range(warm + reps):
        probe.inc()
        state = provider.get_state(None)
        now[0] += TICK
        arms = ((engine_arm, engine_t), (legacy_arm, legacy_t))
        for fn, sink in arms if rep % 2 == 0 else arms[::-1]:
            t0 = time.perf_counter()
            fn(state)
            if rep >= warm:
                sink.append(time.perf_counter() - t0)
            # Both arms advanced their timers for this instant; the
            # next rep gets a fresh due tick for each.
    deltas = [a - b for a, b in zip(engine_t, legacy_t)]
    legacy_ms = float(np.median(legacy_t) * 1e3)
    engine_ms = float(np.median(engine_t) * 1e3)
    delta_ms = float(np.median(deltas) * 1e3)
    pct = delta_ms / legacy_ms * 100.0 if legacy_ms else 0.0
    return {
        "ok": bool(pct < 2.0),
        "engine_ms": round(engine_ms, 4),
        "walk_ms": round(legacy_ms, 4),
        "paired_delta_ms": round(delta_ms, 5),
        "overhead_pct": round(pct, 3),
        "reps": reps,
    }


def stage_device_trace():
    """ROADMAP item-5 carry-over: one real ``jax.profiler.trace()``
    around a seeded SPF dispatch when a TPU is attached; on any other
    platform the row says ``captured: false`` with the reason."""
    import tempfile

    from holo_tpu.telemetry import profiling

    row = profiling.capture_device_trace(
        tempfile.mkdtemp(prefix="holo-device-trace-")
    )
    row["ok"] = True  # informational row by contract
    return row


def stage_explain_spf(k, B, reps=8):
    """ISSUE 12 acceptance row: the dispatch observatory over a seeded
    workload.  Gates: (a) every gather-engine bucket at this scale is
    classified memory-bound by the roofline join (the known truth the
    tropical-matmul PR must flip); (b) the k ∈ {1,2,4,8} multipath
    sweep attributes the fixpoint's A-lane gather bytes per k (ROADMAP
    carry-over — the tropical engine's before-number, persisted via the
    bench ledger); (c) two same-seed deterministic passes produce
    byte-identical sketch serializations + reports; (d) the regression
    sentinel stays silent on the ledger-seeded clean run and flags a
    fault-injected dispatch delay."""
    import hashlib
    import os
    import tempfile

    from holo_tpu.pipeline import tuner as tuner_mod
    from holo_tpu.resilience import faults
    from holo_tpu.spf.backend import TpuSpfBackend
    from holo_tpu.spf.synth import random_ospf_topology
    from holo_tpu.telemetry import observatory, profiling

    topo, masks = _make(k, B)
    # Tied weights force real multipath sets (the A-lane target).
    mp_topo = random_ospf_topology(
        80, n_networks=16, extra_p2p=160, max_cost=4, seed=11
    )

    def workload(be, one=reps, whatif=max(reps // 2, 2)):
        for _ in range(one):
            be.compute(topo)
        for _ in range(whatif):
            be.compute_whatif(topo, masks)
        for kk in (1, 2, 4, 8):
            # 4 reps per k: the first dispatch's device stage reads
            # artificially fast (the async execute overlaps the fresh
            # compile's AOT cost capture) — the sentinel baseline must
            # be seeded from the steady-state majority.
            for _ in range(4):
                be.compute(mp_topo, multipath_k=kk)

    ledger = tempfile.mktemp(prefix="holo-obs-ledger-", suffix=".json")
    # -- pass 1 (wall clock): honest roofline + the sentinel story.
    # The tuner rides along so the explore phase measures EVERY gather
    # engine (cost entries + verdict per engine, not just the pinned
    # default) and the explain surface has a win/loss ledger.
    tuner = tuner_mod.configure_engine_tuner()
    obs = observatory.configure(check_every=4, ledger_path=ledger)
    profiling.set_device_profiling(True)
    try:
        be = TpuSpfBackend()
        workload(be)
        roof = obs.roofline()
        gather_rows = [
            r
            for r in roof
            if r["site"] in ("spf.one", "spf.whatif")
            and r["engine"] in _GATHER_ENGINES + ("mp",)
        ]
        memory_bound_ok = bool(gather_rows) and all(
            r["verdict"] == "memory-bound" for r in gather_rows
        )
        # k-sweep A-lane attribution: the mp_topo buckets per k.
        from holo_tpu.parallel.mesh import mesh_cache_key
        from holo_tpu.pipeline.tuner import shape_bucket

        k_sweep = {}
        k1_bytes = None
        for kk in (1, 2, 4, 8):
            want = list(
                shape_bucket(
                    mp_topo.n_vertices, mp_topo.n_edges, 1,
                    mesh_cache_key(), k=kk,
                )
            )
            row = next(
                (
                    r
                    for r in roof
                    if r["site"] == "spf.one" and r["bucket"] == want
                ),
                None,
            )
            if row is None:
                continue
            if kk == 1:
                k1_bytes = row["bytes"]
            k_sweep[f"k{kk}"] = {
                "engine": row["engine"],
                "gather_bytes": row["bytes"],
                "flops": row["flops"],
                "ai_flops_per_byte": row["ai_flops_per_byte"],
                "verdict": row["verdict"],
                "bytes_vs_k1": (
                    round(row["bytes"] / k1_bytes, 3)
                    if k1_bytes
                    else None
                ),
                "device_p50_ms": (
                    round(row["device_p50_s"] * 1e3, 4)
                    if row.get("device_p50_s") is not None
                    else None
                ),
            }
        # Clean pass over the now-seeded ledger: silence required.
        # checkpoint() closes each phase so every key has a baseline
        # BEFORE the injected regression, regardless of whether its
        # count crossed a check_every boundary (the tuner spreads
        # dispatches across engine keys).
        obs.checkpoint()
        workload(be)
        clean_sentinel = obs.checkpoint()
        sentinel_clean = clean_sentinel["flags"] == 0
        # Fault-injected dispatch delay: the sentinel (not the
        # breaker) must notice a slowed-but-succeeding bucket.
        with faults.inject(
            faults.FaultPlan(dispatch_delay={"spf.dispatch": 0.02})
        ):
            for _ in range(12):
                be.compute(topo)
        sentinel_flagged = obs.checkpoint()["flags"] > 0
        whatif_q = next(
            (
                r
                for r in obs.cost_centers()
                if r["site"] == "spf.whatif" and r["stage"] == "device"
            ),
            None,
        )
        # -- passes 2+3 (deterministic timer, small fixed shape):
        # byte-identity is a structural property — it must hold at any
        # scale, so the digest passes use a bounded workload.
        from holo_tpu.spf.synth import fat_tree_topology, whatif_link_failure_masks

        dtopo = fat_tree_topology(k=12, seed=3)
        dmasks = whatif_link_failure_masks(dtopo, 8, seed=4)
        digests = []
        for _ in range(2):
            # Fresh tuner per pass: its explore counters are part of
            # the dispatch sequence, and identical passes must start
            # from identical state.
            tuner_mod.configure_engine_tuner()
            obs_d = observatory.configure(check_every=4)
            profiling.set_stage_timer(observatory.DeterministicTimer())
            be_d = TpuSpfBackend()
            for _ in range(4):
                be_d.compute(dtopo)
            be_d.compute_whatif(dtopo, dmasks)
            for kk in (1, 2):
                be_d.compute(dtopo, multipath_k=kk)
            h = hashlib.sha256(obs_d.serialize())
            h.update(
                json.dumps(obs_d.report(), sort_keys=True).encode()
            )
            digests.append(h.hexdigest()[:16])
            profiling.set_stage_timer(None)
        digest_identical = digests[0] == digests[1]
    finally:
        profiling.set_stage_timer(None)
        profiling.set_device_profiling(False)
        observatory.configure(enabled=False)
        tuner_mod.reset_engine_tuner()
        try:
            os.unlink(ledger)
        except OSError:
            pass
    row = {
        "ok": bool(
            memory_bound_ok
            and digest_identical
            and sentinel_clean
            and sentinel_flagged
        ),
        "n_vertices": topo.n_vertices,
        "memory_bound_ok": memory_bound_ok,
        "gather_buckets": len(gather_rows),
        "verdicts": sorted(
            {f"{r['engine']}:{r['verdict']}" for r in gather_rows}
        ),
        "k_sweep": k_sweep,
        "digests": digests,
        "digest_identical": digest_identical,
        "sentinel_clean": sentinel_clean,
        "clean_regressions": clean_sentinel["regressed"],
        "sentinel_flagged": sentinel_flagged,
        "tuner_ledger": tuner.ledger(),
    }
    # Ledger scalars (the tropical engine's before-numbers).
    if k_sweep.get("k1"):
        row["k1_gather_bytes_mb"] = round(
            k_sweep["k1"]["gather_bytes"] / 1e6, 4
        )
    if k_sweep.get("k8"):
        row["k8_gather_bytes_mb"] = round(
            k_sweep["k8"]["gather_bytes"] / 1e6, 4
        )
    if whatif_q is not None:
        row["whatif_device_p50_ms"] = round(whatif_q["p50_s"] * 1e3, 4)
    return row


def stage_tropical_spf(ks=(30, 60, 90), B=128, cpu_runs=8, reps=2):
    """ISSUE 13 acceptance: the tropical min-plus matmul engine vs the
    best-recorded gather engine vs the scalar C++ baseline over a
    1k->10k-vertex fat-tree sweep (full SPF what-if batches, parity
    gated bit-for-bit), with the roofline story the PR-12 observatory
    taught us to demand: cost_analysis() flops/bytes per engine, the
    arithmetic-intensity ratio, and the ridge-point verdict.  The
    PR-12 before-numbers (k{1,8}_gather_bytes_mb, whatif_device_p50_ms
    from the persisted bench ledger) ride the row so the flops-moved
    claim is graded against the recorded gather-era baseline."""
    import jax

    from holo_tpu.ops import tropical as trop
    from holo_tpu.ops.graph import build_ell
    from holo_tpu.ops.spf_engine import (
        device_graph_from_ell,
        spf_whatif_batch,
    )
    from holo_tpu.telemetry import observatory, profiling

    deadline = time.monotonic() + 1100  # soft cap under STAGE_TIMEOUT
    profiling.set_device_profiling(True)  # arms cost_analysis capture
    sweep = {}
    parity_all = True
    top = None  # the largest completed size's row
    try:
        for k in ks:
            if time.monotonic() > deadline and sweep:
                sweep["truncated"] = f"soft deadline before k={k}"
                break
            topo, masks = _make(k, B)
            ell = build_ell(topo, n_atoms=64)
            g = jax.device_put(device_graph_from_ell(ell))
            masks_dev = jax.device_put(masks)
            root = topo.root

            def timed(step, *args):
                out = step(*args)
                jax.block_until_ready(out)  # warm: compile + first run
                times = []
                for _ in range(reps):
                    t0 = time.perf_counter()
                    out = step(*args)
                    jax.block_until_ready(out)
                    times.append(time.perf_counter() - t0)
                return out, sum(times) / reps

            step_g = jax.jit(
                lambda gr, ms: spf_whatif_batch(gr, root, ms, engine="seq")
            )
            out_g, dt_g = timed(step_g, g, masks_dev)

            t0 = time.perf_counter()
            tt_host, meta = trop.build_tiles_host(
                ell.in_src, ell.in_cost, ell.in_valid
            )
            tile_marshal_ms = (time.perf_counter() - t0) * 1e3
            tt = jax.device_put(tt_host)
            rr = jax.device_put(
                trop.repair_rows_host(topo.edge_dst, masks, topo.n_vertices)
            )
            step_t = jax.jit(
                lambda gr, tl, ms, rw: trop.tropical_whatif_batch(
                    gr, tl, root, ms, rw
                )
            )
            out_t, dt_t = timed(step_t, g, tt, masks_dev, rr)

            # Parity: every plane, every scenario, bit-for-bit.
            parity = all(
                bool(
                    np.array_equal(
                        np.asarray(getattr(out_g, f)),
                        np.asarray(getattr(out_t, f)),
                    )
                )
                for f in ("dist", "parent", "hops", "nexthops")
            )
            parity_all = parity_all and parity

            # The roofline join: compile-time flops/bytes per engine,
            # AI ratio, ridge verdict against the attached device's
            # peaks (the peaks row names them).
            cost_t = profiling.record_cost(
                "bench.tropical", step_t, g, tt, masks_dev, rr,
                shape_sig=("tropical", k, B),
            ) or {}
            cost_g = profiling.record_cost(
                "bench.gather", step_g, g, masks_dev,
                shape_sig=("seq", k, B),
            ) or {}
            peaks = observatory.device_peaks()

            def ai(c):
                return (
                    c["flops"] / c["bytes"]
                    if c.get("bytes") and c.get("flops") is not None
                    else None
                )

            ai_t, ai_g = ai(cost_t), ai(cost_g)
            row = {
                "n_vertices": topo.n_vertices,
                "n_edges": topo.n_edges,
                "batch": B,
                "parity_ok": parity,
                "gather_runs_per_sec": round(B / dt_g, 3),
                "tropical_runs_per_sec": round(B / dt_t, 3),
                "speedup_vs_gather": round(dt_g / dt_t, 3),
                "tile_block": meta["block"],
                "tiles": meta["pairs"],
                "tile_slots": meta["nb"] * meta["tm"],
                "tile_marshal_ms": round(tile_marshal_ms, 2),
                "tropical_cost": cost_t,
                "gather_cost": cost_g,
                "tropical_ai_flops_per_byte": (
                    round(ai_t, 6) if ai_t is not None else None
                ),
                "gather_ai_flops_per_byte": (
                    round(ai_g, 6) if ai_g is not None else None
                ),
                "ai_ratio_vs_gather": (
                    round(ai_t / ai_g, 3) if ai_t and ai_g else None
                ),
                "roofline_verdict": (
                    None
                    if ai_t is None or peaks is None
                    else (
                        "compute-bound"
                        if ai_t >= peaks.ridge
                        else "memory-bound"
                    )
                ),
                "peaks": peaks.source if peaks else None,
            }
            if k == max(ks):
                cpu_dist, cpu_rps, cpu_p50 = _cpu_baseline(
                    topo, masks, cpu_runs
                )
                check = np.asarray(out_t.dist[:cpu_runs])[
                    :, : topo.n_vertices
                ]
                row["cpu_ok"] = bool(np.array_equal(check, cpu_dist))
                row["cpu_runs_per_sec"] = cpu_rps
                row["cpu_p50_ms"] = cpu_p50
                parity_all = parity_all and row["cpu_ok"]
            sweep[f"v{topo.n_vertices}"] = row
            top = row
    finally:
        profiling.set_device_profiling(False)

    # The PR-12 before-numbers (recorded by explain_spf through the
    # bench ledger): the gather-era cost this engine exists to move.
    before = {}
    try:
        from pathlib import Path as _Path

        ledger = json.loads(
            _Path(__file__).with_name("BENCH_baseline.json").read_text()
        )
        for key in (
            "k1_gather_bytes_mb", "k8_gather_bytes_mb",
            "whatif_device_p50_ms",
        ):
            for mode in ("full", "small"):
                v = ledger.get(f"{mode}/explain_spf/{key}") or ledger.get(
                    f"{mode}/explain_spf_jaxcpu_small/{key}"
                )
                if v is not None:
                    before[key] = v
                    break
    except (OSError, ValueError):
        pass

    out = {
        "ok": bool(parity_all and top is not None),
        "sweep": sweep,
        "before_pr12": before,
    }
    if top is not None:
        # Ledger scalars at the largest (10k) point — the acceptance
        # gates: >= 5x the gather jaxcpu row, compute-bound (or the AI
        # >= 4x fallback) with the flops moved off gather bytes.
        out["n_vertices"] = top["n_vertices"]
        out["tropical_runs_per_sec"] = top["tropical_runs_per_sec"]
        out["gather_runs_per_sec"] = top["gather_runs_per_sec"]
        out["tropical_speedup_vs_gather"] = top["speedup_vs_gather"]
        if top.get("ai_ratio_vs_gather") is not None:
            out["tropical_ai_ratio"] = top["ai_ratio_vs_gather"]
        if top.get("cpu_runs_per_sec"):
            out["cpu_runs_per_sec"] = top["cpu_runs_per_sec"]
        out["meets_5x_vs_gather"] = top["speedup_vs_gather"] >= 5.0
        out["meets_roofline_gate"] = bool(
            top.get("roofline_verdict") == "compute-bound"
            or (
                top.get("ai_ratio_vs_gather") is not None
                and top["ai_ratio_vs_gather"] >= 4.0
            )
        )
    return out


def stage_partitioned_spf(small=False):
    """ISSUE 15 acceptance: the hierarchical partitioned SPF path over
    a 10k -> 100k vertex sweep, flat (BFS/greedy cut) vs multi-area
    (native ``partition_hint``) synth topologies, with per-stage
    marshal / partition-solve (bdist/dist/phase2) / stitch splits.

    Gates: partitioned-vs-MONOLITHIC digest parity on every arm
    (plain, what-if masks, multipath k=2, DeltaPath) at the 10k point
    where the monolithic padded program is still feasible; at >=100k
    the monolithic program is reported infeasible (the padded vertex
    axis would be a 131072-row dense gather plane per dispatch) and
    parity gates against the scalar oracle instead; delta re-solves
    must be BOUNDED (affected partitions + skeleton — asserted via
    resident stats and the ``holo_spf_delta_total`` disposition
    series)."""
    import hashlib

    from holo_tpu import telemetry
    from holo_tpu.ops.graph import diff_topologies
    from holo_tpu.spf.backend import ScalarSpfBackend, TpuSpfBackend
    from holo_tpu.spf.scalar import spf_reference
    from holo_tpu.spf.synth import (
        clone_topology,
        multiarea_topology,
        whatif_link_failure_masks,
    )

    deadline = time.monotonic() + 1300  # soft cap under STAGE_TIMEOUT

    def digest(res) -> str:
        h = hashlib.sha256()
        for f in (
            "dist", "parent", "hops", "nexthop_words",
            "parents", "pdist", "pweight", "npaths", "nh_weights",
        ):
            v = getattr(res, f, None)
            if v is not None:
                h.update(np.ascontiguousarray(v).tobytes())
        return h.hexdigest()[:16]

    def delta_incr() -> float:
        return telemetry.snapshot(prefix="holo_spf_delta").get(
            "holo_spf_delta_total{kind=weight,path="
            "partitioned-incremental}",
            0.0,
        )

    if small:
        specs = [
            ("multiarea_1k", 4, 16, 16, True, True),
            ("flat_1k", 4, 16, 16, False, True),
        ]
    else:
        specs = [
            # (row, areas, rows, cols, native hint, monolithic parity)
            ("multiarea_10k", 10, 32, 32, True, True),
            ("flat_10k", 10, 32, 32, False, True),
            ("multiarea_100k", 25, 64, 64, True, False),
            ("flat_100k", 25, 64, 64, False, False),
        ]
    sweep: dict = {}
    ok_all = True
    top = None
    for name, areas, rows_, cols, hinted, mono_arm in specs:
        if time.monotonic() > deadline and sweep:
            sweep["truncated"] = f"soft deadline before {name}"
            break
        topo = multiarea_topology(
            areas, rows_, cols, seed=3, hint=hinted
        )
        per = rows_ * cols
        part = TpuSpfBackend(
            partition_threshold=1, partition_max_part=per
        )
        t0 = time.perf_counter()
        r_plain = part.compute(topo)
        first_s = time.perf_counter() - t0
        res = part.partition_residents()[0]
        reps = 1 if topo.n_vertices > 20_000 else 2
        t0 = time.perf_counter()
        for _ in range(reps):
            r_plain = part.compute(topo)
        steady_s = (time.perf_counter() - t0) / reps
        row = {
            "n_vertices": topo.n_vertices,
            "n_edges": topo.n_edges,
            "native_hint": hinted,
            "parts": res.plan.n_parts,
            "skeleton": res.plan.n_skel,
            "cut_edges": int(res.plan.cut_src.shape[0]),
            "l_pad": res.plan.l_pad,
            "first_solve_s": round(first_s, 3),
            "solve_s": round(steady_s, 3),
            "runs_per_sec": round(1.0 / steady_s, 3),
            # Per-phase splits of the steady solve (the engine's own
            # walls: batched boundary solves, host stitch, seeded
            # final dist, pinned-halo phase 2).
            "splits_s": {
                k: round(v, 4) for k, v in res.timings.items()
            },
            "exchange_rounds": res.exchange_rounds,
        }
        # The soft cap must also interrupt WITHIN a row: a 100k row
        # whose arms overrun would otherwise blow the hard
        # STAGE_TIMEOUT mid-row and forfeit every completed row.  A
        # truncated row is emitted without its parity/delta gates and
        # never becomes `top`.
        if time.monotonic() > deadline:
            row["truncated"] = "soft deadline before parity arms"
            sweep[name] = row
            break
        parity = True
        # -- arms ------------------------------------------------------
        ref = spf_reference(topo)
        n_at = res.n_atoms
        oracle_ok = (
            np.array_equal(r_plain.dist, ref.dist)
            and np.array_equal(r_plain.parent, ref.parent)
            and np.array_equal(r_plain.hops, ref.hops)
            and np.array_equal(
                r_plain.nexthop_words, ref.nexthop_words(n_at)
            )
        )
        row["oracle_parity"] = bool(oracle_ok)
        parity &= oracle_ok
        if mono_arm:
            mono = TpuSpfBackend()
            oracle = ScalarSpfBackend()
            masks = whatif_link_failure_masks(topo, 4, seed=5)
            arms = {
                # r_plain is the steady-state partitioned result from
                # the timing loop above — same backend, same topology,
                # deterministic, so its digest IS the plain-arm digest
                # (no third full three-phase solve).
                "plain": (
                    digest(r_plain),
                    digest(mono.compute(topo)),
                ),
                "multipath_k2": (
                    digest(part.compute(topo, multipath_k=2)),
                    digest(mono.compute(topo, multipath_k=2)),
                ),
            }
            pw = part.compute_whatif(topo, masks)
            mw = mono.compute_whatif(topo, masks)
            arms["whatif"] = (
                "|".join(digest(x) for x in pw),
                "|".join(digest(x) for x in mw),
            )
            # Breaker-fallback arm: the oracle digest IS the fallback
            # result by construction (breaker.call's fallback lambda),
            # so gate partitioned vs oracle digests directly (the
            # partitioned digest is the plain arm's, already solved).
            arms["fallback_oracle"] = (
                arms["plain"][0],
                digest(oracle.compute(topo)),
            )
            row["arm_digests"] = {
                k: {"partitioned": a, "reference": b, "ok": a == b}
                for k, (a, b) in arms.items()
            }
            mono_parity = all(a == b for a, b in arms.values())
            row["monolithic_parity"] = mono_parity
            parity &= mono_parity
            # The k=2 / what-if arms left the resident off the k=1
            # chain — root it on `topo` so the DeltaPath arm below
            # measures a bounded re-solve, not a kp-flip re-marshal.
            part.compute(topo)
        else:
            row["monolithic"] = (
                "infeasible: padded monolithic program at "
                f"{topo.n_vertices} vertices (pow2 row axis "
                f"{1 << (topo.n_vertices - 1).bit_length()}) — "
                "partitioned is the only device path"
            )
        if time.monotonic() > deadline:
            row["truncated"] = "soft deadline before delta arm"
            sweep[name] = row
            break
        # -- DeltaPath arm: intra-area weight bump deep in the last
        # area; the re-solve must be bounded and counted.
        e = int(
            np.nonzero(
                (topo.edge_src >= (areas - 1) * per)
                & (topo.edge_dst >= (areas - 1) * per)
            )[0][0]
        )
        nxt = clone_topology(
            topo, cost={e: int(topo.edge_cost[e]) + 7}
        )
        d = diff_topologies(topo, nxt)
        before = delta_incr()
        if d is not None:
            nxt.link_delta(d)
        t0 = time.perf_counter()
        r_delta = part.compute(nxt)
        row["delta_ms"] = round((time.perf_counter() - t0) * 1e3, 2)
        # Re-fetch: a declined delta re-marshals a NEW resident under
        # the same key — stats must come from the serving object.
        res = part.partition_residents()[0]
        ref_d = spf_reference(nxt)
        delta_parity = np.array_equal(
            r_delta.dist, ref_d.dist
        ) and np.array_equal(r_delta.parent, ref_d.parent)
        row["delta_parity"] = bool(delta_parity)
        row["delta_disposition_counted"] = bool(delta_incr() > before)
        row["delta_resolved_parts"] = res.last_resolved
        row["delta_bounded"] = bool(
            res.last_resolved < res.plan.n_parts
        )
        parity &= delta_parity
        ok_all = (
            ok_all
            and parity
            and row["delta_disposition_counted"]
            and row["delta_bounded"]
        )
        sweep[name] = row
        top = row
    out = {
        "ok": bool(ok_all and top is not None),
        "sweep": sweep,
    }
    if top is not None:
        out["n_vertices"] = top["n_vertices"]
        out["partitioned_runs_per_sec"] = top["runs_per_sec"]
        out["partitioned_delta_ms"] = top["delta_ms"]
        out["partitioned_100k_ok"] = bool(
            not small
            and all(
                sweep.get(k, {}).get("oracle_parity")
                and sweep.get(k, {}).get("delta_parity")
                for k in ("multiarea_100k", "flat_100k")
                if k in sweep
            )
            and "flat_100k" in sweep
        )
    return out


def stage_observatory_overhead(k, B, reps=24, inner=2):
    """ISSUE 12 overhead gate: the armed observatory (sketch update +
    sentinel tick per sub-span) must cost <2% paired-median on the
    profiled dispatch path; the DISARMED cost is one module-global
    check inside profiling.stage (asserted structurally in
    tests/test_observatory.py).  Device profiling is ON in both arms so
    the delta isolates the observatory itself."""
    from holo_tpu.spf.backend import TpuSpfBackend
    from holo_tpu.telemetry import observatory, profiling

    topo, masks = _make(k, B)
    profiling.set_device_profiling(True)
    obs = observatory.configure(check_every=32)
    try:
        be = TpuSpfBackend()
        for _ in range(6):
            be.compute_whatif(topo, masks)  # warm: compile + sketches

        def sample():
            t0 = time.perf_counter()
            for _ in range(inner):
                be.compute_whatif(topo, masks)
            return (time.perf_counter() - t0) / inner

        armed_t, off_t = [], []
        arms = ((obs._observe, armed_t), (None, off_t))
        for rep in range(reps):
            order = arms if rep % 2 == 0 else arms[::-1]
            for observer, sink in order:
                profiling.set_observer(observer)
                sink.append(sample())
        sketches = len(obs._sketches)
    finally:
        observatory.configure(enabled=False)
        profiling.set_device_profiling(False)
    off_ms = float(np.median(off_t) * 1e3)
    delta = float(np.median([a - b for a, b in zip(armed_t, off_t)]) * 1e3)
    pct = delta / off_ms * 100.0 if off_ms else 0.0
    return {
        "ok": bool(pct < 2.0 and sketches > 0),
        "profiled_ms": round(off_ms, 4),
        "paired_delta_ms": round(delta, 5),
        "overhead_pct": round(pct, 3),
        "sketches": sketches,
        "reps": reps,
        "inner": inner,
    }


def stage_bgp_table(small):
    """ISSUE 16: device-resident BGP best-path over a full Internet
    table.  Three measurements, all gated on engine-level parity:

    1. PARITY (the gate): a synthetic multi-peer feed through the real
       BgpEngine twice — scalar decision process vs TpuBgpTableBackend —
       comparing the complete Loc-RIB snapshot (best route, nexthop
       sets, reject/ineligible reason strings, igp_cost side effects).
       Any mismatch fails the whole stage; the throughput rows below
       never excuse a wrong RIB.
    2. COLD FOLD: prefixes/s of the §9.1.2.2 fold kernel over a packed
       full-table plane (full: 512k prefixes x 64 peers; --small: 32k x
       16 — same code path, honestly labeled).  The feed is synthesized
       at the LANE level (the backend's own packed encoding) because the
       cold wall is the kernel, not the Python marshal the incremental
       path amortizes away.
    3. UPDATE BATCH: p99 wall of a scatter-k-rows + recompute-radius
       round — the steady-state UPDATE burst shape — with the donated
       scatter and the gathered `_decide` sub-fold.

    A scalar-loop row (the engine's `_best_path` over the parity feed)
    anchors the speedup claim, and the armed-profiler cost_analysis of
    the fold lands in the report for the roofline ledger.
    """
    import jax
    import jax.numpy as jnp

    from holo_tpu.ops import bgp_table as bt
    from holo_tpu.protocols.bgp_engine import (
        AdjRib,
        AsSegment,
        BaseAttrs,
        BgpEngine,
        Destination,
        NhtEntry,
        Route,
        RouteOrigin,
    )
    from holo_tpu.telemetry import profiling

    afs = "ipv4-unicast"
    n_prefixes, n_peers = (32_768, 16) if small else (524_288, 64)
    n_parity, parity_peers = (512, 8) if small else (2_048, 8)
    rng = np.random.default_rng(16)

    # -- 1. parity gate through the real engine pair ---------------------
    def build(backend):
        calls = []
        eng = BgpEngine(
            "bench", ibus_cb=lambda k, p: calls.append((k, p)),
            table_backend=backend,
        )
        eng.asn = 65000
        table = eng.tables[afs]
        for nh in range(parity_peers):
            table.nht[f"9.9.{nh}.1"] = NhtEntry(
                metric=int(rng2.integers(1, 64))
                if (nh % 5) else None  # every 5th next hop unresolvable
            )
        for i in range(n_parity):
            prefix = f"10.{(i >> 8) & 255}.{i & 255}.0/24"
            dest = table.prefixes.setdefault(prefix, Destination())
            for p in range(parity_peers):
                if rng2.random() < 0.4:
                    continue
                addr = f"1.1.1.{p + 1}"
                med = None if rng2.random() < 0.2 else int(
                    rng2.integers(0, 1000)
                )
                attrs = BaseAttrs(
                    origin=("Igp", "Egp", "Incomplete")[
                        int(rng2.integers(0, 3))
                    ],
                    as_path=(AsSegment(
                        "Sequence",
                        tuple(int(a) for a in rng2.integers(
                            1, 500, size=int(rng2.integers(1, 5))
                        )),
                    ),),
                    nexthop=f"9.9.{int(rng2.integers(0, parity_peers))}.1",
                    med=med,
                    local_pref=int(rng2.integers(50, 300))
                    if rng2.random() < 0.5 else None,
                )
                dest.adj_rib.setdefault(addr, AdjRib()).in_post = Route(
                    origin=RouteOrigin(
                        identifier=f"0.0.0.{p + 1}", remote_addr=addr
                    ),
                    attrs=attrs,
                    route_type="External" if p % 2 else "Internal",
                )
            table.queued.add(prefix)
            if backend is not None:
                backend.note_route_change(afs, prefix)
        return eng, table

    def snap(table):
        out = {}
        for prefix, dest in table.prefixes.items():
            out[prefix] = (
                None if dest.local is None
                else (dest.local.attrs, dest.local.route_type,
                      dest.local.igp_cost),
                dest.local_nexthops,
                tuple(sorted(
                    (a, adj.in_post.reject_reason,
                     adj.in_post.ineligible_reason, adj.in_post.igp_cost)
                    for a, adj in dest.adj_rib.items() if adj.in_post
                )),
            )
        return out

    mp_cfg = {
        "enabled": True, "ebgp_max": 4, "ibgp_max": 2,
        "allow_multiple_as": True,
    }
    rng2 = np.random.default_rng(17)
    s_eng, s_table = build(None)
    s_eng.multipath[afs] = dict(mp_cfg)
    rng2 = np.random.default_rng(17)  # identical feed for the device arm
    backend = bt.TpuBgpTableBackend()
    d_eng, d_table = build(backend)
    d_eng.multipath[afs] = dict(mp_cfg)
    t0 = time.perf_counter()
    s_eng.run_decision_process()
    scalar_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    profiling.set_device_profiling(True)  # cost_analysis capture
    try:
        d_eng.run_decision_process()
    finally:
        profiling.set_device_profiling(False)
    engine_device_s = time.perf_counter() - t0
    parity = snap(s_table) == snap(d_table)
    stats = backend.stats()

    # -- 2. cold fold over the packed full-table plane -------------------
    R, C = bt._pow2(n_prefixes), bt._pow2(n_peers)
    K = 64  # next-hop id space

    def nbias(a):  # the backend's u32->i32 order-preserving bias
        return (np.asarray(a, np.int64) - (1 << 31)).astype(np.int32)

    planes_np = np.zeros((bt.N_LANES, R, C), np.int32)
    occ = (rng.random((R, C)) < 0.5).astype(np.int32)
    occ[:, bt.LOCAL_COL] = 0  # peer columns only; local column empty
    occ[np.arange(R), 1 + rng.integers(0, C - 1, size=R)] = 1
    planes_np[bt.L_OCC] = occ
    planes_np[bt.L_LP] = nbias(
        0xFFFFFFFF - rng.integers(50, 300, size=(R, C), dtype=np.int64)
    )
    planes_np[bt.L_L1] = (
        rng.integers(1, 6, size=(R, C)) << 2
    ) | rng.integers(0, 3, size=(R, C))
    planes_np[bt.L_MED] = nbias(
        rng.integers(0, 1000, size=(R, C), dtype=np.int64)
    )
    planes_np[bt.L_FAS] = rng.integers(1, 64, size=(R, C))
    planes_np[bt.L_RT] = rng.integers(0, 2, size=(R, C))
    planes_np[bt.L_RID] = nbias(
        rng.integers(0, 1 << 32, size=(R, C), dtype=np.int64)
    )
    planes_np[bt.L_HASRID] = 1
    planes_np[bt.L_NH] = rng.integers(0, K, size=(R, C))
    planes_np[bt.L_PATH] = rng.integers(0, 4096, size=(R, C))
    planes_np[bt.L_LOOP] = (rng.random((R, C)) < 0.02).astype(np.int32)
    planes_np *= occ  # empty cells stay all-zero, as the backend writes
    planes_np[bt.L_OCC] = occ
    order = np.concatenate(
        [np.arange(1, C, dtype=np.int32), [bt.LOCAL_COL]]
    ).astype(np.int32)
    addr_rank = np.arange(C, dtype=np.int32)
    has_addr = (np.arange(C) != bt.LOCAL_COL).astype(np.int32)
    nht_enc = nbias(rng.integers(1, 65, size=K, dtype=np.int64))
    nht_res = (rng.random(K) < 0.9).astype(np.int32)
    nht_res[0] = 1
    mp_vec = np.array([1, 2, 4], np.int32)
    args = [
        jnp.asarray(a)
        for a in (order, addr_rank, has_addr, nht_enc, nht_res, mp_vec)
    ]
    planes = jnp.asarray(planes_np)
    profiling.set_device_profiling(True)
    try:
        out = bt.fold_planes(planes, *args)  # warm: compile
        jax.block_until_ready(out)
        profiling.record_cost(  # roofline numerators for the ledger
            "bgp.table.cold", bt.fold_planes, planes, *args,
            shape_sig=("cold", R, C),
        )
    finally:
        profiling.set_device_profiling(False)
    reps = 3 if small else 5
    cold_t = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = bt.fold_planes(planes, *args)
        jax.block_until_ready(out)
        cold_t.append(time.perf_counter() - t0)
    cold_s = float(np.median(cold_t))
    prefixes_per_sec = n_prefixes / cold_s if cold_s else 0.0

    # -- 3. UPDATE-burst rounds: donated scatter + radius recompute ------
    batch_k = 256 if small else 1_024
    radius = 4 * batch_k  # recompute radius: churned rows + neighbors
    rounds = 20 if small else 40
    upd_t = []
    for r in range(rounds):
        rows_idx = jnp.asarray(
            rng.choice(R, size=batch_k, replace=False).astype(np.int32)
        )
        fresh = jnp.asarray(
            planes_np[:, rng.integers(0, R, size=batch_k), :]
        )
        sub_idx = jnp.asarray(
            np.sort(rng.choice(R, size=radius, replace=False))
            .astype(np.int32)
        )
        t0 = time.perf_counter()
        planes = bt._scatter(planes, rows_idx, fresh)
        out = bt._decide(planes, sub_idx, *args)
        jax.block_until_ready(out)
        upd_t.append(time.perf_counter() - t0)
    upd = np.sort(np.asarray(upd_t[2:])) * 1e3  # drop compile rounds
    p99 = float(upd[min(len(upd) - 1, int(0.99 * len(upd)))])

    scalar_prefixes_per_sec = n_parity / scalar_s if scalar_s else 0.0
    return {
        "ok": bool(parity and stats["fallbacks"] == 0),
        "parity": bool(parity),
        "n_prefixes": n_prefixes,
        "n_peers": n_peers,
        "parity_feed": {"prefixes": n_parity, "peers": parity_peers},
        "bgp_prefixes_per_sec": round(prefixes_per_sec, 1),
        "cold_fold_ms": round(cold_s * 1e3, 3),
        "bgp_update_p99_ms": round(p99, 3),
        "update_batch": {"rows": batch_k, "radius": radius,
                         "rounds": rounds},
        "scalar_prefixes_per_sec": round(scalar_prefixes_per_sec, 1),
        "engine_device_s": round(engine_device_s, 3),
        "backend": stats,
        "cost_analysis": {
            f"{site}{list(sig)}": entry
            for (site, sig), entry in sorted(
                profiling.cost_table().items(), key=lambda kv: kv[0][0]
            )
            if site.startswith("bgp")
        },
    }


# -- bench regression ledger (ISSUE 11 satellite) ------------------------

# Scalar keys lifted from stage rows into the persisted ledger:
# (key, higher_is_better).
_LEDGER_KEYS = (
    ("runs_per_sec", True),
    ("cpu_runs_per_sec", True),
    ("requests_per_sec", True),
    ("batch_ms", False),
    ("p50_ms", False),
    ("cpu_p50_ms", False),
    ("tick_p50_ms", False),
    ("overhead_pct", False),
    ("disabled_overhead_pct", False),
    ("k1_overhead_pct", False),
    # ISSUE 12: the tropical-engine before-numbers — the k-sweep's
    # A-lane gather bytes and the measured what-if device p50 the
    # roofline attribution derives its rates from.
    ("k1_gather_bytes_mb", False),
    ("k8_gather_bytes_mb", False),
    ("whatif_device_p50_ms", False),
    # ISSUE 13: the tropical engine's own acceptance scalars — its
    # throughput at the sweep's largest point, the vs-gather speedup,
    # and the arithmetic-intensity ratio the roofline gate reads.
    ("tropical_runs_per_sec", True),
    ("tropical_speedup_vs_gather", True),
    ("tropical_ai_ratio", True),
    # ISSUE 15: the partitioned path's acceptance scalars — steady
    # full-solve throughput at the sweep's largest point and the
    # bounded DeltaPath re-solve wall.
    ("partitioned_runs_per_sec", True),
    ("partitioned_delta_ms", False),
    # ISSUE 16: the device BGP plane's acceptance scalars — cold
    # best-path throughput over the packed full table and the
    # UPDATE-burst scatter+recompute p99.
    ("bgp_prefixes_per_sec", True),
    ("bgp_update_p99_ms", False),
    # ISSUE 17: the critical-path ledger's per-phase p99 split plus
    # the host-choreography headline — the before-numbers ROADMAP
    # item 5's streaming-convergence refactor must drive down.
    ("critpath_wake_p99_ms", False),
    ("critpath_coalesce_wait_p99_ms", False),
    ("critpath_queue_wait_p99_ms", False),
    ("critpath_marshal_p99_ms", False),
    ("critpath_device_p99_ms", False),
    ("critpath_force_wait_p99_ms", False),
    ("critpath_rib_p99_ms", False),
    ("critpath_fib_commit_p99_ms", False),
    ("host_fraction_p99", False),
    # ISSUE 18: the jaxpr-audit gate cost — warm full-gate wall (the
    # pre-commit price) and the cold full-re-lowering wall.
    ("warm_gate_s", False),
    ("cold_full_s", False),
    # ISSUE 19: the survivability plane's acceptance scalars — the
    # advisory shed count and watchdog hang count of the seeded chaos
    # arms (deterministic by construction: drift means the chaos story
    # changed), the correctness dispatch-wall ratio under flood, and
    # the armed-watchdog hot-path cost.
    ("shed_advisory_total", True),
    ("watchdog_hangs", True),
    ("correctness_p99_ratio", False),
    ("overload_overhead_pct", False),
    # ISSUE 20: the SLO plane's acceptance scalars — trigger→FIB error
    # budget remaining over the seeded storm, the canary's measured
    # probe p99, and the armed-engine hot-path cost.
    ("slo_budget_remaining", True),
    ("canary_p99_ms", False),
    ("slo_overhead_pct", False),
)


def _ledger_scalars(extra: dict, mode: str) -> dict:
    out = {}
    for stage, row in extra.items():
        if not isinstance(row, dict) or not row.get("ok"):
            continue
        for key, hb in _LEDGER_KEYS:
            v = row.get(key)
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                out[f"{mode}/{stage}/{key}"] = (float(v), hb)
    return out


def _apply_bench_ledger(extra: dict, mode: str, path=None) -> dict:
    """Per-stage paired-median regression ledger (lint-baseline-style
    ratchet): unseen keys SEED the baseline from the current run, >10%
    regressions (plus a small absolute slack for the percent gates) are
    flagged in the report, and improvements >5% ratchet the baseline so
    the trajectory only tightens.  The ledger itself never fails the
    bench — it is the report's memory."""
    from pathlib import Path as _Path

    p = _Path(path) if path else _Path(__file__).with_name(
        "BENCH_baseline.json"
    )
    try:
        baseline = json.loads(p.read_text())
    except (OSError, ValueError):
        baseline = {}
    current = _ledger_scalars(extra, mode)
    regressions, seeded, ratcheted = [], 0, 0
    for name, (v, hb) in sorted(current.items()):
        b = baseline.get(name)
        if not isinstance(b, (int, float)):
            baseline[name] = round(v, 6)
            seeded += 1
            continue
        if hb:
            worse = v < b * 0.9
            better = v > b * 1.05
        else:
            # ADDITIVE slack around the baseline: multiplying a
            # NEGATIVE baseline (overhead gates routinely measure
            # below zero) would move the threshold the wrong way and
            # flag byte-identical reruns; the absolute floor keeps
            # near-zero percentages from flagging on sign jitter.
            worse = v > b + max(abs(b) * 0.1, 0.25)
            better = v < b - max(abs(b) * 0.05, 0.05)
        if worse:
            regressions.append(
                {"key": name, "baseline": b, "value": round(v, 4)}
            )
        elif better:
            baseline[name] = round(v, 6)
            ratcheted += 1
    report = {
        "regressions": regressions,
        "seeded": seeded,
        "ratcheted": ratcheted,
        "entries": len(baseline),
        "path": str(p),
    }
    try:
        p.write_text(json.dumps(baseline, indent=1, sort_keys=True) + "\n")
    except OSError as e:
        report["write_error"] = f"{type(e).__name__}: {e}"
    return report


def _run_stage(name, small, engine=None):
    # One process for each chip: this parent never imports JAX, and the
    # stage children run one after the other, each a fresh process.
    cmd = [sys.executable, __file__, "--stage", name]
    if small:
        cmd.append("--small")
    if engine:
        cmd += ["--engine", engine]
    try:
        proc = subprocess.run(
            cmd, timeout=STAGE_TIMEOUT[name], capture_output=True, text=True
        )
    except subprocess.TimeoutExpired:
        return {"ok": False, "error": "timeout"}
    if proc.returncode != 0:
        return {"ok": False, "error": (proc.stderr or "")[-400:]}
    try:
        return json.loads(proc.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        return {"ok": False, "error": f"unparseable: {proc.stdout[-200:]}"}


def main() -> None:
    small = "--small" in sys.argv
    if "--stage" in sys.argv:
        stage = sys.argv[sys.argv.index("--stage") + 1]
        if stage not in _CPU_PINNED:
            import jax

            from holo_tpu.utils.compile_cache import configure_compile_cache

            configure_compile_cache()
            if "--cpu" in sys.argv:
                jax.config.update("jax_platforms", "cpu")
            elif jax.devices()[0].platform != "tpu":
                sys.exit(
                    f"bench.py --stage {stage}: platform is "
                    f"{jax.devices()[0].platform!r}, not 'tpu' "
                    "(--cpu runs the stage on XLA's CPU backend)"
                )
        eng = (
            sys.argv[sys.argv.index("--engine") + 1]
            if "--engine" in sys.argv
            else "seq"
        )
        k10, b10, cpu10 = (20, 32, 8) if small else (90, 512, 32)
        k50, b50, cpu50 = (30, 16, 4) if small else (200, 128, 8)
        b256 = 32 if small else 256
        blat = 32 if small else 128
        fn = {
            "gather10k": lambda: stage_gather10k(k10, b10, cpu10),
            "blocked10k": lambda: stage_blocked10k(k10, b10, cpu10),
            "latency": lambda: stage_latency(k10, blat),
            "scale50k": lambda: stage_scale50k(k50, b50, cpu50),
            "scale50k_packed": lambda: stage_scale50k(
                k50, b50, cpu50, engine="packed"
            ),
            "scale50k_fused": lambda: stage_scale50k(k50, b50, cpu50, engine="fused"),
            "scale50k_hybrid": lambda: stage_scale50k(
                k50, b50, cpu50, engine="hybrid"
            ),
            "scale50k_b256": lambda: stage_scale50k(k50, b256, cpu50, engine=eng),
            "whatif1024": lambda: stage_whatif1024(k10, 8 if small else 16),
            "cspf10k": lambda: stage_cspf10k(k10, 32 if small else 256),
            "cpu100": lambda: stage_cpu100(32 if small else 200),
            "cpubaseline": lambda: stage_cpubaseline(k10, cpu10),
            "ospfv3_multiarea": lambda: (
                stage_ospfv3_multiarea(400, 4, 16, 4)
                if small
                else stage_ospfv3_multiarea(10_000, 4, 128, 8)
            ),
            "isis_l1l2": lambda: (
                stage_isis_l1l2(360, 40, 16, 16, 4)
                if small
                else stage_isis_l1l2(9_000, 1_000, 64, 128, 8)
            ),
            "frr_batch": lambda: (
                stage_frr_batch(6, 6, 3, True)
                if small
                else stage_frr_batch(12, 12, 3, True)
            ),
            "telemetry_overhead": lambda: stage_telemetry_overhead(
                k10, 32 if small else 64
            ),
            "fallback_overhead": lambda: stage_fallback_overhead(
                k10, 32 if small else 64
            ),
            "profiling_overhead": lambda: stage_profiling_overhead(
                k10, 32 if small else 64
            ),
            "convergence_storm": lambda: (
                stage_convergence_storm(400, 120)
                if small
                else stage_convergence_storm(2500, 400)
            ),
            "convergence_overhead": lambda: stage_convergence_overhead(
                k10, 32 if small else 64
            ),
            "delta_spf": lambda: (
                stage_delta_spf(300, 40)
                if small
                else stage_delta_spf(2000, 120)
            ),
            "incremental_overhead": lambda: stage_incremental_overhead(
                40 if small else 90, 32 if small else 64
            ),
            "shard_spf": lambda: (
                stage_shard_spf(60) if small else stage_shard_spf(400)
            ),
            "sharding_overhead": lambda: stage_sharding_overhead(
                20 if small else 40, 16 if small else 32
            ),
            "pipeline_spf": lambda: (
                stage_pipeline_spf(400, 120)
                if small
                else stage_pipeline_spf(2500, 400)
            ),
            "pipeline_overhead": lambda: stage_pipeline_overhead(
                40 if small else 90, 32 if small else 64
            ),
            "overload_storm": lambda: (
                stage_overload_storm(400, 120)
                if small
                else stage_overload_storm(2500, 400)
            ),
            "overload_overhead": lambda: stage_overload_overhead(
                40 if small else 90, 32 if small else 64
            ),
            "multipath_spf": lambda: (
                stage_multipath_spf(8, 16)
                if small
                else stage_multipath_spf(20, 32)
            ),
            "multipath_overhead": lambda: stage_multipath_overhead(
                40 if small else 90, 32 if small else 64
            ),
            "gnmi_fanout": lambda: (
                stage_gnmi_fanout(300, 90, big=1000)
                if small
                else stage_gnmi_fanout(1500, 250, big=1000)
            ),
            "fanout_overhead": lambda: stage_fanout_overhead(
                120 if small else 300
            ),
            "device_trace": lambda: stage_device_trace(),
            "explain_spf": lambda: stage_explain_spf(
                k10, 16 if small else 32
            ),
            "observatory_overhead": lambda: stage_observatory_overhead(
                40 if small else 90, 16 if small else 32
            ),
            "tropical_spf": lambda: (
                stage_tropical_spf(ks=(12, 20), B=16, cpu_runs=4)
                if small
                else stage_tropical_spf(ks=(30, 60, 90), B=128, cpu_runs=8)
            ),
            "partitioned_spf": lambda: stage_partitioned_spf(small),
            "bgp_table": lambda: stage_bgp_table(small),
            "critical_path": lambda: (
                stage_critical_path(400, 120)
                if small
                else stage_critical_path(2500, 400)
            ),
            "critpath_overhead": lambda: stage_critpath_overhead(
                k10, 32 if small else 64
            ),
            "audit_overhead": lambda: stage_audit_overhead(),
            "slo_storm": lambda: (
                stage_slo_storm(400, 120)
                if small
                else stage_slo_storm(2500, 400)
            ),
            "slo_overhead": lambda: stage_slo_overhead(
                40 if small else 90, 32 if small else 64
            ),
        }[stage]
        row = fn()
        if stage in _CPU_PINNED:
            row["platform"] = "cpu"
        print(json.dumps(row))
        return

    extra: dict = {}
    rows = ["gather10k", "blocked10k", "latency"] + (
        []
        if small
        else ["scale50k_hybrid", "scale50k", "scale50k_packed", "scale50k_fused"]
    )
    for name in rows:
        extra[name] = _run_stage(name, small)
        if name.startswith("scale50k") and extra[name].get("ok"):
            # One good 50k row is enough: don't spend two more multi-minute
            # compiles (chip time is the scarce resource) unless needed.
            got = extra[name].get("runs_per_sec", 0)
            cpu = extra[name].get("cpu_runs_per_sec", 0)
            if cpu and got / cpu >= 50:
                break
    # Batch-size leverage: rerun the best 50k engine at B=256 (gather-index
    # work amortizes with batch on TPU; B was tuned at 10k, never at 50k).
    best50 = max(
        (
            extra[n]
            for n in rows
            if n.startswith("scale50k")
            and extra.get(n, {}).get("ok")
            and "runs_per_sec" in extra[n]
        ),
        key=lambda r: r["runs_per_sec"],
        default=None,
    )
    # Only gather-path engines take an engine param; a blocked-Pallas win
    # means every gather engine failed at 50k — rerunning one at a LARGER
    # batch would just burn the timeout on the same failing compile.
    if (
        not small
        and best50 is not None
        and best50.get("engine") in _GATHER_ENGINES
    ):
        extra["scale50k_b256"] = _run_stage(
            "scale50k_b256", small, engine=best50["engine"]
        )
    if not small:
        # BASELINE.md configs 4 and 5 verbatim (CSPF batch; 1024-scenario
        # what-if) — coverage rows, not the headline.
        extra["whatif1024"] = _run_stage("whatif1024", small)
        extra["cspf10k"] = _run_stage("cspf10k", small)
        # BASELINE.md configs 2 and 3: protocol-marshaled topologies
        # (OSPFv3 multi-area; IS-IS L1/L2 with 64-way ECMP) through the
        # shared engine, parity-gated per area/level.
        extra["ospfv3_multiarea"] = _run_stage("ospfv3_multiarea", small)
        extra["isis_l1l2"] = _run_stage("isis_l1l2", small)
    # FRR backup-table batch (ISSUE 1): the all-roots SPF + repair
    # selection scenario, parity-gated vs the scalar oracle.
    extra["frr_batch"] = _run_stage("frr_batch", small)
    # Telemetry overhead gate (ISSUE 2): the instrumented SPF dispatch
    # must stay within noise (<2%) of a registry-disabled run.
    extra["telemetry_overhead"] = _run_stage("telemetry_overhead", small)
    # Breaker instrumentation gate (ISSUE 4): the healthy-path guard
    # around the device dispatch must stay within noise (<2%) of a
    # bypassed breaker.
    extra["fallback_overhead"] = _run_stage("fallback_overhead", small)
    # Deep-profiling + flight-recorder gate (ISSUE 5): sub-spans,
    # exemplars, and the span-tap ring must stay within noise (<2%) of
    # the un-profiled dispatch path.
    extra["profiling_overhead"] = _run_stage("profiling_overhead", small)
    # Convergence observatory (ISSUE 6): seeded flap-storm distributions
    # (deterministic digests) + the armed-instrument <2% gate.  Since
    # ISSUE 7 the storm also runs the full-rebuild comparison arm: the
    # lsa-trigger dispatch-wall split IS the DeltaPath headline.
    extra["convergence_storm"] = _run_stage("convergence_storm", small)
    extra["convergence_overhead"] = _run_stage("convergence_overhead", small)
    # DeltaPath incremental SPF (ISSUE 7): single-flap incremental vs
    # full-rebuild microbench + the <2% no-delta steady-state gate.
    extra["delta_spf"] = _run_stage("delta_spf", small)
    extra["incremental_overhead"] = _run_stage("incremental_overhead", small)
    # Multi-chip sharded dispatch (ISSUE 8): scenario-count sweep per
    # mesh shape through the REAL TpuSpfBackend sharded path (forced
    # 8-device virtual CPU mesh — sharding mechanics, not chip
    # throughput) + the <2% 1-device-mesh overhead gate.
    extra["shard_spf"] = _run_stage("shard_spf", small)
    extra["sharding_overhead"] = _run_stage("sharding_overhead", small)
    # Async dispatch pipeline + engine auto-tuner (ISSUE 9): storm
    # async-vs-sync-vs-scalar arms (FIB + causal-digest gated), the
    # consecutive-dispatch overlap microbench, per-shape tuner winners
    # vs pinned engines with cold-table reproduction, and the <2%
    # depth-1/disabled overhead gate.
    extra["pipeline_spf"] = _run_stage("pipeline_spf", small)
    extra["pipeline_overhead"] = _run_stage("pipeline_overhead", small)
    # Shared-delta gNMI fan-out (ISSUE 11): subscriber-fleet arms over
    # the seeded storm (per-tick render cost ~O(1) in subscriber count,
    # byte-identity vs the walk path, p99 delivery latency) + the <2%
    # 1-subscriber overhead gate.
    extra["gnmi_fanout"] = _run_stage("gnmi_fanout", small)
    extra["fanout_overhead"] = _run_stage("fanout_overhead", small)
    # Dispatch observatory (ISSUE 12): roofline attribution + sketch
    # quantiles + regression-sentinel story over a seeded workload, and
    # the <2% armed-observatory overhead gate.
    extra["explain_spf"] = _run_stage("explain_spf", small)
    extra["observatory_overhead"] = _run_stage("observatory_overhead", small)
    # Tropical min-plus matmul engine (ISSUE 13): the 1k->10k sweep vs
    # the best gather engine vs scalar, parity-gated, with the roofline
    # verdict and flops/bytes attribution per engine.
    extra["tropical_spf"] = _run_stage("tropical_spf", small)
    # Hierarchical partitioned SPF (ISSUE 15): the 10k->100k flat vs
    # multi-area sweep — digest parity on every arm, per-phase splits,
    # bounded delta re-solves, and the >=100k feasibility row.
    extra["partitioned_spf"] = _run_stage("partitioned_spf", small)
    # Device-resident BGP plane (ISSUE 16): cold full-table best-path
    # throughput + UPDATE-burst p99, gated on Loc-RIB parity between
    # the device backend and the scalar decision process.
    extra["bgp_table"] = _run_stage("bgp_table", small)
    # Critical-path ledger (ISSUE 17): per-phase trigger→FIB waterfall
    # split over the seeded storm (chaos-verified attribution, the
    # <1% unattributed-residual gate, residency rows) + the <2%
    # armed-ledger overhead gate.
    extra["critical_path"] = _run_stage("critical_path", small)
    extra["critpath_overhead"] = _run_stage("critpath_overhead", small)
    # Jaxpr kernel audit (ISSUE 18): warm lint gate must stay under 2x
    # the pre-audit wall (and under 1s absolute) via the per-kernel
    # cache; cold re-lowering bounded at 120s.
    extra["audit_overhead"] = _run_stage("audit_overhead", small)
    # Device-trace carry-over: a real jax.profiler capture when the
    # attached platform is an actual TPU; explicit not-used row else.
    extra["device_trace"] = _run_stage("device_trace", small)
    # Config 1: the 100-router CPU-reference floor (no device needed).
    extra["cpu100"] = _run_stage("cpu100", small)
    # Regression ledger (ISSUE 11 satellite): persist per-stage paired
    # medians, flag >10% regressions, ratchet improvements.
    extra["bench_ledger"] = _apply_bench_ledger(
        extra, "small" if small else "full"
    )

    n10 = "500" if small else "10125"
    blocked = extra.get("blocked10k", {})
    gather = extra.get("gather10k", {})
    # Headline = the faster of the two parity-checked engines on the 10k
    # what-if batch (both compute the identical full-SPF result).  The
    # metric NAME stays fixed either way so the driver's per-round series
    # doesn't fragment; the winning engine is recorded in extra.
    candidates = [
        (gather, "gather"),
        (blocked, "blocked"),
    ]
    candidates = [
        (st, eng)
        for st, eng in candidates
        if st.get("ok") and "runs_per_sec" in st
    ]
    if candidates:
        best, engine = max(candidates, key=lambda c: c[0]["runs_per_sec"])
        value = best["runs_per_sec"]
        metric = f"ospfv2_full_spf_whatif_runs_per_sec_{n10}v"
        extra["headline_engine"] = engine
        cpu = best.get("cpu_runs_per_sec") or max(
            (
                st.get("cpu_runs_per_sec", 0)
                for st, _ in candidates
            ),
            default=0,
        )
    else:
        print(
            json.dumps(
                {
                    "metric": f"ospfv2_full_spf_whatif_runs_per_sec_{n10}v_FAILED",
                    "value": 0.0,
                    "unit": "runs/s",
                    "vs_baseline": 0.0,
                    "extra": extra,
                }
            )
        )
        sys.exit(1)
    print(
        json.dumps(
            {
                "metric": metric,
                "value": round(value, 2),
                "unit": "runs/s",
                "vs_baseline": round(value / cpu, 2) if cpu else 0.0,
                "extra": extra,
            }
        )
    )


if __name__ == "__main__":
    main()
