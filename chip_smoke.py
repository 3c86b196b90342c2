#!/usr/bin/env python
"""The served SPF path, once, on one TPU chip: does it still start?

Three stages in ONE process (the chip belongs to one process; the
scalar arms and the C++ baseline are host code and share it), data made
from a fixed seed:

- daemon: two in-process ``Daemon``s on the in-memory fabric, configured
  through northbound transactions with ``spf-control/backend = tpu``.
- storm:  a real ``OspfInstance`` holding a 10,000-router LSDB through
  the RFC 8405 delay FSM, ibus, ``RibManager`` and kernel FIB, on
  ``TpuSpfBackend`` and again on ``ScalarSpfBackend``: the FIB digests
  must be equal.
- whatif: 512 link-failure scenarios on a 10,125-vertex fat-tree through
  ``TpuSpfBackend.compute_whatif``, sampled against the scalar oracle
  and the C++ baseline, then one more dispatch that must not compile.

``main()`` refuses any platform but ``tpu``; no flag or variable lets it
pass on a CPU.  After every stage it fails unless no dispatch was served
by the scalar fallback (the breaker keeps results bit-identical when the
device arm raises, so parity alone proves nothing about the chip).  A
stage that raises ends the run with its traceback.

The timings printed here are smoke timings labelled with the device,
not benchmark metrics.  Last stdout line on success:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""

from __future__ import annotations

import json
import sys
import time

from benchmark.witness import (
    FallbackWitness as BenchWitness,
    SetupClock,
    device_info,
)

SEED = 9


class SmokeFailure(AssertionError):
    """A stage ran but did not prove what it must."""


def _require(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _versions() -> dict:
    from importlib import metadata

    out = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            out[pkg] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            out[pkg] = None
    return out


def _total(family: str, label: str = "") -> int:
    """Sum of one counter family's children whose labels contain ``label``."""
    from holo_tpu import telemetry

    snap = telemetry.snapshot(family)
    return int(sum(v for k, v in snap.items() if label in k))


def _compiles() -> int:
    return _total("holo_spf_jit_compiles_total")


class FallbackWitness(BenchWitness):
    """The benchmark's witness with a verdict that raises: :meth:`check`
    fails the smoke if any dispatch since construction was served by
    the scalar oracle.  The counter is the authority: a failed device
    dispatch moves it even where the storm report's split still reads
    ``device``.
    """

    def check(self, stage: str, report: dict | None = None) -> dict:
        seen = super().check()
        _require(
            seen["clean"],
            f"{stage}: {seen['fallbacks']} dispatch(es) served by the "
            f"scalar fallback, breakers not clean: "
            f"{seen['unclean'] or seen['errors']}",
        )
        if report is not None:
            trig = report["triggers"]
            fb = [t for t, split in trig.items() if "fallback" in split]
            _require(not fb, f"{stage}: fallback split on triggers {fb}")
            for t in ("lsa", "ifconfig"):
                _require(
                    "device" in trig.get(t, {}),
                    f"{stage}: trigger {t!r} has no device split: "
                    f"{sorted(trig.get(t, {}))}",
                )
        return {"fallbacks": seen["fallbacks"], "breakers": seen["breakers"]}


def stage_daemon() -> dict:
    """Normal entry point + the config seam, on the device: adjacency
    full, connected prefix in the RIB, SPF log says ``tpu``."""
    from ipaddress import IPv4Network, ip_address

    from holo_tpu.daemon.daemon import Daemon
    from holo_tpu.spf.backend import TpuSpfBackend
    from holo_tpu.utils.netio import MockFabric
    from holo_tpu.utils.runtime import EventLoop, VirtualClock

    base = "routing/control-plane-protocols/ospfv2"
    loop = EventLoop(clock=VirtualClock())
    fabric = MockFabric(loop)
    d1 = Daemon(loop=loop, netio=fabric, name="d1")
    d2 = Daemon(loop=loop, netio=fabric, name="d2")
    try:
        fabric.join("l12", "d1.ospfv2", "eth0", ip_address("10.0.12.1"))
        fabric.join("l12", "d2.ospfv2", "eth0", ip_address("10.0.12.2"))
        for d, rid, addr in (
            (d1, "1.1.1.1", "10.0.12.1/30"),
            (d2, "2.2.2.2", "10.0.12.2/30"),
        ):
            cand = d.candidate()
            cand.set("interfaces/interface[eth0]/address", [addr])
            cand.set(f"{base}/router-id", rid)
            cand.set(f"{base}/spf-control/backend", "tpu")
            cand.set(
                f"{base}/area[0.0.0.0]/interface[eth0]/interface-type",
                "point-to-point",
            )
            d.commit(cand)
        inst = d1.routing.instances["ospfv2"]
        _require(
            isinstance(inst.backend, TpuSpfBackend),
            f"daemon: backend is {type(inst.backend).__name__}",
        )
        loop.advance(60)
        ospf = d1.routing.get_state()["routing"]["ospfv2"]
        nbr = ospf["neighbors"].get("2.2.2.2", {}).get("state")
        _require(nbr == "full", f"daemon: neighbor state {nbr!r}")
        _require(
            IPv4Network("10.0.12.0/30") in d1.routing.rib.active_routes(),
            "daemon: connected prefix missing from the RIB",
        )
        spf_log = ospf["spf-log"]
        _require(
            spf_log and spf_log[-1]["backend"] == "tpu",
            f"daemon: spf-log tail {spf_log[-1:]}",
        )
        return {"neighbor": nbr, "spf_runs": len(spf_log)}
    finally:
        d1.stop()
        d2.stop()


def stage_storm(n_routers: int, events: int, seed: int = SEED) -> dict:
    """The convergence storm on the device backend, with the same storm
    on the scalar backend as the reference (bit-identical-FIB gate)."""
    from holo_tpu.spf.backend import ScalarSpfBackend, TpuSpfBackend
    from holo_tpu.spf.synth_storm import run_convergence_storm
    from holo_tpu.telemetry.canary import fib_digest

    def incremental() -> int:
        return _total("holo_spf_delta_total", "path=incremental")

    inc0 = incremental()
    t0 = time.perf_counter()
    report, _digest, net = run_convergence_storm(
        n_routers=n_routers, events=events, seed=seed,
        spf_backend=TpuSpfBackend(),
    )
    wall_tpu = time.perf_counter() - t0
    inc = incremental() - inc0
    t0 = time.perf_counter()
    _ref_report, _ref_digest, ref_net = run_convergence_storm(
        n_routers=n_routers, events=events, seed=seed,
        spf_backend=ScalarSpfBackend(),
    )
    wall_scalar = time.perf_counter() - t0
    got, ref = fib_digest(net.kernel.fib), fib_digest(ref_net.kernel.fib)
    _require(got == ref, f"storm: FIB digest {got[:16]} != scalar {ref[:16]}")
    converged = report["outcomes"].get("converged", 0)
    _require(converged > 0, f"storm: outcomes {report['outcomes']}")
    _require(inc > 0, "storm: no path=incremental DeltaPath dispatch ran")
    return {
        "report": report,
        "fib_digest": got,
        "fib_size": report["fib-size"],
        "converged": converged,
        "spf_runs": report["spf-runs"],
        "incremental": inc,
        "wall_tpu_arm_s": round(wall_tpu, 3),
        "wall_scalar_arm_s": round(wall_scalar, 3),
    }


def stage_whatif(
    k: int, n_scenarios: int, n_check: int = 8, seed: int = SEED
) -> dict:
    """One what-if batch through the backend's default engine, sampled
    against the scalar oracle (all four planes) and the C++ baseline
    (distances), then a second dispatch of the same shape that must hit
    the jit cache."""
    import numpy as np

    from holo_tpu.native_build import native_spf_batch_dist
    from holo_tpu.spf.backend import ScalarSpfBackend, TpuSpfBackend
    from holo_tpu.spf.synth import fat_tree_topology, whatif_link_failure_masks

    topo = fat_tree_topology(k=k, seed=seed)
    masks = whatif_link_failure_masks(topo, n_scenarios, seed=seed + 1)
    backend = TpuSpfBackend()
    t0 = time.perf_counter()
    outs = backend.compute_whatif(topo, masks)
    wall_first = time.perf_counter() - t0
    _require(len(outs) == n_scenarios, f"whatif: {len(outs)} results")

    sample = np.unique(
        np.linspace(0, n_scenarios - 1, min(n_check, n_scenarios)).astype(int)
    )
    oracle = ScalarSpfBackend()
    for i in sample:
        ref = oracle.compute(topo, masks[i])
        for plane in ("dist", "parent", "hops", "nexthop_words"):
            _require(
                np.array_equal(getattr(outs[i], plane), getattr(ref, plane)),
                f"whatif: scenario {i} {plane} differs from the scalar oracle",
            )
    cpp = native_spf_batch_dist(topo, masks[sample])
    for row, i in zip(cpp, sample):
        _require(
            np.array_equal(outs[i].dist, row),
            f"whatif: scenario {i} dist differs from the C++ baseline",
        )

    c0 = _compiles()
    t0 = time.perf_counter()
    again = backend.compute_whatif(topo, masks)
    wall_repeat = time.perf_counter() - t0
    recompiles = _compiles() - c0
    _require(recompiles == 0, f"whatif: repeat dispatch compiled {recompiles}")
    _require(
        np.array_equal(again[sample[-1]].dist, outs[sample[-1]].dist),
        "whatif: repeat dispatch changed its answer",
    )
    return {
        "n_vertices": int(topo.n_vertices),
        "n_edges": int(topo.n_edges),
        "n_scenarios": n_scenarios,
        "checked": [int(i) for i in sample],
        "wall_first_s": round(wall_first, 3),
        "wall_repeat_s": round(wall_repeat, 3),
        "recompiles": recompiles,
    }


def _run(
    name: str, fn, witness: FallbackWitness, setup: SetupClock, dev: dict
) -> dict:
    c0, s0, h0 = _compiles(), setup.seconds, setup.cache_hits
    t0 = time.perf_counter()
    row = fn()
    wall = time.perf_counter() - t0
    clean = witness.check(name, row.pop("report", None))
    row = {
        "stage": name, "wall_s": round(wall, 3),
        "compiles": _compiles() - c0,
        "setup_s": round(setup.seconds - s0, 3),
        "cache_hits": setup.cache_hits - h0,
        **clean, **row, "device": dev["kind"],
    }
    print(json.dumps(row), flush=True)
    return row


def main() -> int:
    t_start = time.perf_counter()
    dev = device_info()
    print(
        f"chip_smoke: platform={dev['platform']} device_kind={dev['kind']} "
        f"count={dev['count']} versions={json.dumps(_versions())}",
        flush=True,
    )
    if dev["platform"] != "tpu":
        print(
            f"chip_smoke: found platform {dev['platform']!r}, need 'tpu'; "
            "this check only passes on the chip",
            file=sys.stderr,
        )
        return 2

    import jax

    from holo_tpu.utils.compile_cache import configure_compile_cache

    print(f"chip_smoke: compile cache at {configure_compile_cache()}")
    witness, setup = FallbackWitness(), SetupClock()
    _run("daemon", stage_daemon, witness, setup, dev)
    _run("storm", lambda: stage_storm(10_000, 40), witness, setup, dev)
    _run("whatif", lambda: stage_whatif(90, 512), witness, setup, dev)
    stats = jax.devices()[0].memory_stats() or {}
    print(
        f"chip_smoke: peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
        f"setup_s={setup.seconds:.1f} cache_hits={setup.cache_hits} "
        f"total_wall_s={time.perf_counter() - t_start:.1f} "
        f"device={dev['kind']}",
        flush=True,
    )
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
