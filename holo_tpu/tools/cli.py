"""Dev tools CLI.  See package docstring for commands."""

from __future__ import annotations

import argparse
import json
import sys


def cmd_schema(args) -> int:
    from holo_tpu.yang.modules import full_schema
    from holo_tpu.yang.schema import Container, Leaf, LeafList, List

    def walk(node, indent=0):
        pad = "  " * indent
        if isinstance(node, Leaf):
            extra = f" [{node.type}]"
            if node.default is not None:
                extra += f" = {node.default}"
            print(f"{pad}{node.name}{extra}")
        elif isinstance(node, LeafList):
            print(f"{pad}{node.name}* [{node.type}]")
        elif isinstance(node, List):
            print(f"{pad}{node.name}[{node.key}]/")
            for c in node.children.values():
                walk(c, indent + 1)
        elif isinstance(node, Container):
            print(f"{pad}{node.name}/")
            for c in node.children.values():
                walk(c, indent + 1)

    schema = full_schema()
    roots = [args.module] if args.module else sorted(schema.roots)
    for name in roots:
        node = schema.roots.get(name)
        if node is None:
            print(f"no module {name!r}", file=sys.stderr)
            return 1
        walk(node)
    return 0


def cmd_coverage(args) -> int:
    from holo_tpu.yang.modules import full_schema
    from holo_tpu.yang.schema import Container, Leaf, LeafList, List

    def count(node):
        leaves = lists = containers = 0
        if isinstance(node, (Leaf, LeafList)):
            return 1, 0, 0
        if isinstance(node, List):
            lists = 1
        elif isinstance(node, Container):
            containers = 1
        for c in getattr(node, "children", {}).values():
            l2, li2, c2 = count(c)
            leaves += l2
            lists += li2
            containers += c2
        return leaves, lists, containers

    total = [0, 0, 0]
    for name, node in sorted(full_schema().roots.items()):
        l, li, c = count(node)
        total[0] += l
        total[1] += li
        total[2] += c
        print(f"{name:20s} leaves={l:3d} lists={li:2d} containers={c:2d}")
    print(f"{'TOTAL':20s} leaves={total[0]:3d} lists={total[1]:2d} "
          f"containers={total[2]:2d}")
    return 0


def cmd_validate(args) -> int:
    from holo_tpu.yang.data import DataTree
    from holo_tpu.yang.modules import full_schema
    from holo_tpu.yang.schema import SchemaError

    text = open(args.file).read() if args.file != "-" else sys.stdin.read()
    try:
        DataTree.from_json(full_schema(), text)
    except (SchemaError, json.JSONDecodeError) as e:
        print(f"INVALID: {e}")
        return 1
    print("valid")
    return 0


def cmd_replay(args) -> int:
    from ipaddress import IPv4Address, IPv4Network

    from holo_tpu.protocols.ospf.instance import IfConfig, InstanceConfig, OspfInstance
    from holo_tpu.protocols.ospf.interface import IfType
    from holo_tpu.utils.event_recorder import replay
    from holo_tpu.utils.runtime import EventLoop, VirtualClock

    setup = json.load(open(args.setup))
    loop = EventLoop(clock=VirtualClock())

    class NullIo:
        def send(self, *a):
            pass

    inst = OspfInstance(
        name=setup.get("actor", "ospfv2"),
        config=InstanceConfig(router_id=IPv4Address(setup["router-id"])),
        netio=NullIo(),
    )
    loop.register(inst)
    for ifname, icfg in setup.get("interfaces", {}).items():
        inst.add_interface(
            ifname,
            IfConfig(
                area_id=IPv4Address(icfg.get("area", "0.0.0.0")),
                if_type=(
                    IfType.POINT_TO_POINT
                    if icfg.get("type") == "point-to-point"
                    else IfType.BROADCAST
                ),
                cost=icfg.get("cost", 10),
            ),
            IPv4Network(icfg["prefix"], strict=False),
            IPv4Address(icfg["address"]),
        )
    n = replay(args.events, loop)
    print(f"replayed {n} events")
    for aid, area in inst.areas.items():
        print(f"area {aid}: {len(area.lsdb.entries)} LSAs")
        for key in sorted(area.lsdb.entries, key=str):
            e = area.lsdb.entries[key]
            print(f"  {key.type.name:16s} {key.lsid} adv={key.adv_rtr} "
                  f"seq={e.lsa.seq_no}")
    print(f"routes ({len(inst.routes)}):")
    for prefix, route in sorted(inst.routes.items(), key=lambda kv: str(kv[0])):
        nhs = sorted(f"{nh.ifname}:{nh.addr}" for nh in route.nexthops)
        print(f"  {prefix} dist={route.dist} via {nhs}")
    return 0


def cmd_conformance(args) -> int:
    from pathlib import Path

    if getattr(args, "protocol", "ospf") == "isis":
        from holo_tpu.tools.conformance_isis import (
            REFERENCE_CONFORMANCE_ISIS as corpus,
            run_topology,
        )
    else:
        from holo_tpu.tools.conformance import (
            REFERENCE_CONFORMANCE as corpus,
            run_topology,
        )

    if args.topo_dir:
        dirs = [Path(args.topo_dir)]
    elif corpus.exists():
        dirs = sorted(p for p in corpus.iterdir() if p.is_dir())
    else:
        print(f"conformance corpus not found at {corpus}", file=sys.stderr)
        return 2
    total = ok = 0
    failed = False
    for topo in dirs:
        results = run_topology(topo)
        bad = {rt: p for rt, p in results.items() if p}
        total += len(results)
        ok += len(results) - len(bad)
        print(f"{topo.name}: {len(results) - len(bad)}/{len(results)} conformant")
        for rt, problems in bad.items():
            failed = True
            for p in problems:
                print(f"    {rt}: {p}")
    print(f"TOTAL: {ok}/{total} routers bit-identical")
    return 1 if failed else 0


def _print_table(headers, rows, top=None, indent="  ") -> None:
    """The one fixed-width table renderer ``trace`` / ``postmortem`` /
    ``explain`` share (previously two hand-rolled variants).  ``top``
    truncates AFTER the caller's sort — cost-center ranking lives with
    the data, not the renderer."""
    if top is not None:
        rows = rows[:top]
    rows = [[str(c) for c in r] for r in rows]
    widths = [len(h) for h in headers]
    for r in rows:
        for i, c in enumerate(r):
            widths[i] = max(widths[i], len(c))
    print(
        (indent + "  ".join(h.ljust(w) for h, w in zip(headers, widths)))
        .rstrip()
    )
    for r in rows:
        print(
            (indent + "  ".join(c.ljust(w) for c, w in zip(r, widths)))
            .rstrip()
        )


def _snapshot_cost_rows(snap: dict) -> list[tuple]:
    """Metric-snapshot rows ranked as cost centers: histograms by total
    seconds, scalars by value, descending."""
    rows = []
    for name, v in snap.items():
        if isinstance(v, dict):
            rows.append((name, v.get("count", 0), float(v.get("sum", 0.0))))
        else:
            rows.append((name, "", float(v)))
    rows.sort(key=lambda r: (-r[2], r[0]))
    return [(n, c, f"{s:g}") for n, c, s in rows]


def cmd_trace(args) -> int:
    """Run a synthetic SPF + FRR workload with span tracing and dump the
    spans as Chrome trace-event JSON (load in chrome://tracing or
    https://ui.perfetto.dev) — the quickest way to SEE where a dispatch
    spends its time.  A daemon produces the same artifact at stop via
    ``[telemetry] trace-dump`` or ``HOLO_TPU_TRACE_DUMP=<path>``."""
    from holo_tpu import telemetry
    from holo_tpu.frr.manager import FrrEngine
    from holo_tpu.spf.backend import TpuSpfBackend
    from holo_tpu.spf.synth import grid_topology, whatif_link_failure_masks

    topo = grid_topology(args.rows, args.rows, seed=1)
    backend = TpuSpfBackend()
    with telemetry.span("trace.workload", instance="synth"):
        for _ in range(max(args.repeat, 1)):
            backend.compute(topo)
        masks = whatif_link_failure_masks(topo, 8, seed=2)
        backend.compute_whatif(topo, masks)
        FrrEngine("tpu").compute(topo)
    n = telemetry.tracer().dump(args.output)
    print(f"wrote {n} spans to {args.output}")
    snap = telemetry.snapshot(prefix="holo_spf")
    print(f"top {args.top} cost centers:")
    _print_table(
        ("metric", "count", "total"),
        _snapshot_cost_rows(snap),
        top=args.top,
    )
    return 0


def _explain_workload(k: int, batch: int, reps: int, seed: int) -> None:
    """The explain CLI's seeded dispatch mix: repeated single-SPF runs
    (the tuner's explore rounds), what-if batches, the multipath
    k ∈ {1,2,4,8} sweep (the A-lane gather cost the ROADMAP carries),
    and one FRR backup-table batch.  With the default ``reps`` the tuner
    stays inside its deterministic explore phase, so a deterministic
    stage timer makes the whole run byte-identical."""
    from holo_tpu.frr.manager import FrrEngine
    from holo_tpu.spf.backend import TpuSpfBackend
    from holo_tpu.spf.synth import (
        fat_tree_topology,
        whatif_link_failure_masks,
    )

    topo = fat_tree_topology(k=k, seed=seed)
    masks = whatif_link_failure_masks(topo, batch, seed=seed + 1)
    backend = TpuSpfBackend()
    for _ in range(max(reps, 1)):
        backend.compute(topo)
    for _ in range(max(reps, 1)):
        backend.compute_whatif(topo, masks)
    for kk in (1, 2, 4, 8):
        for _ in range(2):
            backend.compute(topo, multipath_k=kk)
    FrrEngine("tpu").compute(topo)


def cmd_explain(args) -> int:
    """Dispatch-observatory report (ISSUE 12): run a seeded workload —
    the synthetic dispatch mix, or a full convergence storm with
    ``--storm`` — with the observatory, deep profiling, and the engine
    tuner armed, then render top-k cost centers with sketch-derived
    p50/p99, per-(engine, shape-bucket) roofline attribution (achieved
    FLOP/s, bytes/s, arithmetic intensity, memory-/compute-bound
    verdict), the tuner's win/loss ledger, and the sentinel state.

    Deterministic by default: the stage timer is a counter clock, so
    two same-seed runs print byte-identical reports (walls become
    timer-read counts — the classification and attribution signal is
    real; pass ``--wall-clock`` for honest walls at the price of
    run-to-run jitter)."""
    from holo_tpu.pipeline import tuner as tuner_mod
    from holo_tpu.telemetry import critpath, observatory, profiling

    if not args.wall_clock:
        profiling.set_stage_timer(observatory.DeterministicTimer())
    profiling.set_device_profiling(True)
    obs = observatory.configure(
        check_every=16,
        ledger_path=args.ledger,
    )
    # Critical-path ledger (ISSUE 17): stamps read the same stage
    # timer as the observatory, so the waterfall section inherits the
    # byte-identical contract under the deterministic counter clock.
    cp = critpath.configure(check_every=16) if args.critical_path else None
    # SLO plane (ISSUE 20): the engine clock is profiling.clock, so the
    # burn/budget arithmetic inherits the byte-identical contract under
    # the deterministic counter clock exactly like the ledgers above.
    sl = None
    prober = None
    if args.slo:
        from holo_tpu.telemetry import slo

        sl = slo.configure(check_every=16)
    tuner = tuner_mod.configure_engine_tuner()
    try:
        if args.storm:
            from holo_tpu.spf.synth_storm import run_convergence_storm

            hook = None
            if sl is not None:
                from holo_tpu.telemetry import canary

                state: dict = {}

                def hook(net, i, now):
                    if "prober" not in state:
                        # Arm on the first hook tick: the storm loop
                        # only exists once the net is built.  Virtual
                        # heartbeats fire during every advance from
                        # here on — deterministic probe schedule.
                        state["prober"] = canary.CanaryProber(
                            net.loop, period=2.0, warmup=10.0
                        )
                        state["prober"].start()

                run_convergence_storm(
                    n_routers=args.storm, events=args.events,
                    seed=args.seed, event_hook=hook,
                )
                prober = state.get("prober")
                if prober is not None:
                    prober.stop()
            else:
                run_convergence_storm(
                    n_routers=args.storm, events=args.events,
                    seed=args.seed,
                )
        else:
            _explain_workload(args.k, args.batch, args.reps, args.seed)
        # Close the run's sentinel window: seed/compare every key now
        # (not just those that crossed a check_every boundary) and
        # persist the --ledger baseline for the next invocation.
        obs.checkpoint()
        doc = obs.report(top=args.top)
        doc["tuner"] = tuner.ledger()
        if cp is not None:
            cp.checkpoint()
            doc["critical_path"] = cp.report(top=args.top)
        if sl is not None:
            sl.checkpoint()
            doc["slo"] = sl.report()
            if prober is not None:
                doc["slo"]["canary"] = prober.stats()
        if args.json:
            print(json.dumps(doc, sort_keys=True, indent=2))
            return 0
        peaks = doc["peaks"]
        ridge = peaks["ridge_flops_per_byte"]
        print(
            f"dispatch observatory — timing: {doc['timing']}, peaks: "
            f"{peaks['source']} "
            + ("(no peaks: verdicts unknown)" if ridge is None
               else f"(ridge {ridge:g} flop/B)")
        )
        print(f"top {args.top} cost centers:")
        _print_table(
            ("site/stage", "engine", "kind", "bucket", "n",
             "total_s", "p50_ms", "p99_ms"),
            [
                (
                    f"{r['site']}/{r['stage']}", r["engine"], r["kind"],
                    json.dumps(r["bucket"], separators=(",", ":")),
                    r["count"], f"{r['total_s']:g}",
                    f"{r['p50_s'] * 1e3:.3f}", f"{r['p99_s'] * 1e3:.3f}",
                )
                for r in doc["cost_centers"]
            ],
        )
        print("roofline (per engine × shape-bucket):")
        _print_table(
            ("site", "engine", "kind", "bucket", "AI", "verdict",
             "flop/s", "B/s", "roofline", "p50_ms", "p99_ms"),
            [
                (
                    r["site"], r["engine"], r["kind"],
                    json.dumps(r["bucket"], separators=(",", ":")),
                    (
                        f"{r['ai_flops_per_byte']:g}"
                        if r["ai_flops_per_byte"] is not None
                        else "-"
                    ),
                    r["verdict"],
                    (
                        f"{r['achieved_flops_per_sec']:.3e}"
                        if r.get("achieved_flops_per_sec")
                        else "-"
                    ),
                    (
                        f"{r['achieved_bytes_per_sec']:.3e}"
                        if r.get("achieved_bytes_per_sec")
                        else "-"
                    ),
                    (
                        f"{r['roofline_fraction']:.2%}"
                        if r.get("roofline_fraction") is not None
                        else "-"
                    ),
                    (
                        f"{r['device_p50_s'] * 1e3:.3f}"
                        if r.get("device_p50_s") is not None
                        else "-"
                    ),
                    (
                        f"{r['device_p99_s'] * 1e3:.3f}"
                        if r.get("device_p99_s") is not None
                        else "-"
                    ),
                )
                for r in doc["roofline"]
            ],
        )
        print("engine tuner win/loss ledger:")
        _print_table(
            ("kind", "bucket", "winner", "dispatches", "measured", "basis"),
            [
                (
                    t["kind"],
                    json.dumps(t["bucket"], separators=(",", ":")),
                    t["winner"], t["dispatches"],
                    ",".join(
                        f"{e}={v['median_ms']}ms"
                        for e, v in t["engines"].items()
                    ),
                    t["basis"],
                )
                for t in doc["tuner"]
            ],
        )
        s = doc["sentinel"]
        print(
            f"sentinel: {s['ledger-entries']} ledger entries, "
            f"{s['seeded']} seeded, {s['ratcheted']} ratcheted, "
            f"{s['flags']} flags"
            + (f", regressed: {', '.join(s['regressed'])}"
               if s["regressed"] else "")
        )
        if cp is not None:
            cpd = doc["critical_path"]
            v = cpd["verdicts"]
            hf = cpd["host-fraction-p99"]
            uf = cpd["unattributed-frac-p50"]
            print(
                f"critical path — {cpd['completed']} events "
                f"({cpd['dropped']} dropped), verdicts: "
                f"host={v['host']} queue={v['queue']} "
                f"device={v['device']}, host-fraction-p99: "
                + (f"{hf:.2%}" if hf is not None else "-")
                + ", unattributed-frac-p50: "
                + (f"{uf:.2%}" if uf is not None else "-")
            )
            print("phase ledger (cut order):")
            _print_table(
                ("phase", "p50_ms", "p99_ms", "mean_ms", "share_p99"),
                [
                    (
                        r["phase"], f"{r['p50'] * 1e3:.3f}",
                        f"{r['p99'] * 1e3:.3f}",
                        f"{r['mean'] * 1e3:.3f}",
                        f"{r['share_p99']:.2%}",
                    )
                    for r in cpd["phases"]
                ],
            )
            print(f"last {len(cpd['events'])} waterfalls:")
            _print_table(
                ("n", "trigger", "verdict", "wall_ms", "top phases",
                 "stalls"),
                [
                    (
                        w["n"], w["trigger"], w["verdict"],
                        f"{w['wall'] * 1e3:.3f}",
                        " ".join(
                            f"{p}={w['phases'][p] * 1e3:.3f}ms"
                            for p, _ in sorted(
                                w["phases"].items(),
                                key=lambda kv: (-kv[1], kv[0]),
                            )[:3]
                            if w["phases"][p] > 0.0
                        ) or "-",
                        w["stalls"],
                    )
                    for w in cpd["events"]
                ],
            )
        if sl is not None:
            sld = doc["slo"]
            w = sld["windows"]
            print(
                f"slo — windows: fast {w['fast_s']:g}s / slow "
                f"{w['slow_s']:g}s, burn limits "
                f"{w['fast_burn_limit']:g}/{w['slow_burn_limit']:g}"
            )
            _print_table(
                ("objective", "kind", "events", "good", "bad",
                 "burn_fast", "burn_slow", "budget", "fires",
                 "measured_p99_ms"),
                [
                    (
                        r["objective"], r["kind"], r["events"],
                        r["good_fast"], r["bad_fast"],
                        (
                            f"{r['burn_fast']:g}"
                            if r["burn_fast"] is not None else "-"
                        ),
                        (
                            f"{r['burn_slow']:g}"
                            if r["burn_slow"] is not None else "-"
                        ),
                        (
                            f"{r['budget_remaining']:g}"
                            if r["budget_remaining"] is not None else "-"
                        ),
                        r["sentinel_fires_fast"] + r["sentinel_fires_slow"],
                        (
                            f"{r['measured_ms']['p99']:g}"
                            if r.get("measured_ms") else "-"
                        ),
                    )
                    for r in sld["objectives"]
                ],
            )
            if sld["sheds"]:
                print(
                    "sheds: " + ", ".join(
                        f"{k}={v}" for k, v in sld["sheds"].items()
                    )
                )
            if "canary" in sld:
                c = sld["canary"]
                print(
                    f"canary: {c['probes']} probes, "
                    f"{c['attributed']} attributed, "
                    f"{c['unattributed']} unattributed, "
                    f"{c['failed']} failed ({c['sheds']} shed, "
                    f"{c['overdue']} overdue)"
                )
        return 0
    finally:
        observatory.configure(enabled=False)
        if cp is not None:
            critpath.configure(0)
        if sl is not None:
            from holo_tpu.telemetry import slo

            slo.configure(False)
        profiling.set_device_profiling(False)
        profiling.set_stage_timer(None)
        tuner_mod.reset_engine_tuner()


def cmd_import_yang(args) -> int:
    """Parse YANG text file(s) and dump the resulting schema subtrees —
    the libyang-load analog for externally authored modules.  Multiple
    files form one module set with cross-module grouping/typedef
    resolution (pass every import together, like a libyang context)."""
    from pathlib import Path

    from holo_tpu.yang.parser import load_modules
    from holo_tpu.yang.schema import Container, Leaf, LeafList, List, SchemaError

    try:
        mods = load_modules(
            [Path(f).read_text() for f in args.files]
        )
    except (OSError, UnicodeDecodeError, SchemaError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    nodes = [n for ns in mods.values() for n in ns]
    if not nodes:
        print("(no config data nodes — augment/identity-only modules)")

    def dump(node, depth=0):
        pad = "  " * depth
        if isinstance(node, Leaf):
            extra = f" = {node.default!r}" if node.default is not None else ""
            enum = f" {{{','.join(node.enum)}}}" if node.enum else ""
            ro = "" if node.config else " (state)"
            print(f"{pad}{node.name} [{node.type}{enum}]{extra}{ro}")
        elif isinstance(node, LeafList):
            print(f"{pad}{node.name}* [{node.type}]")
        elif isinstance(node, List):
            print(f"{pad}{node.name}[{node.key}]/")
            for c in node.children.values():
                dump(c, depth + 1)
        elif isinstance(node, Container):
            p = " (presence)" if node.presence else ""
            print(f"{pad}{node.name}/{p}")
            for c in node.children.values():
                dump(c, depth + 1)

    for node in nodes:
        dump(node)
    return 0


def cmd_deviations(args) -> int:
    """Generate a "not-supported" deviations skeleton for a YANG module
    (reference holo-tools/src/yang_deviations.rs): one commented-out
    ``deviate not-supported`` per schema node, fully prefixed, ready for
    an implementer to uncomment for the nodes they do NOT support.
    Extra files are the module's imports (one context, like libyang)."""
    from pathlib import Path

    from holo_tpu.yang.parser import load_modules, parse_text
    from holo_tpu.yang.schema import SchemaError

    try:
        texts = [Path(f).read_text() for f in args.files]
        target = parse_text(texts[0])
    except (OSError, UnicodeDecodeError, SchemaError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    if target.keyword != "module":
        print("error: first file must be a YANG module", file=sys.stderr)
        return 2
    name = target.arg
    pfx_stmt = target.sub("prefix")
    prefix = pfx_stmt.arg if pfx_stmt is not None else name
    try:
        mods = load_modules(texts)
    except SchemaError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    print(f"module holo-{name}-deviations {{")
    print("  yang-version 1.1;")
    print(
        f'  namespace "http://holo-routing.org/yang/holo-{name}-deviations";'
    )
    print(f"  prefix holo-{name}-deviations;")
    print(f"\n  import {name} {{\n    prefix {prefix};\n  }}")
    print('\n  organization\n    "Holo Routing Stack";')
    print(
        f'\n  description\n    "This module defines deviation statements '
        f'for the {name}\n     module.";'
    )

    def emit(node, path):
        path = f"{path}/{prefix}:{node.name}"
        print(
            f"\n  /*\n  deviation \"{path}\" {{\n"
            f"    deviate not-supported;\n  }}\n  */"
        )
        for child in getattr(node, "children", {}).values():
            emit(child, path)

    for node in mods.get(name, []):
        emit(node, "")
    print("}")
    return 0


def cmd_postmortem(args) -> int:
    """Pretty-print a flight-recorder postmortem bundle (written by the
    daemon on breaker-open / crash-loop / SIGTERM when ``[telemetry]
    flight-buffer-entries`` + ``postmortem-dir`` are set).  ``--json``
    re-emits the canonical sorted JSON (diff two seeded runs with it)."""
    try:
        with open(args.bundle) as fh:
            bundle = json.load(fh)
    except (OSError, ValueError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    if bundle.get("schema") != "holo-postmortem/1":
        print(
            f"error: {args.bundle} is not a holo-postmortem/1 bundle",
            file=sys.stderr,
        )
        return 2
    if args.json:
        print(json.dumps(bundle, sort_keys=True, indent=2))
        return 0
    ring = bundle.get("ring", [])
    print(f"postmortem #{bundle.get('dump')}: {bundle.get('reason')}")
    kinds = {}
    for e in ring:
        kinds[e[0]] = kinds.get(e[0], 0) + 1
    print(
        f"ring: {len(ring)} entries ("
        + ", ".join(f"{k}={v}" for k, v in sorted(kinds.items()))
        + ")"
    )
    for e in ring:
        if e[0] == "event":
            _, kind, fields, t = e
            kv = " ".join(f"{k}={v}" for k, v in sorted(fields.items()))
            print(f"  [{t:10.3f}] {kind:18s} {kv}")
    spans = [e for e in ring if e[0] == "span"]
    if spans:
        if args.top:
            # Cost-center view (shared with trace/explain): the
            # heaviest spans in the whole ring, duration-descending.
            picked = sorted(spans, key=lambda e: -e[5])[: args.top]
            print(f"top {len(picked)} spans by duration (of {len(spans)}):")
        else:
            picked = spans[-args.spans:]
            print(f"last spans ({len(picked)} of {len(spans)}):")
        rows = [
            (
                f"#{sid}",
                name,
                f"{dur / 1e3:.3f}ms",
                parent if parent is not None else "-",
                " ".join(f"{k}={v}" for k, v in sorted(attrs.items())),
            )
            for _, name, sid, parent, start, dur, attrs in picked
        ]
        _print_table(("span", "name", "wall", "parent", "attrs"), rows)
    health = bundle.get("health", {})
    for name, br in sorted(health.get("breakers", {}).items()):
        print(
            f"breaker {name}: {br['state']} "
            f"(failures={br['consecutive-failures']}"
            f"/{br['failure-threshold']}, last={br['last-error'] or '-'})"
        )
    sup = health.get("supervision")
    if sup:
        print(
            f"supervision: degraded={sup['degraded-actors'] or '-'} "
            f"restarts={sup['restarts']}"
        )
    metrics = bundle.get("metrics", {})
    if metrics:
        print(f"metric deltas since arm ({len(metrics)} series):")
        for name in sorted(metrics):
            print(f"  {name} += {metrics[name]}")
    tail = bundle.get("journal-tail", [])
    if tail:
        print(
            f"journal tail: seq {tail[0][0]}..{tail[-1][0]} "
            f"({len(tail)} markers)"
        )
    return 0


def cmd_lint(args) -> int:
    """holo-lint: repo-native static analysis (JAX hot-path hazards +
    daemon lock discipline), gated against a ratchet baseline.  Exit 0
    when the tree matches the baseline, 1 on new findings, 2 on usage
    or parse errors."""
    from pathlib import Path

    from holo_tpu.analysis import (
        audit_suppressions,
        compare_to_baseline,
        default_baseline_path,
        load_baseline,
        run_paths,
        run_paths_cached,
        self_check,
        write_baseline,
    )

    pkg_root = Path(__file__).resolve().parent.parent  # holo_tpu/
    repo_root = pkg_root.parent
    paths = [Path(p) for p in args.paths] if args.paths else [pkg_root]
    for p in paths:
        if not p.exists():
            print(f"error: no such path {p}", file=sys.stderr)
            return 2

    if args.list_rules:
        from holo_tpu.analysis import all_rules

        for rule in all_rules():
            print(
                f"{rule.id}  [{rule.family:6s}]  [{rule.severity:5s}]  "
                f"{rule.title}"
            )
        return 0

    # The incremental cache covers the default full-package scan only:
    # an ad-hoc `lint some/path` has a different file set and must not
    # overwrite the gate's cache (all-or-nothing validation would then
    # force the next gate run cold).
    use_cache = not args.no_cache and not args.paths
    if args.self_check:
        if not use_cache:
            # self_check exercises the default cache file; running it
            # over an ad-hoc path set would store that partial file
            # set and force the next gate run cold.
            print(
                "error: --self-check validates the full-package cache "
                "and cannot combine with --no-cache or explicit paths",
                file=sys.stderr,
            )
            return 2
        mismatches = self_check(
            paths, root=repo_root, audit=not args.no_audit
        )
        if mismatches:
            for m in mismatches:
                print(f"cache self-check: {m}", file=sys.stderr)
            print(
                "holo-lint: cache self-check FAILED — cached replay "
                "diverged from a cold scan (delete "
                ".holo_lint_cache.json and report this)",
                file=sys.stderr,
            )
            return 2
    if use_cache:
        result = run_paths_cached(paths, root=repo_root)
    else:
        result = run_paths(paths, root=repo_root)
    if result.parse_errors:
        for err in result.parse_errors:
            print(f"parse error: {err}", file=sys.stderr)
        return 2

    # The HL3xx jaxpr kernel audit joins the gate on the default
    # full-package lint only: an ad-hoc `lint some/path` checks files,
    # not compiled kernel contracts.  Audit findings merge into the
    # same baseline/suppression/severity machinery as the AST rules.
    audit = None
    if not args.paths and not args.no_audit:
        from holo_tpu.analysis import run_audit_cached

        audit = run_audit_cached(repo_root, no_cache=args.no_cache)
        result.findings.extend(audit.findings)
        result.suppressed.extend(audit.suppressed)

    stale_suppressions = (
        audit_suppressions(result) if args.check_suppressions else []
    )

    baseline_path = (
        Path(args.baseline) if args.baseline else default_baseline_path()
    )
    if args.write_baseline:
        write_baseline(baseline_path, result.findings)
        print(
            f"baseline: wrote {len(result.findings)} finding(s) to "
            f"{baseline_path}"
        )
        return 0

    baseline = load_baseline(baseline_path)
    new, unused = compare_to_baseline(result.findings, baseline)
    # Severity tiers: only error-tier findings gate (exit 1); warn-tier
    # findings render as warnings and ride the JSON report.
    from holo_tpu.analysis import gate_findings

    new_errors = gate_findings(new)
    new_warns = [f for f in new if f.severity != "error"]

    if args.json:
        doc = {
            # Bump schema_version whenever a field is added/renamed so
            # a reader of this document can gate its
            # parser instead of silently misreading lint telemetry.
            # v3: adds the "audit" block (HL3xx jaxpr kernel audit).
            "schema_version": 3,
            "files_checked": result.files_checked,
            "files_cached": result.files_cached,
            # Wall seconds per rule id (whole run) — the ledger tracks
            # lint cost per rule as the module set grows.
            "rule_seconds": {
                k: round(v, 6)
                for k, v in sorted(result.rule_seconds.items())
            },
            # HL3xx jaxpr kernel audit telemetry: per-kernel lowering
            # wall seconds (0.0 for cache-replayed kernels) so the
            # ledger can track audit cost as the registry grows.  None
            # when the audit did not run (--no-audit or explicit paths).
            "audit": None if audit is None else {
                "kernels_checked": audit.kernels_checked,
                "kernels_cached": audit.kernels_cached,
                "skipped": sorted(audit.skipped),
                "device_count": audit.device_count,
                "kernel_seconds": {
                    k: round(v, 6)
                    for k, v in sorted(audit.kernel_seconds.items())
                },
            },
            "stale_suppressions": stale_suppressions,
            "findings": [
                {
                    "rule": f.rule,
                    "path": f.path,
                    "line": f.line,
                    "context": f.context,
                    "message": f.message,
                    "severity": f.severity,
                    "baselined": f not in new,
                }
                for f in result.findings
            ],
            "new": len(new),
            "new_errors": len(new_errors),
            "new_warnings": len(new_warns),
            "suppressed": len(result.suppressed),
            "unused_baseline_keys": sorted(unused),
        }
        print(json.dumps(doc, indent=2))
    else:
        for f in new_errors:
            print(f.render())
        for f in new_warns:
            print(f"warning: {f.render()}")
        for s in stale_suppressions:
            print(s)
        n_base = len(result.findings) - len(new)
        cached = (
            f" ({result.files_cached} cached)"
            if result.files_cached
            else ""
        )
        print(
            f"holo-lint: {result.files_checked} files{cached}, "
            f"{len(new_errors)} new error(s), "
            f"{len(new_warns)} new warning(s), {n_base} baselined, "
            f"{len(result.suppressed)} suppressed"
        )
        if audit is not None:
            a_cached = (
                f" ({audit.kernels_cached} cached)"
                if audit.kernels_cached
                else ""
            )
            a_skip = (
                f", {len(audit.skipped)} skipped (no mesh)"
                if audit.skipped
                else ""
            )
            print(
                f"holo-lint: audit {audit.kernels_checked} "
                f"kernel(s){a_cached} on {audit.device_count} "
                f"device(s){a_skip}"
            )
        if stale_suppressions:
            print(
                f"holo-lint: {len(stale_suppressions)} stale "
                "suppression(s) — delete the dead disable comment(s) "
                "or fix the rule id they name"
            )
        if unused:
            print(
                f"holo-lint: {sum(unused.values())} baseline entr"
                f"{'y is' if sum(unused.values()) == 1 else 'ies are'} "
                "stale (fixed) — ratchet by removing them:"
            )
            for key in sorted(unused):
                print(f"  {key}")
    return 1 if (new_errors or stale_suppressions) else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="holo-tpu-tools")
    sub = ap.add_subparsers(dest="cmd", required=True)
    s = sub.add_parser("schema", help="dump the management schema tree")
    s.add_argument("module", nargs="?")
    s.set_defaults(fn=cmd_schema)
    s = sub.add_parser("coverage", help="schema node counts per module")
    s.set_defaults(fn=cmd_coverage)
    s = sub.add_parser("validate", help="validate a JSON config")
    s.add_argument("file")
    s.set_defaults(fn=cmd_validate)
    s = sub.add_parser("replay", help="replay recorded events into OSPFv2")
    s.add_argument("events")
    s.add_argument("--setup", required=True,
                   help="JSON: router-id + interfaces layout")
    s.set_defaults(fn=cmd_replay)
    s = sub.add_parser(
        "conformance",
        help="run the reference conformance corpus (RIB bit-identity)",
    )
    s.add_argument("topo_dir", nargs="?",
                   help="one topology dir (default: all)")
    s.add_argument("--protocol", choices=("ospf", "isis"), default="ospf")
    s.set_defaults(fn=cmd_conformance)
    s = sub.add_parser(
        "trace",
        help="trace a synthetic SPF/FRR workload to Chrome trace JSON",
    )
    s.add_argument("-o", "--output", default="holo_tpu_trace.json")
    s.add_argument("--rows", type=int, default=6, help="grid topology side")
    s.add_argument("--repeat", type=int, default=3, help="single-SPF runs")
    s.add_argument(
        "--top", type=int, default=12,
        help="cost centers to print (metric rows, total-descending)",
    )
    s.set_defaults(fn=cmd_trace)
    s = sub.add_parser(
        "explain",
        help="dispatch-observatory report: top-k cost centers, roofline "
             "attribution, tuner win/loss ledger over a seeded workload",
    )
    s.add_argument("--top", type=int, default=10, help="cost centers to show")
    s.add_argument("--seed", type=int, default=7)
    s.add_argument("--k", type=int, default=12, help="fat-tree arity")
    s.add_argument("--batch", type=int, default=16, help="what-if batch size")
    s.add_argument(
        "--reps", type=int, default=8,
        help="single-SPF / what-if repetitions (the default exactly "
             "covers the tuner's deterministic explore phase)",
    )
    s.add_argument(
        "--storm", type=int, default=0, metavar="ROUTERS",
        help="run a seeded convergence storm of this many routers "
             "instead of the synthetic dispatch mix",
    )
    s.add_argument("--events", type=int, default=60, help="storm events")
    s.add_argument(
        "--ledger",
        help="sentinel baseline JSON (seed/flag/ratchet across runs)",
    )
    s.add_argument(
        "--wall-clock", action="store_true",
        help="measure real walls instead of the deterministic "
             "byte-identical counter clock",
    )
    s.add_argument(
        "--critical-path", action="store_true",
        help="arm the critical-path ledger and append the per-phase "
             "trigger→FIB waterfall section (meaningful with --storm)",
    )
    s.add_argument(
        "--slo", action="store_true",
        help="arm the SLO plane (error budgets + burn-rate sentinels) "
             "and append the objective table; with --storm a synthetic "
             "canary rides the storm loop as its own objective",
    )
    s.add_argument("--json", action="store_true", help="JSON report")
    s.set_defaults(fn=cmd_explain)
    s = sub.add_parser(
        "import-yang",
        help="parse YANG text module(s) and dump their schema subtrees",
    )
    s.add_argument("files", nargs="+")
    s.set_defaults(fn=cmd_import_yang)
    s = sub.add_parser(
        "deviations",
        help="generate a not-supported deviations skeleton for a module",
    )
    s.add_argument("files", nargs="+", help="module file, then its imports")
    s.set_defaults(fn=cmd_deviations)
    s = sub.add_parser(
        "postmortem",
        help="pretty-print a flight-recorder postmortem bundle",
    )
    s.add_argument("bundle", help="postmortem-*.json bundle file")
    s.add_argument(
        "--json", action="store_true",
        help="re-emit the canonical sorted JSON instead of a summary",
    )
    s.add_argument(
        "--spans", type=int, default=12,
        help="how many trailing spans to show (default 12)",
    )
    s.add_argument(
        "--top", type=int, default=0, metavar="N",
        help="show the N heaviest spans in the ring instead of the "
             "trailing window (cost-center sorting, shared with "
             "trace/explain)",
    )
    s.set_defaults(fn=cmd_postmortem)
    s = sub.add_parser(
        "lint",
        help="holo-lint: JAX hot-path + lock-discipline static analysis",
    )
    s.add_argument(
        "paths", nargs="*",
        help="files/dirs to lint (default: the holo_tpu package)",
    )
    s.add_argument(
        "--baseline",
        help="ratchet baseline JSON "
             "(default: holo_tpu/analysis/baseline.json)",
    )
    s.add_argument(
        "--write-baseline", action="store_true",
        help="write current findings as the new baseline and exit 0",
    )
    s.add_argument("--json", action="store_true", help="JSON report")
    s.add_argument(
        "--list-rules", action="store_true", help="print the rule catalog"
    )
    s.add_argument(
        "--check-suppressions", action="store_true",
        help="flag stale `# holo-lint: disable=` comments whose rule "
             "no longer fires on that line (exit 1)",
    )
    s.add_argument(
        "--no-cache", action="store_true",
        help="force a full scan (skip the incremental lint cache)",
    )
    s.add_argument(
        "--self-check", action="store_true",
        help="run cached + cold scans and fail loudly (exit 2) if the "
             "cache replay diverges from the full scan",
    )
    s.add_argument(
        "--no-audit", action="store_true",
        help="skip the HL3xx jaxpr kernel audit (the abstract CPU "
             "lowering of every registered jit seam)",
    )
    s.set_defaults(fn=cmd_lint)
    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
