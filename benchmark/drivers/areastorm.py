"""The storm of ``drivers/storm.py`` on the OSPFv3 multi-area network
(``benchmark/areanet.py``): the same window loop, wall stamps and result
keys, with six kinds of event over five areas.

params, beside the parent's: ``mix`` has ``link`` (a hall link down or
up), ``node`` (a switch lost or back), ``summary`` (a border router
withdraws or re-advertises one pod range: the three are ``lsa``
triggers), ``bfd``, ``carrier`` (on the device's own links: the RIB's
local repair) and ``ifconfig`` (cost flip, or shut / no-shut, of one of
the device's hall uplinks); ``area_share`` (the halls' shares of the
link and node events, rotated by one hall every ``area_epoch_events``);
``hot_set``, ``hot_share``, ``zipf_s``, ``hot_epoch_events`` (as
``popstorm``, per hall); ``node_draw`` (edge / agg / core shares of the
losses); ``summary_draw`` (remote / peer); ``ifconfig_draw`` (cost /
shut); ``max_down`` (``switches``, ``summaries`` down at once: a draw
beyond that brings the oldest back; ``shut`` uplinks per hall).
``drop_prob`` holds for each ``lsa``-class event as a whole.  The
warm-up first injects every kind and its undoing in two halls, each
alone in its SPF run, before any random draw.
"""

from __future__ import annotations

import time
from collections import Counter

import numpy as np

from benchmark import fabric, parity, stats
from benchmark.areanet import BACKBONE, AreaNet
from benchmark.drivers import storm
from benchmark.drivers.popstorm import SCRIPT_GAP_S, _moved, _toggle

KINDS = ("link", "node", "summary", "bfd", "carrier", "ifconfig")
LSA_KINDS = ("link", "node", "summary")


class RunStamps(storm.WallStamps):
    """The parent's stamps and, beside ``done``, the SPF run whose
    first install stamped each event: ``(run number, type)`` of the
    instance's newest ``spf_log`` entry, which both kinds of run append
    before they publish."""

    def __init__(self, ledger, inst):
        super().__init__(ledger)
        self._inst = inst
        self._run_of: dict = {}
        self.runs: list = []

    def ev_phase(self, eid, phase) -> None:
        rec = self._open.get(eid)
        unstamped = rec is not None and rec[2] is None
        super().ev_phase(eid, phase)
        if unstamped and rec[2] is not None and self._inst.spf_log:
            entry = self._inst.spf_log[-1]
            self._run_of[eid] = (entry["run"], entry["type"])

    def ev_done(self, eid, outcome, fallback) -> None:
        if eid in self._open:
            self.runs.append(self._run_of.pop(eid, None))
        super().ev_done(eid, outcome, fallback)


class Driver(storm.Driver):
    #: an instance of more than one area whose traffic is drawn whole
    #: from ``--seed``: the same median under a name, and so a bound,
    #: of its own (PERF.md, section 2)
    METRIC = "multiarea_trigger_fib_p50_ms"

    def set_up(self) -> None:
        params, config = self.params, self.config
        self.backend = fabric.backend_of(config)
        self.net = AreaNet(
            config["lsdb"], self.backend, config["spf_delay"],
            params["rxmt_delay_s"], max_paths=config.get("max_paths"),
        )
        lay = self.net.layout
        keep = self._order_blocks()
        self._edges = np.cumsum([params["mix"][k] for k in KINDS[:-1]])
        rank = np.arange(1, params["hot_set"] + 1, dtype=float)
        self._zipf = rank ** -params["zipf_s"]
        self._zipf /= self._zipf.sum()
        self._hot_links: dict = {}  # hall -> (epoch, link indices)
        self._uplinks = [
            link for link in self.net.dut_links if link[0] != BACKBONE
        ]
        self._bfd_down = self._carrier_down = None  # the link held down
        self._ranges = {
            "remote": sorted({
                (abr, p) for (abr, _a, p) in lay.summaries
                if abr in lay.remote_abrs
            }),
            "peer": sorted({
                (abr, p) for (abr, a, p) in lay.summaries
                if abr not in lay.remote_abrs and a == BACKBONE
            }),
        }
        # A reservoir per area, so that the sample spans the areas
        # whatever their shares of the dispatches are.
        per_area = -(-int(params["parity_samples"]) // 3)
        self.kept_by_area = {
            area: parity.Reservoir(per_area, rng)
            for area, rng in zip(lay.adj, keep.spawn(len(lay.adj)))
        }
        self._sampling = False
        self.delta_ops: list[int] = []
        self.dispatch_walls: list[float] = []
        self.dispatched_areas = Counter()
        self.dispatches_of_run = Counter()  # full run number -> areas
        self._wrap_compute()
        self._arm()
        scripted = self._script()
        for event in scripted:
            event()
            self.net.loop.advance(SCRIPT_GAP_S)
        for _ in range(int(params["warmup_events"]) - len(scripted)):
            self._event()
            self.net.loop.advance(self._next_gap())
        self._settle()
        self.warmup = Counter(self.injected)

    def _arm(self) -> None:
        from holo_tpu.telemetry import convergence

        super()._arm()
        self.stamps = RunStamps(self.ledger, self.net.inst)
        convergence.set_critpath_hook(self.stamps)

    def _area_of(self, topo):
        for aid, st in self.net.inst._spf_delta_bases.items():
            if st.topo is topo:
                return int(aid)
        return None

    def _wrap_compute(self) -> None:
        """Inside the window: keep (topology, result) of a seeded
        sample of each area's dispatches for the scalar oracle, the
        delta's size and the dispatch's wall on the benchmark's clock."""
        inner = self.backend.compute

        def compute(topo, edge_mask=None, **kw):
            # also after the window's close: its last events converge
            # in the settle's run
            self.dispatches_of_run[self.net.inst.spf_run_count] += 1
            if not self._sampling:
                return inner(topo, edge_mask, **kw)
            delta = getattr(topo, "delta_base", None)
            self.delta_ops.append(-1 if delta is None else delta.n_ops)
            t0 = time.perf_counter()
            res = inner(topo, edge_mask, **kw)
            self.dispatch_walls.append(time.perf_counter() - t0)
            area = self._area_of(topo)
            self.dispatched_areas[area] += 1
            if area in self.kept_by_area:
                self.kept_by_area[area].offer(
                    lambda: (topo, edge_mask, parity.keep(res))
                )
            return res

        self.backend.compute = compute  # shadows the method; see close()

    def _script(self) -> list:
        """Every kind and its undoing, in two halls, a core switch's
        loss among them; nothing drawn."""
        net, lay = self.net, self.net.layout
        hall_a, hall_b = lay.halls[0], lay.halls[-1]

        def count(kind, fn, *args, **kw):
            def event():
                self.injected[kind] += 1
                fn(*args, **kw)
            return event

        def twice(kind, fn, *args, **kw):
            return [count(kind, fn, *args, **kw)] * 2

        core = net.losable[hall_a]["core"][0]
        agg = net.losable[hall_b]["agg"][0]
        self.warmup_loss = {
            "core": len(lay.adj[hall_a][core]), "agg": len(lay.adj[hall_b][agg]),
        }
        up_a = next(u for u in self._uplinks if u[0] == hall_a)
        up_b = next(u for u in self._uplinks if u[0] == hall_b)
        return [
            *twice("link", net.flap, hall_a, net.flappable[hall_a][0], lost=False),
            *twice("link", net.flap, hall_b, net.flappable[hall_b][-1], lost=False),
            *twice("node", net.node, hall_a, core, lost=False),
            *twice("node", net.node, hall_b, agg, lost=False),
            *twice("summary", net.summary, *self._ranges["remote"][0], lost=False),
            *twice("summary", net.summary, *self._ranges["peer"][0], lost=False),
            count("bfd", net.bfd, up_a, "down"),
            count("bfd", net.bfd, up_a, "up"),
            count("carrier", net.carrier, up_b, operative=False),
            count("carrier", net.carrier, up_b, operative=True),
            *twice("ifconfig", net.ifconfig_cost, up_a),
            *twice("ifconfig", net.ifconfig_shut, up_b),
        ]

    # -- traffic

    def _hall(self) -> int:
        """A hall by ``area_share``, the shares moved on by one hall
        every ``area_epoch_events`` injected events."""
        halls, params = self.net.layout.halls, self.params
        share = params["area_share"][: len(halls)]
        at = int(self._pick.choice(len(share), p=np.array(share) / sum(share)))
        turn = self.injected.total() // params["area_epoch_events"]
        return halls[(at + turn) % len(halls)]

    def _link(self, hall: int) -> tuple[int, int]:
        params, links = self.params, self.net.flappable[hall]
        held = self._hot_links.get(hall)
        if held is None or held[0] != self._block:  # lives one block
            held = self._hot_links[hall] = (self._block, self._hot.choice(
                len(links), size=min(params["hot_set"], len(links)),
                replace=False,
            ))
        hot = held[1]
        if self._pick.random() < params["hot_share"]:
            zipf = self._zipf[: len(hot)]
            at = hot[int(self._pick.choice(len(hot), p=zipf / zipf.sum()))]
        else:
            at = self._pick.integers(len(links))
        return links[int(at)]

    def _one_down(self, held, fn_down, fn_up):
        """bfd and carrier hold one of the device's links down at a
        time: the next event brings it back."""
        if held is not None:
            fn_up(held)
            return None
        links = self.net.dut_links
        link = links[int(self._pick.integers(len(links)))]
        fn_down(link)
        return link

    def _inject(self) -> None:
        net, params, roll = self.net, self.params, self._mix.random()
        kind = KINDS[int(np.searchsorted(self._edges, roll, side="right"))]
        if kind in LSA_KINDS:
            lost = self._loss.random() < params["drop_prob"]
        if kind == "link":
            hall = self._hall()
            net.flap(hall, self._link(hall), lost=lost)
        elif kind == "node":
            hall = self._hall()
            roles = list(params["node_draw"])
            role = roles[int(self._pick.choice(
                len(roles), p=[params["node_draw"][r] for r in roles]
            ))]
            pool = net.losable[hall][role]
            target = (hall, pool[int(self._pick.integers(len(pool)))])
            net.node(
                *_toggle(net.node_down, target, params["max_down"]["switches"]),
                lost=lost,
            )
        elif kind == "summary":
            share = params["summary_draw"]["remote"]
            pool = self._ranges[
                "remote" if self._pick.random() < share else "peer"
            ]
            target = pool[int(self._pick.integers(len(pool)))]
            net.summary(
                *_toggle(net.withdrawn, target, params["max_down"]["summaries"]),
                lost=lost,
            )
        elif kind == "bfd":
            self._bfd_down = self._one_down(
                self._bfd_down, lambda l: net.bfd(l, "down"),
                lambda l: net.bfd(l, "up"),
            )
        elif kind == "carrier":
            self._carrier_down = self._one_down(
                self._carrier_down,
                lambda l: net.carrier(l, operative=False),
                lambda l: net.carrier(l, operative=True),
            )
        else:
            link = self._uplinks[int(self._pick.integers(len(self._uplinks)))]
            if self._pick.random() < params["ifconfig_draw"]["shut"]:
                # one shut uplink a hall: a second draw there brings
                # the first back
                net.ifconfig_shut(net.shut.get(link[0], link))
            else:
                net.ifconfig_cost(link)
        self.injected[kind] += 1

    def _settle(self) -> None:
        """Before the settle, the link bfd or carrier holds down comes
        back: the settled FIB is then the protocol's routes and no
        local repair, which is what the references derive."""
        if self._bfd_down is not None:
            self.net.bfd(self._bfd_down, "up")
        if self._carrier_down is not None:
            self.net.carrier(self._carrier_down, operative=True)
        self._bfd_down = self._carrier_down = None
        super()._settle()

    # -- the window: the parent's, with this deployment's counts beside

    def run(self, window) -> dict:
        from holo_tpu.ops.spf_engine import shared_graph_cache

        self.net.most_lsas = 0
        out = super().run(window)
        ops, walls = self.delta_ops, self.dispatch_walls
        quantiles = (10.0, 25.0, 50.0, 75.0, 90.0)
        out["timing"]["dispatch_wall_s"] = {"count": len(walls)} | {
            stats.label(q): stats.percentile(walls, q)
            for q in quantiles if walls
        }
        # Where the median sits in its population: the spread of a
        # cell's medians over seeds is read against these.
        fib_walls = out["samples"]["trigger_fib_wall_s"]
        out["timing"]["trigger_fib_wall_s"] |= {
            stats.label(q): stats.percentile(fib_walls, q)
            for q in quantiles if fib_walls
        }
        # The same walls by the areas their run dispatched: an event
        # converges at its run's first install, after the work of every
        # area the run dispatches, so the population has a mode for
        # each count (PERF.md, section 6, PR 31).
        by_areas: dict = {}
        for (trigger, outcome, _fb, _t0, wall), spf in zip(
            self.stamps.done, self.stamps.runs
        ):
            if (
                trigger in storm.SPF_TRIGGERS and outcome == "converged"
                and wall is not None and spf is not None
            ):
                number, kind = spf
                key = (
                    str(self.dispatches_of_run[number]) if kind == "full"
                    else "partial"
                )
                by_areas.setdefault(key, []).append(wall)
        out["timing"]["trigger_fib_wall_by_areas_s"] = {
            key: {"count": len(w), "p50": stats.percentile(w, 50.0)}
            for key, w in sorted(by_areas.items())
        }
        # Traced runs only (the stage histograms are armed there): the
        # wall of every actor's deliveries, so that what the instance
        # does between SPF runs (age ticks, retransmit timers) can be
        # told from the run itself.
        first, last = window.snap["open"], window.snap["close"]
        zero = {"count": 0, "sum": 0.0}
        out["timing"]["loop_delivery_s"] = {
            key.split("stage=")[1].split(",")[0]: {
                f: value[f] - first.get(key, zero)[f] for f in ("count", "sum")
            }
            for key, value in last.items()
            if key.startswith("holo_profile_stage_seconds{site=loop,")
        }
        shapes = {}
        for aid, st in self.net.inst._spf_delta_bases.items():
            rows, width = self.backend.prepare(st.topo).in_src.shape
            shapes[str(int(aid))] = [int(rows), int(width)]
        out["counts"].update(
            delta_paths=_moved(window, "holo_spf_delta_total"),
            diff_paths=_moved(window, "holo_spf_delta_diff_total"),
            area_spf=_moved(window, "holo_ospf_area_spf_total"),
            spf_types=_moved(window, "holo_ospf_spf_runs_total"),
            converged_by_areas={
                key: len(w) for key, w in sorted(by_areas.items())
            },
            dispatches_by_area={
                str(a): n for a, n in sorted(
                    self.dispatched_areas.items(), key=lambda kv: str(kv[0])
                )
            },
            largest_delta_ops=max(ops, default=0),
            dispatches_without_lineage=ops.count(-1),
            ell_shapes=shapes,
            injected_by_kind=dict(self.injected - self.warmup),
            warmup_by_kind=dict(self.warmup), warmup_loss=self.warmup_loss,
            most_lsas_in_one_event=self.net.most_lsas,
            chain_depth_at_end=shared_graph_cache().stats()["max-chain-depth"],
            switches_down_at_end=len(self.net.node_down),
            summaries_withdrawn_at_end=len(self.net.withdrawn),
            uplinks_shut_at_end=len(self.net.shut),
            rib_routes=len(self.net.rib.routes),
        )
        return out

    # -- parity, outside the window

    def verify(self) -> dict:
        """(i) the sampled dispatches, from three areas or more, against
        the scalar oracle on four planes; (ii) the settled FIB against
        the FIB the scalar backend derives from the same LSDBs by one
        forced full SPF; (iii) the settled FIB against the plain
        reference's table, prefix for prefix, cost and next-hop set."""
        from holo_tpu.spf.backend import ScalarSpfBackend

        from benchmark import v3ref

        want = int(self.params["parity_samples"])
        pools = [list(r.items) for r in self.kept_by_area.values()]
        samples, areas = [], 0
        for pool in pools:
            areas += bool(pool)
        while len(samples) < want and any(pools):
            for pool in pools:
                if pool and len(samples) < want:
                    samples.append(pool.pop())
        scalar = parity.against_scalar(samples)
        net = self.net
        before = net.fib_table()
        net.inst.backend = ScalarSpfBackend()  # daemon/providers.py seam
        net.inst._schedule_spf()  # trigger-less: a full run
        net.loop.advance(float(self.params["settle_s"]))
        after = net.fib_table()
        ref = v3ref.routes(net.model())
        differing = sorted(
            str(p) for p in set(ref) | set(before)
            if ref.get(p) != before.get(p)
        )
        remote = {p for _abr, p in self._ranges["remote"]}
        return {
            "ok": (
                not scalar["mismatches"] and scalar["checked"] > 0
                and areas >= min(3, len(pools))
                and before == after and not differing and len(before) > 0
            ),
            "scalar": scalar, "sampled_areas": areas,
            "fib_routes": len(before), "fib_equals_scalar_arm": before == after,
            "reference_routes": len(ref),
            "differing_from_reference": len(differing),
            "first_differing": differing[:4],
            "remote_ranges_in_fib": len(remote & set(before)),
        }
