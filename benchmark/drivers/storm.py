"""A seeded flap storm through a real ``OspfInstance``: events injected
until the window's wall has passed, then a virtual settle outside it.

The loop's clock is virtual, so the RFC 8405 holds and the retransmit
penalty of a lost LSA cost no wall: trigger-to-FIB wall is the time the
system adds to its own timers.  The walls are the benchmark's own
``perf_counter`` stamps, taken by a hook chained in front of the
program's critical-path ledger; the ledger's exact per-event records
give the phases.

Where a cell names a ``pool``, every seed injects the same traffic in
another order.  The traffic is a pool of ``pool`` blocks of injected
events, a block as long as the cell's ``hot_epoch_events``
(``BLOCK_EVENTS`` where it names none).  A block's generators (kinds,
targets, losses, gaps, hot sets) are seeded from ``TRAFFIC_SEED`` and
the block's number in the pool, never from ``--seed``, which only
orders the pool (a run goes through it in that order, over and over)
and samples the dispatches the parity check keeps.  Drawn whole from
``--seed``, a window's median followed its seed's hot links and mix by
up to 15% where one seed repeats within 1.5% (PERF.md, section 6).  A
cell with no ``pool`` still draws all its traffic from ``--seed``: one
whose median follows the order of its blocks as far as it followed its
seed gains nothing from a pool (``v3-multiarea-storm``).

params: ``mix`` (shares of lsa / bfd / carrier / ifconfig events),
``gap_short_share``, ``gap_short_s``, ``gap_long_s`` (bursty virtual
gaps), ``drop_prob``, ``rxmt_delay_s`` (lost LSA arrivals),
``pool``, ``warmup_events``, ``settle_s``, ``parity_samples``.
"""

from __future__ import annotations

import time
from collections import Counter

import numpy as np

from benchmark import fabric, parity, stats
from benchmark.stormnet import StormNet

#: triggers whose events run an SPF (the end-to-end population)
SPF_TRIGGERS = ("lsa", "ifconfig")
#: more events than any window can hold: nothing is evicted
CAPACITY = 1 << 17
#: the traffic's generators, drawn anew at the edge of every block
STREAMS = ("_mix", "_loss", "_gap", "_pick", "_hot")
#: the one realisation of a cell's traffic that every ``--seed`` orders
TRAFFIC_SEED = 34
#: injected events a block, where a cell has no ``hot_epoch_events``
BLOCK_EVENTS = 200


class WallStamps:
    """The benchmark's clock on every causal event: ``perf_counter`` at
    its begin and at its first FIB commit, trigger and outcome at its
    end.  Sits where the convergence tracker expects the critical-path
    ledger and forwards every call to it."""

    def __init__(self, ledger):
        self._ledger = ledger
        self._open: dict = {}
        self.done: list = []  # (trigger, outcome, fallback, begin, wall or None)

    def ev_begin(self, eid, trigger) -> None:
        self._open[eid] = [trigger, time.perf_counter(), None]
        self._ledger.ev_begin(eid, trigger)

    def ev_sched(self, eid) -> None:
        self._ledger.ev_sched(eid)

    def ev_phase(self, eid, phase) -> None:
        rec = self._open.get(eid)
        if rec is not None and rec[2] is None and phase in ("fib", "fallback"):
            rec[2] = time.perf_counter()
        self._ledger.ev_phase(eid, phase)

    def ev_done(self, eid, outcome, fallback) -> None:
        rec = self._open.pop(eid, None)
        if rec is not None:
            trigger, t0, t_fib = rec
            wall = None if t_fib is None else t_fib - t0
            self.done.append((trigger, outcome, bool(fallback), t0, wall))
        self._ledger.ev_done(eid, outcome, fallback)


class Driver:
    #: the convergence metric this kind of storm reports its median
    #: under; a bound is keyed to the name (PERF.md, section 2)
    METRIC = "trigger_fib_p50_ms"

    def __init__(self, config: dict, params: dict, seed: int):
        self.config, self.params, self.seed = config, params, seed
        self.backend = None

    # -- set-up

    def set_up(self) -> None:
        lsdb = self.config["lsdb"]
        self.backend = fabric.backend_of(self.config)
        self.net = StormNet(
            n_routers=lsdb["routers"], seed=lsdb["graph_seed"],
            spf_backend=self.backend, prefix_every=lsdb["prefix_every"],
            hubs=lsdb["hubs"], extra_link_share=lsdb["extra_link_share"],
            max_degree=lsdb["max_degree"], spf_delay=self.config["spf_delay"],
            rxmt_delay=self.params["rxmt_delay_s"],
        )
        keep = self._order_blocks()
        self._edges = np.cumsum([
            self.params["mix"][t] for t in ("lsa", "bfd", "carrier")
        ])
        self._bfd_down = self._carrier_down = False
        self.kept = parity.Reservoir(
            int(self.params.get("parity_samples", 4)), keep
        )
        self._sampling = False
        self._wrap_compute()
        # Warm-up: the same traffic for a few events, so that the full
        # and the incremental dispatch and their delta scatter are all
        # compiled (or fetched) before the window opens.
        self._arm()
        for _ in range(int(self.params["warmup_events"])):
            self._event()
            self.net.loop.advance(self._next_gap())
        self._settle()

    def _order_blocks(self) -> np.random.Generator:
        """``--seed`` orders the pool of blocks, or seeds the traffic's
        generators where the cell names no pool; it always seeds the
        parity check's sample (returned)."""
        self._block_events = int(
            self.params.get("hot_epoch_events", BLOCK_EVENTS)
        )
        self._block = -1
        self.injected = Counter()  # by kind, scripted warm-up included
        rng = np.random.default_rng(self.seed)
        if "pool" not in self.params:
            self._order = None  # the streams, in the order they always had
            self._mix, self._loss, self._gap, keep, self._pick, self._hot = (
                rng.spawn(len(STREAMS) + 1)
            )
            return keep
        order, keep = rng.spawn(2)
        self._order = order.permutation(int(self.params["pool"]))
        return keep

    def _event(self) -> None:
        """Inject the traffic's next event, from the generators of the
        block it falls in."""
        block = self.injected.total() // self._block_events
        if block != self._block:
            self._block = block
            if self._order is not None:
                at = int(self._order[block % len(self._order)])
                fresh = np.random.default_rng([TRAFFIC_SEED, at])
                for name, stream in zip(STREAMS, fresh.spawn(len(STREAMS))):
                    setattr(self, name, stream)
        self._inject()

    def _arm(self) -> None:
        """Fresh tracker and ledger: the window sees its own events."""
        from holo_tpu.telemetry import convergence, critpath

        self.tracker = convergence.configure(
            CAPACITY, clock=self.net.loop.clock.now
        )
        self.ledger = critpath.configure(
            CAPACITY, check_every=0, waterfalls=CAPACITY
        )
        self.stamps = WallStamps(self.ledger)
        convergence.set_critpath_hook(self.stamps)

    def _wrap_compute(self) -> None:
        """Keep (topology, result) of a seeded sample of the window's
        dispatches for the scalar oracle."""
        inner = self.backend.compute

        def compute(topo, edge_mask=None, **kw):
            res = inner(topo, edge_mask, **kw)
            if self._sampling:
                self.kept.offer(lambda: (topo, edge_mask, parity.keep(res)))
            return res

        self.backend.compute = compute  # shadows the method; see close()

    # -- traffic

    def _inject(self) -> None:
        net, roll = self.net, self._mix.random()
        if roll < self._edges[0]:
            kind = "lsa"
            edge = net.flappable[int(self._mix.integers(len(net.flappable)))]
            net.flap(
                edge, lost=self._loss.random() < self.params["drop_prob"]
            )
        elif roll < self._edges[1]:
            kind = "bfd"
            net.bfd(net.g0, "up" if self._bfd_down else "down")
            self._bfd_down = not self._bfd_down
        elif roll < self._edges[2]:
            kind = "carrier"
            net.carrier("e1", operative=self._carrier_down)
            self._carrier_down = not self._carrier_down
        else:
            kind = "ifconfig"
            net.ifconfig_metric()
        self.injected[kind] += 1

    def _next_gap(self) -> float:
        """Mostly sub-second (a real flap storm), at times a lull of
        seconds that lets the delay FSM drain.  Virtual seconds."""
        lo, hi = (
            self.params["gap_short_s"]
            if self._gap.random() < self.params["gap_short_share"]
            else self.params["gap_long_s"]
        )
        return lo + self._gap.random() * (hi - lo)

    def _settle(self) -> None:
        self.net.loop.advance(float(self.params["settle_s"]))
        self.tracker.sweep()

    # -- the window

    def run(self, window) -> dict:
        self._arm()
        clock, advance = time.perf_counter, self.net.loop.advance
        generator_s, events = 0.0, 0
        self._sampling = True
        window.open()
        while window.tick():
            t0 = clock()
            self._event()
            generator_s += clock() - t0
            events += 1
            advance(self._next_gap())
        window.close()
        self._sampling = False
        self._settle()  # outside the window: what was begun, converges

        done = self.stamps.done
        outcomes = dict(Counter(outcome for _t, outcome, *_ in done))
        failed = sum(
            1 for _t, outcome, fb, _t0, _w in done
            if outcome == "evicted" or fb
        )
        spf_path = [
            (t0 - window.t_open, wall)
            for trigger, outcome, _fb, t0, wall in done
            if trigger in SPF_TRIGGERS and outcome == "converged"
            and wall is not None
        ]
        walls = [wall for _begin, wall in spf_path]
        end_to_end = {}
        if walls:
            end_to_end = {
                self.METRIC: {
                    "value": stats.percentile(walls, 50.0) * 1e3,
                    "unit": "ms",
                },
            }
        return {
            "attempted": len(done),
            "failed": failed,
            "end_to_end": end_to_end,
            "samples": {
                "trigger_fib_wall_s": walls,
                "trigger_fib_begin_s": [begin for begin, _wall in spf_path],
            },
            "timing": {"trigger_fib_wall_s": stats.summary(walls)},
            "clocks": {"generator_s": generator_s, "window_s": window.wall},
            "waterfalls": self.ledger.waterfalls(),
            "counts": {
                "injected": events, "events": len(done),
                "block_order": (
                    None if self._order is None else self._order.tolist()
                ),
                "blocks_begun": self._block + 1,
                "outcomes": outcomes, "spf_path_converged": len(walls),
                "tail_samples_beyond": (
                    stats.samples_beyond(len(walls), 90.0) if walls else 0
                ),
                "spf_runs": self.net.inst.spf_run_count,
                "fib_size": len(self.net.kernel.fib),
            },
        }

    # -- parity, outside the window

    def verify(self) -> dict:
        """(i) the sampled dispatches against the scalar oracle on four
        planes; (ii) the settled FIB against the FIB the scalar backend
        derives from the same LSDB by one forced full SPF."""
        from holo_tpu.spf.backend import ScalarSpfBackend
        from holo_tpu.telemetry.canary import fib_digest

        scalar = parity.against_scalar(self.kept.items)
        before = fib_digest(self.net.kernel.fib)
        self.net.inst.backend = ScalarSpfBackend()  # daemon/providers.py seam
        self.net.inst._schedule_spf()  # trigger-less: a full run
        self.net.loop.advance(float(self.params["settle_s"]))
        after = fib_digest(self.net.kernel.fib)
        return {
            "ok": (
                not scalar["mismatches"] and scalar["checked"] > 0
                and before == after and len(self.net.kernel.fib) > 0
            ),
            "scalar": scalar,
            "fib_digest": before, "fib_digest_scalar": after,
        }

    def close(self) -> None:
        from holo_tpu.telemetry import convergence, critpath

        if self.backend is not None:
            vars(self.backend).pop("compute", None)
        critpath.configure(0)
        convergence.configure(0)
