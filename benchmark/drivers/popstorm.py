"""The storm of ``drivers/storm.py`` on a PoP-structured backbone
(``benchmark/popnet.py``): the same window loop, wall stamps, result
keys and parity checks, with three kinds of ``lsa`` event instead of
one — a link flap drawn with skew, a shared-risk cut (every link of one
conduit in one event) and a router loss or return (every neighbour's
Router-LSA in one event).

params, beside the parent's: ``mix`` has ``link`` / ``srlg`` / ``node``
in place of ``lsa``; ``hot_set``, ``hot_share``, ``zipf_s``,
``hot_epoch_events`` (with probability ``hot_share`` a link of a hot set
of ``hot_set`` flappable links, by Zipf rank; else uniform; the set is
drawn anew every ``hot_epoch_events`` injected events); ``node_draw``
(shares of access and of backbone or aggregation routers among the
losses); ``max_down`` (routers and shared-risk groups down at once: a
draw beyond that brings the oldest back).  ``drop_prob`` holds for each
of the three kinds as a whole event.  The warm-up first injects one
event of every kind, the loss of a router at the port cap among them,
each alone in its SPF run, before any random draw.
"""

from __future__ import annotations

import time
from collections import Counter

import numpy as np

from benchmark import fabric, parity, stats
from benchmark.drivers import storm
from benchmark.popnet import PopNet

#: virtual seconds after each scripted warm-up event: past the longest
#: RFC 8405 hold, so that each runs its own SPF
SCRIPT_GAP_S = 6.0
KINDS = ("link", "srlg", "node", "bfd", "carrier", "ifconfig")


def _moved(window, family: str) -> dict:
    """``{label string: move over the window}`` of a counter family's
    children that moved."""
    first, last = window.snap["open"], window.snap["close"]
    return {
        key.partition("{")[2].rstrip("}"): value - first.get(key, 0)
        for key, value in last.items()
        if key.split("{", 1)[0] == family and value != first.get(key, 0)
    }


def _toggle(down: list, target: int, most: int) -> int:
    """What a draw of ``target`` toggles: itself if it is down or there
    is room, else the oldest one down (which comes back)."""
    if target in down or len(down) < most:
        return target
    return down[0]


class Driver(storm.Driver):
    def set_up(self) -> None:
        params = self.params
        self.backend = fabric.backend_of(self.config)
        self.net = PopNet(
            self.config["lsdb"], self.backend, self.config["spf_delay"],
            params["rxmt_delay_s"],
        )
        keep = self._order_blocks()
        self._edges = np.cumsum([params["mix"][k] for k in KINDS[:-1]])
        rank = np.arange(1, params["hot_set"] + 1, dtype=float)
        self._zipf = rank ** -params["zipf_s"]
        self._zipf /= self._zipf.sum()
        self._hot_links, self._hot_epoch = None, -1
        self._bfd_down = self._carrier_down = False
        self.kept = parity.Reservoir(int(params["parity_samples"]), keep)
        self._sampling = False
        self._last_topo = None
        self.delta_ops: list[int] = []  # per window dispatch; -1: no lineage
        self.dispatch_walls: list[float] = []
        self._wrap_compute()
        self._arm()
        # Warm-up: every kind once, each alone in its run, so that every
        # program the window can need is compiled (or fetched) before it
        # opens; then the window's own traffic for the rest.
        scripted = self._script()
        for event in scripted:
            event()
            self.net.loop.advance(SCRIPT_GAP_S)
        for _ in range(int(params["warmup_events"]) - len(scripted)):
            self._event()
            self.net.loop.advance(self._next_gap())
        self._settle()
        self.warmup = Counter(self.injected)

    def _wrap_compute(self) -> None:
        super()._wrap_compute()
        inner = self.backend.compute  # the parent's sampler

        def compute(topo, edge_mask=None, **kw):
            self._last_topo = topo
            if not self._sampling:
                return inner(topo, edge_mask, **kw)
            # inside the window: the delta's size, and the dispatch's
            # wall on the benchmark's clock (untraced runs have no stage
            # histogram to say whether a seed's walls differ by the
            # device's share or by the host's)
            delta = getattr(topo, "delta_base", None)
            self.delta_ops.append(-1 if delta is None else delta.n_ops)
            t0 = time.perf_counter()
            res = inner(topo, edge_mask, **kw)
            self.dispatch_walls.append(time.perf_counter() - t0)
            return res

        self.backend.compute = compute

    def _script(self) -> list:
        """One event of every kind and its undoing, nothing drawn."""
        net = self.net
        degree = net.graph.degrees()
        cap = self.config["lsdb"]["port_cap"]
        hub = next(i for i in net.losable["core"] if degree[i] == cap)
        self.warmup_hub = {"router": hub, "degree": int(degree[hub])}
        edge = net.flappable[0]

        def count(kind, fn, *args, **kw):
            def event():
                self.injected[kind] += 1
                fn(*args, **kw)
            return event

        return [
            count("link", net.flap, edge, lost=False),
            count("link", net.flap, edge, lost=False),
            count("srlg", net.srlg, 0, lost=False),
            count("srlg", net.srlg, 0, lost=False),
            count("node", net.node, hub, lost=False),
            count("node", net.node, hub, lost=False),
            count("bfd", net.bfd, net.g0, "down"),
            count("bfd", net.bfd, net.g0, "up"),
            count("carrier", net.carrier, "e1", operative=False),
            count("carrier", net.carrier, "e1", operative=True),
            count("ifconfig", net.ifconfig_metric),
            count("ifconfig", net.ifconfig_metric),
        ]

    # -- traffic

    def _link(self) -> tuple[int, int]:
        params, links = self.params, self.net.flappable
        if self._block != self._hot_epoch:  # a hot set lives one block
            self._hot_epoch = self._block
            self._hot_links = self._hot.choice(
                len(links), size=params["hot_set"], replace=False
            )
        if self._pick.random() < params["hot_share"]:
            at = self._hot_links[
                int(self._pick.choice(params["hot_set"], p=self._zipf))
            ]
        else:
            at = self._pick.integers(len(links))
        return links[int(at)]

    def _inject(self) -> None:
        net, params, roll = self.net, self.params, self._mix.random()
        kind = KINDS[int(np.searchsorted(self._edges, roll, side="right"))]
        if kind in ("link", "srlg", "node"):
            lost = self._loss.random() < params["drop_prob"]
        if kind == "link":
            net.flap(self._link(), lost=lost)
        elif kind == "srlg":
            group = int(self._pick.integers(len(net.graph.srlgs)))
            net.srlg(
                _toggle(net.srlg_down, group, params["max_down"]["srlgs"]),
                lost=lost,
            )
        elif kind == "node":
            share = params["node_draw"]["access"]
            pool = net.losable[
                "access" if self._pick.random() < share else "core"
            ]
            router = pool[int(self._pick.integers(len(pool)))]
            net.node(
                _toggle(net.node_down, router, params["max_down"]["routers"]),
                lost=lost,
            )
        elif kind == "bfd":
            net.bfd(net.g0, "up" if self._bfd_down else "down")
            self._bfd_down = not self._bfd_down
        elif kind == "carrier":
            net.carrier("e1", operative=self._carrier_down)
            self._carrier_down = not self._carrier_down
        else:
            net.ifconfig_metric()
        self.injected[kind] += 1

    # -- the window: the parent's, with this deployment's counts beside

    def run(self, window) -> dict:
        from holo_tpu.ops.spf_engine import shared_graph_cache

        self.net.most_lsas = 0
        out = super().run(window)
        rows, width = self.backend.prepare(self._last_topo).in_src.shape
        ops = self.delta_ops
        walls = self.dispatch_walls
        out["timing"]["dispatch_wall_s"] = {"count": len(walls)} | {
            stats.label(q): stats.percentile(walls, q)
            for q in (10.0, 25.0, 50.0, 75.0, 90.0) if walls
        }
        out["counts"].update(
            delta_paths=_moved(window, "holo_spf_delta_total"),
            diff_paths=_moved(window, "holo_spf_delta_diff_total"),
            largest_delta_ops=max(ops, default=0),
            deltas_over_256_ops=sum(1 for n in ops if n > 256),
            dispatches_without_lineage=ops.count(-1),
            edges=2 * self.net.graph.n_links,
            edges_at_end=int(self._last_topo.n_edges),
            ell_width=int(width), ell_slots=int(rows * width),
            injected_by_kind=dict(self.injected - self.warmup),
            warmup_by_kind=dict(self.warmup), warmup_hub_loss=self.warmup_hub,
            most_lsas_in_one_event=self.net.most_lsas,
            chain_depth_at_end=shared_graph_cache().stats()["max-chain-depth"],
            routers_down_at_end=len(self.net.node_down),
            srlgs_down_at_end=len(self.net.srlg_down),
        )
        return out
