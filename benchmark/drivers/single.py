"""One client, closed loop: ``compute(topo, edge_mask)`` with a fresh
single-link failure per query, result on the host.

params: ``parity_samples`` (queries kept for the scalar oracle).
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import fabric, parity, stats


class Driver:
    def __init__(self, config: dict, params: dict, seed: int):
        self.config, self.params, self.seed = config, params, seed

    def set_up(self) -> None:
        self.topo = fabric.topology_of(self.config)
        self.backend = fabric.backend_of(self.config)
        order, keep = np.random.default_rng(self.seed).spawn(2)
        self.links = fabric.LinkFailures(self.topo.n_edges, order)
        self.mask = np.ones(self.topo.n_edges, bool)
        self.kept = parity.Reservoir(
            int(self.params.get("parity_samples", 4)), keep
        )
        self._query(int(self.links.take(1)[0]))  # the one shape, warmed

    def _query(self, link: int):
        self.mask[2 * link] = self.mask[2 * link + 1] = False
        try:
            return self.backend.compute(self.topo, self.mask)
        finally:
            self.mask[2 * link] = self.mask[2 * link + 1] = True

    def run(self, window) -> dict:
        walls: list[float] = []
        clock = time.perf_counter
        window.open()
        while window.tick():
            link = int(self.links.take(1)[0])
            t0 = clock()
            res = self._query(link)
            walls.append(clock() - t0)
            self.kept.offer(lambda: (link, parity.keep(res)))
        window.close()
        n = len(walls)
        return {
            "attempted": n,
            "failed": 0,  # a query that raises ends the run
            "end_to_end": {
                "spf_query_p50_ms": {
                    "value": stats.percentile(walls, 50.0) * 1e3,
                    "unit": "ms",
                },
            },
            "samples": {"query_wall_s": walls},
            "timing": {"query_wall_s": stats.summary(walls)},
            "clocks": {"window_s": window.wall},
            "counts": {"queries": n},
        }

    def verify(self) -> dict:
        report = parity.against_scalar([
            (self.topo, fabric.mask_of(self.topo.n_edges, link),
             res)
            for link, res in self.kept.items
        ])
        return {"ok": not report["mismatches"], "scalar": report}

    def close(self) -> None:
        pass
