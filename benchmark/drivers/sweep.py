"""A controller's single-link-failure sweep, closed loop, one batch in
flight: consecutive chunks of the seeded link permutation, one
``compute_whatif(topo, masks)`` after another, results on the host.

One host mask buffer is edited in place between batches (restore the
last chunk, clear the next), so every batch is new input at no
generator cost and no result can come from a cache keyed on its input.

params: ``batch`` (rows per dispatch, row 0 = no failure),
``parity_native`` / ``parity_scalar`` (rows kept for the C++ baseline
and, of those, for the scalar oracle).
"""

from __future__ import annotations

import numpy as np

from benchmark import fabric, parity


class Driver:
    def __init__(self, config: dict, params: dict, seed: int):
        self.config, self.params, self.seed = config, params, seed

    def set_up(self) -> None:
        self.topo = fabric.topology_of(self.config)
        self.backend = fabric.backend_of(self.config)
        self.batch = int(self.params["batch"])
        order, self.rng = np.random.default_rng(self.seed).spawn(2)
        self.links = fabric.LinkFailures(self.topo.n_edges, order)
        self.masks = np.ones((self.batch, self.topo.n_edges), bool)
        self.kept = parity.Reservoir(
            int(self.params.get("parity_native", 8)), self.rng
        )
        self._batch()  # the one shape, warmed

    def _batch(self):
        chunk = self.links.take(self.batch - 1)
        fabric.write_rows(self.masks, chunk, up=False)
        try:
            return chunk, self.backend.compute_whatif(self.topo, self.masks)
        finally:
            fabric.write_rows(self.masks, chunk, up=True)

    def _seeded_row(self, chunk, results):
        """(link, result) of one seeded failure row of a kept batch."""
        row = int(self.rng.integers(1, self.batch))
        return int(chunk[row - 1]), parity.keep(results[row])

    def run(self, window) -> dict:
        batches = done = short = 0
        window.open()
        while window.tick():
            chunk, results = self._batch()
            batches += 1
            done += len(results)
            short += self.batch - len(results)
            self.kept.offer(lambda: self._seeded_row(chunk, results))
        window.close()
        return {
            "attempted": batches * self.batch,
            "failed": short,  # rows asked for and not answered
            "end_to_end": {
                "whatif_runs_per_s": {
                    "value": done / window.wall, "unit": "runs/s",
                },
            },
            "samples": {},
            "clocks": {"window_s": window.wall},
            "counts": {"batches": batches, "scenarios": done},
        }

    def verify(self) -> dict:
        links = [link for link, _ in self.kept.items]
        results = [res for _, res in self.kept.items]
        e = self.topo.n_edges
        masks = np.stack(
            [fabric.mask_of(e, link) for link in links]
        )
        native = parity.against_native(self.topo, masks, results)
        n_scalar = int(self.params.get("parity_scalar", 2))
        scalar = parity.against_scalar([
            (self.topo, masks[n], results[n])
            for n in range(min(n_scalar, len(results)))
        ])
        return {
            "ok": not native["mismatches"] and not scalar["mismatches"],
            "native": native, "scalar": scalar,
        }

    def close(self) -> None:
        pass
