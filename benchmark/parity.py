"""The comparison that decides ``correct``: device results against the
plain references — the scalar oracle on all four planes, the C++
baseline on distances.  Always run outside the measured window."""

from __future__ import annotations

from types import SimpleNamespace

import numpy as np

PLANES = ("dist", "parent", "hops", "nexthop_words")


def keep(result) -> SimpleNamespace:
    """A copy of the four compared planes, owning its memory: a row of
    a batch is a view that would keep the whole batch alive."""
    return SimpleNamespace(
        **{p: np.array(getattr(result, p)) for p in PLANES}
    )


def differing_planes(got, ref) -> list[str]:
    """Names of the planes of two ``SpfResult``s that are not
    bit-identical."""
    return [
        p for p in PLANES
        if not np.array_equal(getattr(got, p), getattr(ref, p))
    ]


def against_scalar(samples) -> dict:
    """``samples``: (topology, edge mask or None, device result).  Each
    is recomputed by ``ScalarSpfBackend`` and compared on four planes."""
    from holo_tpu.spf.backend import ScalarSpfBackend

    oracle = ScalarSpfBackend()
    bad = []
    for n, (topo, mask, got) in enumerate(samples):
        diff = differing_planes(got, oracle.compute(topo, mask))
        if diff:
            bad.append({"sample": n, "planes": diff})
    return {"checked": len(samples), "mismatches": bad}


def against_native(topo, masks: np.ndarray, results) -> dict:
    """Distances of ``results`` against the C++ serial baseline run on
    the same ``masks`` (bool[S, E])."""
    from holo_tpu.native_build import native_spf_batch_dist

    ref = native_spf_batch_dist(topo, masks)
    bad = [
        {"sample": n} for n, got in enumerate(results)
        if not np.array_equal(got.dist, ref[n])
    ]
    return {"checked": len(results), "mismatches": bad}


class Reservoir:
    """A seeded uniform sample of ``size`` items from a stream whose
    length is not known beforehand (the window ends on the clock)."""

    def __init__(self, size: int, rng: np.random.Generator):
        self.size = size
        self.items: list = []
        self._rng = rng
        self._seen = 0

    def offer(self, make) -> None:
        """The next item of the stream is ``make()``, built (copied)
        only if it is kept."""
        self._seen += 1
        if len(self.items) < self.size:
            self.items.append(make())
            return
        at = int(self._rng.integers(0, self._seen))
        if at < self.size:
            self.items[at] = make()
