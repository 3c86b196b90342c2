"""The benchmark's own fat-tree LSDB and link-failure traffic.

A k-ary fat-tree (Al-Fares et al., SIGCOMM 2008, sec 3) of p2p router
links in the OSPF vertex model the SPF engines assume: k pods of k/2
edge and k/2 aggregation routers and (k/2)^2 core routers, per-direction
costs drawn from the seed.  Directed edges 2l and 2l+1 are the two
directions of undirected link l, so a link failure is two mask entries
and needs no lookup.
"""

from __future__ import annotations

import numpy as np


def fat_tree(k: int, cost_low: int, cost_high: int, seed: int):
    """``Topology`` of the k-ary fat-tree, root = edge router (0, 0)."""
    from holo_tpu.ops.graph import Topology

    if k < 2 or k % 2:
        raise ValueError(f"fat-tree arity must be even and >= 2, got {k}")
    half = k // 2
    n_core, n_agg = half * half, k * half
    n = n_core + 2 * n_agg
    p, i, j = (a.ravel() for a in np.meshgrid(
        np.arange(k), np.arange(half), np.arange(half), indexing="ij"
    ))
    agg = n_core + p * half + i
    edge = n_core + n_agg + p * half + j
    core = i * half + j
    a = np.concatenate([agg, agg])  # intra-pod bipartite, then agg-core
    b = np.concatenate([edge, core])
    src = np.stack([a, b], axis=1).ravel().astype(np.int32)
    dst = np.stack([b, a], axis=1).ravel().astype(np.int32)
    rng = np.random.default_rng(seed)
    cost = rng.integers(cost_low, cost_high + 1, src.size).astype(np.int32)
    root = n_core + n_agg
    # Direct next-hop atoms: one per edge out of the root, in edge order
    # (all vertices are routers, so there is no root-adjacent network).
    atom = np.full(src.size, -1, np.int32)
    out = np.flatnonzero(src == root)
    atom[out] = np.arange(out.size, dtype=np.int32)
    return Topology(
        n_vertices=n,
        is_router=np.ones(n, bool),
        edge_src=src, edge_dst=dst, edge_cost=cost,
        edge_direct_atom=atom, root=root,
    )


class LinkFailures:
    """A seeded permutation of the undirected links, handed out in
    order and wrapped around when it runs out."""

    def __init__(self, n_edges: int, rng: np.random.Generator):
        if n_edges % 2:
            raise ValueError("edges must come in pairs, one per direction")
        self.n_links = n_edges // 2
        self._perm = rng.permutation(self.n_links)
        self._at = 0

    def take(self, count: int) -> np.ndarray:
        """The next ``count`` links of the permutation."""
        idx = (self._at + np.arange(count)) % self.n_links
        self._at = int((self._at + count) % self.n_links)
        return self._perm[idx]


def write_rows(masks: np.ndarray, links: np.ndarray, up: bool) -> None:
    """In place: rows 1..len(links) of ``masks`` (row 0 is the
    no-failure scenario) get link ``links[r-1]`` set up or down.  The
    caller's buffer is the next dispatch's input: no mask is built
    twice, none is cached."""
    rows = np.arange(1, links.size + 1)
    masks[rows, 2 * links] = up
    masks[rows, 2 * links + 1] = up


def mask_of(n_edges: int, link: int | None) -> np.ndarray:
    """A fresh edge mask with ``link`` failed (None: no failure)."""
    mask = np.ones(n_edges, bool)
    if link is not None:
        mask[2 * link] = mask[2 * link + 1] = False
    return mask


def topology_of(config: dict):
    """The LSDB graph a configuration file describes under
    ``topology``.  The graph is the deployment: its costs come from the
    file's ``cost_seed``, so that every run does the same amount of
    work (the fixpoint's round count moves with the costs), and
    ``--seed`` draws the traffic alone."""
    spec = config["topology"]
    if spec["generator"] != "fat_tree":
        raise ValueError(f"unknown topology generator {spec['generator']!r}")
    return fat_tree(
        spec["k"], spec["cost_low"], spec["cost_high"], spec["cost_seed"]
    )


def backend_of(config: dict):
    """The device backend a configuration file describes under
    ``backend`` (keyword arguments of ``TpuSpfBackend``; none: the
    default engine a daemon gets)."""
    from holo_tpu.spf.backend import TpuSpfBackend

    return TpuSpfBackend(**config.get("backend", {}))
