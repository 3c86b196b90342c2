"""Synthetic LSDB generators for tests and benchmarks.

Produce :class:`Topology` objects honoring the OSPF vertex model the SPF
engine assumes (SURVEY.md §3.3):

- vertex indices in tie-break order: transit networks first, then routers
  (holo-ospf/src/ospfv2/spf.rs:42-45 orders Network < Router);
- router→router (p2p) and router→network links cost >= 1;
- network→router links cost 0 (RFC 2328 §16.1);
- ``edge_direct_atom`` assigned exactly where the reference computes next
  hops directly (parent hops == 0: edges out of the root, and edges out of
  root-adjacent transit networks — holo-ospf/src/spf.rs:744-767).
"""

from __future__ import annotations

import numpy as np

from holo_tpu.ops.graph import Topology


def clone_topology(
    topo: Topology,
    keep: np.ndarray | None = None,
    extra=None,
    cost: dict | None = None,
) -> Topology:
    """Fresh-identity copy of ``topo`` with optional edge mutations —
    the shared mutation helper for DeltaPath tests and fuzzing.  ``keep``: bool[E] edge filter; ``extra``: rows of
    (src, dst, cost, atom) to append; ``cost``: {edge index: new cost}
    over the (post-filter) edge array.  The result has its own
    uid/generation
    (a distinct marshal-cache identity) and NO delta lineage."""
    src, dst, c, atom = (
        topo.edge_src, topo.edge_dst, topo.edge_cost, topo.edge_direct_atom
    )
    if keep is not None:
        src, dst, c, atom = src[keep], dst[keep], c[keep], atom[keep]
    else:
        src, dst, c, atom = src.copy(), dst.copy(), c.copy(), atom.copy()
    if cost is not None:
        for i, v in cost.items():
            c[i] = v
    if extra is not None:
        e = np.asarray(extra, np.int32).reshape(-1, 4)
        src = np.concatenate([src, e[:, 0]])
        dst = np.concatenate([dst, e[:, 1]])
        c = np.concatenate([c, e[:, 2]])
        atom = np.concatenate([atom, e[:, 3]])
    return Topology(
        n_vertices=topo.n_vertices,
        is_router=topo.is_router.copy(),
        edge_src=src, edge_dst=dst, edge_cost=c, edge_direct_atom=atom,
        root=topo.root,
        # The native partition hint is per-vertex state: mutation
        # chains keep it, or diff_topologies refuses to link the delta
        # (the partitioned resident's cut geometry would go stale).
        partition_hint=(
            None
            if topo.partition_hint is None
            else topo.partition_hint.copy()
        ),
    )


def assign_direct_atoms(topo: Topology) -> int:
    """Assign next-hop atom ids in-place; returns the atom count.

    One atom per root out-edge (p2p neighbor / attached network interface),
    plus one per (root-adjacent network → attached router) pair — i.e. the
    distinct (interface, neighbor address) next hops OSPF can produce for
    intra-area destinations.
    """
    atom = np.full(topo.n_edges, -1, np.int32)
    next_id = 0
    root_nets = set()
    for e in range(topo.n_edges):
        if topo.edge_src[e] == topo.root:
            atom[e] = next_id
            next_id += 1
            dst = int(topo.edge_dst[e])
            if not topo.is_router[dst]:
                root_nets.add(dst)
    for e in range(topo.n_edges):
        s = int(topo.edge_src[e])
        if s in root_nets and topo.edge_dst[e] != topo.root:
            atom[e] = next_id
            next_id += 1
    topo.edge_direct_atom = atom
    topo.touch()
    return next_id


def random_ospf_topology(
    n_routers: int,
    n_networks: int = 0,
    extra_p2p: int | None = None,
    max_cost: int = 20,
    seed: int = 0,
    root: int | None = None,
) -> Topology:
    """Random connected OSPF-style topology.

    Routers are joined by a random spanning tree plus ``extra_p2p`` random
    p2p links (both directions, independent costs — OSPF link costs are
    per-direction).  Each transit network connects 2-5 random routers.
    """
    rng = np.random.default_rng(seed)
    n = n_networks + n_routers  # networks occupy indices [0, n_networks)
    is_router = np.zeros(n, bool)
    is_router[n_networks:] = True
    rtr = lambda i: n_networks + i

    src, dst, cost = [], [], []

    def add(a, b, c):
        src.append(a)
        dst.append(b)
        cost.append(c)

    # Random spanning tree over routers.
    order = rng.permutation(n_routers)
    for i in range(1, n_routers):
        a, b = rtr(order[i]), rtr(order[rng.integers(0, i)])
        add(a, b, int(rng.integers(1, max_cost + 1)))
        add(b, a, int(rng.integers(1, max_cost + 1)))

    if extra_p2p is None:
        extra_p2p = n_routers
    seen = set(zip(src, dst))
    for _ in range(extra_p2p):
        a, b = rng.integers(0, n_routers, 2)
        if a == b:
            continue
        a, b = rtr(a), rtr(b)
        if (a, b) in seen:
            continue
        seen.add((a, b))
        seen.add((b, a))
        add(a, b, int(rng.integers(1, max_cost + 1)))
        add(b, a, int(rng.integers(1, max_cost + 1)))

    # Transit networks.
    for net in range(n_networks):
        k = int(rng.integers(2, 6))
        members = rng.choice(n_routers, size=min(k, n_routers), replace=False)
        for m in members:
            add(rtr(m), net, int(rng.integers(1, max_cost + 1)))
            add(net, rtr(m), 0)

    topo = Topology(
        n_vertices=n,
        is_router=is_router,
        edge_src=np.array(src, np.int32),
        edge_dst=np.array(dst, np.int32),
        edge_cost=np.array(cost, np.int32),
        root=rtr(0) if root is None else root,
    )
    assign_direct_atoms(topo)
    return topo


def fat_tree_topology(k: int = 20, seed: int = 0) -> Topology:
    """Three-tier fat-tree of p2p router links (the 10k-node benchmark shape).

    k pods × (k/2 edge + k/2 agg) + (k/2)^2 core routers; k=20 → 300 core +
    20×20 pod routers = 700... scaled variant: use ``k`` and ``hosts`` to hit
    target sizes.  Costs are uniform 1 (typical DC) with per-direction
    symmetric entries.
    """
    rng = np.random.default_rng(seed)
    half = k // 2
    n_core = half * half
    n_agg = k * half
    n_edge = k * half
    n = n_core + n_agg + n_edge
    core = lambda i: i
    agg = lambda p, i: n_core + p * half + i
    edge = lambda p, i: n_core + n_agg + p * half + i

    src, dst, cost = [], [], []

    def add2(a, b):
        c1 = int(rng.integers(1, 4))
        c2 = int(rng.integers(1, 4))
        src.extend((a, b))
        dst.extend((b, a))
        cost.extend((c1, c2))

    for p in range(k):
        for i in range(half):
            for j in range(half):
                add2(agg(p, i), edge(p, j))  # intra-pod full bipartite
            for j in range(half):
                add2(agg(p, i), core(i * half + j))  # agg i ↔ its core group

    topo = Topology(
        n_vertices=n,
        is_router=np.ones(n, bool),
        edge_src=np.array(src, np.int32),
        edge_dst=np.array(dst, np.int32),
        edge_cost=np.array(cost, np.int32),
        root=edge(0, 0),
    )
    assign_direct_atoms(topo)
    return topo


def ring_topology(n_routers: int, max_cost: int = 10, seed: int = 0) -> Topology:
    """Router ring (the canonical LFA-coverage-gap shape: with uniform
    costs half the ring has no per-neighbor LFA and needs rLFA/TI-LFA)."""
    rng = np.random.default_rng(seed)
    src, dst, cost = [], [], []
    for i in range(n_routers):
        j = (i + 1) % n_routers
        src.extend((i, j))
        dst.extend((j, i))
        cost.extend(
            (int(rng.integers(1, max_cost + 1)), int(rng.integers(1, max_cost + 1)))
        )
    topo = Topology(
        n_vertices=n_routers,
        is_router=np.ones(n_routers, bool),
        edge_src=np.array(src, np.int32),
        edge_dst=np.array(dst, np.int32),
        edge_cost=np.array(cost, np.int32),
        root=0,
    )
    assign_direct_atoms(topo)
    return topo


def grid_topology(rows: int, cols: int, max_cost: int = 10, seed: int = 0) -> Topology:
    """rows×cols router grid with per-direction random costs."""
    rng = np.random.default_rng(seed)
    n = rows * cols
    vid = lambda r, c: r * cols + c
    src, dst, cost = [], [], []

    def add2(a, b):
        src.extend((a, b))
        dst.extend((b, a))
        cost.extend(
            (int(rng.integers(1, max_cost + 1)), int(rng.integers(1, max_cost + 1)))
        )

    for r in range(rows):
        for c in range(cols):
            if c + 1 < cols:
                add2(vid(r, c), vid(r, c + 1))
            if r + 1 < rows:
                add2(vid(r, c), vid(r + 1, c))
    topo = Topology(
        n_vertices=n,
        is_router=np.ones(n, bool),
        edge_src=np.array(src, np.int32),
        edge_dst=np.array(dst, np.int32),
        edge_cost=np.array(cost, np.int32),
        root=0,
    )
    assign_direct_atoms(topo)
    return topo


def hub_topology(
    n_routers: int = 48,
    n_hubs: int = 3,
    spokes: int = 14,
    lan_members: int = 30,
    max_cost: int = 2,
    seed: int = 0,
) -> Topology:
    """Rocketfuel-shaped hubs around the root (router 0 after the two
    networks): ``n_hubs`` routers with ``spokes`` extra links each, far
    more in-edges than the rest; the root linked to every hub, to two
    other routers, twice to one of them, and to a stub router whose
    leaf hangs off it alone (both cut off when that link fails); one
    transit network on the root with ``lan_members`` routers (a next-hop
    atom each, so past 32 they spill into a second bitmask word) and
    one off it; three links doubled with their costs.  The root's
    links come last in edge order, so a hub's or the LAN's in-edge from
    the root ranks after its others.  Low costs make equal-cost
    parents common."""
    rng = np.random.default_rng(seed)
    n_nets = 2
    n = n_nets + n_routers
    rtr = lambda i: n_nets + i
    root, stub, leaf = rtr(0), rtr(n_routers - 2), rtr(n_routers - 1)
    hubs = [rtr(1 + i) for i in range(n_hubs)]
    inner = list(range(1, n_routers - 2))  # not the root, stub or leaf
    pairs: list[tuple[int, int]] = []
    order = rng.permutation(inner)
    for i in range(1, len(order)):
        pairs.append((rtr(order[i]), rtr(order[rng.integers(0, i)])))
    for h in hubs:
        for r in rng.choice([i for i in inner if rtr(i) != h], spokes,
                            replace=False):
            pairs.append((h, rtr(r)))
    pairs.append((stub, leaf))
    src, dst, cost = [], [], []

    def add2(a, b, ca=None, cb=None):
        src.extend((a, b))
        dst.extend((b, a))
        cost.extend((
            int(rng.integers(1, max_cost + 1)) if ca is None else ca,
            int(rng.integers(1, max_cost + 1)) if cb is None else cb,
        ))

    for a, b in pairs:
        add2(a, b)
    for e in rng.choice(len(pairs), 3, replace=False):
        add2(src[2 * e], dst[2 * e], cost[2 * e], cost[2 * e + 1])
    for net, members in (
        (0, rng.choice(inner, lan_members, replace=False)),
        (1, rng.choice(inner, 4, replace=False)),
    ):
        for m in members:
            src.extend((rtr(m), net))
            dst.extend((net, rtr(m)))
            cost.extend((int(rng.integers(1, max_cost + 1)), 0))
    # The root's links, last.
    others = [rtr(i) for i in rng.choice(inner[n_hubs:], 2, replace=False)]
    for far in (*hubs, *others, others[0], stub):
        add2(root, far)
    src.extend((root, 0))
    dst.extend((0, root))
    cost.extend((int(rng.integers(1, max_cost + 1)), 0))

    is_router = np.ones(n, bool)
    is_router[:n_nets] = False
    topo = Topology(
        n_vertices=n,
        is_router=is_router,
        edge_src=np.array(src, np.int32),
        edge_dst=np.array(dst, np.int32),
        edge_cost=np.array(cost, np.int32),
        root=root,
    )
    assign_direct_atoms(topo)
    return topo


def whatif_link_failure_masks(topo: Topology, n_scenarios: int, seed: int = 0) -> np.ndarray:
    """bool[B, E] masks, each failing one bidirectional link (both directions).

    Scenario 0 is always the no-failure base case.
    """
    rng = np.random.default_rng(seed)
    pair_of = {}
    for e in range(topo.n_edges):
        pair_of[(int(topo.edge_src[e]), int(topo.edge_dst[e]))] = e
    masks = np.ones((n_scenarios, topo.n_edges), bool)
    for b in range(1, n_scenarios):
        e = int(rng.integers(0, topo.n_edges))
        masks[b, e] = False
        rev = pair_of.get((int(topo.edge_dst[e]), int(topo.edge_src[e])))
        if rev is not None:
            masks[b, rev] = False
    return masks
