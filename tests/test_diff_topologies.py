"""``diff_topologies`` against the formulation it replaced (ISSUE 26).

The general path used to be one ``np.unique(axis=0)`` over the rows of
both edge lists; it is now 1-D sorts over packed int64 keys.  The old
body is kept here as the oracle: the new function must return the same
``TopologyDelta`` field by field and row by row, because
``_lower_delta`` hands out ELL slots in the order the additions arrive.
No case reads a clock.
"""

import dataclasses

import numpy as np
import pytest

from holo_tpu import telemetry
from holo_tpu.ops.graph import (
    Topology,
    TopologyDelta,
    diff_topologies,
    lookup_sorted,
    mutual_keep_mask,
)
from holo_tpu.spf.synth import clone_topology as clone


def unique_axis0_diff(base, new, max_ops=512):
    """``diff_topologies`` as it stood before ISSUE 26, verbatim but for
    the name: the ``np.unique(axis=0)`` multiset diff."""
    if (
        base.n_vertices != new.n_vertices
        or base.root != new.root
        or not np.array_equal(base.is_router, new.is_router)
    ):
        return None
    bh, nh = base.partition_hint, new.partition_hint
    if (bh is None) != (nh is None) or (
        bh is not None and not np.array_equal(bh, nh)
    ):
        return None
    if base.n_edges == new.n_edges and (
        np.array_equal(base.edge_src, new.edge_src)
        and np.array_equal(base.edge_dst, new.edge_dst)
        and np.array_equal(base.edge_direct_atom, new.edge_direct_atom)
    ):
        changed = np.nonzero(base.edge_cost != new.edge_cost)[0]
        if changed.shape[0] > max_ops:
            return None
        return TopologyDelta(
            base_key=base.cache_key,
            w_src=base.edge_src[changed].copy(),
            w_dst=base.edge_dst[changed].copy(),
            w_old=base.edge_cost[changed].copy(),
            w_new=new.edge_cost[changed].copy(),
            w_atom=base.edge_direct_atom[changed].copy(),
            ids_stable=True,
        )
    if abs(base.n_edges - new.n_edges) > max_ops:
        return None

    def rows(t):
        out = np.empty((t.n_edges, 4), np.int32)
        out[:, 0] = t.edge_src
        out[:, 1] = t.edge_dst
        out[:, 2] = t.edge_cost
        out[:, 3] = t.edge_direct_atom
        return out

    both = np.concatenate([rows(base), rows(new)], axis=0)
    uniq, inv = np.unique(both, axis=0, return_inverse=True)
    inv = inv.reshape(-1)
    count = np.zeros(uniq.shape[0], np.int64)
    np.add.at(count, inv[: base.n_edges], 1)
    np.add.at(count, inv[base.n_edges:], -1)
    rem_mask = count > 0
    add_mask = count < 0
    n_ops = int(count[rem_mask].sum() - count[add_mask].sum())
    if n_ops > max_ops:
        return None
    r = np.repeat(uniq[rem_mask], count[rem_mask], axis=0)
    a = np.repeat(uniq[add_mask], -count[add_mask], axis=0)
    return TopologyDelta(
        base_key=base.cache_key,
        r_src=r[:, 0], r_dst=r[:, 1], r_cost=r[:, 2], r_atom=r[:, 3],
        a_src=a[:, 0], a_dst=a[:, 1], a_cost=a[:, 2], a_atom=a[:, 3],
        ids_stable=False,
    )


def graph(n_vertices, n_links, seed, max_cost=65535, root_atoms=True):
    """``n_links`` bidirectional links as ``2 * n_links`` directed
    edges, grouped by source the way ``build_topology`` emits them;
    the root's out-edges carry atoms 0.., every other edge -1."""
    rng = np.random.default_rng(seed)
    a = rng.integers(0, n_vertices, n_links)
    b = (a + 1 + rng.integers(0, n_vertices - 1, n_links)) % n_vertices
    cost = rng.integers(1, max_cost + 1, n_links)
    src, dst = np.concatenate([a, b]), np.concatenate([b, a])
    cost = np.concatenate([cost, cost])
    order = np.argsort(src, kind="stable")
    src, dst, cost = src[order], dst[order], cost[order]
    atom = np.full(src.shape[0], -1, np.int64)
    if root_atoms:
        at_root = np.flatnonzero(src == src[0])
        atom[at_root] = np.arange(at_root.shape[0])
    return Topology(
        n_vertices=n_vertices,
        is_router=np.ones(n_vertices, bool),
        edge_src=src, edge_dst=dst, edge_cost=cost, edge_direct_atom=atom,
        root=int(src[0]),
    )


def link_mask(topo, e):
    """Both directions of the link edge ``e`` belongs to."""
    s, d = int(topo.edge_src[e]), int(topo.edge_dst[e])
    return (
        ((topo.edge_src == s) & (topo.edge_dst == d))
        | ((topo.edge_src == d) & (topo.edge_dst == s))
    )


def case_pair_removed():
    base = graph(300, 700, seed=1)
    return base, clone(base, keep=~link_mask(base, 411))


def case_pair_added():
    base = graph(300, 700, seed=2)
    return base, clone(base, extra=[[7, 290, 33, -1], [290, 7, 33, -1]])


def case_recosted():
    """One edge re-costed by a rebuilt list (order moved, so not the
    pure-weight path): one removal plus one addition."""
    base = graph(300, 700, seed=3)
    e = 123
    row = [base.edge_src[e], base.edge_dst[e], base.edge_cost[e] + 9,
           base.edge_direct_atom[e]]
    keep = np.ones(base.n_edges, bool)
    keep[e] = False
    return base, clone(base, keep=keep, extra=[row])


def case_multiplicity_2_to_1():
    base = graph(200, 400, seed=4)
    dup = [[5, 9, 77, -1]] * 2
    return clone(base, extra=dup), clone(base, extra=dup[:1])


def case_multiplicity_1_to_3():
    base = graph(200, 400, seed=5)
    dup = [[5, 9, 77, -1]] * 3
    return clone(base, extra=dup[:1]), clone(base, extra=dup)


def case_parallel_links_differ_in_cost_and_atom():
    """Rows that tie on (src, dst) and differ further right: both keys
    of the sort decide, and -1 sorts before atom 0."""
    base = graph(200, 400, seed=6)
    old = [[3, 8, 10, -1], [3, 8, 10, 0], [3, 8, 10, 2], [3, 8, 4, 1]]
    new = [[3, 8, 10, 0], [3, 8, 10, -1], [3, 8, 11, -1], [3, 8, 4, 3]]
    return clone(base, extra=old), clone(base, extra=new)


def case_atom_minus_one_beside_atoms():
    """The root's atom-carrying edges flap beside atom-less ones."""
    base = graph(120, 500, seed=7)
    at_root = np.flatnonzero(base.edge_src == base.root)
    keep = ~(link_mask(base, at_root[0]) | link_mask(base, at_root[-1]))
    return base, clone(
        base, keep=keep, extra=[[base.root, 50, 12, -1], [base.root, 50, 12, 5]]
    )


def case_cost_2_24():
    base = graph(300, 700, seed=8, max_cost=1 << 24)
    e = int(np.argmax(base.edge_cost))
    return base, clone(
        base, keep=~link_mask(base, e),
        extra=[[1, 2, 1 << 24, -1], [2, 1, (1 << 24) - 1, -1]],
    )


def case_vertex_ids_near_65535():
    base = graph(65_536, 900, seed=9)
    return base, clone(
        base, keep=~link_mask(base, 17),
        extra=[[65_535, 65_534, 5, -1], [65_534, 65_535, 5, -1],
               [65_535, 0, 5, -1]],
    )


def case_vertex_ids_near_1000000():
    base = graph(1_000_001, 900, seed=10)
    return base, clone(
        base, keep=~link_mask(base, 29),
        extra=[[1_000_000, 999_999, 65_535, -1],
               [999_999, 1_000_000, 65_535, -1]],
    )


def _ops(n_removed, n_added, seed):
    base = graph(400, 1500, seed=seed)
    keep = np.ones(base.n_edges, bool)
    keep[np.random.default_rng(seed).choice(
        base.n_edges, n_removed, replace=False)] = False
    extra = [[399, i % 398, 70_000 + i, -1] for i in range(n_added)]
    return base, clone(base, keep=keep, extra=extra)


def case_exactly_max_ops():
    return _ops(200, 312, seed=11)  # 512 operations: linked


def case_max_ops_plus_one():
    return _ops(200, 313, seed=12)  # 513: refused, by the same count


def case_edge_count_gap_over_max_ops():
    base = graph(400, 1500, seed=13)
    return base, clone(base, keep=np.arange(base.n_edges) >= 600)


def case_pure_weight():
    base = graph(300, 700, seed=14)
    return base, clone(base, cost={5: 9, 600: 1, 1399: 65_535})


def case_pure_weight_over_max_ops():
    base = graph(300, 700, seed=15)
    return base, clone(base, cost={e: 70_000 for e in range(513)})


def case_empty_delta_same_list():
    base = graph(300, 700, seed=16)
    return base, clone(base)


def case_empty_delta_permuted_list():
    """The same multiset in another order: the general path, no rows."""
    base = graph(300, 700, seed=17)
    perm = np.random.default_rng(17).permutation(base.n_edges)
    new = clone(base)
    for name in ("edge_src", "edge_dst", "edge_cost", "edge_direct_atom"):
        setattr(new, name, getattr(base, name)[perm])
    return base, new


def case_no_edges_to_some():
    base = graph(50, 40, seed=18)
    return clone(base, keep=np.zeros(base.n_edges, bool)), base


def case_disjoint_lists():
    """Nothing cancels: every row of both sides is an operation."""
    base = graph(64, 100, seed=19, root_atoms=False)
    new = graph(64, 100, seed=20, root_atoms=False)
    new.root = base.root
    return base, new


def case_other_root_refused():
    base = graph(300, 700, seed=21)
    new = clone(base, keep=~link_mask(base, 3))
    new.root = (base.root + 1) % base.n_vertices
    return base, new


def case_partition_hint_changed_refused():
    base = graph(300, 700, seed=22)
    base.partition_hint = np.zeros(300, np.int32)
    new = clone(base, keep=~link_mask(base, 3))
    new.partition_hint[7] = 1
    return base, new


def case_storm_cell_size():
    """The storm cell's shape: 10,000 vertices, 26,014 directed edges,
    a few coalesced flaps and one metric flip between two SPF runs."""
    base = graph(10_000, 13_007, seed=23)
    assert base.n_edges == 26_014
    down = link_mask(base, 4_000) | link_mask(base, 19_000)
    prev = clone(base, keep=~down)
    gone = link_mask(base, 11) | link_mask(base, 25_000)
    new = clone(base, keep=~gone, cost={9_000: 77})
    return prev, new


CASES = [fn for name, fn in sorted(globals().items())
         if name.startswith("case_")]

DELTA_ARRAYS = [
    f.name for f in dataclasses.fields(TopologyDelta)
    if f.name not in ("base_key", "ids_stable")
]


@pytest.mark.parametrize("case", CASES, ids=lambda fn: fn.__name__[5:])
def test_equals_unique_axis0_oracle(case):
    base, new = case()
    want = unique_axis0_diff(base, new)
    got = diff_topologies(base, new)
    if want is None:
        assert got is None
        return
    assert got is not None
    assert got.base_key == want.base_key == base.cache_key
    assert got.ids_stable == want.ids_stable
    assert got.kind == want.kind and got.n_ops == want.n_ops
    for name in DELTA_ARRAYS:
        g, w = getattr(got, name), getattr(want, name)
        assert g.dtype == w.dtype == np.int32, name
        assert g.shape == w.shape and np.array_equal(g, w), name


def test_cases_cover_what_they_name():
    """The oracle's own answer on the cases whose names promise one."""
    def n(case):
        d = unique_axis0_diff(*case())
        return None if d is None else (
            d.w_src.shape[0], d.r_src.shape[0], d.a_src.shape[0])

    assert n(case_pair_removed) == (0, 2, 0)
    assert n(case_pair_added) == (0, 0, 2)
    assert n(case_recosted) == (0, 1, 1)
    assert n(case_multiplicity_2_to_1) == (0, 1, 0)
    assert n(case_multiplicity_1_to_3) == (0, 0, 2)
    assert n(case_exactly_max_ops) == (0, 200, 312)
    assert n(case_max_ops_plus_one) is None
    assert n(case_pure_weight) == (3, 0, 0)
    assert n(case_empty_delta_same_list) == (0, 0, 0)
    assert n(case_empty_delta_permuted_list) == (0, 0, 0)
    assert n(case_disjoint_lists) == (0, 200, 200)
    assert n(case_storm_cell_size) == (0, 5, 5)


@pytest.mark.parametrize("max_ops", [1, 3, 4, 5])
def test_max_ops_argument_refuses_at_the_same_count(max_ops):
    base, new = case_pair_removed()
    new = clone(new, extra=[[1, 2, 3, -1], [2, 1, 3, -1]])  # 4 operations
    want = unique_axis0_diff(base, new, max_ops=max_ops)
    got = diff_topologies(base, new, max_ops=max_ops)
    assert (got is None) == (want is None) == (max_ops < 4)


@pytest.mark.parametrize("path,case", [
    ("weights", case_pure_weight),
    ("edges", case_pair_removed),
    ("edges", case_empty_delta_permuted_list),
    ("refused", case_max_ops_plus_one),
    ("refused", case_pure_weight_over_max_ops),
    ("refused", case_other_root_refused),
], ids=lambda v: v if isinstance(v, str) else v.__name__[5:])
def test_counter_bumps_its_path_once(path, case):
    def read():
        snap = telemetry.snapshot("holo_spf_delta_diff_total")
        return {p: snap.get(f"holo_spf_delta_diff_total{{path={p}}}", 0)
                for p in ("weights", "edges", "refused")}

    base, new = case()
    before = read()
    diff_topologies(base, new)
    after = read()
    moved = {p: after[p] - before[p] for p in after}
    assert moved == {p: int(p == path) for p in moved}


# -- the mutual-link filter (ISSUE 30): a packed-key membership test


def set_of_pairs_keep_mask(edge_src, edge_dst) -> np.ndarray:
    """``mutual_keep_mask`` as it stood before ISSUE 30, verbatim but for
    the name: a Python set of pairs and a comprehension over the edges."""
    src = np.asarray(edge_src)
    dst = np.asarray(edge_dst)
    fwd = set(zip(src.tolist(), dst.tolist()))
    return np.array([(d, s) in fwd for s, d in zip(src, dst)], dtype=bool)


@pytest.mark.parametrize("dtype", [np.int32, np.int64])
@pytest.mark.parametrize("n_edges", [0, 1, 50, 30_000])
def test_mutual_keep_mask_equals_the_set_of_pairs(n_edges, dtype):
    """Random edge lists with duplicates, self-loops and one-sided
    links; vertex ids up to the int32 limit, and below zero."""
    for seed in range(4):
        rng = np.random.default_rng([n_edges, seed])
        n_vertices = max(2, n_edges // 3)
        src = rng.integers(0, n_vertices, n_edges)
        dst = rng.integers(0, n_vertices, n_edges)
        back = rng.random(n_edges) < 0.5  # half the links have a reverse
        src = np.concatenate((src, dst[back]))
        dst = np.concatenate((dst, src[: n_edges][back]))
        loops = rng.integers(0, n_vertices, n_edges // 10)
        src = np.concatenate((src, loops, src[: n_edges // 5]))  # duplicates
        dst = np.concatenate((dst, loops, dst[: n_edges // 5]))
        order = rng.permutation(len(src))
        src, dst = src[order], dst[order]
        if seed == 2:  # the ends of the id range
            scale = (2**31 - 1) // n_vertices
            src, dst = src * scale, dst * scale
        if seed == 3:
            src, dst = src - n_vertices // 2, dst - n_vertices // 2
        src, dst = src.astype(dtype), dst.astype(dtype)
        before = src.copy(), dst.copy()
        got = mutual_keep_mask(src, dst)
        want = set_of_pairs_keep_mask(src, dst)
        assert got.dtype == want.dtype and got.shape == want.shape
        assert np.array_equal(got, want)
        assert np.array_equal(src, before[0])  # the inputs stay as given
        assert np.array_equal(dst, before[1])
        if n_edges >= 50:
            assert 0 < got.sum() < len(got)
        if n_edges <= 50:  # a list is as good as an array
            assert np.array_equal(
                mutual_keep_mask(src.tolist(), dst.tolist()), want
            )


@pytest.mark.parametrize("n_keys", [0, 1, 7, 5_000])
def test_lookup_sorted_is_dict_get_with_the_last_of_equal_keys(n_keys):
    rng = np.random.default_rng(n_keys)
    keys = np.sort(rng.integers(-50, max(4 * n_keys, 1), n_keys))  # equal keys
    wanted = rng.integers(-60, max(4 * n_keys, 1) + 10, 3 * n_keys + 5)
    index = {int(k): i for i, k in enumerate(keys)}
    at, there = lookup_sorted(keys, wanted)
    assert there.tolist() == [int(w) in index for w in wanted]
    assert at[there].tolist() == [
        index[int(w)] for w in wanted if int(w) in index
    ]
