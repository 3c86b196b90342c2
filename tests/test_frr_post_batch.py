"""The FRR post-convergence batch on the compact graph, alone.

``kernel.post_spf_block`` runs the SPF of every protected link as a
column of one batch over the compact forward rows graph (a low plane of
``ROWS_K_LO`` in-edges a vertex and an overflow plane for the heavy
ones).  Its ``dist``, ``parent`` and ``nexthops`` must equal, bit for
bit, ``jax.vmap(spf_one)`` on the ELL of the same edges with the same
slot masked — the engine every primary SPF runs, and the rule (the
first parent minimising ``(dist[u], u)``, hops along it, direct atoms
where the parent has none) the scalar oracle reproduces.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from holo_tpu.frr.inputs import marshal_frr, rows_graph_shape, rows_graphs
from holo_tpu.frr.kernel import RowsGraph, post_spf_block
from holo_tpu.ops.graph import INF, build_ell
from holo_tpu.ops.spf_engine import device_graph_from_ell, spf_one
from holo_tpu.spf.synth import hub_topology, random_ospf_topology

N_ATOMS = 64  # two bitmask words


def _rows(topo):
    h, k = rows_graph_shape(topo)
    fwd, _ = rows_graphs(topo, -(-max(h, 1) // 8) * 8, -(-max(k, 1) // 8) * 8)
    return RowsGraph(*fwd)


def _ell_masks(ell, topo, fin):
    """in_valid of the ELL with each link's protected slot cleared: the
    first slot of its far end with source root, the link's cost and
    atom (pad links clear nothing)."""
    valid = np.broadcast_to(ell.in_valid, (len(fin.link_far),) + ell.in_valid.shape)
    valid = valid.copy()
    for l in range(fin.n_links):
        far = int(fin.link_far[l])
        hit = np.flatnonzero(
            ell.in_valid[far]
            & (ell.in_src[far] == topo.root)
            & (ell.in_cost[far] == fin.link_cost[l])
            & (ell.in_direct_atom[far] == fin.link_atom[l])
        )
        valid[l, far, hit[0]] = False
    return valid


def _both(topo, max_iters=None):
    fin = marshal_frr(topo)
    ell = build_ell(topo, n_atoms=N_ATOMS)
    g = device_graph_from_ell(ell)
    valid = jnp.asarray(_ell_masks(ell, topo, fin))
    want = jax.jit(jax.vmap(
        lambda v: spf_one(g._replace(in_valid=v), topo.root, None, max_iters)
    ))(valid)
    got = jax.jit(post_spf_block, static_argnums=(7, 8))(
        _rows(topo), g.is_router, topo.root, fin.link_far, fin.link_cost,
        fin.link_atom, fin.link_valid, g.direct_nh_words.shape[2], max_iters,
    )
    return fin, want, got


def _assert_equal(want, got):
    for name in ("dist", "parent", "nexthops"):
        np.testing.assert_array_equal(
            np.asarray(getattr(want, name)), np.asarray(getattr(got, name)),
            err_msg=name,
        )


def _what_it_covers(topo, fin, want):
    """The shapes the hub family must reach: a protected edge in the
    overflow plane, a first parent and an equal-cost tie only there,
    destinations the failure cuts off, atoms of the second word."""
    rg = _rows(topo)
    hi_vtx, hi_src = np.asarray(rg.hi_vtx), np.asarray(rg.hi_src)
    heavy = {int(v): i for i, v in enumerate(hi_vtx) if v < topo.n_vertices}
    root_hi = [
        l for l in range(fin.n_links)
        if int(fin.link_far[l]) in heavy
        and topo.root in hi_src[heavy[int(fin.link_far[l])]]
        and topo.root not in np.asarray(rg.lo_src)[int(fin.link_far[l])]
    ]
    parent = np.asarray(want.parent)
    dist = np.asarray(want.dist)
    lo_src = np.asarray(rg.lo_src)
    from_hi = any(
        p < topo.n_vertices and p not in lo_src[v] and p in hi_src[i]
        for l in range(fin.n_links) for v, i in heavy.items()
        for p in [int(parent[l, v])]
    )
    cut = (dist[: fin.n_links] >= INF).any()
    word1 = (np.asarray(want.nexthops)[: fin.n_links, :, 1] != 0).any()
    return {
        "protected edge in the overflow plane": bool(root_hi),
        "first parent from the overflow plane": from_hi,
        "destination cut off": bool(cut),
        "atoms in the second word": bool(word1),
    }


@pytest.mark.parametrize("max_iters", [None, 2, 5])
@pytest.mark.parametrize("seed", range(4))
def test_post_batch_equals_vmapped_spf_one_on_hubs(seed, max_iters):
    topo = hub_topology(seed=seed)
    fin, want, got = _both(topo, max_iters)
    _assert_equal(want, got)
    assert int(got.unmatched) == 0
    if max_iters is None:
        covers = _what_it_covers(topo, fin, want)
        assert all(covers.values()), covers
        assert int(got.rounds) >= 2 + N_ATOMS // 32


def test_hub_family_has_equal_cost_ties_across_planes():
    """Some heavy vertex has DAG parents in both planes at the same least
    distance, and the lower id in the overflow plane: the two-stage
    first-parent rule is exercised across the planes, and the batch
    picks as ``spf_one`` does."""
    topo = hub_topology(seed=2)
    fin, want, got = _both(topo)
    _assert_equal(want, got)
    rg = _rows(topo)
    dist = np.asarray(want.dist)
    lo_src, lo_cost = np.asarray(rg.lo_src), np.asarray(rg.lo_cost)
    hi_src, hi_cost = np.asarray(rg.hi_src), np.asarray(rg.hi_cost)
    n = topo.n_vertices
    ties = 0
    for l in range(fin.n_links):
        d = np.append(dist[l], INF)
        for i, v in enumerate(np.asarray(rg.hi_vtx)):
            if v >= n or d[v] >= INF or v == topo.root:
                continue
            lo = [(d[u], u) for u, c in zip(lo_src[v], lo_cost[v])
                  if u != topo.root and d[u] < INF and d[u] + c == d[v]]
            hi = [(d[u], u) for u, c in zip(hi_src[i], hi_cost[i])
                  if u != topo.root and d[u] < INF and d[u] + c == d[v]]
            if lo and hi and min(lo)[0] == min(hi)[0] and min(hi) < min(lo):
                ties += 1
    assert ties


@pytest.mark.parametrize("seed", range(3))
def test_post_batch_equals_vmapped_spf_one_with_lans(seed):
    """Pseudo-nodes with zero-cost edges to their routers, few in-edges."""
    topo = random_ospf_topology(
        n_routers=20, n_networks=5, extra_p2p=12, max_cost=3, seed=seed
    )
    _, want, got = _both(topo)
    _assert_equal(want, got)


def test_post_batch_parallel_equal_slots():
    """Two links from the root alike in far end and cost but not atom,
    and a duplicate slot alike in all three: each link masks its own
    slot, and a duplicate masks one of two and leaves the other."""
    topo = hub_topology(seed=2)
    n = len(topo.edge_src)
    e = int(np.flatnonzero(topo.edge_src == topo.root)[0])
    for arr in ("edge_src", "edge_dst", "edge_cost", "edge_direct_atom",
                "edge_srlg"):
        a = getattr(topo, arr)
        setattr(topo, arr, np.append(a, a[e]))
    topo.touch()
    assert len(topo.edge_src) == n + 1
    fin, want, got = _both(topo)
    _assert_equal(want, got)
    # The doubled link's failure leaves its twin: nothing is cut off
    # that the twin reaches.
    l = int(np.flatnonzero(fin.link_edge == e)[0])
    far = int(topo.edge_dst[e])
    assert np.asarray(got.dist)[l, far] <= topo.edge_cost[e]


def test_post_batch_counts_a_link_with_no_slot():
    """A link whose atom matches no slot masks nothing and is counted."""
    topo = hub_topology(seed=3)
    fin = marshal_frr(topo)
    ell = build_ell(topo, n_atoms=N_ATOMS)
    g = device_graph_from_ell(ell)
    atom = fin.link_atom.copy()
    atom[0] = N_ATOMS - 1
    got = post_spf_block(
        _rows(topo), g.is_router, topo.root, fin.link_far, fin.link_cost,
        atom, fin.link_valid, 2,
    )
    base = spf_one(g, topo.root)
    assert int(got.unmatched) == 1
    np.testing.assert_array_equal(np.asarray(got.dist)[0], np.asarray(base.dist))
