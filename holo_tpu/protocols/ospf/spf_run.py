"""SPF marshaling + route derivation for OSPFv2.

Bridges the protocol LSDB to the tensor/scalar SPF backends:

- :func:`build_topology` lowers an area LSDB into the generic
  :class:`~holo_tpu.ops.graph.Topology` (vertex model of RFC 2328 §16.1,
  ordering contract of holo_tpu.ops.graph), assigning next-hop atoms for
  exactly the parent-hops==0 cases (reference calc_nexthops,
  holo-ospf/src/ospfv2/spf.rs:172-…).  :class:`LoweredLsdb` is the
  lowering an area keeps between SPF runs, so that a run lowers only
  the LSAs installed since the last one.
- :func:`derive_routes` turns backend results (distances + ECMP atom
  bitmasks) into per-prefix intra-area routes (reference
  route::update_rib_full, holo-ospf/src/route.rs:146-197).

What OSPFv2 and OSPFv3 share beyond that lives here too, as the
reference shares it over its ``Version`` trait: the RFC 8405 SPF-delay
FSM (:class:`SpfDelayFsm`), area address ranges
(:func:`aggregate_area_ranges`) and OSPFv3's own kept lowering
(:class:`LoweredLsdbV3`, the same splice machinery over v3 LSA types).
"""

from __future__ import annotations

import enum
import itertools
import operator
from dataclasses import dataclass, field
from ipaddress import IPv4Address, IPv4Network

import numpy as np

from holo_tpu import telemetry
from holo_tpu.ops.graph import (
    DELTA_MAX_OPS,
    INF,
    Topology,
    lookup_sorted,
    mutual_keep_mask,
)
from holo_tpu.protocols.ospf.lsdb import Lsdb
from holo_tpu.protocols.ospf.packet import (
    MAX_AGE,
    LsaRouter,
    LsaType,
    RouterFlags,
    RouterLinkType,
)
from holo_tpu.spf.backend import SpfResult
from holo_tpu.utils.ip import apply_mask


# ===== SPF delay FSM (RFC 8405; reference holo-ospf/src/spf.rs:270-484) ==


class SpfFsmState(enum.Enum):
    QUIET = "quiet"
    SHORT_WAIT = "short-wait"
    LONG_WAIT = "long-wait"


@dataclass
class SpfTimers:
    initial_delay: float = 0.05
    short_delay: float = 0.2
    long_delay: float = 5.0
    hold_down: float = 10.0
    time_to_learn: float = 0.5


class SpfDelayFsm:
    """The RFC 8405 SPF-delay FSM of an instance actor, v2 or v3
    (reference holo-ospf/src/spf.rs:295-484, one FSM over the
    ``Version`` trait): QUIET→SHORT_WAIT on the first IGP event
    (``initial_delay``); further events in SHORT_WAIT use
    ``short_delay`` until ``time_to_learn`` expires, then LONG_WAIT uses
    ``long_delay``; ``hold_down`` of quiet returns to QUIET.  The
    instance owns the two loop timers and hands them in."""

    spf_state = SpfFsmState.QUIET
    _learn_deadline: float | None = None

    def _spf_delay_event(
        self, cfg: SpfTimers, now: float, spf_timer, hold_timer
    ) -> None:
        """One IGP event: (re)arm the SPF timer as the state says."""
        hold_timer.start(cfg.hold_down)  # reset on every IGP event
        if self.spf_state == SpfFsmState.QUIET:
            self._learn_deadline = now + cfg.time_to_learn
            self.spf_state = SpfFsmState.SHORT_WAIT
            spf_timer.start(cfg.initial_delay)
        elif self.spf_state == SpfFsmState.SHORT_WAIT:
            if now >= (self._learn_deadline or 0):
                self.spf_state = SpfFsmState.LONG_WAIT
                spf_timer.start(cfg.long_delay)
            elif not spf_timer.armed:
                spf_timer.start(cfg.short_delay)
        elif self.spf_state == SpfFsmState.LONG_WAIT:
            if not spf_timer.armed:
                spf_timer.start(cfg.long_delay)

    def _spf_holddown_fired(self) -> None:
        self.spf_state = SpfFsmState.QUIET
        self._learn_deadline = None


# ===== Area address ranges (RFC 2328 §12.4.3 / Appendix C.2) ============


def _range_key(prefix) -> tuple[int, int]:
    """A range by its length and its network's leading bits: what a
    prefix under it is probed with."""
    return (
        prefix.prefixlen,
        int(prefix.network_address)
        >> (prefix.max_prefixlen - prefix.prefixlen),
    )


def aggregate_area_ranges(routes: dict, ranges, nh_areas_of) -> tuple:
    """One area's intra-area routes as its ABR advertises them into the
    other areas: components of an active advertised range aggregate
    into the range's prefix at the largest component distance (or the
    range's configured cost), ``advertise=false`` ranges black-hole
    their components, the most specific range wins, and a prefix under
    no range goes as it is.

    ``routes``: ``{prefix: route}`` with ``route.dist``.  ``ranges``:
    ``[{"prefix", "advertise", "cost"}]``.  ``nh_areas_of(route)``: the
    areas the route's next hops exit through.  Returns ``(eff,
    range_nh_areas, active)``: ``{prefix: distance}`` to advertise; per
    aggregate the areas its COMPONENTS exit through, which the
    caller's split horizon must cover too; and the prefixes of the
    ranges that are active (one component or more is reachable),
    advertised or not (RFC 2328 §16.2 (3) ignores a summary that
    equals one)."""
    if not ranges:
        return {p: r.dist for p, r in routes.items()}, {}, set()
    # The most specific range of a prefix is one dict probe per range
    # length, longest first, instead of a ``subnet_of`` per range.  Of
    # two ranges with one prefix the first stays, as ``max(matches)``
    # over the list kept it.
    index: dict = {}
    for r in ranges:
        index.setdefault(_range_key(r["prefix"]), r)
    lengths = sorted({k[0] for k in index}, reverse=True)
    eff: dict = {}
    # Per range key (hashing an ip_network costs more than the rest of
    # the loop): [largest advertised distance, exit areas].
    acc: dict = {}
    for prefix, route in routes.items():
        key = None
        plen, bits = prefix.prefixlen, prefix.max_prefixlen
        net = int(prefix.network_address)
        for length in lengths:
            if length <= plen and (length, net >> (bits - length)) in index:
                key = (length, net >> (bits - length))
                break
        if key is None:
            eff[prefix] = route.dist
            continue
        slot = acc.get(key)
        if slot is None:
            slot = acc[key] = [-1, set()]
        if index[key].get("advertise", True):
            if route.dist > slot[0]:
                slot[0] = route.dist
            slot[1].update(nh_areas_of(route))
    active = {index[key]["prefix"] for key in acc}
    range_nh_areas = {
        index[key]["prefix"]: slot[1]
        for key, slot in acc.items() if slot[0] >= 0
    }
    for r in ranges:
        slot = acc.get(_range_key(r["prefix"]))
        if slot is not None and slot[0] >= 0:
            eff[r["prefix"]] = (
                r["cost"] if r.get("cost") is not None else slot[0]
            )
    return eff, range_nh_areas, active


def srlg_bits(groups) -> int:
    """uint32 bitmask of configured SRLG group ids.

    Group ids fold modulo 32 onto the mask bits — membership testing
    stays conservative-correct under folding (a shared bit is treated
    as a shared risk, never the reverse), matching the FRR engines'
    ``srlg_disjoint`` exclusion semantics over ``Topology.edge_srlg``.
    """
    bits = 0
    for gid in groups or ():
        bits |= 1 << (int(gid) % 32)
    return bits


def apply_interface_srlg(
    topo: Topology, atom_ifnames, srlg_of_ifname: dict
) -> None:
    """Stamp ``Topology.edge_srlg`` from per-interface fast-reroute
    config (the ROADMAP carry-over: until now only tests/synth ever set
    the seam).

    ``atom_ifnames[a]`` is the outgoing interface of next-hop atom
    ``a`` (None for borrowed/vlink atoms); ``srlg_of_ifname`` maps
    interface name -> uint32 SRLG bitmask (:func:`srlg_bits`).  Every
    edge resolving through a configured interface — exactly the root
    out-edges the FRR engines treat as protected links and repair
    candidates — carries that interface's groups.  In-place: callers
    stamp after ``edge_direct_atom`` is final."""
    if not srlg_of_ifname:
        return
    srlg = np.zeros(topo.n_edges, np.uint32)
    for e in np.flatnonzero(topo.edge_direct_atom >= 0).tolist():
        a = int(topo.edge_direct_atom[e])
        if a >= len(atom_ifnames):
            continue
        ifn = atom_ifnames[a]
        if ifn is not None:
            srlg[e] = np.uint32(srlg_of_ifname.get(ifn, 0))
    topo.edge_srlg = srlg


def apply_partition_hint(topo: Topology, groups) -> None:
    """Stamp ``Topology.partition_hint`` from a per-vertex grouping
    (ISSUE 15): the protocol seam the hierarchical partitioned-SPF path
    reads (``ops/graph.partition_topology`` honors the hint verbatim).

    ``groups`` is a sequence of hashable, orderable group labels — one
    per vertex in vertex order (IS-IS area addresses, OSPF sub-area
    groupings, synth multi-area ids) — or None entries for ungrouped
    vertices.  The stamp happens only when EVERY vertex is grouped and
    at least two distinct groups exist; otherwise the topology stays
    flat and the deterministic BFS/greedy cut decides at partition
    time.  Distinct labels map onto dense partition ids in ascending
    label order, so the hint is reproducible across marshals (the
    DeltaPath chain contract).  Like ``edge_srlg`` the hint never
    enters the DeviceGraph planes — residents cannot serve it stale."""
    if groups is None:
        return
    labels = list(groups)
    if len(labels) != topo.n_vertices or any(
        g is None for g in labels
    ):
        return
    uniq = sorted(set(labels))
    if len(uniq) < 2:
        return
    dense = {g: i for i, g in enumerate(uniq)}
    topo.partition_hint = np.array(
        [dense[g] for g in labels], np.int32
    )


@dataclass(frozen=True)
class NexthopAtom:
    """Resolved direct next hop: outgoing interface + neighbor address.

    addr is None for p2p links where the neighbor address is learned from
    the adjacency (filled by the instance) — kept explicit for RIB parity.
    ``expand`` (virtual links, §16.1): the atom stands for the transit
    area's next-hop set toward the vlink neighbor and expands to it when
    atoms are converted to route next hops.
    """

    ifname: str | None
    addr: IPv4Address | None
    expand: frozenset = None


@dataclass
class _ReachedFlags:
    """What :meth:`DerivePlan.reached_flags` made last, and from what:
    one per kept lowering, shared by the plans of its runs."""

    routers: list | None = None  # the vertex model's list, by identity
    flags: np.ndarray | None = None
    every: dict = field(default_factory=dict)  # all routers, index order
    reached: np.ndarray | None = None
    out: dict = field(default_factory=dict)


@dataclass
class DerivePlan:
    """What one SPF run's ``derive`` stage reads, by vertex index, as
    :meth:`LoweredLsdb.build_topology` left it: the prefixes the run's
    live LSAs offer and the flags of its routers.  It is the run's: made
    from the LSDB and at the ``now`` of that call."""

    now: float
    # One row per offered prefix, sorted by vertex, a vertex's offers in
    # its LSA's link order: the order a walk over the vertices meets them.
    offer_vertex: np.ndarray  # int64[P]
    offer_metric: np.ndarray  # int64[P]
    prefixes: list[IPv4Network]  # [P]
    # The router index's keys in its order, the vertex of each and the
    # flags of that vertex's Router-LSA.
    routers: list[IPv4Address]
    router_vertex: np.ndarray  # int64[R]
    router_flags: np.ndarray  # int64[R]
    _kept: _ReachedFlags = field(default_factory=_ReachedFlags, repr=False)

    def reached_flags(self, dist: np.ndarray) -> dict:
        """:func:`reachable_router_flags` from the plan.  Callers only
        read the dict: where the routers, their flags and the reached
        set are the last call's, it is the last call's object."""
        kept = self._kept
        if kept.routers is not self.routers or not np.array_equal(
            kept.flags, self.router_flags
        ):
            table = {
                f: RouterFlags(f)
                for f in np.unique(self.router_flags).tolist()
            }
            kept.every = dict(zip(
                self.routers, map(table.get, self.router_flags.tolist())
            ))
            kept.routers, kept.flags = self.routers, self.router_flags
            kept.reached = None
        reached = dist[self.router_vertex] < INF
        if kept.reached is None or not np.array_equal(kept.reached, reached):
            # A copy keeps the keys' hashes and their order; what is
            # hashed again is the few routers the run did not reach.
            out = dict(kept.every)
            routers = self.routers
            for i in np.flatnonzero(~reached).tolist():
                del out[routers[i]]
            kept.reached, kept.out = reached, out
        return kept.out


@dataclass
class SpfTopology:
    topo: Topology
    atoms: list[NexthopAtom]
    # vertex index maps
    router_index: dict[IPv4Address, int]
    network_index: dict[IPv4Address, int]
    # Set by a lowering's build_topology; None on a hand-made topology,
    # which derive_routes and reachable_router_flags serve from the LSDB.
    plan: DerivePlan | None = None


_TOPOLOGY_LSAS = telemetry.counter(
    "holo_ospf_topology_lsas_total",
    "build_topology's LSDB entries by how their lowered segment was had: "
    "lowered from the LSA (an entry the kept lowering had not seen at "
    "that place), or reused from the previous call",
    ("path",),
)

_TOPOLOGY_ROWS = telemetry.counter(
    "holo_ospf_topology_rows_total",
    "LoweredLsdbV3.build_topology's link rows (one per link of every "
    "emitted segment) by how an assembly had them: kept (destination "
    "vertex and mutual flag are the last call's), or resolved anew (the "
    "rows of a segment that was replaced, the rows that end at its "
    "vertex, and every row of a whole assembly)",
    ("path",),
)

# What a lowered entry is, and what a lowered link's neighbour id names:
# a router id (p2p, virtual link, a network-LSA's attached router) or a
# DR interface address (a router-LSA's transit link).
_OTHER, _ROUTER, _NETWORK = 0, 1, 2
_P2P, _VLINK, _TRANSIT, _ATTACHED = 0, 1, 2, 3


def _offered(addr: IPv4Address, mask: IPv4Address):
    """The prefix an LSA offers, made once, when the LSA is lowered.  A
    mask that leaves host bits set (nothing checks one off the wire)
    names no network: the pair is kept, and ``derive_routes`` raises
    over it if a run reaches its vertex, as it did before lowerings."""
    try:
        return apply_mask(addr, mask)
    except ValueError:
        return addr, mask


def _spliced(old: np.ndarray, runs, parts) -> np.ndarray:
    """``old`` with rows ``[lo, hi)`` replaced by ``part``, for each
    ``(lo, hi)`` of ``runs`` (ascending, disjoint) and its ``part``."""
    out, at = [], 0
    for (lo, hi), part in zip(runs, parts):
        out += (old[at:lo], part)
        at = hi
    out.append(old[at:])
    return np.concatenate(out)


def _aligned_runs(kept: list, cur: list) -> tuple[list, list]:
    """``cur`` (an LSDB's entries now) against ``kept`` (as they were),
    by identity: the runs ``(lo, hi)`` of places in ``kept`` whose
    entries are gone, ascending and disjoint, and per run the entries
    of ``cur`` that stand there instead, none of them an object
    ``kept`` holds.  ``lsdb.entries`` is a dict, so the two are one
    sequence with replacements in place, removals (``(p, p + 1)`` and
    nothing) and appends (``(len, len)`` and the new entries)."""
    n = min(len(kept), len(cur))
    stale = [
        i for i, same in enumerate(map(operator.is_, kept, cur)) if not same
    ]
    if not stale and len(kept) == len(cur):
        return [], []
    gone = [kept[i] for i in stale] + kept[n:]
    come = [cur[i] for i in stale] + cur[n:]
    if set(map(id, gone)).isdisjoint(map(id, come)):
        # Every entry that stayed is at its place: replaced in place,
        # and appended to or cut short at the end.
        old_at = new_at = np.array(stale + [n], np.int64)
        old_end, new_end = old_at + 1, new_at + 1
        old_end[-1], new_end[-1] = len(kept), len(cur)
    else:
        # Entries that stayed have shifted (a removal in front of
        # them): the stretches between one that stayed and the next,
        # on either side, from the first difference on.
        lo = stale[0]
        old, new = kept[lo:], cur[lo:]
        old_ids, new_ids = set(map(id, old)), set(map(id, new))
        stays_old = np.fromiter(
            map(new_ids.__contains__, map(id, old)), bool, len(old)
        )
        stays_new = np.fromiter(
            map(old_ids.__contains__, map(id, new)), bool, len(new)
        )
        if not all(map(
            operator.is_,
            itertools.compress(old, stays_old),
            itertools.compress(new, stays_new),
        )):
            # Not a dict's doing (the order of what stayed changed):
            # all from the first difference on.
            return [(lo, len(kept))], [new]
        at_old = lo + np.flatnonzero(stays_old)
        at_new = lo + np.flatnonzero(stays_new)
        old_at = np.concatenate(([lo], at_old + 1))
        new_at = np.concatenate(([lo], at_new + 1))
        old_end = np.concatenate((at_old, [len(kept)]))
        new_end = np.concatenate((at_new, [len(cur)]))
    runs, fresh = [], []
    some = np.flatnonzero((old_end > old_at) | (new_end > new_at))
    for a, b, c, d in zip(
        old_at[some].tolist(), old_end[some].tolist(),
        new_at[some].tolist(), new_end[some].tolist(),
    ):
        if runs and runs[-1][1] == a:
            runs[-1] = (runs[-1][0], b)
            fresh[-1] += cur[c:d]
        else:
            runs.append((a, b))
            fresh.append(cur[c:d])
    return runs, fresh


class _VertexIds:
    """The live LSAs of one type by vertex id, as a dict keyed by id
    holds them after a walk in LSDB order.  All index ``ids``."""

    def __init__(self, ids: np.ndarray) -> None:
        self.ids = ids  # LSDB order
        # Vertex order: ascending id, equal ids in LSDB order.
        self.order = np.argsort(ids, kind="stable")
        by_id = ids[self.order]
        first = np.ones(len(ids), bool)
        first[1:] = by_id[1:] != by_id[:-1]
        # Of equal ids the dict keeps the LAST LSA's body, at the place
        # the id was FIRST inserted: the segments to emit, in order ...
        lasts = self.order[np.roll(first, -1)]
        # (OSPFv3 numbers its vertices by DISTINCT id: several
        # Router-LSAs of one router are one vertex.  ``uniq[g]`` is
        # vertex g's id, ``last[g]`` the LSA whose body it has, and
        # ``emit_rank`` the vertices in the order their segments go.)
        self.uniq, self.last = by_id[first], lasts
        self.emit_rank = np.argsort(self.order[first], kind="stable")
        self.emit = lasts[self.emit_rank]
        # ... and the body behind every vertex.
        self.body = lasts[np.cumsum(first) - 1]


@dataclass
class _VertexModel:
    """Everything that follows from the live vertex ids alone: kept
    while they stay what they were."""

    rtr: _VertexIds
    net: _VertexIds
    routers: list[IPv4Address]
    networks: list[IPv4Address]  # keyed by DR interface address (lsid)
    router_index: dict[IPv4Address, int]
    network_index: dict[IPv4Address, int]
    # Both index dicts as one sorted array: key 2 id + (1 for a router),
    # and the vertex behind each key.
    keys: np.ndarray
    vertex_at: np.ndarray
    emit_vertex: np.ndarray  # source vertex per emitted segment
    # ``router_index`` as a list of its keys and an array of its values
    index_routers: list[IPv4Address]
    index_vertex: np.ndarray


class LoweredLsdb:
    """One area's LSDB lowered to flat arrays, kept between SPF runs.

    Per LSDB entry, in the LSDB's iteration order: what it is, its
    vertex id as an integer (a router-LSA's advertising router, a
    network-LSA's link-state id), the two terms of its age, a
    router-LSA's flags, and its segment of link rows ``(kind, neighbour
    id, metric, link data)``, one per link that can become an edge.
    Segments hold ids, not vertex indices, so they outlive a change of
    the vertex set.  Beside the link rows, per entry, its segment of
    *offers*: the prefixes a route may be derived from (a router-LSA's
    stub links, a network-LSA's own network), each with its metric.
    The prefix objects are made when the LSA is lowered, and kept.

    ``Lsdb.install`` builds a new ``LsaEntry`` per install and nothing
    edits one in place, so what changed since the last call is found by
    identity: :meth:`build_topology` walks ``lsdb.entries`` beside
    ``entries`` and lowers only the entries that are new objects.
    ``lsdb.entries`` is a dict (a replaced key keeps its place, a
    removed one closes its gap, a new one goes to the end), so the two
    are aligned whatever the length did (:func:`_aligned_runs`): a
    removal in the middle lowers nothing, an append what was appended.
    No journal and no hook in the LSDB: any way of writing
    ``lsdb.entries`` is seen.  What is kept is trusted by identity
    alone: a segment while its entry is the kept object; what follows
    from the live vertex ids while they are last call's.  Every array a
    call hands out is the result's own and never written again (the
    instance holds the previous :class:`SpfTopology` and diffs the next
    against it); the kept columns are spliced into new arrays, not
    edited.

    ``router_bodies`` is the live router-LSA bodies in vertex order as
    of the last call (``router_bodies[i]`` is vertex ``n_networks + i``).
    The :class:`SpfTopology` a call returns carries the run's
    :class:`DerivePlan`: the offers and the flags by vertex index.
    """

    #: the per-entry arrays, in the order ``_lower`` returns them
    _COLUMNS = (
        "_kind", "_vid", "_age", "_installed_at", "_n_links", "_n_offers",
        "_flags",
    )

    def __init__(self) -> None:
        self.entries: list = []
        self._bodies: list = []  # per entry; None but for the two types
        self._kind = np.zeros(0, np.int8)
        self._vid = np.zeros(0, np.int64)
        self._age = np.zeros(0, np.float64)
        self._installed_at = np.zeros(0, np.float64)
        self._n_links = np.zeros(0, np.int64)
        self._n_offers = np.zeros(0, np.int64)
        self._flags = np.zeros(0, np.int64)
        self._links = np.zeros((0, 4), np.int64)
        self._link_off = np.zeros(1, np.int64)
        self._offer_metric = np.zeros(0, np.int64)
        self._offer_prefix: list[IPv4Network] = []
        self._offer_off = np.zeros(1, np.int64)
        self._model: _VertexModel | None = None
        self._reached = _ReachedFlags()
        self.router_bodies: list[LsaRouter] = []

    @staticmethod
    def _lower(entries) -> tuple:
        """Lower a run of LSDB entries: the per-entry ``_COLUMNS``, the
        link rows of all of them, their bodies, and the offers of all
        of them ``(metrics, prefixes)``."""
        kind, vid, age, installed_at, n_links, links, bodies = (
            [], [], [], [], [], [], []
        )
        n_offers, flags, offer_metric, offer_prefix = [], [], [], []
        for e in entries:
            lsa = e.lsa
            k, v, body, fl = _OTHER, 0, None, 0
            n0, p0 = len(links), len(offer_prefix)
            if lsa.type == LsaType.ROUTER:
                k, v, body = _ROUTER, int(lsa.adv_rtr), lsa.body
                fl = int(body.flags)
                for link in body.links:
                    lt = link.link_type
                    if lt == RouterLinkType.STUB_NETWORK:
                        offer_prefix.append(_offered(link.id, link.data))
                        offer_metric.append(link.metric)
                        continue
                    if lt == RouterLinkType.POINT_TO_POINT:
                        lk = _P2P
                    elif lt == RouterLinkType.TRANSIT_NETWORK:
                        lk = _TRANSIT
                    elif lt == RouterLinkType.VIRTUAL_LINK:
                        # Virtual links are router-router edges whose
                        # cost is the transit-area distance (§15); for
                        # SPF they behave as p2p.
                        lk = _VLINK
                    else:
                        continue
                    links.append(
                        (lk, int(link.id), link.metric, int(link.data))
                    )
            elif lsa.type == LsaType.NETWORK:
                k, v, body = _NETWORK, int(lsa.lsid), lsa.body
                offer_prefix.append(_offered(lsa.lsid, body.mask))
                offer_metric.append(0)
                for rid in body.attached:
                    links.append((_ATTACHED, int(rid), 0, 0))
            kind.append(k)
            vid.append(v)
            age.append(lsa.age)
            installed_at.append(e.installed_at)
            n_links.append(len(links) - n0)
            n_offers.append(len(offer_prefix) - p0)
            flags.append(fl)
            bodies.append(body)
        return (
            (
                np.array(kind, np.int8),
                np.array(vid, np.int64),
                np.array(age, np.float64),
                np.array(installed_at, np.float64),
                np.array(n_links, np.int64),
                np.array(n_offers, np.int64),
                np.array(flags, np.int64),
            ),
            np.array(links, np.int64).reshape(-1, 4),
            bodies,
            (np.array(offer_metric, np.int64), offer_prefix),
        )

    def _refresh(self, lsdb: Lsdb) -> list:
        """Bring the lowering up to ``lsdb``: lower the entries that are
        new objects, splice their segments in.  Returns the runs
        ``(lo, hi, n)``: the kept places ``[lo, hi)`` gave way to ``n``
        entries lowered anew (a removal has ``n`` 0, an append ``lo ==
        hi``, the kept length)."""
        cur = list(lsdb.entries.values())
        runs, fresh = _aligned_runs(self.entries, cur)
        lowered = sum(len(f) for f in fresh)
        _TOPOLOGY_LSAS.labels(path="lowered").inc(lowered)
        _TOPOLOGY_LSAS.labels(path="reused").inc(len(cur) - lowered)
        if not runs:
            return []
        parts = [self._lower(f) for f in fresh]
        off, offer_off = self._link_off, self._offer_off
        link_runs = [(off[lo], off[hi]) for lo, hi in runs]
        link_parts = [links for _cols, links, _bodies, _offers in parts]
        if any(hi > lo for lo, hi in link_runs) or any(map(len, link_parts)):
            # (most entries that come and go are summaries: no link)
            self._links = _spliced(self._links, link_runs, link_parts)
        offer_runs = [(offer_off[lo], offer_off[hi]) for lo, hi in runs]
        self._offer_metric = _spliced(
            self._offer_metric, offer_runs, [p[3][0] for p in parts]
        )
        for col, name in enumerate(self._COLUMNS):
            setattr(self, name, _spliced(
                getattr(self, name), runs, [p[0][col] for p in parts]
            ))
        for (lo, hi), (olo, ohi), (_cols, _links, bodies, offers) in zip(
            reversed(runs), reversed(offer_runs), reversed(parts)
        ):
            self._bodies[lo:hi] = bodies
            self._offer_prefix[olo:ohi] = offers[1]
        self.entries = cur
        self._link_off = np.concatenate(([0], np.cumsum(self._n_links)))
        self._offer_off = np.concatenate(([0], np.cumsum(self._n_offers)))
        return [(lo, hi, len(f)) for (lo, hi), f in zip(runs, fresh)]

    def _vertex_model(self, r_pos, n_pos) -> _VertexModel:
        """The vertex model of the live router and network LSAs at
        entries ``r_pos`` / ``n_pos``: last call's object, index dicts
        and all, when the ids in LSDB order are last call's."""
        r_ids, n_ids = self._vid[r_pos], self._vid[n_pos]
        m = self._model
        if (
            m is not None
            and np.array_equal(m.rtr.ids, r_ids)
            and np.array_equal(m.net.ids, n_ids)
        ):
            return m
        rtr, net = _VertexIds(r_ids), _VertexIds(n_ids)
        # Vertex ordering contract: Network < Router (ospfv2/spf.rs:42-45).
        networks = [
            self.entries[i].lsa.lsid for i in n_pos[net.order].tolist()
        ]
        routers = [
            self.entries[i].lsa.adv_rtr for i in r_pos[rtr.order].tolist()
        ]
        keys = np.concatenate(
            (2 * n_ids[net.order], 2 * r_ids[rtr.order] + 1)
        )
        vertex_at = np.argsort(keys, kind="stable")
        keys = keys[vertex_at]
        emit_keys = np.concatenate(
            (2 * r_ids[rtr.emit] + 1, 2 * n_ids[net.emit])
        )
        router_index = {r: len(networks) + i for i, r in enumerate(routers)}
        m = self._model = _VertexModel(
            rtr, net, routers, networks,
            router_index=router_index,
            network_index={a: i for i, a in enumerate(networks)},
            keys=keys, vertex_at=vertex_at,
            emit_vertex=vertex_at[lookup_sorted(keys, emit_keys)[0]],
            index_routers=list(router_index),
            index_vertex=np.fromiter(
                router_index.values(), np.int64, len(router_index)
            ),
        )
        return m

    def _derive_plan(self, m: _VertexModel, r_pos, n_pos, now) -> DerivePlan:
        """The run's :class:`DerivePlan`: the offers of the entry behind
        each vertex, networks before routers."""
        r_body = r_pos[m.rtr.body]
        behind = np.concatenate((n_pos[m.net.body], r_body))
        count = self._n_offers[behind]
        end = np.cumsum(count)
        rows = np.repeat(self._offer_off[behind] - (end - count), count)
        rows += np.arange(len(rows))
        prefix = self._offer_prefix
        return DerivePlan(
            now,
            offer_vertex=np.repeat(np.arange(len(behind)), count),
            offer_metric=self._offer_metric[rows],
            prefixes=[prefix[i] for i in rows.tolist()],
            routers=m.index_routers,
            router_vertex=m.index_vertex,
            router_flags=self._flags[r_body[m.index_vertex - len(m.networks)]],
            _kept=self._reached,
        )

    def build_topology(
        self,
        lsdb: Lsdb,
        router_id: IPv4Address,
        now: float,
        iface_by_addr: dict[IPv4Address, str],
        iface_by_nbr: dict[IPv4Address, tuple[str, IPv4Address]],
        p2p_nbr_addr: dict[tuple, IPv4Address] | None = None,
        iface_by_ifindex: dict[int, str] | None = None,
        vlink_nexthops: dict | None = None,
        iface_srlg: dict[str, int] | None = None,
        partition_of: dict | None = None,
    ) -> SpfTopology | None:
        """:func:`build_topology` through this kept lowering."""
        self._refresh(lsdb)
        # MaxAge LSAs are excluded (RFC 2328 §16.1 note): the expression
        # LsaEntry.current_age computes, element for element.
        live = ~(self._age + (now - self._installed_at) >= MAX_AGE)
        r_pos = np.flatnonzero(live & (self._kind == _ROUTER))
        n_pos = np.flatnonzero(live & (self._kind == _NETWORK))
        m = self._vertex_model(r_pos, n_pos)
        routers, networks = m.routers, m.networks
        nn = len(networks)
        n = nn + len(routers)
        bodies = self._bodies
        self.router_bodies = [bodies[i] for i in r_pos[m.rtr.body].tolist()]
        root = m.router_index.get(router_id)
        if root is None:
            return None  # no self LSA yet (reference: SpfRootNotFound)
        plan = self._derive_plan(m, r_pos, n_pos, now)
        is_router = np.zeros(n, bool)
        is_router[nn:] = True

        # Every link row of every emitted segment: router-LSAs before
        # network-LSAs, each in LSDB order, links in LSA order.
        seg = np.concatenate((r_pos[m.rtr.emit], n_pos[m.net.emit]))
        count = self._n_links[seg]
        end = np.cumsum(count)
        rows = np.repeat(self._link_off[seg] - (end - count), count)
        rows += np.arange(len(rows))
        kind, nbr, metric, data = self._links[rows].T
        src = np.repeat(m.emit_vertex, count)
        # A link becomes an edge when its far end is a live vertex ...
        at, there = lookup_sorted(m.keys, 2 * nbr + (kind != _TRANSIT))
        dst = m.vertex_at[at]
        edge = np.flatnonzero(there)
        # ... and the far end links back (bidirectionality check,
        # spf.rs:653-664).
        edge = edge[mutual_keep_mask(src[edge], dst[edge])]
        kind, nbr, data = kind[edge], nbr[edge], data[edge]
        topo = Topology(
            n_vertices=n,
            is_router=is_router,
            edge_src=src[edge].astype(np.int32),
            edge_dst=dst[edge].astype(np.int32),
            edge_cost=metric[edge].astype(np.int32),
            root=root,
        )

        # Next-hop atoms: edges out of the root, and edges out of root-adjacent
        # transit networks (the hops==0 direct-calculation cases).
        atoms: list[NexthopAtom] = []
        atom_ids = np.full(topo.n_edges, -1, np.int32)
        root_nets: list[int] = []
        # Map vertex index -> transit our-iface (for root->net edges).
        net_if: dict[int, str] = {}
        for link in self.router_bodies[root - nn].links:
            if link.link_type == RouterLinkType.TRANSIT_NETWORK:
                vi = m.network_index.get(link.id)
                if vi is not None:
                    ifname = iface_by_addr.get(link.data)
                    if ifname is not None:
                        net_if[vi] = ifname
        for e in np.flatnonzero(topo.edge_src == root).tolist():
            v = int(topo.edge_dst[e])
            if kind[e] == _VLINK:
                # Virtual link: next hops borrowed from the transit area's
                # path to the vlink neighbor (§16.1).
                expand = (vlink_nexthops or {}).get(IPv4Address(int(nbr[e])))
                if expand:
                    atom_ids[e] = len(atoms)
                    atoms.append(NexthopAtom(None, None, expand))
                continue
            # Per-edge link_data (parallel p2p links each resolve to
            # their own interface).
            link_data = IPv4Address(int(data[e]))
            ifname = iface_by_addr.get(link_data)
            if is_router[v]:
                # p2p neighbor: the link's own interface (parallel links
                # each get their own atom), neighbor addr per interface.
                # Unnumbered links carry the MIB ifIndex in link_data
                # (RFC 2328 A.4.2) instead of an address.
                rid = routers[v - nn]
                if (
                    ifname is None
                    and iface_by_ifindex is not None
                    and int(link_data) < 0x1000000  # 0.x.y.z: never an addr
                ):
                    ifname = iface_by_ifindex.get(int(link_data))
                addr = None
                if ifname is not None and p2p_nbr_addr is not None:
                    addr = p2p_nbr_addr.get((ifname, rid))
                if ifname is not None and addr is not None:
                    atom_ids[e] = len(atoms)
                    atoms.append(NexthopAtom(ifname, addr))
                else:
                    hop = iface_by_nbr.get(rid)
                    if hop is not None:
                        atom_ids[e] = len(atoms)
                        atoms.append(NexthopAtom(hop[0], hop[1]))
            else:
                root_nets.append(v)
                # Directly-attached transit network: next hop is the
                # outgoing interface itself (no gateway address).
                if ifname is not None:
                    atom_ids[e] = len(atoms)
                    atoms.append(NexthopAtom(ifname, None))
        # Edges out of a network vertex end at routers only.
        for e in np.flatnonzero(
            np.isin(topo.edge_src, root_nets) & (topo.edge_dst != root)
        ).tolist():
            u = int(topo.edge_src[e])
            # Destination router's address on that network = the link.data
            # of ITS transit link pointing at this network's DR address.
            dr_addr = networks[u]
            ifname = net_if.get(u)
            if ifname is None:
                continue
            for link in self.router_bodies[int(topo.edge_dst[e]) - nn].links:
                if (
                    link.link_type == RouterLinkType.TRANSIT_NETWORK
                    and link.id == dr_addr
                ):
                    atom_ids[e] = len(atoms)
                    atoms.append(NexthopAtom(ifname, link.data))
                    break

        topo.edge_direct_atom = atom_ids
        if iface_srlg:
            # Interface fast-reroute SRLG config -> the edge_srlg seam the
            # FRR policy masks consume (srlg_disjoint).
            apply_interface_srlg(
                topo, [a.ifname for a in atoms], iface_srlg
            )
        if partition_of:
            # Hierarchical partition hint (ISSUE 15): per-router group
            # labels (config/topology-design groupings the operator knows —
            # PoPs, rings, sub-area clusters); a transit network rides the
            # lowest-labeled attached router so zero-cost net->rtr edges
            # stay intra-partition wherever the grouping allows.
            groups: list = []
            for i in n_pos[m.net.body].tolist():
                att = [
                    partition_of[r]
                    for r in bodies[i].attached
                    if r in partition_of
                ]
                groups.append(min(att) if att else None)
            for rid in routers:
                groups.append(partition_of.get(rid))
            apply_partition_hint(topo, groups)
        topo.touch()
        return SpfTopology(
            topo, atoms, m.router_index, m.network_index, plan
        )


# ===== OSPFv3: the same kept lowering over RFC 5340's LSA types ========

# Entry kinds beyond the base's (an Intra-Area-Prefix LSA takes no part
# in the graph, but its body is what routes are derived from).
_PREFIX = 3
#: a network-LSA's attached-router row (router links carry their
#: RouterLinkType, all positive)
_V3_ATTACHED = -1


@dataclass
class SpfTopologyV3(SpfTopology):
    """What one OSPFv3 area marshals to.  ``router_index`` is the whole
    vertex index (``("R", router id)`` and ``("N", DR router id, DR
    interface id)`` keys; ``network_index`` stays empty), so
    :func:`link_spf_delta` guards a v3 area with the comparisons it
    makes for a v2 one."""

    keys: list = field(default_factory=list)  # vertex -> key
    # live Intra-Area-Prefix LSAs, LSDB order: (adv_rtr, body)
    prefix_lsas: list = field(default_factory=list)

    @property
    def index(self) -> dict:
        return self.router_index


@dataclass
class _VertexModelV3:
    rtr: _VertexIds  # live Router-LSAs; vertex nn + g for distinct id g
    net: _VertexIds  # live Network-LSAs; vertex g
    keys: list
    index: dict
    seg_pos: np.ndarray  # emitted segments, as places in r_pos ++ n_pos
    seg_vertex: np.ndarray  # and the vertex each leaves from


@dataclass
class _KeptRows:
    """One assembly's link rows: a row per link of every emitted
    segment, in the order the edges go (Router-LSAs before Network-LSAs,
    each in LSDB order, links in LSA order).  Never written once made."""

    model: _VertexModelV3  # the vertex model ``dst`` was resolved under
    emitted: list  # per segment, the LSDB entry it was lowered from
    off: np.ndarray  # int64[segments + 1]: a segment's rows
    src: np.ndarray  # int32: the vertex a row leaves from
    dst: np.ndarray  # int32: the vertex it ends at, -1 where at none
    cost: np.ndarray  # int32
    mutual: np.ndarray  # bool: ends at a vertex, and a row leads back


class LoweredLsdbV3(LoweredLsdb):
    """:class:`LoweredLsdb` for an OSPFv3 area (RFC 5340 §4.8.1: the
    vertex model of RFC 2328 §16.1 keyed by router id and by the DR's
    (router id, interface id)).  Router-LSAs of one router are one
    vertex, with the links of the last one in LSDB order, as a dict
    keyed by advertising router holds them; a link row is ``(link type
    or -1, neighbour router id, metric, neighbour interface id)``.

    Beside the lowered entries it keeps the last assembly's link rows
    (:class:`_KeptRows`: per row the vertex it leaves from, the vertex
    it ends at and whether a row leads back), trusted while the vertex
    model is last call's OBJECT, so that a flap resolves the rows of
    the LSAs it replaced and not the area's."""

    def __init__(self) -> None:
        super().__init__()
        # (router id << 32 | interface id) needs all 64 bits
        self._vid = np.zeros(0, np.uint64)
        self._model3: _VertexModelV3 | None = None
        self._rows: _KeptRows | None = None
        # The last call's result, the other inputs it was made from and
        # which of the entries that matter were live: handed out again
        # while all of them stay what they were.
        self._kept: tuple | None = None

    @staticmethod
    def _lower(entries) -> tuple:
        from holo_tpu.protocols.ospf import packet_v3 as P

        kind, vid, age, installed_at, n_links, links, bodies = (
            [], [], [], [], [], [], []
        )
        for e in entries:
            lsa = e.lsa
            k, v, body, n0 = _OTHER, 0, None, len(links)
            if lsa.type == P.LsaType.ROUTER:
                k, v, body = _ROUTER, int(lsa.adv_rtr), lsa.body
                for link in body.links:
                    links.append((
                        int(link.link_type), int(link.nbr_router_id),
                        link.metric, link.nbr_iface_id,
                    ))
            elif lsa.type == P.LsaType.NETWORK:
                k, body = _NETWORK, lsa.body
                v = (int(lsa.adv_rtr) << 32) | int(lsa.lsid)
                for rid in body.attached:
                    links.append((_V3_ATTACHED, int(rid), 0, 0))
            elif lsa.type == P.LsaType.INTRA_AREA_PREFIX:
                # as ``prefix_lsas`` lists it: made once, here
                k, body = _PREFIX, (lsa.adv_rtr, lsa.body)
            kind.append(k)
            vid.append(v)
            age.append(lsa.age)
            installed_at.append(e.installed_at)
            n_links.append(len(links) - n0)
            bodies.append(body)
        # No offers and no flags: v3's routes come from the area's
        # Intra-Area-Prefix LSAs (``_derive_intra``).
        none = np.zeros(len(kind), np.int64)
        return (
            (
                np.array(kind, np.int8),
                np.array(vid, np.uint64),
                np.array(age, np.float64),
                np.array(installed_at, np.float64),
                np.array(n_links, np.int64),
                none,
                none,
            ),
            np.array(links, np.int64).reshape(-1, 4),
            bodies,
            (np.zeros(0, np.int64), []),
        )

    def _moved(self, was: np.ndarray, runs: list) -> bool:
        """Whether the refresh that lowered ``runs`` replaced, added or
        dropped an entry that matters to the result (a Router-,
        Network- or Intra-Area-Prefix LSA); ``was``: the kinds as they
        were."""
        shift = 0  # of the places now against the places then
        for lo, hi, n in runs:
            if was[lo:hi].any() or self._kind[lo + shift:lo + shift + n].any():
                return True
            shift += n - (hi - lo)
        return False

    def _vertex_model3(self, r_pos, n_pos) -> _VertexModelV3:
        r_ids, n_ids = self._vid[r_pos], self._vid[n_pos]
        m = self._model3
        if (
            m is not None
            and np.array_equal(m.rtr.ids, r_ids)
            and np.array_equal(m.net.ids, n_ids)
        ):
            return m
        rtr, net = _VertexIds(r_ids), _VertexIds(n_ids)
        # Vertex ordering contract: networks sort before routers, so
        # that zero-cost network->router edges settle first.
        keys: list = []
        for i in n_pos[net.last].tolist():
            lsa = self.entries[i].lsa
            keys.append(("N", lsa.adv_rtr, int(lsa.lsid)))
        keys += [
            ("R", self.entries[i].lsa.adv_rtr)
            for i in r_pos[rtr.last].tolist()
        ]
        m = self._model3 = _VertexModelV3(
            rtr, net, keys, {k: i for i, k in enumerate(keys)},
            seg_pos=np.concatenate((rtr.emit, len(r_pos) + net.emit)),
            seg_vertex=np.concatenate(
                (len(net.uniq) + rtr.emit_rank, net.emit_rank)
            ),
        )
        return m

    def _resolve_rows(self, m: _VertexModelV3, seg: np.ndarray) -> tuple:
        """The link rows of the entries at places ``seg``, in that
        order, resolved under ``m``: per row ``_KeptRows``' ``dst`` and
        ``cost``."""
        from holo_tpu.protocols.ospf import packet_v3 as P

        count = self._n_links[seg]
        end = np.cumsum(count)
        rows = np.repeat(self._link_off[seg] - (end - count), count)
        rows += np.arange(len(rows))
        kind, nbr, metric, ifid = self._links[rows].T
        # A transit link ends at the DR's network vertex, every other
        # link (and a network's attached router) at a router vertex.
        nbr64 = nbr.astype(np.uint64)
        at_r, there_r = lookup_sorted(m.rtr.uniq, nbr64)
        at_n, there_n = lookup_sorted(
            m.net.uniq, (nbr64 << np.uint64(32)) | ifid.astype(np.uint64)
        )
        dst = np.where(
            kind == int(P.RouterLinkType.TRANSIT_NETWORK),
            np.where(there_n, at_n, -1),
            np.where(there_r, len(m.net.uniq) + at_r, -1),
        )
        return dst.astype(np.int32), metric.astype(np.int32)

    def _link_rows(self, m: _VertexModelV3, seg: np.ndarray) -> _KeptRows:
        """The link rows of the segments emitted from the entries at
        places ``seg``.  While ``m`` is the last call's OBJECT, a row's
        destination vertex cannot have changed unless its entry is a new
        one, nor its mutual flag unless the entry behind either of its
        ends is: only those rows are resolved, the others are the last
        call's, copied.  Under another model every row is resolved."""
        entries = self.entries
        emitted = [entries[i] for i in seg.tolist()]
        kept = self._rows
        count = self._n_links[seg]
        off = np.concatenate(([0], np.cumsum(count)))
        src = np.repeat(m.seg_vertex, count).astype(np.int32)
        if kept is None or kept.model is not m:
            dst, cost = self._resolve_rows(m, seg)
            mutual = np.zeros(len(dst), bool)
            sub = np.flatnonzero(dst >= 0)
            anew = len(dst)
        else:
            moved = np.array([
                s for s, same in enumerate(
                    map(operator.is_, kept.emitted, emitted)
                ) if not same
            ], np.int64)
            new = self._resolve_rows(m, seg[moved])
            anew = len(new[0])
            runs = list(zip(
                kept.off[moved].tolist(), kept.off[moved + 1].tolist()
            ))
            cuts = np.cumsum(count[moved])[:-1]
            dst, cost, mutual = (
                _spliced(old, runs, np.split(part, cuts))
                for old, part in zip(
                    (kept.dst, kept.cost, kept.mutual),
                    (*new, np.zeros(anew, bool)),
                )
            )
            # The rows that leave or reach a vertex whose segment is
            # new are closed under reversal: their flags among
            # themselves are their flags among all.  (``behind[-1]``,
            # where a row ends at no vertex, is False.)
            behind = np.zeros(len(m.keys) + 1, bool)
            behind[m.seg_vertex[moved]] = True
            reach = behind[dst]
            anew += np.count_nonzero(reach & ~behind[src])
            for lo, hi in zip(off[moved].tolist(), off[moved + 1].tolist()):
                reach[lo:hi] = dst[lo:hi] >= 0
            sub = np.flatnonzero(reach)
        mutual[sub] = mutual_keep_mask(src[sub], dst[sub])
        _TOPOLOGY_ROWS.labels(path="kept").inc(len(dst) - anew)
        _TOPOLOGY_ROWS.labels(path="resolved").inc(anew)
        self._rows = _KeptRows(m, emitted, off, src, dst, cost, mutual)
        return self._rows

    def build_topology(
        self,
        lsdb: Lsdb,
        router_id: IPv4Address,
        now: float,
        nbr_hop: dict,
        nbr_hop_by_ifid: dict,
        lan_iface_of: dict,
        vlink_nexthops: dict | None = None,
        iface_srlg: dict[str, int] | None = None,
        partition_of: dict | None = None,
        keep_unchanged: bool = False,
    ) -> SpfTopologyV3 | None:
        """One OSPFv3 area's LSDB as the SPF vertex / edge model, or
        None while the area holds no Router-LSA of ours.

        ``keep_unchanged``: where nothing this result is made from has
        changed since the last call, return the last call's OBJECT.
        What it is made from: the area's Router-, Network- and
        Intra-Area-Prefix LSAs (an entry replaced or gone, and one that
        reached MaxAge on the clock since), and every argument but
        ``now``.

        ``nbr_hop``: FULL point-to-point neighbour's router id ->
        ``(ifname, link-local)``; ``nbr_hop_by_ifid``: the same keyed by
        ``(router id, the neighbour's interface id)``, so that parallel
        links each resolve through their own interface;
        ``lan_iface_of``: network vertex key -> our interface on that
        LAN.  Edges come in LSDB order, links in LSA order, Router-LSAs
        before Network-LSAs; next-hop atoms are assigned for the root's
        own out-edges and for the edges out of its LANs.

        By difference: per link row the last assembly's source and
        destination vertex, cost and mutual flag are kept
        (:meth:`_link_rows`) and trusted while ``_vertex_model3`` hands
        back last call's object (the same live Router- and Network-LSA
        ids in LSDB order); then only the rows of the segments whose
        entry is a new object are resolved, and the mutual flags of
        the rows that leave or reach their vertices.  Another vertex
        model (an LSA of either type came, went or reached MaxAge)
        resolves every row.  The ``Topology`` returned holds arrays of
        its own and is never written after this call returns: the
        instance keeps it as the next run's delta base."""
        from holo_tpu.protocols.ospf import packet_v3 as P

        was = self._kind
        runs = self._refresh(lsdb)
        live = ~(self._age + (now - self._installed_at) >= MAX_AGE)
        inputs = (
            router_id, nbr_hop, nbr_hop_by_ifid,
            {
                k: (i.name, sorted(
                    (int(r), nb.src) for r, nb in i.neighbors.items()
                ))
                for k, i in lan_iface_of.items()
            },
            vlink_nexthops or None, iface_srlg or None,
            partition_of or None,
        )
        # In LSDB order, so that an entry of another type coming or
        # going between them moves nothing here.
        live_matter = live[self._kind != _OTHER]
        kept = self._kept
        if (
            keep_unchanged
            and kept is not None
            and not self._moved(was, runs)
            and np.array_equal(live_matter, kept[2])
            and inputs == kept[1]
        ):
            return kept[0]
        self._kept = None
        r_pos = np.flatnonzero(live & (self._kind == _ROUTER))
        n_pos = np.flatnonzero(live & (self._kind == _NETWORK))
        m = self._vertex_model3(r_pos, n_pos)
        keys, index = m.keys, m.index
        nn, n = len(m.net.uniq), len(keys)
        root = index.get(("R", router_id))
        if root is None:
            return None
        bodies = self._bodies
        prefix_lsas = [
            bodies[i]
            for i in np.flatnonzero(live & (self._kind == _PREFIX)).tolist()
        ]
        is_router = np.zeros(n, bool)
        is_router[nn:] = True

        seg = np.concatenate((r_pos, n_pos))[m.seg_pos]
        rows = self._link_rows(m, seg)
        edge = np.flatnonzero(rows.mutual)
        # Arrays of the result's own: a Topology handed out is never
        # written again, and the kept rows are not handed out.
        atom_ids = np.full(len(edge), -1, np.int32)
        topo = Topology(
            n_vertices=n,
            is_router=is_router,
            edge_src=rows.src[edge],
            edge_dst=rows.dst[edge],
            edge_cost=rows.cost[edge],
            edge_direct_atom=atom_ids,
            root=root,
        )

        # Per-link hop resolution: parallel p2p links to one neighbour
        # are distinct atoms, matched by the neighbour's interface id.
        # The root's out-edges are the edges of its own segment's rows.
        atoms: list = []
        root_lans: list[int] = []
        slot = int(np.flatnonzero(m.seg_vertex == root)[0])
        own = self._links[self._link_off[seg[slot]]:]
        first = int(rows.off[slot])
        lo, hi = np.searchsorted(edge, rows.off[slot:slot + 2]).tolist()
        for e in range(lo, hi):
            kind, _nbr, _metric, ifid = own[edge[e] - first].tolist()
            k = keys[int(topo.edge_dst[e])]
            if k[0] == "R":
                hop = None
                if kind == int(P.RouterLinkType.VIRTUAL_LINK):
                    # Virtual link: the borrowed transit-area set only;
                    # a direct adjacency here would pair the vlink
                    # metric with the wrong next hop.
                    borrowed = (vlink_nexthops or {}).get(k[1])
                    if borrowed:
                        hop = NexthopAtom(None, None, borrowed)
                else:
                    hop = nbr_hop_by_ifid.get(
                        (k[1], ifid)
                    ) or nbr_hop.get(k[1])
                if hop is not None:
                    atom_ids[e] = len(atoms)
                    atoms.append(hop)
            elif k in lan_iface_of:
                # Directly-attached LAN: reached on the interface
                # itself, the (ifname, no address) atom of v2.
                root_lans.append(int(topo.edge_dst[e]))
                atom_ids[e] = len(atoms)
                atoms.append((lan_iface_of[k].name, None))
        # Network -> member edges of the root's LANs: the member's
        # link-local on that LAN (the hops == 0 rule).
        if root_lans:
            for e in np.flatnonzero(
                np.isin(topo.edge_src, root_lans)
            ).tolist():
                iface = lan_iface_of[keys[int(topo.edge_src[e])]]
                member = keys[int(topo.edge_dst[e])][1]
                if member == router_id:
                    continue
                member_nbr = iface.neighbors.get(member)
                if member_nbr is not None:
                    atom_ids[e] = len(atoms)
                    atoms.append((iface.name, member_nbr.src))
        if iface_srlg:
            # v3 atoms are NexthopAtom (vlinks) or (ifname, addr) tuples.
            apply_interface_srlg(
                topo,
                [a.ifname if hasattr(a, "ifname") else a[0] for a in atoms],
                iface_srlg,
            )
        if partition_of:
            # A network vertex rides the lowest-labelled attached router.
            groups: list = []
            for i in n_pos[m.net.last].tolist():
                att = [
                    partition_of[r]
                    for r in bodies[i].attached
                    if r in partition_of
                ]
                groups.append(min(att) if att else None)
            groups += [partition_of.get(k[1]) for k in keys[nn:]]
            apply_partition_hint(topo, groups)
        topo.touch()
        st = SpfTopologyV3(
            topo, atoms, index, {}, keys=keys, prefix_lsas=prefix_lsas,
        )
        self._kept = (st, inputs, live_matter)
        return st


# ===== Route derivation by difference ===================================

_DERIVE_ROUTES = telemetry.counter(
    "holo_ospf_derive_routes_total",
    "KeptDerive.derive's routes by how the call had them: kept (the last "
    "call's route object: no offer of the prefix sits on a vertex whose "
    "distance or next-hop row moved, nor in an LSA that came, went or "
    "was lowered anew), or rebuilt from the prefix's offers (every route "
    "of a whole derive)",
    ("path",),
)

#: distinct next-hop rows kept decoded between runs, while the atoms stay
_DECODED_MAX = 4096


def _atom_columns(words: np.ndarray, n_atoms: int) -> np.ndarray:
    """Next-hop bitmask rows (``uint32[N, W]``) as one column per atom,
    ``bool[N, n_atoms]``: atom ``a`` is bit ``a % 32`` of word ``a // 32``."""
    atom = np.arange(n_atoms)
    return (words[:, atom >> 5] >> (atom & 31).astype(words.dtype)) & 1 != 0


class KeptDerive:
    """One area's intra-area routes, derived by difference between SPF
    runs: a route is rebuilt only where an input it is made from moved,
    and is the last run's OBJECT everywhere else.

    The state is the area's *offers* in flat form, in the order a walk
    meets them (the live prefix-offering LSAs in LSDB order, entries in
    LSA order): per offer its vertex (-1 where the LSA names none the
    model has), its metric, its prefix object, its options, and the
    *slot* of its prefix (equal prefixes share one); per slot last
    run's route, or None where no offer of it was reachable.  Beside
    them the planes of the last result.

    ``lower(body)`` gives an LSA body's ``(vertex key or None,
    [(prefix, metric, options), ...])``; ``expand(words, atoms)`` a
    next-hop row's set; ``make_route(prefix, dist, nexthops, options,
    vertex)`` a route, of which ``prefix`` is read back, and ``dist``,
    ``nexthops``, ``prefix_options`` and ``vertex`` when offers tie.
    """

    def __init__(self, lower, expand, make_route) -> None:
        self._lower, self._expand, self._make = lower, expand, make_route
        self._keys: list | None = None  # the vertex model's, by identity
        self._atoms: list | None = None
        self._planes: tuple = ()  # (dist, nexthop_words, nh_weights)
        self._bodies: list = []  # the offering LSAs, LSDB order
        self._count = np.zeros(0, np.int64)  # offers per LSA
        self._vertex = np.zeros(0, np.int64)  # per offer, as are these
        self._metric = np.zeros(0, np.int64)
        self._slot = np.zeros(0, np.int64)
        self._prefix: list = []
        self._opts: list = []
        self._slot_of: dict = {}  # prefix -> slot
        self._route: list = []  # per slot
        self._reach = np.zeros(0, bool)  # per offer, as of the last run
        self._table: dict = {}  # the last run's, never handed out
        self._decoded: dict = {}  # next-hop row -> set, under _atoms

    def _lowered(self, index: dict, bodies: list) -> tuple:
        """``bodies`` as flat offers: ``(offers per body, vertex,
        metric, slot, prefixes, options)``; a prefix not seen before
        takes a new slot."""
        count, vertex, metric, slot, prefix, opts = [], [], [], [], [], []
        slot_of, route = self._slot_of, self._route
        for body in bodies:
            key, offers = self._lower(body)
            v = -1 if key is None else index.get(key, -1)
            count.append(len(offers))
            for p, m, o in offers:
                s = slot_of.get(p)
                if s is None:
                    s = slot_of[p] = len(route)
                    route.append(None)
                vertex.append(v)
                metric.append(m)
                slot.append(s)
                prefix.append(p)
                opts.append(o)
        return (
            np.array(count, np.int64), np.array(vertex, np.int64),
            np.array(metric, np.int64), np.array(slot, np.int64),
            prefix, opts,
        )

    def _reset(self, index: dict, bodies: list) -> None:
        """Lower every LSA anew: no slot and no route of the last run
        is kept."""
        self._slot_of, self._route = {}, []
        (
            self._count, self._vertex, self._metric, self._slot,
            self._prefix, self._opts,
        ) = self._lowered(index, bodies)
        self._bodies = bodies

    def _follow(self, index: dict, bodies: list) -> np.ndarray | None:
        """Bring the offers up to ``bodies``, told from the last run's
        by identity: the segments of the LSAs that stayed are carried
        over, the others lowered.  Returns the slots that an LSA which
        came, went or was lowered anew offers or offered (``bool[S]``),
        or None where no LSA differs."""
        old = self._bodies
        if len(old) == len(bodies) and all(map(operator.is_, old, bodies)):
            return None
        # ``old`` holds its bodies alive, so an id there is no other's.
        at = {id(b): i for i, b in enumerate(old)}
        was = np.array([at.get(id(b), -1) for b in bodies], np.int64)
        came = np.flatnonzero(was < 0)
        count, vertex, metric, slot, prefix, opts = self._lowered(
            index, [bodies[i] for i in came.tolist()]
        )
        dirty = np.zeros(len(self._route), bool)
        dirty[slot] = True
        went = np.ones(len(old), bool)
        went[was[was >= 0]] = False
        dirty[self._slot[np.repeat(went, self._count)]] = True
        # Each LSA's segment in (the last run's offers ++ the lowered).
        n_old = len(self._vertex)
        old_off = np.concatenate(([0], np.cumsum(self._count)))
        new_off = n_old + np.concatenate(([0], np.cumsum(count)))
        # (-1 reads the last place, here and below: overwritten)
        start = old_off[was]
        start[came] = new_off[:-1]
        counts = np.append(self._count, 0)[was]
        counts[came] = count
        end = np.cumsum(counts)
        rows = np.repeat(start - (end - counts), counts)
        rows += np.arange(len(rows))
        self._vertex = np.concatenate((self._vertex, vertex))[rows]
        self._metric = np.concatenate((self._metric, metric))[rows]
        self._slot = np.concatenate((self._slot, slot))[rows]
        at_row = rows.tolist()
        prefix = self._prefix + prefix
        opts = self._opts + opts
        self._prefix = [prefix[i] for i in at_row]
        self._opts = [opts[i] for i in at_row]
        self._count, self._bodies = counts, bodies
        return dirty

    def _sets_of(self, vertex: np.ndarray, words: np.ndarray, atoms) -> tuple:
        """The next-hop set behind each of ``vertex``, one decode per
        distinct row that no earlier run under these atoms decoded.
        Returns ``(sets, rows decoded)``."""
        uniq, inverse = np.unique(vertex, return_inverse=True)
        decoded, fresh, sets = self._decoded, 0, []
        for row in words[uniq]:
            key = row.tobytes()
            nhs = decoded.get(key)
            if nhs is None:
                nhs = decoded[key] = self._expand(row, atoms)
                fresh += 1
            sets.append(nhs)
        return [sets[i] for i in inverse.tolist()], fresh

    def _moved(self, planes: tuple, atoms: list) -> np.ndarray | None:
        """The vertices whose row of any of ``planes`` is not the last
        run's (``bool[N]``): what a route is made from, per vertex.
        Under another atom table a bit names another hop, so the
        next-hop rows are compared atom for atom (equal atoms paired in
        order), and a row that holds an atom with no counterpart has
        moved.  None where the two runs cannot be compared."""
        dist, words, weights = planes
        was_dist, was_words, was_weights = self._planes
        if (weights is None) != (was_weights is None):
            return None
        moved = dist != was_dist
        was_atoms = self._atoms
        if atoms == was_atoms:
            moved |= (words != was_words).any(axis=1)
            if weights is not None:
                moved |= (weights != was_weights).any(axis=1)
            return moved
        if weights is not None:
            return None  # the weights' columns are atoms too: not paired
        places: dict = {}
        for now, atom in enumerate(atoms):
            places.setdefault(atom, []).append(now)
        pairs = [
            (was, places[atom].pop(0))
            for was, atom in enumerate(was_atoms) if places.get(atom)
        ]
        was_at = np.array([was for was, _now in pairs], np.int64)
        now_at = np.array([now for _was, now in pairs], np.int64)
        was_bits = _atom_columns(was_words, len(was_atoms))
        now_bits = _atom_columns(words, len(atoms))
        moved |= (was_bits[:, was_at] != now_bits[:, now_at]).any(axis=1)
        moved |= np.delete(was_bits, was_at, axis=1).any(axis=1)
        moved |= np.delete(now_bits, now_at, axis=1).any(axis=1)
        return moved

    def derive(self, index: dict, keys: list, atoms: list, res, lsas) -> dict:
        """``{prefix: route}`` of one SPF result, key for key and in
        the order a walk over ``lsas`` (``(advertising router, body)``
        of the live offering LSAs, LSDB order) inserts them: a prefix
        stands where its first reachable offer does; of its offers the
        lowest total wins, equal totals unite their next-hop sets and
        keep the first's options and vertex.

        Whole (every route rebuilt, and counted so) where the last
        run's state says nothing about this one: there is none, the
        vertex model is another object (vertex ids moved), or the
        result carries other planes.  The caller drops this object
        where what it cannot see changed (another backend, other
        knobs)."""
        bodies = [body for _adv, body in lsas]
        dist, words = res.dist, res.nexthop_words
        planes = (dist, words, getattr(res, "nh_weights", None))
        moved = None
        if (
            self._keys is keys
            # slots of prefixes long gone: start again
            and len(self._route) <= 2 * len(self._table) + 256
        ):
            moved = self._moved(planes, atoms)
        same_offers = False
        if moved is None:
            self._reset(index, bodies)
            self._keys = keys
            dirty = np.ones(len(self._route), bool)
        else:
            dirty = self._follow(index, bodies)
            same_offers = dirty is None
            if same_offers:
                dirty = np.zeros(len(self._route), bool)
            # (an offer with no vertex reads the last vertex's flag)
            dirty[self._slot[moved[self._vertex] & (self._vertex >= 0)]] = True
        if atoms != self._atoms or len(self._decoded) > _DECODED_MAX:
            self._atoms, self._decoded = list(atoms), {}
        self._planes = planes

        base = dist[self._vertex]
        reach = (self._vertex >= 0) & (base < INF)
        sel = np.flatnonzero(reach & dirty[self._slot])
        vertex = self._vertex[sel]
        sets, fresh = self._sets_of(vertex, words, atoms)
        total = base[sel].astype(np.int64) + self._metric[sel]
        prefix, opts, make = self._prefix, self._opts, self._make
        new: dict = {}
        for i, s, t, v, nhs in zip(
            sel.tolist(), self._slot[sel].tolist(), total.tolist(),
            vertex.tolist(), sets,
        ):
            cur = new.get(s)
            if cur is None or t < cur.dist:
                new[s] = make(prefix[i], t, nhs, opts[i], v)
            elif t == cur.dist:
                new[s] = make(
                    prefix[i], t, cur.nexthops | nhs, cur.prefix_options,
                    cur.vertex,
                )
        route = self._route
        for s in np.flatnonzero(dirty).tolist():
            route[s] = new.get(s)

        if same_offers and np.array_equal(reach, self._reach):
            # The keys and their order are the last run's: a copy keeps
            # their hashes, and only a rebuilt prefix is hashed again.
            table = dict(self._table)
            for r in new.values():
                table[r.prefix] = r
        else:
            at = np.flatnonzero(reach)
            _slots, first = np.unique(self._slot[at], return_index=True)
            at = at[np.sort(first)]
            table = dict(zip(
                [prefix[i] for i in at.tolist()],
                [route[s] for s in self._slot[at].tolist()],
            ))
        self._reach, self._table = reach, table
        _DERIVE_ROUTES.labels(path="rebuilt").inc(len(new))
        _DERIVE_ROUTES.labels(path="kept").inc(len(table) - len(new))
        _DERIVE_NEXTHOPS.labels(path="decoded").inc(fresh)
        _DERIVE_NEXTHOPS.labels(path="reused").inc(int(reach.sum()) - fresh)
        # The caller's own: a partial run edits it in place.
        return dict(table)


def build_topology(
    lsdb: Lsdb,
    router_id: IPv4Address,
    now: float,
    iface_by_addr: dict[IPv4Address, str],
    iface_by_nbr: dict[IPv4Address, tuple[str, IPv4Address]],
    p2p_nbr_addr: dict[tuple, IPv4Address] | None = None,
    iface_by_ifindex: dict[int, str] | None = None,
    vlink_nexthops: dict | None = None,
    iface_srlg: dict[str, int] | None = None,
    partition_of: dict | None = None,
) -> SpfTopology | None:
    """Lower the area LSDB to the SPF vertex/edge model.

    iface_by_addr: our interface address -> ifname (for transit networks we
    attach to).  iface_by_nbr: neighbor router-id -> (ifname, nbr addr)
    for p2p adjacencies (direct next-hop resolution); with
    ``p2p_nbr_addr`` {(ifname, nbr_rid): addr} parallel p2p links each
    resolve through their own interface (the per-link link_data of our
    router LSA selects the interface).
    MaxAge LSAs are excluded (RFC 2328 §16.1 note).

    Edges come in LSDB order, links in LSA order, router-LSAs before
    network-LSAs: ``build_ell``'s slot assignment and DeltaPath's
    lineage read that order.  This lowers the whole LSDB; a caller that
    runs SPF again and again keeps a :class:`LoweredLsdb` per area and
    calls its ``build_topology`` (same arguments, same result), which
    lowers only the LSAs installed since its last call.
    """
    return LoweredLsdb().build_topology(
        lsdb, router_id, now, iface_by_addr, iface_by_nbr, p2p_nbr_addr,
        iface_by_ifindex, vlink_nexthops, iface_srlg, partition_of,
    )



def link_spf_delta(
    prev: SpfTopology | None, new: SpfTopology,
    max_ops: int = DELTA_MAX_OPS,
) -> bool:
    """DeltaPath construction at the LSDB seam: attach delta lineage to
    ``new`` when it differs from the previous run's marshaled topology
    by a small edge-level change over the SAME vertex model and
    next-hop atom table.  The device-graph cache then updates the
    resident EllGraph in place and the TPU backend recomputes
    incrementally instead of re-marshaling the whole LSDB (DeltaPath,
    ISSUE 7).  The equality guards below make vertex and atom ids
    mean the same thing in both topologies, which is all
    ``diff_topologies`` (a packed-key 1-D diff since ISSUE 26) compares;
    the ``ospf.spf.link`` stage times this whole function.  Returns whether
    lineage was attached; False always means the full-rebuild path,
    never an error."""
    if prev is None:
        return False
    if (
        prev.atoms != new.atoms
        or prev.router_index != new.router_index
        or prev.network_index != new.network_index
    ):
        return False
    from holo_tpu.ops.graph import diff_topologies

    delta = diff_topologies(prev.topo, new.topo, max_ops=max_ops)
    if delta is None:
        return False
    new.topo.link_delta(delta)
    return True


@dataclass(frozen=True)
class RouteNexthop:
    ifname: str
    addr: IPv4Address | None


@dataclass
class IntraRoute:
    prefix: IPv4Network
    dist: int
    nexthops: frozenset[RouteNexthop]
    area_id: IPv4Address
    # "intra" | "inter" | "external-1" | "external-2" | "nssa-1" |
    # "nssa-2" — drives per-type admin distance and maps onto the
    # ietf-ospf route-type enumeration in operational state.
    rtype: str = "intra"
    # SPF vertex the winning path terminates at (-1 when the route was
    # not derived from an SPT vertex, e.g. externals): the IP-FRR
    # consumption key — backup tables are indexed by destination vertex.
    vertex: int = -1
    # IP-FRR repairs attached after the backup-table run:
    # {primary RouteNexthop -> (backup RouteNexthop, label stack)}.
    backups: dict | None = None
    # UCMP weights {RouteNexthop -> saturated shortest-path count}
    # (ISSUE 10): present only when the SPF ran with multipath planes;
    # rides RouteMsg.nh_weights into the RIB's weighted install.
    nh_weights: dict | None = None


_DERIVE_CALLS = telemetry.counter(
    "holo_ospf_derive_calls_total",
    "derive_routes calls by where the offers came from: planned (read by "
    "vertex from the plan the area's kept lowering made for the run), or "
    "walked (the LSDB and every vertex, for a topology without a plan)",
    ("path",),
)

_DERIVE_NEXTHOPS = telemetry.counter(
    "holo_ospf_derive_nexthops_total",
    "derive_routes' prefix offers by how the offering vertex's next-hop "
    "set was had: decoded from its bitmask row (once per distinct row "
    "and call), or reused from a row already decoded in that call",
    ("path",),
)


def atom_bits(words: np.ndarray, n_atoms: int) -> list[int]:
    """Indices of set bits in an ECMP atom bitmask (uint32 words).

    The words fold into one Python int (word ``i`` holds atoms
    ``32 i .. 32 i + 31``) and its set bits are walked lowest first: the
    cost follows the bits that are set, not the number of atoms."""
    bits = int.from_bytes(np.asarray(words, "<u4").tobytes(), "little")
    bits &= (1 << n_atoms) - 1
    out = []
    while bits:
        low = bits & -bits
        out.append(low.bit_length() - 1)
        bits ^= low
    return out


def _atoms_of(words: np.ndarray, atoms: list[NexthopAtom]) -> frozenset[RouteNexthop]:
    out = set()
    for a in atom_bits(words, len(atoms)):
        atom = atoms[a]
        if atom.expand is not None:
            out |= atom.expand
        else:
            out.add(RouteNexthop(atom.ifname, atom.addr))
    return frozenset(out)


def _atom_weights_of(
    words: np.ndarray, weights_row: np.ndarray, atoms: list[NexthopAtom]
) -> dict:
    """{RouteNexthop -> UCMP weight} for one vertex's next-hop set;
    atoms resolving to the same next hop (or a vlink expansion) sum."""
    out: dict = {}
    for a in atom_bits(words, len(atoms)):
        atom = atoms[a]
        w = int(weights_row[a]) if a < len(weights_row) else 0
        targets = (
            atom.expand
            if atom.expand is not None
            else (RouteNexthop(atom.ifname, atom.addr),)
        )
        for nh in targets:
            out[nh] = out.get(nh, 0) + w
    return out


def _nh_rank(nh, weights: dict):
    """Deterministic multipath clamp order: UCMP weight descending,
    then lowest next-hop address (the reference's ECMP clamp key),
    then interface name."""
    return (
        -weights.get(nh, 1),
        nh.addr is None,
        nh.addr.packed if nh.addr is not None else b"",
        nh.ifname or "",
    )


def clamp_multipath(routes: dict, max_paths: int | None) -> int:
    """Truncate every route's ECMP set to ``max_paths`` next hops (the
    OSPF ``max-paths`` seam), keeping the highest-weight paths; weights
    dicts are filtered to the survivors.  Returns routes clamped."""
    if not max_paths or max_paths < 1:
        return 0
    clamped = 0
    for route in routes.values():
        if len(route.nexthops) <= max_paths:
            continue
        w = route.nh_weights or {}
        ranked = sorted(route.nexthops, key=lambda nh: _nh_rank(nh, w))
        keep = frozenset(ranked[:max_paths])
        route.nexthops = keep
        if route.nh_weights:
            route.nh_weights = {
                nh: ww for nh, ww in route.nh_weights.items() if nh in keep
            }
        clamped += 1
    return clamped


def reachable_router_flags(
    st: SpfTopology, res: SpfResult, lsdb: Lsdb
) -> dict[IPv4Address, RouterFlags]:
    """Routers this SPF run reached, each with the flags of its
    Router-LSA as of the run (``RouterFlags(0)`` without a live one):
    operational state counts ABRs and ASBRs from these, not from the
    live LSDB (reference area.rs:164-182).

    With the run's plan on ``st`` nothing is read from ``lsdb``: the
    flags are those of the LSA behind each router's vertex."""
    if st.plan is not None:
        return st.plan.reached_flags(res.dist)
    flags = {
        key.adv_rtr: e.lsa.body.flags
        for key, e in lsdb.entries.items()
        if key.type == LsaType.ROUTER and not e.lsa.is_maxage
    }
    dist = res.dist.tolist()
    no_flags = RouterFlags(0)
    return {
        rid: flags.get(rid, no_flags)
        for rid, v in st.router_index.items()
        if dist[v] < INF
    }


def derive_routes(
    st: SpfTopology,
    res: SpfResult,
    lsdb: Lsdb,
    now: float,
    area_id: IPv4Address,
    max_paths: int | None = None,
) -> dict[IPv4Network, IntraRoute]:
    """Intra-area routes from SPF results (RFC 2328 §16.1 steps 2-4).

    Transit networks yield their prefix at the network vertex's distance;
    router stub links yield prefix routes at dist(router)+metric.  Equal
    cost contributions union their next-hop sets; the root's own stubs
    are local (empty next-hop set).  Address-less next-hops (interface
    only) mean DIRECTLY ATTACHED (reference route.rs:96): they render in
    operational state but are never installed to the RIB — the connected
    route owns the FIB entry (see OspfInstance._sync_rib).

    ``st.plan``, where the topology came from a lowering at this
    ``now``, lists what every live vertex offers: only those vertices
    are touched and ``lsdb`` is not read.  Without it the LSDB and every
    vertex are walked; the routes are the same, in the same order.
    """
    routes: dict[IPv4Network, IntraRoute] = {}
    plan = st.plan if st.plan is not None and st.plan.now == now else None
    _DERIVE_CALLS.labels(path="walked" if plan is None else "planned").inc()

    def offer(prefix, dist, nhs, vertex=-1, weights=None):
        cur = routes.get(prefix)
        if cur is None or dist < cur.dist:
            routes[prefix] = IntraRoute(
                prefix, dist, nhs, area_id, vertex=vertex,
                nh_weights=dict(weights) if weights else None,
            )
        elif dist == cur.dist:
            # Equal-cost contributions union next hops; the first
            # contributing vertex keeps the FRR consumption key (its
            # backup covers the merged set's shared failure domain only
            # approximately, matching the reference's per-route pick).
            merged = None
            if cur.nh_weights or weights:
                merged = dict(cur.nh_weights or {})
                for nh, w in (weights or {}).items():
                    merged[nh] = merged.get(nh, 0) + w
            routes[prefix] = IntraRoute(
                prefix, dist, cur.nexthops | nhs, area_id,
                vertex=cur.vertex, nh_weights=merged,
            )

    def walked():
        """``(vertex, prefix, cost)`` of every offer of every reachable
        vertex, from the LSDB."""
        inv_net = {i: a for a, i in st.network_index.items()}
        inv_rtr = {i: r for r, i in st.router_index.items()}
        nlsa = {}
        rlsa = {}
        for e in lsdb.all():
            if e.current_age(now) >= 3600:
                continue
            if e.lsa.type == LsaType.NETWORK:
                nlsa[e.lsa.lsid] = e.lsa.body
            elif e.lsa.type == LsaType.ROUTER:
                rlsa[e.lsa.adv_rtr] = e.lsa.body
        dist = res.dist.tolist()
        for v in range(st.topo.n_vertices):
            if dist[v] >= INF:
                continue
            net = inv_net.get(v)
            if net is not None:
                body = nlsa.get(net)
                if body is None:
                    continue
                yield v, apply_mask(net, body.mask), dist[v]
            else:
                body = rlsa.get(inv_rtr[v])
                if body is None:
                    continue
                for link in body.links:
                    if link.link_type == RouterLinkType.STUB_NETWORK:
                        yield (
                            v, apply_mask(link.id, link.data),
                            dist[v] + link.metric,
                        )

    def planned():
        """The same from the plan: its rows at reachable vertices."""
        d = res.dist[plan.offer_vertex]
        keep = np.flatnonzero(d < INF)
        prefixes = plan.prefixes
        for v, i, cost in zip(
            plan.offer_vertex[keep].tolist(), keep.tolist(),
            (d[keep] + plan.offer_metric[keep]).tolist(),
        ):
            prefix = prefixes[i]
            if prefix.__class__ is tuple:
                prefix = apply_mask(*prefix)  # raises: see _offered
            yield v, prefix, cost

    # Per-vertex UCMP weights ride the multipath planes when the
    # dispatch carried them (max-paths > 1 → multipath kernel).
    nhw = getattr(res, "nh_weights", None)
    # The planes as Python values once.  A 10,000-vertex area holds a
    # handful of distinct bitmask rows (the root has few atoms), so a
    # next-hop set is decoded once per distinct row, keyed by the row's
    # bytes, and only when a vertex that offers a prefix asks for it.
    # The shared frozenset is immutable: offer's union and the clamp
    # rebind, they never mutate.
    words = res.nexthop_words
    stride = words.shape[1] * words.itemsize
    rows = words.tobytes()  # C order, whatever the plane's layout
    decoded: dict[bytes, frozenset] = {}
    offers = 0
    at = -1
    for v, prefix, cost in walked() if plan is None else planned():
        if v != at:  # a vertex's offers come together
            at = v
            row = rows[v * stride:(v + 1) * stride]
            nhs = decoded.get(row)
            if nhs is None:
                nhs = decoded[row] = _atoms_of(words[v], st.atoms)
            # A vertex's weights are its own nhw row's: not shared by mask.
            weights = (
                _atom_weights_of(words[v], nhw[v], st.atoms)
                if nhw is not None
                else None
            )
        offer(prefix, cost, nhs, vertex=v, weights=weights)
        offers += 1
    _DERIVE_NEXTHOPS.labels(path="decoded").inc(len(decoded))
    _DERIVE_NEXTHOPS.labels(path="reused").inc(offers - len(decoded))
    clamp_multipath(routes, max_paths)
    return routes


def attach_frr_backups(
    st: SpfTopology,
    res: SpfResult,
    routes: dict,
    table,
    cfg,
    label_of_vertex=None,
    area_id=None,
) -> int:
    """Attach precomputed repairs to routes derived from ``st``/``res``.

    For every route whose winning path ends at an SPT vertex, each
    primary next-hop atom maps (via the backup table's ``atom_link``) to
    its protected link, and ``resolve_backup`` picks the repair.  Direct
    LFAs attach as plain next hops; remote-LFA / TI-LFA repairs need a
    tunnel to their release vertex, so they attach only when
    ``label_of_vertex`` resolves a segment (node-SID label) for every
    repair vertex — without SR there is no loop-free encapsulation and
    the destination stays unprotected (RFC 7490 §2 applies).  Returns
    the number of routes that gained at least one backup."""
    from holo_tpu.frr.manager import repair_map

    n = st.topo.n_vertices
    attached = 0
    # All prefixes terminating at the same SPT vertex share one repair
    # map — memoize per vertex (O(reachable vertices), not O(routes)).
    memo: dict[int, dict] = {}
    for route in routes.values():
        if area_id is not None and route.area_id != area_id:
            continue
        if not cfg.protects_prefix(route.prefix):
            continue  # per-prefix protection filtering (policy scope)
        v = getattr(route, "vertex", -1)
        if v < 0 or v >= n:
            continue
        repairs = memo.get(v)
        if repairs is None:
            repairs = memo[v] = repair_map(
                table, cfg, res.nexthop_words[v], v
            )
        backups = {}
        for a, entry in repairs.items():
            atom = st.atoms[a]
            batom = st.atoms[entry.atom]
            if atom.expand is not None or batom.expand is not None:
                continue  # vlink bundles have no single protected link
            labels: tuple = ()
            if entry.kind != "lfa":
                if label_of_vertex is None:
                    continue
                resolved = [label_of_vertex(p) for p in entry.via]
                if any(l is None for l in resolved):
                    continue
                labels = tuple(resolved)
            backups[RouteNexthop(atom.ifname, atom.addr)] = (
                RouteNexthop(batom.ifname, batom.addr),
                labels,
            )
        if backups:
            route.backups = backups
            attached += 1
    return attached
