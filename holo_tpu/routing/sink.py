"""The daemon's route sink: a protocol's table, or what changed of it,
turned into ``route_add`` / ``route_del`` calls on a RIB.

Protocol instances that publish through a callback (OSPFv3, IS-IS,
RIP) hand their routes here; ``daemon/providers.py`` holds one
``RouteSink`` over its ``RibManager``, and so does anything else that
wants a protocol's routes in a RIB the way a daemon puts them there
(the chip benchmark's OSPFv3 network does).
"""

from __future__ import annotations

from holo_tpu.utils.southbound import (
    DEFAULT_DISTANCE,
    Nexthop,
    RouteKeyMsg,
    RouteMsg,
)


class RouteSink:
    """Delta route sink over ``rib`` (anything with ``route_add`` and
    ``route_del``).  An item is ``(metric, {(ifname, addr)})`` or, with
    IP-FRR repairs, ``(metric, nhs, {primary -> (backup, labels)})``:
    the backups ride the RouteMsg so the RIB can flip to them on
    BFD/link-down without waiting for this layer.

    The last pushed set is kept per protocol so unchanged routes skip
    RIB churn; :meth:`drop` forgets it when the instance stops
    (otherwise a disable/re-enable would suppress re-installation)."""

    #: distinct next-hop sets kept as ``Nexthop`` sets before a restart
    _NEXTHOP_SETS = 1 << 16

    def __init__(self, rib):
        self.rib = rib
        self._caches: dict = {}
        # {(ifname, addr)} -> frozenset[Nexthop]: a table's routes share
        # a few thousand sets between them, and a run that moves one
        # uplink republishes every route whose set held it.
        self._nexthops: dict = {}

    def _add(self, protocol, prefix, entry) -> None:
        metric, nhs = entry[0], entry[1]
        raw_backups = entry[2] if len(entry) > 2 else None
        backups = {}
        for (pi, pa), ((bi, ba), labels) in (raw_backups or {}).items():
            if pa is None or ba is None:
                continue
            backups[Nexthop(addr=pa, ifname=pi)] = Nexthop(
                addr=ba, ifname=bi, labels=tuple(labels)
            )
        nexthops = self._nexthops.get(nhs)
        if nexthops is None:
            if len(self._nexthops) >= self._NEXTHOP_SETS:
                self._nexthops.clear()
            nexthops = self._nexthops[nhs] = frozenset(
                Nexthop(addr=a, ifname=i) for i, a in nhs
            )
        self.rib.route_add(
            RouteMsg(
                protocol=protocol,
                prefix=prefix,
                distance=DEFAULT_DISTANCE.get(protocol, 250),
                metric=metric,
                nexthops=nexthops,
                backups=backups,
            )
        )

    def push(self, protocol, items: dict) -> None:
        """``items`` is the protocol's whole table, ``{prefix: item}``:
        what is no longer in it is withdrawn, what differs from the last
        push is (re)installed."""
        old = self._caches.get(protocol, {})
        for prefix in old.keys() - items.keys():
            self.rib.route_del(RouteKeyMsg(protocol, prefix))
        for prefix, entry in items.items():
            if old.get(prefix) != entry:
                self._add(protocol, prefix, entry)
        self._caches[protocol] = dict(items)

    def push_delta(self, protocol, changed: dict, removed) -> None:
        """Only what moved: ``changed`` ``{prefix: item}`` is
        (re)installed, ``removed`` prefixes are withdrawn; every other
        prefix of the protocol stays as the last push left it."""
        cache = self._caches.setdefault(protocol, {})
        for prefix in removed:
            if cache.pop(prefix, None) is not None:
                self.rib.route_del(RouteKeyMsg(protocol, prefix))
        for prefix, entry in changed.items():
            if cache.get(prefix) != entry:
                self._add(protocol, prefix, entry)
                cache[prefix] = entry

    def drop(self, protocol, prefixes) -> None:
        """The instance is gone: withdraw ``prefixes`` and forget what
        was pushed."""
        for prefix in prefixes:
            self.rib.route_del(RouteKeyMsg(protocol, prefix))
        self._caches.pop(protocol, None)


def v6_route_item(route) -> tuple:
    """An ``instance_v3.V6Route`` as a sink item."""
    return route.dist, frozenset(route.nexthops), route.backups
