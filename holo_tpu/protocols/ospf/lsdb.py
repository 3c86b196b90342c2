"""LSDB: link-state database with install/originate/flush and aging.

Reference: holo-ospf/src/lsdb.rs (install :397-489, originate :518, flush
:665).  LSAs are stored per scope (area / AS) keyed by (type, lsid, adv_rtr);
install performs the RFC 2328 §13.2 content-change check that drives SPF
scheduling, and origination handles sequence numbers, MinLSInterval batching
and refresh.
"""

from __future__ import annotations

import operator
from dataclasses import dataclass, field
from ipaddress import IPv4Address

import numpy as np

from holo_tpu.protocols.ospf.packet import (
    INITIAL_SEQ_NO,
    LS_REFRESH_TIME,
    MAX_AGE,
    MAX_SEQ_NO,
    Lsa,
    LsaKey,
)

MIN_LS_INTERVAL = 5.0  # §12.4: min seconds between originations of same LSA
MIN_LS_ARRIVAL = 1.0  # §13 (5)(a): min seconds between accepting copies


@dataclass
class LsaEntry:
    lsa: Lsa
    installed_at: float  # loop-clock time of install (for age computation)
    rcvd_time: float = 0.0
    # Origination bookkeeping for self-originated LSAs:
    last_originated: float | None = None

    def current_age(self, now: float) -> int:
        return min(int(self.lsa.age + (now - self.installed_at)), MAX_AGE)


@dataclass
class Lsdb:
    """One LSA scope (an area's LSDB, or the AS-scope external LSDB)."""

    entries: dict[LsaKey, LsaEntry] = field(default_factory=dict)
    # Pending (delayed) originations blocked by MinLSInterval.
    pending: dict[LsaKey, Lsa] = field(default_factory=dict)
    # Kept by ``install`` and ``remove`` (an edit of ``entries`` around
    # them is not seen), each with a count of the installs and removals
    # it saw: LSA type -> [count, {key: entry}], and (advertising router,
    # LSA type) -> [count, {key: None}], in LSDB order.
    _types: dict = field(default_factory=dict, repr=False, compare=False)
    _origins: dict = field(default_factory=dict, repr=False, compare=False)

    def get(self, key: LsaKey) -> LsaEntry | None:
        return self.entries.get(key)

    def all(self):
        return self.entries.values()

    def install(self, lsa: Lsa, now: float) -> tuple[LsaEntry, bool]:
        """Install (replacing any old copy).  Returns (entry, content_changed).

        content_changed implements the §13.2 comparison: options/body bytes
        differ, or MaxAge transition — the trigger condition for SPF
        (lsdb.rs:457-469).
        """
        key = lsa.key
        old = self.entries.get(key)
        changed = True
        if old is not None:
            old_lsa = old.lsa
            changed = (
                old_lsa.options != lsa.options
                or old_lsa.is_maxage != lsa.is_maxage
                or old_lsa.raw[LsaBodyOffset:] != lsa.raw[LsaBodyOffset:]
            )
        entry = LsaEntry(lsa=lsa, installed_at=now, rcvd_time=now)
        if old is not None:
            entry.last_originated = old.last_originated
        self.entries[key] = entry
        of_type = self._types.get(key.type)
        if of_type is None:
            of_type = self._types[key.type] = [0, {}]
        of_type[0] += 1
        of_type[1][key] = entry
        origin = self._origins.get((key.adv_rtr, key.type))
        if origin is None:
            origin = self._origins[(key.adv_rtr, key.type)] = [0, {}]
        origin[0] += 1
        if old is None:
            origin[1][key] = None
        return entry, changed

    def remove(self, key: LsaKey) -> None:
        if self.entries.pop(key, None) is not None:
            for index, at in (
                (self._types, key.type),
                (self._origins, (key.adv_rtr, key.type)),
            ):
                kept = index.get(at)
                if kept is not None:
                    kept[0] += 1
                    kept[1].pop(key, None)

    def of_type(self, ltype) -> tuple[int, dict]:
        """The LSAs of one type: how many times one of them was
        installed or removed (while the count stays, none of them moved)
        and ``{key: entry}`` in LSDB order (the index itself: copy it to
        keep it)."""
        count, entries = self._types.get(ltype, (0, {}))
        return count, entries

    def originated(self, adv_rtr: IPv4Address, ltype) -> tuple[int, dict]:
        """The LSAs of one type that ``adv_rtr`` originated: how many
        times one of them was installed or removed (while the count
        stays, none of them moved) and their keys in LSDB order (the
        index itself: copy it to keep it)."""
        count, keys = self._origins.get((adv_rtr, ltype), (0, {}))
        return count, keys

    def maxage_keys(self, now: float) -> list[LsaKey]:
        return [
            k for k, e in self.entries.items() if e.current_age(now) >= MAX_AGE
        ]

    def refresh_due(self, now: float, self_rid: IPv4Address) -> list[LsaEntry]:
        return [
            e
            for e in self.entries.values()
            if e.lsa.adv_rtr == self_rid
            and not e.lsa.is_maxage
            and e.current_age(now) >= LS_REFRESH_TIME
        ]


class AgeScan:
    """Which entries of one LSDB are at least so old, without a walk
    over the LSDB per age tick: per entry the loop time at which its
    age was 0 (``installed_at - lsa.age``), in one array kept between
    calls.  ``Lsdb.install`` builds a new ``LsaEntry`` per install and
    nothing edits one in place, so what changed since the last call is
    found by identity, beside the kept list (all from the first
    difference on, where the length changed): the scheme of
    ``spf_run.LoweredLsdb``.  An age tick of a 5,000-entry area then
    costs one identity walk and one comparison on an array, where
    ``maxage_keys`` and ``refresh_due`` compute every entry's age in
    Python, twice."""

    def __init__(self) -> None:
        self._entries: list = []
        self._born = np.zeros(0, np.float64)

    def at_least(self, lsdb: Lsdb, now: float, age: int) -> list[LsaEntry]:
        """The entries with ``current_age(now) >= age``, LSDB order
        (``age`` at most MaxAge, where ``current_age`` stops)."""
        cur = list(lsdb.entries.values())
        kept = self._entries
        first = next(
            (i for i, same in enumerate(map(operator.is_, kept, cur))
             if not same),
            min(len(kept), len(cur)),
        )
        if first < len(cur) or len(kept) != len(cur):
            if len(kept) == len(cur):
                stale = [
                    first + i for i, same in enumerate(
                        map(operator.is_, kept[first:], cur[first:])
                    ) if not same
                ]
                self._born[stale] = [
                    cur[i].installed_at - cur[i].lsa.age for i in stale
                ]
            else:
                self._born = np.concatenate((self._born[:first], np.array(
                    [e.installed_at - e.lsa.age for e in cur[first:]],
                    np.float64,
                )))
            self._entries = cur
        # int(age + (now - installed_at)) >= age, for whole ages
        return [cur[i] for i in np.flatnonzero(now - self._born >= age)]


LsaBodyOffset = 20  # compare body beyond the 20-byte header (age/seq differ)


def next_seq_no(old: Lsa | None) -> int:
    if old is None:
        return INITIAL_SEQ_NO
    if old.seq_no >= MAX_SEQ_NO:
        # Sequence wrap requires premature aging first (§12.1.6); callers
        # flush then re-originate at INITIAL_SEQ_NO.
        return INITIAL_SEQ_NO
    return old.seq_no + 1
