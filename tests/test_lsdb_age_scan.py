"""``lsdb.AgeScan``: the entries of an LSDB that are at least so old,
from ages kept between calls, against ``Lsdb.maxage_keys`` and
``Lsdb.refresh_due``, which compute every entry's age anew."""

from ipaddress import IPv4Address

import numpy as np
import pytest

from holo_tpu.protocols.ospf import packet_v3 as P
from holo_tpu.protocols.ospf.lsdb import LS_REFRESH_TIME, MAX_AGE, AgeScan, Lsdb

SELF = IPv4Address("10.0.0.1")


def _lsa(n: int, age: int, adv=None, seq: int = 1):
    lsa = P.Lsa(
        age, P.LsaType.INTER_AREA_PREFIX, IPv4Address(n),
        adv or IPv4Address((10 << 24) + 2 + n % 5), P.INITIAL_SEQ_NO + seq,
        P.LsaInterAreaPrefix(metric=n),
    )
    lsa.encode()
    return lsa


def _same(scan: AgeScan, db: Lsdb, now: float) -> None:
    old = scan.at_least(db, now, MAX_AGE)
    assert [e.lsa.key for e in old] == db.maxage_keys(now)
    due = [
        e for e in scan.at_least(db, now, LS_REFRESH_TIME)
        if e.lsa.adv_rtr == SELF and not e.lsa.is_maxage
    ]
    assert due == db.refresh_due(now, SELF)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_at_least_equals_the_walks_through_installs_removals_and_time(seed):
    rng = np.random.default_rng(seed)
    db, scan, now = Lsdb(), AgeScan(), 100.0
    for n in range(300):
        age = int(rng.choice([1, 900, LS_REFRESH_TIME - 3, MAX_AGE - 4, MAX_AGE]))
        db.install(_lsa(n, age, SELF if n % 7 == 0 else None), now)
    _same(scan, db, now)
    for step in range(40):
        now += float(rng.choice([0.5, 1.0, 2.5, 400.0]))
        kind = step % 4
        if kind == 0:  # a newer copy at the same place
            n = int(rng.integers(300))
            old = db.get(_lsa(n, 1, SELF if n % 7 == 0 else None).key)
            if old is not None:
                db.install(_lsa(n, 2, old.lsa.adv_rtr, seq=step + 2), now)
        elif kind == 1:  # gone from the middle: the length changes
            keys = list(db.entries)
            db.remove(keys[int(rng.integers(len(keys)))])
        elif kind == 2:  # a new entry at the end
            db.install(_lsa(1000 + step, MAX_AGE - 2, SELF), now)
        _same(scan, db, now)
    db.entries.clear()
    _same(scan, db, now)
    assert scan.at_least(db, now, 0) == []


def test_unchanged_lsdb_computes_no_age_in_python(monkeypatch):
    db, scan = Lsdb(), AgeScan()
    for n in range(50):
        db.install(_lsa(n, 1), 0.0)
    scan.at_least(db, 1.0, MAX_AGE)
    from holo_tpu.protocols.ospf.lsdb import LsaEntry

    monkeypatch.setattr(
        LsaEntry, "current_age",
        lambda *a: pytest.fail("an age was computed per entry"),
    )
    assert scan.at_least(db, 2.0, MAX_AGE) == []
    assert len(scan.at_least(db, MAX_AGE + 1.0, MAX_AGE)) == 50


def _old_age_tick(self) -> None:
    """``OspfV3Instance._age_tick`` and ``_sweep_maxage`` as they were
    before ISSUE 31: every entry's age computed in Python, twice a tick."""
    from holo_tpu.protocols.ospf.instance_v3 import AGE_TICK
    from holo_tpu.protocols.ospf.lsdb import next_seq_no

    now = self.loop.clock.now()
    for area in self.areas.values():
        ifaces = [
            i for i in self.interfaces.values() if self._area_of(i) is area
        ]
        dbs = [(area.lsdb, None)] + [(i.link_lsdb, i) for i in ifaces]
        for db, iface in dbs:
            for e in db.refresh_due(now, self.router_id):
                lsa = P.Lsa(
                    age=0, type=e.lsa.type, lsid=e.lsa.lsid,
                    adv_rtr=e.lsa.adv_rtr, seq_no=next_seq_no(e.lsa),
                    body=e.lsa.body,
                )
                lsa.encode()
                self._install_and_flood(area, lsa, from_iface=iface)
            for key in db.maxage_keys(now):
                e = db.get(key)
                if e is not None and not e.lsa.is_maxage:
                    self._install_and_flood(
                        area, self._maxage_copy(e.lsa), from_iface=iface
                    )
    if not self._any_nbr_exchanging():
        held = set()
        for iface in self.interfaces.values():
            for nbr in iface.neighbors.values():
                held |= set(nbr.ls_rxmt)
        dbs = [a.lsdb for a in self.areas.values()] + [
            i.link_lsdb for i in self.interfaces.values()
        ]
        for db in dbs:
            for key in [
                k for k, e in db.entries.items()
                if e.lsa.is_maxage and k not in held
            ]:
                db.remove(key)
    self._age_timer.start(AGE_TICK)


def _databases(net) -> dict:
    inst, now = net.inst, net.loop.clock.now()
    dbs = {f"area {int(a)}": area.lsdb for a, area in inst.areas.items()}
    dbs.update({f"link {n}": i.link_lsdb for n, i in inst.interfaces.items()})
    return {
        name: [
            (int(k.type), int(k.lsid), int(k.adv_rtr), e.lsa.seq_no,
             e.current_age(now))
            for k, e in db.entries.items()
        ]
        for name, db in dbs.items()
    }


def test_v3_age_tick_refreshes_expires_and_sweeps_as_the_old_walk_did():
    """Two equal networks through an hour and more of virtual time, one
    aged by the old body: the device's own LSAs refreshed at 1,800 s,
    everybody else's expired at 3,600 s, flooded as MaxAge and swept,
    the routes gone with them; the databases equal entry for entry, in
    order, at every stop."""
    import json
    import types
    from pathlib import Path

    from benchmark.areanet import AreaNet
    from holo_tpu.spf.backend import ScalarSpfBackend

    config = json.loads(
        (Path(__file__).parents[1] / "benchmark/configs/tiny-v3areas.json")
        .read_text()
    )
    nets = [
        AreaNet(config["lsdb"], ScalarSpfBackend(), config["spf_delay"], 5.0)
        for _ in range(2)
    ]
    nets[1].inst._age_tick = types.MethodType(_old_age_tick, nets[1].inst)
    own = nets[0].inst.router_id
    seqs = {
        k: e.lsa.seq_no
        for k, e in nets[0].inst.areas[IPv4Address(1)].lsdb.entries.items()
        if k.adv_rtr == own
    }
    for stop in (1700.0, 1810.0, 3500.0, 3620.0, 3700.0):
        for net in nets:
            net.loop.advance(stop - net.loop.clock.now())
        assert _databases(nets[0]) == _databases(nets[1]), stop
        assert nets[0].fib_table() == nets[1].fib_table(), stop
    db = nets[0].inst.areas[IPv4Address(1)].lsdb
    assert all(k.adv_rtr == own for k in db.entries)  # the rest expired
    assert all(db.entries[k].lsa.seq_no > s for k, s in seqs.items()
               if k in db.entries)
    assert len(nets[0].fib_table()) == 0
