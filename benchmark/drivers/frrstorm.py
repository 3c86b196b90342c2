"""The storm of ``drivers/popstorm.py`` on the same backbone run as an
SR-MPLS IGP with fast reroute armed (``benchmark/frrnet.py``): every SPF
run of the DUT also computes the backup tables of all its protected
links (``FrrEngine`` inline, before the routes are published), attaches
them to the routes and installs them beside the primaries; a bfd or
carrier event flips to them in the RIB.  The traffic, the window loop,
the wall stamps and the result keys are popstorm's.

``verify`` adds to popstorm's parity:

(i) for each of a seeded sample of ``parity_samples`` window FRR
    dispatches, the backup tables of every protected link over a seeded
    sample of ``frr_dests`` destinations against ``frrref.tables`` on
    the dispatch's own topology;
(ii) the settled FIB digest now covers each prefix's backups, and it
    must not move under a forced full run of the scalar SPF backend
    with the scalar FRR oracle;
(iii) the settled FIB, prefix for prefix — metric, primary next hops,
    and for each protected one the repair kind, the release next hop
    and the label stack — against ``frrref.routes`` on the generator's
    own link model;

and requires the FRR engine's compile counter unmoved in the window,
its breaker closed and no FRR dispatch served by the oracle.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import sys
from collections import Counter

import numpy as np

from benchmark import fabric, frrref, parity
from benchmark.drivers import popstorm
from benchmark.frrnet import FrrNet

#: destinations sampled per FRR dispatch the parity check recomputes
FRR_DESTS = 512


def fib_digest(kernel) -> str:
    """Digest of a kernel FIB with each prefix's backups."""
    text = json.dumps(sorted(
        (str(p), str(entry), str(sorted(
            (str(k), str(v)) for k, v in kernel.backups.get(p, {}).items()
        )))
        for p, entry in kernel.fib.items()
    ))
    return hashlib.sha256(text.encode()).hexdigest()


def plain_graph(topo) -> dict:
    """A dispatch's topology as ``frrref`` reads it: plain lists, an
    edge's SRLG mask as the ids of its set bits.  The edges are the
    program's lowering of the LSDB the DUT held at the dispatch (the
    generator's model cannot give it: lost and dropped LSAs leave that
    LSDB behind the model); (iii) is the check that lowers nothing of
    the program's."""
    return {
        "n": int(topo.n_vertices), "root": int(topo.root),
        "routers": topo.is_router.tolist(),
        "edges": list(zip(
            topo.edge_src.tolist(), topo.edge_dst.tolist(),
            topo.edge_cost.tolist(), topo.edge_direct_atom.tolist(),
            [tuple(i for i in range(32) if m >> i & 1)
             for m in topo.edge_srlg.tolist()],
        )),
    }


def table_rows(table, link: int, d: int) -> tuple:
    """One (protected link, destination) of a ``BackupTable`` in the
    form of ``frrref.tables``."""
    a = int(table.lfa_adj[link, d])
    nbr = int(table.inputs.adj_nbr[a]) if a >= 0 else -1
    return (
        nbr, bool(table.lfa_nodeprot[link, d]), int(table.rlfa_pq[link, d]),
        int(table.tilfa_p[link, d]), int(table.tilfa_q[link, d]),
        int(table.post_dist[link, d]),
    )


class Driver(popstorm.Driver):
    def set_up(self) -> None:
        from holo_tpu.frr import kernel

        if not hasattr(kernel, "rows_block"):
            # A program whose FRR kernel computes the all-roots distance
            # matrix would hold a [N, N] plane and relax it from every
            # vertex: at 10,000 it neither fits nor ends in a run's time.
            # Exit 2, as run.py does where the files do not fit together.
            print(
                "benchmark: this program's FRR kernel has no blocked distance "
                "rows (holo_tpu.frr.kernel.rows_block): it cannot run this cell",
                file=sys.stderr, flush=True,
            )
            raise SystemExit(2)
        params = self.params
        self.backend = fabric.backend_of(self.config)
        self.net = FrrNet(self.config, self.backend, params["rxmt_delay_s"])
        keep = self._order_blocks()
        self.kept_frr = parity.Reservoir(
            int(params["parity_samples"]),
            np.random.default_rng([self.seed, len(popstorm.KINDS)]),
        )
        self._frr_pick = np.random.default_rng([self.seed, 1 + len(popstorm.KINDS)])
        self._edges = np.cumsum([params["mix"][k] for k in popstorm.KINDS[:-1]])
        rank = np.arange(1, params["hot_set"] + 1, dtype=float)
        self._zipf = rank ** -params["zipf_s"]
        self._zipf /= self._zipf.sum()
        self._hot_links, self._hot_epoch = None, -1
        self._bfd_down = self._carrier_down = False
        self.kept = parity.Reservoir(int(params["parity_samples"]), keep)
        self._sampling = False
        self._last_topo = None
        self.delta_ops: list[int] = []
        self.dispatch_walls: list[float] = []
        self._window = None
        self._wrap_compute()
        self._wrap_frr()
        self._arm()
        scripted = self._script()
        for event in scripted:
            event()
            self.net.loop.advance(popstorm.SCRIPT_GAP_S)
        for _ in range(int(params["warmup_events"]) - len(scripted)):
            self._event()
            self.net.loop.advance(self._next_gap())
        self._settle()
        self.warmup = Counter(self.injected)

    def _wrap_frr(self) -> None:
        """Keep (topology, table) of a seeded sample of the window's FRR
        dispatches for the reference."""
        engine = self.net.inst._frr_engine
        inner = engine.compute

        def compute(topo):
            table = inner(topo)
            if self._sampling:
                self.kept_frr.offer(lambda: (topo, table))
            return table

        engine.compute = compute  # shadows the method, like the SPF's

    def run(self, window) -> dict:
        from holo_tpu.frr.manager import REPAIR_KINDS

        self._window = window
        out = super().run(window)
        stats = [
            t.stats for t in self.net.inst.frr_tables.values()
            if t.stats is not None
        ]
        out["counts"].update(
            frr_links=sum(t.n_links for t in self.net.inst.frr_tables.values()),
            frr_last_dispatch=stats[-1] if stats else None,
            # (protected link, protectable destination) pairs of the
            # window's attaches, by the repair they got
            frr_pairs_by_kind={
                kind: int(window.counter_delta(
                    "holo_frr_repairs_total", f"kind={kind}"
                ))
                for kind in REPAIR_KINDS
            },
        )
        return out

    # -- parity, outside the window

    def _restore_dut(self) -> int:
        """Bring the DUT's bfd session and carrier back up, send again
        the Router-LSAs its LSDB dropped (``FrrNet.resync``) and settle:
        the reference models a DUT with every interface up that heard
        every router's last word.  Returns the LSAs sent again."""
        net = self.net
        if self._bfd_down:
            net.bfd(net.g0, "up")
            self._bfd_down = False
        if self._carrier_down:
            net.carrier("e1", operative=True)
            self._carrier_down = False
        self._settle()
        resent = net.resync()
        self._settle()
        return resent

    def _sampled_frr(self) -> dict:
        policy = self.net.model(self.config)["policy"]
        want = int(self.params.get("frr_dests", FRR_DESTS))
        bad, checked = [], 0
        for n, (topo, table) in enumerate(self.kept_frr.items):
            reached = (table.post_dist < frrref.INF).any(axis=0)
            reached[table.root] = False
            valid = np.flatnonzero(reached)
            dests = sorted(self._frr_pick.choice(
                valid, size=min(want, len(valid)), replace=False
            ).tolist())
            ref = frrref.tables(plain_graph(topo), policy, dests)
            links = {key[0] for key in ref}
            diff = [
                key for key, want_row in ref.items()
                if table_rows(table, *key) != want_row
            ]
            checked += 1
            if diff or len(links) != table.n_links:
                bad.append({
                    "sample": n, "differing": len(diff),
                    "first": [list(k) for k in diff[:4]],
                    "links": [len(links), table.n_links],
                })
        return {"checked": checked, "mismatches": bad}

    def _program_table(self) -> dict:
        """The settled FIB as ``frrref.routes`` states it, the kinds
        from the instance's backup tables."""
        from holo_tpu.frr.manager import REPAIR_KINDS, repair_plan

        net, inst = self.net, self.net.inst
        (aid, (st, _res)), = inst._spf_cache["area_results"].items()
        table = inst.frr_tables[aid]
        kind = repair_plan(table, inst.config.frr)[0]
        atom_of = {
            (a.ifname, a.addr): i for i, a in enumerate(st.atoms)
        }
        out = {}
        for prefix, (nhs, _proto) in net.kernel.fib.items():
            vertex = inst.routes[prefix].vertex
            backups = {}
            for pnh, bnh in (net.kernel.backups.get(prefix) or {}).items():
                link = table.inputs.atom_link[atom_of[(pnh.ifname, pnh.addr)]]
                backups[(pnh.ifname, pnh.addr)] = (
                    REPAIR_KINDS[int(kind[link, vertex])],
                    (bnh.ifname, bnh.addr), tuple(bnh.labels),
                )
            out[prefix] = (
                net.rib.routes[prefix].best().msg.metric,
                frozenset((nh.ifname, nh.addr) for nh in nhs), backups,
            )
        return out

    def verify(self) -> dict:
        from holo_tpu.spf.backend import ScalarSpfBackend

        net, inst = self.net, self.net.inst
        resent = self._restore_dut()
        scalar = parity.against_scalar(self.kept.items)
        frr = self._sampled_frr()
        engine = inst._frr_engine
        window = self._window
        frr_compiles = int(
            window.counter_delta("holo_frr_jit_compiles_total")
        ) if window is not None else 0
        from holo_tpu import telemetry

        fallbacks = int(sum(
            v for k, v in telemetry.snapshot(
                "holo_resilience_fallback_total"
            ).items() if "breaker=frr-dispatch" in k
        ))
        breaker = engine.breaker.snapshot()
        before = fib_digest(net.kernel)
        backups = sum(1 for p in net.kernel.fib if net.kernel.backups.get(p))
        ref = frrref.routes(net.model(self.config))
        got = self._program_table()
        differing = sorted(
            str(p) for p in set(ref) | set(got) if ref.get(p) != got.get(p)
        )
        # (ii): the scalar SPF and the scalar FRR oracle, one forced run
        inst.backend = ScalarSpfBackend()  # daemon/providers.py seam
        inst.config.frr = dataclasses.replace(inst.config.frr, engine="scalar")
        inst._schedule_spf()  # trigger-less: a full run
        net.loop.advance(float(self.params["settle_s"]))
        after = fib_digest(net.kernel)
        ok = (
            not scalar["mismatches"] and scalar["checked"] > 0
            and not frr["mismatches"] and frr["checked"] > 0
            and before == after and len(net.kernel.fib) > 0
            and not differing and backups > 0
            and frr_compiles == 0 and fallbacks == 0
            and breaker["state"] == "closed"
        )
        return {
            "ok": ok, "scalar": scalar, "frr": frr,
            "fib_digest": before, "fib_digest_scalar": after,
            "fib_routes": len(net.kernel.fib), "fib_with_backups": backups,
            "reference_routes": len(ref),
            "differing_from_reference": len(differing),
            "first_differing": differing[:4],
            "frr_window_compiles": frr_compiles, "frr_fallbacks": fallbacks,
            "frr_breaker": breaker["state"], "lsas_resent": resent,
        }
