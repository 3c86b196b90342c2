"""The fast-reroute deployment of ``isp-tilfa-storm``: the
configuration's ``generated`` block held to the generator, the plain
reference (``benchmark/frrref.py``) independent of the program and
equal to it, the served path (SR labels, backups through the RIB, the
bfd flip) and the cell's rehearsal with its altered-backup control, on
the CPU at small sizes."""

import ast
import json
from pathlib import Path

import numpy as np
import pytest

from benchmark import frrnet, frrref, run
from benchmark.drivers import frrstorm

REPO = Path(__file__).resolve().parents[2]
CONFIG = "ospf-isp-pop-10k-tilfa"


def _json(kind: str, name: str) -> dict:
    return json.loads((REPO / f"benchmark/{kind}/{name}.json").read_text())


def test_the_file_states_what_the_generator_makes():
    cfg = _json("configs", CONFIG)
    assert cfg["generated"] == json.loads(json.dumps(frrnet.generated(cfg)))


def test_the_deployment_and_its_cell_files():
    cfg = _json("configs", CONFIG)
    base = _json("configs", "ospf-isp-pop-10k")
    assert cfg["lsdb"] == base["lsdb"] and cfg["spf_delay"] == base["spf_delay"]
    assert cfg["reduced"] == [] and cfg["sr"] == {"srgb": [16000, 23999]}
    assert cfg["frr"] == {
        "enabled": True, "remote_lfa": True, "ti_lfa": True, "engine": "tpu",
        "node_protection": False, "srlg_disjoint": True,
    }
    gen = cfg["generated"]
    assert gen["routers"] == gen["loopbacks"] == gen["prefix_sid_lsas"] == 10_000
    assert gen["links"] == base["lsdb"]["links"] and gen["dut_interfaces"] == 12
    top = json.loads((REPO / "BENCHMARK.json").read_text())
    [entry] = [c for c in top["configs"] if c["name"] == CONFIG]
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    cell, zoo = _json("workloads", "isp-tilfa-storm"), _json("workloads", "isp-zoo-storm")
    assert cell["config"] == CONFIG and cell["driver"] == "frrstorm"
    assert {k: v for k, v in cell["params"].items() if k != "frr_dests"} == zoo["params"]
    assert cell["params"]["frr_dests"] >= 512
    [e2e] = [m for m in top["end_to_end"] if m["name"] == "trigger_fib_p50_ms"]
    assert "isp-tilfa-storm" in e2e["workloads"] and e2e["bound"] == 0.09


def test_the_reference_imports_nothing_of_the_program():
    tree = ast.parse((REPO / "benchmark/frrref.py").read_text())
    names = [
        alias.name for node in ast.walk(tree)
        if isinstance(node, ast.Import) for alias in node.names
    ] + [
        node.module or "" for node in ast.walk(tree)
        if isinstance(node, ast.ImportFrom)
    ]
    assert names and not any(
        n.startswith(("holo_tpu", "benchmark", "numpy", "jax")) for n in names
    )


def _petals(k, m, seed):
    from holo_tpu.ops.graph import Topology
    from holo_tpu.spf.synth import assign_direct_atoms

    rng = np.random.default_rng(seed)
    src, dst = [], []
    for p in range(k):
        ring = [0, *range(1 + p * m, 1 + (p + 1) * m)]
        for a, b in zip(ring, ring[1:] + ring[:1]):
            src += [a, b]
            dst += [b, a]
    topo = Topology(
        n_vertices=1 + k * m, is_router=np.ones(1 + k * m, bool),
        edge_src=np.array(src, np.int32), edge_dst=np.array(dst, np.int32),
        edge_cost=rng.integers(1, 4, len(src)).astype(np.int32), root=0,
    )
    assign_direct_atoms(topo)
    topo.edge_srlg = rng.integers(0, 5, topo.n_edges).astype(np.uint32)
    return topo


def _graphs():
    from holo_tpu.spf.synth import grid_topology, ring_topology

    return {
        "petals": _petals(4, 7, 3),
        "ring": ring_topology(16, max_cost=1),
        "grid": grid_topology(5, 6, max_cost=4, seed=2),
    }


@pytest.mark.parametrize("shape", ["petals", "ring", "grid"])
@pytest.mark.parametrize("srlg,nodeprot", [(False, False), (True, True)])
def test_reference_tables_equal_the_engine(shape, srlg, nodeprot):
    """LFA, remote LFA and TI-LFA picks of the device kernel against
    the reference, on graphs where remote repairs are needed."""
    from holo_tpu.frr.manager import FrrConfig, FrrEngine

    topo = _graphs()[shape]
    eng = FrrEngine("tpu")
    eng.set_policy(FrrConfig(
        enabled=True, engine="tpu", srlg_disjoint=srlg,
        node_protection=nodeprot,
    ))
    table = eng.compute(topo)
    policy = {"remote_lfa": True, "ti_lfa": True, "srlg_disjoint": srlg,
              "node_protection": nodeprot}
    ref = frrref.tables(frrstorm.plain_graph(topo), policy,
                        range(topo.n_vertices))
    assert {k[0] for k in ref} == set(range(table.n_links))
    for key, want in ref.items():
        assert frrstorm.table_rows(table, *key) == want, key
    if shape != "grid":
        assert any(r[2] >= 0 or r[3] >= 0 for r in ref.values())


@pytest.fixture(scope="module")
def tiny():
    import jax

    jax.config.update("jax_platforms", "cpu")
    from benchmark import fabric

    cfg = _json("configs", "tiny-isp-tilfa")
    return cfg, frrnet.FrrNet(cfg, fabric.backend_of(cfg), 5.0)


def test_tiny_net_installs_the_reference_s_table_with_labels(tiny):
    cfg, net = tiny
    _count, opaque = net.area.lsdb.of_type(frrnet.LsaType.OPAQUE_AREA)
    prefix_sids = [k for k in opaque if int(k.lsid) >> 24 == 7]
    assert len(prefix_sids) == net.n_routers
    ref = frrref.routes(net.model(cfg))
    assert len(ref) == len(net.kernel.fib) > 0
    for prefix, (metric, hops, backups) in ref.items():
        nhs, _proto = net.kernel.fib[prefix]
        assert {(nh.ifname, nh.addr) for nh in nhs} == hops
        assert net.rib.routes[prefix].best().msg.metric == metric
        got = {
            (p.ifname, p.addr): ((b.ifname, b.addr), tuple(b.labels))
            for p, b in (net.kernel.backups.get(prefix) or {}).items()
        }
        assert got == {k: (v[1], v[2]) for k, v in backups.items()}
    assert net.inst.sr_labels  # every loopback's node SID resolved


def test_bfd_down_flips_to_the_backups(tiny):
    from holo_tpu import telemetry

    _cfg, net = tiny
    ifname, addr = net.hops()[net.g0]
    riding = [
        p for p, (nhs, _proto) in net.kernel.fib.items()
        if {(nh.ifname, nh.addr) for nh in nhs} == {(ifname, addr)}
        and net.kernel.backups.get(p)
    ]
    assert riding
    flips0 = telemetry.snapshot("holo_rib_backup_flips_total").get(
        "holo_rib_backup_flips_total", 0.0)
    net.bfd(net.g0, "down")
    net.loop.run_until_idle() if hasattr(net.loop, "run_until_idle") else None
    nhs, _proto = net.kernel.fib[riding[0]]
    assert (ifname, addr) not in {(nh.ifname, nh.addr) for nh in nhs}
    assert telemetry.snapshot("holo_rib_backup_flips_total")[
        "holo_rib_backup_flips_total"] > flips0
    net.bfd(net.g0, "up")
    net.loop.advance(30.0)


def _measure(seed: int, alter=None, monkeypatch=None):
    if alter is not None:
        from holo_tpu.frr import manager

        real = manager.FrrEngine._finish_tpu

        def altered(self, handle):
            table = real(self, handle)
            alter(table)
            return table

        monkeypatch.setattr(manager.FrrEngine, "_finish_tpu", altered)
    cell = _json("workloads", "tiny-tilfastorm")
    cfg = _json("configs", cell["config"])
    return run.measure(cell, cfg, run.load_plugin("drivers", "frrstorm"),
                       seed, 3.0, False)


def test_rehearsal_holds_all_three_parity_checks():
    result, rc = _measure(2147483647)
    assert rc == run.RC_NO_CHIP
    compared = result["compared"]
    assert {k: v for k, v in compared.items() if v[0]} == {"chips_missing": [1, 0]}
    counts = result["counts"]
    assert counts["frr_links"] == 12
    assert counts["frr_last_dispatch"]["unmatched_links"] == 0


def _drop_one_lfa(table):
    """One backup entry changed where the engine produced it: the first
    destination's LFA on the first link, gone."""
    l = 0
    d = int(np.flatnonzero(table.lfa_adj[l] >= 0)[0])
    table.lfa_adj = table.lfa_adj.copy()  # the readback is read-only
    table.lfa_adj[l, d] = -1


def test_an_altered_backup_entry_is_not_correct(monkeypatch):
    result, rc = _measure(2147483647, _drop_one_lfa, monkeypatch)
    assert rc == run.RC_NO_CHIP
    assert result["compared"]["parity_mismatches"][0] >= 1
    assert result["compared"]["parity_not_ok"][0] == 1


def test_the_srlg_policy_moves_repairs_in_the_rehearsal():
    """The cell's DUT groups share no risk (its ``generated`` block:
    ``pairs_moved_by_srlg`` 0); the rehearsal's e0 and e1 share group 4,
    so there the mask excludes candidates and the parity checks see it."""
    assert _json("configs", CONFIG)["generated"]["pairs_moved_by_srlg"] == 0
    assert frrnet.generated(
        _json("configs", "tiny-isp-tilfa")
    )["pairs_moved_by_srlg"] > 0


@pytest.mark.parametrize("a,b", [
    ((33,), (1,)), ((1, 5), (2,)), ((0,), (32, 7)), ((), (3,)), ((4,), (4,)),
])
def test_the_reference_folds_group_ids_as_the_program_does(a, b):
    from holo_tpu.protocols.ospf.spf_run import srlg_bits

    assert frrref._shared(a, b) == bool(srlg_bits(a) & srlg_bits(b))
