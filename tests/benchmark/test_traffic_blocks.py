"""The storm cells' traffic: where a cell names a pool, every seed
injects the same blocks of events in another order; where it names
none, the traffic is drawn whole from the seed (``drivers/storm.py``)."""

import json
from pathlib import Path

import pytest

from benchmark.drivers import storm

REPO = Path(__file__).resolve().parents[2]
POOL, SIZE = 4, 5
SEEDS = [0, 5, 2147483653, 2**31 + 17]


class Recorder(storm.Driver):
    """The parent's block bookkeeping over a generator that only
    records what its streams draw."""

    def __init__(self, seed: int, pool: int | None = POOL):
        params = {"hot_epoch_events": SIZE} | ({"pool": pool} if pool else {})
        super().__init__({}, params, seed)
        self.keep = self._order_blocks()
        self.drawn: list = []

    def _inject(self) -> None:
        self.drawn.append(tuple(
            float(getattr(self, name).random()) for name in storm.STREAMS
        ))
        self.injected["lsa"] += 1

    def blocks_of(self, cycles: int) -> list:
        for _ in range(cycles * POOL * SIZE):
            self._event()
        return [
            tuple(self.drawn[at: at + SIZE])
            for at in range(0, len(self.drawn), SIZE)
        ]


@pytest.mark.parametrize("seed", SEEDS)
def test_every_seed_injects_the_same_blocks_in_its_own_order(seed):
    ref = Recorder(1)
    by_number = dict(zip(ref._order.tolist(), ref.blocks_of(1)))
    assert len(set(by_number.values())) == POOL
    mine = Recorder(seed)
    order, blocks = mine._order.tolist(), mine.blocks_of(2)
    assert sorted(order) == list(range(POOL))
    assert blocks[:POOL] == [by_number[number] for number in order]
    assert blocks[POOL:] == blocks[:POOL]  # the pool again, same order


@pytest.mark.parametrize("seed", SEEDS)
def test_a_cell_with_no_pool_draws_its_traffic_from_the_seed(seed):
    """As before PR 34, stream for stream: ``--seed`` spawns mix, loss,
    gap, the parity sample, pick and hot, and no block re-seeds them."""
    import numpy as np

    mine = Recorder(seed, pool=None)
    blocks = mine.blocks_of(1)
    mix, loss, gap, keep, pick, hot = np.random.default_rng(seed).spawn(6)
    want = [
        tuple(float(g.random()) for g in (mix, loss, gap, pick, hot))
        for _ in range(POOL * SIZE)
    ]
    assert [draw for block in blocks for draw in block] == want
    assert mine.keep.random() == keep.random()
    assert mine._block == POOL - 1  # hot epochs still turn with the blocks
    assert blocks != Recorder(seed + 1, pool=None).blocks_of(1)


def test_seeds_order_the_pool_differently_and_one_seed_repeats():
    orders = {tuple(Recorder(seed)._order) for seed in range(40)}
    assert len(orders) > 12  # of 24
    assert Recorder(7).blocks_of(1) == Recorder(7).blocks_of(1)
    assert Recorder(7).keep.random() == Recorder(7).keep.random()


def test_another_traffic_seed_is_other_traffic(monkeypatch):
    mine = Recorder(7).blocks_of(1)
    monkeypatch.setattr(storm, "TRAFFIC_SEED", storm.TRAFFIC_SEED + 1)
    assert not set(Recorder(7).blocks_of(1)) & set(mine)


def test_scripted_warm_up_events_count_towards_the_block():
    driver = Recorder(3)
    driver.injected["link"] += 4  # a subclass's script, nothing drawn
    driver._event()
    assert driver._block == 0
    driver._event()  # the sixth injected event: the second block
    assert driver._block == 1 and driver.injected.total() == 6


def test_a_cell_with_no_hot_epoch_gets_blocks_of_the_default_length():
    driver = storm.Driver({}, {"pool": 2}, 0)
    driver._order_blocks()
    assert driver._block_events == storm.BLOCK_EVENTS


def _storm_cells() -> list:
    """The cells of every convergence metric a storm driver reports."""
    from benchmark.drivers import areastorm, popstorm

    metrics = {kind.Driver.METRIC for kind in (storm, popstorm, areastorm)}
    top = json.loads((REPO / "BENCHMARK.json").read_text())
    return [
        cell for m in top["end_to_end"] if m["name"] in metrics
        for cell in m["workloads"]
    ]


@pytest.mark.parametrize("cell", _storm_cells())
def test_a_storm_cell_names_a_pool_or_nothing_of_the_blocks(cell):
    held = json.loads((REPO / f"benchmark/workloads/{cell}.json").read_text())
    params = held["params"]
    assert "blocks" not in params
    assert params.get("pool", 2) >= 2  # orders for the seed to draw


@pytest.mark.parametrize(
    "workload", ["tiny-storm", "tiny-ispstorm", "tiny-areastorm"]
)
def test_a_rehearsed_storm_goes_through_its_pool_in_the_seeds_order(workload):
    from benchmark import run

    cell = run.load_json("workloads", workload)
    result, rc = run.measure(
        cell, run.load_json("configs", cell["config"]),
        run.load_plugin("drivers", cell["driver"]), 2147483659, 0.5, False,
    )
    counts = result["counts"]
    assert rc == 3 and result["checks"]["parity"] and result["failed"] == 0
    pool = cell["params"].get("pool")
    order = counts["block_order"]
    assert order is None if pool is None else sorted(order) == list(range(pool))
    assert counts["blocks_begun"] >= 1
