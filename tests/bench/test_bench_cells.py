"""Every cell's set-up, window and check at a size a CPU test holds, driven
through the harness (the command itself refuses a CPU)."""
import os

import numpy as np
import pytest

import bench_testkit as kit
import base
import harness

CELLS = kit.cells()


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_correct_at_tiny_size(name):
    cell = kit.tiny_cell(name)
    res = kit.run_tiny(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in cell["end_to_end"]}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == set(cell["limits"])
    if cell["traffic"]["driver"] == "partition":     # whole passes
        assert res["attempted"] % cell["traffic"]["jobs"] == 0


@pytest.mark.parametrize("name", CELLS)
def test_traced_run_reports_only_what_it_can_read(name, tmp_path):
    cell = kit.tiny_cell(name)
    res = kit.run_tiny(cell, traced=True, tmp_path=tmp_path)
    # a CPU trace has no device plane: device metrics are left out, and
    # counters read from the program stay
    allowed = {m["name"] for m in cell["per_layer"]
               if m["source"] == "program_counter"}
    assert set(res["metrics"]) <= allowed
    assert res["device"]["busy_s"] == 0.0 and "breakdown" not in res
    assert not os.path.exists(tmp_path / "trace")


def test_judge_takes_the_worst_answer_and_counts_failures():
    checks, failed = harness.judge(
        [{"a": 0.0, "b": 1.0}, {"a": 2.0, "b": float("inf")}],
        {"a": 1.0, "b": 5.0, "c": 0.0})
    assert checks == {"a": {"value": 2.0, "limit": 1.0},
                      "b": {"value": None, "limit": 5.0},
                      "c": {"value": None, "limit": 0.0}}
    assert failed == 1


def test_partition_jobs_are_fixed_and_the_seed_orders_them():
    cell = kit.tiny_cell("ws1m.partition")

    def make(seed):
        d = base.find("drivers", "partition").Driver(
            cell["config"], cell["traffic"], seed)
        d.n = 1000
        return d

    a, b = make(kit.SEED), make(kit.SEED + 1)
    assert np.array_equal(a.random_labels(1), b.random_labels(1))
    assert not np.array_equal(a.random_labels(1), a.random_labels(2))
    orders = lambda d: [d.order.permutation(8).tolist() for _ in range(4)]
    assert orders(make(kit.SEED)) == orders(make(kit.SEED))
    assert orders(make(kit.SEED)) != orders(make(kit.SEED + 1))


def test_find_names_the_missing_piece():
    with pytest.raises(KeyError, match="no generators 'erdos_renyi'"):
        base.find("generators", "erdos_renyi")
    assert base.find("drivers", "partition") is base.find("drivers",
                                                          "partition")
