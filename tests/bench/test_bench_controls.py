"""``correct`` comes out false under the control and under each planted
fault a cell can have, with the rest of a run -- set-up, window, reference
and judgement -- as in a normal run.  (One chip: no exchange between chips
to leave out.  No batch: the calls take whole graphs.)"""
import pytest

import base
import bench_testkit as kit
import controls


def _driver(name: str) -> str:
    return kit.harness.load_cell(name, kit.REPO)["traffic"]["driver"]


CASES = [(name, plant) for name in kit.cells()
         for plant in base.find("drivers", _driver(name)).PLANTS]


@pytest.mark.parametrize("name,plant", CASES)
def test_plant_makes_the_run_incorrect(name, plant):
    cell = kit.tiny_cell(name)
    with controls.plant(cell["traffic"]["driver"], plant):
        res = kit.run_tiny(cell, seconds=0.2)
    assert not res["correct"], res["checks"]
    assert res["failed"] >= 1


@pytest.mark.parametrize("name", kit.cells())
def test_state_unchanged_fails_the_committed_phi_limit(name):
    """A partition that returns its random start is caught by the limit
    the cell runs with, not only by the test size's: the share of local
    edges of a uniform labelling is near 1/k, far from a partition's."""
    cell = kit.tiny_cell(name, committed_limits=True)
    with controls.plant(cell["traffic"]["driver"], "state_unchanged"):
        res = kit.run_tiny(cell, seconds=0.2)
    phi = res["checks"]["phi_rel_gap"]
    assert phi["value"] > 2 * phi["limit"], res["checks"]


def test_unknown_plant_is_refused():
    with pytest.raises(ValueError, match="no plant"):
        with controls.plant("partition", "half_batch"):
            pass
