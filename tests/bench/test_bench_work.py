"""The work counts behind the roofline shares, against hand counts."""
import pytest

import bench_testkit  # noqa: F401  (puts bench/ on the path)
import peaks
import reference
import work


@pytest.fixture()
def path3():
    # the undirected path 0 - 1 - 2, given as the arcs 0->1, 2->1
    return reference.RefGraph(3, [0, 2], [1, 1], directed=True)


def test_lpa_iteration_counts_by_hand(path3):
    V, E, k = path3.n, path3.src.size, 2
    assert (V, E) == (3, 4)
    # per entry: src, dst, weight (12 B) + the neighbour's label (4 B);
    # per vertex: label read and written (8 B) + weighted degree (4 B)
    hand_bytes = sum(12 + 4 for _ in range(E)) + sum(8 + 4 for _ in range(V))
    # one add per entry; normalise, penalise, compare per (vertex, label)
    hand_flops = E + sum(3 for _ in range(V) for _ in range(k))
    assert work.lpa_iteration(V, E, k) == {"bytes": hand_bytes,
                                           "flops": hand_flops}
    assert hand_bytes == 100 and hand_flops == 22


@pytest.mark.parametrize("count,bound", [
    ({"bytes": 819e9, "flops": 1.0}, "hbm"),
    ({"bytes": 1.0, "flops": 394e12}, "flops"),
])
def test_least_seconds_takes_the_larger_bound(count, bound):
    seconds, which = work.least_seconds(count, peaks.peaks("TPU v5 lite"))
    assert which == bound
    assert seconds == pytest.approx(1.0 if bound == "hbm" else 2.0)


def test_unknown_device_kind_raises():
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks("TPU v99")


def test_reference_graph_weights_follow_eq3():
    # 0->1 and 1->0 make weight 2; 1->2 alone weight 1; a self-loop and a
    # duplicate arc count nothing
    g = reference.RefGraph(3, [0, 1, 1, 2, 1], [1, 0, 2, 2, 2], True)
    assert g.deg.tolist() == [2.0, 3.0, 1.0]
    assert g.total_weight == 6.0
    undirected = reference.RefGraph(3, [0, 1], [1, 0], False)
    assert undirected.deg.tolist() == [1.0, 1.0, 0.0]
