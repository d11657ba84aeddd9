"""The configurations' graph generators."""
import numpy as np
import pytest

import bench_testkit  # noqa: F401  (puts bench/ on the path)
import base

kron = base.find("generators", "graph500_kronecker")
ws = base.find("generators", "watts_strogatz")

INITIATOR = (0.57, 0.19, 0.19, 0.05)


def test_kronecker_quadrant_frequencies_match_the_initiator():
    scale, m = 8, 200_000
    src, dst = kron.kronecker_edges(scale, m, INITIATOR,
                                      np.random.default_rng(5))
    for level in (0, scale // 2, scale - 1):
        row, col = (src >> level) & 1, (dst >> level) & 1
        freq = [np.mean((row == r) & (col == c))
                for r, c in ((0, 0), (0, 1), (1, 0), (1, 1))]
        # binomial standard error at m = 200k is below 1.2e-3
        np.testing.assert_allclose(freq, INITIATOR, atol=6e-3)


def test_graph500_counts_and_permutation():
    scale, ef = 10, 16
    src, dst = kron.graph500_kronecker(scale, ef, INITIATOR,
                                         np.random.default_rng(6))
    assert src.shape == dst.shape == (ef << scale,)
    assert src.dtype == np.int32 and 0 <= src.min() and src.max() < 1 << scale
    # unpermuted, vertex 0 (all-zero bits) is the heaviest hub; the seeded
    # permutation moves it elsewhere almost surely
    deg = np.bincount(np.concatenate([src, dst]), minlength=1 << scale)
    assert deg.argmax() != 0
    again = kron.graph500_kronecker(scale, ef, INITIATOR,
                                      np.random.default_rng(6))
    assert np.array_equal(src, again[0]) and np.array_equal(dst, again[1])


def test_watts_strogatz_counts():
    n, k = 1000, 16
    src, dst = ws.watts_strogatz(n, k, 0.3, np.random.default_rng(7))
    assert src.size == n * k and not np.any(src == dst)
    ring = (dst - src) % n
    assert 0.6 < np.mean((ring >= 1) & (ring <= k)) < 0.8   # ~70% unwired


@pytest.mark.parametrize("bad", [(0.5, 0.5, 0.5, -0.5), (0.3, 0.3, 0.3, 0.3)])
def test_kronecker_rejects_a_non_distribution(bad):
    with pytest.raises(ValueError, match="distribution"):
        kron.kronecker_edges(4, 10, bad, np.random.default_rng(0))
