"""Watts-Strogatz directed ring lattice (Spinner paper, Section 5.2).

A configuration names this generator with ``"generator":
"watts_strogatz"`` and gives ``n``, ``out_neighbours`` and ``beta``.  The
raw arcs go to the system as a user would hand them over; the system and
the plain reference each build their own weighted graph from them.
"""
from __future__ import annotations

import numpy as np


def watts_strogatz(n: int, k_nbrs: int, beta: float,
                   rng: np.random.Generator) -> tuple:
    """Directed ring lattice, ``k_nbrs`` out-edges per vertex, a ``beta``
    share of targets rewired uniformly at random.  Self-loops left by
    rewiring move to the next vertex."""
    if not 0 < k_nbrs < n:
        raise ValueError(f"need 0 < k_nbrs < n, got {k_nbrs}, {n}")
    src = np.repeat(np.arange(n, dtype=np.int64), k_nbrs)
    dst = (src + np.tile(np.arange(1, k_nbrs + 1, dtype=np.int64), n)) % n
    rewire = rng.random(src.shape[0]) < beta
    dst[rewire] = rng.integers(0, n, size=int(rewire.sum()))
    loop = dst == src
    dst[loop] = (dst[loop] + 1) % n
    return src.astype(np.int32), dst.astype(np.int32)


def build(spec: dict, rng: np.random.Generator) -> tuple:
    """``(num_vertices, src, dst, directed)`` of a configuration."""
    src, dst = watts_strogatz(spec["n"], spec["out_neighbours"],
                              spec["beta"], rng)
    return spec["n"], src, dst, True
