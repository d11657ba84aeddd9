"""Graph500 Kernel 1 Kronecker graph, vectorised numpy.

A configuration names this generator with ``"generator":
"graph500_kronecker"`` and gives ``scale``, ``edgefactor`` and the
``initiator`` (A, B, C, D).  The raw edges go to the system as a user would
hand them over; the system and the plain reference each build their own
weighted graph from them.
"""
from __future__ import annotations

import numpy as np


def kronecker_edges(scale: int, m: int, initiator: tuple,
                    rng: np.random.Generator) -> tuple:
    """``m`` edges of the recursive (R-MAT) Kronecker generator, before
    the vertex permutation.

    Per edge and per bit level, the quadrant (row bit, column bit) is
    (0, 0), (0, 1), (1, 0) or (1, 1) with probabilities A, B, C, D.  The
    Graph500 reference ``kronecker_generator`` draws the row bit first (1
    with probability C + D) and then the column bit given it; one uniform
    draw against the cumulative (A, A + B, A + B + C) gives the same joint
    law at half the draws.
    """
    a, b, c, d = (float(x) for x in initiator)
    if abs(a + b + c + d - 1.0) > 1e-9 or min(a, b, c, d) < 0:
        raise ValueError(f"initiator must be a distribution, got {initiator}")
    if not 0 < scale < 31:
        raise ValueError(f"scale must be in 1..30, got {scale}")
    t_a, t_ab, t_abc = (np.float32(x) for x in (a, a + b, a + b + c))
    src = np.zeros(m, np.int32)
    dst = np.zeros(m, np.int32)
    for level in range(scale):
        u = rng.random(m, dtype=np.float32)
        row = u >= t_ab
        col = ((u >= t_a) & ~row) | (u >= t_abc)
        src |= row.astype(np.int32) << level
        dst |= col.astype(np.int32) << level
    return src, dst


def graph500_kronecker(scale: int, edgefactor: int, initiator: tuple,
                       rng: np.random.Generator) -> tuple:
    """Graph500 Kernel 1 input: ``edgefactor * 2**scale`` Kronecker edges
    whose vertex ids are relabelled by a seeded random permutation, in a
    shuffled order, as the specification requires.  Self-loops and
    duplicates are kept: the graph builder drops them, as Kernel 1 may."""
    src, dst = kronecker_edges(scale, edgefactor << scale, initiator, rng)
    perm = rng.permutation(1 << scale).astype(np.int32)
    order = rng.permutation(src.size)
    return perm[src[order]], perm[dst[order]]


def build(spec: dict, rng: np.random.Generator) -> tuple:
    """``(num_vertices, src, dst, directed)`` of a configuration."""
    src, dst = graph500_kronecker(spec["scale"], spec["edgefactor"],
                                  tuple(spec["initiator"]), rng)
    return 1 << spec["scale"], src, dst, False
