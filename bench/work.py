"""The algorithm's work per step, counted from (V, E, k) alone.

These counts are the yardstick of the roofline shares.  They count what the
algorithm must touch, whatever implements it (XLA scatter or Pallas tiles,
a split or a fused score kernel), so a later change to a kernel cannot move
them.  Intermediates that an implementation may avoid -- a (V, k) score
matrix, a (V, k) tie-break noise array -- are not counted.

E is the number of directed adjacency entries (each undirected edge twice),
V the number of vertices, k the number of partitions.  int32 ids and float32
weights: 4 bytes each.

One Spinner LPA iteration (ComputeScores + ComputeMigrations):
  bytes = E * (4 src + 4 dst + 4 weight)      the edge list, read once
        + E * 4                               one neighbour label gathered
        + V * (4 + 4)                         labels read and written
        + V * 4                               weighted degree read
  flops = E                                   one add per entry into its
                                              (vertex, label) score
        + 3 * V * k                           normalise, penalise and
                                              compare each (vertex, label)
"""
from __future__ import annotations

B = 4    # bytes of an int32 id or a float32 weight


def lpa_iteration(V: int, E: int, k: int) -> dict:
    return {"bytes": E * (3 * B) + E * B + V * (2 * B) + V * B,
            "flops": E + 3 * V * k}


def least_seconds(work: dict, peaks: dict) -> tuple:
    """(seconds, bound): the least time the chip needs for ``work``, the
    larger of its bytes over HBM bandwidth and its flops over peak."""
    t_bytes = work["bytes"] / peaks["hbm_bytes_s"]
    t_flops = work["flops"] / peaks["flops"]
    return (t_bytes, "hbm") if t_bytes >= t_flops else (t_flops, "flops")
