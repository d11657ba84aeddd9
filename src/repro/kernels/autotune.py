"""Deterministic tile-config autotuner for the Pallas vertex-update kernels.

The engine binds a (tile_v, tile_e) choice into the Pallas backend at
trace time (``engine._autotuned``), so the choice MUST be a pure function
of the graph's shape statistics -- no wall-clock probing, no device
state.  A cost model is enough here because the kernel's behaviour is
simple and fully determined by the tiling:

  * compute: each (tile, chunk) grid step does two one-hot matmuls,
    ``2 * tile_e * (tile_v + k_pad)`` MACs, over ``T * C`` steps with
    ``e_pad = T * C * tile_e`` padded edge slots -- so larger tiles waste
    flops on padding, smaller tiles waste them on ragged chunks;
  * memory: the edge stream (src_local, dst_label, w = 12 B/edge slot)
    plus the (padded_v, k_pad) tie-noise block; the fused megakernel
    never writes the score matrix, so there is no V*k term beyond noise;
  * dispatch: a fixed per-grid-step overhead, which is what actually
    penalizes tiny tiles on ragged degree distributions.

Chunk counts come from the same round-robin degree balancing the real
tiling uses (``graph.round_robin_perm`` semantics), so ``e_pad`` here
matches ``build_tiled_csr`` exactly for the single-tiling path.

Choices are memoized on ``(V, E, k_pad, ndev)``: the first graph of a
session shape bucket decides, and every same-bucket rebind reuses the
choice -- a warm ``PartitionSession.adapt()`` can never flip tile config
mid-session (the autotune-determinism CI check relies on this).
"""
from __future__ import annotations

from typing import Tuple

import numpy as np

from .peaks import device_peaks

# (tile_v, tile_e) sweep; tile_v multiples of 8 (f32 sublane), tile_e is
# the chunk edge count. 128 lanes keeps every operand MXU/VPU aligned.
CANDIDATES = ((128, 128), (128, 256), (128, 512),
              (256, 128), (256, 256))

GRID_STEP_OVERHEAD_S = 5e-7   # per-step dispatch/pipeline bubble (model)

_CHOICE_CACHE: dict = {}


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _padded_edges(deg: np.ndarray, tile_v: int, tile_e: int,
                  min_total: int = 0) -> Tuple[int, int, int]:
    """(T, C, e_pad) after round-robin degree balancing, matching
    ``build_tiled_csr``'s chunk geometry for this degree sequence.

    ``min_total`` mirrors the build's ``min_total_slots`` floor: under
    the bucketed session layout every tiling reserves at least the edge
    bucket's worth of slots so the delta-merge append region survives
    retiling, and the model must charge for those slots too.
    """
    V = int(deg.shape[0])
    T = max(1, -(-V // tile_v))
    if V <= tile_v:
        counts = np.array([deg.sum()], dtype=np.int64)
    else:
        d = np.sort(deg.astype(np.int64))[::-1]
        counts = np.zeros(T, dtype=np.int64)
        np.add.at(counts, np.arange(V, dtype=np.int64) % T, d)
    C = max(1, -(-int(counts.max()) // tile_e))
    if min_total:
        C = max(C, -(-int(min_total) // (T * tile_e)))
    return T, C, T * C * tile_e


def _shard_cost(deg: np.ndarray, tile_v: int, tile_e: int,
                k_pad: int, min_total: int = 0) -> float:
    T, C, e_pad = _padded_edges(deg, tile_v, tile_e, min_total)
    padded_v = T * tile_v
    flops = 2.0 * e_pad * (tile_v + k_pad)      # two one-hot matmuls
    hbm = e_pad * 12.0 + padded_v * k_pad * 4.0  # edge stream + noise
    peaks = device_peaks()
    return (flops / peaks.flops + hbm / peaks.hbm_bytes_s
            + T * C * GRID_STEP_OVERHEAD_S)


def _shard_degrees(graph, ndev: int):
    """Per-shard REAL entry counts (weight-0 filler never gets tiled)."""
    src = np.asarray(graph.src)
    w = np.asarray(graph.weight)
    deg = np.bincount(src[w > 0], minlength=graph.num_vertices
                      ).astype(np.int64)
    if ndev <= 1:
        return [deg]
    v_local = -(-deg.shape[0] // ndev)
    return [deg[p * v_local:(p + 1) * v_local] for p in range(ndev)]


def _min_total(graph, ndev: int) -> int:
    # the single-tiling build floors its slot count to the padded entry
    # count (the delta append region); the per-shard build does not
    return int(np.asarray(graph.src).shape[0]) if ndev <= 1 else 0


def sweep(graph, k: int, ndev: int = 1) -> list:
    """All candidate costs (modeled seconds/iteration, max over shards)."""
    k_pad = round_up(max(k, 1), 128)
    shards = _shard_degrees(graph, ndev)
    min_total = _min_total(graph, ndev)
    rows = []
    for tile_v, tile_e in CANDIDATES:
        cost = max(_shard_cost(d, tile_v, tile_e, k_pad, min_total)
                   for d in shards)
        T, C, e_pad = _padded_edges(shards[0], tile_v, tile_e, min_total)
        rows.append({"tile_v": tile_v, "tile_e": tile_e, "k_pad": k_pad,
                     "cost_s": cost, "grid": T * C, "e_pad": e_pad})
    return rows


def choose_tile_config(graph, k: int, ndev: int = 1
                       ) -> Tuple[int, int, int]:
    """(tile_v, tile_e, k_pad) minimizing the modeled per-iteration cost.

    Deterministic: strict ``<`` comparison with ties broken by CANDIDATES
    order, and the result memoized on the graph's (V, E, k_pad, ndev).
    """
    k_pad = round_up(max(k, 1), 128)
    key = (int(graph.num_vertices), int(np.asarray(graph.src).shape[0]),
           k_pad, int(ndev))
    hit = _CHOICE_CACHE.get(key)
    if hit is not None:
        return hit
    best, best_cost = CANDIDATES[0], float("inf")
    shards = _shard_degrees(graph, ndev)
    min_total = _min_total(graph, ndev)
    for tile_v, tile_e in CANDIDATES:
        cost = max(_shard_cost(d, tile_v, tile_e, k_pad, min_total)
                   for d in shards)
        if cost < best_cost:
            best, best_cost = (tile_v, tile_e), cost
    choice = (best[0], best[1], k_pad)
    _CHOICE_CACHE[key] = choice
    return choice


def modeled_traffic(padded_v: int, e_pad: int, k_pad: int
                    ) -> Tuple[dict, dict]:
    """(split, fused) per-iteration HBM byte models for the update.

    The split path materializes the (padded_v, k_pad) score matrix in HBM
    (kernel write) and immediately re-reads it for the XLA
    normalize/argmax chain; the fused megakernel keeps that block in VMEM,
    so exactly those two V*k terms disappear.  The tie-noise block is
    charged identically to both (write at draw + read at use) -- the fused
    row permute fuses into the consuming kernel's gather.
    """
    vk = padded_v * k_pad * 4.0
    edge = e_pad * 12.0                 # src_local + dst_label + w
    split = {"edge_stream": edge, "noise": 2.0 * vk,
             "score_write": vk, "score_read": vk}
    fused = {"edge_stream": edge, "noise": 2.0 * vk}
    return split, fused
