"""Graph containers and preprocessing for Spinner.

The paper's Giraph substrate stores vertex objects with adjacency lists and
runs two supersteps (NeighborPropagation / NeighborDiscovery) to convert a
directed graph into the weighted undirected form of Eq. (3).  On TPU we adapt
this to a single vectorized symmetrization pass over a structure-of-arrays
COO edge list (sort packed canonical keys, count duplicates -> weight in
{1, 2}), producing a CSR-sorted symmetric representation that every other
module (LPA, Pregel engine, Pallas kernel) consumes.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import numpy as np

from . import trace


@dataclasses.dataclass(frozen=True)
class Graph:
    """Weighted undirected graph in symmetric COO form, CSR-sorted by src.

    Every undirected edge {u, v} appears twice: once as (u, v) and once as
    (v, u), both carrying the Eq. (3) weight w(u, v) in {1, 2}.  This makes
    per-vertex aggregation a pure segment operation over ``src``.

    ``dirs`` records which arcs of each pair were given, on the host only
    (no engine uploads it): one mask per pair, in the order of the pair's
    ``lo -> hi`` entries (``src < dst``, CSR order, which is the order of
    the pairs' keys ``lo * V + hi``), with bit 0 the arc lo -> hi and bit
    1 the arc hi -> lo; ``weight`` is its popcount.  An undirected input
    pair counts as its lo -> hi arc.  ``add_edges`` and the delta ledger
    (``repro.core.delta``) rebuild the exact arc set from it; graphs
    built other than from arcs (a padded view's filler, a relabelled
    copy) may leave it None.
    """

    num_vertices: int
    src: np.ndarray        # int32 (2*E_undirected,)  sorted ascending
    dst: np.ndarray        # int32 (2*E_undirected,)
    weight: np.ndarray     # float32 (2*E_undirected,)
    row_ptr: np.ndarray    # int64 (V+1,)  CSR offsets into src/dst/weight
    deg_w: np.ndarray      # float32 (V,)  weighted degree = sum of incident w
    dirs: Optional[np.ndarray] = None  # uint8 (E_undirected,) arc masks

    @property
    def num_directed_entries(self) -> int:
        return int(self.src.shape[0])

    @property
    def num_undirected_edges(self) -> int:
        return int(self.src.shape[0]) // 2

    @property
    def total_weight(self) -> float:
        """Sum of weighted degrees = 2 * (weighted undirected edge count)."""
        return float(self.deg_w.sum())

    def validate(self) -> None:
        assert self.src.shape == self.dst.shape == self.weight.shape
        assert self.row_ptr.shape == (self.num_vertices + 1,)
        assert np.all(np.diff(self.row_ptr) >= 0)
        assert self.src.size == 0 or (
            self.src.min() >= 0 and self.src.max() < self.num_vertices
        )
        # symmetry: the multiset of (dst, src) equals (src, dst)
        fwd = np.stack([self.src, self.dst]), self.weight
        key_f = self.src.astype(np.int64) * self.num_vertices + self.dst
        key_b = self.dst.astype(np.int64) * self.num_vertices + self.src
        assert np.array_equal(np.sort(key_f), np.sort(key_b)), "not symmetric"


def _dedupe(src: np.ndarray, dst: np.ndarray, num_vertices: int
            ) -> Tuple[np.ndarray, np.ndarray]:
    """Remove self-loops and exact duplicate directed edges."""
    keep = src != dst
    src, dst = src[keep], dst[keep]
    key = src.astype(np.int64) * num_vertices + dst.astype(np.int64)
    key = np.unique(key)
    return (key // num_vertices).astype(np.int32), (key % num_vertices).astype(np.int32)


def from_edges(src, dst, num_vertices: int, directed: bool = True) -> Graph:
    """Build the weighted undirected Graph per Eq. (3).

    w(u,v) = 2 if both (u,v) and (v,u) exist in the directed input, else 1.
    Undirected input gets w = 1 everywhere.  Runs in the span
    ``graph/from_edges`` (``repro.core.trace``).
    """
    with trace.span("graph/from_edges", vertices=int(num_vertices)) as at:
        graph = _from_edges(src, dst, num_vertices, directed)
        at["entries"] = graph.num_directed_entries
    return graph


def _from_edges(src, dst, num_vertices: int, directed: bool) -> Graph:
    src = np.asarray(src, dtype=np.int32)
    dst = np.asarray(dst, dtype=np.int32)
    if src.size:
        assert int(max(src.max(), dst.max())) < num_vertices
    src, dst = _dedupe(src, dst, num_vertices)

    # one sort of (pair, direction) keys gives each pair's weight and
    # which of its arcs were given (lo * V + hi < 2**62 for V <= 2**31)
    key = np.minimum(src, dst).astype(np.int64) * (2 * num_vertices)
    key += np.maximum(src, dst).astype(np.int64) * 2
    key += src > dst
    key.sort()
    pair = key >> 1
    first = np.ones(pair.size + 1, bool)
    first[1:-1] = pair[1:] != pair[:-1]
    starts = np.flatnonzero(first[:-1])
    both = ~first[starts + 1]                   # the pair's second arc
    uniq = pair[starts]
    u = (uniq // num_vertices).astype(np.int32)
    v = (uniq % num_vertices).astype(np.int32)
    if directed:
        w = both.astype(np.float32) + 1         # 1 = one direction, 2 = both
        mask = (key[starts] & 1).astype(np.uint8) + 1
        mask[both] = 3
    else:
        w = np.ones(uniq.size, np.float32)
        mask = np.ones(uniq.size, np.uint8)

    sym_src = np.concatenate([u, v])
    sym_dst = np.concatenate([v, u])
    sym_w = np.concatenate([w, w])
    return _finish(sym_src, sym_dst, sym_w, num_vertices, dirs=mask)


def _finish(src, dst, w, num_vertices: int,
            dirs: Optional[np.ndarray] = None) -> Graph:
    """The CSR graph of symmetric entries; ``dirs``, where given, is
    already in the order of the pairs' keys (see ``Graph``)."""
    order = np.lexsort((dst, src))
    src, dst, w = src[order], dst[order], w[order].astype(np.float32)
    counts = np.bincount(src, minlength=num_vertices).astype(np.int64)
    row_ptr = np.zeros(num_vertices + 1, dtype=np.int64)
    np.cumsum(counts, out=row_ptr[1:])
    deg_w = np.zeros(num_vertices, dtype=np.float32)
    np.add.at(deg_w, src, w)
    return Graph(num_vertices=num_vertices, src=src.astype(np.int32),
                 dst=dst.astype(np.int32), weight=w, row_ptr=row_ptr,
                 deg_w=deg_w, dirs=dirs)


def add_edges(graph: Graph, new_src, new_dst, directed: bool = True,
              num_vertices: Optional[int] = None) -> Graph:
    """Incremental growth (Section 3.4): returns the extended graph.

    The result is ``from_edges`` over the union of the arcs ``graph`` was
    built from (read back from ``graph.dirs``) and the new ones, so a
    chain of calls gives the Eq. (3) weights of all arcs given so far: a
    follow-back ``lo -> hi`` after ``hi -> lo`` makes weight 2, and an arc
    given again changes nothing.  ``num_vertices`` may exceed the old
    count to inject new vertices.
    """
    if graph.dirs is None:
        raise ValueError("graph records no arc directions (Graph.dirs); "
                         "build it with from_edges to add arcs to it")
    V = max(num_vertices or 0, graph.num_vertices,
            int(np.max(new_src) + 1) if len(new_src) else 0,
            int(np.max(new_dst) + 1) if len(new_dst) else 0)
    half = graph.src < graph.dst
    u, v, m = graph.src[half], graph.dst[half], graph.dirs
    fwd, rev = (m & 1) > 0, (m & 2) > 0
    src = np.concatenate([u[fwd], v[rev], np.asarray(new_src, np.int32)])
    dst = np.concatenate([v[fwd], u[rev], np.asarray(new_dst, np.int32)])
    return from_edges(src, dst, V, directed=directed)


def shape_bucket(n: int, floor: int = 64) -> int:
    """Power-of-two-ish rounding for compile-shape buckets.

    Returns the smallest value >= max(n, floor) of the form
    ``m * 2**(e-2)`` with mantissa m in {5, 6, 7, 8} (i.e. quarter steps
    between consecutive powers of two), so padding overhead is at most
    25% while graphs of similar size land in the same bucket and share
    one compiled executable (see ``repro.core.session``).  With the
    default floor every bucket is a multiple of 8, so the sharded
    engine's per-device split stays exact on 1/2/4/8-device meshes.
    """
    n = max(int(n), int(floor), 1)
    p = 1 << (n - 1).bit_length()          # smallest power of two >= n
    half = p // 2
    step = max(half // 4, 1)
    for m in range(1, 5):
        b = half + m * step                # half * {1.25, 1.5, 1.75, 2}
        if b >= n:
            return b
    return p


def pad_graph(graph: Graph, v_pad: int, e_pad: int) -> Graph:
    """Zero-padded view of ``graph`` with bucketed (V, E) compile shapes.

    Pad vertices are isolated (``deg_w`` 0); pad edge slots are
    weight-0 self-loops spread over the pad vertex range (or parked on
    the last vertex when V is already at its bucket), so every score
    backend treats them as exact no-ops: a scatter-add of 0.0 and a
    one-hot matmul against weight 0 both leave the real rows bit-equal.
    The engines mask the pad vertices out of migration and halting
    aggregates with a ``valid`` mask (see ``engine.make_vertex_update``),
    so pads never corrupt the result.  Note the tie-break PRNG draws over
    the PADDED vertex set, so the (equally valid, deterministic)
    trajectory depends on the bucket: bit-reproducibility holds across
    calls that share a padded layout -- which one-shot wrappers and
    sessions do by construction -- not across different buckets or
    ``pad="none"``.
    """
    V, E = graph.num_vertices, graph.num_directed_entries
    if v_pad < V or e_pad < E:
        raise ValueError(f"pad shapes ({v_pad}, {e_pad}) below graph "
                         f"shapes ({V}, {E})")
    if v_pad == V and e_pad == E:
        return graph
    extra = e_pad - E
    if extra and v_pad > V:
        pad_src = np.sort((np.arange(extra, dtype=np.int64)
                           % (v_pad - V)).astype(np.int32) + V)
    else:
        pad_src = np.full(extra, v_pad - 1, np.int32)
    src = np.concatenate([graph.src, pad_src])
    dst = np.concatenate([graph.dst, pad_src])
    w = np.concatenate([graph.weight, np.zeros(extra, np.float32)])
    counts = np.bincount(src, minlength=v_pad).astype(np.int64)
    row_ptr = np.zeros(v_pad + 1, dtype=np.int64)
    np.cumsum(counts, out=row_ptr[1:])
    deg_w = np.concatenate([graph.deg_w, np.zeros(v_pad - V, np.float32)])
    return Graph(num_vertices=v_pad, src=src, dst=dst, weight=w,
                 row_ptr=row_ptr, deg_w=deg_w)


def remove_vertices(graph: Graph, vertices) -> Graph:
    """Drop vertices (keeping ids stable) and their incident edges."""
    drop = np.zeros(graph.num_vertices, dtype=bool)
    drop[np.asarray(vertices)] = True
    keep = ~(drop[graph.src] | drop[graph.dst])
    return _finish(graph.src[keep], graph.dst[keep], graph.weight[keep],
                   graph.num_vertices, None if graph.dirs is None
                   else graph.dirs[keep[graph.src < graph.dst]])


# ---------------------------------------------------------------------------
# Tiled CSR for the Pallas kernel (see kernels/spinner_scores.py)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TiledCSR:
    """Edge chunks grouped by source-vertex tile, padded for the MXU.

    Layout: ``(num_vertex_tiles, max_chunks, tile_e)`` dense arrays.  A pad
    entry has weight 0 and src_local 0, so it contributes nothing.  Degree
    skew across tiles is reduced beforehand by interleaving vertices by
    degree rank (see ``build_tiled_csr``); the permutation is recorded so
    scores can be mapped back.
    """

    tile_v: int
    tile_e: int
    num_tiles: int
    max_chunks: int
    src_local: np.ndarray   # int32 (num_tiles, max_chunks, tile_e)
    dst: np.ndarray         # int32 (num_tiles, max_chunks, tile_e)
    weight: np.ndarray      # float32 (num_tiles, max_chunks, tile_e)
    perm: np.ndarray        # int32 (V,) original vertex -> tiled row
    inv_perm: np.ndarray    # int32 (V_pad,) tiled row -> original vertex (or -1)
    padded_v: int
    deg_t: np.ndarray = None  # f32 (num_tiles, tile_v) weighted degrees in
                              # tiled row order (0 on pad rows) -- the fused
                              # vertex-update kernel's per-tile deg_w view
    fill: np.ndarray = None   # int64 (num_tiles,) occupied slots per tile;
                              # slots [fill[t], max_chunks * tile_e) of tile
                              # t's flat region are weight-0 slack the delta
                              # merge may claim (see repro.core.delta)


def round_robin_perm(deg_w: np.ndarray, tile_v: int) -> np.ndarray:
    """Degree-balanced vertex -> tiled-row permutation.

    Round-robins vertices (sorted by weighted degree, descending) across
    ``ceil(V / tile_v)`` tiles so hub vertices spread out and per-tile edge
    counts even up; ``rank[i]`` (the i-th largest degree) lands at row
    ``(i % num_tiles) * tile_v + (i // num_tiles)``.  Exposed so the
    overlap split can tile the interior and frontier edge segments against
    ONE shared permutation (``ext_perm`` below) and hand the fused kernel a
    single per-tile degree/label/noise layout.
    """
    V = int(np.asarray(deg_w).shape[0])
    num_tiles = max(1, -(-V // tile_v))
    if V <= tile_v:
        return np.arange(V, dtype=np.int32)
    rank = np.argsort(-deg_w, kind="stable")
    # i // num_tiles <= (V-1) // num_tiles < tile_v, so no tile overflows.
    i = np.arange(V, dtype=np.int64)
    rows = np.empty(V, dtype=np.int64)
    rows[rank] = (i % num_tiles) * tile_v + (i // num_tiles)
    return rows.astype(np.int32)


def build_tiled_csr(graph: Graph, tile_v: int = 128, tile_e: int = 128,
                    balance_by_degree: bool = True,
                    pad_chunks: int = 1,
                    min_total_slots: int = 0) -> TiledCSR:
    return _tile_edge_arrays(graph.num_vertices, graph.src, graph.dst,
                             graph.weight, graph.deg_w, tile_v=tile_v,
                             tile_e=tile_e,
                             balance_by_degree=balance_by_degree,
                             pad_chunks=pad_chunks,
                             min_total_slots=min_total_slots)


def _tile_edge_arrays(V: int, src: np.ndarray, dst: np.ndarray,
                      weight: np.ndarray, deg_w: np.ndarray, *,
                      tile_v: int, tile_e: int,
                      balance_by_degree: bool, pad_chunks: int = 1,
                      ext_perm: Optional[np.ndarray] = None,
                      min_total_slots: int = 0
                      ) -> TiledCSR:
    """Tile a raw (src, dst, weight) edge list over ``V`` source rows.

    The core of ``build_tiled_csr``, shared with the per-shard tiling
    (``build_sharded_tiled_csr``), where ``dst`` carries exchange-plan
    lookup indices rather than vertex ids and therefore cannot live in a
    ``Graph`` (whose invariants demand symmetric edges with dst < V).

    ``ext_perm`` overrides the vertex -> tiled-row permutation, so two
    edge segments of the same vertex range (the overlap schedule's
    interior/frontier split) can share one row layout and their kernel
    outputs add without any re-permutation.

    Weight-0 entries (``pad_graph`` bucket filler) are dropped before
    packing: they contribute nothing to any score, and skipping them
    keeps every unused slot at the TAIL of its tile's flat region, so
    the per-tile slack is a contiguous append region the on-device delta
    merge can scatter new edges into.  ``min_total_slots`` floors the
    total slot count (num_tiles * max_chunks * tile_e), guaranteeing the
    layout carries at least the bucketed edge capacity in slack.
    """
    num_tiles = max(1, -(-V // tile_v))
    padded_v = num_tiles * tile_v

    if ext_perm is not None:
        perm = np.asarray(ext_perm, dtype=np.int32)
        assert perm.shape == (V,)
    elif balance_by_degree:
        perm = round_robin_perm(deg_w, tile_v)
    else:
        perm = np.arange(V, dtype=np.int32)

    inv_perm = np.full(padded_v, -1, dtype=np.int32)
    inv_perm[perm] = np.arange(V, dtype=np.int32)

    real = weight > 0
    if not real.all():
        src, dst, weight = src[real], dst[real], weight[real]

    new_src = perm[src]
    order = np.argsort(new_src, kind="stable")
    s = new_src[order]
    d = dst[order]                # dst stays in ORIGINAL ids (labels indexed)
    w = weight[order]

    tile_of = s // tile_v
    counts = np.bincount(tile_of, minlength=num_tiles)
    chunks_per_tile = np.maximum(1, -(-counts // tile_e))
    max_chunks = int(chunks_per_tile.max())
    if min_total_slots:
        floor_chunks = -(-int(min_total_slots) // (num_tiles * tile_e))
        max_chunks = max(max_chunks, floor_chunks)
    # pad_chunks > 1 rounds the chunk count up so the kernel's compile
    # shape stays stable as edges shift between tiles (session reuse)
    max_chunks = -(-max_chunks // pad_chunks) * pad_chunks

    src_local = np.zeros((num_tiles, max_chunks, tile_e), dtype=np.int32)
    dstA = np.zeros((num_tiles, max_chunks, tile_e), dtype=np.int32)
    wA = np.zeros((num_tiles, max_chunks, tile_e), dtype=np.float32)

    starts = np.zeros(num_tiles + 1, dtype=np.int64)
    np.cumsum(counts, out=starts[1:])
    for t in range(num_tiles):
        lo, hi = starts[t], starts[t + 1]
        n = hi - lo
        if n == 0:
            continue
        flat_sl = (s[lo:hi] - t * tile_v).astype(np.int32)
        flat_d = d[lo:hi]
        flat_w = w[lo:hi]
        nc = -(-n // tile_e)
        pad = nc * tile_e - n
        src_local[t, :nc].reshape(-1)[:n] = flat_sl
        dstA[t, :nc].reshape(-1)[:n] = flat_d
        wA[t, :nc].reshape(-1)[:n] = flat_w
        del pad
    deg_t = np.zeros(padded_v, dtype=np.float32)
    deg_t[perm] = np.asarray(deg_w[:V], dtype=np.float32)
    return TiledCSR(tile_v=tile_v, tile_e=tile_e, num_tiles=num_tiles,
                    max_chunks=max_chunks, src_local=src_local, dst=dstA,
                    weight=wA, perm=perm, inv_perm=inv_perm, padded_v=padded_v,
                    deg_t=deg_t.reshape(num_tiles, tile_v),
                    fill=counts.astype(np.int64))


@dataclasses.dataclass(frozen=True)
class ShardedTiledCSR:
    """Per-edge-shard tilings, stacked for ``shard_map`` (leading dim ndev).

    The sharded counterpart of ``TiledCSR``: each device's edge shard (see
    ``repro.core.distributed.ShardedGraph``) is tiled independently over
    its LOCAL vertex range, then padded to common (num_tiles, max_chunks)
    so the stacked arrays shard evenly over the mesh.  ``dst`` carries
    whatever index the exchange plan's lookup array expects (global vertex
    ids for all-gather/delta, halo-remapped slots for halo); pad entries
    have weight 0 and contribute nothing.
    """

    ndev: int
    tile_v: int
    tile_e: int
    num_tiles: int          # per shard (max across shards)
    max_chunks: int         # max across shards
    src_local: np.ndarray   # int32 (ndev, num_tiles, max_chunks, tile_e)
    dst: np.ndarray         # int32 (ndev, num_tiles, max_chunks, tile_e)
    weight: np.ndarray      # float32 (ndev, num_tiles, max_chunks, tile_e)
    perm: np.ndarray        # int32 (ndev, v_per_dev) local vertex -> tiled row
    inv_perm: np.ndarray = None  # int32 (ndev, num_tiles * tile_v) tiled row
                                 # -> local vertex (or -1 on pad rows)
    deg_t: np.ndarray = None     # f32 (ndev, num_tiles, tile_v) weighted
                                 # degrees in tiled row order (0 on pads)
    fill: np.ndarray = None      # int64 (ndev, num_tiles) occupied slots per
                                 # shard tile (tail slack = delta append room)


def build_sharded_tiled_csr(sg, dst_index: Optional[np.ndarray] = None,
                            tile_v: int = 128, tile_e: int = 128,
                            balance_by_degree: bool = True,
                            pad_chunks: int = 1,
                            ext_perm: Optional[np.ndarray] = None,
                            min_total_slots: int = 0
                            ) -> ShardedTiledCSR:
    """Retile a ``ShardedGraph``'s edge shards for the Pallas kernel.

    ``dst_index`` overrides the global destination ids (e.g. with an
    exchange plan's halo-remapped indices).  Each shard is tiled by
    ``build_tiled_csr`` over a per-shard view (local source ids, the
    shard's slice of the weighted degrees), so the kernel launched inside
    ``shard_map`` sees exactly the layout the single-device kernel does.
    ``ext_perm`` (``(ndev, v_per_dev)``) pins every shard's row
    permutation, letting two edge segments of one shard share a layout
    (see ``_tile_edge_arrays``).
    """
    ndev, vl = sg.ndev, sg.v_per_dev
    dsts = sg.dst if dst_index is None else np.asarray(dst_index)
    tiles = []
    for p in range(ndev):
        real = sg.weight[p] > 0
        tiles.append(_tile_edge_arrays(
            vl, sg.src_local[p][real].astype(np.int32),
            dsts[p][real].astype(np.int32),
            sg.weight[p][real].astype(np.float32), sg.deg_w[p],
            tile_v=tile_v, tile_e=tile_e,
            balance_by_degree=balance_by_degree, pad_chunks=pad_chunks,
            ext_perm=None if ext_perm is None else ext_perm[p],
            min_total_slots=min_total_slots))
    T = max(t.num_tiles for t in tiles)
    C = max(t.max_chunks for t in tiles)
    src_local = np.zeros((ndev, T, C, tile_e), np.int32)
    dstA = np.zeros((ndev, T, C, tile_e), np.int32)
    wA = np.zeros((ndev, T, C, tile_e), np.float32)
    perm = np.zeros((ndev, vl), np.int32)
    inv = np.full((ndev, T * tile_v), -1, np.int32)
    deg_t = np.zeros((ndev, T, tile_v), np.float32)
    fill = np.zeros((ndev, T), np.int64)
    for p, t in enumerate(tiles):
        src_local[p, : t.num_tiles, : t.max_chunks] = t.src_local
        dstA[p, : t.num_tiles, : t.max_chunks] = t.dst
        wA[p, : t.num_tiles, : t.max_chunks] = t.weight
        perm[p] = t.perm
        inv[p, : t.padded_v] = t.inv_perm
        deg_t[p, : t.num_tiles] = t.deg_t
        fill[p, : t.num_tiles] = t.fill
    return ShardedTiledCSR(ndev=ndev, tile_v=tile_v, tile_e=tile_e,
                           num_tiles=T, max_chunks=C, src_local=src_local,
                           dst=dstA, weight=wA, perm=perm, inv_perm=inv,
                           deg_t=deg_t, fill=fill)
