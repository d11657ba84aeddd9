"""On-device CSR delta merge: the ``adapt(edge_updates=...)`` fast path.

Spinner's operational pitch is cheap adaptation -- "efficiently adapts the
partitioning" upon graph changes (Section 3.4) -- but a naive adapt pays a
host-side O(E) rebuild (``graph.add_edges`` -> ``from_edges``) plus an
O(E) re-upload for ANY delta.  This module makes a warm delta cost
O(|delta| log E) on the host and O(|delta|) on the wire:

  * ``DeltaTracker`` -- the host-side pair ledger.  Built once per session
    graph (the one O(E) cold cost: a sorted canonical-pair key index over
    the base edge list), it keeps each pair's two-bit arc mask
    (``Graph.dirs``: which of lo -> hi and hi -> lo were given), folds
    each ``(src, dst)`` batch in as ``old mask | batch mask`` -- Eq. 3
    over the union of all arcs given, exactly as ``add_edges`` -- and
    emits the per-batch ``BatchPlan``: the symmetric weight-DELTA entries
    to append, the per-vertex degree increments, and the endpoints whose
    scores changed.
    Appended entries are PARALLEL edges carrying the weight delta; the
    integer Eq. 3 weights make every scatter-add sum exact, so a layout
    holding ``(u, v, 1)`` in a base slot and ``(u, v, 1)`` in a slack slot
    is score-for-score bit-identical to a rebuilt layout holding
    ``(u, v, 2)``.
  * ``DeviceDelta`` -- the session's resident merged arrays for one
    engine mode, plus the host slot bookkeeping over the layout's slack
    regions (``pad_graph``'s tail filler, the tiled CSR's per-tile tail
    slack, the sharded layout's per-segment tails).  ``plan_slots``
    assigns flat scatter indices for a batch (or reports slack overflow,
    upon which the session falls back to the bit-identical host rebuild)
    and ``apply_batch`` runs the engine's ``("delta_merge",)`` program --
    a shape-bucketed scatter, so every same-sized batch reuses one
    compiled entry and only O(|delta|) bytes cross the wire.

The session layer (``repro.core.session``) owns eligibility, fallback and
the oracle contract; this module is pure mechanism and is the coalescing
primitive the multi-tenant scheduler follow-on builds on.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import trace
from .graph import Graph, shape_bucket

# Batch arrays are padded to a bucketed length so every same-bucket batch
# shares one compiled merge entry; sentinel indices (== the target's flat
# size) are dropped by the scatter's mode="drop".
BATCH_FLOOR = 64


def check_edge_updates(src, dst, num_vertices: int,
                       new_num_vertices: Optional[int] = None
                       ) -> Tuple[np.ndarray, np.ndarray]:
    """Validate an ``edge_updates`` batch; returns int32 (src, dst).

    Rejects mismatched lengths, non-integer dtypes, negative ids and ids
    beyond the (possibly grown) vertex count with a clear ``ValueError``
    -- previously these flowed into the CSR build and either failed
    obscurely or silently grew the vertex set.
    """
    src = np.asarray(src)
    dst = np.asarray(dst)
    if src.ndim != 1 or dst.ndim != 1:
        raise ValueError(
            "edge_updates src/dst must be 1-D index arrays; got shapes "
            f"{src.shape} and {dst.shape}")
    if src.shape[0] != dst.shape[0]:
        raise ValueError(
            f"edge_updates src/dst length mismatch: {src.shape[0]} src "
            f"vs {dst.shape[0]} dst entries")
    for name, a in (("src", src), ("dst", dst)):
        if a.size and not np.issubdtype(a.dtype, np.integer):
            raise ValueError(
                f"edge_updates {name} must be integer vertex ids; got "
                f"dtype {a.dtype}")
    bound = max(int(num_vertices), int(new_num_vertices or 0))
    if src.size:
        lo = int(min(src.min(), dst.min()))
        hi = int(max(src.max(), dst.max()))
        if lo < 0:
            raise ValueError(
                f"edge_updates contain a negative vertex id ({lo})")
        if hi >= bound:
            raise ValueError(
                f"edge_updates reference vertex {hi} but the graph has "
                f"{num_vertices} vertices"
                + ("" if new_num_vertices is None else
                   f" (growing to {new_num_vertices})")
                + "; pass num_vertices to grow the vertex set explicitly")
    return src.astype(np.int32), dst.astype(np.int32)


def coalesce_updates(batches) -> Tuple[np.ndarray, np.ndarray]:
    """Fold queued ``(src, dst)`` edge-update batches into ONE batch
    whose single ``apply_delta`` is bit-identical to applying the
    batches one by one.

    This is the serving tier's request coalescing (``repro.serve``): N
    queued edge-update requests against one graph collapse into a single
    ``apply_delta`` plan -- one scatter, one reconvergence -- instead of
    N.  The ledger keeps the exact arcs of every pair (Eq. 3 over their
    union), so one batch holding the union of the arcs reaches the same
    weights as the batches in turn: the union of the arcs, each directed
    arc once and self-loops dropped (they never count).
    """
    batches = [b for b in batches if b is not None]
    empty = (np.zeros(0, np.int64), np.zeros(0, np.int64))
    if not batches:
        return empty
    src = np.concatenate([np.asarray(b[0]) for b in batches]).astype(np.int64)
    dst = np.concatenate([np.asarray(b[1]) for b in batches]).astype(np.int64)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    if not src.size:
        return empty
    base = int(max(src.max(), dst.max())) + 1
    arcs = np.unique(src * base + dst)
    return arcs // base, arcs % base


# What a BatchPlan counts of the ledger's view of its batch; the
# ``delta/plan`` span and the session's ``stats()["delta"]`` carry them.
LEDGER_COUNTS = ("arcs_given", "pairs_changed", "pairs_reciprocated",
                 "arcs_redelivered")


def _popcount(mask: np.ndarray) -> np.ndarray:
    """Arcs in a two-bit pair mask: its Eq. 3 weight."""
    return (mask & 1) + (mask >> 1)


@dataclasses.dataclass
class BatchPlan:
    """One batch folded to its append-delta form (see ``DeltaTracker``),
    with what the ledger saw in it: ``arcs_given``, the pairs it took
    from weight 1 to 2 (``pairs_reciprocated``), and the arcs that
    changed nothing (``arcs_redelivered``: given before, or twice in the
    batch; self-loops are neither)."""

    src: np.ndarray        # int32 (2 * changed_pairs,) entries to append
    dst: np.ndarray        # int32, symmetric counterparts interleaved
    dw: np.ndarray         # f32 weight DELTA carried by each entry
    touched: np.ndarray    # int32 unique endpoints of changed pairs
    pair_keys: np.ndarray  # int64 canonical keys of changed pairs
    pair_mask: np.ndarray  # uint8 NEW arc masks of changed pairs
    tw_delta: float        # total_weight change (2 * sum of pair deltas)
    arcs_given: int = 0
    pairs_reciprocated: int = 0
    arcs_redelivered: int = 0

    @property
    def num_entries(self) -> int:
        return int(self.src.shape[0])

    @property
    def pairs_changed(self) -> int:
        return int(self.pair_keys.shape[0])


class DeltaTracker:
    """Host ledger of each pair's arc mask across a session's deltas.

    ``plan(src, dst)`` is pure; ``commit(plan)`` folds a successfully
    merged batch into the overlay so later batches see it (sequential
    per-batch semantics, matching a chain of ``add_edges`` calls, and
    equal to one batch of their union).
    """

    def __init__(self, graph: Graph):
        if graph.dirs is None:
            raise ValueError("graph records no arc directions (Graph.dirs)"
                             "; build it with from_edges to stream arcs")
        V = graph.num_vertices
        half = graph.src < graph.dst
        # graph arrays are lexsorted by (src, dst), so the canonical-half
        # keys come out sorted: one O(E) pass, then O(log E) lookups
        self.num_vertices = V
        self.canon_keys = (graph.src[half].astype(np.int64) * V
                           + graph.dst[half])
        self.canon_mask = graph.dirs            # one mask per such pair
        self.pairs: dict = {}          # canonical key -> overlaid mask
        self.total_weight = float(graph.total_weight)

    def _masks(self, keys: np.ndarray) -> np.ndarray:
        """The arc masks of canonical pair ``keys`` (0: no arc yet)."""
        m = np.zeros(keys.size, np.uint8)
        if self.canon_keys.size:
            pos = np.searchsorted(self.canon_keys, keys)
            pos_c = np.minimum(pos, self.canon_keys.size - 1)
            found = self.canon_keys[pos_c] == keys
            m[found] = self.canon_mask[pos_c[found]]
        for i, key in enumerate(keys.tolist()):
            ov = self.pairs.get(key)
            if ov is not None:
                m[i] = ov
        return m

    def plan(self, src: np.ndarray, dst: np.ndarray) -> BatchPlan:
        V = self.num_vertices
        arcs_given = int(src.size)
        keep = src != dst                       # self-loops never count
        src, dst = src[keep], dst[keep]
        # dedupe directed arcs within the batch (from_edges semantics)
        dirkey = np.unique(src.astype(np.int64) * V + dst)
        s = dirkey // V
        d = dirkey % V
        uniq, inv = np.unique(np.minimum(s, d) * V + np.maximum(s, d),
                              return_inverse=True)
        batch_mask = np.zeros(uniq.size, np.uint8)
        np.bitwise_or.at(batch_mask, inv, np.where(s < d, 1, 2)
                         .astype(np.uint8))
        m0 = self._masks(uniq)
        m1 = m0 | batch_mask
        change = m1 != m0                       # an arc not given before
        uniq, m1 = uniq[change], m1[change]
        w0, w1 = _popcount(m0[change]), _popcount(m1)
        dw_pair = (w1 - w0).astype(np.float32)
        p_lo = (uniq // V).astype(np.int32)
        p_hi = (uniq % V).astype(np.int32)
        # each changed pair appends BOTH directed entries carrying dw
        e_src = np.stack([p_lo, p_hi], axis=1).reshape(-1)
        e_dst = np.stack([p_hi, p_lo], axis=1).reshape(-1)
        e_dw = np.stack([dw_pair, dw_pair], axis=1).reshape(-1)
        return BatchPlan(
            src=e_src, dst=e_dst, dw=e_dw,
            touched=np.unique(e_src).astype(np.int32),
            pair_keys=uniq, pair_mask=m1,
            tw_delta=float(2.0 * dw_pair.sum()), arcs_given=arcs_given,
            pairs_reciprocated=int(np.count_nonzero((w0 == 1) & (w1 == 2))),
            arcs_redelivered=int(src.size - (w1 - w0).sum()))

    def commit(self, plan: BatchPlan) -> None:
        for key, m in zip(plan.pair_keys.tolist(), plan.pair_mask.tolist()):
            self.pairs[key] = m
        self.total_weight += plan.tw_delta


# ---------------------------------------------------------------------------
# Device-resident merged arrays + slack-slot bookkeeping per engine mode
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class DeviceDelta:
    """The session's merged device arrays for one engine mode.

    ``score`` mirrors the score backend's arg tuple structure exactly and
    ``deg_w`` the engine's degree array, so a hand-built ``GraphBind`` /
    sharded arg tuple over these arrays drops into the SAME compiled
    programs the session's regular runs use.  The remaining fields are
    host-side slot state over the layout's slack regions.
    """

    mode: str                  # single_xla | single_pallas | sharded_xla
    score: tuple               # merged backend edge arrays (jnp)
    deg_w: jax.Array           # merged degrees: (v_pad,) or (ndev, v_l)
    coo: tuple = ()            # single_pallas: merged COO (src, dst) for
                               # the frontier expansion index
    # --- single-device COO (and the pallas frontier COO) ---
    next_slot: int = 0         # first free tail slot of the padded COO
    e_capacity: int = 0        # total COO slots (the edge bucket)
    csr_entries: int = 0       # single_xla: real entries, in CSR order
    # --- single_pallas tiled layout ---
    tile_v: int = 0
    region: int = 0            # max_chunks * tile_e slots per tile
    perm: Optional[np.ndarray] = None     # (V,) vertex -> tiled row
    fill: Optional[np.ndarray] = None     # (T,) occupied slots per tile
    # --- sharded_xla layout ---
    v_per_dev: int = 0
    e_shard: int = 0
    e_interior: int = 0
    int_fill: Optional[np.ndarray] = None  # (ndev,) abs col of int. slack
    fro_fill: Optional[np.ndarray] = None  # (ndev,) abs col of fro. slack


def init_single_xla(score_args: tuple, deg_w: jax.Array,
                    num_entries: int) -> DeviceDelta:
    """Mode A: the padded COO upload; slack = pad_graph's tail filler.
    ``score_args`` is the XLA backend's ``(src, dst, w, row_ptr,
    merged)``; a merge writes the batch into the first three, sorts them
    back into CSR order with the slack last and gives the matching
    ``row_ptr``, so ``merged`` stays 0 and the score pass the transposed
    one."""
    return DeviceDelta(mode="single_xla", score=tuple(score_args),
                       deg_w=deg_w, next_slot=int(num_entries),
                       e_capacity=int(score_args[0].shape[0]),
                       csr_entries=int(num_entries))


def init_single_pallas(score_args: tuple, deg_w: jax.Array, coo: tuple,
                       tiled_meta, num_entries: int) -> DeviceDelta:
    """Mode B: the fused tiled layout; slack = per-tile tail slots.

    ``tiled_meta`` is the host ``TiledCSR`` whose jnp mirror ``score_args``
    is (same deterministic build); ``coo`` is the padded COO (src, dst)
    pair that doubles as the frontier expansion index, merged in lockstep
    so expansion sees appended edges.
    """
    return DeviceDelta(
        mode="single_pallas", score=tuple(score_args), deg_w=deg_w,
        coo=tuple(coo), next_slot=int(num_entries),
        e_capacity=int(coo[0].shape[0]), tile_v=int(tiled_meta.tile_v),
        region=int(tiled_meta.max_chunks * tiled_meta.tile_e),
        perm=np.asarray(tiled_meta.perm),
        fill=np.asarray(tiled_meta.fill, dtype=np.int64).copy())


def init_sharded_xla(score_args: tuple, deg_w: jax.Array, sg) -> DeviceDelta:
    """Mode C: the sharded [interior | frontier] layout; slack = both
    segment tails of every device row (segment identity is irrelevant off
    the overlap schedule, which the fast path pins off)."""
    return DeviceDelta(
        mode="sharded_xla", score=tuple(score_args), deg_w=deg_w,
        v_per_dev=int(sg.v_per_dev), e_shard=int(sg.src_local.shape[1]),
        e_interior=int(sg.e_interior),
        int_fill=np.asarray(sg.interior_counts, np.int64).copy(),
        fro_fill=(int(sg.e_interior)
                  + np.asarray(sg.frontier_counts, np.int64)).copy())


def _bucket_pad(arrs, n: int, sentinel: int):
    """Pad batch arrays to a shape bucket; index arrays get the dropped
    sentinel, value arrays zero."""
    m = shape_bucket(max(n, 1), BATCH_FLOOR)
    out = []
    for a, is_idx in arrs:
        padded = np.full(m, sentinel if is_idx else 0,
                         dtype=a.dtype if a.size else
                         (np.int64 if is_idx else np.float32))
        padded[:n] = a
        out.append(padded)
    return out


def plan_slots(dd: DeviceDelta, plan: BatchPlan):
    """Flat scatter slots for a batch, or None if slack would overflow.

    Pure: commits nothing.  Returns ``(slots, commit)`` where ``commit()``
    advances the host fill state after a successful device merge.
    """
    n = plan.num_entries
    e_src = plan.src.astype(np.int64)
    if dd.mode == "single_xla":
        if dd.next_slot + n > dd.e_capacity:
            return None
        slots = dd.next_slot + np.arange(n, dtype=np.int64)

        def commit():
            dd.next_slot += n

        return (slots,), commit
    if dd.mode == "single_pallas":
        if dd.next_slot + n > dd.e_capacity:
            return None
        rows = dd.perm[plan.src].astype(np.int64)
        tiles = rows // dd.tile_v
        counts = np.bincount(tiles, minlength=dd.fill.shape[0])
        if np.any(dd.fill + counts > dd.region):
            return None
        order = np.argsort(tiles, kind="stable")
        ts = tiles[order]
        csum = np.cumsum(counts) - counts
        within = np.arange(n, dtype=np.int64) - csum[ts]
        tile_slots = np.empty(n, dtype=np.int64)
        tile_slots[order] = ts * dd.region + dd.fill[ts] + within
        coo_slots = dd.next_slot + np.arange(n, dtype=np.int64)

        def commit():
            dd.fill += counts
            dd.next_slot += n

        return (tile_slots, coo_slots), commit
    if dd.mode == "sharded_xla":
        dev = e_src // dd.v_per_dev
        ndev = dd.int_fill.shape[0]
        counts = np.bincount(dev, minlength=ndev)
        int_avail = dd.e_interior - dd.int_fill
        fro_avail = dd.e_shard - dd.fro_fill
        if np.any(counts > int_avail + fro_avail):
            return None
        order = np.argsort(dev, kind="stable")
        ds = dev[order]
        csum = np.cumsum(counts) - counts
        within = np.arange(n, dtype=np.int64) - csum[ds]
        in_interior = within < int_avail[ds]
        col = np.where(in_interior, dd.int_fill[ds] + within,
                       dd.fro_fill[ds] + within - int_avail[ds])
        slots = np.empty(n, dtype=np.int64)
        slots[order] = ds * dd.e_shard + col

        def commit():
            used_int = np.minimum(counts, int_avail)
            dd.int_fill += used_int
            dd.fro_fill += counts - used_int

        return (slots,), commit
    raise ValueError(f"unknown DeviceDelta mode {dd.mode!r}")


def apply_batch(dd: DeviceDelta, plan: BatchPlan, slotting,
                merge_run) -> Tuple[DeviceDelta, int]:
    """Scatter one planned batch into the merged arrays on device.

    ``merge_run`` is the engine's ``("delta_merge",)`` program callable.
    Returns the updated ``DeviceDelta`` (fresh jnp arrays, functional
    update) and the batch upload byte count -- O(|delta|), the transfer
    the session's ``stats()`` counters account.  The batch's uploads run
    in the span ``delta/upload``, the merge's dispatch in ``delta/merge``;
    in ``single_xla`` mode the merge also re-sorts the edge list into CSR
    order, and the span's ``csr_entries`` counts its real entries.
    """
    slots, commit = slotting
    n = plan.num_entries
    src32 = plan.src.astype(np.int32)
    dst32 = plan.dst.astype(np.int32)
    dw32 = plan.dw.astype(np.float32)
    host_arrays = []
    order = ()      # single_xla: the merge's CSR re-sort (engine._csr_order)

    def dev(a):
        host_arrays.append(a)
        return jnp.asarray(a)

    with trace.span("delta/upload") as at:
        if dd.mode == "single_xla":
            (coo_slots,) = slots
            idx = dev(_bucket_pad([(coo_slots, True)], n,
                                  int(dd.score[0].size))[0])
            vs, vd, vw = (dev(a) for a in _bucket_pad(
                [(src32, False), (dst32, False), (dw32, False)], n, 0))
            set_groups = ((dd.score[:3], idx, (vs, vd, vw)),)
            didx = dev(_bucket_pad([(plan.src.astype(np.int64), True)], n,
                                   int(dd.deg_w.size))[0])
            add_groups = ((dd.deg_w, didx, vw),)
            fill = dd.next_slot + n
            order = (dd.score[3], didx, dev(np.int32(fill)))

            def unpack(merged):
                (new_score,), (new_deg,), row_ptr = merged
                return dataclasses.replace(
                    dd, score=tuple(new_score) + (row_ptr, dd.score[4]),
                    deg_w=new_deg, csr_entries=fill)
        elif dd.mode == "single_pallas":
            tile_slots, coo_slots = slots
            sl_local = (dd.perm[plan.src] % dd.tile_v).astype(np.int32)
            t_idx = dev(_bucket_pad([(tile_slots, True)], n,
                                    int(dd.score[0].size))[0])
            c_idx = dev(_bucket_pad([(coo_slots, True)], n,
                                    int(dd.coo[0].size))[0])
            v_sl, v_s, v_d, v_w = (dev(a) for a in _bucket_pad(
                [(sl_local, False), (src32, False), (dst32, False),
                 (dw32, False)], n, 0))
            # tiled (src_local, dst, weight) share tile slots; the COO
            # mirror (frontier expansion index) shares its own tail slots
            set_groups = (
                ((dd.score[0], dd.score[1], dd.score[2]), t_idx,
                 (v_sl, v_d, v_w)),
                (dd.coo, c_idx, (v_s, v_d)),
            )
            row_idx = dev(_bucket_pad(
                [(dd.perm[plan.src].astype(np.int64), True)], n,
                int(dd.score[5].size))[0])
            deg_idx = dev(_bucket_pad([(plan.src.astype(np.int64), True)],
                                      n, int(dd.deg_w.size))[0])
            add_groups = ((dd.score[5], row_idx, v_w),
                          (dd.deg_w, deg_idx, v_w))

            def unpack(merged):
                (tiled3, coo2), (new_deg_t, new_deg), _ = merged
                return dataclasses.replace(
                    dd, score=tuple(tiled3) + dd.score[3:5] + (new_deg_t,),
                    coo=tuple(coo2), deg_w=new_deg)
        elif dd.mode == "sharded_xla":
            (flat_slots,) = slots
            sl_local = (plan.src.astype(np.int64) % dd.v_per_dev
                        ).astype(np.int32)
            idx = dev(_bucket_pad([(flat_slots, True)], n,
                                  int(dd.score[0].size))[0])
            v_sl, v_d, v_w = (dev(a) for a in _bucket_pad(
                [(sl_local, False), (dst32, False), (dw32, False)], n, 0))
            set_groups = ((dd.score, idx, (v_sl, v_d, v_w)),)
            # deg_w is (ndev, v_per_dev) over contiguous ranges: flat id = u
            didx = dev(_bucket_pad([(plan.src.astype(np.int64), True)], n,
                                   int(dd.deg_w.size))[0])
            add_groups = ((dd.deg_w, didx, v_w),)

            def unpack(merged):
                (new_score,), (new_deg,), _ = merged
                return dataclasses.replace(dd, score=tuple(new_score),
                                           deg_w=new_deg)
        else:
            raise ValueError(f"unknown DeviceDelta mode {dd.mode!r}")
        nbytes = int(sum(a.nbytes for a in host_arrays))
        at["bytes"] = nbytes
    with trace.span("delta/merge") as at:
        out = unpack(merge_run(set_groups, add_groups, order))
        if order:
            at["csr_entries"] = out.csr_entries
    # commit AFTER a successful scatter but BEFORE snapshotting the host
    # slot state into the returned DeviceDelta (commit mutates dd's
    # fill/next_slot fields in place)
    commit()
    out = dataclasses.replace(
        out, next_slot=dd.next_slot, fill=dd.fill,
        int_fill=dd.int_fill, fro_fill=dd.fro_fill)
    return out, nbytes


def apply_delta(tracker: DeltaTracker, dd: DeviceDelta, src, dst,
                merge_run):
    """The one-call coalescing primitive: plan a ``(src, dst)`` batch
    against the pair ledger, assign slack slots, scatter it into the
    resident device arrays, and commit the ledger.

    Returns ``(new_dd, plan, uploaded_bytes)``, or ``None`` when the
    batch would overflow the layout's slack (nothing is committed; the
    caller rebuilds from the logical edge list -- bit-identically,
    because appended delta entries carry exact integer weight sums).
    This is the primitive a multi-tenant delta scheduler coalesces
    through: batches validated with ``check_edge_updates`` fold
    sequentially with ``add_edges`` union semantics.  The ``delta/plan``
    span carries the plan's entries and what the ledger saw
    (``LEDGER_COUNTS``).
    """
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    with trace.span("delta/plan") as at:
        plan = tracker.plan(src, dst)
        slotting = plan_slots(dd, plan) if plan.num_entries else None
        at["entries"] = plan.num_entries
        at.update((c, getattr(plan, c)) for c in LEDGER_COUNTS)
    nbytes = 0
    if plan.num_entries:
        if slotting is None:
            return None
        dd, nbytes = apply_batch(dd, plan, slotting, merge_run)
    tracker.commit(plan)
    return dd, plan, nbytes
