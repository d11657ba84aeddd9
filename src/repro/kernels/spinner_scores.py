"""Pallas TPU kernels for Spinner's vertex-update hot loop.

The per-iteration work of LPA starts with ``scores[u, label(v)] += w(u, v)``
over all edges -- a sparse-dense matmul A @ onehot(labels).  A GPU
implementation would use atomics; the TPU has none, and scatter lowers to
serialized dynamic-update-slices.  The TPU-native re-cast: process edges in
chunks that all share one source-vertex tile and turn the scatter into a
dense MXU matmul

    out[TILE_V, K] += onehot(src_local)[TILE_E, TILE_V]^T
                      @ (onehot(dst_label) * w)[TILE_E, K]

accumulated in a VMEM-resident (TILE_V, K) block across the chunk grid
dimension (flash-attention-style revisiting).  Preprocessing
(``core.graph.build_tiled_csr``) sorts edges by source tile, pads each tile's
chunk list, and interleaves vertices by degree so hub-heavy tiles do not
dominate the chunk count.

Two kernels share that reduction:

  * ``_kernel`` / ``spinner_scores_pallas`` -- the SPLIT pipeline: emit the
    full (V_pad, k_pad) score matrix to HBM and let XLA ops do the Eq. 7-8
    normalization, tie-noise argmax and migration bookkeeping.
  * ``_fused_kernel`` / ``fused_update_pallas`` -- the FUSED vertex-update
    megakernel: on each tile's LAST chunk the VMEM accumulator flows
    directly into ``scores / max(deg_w, 1)``, the load penalty and
    current-label bonus, the -inf-masked tie-noise argmax, and the
    ComputeMigrations candidate bookkeeping -- emitting only per-tile
    ``(tile_v,)`` best-label / total-score vectors plus a revisited
    ``(1, k_pad)`` partial of the migration-candidate mass M(l).  The
    (V_pad, k_pad) matrix never touches HBM.  The epilogue that needs the
    globally psum-reduced M(l) -- the Eq. 11-12 probability test, the load
    delta and score(G) -- runs as cheap O(V + k) XLA ops on the kernel's
    vectors (``engine.make_update_parts``'s ``finish`` half), shared
    bit-for-bit with the split path.

Bit parity with the split path holds because the Eq. 3 edge weights are
small integers (f32 sums are exact under any tiling/order), the
normalization/penalty/bonus/argmax ops are the same primitives in the same
association order, and the tie-noise / migration draws are handed in over
the padded vertex set in ORIGINAL vertex order (the wrapper permutes noise
into tiled rows; the first-match argmax over the -inf-masked k_pad columns
equals ``jnp.argmax`` over k columns).

Pad entries carry weight 0 and therefore contribute nothing; pad ROWS
(``inv_perm < 0``) carry valid=0 and are masked out of the migration mass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Both grid axes revisit an output block (the accumulator across chunks, the
# migration mass across tiles), so neither may be split across cores.
COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("arbitrary", "arbitrary"))


# Mosaic tiles the last two dims of every block by (8, 128) unless a block
# dim spans the whole array dim.  So each (TILE_E,) edge chunk travels as
# the (1, TILE_E) row of a (T, C, 1, TILE_E) view, and each (tile_v,)
# per-vertex vector as the (1, tile_v) row of a (T, 1, tile_v) view; the
# leading grid dims are squeezed, so kernels see 2-D (1, n) refs.

def edge_view(x: jax.Array) -> jax.Array:
    """(T, C, TILE_E) -> (T, C, 1, TILE_E) for ``edge_spec``."""
    t, c, tile_e = x.shape
    return x.reshape(t, c, 1, tile_e)


def edge_spec(tile_e: int) -> pl.BlockSpec:
    return pl.BlockSpec((pl.squeezed, pl.squeezed, 1, tile_e),
                        lambda i, j: (i, j, 0, 0))


def row_spec(tile_v: int) -> pl.BlockSpec:
    """Block of a (T, 1, tile_v) per-tile row array."""
    return pl.BlockSpec((pl.squeezed, 1, tile_v), lambda i, j: (i, 0, 0))


def _kernel(src_local_ref, dst_label_ref, w_ref, out_ref, *, tile_v: int,
            k_pad: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    sl = src_local_ref[0, :]                          # (TILE_E,) int32
    lbl = dst_label_ref[0, :]                         # (TILE_E,) int32
    w = w_ref[0, :]                                   # (TILE_E,) f32

    rows = jax.lax.broadcasted_iota(jnp.int32, (sl.shape[0], tile_v), 1)
    onehot_v = (sl[:, None] == rows).astype(jnp.float32)
    cols = jax.lax.broadcasted_iota(jnp.int32, (lbl.shape[0], k_pad), 1)
    onehot_l = (lbl[:, None] == cols).astype(jnp.float32) * w[:, None]

    out_ref[...] += jax.lax.dot_general(
        onehot_v, onehot_l, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def scores_from_tiles(labels_lookup: jax.Array, src_local: jax.Array,
                      dst: jax.Array, w: jax.Array, perm: jax.Array, *,
                      tile_v: int, k_pad: int, k: int,
                      interpret: bool = False) -> jax.Array:
    """Gather destination labels, run the kernel, un-permute the rows.

    The full ComputeScores pipeline for one tiling: ``dst`` indexes
    ``labels_lookup`` (the whole label vector on a single device; an
    exchange plan's ``[local | halo]`` lookup inside ``shard_map``), the
    kernel accumulates the (padded_v, k_pad) block, and ``perm`` maps the
    tiled rows back to vertex order.  Pure and trace-friendly, so it
    inlines into ``lax.while_loop`` bodies on either path.
    """
    with jax.named_scope("lpa/gather"):
        dst_label = labels_lookup[dst]           # gather (T, C, TILE_E)
    with jax.named_scope("lpa/scatter"):
        scores_pad = spinner_scores_pallas(src_local, dst_label, w,
                                           tile_v=tile_v, k_pad=k_pad,
                                           interpret=interpret)
        return scores_pad[perm, :k]


def spinner_scores_pallas(src_local: jax.Array, dst_label: jax.Array,
                          w: jax.Array, *, tile_v: int, k_pad: int,
                          interpret: bool = False) -> jax.Array:
    """Run the tiled ComputeScores kernel.

    Args:
      src_local: (T, C, TILE_E) int32, row of each edge within its tile.
      dst_label: (T, C, TILE_E) int32, label of each edge's destination.
      w: (T, C, TILE_E) float32, Eq. (3) edge weights (0 for padding).
      tile_v: rows per source-vertex tile (multiple of 8; 128 for MXU).
      k_pad: padded label count (multiple of 128 for lane alignment).
    Returns:
      (T * tile_v, k_pad) float32 score matrix in tiled row order.
    """
    t, c, tile_e = src_local.shape
    assert dst_label.shape == w.shape == (t, c, tile_e)
    kernel = functools.partial(_kernel, tile_v=tile_v, k_pad=k_pad)
    return pl.pallas_call(
        kernel,
        name="spinner_scores",
        grid=(t, c),
        in_specs=[edge_spec(tile_e)] * 3,
        out_specs=pl.BlockSpec((tile_v, k_pad), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((t * tile_v, k_pad), jnp.float32),
        interpret=interpret,
        compiler_params=COMPILER_PARAMS,
    )(edge_view(src_local), edge_view(dst_label), edge_view(w))


# ---------------------------------------------------------------------------
# Fused vertex-update megakernel
# ---------------------------------------------------------------------------

def _fused_kernel(*refs, tile_v: int, k_pad: int, k: int, nc: int,
                  current_bonus: float, degree_weighted: bool,
                  has_init: bool, has_act: bool = False):
    """Edge reduction + per-tile vertex update in one VMEM residency.

    Grid (T, C): chunk j accumulates its one-hot matmul into the scratch
    accumulator; the LAST chunk of each tile (j == nc - 1) finalizes the
    Eq. 7-8 per-vertex totals and the argmax proposal without the
    (tile_v, k_pad) block ever leaving VMEM.  ``m_ref`` is a revisited
    (1, k_pad) output accumulating the migration-candidate mass M(l)
    across all tiles (zeroed on the very first grid step).

    ``has_act`` threads the frontier mode's (T, 1) tile-activity bitmap:
    a tile with no active vertex skips its matmul chain and final update
    entirely and writes the safe proposal ``best = labels`` (a no-op for
    the epilogue: ``want`` is already false for every inactive vertex),
    ``tb = tc = 0``.  Inactive tiles therefore cost O(1) per chunk
    instead of O(tile_e * (tile_v + k_pad)) -- the compute analogue of
    the delta exchange plan.
    """
    n_in = 8 + int(has_init) + int(has_act)
    in_refs = refs[:n_in]
    best_ref, tb_ref, tc_ref, m_ref, acc_ref = refs[n_in:]
    (src_ref, lbl_ref, w_ref, labels_ref, deg_ref, valid_ref,
     pen_ref, noise_ref) = in_refs[:8]
    pos = 8
    init_ref = in_refs[pos] if has_init else None
    pos += int(has_init)
    act_ref = in_refs[pos] if has_act else None
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _zero_m():
        m_ref[...] = jnp.zeros_like(m_ref)

    @pl.when(j == 0)
    def _init_acc():
        # overlap schedule: seed with the interior partial (same tiling)
        acc_ref[...] = (init_ref[...] if init_ref is not None
                        else jnp.zeros_like(acc_ref))

    def _accumulate():
        sl = src_ref[0, :]                            # (TILE_E,) int32
        lbl = lbl_ref[0, :]                           # (TILE_E,) int32
        w = w_ref[0, :]                               # (TILE_E,) f32
        rows = jax.lax.broadcasted_iota(jnp.int32, (sl.shape[0], tile_v), 1)
        onehot_v = (sl[:, None] == rows).astype(jnp.float32)
        ecols = jax.lax.broadcasted_iota(jnp.int32, (lbl.shape[0], k_pad), 1)
        onehot_l = (lbl[:, None] == ecols).astype(jnp.float32) * w[:, None]
        acc_ref[...] += jax.lax.dot_general(
            onehot_v, onehot_l, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    def _vertex_update():
        scores = acc_ref[...]                         # (tile_v, k_pad)
        deg = deg_ref[0, :]                           # (tile_v,) f32
        labels = labels_ref[0, :]                     # (tile_v,) int32
        valid = valid_ref[0, :] != 0
        # ---- Eq. 7-8: normalize, penalize, bonus, tie-noise argmax -----
        norm = scores / jnp.maximum(deg, 1.0)[:, None]
        total = norm - pen_ref[0, :][None, :]
        cols = jax.lax.broadcasted_iota(jnp.int32, (tile_v, k_pad), 1)
        cur = cols == labels[:, None]
        x = (total + noise_ref[...]) + jnp.where(
            cur, jnp.float32(current_bonus), jnp.float32(0.0))
        x = jnp.where(cols < k, x, -jnp.inf)
        # first-match argmax == jnp.argmax over the unpadded k columns
        vmax = jnp.max(x, axis=1)
        best = jnp.min(jnp.where(x == vmax[:, None], cols, k_pad),
                       axis=1).astype(jnp.int32)
        hit = cols == best[:, None]
        best_ref[0, :] = best
        tb_ref[0, :] = jnp.sum(jnp.where(hit, total, 0.0), axis=1)
        tc_ref[0, :] = jnp.sum(jnp.where(cur, total, 0.0), axis=1)
        # ---- migration-candidate mass M(l) partial (Eq. 11 numerator) --
        want = (best != labels) & valid
        measure = deg if degree_weighted else jnp.ones_like(deg)
        m_ref[0, :] += jnp.sum(
            jnp.where(hit & want[:, None], measure[:, None], 0.0), axis=0)

    if has_act:
        act = act_ref[0, 0] != 0

        @pl.when(act)
        def _accum_active():
            _accumulate()

        @pl.when((j == nc - 1) & act)
        def _update_active():
            _vertex_update()

        @pl.when((j == nc - 1) & jnp.logical_not(act))
        def _update_skipped():
            # safe no-op proposal: epilogue sees want == False everywhere
            best_ref[0, :] = labels_ref[0, :]
            tb_ref[0, :] = jnp.zeros((tile_v,), jnp.float32)
            tc_ref[0, :] = jnp.zeros((tile_v,), jnp.float32)
    else:
        _accumulate()

        @pl.when(j == nc - 1)
        def _update():
            _vertex_update()


def fused_update_pallas(src_local: jax.Array, dst_label: jax.Array,
                        w: jax.Array, labels_t: jax.Array,
                        deg_t: jax.Array, valid_t: jax.Array,
                        penalty_row: jax.Array, noise_t: jax.Array, *,
                        tile_v: int, k_pad: int, k: int,
                        current_bonus: float, degree_weighted: bool,
                        interpret: bool = False,
                        acc_init: jax.Array = None,
                        tile_act: jax.Array = None) -> tuple:
    """Launch the fused megakernel over one tiling (tiled row order).

    Args:
      src_local/dst_label/w: (T, C, TILE_E) edge chunks as in
        ``spinner_scores_pallas``.
      labels_t: (T, tile_v) int32 current labels, tiled row order.
      deg_t: (T, tile_v) f32 weighted degrees (0 on pad rows).
      valid_t: (T, tile_v) int32 1 on real vertices, 0 on pads.
      penalty_row: (1, k_pad) f32 ``loads / C`` (0 beyond k).
      noise_t: (T * tile_v, k_pad) f32 tie noise, tiled row order.
      acc_init: optional (T * tile_v, k_pad) f32 interior score partial
        (overlap schedule); the kernel seeds its accumulator with it.
      tile_act: optional (T, 1) int32 frontier-mode activity bitmap;
        tiles with 0 skip their matmuls and write no-op proposals.
    Returns:
      (best, tot_best, tot_cur, m_partial): (T, tile_v) int32 proposals,
      (T, tile_v) f32 totals at the proposal / the current label, and the
      (1, k_pad) migration-candidate mass partial.
    """
    t, c, tile_e = src_local.shape
    assert dst_label.shape == w.shape == (t, c, tile_e)
    kernel = functools.partial(
        _fused_kernel, tile_v=tile_v, k_pad=k_pad, k=k, nc=c,
        current_bonus=float(current_bonus),
        degree_weighted=degree_weighted, has_init=acc_init is not None,
        has_act=tile_act is not None)
    e_spec, r_spec = edge_spec(tile_e), row_spec(tile_v)
    mat_spec = pl.BlockSpec((tile_v, k_pad), lambda i, j: (i, 0))
    k_spec = pl.BlockSpec((1, k_pad), lambda i, j: (0, 0))
    in_specs = [e_spec, e_spec, e_spec, r_spec, r_spec, r_spec, k_spec,
                mat_spec]
    inputs = [edge_view(src_local), edge_view(dst_label), edge_view(w),
              labels_t[:, None, :], deg_t[:, None, :], valid_t[:, None, :],
              penalty_row, noise_t]
    if acc_init is not None:
        in_specs.append(mat_spec)
        inputs.append(acc_init)
    if tile_act is not None:
        in_specs.append(pl.BlockSpec((pl.squeezed, 1, 1),
                                     lambda i, j: (i, 0, 0)))
        inputs.append(tile_act[:, None, :])
    best, tb, tc, m = pl.pallas_call(
        kernel,
        name="spinner_fused_update",
        grid=(t, c),
        in_specs=in_specs,
        out_specs=[r_spec, r_spec, r_spec, k_spec],
        out_shape=[jax.ShapeDtypeStruct((t, 1, tile_v), jnp.int32),
                   jax.ShapeDtypeStruct((t, 1, tile_v), jnp.float32),
                   jax.ShapeDtypeStruct((t, 1, tile_v), jnp.float32),
                   jax.ShapeDtypeStruct((1, k_pad), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((tile_v, k_pad), jnp.float32)],
        interpret=interpret,
        compiler_params=COMPILER_PARAMS,
    )(*inputs)
    return best[:, 0], tb[:, 0], tc[:, 0], m


def fused_update_from_tiles(labels_lookup: jax.Array, labels: jax.Array,
                            deg_t: jax.Array, noise: jax.Array,
                            valid: jax.Array, penalty: jax.Array,
                            src_local: jax.Array, dst: jax.Array,
                            w: jax.Array, perm: jax.Array,
                            inv_perm: jax.Array, *, tile_v: int,
                            k_pad: int, k: int, current_bonus: float,
                            degree_weighted: bool, interpret: bool = False,
                            acc_init: jax.Array = None,
                            frontier: bool = False) -> tuple:
    """The fused vertex-update proposal over one tiling, in VERTEX order.

    Gathers destination labels via ``dst``, permutes labels/valid/noise
    into tiled rows (``inv_perm``; pad rows get valid=0), launches the
    megakernel, and un-permutes the per-vertex outputs via ``perm``.
    ``labels``/``noise``/``valid`` are over the caller's vertex range in
    ORIGINAL order -- the same arrays the split path consumes -- which is
    what keeps the fused trajectory bit-identical.

    With ``frontier=True`` the caller's ``valid`` is the frontier mode's
    ``valid & active`` mask; a (T, 1) tile-activity bitmap is derived
    from its tiled view and handed to the kernel so all-inactive tiles
    skip their matmul chain (see ``_fused_kernel``).  Bit parity with
    the dense masked path holds because inactive vertices can never
    migrate (``want`` is false) and their score contribution is zeroed
    by the same ``valid`` mask in the epilogue.

    Returns ``(best, tot_best, tot_cur, m_partial)``: (V,) int32 / f32 /
    f32 vectors in vertex order plus the (k,) local M(l) partial, i.e.
    exactly the contract of ``engine.make_update_parts``'s ``propose``.
    """
    with jax.named_scope("lpa/gather"):
        dst_label = labels_lookup[dst]           # gather (T, C, TILE_E)
    with jax.named_scope("lpa/propose"):
        t = src_local.shape[0]
        inv_safe = jnp.maximum(inv_perm, 0)
        labels_t = labels[inv_safe].reshape(t, tile_v)
        valid_t = ((inv_perm >= 0) & valid[inv_safe]).astype(
            jnp.int32).reshape(t, tile_v)
        if k_pad != k:
            noise = jnp.pad(noise, ((0, 0), (0, k_pad - k)))
            penalty = jnp.pad(penalty, (0, k_pad - k))
        noise_t = noise[inv_safe]
        tile_act = (jnp.max(valid_t, axis=1, keepdims=True) if frontier
                    else None)
        best_t, tb_t, tc_t, m = fused_update_pallas(
            src_local, dst_label, w, labels_t, jnp.asarray(deg_t), valid_t,
            penalty[None, :], noise_t, tile_v=tile_v, k_pad=k_pad, k=k,
            current_bonus=current_bonus, degree_weighted=degree_weighted,
            interpret=interpret, acc_init=acc_init, tile_act=tile_act)
        return (best_t.reshape(-1)[perm], tb_t.reshape(-1)[perm],
                tc_t.reshape(-1)[perm], m[0, :k])
