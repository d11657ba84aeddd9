"""Pallas TPU kernels for the Pregel message-combine hot loop.

A Pregel superstep's inner reduction is ``acc[dst-owner-local(src)] =
combine(acc[...], message(dst))`` over every edge of the shard -- the
same sparse pattern as Spinner's ComputeScores, but reducing a SCALAR
per vertex instead of a (k,) score row.  The kernels reuse the
``spinner_scores`` tiling verbatim: edges arrive pre-sorted into
``(T, C, TILE_E)`` chunks whose chunk rows all map into one
``tile_v``-row vertex tile (``core.graph.build_sharded_tiled_csr``),
message values are gathered OUTSIDE the kernel (``lookup[dst]``, the
exchange plan's ``[local | halo]`` layout), and a VMEM scratch
accumulator is revisited across the chunk grid dimension.

Two combine monoids cover the workload suite (``repro.apps``):

  * ``sum``  -- PageRank: a one-hot matmul per chunk, exactly the
    ``spinner_scores`` reduction with k = 1, at full f32 precision (the
    messages are not small integers).  Tolerance-exact vs. the XLA
    scatter-add (different association order).
  * ``min``  -- WCC / BFS / SSSP: a masked minimum per chunk.  int32,
    BIT-exact vs. the XLA ``.at[].min`` path (min is order-free).

and two kernels share them:

  * ``pregel_reduce_pallas`` -- reduce only, emitting the raw
    ``(T, tile_v)`` partial in tiled row order.  The overlap schedule
    runs it on the interior segment while the halo exchange is in
    flight.
  * ``pregel_combine_pallas`` -- the FUSED form: on each tile's last
    chunk the VMEM accumulator flows straight into the vertex update
    (PageRank's damped affine map, or the monotone ``min(old, acc)``
    with a changed flag), optionally seeded from the interior partial
    (``acc_init``), row-compatible because both segment tilings share
    one ``ext_perm`` row layout (the `ops.PallasBackend` split idiom).

Pad edge slots carry weight-mask 0 and contribute the monoid identity;
pad ROWS (``inv_perm < 0``) carry valid=0 and emit changed=0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .spinner_scores import COMPILER_PARAMS, edge_spec, edge_view, row_spec

INF_I32 = 2 ** 30        # "unreached" sentinel for min-combine workloads


def _accumulate(acc_ref, sl, msg, wm, *, tile_v: int, combine: str):
    """Fold one edge chunk into the (1, tile_v) scratch accumulator."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (sl.shape[0], tile_v), 1)
    hit = sl[:, None] == rows                       # (TILE_E, TILE_V)
    if combine == "sum":
        onehot_v = hit.astype(jnp.float32)
        # HIGHEST: the MXU's default f32 precision rounds the messages to
        # bf16 (~3e-3 relative error in PageRank on the chip)
        part = jax.lax.dot_general(                 # (TILE_V, 1) on the MXU
            onehot_v, (msg * wm)[:, None], (((0,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        acc_ref[...] += part[:, 0][None, :]
    else:                                           # min
        cand = jnp.where(hit & (wm[:, None] > 0), msg[:, None], INF_I32)
        acc_ref[...] = jnp.minimum(acc_ref[...], cand.min(axis=0)[None, :])


def _neutral(acc_ref, combine: str):
    if combine == "sum":
        acc_ref[...] = jnp.zeros_like(acc_ref)
    else:
        acc_ref[...] = jnp.full_like(acc_ref, INF_I32)


def _reduce_kernel(*refs, tile_v: int, nc: int, combine: str,
                   has_init: bool):
    if has_init:
        src_ref, msg_ref, wm_ref, init_ref, out_ref, acc_ref = refs
    else:
        src_ref, msg_ref, wm_ref, out_ref, acc_ref = refs
        init_ref = None
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        if init_ref is None:
            _neutral(acc_ref, combine)
        else:
            acc_ref[...] = init_ref[...]

    _accumulate(acc_ref, src_ref[0, :], msg_ref[0, :], wm_ref[0, :],
                tile_v=tile_v, combine=combine)

    @pl.when(j == nc - 1)
    def _emit():
        out_ref[...] = acc_ref[...]


def _fused_kernel(*refs, tile_v: int, nc: int, combine: str, update: str,
                  damping: float, has_init: bool):
    if has_init:
        (src_ref, msg_ref, wm_ref, vals_ref, valid_ref, base_ref,
         init_ref, out_ref, chg_ref, acc_ref) = refs
    else:
        (src_ref, msg_ref, wm_ref, vals_ref, valid_ref, base_ref,
         out_ref, chg_ref, acc_ref) = refs
        init_ref = None
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        if init_ref is None:
            _neutral(acc_ref, combine)
        else:
            acc_ref[...] = init_ref[...]

    _accumulate(acc_ref, src_ref[0, :], msg_ref[0, :], wm_ref[0, :],
                tile_v=tile_v, combine=combine)

    @pl.when(j == nc - 1)
    def _vertex_update():
        acc = acc_ref[0, :]
        valid = valid_ref[0, :] != 0
        if update == "pagerank":
            new = jnp.where(valid, base_ref[0, :] + damping * acc, 0.0)
            chg = valid
        else:                                        # monotone min update
            vals = vals_ref[0, :]
            new = jnp.where(valid, jnp.minimum(vals, acc), vals)
            chg = (new != vals) & valid
        out_ref[...] = new[None, :]
        chg_ref[...] = chg.astype(jnp.int32)[None, :]


def pregel_reduce_pallas(src_local: jax.Array, msg: jax.Array,
                         wm: jax.Array, *, tile_v: int, combine: str,
                         interpret: bool = False,
                         acc_init=None) -> jax.Array:
    """Segmented combine of pre-gathered messages; (T, tile_v) partial.

    Args:
      src_local: (T, C, TILE_E) int32 row of each edge within its tile.
      msg: (T, C, TILE_E) message value at each edge's destination
        (f32 for ``sum``, int32 for ``min``).
      wm: (T, C, TILE_E) f32 weight MASK (0 pads edges out; the Eq. 3
        weight magnitude is deliberately ignored -- Pregel messages are
        combined unweighted, matching ``core.pregel``'s oracles).
      acc_init: optional (T, tile_v) accumulator seed (the interior
        partial, in the SAME shared row layout).
    """
    t, c, tile_e = src_local.shape
    assert msg.shape == wm.shape == (t, c, tile_e)
    dtype = jnp.float32 if combine == "sum" else jnp.int32
    kernel = functools.partial(_reduce_kernel, tile_v=tile_v, nc=c,
                               combine=combine,
                               has_init=acc_init is not None)
    e_spec, r_spec = edge_spec(tile_e), row_spec(tile_v)
    in_specs = [e_spec, e_spec, e_spec]
    args = [edge_view(src_local), edge_view(msg.astype(dtype)),
            edge_view(wm)]
    if acc_init is not None:
        in_specs.append(r_spec)
        args.append(acc_init.astype(dtype)[:, None, :])
    out = pl.pallas_call(
        kernel,
        grid=(t, c),
        in_specs=in_specs,
        out_specs=r_spec,
        out_shape=jax.ShapeDtypeStruct((t, 1, tile_v), dtype),
        scratch_shapes=[pltpu.VMEM((1, tile_v), dtype)],
        interpret=interpret,
        compiler_params=COMPILER_PARAMS,
    )(*args)
    return out[:, 0]


def pregel_combine_pallas(src_local: jax.Array, msg: jax.Array,
                          wm: jax.Array, vals: jax.Array,
                          valid: jax.Array, base: jax.Array, *,
                          tile_v: int, combine: str, update: str,
                          damping: float = 0.85, interpret: bool = False,
                          acc_init=None) -> tuple:
    """Fused combine + vertex update; ((T, tile_v) new, (T, tile_v) chg).

    ``vals``/``valid``/``base`` are (T, tile_v) rows in tiled order
    (current values, real-vertex mask, and PageRank's ``(1-d)/N``
    teleport row -- zeros for min workloads).  With ``acc_init`` the
    VMEM accumulator is seeded from the interior partial instead of the
    monoid identity, fusing the overlap schedule's second phase.
    """
    t, c, tile_e = src_local.shape
    assert msg.shape == wm.shape == (t, c, tile_e)
    dtype = jnp.float32 if combine == "sum" else jnp.int32
    kernel = functools.partial(_fused_kernel, tile_v=tile_v, nc=c,
                               combine=combine, update=update,
                               damping=float(damping),
                               has_init=acc_init is not None)
    e_spec, r_spec = edge_spec(tile_e), row_spec(tile_v)
    in_specs = [e_spec, e_spec, e_spec, r_spec, r_spec, r_spec]
    args = [edge_view(src_local), edge_view(msg.astype(dtype)),
            edge_view(wm)] + [
        a[:, None, :] for a in (vals.astype(dtype), valid.astype(jnp.int32),
                                base.astype(jnp.float32))]
    if acc_init is not None:
        in_specs.append(r_spec)
        args.append(acc_init.astype(dtype)[:, None, :])
    out, chg = pl.pallas_call(
        kernel,
        grid=(t, c),
        in_specs=in_specs,
        out_specs=[r_spec, r_spec],
        out_shape=[jax.ShapeDtypeStruct((t, 1, tile_v), dtype),
                   jax.ShapeDtypeStruct((t, 1, tile_v), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((1, tile_v), dtype)],
        interpret=interpret,
        compiler_params=COMPILER_PARAMS,
    )(*args)
    return out[:, 0], chg[:, 0]


# ---------------------------------------------------------------------------
# Vertex-order wrappers (gather outside, permute in/out; trace-friendly)
# ---------------------------------------------------------------------------

def combine_tiles_interior(send: jax.Array, src_t: jax.Array,
                           idx_t: jax.Array, wm_t: jax.Array, *,
                           tile_v: int, combine: str, bias: int = 0,
                           interpret: bool = False) -> jax.Array:
    """Interior-segment reduce over the local send vector -> raw partial.

    ``idx_t`` holds LOCAL destination ids (interior edges' dst live on
    their own device by construction), so this phase needs no exchange
    data and runs while the halo collective is in flight.
    """
    msg = send[idx_t]
    if bias:
        msg = msg + bias
    return pregel_reduce_pallas(src_t, msg, wm_t, tile_v=tile_v,
                                combine=combine, interpret=interpret)


def combine_tiles_finish(partial, lookup: jax.Array, values: jax.Array,
                         valid: jax.Array, base, src_t: jax.Array,
                         idx_t: jax.Array, wm_t: jax.Array,
                         perm: jax.Array, inv_perm: jax.Array, *,
                         tile_v: int, combine: str, update: str,
                         damping: float = 0.85, bias: int = 0,
                         interpret: bool = False) -> tuple:
    """Frontier reduce seeded with the interior partial + fused update.

    ``lookup`` is the exchange plan's value table; ``values``/``valid``
    arrive in vertex order and are permuted into the shared tiled row
    layout (``inv_perm``; pad rows -> valid 0).  Returns
    ``(new_values, changed)`` back in vertex order, (v_local,) each.
    """
    t = src_t.shape[0]
    msg = lookup[idx_t]
    if bias:
        msg = msg + bias
    inv_safe = jnp.maximum(inv_perm, 0)
    vals_t = values[inv_safe].reshape(t, tile_v)
    valid_t = jnp.where(inv_perm >= 0, valid[inv_safe],
                        False).reshape(t, tile_v)
    base_t = jnp.full((t, tile_v), base, jnp.float32)
    out_t, chg_t = pregel_combine_pallas(
        src_t, msg, wm_t, vals_t, valid_t, base_t, tile_v=tile_v,
        combine=combine, update=update, damping=damping,
        interpret=interpret, acc_init=partial)
    new = out_t.reshape(-1)[perm]
    chg = chg_t.reshape(-1)[perm].astype(bool)
    return new, chg
