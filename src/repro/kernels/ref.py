"""Pure-jnp oracles for the kernels package."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def scatter_scores(out: jax.Array, rows: jax.Array, lookup: jax.Array,
                   dst: jax.Array, w: jax.Array) -> jax.Array:
    """``out[rows, lookup[dst]] += w``: the gather of each entry's
    neighbour label (device scope ``lpa/gather``) and the scatter-add of
    its weight into the score rows (``lpa/scatter``).

    The scatter goes through one flat index where it fits in int32: the
    TPU compiler lowers a 2-D scatter to a sort and a scatter of its own
    that carry no op name, so their device time would fall outside every
    scope.  The weights are small integers, so the sums are exact in any
    order and both forms give the same scores bit for bit."""
    with jax.named_scope("lpa/gather"):
        nbr = lookup[dst]
    return add_scores(out, rows, nbr, w)


def add_scores(out: jax.Array, rows: jax.Array, cols: jax.Array,
               w: jax.Array) -> jax.Array:
    """``out[rows, cols] += w`` (device scope ``lpa/scatter``): the
    scatter-add half of ``scatter_scores``."""
    with jax.named_scope("lpa/scatter"):
        v, k = out.shape
        if v * k >= 2 ** 31:
            return out.at[rows, cols].add(w)
        return out.reshape(-1).at[rows * k + cols].add(w).reshape(v, k)


def spinner_scores_ref(labels: jax.Array, src: jax.Array, dst: jax.Array,
                       w: jax.Array, num_vertices: int, k: int) -> jax.Array:
    """ComputeScores by scatter-add: scores[u, labels[v]] += w(u, v)."""
    return scatter_scores(jnp.zeros((num_vertices, k), jnp.float32), src,
                          labels, dst, w)


def spinner_scores_tiled_ref(labels: jax.Array, src_local: jax.Array,
                             dst: jax.Array, w: jax.Array, tile_v: int,
                             k: int) -> jax.Array:
    """Oracle operating directly on the tiled-CSR layout (incl. padding)."""
    t, c, tile_e = src_local.shape
    rows = (src_local
            + tile_v * jnp.arange(t, dtype=jnp.int32)[:, None, None]).reshape(-1)
    return scatter_scores(jnp.zeros((t * tile_v, k), jnp.float32), rows,
                          labels, dst.reshape(-1), w.reshape(-1))
