"""Compile rehearsals of the Pallas kernels for a TPU v5e, without the chip.

Each case lowers one kernel at the widths of a 4M-vertex graph, twice
the one-chip smoke's default (``chip_smoke.py``): 32,768 vertex tiles of
128 rows, k = 64 padded to 128 lanes, and the chunk counts the tilings
give there -- and compiles it for one chip of a described ``v5e:2x2``
topology.  The TPU compiler refuses here what the chip would refuse
(block shapes off the (8, 128) tiling, unknown compiler parameters, VMEM
overflow); interpret mode on the CPU sees none of it.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, so the test workers must
all collect the same tests and only the one running this file loads it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.pregel_combine import (pregel_combine_pallas,
                                          pregel_reduce_pallas)
from repro.kernels.spinner_scores import (fused_update_pallas,
                                          spinner_scores_pallas)

T = 32_768          # vertex tiles at V = 4,194,304 padded vertices
TILE_V, K, K_PAD = 128, 64, 128
SCORE_TILE_E, SCORE_C = 512, 8    # the autotuned score tiling at 4M
APP_TILE_E, APP_C = 128, 32       # the application combiner's tiling


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _score_case(variant: str):
    e_i = ((T, SCORE_C, SCORE_TILE_E), jnp.int32)
    e_f = ((T, SCORE_C, SCORE_TILE_E), jnp.float32)
    if variant == "split":
        def fn(src, lbl, w):
            return spinner_scores_pallas(src, lbl, w, tile_v=TILE_V,
                                         k_pad=K_PAD)
        return fn, [e_i, e_i, e_f]
    rows = [((T, TILE_V), jnp.int32), ((T, TILE_V), jnp.float32),
            ((T, TILE_V), jnp.int32), ((1, K_PAD), jnp.float32),
            ((T * TILE_V, K_PAD), jnp.float32)]
    extra = {"fused": [], "fused_acc_init": [((T * TILE_V, K_PAD),
                                              jnp.float32)],
             "fused_tile_act": [((T, 1), jnp.int32)]}[variant]

    def fn(*args):
        kw = {}
        if variant == "fused_acc_init":
            kw["acc_init"] = args[8]
        if variant == "fused_tile_act":
            kw["tile_act"] = args[8]
        return fused_update_pallas(*args[:8], tile_v=TILE_V, k_pad=K_PAD,
                                   k=K, current_bonus=1e-6,
                                   degree_weighted=True, **kw)
    return fn, [e_i, e_i, e_f] + rows + extra


def _combine_case(variant: str):
    kernel, combine = variant.split("_")
    msg = jnp.float32 if combine == "sum" else jnp.int32
    edges = [((T, APP_C, APP_TILE_E), jnp.int32),
             ((T, APP_C, APP_TILE_E), msg),
             ((T, APP_C, APP_TILE_E), jnp.float32)]
    if kernel == "reduce":
        def fn(src, m, wm):
            return pregel_reduce_pallas(src, m, wm, tile_v=TILE_V,
                                        combine=combine)
        return fn, edges
    update = "pagerank" if combine == "sum" else "min"

    def fn(src, m, wm, vals, valid, base, init):
        return pregel_combine_pallas(src, m, wm, vals, valid, base,
                                     tile_v=TILE_V, combine=combine,
                                     update=update, acc_init=init)
    rows = [((T, TILE_V), msg), ((T, TILE_V), jnp.int32),
            ((T, TILE_V), jnp.float32), ((T, TILE_V), msg)]
    return fn, edges + rows


@pytest.mark.parametrize("variant", ["split", "fused", "fused_acc_init",
                                     "fused_tile_act", "reduce_sum",
                                     "reduce_min", "combine_sum",
                                     "combine_min"])
def test_kernel_compiles_for_v5e(one_chip, variant):
    make = _combine_case if variant.startswith(("reduce", "combine")) \
        else _score_case
    fn, specs = make(variant)
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
