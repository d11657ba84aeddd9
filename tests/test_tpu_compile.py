"""Compile rehearsals of the Pallas kernels for a TPU v5e, without the chip.

Each case lowers one kernel at the widths of a 4M-vertex graph, twice
the one-chip smoke's default (``chip_smoke.py``): 32,768 vertex tiles of
128 rows, k = 64 padded to 128 lanes, and the chunk counts the tilings
give there -- and compiles it for one chip of a described ``v5e:2x2``
topology.  The TPU compiler refuses here what the chip would refuse
(block shapes off the (8, 128) tiling, unknown compiler parameters, VMEM
overflow); interpret mode on the CPU sees none of it.

Two more cases compile the XLA score passes at the widths of the
benchmark's 1M-vertex graph and check that the TPU compiler's sort and
scatter keep the ``lpa/scatter`` scope the program gives them
(``kernels/ref.py``), and that the transposed pass (``kernels/ops.py``)
reads no entry's label through a gather.

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
from repro.kernels.ops import transposed_scores
from repro.kernels.ref import spinner_scores_ref
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


def test_xla_scores_keep_their_scopes_on_v5e(one_chip):
    """Every sort, scatter and gather the compiler emits for the score
    path names its scope: a 2-D scatter would lose it to the sort-based
    rewrite, and 30-40% of an LPA iteration's device time with it."""
    import re
    v, e, k = 1_048_576, 33_554_432, 64

    def fn(labels, src, dst, w):
        return spinner_scores_ref(labels, src, dst, w, v, k)

    args = [jax.ShapeDtypeStruct((n,), dt, sharding=one_chip)
            for n, dt in ((v, jnp.int32), (e, jnp.int32), (e, jnp.int32),
                          (e, jnp.float32))]
    text = jax.jit(fn).lower(*args).compile().as_text()
    ops = [line for line in text.splitlines()
           if re.search(r"\b(sort|scatter|gather)\(", line)
           or "kind=kCustom" in line]
    assert ops
    for line in ops:
        name = re.search(r'op_name="([^"]*)"', line)
        assert name and re.search(r"lpa/(gather|scatter)", name.group(1)), \
            line[:160]


def test_transposed_scores_gather_nothing_on_v5e(one_chip):
    """The transposed pass at the benchmark's widths: no gather over the
    33.5M entries, the row expansion under ``lpa/gather`` and the sort
    and scatter-add under ``lpa/scatter``."""
    import re
    v, e, k = 1_048_576, 33_554_432, 64

    def fn(labels, dst, w, row_ptr):
        return transposed_scores(labels, dst, w, row_ptr, k)

    args = [jax.ShapeDtypeStruct((n,), dt, sharding=one_chip)
            for n, dt in ((v, jnp.int32), (e, jnp.int32), (e, jnp.float32),
                          (v + 1, jnp.int32))]
    text = jax.jit(fn).lower(*args).compile().as_text()
    lines = text.splitlines()
    assert not [line for line in lines if re.search(r"\bgather\(", line)]
    scoped = {}
    for line in lines:
        op = re.search(r"\b(sort|scatter|reduce-window)\(", line)
        if op:
            name = re.search(r'op_name="[^"]*(lpa/(gather|scatter))', line)
            assert name, line[:160]
            scoped.setdefault(op.group(1), set()).add(name.group(1))
    assert scoped["sort"] == {"lpa/scatter"}
    assert scoped["reduce-window"] == {"lpa/gather"}
    assert "lpa/scatter" in scoped["scatter"]
