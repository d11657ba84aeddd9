"""Partition-aware Pregel execution engine: ONE shard_map per run.

The consumer side of Spinner: given any vertex->device placement
(``apps.layout``), run a vertex program to convergence as a single
``jax.jit(jax.shard_map(lax.while_loop))`` dispatch over the device mesh --
the exact architecture of the LPA partitioner engine, re-instantiated
for application state:

  * per superstep every vertex's message value is exchanged through a
    pluggable :class:`repro.core.comm.ExchangePlan` -- the allgather
    oracle, the boundary-only HALO plan (O(cut) values), or the DELTA
    changed-values plan (shrinking-frontier workloads: WCC/BFS send
    only vertices that improved last superstep) -- with per-iteration
    wire bytes accumulated ON DEVICE into the state, exactly as the
    LPA engine's ``exchanged_bytes``;
  * the message combine runs over the layout's [interior | frontier]
    edge split, so the overlap schedule (``start_exchange -> combine
    interior -> finish_exchange -> combine frontier``) is
    dataflow-identical to the sequential one -- bit-identical results,
    collective hidden behind the interior reduction;
  * the combine itself is either XLA scatter ops or the fused Pallas
    combiner (``kernels.pregel_combine``: segmented reduce + vertex
    update per VMEM tile, seeded from the interior partial);
  * programs join the engine's global ``_PROGRAM_CACHE`` keyed on
    static shape/plan/mesh signatures only, so warm re-runs (and the
    hash-vs-spinner A/B on one graph) compile NOTHING new.

Per-device straggler accounting rides in the state: ``msgs[p]`` counts
the messages device p combined (sum of senders' out-degrees), whose
max/mean is the barrier-skew proxy of ``core.pregel``'s simulated-time
model, now measured on device.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec

from repro.core import comm
from repro.core import engine as _engine
from repro.core.graph import Graph, build_sharded_tiled_csr, round_robin_perm
from repro.kernels import ops as kernel_ops
from repro.kernels.pregel_combine import (INF_I32, combine_tiles_finish,
                                          combine_tiles_interior)

from .layout import AppLayout, build_app_layout
from .workloads import APPS, AppSpec, finalize_values, init_active, init_values


class AppState(NamedTuple):
    """The while_loop carry (global view; sharded inside shard_map)."""
    values: jax.Array     # (v_pad,) vertex values, placed+padded order
    changed: jax.Array    # (v_pad,) bool: improved last superstep (senders)
    step: jax.Array       # i32 replicated: supersteps completed
    active: jax.Array     # i32 replicated: global changed count (halting)
    wire: jax.Array       # f32 replicated: cumulative exchanged bytes
    msgs: jax.Array       # (ndev,) f32: messages combined per device


def _app_state_spec(axis: str) -> AppState:
    rep = PartitionSpec()
    return AppState(values=PartitionSpec(axis), changed=PartitionSpec(axis),
                    step=rep, active=rep, wire=rep,
                    msgs=PartitionSpec(axis))


# ---------------------------------------------------------------------------
# Combine closures (per backend x monoid), over the interior/frontier split
# ---------------------------------------------------------------------------

_APP_ARG_CACHE: dict = {}


def _xla_app_args(sg, plan) -> tuple:
    """(si, di, wmi, sf, df, wmf): the split edge blocks with weight MASKS
    (messages combine unweighted; 0 disables layout-padding slots)."""
    e = sg.e_interior
    d_int, d_fro = kernel_ops._split_dst_views(sg, plan.dst_index)
    wm = (np.asarray(sg.weight) > 0).astype(np.float32)
    return (sg.src_local[:, :e], d_int, wm[:, :e],
            sg.src_local[:, e:], d_fro, wm[:, e:])


def _pallas_app_args(sg, plan, tile_v: int, tile_e: int) -> tuple:
    """Two segment tilings sharing ONE ``ext_perm`` row layout (the
    `ops.PallasBackend` split idiom), so the interior partial seeds the
    frontier kernel's accumulator row-for-row."""
    e = sg.e_interior
    d_int, d_fro = kernel_ops._split_dst_views(sg, plan.dst_index)
    ext = np.stack([round_robin_perm(sg.deg_w[p], tile_v)
                    for p in range(sg.ndev)])
    seg_i = dataclasses.replace(sg, src_local=sg.src_local[:, :e],
                                dst=sg.dst[:, :e],
                                weight=sg.weight[:, :e], edge_perm=None)
    seg_f = dataclasses.replace(sg, src_local=sg.src_local[:, e:],
                                dst=sg.dst[:, e:],
                                weight=sg.weight[:, e:], edge_perm=None)
    st_i = build_sharded_tiled_csr(seg_i, d_int, tile_v=tile_v,
                                   tile_e=tile_e, ext_perm=ext)
    st_f = build_sharded_tiled_csr(seg_f, d_fro, tile_v=tile_v,
                                   tile_e=tile_e, ext_perm=ext)
    wm_i = (st_i.weight > 0).astype(np.float32)
    wm_f = (st_f.weight > 0).astype(np.float32)
    return (st_i.src_local, st_i.dst, wm_i, st_f.src_local, st_f.dst, wm_f,
            st_f.perm, st_f.inv_perm)


def _make_combine(spec: AppSpec, backend: str, v_local: int,
                  damping: float, tile_v: int, interpret: bool) -> tuple:
    """(interior, finish): interior reduces the local-dst segment from
    the SEND vector (no exchange data -- runs while the collective is in
    flight); finish folds the frontier segment through the plan's lookup
    and applies the vertex update, returning ``(new_values, changed)``.
    Both schedules call the same pair, so overlap on/off is
    bit-identical."""
    bias = spec.bias
    if backend == "xla":
        if spec.combine == "sum":
            def interior(send, si, di, wi, sf, df, wf):
                return jnp.zeros((v_local,), jnp.float32) \
                          .at[si].add(send[di] * wi)

            def finish(partial, lookup, values, valid, base,
                       si, di, wi, sf, df, wf):
                acc = partial.at[sf].add(lookup[df] * wf)
                new = jnp.where(valid, base + damping * acc, 0.0)
                return new, valid
        else:
            inf = jnp.int32(INF_I32)

            def interior(send, si, di, wi, sf, df, wf):
                cand = jnp.where(wi > 0, send[di] + bias, inf)
                return jnp.full((v_local,), inf, jnp.int32) \
                          .at[si].min(cand)

            def finish(partial, lookup, values, valid, base,
                       si, di, wi, sf, df, wf):
                acc = partial.at[sf].min(
                    jnp.where(wf > 0, lookup[df] + bias, inf))
                new = jnp.where(valid, jnp.minimum(values, acc), values)
                return new, jnp.logical_and(new != values, valid)
        return interior, finish

    update = "pagerank" if spec.combine == "sum" else "min"

    def interior(send, si, ii, wmi, sf, fi, wmf, perm, inv_perm):
        return combine_tiles_interior(send, si, ii, wmi, tile_v=tile_v,
                                      combine=spec.combine, bias=bias,
                                      interpret=interpret)

    def finish(partial, lookup, values, valid, base,
               si, ii, wmi, sf, fi, wmf, perm, inv_perm):
        return combine_tiles_finish(partial, lookup, values, valid, base,
                                    sf, fi, wmf, perm, inv_perm,
                                    tile_v=tile_v, combine=spec.combine,
                                    update=update, damping=damping,
                                    bias=bias, interpret=interpret)

    return interior, finish


# ---------------------------------------------------------------------------
# The compiled app program (one per static signature, globally cached)
# ---------------------------------------------------------------------------

def _app_program(spec: AppSpec, mesh: Mesh, axis: str, plan_sig: tuple,
                 combine_sig: tuple, overlap: bool, n_steps: int,
                 damping: float, n_score: int) -> "_engine.Program":
    """The jitted ``shard_map(while_loop)`` runner for one static
    (workload, mesh, plan signature, combine backend, schedule) tuple.
    Traces against an array-free ``plan_from_signature`` view and joins
    the engine's global ``_PROGRAM_CACHE``, so every graph whose layout
    lands in the same shape bucket -- and both placements of ONE graph
    -- share a single compiled executable."""
    key = ("app", spec.name, spec.combine, spec.bias, spec.halts, mesh,
           axis, plan_sig, combine_sig, overlap, n_steps, float(damping),
           n_score)
    ndev = mesh.shape[axis]

    def build():
        plan = comm.plan_from_signature(plan_sig)
        v_local = plan_sig[2] if plan_sig[0] != "allgather" \
            else plan_sig[2] // ndev
        backend, tile_v, _tile_e, interpret = combine_sig
        interior_fn, finish_fn = _make_combine(
            spec, backend, v_local, damping, tile_v, interpret)
        pagerank = spec.combine == "sum"
        halts = spec.halts
        plan_specs = tuple(plan.arg_specs(axis))
        # sharded args arrive with a leading length-1 shard dim to strip
        strip = (False, True, True) + (True,) * n_score \
            + tuple(s == PartitionSpec(axis) for s in plan_specs)

        def run_local(state, base, counts, deg, *rest):
            blocks = tuple(r[0] if s else r
                           for r, s in zip((base, counts, deg) + rest,
                                           strip))
            base_l, count_l, deg_l = blocks[:3]
            score_blocks = blocks[3:3 + n_score]
            plan_blocks = blocks[3 + n_score:]
            valid = jax.lax.broadcasted_iota(
                jnp.int32, (v_local,), 0) < count_l

            def to_msg(vals):
                return vals / jnp.maximum(deg_l, 1.0) if pagerank else vals

            def body(carry):
                s, aux = carry
                send = to_msg(s.values)
                if overlap:
                    pending = plan.start_exchange(send, aux, axis,
                                                  *plan_blocks)
                    partial = interior_fn(send, *score_blocks)
                    lookup, aux, xb = plan.finish_exchange(pending)
                else:
                    lookup, aux, xb = plan.exchange(send, aux, axis,
                                                    *plan_blocks)
                    partial = interior_fn(send, *score_blocks)
                new, chg = finish_fn(partial, lookup, s.values, valid,
                                     base_l, *score_blocks)
                # messages combined here = senders' out-degrees (each
                # sender's out-edges terminate at exactly one combiner)
                msgs = s.msgs + jnp.sum(
                    deg_l * s.changed.astype(jnp.float32))[None]
                n_act = jax.lax.psum(jnp.sum(chg.astype(jnp.int32)), axis)
                return AppState(values=new, changed=chg, step=s.step + 1,
                                active=n_act, wire=s.wire + xb,
                                msgs=msgs), aux

            def cond(carry):
                s = carry[0]
                go = s.step < jnp.int32(n_steps)
                if halts:
                    go = jnp.logical_and(go, s.active > 0)
                return go

            aux0 = plan.init_aux(to_msg(state.values), axis, *plan_blocks)
            final, _ = jax.lax.while_loop(cond, body, (state, aux0))
            return final

        spec_s = _app_state_spec(axis)
        rep = PartitionSpec()
        arg_specs = (rep, PartitionSpec(axis), PartitionSpec(axis)) \
            + (PartitionSpec(axis),) * n_score + plan_specs
        return jax.jit(jax.shard_map(
            run_local, mesh=mesh, in_specs=(spec_s,) + arg_specs,
            out_specs=spec_s, check_vma=False))

    return _engine._program(key, build)


# ---------------------------------------------------------------------------
# Public entry
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class AppResult:
    """One application run on one placement.

    ``values`` is in ORIGINAL vertex order, oracle-comparable
    (BFS/SSSP: float with inf for unreached).  ``wire_bytes`` is the
    on-device-accumulated total the exchange plan moved;
    ``device_messages`` the per-device combined-message counts whose
    ``straggler_skew`` (max/mean) is the barrier-idle proxy of the
    paper's Table 4 model; ``edge_counts`` the per-device stored-edge
    load.  ``program`` is the cached compiled runner (session compile
    accounting)."""
    workload: str
    plan: str
    ndev: int
    values: np.ndarray
    supersteps: int
    converged: bool
    wire_bytes: float
    wire_bytes_per_step: float
    device_messages: np.ndarray
    straggler_skew: float
    edge_counts: np.ndarray
    program: object = dataclasses.field(repr=False, default=None)


def run_app(graph: Graph, labels: np.ndarray, workload: str, *,
            mesh: Optional[Mesh] = None, axis: str = "data",
            plan: Optional[str] = None, combine: str = "xla",
            overlap: bool = True, iters: Optional[int] = None,
            max_steps: Optional[int] = None, source: int = 0,
            damping: float = 0.85, delta_cap: Optional[int] = None,
            tile_v: int = 128, tile_e: int = 128,
            interpret: Optional[bool] = None) -> AppResult:
    """Run ``workload`` on ``graph`` placed by ``labels`` -- one dispatch.

    ``labels`` is ANY per-vertex assignment: a Spinner partition, or the
    hash baseline (``benchmarks.common.hash_labels``); the layout,
    exchange plan, edge blocks and compiled program are all cached, so
    an A/B between placements costs two dispatches and zero recompiles.

    ``plan`` defaults per workload (halo for PageRank's dense frontier,
    delta for WCC/BFS's shrinking one); ``combine`` picks the XLA
    scatter path or the fused Pallas combiner (``"pallas"``, interpret
    mode off-TPU).  ``overlap`` toggles the in-flight-collective
    schedule (bit-identical either way).
    """
    spec = APPS.get(workload)
    if spec is None:
        raise ValueError(f"unknown workload {workload!r}; "
                         f"available: {', '.join(sorted(APPS))}")
    if mesh is None:
        mesh = _engine._default_partition_mesh()
    ndev = mesh.shape[axis]
    layout = build_app_layout(graph, labels, ndev)
    plan_name = plan or spec.default_plan
    plan_obj = comm.make_exchange_plan(plan_name, layout.sg,
                                       delta_cap=delta_cap, pad=True)
    if spec.halts:
        n_steps = max_steps or spec.default_iters
    else:
        n_steps = iters or spec.default_iters
    if combine == "pallas":
        if interpret is None:
            interpret = kernel_ops._default_interpret()
        combine_sig = ("pallas", tile_v, tile_e, bool(interpret))
        args_of = lambda: _pallas_app_args(layout.sg, plan_obj,
                                           tile_v, tile_e)
    elif combine == "xla":
        combine_sig = ("xla", 0, 0, False)
        args_of = lambda: _xla_app_args(layout.sg, plan_obj)
    else:
        raise ValueError(f"combine must be 'xla' or 'pallas', "
                         f"got {combine!r}")
    dst_layout = "halo" if plan_obj.dst_index is not layout.sg.dst \
        else "global"
    score_args = _engine._graph_cached(
        _APP_ARG_CACHE, layout.sg,
        ("app", combine_sig, dst_layout, mesh, axis),
        lambda: _engine.shard_rows(args_of(), mesh, axis))
    prog = _app_program(spec, mesh, axis, plan_obj.signature(),
                        combine_sig, overlap, n_steps, damping,
                        len(score_args))
    vals0 = init_values(spec, layout, source)
    act0 = init_active(spec, layout, source)
    state0 = AppState(
        values=jnp.asarray(vals0), changed=jnp.asarray(act0),
        step=jnp.int32(0), active=jnp.int32(int(act0.sum())),
        wire=jnp.float32(0),
        msgs=jnp.zeros((ndev,), jnp.float32))
    final = prog.run(state0, jnp.float32((1.0 - damping) / layout.num_real),
                     jnp.asarray(layout.counts), jnp.asarray(layout.deg_cnt),
                     *score_args, *plan_obj.device_args())
    supersteps = int(final.step)
    msgs = np.asarray(final.msgs, np.float64)
    skew = float(msgs.max() / msgs.mean()) if msgs.sum() > 0 else 1.0
    values = finalize_values(spec, layout.unpermute(np.asarray(final.values)))
    return AppResult(
        workload=spec.name, plan=plan_name, ndev=ndev, values=values,
        supersteps=supersteps,
        converged=(not spec.halts) or int(final.active) == 0,
        wire_bytes=float(final.wire),
        wire_bytes_per_step=float(final.wire) / max(supersteps, 1),
        device_messages=msgs, straggler_skew=skew,
        edge_counts=np.asarray(layout.edge_counts, np.int64),
        program=prog)
