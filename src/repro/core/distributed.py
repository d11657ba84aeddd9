"""Sharded Spinner: the edge-shard layout layer + legacy entry points.

The iteration math no longer lives here.  Pre-PR-2 this module was a fork
of the engine: a hand-rolled per-iteration ``shard_map`` step with its own
copy of the two-phase update and a host halting loop that paid a
``float(score_g)`` sync every superstep -- exactly the distributed
overhead xDGP (1309.1049) and SDP (2110.15669) show must be driven to the
floor for adaptive repartitioning to pay off.  The sharded engine in
``repro.core.engine`` now runs the whole LPA as ONE
``shard_map(lax.while_loop)`` dispatch built on the same
``make_vertex_update`` math as every other engine.  What remains here:

  * ``ShardedGraph`` / ``shard_graph`` -- the padding/layout layer:
    vertices range-partitioned across devices (ceil(V/ndev) contiguous
    ids, tail padded with degree-0 vertices), edges living on their source
    vertex's owner (zero-weight rows pad the shards square);
  * ``shard_layout`` / ``device_upload`` -- the cached layout per
    (graph, ndev) and one cached upload per (layout, array, mesh), each
    row on its own device;
  * ``make_sharded_step`` -- ONE iteration as a jitted ``shard_map``
    dispatch (the engine's step_fn under a per-call ``shard_map``), kept
    for the dispatch-overhead benchmark;
  * ``run_sharded_hostloop`` -- the pre-PR-2 driving mode: one dispatch
    per iteration with a host sync on ``state.halted``.  The halting
    criterion is the on-device ``engine._halting_update`` carried in the
    state, so iteration counts match ``partition(engine="sharded")``
    exactly -- the ONLY difference this driver measures is dispatch/sync
    overhead (see ``benchmarks/bench_engine.py``);
  * ``partition_distributed`` -- back-compat wrapper over
    ``partition(graph, cfg, engine="sharded", mesh=...)`` returning
    (labels, comm stats), the quantities Figure 5 scales.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from . import engine
from .graph import Graph
from .spinner import SpinnerConfig

_SHARD_CACHE: dict = {}   # per graph: (ndev, pad) -> ShardedGraph
_UPLOAD_CACHE: dict = {}  # per ShardedGraph: () -> device edge arrays


@dataclasses.dataclass(frozen=True)
class ShardedGraph:
    """Host-side edge shards, one row per device, interior-first.

    Each device's edge row holds two contiguous segments: columns
    ``[0, e_interior)`` are INTERIOR edges -- their dst vertex is owned
    by the same device, so its label is readable from the local label
    shard without any communication -- and columns ``[e_interior, E)``
    are FRONTIER edges, whose dst label arrives via the exchange plan.
    The split is what lets the sharded step overlap the label collective
    with interior scoring (``EngineOptions.overlap``): only the frontier
    segment depends on the wire.  Within each segment the CSR edge order
    is preserved; ``edge_perm`` records where each slot's edge sat in
    the original ``Graph`` arrays (-1 for padding), so tests can
    reconstruct the permutation exactly.
    """
    num_vertices: int          # padded to ndev multiple
    num_real_vertices: int
    ndev: int
    v_per_dev: int
    src_local: np.ndarray      # (ndev, E_shard) int32, src - owner_offset
    dst: np.ndarray            # (ndev, E_shard) int32 global ids
    weight: np.ndarray         # (ndev, E_shard) f32, 0 = padding
    deg_w: np.ndarray          # (ndev, v_per_dev) f32
    e_interior: int = 0        # static split column (padded segment width)
    interior_counts: Optional[np.ndarray] = None  # (ndev,) real interior
    frontier_counts: Optional[np.ndarray] = None  # (ndev,) real frontier
    edge_perm: Optional[np.ndarray] = None  # (ndev, E_shard) orig idx | -1
    local_only: Optional[int] = None  # set: arrays hold ONE host's row


@dataclasses.dataclass(frozen=True)
class EdgeShardView:
    """One host's edge file as ``shard_graph(local_only=...)`` input.

    The multi-process bootstrap (``repro.cluster.bootstrap``) splits a
    graph's directed-edge list by owning host and writes one file per
    host; a worker process loads ONLY its file, so it never materializes
    the full O(E) edge set.  ``deg_w`` is the full (V,) weighted-degree
    vector -- O(V) vertex state, shipped in the shard manifest alongside
    the globally agreed segment widths so all hosts build
    layout-compatible rows.
    """
    num_vertices: int
    src: np.ndarray
    dst: np.ndarray
    weight: np.ndarray
    deg_w: np.ndarray


def shard_graph(graph, ndev: int, pad: bool = False, *,
                local_only: Optional[int] = None,
                seg_widths: Optional[Tuple[int, int]] = None
                ) -> ShardedGraph:
    """Range-partition vertices and edges into per-device shards.

    Pure layout: contiguous blocks of ceil(V/ndev) vertex ids per
    device, every edge stored with its source's owner and reordered
    ``[interior | frontier]`` (dst owned locally vs. remotely; see
    ``ShardedGraph``).  CSR order is preserved inside each segment, and
    on 1 device every edge is interior, so the shard IS the graph's
    edge list; on any layout the scatter-add totals are bit-identical
    to the unsharded ones because the integer edge weights make f32
    sums exact under reordering.  ``pad`` buckets each segment's width
    so a session rebinding a slightly grown graph keeps the compile
    shape: the interior (bulk) segment gets the usual quarter-step
    ``shape_bucket`` (<= 25% overhead), while the frontier segment is
    rounded to a full power of two -- it is the minority of the shard,
    and its width tracks the boundary SET, which drifts more than the
    edge count under growth, so the coarser steps (<= 2x padding on
    <= ~25% of the edges) halve the bucket boundaries a drifting
    boundary set can cross.  Interior pad slots point at the device's
    own vertex 0 (global id ``p * v_per_dev``) so every dst view --
    global ids, the halo remap, the split local view -- stays in
    bounds; weight 0 makes all pads exact no-ops.

    ``local_only=p`` is the per-host loading path: ``graph`` holds ONLY
    host ``p``'s edges (a :class:`Graph` or an :class:`EdgeShardView`
    from one edge file) and the result carries a single row -- row 0 is
    device ``p``'s shard, byte-identical to row ``p`` of the full-graph
    layout when ``seg_widths`` passes the globally agreed raw
    ``(max interior, max frontier)`` counts (from the shard manifest;
    the bucketing rules above are applied to them identically).  Without
    ``seg_widths`` the widths come from the local counts alone --
    standalone mode, fine when rows are never stacked across hosts.
    """
    from .graph import shape_bucket
    v_per_dev = -(-graph.num_vertices // ndev)
    v_pad = v_per_dev * ndev
    # weight-0 edges (pad_graph's bucket-filler self-loops) are dropped
    # from the layout entirely: they are exact no-ops for every consumer,
    # and excluding them keeps each (device, segment) run's unused slots
    # at the TAIL -- a contiguous per-segment append region the on-device
    # delta merge can scatter new edges into (see repro.core.delta)
    real = graph.weight > 0
    owner_all = graph.src // v_per_dev
    frontier_all = (graph.dst // v_per_dev) != owner_all
    oidx_all = np.arange(graph.src.shape[0], dtype=np.int32)
    owner, frontier = owner_all[real], frontier_all[real]
    if local_only is not None:
        if not 0 <= local_only < ndev:
            raise ValueError(f"local_only={local_only} outside [0, {ndev})")
        if owner.size and not (owner == local_only).all():
            raise ValueError(
                f"local_only={local_only}: edge list contains edges owned "
                f"by hosts {sorted(set(np.unique(owner)) - {local_only})}")
    n_int = np.bincount(owner[~frontier], minlength=ndev).astype(np.int64)
    n_fro = np.bincount(owner[frontier], minlength=ndev).astype(np.int64)
    int_counts, fro_counts = n_int, n_fro
    if local_only is None:
        e_int = int(n_int.max()) if n_int.size else 0
        e_fro = int(n_fro.max()) if n_fro.size else 0
    elif seg_widths is not None:
        e_int, e_fro = int(seg_widths[0]), int(seg_widths[1])
    else:
        e_int, e_fro = int(n_int[local_only]), int(n_fro[local_only])
    if e_int + e_fro == 0:
        e_int = 1                       # keep one (zeroed) slot per shard
    if pad:
        e_int = shape_bucket(e_int, floor=128)
        if e_fro:                       # 1-device shards stay frontier-free
            e_fro = max(128, 1 << (e_fro - 1).bit_length())
    e_shard = e_int + e_fro
    devs = range(ndev) if local_only is None else (local_only,)
    rows = len(devs) if local_only is None else 1
    src_l = np.zeros((rows, e_shard), np.int32)
    w = np.zeros((rows, e_shard), np.float32)
    perm = np.full((rows, e_shard), -1, np.int32)
    # pad slots read the owner's vertex 0 under every dst layout
    dst = np.tile((np.asarray(list(devs), np.int32) * v_per_dev)[:, None],
                  (1, e_shard))
    # stable sort by (owner, frontier flag): per device, the interior run
    # comes first, each run in CSR order
    order = np.argsort(owner.astype(np.int64) * 2 + frontier, kind="stable")
    s = graph.src[real][order]
    d = graph.dst[real][order]
    ww = graph.weight[real][order]
    oidx = oidx_all[real][order]
    starts = np.zeros(2 * ndev + 1, np.int64)
    np.cumsum(np.stack([n_int, n_fro], axis=1).reshape(-1), out=starts[1:])
    for row, p in enumerate(devs):
        for lo, hi, col in ((starts[2 * p], starts[2 * p + 1], 0),
                            (starts[2 * p + 1], starts[2 * p + 2], e_int)):
            n = hi - lo
            src_l[row, col: col + n] = s[lo:hi] - p * v_per_dev
            dst[row, col: col + n] = d[lo:hi]
            w[row, col: col + n] = ww[lo:hi]
            perm[row, col: col + n] = oidx[lo:hi]
    if local_only is None:
        deg = np.zeros(v_pad, np.float32)
        deg[: graph.num_vertices] = graph.deg_w
        deg = deg.reshape(ndev, v_per_dev)
    else:
        # deg_w must be the full (V,) vector; slice this host's range
        p = local_only
        deg = np.zeros((1, v_per_dev), np.float32)
        lo, hi = p * v_per_dev, min((p + 1) * v_per_dev, graph.num_vertices)
        deg[0, : hi - lo] = np.asarray(graph.deg_w)[lo:hi]
        int_counts = n_int[[p]]
        fro_counts = n_fro[[p]]
    return ShardedGraph(num_vertices=v_pad,
                        num_real_vertices=graph.num_vertices, ndev=ndev,
                        v_per_dev=v_per_dev, src_local=src_l, dst=dst,
                        weight=w, deg_w=deg,
                        e_interior=e_int, interior_counts=int_counts,
                        frontier_counts=fro_counts, edge_perm=perm,
                        local_only=local_only)


def shard_layout(graph: Graph, ndev: int, pad: bool = False) -> ShardedGraph:
    """The cached ``ShardedGraph`` layout for a (graph, ndev, pad) tuple."""
    return engine._graph_cached(_SHARD_CACHE, graph, (ndev, pad),
                                lambda: shard_graph(graph, ndev, pad=pad))


def device_upload(sg: ShardedGraph, field: str, mesh, axis: str
                  ) -> jax.Array:
    """One layout array (e.g. ``deg_w``) with each row on its device of
    ``mesh``, cached per (layout, field, mesh, axis).

    Keyed on the ShardedGraph identity (itself cached per (graph, ndev)),
    so runner variants -- different cfg / exchange plan / score backend
    sweeping one graph on one mesh -- share a single device copy.
    """
    return engine._graph_cached(
        _UPLOAD_CACHE, sg, (field, mesh, axis),
        lambda: engine.shard_rows((getattr(sg, field),), mesh, axis)[0])


def comm_stats(sg: ShardedGraph, cfg: SpinnerConfig,
               options: Optional[engine.EngineOptions] = None,
               graph: Optional[Graph] = None) -> dict:
    """Per-iteration communication volume of the sharded engine.

    The label exchange (plan selected by ``options.label_exchange``, see
    ``repro.core.comm``) plus the psum'd (k,) aggregators (M(l), load
    delta, score/migration scalars) -- the quantities Figure 5 scales
    with workers and Figure 7 shows decaying.  ``message_bytes_per_iter``
    is the plan's static message volume; None for the delta plan, whose
    volume is measured on device (``PartitionResult.exchanged_bytes``).

    Passing ``graph`` (the padded view the runner binds) additionally
    resolves the tile autotuner, so the reported ``score_backend`` /
    ``fused_update`` / ``tile_config`` match the compiled program.
    """
    from . import comm, metrics
    opts = options if options is not None else engine.EngineOptions()
    if graph is not None:
        opts = engine._autotuned(graph, cfg, opts, ndev=sg.ndev)
    name = opts.resolved_label_exchange(sg.ndev)
    # same pad flag as the runner's plan (engine._sharded_parts), so this
    # hits the cached plan and halo's padded volume matches what the
    # compiled all_to_all physically moves
    pad = opts.pad == "bucket"
    plan = comm.make_exchange_plan(name, sg, delta_cap=opts.delta_cap,
                                   pad=pad)
    wire = plan.wire_bytes_per_iter()
    stats = {
        "label_exchange": name,
        "overlap": opts.resolved_overlap(sg.ndev),
        "frontier_fraction": metrics.frontier_fraction(sg),
        "message_bytes_per_iter": None if wire is None else int(wire),
        "allgather_bytes_per_iter": int(comm.make_exchange_plan(
            "allgather", sg, pad=pad).wire_bytes_per_iter()),
        "aggregator_bytes_per_iter": int(3 * cfg.k * 4 * sg.ndev),
        "edge_shard_sizes": [int((sg.weight[p] > 0).sum())
                             for p in range(sg.ndev)],
    }
    backend = opts.backend()
    stats["score_backend"] = backend.name
    stats["fused_update"] = opts.resolved_fused_update()
    if backend.name == "pallas":
        from repro.kernels.ops import round_up
        stats["tile_config"] = {"tile_v": backend.tile_v,
                                "tile_e": backend.tile_e,
                                "k_pad": round_up(max(cfg.k, 1), 128)}
    if name == "halo":
        # message_bytes_per_iter above is the TRUE halo volume; this is
        # what the static-shape all_to_all physically moves
        stats["halo_padded_bytes_per_iter"] = \
            plan.padded_wire_bytes_per_iter()
    if name == "delta":
        stats["delta_cap"] = plan.cap
    return stats


def make_sharded_step(graph: Graph, cfg: SpinnerConfig, mesh: Mesh,
                      axis: str = "data",
                      options: Optional[engine.EngineOptions] = None):
    """One LPA iteration as a single jitted ``shard_map`` dispatch.

    ``step(state) -> state`` over the engine's ``SpinnerState`` (padded
    labels).  This is the engine's sharded step_fn without the surrounding
    ``while_loop`` -- the building block of ``run_sharded_hostloop``.
    The step is assembled by the one shared ``engine._sharded_parts``
    code path (``single_step=True`` pins the aux-free allgather oracle
    and the non-overlapped schedule there), and the compiled program is
    cached globally like the engine's runners, so the hostloop driver's
    repeat calls pay dispatch, not retrace/recompile.
    """
    opts = options if options is not None else engine.EngineOptions()
    _, _, prog, args = engine._sharded_parts(graph, cfg, opts, mesh, axis,
                                             single_step=True)

    def run_step(state: engine.SpinnerState) -> engine.SpinnerState:
        return prog.run(state, *args)

    run_step.program = prog
    return run_step


def run_sharded_hostloop(graph: Graph, cfg: SpinnerConfig, mesh: Mesh,
                         axis: str = "data",
                         init: Optional[np.ndarray] = None,
                         options: Optional[engine.EngineOptions] = None
                         ) -> engine.SpinnerState:
    """Drive the sharded step from the host, one dispatch per iteration.

    The pre-PR-2 driving mode, preserved as the dispatch-overhead baseline:
    identical math and identical on-device ``_halting_update`` as
    ``partition(engine="sharded")`` (so labels and iteration counts match
    bit for bit -- both run the same shape-bucketed padded layout), but
    the loop pays a host sync on ``state.halted`` every iteration instead
    of running as one fused ``while_loop``.
    """
    from .spinner import prepare_init, resolve_options
    cfg, opts = resolve_options(cfg, options)
    labels, loads, key = prepare_init(graph, cfg, init)
    v_pad = engine.sharded_v_pad(graph, opts, mesh, axis)
    step = make_sharded_step(graph, cfg, mesh, axis, opts)
    state = engine.init_state(engine.pad_labels(labels, v_pad), loads, key)
    for _ in range(cfg.max_iters):
        state = step(state)
        if bool(state.halted):      # the per-iteration host round-trip
            break
    return state


def partition_distributed(graph: Graph, cfg: SpinnerConfig, mesh: Mesh,
                          axis: str = "data",
                          init: Optional[np.ndarray] = None,
                          options: Optional[engine.EngineOptions] = None,
                          ) -> Tuple[np.ndarray, dict]:
    """Run sharded Spinner to the halting criterion; returns (labels, stats).

    Back-compat wrapper: the run itself is
    ``partition(graph, cfg, engine="sharded", mesh=mesh)`` -- one
    ``while_loop`` dispatch across the mesh, halting unified on
    ``engine._halting_update`` with every other engine.  Stats carry the
    per-iteration communication volume (see ``comm_stats``).
    """
    from .spinner import partition, resolve_options
    cfg, opts = resolve_options(cfg, options)
    res = partition(graph, cfg, init=init, record_history=False,
                    engine="sharded", mesh=mesh, axis=axis, options=opts)
    padded, _ = engine.padded_view(graph, opts)
    sg = shard_layout(padded, mesh.shape[axis], pad=opts.pad == "bucket")
    stats = dict(comm_stats(sg, cfg, opts, graph=padded),
                 iterations=res.iterations,
                 halted=res.halted,
                 exchanged_bytes=res.exchanged_bytes)
    return res.labels, stats
