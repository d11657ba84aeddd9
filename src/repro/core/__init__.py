"""Spinner core: the paper's contribution as a composable JAX module."""
from . import comm, delta, engine, generators, graph, incremental, metrics, \
    session, trace
from .delta import (DeltaTracker, DeviceDelta, apply_delta,
                    check_edge_updates, coalesce_updates)
from .engine import (EngineOptions, SpinnerState, batch_signature,
                     make_fused_runner,
                     make_chunked_runner, make_frontier_runner,
                     make_iteration, make_sharded_runner,
                     make_step_fn, make_vertex_update, run_batched,
                     run_chunked, run_fused,
                     run_frontier, run_sharded, run_sharded_frontier)
from .graph import (Graph, TiledCSR, add_edges, build_tiled_csr, from_edges,
                    pad_graph, shape_bucket)
from .incremental import adapt, elastic_relabel, extend_labels, resize
from .metrics import (comm_volume, frontier_fraction,
                      partitioning_difference, phi, phi_weighted, rho,
                      score_global, summarize)
from .session import PartitionSession, open_session
from .spinner import (PartitionResult, SpinnerConfig,
                      SpinnerDeprecationWarning, compute_loads, init_labels,
                      make_step, partition, prepare_init, resolve_options)

__all__ = [
    "Graph", "TiledCSR", "from_edges", "add_edges", "build_tiled_csr",
    "pad_graph", "shape_bucket",
    "SpinnerConfig", "SpinnerDeprecationWarning", "EngineOptions",
    "PartitionResult", "PartitionSession", "open_session", "SpinnerState",
    "DeltaTracker", "DeviceDelta", "apply_delta", "check_edge_updates",
    "coalesce_updates", "run_batched", "batch_signature",
    "partition", "prepare_init", "resolve_options", "make_step",
    "make_step_fn", "make_iteration", "make_vertex_update",
    "make_fused_runner", "make_chunked_runner", "make_frontier_runner",
    "make_sharded_runner",
    "run_fused", "run_chunked", "run_sharded", "run_frontier",
    "run_sharded_frontier", "init_labels",
    "compute_loads", "adapt", "resize", "elastic_relabel", "extend_labels",
    "phi", "phi_weighted", "rho", "score_global", "comm_volume",
    "frontier_fraction",
    "partitioning_difference", "summarize", "comm", "delta", "engine",
    "generators", "graph", "metrics", "incremental", "session", "trace",
]
