"""Device-resident Spinner LPA engine (program / bind / runner layering).

The legacy driver in ``spinner.py`` round-trips to the host every iteration
(``float(score_g)`` sync, host PRNG splitting, per-iteration numpy history),
so on small graphs wall-clock is dominated by dispatch latency rather than
the ComputeScores kernel.  This module keeps the whole run on device, and --
since PR 4 -- separates WHAT is compiled from WHICH graph it runs on:

  * ``SpinnerState`` -- a pure functional pytree carrying everything one LPA
    iteration reads or writes: labels, loads, the PRNG key, the Eq. 9
    halting aggregates (best_score / stall), iteration counter, and the
    migration statistics of the last step.
  * **Programs** -- jitted executables cached GLOBALLY per static
    configuration (``_PROGRAM_CACHE``): the paper parameters that enter
    the trace (k, eps, halt_window, max_iters, weighting, noise
    amplitudes), the score-backend signature, and -- for the sharded
    runner -- the mesh, axis and exchange-plan signature.  A program
    closes over NO graph data; every per-graph array arrives as a traced
    argument, so two graphs with the same compile shapes share one
    executable and a run on a new graph costs an upload, not a compile.
  * **Binds** (``GraphBind``) -- the per-graph argument pytree: weighted
    degrees, the Eq. 5 capacity C and the real vertex count as traced
    scalars, the score backend's edge arrays, and (for the chunked
    history) the raw edge list.  Padding vertices/edges introduced by the
    shape-bucket layer (``graph.pad_graph``; see ``repro.core.session``)
    are masked out of every migration/halting aggregate by a ``valid``
    mask derived from the traced real-vertex count.
  * ``run_fused`` -- the entire run as a single ``jax.lax.while_loop``
    dispatch; ``run_chunked`` -- ``chunk_size`` iterations per dispatch
    with fixed-size on-device history; ``run_sharded`` -- the fused loop
    over a DEVICE MESH in ONE ``shard_map(lax.while_loop)`` dispatch,
    with (k,) aggregates psum-reduced in the step, the halting decision
    on device, and a pluggable per-iteration label exchange
    (``repro.core.comm``: all-gather oracle / boundary halo / Figure 7
    delta), wire bytes accumulated in ``SpinnerState.exchanged_bytes``.
    Under ``EngineOptions.overlap`` the sharded step splits each edge
    shard at ``ShardedGraph.e_interior`` and reschedules to
    start_exchange -> score_interior -> finish_exchange ->
    score_frontier, overlapping the collective with the
    exchange-independent majority of ComputeScores -- bit-identical
    to the sequential schedule.
    All runners share ``make_vertex_update`` (Eqs. 7-8, 11-12) and
    ``_halting_update``, so for one padded layout every engine walks the
    same trajectory bit for bit.

``EngineOptions`` is the runtime half of the old ``SpinnerConfig``: engine
choice, mesh/axis, score backend, exchange plan, chunking and the shape-pad
policy.  ``repro.core.session.PartitionSession`` owns a (graph, cfg,
options) triple and drives these programs across a stream of
partition/adapt/resize calls; ``spinner.partition`` opens a throwaway
session, so one-shot calls and long-lived sessions execute the exact same
compiled programs.
"""
from __future__ import annotations

import weakref
import dataclasses
from typing import (Callable, List, NamedTuple, Optional, Sequence, Tuple,
                    Union)

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .graph import Graph, pad_graph, shape_bucket

DEFAULT_CHUNK = 32

# Shape-bucket floors: graphs below these sizes all share one bucket.
V_FLOOR = 64
E_FLOOR = 128

# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------
# Programs are cached globally by STATIC configuration -- they hold no graph
# data, so entries are small (a jitted callable) and survive their graphs.
# Everything graph-shaped lives in weakref-guarded per-graph caches keyed on
# id(graph) + a suffix, evicted when the graph dies so a recycled id() can
# never alias.

_PROGRAM_CACHE: dict = {}     # static key -> Program
_SCORE_ARG_CACHE: dict = {}   # per graph/layout: backend edge-array uploads
_EDGE_UPLOAD_CACHE: dict = {} # per graph: (src, dst, weight, deg_w) on device
_PAD_CACHE: dict = {}         # per graph: (v_bucket, e_bucket) -> padded view


def _graph_cached(cache: dict, graph, suffix: tuple,
                  build: Callable[[], object]):
    """Memoize ``build()`` per (graph, suffix); evicted when graph dies."""
    key = (id(graph),) + suffix
    entry = cache.get(key)
    if entry is not None and entry[0]() is graph:
        return entry[1]
    value = build()
    cache[key] = (weakref.ref(graph, lambda _: cache.pop(key, None)), value)
    return value


def shard_rows(arrays, mesh: Mesh, axis: str) -> tuple:
    """Commit (ndev, ...) host layout arrays row-per-device to ``mesh``.

    A whole-array upload (``jnp.asarray``) lands on the default device:
    every dispatch of a sharded program then copies the shards out again,
    and the default device holds every shard for the life of the cache
    entry.
    """
    sharding = NamedSharding(mesh, PartitionSpec(axis))
    return tuple(jax.device_put(a, sharding) for a in arrays)


@dataclasses.dataclass
class Program:
    """A compiled (shape-polymorphic) runner plus its cache identity."""

    run: Callable
    key: Optional[tuple] = None

    def compiles(self) -> int:
        """Number of traced/compiled entries behind this program."""
        size = getattr(self.run, "_cache_size", None)
        return int(size()) if size is not None else 0


# Each cached program retains its jit-compiled executables, so a config
# sweep must not grow the cache forever: FIFO-evict past the cap (live
# runners/sessions keep their own references; a re-request just
# rebuilds and recompiles).
_PROGRAM_CACHE_MAX = 128


def _program(key: tuple, build: Callable[[], Callable]) -> Program:
    prog = _PROGRAM_CACHE.get(key)
    if prog is None:
        while len(_PROGRAM_CACHE) >= _PROGRAM_CACHE_MAX:
            _PROGRAM_CACHE.pop(next(iter(_PROGRAM_CACHE)))
        prog = _PROGRAM_CACHE[key] = Program(run=build(), key=key)
    return prog


def _static_cfg(cfg) -> tuple:
    """The paper parameters that enter a program's trace.

    ``seed`` feeds host-side PRNGKey creation only and ``c`` only enters
    via the traced capacity scalar, so seed/slack sweeps share programs.
    """
    return (cfg.k, float(cfg.eps), cfg.halt_window, cfg.max_iters,
            cfg.migration_weighting, float(cfg.tie_noise),
            float(cfg.current_bonus))


# ---------------------------------------------------------------------------
# Engine options (the runtime half of the old SpinnerConfig)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class EngineOptions:
    """How a Spinner run executes -- everything that is NOT a paper
    parameter: runner choice, device layout, score backend, exchange
    plan, chunking and the compile-shape policy.  ``SpinnerConfig`` keeps
    only the algorithm (Sections 3.1-3.5); the old config fields for
    these knobs survive as a deprecation shim (see ``repro.core.spinner``).

    ``pad="bucket"`` (default) runs every engine on a power-of-two-ish
    padded (V, E) layout (``graph.shape_bucket``), which is what lets a
    ``PartitionSession`` -- and the one-shot wrappers, which open
    throwaway sessions -- reuse one compiled program across all graphs
    in a bucket.  ``pad="none"`` keeps exact shapes (one compile per
    graph size, marginally less memory/compute per step).
    """

    engine: str = "auto"             # auto | fused | chunked | sharded | host
    chunk_size: Optional[int] = None
    mesh: Optional[Mesh] = None
    axis: str = "data"
    # ComputeScores backend: "xla" | "pallas" or a ScoreBackend instance.
    score_backend: Union[str, object] = "xla"
    # Sharded label exchange (repro.core.comm): "allgather" ships the full
    # label vector per iteration (the bit-compatible oracle), "halo" only
    # boundary labels, "delta" only changed labels (the Figure 7 decay).
    # All walk identical trajectories; "auto" picks allgather on 1 device
    # and delta on a real mesh.
    label_exchange: str = "auto"
    # Per-device compact-buffer capacity of the delta exchange (entries);
    # None = v_per_dev // 4.
    delta_cap: Optional[int] = None
    # "replicated" draws tie-break noise over the full padded vertex set
    # (bit parity with the single-device engines); "folded" draws only
    # the local shard from a device-folded key (O(V/ndev) memory).
    sharded_noise: str = "replicated"
    # Sharded step schedule.  "on" splits each device's edge shard at
    # ShardedGraph.e_interior and reschedules the step as start_exchange
    # -> score_interior -> finish_exchange -> score_frontier: only the
    # frontier segment depends on remote labels, so the label collective
    # and the interior scatter-add/matmul are dataflow-independent and
    # can run concurrently.  Bit-identical to "off" for every exchange
    # plan and score backend (integer edge weights make the f32 partial
    # sums exact under the segment split).  "auto" = on over a real
    # mesh, off on a single device (nothing to overlap).
    overlap: str = "auto"            # auto | on | off
    # Fused vertex update.  "on" asks the score backend for its
    # make_fused_update entry: the edge reduction, Eq. 7-8 normalization,
    # tie-noise argmax and migration bookkeeping run inside ONE kernel and
    # the (V_pad, k) score matrix never touches HBM (see
    # kernels/spinner_scores._fused_kernel); only the O(V + k) epilogue
    # (make_update_parts's ``finish``) runs as XLA ops.  Bit-identical to
    # "off" for every engine, exchange plan and overlap schedule (integer
    # Eq. 3 weights; same op order; same noise/u streams).  "auto" = on
    # iff the backend advertises ``fused_auto`` (the Pallas backend does;
    # XLA's scatter path gains nothing from fusing by hand).
    fused_update: str = "auto"       # auto | on | off
    # Tile autotuning for the Pallas backend: sweep the
    # kernels.autotune.CANDIDATES (tile_v, tile_e) configs against a
    # static roofline cost model of the actual degree distribution and
    # bind the winner (a dataclasses.replace of the backend, so it flows
    # into every program/arg cache key like any other backend).  The
    # choice is memoized per padded (V, E, k_pad, ndev) bucket -- the
    # first graph in a bucket decides -- so a session's warm same-bucket
    # adapt() never flips config and costs zero new compiles.  "auto"
    # tunes the registry default ("pallas" by name); explicit
    # PallasTiledBackend instances pin their tile config unless "on".
    autotune: str = "auto"           # auto | on | off
    pad: str = "bucket"              # bucket | none

    def resolved_label_exchange(self, ndev: int) -> str:
        from .comm import EXCHANGE_PLANS     # the one plan registry
        if self.label_exchange == "auto":
            return "allgather" if ndev == 1 else "delta"
        if self.label_exchange not in EXCHANGE_PLANS:
            raise ValueError(
                f"unknown label_exchange {self.label_exchange!r}; "
                f"available: auto, {', '.join(sorted(EXCHANGE_PLANS))}")
        return self.label_exchange

    def resolved_sharded_noise(self) -> str:
        if self.sharded_noise not in ("replicated", "folded"):
            raise ValueError(
                f"unknown sharded_noise {self.sharded_noise!r}; "
                "available: replicated, folded")
        return self.sharded_noise

    def resolved_overlap(self, ndev: int) -> str:
        if self.overlap == "auto":
            return "on" if ndev > 1 else "off"
        if self.overlap not in ("on", "off"):
            raise ValueError(f"unknown overlap {self.overlap!r}; "
                             "available: auto, on, off")
        return self.overlap

    def resolved_fused_update(self) -> str:
        if self.fused_update not in ("auto", "on", "off"):
            raise ValueError(f"unknown fused_update {self.fused_update!r}; "
                             "available: auto, on, off")
        if self.fused_update == "off":
            return "off"
        backend = self.backend()
        has = callable(getattr(backend, "make_fused_update", None))
        if self.fused_update == "auto":
            return "on" if (has and getattr(backend, "fused_auto", False)) \
                else "off"
        if not has:
            raise ValueError(
                f"score backend {getattr(backend, 'name', backend)!r} has "
                "no fused vertex-update entry (make_fused_update); use "
                "fused_update='auto'/'off' or a backend implementing the "
                "fused protocol")
        return "on"

    def resolved_autotune(self) -> str:
        if self.autotune not in ("auto", "on", "off"):
            raise ValueError(f"unknown autotune {self.autotune!r}; "
                             "available: auto, on, off")
        return self.autotune

    def backend(self):
        from repro.kernels import ops as kernel_ops   # lazy: no import cycle
        return kernel_ops.get_score_backend(self.score_backend)


_DEFAULT_OPTS = EngineOptions()
_UNPADDED_OPTS = EngineOptions(pad="none")


# ---------------------------------------------------------------------------
# State
# ---------------------------------------------------------------------------

class SpinnerState(NamedTuple):
    """Carry of the fused LPA loop -- one pytree, fully device-resident."""

    labels: jax.Array          # (V,) int32 current assignment
    loads: jax.Array           # (k,) float32 B(l) (Eq. 6), running update
    key: jax.Array             # PRNG key consumed by splitting each iter
    best_score: jax.Array      # f32 scalar, best score(G) so far (Eq. 9)
    stall: jax.Array           # int32, consecutive non-improving iterations
    iteration: jax.Array       # int32, iterations completed
    halted: jax.Array          # bool, eps/halt_window criterion fired
    total_messages: jax.Array  # f32, cumulative migrant degree mass
    score: jax.Array           # f32, score(G) after the last iteration
    migrations: jax.Array      # int32, migrating vertices last iteration
    message_mass: jax.Array    # f32, migrant degree mass last iteration
    exchanged_bytes: jax.Array # f32, cumulative label-exchange wire bytes
                               # (0 off the sharded engine; see core.comm)


def init_state(labels: jax.Array, loads: jax.Array,
               key: jax.Array) -> SpinnerState:
    return SpinnerState(
        labels=jnp.asarray(labels, jnp.int32),
        loads=jnp.asarray(loads, jnp.float32),
        key=key,
        best_score=jnp.float32(-jnp.inf),
        stall=jnp.int32(0),
        iteration=jnp.int32(0),
        halted=jnp.asarray(False),
        total_messages=jnp.float32(0.0),
        score=jnp.float32(0.0),
        migrations=jnp.int32(0),
        message_mass=jnp.float32(0.0),
        exchanged_bytes=jnp.float32(0.0),
    )


class GraphBind(NamedTuple):
    """Per-graph traced arguments of the single-device programs.

    Uploaded/derived once per (graph, backend, pad policy) and passed to
    the program on every call -- the program itself never closes over
    them, which is what makes compile reuse across graphs possible.
    """

    deg_w: jax.Array           # (V_pad,) f32 weighted degrees (0 on pads)
    capacity: jax.Array        # f32 scalar C (Eq. 5) of the REAL graph
    num_real: jax.Array        # int32 scalar: vertices < num_real are real
    score: tuple               # score backend's edge arrays
    hist: tuple = ()           # (src, dst, w, ideal, real_e) for history
    frontier: tuple = ()       # (src, dst) COO expansion index, frontier mode


# ---------------------------------------------------------------------------
# Shape-bucketed padded views
# ---------------------------------------------------------------------------

def graph_buckets(graph: Graph) -> Tuple[int, int]:
    """(vertex bucket, edge bucket) the graph's compile shapes land in."""
    return (shape_bucket(graph.num_vertices, V_FLOOR),
            shape_bucket(graph.num_directed_entries, E_FLOOR))


def padded_view(graph: Graph, opts: EngineOptions) -> Tuple[Graph, int]:
    """(padded graph, real vertex count) under the options' pad policy.

    The padded view is cached per (graph, buckets) and dies with the
    graph; with ``pad="none"`` the graph itself is returned.
    """
    if opts.pad == "none":
        return graph, graph.num_vertices
    if opts.pad != "bucket":
        raise ValueError(f"unknown pad policy {opts.pad!r}; "
                         "available: bucket, none")
    vb, eb = graph_buckets(graph)
    padded = _graph_cached(_PAD_CACHE, graph, (vb, eb),
                           lambda: pad_graph(graph, vb, eb))
    return padded, graph.num_vertices


def device_edges(graph: Graph):
    """(src, dst, weight, deg_w) as device arrays, uploaded once per Graph.

    Shared by every runner variant and the XLA score backend: a config
    sweep over one graph would otherwise hold one 2*E copy of
    src/dst/weight per variant.
    """
    return _graph_cached(
        _EDGE_UPLOAD_CACHE, graph, (),
        lambda: (jnp.asarray(graph.src), jnp.asarray(graph.dst),
                 jnp.asarray(graph.weight), jnp.asarray(graph.deg_w)))


def pad_labels(labels: jax.Array, v_pad: int) -> jax.Array:
    """Extend labels to a padded vertex count (pads land on partition 0;
    they are masked out of every aggregate and never migrate)."""
    labels = jnp.asarray(labels, jnp.int32)
    pad = v_pad - labels.shape[0]
    if pad:
        labels = jnp.concatenate([labels, jnp.zeros((pad,), jnp.int32)])
    return labels


def _single_bind(graph: Graph, cfg, opts: EngineOptions,
                 hist: bool = False,
                 score_fn: Optional[Callable] = None,
                 frontier: bool = False
                 ) -> Tuple[GraphBind, Graph]:
    """Build (or fetch cached pieces of) the bind for a one-device run."""
    padded, num_real = padded_view(graph, opts)
    deg_w = device_edges(padded)[3]
    if score_fn is not None:
        score_args = ()
    else:
        backend = opts.backend()
        pad = opts.pad == "bucket"
        fused = opts.resolved_fused_update() == "on"
        args_of = backend.fused_graph_args if fused else backend.graph_args
        score_args = _graph_cached(
            _SCORE_ARG_CACHE, padded,
            ("single", backend.signature(), pad, fused),
            lambda: tuple(args_of(padded, cfg.k, pad=pad)))
    if hist and graph.src.size:
        src, dst, w, _ = device_edges(padded)
        hist_args = (src, dst, w,
                     jnp.float32(graph.total_weight / cfg.k),
                     jnp.float32(graph.num_directed_entries))
    else:
        hist_args = ()
    # The padded COO (cached upload) doubles as the frontier expansion
    # index: pad entries are weight-0 self-loops on pad vertices, which
    # never change label, so they can never activate anything.
    frontier_args = device_edges(padded)[:2] if frontier else ()
    return GraphBind(deg_w=deg_w,
                     capacity=jnp.float32(cfg.capacity(graph)),
                     num_real=jnp.int32(num_real),
                     score=score_args, hist=hist_args,
                     frontier=frontier_args), padded


def _autotuned(graph: Graph, cfg, opts: EngineOptions,
               ndev: int = 1) -> EngineOptions:
    """Options with the tile autotuner's (tile_v, tile_e) choice applied.

    Only the Pallas backend is tunable; the winner is bound by
    ``dataclasses.replace`` on the backend instance, so it flows into
    ``signature()`` and thence every program / score-arg cache key -- an
    autotuned config is cached exactly like a hand-picked one.  The
    choice is memoized per padded (V, E, k_pad, ndev) shape
    (``kernels.autotune``), so every graph in a shape bucket resolves to
    ONE config and warm session rebinds stay compile-free.  Under
    ``autotune="auto"`` explicit backend INSTANCES are left alone (they
    pin their tile config); ``"on"`` tunes those too.
    """
    mode = opts.resolved_autotune()
    if mode == "off":
        return opts
    if mode == "auto" and not isinstance(opts.score_backend, str):
        return opts
    backend = opts.backend()
    if getattr(backend, "name", None) != "pallas":
        return opts
    from repro.kernels import autotune as _tune   # lazy: no import cycle
    padded, _ = padded_view(graph, opts)
    tile_v, tile_e, _kp = _tune.choose_tile_config(padded, cfg.k, ndev=ndev)
    if (tile_v, tile_e) == (backend.tile_v, backend.tile_e):
        return opts
    return dataclasses.replace(opts, score_backend=dataclasses.replace(
        backend, tile_v=tile_v, tile_e=tile_e))


# ---------------------------------------------------------------------------
# The iteration math (shared verbatim by every engine)
# ---------------------------------------------------------------------------

def make_update_parts(k: int, *, degree_weighted: bool,
                      current_bonus: float) -> Tuple[Callable, Callable]:
    """The vertex update split at its one global synchronization point.

    ``propose(scores, labels, deg_w, loads, noise, valid, C)`` is the
    per-vertex half -- Eq. 7-8 normalization, penalty, current-label
    bonus and tie-noise argmax plus the local migration-candidate mass
    partial -- returning ``(best, tot_best, tot_cur, m_partial)``:
    the proposed label, the Eq. 8 total at the proposal and at the
    current label, and the un-reduced (k,) M(l) contribution.  A fused
    score backend computes these INSIDE its kernel (the (V, k) score
    matrix never materializes); this reference form shares its exact op
    sequence so the two are bit-identical.

    ``finish(best, tot_best, tot_cur, m_partial, labels, deg_w, loads,
    u, valid, reduce_, C)`` is the epilogue that needs the globally
    reduced M(l): the Eq. 11-12 probability test, the load delta, and
    the score(G)/migration aggregates.  O(V + k) -- no (V, k) operand.

    ``reduce_`` is identity on a single device and ``lax.psum`` under
    ``shard_map`` (the Giraph sharded aggregators as one collective
    each); ``valid`` masks padding vertices (``None`` statically skips
    the masking ops).
    """

    def propose(scores, labels, deg_w, loads, noise, valid, C):
        # ---- ComputeScores (Eq. 8) -------------------------------------
        norm = scores / jnp.maximum(deg_w, 1.0)[:, None]
        penalty = loads / C                                # pi(l) (Eq. 7)
        total = norm - penalty[None, :]
        bonus = current_bonus * jax.nn.one_hot(labels, k,
                                               dtype=jnp.float32)
        best = jnp.argmax(total + noise + bonus, axis=1).astype(jnp.int32)
        want = best != labels
        if valid is not None:
            want = want & valid
        measure = deg_w if degree_weighted else jnp.ones_like(deg_w)
        m_partial = jnp.zeros((k,), jnp.float32).at[best].add(
            jnp.where(want, measure, 0.0))
        tot_best = jnp.take_along_axis(total, best[:, None], axis=1)[:, 0]
        tot_cur = jnp.take_along_axis(total, labels[:, None],
                                      axis=1)[:, 0]
        return best, tot_best, tot_cur, m_partial

    def finish(best, tot_best, tot_cur, m_partial, labels, deg_w, loads,
               u, valid, reduce_, C):
        want = best != labels
        if valid is not None:
            want = want & valid

        # ---- ComputeMigrations (Eq. 11-12) -----------------------------
        M = reduce_(m_partial)                             # aggregator
        R = jnp.maximum(C - loads, 0.0)                    # Eq. 11
        p = jnp.clip(R / jnp.maximum(M, 1e-9), 0.0, 1.0)   # Eq. 12
        migrate = want & (u < p[best])

        new_labels = jnp.where(migrate, best, labels)
        mig_deg = jnp.where(migrate, deg_w, 0.0)
        delta = (jnp.zeros((k,), jnp.float32)
                 .at[best].add(mig_deg)
                 .at[labels].add(-mig_deg))
        new_loads = loads + reduce_(delta)                 # aggregator

        # ---- halting aggregate: score(G) at the new assignment (Eq. 9) --
        # total[v, new_labels[v]] == tot_best where migrating else tot_cur
        sel = jnp.where(migrate, tot_best, tot_cur)
        if valid is not None:
            sel = jnp.where(valid, sel, 0.0)
        score_g = reduce_(jnp.sum(sel))                    # aggregator
        # migration mass = sum of migrant degrees = Pregel messages sent
        # (each migrating vertex notifies all neighbors, Section 4.1.3)
        n_mig = reduce_(jnp.sum(migrate).astype(jnp.int32))
        mig_mass = reduce_(jnp.sum(mig_deg))
        return new_labels, new_loads, score_g, n_mig, mig_mass

    return propose, finish


def make_vertex_update(cfg) -> Callable:
    """The per-vertex two-phase update (Eqs. 7-8, 11-12) as a pure function.

    Shared verbatim by the single-device iteration and the per-shard
    sharded iteration, which is what makes every engine an oracle of the
    others.  The caller supplies whatever slice of the vertex set it owns
    plus the matching noise/u draws and the Eq. 5 capacity ``C`` (a
    traced scalar, so graph growth never forces a recompile).  Composed
    from ``make_update_parts`` -- the same two halves a fused score
    backend splits across its kernel and the XLA epilogue -- so the
    dense-scores and fused paths walk identical trajectories.

    ``valid`` masks padding vertices introduced by the shape-bucket /
    sharded layouts; pads never migrate and contribute nothing to any
    aggregate.  (``None`` statically skips the masking ops.  Tie-break
    noise is drawn over the padded set, so trajectories are
    deterministic PER padded layout -- see ``graph.pad_graph``.)
    """
    propose, finish = make_update_parts(
        cfg.k, degree_weighted=cfg.migration_weighting == "edges",
        current_bonus=cfg.current_bonus)

    def update(scores, labels, deg_w, loads, noise, u, valid, reduce_, C):
        best, tot_best, tot_cur, m_partial = propose(
            scores, labels, deg_w, loads, noise, valid, C)
        return finish(best, tot_best, tot_cur, m_partial, labels, deg_w,
                      loads, u, valid, reduce_, C)

    return update


def _halting_update(best_score, stall, score_g, eps, halt_window):
    """Section 3.3 stall logic on device, mirroring the host loop exactly.

    On the first iteration best_score is -inf, so tol is inf and
    ``best + tol`` is NaN: the comparison is False and the iteration counts
    toward the stall window -- the same (intentional) behaviour as the
    legacy host loop's float arithmetic.
    """
    tol = eps * jnp.maximum(jnp.float32(1.0), jnp.abs(best_score))
    improved = score_g > best_score + tol
    new_best = jnp.maximum(best_score, score_g)
    new_stall = jnp.where(improved, jnp.int32(0), stall + 1)
    return new_best, new_stall, new_stall >= halt_window


def _bind_iterate(cfg, scores_fn: Callable, fused: bool = False) -> Callable:
    """One LPA iteration in bind-argument form (graph data as arguments).

    ``iterate(labels, loads, key, bind) -> (labels, loads, score_g,
    n_migrations, migration_mass)``.  Noise/u are drawn over the padded
    vertex set, so for a fixed padded layout the host loop, the fused
    runner and a 1-device sharded mesh consume identical streams.

    With ``fused=True``, ``scores_fn`` is the backend's whole-update
    closure (``make_fused_update``): it consumes the same noise/u/valid
    arrays and returns the iteration outputs directly -- the (V_pad, k)
    score matrix never materializes.
    """
    k, tie = cfg.k, cfg.tie_noise
    update = None if fused else make_vertex_update(cfg)

    def iterate(labels, loads, key, bind: GraphBind):
        v_pad = labels.shape[0]
        k_noise, k_mig = jax.random.split(key)
        noise = jax.random.uniform(k_noise, (v_pad, k), jnp.float32,
                                   0.0, tie)
        u = jax.random.uniform(k_mig, (v_pad,), jnp.float32)
        valid = jnp.arange(v_pad, dtype=jnp.int32) < bind.num_real
        if fused:
            return scores_fn(labels, labels, bind.deg_w, loads, noise, u,
                             valid, lambda x: x, bind.capacity,
                             *bind.score)
        scores = scores_fn(labels, *bind.score)            # (V_pad, k) f32
        return update(scores, labels, bind.deg_w, loads, noise, u, valid,
                      lambda x: x, bind.capacity)

    return iterate


def _bind_step(cfg, scores_fn: Callable, fused: bool = False) -> Callable:
    """Jittable ``(SpinnerState, GraphBind) -> SpinnerState`` transition."""
    iterate = _bind_iterate(cfg, scores_fn, fused)
    eps = jnp.float32(cfg.eps)
    halt_window = cfg.halt_window

    def step_fn(state: SpinnerState, bind: GraphBind) -> SpinnerState:
        key, k_it = jax.random.split(state.key)
        labels, loads, score_g, n_mig, mig_mass = iterate(
            state.labels, state.loads, k_it, bind)
        best, stall, halted = _halting_update(
            state.best_score, state.stall, score_g, eps, halt_window)
        return SpinnerState(
            labels=labels, loads=loads, key=key,
            best_score=best, stall=stall,
            iteration=state.iteration + 1, halted=halted,
            total_messages=state.total_messages + mig_mass,
            score=score_g, migrations=n_mig, message_mass=mig_mass,
            exchanged_bytes=state.exchanged_bytes)

    return step_fn


def _update_for(cfg, opts: EngineOptions, score_fn: Optional[Callable]
                ) -> Tuple[Callable, tuple, bool]:
    """(traced closure, static signature, fused?) for single-device runs.

    Non-fused: the backend's ``make_scores`` closure (or a custom
    ``score_fn``, which is single-phase dense by contract and therefore
    pins fused off).  Fused: the backend's ``make_fused_update`` whole-
    iteration closure.  The fused flag is part of every program cache
    key, so the two paths never share an executable.
    """
    if score_fn is not None:
        return (lambda labels, *unused: score_fn(labels)), ("custom",), False
    backend = opts.backend()
    if opts.resolved_fused_update() == "on":
        fn = backend.make_fused_update(
            cfg.k, degree_weighted=cfg.migration_weighting == "edges",
            current_bonus=float(cfg.current_bonus))
        return fn, backend.signature(), True
    return backend.make_scores(cfg.k), backend.signature(), False


# ---------------------------------------------------------------------------
# Single-device programs
# ---------------------------------------------------------------------------

def _iterate_program(cfg, opts, score_fn=None) -> Program:
    """``run(labels, loads, key, bind)`` -- the host loop's jitted step."""
    scores_fn, sig, fused = _update_for(cfg, opts, score_fn)

    def build():
        return jax.jit(_bind_iterate(cfg, scores_fn, fused))

    if score_fn is not None:
        return Program(run=build())
    return _program(("iterate", _static_cfg(cfg), sig, fused), build)


def _state_step_program(cfg, opts, score_fn=None) -> Program:
    """``run(state, bind)`` -- one state transition (make_step_fn)."""
    scores_fn, sig, fused = _update_for(cfg, opts, score_fn)

    def build():
        return jax.jit(_bind_step(cfg, scores_fn, fused))

    if score_fn is not None:
        return Program(run=build())
    return _program(("state_step", _static_cfg(cfg), sig, fused), build)


def _fused_program(cfg, opts, score_fn=None) -> Program:
    """``run(state, bind)`` -- the whole run as one while_loop dispatch."""
    scores_fn, sig, fused = _update_for(cfg, opts, score_fn)
    max_iters = cfg.max_iters

    def build():
        step_fn = _bind_step(cfg, scores_fn, fused)

        def cond_fn(s: SpinnerState):
            return jnp.logical_and(jnp.logical_not(s.halted),
                                   s.iteration < max_iters)

        @jax.jit
        def run(state: SpinnerState, bind: GraphBind) -> SpinnerState:
            return jax.lax.while_loop(cond_fn, lambda s: step_fn(s, bind),
                                      state)

        return run

    if score_fn is not None:
        return Program(run=build())
    return _program(("fused", _static_cfg(cfg), sig, fused), build)


def _chunked_program(cfg, opts, chunk_size: int, record: bool,
                     has_edges: bool, score_fn=None) -> Program:
    """``run(state, bind) -> (state, records)`` -- one guarded scan chunk."""
    scores_fn, sig, fused = _update_for(cfg, opts, score_fn)
    max_iters = cfg.max_iters

    def build():
        step_fn = _bind_step(cfg, scores_fn, fused)

        @jax.jit
        def run(state: SpinnerState, bind: GraphBind):
            def body(state, _):
                active = jnp.logical_and(jnp.logical_not(state.halted),
                                         state.iteration < max_iters)
                new_state = jax.lax.cond(active,
                                         lambda s: step_fn(s, bind),
                                         lambda s: s, state)
                if not record:
                    return new_state, {"valid": active}
                if has_edges:
                    src, dst, w, ideal, real_e = bind.hist
                    # count only real edges: pads are weight-0 self-loops
                    local = (new_state.labels[src] == new_state.labels[dst]
                             ) & (w > 0)
                    phi = jnp.sum(local.astype(jnp.float32)) / real_e
                    rho = jnp.max(new_state.loads) / ideal
                else:
                    # edgeless graph: mirror metrics.rho's ideal<=0
                    # convention (rho = 1)
                    phi = jnp.float32(1.0)
                    rho = jnp.float32(1.0)
                rec = {
                    "iteration": new_state.iteration,
                    "score": new_state.score,
                    "migrations": new_state.migrations,
                    "message_mass": new_state.message_mass,
                    "phi": phi,
                    "rho": rho,
                    "valid": active,
                }
                return new_state, rec

            return jax.lax.scan(body, state, None, length=chunk_size)

        return run

    if score_fn is not None:
        return Program(run=build())
    return _program(("chunked", _static_cfg(cfg), sig, fused, chunk_size,
                     record, has_edges), build)


# ---------------------------------------------------------------------------
# Frontier mode: dirty-set LPA reconvergence (delta-proportional compute)
# ---------------------------------------------------------------------------
# After a small edge delta on a converged partition, only the endpoints of
# changed edges can want to move -- and migrations propagate label changes
# one hop per iteration.  Frontier mode exploits that: the step scores only
# the ACTIVE vertex set (valid &= active), expands it along edges out of
# vertices that changed label, and halts when no active vertex wants to
# move.  Inactive vertices keep their labels and contribute nothing to any
# aggregate, so under the fused Pallas backend whole tiles without active
# vertices skip their edge reduction entirely (the tile-activity bitmap in
# kernels/spinner_scores); the XLA backend keeps dense compute but the same
# masked semantics.  On a base labeling that is a fixed point robust to the
# delta's load perturbation the final labels are bit-identical to a full
# re-adapt (the oracle); the per-iteration scored-vertex counts come back
# as a (max_iters,) history for sub-linearity reporting.


def _frontier_update_for(cfg, opts: EngineOptions
                         ) -> Tuple[Callable, tuple, bool]:
    """(traced closure, signature, fused?) for frontier-mode runs.

    The fused form asks the backend for its ``frontier=True`` variant,
    which additionally returns the post-proposal ``want`` mask (the
    drain-halting signal) and -- for the Pallas backend -- skips tiles
    with no active vertex.
    """
    backend = opts.backend()
    if opts.resolved_fused_update() == "on":
        fn = backend.make_fused_update(
            cfg.k, degree_weighted=cfg.migration_weighting == "edges",
            current_bonus=float(cfg.current_bonus), frontier=True)
        return fn, backend.signature(), True
    return backend.make_scores(cfg.k), backend.signature(), False


def _bind_frontier_step(cfg, scores_fn: Callable, fused: bool) -> Callable:
    """One frontier-mode LPA iteration over ``(state, active, hist)``.

    Identical update math to ``_bind_step`` except ``valid`` is
    additionally masked by the active set, halting is drain-based
    (no active vertex wants to move) rather than score-stall, and the
    active set for the next iteration is ``want | touched`` where
    ``touched`` marks endpoints of edges whose other endpoint changed
    label this iteration.  Noise/u are still drawn over the FULL padded
    vertex set, so on a converged base the frontier trajectory replays
    the oracle's migration decisions bit for bit.
    """
    k, tie = cfg.k, cfg.tie_noise
    eps = jnp.float32(cfg.eps)
    halt_window = cfg.halt_window
    propose, finish = make_update_parts(
        k, degree_weighted=cfg.migration_weighting == "edges",
        current_bonus=cfg.current_bonus)

    def step_fn(carry, bind: GraphBind):
        state, active, hist = carry
        key, k_it = jax.random.split(state.key)
        v_pad = state.labels.shape[0]
        k_noise, k_mig = jax.random.split(k_it)
        noise = jax.random.uniform(k_noise, (v_pad, k), jnp.float32,
                                   0.0, tie)
        u = jax.random.uniform(k_mig, (v_pad,), jnp.float32)
        valid = (jnp.arange(v_pad, dtype=jnp.int32) < bind.num_real) \
            & active
        if fused:
            labels, loads, score_g, n_mig, mig_mass, want = scores_fn(
                state.labels, state.labels, bind.deg_w, state.loads,
                noise, u, valid, lambda x: x, bind.capacity, *bind.score)
        else:
            scores = scores_fn(state.labels, *bind.score)
            best, tot_best, tot_cur, m_partial = propose(
                scores, state.labels, bind.deg_w, state.loads, noise,
                valid, bind.capacity)
            want = (best != state.labels) & valid
            labels, loads, score_g, n_mig, mig_mass = finish(
                best, tot_best, tot_cur, m_partial, state.labels,
                bind.deg_w, state.loads, u, valid, lambda x: x,
                bind.capacity)
        src, dst = bind.frontier
        changed = (labels != state.labels).astype(jnp.int32)
        touched = jnp.zeros((v_pad,), jnp.int32).at[src].max(
            changed[dst]) > 0
        hist = hist.at[state.iteration].set(
            jnp.sum(valid.astype(jnp.float32)))
        best_s, stall, _ = _halting_update(
            state.best_score, state.stall, score_g, eps, halt_window)
        new_state = SpinnerState(
            labels=labels, loads=loads, key=key,
            best_score=best_s, stall=stall,
            iteration=state.iteration + 1,
            halted=jnp.sum(want.astype(jnp.int32)) == 0,
            total_messages=state.total_messages + mig_mass,
            score=score_g, migrations=n_mig, message_mass=mig_mass,
            exchanged_bytes=state.exchanged_bytes)
        return new_state, want | touched, hist

    return step_fn


def _frontier_program(cfg, opts: EngineOptions) -> Program:
    """``run(state, active, bind) -> (state, scored_hist)``: the frontier
    loop as one while_loop dispatch.  ``scored_hist`` is the (max_iters,)
    per-iteration count of scored (valid & active) vertices, 0 past the
    final iteration."""
    scores_fn, sig, fused = _frontier_update_for(cfg, opts)
    max_iters = cfg.max_iters

    def build():
        step_fn = _bind_frontier_step(cfg, scores_fn, fused)

        def cond_fn(carry):
            s = carry[0]
            return jnp.logical_and(jnp.logical_not(s.halted),
                                   s.iteration < max_iters)

        @jax.jit
        def run(state: SpinnerState, active, bind: GraphBind):
            hist0 = jnp.zeros((max_iters,), jnp.float32)
            state, _, hist = jax.lax.while_loop(
                cond_fn, lambda c: step_fn(c, bind),
                (state, active, hist0))
            return state, hist

        return run

    return _program(("frontier", _static_cfg(cfg), sig, fused), build)


def make_frontier_runner(graph: Graph, cfg,
                         opts: EngineOptions = _DEFAULT_OPTS) -> Callable:
    """``runner(state, active) -> (state, scored_hist)`` over the padded
    layout; accepts state/active over the REAL vertex set."""
    opts = _autotuned(graph, cfg, opts)
    bind, padded = _single_bind(graph, cfg, opts, frontier=True)
    prog = _frontier_program(cfg, opts)
    v_pad, num_real = padded.num_vertices, graph.num_vertices

    def runner(state: SpinnerState, active):
        state = state._replace(labels=pad_labels(state.labels, v_pad))
        active = jnp.asarray(active, jnp.bool_)
        pad = v_pad - active.shape[0]
        if pad:
            active = jnp.concatenate(
                [active, jnp.zeros((pad,), jnp.bool_)])
        out, hist = prog.run(state, active, bind)
        return out._replace(labels=out.labels[:num_real]), hist

    runner.program = prog
    runner.v_pad = v_pad
    return runner


def run_frontier(graph: Graph, cfg, labels, loads, key, active,
                 opts: EngineOptions = _DEFAULT_OPTS,
                 on_program: Optional[Callable] = None):
    """Frontier-mode run to drain: ``(state, scored_hist)``."""
    runner = make_frontier_runner(graph, cfg, opts)
    if on_program is not None:
        on_program(runner.program)
    return runner(init_state(labels, loads, key), active)


# ---------------------------------------------------------------------------
# On-device delta merge programs (the adapt(edge_updates=...) fast path)
# ---------------------------------------------------------------------------

def _merge_program() -> Program:
    """``run(set_groups, add_groups)``: scatter a delta batch into resident
    device arrays.

    ``set_groups`` is a tuple of ``(arrays, idx, vals)`` where every array
    in ``arrays`` receives ``vals[i]`` at the shared flat slots ``idx``
    (the slack/filler slots of a padded edge layout); ``add_groups`` is a
    tuple of ``(array, idx, inc)`` flat scatter-adds (per-vertex degree
    updates).  Batches are shape-bucketed by the caller with
    out-of-range sentinel indices, which ``mode="drop"`` discards -- so
    one compiled entry serves every batch in a size bucket.
    """

    def build():
        @jax.jit
        def run(set_groups, add_groups):
            merged = tuple(
                tuple(a.reshape(-1).at[idx].set(v, mode="drop")
                      .reshape(a.shape) for a, v in zip(arrays, vals))
                for arrays, idx, vals in set_groups)
            bumped = tuple(
                a.reshape(-1).at[idx].add(inc, mode="drop").reshape(a.shape)
                for a, idx, inc in add_groups)
            return merged, bumped

        return run

    return _program(("delta_merge",), build)


def _loads_program(k: int) -> Program:
    """``run(labels, deg_w) -> (k,) loads``: compute_loads on device.

    Bit-identical to ``spinner.compute_loads`` over the real graph: pads
    carry zero degree, and the integer-valued f32 degrees make the
    scatter-add exact under any ordering.
    """

    def build():
        @jax.jit
        def run(labels, deg_w):
            return jnp.zeros((k,), jnp.float32).at[labels.reshape(-1)].add(
                deg_w.reshape(-1))

        return run

    return _program(("delta_loads", k), build)


# ---------------------------------------------------------------------------
# Batched multi-graph programs (the serving tier's same-bucket executor)
# ---------------------------------------------------------------------------

def batch_bucket(n: int) -> int:
    """Power-of-two batch-size bucket (1, 2, 4, 8, ...): a fleet whose
    size wobbles between dispatch rounds keeps hitting the same compiled
    batched program instead of tracing one per batch size."""
    n = max(int(n), 1)
    return 1 << (n - 1).bit_length()


def stack_states(states: Sequence[SpinnerState]) -> SpinnerState:
    """Stack per-tenant states along a new leading batch dimension."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *states)


def stack_binds(binds: Sequence[GraphBind]) -> GraphBind:
    """Stack same-shaped GraphBinds along a new leading batch dimension.

    Requires identical tree structure and leaf shapes -- i.e. the graphs
    share a padded (V, E) shape bucket and score-backend signature (see
    ``batch_signature``).
    """
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *binds)


def index_state(states: SpinnerState, i: int) -> SpinnerState:
    """Slice element ``i`` back out of a stacked batch of states."""
    return jax.tree_util.tree_map(lambda x: x[i], states)


def batch_signature(cfg, opts: EngineOptions, bind: GraphBind) -> tuple:
    """Stackability key: two (cfg, opts, bind) triples with equal keys
    resolve to the same batched program and stack leaf-for-leaf."""
    shapes = tuple((tuple(x.shape), str(x.dtype))
                   for x in jax.tree_util.tree_leaves(bind))
    return (_static_cfg(cfg), opts.backend().signature(),
            opts.resolved_fused_update() == "on", shapes)


def _batched_program(cfg, opts: EngineOptions, nb: int) -> Program:
    """``run(states, binds) -> states``: ``nb`` independent fused runs as
    ONE while_loop dispatch over a leading batch dimension.

    Per-element semantics are exactly the unbatched fused program's: the
    loop continues while ANY element is still active, the shared step is
    ``vmap`` of the same ``_bind_step`` transition, and an element that
    has halted (or exhausted ``max_iters``) is frozen by a post-step
    select -- its state stops changing at precisely the iteration where
    its own ``while_loop`` would have exited, so every element's final
    state is bit-identical to running it alone (a batch of 1 is
    bit-identical to ``_fused_program``).
    """
    scores_fn, sig, fused = _update_for(cfg, opts, None)
    max_iters = cfg.max_iters

    def build():
        step_fn = _bind_step(cfg, scores_fn, fused)

        def active(s: SpinnerState):
            return jnp.logical_and(jnp.logical_not(s.halted),
                                   s.iteration < max_iters)

        v_active = jax.vmap(active)
        v_step = jax.vmap(step_fn)

        def body(states: SpinnerState, binds: GraphBind) -> SpinnerState:
            act = v_active(states)
            new = v_step(states, binds)

            def freeze(n, o):
                return jnp.where(act.reshape((nb,) + (1,) * (n.ndim - 1)),
                                 n, o)

            return jax.tree_util.tree_map(freeze, new, states)

        @jax.jit
        def run(states: SpinnerState, binds: GraphBind) -> SpinnerState:
            return jax.lax.while_loop(lambda s: jnp.any(v_active(s)),
                                      lambda s: body(s, binds), states)

        return run

    return _program(("batched", _static_cfg(cfg), sig, fused, nb), build)


def run_batched(items: Sequence[Tuple[SpinnerState, GraphBind]], cfg,
                opts: EngineOptions = _DEFAULT_OPTS,
                on_program: Optional[Callable] = None
                ) -> List[SpinnerState]:
    """Run independent same-shape ``(state, bind)`` fused work items as
    ONE batched device dispatch; returns each item's final state.

    All items must share one ``batch_signature`` (the serving scheduler
    groups tenants by it).  The batch size is rounded up to a power-of-
    two bucket; pad slots replicate item 0 pre-halted, so they are
    frozen from the very first cond evaluation and cost a vector lane,
    not a run.  States arrive and leave PADDED to the layout's vertex
    bucket (``adapt_parts``/``commit_adapt`` on the session handle the
    pad/slice).
    """
    nb_real = len(items)
    if nb_real == 0:
        return []
    nb = batch_bucket(nb_real)
    states = [s for s, _ in items]
    binds = [b for _, b in items]
    if nb > nb_real:
        pad_state = states[0]._replace(halted=jnp.asarray(True))
        states = states + [pad_state] * (nb - nb_real)
        binds = binds + [binds[0]] * (nb - nb_real)
    prog = _batched_program(cfg, opts, nb)
    if on_program is not None:
        on_program(prog)
    out = prog.run(stack_states(states), stack_binds(binds))
    return [index_state(out, i) for i in range(nb_real)]


# ---------------------------------------------------------------------------
# Single-device runners (legacy-compatible wrappers over programs)
# ---------------------------------------------------------------------------

def _pad_slice_runner(prog: Program, bind: GraphBind, padded: Graph,
                      num_real: int) -> Callable:
    """Wrap a (state, bind) program: pad labels in, slice real labels out."""
    v_pad = padded.num_vertices

    def runner(state: SpinnerState) -> SpinnerState:
        state = state._replace(labels=pad_labels(state.labels, v_pad))
        out = prog.run(state, bind)
        return out._replace(labels=out.labels[:num_real])

    runner.program = prog
    return runner


def make_host_step(graph: Graph, cfg, opts: EngineOptions = _UNPADDED_OPTS,
                   score_fn: Optional[Callable] = None) -> Callable:
    """``step(labels, loads, key)`` on the options' padded layout.

    Labels are carried PADDED between calls (the session's host driver
    slices for metrics only); ``step.v_pad`` / ``step.num_real`` describe
    the layout and ``step.program`` exposes the compiled program.  A
    custom ``score_fn`` closure is shaped to the real graph, so it
    forces ``pad="none"``.
    """
    if score_fn is not None:
        opts = dataclasses.replace(opts, pad="none")
    else:
        opts = _autotuned(graph, cfg, opts)
    bind, padded = _single_bind(graph, cfg, opts, score_fn=score_fn)
    prog = _iterate_program(cfg, opts, score_fn)

    def step(labels, loads, key):
        return prog.run(labels, loads, key, bind)

    step.program = prog
    step.v_pad = padded.num_vertices
    step.num_real = graph.num_vertices
    return step


def cached_jit_step(graph: Graph, cfg) -> Callable:
    """Jitted ``iterate(labels, loads, key)`` on the graph's exact shapes.

    The compiled program is shared globally per (cfg statics, backend),
    so repeated host-engine runs -- and config sweeps -- never re-trace.
    """
    return make_host_step(graph, cfg, _UNPADDED_OPTS)


def make_iteration(graph: Graph, cfg,
                   score_fn: Optional[Callable] = None) -> Callable:
    """One LPA iteration bound to ``graph`` (exact shapes, jitted)."""
    return make_host_step(graph, cfg, _UNPADDED_OPTS, score_fn)


def make_step_fn(graph: Graph, cfg,
                 score_fn: Optional[Callable] = None) -> Callable:
    """``SpinnerState -> SpinnerState`` bound to ``graph`` (exact shapes)."""
    bind, _ = _single_bind(graph, cfg, _UNPADDED_OPTS, score_fn=score_fn)
    prog = _state_step_program(cfg, _UNPADDED_OPTS, score_fn)

    def step_fn(state: SpinnerState) -> SpinnerState:
        return prog.run(state, bind)

    step_fn.program = prog
    return step_fn


def make_fused_runner(graph: Graph, cfg,
                      score_fn: Optional[Callable] = None,
                      opts: EngineOptions = _DEFAULT_OPTS) -> Callable:
    """``runner(state) -> state``: the full run as a single device call.

    Accepts a state over the REAL vertex set; padding to the options'
    shape bucket (and slicing back) happens inside, so callers never see
    the padded layout.  A custom ``score_fn`` closure is shaped to the
    real graph, so it forces ``pad="none"``.
    """
    if score_fn is not None:
        opts = dataclasses.replace(opts, pad="none")
    else:
        opts = _autotuned(graph, cfg, opts)
    bind, padded = _single_bind(graph, cfg, opts, score_fn=score_fn)
    prog = _fused_program(cfg, opts, score_fn)
    return _pad_slice_runner(prog, bind, padded, graph.num_vertices)


def run_fused(graph: Graph, cfg, labels, loads, key,
              score_fn: Optional[Callable] = None,
              opts: EngineOptions = _DEFAULT_OPTS,
              on_program: Optional[Callable] = None) -> SpinnerState:
    """Run to the stable state in one ``lax.while_loop`` dispatch.

    Compiled programs are cached globally per (cfg statics, backend) and
    reused across graphs sharing a shape bucket, so repeated runs --
    determinism checks, incremental adapt/resize restarts, session
    streams -- skip re-tracing entirely.
    """
    runner = make_fused_runner(graph, cfg, score_fn, opts)
    if on_program is not None:
        on_program(getattr(runner, "program", None))
    return runner(init_state(labels, loads, key))


def make_chunked_runner(graph: Graph, cfg, chunk_size: int = DEFAULT_CHUNK,
                        score_fn: Optional[Callable] = None,
                        record: bool = True,
                        opts: EngineOptions = _DEFAULT_OPTS) -> Callable:
    """Compile ``chunk_size`` iterations + history recording into one scan.

    Each scan step is guarded: once the halting criterion fires (or
    ``max_iters`` is reached) the state passes through unchanged and the
    record is marked invalid, so a trailing partial chunk costs nothing but
    pass-through work.  With ``record=False`` the per-iteration phi trace
    (an O(E) gather) is skipped and only the validity flags come back.
    A custom ``score_fn`` closure is shaped to the real graph, so it
    forces ``pad="none"``.
    """
    if score_fn is not None:
        opts = dataclasses.replace(opts, pad="none")
    else:
        opts = _autotuned(graph, cfg, opts)
    has_edges = graph.src.size > 0
    bind, padded = _single_bind(graph, cfg, opts,
                                hist=record and has_edges,
                                score_fn=score_fn)
    prog = _chunked_program(cfg, opts, chunk_size, record, has_edges,
                            score_fn)
    v_pad, num_real = padded.num_vertices, graph.num_vertices

    def run_chunk(state: SpinnerState):
        state = state._replace(labels=pad_labels(state.labels, v_pad))
        out, recs = prog.run(state, bind)
        return out._replace(labels=out.labels[:num_real]), recs

    run_chunk.program = prog
    return run_chunk


def run_chunked(graph: Graph, cfg, labels, loads, key,
                chunk_size: int = DEFAULT_CHUNK,
                score_fn: Optional[Callable] = None,
                callback: Optional[Callable[[int, dict], None]] = None,
                record: bool = True,
                opts: EngineOptions = _DEFAULT_OPTS,
                on_program: Optional[Callable] = None,
                ) -> Tuple[SpinnerState, List[dict]]:
    """Run with at most ``ceil(max_iters / chunk_size)`` device dispatches.

    Returns the final state plus the per-iteration history (same dict
    schema as the legacy host loop: iteration / score / migrations /
    message_mass / phi / rho), recorded on device and synced once per
    chunk.  ``record=False`` skips history recording entirely (the
    returned list is empty); a ``callback`` forces recording on.
    """
    record = record or callback is not None
    run_chunk = make_chunked_runner(graph, cfg, chunk_size, score_fn,
                                    record=record, opts=opts)
    if on_program is not None:
        on_program(getattr(run_chunk, "program", None))
    state = init_state(labels, loads, key)
    history: List[dict] = []
    num_chunks = -(-cfg.max_iters // chunk_size)
    for _ in range(num_chunks):
        state, recs = run_chunk(state)
        recs = jax.device_get(recs)
        if record:
            for i in range(chunk_size):
                if not bool(recs["valid"][i]):
                    break
                entry = {
                    "iteration": int(recs["iteration"][i]),
                    "score": float(recs["score"][i]),
                    "migrations": int(recs["migrations"][i]),
                    "message_mass": float(recs["message_mass"][i]),
                    "phi": float(recs["phi"][i]),
                    "rho": float(recs["rho"][i]),
                }
                history.append(entry)
                if callback is not None:
                    callback(entry["iteration"], entry)
        # One scalar sync per chunk: stop dispatching once the run is over.
        if not bool(recs["valid"][chunk_size - 1]) or bool(
                jax.device_get(state.halted)):
            break
    return state, history


# ---------------------------------------------------------------------------
# Sharded runner: one lax.while_loop dispatch across the whole device mesh
# ---------------------------------------------------------------------------

def state_partition_spec(axis: str) -> SpinnerState:
    """``shard_map`` specs for a ``SpinnerState``: labels sharded over the
    vertex ``axis``, every aggregate (loads, key, halting scalars, the
    exchange-byte counter) replicated -- they are psum-consistent across
    devices by construction, whichever exchange plan is active."""
    rep = PartitionSpec()
    return SpinnerState(
        labels=PartitionSpec(axis), loads=rep, key=rep, best_score=rep,
        stall=rep, iteration=rep, halted=rep, total_messages=rep,
        score=rep, migrations=rep, message_mass=rep, exchanged_bytes=rep)


def _default_partition_mesh() -> Mesh:
    """1-D mesh over all local devices (cached so cache keys stay stable)."""
    global _DEFAULT_MESH
    if _DEFAULT_MESH is None:
        from repro.launch.mesh import make_partition_mesh
        _DEFAULT_MESH = make_partition_mesh()
    return _DEFAULT_MESH


_DEFAULT_MESH: Optional[Mesh] = None


def make_sharded_step_fn(cfg, axis: str, ndev: int, v_local: int, plan,
                         scores, noise_mode: str,
                         overlap: bool = False,
                         fused: bool = False) -> Callable:
    """Per-device jittable sharded transition, parameterized by the plan.

    Runs INSIDE ``shard_map`` over ``axis``: ``state.labels`` arrives as
    this device's ``(v_local,)`` shard, the edge blocks as this device's
    rows of the score backend's layout, scalars replicated.  The label
    exchange is delegated to ``plan`` (``repro.core.comm.ExchangePlan``):
    the all-gather oracle, the boundary-only halo exchange, or the
    changed-labels-only delta exchange -- all bit-compatible, differing
    only in bytes on the wire (accumulated into
    ``state.exchanged_bytes``).  The (k,) and scalar aggregates inside
    ``make_vertex_update`` are psum-reduced, so every device computes the
    same ``_halting_update`` decision and a surrounding ``while_loop``
    stays in lockstep with no host involvement.

    Schedule (``overlap``): with ``overlap=False``, ``scores`` is the
    backend's single-phase closure and the step is exchange -> score.
    With ``overlap=True``, ``scores`` is the backend's ``(interior_fn,
    frontier_fn)`` pair over the [interior | frontier] edge split (see
    ``distributed.ShardedGraph``) and the step is rescheduled to
    ``start_exchange -> score_interior -> finish_exchange ->
    score_frontier``: the collective is issued before any edge is
    scored and only the frontier phase consumes it, so the two are
    dataflow-independent and XLA's latency-hiding scheduler can overlap
    wire and compute.  Both schedules are bit-identical (the integer
    edge weights make the f32 partial sums exact).

    Fused (``fused=True``): ``scores`` is the backend's whole-iteration
    closure (``make_sharded_fused_update``; under overlap the
    ``(interior_fn, frontier_fn)`` split form, where the interior phase
    returns a RAW tiled score partial and the frontier megakernel seeds
    its accumulator with it).  The closure consumes the exact same
    noise/u/valid slices and the psum reducer the dense path hands to
    ``make_vertex_update``, so the trajectory is bit-identical.

    Closes over static shape ints only (``ndev``, ``v_local``, the plan's
    signature) -- capacity, the real vertex count and every edge array
    are traced arguments, so one compiled program serves every graph in a
    shape bucket.  Returns ``step(state, aux, capacity, num_real, deg_l,
    score_blocks, plan_blocks) -> (state, aux)`` where ``aux`` is the
    plan's loop-carried state (e.g. delta's replicated label mirror;
    ``()`` for stateless plans).

    PRNG (``EngineOptions.sharded_noise``): with ``"replicated"``
    (default) noise/u are drawn over the full padded vertex set from the
    replicated key and sliced to the local shard -- on a 1-device mesh
    the padded set IS the engine's padded vertex set, so draws (and
    therefore labels and iteration counts) are bit-identical to the
    single-device engines.  With ``"folded"`` each device folds its axis
    index into the key and draws only its local (v_local, k) block --
    O(V/ndev) instead of O(V) noise memory for very large V, at the cost
    of a different (still deterministic) stream.
    """
    k = cfg.k
    v_pad = ndev * v_local
    update = make_vertex_update(cfg)
    eps = jnp.float32(cfg.eps)
    halt_window = cfg.halt_window

    def psum(x):
        return jax.lax.psum(x, axis)

    def step_fn(state: SpinnerState, aux, capacity, num_real, deg_l,
                score_blocks, plan_blocks):
        key, k_it = jax.random.split(state.key)
        # Pregel messages: one plan-defined label exchange.
        if overlap:
            interior_fn, frontier_fn = scores
            pending = plan.start_exchange(state.labels, aux, axis,
                                          *plan_blocks)
            partial = interior_fn(state.labels, *score_blocks)
            lookup, aux, xbytes = plan.finish_exchange(pending)
        else:
            lookup, aux, xbytes = plan.exchange(state.labels, aux, axis,
                                                *plan_blocks)
        off = jax.lax.axis_index(axis) * v_local
        if noise_mode == "folded":
            k_dev = jax.random.fold_in(k_it, jax.lax.axis_index(axis))
            k_noise, k_mig = jax.random.split(k_dev)
            noise = jax.random.uniform(k_noise, (v_local, k), jnp.float32,
                                       0.0, cfg.tie_noise)
            u = jax.random.uniform(k_mig, (v_local,), jnp.float32)
        else:
            k_noise, k_mig = jax.random.split(k_it)
            noise_full = jax.random.uniform(k_noise, (v_pad, k), jnp.float32,
                                            0.0, cfg.tie_noise)
            u_full = jax.random.uniform(k_mig, (v_pad,), jnp.float32)
            noise = jax.lax.dynamic_slice_in_dim(noise_full, off, v_local, 0)
            u = jax.lax.dynamic_slice_in_dim(u_full, off, v_local, 0)
        valid = off + jnp.arange(v_local, dtype=jnp.int32) < num_real
        if fused:
            fused_fn = frontier_fn if overlap else scores
            head = (partial, lookup) if overlap else (lookup,)
            labels, loads, score_g, n_mig, mig_mass = fused_fn(
                *head, state.labels, deg_l, state.loads, noise, u, valid,
                psum, capacity, *score_blocks)
        else:
            scores_v = (frontier_fn(partial, lookup, *score_blocks)
                        if overlap else
                        scores(lookup, *score_blocks))     # (v_local, k)
            labels, loads, score_g, n_mig, mig_mass = update(
                scores_v, state.labels, deg_l, state.loads, noise, u,
                valid, psum, capacity)
        best, stall, halted = _halting_update(
            state.best_score, state.stall, score_g, eps, halt_window)
        return SpinnerState(
            labels=labels, loads=loads, key=key,
            best_score=best, stall=stall,
            iteration=state.iteration + 1, halted=halted,
            total_messages=state.total_messages + mig_mass,
            score=score_g, migrations=n_mig, message_mass=mig_mass,
            exchanged_bytes=state.exchanged_bytes + xbytes), aux

    return step_fn


def _sharded_program(cfg, opts: EngineOptions, mesh: Mesh, axis: str,
                     plan_sig: tuple, n_score: int,
                     score_fn: Optional[Callable] = None,
                     single_step: bool = False,
                     overlap: bool = False,
                     fused: bool = False) -> Program:
    """The compiled sharded runner (or one-iteration step) for a static
    (cfg, backend, mesh, axis, plan signature, noise mode, overlap
    schedule, fused-update) tuple.

    Traces against an array-free ``plan_from_signature`` view, so the
    program closes over shape ints only and is shared by every graph
    whose sharded layout lands in the same bucket.
    """
    from . import comm                                    # sibling, no cycle
    noise_mode = opts.resolved_sharded_noise()
    ndev = mesh.shape[axis]
    if score_fn is not None:
        scores_sig = ("custom",)
    else:
        backend = opts.backend()
        scores_sig = backend.signature()
    kind = "sharded_step" if single_step else "sharded"
    key = (kind, _static_cfg(cfg), scores_sig, mesh, axis, plan_sig,
           noise_mode, overlap, fused)
    max_iters = cfg.max_iters

    def build():
        plan = comm.plan_from_signature(plan_sig)
        v_local = plan_sig[2] if plan_sig[0] != "allgather" \
            else plan_sig[2] // ndev
        deg_weighted = cfg.migration_weighting == "edges"
        if score_fn is not None:
            scores = lambda lookup, *blocks: score_fn(lookup, *blocks)
        elif fused and overlap:
            scores = opts.backend().make_sharded_fused_update_split(
                cfg.k, v_local, degree_weighted=deg_weighted,
                current_bonus=float(cfg.current_bonus))
        elif fused:
            scores = opts.backend().make_sharded_fused_update(
                cfg.k, v_local, degree_weighted=deg_weighted,
                current_bonus=float(cfg.current_bonus))
        elif overlap:
            scores = opts.backend().make_sharded_scores_split(cfg.k,
                                                              v_local)
        else:
            scores = opts.backend().make_sharded_scores(cfg.k, v_local)
        step_fn = make_sharded_step_fn(cfg, axis, ndev, v_local, plan,
                                       scores, noise_mode,
                                       overlap=overlap, fused=fused)

        def cond_fn(carry):
            s = carry[0]
            return jnp.logical_and(jnp.logical_not(s.halted),
                                   s.iteration < max_iters)

        plan_specs = tuple(plan.arg_specs(axis))
        # sharded args arrive with a leading length-1 shard dim to strip;
        # replicated plan args (e.g. halo's wire-bytes scalar) do not
        strip = (True,) * n_score + tuple(s == PartitionSpec(axis)
                                          for s in plan_specs)

        def run_local(state, capacity, num_real, deg_l, *rest):
            blocks = tuple(r[0] if s else r for r, s in zip(rest, strip))
            score_blocks, plan_blocks = blocks[:n_score], blocks[n_score:]
            dl = deg_l[0]
            aux0 = plan.init_aux(state.labels, axis, *plan_blocks)
            if single_step:
                new_state, _ = step_fn(state, aux0, capacity, num_real, dl,
                                       score_blocks, plan_blocks)
                return new_state

            def body(carry):
                s, aux = carry
                return step_fn(s, aux, capacity, num_real, dl,
                               score_blocks, plan_blocks)

            state, _ = jax.lax.while_loop(cond_fn, body, (state, aux0))
            return state

        spec = state_partition_spec(axis)
        rep = PartitionSpec()
        arg_specs = (rep, rep, PartitionSpec(axis)) \
            + (PartitionSpec(axis),) * n_score + tuple(plan.arg_specs(axis))
        return jax.jit(jax.shard_map(
            run_local, mesh=mesh, in_specs=(spec,) + arg_specs,
            out_specs=spec, check_vma=False))

    if score_fn is not None:
        return Program(run=build())
    return _program(key, build)


def _sharded_parts(graph: Graph, cfg, opts: EngineOptions, mesh: Mesh,
                   axis: str, score_fn: Optional[Callable] = None,
                   single_step: bool = False):
    """Everything the sharded runner and one-step dispatcher share.

    Resolves the exchange plan and the overlap schedule, builds (or
    fetches cached) the score backend's sharded edge arrays against the
    plan's ``dst_index`` (the two-phase split arrays under overlap), and
    returns ``(sg, plan, program, args)`` where ``args`` is the full
    argument tuple after the state: ``(capacity, num_real, deg_w,
    *score_args, *plan_args)``.

    ``single_step=True`` (the hostloop baseline's one-iteration
    dispatcher) pins the aux-free allgather oracle -- delta's label
    mirror would have to round-trip between dispatches -- and the
    non-overlapped schedule, so there is exactly ONE step-construction
    code path for every driver.  Every plan/schedule combination walks
    the same trajectory, so parity with ``engine="sharded"`` is
    unaffected.
    """
    from . import comm                                    # sibling, no cycle
    from .distributed import device_upload, shard_layout  # layout layer
    if single_step:
        opts = dataclasses.replace(opts, label_exchange="allgather",
                                   overlap="off")
    ndev = mesh.shape[axis]
    if score_fn is None:
        opts = _autotuned(graph, cfg, opts, ndev=ndev)
    padded, num_real = padded_view(graph, opts)
    pad = opts.pad == "bucket"
    # custom score closures are single-phase by contract
    overlap = (opts.resolved_overlap(ndev) == "on" and score_fn is None)
    fused = score_fn is None and opts.resolved_fused_update() == "on"
    sg = shard_layout(padded, ndev, pad=pad)
    plan = comm.make_exchange_plan(opts.resolved_label_exchange(ndev), sg,
                                   delta_cap=opts.delta_cap, pad=pad)
    # score args are cached per layout: the build retiles/uploads O(E)
    # arrays (for pallas, a host retile per shard) and depends only on the
    # layout, the backend, the plan's dst layout and the schedule -- so a
    # cfg sweep (eps/seed/max_iters/...) over one graph shares one build,
    # and so do the allgather/delta plans (both index with sg.dst)
    dst_layout = "halo" if plan.dst_index is not sg.dst else "global"
    if score_fn is None:
        backend = opts.backend()
        if fused:
            args_of = (backend.sharded_fused_graph_args_split if overlap
                       else backend.sharded_fused_graph_args)
        else:
            args_of = (backend.sharded_graph_args_split if overlap
                       else backend.sharded_graph_args)
    else:
        # custom closures get the XLA backend's edge layout (same arrays,
        # same normalization), just a different scores fn
        from repro.kernels import ops as kernel_ops
        backend = kernel_ops.get_score_backend("xla")
        args_of = backend.sharded_graph_args
    score_args = _graph_cached(
        _SCORE_ARG_CACHE, sg,
        ("sharded", backend.signature(), dst_layout, pad, overlap, fused,
         mesh, axis),
        lambda: shard_rows(args_of(sg, cfg.k, plan.dst_index, pad=pad),
                           mesh, axis))
    prog = _sharded_program(cfg, opts, mesh, axis, plan.signature(),
                            len(score_args), score_fn,
                            single_step=single_step, overlap=overlap,
                            fused=fused)
    args = (jnp.float32(cfg.capacity(graph)), jnp.int32(num_real),
            device_upload(sg, "deg_w", mesh, axis)) + tuple(score_args) \
        + tuple(plan.device_args())
    return sg, plan, prog, args


def make_sharded_frontier_step_fn(cfg, axis: str, ndev: int, v_local: int,
                                  plan, scores, noise_mode: str,
                                  fused: bool = False) -> Callable:
    """Frontier-mode per-device sharded transition.

    Same exchange/noise/update structure as ``make_sharded_step_fn``
    (non-overlapped schedule) with the frontier additions: ``valid`` is
    masked by the local active set, the next active set is the
    post-proposal ``want`` mask, expansion rides the LOOKUP DIFF -- the
    carry keeps the previous iteration's lookup array and any local
    vertex with an edge whose remote endpoint's looked-up label changed
    is re-activated (the plan-agnostic analogue of the single-device
    ``changed[dst]`` gather; works for allgather/delta's global mirror
    and halo's fixed boundary-slot layout alike).  Halting is
    psum-reduced drain: no device has an active vertex that wants to
    move.  The carry is ``(state, aux, active, prev_lookup, hist)``.

    The score backend's first two edge blocks must be the XLA layout's
    ``(src_local, dst_index)`` pair -- they double as the expansion
    index, which is why sharded frontier mode is XLA-backend-only.
    """
    k = cfg.k
    v_pad = ndev * v_local
    eps = jnp.float32(cfg.eps)
    halt_window = cfg.halt_window
    propose, finish = make_update_parts(
        k, degree_weighted=cfg.migration_weighting == "edges",
        current_bonus=cfg.current_bonus)

    def psum(x):
        return jax.lax.psum(x, axis)

    def step_fn(carry, capacity, num_real, deg_l, score_blocks,
                plan_blocks):
        state, aux, active, prev_lookup, hist = carry
        key, k_it = jax.random.split(state.key)
        lookup, aux, xbytes = plan.exchange(state.labels, aux, axis,
                                            *plan_blocks)
        # Expand: re-activate local endpoints of edges whose remote
        # endpoint changed label last iteration (pad edges point at a
        # fixed in-range slot, so a spurious hit only re-activates an
        # already-active migrant -- conservative, never unsound).
        src_local, dst_idx = score_blocks[0], score_blocks[1]
        changed_dst = (lookup[dst_idx] != prev_lookup[dst_idx]
                       ).astype(jnp.int32)
        touched = jnp.zeros((v_local,), jnp.int32).at[src_local].max(
            changed_dst) > 0
        active = active | touched
        off = jax.lax.axis_index(axis) * v_local
        if noise_mode == "folded":
            k_dev = jax.random.fold_in(k_it, jax.lax.axis_index(axis))
            k_noise, k_mig = jax.random.split(k_dev)
            noise = jax.random.uniform(k_noise, (v_local, k), jnp.float32,
                                       0.0, cfg.tie_noise)
            u = jax.random.uniform(k_mig, (v_local,), jnp.float32)
        else:
            k_noise, k_mig = jax.random.split(k_it)
            noise_full = jax.random.uniform(k_noise, (v_pad, k),
                                            jnp.float32, 0.0,
                                            cfg.tie_noise)
            u_full = jax.random.uniform(k_mig, (v_pad,), jnp.float32)
            noise = jax.lax.dynamic_slice_in_dim(noise_full, off, v_local,
                                                 0)
            u = jax.lax.dynamic_slice_in_dim(u_full, off, v_local, 0)
        valid = (off + jnp.arange(v_local, dtype=jnp.int32) < num_real) \
            & active
        if fused:
            labels, loads, score_g, n_mig, mig_mass, want = scores(
                lookup, state.labels, deg_l, state.loads, noise, u, valid,
                psum, capacity, *score_blocks)
        else:
            scores_v = scores(lookup, *score_blocks)
            best, tot_best, tot_cur, m_partial = propose(
                scores_v, state.labels, deg_l, state.loads, noise, valid,
                capacity)
            want = (best != state.labels) & valid
            labels, loads, score_g, n_mig, mig_mass = finish(
                best, tot_best, tot_cur, m_partial, state.labels, deg_l,
                state.loads, u, valid, psum, capacity)
        hist = hist.at[state.iteration].set(
            psum(jnp.sum(valid.astype(jnp.float32))))
        n_want = psum(jnp.sum(want.astype(jnp.int32)))
        best_s, stall, _ = _halting_update(
            state.best_score, state.stall, score_g, eps, halt_window)
        new_state = SpinnerState(
            labels=labels, loads=loads, key=key,
            best_score=best_s, stall=stall,
            iteration=state.iteration + 1, halted=n_want == 0,
            total_messages=state.total_messages + mig_mass,
            score=score_g, migrations=n_mig, message_mass=mig_mass,
            exchanged_bytes=state.exchanged_bytes + xbytes)
        return new_state, aux, want, lookup, hist

    return step_fn


def _sharded_frontier_program(cfg, opts: EngineOptions, mesh: Mesh,
                              axis: str, plan_sig: tuple, n_score: int,
                              fused: bool = False) -> Program:
    """``run(state, active, capacity, num_real, deg_w, *score, *plan)
    -> (state, scored_hist)``: the sharded frontier loop in one
    shard_map(while_loop) dispatch, primed with a pre-loop exchange of
    the initial labels (``ExchangePlan.prime``)."""
    from . import comm                                    # sibling, no cycle
    noise_mode = opts.resolved_sharded_noise()
    ndev = mesh.shape[axis]
    backend = opts.backend()
    key = ("sharded_frontier", _static_cfg(cfg), backend.signature(), mesh,
           axis, plan_sig, noise_mode, fused)
    max_iters = cfg.max_iters

    def build():
        plan = comm.plan_from_signature(plan_sig)
        v_local = plan_sig[2] if plan_sig[0] != "allgather" \
            else plan_sig[2] // ndev
        deg_weighted = cfg.migration_weighting == "edges"
        if fused:
            scores = backend.make_sharded_fused_update(
                cfg.k, v_local, degree_weighted=deg_weighted,
                current_bonus=float(cfg.current_bonus), frontier=True)
        else:
            scores = backend.make_sharded_scores(cfg.k, v_local)
        step_fn = make_sharded_frontier_step_fn(
            cfg, axis, ndev, v_local, plan, scores, noise_mode,
            fused=fused)

        def cond_fn(carry):
            s = carry[0]
            return jnp.logical_and(jnp.logical_not(s.halted),
                                   s.iteration < max_iters)

        plan_specs = tuple(plan.arg_specs(axis))
        strip = (True,) * n_score + tuple(s == PartitionSpec(axis)
                                          for s in plan_specs)

        def run_local(state, active, capacity, num_real, deg_l, *rest):
            blocks = tuple(r[0] if s else r for r, s in zip(rest, strip))
            score_blocks, plan_blocks = blocks[:n_score], blocks[n_score:]
            dl = deg_l[0]
            prev_lookup, aux0, b0 = plan.prime(state.labels, axis,
                                               *plan_blocks)
            state = state._replace(
                exchanged_bytes=state.exchanged_bytes + b0)

            def body(carry):
                return step_fn(carry, capacity, num_real, dl,
                               score_blocks, plan_blocks)

            carry = (state, aux0, active, prev_lookup,
                     jnp.zeros((max_iters,), jnp.float32))
            carry = jax.lax.while_loop(cond_fn, body, carry)
            return carry[0], carry[4]

        spec = state_partition_spec(axis)
        rep = PartitionSpec()
        arg_specs = (PartitionSpec(axis), rep, rep, PartitionSpec(axis)) \
            + (PartitionSpec(axis),) * n_score + plan_specs
        return jax.jit(jax.shard_map(
            run_local, mesh=mesh, in_specs=(spec,) + arg_specs,
            out_specs=(spec, rep), check_vma=False))

    return _program(key, build)


def _sharded_frontier_parts(graph: Graph, cfg, opts: EngineOptions,
                            mesh: Mesh, axis: str):
    """Layout/plan/program/args for a sharded frontier run.

    Frontier mode pins the non-overlapped schedule (the expansion diff
    needs the whole lookup before scoring) and the XLA score backend
    (its COO edge blocks double as the expansion index).
    """
    from . import comm                                    # sibling, no cycle
    from .distributed import device_upload, shard_layout  # layout layer
    opts = dataclasses.replace(opts, overlap="off")
    ndev = mesh.shape[axis]
    opts = _autotuned(graph, cfg, opts, ndev=ndev)
    backend = opts.backend()
    if getattr(backend, "name", None) != "xla":
        raise ValueError(
            "frontier mode on the sharded engine requires the XLA score "
            "backend (its (src_local, dst_index) edge blocks double as "
            "the frontier expansion index); got "
            f"{getattr(backend, 'name', backend)!r}")
    padded, num_real = padded_view(graph, opts)
    pad = opts.pad == "bucket"
    fused = opts.resolved_fused_update() == "on"
    sg = shard_layout(padded, ndev, pad=pad)
    plan = comm.make_exchange_plan(opts.resolved_label_exchange(ndev), sg,
                                   delta_cap=opts.delta_cap, pad=pad)
    dst_layout = "halo" if plan.dst_index is not sg.dst else "global"
    args_of = (backend.sharded_fused_graph_args if fused
               else backend.sharded_graph_args)
    score_args = _graph_cached(
        _SCORE_ARG_CACHE, sg,
        ("sharded", backend.signature(), dst_layout, pad, False, fused,
         mesh, axis),
        lambda: shard_rows(args_of(sg, cfg.k, plan.dst_index, pad=pad),
                           mesh, axis))
    prog = _sharded_frontier_program(cfg, opts, mesh, axis,
                                     plan.signature(), len(score_args),
                                     fused=fused)
    args = (jnp.float32(cfg.capacity(graph)), jnp.int32(num_real),
            device_upload(sg, "deg_w", mesh, axis)) + tuple(score_args) \
        + tuple(plan.device_args())
    return sg, plan, prog, args


def run_sharded_frontier(graph: Graph, cfg, labels, loads, key, active,
                         mesh: Optional[Mesh] = None, axis: str = "data",
                         opts: EngineOptions = _DEFAULT_OPTS,
                         on_program: Optional[Callable] = None):
    """Sharded frontier-mode run to drain: ``(state, scored_hist)``.

    ``state.labels`` comes back PADDED (slice ``[:graph.num_vertices]``);
    ``active`` is a bool mask over the real vertex set.
    """
    if mesh is None:
        mesh = _default_partition_mesh()
    sg, plan, prog, args = _sharded_frontier_parts(graph, cfg, opts, mesh,
                                                   axis)
    if on_program is not None:
        on_program(prog)
    v_pad = sg.num_vertices
    active = jnp.asarray(active, jnp.bool_)
    pad = v_pad - active.shape[0]
    if pad:
        active = jnp.concatenate([active, jnp.zeros((pad,), jnp.bool_)])
    state = init_state(pad_labels(labels, v_pad), loads, key)
    return prog.run(state, active, *args)


def make_sharded_runner(graph: Graph, cfg, mesh: Mesh, axis: str = "data",
                        score_fn: Optional[Callable] = None,
                        opts: EngineOptions = _DEFAULT_OPTS) -> Callable:
    """Compile the full sharded run into ONE device dispatch.

    Returns ``runner(state) -> state`` where ``state.labels`` is the
    padded (ndev * v_per_dev,) vector over the shape-bucketed layout; the
    ``lax.while_loop`` lives INSIDE the ``shard_map``, so all devices
    iterate in lockstep driven purely by the psum-reduced halting scalars
    -- no per-iteration host sync exists even in principle.  The
    while_loop carry is ``(state, plan aux)``: the exchange plan's
    auxiliary state (e.g. delta's label mirror) never leaves the device
    either.  A custom ``score_fn`` closure is shaped to the real graph's
    layout, so it forces ``pad="none"``.
    """
    if score_fn is not None:
        opts = dataclasses.replace(opts, pad="none")
    sg, plan, prog, args = _sharded_parts(graph, cfg, opts, mesh, axis,
                                          score_fn)

    def runner(state: SpinnerState) -> SpinnerState:
        return prog.run(state, *args)

    runner.program = prog
    runner.v_pad = sg.num_vertices
    return runner


def sharded_v_pad(graph: Graph, opts: EngineOptions, mesh: Mesh,
                  axis: str = "data") -> int:
    """Padded vertex count of the sharded layout (bucket + mesh rounding)."""
    padded, _ = padded_view(graph, opts)
    ndev = mesh.shape[axis]
    return -(-padded.num_vertices // ndev) * ndev


def run_sharded(graph: Graph, cfg, labels, loads, key,
                mesh: Optional[Mesh] = None, axis: str = "data",
                score_fn: Optional[Callable] = None,
                opts: EngineOptions = _DEFAULT_OPTS,
                on_program: Optional[Callable] = None) -> SpinnerState:
    """Run to the stable state in one ``while_loop`` dispatch over ``mesh``.

    ``mesh=None`` uses a 1-D mesh over all local devices
    (``repro.launch.mesh.make_partition_mesh``).  The returned state
    carries PADDED labels (the bucketed layout rounded up to a mesh
    multiple); callers slice ``[:graph.num_vertices]``.  Compiled
    programs are cached globally per (cfg statics, backend, mesh, axis,
    plan signature) -- meshes compare by value, so rebuilding an
    identical mesh reuses the compilation, and so do all graphs sharing
    a shape bucket.
    """
    if mesh is None:
        mesh = _default_partition_mesh()
    if score_fn is not None:             # custom closures run unpadded
        opts = dataclasses.replace(opts, pad="none")
    runner = make_sharded_runner(graph, cfg, mesh, axis, score_fn, opts=opts)
    if on_program is not None:
        on_program(getattr(runner, "program", None))
    v_pad = sharded_v_pad(graph, opts, mesh, axis)
    return runner(init_state(pad_labels(labels, v_pad), loads, key))
