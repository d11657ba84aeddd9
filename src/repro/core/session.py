"""PartitionSession: a device-resident handle for continuous partitioning.

Spinner's pitch is CONTINUOUS partitioning (Sections 3.4-3.5): react to a
stream of graph changes and cluster resizes by restarting from the previous
assignment, not from scratch.  xDGP and SDP frame the same workload as a
long-lived service.  The one-shot ``partition(graph, cfg)`` call hides what
such a service needs to amortize: the O(E) edge upload, the sharded layout
and exchange-plan construction, and -- dominating small-graph latency --
the XLA compile of the fused runner.

``PartitionSession`` makes that state explicit::

    from repro.core import EngineOptions, SpinnerConfig, open_session

    with open_session(g, SpinnerConfig(k=32)) as s:
        res = s.partition()                  # cold: upload + compile
        while serving:
            delta = next_edge_batch()
            res = s.adapt(edge_updates=delta)    # warm: O(|delta|) cost
            if cluster_resized(new_k):
                res = s.resize(new_k)        # new k: exactly one compile

Lifecycle: ``open (upload/bind lazily) -> partition / adapt / resize /
update -> close``.  The session owns the (graph, config, options) triple,
the previous stable labels (``adapt``/``resize`` default to them), and the
set of compiled programs it has touched -- ``stats()`` reports shape
buckets, per-session compile counts (via the programs' jit cache sizes),
the exchange-plan communication volumes, and the delta fast-path counters.
``stage(next_graph)`` double-buffers the upload: it issues the NEXT
snapshot's host->device transfers (asynchronously, overlapping in-flight
device work) so the following ``adapt()`` consumes a device-resident bind
with zero synchronous copies -- the serving-loop pattern ``res =
s.adapt(); s.stage(next); ... ; res = s.adapt()``.

Shape-bucketed compile reuse: with the default ``EngineOptions(pad=
"bucket")`` every engine runs on a power-of-two-ish padded (V, E) layout
(``graph.shape_bucket`` / ``graph.pad_graph``).  Compiled programs take
all graph data as arguments (see ``repro.core.engine``), so an ``adapt``
on a grown graph that stays inside its bucket re-uses the same executable
-- zero re-traces, asserted in tests/test_session.py -- and crossing a
bucket costs exactly one.  Because ``spinner.partition`` opens a throwaway
session with the same defaults, a warm session call is bit-identical to
the one-shot API on every engine and exchange plan.

Delta-proportional adapt (the ``edge_updates`` fast path): a warm
``adapt(edge_updates=(src, dst))`` that fits the layout's slack costs
O(|delta|), not O(E).  The data path scatters the batch into the resident
padded edge arrays on device (``repro.core.delta`` -- zero host CSR
rebuild, zero O(E) re-upload, zero new compiles once the batch-size
bucket is warm); the logical graph update is recorded in a pending log
and only materialized on host when something genuinely needs the Graph
object (a full ``partition()``, ``stage()``, a bucket-crossing delta, or
slack overflow -- in which case the call falls back to the classic
rebuild path, which is bit-identical by construction).  Eligible modes:
single-device fused runs on the XLA backend, the Pallas backend with
``fused_update="on"``, and the sharded engine on the XLA backend with the
allgather/delta exchange plans and the non-overlapped schedule; anything
else (halo's boundary-slot dst layout, the overlap split arrays, chunked/
host engines, per-iteration history) takes the fallback and is counted in
``stats()["delta"]["fallback_adapts"]``.

Frontier reconvergence (``adapt(..., frontier=True)``): scores only the
dirty vertex set -- endpoints of changed edges, expanded one hop per
iteration along edges out of vertices that changed label -- and halts
when no active vertex wants to move (see ``engine._frontier_program``).
On a converged base labeling robust to the delta's load perturbation the
final labels are bit-identical to a full re-adapt; the result carries
``scored_vertices``/``scored_per_iter`` so callers can verify the scored
fraction is sub-linear in V.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import delta as _delta
from . import engine as _engine
from . import metrics, trace
from .engine import EngineOptions
from .graph import Graph, add_edges
from .spinner import (PartitionResult, SpinnerConfig, prepare_init,
                      resolve_options)

_ENGINES = ("auto", "fused", "sharded", "chunked", "host")

# The one closed-session error, shared by every entry point: the serving
# tier (repro.serve) retires sessions aggressively and matches on this
# message, so it must not vary by code path.
_CLOSED_MSG = ("PartitionSession is closed; open a new session "
               "(close() released its state and is idempotent)")


@dataclasses.dataclass
class _DeltaFast:
    """The session's delta fast-path state (see ``repro.core.delta``).

    Built lazily on the first eligible ``adapt(edge_updates=...)`` -- the
    one O(E) cold cost (pair-key index + for Pallas a host retile whose
    geometry mirrors the cached device upload).  ``merged`` counts the
    prefix of the session's pending log already scattered into ``dd``.
    """

    mode: str                         # "single" | "sharded"
    tracker: _delta.DeltaTracker
    dd: _delta.DeviceDelta
    opts_t: EngineOptions             # autotuned options the arrays match
    v_pad: int
    merged: int = 0
    # sharded mode only
    mesh: object = None
    axis: str = "data"
    plan: object = None
    prog_full: object = None          # the regular (non-frontier) program


class PartitionSession:
    """Device-resident handle: open -> partition/adapt/resize/update -> close.

    See the module docstring for the lifecycle.  All runs go through the
    same engine programs as the one-shot API; the session adds the
    previous-labels memory, program/compile tracking, and the rebind
    logic that keeps a growing graph inside its compile-shape bucket.
    """

    def __init__(self, graph: Graph, cfg: SpinnerConfig,
                 options: Optional[EngineOptions] = None):
        cfg, opts = resolve_options(cfg, options)
        self._pending: List[tuple] = []   # validated directed delta batches
        self._dirty: Optional[np.ndarray] = None  # endpoints since last run
        self._delta: Optional[_DeltaFast] = None
        self._fast_adapts = 0
        self._fallback_adapts = 0
        self._host_rebuilds = 0
        self._delta_bytes_last = 0
        self._delta_bytes_total = 0
        self._ledger = dict.fromkeys(_delta.LEDGER_COUNTS, 0)
        self._score_pass: Optional[str] = None   # this call's (_note_pass)
        self._score_passes = {"transposed": 0, "forward": 0}
        self.graph = graph
        self.cfg = cfg
        self.options = opts
        self._prev: Optional[np.ndarray] = None
        self._last: Optional[PartitionResult] = None
        self._staged: Optional[Graph] = None
        self._programs: dict = {}       # id(program) -> (program, base)
        self._runs = 0
        self._delta_seq = 0             # delta batches accepted, ever
        self._closed = False

    # -- the logical graph (base + pending delta log) ----------------------

    @property
    def graph(self) -> Graph:
        """The session's logical graph.  Reading it MATERIALIZES any
        pending edge deltas into a host Graph (one ``add_edges`` rebuild
        -- the cost the fast path defers); ``stats()`` reports the base
        graph plus the pending-log counters without materializing."""
        if self._pending:
            self._materialize()
        return self._graph

    @graph.setter
    def graph(self, g: Graph) -> None:
        self._graph = g
        self._pending = []
        self._dirty = None
        self._delta = None

    def _materialize(self) -> None:
        """Fold the pending delta log into a host Graph.  One coalesced
        ``add_edges`` call: Eq. 3 over the union of the arcs is
        order-independent, so batching is exact."""
        pending, self._pending = self._pending, []
        if not pending:
            return
        src = np.concatenate([b[0] for b in pending])
        dst = np.concatenate([b[1] for b in pending])
        self._graph = add_edges(self._graph, src, dst)
        self._host_rebuilds += 1
        self._delta = None   # device arrays were keyed to the old base

    def _mark_dirty(self, *vertex_sets) -> None:
        if self._dirty is None:
            self._dirty = np.zeros(self._graph.num_vertices, bool)
        for vs in vertex_sets:
            if len(vs):
                self._dirty[np.asarray(vs)] = True

    # -- lifecycle ---------------------------------------------------------

    def close(self) -> None:
        """Release the session's references (programs stay in the global
        cache for other sessions; graph uploads die with the graph).

        Idempotent: closing an already-closed session is a no-op, so
        schedulers that retire tenants aggressively (repro.serve) may
        double-close without tracking state.  Every subsequent entry
        point raises the same ``RuntimeError`` (one fixed message).
        """
        if self._closed:
            return
        self._programs.clear()
        self._prev = None
        self._last = None
        self._staged = None
        self._pending = []
        self._delta = None
        self._dirty = None
        self._closed = True

    def __enter__(self) -> "PartitionSession":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def _check_open(self) -> None:
        if self._closed:
            raise RuntimeError(_CLOSED_MSG)

    # -- program / compile tracking ---------------------------------------

    def _track(self, program) -> None:
        if program is None:            # e.g. a monkeypatched test runner
            return
        if id(program) not in self._programs:
            self._programs[id(program)] = (program, program.compiles())

    @property
    def compiles(self) -> int:
        """Compilations this session caused (jit cache growth of the
        programs it ran, measured from first acquisition)."""
        return sum(max(0, prog.compiles() - base)
                   for prog, base in self._programs.values())

    # -- the four drivers --------------------------------------------------

    def partition(self, init: Optional[np.ndarray] = None,
                  record_history: Optional[bool] = None,
                  callback: Optional[Callable[[int, dict], None]] = None,
                  ) -> PartitionResult:
        """Run to a stable state from ``init`` (or a fresh random start)."""
        self._check_open()
        return self._traced("session/partition", self._run, init,
                            record_history, callback)

    def adapt(self, new_graph: Optional[Graph] = None,
              prev: Optional[np.ndarray] = None, *,
              edge_updates: Optional[tuple] = None,
              num_vertices: Optional[int] = None,
              record_history: Optional[bool] = None,
              callback: Optional[Callable[[int, dict], None]] = None,
              frontier: Optional[bool] = None,
              ) -> PartitionResult:
        """Incremental restart (Section 3.4) from the previous labels.

        Rebinds the session to ``new_graph`` (or to the current graph
        extended by ``edge_updates=(src, dst)``; neither = the snapshot
        previously ``stage()``-d if one is pending, else re-run on the
        current graph, e.g. after ``update()``), carries ``prev`` labels
        (default: the last result) extending new vertices as -1 ->
        least-loaded, and restarts.  While the new graph stays inside the
        session's shape bucket this performs ZERO new compilations; a
        staged snapshot additionally starts from device-resident edge
        arrays, with zero synchronous host->device copies on this call.

        An ``edge_updates`` delta that fits the resident layout's slack
        takes the O(|delta|) fast path (on-device scatter merge, no host
        CSR rebuild, no O(E) re-upload -- see the module docstring for
        eligibility); otherwise it falls back to the bit-identical
        rebuild.  ``frontier=True`` reconverges only the dirty vertex
        set and drain-halts (see the module docstring); the result's
        ``scored_per_iter`` reports per-iteration scored-vertex counts.
        """
        self._check_open()
        if new_graph is not None and edge_updates is not None:
            raise ValueError("pass at most one of new_graph/edge_updates")
        return self._traced("session/adapt", self._adapt, new_graph, prev,
                            edge_updates, num_vertices, record_history,
                            callback, frontier,
                            delta=edge_updates is not None)

    def _adapt(self, new_graph, prev, edge_updates, num_vertices,
               record_history, callback, frontier) -> PartitionResult:
        batch = None
        if edge_updates is not None:
            e_src, e_dst = edge_updates
            e_src, e_dst = _delta.check_edge_updates(
                e_src, e_dst, self._graph.num_vertices, num_vertices)
            self._delta_seq += 1
            grows = (num_vertices is not None
                     and num_vertices > self._graph.num_vertices)
            if not grows:
                prev_arr = self._require_prev(prev)
                res = self._try_fast_adapt(e_src, e_dst, prev_arr,
                                           frontier, record_history,
                                           callback)
                if res is not None:
                    self._staged = None
                    return res
                self._fallback_adapts += 1
            # fallback: the classic host rebuild (bit-identical oracle)
            with trace.span("session/rebuild"):
                new_graph = add_edges(self.graph, e_src, e_dst,
                                      num_vertices=num_vertices)
            self._host_rebuilds += 1
            batch = (e_src, e_dst)
        prev = self._require_prev(prev)
        if new_graph is None and self._staged is not None:
            new_graph = self._staged
        dirty, old_v = self._dirty, self._graph.num_vertices
        if new_graph is not None:
            # any rebinding -- staged or explicit -- supersedes a pending
            # staged snapshot, which was built against the graph this call
            # replaces (see stage())
            self._staged = None
            self.graph = new_graph
        from .incremental import extend_labels
        init = extend_labels(prev, self.graph.num_vertices)
        if frontier:
            active = self._frontier_active(dirty, old_v, batch,
                                           full=batch is None)
            return self._run_frontier(init, active, record_history,
                                      callback)
        return self._run(init, record_history, callback)

    def _frontier_active(self, dirty, old_v: int, batch,
                         full: bool) -> np.ndarray:
        """Initial active mask for a frontier fallback run: accumulated
        dirty endpoints + this call's batch endpoints + grown vertices.
        With no delta provenance at all (``full``) every vertex starts
        active and frontier mode degenerates to drain-halting LPA."""
        V = self._graph.num_vertices
        active = np.zeros(V, bool)
        if full and dirty is None:
            active[:] = True
            return active
        if dirty is not None:
            active[:dirty.shape[0]] = dirty
        active[old_v:] = True
        if batch is not None:
            active[batch[0]] = True
            active[batch[1]] = True
        return active

    def stage(self, new_graph: Optional[Graph] = None, *,
              edge_updates: Optional[tuple] = None,
              num_vertices: Optional[int] = None) -> "PartitionSession":
        """Double-buffer the NEXT snapshot: begin its host->device
        uploads now, so a following ``adapt()`` starts from a
        device-resident bind with zero synchronous copies.

        Builds the padded view, sharded layout, exchange plan and
        compiled-program handle for ``new_graph`` (or for the current
        graph extended by ``edge_updates=(src, dst)``) through the
        engine's bind caches, issuing every per-graph device transfer
        immediately.  JAX dispatches transfers asynchronously, so they
        overlap whatever device work is still in flight (e.g. the
        current fused run) and the host-side layout work happens off the
        next ``adapt()``'s critical path.  The staged snapshot is
        consumed by the next argument-less ``adapt()``; staging again
        replaces it, and any other rebinding (``update()``, an explicit
        ``adapt(new_graph=...)``/``adapt(edge_updates=...)``) discards
        it, since it was built against the superseded graph.  Staging
        materializes any pending fast-path deltas first (the staged
        snapshot is a full host Graph).  Chainable.
        """
        self._check_open()
        new_graph = self._graph_delta(new_graph, edge_updates, num_vertices)
        if new_graph is None:
            raise ValueError("stage() needs new_graph or edge_updates")
        self._prestage(new_graph)
        self._staged = new_graph
        return self

    def _graph_delta(self, new_graph: Optional[Graph], edge_updates,
                     num_vertices: Optional[int]) -> Optional[Graph]:
        """Resolve the mutually-exclusive new_graph/edge_updates pair;
        ``edge_updates=(src, dst)`` extends the current graph (validated:
        out-of-range or negative ids and mismatched lengths raise
        ``ValueError`` before any state changes)."""
        if new_graph is not None and edge_updates is not None:
            raise ValueError("pass at most one of new_graph/edge_updates")
        if edge_updates is not None:
            e_src, e_dst = edge_updates
            e_src, e_dst = _delta.check_edge_updates(
                e_src, e_dst, self._graph.num_vertices, num_vertices)
            new_graph = add_edges(self.graph, e_src, e_dst,
                                  num_vertices=num_vertices)
            self._host_rebuilds += 1
        return new_graph

    def _prestage(self, graph: Graph) -> None:
        """Warm every per-graph cache ``_run`` would touch for ``graph``.

        The engine's bind pieces (padded view, edge uploads, score-
        backend arrays, sharded layout + plan) are memoized per graph
        OBJECT, so building them here means the later ``adapt()`` --
        which receives the same object -- finds everything device-
        resident.  The sharded path also resolves (and tracks) its
        program handle; note a CROSS-bucket stage does not pre-pay the
        new program's XLA compile -- jit compiles lazily, so that one
        compile still lands on the first dispatch inside ``adapt()``
        (stage removes the uploads and layout work from that path, not
        the compiler).  A dummy ``prepare_init`` pass
        additionally warms the init-path op compilations (load scatter,
        label pad/concat), which run on the EXACT vertex count and would
        otherwise retrace on every new snapshot shape even when the
        bucketed runner itself is compile-warm.
        """
        opts, cfg = self.options, self.cfg
        if opts.mesh is not None or opts.engine == "sharded":
            mesh = opts.mesh
            if mesh is None:
                mesh = _engine._default_partition_mesh()
            _, _, prog, _ = _engine._sharded_parts(graph, cfg, opts, mesh,
                                                   opts.axis)
            self._track(prog)
            v_pad = _engine.sharded_v_pad(graph, opts, mesh, opts.axis)
        else:
            # warm the arg cache the runner will actually read: the tile
            # autotuner may rebind (tile_v, tile_e) on the backend
            opts_t = _engine._autotuned(graph, cfg, opts)
            _, padded = _engine._single_bind(graph, cfg, opts_t, hist=True)
            v_pad = padded.num_vertices
        labels, _, _ = prepare_init(
            graph, cfg, np.zeros(graph.num_vertices, np.int32))
        _engine.pad_labels(labels, v_pad)

    def resize(self, k_new: int, prev: Optional[np.ndarray] = None,
               seed: Optional[int] = None,
               record_history: Optional[bool] = None,
               callback: Optional[Callable[[int, dict], None]] = None,
               ) -> PartitionResult:
        """Elastic restart (Section 3.5, Eq. 10) to ``k_new`` partitions.

        Relabels the previous assignment probabilistically, updates the
        session's config to the new k, and restarts.  A changed k means
        new (k,) aggregate shapes, so this costs exactly one compile per
        new k (returning to a previous k is free again).
        """
        self._check_open()
        prev = self._require_prev(prev)
        from .incremental import elastic_relabel
        k_old = self.cfg.k
        cfg_new = dataclasses.replace(self.cfg, k=k_new)
        init = elastic_relabel(prev, k_old, k_new,
                               seed=cfg_new.seed if seed is None else seed)
        # run first, commit the new k only on success: a rejected call
        # (bad history/callback combination) must not leave the session
        # with k_new but labels from k_old
        res = self._traced("session/resize", self._run, init,
                           record_history, callback, cfg_new)
        self.cfg = cfg_new
        return res

    def update(self, edge_src, edge_dst, num_vertices: Optional[int] = None,
               directed: bool = True) -> "PartitionSession":
        """Apply a graph delta WITHOUT running; the next ``adapt()`` (or
        ``partition()``) sees the extended graph.  Discards any pending
        staged snapshot (it was built against the graph this call
        replaces).

        Same-vertex-set deltas are appended to the session's pending log
        (validated immediately, materialized lazily) so a following
        ``adapt(edge_updates=...)``/``adapt()`` chain stays on the
        O(|delta|) fast path; a delta that grows the vertex set rebuilds
        the host graph right away.  Chainable."""
        self._check_open()
        self._staged = None
        e_src, e_dst = _delta.check_edge_updates(
            edge_src, edge_dst, self._graph.num_vertices, num_vertices)
        self._delta_seq += 1
        if num_vertices is not None \
                and num_vertices > self._graph.num_vertices:
            self.graph = add_edges(self.graph, e_src, e_dst,
                                   directed=directed,
                                   num_vertices=num_vertices)
            self._host_rebuilds += 1
            return self
        if not directed:
            e_src, e_dst = (np.concatenate([e_src, e_dst]),
                            np.concatenate([e_dst, e_src]))
        self._pending.append((e_src, e_dst))
        self._mark_dirty(e_src, e_dst)   # conservative: all endpoints
        return self

    # -- the delta fast path ----------------------------------------------

    def _fast_mode(self, record_history, callback) -> Optional[tuple]:
        """(mode, mesh) when the session's configuration supports the
        on-device delta merge, else None (-> classic fallback).  See the
        module docstring for the eligible-mode table."""
        opts, cfg = self.options, self.cfg
        if opts.pad != "bucket":
            return None                 # no slack region to merge into
        if callback is not None or record_history is True:
            return None                 # per-iteration visibility paths
        if opts.mesh is not None or opts.engine == "sharded":
            mesh = opts.mesh
            if mesh is None:
                mesh = _engine._default_partition_mesh()
            ndev = mesh.shape[opts.axis]
            opts_t = _engine._autotuned(self._graph, cfg, opts, ndev=ndev)
            if getattr(opts_t.backend(), "name", None) != "xla":
                return None             # sharded pallas retile is host-side
            if opts_t.resolved_overlap(ndev) == "on":
                return None             # overlap's split arrays differ
            if opts_t.resolved_label_exchange(ndev) == "halo":
                return None             # halo dst slots aren't global ids
            return ("sharded", mesh)
        if opts.engine not in ("auto", "fused"):
            return None                 # chunked/host replay per-iteration
        if opts.engine == "auto" and record_history is not False:
            return None                 # auto+history resolves to chunked
        opts_t = _engine._autotuned(self._graph, cfg, opts)
        backend = opts_t.backend()
        if getattr(backend, "name", None) == "pallas" \
                and opts_t.resolved_fused_update() != "on":
            return None                 # split pallas args carry no deg_t
        return ("single", None)

    def _delta_init(self, mode: str, mesh) -> _DeltaFast:
        """Cold-start the fast path from the CURRENT base graph: pair-key
        index + DeviceDelta over the resident (cached) device arrays.
        O(E) host work, paid once per base graph."""
        graph, cfg, opts = self._graph, self.cfg, self.options
        tracker = _delta.DeltaTracker(graph)
        if mode == "single":
            opts_t = _engine._autotuned(graph, cfg, opts)
            bind, padded = _engine._single_bind(graph, cfg, opts_t,
                                                frontier=True)
            backend = opts_t.backend()
            if getattr(backend, "name", None) == "pallas":
                from .graph import build_tiled_csr
                # the host twin of the cached fused upload: same
                # deterministic build, gives perm/fill/geometry
                tiled = build_tiled_csr(
                    padded, tile_v=backend.tile_v, tile_e=backend.tile_e,
                    pad_chunks=4,
                    min_total_slots=padded.num_directed_entries)
                dd = _delta.init_single_pallas(
                    bind.score, bind.deg_w, bind.frontier, tiled,
                    graph.num_directed_entries)
            else:
                dd = _delta.init_single_xla(bind.score, bind.deg_w,
                                            graph.num_directed_entries)
            prog = _engine._fused_program(cfg, opts_t)
            self._track(prog)
            return _DeltaFast(mode="single", tracker=tracker, dd=dd,
                              opts_t=opts_t, v_pad=padded.num_vertices,
                              prog_full=prog)
        ndev = mesh.shape[opts.axis]
        opts_t = _engine._autotuned(graph, cfg, opts, ndev=ndev)
        sg, plan, prog, args = _engine._sharded_parts(graph, cfg, opts_t,
                                                      mesh, opts.axis)
        self._track(prog)
        n_plan = len(plan.device_args())
        score_args = args[3:len(args) - n_plan] if n_plan \
            else args[3:]
        dd = _delta.init_sharded_xla(tuple(score_args), args[2], sg)
        return _DeltaFast(mode="sharded", tracker=tracker, dd=dd,
                          opts_t=opts_t, v_pad=sg.num_vertices,
                          mesh=mesh, axis=opts.axis, plan=plan,
                          prog_full=prog)

    def _fast_prepare(self, e_src, e_dst, prev, record_history,
                      callback) -> Optional[tuple]:
        """The shared first half of the O(|delta|) adapt: merge (pending
        log + this batch) into the resident device delta and build the
        warm restart state.  Returns ``(fs, state)`` or None when
        ineligible / on slack overflow (-> the caller rebuilds)."""
        mode = self._fast_mode(record_history, callback)
        if mode is None:
            return None
        if prev.shape[0] != self._graph.num_vertices:
            return None     # shorter prev needs the -1/least-loaded init
        if self._delta is None:
            with trace.span("session/bind"):
                self._delta = self._delta_init(*mode)
        fs = self._delta
        mp = _engine._merge_program()
        self._track(mp)
        dd, tracker = fs.dd, fs.tracker
        nbytes, plans = 0, []
        batches = self._pending[fs.merged:] + [(e_src, e_dst)]
        for bs, bd in batches:
            out = _delta.apply_delta(tracker, dd, bs, bd, mp.run)
            if out is None:
                return None          # slack overflow -> rebuild fallback
            dd, plan, b = out
            nbytes += b
            plans.append(plan)
            self._mark_dirty(plan.touched)
        for c in _delta.LEDGER_COUNTS:
            self._ledger[c] += sum(getattr(p, c) for p in plans)
        self._pending.append((e_src, e_dst))
        fs.dd, fs.merged = dd, len(self._pending)
        self._delta_bytes_last = nbytes
        self._delta_bytes_total += nbytes
        self._fast_adapts += 1

        with trace.span("session/prepare"):
            key, _ = jax.random.split(jax.random.PRNGKey(self.cfg.seed))
            lp = _engine._loads_program(self.cfg.k)
            self._track(lp)
            labels_p = _engine.pad_labels(jnp.asarray(prev, jnp.int32),
                                          fs.v_pad)
            loads = lp.run(labels_p, fs.dd.deg_w)
            return fs, _engine.init_state(labels_p, loads, key)

    def _fast_bind(self, fs: _DeltaFast,
                   frontier: bool) -> "_engine.GraphBind":
        """The single-device GraphBind over the fast path's resident
        merged arrays (row-for-row what ``_single_bind`` builds from a
        rebuilt host graph)."""
        cfg, dd = self.cfg, fs.dd
        capacity = cfg.c * fs.tracker.total_weight / cfg.k
        exp = dd.coo if dd.mode == "single_pallas" else dd.score[:2]
        return _engine.GraphBind(
            deg_w=dd.deg_w, capacity=jnp.float32(capacity),
            num_real=jnp.int32(self._graph.num_vertices), score=dd.score,
            frontier=exp if frontier else ())

    def _try_fast_adapt(self, e_src, e_dst, prev, frontier,
                        record_history, callback
                        ) -> Optional[PartitionResult]:
        """The O(|delta|) adapt: merge on device, restart warm.  Returns
        None when ineligible or when the batch overflows the layout's
        slack (-> the caller rebuilds, bit-identically)."""
        out = self._fast_prepare(e_src, e_dst, prev, record_history,
                                 callback)
        if out is None:
            return None
        fs, state = out
        cfg = self.cfg
        V = self._graph.num_vertices
        capacity = cfg.c * fs.tracker.total_weight / cfg.k
        dd = fs.dd
        hist = None
        self._note_pass(sharded=fs.mode == "sharded",
                        merged=dd.next_slot - dd.csr_entries)
        if fs.mode == "single":
            bind = self._fast_bind(fs, bool(frontier))
            if frontier:
                prog = _engine._frontier_program(cfg, fs.opts_t)
                self._track(prog)
                with trace.span("session/dispatch"):
                    state, hist = prog.run(
                        state, self._active_mask(fs.v_pad), bind)
            else:
                with trace.span("session/dispatch"):
                    state = fs.prog_full.run(state, bind)
            eng = "fused"
        else:
            args = (jnp.float32(capacity), jnp.int32(V), dd.deg_w) \
                + tuple(dd.score) + tuple(fs.plan.device_args())
            if frontier:
                fused = fs.opts_t.resolved_fused_update() == "on"
                prog = _engine._sharded_frontier_program(
                    cfg, fs.opts_t, fs.mesh, fs.axis, fs.plan.signature(),
                    len(dd.score), fused=fused)
                self._track(prog)
                with trace.span("session/dispatch"):
                    state, hist = prog.run(
                        state, self._active_mask(fs.v_pad), *args)
            else:
                with trace.span("session/dispatch"):
                    state = fs.prog_full.run(state, *args)
            eng = "sharded"
        res = self._finish_state(state, V, eng, hist)
        self._dirty = None
        return res

    # -- scheduler-driven batched execution (repro.serve) ------------------

    def batchable(self) -> bool:
        """True when this session's adapts can ride the engine's batched
        same-bucket runner (``engine.run_batched``): single-device fused
        while_loop programs on the XLA score backend.  Sharded, chunked
        and host sessions -- and Pallas backends, whose kernels are not
        stacked under ``vmap`` here -- run serially through their own
        programs instead (the scheduler falls back transparently)."""
        self._check_open()
        opts = self.options
        if opts.mesh is not None or opts.engine not in ("auto", "fused"):
            return False
        return getattr(opts.backend(), "name", None) == "xla"

    def batch_key(self) -> tuple:
        """Cheap same-bucket compatibility key: two sessions whose keys
        match produce stackable ``adapt_parts`` work items (one compiled
        batched program, identical traced shapes).  Reads the BASE graph
        (no pending-delta materialization)."""
        self._check_open()
        graph, cfg = self._graph, self.cfg
        opts_t = _engine._autotuned(graph, cfg, self.options)
        padded, _ = _engine.padded_view(graph, opts_t)
        return (_engine._static_cfg(cfg), opts_t.backend().signature(),
                opts_t.resolved_fused_update() == "on",
                padded.num_vertices, padded.num_directed_entries)

    def adapt_parts(self, edge_updates: Optional[tuple] = None,
                    prev: Optional[np.ndarray] = None
                    ) -> Optional[tuple]:
        """Build -- without dispatching -- this session's next adapt as a
        ``(state, bind, cfg, opts)`` work item for the engine's batched
        same-bucket runner; the serving scheduler stacks items whose
        ``engine.batch_signature`` matches and runs them as ONE device
        call.  Returns None when the session is not ``batchable()``.

        Mirrors ``adapt(record_history=False)`` exactly: an eligible
        ``edge_updates`` delta takes the O(|delta|) merged-arrays fast
        path (one ``apply_delta`` scatter for the whole -- possibly
        coalesced -- batch); otherwise the classic rebuild produces the
        same work item from the rebuilt graph's bind, bit-identically.
        Feed the runner's output state to ``commit_adapt``; until then
        the session's previous labels are unchanged.
        """
        self._check_open()
        if not self.batchable():
            return None
        prev_arr = self._require_prev(prev)
        if edge_updates is not None:
            e_src, e_dst = _delta.check_edge_updates(
                edge_updates[0], edge_updates[1],
                self._graph.num_vertices, None)
            self._delta_seq += 1
            out = self._fast_prepare(e_src, e_dst, prev_arr, False, None)
            if out is not None:
                self._staged = None
                fs, state = out
                return state, self._fast_bind(fs, False), self.cfg, \
                    fs.opts_t
            self._fallback_adapts += 1
            new_graph = add_edges(self.graph, e_src, e_dst)
            self._host_rebuilds += 1
            self._staged = None
            self.graph = new_graph
        elif self._staged is not None:
            staged, self._staged = self._staged, None
            self.graph = staged
        graph = self.graph     # materializes any pending delta log
        from .incremental import extend_labels
        init = extend_labels(prev_arr, graph.num_vertices)
        cfg = self.cfg
        labels, loads, key = prepare_init(graph, cfg, init)
        opts_t = _engine._autotuned(graph, cfg, self.options)
        bind, padded = _engine._single_bind(graph, cfg, opts_t)
        state = _engine.init_state(
            _engine.pad_labels(labels, padded.num_vertices), loads, key)
        return state, bind, cfg, opts_t

    def commit_adapt(self, state) -> PartitionResult:
        """Record a batched runner's output state as this session's new
        stable result -- the exact bookkeeping ``adapt`` performs after
        its own dispatch (labels sliced to the real vertex set, previous
        labels advanced, dirty set cleared).  Materializes the state to
        host, so calling it after ``engine.run_batched`` blocks on the
        batch; schedulers run their prefetch policies first."""
        self._check_open()
        res = self._finish_state(state, self._graph.num_vertices,
                                 "fused", None)
        self._dirty = None
        return res

    def _note_pass(self, sharded: bool, merged: int = 0) -> None:
        """Note the score pass of the call's program, from the host's own
        merged-entry count (no device read): ``"transposed"`` on the XLA
        backend's single-device CSR-ordered arrays
        (``repro.kernels.ops.xla_scores``), a fast adapt's merged ones
        included, since its merge re-sorts them into CSR order;
        ``"forward"``, a label gather per entry, on a mesh and on the
        Pallas kernels."""
        xla = getattr(self.options.backend(), "name", None) == "xla"
        self._score_pass = ("transposed" if xla and not sharded
                            and not merged else "forward")

    def _active_mask(self, v_pad: int) -> jax.Array:
        active = np.zeros(v_pad, bool)
        if self._dirty is not None:
            active[:self._dirty.shape[0]] = self._dirty
        return jnp.asarray(active)

    def _finish_state(self, state, num_real: int, eng: str, hist,
                      history: Optional[list] = None) -> PartitionResult:
        """Wait for a run's final state (span ``session/wait``), copy it
        to the host as this session's new result (``session/fetch``)."""
        with trace.span("session/wait"):
            jax.block_until_ready((state, hist))
        with trace.span("session/fetch"):
            iters = int(state.iteration)
            if hist is not None:
                per_iter = tuple(float(x) for x in np.asarray(hist)[:iters])
                scored = float(sum(per_iter))
            else:
                per_iter, scored = (), -1.0
            res = PartitionResult(
                labels=np.asarray(state.labels)[:num_real],
                loads=np.asarray(state.loads), iterations=iters,
                halted=bool(state.halted), history=history or [],
                total_messages=float(state.total_messages), engine=eng,
                exchanged_bytes=float(state.exchanged_bytes),
                scored_vertices=scored, scored_per_iter=per_iter)
        self._last = res
        self._prev = res.labels
        self._runs += 1
        return res

    def _run_frontier(self, init, active, record_history,
                      callback) -> PartitionResult:
        """Frontier reconvergence on a materialized graph (the fallback
        compute path; the fast path drives the same programs off its
        resident merged arrays)."""
        if callback is not None or record_history is True:
            raise ValueError(
                "frontier=True records only per-iteration scored-vertex "
                "counts (PartitionResult.scored_per_iter); run without "
                "frontier for history/callbacks")
        graph, opts, cfg = self.graph, self.options, self.cfg
        if opts.engine in ("chunked", "host"):
            raise ValueError(
                f"frontier=True requires a while_loop engine (fused/"
                f"sharded/auto), not engine={opts.engine!r}")
        sharded = opts.mesh is not None or opts.engine == "sharded"
        self._note_pass(sharded=sharded)
        with trace.span("session/prepare"):
            labels, loads, key = prepare_init(graph, cfg, init)
        with trace.span("session/dispatch"):
            if sharded:
                state, hist = _engine.run_sharded_frontier(
                    graph, cfg, labels, loads, key, active, mesh=opts.mesh,
                    axis=opts.axis, opts=opts, on_program=self._track)
                eng = "sharded"
            else:
                state, hist = _engine.run_frontier(
                    graph, cfg, labels, loads, key, active, opts=opts,
                    on_program=self._track)
                eng = "fused"
        res = self._finish_state(state, graph.num_vertices, eng, hist)
        self._dirty = None
        return res

    def run_app(self, workload: str, labels: Optional[np.ndarray] = None,
                **kwargs) -> "repro.apps.AppResult":
        """Consume this session's partition: run a Pregel application
        (``"pagerank"`` / ``"wcc"`` / ``"bfs"`` / ``"sssp"``) on the
        session graph placed by its labels -- the end-to-end speedup
        measurement of the paper's Section 7, via
        :func:`repro.apps.run_app`.

        ``labels`` defaults to the session's current stable assignment
        (``partition()`` must have run); pass any vector (e.g.
        ``benchmarks.common.hash_labels``) to A/B a baseline placement
        on the same graph with zero recompiles.  Keyword args forward
        to :func:`repro.apps.run_app` (``plan``, ``combine``,
        ``overlap``, ``iters``, ``source``, ...); the mesh defaults to
        the session's ``options.mesh``.  The compiled app program joins
        the session's compile accounting (``session.compiles``).
        """
        self._check_open()
        from repro.apps import run_app as _run_app
        if labels is None:
            labels = self._prev
            if labels is None:
                raise ValueError("no labels yet: run partition() first "
                                 "or pass labels= explicitly")
        if "mesh" not in kwargs and self.options.mesh is not None:
            kwargs["mesh"] = self.options.mesh
        kwargs.setdefault("axis", self.options.axis)
        res = _run_app(self.graph, np.asarray(labels), workload, **kwargs)
        self._track(res.program)
        return res

    # -- introspection -----------------------------------------------------

    @property
    def labels(self) -> Optional[np.ndarray]:
        """The previous stable assignment (None before the first run)."""
        return self._prev

    @property
    def delta_watermark(self) -> int:
        """Monotone count of delta batches this session has accepted
        (``update()`` / ``adapt(edge_updates=)`` / ``adapt_parts``),
        whether merged on device, pending, or already materialized.
        Snapshots record it so a restore knows how many batches the
        saved labels reflect (``repro.cluster.snapshot``)."""
        return self._delta_seq

    def export_state(self) -> dict:
        """The session's partition state as a flat pytree of host arrays
        -- the checkpointable surface ``repro.cluster.snapshot`` saves
        through ``repro.ckpt``.

        O(V + k) only: the previous stable ``labels``, the ``loads``
        they imply, the rng key every run derives from
        (``jax.random.PRNGKey(cfg.seed)`` -- recorded for auditability;
        runs are deterministic functions of (graph, cfg, prev labels),
        which is what makes a restored session's continuation
        bit-identical), and the run / delta-watermark counters.  The
        graph itself is NOT included; it is rebuilt from the durable
        inputs (edge shards / base graph + replayed deltas) on restore.
        """
        self._check_open()
        if self._prev is None:
            raise ValueError("no stable labels to snapshot; run "
                             "partition() first or import_state()")
        if self._last is not None:
            loads = np.asarray(self._last.loads, np.float32)
        else:                  # re-derive exactly as prepare_init does
            loads = np.zeros(self.cfg.k, np.float32)
            np.add.at(loads, self._prev,
                      np.asarray(self._graph.deg_w, np.float32))
        return {
            "labels": np.asarray(self._prev, np.int32),
            "loads": loads,
            "rng_key": np.asarray(jax.random.PRNGKey(self.cfg.seed)),
            "runs": np.int64(self._runs),
            "delta_watermark": np.int64(self._delta_seq),
            "k": np.int64(self.cfg.k),
            "num_vertices": np.int64(self._graph.num_vertices),
        }

    def import_state(self, state: dict) -> "PartitionSession":
        """Restore a snapshot produced by :meth:`export_state` into this
        (freshly opened) session: the next ``adapt()``/``resize()``
        continues from the restored labels exactly as if this session
        had computed them.  The session's graph must already be at the
        snapshot's logical state (same vertices, deltas up to the
        watermark applied); labels for a since-grown vertex set are
        extended by the usual -1 -> least-loaded rule on the next run.
        Chainable."""
        self._check_open()
        labels = np.asarray(state["labels"], np.int32)
        if labels.shape[0] > self._graph.num_vertices:
            raise ValueError(
                f"snapshot has {labels.shape[0]} labels but the session "
                f"graph has {self._graph.num_vertices} vertices; rebuild "
                f"the graph at (or past) the snapshot watermark first")
        if int(state["k"]) != self.cfg.k:
            raise ValueError(
                f"snapshot was taken at k={int(state['k'])} but the "
                f"session is configured with k={self.cfg.k}; open with "
                f"the saved k and resize() afterwards")
        self._prev = labels
        self._last = None
        self._runs = int(state["runs"])
        self._delta_seq = int(state["delta_watermark"])
        self._staged = None
        self._dirty = None
        return self

    def stats(self) -> dict:
        """Session state: shape buckets, compile/run counters, padded
        layout, the delta fast-path counters (with what the pair ledger
        saw in the batches it planned: arcs given, pairs changed, pairs
        reciprocated from weight 1 to 2, redelivered arcs that changed
        nothing), and (on a mesh) the exchange plan's wire volumes.
        Reads the BASE graph -- pending fast-path deltas are reported
        under ``"delta"`` without forcing a host materialization."""
        self._check_open()
        graph, opts = self._graph, self.options
        padded, _ = _engine.padded_view(graph, opts)
        fs = self._delta
        d = {
            "num_vertices": graph.num_vertices,
            "num_directed_entries": graph.num_directed_entries,
            "k": self.cfg.k,
            "engine": opts.engine,
            "pad": opts.pad,
            "bucket": (_engine.graph_buckets(graph)
                       if opts.pad == "bucket" else None),
            "padded_shape": (padded.num_vertices,
                             padded.num_directed_entries),
            "runs": self._runs,
            "compiles": self.compiles,
            "score_pass": dict(self._score_passes),
            "programs": len(self._programs),
            "staged": (self._staged.num_vertices
                       if self._staged is not None else None),
            "delta": {
                "watermark": self._delta_seq,
                "pending_batches": len(self._pending),
                "merged_batches": fs.merged if fs is not None else 0,
                "fast_adapts": self._fast_adapts,
                "fallback_adapts": self._fallback_adapts,
                "host_rebuilds": self._host_rebuilds,
                "last_upload_bytes": self._delta_bytes_last,
                "upload_bytes_total": self._delta_bytes_total,
                "tracked_total_weight": (
                    fs.tracker.total_weight if fs is not None
                    else float(graph.total_weight)),
                **self._ledger,
            },
        }
        ndev = (opts.mesh.shape[opts.axis] if opts.mesh is not None else 1)
        opts_t = _engine._autotuned(graph, self.cfg, opts, ndev=ndev)
        backend = opts_t.backend()
        d["score_backend"] = backend.name
        d["fused_update"] = opts_t.resolved_fused_update()
        if backend.name == "pallas":
            from repro.kernels.ops import round_up
            d["tile_config"] = {"tile_v": backend.tile_v,
                                "tile_e": backend.tile_e,
                                "k_pad": round_up(max(self.cfg.k, 1), 128)}
        if self._last is not None:
            d["last"] = {"iterations": self._last.iterations,
                         "halted": self._last.halted,
                         "engine": self._last.engine,
                         "exchanged_bytes": self._last.exchanged_bytes,
                         "scored_vertices": self._last.scored_vertices,
                         "scored_per_iter": self._last.scored_per_iter}
        if opts.mesh is not None:
            from .distributed import comm_stats, shard_layout
            sg = shard_layout(padded, opts.mesh.shape[opts.axis],
                              pad=opts.pad == "bucket")
            d["exchange"] = comm_stats(sg, self.cfg, opts, graph=padded)
        return d

    # -- internals ---------------------------------------------------------

    def _require_prev(self, prev) -> np.ndarray:
        if prev is None:
            prev = self._prev
        if prev is None:
            raise ValueError("no previous labels in this session; run "
                             "partition() first or pass prev=")
        return np.asarray(prev, dtype=np.int32)

    def _traced(self, name: str, call: Callable, *args,
                delta: bool = False) -> PartitionResult:
        """``call(*args)`` in span ``name`` (``repro.core.trace``), with
        the result's ``iterations``, ``halted`` and ``engine``, the
        call's ``compiles`` (the growth of ``self.compiles``) and its
        ``score_pass`` (``_note_pass``, counted in ``stats()``) as attrs;
        a ``delta`` call adds ``fast`` (it took the O(|delta|) path) and
        that path's ``upload_bytes``."""
        compiles, fast = self.compiles, self._fast_adapts
        self._score_pass = None
        with trace.span(name) as attrs:
            res = call(*args)
            attrs.update(iterations=res.iterations, halted=res.halted,
                         engine=res.engine,
                         compiles=self.compiles - compiles,
                         score_pass=self._score_pass)
            self._score_passes[self._score_pass] += 1
            if delta:
                attrs["fast"] = self._fast_adapts > fast
                attrs["upload_bytes"] = (self._delta_bytes_last
                                         if attrs["fast"] else 0)
        return res

    def _run(self, init, record_history, callback,
             cfg: Optional[SpinnerConfig] = None) -> PartitionResult:
        """One run on the current graph, in the spans ``session/prepare``
        (initial labels and loads), ``session/bind`` (the runner: on a
        graph's first call its padded layout and edge upload),
        ``session/dispatch``, ``session/wait`` and ``session/fetch``."""
        self._check_open()
        graph, opts = self.graph, self.options
        cfg = self.cfg if cfg is None else cfg
        eng = opts.engine
        if eng == "auto":
            if opts.mesh is not None:
                eng = "sharded"   # an explicit mesh implies the sharded runner
            else:
                eng = "fused" if (record_history is False and
                                  callback is None) else "chunked"
        if opts.mesh is not None and eng != "sharded":
            raise ValueError(
                f"mesh= is only meaningful for engine='sharded', got "
                f"{eng!r}")
        if eng not in _ENGINES:
            raise ValueError(
                f"unknown engine {eng!r}; "
                "available: auto, fused, sharded, chunked, host")
        if eng in ("fused", "sharded"):
            # "chunked" is single-device only, so on a mesh there is no
            # per-iteration visibility at all -- say so instead of pointing
            # at an option the mesh check forbids.
            remedy = ("per-iteration history/callbacks are not available "
                      "on a device mesh; run engine='chunked' without "
                      "mesh= for traces" if eng == "sharded"
                      else "use engine='chunked' (or 'auto') instead")
            if callback is not None:
                raise ValueError(
                    f"engine={eng!r} cannot invoke a per-iteration "
                    f"callback; {remedy}")
            if record_history is True:
                raise ValueError(
                    f"engine={eng!r} cannot record per-iteration history; "
                    f"{remedy}")

        self._note_pass(sharded=eng == "sharded")
        with trace.span("session/prepare"):
            labels, loads, key = prepare_init(graph, cfg, init)
        with trace.span("session/bind"):
            run = self._runner(eng, graph, cfg, record_history, callback)
        with trace.span("session/dispatch"):
            state, history = run(labels, loads, key)
        # sharded labels come back padded to the sharded layout
        res = self._finish_state(state, graph.num_vertices, eng, None,
                                 history)
        self._dirty = None     # a full run reconverges every vertex
        return res

    def _runner(self, eng: str, graph: Graph, cfg: SpinnerConfig,
                record_history, callback) -> Callable:
        """Bind ``graph`` to engine ``eng``'s program (cached per graph
        and static configuration); ``run(labels, loads, key) -> (state,
        history)``.  The fused and sharded runs are one asynchronous
        dispatch; chunked and host runs sync per chunk or iteration."""
        opts = self.options
        if eng == "fused":
            runner = _engine.make_fused_runner(graph, cfg, opts=opts)
            self._track(getattr(runner, "program", None))
            return lambda labels, loads, key: (
                runner(_engine.init_state(labels, loads, key)), [])
        if eng == "sharded":
            mesh = opts.mesh
            if mesh is None:
                mesh = _engine._default_partition_mesh()
            runner = _engine.make_sharded_runner(graph, cfg, mesh,
                                                 opts.axis, opts=opts)
            self._track(getattr(runner, "program", None))
            v_pad = _engine.sharded_v_pad(graph, opts, mesh, opts.axis)
            return lambda labels, loads, key: (runner(_engine.init_state(
                _engine.pad_labels(labels, v_pad), loads, key)), [])
        if eng == "host":
            step = _engine.make_host_step(graph, cfg, opts)
            self._track(step.program)
            return lambda labels, loads, key: self._host_loop(
                step, graph, cfg, labels, loads, key,
                record_history is not False, callback)
        record = record_history is not False
        recorded = record or callback is not None   # a callback needs it
        chunk = opts.chunk_size or _engine.DEFAULT_CHUNK
        run_chunk = _engine.make_chunked_runner(graph, cfg, chunk,
                                                record=recorded, opts=opts)
        self._track(getattr(run_chunk, "program", None))

        def run(labels, loads, key):
            state, history = _engine.drive_chunks(
                run_chunk, _engine.init_state(labels, loads, key), cfg,
                chunk, recorded, callback)
            return state, history if record else []

        return run

    def _host_loop(self, step, graph, cfg, labels, loads, key,
                   record_history: bool, callback) -> tuple:
        """Legacy per-iteration host loop -- the fused engines' oracle.

        Runs the same padded layout and jitted step program as the fused
        runner; the halting compare runs in float32 (matching the
        on-device ``engine._halting_update`` bit for bit), so host and
        fused engines agree on iteration counts, not just trajectories.
        ``cfg`` arrives from ``_run`` (resize runs the new k before
        committing it to the session).  Returns ``(state, history)``.
        """
        num_real = graph.num_vertices
        labels = _engine.pad_labels(labels, step.v_pad)
        best_score = np.float32(-np.inf)
        eps32 = np.float32(cfg.eps)
        stall = 0
        history: List[dict] = []
        halted = False
        total_messages = 0.0
        it = 0
        for it in range(1, cfg.max_iters + 1):
            key, k_it = jax.random.split(key)
            labels, loads, score_g, n_mig, mig_mass = step(labels, loads,
                                                           k_it)
            score_g = np.float32(score_g)
            total_messages += float(mig_mass)
            if record_history or callback is not None:
                lab_np = np.asarray(labels)[:num_real]
                entry = {
                    "iteration": it,
                    "score": float(score_g),
                    "migrations": int(n_mig),
                    "message_mass": float(mig_mass),
                    "phi": metrics.phi(graph, lab_np),
                    "rho": metrics.rho(graph, lab_np, cfg.k),
                }
                if record_history:
                    history.append(entry)
                if callback is not None:
                    callback(it, entry)
            # Halting (Section 3.3): relative improvement below eps for
            # > w iters.  f32 arithmetic mirroring engine._halting_update;
            # on iteration 1 best_score is -inf, tol is inf, best + tol is
            # NaN and the compare is False (the invalid-op warning is
            # expected and suppressed).
            with np.errstate(invalid="ignore"):
                tol = eps32 * np.maximum(np.float32(1.0),
                                         np.abs(best_score))
                improved = score_g > best_score + tol
            best_score = np.maximum(best_score, score_g)
            if improved:
                stall = 0
            else:
                stall += 1
                if stall >= cfg.halt_window:
                    halted = True
                    break
        state = _engine.init_state(labels, loads, key)._replace(
            iteration=it, halted=halted, total_messages=total_messages)
        return state, history


def open_session(graph: Graph, cfg: SpinnerConfig,
                 options: Optional[EngineOptions] = None
                 ) -> PartitionSession:
    """Open a device-resident partitioning session (``spinner.open``)."""
    return PartitionSession(graph, cfg, options)
