"""Jit'd public wrappers for the Pallas kernels + the score-backend registry.

On a TPU backend the kernel runs compiled.  On the CPU backend it runs in
``interpret=True`` mode (the kernel body executed op-by-op on the host),
which is how the tests validate it.  Any other backend is an error: a run
that misses the chip must not pass for one that used it.

The score-backend protocol at the bottom is how the device-resident engine
(``repro.core.engine``) picks its ComputeScores implementation: a backend is
built once per (graph, k) at trace time and the returned closure is inlined
into the fused ``lax.while_loop`` / ``lax.scan`` body, so the XLA
scatter-add path and the Pallas tiled kernel are interchangeable without
any per-call dispatch.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Protocol, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import (Graph, TiledCSR, build_sharded_tiled_csr,
                              build_tiled_csr, round_robin_perm)

from . import ref
from .spinner_scores import (fused_update_from_tiles, scores_from_tiles,
                             spinner_scores_pallas)


def _default_interpret() -> bool:
    """Compiled on TPU, interpreted on CPU; no other backend runs them."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"the Pallas kernels run compiled on TPU or interpreted on CPU; "
            f"the default JAX backend is {backend!r}")
    return backend == "cpu"


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


@functools.partial(jax.jit, static_argnames=("tile_v", "k_pad", "k",
                                             "interpret"))
def _scores_from_tiles(labels, src_local, dst, w, perm, *, tile_v: int,
                       k_pad: int, k: int, interpret: bool):
    # jitted entry so standalone spinner_scores_tiled() calls cache their
    # compilation; engine traces inline scores_from_tiles directly
    return scores_from_tiles(labels, src_local, dst, w, perm, tile_v=tile_v,
                             k_pad=k_pad, k=k, interpret=interpret)


def spinner_scores_tiled(labels: jax.Array, *, tiled: TiledCSR, k: int,
                         interpret: Optional[bool] = None) -> jax.Array:
    """(V, k) ComputeScores matrix via the Pallas kernel."""
    if interpret is None:
        interpret = _default_interpret()
    k_pad = round_up(max(k, 1), 128)
    return _scores_from_tiles(
        labels, jnp.asarray(tiled.src_local), jnp.asarray(tiled.dst),
        jnp.asarray(tiled.weight), jnp.asarray(tiled.perm),
        tile_v=tiled.tile_v, k_pad=k_pad, k=k, interpret=interpret)


def spinner_scores(labels: jax.Array, graph: Graph, k: int,
                   tile_v: int = 128, tile_e: int = 128,
                   interpret: Optional[bool] = None) -> jax.Array:
    """Convenience: tile a Graph and compute its score matrix."""
    tiled = build_tiled_csr(graph, tile_v=tile_v, tile_e=tile_e)
    return spinner_scores_tiled(labels, tiled=tiled, k=k, interpret=interpret)


# ---------------------------------------------------------------------------
# Score-backend protocol: pluggable ComputeScores (Eq. 8 numerator)
# ---------------------------------------------------------------------------

class ScoreBackend(Protocol):
    """The Eq. 8 numerator as (graph-independent closure, per-graph args).

    The device-resident engine compiles runners once per SHAPE BUCKET and
    reuses them across graphs (see ``repro.core.session``), so a backend
    is split in two:

      * ``make_scores(k)`` / ``make_sharded_scores(k, v_local)`` return a
        pure traced closure ``(labels_or_lookup, *edge_args) -> scores``
        that reads only static python ints (k, tile sizes, interpret
        mode) off the backend -- its identity for the engine's program
        cache is ``signature()``;
      * ``graph_args(graph, k, pad)`` / ``sharded_graph_args(sg, k,
        dst_index, pad)`` build the per-graph device arrays the closure
        consumes.  ``pad=True`` buckets derived shapes (the Pallas chunk
        count) so a session rebinding a grown graph keeps the compile
        shape.  For the sharded form the arrays are host arrays with a
        leading ndev dimension; the engine commits row p to device p
        (``engine.shard_rows``) and threads them through ``shard_map``
        with ``PartitionSpec(axis)``; ``dst_index`` is the exchange plan's
        per-edge index (global vertex ids for all-gather/delta,
        halo-remapped slots for halo);
      * ``make_sharded_scores_split(k, v_local)`` /
        ``sharded_graph_args_split(sg, k, dst_index, pad)`` are the
        TWO-PHASE form for the engine's overlap schedule
        (``EngineOptions.overlap``): the edge shard is split at
        ``ShardedGraph.e_interior`` into an interior segment (dst labels
        readable from the local label shard) and a frontier segment (dst
        labels arriving via the exchange plan's lookup).  The returned
        ``(interior_fn, frontier_fn)`` closures both take the full split
        arg tuple: ``interior_fn(labels_local, *args)`` accumulates the
        interior partial while the exchange is in flight, and
        ``frontier_fn(partial, lookup, *args)`` finishes the (v_local,
        k) block.  The integer Eq. 3 edge weights make both f32 phases
        exact, so interior + frontier is bit-identical to the
        single-phase sum.

    A backend may ADDITIONALLY implement the FUSED vertex-update protocol
    (``EngineOptions.fused_update``): ``make_fused_update(k, *,
    degree_weighted, current_bonus)`` returns a whole-iteration closure
    ``fused(lookup, labels, deg_w, loads, noise, u, valid, reduce_, C,
    *fused_graph_args) -> (new_labels, new_loads, score_g, n_mig,
    mig_mass)`` matching ``engine.make_vertex_update``'s output contract
    bit for bit, but free to keep the (V, k) score matrix out of HBM
    (the Pallas megakernel does).  The sharded forms
    ``make_sharded_fused_update(k, v_local, ...)`` /
    ``make_sharded_fused_update_split(k, v_local, ...)`` mirror the
    scores/scores_split pair (the split interior returns a RAW partial in
    whatever layout the backend's frontier closure expects), with
    ``sharded_fused_graph_args`` / ``sharded_fused_graph_args_split``
    building their per-graph arrays.  ``fused_auto = True`` opts the
    backend into ``fused_update="auto"`` selection.

    The legacy ``build`` / ``build_sharded`` closure forms (args baked
    in) are RETIRED: every in-repo caller uses the split protocol above,
    and the base class methods below raise with a pointer at it.
    """

    name: str

    def signature(self) -> tuple: ...

    def make_scores(self, k: int) -> Callable: ...

    def graph_args(self, graph: Graph, k: int, pad: bool = False
                   ) -> tuple: ...

    def make_sharded_scores(self, k: int, v_local: int) -> Callable: ...

    def sharded_graph_args(self, sg, k: int, dst_index: np.ndarray,
                           pad: bool = False) -> tuple: ...

    def make_sharded_scores_split(self, k: int, v_local: int
                                  ) -> tuple: ...

    def sharded_graph_args_split(self, sg, k: int, dst_index: np.ndarray,
                                 pad: bool = False) -> tuple: ...


def _legacy_build_error(name: str) -> NotImplementedError:
    return NotImplementedError(
        f"ScoreBackend.{name} was retired: the baked-in closure form kept "
        "per-graph arrays alive inside compiled programs.  Use the split "
        "protocol instead -- make_scores(k) / graph_args(graph, k, pad) "
        "(or the sharded/fused variants) -- and pass the args explicitly; "
        "see the ScoreBackend docstring in repro.kernels.ops.")


def _split_dst_views(sg, dst_index) -> tuple:
    """(interior dst as LOCAL vertex ids, frontier dst in plan layout).

    The interior conversion is plan-independent: an interior edge's dst
    lives on its own device by construction, so its local id is just the
    global id minus the owner offset (interior pad slots carry the
    owner's vertex 0 and land on local id 0).  The frontier half keeps
    whatever index the exchange plan's lookup array expects.
    """
    e = sg.e_interior
    offs = (np.arange(sg.ndev, dtype=np.int64) * sg.v_per_dev)[:, None]
    d_int = (sg.dst[:, :e].astype(np.int64) - offs).astype(np.int32)
    d_fro = np.asarray(dst_index)[:, e:].astype(np.int32)
    return d_int, d_fro


def _blocked_cumsum(x: jax.Array, block: int = 128) -> jax.Array:
    """``jnp.cumsum`` of a 1-D array as sums within ``block``-wide rows
    plus the carried sums of the rows before.  The TPU compiler rewrites
    a longer scan into this tree itself, but its ops then carry no op
    name and their device time falls outside every scope."""
    n = x.shape[0]
    if n <= block:
        return jnp.cumsum(x.reshape(1, n), axis=1).reshape(n)
    rows = jnp.pad(x, (0, -n % block)).reshape(-1, block)
    inner = jnp.cumsum(rows, axis=1)
    totals = inner[:, -1]
    before = _blocked_cumsum(totals, block) - totals
    return (inner + before[:, None]).reshape(-1)[:n]


def transposed_scores(labels: jax.Array, dst: jax.Array, w: jax.Array,
                      row_ptr: jax.Array, k: int) -> jax.Array:
    """``spinner_scores_ref`` over a symmetric CSR-ordered edge list,
    bit for bit, without gathering a label per entry.

    Each entry (a, b, w) scores ``[b, labels[a]]`` -- its twin's score
    -- so its label is its own row's: a run-length expansion of
    ``labels`` over ``row_ptr`` (scope ``lpa/gather``), a scatter of the
    label steps ``labels[v] - labels[v - 1]`` at the sorted row starts
    and a cumulative sum along the entries.  Empty rows telescope; a row
    start at the end of the list is dropped.  The scatter-add then sees
    the same multiset of (flat index, weight) pairs as the forward pass,
    and the integer weights make its sums exact in any order.
    """
    with jax.named_scope("lpa/gather"):
        steps = jnp.diff(labels, prepend=jnp.zeros((1,), labels.dtype))
        marks = jnp.zeros(dst.shape, labels.dtype).at[row_ptr[:-1]].add(
            steps, mode="drop", indices_are_sorted=True)
        row_label = _blocked_cumsum(marks)
    return ref.add_scores(jnp.zeros((labels.shape[0], k), jnp.float32),
                          dst, row_label, w)


def xla_scores(labels: jax.Array, src: jax.Array, dst: jax.Array,
               w: jax.Array, row_ptr: jax.Array, merged: jax.Array,
               k: int) -> jax.Array:
    """The XLA backend's (V, k) scores: the transposed pass while the
    edge arrays are in CSR order (``merged == 0``), else the forward
    gather + scatter-add of ``spinner_scores_ref``.  ``merged`` counts
    entries out of that order; a session's delta merge re-sorts what it
    writes into CSR order (``repro.core.delta``), so it stays 0 there."""
    return jax.lax.cond(
        merged == 0,
        lambda: transposed_scores(labels, dst, w, row_ptr, k),
        lambda: ref.spinner_scores_ref(labels, src, dst, w,
                                       labels.shape[0], k))


@dataclasses.dataclass(frozen=True)
class XlaScatterBackend:
    """ComputeScores via XLA scatter-add -- the Pallas kernel's oracle.

    Its single-device args are ``(src, dst, w, row_ptr, merged)``: the
    padded CSR upload, its int32 row offsets and the count of entries
    merged out of CSR order (0 here; see ``xla_scores``)."""

    name: str = "xla"

    def signature(self) -> tuple:
        return ("xla",)

    def make_scores(self, k: int) -> Callable:
        def scores(labels, src, dst, w, row_ptr, merged):
            return xla_scores(labels, src, dst, w, row_ptr, merged, k)
        return scores

    def graph_args(self, graph: Graph, k: int, pad: bool = False) -> tuple:
        from repro.core.engine import device_edges   # shared upload cache
        src, dst, w, _ = device_edges(graph)
        return (src, dst, w, jnp.asarray(graph.row_ptr, jnp.int32),
                jnp.int32(0))

    def make_sharded_scores(self, k: int, v_local: int) -> Callable:
        """Local scatter-add over this device's edge shard.

        Row-for-row ``spinner_scores_ref`` restricted to the local vertex
        range (zero-weight padding rows add 0 to row 0 and change
        nothing), so on a 1-device mesh -- where the shard is the whole
        CSR-ordered edge list -- the result is bit-identical to the
        unsharded path.
        """
        def scores(lookup, src_local, dst_idx, w):
            return ref.scatter_scores(
                jnp.zeros((v_local, k), jnp.float32), src_local, lookup,
                dst_idx, w)
        return scores

    def sharded_graph_args(self, sg, k: int, dst_index: np.ndarray,
                           pad: bool = False) -> tuple:
        # allgather/delta index with the global dst ids (dst_index IS
        # sg.dst); halo's remapped slots are a different array
        return (sg.src_local, np.asarray(dst_index, np.int32), sg.weight)

    def make_sharded_scores_split(self, k: int, v_local: int) -> tuple:
        """Two-phase scatter-add over the [interior | frontier] segments
        (see the protocol docstring): the interior half reads the local
        label shard, the frontier half the exchange plan's lookup."""
        def interior(labels_local, src_i, dst_i, w_i, src_f, dst_f, w_f):
            return ref.scatter_scores(
                jnp.zeros((v_local, k), jnp.float32), src_i, labels_local,
                dst_i, w_i)

        def frontier(partial, lookup, src_i, dst_i, w_i, src_f, dst_f,
                     w_f):
            return ref.scatter_scores(partial, src_f, lookup, dst_f, w_f)

        return interior, frontier

    def sharded_graph_args_split(self, sg, k: int, dst_index: np.ndarray,
                                 pad: bool = False) -> tuple:
        e = sg.e_interior
        d_int, d_fro = _split_dst_views(sg, dst_index)
        return (sg.src_local[:, :e], d_int, sg.weight[:, :e],
                sg.src_local[:, e:], d_fro, sg.weight[:, e:])

    # ---- fused vertex update: scatter scores + the reference halves ----
    # XLA has no VMEM residency to exploit, so the "fused" form is simply
    # the scatter-add composed with engine.make_update_parts -- the
    # reference implementation every fused kernel is measured against.
    fused_auto = False

    def make_fused_update(self, k: int, *, degree_weighted: bool,
                          current_bonus: float,
                          frontier: bool = False) -> Callable:
        from repro.core.engine import make_update_parts   # lazy: no cycle
        propose, finish = make_update_parts(
            k, degree_weighted=degree_weighted, current_bonus=current_bonus)

        def fused(lookup, labels, deg_w, loads, noise, u, valid, reduce_,
                  C, src, dst, w, row_ptr, merged):
            scores = xla_scores(lookup, src, dst, w, row_ptr, merged, k)
            best, tb, tc, m = propose(scores, labels, deg_w, loads, noise,
                                      valid, C)
            out = finish(best, tb, tc, m, labels, deg_w, loads, u, valid,
                         reduce_, C)
            if frontier:
                # the frontier runner needs the pre-throttle want mask to
                # carry the active set forward and detect the drain
                return out + ((best != labels) & valid,)
            return out
        return fused

    def fused_graph_args(self, graph: Graph, k: int,
                         pad: bool = False) -> tuple:
        return self.graph_args(graph, k, pad=pad)

    def make_sharded_fused_update(self, k: int, v_local: int, *,
                                  degree_weighted: bool,
                                  current_bonus: float,
                                  frontier: bool = False) -> Callable:
        from repro.core.engine import make_update_parts
        propose, finish = make_update_parts(
            k, degree_weighted=degree_weighted, current_bonus=current_bonus)

        def fused(lookup, labels, deg_w, loads, noise, u, valid, reduce_,
                  C, src_local, dst_idx, w):
            scores = ref.scatter_scores(
                jnp.zeros((v_local, k), jnp.float32), src_local, lookup,
                dst_idx, w)
            best, tb, tc, m = propose(scores, labels, deg_w, loads, noise,
                                      valid, C)
            out = finish(best, tb, tc, m, labels, deg_w, loads, u, valid,
                         reduce_, C)
            if frontier:
                return out + ((best != labels) & valid,)
            return out
        return fused

    def sharded_fused_graph_args(self, sg, k: int, dst_index: np.ndarray,
                                 pad: bool = False) -> tuple:
        return self.sharded_graph_args(sg, k, dst_index, pad=pad)

    def make_sharded_fused_update_split(self, k: int, v_local: int, *,
                                        degree_weighted: bool,
                                        current_bonus: float) -> tuple:
        from repro.core.engine import make_update_parts
        propose, finish = make_update_parts(
            k, degree_weighted=degree_weighted, current_bonus=current_bonus)

        def interior(labels_local, src_i, dst_i, w_i, src_f, dst_f, w_f):
            return ref.scatter_scores(
                jnp.zeros((v_local, k), jnp.float32), src_i, labels_local,
                dst_i, w_i)

        def frontier(partial, lookup, labels, deg_w, loads, noise, u,
                     valid, reduce_, C, src_i, dst_i, w_i, src_f, dst_f,
                     w_f):
            scores = ref.scatter_scores(partial, src_f, lookup, dst_f, w_f)
            best, tb, tc, m = propose(scores, labels, deg_w, loads, noise,
                                      valid, C)
            return finish(best, tb, tc, m, labels, deg_w, loads, u, valid,
                          reduce_, C)

        return interior, frontier

    def sharded_fused_graph_args_split(self, sg, k: int,
                                       dst_index: np.ndarray,
                                       pad: bool = False) -> tuple:
        return self.sharded_graph_args_split(sg, k, dst_index, pad=pad)

    def build(self, graph: Graph, k: int):
        raise _legacy_build_error("build")

    def build_sharded(self, sg, k: int, dst_index: np.ndarray):
        raise _legacy_build_error("build_sharded")


@dataclasses.dataclass(frozen=True)
class PallasTiledBackend:
    """ComputeScores via the tiled one-hot-matmul Pallas kernel.

    Edge weights are small integers ({1, 2}, Eq. 3), so the f32 MXU
    accumulation is exact and the result is bit-identical to the XLA
    scatter-add backend regardless of summation order -- including on
    per-shard retilings inside ``shard_map``.
    """

    name: str = "pallas"
    tile_v: int = 128
    tile_e: int = 128
    interpret: Optional[bool] = None   # None -> compiled on TPU, interpret on CPU

    def _interpret(self) -> bool:
        return (self.interpret if self.interpret is not None
                else _default_interpret())

    def signature(self) -> tuple:
        return ("pallas", self.tile_v, self.tile_e, self._interpret())

    def make_scores(self, k: int) -> Callable:
        k_pad = round_up(max(k, 1), 128)
        interpret = self._interpret()

        def scores(labels, src_local, dst, w, perm):
            return scores_from_tiles(labels, src_local, dst, w, perm,
                                     tile_v=self.tile_v, k_pad=k_pad, k=k,
                                     interpret=interpret)
        return scores

    def graph_args(self, graph: Graph, k: int, pad: bool = False) -> tuple:
        # pad mode floors the total slot count at the bucketed edge
        # capacity, so the tiled layout carries at least the COO bucket's
        # slack for the on-device delta merge (see repro.core.delta)
        tiled = build_tiled_csr(
            graph, tile_v=self.tile_v, tile_e=self.tile_e,
            pad_chunks=4 if pad else 1,
            min_total_slots=graph.num_directed_entries if pad else 0)
        return tuple(map(jnp.asarray, (tiled.src_local, tiled.dst,
                                       tiled.weight, tiled.perm)))

    def make_sharded_scores(self, k: int, v_local: int) -> Callable:
        return self.make_scores(k)     # perm is (v_local,): same pipeline

    def sharded_graph_args(self, sg, k: int, dst_index: np.ndarray,
                           pad: bool = False) -> tuple:
        st = build_sharded_tiled_csr(sg, dst_index, tile_v=self.tile_v,
                                     tile_e=self.tile_e,
                                     pad_chunks=4 if pad else 1)
        return st.src_local, st.dst, st.weight, st.perm

    def make_sharded_scores_split(self, k: int, v_local: int) -> tuple:
        """Two kernel launches over independent segment tilings: the
        interior tiles gather from the local label shard (their dst ids
        are pre-remapped to local), the frontier tiles from the exchange
        lookup; the f32 MXU accumulations are exact on the integer
        weights, so the sum matches the single-tiling kernel bit for
        bit."""
        base = self.make_scores(k)

        def interior(labels_local, si, di, wi, pi, sf, df, wf, pf):
            return base(labels_local, si, di, wi, pi)

        def frontier(partial, lookup, si, di, wi, pi, sf, df, wf, pf):
            return partial + base(lookup, sf, df, wf, pf)

        return interior, frontier

    def sharded_graph_args_split(self, sg, k: int, dst_index: np.ndarray,
                                 pad: bool = False) -> tuple:
        e = sg.e_interior
        d_int, d_fro = _split_dst_views(sg, dst_index)
        seg_i = dataclasses.replace(sg, src_local=sg.src_local[:, :e],
                                    dst=sg.dst[:, :e],
                                    weight=sg.weight[:, :e], edge_perm=None)
        seg_f = dataclasses.replace(sg, src_local=sg.src_local[:, e:],
                                    dst=sg.dst[:, e:],
                                    weight=sg.weight[:, e:], edge_perm=None)
        st_i = build_sharded_tiled_csr(seg_i, d_int, tile_v=self.tile_v,
                                       tile_e=self.tile_e,
                                       pad_chunks=4 if pad else 1)
        st_f = build_sharded_tiled_csr(seg_f, d_fro, tile_v=self.tile_v,
                                       tile_e=self.tile_e,
                                       pad_chunks=4 if pad else 1)
        return (st_i.src_local, st_i.dst, st_i.weight, st_i.perm,
                st_f.src_local, st_f.dst, st_f.weight, st_f.perm)

    # ---- fused vertex update: the megakernel (scores never hit HBM) ----
    # The (tile_v, k_pad) block stays in VMEM from edge reduction through
    # the Eq. 7-8 argmax proposal; only (tile_v,) vectors and the (1,
    # k_pad) M(l) partial come back.  The Eq. 11-12 migration test runs as
    # an XLA epilogue (engine.make_update_parts' ``finish``) because the
    # acceptance probability needs the globally reduced M(l).
    fused_auto = True

    def make_fused_update(self, k: int, *, degree_weighted: bool,
                          current_bonus: float,
                          frontier: bool = False) -> Callable:
        from repro.core.engine import make_update_parts   # lazy: no cycle
        _, finish = make_update_parts(
            k, degree_weighted=degree_weighted, current_bonus=current_bonus)
        k_pad = round_up(max(k, 1), 128)
        interpret = self._interpret()

        def fused(lookup, labels, deg_w, loads, noise, u, valid, reduce_,
                  C, src_local, dst, w, perm, inv_perm, deg_t):
            best, tb, tc, m = fused_update_from_tiles(
                lookup, labels, deg_t, noise, valid, loads / C,
                src_local, dst, w, perm, inv_perm, tile_v=self.tile_v,
                k_pad=k_pad, k=k, current_bonus=current_bonus,
                degree_weighted=degree_weighted, interpret=interpret,
                frontier=frontier)
            out = finish(best, tb, tc, m, labels, deg_w, loads, u, valid,
                         reduce_, C)
            if frontier:
                return out + ((best != labels) & valid,)
            return out
        return fused

    def fused_graph_args(self, graph: Graph, k: int,
                         pad: bool = False) -> tuple:
        tiled = build_tiled_csr(
            graph, tile_v=self.tile_v, tile_e=self.tile_e,
            pad_chunks=4 if pad else 1,
            min_total_slots=graph.num_directed_entries if pad else 0)
        return tuple(map(jnp.asarray, (tiled.src_local, tiled.dst,
                                       tiled.weight, tiled.perm,
                                       tiled.inv_perm, tiled.deg_t)))

    def make_sharded_fused_update(self, k: int, v_local: int, *,
                                  degree_weighted: bool,
                                  current_bonus: float,
                                  frontier: bool = False) -> Callable:
        # per-shard arrays are exactly a single-device tiling of the
        # shard's local vertex range: same closure
        return self.make_fused_update(k, degree_weighted=degree_weighted,
                                      current_bonus=current_bonus,
                                      frontier=frontier)

    def sharded_fused_graph_args(self, sg, k: int, dst_index: np.ndarray,
                                 pad: bool = False) -> tuple:
        st = build_sharded_tiled_csr(sg, dst_index, tile_v=self.tile_v,
                                     tile_e=self.tile_e,
                                     pad_chunks=4 if pad else 1)
        return (st.src_local, st.dst, st.weight, st.perm, st.inv_perm,
                st.deg_t)

    def make_sharded_fused_update_split(self, k: int, v_local: int, *,
                                        degree_weighted: bool,
                                        current_bonus: float) -> tuple:
        """Overlap form: the interior kernel runs while the exchange is in
        flight and returns its RAW tiled (T * tile_v, k_pad) partial; the
        frontier megakernel seeds its VMEM accumulator with that partial
        (``acc_init``), which is row-compatible because both segments are
        tiled against ONE shared permutation (``ext_perm``)."""
        from repro.core.engine import make_update_parts
        _, finish = make_update_parts(
            k, degree_weighted=degree_weighted, current_bonus=current_bonus)
        k_pad = round_up(max(k, 1), 128)
        interpret = self._interpret()

        def interior(labels_local, si, di, wi, sf, df, wf, perm, inv_perm,
                     deg_t):
            with jax.named_scope("lpa/gather"):
                dst_label = labels_local[di]
            with jax.named_scope("lpa/scatter"):
                return spinner_scores_pallas(si, dst_label, wi,
                                             tile_v=self.tile_v,
                                             k_pad=k_pad,
                                             interpret=interpret)

        def frontier(partial, lookup, labels, deg_w, loads, noise, u,
                     valid, reduce_, C, si, di, wi, sf, df, wf, perm,
                     inv_perm, deg_t):
            best, tb, tc, m = fused_update_from_tiles(
                lookup, labels, deg_t, noise, valid, loads / C,
                sf, df, wf, perm, inv_perm, tile_v=self.tile_v,
                k_pad=k_pad, k=k, current_bonus=current_bonus,
                degree_weighted=degree_weighted, interpret=interpret,
                acc_init=partial)
            return finish(best, tb, tc, m, labels, deg_w, loads, u, valid,
                          reduce_, C)

        return interior, frontier

    def sharded_fused_graph_args_split(self, sg, k: int,
                                       dst_index: np.ndarray,
                                       pad: bool = False) -> tuple:
        e = sg.e_interior
        d_int, d_fro = _split_dst_views(sg, dst_index)
        # one degree-balanced row layout shared by both segment tilings,
        # so interior partial rows line up with the frontier accumulator
        ext = np.stack([round_robin_perm(sg.deg_w[p], self.tile_v)
                        for p in range(sg.ndev)])
        seg_i = dataclasses.replace(sg, src_local=sg.src_local[:, :e],
                                    dst=sg.dst[:, :e],
                                    weight=sg.weight[:, :e], edge_perm=None)
        seg_f = dataclasses.replace(sg, src_local=sg.src_local[:, e:],
                                    dst=sg.dst[:, e:],
                                    weight=sg.weight[:, e:], edge_perm=None)
        st_i = build_sharded_tiled_csr(seg_i, d_int, tile_v=self.tile_v,
                                       tile_e=self.tile_e,
                                       pad_chunks=4 if pad else 1,
                                       ext_perm=ext)
        st_f = build_sharded_tiled_csr(seg_f, d_fro, tile_v=self.tile_v,
                                       tile_e=self.tile_e,
                                       pad_chunks=4 if pad else 1,
                                       ext_perm=ext)
        # shared layout -> one perm/inv_perm/deg_t triple serves both
        return (st_i.src_local, st_i.dst, st_i.weight,
                st_f.src_local, st_f.dst, st_f.weight,
                st_f.perm, st_f.inv_perm, st_f.deg_t)

    def build(self, graph: Graph, k: int):
        raise _legacy_build_error("build")

    def build_sharded(self, sg, k: int, dst_index: np.ndarray):
        raise _legacy_build_error("build_sharded")


SCORE_BACKENDS = {
    "xla": XlaScatterBackend(),
    "pallas": PallasTiledBackend(),
}


def get_score_backend(backend: Union[str, ScoreBackend]) -> ScoreBackend:
    """Resolve a backend name; backend instances pass through unchanged."""
    if isinstance(backend, str):
        try:
            return SCORE_BACKENDS[backend]
        except KeyError:
            raise ValueError(
                f"unknown score backend {backend!r}; "
                f"available: {sorted(SCORE_BACKENDS)}") from None
    return backend
