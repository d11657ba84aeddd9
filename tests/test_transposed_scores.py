"""The XLA backend's transposed score pass against the forward oracle.

``ops.transposed_scores`` takes each entry's label from its own CSR row
instead of gathering its neighbour's; on a symmetric graph it must give
``ref.spinner_scores_ref``'s scores bit for bit, and the runs built on it
the oracle's labels and iteration counts.  A session's fast adapt sorts
the arrays it merges into back into CSR order, so it runs the transposed
pass too.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (add_edges, engine, from_edges, generators,
                        open_session, trace)
from repro.core.engine import EngineOptions
from repro.core.graph import pad_graph
from repro.core.spinner import SpinnerConfig, prepare_init
from repro.kernels import ops, ref


def _kronecker(scale: int, edgefactor: int, seed: int):
    """A Graph500-style R-MAT graph (initiator 0.57/0.19/0.19/0.05):
    hub skew and many isolated vertices."""
    rng = np.random.default_rng(seed)
    m = edgefactor << scale
    src = np.zeros(m, np.int64)
    dst = np.zeros(m, np.int64)
    for level in range(scale):
        u = rng.random(m)
        row = u >= 0.76
        col = ((u >= 0.57) & ~row) | (u >= 0.95)
        src |= row.astype(np.int64) << level
        dst |= col.astype(np.int64) << level
    return from_edges(src, dst, 1 << scale, directed=False)


def _with_isolated(graph, seed: int):
    """The same edges over a quarter more vertex ids, spread at random,
    so isolated vertices sit between the rows."""
    v = graph.num_vertices
    ids = np.sort(np.random.default_rng(seed).choice(
        v + v // 4, v, replace=False))
    return from_edges(ids[graph.src], ids[graph.dst], v + v // 4)


GENERATORS = {
    "watts_strogatz": lambda: generators.watts_strogatz(700, 8, 0.3,
                                                        seed=3),
    "kronecker": lambda: _kronecker(9, 8, seed=4),
}


def _layout(graph, layout: str):
    v, e = graph.num_vertices, graph.num_directed_entries
    if layout == "isolated":
        return _with_isolated(graph, seed=5)
    if layout == "pad_rows":          # v_pad > V: pad rows of their own
        return pad_graph(graph, v + 37, e + 101)
    assert layout == "pad_last"       # v_pad == V: pads on the last row
    return pad_graph(graph, v, e + 101)


@pytest.mark.parametrize("k", [2, 64, 256])
@pytest.mark.parametrize("layout", ["isolated", "pad_rows", "pad_last"])
@pytest.mark.parametrize("gen", sorted(GENERATORS))
def test_transposed_pass_matches_oracle(gen, layout, k):
    g = _layout(GENERATORS[gen](), layout)
    g.validate()
    v = g.num_vertices
    labels = jnp.asarray(
        np.random.default_rng(k).integers(0, k, v), jnp.int32)
    got = ops.transposed_scores(labels, jnp.asarray(g.dst),
                                jnp.asarray(g.weight),
                                jnp.asarray(g.row_ptr, jnp.int32), k)
    want = ref.spinner_scores_ref(labels, jnp.asarray(g.src),
                                  jnp.asarray(g.dst), jnp.asarray(g.weight),
                                  v, k)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n", [1, 127, 128, 129, 16_385, 40_000])
def test_blocked_cumsum_is_cumsum(n):
    x = jnp.asarray(np.random.default_rng(n).integers(-9, 9, n), jnp.int32)
    assert np.array_equal(np.asarray(ops._blocked_cumsum(x)),
                          np.cumsum(np.asarray(x)))


@dataclasses.dataclass(frozen=True)
class ForwardOnly(ops.XlaScatterBackend):
    """The XLA backend held to the forward pass: the oracle of a run."""

    name: str = "xla-forward"

    def signature(self) -> tuple:
        return ("xla-forward",)

    def make_scores(self, k: int):
        def scores(labels, src, dst, w, row_ptr, merged):
            return ref.spinner_scores_ref(labels, src, dst, w,
                                          labels.shape[0], k)
        return scores


def _same_run(a, b):
    assert np.array_equal(np.asarray(a.labels), np.asarray(b.labels))
    assert int(a.iteration) == int(b.iteration)
    assert np.array_equal(np.asarray(a.loads), np.asarray(b.loads))
    assert float(a.score) == float(b.score)


@pytest.mark.parametrize("gen", sorted(GENERATORS))
def test_fused_runner_matches_forward_oracle(gen):
    g = GENERATORS[gen]()
    cfg = SpinnerConfig(k=8, seed=11, max_iters=60)
    labels, loads, key = prepare_init(g, cfg, None)
    runs = [engine.make_fused_runner(g, cfg, opts=opts)(
                engine.init_state(labels, loads, key))
            for opts in (EngineOptions(),
                         EngineOptions(score_backend=ForwardOnly()))]
    assert int(runs[0].iteration) > 3
    _same_run(*runs)


@pytest.mark.parametrize("eng", ["fused", "chunked", "host"])
@pytest.mark.parametrize("gen", sorted(GENERATORS))
def test_session_partition_matches_forward_oracle(gen, eng):
    g = GENERATORS[gen]()
    cfg = SpinnerConfig(k=8, seed=12, max_iters=61)
    results = []
    for backend in ("xla", ForwardOnly()):
        opts = EngineOptions(engine=eng, score_backend=backend)
        with open_session(g, cfg, opts) as s:
            results.append(s.partition(record_history=False))
            assert s.stats()["score_pass"] == (
                {"transposed": 1, "forward": 0} if backend == "xla"
                else {"transposed": 0, "forward": 1})
    a, b = results
    assert np.array_equal(a.labels, b.labels)
    assert a.iterations == b.iterations
    assert np.array_equal(a.loads, b.loads)


def test_fast_adapt_takes_the_transposed_pass():
    """A fast adapt merges entries into the slack and sorts the edge list
    back into CSR order: its run takes the transposed branch of the same
    compiled program and gives the rebuilt layout's result bit for bit."""
    g = generators.watts_strogatz(600, 8, 0.3, seed=6)
    cfg = SpinnerConfig(k=6, seed=13, max_iters=62)
    rng = np.random.default_rng(7)
    batch = (rng.integers(0, 600, 24), rng.integers(0, 600, 24))
    with open_session(g, cfg) as fast, open_session(g, cfg) as slow:
        base = fast.partition(record_history=False)
        slow.partition(record_history=False)
        program = engine._fused_program(
            cfg, engine._autotuned(g, cfg, EngineOptions()))
        before = program.compiles()
        trace.clear()
        got = fast.adapt(edge_updates=batch, record_history=False)
        assert program.compiles() == before
        want = slow.adapt(new_graph=fast.graph, prev=base.labels,
                          record_history=False)
        stats = fast.stats()
    (call, rebuilt) = trace.spans("session/adapt")
    assert call.attrs["fast"] is True
    assert call.attrs["score_pass"] == "transposed"
    assert rebuilt.attrs["score_pass"] == "transposed"
    assert stats["score_pass"] == {"transposed": 2, "forward": 0}
    assert np.array_equal(got.labels, want.labels)
    assert got.iterations == want.iterations
    assert np.array_equal(got.loads, want.loads)


def _arc_stream(g, rounds: int, seed: int):
    """``rounds`` batches of arcs: new random arcs, follow-backs of
    pairs given one way (their weight goes from 1 to 2), an arc of the
    base graph given again, and one of the previous batch."""
    rng = np.random.default_rng(seed)
    v = g.num_vertices
    half = g.src < g.dst
    lo, hi = g.src[half], g.dst[half]
    one_way = np.flatnonzero(g.dirs != 3)
    given_lo_hi = g.dirs[one_way] == 1
    back = np.stack([np.where(given_lo_hi, hi[one_way], lo[one_way]),
                     np.where(given_lo_hi, lo[one_way], hi[one_way])], 1)
    again = back[:, ::-1]
    order = rng.permutation(one_way.size)
    batches = []
    for r in range(rounds):
        pick = order[4 * r:4 * r + 4]
        arcs = [rng.integers(0, v, (10, 2)), back[pick[:3]], again[pick[3:]]]
        if batches:                     # the last batch's follow-back
            arcs.append(np.stack(batches[-1], 1)[10:11])
        a = np.concatenate(arcs)
        batches.append((a[:, 0], a[:, 1]))
    return batches


def _pads_last(v: int, seed: int):
    """A small-world graph whose vertex count is its own bucket, so the
    padded layout parks its slack on the last real row."""
    g = generators.watts_strogatz(v, 6, 0.3, seed=seed)
    assert engine.graph_buckets(g)[0] == v
    return g


STREAM_GRAPHS = {
    "pad_rows": lambda: generators.watts_strogatz(600, 8, 0.3, seed=6),
    "pad_last": lambda: _pads_last(640, seed=8),
}


@pytest.mark.parametrize("name", sorted(STREAM_GRAPHS))
def test_fast_adapts_keep_the_merged_list_in_csr_order(name):
    """Consecutive fast adapts: after each merge the real entries
    ``[0, next_slot)`` are sorted by row with ``row_ptr`` their host
    recount, the slack past them weighs 0, and the adapt gives a rebuilt
    session's labels, iterations and loads."""
    g = STREAM_GRAPHS[name]()
    v = g.num_vertices
    cfg = SpinnerConfig(k=5, seed=17, max_iters=63)
    with open_session(g, cfg) as fast:
        prev = fast.partition(record_history=False).labels
        want_g = g
        for batch in _arc_stream(g, 4, seed=9):
            trace.clear()
            got = fast.adapt(edge_updates=batch, record_history=False)
            (merge,) = trace.spans("delta/merge")
            dd = fast._delta.dd
            src, _, w, row_ptr, merged = (np.asarray(a) for a in dd.score)
            fill = dd.next_slot
            assert merge.attrs["csr_entries"] == dd.csr_entries == fill
            assert int(merged) == 0
            assert np.all(np.diff(src[:fill]) >= 0)
            assert not np.any(w[fill:])
            assert np.array_equal(row_ptr[:v],
                                  np.searchsorted(src[:fill], np.arange(v)))
            assert row_ptr[v] == (fill if fast._delta.v_pad > v
                                  else src.size)
            assert np.all(np.diff(row_ptr) >= 0)
            assert row_ptr[-1] == src.size
            want_g = add_edges(want_g, *batch)
            with open_session(want_g, cfg) as rebuilt:
                want = rebuilt.adapt(prev=prev, record_history=False)
            assert np.array_equal(got.labels, want.labels)
            assert got.iterations == want.iterations
            assert np.array_equal(got.loads, want.loads)
            prev = got.labels
        stats = fast.stats()
    assert stats["delta"]["fast_adapts"] == 4
    assert stats["delta"]["pairs_reciprocated"] >= 12
    assert stats["delta"]["arcs_redelivered"] >= 7
    assert stats["score_pass"] == {"transposed": 5, "forward": 0}


def _gathers(jaxpr, size: int, in_forward: bool = False):
    """(inside a cond's forward branch?) for every gather in ``jaxpr``
    whose indices hold ``size`` entries or more."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather" and \
                eqn.invars[1].aval.size >= size:
            found.append(in_forward)
        for name, sub in eqn.params.items():
            subs = sub if isinstance(sub, (tuple, list)) else (sub,)
            for i, j in enumerate(subs):
                j = getattr(j, "jaxpr", j)
                if hasattr(j, "eqns"):
                    forward = in_forward or (eqn.primitive.name == "cond"
                                             and name == "branches"
                                             and i == 0)
                    found += _gathers(j, size, forward)
    return found


def test_batched_program_gathers_only_in_the_forward_branch():
    """Under ``vmap`` the merged count goes in unbatched, so the pass
    stays a branch: no E-sized gather outside the forward branch."""
    cfg = SpinnerConfig(k=8, max_iters=40, seed=2)
    graphs = [generators.watts_strogatz(400 + 4 * i, 8, 0.3, seed=i)
              for i in range(2)]
    items = []
    for g in graphs:
        opts = engine._autotuned(g, cfg, EngineOptions())
        bind, padded = engine._single_bind(g, cfg, opts)
        labels, loads, key = prepare_init(g, cfg, None)
        items.append((engine.init_state(
            engine.pad_labels(labels, padded.num_vertices), loads, key),
            bind))
    e = items[0][1].score[0].shape[0]
    assert e > items[0][0].labels.shape[0]
    run = engine._batched_program(cfg, opts, 2).run
    jaxpr = jax.make_jaxpr(run)(engine.stack_states([s for s, _ in items]),
                                engine.stack_binds([b for _, b in items]))
    found = _gathers(jaxpr.jaxpr, e)
    assert found and all(found), found
