"""Eq. 3 over streamed arcs: every path that adds arcs to a graph gives
the pair weights of ``from_edges`` over the union of all arcs given so far.

Eq. 3 weighs a pair 2 when both of its arcs were given and 1 when one
was; an arc given again changes nothing.  The paths under test are a
chain of ``add_edges`` calls, a session's O(|delta|) fast adapts (the
delta ledger, ``repro.core.delta.DeltaTracker``), the host rebuild they
fall back to, ``coalesce_updates`` and the scheduler's coalesced windows.
Each is compared with ``from_edges`` and with the benchmark's plain
reference (``bench/reference.RefGraph``) over the arc union, on seeded
streams of new arcs, follow-backs (the reverse of a one-way arc) and
repeats, over a Watts-Strogatz and an R-MAT graph.
"""
import os
import sys

import numpy as np
import pytest

from repro.core import (EngineOptions, SpinnerConfig, add_edges,
                        coalesce_updates, from_edges, generators,
                        open_session)
from repro.serve import PartitionScheduler

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench"))
import reference  # noqa: E402


def _rmat_arcs(scale: int, m: int, rng):
    """Directed R-MAT arcs (Graph500 initiator), hub-skewed."""
    n = 1 << scale
    src = np.zeros(m, np.int64)
    dst = np.zeros(m, np.int64)
    for bit in range(scale):
        q = rng.choice(4, size=m, p=(0.57, 0.19, 0.19, 0.05))
        src |= (q >> 1) << bit
        dst |= (q & 1) << bit
    return n, src, dst


def _ws_arcs(n: int, rng):
    g = generators.watts_strogatz(n, 6, 0.3, seed=int(rng.integers(1 << 30)))
    half = g.src < g.dst             # a one-way arc per pair, random side
    u, v = g.src[half].astype(np.int64), g.dst[half].astype(np.int64)
    flip = rng.random(u.size) < 0.5
    return n, np.where(flip, v, u), np.where(flip, u, v)


GRAPHS = {"ws": lambda rng: _ws_arcs(400, rng),
          "rmat": lambda rng: _rmat_arcs(9, 3000, rng)}


def _stream(n, src, dst, rng, batches=4, size=40):
    """Batches of new arcs, follow-backs of one-way arcs present so far,
    repeats of present arcs, in-batch duplicates and a self-loop."""
    present = set(zip(src.tolist(), dst.tolist()))
    out = []
    for _ in range(batches):
        arcs = []
        for _ in range(size // 2):                       # new arcs
            arcs.append((int(rng.integers(n)), int(rng.integers(n))))
        pool = sorted(present)
        pick = rng.choice(len(pool), size=size // 2, replace=False)
        for i in pick[: size // 4]:                      # follow-backs
            a, b = pool[i]
            arcs.append((b, a) if (b, a) not in present else (a, b))
        for i in pick[size // 4:]:                       # redelivered
            arcs.append(pool[i])
        arcs.append(arcs[0])                             # twice in batch
        arcs.append((3, 3))                              # self-loop
        order = rng.permutation(len(arcs))
        bs = np.array([arcs[i][0] for i in order], np.int64)
        bd = np.array([arcs[i][1] for i in order], np.int64)
        present |= set(zip(bs.tolist(), bd.tolist()))
        out.append((bs, bd))
    return out


def _union(src, dst, batches):
    return (np.concatenate([src] + [b[0] for b in batches]),
            np.concatenate([dst] + [b[1] for b in batches]))


def _assert_eq3(graph, n, src, dst):
    """``graph`` is ``from_edges`` of the arc union, and its weighted
    degrees are the plain reference's."""
    want = from_edges(src, dst, n)
    for field in ("src", "dst", "weight", "deg_w", "dirs", "row_ptr"):
        assert np.array_equal(getattr(graph, field), getattr(want, field)), \
            field
    ref = reference.RefGraph(n, src, dst, True)
    assert np.array_equal(graph.deg_w, ref.deg)
    return ref


CASES = [(g, seed) for g in GRAPHS for seed in (11, 12, 13)]


@pytest.fixture(params=CASES, ids=[f"{g}-{s}" for g, s in CASES])
def stream(request):
    name, seed = request.param
    rng = np.random.default_rng(seed)
    n, src, dst = GRAPHS[name](rng)
    return n, src, dst, _stream(n, src, dst, rng)


def test_witness_follow_back_weighs_two():
    """A follow-back after a one-way arc makes the pair weigh 2; the
    same arc given twice leaves it at 1."""
    back = add_edges(from_edges([5], [2], 6), [2], [5])
    assert back.deg_w[[2, 5]].tolist() == [2.0, 2.0]
    assert np.array_equal(back.weight, from_edges([5, 2], [2, 5], 6).weight)
    again = add_edges(from_edges([5], [2], 6), [5], [2])
    assert again.deg_w[[2, 5]].tolist() == [1.0, 1.0]
    assert again.dirs.tolist() == [2]             # only hi -> lo given


def test_from_edges_records_the_given_arcs():
    g = from_edges([0, 2, 1, 3, 3, 1], [1, 0, 0, 1, 1, 2], 4)
    half = g.src < g.dst                          # one mask per pair
    pairs = {(int(a), int(b)): (float(w), int(m)) for a, b, w, m in
             zip(g.src[half], g.dst[half], g.weight[half], g.dirs)}
    assert pairs == {(0, 1): (2.0, 3),            # both arcs
                     (0, 2): (1.0, 2),            # 2 -> 0: hi -> lo
                     (1, 2): (1.0, 1),            # 1 -> 2: lo -> hi
                     (1, 3): (1.0, 2)}            # 3 -> 1, given twice
    und = from_edges([2], [0], 3, directed=False)
    assert und.weight.tolist() == [1.0, 1.0] and und.dirs.tolist() == [1]
    # removing a vertex keeps the masks of the pairs that stay
    from repro.core.graph import remove_vertices
    rest = remove_vertices(g, [0])
    assert rest.dirs.tolist() == [1, 2]


def test_add_edges_chain_equals_the_union(stream):
    n, src, dst, batches = stream
    g = from_edges(src, dst, n)
    for i, b in enumerate(batches):
        g = add_edges(g, *b)
        _assert_eq3(g, n, *_union(src, dst, batches[: i + 1]))


def test_fast_adapts_equal_the_union(stream):
    """Each fast adapt reports loads that the reference recounts from its
    labels over the arc union; the materialized graph is the union's."""
    n, src, dst, batches = stream
    cfg = SpinnerConfig(k=4, max_iters=61, seed=2)
    s = open_session(from_edges(src, dst, n), cfg,
                     EngineOptions(engine="fused"))
    s.partition(record_history=False)
    for i, b in enumerate(batches):
        res = s.adapt(edge_updates=b, record_history=False)
        ref = reference.RefGraph(n, *_union(src, dst, batches[: i + 1]),
                                 True)
        assert np.array_equal(res.loads[:4],
                              reference.loads(ref.deg, res.labels, 4))
        assert s.stats()["delta"]["tracked_total_weight"] == \
            ref.total_weight
    st = s.stats()["delta"]
    assert st["fast_adapts"] == len(batches) and st["fallback_adapts"] == 0
    _assert_eq3(s.graph, n, *_union(src, dst, batches))
    assert st["arcs_given"] == sum(b[0].size for b in batches)


def test_coalesced_batch_then_one_adapt_equals_the_union(stream):
    n, src, dst, batches = stream
    cfg = SpinnerConfig(k=4, max_iters=62, seed=3)
    s = open_session(from_edges(src, dst, n), cfg,
                     EngineOptions(engine="fused"))
    s.partition(record_history=False)
    res = s.adapt(edge_updates=coalesce_updates(batches),
                  record_history=False)
    assert s.stats()["delta"]["fast_adapts"] == 1
    ref = _assert_eq3(s.graph, n, *_union(src, dst, batches))
    assert np.array_equal(res.loads[:4],
                          reference.loads(ref.deg, res.labels, 4))
    # the plain concatenation folds to the same weights
    g = add_edges(from_edges(src, dst, n),
                  np.concatenate([b[0] for b in batches]),
                  np.concatenate([b[1] for b in batches]))
    _assert_eq3(g, n, *_union(src, dst, batches))


def test_scheduler_windows_equal_the_union(stream):
    """Queued edge updates coalesce into windows of two; the tenant's
    graph after every window is the union's."""
    n, src, dst, batches = stream
    cfg = SpinnerConfig(k=4, max_iters=63, seed=4)
    sched = PartitionScheduler()
    sched.add_tenant("t", from_edges(src, dst, n), cfg, partition=True)
    for i in range(0, len(batches), 2):
        tickets = [sched.submit("t", "edge_updates", edge_updates=b)
                   for b in batches[i:i + 2]]
        sched.drain()
        assert all(t.done and not t.failed for t in tickets)
        res = tickets[-1].result
        session = sched.tenants["t"].session
        ref = _assert_eq3(session.graph, n,
                          *_union(src, dst, batches[: i + 2]))
        assert np.array_equal(res.loads[:4],
                              reference.loads(ref.deg, res.labels, 4))
    assert sched.stats()["errors"] == 0


def test_fallback_rebuild_gives_the_fast_labels(stream):
    """At every step of the stream the fast adapt's labels are
    bit-identical to the session's fallback, which rebuilds the host
    graph with ``add_edges`` and restarts from the same labels."""
    n, src, dst, batches = stream
    cfg = SpinnerConfig(k=4, max_iters=64, seed=5)
    base = from_edges(src, dst, n)
    fast = open_session(base, cfg, EngineOptions(engine="fused"))
    slow = open_session(base, cfg, EngineOptions(engine="fused"))
    slow._fast_mode = lambda *a: None            # every adapt rebuilds
    r_fast = fast.partition(record_history=False)
    r_slow = slow.partition(record_history=False)
    assert np.array_equal(r_fast.labels, r_slow.labels)
    for b in batches:
        r_fast = fast.adapt(edge_updates=b, record_history=False)
        r_slow = slow.adapt(edge_updates=b, record_history=False)
        assert np.array_equal(r_fast.labels, r_slow.labels)
        assert np.array_equal(r_fast.loads, r_slow.loads)
        assert r_fast.iterations == r_slow.iterations
    assert fast.stats()["delta"]["fallback_adapts"] == 0
    assert slow.stats()["delta"]["fallback_adapts"] == len(batches)
    assert np.array_equal(fast.graph.weight, slow.graph.weight)
