"""Spans and device scopes of the program (``repro.core.trace``).

* the ring: nesting, parent and call ids, ``self_ns``, its bound, and
  spans opened on two threads;
* the session's spans: a partition's five phases, ``compiles`` per call,
  the delta fast path's phases and the fallback's host rebuild;
* the device scopes in the fused program's lowered text, so a refactor
  cannot drop them silently.

Each test that runs the engine uses a unique ``max_iters`` so that its
programs are private in the global program cache and its compile counts
cannot be perturbed by other tests.
"""
import threading

import numpy as np
import pytest

from repro.core import (SpinnerConfig, engine, generators, open_session,
                        spinner, trace)

PHASES = ["session/prepare", "session/bind", "session/dispatch",
          "session/wait", "session/fetch"]


@pytest.fixture(autouse=True)
def empty_ring():
    trace.clear()
    yield
    trace.clear()


@pytest.fixture(scope="module")
def ws_graph():
    return generators.watts_strogatz(400, 6, 0.2, seed=21)


def _names(spans):
    return [s.name for s in spans]


class TestRing:
    def test_nesting_links_parent_and_call(self):
        with trace.span("outer", a=1) as attrs:
            with trace.span("inner"):
                pass
            with trace.span("inner"):
                with trace.span("leaf"):
                    pass
            attrs["b"] = 2
        with trace.span("next"):
            pass
        outer, in1, in2, leaf, nxt = trace.spans()
        assert _names(trace.spans()) == ["outer", "inner", "inner", "leaf",
                                         "next"]
        assert outer.parent_id is None and outer.attrs == {"a": 1, "b": 2}
        assert in1.parent_id == in2.parent_id == outer.span_id
        assert leaf.parent_id == in2.span_id
        assert {s.call_id for s in (outer, in1, in2, leaf)} == \
            {outer.span_id}
        assert nxt.parent_id is None and nxt.call_id == nxt.span_id
        assert trace.children(outer) == [in1, in2]
        assert _names(trace.spans("inner")) == ["inner", "inner"]
        assert all(s.start_ns <= s.end_ns for s in trace.spans())
        assert outer.start_ns <= in1.start_ns and in2.end_ns <= \
            outer.end_ns

    def test_a_span_that_raises_is_recorded_and_closed(self):
        with pytest.raises(ValueError):
            with trace.span("fails"):
                raise ValueError("x")
        with trace.span("after"):
            pass
        fails, after = trace.spans()
        assert fails.name == "fails" and after.parent_id is None

    def test_self_ns_subtracts_the_children(self):
        parent = trace.Span("p", 0, 100, 1, None, 1, {})
        kids = [trace.Span("c", 10, 30, 2, 1, 1, {}),
                trace.Span("c", 20, 50, 3, 1, 1, {}),     # overlaps
                trace.Span("c", 60, 70, 4, 1, 1, {}),
                trace.Span("g", 62, 65, 5, 4, 1, {})]     # a grandchild
        trace._ring.extend(kids + [parent])
        assert trace.self_ns(parent) == 100 - 40 - 10
        assert trace.self_ns(kids[2]) == 10 - 3
        assert trace.self_ns(kids[3]) == 3

    def test_the_ring_keeps_the_newest(self):
        for i in range(trace.CAPACITY + 10):
            with trace.span("s", i=i):
                pass
        kept = trace.spans()
        assert len(kept) == trace.CAPACITY
        assert kept[0].attrs["i"] == 10
        assert kept[-1].attrs["i"] == trace.CAPACITY + 9

    def test_two_threads_keep_their_own_parents(self):
        barrier = threading.Barrier(2)

        def work(tag):
            with trace.span("call", tag=tag):
                barrier.wait()      # both calls are open at once
                with trace.span("phase", tag=tag):
                    barrier.wait()

        threads = [threading.Thread(target=work, args=(t,))
                   for t in ("a", "b")]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        calls = {s.attrs["tag"]: s for s in trace.spans("call")}
        phases = {s.attrs["tag"]: s for s in trace.spans("phase")}
        for tag in ("a", "b"):
            assert calls[tag].parent_id is None
            assert phases[tag].parent_id == calls[tag].span_id
            assert phases[tag].call_id == calls[tag].span_id
        assert calls["a"].call_id != calls["b"].call_id


class TestSessionSpans:
    def test_partition_records_its_five_phases(self, ws_graph):
        cfg = SpinnerConfig(k=6, seed=3, max_iters=61)
        with open_session(ws_graph, cfg) as s:
            first = s.partition(record_history=False)
            second = s.partition(record_history=False)
        calls = trace.spans("session/partition")
        assert len(calls) == 2
        for call, res in zip(calls, (first, second)):
            kids = trace.children(call)
            assert _names(kids) == PHASES
            assert all(k.call_id == call.span_id for k in kids)
            assert call.attrs["iterations"] == res.iterations
            assert call.attrs["halted"] == res.halted
            assert call.attrs["engine"] == "fused"
            assert trace.self_ns(call) >= 0
        assert calls[0].attrs["compiles"] >= 1
        assert calls[1].attrs["compiles"] == 0    # same bucket: warm

    @pytest.mark.parametrize("eng", ["chunked", "host"])
    def test_every_engine_records_the_phases(self, ws_graph, eng):
        cfg = SpinnerConfig(k=6, seed=3, max_iters=62)
        res = spinner.partition(ws_graph, cfg, engine=eng,
                                record_history=True)
        (call,) = trace.spans("session/partition")
        assert _names(trace.children(call)) == PHASES
        assert call.attrs["engine"] == eng
        assert call.attrs["iterations"] == res.iterations

    def test_fast_adapt_records_the_delta_phases(self, ws_graph):
        cfg = SpinnerConfig(k=6, seed=4, max_iters=63)
        rng = np.random.default_rng(0)
        v = ws_graph.num_vertices
        with open_session(ws_graph, cfg) as s:
            s.partition(record_history=False)
            trace.clear()
            s.adapt(edge_updates=(rng.integers(0, v, 8),
                                  rng.integers(0, v, 8)),
                    record_history=False)
            stats = s.stats()["delta"]
        (call,) = trace.spans("session/adapt")
        assert call.attrs["fast"] is True
        assert call.attrs["upload_bytes"] == stats["last_upload_bytes"] > 0
        names = _names(trace.children(call))
        assert names == ["session/bind", "delta/plan", "delta/upload",
                         "delta/merge", "session/prepare",
                         "session/dispatch", "session/wait",
                         "session/fetch"]
        (upload,) = trace.spans("delta/upload")
        assert upload.attrs["bytes"] == stats["last_upload_bytes"]
        assert "session/rebuild" not in _names(trace.spans())

    def test_fallback_adapt_records_the_rebuild(self, ws_graph):
        cfg = SpinnerConfig(k=6, seed=4, max_iters=64)
        v = ws_graph.num_vertices
        with open_session(ws_graph, cfg) as s:
            s.partition(record_history=False)
            trace.clear()
            res = s.adapt(edge_updates=(np.array([0, 1]),
                                        np.array([v, v + 1])),
                          num_vertices=v + 2, record_history=False)
            entries = s.stats()["num_directed_entries"]
        (call,) = trace.spans("session/adapt")
        assert call.attrs["fast"] is False
        assert call.attrs["upload_bytes"] == 0
        assert call.attrs["iterations"] == res.iterations
        kids = trace.children(call)
        assert _names(kids) == ["session/rebuild"] + PHASES
        (build,) = trace.children(kids[0])
        assert build.name == "graph/from_edges"
        assert build.attrs == {"vertices": v + 2, "entries": entries}
        assert "delta/merge" not in _names(trace.spans())


def test_from_edges_records_its_size():
    from repro.core import from_edges
    g = from_edges([0, 1, 2, 2], [1, 2, 0, 0], 5)
    (build,) = trace.spans("graph/from_edges")
    assert build.attrs == {"vertices": 5,
                           "entries": g.num_directed_entries}
    assert build.parent_id is None


def test_fused_program_names_its_device_scopes(ws_graph):
    cfg = SpinnerConfig(k=6, seed=5, max_iters=65)
    opts = engine._autotuned(ws_graph, cfg, engine.EngineOptions())
    bind, padded = engine._single_bind(ws_graph, cfg, opts)
    labels, loads, key = spinner.prepare_init(ws_graph, cfg, None)
    state = engine.init_state(
        engine.pad_labels(labels, padded.num_vertices), loads, key)
    text = engine._fused_program(cfg, opts).run.lower(
        state, bind).as_text(debug_info=True)
    for scope in ("lpa/gather", "lpa/scatter", "lpa/propose",
                  "lpa/migrate", "lpa/noise", "lpa/halt"):
        assert scope in text, scope


def test_merge_program_names_its_device_scope():
    import jax.numpy as jnp
    a = jnp.zeros(8, jnp.float32)
    idx = jnp.array([1, 9], jnp.int32)
    text = engine._merge_program().run.lower(
        (((a,), idx, (jnp.ones(2, jnp.float32),)),), ()).as_text(
            debug_info=True)
    assert "delta/merge" in text
