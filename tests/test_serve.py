"""Serving tier: batched same-bucket execution, delta coalescing,
scheduler parity, program sharing, session lifecycle.

The load-bearing claims (ISSUE 8 acceptance):

* every label set produced under the scheduler is bit-identical to
  serial per-session execution for the tested interleavings -- coalesced
  vs one-by-one deltas, batch-of-1 vs the unbatched program -- across
  engines x exchange plans on 1 and (via subprocesses) 8 forced host
  devices;
* two sessions in one (V, E, k) bucket share compiled programs: zero
  new compiles for the second tenant, unbatched AND via the batched
  runner;
* ``close()`` is idempotent and every closed-session entry point raises
  the same RuntimeError.

Each test uses a unique ``max_iters`` so its programs are private to it
(compile counters can't be perturbed by other tests).
"""
import dataclasses

import numpy as np
import pytest

from repro.core import (EngineOptions, SpinnerConfig, generators,
                        open_session)
from repro.core import delta as _delta
from repro.core import engine as _engine
from repro.core.graph import add_edges
from repro.core.spinner import prepare_init
from repro.serve import (KSweepPrecompile, PartitionScheduler,
                         StagePrefetch, Ticket, traffic)

from test_distributed import run_devices_subprocess


def _graph(v, seed):
    return generators.watts_strogatz(v, 8, 0.1, seed=seed)


def _delta_batch(rng, v, n=12):
    src = rng.integers(0, v, n)
    dst = rng.integers(0, v, n)
    m = src != dst
    return src[m], dst[m]


def _assert_same(a, b, what=""):
    assert np.array_equal(a.labels, b.labels), what
    assert a.iterations == b.iterations, what
    assert a.halted == b.halted, what
    assert np.array_equal(a.loads, b.loads), what


def _parts_for(graph, cfg, seed_cfg=None):
    """An (init_state, bind) work item the way run_fused would build it."""
    c = cfg if seed_cfg is None else seed_cfg
    labels, loads, key = prepare_init(graph, c, None)
    opts_t = _engine._autotuned(graph, c, _engine._DEFAULT_OPTS)
    bind, padded = _engine._single_bind(graph, c, opts_t)
    state = _engine.init_state(
        _engine.pad_labels(labels, padded.num_vertices), loads, key)
    return state, bind, opts_t


# ---------------------------------------------------------------------------
# engine.run_batched: the vmap'd same-bucket executor
# ---------------------------------------------------------------------------

class TestBatchedRunner:
    def test_batched_matches_unbatched_per_element(self):
        """3 same-bucket graphs (padded to a batch of 4): every element's
        final state is bit-identical to its own unbatched fused run."""
        cfg = SpinnerConfig(k=8, max_iters=141, seed=3)
        graphs = [_graph(490 + 5 * i, seed=i) for i in range(3)]
        assert len({_engine.graph_buckets(g) for g in graphs}) == 1
        items, refs, opts_t = [], [], None
        for i, g in enumerate(graphs):
            c = dataclasses.replace(cfg, seed=10 + i)
            state, bind, opts_t = _parts_for(g, cfg, c)
            items.append((state, bind))
            labels, loads, key = prepare_init(g, c, None)
            refs.append(_engine.run_fused(g, c, labels, loads, key,
                                          opts=_engine._DEFAULT_OPTS))
        outs = _engine.run_batched(items, cfg, opts_t)
        sigs = {_engine.batch_signature(cfg, opts_t, b) for _, b in items}
        assert len(sigs) == 1
        for g, out, ref in zip(graphs, outs, refs):
            v = g.num_vertices
            assert np.array_equal(np.asarray(out.labels)[:v],
                                  np.asarray(ref.labels))
            assert int(out.iteration) == int(ref.iteration)
            assert bool(out.halted) == bool(ref.halted)
            assert float(out.score) == float(ref.score)
            assert np.array_equal(np.asarray(out.loads),
                                  np.asarray(ref.loads))

    def test_batch_of_one_bit_identical(self):
        cfg = SpinnerConfig(k=6, max_iters=142, seed=1)
        g = _graph(430, seed=4)
        state, bind, opts_t = _parts_for(g, cfg)
        labels, loads, key = prepare_init(g, cfg, None)
        ref = _engine.run_fused(g, cfg, labels, loads, key,
                                opts=_engine._DEFAULT_OPTS)
        (out,) = _engine.run_batched([(state, bind)], cfg, opts_t)
        v = g.num_vertices
        assert np.array_equal(np.asarray(out.labels)[:v],
                              np.asarray(ref.labels))
        assert int(out.iteration) == int(ref.iteration)
        assert float(out.score) == float(ref.score)

    def test_batch_bucket(self):
        assert [_engine.batch_bucket(n) for n in (1, 2, 3, 4, 5, 8, 9)] \
            == [1, 2, 4, 4, 8, 8, 16]


# ---------------------------------------------------------------------------
# session scheduler entry points
# ---------------------------------------------------------------------------

class TestAdaptParts:
    def test_stream_matches_adapt(self, rng):
        """adapt_parts -> run_batched -> commit_adapt walks the same
        stream as adapt(): fast-path deltas, then an argless re-run."""
        cfg = SpinnerConfig(k=8, max_iters=143, seed=5)
        g = _graph(400, seed=0)
        stream = [_delta_batch(rng, 400), _delta_batch(rng, 400), None]
        ref = open_session(g, cfg)
        ref.partition(record_history=False)
        s = open_session(g, cfg)
        s.partition(record_history=False)
        for d in stream:
            r_ref = ref.adapt(edge_updates=d, record_history=False) \
                if d is not None else ref.adapt(record_history=False)
            state, bind, c, opts_t = s.adapt_parts(edge_updates=d)
            # merged arrays stay in CSR order: the lane takes the
            # transposed branch of the score pass
            assert int(bind.score[4]) == 0
            (out,) = _engine.run_batched([(state, bind)], c, opts_t)
            _assert_same(s.commit_adapt(out), r_ref, f"delta {d is None}")
        assert s.stats()["delta"]["fast_adapts"] == 2
        assert np.array_equal(s.labels, ref.labels)

    def test_batchable_eligibility(self):
        cfg = SpinnerConfig(k=4, max_iters=144, seed=0)
        g = _graph(300, seed=1)
        assert open_session(g, cfg).batchable()
        for opts in (EngineOptions(engine="chunked"),
                     EngineOptions(engine="host"),
                     EngineOptions(engine="sharded"),
                     EngineOptions(score_backend="pallas")):
            s = open_session(g, cfg, opts)
            assert not s.batchable(), opts
            assert s.adapt_parts() is None, opts

    def test_batch_key_same_bucket(self):
        cfg = SpinnerConfig(k=4, max_iters=144, seed=0)
        assert open_session(_graph(300, seed=1), cfg).batch_key() \
            == open_session(_graph(310, seed=2), cfg).batch_key()
        assert open_session(_graph(300, seed=1), cfg).batch_key() \
            != open_session(_graph(900, seed=2), cfg).batch_key()


# ---------------------------------------------------------------------------
# delta coalescing
# ---------------------------------------------------------------------------

class TestCoalescing:
    def test_coalesce_updates_concat_and_dedupe(self):
        b1 = (np.array([0, 1]), np.array([2, 3]))
        b2 = (np.array([0, 4]), np.array([2, 5]))   # (0->2) repeats
        src, dst = _delta.coalesce_updates([b1, b2])
        assert list(zip(src, dst)) == [(0, 2), (1, 3), (4, 5)]
        src, dst = _delta.coalesce_updates([])
        assert src.size == 0 and dst.size == 0

    def test_coalesce_updates_direction_canonicalization(self):
        """Eq. 3 counts each pair's given arcs, so the coalesced batch is
        the union of the arcs: an arc repeated across batches appears
        once, and the two directions of a pair stay two arcs (weight 2)
        in whichever order they came."""
        rev = (np.array([2]), np.array([0]))        # hi -> lo
        can = (np.array([0]), np.array([2]))        # lo -> hi
        # the same arc twice across batches: one arc, w stays 1
        src, dst = _delta.coalesce_updates([rev, rev])
        assert list(zip(src, dst)) == [(2, 0)]
        # a follow-back in either order: both arcs, w=2
        src, dst = _delta.coalesce_updates([rev, can])
        assert sorted(zip(src, dst)) == [(0, 2), (2, 0)]
        src, dst = _delta.coalesce_updates([can, rev])
        assert sorted(zip(src, dst)) == [(0, 2), (2, 0)]
        # canonical repeated: idempotent
        src, dst = _delta.coalesce_updates([can, can])
        assert list(zip(src, dst)) == [(0, 2)]
        # both directions in ONE batch: w=2 from the start
        both = (np.array([0, 2]), np.array([2, 0]))
        src, dst = _delta.coalesce_updates([both])
        assert sorted(zip(src, dst)) == [(0, 2), (2, 0)]
        # self-loops never count
        src, dst = _delta.coalesce_updates([(np.array([3]), np.array([3]))])
        assert src.size == 0

    def test_coalesced_equals_one_by_one(self, rng):
        """One concatenated apply_delta plan == N sequential plans (the
        union weight semantics), down to bit-identical labels -- and both
        equal the host-rebuild oracle."""
        cfg = SpinnerConfig(k=8, max_iters=145, seed=2)
        g = _graph(420, seed=3)
        b1, b2 = _delta_batch(rng, 420), _delta_batch(rng, 420)
        # b3 overlaps b1: the dedupe path must stay exact
        b3 = (np.concatenate([b1[0][:3], _delta_batch(rng, 420, 6)[0]]),
              np.concatenate([b1[1][:3], _delta_batch(rng, 420, 6)[1]]))

        one_by_one = open_session(g, cfg)
        one_by_one.partition(record_history=False)
        one_by_one.update(*b1).update(*b2)
        r_seq = one_by_one.adapt(edge_updates=b3, record_history=False)
        assert one_by_one.stats()["delta"]["fast_adapts"] == 1

        coalesced = open_session(g, cfg)
        coalesced.partition(record_history=False)
        r_coal = coalesced.adapt(
            edge_updates=_delta.coalesce_updates([b1, b2, b3]),
            record_history=False)
        _assert_same(r_seq, r_coal, "coalesced vs one-by-one")

        oracle = open_session(g, cfg)
        oracle.partition(record_history=False)
        g2 = add_edges(add_edges(add_edges(g, *b1), *b2), *b3)
        _assert_same(oracle.adapt(new_graph=g2, record_history=False),
                     r_coal, "coalesced vs rebuild oracle")


# ---------------------------------------------------------------------------
# the scheduler
# ---------------------------------------------------------------------------

class TestScheduler:
    def test_window_coalescing_matches_serial(self, rng):
        """A queued [eu, eu, adapt] window dispatches once; all three
        tickets resolve to the result of update;update;adapt replayed
        serially on a twin session."""
        cfg = SpinnerConfig(k=8, max_iters=146, seed=4)
        g = _graph(410, seed=5)
        b1, b2 = _delta_batch(rng, 410), _delta_batch(rng, 410)
        sched = PartitionScheduler()
        sched.add_tenant("a", g, cfg, partition=True)
        t1 = sched.submit("a", "edge_updates", edge_updates=b1)
        t2 = sched.submit("a", "edge_updates", edge_updates=b2)
        t3 = sched.submit("a", "adapt")
        assert sched.drain() == 3
        assert t1.result is t2.result is t3.result
        assert t3.coalesced == 3 and t3.done and not t3.failed
        assert sched.stats()["coalescing_factor"] == 2.0

        twin = open_session(g, cfg)
        twin.partition(record_history=False)
        twin.update(*b1).update(*b2)
        _assert_same(t3.result, twin.adapt(record_history=False))

    def test_mixed_fleet_parity_engines_and_plans(self, rng):
        """Batched fused tenants + sharded tenants on both exchange
        plans + a chunked tenant, all in one fleet, over two rounds of
        edge updates: every ticket's labels are bit-identical to direct
        session calls (1 device).  The batched lanes' second round scores
        the edge lists their first merge re-sorted into CSR order."""
        from repro.launch.mesh import make_partition_mesh
        cfg = SpinnerConfig(k=4, max_iters=147, seed=6)
        mesh = make_partition_mesh(1)
        fleet = {
            "f1": (_graph(400, seed=1), None),
            "f2": (_graph(405, seed=2), None),   # same bucket as f1
            "sh_ag": (_graph(600, seed=3),
                      EngineOptions(engine="sharded", mesh=mesh,
                                    label_exchange="allgather")),
            "sh_dl": (_graph(600, seed=4),
                      EngineOptions(engine="sharded", mesh=mesh,
                                    label_exchange="delta")),
            "ch": (_graph(500, seed=5), EngineOptions(engine="chunked")),
        }
        rounds = [{n: _delta_batch(rng, g.num_vertices)
                   for n, (g, _) in fleet.items()} for _ in range(2)]
        sched = PartitionScheduler(max_batch=8, batch_min=2)
        for n, (g, opts) in fleet.items():
            sched.add_tenant(n, g, cfg, opts, partition=True)
        tks = []
        for r, deltas in enumerate(rounds, 1):
            tks.append({n: sched.submit(n, "edge_updates",
                                        edge_updates=deltas[n])
                        for n in fleet})
            assert sched.drain() == len(fleet)
            st = sched.stats()
            assert st["errors"] == 0, st
            assert st["batched_dispatches"] == r      # f1 + f2 stacked
            assert st["serial_dispatches"] == 3 * r   # sharded x2 + chunked
        for n, (g, opts) in fleet.items():
            twin = open_session(g, cfg, opts)
            twin.partition(record_history=False)
            for r, deltas in enumerate(rounds):
                ref = twin.adapt(edge_updates=deltas[n],
                                 record_history=False)
                _assert_same(tks[r][n].result, ref, (n, r))
            if opts is None:        # the batched lanes merged on device
                lane = sched.tenants[n].session.stats()["delta"]
                assert lane["fast_adapts"] == 2, (n, lane)

    def test_batch_min_one_forces_batched_path(self, rng):
        """batch_min=1 routes even a lone window through run_batched --
        the batch-of-1 path -- with unchanged results."""
        cfg = SpinnerConfig(k=6, max_iters=148, seed=7)
        g = _graph(440, seed=6)
        d = _delta_batch(rng, 440)
        sched = PartitionScheduler(batch_min=1)
        sched.add_tenant("a", g, cfg, partition=True)
        tk = sched.submit("a", "edge_updates", edge_updates=d)
        assert sched.drain() == 1
        assert sched.stats()["batched_dispatches"] == 1
        twin = open_session(g, cfg)
        twin.partition(record_history=False)
        _assert_same(tk.result,
                     twin.adapt(edge_updates=d, record_history=False))

    def test_priority_and_staleness_order(self):
        clock = {"t": 0.0}
        cfg = SpinnerConfig(k=4, max_iters=149, seed=8)
        sched = PartitionScheduler(max_batch=1, policies=(),
                                   clock=lambda: clock["t"])
        sched.add_tenant("lo", _graph(300, seed=1), cfg, priority=1.0,
                         partition=True)
        sched.add_tenant("hi", _graph(300, seed=2), cfg, priority=5.0,
                         partition=True)
        t_lo = sched.submit("lo", "adapt")
        clock["t"] = 1.0
        t_hi = sched.submit("hi", "adapt")
        clock["t"] = 2.0
        sched.step()   # urgency: hi 5*1 > lo 1*2
        assert t_hi.done and not t_lo.done
        sched.step()
        assert t_lo.done

    def test_preempt_staleness_overrides_priority(self):
        clock = {"t": 0.0}
        cfg = SpinnerConfig(k=4, max_iters=149, seed=9)
        sched = PartitionScheduler(max_batch=1, policies=(),
                                   preempt_staleness=10.0,
                                   clock=lambda: clock["t"])
        sched.add_tenant("lo", _graph(300, seed=3), cfg, priority=1.0,
                         partition=True)
        sched.add_tenant("hi", _graph(300, seed=4), cfg, priority=100.0,
                         partition=True)
        t_lo = sched.submit("lo", "adapt")
        clock["t"] = 11.0
        t_hi = sched.submit("hi", "adapt")
        sched.step()   # lo is past the SLO: jumps the priority queue
        assert t_lo.done and not t_hi.done

    def test_resize_and_errors(self, rng):
        cfg = SpinnerConfig(k=4, max_iters=151, seed=1)
        g = _graph(350, seed=7)
        sched = PartitionScheduler(policies=())
        sched.add_tenant("a", g, cfg, partition=True)
        tk = sched.submit("a", "resize", k=6)
        bad = sched.submit("a", "edge_updates",
                           edge_updates=(np.array([999999]),
                                         np.array([0])))
        sched.drain()
        twin = open_session(g, cfg)
        twin.partition(record_history=False)
        _assert_same(tk.result, twin.resize(6, record_history=False))
        assert bad.failed and isinstance(bad.error, ValueError)
        ok = sched.submit("a", "adapt")      # errors don't wedge the queue
        sched.drain()
        assert ok.done and not ok.failed
        _assert_same(ok.result, twin.adapt(record_history=False))

    def test_remove_tenant_fails_queued_and_is_final(self):
        cfg = SpinnerConfig(k=4, max_iters=152, seed=2)
        sched = PartitionScheduler()
        t = sched.add_tenant("a", _graph(300, seed=8), cfg,
                             partition=True)
        tk = sched.submit("a", "adapt")
        sched.remove_tenant("a")
        assert tk.failed and "retired" in str(tk.error)
        t.session.close()          # double close via scheduler + here: ok
        with pytest.raises(KeyError):
            sched.remove_tenant("a")
        with pytest.raises(KeyError):
            sched.submit("a", "adapt")


# ---------------------------------------------------------------------------
# prefetch policies
# ---------------------------------------------------------------------------

class TestPolicies:
    def test_ksweep_precompile_warms_resize(self, rng):
        cfg = SpinnerConfig(k=4, max_iters=153, seed=3)
        g = _graph(380, seed=9)
        pol = KSweepPrecompile()
        sched = PartitionScheduler(max_batch=1, policies=(pol,))
        sched.add_tenant("a", g, cfg, partition=True)
        sched.add_tenant("b", _graph(380, seed=10), cfg, partition=True)
        sched.submit("a", "edge_updates",
                     edge_updates=_delta_batch(rng, 380))
        tk = sched.submit("b", "resize", k=7)
        sched.step()    # dispatches a; warms b's k=7 program off-path
        assert pol.compiled >= 1 and ("b", 7) in pol.warmed
        prog = _engine._fused_program(
            dataclasses.replace(cfg, k=7),
            _engine._autotuned(g, dataclasses.replace(cfg, k=7),
                               _engine._DEFAULT_OPTS))
        before = prog.compiles()
        sched.drain()
        assert prog.compiles() == before   # resize dispatch: no compile
        twin = open_session(_graph(380, seed=10), cfg)
        twin.partition(record_history=False)
        _assert_same(tk.result, twin.resize(7, record_history=False))

    def test_stage_prefetch_stages_next_rebind(self, rng):
        cfg = SpinnerConfig(k=4, max_iters=154, seed=4)
        g = _graph(360, seed=11)
        g2 = add_edges(g, *_delta_batch(rng, 360, 30))
        pol = StagePrefetch()
        sched = PartitionScheduler(max_batch=1, policies=(pol,))
        sched.add_tenant("a", _graph(360, seed=12), cfg, partition=True)
        sched.add_tenant("b", g, cfg, partition=True)
        sched.submit("a", "adapt")
        tk = sched.submit("b", "adapt", new_graph=g2)
        sched.step()    # dispatches a; stages b's snapshot off-path
        assert pol.staged == 1
        assert sched.tenants["b"].session.stats()["staged"] is not None
        sched.drain()
        twin = open_session(g, cfg)
        twin.partition(record_history=False)
        _assert_same(tk.result,
                     twin.adapt(new_graph=g2, record_history=False))


# ---------------------------------------------------------------------------
# cross-tenant program sharing (satellite: zero compiles for tenant #2)
# ---------------------------------------------------------------------------

class TestProgramSharing:
    def test_second_session_zero_compiles_unbatched(self):
        cfg = SpinnerConfig(k=6, max_iters=156, seed=5)
        s1 = open_session(_graph(460, seed=13), cfg)
        s1.partition(record_history=False)
        assert s1.compiles > 0
        s2 = open_session(_graph(465, seed=14), cfg)   # same bucket
        s2.partition(record_history=False)
        assert s2.compiles == 0

    def test_second_fleet_zero_compiles_batched(self, rng):
        """After one fleet warms the batched program, a FRESH scheduler
        with fresh same-bucket sessions serves a batched round with zero
        compiles anywhere (global _PROGRAM_CACHE hit)."""
        cfg = SpinnerConfig(k=6, max_iters=157, seed=6)
        def fleet(sched, seeds):
            for i, s in enumerate(seeds):
                sched.add_tenant(f"t{i}", _graph(450 + i, seed=s), cfg,
                                 partition=True)
            for i in range(len(seeds)):
                sched.submit(f"t{i}", "edge_updates",
                             edge_updates=_delta_batch(rng, 450))
            sched.drain()
        warm = PartitionScheduler(batch_min=2)
        fleet(warm, [20, 21])
        assert warm.stats()["batched_dispatches"] == 1
        assert warm.compiles > 0
        warm.mark()
        # steady state on the same fleet: zero new compiles
        warm.submit("t0", "edge_updates",
                    edge_updates=_delta_batch(rng, 450))
        warm.submit("t1", "edge_updates",
                    edge_updates=_delta_batch(rng, 450))
        warm.drain()
        assert warm.stats()["compiles_since_mark"] == 0
        # a brand-new fleet in the same bucket: zero compiles, period
        fresh = PartitionScheduler(batch_min=2)
        fleet(fresh, [22, 23])
        st = fresh.stats()
        assert st["batched_dispatches"] == 1 and st["errors"] == 0
        assert fresh.compiles == 0


# ---------------------------------------------------------------------------
# closed-session lifecycle (satellite: idempotent close, one message)
# ---------------------------------------------------------------------------

class TestClosedSession:
    def test_close_idempotent_and_uniform_message(self, rng):
        cfg = SpinnerConfig(k=4, max_iters=158, seed=7)
        s = open_session(_graph(320, seed=15), cfg)
        s.partition(record_history=False)
        s.close()
        s.close()                                  # double close: no-op
        from repro.core.session import _CLOSED_MSG
        entry_points = [
            lambda: s.partition(),
            lambda: s.adapt(),
            lambda: s.resize(8),
            lambda: s.update(np.array([0]), np.array([1])),
            lambda: s.stage(edge_updates=(np.array([0]), np.array([1]))),
            lambda: s.stats(),
            lambda: s.batchable(),
            lambda: s.batch_key(),
            lambda: s.adapt_parts(),
            lambda: s.commit_adapt(None),
        ]
        for fn in entry_points:
            with pytest.raises(RuntimeError) as ei:
                fn()
            assert str(ei.value) == _CLOSED_MSG
        with open_session(_graph(320, seed=15), cfg) as ctx:
            ctx.partition(record_history=False)
        ctx.close()                                # after __exit__: no-op


# ---------------------------------------------------------------------------
# synthetic traffic
# ---------------------------------------------------------------------------

class TestTraffic:
    def test_powerlaw_sizes_bounds_and_determinism(self):
        a = traffic.powerlaw_sizes(50, v_min=256, v_max=4096, seed=3)
        b = traffic.powerlaw_sizes(50, v_min=256, v_max=4096, seed=3)
        assert a == b
        assert all(256 <= v <= 4096 for v in a)
        assert min(a) < 1024 < max(a)   # a tail and a head

    def test_poisson_trace_shape(self):
        ev = traffic.poisson_trace({"a": 300, "b": 400}, duration=5.0,
                                   rate=3.0, k_choices=(4, 8), seed=1)
        assert ev == sorted(ev, key=lambda e: (e.t, e.tenant))
        kinds = {e.kind for e in ev}
        assert kinds <= {"edge_updates", "adapt", "resize"}
        assert "edge_updates" in kinds
        for e in ev:
            if e.kind == "edge_updates":
                src, dst = e.payload["edge_updates"]
                hi = {"a": 300, "b": 400}[e.tenant]
                assert src.size and int(max(src.max(), dst.max())) < hi

    def test_open_loop_replay_smoke(self):
        cfg = SpinnerConfig(k=4, max_iters=159, seed=8)
        names = {"a": 300, "b": 310}
        sched = PartitionScheduler(batch_min=2)
        for n, v in names.items():
            sched.add_tenant(n, _graph(v, seed=ord(n[0])), cfg,
                             partition=True)
        ev = traffic.poisson_trace(names, duration=0.3, rate=20.0,
                                   burst_mean=3.0, mix=(0.9, 0.1, 0.0),
                                   seed=2)
        done = traffic.replay(sched, ev)
        st = sched.stats()
        assert done == len(ev) == st["completed"]
        assert st["errors"] == 0
        assert st["coalescing_factor"] >= 1.0
        assert st["latency"]["p50"] >= 0.0


# ---------------------------------------------------------------------------
# 8 forced host devices (subprocess matrix)
# ---------------------------------------------------------------------------

SCHED_BATCHED_NDEV = """
import numpy as np
from repro.core import SpinnerConfig, generators, open_session
from repro.serve import PartitionScheduler

ndev = {ndev}
cfg = SpinnerConfig(k=8, max_iters=161, seed=2)
gs = [generators.watts_strogatz(1500 + 7 * i, 8, 0.1, seed=i)
      for i in range(3)]
rng = np.random.default_rng(0)
def delta(v, n=14):
    s = rng.integers(0, v, n); d = rng.integers(0, v, n); m = s != d
    return s[m], d[m]
deltas = [delta(g.num_vertices) for g in gs]

sched = PartitionScheduler(max_batch=8, batch_min=2)
for i, g in enumerate(gs):
    sched.add_tenant(f"t{{i}}", g, cfg, partition=True)
tks = [sched.submit(f"t{{i}}", "edge_updates", edge_updates=deltas[i])
       for i in range(3)]
sched.drain()
st = sched.stats()
assert st["errors"] == 0, st
assert st["batched_dispatches"] == 1, st
sched.mark()
tks2 = [sched.submit(f"t{{i}}", "edge_updates",
                     edge_updates=delta(gs[i].num_vertices))
        for i in range(3)]
sched.drain()
assert sched.stats()["compiles_since_mark"] == 0, sched.stats()
for i, g in enumerate(gs):
    s = open_session(g, cfg)
    s.partition(record_history=False)
    r = s.adapt(edge_updates=deltas[i], record_history=False)
    assert np.array_equal(tks[i].result.labels, r.labels), i
    assert tks[i].result.iterations == r.iterations, i
    r2 = s.adapt(edge_updates=(tks2[i].payload["edge_updates"]),
                 record_history=False)
    assert np.array_equal(tks2[i].result.labels, r2.labels), i
print("SCHED BATCHED OK", ndev)
"""


@pytest.mark.slow
@pytest.mark.parametrize("ndev", [8])
def test_scheduler_batched_parity_ndev(ndev):
    r = run_devices_subprocess(SCHED_BATCHED_NDEV.format(ndev=ndev),
                               ndev=ndev)
    assert r.returncode == 0, r.stderr[-4000:]
    assert f"SCHED BATCHED OK {ndev}" in r.stdout


SCHED_SHARDED_EXCHANGE_NDEV = """
import numpy as np
from repro.core import (EngineOptions, SpinnerConfig, generators,
                        open_session)
from repro.launch.mesh import make_partition_mesh
from repro.serve import PartitionScheduler

ndev = {ndev}
mesh = make_partition_mesh(ndev)
cfg = SpinnerConfig(k=8, max_iters=162, seed=4)
rng = np.random.default_rng(1)
def delta(v, n=16):
    s = rng.integers(0, v, n); d = rng.integers(0, v, n); m = s != d
    return s[m], d[m]

fleet = {{}}
for plan in ("allgather", "delta"):
    g = generators.watts_strogatz(2000, 8, 0.15, seed=len(fleet))
    opts = EngineOptions(engine="sharded", mesh=mesh, label_exchange=plan)
    fleet[f"sh_{{plan}}"] = (g, opts, delta(g.num_vertices))
g = generators.watts_strogatz(900, 8, 0.15, seed=9)
fleet["fused"] = (g, None, delta(g.num_vertices))

sched = PartitionScheduler(max_batch=8)
tks = {{}}
for name, (g, opts, d) in fleet.items():
    sched.add_tenant(name, g, cfg, opts, partition=True)
    sched.submit(name, "edge_updates", edge_updates=d)
    tks[name] = sched.submit(name, "adapt")     # coalesces into the eu
sched.drain()
st = sched.stats()
assert st["errors"] == 0, st
assert st["serial_dispatches"] >= 2, st       # the sharded tenants
for name, (g, opts, d) in fleet.items():
    twin = open_session(g, cfg, opts)
    twin.partition(record_history=False)
    twin.update(*d)
    ref = twin.adapt(record_history=False)
    assert np.array_equal(tks[name].result.labels, ref.labels), name
    assert tks[name].result.iterations == ref.iterations, name
print("SCHED EXCHANGE OK", ndev)
"""


@pytest.mark.slow
@pytest.mark.parametrize("ndev", [8])
def test_scheduler_sharded_exchange_parity_ndev(ndev):
    r = run_devices_subprocess(SCHED_SHARDED_EXCHANGE_NDEV.format(ndev=ndev),
                               ndev=ndev)
    assert r.returncode == 0, r.stderr[-4000:]
    assert f"SCHED EXCHANGE OK {ndev}" in r.stdout
