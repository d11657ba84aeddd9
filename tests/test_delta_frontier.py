"""Delta-proportional adapt (PR 7): on-device CSR delta merge +
dirty-frontier LPA reconvergence.

Three claims under test (see repro.core.delta / session module docs):

  1. DATA PATH -- a warm ``adapt(edge_updates=...)`` whose batch fits the
     bucketed layout's slack performs ZERO new compiles, no host O(E)
     CSR rebuild and no full-graph re-upload, and is bit-identical to
     the classic ``add_edges`` + re-adapt oracle (integer Eq. 3 weights
     make the appended-slot layout score-exact).
  2. FALLBACK -- a batch overflowing the slack, a grown vertex set, or an
     ineligible configuration falls back to the rebuild path,
     bit-identically, and is counted in ``stats()["delta"]``.
  3. COMPUTE PATH -- ``adapt(..., frontier=True)`` on a converged base
     scores a strictly sub-linear fraction of vertices (reported per
     iteration via ``PartitionResult.scored_per_iter``) and lands on
     labels bit-identical to the full re-adapt oracle, for every
     engine x exchange plan x score backend in the matrix below.

CI split (like tests/test_overlap.py): tests named ``*pallas*`` /
``*exchange*`` run in the pallas-sharded job, the rest in the
multidevice job; the sharded matrices run on 2/4/8 forced host devices
via subprocesses, single-device in-process.
"""
import numpy as np
import pytest

from repro.core import (EngineOptions, SpinnerConfig, add_edges, delta,
                        extend_labels, from_edges, open_session,
                        shape_bucket)
from repro.core.generators import clustered_graph

from test_distributed import run_devices_subprocess


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def base_graph():
    """A random directed-edge graph (mixed w=1/w=2 Eq. 3 weights)."""
    rng = np.random.default_rng(0)
    V, E = 600, 2400
    return from_edges(rng.integers(0, V, E), rng.integers(0, V, E),
                      num_vertices=V)


@pytest.fixture(scope="module")
def fixed_point_graph():
    """Planted communities: LPA reaches a TRUE fixed point (re-adapt
    moves nothing), which is what frontier-parity needs -- on a graph
    that never quiesces the frontier legitimately never drains."""
    return clustered_graph(4, 150, p_in=0.2, p_out_edges_per_v=0.05,
                           seed=2)


def _converged(g, cfg, opts):
    """(session, fixed-point labels): partition, then one adapt to land
    exactly on the fixed point (asserted -- the parity claim is vacuous
    otherwise)."""
    s = open_session(g, cfg, opts)
    s.partition()
    r1 = s.adapt()
    r2 = s.adapt()
    assert np.array_equal(r1.labels, r2.labels), \
        "fixture regression: base labeling is not an LPA fixed point"
    return s, r2


# ---------------------------------------------------------------------------
# satellite: input validation (session.update / adapt / stage)
# ---------------------------------------------------------------------------

class TestEdgeUpdateValidation:
    CFG = SpinnerConfig(k=3, max_iters=7, seed=1)

    def _session(self, base_graph):
        return open_session(base_graph, self.CFG, EngineOptions())

    def test_mismatched_lengths(self, base_graph):
        s = self._session(base_graph)
        with pytest.raises(ValueError, match="length"):
            s.update([1, 2, 3], [4, 5])

    def test_negative_ids(self, base_graph):
        s = self._session(base_graph)
        with pytest.raises(ValueError, match="negative"):
            s.update([1, -2], [3, 4])

    def test_out_of_range_ids(self, base_graph):
        s = self._session(base_graph)
        V = base_graph.num_vertices
        with pytest.raises(ValueError, match="vertices"):
            s.update([1, V], [3, 4])
        # ...but in-range for a GROWN vertex set is fine
        s.update([1, V], [3, 4], num_vertices=V + 1)
        assert s.graph.num_vertices == V + 1

    def test_non_integer_dtype(self, base_graph):
        s = self._session(base_graph)
        with pytest.raises(ValueError, match="integer"):
            s.update(np.array([1.5, 2.0]), np.array([3, 4]))

    def test_non_1d(self, base_graph):
        s = self._session(base_graph)
        with pytest.raises(ValueError, match="1-D"):
            s.update(np.zeros((2, 2), np.int32), np.zeros((2, 2), np.int32))

    def test_adapt_and_stage_validate_too(self, base_graph):
        s = self._session(base_graph)
        s.partition()
        with pytest.raises(ValueError, match="negative"):
            s.adapt(edge_updates=([1], [-1]))
        with pytest.raises(ValueError, match="length"):
            s.stage(edge_updates=([1, 2], [3]))

    def test_check_edge_updates_direct(self):
        src, dst = delta.check_edge_updates([0, 1], [1, 2], 3)
        assert src.dtype == np.int32 and dst.dtype == np.int32
        with pytest.raises(ValueError):
            delta.check_edge_updates([0], [5], 3)
        # growth bound wins when larger
        delta.check_edge_updates([0], [5], 3, new_num_vertices=6)


def test_extend_labels_shrink_raises():
    with pytest.raises(ValueError, match="remove_vertices"):
        extend_labels(np.zeros(10, np.int32), 5)
    out = extend_labels(np.zeros(10, np.int32), 12)
    assert out.shape == (12,) and (out[10:] == -1).all()


# ---------------------------------------------------------------------------
# tentpole data path: on-device delta merge
# ---------------------------------------------------------------------------

class TestDeltaMerge:
    OPTS = EngineOptions(engine="fused")

    def _oracle(self, g, batch, prev, cfg, num_vertices=None):
        g2 = add_edges(g, *batch, num_vertices=num_vertices)
        o = open_session(g2, cfg, self.OPTS)
        return o.adapt(prev=prev), g2

    def test_warm_delta_zero_compiles_no_rebuild_no_reupload(
            self, base_graph):
        cfg = SpinnerConfig(k=4, max_iters=37, seed=3)
        s = open_session(base_graph, cfg, self.OPTS)
        r0 = s.partition()
        rng = np.random.default_rng(1)
        V = base_graph.num_vertices
        full_bytes = 12 * base_graph.num_directed_entries  # src+dst+w f32/i32

        b1 = (rng.integers(0, V, 16), rng.integers(0, V, 16))
        r1 = s.adapt(edge_updates=b1)
        st = s.stats()
        assert st["delta"]["fast_adapts"] == 1
        assert st["delta"]["host_rebuilds"] == 0
        assert st["delta"]["fallback_adapts"] == 0
        assert 0 < st["delta"]["last_upload_bytes"] < full_bytes // 10
        warm_compiles = st["compiles"]

        # second same-bucket batch: ZERO new compiles, still no rebuild
        b2 = (rng.integers(0, V, 16), rng.integers(0, V, 16))
        r2 = s.adapt(edge_updates=b2)
        st = s.stats()
        assert st["compiles"] == warm_compiles, \
            "warm same-bucket delta adapt must not compile"
        assert st["delta"]["fast_adapts"] == 2
        assert st["delta"]["host_rebuilds"] == 0

        # bit-parity with the classic rebuild oracle at every step
        ro1, g1 = self._oracle(base_graph, b1, r0.labels, cfg)
        ro2, _ = self._oracle(g1, b2, ro1.labels, cfg)
        assert np.array_equal(r1.labels, ro1.labels)
        assert np.array_equal(r2.labels, ro2.labels)
        assert st["delta"]["tracked_total_weight"] == \
            add_edges(g1, *b2).total_weight

    def test_duplicate_edges_one_batch(self, base_graph):
        """Duplicates within a batch, reverse-direction upgrades of an
        existing w=1 edge, and self-loops all coalesce exactly like
        ``add_edges`` (union-of-directions semantics)."""
        cfg = SpinnerConfig(k=4, max_iters=31, seed=5)
        s = open_session(base_graph, cfg, self.OPTS)
        r0 = s.partition()
        # an existing single-direction (w=1) edge to upgrade
        w = np.asarray(base_graph.weight)
        src = np.asarray(base_graph.src)
        dst = np.asarray(base_graph.dst)
        one = np.flatnonzero((w == 1) & (src != dst))[0]
        u, v = int(src[one]), int(dst[one])
        batch = (np.array([u, u, v, 7, 9, 9, 11], np.int64),
                 np.array([v, v, u, 7, 10, 10, 12], np.int64))
        # (u,v) dup + (v,u) -> upgrade to w=2; (7,7) self-loop dropped;
        # (9,10) dup; (11,12) plain new
        r1 = s.adapt(edge_updates=batch)
        assert s.stats()["delta"]["fast_adapts"] == 1
        ro, g2 = self._oracle(base_graph, batch, r0.labels, cfg)
        assert np.array_equal(r1.labels, ro.labels)
        assert s.stats()["delta"]["tracked_total_weight"] == g2.total_weight

    def test_overflow_falls_back_bit_identical(self):
        """A delta larger than the bucket slack rebuilds on host --
        same labels, counted as a fallback."""
        V = 500
        g = from_edges(np.arange(V - 1), np.arange(1, V), num_vertices=V,
                       directed=False)   # path graph: tiny E bucket slack
        cfg = SpinnerConfig(k=4, max_iters=29, seed=7)
        slack = shape_bucket(g.num_directed_entries) - g.num_directed_entries
        batch = (np.arange(0, V - 2), np.arange(2, V))  # all-new pairs
        assert 2 * (V - 2) > slack
        s = open_session(g, cfg, self.OPTS)
        r0 = s.partition()
        r1 = s.adapt(edge_updates=batch)
        st = s.stats()["delta"]
        assert st["fast_adapts"] == 0
        assert st["fallback_adapts"] == 1
        assert st["host_rebuilds"] >= 1
        ro, _ = self._oracle(g, batch, r0.labels, cfg)
        assert np.array_equal(r1.labels, ro.labels)

    def test_vertex_growth_falls_back(self, base_graph):
        cfg = SpinnerConfig(k=4, max_iters=23, seed=9)
        V = base_graph.num_vertices
        s = open_session(base_graph, cfg, self.OPTS)
        r0 = s.partition()
        batch = (np.array([1, V + 2]), np.array([V, V + 1]))
        r1 = s.adapt(edge_updates=batch, num_vertices=V + 3)
        assert s.graph.num_vertices == V + 3
        assert s.stats()["delta"]["fast_adapts"] == 0
        ro, _ = self._oracle(base_graph, batch, r0.labels, cfg,
                             num_vertices=V + 3)
        assert np.array_equal(r1.labels, ro.labels)

    def test_update_pending_log_chains_with_fast_adapt(self, base_graph):
        """``update()`` batches join the pending log and are folded into
        the next fast adapt without a host rebuild."""
        cfg = SpinnerConfig(k=4, max_iters=43, seed=11)
        rng = np.random.default_rng(2)
        V = base_graph.num_vertices
        s = open_session(base_graph, cfg, self.OPTS)
        r0 = s.partition()
        b1 = (rng.integers(0, V, 8), rng.integers(0, V, 8))
        b2 = (rng.integers(0, V, 8), rng.integers(0, V, 8))
        s.update(*b1)
        r = s.adapt(edge_updates=b2)
        st = s.stats()["delta"]
        assert st["fast_adapts"] == 1 and st["host_rebuilds"] == 0
        assert st["merged_batches"] == 2
        ro1, g1 = self._oracle(base_graph, b1, r0.labels, cfg)
        del ro1  # update() does not run; only the final state must match
        o = open_session(add_edges(g1, *b2), cfg, self.OPTS)
        ro = o.adapt(prev=r0.labels)
        assert np.array_equal(r.labels, ro.labels)

    def test_stage_interaction(self, base_graph):
        """stage(edge_updates=) materializes the pending log (full host
        Graph) and the staged snapshot is consumed by the next adapt."""
        cfg = SpinnerConfig(k=4, max_iters=47, seed=13)
        rng = np.random.default_rng(3)
        V = base_graph.num_vertices
        s = open_session(base_graph, cfg, self.OPTS)
        r0 = s.partition()
        b1 = (rng.integers(0, V, 8), rng.integers(0, V, 8))
        b2 = (rng.integers(0, V, 8), rng.integers(0, V, 8))
        r1 = s.adapt(edge_updates=b1)          # fast path
        assert s.stats()["delta"]["fast_adapts"] == 1
        s.stage(edge_updates=b2)               # materializes + rebuilds
        st = s.stats()
        assert st["delta"]["host_rebuilds"] >= 1
        assert st["staged"] == V
        r2 = s.adapt()                         # consumes the staged graph
        g1 = add_edges(base_graph, *b1)
        g2 = add_edges(g1, *b2)
        o1 = open_session(g1, cfg, self.OPTS)
        ro1 = o1.adapt(prev=r0.labels)
        o2 = open_session(g2, cfg, self.OPTS)
        ro2 = o2.adapt(prev=ro1.labels)
        assert np.array_equal(r1.labels, ro1.labels)
        assert np.array_equal(r2.labels, ro2.labels)

    def test_pallas_fused_delta_parity_zero_compiles(self, base_graph):
        """The tiled-CSR merge: per-tile slack slots + deg_t + the COO
        mirror, on the Pallas fused backend (interpret on CPU)."""
        cfg = SpinnerConfig(k=4, max_iters=41, seed=15)
        opts = EngineOptions(engine="fused", score_backend="pallas",
                             fused_update="on")
        s = open_session(base_graph, cfg, opts)
        r0 = s.partition()
        rng = np.random.default_rng(4)
        V = base_graph.num_vertices
        b1 = (rng.integers(0, V, 24), rng.integers(0, V, 24))
        r1 = s.adapt(edge_updates=b1)
        st = s.stats()
        assert st["delta"]["fast_adapts"] == 1
        assert st["delta"]["host_rebuilds"] == 0
        warm = st["compiles"]
        b2 = (rng.integers(0, V, 24), rng.integers(0, V, 24))
        r2 = s.adapt(edge_updates=b2)
        assert s.stats()["compiles"] == warm
        g1 = add_edges(base_graph, *b1)
        g2 = add_edges(g1, *b2)
        o1 = open_session(g1, cfg, opts)
        ro1 = o1.adapt(prev=r0.labels)
        o2 = open_session(g2, cfg, opts)
        ro2 = o2.adapt(prev=ro1.labels)
        assert np.array_equal(r1.labels, ro1.labels)
        assert np.array_equal(r2.labels, ro2.labels)


# ---------------------------------------------------------------------------
# tentpole compute path: dirty-frontier reconvergence (single device)
# ---------------------------------------------------------------------------

class TestFrontierSingleDevice:

    def _parity(self, g, cfg, opts, seed=3, nb=8, rounds=1):
        """``rounds`` fast frontier adapts in a row, each against the
        full re-adapt of a session rebuilt on the grown graph."""
        s, r1 = _converged(g, cfg, opts)
        rng = np.random.default_rng(seed)
        V = g.num_vertices
        for _ in range(rounds):
            b = (rng.integers(0, V, nb), rng.integers(0, V, nb))
            rf = s.adapt(edge_updates=b, frontier=True)
            g = add_edges(g, *b)
            o = open_session(g, cfg, opts)
            ro = o.adapt(prev=r1.labels)
            assert np.array_equal(rf.labels, ro.labels), \
                "frontier labels diverge from the full re-adapt oracle"
            # strictly sub-linear scored fraction, reported per iteration
            assert rf.iterations >= 1
            assert len(rf.scored_per_iter) == rf.iterations
            assert rf.scored_vertices == sum(rf.scored_per_iter)
            assert rf.scored_vertices < 0.25 * V * rf.iterations
            r1 = rf
        assert s.stats()["delta"]["fast_adapts"] == rounds
        return rf

    def test_frontier_parity_xla(self, fixed_point_graph):
        """Two fast frontier adapts: the second expands over the edge
        list the first merge re-sorted into CSR order."""
        cfg = SpinnerConfig(k=4, max_iters=120, seed=9, c=1.6)
        self._parity(fixed_point_graph, cfg, EngineOptions(engine="fused"),
                     rounds=2)

    def test_frontier_parity_xla_fused_on(self, fixed_point_graph):
        cfg = SpinnerConfig(k=4, max_iters=121, seed=9, c=1.6)
        self._parity(fixed_point_graph, cfg,
                     EngineOptions(engine="fused", fused_update="on"))

    def test_frontier_parity_pallas_fused(self, fixed_point_graph):
        cfg = SpinnerConfig(k=4, max_iters=122, seed=9, c=1.6)
        self._parity(fixed_point_graph, cfg,
                     EngineOptions(engine="fused", score_backend="pallas",
                                   fused_update="on"))

    def test_frontier_full_active_degenerates_to_drain_lpa(
            self, fixed_point_graph):
        """No delta provenance -> every vertex active; on a fixed point
        the frontier drains immediately with unchanged labels."""
        cfg = SpinnerConfig(k=4, max_iters=123, seed=9, c=1.6)
        s, r1 = _converged(fixed_point_graph, cfg,
                           EngineOptions(engine="fused"))
        rf = s.adapt(frontier=True)
        assert np.array_equal(rf.labels, r1.labels)
        assert rf.halted

    def test_frontier_rejects_history_and_chunked(self, fixed_point_graph):
        cfg = SpinnerConfig(k=4, max_iters=124, seed=9, c=1.6)
        s, _ = _converged(fixed_point_graph, cfg,
                          EngineOptions(engine="fused"))
        with pytest.raises(ValueError, match="frontier"):
            s.adapt(frontier=True, record_history=True)
        with pytest.raises(ValueError, match="frontier"):
            s.adapt(frontier=True, callback=lambda i, e: None)
        s2 = open_session(fixed_point_graph, cfg,
                          EngineOptions(engine="chunked"))
        s2.partition()
        with pytest.raises(ValueError, match="while_loop"):
            s2.adapt(frontier=True)


# ---------------------------------------------------------------------------
# sharded matrix: 2/4/8 forced host devices (subprocess), exchange plans
# ---------------------------------------------------------------------------

SHARDED_DELTA_FRONTIER = """
import numpy as np, jax
from jax.sharding import Mesh
import repro.core as core
from repro.core.generators import clustered_graph

ndev = {ndev}
g = clustered_graph(4, 150, p_in=0.2, p_out_edges_per_v=0.05, seed=2)
V = g.num_vertices
cfg = core.SpinnerConfig(k=4, max_iters=83, seed=9, c=1.6)
mesh = Mesh(np.array(jax.devices()), ("data",))
assert mesh.size == ndev, mesh
rng = np.random.default_rng(3)
b = (rng.integers(0, V, 8), rng.integers(0, V, 8))
g1 = core.add_edges(g, *b)

for plan in ("allgather", "delta"):
    for fused in ("off", "on"):
        opts = core.EngineOptions(engine="sharded", mesh=mesh,
                                  label_exchange=plan, overlap="off",
                                  fused_update=fused)
        s = core.open_session(g, cfg, opts)
        s.partition()
        r1 = s.adapt()
        r2 = s.adapt()
        assert np.array_equal(r1.labels, r2.labels), (plan, "fixed point")
        o = core.open_session(g1, cfg, opts)
        ro = o.adapt(prev=r2.labels)
        # data path: on-device merge into the sharded segment slack
        rfast = s.adapt(edge_updates=b)
        st = s.stats()["delta"]
        assert st["fast_adapts"] == 1 and st["host_rebuilds"] == 0, st
        assert np.array_equal(rfast.labels, ro.labels), (plan, fused, "fast")
        # compute path: sharded dirty-frontier reconvergence
        s2 = core.open_session(g, cfg, opts)
        s2.partition(); s2.adapt()
        rf = s2.adapt(edge_updates=b, frontier=True)
        assert np.array_equal(rf.labels, ro.labels), (plan, fused, "frontier")
        assert rf.scored_vertices < 0.25 * V * max(1, rf.iterations), (
            plan, fused, rf.scored_per_iter)

# halo's boundary-slot dst layout is ineligible for the on-device merge:
# the fast path must refuse, the fallback must stay bit-identical, and
# frontier mode must still work through the materialized run
opts = core.EngineOptions(engine="sharded", mesh=mesh,
                          label_exchange="halo", overlap="off")
s = core.open_session(g, cfg, opts)
s.partition()
r1 = s.adapt()
o = core.open_session(g1, cfg, opts)
ro = o.adapt(prev=r1.labels)
rf = s.adapt(edge_updates=b, frontier=True)
st = s.stats()["delta"]
assert st["fast_adapts"] == 0 and st["fallback_adapts"] == 1, st
assert np.array_equal(rf.labels, ro.labels), "halo frontier"
print("SHARDED DELTA/FRONTIER OK", ndev)
"""


@pytest.mark.slow
@pytest.mark.parametrize("ndev", [2, 4, 8])
def test_sharded_delta_frontier_exchange_parity(ndev):
    r = run_devices_subprocess(SHARDED_DELTA_FRONTIER.format(ndev=ndev),
                               ndev=ndev)
    assert r.returncode == 0, r.stderr[-4000:]
    assert f"SHARDED DELTA/FRONTIER OK {ndev}" in r.stdout
