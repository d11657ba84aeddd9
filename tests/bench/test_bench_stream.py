"""The stream cell: what its batches hold, that they take the fast path at
the test size, that the ledger's old canonical-direction rule is caught by
the comparison that decides ``correct``, and that a program without the
arc record ends the run before any work."""
import dataclasses
import json
import sys
import time
import types

import numpy as np
import pytest

import bench_testkit as kit  # (puts bench/ on the path)
import base
import controls

CELL = "ws1m.stream"


def _records(logs: list) -> dict:
    (line,) = [m for m in logs if m.startswith("records: ")]
    return json.loads(line[len("records: "):])


@pytest.fixture(scope="module")
def driver():
    cell = kit.tiny_cell(CELL)
    d = base.find("drivers", "stream").Driver(cell["config"],
                                              cell["traffic"], kit.SEED)
    d.open()
    return d


def test_batches_hold_held_back_arcs_follow_backs_and_repeats(driver):
    n, cfg = driver.n, driver.config
    key = lambda s, d: s.astype(np.int64) * n + d  # noqa: E731
    present = set(key(driver.src, driver.dst).tolist())
    held = follow = repeat = 0
    for s, d in driver.batches:
        assert s.size == cfg["batch_arcs"]
        arcs = key(s, d).tolist()
        rev = key(d, s).tolist()
        for a, r in zip(arcs, rev):
            if a in present:
                repeat += 1
            elif r in present:
                follow += 1
            else:
                held += 1
        present |= set(arcs)
    per = cfg["batch_arcs"] * cfg["batches"]
    assert repeat == per * cfg["repeat_share"]
    assert held + follow == per * (1 - cfg["repeat_share"])
    # a held-back arc whose reverse the base graph has also reciprocates
    assert per * cfg["follow_back_share"] <= follow < per * (
        cfg["follow_back_share"] + 0.01)


def test_the_seed_orders_arcs_and_changes_no_batch(driver):
    cell = kit.tiny_cell(CELL)
    other = base.find("drivers", "stream").Driver(cell["config"],
                                                  cell["traffic"],
                                                  kit.SEED + 1)
    other.open()
    for (s1, d1), (s2, d2) in zip(driver.batches, other.batches):
        assert not np.array_equal(s1, s2)
        assert sorted(zip(s1.tolist(), d1.tolist())) == \
            sorted(zip(s2.tolist(), d2.tolist()))


def test_changed_pairs_are_the_pairs_a_batch_reweighs(driver):
    """``batch_phi_gap`` looks at the pairs whose Eq. 3 weight the batch
    changes: new pairs and reciprocated ones, not redelivered arcs."""
    n = driver.n

    def weighted(g):
        half = g.src < g.dst
        return g.src[half].astype(np.int64) * n + g.dst[half], g.w[half]

    for given in range(1, len(driver.batches) + 1):
        k0, w0 = weighted(driver.union_ref(given - 1))
        k1, w1 = weighted(driver.union_ref(given))
        changed = np.setdiff1d(k1 * 4 + w1.astype(np.int64),
                               k0 * 4 + w0.astype(np.int64)) // 4
        lo, hi = driver.changed_pairs(given)
        assert np.array_equal(lo.astype(np.int64) * n + hi, changed)
        assert 0 < changed.size < driver.config["batch_arcs"]


def test_a_program_without_arc_directions_ends_the_run_at_once(
        monkeypatch):
    import repro.core
    stream = base.find("drivers", "stream")
    stream.require_arc_ledger()
    real = repro.core.from_edges
    monkeypatch.setattr(repro.core, "from_edges", lambda *a, **kw:
                        dataclasses.replace(real(*a, **kw), dirs=None))
    with pytest.raises(SystemExit, match="no arc directions"):
        stream.require_arc_ledger()


def test_canonical_direction_is_caught_and_batches_take_the_fast_path():
    cell = kit.tiny_cell(CELL)
    logs: list = []
    with controls.plant(cell["traffic"]["driver"], "canonical_direction"):
        res = kit.harness.run(cell, kit.SEED, 0.2, False, time.perf_counter(),
                              "", log=logs.append)
    checks = res["checks"]
    assert checks["loads_gap"]["value"] > 0 or checks["deg_gap"]["value"] > 0
    assert not res["correct"]
    delta = _records(logs)["delta"]
    assert delta["fast_adapts"] == cell["config"]["batches"]
    assert delta["fallback_adapts"] == 0


def test_adapt_host_ms_reads_the_adapts_less_their_wait(monkeypatch):
    from repro.core import SpinnerConfig, from_edges, open_session, trace
    trace.clear()
    rng = np.random.default_rng(3)
    n = 300
    graph = from_edges(rng.integers(0, n, 2400), rng.integers(0, n, 2400), n)
    with open_session(graph, SpinnerConfig(k=8, seed=2, max_iters=72)) as s:
        s.partition(record_history=False)
        its = [s.adapt(edge_updates=(rng.integers(0, n, 16),
                                     rng.integers(0, n, 16)),
                       record_history=False).iterations for _ in range(3)]
    run = types.SimpleNamespace(records={"adapt_iterations": its}, trace={})
    reader = base.find("metrics", "adapt_host_ms.stream")
    calls = trace.spans("session/adapt")
    host = []
    for call in calls:
        (wait,) = [c for c in trace.children(call) if c.name == "session/wait"]
        host.append(call.duration_ns - wait.duration_ns)
    assert reader.read(run) == pytest.approx(1e-6 * sum(host) / 3)
    plan = trace.spans("delta/plan")[-1]
    assert plan.attrs["arcs_given"] == 16
    assert "pairs_reciprocated" in plan.attrs
    monkeypatch.delattr(sys.modules["repro.core"], "trace")
    monkeypatch.setitem(sys.modules, "repro.core.trace", None)
    assert reader.read(run) is None
    trace.clear()
