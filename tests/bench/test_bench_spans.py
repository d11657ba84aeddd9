"""The per-layer metrics read from the program's own spans
(``repro.core.trace``): what they read from a tiny partition, and that
they read nothing from a program that records no spans."""
import sys
import types

import numpy as np
import pytest

import bench_testkit as kit  # noqa: F401  (puts bench/ on the path)
import base

from repro.core import SpinnerConfig, from_edges, open_session, trace

SESSION_HOST = "session_host_ms.partition"
CSR_BUILD = "csr_build_s.partition"


@pytest.fixture()
def window():
    """A CSR build, a warm-up partition and a window of three, as a
    partition cell runs them; the run view its readers get."""
    trace.clear()
    rng = np.random.default_rng(7)
    n = 300
    graph = from_edges(rng.integers(0, n, 2400), rng.integers(0, n, 2400),
                       n)
    with open_session(graph, SpinnerConfig(k=8, seed=2, max_iters=71)) as s:
        s.partition(record_history=False)
        its = [s.partition(init=rng.integers(0, 8, n, dtype=np.int32),
                           record_history=False).iterations
               for _ in range(3)]
    yield types.SimpleNamespace(records={"iterations": its}, trace={})
    trace.clear()


def test_session_host_ms_is_the_window_calls_less_their_wait(window):
    calls = trace.spans("session/partition")
    assert len(calls) == 4
    host = []
    for call in calls[1:]:
        (wait,) = [c for c in trace.children(call)
                   if c.name == "session/wait"]
        host.append(call.duration_ns - wait.duration_ns)
    value = base.find("metrics", SESSION_HOST).read(window)
    assert value == pytest.approx(1e-6 * sum(host) / 3)
    assert 0 < value < 1e-6 * sum(c.duration_ns for c in calls[1:]) / 3


def test_csr_build_s_is_the_build_before_the_window(window):
    (build,) = trace.spans("graph/from_edges")
    value = base.find("metrics", CSR_BUILD).read(window)
    assert value == pytest.approx(1e-9 * build.duration_ns)
    # a build after the window's first call is not the cell's graph
    with trace.span("graph/from_edges"):
        pass
    assert base.find("metrics", CSR_BUILD).read(window) == value


@pytest.mark.parametrize("name", [SESSION_HOST, CSR_BUILD])
def test_readers_need_every_window_call(window, name):
    short = types.SimpleNamespace(records={"iterations": [1] * 5}, trace={})
    assert base.find("metrics", name).read(short) is None
    empty = types.SimpleNamespace(records={}, trace={})
    assert base.find("metrics", name).read(empty) is None


@pytest.mark.parametrize("name", [SESSION_HOST, CSR_BUILD])
def test_readers_read_nothing_without_program_spans(window, name,
                                                    monkeypatch):
    # a program without repro.core.trace, as the parent of these metrics
    monkeypatch.delattr(sys.modules["repro.core"], "trace")
    monkeypatch.setitem(sys.modules, "repro.core.trace", None)
    assert base.find("metrics", name).read(window) is None
