"""The trace reduction: exact on hand-made timelines, and sound on a small
trace recorded on the CPU (``data/cpu_trace.xplane.pb``; re-record it with
``python tests/bench/test_bench_trace.py``)."""
import os
import types

import pytest

import bench_testkit  # noqa: F401
import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CPU_TRACE = os.path.join(DATA, "cpu_trace.xplane.pb")


def _profile(device_ops, spans):
    """A stand-in for ``jax.profiler.ProfileData``: times in ns."""
    ev = lambda name, s, e: types.SimpleNamespace(
        name=name, start_ns=s, duration_ns=e - s)
    line = lambda name, evs: types.SimpleNamespace(
        name=name, events=[ev(*e) for e in evs])
    return types.SimpleNamespace(planes=[
        types.SimpleNamespace(name="/device:TPU:0",
                              lines=[line("XLA Ops", device_ops),
                                     line("XLA Modules", [("m", 0, 99)])]),
        types.SimpleNamespace(name="/host:CPU",
                              lines=[line("python", spans)]),
    ])


def test_union_and_covered():
    merged = tr.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert merged == [(0, 3), (5, 8)]
    assert tr.covered(merged, 2, 6) == 2
    assert tr.covered(merged, 9, 10) == 0


def test_reduce_by_hand():
    s = 1e-9
    prof = _profile(
        device_ops=[("%while.7 = (s32[8]) while(...)", 10, 80),
                    ("%fusion = f32[8] fusion(...)", 10, 40),
                    ("scatter", 30, 60),
                    ("fusion", 70, 80), ("copy", 95, 120)],
        spans=[("bench/window", 0, 100), ("bench/partition", 5, 65),
               ("bench/init_labels", 65, 70), ("bench/partition", 70, 90),
               ("other", 0, 100)])
    r = tr.reduce(prof)
    assert r["window_s"] == pytest.approx(100 * s)
    # busy: [10, 80] + [95, 100] inside the window
    assert r["busy_s"] == pytest.approx(75 * s)
    assert r["busy_in"]["partition"] == pytest.approx(65 * s)
    assert r["span_s"] == pytest.approx({"partition": 80 * s,
                                         "init_labels": 5 * s})
    assert r["span_n"] == {"partition": 2, "init_labels": 1}
    ops = dict(r["device_ops"])
    assert "while.7" not in ops                  # it holds the others
    assert ops["fusion"] == pytest.approx(40 * s)
    assert ops["copy"] == pytest.approx(5 * s)   # clipped to the window
    idle = dict(r["idle_gaps"])
    # partition [5,10) [80,90), host [0,5) and [90,95)
    assert idle["partition"] == pytest.approx(15 * s)
    assert "init_labels" not in idle
    assert idle["host"] == pytest.approx(10 * s)
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_reduce_finds_nothing_without_a_window_or_device():
    assert tr.reduce(_profile([("f", 0, 5)], [("bench/partition", 0, 9)])) \
        == {}
    assert tr.reduce(_profile([], [("bench/window", 0, 9)])) == {}


def test_recorded_cpu_trace():
    if not os.path.exists(CPU_TRACE):
        pytest.fail(f"missing {CPU_TRACE}; re-record it")
    import jax
    prof = jax.profiler.ProfileData.from_file(CPU_TRACE)
    assert tr.reduce(prof) == {}          # no TPU plane in a CPU trace
    r = tr.reduce(prof, keep=tr.cpu_ops)
    assert 0 < r["busy_s"] < r["window_s"] < 5
    assert r["span_n"] == {"partition": 3, "init_labels": 3}
    assert 0 < r["busy_in"]["partition"] <= r["span_s"]["partition"]
    assert r["device_ops"] and all(0 < t <= r["busy_s"]
                                   for _, t in r["device_ops"])
    idle = dict(r["idle_gaps"])
    assert idle["init_labels"] >= 3 * 0.02 * 0.9   # three 20 ms sleeps
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def record(path: str = CPU_TRACE) -> None:
    """Record the committed trace: three ``bench/partition`` spans of
    matrix products, each after a 20 ms ``bench/init_labels`` sleep."""
    import glob
    import shutil
    import tempfile
    import time
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((512, 512))
    f(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench/window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench/init_labels"):
                time.sleep(0.02)
            with jax.profiler.TraceAnnotation("bench/partition"):
                for _ in range(3):
                    f(x).block_until_ready()
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    shutil.copy(found[0], path)
    shutil.rmtree(tmp)


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    record()
