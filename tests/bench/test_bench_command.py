"""The command refuses a machine without a TPU: non-zero exit, no result."""
import os
import shutil
import subprocess
import sys

import bench_testkit as kit


def _run(cwd: str):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ws1m.partition",
         "--seed", "4294967311", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_exits_nonzero_without_a_tpu():
    out = _run(kit.REPO)
    assert out.returncode == 2, out.stderr[-2000:]
    assert "needs 1 TPU chip" in out.stderr
    assert not any(line.startswith("{") for line in out.stdout.splitlines())


def test_command_in_a_bare_checkout_prints_no_result(tmp_path):
    shutil.copy(os.path.join(kit.REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(kit.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(str(tmp_path))
    assert out.returncode != 0
    assert out.stdout.strip() == ""
