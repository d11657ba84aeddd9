"""Figure 5: per-iteration runtime scaling.

(a) vs |V| at fixed degree (Watts-Strogatz, as in the paper),
(b) vs workers (the SHARDED FUSED engine -- one ``shard_map(while_loop)``
    dispatch per run -- in a subprocess with N forced host devices; on
    this 1-core container the numbers validate *overhead*, not speedup;
    see EXPERIMENTS.md),
(c) vs number of partitions k.

For (a)/(c) we time the FIRST full iteration (every vertex active), as in
the paper, averaged over a few repeats after a warmup call.  For (b) we
time a fixed-length fused run (halting disabled) and report the amortized
per-iteration cost, which is exactly what the sharded engine changes.
"""
from __future__ import annotations

import subprocess
import sys
import time

import jax
import numpy as np

from repro.core import SpinnerConfig, generators
from repro.core.spinner import compute_loads, init_labels, make_step

from .common import cpu_child_env, emit


def _iter_time(g, k: int, repeats: int = 3) -> float:
    cfg = SpinnerConfig(k=k, seed=0)
    step = make_step(g, cfg)
    key = jax.random.PRNGKey(0)
    labels = init_labels(g, cfg, key)
    loads = compute_loads(g, labels, k)
    out = step(labels, loads, key)           # warmup/compile
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(repeats):
        out = step(labels, loads, key)       # first-iteration semantics
        jax.block_until_ready(out)
    return (time.time() - t0) / repeats


def run(quick: bool = False) -> list:
    rows = []
    # (a) vs graph size
    sizes = (2**14, 2**15, 2**16) if quick else (2**14, 2**15, 2**16, 2**17)
    for v in sizes:
        g = generators.watts_strogatz(v, 20, 0.3, seed=1)
        dt = _iter_time(g, 16)
        rows.append({
            "name": f"scalability/V{v}",
            "us_per_call": dt * 1e6,
            "derived": f"edges={g.num_undirected_edges};"
                       f"us_per_edge={dt * 1e6 / g.num_undirected_edges:.4f}",
            "V": v, "E": g.num_undirected_edges, "seconds": dt,
        })
    # (c) vs partitions
    g = generators.watts_strogatz(2**15, 20, 0.3, seed=1)
    for k in (2, 8, 32, 128) if quick else (2, 8, 32, 128, 512):
        dt = _iter_time(g, k)
        rows.append({
            "name": f"scalability/k{k}",
            "us_per_call": dt * 1e6,
            "derived": f"us_per_k={dt * 1e6 / k:.2f}",
            "k": k, "seconds": dt,
        })
    # (b) vs workers: the sharded fused engine (ONE while_loop dispatch per
    # run) in a subprocess with forced host device counts.  halt_window >
    # max_iters disables halting so every device count runs the same fixed
    # iteration count and the per-iteration cost is directly comparable.
    iters = 10 if quick else 20
    for ndev in (1, 2, 4) if quick else (1, 2, 4, 8):
        code = (
            "import time;"
            "from repro.core import generators;"
            "from repro.core.spinner import SpinnerConfig, partition;"
            "from repro.launch.mesh import make_partition_mesh;"
            "g = generators.watts_strogatz(2**15, 20, 0.3, seed=1);"
            f"cfg = SpinnerConfig(k=16, seed=0, max_iters={iters},"
            " halt_window=10**6);"
            "mesh = make_partition_mesh();"
            "kw = dict(record_history=False, engine='sharded', mesh=mesh);"
            "res = partition(g, cfg, **kw);"
            "t0 = time.time();"
            "res = partition(g, cfg, **kw);"
            "print('RUN_S', time.time() - t0, res.iterations)"
        )
        env = cpu_child_env(ndev)
        r = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=600)
        line = [ln for ln in r.stdout.splitlines() if "RUN_S" in ln]
        if line:
            total_s, ran = float(line[0].split()[1]), int(line[0].split()[2])
            dt = total_s / max(1, ran)
        else:
            total_s, ran, dt = float("nan"), 0, float("nan")
        rows.append({
            "name": f"scalability/workers{ndev}",
            "us_per_call": dt * 1e6,
            "derived": f"platform=cpu;devices={ndev};iters={ran};"
                       f"run_s={total_s:.3f};engine=sharded",
            "workers": ndev, "seconds": dt, "platform": "cpu",
        })
    emit(rows, "bench_scalability")
    return rows


if __name__ == "__main__":
    run()
