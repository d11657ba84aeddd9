"""Figure 8 + Table 4: application performance under Spinner vs hash.

The REAL measurement this time: every row is the device-resident
application engine (``repro.apps``) running a workload as one
``shard_map(while_loop)`` dispatch on 8 forced host devices, with

  * wall-clock per run, WARM (the program is compiled and every layout
    /plan/arg cache hot before the timed calls -- we measure dispatch,
    not tracing).  Honest-reporting note: forced host devices share one
    CPU's memory, so wall-clock does NOT see real network latency; the
    wire-byte and skew columns carry the paper's mechanism, and the
    reduction there is the transferable claim;
  * wire bytes per superstep, accumulated ON DEVICE by the exchange
    plan (the boundary-only halo / changed-values halo_delta traffic);
  * straggler skew (max/mean of per-device combined messages) -- the
    Table 4 barrier-idle proxy;
  * the static ``comm_volume`` predictor from ``metrics.summarize`` on
    every row, so the artifact correlates prediction with measurement.

Matrix: workload (PageRank / WCC / BFS) x placement (hash baseline /
Spinner) x exchange plan, plus the beyond-paper MoE expert-placement
leg (Pregel over the expert co-activation graph).  Speedup rows divide
hash by Spinner wall-clock per (workload, plan); the wire-reduction
acceptance (>= 40% on every workload) is asserted in
``tests/test_apps.py``.

The multi-device matrix runs in ONE subprocess under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the
conftest-free path tests use); rows come back as JSON on stdout.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from .common import cpu_child_env, emit, mark_cpu

_CHILD = """
import json
import time

import numpy as np

from repro.apps import APPS, run_app
from repro.core import generators, metrics
from repro.core.placement import expert_placement_case
from repro.core.spinner import SpinnerConfig, partition
from repro.launch.mesh import make_partition_mesh

QUICK = {quick}
NDEV = 8
mesh = make_partition_mesh(NDEV)

g = generators.clustered_graph(
    8, 500 if QUICK else 2000, p_in=0.02 if QUICK else 0.01,
    p_out_edges_per_v=1.0, seed=5)
res = partition(g, SpinnerConfig(k=NDEV, seed=1,
                                 max_iters=80 if QUICK else 200),
                record_history=False)
hash_l = (np.arange(g.num_vertices) * np.int64(2654435761)
          % NDEV).astype(np.int32)
placements = {{"hash": hash_l, "spinner": res.labels}}
comm_vol = {{name: metrics.summarize(g, lab, NDEV)["comm_volume"]
            for name, lab in placements.items()}}

PLANS = {{
    "pagerank": ("halo",) if QUICK else ("allgather", "halo"),
    "wcc": ("halo_delta",) if QUICK else ("halo", "halo_delta"),
    "bfs": ("halo_delta",) if QUICK else ("halo", "halo_delta"),
}}
ITERS = 5 if QUICK else 10
REPEATS = 2 if QUICK else 3


def bench_one(graph, labels, wl, plan, kvol):
    kw = dict(mesh=mesh, plan=plan, iters=ITERS)
    r = run_app(graph, labels, wl, **kw)          # warm: compile + caches
    t0 = time.perf_counter()
    for _ in range(REPEATS):
        r = run_app(graph, labels, wl, **kw)
        np.asarray(r.values)                      # block on the dispatch
    dt = (time.perf_counter() - t0) / REPEATS
    return r, dt


rows = []
for wl in ("pagerank", "wcc", "bfs"):
    for plan in PLANS[wl]:
        wall = {{}}
        for pname, labels in placements.items():
            r, dt = bench_one(g, labels, wl, plan, comm_vol[pname])
            wall[pname] = dt
            rows.append({{
                "name": f"apps/{{wl}}/{{plan}}/{{pname}}",
                "us_per_call": dt * 1e6,
                "workload": wl, "plan": plan, "placement": pname,
                "ndev": NDEV, "supersteps": r.supersteps,
                "converged": r.converged,
                "wall_s": dt,
                "wire_bytes": r.wire_bytes,
                "wire_bytes_per_step": r.wire_bytes_per_step,
                "straggler_skew": r.straggler_skew,
                "comm_volume": comm_vol[pname],
                "derived": f"wire/step={{r.wire_bytes_per_step:.0f}}B;"
                           f"skew={{r.straggler_skew:.2f}};"
                           f"steps={{r.supersteps}}",
            }})
        wp = {{p: next(x for x in rows
                      if x["name"] == f"apps/{{wl}}/{{plan}}/{{p}}")
              for p in placements}}
        red = 1 - (wp["spinner"]["wire_bytes_per_step"]
                   / max(wp["hash"]["wire_bytes_per_step"], 1e-9))
        rows.append({{
            "name": f"apps/{{wl}}/{{plan}}/speedup",
            "us_per_call": 0.0,
            "workload": wl, "plan": plan, "ndev": NDEV,
            "speedup_wall": wall["hash"] / max(wall["spinner"], 1e-12),
            "wire_reduction": red,
            "comm_volume_reduction":
                1 - comm_vol["spinner"] / max(comm_vol["hash"], 1e-9),
            "derived": f"wall_speedup="
                       f"{{wall['hash'] / max(wall['spinner'], 1e-12):.2f}}x;"
                       f"wire_reduction={{red:.1%}}",
        }})

# beyond-paper leg: Pregel over the MoE expert co-activation graph
eg, elabels, estats = expert_placement_case(
    n_experts=128 if QUICK else 512, n_tokens=1000 if QUICK else 4000,
    n_shards=NDEV, seed=0)
ehash = (np.arange(eg.num_vertices) * np.int64(2654435761)
         % NDEV).astype(np.int32)
ecomm = {{"hash": metrics.summarize(eg, ehash, NDEV)["comm_volume"],
         "spinner": metrics.summarize(eg, elabels, NDEV)["comm_volume"]}}
ewire = {{}}
for pname, labels in (("hash", ehash), ("spinner", elabels)):
    r, dt = bench_one(eg, labels, "pagerank", "halo", ecomm[pname])
    ewire[pname] = r.wire_bytes_per_step
    rows.append({{
        "name": f"apps/moe-experts/pagerank/halo/{{pname}}",
        "us_per_call": dt * 1e6,
        "workload": "pagerank", "plan": "halo", "placement": pname,
        "graph": "moe-coactivation", "ndev": NDEV,
        "wall_s": dt, "wire_bytes": r.wire_bytes,
        "wire_bytes_per_step": r.wire_bytes_per_step,
        "straggler_skew": r.straggler_skew,
        "comm_volume": ecomm[pname],
        "derived": f"wire/step={{r.wire_bytes_per_step:.0f}}B;"
                   f"skew={{r.straggler_skew:.2f}}",
    }})
rows.append({{
    "name": "apps/moe-experts/pagerank/halo/speedup",
    "us_per_call": 0.0,
    "graph": "moe-coactivation",
    "wire_reduction": 1 - ewire["spinner"] / max(ewire["hash"], 1e-9),
    "traffic_reduction": estats["traffic_reduction"],
    "derived": f"wire_reduction="
               f"{{1 - ewire['spinner'] / max(ewire['hash'], 1e-9):.1%}};"
               f"router_traffic_reduction="
               f"{{estats['traffic_reduction']:.1%}}",
}})
print("ROWS_JSON:" + json.dumps(rows, default=float))
"""


def run(quick: bool = False) -> list:
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = cpu_child_env(8)
    code = _CHILD.format(quick=repr(bool(quick)))
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=here,
                       capture_output=True, text=True, timeout=1800)
    payload = [ln for ln in r.stdout.splitlines()
               if ln.startswith("ROWS_JSON:")]
    if not payload:
        raise RuntimeError(
            f"apps bench subprocess failed:\n{r.stdout}\n{r.stderr}")
    rows = mark_cpu(json.loads(payload[0][len("ROWS_JSON:"):]))
    emit(rows, "bench_apps")
    return rows


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    rows = run(quick=ap.parse_args().quick)
    out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_apps.json")
    with open(out, "w") as fh:
        json.dump(rows, fh, indent=1, default=float)
