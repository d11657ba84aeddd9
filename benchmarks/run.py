"""Benchmark orchestrator: one suite per paper table/figure + roofline.

Prints ``name,us_per_call,derived`` CSV.  ``--quick`` shrinks sweeps;
``--suite X`` runs one suite.  Artifacts land in benchmarks/artifacts/.
"""
from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--suite", default=None,
                    help="quality|convergence|scalability|dynamic|elastic|"
                         "apps|placement|kernel|engine|serve|cluster|"
                         "roofline")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from . import (bench_apps, bench_convergence, bench_dynamic,
                   bench_elastic, bench_engine, bench_kernel,
                   bench_placement, bench_quality, bench_scalability,
                   bench_serve, roofline)
    suites = {
        "quality": bench_quality.run,          # Fig 3, Tables 1 & 3
        "convergence": bench_convergence.run,  # Fig 4
        "scalability": bench_scalability.run,  # Fig 5
        "dynamic": bench_dynamic.run,          # Fig 6
        "elastic": bench_elastic.run,          # Fig 7
        "apps": bench_apps.run,                # Fig 8, Table 4
        "placement": bench_placement.run,      # beyond-paper
        "kernel": bench_kernel.run,            # Pallas kernel
        "engine": bench_engine.run,            # dispatch/overlap/staged
        "serve": bench_serve.run,              # multi-tenant scheduler
        "cluster": bench_elastic.run_fault,    # fault-injected recovery
        "roofline": roofline.run,              # deliverable (g)
    }
    selected = ([args.suite] if args.suite else list(suites))
    print("name,us_per_call,derived")
    t0 = time.time()
    failures = []
    for name in selected:
        try:
            rows = suites[name](quick=args.quick)
            if name in ("dynamic", "serve", "cluster", "apps"):
                # perf-trajectory artifacts (delta adapt, serving tier,
                # application speedup): machine-readable, at the repo root
                import json
                import os
                root = os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__)))
                with open(os.path.join(root, f"BENCH_{name}.json"),
                          "w") as fh:
                    json.dump(rows, fh, indent=1, default=float)
        except Exception as e:  # keep the suite running; report at the end
            import traceback
            traceback.print_exc()
            failures.append((name, repr(e)))
    print(f"# total_seconds={time.time() - t0:.1f} failures={failures}")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
