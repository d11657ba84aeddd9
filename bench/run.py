"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload ws1m.partition --seed 7 --seconds 51 \
        --trace 0

From the root of a checkout.  The cell, its configuration, traffic mix,
limits and per-layer metrics are read from ``BENCHMARK.json`` and the files
it names (see ``harness.py``).  With ``--trace 0`` the result carries the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics, read
from a profiler trace of the window.  The last line of standard output is
one JSON object; the last lines of standard error give each number that
decided ``correct`` beside its limit.  A machine without a TPU, or with
fewer chips than the cell asks for, exits 2 and prints no result.

JAX's persistent compilation cache lives in ``<checkout>/.jax_cache``, so
only the first run of a cell in a checkout compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")

    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    # the TPU runtime otherwise writes its logs to a fixed directory outside
    # the checkout, which two runs side by side would share
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import harness
    cell = harness.load_cell(args.workload, ROOT)
    if harness.tpu_devices(cell["chips"], ROOT) is None:
        print(f"bench: {args.workload} needs {cell['chips']} TPU chip(s); "
              f"JAX found none or fewer", file=sys.stderr)
        return 2
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         T_START, os.path.join(ROOT, ".bench_trace"),
                         log=lambda m: print(m, flush=True))
    print(f"correct {result['correct']}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
