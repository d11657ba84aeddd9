"""The control and the planted faults that ``correct`` must catch.

    python3 bench/controls.py --workload ws1m.partition --plant control \
        --seeds 11,12,13 --seconds 10

runs the cell at its own size, once per seed, with one plant in place, and
prints each run's compared numbers beside their limits; ``correct`` must
come out false in every run.  The benchmark's own runs never plant
anything.  ``tests/bench/test_bench_controls.py`` runs the same plants at a size
a test run holds.

Each driver (``bench/drivers/<driver>.py``) lists its plants in
``PLANTS``: ``control``, the reference put in the program's place with a
guarantee of the configuration broken or at a precision below the one the
program states; ``state_unchanged``, a call that returns the state it
started from; ``answer_altered``, an answer changed in one place where it
is produced.
"""
from __future__ import annotations

import contextlib
from unittest import mock

import base


@contextlib.contextmanager
def plant(driver: str, name: str):
    """Patch the timed path of traffic driver ``driver`` with its plant
    ``name``."""
    plants = base.find("drivers", driver).PLANTS
    if name not in plants:
        raise ValueError(f"no plant {name!r} for driver {driver!r}; have "
                         f"{', '.join(plants)}")
    owner, attr, fn = plants[name]()
    with mock.patch.object(owner, attr, fn):
        yield


def main(argv=None) -> int:
    import argparse
    import json
    import os
    import sys
    import time
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--plant", required=True,
                    help="a name in the driver's PLANTS, or none")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    bench = os.path.dirname(os.path.abspath(__file__))
    root = os.path.dirname(bench)
    sys.path[:0] = [bench, os.path.join(root, "src")]
    import harness
    cell = harness.load_cell(args.workload, root)
    if harness.tpu_devices(cell["chips"], root) is None:
        print("controls: needs a TPU", file=sys.stderr)
        return 2
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = contextlib.nullcontext() if args.plant == "none" else \
            plant(cell["traffic"]["driver"], args.plant)
        t0 = time.perf_counter()
        with ctx:
            res = harness.run(cell, seed, args.seconds, False, t0,
                              os.path.join(root, ".bench_trace"),
                              log=lambda m: None)
        print(json.dumps({"workload": args.workload, "plant": args.plant,
                          "seed": seed, "correct": res["correct"],
                          "seconds": time.perf_counter() - t0,
                          "checks": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
