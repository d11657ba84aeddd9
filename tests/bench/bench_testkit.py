"""Shared helpers of the benchmark's tests: the ``bench/`` modules on the
path, and cells cut to a size a CPU test holds."""
from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402

SEED = 3_000_000_019        # wider than 32 bits, as the driver's are


def cells() -> list:
    """Every cell of BENCHMARK.json."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


def tiny_cell(name: str, committed_limits: bool = False) -> dict:
    """The cell at its configuration's ``test_size``.  Its limits are the
    committed ones where ``committed_limits``, else those with the limits
    file's ``test_limits`` in their place: k = 64 parts of a few hundred
    vertices balance and converge less evenly than at a million."""
    cell = harness.load_cell(name, REPO)
    cell["config"].update(cell["config"]["test_size"])
    if not committed_limits:
        with open(os.path.join(BENCH, "limits", name + ".json")) as f:
            cell["limits"].update(json.load(f)["test_limits"])
    return cell


def run_tiny(cell: dict, seconds: float = 0.5, traced: bool = False,
             tmp_path=None) -> dict:
    trace_dir = str(tmp_path / "trace") if tmp_path is not None else ""
    return harness.run(cell, SEED, seconds, traced, time.perf_counter(),
                       trace_dir, log=lambda m: None)
