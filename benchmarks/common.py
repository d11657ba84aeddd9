"""Shared benchmark utilities: graph cache, CSV/JSON emission."""
from __future__ import annotations

import json
import os
import time
from typing import Callable

import numpy as np

ARTIFACTS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "artifacts")


_GRAPH_CACHE = {}


def get_graph(name: str):
    """Graphs named in repro.configs.spinner_paper.QUALITY_GRAPHS (cached)."""
    from repro.configs.spinner_paper import QUALITY_GRAPHS
    from repro.core import generators
    if name not in _GRAPH_CACHE:
        gen, kw = QUALITY_GRAPHS[name]
        _GRAPH_CACHE[name] = getattr(generators, gen)(**kw)
    return _GRAPH_CACHE[name]


def emit(rows, artifact_name: str) -> None:
    """Print CSV rows (name,us_per_call,derived) and save the JSON artifact."""
    os.makedirs(ARTIFACTS, exist_ok=True)
    with open(os.path.join(ARTIFACTS, artifact_name + ".json"), "w") as f:
        json.dump(rows, f, indent=1, default=float)
    for r in rows:
        print(f"{r['name']},{r.get('us_per_call', 0):.1f},"
              f"{r.get('derived', '')}", flush=True)


def timed(fn: Callable, *args, repeats: int = 1, **kw):
    t0 = time.time()
    out = None
    for _ in range(repeats):
        out = fn(*args, **kw)
    dt = (time.time() - t0) / repeats
    return out, dt


def cpu_child_env(ndev: int) -> dict:
    """Environment for a benchmark child on ``ndev`` forced host devices.

    Such children measure CPU semantics (multi-device layouts on one
    host), so they pin ``JAX_PLATFORMS=cpu``: a child never claims an
    accelerator that its parent already holds.
    """
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return dict(os.environ, JAX_PLATFORMS="cpu",
                XLA_FLAGS=f"--xla_force_host_platform_device_count={ndev}",
                PYTHONPATH=os.path.join(here, "src"))


def mark_cpu(rows: list) -> list:
    """Label rows measured in a ``cpu_child_env`` child as CPU rows."""
    for r in rows:
        r["platform"] = "cpu"
        r["derived"] = "platform=cpu;" + r.get("derived", "")
    return rows


def hash_labels(v: int, k: int) -> np.ndarray:
    return (np.arange(v) * np.int64(2654435761) % k).astype(np.int32)
