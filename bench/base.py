"""What every traffic driver shares, and how the harness finds the pieces
of a cell by name.

``find(kind, name)`` loads ``bench/<kind>/<name>.py``:

* ``bench/drivers/<driver>.py``       a kind of traffic.  A mix
                                      ``bench/traffic/<mix>.json`` names its
                                      driver under ``"driver"``; its other
                                      keys are that driver's parameters.
                                      The module exports ``Driver``, a
                                      subclass of ``base.Driver`` made as
                                      ``Driver(config, traffic, seed)``, and
                                      ``PLANTS``, the control and planted
                                      faults of ``controls.py``;
* ``bench/generators/<generator>.py``  a graph generator.  A configuration
                                      names it under ``"generator"``; its
                                      ``build(config, rng)`` returns
                                      ``(num_vertices, src, dst, directed)``;
* ``bench/metrics/<metric>.py``       one per-layer metric's ``read(run)``.

A driver runs the system through its public session API with the
program's default ``EngineOptions``, in four phases:

``setup()``      build the graph from the seed and warm every shape the
                 window uses (all of it counts as set-up);
``window(s)``    drive the system for ``s`` seconds of whole calls;
``release()``    drop every reference to the program's device state;
``check()``      compare what the window produced with ``reference.py``.

It fills ``records`` with what its per-layer metrics read, and
``end_to_end`` with the end-to-end metrics its traffic produces.  Every
call into the system runs inside a ``bench/<name>`` span (see
``trace_reduce.py``).
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import os
import sys

import numpy as np

import reference

BENCH = os.path.dirname(os.path.abspath(__file__))
SEED_MOD = 2 ** 31 - 1    # the program's PRNG seeds are 32-bit


def find(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py``, loaded once per process (a
    plant patches the class that the run then uses)."""
    key = f"bench_{kind}_" + name.replace(".", "_").replace("-", "_")
    if key in sys.modules:
        return sys.modules[key]
    path = os.path.join(BENCH, kind, name + ".py")
    if not os.path.isfile(path):
        raise KeyError(f"no {kind} {name!r}: {path} does not exist")
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    sys.modules[key] = mod
    return mod


@contextlib.contextmanager
def span(name: str):
    import jax
    with jax.profiler.TraceAnnotation(f"bench/{name}"):
        yield


def spinner_config(config: dict, seed: int):
    from repro.core import SpinnerConfig
    return SpinnerConfig(
        k=config["k"], c=config["c"], eps=config["eps"],
        halt_window=config["halt_window"], max_iters=config["max_iters"],
        seed=seed % SEED_MOD,
        migration_weighting=config["migration_weighting"],
        tie_noise=config["tie_noise"], current_bonus=config["current_bonus"])


class Driver:
    """Shared set-up: the seeded graph and a session on it."""

    def __init__(self, config: dict, seed: int):
        self.config, self.seed = config, seed
        self.k = int(config["k"])
        self.records: dict = {}
        self.end_to_end: dict = {}
        self.session = None
        self._ref = None

    def rng(self, *stream) -> np.random.Generator:
        return np.random.default_rng((self.seed, *stream))

    def open(self) -> None:
        from repro.core import from_edges, open_session
        with span("graph_build"):
            self.n, self.src, self.dst, self.directed = find(
                "generators", self.config["generator"]).build(
                    self.config, self.rng(0))
            graph = from_edges(self.src, self.dst, self.n,
                               directed=self.directed)
        self.num_entries = graph.num_directed_entries
        self.session = open_session(graph,
                                    spinner_config(self.config, self.seed))

    def release(self) -> None:
        if self.session is not None:
            self.session.close()
        self.session = None
        gc.collect()

    def ref_graph(self) -> reference.RefGraph:
        if self._ref is None:
            self._ref = reference.RefGraph(self.n, self.src, self.dst,
                                           self.directed)
        return self._ref

    def ref_lpa(self, g, init, balance=True):
        return reference.lpa(g, self.config, init,
                             seed=(self.seed + 1) % SEED_MOD,
                             balance=balance)
