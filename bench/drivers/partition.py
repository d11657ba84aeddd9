"""Traffic kind ``partition``: whole cold partitions, back to back.

Parameters of a mix (``bench/traffic/<mix>.json``):

``jobs``      the number of starting labellings in the set;
``job_seed``  the seed of the set: the graph, each job's uniform random
              labelling and the program's seed are drawn from it, so that
              every run does the same work.

A partition halts when its score stops improving, and from one random start
to the next that takes 13 to 27 iterations on graph500-s20-k64: a set drawn
from ``--seed`` moved ``partition_s`` by 12% from seed to seed on a TPU v5e,
more than the changes the cells are there to see.  ``--seed`` orders the
jobs of each pass; the window runs whole passes over the set until
``seconds`` have passed.  A change to the program that draws other random
numbers halts these starts at other iterations: read ``partition_s``
together with ``lpa_iterations.partition``.

Every partition of the window is compared with one reference run from the
first job's labelling (``check``).
"""
from __future__ import annotations

import time
import types

import numpy as np

import base
import reference


class Driver(base.Driver):

    def __init__(self, config: dict, traffic: dict, seed: int):
        super().__init__(config, int(traffic["job_seed"]))
        self.order = np.random.default_rng(seed)
        self.jobs = int(traffic["jobs"])

    def block_labels(self) -> np.ndarray:
        """Vertex ``v`` on part ``v * k // n``: a balanced start that a
        lattice-ordered graph leaves in few iterations.  Set-up partitions
        start here, so that warming the partition program costs less than
        a cold partition; the program and its shapes are the same."""
        return (np.arange(self.n, dtype=np.int64) * self.k
                // self.n).astype(np.int32)

    def random_labels(self, index: int) -> np.ndarray:
        """The ``index``-th uniform random labelling of the set."""
        return self.rng(1, index).integers(0, self.k, self.n,
                                           dtype=np.int32)

    def partition(self, init: np.ndarray):
        with base.span("partition"):
            return self.session.partition(init=init, record_history=False)

    def setup(self) -> None:
        self.open()
        self.partition(self.block_labels())             # compiles, warms

    def window(self, seconds: float) -> None:
        self.runs = []
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            for job in self.order.permutation(self.jobs).tolist():
                with base.span("init_labels"):
                    init = self.random_labels(1 + job)
                self.runs.append(self.partition(init))
        elapsed = time.perf_counter() - t0
        self.end_to_end["partition_s"] = elapsed / len(self.runs)
        self.records.update(
            iterations=[r.iterations for r in self.runs],
            num_vertices=self.n, num_entries=self.num_entries, k=self.k)

    def check(self) -> list:
        """Per partition of the window: ``loads_gap``, the largest gap
        between the loads it reports and the reference's recount from its
        labels; ``not_halted``, 1 if it ran out of iterations; and its gaps
        to one reference run from the first job's labelling --
        ``phi_rel_gap`` (the share of local edges, relative),
        ``rho_gap`` (largest load over the ideal one) and ``iter_gap``
        (iterations, relative)."""
        g = self.ref_graph()
        ref_labels, _, ref_it, _ = self.ref_lpa(g, self.random_labels(1))
        ref_phi = reference.phi(g, ref_labels)
        ref_rho = reference.rho(g.deg, ref_labels, self.k)
        per_run, phis, rhos = [], [], []
        for r in self.runs:
            lab = np.asarray(r.labels)
            if lab.shape != (self.n,) or lab.min() < 0 or lab.max() >= self.k:
                per_run.append({"loads_gap": float("inf")})
                continue
            phi = reference.phi(g, lab)
            rho = reference.rho(g.deg, lab, self.k)
            gap = np.abs(r.loads[:self.k] - reference.loads(g.deg, lab,
                                                             self.k))
            per_run.append({"loads_gap": float(gap.max()),
                            "not_halted": 0.0 if r.halted else 1.0,
                            "phi_rel_gap": abs(phi - ref_phi) / ref_phi,
                            "rho_gap": abs(rho - ref_rho),
                            "iter_gap": abs(r.iterations - ref_it) / ref_it})
            phis.append(phi)
            rhos.append(rho)
        self.records.update(phi=phis, rho=rhos, reference={
            "phi": ref_phi, "rho": ref_rho, "iterations": ref_it})
        return per_run


# The control and the planted faults (``controls.py``): each returns
# ``(owner, attribute, replacement)`` to patch for the length of a run.

def _result(labels, loads, iterations, halted=True):
    return types.SimpleNamespace(labels=labels, loads=loads,
                                 iterations=iterations, halted=halted)


def control():
    """The reference put in the program's place with the configuration's
    balance guarantee broken: label propagation without the load penalty
    and the migration throttle."""
    def call(self, init):
        return _result(*self.ref_lpa(self.ref_graph(), init, balance=False))
    return Driver, "partition", call


def state_unchanged():
    """The program's call returns the labelling it started from, with its
    loads, after ``halt_window + 1`` iterations."""
    from repro.core.session import PartitionSession
    real = PartitionSession.partition

    def call(self, init=None, **kw):
        real(self, init=init, **kw)
        deg = np.asarray(self.graph.deg_w, np.float64)
        return _result(np.asarray(init), np.bincount(
            init, weights=deg, minlength=self.cfg.k),
            self.cfg.halt_window + 1)
    return PartitionSession, "partition", call


def answer_altered():
    """The label of the vertex of highest degree moves on by one where the
    answer is produced; the loads are left as they are."""
    from repro.core.session import PartitionSession
    real = PartitionSession.partition

    def call(self, init=None, **kw):
        res = real(self, init=init, **kw)
        labels = np.array(res.labels)
        v = int(np.argmax(self.graph.deg_w))
        labels[v] = (labels[v] + 1) % self.cfg.k
        return _result(labels, res.loads, res.iterations)
    return PartitionSession, "partition", call


PLANTS = {"control": control, "state_unchanged": state_unchanged,
          "answer_altered": answer_altered}
