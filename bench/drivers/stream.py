"""Traffic kind ``stream``: a graph kept partitioned while arcs arrive in
batches (Spinner's incremental mode, arXiv:1404.3861 Section 3.4).

Parameters of a mix (``bench/traffic/<mix>.json``): ``job_seed``, the
seed of the graph, the cold start and the stream, so that every run does
the same work.  The configuration gives the stream:

``batches``            batches a pass streams;
``batch_arcs``         arcs in a batch;
``follow_back_share``  the share of a batch that reverses a one-way arc
                       present at that point, uniformly drawn (the pair
                       goes from weight 1 to 2);
``repeat_share``       the share that gives again an arc already present
                       (at-least-once redelivery: half of them drawn from
                       the arcs streamed earlier in the pass, where there
                       are any, the rest from all arcs present);

the rest of a batch are held-back arcs: a seeded uniform sample of the
generated arcs, ``batches`` times that many, taken out of the base graph
and streamed back.

A pass opens a session on the prebuilt base graph, partitions it cold
from one fixed uniform random labelling, then hands the batches over one
``adapt(edge_updates=batch)`` at a time, each when the previous one has
returned its labels (a closed loop, frontier unset: the full warm
restart), and closes the session.  Every pass streams the same batches;
``--seed`` orders the arcs inside each batch, which the ledger's answer
does not depend on.  The window runs whole passes.

``check`` compares every call with ``reference.py`` over the base graph
and the arcs streamed so far (Eq. 3 over their union).
"""
from __future__ import annotations

import time
import types

import numpy as np

import base
import reference


def require_arc_ledger() -> None:
    """The configuration's weights need a program that records which arcs
    of a pair were given (``Graph.dirs``); a program without that record
    cannot hold them, and the run ends here, before any work."""
    from repro.core import from_edges
    if getattr(from_edges([0], [1], 2), "dirs", None) is None:
        raise SystemExit(
            "stream: the program records no arc directions (Graph.dirs), "
            "so it cannot weigh a stream by Eq. 3 over the union of the "
            "arcs given; it cannot hold this configuration's guarantees")


class Driver(base.Driver):

    def __init__(self, config: dict, traffic: dict, seed: int):
        super().__init__(config, int(traffic["job_seed"]))
        self.order = np.random.default_rng(seed)
        self.batches_n = int(config["batches"])
        arcs = int(config["batch_arcs"])
        self.follow_n = int(round(arcs * config["follow_back_share"]))
        self.repeat_n = int(round(arcs * config["repeat_share"]))
        self.held_n = arcs - self.follow_n - self.repeat_n
        self._union: dict = {}

    # -- the graph and its stream ------------------------------------------

    def open(self) -> None:
        """The generated arcs less the held-back sample, built into the
        base graph once; the stream's batches."""
        from repro.core import from_edges
        with base.span("graph_build"):
            n, src, dst, directed = base.find(
                "generators", self.config["generator"]).build(
                    self.config, self.rng(0))
            if not directed:
                raise ValueError("a stream of arcs needs a directed graph")
            self.n, self.directed = n, True
            keys = np.unique(src.astype(np.int64) * n + dst)
            keys = keys[keys // n != keys % n]
            rng = self.rng(2)
            held = rng.choice(keys.size, self.batches_n * self.held_n,
                              replace=False)
            base_keys = np.delete(keys, held)
            self.src, self.dst = base_keys // n, base_keys % n
            self.graph = from_edges(self.src, self.dst, n)
            self.batches = self._stream(base_keys, keys[held], rng)
        self.num_entries = self.graph.num_directed_entries

    def _stream(self, present: np.ndarray, held: np.ndarray, rng) -> list:
        """The batches, as arc keys ``src * n + dst``: ``present`` holds
        the base graph's (sorted), ``held`` the held-back sample."""
        n = self.n
        rev = lambda k: (k % n) * n + k // n  # noqa: E731
        pair = lambda k: np.minimum(k // n, k % n) * n + np.maximum(  # noqa
            k // n, k % n)

        def has(sorted_keys, k):
            if not sorted_keys.size:
                return np.zeros(k.shape, bool)
            pos = np.minimum(np.searchsorted(sorted_keys, k),
                             sorted_keys.size - 1)
            return sorted_keys[pos] == k

        one_way = present[~has(present, rev(present))]
        one_way_pair, one_way_rev = pair(one_way), rev(one_way)
        chunks = np.split(held, self.batches_n)
        streamed = np.zeros(0, np.int64)       # held-back and follow-backs
        followed = np.zeros(0, np.int64)       # pairs made two-way
        batches = []
        for i, new in enumerate(chunks):
            done = np.unique(streamed)
            # one-way arcs present now, on no pair that a held-back arc
            # still to come (or an earlier follow-back) makes two-way
            block = np.union1d(pair(np.concatenate(chunks[i:])), followed)
            cand = np.concatenate([
                one_way[~has(block, one_way_pair) & ~has(done, one_way_rev)],
                done[~has(present, rev(done)) & ~has(done, rev(done))
                     & ~has(block, pair(done))]])
            follow = rev(rng.choice(cand, self.follow_n, replace=False))
            followed = np.union1d(followed, pair(follow))
            recent = self.repeat_n // 2 if done.size else 0
            repeat = np.concatenate([
                rng.choice(done, recent, replace=False),
                rng.choice(np.concatenate([present, done]),
                           self.repeat_n - recent, replace=False)])
            batches.append(rng.permutation(np.concatenate([new, follow,
                                                           repeat])))
            streamed = np.concatenate([streamed, new, follow])
        out = []
        for b in batches:
            b = b[self.order.permutation(b.size)]
            out.append((b // n, b % n))
        return out

    def union_ref(self, given: int) -> reference.RefGraph:
        """The reference graph of the base and the first ``given``
        batches."""
        if given not in self._union:
            if given == 0:
                self._union[0] = self.ref_graph()
            else:
                bs = self.batches[:given]
                self._union[given] = reference.RefGraph(
                    self.n, np.concatenate([self.src] + [b[0] for b in bs]),
                    np.concatenate([self.dst] + [b[1] for b in bs]), True)
        return self._union[given]

    def start_labels(self) -> np.ndarray:
        """The cold partition's fixed uniform random labelling."""
        return self.rng(1, 1).integers(0, self.k, self.n, dtype=np.int32)

    def block_labels(self) -> np.ndarray:
        return (np.arange(self.n, dtype=np.int64) * self.k
                // self.n).astype(np.int32)

    # -- the calls ---------------------------------------------------------

    def call(self, kind: str, arg):
        """One call into the system: ``partition`` from labels ``arg`` or
        ``adapt`` with the batch ``arg``, in its ``bench/<kind>`` span."""
        with base.span(kind):
            if kind == "partition":
                return self.session.partition(init=arg, record_history=False)
            return self.session.adapt(edge_updates=arg, record_history=False)

    def run_pass(self) -> dict:
        from repro.core import open_session
        with base.span("open"):
            self.session = open_session(
                self.graph, base.spinner_config(self.config, self.seed))
        self.given, self.last = 0, self.start_labels()
        cold = self.call("partition", self.last)
        self.last = np.asarray(cold.labels)
        adapts, seconds, entries = [], [], []
        for _ in self.batches:
            self.given += 1
            t = time.perf_counter()
            res = self.call("adapt", self.batches[self.given - 1])
            seconds.append(time.perf_counter() - t)
            self.last = np.asarray(res.labels)
            adapts.append(res)
            d = self.session.stats()["delta"]
            entries.append(self.num_entries + 2 * d.get("pairs_changed", 0))
        return {"cold": cold, "adapts": adapts, "seconds": seconds,
                "entries": entries, "delta": self.session.stats()["delta"]}

    def setup(self) -> None:
        from repro.core import open_session
        require_arc_ledger()
        self.open()
        # every shape of the window, on a session the window does not
        # reuse: the partition program, and an adapt's ledger build, merge
        # at the batch bucket and forward score pass
        with open_session(self.graph, base.spinner_config(
                self.config, self.seed)) as warm:
            warm.partition(init=self.block_labels(), record_history=False)
            warm.adapt(edge_updates=self.batches[0], record_history=False)

    def window(self, seconds: float) -> None:
        self.passes = []
        t0 = time.perf_counter()
        while True:
            self.passes.append(self.run_pass())
            if time.perf_counter() - t0 >= seconds:
                break                 # the last pass's session stays open
            with base.span("close"):
                self.session.close()
        adapt_s = [s for p in self.passes for s in p["seconds"]]
        self.end_to_end["partition_s"] = sum(adapt_s) / len(adapt_s)
        counters = ("fast_adapts", "fallback_adapts", "arcs_given",
                    "pairs_changed", "pairs_reciprocated",
                    "arcs_redelivered")
        self.records.update(
            adapt_iterations=[r.iterations for p in self.passes
                              for r in p["adapts"]],
            partition_iterations=[p["cold"].iterations
                                  for p in self.passes],
            adapt_s=adapt_s,
            adapt_entries=[e for p in self.passes for e in p["entries"]],
            delta={c: sum(p["delta"].get(c, 0) for p in self.passes)
                   for c in counters},
            num_vertices=self.n, num_entries=self.num_entries, k=self.k)

    def release(self) -> None:
        """The last pass's session materializes its host graph (the base
        and every batch it merged) before it is closed."""
        if self.session is not None:
            self.final_deg = np.asarray(self.session.graph.deg_w)
        super().release()

    def changed_pairs(self, given: int) -> tuple:
        """The pairs whose Eq. 3 weight batch ``given`` changes (an arc of
        it not given before), as ``(lo, hi)``."""
        n = self.n
        key = lambda s, d: s.astype(np.int64) * n + d  # noqa: E731
        before = np.concatenate([key(self.src, self.dst)] + [
            key(*b) for b in self.batches[:given - 1]])
        new = np.setdiff1d(key(*self.batches[given - 1]), before)
        lo, hi = np.minimum(new // n, new % n), np.maximum(new // n, new % n)
        pairs = np.unique(lo[lo != hi] * n + hi[lo != hi])
        return pairs // n, pairs % n

    def check(self) -> list:
        """Per call: ``loads_gap``, the largest gap between the loads it
        reports and the reference's recount from its labels over the base
        and the arcs streamed so far, and ``not_halted``.  The cold
        partitions add their gaps to a reference run from the same start:
        ``phi_rel_gap`` (the share of local edges, relative) and
        ``rho_gap`` (largest load over the ideal one).  Each adapt adds
        the same gaps to the reference restarted from the labels the adapt
        was given, on the grown graph (Section 3.4's step), and two that
        look at what the step did: ``label_gap``, the vertices whose label
        differs from the restart's, over the vertices the restart moved;
        ``batch_phi_gap``, the gap in the share of local pairs among the
        pairs the batch changed.  The last answer adds ``deg_gap``, the
        largest gap between the weighted degrees of the session's host
        graph after the last pass and the reference's."""
        g0 = self.union_ref(0)
        ref_cold = self.ref_lpa(g0, self.start_labels())[0]
        restarts: dict = {}
        per_call, phis = [], []

        def answer(res, g) -> dict:
            lab = np.asarray(res.labels)
            if lab.shape != (self.n,) or lab.min() < 0 or lab.max() >= self.k:
                return {"loads_gap": float("inf")}
            gap = np.abs(np.asarray(res.loads)[:self.k]
                         - reference.loads(g.deg, lab, self.k))
            return {"loads_gap": float(gap.max()),
                    "not_halted": 0.0 if res.halted else 1.0}

        def gaps(a: dict, lab, ref_lab, g) -> None:
            phi, ref_phi = reference.phi(g, lab), reference.phi(g, ref_lab)
            a.update(phi_rel_gap=abs(phi - ref_phi) / ref_phi,
                     rho_gap=abs(reference.rho(g.deg, lab, self.k)
                                 - reference.rho(g.deg, ref_lab, self.k)))
            phis.append(phi)

        def step(a: dict, given: int, prev, lab, ref_lab) -> None:
            lo, hi = self.changed_pairs(given)
            moved = np.count_nonzero(ref_lab != prev)
            a.update(
                label_gap=np.count_nonzero(lab != ref_lab) / max(moved, 1),
                batch_phi_gap=abs(np.mean(lab[lo] == lab[hi])
                                  - np.mean(ref_lab[lo] == ref_lab[hi])))

        for p in self.passes:
            a = answer(p["cold"], g0)
            prev = np.asarray(p["cold"].labels) if "not_halted" in a else None
            if prev is not None:
                gaps(a, prev, ref_cold, g0)
            per_call.append(a)
            for i, res in enumerate(p["adapts"], start=1):
                g = self.union_ref(i)
                a = answer(res, g)
                lab = np.asarray(res.labels) if "not_halted" in a else None
                if prev is not None and lab is not None:
                    key = (i, prev.tobytes())
                    if key not in restarts:
                        restarts[key] = self.ref_lpa(g, prev)[0]
                    gaps(a, lab, restarts[key], g)
                    step(a, i, prev, lab, restarts[key])
                per_call.append(a)
                prev = lab
        deg = getattr(self, "final_deg", None)
        want = self.union_ref(len(self.batches)).deg
        per_call[-1]["deg_gap"] = (
            float(np.abs(deg - want).max()) if deg is not None
            and deg.shape == want.shape else float("inf"))
        self.records.update(phi=phis)
        return per_call


# The control and the planted faults (``controls.py``): each returns
# ``(owner, attribute, replacement)`` to patch for the length of a run.

def _result(labels, loads, iterations, halted=True):
    return types.SimpleNamespace(labels=labels, loads=loads,
                                 iterations=iterations, halted=halted)


def control():
    """The reference put in the program's place with the configuration's
    balance guarantee broken (no load penalty, no migration throttle), on
    the base graph and the arcs streamed so far."""
    def call(self, kind, arg):
        start = arg if kind == "partition" else self.last
        return _result(*self.ref_lpa(self.union_ref(self.given), start,
                                     balance=False))
    return Driver, "call", call


def state_unchanged():
    """Both calls return the state they started from: the labelling they
    were given, with its loads, after ``halt_window + 1`` iterations."""
    real = Driver.call

    def call(self, kind, arg):
        start = np.asarray(arg if kind == "partition" else self.last)
        real(self, kind, arg)
        deg = self.union_ref(self.given).deg
        return _result(start, np.bincount(start, weights=deg,
                                          minlength=self.k),
                       int(self.config["halt_window"]) + 1)
    return Driver, "call", call


def adapt_unchanged():
    """Each adapt returns the labels it was given, with their loads over
    the grown graph: the batch is taken in, the step is not run."""
    real = Driver.call

    def call(self, kind, arg):
        res = real(self, kind, arg)
        if kind == "partition":
            return res
        deg = self.union_ref(self.given).deg
        return _result(self.last, np.bincount(self.last, weights=deg,
                                              minlength=self.k),
                       res.iterations)
    return Driver, "call", call


def merge_unscored():
    """The merge adds each batch to the degrees (and so to the loads and
    the ledger) but leaves its entries out of the scores: the score pass
    keeps reading the edge arrays as they were before the batch."""
    import dataclasses
    from repro.core import delta
    real = delta.apply_batch

    def apply_batch(dd, plan, slotting, merge_run):
        out, nbytes = real(dd, plan, slotting, merge_run)
        return dataclasses.replace(
            out, score=tuple(dd.score[:3]) + tuple(out.score[3:])), nbytes
    return delta, "apply_batch", apply_batch


def answer_altered():
    """The label of the base graph's vertex of highest degree moves on by
    one where an adapt's answer is produced; the loads are left as they
    are."""
    from repro.core.session import PartitionSession
    real = PartitionSession.adapt

    def adapt(self, *a, **kw):
        res = real(self, *a, **kw)
        labels = np.array(res.labels)
        v = int(np.argmax(self._graph.deg_w))
        labels[v] = (labels[v] + 1) % self.cfg.k
        return _result(labels, res.loads, res.iterations)
    return PartitionSession, "adapt", adapt


def canonical_direction():
    """The delta ledger reads a weight-1 pair as its ``lo -> hi`` arc,
    whichever arc was given: a follow-back of a ``hi -> lo`` arc changes
    nothing, and ``hi -> lo`` given again counts as its reverse."""
    from repro.core.delta import DeltaTracker
    real = DeltaTracker._masks

    def masks(self, keys):
        m = real(self, keys)
        return np.where(m == 2, 1, m).astype(np.uint8)
    return DeltaTracker, "_masks", masks


PLANTS = {"control": control, "state_unchanged": state_unchanged,
          "answer_altered": answer_altered,
          "canonical_direction": canonical_direction,
          "adapt_unchanged": adapt_unchanged,
          "merge_unscored": merge_unscored}
