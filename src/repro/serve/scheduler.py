"""PartitionScheduler: many PartitionSessions behind one request queue.

Spinner frames partitioning as a continuously running cloud service
(§ dynamicity); this module is that serving tier.  One scheduler holds
many independent tenants (graph + ``PartitionSession``) and drains a
stream of ``partition`` / ``edge_updates`` / ``adapt`` / ``resize``
requests through three performance layers:

1. **Delta coalescing** (``core.delta.coalesce_updates``): each dispatch
   round pops a tenant's leading run of queued edge-update requests (plus
   at most one trailing plain ``adapt``) as ONE window; the coalesced
   delta folds through a single ``apply_delta`` scatter and one
   reconvergence.  ``coalesce_updates`` preserves Eq. 3's
   direction-canonicalized pair weights exactly, so every ticket in the
   window resolves to the same bit-identical result a one-by-one replay
   would reach.

2. **Same-bucket batched execution** (``engine.run_batched``): windows
   from tenants whose padded (V, E) buckets, config statics and backend
   signatures match (``engine.batch_signature``) are stacked along a
   leading batch dimension and run as ONE ``vmap``'d while_loop dispatch.
   Per-element freezing keeps every tenant's trajectory bit-identical to
   its own unbatched program; ineligible windows (``partition``,
   ``resize``, rebinds, frontier adapts, sharded/chunked/host/Pallas
   sessions) fall back to serial dispatch through the session's own
   entry points, so correctness never depends on batch eligibility.

3. **Policy-driven prefetch**: between dispatching a batch and blocking
   on its results (JAX dispatch is asynchronous), the scheduler runs its
   policies off the critical path -- :class:`StagePrefetch` double-buffers
   the next queued snapshot rebind (PR 5's ``stage()`` as a policy) and
   :class:`KSweepPrecompile` speculatively compiles fused programs for
   queued ``resize`` targets by invoking them on a pre-halted state
   (full compile, ~zero execution).

Dispatch order is priority-weighted staleness (age of the tenant's
oldest queued request x tenant priority), with an optional hard
``preempt_staleness`` SLO that jumps an aging tenant to the front of
the round regardless of priority.

::

    from repro.serve import PartitionScheduler

    sched = PartitionScheduler(max_batch=8)
    sched.add_tenant("social", g1, SpinnerConfig(k=16), partition=True)
    sched.add_tenant("web", g2, SpinnerConfig(k=16), partition=True)
    t = sched.submit("social", "edge_updates", edge_updates=(src, dst))
    sched.drain()
    assert t.done and t.result.halted
    print(sched.stats()["coalescing_factor"])
"""
from __future__ import annotations

import dataclasses
import logging
import os
import time
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import delta as _delta
from repro.core import engine as _engine
from repro.core.graph import Graph
from repro.core.session import PartitionSession
from repro.core.spinner import SpinnerConfig

from .requests import KINDS, Tenant, Ticket

_log = logging.getLogger(__name__)


class _Work(NamedTuple):
    """A prepared batchable window: the session's work item + its
    stackability signature."""

    state: object
    bind: object
    cfg: object
    opts: object
    sig: tuple


class StagePrefetch:
    """Warm the NEXT queued snapshot rebind off the critical path.

    When a tenant's head-of-queue request is an ``adapt(new_graph=...)``,
    stage the snapshot now: ``PartitionSession.stage`` issues the padded
    view's host->device uploads asynchronously, so they overlap the
    in-flight batch and the eventual serial dispatch starts from
    device-resident arrays (PR 5's double buffering, scheduler-driven)."""

    name = "stage_prefetch"

    def __init__(self) -> None:
        self.staged = 0

    def run(self, sched: "PartitionScheduler") -> None:
        for t in sched.tenants.values():
            if not t.queue:
                continue
            tk = t.queue[0]
            g = tk.payload.get("new_graph")
            if g is None or tk.payload.get("_staged"):
                continue
            t.session.stage(g)
            tk.payload["_staged"] = True
            self.staged += 1
            return                    # one staging per round

    def stats(self) -> dict:
        return {"staged": self.staged}


class KSweepPrecompile:
    """Speculatively compile fused programs for queued ``resize`` targets.

    Scans the queues for resize requests and, once per (tenant, k),
    builds the new-k program and invokes it with a pre-halted state: the
    while_loop's cond is False on entry, so the call costs a full XLA
    compile and essentially zero execution.  By the time the resize
    reaches the head of the queue its dispatch is compile-free -- the
    k-sweep prefetch follow-on as a scheduler policy."""

    name = "ksweep_precompile"

    def __init__(self) -> None:
        self.warmed: set = set()
        self.compiled = 0

    def run(self, sched: "PartitionScheduler") -> None:
        for t in sched.tenants.values():
            for tk in t.queue:
                if tk.kind != "resize":
                    continue
                key = (t.name, tk.payload["k"])
                if key in self.warmed:
                    continue
                self.warmed.add(key)
                self.compiled += self._warm(sched, t, tk.payload["k"])
                return                # one warm compile per round
        return

    def _warm(self, sched: "PartitionScheduler", t: Tenant,
              k_new: int) -> int:
        sess = t.session
        if not sess.batchable():      # fused single-device programs only
            return 0
        graph = sess._graph           # base graph: shapes only
        cfg_new = dataclasses.replace(sess.cfg, k=k_new)
        opts_t = _engine._autotuned(graph, cfg_new, sess.options)
        bind, padded = _engine._single_bind(graph, cfg_new, opts_t)
        prog = _engine._fused_program(cfg_new, opts_t)
        sched._track(prog)
        sess._track(prog)
        before = prog.compiles()
        state = _engine.init_state(
            jnp.zeros((padded.num_vertices,), jnp.int32),
            jnp.zeros((k_new,), jnp.float32),
            jax.random.PRNGKey(0))._replace(halted=jnp.asarray(True))
        prog.run(state, bind)         # cond False on entry: compile only
        return prog.compiles() - before

    def stats(self) -> dict:
        return {"warmed": len(self.warmed), "compiled": self.compiled}


def default_policies() -> tuple:
    return (StagePrefetch(), KSweepPrecompile())


def default_batch_min() -> int:
    """Smallest same-bucket group worth stacking on THIS host.

    A vmapped while_loop iteration does ``nb`` lanes of work and runs
    until the slowest lane halts, so stacking only pays where the lanes
    execute in parallel -- an accelerator, or a multicore CPU host.  On
    a single-core CPU host it is strictly extra work, so the scheduler
    defaults to delta coalescing + serial dispatch there; pass
    ``batch_min`` explicitly to force either path.
    """
    try:
        cores = os.cpu_count() or 1
    except Exception:
        cores = 1
    if cores > 1:
        return 2
    try:
        platform = jax.devices()[0].platform
    except Exception:
        platform = "cpu"
    return 2 if platform != "cpu" else 10 ** 9


class PartitionScheduler:
    """Multi-tenant serving loop over :class:`PartitionSession`\\ s.

    ``max_batch`` bounds how many tenant windows one round dispatches
    (and therefore the widest stacked batch); ``batch_min`` is the
    smallest group that takes the batched runner -- below it a window
    runs through the session's own (already warm) unbatched program,
    which avoids tracing a batch-of-1 program for lone tenants (tests
    set ``batch_min=1`` to force the batch-of-1 path).  It defaults to
    :func:`default_batch_min`: 2 where the host has parallel lanes to
    run stacked work (multicore / accelerator), effectively-off on a
    single-core CPU host where stacking is strictly extra work.
    ``clock`` is injectable for deterministic tests.
    """

    def __init__(self, *, max_batch: int = 8,
                 batch_min: Optional[int] = None,
                 preempt_staleness: Optional[float] = None,
                 policies: Optional[Sequence] = None,
                 deployment=None,
                 clock: Callable[[], float] = time.monotonic) -> None:
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        self.max_batch = max_batch
        # cluster deployment mode (repro.cluster.deploy.ClusterDeployment):
        # tenants pinned to the deployment mesh, snapshotted on commit,
        # recovered + retried once on dispatch failure
        self.deployment = deployment
        self._recoveries = 0
        self.batch_min = max(1, default_batch_min() if batch_min is None
                             else batch_min)
        self.preempt_staleness = preempt_staleness
        self.policies = tuple(default_policies() if policies is None
                              else policies)
        self.clock = clock
        self.tenants: Dict[str, Tenant] = {}
        self._seq = 0
        self._programs: dict = {}     # id(program) -> (program, base)
        self._mark = 0
        self._submitted = 0
        self._completed = 0
        self._errors = 0
        self._eu_folded = 0           # edge-update tickets folded ...
        self._delta_dispatches = 0    # ... into this many dispatches
        self._batched_dispatches = 0
        self._serial_dispatches = 0
        self._occupancy: List[float] = []
        self._batch_sizes: List[int] = []
        self._latencies: Dict[str, List[float]] = {}
        self._policy_errors: List[str] = []
        self._first_arrival: Optional[float] = None
        self._last_finish: Optional[float] = None

    # -- tenant lifecycle --------------------------------------------------

    def add_tenant(self, name: str, graph: Graph, cfg: SpinnerConfig,
                   options: Optional[_engine.EngineOptions] = None, *,
                   priority: float = 1.0,
                   partition: bool = False) -> Tenant:
        """Admit a tenant.  ``partition=True`` runs the cold first
        partition synchronously on admission (upload + compile paid
        here, not inside the serving loop); otherwise the tenant's
        first request must be ``partition``."""
        if name in self.tenants:
            raise ValueError(f"tenant {name!r} already registered")
        if self.deployment is not None:
            options = self.deployment.admit(name, options)
        t = Tenant(name=name,
                   session=PartitionSession(graph, cfg, options),
                   priority=float(priority))
        self.tenants[name] = t
        if partition:
            t.session.partition(record_history=False)
        return t

    def remove_tenant(self, name: str) -> None:
        """Retire a tenant: fail its queued tickets, close its session
        (idempotent), fold its compile history into the scheduler's."""
        t = self.tenants.pop(name)
        now = self.clock()
        err = RuntimeError(f"tenant {name!r} retired with requests queued")
        while t.queue:
            tk = t.queue.popleft()
            tk.done, tk.error, tk.finish = True, err, now
            self._errors += 1
        # keep compile accounting stable across retirement
        for pid, (prog, base) in t.session._programs.items():
            have = self._programs.get(pid)
            if have is None or have[1] > base:
                self._programs[pid] = (prog, base)
        t.session.close()

    # -- admission ---------------------------------------------------------

    def submit(self, tenant: str, kind: str, *, edge_updates=None,
               new_graph: Optional[Graph] = None, k: Optional[int] = None,
               frontier: bool = False,
               arrival: Optional[float] = None) -> Ticket:
        """Enqueue one request; returns its :class:`Ticket` (resolved in
        place by a later ``step``/``drain``)."""
        if kind not in KINDS:
            raise ValueError(f"unknown request kind {kind!r}; "
                             f"available: {', '.join(KINDS)}")
        t = self.tenants[tenant]
        payload: dict = {}
        if kind == "edge_updates":
            if edge_updates is None:
                raise ValueError("edge_updates request needs "
                                 "edge_updates=(src, dst)")
            payload["edge_updates"] = edge_updates
        elif kind == "resize":
            if k is None:
                raise ValueError("resize request needs k=")
            payload["k"] = int(k)
        elif kind == "adapt":
            if new_graph is not None:
                payload["new_graph"] = new_graph
            if frontier:
                payload["frontier"] = True
        now = self.clock() if arrival is None else arrival
        tk = Ticket(tenant=tenant, kind=kind, seq=self._seq, arrival=now,
                    payload=payload)
        self._seq += 1
        self._submitted += 1
        if self._first_arrival is None:
            self._first_arrival = now
        t.queue.append(tk)
        return tk

    # -- the dispatch loop -------------------------------------------------

    def step(self) -> int:
        """One dispatch round; returns the number of requests completed.

        Picks up to ``max_batch`` tenant windows by priority-weighted
        staleness, groups the batchable ones by stack signature, runs
        each group as one batched device dispatch (serial fallbacks and
        sub-``batch_min`` groups through the sessions' own programs),
        runs the prefetch policies while the batch is in flight, then
        materializes results and resolves every ticket in each window.
        """
        now = self.clock()
        ready = [t for t in self.tenants.values() if t.queue]
        if not ready:
            return 0
        ready.sort(key=lambda t: self._rank(t, now))
        take = ready[: self.max_batch]

        groups: Dict[tuple, list] = {}
        serial: list = []
        completed = 0
        for t in take:
            window = t.next_window()
            n_eu = sum(1 for tk in window if tk.kind == "edge_updates")
            if n_eu:
                self._eu_folded += n_eu
                self._delta_dispatches += 1
            try:
                work = self._prepare(t, window)
            except Exception as e:              # bad request: fail tickets
                completed += self._fail(t, window, e)
                continue
            if work is None:
                serial.append((t, window))
            else:
                groups.setdefault(work.sig, []).append((t, window, work))

        pending: list = []   # (tenant, window, out_state)
        for group in groups.values():
            if len(group) < self.batch_min:
                for t, window, work in group:
                    prog = _engine._fused_program(work.cfg, work.opts)
                    self._track(prog)
                    t.session._track(prog)
                    t.serial_dispatches += 1
                    self._serial_dispatches += 1
                    pending.append((t, window, prog.run(work.state,
                                                        work.bind)))
                continue
            items = [(w.state, w.bind) for _, _, w in group]

            def on_program(prog, group=group):
                self._track(prog)
                for t, _, _ in group:
                    t.session._track(prog)

            outs = _engine.run_batched(items, group[0][2].cfg,
                                       group[0][2].opts,
                                       on_program=on_program)
            self._batched_dispatches += 1
            self._occupancy.append(
                len(group) / _engine.batch_bucket(len(group)))
            self._batch_sizes.append(len(group))
            for (t, window, _w), out in zip(group, outs):
                t.batched_dispatches += 1
                pending.append((t, window, out))

        # the batch is dispatched but not yet materialized: prefetch now
        self._run_policies()

        for t, window, out in pending:
            try:
                completed += self._finish(t, window,
                                          t.session.commit_adapt(out))
            except Exception as e:
                completed += self._resolve_failure(t, window, e)
        for t, window in serial:
            try:
                completed += self._finish(t, window,
                                          self._dispatch_serial(t, window))
            except Exception as e:
                completed += self._resolve_failure(t, window, e)
        return completed

    def drain(self, max_rounds: Optional[int] = None) -> int:
        """Run rounds until every queue is empty; returns completions."""
        completed = 0
        rounds = 0
        while any(t.queue for t in self.tenants.values()):
            if max_rounds is not None and rounds >= max_rounds:
                break
            completed += self.step()
            rounds += 1
        return completed

    # -- internals ---------------------------------------------------------

    def _rank(self, t: Tenant, now: float) -> tuple:
        """Sort key (ascending): SLO-preempted first, then priority x
        staleness, then raw priority, then admission order."""
        stale = t.staleness(now)
        preempt = (self.preempt_staleness is not None
                   and stale >= self.preempt_staleness)
        return (not preempt, -(t.priority * stale), -t.priority,
                t.queue[0].seq)

    def _prepare(self, t: Tenant, window: List[Ticket]
                 ) -> Optional[_Work]:
        """A window's batched work item, or None for serial dispatch."""
        last = window[-1]
        if last.kind in ("partition", "resize"):
            return None
        if last.payload.get("new_graph") is not None \
                or last.payload.get("frontier"):
            return None
        if not t.session.batchable():
            return None
        eu = [tk.payload["edge_updates"] for tk in window
              if tk.kind == "edge_updates"]
        updates = _delta.coalesce_updates(eu) if eu else None
        parts = t.session.adapt_parts(edge_updates=updates)
        if parts is None:
            return None
        state, bind, cfg, opts = parts
        return _Work(state, bind, cfg, opts,
                     _engine.batch_signature(cfg, opts, bind))

    def _dispatch_serial(self, t: Tenant, window: List[Ticket]):
        """Run a non-batchable window through the session's own entry
        points (still coalesced: one adapt per window)."""
        sess = t.session
        last = window[-1]
        t.serial_dispatches += 1
        self._serial_dispatches += 1
        if last.kind == "partition":
            return sess.partition(record_history=False)
        if last.kind == "resize":
            return sess.resize(last.payload["k"], record_history=False)
        kw: dict = {"record_history": False}
        eu = [tk.payload["edge_updates"] for tk in window
              if tk.kind == "edge_updates"]
        if eu:
            kw["edge_updates"] = _delta.coalesce_updates(eu)
        if last.kind == "adapt":
            if last.payload.get("new_graph") is not None:
                kw["new_graph"] = last.payload["new_graph"]
            if last.payload.get("frontier"):
                kw["frontier"] = True
        return sess.adapt(**kw)

    def _run_policies(self) -> None:
        for p in self.policies:
            try:
                p.run(self)
            except Exception as e:    # prefetch must never fail serving
                name = getattr(p, "name", type(p).__name__)
                _log.exception("scheduler policy %s failed", name)
                self._policy_errors.append(f"{name}: {e!r}")

    def _finish(self, t: Tenant, window: List[Ticket], res) -> int:
        now = self.clock()
        for tk in window:
            tk.done, tk.result, tk.finish = True, res, now
            tk.coalesced = len(window)
            self._latencies.setdefault(tk.kind, []).append(tk.latency())
        t.completed += len(window)
        self._completed += len(window)
        self._last_finish = now
        if self.deployment is not None:
            self.deployment.after_commit(t.name, t.session)
        return len(window)

    def _resolve_failure(self, t: Tenant, window: List[Ticket],
                         err: BaseException) -> int:
        """A dispatch raised: under a cluster deployment, recover the
        tenant from its newest snapshot and retry the window ONCE;
        otherwise (or when recovery itself cannot proceed) fail the
        tickets.  The recovery graph is the failed session's
        materialized logical graph -- base plus every accepted delta
        batch, INCLUDING this window's (``adapt_parts``/``adapt``
        append to the pending log before dispatching) -- so the retry
        is a plain reconvergence: re-applying the window's
        edge-updates would double-count them.  A resize committed after
        the newest snapshot is rolled forward by ``recover`` (skipped
        when the retried window is itself a resize, which sets k)."""
        if self.deployment is None:
            return self._fail(t, window, err)
        try:
            graph = t.session.graph       # materializes the delta log
            info = self.deployment.recover(
                t.name, graph, options=t.session.options,
                roll_forward_k=window[-1].kind != "resize")
            if info is None:              # no snapshot yet: fail normally
                return self._fail(t, window, err)
            old, t.session = t.session, info.session
            old.close()
            self._recoveries += 1
            last = window[-1]
            t.serial_dispatches += 1
            self._serial_dispatches += 1
            if last.kind == "partition":
                res = t.session.partition(record_history=False)
            elif last.kind == "resize":
                res = t.session.resize(last.payload["k"],
                                       record_history=False)
            else:
                kw: dict = {"record_history": False}
                if last.payload.get("new_graph") is not None:
                    kw["new_graph"] = last.payload["new_graph"]
                res = t.session.adapt(**kw)
            return self._finish(t, window, res)
        except Exception as e:
            return self._fail(t, window, e)

    def _fail(self, t: Tenant, window: List[Ticket],
              err: BaseException) -> int:
        now = self.clock()
        for tk in window:
            tk.done, tk.error, tk.finish = True, err, now
        t.failed += len(window)
        self._errors += len(window)
        return len(window)

    # -- compile tracking / stats -----------------------------------------

    def _track(self, program) -> None:
        if program is not None and id(program) not in self._programs:
            self._programs[id(program)] = (program, program.compiles())

    @property
    def compiles(self) -> int:
        """Compilations this scheduler's serving caused: union of its own
        tracked programs and every live session's, earliest-acquisition
        base, each program counted once however many tenants share it."""
        progs = dict(self._programs)
        for t in self.tenants.values():
            for pid, (prog, base) in t.session._programs.items():
                have = progs.get(pid)
                if have is None or have[1] > base:
                    progs[pid] = (prog, base)
        return sum(max(0, prog.compiles() - base)
                   for prog, base in progs.values())

    def mark(self) -> None:
        """Snapshot the compile counter; ``stats()["compiles_since_mark"]``
        then measures steady-state compiles (0 for a warm fleet)."""
        self._mark = self.compiles

    def stats(self) -> dict:
        """Serving metrics: latency percentiles, throughput, coalescing
        factor, batch occupancy, compile counters, per-policy stats."""

        def pct(xs: List[float], q: float) -> float:
            if not xs:
                return float("nan")
            ys = sorted(xs)
            return ys[min(int(q * len(ys)), len(ys) - 1)]

        def summary(xs: List[float]) -> dict:
            return {"p50": pct(xs, 0.50), "p99": pct(xs, 0.99),
                    "mean": float(np.mean(xs)) if xs else float("nan"),
                    "count": len(xs)}

        lat_all = [x for xs in self._latencies.values() for x in xs]
        lat_adapt = (self._latencies.get("edge_updates", [])
                     + self._latencies.get("adapt", []))
        span = ((self._last_finish - self._first_arrival)
                if self._last_finish is not None
                and self._first_arrival is not None else 0.0)
        return {
            "tenants": len(self.tenants),
            "submitted": self._submitted,
            "completed": self._completed,
            "errors": self._errors,
            "queued": sum(len(t.queue) for t in self.tenants.values()),
            "throughput_rps": (self._completed / span if span > 0
                               else float("nan")),
            "latency": summary(lat_all),
            "adapt_latency": summary(lat_adapt),
            "coalescing_factor": (self._eu_folded
                                  / max(self._delta_dispatches, 1)),
            "batched_dispatches": self._batched_dispatches,
            "serial_dispatches": self._serial_dispatches,
            "batch_occupancy": (float(np.mean(self._occupancy))
                                if self._occupancy else 0.0),
            "mean_batch_size": (float(np.mean(self._batch_sizes))
                                if self._batch_sizes else 0.0),
            "compiles": self.compiles,
            "compiles_since_mark": self.compiles - self._mark,
            "policies": {getattr(p, "name", type(p).__name__):
                         (p.stats() if hasattr(p, "stats") else {})
                         for p in self.policies},
            "policy_errors": list(self._policy_errors),
            "recoveries": self._recoveries,
            "deployment": (self.deployment.stats()
                           if self.deployment is not None else None),
        }
