"""Bring-up smoke test: the partitioner's main path on a TPU, end to end.

Run from the repository root, on a machine with TPU chips:

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the sharded path on a 4-chip mesh

One process drives everything.  The graph is the paper's synthetic family
(Watts-Strogatz, Section 5.2) with 2M vertices and 32M undirected edges,
generated from ``--seed``.  One chip (default):

  1. ``open_session(graph, paper_config(k=64))`` on the XLA score backend:
     ``partition()``, then one cold and three warm ``adapt(edge_updates=)``
     calls of 4,096 new edges each.  Checks phi >= 3x the hash placement's
     phi, rho <= c, and that the warm adapts take the O(|delta|) path and
     compile nothing.
  2. The same graph with the Pallas backend and the fused vertex-update
     megakernel, compiled (not interpreted): labels and iteration count
     must equal step 1's bit for bit (integer Eq. 3 weights).
  3. ``session.run_app`` PageRank and WCC on the Spinner labels, on both
     combine backends, against the numpy oracles of ``repro.core.pregel``.

``--chips 4`` runs only the sharded engine on a 4-chip mesh at 250k
vertices (delta label exchange with overlap, against allgather without
overlap: bit-identical labels and iterations), and PageRank on that mesh
under hash and Spinner placements against the oracle.

Earlier lines report device, seconds per phase, compiles, persistent-cache
hits and peak device memory; none of them is a benchmark metric.  The last
line is one JSON object naming the device.  Any failed check exits non-zero
without printing it, and so does a process that finds no TPU.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# Watts-Strogatz (n, out-neighbours, rewiring).  Not a power of two: at
# n = 2**m the directed entries fill their edge shape bucket to within 288
# per 2**18 vertices, leaving no slack for the O(|delta|) adapt path, so
# every adapt would rebuild and recompile.  n is cut from 4,000,000 (which
# passes on one v5e in 900-966 s, too close to a 1,200 s limit) to
# 2,000,000: 64M directed entries.
N_VERTICES, K_NBRS, BETA = 2_000_000, 16, 0.3
FULL_VERTICES = 4_000_000
# The 4-chip phase runs once per change, by hand, at four times the chip
# cost per second, and its compiles outweigh its iterations: cut to
# 250,000 vertices (2M directed entries a chip).
FOUR_CHIP_VERTICES = 250_000
PR_RTOL = 1e-4                # PageRank vs the float64 oracle
K = 64
ADAPT_EDGES = 4096            # new undirected edges per adapt batch
WARM_ADAPTS = 3


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    log(f"  ok: {what}")


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileMeter:
    """Process-wide backend compiles, their seconds and persistent-cache
    hits, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.compiles, self.seconds, self.cache_hits = 0, 0.0, 0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.seconds += secs

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> tuple:
        return self.compiles, self.seconds, self.cache_hits


@contextlib.contextmanager
def phase(name: str, meter: CompileMeter):
    c0, s0, h0 = meter.snapshot()
    t0 = time.perf_counter()
    log(f"[{name}]")
    yield
    c1, s1, h1 = meter.snapshot()
    log(f"[{name}] {time.perf_counter() - t0:.2f} s; compiles {c1 - c0} "
        f"({s1 - s0:.2f} s), persistent-cache hits {h1 - h0}; "
        f"peak device bytes {device_bytes()}")


def device_bytes(stat: str = "peak_bytes_in_use") -> list:
    """One ``memory_stats()`` entry per device (None where unreported)."""
    import jax
    return [(d.memory_stats() or {}).get(stat) for d in jax.devices()]


def live_bytes() -> list:
    """Bytes of live JAX arrays resident on each device."""
    import jax
    held = {d: 0 for d in jax.devices()}
    for a in jax.live_arrays():
        for shard in a.addressable_shards:
            held[shard.device] += shard.data.nbytes
    return list(held.values())


def build_graph(seed: int, n: int):
    from repro.core import generators
    g = generators.watts_strogatz(n, K_NBRS, BETA, seed=seed)
    log(f"  graph: V={g.num_vertices} undirected E={g.num_undirected_edges} "
        f"directed entries={g.num_directed_entries} (cut from "
        f"V={FULL_VERTICES} to stay well inside the run's time limit)")
    return g


def quality(graph, labels, cfg) -> tuple:
    from repro.core import metrics
    return metrics.phi(graph, labels), metrics.rho(graph, labels, cfg.k)


def max_rel_err(got, want) -> float:
    return float(np.max(np.abs(got - want) / np.abs(want)))


def edge_batch(rng, n: int) -> tuple:
    src = rng.integers(0, n, ADAPT_EDGES)
    dst = (src + rng.integers(1, n, ADAPT_EDGES)) % n     # no self loops
    return src.astype(np.int32), dst.astype(np.int32)


def one_chip(seed: int, meter: CompileMeter) -> None:
    from benchmarks.common import hash_labels
    from repro.configs.spinner_paper import paper_config
    from repro.core import EngineOptions, open_session, pregel

    cfg = paper_config(k=K, seed=seed)
    with phase("graph", meter):
        g = build_graph(seed, N_VERTICES)
        hash_phi, _ = quality(g, hash_labels(g.num_vertices, K), cfg)
        log(f"  hash placement phi={hash_phi:.6f}")

    # 1. XLA backend: partition, then delta adapts
    xla = open_session(g, cfg)
    with phase("partition xla", meter):
        ref = xla.partition(record_history=False)
        phi, rho = quality(g, ref.labels, cfg)
        log(f"  iterations={ref.iterations} halted={ref.halted} "
            f"phi={phi:.6f} rho={rho:.6f}")
        check(phi >= 3 * hash_phi, f"phi {phi:.4f} >= 3 x hash phi")
        check(rho <= cfg.c, f"rho {rho:.4f} <= c = {cfg.c}")
    rng = np.random.default_rng(seed + 1)
    with phase("adapt xla cold", meter):
        res = xla.adapt(edge_updates=edge_batch(rng, g.num_vertices),
                        record_history=False)
        log(f"  iterations={res.iterations}")
    before = xla.stats()
    c0 = meter.snapshot()[0]
    with phase(f"adapt xla warm x{WARM_ADAPTS}", meter):
        for i in range(WARM_ADAPTS):
            t0 = time.perf_counter()
            res = xla.adapt(edge_updates=edge_batch(rng, g.num_vertices),
                            record_history=False)
            log(f"  adapt {i}: {time.perf_counter() - t0:.3f} s, "
                f"iterations={res.iterations}")
    after = xla.stats()
    log(f"  delta stats: {after['delta']}")
    log(f"  process-wide compiles in the warm adapts: "
        f"{meter.snapshot()[0] - c0}")
    check(after["compiles"] == before["compiles"],
          "warm adapts add zero session compiles")
    check(after["delta"]["fast_adapts"]
          == before["delta"]["fast_adapts"] + WARM_ADAPTS
          and after["delta"]["fallback_adapts"] == 0,
          "warm adapts take the O(|delta|) path")
    xla.close()

    # 2. Pallas backend, fused megakernel, compiled
    opts = EngineOptions(score_backend="pallas", fused_update="on")
    pal = open_session(g, cfg, opts)
    check(not pal.options.backend()._interpret(),
          "Pallas kernels resolve to interpret=False")
    with phase("partition pallas fused", meter):
        res = pal.partition(record_history=False)
        st = pal.stats()
        log(f"  iterations={res.iterations} backend={st['score_backend']} "
            f"fused_update={st['fused_update']} tiles={st['tile_config']}")
        check(st["score_backend"] == "pallas" and st["fused_update"] == "on",
              "session ran the fused Pallas backend")
        check(res.iterations == ref.iterations
              and np.array_equal(res.labels, ref.labels),
              "Pallas labels and iterations equal the XLA run bit for bit")

    # 3. Pregel applications on the Spinner layout
    labels = res.labels
    with phase("pagerank oracle", meter):
        pr_ref = pregel.pagerank(g, labels, K).values
    with phase("wcc oracle", meter):
        cc_ref = pregel.wcc(g, labels, K).values
    for combine in ("xla", "pallas"):
        with phase(f"run_app {combine}", meter):
            pr = pal.run_app("pagerank", combine=combine)
            err = max_rel_err(pr.values, pr_ref)
            log(f"  pagerank supersteps={pr.supersteps}")
            check(err <= PR_RTOL, f"pagerank ({combine}) matches the oracle "
                                  f"(max rel err {err:.3e})")
            cc = pal.run_app("wcc", combine=combine)
            log(f"  wcc supersteps={cc.supersteps} converged={cc.converged}"
                f" components={np.unique(cc.values).size}")
            check(cc.converged and np.array_equal(cc.values, cc_ref),
                  f"wcc ({combine}) matches the oracle")
    pal.close()


def four_chips(seed: int, meter: CompileMeter) -> None:
    from benchmarks.common import hash_labels
    from repro.configs.spinner_paper import paper_config
    from repro.core import EngineOptions, open_session, pregel
    from repro.launch.mesh import make_partition_mesh

    mesh = make_partition_mesh(4)
    cfg = paper_config(k=K, seed=seed)
    with phase("graph", meter):
        g = build_graph(seed, FOUR_CHIP_VERTICES)
    runs = {}
    for plan, overlap in (("delta", "on"), ("allgather", "off")):
        opts = EngineOptions(engine="sharded", mesh=mesh,
                             label_exchange=plan, overlap=overlap)
        s = open_session(g, cfg, opts)
        with phase(f"partition sharded {plan} overlap={overlap}", meter):
            r = s.partition(record_history=False)
            phi, rho = quality(g, r.labels, cfg)
            log(f"  iterations={r.iterations} phi={phi:.6f} rho={rho:.6f} "
                f"exchanged bytes={r.exchanged_bytes:.0f}")
            log(f"  per-device bytes in use: "
                f"{device_bytes('bytes_in_use')}")
        runs[plan] = (s, r)
    (s, r), (_, r_ag) = runs["delta"], runs["allgather"]
    check(r.iterations == r_ag.iterations
          and np.array_equal(r.labels, r_ag.labels),
          "delta+overlap equals allgather without overlap bit for bit")
    held = live_bytes()
    check(min(held) >= max(held) // 4,
          f"all 4 devices hold the layout (live array bytes {held})")
    with phase("pagerank oracle", meter):
        pr_ref = pregel.pagerank(g, r.labels, K).values
    for name, labels in (("hash", hash_labels(g.num_vertices, K)),
                         ("spinner", r.labels)):
        with phase(f"run_app pagerank {name} placement", meter):
            pr = s.run_app("pagerank", labels=labels)
            log(f"  supersteps={pr.supersteps} plan={pr.plan} wire bytes="
                f"{pr.wire_bytes:.0f} ({pr.wire_bytes_per_step:.0f}/step) "
                f"straggler skew={pr.straggler_skew:.4f}")
            err = max_rel_err(pr.values, pr_ref)
            check(err <= PR_RTOL, f"pagerank on the {name} placement "
                                  f"matches the oracle (max rel err "
                                  f"{err:.3e})")
    for sess, _ in runs.values():
        sess.close()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    meter = CompileMeter()
    dev = devices[0]
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"jax {jax.__version__}; compile cache {cache}")
    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)(args.seed, meter)
    compiles, secs, hits = meter.snapshot()
    log(f"total {time.perf_counter() - t0:.2f} s; compiles {compiles} "
        f"({secs:.2f} s); persistent-cache hits {hits}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
