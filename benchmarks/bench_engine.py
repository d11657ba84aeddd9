"""Host vs fused vs chunked vs sharded engine: dispatch overhead.

The fused runner executes the whole run as one ``lax.while_loop`` device
call; the host loop pays a dispatch + sync round-trip per iteration.  This
suite isolates that overhead: each runner is compiled once, then timed on a
steady-state run with the same seed (so all engines execute the identical
label trajectory and iteration count), and the per-iteration gap between
host and fused is reported as dispatch overhead.

The sharded section measures the same quantity for the mesh engine: the
single ``shard_map(while_loop)`` dispatch of ``engine="sharded"`` against
``run_sharded_hostloop``, the pre-PR-2 driving mode that dispatches the
identical sharded step once per iteration with a host sync on
``state.halted``.  Both walk the same trajectory bit for bit, so the gap
is pure dispatch/sync cost -- the overhead this PR removes from the
distributed path.  (In-process this runs on a 1-device mesh; see
EXPERIMENTS.md for the multi-device workers sweep.)

The exchange-mode matrix (subprocess, 8 forced host devices, clustered
graph) compares the three label-exchange plans -- allgather / halo /
delta, identical trajectories by construction -- on per-iteration bytes
on the wire next to wall-clock: the Section 3.3 / Figure 7 claim that
converging LPA needs ever less communication, measured on device.  The
``sharded_pallas`` row times the per-shard tiled MXU kernel inside
``shard_map`` (interpret mode off-TPU, so it is a correctness/coverage
row there, not a speed claim).

The overlap matrix (subprocess, 8 forced host devices) times the
interior/frontier overlap schedule (``EngineOptions.overlap``) against
the sequential exchange->score step on the same mesh and plan --
bit-identical trajectories, so the gap is pure schedule -- and reports
the layout's frontier fraction (the share of scoring that must wait for
the wire).  The ``staged_adapt`` row measures the session's
double-buffered uploads: ``stage()`` issues the next snapshot's
transfers ahead of time, so the following ``adapt()`` dispatches from a
device-resident bind (compare against ``session_cold_adapt`` /
``session_warm_adapt``).
"""
from __future__ import annotations

import os
import subprocess
import sys
import time

import jax

import numpy as np

from repro.core import EngineOptions, SpinnerConfig, adapt, engine, \
    generators, open_session, partition, prepare_init
from repro.core.graph import add_edges
from repro.core.distributed import run_sharded_hostloop
from repro.launch.mesh import make_partition_mesh

from .common import cpu_child_env, emit, get_graph, mark_cpu

EXCHANGE_MATRIX_CODE = """
import time
from repro.core import EngineOptions, SpinnerConfig, generators, partition
from repro.launch.mesh import make_partition_mesh

g = generators.clustered_graph(8, {n_per}, 0.02, 0.5, seed=5)
cfg = SpinnerConfig(k=8, seed=1, max_iters={max_iters})
mesh = make_partition_mesh()
for mode in ("allgather", "halo", "delta"):
    kw = dict(record_history=False, engine="sharded", mesh=mesh,
              options=EngineOptions(label_exchange=mode))
    partition(g, cfg, **kw)                       # warm-up/compile
    t0 = time.time()
    res = partition(g, cfg, **kw)
    dt = time.time() - t0
    bpi = res.exchanged_bytes / max(1, res.iterations)
    print(f"MODE {{mode}} ndev={{mesh.size}} iters={{res.iterations}} "
          f"total_s={{dt:.3f}} bytes_per_iter={{bpi:.0f}}")
"""


def _exchange_matrix_rows(quick: bool) -> list:
    """allgather/halo/delta wire bytes + wall-clock on an 8-device mesh."""
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = cpu_child_env(8)
    code = EXCHANGE_MATRIX_CODE.format(n_per=250 if quick else 500,
                                       max_iters=60 if quick else 120)
    try:
        r = subprocess.run([sys.executable, "-c", code],
                           env=env, cwd=here, capture_output=True,
                           text=True, timeout=900)
        stdout, err = r.stdout, ("" if r.returncode == 0 else
                                 f"rc={r.returncode}: {r.stderr.strip()}")
    except subprocess.TimeoutExpired as e:
        stdout, err = "", f"timeout after {e.timeout}s"
    rows = []
    parsed = {}
    if not err:
        for line in stdout.splitlines():
            if not line.startswith("MODE "):
                continue
            fields = dict(f.split("=") for f in line.split()[2:])
            parsed[line.split()[1]] = fields
    ag_bytes = float(parsed.get("allgather", {}).get("bytes_per_iter", 0))
    for mode, f in parsed.items():
        bpi = float(f["bytes_per_iter"])
        red = 1 - bpi / ag_bytes if ag_bytes and mode != "allgather" else 0.0
        iters = int(f["iters"])
        rows.append({
            "name": f"engine/exchange_{mode}",
            "us_per_call": float(f["total_s"]) / max(1, iters) * 1e6,
            "derived": f"ndev={f['ndev']};iters={iters};"
                       f"bytes_per_iter={bpi:.0f}"
                       + (f";vs_allgather=-{red:.1%}" if mode != "allgather"
                          else ""),
            "bytes_per_iter": bpi,
        })
    if not rows:
        rows.append({"name": "engine/exchange_matrix", "us_per_call": 0.0,
                     "derived": "FAILED: " + (err or "no MODE lines")[-200:]})
    return mark_cpu(rows)


OVERLAP_MATRIX_CODE = """
import time
import numpy as np
from repro.core import EngineOptions, SpinnerConfig, generators, metrics, \\
    partition
from repro.core.distributed import shard_layout
from repro.core.engine import padded_view
from repro.launch.mesh import make_partition_mesh

g = generators.clustered_graph(8, {n_per}, 0.02, 0.5, seed=5)
cfg = SpinnerConfig(k=8, seed=1, max_iters={max_iters})
mesh = make_partition_mesh()
labels = {{}}
for ov in ("off", "on"):
    opts = EngineOptions(label_exchange="halo", overlap=ov)
    kw = dict(record_history=False, engine="sharded", mesh=mesh,
              options=opts)
    partition(g, cfg, **kw)                       # warm-up/compile
    t0 = time.time()
    res = partition(g, cfg, **kw)
    dt = time.time() - t0
    labels[ov] = res.labels
    padded, _ = padded_view(g, opts)
    sg = shard_layout(padded, mesh.size, pad=True)
    bpi = res.exchanged_bytes / max(1, res.iterations)
    print(f"OVERLAP {{ov}} ndev={{mesh.size}} iters={{res.iterations}} "
          f"total_s={{dt:.3f}} bytes_per_iter={{bpi:.0f}} "
          f"frontier_fraction={{metrics.frontier_fraction(sg):.3f}}")
assert (labels["off"] == labels["on"]).all()      # pure schedule change
"""


def _overlap_matrix_rows(quick: bool) -> list:
    """Overlap-on vs overlap-off wall-clock on an 8-device mesh (halo
    plan; identical trajectories, asserted in the subprocess)."""
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = cpu_child_env(8)
    code = OVERLAP_MATRIX_CODE.format(n_per=250 if quick else 500,
                                      max_iters=60 if quick else 120)
    try:
        r = subprocess.run([sys.executable, "-c", code],
                           env=env, cwd=here, capture_output=True,
                           text=True, timeout=900)
        err = ("" if r.returncode == 0 else
               f"rc={r.returncode}: {r.stderr.strip()}")
        stdout = r.stdout
    except subprocess.TimeoutExpired as e:
        stdout, err = "", f"timeout after {e.timeout}s"
    rows = []
    parsed = {}
    if not err:
        for line in stdout.splitlines():
            if line.startswith("OVERLAP "):
                parsed[line.split()[1]] = dict(
                    f.split("=") for f in line.split()[2:])
    t_off = float(parsed.get("off", {}).get("total_s", 0))
    for ov, f in parsed.items():
        dt = float(f["total_s"])
        iters = int(f["iters"])
        extra = (f";vs_overlap_off={t_off / max(dt, 1e-12):.2f}x"
                 if ov == "on" and t_off else "")
        rows.append({
            "name": f"engine/overlap_{ov}",
            "us_per_call": dt / max(1, iters) * 1e6,
            "derived": f"ndev={f['ndev']};iters={iters};"
                       f"total_s={dt:.3f};plan=halo;"
                       f"frontier_fraction={f['frontier_fraction']};"
                       f"bytes_per_iter={f['bytes_per_iter']}" + extra,
        })
    if not rows:
        rows.append({"name": "engine/overlap_matrix", "us_per_call": 0.0,
                     "derived": "FAILED: "
                                + (err or "no OVERLAP lines")[-200:]})
    return mark_cpu(rows)


FUSED_MATRIX_CODE = """
import time
import numpy as np
from repro.core import EngineOptions, SpinnerConfig, generators, partition
from repro.launch.mesh import make_partition_mesh

g = generators.clustered_graph(8, {n_per}, 0.02, 0.5, seed=5)
cfg = SpinnerConfig(k=8, seed=1, max_iters={max_iters})
mesh = make_partition_mesh()
labels = {{}}
for fu in ("off", "on"):
    opts = EngineOptions(score_backend="pallas", label_exchange="halo",
                         fused_update=fu)
    kw = dict(record_history=False, engine="sharded", mesh=mesh,
              options=opts)
    partition(g, cfg, **kw)                       # warm-up/compile
    t0 = time.time()
    res = partition(g, cfg, **kw)
    dt = time.time() - t0
    labels[fu] = res.labels
    print(f"FUSED {{fu}} ndev={{mesh.size}} iters={{res.iterations}} "
          f"total_s={{dt:.3f}}")
assert (labels["off"] == labels["on"]).all()      # bit-exact megakernel
"""


def _fused_matrix_rows(quick: bool) -> list:
    """Fused megakernel on vs off on an 8-device mesh (pallas backend,
    halo plan; identical trajectories, asserted in the subprocess).
    Interpret-mode Pallas runs the kernel op-by-op on host, so the
    wall-clock here tracks dispatch count, not the TPU win the roofline
    mode models."""
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = cpu_child_env(8)
    code = FUSED_MATRIX_CODE.format(n_per=100 if quick else 200,
                                    max_iters=20 if quick else 40)
    try:
        r = subprocess.run([sys.executable, "-c", code],
                           env=env, cwd=here, capture_output=True,
                           text=True, timeout=900)
        err = ("" if r.returncode == 0 else
               f"rc={r.returncode}: {r.stderr.strip()}")
        stdout = r.stdout
    except subprocess.TimeoutExpired as e:
        stdout, err = "", f"timeout after {e.timeout}s"
    rows = []
    parsed = {}
    if not err:
        for line in stdout.splitlines():
            if line.startswith("FUSED "):
                parsed[line.split()[1]] = dict(
                    f.split("=") for f in line.split()[2:])
    for fu, f in parsed.items():
        dt = float(f["total_s"])
        iters = int(f["iters"])
        rows.append({
            "name": f"engine/fused_update_{fu}",
            "us_per_call": dt / max(1, iters) * 1e6,
            "derived": f"ndev={f['ndev']};iters={iters};"
                       f"total_s={dt:.3f};plan=halo;backend=pallas",
        })
    if not rows:
        rows.append({"name": "engine/fused_matrix", "us_per_call": 0.0,
                     "derived": "FAILED: "
                                + (err or "no FUSED lines")[-200:]})
    return mark_cpu(rows)


def _time_engine(graph, cfg, eng, chunk_size=None):
    """(seconds_warm, iterations): second call timed, first pays compile."""
    kw = {"record_history": False, "engine": eng}
    if chunk_size is not None:
        kw["chunk_size"] = chunk_size
    res = partition(graph, cfg, **kw)        # warm-up/compile
    t0 = time.time()
    res = partition(graph, cfg, **kw)
    return time.time() - t0, res.iterations


def run(quick: bool = False) -> list:
    g = get_graph("powerlaw-50k" if quick else "smallworld-100k")
    cfg = SpinnerConfig(k=32, seed=0, max_iters=40 if quick else 100)
    rows = []

    t_host, iters = _time_engine(g, cfg, "host")
    t_fused, it_f = _time_engine(g, cfg, "fused")
    # both engines run f32 halting, so counts should agree; report rather
    # than assert so a divergence can't abort the whole benchmark run
    parity = "ok" if it_f == iters else f"DIVERGED({iters}vs{it_f})"
    per_host = t_host / max(1, iters)
    per_fused = t_fused / max(1, it_f)
    rows.append({
        "name": "engine/host",
        "us_per_call": per_host * 1e6,
        "derived": f"iters={iters};total_s={t_host:.3f}",
        "iterations": iters, "total_s": t_host,
    })
    rows.append({
        "name": "engine/fused",
        "us_per_call": per_fused * 1e6,
        "derived": f"iters={it_f};total_s={t_fused:.3f};"
                   f"speedup={per_host / max(per_fused, 1e-12):.2f}x;"
                   f"parity={parity}",
        "iterations": it_f, "total_s": t_fused,
    })
    rows.append({
        "name": "engine/dispatch_overhead",
        "us_per_call": (per_host - per_fused) * 1e6,
        "derived": f"host_per_iter_us={per_host * 1e6:.1f};"
                   f"fused_per_iter_us={per_fused * 1e6:.1f}",
    })

    for chunk in (8, 32):
        t_chunk, it_c = _time_engine(g, cfg, "chunked", chunk_size=chunk)
        per_chunk = t_chunk / max(1, it_c)
        dispatches = -(-it_c // chunk)
        rows.append({
            "name": f"engine/chunked_cs{chunk}",
            "us_per_call": per_chunk * 1e6,
            "derived": f"iters={it_c};dispatches={dispatches};"
                       f"total_s={t_chunk:.3f};"
                       f"speedup_vs_host={per_host / max(per_chunk, 1e-12):.2f}x",
            "iterations": it_c, "dispatches": dispatches,
        })

    # sharded engine: one shard_map(while_loop) dispatch vs per-iteration
    # host driving of the same sharded step (identical trajectory)
    mesh = make_partition_mesh()
    kw = {"record_history": False, "engine": "sharded", "mesh": mesh}
    partition(g, cfg, **kw)                  # warm-up/compile
    t0 = time.time()
    res_sh = partition(g, cfg, **kw)
    t_sharded = time.time() - t0
    it_s = res_sh.iterations
    per_sharded = t_sharded / max(1, it_s)

    state = run_sharded_hostloop(g, cfg, mesh)   # warm-up/compile
    t0 = time.time()
    state = run_sharded_hostloop(g, cfg, mesh)
    t_hloop = time.time() - t0
    it_h = int(state.iteration)
    per_hloop = t_hloop / max(1, it_h)
    parity_sh = "ok" if it_h == it_s else f"DIVERGED({it_s}vs{it_h})"
    rows.append({
        "name": "engine/sharded_fused",
        "us_per_call": per_sharded * 1e6,
        "derived": f"iters={it_s};total_s={t_sharded:.3f};"
                   f"mesh={mesh.size}dev",
        "iterations": it_s, "total_s": t_sharded,
    })
    rows.append({
        "name": "engine/sharded_hostloop",
        "us_per_call": per_hloop * 1e6,
        "derived": f"iters={it_h};total_s={t_hloop:.3f};"
                   f"speedup_fused={per_hloop / max(per_sharded, 1e-12):.2f}x;"
                   f"parity={parity_sh}",
        "iterations": it_h, "total_s": t_hloop,
    })
    rows.append({
        "name": "engine/sharded_dispatch_overhead",
        "us_per_call": (per_hloop - per_sharded) * 1e6,
        "derived": f"hostloop_per_iter_us={per_hloop * 1e6:.1f};"
                   f"sharded_per_iter_us={per_sharded * 1e6:.1f}",
    })

    # exchange-mode matrix: bytes on the wire per iteration per plan,
    # measured on a real 8-device mesh (subprocess, forced host devices)
    rows.extend(_exchange_matrix_rows(quick))

    # overlap schedule: interior scoring concurrent with the halo
    # exchange vs the sequential step, same mesh and trajectory
    rows.extend(_overlap_matrix_rows(quick))
    rows.extend(_fused_matrix_rows(quick))

    # Figure 7 traffic decay: the delta plan ships one (index, label) pair
    # per migration to each peer, so the per-iteration wire volume is the
    # migration curve -- run a clustered graph to convergence and read the
    # decay from the chunked history
    g_cl = generators.clustered_graph(8, 250 if quick else 500, 0.02, 0.5,
                                      seed=5)
    hist = partition(g_cl, SpinnerConfig(k=8, seed=1,
                                         max_iters=60 if quick else 120),
                     engine="chunked").history
    if hist:
        ndev_hypo = 8
        decay = [h["migrations"] * 8 * (ndev_hypo - 1) for h in hist]
        picks = {i: decay[i] for i in (0, len(decay) // 4, len(decay) // 2,
                                       len(decay) - 1)}
        allgather_bpi = (ndev_hypo - 1) * g_cl.num_vertices * 4
        rows.append({
            "name": "engine/delta_traffic_decay",
            "us_per_call": 0.0,
            "derived": ";".join(f"iter{i + 1}={b}B"
                                for i, b in sorted(picks.items()))
                       + f";allgather={allgather_bpi}B/iter(ndev=8)",
        })

    # sharded Pallas score backend inside shard_map (interpret off-TPU):
    # a small fixed-iteration run -- interpret mode emulates the MXU
    # kernel op-by-op, so this row tracks coverage/cost, not TPU speed
    g_pal = generators.watts_strogatz(1000 if quick else 2000, 10, 0.2,
                                      seed=9)
    cfg_pal = SpinnerConfig(k=16, seed=0, max_iters=4 if quick else 6)
    mesh1 = make_partition_mesh(1)
    kw = {"record_history": False, "engine": "sharded", "mesh": mesh1,
          "options": EngineOptions(score_backend="pallas")}
    partition(g_pal, cfg_pal, **kw)              # warm-up/compile
    t0 = time.time()
    res_p = partition(g_pal, cfg_pal, **kw)
    t_pal = time.time() - t0
    kw["options"] = EngineOptions(score_backend="xla")
    partition(g_pal, cfg_pal, **kw)              # warm-up/compile
    t0 = time.time()
    res_x = partition(g_pal, cfg_pal, **kw)
    t_xla = time.time() - t0
    parity_p = ("ok" if (res_p.labels == res_x.labels).all()
                else "DIVERGED")
    rows.append({
        "name": "engine/sharded_pallas",
        "us_per_call": t_pal / max(1, res_p.iterations) * 1e6,
        "derived": f"iters={res_p.iterations};total_s={t_pal:.3f};"
                   f"interpret={jax.default_backend() == 'cpu'};"
                   f"xla_total_s={t_xla:.3f};parity={parity_p}",
        "iterations": res_p.iterations, "total_s": t_pal,
    })

    # session amortization (PR 4): a long-lived PartitionSession compiles
    # its fused runner against the graph's (V, E) shape bucket, so a warm
    # adapt() on a grown same-bucket graph pays upload + dispatch only.
    # Cold = one-shot adapt with fresh cfg statics (nothing pre-compiled:
    # full trace + XLA compile on the critical path); warm = the live
    # session (zero new compiles, asserted).
    g_s = generators.watts_strogatz(3000 if quick else 10_000, 10, 0.2,
                                    seed=13)
    v_s = g_s.num_vertices
    rng = np.random.default_rng(5)
    sess_cfg = SpinnerConfig(k=16, seed=0, max_iters=41)
    sess = open_session(g_s, sess_cfg, EngineOptions(engine="fused"))
    res0 = sess.partition(record_history=False)
    g_grown = add_edges(g_s, rng.integers(0, v_s, 200),
                        rng.integers(0, v_s, 200), num_vertices=v_s + 10)
    cold_cfg = SpinnerConfig(k=16, seed=0, max_iters=43)   # fresh statics
    t0 = time.time()
    res_cold = adapt(g_grown, res0.labels, cold_cfg, record_history=False)
    t_cold_adapt = time.time() - t0
    compiles_before = sess.compiles
    t0 = time.time()
    res_warm = sess.adapt(g_grown, record_history=False)
    t_warm_adapt = time.time() - t0
    warm_compiles = sess.compiles - compiles_before
    parity_s = ("ok" if (res_cold.labels == res_warm.labels).all()
                else "DIVERGED")
    rows.append({
        "name": "engine/session_cold_adapt",
        "us_per_call": t_cold_adapt * 1e6,
        "derived": f"iters={res_cold.iterations};"
                   f"total_s={t_cold_adapt:.3f};compile_on_path=1",
    })
    rows.append({
        "name": "engine/session_warm_adapt",
        "us_per_call": t_warm_adapt * 1e6,
        "derived": f"iters={res_warm.iterations};"
                   f"total_s={t_warm_adapt:.3f};"
                   f"new_compiles={warm_compiles};"
                   f"speedup_vs_cold="
                   f"{t_cold_adapt / max(t_warm_adapt, 1e-12):.1f}x;"
                   f"bucket={sess.stats()['bucket']};parity={parity_s}",
    })

    # staged (double-buffered) adapt (PR 5): stage() issues the next
    # snapshot's uploads -- and the per-shape init-op warmup -- ahead of
    # time, so the following adapt() dispatches straight from a
    # device-resident bind with zero new compiles and zero synchronous
    # copies.  Baseline: a synchronous warm adapt of an equally FRESH
    # snapshot (the session_warm_adapt row above is shape-warm because
    # the cold one-shot just ran the identical graph).
    g_sync = add_edges(g_grown, rng.integers(0, v_s, 200),
                       rng.integers(0, v_s, 200), num_vertices=v_s + 12)
    t0 = time.time()
    res_sync = sess.adapt(g_sync, record_history=False)
    t_sync = time.time() - t0
    g_next = add_edges(g_sync, rng.integers(0, v_s, 200),
                       rng.integers(0, v_s, 200), num_vertices=v_s + 14)
    t0 = time.time()
    sess.stage(g_next)
    t_stage = time.time() - t0
    compiles_before = sess.compiles
    t0 = time.time()
    res_staged = sess.adapt(record_history=False)
    t_staged = time.time() - t0
    staged_compiles = sess.compiles - compiles_before
    rows.append({
        "name": "engine/staged_adapt",
        "us_per_call": t_staged * 1e6,
        "derived": f"iters={res_staged.iterations};"
                   f"total_s={t_staged:.3f};stage_s={t_stage:.3f};"
                   f"sync_adapt_s={t_sync:.3f};"
                   f"new_compiles={staged_compiles};"
                   f"speedup_vs_cold="
                   f"{t_cold_adapt / max(t_staged, 1e-12):.1f}x;"
                   f"speedup_vs_sync="
                   f"{t_sync / max(t_staged, 1e-12):.1f}x",
    })
    sess.close()

    # compile cost of the single-dispatch path (first call - steady state)
    labels, loads, key = prepare_init(g, cfg)
    runner = engine.make_fused_runner(g, cfg)
    state0 = engine.init_state(labels, loads, key)
    t0 = time.time()
    jax.block_until_ready(runner(state0))
    t_cold = time.time() - t0
    rows.append({
        "name": "engine/fused_compile",
        "us_per_call": (t_cold - t_fused) * 1e6,
        "derived": f"cold_s={t_cold:.3f};steady_s={t_fused:.3f}",
    })

    emit(rows, "bench_engine")
    return rows


if __name__ == "__main__":
    run()
