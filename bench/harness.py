"""The benchmark, driven by data: ``BENCHMARK.json`` names each cell's
configuration and traffic mix, and every piece is found by that name.

* ``bench/configs/<config>.json``   the deployment: its graph generator
                                    (``bench/generators/<generator>.py``)
                                    with its sizes, the paper parameters,
                                    its source and its cuts;
* ``bench/traffic/<traffic>.json``  the mix: its ``driver``
                                    (``bench/drivers/<driver>.py``) and
                                    that driver's parameters;
* ``bench/limits/<cell>.json``      the limit of each number that decides
                                    ``correct`` (``PERF.md`` gives the
                                    readings each was set from);
* ``bench/metrics/<metric>.py``     one reader per per-layer metric, a
                                    ``read(run)`` that returns the number
                                    or None when there is nothing to read.

So a later cell or metric comes as new files and a new entry of
``BENCHMARK.json``; no file here changes (see ``base.py``).

A cell is run by ``run(cell, seed, seconds, trace)``, which returns the
result object that ``run.py`` prints.
"""
from __future__ import annotations

import json
import math
import os
import shutil
import time
import types

import base
import compile_meter
import peaks
import trace_reduce
import work

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def _json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> dict:
    """Everything one cell needs, read from the files its name leads to."""
    bench = _json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{', '.join(sorted(cells))}")
    cell = cells[name]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])

    def reported(metric: dict) -> bool:
        return name in metric.get("workloads", [name])

    e2e = [m for m in bench["end_to_end"] if reported(m)]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if reported(m) and m["moves"] in e2e_names]
    return {"name": name, "chips": cell["chips"],
            "config": _json(root, config["file"]),
            "traffic": _json(BENCH, "traffic", cell["traffic"] + ".json"),
            "limits": _json(BENCH, "limits", name + ".json")["limits"],
            "end_to_end": e2e, "per_layer": layer,
            "run_seconds": bench["run_seconds"]}


def tpu_devices(chips: int, root: str = ROOT):
    """The first ``chips`` TPU devices, with JAX's persistent compilation
    cache in ``<root>/.jax_cache`` (a fixed path, part of the cache key);
    None where JAX finds no TPU or fewer chips."""
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(root, ".jax_cache"))
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        return None
    return devices[:chips]


def judge(per_answer: list, limits: dict) -> tuple:
    """(checks, failed): each number's worst reading over the answers
    beside its limit, and how many answers exceed any limit.  A number
    that no answer reports is a failure: the comparison did not run."""
    checks, failed = {}, 0
    for name, limit in limits.items():
        vals = [a[name] for a in per_answer if name in a]
        worst = max(vals) if vals else float("inf")
        # JSON has no infinity: a number that could not be read is null
        checks[name] = {"value": worst if math.isfinite(worst) else None,
                        "limit": limit}
    for a in per_answer:
        if any(a[n] > limits[n] for n in a if n in limits):
            failed += 1
    return checks, failed


def device_info(devices) -> dict:
    dev = devices[0]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak)}


def run(cell: dict, seed: int, seconds: float, traced: bool,
        t_start: float, trace_dir: str, log=print) -> dict:
    """Set up, measure and check one cell once; the result object."""
    import jax
    meter = compile_meter.CompileMeter()
    driver = base.find("drivers", cell["traffic"]["driver"]).Driver(
        cell["config"], cell["traffic"], seed)
    driver.setup()
    setup_s = time.perf_counter() - t_start
    c0 = meter.snapshot()
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    with base.span("window"):
        driver.window(seconds)
    if traced:
        jax.profiler.stop_trace()
    c1 = meter.snapshot()
    devices = jax.devices()[:cell["chips"]]
    device = device_info(devices)
    driver.release()
    t_check = time.perf_counter()
    per_answer = driver.check()
    check_s = time.perf_counter() - t_check
    checks, failed = judge(per_answer, cell["limits"])
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in checks.values())
    log(f"set-up {setup_s:.3f} s, compiles {c0[0]} ({c0[1]:.3f} s), "
        f"persistent-cache hits {c0[2]}")
    log(f"window: compiles {c1[0] - c0[0]} ({c1[1] - c0[1]:.3f} s), "
        f"persistent-cache hits {c1[2] - c0[2]}; check {check_s:.3f} s")
    log("records: " + json.dumps({k: v for k, v in driver.records.items()
                                  if not isinstance(v, list)}))
    for key, v in driver.records.items():
        if isinstance(v, list):
            log(f"{key}: n={len(v)} min={min(v, default=0)} "
                f"max={max(v, default=0)} values={v[:40]}")
    metrics, breakdown = {}, None
    if not traced:
        e2e = dict(driver.end_to_end, setup_s=setup_s)
        for m in cell["end_to_end"]:
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    else:
        reduced = trace_reduce.reduce(trace_reduce.load(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        view = types.SimpleNamespace(
            records=driver.records, trace=reduced, work=work,
            peaks=lambda: peaks.peaks(device["kind"]),
            end_to_end=driver.end_to_end)
        for m in cell["per_layer"]:
            value = base.find("metrics", m["name"]).read(view)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = reduced.get("busy_s", 0.0)
        device["window_s"] = reduced.get("window_s", 0.0)
        if reduced:
            breakdown = {"device_ops": reduced["device_ops"],
                         "idle_gaps": reduced["idle_gaps"]}
    result = {"correct": correct, "attempted": len(per_answer),
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result
