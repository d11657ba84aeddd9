"""Roofline analysis from the dry-run artifacts (deliverable g).

Hardware model: the TPU v5e row of ``repro.kernels.peaks`` (197 TFLOP/s
bf16, 819 GB/s HBM, 50 GB/s per ICI link; see there for the source); we
credit 2 links per chip for a 2D-torus axis -> 100 GB/s/chip aggregate
collective bandwidth.  Every number here is a model of that chip, not a
measurement.  All inputs are PER-DEVICE quantities from
the trip-count-weighted HLO analysis (see repro/launch/hlo_analysis.py):

  compute_term    = dot_flops / 197e12            (s)
  memory_term     = tpu_bytes / 819e9             (s) where tpu_bytes counts
                    dot/gather/scatter/DUS/copy/collective I/O only --
                    i.e. assumes XLA-TPU fuses every elementwise chain into
                    its neighbors.  Two brackets are reported alongside:
                    hbm_bytes (CPU-fusion granularity, upper bound) and
                    model_min_bytes (weights+states+caches, lower bound).
  collective_term = collective_bytes / 100e9      (s)

MODEL_FLOPS = 6 N_active D (train) / 2 N_active D (prefill) /
2 N_active B (decode), D = global tokens per step.  The useful-compute
fraction MODEL_FLOPS / (chips * dot_flops) exposes remat/dispatch/causal
overheads; roofline_fraction = useful_compute_time / max(term) is the
score per cell.
"""
from __future__ import annotations

import glob
import json
import os
import sys

from repro.kernels.peaks import device_peaks

_V5E = device_peaks("TPU v5 lite")     # the modelled chip
PEAK_FLOPS = _V5E.flops
HBM_BW = _V5E.hbm_bytes_s
ICI_BW = 2 * _V5E.ici_link_bytes_s     # 2 links per torus axis

ARTIFACTS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "artifacts")


def model_flops(arch: str, shape_name: str) -> float:
    from repro.configs import ARCHS, SHAPES_BY_NAME
    from repro.models import build
    cfg = ARCHS[arch]
    shape = SHAPES_BY_NAME[shape_name]
    api = build(cfg)
    n = api.num_active_params
    b, s = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        return 6.0 * n * b * s
    if shape.kind == "prefill":
        return 2.0 * n * b * s
    return 2.0 * n * b          # decode: one token per sequence


def min_hbm_bytes(arch: str, shape_name: str, chips: int) -> float:
    """Lower bound on per-device HBM traffic per step."""
    from repro.configs import ARCHS, SHAPES_BY_NAME
    from repro.models import build, input_specs
    import math
    cfg = ARCHS[arch]
    shape = SHAPES_BY_NAME[shape_name]
    api = build(cfg)
    n, n_act = api.num_params, api.num_active_params
    if shape.kind == "train":
        # bf16 weight reads fwd+bwd+remat-fwd (3x active) + fp32 AdamW
        # state read/write (16 B/param r+w -> 32) spread over all chips
        return (3 * 2 * n_act + 32 * n) / chips
    if shape.kind == "prefill":
        return 2 * n_act / chips
    _, cache = input_specs(cfg, shape)
    cache_bytes = sum(math.prod(l.shape) * l.dtype.itemsize
                      for l in __import__("jax").tree.leaves(cache))
    return (2 * n_act + cache_bytes) / chips


def analyze_cell(rec: dict) -> dict:
    chips = rec["n_devices"]
    a = rec["analyzed"]
    compute = a["dot_flops"] / PEAK_FLOPS
    memory = a.get("tpu_bytes", a["hbm_bytes"]) / HBM_BW
    collective = a["collective_bytes"] / ICI_BW
    terms = {"compute": compute, "memory": memory,
             "collective": collective}
    dominant = max(terms, key=terms.get)
    mf = model_flops(rec["arch"], rec["shape"])
    useful_time = mf / chips / PEAK_FLOPS
    bound = max(terms.values())
    minb = min_hbm_bytes(rec["arch"], rec["shape"], chips)
    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "chips": chips,
        "compute_s": compute, "memory_s": memory,
        "collective_s": collective, "dominant": dominant,
        "model_flops": mf,
        "useful_flops_ratio": mf / max(1.0, a["dot_flops"] * chips),
        "roofline_fraction": useful_time / max(bound, 1e-12),
        "memory_upper_s": a["hbm_bytes"] / HBM_BW,
        "memory_lower_s": minb / HBM_BW,
        "bytes_by_op": a.get("bytes_by_op", {}),
        "hbm_utilization_lower": minb / max(
            1.0, a.get("tpu_bytes", a["hbm_bytes"])),
        "mem_per_device_gib": (rec["memory"]["argument_bytes"]
                               + rec["memory"]["temp_bytes"]) / 2**30,
        "collectives": a["collectives"],
    }


def note(r: dict) -> str:
    d = r["dominant"]
    if d == "compute":
        gap = 1 - r["useful_flops_ratio"]
        return (f"compute-bound; {gap:.0%} of dot flops are overhead "
                "(remat/causal-waste/dispatch) - cut those to move the term")
    if d == "memory":
        return ("memory-bound; HLO traffic is "
                f"{1 / max(r['hbm_utilization_lower'], 1e-9):.0f}x the "
                "weight+state lower bound - fuse elementwise chains / "
                "larger per-core batch")
    return ("collective-bound; shrink FSDP all-gathers (bf16 gathers, "
            "wider TP) or overlap with compute")


def load_records(mesh: str = "single"):
    recs = []
    for path in sorted(glob.glob(os.path.join(ARTIFACTS, "dryrun",
                                              f"*__{mesh}.json"))):
        with open(path) as f:
            rec = json.load(f)
        if "error" not in rec:
            recs.append(rec)
    return recs


def spinner_kernel_rows(quick: bool = False) -> list:
    """Roofline model of the Spinner vertex update, fused vs. split.

    For each (graph, k) cell: the autotuner's tile choice, the REAL padded
    edge geometry from ``build_tiled_csr``, and the modeled HBM traffic of
    the split path (score matrix written by the kernel, re-read by the XLA
    normalize/argmax chain) against the fused megakernel (score block
    VMEM-resident; the 2 * V_pad * k_pad bytes disappear).  Writes
    ``artifacts/roofline_spinner.md``.
    """
    from repro.core import generators
    from repro.core.graph import build_tiled_csr
    from repro.kernels import autotune

    cells = [("ws", generators.watts_strogatz(
                 2000 if quick else 20_000, 8, 0.2, seed=0)),
             ("powerlaw", generators.powerlaw_ba(
                 2000 if quick else 20_000, 8, seed=0))]
    rows = []
    table = ["| graph | k | tile (v,e) | split B/edge | fused B/edge "
             "| removed V*k MiB | compute s | mem s (fused) | dominant |",
             "|---|---|---|---|---|---|---|---|---|"]
    for name, g in cells:
        for k in (16, 64) if quick else (16, 64, 256):
            tile_v, tile_e, k_pad = autotune.choose_tile_config(g, k)
            tiled = build_tiled_csr(g, tile_v=tile_v, tile_e=tile_e)
            e_pad = tiled.num_tiles * tiled.max_chunks * tiled.tile_e
            split, fused = autotune.modeled_traffic(tiled.padded_v, e_pad,
                                                    k_pad)
            s_b, f_b = sum(split.values()), sum(fused.values())
            removed = split["score_write"] + split["score_read"]
            assert s_b - f_b == removed      # exactly the V*k round-trip
            flops = 2.0 * e_pad * (tile_v + k_pad)
            compute = flops / PEAK_FLOPS
            mem_f, mem_s = f_b / HBM_BW, s_b / HBM_BW
            dominant = "compute" if compute > mem_f else "memory"
            n_edges = 2 * g.num_undirected_edges
            rows.append({
                "name": f"roofline/spinner/{name}/k{k}",
                "us_per_call": max(compute, mem_f) * 1e6,
                "derived": f"tile=({tile_v},{tile_e},{k_pad});"
                           f"split_Bpe={s_b / n_edges:.1f};"
                           f"fused_Bpe={f_b / n_edges:.1f};"
                           f"removed_bytes={removed:.0f};"
                           f"dominant={dominant}",
                "graph": name, "k": k,
                "tile_config": (tile_v, tile_e, k_pad),
                "split_bytes": s_b, "fused_bytes": f_b,
                "removed_bytes": removed, "compute_s": compute,
                "memory_s_fused": mem_f, "memory_s_split": mem_s,
                "dominant": dominant,
            })
            table.append(
                f"| {name} | {k} | ({tile_v},{tile_e}) "
                f"| {s_b / n_edges:.1f} | {f_b / n_edges:.1f} "
                f"| {removed / 2**20:.1f} | {compute:.2e} "
                f"| {mem_f:.2e} | {dominant} |")
    os.makedirs(ARTIFACTS, exist_ok=True)
    with open(os.path.join(ARTIFACTS, "roofline_spinner.md"), "w") as f:
        f.write("\n".join(table) + "\n")
    return rows


def run(quick: bool = False) -> list:
    rows = spinner_kernel_rows(quick)
    table_md = ["| arch | shape | compute s | memory s | coll s | dominant "
                "| useful/dot | roofline frac |",
                "|---|---|---|---|---|---|---|---|"]
    for rec in load_records("single"):
        r = analyze_cell(rec)
        r["note"] = note(r)
        rows.append({
            "name": f"roofline/{r['arch']}/{r['shape']}",
            "us_per_call": max(r["compute_s"], r["memory_s"],
                               r["collective_s"]) * 1e6,
            "derived": f"dominant={r['dominant']};"
                       f"roofline_frac={r['roofline_fraction']:.3f};"
                       f"useful_ratio={r['useful_flops_ratio']:.2f}",
            **r,
        })
        table_md.append(
            f"| {r['arch']} | {r['shape']} | {r['compute_s']:.3f} "
            f"| {r['memory_s']:.3f} | {r['collective_s']:.3f} "
            f"| **{r['dominant']}** | {r['useful_flops_ratio']:.2f} "
            f"| {r['roofline_fraction']:.3f} |")
    os.makedirs(ARTIFACTS, exist_ok=True)
    with open(os.path.join(ARTIFACTS, "roofline.md"), "w") as f:
        f.write("\n".join(table_md) + "\n")
    with open(os.path.join(ARTIFACTS, "roofline.json"), "w") as f:
        json.dump(rows, f, indent=1, default=float)
    for r in rows:
        print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
    return rows


if __name__ == "__main__":
    run()
