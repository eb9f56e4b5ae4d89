"""Roofline analysis from the compiled dry-run artifacts.

For every (arch × shape × mesh) cell produced by ``repro.launch.dryrun``:

  compute term    = HLO_FLOPs_per_device / peak_FLOP/s
  memory term     = HLO_bytes_per_device / HBM_bw
  collective term = collective_bytes_per_device / link_bw

Hardware model (TPU v5e-class, per chip): 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI.

XLA's ``cost_analysis()`` counts while-loop bodies ONCE — a scan-over-layers
program under-reports by ~num_layers×. ``launch.hlo_analysis`` re-derives
dot FLOPs and collective bytes from the post-SPMD HLO with
known_trip_count multipliers; when the saved HLO is available we use those
and scale the cost-analysis byte count by the same trip-count ratio
(documented assumption: loop bodies dominate both terms equally).

MODEL_FLOPS uses 6·N·D for training (N params, D tokens) and 2·N_active·D
for inference; the ratio MODEL_FLOPS/HLO_FLOPs exposes remat/redundancy
waste (>1/3 for a remat-everything training step is good; decode is
memory-bound so the ratio matters less there).
"""
from __future__ import annotations

import argparse
import json
from pathlib import Path

import jax

from repro.configs.base import ARCHITECTURES, SHAPES, get_config
from repro.models.model import count_params
from repro.obs.prof import LINK_BW, PEAKS, analyze_hlo

# hardware model now lives in repro.obs.prof (shared with the runtime
# roofline gauges); this table is always priced for the TPU part.
PEAK_FLOPS, HBM_BW = PEAKS["TPU v5 lite"]

DRYRUN_DIR = Path(__file__).resolve().parent.parent / "results" / "dryrun"


def model_flops_per_device(arch: str, shape_name: str, devices: int) -> float:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    n_active = count_params(cfg, active_only=True)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens / devices
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens / devices
    tokens = shape.global_batch          # one new token per sequence
    return 2.0 * n_active * tokens / devices


def min_bytes_per_device(arch: str, shape_name: str, devices: int,
                         dp: int = 16, tp: int = 16) -> float:
    """Analytic irreducible HBM traffic per device per step (lower bound).

    Counts only unavoidable streams: parameter/optimizer state movement,
    saved activations at remat granularity, KV-cache reads. XLA's
    ``bytes accessed`` is the matching UPPER bound (every fusion operand
    billed as HBM). Truth lives between; both are reported.
    """
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    n = count_params(cfg)
    n_dev = n / devices                       # params fully sharded (FSDP)
    tokens_dev = shape.global_batch * shape.seq_len / (devices / tp)
    d, L, V = cfg.d_model, cfg.num_layers, cfg.vocab_size
    if shape.kind == "train":
        # params: fwd read + bwd read + write (bf16) = 6 B/param
        # grads: write + read (f32)               = 8 B/param
        # adam m, v: read + write each (f32)      = 16 B/param
        pbytes = 30.0 * n_dev
        # remat: save + reload layer inputs (bf16) + block output write
        act = tokens_dev * L * d * 6.0
        logits = tokens_dev * V * 4.0 / tp      # f32 logits, vocab-sharded
        return pbytes + act + logits
    if shape.kind == "prefill":
        pbytes = 2.0 * n_dev
        act = tokens_dev * L * d * 4.0
        return pbytes + act
    # decode: read every (active) weight shard once + stream the KV cache
    from repro.models.model import cache_shapes
    import math as _m
    cache_elems = sum(_m.prod(s) for s in jax.tree.leaves(
        cache_shapes(cfg, shape.global_batch, shape.seq_len),
        is_leaf=lambda x: isinstance(x, tuple)))
    return 2.0 * n_dev + 2.0 * cache_elems / devices


def analyze_cell(rec: dict, hlo_path: Path | None) -> dict:
    arch, shape_name = rec["arch"], rec["shape"]
    devices = rec["devices"]
    flops_ca = rec.get("flops_per_device", 0.0)
    bytes_ca = rec.get("bytes_accessed_per_device", 0.0)
    coll = dict(rec.get("collective_bytes_per_device", {}))

    flops = flops_ca
    trip_ratio = 1.0
    if hlo_path and hlo_path.exists():
        h = analyze_hlo(hlo_path.read_text())
        if h["dot_flops_per_device"] > flops_ca:
            flops = h["dot_flops_per_device"]
            trip_ratio = flops / max(flops_ca, 1.0)
        if h["collective_bytes_per_device"]:
            coll = h["collective_bytes_per_device"]
    # raw cost-analysis bytes: while bodies counted once (under-count) but
    # every fusion operand billed as HBM (over-count); used UNSCALED — the
    # trip-corrected variant proved unstable across dtype changes. The
    # analytic min_bytes column bounds from below.
    mem_bytes = bytes_ca
    coll_bytes = sum(coll.values())
    min_bytes = min_bytes_per_device(arch, shape_name, devices)

    t_compute = flops / PEAK_FLOPS
    t_memory = mem_bytes / HBM_BW            # XLA upper bound
    t_memory_min = min_bytes / HBM_BW        # analytic lower bound
    t_coll = coll_bytes / LINK_BW
    terms = {"compute": t_compute, "memory": t_memory,
             "collective": t_coll}
    dominant = max(terms, key=terms.get)
    bound = max(terms.values())
    # achievable bound: memory credited at the analytic minimum
    bound_min = max(t_compute, t_memory_min, t_coll)
    mf = model_flops_per_device(arch, shape_name, devices)
    return {
        "arch": arch, "shape": shape_name, "mesh": rec["mesh"],
        "compute_s": t_compute, "memory_s": t_memory,
        "memory_min_s": t_memory_min,
        "collective_s": t_coll, "dominant": dominant,
        "model_flops_per_device": mf,
        "useful_ratio": mf / max(flops, 1.0),
        "roofline_step_s": bound,
        "roofline_fraction": mf / PEAK_FLOPS / bound if bound > 0 else 0.0,
        "roofline_fraction_achievable": (mf / PEAK_FLOPS / bound_min
                                         if bound_min > 0 else 0.0),
        "hlo_flops_per_device": flops,
        "hlo_bytes_per_device": mem_bytes,
        "min_bytes_per_device": min_bytes,
        "collective_bytes_per_device": coll_bytes,
        "peak_hbm_bytes": rec.get("memory", {}).get("peak_bytes"),
        "trip_ratio": round(trip_ratio, 1),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dryrun-dir", type=Path, default=DRYRUN_DIR)
    ap.add_argument("--mesh", default="16x16",
                    help="roofline table mesh (single-pod per spec)")
    ap.add_argument("--out", type=Path,
                    default=DRYRUN_DIR.parent / "roofline.json")
    args = ap.parse_args()

    results = []
    for f in sorted(args.dryrun_dir.glob("*.json")):
        rec = json.loads(f.read_text())
        if not rec.get("ok"):
            continue
        if rec["mesh"] != args.mesh:
            continue
        hlo = f.with_suffix("").with_suffix("")  # strip .json
        hlo = args.dryrun_dir / (f.stem + ".hlo.txt")
        results.append(analyze_cell(rec, hlo))

    results.sort(key=lambda r: (r["arch"], r["shape"]))
    args.out.write_text(json.dumps(results, indent=1))

    hdr = (f"{'arch':<22}{'shape':<13}{'compute_s':>10}{'mem_xla_s':>10}"
           f"{'mem_min_s':>10}{'coll_s':>9}  {'dominant':<11}{'useful':>7}"
           f"{'roofl%':>7}{'achv%':>7}")
    print(hdr)
    print("-" * len(hdr))
    for r in results:
        print(f"{r['arch']:<22}{r['shape']:<13}"
              f"{r['compute_s']:>10.4f}{r['memory_s']:>10.4f}"
              f"{r['memory_min_s']:>10.4f}"
              f"{r['collective_s']:>9.4f}  {r['dominant']:<11}"
              f"{r['useful_ratio']:>7.2f}"
              f"{100*r['roofline_fraction']:>6.1f}%"
              f"{100*r['roofline_fraction_achievable']:>6.1f}%")
    return results


if __name__ == "__main__":
    main()
