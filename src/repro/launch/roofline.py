"""Roofline analysis (deliverable g): three terms per (arch x shape x mesh)
from the dry-run artifacts.

  compute    = HLO_FLOPs_per_device   / peak_FLOP/s      (197 TFLOP/s bf16)
  memory     = HLO_bytes_per_device   / HBM_bw           (819 GB/s)
  collective = collective_bytes/dev   / ICI link bw      (50 GB/s)

(The per-chip divisions cancel: cost_analysis and the HLO are per-device
SPMD programs.) MODEL_FLOPS uses 6·N·D for training and 2·N·D for inference
steps, with N_active for MoE.

Usage: PYTHONPATH=src python -m repro.launch.roofline [--dir dryrun_results]
Writes a markdown table to stdout and JSON to <dir>/roofline.json.
"""
from __future__ import annotations

import argparse
import glob
import json
import os

# Published per-chip peaks (bf16 FLOP/s, HBM bytes/s), keyed by JAX's
# ``Device.device_kind``. Source: Google Cloud TPU documentation, "TPU v5e".
PEAKS = {"TPU v5 lite": (197e12, 819e9)}
# The dry-run reports (this module's main) model a v5e pod.
DRYRUN_KIND = "TPU v5 lite"
LINK_BW = 50e9


def chip_peaks(device_kind: str):
    """(peak bf16 FLOP/s, HBM bytes/s) of one chip of ``device_kind``. A
    kind missing from ``PEAKS`` is an error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; add them to PEAKS") from None


def count_params(cfg) -> dict:
    d, hd = cfg.d_model, cfg.head_dim
    V = cfg.vocab_size
    H, KV = cfg.num_heads, cfg.num_kv_heads
    attn = d * hd * (2 * H + 2 * KV)
    out = {"embed": V * d * (1 if cfg.tie_embeddings else 2)}
    if cfg.family == "ssm":
        att_dim = d
        per_layer = 5 * d * att_dim + att_dim * d + 2 * d * cfg.d_ff \
            + d * d + 64 * (d + att_dim)
        out["layers"] = cfg.num_layers * per_layer
        out["active"] = out["layers"] + out["embed"]
        out["total"] = out["active"]
        return out
    if cfg.family == "hybrid":
        di = cfg.mamba_expand * d
        N = cfg.mamba_d_state
        mamba = 2 * d * di + 2 * d * N + d * (di // cfg.mamba_headdim) \
            + di * d
        shared = attn + 3 * d * cfg.d_ff
        out["layers"] = cfg.num_layers * mamba + shared
        out["active"] = out["layers"] + out["embed"]
        out["total"] = out["active"]
        return out
    if cfg.family == "encdec":
        per = attn + 2 * d * cfg.d_ff
        dec = 2 * attn + 2 * d * cfg.d_ff
        out["layers"] = cfg.encoder_layers * per + cfg.num_layers * dec
        out["active"] = out["layers"] + out["embed"]
        out["total"] = out["active"]
        return out
    if cfg.num_experts:
        expert = 3 * d * cfg.moe_d_ff
        per_layer_dense = attn + d * cfg.num_experts
        out["layers"] = cfg.num_layers * (
            per_layer_dense + cfg.num_experts * expert)
        active = cfg.num_layers * (
            per_layer_dense + cfg.experts_per_token * expert)
        out["active"] = active + out["embed"]
        out["total"] = out["layers"] + out["embed"]
        return out
    per_layer = attn + 3 * d * cfg.d_ff
    out["layers"] = cfg.num_layers * per_layer
    out["active"] = out["layers"] + out["embed"]
    out["total"] = out["active"]
    return out


def model_flops_per_device(cfg, shape, devices, micro=1) -> float:
    n = count_params(cfg)
    n_active = n["active"]
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens / devices
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens / devices
    # decode: one token per sequence + attention KV reads (2*2*S*d_kv FLOPs)
    toks = shape.global_batch
    attn_read = 4.0 * shape.seq_len * cfg.num_kv_heads * cfg.head_dim \
        * max(1, cfg.num_layers) * toks
    return (2.0 * n_active * toks + attn_read) / devices


def loop_factor(cfg, shape) -> int:
    """Static trip count of the layer scan (XLA cost_analysis counts while
    bodies ONCE — see EXPERIMENTS.md 'loop-accounting' note)."""
    if cfg.family == "hybrid":
        base = cfg.num_layers // cfg.attn_every
    elif cfg.family in ("ssm", "encdec"):
        base = cfg.num_layers
    else:
        base = cfg.num_layers // max(1, len(cfg.attn_pattern))
    if shape.kind == "train":
        from .input_specs import default_micro_batches
        base *= default_micro_batches(cfg)
    return max(1, base)


def kv_bytes_per_device(cfg, shape, dist_tp=16, dp=16):
    """Bytes of KV/state one device holds for this workload (local units,
    replica-split accounted)."""
    from jax.sharding import AbstractMesh
    from ..models.registry import build_model
    from ..models.tp import Dist
    sp = shape.kind == "decode" and shape.global_batch < 32
    mesh = AbstractMesh((16, 16), ("data", "model"))
    dist = Dist(mesh=mesh, dp_axes=("data",), sp=sp)
    model = build_model(cfg, dist)
    repl = model.ri.get("repl", 1) if isinstance(model.ri, dict) else 1
    if dist.sp:
        b_loc, toks = shape.global_batch, shape.seq_len // 16
    else:
        b_loc, toks = shape.global_batch // dist.dp, shape.seq_len
    toks_attn = -(-toks // max(1, repl))
    total = 0
    for sp in model.kv_specs():
        if sp.kind in ("mamba", "rwkv"):
            total += b_loc * sp.page_units
        elif sp.kind == "cross_attn":
            total += b_loc * sp.pages_for_tokens(cfg.encoder_seq)                 * sp.page_units
        elif sp.kind == "swa":
            w = min(sp.sliding_window, toks_attn)
            total += b_loc * sp.pages_for_tokens(max(1, w)) * sp.page_units
        else:
            total += b_loc * sp.pages_for_tokens(toks_attn) * sp.page_units
    return 2 * total            # bf16


def analytic_terms(cfg, shape, devices):
    """First-principles compute/memory terms (per device, seconds)."""
    n = count_params(cfg)
    tp, dp = 16, devices // 16
    params_dev = 2 * n["total"] / tp / (1 if shape.kind != "train" else 1)
    kvb = kv_bytes_per_device(cfg, shape)
    d_attn = cfg.num_kv_heads * cfg.head_dim
    Lf = getattr(cfg, "num_layers", 0)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        flops = 6.0 * n["active"] * tokens / devices
        # causal attention flops (fwd+bwd ~3x fwd)
        attn = 3 * 2 * 2 * cfg.num_heads * cfg.head_dim             * shape.seq_len ** 2 / 2 * shape.global_batch * Lf / devices
        flops += attn
        act = tokens * cfg.d_model * 2 * Lf * 4 / devices
        bytes_dev = 3 * params_dev * 2 + act     # fp32 grads+params rw
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        flops = 2.0 * n["active"] * tokens / devices
        flops += 2 * 2 * cfg.num_heads * cfg.head_dim             * shape.seq_len ** 2 / 2 * shape.global_batch * Lf / devices
        bytes_dev = params_dev + 2 * kvb             + tokens / devices * cfg.d_model * 2 * Lf
    else:
        toks = shape.global_batch
        flops = 2.0 * n["active"] * toks / devices             + 4.0 * shape.seq_len * d_attn * Lf * toks / devices
        bytes_dev = params_dev + kvb
    return flops, bytes_dev


def load(dirname):
    from ..configs import ARCHS, SHAPES_BY_NAME
    peak_flops, hbm_bw = chip_peaks(DRYRUN_KIND)
    rows = []
    for f in sorted(glob.glob(os.path.join(dirname, "*.json"))):
        if f.endswith("roofline.json"):
            continue
        r = json.load(open(f))
        if r.get("status") == "skipped":
            rows.append({"arch": r["arch"], "shape": r["shape"],
                         "mesh": f.split("__")[-1][:-5], "status": "skipped"})
            continue
        if r.get("status") != "ok":
            rows.append({"arch": r["arch"], "shape": r["shape"],
                         "mesh": r.get("mesh", "?"), "status": "error"})
            continue
        cfg = ARCHS[r["arch"]]
        shape = SHAPES_BY_NAME[r["shape"]]
        coll = sum(v["bytes"] for v in r["collectives"].values())
        lf = loop_factor(cfg, shape)
        a_flops, a_bytes = analytic_terms(cfg, shape, r["devices"])
        t_c = a_flops / peak_flops
        t_m = a_bytes / hbm_bw
        t_x = coll * lf / LINK_BW
        dominant = max((("compute", t_c), ("memory", t_m),
                        ("collective", t_x)), key=lambda kv: kv[1])[0]
        mf = model_flops_per_device(cfg, shape, r["devices"])
        # HLO-direct (uncorrected) terms for transparency
        useful = mf / max(1.0, r.get("flops_per_device", 1) * lf)
        bound = max(t_c, t_m, t_x)
        # roofline fraction: useful model FLOPs at peak vs the bound term
        frac = (mf / peak_flops) / bound if bound else 0.0
        pool = 2 * r.get("buffer_units_per_device", 0)
        temp = r.get("temp_size_in_bytes", 0)
        copies = int(temp // pool) if pool else 0
        adj_peak = r.get("peak_bytes_per_device", 0) - copies * pool
        rows.append({
            "arch": r["arch"], "shape": r["shape"], "mesh": r["mesh"],
            "status": "ok", "devices": r["devices"],
            "t_compute_s": t_c, "t_memory_s": t_m, "t_collective_s": t_x,
            "hlo_t_compute_s": r.get("flops_per_device", 0) / peak_flops,
            "hlo_t_memory_s": r.get("bytes_accessed_per_device", 0) / hbm_bw,
            "loop_factor": lf,
            "dominant": dominant, "model_flops_per_dev": mf,
            "hlo_flops_per_dev": r.get("flops_per_device", 0),
            "useful_ratio": useful, "roofline_frac": frac,
            "peak_gb": r.get("peak_bytes_per_device", 0) / 1e9,
            "adj_peak_gb": adj_peak / 1e9,
            "pool_copies": copies,
            "collective_bytes": coll,
            "coll_detail": {k: v for k, v in r["collectives"].items()
                            if v["count"]},
        })
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="dryrun_results")
    args = ap.parse_args()
    rows = load(args.dir)
    with open(os.path.join(args.dir, "roofline.json"), "w") as fh:
        json.dump(rows, fh, indent=1)
    hdr = ("| arch | shape | mesh | compute s | memory s | coll s | "
           "dominant | useful | roofline | peak GB (adj) |")
    print(hdr)
    print("|" + "---|" * 10)
    for r in rows:
        if r["status"] != "ok":
            print(f"| {r['arch']} | {r['shape']} | {r['mesh']} | "
                  + f"{r['status']} |" + " |" * 6)
            continue
        print(f"| {r['arch']} | {r['shape']} | {r['mesh']} "
              f"| {r['t_compute_s']:.2e} | {r['t_memory_s']:.2e} "
              f"| {r['t_collective_s']:.2e} | {r['dominant']} "
              f"| {r['useful_ratio']:.2f} | {r['roofline_frac']:.2f} "
              f"| {r['peak_gb']:.1f} ({r['adj_peak_gb']:.1f}) |")


if __name__ == "__main__":
    main()
