"""Roofline analysis from the dry-run artifacts (EXPERIMENTS.md §Roofline).

Reads experiments/dryrun/*.json (written by repro.launch.dryrun), derives the
three roofline terms per (arch × shape × variant) on the single-pod mesh:

    T_compute = HLO_FLOPs   / (chips · peak bf16 FLOP/s)
    T_memory  = HLO_bytes   / (chips · peak HBM B/s)
    T_coll    = coll_bytes  / (chips · peak ICI B/s)

with the TPU v5e peaks of ``repro.launch.metrics.DEVICE_PEAKS``.

FLOPs/bytes/coll_bytes use the L=p vs L=2p unrolled deltas scaled to full
depth (scan bodies are counted once by XLA cost analysis — see dryrun.py).
MODEL_FLOPS = 6·N_active·D_tokens for train, 2·N_active·D for forward-only.
"""
from __future__ import annotations

import glob
import json
import os
from typing import Dict, List, Optional

from repro.launch.metrics import DEVICE_PEAKS

# the dry run models TPU v5e pods; peaks come from the one device table
_PEAKS = DEVICE_PEAKS["TPU v5 lite"]
PEAK_FLOPS = _PEAKS["bf16_flops"]
HBM_BW = _PEAKS["hbm_bytes_per_s"]
ICI_BW = _PEAKS["ici_bytes_per_s"]

DRYRUN_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments", "dryrun")

_SHAPE_TOKENS = {
    "train_4k": 4096 * 256,
    "prefill_32k": 32768 * 32,
    "decode_32k": 128,            # one token per sequence
    "long_500k": 1,
}


def param_counts(arch: str) -> Dict[str, float]:
    """Total + active parameter counts from the registered config."""
    from repro import configs
    from repro.models.model import ModelConfig
    cfg = configs.get_config(arch)
    D, F, V, L = cfg.d_model, cfg.d_ff, cfg.vocab_size, cfg.num_layers
    H, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    attn = D * H * Dh + 2 * D * Hkv * Dh + H * Dh * D
    total = active = 0.0
    for i in range(L):
        if cfg.family == "ssm":
            blk = 5 * D * D + D * D          # rwkv time (r,k,v,g,o) + lora-ish
            blk += D * F + F * D + D * D     # channel mix
            total += blk; active += blk
        elif cfg.family == "hybrid":
            ssm = 2 * D * D + 2 * D * H * cfg.ssm_state + D * H
            blk = attn + ssm + 3 * D * F
            total += blk; active += blk
        elif cfg.family == "moe" and i >= cfg.first_k_dense:
            e_blk = 3 * D * F
            total += attn + cfg.num_experts * e_blk
            active += attn + cfg.num_experts_per_tok * e_blk
        elif cfg.family == "moe":
            blk = attn + 3 * D * cfg.d_ff_dense
            total += blk; active += blk
        else:
            blk = attn + 3 * D * F
            total += blk; active += blk
    emb = V * D * (1 if cfg.tie_embeddings else 2)
    total += emb
    active += emb
    return {"total": total, "active": active}


def load_cells(mesh: str = "single", include_perf_variants: bool = False) -> List[Dict]:
    out = []
    for path in sorted(glob.glob(os.path.join(DRYRUN_DIR, f"*__{mesh}__*.json"))):
        tag = os.path.basename(path).split("__")[-1][:-5]
        if not include_perf_variants and (
                "@" in tag or tag in ("subset", "select")):
            continue                      # §Perf hillclimb artifacts
        with open(path) as f:
            d = json.load(f)
        if d.get("ok"):
            out.append(d)
    return out


def roofline_row(d: Dict) -> Optional[Dict]:
    mesh_shape = d.get("mesh_shape", {})
    chips = 1
    for v in mesh_shape.values():
        chips *= v
    src = d.get("scaled") or {
        "flops": d["full"]["flops"],
        "bytes_accessed": d["full"]["bytes_accessed"],
        "collective_bytes": d["full"]["collectives"]["total_bytes"],
    }
    # XLA cost_analysis reports PER-PARTITION numbers (the compiled module is
    # the per-device program — verified: ×chips ≈ 1.8·6ND for dense trains,
    # the expected remat+attention overhead). Collective operand bytes parsed
    # from the partitioned HLO are also per-device.
    # Guard: L2−L1 deltas can go slightly negative from fusion differences;
    # never report below the L1 measurement.
    flops = max(src["flops"], d.get("unrolled_p1", d["full"])["flops"])
    bytes_acc = max(src["bytes_accessed"],
                    d.get("unrolled_p1", d["full"])["bytes_accessed"])
    coll = max(src["collective_bytes"] if "collective_bytes" in src else 0.0,
               0.0)
    t_comp = flops / PEAK_FLOPS
    t_mem = bytes_acc / HBM_BW
    t_coll = coll / ICI_BW
    src = {"flops": flops * chips, "bytes_accessed": bytes_acc * chips,
           "collective_bytes": coll * chips}
    dom = max(("compute", t_comp), ("memory", t_mem),
              ("collective", t_coll), key=lambda kv: kv[1])
    counts = param_counts(d["arch"])
    tokens = _SHAPE_TOKENS[d["shape"]]
    mult = 6.0 if d["shape"] == "train_4k" else 2.0
    graft_note = ""
    if d["variant"] == "graft":
        # selection fwd (2·N·D) + subset train (6·N·D·R/K with R=K/2 max rank)
        model_flops = 2.0 * counts["active"] * tokens + \
            6.0 * counts["active"] * tokens * 0.5
        graft_note = "graft(R=K/2)"
    else:
        model_flops = mult * counts["active"] * tokens
    useful = model_flops / src["flops"] if src["flops"] else 0.0
    mem = d["full"]["memory"]
    return {
        "arch": d["arch"], "shape": d["shape"], "variant": d["variant"],
        "chips": chips,
        "t_compute_s": t_comp, "t_memory_s": t_mem, "t_collective_s": t_coll,
        "dominant": dom[0], "bound_s": dom[1],
        "model_flops": model_flops, "hlo_flops": src["flops"],
        "useful_ratio": useful, "note": graft_note,
        "temp_gib": mem.get("temp_size_in_bytes", 0) / 2 ** 30,
        "args_gib": mem.get("argument_size_in_bytes", 0) / 2 ** 30,
    }


def format_table(rows: List[Dict]) -> str:
    hdr = (f"{'arch':24s} {'shape':12s} {'variant':8s} {'T_comp(ms)':>10s} "
           f"{'T_mem(ms)':>10s} {'T_coll(ms)':>10s} {'dominant':>10s} "
           f"{'useful':>7s} {'temp GiB':>9s}")
    lines = [hdr, "-" * len(hdr)]
    for r in rows:
        lines.append(
            f"{r['arch']:24s} {r['shape']:12s} {r['variant']:8s} "
            f"{r['t_compute_s']*1e3:10.2f} {r['t_memory_s']*1e3:10.2f} "
            f"{r['t_collective_s']*1e3:10.2f} {r['dominant']:>10s} "
            f"{r['useful_ratio']:7.3f} {r['temp_gib']:9.2f}")
    return "\n".join(lines)


def run() -> List[str]:
    rows = [roofline_row(d) for d in load_cells("single")]
    rows = [r for r in rows if r]
    out = []
    for r in rows:
        out.append(
            f"roofline_{r['arch']}_{r['shape']}_{r['variant']},0.0,"
            f"Tc={r['t_compute_s']*1e3:.2f}ms;Tm={r['t_memory_s']*1e3:.2f}ms;"
            f"Tcoll={r['t_collective_s']*1e3:.2f}ms;dom={r['dominant']};"
            f"useful={r['useful_ratio']:.3f}")
    return out


def main():
    rows = [roofline_row(d) for d in load_cells("single")]
    rows = [r for r in rows if r]
    rows.sort(key=lambda r: (r["arch"], r["shape"], r["variant"]))
    print(format_table(rows))


if __name__ == "__main__":
    main()
