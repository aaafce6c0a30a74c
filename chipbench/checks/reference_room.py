"""Run the float32 reference alone, with no program, at a size whose
float32 state does not fit on one chip.

    python -m chipbench.checks.reference_room [--layers 4]

The dense block at StableLM-2-12B's widths (d 5,120; 32 query heads and 8
key-value heads of 160; d_ff 13,824; an untied 100,352-row vocabulary),
``--layers`` layers, three steps of the ``graft-s1`` traffic with K = 16
rows: at 4 layers 2.14e9 parameters, whose bfloat16 weights, float32
gradient and float32 AdamW moments take 14 bytes a parameter, 29.9 GB.
Prints one JSON line: the seconds ``reference.train.steps`` took, the
chip's ``peak_bytes_in_use`` and its losses, or the error it stopped on.
Needs one chip; the reference runs on the first.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

WIDTHS = {"hidden_size": 5120, "intermediate_size": 13824,
          "num_attention_heads": 32, "num_key_value_heads": 8,
          "head_dim": 160, "vocab_size": 100352,
          "tie_word_embeddings": False, "rope_theta": 10000.0,
          "rms_norm_eps": 1e-05, "torch_dtype": "bfloat16"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--seed", type=int, default=3_000_001_501)
    args = ap.parse_args(argv)
    import jax

    from chipbench.reference import train as ref_train
    with open(os.path.join(ROOT, "chipbench", "traffic", "graft-s1.json")) as f:
        traffic = json.load(f)
    config = dict(WIDTHS, num_hidden_layers=args.layers)
    dev = jax.devices()[0]
    t = time.time()
    out = {"layers": args.layers, "device": dev.device_kind}
    try:
        ref = ref_train.steps(config, traffic, traffic["batch_per_chip"],
                              args.seed)
        out.update(ok=True, losses=ref["losses"],
                   ranks=[int(s["rank"]) for s in ref["select"]])
    except Exception as e:          # noqa: BLE001 - reported, not hidden
        out.update(ok=False, error=f"{type(e).__name__}: {e}"[:2000])
    out["seconds"] = time.time() - t
    out["peak_bytes_in_use"] = (dev.memory_stats() or {}).get(
        "peak_bytes_in_use")
    print(json.dumps(out), flush=True)
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
