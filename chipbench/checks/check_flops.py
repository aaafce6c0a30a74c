"""Check ``chipbench/flops.py`` against XLA's own count.

    JAX_PLATFORMS=cpu python -m chipbench.checks.check_flops

Compiles, on the CPU, the program's selection forward (hidden states of
all rows, then the head at the probe positions) and the forward and
backward of the per-sequence loss over a few rows, for a small dense
configuration with no remat, unrolled layers (XLA counts a loop body
once) and dense attention (which computes every
query-key pair, so the count is taken with ``causal=False``), and
compares ``compiled.cost_analysis()["flops"]`` with the benchmark's count.
XLA also counts elementwise work, which the benchmark leaves out, so the
ratio must lie in [1, 1.1).
"""
from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)


def main() -> int:
    import jax
    import jax.numpy as jnp
    from repro.models import model as model_lib

    from chipbench import flops
    cfg = model_lib.ModelConfig(
        name="check", family="dense", num_layers=2, d_model=512,
        num_heads=8, num_kv_heads=2, head_dim=64, d_ff=1408, vocab_size=2048,
        remat="none", attn_backend="dense", param_dtype="float32",
        scan_layers=False)
    dims = flops.Dims.from_config({
        "hidden_size": cfg.d_model, "intermediate_size": cfg.d_ff,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "num_hidden_layers": cfg.num_layers, "vocab_size": cfg.vocab_size})
    K, R, S, P = 4, 2, 128, 32
    params = jax.eval_shape(lambda: model_lib.init_params(
        cfg, jax.random.PRNGKey(0)))
    tok = jax.ShapeDtypeStruct((K, S), jnp.int32)
    sub = jax.ShapeDtypeStruct((R, S), jnp.int32)

    def selection(p, t):
        h, _ = model_lib.forward_hiddens(cfg, p, {"tokens": t, "labels": t})
        return model_lib.logits_from_hiddens(cfg, p, h[:, ::S // P])

    def train(p, t):
        return jax.grad(lambda q: jnp.sum(model_lib.per_example_loss(
            cfg, q, {"tokens": t, "labels": t})))(p)

    ok = True
    for name, fn, arg, want in [
            ("selection forward", selection, tok,
             flops.selection_forward_flops(dims, K, S, P, causal=False)),
            ("subset forward+backward", train, sub,
             flops.subset_train_flops(dims, R, S, causal=False))]:
        got = jax.jit(fn).lower(params, arg).compile().cost_analysis()["flops"]
        ratio = got / want
        ok &= 1.0 <= ratio < 1.1
        print(f"{name}: XLA {got:.4e}, chipbench {want:.4e}, "
              f"ratio {ratio:.4f}")
    print("ok" if ok else "MISMATCH")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
