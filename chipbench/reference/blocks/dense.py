"""The dense pre-norm decoder block.

As the configuration files describe it and as published up to the
departures a file lists: RMSNorm ``x * rsqrt(mean(x^2) + eps) * (1 + w)``;
rotary embedding on the two halves of every head dimension with
frequencies ``theta^(-i / (head_dim / 2))``; causal attention with
``num_key_value_heads`` shared by groups of query heads, scores scaled by
``1 / sqrt(head_dim)``; a SwiGLU MLP ``(silu(x Wg) * (x Wu)) Wd``; both
residual branches sequential. The final norm is the same RMSNorm.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp

from chipbench.reference.model import mm

# configuration keys (Hugging Face names) → the program's ModelConfig fields
KEYS = {
    "intermediate_size": "d_ff", "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
    "rms_norm_eps": "rms_eps", "rope_theta": "rope_theta",
    "hidden_act": "mlp_activation",
}


class Dims(NamedTuple):
    d: int
    f: int
    heads: int
    kv_heads: int
    head_dim: int
    eps: float
    theta: float
    dtype: str

    @classmethod
    def from_config(cls, c: Dict[str, Any]) -> "Dims":
        if c.get("hidden_act", "silu") != "silu":
            raise ValueError(f"the dense block's MLP is SwiGLU, not "
                             f"{c['hidden_act']!r}")
        return cls(c["hidden_size"], c["intermediate_size"],
                   c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"], float(c.get("rms_norm_eps", 1e-5)),
                   float(c.get("rope_theta", 1e4)),
                   c.get("torch_dtype", "bfloat16"))


def init_layer(m: Dims, k):
    """One layer's weights: normal draws scaled by ``d^-1/2`` (input
    projections), ``(heads * head_dim)^-1/2`` (attention output) and
    ``f^-1/2`` (MLP output), in the stored type; norm scales zero. Key
    splits: (attention, MLP, ...) from the layer's key; then one per
    matrix."""
    bf = jnp.dtype(m.dtype)
    D, F, H, G, Dh = m.d, m.f, m.heads, m.kv_heads, m.head_dim

    def normal(k, shape, scale):
        return (jax.random.normal(k, shape) * scale).astype(bf)

    ks = jax.random.split(k, 8)
    a = jax.random.split(ks[0], 4)
    f = jax.random.split(ks[1], 3)
    s = D ** -0.5
    return {
        "ln1": jnp.zeros((D,), jnp.float32),
        "attn": {"wq": normal(a[0], (D, H, Dh), s),
                 "wk": normal(a[1], (D, G, Dh), s),
                 "wv": normal(a[2], (D, G, Dh), s),
                 "wo": normal(a[3], (H, Dh, D), (H * Dh) ** -0.5)},
        "ln2": jnp.zeros((D,), jnp.float32),
        "mlp": {"w_gate": normal(f[0], (D, F), s),
                "w_up": normal(f[1], (D, F), s),
                "w_down": normal(f[2], (F, D), F ** -0.5)},
    }


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + w)


def rope(x, theta):
    """x: (S, heads, head_dim)."""
    S, _, Dh = x.shape
    half = Dh // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


Q_BLOCK = 512


def attention(m: Dims, p, x, f8: bool):
    S = x.shape[0]
    group = m.heads // m.kv_heads
    q = rope(mm("sd,dhk->shk", x, p["wq"], f8), m.theta) / jnp.sqrt(
        jnp.float32(m.head_dim))
    k = rope(mm("sd,dhk->shk", x, p["wk"], f8), m.theta)
    v = mm("sd,dhk->shk", x, p["wv"], f8)
    k = jnp.repeat(k, group, axis=1)           # query head h reads kv h // group
    v = jnp.repeat(v, group, axis=1)
    outs = []
    for s0 in range(0, S, Q_BLOCK):            # query blocks keep scores small
        qb = q[s0:s0 + Q_BLOCK]
        n = qb.shape[0]
        scores = mm("qhd,khd->hqk", qb, k, f8)
        causal = jnp.arange(S)[None, :] <= (s0 + jnp.arange(n))[:, None]
        scores = jnp.where(causal[None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        outs.append(mm("hqk,khd->qhd", probs, v, f8))
    out = jnp.concatenate(outs, axis=0)
    return mm("shk,hkd->sd", out, p["wo"], f8)


def mlp(p, x, f8: bool):
    gate = jax.nn.silu(mm("sd,df->sf", x, p["w_gate"], f8))
    up = mm("sd,df->sf", x, p["w_up"], f8)
    return mm("sf,fd->sd", gate * up, p["w_down"], f8)


def block(m: Dims, p, x, f8: bool = False):
    """One decoder layer on one sequence; ``p`` is that layer's weights."""
    x = x + attention(m, p["attn"], rms(x, p["ln1"], m.eps), f8)
    return x + mlp(p["mlp"], rms(x, p["ln2"], m.eps), f8)


def init_final_norm(m: Dims):
    return jnp.zeros((m.d,), jnp.float32)


def final_norm(m: Dims, w, x):
    return rms(x, w, m.eps)


def layer_forward_flops(m: Dims, rows: int, seq: int,
                        causal: bool = True) -> float:
    """One layer's forward over ``rows`` sequences of ``seq`` tokens:
    q/k/v/o projections, the SwiGLU MLP, and attention (with ``causal``,
    query i attends to i + 1 keys; else to all ``seq``)."""
    tokens = rows * seq
    proj = 2 * m.d * (m.heads + 2 * m.kv_heads) * m.head_dim \
        + 2 * m.heads * m.head_dim * m.d
    mlp = 3 * 2 * m.d * m.f
    keys = (seq + 1) / 2 if causal else seq
    attn = rows * 2 * 2 * m.heads * m.head_dim * seq * keys
    return tokens * (proj + mlp) + attn
