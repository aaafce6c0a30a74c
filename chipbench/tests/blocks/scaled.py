"""A block for the tests alone: the dense block with its queries scaled by
the configuration's ``attn_query_scale`` (the program's ``query_scale``)
in place of ``1 / sqrt(head_dim)``. It declares one key more than the
dense block and reuses the rest of it."""
from __future__ import annotations

from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp

from chipbench.reference import blocks
from chipbench.reference.model import mm

dense = blocks.load("dense")
KEYS = dict(dense.KEYS, attn_query_scale="query_scale")


class Dims(NamedTuple):
    base: Any               # the dense block's dimensions
    scale: float

    @classmethod
    def from_config(cls, c: Dict[str, Any]) -> "Dims":
        return cls(dense.Dims.from_config(c), float(c["attn_query_scale"]))


def init_layer(m: Dims, k):
    return dense.init_layer(m.base, k)


def attention(m: Dims, p, x, f8: bool):
    b = m.base
    S = x.shape[0]
    group = b.heads // b.kv_heads
    q = dense.rope(mm("sd,dhk->shk", x, p["wq"], f8), b.theta) * m.scale
    k = jnp.repeat(dense.rope(mm("sd,dhk->shk", x, p["wk"], f8), b.theta),
                   group, axis=1)
    v = jnp.repeat(mm("sd,dhk->shk", x, p["wv"], f8), group, axis=1)
    scores = mm("qhd,khd->hqk", q, k, f8)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
    return mm("shk,hkd->sd", mm("hqk,khd->qhd", probs, v, f8), p["wo"], f8)


def block(m: Dims, p, x, f8: bool = False):
    eps = m.base.eps
    x = x + attention(m, p["attn"], dense.rms(x, p["ln1"], eps), f8)
    return x + dense.mlp(p["mlp"], dense.rms(x, p["ln2"], eps), f8)


def init_final_norm(m: Dims):
    return dense.init_final_norm(m.base)


def final_norm(m: Dims, w, x):
    return dense.final_norm(m.base, w, x)


def layer_forward_flops(m: Dims, rows: int, seq: int,
                        causal: bool = True) -> float:
    return dense.layer_forward_flops(m.base, rows, seq, causal)
