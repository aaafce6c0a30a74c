"""Plain reference of the dense pre-norm decoder the configurations run.

One block, as the configuration files describe it and as published up to
the departures its file lists: RMSNorm ``x * rsqrt(mean(x^2) + eps) *
(1 + w)``; rotary embedding on the two halves of every head dimension with
frequencies ``theta^(-i / (head_dim / 2))``; causal attention with
``num_key_value_heads`` shared by groups of query heads, scores scaled by
``1 / sqrt(head_dim)``; a SwiGLU MLP ``(silu(x Wg) * (x Wu)) Wd``; both
residual branches sequential. The head is the embedding's transpose when
tied. Loss: mean next-token cross entropy over a sequence.

Everything is ``jax.numpy`` in float32 with every matrix product at
``highest`` precision, one sequence at a time on one chip; the gradient is
taken layer by layer (``Programs``), so that the reference's weights,
gradient and AdamW moments fit on one chip beside nothing else. ``mm``
can instead run every product as a float8 training path would (its
``f8``): the control of the correctness check. Weights are drawn from the seed
the way the program draws its own (the same key splits, distributions
and scales) and stored as the configuration states them: matrices in its
``torch_dtype`` (bfloat16), norm scales in float32.

Nothing here imports the program.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp

Params = Dict[str, Any]


class Dims(NamedTuple):
    d: int
    f: int
    heads: int
    kv_heads: int
    head_dim: int
    layers: int
    vocab: int
    eps: float
    theta: float
    tied: bool
    dtype: str

    @classmethod
    def from_config(cls, c: Dict[str, Any]) -> "Dims":
        return cls(c["hidden_size"], c["intermediate_size"],
                   c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"], c["num_hidden_layers"], c["vocab_size"],
                   float(c.get("rms_norm_eps", 1e-5)),
                   float(c.get("rope_theta", 1e4)),
                   bool(c.get("tie_word_embeddings", False)),
                   c.get("torch_dtype", "bfloat16"))


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------

def init(m: Dims, key) -> Params:
    """The configuration's initial weights for ``key``, the seed's
    ``PRNGKey`` (an argument, so that one compiled program serves every
    seed): normal draws scaled by 0.02 (embedding), ``d^-1/2`` (input
    projections and head), ``(heads * head_dim)^-1/2`` (attention output)
    and ``f^-1/2`` (MLP output); norm scales zero. Key splits:
    (embedding, layers, head, -) from the seed's key; one key a layer;
    (attention, MLP, ...) from each layer's; then one per matrix."""
    bf = jnp.dtype(m.dtype)
    k_embed, k_blocks, k_head, _ = jax.random.split(key, 4)
    D, F, H, G, Dh = m.d, m.f, m.heads, m.kv_heads, m.head_dim

    def normal(k, shape, scale):
        return (jax.random.normal(k, shape) * scale).astype(bf)

    def layer(k):
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

    layers = [layer(k) for k in jax.random.split(k_blocks, m.layers)]
    params: Params = {
        "embed": normal(k_embed, (m.vocab, D), 0.02),
        "final_norm": jnp.zeros((D,), jnp.float32),
        "blocks": jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers),
    }
    if not m.tied:
        params["lm_head"] = normal(k_head, (D, m.vocab), D ** -0.5)
    return params


# ---------------------------------------------------------------------------
# products: float32 at highest precision, or the float8 control
# ---------------------------------------------------------------------------

F8_MAX = {jnp.float8_e4m3fn: 448.0, jnp.float8_e5m2: 57344.0}


def _f8(x, dtype=jnp.float8_e4m3fn):
    """``x`` rounded to float8 with one scale for the tensor, in float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX[dtype]
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def _einsum(eq, a, b):
    return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _f8_einsum(eq, a, b):
    return _einsum(eq, _f8(a), _f8(b))


def _f8_fwd(eq, a, b):
    qa, qb = _f8(a), _f8(b)
    return _einsum(eq, qa, qb), (qa, qb)


def _f8_bwd(eq, res, g):
    _, vjp = jax.vjp(functools.partial(_einsum, eq), *res)
    return vjp(_f8(g, jnp.float8_e5m2))


_f8_einsum.defvjp(_f8_fwd, _f8_bwd)


def mm(eq: str, a, b, f8: bool = False):
    """``einsum(eq, a, b)`` in float32 at highest precision. With ``f8``,
    a float8 training path: both operands rounded to e4m3 and the
    incoming gradient of the backward products to e5m2, one scale per
    tensor, products accumulated in float32."""
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return _f8_einsum(eq, a, b) if f8 else _einsum(eq, a, b)


# ---------------------------------------------------------------------------
# one sequence
# ---------------------------------------------------------------------------

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


def layer(blocks, i):
    """Layer ``i`` of the stacked block weights, in float32."""
    return jax.tree_util.tree_map(
        lambda w: jax.lax.dynamic_index_in_dim(w, i, keepdims=False)
        .astype(jnp.float32), blocks)


def layer_inputs(m: Dims, params: Params, tokens, f8: bool = False):
    """The input of every layer and the last layer's output, (L + 1, S, d),
    of one sequence."""
    x = params["embed"][tokens].astype(jnp.float32)

    def step(x, i):
        return block(m, layer(params["blocks"], i), x, f8), x

    x, xs = jax.lax.scan(step, x, jnp.arange(m.layers))
    return jnp.concatenate([xs, x[None]], axis=0)


def head(m: Dims, params: Params):
    return params["embed"].T if m.tied else params["lm_head"]


LOSS_CHUNK = 256


def head_loss(m: Dims, final_norm, w_head, x, labels, f8: bool = False):
    """Mean next-token cross entropy of one sequence from the last layer's
    output ``x``, the logits made ``LOSS_CHUNK`` positions at a time."""
    h = rms(x, final_norm, m.eps)
    S = h.shape[0]
    n = S // LOSS_CHUNK if S % LOSS_CHUNK == 0 else 1
    hc = h.reshape(n, S // n, m.d)
    lc = labels.reshape(n, S // n)

    @jax.checkpoint
    def chunk(total, xs):
        hi, li = xs
        logp = jax.nn.log_softmax(mm("sd,dv->sv", hi, w_head, f8), axis=-1)
        return total - jnp.sum(jnp.take_along_axis(logp, li[:, None], 1)), None

    total, _ = jax.lax.scan(chunk, jnp.float32(0.0), (hc, lc))
    return total / S


def selection_row(m: Dims, params: Params, tokens, labels, stride: int,
                  f8: bool = False):
    """One sequence's selection inputs: (mean-pooled hidden (d,), probe
    gradient embedding (d,)). The embedding is the pooled hidden over the
    probe positions (every ``stride``-th), weighted by the norm of each
    position's softmax error ``p - onehot(label)`` normalised to sum 1,
    times the sequence's mean probe cross entropy."""
    x = params["embed"][tokens].astype(jnp.float32)

    def step(x, i):
        return block(m, layer(params["blocks"], i), x, f8), None

    x, _ = jax.lax.scan(step, x, jnp.arange(m.layers))
    h = rms(x, params["final_norm"], m.eps)
    hp, lp = h[::stride], labels[::stride]
    logp = jax.nn.log_softmax(mm("sd,dv->sv", hp, head(m, params), f8), -1)
    err = jnp.exp(logp) - jax.nn.one_hot(lp, m.vocab, dtype=jnp.float32)
    err_norm = jnp.sqrt(jnp.sum(err * err, axis=-1))
    nll = -jnp.take_along_axis(logp, lp[:, None], 1)[:, 0]
    w = err_norm / (jnp.sum(err_norm) + 1e-9)
    emb = jnp.einsum("s,sd->d", w, hp,
                     precision=jax.lax.Precision.HIGHEST) * jnp.mean(nll)
    return jnp.mean(h, axis=0), emb


# ---------------------------------------------------------------------------
# the programs: one sequence at a time
# ---------------------------------------------------------------------------

class Programs(NamedTuple):
    """Jitted pieces of a reference step, one sequence a call; ``acc`` is a
    gradient being summed, a float32 tree of the weights' shapes.

    * ``select(p, tokens, labels)``: selection inputs of a block of rows;
    * ``forward(p, tokens)``: one row's layer inputs and output;
    * ``head(p, xs, labels, w, acc)``: the row's loss, times its weight
      ``w``; the gradient of the weighted loss by the final norm and the
      head added to ``acc``; and by the last layer's output;
    * ``layer_back(p, i, xs, dx, acc)``: back through layer ``i``, its
      weights' gradient added to ``acc``;
    * ``embed_back(tokens, dx, acc)``: the embedding rows' gradient added
      to ``acc``.
    """
    select: Any
    forward: Any
    head: Any
    layer_back: Any
    embed_back: Any


def programs(m: Dims, stride: int, f8: bool) -> Programs:
    def select(p, tokens, labels):
        def row(_, xs):
            return None, selection_row(m, p, xs[0], xs[1], stride, f8)
        return jax.lax.scan(row, None, (tokens, labels))[1]

    def forward(p, tokens):
        return layer_inputs(m, p, tokens, f8)

    def head_(p, xs, labels, w, acc):
        w_head = head(m, p).astype(jnp.float32)
        loss, vjp = jax.vjp(
            lambda fn, wh, x: head_loss(m, fn, wh, x, labels, f8),
            p["final_norm"].astype(jnp.float32), w_head, xs[-1])
        d_norm, d_head, dx = vjp(w)
        acc = dict(acc, final_norm=acc["final_norm"] + d_norm)
        if m.tied:
            acc["embed"] = acc["embed"] + d_head.T
        else:
            acc["lm_head"] = acc["lm_head"] + d_head
        return w * loss, dx, acc

    def layer_back(p, i, xs, dx, acc):
        _, vjp = jax.vjp(lambda q, x: block(m, q, x, f8),
                         layer(p["blocks"], i), xs[i])
        dq, dx = vjp(dx)
        blocks = jax.tree_util.tree_map(
            lambda a, d: a.at[i].add(d), acc["blocks"], dq)
        return dx, dict(acc, blocks=blocks)

    def embed_back(tokens, dx, acc):
        return dict(acc, embed=acc["embed"].at[tokens].add(dx))

    return Programs(
        select=jax.jit(select), forward=jax.jit(forward),
        head=jax.jit(head_, donate_argnums=(4,)),
        layer_back=jax.jit(layer_back, donate_argnums=(4,)),
        embed_back=jax.jit(embed_back, donate_argnums=(2,)))
