"""Plain reference of the decoder the configurations run: what every
block shares.

The embedding, the stack of layers, the norm before the head and the
head; the block itself (its layer, its final norm, its initial weights)
comes from the block file the configuration names (``blocks/``, see
``blocks/__init__.py``). The head is the embedding's transpose when tied.
Loss: mean next-token cross entropy over a sequence.

Everything is ``jax.numpy`` in float32 with every matrix product at
``highest`` precision, one sequence at a time; the gradient is taken
layer by layer (``Programs``), so that the chip holds the weights, one
layer's float32 gradient and the activations, and the rest of the float32
state waits on the host (``train.py``). ``mm`` can instead run every
product as a float8 training path would (its ``f8``): the control of the
correctness check. Weights are drawn from the seed the way the program
draws its own (the same key splits, distributions and scales) and stored
as the configuration states them: matrices in its ``torch_dtype``
(bfloat16), norm scales in float32.

Nothing here or in a block file imports the program.
"""
from __future__ import annotations

import functools
from types import ModuleType
from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp

from chipbench.reference import blocks as blocks_lib

Params = Dict[str, Any]


class Dims(NamedTuple):
    d: int
    layers: int
    vocab: int
    tied: bool
    dtype: str
    block: ModuleType       # the block file
    bd: Any                 # its dimensions (``block.Dims``)

    @classmethod
    def from_config(cls, c: Dict[str, Any]) -> "Dims":
        block = blocks_lib.of(c)
        return cls(c["hidden_size"], c["num_hidden_layers"], c["vocab_size"],
                   bool(c.get("tie_word_embeddings", False)),
                   c.get("torch_dtype", "bfloat16"), block,
                   block.Dims.from_config(c))


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------

def init(m: Dims, key) -> Params:
    """The configuration's initial weights for ``key``, the seed's
    ``PRNGKey`` (an argument, so that one compiled program serves every
    seed): normal draws scaled by 0.02 (embedding) and ``d^-1/2`` (head),
    each layer's as its block file draws them, the final norm as the block
    file makes it. Key splits: (embedding, layers, head, -) from the seed's
    key; one key a layer."""
    bf = jnp.dtype(m.dtype)
    k_embed, k_blocks, k_head, _ = jax.random.split(key, 4)
    D = m.d

    def normal(k, shape, scale):
        return (jax.random.normal(k, shape) * scale).astype(bf)

    layers = [m.block.init_layer(m.bd, k)
              for k in jax.random.split(k_blocks, m.layers)]
    params: Params = {
        "embed": normal(k_embed, (m.vocab, D), 0.02),
        "final_norm": m.block.init_final_norm(m.bd),
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

def block(m: Dims, p, x, f8: bool = False):
    """One decoder layer of the configuration's block on one sequence;
    ``p`` is that layer's weights."""
    return m.block.block(m.bd, p, x, f8)


def final_norm(m: Dims, w, x):
    return m.block.final_norm(m.bd, w, x)


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


def head_loss(m: Dims, w_norm, w_head, x, labels, f8: bool = False):
    """Mean next-token cross entropy of one sequence from the last layer's
    output ``x``, the logits made ``LOSS_CHUNK`` positions at a time."""
    h = final_norm(m, w_norm, x)
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
    h = final_norm(m, params["final_norm"], x)
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
    """Jitted pieces of a reference step, one sequence a call; ``acc`` is
    a part of the gradient being summed, in float32.

    * ``select(p, tokens, labels)``: selection inputs of a block of rows;
    * ``forward(p, tokens)``: one row's layer inputs and output;
    * ``head(p, xs, labels, w, acc)``: the row's loss, times its weight
      ``w``; the gradient of the weighted loss by the final norm, the
      head, or both, added to the entries of ``acc`` (a dict with any of
      ``final_norm``, ``lm_head`` and, for a tied head, ``embed``); and by
      the last layer's output;
    * ``layer_back(p, i, xs, dx, acc)``: back through layer ``i``, its
      weights' gradient added to ``acc``, one layer's tree;
    * ``embed_back(tokens, dx, acc)``: the embedding rows' gradient added
      to ``acc["embed"]``.
    """
    select: Any
    forward: Any
    head: Any
    layer_back: Any
    embed_back: Any


def programs(m: Dims, stride: int, f8: bool) -> Programs:
    f32 = functools.partial(jax.tree_util.tree_map,
                            lambda w: w.astype(jnp.float32))
    add = functools.partial(jax.tree_util.tree_map, jnp.add)

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
            f32(p["final_norm"]), w_head, xs[-1])
        d_norm, d_head, dx = vjp(w)
        acc = dict(acc)
        if "final_norm" in acc:
            acc["final_norm"] = add(acc["final_norm"], d_norm)
        if "embed" in acc and m.tied:
            acc["embed"] = acc["embed"] + d_head.T
        if "lm_head" in acc:
            acc["lm_head"] = acc["lm_head"] + d_head
        return w * loss, dx, acc

    def layer_back(p, i, xs, dx, acc):
        _, vjp = jax.vjp(lambda q, x: block(m, q, x, f8),
                         layer(p["blocks"], i), xs[i])
        dq, dx = vjp(dx)
        return dx, add(acc, dq)

    def embed_back(tokens, dx, acc):
        return dict(acc, embed=acc["embed"].at[tokens].add(dx))

    return Programs(
        select=jax.jit(select), forward=jax.jit(forward),
        head=jax.jit(head_, donate_argnums=(4,)),
        layer_back=jax.jit(layer_back, donate_argnums=(4,)),
        embed_back=jax.jit(embed_back, donate_argnums=(2,)))
