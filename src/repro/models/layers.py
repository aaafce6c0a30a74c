"""Transformer building blocks: RMSNorm, RoPE, GQA attention (sliding window,
logit softcap, QKV bias, KV cache), gated MLP, and the capacity-factor MoE.

Pure functional JAX: every block is ``f(cfg, params, x, ...)`` with params a
nested dict. Sharding is injected by ``repro.distributed.sharding`` via
``with_sharding_constraint`` on the annotated logical axes.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain, shard_kernel


# ---------------------------------------------------------------------------
# norms / rope / misc
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
    return out.astype(x.dtype)


def softcap(x: jax.Array, cap: Optional[float]) -> jax.Array:
    if cap is None:
        return x
    return (cap * jnp.tanh(x.astype(jnp.float32) / cap)).astype(x.dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, Dh); positions: (B, S) int32."""
    head_dim = x.shape[-1]
    half = head_dim // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freq  # (B,S,half)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _activation(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}[name]


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

# The backward kernels' tiles (flash_dq, flash_dkv): the largest of these
# dividing each length. The forward's tiles below 128 too.
_FLASH_BLOCKS = (128, 64, 32, 16, 8)
# The forward's (q tile, K tile), first that divides the lengths and fits
# the VMEM budget; larger tiles pay once the loop body runs at the MXU's
# operand type, a long K tile most (swept on a v5e at 576 × 2048 × 64:
# 256 × 512 ran 2.7× faster than 128 × 128; PERF.md §6).
_FLASH_FWD_BLOCKS = ((256, 512), (256, 256), (128, 128))


def _flash_blocks(S: int, T: int, head_dim: int, itemsize: int):
    """((fwd_q, fwd_k), (bwd_q, bwd_k)) kernel tiles for q/kv lengths S/T,
    or None where no tile divides them or no forward fits VMEM — the SAME
    estimator the kernel wrapper enforces (repro.analysis.vmem), so the
    shape the router plans with is the shape the kernel accepts."""
    from repro.analysis.vmem import flash_forward_vmem
    bq = next((b for b in _FLASH_BLOCKS if S % b == 0), None)
    bk = next((b for b in _FLASH_BLOCKS if T % b == 0), None)
    if bq is None or bk is None:
        return None
    fwd = next((
        (fq, fk) for fq, fk in _FLASH_FWD_BLOCKS + ((bq, bk),)
        if S % fq == 0 and T % fk == 0
        and flash_forward_vmem(T, head_dim, fq, fk, itemsize).fits), None)
    return None if fwd is None else (fwd, (bq, bk))


def _flash_feasible(cfg, S: int, T: int) -> bool:
    return _flash_blocks(S, T, cfg.head_dim, cfg.dtype.itemsize) is not None


def resolve_attn_backend(cfg, S: int, T: int) -> str:
    """Training/prefill backend for this shape → flash | chunked | dense.

    "auto" keeps the jnp paths off-TPU (interpret-mode Pallas is orders of
    magnitude slower than XLA:CPU); explicit "flash" runs the kernel anywhere
    (interpret on CPU), falling back to the jnp paths only when the
    block-divisibility or VMEM guard refuses the shape.
    """
    b = getattr(cfg, "attn_backend", "auto")
    chunked = "chunked" if cfg.attn_chunk and T > cfg.attn_chunk else "dense"
    if b == "dense":
        return "dense"
    if b == "chunked":
        return chunked
    if b == "flash":
        return "flash" if _flash_feasible(cfg, S, T) else chunked
    if b == "auto":
        if jax.default_backend() == "tpu" and _flash_feasible(cfg, S, T):
            return "flash"
        return chunked
    raise ValueError(f"unknown attn_backend: {b!r}")


def _flash_attention(cfg, q: jax.Array, k: jax.Array, v: jax.Array,
                     is_local) -> jax.Array:
    """Single-dispatch Pallas path: ONE pallas_call per layer. q (B,S,H,Dh)
    pre-scaled (kernel scale=1); k/v (B,S,Hkv,Dh) — streams fold head-major
    so GQA q stream i reads kv stream i // group without repeating K/V.
    Assumes contiguous from-zero positions (forward_hiddens' layout); the
    cache/decode path never routes here. Under a multi-device mesh each
    device runs the kernel on its batch shard (``shard_kernel``).
    """
    from repro.kernels import flash_attention as _fa
    S = q.shape[1]
    (fbq, fbk), (bq, bk) = _flash_blocks(S, S, cfg.head_dim,
                                         cfg.dtype.itemsize)
    if cfg.sliding_window is None:
        window = None
    elif isinstance(is_local, bool):
        window = cfg.sliding_window if is_local else None
    else:
        # traced per-layer local/global pattern (gemma2's scanned
        # alternation): dynamic window operand; w >= S is a no-op mask.
        window = jnp.where(is_local, cfg.sliding_window, S).astype(jnp.int32)

    def kernel(q, k, v, *w):
        B, _, H, Dh = q.shape
        Hkv = k.shape[2]
        qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, Dh)
        kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, S, Dh)
        vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, S, Dh)
        out = _fa.flash_attention_pallas(
            qf, kf, vf, block_q=bq, block_k=bk, fwd_block_q=fbq,
            fwd_block_k=fbk, causal=True,
            window=w[0] if w else None,
            softcap=cfg.attn_logit_softcap, group=H // Hkv, scale=1.0,
            interpret=jax.default_backend() != "tpu")
        return out.reshape(B, H, S, Dh).transpose(0, 2, 1, 3)

    rows = ("act_batch", None, None, None)
    args, logical = (q, k, v), (rows,) * 3
    if window is not None:
        args = args + (jnp.asarray(window, jnp.int32),)
        logical = logical + ((),)
    return shard_kernel(kernel, args, logical, rows)


def attention(cfg, p, x: jax.Array, positions: jax.Array,
              *, is_local: jax.Array | bool = False,
              cache: Optional[dict] = None,
              cache_index: Optional[jax.Array] = None,
              ) -> Tuple[jax.Array, Optional[dict]]:
    """GQA attention. x: (B, S, D). If ``cache`` given, runs one decode step
    (S == new tokens, usually 1) against the cache and returns the updated
    cache; otherwise full self-attention with a causal (+ optional sliding
    window) mask.

    ``is_local`` may be a traced bool (scanned layer pattern, e.g. gemma2's
    local/global alternation) — the window mask is blended with ``where``.
    """
    B, S, D = x.shape
    H, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim

    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    # q seq-sharded (scores stay S/tp × T per device); k/v gathered — GQA keeps
    # them small. When the "act_q_seq" rule is None this degrades gracefully to
    # Megatron head-TP (heads entry wins the axis).
    q = constrain(q, ("act_batch", "act_q_seq", "act_heads", None))
    k = constrain(k, ("act_batch", "act_seq", "act_kv_heads", None))
    v = constrain(v, ("act_batch", "act_seq", "act_kv_heads", None))

    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    if cfg.query_scale is not None:
        q = q * cfg.query_scale
    else:
        q = q / jnp.sqrt(jnp.float32(Dh)).astype(q.dtype)

    if cache is not None:
        # decode/prefill-into-cache: append new k/v at cache_index, attend to
        # the cache with per-query causality inside the new chunk.
        k_cache = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, cache_index, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, cache_index, axis=1)
        k_cache = constrain(k_cache, ("act_batch", "act_kv_seq", "act_kv_heads", None))
        v_cache = constrain(v_cache, ("act_batch", "act_kv_seq", "act_kv_heads", None))
        new_cache = {"k": k_cache, "v": v_cache}
        kv_len = k_cache.shape[1]
        k_all, v_all = k_cache, v_cache
        kv_pos = jnp.arange(kv_len, dtype=jnp.int32)[None, :]          # (1,T)
        q_abs = cache_index + jnp.arange(S, dtype=jnp.int32)[:, None]  # (S,1)
        causal = kv_pos <= q_abs                                       # (S,T)
        mask = jnp.broadcast_to(causal[None], (B, S, kv_len))
        if cfg.sliding_window is not None:
            in_window = kv_pos > (q_abs - cfg.sliding_window)
            wmask = jnp.broadcast_to(jnp.logical_and(causal, in_window)[None], mask.shape)
            mask = jnp.where(is_local, wmask, mask) if not isinstance(is_local, bool) \
                else (wmask if is_local else mask)
        # decode/prefill-into-cache keeps the jnp paths (per-query absolute
        # positions; attn_backend targets the training/prefill hot path)
        backend = "chunked" if cfg.attn_chunk and kv_len > cfg.attn_chunk \
            else "dense"
    else:
        new_cache = None
        k_all, v_all = k, v
        kv_len = S
        backend = resolve_attn_backend(cfg, S, kv_len)
        mask = None
        if backend != "flash":      # flash masks inside the kernel
            qpos = positions[:, :, None]
            kpos = positions[:, None, :]
            mask = kpos <= qpos
            if cfg.sliding_window is not None:
                wmask = jnp.logical_and(mask, kpos > qpos - cfg.sliding_window)
                if isinstance(is_local, bool):
                    mask = wmask if is_local else mask
                else:
                    mask = jnp.where(is_local, wmask, mask)

    # grouped query attention: fold the group dim into heads
    group = H // Hkv
    if backend == "flash":
        out = _flash_attention(cfg, q, k_all, v_all, is_local)
    elif backend == "chunked":
        qg = q.reshape(B, S, Hkv, group, Dh)
        out = _chunked_attention(cfg, qg, k_all, v_all, mask)
        out = out.reshape(B, S, H, Dh)
    else:
        qg = q.reshape(B, S, Hkv, group, Dh)
        logits = jnp.einsum("bskgh,btkh->bkgst", qg, k_all)      # (B,Hkv,g,S,T)
        logits = softcap(logits, cfg.attn_logit_softcap)
        logits = jnp.where(mask[:, None, None, :, :], logits.astype(jnp.float32), -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(v_all.dtype)
        out = jnp.einsum("bkgst,btkh->bskgh", probs, v_all).reshape(B, S, H, Dh)
    out = constrain(out, ("act_batch", "act_q_seq", "act_heads", None))
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    out = constrain(out, ("act_batch", "act_res_seq", "act_embed"))
    return out, new_cache


def _chunked_attention(cfg, qg: jax.Array, k_all: jax.Array, v_all: jax.Array,
                       mask: jax.Array) -> jax.Array:
    """Flash-style online-softmax attention over KV chunks (pure jnp).

    Scores are materialized only per (S × chunk) tile — this is what makes
    the 32k-prefill cells fit HBM, and it is the jnp analog of a Pallas
    flash kernel (the lowered scan is the schedule a TPU kernel would use).
    qg: (B,S,Hkv,g,Dh); k/v: (B,T,Hkv,Dh); mask: (B,S,T) bool.
    Returns (B,S,Hkv,g,Dh) in v dtype.
    """
    B, S, Hkv, g, Dh = qg.shape
    T = k_all.shape[1]
    C = cfg.attn_chunk
    n_chunks = T // C
    assert T % C == 0, (T, C)
    kc = k_all.reshape(B, n_chunks, C, Hkv, Dh).transpose(1, 0, 2, 3, 4)
    vc = v_all.reshape(B, n_chunks, C, Hkv, Dh).transpose(1, 0, 2, 3, 4)
    maskc = mask.reshape(B, S, n_chunks, C).transpose(2, 0, 1, 3)

    def body(carry, xs):
        m, l, acc = carry                       # (B,Hkv,g,S), (…), (B,Hkv,g,S,Dh)
        k_i, v_i, mask_i = xs                   # (B,C,Hkv,Dh), …, (B,S,C)
        s = jnp.einsum("bskgh,btkh->bkgst", qg, k_i)
        s = softcap(s, cfg.attn_logit_softcap).astype(jnp.float32)
        s = jnp.where(mask_i[:, None, None, :, :], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        # A chunk whose mask row is ALL false keeps m_new at -1e30; without
        # the guard p = exp(0) = 1 there, silently averaging V for rows
        # whose whole horizon is masked.
        p = jnp.where(m_new[..., None] > -0.5e30,
                      jnp.exp(s - m_new[..., None]), 0.0)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bkgst,btkh->bkgsh", p.astype(v_i.dtype), v_i).astype(jnp.float32)
        return (m_new, l, acc), None

    m0 = jnp.full((B, Hkv, g, S), -1e30, jnp.float32)
    l0 = jnp.zeros((B, Hkv, g, S), jnp.float32)
    acc0 = jnp.zeros((B, Hkv, g, S, Dh), jnp.float32)
    body = jax.checkpoint(body)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), (kc, vc, maskc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).astype(v_all.dtype)  # (B,S,Hkv,g,Dh)


def init_attention_params(key, cfg, dtype) -> dict:
    D, H, Hkv, Dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = D ** -0.5
    p = {
        "wq": (jax.random.normal(k1, (D, H, Dh)) * s).astype(dtype),
        "wk": (jax.random.normal(k2, (D, Hkv, Dh)) * s).astype(dtype),
        "wv": (jax.random.normal(k3, (D, Hkv, Dh)) * s).astype(dtype),
        "wo": (jax.random.normal(k4, (H, Dh, D)) * (H * Dh) ** -0.5).astype(dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H, Dh), dtype)
        p["bk"] = jnp.zeros((Hkv, Dh), dtype)
        p["bv"] = jnp.zeros((Hkv, Dh), dtype)
    return p


# ---------------------------------------------------------------------------
# dense MLP
# ---------------------------------------------------------------------------

def mlp(cfg, p, x: jax.Array) -> jax.Array:
    """Gated MLP (SwiGLU / GeGLU per cfg.mlp_activation)."""
    act = _activation(cfg.mlp_activation)
    gate = act(jnp.einsum("bsd,df->bsf", x, p["w_gate"]))
    up = jnp.einsum("bsd,df->bsf", x, p["w_up"])
    h = constrain(gate * up, ("act_batch", "act_seq", "act_mlp"))
    out = jnp.einsum("bsf,fd->bsd", h, p["w_down"])
    return constrain(out, ("act_batch", "act_res_seq", "act_embed"))


def init_mlp_params(key, cfg, dtype, d_ff: Optional[int] = None) -> dict:
    D, F = cfg.d_model, d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": (jax.random.normal(k1, (D, F)) * D ** -0.5).astype(dtype),
        "w_up": (jax.random.normal(k2, (D, F)) * D ** -0.5).astype(dtype),
        "w_down": (jax.random.normal(k3, (F, D)) * F ** -0.5).astype(dtype),
    }


# ---------------------------------------------------------------------------
# Mixture of Experts (GShard/MaxText-style capacity-factor dispatch)
# ---------------------------------------------------------------------------

def moe(cfg, p, x: jax.Array, dropless: bool = False) -> jax.Array:
    """Top-k routed MoE with static capacity, sort-based dispatch.

    Tokens are split into groups of ``cfg.moe_group_size``; each (group,
    expert) pair has capacity C = group·k/E·cf. Dispatch is a stable
    argsort over expert ids + two gathers (token→buffer, buffer→token) —
    NO (tokens×E×C) one-hot ever materializes (the GShard dispatch-einsum
    formulation costs T·E·C memory/FLOPs, which at kimi-k2's E=384 is
    ~10 TB per layer; gathers are O(T·k)). Tokens stay on their data shard,
    experts on their model shard; the combine's expert-partial sum is the
    only model-axis collective. Overflow tokens beyond capacity are dropped
    (standard; decode uses ``dropless`` so serving is batching-invariant).
    """
    B, S, D = x.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    gs = min(cfg.moe_group_size, B * S)
    T = B * S
    G = T // gs
    if dropless:
        # capacity = group size ⇒ no token is ever dropped
        cap = gs
    else:
        cap = int(gs * k / E * cfg.moe_capacity_factor) + 1

    if cfg.moe_local_groups and S % gs == 0 and S >= gs:
        # chunk-major grouping: groups = contiguous seq chunks, group dim
        # ordered (chunk, batch) so its sharding composes as model-major —
        # byte-identical to the residual stream's (batch:dp, seq:model)
        # layout ⇒ routing/top-k/sort all run on LOCAL tokens, no seq
        # all-gather before the router (§Perf i6).
        n = S // gs
        xt = x.reshape(B, n, gs, D).transpose(1, 0, 2, 3).reshape(G, gs, D)
        xt = constrain(xt, ("act_moe_groups", None, None))
        regroup = "chunk_major"
        g_axis = "act_moe_dispatch"      # expert buffers: model axis is spent
                                         # on experts, G keeps the dp axes
    else:
        xt = x.reshape(G, gs, D)
        xt = constrain(xt, ("act_batch", None, None))
        regroup = "flat"
        g_axis = "act_batch"
    router_logits = jnp.einsum("gsd,de->gse", xt, p["router"].astype(x.dtype))
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    gate_vals, ids = jax.lax.top_k(probs, k)                     # (G,gs,k)
    gate_vals = gate_vals / (jnp.sum(gate_vals, -1, keepdims=True) + 1e-9)

    F = gs * k
    ids_flat = ids.reshape(G, F)
    counts = jax.vmap(lambda i: jnp.zeros((E,), jnp.int32).at[i].add(1))(ids_flat)
    starts = jnp.cumsum(counts, axis=1) - counts                  # (G,E) exclusive
    order = jnp.argsort(ids_flat, axis=1)                         # stable (G,F)
    sorted_eid = jnp.take_along_axis(ids_flat, order, axis=1)
    pos_sorted = (jnp.arange(F, dtype=jnp.int32)[None, :] -
                  jnp.take_along_axis(starts, sorted_eid, axis=1))
    # rank of each (token, slot) within its expert queue, original order
    pos_flat = jax.vmap(lambda o, ps: jnp.zeros((F,), jnp.int32).at[o].set(ps)
                        )(order, pos_sorted)
    keep_flat = pos_flat < cap                                    # (G,F)

    # buffer side: which flat assignment fills buffer slot (e, c)?
    b_e = jnp.arange(E * cap, dtype=jnp.int32) // cap             # (E·C,)
    b_c = jnp.arange(E * cap, dtype=jnp.int32) % cap
    src_sorted = starts[:, b_e] + b_c[None, :]                    # (G, E·C)
    slot_valid = b_c[None, :] < jnp.minimum(counts[:, b_e], cap)
    src_flat = jnp.take_along_axis(
        order, jnp.clip(src_sorted, 0, F - 1), axis=1)            # (G, E·C)
    token_of_slot = jnp.where(slot_valid, src_flat // k, 0)
    # shard the slot axis over the expert (model) axis BEFORE gathering so
    # the gather output is born expert-sharded — without this the (G,E·C,D)
    # buffer materializes model-replicated (~10 GB/device at kimi-k2 scale)
    token_of_slot = constrain(token_of_slot, (g_axis, "act_experts"))
    slot_valid = constrain(slot_valid, (g_axis, "act_experts"))

    expert_in = jnp.take_along_axis(xt, token_of_slot[..., None], axis=1)
    expert_in = expert_in * slot_valid[..., None].astype(x.dtype)
    expert_in = expert_in.reshape(G, E, cap, D)
    expert_in = constrain(expert_in, (g_axis, "act_experts", None, None))

    act = _activation(cfg.mlp_activation)
    h = act(jnp.einsum("gecd,edf->gecf", expert_in, p["w_gate"])) * \
        jnp.einsum("gecd,edf->gecf", expert_in, p["w_up"])
    h = constrain(h, (g_axis, "act_experts", None, "act_mlp_inner"))
    expert_out = jnp.einsum("gecf,efd->gecd", h, p["w_down"])
    expert_out = constrain(expert_out, (g_axis, "act_experts", None, None))

    # combine as a scatter-add from the expert side: every shard accumulates
    # its local slots' weighted outputs into (G,gs,D) partials; the psum over
    # the model axis is the layer's only combine collective (embedding-grad
    # pattern — avoids a cross-shard gather that would replicate the buffer).
    flat_out = expert_out.reshape(G, E * cap, D)
    gate_flat = (gate_vals.reshape(G, F) * keep_flat).astype(x.dtype)  # (G,F)
    w_of_slot = jnp.take_along_axis(
        gate_flat, jnp.clip(src_flat, 0, F - 1), axis=1)
    w_of_slot = w_of_slot * slot_valid.astype(x.dtype)            # (G, E·C)
    contrib = flat_out * w_of_slot[..., None]

    def scatter_group(tos, c):
        return jnp.zeros((gs, D), jnp.float32).at[tos].add(c.astype(jnp.float32))

    out = jax.vmap(scatter_group)(token_of_slot, contrib)          # (G,gs,D) f32
    out = out.astype(x.dtype)
    if regroup == "chunk_major":
        n = S // gs
        out = constrain(out, ("act_moe_groups", None, None))
        out = out.reshape(n, B, gs, D).transpose(1, 0, 2, 3).reshape(B, S, D)
    else:
        out = out.reshape(B, S, D)
    return constrain(out, ("act_batch", "act_res_seq", "act_embed"))


def init_moe_params(key, cfg, dtype) -> dict:
    D, F, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    k0, k1, k2, k3 = jax.random.split(key, 4)
    return {
        "router": (jax.random.normal(k0, (D, E)) * D ** -0.5).astype(jnp.float32),
        "w_gate": (jax.random.normal(k1, (E, D, F)) * D ** -0.5).astype(dtype),
        "w_up": (jax.random.normal(k2, (E, D, F)) * D ** -0.5).astype(dtype),
        "w_down": (jax.random.normal(k3, (E, F, D)) * F ** -0.5).astype(dtype),
    }
