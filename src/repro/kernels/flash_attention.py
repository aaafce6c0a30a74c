"""Pallas TPU flash attention (causal, optional sliding window + softcap).

The jnp chunked-attention path (models/layers._chunked_attention) is the
SPMD-dry-run reference; this kernel is the TPU fast path with the same
online-softmax schedule but explicit VMEM residency:

  grid = (BH, Sq // BLOCK_Q): each program owns one query tile. K/V for the
  (b,h) stream stay VMEM-resident across the program's KV loop (budget-
  guarded by the wrapper); scores exist only as a (BLOCK_Q, BLOCK_K) tile in
  registers/VMEM. m/l/acc run in f32 for numerical parity with the oracle.

GQA folds into the grid: q streams are (B·H) while k/v stay (B·Hkv); the
k/v BlockSpec index map divides the stream id by ``group`` so no repeated
K/V ever materializes. The sliding window rides along as a dynamic int32
scalar operand (w ≥ T disables it) so a traced per-layer ``is_local`` —
gemma2's scanned local/global pattern — selects the window without a
second kernel in the jaxpr.

Backward: custom_vjp with full recompute. Two kernels — dQ over the q grid
(same KV loop as forward) and dK/dV over the KV grid (loop over q tiles,
python-unrolled over the GQA group) — using the saved logsumexp residual
and the precomputed ``delta = Σ o·do`` row sums, so no (Sq × T) score
matrix ever materializes in either direction.

For KV streams too large for VMEM the wrapper refuses — the production
answer at 32k+ context is KV-tiling via a third grid axis, noted as future
work (the jnp path covers those cells today).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Mask fill value. A tile whose mask is ALL false leaves the running max at
# this sentinel; the online-softmax update must then suppress its
# contribution entirely (p = 0), not exp(0) = 1 — see _tile_probs.
_MASK = -1e30
_MASK_GUARD = -0.5e30


def _tile_mask(q_offset, k_offset, BQ: int, BK: int, causal: bool,
               use_window: bool, w) -> jax.Array:
    """(BQ, BK) validity mask for one score tile."""
    q_pos = q_offset + jax.lax.iota(jnp.int32, BQ)[:, None]
    k_pos = k_offset + jax.lax.iota(jnp.int32, BK)[None, :]
    mask = jnp.ones((BQ, BK), jnp.bool_)
    if causal:
        mask = jnp.logical_and(mask, k_pos <= q_pos)
    if use_window:
        mask = jnp.logical_and(mask, k_pos > q_pos - w)
    return mask


def _tile_scores(qs, k, mask, softcap: Optional[float]) -> jax.Array:
    """Masked (and optionally softcapped) scores for one tile; qs pre-scaled."""
    s = qs @ k.T
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    return jnp.where(mask, s, _MASK)


def _kv_bounds(q_offset, BQ: int, block_k: int, n_tiles: int, causal: bool,
               use_window: bool, w, bound_loop: bool):
    """[lo, hi) KV-tile range that can contain unmasked entries for this
    q tile. Causal bounds hi at ceil((q_offset + BQ) / block_k); the window
    bounds lo at the first tile reaching past ``q_offset - w``. With
    ``bound_loop=False`` the full range is scanned (the skipped tiles are
    all-masked, so with the _MASK_GUARD fix both variants are bit-equal —
    asserted in tests)."""
    lo: jax.Array | int = 0
    hi: jax.Array | int = n_tiles
    if bound_loop:
        if causal:
            hi = jnp.minimum(n_tiles, (q_offset + BQ + block_k - 1) // block_k)
        if use_window:
            lo = jnp.maximum(0, (q_offset - w + 1) // block_k)
    return lo, hi


def _flash_kernel(q_ref, k_ref, v_ref, w_ref, o_ref, lse_ref, *, block_k: int,
                  causal: bool, use_window: bool, softcap: Optional[float],
                  scale: float, bound_loop: bool):
    """Blocks: q (1, BQ, Dh); k/v (1, T, Dh); w (1,) in SMEM;
    o (1, BQ, Dh); lse (1, 1, BQ) f32."""
    qs = q_ref[0].astype(jnp.float32) * scale          # (BQ, Dh)
    BQ = qs.shape[0]
    T = k_ref.shape[1]
    q_offset = pl.program_id(1) * BQ
    w = w_ref[0]

    m0 = jnp.full((BQ,), _MASK, jnp.float32)
    l0 = jnp.zeros((BQ,), jnp.float32)
    acc0 = jnp.zeros_like(qs)

    def body(i, carry):
        m, l, acc = carry
        k = k_ref[0, pl.dslice(i * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.dslice(i * block_k, block_k), :].astype(jnp.float32)
        mask = _tile_mask(q_offset, i * block_k, BQ, block_k,
                          causal, use_window, w)
        s = _tile_scores(qs, k, mask, softcap)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        # A fully-masked row keeps m_new at the _MASK sentinel; without the
        # guard p = exp(s - m_new) = exp(0) = 1 there, silently averaging V.
        p = jnp.where(m_new[:, None] > _MASK_GUARD,
                      jnp.exp(s - m_new[:, None]), 0.0)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[:, None] + p @ v
        return m_new, l, acc

    lo, hi = _kv_bounds(q_offset, BQ, block_k, T // block_k,
                        causal, use_window, w, bound_loop)
    m, l, acc = jax.lax.fori_loop(lo, hi, body, (m0, l0, acc0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)
    # logsumexp residual for the backward recompute; +inf marks rows whose
    # whole horizon is masked (output 0), so bwd p = exp(s - lse) = 0 there.
    lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), jnp.inf)
    lse_ref[0] = lse[None, :]


def _flash_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, w_ref,
                     dq_ref, *, block_k: int, causal: bool, use_window: bool,
                     softcap: Optional[float], scale: float, bound_loop: bool):
    """dQ over the same (BH, Sq//BQ) grid / KV loop as the forward."""
    qs = q_ref[0].astype(jnp.float32) * scale
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0].T                                  # (BQ, 1)
    delta = delta_ref[0].T                              # (BQ, 1)
    BQ = qs.shape[0]
    T = k_ref.shape[1]
    q_offset = pl.program_id(1) * BQ
    w = w_ref[0]

    def body(i, dq):
        k = k_ref[0, pl.dslice(i * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.dslice(i * block_k, block_k), :].astype(jnp.float32)
        mask = _tile_mask(q_offset, i * block_k, BQ, block_k,
                          causal, use_window, w)
        s = _tile_scores(qs, k, mask, softcap)
        p = jnp.exp(s - lse)                            # normalized; 0 if masked
        dp = do @ v.T
        ds = p * (dp - delta)
        if softcap is not None:
            t = s / softcap                             # tanh(s_raw/cap) where unmasked
            ds = ds * jnp.where(mask, 1.0 - t * t, 0.0)
        return dq + ds @ k

    lo, hi = _kv_bounds(q_offset, BQ, block_k, T // block_k,
                        causal, use_window, w, bound_loop)
    dq = jax.lax.fori_loop(lo, hi, body, jnp.zeros_like(qs))
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _flash_dkv_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref, w_ref,
                      dk_ref, dv_ref, *, block_q: int, causal: bool,
                      use_window: bool, softcap: Optional[float], scale: float,
                      group: int, bound_loop: bool):
    """dK/dV over the (B·Hkv, T//BK) grid; loops q tiles, unrolls the GQA
    group (each kv stream serves ``group`` q streams). Blocks: q/do
    (group, Sq, Dh); lse/delta (group, 1, Sq); k/v/dk/dv (1, BK, Dh)."""
    k = k_ref[0].astype(jnp.float32)                    # (BK, Dh)
    v = v_ref[0].astype(jnp.float32)
    BK = k.shape[0]
    Sq = q_ref.shape[1]
    k_offset = pl.program_id(1) * BK
    w = w_ref[0]
    n_q = Sq // block_q

    # q-tile range that can see this kv tile: causal needs q ≥ k_offset;
    # the window needs q < k_offset + BK - 1 + w.
    lo: jax.Array | int = 0
    hi: jax.Array | int = n_q
    if bound_loop:
        if causal:
            lo = k_offset // block_q
        if use_window:
            hi = jnp.minimum(n_q, (k_offset + BK + w + block_q - 2) // block_q)

    dk = jnp.zeros_like(k)
    dv = jnp.zeros_like(v)
    for g in range(group):
        def body(iq, carry, g=g):
            dk, dv = carry
            qs = q_ref[g, pl.dslice(iq * block_q, block_q), :].astype(
                jnp.float32) * scale
            do = do_ref[g, pl.dslice(iq * block_q, block_q), :].astype(
                jnp.float32)
            lse = lse_ref[g, :, pl.dslice(iq * block_q, block_q)].T
            delta = delta_ref[g, :, pl.dslice(iq * block_q, block_q)].T
            mask = _tile_mask(iq * block_q, k_offset, block_q, BK,
                              causal, use_window, w)
            s = _tile_scores(qs, k, mask, softcap)
            p = jnp.exp(s - lse)
            dp = do @ v.T
            ds = p * (dp - delta)
            if softcap is not None:
                t = s / softcap
                ds = ds * jnp.where(mask, 1.0 - t * t, 0.0)
            return dk + ds.T @ qs, dv + p.T @ do
        dk, dv = jax.lax.fori_loop(lo, hi, body, (dk, dv))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


# Per-row residuals (lse, delta) live as (B·H, 1, Sq): a (1, 1, block_q)
# block keeps the last two block dims legal for the TPU lowering ((1, Sq)
# is the array's own size on the sublane axis, block_q a lane multiple),
# where a (1, block_q) block of a (B·H, Sq) array is refused. The window
# scalar rides in SMEM, where the kernel can read it as a scalar.
_W_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def _row_spec(block_q: int):
    return pl.BlockSpec((1, 1, block_q), lambda bh, iq: (bh, 0, iq))


def _forward(q, k, v, w, *, block_q, block_k, causal, use_window, softcap,
             scale, group, bound_loop, interpret) -> Tuple[jax.Array, jax.Array]:
    BH, Sq, Dh = q.shape
    T = k.shape[1]
    kernel = functools.partial(
        _flash_kernel, block_k=block_k, causal=causal, use_window=use_window,
        softcap=softcap, scale=scale, bound_loop=bound_loop)
    kv_spec = pl.BlockSpec((1, T, Dh), lambda bh, iq: (bh // group, 0, 0))
    return pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((BH, Sq, Dh), q.dtype),
                   jax.ShapeDtypeStruct((BH, 1, Sq), jnp.float32)),
        in_specs=[pl.BlockSpec((1, block_q, Dh), lambda bh, iq: (bh, iq, 0)),
                  kv_spec, kv_spec, _W_SPEC],
        out_specs=(pl.BlockSpec((1, block_q, Dh), lambda bh, iq: (bh, iq, 0)),
                   _row_spec(block_q)),
        grid=(BH, Sq // block_q),
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v, w)


def _backward(q, k, v, w, o, lse, do, *, block_q, block_k, causal, use_window,
              softcap, scale, group, bound_loop, interpret):
    BH, Sq, Dh = q.shape
    BHkv, T = k.shape[0], k.shape[1]
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1,
                    keepdims=True).transpose(0, 2, 1)             # (BH, 1, Sq)

    dq_kernel = functools.partial(
        _flash_dq_kernel, block_k=block_k, causal=causal,
        use_window=use_window, softcap=softcap, scale=scale,
        bound_loop=bound_loop)
    kv_spec = pl.BlockSpec((1, T, Dh), lambda bh, iq: (bh // group, 0, 0))
    q_spec = pl.BlockSpec((1, block_q, Dh), lambda bh, iq: (bh, iq, 0))
    row_spec = _row_spec(block_q)
    dq = pl.pallas_call(
        dq_kernel,
        out_shape=jax.ShapeDtypeStruct((BH, Sq, Dh), q.dtype),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec,
                  _W_SPEC],
        out_specs=q_spec,
        grid=(BH, Sq // block_q),
        interpret=interpret,
        name="flash_dq",
    )(q, k, v, do, lse, delta, w)

    dkv_kernel = functools.partial(
        _flash_dkv_kernel, block_q=block_q, causal=causal,
        use_window=use_window, softcap=softcap, scale=scale, group=group,
        bound_loop=bound_loop)
    g_spec = pl.BlockSpec((group, Sq, Dh), lambda bkv, jk: (bkv, 0, 0))
    grow_spec = pl.BlockSpec((group, 1, Sq), lambda bkv, jk: (bkv, 0, 0))
    k_spec = pl.BlockSpec((1, block_k, Dh), lambda bkv, jk: (bkv, jk, 0))
    dk, dv = pl.pallas_call(
        dkv_kernel,
        out_shape=(jax.ShapeDtypeStruct((BHkv, T, Dh), k.dtype),
                   jax.ShapeDtypeStruct((BHkv, T, Dh), v.dtype)),
        in_specs=[g_spec, g_spec, grow_spec, grow_spec, k_spec, k_spec,
                  _W_SPEC],
        out_specs=(k_spec, k_spec),
        grid=(BHkv, T // block_k),
        interpret=interpret,
        name="flash_dkv",
    )(q, do, lse, delta, k, v, w)
    return dq, dk, dv


@functools.lru_cache(maxsize=None)
def _make_flash_fn(block_q: int, block_k: int, causal: bool, use_window: bool,
                   softcap: Optional[float], scale: float, group: int,
                   bound_loop: bool, interpret: bool):
    """custom_vjp flash attention for one static config. The sliding window
    ``w`` is a (1,) int32 PRIMAL (it may be traced — gemma2's scanned
    is_local); its cotangent is float0."""
    opts = {"block_q": block_q, "block_k": block_k, "causal": causal,
            "use_window": use_window, "softcap": softcap, "scale": scale,
            "group": group, "bound_loop": bound_loop, "interpret": interpret}

    @jax.custom_vjp
    def fa(q, k, v, w):
        return _forward(q, k, v, w, **opts)[0]

    def fa_fwd(q, k, v, w):
        o, lse = _forward(q, k, v, w, **opts)
        return o, (q, k, v, w, o, lse)

    def fa_bwd(res, do):
        q, k, v, w, o, lse = res
        dq, dk, dv = _backward(q, k, v, w, o, lse, do, **opts)
        return dq, dk, dv, np.zeros((1,), jax.dtypes.float0)

    fa.defvjp(fa_fwd, fa_bwd)
    return fa


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                           block_q: int = 128, block_k: int = 128,
                           causal: bool = True,
                           window=None,
                           softcap: Optional[float] = None,
                           interpret: bool = False,
                           group: int = 1,
                           scale: Optional[float] = None,
                           bound_loop: bool = True) -> jax.Array:
    """q: (B·H, Sq, Dh); k/v: (B·Hkv, T, Dh) with H = Hkv·group (streams
    ordered head-major so q stream i reads kv stream i // group). Returns
    (B·H, Sq, Dh) in q dtype. Differentiable (custom_vjp with recompute).

    ``scale`` defaults to 1/sqrt(Dh); pass 1.0 for pre-scaled queries.
    ``window`` may be a python int or a traced int scalar (dynamic per-layer
    sliding window); values ≥ T are a no-op. VMEM per program: 2·T·Dh f32
    (K,V) + 3 q-tiles ⇒ guard at ~12 MB.
    """
    BH, Sq, Dh = q.shape
    BHkv, T = k.shape[0], k.shape[1]
    if BHkv * group != BH or v.shape != k.shape:
        raise ValueError(f"GQA shapes: q {q.shape}, k {k.shape}, group={group}")
    if Sq % block_q or T % block_k:
        raise ValueError(f"Sq={Sq} % {block_q} or T={T} % {block_k} != 0")
    from repro.analysis.vmem import flash_forward_vmem
    est = flash_forward_vmem(T, Dh, block_q)
    if not est.fits:
        raise ValueError(
            f"KV stream exceeds the single-program VMEM budget "
            f"({est.describe()}); use the jnp chunked path (or KV grid "
            "tiling, TBD)")
    if scale is None:
        scale = 1.0 / (Dh ** 0.5)
    use_window = window is not None
    if window is None:
        w = jnp.full((1,), T, jnp.int32)
    else:
        w = jnp.reshape(jnp.asarray(window, jnp.int32), (1,))
    fa = _make_flash_fn(block_q, block_k, bool(causal), use_window,
                        None if softcap is None else float(softcap),
                        float(scale), int(group), bool(bound_loop),
                        bool(interpret))
    return fa(q, k, v, w)
