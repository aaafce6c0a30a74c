"""Pallas TPU flash attention (causal, optional sliding window + softcap).

The jnp chunked-attention path (models/layers._chunked_attention) is the
SPMD-dry-run reference; this kernel is the TPU fast path with the same
online-softmax schedule but explicit VMEM residency:

  grid = (BH, Sq // BLOCK_Q): each program owns one query tile. K/V for the
  (b,h) stream stay VMEM-resident across the program's KV loop (budget-
  guarded by the wrapper); scores exist only as a (BLOCK_Q, BLOCK_K) tile in
  registers/VMEM.

Forward: q·kᵀ and p·v run on the tiles as stored (bf16 on the chip) with
f32 accumulation; q·kᵀ products of bf16 values are exact in f32, and p
enters p·v as two terms of v's type (hi + lo, about 16 significant bits).
m and l are f32 (BLOCK_Q, 1) columns, so no tile relayouts them (a
lane-replicated (BLOCK_Q, 128) runs no faster on a v5e); acc is f32. Only
the KV tiles that cross the causal diagonal or the window's edge build the
mask. The forward takes its own, larger tiles
(``models/layers.py:_flash_blocks``); the backward kernels keep theirs.

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
# contribution entirely (p = 0), not exp(0) = 1 — see _flash_kernel.
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


def _pv(p, v) -> jax.Array:
    """p·v accumulated in f32 at about 16 significant bits of p: a v in a
    narrower type takes p as two terms of that type, hi + lo, so the MXU
    runs at v's own operand type and p loses only what the two drop."""
    if v.dtype == jnp.float32:
        return jnp.dot(p, v, preferred_element_type=jnp.float32)
    p_hi = p.astype(v.dtype)
    p_lo = (p - p_hi.astype(jnp.float32)).astype(v.dtype)
    return (jnp.dot(p_hi, v, preferred_element_type=jnp.float32)
            + jnp.dot(p_lo, v, preferred_element_type=jnp.float32))


def _flash_kernel(q_ref, k_ref, v_ref, w_ref, o_ref, lse_ref, *, block_k: int,
                  causal: bool, use_window: bool, softcap: Optional[float],
                  scale: float, bound_loop: bool):
    """Blocks: q (1, BQ, Dh); k/v (1, T, Dh); w (1,) in SMEM;
    o (1, BQ, Dh); lse (1, 1, BQ) f32.

    q·kᵀ and p·v take the tiles in their stored type with f32
    accumulation. Only the KV tiles that cross the causal diagonal or the
    window's edge build and apply the mask; the tiles between them are
    seen whole by every row of the q tile."""
    q = q_ref[0]                                       # (BQ, Dh)
    BQ, Dh = q.shape
    T = k_ref.shape[1]
    q_offset = pl.program_id(1) * BQ
    w = w_ref[0]

    def tile(i, carry, masked: bool):
        m, l, acc = carry
        start = pl.multiple_of(i * block_k, block_k)
        k = k_ref[0, pl.dslice(start, block_k), :]
        v = v_ref[0, pl.dslice(start, block_k), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if scale != 1.0:
            s = s * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        if masked:
            s = jnp.where(_tile_mask(q_offset, start, BQ, block_k, causal,
                                     use_window, w), s, _MASK)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        if masked and use_window:
            # Only a window can mask a row's whole horizon so far: its m_new
            # stays at the _MASK sentinel, and without the guard p = exp(0)
            # = 1 there, silently averaging V.
            p = jnp.where(m_new > _MASK_GUARD, p, 0.0)
        l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        acc = acc * alpha + _pv(p, v)
        return m_new, l, acc

    masked = functools.partial(tile, masked=True)
    whole = functools.partial(tile, masked=False)
    lo, hi = _kv_bounds(q_offset, BQ, block_k, T // block_k,
                        causal, use_window, w, bound_loop)
    # [first, last): tiles every row of this q tile sees whole — past the
    # window's edge for the tile's last row, at or below the diagonal for
    # its first. Tiles wholly outside the mask (bound_loop=False) stay on
    # the masked path, where they add exactly nothing.
    first, last = lo, hi
    if use_window:
        first = jnp.clip((q_offset + BQ - 1 - w) // block_k + 1, lo, hi)
    if causal:
        last = jnp.clip((q_offset + 1) // block_k, first, hi)
    carry = (jnp.full((BQ, 1), _MASK, jnp.float32),
             jnp.zeros((BQ, 1), jnp.float32),
             jnp.zeros((BQ, Dh), jnp.float32))
    if use_window:
        carry = jax.lax.fori_loop(lo, first, masked, carry)
    carry = jax.lax.fori_loop(first, last, whole, carry)
    if causal:
        carry = jax.lax.fori_loop(last, hi, masked, carry)
    m, l, acc = carry
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    # logsumexp residual for the backward recompute; +inf marks rows whose
    # whole horizon is masked (output 0), so bwd p = exp(s - lse) = 0 there.
    lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), jnp.inf)
    lse_ref[0] = lse.T


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
def _make_flash_fn(fwd_blocks: Tuple[int, int], bwd_blocks: Tuple[int, int],
                   causal: bool, use_window: bool, softcap: Optional[float],
                   scale: float, group: int, bound_loop: bool,
                   interpret: bool):
    """custom_vjp flash attention for one static config; the forward and
    the backward kernels take their own (block_q, block_k) tiles. The
    sliding window ``w`` is a (1,) int32 PRIMAL (it may be traced —
    gemma2's scanned is_local); its cotangent is float0."""
    opts = {"causal": causal, "use_window": use_window, "softcap": softcap,
            "scale": scale, "group": group, "bound_loop": bound_loop,
            "interpret": interpret}
    fwd = dict(opts, block_q=fwd_blocks[0], block_k=fwd_blocks[1])
    bwd = dict(opts, block_q=bwd_blocks[0], block_k=bwd_blocks[1])

    @jax.custom_vjp
    def fa(q, k, v, w):
        return _forward(q, k, v, w, **fwd)[0]

    def fa_fwd(q, k, v, w):
        o, lse = _forward(q, k, v, w, **fwd)
        return o, (q, k, v, w, o, lse)

    def fa_bwd(res, do):
        q, k, v, w, o, lse = res
        dq, dk, dv = _backward(q, k, v, w, o, lse, do, **bwd)
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
                           bound_loop: bool = True,
                           fwd_block_q: Optional[int] = None,
                           fwd_block_k: Optional[int] = None) -> jax.Array:
    """q: (B·H, Sq, Dh); k/v: (B·Hkv, T, Dh) with H = Hkv·group (streams
    ordered head-major so q stream i reads kv stream i // group). Returns
    (B·H, Sq, Dh) in q dtype. Differentiable (custom_vjp with recompute).

    ``block_q``/``block_k`` tile the backward kernels, and the forward
    unless ``fwd_block_q``/``fwd_block_k`` say otherwise.
    ``scale`` defaults to 1/sqrt(Dh); pass 1.0 for pre-scaled queries.
    ``window`` may be a python int or a traced int scalar (dynamic per-layer
    sliding window); values ≥ T are a no-op. The forward's VMEM per program
    (``analysis/vmem.py:flash_forward_vmem``) is guarded at 12 MB.
    """
    BH, Sq, Dh = q.shape
    BHkv, T = k.shape[0], k.shape[1]
    fbq = block_q if fwd_block_q is None else fwd_block_q
    fbk = block_k if fwd_block_k is None else fwd_block_k
    if BHkv * group != BH or v.shape != k.shape:
        raise ValueError(f"GQA shapes: q {q.shape}, k {k.shape}, group={group}")
    for bq, bk in ((block_q, block_k), (fbq, fbk)):
        if Sq % bq or T % bk:
            raise ValueError(f"Sq={Sq} % {bq} or T={T} % {bk} != 0")
    from repro.analysis.vmem import flash_forward_vmem
    est = flash_forward_vmem(T, Dh, fbq, fbk, k.dtype.itemsize)
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
    fa = _make_flash_fn((int(fbq), int(fbk)), (int(block_q), int(block_k)),
                        bool(causal), use_window,
                        None if softcap is None else float(softcap),
                        float(scale), int(group), bool(bound_loop),
                        bool(interpret))
    return fa(q, k, v, w)
