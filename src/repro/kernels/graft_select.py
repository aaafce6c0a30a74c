"""Fused Pallas kernel for one whole GRAFT selection refresh (paper §3.1-3.2).

The unfused path is three device dispatches with an HBM round-trip between
each: ``fast_maxvol`` (pivots) → ``jnp.take`` (gather the pivot columns of
G) → ``projection_sweep`` (prefix errors). Here all three run in a single
``pallas_call`` with ``V (K, R)`` and ``G (d, K)`` resident in VMEM for the
whole refresh:

  1. Fast MaxVol pivot loop on V (``maxvol_loop``) — the arithmetic of
     ``core.maxvol.fast_maxvol``, so pivots are bit-identical to it.
  2. Column gather ``G_sel = G[:, pivots]`` (``gather_columns``) — exact.
  3. MGS prefix projection-error sweep over ``G_sel`` against ``ḡ``
     (``mgs_errors``).

The standalone ``fast_maxvol`` and ``projection_sweep`` kernels run these
same loop functions, so the fused and unfused kernel chains agree bit for
bit. Both entry points launch one ``grid=(B,)`` kernel:

  * ``fused_graft_select_pallas``          — one (K, R) batch, B = 1.
  * ``fused_graft_select_batched_pallas``  — a whole microbatch stack in
    ONE kernel launch (each grid step owns one batch's VMEM blocks). This
    is what ``engine.select_multi_batch`` dispatches instead of vmapping
    the single-batch kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.numerics import safe_pivot

# MGS guard, as in core/projection.py
_EPS = 1e-12


def _iota(shape, dim: int) -> jax.Array:
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


# Mosaic lowers no dynamic slice of a loaded value, so every per-step
# column, row or element read below is a masked reduction: ``where`` keeps
# exactly one nonzero per output and the sum of one value and zeros is
# that value, so the reads are exact. Vectors stay 2-D ((K, 1) columns,
# (1, R) rows, (1, 1) scalars), as the TPU lowering wants, and row indices
# ride as f32 next to the data (exact below 2**24).

def maxvol_loop(V: jax.Array, rank: int):
    """Fast MaxVol on a loaded (K, R) f32 value — the arithmetic of
    ``core.maxvol.fast_maxvol`` (same ``safe_pivot`` guard, same
    first-index tie-break), so pivots are bit-identical to it.

    Returns (pivots (1, rank) f32 row indices, logvol (1, 1) f32).
    """
    K, R = V.shape
    rows = _iota((K, 1), 0).astype(jnp.float32)
    cols = _iota((1, R), 1)
    slots = _iota((1, rank), 1)

    def body(j, carry):
        W, avail, pivots, logvol = carry
        col = jnp.sum(jnp.where(cols == j, W, 0.0), axis=1,
                      keepdims=True)                    # W[:, j]   (K, 1)
        scores = jnp.where(avail > 0, jnp.abs(col), -1.0)
        best = jnp.max(scores, axis=0, keepdims=True)
        # argmax: the first row attaining the max
        pj = jnp.min(jnp.where(scores == best, rows, float(K)), axis=0,
                     keepdims=True)                     # (1, 1)
        is_pivot = rows == pj                           # (K, 1)
        pivot_val = safe_pivot(jnp.sum(jnp.where(is_pivot, col, 0.0),
                                       axis=0, keepdims=True))
        factor = col / pivot_val                        # (K, 1)
        pivot_row = jnp.sum(jnp.where(is_pivot, W, 0.0), axis=0,
                            keepdims=True)              # W[pj, :]  (1, R)
        # eliminate column j everywhere but the pivot row
        W = jnp.where(is_pivot, W, W - factor * pivot_row)
        avail = jnp.where(is_pivot, 0.0, avail)
        pivots = jnp.where(slots == j, pj, pivots)
        return W, avail, pivots, logvol + jnp.log(jnp.abs(pivot_val))

    _, _, pivots, logvol = jax.lax.fori_loop(
        0, rank, body,
        (V, jnp.ones((K, 1), jnp.float32), jnp.zeros((1, rank), jnp.float32),
         jnp.zeros((1, 1), jnp.float32)))
    return pivots, logvol


def gather_columns(G: jax.Array, pivots: jax.Array) -> jax.Array:
    """``G[:, pivots]`` for G (d, K) and f32 pivots (1, rank) — exact."""
    d, K = G.shape
    rank = pivots.shape[1]
    lanes = _iota((1, K), 1).astype(jnp.float32)
    slots = _iota((1, rank), 1)

    def body(j, G_sel):
        pj = jnp.sum(jnp.where(slots == j, pivots, 0.0), axis=1,
                     keepdims=True)                     # (1, 1)
        col = jnp.sum(jnp.where(lanes == pj, G, 0.0), axis=1,
                      keepdims=True)                    # G[:, pj]  (d, 1)
        return jnp.where(slots == j, col, G_sel)

    return jax.lax.fori_loop(0, rank, body,
                             jnp.zeros((d, rank), jnp.float32))


def mgs_errors(G: jax.Array, g_bar: jax.Array) -> jax.Array:
    """Prefix projection errors ``1 - ||Q_r^T g_hat||^2`` of G (d, R)
    against g_bar (d, 1), by two-pass modified Gram-Schmidt (the
    arithmetic of ``core.projection.prefix_projection_errors``, with the
    products summed on the VPU). Returns (1, R) f32."""
    d, R = G.shape
    slots = _iota((1, R), 1)
    g_hat = g_bar / jnp.sqrt(jnp.sum(g_bar * g_bar, axis=0, keepdims=True)
                             + _EPS)

    def body(j, carry):
        Q, captured, errs = carry                       # Q: (d, R), cols < j
        q = jnp.sum(jnp.where(slots == j, G, 0.0), axis=1,
                    keepdims=True)                      # G[:, j]   (d, 1)
        for _ in range(2):                              # q -= Q (Q^T q)
            coef = jnp.sum(Q * q, axis=0, keepdims=True)
            q = q - jnp.sum(Q * coef, axis=1, keepdims=True)
        nrm = jnp.sqrt(jnp.sum(q * q, axis=0, keepdims=True))
        q = jnp.where(nrm > 1e-8, q / (nrm + _EPS), 0.0)
        Q = jnp.where(slots == j, q, Q)
        captured = captured + jnp.sum(q * g_hat, axis=0, keepdims=True) ** 2
        errs = jnp.where(slots == j, jnp.clip(1.0 - captured, 0.0, 1.0),
                         errs)
        return Q, captured, errs

    _, _, errs = jax.lax.fori_loop(
        0, R, body,
        (jnp.zeros((d, R), jnp.float32), jnp.zeros((1, 1), jnp.float32),
         jnp.zeros((1, R), jnp.float32)))
    return errs


def _fused_body(V, G, g_bar, rank: int):
    """The whole refresh on loaded VMEM values: V (K, R), G (d, K),
    g_bar (d, 1). Returns (pivots (1, rank) f32, errors (1, rank),
    logvol (1, 1), G_sel (d, rank))."""
    pivots, logvol = maxvol_loop(V, rank)
    G_sel = gather_columns(G, pivots)
    return pivots, mgs_errors(G_sel, g_bar), logvol, G_sel


def _fused_kernel(v_ref, g_ref, gbar_ref,
                  piv_ref, err_ref, logvol_ref, gsel_ref, *, rank: int):
    # every ref carries a leading block dim of 1 (one grid step = one batch)
    pivots, errors, logvol, G_sel = _fused_body(
        v_ref[0], g_ref[0], gbar_ref[0], rank)
    piv_ref[0] = pivots.astype(jnp.int32)
    err_ref[0] = errors
    logvol_ref[0] = logvol
    gsel_ref[0] = G_sel


def _check_budget(K: int, R: int, d: int, rank: int) -> None:
    # resident f32 blocks: V, the residual W, G, G_sel and the MGS basis Q —
    # accounted by the shared estimator (repro.analysis.vmem), so the
    # static checker and this runtime guard can never disagree
    from repro.analysis.vmem import fused_select_vmem
    est = fused_select_vmem(K, R, d, rank)
    if not est.fits:
        raise ValueError(
            f"fused selection blocks ({est.total / 2**20:.1f} MB) exceed "
            f"the VMEM budget; shrink K={K}, d={d} or rank={rank}")


def _select_call(V, G, g_bar, rank: int, interpret: bool):
    """The ``grid=(B,)`` launch over V (B, K, R), G (B, d, K), g_bar
    (B, d). Every block's last two dims are the array's own, which is
    what the TPU lowering accepts for shapes off the (8, 128) tile."""
    B, K, R = V.shape
    d = G.shape[1]
    _check_budget(K, R, d, rank)
    pivots, errors, logvol, gsel = pl.pallas_call(
        functools.partial(_fused_kernel, rank=rank),
        out_shape=(jax.ShapeDtypeStruct((B, 1, rank), jnp.int32),
                   jax.ShapeDtypeStruct((B, 1, rank), jnp.float32),
                   jax.ShapeDtypeStruct((B, 1, 1), jnp.float32),
                   jax.ShapeDtypeStruct((B, d, rank), jnp.float32)),
        in_specs=[pl.BlockSpec((1, K, R), lambda b: (b, 0, 0)),
                  pl.BlockSpec((1, d, K), lambda b: (b, 0, 0)),
                  pl.BlockSpec((1, d, 1), lambda b: (b, 0, 0))],
        out_specs=(pl.BlockSpec((1, 1, rank), lambda b: (b, 0, 0)),
                   pl.BlockSpec((1, 1, rank), lambda b: (b, 0, 0)),
                   pl.BlockSpec((1, 1, 1), lambda b: (b, 0, 0)),
                   pl.BlockSpec((1, d, rank), lambda b: (b, 0, 0))),
        grid=(B,),
        interpret=interpret,
        name="graft_select",
    )(V.astype(jnp.float32), G.astype(jnp.float32),
      g_bar.astype(jnp.float32)[..., None])
    return pivots[:, 0], errors[:, 0], logvol[:, 0, 0], gsel


@functools.partial(jax.jit, static_argnames=("rank", "interpret"))
def fused_graft_select_pallas(V: jax.Array, G: jax.Array, g_bar: jax.Array,
                              rank: int, interpret: bool = False):
    """One refresh, one dispatch. V: (K, R); G: (d, K); g_bar: (d,).

    Returns ``(pivots (rank,), errors (rank,), logvol (), G_sel (d, rank))``
    — pivots bit-identical to ``fast_maxvol_pallas``, errors bit-identical
    to ``projection_sweep_pallas`` on the gathered columns.
    """
    K, R = V.shape
    d, Kg = G.shape
    if Kg != K:
        raise ValueError(f"V rows {K} != G columns {Kg}")
    if g_bar.shape != (d,):
        raise ValueError(f"g_bar shape {g_bar.shape} != ({d},)")
    if rank > min(K, R):
        raise ValueError(f"rank {rank} > min{V.shape}")
    pivots, errors, logvol, gsel = _select_call(
        V[None], G[None], g_bar[None], rank, interpret)
    return pivots[0], errors[0], logvol[0], gsel[0]


@functools.partial(jax.jit, static_argnames=("rank", "interpret"))
def fused_graft_select_batched_pallas(V: jax.Array, G: jax.Array,
                                      g_bar: jax.Array, rank: int,
                                      interpret: bool = False):
    """A whole microbatch stack in ONE launch (``grid=(B,)``).

    V: (B, K, R); G: (B, d, K); g_bar: (B, d). Returns per-batch
    ``(pivots (B, rank), errors (B, rank), logvol (B,), G_sel (B, d, rank))``
    — row ``b`` identical to ``fused_graft_select_pallas`` on batch ``b``.
    """
    B, K, R = V.shape
    _, d, Kg = G.shape
    if G.shape[0] != B or g_bar.shape != (B, d) or Kg != K:
        raise ValueError(f"inconsistent batch shapes V={V.shape} G={G.shape} "
                         f"g_bar={g_bar.shape}")
    if rank > min(K, R):
        raise ValueError(f"rank {rank} > min({K}, {R})")
    return _select_call(V, G, g_bar, rank, interpret)
