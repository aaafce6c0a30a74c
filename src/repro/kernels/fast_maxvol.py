"""Pallas TPU kernel for Fast MaxVol row selection (paper §3.1 Step 2).

TPU adaptation (DESIGN.md §3): the K×R feature matrix is tiny (K ≤ 1024,
R ≤ 128 ⇒ ≤ 512 KB fp32), so the WHOLE matrix lives in VMEM for the entire
R-step pivot loop — zero HBM round trips between steps, unlike the GPU
implementation's per-step kernel launches. Each step is a K-vector scan
(argmax) + rank-1 FMA update on the VPU; the loop is
``graft_select.maxvol_loop``, shared with the fused kernel.

Grid: () — selection is inherently sequential in R; parallelism is across
the K rows inside each step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.graft_select import maxvol_loop


def _fast_maxvol_kernel(v_ref, pivots_ref, logvol_ref, *, rank: int):
    """One invocation selects all ``rank`` pivots.

    v_ref:      (K, R) f32 VMEM — feature matrix.
    pivots_ref: (1, rank) i32 VMEM out.
    logvol_ref: (1, 1) f32 VMEM out — accumulated log|det|.
    """
    pivots, logvol = maxvol_loop(v_ref[...], rank)
    pivots_ref[...] = pivots.astype(jnp.int32)
    logvol_ref[...] = logvol


@functools.partial(jax.jit, static_argnames=("rank", "interpret"))
def fast_maxvol_pallas(V: jax.Array, rank: int, interpret: bool = False):
    """Run the Fast MaxVol kernel. V: (K, R) — returns (pivots (rank,), logvol).

    BlockSpec: whole array resident in VMEM (K·R ≤ 128K fp32 elements by
    construction of GRAFT's K=batch, R=r_max regime — checked by the wrapper).
    """
    K, R = V.shape
    if rank > min(K, R):
        raise ValueError(f"rank {rank} > min{V.shape}")
    if K * R * 4 > 8 * 1024 * 1024:
        raise ValueError("feature matrix exceeds the VMEM budget; shrink K or R")
    kernel = functools.partial(_fast_maxvol_kernel, rank=rank)
    pivots, logvol = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((1, rank), jnp.int32),
                   jax.ShapeDtypeStruct((1, 1), jnp.float32)),
        in_specs=[pl.BlockSpec((K, R), lambda: (0, 0))],
        out_specs=(pl.BlockSpec((1, rank), lambda: (0, 0)),
                   pl.BlockSpec((1, 1), lambda: (0, 0))),
        grid=(),
        interpret=interpret,
        name="fast_maxvol",
    )(V.astype(jnp.float32))
    return pivots[0], logvol[0, 0]
