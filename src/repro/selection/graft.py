"""GRAFT selector — the paper's Algorithm 1 as a jit-able JAX module.

Pipeline per refresh step (every ``S`` iterations):
  1. features: V = f(batch) ∈ R^{K×R_max}, relevance-ordered columns
  2. Fast MaxVol: pivot order p (prefixes = candidate subsets for every rank)
  3. gradient matrix G[:, j] = grad-embedding of sample p_j; ḡ = batch mean
  4. prefix projection errors d_r; R* = smallest candidate rank with d ≤ ε
  5. emit (pivots, R*, weights) — the R* active pivots first, weight 0 on
     the pivots beyond R*. The state keeps a static shape (R_max); the train
     step runs its forward and backward over the first R* rows only
     (``launch/steps.py:subset_grads`` reads the zero tail of the weights).

Between refreshes the previous selection is reused (Alg. 1 'else' branch).

This module is the real implementation; ``repro.core.graft`` re-exports it
for backwards compatibility.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import features as feat_lib
from repro.core import maxvol as maxvol_lib
from repro.core import projection as proj_lib
from repro.selection.base import GraftConfig, SelectionInputs, SelectionState, init_state

# the paper's names, kept as the canonical aliases
GraftState = SelectionState


def pivot_and_sweep(cfg: GraftConfig, V: jax.Array, G: jax.Array,
                    g_bar: jax.Array):
    """Stages 2-4 of the refresh: ``(pivots, prefix errors, G_sel)``.

    With ``cfg.use_pallas`` this is ONE fused Pallas dispatch
    (``kernels/graft_select.py``: MaxVol + gather + MGS sweep, everything
    VMEM-resident); otherwise the jnp three-op reference chain.
    """
    if cfg.use_pallas:
        from repro.kernels import ops as kernel_ops
        return kernel_ops.fused_graft_select(V, G, g_bar, cfg.r_max)
    pivots, _ = maxvol_lib.fast_maxvol(V, cfg.r_max)
    G_sel = jnp.take(G, pivots, axis=1)                    # (d, R_max)
    errors = proj_lib.prefix_projection_errors(G_sel, g_bar)
    return pivots, errors, G_sel


def _finalize(cfg: GraftConfig, pivots: jax.Array, errors: jax.Array,
              G_sel: jax.Array, g_bar: jax.Array,
              step: jax.Array) -> SelectionState:
    """Rank decision + weights + diagnostics — the cheap jnp epilogue shared
    by the single, batched and sharded refresh paths."""
    rank, err = proj_lib.select_rank(errors, cfg.rset, cfg.eps)
    active = (jnp.arange(cfg.r_max) < rank).astype(jnp.float32)
    weights = active / jnp.maximum(jnp.sum(active), 1.0)
    g_sub = G_sel @ weights                                # subset mean gradient
    align = proj_lib.cosine_alignment(g_sub, g_bar)
    return SelectionState(pivots=pivots, weights=weights, rank=rank,
                          last_error=err, alignment=align, step=step)


@functools.partial(jax.jit, static_argnames=("cfg",))
def graft_select(cfg: GraftConfig, V: jax.Array, G: jax.Array,
                 g_bar: jax.Array, step: jax.Array) -> SelectionState:
    """One selection refresh. V: (K, R_max) features (relevance-ordered);
    G: (d, K) per-sample grad embeddings; ḡ: (d,). Returns new state."""
    pivots, errors, G_sel = pivot_and_sweep(cfg, V, G, g_bar)
    return _finalize(cfg, pivots, errors, G_sel, g_bar, step)


@functools.partial(jax.jit, static_argnames=("cfg",))
def graft_select_batched(cfg: GraftConfig, V: jax.Array, G: jax.Array,
                         g_bar: jax.Array, step: jax.Array) -> SelectionState:
    """A whole microbatch stack of refreshes: V (B, K, R_max), G (B, d, K),
    ḡ (B, d). Semantically ``vmap(graft_select)`` — but with
    ``cfg.use_pallas`` the stack runs as ONE ``grid=(B,)`` kernel launch
    (vmap cannot lower a ``grid=()`` ``pallas_call`` on TPU)."""
    if cfg.use_pallas:
        from repro.kernels import ops as kernel_ops
        pivots, errors, G_sel = kernel_ops.fused_graft_select_batched(
            V, G, g_bar, cfg.r_max)
        return jax.vmap(
            lambda p, e, gs, gb: _finalize(cfg, p, e, gs, gb, step)
        )(pivots, errors, G_sel, g_bar)
    return jax.vmap(lambda v, g, gb: graft_select(cfg, v, g, gb, step)
                    )(V, G, g_bar)


def graft_sampler_fn(cfg: GraftConfig, inputs: SelectionInputs,
                     step: jax.Array) -> SelectionState:
    """Registry adapter: the ``Sampler.fn`` signature over ``graft_select``."""
    return graft_select(cfg, inputs.V, inputs.G, inputs.g_bar, step)


def maybe_refresh(cfg: GraftConfig, state: SelectionState, step: jax.Array,
                  V: jax.Array, G: jax.Array, g_bar: jax.Array) -> SelectionState:
    """Alg. 1 outer branch: refresh every S steps, else carry the old subset."""
    def do_refresh(_):
        return graft_select(cfg, V, G, g_bar, step)

    def keep(_):
        return state._replace(step=step)

    return jax.lax.cond(step % cfg.refresh_every == 0, do_refresh, keep, None)


# ---------------------------------------------------------------------------
# convenience: full selection from a raw batch matrix (paper's CNN/MLP path)
# ---------------------------------------------------------------------------

def select_from_batch(cfg: GraftConfig, batch_matrix: jax.Array,
                      loss_fn=None, params=None,
                      grad_fn_outputs: Optional[Tuple[jax.Array, jax.Array]] = None,
                      step: int = 0) -> SelectionState:
    """End-to-end selection when the batch is a plain (K, M) matrix.

    ``grad_fn_outputs``: optional precomputed (G (d,K), ḡ (d,)). If absent and
    ``loss_fn``/``params`` given, exact per-sample grads are used (small
    models). Features always from ``cfg.feature_mode`` on the raw batch.
    """
    from repro.core import grad_features as gf
    V = feat_lib.extract(cfg.feature_mode, batch_matrix, cfg.r_max)
    if grad_fn_outputs is not None:
        G, g_bar = grad_fn_outputs
    else:
        if loss_fn is None or params is None:
            raise ValueError("need loss_fn+params or grad_fn_outputs")
        G, g_bar = gf.per_sample_grads_full(loss_fn, params, batch_matrix)
    return graft_select(cfg, V, G, g_bar, jnp.int32(step))


__all__ = ["GraftConfig", "GraftState", "SelectionState", "init_state",
           "graft_select", "graft_select_batched", "graft_sampler_fn",
           "maybe_refresh", "pivot_and_sweep", "select_from_batch"]
