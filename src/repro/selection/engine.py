"""Selection execution engines: single-batch, vmapped multi-batch, and
shard_map data-parallel.

Three ways to run one sampler (all speak the Sampler-v2 protocol: every
path threads the sampler's *carry* — its cross-step state pytree — and
returns ``(SelectionState, carry')``; stateless samplers carry ``{}``
untouched, so their numerics are bit-identical to the pre-v2 engine):

  * :func:`select_batch` — one (K, R_max) batch on one device (the seed
    repo's only path, now sampler-generic).
  * :func:`select_multi_batch` — a stack of B per-device microbatches
    selected under ONE jit via vmap; a stateful sampler's carry gets a
    leading B axis (B independent streams).
  * :func:`make_sharded_selector` — selection over the data-parallel mesh
    axes. V/G are sharded along K by the ``act_batch`` logical rule from
    ``distributed/sharding.py``; each shard runs the sampler on its local
    rows. For GRAFT (the default) the prefix projection-error statistics
    are psum'd so every shard applies the same globally-decided rank R*;
    generic samplers run shard-locally against the pmean'd global ḡ, and a
    stateful carry is kept replicated by cross-shard averaging after each
    update.

Engines cache one jitted callable per (cfg, sampler) pair, so repeated calls
from a training loop never re-trace. ``carry=None`` means "initialize a
fresh carry from the input shapes" — one-shot call sites never have to
touch :meth:`Sampler.init_carry` themselves.
"""
from __future__ import annotations

import functools
from typing import Optional, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from repro.core import projection as proj_lib
from repro.distributed import sharding as sh
from repro.selection import graft as graft_lib
from repro.selection import registry
from repro.selection.base import (Carry, CarrySpec, GraftConfig, Sampler,
                                  SelectionInputs, SelectionState,
                                  default_select_key)

SamplerLike = Union[str, Sampler]


# shared step-folded derivation — kept under the old name for engine-internal
# call sites
_default_key = default_select_key


def _resolve(cfg: GraftConfig, sampler: SamplerLike, scores) -> Sampler:
    smp = registry.get_sampler(sampler)
    if smp.needs_scores and scores is None:
        # same actionable error as Sampler.select: the engine auto-derives a
        # key for stochastic samplers but NEVER invents scores
        raise ValueError(
            f"sampler '{smp.name}' requires SelectionInputs.scores — "
            f"pass scores=... (engine paths fill defaults only for "
            f"samplers that do not declare needs_scores)")
    return smp


def _fresh_carry(smp: Sampler, cfg: GraftConfig, V: jax.Array,
                 G: jax.Array) -> Carry:
    return smp.init_carry(cfg, CarrySpec(batch_size=int(V.shape[-2]),
                                         grad_dim=int(G.shape[-2])))


# ---------------------------------------------------------------------------
# single batch
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=64)
def _single_batch_compiled(cfg: GraftConfig, smp: Sampler):
    # keyed on the Sampler VALUE (frozen dataclass), not its name, so a
    # re-registration under the same name gets its own compiled entry
    def fn(V, G, g_bar, scores, key, carry, step):
        return smp.select(cfg, SelectionInputs(V, G, g_bar, scores, key),
                          carry, step)

    return jax.jit(fn)


def select_batch(cfg: GraftConfig, sampler: SamplerLike, V: jax.Array,
                 G: jax.Array, g_bar: jax.Array, *,
                 scores: Optional[jax.Array] = None,
                 key: Optional[jax.Array] = None,
                 carry: Carry = None, step=0):
    """Run ``sampler`` on one (K, R_max) batch. Registry-resolved, jit-cached.

    Returns ``(SelectionState, carry')``; feed ``carry'`` back in to stream
    across calls (stateless samplers return ``{}`` unchanged).
    """
    smp = _resolve(cfg, sampler, scores)
    if scores is None:
        scores = jnp.zeros((V.shape[0],), jnp.float32)
    if key is None:
        key = _default_key(step)
    if carry is None:
        carry = _fresh_carry(smp, cfg, V, G)
    return _single_batch_compiled(cfg, smp)(
        V, G, g_bar, scores, key, carry, jnp.int32(step))


# ---------------------------------------------------------------------------
# vmapped multi-batch
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=64)
def _multi_batch_compiled(cfg: GraftConfig, smp: Sampler):
    if cfg.use_pallas and smp.fn is graft_lib.graft_sampler_fn:
        # vmap over a grid=() pallas_call has no Mosaic lowering — the GRAFT
        # fast path dispatches the whole stack as ONE grid=(B,) fused launch
        def fn(V, G, g_bar, scores, keys, carry, step):
            return graft_lib.graft_select_batched(cfg, V, G, g_bar, step), carry
        return jax.jit(fn)

    def fn(V, G, g_bar, scores, keys, carry, step):
        def one(v, g, gb, sc, k, c):
            return smp.select(cfg, SelectionInputs(v, g, gb, sc, k), c, step)
        return jax.vmap(one)(V, G, g_bar, scores, keys, carry)

    return jax.jit(fn)


def select_multi_batch(cfg: GraftConfig, sampler: SamplerLike, V: jax.Array,
                       G: jax.Array, g_bar: jax.Array, *,
                       scores: Optional[jax.Array] = None,
                       keys: Optional[jax.Array] = None,
                       carry: Carry = None, step=0):
    """Select for a STACK of microbatches under one jit.

    ``V``: (B, K, R_max); ``G``: (B, d, K); ``g_bar``: (B, d); optional
    ``scores``: (B, K) and ``keys``: (B, 2) per-microbatch PRNG keys.
    Returns ``(SelectionState, carry')`` whose leaves carry a leading B
    axis — semantically identical to a Python loop of :func:`select_batch`
    calls, but compiled once and batched on-device. A stateful sampler's
    carry is B-stacked: each microbatch lane streams independently
    (``carry=None`` broadcasts one fresh carry across the stack).
    """
    smp = _resolve(cfg, sampler, scores)
    B = V.shape[0]
    if scores is None:
        scores = jnp.zeros(V.shape[:2], jnp.float32)
    if keys is None:
        keys = jax.random.split(_default_key(step), B)
    if carry is None:
        carry = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (B,) + x.shape),
            _fresh_carry(smp, cfg, V, G))
    return _multi_batch_compiled(cfg, smp)(
        V, G, g_bar, scores, keys, carry, jnp.int32(step))


# ---------------------------------------------------------------------------
# shard_map data-parallel selection
# ---------------------------------------------------------------------------

def _as_mesh(mesh_or_backend) -> Mesh:
    """Accept a ``repro.backend.Backend`` anywhere a mesh is expected —
    callers holding a backend shouldn't have to know it owns a mesh."""
    if isinstance(mesh_or_backend, Mesh):
        return mesh_or_backend
    getter = getattr(mesh_or_backend, "mesh", None)
    return getter() if callable(getter) else mesh_or_backend


def _batch_axes(mesh: Mesh, batch_logical: str, rules):
    """Mesh axis names the logical rule table maps ``batch_logical`` to."""
    entry = tuple(sh.logical_to_spec((batch_logical,), mesh, rules))[0]
    if entry is None:
        raise ValueError(
            f"logical axis '{batch_logical}' maps to no axis of mesh "
            f"{mesh.axis_names}; nothing to shard selection over")
    axes = (entry,) if isinstance(entry, str) else tuple(entry)
    return entry, axes


def make_sharded_selector(cfg: GraftConfig, mesh: Mesh, *,
                          sampler: SamplerLike = "graft",
                          batch_logical: str = "act_batch", rules=None):
    """Build (or fetch the cached) jitted data-parallel selector.

    Returns ``fn(V, G, step=0, *, scores=None, carry=None) ->
    (SelectionState, carry')`` where V (K, R_max) and G (d, K) are sharded
    along K over the mesh axes assigned to ``batch_logical`` (n_shards
    ways). Per shard: the sampler runs on the local K/n rows against the
    pmean'd global ḡ. For GRAFT (the default) the prefix projection errors
    are additionally pmean'd so the rank decision R* is identical on every
    shard. The returned state concatenates the shards — pivots/weights have
    shape (n_shards·R_max,) with GLOBAL row indices and weights summing to
    1 over the active entries; ``rank`` is the per-shard R*. A stateful
    carry stays replicated: every shard's update is averaged (float leaves)
    or pmax'd (integer leaves) across the mesh.
    """
    smp = registry.get_sampler(sampler)
    rules_key = tuple(sorted(rules.items())) if rules else None
    return _sharded_selector_cached(cfg, smp, _as_mesh(mesh), batch_logical,
                                    rules_key)


@functools.lru_cache(maxsize=64)
def _sharded_selector_cached(cfg: GraftConfig, smp: Sampler, mesh: Mesh,
                             batch_logical: str, rules_key):
    rules = dict(rules_key) if rules_key else None
    entry, axes = _batch_axes(mesh, batch_logical, rules)
    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]
    r_max = cfg.r_max

    def _shard_index(K_local):
        shard = jnp.int32(0)
        for a in axes:              # global shard index, first axis major
            shard = shard * mesh.shape[a] + jax.lax.axis_index(a)
        return shard

    def _sync_carry(carry):
        # keep the carry replicated across the mesh: shard-local updates are
        # averaged (float leaves) / pmax'd (integer leaves) — stateless {}
        # passes through untouched
        def sync(leaf):
            if jnp.issubdtype(leaf.dtype, jnp.inexact):
                return jax.lax.pmean(leaf, axes)
            return jax.lax.pmax(leaf, axes)
        return jax.tree_util.tree_map(sync, carry)

    if smp.fn is graft_lib.graft_sampler_fn:
        # the specialized GRAFT path: globally-synchronized rank decision,
        # bit-identical to the pre-v2 sharded selector
        def shard_fn(V_s, G_s, scores_s, carry, step):
            K_local = V_s.shape[0]
            g_bar = jax.lax.pmean(jnp.mean(G_s, axis=1), axes)      # global ḡ
            # local refresh: ONE fused Pallas dispatch under cfg.use_pallas,
            # else the jnp chain — then the error statistics are pmean'd so
            # the rank decision R* is identical on every shard
            pivots, local_errors, G_sel = graft_lib.pivot_and_sweep(
                cfg, V_s, G_s, g_bar)
            errors = jax.lax.pmean(local_errors, axes)
            rank, err = proj_lib.select_rank(errors, cfg.rset, cfg.eps)
            active = (jnp.arange(r_max) < rank).astype(jnp.float32)
            weights = active / jnp.maximum(n_shards * jnp.sum(active), 1.0)
            g_sub = jax.lax.psum(G_sel @ weights, axes)     # global subset ḡ
            align = proj_lib.cosine_alignment(g_sub, g_bar)
            pivots_global = pivots + _shard_index(K_local) * K_local
            state = SelectionState(pivots=pivots_global.astype(jnp.int32),
                                   weights=weights, rank=rank, last_error=err,
                                   alignment=align, step=jnp.int32(step))
            return state, carry
    else:
        def shard_fn(V_s, G_s, scores_s, carry, step):
            K_local = V_s.shape[0]
            g_bar = jax.lax.pmean(jnp.mean(G_s, axis=1), axes)      # global ḡ
            shard = _shard_index(K_local)
            # per-shard key so stochastic samplers draw independent rows
            key = jax.random.fold_in(_default_key(step), shard)
            state, carry = smp.select(
                cfg, SelectionInputs(V_s, G_s, g_bar, scores_s, key),
                carry, step)
            # local weights sum to 1 → global sum 1 across n_shards
            weights = state.weights / n_shards
            state = SelectionState(
                pivots=(state.pivots + shard * K_local).astype(jnp.int32),
                weights=weights,
                rank=jax.lax.pmax(state.rank, axes),
                last_error=jax.lax.pmean(state.last_error, axes),
                alignment=jax.lax.pmean(state.alignment, axes),
                step=jnp.int32(step))
            return state, _sync_carry(carry)

    # check_vma=False: the scan/fori_loop bodies inside MaxVol and the MGS
    # sweep defeat shard_map's conservative replication inference even though
    # every P() output is pmean/psum-replicated by construction.
    fn = shard_map(shard_fn, mesh=mesh,
                   in_specs=(P(entry, None), P(None, entry), P(entry),
                             P(), P()),
                   out_specs=(SelectionState(P(entry), P(entry), P(),
                                             P(), P(), P()),
                              P()),
                   check_vma=False)
    jitted = jax.jit(fn)

    def selector(V, G, step=0, *, scores=None, carry=None):
        if smp.needs_scores and scores is None:
            raise ValueError(
                f"sampler '{smp.name}' requires SelectionInputs.scores — "
                f"pass scores=... (engine paths fill defaults only for "
                f"samplers that do not declare needs_scores)")
        if scores is None:
            scores = jnp.zeros((V.shape[0],), jnp.float32)
        if carry is None:
            carry = _fresh_carry(smp, cfg, V, G)
        return jitted(V, G, scores, carry, jnp.int32(step))

    return selector


def select_sharded(cfg: GraftConfig, mesh: Mesh, V: jax.Array, G: jax.Array,
                   *, sampler: SamplerLike = "graft",
                   scores: Optional[jax.Array] = None, carry: Carry = None,
                   step=0, batch_logical: str = "act_batch", rules=None):
    """One-shot convenience over :func:`make_sharded_selector`."""
    mesh = _as_mesh(mesh)
    _, axes = _batch_axes(mesh, batch_logical, rules)
    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]
    K = V.shape[0]
    if K % n_shards:
        raise ValueError(f"batch {K} not divisible by {n_shards} shards")
    if K // n_shards < cfg.r_max:
        raise ValueError(f"per-shard batch {K // n_shards} < r_max {cfg.r_max}")
    return make_sharded_selector(cfg, mesh, sampler=sampler,
                                 batch_logical=batch_logical,
                                 rules=rules)(V, G, step,
                                              scores=scores, carry=carry)
