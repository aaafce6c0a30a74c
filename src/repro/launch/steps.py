"""Jittable train / serve steps with GRAFT integrated as a first-class
feature, plus abstract state construction for the no-allocation dry-run.

Three step families:
  * ``baseline_train_step``  — full-batch fwd+bwd+update (the paper's "Full")
  * ``graft_train_step``     — selection forward (features + grad embeddings
    + Fast MaxVol + rank choice) followed by subset fwd+bwd+update. With
    ``refresh_every == 1`` the selection is unconditional (dry-run worst
    case); otherwise a ``lax.cond`` reuses the previous subset (paper Alg. 1).
    ``subset_train_step`` is the update alone, on the stored subset.
  * ``prefill_step`` / ``decode_step`` — serving paths.

The subset's forward and backward run over the selected rows of nonzero
weight only: ``subset_grads`` picks, on the device, the smallest row count
of ``rank_ladder`` (the candidate ranks and R_max) past which every weight
is 0, and runs that count's static-shape branch of one ``lax.switch``. A
rank-R* selection thus costs R* rows, not R_max; the step reports the count
as ``subset_rows``.

Each train step names its phases with ``jax.named_scope``
(``repro.launch.tracing``: ``select_forward``, ``select_decide``,
``train_grad``, ``optimizer``, ``sentinel``), so a device trace splits the
step by phase. The scopes are metadata only.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain, logical_axis_size
from repro.launch import tracing
from repro.models import decode as decode_lib
from repro.models import model as model_lib
from repro.optim import OptimizerConfig, make_optimizer
from repro.selection import base as selection_base
from repro.selection import graft as graft_lib
from repro.selection import registry as sampler_registry
from repro.selection import sources as sources_lib

PyTree = Any


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    optimizer: OptimizerConfig = OptimizerConfig()
    graft: Optional[graft_lib.GraftConfig] = None
    sampler: str = "graft"          # registry name; any repro.selection sampler
    probe_positions: int = 256      # positions per sequence for grad embeddings
                                    # (0 = all; the paper's K×M regime is tiny)
    microbatches: int = 1           # >1: sequential accumulation (§Perf memory lever)
    sentinel: bool = True           # on-device divergence sentinel: fused
                                    # health word + skip-update (a poisoned
                                    # gradient never touches params)
    spike_z: float = 6.0            # loss-spike z-score vs the EMA carried in
                                    # train state (0 = finite-checks only)

    @property
    def use_graft(self) -> bool:
        return self.graft is not None


# ---------------------------------------------------------------------------
# state
# ---------------------------------------------------------------------------

# steps of healthy-loss EMA history required before the spike z-score may
# veto a step — a cold EMA (mean 0, var 0) would flag the very first loss
SENTINEL_WARMUP = 16


def init_health() -> Dict[str, jax.Array]:
    """Divergence-sentinel carry: loss EMA (mean/var), its sample count,
    and the consecutive-bad-step streak — all device scalars, updated
    inside the train step so the sentinel costs zero host syncs."""
    return {"ema_mean": jnp.float32(0.0), "ema_var": jnp.float32(0.0),
            "count": jnp.int32(0), "bad_streak": jnp.int32(0)}


def init_sampler_carry(mcfg, tcfg: TrainConfig, params, batch_size: int):
    """The registry sampler's initial cross-step state (Sampler-v2 carry).

    ``{}`` for the stateless strategies; the (L, d) sketch reservoir for
    ``streaming_graft``. The gradient-embedding width d comes from the
    registered grad source (``embed_dim``), so the carry is sized before
    any batch exists — shape-only, safe under ``eval_shape``.
    """
    smp = sampler_registry.get_sampler(tcfg.sampler)
    grad_source = sources_lib.resolve_grad_source(tcfg.graft.grad_mode)
    spec = selection_base.CarrySpec(
        batch_size=batch_size, grad_dim=grad_source.embed_dim(mcfg, params))
    return smp.init_carry(tcfg.graft, spec)


def init_train_state(mcfg: model_lib.ModelConfig, tcfg: TrainConfig,
                     key: jax.Array, batch_size: int) -> Dict[str, PyTree]:
    params = model_lib.init_params(mcfg, key)
    opt = make_optimizer(tcfg.optimizer)
    state: Dict[str, PyTree] = {
        "params": params,
        "opt": opt.init(params),
        "step": jnp.int32(0),
    }
    if tcfg.use_graft:
        state["graft"] = graft_lib.init_state(tcfg.graft, batch_size)
        state["sampler_carry"] = init_sampler_carry(mcfg, tcfg, params,
                                                    batch_size)
    if tcfg.sentinel:
        state["health"] = init_health()
    return state


def _replicated_logical(tree):
    return jax.tree_util.tree_map(
        lambda leaf: tuple(None for _ in getattr(leaf, "shape", ())), tree)


def opt_state_logical(opt_name: str, p_logical, abstract_params):
    """Logical-axis tree for the optimizer state (mirrors param sharding;
    Adafactor's factored moments drop the reduced axis)."""
    if opt_name in ("sgd", "lion"):
        return {"m": p_logical}
    if opt_name == "adamw":
        return {"m": p_logical, "v": p_logical}
    if opt_name == "adafactor":
        def factored(lg, leaf):
            if len(leaf.shape) >= 2:
                return {"vr": tuple(lg[:-1]), "vc": tuple(lg[:-2]) + (lg[-1],)}
            return {"v": tuple(lg)}
        return {"v": jax.tree_util.tree_map(
            factored, p_logical, abstract_params,
            is_leaf=lambda x: isinstance(x, tuple))}
    raise ValueError(opt_name)


def train_state_logical(mcfg, tcfg: TrainConfig, abstract_state):
    p_logical = model_lib.params_logical(mcfg, abstract_state["params"])
    out = {
        "params": p_logical,
        "opt": opt_state_logical(tcfg.optimizer.name, p_logical,
                                 abstract_state["params"]),
        "step": (),
    }
    if "graft" in abstract_state:
        out["graft"] = _replicated_logical(abstract_state["graft"])
    if "sampler_carry" in abstract_state:
        out["sampler_carry"] = _replicated_logical(
            abstract_state["sampler_carry"])
    if "health" in abstract_state:
        out["health"] = _replicated_logical(abstract_state["health"])
    return out


# ---------------------------------------------------------------------------
# GRAFT selection inputs at LM scale (DESIGN.md §3 hardware adaptation)
# ---------------------------------------------------------------------------

def selection_inputs(mcfg, tcfg: TrainConfig, params, batch
                     ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One full-batch forward → (V (K,R_max), G (d,K), ḡ (d,), scores (K,)).

    The feature path (V) and gradient-embedding path (G) are resolved from
    the ``repro.selection.sources`` registries by ``GraftConfig.feature_mode``
    (``svd`` | ``sketch_svd`` | ``pca_sketch`` | ``pooled_raw`` | ``ica``)
    and ``GraftConfig.grad_mode`` (``probe`` | ``logit_embed`` | ``full``).
    Batch-layout agnostic: any registered data source's batch works —
    ``forward_hiddens`` dispatches on the model frontend, and the label
    padding below covers frontends whose labels don't span every position.
    Defaults reproduce the paper's setup:
    relevance-ordered SVD of mean-pooled final hiddens × per-example probe
    gradients from the softmax error signal (no extra backward). Scores =
    per-example probe cross-entropy (drives ``loss_topk``-style samplers for
    free — same logits).
    """
    gcfg = tcfg.graft
    extractor = sources_lib.resolve_features(gcfg.feature_mode)
    grad_source = sources_lib.resolve_grad_source(gcfg.grad_mode)
    h, mask = model_lib.forward_hiddens(mcfg, params, batch)
    h = jax.lax.stop_gradient(h)
    S = h.shape[1]
    stride = max(1, S // tcfg.probe_positions) if tcfg.probe_positions else 1
    hp = h[:, ::stride, :]
    labels = batch["labels"]
    if labels.shape[1] != S:                       # vlm: pad vision positions
        pad = S - labels.shape[1]
        labels = jnp.concatenate(
            [jnp.zeros((labels.shape[0], pad), labels.dtype), labels], axis=1)
    lp = labels[:, ::stride]
    mp = mask[:, ::stride].astype(jnp.float32)     # labeled probe positions
    logits = model_lib.logits_from_hiddens(mcfg, params, hp)
    emb = grad_source(sources_lib.GradSourceInputs(
        logits=logits, labels=lp, hiddens=hp, mcfg=mcfg, params=params,
        batch=batch, mask=mp))
    emb = constrain(emb, ("act_batch", None))      # (K, E) f32
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, lp[..., None], axis=-1)[..., 0]
    # masked mean: frontends that prepend unlabeled patch/frame positions
    # (vlm) must not let fake label-0 CE at those positions swamp the score
    scores = jnp.sum(nll * mp, axis=-1) / \
        jnp.maximum(jnp.sum(mp, axis=-1), 1.0)     # (K,) probe CE per example
    # the K×R feature/gradient matrices are tiny — replicate for MaxVol
    pooled = jnp.sum(h.astype(jnp.float32) * mask[..., None], axis=1) / \
        jnp.maximum(jnp.sum(mask, axis=1), 1.0)[:, None]
    V = extractor(pooled, gcfg.r_max)
    G = emb.T                                      # (d=E, K)
    g_bar = jnp.mean(emb, axis=0)
    return V, G, g_bar, scores


def make_selection_refresh(mcfg, tcfg: TrainConfig):
    """``(params, batch, carry, step) → (SelectionState, carry')``: the
    selection forward alone — features + grad embeddings + the registry
    sampler's decision, with the sampler's cross-step carry threaded
    through (Sampler-v2; ``{}`` in/out for stateless strategies).

    ``graft_train_step`` inlines this under its refresh ``lax.cond``; the
    ``OverlappedSelector`` (``repro.selection.overlap``) jits it as its OWN
    dispatch so the refresh pipelines against the train-step stream instead
    of serializing inside it.
    """
    smp = sampler_registry.get_sampler(tcfg.sampler)
    gcfg = tcfg.graft

    def refresh(params, batch, carry, step):
        with jax.named_scope(tracing.SELECT_FORWARD):
            V, G, g_bar, scores = selection_inputs(mcfg, tcfg, params, batch)
        with jax.named_scope(tracing.SELECT_DECIDE):
            key = selection_base.default_select_key(step)
            return smp.select(gcfg, selection_base.SelectionInputs(
                V, G, g_bar, scores, key), carry, step)

    return refresh


def _take_batch(batch, pivots: jax.Array, k_global: int):
    def take(x):
        if hasattr(x, "shape") and x.ndim >= 1 and x.shape[0] == k_global:
            sub = jnp.take(x, pivots, axis=0)
            return constrain(sub, ("act_batch",) + (None,) * (sub.ndim - 1))
        return x
    return jax.tree_util.tree_map(take, batch)


def _state_carry(tcfg: TrainConfig, state):
    """The sampler carry held in the train state; ``{}`` for legacy state
    dicts built before the v2 protocol (their structure is preserved — the
    step functions only store a carry back when the key exists)."""
    if "sampler_carry" in state:
        return state["sampler_carry"]
    smp = sampler_registry.get_sampler(tcfg.sampler)
    if smp.stateful:
        raise ValueError(
            f"sampler '{smp.name}' is stateful but the train state has no "
            f"'sampler_carry' — build the state with init_train_state")
    return selection_base.EMPTY_CARRY


# ---------------------------------------------------------------------------
# train steps
# ---------------------------------------------------------------------------

def baseline_train_step(mcfg, tcfg: TrainConfig, state, batch):
    opt = make_optimizer(tcfg.optimizer)

    if tcfg.microbatches > 1:
        from repro.distributed.accumulate import accumulated_grads
        with jax.named_scope(tracing.TRAIN_GRAD):
            loss_val, grads = accumulated_grads(
                lambda p, mb: model_lib.loss_fn(mcfg, p, mb)[0],
                state["params"], batch, tcfg.microbatches)
        with jax.named_scope(tracing.OPTIMIZER):
            params, opt_state, metrics = opt.apply(
                state["params"], grads, state["opt"], state["step"])
        new_state = dict(state, params=params, opt=opt_state,
                         step=state["step"] + 1)
        return new_state, dict(metrics, loss=loss_val)

    def loss(params):
        return model_lib.loss_fn(mcfg, params, batch)

    with jax.named_scope(tracing.TRAIN_GRAD):
        (loss_val, aux), grads = jax.value_and_grad(loss, has_aux=True)(
            state["params"])
    with jax.named_scope(tracing.OPTIMIZER):
        params, opt_state, metrics = opt.apply(
            state["params"], grads, state["opt"], state["step"])
    new_state = dict(state, params=params, opt=opt_state, step=state["step"] + 1)
    metrics = dict(metrics, loss=loss_val)
    return new_state, metrics


def rank_ladder(rset: Tuple[int, ...], n_rows: int) -> Tuple[int, ...]:
    """The row counts a subset update can run: the candidate ranks below
    ``n_rows`` that the active mesh's ``act_batch`` devices divide, and
    ``n_rows`` itself. Ascending; one entry means no choice."""
    shards = logical_axis_size("act_batch")
    return tuple(sorted({r for r in rset if r < n_rows and r % shards == 0}
                        | {n_rows}))


def bucket_index(weights: jax.Array, ladder: Tuple[int, ...]) -> jax.Array:
    """Index into ``ladder`` of the smallest row count ``b`` for which
    ``weights[b:]`` are all exactly 0 (the last entry, all rows, when no
    smaller one is)."""
    n = weights.shape[0]
    used = jnp.max(jnp.where(weights != 0, jnp.arange(1, n + 1), 0))
    return jnp.sum(jnp.asarray(ladder[:-1]) < used).astype(jnp.int32)


def subset_grads(mcfg, gcfg, params, batch, graft_state, k_global: int):
    """``(loss, grads, rows)``: value and gradient of ``Σ pel·w`` over the
    first ``rows`` selected rows, ``rows`` the smallest entry of
    ``rank_ladder`` past which every weight is exactly 0.

    A row of weight 0 adds exactly 0 to the loss and to every gradient, so
    each row count computes the all-rows value up to the order of its f32
    sums; the all-rows branch is the all-rows computation. One
    ``lax.switch`` branch a row count, each a static shape."""
    pivots, weights = graft_state.pivots, graft_state.weights
    ladder = rank_ladder(gcfg.rset, weights.shape[0])

    def over(rows):
        def branch(p):
            sub_batch = _take_batch(batch, pivots[:rows], k_global)

            def loss(q):
                pel = model_lib.per_example_loss(mcfg, q, sub_batch)
                return jnp.sum(pel * weights[:rows])

            return jax.value_and_grad(loss)(p)
        return branch

    # lax.switch calls the only branch of a one-entry ladder directly
    index = bucket_index(weights, ladder)
    loss_val, grads = jax.lax.switch(index, [over(r) for r in ladder],
                                     params)
    return loss_val, grads, jnp.asarray(ladder, jnp.int32)[index]


def _subset_update(mcfg, gcfg, opt, state, batch, graft_state,
                   k_global: int):
    """Forward and backward over the selected rows of nonzero weight
    (``subset_grads``), then the update. Returns ``(params, opt_state,
    metrics, loss)``; the metrics carry ``subset_rows``, the row count the
    gradient ran over."""
    with jax.named_scope(tracing.TRAIN_GRAD):
        loss_val, grads, rows = subset_grads(
            mcfg, gcfg, state["params"], batch, graft_state, k_global)
    with jax.named_scope(tracing.OPTIMIZER):
        params, opt_state, metrics = opt.apply(
            state["params"], grads, state["opt"], state["step"])
    return params, opt_state, dict(metrics, subset_rows=rows), loss_val


def graft_train_step(mcfg, tcfg: TrainConfig, state, batch):
    """Alg. 1 as one jitted step, sampler-generic: the subset strategy is
    resolved from the registry by ``tcfg.sampler`` (default: GRAFT)."""
    gcfg = tcfg.graft
    refresh = make_selection_refresh(mcfg, tcfg)
    opt = make_optimizer(tcfg.optimizer)
    k_global = jax.tree_util.tree_leaves(batch)[0].shape[0]
    carry0 = _state_carry(tcfg, state)

    def do_select(_):
        return refresh(state["params"], batch, carry0, state["step"])

    if gcfg.refresh_every == 1:
        graft_state, carry = do_select(None)
    else:
        # both branches return (SelectionState, carry): the non-refresh
        # branch keeps the carry untouched, so the reservoir only advances
        # on refresh steps (what makes rollback/resume bit-exact)
        graft_state, carry = jax.lax.cond(
            state["step"] % gcfg.refresh_every == 0,
            do_select,
            lambda _: (state["graft"]._replace(step=state["step"]), carry0),
            None)

    params, opt_state, metrics, loss_val = _subset_update(
        mcfg, gcfg, opt, state, batch, graft_state, k_global)
    new_state = dict(state, params=params, opt=opt_state,
                     step=state["step"] + 1, graft=graft_state)
    if "sampler_carry" in state:
        new_state["sampler_carry"] = carry
    metrics = dict(metrics, loss=loss_val, rank=graft_state.rank,
                   proj_error=graft_state.last_error,
                   alignment=graft_state.alignment)
    return new_state, metrics


def subset_train_step(mcfg, tcfg: TrainConfig, state, batch):
    """Alg. 1 'else' branch: steady-state GRAFT step between refreshes —
    train on the STORED subset, no selection forward. This is the per-step
    cost once the selection is amortized over S (the paper's S = 20–50)."""
    opt = make_optimizer(tcfg.optimizer)
    k_global = jax.tree_util.tree_leaves(batch)[0].shape[0]
    graft_state = state["graft"]
    params, opt_state, metrics, loss_val = _subset_update(
        mcfg, tcfg.graft, opt, state, batch, graft_state, k_global)
    new_state = dict(state, params=params, opt=opt_state,
                     step=state["step"] + 1,
                     graft=graft_state._replace(step=state["step"] + 1))
    return new_state, dict(metrics, loss=loss_val)


def selection_step(mcfg, tcfg: TrainConfig, state, batch):
    """Selection only (features + grad embeddings + MaxVol + rank sweep) —
    isolates the refresh cost for the amortization analysis (§Perf)."""
    refresh = make_selection_refresh(mcfg, tcfg)
    graft_state, carry = refresh(state["params"], batch,
                                 _state_carry(tcfg, state), state["step"])
    new_state = dict(state, graft=graft_state)
    if "sampler_carry" in state:
        new_state["sampler_carry"] = carry
    return new_state, {"rank": graft_state.rank,
                       "proj_error": graft_state.last_error}


def apply_sentinel(tcfg: TrainConfig, state, new_state, metrics):
    """Fused divergence sentinel + skip-update, entirely on device.

    The health word: loss and global grad norm must be finite (the norm is
    a sum of squares over EVERY grad leaf, so one non-finite grad entry
    anywhere poisons it — an all-leaves check for the price of a scalar),
    and — once the loss EMA has ``SENTINEL_WARMUP`` healthy samples — the
    loss must sit within ``spike_z`` EMA standard deviations of the mean.

    Skip-update: on an unhealthy step every updated leaf (params, opt,
    graft) is ``where``-selected back to its input value with only ``step``
    advanced, so a poisoned gradient never touches params. On a healthy
    step the select returns the new values bit-exactly — the sentinel is
    trajectory-neutral (why ``train.sentinel`` is excluded from
    ``config_hash``, like ``graft.overlap``).

    The verdict rides the step's metrics (``healthy``, ``bad_streak``) and
    the ``bad_streak`` counter in the carried health state, so the host
    learns about divergence lazily at its existing drain boundaries — zero
    new syncs on the step path.
    """
    health = state["health"]
    loss = metrics["loss"].astype(jnp.float32)
    finite = jnp.isfinite(loss)
    if "grad_norm" in metrics:
        finite = finite & jnp.isfinite(
            metrics["grad_norm"].astype(jnp.float32))
    mean, var = health["ema_mean"], health["ema_var"]
    if tcfg.spike_z:
        std = jnp.sqrt(jnp.maximum(var, 1e-6))
        warm = health["count"] >= SENTINEL_WARMUP
        spike = warm & (jnp.abs(loss - mean) > tcfg.spike_z * std)
        healthy = finite & ~spike
    else:
        healthy = finite
    # EMA advances on healthy steps only: a poisoned loss must never drag
    # the reference it is judged against (the where's untaken branch may
    # hold NaN — select drops it, nothing differentiates through this)
    decay = jnp.float32(0.9)
    dev = loss - mean
    new_health = {
        "ema_mean": jnp.where(healthy, decay * mean + (1 - decay) * loss,
                              mean),
        "ema_var": jnp.where(healthy, decay * var + (1 - decay) * dev * dev,
                             var),
        "count": jnp.where(healthy, health["count"] + 1, health["count"]),
        "bad_streak": jnp.where(healthy, jnp.int32(0),
                                health["bad_streak"] + 1),
    }
    fallback = dict(state, step=state["step"] + 1)
    if "graft" in state:
        fallback["graft"] = state["graft"]._replace(step=state["step"] + 1)
    fallback.pop("health")
    candidate = {k: v for k, v in new_state.items() if k != "health"}
    selected = jax.tree_util.tree_map(
        lambda n, f: jnp.where(healthy, n, f), candidate, fallback)
    selected["health"] = new_health
    # the step's own metrics keep their true (possibly non-finite) values —
    # telemetry should show WHAT was skipped, not hide it
    return selected, dict(metrics, healthy=healthy.astype(jnp.float32),
                          bad_streak=new_health["bad_streak"])


def make_train_step(mcfg, tcfg: TrainConfig, kind: Optional[str] = None):
    step = {None: graft_train_step if tcfg.use_graft else baseline_train_step,
            "graft": graft_train_step, "baseline": baseline_train_step,
            "subset": subset_train_step, "select": selection_step}[kind]
    use_sentinel = tcfg.sentinel and kind != "select"

    def fn(state, batch):
        new_state, metrics = step(mcfg, tcfg, state, batch)
        if use_sentinel and "health" in state:
            with jax.named_scope(tracing.SENTINEL):
                new_state, metrics = apply_sentinel(tcfg, state, new_state,
                                                    metrics)
        return new_state, metrics
    return fn


def detach_metrics(metrics):
    """Fresh buffers for metric scalars (tiny async copies, no sync).

    A metric that PASSES THROUGH a donating jit untouched (e.g. the graft
    ``rank`` between refreshes) comes back aliased to the donated input
    buffer, which the NEXT step's donation recycles — a deferred read
    (``MetricsFuture`` drained at a flush boundary) would then hit a
    deleted array. The copies are enqueued before that next dispatch, so
    PjRt orders them ahead of the buffer reuse."""
    return {k: jnp.copy(v) if isinstance(v, jax.Array) else v
            for k, v in metrics.items()}


def make_run_step(mcfg, tcfg: TrainConfig, donate: bool = True):
    """Uniform host-callable ``(state, batch, step) → (state, metrics)`` —
    the one place that owns the jit/donation wiring for the training loop.

    Resolves to the :class:`~repro.selection.overlap.OverlappedSelector`
    when ``graft.overlap`` is set (refresh and subset-train as separate,
    pipelined dispatches), else a single donated jit of the sequential
    step. Either way the returned metrics are DEVICE scalars of a
    still-in-flight dispatch: callers that want the host to run ahead must
    not ``float()`` them per step (the ``Trainer`` wraps them in a
    ``MetricsFuture`` and drains at flush boundaries). Side computations
    on ``state['params']`` (eval, refresh) must follow the
    ``SideStream`` discipline: enqueue before the next call donates.
    """
    if tcfg.use_graft and tcfg.graft.overlap:
        from repro.selection.overlap import OverlappedSelector
        inner = OverlappedSelector(mcfg, tcfg, donate=donate).step
    else:
        jitted = jax.jit(make_train_step(mcfg, tcfg),
                         donate_argnums=(0,) if donate else ())

        def inner(state, batch, step):
            return jitted(state, batch)

    def run_step(state, batch, step):
        state, metrics = inner(state, batch, step)
        return state, detach_metrics(metrics)

    return run_step


# ---------------------------------------------------------------------------
# serve steps
# ---------------------------------------------------------------------------

def prefill_step(mcfg, params, batch, max_seq: int):
    return decode_lib.prefill(mcfg, params, batch, max_seq)


def decode_step(mcfg, params, cache, tokens):
    return decode_lib.decode_step(mcfg, params, cache, tokens)
