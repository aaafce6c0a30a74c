"""Cross-process batch staging + experimental GPipe pipeline parallelism.

Two collaborators of the execution-backend subsystem (``repro.backend``):

  * **Batch staging** — ``assemble_global_batch`` turns each process's
    host-local batch shard into global arrays laid out along the mesh's
    data axis (the ``MultiProcessBackend.shard_batch`` primitive), and
    :class:`BatchStager` decouples the host→device transfer from the
    dispatch loop with optional lookahead, while keeping the data source's
    one-integer resumable state accounted to the batch actually CONSUMED
    (what checkpoints must record — a prefetched-but-unconsumed batch must
    replay after resume).
  * **GPipe executor** — DESIGN.md §5 maps the 2-pod production mesh's pod
    axis to data parallelism (batch 256 ≥ 512 chips makes DP strictly
    better than a 2-stage pipeline's bubble). ``pipeline_forward`` exists
    for >2-pod deployments where DP batch runs out: a shard_map+ppermute
    GPipe executor with the standard (S + M − 1)/M bubble.
"""
from __future__ import annotations

import collections
import concurrent.futures
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from repro.launch import tracing


# ---------------------------------------------------------------------------
# cross-process batch staging (backend collaborator)
# ---------------------------------------------------------------------------

def assemble_global_batch(mesh: Mesh, batch: Dict[str, np.ndarray],
                          axis: str = "data") -> Dict[str, Any]:
    """Each process's host-local batch shard → global device arrays.

    The leading (batch) dimension of every array is laid out along the
    mesh's ``axis``; each process contributes only its own shard
    (``jax.make_array_from_process_local_data`` stitches the global view).
    Single-process meshes degrade to a plain sharded device_put."""
    out = {}
    for k, v in batch.items():
        v = np.asarray(v)
        spec = P(axis, *([None] * (v.ndim - 1)))
        out[k] = jax.make_array_from_process_local_data(
            NamedSharding(mesh, spec), v)
    return out


class BatchStager:
    """Stages host batches onto devices ahead of the dispatch loop.

    ``depth=0`` pulls + stages inline on ``next()`` — the order of
    operations is exactly the pre-stager loop (bit-identical). ``depth>=1``
    keeps that many batches pulled + staged ahead on a worker thread, so
    the host transfer of batch N+1 overlaps step N's dispatch.

    State accounting: ``consumed_state()`` is the data source's
    ``state_dict`` as of the last batch handed to the caller — lookahead
    pulls advance the live source, but a checkpoint written mid-stream must
    replay the staged-yet-unconsumed batches after resume. ``reset()``
    drops the lookahead after an external rewind (restore/rollback
    ``load_state_dict``) so stale staged batches never reach the loop.
    """

    def __init__(self, source, stage: Callable[[Dict[str, np.ndarray]], Any],
                 depth: int = 0):
        self.source = source
        self.stage = stage
        self.depth = depth
        self._it = iter(source)
        self._queue: collections.deque = collections.deque()
        self._consumed = source.state_dict()
        self._pool = (concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="batch-stager")
            if depth > 0 else None)

    def _submit(self) -> None:
        host = next(self._it)
        state_after = self.source.state_dict()
        self._queue.append((self._pool.submit(self.stage, host), state_after))

    def __iter__(self):
        return self

    def __next__(self):
        if self.depth == 0:
            host = next(self._it)
            self._consumed = self.source.state_dict()
            # a host span on the caller's thread only: with lookahead the
            # worker stages, and the caller's wait is its own span
            with tracing.span(tracing.STAGE):
                return self.stage(host)
        while len(self._queue) < self.depth + 1:
            self._submit()
        fut, state_after = self._queue.popleft()
        self._consumed = state_after
        return fut.result()

    def consumed_state(self) -> Dict[str, int]:
        return dict(self._consumed)

    def reset(self) -> None:
        """Drop the lookahead after the source was rewound externally."""
        for fut, _ in self._queue:
            fut.cancel()
        self._queue.clear()
        self._consumed = self.source.state_dict()

    def close(self) -> None:
        self.reset()
        if self._pool is not None:
            self._pool.shutdown(wait=False)


def pipeline_forward(layer_fn: Callable, stage_params, x_micro: jax.Array,
                     mesh: Mesh, axis: str = "pod") -> jax.Array:
    """Run M microbatches through S pipeline stages.

    layer_fn(params, x) -> x          one stage's computation
    stage_params: pytree with leading (S,) stage axis, sharded over ``axis``
    x_micro: (M, mb, ...) microbatches (replicated; stage 0 consumes them)
    Returns (M, mb, ...) outputs as produced by the last stage.
    """
    S = mesh.shape[axis]
    M = x_micro.shape[0]
    ticks = S + M - 1

    def per_stage(params_s, x_all):
        # params_s: this stage's params (leading axis stripped by shard_map)
        stage = jax.lax.axis_index(axis)
        p_local = jax.tree_util.tree_map(lambda a: a[0], params_s)
        # carries are device-varying (they hold per-stage state) — mark them
        buf = jax.lax.pcast(jnp.zeros_like(x_all[0]), axis,
                            to="varying")                 # (mb, …)
        outs = jax.lax.pcast(jnp.zeros_like(x_all), axis,
                             to="varying")                # (M, mb, …)

        def tick(t, carry):
            buf, outs = carry
            # stage 0 ingests microbatch t (if any remain)
            feed = x_all[jnp.clip(t, 0, M - 1)]
            buf = jnp.where(stage == 0,
                            jnp.where(t < M, feed, jnp.zeros_like(feed)), buf)
            buf = layer_fn(p_local, buf)
            # last stage emits microbatch index t - (S - 1); masked update
            # (a lax.cond would mix varying/invariant manual axes)
            out_idx = t - (S - 1)
            emit = jnp.logical_and(stage == S - 1, out_idx >= 0)
            idx = jnp.clip(out_idx, 0, M - 1)
            outs = outs.at[idx].set(jnp.where(emit, buf, outs[idx]))
            # rotate activations forward one stage
            buf = jax.lax.ppermute(
                buf, axis, [(i, (i + 1) % S) for i in range(S)])
            return buf, outs

        _, outs = jax.lax.fori_loop(0, ticks, tick, (buf, outs))
        # only the last stage holds real outputs; psum broadcasts them so
        # every shard returns the identical (replicated) result
        outs = jnp.where(stage == S - 1, outs, jnp.zeros_like(outs))
        return jax.lax.psum(outs, axis)

    specs_params = jax.tree_util.tree_map(lambda _: P(axis), stage_params)
    fn = shard_map(per_stage, mesh=mesh,
                   in_specs=(specs_params, P()),
                   out_specs=P())
    return fn(stage_params, x_micro)


def pipeline_bubble_fraction(num_stages: int, num_micro: int) -> float:
    """Idle fraction of the GPipe schedule: (S−1)/(S+M−1)."""
    return (num_stages - 1) / (num_stages + num_micro - 1)
