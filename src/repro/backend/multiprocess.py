"""Multi-process execution: one JAX process per host, one global mesh.

Bring-up recipe (the part that is easy to get wrong on CPU): the gloo
collectives implementation must be selected BEFORE
``jax.distributed.initialize`` — the default CPU backend cannot run
multi-process computations at all. After initialize, ``jax.devices()``
returns the GLOBAL device list and the mesh spans every process.

Data flows per-process: each rank builds a ``DataSource`` shard keyed on
``process_index`` (the per-global-example seeding in ``repro.data`` makes
the union byte-identical to a single-host run), and ``shard_batch``
stitches the host-local shards into global arrays laid out along the
``data`` axis via ``assemble_global_batch``. Train state is replicated
everywhere; carry/rank stats reduce over the same mesh axis the step
function already psums over, optionally through the int8 error-feedback
compressed reduce.
"""
from __future__ import annotations

import os
from typing import Any, Dict

import numpy as np

from repro.backend import base


def _env_int(name: str, default: int) -> int:
    raw = os.environ.get(name, "")
    return int(raw) if raw else default


class MultiProcessBackend(base.Backend):
    name = "multiprocess"

    def __init__(self, config: base.MultiProcessBackendConfig):
        super().__init__(config)
        self._initialized = False
        # error-feedback accumulators for the compressed reduce, keyed by
        # leaf position (reset when the reduced tree changes shape)
        self._ef_errors = None

    # -------------------------------- lifecycle -----------------------------
    def setup(self) -> None:
        import jax

        num = self.config.num_processes or _env_int("JAX_NUM_PROCESSES", 0)
        pid = self.config.process_id
        if pid < 0:
            pid = _env_int("JAX_PROCESS_ID", -1)
        if num <= 0 or pid < 0:
            raise ValueError(
                "multiprocess backend needs num_processes>=1 and process_id "
                ">=0 — set backend.num_processes/backend.process_id or the "
                "JAX_NUM_PROCESSES/JAX_PROCESS_ID environment variables")
        # the default CPU collectives cannot run multi-process programs;
        # must be set BEFORE initialize — and nothing here may query devices
        # first (jax.devices()/default_backend() would freeze a
        # single-process runtime before the fleet forms)
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(
            coordinator_address=self.config.coordinator,
            num_processes=num,
            process_id=pid)
        self._initialized = True

    def teardown(self) -> None:
        if not self._initialized:
            return
        import jax
        try:
            jax.distributed.shutdown()
        finally:
            self._initialized = False

    # -------------------------------- topology ------------------------------
    @property
    def process_index(self) -> int:
        import jax
        return jax.process_index()

    @property
    def process_count(self) -> int:
        import jax
        return jax.process_count()

    def local_device_count(self) -> int:
        import jax
        return jax.local_device_count()

    # -------------------------------- devices -------------------------------
    def _build_mesh(self):
        from repro.launch.mesh import make_mesh
        return make_mesh((self.device_count(),), ("data",))

    def shard_batch(self, batch: Dict[str, Any]) -> Dict[str, Any]:
        from repro.distributed.pipeline import assemble_global_batch
        return assemble_global_batch(self.mesh(), batch, axis="data")

    def device_put(self, arr):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.make_array_from_process_local_data(
            NamedSharding(self.mesh(), P()), np.asarray(arr))

    def replicate(self, tree):
        import jax
        return jax.tree_util.tree_map(self.device_put, tree)

    def to_host(self, tree):
        import jax
        from jax.experimental import multihost_utils

        def gather(leaf):
            if hasattr(leaf, "is_fully_addressable") and \
                    not leaf.is_fully_addressable:
                shards = getattr(leaf, "addressable_shards", None)
                if shards and tuple(shards[0].data.shape) == \
                        tuple(leaf.shape):
                    # replicated: this process's shard IS the global array
                    return np.asarray(shards[0].data)
                return np.asarray(
                    multihost_utils.process_allgather(leaf, tiled=True))
            return np.asarray(leaf)

        return jax.tree_util.tree_map(gather, tree)

    # ------------------------------ collectives -----------------------------
    def all_reduce_spec(self) -> base.AllReduceSpec:
        return base.AllReduceSpec(axis="data",
                                  num_shards=self.process_count,
                                  compressed=self.config.compress_reduce)

    def all_reduce(self, tree):
        """Cross-process mean of host-side scalars/arrays (metrics, rank
        stats). Routed through the global mesh so every rank agrees;
        ``compress_reduce`` swaps the f32 psum for the int8 error-feedback
        reduce from ``repro.distributed.compression`` (per-call rounding
        carried in host-side accumulators, cancels over calls)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from jax import shard_map

        mesh = self.mesh()
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        arrs = [self.device_put(np.asarray(l, dtype=np.float32))
                for l in leaves]
        spec = tuple(P() for _ in arrs)

        if self.config.compress_reduce:
            from repro.distributed.compression import ef_compressed_psum
            nshards = mesh.shape["data"]
            if (self._ef_errors is None
                    or len(self._ef_errors) != len(arrs)
                    or any(e.shape != np.shape(l)
                           for e, l in zip(self._ef_errors, leaves))):
                self._ef_errors = [
                    np.zeros(np.shape(l), np.float32) for l in leaves]
            errs = [self.device_put(e) for e in self._ef_errors]

            def reduce_all(*args):
                xs, es = args[:len(arrs)], args[len(arrs):]
                outs = [ef_compressed_psum(x, e, "data", nshards)
                        for x, e in zip(xs, es)]
                return tuple(o[0] for o in outs) + tuple(o[1] for o in outs)

            fn = shard_map(reduce_all, mesh=mesh,
                           in_specs=spec + spec, out_specs=spec + spec)
            out = fn(*(tuple(arrs) + tuple(errs)))
            reduced, new_errs = out[:len(arrs)], out[len(arrs):]
            self._ef_errors = [np.asarray(self.to_host(e)).reshape(
                np.shape(l)) for e, l in zip(new_errs, leaves)]
        else:
            def mean_all(*xs):
                # values are replicated — psum over the axis then
                # renormalize by shard count gives the cross-process mean
                # of per-process values
                n = jax.lax.psum(jnp.ones(()), "data")
                return tuple(jax.lax.psum(x, "data") / n for x in xs)

            fn = shard_map(mean_all, mesh=mesh, in_specs=spec,
                           out_specs=spec)
            reduced = fn(*arrs)

        host = [np.asarray(self.to_host(o)) for o in reduced]
        return jax.tree_util.tree_unflatten(
            treedef, [h.reshape(np.shape(l)) for h, l in zip(host, leaves)])

    def check_consistent(self, tag: str) -> None:
        """All processes must agree on ``tag`` (e.g. the config hash) —
        divergence now is silent state corruption later."""
        import hashlib
        from jax.experimental import multihost_utils
        # NOT Python hash() — that's salted per process (PYTHONHASHSEED)
        word = int.from_bytes(hashlib.sha256(tag.encode()).digest()[:8],
                              "big", signed=True)
        digest = np.asarray([word], dtype=np.int64)
        gathered = np.asarray(multihost_utils.process_allgather(digest))
        if not (gathered == gathered.reshape(-1)[0]).all():
            raise RuntimeError(
                f"processes disagree on '{tag[:32]}…' — every rank must "
                "launch with an identical experiment config")

    # -------------------------------- staging -------------------------------
    @property
    def staging_depth(self) -> int:
        return self.config.prefetch


def _build(cfg: base.MultiProcessBackendConfig) -> MultiProcessBackend:
    return MultiProcessBackend(cfg)


MULTIPROCESS = base.register_backend(base.BackendEntry(
    "multiprocess", base.MultiProcessBackendConfig, _build))
