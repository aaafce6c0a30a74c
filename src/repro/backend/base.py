"""Pluggable execution backends — how a ``Trainer`` touches devices.

A :class:`Backend` owns every process/device decision the training loop
used to hardwire: distributed runtime bring-up, mesh construction, host →
device batch staging, cross-process reduction, data-pipeline host sharding,
and the topology stamp that makes checkpoints elastically restorable.
The ``Trainer`` itself stays a pure step-dispatch loop — it asks the
backend, never ``jax`` directly (machine-enforced by lint rule LN004:
``jax.distributed.*`` / mesh construction / ``jax.process_index`` are
forbidden outside ``repro/backend/`` + ``launch/mesh.py``).

Two implementations ship:

  * :class:`~repro.backend.local.LocalBackend` — single process, all local
    devices as a 1-D ``data`` mesh. Bit-identical to the pre-backend
    trainer (its ``shard_batch`` is exactly ``jnp.asarray``).
  * :class:`~repro.backend.multiprocess.MultiProcessBackend` —
    ``jax.distributed.initialize`` over every participating process (gloo
    collectives on CPU), a global data mesh, per-process ``DataSource``
    shards keyed on ``process_index``, and global-array batch assembly.

The registry mirrors ``repro.data.sources``: each backend pairs a frozen
config dataclass (the tagged, hash-neutral ``backend`` section of an
``ExperimentConfig``) with a builder. ``--backend.kind=multiprocess`` swaps
the section; per-backend fields override on top.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

from repro.registry import Registry


class AllReduceSpec(NamedTuple):
    """How cross-process reductions run on this backend: the mesh axis they
    travel over and whether the int8 error-feedback compression
    (``repro.distributed.compression``) wraps them."""
    axis: str
    num_shards: int
    compressed: bool


# ---------------------------------------------------------------------------
# configs (the tagged ``backend`` section)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LocalBackendConfig:
    """Single-process execution on whatever devices exist (the default)."""


@dataclasses.dataclass(frozen=True)
class MultiProcessBackendConfig:
    """One process per host, joined via ``jax.distributed.initialize``.

    ``num_processes``/``process_id`` of 0/-1 mean "read the
    ``JAX_NUM_PROCESSES`` / ``JAX_PROCESS_ID`` environment" — the launch
    recipe sets them per worker so one config file serves every rank.
    """
    coordinator: str = "127.0.0.1:12321"
    num_processes: int = 0              # 0 = $JAX_NUM_PROCESSES
    process_id: int = -1                # -1 = $JAX_PROCESS_ID
    compress_reduce: bool = False       # int8 error-feedback on all_reduce
    prefetch: int = 0                   # BatchStager lookahead depth


# ---------------------------------------------------------------------------
# protocol
# ---------------------------------------------------------------------------

class Backend:
    """Execution-strategy protocol. Subclasses override the device-touching
    primitives; everything here is the single-process default so a new
    backend only implements what it changes."""

    name: str = "abstract"

    def __init__(self, config: Any):
        self.config = config
        self._mesh = None

    # -------------------------------- lifecycle -----------------------------
    def setup(self) -> None:
        """Bring up the distributed runtime (before any device query)."""

    def teardown(self) -> None:
        """Release the distributed runtime (idempotent)."""

    # -------------------------------- topology ------------------------------
    @property
    def process_index(self) -> int:
        return 0

    @property
    def process_count(self) -> int:
        return 1

    def device_count(self) -> int:
        import jax
        return len(jax.devices())       # lint: allow — backend owns devices

    def local_device_count(self) -> int:
        return self.device_count()

    @property
    def is_primary(self) -> bool:
        """The one process that writes checkpoints/telemetry files."""
        return self.process_index == 0

    def data_shard(self) -> Tuple[int, int]:
        """``(num_hosts, host_index)`` for the data pipeline — which slice
        of every global batch this process generates."""
        return self.process_count, self.process_index

    def topology(self) -> Dict[str, Any]:
        """The manifest stamp that makes checkpoints elastic: enough to
        detect a mismatched restore and to decide a reshard is safe."""
        return {"process_count": self.process_count,
                "device_count": self.device_count(),
                "shard_layout": "replicated"}

    # -------------------------------- devices -------------------------------
    def mesh(self):
        """The backend's mesh (cached — construction queries devices)."""
        if self._mesh is None:
            self._mesh = self._build_mesh()
        return self._mesh

    def _build_mesh(self):
        raise NotImplementedError

    def shard_batch(self, batch: Dict[str, Any]) -> Dict[str, Any]:
        """Host-local numpy batch → device arrays the step function can
        consume (global arrays on multi-process backends)."""
        raise NotImplementedError

    def device_put(self, arr):
        """One host array → a device array replicated the way this backend
        replicates train state (the elastic-restore leaf primitive)."""
        import jax
        return jax.device_put(arr)

    def replicate(self, tree):
        """Train-state tree → this backend's resident form (identity
        unless a backend lays it out on its mesh)."""
        return tree

    def to_host(self, tree):
        """Device tree → host numpy (the checkpoint gather). Must work for
        every array the backend produces, addressable or not."""
        import jax
        import numpy as np
        return jax.tree_util.tree_map(np.asarray, tree)

    # ------------------------------ collectives -----------------------------
    def all_reduce_spec(self) -> AllReduceSpec:
        return AllReduceSpec(axis="data", num_shards=self.process_count,
                             compressed=False)

    def all_reduce(self, tree):
        """Cross-process mean of host-side values (identity when single
        process). Multi-process backends route this over the global mesh —
        optionally through the int8 error-feedback compressed reduce."""
        return tree

    def check_consistent(self, tag: str) -> None:
        """Fail loudly when the participating processes disagree on
        ``tag`` (config-hash divergence = silent corruption later)."""

    # -------------------------------- staging -------------------------------
    @property
    def staging_depth(self) -> int:
        """BatchStager lookahead (0 = stage inline, bit-identical order)."""
        return 0


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BackendEntry:
    name: str
    config_cls: type
    build: Callable[[Any], Backend]


_BACKENDS: Registry = Registry("execution backend")


def register_backend(entry: BackendEntry, *,
                     overwrite: bool = False) -> BackendEntry:
    for other in _BACKENDS.values():
        if other.name != entry.name and other.config_cls is entry.config_cls:
            raise ValueError(
                f"config class {entry.config_cls.__name__} already tags "
                f"backend '{other.name}' — one config class per backend")
    return _BACKENDS.register(entry.name, entry, overwrite=overwrite)


def get_backend(name: str) -> BackendEntry:
    return _BACKENDS.get(name)


def available_backends() -> Tuple[str, ...]:
    return _BACKENDS.available()


def entry_for_config(bcfg: Any) -> BackendEntry:
    for entry in _BACKENDS.values():
        if type(bcfg) is entry.config_cls:
            return entry
    raise KeyError(f"no registered backend owns config type "
                   f"{type(bcfg).__name__} (available: "
                   f"{available_backends()})")


def backend_name_of(bcfg: Any) -> str:
    return entry_for_config(bcfg).name


def resolve(bcfg: Optional[Any]) -> Backend:
    """Backend-config section → live ``Backend`` (``None`` = local)."""
    if bcfg is None:
        bcfg = LocalBackendConfig()
    if isinstance(bcfg, Backend):       # tests hand a pre-built backend in
        return bcfg
    return entry_for_config(bcfg).build(bcfg)
