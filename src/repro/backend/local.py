"""The single-process backend — today's semantics, bit-identical.

The mesh is ``launch.mesh.make_host_mesh()`` (all local devices, 1-D
``data`` axis); ``shard_batch`` lays each batch's leading dim out along
that axis, so on N devices each holds B/N rows (on one device it is a plain
transfer); ``replicate`` lays the state out replicated over the mesh, and
there is no distributed runtime to bring up. The bit-identity regression test
(``tests/test_backend.py``) pins the one-device loop against a hand-rolled
pre-backend loop — trajectory and graft pivots both.
"""
from __future__ import annotations

from typing import Any, Dict

from repro.backend import base
from repro.distributed.pipeline import assemble_global_batch
from repro.launch.mesh import make_host_mesh


class LocalBackend(base.Backend):
    name = "local"

    def _build_mesh(self):
        return make_host_mesh()

    def shard_batch(self, batch: Dict[str, Any]) -> Dict[str, Any]:
        return assemble_global_batch(self.mesh(), batch)

    def replicate(self, tree):
        """The state replicated over the mesh, the layout the train step
        returns it in: the first step then compiles the program every
        later step runs, where a state left on one device would make the
        second step compile again. On one device no copy is made."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.device_put(tree, NamedSharding(self.mesh(), P()))


def _build(cfg: base.LocalBackendConfig) -> LocalBackend:
    return LocalBackend(cfg)


LOCAL = base.register_backend(base.BackendEntry(
    "local", base.LocalBackendConfig, _build))
