"""Per-device selection overhead: the shard_map data-parallel GRAFT path vs
the single-device reference and the vmapped multi-batch path.

The interesting number is the ratio ``sharded / single``: each shard's local
work is one K_local-row Fast MaxVol (identical to the single-device call),
so anything above 1.0 is the price of the psum'd rank statistics. Run
standalone (``python benchmarks/bench_sharded_selection.py`` or
``run.py --suite sharded``) this module forces 8 host CPU devices; when jax
is already initialized (``--suite all``) it degrades to the real device
count — on one device the mesh is (1, 1) and the ratio isolates the
shard_map machinery itself.
"""
from __future__ import annotations

import os
import sys
from typing import List

_FORCE_DEVICES = 8
if ("jax" not in sys.modules
        and "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", "")):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               f" --xla_force_host_platform_device_count={_FORCE_DEVICES}").strip()

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import csv_row, time_call
from repro.distributed import sharding as sh
from repro.launch.mesh import make_mesh
from repro.selection import GraftConfig, engine


def run() -> List[str]:
    rng = np.random.default_rng(0)
    rows: List[str] = []
    n = len(jax.devices())
    K_local, d, R = 128, 512, 32
    cfg = GraftConfig(rset=(8, 16, 32), eps=0.25)

    def batch(k):
        V = jnp.asarray(rng.normal(size=(k, R)).astype(np.float32))
        G = jnp.asarray(rng.normal(size=(d, k)).astype(np.float32))
        return V, G, jnp.mean(G, axis=1)

    # single-device reference: one K_local-row selection
    V1, G1, gb1 = batch(K_local)
    t_single = time_call(
        lambda v, g, gb: engine.select_batch(cfg, "graft", v, g, gb),
        V1, G1, gb1)
    rows.append(csv_row(f"select_single_K{K_local}", t_single, "reference"))

    # vmapped multi-batch: n microbatches under one jit on one device
    Vs, Gs, gbs = (jnp.stack(x) for x in zip(*(batch(K_local) for _ in range(n))))
    t_vmap = time_call(
        lambda v, g, gb: engine.select_multi_batch(cfg, "graft", v, g, gb),
        Vs, Gs, gbs)
    rows.append(csv_row(f"select_vmap_B{n}_K{K_local}", t_vmap,
                        f"per_batch_us={t_vmap / n:.1f}"))

    # shard_map data-parallel: n shards of K_local rows each
    mesh = make_mesh((n, 1), ("data", "model"))
    Vg, Gg, _ = batch(n * K_local)
    Vg = jax.device_put(Vg, sh.named_sharding(mesh, ("act_batch", None)))
    Gg = jax.device_put(Gg, sh.named_sharding(mesh, (None, "act_batch")))
    selector = engine.make_sharded_selector(cfg, mesh)
    t_shard = time_call(lambda v, g: selector(v, g, jnp.int32(0)), Vg, Gg)
    rows.append(csv_row(
        f"select_sharded_n{n}_Kglobal{n * K_local}", t_shard,
        f"per_device_overhead={t_shard / max(t_single, 1e-9):.2f}x"))
    return rows


if __name__ == "__main__":
    for r in run():
        print(r)
