"""Chip smoke test: the GRAFT trainer on a TPU at minicpm-2b width.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the data-parallel path on four chips

One chip, through ``Trainer(cfg).fit()`` (the path ``python -m repro.api``
takes), with random weights from a seed and ``synthetic_lm`` traffic:

  * phase A — today's defaults: the jnp selection chain and
    ``attn_backend=auto``. Its compiled step must hold the flash kernels.
  * dense check — the first step's loss recomputed with
    ``attn_backend=dense`` from the same initial weights, batch rows and
    subset weights: it must agree with phase A's within ``DENSE_RTOL``.
  * phase B — ``graft.use_pallas=true``: the fused selection kernel. Its
    pivots at the first refresh must equal phase A's.

``--chips 4`` runs only the four-chip path and what it is compared with:
the same trainer over a 4-chip ``data`` mesh (each chip must hold B/4
rows of the staged batch) against the same steps on one of those chips,
and ``engine.select_sharded`` over the 4 chips against one device.

Every phase must pass; any failure raises. The last line of standard
output is one JSON object naming the device. There is no CPU fallback:
without a TPU the script exits non-zero before running anything.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))
# libtpu writes its logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")

# the program itself: without the checkout these imports fail, and the
# script exits non-zero before printing anything
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.api import ExperimentConfig, Trainer  # noqa: E402
from repro.api.callbacks import Callback  # noqa: E402
from repro.api.cli import configure_compile_cache  # noqa: E402
from repro.backend.base import LocalBackendConfig  # noqa: E402
from repro.backend.local import LocalBackend  # noqa: E402
from repro.distributed import sharding as sh  # noqa: E402
from repro.kernels.ops import compiled_kernel_names  # noqa: E402
from repro.launch import steps as steps_lib  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import model as model_lib  # noqa: E402
from repro.selection import GraftConfig, engine  # noqa: E402


@dataclasses.dataclass(frozen=True)
class Smoke:
    """The run's shape. Widths are minicpm-2b's published ones; depth is
    cut from 40 layers so that params, AdamW state and activations fit one
    16 GB chip (the step compiles to 13.5 GiB at 8 layers)."""
    arch: str = "minicpm-2b"
    layers: int = 8
    batch: int = 16
    seq: int = 2048
    steps: int = 5
    rset: tuple = (2, 4, 8)             # r_max = batch / 2
    loss_chunk: int = 256               # CE over 256-token chunks
    seed: int = 0


# bf16 weights and activations: flash and dense attention round their
# outputs differently, which moves a loss averaged over ~16k tokens by
# about 1e-6 relative (2.8e-6 measured on a v5e); a wrong mask, scale or
# softmax moves it by orders of magnitude more
DENSE_RTOL = 1e-4
# cross-device-count losses reassociate the batch reductions (README,
# "Execution backends")
MESH_RTOL = 3e-3


def experiment(s: Smoke, **graft) -> ExperimentConfig:
    model = {"num_layers": s.layers, "loss_chunk": s.loss_chunk}
    overrides = [
        f"model.arch={s.arch}", "model.smoke=false",
        f"model.overrides={json.dumps(model)}",
        f"train.steps={s.steps}", f"train.batch={s.batch}",
        f"train.seq={s.seq}", f"train.seed={s.seed}", "train.log_every=0",
        "graft.refresh_every=1", f"graft.rset={json.dumps(list(s.rset))}",
    ] + [f"graft.{k}={json.dumps(v)}" for k, v in graft.items()]
    return ExperimentConfig().apply_overrides(overrides).finalized()


def log(msg: str) -> None:
    print(msg, flush=True)


class FirstRefresh(Callback):
    """Copies the first refresh's subset to the host and stamps the first
    step's dispatch (trace + compile + enqueue)."""

    def on_train_start(self, trainer):
        self.t_start = time.time()

    def on_step_end(self, trainer, step, metrics):
        if step == trainer.start_step:
            self.first_dispatch_s = time.time() - self.t_start
            graft = trainer.state["graft"]
            self.pivots = np.asarray(graft.pivots)
            self.weights = np.asarray(graft.weights)


class MeshBackend(LocalBackend):
    """The local backend over a chosen device list; records the first
    batch it stages."""

    def __init__(self, devices):
        super().__init__(LocalBackendConfig())
        self.devices = devices
        self.staged = None

    def _build_mesh(self):
        return make_mesh((len(self.devices),), ("data",),
                         devices=self.devices)

    def shard_batch(self, batch):
        out = super().shard_batch(batch)
        if self.staged is None:
            self.staged = out
        return out


def run_trainer(name: str, cfg, backend=None):
    """``Trainer(cfg).fit()`` with the first refresh recorded. Returns
    (trainer, report, first-refresh record); checks the losses."""
    rec = FirstRefresh()
    trainer = Trainer(cfg, callbacks=[rec], backend=backend)
    report = trainer.fit()
    losses = [row["loss"] for row in report["history"]]
    if len(losses) != cfg.train.steps or not np.all(np.isfinite(losses)):
        raise AssertionError(f"{name}: losses {losses}")
    log(f"{name}: steps={len(losses)} losses={losses} "
        f"first_dispatch_s={rec.first_dispatch_s:.1f} "
        f"pivots={rec.pivots.tolist()}")
    return trainer, report, rec


def compiled_step_kernels(name: str, trainer) -> set:
    """Kernel names in the compiled train step of ``trainer``'s config,
    lowered on its live state and a staged batch; logs the program's
    memory analysis."""
    batch = trainer.backend.shard_batch(next(iter(trainer.data)))
    step = jax.jit(steps_lib.make_train_step(trainer.mcfg, trainer.tcfg),
                   donate_argnums=(0,))
    with sh.sharding_rules(trainer.backend.mesh()):
        compiled = step.lower(trainer.state, batch).compile()
    mem = compiled.memory_analysis()
    log(f"{name} compiled step: argument_bytes="
        f"{mem.argument_size_in_bytes} temp_bytes={mem.temp_size_in_bytes} "
        f"output_bytes={mem.output_size_in_bytes} "
        f"alias_bytes={mem.alias_size_in_bytes}")
    return compiled_kernel_names(compiled.as_text())


def peak_bytes(device) -> int:
    return int(device.memory_stats()["peak_bytes_in_use"])


def dense_first_loss(s: Smoke, cfg, rec) -> float:
    """Phase A's first-step loss, ``sum(per_example_loss · weights)`` over
    the first refresh's subset, recomputed with dense attention from the
    initial weights (two rows per call, which is exact per row)."""
    mcfg, _, data = cfg.build()
    dense = dataclasses.replace(mcfg, attn_backend="dense")
    params = model_lib.init_params(mcfg, jax.random.PRNGKey(s.seed))
    batch = next(iter(data))
    rows = {k: np.asarray(v)[rec.pivots] for k, v in batch.items()}
    pel = jax.jit(lambda p, b: model_lib.per_example_loss(dense, p, b))
    losses = [pel(params, {k: jnp.asarray(v[i:i + 2])
                           for k, v in rows.items()})
              for i in range(0, len(rec.pivots), 2)]
    return float(jnp.sum(jnp.concatenate(losses) * rec.weights))


def one_chip(s: Smoke, device) -> None:
    cfg_a = experiment(s)
    mcfg = cfg_a.build()[0]
    log(f"model: {mcfg.name} d_model={mcfg.d_model} heads={mcfg.num_heads} "
        f"head_dim={mcfg.head_dim} d_ff={mcfg.d_ff} "
        f"vocab={mcfg.vocab_size} layers={mcfg.num_layers}")
    log(f"reduced: num_layers 40 -> {mcfg.num_layers} (one 16 GB chip); "
        f"traffic synthetic_lm batch={s.batch} seq={s.seq} "
        f"refresh_every=1 rset={list(s.rset)}")

    trainer, report, rec_a = run_trainer("phase A (jnp selection chain)",
                                         cfg_a)
    kernels = compiled_step_kernels("phase A", trainer)
    log(f"phase A compiled kernels: {sorted(kernels)}")
    if not {"flash_fwd", "flash_dq", "flash_dkv"} <= kernels:
        raise AssertionError(f"phase A step lacks the flash kernels: "
                             f"{sorted(kernels)}")
    if "graft_select" in kernels:
        raise AssertionError("phase A must run the jnp selection chain")
    loss_a = report["history"][0]["loss"]
    log(f"phase A peak_bytes_in_use={peak_bytes(device)}")
    del trainer, report
    gc.collect()

    loss_dense = dense_first_loss(s, cfg_a, rec_a)
    rel = abs(loss_a - loss_dense) / abs(loss_dense)
    log(f"dense check: flash first loss={loss_a} dense={loss_dense} "
        f"rel_diff={rel:.3e} (rtol {DENSE_RTOL})")
    if not rel <= DENSE_RTOL:
        raise AssertionError("flash and dense first-step losses disagree")

    cfg_b = experiment(s, use_pallas=True)
    trainer, report, rec_b = run_trainer("phase B (fused Pallas selection)",
                                         cfg_b)
    kernels = compiled_step_kernels("phase B", trainer)
    log(f"phase B compiled kernels: {sorted(kernels)}")
    if "graft_select" not in kernels:
        raise AssertionError("phase B step lacks the graft_select kernel")
    if not np.array_equal(rec_a.pivots, rec_b.pivots):
        raise AssertionError(f"first-refresh pivots differ: "
                             f"{rec_a.pivots} vs {rec_b.pivots}")
    log("phase B pivots at the first refresh == phase A pivots")
    log(f"phase B peak_bytes_in_use={peak_bytes(device)}")


def four_chips(s: Smoke, devices) -> None:
    cfg = experiment(s)
    wide = MeshBackend(devices)
    trainer, report, _ = run_trainer("4-chip data mesh", cfg, backend=wide)
    losses4 = [row["loss"] for row in report["history"]]
    rows = s.batch // len(devices)
    for name, arr in wide.staged.items():
        held = sorted((shard.device.id, shard.data.shape[0])
                      for shard in arr.addressable_shards)
        if len({d for d, _ in held}) != len(devices) or \
                any(n != rows for _, n in held):
            raise AssertionError(f"staged {name} is not split {rows} rows "
                                 f"per chip: {held}")
    log(f"staged batch: each of {len(devices)} chips holds {rows} of "
        f"{s.batch} rows")
    del trainer, report, wide
    gc.collect()

    trainer, report, _ = run_trainer("1 chip of the 4", cfg,
                                     backend=MeshBackend(devices[:1]))
    losses1 = [row["loss"] for row in report["history"]]
    del trainer, report
    gc.collect()
    rel = np.max(np.abs(np.subtract(losses4, losses1)) / np.abs(losses1))
    log(f"4-chip vs 1-chip losses: max rel diff {rel:.3e} "
        f"(rtol {MESH_RTOL})")
    np.testing.assert_allclose(losses4, losses1, rtol=MESH_RTOL)

    # engine.select_sharded over the 4 chips vs one device: every shard
    # holds a replica of the same batch, so each must pick the
    # single-device pivots, and the global rank must match
    rng = np.random.default_rng(s.seed)
    K, d = s.batch, cfg.build()[0].d_model
    mesh = make_mesh((len(devices),), ("data",), devices=devices)
    for use_pallas in (False, True):
        gcfg = GraftConfig(rset=s.rset, eps=0.2, use_pallas=use_pallas)
        V1 = jnp.asarray(rng.normal(size=(K, gcfg.r_max)).astype(np.float32))
        G1 = jnp.asarray(rng.normal(size=(d, K)).astype(np.float32))
        single, _ = engine.select_batch(gcfg, "graft", V1, G1,
                                        jnp.mean(G1, axis=1))
        n = len(devices)
        sharded, _ = engine.select_sharded(gcfg, mesh, jnp.tile(V1, (n, 1)),
                                           jnp.tile(G1, (1, n)))
        piv = np.asarray(sharded.pivots).reshape(n, gcfg.r_max)
        for shard in range(n):
            np.testing.assert_array_equal(piv[shard] - shard * K,
                                          np.asarray(single.pivots))
        if int(sharded.rank) != int(single.rank):
            raise AssertionError(f"rank {int(sharded.rank)} != "
                                 f"{int(single.rank)}")
        np.testing.assert_allclose(float(sharded.last_error),
                                   float(single.last_error), atol=1e-5)
        log(f"select_sharded (use_pallas={use_pallas}) over {n} chips == "
            f"single device: pivots {np.asarray(single.pivots).tolist()}, "
            f"rank {int(single.rank)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    cache = configure_compile_cache()
    devices = jax.devices()
    device = devices[0]
    if device.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {device.platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              "devices", file=sys.stderr)
        return 1
    log(f"device: {device.device_kind} x{len(devices)}; compile cache "
        f"{cache}")

    s = Smoke()
    t0 = time.time()
    if args.chips == 4:
        four_chips(s, devices[:4])
    else:
        one_chip(s, device)
    log(f"total_s={time.time() - t0:.1f}")
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
