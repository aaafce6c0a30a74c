"""Compile the hot-path Pallas kernels for a described TPU v5e chip.

Interpret mode runs every kernel body on the CPU, but it accepts what the
chip's compiler refuses: dynamic slices of loaded values, block shapes off
the (8, 128) tile, more VMEM than a program may hold. These tests lower
and compile each kernel against a ``v5e:2x2`` topology description (no
chip needed) at minicpm-2b widths, and check that the compiled program
holds the kernel as a ``tpu_custom_call`` under its stable name.

The topology is described inside a fixture, never at import time: only
one process may load the TPU library at a time, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fast_maxvol import fast_maxvol_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.graft_select import (fused_graft_select_batched_pallas,
                                        fused_graft_select_pallas)
from repro.kernels.ops import compiled_kernel_names
from repro.kernels.projection_sweep import projection_sweep_pallas

# minicpm-2b: d_model 2304 (the probe grad-embedding width), 36 heads of
# 64; a 64-example batch with r_max 32 and 2048-token sequences
K, R, D, RANK = 64, 32, 2304, 32
FLASH_SHAPE = (72, 2048, 64)            # (B·H, S, head_dim), B = 2


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _assert_kernels(compiled, *names):
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert compiled_kernel_names(text) == set(names)


def test_fused_graft_select_compiles(one_chip):
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    compiled = _compile(
        lambda V, G, g: fused_graft_select_pallas(V, G, g, rank=RANK),
        f32(K, R), f32(D, K), f32(D))
    _assert_kernels(compiled, "graft_select")


def test_fused_graft_select_batched_compiles(one_chip):
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    B = 4
    compiled = _compile(
        lambda V, G, g: fused_graft_select_batched_pallas(V, G, g, rank=RANK),
        f32(B, K, R), f32(B, D, K), f32(B, D))
    _assert_kernels(compiled, "graft_select")


def test_fast_maxvol_compiles(one_chip):
    V = jax.ShapeDtypeStruct((K, R), jnp.float32, sharding=one_chip)
    _assert_kernels(_compile(lambda v: fast_maxvol_pallas(v, rank=RANK), V),
                    "fast_maxvol")


def test_projection_sweep_compiles(one_chip):
    G = jax.ShapeDtypeStruct((D, RANK), jnp.float32, sharding=one_chip)
    g = jax.ShapeDtypeStruct((D,), jnp.float32, sharding=one_chip)
    _assert_kernels(_compile(projection_sweep_pallas, G, g),
                    "projection_sweep")


def test_flash_forward_compiles(one_chip):
    qkv = [jax.ShapeDtypeStruct(FLASH_SHAPE, jnp.bfloat16,
                                sharding=one_chip)] * 3
    compiled = _compile(
        lambda q, k, v: flash_attention_pallas(q, k, v, scale=1.0), *qkv)
    _assert_kernels(compiled, "flash_fwd")


def test_flash_forward_backward_compiles(one_chip):
    qkv = [jax.ShapeDtypeStruct(FLASH_SHAPE, jnp.bfloat16,
                                sharding=one_chip)] * 3

    def loss(q, k, v):
        o = flash_attention_pallas(q, k, v, scale=1.0)
        return jnp.sum(o.astype(jnp.float32))

    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2)), *qkv)
    _assert_kernels(compiled, "flash_fwd", "flash_dq", "flash_dkv")
