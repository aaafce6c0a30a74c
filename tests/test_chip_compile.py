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
from repro.models.layers import _flash_blocks

# minicpm-2b: d_model 2304 (the probe grad-embedding width), 36 heads of
# 64; a 64-example batch with r_max 32 and 2048-token sequences
K, R, D, RANK = 64, 32, 2304, 32
# flash cases: (B·H, B·Hkv, S, head_dim), softcap, traced window. minicpm-2b's
# selection forward over 16 rows (the benchmark cell's) and its 2-row
# subset; gemma2-27b's GQA 32/16 heads of 128 with its softcap and the
# scanned local/global window
FLASH_FWD = {"minicpm-select": ((576, 576, 2048, 64), None, False),
             "gemma2": ((32, 16, 2048, 128), 50.0, True)}
FLASH_FWD_BWD = {"minicpm-subset": ((72, 72, 2048, 64), None, False),
                 "gemma2": ((32, 16, 2048, 128), 50.0, True)}


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


def _flash(shape, softcap, windowed, one_chip):
    """The model path's call: the router's tiles, pre-scaled q, and for a
    windowed case the window as a traced operand."""
    bh, bhkv, S, Dh = shape
    (fbq, fbk), (bq, bk) = _flash_blocks(S, S, Dh, 2)
    bf16 = lambda n: jax.ShapeDtypeStruct((n, S, Dh), jnp.bfloat16,
                                          sharding=one_chip)
    shapes = [bf16(bh), bf16(bhkv), bf16(bhkv)]
    if windowed:
        shapes.append(jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip))

    def attn(q, k, v, *w):
        return flash_attention_pallas(
            q, k, v, block_q=bq, block_k=bk, fwd_block_q=fbq, fwd_block_k=fbk,
            window=w[0] if w else None, softcap=softcap, group=bh // bhkv,
            scale=1.0)
    return attn, shapes


@pytest.mark.parametrize("case", sorted(FLASH_FWD))
def test_flash_forward_compiles(one_chip, case):
    attn, shapes = _flash(*FLASH_FWD[case], one_chip)
    _assert_kernels(_compile(attn, *shapes), "flash_fwd")


@pytest.mark.parametrize("case", sorted(FLASH_FWD_BWD))
def test_flash_forward_backward_compiles(one_chip, case):
    attn, shapes = _flash(*FLASH_FWD_BWD[case], one_chip)

    def loss(q, k, v, *w):
        return jnp.sum(attn(q, k, v, *w).astype(jnp.float32))

    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2)), *shapes)
    _assert_kernels(compiled, "flash_fwd", "flash_dq", "flash_dkv")
