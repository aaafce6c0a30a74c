"""Jit'd public wrappers over the Pallas kernels.

On TPU these dispatch to the Mosaic-compiled kernels; on CPU (tests, the
dry-run container) they run in ``interpret=True`` mode, executing the same
kernel body in Python — bit-identical control flow, so the allclose tests
against ``ref.py`` validate the TPU target logic.
"""
from __future__ import annotations

import functools
import re
from typing import Set

import jax

from repro.distributed.sharding import shard_kernel
from repro.kernels import fast_maxvol as _fm
from repro.kernels import graft_select as _gs
from repro.kernels import projection_sweep as _ps
from repro.kernels import rwkv_scan as _rw


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


_KERNEL_OP = re.compile(r'op_name="[^"]*?([^"/]*)/pallas_call"')


def compiled_kernel_names(hlo_text: str) -> Set[str]:
    """Names of the Pallas kernels compiled into a program's HLO text
    (``compiled.as_text()``): each kernel's ``pallas_call(name=...)``,
    with transform wrappers such as ``transpose(jvp(...))`` removed.
    Only a chip compile holds these; interpret mode leaves no kernel."""
    names = set()
    for op in _KERNEL_OP.findall(hlo_text):
        while op.endswith(")") and "(" in op:
            op = op[op.index("(") + 1:-1]
        names.add(op)
    return names


def fast_maxvol(V: jax.Array, rank: int) -> jax.Array:
    """Pivot indices (rank,) — Pallas fast MaxVol."""
    pivots, _ = _fm.fast_maxvol_pallas(V, rank, interpret=not _on_tpu())
    return pivots


def fast_maxvol_with_logvol(V: jax.Array, rank: int):
    return _fm.fast_maxvol_pallas(V, rank, interpret=not _on_tpu())


def projection_sweep(G: jax.Array, g_bar: jax.Array) -> jax.Array:
    """Prefix projection errors (R,) — Pallas MGS sweep."""
    return _ps.projection_sweep_pallas(G, g_bar, interpret=not _on_tpu())


def _replicated(kernel, rank: int, *args):
    """The selection matrices are tiny and replicated: under a mesh every
    device runs the kernel on the whole of them (``shard_kernel``).
    Returns (pivots, errors, G_sel)."""
    fn = functools.partial(kernel, rank=rank, interpret=not _on_tpu())
    pivots, errors, _, gsel = shard_kernel(
        fn, args, [(None,) * a.ndim for a in args], ())
    return pivots, errors, gsel


def fused_graft_select(V: jax.Array, G: jax.Array, g_bar: jax.Array,
                       rank: int):
    """One GRAFT refresh (MaxVol + gather + MGS sweep) in ONE dispatch.
    Returns (pivots (rank,), errors (rank,), G_sel (d, rank))."""
    return _replicated(_gs.fused_graft_select_pallas, rank, V, G, g_bar)


def fused_graft_select_batched(V: jax.Array, G: jax.Array, g_bar: jax.Array,
                               rank: int):
    """A microbatch stack of refreshes in ONE grid=(B,) launch. Returns
    (pivots (B, rank), errors (B, rank), G_sel (B, d, rank))."""
    return _replicated(_gs.fused_graft_select_batched_pallas, rank,
                       V, G, g_bar)


def rwkv_scan(r, k, v, w, u, chunk: int = 32) -> jax.Array:
    """Chunked RWKV6 recurrence (BH, T, D) — Pallas state-resident scan."""
    return _rw.rwkv_scan_pallas(r, k, v, w, u, chunk=chunk,
                                interpret=not _on_tpu())


def flash_attention(q, k, v, causal: bool = True, window=None, softcap=None,
                    block_q: int = 128, block_k: int = 128, group: int = 1,
                    scale=None):
    """Pallas flash attention q (B·H, S, Dh), k/v (B·Hkv, T, Dh) — the
    model hot path (differentiable; GQA via ``group``)."""
    from repro.kernels import flash_attention as _fa
    return _fa.flash_attention_pallas(q, k, v, block_q=block_q,
                                      block_k=block_k, causal=causal,
                                      window=window, softcap=softcap,
                                      group=group, scale=scale,
                                      interpret=not _on_tpu())
