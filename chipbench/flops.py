"""Operations and bytes the GRAFT step and the flash kernels require,
counted from shapes. This is the benchmark's own count: matrix
multiplications at two operations a multiply-add, remat's recompute not
counted, elementwise work left out.

Each layer's forward is counted by the configuration's block file
(``reference/blocks/<block>.py:layer_forward_flops``); the head here.
A GRAFT step does

* on a refresh step (every ``refresh_every`` steps, from step 0) the
  selection forward over all K rows: every layer, plus the head at the
  probe positions only (``probe_positions`` of them a row); on any other
  step no selection;
* forward and backward over the rows the selection gave a nonzero weight
  (the step's reported ``rank``), the head at every position. The program
  also runs its zero-weight rows; that work is not required and not
  counted.

``python -m chipbench.checks.check_flops`` compares these counts with
XLA's own ``cost_analysis()`` on a small configuration on the CPU.
"""
from __future__ import annotations

from types import ModuleType
from typing import Any, Dict, NamedTuple

from chipbench.reference import blocks


class Dims(NamedTuple):
    d: int          # hidden
    layers: int
    vocab: int
    block: ModuleType       # the configuration's block file
    bd: Any                 # its dimensions (``block.Dims``)

    @classmethod
    def from_config(cls, c: Dict) -> "Dims":
        block = blocks.of(c)
        return cls(c["hidden_size"], c["num_hidden_layers"], c["vocab_size"],
                   block, block.Dims.from_config(c))


def layer_forward_flops(m: Dims, rows: int, seq: int,
                        causal: bool = True) -> float:
    """One layer's forward over ``rows`` sequences of ``seq`` tokens, as
    the configuration's block file counts it (``layer_forward_flops``)."""
    return m.block.layer_forward_flops(m.bd, rows, seq, causal)


def head_flops(m: Dims, positions: int) -> float:
    return 2.0 * m.d * m.vocab * positions


def selection_forward_flops(m: Dims, rows: int, seq: int,
                            probe_positions: int, causal: bool = True
                            ) -> float:
    stride = max(1, seq // probe_positions) if probe_positions else 1
    probes = rows * len(range(0, seq, stride))
    return m.layers * layer_forward_flops(m, rows, seq, causal) + \
        head_flops(m, probes)


def subset_train_flops(m: Dims, rows: int, seq: int,
                       causal: bool = True) -> float:
    """Forward and backward (twice the forward) over ``rows`` sequences."""
    fwd = m.layers * layer_forward_flops(m, rows, seq, causal) \
        + head_flops(m, rows * seq)
    return 3.0 * fwd


def graft_step_flops(m: Dims, batch: int, seq: int, probe_positions: int,
                     rank: int, refresh: bool = True) -> float:
    select = selection_forward_flops(m, batch, seq, probe_positions) \
        if refresh else 0.0
    return select + subset_train_flops(m, rank, seq)


# ---------------------------------------------------------------------------
# flash kernels: one call over B·H query streams of length S
# ---------------------------------------------------------------------------

# causal matmuls each kernel needs, given that the forward keeps only the
# log-sum-exp: the forward QKᵀ and PV; dq recomputes QKᵀ, then dO·Vᵀ and
# dS·K; dkv recomputes QKᵀ, then Pᵀ·dO, dO·Vᵀ and dSᵀ·Q
FLASH_MATMULS = {"flash_fwd": 2, "flash_dq": 3, "flash_dkv": 4}


def flash_call(kernel: str, q_streams: int, kv_streams: int, seq: int,
               head_dim: int, itemsize: int = 2) -> Dict[str, float]:
    """(flops, bytes) one call of ``kernel`` requires: causal matmuls, and
    each input and output read or written once."""
    causal = head_dim * seq * (seq + 1)          # one causal matmul, per stream
    flops = FLASH_MATMULS[kernel] * causal * q_streams
    q_tensor = q_streams * seq * head_dim * itemsize
    kv_tensor = kv_streams * seq * head_dim * itemsize
    rowvec = q_streams * seq * 4                  # lse or delta, f32
    if kernel == "flash_fwd":                     # q, k, v, o, lse
        nbytes = 2 * q_tensor + 2 * kv_tensor + rowvec
    elif kernel == "flash_dq":                    # q, do, dq, k, v, lse, delta
        nbytes = 3 * q_tensor + 2 * kv_tensor + 2 * rowvec
    else:                                         # q, do, k, v, dk, dv, lse, delta
        nbytes = 2 * q_tensor + 4 * kv_tensor + 2 * rowvec
    return {"flops": float(flops), "bytes": float(nbytes)}


def roofline_seconds(cost: Dict[str, float], peak: Dict[str, float]) -> float:
    """The least time the chip could take: the larger of operations over
    peak FLOP/s and bytes over HBM bandwidth."""
    return max(cost["flops"] / peak["bf16_flops"],
               cost["bytes"] / peak["hbm_bytes_per_s"])
