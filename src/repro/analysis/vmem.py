"""Static VMEM footprint + grid/block feasibility for the Pallas kernels.

One audited estimator instead of three inline mirrors: the flash-attention
wrapper's guard (``kernels/flash_attention.py``), the backend router's
feasibility probe (``models/layers.py:_flash_feasible``), and the fused
selection budget (``kernels/graft_select.py:_check_budget``) all consult
the formulas here, so the number the router plans with is the number the
kernel enforces.

The budget is the per-program share of TPU VMEM a single kernel instance
may keep resident (~12 MB of the ~16 MB/core arena, leaving headroom for
semaphores/compiler spill). Footprints are computed from BlockSpec block
shapes and dtypes — what the Pallas runtime actually keeps resident per
grid program — NOT from the full operand shapes.

Headroom reports (VM003, info) are the groundwork for the ROADMAP's
blockwise-KV item: they say how far ``T`` can grow before flash attention
must tile the KV stream.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

from repro.analysis.report import Finding, Report

# per-program resident budget (f32 words * 4 bytes accounting everywhere)
VMEM_BUDGET_BYTES = 12 * 1024 * 1024


@dataclasses.dataclass(frozen=True)
class VmemEstimate:
    """Per-program VMEM residency of one kernel configuration."""
    kernel: str                     # "flash_attention(T=512, Dh=64, bq=128)"
    parts: Dict[str, int]           # resident block → bytes
    budget: int = VMEM_BUDGET_BYTES

    @property
    def total(self) -> int:
        return sum(self.parts.values())

    @property
    def headroom(self) -> int:
        return self.budget - self.total

    @property
    def fits(self) -> bool:
        return self.total <= self.budget

    def describe(self) -> str:
        parts = ", ".join(f"{k}={v / 2**20:.2f}MB"
                          for k, v in sorted(self.parts.items()))
        return (f"{self.kernel}: {self.total / 2**20:.2f}MB resident "
                f"({parts}); headroom {self.headroom / 2**20:.2f}MB")

    def report(self, location: str = "") -> Report:
        """VM001 on overflow, VM003 headroom note otherwise."""
        rep = Report()
        loc = location or self.kernel
        if not self.fits:
            rep.add(Finding(
                rule="VM001", location=loc,
                message=f"resident blocks {self.total / 2**20:.2f}MB exceed "
                        f"the {self.budget / 2**20:.0f}MB per-program budget "
                        f"({self.describe()})",
                fix_hint="shrink the block sizes / KV length, or route this "
                         "shape to the chunked jnp path"))
        else:
            rep.add(Finding(rule="VM003", location=loc,
                            message=self.describe()))
        return rep


def check_divisible(extent: int, block: int, axis: str,
                    location: str) -> Optional[Finding]:
    """VM002: a block that does not divide its extent drops or pads rows."""
    if block <= 0 or extent % block:
        return Finding(
            rule="VM002", location=location,
            message=f"block size {block} does not divide {axis}={extent}",
            fix_hint="pick a block from the divisor ladder "
                     "(models/layers.py:_FLASH_BLOCKS) or pad the operand")
    return None


# ---------------------------------------------------------------------------
# per-kernel footprints (formulas bit-exact with the kernel wrappers)
# ---------------------------------------------------------------------------

def flash_forward_vmem(T: int, head_dim: int, block_q: int,
                       block_k: int = 128, itemsize: int = 2) -> VmemEstimate:
    """Flash-attention forward, one grid program: the whole K and V streams
    (kv BlockSpec ``(1, T, Dh)``) and the q and o tiles at the operands'
    own itemsize, each double-buffered by the pipeline; the f32 score and
    p tiles with p's two halves at that itemsize; the f32 (block_q, 1)
    m and l, each padded to 128 lanes; the f32 accumulator."""
    return VmemEstimate(
        kernel=(f"flash_attention(T={T}, Dh={head_dim}, bq={block_q}, "
                f"bk={block_k})"),
        parts={"kv_stream": 2 * 2 * T * head_dim * itemsize,
               "q_o_tiles": 2 * 2 * block_q * head_dim * itemsize,
               "score_tiles": block_q * block_k * (2 * 4 + 2 * itemsize),
               "stats": 2 * block_q * 128 * 4,
               "acc": block_q * head_dim * 4})


def flash_attention_report(S: int, T: int, head_dim: int, block_q: int,
                           block_k: int, itemsize: int = 2) -> Report:
    """Full feasibility check for one flash shape: divisibility + VMEM."""
    rep = Report()
    loc = f"flash_attention(S={S}, T={T}, Dh={head_dim})"
    for extent, block, axis in ((S, block_q, "Sq"), (T, block_k, "T")):
        f = check_divisible(extent, block, axis, loc)
        if f:
            rep.add(f)
    rep.extend(flash_forward_vmem(T, head_dim, block_q, block_k,
                                  itemsize).report(loc))
    return rep


def flash_feasible(S: int, T: int, head_dim: int, block_q: int,
                   block_k: int, itemsize: int = 2) -> bool:
    """The router's go/no-go: blocks divide AND the footprint fits."""
    return flash_attention_report(S, T, head_dim, block_q, block_k,
                                  itemsize).ok


def fused_select_vmem(K: int, R: int, d: int, rank: int,
                      itemsize: int = 4) -> VmemEstimate:
    """Fused GRAFT selection, single program: V (K,R) and the MaxVol
    residual W (K,R), G (d,K), the selected-columns output G_sel (d,rank)
    and the MGS basis Q (d,rank) — the ``words = 2*K*R + d*K + 2*d*rank``
    that ``graft_select.py:_check_budget`` enforces."""
    return VmemEstimate(
        kernel=f"graft_select(K={K}, R={R}, d={d}, rank={rank})",
        parts={"V+W": 2 * K * R * itemsize,
               "G": d * K * itemsize,
               "G_sel+Q": 2 * d * rank * itemsize})


def fast_maxvol_vmem(K: int, R: int, itemsize: int = 4) -> VmemEstimate:
    """Standalone Fast MaxVol: the whole K×R feature matrix stays resident
    through the R-step pivot loop, plus one K-vector of scores and one
    R-row workspace for the rank-1 update."""
    return VmemEstimate(
        kernel=f"fast_maxvol(K={K}, R={R})",
        parts={"V": K * R * itemsize,
               "scores": K * itemsize,
               "row_ws": R * itemsize})
