"""The whole step's share of the chips' peak: the FLOPs each step of the
window required (``chipbench/flops.py``: the selection forward over all
rows on the refresh steps, forward and backward over the rows of nonzero
weight from the step's reported rank; no recompute), over window seconds
x chips x peak bf16 FLOP/s."""
from chipbench import flops


def read(ctx):
    cell, win, peak = ctx["cell"], ctx["window"], ctx["peak"]
    rows = ctx["rows"]
    if peak is None or not rows or any("rank" not in r for r in rows):
        return None
    dims = flops.Dims.from_config(cell.config)
    every = cell.traffic["graft"].get("refresh_every", 1)
    total = sum(flops.graft_step_flops(
        dims, cell.batch, cell.seq, cell.traffic["probe_positions"],
        int(r["rank"]), refresh=(win.first_step + i) % every == 0)
        for i, r in enumerate(rows))
    return 100.0 * total / (win.window_s * cell.chips * peak["bf16_flops"])
