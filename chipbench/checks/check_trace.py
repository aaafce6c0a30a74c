"""Check ``chipbench/trace.py`` by hand.

    JAX_PLATFORMS=cpu python -m chipbench.checks.check_trace

First on intervals whose answers are known (union, subtraction, nested
operations' own time), then on the small trace ``record_trace`` recorded
on a TPU v5e (``checks/small_trace/``, described in
``checks/small_trace.json``): four 2048x2048 bf16 products inside a
``loop`` span, three of them after a 50 ms host sleep inside a ``stage``
span and one after a bare 50 ms sleep. The reduction must find the four
products on the chip, busy plus idle equal to the window, three idle gaps
of about 50 ms put to ``stage`` and one put to ``loop``.
"""
from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def check_intervals(trace) -> None:
    assert trace.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert trace.subtract([(0, 10)], [(1, 2), (4, 5)]) == \
        [(0, 1), (2, 4), (5, 10)]
    assert trace.subtract([(0, 3), (5, 9)], [(2, 6)]) == [(0, 2), (6, 9)]
    ops = [trace.Op("while.1", 0.0, 10.0, ""), trace.Op("a.1", 1.0, 4.0, ""),
           trace.Op("b.2", 5.0, 9.0, ""), trace.Op("c.3", 12.0, 13.0, "")]
    trace._self_times(ops)
    assert [round(o.self_s, 9) for o in ops] == [3.0, 3.0, 4.0, 1.0]
    assert trace.op_name("%flash_fwd.25 = (bf16[1]) custom-call()") == \
        "flash_fwd.25"


def check_recorded(trace) -> None:
    with open(os.path.join(HERE, "small_trace.json")) as f:
        spec = json.load(f)
    red = trace.reduce(trace.load(os.path.join(HERE, "small_trace")))
    gaps = dict()
    for name, s in red.gaps:
        gaps.setdefault(name, []).append(s)
    idle = sum(s for _, s in red.gaps)
    print(f"window {red.window_s:.6f} s, busy {red.busy_s:.6f} s, "
          f"idle {idle:.6f} s")
    print("ops:", red.breakdown()["device_ops"])
    print("gaps:", {k: [round(x, 4) for x in sorted(v)[-4:]]
                    for k, v in gaps.items()})
    assert abs(red.busy_s + idle - red.window_s) < 1e-6 * red.window_s
    long_stage = [s for s in gaps.get("stage", [])
                  if s > 0.9 * spec["stage_sleep_s"]]
    long_loop = [s for s in gaps.get("loop", [])
                 if s > 0.9 * spec["loop_sleep_s"]]
    assert len(long_stage) == spec["stage_gaps"], gaps
    assert len(long_loop) >= 1, gaps
    # XLA fuses each product into an output fusion (``kind=kOutput``)
    products = [o for v in red.ops.values() for o in v
                if "kind=kOutput" in o.text]
    assert len(products) == spec["matmuls"], len(products)
    assert 0 < red.busy_s < red.window_s


def main() -> int:
    from chipbench import trace
    check_intervals(trace)
    check_recorded(trace)
    print("ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
