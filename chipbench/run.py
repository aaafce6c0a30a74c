"""Run one benchmark cell on the chips of this machine.

    python -m chipbench.run --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

from the root of a checkout. The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer ones), ``device``,
with ``--trace 1`` a ``breakdown`` of the trace, and last ``compared``:
each number the correctness check compared, beside its limit. The same
numbers end standard error. Without a TPU, or with fewer chips than the
cell asks for, the run exits non-zero before printing a result.
"""
from __future__ import annotations

import time

T_PROCESS = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "src"))
sys.path.insert(0, _ROOT)
# libtpu writes its logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")

    from chipbench import harness
    cell = harness.find_cell(args.workload)
    # the program: without it these imports fail, and the run exits
    # non-zero before printing anything
    import repro.api  # noqa: F401
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         T_PROCESS)
    print(json.dumps(result), flush=True)
    for name, c in result["compared"].items():
        harness.log(f"compared {name}: {c['value']!r} limit {c['limit']!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
