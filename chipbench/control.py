"""The readings the correctness limits are set from, at a cell's own size.

    python -m chipbench.control --workload <cell> --seeds 1,2,3 \
        [--program] [--control] [--faults]

For each seed, in one process:

* ``--program``: a benchmark run of the cell with a one-step window; its
  numbers against the float32 reference are the *lower* readings;
* ``--control``: the reference computed with every matrix product in
  float8 (e4m3, a scale per tensor; the configurations state bfloat16),
  put in the program's place;
* ``--faults``: the reference with the second half of the batch left
  out, put in the program's place, for one step (its selection is what
  the check reads; the reference then follows its rows, so its training
  numbers read 0). A step that returns its state unchanged needs no run:
  its gradient and change norms are 0, which reads 1 on ``grad_norm`` and
  ``param_change``.

One JSON line per seed and kind on standard output. Needs the chips the
cell asks for.
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
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def readings(cell, seeds, program: bool, control: bool, faults: bool,
             require_tpu=True, emit=print):
    """Yield ``{"seed", "kind", "numbers"}`` for each seed and kind."""
    from chipbench import correct, harness
    from chipbench.reference import train as ref_train
    harness.devices_for(cell, require_tpu)
    harness.configure_cache()
    out = []

    def put(seed, kind, nums, extra=None):
        row = {"seed": seed, "kind": kind, "numbers": nums}
        if extra:
            row.update(extra)
        emit(json.dumps(row))
        out.append(row)

    for seed in seeds:
        box = {}

        def check(c, s, prog):
            box["prog"] = prog
            return True, {}

        def against(run):
            """The numbers of ``run`` against the float32 reference that
            follows its decisions, as ``correct.check`` computes them."""
            ref = ref_train.steps(cell.config, cell.traffic, cell.batch, seed,
                                  n_steps=len(run["losses"]),
                                  follow=run["select"])
            where = {}
            nums = correct.numbers(run, ref, where)
            ref["worst_leaves"] = where
            return nums, ref

        t = time.time()
        if program:
            harness.run(cell, seed, 0.1, False, t, require_tpu=require_tpu,
                        check=check)
            nums, ref = against(box["prog"])
            put(seed, "program", nums, {"margins": ref["margins"],
                                        "worst_leaves": ref["worst_leaves"],
                                        "program_select": box["prog"]["select"],
                                        "reference_decided": ref["decided"]})
        if control:
            ctl = ref_train.steps(cell.config, cell.traffic, cell.batch,
                                  seed, f8=True)
            nums, ref = against(ctl)
            put(seed, "control_f8", nums,
                {"worst_leaves": ref["worst_leaves"]})
        if faults:
            half = ref_train.steps(cell.config, cell.traffic, cell.batch,
                                   seed, n_steps=1, fault="half")
            put(seed, "fault_half_batch", against(half)[0])
        put(seed, "seconds", {"total": time.time() - t})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--program", action="store_true")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--faults", action="store_true")
    args = ap.parse_args(argv)
    from chipbench import harness
    cell = harness.find_cell(args.workload)
    readings(cell, [int(s) for s in args.seeds.split(",")], args.program,
             args.control, args.faults)
    return 0


if __name__ == "__main__":
    sys.exit(main())
