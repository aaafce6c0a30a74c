"""Check ``chipbench/scopes.py`` by hand.

    JAX_PLATFORMS=cpu python -m chipbench.checks.check_scopes

First ``classify`` on scope paths whose phases are known, then the small
trace ``record_scopes`` recorded on a TPU v5e (``checks/scope_trace/``,
described in ``checks/scope_trace.json``): a toy step in the program's
five scopes, three times inside a ``loop`` span, each after a host sleep
inside a ``data_wait`` span. The decoded start of every event must lie
within 1 ns of ``jax.profiler.ProfileData``'s; every phase must hold
time; the phases, ``unscoped`` included, must sum to the busy time within
1%; and the ``data_wait`` time must hold the sleeps.
"""
from __future__ import annotations

import glob
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

PATHS = {
    "jit(fn)/select_forward/while/body/dot_general:": "select_forward",
    "jit(fn)/cond/branch_1_fun/select_decide/pallas_call:": "select_decide",
    "jit(fn)/train_grad/jvp()/while/body/closed_call/dot_general:":
        "forward",
    "jit(fn)/train_grad/jvp(jit(_take))/gather:": "forward",
    "jit(fn)/train_grad/jvp()/while/body/transpose:": "forward",
    "jit(fn)/train_grad/transpose(jvp())/while/body/dot_general:":
        "backward",
    "jit(fn)/train_grad/transpose(jvp())/while/body/closed_call/checkpoint/"
    "rematted_computation/dot_general:": "recompute",
    "jit(fn)/optimizer/mul:": "update",
    "jit(fn)/sentinel/select_n:": "update",
    "jit(fn)/add:": "unscoped",
    "": "unscoped",
}


def check_classify(scopes) -> None:
    for path, phase in PATHS.items():
        assert scopes.classify(path) == phase, (path, scopes.classify(path))


def check_starts(scopes, path: str) -> None:
    """Every event's start, as ``load`` computes it, against
    ``ProfileData``'s, in nanoseconds."""
    import jax
    space = scopes._xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    mine = {(p.name, ln.name): [ln.timestamp_ns + ev.offset_ps * 1e-3
                                for ev in ln.events]
            for p in space.planes for ln in p.lines}
    worst, n = 0.0, 0
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            theirs = [ev.start_ns for ev in line.events]
            ours = mine[(plane.name, line.name)]
            assert len(theirs) == len(ours), (plane.name, line.name)
            for a, b in zip(theirs, ours):
                worst = max(worst, abs(a - b))
                n += 1
    print(f"starts: {n} events, largest gap {worst:.3f} ns")
    assert n > 0 and worst <= 1.0, worst


def check_recorded(scopes) -> None:
    with open(os.path.join(HERE, "scope_trace.json")) as f:
        spec = json.load(f)
    directory = os.path.join(HERE, "scope_trace")
    for path in glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                          recursive=True):
        check_starts(scopes, path)
    sp = scopes.split(scopes.load(directory))
    total = sum(sp.class_s.values())
    print(f"window {sp.window_s:.6f} s, busy {sp.busy_s:.6f} s, "
          f"phases {total:.6f} s, data_wait {sp.data_wait_s:.6f} s")
    print("phases:", {k: round(v, 6) for k, v in sp.class_s.items()})
    for phase in scopes.CLASSES:
        if phase != "unscoped":
            assert sp.class_s[phase] > 0, phase
    assert abs(total - sp.busy_s) <= 0.01 * sp.busy_s, (total, sp.busy_s)
    assert sp.data_wait_s >= 0.9 * spec["steps"] * spec["data_wait_s"], \
        sp.data_wait_s
    assert 0 < sp.busy_s < sp.window_s


def main() -> int:
    from chipbench import scopes
    check_classify(scopes)
    check_recorded(scopes)
    print("ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
