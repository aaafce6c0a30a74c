"""The correctness check must fail the control and every fault a training
cell can have. Run by hand, on the CPU, at a size a test run holds:

    JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests

Each test drives the harness's own run (with its look for a chip skipped)
of a cell cut to a small size and reads ``correct``: true for the program
as it is, false with the control (the reference in float8) in its place,
and false with each fault planted in the timed path underneath the
trainer: a step that returns its state unchanged; half of each batch left
out; the step's loss altered where it is produced. (The cells run on one
chip, so there is no exchange between chips to leave out.) Two more tests
hold the reference to the traffic's refresh period: a program refreshing
every second step is correct, and one that selects anew on every step is
not, judged against that period.

The limits are those of this size (``SMALL_LIMITS``): a leaf of a few
thousand elements rounds further from its float32 norm than one of
millions, so the program at this size reads above the cells' own limits
on ``grad_norm`` and ``param_change``. They were set as the cells' were:
the program's readings at this size on the CPU (``loss`` 2.4e-05,
``grad_norm`` 2.1e-03, ``param_change`` 2.8e-03, ``selection`` 0.031 at
most, over both cells and seeds 11 and 3000000019) below them, the
control's (``loss`` 1.8e-04, ``grad_norm`` 8.3e-03 at least) above.
"""
from __future__ import annotations

import contextlib
import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

from chipbench import correct, harness  # noqa: E402

SEED = 3_000_000_019
SMALL = {"hidden_size": 72, "intermediate_size": 144,
         "num_attention_heads": 6, "head_dim": 12, "num_hidden_layers": 2,
         "vocab_size": 257}
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]
FAULTS = ["unchanged", "half_batch", "loss_altered"]
SMALL_LIMITS = {"loss": 1e-4, "grad_norm": 5e-3, "param_change": 6e-3,
                "selection": 0.05}


@pytest.fixture(autouse=True)
def small_limits(monkeypatch):
    monkeypatch.setattr(correct, "limits", lambda workload: SMALL_LIMITS)


def small_cell(name: str) -> harness.Cell:
    cell = harness.find_cell(name)
    kv = cell.config["num_key_value_heads"] * 6 // \
        cell.config["num_attention_heads"]
    cell.config.update(SMALL, num_key_value_heads=max(kv, 1))
    cell.traffic.update({"seq_len": 64, "probe_positions": 16,
                         "loss_chunk": 16, "pool_steps": 8})
    return cell


@contextlib.contextmanager
def planted(fault: str):
    """Break the train step the trainer compiles, underneath it."""
    from repro.launch import steps as steps_lib
    orig = steps_lib.make_train_step

    def make_train_step(mcfg, tcfg, kind=None):
        step = orig(mcfg, tcfg, kind)

        def broken(state, batch):
            if fault == "unchanged":
                return state, step(state, batch)[1]
            if fault == "half_batch":
                half = {k: v[:v.shape[0] // 2] for k, v in batch.items()}
                return step(state, half)
            if fault == "loss_altered":
                new, metrics = step(state, batch)
                return new, dict(metrics, loss=metrics["loss"] * 1.01)
            raise ValueError(fault)
        return broken

    steps_lib.make_train_step = make_train_step
    try:
        yield
    finally:
        steps_lib.make_train_step = orig


def run(cell, check=None):
    return harness.run(cell, SEED, 0.1, False, time.time(),
                       require_tpu=False, check=check)


@pytest.mark.parametrize("name", CELLS)
def test_program_is_correct(name):
    assert run(small_cell(name))["correct"]


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    from chipbench.reference import train as ref_train

    def control(cell, seed, prog):
        ctl = ref_train.steps(cell.config, cell.traffic, cell.batch, seed,
                              f8=True)
        return correct.check(cell, seed, ctl)

    assert not run(small_cell(name), check=control)["correct"]


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("name", CELLS)
def test_fault_is_not_correct(name, fault):
    cell = small_cell(name)
    with planted(fault):
        result = run(cell)
    assert not result["correct"], result["compared"]


def with_refresh(cell, every: int):
    cell.traffic["graft"] = dict(cell.traffic["graft"], refresh_every=every)
    return cell


def test_refresh_period_is_followed():
    """A refresh every second step covers both of the reference's branches
    in the three compared steps: select at 0 and 2, keep at 1."""
    assert run(with_refresh(small_cell(CELLS[0]), 2))["correct"]


def test_reselecting_is_not_correct():
    """A program that selects anew on every step, judged by a reference
    that keeps the decision between refreshes."""
    kept = with_refresh(small_cell(CELLS[0]), 2)

    def check(cell, seed, prog):
        return correct.check(kept, seed, prog)

    assert not run(with_refresh(small_cell(CELLS[0]), 1),
                   check=check)["correct"]
