"""``chipbench/scopes.py``: the phase of a scope path, and the split of the
small trace recorded on a v5e. Run by hand, on the CPU:

    JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests
"""
from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import scopes  # noqa: E402
from chipbench.checks import check_scopes  # noqa: E402


@pytest.mark.parametrize("path,phase", sorted(check_scopes.PATHS.items()))
def test_classify(path, phase):
    assert scopes.classify(path) == phase


@pytest.mark.parametrize("path,phase", [
    # the first matching rule wins: a decision inside a selection forward's
    # scope is a decision, a recompute inside the backward is a recompute
    ("jit(fn)/select_forward/select_decide/top_k:", "select_decide"),
    ("jit(fn)/train_grad/transpose(jvp())/checkpoint/"
     "rematted_computation/tanh:", "recompute"),
    # a plain transpose operation in the forward is not the backward
    ("jit(fn)/train_grad/jvp()/transpose:", "forward"),
    # a scope name must be a whole segment
    ("jit(fn)/my_optimizer_helper/mul:", "unscoped"),
    ("jit(fn)/train_gradient/mul:", "unscoped"),
])
def test_classify_rule_order(path, phase):
    assert scopes.classify(path) == phase


def test_recorded_scope_trace():
    check_scopes.check_recorded(scopes)


def test_small_trace_without_scopes_is_unscoped():
    """A program without the scopes: all of its busy time is unscoped, so
    the readers report nothing."""
    sp = scopes.split(scopes.load(os.path.join(
        ROOT, "chipbench", "checks", "small_trace")))
    assert sp.busy_s > 0 and not sp.scoped
    assert sp.class_s["unscoped"] == pytest.approx(sp.busy_s, rel=1e-6)
