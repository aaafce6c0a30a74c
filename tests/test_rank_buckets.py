"""The rank-bucketed subset update (``launch/steps.py:subset_grads``): the
forward and backward run over the first ``b`` selected rows, ``b`` the
smallest entry of the rank ladder past which every weight is exactly 0.

The oracle is the all-rows computation, kept here as it ran before the
buckets: ``value_and_grad`` of ``Σ pel·w`` over all R_max selected rows,
rows of weight 0 included."""
import collections
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import steps as steps_lib
from repro.models import model as model_lib
from repro.optim import make_optimizer
from repro.selection.base import SelectionState

from conftest import run_forced_devices

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

RSET = (2, 4, 8)
K = 8
# rank < R_max: float32 parameters, so the comparison is of f32 sums, and
# SGD at a constant unit rate, whose update is the clipped gradient itself
# (AdamW's g/(|g|+eps) would magnify an f32 rounding of a near-zero
# gradient entry up to lr, and a small rate would sink the update below
# the parameters' own rounding)
F32 = ['model.overrides={"param_dtype": "float32"}', "optimizer.name=sgd",
       "optimizer.learning_rate=1.0", "optimizer.schedule=constant"]


def _experiment(overrides=()):
    from repro.api import ExperimentConfig
    base = ["train.steps=3", f"train.batch={K}", "train.seq=16",
            "train.log_every=0", "graft.rset=[2,4,8]",
            "graft.refresh_every=1"]
    return ExperimentConfig().apply_overrides(base + list(overrides))


def _setup(overrides=()):
    mcfg, tcfg, data = _experiment(overrides).finalized().build()
    state = steps_lib.init_train_state(mcfg, tcfg, jax.random.PRNGKey(0), K)
    batch = {k: jnp.asarray(v) for k, v in data.batch_at(0).items()}
    return mcfg, tcfg, state, batch


def _oracle_grads(mcfg, params, batch, graft_state):
    """All R_max rows, weighted: the subset gradient without buckets."""
    sub = steps_lib._take_batch(batch, graft_state.pivots, K)

    def loss(p):
        return jnp.sum(model_lib.per_example_loss(mcfg, p, sub)
                       * graft_state.weights)
    return jax.value_and_grad(loss)(params)


def _oracle_step(mcfg, tcfg, state, batch):
    """Refresh, the all-rows gradient and the update: the selection, loss
    and new parameters of one GRAFT step without buckets."""
    refresh = steps_lib.make_selection_refresh(mcfg, tcfg)
    sel, _ = refresh(state["params"], batch,
                     steps_lib._state_carry(tcfg, state), state["step"])
    loss, grads = _oracle_grads(mcfg, state["params"], batch, sel)
    params, _, _ = make_optimizer(tcfg.optimizer).apply(
        state["params"], grads, state["opt"], state["step"])
    return sel, loss, grads, params


def _bucketed_grads(mcfg, tcfg, params, batch, sel):
    return steps_lib.subset_grads(mcfg, tcfg.graft, params, batch, sel, K)


def _leaves(tree):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


def _assert_bitwise(a, b):
    for x, y in zip(_leaves(a), _leaves(b), strict=True):
        np.testing.assert_array_equal(x, y)


def _assert_close(a, b):
    """Equal up to the order of f32 sums: within 1e-5 of each value and of
    its leaf's largest magnitude (about 84 f32 epsilons)."""
    pairs = list(zip(_leaves(a), _leaves(b), strict=True))
    assert any(np.any(y != 0) for _, y in pairs)
    for x, y in pairs:
        np.testing.assert_allclose(x, y, rtol=1e-5,
                                   atol=1e-5 * float(np.max(np.abs(y))))


def _change(new_params, state):
    """The update a step made: new parameters less the old."""
    return jax.tree_util.tree_map(lambda a, b: a - b, new_params,
                                  state["params"])


# ---------------------------------------------------------------------------
# the bucket choice
# ---------------------------------------------------------------------------

def test_ladder_is_rset_and_all_rows_without_a_mesh():
    assert steps_lib.rank_ladder(RSET, 8) == (2, 4, 8)
    assert steps_lib.rank_ladder((2, 4), 8) == (2, 4, 8)
    assert steps_lib.rank_ladder((8,), 8) == (8,)
    assert int(steps_lib.bucket_index(jnp.ones(8), (8,))) == 0


@pytest.mark.parametrize("rank,rows", [(1, 2), (2, 2), (3, 4), (4, 4),
                                       (5, 8), (6, 8), (7, 8), (8, 8)])
def test_zero_tail_picks_the_smallest_bucket_holding_it(rank, rows):
    w = np.zeros(8, np.float32)
    w[:rank] = 1.0 / rank
    ladder = steps_lib.rank_ladder(RSET, 8)
    index = int(steps_lib.bucket_index(jnp.asarray(w), ladder))
    assert ladder[index] == rows


@pytest.mark.parametrize("weights,rows", [
    ([0.5, 0.0, 0.5, 0, 0, 0, 0, 0], 4),     # a zero inside the first rows
    ([0.5, 0, 0, 0, 0, 0, 0, 0.5], 8),       # zeros but no zero tail
    ([0.0, 1.0, 0, 0, 0, 0, 0, 0], 2),
    ([0.0] * 8, 2),                           # nothing to train on
    ([0.25, 0.25, 0.25, float("nan"), 0, 0, 0, 0.25], 8),
])
def test_bucket_reads_the_last_nonzero_weight(weights, rows):
    ladder = steps_lib.rank_ladder(RSET, 8)
    index = int(steps_lib.bucket_index(jnp.asarray(weights, jnp.float32),
                                       ladder))
    assert ladder[index] == rows


def test_ladder_under_a_four_device_mesh_drops_what_four_does_not_divide():
    """On a 4-device data mesh the ladder keeps the ranks whose rows lay
    evenly along ``act_batch``, and a rank-2 step runs the 4-row bucket."""
    out = run_forced_devices("""
        import json
        import jax, jax.numpy as jnp
        from repro.api import ExperimentConfig
        from repro.distributed import sharding as sh
        from repro.launch import mesh as mesh_lib, steps as steps_lib
        mesh = mesh_lib.make_host_mesh()
        res = {"outside": steps_lib.rank_ladder((2, 4, 8, 16), 16)}
        with sh.sharding_rules(mesh):
            res["inside"] = steps_lib.rank_ladder((2, 4, 8, 16), 16)
            res["three"] = steps_lib.rank_ladder((3, 6), 6)
            cfg = ExperimentConfig().apply_overrides([
                "train.batch=8", "train.seq=16", "graft.rset=[2,4,8]",
                "graft.eps=1.0", "graft.refresh_every=1"])
            mcfg, tcfg, data = cfg.finalized().build()
            state = steps_lib.init_train_state(
                mcfg, tcfg, jax.random.PRNGKey(0), 8)
            batch = {k: jnp.asarray(v) for k, v in data.batch_at(0).items()}
            _, m = jax.jit(steps_lib.make_train_step(mcfg, tcfg))(
                state, batch)
            res["rank"], res["rows"] = int(m["rank"]), int(m["subset_rows"])
        print(json.dumps(res))
    """)
    res = json.loads(out.strip().splitlines()[-1])
    assert res["outside"] == [2, 4, 8, 16]
    assert res["inside"] == [4, 8, 16]
    assert res["three"] == [6]
    assert res["rank"] == 2 and res["rows"] == 4


# ---------------------------------------------------------------------------
# the update: all rows bit for bit, fewer rows within f32
# ---------------------------------------------------------------------------

def test_rank_rmax_is_the_all_rows_computation_bit_for_bit():
    mcfg, tcfg, state, batch = _setup(["graft.eps=1e-12"])
    sel, loss, grads, params = jax.jit(
        lambda s, b: _oracle_step(mcfg, tcfg, s, b))(state, batch)
    assert int(sel.rank) == 8
    b_loss, b_grads, rows = jax.jit(
        lambda p, b, s: _bucketed_grads(mcfg, tcfg, p, b, s))(
            state["params"], batch, sel)
    assert int(rows) == 8
    _assert_bitwise(loss, b_loss)
    _assert_bitwise(grads, b_grads)
    new_state, metrics = jax.jit(steps_lib.make_train_step(mcfg, tcfg))(
        state, batch)
    assert int(metrics["subset_rows"]) == 8
    _assert_bitwise(loss, metrics["loss"])
    _assert_bitwise(params, new_state["params"])


def test_no_zero_tail_takes_the_all_rows_branch_bit_for_bit():
    """A gradmatch-style selection: zeros inside, a nonzero last weight."""
    mcfg, tcfg, state, batch = _setup()
    w = jnp.asarray([0.3, 0.0, 0.2, 0.0, 0.1, 0.0, 0.0, 0.4], jnp.float32)
    sel = SelectionState(pivots=jnp.asarray([5, 1, 7, 0, 2, 3, 6, 4],
                                            jnp.int32),
                         weights=w, rank=jnp.int32(4),
                         last_error=jnp.float32(0.0),
                         alignment=jnp.float32(0.0), step=jnp.int32(0))
    loss, grads = jax.jit(
        lambda p, b, s: _oracle_grads(mcfg, p, b, s))(
            state["params"], batch, sel)
    b_loss, b_grads, rows = jax.jit(
        lambda p, b, s: _bucketed_grads(mcfg, tcfg, p, b, s))(
            state["params"], batch, sel)
    assert int(rows) == 8
    _assert_bitwise(loss, b_loss)
    _assert_bitwise(grads, b_grads)
    # the stored-subset step takes the same branch
    _, metrics = jax.jit(steps_lib.make_train_step(mcfg, tcfg, "subset"))(
        dict(state, graft=sel), batch)
    assert int(metrics["subset_rows"]) == 8
    _assert_bitwise(loss, metrics["loss"])


@pytest.mark.parametrize("overrides,sampler", [
    ([], "graft"),
    (["train.sampler=streaming_graft"], "streaming_graft"),
])
def test_rank_below_rmax_matches_all_rows_sequential(overrides, sampler):
    mcfg, tcfg, state, batch = _setup(["graft.eps=1.0"] + F32 + overrides)
    assert tcfg.sampler == sampler
    oracle = jax.jit(lambda s, b: _oracle_step(mcfg, tcfg, s, b))
    bucketed = jax.jit(lambda p, b, s: _bucketed_grads(mcfg, tcfg, p, b, s))
    step = jax.jit(steps_lib.make_train_step(mcfg, tcfg))
    for _ in range(2):        # the second step: a streaming reservoir filled
        sel, loss, grads, params = oracle(state, batch)
        assert int(sel.rank) == 2
        b_loss, b_grads, rows = bucketed(state["params"], batch, sel)
        assert int(rows) == 2
        np.testing.assert_allclose(np.asarray(b_loss), np.asarray(loss),
                                   rtol=2e-6)
        _assert_close(b_grads, grads)
        new_state, metrics = step(state, batch)
        assert int(metrics["rank"]) == 2
        assert int(metrics["subset_rows"]) == 2
        np.testing.assert_allclose(np.asarray(metrics["loss"]),
                                   np.asarray(loss), rtol=2e-6)
        _assert_close(_change(new_state["params"], state),
                  _change(params, state))
        state = new_state


def test_rank_below_rmax_matches_all_rows_overlap():
    from repro.selection.overlap import OverlappedSelector
    mcfg, tcfg, state, batch = _setup(["graft.eps=1.0", "graft.overlap=true"]
                                      + F32)
    sel_path = OverlappedSelector(mcfg, tcfg, donate=False)
    sel, loss, _, params = jax.jit(
        lambda s, b: _oracle_step(mcfg, tcfg, s, b))(state, batch)
    new_state, metrics = sel_path.step(state, batch, 0)
    assert int(metrics["rank"]) == 2
    assert int(metrics["subset_rows"]) == 2
    np.testing.assert_allclose(np.asarray(metrics["loss"]),
                               np.asarray(loss), rtol=2e-6)
    _assert_close(_change(new_state["params"], state),
                  _change(params, state))


# ---------------------------------------------------------------------------
# the counter
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("overlap", [False, True])
def test_subset_rows_reaches_rows_and_jsonl_without_a_sync(tmp_path,
                                                           overlap):
    from repro.api import Trainer
    path = tmp_path / "metrics.jsonl"
    cfg = _experiment(["graft.eps=1.0", "train.audit=true",
                       f"train.metrics_path={path}",
                       f"graft.overlap={str(overlap).lower()}"])
    report = Trainer(cfg).fit()
    assert report["audit"]["unsanctioned"] == 0
    assert report["audit"]["recompiles"] == 0
    rows = [json.loads(line) for line in path.read_text().splitlines()]
    assert len(rows) == 3
    for row, hist in zip(rows, report["history"], strict=True):
        assert row["rank"] == 2.0 and row["subset_rows"] == 2.0
        assert hist["subset_rows"] == 2.0


def test_branches_keep_the_phase_scopes():
    """Every branch's operations sit under ``train_grad``: the benchmark's
    classifier finds the forward, backward and recompute of each in the
    compiled step."""
    from chipbench import scopes
    mcfg, tcfg, state, batch = _setup(
        ['model.overrides={"remat": "full", "num_layers": 2}'])
    text = jax.jit(steps_lib.make_train_step(mcfg, tcfg)).lower(
        state, batch).compile().as_text()
    phases = collections.Counter()
    for name in re.findall(r'op_name="([^"]*)"', text):
        branch = re.search(r"/branch_(\d+)_fun/", name)
        if branch:
            assert "/train_grad/" in name, name
            phases[branch.group(1), scopes.classify(name)] += 1
    for branch in ("0", "1", "2"):
        for phase in ("forward", "backward", "recompute"):
            assert phases[branch, phase] > 0, phases
    assert {b for b, _ in phases} == {"0", "1", "2"}
