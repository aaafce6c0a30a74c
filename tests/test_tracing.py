"""The trainer's own tracing (``repro.launch.tracing``): the phase scopes
reach every step program's operations, the benchmark's classification of
scope paths splits a remat model's gradient into forward, backward and
recompute, ``Trainer.fit`` opens its host spans once a step and reports
them, and the step compiles once."""
import collections
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import steps as steps_lib
from repro.launch import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

SELECT = {tracing.SELECT_FORWARD, tracing.SELECT_DECIDE}
TRAIN = {tracing.TRAIN_GRAD, tracing.OPTIMIZER, tracing.SENTINEL}


def _experiment(overrides=()):
    from repro.api import ExperimentConfig
    base = ["train.steps=3", "train.batch=8", "train.seq=16",
            "train.log_every=0", "graft.rset=[2,4]"]
    return ExperimentConfig().apply_overrides(base + list(overrides))


def _abstract(overrides=()):
    """(mcfg, tcfg, abstract state, abstract batch) of a smoke run."""
    mcfg, tcfg, data = _experiment(overrides).finalized().build()
    state = jax.eval_shape(
        lambda k: steps_lib.init_train_state(mcfg, tcfg, k, 8),
        jax.random.PRNGKey(0))
    batch = jax.eval_shape(
        lambda: {k: jnp.asarray(v) for k, v in data.batch_at(0).items()})
    return mcfg, tcfg, state, batch


def _scopes_in(lowered):
    """The phase scopes named in a lowered program's locations."""
    text = lowered.as_text(debug_info=True)
    return {s for s in tracing.SCOPES
            if re.search(r'loc\("[^"]*/%s/' % s, text)}


@pytest.mark.parametrize("kind,refresh,expected", [
    (None, 1, SELECT | TRAIN),
    (None, 20, SELECT | TRAIN),
    ("subset", 20, TRAIN),
    ("select", 20, SELECT),
    ("baseline", None, TRAIN),
])
def test_step_carries_its_scopes(kind, refresh, expected):
    over = ["graft=none"] if refresh is None else \
        [f"graft.refresh_every={refresh}"]
    mcfg, tcfg, state, batch = _abstract(over)
    step = steps_lib.make_train_step(mcfg, tcfg, kind)
    assert _scopes_in(jax.jit(step).lower(state, batch)) == expected


def test_overlapped_selector_carries_scopes():
    """Under ``graft.overlap`` the refresh and the subset step are two
    programs; each carries its own phases."""
    from repro.selection.overlap import OverlappedSelector
    mcfg, tcfg, state, batch = _abstract(["graft.refresh_every=20",
                                          "graft.overlap=true"])
    sel = OverlappedSelector(mcfg, tcfg, donate=False)
    refresh = sel._refresh.lower(state["params"], batch,
                                 state["sampler_carry"], jnp.int32(0))
    assert _scopes_in(refresh) == SELECT
    assert _scopes_in(sel._train.lower(state, batch)) == TRAIN


def test_remat_model_splits_into_forward_backward_recompute():
    """On a two-layer remat model every phase of the compiled GRAFT step
    holds operations, the gradient's three among them."""
    from chipbench import scopes
    mcfg, tcfg, state, batch = _abstract(
        ["graft.refresh_every=1",
         'model.overrides={"remat": "full", "num_layers": 2}'])
    assert mcfg.remat == "full" and mcfg.num_layers == 2
    text = jax.jit(steps_lib.make_train_step(mcfg, tcfg)).lower(
        state, batch).compile().as_text()
    phases = collections.Counter(
        scopes.classify(n) for n in re.findall(r'op_name="([^"]*)"', text))
    for phase in ("select_forward", "select_decide", "forward", "backward",
                  "recompute", "update"):
        assert phases[phase] > 0, phases


@pytest.mark.parametrize("path,phase", [
    ("jit(fn)/train_grad/jvp()/while/body/dot_general", "forward"),
    ("jit(fn)/train_grad/transpose(jvp())/while/body/dot_general",
     "backward"),
    ("jit(fn)/train_grad/transpose(jvp())/while/body/checkpoint/"
     "rematted_computation/dot_general", "recompute"),
    ("jit(fn)/cond/branch_1_fun/select_forward/dot_general",
     "select_forward"),
    ("jit(fn)/select_decide/pallas_call", "select_decide"),
    ("jit(fn)/sentinel/select_n", "update"),
    ("jit(fn)/add", "unscoped"),
])
def test_classify(path, phase):
    from chipbench import scopes
    assert scopes.classify(path) == phase


@pytest.fixture(scope="module")
def traced_fit(tmp_path_factory):
    """A smoke run of ``Trainer.fit`` under a profiler trace."""
    from repro.api import Trainer
    out = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(out):
        report = Trainer(_experiment(["graft.refresh_every=2"])).fit()
    return report, out


def test_fit_reports_spans_once_a_step(traced_fit):
    report, _ = traced_fit
    spans = report["spans"]["spans"]
    for name in (tracing.DATA_WAIT, tracing.DISPATCH, tracing.CALLBACKS,
                 tracing.STAGE):
        assert spans[name]["count"] == 3, (name, spans[name])
    for name in (tracing.SETUP_BACKEND, tracing.SETUP_BUILD,
                 tracing.SETUP_INIT, tracing.DRAIN):
        assert spans[name]["count"] == 1, name
    # the first dispatch traces and compiles the step; the others enqueue
    first = spans[tracing.DISPATCH]["first_s"]
    assert first > (spans[tracing.DISPATCH]["total_s"] - first)
    assert report["spans"]["compile"][tracing.BACKEND_COMPILE]["count"] >= 1


def test_fit_spans_reach_the_profiler_trace(traced_fit):
    import glob
    _, out = traced_fit
    names = collections.Counter()
    for path in glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                          recursive=True):
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            if plane.name.startswith("/host:CPU"):
                for line in plane.lines:
                    names.update(ev.name for ev in line.events)
    for name in (tracing.DATA_WAIT, tracing.DISPATCH, tracing.CALLBACKS,
                 tracing.STEP):
        assert names[name] == 3, (name, names[name])


@pytest.mark.parametrize("refresh", [1, 2])
def test_no_compile_after_step_zero(refresh):
    """The initial state is laid out as the step returns it, so step 0
    compiles the only program the loop runs."""
    from repro.api import Trainer
    report = Trainer(_experiment([f"graft.refresh_every={refresh}",
                                  "train.steps=4"])).fit()
    assert report["spans"]["late_compiles"] == []
    assert report["spans"]["spans"][tracing.DISPATCH]["count"] == 4


def test_span_table_counts_and_first():
    table = tracing.SpanTable()
    for _ in range(3):
        with table.span("x"):
            pass
    row = table.summary()["spans"]["x"]
    assert row["count"] == 3 and 0 <= row["first_s"] <= row["total_s"]
    assert table.first("x") == row["first_s"] and table.first("y") is None
    table.reset()
    assert table.summary()["spans"] == {}


def test_graft_flops_follow_rank():
    """A GRAFT row's required FLOPs follow the rank it trained, the
    selection forward is counted on refresh steps only, and remat's
    recompute never."""
    from repro import configs
    from repro.launch.metrics import graft_step_flops, train_step_flops
    from repro.models import model as M
    mcfg = configs.get_smoke_config("minicpm-2b")
    params = jax.eval_shape(lambda k: M.init_params(mcfg, k),
                            jax.random.PRNGKey(0))
    n = sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))

    def count(rank, refresh=True):
        return graft_step_flops(n, mcfg, 16, 64, 16, rank, refresh=refresh)

    assert count(2) < count(8)
    assert count(8, refresh=False) < count(8)
    assert count(8) - count(8, refresh=False) == \
        count(2) - count(2, refresh=False)
    # all 16 rows trained without selection: the full step without remat
    full = train_step_flops(n, 16 * 64, remat=False, mcfg=mcfg, seq=64)
    assert count(16, refresh=False) == pytest.approx(
        full - 2.0 * 3 * mcfg.vocab_size * mcfg.d_model * 16 * 64
        * (0 if mcfg.tie_embeddings else 1), rel=1e-9)


def test_metrics_logger_counts_graft_rows_by_rank(tmp_path):
    from repro.launch.metrics import MetricsLogger, read_metrics
    path = str(tmp_path / "m.jsonl")
    lg = MetricsLogger(path, num_chips=1, flops_per_step=1e15,
                       peak_flops_per_chip=197e12,
                       row_flops=lambda row: 1e9 * row["rank"])
    lg.log(0, {"loss": 1.0, "rank": 2.0}, tokens=100, step_time=0.01)
    lg.log(1, {"loss": 1.0, "rank": 8.0}, tokens=100, step_time=0.01)
    lg.close()
    rows = read_metrics(path)
    assert rows[0]["mfu"] < rows[1]["mfu"]
    assert rows[0]["mfu"] == pytest.approx(2e9 / (0.01 * 197e12))
    assert rows[1]["mfu_source"] == "dispatch"


def test_metrics_stream_mfu_follows_graft_rank(tmp_path, monkeypatch):
    """The trainer's JSONL stream counts each GRAFT row from its own rank
    (``graft_step_flops``), not 6·N over all K rows plus remat."""
    from repro.api import Trainer
    from repro.api import callbacks as cb_lib
    from repro.launch.metrics import graft_step_flops, read_metrics
    monkeypatch.setattr(cb_lib, "peak_flops", lambda kind: 197e12)
    path = str(tmp_path / "m.jsonl")
    tr = Trainer(_experiment(["graft.refresh_every=2",
                              f"train.metrics_path={path}"]))
    tr.fit()
    rows = read_metrics(path)
    assert len(rows) == 3
    for row in rows:
        want = graft_step_flops(tr.num_params, tr.mcfg, 8, 16,
                                tr.tcfg.probe_positions, int(row["rank"]),
                                refresh=row["step"] % 2 == 0)
        dt = row.get("device_step_time_s", row["step_time_s"])
        assert row["mfu"] * dt * 197e12 == pytest.approx(want, rel=1e-9)
