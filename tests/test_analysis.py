"""repro.analysis: each checker must catch its deliberately-broken fixture
with the right rule id, and the real Trainer probe config must pass clean.
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import jaxpr_audit, lint, vmem
from repro.analysis.recompile import RecompileWatcher
from repro.analysis.report import RULES, Finding, Report, rule_table
from repro.analysis.sync_guard import (SyncGuard, SyncGuardError,
                                       sync_allowed)


# ---------------------------------------------------------------------------
# report format
# ---------------------------------------------------------------------------

def test_finding_defaults_severity_from_registry():
    f = Finding(rule="VM003", location="x", message="m")
    assert f.severity == "info"
    assert Finding(rule="JX001", location="x", message="m").severity == "error"


def test_report_accounting_and_json():
    r = Report([Finding(rule="JX001", location="a", message="bad"),
                Finding(rule="VM003", location="b", message="note")])
    assert not r.ok and len(r.errors) == 1
    assert r.by_rule("VM003")[0].location == "b"
    assert '"ok": false' in r.to_json()
    assert all(rid in rule_table() for rid in RULES)


# ---------------------------------------------------------------------------
# jaxpr_audit
# ---------------------------------------------------------------------------

def test_count_primitives_recurses_into_pjit():
    def fn(x):
        return jax.jit(lambda y: y * 2)(x) + 1

    counts = jaxpr_audit.count_primitives(fn, jnp.ones(3))
    assert counts.get("mul", 0) >= 1          # found inside the pjit body


def test_forbidden_callback_primitive_flagged():
    def fn(x):
        return jax.pure_callback(
            lambda v: np.asarray(v), jax.ShapeDtypeStruct((3,), jnp.float32),
            x)

    report = jaxpr_audit.audit_step(fn, (jnp.ones(3),), label="fixture")
    assert [f.rule for f in report.errors] == ["JX001"]
    assert "pure_callback" in report.errors[0].message


def test_f64_op_flagged():
    def fn(x):
        return x.astype(jnp.float64) * 2.0

    with jax.enable_x64(True):
        report = jaxpr_audit.audit_dtypes(fn, jnp.ones(3, jnp.float32),
                                          label="fixture")
    assert any(f.rule == "JX002" and f.severity == "error" for f in report)


def test_clean_step_passes():
    report = jaxpr_audit.audit_step(lambda x: x * 2 + 1, (jnp.ones(3),))
    assert report.ok and len(report) == 0


def test_fused_selection_rules_catch_unfused_shape():
    # 0 pallas_call + a gather = the unfused chain → JX003 and JX004
    def unfused(v, idx):
        return jnp.take(v, idx, axis=0)

    report = jaxpr_audit.audit_counts(
        unfused, (jnp.ones((8, 4)), jnp.arange(2)),
        jaxpr_audit.fused_selection_rules(), label="fixture")
    assert {f.rule for f in report.errors} == {"JX003", "JX004"}


def test_monotone_count_rows():
    rows, problems = jaxpr_audit.monotone_count_rows(
        "d", {"pallas_call": 1, "gather": 0}, {"pallas_call": 2, "gather": 0},
        ("pallas_call", "gather"), "count increased")
    assert ("d.pallas_call", 1.0, 2.0, True) in rows
    assert len(problems) == 1 and "d.pallas_call" in problems[0]
    _, ok = jaxpr_audit.monotone_count_rows(
        "d", {"pallas_call": 2}, {"pallas_call": 1}, ("pallas_call",), "w")
    assert ok == []                          # decrease is an improvement


# ---------------------------------------------------------------------------
# sync_guard
# ---------------------------------------------------------------------------

def test_sync_guard_strict_raises_on_float():
    x = jnp.ones(())
    with pytest.raises(SyncGuardError, match="unsanctioned"), \
            SyncGuard(strict=True):
        float(x)


def test_sync_guard_records_and_reports_sy001():
    x = jnp.ones(())
    with SyncGuard() as g:
        float(x)                             # violation
        with sync_allowed("probe"):
            jax.device_get(x)                # sanctioned
    kinds = [(e.kind, e.site) for e in g.events]
    assert ("__float__", None) in kinds and ("device_get", "probe") in kinds
    report = g.report()
    assert [f.rule for f in report.errors] == ["SY001"]
    assert "test_analysis.py" in report.errors[0].location


def test_sync_guard_sanctioned_sites_pass_strict():
    x = jnp.ones(())
    with SyncGuard(strict=True) as g, sync_allowed("flush"):
        jax.block_until_ready(x)
        float(x)
    assert g.violations == [] and len(g.events) == 2


def test_sync_guard_is_thread_local():
    x = jnp.ones(())
    errors = []

    def other_thread():
        try:
            jax.block_until_ready(x)         # unguarded thread: free
        except Exception as e:               # pragma: no cover
            errors.append(e)

    with SyncGuard(strict=True) as g:
        t = threading.Thread(target=other_thread)
        t.start()
        t.join()
    assert errors == [] and g.events == []


def test_sync_guard_restores_patches():
    x = jnp.ones(()) * 3
    orig = jax.block_until_ready
    with SyncGuard():
        assert jax.block_until_ready is not orig
    assert jax.block_until_ready is orig
    assert float(x) == 3.0                   # dunder restored


# ---------------------------------------------------------------------------
# recompile
# ---------------------------------------------------------------------------

def test_recompile_watcher_names_drifting_arg():
    w = RecompileWatcher(label="step")
    assert w.observe(step=0, batch={"x": jnp.ones((8, 16))}) == []
    assert w.observe(step=1, batch={"x": jnp.ones((8, 16))}) == []
    drift = w.observe(step=2, batch={"x": jnp.ones((8, 32))})
    assert [f.rule for f in drift] == ["RC001"]
    assert "batch['x']" in drift[0].message
    assert "float32[8,16]" in drift[0].message
    assert "float32[8,32]" in drift[0].message
    assert not w.ok


def test_recompile_watcher_dtype_and_static_drift():
    w = RecompileWatcher()
    w.observe(x=jnp.ones(3, jnp.float32), n=4)
    drift = w.observe(x=jnp.ones(3, jnp.bfloat16), n=5)
    msgs = " ".join(f.message for f in drift)
    assert "bfloat16" in msgs and "'n'" in msgs


def test_recompile_cache_watch():
    @jax.jit
    def f(x):
        return x * 2

    f(jnp.ones(3))
    f(jnp.ones(5))                           # second specialization
    w = RecompileWatcher(label="probe")
    w.watch("f", f, expected_specializations=1)
    findings = w.check_caches()
    assert [x.rule for x in findings] == ["RC001"]
    assert "2 specializations" in findings[0].message


# ---------------------------------------------------------------------------
# vmem
# ---------------------------------------------------------------------------

def test_vmem_overflow_flagged():
    est = vmem.flash_forward_vmem(T=65536, head_dim=128, block_q=128)
    assert not est.fits
    report = est.report()
    assert [f.rule for f in report.errors] == ["VM001"]


def test_vmem_divisibility_flagged():
    report = vmem.flash_attention_report(S=100, T=64, head_dim=16,
                                         block_q=64, block_k=64)
    assert any(f.rule == "VM002" for f in report.errors)


def test_vmem_formulas_match_kernel_guards():
    # flash forward: K/V and the q/o tiles at their itemsize, double-
    # buffered; f32 s and p with p's two halves; f32 m, l (columns padded to 128 lanes), acc
    T, Dh, bq, bk = 512, 64, 256, 128
    for itemsize in (2, 4):
        assert vmem.flash_forward_vmem(T, Dh, bq, bk, itemsize).total == (
            4 * T * Dh * itemsize + 4 * bq * Dh * itemsize
            + bq * bk * (8 + 2 * itemsize) + 2 * bq * 128 * 4 + bq * Dh * 4)
    # fused selection: graft_select._check_budget's word count, bit-exact
    K, R, d, rank = 256, 32, 1024, 16
    assert vmem.fused_select_vmem(K, R, d, rank).total == \
        (2 * K * R + d * K + 2 * d * rank) * 4
    assert vmem.VMEM_BUDGET_BYTES == 12 * 1024 * 1024


def test_vmem_feasible_agrees_with_attn_router():
    from repro.models import layers as layers_lib

    class Cfg:
        head_dim = 64
        dtype = jnp.dtype(jnp.bfloat16)

    # (2048, 2048) takes a forward tile larger than the backward's 128
    for S, T in ((64, 64), (128, 4096), (128, 65536), (2048, 2048)):
        blocks = layers_lib._flash_blocks(S, T, Cfg.head_dim, 2)
        expect = layers_lib._flash_feasible(Cfg, S, T)
        got = blocks is not None and vmem.flash_feasible(
            S, T, Cfg.head_dim, *blocks[0], itemsize=2)
        assert got == expect, (S, T)
    (fbq, fbk), (bq, bk) = layers_lib._flash_blocks(2048, 2048, 64, 2)
    assert (bq, bk) == (128, 128) and fbq > bq


def test_vmem_headroom_reported():
    report = vmem.fast_maxvol_vmem(1024, 64).report()
    assert report.ok
    assert [f.rule for f in report.findings] == ["VM003"]


# ---------------------------------------------------------------------------
# lint
# ---------------------------------------------------------------------------

_BAD_HOT_PATH = """
import time
import numpy as np
import jax

def f(x):
    t = time.perf_counter()
    return float(x), np.asarray(x), jax.device_get(x)
"""

_BAD_PALLAS = """
from jax.experimental import pallas as pl

def launch(k, x):
    return pl.pallas_call(k)(x)
"""


def test_lint_flags_host_sync_in_hot_path():
    findings = lint.lint_source(_BAD_HOT_PATH, "launch/steps.py")
    rules = sorted(f.rule for f in findings)
    assert rules == ["LN001", "LN001", "LN001", "LN002"]


def test_lint_scopes_rules_by_module():
    # same source in a non-hot-path module: only the wall clock is illegal
    findings = lint.lint_source(_BAD_HOT_PATH, "kernels/somekernel.py")
    assert sorted(f.rule for f in findings) == ["LN002"]
    assert lint.lint_source(_BAD_HOT_PATH, "core/maxvol.py") == []


def test_lint_flags_pallas_call_outside_kernels():
    findings = lint.lint_source(_BAD_PALLAS, "selection/graft.py")
    assert [f.rule for f in findings] == ["LN003"]
    assert lint.lint_source(_BAD_PALLAS, "kernels/mine.py") == []


_BAD_TOPOLOGY = """
import jax
from jax.sharding import Mesh

def f(devices):
    jax.distributed.initialize("127.0.0.1:1", 2, 0)
    m1 = jax.make_mesh((2,), ("data",))
    m2 = Mesh(devices, ("data",))
    return jax.process_index(), jax.process_count()
"""


def test_lint_flags_topology_outside_backend():
    findings = lint.lint_source(_BAD_TOPOLOGY, "api/trainer.py")
    assert [f.rule for f in findings] == ["LN004"] * 5
    # the backend package and the mesh helpers own topology
    assert lint.lint_source(_BAD_TOPOLOGY, "backend/multiprocess.py") == []
    assert lint.lint_source(_BAD_TOPOLOGY, "launch/mesh.py") == []


def test_lint_allow_marker_whitelists_line():
    src = ("import jax\n"
           "def f(x):\n"
           "    # lint: allow drain point\n"
           "    return jax.device_get(x)\n")
    assert lint.lint_source(src, "launch/metrics.py") == []


def test_lint_tree_clean_on_repo():
    report = lint.lint_tree()
    assert report.ok, report.format()


# ---------------------------------------------------------------------------
# trainer integration: the train.audit knob
# ---------------------------------------------------------------------------

def _tiny_cfg(**overrides):
    from repro.api import ExperimentConfig
    pairs = ["train.steps=3", "train.batch=4", "train.seq=16",
             "train.log_every=0", "train.audit=true",
             "graft.rset=[2,4]", "graft.refresh_every=2"]
    pairs += [f"{k}={v}" for k, v in overrides.items()]
    return ExperimentConfig().apply_overrides(pairs)


def test_audit_knob_does_not_change_config_hash():
    from repro.api import ExperimentConfig
    base = ExperimentConfig()
    assert base.config_hash() == \
        base.apply_overrides(["train.audit=true"]).config_hash()


def test_trainer_audit_catches_per_step_sync():
    from repro.api import Trainer
    from repro.api.callbacks import Callback

    class PerStepSync(Callback):
        def on_step_end(self, trainer, step, metrics):
            _ = metrics["loss"]              # float() inside the step loop

    with pytest.raises(SyncGuardError, match="unsanctioned"):
        Trainer(_tiny_cfg(), callbacks=[PerStepSync()]).fit()


def test_trainer_audit_clean_run_reports_sites():
    from repro.api import Trainer
    report = Trainer(_tiny_cfg()).fit()
    audit = report["audit"]
    assert audit["unsanctioned"] == 0
    assert audit["recompiles"] == 0
    assert report["final_loss"] is not None


def test_runner_probe_config_passes_clean(tmp_path):
    """The acceptance criterion: the full probe config (async loop, eval
    side stream, checkpointing, console) under strict audit — clean."""
    from repro.analysis import runner
    report = runner.check_runtime()
    assert report.ok, report.format()
    assert any(f.rule == "SY001" and f.severity == "info"
               for f in report.findings)


def test_runner_rules_flag():
    from repro.analysis import runner
    assert runner.main(["--rules"]) == 0
