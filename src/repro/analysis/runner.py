"""The full analysis battery — what ``python -m repro.analysis`` and the
CI ``analysis`` job run.

Four sections, each returning findings in the shared report format:

  * **lint**   — the AST rules over every module under ``src/repro``;
  * **jaxpr**  — trace the fused selection refresh, the streaming
    (sketch-reservoir) refresh, and the flash-attention model against the
    declarative contracts (1 ``pallas_call`` per fused refresh with no
    gather — streaming included, 1 per attention layer, no host callbacks
    or f64 ops in either step function), plus the SP001 sweep: no
    registered sampler may close over mutable Python state;
  * **vmem**   — static footprint/divisibility for the production kernel
    configurations, with headroom notes;
  * **runtime** — a short REAL ``Trainer.fit`` on the probe config with
    ``train.audit=true``: the strict SyncGuard + RecompileWatcher wrap the
    live step loop; a sync outside a sanctioned site or a step-signature
    drift fails the run. Skippable with ``--no-runtime`` (it trains for a
    few seconds).

Exit code 1 on any error-severity finding.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import jaxpr_audit, lint, vmem
from repro.analysis.report import Finding, Report, rule_table
from repro.analysis.sync_guard import SyncGuardError

# probe shapes — the bench acceptance configs (benchmarks/
# bench_selection_overhead.py), so the CLI audits what the bench measures
_SEL_K, _SEL_D, _SEL_R = 256, 1024, 32
_ATTN_LAYERS, _ATTN_B, _ATTN_S = 2, 4, 64


def check_lint() -> Report:
    return lint.lint_tree()


def check_fused_selection() -> Report:
    """PR 3's contract on the real refresh entry point."""
    from repro.selection import GraftConfig
    from repro.selection import graft as graft_lib

    rng = np.random.default_rng(0)
    cfg = GraftConfig(rset=(8, 16, 32), eps=0.25, use_pallas=True)
    V = jnp.asarray(rng.normal(size=(_SEL_K, _SEL_R)).astype(np.float32))
    G = jnp.asarray(rng.normal(size=(_SEL_D, _SEL_K)).astype(np.float32))
    g_bar = jnp.mean(G, axis=1)

    def fused(v, g, gb):
        return graft_lib.pivot_and_sweep(cfg, v, g, gb)

    return jaxpr_audit.audit_step(
        fused, (V, G, g_bar), label="fused_selection_refresh",
        extra_rules=jaxpr_audit.fused_selection_rules())


def check_streaming_selection() -> Report:
    """PR 9's contract: the streaming (sketch-reservoir) refresh reuses the
    fused dispatch — ONE ``pallas_call`` for the whole select, and the
    reservoir update adds no gathers beyond the per-batch GRAFT epilogue
    (``select_rank``'s candidate lookup, shared by both paths)."""
    from repro.selection import (CarrySpec, GraftConfig, SelectionInputs,
                                 get_sampler)

    rng = np.random.default_rng(0)
    cfg = GraftConfig(rset=(8, 16, 32), eps=0.25, use_pallas=True,
                      streaming=True)
    V = jnp.asarray(rng.normal(size=(_SEL_K, _SEL_R)).astype(np.float32))
    G = jnp.asarray(rng.normal(size=(_SEL_D, _SEL_K)).astype(np.float32))
    g_bar = jnp.mean(G, axis=1)
    smp = get_sampler("streaming_graft")
    carry = smp.init_carry(cfg, CarrySpec(batch_size=_SEL_K,
                                          grad_dim=_SEL_D))

    def streaming(v, g, gb, c):
        return smp.select_fn(cfg, SelectionInputs(v, g, gb), c, jnp.int32(0))

    def per_batch(v, g, gb):
        return get_sampler("graft").fn(cfg, SelectionInputs(v, g, gb),
                                       jnp.int32(0))

    gather_budget = jaxpr_audit.count_primitives(
        per_batch, V, G, g_bar).get("gather", 0)
    rules = [
        jaxpr_audit.PrimitiveRule(
            "pallas_call", exact=1, rule="JX003",
            why="the streaming refresh (sketch update + blended-target "
                "select) must stay a single fused kernel launch",
            fix_hint="keep streaming_select_fn on graft.pivot_and_sweep — "
                     "do not add a second dispatch for the reservoir"),
        jaxpr_audit.PrimitiveRule(
            "gather", max_count=gather_budget, rule="JX004",
            why=f"the reservoir update must add no gathers over the "
                f"per-batch GRAFT select (budget {gather_budget} from the "
                f"shared rank-decision epilogue)",
            fix_hint="express the FD sketch update with slices/matmuls, "
                     "not fancy indexing"),
    ]
    return jaxpr_audit.audit_step(
        streaming, (V, G, g_bar, carry), label="streaming_selection_refresh",
        extra_rules=rules)


def check_sampler_closures() -> Report:
    """SP001: no registered sampler may smuggle cross-step state through a
    closed-over mutable (list/dict/set/bytearray) — under jit it would be
    baked at trace time, and rollback/resume could never restore it. The
    Sampler-v2 carry is the only sanctioned channel."""
    from repro.selection import available, get_sampler

    mutable = (list, dict, set, bytearray)
    report = Report()
    for name in available():
        smp = get_sampler(name)
        for attr in ("fn", "select_fn", "init_carry_fn"):
            fn = getattr(smp, attr)
            cells = getattr(fn, "__closure__", None) or ()
            for cell in cells:
                try:
                    value = cell.cell_contents
                except ValueError:       # empty cell
                    continue
                if isinstance(value, mutable):
                    report.add(Finding(
                        rule="SP001", location=f"sampler '{name}'.{attr}",
                        message=f"closes over mutable "
                                f"{type(value).__name__}: {value!r:.80}",
                        fix_hint="thread the state through init_carry_fn/"
                                 "select_fn (Sampler-v2 carry) so it rides "
                                 "the train state and checkpoints"))
    if report.ok:
        report.add(Finding(
            rule="SP001", severity="info", location="selection.registry",
            message=f"no mutable closures across "
                    f"{len(available())} registered samplers"))
    return report


def check_attention() -> Report:
    """PR 6's contract on the bench probe model: one launch per layer in
    the forward, and a callback/f64-free train step."""
    from repro.models import model as model_lib

    rng = np.random.default_rng(0)
    mcfg = model_lib.ModelConfig(
        family="dense", num_layers=_ATTN_LAYERS, d_model=64, num_heads=4,
        num_kv_heads=2, head_dim=16, d_ff=128, vocab_size=256,
        param_dtype="float32", scan_layers=False, attn_backend="flash")
    params = model_lib.init_params(mcfg, jax.random.PRNGKey(0))
    batch = {
        "tokens": jnp.asarray(rng.integers(
            0, 256, (_ATTN_B, _ATTN_S)).astype(np.int32)),
        "labels": jnp.asarray(rng.integers(
            0, 256, (_ATTN_B, _ATTN_S)).astype(np.int32)),
    }

    def fwd(p, b):
        return model_lib.loss_fn(mcfg, p, b)[0]

    def step(p, b):
        return jax.grad(lambda pp: model_lib.loss_fn(mcfg, pp, b)[0])(p)

    report = jaxpr_audit.audit_step(
        fwd, (params, batch), label="flash_forward",
        extra_rules=jaxpr_audit.attention_rules(_ATTN_LAYERS))
    report.extend(jaxpr_audit.audit_step(
        step, (params, batch), label="flash_train_step"))
    return report


def check_vmem() -> Report:
    """The production kernel configurations + headroom notes (the
    blockwise-KV groundwork: how far T can grow before flash must tile)."""
    from repro.models import layers as layers_lib

    report = Report()
    # probe model shape, and a TPU-production shape for the headroom note
    report.extend(vmem.flash_attention_report(
        S=_ATTN_S, T=_ATTN_S, head_dim=16, block_q=64, block_k=64,
        itemsize=4))
    (fbq, fbk), _ = layers_lib._flash_blocks(2048, 2048, 64, 2)
    report.extend(vmem.flash_attention_report(
        S=2048, T=2048, head_dim=64, block_q=fbq, block_k=fbk))
    report.extend(vmem.fused_select_vmem(
        _SEL_K, _SEL_R, _SEL_D, _SEL_R).report())
    report.extend(vmem.fast_maxvol_vmem(1024, 64).report())
    return report


def probe_overrides(tmpdir: str) -> List[str]:
    """The host-stall probe config (bench's async-loop gate) with the
    audit knob on — the clean-pass configuration CI certifies."""
    return [
        "train.steps=8", "train.batch=8", "train.seq=16",
        "train.log_every=4", "train.eval_every=4",
        f"train.metrics_path={tmpdir}/metrics.jsonl",
        "train.metrics_flush_every=4",
        f"train.checkpoint_dir={tmpdir}/ckpt", "train.checkpoint_every=4",
        "graft.rset=[2,4]", "graft.refresh_every=3", "graft.overlap=true",
        "train.audit=true",
    ]


def check_runtime(overrides: Sequence[str] = ()) -> Report:
    """Run the REAL Trainer under ``train.audit`` on the probe config."""
    import tempfile

    from repro.api import ExperimentConfig, Trainer

    report = Report()
    with tempfile.TemporaryDirectory() as td:
        cfg = ExperimentConfig().apply_overrides(
            probe_overrides(td) + list(overrides))
        try:
            run_report = Trainer(cfg).fit()
        except SyncGuardError as e:
            report.add(Finding(
                rule="SY001", location="train.audit", message=str(e)))
            return report
        except RuntimeError as e:
            if "[train.audit]" not in str(e):
                raise
            report.add(Finding(
                rule="RC001", location="train.audit", message=str(e)))
            return report
    audit = run_report.get("audit", {})
    sites = ", ".join(f"{k}={v}" for k, v
                      in audit.get("sync_sites", {}).items()) or "none"
    report.add(Finding(
        rule="SY001", severity="info", location="train.audit",
        message=f"clean audited run: {audit.get('sync_events', 0)} "
                f"sanctioned sync(s) [{sites}], 0 unsanctioned, "
                f"{audit.get('recompiles', 0)} re-trace(s)"))
    return report


def run_all(runtime: bool = True,
            overrides: Sequence[str] = ()) -> Report:
    report = Report()
    report.extend(check_lint())
    report.extend(check_fused_selection())
    report.extend(check_streaming_selection())
    report.extend(check_sampler_closures())
    report.extend(check_attention())
    report.extend(check_vmem())
    if runtime:
        report.extend(check_runtime(overrides))
    return report


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="static + runtime audit of the training hot-path "
                    "contracts (lint, jaxpr, VMEM, sync/recompile)")
    ap.add_argument("--json", nargs="?", const="-", default=None,
                    help="emit the report as JSON (to PATH, or stdout "
                         "with no argument)")
    ap.add_argument("--rules", action="store_true",
                    help="print the rule table and exit")
    ap.add_argument("--no-runtime", action="store_true",
                    help="skip the audited Trainer probe run")
    ap.add_argument("--quiet", action="store_true",
                    help="hide info-severity findings")
    ap.add_argument("--set", action="append", default=[], metavar="KEY=VAL",
                    dest="overrides",
                    help="extra ExperimentConfig override for the runtime "
                         "probe (repeatable)")
    args = ap.parse_args(argv)
    if args.rules:
        print(rule_table())
        return 0
    report = run_all(runtime=not args.no_runtime, overrides=args.overrides)
    if args.json:
        blob = report.to_json(indent=1)
        if args.json == "-":
            print(blob)
        else:
            with open(args.json, "w") as f:
                f.write(blob + "\n")
    print(report.format(show_info=not args.quiet))
    return 0 if report.ok else 1


if __name__ == "__main__":
    sys.exit(main())
