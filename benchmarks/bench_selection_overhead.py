"""Paper Table 7 / §3.3: empirical complexity of the selection machinery —
Fast MaxVol must scale O(K·R²), the projection sweep O(R·d); wall-clock and
compiled-FLOP scaling are both reported, plus a sweep of every registered
sampler through the selection engine on identical inputs.

This suite is also the repo's perf gate for the selection hot path:

  * dispatch accounting — the fused Pallas refresh
    (``kernels/graft_select.py``) must trace to ONE ``pallas_call`` (and no
    gather), vs 2 ``pallas_call`` + 1 gather for the unfused chain, and the
    batched variant must keep ONE launch for a whole microbatch stack;
  * ``sketch_svd`` vs ``svd`` compiled FLOPs at K=1024, M=4096, R=64 — the
    sketch path must win by ≥ 5×;
  * host-stall accounting — the async train loop (deferred ``MetricsFuture``
    drain + side-stream eval) must keep dispatching ahead of the device
    queue: under ``graft.overlap`` with ``eval_every`` set, step N+1 is
    issued while step N's metrics are still device futures. The counter is
    deterministic for a fixed config (materialization happens only at flush
    boundaries), so it is gated like the dispatch counts; ``blocked_ms`` is
    wall clock and recorded but not gated. The same probe now also gates
    DeviceClock coverage (every step but the first gets a device-time
    stamp) and device-sourced ``mfu`` in the flushed metrics;
  * attention hot path — with ``attn_backend=flash`` the model forward must
    trace to exactly ONE ``pallas_call`` per layer (layers unrolled so the
    count is per-layer, not per scan body); compiled train-step FLOPs
    (flash vs dense jnp path) and the analytic ``train_step_flops``
    estimate ride along for the regression diff.

Run standalone to emit machine-readable results (tracked across PRs by the
``perf-smoke`` CI job)::

    PYTHONPATH=src:. python benchmarks/bench_selection_overhead.py \
        --quick --json BENCH_selection.json
"""
from __future__ import annotations

import argparse
import json
import os
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import csv_row, time_call
from repro.analysis import jaxpr_audit
from repro.core.features import sketch_svd_features, svd_features
from repro.core.maxvol import fast_maxvol
from repro.core.projection import prefix_projection_errors
from repro.kernels import ops as kernel_ops
from repro.selection import GraftConfig, engine, registry
from repro.selection import graft as graft_lib

_DEFAULT_JSON = os.path.join(os.path.dirname(__file__), "..",
                             "BENCH_selection.json")

# acceptance configs shared by collect() and the --check gate
_B = 8                                   # batched-fused microbatch stack
_KF, _MF, _RF = 1024, 4096, 64           # feature-path FLOPs comparison
_MIN_FLOPS_RATIO = 5.0                   # sketch_svd must beat svd by this


def _flops(fn, *args) -> float:
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled.cost_analysis().get("flops", 0.0)


# jaxpr walking lives in repro.analysis.jaxpr_audit — one implementation
# feeding the bench entries, the regression gate, and `python -m
# repro.analysis`, so measured and gated counts cannot drift apart
_count_primitives = jaxpr_audit.count_primitives
_dispatch_entry = jaxpr_audit.dispatch_summary


_HOST_STALL_STEPS = 12                   # async-loop probe config (must stay
_HOST_STALL_FLUSH = 4                    # fixed: the gate is deterministic
                                         # only for a fixed cadence)


def _host_stall_entry() -> Dict[str, Any]:
    """Drive the REAL async Trainer loop (overlap + side-stream eval +
    deferred metrics) and report the dispatch-ahead depth: how many steps
    were issued while the previous step's metrics were still device
    futures. Drains happen only at metrics flush boundaries, so for this
    fixed config the counter is deterministic (steps − flush drains − 1).
    Also reports the DeviceClock coverage: every step but the first must
    get a device-time stamp, and the JSONL ``mfu`` must be device-sourced."""
    import tempfile

    from repro.api import ExperimentConfig, Trainer
    from repro.launch.metrics import read_metrics

    with tempfile.TemporaryDirectory() as td:
        cfg = ExperimentConfig().apply_overrides([
            f"train.steps={_HOST_STALL_STEPS}", "train.batch=8",
            "train.seq=16", "train.log_every=0", "train.eval_every=4",
            f"train.metrics_path={td}/m.jsonl",
            f"train.metrics_flush_every={_HOST_STALL_FLUSH}",
            "graft.rset=[2,4]", "graft.refresh_every=3",
            "graft.overlap=true",
        ])
        report = Trainer(cfg).fit()
        mrows = read_metrics(f"{td}/m.jsonl")
    h = report["host_loop"]
    dev_rows = [r for r in mrows if r.get("mfu_source") == "device"]
    return {
        "steps": h["steps"],
        "dispatch_ahead_steps": h["dispatched_ahead"],
        "blocked_ms_per_step": (1e3 * h.get("metrics_drain_s", 0.0)
                                / max(h["steps"], 1)),
        "device_timed_steps": h.get("device_timed_steps", 0),
        "device_time_s": h.get("device_time_s", 0.0),
        "mfu_source": "device" if dev_rows else "dispatch",
        "mfu_device_rows": len(dev_rows),
        "mfu": dev_rows[-1]["mfu"] if dev_rows else None,
    }


_ATTN_LAYERS = 2                         # attention-gate probe model (fixed:
_ATTN_B, _ATTN_S = 4, 64                 # dispatch counts are exact gates)


def _attention_entry() -> Dict[str, Any]:
    """Model-hot-path accounting for ``attn_backend=flash``: the forward
    jaxpr must dispatch exactly ONE ``pallas_call`` per layer (the layers
    are unrolled here so per-layer really means per layer, not per scan
    body), and the compiled train-step FLOPs ride along for the regression
    diff (flash vs the dense jnp path on the same shapes)."""
    from repro.launch.metrics import train_step_flops
    from repro.models import model as model_lib

    rng = np.random.default_rng(0)

    def mk(backend: str):
        return model_lib.ModelConfig(
            family="dense", num_layers=_ATTN_LAYERS, d_model=64, num_heads=4,
            num_kv_heads=2, head_dim=16, d_ff=128, vocab_size=256,
            param_dtype="float32", scan_layers=False, attn_backend=backend)

    cfg_f, cfg_d = mk("flash"), mk("dense")
    params = model_lib.init_params(cfg_f, jax.random.PRNGKey(0))
    batch = {
        "tokens": jnp.asarray(
            rng.integers(0, 256, (_ATTN_B, _ATTN_S)).astype(np.int32)),
        "labels": jnp.asarray(
            rng.integers(0, 256, (_ATTN_B, _ATTN_S)).astype(np.int32)),
    }

    def fwd(p, b):
        return model_lib.loss_fn(cfg_f, p, b)[0]

    def step(cfg):
        def f(p, b):
            return jax.grad(lambda pp: model_lib.loss_fn(cfg, pp, b)[0])(p)
        return f

    fwd_counts = _count_primitives(fwd, params, batch)
    step_counts = _count_primitives(step(cfg_f), params, batch)
    num_params = sum(int(np.prod(l.shape))
                     for l in jax.tree_util.tree_leaves(params))
    tokens = _ATTN_B * _ATTN_S
    return {
        "layers": _ATTN_LAYERS,
        "forward_pallas_call": fwd_counts.get("pallas_call", 0),
        "train_step_pallas_call": step_counts.get("pallas_call", 0),
        "train_step_flops": {
            "flash": _flops(step(cfg_f), params, batch),
            "dense": _flops(step(cfg_d), params, batch),
        },
        "analytic_train_flops": {
            "param_only": train_step_flops(num_params, tokens),
            "with_attention": train_step_flops(num_params, tokens,
                                               mcfg=cfg_f, seq=_ATTN_S),
        },
    }


def collect(quick: bool = False) -> Tuple[List[str], Dict[str, Any]]:
    rng = np.random.default_rng(0)
    rows: List[str] = []
    report: Dict[str, Any] = {
        "meta": {"backend": jax.default_backend(), "quick": quick,
                 "interpret_mode": jax.default_backend() != "tpu"},
    }
    repeats = 5 if quick else 20
    warmup = 1 if quick else 3

    def timed(fn, *args):
        return time_call(fn, *args, repeats=repeats, warmup=warmup)

    # ------------------------------------------------------------------
    # dispatch accounting: fused refresh vs the unfused 3-op chain
    # ------------------------------------------------------------------
    K, d, R = 256, 1024, 32
    cfg_p = GraftConfig(rset=(8, 16, 32), eps=0.25, use_pallas=True)
    V = jnp.asarray(rng.normal(size=(K, R)).astype(np.float32))
    G = jnp.asarray(rng.normal(size=(d, K)).astype(np.float32))
    g_bar = jnp.mean(G, axis=1)

    def fused(v, g, gb):
        return graft_lib.pivot_and_sweep(cfg_p, v, g, gb)

    def unfused(v, g, gb):
        piv = kernel_ops.fast_maxvol(v, cfg_p.r_max)
        g_sel = jnp.take(g, piv, axis=1)
        return piv, kernel_ops.projection_sweep(g_sel, gb), g_sel

    B = _B
    Vs = jnp.asarray(rng.normal(size=(B, K, R)).astype(np.float32))
    Gs = jnp.asarray(rng.normal(size=(B, d, K)).astype(np.float32))
    gbs = jnp.mean(Gs, axis=2)

    def batched_fused(vs, gs, gbss):
        # the refresh chain for a whole stack (apples-to-apples with
        # fused/unfused above, which also exclude the rank-decision epilogue)
        return kernel_ops.fused_graft_select_batched(vs, gs, gbss,
                                                     cfg_p.r_max)

    report["dispatch_per_refresh"] = {
        "fused": _dispatch_entry(_count_primitives(fused, V, G, g_bar)),
        "unfused": _dispatch_entry(_count_primitives(unfused, V, G, g_bar)),
        f"batched_fused_B{B}": _dispatch_entry(
            _count_primitives(batched_fused, Vs, Gs, gbs)),
    }
    report["refresh_wall_us"] = {
        "fused": timed(jax.jit(fused), V, G, g_bar),
        "unfused": timed(jax.jit(unfused), V, G, g_bar),
    }
    for name, entry in report["dispatch_per_refresh"].items():
        rows.append(csv_row(
            f"dispatch_{name}", 0.0,
            f"pallas_calls={entry['pallas_call']};gathers={entry['gather']}"))

    # ------------------------------------------------------------------
    # feature path: sketch_svd vs svd at the acceptance config
    # ------------------------------------------------------------------
    Kf, Mf, Rf = _KF, _MF, _RF
    A = jnp.asarray(rng.normal(size=(Kf, Mf)).astype(np.float32))
    feats: Dict[str, Any] = {}
    for name, fn in (("svd", lambda a: svd_features(a, Rf)),
                     ("sketch_svd", lambda a: sketch_svd_features(a, Rf))):
        f = _flops(fn, A)
        t = timed(jax.jit(fn), A)
        feats[name] = {"flops": f, "wall_us": t}
        rows.append(csv_row(f"features_{name}_K{Kf}_M{Mf}_R{Rf}", t,
                            f"flops={f:.3e}"))
    feats["flops_ratio"] = (feats["svd"]["flops"] /
                            max(feats["sketch_svd"]["flops"], 1.0))
    report[f"features_K{Kf}_M{Mf}_R{Rf}"] = feats
    rows.append(csv_row("features_flops_ratio", 0.0,
                        f"svd/sketch_svd={feats['flops_ratio']:.2f}"))

    # ------------------------------------------------------------------
    # scaling: K at fixed R (expect ~linear), R at fixed K (~quadratic),
    # projection sweep d (~linear)
    # ------------------------------------------------------------------
    scaling: List[Dict[str, Any]] = []
    R_ = 16
    for K_ in (128, 256, 512, 1024):
        Vk = jnp.asarray(rng.normal(size=(K_, R_)).astype(np.float32))
        t = timed(jax.jit(lambda v: fast_maxvol(v, R_)), Vk)
        f = _flops(lambda v: fast_maxvol(v, R_), Vk)
        scaling.append({"name": f"maxvol_K{K_}_R{R_}", "wall_us": t,
                        "flops": f})
        rows.append(csv_row(f"maxvol_K{K_}_R{R_}", t, f"flops={f:.3e}"))

    K_ = 512
    for Rv in (8, 16, 32, 64):
        Vk = jnp.asarray(rng.normal(size=(K_, Rv)).astype(np.float32))
        t = timed(jax.jit(lambda v, r=Rv: fast_maxvol(v, r)), Vk)
        f = _flops(lambda v, r=Rv: fast_maxvol(v, r), Vk)
        scaling.append({"name": f"maxvol_K{K_}_R{Rv}", "wall_us": t,
                        "flops": f})
        rows.append(csv_row(f"maxvol_K{K_}_R{Rv}", t, f"flops={f:.3e}"))

    Rv = 32
    for dv in (256, 1024, 4096):
        Gd = jnp.asarray(rng.normal(size=(dv, Rv)).astype(np.float32))
        gd = jnp.asarray(rng.normal(size=(dv,)).astype(np.float32))
        t = timed(jax.jit(prefix_projection_errors), Gd, gd)
        f = _flops(prefix_projection_errors, Gd, gd)
        scaling.append({"name": f"projsweep_d{dv}_R{Rv}", "wall_us": t,
                        "flops": f})
        rows.append(csv_row(f"projsweep_d{dv}_R{Rv}", t, f"flops={f:.3e}"))
    report["scaling"] = scaling

    # ------------------------------------------------------------------
    # host-stall: the async train loop must run ahead of the device queue
    # ------------------------------------------------------------------
    stall = _host_stall_entry()
    report["host_stall"] = stall
    rows.append(csv_row(
        "host_stall", stall["blocked_ms_per_step"] * 1e3,
        f"dispatch_ahead={stall['dispatch_ahead_steps']}/{stall['steps']}"
        f";blocked_ms_per_step={stall['blocked_ms_per_step']:.3f}"))

    # ------------------------------------------------------------------
    # model hot path: flash attention dispatch + train-step FLOPs
    # ------------------------------------------------------------------
    attn = _attention_entry()
    report["attention"] = attn
    rows.append(csv_row(
        "attention_dispatch", 0.0,
        f"forward_pallas_calls={attn['forward_pallas_call']}"
        f"/{attn['layers']}layers"
        f";train_step_pallas_calls={attn['train_step_pallas_call']}"))
    rows.append(csv_row(
        "attention_train_flops", 0.0,
        f"flash={attn['train_step_flops']['flash']:.3e}"
        f";dense={attn['train_step_flops']['dense']:.3e}"
        f";analytic={attn['analytic_train_flops']['with_attention']:.3e}"))

    # ------------------------------------------------------------------
    # every registered sampler through the engine on identical inputs
    # ------------------------------------------------------------------
    K, dv, Rv = 256, 1024, 32
    cfg = GraftConfig(rset=(8, 16, 32), eps=0.25)
    V = jnp.asarray(rng.normal(size=(K, Rv)).astype(np.float32))
    G = jnp.asarray(rng.normal(size=(dv, K)).astype(np.float32))
    g_bar = jnp.mean(G, axis=1)
    scores = jnp.asarray(rng.random(K).astype(np.float32))
    samplers: Dict[str, float] = {}
    for name in registry.available():
        def call(v, g, gb, sc, n=name):
            return engine.select_batch(cfg, n, v, g, gb, scores=sc)
        t = timed(call, V, G, g_bar, scores)
        samplers[name] = t
        rows.append(csv_row(f"sampler_{name}_K{K}_d{dv}", t, "registry-engine"))
    report["samplers_wall_us"] = samplers

    # ------------------------------------------------------------------
    # streaming reservoir: the cross-batch sketch refresh must keep the
    # single-dispatch contract (ONE pallas_call, no extra gathers) and its
    # reservoir-update overhead (FD eigh + EMA blend) is tracked as a
    # compiled-FLOPs delta over the per-batch GRAFT refresh
    # ------------------------------------------------------------------
    from repro.selection import CarrySpec, SelectionInputs

    smp_stream = registry.get_sampler("streaming_graft")
    smp_graft = registry.get_sampler("graft")
    cfg_sp = GraftConfig(rset=(8, 16, 32), eps=0.25, use_pallas=True,
                         streaming=True)
    carry0 = smp_stream.init_carry(cfg_sp,
                                   CarrySpec(batch_size=K, grad_dim=dv))

    def stream_refresh(v, g, gb, c):
        return smp_stream.select_fn(cfg_sp, SelectionInputs(v, g, gb), c,
                                    jnp.int32(0))

    def batch_refresh(v, g, gb):
        return smp_graft.fn(cfg_sp, SelectionInputs(v, g, gb), jnp.int32(0))

    stream_disp = _dispatch_entry(
        _count_primitives(stream_refresh, V, G, g_bar, carry0))
    batch_disp = _dispatch_entry(
        _count_primitives(batch_refresh, V, G, g_bar))
    f_stream = _flops(stream_refresh, V, G, g_bar, carry0)
    f_batch = _flops(batch_refresh, V, G, g_bar)
    report["streaming"] = {
        "sketch_rows": cfg_sp.sketch_rows,
        "dispatch": {"streaming": stream_disp, "per_batch": batch_disp},
        "flops": {"streaming": f_stream, "per_batch": f_batch,
                  "reservoir_update": f_stream - f_batch},
        "wall_us": {
            "streaming": timed(jax.jit(stream_refresh), V, G, g_bar, carry0),
            "per_batch": timed(jax.jit(batch_refresh), V, G, g_bar)},
    }
    rows.append(csv_row(
        "streaming_dispatch", 0.0,
        f"pallas_calls={stream_disp['pallas_call']}"
        f";gathers={stream_disp['gather']}"
        f";per_batch_pallas_calls={batch_disp['pallas_call']}"))
    rows.append(csv_row(
        "streaming_reservoir_flops",
        report["streaming"]["wall_us"]["streaming"],
        f"update={f_stream - f_batch:.3e}"
        f";streaming={f_stream:.3e};per_batch={f_batch:.3e}"))

    # derived scaling exponents (log-log slope)
    def slope(prefixes, var_vals):
        ts = [next(e["wall_us"] for e in scaling if e["name"] == p)
              for p in prefixes]
        return float(np.polyfit(np.log(var_vals), np.log(ts), 1)[0])

    k_slope = slope([f"maxvol_K{k}_R16" for k in (128, 256, 512, 1024)],
                    np.asarray([128, 256, 512, 1024]))
    r_slope = slope([f"maxvol_K512_R{r}" for r in (8, 16, 32, 64)],
                    np.asarray([8, 16, 32, 64]))
    report["scaling_exponents"] = {"K_slope": k_slope, "R_slope": r_slope}
    rows.append(csv_row("maxvol_scaling_exponents", 0.0,
                        f"K_slope={k_slope:.2f};R_slope={r_slope:.2f}"))
    return rows, report


def run() -> List[str]:
    rows, _ = collect()
    return rows


def check(report: Dict[str, Any]) -> List[str]:
    """The perf gate: dispatch shape and FLOPs wins that must not regress.
    Returns a list of violations (empty = pass)."""
    problems: List[str] = []
    disp = report["dispatch_per_refresh"]
    if disp["fused"] != {"pallas_call": 1, "gather": 0}:
        problems.append(f"fused refresh is not 1 pallas_call / 0 gathers: "
                        f"{disp['fused']}")
    if disp[f"batched_fused_B{_B}"]["pallas_call"] != 1:
        problems.append(f"batched fused refresh is not ONE launch: "
                        f"{disp[f'batched_fused_B{_B}']}")
    ratio = report[f"features_K{_KF}_M{_MF}_R{_RF}"]["flops_ratio"]
    if ratio < _MIN_FLOPS_RATIO:
        problems.append(f"sketch_svd FLOPs win {ratio:.2f}x < "
                        f"{_MIN_FLOPS_RATIO}x vs svd")
    stall = report["host_stall"]
    if stall["dispatch_ahead_steps"] < 1:
        problems.append(
            "async host loop never dispatched ahead of metrics "
            f"materialization: {stall} — a float()/sync crept back onto "
            "the per-step path")
    if stall.get("device_timed_steps", 0) != stall["steps"] - 1:
        problems.append(
            f"DeviceClock timed {stall.get('device_timed_steps')} steps, "
            f"expected {stall['steps'] - 1} (every step but the first) — "
            "completion stamps are being dropped")
    if stall.get("mfu_source") != "device":
        problems.append(
            f"flushed metrics mfu_source={stall.get('mfu_source')!r}, "
            "expected 'device' — mfu fell back to the dispatch clock")
    stream = report.get("streaming", {})
    sdisp = stream.get("dispatch", {}).get("streaming", {})
    bdisp = stream.get("dispatch", {}).get("per_batch", {})
    if sdisp.get("pallas_call") != 1:
        problems.append(
            f"streaming refresh dispatches {sdisp.get('pallas_call')} "
            "pallas_call — the reservoir update broke the single-dispatch "
            "contract (must stay ONE fused launch)")
    if sdisp.get("gather", 0) > bdisp.get("gather", 0):
        problems.append(
            f"streaming refresh adds gathers over per-batch GRAFT "
            f"({sdisp.get('gather')} vs {bdisp.get('gather')}) — the "
            "sketch update must stay gather-free")
    if stream.get("flops", {}).get("reservoir_update", 0.0) <= 0.0:
        problems.append(
            "streaming reservoir-update FLOPs delta is non-positive — the "
            "bench is no longer measuring the FD update")
    attn = report.get("attention", {})
    if attn.get("forward_pallas_call") != attn.get("layers"):
        problems.append(
            f"flash forward dispatches {attn.get('forward_pallas_call')} "
            f"pallas_call for {attn.get('layers')} layers — must be exactly "
            "one kernel launch per layer")
    return problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fewer timing repeats (CI smoke mode)")
    ap.add_argument("--json", nargs="?", const=_DEFAULT_JSON, default=None,
                    help="write the machine-readable report "
                         "(default: BENCH_selection.json at the repo root)")
    ap.add_argument("--check", action="store_true",
                    help="exit non-zero if the perf gate regresses (fused "
                         "refresh != 1 pallas_call, batched != 1 launch, "
                         f"sketch_svd FLOPs win < {_MIN_FLOPS_RATIO}x, "
                         "the async host loop never dispatches ahead, "
                         "flash attention != 1 pallas_call per layer, or "
                         "DeviceClock coverage/mfu sourcing slips)")
    args = ap.parse_args(argv)
    rows, report = collect(quick=args.quick)
    for r in rows:
        print(r)
    if args.json:
        path = os.path.abspath(args.json)
        with open(path, "w") as f:
            json.dump(report, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"# wrote {path}")
    if args.check:
        problems = check(report)
        for p in problems:
            print(f"# PERF GATE FAILED: {p}")
        return 1 if problems else 0
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
