import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=" +
                           os.environ.get("REPRO_DRYRUN_DEVICES", "512")).strip()
# ^ MUST precede any jax import: jax locks the device count on first init.
#
# Multi-pod dry-run: lower + compile every (arch × input-shape) cell on the
# production meshes with ShapeDtypeStruct stand-ins (zero allocation), print
# memory_analysis (fits 16 GB/chip?) and cost_analysis (roofline terms), and
# parse collective bytes from the compiled HLO. Results are cached per cell
# as JSON under experiments/dryrun/ so the sweep is resumable.
#
# Scan-over-layers keeps compiles O(1) in depth but XLA cost_analysis counts
# the loop body ONCE — so FLOPs/bytes/collective-bytes are measured via an
# L=p vs L=2p UNROLLED delta (p = layer-pattern period) and scaled to the
# full depth: total = c(p) + (L-p)/p · [c(2p) - c(p)]. memory_analysis comes
# from the real full-depth scan compile. See EXPERIMENTS.md §Dry-run.
import argparse
import json
import re
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax

from repro import configs as config_lib
from repro.distributed import sharding as sh
from repro.launch import specs as specs_lib
from repro.launch.mesh import make_mesh, make_production_mesh

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def parse_collective_bytes(hlo_text: str) -> Dict[str, Any]:
    """Sum operand bytes of every collective op in the (optimized) HLO."""
    totals: Dict[str, int] = {c: 0 for c in _COLLECTIVES}
    counts: Dict[str, int] = {c: 0 for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        if "=" not in s:
            continue
        rhs = s.split("=", 1)[1]
        op = None
        for c in _COLLECTIVES:
            # match ` all-reduce(` / `all-reduce-start(` but not fused names
            if re.search(rf"\b{c}(-start)?\(", rhs):
                op = c
                break
        if op is None:
            continue
        # operand shapes = shape literals inside the call parens
        inner = rhs.split("(", 1)[1]
        nbytes = sum(_shape_bytes(d, dims) for d, dims in _SHAPE_RE.findall(inner))
        if nbytes == 0:
            # older syntax: operands without inline shapes — fall back to
            # the result shape (lhs)
            lhs = s.split("=", 1)[0]
            nbytes = sum(_shape_bytes(d, dims) for d, dims in _SHAPE_RE.findall(rhs.split("(")[0]))
        totals[op] += nbytes
        counts[op] += 1
    return {"bytes_by_op": totals, "count_by_op": counts,
            "total_bytes": sum(totals.values()),
            "total_count": sum(counts.values())}


def _memory_dict(compiled) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    try:
        ma = compiled.memory_analysis()
        for attr in ("generated_code_size_in_bytes", "argument_size_in_bytes",
                     "output_size_in_bytes", "temp_size_in_bytes",
                     "alias_size_in_bytes", "host_generated_code_size_in_bytes",
                     "host_argument_size_in_bytes", "host_output_size_in_bytes",
                     "host_temp_size_in_bytes", "host_alias_size_in_bytes"):
            if hasattr(ma, attr):
                out[attr] = int(getattr(ma, attr))
        out["repr"] = str(ma)
    except Exception as e:                                    # pragma: no cover
        out["error"] = repr(e)
    return out


def _shardings_for(cell, mesh, rules):
    def one(abstract, logical):
        if isinstance(logical, tuple) and all(
                isinstance(e, (str, type(None))) for e in logical):
            spec = sh.logical_to_spec(logical, mesh, dict(sh.DEFAULT_RULES, **rules))
            spec = sh.drop_indivisible(spec, abstract.shape, mesh)
            return jax.sharding.NamedSharding(mesh, spec)
        return jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())

    return tuple(
        jax.tree_util.tree_map(one, aa, lg,
                               is_leaf=lambda x: isinstance(x, tuple) and all(
                                   isinstance(e, (str, type(None))) for e in x))
        for aa, lg in zip(cell.abstract_args, cell.arg_logical))


def compile_cell(cell: specs_lib.Cell, mesh) -> Dict[str, Any]:
    rules = cell.rules
    in_shardings = _shardings_for(cell, mesh, rules)
    with sh.sharding_rules(mesh, rules), mesh:
        jitted = jax.jit(cell.step_fn, in_shardings=in_shardings,
                         donate_argnums=cell.donate)
        t0 = time.time()
        lowered = jitted.lower(*cell.abstract_args)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = parse_collective_bytes(hlo)
    result = {
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "cost_analysis_keys": sorted(cost)[:40],
        "collectives": coll,
        "memory": _memory_dict(compiled),
        "lower_s": round(t1 - t0, 2),
        "compile_s": round(t2 - t1, 2),
        "hlo_ops": hlo.count("\n"),
    }
    del compiled, lowered, hlo
    return result


def run_cell(arch: str, shape: str, mesh_kind: str, variant: str,
             with_deltas: bool = True, smoke: bool = False,
             mesh_override=None, rules_preset: str = "default",
             feature_mode: str = "svd", grad_mode: str = "probe",
             data_source: str = "synthetic_lm") -> Dict[str, Any]:
    cfg = config_lib.get_config(arch)
    period = max(len(cfg.layer_pattern), 1) if cfg.layer_pattern else 1
    if cfg.global_layer_indices:
        period = 1            # pattern handled via indices; uniform enough
    period = max(period, 1)
    p1 = period + cfg.first_k_dense
    p2 = 2 * period + cfg.first_k_dense

    if mesh_override is not None:
        mesh = mesh_override
    else:
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))

    rule_overrides = dict(specs_lib.RULE_PRESETS[rules_preset])
    sel_modes = {"feature_mode": feature_mode, "grad_mode": grad_mode}
    if shape.startswith("train"):
        # task workloads only exist for train cells (serve stays LM-shaped)
        sel_modes["data_source"] = data_source
    out: Dict[str, Any] = {
        "arch": arch, "shape": shape, "mesh": mesh_kind,
        "mesh_shape": dict(zip(mesh.axis_names, mesh.devices.shape)),
        "variant": variant, "smoke": smoke, "rules_preset": rules_preset,
        "num_layers": cfg.num_layers, "period": period, **sel_modes,
    }

    # 1) full-depth scan compile — THE dry-run artifact (memory + success)
    cell = specs_lib.build_cell(arch, shape, variant=variant, smoke=smoke,
                                rule_overrides=rule_overrides, **sel_modes)
    out["full"] = compile_cell(cell, mesh)

    # 2) unrolled L=p / L=2p compiles — roofline cost deltas (exact_cost:
    #    chunked attention/CE disabled so no lax.scan hides FLOPs)
    if with_deltas:
        cell1 = specs_lib.build_cell(arch, shape, variant=variant,
                                     num_layers_override=p1,
                                     scan_override=False, smoke=smoke,
                                     exact_cost=True,
                                     rule_overrides=rule_overrides,
                                     **sel_modes)
        cell2 = specs_lib.build_cell(arch, shape, variant=variant,
                                     num_layers_override=p2,
                                     scan_override=False, smoke=smoke,
                                     exact_cost=True,
                                     rule_overrides=rule_overrides,
                                     **sel_modes)
        c1 = compile_cell(cell1, mesh)
        c2 = compile_cell(cell2, mesh)
        out["unrolled_p1"] = c1
        out["unrolled_p2"] = c2
        L_scan = cfg.num_layers - cfg.first_k_dense
        reps = (L_scan - period) / period
        def scaled(key):
            return c1[key] + reps * (c2[key] - c1[key])
        out["scaled"] = {
            "flops": scaled("flops"),
            "bytes_accessed": scaled("bytes_accessed"),
            "collective_bytes": (c1["collectives"]["total_bytes"] + reps *
                                 (c2["collectives"]["total_bytes"] -
                                  c1["collectives"]["total_bytes"])),
        }
    return out


def result_path(arch: str, shape: str, mesh_kind: str, variant: str) -> str:
    os.makedirs(OUT_DIR, exist_ok=True)
    return os.path.join(
        OUT_DIR, f"{arch}__{shape}__{mesh_kind}__{variant}.json")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(specs_lib.SHAPES))
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--variant", default=None,
                    help="train cells: graft|baseline (default: both)")
    ap.add_argument("--all", action="store_true", help="sweep every cell")
    ap.add_argument("--no-deltas", action="store_true",
                    help="skip the unrolled L1/L2 roofline compiles")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced configs (CI)")
    ap.add_argument("--feature-mode", default="svd",
                    help="selection feature extractor for graft cells "
                         "(repro.selection.sources registry)")
    ap.add_argument("--grad-mode", default="probe",
                    help="selection gradient source for graft cells")
    ap.add_argument("--data-source", default="synthetic_lm",
                    help="task/data-source registry name for train cells "
                         "(repro.data.sources registry)")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args(argv)

    cells = []
    for arch, shape in specs_lib.all_cells():
        if args.arch and arch != args.arch:
            continue
        if args.shape and shape != args.shape:
            continue
        ok, why = specs_lib.cell_is_supported(arch, shape)
        if not ok:
            if args.list:
                print(f"SKIP {arch:24s} {shape:12s} — {why}")
            continue
        if shape == "train_4k":
            variants = [args.variant] if args.variant else ["graft", "baseline"]
        else:
            variants = ["serve"]
        for v in variants:
            cells.append((arch, shape, v))
    if args.list:
        for arch, shape, v in cells:
            print(f"CELL {arch:24s} {shape:12s} {v}")
        return 0
    if not cells:
        print("nothing to do")
        return 1

    failures = 0
    for arch, shape, v in cells:
        path = result_path(arch, shape, args.mesh, v)
        if args.skip_existing and os.path.exists(path):
            print(f"[cached] {arch} {shape} {args.mesh} {v}")
            continue
        print(f"[dryrun] {arch} {shape} {args.mesh} {v} ...", flush=True)
        t0 = time.time()
        try:
            res = run_cell(arch, shape, args.mesh,
                           "graft" if v == "graft" else
                           ("baseline" if v == "baseline" else "serve"),
                           with_deltas=not args.no_deltas, smoke=args.smoke,
                           feature_mode=args.feature_mode,
                           grad_mode=args.grad_mode,
                           data_source=args.data_source)
            res["ok"] = True
        except Exception:
            res = {"arch": arch, "shape": shape, "mesh": args.mesh,
                   "variant": v, "ok": False,
                   "error": traceback.format_exc()}
            failures += 1
            print(res["error"], file=sys.stderr)
        res["wall_s"] = round(time.time() - t0, 1)
        with open(path, "w") as f:
            json.dump(res, f, indent=1)
        status = "OK" if res.get("ok") else "FAIL"
        mem = res.get("full", {}).get("memory", {})
        print(f"  -> {status} in {res['wall_s']}s  "
              f"args={mem.get('argument_size_in_bytes', 0)/2**30:.2f}GiB "
              f"temp={mem.get('temp_size_in_bytes', 0)/2**30:.2f}GiB",
              flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
