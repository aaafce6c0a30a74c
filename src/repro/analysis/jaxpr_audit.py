"""Declarative primitive accounting over traced jaxprs.

Generalizes the dispatch-shape evidence that ``benchmarks/
bench_selection_overhead.py`` used to hand-roll: trace a function, walk
every equation (recursing into pjit bodies, cond branches, scan bodies,
custom-vjp calls), and check the primitive counts against declarative
rules. The same counters feed three consumers:

  * the bench's ``dispatch_per_refresh`` / ``attention`` entries
    (:func:`count_primitives`, :func:`dispatch_summary`);
  * ``check_bench_regression``'s monotone launch-count gates
    (:func:`monotone_count_rows` — one implementation, so the measured and
    the gated counts can never drift apart);
  * the contract audits in ``python -m repro.analysis``
    (:func:`audit_step`, :func:`fused_selection_rules`, …).

Host-callback primitives are the jaxpr-visible evidence of a device→host
sync compiled INTO a step (``pure_callback`` and friends) — a step function
containing one stalls the dispatch queue every step, reverting the async
host loop (PR 5). f64 ops are audited from the equation output avals: a
single ``float64`` constant silently doubles bandwidth on the whole
downstream chain.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Iterator, List, Mapping, Optional, \
    Sequence, Tuple

import jax
from jax.extend import core as jex_core

from repro.analysis.report import Finding, Report

# jaxpr primitives that call back into the host — any of these inside a
# train/selection step is a per-dispatch host sync
HOST_CALLBACK_PRIMITIVES = (
    "pure_callback", "io_callback", "debug_callback", "callback",
    "outside_call", "host_callback_call",
)

_WIDE_DTYPES = ("float64", "complex128")


# ---------------------------------------------------------------------------
# traversal
# ---------------------------------------------------------------------------

def _subjaxprs(v) -> Iterator[Any]:
    if isinstance(v, jex_core.ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, jex_core.Jaxpr):
        yield v
    elif isinstance(v, (list, tuple)):
        for item in v:
            yield from _subjaxprs(item)


def iter_eqns(jaxpr) -> Iterator[Any]:
    """Every equation in ``jaxpr``, recursing into sub-jaxprs (pjit bodies,
    cond branches, scans, custom-vjp calls)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _subjaxprs(v):
                yield from iter_eqns(sub)


def trace_jaxpr(fn: Callable, *args, **kwargs):
    """The traced (unlowered) jaxpr of ``fn(*args, **kwargs)``."""
    return jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args).jaxpr


def eqn_location(eqn) -> str:
    """Best-effort user source location of one equation."""
    try:
        from jax._src import source_info_util
        return source_info_util.summarize(eqn.source_info)
    except Exception:
        return "<unknown>"


def count_primitives(fn: Callable, *args, **kwargs) -> Dict[str, int]:
    """Primitive → occurrence count in the traced jaxpr of ``fn`` —
    ``pallas_call`` entries are kernel launches per dispatch."""
    counts: Dict[str, int] = {}
    for eqn in iter_eqns(trace_jaxpr(fn, *args, **kwargs)):
        counts[eqn.primitive.name] = counts.get(eqn.primitive.name, 0) + 1
    return counts


def dispatch_summary(counts: Mapping[str, int],
                     keys: Sequence[str] = ("pallas_call", "gather"),
                     ) -> Dict[str, int]:
    """The dispatch-shape entry the bench reports and the gate diffs."""
    return {k: int(counts.get(k, 0)) for k in keys}


# ---------------------------------------------------------------------------
# declarative rules
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PrimitiveRule:
    """Bound on one primitive's count in a traced function.

    ``exact``/``max_count``/``min_count`` — any subset; unset bounds are
    not checked. ``rule`` is the report rule id the violation carries.
    """
    primitive: str
    exact: Optional[int] = None
    max_count: Optional[int] = None
    min_count: Optional[int] = None
    rule: str = "JX003"
    why: str = ""
    fix_hint: str = ""

    def check(self, counts: Mapping[str, int], label: str) -> List[Finding]:
        n = counts.get(self.primitive, 0)
        problems = []
        if self.exact is not None and n != self.exact:
            problems.append(f"expected exactly {self.exact}")
        if self.max_count is not None and n > self.max_count:
            problems.append(f"expected at most {self.max_count}")
        if self.min_count is not None and n < self.min_count:
            problems.append(f"expected at least {self.min_count}")
        if not problems:
            return []
        why = f" — {self.why}" if self.why else ""
        return [Finding(
            rule=self.rule, location=label,
            message=f"{n}× '{self.primitive}' in the traced jaxpr "
                    f"({'; '.join(problems)}){why}",
            fix_hint=self.fix_hint)]


def no_host_callback_rules() -> List[PrimitiveRule]:
    """Forbid every host-callback primitive (JX001) — the jaxpr evidence of
    a device→host transfer compiled into the step."""
    return [PrimitiveRule(
        p, max_count=0, rule="JX001",
        why="a host callback inside a jitted step syncs the dispatch "
            "queue every step",
        fix_hint="move the host computation out of the jitted function "
                 "(drain it at a flush boundary instead)")
        for p in HOST_CALLBACK_PRIMITIVES]


def fused_selection_rules() -> List[PrimitiveRule]:
    """PR 3's single-dispatch contract: ONE ``pallas_call``, NO gather."""
    return [
        PrimitiveRule(
            "pallas_call", exact=1, rule="JX003",
            why="the fused selection refresh must be a single kernel launch",
            fix_hint="route through kernels/graft_select.py "
                     "(GraftConfig.use_pallas) instead of the unfused chain"),
        PrimitiveRule(
            "gather", max_count=0, rule="JX004",
            why="the fused path gathers pivot columns inside the kernel "
                "(one-hot matmul); a jaxpr-level gather means an HBM "
                "round-trip crept back in",
            fix_hint="keep the G-gather inside the fused kernel "
                     "(no jnp.take on the fused path)"),
    ]


def attention_rules(layers: int) -> List[PrimitiveRule]:
    """PR 6's contract: exactly one kernel launch per attention layer."""
    return [PrimitiveRule(
        "pallas_call", exact=layers, rule="JX003",
        why=f"flash attention must launch exactly one kernel per layer "
            f"({layers} layers)",
        fix_hint="check resolve_attn_backend routing and the kernel "
                 "factory cache key")]


# ---------------------------------------------------------------------------
# audits
# ---------------------------------------------------------------------------

def audit_dtypes(fn: Callable, *args, label: str = "fn",
                 forbidden: Sequence[str] = _WIDE_DTYPES,
                 **kwargs) -> Report:
    """JX002: flag equations whose outputs are f64/c128 — one wide constant
    poisons the dtype of the whole downstream chain."""
    report = Report()
    seen: Dict[Tuple[str, str], int] = {}
    for eqn in iter_eqns(trace_jaxpr(fn, *args, **kwargs)):
        for var in eqn.outvars:
            aval = getattr(var, "aval", None)
            dtype = str(getattr(aval, "dtype", ""))
            if dtype in forbidden:
                key = (eqn.primitive.name, dtype)
                seen[key] = seen.get(key, 0) + 1
                if seen[key] == 1:       # one finding per (primitive, dtype)
                    report.add(Finding(
                        rule="JX002", location=f"{label} @ {eqn_location(eqn)}",
                        message=f"'{eqn.primitive.name}' produces {dtype} "
                                "inside a step function",
                        fix_hint="cast to float32 (or audit for a stray "
                                 "float64 constant / np scalar); x64 does "
                                 "not belong on the train hot path"))
    for (prim, dtype), n in seen.items():
        if n > 1:
            report.add(Finding(
                rule="JX002", severity="info", location=label,
                message=f"'{prim}' → {dtype} occurs {n}× in total"))
    return report


def audit_counts(fn: Callable, args: Sequence[Any],
                 rules: Sequence[PrimitiveRule],
                 label: str = "fn") -> Report:
    """Check declarative primitive-count rules against ``fn``'s jaxpr."""
    counts = count_primitives(fn, *args)
    report = Report()
    for rule in rules:
        report.extend(rule.check(counts, label))
    return report


def audit_step(fn: Callable, args: Sequence[Any], *, label: str = "step",
               extra_rules: Sequence[PrimitiveRule] = (),
               check_dtypes: bool = True) -> Report:
    """The standard train/selection-step audit: no host callbacks (JX001),
    no f64 ops (JX002), plus any caller-specific count rules."""
    rules = list(no_host_callback_rules()) + list(extra_rules)
    report = audit_counts(fn, args, rules, label=label)
    if check_dtypes:
        report.extend(audit_dtypes(fn, *args, label=label))
    return report


# ---------------------------------------------------------------------------
# regression-gate helper (shared with benchmarks/check_bench_regression.py)
# ---------------------------------------------------------------------------

def monotone_count_rows(prefix: str, baseline: Mapping[str, Any],
                        current: Mapping[str, Any],
                        keys: Sequence[str], why: str,
                        ) -> Tuple[List[Tuple[str, float, float, bool]],
                                   List[str]]:
    """Diff integer counters that must never INCREASE (launch/dispatch
    counts). Returns ``(rows, problems)`` in the regression gate's row
    format: ``(metric, baseline, current, regressed)``."""
    rows: List[Tuple[str, float, float, bool]] = []
    problems: List[str] = []
    for k in keys:
        b = float(baseline.get(k, 0))
        c = float(current.get(k, 0))
        bad = c > b
        rows.append((f"{prefix}.{k}", b, c, bad))
        if bad:
            problems.append(f"{prefix}.{k}: {why} "
                            f"(baseline {int(b)}, current {int(c)})")
    return rows, problems
