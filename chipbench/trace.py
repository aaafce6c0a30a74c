"""From a profiler trace to the numbers the per-layer metrics read.

``load(dir)`` reads the ``.xplane.pb`` the JAX profiler wrote under
``dir`` with nothing but ``jax.profiler.ProfileData``: the operations on
each TPU's ops line, and the host spans the benchmark opens (``loop`` for
the window; ``stage``, ``dispatch`` and ``callbacks`` inside it).
``reduce(trace)`` then gives, over the ``loop`` span only:

* busy time: the union of the intervals in which an operation ran on a
  chip, averaged over the chips;
* time by operation name (each operation's own, less the operations
  nested in it), and per kernel name;
* idle gaps: the complement of the union, each put to the host span open
  at its middle (``loop`` when none of the inner ones is).

``python -m chipbench.checks.check_trace`` runs this on a small recorded
trace kept in ``checks/``.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

SPANS = ("loop", "stage", "dispatch", "callbacks")
OPS_LINES = ("XLA Ops",)

Interval = Tuple[float, float]


@dataclasses.dataclass
class Op:
    name: str                       # the HLO instruction's name
    start: float                    # seconds on the trace's clock
    end: float
    text: str                       # the instruction as the trace names it
    self_s: float = 0.0             # time not covered by nested operations


_NAME = re.compile(r"%([^\s=]+) = ")


def op_name(text: str) -> str:
    """``%flash_fwd.25 = (bf16[...]) custom-call(...)`` -> ``flash_fwd.25``."""
    m = _NAME.match(text)
    return m.group(1) if m else text


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Op]]        # device plane name -> its operations
    spans: List[Tuple[str, float, float]]


def load(directory: str) -> Trace:
    import jax
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    ops: Dict[str, List[Op]] = {}
    spans: List[Tuple[str, float, float]] = []
    for path in paths:
        data = jax.profiler.ProfileData.from_file(path)
        for plane in data.planes:
            if re.fullmatch(r"/device:TPU:\d+", plane.name):
                out = ops.setdefault(plane.name, [])
                for line in plane.lines:
                    if line.name not in OPS_LINES:
                        continue
                    for ev in line.events:
                        s = ev.start_ns * 1e-9
                        out.append(Op(op_name(ev.name), s,
                                      s + ev.duration_ns * 1e-9, ev.name))
            elif plane.name.startswith("/host:CPU"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name in SPANS:
                            s = ev.start_ns * 1e-9
                            spans.append((ev.name, s,
                                          s + ev.duration_ns * 1e-9))
    for v in ops.values():
        v.sort(key=lambda o: (o.start, -o.end))
        _self_times(v)
    return Trace(ops, spans)


def _self_times(ops: List[Op]) -> None:
    """An operation's time less that of the operations nested in it (a
    loop's body runs inside the loop's own event)."""
    stack: List[Op] = []
    for o in ops:
        o.self_s = o.end - o.start
        while stack and stack[-1].end <= o.start:
            stack.pop()
        if stack:
            stack[-1].self_s -= min(o.end, stack[-1].end) - o.start
        stack.append(o)


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: List[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(iv: Interval, lo: float, hi: float) -> Optional[Interval]:
    s, e = max(iv[0], lo), min(iv[1], hi)
    return (s, e) if e > s else None


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Parts of the (disjoint, sorted) intervals ``a`` outside the
    (disjoint, sorted) intervals ``b``."""
    out: List[Interval] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                           # mean over chips
    op_s: Dict[str, float]                  # op name -> self seconds, mean
                                            # over chips
    gaps: List[Tuple[str, float]]           # (host span, seconds), chip 0
    ops: Dict[str, List[Op]]                # clipped to the window
    chips: int

    def kernel(self, name: str) -> List[Op]:
        """Every call of kernel ``name`` in the window, on all chips."""
        return [o for v in self.ops.values() for o in v
                if _kernel_name(o.name) == name]

    def breakdown(self) -> Dict[str, List[List[object]]]:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:10]
        by_span: Dict[str, float] = {}
        for name, s in self.gaps:
            by_span[name] = by_span.get(name, 0.0) + s
        gaps = sorted(by_span.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def _kernel_name(op_name: str) -> str:
    """An operation's name without XLA's numeric suffix (``flash_fwd.3``)."""
    return re.sub(r"\.\d+$", "", op_name)


def reduce(trace: Trace, devices: Optional[int] = None) -> Reduced:
    loops = [(s, e) for n, s, e in trace.spans if n == "loop"]
    if not loops:
        raise ValueError("the trace holds no 'loop' span")
    lo, hi = loops[0]
    planes = sorted(trace.ops, key=lambda n: int(n.rsplit(":", 1)[1]))
    if devices is not None:
        planes = planes[:devices]
    busy = []
    op_s: Dict[str, float] = {}
    gaps: List[Tuple[str, float]] = []
    clipped: Dict[str, List[Op]] = {}
    for i, plane in enumerate(planes):
        kept = []
        for o in trace.ops[plane]:
            iv = clip((o.start, o.end), lo, hi)
            if iv:
                share = (iv[1] - iv[0]) / max(o.end - o.start, 1e-15)
                kept.append(Op(o.name, iv[0], iv[1], o.text,
                               o.self_s * share))
        clipped[plane] = kept
        u = union([(o.start, o.end) for o in kept])
        busy.append(total(u))
        for o in kept:
            op_s[o.name] = op_s.get(o.name, 0.0) + o.self_s
        if i == 0:
            for s, e in subtract([(lo, hi)], u):
                gaps.append((_host_span(trace.spans, (s + e) / 2), e - s))
    n = max(len(planes), 1)
    return Reduced(window_s=hi - lo, busy_s=sum(busy) / n,
                   op_s={k: v / n for k, v in op_s.items()}, gaps=gaps,
                   ops=clipped, chips=len(planes))


def _host_span(spans, t: float) -> str:
    """The innermost benchmark span open at time ``t`` (``loop`` if only
    the window's own is)."""
    best, width = "loop", float("inf")
    for name, s, e in spans:
        if name != "loop" and s <= t < e and e - s < width:
            best, width = name, e - s
    return best
