"""The train step's device time by phase, read from the scope path each
operation carries in the profiler's trace.

The program wraps its phases in ``jax.named_scope``s (``select_forward``,
``select_decide``, ``train_grad``, ``optimizer``, ``sentinel``). The path
reaches the trace as the ``tf_op`` stat of each operation's *event
metadata* (``jit(fn)/train_grad/transpose(jvp())/while/body/...``), which
``jax.profiler.ProfileData`` does not expose. ``load(dir)`` therefore
decodes the ``.xplane.pb`` with the XSpace schema declared by hand below
(``google.protobuf`` only), and keeps:

* per TPU, the operations of its ``XLA Ops`` line with their ``tf_op``
  (start: the line's ``timestamp_ns`` plus the event's ``offset_ps``);
* the host spans ``loop`` (the window) and ``data_wait`` (the program's
  wait for its next batch).

``split(trace)`` gives, over the ``loop`` span and averaged over the chips,
each operation's own time (``chipbench.trace``'s clipping and self times)
summed by phase, ``classify``'s first matching rule on the path's
segments:

1. ``select_decide``                          -> ``select_decide``
2. ``select_forward``                         -> ``select_forward``
3. ``train_grad`` and ``rematted_computation`` -> ``recompute``
4. ``train_grad`` and ``transpose(...)``       -> ``backward``
5. ``train_grad``                             -> ``forward``
6. ``optimizer`` or ``sentinel``              -> ``update`` (XLA fuses the
   sentinel's select into the update; a fusion carries one path)
7. anything else                              -> ``unscoped``

A program without the scopes puts every operation under ``unscoped``; the
readers then report nothing. ``python -m chipbench.checks.check_scopes``
checks this module on a small trace recorded on a v5e.
"""
from __future__ import annotations

import dataclasses
import functools
import glob
import os
import re
import sys
import traceback
from typing import Dict, List, Optional, Tuple

from chipbench import trace as trace_lib

CLASSES = ("select_forward", "select_decide", "forward", "backward",
           "recompute", "update", "unscoped")
HOST_SPANS = ("loop", "data_wait")


def classify(path: str) -> str:
    """The phase of an operation whose scope path is ``path``."""
    segs = path.split("/")
    if "select_decide" in segs:
        return "select_decide"
    if "select_forward" in segs:
        return "select_forward"
    if "train_grad" in segs:
        if "rematted_computation" in segs:
            return "recompute"
        if any(s.startswith("transpose(") for s in segs):
            return "backward"
        return "forward"
    if "optimizer" in segs or "sentinel" in segs:
        return "update"
    return "unscoped"


# ---------------------------------------------------------------------------
# the XSpace schema (tsl/profiler/protobuf/xplane.proto), the fields read
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=1)
def _xspace_class():
    from google.protobuf import descriptor_pb2, descriptor_pool, \
        message_factory
    F = descriptor_pb2.FieldDescriptorProto
    one, many = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    i64, u64, dbl = F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_DOUBLE
    text, raw, sub = F.TYPE_STRING, F.TYPE_BYTES, F.TYPE_MESSAGE
    fd = descriptor_pb2.FileDescriptorProto(
        name="chipbench_xplane.proto", package="xp", syntax="proto3")

    def message(name, fields, into=None):
        m = (fd.message_type if into is None else into).add(name=name)
        for fname, num, typ, label, *ref in fields:
            m.field.add(name=fname, number=num, type=typ, label=label,
                        type_name=ref[0] if ref else None)
        return m

    stat = [("metadata_id", 1, i64, one), ("double_value", 2, dbl, one),
            ("uint64_value", 3, u64, one), ("int64_value", 4, i64, one),
            ("str_value", 5, text, one), ("bytes_value", 6, raw, one),
            ("ref_value", 7, u64, one)]
    message("XStat", stat)
    message("XEvent", [("metadata_id", 1, i64, one),
                       ("offset_ps", 2, i64, one),
                       ("duration_ps", 3, i64, one),
                       ("stats", 4, sub, many, ".xp.XStat")])
    message("XLine", [("id", 1, i64, one), ("name", 2, text, one),
                      ("timestamp_ns", 3, i64, one),
                      ("events", 4, sub, many, ".xp.XEvent")])
    message("XEventMetadata", [("id", 1, i64, one), ("name", 2, text, one),
                               ("stats", 5, sub, many, ".xp.XStat")])
    message("XStatMetadata", [("id", 1, i64, one), ("name", 2, text, one)])
    plane = message("XPlane", [
        ("id", 1, i64, one), ("name", 2, text, one),
        ("lines", 3, sub, many, ".xp.XLine"),
        ("event_metadata", 4, sub, many, ".xp.XPlane.EventMetadataEntry"),
        ("stat_metadata", 5, sub, many, ".xp.XPlane.StatMetadataEntry")])
    for entry, value in (("EventMetadataEntry", ".xp.XEventMetadata"),
                         ("StatMetadataEntry", ".xp.XStatMetadata")):
        e = message(entry, [("key", 1, i64, one),
                            ("value", 2, sub, one, value)],
                    into=plane.nested_type)
        e.options.map_entry = True
    message("XSpace", [("planes", 1, sub, many, ".xp.XPlane")])
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("xp.XSpace"))


def load(directory: str) -> trace_lib.Trace:
    """The window's operations per TPU, each ``Op.text`` its ``tf_op``,
    and the host spans named in ``HOST_SPANS``."""
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    ops: Dict[str, List[trace_lib.Op]] = {}
    spans: List[Tuple[str, float, float]] = []
    for path in paths:
        space = _xspace_class()()
        with open(path, "rb") as f:
            space.ParseFromString(f.read())
        for plane in space.planes:
            device = re.fullmatch(r"/device:TPU:\d+", plane.name)
            if not device and not plane.name.startswith("/host:CPU"):
                continue
            names = {k: v.name for k, v in plane.event_metadata.items()}
            if device:
                tf_op = _tf_ops(plane)
                out = ops.setdefault(plane.name, [])
            for line in plane.lines:
                if device and line.name not in trace_lib.OPS_LINES:
                    continue
                base = line.timestamp_ns
                for ev in line.events:
                    s = (base + ev.offset_ps * 1e-3) * 1e-9
                    e = s + ev.duration_ps * 1e-12
                    name = names.get(ev.metadata_id, "")
                    if device:
                        out.append(trace_lib.Op(
                            trace_lib.op_name(name), s, e,
                            tf_op.get(ev.metadata_id, "")))
                    elif name in HOST_SPANS:
                        spans.append((name, s, e))
    for v in ops.values():
        v.sort(key=lambda o: (o.start, -o.end))
        trace_lib._self_times(v)
    return trace_lib.Trace(ops, spans)


def _tf_ops(plane) -> Dict[int, str]:
    """Event metadata id -> its ``tf_op`` stat (the scope path)."""
    ids = [k for k, v in plane.stat_metadata.items() if v.name == "tf_op"]
    if not ids:
        return {}
    out = {}
    for k, md in plane.event_metadata.items():
        for st in md.stats:
            if st.metadata_id == ids[0]:
                out[k] = st.str_value
    return out


@dataclasses.dataclass
class Split:
    window_s: float
    busy_s: float                   # mean over chips
    class_s: Dict[str, float]       # phase -> own seconds, mean over chips
    data_wait_s: float              # the program's data_wait spans, in loop
    flash: Dict[str, List[str]]     # phase -> the flash_* ops in it

    @property
    def scoped(self) -> bool:
        """Whether any operation carried one of the program's scopes."""
        return any(v > 0 for k, v in self.class_s.items()
                   if k != "unscoped")


def split(tr: trace_lib.Trace, devices: Optional[int] = None) -> Split:
    loops = [(s, e) for n, s, e in tr.spans if n == "loop"]
    if not loops:
        raise ValueError("the trace holds no 'loop' span")
    lo, hi = loops[0]
    planes = sorted(tr.ops, key=lambda n: int(n.rsplit(":", 1)[1]))
    if devices is not None:
        planes = planes[:devices]
    class_s = {c: 0.0 for c in CLASSES}
    flash: Dict[str, set] = {}
    busy = 0.0
    for plane in planes:
        kept = []
        for o in tr.ops[plane]:
            iv = trace_lib.clip((o.start, o.end), lo, hi)
            if not iv:
                continue
            share = (iv[1] - iv[0]) / max(o.end - o.start, 1e-15)
            kept.append(iv)
            c = classify(o.text)
            class_s[c] += o.self_s * share
            if o.name.startswith("flash_"):
                flash.setdefault(c, set()).add(o.name)
        busy += trace_lib.total(trace_lib.union(kept))
    n = max(len(planes), 1)
    wait = sum(iv[1] - iv[0] for iv in (
        trace_lib.clip((s, e), lo, hi) for name, s, e in tr.spans
        if name == "data_wait") if iv)
    return Split(window_s=hi - lo, busy_s=busy / n,
                 class_s={k: v / n for k, v in class_s.items()},
                 data_wait_s=wait, flash={k: sorted(v)
                                          for k, v in flash.items()})


# ---------------------------------------------------------------------------
# for the metric readers: one decode per window trace
# ---------------------------------------------------------------------------

def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@functools.lru_cache(maxsize=1)
def _window_split(directory: str, devices: int) -> Optional[Split]:
    try:
        sp = split(load(directory), devices)
    except Exception:           # the readers report nothing, and say why
        _log("scopes: no split of the window trace:\n"
             + traceback.format_exc())
        return None
    busy = max(sp.busy_s, 1e-15)
    _log("scopes: " + ", ".join(
        f"{k} {v:.6f} s ({100 * v / busy:.3f}%)"
        for k, v in sp.class_s.items())
        + f"; busy {sp.busy_s:.6f} s; data_wait {sp.data_wait_s:.6f} s")
    _log(f"scopes: unscoped share of busy time "
         f"{100 * sp.class_s['unscoped'] / busy:.4f}%")
    _log(f"scopes: flash ops by phase {sp.flash}")
    return sp


def window_split(ctx) -> Optional[Split]:
    """The traced window's split, or ``None`` (no trace, or no scopes)."""
    win = ctx["window"]
    if ctx.get("trace") is None or not getattr(win, "trace_dir", None):
        return None
    sp = _window_split(win.trace_dir, ctx["cell"].chips)
    return sp if sp is not None and sp.scoped else None


def refresh_steps(ctx) -> int:
    """Steps of the window that refreshed the selection (every
    ``refresh_every`` steps from step 0), as ``metrics/step.mfu.py``
    counts them."""
    win, cell = ctx["window"], ctx["cell"]
    every = cell.traffic["graft"].get("refresh_every", 1)
    return sum(1 for i in range(win.steps)
               if (win.first_step + i) % every == 0)


def per_step_ms(ctx, phases, refresh: bool = False) -> Optional[float]:
    """Milliseconds of ``phases`` a window step (a refresh step with
    ``refresh``)."""
    sp = window_split(ctx)
    steps = refresh_steps(ctx) if refresh else ctx["window"].steps
    if sp is None or not steps:
        return None
    return 1e3 * sum(sp.class_s[p] for p in phases) / steps
