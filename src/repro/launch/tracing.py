"""The training run's own tracing: device phase scopes, host spans, and
compile events.

**Device phases.** The train step wraps each of its phases in a
``jax.named_scope`` with one of the names below. The scope path reaches
every operation the step lowers to (its HLO ``op_name``; the profiler's
``tf_op`` stat), so a device trace splits the step by phase:

* ``select_forward``: the selection forward over all K rows, the features
  and the gradient embeddings (``steps.selection_inputs``);
* ``select_decide``: the sampler's decision (the fused MaxVol and
  projection-error sweep, the rank and the weights);
* ``train_grad``: the row gather and ``value_and_grad`` of the loss. Under
  it, JAX names the forward ``jvp(...)``, the backward ``transpose(jvp(
  ...))`` and remat's recompute ``.../checkpoint/rematted_computation``;
* ``optimizer``: ``opt.apply``, clipping included;
* ``sentinel``: the divergence sentinel's health word and skip-update.

Scopes are metadata only: the arithmetic is the same with or without
them.

**Host spans.** ``span(name)`` opens a ``jax.profiler.TraceAnnotation``
(on the profiler's clock, beside the device operations, when a trace is
being captured) and adds its ``perf_counter`` duration to the
process-wide ``TABLE``. It never waits for the device. ``Trainer.fit``
opens them on its loop thread only: ``setup/backend``, ``setup/build``,
``setup/init`` before the loop; for each step, inside a
``StepTraceAnnotation("train", step_num=step)``, ``data_wait`` (the next
batch), ``dispatch`` (the step call: on the first step also its trace,
lowering and compile or cache load) and ``callbacks``; ``drain`` for the
report's materialization. ``BatchStager`` opens ``stage`` around an
inline ``shard_batch``.

**Compile events.** ``TABLE.listen()`` registers a ``jax.monitoring``
listener that counts and times tracing, lowering, backend compiles and the
persistent compilation cache's events, and records each backend compile
that fires after step 0 with its step and the host span open at the time.

``Trainer.fit`` resets the table when it starts and writes
``TABLE.summary()`` to ``report["spans"]``.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Any, Dict, Iterator, List, Optional

import jax
from jax import monitoring

# device phase scopes
SELECT_FORWARD = "select_forward"
SELECT_DECIDE = "select_decide"
TRAIN_GRAD = "train_grad"
OPTIMIZER = "optimizer"
SENTINEL = "sentinel"
SCOPES = (SELECT_FORWARD, SELECT_DECIDE, TRAIN_GRAD, OPTIMIZER, SENTINEL)

# host spans
STEP = "train"
DATA_WAIT = "data_wait"
DISPATCH = "dispatch"
CALLBACKS = "callbacks"
DRAIN = "drain"
STAGE = "stage"
SETUP_BACKEND = "setup/backend"
SETUP_BUILD = "setup/build"
SETUP_INIT = "setup/init"

# jax.monitoring events the table counts
JAXPR_TRACE = "/jax/core/compile/jaxpr_trace_duration"
JAXPR_TO_MLIR = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
COMPILE_EVENTS = (JAXPR_TRACE, JAXPR_TO_MLIR, BACKEND_COMPILE)
CACHE_PREFIX = "/jax/compilation_cache/"


class SpanTable:
    """Host spans and compile events of a run: per name its count, total
    seconds and the first occurrence's seconds. Thread-safe; the stack of
    open spans is per thread."""

    def __init__(self):
        self._lock = threading.Lock()
        self._local = threading.local()
        self._listening = False
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self.spans: Dict[str, Dict[str, float]] = {}
            self.events: Dict[str, Dict[str, float]] = {}
            self.late_compiles: List[Dict[str, Any]] = []
            self.step: Optional[int] = None

    def _open(self) -> List[str]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    @staticmethod
    def _add(table: Dict[str, Dict[str, float]], name: str,
             seconds: float) -> None:
        row = table.get(name)
        if row is None:
            table[name] = {"count": 1, "total_s": seconds,
                           "first_s": seconds}
        else:
            row["count"] += 1
            row["total_s"] += seconds

    @contextlib.contextmanager
    def span(self, name: str) -> Iterator[None]:
        stack = self._open()
        stack.append(name)
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield
        finally:
            dt = time.perf_counter() - t0
            stack.pop()
            with self._lock:
                self._add(self.spans, name, dt)

    def first(self, name: str) -> Optional[float]:
        """Seconds of the first span ``name`` since the last reset."""
        row = self.spans.get(name)
        return None if row is None else row["first_s"]

    # -- jax.monitoring ---------------------------------------------------
    def listen(self) -> None:
        """Register the compile-event listeners (once per table)."""
        with self._lock:
            if self._listening:
                return
            self._listening = True
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        if event not in COMPILE_EVENTS and not event.startswith(CACHE_PREFIX):
            return
        stack = self._open()
        with self._lock:
            self._add(self.events, event, duration)
            if event == BACKEND_COMPILE and self.step:
                self.late_compiles.append({
                    "step": self.step, "span": stack[-1] if stack else None,
                    "fun": kw.get("fun_name"), "seconds": duration})

    def _on_event(self, event: str, **kw) -> None:
        if event.startswith(CACHE_PREFIX):
            with self._lock:
                self._add(self.events, event, 0.0)

    def summary(self) -> Dict[str, Any]:
        with self._lock:
            return {"spans": {k: dict(v) for k, v in self.spans.items()},
                    "compile": {k: dict(v) for k, v in self.events.items()},
                    "late_compiles": [dict(c) for c in self.late_compiles]}


TABLE = SpanTable()


def span(name: str):
    """A host span ``name`` in the process-wide table (and the profiler's
    trace while one is captured)."""
    return TABLE.span(name)
