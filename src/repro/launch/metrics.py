"""Training telemetry: JSONL metrics stream + throughput/MFU tracking.

Production habits kept: append-only JSONL (greppable), host-side only, and
— since the async host loop — NO device sync on the step path at all. The
trainer hands each step's metrics over as a :class:`MetricsFuture` (a
mapping over still-in-flight device scalars); the logger stamps the
host-side fields (wall time, tokens_seen, step timing) at ``log`` time but
defers the device→host materialization to the flush boundary, so the host
keeps dispatching ahead of the device between flushes.

Rows are BUFFERED: one logical row per step, but materialization + the
write syscall happen only every ``flush_every`` rows (and on ``flush``/
``close``). The trade: crash-safety is BOUNDED, not per-row — a hard kill
between flushes drops at most the last ``flush_every − 1`` rows (a clean
stop, including preemption via ``EmergencySaver``, drains the buffer
through ``close``). Set ``flush_every=1`` to restore per-row durability.

Step timing is HONEST: ``step_time_s`` is the duration the caller measured
around the step dispatch itself (``step_time=``), not the wall time between
``log`` calls — so an eval or checkpoint pause between steps no longer
contaminates the next step's ``mfu``/``tokens_per_s``. The host-side gap on
top of the dispatch is reported separately as ``host_overhead_s``. Callers
that don't pass ``step_time`` keep the legacy between-calls clock.
"""
from __future__ import annotations

import json
import math
import os
import queue
import threading
import time
from typing import Any, Callable, Dict, Iterator, List, Mapping, \
    MutableMapping, Optional, Tuple

import jax

from repro.analysis.sync_guard import sync_allowed

# Published per-chip peaks, keyed by ``jax.Device.device_kind``. Source:
# Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM at
# 819 GB/s, 1,600 Gbit/s inter-chip interconnect). A kind missing here has
# no peak: its ``mfu`` is null, never computed against an assumed one.
DEVICE_PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "ici_bytes_per_s": 1600e9 / 8},
}


def peak_flops(device_kind: str) -> Optional[float]:
    """bf16 peak FLOP/s of one chip of this kind; ``None`` if not tabled."""
    peaks = DEVICE_PEAKS.get(device_kind)
    return peaks["bf16_flops"] if peaks else None


def _attn_kv_horizon(S: int, window: Optional[int]) -> float:
    """Mean per-query causal KV horizon length over a length-S sequence."""
    if window is not None and window < S:
        w = window
        # the first w queries see q+1 keys, the rest see exactly w
        return (w * (w + 1) / 2.0 + (S - w) * w) / S
    return (S + 1) / 2.0


def attention_train_flops(mcfg, seq: int, tokens_per_step: int,
                          remat: bool = True) -> float:
    """Per-step matmul FLOPs of the attention score/value products — the
    O(S²·Dh·H) term that 6·N·tokens misses. Causal- and window-aware,
    honoring the per-layer local/global pattern (gemma2, hymba)."""
    if mcfg.family == "ssm" or not mcfg.num_heads:
        return 0.0
    local = mcfg.is_local_pattern()
    per_token = 0.0
    for i in range(mcfg.num_layers):
        window = mcfg.sliding_window if (mcfg.sliding_window and local[i]) \
            else None
        kv = _attn_kv_horizon(seq, window)
        per_token += 4.0 * kv * mcfg.num_heads * mcfg.head_dim  # QKᵀ + PV
    total = per_token * 3.0                  # forward + 2× backward
    if remat:
        total *= 4.0 / 3.0                   # forward recompute under remat
    return total * tokens_per_step


def train_step_flops(num_params: int, tokens_per_step: int,
                     remat: bool = True, mcfg=None,
                     seq: Optional[int] = None) -> float:
    """6·N·D (+2·N·D recompute under full remat), plus — when the model
    config and sequence length are given — the attention O(S²) term.
    Without them the legacy parameter-only estimate is returned (inflating
    ``mfu`` as sequence length grows)."""
    base = 6.0 * num_params * tokens_per_step
    total = base * (8.0 / 6.0) if remat else base
    if mcfg is not None and seq:
        total += attention_train_flops(mcfg, seq, tokens_per_step, remat=remat)
    return total


def graft_step_flops(num_params: int, mcfg, batch: int, seq: int,
                     probe_positions: int, rank: int,
                     refresh: bool = True) -> float:
    """Required FLOPs of one GRAFT step: on a refresh step the selection
    forward over all ``batch`` rows (every layer, the head at the probe
    positions only), then forward and backward over the ``rank`` rows the
    selection weighted (the head at every position). Remat's recompute and
    the zero-weight rows the step also runs are not required work and are
    not counted. Matmuls at 2·N per token; the attention term as in
    :func:`attention_train_flops`."""
    head = mcfg.vocab_size * mcfg.d_model
    body = num_params - head * (1 if mcfg.tie_embeddings else 2)

    def forward(rows: int, head_positions: int) -> float:
        tokens = rows * seq
        return (2.0 * body * tokens + 2.0 * head * head_positions
                + attention_train_flops(mcfg, seq, tokens, remat=False) / 3.0)

    total = 3.0 * forward(rank, rank * seq)
    if refresh:
        stride = max(1, seq // probe_positions) if probe_positions else 1
        total += forward(batch, batch * len(range(0, seq, stride)))
    return total


class DeviceClock:
    """Device-time source: completion stamps without syncing the step path.

    The dispatch clock (``Trainer.last_step_time``) measures how long the
    host took to ENQUEUE a step — under the async host loop that is dispatch
    jitter, not device time. Instead, each step hands one of its detached
    device scalars to :meth:`observe`; a daemon thread ``block_until_ready``s
    the markers in order and stamps the completion wall time. With the
    dispatch queue saturated (the steady state the async loop maintains),
    the delta between consecutive completion stamps IS the device execution
    time of the step. The first observed step has no predecessor stamp and
    is never timed, so N observed steps yield N−1 device timings.

    ``stall_timeout_s`` arms a watchdog: when the stamper thread has been
    blocked on one marker longer than the timeout, the blocking consumers
    (:meth:`device_time`, :meth:`drain`) log the stuck step once, stop
    waiting, and return what they have — so a wedged device degrades the
    report to dispatch-sourced timing (``mfu_source: dispatch``) instead of
    hanging it. The stall clears itself if the marker eventually completes.
    """

    def __init__(self, stall_timeout_s: Optional[float] = None):
        self.stall_timeout_s = stall_timeout_s
        self.stalled = False
        self._stall_warned = False
        self._q: "queue.SimpleQueue" = queue.SimpleQueue()
        self._cond = threading.Condition()
        self._times: Dict[int, float] = {}          # step → device seconds
        self._fresh: List[Tuple[int, float]] = []   # not yet poll()ed
        self._prev_t: Optional[float] = None
        self._pending = 0
        self._waiting: Optional[Tuple[int, float]] = None  # (step, t_block)
        self._closed = False
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="device-clock")
        self._thread.start()

    def _run(self):
        while True:
            item = self._q.get()
            if item is None:
                return
            step, marker = item
            with self._cond:
                self._waiting = (step, time.time())
            try:
                # clock-thread blocking IS the design (off the step path);
                # duck-typed so chaos StallMarkers time-shift the stamp
                if hasattr(marker, "block_until_ready"):
                    marker.block_until_ready()              # lint: allow
                else:
                    jax.block_until_ready(marker)           # lint: allow
            except Exception:
                pass                      # a failed step still advances time
            t = time.time()
            with self._cond:
                self._waiting = None
                self.stalled = False      # marker landed — stall cleared
                if self._prev_t is not None:
                    dt = t - self._prev_t
                    self._times[step] = dt
                    self._fresh.append((step, dt))
                self._prev_t = t
                self._pending -= 1
                self._cond.notify_all()

    def _stalled_now(self) -> bool:
        """Watchdog check (condition must be held): has the stamper been
        blocked on a single marker past ``stall_timeout_s``? Warns once,
        naming the stuck step."""
        if self.stall_timeout_s is not None and self._waiting is not None:
            step, t0 = self._waiting
            if time.time() - t0 >= self.stall_timeout_s:
                self.stalled = True
                if not self._stall_warned:
                    self._stall_warned = True
                    print(f"[device-clock] WARNING: step {step} marker "
                          f"incomplete after {self.stall_timeout_s:.1f}s — "
                          "device stall suspected; timing falls back to the "
                          "dispatch clock (mfu_source: dispatch)", flush=True)
        return self.stalled

    def observe(self, step: int, marker) -> None:
        """Register one step's device marker (must be a DETACHED array —
        the clock thread holds it until it completes)."""
        if self._closed:
            return
        with self._cond:
            self._pending += 1
        self._q.put((step, marker))

    def device_time(self, step: int,
                    timeout: Optional[float] = None) -> Optional[float]:
        """Device seconds for ``step``; optionally wait for the stamp.
        Returns immediately (with what exists) once the watchdog trips."""
        with self._cond:
            if timeout and step not in self._times and self._pending:
                deadline = time.time() + timeout
                while (step not in self._times and self._pending
                       and not self._stalled_now()):
                    remaining = deadline - time.time()
                    if remaining <= 0:
                        break
                    # sliced wait: a hung stamper never notifies, so the
                    # watchdog must get re-checked on a bounded cadence
                    self._cond.wait(min(remaining, 0.25))
            return self._times.get(step)

    def poll(self) -> List[Tuple[int, float]]:
        """Drain newly completed (step, device_dt) pairs (straggler feed)."""
        with self._cond:
            out, self._fresh = self._fresh, []
            return out

    def drain(self, timeout: float = 30.0) -> None:
        """Block until every observed marker has been stamped — or the
        watchdog declares the device stalled."""
        with self._cond:
            deadline = time.time() + timeout
            while self._pending and not self._stalled_now():
                remaining = deadline - time.time()
                if remaining <= 0:
                    break
                self._cond.wait(min(remaining, 0.25))

    @property
    def timed_steps(self) -> int:
        with self._cond:
            return len(self._times)

    @property
    def total_device_s(self) -> float:
        with self._cond:
            return sum(self._times.values())

    def close(self) -> None:
        if not self._closed:
            self._closed = True
            self._q.put(None)
            self._thread.join(timeout=5.0)


class MetricsFuture(MutableMapping):
    """One step's metrics as unmaterialized device scalars.

    Behaves like a dict (callbacks may mutate it in place, per the
    ``on_step_end`` contract), but ``float()``-ing the values — the
    host↔device sync — is deferred until someone actually reads one
    (``[]``/``items``) or calls :meth:`materialize`. Key-level operations
    (``in``, ``keys``, ``len``, assignment) never sync, so callbacks can
    route on the row shape without stalling the dispatch queue. ``update``
    merges more values in (the eval side stream injects its device scalars
    here, tagged to the step they were dispatched at).
    """

    __slots__ = ("_data", "_ready")

    def __init__(self, data: Optional[Mapping[str, Any]] = None):
        self._data: Dict[str, Any] = dict(data) if data else {}
        self._ready = False

    # -- key-level ops: never sync --------------------------------------
    def __iter__(self) -> Iterator[str]:
        return iter(self._data)

    def __len__(self) -> int:
        return len(self._data)

    def __contains__(self, key) -> bool:
        return key in self._data

    def keys(self):
        return self._data.keys()

    @property
    def materialized(self) -> bool:
        return self._ready

    def __setitem__(self, key: str, value: Any) -> None:
        self._data[key] = value
        if self._ready:              # keep the materialized invariant
            self._ready = False
            self.materialize()

    def __delitem__(self, key: str) -> None:
        del self._data[key]

    # -- value-level ops: sync ------------------------------------------
    def __getitem__(self, key: str) -> float:
        return self.materialize()[key]

    def materialize(self) -> Dict[str, float]:
        """Pull every value to the host as a plain float (cached)."""
        if not self._ready:
            # deliberately NOT a sanctioned site itself: under train.audit
            # a materialize outside a wrapped drain point must fire SY001
            self._data = {k: float(v)                       # lint: allow
                          for k, v in jax.device_get(self._data).items()}
            self._ready = True
        return self._data

    def update(self, other: Mapping[str, Any]) -> None:
        if isinstance(other, MetricsFuture):
            other = other._data
        self._data.update(other)
        if self._ready:                  # keep the materialized invariant
            self._ready = False
            self.materialize()


def sanitize_row(row: Mapping[str, Any]) -> Dict[str, Any]:
    """JSON-safe copy of a metrics row: non-finite floats become ``null``
    and their keys are listed under ``nonfinite_keys``. Python's default
    ``json.dumps`` emits bare ``NaN``/``Infinity`` literals — NOT valid
    JSON — which breaks every strict downstream parser; a sentinel-skipped
    step (NaN loss is recorded honestly) must not poison the stream."""
    out: Dict[str, Any] = {}
    bad = []
    for k, v in row.items():
        if isinstance(v, float) and not math.isfinite(v):
            out[k] = None
            bad.append(k)
        else:
            out[k] = v
    if bad:
        out["nonfinite_keys"] = sorted(bad)
    return out


def materialize_metrics(metrics: Mapping[str, Any]) -> Dict[str, float]:
    """Plain ``{k: float}`` from a MetricsFuture or an eager dict — the one
    sync point for consumers that need host values NOW (checkpoint
    manifests, console lines, reports)."""
    if isinstance(metrics, MetricsFuture):
        return metrics.materialize()
    return {k: float(v) for k, v in metrics.items()}       # lint: allow


class MetricsLogger:
    def __init__(self, path: Optional[str] = None, num_chips: int = 1,
                 flops_per_step: Optional[float] = None,
                 flush_every: int = 20,
                 device_clock: Optional[DeviceClock] = None,
                 peak_flops_per_chip: Optional[float] = None,
                 row_flops: Optional[Callable[[Dict[str, Any]],
                                              Optional[float]]] = None):
        self.path = path
        self.num_chips = num_chips
        self.flops_per_step = flops_per_step
        # a row's FLOPs from its materialized values (a GRAFT row's rank),
        # counted at flush; replaces ``flops_per_step`` when given
        self.row_flops = row_flops
        # None (a device kind without a tabled peak) makes ``mfu`` null
        self.peak_flops_per_chip = peak_flops_per_chip
        self.flush_every = max(1, flush_every)
        self.device_clock = device_clock
        self._f = open(path, "a") if path else None
        # pending rows: (host-side fields, metrics mapping, tokens) triples;
        # device values are materialized only when the row is drained
        self._pending: list = []
        self._last_t: Optional[float] = None
        self.tokens_seen = 0
        self.drain_s = 0.0               # cumulative time spent materializing

    def log(self, step: int, metrics: Mapping[str, Any], tokens: int = 0,
            step_time: Optional[float] = None) -> Dict[str, Any]:
        """Queue one row. Host-side fields (time, tokens_seen, timing) are
        stamped NOW; device values drain at the next flush boundary.

        ``step_time`` is the caller's measurement around the step dispatch
        (``Trainer.last_step_time``); when given, throughput/MFU are
        computed from it and the extra host-side gap between ``log`` calls
        lands in ``host_overhead_s``. Without it, the legacy between-calls
        clock is used (which smears eval/checkpoint pauses into the next
        step — pass ``step_time`` for honest numbers).
        """
        now = time.time()
        base: Dict[str, Any] = {"step": step, "time": now}
        if tokens:
            self.tokens_seen += tokens
            base["tokens_seen"] = self.tokens_seen
        gap = (now - self._last_t) if self._last_t is not None else None
        dt = step_time if step_time is not None else gap
        if dt is not None and dt > 0:
            base["step_time_s"] = dt
            if tokens:
                base["tokens_per_s"] = tokens / dt
            if self.flops_per_step and self.row_flops is None:
                base["mfu"] = self._mfu(self.flops_per_step, dt)
                base["mfu_source"] = "dispatch"
            if step_time is not None and gap is not None:
                base["host_overhead_s"] = max(0.0, gap - step_time)
        self._last_t = now
        if self._f:
            # no stream, no queue: without a file the row would only be
            # materialized to be thrown away — leave the futures untouched
            self._pending.append((base, metrics, tokens))
            if len(self._pending) >= self.flush_every:
                self.flush()
        return base

    def _mfu(self, flops: float, step_time: float) -> Optional[float]:
        if self.peak_flops_per_chip is None:
            return None
        return flops / (step_time * self.num_chips *
                        self.peak_flops_per_chip)

    def flush(self):
        """Drain the pending rows: materialize device values (the only
        host↔device sync in the logger) and write the JSONL block. With a
        :class:`DeviceClock` attached, ``mfu``/throughput are re-sourced
        from device time here — materializing the row's metrics guarantees
        the device has finished the step, so the stamp is (near-)ready."""
        if not self._pending:
            return
        t0 = time.time()
        lines = []
        with sync_allowed("metrics_flush"):
            for base, metrics, tokens in self._pending:
                row = dict(base)
                row.update(materialize_metrics(metrics))
                flops = self.flops_per_step
                if self.row_flops is not None:
                    flops = self.row_flops(row)
                    if flops and "step_time_s" in row:
                        row["mfu"] = self._mfu(flops, row["step_time_s"])
                        row["mfu_source"] = "dispatch"
                if self.device_clock is not None:
                    dev_dt = self.device_clock.device_time(row["step"],
                                                           timeout=1.0)
                    if dev_dt is not None and dev_dt > 0:
                        row["device_step_time_s"] = dev_dt
                        if tokens:
                            row["tokens_per_s"] = tokens / dev_dt
                        if flops:
                            row["mfu"] = self._mfu(flops, dev_dt)
                            row["mfu_source"] = "device"
                lines.append(json.dumps(sanitize_row(row), allow_nan=False))
        self._pending.clear()
        self.drain_s += time.time() - t0
        if self._f:
            self._f.write("\n".join(lines) + "\n")
            self._f.flush()

    def close(self):
        self.flush()
        if self._f:
            self._f.close()


def format_step_line(step: int, metrics: Mapping[str, Any], dt: float,
                     use_graft: bool = False) -> str:
    """One console progress line (the ConsoleCallback / legacy-loop format).
    Materializes ``metrics`` — only call for rows actually printed."""
    metrics = materialize_metrics(metrics)
    extra = (f" rank={metrics.get('rank', 0):.0f}"
             f" align={metrics.get('alignment', 0):.3f}" if use_graft else "")
    return (f"[train] step {step:5d} loss {metrics['loss']:.4f} "
            f"gnorm {metrics['grad_norm']:.3f} {dt*1e3:.0f}ms{extra}")


def read_metrics(path: str):
    out = []
    if not os.path.exists(path):
        return out
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out
