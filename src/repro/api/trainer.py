"""The ``Trainer``: a pure step-dispatch loop over a declarative
``ExperimentConfig``, with every side effect (checkpointing, eval,
telemetry, monitoring, early stop) delegated to ``Callback`` plugins.

The loop is ASYNC with respect to the device queue: a step's metrics leave
``run_step`` as device scalars and flow through ``on_step_end`` wrapped in
a lazy :class:`~repro.launch.metrics.MetricsFuture` — nothing on the step
path calls ``float()``, so the host keeps dispatching ahead (under
``graft.overlap`` the next refresh too) while the device drains earlier
steps. Materialization happens in bulk at flush boundaries (the
``MetricsCallback`` logger), at console/checkpoint boundaries, and when the
final report is assembled. ``last_step_time`` therefore times the step
DISPATCH, not device execution — the honest host-side number; the logger
reports the host-side gap on top of it as ``host_overhead_s``.

Typical use::

    from repro.api import ExperimentConfig, Trainer

    cfg = ExperimentConfig().apply_overrides(["train.steps=40"])
    report = Trainer(cfg).fit()

Resume needs nothing but the checkpoint directory — the finalized config
rides in the manifest::

    report = Trainer.from_checkpoint("/ckpts/run1").fit()
"""
from __future__ import annotations

import collections
import contextlib
import time
from typing import Any, Dict, Iterable, List, Optional

import jax
import numpy as np

from repro import backend as backend_lib
from repro.analysis.sync_guard import sync_allowed
from repro.api import callbacks as cb_lib
from repro.api.config import ExperimentConfig
from repro.distributed import sharding as sh
from repro.distributed.pipeline import BatchStager
from repro.launch import steps as steps_lib
from repro.launch import tracing
from repro.launch.metrics import (DeviceClock, MetricsFuture,
                                  materialize_metrics)


class HistoryBuffer:
    """Bounded per-step history: with ``cap > 0`` keeps the FIRST row plus
    a tail window of the last ``cap`` rows (dropping the middle), so a
    million-step run doesn't hold every row — and every retained
    ``MetricsFuture`` — in host memory. ``cap == 0`` keeps everything
    (the historical behavior)."""

    def __init__(self, cap: int = 0):
        self.cap = cap
        self._first: Optional[Any] = None
        self._tail: collections.deque = collections.deque(
            maxlen=cap if cap > 0 else None)
        self.total = 0

    def append(self, row) -> None:
        # rows falling off the tail window are dropped UNMATERIALIZED —
        # a device future nobody will read again costs no sync
        if self.total == 0 and self.cap > 0:
            self._first = row
        else:
            self._tail.append(row)
        self.total += 1

    @property
    def last(self):
        if self._tail:
            return self._tail[-1]
        return self._first

    @property
    def dropped(self) -> int:
        return self.total - len(self._tail) - \
            (1 if self._first is not None else 0)

    def rows(self) -> List[Dict[str, float]]:
        """Materialized retained rows, oldest first."""
        out = ([self._first] if self._first is not None else []) + \
            list(self._tail)
        return [materialize_metrics(r) for r in out]


class Trainer:
    """Runs one experiment. ``callbacks`` are appended to the stock set
    derived from the config; pass ``use_default_callbacks=False`` to take
    full control of the plugin list."""

    def __init__(self, config: ExperimentConfig,
                 callbacks: Optional[Iterable[cb_lib.Callback]] = None,
                 use_default_callbacks: bool = True,
                 backend: Optional[backend_lib.Backend] = None):
        self.config = config.finalized()
        # how this run touches devices; ``None`` resolves the config's
        # tagged ``backend`` section (local when absent). The trainer
        # itself never constructs meshes or queries process topology —
        # lint rule LN004 enforces that boundary machine-wide.
        self.backend = (backend if backend is not None
                        else backend_lib.resolve(self.config.backend))
        cbs = list(cb_lib.default_callbacks(self.config)
                   if use_default_callbacks else [])
        if callbacks:
            cbs.extend(callbacks)
        self.callbacks = sorted(cbs, key=lambda c: c.priority)

        # populated by fit(); callbacks read these
        self.mcfg = None
        self.tcfg: Optional[steps_lib.TrainConfig] = None
        self.data = None
        self.state = None
        self.start_step: int = 0
        self.num_params: int = 0
        self.last_step_time: float = 0.0
        self.device_clock: Optional[DeviceClock] = None
        self.should_stop: bool = False
        self.stop_reason: Optional[str] = None
        self.checkpoint_manager = None
        # resilience: set by the DivergenceGuardCallback, consumed by the
        # loop (rollback) and the CheckpointCallback (save refusal)
        self.sentinel_tripped: bool = False
        self.rollbacks: List[Dict[str, Any]] = []
        self._rollback_reason: Optional[str] = None
        self._chaos = None
        self._stager: Optional[BatchStager] = None

    # ------------------------------------------------------------------
    @classmethod
    def from_checkpoint(cls, directory: str,
                        callbacks: Optional[Iterable[cb_lib.Callback]] = None,
                        use_default_callbacks: bool = True) -> "Trainer":
        """Reconstruct the exact experiment from a checkpoint directory
        alone: the manifest-embedded ``ExperimentConfig`` is reloaded,
        ``stop_after`` (a one-shot simulated preemption, already consumed)
        and ``fault_plan`` (injected faults must not replay into the
        recovered run) are cleared, and ``checkpoint_dir`` is pointed at
        ``directory`` so the run restores and keeps checkpointing in
        place. The embedded ``backend`` section is cleared too — resume is
        ELASTIC: the restart picks its own topology (local by default;
        pass ``backend=`` or re-launch with ``--backend.*`` overrides for
        multi-process), and ``restore`` reshards the state onto it."""
        from repro.checkpoint import load_experiment
        import dataclasses
        cfg = load_experiment(directory)
        cfg = dataclasses.replace(cfg, backend=None,
                                  train=dataclasses.replace(
            cfg.train, stop_after=None, fault_plan=None,
            checkpoint_dir=directory))
        return cls(cfg, callbacks=callbacks,
                   use_default_callbacks=use_default_callbacks)

    # ------------------------------------------------------------------
    def data_state(self) -> Dict[str, int]:
        """The data-pipeline state a checkpoint must record: the position
        of the last CONSUMED batch. With staging lookahead the live source
        runs ahead of the loop, so the stager's accounting is the truth."""
        if self._stager is not None:
            return self._stager.consumed_state()
        return self.data.state_dict()

    # ------------------------------------------------------------------
    def request_stop(self, reason: str = "requested") -> None:
        """Ask the loop to exit after this step's callbacks finish. The
        checkpointer runs after stop-requesting callbacks (priority order),
        so the stop is checkpointed before the loop breaks."""
        self.should_stop = True
        if self.stop_reason is None:
            self.stop_reason = reason

    def request_rollback(self, reason: str = "diverged") -> None:
        """Ask the loop to restore the last healthy checkpoint after this
        step's callbacks finish (the DivergenceGuardCallback's trip path).
        Without a checkpoint manager the run stops instead — continuing a
        diverged trajectory would only burn compute."""
        if self._rollback_reason is None:
            self._rollback_reason = reason

    def _fire(self, hook: str, *args) -> None:
        for cb in self.callbacks:
            getattr(cb, hook)(self, *args)

    def _fire_abort(self) -> None:
        """Best-effort cleanup when fit() is exiting on an exception and
        ``on_train_end`` will never run — each callback gets its shot even
        if an earlier one fails."""
        for cb in self.callbacks:
            try:
                cb.on_train_abort(self)
            except Exception as e:                      # noqa: BLE001
                print(f"[train] abort cleanup error in "
                      f"{type(cb).__name__}: {e}", flush=True)

    def _perform_rollback(self, at_step: int) -> Optional[int]:
        """Restore the newest checkpoint that verifies + is stamped healthy
        and rewind the data pipeline to it. Returns the step to resume from,
        or ``None`` (with a stop requested) when no rollback is possible."""
        reason = self._rollback_reason
        self._rollback_reason = None
        mgr = self.checkpoint_manager
        if mgr is None:
            print(f"[train] divergence ({reason}) with no checkpoint "
                  "manager — stopping", flush=True)
            self.request_stop("diverged")
            return None
        with sync_allowed("rollback"):
            mgr.wait()
            try:
                _, tree, manifest = mgr.restore_latest_good(
                    self.state, backend=self.backend)
            except FileNotFoundError:
                print(f"[train] divergence ({reason}) and no healthy "
                      "checkpoint to roll back to — stopping", flush=True)
                self.request_stop("diverged")
                return None
            self.state = tree
            self.data.load_state_dict(manifest["extra"]["data"])
            if self._stager is not None:
                # staged-ahead batches predate the rewind — drop them
                self._stager.reset()
        resume = int(manifest["extra"]["train_step"])
        self.sentinel_tripped = False
        self.rollbacks.append(
            {"at_step": at_step, "to_step": resume, "reason": reason})
        print(f"[train] ROLLBACK at step {at_step}: {reason} — resumed "
              f"from checkpoint step {resume}", flush=True)
        return resume

    # ------------------------------------------------------------------
    def fit(self) -> Dict[str, Any]:
        cfg = self.config
        tr = cfg.train
        from repro.resilience import chaos as chaos_lib
        self._chaos = chaos_lib.load_plan(tr.fault_plan)
        if self._chaos is not None:
            # module-global so the checkpoint writer (its own thread) sees
            # the crash points too
            chaos_lib.activate(self._chaos)
        # the span table holds this run's spans and compile events
        tracing.TABLE.reset()
        tracing.TABLE.listen()
        # backend first: distributed bring-up must precede ANY device query
        # (mesh construction, data sharding, state init all depend on it)
        with tracing.span(tracing.SETUP_BACKEND):
            self.backend.setup()
            self.backend.check_consistent(cfg.config_hash())
        with tracing.span(tracing.SETUP_BUILD):
            self.mcfg, self.tcfg, self.data = cfg.build(backend=self.backend)
            mesh = self.backend.mesh()
            run_step = steps_lib.make_run_step(self.mcfg, self.tcfg)

        history = HistoryBuffer(cap=tr.history_cap)
        dispatched_ahead = 0
        dispatch_s = 0.0
        prev_row: Optional[MetricsFuture] = None
        if tr.device_timing:
            self.device_clock = DeviceClock(
                stall_timeout_s=tr.device_timeout_s or None)
        audit_guard = watcher = None
        if tr.audit:
            # fail-fast enforcement of the async-loop contract: any host
            # sync outside a sync_allowed(...) site raises at the call
            # site; any step-signature drift (→ jit re-trace) raises too
            from repro.analysis.recompile import RecompileWatcher
            from repro.analysis.sync_guard import SyncGuard
            audit_guard = SyncGuard(strict=True, label="train.audit")
            watcher = RecompileWatcher(label="run_step")
        completed = False
        try:
            with sh.sharding_rules(mesh):
                with tracing.span(tracing.SETUP_INIT):
                    self.state = steps_lib.init_train_state(
                        self.mcfg, self.tcfg, jax.random.PRNGKey(tr.seed),
                        tr.batch)
                    # every process computes the identical init (same
                    # PRNGKey); replicate makes it the backend's resident
                    # form, the layout the step returns it in
                    self.state = self.backend.replicate(self.state)
                self.num_params = sum(
                    int(np.prod(l.shape)) for l in
                    jax.tree_util.tree_leaves(self.state["params"]))
                self.start_step = 0
                # hooks may restore state + data-pipeline position
                # (checkpoint resume); the iterator is created only after
                self._fire("on_train_start")
                it = None
                if self._chaos is not None:
                    # chaos corrupts HOST batches per step — keep the plain
                    # pull→corrupt→stage path (no lookahead) so injection
                    # sees the batch before it leaves the host
                    it = iter(self.data)
                else:
                    self._stager = BatchStager(
                        self.data, self.backend.shard_batch,
                        depth=self.backend.staging_depth)
                t_start = time.time()
                with contextlib.ExitStack() as audit_scope:
                    if audit_guard is not None:
                        # guard covers the step loop only — state init,
                        # restore hooks, and report assembly sync
                        # legitimately
                        audit_scope.enter_context(audit_guard)
                    step = self.start_step
                    while step < tr.steps:
                        tracing.TABLE.step = step
                        with jax.profiler.StepTraceAnnotation(
                                tracing.STEP, step_num=step):
                            with tracing.span(tracing.DATA_WAIT):
                                if self._chaos is not None:
                                    self._chaos.fire_signals(step)
                                    batch_np = self._chaos.corrupt_batch(
                                        step, next(it))
                                    batch = self.backend.shard_batch(
                                        batch_np)
                                else:
                                    batch = next(self._stager)
                            if watcher is not None:
                                drift = watcher.observe(
                                    step=step, state=self.state, batch=batch)
                                if drift:
                                    raise RuntimeError(
                                        "[train.audit] " +
                                        "; ".join(f.message for f in drift))
                            t0 = time.time()
                            with tracing.span(tracing.DISPATCH):
                                self.state, dev_metrics = run_step(
                                    self.state, batch, step)
                            self.last_step_time = time.time() - t0
                            dispatch_s += self.last_step_time
                            if self.device_clock is not None and dev_metrics:
                                # metrics are detached (jnp.copy) — safe
                                # for the clock thread to hold while
                                # donated buffers are reused
                                marker = dev_metrics.get(
                                    "loss", next(iter(dev_metrics.values())))
                                if self._chaos is not None:
                                    marker = self._chaos.wrap_marker(
                                        step, marker)
                                self.device_clock.observe(step, marker)
                            # dispatch accounting: run_step returning means
                            # step N is ISSUED; if step N−1's metrics are
                            # still device futures at that point, the host
                            # ran ahead of the device queue
                            if prev_row is not None and \
                                    not prev_row.materialized:
                                dispatched_ahead += 1
                            metrics = MetricsFuture(dev_metrics)
                            prev_row = metrics
                            with tracing.span(tracing.CALLBACKS):
                                self._fire("on_step_end", step, metrics)
                            history.append(metrics)
                            if self._rollback_reason is not None:
                                resumed = self._perform_rollback(step)
                                if resumed is not None:
                                    step = resumed
                                    prev_row = None
                                    continue
                            if self.should_stop:
                                break
                        step += 1
                wall = time.time() - t_start
                with tracing.span(tracing.DRAIN):
                    last = history.last
                    report: Dict[str, Any] = {
                        "final_loss": (last["loss"] if last is not None
                                       else None),
                        "history": history.rows(),
                        "wall_s": wall,
                        "config_hash": cfg.config_hash(),
                        "host_loop": {
                            "steps": history.total,
                            "dispatched_ahead": dispatched_ahead,
                            "dispatch_s": dispatch_s,
                        },
                    }
                    if self.device_clock is not None:
                        self.device_clock.drain()
                        report["host_loop"]["device_timed_steps"] = \
                            self.device_clock.timed_steps
                        report["host_loop"]["device_time_s"] = \
                            self.device_clock.total_device_s
                        if self.device_clock.stalled:
                            report["host_loop"]["device_stalled"] = True
                if audit_guard is not None:
                    report["audit"] = {
                        "sync_events": len(audit_guard.events),
                        "unsanctioned": len(audit_guard.violations),
                        "sync_sites": {f"{site}:{kind}": n
                                       for (site, kind), n
                                       in sorted(audit_guard.site_counts()
                                                 .items())},
                        "recompiles": len(watcher.findings),
                    }
                if history.dropped:
                    report["history_dropped"] = history.dropped
                if self.stop_reason is not None:
                    report["stopped"] = self.stop_reason
                if self.rollbacks:
                    report["resilience"] = {"rollbacks": self.rollbacks}
                report["spans"] = tracing.TABLE.summary()
                self._fire("on_train_end", report)
            completed = True
            return report
        finally:
            tracing.TABLE.step = None
            if not completed:
                # exiting on an exception: on_train_end never fires, but
                # signal handlers / open files / writer threads must still
                # be released (chaos crash tests restart in-process)
                self._fire_abort()
            if self._chaos is not None:
                chaos_lib.deactivate()
                self._chaos = None
            if self._stager is not None:
                self._stager.close()
                self._stager = None
            if self.device_clock is not None:
                self.device_clock.close()
            self.backend.teardown()
