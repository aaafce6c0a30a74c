"""Lifecycle callbacks — everything the training loop does besides stepping.

The ``Trainer`` loop is pure step-dispatch; checkpointing, held-out eval,
JSONL telemetry, straggler monitoring, console logging, and
preemption/early-stop are all ``Callback`` plugins dispatched at four hooks:

  * ``on_train_start(trainer)``             — after state init, BEFORE the
    data iterator is created (so a restore can rewind the pipeline)
  * ``on_step_end(trainer, step, metrics)`` — once per step, in ascending
    ``priority`` order; callbacks may mutate ``metrics`` in place (eval
    merges its numbers here) and call ``trainer.request_stop(reason)``
  * ``on_checkpoint(trainer, step, path)``  — after a checkpoint commits
  * ``on_train_end(trainer, report)``       — once, may enrich the report

Ordering contract (the ``priority`` numbers below): preemption decides stop
BEFORE eval/telemetry run, eval merges metrics BEFORE the JSONL logger
queues them, and the checkpointer runs LAST so a stop request is always
checkpointed before the loop exits (checkpoint-before-stop).

The ``metrics`` argument of ``on_step_end`` is a lazy
``MetricsFuture`` over device scalars: reading a VALUE (``metrics["loss"]``)
syncs the host on the device queue, so a callback on the per-step path
should only touch values at its own boundaries (print steps, checkpoint
saves, flush drains) — key-level checks (``"eval_loss" in metrics``) are
always free.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Dict, Optional

import jax

from repro.analysis.sync_guard import sync_allowed
from repro.checkpoint import CheckpointManager, EmergencySaver
from repro.distributed.straggler import StragglerMonitor
from repro.launch.evaluate import make_eval_fn_for
from repro.launch.metrics import (MetricsLogger, format_step_line,
                                  graft_step_flops, materialize_metrics,
                                  peak_flops, sanitize_row, train_step_flops)
from repro.selection.overlap import SideStream


class Callback:
    """Base lifecycle plugin. Lower ``priority`` runs earlier in every hook.

    The default (50) sits between the stock telemetry plugins (10-40) and
    the checkpointer (90), so a user callback that calls
    ``trainer.request_stop()`` still gets its stop checkpointed in the same
    step — keep custom priorities below 90 to preserve that guarantee."""
    priority: int = 50

    def on_train_start(self, trainer) -> None:
        pass

    def on_step_end(self, trainer, step: int, metrics: Dict[str, Any]) -> None:
        pass

    def on_checkpoint(self, trainer, step: int, path: str) -> None:
        pass

    def on_train_end(self, trainer, report: Dict[str, Any]) -> None:
        pass

    def on_train_abort(self, trainer) -> None:
        """Fired (instead of ``on_train_end``) when ``fit()`` exits on an
        exception — release external resources here (signal handlers, open
        files, writer threads) so a crashed run can be restarted in the
        same process. Exceptions raised here are logged, not propagated."""


class PreemptionCallback(Callback):
    """SIGTERM/SIGINT emergency stop + ``stop_after`` simulated preemption.
    Runs first so the checkpointer (last) sees the stop request in the same
    step — the checkpoint-before-stop ordering guarantee."""
    priority = 10

    def __init__(self, stop_after: Optional[int] = None):
        self.stop_after = stop_after
        self.saver: Optional[EmergencySaver] = None

    def on_train_start(self, trainer) -> None:
        self.saver = EmergencySaver()

    def on_step_end(self, trainer, step, metrics) -> None:
        if self.saver is not None and self.saver.should_stop:
            trainer.request_stop("preempted")
        elif self.stop_after is not None and step + 1 >= self.stop_after:
            trainer.request_stop("stop_after")

    def on_train_end(self, trainer, report) -> None:
        if self.saver is not None:
            self.saver.restore_handlers()

    def on_train_abort(self, trainer) -> None:
        # a crashed fit() must not leave our handlers (and their stale
        # stop flag) installed for the next Trainer in this process
        if self.saver is not None:
            self.saver.restore_handlers()


class EvalCallback(Callback):
    """Held-out eval every N steps, OFF the critical path by default.

    At an eval boundary the jitted evals are DISPATCHED as a non-donated
    side stream against the live ``state["params"]`` (safe because the
    dispatch happens before the trainer issues the next donating step —
    the ``SideStream`` discipline shared with the ``OverlappedSelector``)
    and the device-scalar results merge into the step's ``MetricsFuture``
    immediately, tagged with the step they were dispatched at; the actual
    numbers are collected at the NEXT eval boundary (or ``on_train_end``),
    by which point the device finished them long ago. ``sync=True``
    restores blocking eval inside ``on_step_end`` (the escape hatch for
    tests) — both modes run the identical device computation, so the
    numbers are bit-identical."""
    priority = 20

    def __init__(self, every: int, num_batches: int = 4, sync: bool = False):
        self.every = every
        self.num_batches = num_batches
        self.sync = sync
        self.eval_fn = None
        self.stream = SideStream()

    def on_train_start(self, trainer) -> None:
        self.eval_fn = make_eval_fn_for(trainer.config, trainer.mcfg,
                                        num_batches=self.num_batches)

    def on_step_end(self, trainer, step, metrics) -> None:
        if not (self.every and (step + 1) % self.every == 0):
            return
        if self.sync:
            metrics.update(self.eval_fn(trainer.state["params"]))
            return
        handle = self.eval_fn.dispatch(trainer.state["params"])
        metrics.update(handle)          # row tagged with the dispatch step
        self.stream.launch(step, handle)  # collects the PREVIOUS boundary's

    def on_train_end(self, trainer, report) -> None:
        self.stream.drain()             # nothing in flight past the loop


class MetricsCallback(Callback):
    """JSONL telemetry stream + throughput/MFU tracking. Runs after eval so
    held-out numbers reach the stream (one row per step). Rows are queued
    lazily (device values and all) and the logger materializes + writes
    only every ``flush_every`` steps and on close, so ``on_step_end`` pays
    neither a device sync nor a write syscall per step. Step timing comes
    from the trainer's dispatch clock, not the gap between log calls —
    eval/checkpoint pauses land in ``host_overhead_s``, not in ``mfu``.
    A GRAFT row's ``mfu`` counts the work its own rank required
    (``graft_step_flops``), read at flush."""
    priority = 30

    def __init__(self, path: Optional[str] = None, flush_every: int = 20):
        self.path = path
        self.flush_every = flush_every
        self.logger: Optional[MetricsLogger] = None
        self._primed = False

    def on_train_start(self, trainer) -> None:
        tr = trainer.config.train
        row_flops = None
        if trainer.tcfg.use_graft:
            every = trainer.tcfg.graft.refresh_every

            def row_flops(row):
                # a GRAFT step's work follows the rank it chose
                if "rank" not in row:
                    return None
                return graft_step_flops(
                    trainer.num_params, trainer.mcfg, tr.batch, tr.seq,
                    trainer.tcfg.probe_positions, int(row["rank"]),
                    refresh=row["step"] % every == 0)
        self.logger = MetricsLogger(
            self.path, num_chips=trainer.backend.device_count(),
            flops_per_step=train_step_flops(
                trainer.num_params, tr.batch * tr.seq,
                remat=trainer.mcfg.remat != "none",
                mcfg=trainer.mcfg, seq=tr.seq),
            flush_every=self.flush_every,
            device_clock=trainer.device_clock,
            peak_flops_per_chip=peak_flops(jax.devices()[0].device_kind),
            row_flops=row_flops)

    def on_step_end(self, trainer, step, metrics) -> None:
        tr = trainer.config.train
        tokens = tr.batch * tr.seq
        if not self._primed:
            # checkpoint resume hands this fresh logger a mid-run step
            # counter (start_step is only known after the checkpointer's
            # on_train_start): seed the cumulative token counter so
            # resumed runs don't report tokens_seen from zero
            if trainer.start_step:
                self.logger.tokens_seen = trainer.start_step * tokens
            self._primed = True
        self.logger.log(step, metrics, tokens=tokens,
                        step_time=trainer.last_step_time)

    def on_train_end(self, trainer, report) -> None:
        if self.logger is not None:
            self.logger.close()
            report.setdefault("host_loop", {})["metrics_drain_s"] = \
                self.logger.drain_s

    def on_train_abort(self, trainer) -> None:
        if self.logger is not None:
            self.logger.close()     # flush the buffered tail of the stream


class StragglerCallback(Callback):
    """Per-step time distribution; summary lands in the report. With the
    trainer's :class:`DeviceClock` active the monitor is fed DEVICE step
    times (completion-stamp deltas, drained as they land) — dispatch jitter
    on an async host loop says nothing about a slow device. Without the
    clock it falls back to the dispatch clock."""
    priority = 40

    def __init__(self):
        self.monitor = StragglerMonitor()
        self._source = "dispatch"

    def on_train_start(self, trainer) -> None:
        # per-process attribution: the fleet view (merge_summaries) names
        # the worst host, so each monitor's summary carries its rank
        self.monitor.process_index = trainer.backend.process_index

    def on_step_end(self, trainer, step, metrics) -> None:
        if trainer.device_clock is not None:
            self._source = "device"
            for _, dt in trainer.device_clock.poll():
                self.monitor.record(dt)
        else:
            self.monitor.record(trainer.last_step_time)

    def on_train_end(self, trainer, report) -> None:
        if trainer.device_clock is not None:
            trainer.device_clock.drain()
            for _, dt in trainer.device_clock.poll():
                self.monitor.record(dt)
        summary = self.monitor.summary()
        summary["source"] = self._source
        report["straggler"] = summary


class LegacyFunctionCallback(Callback):
    """Adapter for the pre-API ``train(run, callbacks=[fn])`` hook:
    ``fn(step, state, metrics)`` once per step."""
    priority = 55

    def __init__(self, fn: Callable[[int, Any, Dict[str, Any]], None]):
        self.fn = fn

    def on_step_end(self, trainer, step, metrics) -> None:
        self.fn(step, trainer.state, metrics)


class ConsoleCallback(Callback):
    """Progress lines every ``log_every`` steps (post-eval metrics). Only
    the rows actually printed are materialized — the cadence check is
    key-free, so non-print steps stay sync-free."""
    priority = 60

    def __init__(self, every: int = 10):
        self.every = every

    def on_step_end(self, trainer, step, metrics) -> None:
        if self.every and step % self.every == 0:
            with sync_allowed("console"):
                print(format_step_line(step, metrics, trainer.last_step_time,
                                       use_graft=trainer.tcfg.use_graft),
                      flush=True)


class CheckpointCallback(Callback):
    """Fault-tolerant checkpointing: auto-restore on start, periodic +
    final + stop-triggered saves, manifest embedding of the finalized
    ``ExperimentConfig`` so a resume needs nothing but the directory.

    Runs LAST in ``on_step_end`` so any stop requested earlier in the same
    step (preemption, ``stop_after``) is checkpointed before the loop exits.
    """
    priority = 90

    def __init__(self, directory: str, every: int = 50, keep_last_n: int = 2,
                 async_save: bool = True, restore: bool = True):
        self.directory = directory
        self.every = every
        self.restore = restore
        self.manager = CheckpointManager(directory, keep_last_n=keep_last_n,
                                         async_save=async_save)

    def on_train_start(self, trainer) -> None:
        trainer.checkpoint_manager = self.manager
        if not self.restore:
            return
        try:
            # newest checkpoint that verifies (checksums) AND is stamped
            # healthy — a bit-flipped or mid-crash dir is quarantined to
            # corrupt.<step> and the walk falls back to the previous one
            _, tree, manifest = self.manager.restore_latest_good(
                trainer.state, backend=trainer.backend)
        except FileNotFoundError:
            return                            # fresh run — nothing on disk
        trainer.state = tree
        # restore the full pipeline state from the manifest ONCE — the
        # trainer creates its iterator only after on_train_start, so
        # nothing can clobber this
        trainer.data.load_state_dict(manifest["extra"]["data"])
        trainer.start_step = int(manifest["extra"]["train_step"])
        saved_hash = manifest["extra"].get("config_hash")
        ours = trainer.config.config_hash()
        if saved_hash is not None and saved_hash != ours:
            print(f"[train] WARNING: resuming config {ours} from a "
                  f"checkpoint written by config {saved_hash}")
        print(f"[train] resumed from step {trainer.start_step}")

    def on_step_end(self, trainer, step, metrics) -> None:
        total = trainer.config.train.steps
        due = (step + 1) % self.every == 0
        if not (due or trainer.should_stop or step + 1 == total):
            return
        if trainer.sentinel_tripped:
            # the divergence guard tripped earlier in this hook pass: the
            # live state is poisoned — refusing to save means keep-last-N
            # can never rotate entirely onto bad states while the trainer
            # rolls back (and GC won't run either, since it runs in save)
            if trainer.backend.is_primary:
                print(f"[ckpt] sentinel tripped — refusing to save step "
                      f"{step + 1}", flush=True)
            return
        with sync_allowed("checkpoint"):
            # a checkpoint boundary is a legitimate sync point: the
            # manifest needs JSON floats, not device futures
            vals = materialize_metrics(metrics)
            healthy = (vals.get("healthy", 1.0) >= 0.5
                       and math.isfinite(vals.get("loss", 0.0)))
            # the state gather is a COLLECTIVE on multi-process backends
            # (sharded leaves allgather across ranks) — every process must
            # participate or the primary deadlocks waiting for peers that
            # already moved on. One writer per run: every process gathers
            # (and RESTOREs in on_train_start), only process 0 writes.
            host_state = trainer.backend.to_host(trainer.state)
            if not trainer.backend.is_primary:
                return
            path = self.manager.save(
                step + 1, host_state,
                topology=trainer.backend.topology(),
                extra={"train_step": step + 1,
                       "data": trainer.data_state(),
                       "metrics": sanitize_row(vals),
                       "health": {"healthy": bool(healthy),
                                  "bad_streak":
                                      int(vals.get("bad_streak", 0.0))},
                       "experiment": trainer.config.to_dict(),
                       "config_hash": trainer.config.config_hash()})
        listeners = [cb for cb in trainer.callbacks
                     if type(cb).on_checkpoint is not Callback.on_checkpoint]
        if listeners:
            # the hook contract is "after the checkpoint commits": an async
            # save returns before the tmp→final rename, so join the writer
            # before announcing. No listeners → keep the save fully async.
            self.manager.wait()
            for cb in listeners:
                cb.on_checkpoint(trainer, step, path)
        if trainer.should_stop:
            print("[train] emergency checkpoint written — exiting")

    def on_train_end(self, trainer, report) -> None:
        self.manager.wait()

    def on_train_abort(self, trainer) -> None:
        try:
            self.manager.wait()
        except Exception:       # noqa: BLE001 — a writer that died
            pass                # mid-save left its breadcrumbs on disk;
                                # _recover() rolls them back on restart


class HookRecorder(Callback):
    """Test/debug helper: records (hook, step) tuples in call order."""
    priority = 95

    def __init__(self):
        self.events = []

    def on_train_start(self, trainer) -> None:
        self.events.append(("on_train_start", None))

    def on_step_end(self, trainer, step, metrics) -> None:
        self.events.append(("on_step_end", step))

    def on_checkpoint(self, trainer, step, path) -> None:
        self.events.append(("on_checkpoint", step))

    def on_train_end(self, trainer, report) -> None:
        self.events.append(("on_train_end", None))


def default_callbacks(cfg) -> list:
    """The stock plugin set for an ``ExperimentConfig`` (mirrors what the
    legacy monolithic loop hardwired)."""
    tr = cfg.train
    cbs: list = [PreemptionCallback(tr.stop_after)]
    if tr.eval_every:
        cbs.append(EvalCallback(tr.eval_every, sync=tr.sync_eval))
    cbs.append(MetricsCallback(tr.metrics_path,
                               flush_every=tr.metrics_flush_every))
    cbs.append(StragglerCallback())
    if tr.sentinel:
        # lazy: repro.resilience.guard imports this module
        from repro.resilience.guard import DivergenceGuardCallback
        cbs.append(DivergenceGuardCallback(
            patience=tr.bad_step_patience,
            check_every=max(1, tr.metrics_flush_every)))
    if tr.log_every:
        cbs.append(ConsoleCallback(tr.log_every))
    if tr.checkpoint_dir:
        cbs.append(CheckpointCallback(tr.checkpoint_dir,
                                      every=tr.checkpoint_every))
    return cbs
