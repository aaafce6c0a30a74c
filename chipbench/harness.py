"""Drives one benchmark cell through the program's own training path.

A cell is one ``workloads`` entry of ``BENCHMARK.json``: a configuration
file (``configs/<name>.json``) under a traffic file
(``traffic/<name>.json``). Everything here is driven by those files and by
the metric readers in ``metrics/``, found by name.

One run:

1. set-up: the experiment is built from the cell's files; the traffic pool
   is drawn from ``--seed``; ``repro.api.Trainer(cfg).fit()`` inits the
   weights on the device from the seed and runs the first
   ``run_warmup_steps`` steps through the window's own step call and feed.
   The first three of them are the ones the reference follows
   (``Capture``); the last blocks once and opens the window;
2. the window: the same ``fit()`` loop runs on, undisturbed, until the
   steps it has dispatched fill ``--seconds``; the callback then asks the
   trainer to stop and blocks once more;
3. after the window: peak memory is read, the state is freed, and the
   float32 reference runs the first three steps again for the comparison
   that decides ``correct`` (``correct.py``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT = os.path.join(HERE, "out")

# config-file keys (Hugging Face names) → the repo's ModelConfig fields:
# those every block shares (the reference's ``model.py`` reads them); a
# block file's own ``KEYS`` add the rest
MODEL_KEYS = {
    "hidden_size": "d_model", "num_hidden_layers": "num_layers",
    "vocab_size": "vocab_size", "tie_word_embeddings": "tie_embeddings",
    "torch_dtype": "param_dtype",
}
# configuration-file keys that describe the file rather than set the model
# (``block`` names the reference's block file, ``reference/blocks/``)
CONFIG_NOTES = {"name", "source", "paper", "arch", "reduced", "assumed",
                "departures", "deployment", "block"}
TRAFFIC_KEYS = {"name", "kind", "why", "seq_len", "batch_per_chip",
                "pool_steps", "num_clusters", "cluster_stickiness", "sampler",
                "graft", "probe_positions", "loss_chunk", "train_steps",
                "optimizer", "run_warmup_steps"}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]          # the configuration file, as run
    traffic: Dict[str, Any]         # the traffic file
    bench: Dict[str, Any]           # BENCHMARK.json

    @property
    def batch(self) -> int:
        return self.traffic["batch_per_chip"] * self.chips

    @property
    def seq(self) -> int:
        return self.traffic["seq_len"]

    def metrics(self, section: str) -> List[Dict[str, Any]]:
        """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
        return [m for m in self.bench[section]
                if "workloads" not in m or self.name in m["workloads"]]


def find_cell(name: str, root: str = ROOT) -> Cell:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"unknown workload {name!r}; have {sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return Cell(name=name, chips=int(w["chips"]),
                config=load_json(os.path.join(root, conf["file"])),
                traffic=load_json(os.path.join(HERE, "traffic",
                                               w["traffic"] + ".json")),
                bench=bench)


def refuse_unknown(what: str, keys, known) -> None:
    """A key the benchmark would not run, on either side, is an error and
    not a silent default."""
    extra = sorted(set(keys) - set(known))
    if extra:
        raise ValueError(f"{what}: keys the benchmark does not run: {extra}")


def experiment(cell: Cell, seed: int):
    """The cell's ``ExperimentConfig``: library defaults, with the model,
    loop, selection and optimizer fields the cell's files give. Refuses a
    file with a key that neither the program's run nor the reference
    would follow: the common ``MODEL_KEYS``, the ``KEYS`` of the block
    file it names, and ``CONFIG_NOTES``."""
    from repro.api import ExperimentConfig

    from chipbench import traffic as traffic_lib
    from chipbench.reference import blocks
    from chipbench.reference.train import check_traffic
    tr = cell.traffic
    keys = {**MODEL_KEYS, **blocks.of(cell.config).KEYS}
    refuse_unknown(f"configuration of {cell.name}", cell.config,
                   set(keys) | CONFIG_NOTES)
    refuse_unknown(f"traffic of {cell.name}", tr, TRAFFIC_KEYS)
    check_traffic(tr)
    model = {keys[k]: v for k, v in cell.config.items() if k in keys}
    model["loss_chunk"] = tr["loss_chunk"]
    overrides = [
        f"model.arch={cell.config['arch']}", "model.smoke=false",
        f"model.overrides={json.dumps(model)}",
        f"train.steps={tr['train_steps']}", f"train.batch={cell.batch}",
        f"train.seq={cell.seq}", f"train.seed={seed}", "train.log_every=0",
        f"train.sampler={tr['sampler']}",
        f"train.probe_positions={tr['probe_positions']}",
    ] + [f"graft.{k}={json.dumps(v)}" for k, v in tr["graft"].items()] \
      + [f"optimizer.{k}={json.dumps(v)}" for k, v in tr["optimizer"].items()]
    cfg = ExperimentConfig().apply_overrides(overrides)
    data = traffic_lib.PoolConfig(
        vocab_size=cell.config["vocab_size"], seq_len=cell.seq,
        global_batch=cell.batch, seed=seed, pool_steps=tr["pool_steps"],
        num_clusters=tr["num_clusters"],
        cluster_stickiness=tr["cluster_stickiness"])
    return dataclasses.replace(cfg, data=data).finalized()


# ---------------------------------------------------------------------------
# the program side of the comparison
# ---------------------------------------------------------------------------

class Capture:
    """What the reference is compared with, read from the program's own
    state during its first three steps: each step's loss and selection,
    the first gradient as AdamW received it (its first moment after one
    step over ``1 - beta1``) and each leaf's change over the three steps
    (the parameters step 4 receives, less the initial ones)."""

    STEPS = 3

    def __init__(self, beta1: float):
        self.beta1 = beta1
        self.p0: Optional[List[np.ndarray]] = None
        self.names: List[str] = []
        self.losses: List[float] = []
        self.select: List[Dict[str, Any]] = []
        self.grad_norms: Dict[str, float] = {}
        self.change_norms: Dict[str, float] = {}

    def start(self, trainer) -> None:
        import jax

        from chipbench.reference.train import leaf_names
        params = trainer.state["params"]
        self.names = leaf_names(params)
        self.p0 = [np.asarray(x) for x in
                   jax.device_get(jax.tree_util.tree_leaves(params))]

    def step(self, trainer, step: int, metrics) -> None:
        import jax
        import jax.numpy as jnp

        from chipbench.reference.train import leaf_norms
        if step >= self.STEPS:
            return
        self.losses.append(float(metrics["loss"]))
        g = jax.device_get(trainer.state["graft"])
        self.select.append({"pivots": np.asarray(g.pivots).tolist(),
                            "rank": int(g.rank),
                            "weights": np.asarray(g.weights).tolist()})
        if step == 0:
            norms = leaf_norms(trainer.state["opt"]["m"])
            self.grad_norms = {n: float(v) / (1.0 - self.beta1)
                               for n, v in zip(self.names, norms)}
        if step == self.STEPS - 1:
            diff = jax.jit(lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
                x.astype(jnp.float32) - y.astype(jnp.float32)))))
            now = jax.tree_util.tree_leaves(trainer.state["params"])
            for name, a, b in zip(self.names, now, self.p0):
                self.change_norms[name] = float(
                    diff(a, jax.device_put(b, a.sharding)))
            self.p0 = None

    def readings(self) -> Dict[str, Any]:
        return {"losses": self.losses, "select": self.select,
                "grad_norms": self.grad_norms,
                "change_norms": self.change_norms}


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

def _marker(metrics):
    """The step's loss as a device array, without reading it."""
    data = getattr(metrics, "_data", None)
    if isinstance(data, dict):
        return data.get("loss")
    return None


class Window:
    """Callbacks of the benchmark (built lazily so that importing this
    module touches nothing of the program)."""

    def __init__(self, warmup: int, seconds: float, capture: Capture,
                 t_process: float, trace_dir: Optional[str]):
        self.warmup = warmup
        self.seconds = seconds
        self.capture = capture
        self.t_process = t_process
        self.trace_dir = trace_dir
        self.t_prev = self.t_open = self.t_close = None
        self.first_step = self.last_step = None
        self.pending: list = []
        self.completed = 0
        self.closed = False
        self.compiles_in_window = 0
        self._loop_span = self._span = None

    def callbacks(self):
        from repro.api.callbacks import Callback
        win = self

        class Open(Callback):
            priority = -1000

            def on_train_start(self, trainer):
                win.phase("weights made")
                win.capture.start(trainer)
                win.phase("initial weights read")

            def on_step_end(self, trainer, step, metrics):
                win.enter_span("callbacks")

        class Close(Callback):
            priority = 1000

            def on_step_end(self, trainer, step, metrics):
                win.on_step_end(trainer, step, metrics)
                win.exit_span()

        return [Open(), Close()]

    def phase(self, what: str) -> None:
        log(f"set-up: {what} at {time.time() - self.t_process:.2f} s")

    def enter_span(self, name: str) -> None:
        import jax
        self._span = jax.profiler.TraceAnnotation(name)
        self._span.__enter__()

    def exit_span(self) -> None:
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None

    def on_step_end(self, trainer, step: int, metrics) -> None:
        import jax
        if self.closed:
            return
        if step < self.warmup:
            self.capture.step(trainer, step, metrics)
            self.phase(f"warm-up step {step} done")
            if step == self.warmup - 2:
                jax.block_until_ready(trainer.state)
                self.t_prev = time.time()
            elif step == self.warmup - 1:
                jax.block_until_ready(trainer.state)
                self.open()
            return
        self.pending.append(_marker(metrics))
        while self.pending and self.pending[0] is not None \
                and self.pending[0].is_ready():
            self.pending.pop(0)
            self.completed += 1
        elapsed = time.time() - self.t_open
        dispatched = step - self.first_step + 1
        per_step = elapsed / self.completed if self.completed \
            else self.est_step_s
        # stop dispatching once the steps already queued fill the window
        if elapsed + (dispatched - self.completed) * per_step >= self.seconds:
            trainer.request_stop("benchmark window")
            jax.block_until_ready(trainer.state)
            self.close(step)

    def open(self) -> None:
        import jax
        self.t_open = time.time()
        self.est_step_s = self.t_open - self.t_prev
        self.setup_s = self.t_open - self.t_process
        self.first_step = self.warmup
        if self.trace_dir:
            jax.profiler.start_trace(self.trace_dir,
                                     profiler_options=_profile_options())
        self._loop_span = jax.profiler.TraceAnnotation("loop")
        self._loop_span.__enter__()
        self._watch_compiles(True)

    def close(self, step: int) -> None:
        import jax
        self.t_close = time.time()
        self.last_step = step
        self.closed = True
        self._watch_compiles(False)
        self._loop_span.__exit__(None, None, None)
        if self.trace_dir:
            jax.profiler.stop_trace()

    def _watch_compiles(self, on: bool) -> None:
        from jax import monitoring
        if on:
            def listener(event, duration, **kw):
                if event.endswith("backend_compile_duration"):
                    self.compiles_in_window += 1
                    log(f"compiled in the window: {kw}")
            self._listener = listener
            monitoring.register_event_duration_secs_listener(listener)
        else:
            monitoring.unregister_event_duration_listener(self._listener)

    @property
    def steps(self) -> int:
        return self.last_step - self.first_step + 1

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open


def _profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


# ---------------------------------------------------------------------------
# spans around the calls into the program's layers
# ---------------------------------------------------------------------------

def staging_backend(devices, spans: List[float]):
    """The program's local backend over the cell's chips, with a host span
    ``stage`` around every ``shard_batch`` (its seconds go to ``spans``)."""
    import jax
    from repro.backend.base import LocalBackendConfig
    from repro.backend.local import LocalBackend
    from repro.launch.mesh import make_mesh

    class StagingBackend(LocalBackend):
        def _build_mesh(self):
            return make_mesh((len(devices),), ("data",), devices=devices)

        def shard_batch(self, batch):
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation("stage"):
                out = super().shard_batch(batch)
            spans.append(time.perf_counter() - t)
            return out

    return StagingBackend(LocalBackendConfig())


@contextlib.contextmanager
def dispatch_spans(jitted: list):
    """A host span ``dispatch`` around every train-step call the trainer
    makes (the trainer builds its step with ``make_run_step``); the jitted
    functions that call builds go to ``jitted``."""
    import jax
    from repro.launch import steps as steps_lib
    orig = steps_lib.make_run_step

    def make_run_step(*args, **kwargs):
        real_jit = jax.jit

        def spy(fun, *a, **kw):
            out = real_jit(fun, *a, **kw)
            jitted.append(out)
            return out
        jax.jit = spy
        try:
            run_step = orig(*args, **kwargs)
        finally:
            jax.jit = real_jit

        def annotated(state, batch, step):
            with jax.profiler.TraceAnnotation("dispatch"):
                return run_step(state, batch, step)
        return annotated

    steps_lib.make_run_step = make_run_step
    try:
        yield
    finally:
        steps_lib.make_run_step = orig


# ---------------------------------------------------------------------------
# metric readers
# ---------------------------------------------------------------------------

def load_reader(name: str) -> Callable[[Dict[str, Any]], Optional[float]]:
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_metrics(entries, ctx) -> Dict[str, Dict[str, Any]]:
    out = {}
    for m in entries:
        value = load_reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def configure_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    holding every program, so that only a checkout's first run compiles.
    The directory is the benchmark's own: one that the environment names
    may hold entries written under other settings. No size limit: the
    cells' programs together take a few hundred MB, and an eviction would
    put a compilation back into some run's set-up."""
    import jax
    path = os.path.join(OUT, "jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def devices_for(cell: Cell, require_tpu: bool):
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise SystemExit(f"chipbench: needs a TPU, found "
                         f"{devices[0].platform}")
    if len(devices) < cell.chips:
        raise SystemExit(f"chipbench: {cell.name} needs {cell.chips} chips, "
                         f"found {len(devices)}")
    return devices[:cell.chips]


def step_memory(trainer, jitted) -> Optional[int]:
    """Bytes a chip holds while the trainer's own compiled step runs: its
    arguments, temporaries, and outputs not aliased to an argument
    (``memory_analysis`` of the executable the window ran; the lowering
    and the executable come from JAX's caches)."""
    from repro.distributed import sharding as sh
    batch = trainer.backend.shard_batch(trainer.data.batch_at(0))
    with sh.sharding_rules(trainer.backend.mesh()):
        mem = jitted.lower(trainer.state, batch).compile().memory_analysis()
    if mem is None:
        return None
    return int(mem.argument_size_in_bytes + mem.temp_size_in_bytes
               + mem.output_size_in_bytes - mem.alias_size_in_bytes)


def selection_probe(trainer, calls: int, trace_dir: str) -> Optional[float]:
    """Device seconds per call of the selection-only program
    (``make_train_step(..., kind="select")``) on the trainer's state and
    its first batch, read from a trace of ``calls`` calls."""
    import jax
    from repro.distributed import sharding as sh
    from repro.launch import steps as steps_lib

    from chipbench import trace as trace_lib
    select = steps_lib.make_train_step(trainer.mcfg, trainer.tcfg,
                                       kind="select")
    fn = jax.jit(lambda s, b: select(s, b)[1])
    batch = trainer.backend.shard_batch(trainer.data.batch_at(0))
    with sh.sharding_rules(trainer.backend.mesh()):
        jax.block_until_ready(fn(trainer.state, batch))
        jax.profiler.start_trace(trace_dir, profiler_options=_profile_options())
        with jax.profiler.TraceAnnotation("loop"):
            for _ in range(calls):
                jax.block_until_ready(fn(trainer.state, batch))
        jax.profiler.stop_trace()
    red = trace_lib.reduce(trace_lib.load(trace_dir))
    return red.busy_s / calls if red.busy_s else None


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_process: float, require_tpu: bool = True,
        check: Optional[Callable] = None) -> Dict[str, Any]:
    """One run of ``cell``; returns the result object."""
    import jax

    from chipbench import correct as correct_lib
    from chipbench import trace as trace_lib
    from chipbench import traffic as traffic_lib
    devices = devices_for(cell, require_tpu)
    configure_cache()
    traffic_lib.register()
    from repro.api import Trainer
    cfg = experiment(cell, seed)
    trace_dir = None
    if trace:
        trace_dir = os.path.join(OUT, "trace", cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
    capture = Capture(cfg.optimizer.beta1)
    win = Window(cell.traffic["run_warmup_steps"], seconds, capture,
                 t_process, os.path.join(trace_dir, "window")
                 if trace_dir else None)
    stage_s: List[float] = []
    backend = staging_backend(devices, stage_s)
    trainer = Trainer(cfg, callbacks=win.callbacks(), backend=backend)
    win.phase("trainer built")
    jitted: list = []
    with dispatch_spans(jitted):
        report = trainer.fit()
    if not win.closed:
        raise RuntimeError(f"the window never closed: {report.get('stopped')}")
    history = report["history"]
    window_rows = history[win.first_step:win.last_step + 1]
    # the fullest chip's peak: the allocator's, or the compiled step's
    # arguments, temporaries and outputs, whichever is larger
    in_use = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
              for d in devices]
    compiled = None
    if len(jitted) == 1:
        try:
            compiled = step_memory(trainer, jitted[0])
        except Exception as e:          # the allocator's peak still stands
            log(f"memory: no analysis of the compiled step: {e!r}")
    memory_peak = max(in_use + [compiled or 0]) or None
    log(f"memory: peak_bytes_in_use {in_use}, compiled step {compiled}")
    ctx: Dict[str, Any] = {
        "cell": cell, "window": win, "rows": window_rows,
        "stage_s": stage_s[win.first_step:win.last_step + 1],
        "peak": load_json(os.path.join(HERE, "peaks.json")).get(
            devices[0].device_kind),
        "trace": None, "selection_s": None,
    }
    if trace:
        ctx["trace"] = trace_lib.reduce(trace_lib.load(win.trace_dir),
                                        devices=len(devices))
        ctx["selection_s"] = selection_probe(
            trainer, 3, os.path.join(trace_dir, "select"))
    trainer.state = None
    del trainer
    import gc
    gc.collect()
    readings = capture.readings()
    checker = check or correct_lib.check
    correct, compared = checker(cell, seed, readings)
    metrics = read_metrics(cell.metrics("per_layer" if trace
                                        else "end_to_end"), ctx)
    failed = sum(1 for r in window_rows if r.get("healthy", 1.0) < 1.0)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    result: Dict[str, Any] = {"correct": bool(correct),
                              "attempted": win.steps, "failed": failed,
                              "metrics": metrics, "device": device}
    if trace:
        red = ctx["trace"]
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        result["breakdown"] = red.breakdown()
    log(f"window: {win.steps} steps in {win.window_s:.3f} s after "
        f"{win.setup_s:.3f} s of set-up; compiles in the window: "
        f"{win.compiles_in_window}")
    log(f"program: losses {readings['losses']} select "
        f"{[(s['pivots'], s['rank']) for s in readings['select']]}")
    result["compared"] = compared
    return result
