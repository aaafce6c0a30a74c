"""Record the small trace ``check_scopes`` reads (on a TPU, once).

    python -m chipbench.checks.record_scopes

A toy train step in the program's five scopes: ``select_forward`` (a
forward over all rows), ``select_decide`` (a top-k of the rows' scores),
``train_grad`` (``value_and_grad`` of a loss over the chosen rows through
two scanned, ``jax.checkpoint``ed layers: forward, backward and
recompute), ``optimizer`` (an SGD update) and ``sentinel`` (a finite check
and select). Inside a ``loop`` span it runs ``steps`` steps, each after a
host sleep inside a ``data_wait`` span. Writes ``checks/scope_trace/``
(the ``.xplane.pb`` only) and ``checks/scope_trace.json`` (what was done).
"""
from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROWS, CHOSEN, WIDTH, LAYERS, STEPS, WAIT_S = 64, 16, 1024, 2, 3, 0.02


def toy_step(params, x):
    import jax
    import jax.numpy as jnp

    def layer(h, w):
        return jnp.tanh(h @ w), None

    def forward(ws, h):
        return jax.lax.scan(jax.checkpoint(layer), h, ws)[0]

    with jax.named_scope("select_forward"):
        scores = jnp.sum(forward(params, x).astype(jnp.float32) ** 2,
                         axis=(1, 2))
    with jax.named_scope("select_decide"):
        _, rows = jax.lax.top_k(scores, CHOSEN)
    with jax.named_scope("train_grad"):
        sub = x[rows]
        loss, grads = jax.value_and_grad(
            lambda ws: jnp.mean(forward(ws, sub) ** 2))(params)
    with jax.named_scope("optimizer"):
        new = params - 0.01 * grads
    with jax.named_scope("sentinel"):
        new = jnp.where(jnp.isfinite(loss), new, params)
    return new, loss


def main() -> int:
    import jax
    import jax.numpy as jnp
    out = os.path.join(HERE, "scope_trace")
    shutil.rmtree(out, ignore_errors=True)
    kp, kx = jax.random.split(jax.random.PRNGKey(0))
    params = jax.random.normal(kp, (LAYERS, WIDTH, WIDTH),
                               jnp.bfloat16) / WIDTH ** 0.5
    x = jax.random.normal(kx, (ROWS, 256, WIDTH), jnp.bfloat16)
    step = jax.jit(toy_step)
    jax.block_until_ready(step(params, x))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out, profiler_options=opts)
    with jax.profiler.TraceAnnotation("loop"):
        for _ in range(STEPS):
            with jax.profiler.TraceAnnotation("data_wait"):
                time.sleep(WAIT_S)
            params, loss = step(params, x)
            jax.block_until_ready(loss)
    jax.profiler.stop_trace()
    for path in glob.glob(os.path.join(out, "**", "*"), recursive=True):
        if os.path.isfile(path) and not path.endswith(".xplane.pb"):
            os.remove(path)
    with open(os.path.join(HERE, "scope_trace.json"), "w") as f:
        json.dump({"device_kind": jax.devices()[0].device_kind,
                   "rows": ROWS, "chosen": CHOSEN, "width": WIDTH,
                   "layers": LAYERS, "steps": STEPS,
                   "data_wait_s": WAIT_S}, f, indent=1)
    print(glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
