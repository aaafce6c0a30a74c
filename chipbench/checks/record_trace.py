"""Record the small trace ``check_trace`` reads (on a TPU, once).

    python -m chipbench.checks.record_trace

Inside a ``loop`` span: three matrix products of known size, each
dispatched inside a ``dispatch`` span and separated by host sleeps inside
``stage`` spans (idle gaps the reduction must put to ``stage``), then one
more product after a bare sleep (a gap it must put to ``loop``). Writes
``checks/small_trace/`` and ``checks/small_trace.json`` (what was done).
"""
from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def main() -> int:
    import jax
    import jax.numpy as jnp
    out = os.path.join(HERE, "small_trace")
    shutil.rmtree(out, ignore_errors=True)
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    mat = jax.jit(lambda a: a @ a)
    mat(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out, profiler_options=opts)
    with jax.profiler.TraceAnnotation("loop"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("stage"):
                time.sleep(0.05)
            with jax.profiler.TraceAnnotation("dispatch"):
                y = mat(x)
            y.block_until_ready()
        time.sleep(0.05)
        mat(x).block_until_ready()
    jax.profiler.stop_trace()
    # keep only the .xplane.pb
    for path in glob.glob(os.path.join(out, "**", "*"), recursive=True):
        if os.path.isfile(path) and not path.endswith(".xplane.pb"):
            os.remove(path)
    with open(os.path.join(HERE, "small_trace.json"), "w") as f:
        json.dump({"device_kind": jax.devices()[0].device_kind,
                   "matmuls": 4, "shape": [2048, 2048],
                   "stage_sleep_s": 0.05, "stage_gaps": 3,
                   "loop_sleep_s": 0.05}, f, indent=1)
    print(glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
