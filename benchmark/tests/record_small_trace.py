#!/usr/bin/env python3
"""Record the small trace the reduction's test reads: a scanned jitted step
(so the trace holds a `while` wrapper and its children) with idle sleeps
between calls and the harness's sync annotation, on whatever device JAX
finds. Writes <out>.xplane.pb and <out>.json (what the recorder knows:
calls, window on the perf_counter clock, sync instant).

    python3 benchmark/tests/record_small_trace.py <out-prefix>"""

import glob
import json
import os
import shutil
import sys
import tempfile
import time


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x):
        def body(c, _):
            return jnp.tanh(c @ c) * 0.5, None
        return jax.lax.scan(body, x, None, length=4)[0]

    x = jnp.ones((256, 256), jnp.float32)
    step(x).block_until_ready()
    tmp = tempfile.mkdtemp(prefix="small_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(tmp, profiler_options=opts)
    sync = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench_sync"):
        pass
    t0 = time.perf_counter()
    spans = []
    for _ in range(3):
        s = time.perf_counter()
        time.sleep(0.02)
        spans.append(("sleep", s, time.perf_counter() - s))
        x = step(x)
        x.block_until_ready()
    t1 = time.perf_counter()
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                 "*.xplane.pb"))[0]
    shutil.copy(src, out + ".xplane.pb")
    shutil.rmtree(tmp)
    with open(out + ".json", "w") as f:
        json.dump({"device": str(jax.devices()[0]), "calls": 3,
                   "sync_perf": sync, "t0": t0, "t1": t1, "spans": spans}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
