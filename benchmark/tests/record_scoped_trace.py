#!/usr/bin/env python3
"""Record the small trace that test_program_trace.py reads: the recipe of
record_small_trace.py (a scanned jitted step, idle sleeps between calls,
the harness's sync annotation) with what the program adds planted in it:
the step's phases under `jax.named_scope` (hm.gather, hm.grad, hm.update
inside hm.scan; no hm.scatter; the scan's own copies outside any phase), and
each call staged and dispatched under the program tracer's `h2d.stage` /
`dispatch.megastep` spans with a `seq`, which the tracer mirrors into the
profiler session. The first input is staged before the session starts,
as the window's first inputs are. Writes <out>.xplane.pb and <out>.json
(what record_small_trace.py writes, plus the planted phases and the
tracer's spans on the perf_counter clock).

    python3 benchmark/tests/record_scoped_trace.py <out-prefix>"""

import glob
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (os.path.dirname(BENCH), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

CALLS = 3
PLANTED = ["hm.gather", "hm.grad", "hm.update"]


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from harness import program_trace
    from hivemall_tpu.obs.trace import get_tracer

    @jax.jit
    def step(x, rows):
        def body(c, _):
            with jax.named_scope("hm.gather"):
                g = c[rows]
            with jax.named_scope("hm.grad"):
                h = jnp.tanh(g @ g.T)
            with jax.named_scope("hm.update"):
                # a sort: no compiler folds it into the matmul's fusion,
                # so the phase keeps an operation of its own everywhere
                c = jnp.sort(c * 0.5 + h, axis=1)
            return c, None
        with jax.named_scope("hm.scan"):
            return jax.lax.scan(body, x, None, length=4)[0]

    n = 512
    rows_host = np.random.default_rng(0).permutation(n).astype(np.int32)
    x = jnp.ones((n, n), jnp.float32)
    step(x, jax.device_put(rows_host)).block_until_ready()
    tracer = get_tracer()
    tracer.reset()
    tracer.enable()

    def stage(seq):
        with tracer.span("h2d.stage", seq):
            rows = jax.device_put(rows_host)
            rows.block_until_ready()
        return rows

    staged = stage(0)                       # before the session, as in a run
    tmp = tempfile.mkdtemp(prefix="scoped_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(tmp, profiler_options=opts)
    sync = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench_sync"):
        pass
    t0 = time.perf_counter()
    spans = []
    for seq in range(CALLS):
        s = time.perf_counter()
        time.sleep(0.02)
        spans.append(("sleep", s, time.perf_counter() - s))
        with tracer.span("dispatch.megastep", seq):
            x = step(x, staged)
        if seq + 1 < CALLS:
            staged = stage(seq + 1)
        x.block_until_ready()
    t1 = time.perf_counter()
    jax.profiler.stop_trace()
    tracer.disable()
    src = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                 "*.xplane.pb"))[0]
    shutil.copy(src, out + ".xplane.pb")
    shutil.rmtree(tmp)
    with open(out + ".json", "w") as f:
        json.dump({"device": str(jax.devices()[0]), "calls": CALLS,
                   "sync_perf": sync, "t0": t0, "t1": t1, "spans": spans,
                   "planted": PLANTED,
                   "program_spans": program_trace.all_spans()}, f)
    tracer.reset()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
