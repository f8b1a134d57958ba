"""Probe: what the distinct-row tail of the packed FM step costs on the chip,
against the dense tail — the readings behind the constants of `ops/fm.py`
`tail_cap` (PERF.md section 6, PR 28; read again in PR 30 with the one row
kernel).

Times the WHOLE one-step program of `make_fm_step_minibatch` at the geometry
of the benchmark's cell `fm_criteo.stream` (-dims 2^26 -factors 5: a packed
table of 4,194,304 x 128 float32 and its AdaGrad state, B = 32768, L = 39,
unit values elided), never a phase alone (PR 25: phases alone are not
floors). A variant is a capacity (the shipped rule's, or a forced one), who
updates the distinct rows (ops/rows_pallas.py's kernel, or the XLA gather,
update and scatter that `update_rows` is off a TPU) and a batch with a
chosen number of distinct table rows; `dense` is the step with no ranking
at all. The `wide_*` variants force a capacity of a third of the slots: the
slope of their times over the distinct rows is the kernel's cost a row past
the shipped capacity; the `prior_cap_*` variants force PR 28's capacity: the
shipped capacity's distance from them at the same batch is what a larger
compact gradient costs.
For each: milliseconds a step on the host's clock around 10 steps ended by
`block_until_ready`, then the device operations of 4 traced steps by
`hm.*` scope and by name (the benchmark's own trace reader).

Run on the chip: `python experiments/probe_distinct_tail.py`; one JSON line
per variant, all of them in `chiprun_out/probe_distinct_tail.json`. It
exits non-zero off a TPU (`--tiny` rehearses the script on the CPU at a toy
size: its times mean nothing).
"""
from __future__ import annotations

import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

import jax
import jax.numpy as jnp
import numpy as np

from hivemall_tpu.ops import fm, rows_pallas
from hivemall_tpu.ops.losses import get_loss
from hivemall_tpu.ops.optimizers import make_optimizer

TINY = "--tiny" in sys.argv          # a rehearsal of the script on the CPU
K, B, L = (5, 256, 8) if TINY else (5, 32768, 39)
WF, P = fm.fm_pack_geometry(K)
R = (1 << (17 if TINY else 26)) // P
N = B * L


def batch_ids(rng, n_distinct: int) -> np.ndarray:
    """[B, L] feature ids over exactly `n_distinct` table rows: every row
    of a random pool once, the other slots Zipf(1.25) over the pool."""
    pool = rng.choice(np.arange(1, R), n_distinct, replace=False)
    rows = pool[(rng.zipf(1.25, N) - 1) % n_distinct]
    rows[rng.choice(N, n_distinct, replace=False)] = pool
    return (rows * P + rng.integers(0, P, N)).reshape(B, L).astype(np.int32)


def device_ops(trace_dir: str, steps: int) -> dict:
    from harness import program_trace, xplane
    raw = xplane.read(xplane.find_xplane(trace_dir))
    events = [ev for evs in raw["planes"].values() for ev in evs]
    lo = min(s for s, *_ in events)
    hi = max(s + d for s, d, *_ in events)
    red = xplane.reduce(raw, (lo, hi), [], top=12)
    paths = program_trace.op_paths(xplane.find_xplane(trace_dir))
    phases: dict = {}
    for op, secs, phase in program_trace.phase_table(red["ops"], paths):
        phases[phase or "unscoped"] = phases.get(phase or "unscoped", 0.0) \
            + 1e3 * secs / steps
    return {"busy_ms": 1e3 * red["busy_s"] / steps,
            "phase_ms": {k: round(v, 3) for k, v in sorted(phases.items())},
            "top_ops_ms": [[k, round(1e3 * v / steps, 3)]
                           for k, v in red["device_ops"]]}


def main() -> int:
    from harness import xplane
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not TINY:
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 1
    opt = make_optimizer("adagrad", eta_scheme="inverse", eta0=0.1, reg="no")
    loss = get_loss("logloss")
    rng = np.random.default_rng(28)
    key = jax.random.PRNGKey(0)
    params = {"T": jax.jit(lambda k: 0.1 * jax.random.normal(
        k, (R, P * WF), jnp.float32))(key), "w0": jnp.zeros(())}
    state = {"T": {"gg": jnp.zeros((R, P * WF))}, "w0": {"gg": jnp.zeros(())}}
    label = jnp.asarray(np.where(rng.random(B) < 0.25, 1.0, -1.0)
                        .astype(np.float32))
    mask = jnp.ones(B, jnp.float32)
    # (name, forced capacity or None for the shipped rule, row kernel,
    #  distinct rows). The cell's batches at Zipf 1.25 / 1.5 / 1.05 hold
    # 73.0k / 27.7k / 161.6k distinct rows of 1,277,952 slots (ISSUE 28).
    shipped_cap, kernels = fm.tail_cap, rows_pallas.use_kernels_default
    wide = N // 3 // rows_pallas.LIST_MULTIPLE * rows_pallas.LIST_MULTIPLE
    prior = 256 if TINY else 217_088  # PR 28's capacity, in whole blocks
    variants = [("dense", 0, True, N // 17)]
    variants += [(f"shipped_n/{d}", None, True, int(N / d))
                 for d in (46, 17.5, 10.6, 7.9, 6, 4.6)]
    variants += [("shipped_n/4.3_falls_through", None, True, int(N / 4.3))]
    variants += [(f"prior_cap_n/{d}", prior, True, int(N / d))
                 for d in (46, 17.5, 7.9)]
    variants += [(f"wide_n/{d}", wide, True, int(N / d)) for d in (4.3, 3.3)]
    variants += [("xla_rows_cap_n/16_half", N // 16, False, N // 32)]
    out = []
    for name, cap, use_kernels, nd in variants:
        fm.tail_cap = shipped_cap if cap is None else (lambda n, r, c=cap: c)
        rows_pallas.use_kernels_default = (kernels if use_kernels
                                           else (lambda: False))
        step = fm.make_fm_step_minibatch(loss, opt, (0.0, 0.0, 0.0), K)
        idx = jnp.asarray(batch_ids(rng, nd))
        t0 = time.perf_counter()
        params, state, ls, stats = step(params, state, 1.0, idx, None, label,
                                        mask)
        jax.block_until_ready(ls)
        compile_s = time.perf_counter() - t0
        for _ in range(2):
            params, state, ls, stats = step(params, state, 1.0, idx, None,
                                            label, mask)
        jax.block_until_ready(ls)
        t0 = time.perf_counter()
        for _ in range(10):
            params, state, ls, stats = step(params, state, 1.0, idx, None,
                                            label, mask)
        jax.block_until_ready(ls)
        ms = 1e2 * (time.perf_counter() - t0)
        trace_dir = os.path.join(ROOT, "chiprun_out", "probe_trace", name
                                 .replace("/", "_"))
        xplane.start(trace_dir)
        for _ in range(4):
            params, state, ls, stats = step(params, state, 1.0, idx, None,
                                            label, mask)
        jax.block_until_ready(ls)
        jax.profiler.stop_trace()
        rec = {"variant": name,
               "cap": shipped_cap(N, R) if cap is None else cap,
               "row_kernels": use_kernels, "n_distinct": nd,
               "step_ms": round(ms, 3), "first_call_s": round(compile_s, 1),
               "stats": {k: int(v) for k, v in stats.items()},
               **device_ops(trace_dir, 4)}
        shutil.rmtree(trace_dir, ignore_errors=True)
        print(json.dumps(rec), flush=True)
        out.append(rec)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "probe_distinct_tail.json"),
              "w") as f:
        json.dump({"device": dev.device_kind, "variants": out}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
