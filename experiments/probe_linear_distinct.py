"""Probe: what the linear family's distinct-slot step costs on the chip, by
distinct slots a batch, against its dense step — the readings behind
`ops/linear.py` `_COST` and so `distinct_cap` (PERF.md section 6).

Times WHOLE programs, never a phase alone (a phase timed alone is no
floor), at the geometry of the cell `logreg_criteo.stream`:
`train_classifier -loss logloss -opt adagrad` (AdaGrad-RDA), -dims 2^28,
`w`, `u`, `gg` three float32 [2^28] arrays, B = 32768, L = 39, unit values
elided, the MEGASTEP of 8 steps (the cell's dispatch), so the distinct
step's one rebuild of `w` a dispatch is in every reading.

Batches are the cell's rows (`benchmark/harness/data.py`: 39 fields,
Criteo-1TB's vocabularies, ids Zipf(s) within a field, hashed over the
table) at s = 1.5 / 1.25 (the cell's) / 1.05, and uniformly hashed ids.
Programs: `dense` (the step every other optimizer runs), `shipped` (the
distinct-slot step with `distinct_cap`'s capacity: a batch past it takes
the dense branch of the same program) and `wide` (capacity = every slot:
the distinct-slot step at any count, for the slope).

For each: milliseconds a step on the host's clock around 10 megasteps
ended by `block_until_ready`, then the device operations of 2 traced
megasteps by `hm.*` scope and by name (the benchmark's own trace reader).

Run on the chip: `python experiments/probe_linear_distinct.py` (~5
minutes); one JSON line per reading, all of them in
`chiprun_out/probe_linear_distinct.json`. It exits non-zero off a TPU
(`--tiny` rehearses the script on the CPU at a toy size: its times mean
nothing). Nothing a cell runs imports it.
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

from harness import data
from hivemall_tpu.ops import linear
from hivemall_tpu.ops.linear import distinct_cap, make_linear_step
from hivemall_tpu.ops.losses import get_loss
from hivemall_tpu.ops.optimizers import make_optimizer
from hivemall_tpu.ops.scan import make_megastep

TINY = "--tiny" in sys.argv          # a rehearsal of the script on the CPU
B, L, K = (256, 39, 8) if TINY else (32768, 39, 8)
N = 1 << (16 if TINY else 28)
OPT = make_optimizer("adagrad", eta_scheme="inverse", eta0=0.1,
                     power_t=0.1, reg="rda", lam=1e-6)
LOSS = get_loss("logloss")
OUT = os.path.join(ROOT, "chiprun_out", "probe_linear_distinct.json")


def batches(rng, zipf):
    """K batches of the cell's rows at Zipf(zipf), or uniform ids."""
    if zipf is None:
        idx = rng.integers(1, N, (K, B, L), dtype=np.int64)
    else:
        cfg = json.load(open(os.path.join(
            ROOT, "benchmark", "configs", "logreg_criteo.json")))
        spec = data.RowSpec(dict(cfg["data"], zipf_exponent=zipf), N)
        idx = data.draw_ids(spec, K * B, rng).reshape(K, B, L)
    label = np.where(rng.random((K, B)) < 0.25, 1.0, -1.0)
    distinct = [len(np.unique(b)) for b in idx]
    return (jnp.asarray(idx, jnp.int32), jnp.asarray(label, jnp.float32),
            int(np.mean(distinct)))


def device_ops(trace_dir: str, steps: int) -> dict:
    from harness import program_trace, xplane
    raw = xplane.read(xplane.find_xplane(trace_dir))
    events = [ev for evs in raw["planes"].values() for ev in evs]
    lo = min(s for s, *_ in events)
    hi = max(s + d for s, d, *_ in events)
    red = xplane.reduce(raw, (lo, hi), [], top=14)
    paths = program_trace.op_paths(xplane.find_xplane(trace_dir))
    phases: dict = {}
    for op, secs, phase in program_trace.phase_table(red["ops"], paths):
        phases[phase or "unscoped"] = phases.get(phase or "unscoped", 0.0) \
            + 1e3 * secs / steps
    top = sorted(program_trace.phase_table(red["ops"], paths),
                 key=lambda r: -r[1])[:20]
    return {"busy_ms": 1e3 * red["busy_s"] / steps,
            "phase_ms": {k: round(v, 3) for k, v in sorted(phases.items())},
            "top_ops_ms": [[op, round(1e3 * secs / steps, 3), phase]
                           for op, secs, phase in top]}


def reading(mega, state, batch, tag):
    from harness import xplane
    idx, label, _ = batch
    nv = jnp.full((K,), B, jnp.int32)
    t = [8.0]

    def call(state):
        out = mega(*state, t[0], nv, idx, None, label, None, None)
        t[0] += K
        return out[:2], out[2], (out[3] if len(out) > 3 else {})
    t0 = time.perf_counter()
    for i in range(2):
        state, ls, stats = call(state)
        jax.block_until_ready(ls)
        if not i:
            first_call_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(10):
        state, ls, stats = call(state)
    jax.block_until_ready(ls)
    ms = 1e3 * (time.perf_counter() - t0) / (10 * K)
    trace_dir = os.path.join(ROOT, "chiprun_out", "probe_trace", tag)
    xplane.start(trace_dir)
    for _ in range(2):
        state, ls, stats = call(state)
    jax.block_until_ready(ls)
    jax.profiler.stop_trace()
    out = {"step_ms": round(ms, 3), "first_call_s": round(first_call_s, 1),
           "stats": {k: int(v.sum()) for k, v in stats.items()},
           **device_ops(trace_dir, 2 * K)}
    shutil.rmtree(trace_dir, ignore_errors=True)
    return state, out


def main() -> int:
    if not TINY and jax.devices()[0].platform != "tpu":
        print("probe_linear_distinct: not on a TPU", file=sys.stderr)
        return 1
    n = B * L
    rng = np.random.default_rng(41)
    loads = {name: batches(rng, z) for name, z in
             (("zipf1.5", 1.5), ("zipf1.25", 1.25), ("zipf1.05", 1.05),
              ("uniform", None))}
    cap = distinct_cap(n, N)
    wide = n // 8192 * 8192 if not TINY else n // 128 * 128
    programs = [
        ("dense", make_linear_step(LOSS, OPT, distinct=False), ["zipf1.25"]),
        ("shipped", make_linear_step(LOSS, OPT), list(loads)),
        ("wide", None, ["zipf1.05", "uniform"]),
    ]
    init = jax.jit(lambda: (jnp.zeros(N), OPT.init(N)))
    rows = []
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    for name, step, loadnames in programs:
        if step is None:                # every batch within the capacity
            linear.distinct_cap = lambda n, N: wide
            step = make_linear_step(LOSS, OPT)
        mega = make_megastep(step.core, none_val=True,
                             finish=getattr(step, "finish", None))
        state = init()
        for load in loadnames:
            state, r = reading(mega, state, loads[load], f"{name}_{load}")
            r = {"program": name, "load": load,
                 "distinct_a_batch": loads[load][2],
                 "cap": {"dense": 0, "shipped": cap, "wide": wide}[name],
                 **r}
            print(json.dumps(r), flush=True)
            rows.append(r)
            json.dump(rows, open(OUT, "w"), indent=1)
        del state
    return 0


if __name__ == "__main__":
    sys.exit(main())
