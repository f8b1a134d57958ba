"""Probe: what the distinct-row tail of a minibatch step costs on the chip,
against the dense tail — the readings behind `ops/fm.py` `_TAIL_COSTS`, one
entry a geometry (PERF.md section 6: PR 28, read again in PR 30 with the one
row kernel; the flagship's geometry in PR 32).

Times WHOLE programs, never a phase alone (PR 25: phases alone are not
floors), at the geometry of a cell of the benchmark:

  fm   `fm_criteo.stream`: `make_fm_step_minibatch`, -dims 2^26 -factors 5,
       a packed table of 4,194,304 x 128 float32 and its AdaGrad state,
       B = 32768, L = 39, unit values elided; the one-step program.
  ffm  `ffm_criteo_joint.stream`: `make_ffm_step_fused` (fieldmajor, unit
       values), -dims 2^28 -fields 39 -factors 4 -halffloat, a table of
       4,194,304 x 164 bfloat16 and its float32 AdaGrad state; the MEGASTEP
       of 4 steps, divided by 4: the chip keeps a 164-lane table transposed
       (`{0,1}`), a program relayouts it on the way in and out, and the
       cell's megastep pays that once a dispatch, a one-step program every
       step.

A variant is a capacity (the shipped rule's, or a forced one), who updates
the distinct rows (ops/rows_pallas.py's kernel where Mosaic takes the
tables, or XLA's gather, update and scatter in blocks of a chosen size) and
batches with chosen numbers of distinct table rows; `dense` is the step
with no ranking at all. A forced capacity of a third of the slots (`wide`)
gives the cost a distinct row past the shipped capacity by the slope of its
times; a small forced one at the same batch what a capacity row costs; one
block as long as the list (`oneshot`) what the blocks' loop saves.
For each: milliseconds a step on the host's clock around 10 calls ended by
`block_until_ready`, then the device operations of 4 traced calls by `hm.*`
scope and by name (the benchmark's own trace reader).

Run on the chip: `python experiments/probe_distinct_tail.py [fm] [ffm]`
(default: both); one JSON line per reading, all of them in
`chiprun_out/probe_distinct_tail.json`. It exits non-zero off a TPU
(`--tiny` rehearses the script on the CPU at a toy size: its times mean
nothing).
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
from hivemall_tpu.ops.scan import make_megastep

TINY = "--tiny" in sys.argv          # a rehearsal of the script on the CPU
B, L = (256, 8) if TINY else (32768, 39)
N = B * L
R = 1 << (14 if TINY else 22)        # table rows, both geometries
OPT = make_optimizer("adagrad", eta_scheme="inverse", eta0=0.1, reg="no")
LOSS = get_loss("logloss")


def zipf_over(rng, pool: np.ndarray) -> np.ndarray:
    """N draws over `pool`, every member once, the rest Zipf(1.25)."""
    out = pool[(rng.zipf(1.25, N) - 1) % len(pool)]
    out[rng.choice(N, len(pool), replace=False)] = pool
    return out


class FM:
    """`fm_criteo.stream`: ids over exactly n_distinct packed rows."""
    name, steps, W, itemsize = "fm", 1, 128, 4
    K = 5
    WF, P = fm.fm_pack_geometry(K)

    def state(self, key):
        T = jax.jit(lambda k: 0.1 * jax.random.normal(
            k, (R, self.P * self.WF), jnp.float32))(key)
        return ({"T": T, "w0": jnp.zeros(())},
                {"T": {"gg": jnp.zeros((R, self.P * self.WF))},
                 "w0": {"gg": jnp.zeros(())}})

    def program(self):
        step = fm.make_fm_step_minibatch(LOSS, OPT, (0.0, 0.0, 0.0), self.K)
        return lambda p, s, idx, label, mask: step(p, s, 1.0, idx, None,
                                                   label, mask)

    def ids(self, rng, n_distinct):
        rows = zipf_over(rng, rng.choice(np.arange(1, R), n_distinct,
                                         replace=False))
        return (rows * self.P + rng.integers(0, self.P, N)) \
            .reshape(B, L).astype(np.int32)


class FFM:
    """`ffm_criteo_joint.stream`: n_distinct feature ids, hashed into the
    table's rows by the step (a few collide: the step's own count of
    distinct rows is recorded beside the asked one)."""
    name, steps, W, itemsize = "ffm", 4, 164, 2
    F, K = (8, 4) if TINY else (39, 4)
    step_kw: dict = {}                  # more of make_ffm_step_fused's

    def __init__(self):
        self.W = self.F * self.K + 8

    def state(self, key):
        T = jax.jit(lambda k: (0.1 * jax.random.normal(
            k, (R, self.W), jnp.float32)).astype(jnp.bfloat16))(key)
        return ({"T": T, "w0": jnp.zeros(())},
                {"T": {"gg": jnp.zeros((R, self.W))},
                 "w0": {"gg": jnp.zeros(())}})

    def program(self):
        step = fm.make_ffm_step_fused(LOSS, OPT, (0.0, 0.0, 0.0), self.F,
                                      self.K, fieldmajor=True, unit_val=True,
                                      **self.step_kw)
        mega = make_megastep(step.core)
        nv = jnp.full((self.steps,), B, jnp.int32)

        def call(p, s, idx, label, mask):
            p, s, losses, stats = mega(
                p, s, 1.0, nv, jnp.broadcast_to(idx, (self.steps, B, L)),
                None, jnp.broadcast_to(label, (self.steps, B)), None, None)
            return p, s, losses, {k: v[-1] for k, v in stats.items()}
        return call

    def ids(self, rng, n_distinct):
        pool = rng.choice(np.arange(1, R * 64), n_distinct, replace=False)
        return zipf_over(rng, pool).reshape(B, L).astype(np.int32)


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


def time_step(geo, call, params, state, batch, tag: str):
    """One reading of `call` on `batch`: 3 calls each waited for (the first
    of a program compiles: `first_call_s`), ms a step on the host's clock
    around 10 calls ended by `block_until_ready`, then the device's
    operations of 4 traced calls. Returns (params, state, reading)."""
    from harness import xplane
    t0 = time.perf_counter()
    for i in range(3):
        params, state, ls, stats = call(params, state, *batch)
        jax.block_until_ready(ls)
        if not i:
            first_call_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(10):
        params, state, ls, stats = call(params, state, *batch)
    jax.block_until_ready(ls)
    ms = 1e2 * (time.perf_counter() - t0) / geo.steps
    trace_dir = os.path.join(ROOT, "chiprun_out", "probe_trace",
                             f"{geo.name}_{tag}")
    xplane.start(trace_dir)
    for _ in range(4):
        params, state, ls, stats = call(params, state, *batch)
    jax.block_until_ready(ls)
    jax.profiler.stop_trace()
    reading = {"step_ms": round(ms, 3),
               "first_call_s": round(first_call_s, 1),
               "stats": {k: int(v) for k, v in stats.items()},
               **device_ops(trace_dir, 4 * geo.steps)}
    shutil.rmtree(trace_dir, ignore_errors=True)
    return params, state, reading


def programs(geo):
    """(name, forced capacity or None for the shipped rule, the XLA rows'
    block or None for the module's, [distinct rows asked])."""
    d = lambda *fracs: [int(N / f) for f in fracs]   # noqa: E731
    wide = N // 3 // rows_pallas.LIST_MULTIPLE * rows_pallas.LIST_MULTIPLE
    # the cells' batches at Zipf 1.5 / 1.25 / 1.05 hold 27.7k / 73.0k /
    # 161.6k distinct rows of 1,277,952 slots (ISSUE 28; FFM's: PERF.md §4)
    if geo.name == "fm":
        prior = 256 if TINY else 217_088    # PR 28's capacity
        return [("dense", 0, None, d(17)),
                ("shipped", None, None, d(46, 17.5, 10.6, 7.9, 6, 4.6, 4.3)),
                ("prior_cap", prior, None, d(46, 17.5, 7.9)),
                ("wide", wide, None, d(4.3, 3.3)),
                ("xla_rows_cap_n/16", N // 16, 0, d(32))]
    small = 256 if TINY else 81_920
    return [("dense", 0, None, d(17.5)),
            ("shipped", None, None, d(46, 17.5, 10.6, 7.9, 6.4)),
            ("wide", wide, None, d(17.5, 6, 4.3)),
            ("small_cap", small, None, d(17.5)),
            ("small_cap_oneshot", small, small, d(17.5)),
            ("shipped_block_2048", None, 2048, d(17.5))]


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not TINY:
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 1
    rng = np.random.default_rng(28)
    label = jnp.asarray(np.where(rng.random(B) < 0.25, 1.0, -1.0)
                        .astype(np.float32))
    mask = jnp.ones(B, jnp.float32)
    shipped_cap, kernels = fm.tail_cap, rows_pallas.use_kernels_default
    shipped_block = rows_pallas.XLA_BLOCK_ROWS
    asked = [a for a in sys.argv[1:] if not a.startswith("-")]
    out = []
    for geo in (g() for g in (FM, FFM) if not asked or g.name in asked):
        params, state = geo.state(jax.random.PRNGKey(0))
        for name, cap, block, nds in programs(geo):
            fm.tail_cap = shipped_cap if cap is None else (
                lambda *a, c=cap: c)
            # block 0: XLA's rows where the kernel would run, one block
            rows_pallas.use_kernels_default = (
                (lambda: False) if block == 0 else kernels)
            rows_pallas.XLA_BLOCK_ROWS = (block or shipped_block
                                          if block != 0 else N)
            call = geo.program()
            for i, nd in enumerate(nds):
                idx = jnp.asarray(geo.ids(rng, nd))
                params, state, reading = time_step(
                    geo, call, params, state, (idx, label, mask),
                    f"{name}_{nd}")
                if i:                   # the program's first call compiled
                    reading["first_call_s"] = out[-1]["first_call_s"]
                rec = {"geometry": geo.name, "variant": name,
                       "cap": (shipped_cap(N, R, geo.W, geo.itemsize)
                               if cap is None else cap),
                       "xla_block": block, "n_distinct_asked": nd, **reading}
                print(json.dumps(rec), flush=True)
                out.append(rec)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "probe_distinct_tail.json"),
              "w") as f:
        json.dump({"device": dev.device_kind, "readings": out}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
