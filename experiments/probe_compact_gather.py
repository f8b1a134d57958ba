"""Probe: is XLA's row gather cheaper from a compact table than from the
2 GiB one? One reading for the next issue on the step-kernels layer (PERF.md
section 7, "What the distinct-row tail leaves").

The packed FM step's gather reads 1,277,952 table rows a batch (13.2 ms,
10.3 ns a row) of which ~73k are distinct. "Gather the distinct rows, expand
from a compact table" would read the ~73k rows once and then gather the
1,277,952 slots from a [cap, 128] operand by each slot's rank among the
batch's distinct rows; it needs one more 1.7 ms sort. Whether the second
gather is cheaper is what this times, at the geometry of the benchmark's
cell `fm_criteo.stream` (a [4194304, 128] float32 table, B = 32768, L = 39,
a batch of ~73.0k distinct rows, Zipf(1.25) over them):

  table            the step's own: T[rows], rows the slots' table rows
  compact_<cap>    C[rank], C an ARGUMENT of the program (so in HBM)
  expand_<cap>     T[urows][rank]: C made inside the program, where the
                   compiler chooses its memory space

each a whole jitted program, timed as `probe_distinct_tail.py` times its
variants (the host's clock around 10 runs ended by `block_until_ready`),
with the layout of the compact operand as the compiled text gives it (an
`S(n)` in it is a memory space other than HBM).

Run on the chip: `python experiments/probe_compact_gather.py`; one JSON line
a variant, all of them in `chiprun_out/probe_compact_gather.json`. It exits
non-zero off a TPU (`--tiny` rehearses the script on the CPU at a toy size:
its times mean nothing).
"""
from __future__ import annotations

import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

TINY = "--tiny" in sys.argv
B, L, R, W = (256, 8, 1 << 14, 128) if TINY else (32768, 39, 1 << 22, 128)
N = B * L
CAPS = (256, 512) if TINY else (79_872, 218_496)
N_DISTINCT = 150 if TINY else 73_000


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not TINY:
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 1
    rng = np.random.default_rng(30)
    pool = np.sort(rng.choice(np.arange(1, R), N_DISTINCT, replace=False))
    rank = (rng.zipf(1.25, N) - 1) % N_DISTINCT
    rank[rng.choice(N, N_DISTINCT, replace=False)] = np.arange(N_DISTINCT)
    rows = jnp.asarray(pool[rank].astype(np.int32))
    rank = jnp.asarray(rank.astype(np.int32))
    T = jax.jit(lambda k: jax.random.normal(k, (R, W), jnp.float32))(
        jax.random.PRNGKey(0))

    variants = [("table", jax.jit(lambda T, i: T[i]), (T, rows), None)]
    for cap in CAPS:
        urows = jnp.asarray(np.concatenate(
            [pool, R + np.arange(cap - N_DISTINCT)]).astype(np.int32))
        C = jax.jit(lambda T, u: T.at[u].get(mode="clip"))(T, urows)
        variants += [
            (f"compact_{cap}", jax.jit(lambda C, i: C[i]), (C, rank), cap),
            (f"expand_{cap}", jax.jit(
                lambda T, u, i: T.at[u].get(mode="clip")[i]),
             (T, urows, rank), cap)]
    want = np.asarray(T[rows[:4096]])
    out = []
    for name, f, args, cap in variants:
        run = f.lower(*args).compile()
        text = run.as_text()
        np.testing.assert_array_equal(np.asarray(run(*args)[:4096]), want)
        jax.block_until_ready(run(*args))
        t0 = time.perf_counter()
        for _ in range(10):
            got = run(*args)
        jax.block_until_ready(got)
        ms = 1e2 * (time.perf_counter() - t0)
        layouts = sorted(set(re.findall(
            r"f32\[%d,%d\](\{[^}]*\})" % (cap, W), text))) if cap else []
        rec = {"variant": name, "ms": round(ms, 3),
               "ns_per_slot": round(1e6 * ms / N, 2),
               "compact_layouts": layouts}
        print(json.dumps(rec), flush=True)
        out.append(rec)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "probe_compact_gather.json"),
              "w") as f:
        json.dump({"device": dev.device_kind, "slots": N,
                   "n_distinct": N_DISTINCT, "variants": out}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
