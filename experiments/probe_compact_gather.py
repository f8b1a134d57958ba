"""Probe: is XLA's row gather cheaper from a compact table than from the
2 GiB one, alone and inside the step? The readings behind `ops/fm.py`
`gather_rows`, `COMPACT_TABLE_BYTES` and `FILL_BLOCK_ROWS` (PERF.md section 6,
PRs 30, 34).

The packed FM step's gather read 1,277,952 table rows a batch (13.2 ms,
10.3 ns a row) of which ~73k are distinct. "Gather the distinct rows, expand
from a compact table" reads the ~73k rows once and then gathers the
1,277,952 slots from a [cap, 128] operand by each slot's rank among the
batch's distinct rows; it needs one more 1.7 ms sort.

`gathers` (PR 30): the gather alone, at the geometry of the benchmark's cell
`fm_criteo.stream` (a [4194304, 128] float32 table, B = 32768, L = 39, a
batch of ~73.0k distinct rows, Zipf(1.25) over them):

  table            the step's own: T[rows], rows the slots' table rows
  compact_<cap>    C[rank], C an ARGUMENT of the program (so in HBM)
  expand_<cap>     T[urows][rank]: C made inside the program, where the
                   compiler chooses its memory space

each a whole jitted program, timed as `probe_distinct_tail.py` times its
variants (the host's clock around 10 runs ended by `block_until_ready`),
with the layout of the compact operand as the compiled text gives it (an
`S(n)` in it is a memory space other than HBM).

`steps` (PR 34): the WHOLE step by how it gathers, since a phase alone is no
floor, at both cells' geometries as `probe_distinct_tail.py` builds them
(`fm`: the one-step program; `ffm`: `ffm_criteo_joint.stream`'s [4194304,
164] bfloat16 table, which the chip keeps transposed, so the megastep of two
steps, halved, as tests/tpu_aot_worker.py `ffm_joint_megastep` compiles it):

  direct           no room for a compact table (`COMPACT_TABLE_BYTES` 0):
                   T[rows], ranking in front
  compact_<block>  `gather_rows` through the distinct rows, C filled
                   <block> rows a trip (`FILL_BLOCK_ROWS`; `cap`: one trip
                   of the whole capacity, what a fill that is not bounded
                   by the count costs)
  compact_hbm      C as large as the ranking's capacity (FM: 282,624 rows,
                   145 MB, which the compiler leaves in HBM; the
                   flagship's 174,080 is the shipped capacity already)

by batches of chosen numbers of distinct rows (the cells' at Zipf 1.5, 1.25,
1.05; one under each capacity and one over it: over the gather's the step
reads the table and keeps the distinct tail, over the ranking's it is
`direct` plus the dense tail): ms a step on the host's clock, then the
device's operations by `hm.*` scope and by name from 4 traced calls.

Run on the chip: `python experiments/probe_compact_gather.py [gathers] [fm]
[ffm] [--blocks=2048,4096]` (default: all sections, every variant; with
`--blocks` the compact step at those fill blocks alone); one JSON line a
reading, all of them in
`chiprun_out/probe_compact_gather.json`. It exits non-zero off a TPU
(`--tiny` rehearses the script on the CPU at a toy size: its times mean
nothing).
"""
from __future__ import annotations

import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
sys.path.insert(0, os.path.join(ROOT, "experiments"))

import jax
import jax.numpy as jnp
import numpy as np

TINY = "--tiny" in sys.argv
B, L, R, W = (256, 8, 1 << 14, 128) if TINY else (32768, 39, 1 << 22, 128)
N = B * L
CAPS = (256, 512) if TINY else (79_872, 218_496)
N_DISTINCT = 150 if TINY else 73_000
# `--blocks=2048,4096`: the compact step at these fill blocks and no other
BLOCKS = [int(b) for a in sys.argv if a.startswith("--blocks=")
          for b in a.split("=")[1].split(",")]


def gathers() -> list:
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
        rec = {"section": "gathers", "variant": name, "ms": round(ms, 3),
               "ns_per_slot": round(1e6 * ms / N, 2),
               "compact_layouts": layouts}
        print(json.dumps(rec), flush=True)
        out.append(rec)
    return out


def steps(geo) -> list:
    """The whole step of `geo` (probe_distinct_tail's FM or FFM) by how it
    gathers and by the batch's distinct rows."""
    from probe_distinct_tail import B, time_step
    from hivemall_tpu.ops import fm
    rng = np.random.default_rng(34)
    label = jnp.asarray(np.where(rng.random(B) < 0.25, 1.0, -1.0)
                        .astype(np.float32))
    mask = jnp.ones(B, jnp.float32)
    cap = fm.tail_cap(N, R, geo.W, geo.itemsize)
    gcap = fm.gather_cap(cap, geo.W, geo.itemsize)
    shipped = fm.COMPACT_TABLE_BYTES, fm.FILL_BLOCK_ROWS
    d = lambda *fracs: [int(N / f) for f in fracs]   # noqa: E731
    near = cap // 128
    typical = d(17.5)
    spread = d(46, 10.6) + sorted({gcap - near, gcap + near, cap - near,
                                   cap + near})
    programs = [("direct", 0, shipped[1], typical + spread[-1:]),
                ("compact_8192", shipped[0], 8192, typical + spread),
                ("compact_2048", shipped[0], 2048, typical),
                ("compact_32768", shipped[0], 32768, typical),
                ("compact_cap", shipped[0], gcap, typical)]
    if gcap < cap:
        programs.append(("compact_hbm", 1 << 40, 8192, typical))
    if BLOCKS:
        programs = [(f"compact_{b}", shipped[0], b, typical) for b in BLOCKS]
    params, state = geo.state(jax.random.PRNGKey(0))
    out = []
    for name, table_bytes, block, nds in programs:
        fm.COMPACT_TABLE_BYTES, fm.FILL_BLOCK_ROWS = table_bytes, block
        call = geo.program()
        for i, nd in enumerate(nds):
            idx = jnp.asarray(geo.ids(rng, nd))
            params, state, reading = time_step(
                geo, call, params, state, (idx, label, mask), f"{name}_{nd}")
            if i:                       # the program's first call compiled
                reading["first_call_s"] = out[-1]["first_call_s"]
            rec = {"section": "steps", "geometry": geo.name, "variant": name,
                   "cap": cap, "gather_cap": fm.gather_cap(
                       cap, geo.W, geo.itemsize), "n_distinct_asked": nd,
                   **reading}
            print(json.dumps(rec), flush=True)
            out.append(rec)
    fm.COMPACT_TABLE_BYTES, fm.FILL_BLOCK_ROWS = shipped
    return out


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not TINY:
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 1
    from probe_distinct_tail import FFM, FM
    FFM.steps = 2
    asked = [a for a in sys.argv[1:] if not a.startswith("-")]
    out = gathers() if not asked or "gathers" in asked else []
    for geo in (g() for g in (FM, FFM) if not asked or g.name in asked):
        out += steps(geo)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "probe_compact_gather.json"),
              "w") as f:
        json.dump({"device": dev.device_kind, "slots": N,
                   "n_distinct": N_DISTINCT, "readings": out}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
