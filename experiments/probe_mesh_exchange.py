"""Probe: how the slab crosses four chips in the FFM joint step under `-mesh
dp=1,tp=4` (PERF.md section 6), by which exchange the step takes.

The geometry of the benchmark's cell `ffm_criteo_joint_tp4.stream_mesh`
(a [4194304, 164] bfloat16 block and its float32 AdaGrad state a chip,
B = 32768, L = 39, unit values elided), the MEGASTEP of 4 steps divided by
4, with `probe_mesh_tail.py`'s state and batches and `probe_distinct_tail.py`'s
clock and trace:

  exchange  the shipped step: where every block holds at most 49,152
            distinct rows, the blocks' compact tables cross the mesh (an
            all-gather of bf16[196608,164]) with each slot's row in them
            (an all-reduce of s32[1277952]), and every chip expands the
            slab itself (`ops/fm.py` `ffm_slab_over_blocks`)
  fallback  the same program made to take the other branch of its `cond`:
            COMPACT_TABLE_BYTES cut so that a chip's share of the tables
            is 8,192 rows, fewer than the ~18.3k a chip holds at the
            cell's skew, while each chip's own gather still reads through
            its distinct rows (32,768 rows of room): the blocks' slabs
            summed (an all-reduce of bf16[32768,39,164], 419 MB)
  slabs     no exchange in the program at all (`gather_cap` of more than
            one chip read as 0): the blocks' slabs summed with no `cond`
            around them, the program before the exchange

each on batches of chosen numbers of distinct feature ids (the cell's at
Zipf 1.25, 1.5 and 1.05: 73.0k, 27.7k, 161.6k, a quarter of them a chip;
`fallback` and `slabs` on the cell's alone): ms a step on the host's clock,
the step's own stats, the device's time by `hm.*` scope, its top
operations, and the collectives' device time (every operation the
partitioner named `all-reduce*`, `all-gather*`, ...), all A CHIP (the trace
reader sums the four planes' operations, divided by four here, and
averages their busy time).

Run on four chips: `python experiments/probe_mesh_exchange.py [exchange]
[fallback] [slabs]` (default: all three); one JSON line a reading on
stdout, with the device's kind and the chips. It exits non-zero off a
TPU (`--tiny` rehearses the script on four virtual CPU devices at a toy
size: its times mean nothing, and its batches are too small to leave the
exchange). Nothing a benchmark cell runs imports it.
"""
from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "experiments"))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
if "--tiny" in sys.argv:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=4")

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

import probe_distinct_tail as one
import probe_mesh_tail as mesh_tail
from probe_distinct_tail import B, N, TINY

from hivemall_tpu.ops import fm
from hivemall_tpu.parallel.mesh import make_mesh

TP = mesh_tail.TP
#: a chip's share of the compact tables in the forced fallback
FALLBACK_ROWS = 128 if TINY else 8192
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def device_ops(trace_dir: str, steps: int) -> dict:
    """`probe_distinct_tail.device_ops`, and the collectives' ms a step."""
    from harness import xplane
    out = plain_device_ops(trace_dir, steps)
    raw = xplane.read(xplane.find_xplane(trace_dir))
    events = [ev for evs in raw["planes"].values() for ev in evs]
    red = xplane.reduce(raw, (min(s for s, *_ in events),
                              max(s + d for s, d, *_ in events)), [])
    coll = {k: 1e3 * v / steps for k, v in red["ops"].items()
            if k.startswith(COLLECTIVES)}
    out["collective_ms"] = round(sum(coll.values()), 3)
    out["collectives_ms"] = {k: round(v, 3) for k, v in sorted(
        coll.items(), key=lambda kv: -kv[1])}
    return out


plain_device_ops = one.device_ops


def patched(variant: str):
    """(module attribute, value) pairs that make `variant`'s program."""
    if variant == "fallback":
        row_bytes = 256 * 2                 # 164 bfloat16 lanes, padded
        return [("COMPACT_TABLE_BYTES", FALLBACK_ROWS * row_bytes * TP)]
    if variant == "slabs":
        gather_cap = fm.gather_cap
        return [("gather_cap", lambda cap, W, itemsize, chips=1: 0
                 if chips > 1 else gather_cap(cap, W, itemsize))]
    return []


def main() -> int:
    devs = jax.devices()
    if (devs[0].platform != "tpu" and not TINY) or len(devs) < TP:
        print(f"needs {TP} TPU chips, found {len(devs)} {devs[0].platform}",
              file=sys.stderr)
        return 1
    one.device_ops = device_ops
    mesh = make_mesh(dp=1, tp=TP)
    everywhere = NamedSharding(mesh, P())
    rng = np.random.default_rng(42)
    label = jax.device_put(np.where(rng.random(B) < 0.25, 1.0, -1.0)
                           .astype(np.float32), everywhere)
    mask = jax.device_put(np.ones(B, np.float32), everywhere)
    asked = [a for a in sys.argv[1:] if not a.startswith("-")] \
        or ["exchange", "fallback", "slabs"]
    nds = [int(N / f) for f in (17.5, 46, 7.9)]     # the cell's first
    batches = [jax.device_put(mesh_tail.FFMBlocks(mesh, "").ids(rng, nd),
                              everywhere) for nd in nds]
    for variant in asked:
        saved = [(name, getattr(fm, name)) for name, _ in patched(variant)]
        for name, value in patched(variant):
            setattr(fm, name, value)
        try:
            geo = mesh_tail.FFMBlocks(mesh, variant)
            params, state = geo.state(jax.random.PRNGKey(0))
            call = geo.program()
            for nd, idx in list(zip(nds, batches))[
                    :None if variant == "exchange" else 1]:
                params, state, reading = one.time_step(
                    geo, call, params, state, (idx, label, mask),
                    f"{variant}_{nd}")
                # the trace reader summed the chips' planes (busy_ms is
                # their mean already)
                reading["collective_ms"] = round(
                    reading["collective_ms"] / TP, 3)
                for key in ("phase_ms", "collectives_ms"):
                    reading[key] = {k: round(v / TP, 3)
                                    for k, v in reading[key].items()}
                reading["top_ops_ms"] = [[k, round(v / TP, 3)]
                                         for k, v in reading["top_ops_ms"]]
                rec = {"device": devs[0].device_kind, "chips": TP,
                       "geometry": geo.name, "variant": variant,
                       "n_distinct_asked": nd, **reading}
                print(json.dumps(rec), flush=True)
            del params, state
        finally:
            for name, value in saved:
                setattr(fm, name, value)
    return 0


if __name__ == "__main__":
    sys.exit(main())
