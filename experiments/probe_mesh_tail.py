"""Probe: what a chip of four pays for the FFM joint step under `-mesh
dp=1,tp=4`, by which program runs it (PERF.md section 6, PR 36).

The geometry of the benchmark's cell `ffm_criteo_joint_tp4.stream_mesh`
(`train_ffm -dims 2^30 -fields 39 -factors 4 -halffloat`: 16,777,216 rows
of 164 lanes over tp=4, so a [4194304, 164] bfloat16 block and its float32
AdaGrad state a chip, the one-chip flagship's; B = 32768, L = 39, unit
values elided), the MEGASTEP of 4 steps divided by 4, as
`probe_distinct_tail.py` times the one-chip flagship and with its clock,
batches and trace reader:

  gspmd_dense  `make_ffm_step_fused(distinct_tail=False)` jitted over the
               row-sharded state: GSPMD's cut of the dense step, what
               `-mesh` ran through PR 35 (a table-sized G, zero-filled and
               scattered into, a dense AdaGrad pass, a masked `T[rows]`)
  blocks       `make_ffm_step_fused(mesh=...)`: `shard_map` over tp, each
               chip ranking, gathering and updating its own block's rows
  blocks_dense the same with `distinct_tail=False`: what that step is
               under an optimizer `rank_rows` does not rank for (FTRL,
               Adam, RDA): the dense tail on each chip's own block

each on batches of chosen numbers of distinct feature ids (the cell's at
Zipf 1.5, 1.25 and 1.05: 27.7k, 73.0k, 161.6k; hashed over FOUR blocks, so
a quarter of them a chip; a dense program, whose time does not follow the
count, on the cell's alone): ms a step on the host's clock, the step's own
stats, and the device's operations by `hm.*` scope and by name, A CHIP (the
trace reader sums the four planes; divided by four here).

Run on four chips: `python experiments/probe_mesh_tail.py [gspmd_dense]
[blocks] [blocks_dense]` (default: the first two); one JSON line a reading, all of them in
`chiprun_out/probe_mesh_tail.json`. It exits non-zero off a TPU (`--tiny`
rehearses the script on four virtual CPU devices at a toy size: its times
mean nothing).
"""
from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "experiments"))
if "--tiny" in sys.argv:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=4")

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

import probe_distinct_tail as one
from probe_distinct_tail import B, L, N, R, TINY

from hivemall_tpu.ops import fm
from hivemall_tpu.parallel.mesh import make_mesh

TP = 4


class FFMBlocks(one.FFM):
    """`ffm_criteo_joint_tp4.stream_mesh`: TP blocks of the one-chip
    flagship's R rows, the state born in its row sharding."""
    name = "ffm_tp4"

    def __init__(self, mesh, variant):
        super().__init__()
        self.mesh = mesh
        self.step_kw = {"gspmd_dense": dict(distinct_tail=False),
                        "blocks_dense": dict(mesh=mesh, distinct_tail=False)
                        }.get(variant, dict(mesh=mesh))

    def state(self, key):
        rows, everywhere = (NamedSharding(self.mesh, P("tp", None)),
                            NamedSharding(self.mesh, P()))
        tables = {"T": rows, "w0": everywhere}

        def init(k):
            T = (0.1 * jax.random.normal(k, (TP * R, self.W), jnp.float32)
                 ).astype(jnp.bfloat16)
            return ({"T": T, "w0": jnp.zeros(())},
                    {"T": {"gg": jnp.zeros((TP * R, self.W))},
                     "w0": {"gg": jnp.zeros(())}})
        return jax.jit(init, out_shardings=(
            tables, {"T": {"gg": rows}, "w0": {"gg": everywhere}}))(key)

    def ids(self, rng, n_distinct):
        pool = rng.choice(np.arange(1, TP * R * 64), n_distinct,
                          replace=False)
        return one.zipf_over(rng, pool).reshape(B, L).astype(np.int32)


def main() -> int:
    devs = jax.devices()
    if (devs[0].platform != "tpu" and not TINY) or len(devs) < TP:
        print(f"needs {TP} TPU chips, found {len(devs)} {devs[0].platform}",
              file=sys.stderr)
        return 1
    mesh = make_mesh(dp=1, tp=TP)
    everywhere = NamedSharding(mesh, P())
    rng = np.random.default_rng(36)
    label = jax.device_put(np.where(rng.random(B) < 0.25, 1.0, -1.0)
                           .astype(np.float32), everywhere)
    mask = jax.device_put(np.ones(B, np.float32), everywhere)
    asked = [a for a in sys.argv[1:] if not a.startswith("-")] \
        or ["gspmd_dense", "blocks"]
    nds = [int(N / f) for f in (17.5, 46, 7.9)]     # the cell's first
    batches = [jax.device_put(FFMBlocks(mesh, "").ids(rng, nd), everywhere)
               for nd in nds]
    out = []
    for variant in asked:
        geo = FFMBlocks(mesh, variant)
        params, state = geo.state(jax.random.PRNGKey(0))
        call = geo.program()
        for nd, idx in list(zip(nds, batches))[
                :1 if variant.endswith("dense") else None]:
            params, state, reading = one.time_step(
                geo, call, params, state, (idx, label, mask),
                f"{variant}_{nd}")
            # the trace reader summed the chips' planes
            reading["busy_ms"] /= TP
            reading["phase_ms"] = {k: round(v / TP, 3)
                                   for k, v in reading["phase_ms"].items()}
            reading["top_ops_ms"] = [[k, round(v / TP, 3)]
                                     for k, v in reading["top_ops_ms"]]
            rec = {"geometry": geo.name, "variant": variant,
                   "cap_a_chip": fm.tail_cap(N, R, geo.W, geo.itemsize),
                   "n_distinct_asked": nd, **reading}
            print(json.dumps(rec), flush=True)
            out.append(rec)
        del params, state
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "probe_mesh_tail.json"),
              "w") as f:
        json.dump({"device": devs[0].device_kind, "chips": TP,
                   "readings": out}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
