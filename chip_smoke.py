#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, in ONE process, through the entry points a user
calls, at the full width of the flagship (train_ffm at libffm's Criteo
setting: 40 fields, 4 factors, 32768-row batches, 2^24 hashed slots;
depth cut to a few windows, weights random from a seed):

  train   lookup("train_ffm") -> fit on a planted-signal SparseDataset (two
          full K=8 windows + a ragged tail) -> fit_stream from a Parquet
          shard dir this script writes (ParquetStream -> ingest pool ->
          stager -> prefetcher), every option but the config at its default
          so the accelerator-only defaults are what run
  sync    the same 30-step loop ended by block_until_ready and by a value
          fetch (what synchronises on this device)
  serve   save_bundle -> PredictEngine (jitted scorer) -> PredictServer in
          this process -> /predict with 1, 7 and 256 rows, scores held to
          trainer.predict on those rows
  mesh    with >= 4 chips: the train leg under -mesh dp=2,tp=2 (GSPMD),
          the table's shards on four distinct devices, first-window losses
          held to the one-chip train leg's

The table is -ffm_table auto, the joint layout every cell and user runs.

Any leg that raises fails the script. It needs a TPU: there is no option
that lets it pass without one (tests/test_chip_smoke.py calls the legs at
toy size on the CPU instead). Once the device is known, the last stdout
line is `{"ok": ..., "device": {"platform", "kind", "count"}}` and nothing
else; the line before it is the summary (legs, seconds, compile cache).
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time
import urllib.request

import numpy as np

# flagship geometry: train_ffm, 40 fields x 4 factors, 2^24 hashed rows
FULL = dict(dims=1 << 24, fields=40, factors=4, batch=32768, vocab=1000,
            n_batches=20, stream_batches=10)
WINDOW = 8                     # -steps_per_dispatch auto on accelerators


def ffm_options(size: dict, *, mesh: str = "", extra: str = "") -> str:
    opts = (f"-dims {size['dims']} -factors {size['factors']} "
            f"-fields {size['fields']} -mini_batch {size['batch']} "
            "-opt adagrad -classification -halffloat")
    if mesh:
        opts += f" -mesh {mesh}"
    return f"{opts} {extra}".strip()


def planted_dataset(n: int, size: dict, seed: int):
    """Criteo-shaped rows (one feature per field, unit values) whose label
    is a noisy linear function of planted per-feature weights, so a few
    AdaGrad steps must lower the logloss."""
    from hivemall_tpu.io.sparse import SparseDataset
    F, V = size["fields"], size["vocab"]
    rng = np.random.default_rng(seed)
    w_true = np.random.default_rng(1234).normal(0, 1.0, (F, V))
    r = rng.integers(0, V, (n, F))
    idx = (1 + np.arange(F)[None, :] * V + r).astype(np.int32)
    margin = w_true[np.arange(F)[None, :], r].sum(1) / np.sqrt(F)
    lab = np.where(margin + rng.normal(0, 0.3, n) > 0, 1.0, -1.0)
    fld = np.tile(np.arange(F, dtype=np.int32), (n, 1))
    return SparseDataset(idx.ravel(),
                         np.arange(0, n * F + 1, F, dtype=np.int64),
                         np.ones(n * F, np.float32),
                         lab.astype(np.float32), fld.ravel())


def _uses_mosaic(trainer, size: dict) -> bool:
    """Whether the step this trainer dispatches lowers to a Mosaic custom
    call, i.e. its pallas_call was built with interpret=False."""
    import jax
    import jax.numpy as jnp
    B, L = size["batch"], size["fields"]
    sds = jax.ShapeDtypeStruct
    text = trainer._step_fm_unit.lower(
        trainer.params, trainer.opt_state, 0.0, sds((B, L), jnp.int32),
        sds((B,), jnp.float32), sds((B,), jnp.float32)).as_text()
    return "tpu_custom_call" in text


def _shard_devices(arr) -> int:
    return len({s.device for s in arr.addressable_shards})


def train_leg(size: dict, *, mesh: str = "", extra_opts: str = "",
              stream: bool = True) -> tuple:
    """fit (+ second fit for the no-retrace check) (+ fit_stream). Returns
    (trainer, options, report); raises on any failed check."""
    import jax
    from hivemall_tpu.catalog import lookup
    from hivemall_tpu.obs.devprof import get_devprof
    from hivemall_tpu.utils.device import pallas_interpret

    B = size["batch"]
    opts = ffm_options(size, mesh=mesh, extra=extra_opts)
    dp = get_devprof()
    c0, s0 = dp.compiles, dp.compile_s
    t0 = time.perf_counter()
    trainer = lookup("train_ffm").resolve()(opts)
    K = trainer._resolved_steps_per_dispatch()
    ds = planted_dataset(size["n_batches"] * B + B // 3, size, seed=0)

    trainer._trace_losses = []
    trainer.fit(ds, epochs=1, shuffle=False)
    jax.block_until_ready(trainer.params)
    fit_s = time.perf_counter() - t0
    losses = [v / B for v in trainer._trace_losses[:size["n_batches"]]]
    stats = trainer.pipeline_stats.as_dict()
    compiles, compile_s = dp.compiles - c0, dp.compile_s - s0

    assert len(losses) == size["n_batches"], len(losses)
    assert np.all(np.isfinite(trainer._trace_losses)), "non-finite loss"
    w = min(WINDOW, size["n_batches"] // 2)
    first, last = np.mean(losses[:w]), np.mean(losses[-w:])
    assert last < first, f"loss did not fall: {first:.4f} -> {last:.4f}"
    if K > 1:
        windows = size["n_batches"] // K
        assert stats["megabatches_staged"] == windows, stats
        assert stats["singles_flushed"] == \
            size["n_batches"] - windows * K + 1, stats
    packed = trainer._pack_input_on()
    if packed and K > 1:
        assert "ffm.packed_megastep" in dp.builds, sorted(dp.builds)
    assert trainer.layout == "joint", trainer.layout
    if not mesh:
        # the step that ran is compiled Pallas exactly when the device
        # policy says so (never on a CPU nobody named, always on a TPU)
        assert _uses_mosaic(trainer, size) == (not pallas_interpret())

    # warm-up is over (fit armed the sentinel): same shapes, no compiles
    r0 = dp.retraces
    trainer._trace_losses = None
    t1 = time.perf_counter()
    trainer.fit(ds, epochs=1, shuffle=False)
    jax.block_until_ready(trainer.params)
    warm_fit_s = time.perf_counter() - t1
    assert dp.retraces == r0, f"{dp.retraces - r0} retrace(s) after warm-up"

    report = {
        "options": opts, "steps_per_dispatch": K,
        "ingest_workers": trainer._resolved_ingest_workers(),
        "packed_input": packed,
        "loss_first_window": round(float(first), 4),
        "loss_last_window": round(float(last), 4),
        "first_window_losses": [round(v, 4) for v in losses[:w]],
        "fit_cold_seconds": round(fit_s, 2),
        "fit_warm_seconds": round(warm_fit_s, 2),
        "compiles": compiles, "compile_seconds": round(compile_s, 2),
        "megabatches_staged": stats["megabatches_staged"],
        "singles_flushed": stats["singles_flushed"],
        "batches_staged": stats["batches_staged"],
    }
    if stream:
        report["stream"] = _stream_leg(trainer, size, K)
    return trainer, opts, report


def _stream_leg(trainer, size: dict, K: int) -> dict:
    import jax
    from hivemall_tpu.io.arrow import ParquetStream, write_parquet_shards
    B, n = size["batch"], size["stream_batches"]
    tmp = tempfile.mkdtemp(prefix="chip_smoke_pq_")
    try:
        write_parquet_shards(planted_dataset(n * B, size, seed=7), tmp,
                             rows_per_shard=B)
        src = ParquetStream(tmp)
        trainer._trace_losses = []
        t0 = time.perf_counter()
        trainer.fit_stream(src.batches(B, epochs=1,
                                       max_len=size["fields"]))
        jax.block_until_ready(trainer.params)
        secs = time.perf_counter() - t0
        losses = trainer._trace_losses
        trainer._trace_losses = None
        stats = trainer.pipeline_stats.as_dict()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    assert len(losses) == n and np.all(np.isfinite(losses)), losses
    if K > 1:
        assert stats["megabatches_staged"] == n // K, stats
    assert stats["batches_prepared"] == n, stats
    return {"seconds": round(secs, 2), "batches": n,
            "loss_last": round(losses[-1] / B, 4),
            "megabatches_staged": stats["megabatches_staged"],
            "batches_staged": stats["batches_staged"],
            "shard_batches_decoded": src.stats.batches_prepared}


def sync_leg(trainer, size: dict, n_steps: int = 30) -> dict:
    """What synchronises: the same n-step loop of the flagship train step
    on one device-resident batch, ended three ways. Each timing is the
    best of two; an ending that does not wait reads like the enqueue."""
    import jax
    import jax.numpy as jnp
    from hivemall_tpu.io.sparse import SparseBatch
    B = size["batch"]
    hb = trainer._preprocess_batch(
        next(planted_dataset(B, size, seed=3).batches(B, shuffle=False)))
    b = SparseBatch(jnp.asarray(hb.idx),
                    None if hb.val is None else jnp.asarray(hb.val),
                    jnp.asarray(hb.label), None, n_valid=hb.n_valid,
                    fieldmajor=hb.fieldmajor)

    def loop(end) -> float:
        t0 = time.perf_counter()
        for _ in range(n_steps):
            loss = trainer._train_batch(b)
        if end == "block_until_ready":
            jax.block_until_ready(trainer.params)
        elif end == "value_fetch":
            float(loss)
        dt = time.perf_counter() - t0
        jax.block_until_ready(trainer.params)
        return dt

    loop("block_until_ready")                       # warm
    out = {}
    for end in ("enqueue_only", "block_until_ready", "value_fetch") * 2:
        ms = loop(end) * 1e3 / n_steps
        out[end] = round(min(ms, out.get(end, ms)), 3)
    return {"steps": n_steps, "ms_per_step": out}


def serve_leg(trainer, opts: str, size: dict) -> dict:
    """bundle -> PredictEngine (default precision = the jitted scorer) ->
    PredictServer in this process -> three /predict requests."""
    from hivemall_tpu.io.sparse import SparseDataset
    from hivemall_tpu.serve.engine import PredictEngine
    from hivemall_tpu.serve.http import PredictServer

    F = size["fields"]
    ds = planted_dataset(256, size, seed=11)
    rows = []
    for i in range(256):
        idx, _ = ds.row(i)
        rows.append([f"{f}:{int(a)}:1" for f, a in enumerate(idx)])
    tmp = tempfile.mkdtemp(prefix="chip_smoke_ck_")
    srv = None
    t0 = time.perf_counter()
    try:
        path = os.path.join(tmp, f"{trainer.NAME}-step{trainer._t:010d}.npz")
        trainer.save_bundle(path)
        engine = PredictEngine("train_ffm", opts, bundle=path,
                               warmup_len=F)
        assert engine._model.arena is None      # jitted, not the twin
        srv = PredictServer(engine, port=0).start()
        up_s = time.perf_counter() - t0
        out = {"bundle_mb": round(os.path.getsize(path) / 2**20, 1),
               "platform": engine.platform, "startup_seconds": round(up_s, 2),
               "requests": []}
        for n in (1, 7, 256):
            parsed = [trainer._parse_row(r) for r in rows[:n]]
            ref = trainer.predict(SparseDataset.from_rows(
                [(p[0], p[1]) for p in parsed], [1.0] * n,
                fields=[p[2] for p in parsed]))
            req = urllib.request.Request(
                f"http://127.0.0.1:{srv.port}/predict",
                data=json.dumps({"rows": rows[:n]}).encode(),
                headers={"Content-Type": "application/json"})
            t1 = time.perf_counter()
            with urllib.request.urlopen(req, timeout=120) as resp:
                assert resp.status == 200, resp.status
                body = json.loads(resp.read())
            ms = (time.perf_counter() - t1) * 1e3
            got = np.asarray(body["scores"], np.float32)
            assert got.shape == (n,) and np.all(np.isfinite(got)), got
            diff = float(np.max(np.abs(got - np.asarray(ref, np.float32))))
            assert diff <= 1e-6, f"{n} rows: served != predict ({diff})"
            assert body["model_step"] == trainer._t, body["model_step"]
            out["requests"].append({"rows": n, "ms": round(ms, 1),
                                    "max_abs_diff": diff})
        return out
    finally:
        if srv is not None:
            srv.stop()
        shutil.rmtree(tmp, ignore_errors=True)


def mesh_leg(size: dict, one_chip_losses, *, mesh: str = "dp=2,tp=2",
             extra_opts: str = "", rtol: float = 0.06) -> dict:
    """The train leg under a (dp, tp) mesh (GSPMD). The table must live on
    every device of the mesh, and the first window must track the one-chip
    train leg's (same init, data, order and minibatch-AdaGrad semantics)
    within bf16 noise."""
    trainer, _, rep = train_leg(size, mesh=mesh, extra_opts=extra_opts,
                                stream=False)
    n_dev = trainer.mesh.devices.size
    on = _shard_devices(trainer.params["T"])
    assert on == n_dev, f"T on {on} of {n_dev} devices"
    rel = np.abs(np.asarray(rep["first_window_losses"])
                 - np.asarray(one_chip_losses)) / np.asarray(one_chip_losses)
    print(f"chip_smoke: mesh on {on} devices, first-window rel diff vs "
          f"one chip {rel.round(4).tolist()}", flush=True)
    assert rel[0] <= 5e-3, f"step-1 loss off by {rel[0]:.4f}"
    assert rel.max() <= rtol, f"first window off by {rel.max()}"
    rep.update(layout=trainer.layout, shard_devices=on,
               max_rel_diff_vs_one_chip=round(float(rel.max()), 4))
    return rep


def main() -> int:
    import jax
    if jax.default_backend() != "tpu":
        print("chip_smoke: needs a TPU and JAX found none "
              f"(jax.default_backend() = {jax.default_backend()!r}, "
              f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', '')!r})",
              file=sys.stderr)
        return 2
    from hivemall_tpu.utils.compile_cache import enable_compile_cache
    t_start = time.perf_counter()
    cache_dir = enable_compile_cache()
    d = jax.devices()[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(jax.devices())}
    print(f"chip_smoke: device {device}", flush=True)
    print(f"chip_smoke: compile cache {cache_dir} "
          f"(JAX_COMPILATION_CACHE_DIR "
          f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'})",
          flush=True)
    # from here on the run ends with the result line, pass or fail; a leg
    # that raises still raises (traceback on stderr, exit code 1)
    result = {"ok": False, "device": device}
    try:
        _run_legs(device, cache_dir, t_start)
        result["ok"] = True
    finally:
        # the last stdout line: exactly these two keys, nothing else
        print(json.dumps(result), flush=True)
    return 0


def _run_legs(device: dict, cache_dir: str, t_start: float) -> None:
    from hivemall_tpu.io.shard_cache import counters as ingest_counters
    from hivemall_tpu.obs.devprof import get_devprof
    from hivemall_tpu.utils import native
    from hivemall_tpu.utils.compile_cache import cache_stats

    nat = native.status()
    print(f"chip_smoke: native {nat}", flush=True)
    assert nat["loaded"], f"native library did not build: {nat['error']}"

    legs = {}

    def run(name, fn, *a, **kw):
        t0 = time.perf_counter()
        res = fn(*a, **kw)
        rep = res[-1] if isinstance(res, tuple) else res
        rep["seconds"] = round(time.perf_counter() - t0, 1)
        legs[name] = rep
        print(f"chip_smoke: leg {name} PASS {json.dumps(rep)}", flush=True)
        return res

    trainer, opts, rep = run("train", train_leg, FULL)
    assert rep["steps_per_dispatch"] == WINDOW and rep["packed_input"] \
        and rep["ingest_workers"] > 1 and rep["batches_staged"] > 0, \
        f"accelerator defaults did not engage: {rep}"
    assert ingest_counters.as_dict()["canonicalizer"] == "native", \
        "the native canonicalizer is not what ran"
    run("sync", sync_leg, trainer, FULL)
    run("serve", serve_leg, trainer, opts, FULL)
    if device["count"] >= 4:
        del trainer
        run("mesh", mesh_leg, FULL, rep["first_window_losses"])

    dp = get_devprof()
    summary = {
        "device": device,
        "seconds": round(time.perf_counter() - t_start, 1),
        "compile": {"cache_dir": cache_dir, "compiles": dp.compiles,
                    "seconds": round(dp.compile_s, 1), **cache_stats()},
        "native": nat["path"], "legs": legs, "claim": None,
    }
    print(f"chip_smoke: summary {json.dumps(summary)}", flush=True)


if __name__ == "__main__":
    sys.exit(main())
