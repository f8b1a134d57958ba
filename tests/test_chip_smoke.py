"""chip_smoke.py's legs at toy size on the 8-virtual-device CPU backend.

The script itself only passes on a TPU; its legs are importable functions
taking sizes, so the checks it makes on the chip (windows staged, packed
megastep built, no retrace after warm-up, served scores == predict, shards
on every mesh device) are held here too — with the accelerator-only
defaults asked for by option and Pallas interpreted because conftest names
the CPU platform.
"""

import json
import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
import chip_smoke  # noqa: E402

# the joint layout at a toy geometry (2048 table rows), learnable in six
# steps; K=2 windows so two full windows + a ragged tail stay cheap
TOY = dict(dims=1 << 16, fields=32, factors=4, batch=256, vocab=4,
           n_batches=6, stream_batches=5)
CHIP_DEFAULTS = "-eta0 0.02 -steps_per_dispatch 2 -ingest_workers 2"


@pytest.fixture(scope="module")
def trained():
    return chip_smoke.train_leg(TOY,
                                extra_opts=CHIP_DEFAULTS + " -pack_input on")


def test_train_leg(trained):
    trainer, _, rep = trained
    assert trainer.layout == "joint"
    assert rep["packed_input"] and rep["steps_per_dispatch"] == 2
    assert rep["megabatches_staged"] == 3 and rep["singles_flushed"] == 1
    assert rep["loss_last_window"] < rep["loss_first_window"]
    assert rep["stream"]["megabatches_staged"] == 2


def test_sync_and_serve_legs(trained):
    trainer, opts, _ = trained
    ms = chip_smoke.sync_leg(trainer, TOY, n_steps=2)["ms_per_step"]
    assert set(ms) == {"enqueue_only", "block_until_ready", "value_fetch"}
    rep = chip_smoke.serve_leg(trainer, opts, TOY)
    assert [r["rows"] for r in rep["requests"]] == [1, 7, 256]
    assert rep["platform"] == "cpu"


def test_mesh_leg(trained):
    _, _, one = trained
    rep = chip_smoke.mesh_leg(TOY, one["first_window_losses"],
                              extra_opts=CHIP_DEFAULTS)
    assert rep["layout"] == "joint" and rep["shard_devices"] == 4


def test_script_refuses_without_a_tpu():
    r = subprocess.run([sys.executable, os.path.join(_ROOT, "chip_smoke.py")],
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr and not r.stdout.strip()


@pytest.mark.parametrize("fail", [False, True])
def test_result_line_is_exactly_ok_and_device(monkeypatch, capsys, fail):
    """The last stdout line is the driver's contract: {"ok", "device":
    {"platform", "kind", "count"}} and no other key, pass or fail."""
    import jax
    from hivemall_tpu.utils import compile_cache

    def legs(device, cache_dir, t_start):
        print("chip_smoke: summary {}")
        if fail:
            raise AssertionError("a leg failed")

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "x")
    monkeypatch.setattr(chip_smoke, "_run_legs", legs)
    if fail:
        with pytest.raises(AssertionError):
            chip_smoke.main()
    else:
        assert chip_smoke.main() == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert set(last) == {"ok", "device"} and last["ok"] is (not fail)
    assert set(last["device"]) == {"platform", "kind", "count"}
    d = jax.devices()[0]
    assert last["device"] == {"platform": d.platform, "kind": d.device_kind,
                              "count": len(jax.devices())}
