"""The cell `mf_hugewiki.stream_ratings` at toy size on the CPU asked for
by name: the rehearsal and its two readers, each planted fault failing its
limit under the timed path, the generator's laws, and the work functions
by hand."""

import json
import os

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CELL = "mf_hugewiki.stream_ratings"


def run_cell(capsys, trace=0, seconds=1.0, seed=2 ** 31 + 43, **hooks):
    import run as bench_run
    capsys.readouterr()
    assert bench_run.main(["--workload", CELL, "--seed", str(seed),
                           "--seconds", str(seconds), "--trace", str(trace),
                           "--toy"], **hooks) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_traced_rehearsal_reports_every_listed_metric(capsys):
    res = run_cell(capsys, trace=1, seconds=2.0)
    assert res["correct"] is True and "rehearsal" in res
    bench = json.load(open(os.path.join(os.path.dirname(BENCH),
                                        "BENCHMARK.json")))
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m["workloads"]}
    assert {"mf.user_rows", "mf.item_fold"} <= listed
    assert "step.dense_pass_share" not in listed
    got = {k: v["value"] for k, v in res["metrics"].items()}
    # on the CPU: no peak, no memory reading, no staging thread's account
    assert listed - set(got) <= {"step.mfu", "device.peak_hbm_gb",
                                 "feed.unattributed_share"}
    # toy: 512 ratings a step over 16,384 users and 512 items
    assert 400 < got["mf.user_rows"] <= 512
    assert 1.0 < got["mf.item_fold"] < 4.0
    w = res["notes"]["window"]
    assert w["examples"] == w["fed_examples"] and w["compiles"] == 0
    assert w["tail_dense_steps"] == 0 and w["tail_distinct_steps"] \
        == w["steps"]


def test_new_readers_find_nothing_in_a_program_without_them():
    """A program without the counters: each reader returns None and
    raises nothing."""
    from harness import common
    ctx = {"job": "stream", "trace": None, "spans": [],
           "window": {"t0": 0.0, "t1": 1.0, "steps": 8, "dispatches": 1,
                      "batch": 512}}
    for name in ("mf.user_rows", "mf.item_fold"):
        reader = common.load_module(
            os.path.join(BENCH, "layer_metrics", f"{name}.py"),
            "reader_" + name.replace(".", "_"))
        assert reader.read(dict(ctx)) is None
        assert reader.read(dict(ctx, job="predict_open_loop")) is None


def test_a_program_without_fit_stream_exits_at_once(monkeypatch):
    """The parent's `train_mf_adagrad` is no LearnerBase: the job says so
    and exits before it makes any data."""
    from harness import job_stream_ratings as job
    from hivemall_tpu.catalog import registry

    class Old:
        pass
    monkeypatch.setattr(registry.lookup("train_mf_adagrad"), "resolve",
                        lambda: Old)
    with pytest.raises(SystemExit) as e:
        job.trainer_class({"catalog": "train_mf_adagrad"})
    assert e.value.code == 3


# -- faults planted under the timed path ---------------------------------------

def _state_unchanged(trainer):
    import jax
    import jax.numpy as jnp
    inner = trainer._train_megabatch

    def stuck(mb):
        keep = jax.tree_util.tree_map(jnp.copy,
                                      (trainer.params, trainer.opt_state))
        losses = inner(mb)
        trainer.params, trainer.opt_state = keep
        return losses
    trainer._train_megabatch = stuck


def _half_ratings(trainer):
    import dataclasses
    inner = trainer._train_megabatch

    def half(mb):
        return inner(dataclasses.replace(mb, nv=mb.nv // 2, nv_dev=None))
    trainer._train_megabatch = half


def _dropped_dispatch(trainer):
    inner = trainer._dispatch_mega
    calls = {"n": 0}

    def lossy(mb):
        calls["n"] += 1
        if calls["n"] != 3:
            inner(mb)
    trainer._dispatch_mega = lossy


@pytest.mark.parametrize("fault,number", [
    (_state_unchanged, "change_gap"), (_half_ratings, "loss_gap"),
    (_dropped_dispatch, "window_lost_examples")],
    ids=lambda v: getattr(v, "__name__", v))
def test_planted_fault_fails_its_limit(capsys, fault, number):
    res = run_cell(capsys, break_trainer=fault)
    assert res["correct"] is False
    c = res["compared"][number]
    assert c["value"] > c["limit"]


def test_the_control_fails_a_limit():
    """The reference in bfloat16 in the program's place, against the
    float32 reference, at toy size: outside at least one limit."""
    from harness import check, common, ratings
    import run as bench_run
    cell = bench_run.load_cell(CELL, True)
    cfg, m = cell["cfg"], cell["cfg"]["model"]
    reference = common.family_module("reference", "mf")
    law = ratings.RatingLaw(cell["traffic"]["ratings"], int(m["users"]),
                            int(m["items"]))
    u, i, r = ratings.make_ratings(law, 8 * int(m["mini_batch"]), 7)
    ids = np.stack([u, i], 1).reshape(8, -1, 2)
    ref = reference.run(cfg, 7, ids, r.reshape(8, -1))
    low = reference.run(cfg, 7, ids, r.reshape(8, -1),
                        precision=cfg["correct"]["stream_ratings"]
                        ["control_precision"])
    verdict = check.verdict(
        dict(check.train_numbers(low, ref), decode_missed_rows=0.0,
             window_lost_examples=0.0),
        cfg["correct"]["stream_ratings"]["limits"])
    assert verdict["correct"] is False


# -- the generator -------------------------------------------------------------

def test_generator_is_the_seeds_alone_and_keeps_its_laws():
    from harness import ratings
    import run as bench_run
    cell = bench_run.load_cell(CELL, False)
    m, law_cfg = cell["cfg"]["model"], cell["traffic"]["ratings"]
    law = ratings.RatingLaw(law_cfg, int(m["users"]), int(m["items"]))
    a = ratings.make_ratings(law, 300_000, 2 ** 31 + 3, threads=1)
    b = ratings.make_ratings(law, 300_000, 2 ** 31 + 3, threads=7)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    u, i, r = a
    assert 0 <= u.min() and u.max() < law.users
    assert 0 <= i.min() and i.max() < law.items
    # an epoch is the deployment's ratings over 8 chips; the mean user
    # rates ~55 times, the most active under the items one can rate, the
    # head item under the users who can rate it
    published = cell["cfg"]["published"]
    epoch = published["ratings"] / 8
    assert 50 < epoch / law.users < 60
    head_user = ratings.rank_share(law.users, law.user_s)
    head_item = ratings.rank_share(law.items, law.item_s)
    assert 10_000 < head_user * epoch < law.items
    assert 0.005 < head_item < 0.02 and head_item * epoch < law.users
    # the draws follow the law: the head item's share, and the most
    # active user at its expected count
    top = np.bincount(i).max() / len(i)
    assert abs(top - head_item) < 0.1 * head_item
    assert abs(np.bincount(u).max() - head_user * len(u)) \
        < 5 * np.sqrt(head_user * len(u))
    assert abs(r.mean() - law.mean) < 0.01
    assert abs(r.std() - np.sqrt(1.0 + law.noise_sd ** 2)) < 0.02
    assert not np.array_equal(u, ratings.make_ratings(law, 300_000,
                                                      2 ** 31 + 4)[0])


# -- work -------------------------------------------------------------------------

def test_mf_work_by_hand():
    from work import common, mf
    cfg = {"model": {"users": 1000, "items": 100, "factors": 4,
                     "table_dtype": "float32", "state_dtype": "float32"}}
    w = mf.train_step(cfg, rows=3)
    # 3 ratings, 2 rows each of 5 numbers read and written with their
    # accumulators (2 x (4 + 4) bytes a number), ids and rating
    assert w["bytes"] == 6 * 5 * 16 + 3 * 12
    # forward 2 x 4 + 6 = 14, three times; 10 a number of a slot
    assert w["flops"] == 3 * 3 * 14 + 6 * 5 * 10
    assert mf.table_elements(cfg) == 1100 * 5
    big = dict(cfg, model=dict(cfg["model"], users=10 ** 6))
    assert mf.train_step(big, 3) == w            # no term grows with U
    from harness.common import BENCH_DIR, load_json, peaks_for
    real = load_json(os.path.join(BENCH_DIR, "configs", "mf_hugewiki.json"))
    step = mf.train_step(real, 131072)
    peaks = peaks_for("TPU v5 lite")
    assert common.bound_by(step, peaks) == "hbm"
    # 262,144 rows of 101 numbers x 16 bytes: 0.42 GB, half a millisecond
    assert 4e-4 < common.least_seconds(step, peaks) < 6e-4
