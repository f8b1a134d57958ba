"""The linear family's configuration in the benchmark (PR 33):
`train_classifier -loss logloss -opt adagrad` (AdaGrad-RDA) as the cell
`logreg_criteo.stream` runs it, held to `benchmark/reference/linear.py` at
toy size on the CPU, and what the `stream` job needs of every catalog
learner it can build: `-seed`, `params`, `opt_state`."""

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "logreg_criteo.stream"


@pytest.fixture
def bench(monkeypatch, tmp_path):
    """The benchmark's modules, importable as they are in a run of
    `benchmark/run.py`, and everything such a run sets for a process of
    its own (import path, compile cache) put back afterwards."""
    import jax
    monkeypatch.setattr(sys, "path", [BENCH, ROOT] + list(sys.path))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    cache_keys = ("jax_compilation_cache_dir",
                  "jax_persistent_cache_min_compile_time_secs",
                  "jax_persistent_cache_min_entry_size_bytes")
    before = {key: getattr(jax.config, key) for key in cache_keys}
    spec = importlib.util.spec_from_file_location(
        "benchmark_run", os.path.join(BENCH, "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    yield run
    for key, value in before.items():
        jax.config.update(key, value)


def test_logreg_cell_runs_end_to_end_at_toy_size(bench, capsys):
    """`benchmark/run.py --toy --workload logreg_criteo.stream`: the
    catalog's constructor, `fit_stream` over Parquet shards, the first
    dispatch one fused dispatch of 4 steps (the harness raises otherwise)
    against the reference, a window, `correct`."""
    assert bench.main(["--workload", CELL, "--seed", str(2 ** 31 + 33),
                       "--seconds", "1", "--trace", "0", "--toy"]) == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is True
    assert set(res["metrics"]) == {"train_rate", "setup_s"}
    compared = res["compared"]
    assert compared["decode_missed_rows"]["value"] == 0
    assert compared["window_lost_examples"]["value"] == 0
    for name in ("loss_gap", "grad_gap", "change_gap"):
        assert compared[name]["value"] <= compared[name]["limit"]


@pytest.fixture
def first_dispatch(bench):
    """The program's first dispatch on seeded rows and the reference's run
    over the same rows, as `benchmark/tests/read_limits.py` makes them."""
    from harness import check, common, data, job_stream
    from hivemall_tpu.io.arrow import ParquetStream
    cfg = bench.load_cell(CELL, toy=True)["cfg"]
    model = cfg["model"]
    B, F = int(model["mini_batch"]), int(model["fields"])
    K = job_stream.steps_per_dispatch(cfg)
    reference = common.family_module("reference", cfg["family"])
    big_seed = 2 ** 31 + 34
    seed = common.seed31(big_seed)
    ids, labels = data.make_rows(
        data.RowSpec(cfg["data"], int(model["dims"])), K * B, big_seed)
    shard_dir = os.path.join(common.RUN_DIR, "test_logreg_cell", "shards")
    data.write_shards(ids, labels, shard_dir, K * B // 2, with_fields=False)
    trainer = job_stream.build_trainer(cfg, seed)
    batches = list(ParquetStream(shard_dir).batches(B, epochs=1, max_len=F))
    prog = job_stream.first_dispatch(trainer, cfg, reference, batches)
    ref = reference.run(cfg, seed, prog["ids"], prog["labels"])
    return {"cfg": cfg, "seed": seed, "prog": prog, "ref": ref,
            "reference": reference, "check": check, "ids": ids,
            "labels": labels, "job": job_stream}


def test_first_dispatch_agrees_with_the_reference(first_dispatch):
    """`loss_gap`, `grad_gap`, `change_gap` of the program's own first
    dispatch inside the configuration's limits; the first loss is
    B x ln 2 on both sides (the table starts at zero)."""
    d = first_dispatch
    numbers = d["job"].compare_first_dispatch(
        d["cfg"], d["seed"], d["reference"], d["prog"], d["ids"],
        d["labels"])
    numbers.pop("_seconds")
    numbers["window_lost_examples"] = 0.0
    verdict = d["check"].verdict(numbers,
                                 d["cfg"]["correct"]["stream"]["limits"])
    assert verdict["correct"], verdict["compared"]
    B = int(d["cfg"]["model"]["mini_batch"])
    assert d["prog"]["losses"][0] == pytest.approx(B * np.log(2.0), rel=1e-6)
    assert d["ref"]["losses"][0] == pytest.approx(B * np.log(2.0), rel=1e-6)
    assert not d["ref"]["before"]["w"].any()
    assert d["ref"]["after"]["w"].any() and d["prog"]["after"]["w"].any()


@pytest.mark.parametrize("kw", [{"precision": "bfloat16"},
                                {"fault": "half_batch"}],
                         ids=["bfloat16_control", "half_batch"])
def test_a_lower_precision_and_a_fault_come_out_incorrect(first_dispatch,
                                                          kw):
    """The reference in the precision below the configuration's, and with
    half of every batch left out, each put in the program's place: neither
    passes the limits the program passes, and the loss cannot tell (every
    side starts at B x ln 2)."""
    d = first_dispatch
    if "precision" in kw:
        assert kw["precision"] \
            == d["cfg"]["correct"]["stream"]["control_precision"]
    bad = d["reference"].run(d["cfg"], d["seed"], d["prog"]["ids"],
                             d["prog"]["labels"], **kw)
    numbers = d["check"].train_numbers(bad, d["ref"])
    limits = d["cfg"]["correct"]["stream"]["limits"]
    verdict = d["check"].verdict(numbers, limits)
    assert not verdict["correct"]
    assert numbers["loss_gap"] <= limits["loss_gap"]
    over = {n for n in ("grad_gap", "change_gap") if numbers[n] > limits[n]}
    assert over if "precision" in kw else over == {"grad_gap", "change_gap"}


def test_an_unchanged_state_comes_out_incorrect(first_dispatch):
    d = first_dispatch
    ref = d["ref"]
    same = dict(ref, after=ref["before"],
                gg={k: np.zeros_like(v) for k, v in ref["gg"].items()})
    numbers = d["check"].train_numbers(same, ref)
    assert numbers["grad_gap"] == numbers["change_gap"] == 1.0


def test_reference_follows_the_published_update_by_hand(bench):
    """Two rows, three slots, two steps, against AdaGrad-RDA written out
    in numpy float64: u += g; gg += g * g; w = -sign(u) * eta(t) * (t+1) *
    max(0, |u|/(t+1) - lambda) / (sqrt(gg) + eps), eta(t) = eta0 /
    (1+t)^power_t, the batch's gradients summed per slot."""
    from reference import linear
    cfg = {"model": {"lambda": 1e-3, "eta0": 0.1, "power_t": 0.1}}
    ids = np.array([[[5, 9], [5, 7]], [[9, 7], [5, 5]]], np.int32)
    labels = np.array([[1.0, -1.0], [-1.0, 1.0]], np.float32)
    got = linear.run(cfg, 0, ids, labels)
    keys = np.array([5, 7, 9])
    assert np.array_equal(got["keys"], keys)
    w, u, gg = np.zeros(3), np.zeros(3), np.zeros(3)
    losses = []
    for t in range(2):
        at = np.searchsorted(keys, ids[t])
        phi = w[at].sum(1)
        losses.append(np.log1p(np.exp(-phi * labels[t])).sum())
        d = -labels[t] / (1.0 + np.exp(phi * labels[t]))
        g = np.zeros(3)
        np.add.at(g, at.reshape(-1), np.repeat(d, 2))
        u, gg = u + g, gg + g * g
        eta = 0.1 / (1.0 + t) ** 0.1
        w = -np.sign(u) * eta * (t + 1) \
            * np.maximum(0.0, np.abs(u) / (t + 1) - 1e-3) \
            / (np.sqrt(gg) + 1e-6)
    np.testing.assert_allclose(got["losses"], losses, rtol=1e-6)
    np.testing.assert_allclose(got["after"]["w"], w, rtol=1e-5, atol=1e-8)
    np.testing.assert_allclose(got["gg"]["w"], gg, rtol=1e-6)
    assert not got["before"]["w"].any()
    p = linear.score(cfg, got, np.array([[5, 7]]))
    np.testing.assert_allclose(p, 1 / (1 + np.exp(-(w[0] + w[1]))),
                               rtol=1e-5)


def test_linear_work_against_a_hand_count(bench):
    """benchmark/work/linear.py: 2 rows of 3 unit-valued features, float32
    table and state. Per slot one read of `w`, then one read and one write
    of `w`, `u`, `gg`; ids and labels once. No term follows the table's
    size (no dense pass is counted)."""
    from work import linear

    def cfg(dims):
        return {"model": {"fields": 3, "dims": dims,
                          "table_dtype": "float32",
                          "state_dtype": "float32"}}
    w = linear.train_step(cfg(1 << 10), rows=2)
    slots = 2 * 3
    assert w["bytes"] == slots * (4 + 2 * (4 + 4 + 4)) + 2 * (3 * 4 + 4)
    assert linear.forward_flops(3) == 2 * 3 + 8 == 14
    assert w["flops"] == 2 * 14 + slots * (2 + 15)
    assert linear.train_step(cfg(1 << 28), rows=2) == w
    assert linear.score(cfg(1 << 10), rows=2) \
        == {"bytes": slots * 4 + 2 * 3 * 4, "flops": 2 * 14}
    assert linear.table_elements(cfg(1 << 28)) == 1 << 28


STREAM_LEARNERS = {
    "train_classifier": "-loss logloss -opt adagrad -dims 4096",
    "train_regressor": "-dims 4096",
    "train_logregr": "-dims 4096",
    "train_arow": "-dims 4096",
    "train_fm": "-dims 4096 -factors 4 -opt adagrad -classification",
    "train_ffm": "-dims 4096 -factors 2 -fields 4 -opt adagrad "
                 "-classification",
}


@pytest.mark.parametrize("name", sorted(STREAM_LEARNERS))
def test_stream_job_learners_take_seed_and_expose_their_state(name):
    """What `benchmark/harness/job_stream.py` does to every trainer it
    builds: `-seed` appended to the configuration's options, `params` and
    `opt_state` awaited, then freed by assignment."""
    import jax
    from hivemall_tpu.catalog import lookup
    trainer = lookup(name).resolve()(f"{STREAM_LEARNERS[name]} -seed 7")
    assert int(trainer.opts.seed) == 7
    leaves = jax.tree_util.tree_leaves(
        jax.block_until_ready((trainer.params, trainer.opt_state)))
    assert leaves and all(hasattr(leaf, "shape") for leaf in leaves)
    assert trainer._megastep_state()[0] is trainer.params
    if hasattr(type(trainer), "w"):          # the flat-table families
        assert trainer.w is trainer.params
        assert trainer.params.shape == (4096,)
    trainer.params = trainer.opt_state = None
    assert trainer.params is None and trainer.opt_state is None
