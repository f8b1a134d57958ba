"""Bulk offline scoring (ISSUE 17): multi-shard ``predict --input <dir>``,
fused score->each_top_k, promoted-pointer model resolution.

The process-pool + sanitizer coverage (bit-match under 2 spawned workers,
int8 error bound, fd/thread leak census) lives in the run_tests.sh smoke
(``python -m hivemall_tpu.io.bulk --smoke``); these tests pin the
composition semantics at suite-friendly shapes with in-process pools."""

import os

import numpy as np
import pytest
import pyarrow as pa
import pyarrow.parquet as pq

from hivemall_tpu.catalog import lookup
from hivemall_tpu.frame.evaluation import auc, logloss
from hivemall_tpu.frame.tools import TopKAccumulator, each_top_k
from hivemall_tpu.io.arrow import _parquet_files, write_parquet_shards
from hivemall_tpu.io.bulk import _synth, bulk_predict, resolve_model_bundle

DIMS = 512
OPTS = f"-dims {DIMS} -mini_batch 64"


def _trained(ckdir, n=192, seed=1):
    cls = lookup("train_classifier").resolve()
    tr = cls(OPTS)
    tr.fit(_synth(n, DIMS, 8, seed=seed))
    os.makedirs(ckdir, exist_ok=True)
    path = os.path.join(ckdir, f"{cls.NAME}-step{int(tr._t):010d}.npz")
    tr.save_bundle(path)
    return tr, path


def _scores(out_dir):
    return np.concatenate([
        pq.read_table(f).column("score").to_numpy(
            zero_copy_only=False).astype(np.float32)
        for f in _parquet_files(out_dir)])


def test_topk_accumulator_matches_each_top_k():
    """Interleaved-arrival accumulation == the reference UDTF over
    CLUSTER BY input: ranks, scores, stable ties, and bottom-k."""
    rng = np.random.default_rng(3)
    n, k = 400, 5
    groups = rng.integers(0, 11, n).tolist()
    scores = np.round(rng.standard_normal(n), 2)   # force score ties
    vals = [f"v{i}" for i in range(n)]

    for kk in (k, -k):
        acc = TopKAccumulator(kk)
        acc.add_many(groups, scores, vals)
        got = {}
        for g, rank, s, v in acc.result():
            got.setdefault(g, []).append((rank, s, v))
        order = np.argsort(groups, kind="stable")  # CLUSTER BY arrival
        want = {}
        cg = [groups[i] for i in order]
        rows = list(each_top_k(kk, cg, [float(scores[i]) for i in order],
                               [vals[i] for i in order]))
        j = 0
        for g in dict.fromkeys(cg):                # first-seen group order
            want[g] = []
            while j < len(rows) and (not want[g] or rows[j][0] > 1):
                want[g].append(rows[j])
                j += 1
        assert got == want, f"k={kk}"


def test_bulk_topk_composes_with_each_top_k(tmp_path):
    """End-to-end: multi-shard Parquet (ragged tail + an EMPTY shard) with
    a per-row group column, scored through a 2-worker thread pool. The f32
    output bit-matches predict_proba, the streamed eval UDAFs match the
    frame ones, and topk.tsv matches each_top_k replayed over the scored
    output — and an independent numpy argsort oracle."""
    tr, bundle = _trained(str(tmp_path / "ck"))
    n = 300
    test = _synth(n, DIMS, 8, seed=2)
    in_dir = str(tmp_path / "in")
    write_parquet_shards(test, in_dir, rows_per_shard=128)  # 128/128/44
    rng = np.random.default_rng(5)
    parts = []
    for f in _parquet_files(in_dir):
        t = pq.read_table(f)
        g = rng.integers(0, 7, t.num_rows).astype(np.int64)
        parts.append(g)
        pq.write_table(t.append_column("user", pa.array(g)), f)
    groups = np.concatenate(parts)
    empty = pq.read_table(_parquet_files(in_dir)[0]).slice(0, 0)
    pq.write_table(empty, os.path.join(in_dir, "shard-00099.parquet"))

    out = str(tmp_path / "out")
    r = bulk_predict("train_classifier", in_dir, out, options=OPTS,
                     bundle=bundle, backend="kernel", workers=2,
                     pool="thread", top_k=3, group_col="user",
                     cache_dir=str(tmp_path / "cache"))
    assert r["rows"] == n and r["shards"] == 4
    assert r["bundle_source"] == "explicit" and r["pool"] == "thread"

    want = np.asarray(tr.predict_proba(test), np.float32)
    got = _scores(out)
    assert np.array_equal(got, want)
    got_groups = np.concatenate([
        pq.read_table(f).column("user").to_numpy()
        for f in _parquet_files(out)])
    assert np.array_equal(got_groups, groups)
    assert abs(r["metrics"]["logloss"] - logloss(test.labels, want)) < 1e-5
    assert abs(r["metrics"]["auc"] - auc(test.labels, want)) < 1e-5
    assert r["metrics"]["auc_method"] == "exact"

    # topk.tsv: ref is "<shard_index>:<row_in_shard>" -> global row
    offs = [0, 128, 256, 300]
    topk = {}
    with open(r["topk_file"]) as fh:
        for line in fh:
            g, rank, s, ref = line.rstrip("\n").split("\t")
            si, row = (int(x) for x in ref.split(":"))
            topk.setdefault(int(g), []).append(
                (int(rank), float(s), offs[si] + row))
    assert r["topk_rows"] == sum(len(v) for v in topk.values())

    # oracle 1: each_top_k replayed over the scored output, clustered by
    # group (rank==1 marks each group's first emitted row)
    order = np.argsort(groups, kind="stable")
    rows = list(each_top_k(3, groups[order].tolist(),
                           want[order].tolist(), order.tolist()))
    seen = list(dict.fromkeys(groups[order].tolist()))
    replay = {g: [] for g in seen}
    git = iter(seen)
    cur = None
    for rank, s, gi in rows:
        if rank == 1:
            cur = next(git)
        replay[cur].append((rank, gi))
    assert set(replay) == set(topk)
    for g, rws in topk.items():
        assert [(rk, gi) for rk, _s, gi in sorted(rws)] == replay[g], \
            f"group {g}: bulk topk diverged from each_top_k replay"
    # oracle 2: per-group numpy argsort (independent of frame/tools)
    for g in np.unique(groups):
        idx = np.flatnonzero(groups == g)
        best = idx[np.argsort(-want[idx].astype(np.float64),
                              kind="stable")][:3]
        rows_g = sorted(topk[int(g)])
        assert [r_[2] for r_ in rows_g] == best.tolist(), f"group {g}"
        assert [r_[0] for r_ in rows_g] == list(range(1, len(best) + 1))
        for rank, s, gi in rows_g:
            assert np.isclose(s, want[gi], rtol=1e-4), (g, rank)


def test_group_aware_shard_routing(tmp_path):
    """Group-aware routing (ROADMAP item 5 follow-up): shards sharing
    group values union into one pooled component so per-group top-k
    never splits a group across workers; disjoint shards stay separate
    tasks; routed results are identical to the unrouted single-worker
    scan."""
    from hivemall_tpu.io.bulk import _group_components
    tr, bundle = _trained(str(tmp_path / "ck"), n=128, seed=7)
    n = 256
    test = _synth(n, DIMS, 8, seed=8)
    in_dir = str(tmp_path / "in")
    write_parquet_shards(test, in_dir, rows_per_shard=64)  # 4 shards
    files = _parquet_files(in_dir)
    assert len(files) == 4
    # shards 0+1 share groups {0..3}, shards 2+3 share {10..13}: two
    # components, each spanning two shards, mutually disjoint
    rng = np.random.default_rng(9)
    for si, f in enumerate(files):
        t = pq.read_table(f)
        lo = 0 if si < 2 else 10
        g = rng.integers(lo, lo + 4, t.num_rows).astype(np.int64)
        pq.write_table(t.append_column("user", pa.array(g)), f)

    comps = _group_components(files, "user")
    assert comps == [[0, 1], [2, 3]]

    kw = dict(options=OPTS, bundle=bundle, backend="kernel",
              top_k=3, group_col="user")
    routed = bulk_predict("train_classifier", in_dir,
                          str(tmp_path / "out_routed"), workers=2,
                          pool="thread", **kw)
    assert routed["group_components"] == 2
    baseline = bulk_predict("train_classifier", in_dir,
                            str(tmp_path / "out_base"), workers=1, **kw)
    with open(routed["topk_file"]) as fh:
        got = fh.read()
    with open(baseline["topk_file"]) as fh:
        want = fh.read()
    assert got == want and routed["topk_rows"] == baseline["topk_rows"]
    assert np.array_equal(_scores(str(tmp_path / "out_routed")),
                          _scores(str(tmp_path / "out_base")))

    # a chain shard bridging both halves collapses routing to ONE
    # component (transitive closure, not pairwise overlap)
    bridge = pq.read_table(files[0]).slice(0, 2)
    bridge = bridge.set_column(
        bridge.column_names.index("user"), "user",
        pa.array(np.array([3, 10], np.int64)))
    pq.write_table(bridge, os.path.join(in_dir, "shard-bridge.parquet"))
    comps = _group_components(_parquet_files(in_dir), "user")
    assert sorted(len(c) for c in comps) == [5] or len(comps) == 1


def test_bulk_promoted_pointer_default(tmp_path):
    """The promotion pointer is the default model source (the nightly-job
    contract): promoted beats newest, explicit beats both, and the scored
    output provably comes from the PROMOTED (older) weights."""
    from hivemall_tpu.io.checkpoint import promote_bundle
    ck = str(tmp_path / "ck")
    old, p_old = _trained(ck, n=128, seed=3)
    new, p_new = _trained(ck, n=256, seed=4)
    assert p_new != p_old                      # distinct step filenames

    path, src = resolve_model_bundle("train_classifier", checkpoint_dir=ck)
    assert (path, src) == (p_new, "newest")
    promote_bundle(ck, p_old)
    path, src = resolve_model_bundle("train_classifier", checkpoint_dir=ck)
    assert (path, src) == (p_old, "promoted")
    path, src = resolve_model_bundle("train_classifier", bundle=p_new,
                                     checkpoint_dir=ck)
    assert (path, src) == (p_new, "explicit")

    test = _synth(96, DIMS, 8, seed=5)
    in_dir = str(tmp_path / "in")
    write_parquet_shards(test, in_dir, rows_per_shard=64)
    r = bulk_predict("train_classifier", in_dir, str(tmp_path / "out"),
                     options=OPTS, checkpoint_dir=ck, backend="kernel")
    assert r["bundle_source"] == "promoted"
    assert r["model_step"] == int(old._t)
    got = _scores(str(tmp_path / "out"))
    assert np.array_equal(got,
                          np.asarray(old.predict_proba(test), np.float32))
    assert not np.array_equal(got,
                              np.asarray(new.predict_proba(test),
                                         np.float32))


@pytest.mark.parametrize("precision,margin", [("f32", 1e-6), ("int8", 0.05)])
def test_bulk_arena_twins_score_every_row(tmp_path, precision, margin):
    """The mmap'd arena twins through the bulk path: every row of every
    shard scored, in order, inside the tier's margin of predict_proba,
    and the streamed eval metrics computed from those scores."""
    tr, bundle = _trained(str(tmp_path / "ck"))
    n = 300
    test = _synth(n, DIMS, 8, seed=2)
    in_dir = str(tmp_path / "in")
    write_parquet_shards(test, in_dir, rows_per_shard=128)  # 128/128/44
    out = str(tmp_path / "out")
    r = bulk_predict("train_classifier", in_dir, out, options=OPTS,
                     bundle=bundle, backend="arena", precision=precision,
                     workers=2, pool="thread")
    assert r["rows"] == n and r["shards"] == 3
    assert r["backend"] == "arena" and r["precision"] == precision
    want = np.asarray(tr.predict_proba(test), np.float32)
    got = _scores(out)
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= margin
    assert abs(r["metrics"]["logloss"] - logloss(test.labels, got)) < 1e-5
