"""-ffm_table parts: the Pallas VMEM scatter+AdaGrad FFM layout.

Covers (reference: FieldAwareFactorizationMachineUDTF semantics,
SURVEY.md §3.6): step equivalence vs an XLA scatter oracle, trainer-level
fit/score/emission, kernel-grid padding of partial batches, and the
unsupported-combination guards. Runs on the CPU mesh via the kernel's
interpret mode.
"""
import os
import tempfile

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from hivemall_tpu.io.sparse import SparseBatch, SparseDataset
from hivemall_tpu.models.fm import FFMTrainer
from hivemall_tpu.ops import fm_pallas as fp
from hivemall_tpu.ops.losses import get_loss
from hivemall_tpu.utils.device import pallas_interpret

B, F, K, MRF = 128, 31, 8, 1 << 10   # Wp = 31*8+8 -> 256 (HP=2)
L = F
DIMS = 1 << 16


def _mk_batch(rng, b=B, zero_frac=0.1):
    idx = rng.integers(0, 1 << 20, (b, L)).astype(np.int32)
    idx[rng.random((b, L)) < zero_frac] = 0
    val = (idx != 0).astype(np.float32)
    lab = (rng.integers(0, 2, b) * 2 - 1).astype(np.float32)
    return idx, val, lab


def _oracle_step(params, opt_state, t, idx, val, label, row_mask, eta=0.1):
    """XLA scatter + dense AdaGrad with the identical math."""
    loss = get_loss("logloss")
    wp, hp = 256, 2
    T2, w0 = params["T2"], params["w0"]
    S2 = opt_state["T2"]["gg"]
    b = idx.shape[0]
    idxT, valT = idx.T, val.T
    fieldT = (jnp.arange(L, dtype=jnp.int32) % F)[:, None]
    rows = fp.parts_row_hash(idxT, fieldT, MRF)
    slab = T2.reshape(F * MRF, hp, 128)[rows]

    def batch_loss(w0f, slabf):
        phi = fp._phi_parts(w0f, slabf.reshape(L, b, wp), valT, F, K)
        return (loss.loss(phi, label) * row_mask).sum()

    loss_sum, (g0, gslab) = jax.value_and_grad(
        batch_loss, argnums=(0, 1))(w0.astype(jnp.float32), slab)
    gslab = gslab.astype(jnp.bfloat16).astype(jnp.float32)
    G = jnp.zeros((F * MRF, hp, 128), jnp.float32).at[rows].add(
        gslab.reshape(L, b, hp, 128))
    G2 = G.reshape(F * MRF * hp, 128)
    gg = S2 + G2 * G2
    T2n = (T2.astype(jnp.float32)
           - eta * G2 / (jnp.sqrt(gg) + 1e-6)).astype(T2.dtype)
    return T2n, gg, loss_sum


def test_geometry():
    mrf, wp, hp = fp.parts_geometry(1 << 24, 40, 4)
    assert (mrf, wp, hp) == (8192, 256, 2)
    assert 40 * mrf >= (1 << 24) // 64          # joint-capacity parity
    mrf2, wp2, hp2 = fp.parts_geometry(1 << 16, 31, 8)
    assert wp2 == 256 and hp2 == 2


def test_step_matches_oracle():
    rng = np.random.default_rng(1)
    idx, val, lab = _mk_batch(rng)
    mask = np.ones(B, np.float32)
    mask[-5:] = 0.0
    loss = get_loss("logloss")
    interp = pallas_interpret()
    step = fp.make_parts_step(loss, lambda t: 0.1, (0.0, 0.0, 0.0),
                              F, K, MRF, interpret=interp)

    key = jax.random.PRNGKey(0)
    Tl = jnp.concatenate([
        jax.random.normal(key, (F * MRF, F * K)) * 0.1,
        jnp.zeros((F * MRF, 256 - F * K))], axis=1)
    T2_np = np.asarray(Tl.reshape(F * MRF * 2, 128).astype(jnp.bfloat16))
    params = {"T2": jnp.asarray(T2_np), "w0": jnp.zeros((), jnp.float32)}
    opt = {"T2": {"gg": jnp.zeros((F * MRF * 2, 128), jnp.float32)},
           "w0": {"gg": jnp.zeros((), jnp.float32)}}
    T2_0 = jnp.asarray(T2_np)           # step donates its inputs
    S2_0 = jnp.zeros((F * MRF * 2, 128), jnp.float32)

    p1, s1, l1 = step(params, opt, 0.0, jnp.asarray(idx), jnp.asarray(val),
                      jnp.asarray(lab), jnp.asarray(mask))
    T2o, ggo, lo = jax.jit(_oracle_step)(
        {"T2": T2_0, "w0": jnp.zeros((), jnp.float32)},
        {"T2": {"gg": S2_0}, "w0": {"gg": jnp.zeros((), jnp.float32)}},
        0.0, jnp.asarray(idx), jnp.asarray(val), jnp.asarray(lab),
        jnp.asarray(mask))

    assert abs(float(l1) - float(lo)) < 1e-3 * max(1.0, abs(float(lo)))
    # AdaGrad's first step is sign-unstable where G ~ 0 (summation-order
    # noise); compare weights only where the accumulator is meaningful.
    sig = ggo > 1e-5
    dT = float((jnp.abs(p1["T2"].astype(jnp.float32)
                        - T2o.astype(jnp.float32)) * sig).max())
    rS = float((jnp.abs(s1["T2"]["gg"] - ggo) / (ggo + 1e-2)).max())
    assert dT < 2e-2, f"T2 mismatch {dT}"
    assert rS < 0.2, f"gg mismatch {rS}"


def test_trainer_fit_and_score():
    rng = np.random.default_rng(2)
    t = FFMTrainer(f"-dims {DIMS} -factors {K} -fields {F} -mini_batch {B} "
                   "-opt adagrad -classification -halffloat "
                   "-ffm_table parts -eta0 0.05")
    assert t.layout == "parts" and t.interaction == "fieldmajor"
    # planted signal: label = sign of w-ish feature pattern
    n = 512
    idx = rng.integers(1, DIMS, (n, L)).astype(np.int32)
    lab = np.where(idx[:, 0] % 2 == 0, 1.0, -1.0).astype(np.float32)
    fld = np.tile(np.arange(L, dtype=np.int32) % F, (n, 1))
    losses = []
    # 3 epochs, not 6: the planted signal converges fully inside epoch 1
    # (loss ratio ~0.0, acc 1.0 measured) — the extra epochs were ~40s of
    # pure wall against the 870s tier-1 cap on the 2-core container
    for e in range(3):
        for st in range(0, n, B):
            sl = slice(st, st + B)
            batch = SparseBatch(idx[sl], (idx[sl] != 0).astype(np.float32),
                                lab[sl], fld[sl])
            losses.append(float(t._train_batch(t._preprocess_batch(batch))))
    assert losses[-1] < losses[0] * 0.8, losses[:2] + losses[-2:]

    scores = t._score_batch(SparseBatch(
        idx[:64], (idx[:64] != 0).astype(np.float32), lab[:64], fld[:64]))
    assert scores.shape == (64,) and np.isfinite(scores).all()
    # scores orient with labels after training
    acc = ((scores > 0) == (lab[:64] > 0)).mean()
    assert acc > 0.7, acc


def test_partial_batch_padding():
    rng = np.random.default_rng(3)
    t = FFMTrainer(f"-dims {DIMS} -factors {K} -fields {F} -mini_batch {B} "
                   "-opt adagrad -classification -halffloat "
                   "-ffm_table parts")
    idx, val, lab = _mk_batch(rng, b=37)     # not a multiple of 8
    fld = np.tile(np.arange(L, dtype=np.int32) % F, (37, 1))
    b2 = t._preprocess_batch(SparseBatch(idx, val, lab, fld))
    assert b2.batch_size == 128 and b2.n_valid == 37
    lo = float(t._train_batch(b2))
    assert np.isfinite(lo)
    s = t._score_batch(SparseBatch(idx, val, lab, fld))
    assert s.shape == (37,)


def test_model_rows_and_weights_roundtrip():
    rng = np.random.default_rng(4)
    t = FFMTrainer(f"-dims {DIMS} -factors {K} -fields {F} -mini_batch 64 "
                   "-opt adagrad -classification -halffloat "
                   "-ffm_table parts")
    idx, val, lab = _mk_batch(rng, b=64, zero_frac=0.0)
    fld = np.tile(np.arange(L, dtype=np.int32) % F, (64, 1))
    t._train_batch(t._preprocess_batch(SparseBatch(idx, val, lab, fld)))
    t._note_batch(SparseBatch(idx, val, lab, fld))
    rows = list(t.model_rows())
    assert rows[0][0] == "0"                  # w0 row
    assert len(rows) > 1
    w = t._finalized_weights()
    assert w.shape == (F * t.MRF,)
    t._load_weights(np.zeros_like(w))
    assert np.abs(t._finalized_weights()).max() == 0.0


def test_guards():
    with pytest.raises(ValueError, match="adagrad"):
        FFMTrainer(f"-dims {DIMS} -factors {K} -fields {F} -mini_batch 64 "
                   "-opt sgd -classification -halffloat -ffm_table parts")
    t = FFMTrainer(f"-dims {DIMS} -factors {K} -fields {F} -mini_batch 64 "
                   "-opt adagrad -classification -halffloat "
                   "-ffm_table parts")
    # round 4: parts DOES mesh now (make_parts_step_sharded) — but field
    # and batch divisibility are validated (F=31 here; tp=4 cannot divide)
    with pytest.raises(ValueError, match="divisible by the tp axis"):
        t._apply_mesh("dp=2,tp=4")
    with pytest.raises(ValueError, match="MIX"):
        t._get_weights_at(np.array([1, 2], np.int64))


def test_l2_count_lane_matches_slab_oracle():
    """The kernel's count-lane L2 (lam * T[r] * count) must equal the
    joint step's slab-level per-occurrence L2 summed over occurrences."""
    rng = np.random.default_rng(5)
    idx, val, lab = _mk_batch(rng, b=128)
    mask = np.ones(128, np.float32)
    loss = get_loss("logloss")
    interp = pallas_interpret()
    lam_w, lam_v = 0.02, 0.01
    step = fp.make_parts_step(loss, lambda t: 0.1, (0.0, lam_w, lam_v),
                              F, K, MRF, interpret=interp)

    key = jax.random.PRNGKey(7)
    Tl = jnp.concatenate([
        jax.random.normal(key, (F * MRF, F * K)) * 0.1,
        jnp.zeros((F * MRF, 256 - F * K))], axis=1)
    T2_np = np.asarray(Tl.reshape(F * MRF * 2, 128).astype(jnp.bfloat16))
    params = {"T2": jnp.asarray(T2_np), "w0": jnp.zeros((), jnp.float32)}
    opt = {"T2": {"gg": jnp.zeros((F * MRF * 2, 128), jnp.float32)},
           "w0": {"gg": jnp.zeros((), jnp.float32)}}
    p1, s1, _ = step(params, opt, 0.0, jnp.asarray(idx), jnp.asarray(val),
                     jnp.asarray(lab), jnp.asarray(mask))

    # oracle: XLA scatter of (grad + lam*slab*pm), dense AdaGrad
    def oracle(T2, S2):
        wp, hp = 256, 2
        b = idx.shape[0]
        valj = jnp.asarray(val)
        idxT, valT = jnp.asarray(idx).T, valj.T
        fieldT = (jnp.arange(L, dtype=jnp.int32) % F)[:, None]
        rows = fp.parts_row_hash(idxT, fieldT, MRF)
        slab = T2.reshape(F * MRF, hp, 128)[rows]

        def bl(slabf):
            phi = fp._phi_parts(0.0, slabf.reshape(L, b, wp), valT, F, K)
            return (loss.loss(phi, jnp.asarray(lab))).sum()

        gslab = jax.grad(bl)(slab).astype(jnp.bfloat16).astype(
            jnp.float32).reshape(L, b, wp)
        FK = F * K
        pm = (valT != 0).astype(jnp.float32)
        lam_col = jnp.concatenate([
            jnp.full((FK,), lam_v, jnp.float32), jnp.zeros((1,)),
            jnp.zeros((wp - FK - 1,), jnp.float32)])
        lam_col = lam_col.at[FK].set(lam_w)
        gslab = gslab + lam_col * slab.astype(jnp.float32).reshape(
            L, b, wp) * pm[..., None]
        G = jnp.zeros((F * MRF, hp, 128), jnp.float32).at[rows].add(
            gslab.reshape(L, b, hp, 128))
        G2 = G.reshape(F * MRF * hp, 128)
        # pad columns carry no L2 and no grad in the oracle
        gg = S2 + G2 * G2
        T2n = (T2.astype(jnp.float32)
               - 0.1 * G2 / (jnp.sqrt(gg) + 1e-6)).astype(T2.dtype)
        return T2n, gg

    T2o, ggo = jax.jit(oracle)(jnp.asarray(T2_np),
                               jnp.zeros((F * MRF * 2, 128), jnp.float32))
    # compare on live columns only (kernel masks pads; count lane differs)
    wlane = F * K - 128
    live = np.ones((1, 128), np.float32)
    live_odd = (np.arange(128) <= wlane).astype(np.float32)
    live2 = np.stack([live[0], live_odd])
    liveM = jnp.asarray(np.tile(live2, (F * MRF, 1)))
    sig = (ggo > 1e-5) & (liveM > 0)
    dT = float((jnp.abs(p1["T2"].astype(jnp.float32)
                        - T2o.astype(jnp.float32)) * sig).max())
    rS = float(((jnp.abs(s1["T2"]["gg"] - ggo) / (ggo + 1e-2)) * liveM).max())
    assert dT < 2e-2, f"L2 T2 mismatch {dT}"
    assert rS < 0.2, f"L2 gg mismatch {rS}"
