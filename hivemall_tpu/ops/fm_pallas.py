"""Pallas fused scatter/optimizer step for train_ffm — the "parts" layout.

Reference behavior: hivemall.fm.FieldAwareFactorizationMachineUDTF's per-row
AdaGrad updates of (feature, field) latent cells (SURVEY.md §3.6). This
module is the round-3 answer to the flagship gap: the XLA scatter-add costs
~24-26 ns per table row (measured, experiments/probe_idx.py) and the dense
optimizer pass another ~7 ms — together over half the step. Here the whole
gradient-accumulate + AdaGrad-apply side runs in one Pallas kernel against a
VMEM-resident per-field gradient tile, so the batch gradient NEVER
materializes in HBM and the optimizer pass rides the same kernel.

Layout ("parts" = field-partitioned fused feature rows):
  - logical table: F partitions x MRF rows, row (g, h) = the fused record
    [V[g,h][0..F-1][0..K-1] | w | pad] of one hashed feature whose OWN field
    is g: Wp = 128*ceil((F*K+8)/128) columns. Capacity F*MRF >= Mr matches
    the joint layout's -dims semantics (collisions only within a field).
  - physical storage: T2 [F*MRF*HP, 128] (HP = Wp/128 half-rows), i.e. each
    logical row r is HP consecutive 128-lane rows starting at HP*r. ONE
    gather index per slot fetches the (HP, 128) window via the free
    [N*HP, 128] -> [N, HP, 128] reshape; the same trick makes the gradient
    slab reshape into the kernel's (16, 128) bf16 tiles for free.
  - AdaGrad state S2 f32, co-shaped with T2.

Step (shapes for the flagship: B=32768, L=F=40, K=4, MRF=8192, Wp=256):
  1. XLA: rows[l, b] = l//? -- slot l has field l % F; flat row id =
     (l % F) * MRF + (murmur-mix(idx) & (MRF-1)).
  2. XLA: slab = T3[rows]  ([L, B, HP, 128], ONE index op per slot), fwd
     phi + loss + grad wrt slab via autodiff (same math as
     ops.fm._fused_phi_fieldmajor, axes [L, B]), per-occurrence L2 folded
     into the slab gradient exactly like make_ffm_step_fused.
  3. Pallas (grid (F, m*nc + n_opt)): accumulate the packed bf16 gradient
     tiles into G [MRF*HP/8, 8, 128] f32 VMEM scratch by per-slot
     roll+add RMW (measured ~17 ns/row vs XLA scatter's 24-26), then in the
     same kernel's tail steps apply AdaGrad to the partition's T2/S2 blocks
     (in-place via input_output_aliases).

Semantics deltas vs make_ffm_step_fused (documented, tested):
  - hashing: per-field hash h_g(idx) instead of one joint feature hash, so
    a feature id appearing under two different fields occupies two rows
    (the reference's packed-long (feature, field) keys are also distinct
    per field; capacity is F*MRF*f_pow2-ish >= -dims).
  - AdaGrad accumulators see the square of the summed minibatch gradient,
    same as the joint fused step.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .losses import Loss

__all__ = ["parts_geometry", "parts_row_hash", "make_parts_step",
           "make_parts_step_sharded", "make_parts_score", "parts_supported"]

_J1, _J3 = 0x9E3779B1, 0xC2B2AE35
_EPS = 1e-6


def parts_geometry(dims: int, F: int, K: int) -> Tuple[int, int, int]:
    """(MRF, Wp, HP): per-field partition rows, padded row width, and
    half-rows per logical row. MRF is the power of two making F*MRF the
    smallest field-partitioned table with at least the joint layout's
    Mr = dims / next_pow2(F) rows (same -dims capacity semantics)."""
    f_pow2 = 1
    while f_pow2 < F:
        f_pow2 <<= 1
    mr_joint = max(1 << 10, dims // f_pow2)
    mrf = 1 << 10
    while F * mrf < mr_joint:
        mrf <<= 1
    wp = 128 * (-(-(F * K + 8) // 128))
    return mrf, wp, wp // 128


def parts_row_hash(idx, field, MRF: int):
    """Flat physical row id in [0, F*MRF): field partition + murmur-mix of
    the feature id folded to the partition (ops.fm.ffm_row_hash's mix).
    Row 0 of each partition doubles as that partition's padding row."""
    h = idx.astype(jnp.uint32) * jnp.uint32(_J1)
    h = h ^ (h >> 15)
    h = h * jnp.uint32(_J3)
    h = h ^ (h >> 13)
    return (field.astype(jnp.int32) * MRF
            + (h & jnp.uint32(MRF - 1)).astype(jnp.int32))


def _phi_parts(w0f, slab, val, F: int, K: int):
    """Field-major FFM score over [L, B, Wp] slabs (slot-major axes — the
    [B, L] version is ops.fm._fused_phi_fieldmajor; same math, same no-L^2
    factorization). The interaction runs in the slab's own dtype with f32
    accumulation — the same -halffloat policy the joint path applies to
    its pair mixing (bf16 halves the C-tensor traffic, measured +17%
    there); the linear part is always f32."""
    L, B = val.shape
    m = L // F
    FK = F * K
    Vg = slab[..., :FK].reshape(m, F, B, F, K)       # [m, g, B, f, k]
    wg = slab[..., FK].astype(jnp.float32)           # [L, B]
    U = Vg * val.reshape(m, F, B, 1, 1).astype(Vg.dtype)
    C = U if m == 1 else U.astype(jnp.float32).sum(0, keepdims=True)
    C = C.reshape(F, B, F, K)                        # [g, B, f, k]
    full = jnp.einsum("gbfk,fbgk->b", C, C,
                      preferred_element_type=jnp.float32)
    own = jnp.einsum("mgbgk->mbgk", U.reshape(m, F, B, F, K)).astype(
        jnp.float32)
    diag = (own * own).sum((0, 2, 3))
    return w0f + (wg * val).sum(0) + 0.5 * (full - diag)


def _roll_pad8(piece, shift):
    """piece [2, 128] f32 -> [8, 128] with the pair placed at sublane-pair
    `shift` (dynamic); other sublanes zero."""
    padded = jnp.concatenate([piece, jnp.zeros((6, 128), piece.dtype)], 0)
    return pltpu.roll(padded, shift * 2, 0)


def _make_scatter_opt_kernel(B: int, L: int, F: int, MRF: int, HP: int,
                             chunk: int, r_opt: int, FK: int,
                             lam_w: float = 0.0, lam_v: float = 0.0,
                             interpret: bool = False):
    """pallas_call: accumulate packed gradient tiles into a VMEM G and
    apply AdaGrad to the field partition's T2/S2 blocks in the tail steps.

    Per-occurrence L2 rides a COUNT LANE: the XLA side writes the slot's
    presence (pm) into pad column FK+2 of each gradient row, so the same
    accumulate pass yields count(r) = number of live occurrences of row r,
    and the opt phase applies lam * T[r] * count(r) — exactly the summed
    slab-level lam * slab * pm of the joint step (every occurrence's slab
    IS T[r]). Pad lanes are masked out of the weight update.

    Only HP == 2 with FK >= 128 is wired (Wp = 256, count lane in the odd
    half-row); other widths fall back to the XLA step.
    """
    assert HP == 2 and 128 <= FK <= 248
    m = L // F
    nc = B // chunk
    n_acc = m * nc
    gt_rows = MRF * HP // 8          # f32 (8,128) G tiles per partition
    n_opt = MRF * HP // r_opt
    grid = (F, n_acc + n_opt)
    cnt_lane = FK + 2 - 128          # pad column FK+2, odd half-row
    w_lane = FK - 128                # linear-weight column, odd half-row


    def kernel(rows_ref, eta_ref, lam_ref, live_ref, g_ref, t_ref, s_ref,
               tout_ref, sout_ref, G_ref):
        c = pl.program_id(1)

        @pl.when(c == 0)
        def _():
            G_ref[...] = jnp.zeros_like(G_ref)

        @pl.when(c < n_acc)
        def _acc():
            cc = c % nc
            base = (c // nc) * B          # slot-row offset (m > 1)

            def body(i, _):
                # one packed bf16 tile = 8 slots' (2,128) gradient rows
                gtile = g_ref[0, i].astype(jnp.float32)       # [16, 128]
                for u in range(8):
                    j = base + cc * chunk + i * 8 + u
                    r = rows_ref[0, j >> 7, j & 127]          # local row
                    piece = gtile[2 * u:2 * u + 2, :]
                    G_ref[r >> 2] += _roll_pad8(piece, r & 3)
                return 0

            jax.lax.fori_loop(0, chunk // 8, body, 0)

        @pl.when(c >= n_acc)
        def _opt():
            j = c - n_acc
            Gt = G_ref[pl.ds(j * (r_opt // 8), r_opt // 8)]
            G2 = Gt.reshape(r_opt, 128)
            w = t_ref[...].astype(jnp.float32)
            if lam_w or lam_v:
                # occurrence counts ride pad lane cnt_lane of ODD rows.
                # Mosaic has no two-axis broadcast, so: mask everything
                # but that lane, lane-broadcast by a ones matmul (MXU),
                # then spread odd->even sublanes with a roll.
                row_i = jax.lax.broadcasted_iota(jnp.int32,
                                                 (r_opt, 128), 0)
                lane_i = jax.lax.broadcasted_iota(jnp.int32,
                                                  (r_opt, 128), 1)
                sel = ((lane_i == cnt_lane)
                       & ((row_i & 1) == 1)).astype(jnp.float32)
                ones_m = (jax.lax.broadcasted_iota(
                    jnp.int32, (128, 128), 0) >= 0).astype(jnp.float32)
                bcast = jax.lax.dot_general(
                    G2 * sel, ones_m, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                cnt = bcast + pltpu.roll(bcast, r_opt - 1, 0)
                lam_t = jnp.tile(lam_ref[...], (r_opt // 8, 1))
                live_t = jnp.tile(live_ref[...], (r_opt // 8, 1))
                Geff = (G2 + lam_t * w * cnt) * live_t
            else:
                Geff = G2
            gg = s_ref[...] + Geff * Geff
            wn = w - eta_ref[0, 0] * Geff / (jnp.sqrt(gg) + _EPS)
            sout_ref[...] = gg
            tout_ref[...] = wn.astype(tout_ref.dtype)

    def rows_spec():
        return pl.BlockSpec((1, (m * B) // 128, 128),
                            lambda g, c: (g, 0, 0),
                            memory_space=pltpu.SMEM)

    def g_spec():
        # packed grad [F, m*B*HP/16, 16, 128] bf16; block = one chunk of
        # one slot-row (m index folded into the chunk sequence)
        return pl.BlockSpec(
            (1, chunk * HP // 16, 16, 128),
            lambda g, c: (g, jnp.minimum(c, n_acc - 1), 0, 0),
            memory_space=pltpu.VMEM)

    def t_spec():
        # T2 [F*MRF*HP, 128] -> per-partition opt blocks of r_opt rows;
        # during accumulate steps park on the partition's block 0 (fetched
        # once; contents unused there)
        def imap(g, c):
            j = jnp.maximum(c - n_acc, 0)
            return (g * n_opt + j, 0)
        return imap

    eta_spec = pl.BlockSpec((1, 1), lambda g, c: (0, 0),
                            memory_space=pltpu.SMEM)
    pat_spec = pl.BlockSpec((8, 128), lambda g, c: (0, 0),
                            memory_space=pltpu.VMEM)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            rows_spec(),
            eta_spec,
            pat_spec,
            pat_spec,
            g_spec(),
            pl.BlockSpec((r_opt, 128), t_spec(), memory_space=pltpu.VMEM),
            pl.BlockSpec((r_opt, 128), t_spec(), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((r_opt, 128), t_spec(), memory_space=pltpu.VMEM),
            pl.BlockSpec((r_opt, 128), t_spec(), memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((F * MRF * HP, 128), jnp.bfloat16),
            jax.ShapeDtypeStruct((F * MRF * HP, 128), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((gt_rows, 8, 128), jnp.float32)],
        input_output_aliases={5: 0, 6: 1},
        interpret=interpret,
    )


def _make_scatter_accum_kernel(Bd: int, Ll: int, Fl: int, MRF: int, HP: int,
                               chunk: int, interpret: bool = False):
    """Accumulate-only twin of _make_scatter_opt_kernel for the SHARDED
    parts step: the same per-slot roll+add RMW (~17 ns/slot) into the same
    VMEM-resident G scratch, but the tail steps copy G out to HBM instead
    of running the optimizer — the sharded step must psum G over 'dp'
    before any optimizer math (per-replica AdaGrad on partial gradients is
    NOT minibatch AdaGrad), so the tail runs as a dense XLA update on each
    rank's table shard. Extra HBM traffic vs the fused kernel: one G
    write + one read (~2 table passes) — the scatter itself still never
    materializes per-slot.

    G is a scratch with small tail out blocks, not one whole-partition out
    block: at MRF=8192 that block is 8 MiB and Pallas double-buffers it,
    which Mosaic refuses on v5e (18 MiB against the 16 MiB scoped-VMEM
    limit). This way the footprint matches the single-chip kernel's."""
    assert HP == 2
    m = Ll // Fl
    nc = Bd // chunk
    n_acc = m * nc
    gt_rows = MRF * HP // 8          # f32 (8,128) G tiles per partition
    t_out = min(256, gt_rows)        # G tiles copied out per tail step
    n_out = gt_rows // t_out
    grid = (Fl, n_acc + n_out)

    def kernel(rows_ref, g_ref, out_ref, G_ref):
        c = pl.program_id(1)

        @pl.when(c == 0)
        def _():
            G_ref[...] = jnp.zeros_like(G_ref)

        @pl.when(c < n_acc)
        def _acc():
            cc = c % nc
            base = (c // nc) * Bd

            def body(i, _):
                gtile = g_ref[0, i].astype(jnp.float32)       # [16, 128]
                for u in range(8):
                    j = base + cc * chunk + i * 8 + u
                    r = rows_ref[0, j >> 7, j & 127]
                    piece = gtile[2 * u:2 * u + 2, :]
                    G_ref[r >> 2] += _roll_pad8(piece, r & 3)
                return 0

            jax.lax.fori_loop(0, chunk // 8, body, 0)

        @pl.when(c >= n_acc)
        def _out():
            out_ref[0] = G_ref[pl.ds((c - n_acc) * t_out, t_out)]

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, (m * Bd) // 128, 128), lambda g, c: (g, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, chunk * HP // 16, 16, 128),
                         lambda g, c: (g, jnp.minimum(c, n_acc - 1), 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        # parked on the partition's block 0 while accumulating; the first
        # tail step fills that block before the index ever moves
        out_specs=pl.BlockSpec(
            (1, t_out, 8, 128),
            lambda g, c: (g, jnp.maximum(c - n_acc, 0), 0, 0),
            memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((Fl, gt_rows, 8, 128), jnp.float32),
        scratch_shapes=[pltpu.VMEM((gt_rows, 8, 128), jnp.float32)],
        interpret=interpret,
    )


def parts_supported(F: int, K: int, opt_name: str, dtype) -> bool:
    """The pallas step handles the flagship envelope; everything else uses
    the XLA joint step."""
    wp = 128 * (-(-(F * K + 8) // 128))
    return (wp == 256 and 128 <= F * K <= 248 and opt_name == "adagrad"
            and dtype == jnp.bfloat16
            and jax.default_backend() in ("tpu", "cpu"))


def make_parts_step(loss: Loss, eta_fn: Callable, lambdas, F: int, K: int,
                    MRF: int, unit_val: bool = False,
                    interpret: bool = False) -> Callable:
    """Jitted train step over the parts layout.

    params: {"w0": f32 scalar-ish, "T2": [F*MRF*HP, 128] bf16}
    opt_state: {"w0": {"gg"}, "T2": {"gg": S2 [F*MRF*HP, 128] f32}}
    batch: canonical field-major idx [B, L] (slot s -> field s % F), val
    [B, L] (or elided), label [B], row_mask [B].
    """
    lam0, lam_w, lam_v = lambdas
    wp = 128 * (-(-(F * K + 8) // 128))
    hp = wp // 128
    assert hp == 2, "parts step requires Wp == 256 (use parts_supported)"
    FK = F * K

    def step_impl(params, opt_state, t, idx, val, label, row_mask):
        T2, w0 = params["T2"], params["w0"]
        S2 = opt_state["T2"]["gg"]
        B, L = idx.shape
        m = L // F
        chunk = min(2048, B)
        assert B % chunk == 0 and (m * B) % 128 == 0, \
            "parts step needs the batch padded to a multiple of 128 " \
            "(<=2048) or 2048 (see FFMTrainer._pad_parts_rows)"
        r_opt = min(1024, MRF * hp)
        kern = _make_scatter_opt_kernel(B, L, F, MRF, hp, chunk, r_opt,
                                        FK, lam_w, lam_v,
                                        interpret=interpret)

        if val is None:
            val = (idx != 0).astype(jnp.float32)
        # slot-major orientation
        idxT = idx.T                                    # [L, B]
        valT = val.T
        fieldT = (jnp.arange(L, dtype=jnp.int32) % F)[:, None]
        rows = parts_row_hash(idxT, fieldT, MRF)        # [L, B] flat ids
        if m == 1:
            # one gather PER FIELD PARTITION: XLA's row-gather runs
            # ~10.7 ns/row from an 8k-row partition vs ~17 ns from the
            # full table (measured, /tmp gather A/B + probe_size.py) —
            # the slot order IS the field order, so the stack is slab
            T4 = T2.reshape(F, MRF, hp, 128)
            local_rows = rows - fieldT * MRF
            slab = jnp.stack([T4[g][local_rows[g]] for g in range(F)])
        else:
            T3 = T2.reshape(F * MRF, hp, 128)
            slab = T3[rows]                             # [L, B, hp, 128]

        def batch_loss(w0f, slabf):
            s = slabf.reshape(L, B, wp)
            phi = _phi_parts(w0f, s, valT, F, K)
            data = (loss.loss(phi, label) * row_mask).sum()
            if lam_w or lam_v:
                # per-occurrence L2 rides the kernel's count lane (pad
                # column FK+2): each slot's gradient must carry pm there
                # so the scatter pass accumulates count(r) and the opt
                # phase applies lam * T[r] * count(r) — identical to the
                # joint step's slab-level lam * slab * pm. Emitting the
                # lane THROUGH autodiff (gradient of sum(slab_cnt * pm)
                # is exactly pm) fuses it into the existing backward pass;
                # the loss value is unchanged because pad columns of T are
                # zero forever (live-masked in the kernel's update).
                pm = ((valT != 0).astype(jnp.float32)
                      * row_mask[None, :])
                data = data + jnp.sum(
                    s[..., FK + 2].astype(jnp.float32) * pm)
            return data

        loss_sum, (g0, gslab) = jax.value_and_grad(
            batch_loss, argnums=(0, 1))(w0.astype(jnp.float32), slab)
        gslab = gslab.astype(jnp.bfloat16).reshape(L, B, wp)
        g0 = g0 + lam0 * w0.astype(jnp.float32)

        # pack for the kernel: [L, B, hp, 128] -> [F, m*B*hp/16, 16, 128]
        gpack = gslab.reshape(L, B, hp, 128).astype(jnp.bfloat16)
        gpack = gpack.reshape(m, F, B * hp // 16, 16, 128)
        gpack = gpack.transpose(1, 0, 2, 3, 4).reshape(
            F, m * B * hp // 16, 16, 128)
        # local (within-partition) row ids for the kernel, [F, m*B] packed
        local = (rows - fieldT * MRF).reshape(m, F, B)
        local = local.transpose(1, 0, 2).reshape(F, (m * B) // 128, 128)

        eta_t = jnp.asarray(eta_fn(t), jnp.float32).reshape(1, 1)
        w_lane = FK - 128
        lane = jnp.arange(128)
        lam_row = jnp.where(lane < w_lane, lam_v,
                            jnp.where(lane == w_lane, lam_w, 0.0))
        lam8 = jnp.tile(jnp.stack([jnp.full((128,), lam_v, jnp.float32),
                                   lam_row.astype(jnp.float32)]), (4, 1))
        live8 = jnp.tile(jnp.stack([
            jnp.ones((128,), jnp.float32),
            (lane <= w_lane).astype(jnp.float32)]), (4, 1))
        T2n, S2n = kern(local, eta_t, lam8, live8, gpack, T2, S2)

        # w0: plain AdaGrad scalar step
        gg0 = opt_state["w0"]["gg"] + g0 * g0
        w0n = (w0.astype(jnp.float32)
               - eta_fn(t) * g0 / (jnp.sqrt(gg0) + _EPS)).astype(w0.dtype)

        return ({"T2": T2n, "w0": w0n},
                {"T2": {"gg": S2n}, "w0": {"gg": gg0}}, loss_sum)

    if unit_val:
        def core(params, opt_state, t, idx, label, row_mask):
            return step_impl(params, opt_state, t, idx, None, label,
                             row_mask)
    else:
        def core(params, opt_state, t, idx, val, label, row_mask):
            return step_impl(params, opt_state, t, idx, val, label,
                             row_mask)
    # scannable: -steps_per_dispatch > 1 runs this same core as a lax.scan
    # body (the pallas_call is an ordinary custom call in the loop body;
    # state flows through the donated scan carry)
    from .scan import scannable
    return scannable(partial(jax.jit, donate_argnums=(0, 1))(core), core)


def _phi_parts_sharded(w0f, slab, val_l, F: int, Fl: int,
                       K: int, m: int, ti):
    """Per-tp-rank partial of _phi_parts over the rank's Fl local field
    partitions, completed by one all_to_all + psum over 'tp'.

    The cross-field sum full = Σ_{g,f,k} C[g,b,f,k]·C[f,b,g,k] factors by
    which rank owns f: each rank holds C_local[fl, b, g, k] for its own
    fields fl and ALL g, and needs C[g, b, f(fl), k] for all g — exactly
    the field-axis transpose an all_to_all over 'tp' delivers (the
    sequence-parallel a2a pattern, with fields in the sequence role).
    Every (g, f) term is produced on exactly one rank, so psum('tp')
    completes phi; autodiff through the collectives gives each rank its
    local slab cotangent with no extra communication."""
    Ll, Bd = val_l.shape
    FK = F * K
    Vg = slab[..., :FK].reshape(m, Fl, Bd, F, K)
    wg = slab[..., FK].astype(jnp.float32)
    U = Vg * val_l.reshape(m, Fl, Bd, 1, 1).astype(Vg.dtype)
    C = U if m == 1 else U.astype(jnp.float32).sum(0, keepdims=True)
    C = C.reshape(Fl, Bd, F, K)
    Cx = jax.lax.all_to_all(C, "tp", split_axis=2, concat_axis=0,
                            tiled=True)              # [F, Bd, Fl, K]
    partial_full = jnp.einsum("gbfk,fbgk->b", Cx, C,
                              preferred_element_type=jnp.float32)
    gidx = (ti * Fl + jnp.arange(Fl, dtype=jnp.int32))
    own = jnp.take_along_axis(
        U.reshape(m, Fl, Bd, F, K),
        gidx[None, :, None, None, None], axis=3)[..., 0, :].astype(
            jnp.float32)                             # [m, Fl, Bd, K]
    diag = (own * own).sum((0, 1, 3))
    lin = (wg * val_l).sum(0)
    return w0f + jax.lax.psum(lin + 0.5 * (partial_full - diag), "tp")


def make_parts_step_sharded(loss: Loss, eta_fn: Callable, lambdas, F: int,
                            K: int, MRF: int, mesh, unit_val: bool = False,
                            interpret: bool = False) -> Callable:
    """Multi-chip parts step: fields shard over 'tp', batch over 'dp'
    (VERDICT r3 next #2; SURVEY §4.4 rebuild note — table sharded TP-like,
    psum partial dots).

    Decomposition per device (shard_map; pallas_call cannot be GSPMD-cut):
      - T2/S2 shard by FIELD PARTITION over 'tp' (rows are partition-major,
        so the shard boundary is a partition boundary and every slab gather
        stays inside the rank's own shard — zero gather communication).
      - idx/val/label/row_mask shard over 'dp'; each rank slices its own
        tp field columns locally ([Bd, m, F] -> [Bd, m, Fl]).
      - interaction: one bf16 all_to_all of the C tensor + psum over 'tp'
        (_phi_parts_sharded).
      - scatter: the accumulate-only Pallas kernel per rank; G then psums
        over 'dp' (minibatch-AdaGrad semantics) and the optimizer tail is
        a dense XLA update on the local shard — same count-lane L2 and
        live masks as the fused single-chip kernel, which stays the
        mesh=None path (its rate is the flagship headline).
    """
    from jax.sharding import PartitionSpec as P
    dp, tp = mesh.shape["dp"], mesh.shape["tp"]
    assert F % tp == 0, (F, tp)
    Fl = F // tp
    lam0, lam_w, lam_v = lambdas
    wp = 128 * (-(-(F * K + 8) // 128))
    hp = wp // 128
    assert hp == 2, "sharded parts step requires Wp == 256"
    FK = F * K
    cnt_lane = FK + 2 - 128
    w_lane = FK - 128

    def local_step(params, opt_state, t, idx, val, label, row_mask):
        T2, w0 = params["T2"], params["w0"]
        S2 = opt_state["T2"]["gg"]
        Bd, L = idx.shape
        m = L // F
        Ll = m * Fl
        chunk = min(2048, Bd)
        if Bd % chunk or (m * Bd) % 128:
            raise ValueError(
                f"sharded parts step: per-rank batch {Bd} must be a "
                f"multiple of 128 and, above 2048, of 2048 (see "
                "FFMTrainer._pad_parts_rows / _apply_mesh_parts)")
        ti = jax.lax.axis_index("tp")
        if val is None:
            val = (idx != 0).astype(jnp.float32)
        idx3 = idx.reshape(Bd, m, F)
        val3 = val.reshape(Bd, m, F)
        idx_l = jax.lax.dynamic_slice_in_dim(idx3, ti * Fl, Fl, 2)
        val_l = jax.lax.dynamic_slice_in_dim(val3, ti * Fl, Fl, 2)
        idxT = idx_l.transpose(1, 2, 0).reshape(Ll, Bd)   # slot = r*Fl + gl
        valT = val_l.transpose(1, 2, 0).reshape(Ll, Bd)
        glT = (jnp.arange(Ll, dtype=jnp.int32) % Fl)[:, None]
        # the hash FOLD depends only on idx, so local row placement is
        # identical to the single-chip table's placement in this partition
        rows = parts_row_hash(idxT, glT, MRF)             # [Ll, Bd] local
        if m == 1:
            T4 = T2.reshape(Fl, MRF, hp, 128)
            local_rows = rows - glT * MRF
            slab = jnp.stack([T4[g][local_rows[g]] for g in range(Fl)])
        else:
            T3g = T2.reshape(Fl * MRF, hp, 128)
            slab = T3g[rows]                              # [Ll, Bd, hp, 128]

        def batch_loss(w0f, slabf):
            s = slabf.reshape(Ll, Bd, wp)
            phi = _phi_parts_sharded(w0f, s, valT, F, Fl, K, m, ti)
            data = (loss.loss(phi, label) * row_mask).sum()
            # tp rank 0 OWNS each row's data loss: shard_map transposes
            # psum to psum, so an unmasked (replicated) loss would hand
            # every rank a tp-x slab cotangent through _phi_parts_sharded's
            # psum — this mask makes the summed cotangent exactly 1x on
            # every rank (and g0/loss_sum recover the total via a
            # ('dp','tp') psum below). The count-lane L2 term sits OUTSIDE
            # the mask: it is rank-local slab state, already 1x.
            data = data * jnp.where(ti == 0, 1.0, 0.0)
            if lam_w or lam_v:
                pm = ((valT != 0).astype(jnp.float32) * row_mask[None, :])
                data = data + jnp.sum(
                    s[..., FK + 2].astype(jnp.float32) * pm)
            return data

        loss_sum, (g0, gslab) = jax.value_and_grad(
            batch_loss, argnums=(0, 1))(w0.astype(jnp.float32), slab)
        gslab = gslab.astype(jnp.bfloat16).reshape(Ll, Bd, wp)
        g0 = jax.lax.psum(g0, ("dp", "tp")) + lam0 * w0.astype(jnp.float32)
        loss_sum = jax.lax.psum(loss_sum, ("dp", "tp"))

        gpack = gslab.reshape(Ll, Bd, hp, 128)
        gpack = gpack.reshape(m, Fl, Bd * hp // 16, 16, 128)
        gpack = gpack.transpose(1, 0, 2, 3, 4).reshape(
            Fl, m * Bd * hp // 16, 16, 128)
        local = (rows - glT * MRF).reshape(m, Fl, Bd)
        local = local.transpose(1, 0, 2).reshape(Fl, (m * Bd) // 128, 128)
        kern = _make_scatter_accum_kernel(Bd, Ll, Fl, MRF, hp, chunk,
                                          interpret=interpret)
        G = kern(local, gpack)                            # [Fl, ·, 8, 128]
        G = jax.lax.psum(G, "dp")

        # dense XLA optimizer tail on the local shard — same math as the
        # fused kernel's _opt phase (count-lane L2, live masks)
        G3 = G.reshape(Fl * MRF, hp, 128)
        T3 = T2.astype(jnp.float32).reshape(Fl * MRF, hp, 128)
        S3 = S2.reshape(Fl * MRF, hp, 128)
        lane = jnp.arange(128)
        if lam_w or lam_v:
            cnt = G3[:, 1, cnt_lane]                      # [rows]
            lam_hp = jnp.stack([
                jnp.full((128,), lam_v, jnp.float32),
                jnp.where(lane < w_lane, lam_v,
                          jnp.where(lane == w_lane, lam_w, 0.0))])
            live_hp = jnp.stack([jnp.ones((128,), jnp.float32),
                                 (lane <= w_lane).astype(jnp.float32)])
            Geff = (G3 + lam_hp[None] * T3 * cnt[:, None, None]) \
                * live_hp[None]
        else:
            Geff = G3
        gg = S3 + Geff * Geff
        eta_t = jnp.asarray(eta_fn(t), jnp.float32)
        T3n = T3 - eta_t * Geff / (jnp.sqrt(gg) + _EPS)
        T2n = T3n.reshape(Fl * MRF * hp, 128).astype(T2.dtype)
        S2n = gg.reshape(Fl * MRF * hp, 128)

        gg0 = opt_state["w0"]["gg"] + g0 * g0
        w0n = (w0.astype(jnp.float32)
               - eta_fn(t) * g0 / (jnp.sqrt(gg0) + _EPS)).astype(w0.dtype)
        return ({"T2": T2n, "w0": w0n},
                {"T2": {"gg": S2n}, "w0": {"gg": gg0}}, loss_sum)

    pT = P("tp", None)
    param_spec = {"T2": pT, "w0": P()}
    opt_spec = {"T2": {"gg": pT}, "w0": {"gg": P()}}
    if unit_val:
        def fn(params, opt_state, t, idx, label, row_mask):
            return local_step(params, opt_state, t, idx, None, label,
                              row_mask)
        in_specs = (param_spec, opt_spec, P(), P("dp", None), P("dp"),
                    P("dp"))
    else:
        fn = local_step
        in_specs = (param_spec, opt_spec, P(), P("dp", None),
                    P("dp", None), P("dp"), P("dp"))
    smapped = jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                            out_specs=(param_spec, opt_spec, P()),
                            check_vma=False)
    return jax.jit(smapped, donate_argnums=(0, 1))


def make_parts_score(F: int, K: int, MRF: int):
    """Jitted scorer over the parts layout for canonical field-major
    batches (slot s -> field s % F)."""
    wp = 128 * (-(-(F * K + 8) // 128))
    hp = wp // 128

    @jax.jit
    def score(w0, T2, idx, val):
        if val is None:
            val = (idx != 0).astype(jnp.float32)
        B, L = idx.shape
        idxT, valT = idx.T, val.T
        fieldT = (jnp.arange(L, dtype=jnp.int32) % F)[:, None]
        rows = parts_row_hash(idxT, fieldT, MRF)
        T3 = T2.reshape(F * MRF, hp, 128)
        slab = T3[rows].astype(jnp.float32).reshape(L, B, wp)
        return _phi_parts(w0.astype(jnp.float32), slab, valT, F, K)

    return score
