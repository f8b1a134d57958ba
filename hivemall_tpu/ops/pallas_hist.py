"""Pallas TPU kernel: level-wise (node, feature, bin) histogram build.

Reference (SURVEY.md §3.9, §4.5): the tree hot loop in hivemall.smile's
DecisionTree.split() is a per-node candidate-split scan, and the xgboost
module's native C++ core does the same with binned histograms. BASELINE names
"Pallas histogram kernels" as the TPU-native replacement. This module is that
kernel.

Design — scatter-add is the natural formulation but lowers poorly on TPU
(XLA serializes scatter updates). Instead the histogram is recast as a
matmul so it rides the MXU:

    hist[f, s, m*B + b]  =  sum_r  onehot(idx_{r,f})[m*B + b] * ws[s, r]

where idx_{r,f} = node_local(r) * B + bin_code(r, f) is a combined
(node, bin) one-hot column per (row, feature). Layout is chosen for
Mosaic's tiling rules (last two block dims divisible by (8, 128)):

  - idx is transposed to [d_pad8, n_pad] so a (8, ROWS) block holds the
    feature's row chunk; the kernel selects its feature row with a dynamic
    SUBLANE index (supported), never a lane index (not supported);
  - ws is transposed/padded to [8, n_pad] (stat channels ≤ 8 per call);
  - the one-hot is built TRANSPOSED ([MB_TILE, ROWS], rows on the lane
    axis, matching idx's layout) via an iota compare on the VPU, then
    contracted with ws on the MXU, accumulating across the sequential
    row-chunk grid dimension.

Inactive / padded rows carry idx < 0 and match no one-hot column, so no
separate mask multiply is needed.

Cost note: the flat kernel's work is n * (M*B) * d compares + MACs per
level; once the frontier outgrows one 512-column tile the builder switches
to ``level_histogram_sorted`` below, whose per-level cost is n * 512 * d
independent of M (measured on v5e at n=1e6, d=28, M=256, B=64: 142ms vs
2208ms flat — 15x).

The pure-JAX scatter path in ops/trees.py is the reference the tests hold
this kernel to (in interpret mode, on a CPU asked for by name); on a TPU
the kernels always run, compiled via Mosaic (utils/device.py policy).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.device import pallas_interpret

__all__ = ["level_histogram", "level_histogram_sorted",
           "use_pallas_default"]

_ROWS = 256        # row-chunk tile (lane axis; multiple of 128)
_MB_TILE = 512     # flat-kernel max column tile (sublane axis of ohT)
_TW = 128          # sorted-kernel window tile: 4x fewer one-hot compares
                   # than 512 (the kernels are VPU-compare bound, not MXU
                   # bound — measured round 3, experiments/probe_trees.py)
_SCH = 8           # stat-channel slab (sublane tile) — S ≤ 8 per call


def use_pallas_default() -> bool:
    """The Pallas kernels whenever they compile (a TPU). On a CPU that was
    asked for by name the XLA scatter reference runs — the interpreter is
    too slow for whole forests; tests that want the kernels pass
    ``use_pallas=True``. Any other backend raises (pallas_interpret)."""
    return not pallas_interpret()


def _hist_kernel(idx_ref, ws_ref, out_ref, *, precision, tile, d):
    # The FEATURE loop lives INSIDE the kernel: one grid step histograms
    # every feature's row chunk against one column tile, so Mosaic's
    # per-grid-step overhead (measured dominant in round 3 — the per-step
    # compute is only ~1 us) amortizes over d features.
    m = pl.program_id(0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (tile, _ROWS), 0)
    first = pl.program_id(1) == 0
    for f in range(d):
        local = idx_ref[f, :] - m * tile                  # [_ROWS] lane vec
        oh_t = (cols == local[None, :]).astype(jnp.float32)
        acc = jax.lax.dot_general(                        # [_SCH, tile]
            ws_ref[:], oh_t,
            dimension_numbers=(((1,), (1,)), ((), ())),
            # (tile is the column-tile width: shallow levels size it to the
            # actual mb so a 64-column level-0 histogram does not pay for
            # 512 one-hot compare columns — an 8x waste measured in r2)
            # HIGHEST = f32-equivalent MXU passes (the default: gini /
            # gradient sums feed gain comparisons and must not round to
            # bf16). Callers whose stat channels are SMALL INTEGERS
            # (classification: class indicator x bootstrap count) pass
            # DEFAULT — single-pass bf16 products of exact-in-bf16
            # operands with f32 accumulation are still exact, at ~6x
            # fewer MXU passes. Mosaic supports only DEFAULT|HIGHEST.
            precision=precision,
            preferred_element_type=jnp.float32)

        @pl.when(first)
        def _init():
            out_ref[f, :, :] = acc

        @pl.when(jnp.logical_not(first))
        def _accum():
            out_ref[f, :, :] += acc


def level_histogram(bins: jnp.ndarray, loc: jnp.ndarray, ws: jnp.ndarray,
                    n_nodes: int, n_bins: int,
                    fast: bool = False) -> jnp.ndarray:
    """Histogram one tree level on TPU.

    bins: int [n, d] bin codes; loc: int32 [n] node-local id in [0, n_nodes)
    or -1 for inactive rows; ws: f32 [n, S] weighted stat channels (S ≤ 8).
    Returns f32 [n_nodes, d, n_bins, S].
    """
    n, d = bins.shape
    S = ws.shape[1]
    if S > _SCH:                 # e.g. >8-class gini: chunk the channels
        parts = [level_histogram(bins, loc, ws[:, s:s + _SCH],
                                 n_nodes, n_bins, fast=fast)
                 for s in range(0, S, _SCH)]
        return jnp.concatenate(parts, axis=-1)
    mb = n_nodes * n_bins
    # adaptive column tile: smallest 128-multiple covering mb, capped at
    # _MB_TILE (the out-block's last dim must be a 128-multiple)
    tile = min(_MB_TILE, -(-mb // 128) * 128)
    mbp = -(-mb // tile) * tile
    np_ = -(-n // _ROWS) * _ROWS
    dp = -(-d // 8) * 8

    # combined (node, bin) one-hot column per (row, feature); <0 ⇒ no match
    idx = jnp.where(loc[:, None] >= 0,
                    loc[:, None] * n_bins + bins.astype(jnp.int32),
                    -1)
    idx_t = jnp.pad(idx, ((0, np_ - n), (0, dp - d)),
                    constant_values=-1).T                 # [dp, np_]
    ws_t = jnp.pad(ws.astype(jnp.float32),
                   ((0, np_ - n), (0, _SCH - S))).T       # [_SCH, np_]

    from functools import partial as _partial
    prec = (jax.lax.Precision.DEFAULT if fast
            else jax.lax.Precision.HIGHEST)
    out = pl.pallas_call(
        _partial(_hist_kernel, precision=prec, tile=tile, d=d),
        grid=(mbp // tile, np_ // _ROWS),
        in_specs=[
            pl.BlockSpec((dp, _ROWS), lambda m, r: (0, r),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((_SCH, _ROWS), lambda m, r: (0, r),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((d, _SCH, tile),
                               lambda m, r: (0, 0, m),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((d, _SCH, mbp), jnp.float32),
        interpret=pallas_interpret(),
    )(idx_t, ws_t)

    # [d, _SCH, mbp] → [n_nodes, d, n_bins, S]
    return (out[:, :S, :mb]
            .reshape(d, S, n_nodes, n_bins)
            .transpose(2, 0, 3, 1))


# --------------------------------------------------------------------------
# Sorted-window variant: the deep-level scaling path.
#
# The flat kernel compares every row against every (node, bin) column —
# n * (M*B) * d work per level, which dominates once M = 2^t is large.
# Sorting rows by node makes each node's rows contiguous, so a chunk of
# C sorted rows only needs a one-hot over the W-node window it lands in:
# n * (W*B) * d work, independent of M. Chunks that straddle an aligned
# window boundary contribute their out-of-window rows to a fixed-size
# spill buffer (≤ one chunk per boundary ⇒ R = ceil(M/W)*C rows exact
# bound), which replays through the flat kernel — small n, full M.
# --------------------------------------------------------------------------

_CHUNK = 256                   # sorted rows per grid step (= _ROWS)


def _windowed_kernel(wseq_ref, idx_ref, ws_ref, out_ref, *, precision, d):
    c = pl.program_id(0)
    base = wseq_ref[c] * _TW
    cols = jax.lax.broadcasted_iota(jnp.int32, (_TW, _CHUNK), 0)
    first = jnp.logical_or(
        c == 0, wseq_ref[c] != wseq_ref[jnp.maximum(c - 1, 0)])
    for f in range(d):
        local = idx_ref[f, :] - base                      # [_CHUNK]
        oh_t = (cols == local[None, :]).astype(jnp.float32)
        acc = jax.lax.dot_general(
            ws_ref[:], oh_t,
            dimension_numbers=(((1,), (1,)), ((), ())),
            precision=precision,
            preferred_element_type=jnp.float32)           # [_SCH, _TW]

        @pl.when(first)
        def _init():
            out_ref[f, :, :] = acc

        @pl.when(jnp.logical_not(first))
        def _accum():
            out_ref[f, :, :] += acc


def _hist_scatter(bins, loc, ws, n_nodes: int, n_bins: int):
    """Plain scatter-add histogram for SMALL row sets (the sorted kernel's
    spill replay): [M, d, B, S] with inactive rows (loc < 0) dropped."""
    n, d = bins.shape
    S = ws.shape[1]
    active = loc >= 0
    l0 = jnp.where(active, loc, 0)
    fidx = (l0[:, None] * d + jnp.arange(d)[None, :]) * n_bins \
        + bins.astype(jnp.int32)
    contrib = jnp.where(active[:, None, None], ws[:, None, :], 0.0)
    contrib = jnp.broadcast_to(contrib, (n, d, S))
    hist = jnp.zeros((n_nodes * d * n_bins, S), jnp.float32)
    hist = hist.at[fidx.ravel()].add(contrib.reshape(n * d, S))
    return hist.reshape(n_nodes, d, n_bins, S)


def level_histogram_sorted(bins: jnp.ndarray, loc: jnp.ndarray,
                           ws: jnp.ndarray, n_nodes: int, n_bins: int,
                           fast: bool = False) -> jnp.ndarray:
    """Sorted-window histogram: same contract as level_histogram, cost
    n * 512 * d instead of n * (M*B) * d at deep levels. Window alignment
    needs n_bins to divide 512; other bin counts fall back to the flat
    kernel (still correct, just M-dependent)."""
    n, d = bins.shape
    S = ws.shape[1]
    if _TW % n_bins:
        return level_histogram(bins, loc, ws, n_nodes, n_bins, fast=fast)
    W = _TW // n_bins                    # nodes per window
    nw = -(-n_nodes // W)

    # ---- shared prep, computed once for all channel slabs ----
    # sort rows by node (inactive rows last)
    key = jnp.where(loc >= 0, loc, n_nodes)
    order = jnp.argsort(key)
    loc_s = loc[order]
    bins_s = bins[order]
    ws_s = ws[order].astype(jnp.float32)

    np_ = -(-n // _CHUNK) * _CHUNK
    dp = -(-d // 8) * 8
    idx = jnp.where(loc_s[:, None] >= 0,
                    loc_s[:, None] * n_bins + bins_s.astype(jnp.int32),
                    -1)
    idx_t = jnp.pad(idx, ((0, np_ - n), (0, dp - d)),
                    constant_values=-1).T                 # [dp, np_]

    n_chunks = np_ // _CHUNK
    first_loc = jnp.pad(loc_s, (0, np_ - n),
                        constant_values=-1)[:: _CHUNK]    # [n_chunks]
    valid = first_loc >= 0
    # forward-fill invalid (all-inactive) chunks with the last valid
    # window: they then accumulate zero into an already-open block instead
    # of re-initializing window 0 (windows are non-decreasing once sorted)
    w_raw = jnp.where(valid, first_loc // W, -1)
    wseq = jnp.clip(jax.lax.cummax(w_raw), 0, nw - 1).astype(jnp.int32)
    # mask windows never opened by a valid chunk (their rows are spill);
    # .at[].max so a later inactive chunk cannot clear a visited flag
    visited = jnp.zeros(nw, bool).at[wseq].max(valid)

    # spill: rows whose node window differs from their chunk home window
    chunk_of = jnp.arange(np_) // _CHUNK
    loc_pad = jnp.pad(loc_s, (0, np_ - n), constant_values=-1)
    w_row = jnp.clip(jnp.where(loc_pad >= 0, loc_pad, 0) // W, 0, nw - 1)
    spill = (loc_pad >= 0) & (w_row != wseq[chunk_of])
    R = min(np_, nw * _CHUNK)            # <= one straddling chunk per window
    sp_ix = jnp.nonzero(spill, size=R, fill_value=np_ - 1)[0]
    sp_valid = spill[sp_ix]
    sp_bins = jnp.pad(bins_s, ((0, np_ - n), (0, 0)))[sp_ix]
    sp_loc = jnp.where(sp_valid, loc_pad[sp_ix], -1)
    sp_ws = jnp.pad(ws_s, ((0, np_ - n), (0, 0)))[sp_ix]  # [R, S]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_chunks,),
        in_specs=[
            pl.BlockSpec((dp, _CHUNK), lambda c, wseq: (0, c),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((_SCH, _CHUNK), lambda c, wseq: (0, c),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((d, _SCH, _TW),
                               lambda c, wseq: (0, 0, wseq[c]),
                               memory_space=pltpu.VMEM),
    )

    # ---- one kernel pass per <=8-channel slab over the shared prep ----
    parts = []
    for s0 in range(0, S, _SCH):
        slab = ws_s[:, s0:s0 + _SCH]
        Sk = slab.shape[1]
        ws_t = jnp.pad(slab, ((0, np_ - n), (0, _SCH - Sk))).T
        from functools import partial as _partial
        prec = (jax.lax.Precision.DEFAULT if fast
                else jax.lax.Precision.HIGHEST)
        out = pl.pallas_call(
            _partial(_windowed_kernel, precision=prec, d=d),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((d, _SCH, nw * _TW),
                                           jnp.float32),
            interpret=pallas_interpret(),
        )(wseq, idx_t, ws_t)
        out = jnp.where(jnp.repeat(visited, _TW)[None, None, :],
                        out, 0.0)
        main = (out[:, :Sk]
                .reshape(d, Sk, nw * W, n_bins)[:, :, :n_nodes]
                .transpose(2, 0, 3, 1))                   # [M, d, B, Sk]
        # spill rows (boundary-straddling chunks) replay through a plain
        # scatter-add: at R <= nw*_CHUNK rows the index-op cost (~26 ns x
        # R*d) beats re-running the flat compare kernel at full M*B width
        parts.append(main + _hist_scatter(sp_bins, sp_loc,
                                          sp_ws[:, s0:s0 + _SCH],
                                          n_nodes, n_bins))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=-1)


# --------------------------------------------------------------------------
# Dense-channel kernel (round 3): node x stat channels on the matmul's LANE
# axis, (feature, bin) one-hots on the sublane axis — no sorting, no spill,
# no per-row ops at all.
#
#     out[(f, b), (n_, s)] = sum_r (bins[r,f] == b) * (loc[r] == n_) * ws[r,s]
#
# Per row-chunk the kernel builds W2T[(n_, s), r] = (node_of_col == loc_r)
# * ws_{s, r} (everything lane-oriented, VPU) and contracts it with each
# feature's bin one-hot on the MXU: [B, CHUNK] x [CS, CHUNK]^T -> [B, CS]
# accumulated into a VMEM-resident [d*B, CS] output. Cost is
# n * (d*B) * max(128, M*S) MACs with BOTH matmul axes full — the round-2
# kernels idled 94% of the MXU on an 8-wide stat axis AND paid per-tree
# argsort + gather + spill-replay per level (~3-4 per-row ops x 26 ns x n,
# the real bound at 1M rows). Node counts beyond 512/S channel lanes are
# processed in channel GROUPS (an extra grid dimension); total MACs stay
# n * d*B * M*S.
# --------------------------------------------------------------------------

_DCHUNK = 512      # rows per grid step (lane axis): sized so the fused
                   # [d*B, CHUNK] one-hot + the [d*B, cs] out tile coexist
                   # in VMEM (round 4 — the round-3 kernel used 1024 with
                   # per-feature [B, CHUNK] one-hots)
_DCS = 512         # channel lanes per group (VMEM: d*B x 512 f32 <= ~4MB)


def _dense_kernel(bins_ref, loc_ref, ws_ref, out_ref, *, precision,
                  d, n_bins, S, cs, chunk):
    """Round-4 fused variant: ONE [d*n_bins, CHUNK] x [CHUNK, cs] matmul
    per chunk-step instead of d separate [n_bins, CHUNK] matmuls — the
    M-axis fills the MXU (d*64 = 2048 wide vs 64) and the VMEM out tile
    accumulates once per step instead of d slice-RMWs (probe_trees.py:
    1.5x on the hist share, bit-identical results)."""
    g = pl.program_id(0)              # channel (node) group
    first = pl.program_id(1) == 0
    loc = loc_ref[0, :]                                   # [CHUNK] lanes
    col = jax.lax.broadcasted_iota(jnp.int32, (cs, chunk), 0)
    node_col = col // S + g * (cs // S)
    s_col = col % S
    w2t = jnp.zeros((cs, chunk), jnp.float32)
    for s in range(S):
        w2t = jnp.where(s_col == s, ws_ref[s, :][None, :], w2t)
    w2t = jnp.where(node_col == loc[None, :], w2t, 0.0)   # [cs, CHUNK]
    # fused one-hot over ALL features: [(f, b), CHUNK]
    fb = jax.lax.broadcasted_iota(jnp.int32, (d * n_bins, chunk), 0)
    frow = fb // n_bins
    brow = fb % n_bins
    bv = jnp.zeros((d * n_bins, chunk), jnp.int32)
    for f in range(d):
        bv = jnp.where(frow == f, bins_ref[f, :][None, :], bv)
    oh = (brow == bv).astype(jnp.bfloat16)           # 0/1 exact in bf16
    acc = jax.lax.dot_general(
        oh, w2t.astype(jnp.bfloat16),
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=precision,
        preferred_element_type=jnp.float32)               # [d*B, cs]

    @pl.when(first)
    def _init():
        out_ref[0] = acc

    @pl.when(jnp.logical_not(first))
    def _accum():
        out_ref[0] += acc


def _dense_kernel_f32(bins_ref, loc_ref, ws_ref, out_ref, *, precision,
                      d, n_bins, S, cs, chunk):
    """Per-feature f32 variant for HIGHEST-precision channels (gradient
    sums): the fused bf16 one-hot is off the table, and at f32 the big
    fused operand loses to d smaller matmuls (measured: GBT regressed 15%
    under the fused kernel at chunk 256; this body is the round-3 kernel)."""
    g = pl.program_id(0)
    first = pl.program_id(1) == 0
    loc = loc_ref[0, :]
    col = jax.lax.broadcasted_iota(jnp.int32, (cs, chunk), 0)
    node_col = col // S + g * (cs // S)
    s_col = col % S
    w2t = jnp.zeros((cs, chunk), jnp.float32)
    for s in range(S):
        w2t = jnp.where(s_col == s, ws_ref[s, :][None, :], w2t)
    w2t = jnp.where(node_col == loc[None, :], w2t, 0.0)
    rows = jax.lax.broadcasted_iota(jnp.int32, (n_bins, chunk), 0)
    for f in range(d):
        oh = (rows == bins_ref[f, :][None, :]).astype(jnp.float32)
        acc = jax.lax.dot_general(
            oh, w2t, dimension_numbers=(((1,), (1,)), ((), ())),
            precision=precision,
            preferred_element_type=jnp.float32)

        @pl.when(first)
        def _init():
            out_ref[0, f * n_bins:(f + 1) * n_bins, :] = acc

        @pl.when(jnp.logical_not(first))
        def _accum():
            out_ref[0, f * n_bins:(f + 1) * n_bins, :] += acc


def level_histogram_dense(bins_t: jnp.ndarray, loc: jnp.ndarray,
                          ws: jnp.ndarray, n_nodes: int, n_bins: int,
                          fast: bool = False) -> jnp.ndarray:
    """Dense-channel level histogram.

    bins_t: uint8/int32 [dp, np_] PRE-transposed (+row-padded) bin codes —
    build it once per tree build, it never changes across levels/trees.
    loc: int32 [n] node-local ids (-1 = inactive); ws: f32 [n, S].
    Returns f32 [n_nodes, d_pad_rows_of_bins_t? -> caller slices] — same
    contract as level_histogram: [n_nodes, d, n_bins, S] with d inferred
    from bins_t's first dim (callers pass dp == padded d and slice).
    """
    dp, np_ = bins_t.shape
    n = loc.shape[0]
    S = ws.shape[1]
    import math as _math
    cs_need = n_nodes * S
    cs0 = (S * 128) // _math.gcd(S, 128)   # lanes per valid channel unit
    cs = min(max(_DCS // cs0, 1) * cs0,
             -(-cs_need // cs0) * cs0)
    n_groups = -(-cs_need // cs)
    nodes_per_group = cs // S

    locp = jnp.pad(jnp.where(loc >= 0, loc, -1), (0, np_ - n),
                   constant_values=-1).reshape(1, np_)
    wsp = jnp.pad(ws.astype(jnp.float32),
                  ((0, np_ - n), (0, 0))).T               # [S, np_]

    from functools import partial as _partial
    prec = (jax.lax.Precision.DEFAULT if fast
            else jax.lax.Precision.HIGHEST)
    # fast (bf16-exact integer channels): the fused all-features kernel;
    # HIGHEST (gradient channels): the per-feature f32 kernel at the
    # round-3 chunk — measured faster there (see _dense_kernel_f32)
    chunk = _DCHUNK if fast else 1024
    assert np_ % chunk == 0, (
        f"bins_t rows ({np_}) must pad to a multiple of {chunk} "
        f"(fast={fast}); ops.trees pads to 1024 which divides both")
    kern = _dense_kernel if fast else _dense_kernel_f32
    out = pl.pallas_call(
        _partial(kern, precision=prec, d=dp, n_bins=n_bins,
                 S=S, cs=cs, chunk=chunk),
        grid=(n_groups, np_ // chunk),
        in_specs=[
            pl.BlockSpec((dp, chunk), lambda g, r: (0, r),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, chunk), lambda g, r: (0, r),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((S, chunk), lambda g, r: (0, r),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, dp * n_bins, cs),
                               lambda g, r: (g, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n_groups, dp * n_bins, cs),
                                       jnp.float32),
        interpret=pallas_interpret(),
    )(bins_t.astype(jnp.int32), locp, wsp)

    # [n_groups, dp*B, cs] -> [n_nodes, dp, B, S]
    out = out.reshape(n_groups, dp, n_bins, nodes_per_group, S)
    out = out.transpose(0, 3, 1, 2, 4).reshape(
        n_groups * nodes_per_group, dp, n_bins, S)
    return out[:n_nodes]
