"""Factorization-machine kernels: FM and field-aware FM (FFM) on TPU.

Reference: hivemall.fm (SURVEY.md §3.6, §4.4) — FactorizationMachineUDTF's
per-row O(n*k) FM update and FieldAwareFactorizationMachineUDTF's O(n^2*k)
pair loop over (feature, field) latent vectors in a packed-long hash table.

TPU shape: the per-row loops become batched gathers + einsums —
  FM:  gather V[idx] -> [B,L,K]; phi uses the (sum^2 - sum-of-squares)/2
       identity, all MXU/VPU friendly.
  FFM: the pair tensor A[b,i,j,:] = V[idx[b,i], field[b,j], :] is one flat
       gather into V viewed [N*F, K]; interactions = einsum('bijk,bjik->bij')
       masked to i<j. Padding (idx=0, val=0) self-cancels through val.
Gradients via jax.grad: XLA turns the gathers' adjoints into scatter-adds on
the dense tables — the batched analog of the reference's per-entry AdaGrad
cell updates.

The fused/minibatch step bodies wrap their phases in ``jax.named_scope``
with one family-neutral vocabulary — ``hm.gather`` (the table-row gather, with
the compact table's fill and the slots' ranks where it reads through the
batch's distinct rows),
``hm.grad`` (unpack, forward, loss, backward), ``hm.scatter`` (zeros +
scatter-add into G, dense or compact with the ranking it needs, or the
sparse variants' per-occurrence chain) and ``hm.update`` (the optimizer's
update of table and state, whole or by distinct rows, w0 included) —
so a profiler trace reduces device time by phase, not by ``fusion.48``
(ops/scan.py ``SCOPES`` on what the compile cache does to a renamed scope).
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .losses import Loss
from .optimizers import Optimizer
from .rows_pallas import (BLOCK_ROWS, LIST_MULTIPLE, scatter_rows,
                          scatter_rows_cap, update_rows)
from .scan import scannable

__all__ = ["fm_score", "ffm_score", "make_fm_step", "make_ffm_step",
           "ffm_joint_slot", "ffm_row_hash", "make_ffm_step_fused",
           "make_ffm_score_fused", "make_fm_step_fused",
           "make_fm_score_fused", "fm_pack_geometry"]

# odd 32-bit mixing constants (golden-ratio / murmur finalizer family)
_J1, _J2, _J3 = 0x9E3779B1, 0x85EBCA6B, 0xC2B2AE35


def ffm_joint_slot(idx, field, M: int):
    """Joint (feature, field) hash into one flat [M, K] latent table.

    The TPU analog of the reference's packed-long (feature,field) keys in
    FFMStringFeatureMapModel (SURVEY.md §3.6): instead of a dense [N, F, K]
    cube (8.6 GB at -dims 2^24 -fields 64 bf16, which cannot fit one chip's
    HBM with f32 optimizer state), both key halves mix into a single slot id
    in [0, M). Collisions share a latent vector — the same hashing-trick
    semantics feature_hashing already applies to the linear weights.

    M must be a power of two (the & (M-1) fold). Slot 0 doubles as the
    padding row; a real pair landing there shares it, which is benign: the
    padding contributions carry zero gradient. Field ids are taken as-is
    (callers normalize mod F — the hash itself is field-space agnostic).
    """
    h = (idx.astype(jnp.uint32) * jnp.uint32(_J1)
         + field.astype(jnp.uint32) * jnp.uint32(_J2))
    h = h ^ (h >> 15)
    h = h * jnp.uint32(_J3)
    h = h ^ (h >> 13)
    return (h & jnp.uint32(M - 1)).astype(jnp.int32)


def _fm_slab_phi(w0, wg, Vg, val):
    """FM score from gathered slabs wg [B,L], Vg [B,L,K]:
    phi = w0 + sum_i w_i x_i + 1/2 sum_f [(sum_i v_if x_i)^2 - sum v^2 x^2]."""
    wi = (wg * val).sum(-1)
    xv = Vg * val[..., None]
    s = xv.sum(1)
    s2 = (xv ** 2).sum(1)
    return w0 + wi + 0.5 * (s * s - s2).sum(-1)


def _ffm_slab_phi(w0, wg, Ag, val):
    """FFM score from gathered slabs wg [B,L], Ag [B,L,L,K] where
    Ag[b,i,j] = V[idx[b,i], field[b,j]]:
    phi = w0 + sum_i w_i x_i + sum_{i<j} (A[i,j] . A[j,i]) x_i x_j."""
    L = val.shape[1]
    wi = (wg * val).sum(-1)
    inter = jnp.einsum("bijk,bjik->bij", Ag, Ag)
    xx = val[:, :, None] * val[:, None, :]
    iu = jnp.triu(jnp.ones((L, L), jnp.float32), k=1)
    return w0 + wi + (inter * xx * iu[None]).sum((1, 2))


def fm_score(w0, w, V, idx, val):
    """Table-level FM score: gather slabs, delegate to _fm_slab_phi.

    Reference formula: FMPredictGenericUDAF (SURVEY.md §3.6 row 2)."""
    return _fm_slab_phi(w0.astype(jnp.float32),
                        w[idx].astype(jnp.float32),
                        V[idx].astype(jnp.float32), val)


def ffm_score(w0, w, V, idx, val, field):
    """Table-level FFM score: pair-flat gather, delegate to _ffm_slab_phi.

    Two layouts, told apart by V.ndim (reference: FFMPredictUDF pairwise
    field-crossed dots, SURVEY.md §3.6 row 4):
      V [N, F, K]  — dense field cube, flat index = idx*F + field
      V [M, K]     — joint-hashed table, flat index = ffm_joint_slot
    """
    if V.ndim == 2:
        M, K = V.shape
        V2 = V
        flat = ffm_joint_slot(idx[:, :, None], field[:, None, :], M)
    else:
        N, F, K = V.shape
        V2 = V.reshape(N * F, K)
        field = field % F            # parse-path mod-F normalization
        flat = idx[:, :, None] * F + field[:, None, :]   # [B, L(i), L(j)]
    return _ffm_slab_phi(w0.astype(jnp.float32),
                         w[idx].astype(jnp.float32),
                         V2[flat].astype(jnp.float32), val)


def _make_factor_step_dense(score_fn: Callable, loss: Loss,
                            optimizer: Optimizer,
                            lambdas: Tuple[float, float, float]) -> Callable:
    """Shared FM/FFM jitted step: value_and_grad + per-table optimizer.
    The classification-vs-regression split is carried by ``loss`` (logloss on
    +-1 labels vs squaredloss on targets), as in the reference's
    -classification flag. O(table) work per step — used for optimizers whose
    state decays every step (adam/momentum/adadelta) and so has no exact
    sparse form."""
    lam0, lam_w, lam_v = lambdas

    def core(params, opt_state, t, idx, val, label, row_mask, *extra):
        def batch_loss(p):
            phi = score_fn(p["w0"], p["w"], p["V"], idx, val, *extra)
            return (loss.loss(phi, label) * row_mask).sum()

        loss_sum, grads = jax.value_and_grad(batch_loss)(params)
        # L2 (reference: -lambda* FM hyperparams), folded into the gradient
        grads = {"w0": grads["w0"] + lam0 * params["w0"],
                 "w": grads["w"] + lam_w * params["w"],
                 "V": grads["V"] + lam_v * params["V"]}
        new_p = {}
        new_s = {}
        for k in ("w0", "w", "V"):
            p32 = params[k].astype(jnp.float32)
            nk, sk = optimizer.update(p32, grads[k].astype(jnp.float32),
                                      opt_state[k], t)
            new_p[k] = nk.astype(params[k].dtype)
            new_s[k] = sk
        return new_p, new_s, loss_sum

    return scannable(partial(jax.jit, donate_argnums=(0, 1))(core), core)


def _make_factor_step_sparse(kind: str, loss: Loss, optimizer: Optimizer,
                             lambdas: Tuple[float, float, float]) -> Callable:
    """Gather/scatter FM/FFM step: O(batch), not O(table), HBM traffic.

    The reference's per-row updates only ever touch features present in the
    row (SURVEY.md §4.1/§4.4 hot loops); this is the batched TPU equivalent —
    gather the touched slabs, autodiff at slab level, scatter the optimizer
    step back through Optimizer.sparse_update. L2 (-lambda*) is likewise
    applied per-occurrence to touched entries only, masked by row validity,
    matching the reference's regularize-on-update semantics rather than a
    whole-table decay. Requires optimizer.sparse_update (SGD/AdaGrad/FTRL/
    RDA — the families BASELINE.json names)."""
    lam0, lam_w, lam_v = lambdas
    assert optimizer.sparse_update is not None

    def core(params, opt_state, t, idx, val, label, row_mask, *extra):
        w0, w, V = params["w0"], params["w"], params["V"]
        wg = w[idx].astype(jnp.float32)                       # [B, L]
        # presence mask: a feature slot participates only if its value is
        # nonzero AND the row is valid — padding slots and padded rows must
        # not receive L2 decay (the reference regularizes on update, and it
        # only updates features present in the row)
        pm = (val != 0).astype(jnp.float32) * row_mask[:, None]   # [B, L]
        if kind == "ffm":
            # dense [N, F, K] field cube (-ffm_table dense); the joint
            # layout trains through make_ffm_step_fused instead
            (field,) = extra
            L = idx.shape[1]
            N, F, K = V.shape
            V2 = V.reshape(N * F, K)
            field = field % F        # parse-path mod-F normalization
            raw = idx[:, :, None] * F + field[:, None, :]
            # redirect inactive pairs to the reserved padding row 0: diagonal
            # self-pairs (triu-masked out of the score) AND pairs touching a
            # padding slot or padded row. Their loss gradient is zero, but
            # FTRL/RDA sparse updates re-materialize w at every scattered id
            # — routing them to row 0 keeps never-trained real cells at
            # their lazy init.
            eye = jnp.eye(L, dtype=bool)[None]
            pb = pm > 0                                       # [B, L] bool
            active = pb[:, :, None] & pb[:, None, :] & ~eye   # [B, L, L]
            flat = jnp.where(active, raw, 0)
            Ag = V2[flat].astype(jnp.float32)                 # [B, L, L, K]
            phi_fn = _ffm_slab_phi
            slab = Ag
        else:
            Vg = V[idx].astype(jnp.float32)                   # [B, L, K]
            phi_fn = _fm_slab_phi
            slab = Vg

        def batch_loss(w0f, wgf, slabf):
            phi = phi_fn(w0f, wgf, slabf, val)
            return (loss.loss(phi, label) * row_mask).sum()

        loss_sum, (g0, gw, gs) = jax.value_and_grad(
            batch_loss, argnums=(0, 1, 2))(
                w0.astype(jnp.float32), wg, slab)

        # per-occurrence L2 on present entries (reference: -lambda* applied
        # at update time to the row's features)
        g0 = g0 + lam0 * w0.astype(jnp.float32)
        gw = gw + lam_w * wg * pm
        w0n, s0 = optimizer.update(w0.astype(jnp.float32), g0,
                                   opt_state["w0"], t)
        wn, sw = optimizer.sparse_update(
            w, gw.reshape(-1), opt_state["w"], idx.ravel(), t)

        if kind == "ffm":
            # pair presence: both sides present, and not a self-pair
            gs = gs + lam_v * slab * active[..., None]
            # optimizer state is co-shaped with V [N,F,K]; flatten to
            # the [N*F, K] view the pair-flat indices address
            sV2 = {k: v.reshape(N * F, K)
                   for k, v in opt_state["V"].items()}
            Vn2, sV2 = optimizer.sparse_update(
                V2, gs.reshape(-1, K), sV2, flat.ravel(), t)
            Vn = Vn2.reshape(N, F, K)
            sV = {k: v.reshape(N, F, K) for k, v in sV2.items()}
        else:
            K = V.shape[-1]
            gs = gs + lam_v * slab * pm[..., None]
            Vn, sV = optimizer.sparse_update(
                V, gs.reshape(-1, K), opt_state["V"], idx.ravel(), t)

        return ({"w0": w0n.astype(w0.dtype), "w": wn, "V": Vn},
                {"w0": s0, "w": sw, "V": sV}, loss_sum)

    return scannable(partial(jax.jit, donate_argnums=(0, 1))(core), core)


def ffm_row_hash(idx, Mr: int):
    """Feature-id -> table row for the fused joint layout: murmur-style
    mix folded to [0, Mr). Row 0 doubles as the padding row (idx 0 maps
    there); real features colliding with it are benign (padding carries
    zero gradient)."""
    h = idx.astype(jnp.uint32) * jnp.uint32(_J1)
    h = h ^ (h >> 15)
    h = h * jnp.uint32(_J3)
    h = h ^ (h >> 13)
    return (h & jnp.uint32(Mr - 1)).astype(jnp.int32)


def _fused_phi(w0f, slab, val, field, F: int, K: int):
    """FFM score from one fused gathered slab [B, L, F*K + pad]:
    columns [:F*K] are the per-field latent vectors of each feature,
    column F*K is its linear weight. The (i, j) pair interaction
    A[b,i,j] . A[b,j,i] selects field columns by ONE-HOT MATMUL (MXU),
    not a per-pair gather. General path: arbitrary per-slot field ids.
    (Scatter-built field grouping was measured 5.7x SLOWER on v5e —
    TPU scatter serializes; the canonical-layout fast path below gets
    the grouping for free instead.)

    Pair mixing runs in the slab's own dtype (bf16 under -halffloat:
    MXU-native, halves the [B,L,L,K] intermediate traffic — measured
    +17%); the interaction accumulates in f32, and the linear/phi part
    is always f32."""
    B, L = val.shape
    FK = F * K
    Vg = slab[..., :FK].reshape(B, L, F, K)
    wg = slab[..., FK].astype(jnp.float32)
    # fold out-of-range field ids mod F (parse-path normalization — a zero
    # one-hot row would silently drop the feature's interactions while the
    # canonical fieldmajor path keeps them)
    oh = jax.nn.one_hot(field % F, F, dtype=Vg.dtype)
    A = jnp.einsum("bifk,bjf->bijk", Vg, oh)       # A[b,i,j] = V_i[f_j]
    inter = jnp.einsum("bijk,bjik->bij", A, A,
                       preferred_element_type=jnp.float32)
    xx = val[:, :, None] * val[:, None, :]
    iu = jnp.triu(jnp.ones((L, L), jnp.float32), k=1)
    return w0f + (wg * val).sum(-1) + (inter * xx * iu[None]).sum((1, 2))


def _fused_phi_fieldmajor(w0f, slab, val, F: int, K: int):
    """FFM score over a FIELD-MAJOR canonical batch — O(B*L*F*K), no L^2.

    Slot s of the row holds a feature of field s % F (block s // F is the
    occurrence rank; io.sparse.canonicalize_fieldmajor builds this layout
    host-side — FFM is order-invariant, so reordering a row's features is
    free). With U[b,s] = x_s * V_s (the [F, K] latent block scaled by the
    value) grouped by own field g = s % F:

        C[b,g,f,k] = sum_blocks U[b, block*F + g, f, k]
        sum_{i != j} <U_i[f_j], U_j[f_i]> = sum_{g,f,k} C[g,f,k] C[f,g,k]

    (grouping i by f_i = g and j by f_j = f factorizes the double sum;
    the i < j triangle is (full - diag)/2 by symmetry). Because the
    field pattern is STATIC, C is a reshape+sum — no gather, no scatter,
    no matmul anywhere in the interaction: pure VPU elementwise work,
    which replaces the pair path's [B,L,L,K] slab and its padded-small
    one-hot batched matmuls (under 10% MXU utilization at F=40, K=4).
    Criteo-shaped rows (one feature per field, in field order) ARE this
    layout with m = 1. Sums accumulate in f32."""
    B, L = val.shape
    m = L // F
    FK = F * K
    Vg = slab[..., :FK].reshape(B, m, F, F, K)       # [B, m, g, f, k]
    wg = slab[..., FK].astype(jnp.float32)           # [B, L]
    U = Vg * val.reshape(B, m, F, 1, 1).astype(Vg.dtype)
    C = U.astype(jnp.float32).sum(1)                 # [B, g, f, k]
    full = jnp.einsum("bgfk,bfgk->b", C, C)
    own = jnp.einsum("bmffk->bmfk", U).astype(jnp.float32)   # U_s[f_s]
    diag = (own * own).sum((1, 2, 3))
    return w0f + (wg * val).sum(-1) + 0.5 * (full - diag)


def make_ffm_score_fused(F: int, K: int):
    """Jitted scorer over the fused joint table T [Mr, F*K + pad]."""
    @jax.jit
    def score(w0, T, idx, val, field):
        rows = ffm_row_hash(idx, T.shape[0])
        return _fused_phi(w0.astype(jnp.float32), T[rows], val, field, F, K)
    return score


def make_ffm_score_fieldmajor(F: int, K: int):
    """Jitted scorer over canonical field-major batches (slot s ↔ field
    s % F) — same no-L^2 kernel the fieldmajor train step uses. val=None
    is unit-value elision (rebuilt from idx on device)."""
    @jax.jit
    def score(w0, T, idx, val):
        if val is None:
            val = (idx != 0).astype(jnp.float32)
        rows = ffm_row_hash(idx, T.shape[0])
        return _fused_phi_fieldmajor(w0.astype(jnp.float32), T[rows],
                                     val, F, K)
    return score


def _ffm_ranked(T, state, idx, optimizer: Optimizer, distinct_tail: bool,
                tp: int = 1, block: bool = False):
    """The joint step's table rows of the batch ``idx`` and their ranking
    (`rank_rows`). Of a ``block`` of a table dealt over 'tp' (T is this
    chip's), the hash folds to the whole table's rows and a slot of
    another block carries the id R."""
    R = T.shape[0]
    with jax.named_scope("hm.gather"):
        rows = ffm_row_hash(idx, R * tp)
        if block:                       # another block's slot: the id R
            rows = rows - jax.lax.axis_index("tp") * R
            rows = jnp.where((rows >= 0) & (rows < R), rows, R)
    return rows, rank_rows(rows.reshape(-1), T, state, optimizer,
                           None if distinct_tail else 0, block)


def _lead(tree):                        # a chip's own, stacked over 'tp'
    return jax.tree_util.tree_map(lambda a: a[None], tree)


def _own(tree):
    return jax.tree_util.tree_map(lambda a: a[0], tree)


def ffm_slab_over_blocks(mesh, T, state, idx, optimizer: Optimizer,
                         distinct_tail: bool = True):
    """The front of `make_ffm_step_fused(mesh=)`: the slab ``T[rows]`` of
    the batch ``idx`` over the WHOLE table T [Mr, W], whose rows and
    ``state`` are dealt over the mesh's 'tp' (P('tp', None)), bit for bit
    and replicated; each chip's (rows, ranks, compact) in its block's
    numbering, stacked over 'tp'; and whether the slab crossed the mesh as
    the blocks' compact tables (an int32 scalar, the step's
    ``exchange_compact_steps``).

    Every chip ranks the slots by its own block's rows (`rank_rows`). No
    collective is written here: what leaves a `shard_map` stacked over
    'tp' and is summed or laid out whole between them is the
    partitioner's to move, and named as it names its own.

      * Where every block holds at most ``gather_cap(cap, W, itemsize,
        tp)`` distinct rows (the flagship's block over tp=4: 49,152, so
        that the tables of all chips fit COMPACT_TABLE_BYTES together),
        each chip fills its compact table C [cap, W] of its distinct rows
        (`_compact_table`) and gives each of its slots the row of C at
        ``chip * cap + slot_rank`` (0 at another block's slot). The
        tables laid end to end reach every chip (an all-gather of [tp *
        cap, W]), the slots' rows summed (an int32 all-reduce of one
        scalar a slot; one owner a slot, so exact), and every chip
        expands the slab out of them as `gather_rows` does out of its
        own.
      * Else (one block over, a `lax.cond` on the blocks' largest count),
        each chip's slab is its `gather_rows` of its block, zero at the
        others' slots, and the slabs are summed (an all-reduce of the
        whole [B, L, W] slab, exact in T's dtype), as a step whose blocks
        rank nothing (`rank_rows` None) always does."""
    from jax.sharding import PartitionSpec as P
    tp, chips = mesh.shape["tp"], P("tp")

    def shares(T, state, idx):
        """A chip's (rows, ranks, compact) and its part of the slab: C
        (filled only where its count is within cap) and its slots' rows of
        the tables laid end to end; or, where nothing ranks the rows or
        no row fits, its slab, zero at the others' slots."""
        rows, ranks = _ffm_ranked(T, state, idx, optimizer, distinct_tail,
                                  tp, True)
        R, W = T.shape
        cap = 0 if ranks is None else gather_cap(
            ranks.urows.shape[0], W, T.dtype.itemsize, tp)
        if not cap:  # graftcheck: disable=GC09 (static)
            slab, compact = gather_rows(T, rows, ranks, True)
            return _lead(((rows, ranks, compact), slab))
        n = ranks.n_distinct
        with jax.named_scope("hm.gather"):
            tb = min(cap, FILL_BLOCK_ROWS)
            C = _compact_table(T, ranks, cap,
                               jnp.where(n <= cap, (n + tb - 1) // tb, 0))
            at = jnp.where(rows.reshape(-1) < R, jax.lax.axis_index("tp")
                           * cap + ranks.slot_rank, 0)
        # what gather_rows reports for the same ranking
        compact = (n <= gather_cap(ranks.urows.shape[0], W,
                                   T.dtype.itemsize)).astype(jnp.int32)
        # C leaves as a block of the tables laid end to end, [tp * cap,
        # W], not stacked: the compiler gathers a stacked [tp, cap, W]
        # transposed and relayouts it twice around the all-gather
        return _lead((rows, ranks, compact)), (C, *_lead((at, n)))

    def summed(slabs):
        with jax.named_scope("hm.gather"):
            # one owner a slot, so exact in the table's own dtype
            return jax.lax.reduce(slabs, jnp.zeros((), slabs.dtype),
                                  jax.lax.add, (0,))

    if T.shape[0] % tp:
        raise ValueError(f"T's {T.shape[0]} rows do not deal into {tp} "
                         "equal blocks over 'tp'")
    # no replication check: a branch of a cond may leave a constant where
    # the other leaves a chip's own
    mine, part = jax.shard_map(
        shares, mesh=mesh, in_specs=(P("tp", None), P("tp", None), P()),
        out_specs=chips, check_vma=False)(T, state, idx)
    if not isinstance(part, tuple):
        return mine, summed(part), jnp.zeros((), jnp.int32)
    (rows, ranks, _), (C, at, n) = mine, part
    cap = C.shape[0] // tp

    def of_tables():
        with jax.named_scope("hm.gather"):
            slot = jax.lax.reduce(at, jnp.zeros((), at.dtype), jax.lax.add,
                                  (0,))

        def expand(C, slot):
            with jax.named_scope("hm.gather"):
                return C[slot.reshape(rows.shape[1:])]
        return jax.shard_map(expand, mesh=mesh, in_specs=(P(), P()),
                             out_specs=P(), check_vma=False)(C, slot)

    def of_slabs():
        return summed(jax.shard_map(
            lambda T, rows, ranks: _lead(gather_rows(
                T, rows[0], _own(ranks), True)[0]),
            mesh=mesh, in_specs=(P("tp", None), chips, chips),
            out_specs=chips, check_vma=False)(T, rows, ranks))
    fits = n.max() <= cap
    return mine, jax.lax.cond(fits, of_tables, of_slabs), \
        fits.astype(jnp.int32)


def make_ffm_step_fused(loss: Loss, optimizer: Optimizer,
                        lambdas: Tuple[float, float, float],
                        F: int, K: int,
                        fieldmajor: bool = False,
                        unit_val: bool = False,
                        distinct_tail: bool = True,
                        mesh=None) -> Callable:
    """The flagship train_ffm step — fused feature-row joint layout.

    Design (measured on v5e, B=32k L=40: 9.85 s/step -> 103 ms/step):
    TPU scatter/gather cost is per-ROW, nearly independent of row width,
    so the O(B*L^2) per-pair slab updates of a flat (feature,field) table
    are replaced by TWO row operations per step on a fused table
    T [Mr, F*K + 8] holding every field's latent vector AND the linear
    weight of one hashed feature per row:

      1. one gather  T[rows]            -> [B, L, 672B] slabs (read
         through the batch's distinct rows where the tail ranks them:
         gather_rows)
      2. pair mixing (one-hot einsum; or, with fieldmajor=True over
         canonical batches, the static field-grouped form — pure VPU,
         no L^2 intermediate: _fused_phi_fieldmajor)
      3. one scatter-add of the slab gradient, duplicates summed by
         table row
      4. the optimizer's update of T and its state

    Steps 3 and 4 are rows_update, the tail the packed FM minibatch step
    has: where the optimizer leaves a zero-gradient row as it was (AdaGrad
    and SGD: `zero_grad_noop`) and the table is large against the batch,
    the sum goes into a compact gradient and the update to the batch's
    distinct rows; else into a dense G with an update over [Mr, W] (any
    -opt works there). ``distinct_tail=False`` keeps the dense tail
    whatever the shapes: the trainer's choice for a mesh this step is
    not handed, not a user's.

    ``mesh`` (a (dp, tp) mesh with dp == 1; None: one chip, or GSPMD's
    cut of this same program) runs the step under `jax.shard_map` over
    'tp', T and its state in their row sharding P('tp', None), w0 and
    the batch replicated: every chip makes the calls the one chip makes,
    rank_rows -> gather_rows -> fwd/bwd -> rows_update, on its own
    [Mr/tp, W] block. The hash folds to the GLOBAL Mr and a chip's slots
    are those whose row falls in its block. No collective is written in
    the step: the front, `ffm_slab_over_blocks`, ranks each block's rows
    and hands the whole slab T[rows] to every chip, bit for bit, by what
    the partitioner moves between `shard_map`s: where every block holds
    few enough distinct rows, the blocks' compact tables (an all-gather)
    and each slot's row in them (an int32 all-reduce); else the blocks'
    slabs summed (an all-reduce of the slab, the cut of the dense step
    GSPMD makes). In the last `shard_map`, forward and backward run
    replicated on the whole slab and each chip applies the gradient of
    its own slots, taking its own branch of each cond by its own count
    of distinct rows (an optimizer rank_rows does not rank for: the
    dense tail on each block). The stats come out replicated:
    distinct_rows the sum over the chips, tail_distinct_steps and
    gather_compact_steps 1 only where EVERY chip took that branch (else
    the step counts as dense), and, beside TAIL_STATS,
    exchange_compact_steps 1 where the slab crossed as compact tables.

    The fieldmajor step takes no field array (the layout IS the field
    assignment: slot s -> field s % F).

    Returns (params, opt_state, loss_sum, stats): stats counts which tail
    ran, the batch's distinct rows and whether the gather read through
    them (TAIL_STATS).

    Semantics delta vs the reference's per-entry updates (documented):
    AdaGrad-family accumulators see the SQUARE OF THE SUMMED minibatch
    gradient (standard minibatch AdaGrad) rather than per-occurrence
    squares; L2 (-lambda*) is still applied per-occurrence at slab level.
    """
    lam0, lam_w, lam_v = lambdas

    def front(params, opt_state, idx):
        rows, ranks = _ffm_ranked(params["T"], opt_state["T"], idx,
                                  optimizer, distinct_tail)
        slab, compact = gather_rows(params["T"], rows, ranks)
        return rows, ranks, compact, slab    # the slab in T's own dtype

    def back(params, opt_state, t, rows, ranks, compact, slab, val, label,
             row_mask, field):
        T, w0 = params["T"], params["w0"]
        FK = F * K
        W = T.shape[1]

        def batch_loss(w0f, slabf):
            if fieldmajor:
                phi = _fused_phi_fieldmajor(w0f, slabf, val, F, K)
            else:
                phi = _fused_phi(w0f, slabf, val, field, F, K)
            return (loss.loss(phi, label) * row_mask).sum()

        with jax.named_scope("hm.grad"):
            loss_sum, (g0, gslab) = jax.value_and_grad(
                batch_loss, argnums=(0, 1))(w0.astype(jnp.float32), slab)
            gslab = gslab.astype(jnp.float32)

            # per-occurrence L2 on present entries (reference: -lambda* at
            # update time on the row's features), at slab level pre-scatter
            pm = (val != 0).astype(jnp.float32) * row_mask[:, None]
            lam_col = jnp.concatenate([
                jnp.full((FK,), lam_v, jnp.float32),
                jnp.full((W - FK,), lam_w, jnp.float32)])
            gslab = gslab + lam_col * slab.astype(jnp.float32) \
                * pm[..., None]
            g0 = g0 + lam0 * w0.astype(jnp.float32)

        Tn, sT, stats = rows_update(T, opt_state["T"], rows.reshape(-1),
                                    gslab, optimizer, t, ranks)
        stats["gather_compact_steps"] = compact
        with jax.named_scope("hm.update"):
            w0n, s0 = optimizer.update(w0.astype(jnp.float32), g0,
                                       opt_state["w0"], t)
        return ({"T": Tn, "w0": w0n.astype(w0.dtype)},
                {"T": sT, "w0": s0}, loss_sum, stats)

    def body(params, opt_state, t, idx, *batch):
        return back(params, opt_state, t, *front(params, opt_state, idx),
                    *batch)

    if mesh is not None:
        from jax.sharding import PartitionSpec as P
        if mesh.shape["dp"] != 1:
            raise ValueError("make_ffm_step_fused(mesh=) deals rows over "
                             "'tp' alone; a dp axis sums a gradient over "
                             "replicas: leave that mesh to GSPMD")
        tables, chips = {"T": P("tp", None), "w0": P()}, P("tp")

        def body(params, opt_state, t, idx, *batch):
            # a None (no val, no field, no ranking) is an empty pytree to
            # a spec. No replication check: a branch of a cond or a trip
            # of a loop may leave a constant where the other leaves a
            # chip's own
            mine, slab, exchanged = ffm_slab_over_blocks(
                mesh, params["T"], opt_state["T"], idx, optimizer,
                distinct_tail)

            def local(params, opt_state, t, mine, slab, *batch):
                *out, stats = back(params, opt_state, t, *_own(mine), slab,
                                   *batch)
                return (*out, _lead(stats))
            *out, stats = jax.shard_map(
                local, mesh=mesh,
                in_specs=(tables, tables, P(), chips) + (P(),) * (
                    1 + len(batch)),
                out_specs=(tables, tables, P(), chips),
                check_vma=False)(params, opt_state, t, mine, slab, *batch)
            return (*out, _stats_over_blocks(stats, exchanged))

    if unit_val:
        assert fieldmajor, "unit_val implies the canonical fieldmajor batch"

        def core(params, opt_state, t, idx, label, row_mask):
            # unit-value elision: val == (idx != 0) by construction, so the
            # val array is never transferred — rebuild it on device
            val = (idx != 0).astype(jnp.float32)
            return body(params, opt_state, t, idx, val, label, row_mask,
                        None)
    elif fieldmajor:
        def core(params, opt_state, t, idx, val, label, row_mask):
            return body(params, opt_state, t, idx, val, label, row_mask,
                        None)
    else:
        def core(params, opt_state, t, idx, val, label, row_mask, field):
            return body(params, opt_state, t, idx, val, label, row_mask,
                        field)
    return scannable(partial(jax.jit, donate_argnums=(0, 1))(core), core)


def fm_pack_geometry(K: int) -> Tuple[int, int]:
    """(Wf, P) for the packed fused FM table: Wf = per-feature row width
    (V's K columns + the linear weight, padded to an 8-multiple), P =
    features packed per physical table row, chosen as the power of two
    that makes P*Wf >= 128 — TPU gather/scatter of rows NARROWER than the
    128-lane vreg degrades to element granularity (measured: scatter-add
    of 1M rows into [16M, 16] = 137 ms vs [2M, 128] = 36 ms)."""
    Wf = -(-(K + 1) // 8) * 8
    P = 1
    while P * Wf < 128:
        P <<= 1
    return Wf, P


def _fm_packed_phi(w0f, s128, sub, val, K: int, Wf: int, P: int, l2=None):
    """FM score phi [B] straight from the packed rows, SLOT-MAJOR: s128
    [L, B, P*Wf] with sub, val [L, B]. Every array of L*B rows keeps its
    whole lane axis, and L is the major axis so that [L, B, .] <-> [L*B, .]
    (the gather's output, the scatter-add's input) is a view when B is a
    multiple of 8, not a copy through the (8, 128) tiles.

    A slot's [Wf] block is picked by a lane mask (lane // Wf == sub, an
    iota compare) times val; s = sum_L and q = sum_L of squares are taken
    per lane on whole rows, and only the [B, P*Wf] results fold their P
    blocks (sibling blocks hold true zeros, so the fold is exact f32):
    phi = w0 + s[K] + 1/2 sum_{k<K} (s[k]^2 - q[k]). Viewing the rows as
    [B, L, P, Wf] instead costs a relayout on a TPU (an 8-minor array is
    stored padded to 128 lanes) that the compiler wrote as two 128-trip
    loops, 43% of the step (PERF.md section 5, PR 24); take_along_axis
    lowers to a real per-slot gather and its adjoint to a scatter.

    l2 = (lam_w, lam_v, pm) adds per-occurrence L2 on the occupied block
    THROUGH autodiff: 0.5*lam*pm*(x^2 - sg(x^2)) has value exactly 0 and
    gradient lam*pm*x, so it folds into the same backward pass and the
    gradient wrt s128 arrives packed, sibling blocks exact zeros.
    Returns (phi, reg)."""
    B = sub.shape[1]
    x = s128.astype(jnp.float32)
    lane = jnp.arange(P * Wf)
    own = lane // Wf == sub[..., None]               # [L, B, P*Wf]
    xm = jnp.where(own, x * val[..., None], 0.0)
    s = xm.sum(0).reshape(B, P, Wf).sum(1)           # fold at [B, P*Wf]
    q = (xm * xm).sum(0).reshape(B, P, Wf).sum(1)
    phi = w0f + s[:, K] + 0.5 * (s[:, :K] ** 2 - q[:, :K]).sum(-1)
    if l2 is None:
        return phi, 0.0
    lam_w, lam_v, pm = l2
    col = lane % Wf
    lam = jnp.where(col < K, lam_v, jnp.where(col == K, lam_w, 0.0))
    x2 = x * x
    reg = 0.5 * jnp.sum(jnp.where(own, lam * pm[..., None], 0.0)
                        * (x2 - jax.lax.stop_gradient(x2)))
    return phi, reg


def _fm_packed_grad(loss: Loss, params, idx, val, label, row_mask, lams,
                    l2_on: bool, K: int, Wf: int, P: int, rank=None):
    """The front half both packed steps share: ONE gather of 128-lane
    rows, then loss and gradient wrt w0 and the PACKED rows — the lane
    mask's adjoint IS the expansion to the packed row, so the gradient
    arrives whole-row for the scatter-add: no separate expand pass, no
    hidden per-slot gather/scatter, no relayout (_fm_packed_phi).
    Per-occurrence L2 (lam_w, lam_v) rides the same backward pass; lam0
    is added to g0. ``rank`` (the minibatch step's: rows [L*B] ->
    `rank_rows` of them) ranks the slots in front of the gather, which
    then reads through the batch's distinct rows (`gather_rows`). Returns
    (rows [L*B], ranks or None, whether the gather read through them,
    loss_sum, g0, g128 [L*B, P*Wf] float32), slot-major."""
    lam0, lam_w, lam_v = lams
    if val is None:
        # unit-value elision (io.sparse.SparseBatch): categorical
        # batches never transfer val; rebuild it from idx on device
        # (None is static under jit — a separate compiled variant)
        val = (idx != 0).astype(jnp.float32)
    T, w0f = params["T"], params["w0"].astype(jnp.float32)
    with jax.named_scope("hm.gather"):
        idx, val = idx.T, val.T                      # slot-major [L, B]
        rows, sub = idx // P, idx % P
    ranks = rank(rows.reshape(-1)) if rank else None
    slab128, compact = gather_rows(T, rows, ranks)   # ONE 128-lane gather
    pm = (val != 0).astype(jnp.float32) * row_mask
    l2 = (lam_w, lam_v, pm) if l2_on else None

    def batch_loss(w0f, s128):
        phi, reg = _fm_packed_phi(w0f, s128, sub, val, K, Wf, P, l2)
        return (loss.loss(phi, label) * row_mask).sum() + reg

    with jax.named_scope("hm.grad"):
        loss_sum, (g0, g128) = jax.value_and_grad(
            batch_loss, argnums=(0, 1))(w0f, slab128)
        # the barrier keeps the flattening to [L*B, P*Wf] OUT of the
        # backward fusion: sunk into it, the cotangents of s and q can no
        # longer be broadcast over L in place and are written out, two
        # more [L, B, P*Wf] arrays (0.65 GB each in the benchmark's cell:
        # 63.5 ms a step without, 59.0 with; chip, PERF.md PR 25)
        g128 = jax.lax.optimization_barrier(g128.astype(jnp.float32))
        g0 = g0 + lam0 * w0f
    return (rows.reshape(-1), ranks, compact, loss_sum, g0,
            g128.reshape(-1, P * Wf))


def make_fm_score_fused(K: int):
    """Jitted FM scorer over the packed fused table T [ceil(N/P), P*Wf]:
    feature i lives in row i // P, column block (i % P) * Wf; inside the
    block, columns [:K] are the latent vector and column K the linear
    weight."""
    Wf, P = fm_pack_geometry(K)

    @jax.jit
    def score(w0, T, idx, val):
        idx = idx.T                                  # slot-major [L, B]
        return _fm_packed_phi(w0.astype(jnp.float32), T[idx // P], idx % P,
                              val.T, K, Wf, P)[0]
    return score


def make_fm_step_fused(loss: Loss, optimizer: Optimizer,
                       lambdas: Tuple[float, float, float],
                       K: int) -> Callable:
    """train_fm step over the packed fused table — w and V share rows, and
    P features share one 128-lane-wide physical row.

    Rationale (same cost model as the FFM fused layout): on TPU the sparse
    step is bound by gather/scatter INDEX-ops, and rows narrower than the
    128-lane vreg pay ~4-5x per index (see fm_pack_geometry). The split
    w/V layout spends 8 narrow-row chains per slot; this layout does ONE
    gather + one 3-op sparse-optimizer chain on 128-lane rows. The
    gradient of a slot expands to its [P*Wf] row via a one-hot mask —
    sibling features in the row receive exact zeros, so the optimizer's
    elementwise sparse update leaves them untouched (requires reg='no' on
    the optimizer, which factor trainers always use: -lambda* L2 is
    applied per-occurrence at slab level here instead). Duplicate-id
    accumulation inside the batch is handled by the scatter-add in
    sparse_update exactly as before.

    lambdas=None builds the DYNAMIC-lambda variant: the step takes a
    trailing `lams` [3] array (lam0, lam_w, lam_v) so train_fm's -adareg
    can adapt regularization per epoch without a recompile per value."""
    dyn = lambdas is None
    assert optimizer.sparse_update is not None
    Wf, P = fm_pack_geometry(K)

    def body(params, opt_state, t, idx, val, label, row_mask, lams):
        lams = (lams[0], lams[1], lams[2]) if dyn else lambdas
        T, w0 = params["T"], params["w0"]
        rows, _, _, loss_sum, g0, g128 = _fm_packed_grad(
            loss, params, idx, val, label, row_mask, lams,
            dyn or bool(lams[1] or lams[2]), K, Wf, P)

        # the per-occurrence chain scatters and updates in one: it is the
        # sparse variant's hm.scatter; hm.update is what is left, w0
        with jax.named_scope("hm.scatter"):
            Tn, sT = optimizer.sparse_update(T, g128, opt_state["T"], rows, t)
        with jax.named_scope("hm.update"):
            w0n, s0 = optimizer.update(w0.astype(jnp.float32), g0,
                                       opt_state["w0"], t)
        return ({"T": Tn, "w0": w0n.astype(w0.dtype)},
                {"T": sT, "w0": s0}, loss_sum)

    if dyn:
        def core(params, opt_state, t, idx, val, label, row_mask, lams):
            return body(params, opt_state, t, idx, val, label, row_mask,
                        lams)
    else:
        def core(params, opt_state, t, idx, val, label, row_mask):
            return body(params, opt_state, t, idx, val, label, row_mask,
                        None)
    return scannable(partial(jax.jit, donate_argnums=(0, 1))(core), core)


# --- the distinct-row tail ----------------------------------------------------
# A minibatch step's tail used to zero-fill a table-sized G, scatter-add the
# batch gradient into it and run the optimizer over every table row, to
# change the rows one batch touches (1.7% of 4,194,304 in the benchmark's
# cells). rows_update sums duplicates into a COMPACT gradient and updates
# the batch's distinct rows only, where the shapes and the batch say that
# pays: up to tail_cap distinct rows, the count at which the two tails cost
# the same.


class _TailCost(NamedTuple):
    """What one tail runs and the other does not, on a TPU v5e, for 1,277,952
    slots into a table of 4,194,304 rows: the readings of
    experiments/probe_distinct_tail.py (chiprun_out/probe_distinct_tail.json;
    PERF.md section 6 has them by PR)."""
    #: ns a TABLE row, the dense tail alone: the optimizer's pass over the
    #: table, and what the scatter-add into a zero-filled table-sized G
    #: costs over the one into the compact Gc
    dense_table_row: float
    #: ns a SLOT, the distinct tail alone: the sort that brings the
    #: distinct row ids to the front, and the running sum that ranks
    rank_slot: float
    #: ns a DISTINCT row at capacity: the row's copies out of every table
    #: and back, and a capacity row of Gc zero-filled and scattered into
    distinct_row: float
    #: the most passes of the compact sum (`scatter_cap` rows each, every
    #: pass a read of the whole slab) that the whole step still takes for
    #: less than the dense tail (PR 38, experiments/probe_compact_scatter.py
    #: `steps`: FM's second pass 56.44 ms a step at 239.6k distinct rows,
    #: the dense tail 55.55 at 286.7k; the flagship's 82.58 at 134k, the
    #: dense tail 103.92)
    passes: int


# by (lanes, bytes of the narrowest item): the tables' rows as
# ops/rows_pallas.py moves them
_TAIL_COSTS = {
    # fm_criteo: float32 T and gg through the row kernel, four row DMAs at
    # 15.5 ns (PRs 28 and 30: dense 15.9 + 2.7 ms, sort 1.73 + 0.24 ms; the
    # two tails cost the same at ~284k rows)
    (128, 4): _TailCost(4.43, 1.54, 58.5, 1),
    # ffm_criteo_joint: bfloat16 T and float32 gg, which Mosaic refuses,
    # through XLA's gather and scatter in blocks, 77-79 ns a row a table
    # (PR 32, the megastep: dense 103.98 ms a step; 85.34 at 72.4k distinct
    # rows and 177 ns more a row, 2 a capacity row; equal at ~177k)
    (164, 2): _TailCost(7.97, 1.54, 179.0, 2),
}

#: what a minibatch step counts about itself: rows_update's (which tail
#: ran, the distinct rows of a step that ranked, whether its scatter-add
#: summed into the compact gradient in fast memory) and gather_rows'
#: (whether the gather read through the distinct rows)
TAIL_STATS = ("tail_distinct_steps", "tail_dense_steps", "distinct_rows",
              "gather_compact_steps", "scatter_compact_steps")


def _stats_over_blocks(stats: dict, exchanged) -> dict:
    """TAIL_STATS of one step whose table is dealt in blocks over a mesh's
    chips, from each block's own, stacked [blocks]: the distinct rows
    summed (the blocks share none), a branch counted as taken where every
    block took it, and the step's tail dense wherever one block's was;
    and ``exchange_compact_steps``, ``exchanged``: 1 where the slab
    crossed the mesh as the blocks' compact tables."""
    every = {k: stats[k].min(0)
             for k in ("tail_distinct_steps", "gather_compact_steps",
                       "scatter_compact_steps")}
    return {**every, "tail_dense_steps": 1 - every["tail_distinct_steps"],
            "distinct_rows": stats["distinct_rows"].sum(0),
            "exchange_compact_steps": exchanged}


def _tail_cost(W: int, itemsize: int) -> _TailCost:
    """The readings at (W, itemsize). A shape nobody read moves its rows
    as the flagship's do (only (128, 4) has the kernel): the flagship's,
    the table-row cost by the bytes of a padded row of T, gg and G."""
    if (W, itemsize) in _TAIL_COSTS:  # graftcheck: disable=GC09 (static)
        return _TAIL_COSTS[W, itemsize]

    def row_bytes(W, itemsize):
        return -(-W // 128) * 128 * (itemsize + 4 + 4)
    c = _TAIL_COSTS[164, 2]
    return c._replace(dense_table_row=c.dense_table_row
                      * row_bytes(W, itemsize) / row_bytes(164, 2))


def tail_cap(n: int, R: int, W: int = 128, itemsize: int = 4) -> int:
    """Most distinct rows the distinct-row tail takes on for n slots into
    a table of R rows of W lanes whose narrowest items (the table's, or a
    state leaf's) have `itemsize` bytes, in whole blocks of the row
    kernel's (whole id tiles under one block), and no more than the
    passes of the compact sum that still cost less than the dense tail
    (FM one, 196,608 rows; the flagship two); 0 where the dense tail is
    the cheaper one at any count (a table small against the batch: the toy
    config's 4,096 rows against 9,984 slots), or where R + n overflows the
    int32 ids that pad the distinct-row list."""
    if R + n >= 2 ** 31:
        return 0
    c = _tail_cost(W, itemsize)
    even = (R * c.dense_table_row - n * c.rank_slot) / c.distinct_row
    # graftcheck: disable=GC09 (Python numbers of the shapes)
    cap = max(0, min(n, int(even), c.passes * scatter_rows_cap(W)))
    return (cap // BLOCK_ROWS * BLOCK_ROWS
            or cap // LIST_MULTIPLE * LIST_MULTIPLE)


def whole_table_cap(n: int, R: int, W: int = 128) -> int:
    """The distinct-row tail's capacity for a table SMALL against the
    batch (MF's item table: 39,780 rows against 131,072 slots), which
    `tail_cap` prices at 0 because a pass over the table is cheaper than
    ranking the batch. Its readings were taken before the compact sum was
    a kernel: they leave out the slots' scatter-add, common to both tails
    then, which the dense tail still gives to XLA (13.8-20.6 ns a slot on
    a v5e) and the distinct one to `scatter_rows` (~7). Where one pass of
    that sum holds the whole table, the list holds as many rows as the
    batch can touch, cut to whole blocks of the row kernel (a list is no
    longer than its table): 0 for a larger table, which keeps `tail_cap`'s
    rule."""
    if R > scatter_rows_cap(W):
        return 0
    cap = min(n, R)
    return (cap // BLOCK_ROWS * BLOCK_ROWS
            or cap // LIST_MULTIPLE * LIST_MULTIPLE)


# --- the gather through the distinct rows --------------------------------------
# XLA's row gather costs by the ROW and by where its operand lives: 10.2 ns a
# row out of HBM, out of the 2 GiB table as out of a 145 MB one, and 1.8-2.0
# ns out of an operand the compiler keeps in the chip's fast memory (`S(1)`
# in the compiled text; 3.2 ns a row of 164 bfloat16 lanes; a v5e,
# experiments/probe_compact_gather.py, PERF.md section 6, PR 34). A batch of
# the benchmark's cells reads each of its ~73k distinct rows 17.5 times over,
# so gather_rows reads them out of the table once, into a compact table that
# fits there, and the 1,277,952 slots out of that.

#: bytes of the compact table: what the v5e compiler still keeps in fast
#: memory beside the step's other residents (it does at 112 MiB and no
#: longer at 116, the FM step compiled here; tests/tpu_aot_worker.py holds
#: that the cells' tables are placed there; left in HBM, at the ranking's
#: 282,624 rows, the FM step LOSES 2.3 ms to the direct gather). 196,608
#: rows of 128 float32 lanes or of 164 bfloat16 ones, which are padded to
#: 256.
COMPACT_TABLE_BYTES = 96 << 20
#: the most distinct rows one trip of the compact table's fill reads (the
#: whole step at 1024 / 2048 / 4096 / 8192 / 32768: FM 33.93 / 33.88 /
#: 34.19 / 34.15 / 34.84 ms, the flagship's megastep of two 84.68 / 84.69 /
#: 84.65 / 84.64 / 86.24 with a stable sort; one trip of the whole capacity
#: leaves the table in HBM, 46.83)
FILL_BLOCK_ROWS = 2048


def gather_cap(cap: int, W: int, itemsize: int, chips: int = 1) -> int:
    """Most distinct rows `gather_rows` reads through: the ranking's
    capacity, or the rows of [., W] the compact table's bytes hold, whole
    id tiles of them. Of ``chips`` that exchange their compact tables
    (`make_ffm_step_fused(mesh=)`), the rows one chip may add so that the
    tables of all of them fit those bytes together (the flagship's block
    over tp=4: 49,152)."""
    row_bytes = -(-W // 128) * 128 * itemsize
    return min(cap, COMPACT_TABLE_BYTES // row_bytes // chips
               // LIST_MULTIPLE * LIST_MULTIPLE)


def _compact_table(T, ranks: RowRanks, cap: int, trips=None):
    """C [cap, W]: the distinct rows ``ranks.urows`` read out of T once, a
    block of FILL_BLOCK_ROWS a trip up to the count (or ``trips``), so
    that the fill costs by the rows a batch holds and not by the
    capacity (rows of C past the count are never read: a rank is under
    the count)."""
    tb = min(cap, FILL_BLOCK_ROWS)

    def block(i, C):
        # the last block of a capacity that is not whole blocks ends at
        # the capacity and reads some rows a second time
        at = jnp.minimum(i * tb, cap - tb)
        ids = jax.lax.dynamic_slice_in_dim(ranks.urows, at, tb)
        return jax.lax.dynamic_update_slice_in_dim(
            C, T.at[ids].get(mode="clip"), at, 0)
    if trips is None:
        trips = (ranks.n_distinct + tb - 1) // tb
    return jax.lax.fori_loop(0, trips, block,
                             jnp.zeros((cap, T.shape[1]), T.dtype))


def scatter_cap(cap: int, W: int) -> int:
    """Most distinct rows one pass of the tail sums into a compact
    gradient held in fast memory: the ranking's capacity, or the rows the
    kernel's own budget holds (`rows_pallas.scatter_rows_cap`: FM 196,608
    rows of 128 lanes, the flagship 98,304, its 164 lanes padded to
    256)."""
    return min(cap, scatter_rows_cap(W))


class RowRanks(NamedTuple):
    """A batch's slots ranked by table row (`rank_rows`), for the gather in
    front of the step and the tail behind it."""
    srows: jax.Array        #: [n] the slots' rows in order
    perm: jax.Array         #: [n] the slot each came from
    urows: jax.Array        #: [cap] the distinct rows in order, then ids >= R
    n_distinct: jax.Array   #: int32 scalar
    slot_rank: jax.Array    #: [n] each slot's rank, in slot order


def rank_rows(rows, T, state, optimizer: Optimizer, cap=None,
              block: bool = False):
    """Rank the slots ``rows`` [n] of a batch by table row, once, for
    `gather_rows` and `rows_update`; None where the step takes the dense
    tail whatever the batch holds, and then has no ranking in its program.

    ``block`` says that T is one block of a table whose rows are dealt
    over the chips of a mesh, and ``rows`` the block's own numbering, in
    which a slot of another block carries the id R. Such slots sort behind
    every row of the block, are not counted in ``n_distinct``, never enter
    ``urows`` and rank at the capacity, so that the gather reads nothing
    for them and the tail's `mode="drop"` adds nothing. Capacities follow
    the shapes handed in: the block's.

    ``cap`` (None: tail_cap of T [R, W] and the ``state`` leaves' shapes, 0
    for an optimizer that moves a zero-gradient row) is static: the most
    distinct rows the ranking lists. ONE key-value sort of (rows, iota)
    gives the rows in order and the slot each came from; a flag where the
    sorted row changes counts the distinct rows, and its running sum is
    each sorted slot's rank; the flagged row ids, sorted to the front, are
    the distinct rows, padded with out-of-range ids; one more key-value
    sort, of (perm, rank), carries the ranks back to SLOT order, for the
    gather and the tail's scatter-add alike (an int32 scatter of as many
    scalars costs seven times that). The last three only for a batch
    within the capacity: no other is read through them. All of it stays
    under ``hm.scatter`` but the last sort, which was the gather's alone
    through PR 37 and stays under ``hm.gather``."""
    n, (R, W) = rows.shape[0], T.shape
    if cap is None:
        leaves = jax.tree_util.tree_leaves(state)
        cap = tail_cap(n, R, W, min(a.dtype.itemsize for a in (T, *leaves))
                       ) if optimizer.zero_grad_noop else 0
    if not cap:  # graftcheck: disable=GC09 (static)
        return None
    with jax.named_scope("hm.scatter"):
        slot = jnp.arange(n, dtype=jnp.int32)
        srows, perm = jax.lax.sort_key_val(rows, slot)
        first = jnp.concatenate(
            [jnp.ones((1,), bool), srows[1:] != srows[:-1]])
        if block:
            first &= srows < R
        n_distinct = first.sum(dtype=jnp.int32)

    def listed():
        with jax.named_scope("hm.scatter"):
            rank = jnp.cumsum(first.astype(jnp.int32)) - 1
            if block:
                rank = jnp.where(srows < R, rank, cap)
            urows = jnp.sort(jnp.where(first, srows, R + slot))[:cap]
        with jax.named_scope("hm.gather"):
            # (a permutation's keys are distinct: a stable sort would
            # carry a third operand to break ties that cannot occur,
            # 2.41 ms against 1.6)
            _, slot_rank = jax.lax.sort_key_val(perm, rank, is_stable=False)
        return urows, slot_rank
    # a batch over the capacity reads neither: its step stays the dense
    # tail's, which is fed the sorted rows alone (1.9 ms less)
    urows, slot_rank = jax.lax.cond(
        n_distinct <= cap, listed,
        lambda: (jnp.zeros((cap,), jnp.int32), jnp.zeros((n,), jnp.int32)))
    return RowRanks(srows, perm, urows, n_distinct, slot_rank)


def gather_rows(T, rows, ranks: Optional[RowRanks], block: bool = False):
    """``T[rows]`` (rows of any shape), bit for bit, and whether it was
    read through the batch's distinct rows (an int32 scalar, the step's
    ``gather_compact_steps``): where `rank_rows` ranked them and the batch
    holds at most `gather_cap` of them, the distinct rows are read out of
    the table ONCE into a compact C [gather_cap, W], and every slot reads
    C at its row's rank. Of a ``block`` of a table (`rank_rows`) a slot
    of another block, the id R, reads zeros, so that the blocks' slabs sum
    to the whole table's.

    A slot reads C (`_compact_table`) at `rank_rows`' ``slot_rank``. A
    batch over the capacity reads the table directly, as a step without
    ranking does. The ``cond`` is outside ``hm.gather`` and its branches
    inside, as the tail's: a trace reader that took the ``cond`` for an
    operation under a scope would count the gather twice."""
    def direct():
        with jax.named_scope("hm.gather"):
            return T[rows]

    def of_the_block(slab):
        # behind the cond, not in its branches: the compiler hoists a
        # select both branches end in, and materialises its mask for it
        if not block:
            return slab
        with jax.named_scope("hm.gather"):
            return jnp.where((rows < T.shape[0])[..., None], slab, 0)

    cap = gather_cap(ranks.urows.shape[0], T.shape[1], T.dtype.itemsize) \
        if ranks is not None else 0
    if not cap:  # graftcheck: disable=GC09 (static)
        return of_the_block(direct()), jnp.zeros((), jnp.int32)

    def compact():
        with jax.named_scope("hm.gather"):
            C = _compact_table(T, ranks, cap)
            return C[ranks.slot_rank.reshape(rows.shape)]
    fits = ranks.n_distinct <= cap
    return (of_the_block(jax.lax.cond(fits, compact, direct)),
            fits.astype(jnp.int32))


def rows_update(T, state, rows, g, optimizer: Optimizer, t,
                ranks: Optional[RowRanks]):
    """The tail of a minibatch step: apply ``optimizer.update`` with the
    gradient rows ``g`` [..., W] (float32; the slots of its leading axes
    in order) summed by table row ``rows`` [n] into T [R, W] and its
    co-shaped ``state``, whose leaves may be of another dtype than T (a
    bfloat16 table with float32 accumulators). Returns (T, state,
    stats), stats a dict of int32 scalars named by TAIL_STATS but
    ``gather_compact_steps``. Knows nothing of what a row holds.

    For an optimizer whose update leaves a zero-gradient entry as it was
    (``zero_grad_noop``: AdaGrad and SGD with reg='no', which factor
    trainers always use), updating the distinct rows is the dense update:
    what differs is the order in which f32 addends meet in a duplicate's
    sum. Either way a row of T is widened to float32, updated there and
    rounded to T's dtype once.

      1. ``ranks``: `rank_rows` of the same ``rows``, computed in front of
         the step's gather, which reads through it too.
      2. The compact gradient Gc = zeros([scap, W]).at[slot_rank].add(g)
         by ops/rows_pallas.py `scatter_rows`: the slab read in its own
         order, each row added at its rank into a Gc the kernel holds in
         fast memory, `scatter_cap` rows of it (the gather's cure turned
         round, PR 34: a row costs 10-17 ns out of an HBM operand, 2-3 out
         of fast memory; XLA's own scatter-add sorts its indices and reads
         the slab through the permutation, out of HBM). A batch of more
         distinct rows, within the ranking's capacity (`tail_cap` allows
         the passes that beat the dense tail: FM one, the flagship two),
         is summed and updated in passes of scap rows, a trip of a loop
         each, as many as its count needs (the counter
         ``scatter_compact_steps`` is the steps of one pass).
      3. T and every leaf of the state are read at the distinct rows,
         given the optimizer's own update (same function, same float32,
         same t) and written back in place by ops/rows_pallas.py
         `update_rows`: ONE kernel where Mosaic compiles it (a TPU, 128
         lanes of 32-bit words), else XLA's gather, update and scatter in
         blocks; both cost by the count of distinct rows. A pass updates
         the rows it summed, in place, before the next sums its own.

    ``ranks`` None is the dense tail alone. A batch with more distinct
    rows than the ranking's capacity takes the dense tail too (lax.cond),
    fed the same sorted rows. Where T is a block of a table (`rank_rows`'
    ``block``), a slot of another block carries the id R, or the rank
    ``cap``, and every pass drops it: its gradient row is added by the
    chip that holds its row."""
    R, W = T.shape
    tree = jax.tree_util.tree_structure(state)

    def flat(perm=None):
        if perm is None:
            return g.reshape(-1, W)
        if g.ndim == 2:
            return g[perm]
        # a [B, L, W] slab read where it lies: its reshape would retile
        # it (L is not whole 8-row tiles), a second slab beside the one
        # the tail's cond holds
        L = g.shape[1]
        return g[perm // L, perm % L]

    def dense(T, state, rows=rows, perm=None, **sorted_):
        with jax.named_scope("hm.scatter"):
            G = jnp.zeros((R, W), jnp.float32).at[rows].add(flat(perm),
                                                            **sorted_)
        with jax.named_scope("hm.update"):
            Tn, sn = optimizer.update(T.astype(jnp.float32), G, state, t)
            return Tn.astype(T.dtype), sn

    if ranks is None:
        return (*dense(T, state), dict(zip(
            ("tail_distinct_steps", "tail_dense_steps", "distinct_rows",
             "scatter_compact_steps"), jnp.asarray([0, 1, 0, 0], jnp.int32))))
    srows, perm, urows, n_distinct, slot_rank = ranks
    cap = urows.shape[0]
    size = scatter_cap(cap, W) or cap      # rows of Gc a pass sums
    # the list in whole passes: padded with ids past the table
    listed = jnp.concatenate([urows, R + jnp.arange(-cap % size,
                                                    dtype=jnp.int32)])

    def a_pass(p, tables):
        """The rows ranked [lo, lo + size): Gc summed, then updated."""
        lo = p * size
        with jax.named_scope("hm.scatter"):
            # a slot of another pass, or of another block, adds nothing
            rank = jnp.where((slot_rank >= lo) & (slot_rank < cap),
                             slot_rank - lo, size)
            Gc = scatter_rows(g, rank, size)
        with jax.named_scope("hm.update"):
            def update(blocks, g, t):
                w, s = optimizer.update(blocks[0].astype(jnp.float32), g,
                                        tree.unflatten(blocks[1:]), t)
                return (w, *jax.tree_util.tree_leaves(s))
            return update_rows(
                tables, jax.lax.dynamic_slice_in_dim(listed, lo, size),
                jnp.minimum(n_distinct - lo, size), Gc, t, update)

    def passes(T, state):
        Tn, *sn = jax.lax.fori_loop(
            0, (n_distinct + size - 1) // size, a_pass,
            (T, *jax.tree_util.tree_leaves(state)))
        return Tn, tree.unflatten(sn)

    took = n_distinct <= cap
    Tn, sn = jax.lax.cond(
        took, passes,
        lambda T, s: dense(T, s, srows, perm, indices_are_sorted=True),
        T, state)
    took = took.astype(jnp.int32)
    return Tn, sn, {"tail_distinct_steps": took, "tail_dense_steps": 1 - took,
                    "distinct_rows": n_distinct,
                    "scatter_compact_steps": (n_distinct <= size).astype(
                        jnp.int32)}


def make_fm_step_minibatch(loss: Loss, optimizer: Optimizer,
                           lambdas: Tuple[float, float, float],
                           K: int, distinct_tail: bool = True) -> Callable:
    """train_fm step over the packed fused table with MINIBATCH-summed
    accumulators — the FFM joint fused step's update shape applied to FM.

    Why: the per-occurrence sparse chain (make_fm_step_fused +
    Optimizer.sparse_update) spends 5 table-row index ops per slot
    (gather, gg scatter-add, gg re-gather, w scatter-add, + the forward
    gather), and on this hardware index ops ARE the cost (docs/
    PERFORMANCE.md cost model) — train_fm measured 0.47x of the per-chip
    share while the strictly harder FFM ran 1.145x. This step does ONE
    forward gather + ONE scatter-add of the batch gradient, then the
    optimizer's elementwise update, through rows_update: summed into a
    compact gradient and applied to the batch's distinct rows where the
    table is large against the batch and the batch repeats its rows
    (tail_cap), else summed into a dense G with an O(table) pass (priced
    at ~5 ms from a round-5 probe on a small table; the benchmark's 2 GiB
    float32 table reads 15.9 ms, and 3.2 more to zero G: PERF.md section
    6). The dense tail stays for what the distinct rows cannot
    take: a table small against the batch, where one pass over it costs
    less than ranking the batch's rows (tail_cap is 0 there), and a
    batch with more distinct rows than the cap. ``distinct_tail=False``
    keeps the dense tail whatever the shapes: the trainer's choice under
    -mesh, not a user's. Where the tail ranks the
    batch's rows it does so in FRONT of the step, and the forward gather
    reads through the distinct rows too (gather_rows: each read out of the
    table once, the slots out of a compact copy of them).

    Returns (params, opt_state, loss_sum, stats): stats counts which tail
    ran, the batch's distinct rows and whether the gather read through
    them (TAIL_STATS).

    Semantics delta (documented, same as the FFM fused path):
    adaptive accumulators see the square of the SUMMED minibatch
    gradient rather than per-occurrence squares. Per-occurrence L2 is
    unchanged — it folds into the slab gradient BEFORE the scatter,
    exactly like make_fm_step_fused.

    lambdas=None builds the dynamic-lambda variant (trailing `lams` [3]
    step argument) for -adareg."""
    dyn = lambdas is None
    Wf, P = fm_pack_geometry(K)

    def body(params, opt_state, t, idx, val, label, row_mask, lams):
        lams = (lams[0], lams[1], lams[2]) if dyn else lambdas
        T, w0 = params["T"], params["w0"]
        rows, ranks, compact, loss_sum, g0, g128 = _fm_packed_grad(
            loss, params, idx, val, label, row_mask, lams,
            dyn or bool(lams[1] or lams[2]), K, Wf, P,
            lambda rows: rank_rows(rows, T, opt_state["T"], optimizer,
                                   None if distinct_tail else 0))

        Tn, sT, stats = rows_update(T, opt_state["T"], rows, g128, optimizer,
                                    t, ranks)
        stats["gather_compact_steps"] = compact
        with jax.named_scope("hm.update"):
            w0n, s0 = optimizer.update(w0.astype(jnp.float32), g0,
                                       opt_state["w0"], t)
        return ({"T": Tn, "w0": w0n.astype(w0.dtype)},
                {"T": sT, "w0": s0}, loss_sum, stats)

    if dyn:
        def core(params, opt_state, t, idx, val, label, row_mask, lams):
            return body(params, opt_state, t, idx, val, label, row_mask,
                        lams)
    else:
        def core(params, opt_state, t, idx, val, label, row_mask):
            return body(params, opt_state, t, idx, val, label, row_mask,
                        None)
    return scannable(partial(jax.jit, donate_argnums=(0, 1))(core), core)


def make_fm_step(loss, optimizer, lambdas):
    if optimizer.sparse_update is not None:
        return _make_factor_step_sparse("fm", loss, optimizer, lambdas)
    return _make_factor_step_dense(fm_score, loss, optimizer, lambdas)


def make_ffm_step(loss, optimizer, lambdas):
    if optimizer.sparse_update is not None:
        return _make_factor_step_sparse("ffm", loss, optimizer, lambdas)
    return _make_factor_step_dense(ffm_score, loss, optimizer, lambdas)
