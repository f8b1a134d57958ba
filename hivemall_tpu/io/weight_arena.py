"""Weight arena — packed, mmap-able, multi-precision serving weights.

ROADMAP item 3 ("raw-speed serving"): every PR 7 fleet replica used to
deserialize its OWN copy of the promoted checkpoint bundle — npz decode,
host staging, device placement, optimizer-state ballast — so N replicas
cost N× host RAM and N× reload I/O for weights that serving only ever
READS. The arena is the shard-cache idiom (io/shard_cache.py container:
magic | json header | raw payload, sha256 over the payload, written
tmp → fsync → ``os.replace``) applied to inference weights:

- **publish once**: promotion (serve/promote.py PromotionGate) extracts
  the trainer's *serving tables* — the finalized f32 inference weights,
  NOT the training state — and writes ``<bundle>.npz.arena`` next to the
  bundle, carrying three precision tiers per table (f32, bf16 stored as
  uint16 bit patterns, int8 with a symmetric per-table scale) plus the
  source bundle's leaf digest so a stale or mismatched arena can never
  serve.
- **map everywhere**: every PredictEngine replica ``mmap``s the arena
  read-only instead of loading its own bundle copy. The kernel page
  cache shares the physical pages across processes — fleet-wide weight
  memory is O(1) in the replica count, and a rolling hot reload is a
  remap, not a deserialize (near-instant, no allocation spike).
- **score host-side**: the arena scorers are pure-NumPy twins of the
  jitted bucketed predict kernels (ops/linear.py::linear_margin,
  ops/fm.py::fm_score/ffm_score) operating directly on the mapped
  views. At serve batch shapes (B ≤ 256) the per-call XLA dispatch +
  h2d staging dominates the math by ~2 orders of magnitude on CPU
  hosts, so the gather-dot twins are both the zero-copy path AND the
  raw-speed path. They are numerically equivalent but NOT bit-identical
  to XLA (reduction order differs at the ulp level), which is why the
  engine's default f32 path stays on the trainer's jitted scorer —
  quantization off bit-matches the pre-arena serving path exactly
  (pinned by tests/test_weight_arena.py).

Quantization error contract (docs/PERFORMANCE.md "Weight arena +
quantized scoring"): int8 is symmetric per-table — ``scale =
max|w| / 127``, per-weight absolute error ≤ ``scale / 2``; bf16 keeps
8 mantissa bits — per-weight relative error ≤ 2^-8. Each family's
:func:`score_error_bound` propagates those per-weight errors through
the exact margin polynomial to a per-row MARGIN bound (probabilities
tighten it further: sigmoid is 1/4-Lipschitz). The bound is what the
property tests enforce and what the promotion gate's quantized scoring
leg inherits — an over-error quantized candidate fails the same
logloss/AUC/calibration deltas as any bad model and is quarantined.
"""

from __future__ import annotations

import os
from typing import Callable, Dict, Optional, Tuple

import numpy as np

from .shard_cache import (CacheInvalid, read_cache_file, write_cache_file)
from .sparse import SparseBatch

__all__ = ["ArenaUnsupported", "WeightArena", "arena_path",
           "publish_arena", "open_arena", "try_open_arena", "quantize_int8",
           "score_error_bound", "factor_score_error_bound",
           "host_rss_bytes", "PRECISIONS"]

ARENA_SUFFIX = ".arena"
ARENA_KIND = "weight_arena"
_FORMAT = 1
PRECISIONS = ("f32", "bf16", "int8")

#: per-weight relative error of a round-to-nearest bf16 cast (8 mantissa
#: bits): |w - bf16(w)| <= |w| * 2^-8
_BF16_REL = 2.0 ** -8

# fused joint-table row-hash constants — MUST stay equal to the jitted
# ffm_row_hash (ops/fm.py) or the arena would gather different rows than
# training wrote
from ..ops.fm import _J1 as _ROWHASH_J1, _J3 as _ROWHASH_J3  # noqa: E402


class ArenaUnsupported(ValueError):
    """The trainer's serving state has no arena mapping (a trainer with no
    ``serving_tables()`` surface, e.g. the tree ensembles). The engine
    degrades to the bundle path; quantized serving is refused."""


def arena_path(bundle_path: str) -> str:
    """The arena sidecar published next to a checkpoint bundle."""
    return bundle_path + ARENA_SUFFIX


def host_rss_bytes() -> Optional[int]:
    """This process's CURRENT resident set size in bytes (Linux
    /proc/self/statm), or None where unavailable. The serve/fleet obs
    gauge behind the arena's ≥4× fleet-memory claim — devprof's memory
    gauges cover device allocations only, host RSS was unmeasured."""
    try:
        with open("/proc/self/statm") as f:
            rss_pages = int(f.read().split()[1])
        return rss_pages * (os.sysconf("SC_PAGE_SIZE")
                            if hasattr(os, "sysconf") else 4096)
    except (OSError, ValueError, IndexError):
        return None


# --- quantization -----------------------------------------------------------

def quantize_int8(a: np.ndarray) -> Tuple[np.ndarray, float]:
    """Symmetric per-table int8: ``q = rint(a / scale)`` with ``scale =
    max|a| / 127`` (1.0 for an all-zero table so dequant is exact).
    Round-to-nearest ⇒ per-weight absolute error ≤ scale / 2."""
    a = np.asarray(a, np.float32)
    amax = float(np.max(np.abs(a))) if a.size else 0.0
    scale = amax / 127.0 if amax > 0 else 1.0
    q = np.clip(np.rint(a / scale), -127, 127).astype(np.int8)
    return q, scale


def _to_bf16_bits(a: np.ndarray) -> np.ndarray:
    """f32 → bf16 bit patterns stored as uint16 (the container has no
    bf16 dtype; ml_dtypes reinterprets the bits on the read side)."""
    import ml_dtypes
    return np.asarray(a, np.float32).astype(ml_dtypes.bfloat16) \
        .view(np.uint16)


def _bf16_bits_to_f32(bits: np.ndarray) -> np.ndarray:
    """bf16 is f32's top half: widen by a 16-bit left shift (measured
    ~5x the ml_dtypes astype on gathered slabs — the hot-path direction
    needs no rounding logic, only the publish-side narrowing does)."""
    return (bits.astype(np.uint32) << np.uint32(16)).view(np.float32)


# --- numpy scorer kernels (host twins of ops/linear.py + ops/fm.py) ---------

def _np_val(batch: SparseBatch) -> np.ndarray:
    v = batch.val
    if v is None:                    # unit-value elision: val == (idx != 0)
        return (np.asarray(batch.idx) != 0).astype(np.float32)
    return np.asarray(v, np.float32)


def _row_hash_np(idx: np.ndarray, Mr: int) -> np.ndarray:
    """NumPy twin of ops.fm.ffm_row_hash — identical uint32 mix (the
    uint64+mask form sidesteps NumPy overflow warnings)."""
    h = (idx.astype(np.uint64) & 0xFFFFFFFF) * _ROWHASH_J1 & 0xFFFFFFFF
    h = h ^ (h >> 15)
    h = (h * _ROWHASH_J3) & 0xFFFFFFFF
    h = h ^ (h >> 13)
    return (h & np.uint64(Mr - 1)).astype(np.int64)


def _linear_margin(gw, batch: SparseBatch) -> np.ndarray:
    val = _np_val(batch)
    return (gw(np.asarray(batch.idx)) * val).sum(axis=-1)


def _fm_margin(w0, gw, gV, batch: SparseBatch) -> np.ndarray:
    idx = np.asarray(batch.idx)
    val = _np_val(batch)
    wi = (gw(idx) * val).sum(-1)
    xv = gV(idx) * val[..., None]               # [B, L, K]
    s = xv.sum(1)
    s2 = (xv ** 2).sum(1)
    return w0 + wi + 0.5 * (s * s - s2).sum(-1)


def _pairwise_ffm_phi(w0, wg, A, val) -> np.ndarray:
    """phi from the gathered pair cube A[b,i,j,k] = V[feature_i][f_j]:
    the exact _ffm_slab_phi sum (upper triangle of A[i,j]·A[j,i])."""
    L = val.shape[1]
    inter = np.einsum("bijk,bjik->bij", A, A)
    xx = val[:, :, None] * val[:, None, :]
    iu = np.triu(np.ones((L, L), np.float32), k=1)
    return w0 + (wg * val).sum(-1) + (inter * xx * iu[None]).sum((1, 2))


def _ffm_joint_margin(w0, gT, Mr, F, K, batch: SparseBatch) -> np.ndarray:
    idx = np.asarray(batch.idx)
    val = _np_val(batch)
    fld = np.asarray(batch.field) % F
    B, L = idx.shape
    slab = gT(_row_hash_np(idx, Mr))            # [B, L, F*K + 1]
    Vg = slab[..., :F * K].reshape(B, L, F, K)
    wg = slab[..., F * K]
    A = Vg[np.arange(B)[:, None, None],
           np.arange(L)[None, :, None], fld[:, None, :], :]
    return _pairwise_ffm_phi(w0, wg, A, val)


def _ffm_dense_margin(w0, gw, gV2, F, batch: SparseBatch) -> np.ndarray:
    idx = np.asarray(batch.idx)
    val = _np_val(batch)
    fld = np.asarray(batch.field) % F
    flat = idx.astype(np.int64)[:, :, None] * F + fld[:, None, :]
    A = gV2(flat)                                # [B, L, L, K]
    return _pairwise_ffm_phi(w0, gw(idx), A, val)


def _sigmoid_exp(phi: np.ndarray) -> np.ndarray:
    """The FM family's historical probability form (models/fm.py
    ``predict``) — mirrored exactly so arena FM probabilities match the
    offline path's float behavior, not sigmoid_np's piecewise form."""
    with np.errstate(over="ignore"):
        return np.asarray(1.0 / (1.0 + np.exp(-np.asarray(phi,
                                                          np.float32))),
                          np.float32)


# --- publish ----------------------------------------------------------------

def publish_arena(bundle_path: str, trainer, *,
                  precisions: Tuple[str, ...] = PRECISIONS) -> str:
    """Extract ``trainer``'s serving tables and write the arena sidecar
    atomically next to ``bundle_path``. The trainer must be the one
    loaded FROM that bundle (the header records the bundle's leaf digest;
    readers refuse a digest mismatch). Returns the arena path. Raises
    :class:`ArenaUnsupported` for trainers/layouts without a serving-
    table mapping."""
    from .checkpoint import bundle_meta
    meta, tables = _serving_tables(trainer)
    bm = bundle_meta(bundle_path)
    scales: Dict[str, float] = {}
    arrays: Dict[str, np.ndarray] = {}
    for prec in precisions:
        if prec not in PRECISIONS:
            raise ValueError(f"unknown arena precision {prec!r}")
    for name, a in tables.items():
        a = np.asarray(a, np.float32)
        if "f32" in precisions:
            arrays[f"{name}/f32"] = a
        if "bf16" in precisions:
            arrays[f"{name}/bf16"] = _to_bf16_bits(a)
        if "int8" in precisions:
            q, scale = quantize_int8(a)
            arrays[f"{name}/int8"] = q
            scales[name] = scale
    header = {
        "kind": ARENA_KIND,
        "arena_format": _FORMAT,
        "precisions": list(precisions),
        "scales": scales,
        "source": {"bundle": os.path.basename(bundle_path),
                   "digest": bm.get("digest"),
                   "step": int(bm.get("t") or 0),
                   "trainer": bm.get("trainer")},
        **meta,
    }
    path = arena_path(bundle_path)
    write_cache_file(path, header, arrays)
    return path


def _serving_tables(trainer) -> Tuple[dict, Dict[str, np.ndarray]]:
    st = getattr(trainer, "serving_tables", None)
    if st is None:
        raise ArenaUnsupported(
            f"{type(trainer).__name__} has no serving_tables() surface")
    return st()


# --- open / score -----------------------------------------------------------

class WeightArena:
    """One validated, mmap-opened arena. ``table views`` are read-only
    ``np.memmap``s over the shared file — gathers copy only the touched
    rows into RAM; the table itself stays in the (cross-process shared)
    page cache."""

    def __init__(self, path: str, header: dict,
                 views: Dict[str, np.ndarray]):
        self.path = path
        self.header = header
        self._views = views
        self.family = str(header.get("family"))
        self.classification = bool(header.get("classification"))
        self.mapped_bytes = int(header.get("payload_bytes") or 0)
        self.step = int((header.get("source") or {}).get("step") or 0)
        self.trainer_name = (header.get("source") or {}).get("trainer")
        self.precisions = tuple(header.get("precisions") or ())
        self._scales = {k: float(v)
                        for k, v in (header.get("scales") or {}).items()}

    # -- validation ----------------------------------------------------------
    def matches_bundle(self, bundle_path: str) -> bool:
        """Does this arena's recorded source digest match the bundle it
        sits next to? A bundle rewritten in place (or an arena copied
        from elsewhere) reads as stale and the engine falls back."""
        from .checkpoint import bundle_meta
        try:
            bm = bundle_meta(bundle_path)
        except (OSError, ValueError, KeyError):
            return False
        src = self.header.get("source") or {}
        return bool(src.get("digest")) and src["digest"] == bm.get("digest")

    # -- gathers -------------------------------------------------------------
    def _view(self, name: str, precision: str) -> np.ndarray:
        key = f"{name}/{precision}"
        v = self._views.get(key)
        if v is None:
            raise KeyError(
                f"arena {self.path} has no {key} tier "
                f"(published precisions: {self.precisions})")
        return v

    def table(self, name: str, precision: str = "f32") -> np.ndarray:
        """The FULL table at a precision tier, as float32 values. The f32
        tier returns the mmap'd view itself (read-only, zero-copy — the
        retrieval plane's full-scan scoring and index builds read pages
        shared with every other replica); quantized tiers dequantize once
        into an owned array (bounded: one table per model version)."""
        if precision == "f32":
            return self._view(name, "f32")
        if precision == "bf16":
            return _bf16_bits_to_f32(np.asarray(self._view(name, "bf16")))
        if precision == "int8":
            return np.asarray(self._view(name, "int8"), np.float32) \
                * np.float32(self._scales.get(name, 1.0))
        raise ValueError(f"unknown precision {precision!r} "
                         f"(one of {PRECISIONS})")

    def gather(self, name: str, precision: str) -> Callable:
        """``fn(index_array) -> float32 gathered values`` at the given
        precision tier — dequantization runs on the gathered slab only
        (O(touched rows)), never on the full table. Indices are clamped
        to the table like XLA's gather (a client-supplied raw integer
        feature id past dims must degrade exactly as the jitted path
        does, never crash a replica)."""
        if precision == "f32":
            tbl = self._view(name, "f32")
            hi = tbl.shape[0] - 1
            return lambda i: np.asarray(tbl[np.clip(i, 0, hi)],
                                        np.float32)
        if precision == "bf16":
            tbl = self._view(name, "bf16")
            hi = tbl.shape[0] - 1
            return lambda i: _bf16_bits_to_f32(
                np.asarray(tbl[np.clip(i, 0, hi)]))
        if precision == "int8":
            tbl = self._view(name, "int8")
            hi = tbl.shape[0] - 1
            scale = np.float32(self._scales.get(name, 1.0))
            return lambda i: np.asarray(tbl[np.clip(i, 0, hi)],
                                        np.float32) * scale
        raise ValueError(f"unknown precision {precision!r} "
                         f"(one of {PRECISIONS})")

    # -- scorers -------------------------------------------------------------
    def margin_fn(self, precision: str = "f32") -> Callable:
        """``fn(SparseBatch) -> float32 [B] margins`` over the mapped
        tables — the numpy twin of the family's jitted predict kernel."""
        w0 = float(self.header.get("w0") or 0.0)
        if self.family == "linear":
            gw = self.gather("w", precision)
            return lambda b: _linear_margin(gw, b)
        if self.family == "fm":
            gw = self.gather("w", precision)
            gV = self.gather("V", precision)
            return lambda b: _fm_margin(w0, gw, gV, b)
        if self.family == "ffm_joint":
            gT = self.gather("T", precision)
            Mr = int(self.header["Mr"])
            F, K = int(self.header["F"]), int(self.header["k"])
            return lambda b: _ffm_joint_margin(w0, gT, Mr, F, K, b)
        if self.family == "ffm_dense":
            gw = self.gather("w", precision)
            gV2 = self.gather("V2", precision)
            F = int(self.header["F"])
            return lambda b: _ffm_dense_margin(w0, gw, gV2, F, b)
        if self.family == "factor":
            raise ArenaUnsupported(
                "factor arenas score (user, item) PAIRS, not SparseBatch "
                "rows — use factor_scorer() / the retrieval plane "
                "(serve.retrieve)")
        raise ArenaUnsupported(f"unknown arena family {self.family!r}")

    def factor_scorer(self, precision: str = "f32") -> Callable:
        """``fn(user_ids, item_ids) -> float32 scores`` for the factor
        family: ``mu + P[u].Q[i] (+ bu[u] + bi[i])`` over the mapped
        tables. Broadcasts like the gathers do — a scalar user against an
        item id array is the retrieval plane's candidate-rescore shape."""
        if self.family != "factor":
            raise ArenaUnsupported(
                f"factor_scorer on family {self.family!r}")
        mu = np.float32(self.header.get("mu") or 0.0)
        gP = self.gather("P", precision)
        gQ = self.gather("Q", precision)
        gbu = self.gather("bu", precision) \
            if self.header.get("user_bias") else None
        gbi = self.gather("bi", precision) \
            if self.header.get("item_bias") else None

        def score(users, items):
            out = mu + (gP(users) * gQ(items)).sum(-1)
            if gbu is not None:
                out = out + gbu(users)
            if gbi is not None:
                out = out + gbi(items)
            return np.asarray(out, np.float32)

        return score

    def scorer(self, precision: str = "f32") -> Callable:
        """Output-space scorer (probabilities for classification) —
        mirrors the family's own margin→probability map so arena scores
        line up with the offline path's float behavior: linear uses the
        shared stable sigmoid (models/base.py sigmoid_np), the FM family
        its historical ``1/(1+exp(-phi))`` form."""
        margin = self.margin_fn(precision)
        if not self.classification:
            return lambda b: np.asarray(margin(b), np.float32)
        if self.family == "linear":
            from ..models.base import sigmoid_np
            return lambda b: np.asarray(
                sigmoid_np(np.asarray(margin(b), np.float32)), np.float32)
        return lambda b: _sigmoid_exp(margin(b))

    # -- error bounds --------------------------------------------------------
    def _weight_err(self, name: str, precision: str) -> Callable:
        """``fn(index_array) -> per-weight absolute error bound`` for the
        tier, evaluated on the gathered slab (bf16's bound is relative,
        so it needs the f32 magnitudes)."""
        trail = tuple(self._view(name, "f32").shape[1:]) \
            if f"{name}/f32" in self._views else ()
        if precision == "f32":
            return lambda i: np.zeros(
                tuple(np.asarray(i).shape) + trail, np.float32)
        if precision == "int8":
            half = np.float32(self._scales.get(name, 1.0) * 0.5)
            return lambda i: np.full(
                tuple(np.asarray(i).shape) + trail, half, np.float32)
        if precision == "bf16":
            gw = self.gather(name, "f32")
            return lambda i: np.abs(gw(i)) * np.float32(_BF16_REL)
        raise ValueError(f"unknown precision {precision!r}")

    def release(self) -> None:
        """Drop the mmap views (GC then unmaps). The engine calls this on
        close so a drained replica's leak census reads clean."""
        self._views = {}


def score_error_bound(arena: WeightArena, precision: str,
                      batch: SparseBatch) -> np.ndarray:
    """Per-row upper bound on |quantized margin − f32 margin| for this
    batch, by propagating the tier's per-weight error through the exact
    margin polynomial (docs/PERFORMANCE.md "Weight arena + quantized
    scoring" derives the algebra; tests/test_weight_arena.py enforces
    it empirically across every (B, L) bucket and family). For
    classification probabilities divide by 4 (sigmoid is 1/4-Lipschitz).
    """
    idx = np.asarray(batch.idx)
    val = np.abs(_np_val(batch))
    fam = arena.family
    if fam == "linear":
        return (arena._weight_err("w", precision)(idx) * val).sum(-1)
    if fam == "fm":
        ew = (arena._weight_err("w", precision)(idx) * val).sum(-1)
        gV = arena.gather("V", "f32")
        eV = arena._weight_err("V", precision)
        # |Δ(0.5 Σ_k s_k² − Σ xv²)|: s_k = Σ_l V_lk x_l with per-element
        # error e_lk|x_l| ⇒ |Δs_k| ≤ εs_k; |Δs_k²| ≤ 2|s_k|εs_k + εs_k²;
        # |Δxv²| ≤ 2|xv|e|x| + (e|x|)²  — triangle inequality throughout
        xv = gV(idx) * _np_val(batch)[..., None]
        exv = eV(idx) * val[..., None]
        s = xv.sum(1)
        es = exv.sum(1)
        d_s2 = (2.0 * np.abs(s) * es + es ** 2).sum(-1)
        d_x2 = (2.0 * np.abs(xv) * exv + exv ** 2).sum((1, 2))
        return ew + 0.5 * (d_s2 + d_x2)
    if fam in ("ffm_joint", "ffm_dense"):
        F = int(arena.header["F"])
        K = int(arena.header["k"])
        fld = np.asarray(batch.field) % F
        B, L = idx.shape
        if fam == "ffm_joint":
            Mr = int(arena.header["Mr"])
            rows = _row_hash_np(idx, Mr)
            slab = arena.gather("T", "f32")(rows)
            eslab = arena._weight_err("T", precision)(rows)
            Vg = slab[..., :F * K].reshape(B, L, F, K)
            eVg = eslab[..., :F * K].reshape(B, L, F, K)
            ew_l = eslab[..., F * K]
            bsel = np.arange(B)[:, None, None]
            lsel = np.arange(L)[None, :, None]
            A = Vg[bsel, lsel, fld[:, None, :], :]
            eA = eVg[bsel, lsel, fld[:, None, :], :]
        else:
            flat = idx.astype(np.int64)[:, :, None] * F + fld[:, None, :]
            A = arena.gather("V2", "f32")(flat)
            eA = arena._weight_err("V2", precision)(flat)
            ew_l = arena._weight_err("w", precision)(idx)
        ew = (ew_l * val).sum(-1)
        # |Δ(A_ij·A_ji)| ≤ Σ_k |A_ij|εA_ji + |A_ji|εA_ij + εA_ij εA_ji
        At = np.swapaxes(np.abs(A), 1, 2)
        eAt = np.swapaxes(eA, 1, 2)
        d_pair = (np.abs(A) * eAt + At * eA + eA * eAt).sum(-1)
        xx = val[:, :, None] * val[:, None, :]
        iu = np.triu(np.ones((L, L), np.float32), k=1)
        return ew + (d_pair * xx * iu[None]).sum((1, 2))
    raise ArenaUnsupported(f"no error bound for family {fam!r}")


def factor_score_error_bound(arena: WeightArena, precision: str,
                             users, items) -> np.ndarray:
    """Per-pair upper bound on |quantized factor score − f32 score| for
    ``score = mu + P[u].Q[i] (+ bu[u] + bi[i])`` — the factor family's
    instance of :func:`score_error_bound`'s derivation, propagating the
    tier's per-weight error through the exact score polynomial:

        |Δ(p.q)| ≤ Σ_k |p_k|εq_k + |q_k|εp_k + εp_k εq_k

    (triangle inequality on (p+εp).(q+εq) − p.q) plus the bias tables'
    per-weight bounds. ``users``/``items`` broadcast like the gathers, so
    a scalar user against a candidate id array yields the candidate-set
    bound the retrieval plane's ranking guardrail needs: an LSH-tier
    top-k over an int8 arena can reorder two items only where their f32
    score gap is below the summed pair bounds."""
    if arena.family != "factor":
        raise ArenaUnsupported(
            f"factor_score_error_bound on family {arena.family!r}")
    u = np.asarray(users)
    i = np.asarray(items)
    pu = arena.gather("P", "f32")(u)
    qi = arena.gather("Q", "f32")(i)
    ep = arena._weight_err("P", precision)(u)
    eq = arena._weight_err("Q", precision)(i)
    bound = (np.abs(pu) * eq + np.abs(qi) * ep + ep * eq).sum(-1)
    if arena.header.get("user_bias"):
        bound = bound + arena._weight_err("bu", precision)(u)
    if arena.header.get("item_bias"):
        bound = bound + arena._weight_err("bi", precision)(i)
    return np.asarray(bound, np.float32)


def open_arena(path: str) -> WeightArena:
    """Open + validate an arena (magic, header, full payload sha256 —
    read_cache_file's contract: a torn or bit-flipped arena can never
    feed a scorer). Raises CacheInvalid / OSError on any failure."""
    header, views = read_cache_file(path)
    if header.get("kind") != ARENA_KIND:
        raise CacheInvalid(f"{path}: not a weight arena "
                           f"(kind={header.get('kind')!r})")
    return WeightArena(path, header, views)


def try_open_arena(bundle_path: str, *, trainer_name: Optional[str] = None,
                   precision: Optional[str] = None
                   ) -> Optional[WeightArena]:
    """Open ``<bundle>.arena`` IFF it is valid FOR THIS BUNDLE, else None.

    The shared open-or-miss step of the serve engine's arena load and the
    bulk scorer's arena backend: a missing, torn, stale (digest mismatch
    after an in-place republish), foreign-trainer, or partial-precision
    sidecar is a MISS — callers route into publish_arena — never an
    exception. A mismatched arena that did open is released before
    returning so the probe itself can never leak an mmap."""
    ap = arena_path(bundle_path)
    if not os.path.exists(ap):
        return None
    try:
        a = open_arena(ap)
    except (ValueError, OSError, KeyError):
        return None
    if a.matches_bundle(bundle_path) \
            and (trainer_name is None or a.trainer_name == trainer_name) \
            and (precision is None or precision in a.precisions):
        return a
    a.release()
    return None
