"""Anomaly / change-point detection — changefinder and sst (SURVEY.md §3.11).

Reference: hivemall.anomaly.{ChangeFinderUDF,ChangeFinder1D,ChangeFinder2D,
SDAR1D,SDAR2D,SingularSpectrumTransformUDF}.

changefinder: two-stage sequentially-discounted AR (SDAR). Stage 1 scores
each point by -log p(x_t | AR model); smoothed scores feed a second SDAR
whose score is the change-point score. The reference accepts a double OR
vector stream (ChangeFinder2D/SDAR2D for the vector case).

Two forms, same math:
  - streaming classes (SDAR1D/SDAR2D, ChangeFinder/ChangeFinder2D): the
    UDF-per-row form, tiny O(k^2 d^2) host state — and the oracles the
    batched path is tested against.
  - the batched TPU path (`changefinder`): the SDAR recurrence LOOKS
    sequential, but its state splits into (a) discounted moments (mu, the
    lag covariances, sigma) — affine EMAs s_t = a_t s_{t-1} + b_t whose
    coefficients never depend on the AR solves, and (b) the Yule-Walker
    solve + prediction, which reads only the moments at t. So the whole
    series runs as three lax.associative_scan EMA passes + ONE batched
    (vmapped) Yule-Walker solve + elementwise scoring per stage — no
    per-step linear algebra, no Python loop, one device dispatch. The
    round-4 per-row Python loop ran 16k points/s; this path is bounded by
    a few passes over [T, (k+1)d^2] arrays.

sst: singular-spectrum transformation — past/future Hankel matrices at each
t; score = 1 - overlap of principal left subspaces. Two batched score
functions, mirroring the reference's svd/power-iteration pair: `-scorefunc
svd` stacks every offset's Hankel and runs one vmapped SVD; `-scorefunc
ika` runs subspace iteration on the [w, w] Hankel Grams — batched matmuls
only, ~100x faster on TPU at the same detections.
"""

from __future__ import annotations

from collections import deque
from functools import lru_cache, partial
from typing import List, Sequence, Tuple

import numpy as np

from ..obs.devprof import instrument_factory as _instrument

from ..utils.options import OptionSpec

__all__ = ["SDAR1D", "SDAR2D", "ChangeFinder", "ChangeFinder2D",
           "changefinder", "sst"]


class SDAR1D:
    """Sequentially discounted AR(k) estimator (reference SDAR1D):
    discounted mean/autocovariances + Yule-Walker solve; score is the
    negative log likelihood of x_t under the one-step prediction."""

    def __init__(self, r: float = 0.02, k: int = 3):
        self.r = r
        self.k = k
        self.mu = 0.0
        self.sigma = 1.0
        self.c = np.zeros(k + 1)
        self.hist = deque(maxlen=k)
        self.n = 0

    def update(self, x: float) -> float:
        r, k = self.r, self.k
        self.n += 1
        self.mu = (1 - r) * self.mu + r * x
        xc = x - self.mu
        hist = list(self.hist)
        for j in range(min(len(hist), k + 1)):
            lagged = hist[-1 - j] - self.mu if j < len(hist) else 0.0
            self.c[j] = (1 - r) * self.c[j] + r * xc * (
                xc if j == 0 else lagged)
        if len(hist) >= 1:
            m = min(k, len(hist))
            # Yule-Walker: Toeplitz(c[0..m-1]) a = c[1..m]
            T = np.empty((m, m))
            for i in range(m):
                for j in range(m):
                    T[i, j] = self.c[abs(i - j)]
            try:
                # per-diagonal relative ridge (floored at the absolute
                # 1e-6), matching the batched path: right after warmup the
                # system is a rank-1 outer product, and against moments
                # ~1e13 (|x| ~ 5e6 series) an absolute 1e-6 is nothing —
                # the near-singular solve returns garbage ~1e16 that the
                # batch path (relatively ridged) never produces
                rg = 1e-6 * np.maximum(np.abs(np.diag(T)), 1.0)
                a = np.linalg.solve(T + np.diag(rg), self.c[1:m + 1])
            except np.linalg.LinAlgError:
                a = np.zeros(m)
            pred = self.mu + sum(a[j] * (hist[-1 - j] - self.mu)
                                 for j in range(m))
        else:
            pred = self.mu
        err = x - pred
        self.sigma = (1 - r) * self.sigma + r * err * err
        self.hist.append(x)
        sig = max(self.sigma, 1e-12)
        return 0.5 * (np.log(2 * np.pi * sig) + err * err / sig)


class SDAR2D:
    """Vector-stream SDAR(k) (reference SDAR2D): the same discounted
    moments with [d, d] lag-covariance blocks, a block-Toeplitz
    Yule-Walker solve for the AR matrices, and a multivariate Gaussian
    NLL score (logdet + Mahalanobis). Mirrors SDAR1D's warmup exactly
    (moment update only for lags the history covers; system size grows
    min(k, len(hist)))."""

    def __init__(self, r: float = 0.02, k: int = 3, d: int = 2):
        self.r = r
        self.k = k
        self.d = d
        self.mu = np.zeros(d)
        self.sigma = np.eye(d)
        self.c = np.zeros((k + 1, d, d))
        self.hist = deque(maxlen=k)
        self.n = 0

    def update(self, x: np.ndarray) -> float:
        r, k, d = self.r, self.k, self.d
        x = np.asarray(x, np.float64).reshape(d)
        self.n += 1
        self.mu = (1 - r) * self.mu + r * x
        xc = x - self.mu
        hist = list(self.hist)
        for j in range(min(len(hist), k + 1)):
            lag = (xc if j == 0 else hist[-1 - j] - self.mu)
            self.c[j] = (1 - r) * self.c[j] + r * np.outer(xc, lag)
        m = min(k, len(hist))
        if m >= 1:
            # block-Toeplitz G[i,j] = c[|i-j|] (transposed below diag so
            # the block matrix is symmetric), solve G S = R with R block
            # i = c[i+1]^T; S block j = A_j^T
            G = np.empty((m * d, m * d))
            R = np.empty((m * d, d))
            for i in range(m):
                R[i * d:(i + 1) * d] = self.c[i + 1].T
                for j in range(m):
                    blk = self.c[abs(i - j)]
                    G[i * d:(i + 1) * d, j * d:(j + 1) * d] = (
                        blk if i <= j else blk.T)
            try:
                # per-diagonal relative ridge (same rationale as SDAR1D's
                # and the batched path's _sdar_scores ridge)
                rg = 1e-6 * np.maximum(np.abs(np.diag(G)), 1.0)
                S = np.linalg.solve(G + np.diag(rg), R)
            except np.linalg.LinAlgError:
                S = np.zeros((m * d, d))
            pred = self.mu.copy()
            for j in range(m):
                pred += S[j * d:(j + 1) * d].T @ (hist[-1 - j] - self.mu)
        else:
            pred = self.mu
        err = x - pred
        self.sigma = (1 - r) * self.sigma + r * np.outer(err, err)
        self.hist.append(x)
        # relative per-diagonal ridge, mirroring the batch path's sigma
        # ridge (1e-9 * max(diag, 1)) so the two stay score-equivalent at
        # any channel magnitude
        sig = self.sigma + np.diag(
            1e-9 * np.maximum(np.abs(np.diag(self.sigma)), 1.0))
        sign, logdet = np.linalg.slogdet(sig)
        maha = float(err @ np.linalg.solve(sig, err))
        return 0.5 * (d * np.log(2 * np.pi) + logdet + maha)


class ChangeFinder:
    """Two-stage ChangeFinder over a scalar stream (UDF-per-row semantics).

    update(x) -> (outlier_score, change_score)."""

    def __init__(self, r: float = 0.02, k: int = 3, T1: int = 7, T2: int = 7):
        self.stage1 = SDAR1D(r, k)
        self.stage2 = SDAR1D(r, k)
        self.w1 = deque(maxlen=T1)
        self.w2 = deque(maxlen=T2)

    def update(self, x: float) -> Tuple[float, float]:
        s1 = self.stage1.update(float(x))
        self.w1.append(s1)
        y = float(np.mean(self.w1))
        s2 = self.stage2.update(y)
        self.w2.append(s2)
        return s1, float(np.mean(self.w2))


class ChangeFinder2D:
    """Two-stage ChangeFinder over a vector stream (reference
    ChangeFinder2D): stage 1 is a vector SDAR2D, its smoothed NLL feeds a
    scalar stage-2 SDAR exactly like the 1D form."""

    def __init__(self, d: int, r: float = 0.02, k: int = 3,
                 T1: int = 7, T2: int = 7):
        self.stage1 = SDAR2D(r, k, d)
        self.stage2 = SDAR1D(r, k)
        self.w1 = deque(maxlen=T1)
        self.w2 = deque(maxlen=T2)

    def update(self, x) -> Tuple[float, float]:
        s1 = self.stage1.update(np.asarray(x, np.float64))
        self.w1.append(s1)
        y = float(np.mean(self.w1))
        s2 = self.stage2.update(y)
        self.w2.append(s2)
        return s1, float(np.mean(self.w2))


# --- batched TPU path --------------------------------------------------


def _ema_scan(a, b):
    """s_t = a_t * s_{t-1} + b_t with s_{-1} = 0, via associative affine
    composition (numerically stable for any per-step a_t pattern — the
    warmup steps SKIP moment updates, i.e. a_t = 1, b_t = 0)."""
    import jax

    def comp(lo, hi):
        return (hi[0] * lo[0], hi[0] * lo[1] + hi[1])

    return jax.lax.associative_scan(comp, (a, b), axis=0)[1]


def _solve_small(G, R, pd: bool = False, with_logdet: bool = False):
    """Batched solve of symmetric [T, n, n] systems by closed form for
    n <= 3 — pure elementwise VPU work. jnp.linalg.solve's batched LU
    measured 64.2 ms vs 8.9 ms at [65536, 3, 3] on v5e (7.2x), and the
    default 1D changefinder pays TWO such solves per run. n > 3 (the 2D
    stream's kd = 6 Yule-Walker) falls back to the LAPACK-style path.

    Numerical design: each system is Jacobi-equilibrated by
    D = diag(1/sqrt(|G_ii|)) — solve (D G D) y = D R, x = D y — then
    solved by an UNROLLED LDL^T factorization. Equilibration respects
    heterogeneous channel scales (a [1e12, 1e-6] diagonal becomes a
    correlation-like matrix instead of drowning the small channel) and
    keeps products inside f32 range (covariance entries ~1e13 overflowed
    an explicit 3x3 det). LDL rather than Cramer/adjugate because the
    SEQUENTIAL pivots are each individually f32-representable: a smooth
    series (ChangeFinder's stage-2 input) makes the YW matrix
    near-all-ones, whose true ridge-induced det ~1e-12 is far below the
    ~1e-7 cancellation noise of an explicit cofactor product — Cramer +
    a det floor returned coefficients ~1e5 off there, while LDL's pivots
    carry only per-factor rounding (the same reason LAPACK works in f32).

    pd=False (default): pivots keep their sign, floored at |1e-7| — the
    SDAR discounted-moment Toeplitz is INDEFINITE in general (its c[j]
    are cross-moments, not true autocovariances; a measured t=4 stage-2
    system had det(correlation) = -0.0037 with a legitimate -0.018 third
    pivot that a positive clamp turned into garbage x1e5). pd=True: the
    caller asserts PD (ridged sigma from an outer-product EMA + PD
    init), so a non-positive pivot is pure f32 cancellation noise and
    clamps POSITIVE.

    with_logdet=True (requires pd=True, n <= 3): also return
    log det(G) = sum_i log d_i + 2 sum_i log s_i computed from the SAME
    floored pivots the solve used — the caller's Gaussian NLL then pairs
    a Mahalanobis term and a logdet that assume one determinant, by
    construction rather than by parallel code.

    Known limit (documented, not defended): unpivoted LDL on an
    INDEFINITE system whose leading 2x2 block is near-singular while the
    full matrix is well-conditioned (c0 ~= c1 with c2 << c0) floors d2
    and computes x2 as a difference of ~1/1e-7-scaled terms — ~O(1)
    relative error for that system where pivoted LU is exact. Scores
    stay finite (SDAR absorbs one bad prediction into sigma), the
    pattern needs an autocorrelation shape smooth/noisy streams don't
    produce, and per-system pivoting would forfeit the closed form."""
    import jax.numpy as jnp

    n = G.shape[-1]
    if n == 1:
        # same contract as n >= 2: equilibrate, floor the (single) pivot
        # at 1e-7 — a zero 1x1 system must return a finite solve and a
        # finite logdet, not inf — and keep logdet from the SAME floored
        # pivot the solve used
        if with_logdet:
            assert pd, "with_logdet requires a PD system (log of pivots)"
        g = G[..., 0, 0]
        s2 = jnp.maximum(jnp.abs(g), 1e-30)       # Jacobi scale squared
        gn = g / s2                               # equilibrated pivot, ±1|0
        if pd:
            d1 = jnp.maximum(gn, 1e-7)
        else:
            d1 = jnp.where(jnp.abs(gn) < 1e-7,
                           jnp.where(gn < 0, -1e-7, 1e-7), gn)
        x = R / (d1 * s2)[..., None, None]
        if with_logdet:
            return x, jnp.log(d1) + jnp.log(s2)
        return x
    if n > 3:
        # LAPACK-style path on the RAW system (pivoting handles scale)
        assert not with_logdet
        return jnp.linalg.solve(G, R)
    s = jnp.sqrt(jnp.maximum(
        jnp.abs(jnp.diagonal(G, axis1=-2, axis2=-1)), 1e-30))   # [..., n]
    G = G / (s[..., :, None] * s[..., None, :])
    R = R / s[..., :, None]

    if pd:
        def _piv(dd):
            return jnp.maximum(dd, 1e-7)
    else:
        def _piv(dd):
            return jnp.where(jnp.abs(dd) < 1e-7,
                             jnp.where(dd < 0, -1e-7, 1e-7), dd)

    def _with_ld(x, pivots):
        if not with_logdet:
            return x
        assert pd, "with_logdet requires a PD system (log of pivots)"
        ld = 2.0 * jnp.log(s).sum(-1)
        for dd in pivots:
            ld = ld + jnp.log(dd)
        return x, ld

    if n == 2:
        d1 = _piv(G[..., 0, 0])
        l21 = G[..., 1, 0] / d1
        d2 = _piv(G[..., 1, 1] - l21 * l21 * d1)
        z1 = R[..., 0, :]
        z2 = R[..., 1, :] - l21[..., None] * z1
        x2 = z2 / d2[..., None]
        x1 = z1 / d1[..., None] - l21[..., None] * x2
        return _with_ld(jnp.stack([x1, x2], axis=-2) / s[..., :, None],
                        (d1, d2))

    d1 = _piv(G[..., 0, 0])
    l21 = G[..., 1, 0] / d1
    l31 = G[..., 2, 0] / d1
    d2 = _piv(G[..., 1, 1] - l21 * l21 * d1)
    l32 = (G[..., 2, 1] - l31 * l21 * d1) / d2
    d3 = _piv(G[..., 2, 2] - l31 * l31 * d1 - l32 * l32 * d2)
    z1 = R[..., 0, :]
    z2 = R[..., 1, :] - l21[..., None] * z1
    z3 = R[..., 2, :] - l31[..., None] * z1 - l32[..., None] * z2
    x3 = z3 / d3[..., None]
    x2 = z2 / d2[..., None] - l32[..., None] * x3
    x1 = (z1 / d1[..., None] - l21[..., None] * x2
          - l31[..., None] * x3)
    return _with_ld(jnp.stack([x1, x2, x3], axis=-2) / s[..., :, None],
                    (d1, d2, d3))


def _sdar_scores(x, r: float, k: int):
    """Batched SDAR over x [T, d] -> NLL scores [T] (matches the
    streaming oracles' semantics step for step).

    The per-step Yule-Walker system embeds warmup as a block-diagonal
    identity: blocks >= m_t = min(t, k) become I rows with zero rhs, so
    their coefficients solve to exactly 0 — the same AR order growth the
    oracle gets from its m x m system."""
    import jax.numpy as jnp

    T, d = x.shape
    t_idx = jnp.arange(T)

    # discounted mean (always updated)
    mu = _ema_scan(jnp.full((T, 1), 1.0 - r), r * x)             # [T, d]
    xc = x - mu

    # lagged values x_{t-1-j} and their centered forms (zeros before
    # start); j runs 0..k because c[k]'s update reads one lag further
    # back than the prediction does
    lags = jnp.stack([
        jnp.concatenate([jnp.zeros((j + 1, d), x.dtype), x[:T - j - 1]])
        for j in range(k + 1)], axis=1)                        # [T, k+1, d]
    lagc = lags - mu[:, None, :]

    # discounted lag covariances: c[0] <- xc xc^T and c[j] <- xc
    # (x_{t-1-j} - mu)^T for j>=1 — the oracle's hist[-1-j], i.e. c[j]
    # pairs the current residual with lag j+1, NOT the textbook lag j.
    # update mask: j < min(t, k)  (the oracle skips lags history can't
    # cover — skipped lags keep their previous value WITHOUT decay)
    pair = jnp.concatenate([xc[:, None, :], lagc[:, 1:]], axis=1)  # [T,k+1,d]
    terms = r * xc[:, None, :, None] * pair[:, :, None, :]       # [T,k+1,d,d]
    jm = jnp.arange(k + 1)
    upd = (jm[None, :] < jnp.minimum(t_idx, k)[:, None]).astype(x.dtype)
    a_c = jnp.where(upd[..., None, None] > 0, 1.0 - r, 1.0)
    b_c = terms * upd[..., None, None]
    c = _ema_scan(a_c, b_c)                                      # [T,k+1,d,d]

    # batched block-Toeplitz Yule-Walker with warmup embedding
    m_t = jnp.minimum(t_idx, k)                                  # [T]
    ii = jnp.arange(k)
    absd = jnp.abs(ii[:, None] - ii[None, :])                    # [k, k]
    blk = c[:, absd]                                             # [T,k,k,d,d]
    blk = jnp.where((ii[:, None] <= ii[None, :])[None, :, :, None, None],
                    blk, jnp.swapaxes(blk, -1, -2))
    act = (ii[None, :] < m_t[:, None])                           # [T, k]
    act2 = act[:, :, None] & act[:, None, :]
    eye_blk = jnp.broadcast_to(
        jnp.eye(k)[:, :, None, None] * jnp.eye(d)[None, None],
        (T, k, k, d, d))
    blk = jnp.where(act2[..., None, None], blk, eye_blk)
    G = blk.transpose(0, 1, 3, 2, 4).reshape(T, k * d, k * d)
    # ridge relative PER DIAGONAL ENTRY (floored at the oracle's absolute
    # 1e-6 so O(1)-magnitude channels match it bit-for-tolerance): right
    # after warmup the active block is a rank-1 outer product, and against
    # covariances ~1e13 (|x| ~ 5e6 series) an absolute 1e-6 is below f32
    # cancellation noise — the CPU LU's second pivot cancels to exactly 0
    # and the solve returns inf (the TPU lowering happened to survive).
    # Per-entry (not global-max) keeps a small-scale channel's ridge at
    # the absolute 1e-6 instead of drowning its variance.
    gd = jnp.abs(jnp.diagonal(G, axis1=-2, axis2=-1))            # [T, kd]
    G = G + jnp.eye(k * d) * (1e-6 * jnp.maximum(gd, 1.0))[:, :, None]
    R = jnp.where(act[..., None, None],
                  jnp.swapaxes(c[:, 1:], -1, -2),
                  0.0).reshape(T, k * d, d)
    S = _solve_small(G, R)                                       # [T, kd, d]

    # pred_t = mu_t + sum_j A_j (x_{t-1-j} - mu_t),  A_j^T = S block j
    Sb = S.reshape(T, k, d, d)
    pred = mu + jnp.einsum("tjde,tjd->te", Sb, lagc[:, :k])
    err = x - pred

    # discounted residual covariance, init I (EMA from s_{-1}=I: fold the
    # init into step 0's b)
    ee = r * err[:, :, None] * err[:, None, :]
    b0 = ee.at[0].add((1.0 - r) * jnp.eye(d))
    sigma = _ema_scan(jnp.full((T, 1, 1), 1.0 - r), b0)          # [T, d, d]

    if d == 1:
        sig = jnp.maximum(sigma[:, 0, 0], 1e-12)
        e = err[:, 0]
        return 0.5 * (jnp.log(2 * jnp.pi * sig) + e * e / sig)
    # per-diagonal relative ridge (same rationale as the YW system's)
    sd = jnp.abs(jnp.diagonal(sigma, axis1=-2, axis2=-1))        # [T, d]
    sig = sigma + jnp.eye(d) * (1e-9 * jnp.maximum(sd, 1.0))[:, :, None]
    if d <= 3:
        # one LDL factorization serves both halves of the NLL: the
        # Mahalanobis solve and the logdet come from the SAME equilibrated
        # floored pivots, so they assume one determinant by construction
        sol, logdet = _solve_small(sig, err[..., None], pd=True,
                                   with_logdet=True)
    else:
        _, logdet = jnp.linalg.slogdet(sig)
        sol = jnp.linalg.solve(sig, err[..., None])
    maha = jnp.einsum("td,td->t", err, sol[..., 0])
    return 0.5 * (d * jnp.log(2 * jnp.pi) + logdet + maha)


def _rolling_mean(s, w: int):
    """Mean over the last min(t+1, w) values (the oracle's deque mean)."""
    import jax.numpy as jnp

    T = s.shape[0]
    cs = jnp.cumsum(s)
    shifted = jnp.concatenate([jnp.zeros((w,), s.dtype), cs[:T - w]]) \
        if T > w else jnp.zeros((T,), s.dtype)
    cnt = jnp.minimum(jnp.arange(T) + 1, w).astype(s.dtype)
    return (cs - shifted[:T]) / cnt


@_instrument("changefinder", "run")
@lru_cache(maxsize=32)
def _changefinder_jit(r: float, k: int, T1: int, T2: int, d: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def run(x):
        # full padded outputs; the caller slices host-side so one compile
        # per (bucket, d) serves every series length in the bucket. The
        # two score streams come back STACKED — one device->host fetch
        # (a fetch pays a fixed latency regardless of size)
        s1 = _sdar_scores(x, r, k)
        y = _rolling_mean(s1, T1)
        s2 = _sdar_scores(y[:, None], r, k)
        cp = _rolling_mean(s2, T2)
        return jnp.stack([s1, cp])

    return run


def _bucket(n: int) -> int:
    b = 256
    while b < n:
        b <<= 1
    return b


CHANGEFINDER_SPEC = (OptionSpec("changefinder")
                     .add("r", "forget", type=float, default=0.02,
                          help="discounting rate")
                     .add("k", "order", type=float, default=3,
                          help="AR order")
                     .add("T1", "smooth1", type=int, default=7)
                     .add("T2", "smooth2", type=int, default=7)
                     .add("outlier_threshold", type=float, default=0.0)
                     .add("changepoint_threshold", type=float, default=0.0))


def changefinder(series, options: str = "") -> List[Tuple[float, float]]:
    """SQL: changefinder(x[, options]) — batch over a series of doubles OR
    of array<double> rows (the reference's ChangeFinder1D / ChangeFinder2D
    dispatch), emitting (outlier_score, changepoint_score) per element.
    Runs the fully batched scan path: one device dispatch per series."""
    import jax.numpy as jnp

    ns = CHANGEFINDER_SPEC.parse(options)
    x = np.asarray(series, np.float32)
    if x.ndim == 1:
        x = x[:, None]
    T, d = x.shape
    if T == 0:
        return []
    pad = _bucket(T)
    # memory guard: the batched path holds O(bucket * (k*d)^2) f32 for
    # the Yule-Walker systems (plus the [T, k, k, d, d] block build) —
    # fine for the scalar/small-d streams it was built for, but a wide
    # vector stream would allocate gigabytes. Route those through the
    # O(k^2 d^2)-memory streaming oracle instead (identical math).
    k = int(ns.k)
    batch_bytes = pad * ((k * d) ** 2 * 3 + (k + 1) * d * d * 4) * 4
    if batch_bytes > (256 << 20):
        if d == 1:
            cf = ChangeFinder(float(ns.r), k, int(ns.T1), int(ns.T2))
            return [cf.update(float(v[0])) for v in x]
        cf2 = ChangeFinder2D(d, float(ns.r), k, int(ns.T1), int(ns.T2))
        return [cf2.update(v) for v in x]
    xp = np.zeros((pad, d), np.float32)
    xp[:T] = x
    run = _changefinder_jit(float(ns.r), int(ns.k), int(ns.T1),
                            int(ns.T2), d)
    packed = np.asarray(run(jnp.asarray(xp)), np.float64)
    s1, cp = packed[0, :T], packed[1, :T]
    return list(zip(s1.tolist(), cp.tolist()))


SST_SPEC = (OptionSpec("sst")
            .add("w", "window", type=int, default=30,
                 help="Hankel window size")
            .add("n", "n_past", type=int, default=0,
                 help="past columns (default w)")
            .add("m", "n_current", type=int, default=0,
                 help="future columns (default w)")
            .add("g", "gap", type=int, default=0,
                 help="gap between past and future (default w/4)")
            .add("r", "components", type=int, default=3,
                 help="principal components compared")
            .add("scorefunc", type=str, default="svd",
                 choices=("svd", "ika"),
                 help="svd (exact, reference default) | ika "
                      "(power/subspace iteration on the Hankel Grams — "
                      "the reference's fast score function; batched "
                      "matmuls only, ~100x on TPU)")
            .add("threshold", type=float, default=0.0))


def _mgs(Z):
    """Batched modified Gram-Schmidt over the (small, static) last axis:
    Z [..., w, r] -> orthonormal columns. Unrolled per column — pure
    elementwise/matmul work, no LAPACK."""
    import jax.numpy as jnp

    r = Z.shape[-1]
    cols = []
    for j in range(r):
        v = Z[..., j]
        for q in cols:
            v = v - jnp.sum(q * v, axis=-1, keepdims=True) * q
        v = v / jnp.maximum(
            jnp.linalg.norm(v, axis=-1, keepdims=True), 1e-20)
        cols.append(v)
    return jnp.stack(cols, axis=-1)


def _sst_ika_scores(H_p, H_f, r: int, iters: int = 20):
    """Power/subspace-iteration SST score per offset (reference
    'ika'-style score function, SURVEY.md:265 'Hankel matrix SVD/power
    iteration'): top-r left subspaces of past/future Hankels via
    subspace iteration on the [w, w] Grams, then 1 - sigma_max of
    Up^T Uf by power iteration on the tiny [r, r] overlap. Everything
    is a batched matmul — no per-offset LAPACK calls.

    iters=20: on flat-spectrum (noise) regions the eigengap is tiny and
    12 iterations left the true-change score ~0.2 under the SVD's,
    losing the argmax to a noise point; 20 matches SVD's peak on the
    measured hard case and 32 adds nothing."""
    import jax.numpy as jnp

    def topr(H):
        A = jnp.einsum("twn,tvn->twv", H, H)          # [K, w, w] Gram
        Q = _mgs(A[..., :, :r])                        # data-aligned init
        for _ in range(iters):
            Q = _mgs(jnp.einsum("twv,tvr->twr", A, Q))
        return Q

    Up = topr(H_p)
    Uf = topr(H_f)
    M = jnp.einsum("twr,tws->trs", Up, Uf)             # [K, r, r]
    B = jnp.einsum("tsr,tsq->trq", M, M)               # M^T M
    v = jnp.ones(B.shape[:-1], B.dtype) / (r ** 0.5)   # [K, r]
    for _ in range(10):
        v = jnp.einsum("trq,tq->tr", B, v)
        v = v / jnp.maximum(jnp.linalg.norm(v, axis=-1, keepdims=True),
                            1e-20)
    smax2 = jnp.einsum("tr,trq,tq->t", v, B, v)
    return jnp.clip(1.0 - jnp.sqrt(jnp.maximum(smax2, 0.0)), 0.0, 1.0)


@_instrument("sst", "ika")
@lru_cache(maxsize=32)
def _sst_ika_jit(w: int, n: int, m: int, g: int, r: int, Tpad: int):
    """Module-cached jitted ika runner for one (geometry, bucket) — the
    same one-compile-per-config discipline as _changefinder_jit.

    Offsets are CONSECUTIVE, so every Hankel entry is a static shift of
    the series: H[k][i, j] = x[base + k + j + i]. The [Tpad-w+1, w]
    sliding-window view builds from w static slices and each Hankel
    column j is a static K-row slice of it — zero gathers (a [K, w, n]
    advanced-index gather lowered to ~2.2M scalar loads and ran 100x
    slower than the matmuls it fed)."""
    import jax
    import jax.numpy as jnp

    start = w + n - 1
    K = Tpad - g - m - start
    base_p = start - n - w + 1                     # = 0
    # future column j covers x[t+g-w+1+j : t+g+1+j] — the FIRST future
    # window ends at t+g, the first post-gap point (without the +1 it
    # ended at t+g-1, scoring a window that never looked past the gap);
    # the svd scorer below builds the same window, pinned by
    # test_sst_ika_matches_svd_detection's argmax tolerance of 1
    base_f = start + g - w + 1

    @jax.jit
    def run(xj):
        W = jnp.stack([xj[s:s + (Tpad - w + 1)] for s in range(w)],
                      axis=1)                      # W[p] = x[p:p+w]
        H_p = jnp.stack([W[base_p + j:base_p + j + K]
                         for j in range(n)], axis=2)   # [K, w, n]
        H_f = jnp.stack([W[base_f + j:base_f + j + K]
                         for j in range(m)], axis=2)   # [K, w, m]
        return _sst_ika_scores(H_p, H_f, r)

    return run


def sst(series: Sequence[float], options: str = "") -> List[float]:
    """SQL: sst(x[, options]) — singular-spectrum-transform change score
    per element (0 until enough history). Batched: every offset's past
    and future Hankel matrices process in one dispatch. `-scorefunc svd`
    (default, reference default) runs the exact vmapped SVD; `-scorefunc
    ika` runs the reference's power-iteration score function as pure
    batched matmuls (~100x on TPU — SVD lowers to per-matrix iterative
    LAPACK-style loops there)."""
    import jax
    import jax.numpy as jnp

    ns = SST_SPEC.parse(options)
    x = np.asarray(list(series), np.float32)
    w = int(ns.w)
    n = int(ns.n) or w
    m = int(ns.m) or w
    g = int(ns.g) or max(1, w // 4)
    r = int(ns.r)
    scorefunc = str(ns.scorefunc).lower()
    T = len(x)
    start = w + n - 1          # first t with a full past matrix
    need = start + g + m       # and a full future matrix
    if T <= need:
        return [0.0] * T

    ts = np.arange(start, T - g - m)
    scores = np.zeros(T, np.float32)

    if scorefunc == "ika":
        # pad to a bucket so one compile serves every series length in
        # the bucket (the jitted runner is module-cached — a per-call
        # closure re-traced each call, ~5 s of the 5.6 s wall), then
        # slice the valid offsets; padded offsets read only zeros
        Tpad = _bucket(T)
        xp = np.zeros(Tpad, np.float32)
        xp[:T] = x
        run = _sst_ika_jit(w, n, m, g, r, Tpad)
        scores[ts] = np.asarray(run(jnp.asarray(xp)))[:len(ts)]
        return scores.tolist()

    xj = jnp.asarray(x)

    def hankel(t0, cols):
        # columns j: x[t0 + j - w + 1 : t0 + j + 1]
        return jnp.stack([jax.lax.dynamic_slice(xj, (t0 + j - w + 1,), (w,))
                          for j in range(cols)], axis=1)

    @jax.jit
    def score_at(t):
        past = hankel(t - n + 1 - 1, n)       # ends at t-1... columns upto t
        fut = hankel(t + g, m)                # first column ends at t+g (the
        # first post-gap point) — the same window the ika path's base_f
        # builds, so the two score functions disagree only by iteration
        # convergence, never by alignment
        up, _, _ = jnp.linalg.svd(past, full_matrices=False)
        uf, _, _ = jnp.linalg.svd(fut, full_matrices=False)
        s = jnp.linalg.svd(up[:, :r].T @ uf[:, :r], compute_uv=False)
        return 1.0 - s[0]

    vals = jax.vmap(score_at)(jnp.asarray(ts))
    scores[ts] = np.asarray(vals)
    return scores.tolist()
