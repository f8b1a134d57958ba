"""Histogram-based decision-tree kernels — level-wise growth on TPU.

Reference (SURVEY.md §3.9, §4.5): hivemall.smile vendored DecisionTree /
RegressionTree (per-node candidate-split scans over sorted values) and the
xgboost JNI wrapper's native C++ core. The TPU rebuild replaces both with one
histogram machinery [B: "Pallas histogram kernels"]:

  1. features are quantile-binned once (uint8 codes, LightGBM-style);
  2. a tree grows LEVEL-WISE with fixed-width frontiers (2^t nodes at depth
     t): one scatter-add builds the (node, feature, bin, channel) histogram
     for the whole level, a cumulative-sum scan turns it into left/right
     split statistics, and an argmax picks each node's best (feature, bin);
  3. rows route to children with one gather+compare — no per-node recursion,
     no data-dependent control flow, everything jit-compiled with static
     shapes per level.

The same skeleton serves Gini classification (channel = class counts),
variance regression (channels w, wy, wy^2), and XGBoost-style boosting
(channels g, h) via pluggable gain/leaf functions. Trees vmap over the
ensemble axis (bootstrap weights differ per tree).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache, partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from hivemall_tpu.ops.pallas_hist import (level_histogram,
                                          level_histogram_dense,
                                          level_histogram_sorted,
                                          use_pallas_default)

__all__ = ["quantize_bins", "Tree", "build_tree_classifier",
           "build_tree_regressor", "build_tree_xgb", "predict_bins",
           "predict_bins_device",
           "predict_raw"]


def quantize_bins(X: np.ndarray, n_bins: int = 64
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """Quantile-bin features: returns (codes uint8 [n,d], edges [d, n_bins-1]).
    Code b means value <= edges[f, b] (last bin catches the rest)."""
    X = np.asarray(X, np.float32)
    n, d = X.shape
    edges = np.empty((d, n_bins - 1), np.float32)
    codes = np.empty((n, d), np.uint8)
    qs = np.linspace(0, 1, n_bins + 1)[1:-1]
    # quantile sketch on a sample (xgboost-style approx): exact quantiles
    # over 1M+ rows cost ~2 s host-side for no accuracy benefit at 64 bins
    if n > 262144:
        sample = X[np.random.default_rng(0).choice(n, 262144,
                                                   replace=False)]
    else:
        sample = X
    # one axis-0 sort + order-stat indexing replaces d np.quantile calls
    # (measured 0.14 s -> 0.03 s at 100k x 28; with searchsorted this made
    # quantize_bins ~45% of the whole fused-GBT fit wall, round 4). Edges
    # are lower order statistics, not interpolated — an equally valid
    # quantile sketch (xgboost-style approx), stored in `edges` so predict
    # bins identically.
    S = np.sort(sample, axis=0)
    order = (qs * (len(S) - 1)).astype(int)
    E = S[order, :]                          # [n_bins-1, d]
    for f in range(d):
        e = np.unique(E[:, f])
        pad = np.full(n_bins - 1, np.inf, np.float32)
        pad[:len(e)] = e
        edges[f] = pad
    # the per-column searchsorted loop measured 1.6-1.9 s of the 1M x 28
    # RF build — the C++ twin (OpenMP over columns) takes over when built;
    # inf padding keeps the binary search exact over the full edge rows
    return _bin_columns(X, edges), edges


def _bin_columns(X: np.ndarray, edges: np.ndarray) -> np.ndarray:
    """Code each column against its FULL inf-padded edge row (NaN sorts
    last -> n_edges). The ONE binning rule, shared by fit (quantize_bins)
    and raw predict (bin_raw) so their NaN routing can't diverge."""
    d = X.shape[1]
    from hivemall_tpu.utils.native import bin_columns_native
    ne = np.full(d, edges.shape[1], np.int32)
    native = bin_columns_native(np.ascontiguousarray(X), edges, ne)
    if native is not NotImplemented:
        return native
    codes = np.empty(X.shape, np.uint8)
    for f in range(d):
        codes[:, f] = np.searchsorted(edges[f], X[:, f],
                                      side="left").astype(np.uint8)
    return codes


@dataclass
class Tree:
    """Complete-binary-layout tree: node i's children are 2i+1 / 2i+2."""
    feat: np.ndarray        # int32 [Nn], split feature (-1 for leaf)
    thr: np.ndarray         # uint8 [Nn], split bin (go right if code > thr)
    value: np.ndarray       # f32 [Nn, C] leaf payload (class counts / value)
    edges: np.ndarray       # f32 [d, B-1] bin edges for raw-value predict

    @property
    def depth(self) -> int:
        # feat is [E, Nn]; Nn = 2^(depth+1) - 1
        return int(np.log2(self.feat.shape[-1] + 1)) - 1


def _gini_gain(left, right, parent, min_leaf):
    """Weighted Gini impurity decrease. stats channels = class counts."""
    def wgini(c):
        n = c.sum(-1)
        sq = (c * c).sum(-1)
        return n - sq / jnp.maximum(n, 1e-12)      # n * gini(c)
    nl = left.sum(-1)
    nr = right.sum(-1)
    gain = wgini(parent)[:, None, None] - wgini(left) - wgini(right)
    ok = (nl >= min_leaf) & (nr >= min_leaf)
    return jnp.where(ok, gain, -jnp.inf)


def _var_gain(left, right, parent, min_leaf):
    """SSE decrease. stats channels = (w, wy, wy^2)."""
    def sse(s):
        w, wy, wy2 = s[..., 0], s[..., 1], s[..., 2]
        return wy2 - wy * wy / jnp.maximum(w, 1e-12)
    ok = (left[..., 0] >= min_leaf) & (right[..., 0] >= min_leaf)
    gain = sse(parent)[:, None, None] - sse(left) - sse(right)
    return jnp.where(ok, gain, -jnp.inf)


def _xgb_gain(lam):
    def gain(left, right, parent, min_leaf):
        """stats channels = (g, h, w). score = G^2/(H+lam)."""
        def score(s):
            return s[..., 0] ** 2 / (s[..., 1] + lam)
        ok = (left[..., 2] >= min_leaf) & (right[..., 2] >= min_leaf)
        g = score(left) + score(right) - score(parent)[:, None, None]
        return jnp.where(ok, g, -jnp.inf)
    return gain


def _xgb_task(lam):
    """(gain, leaf, count) closures for the xgb builder task — shared by
    the per-tree builder cache and the fused boosting loop so the
    -G/(H+lam) leaf policy lives in exactly one place."""
    def xleaf(parent):
        val = -parent[..., 0] / (parent[..., 1] + lam)
        return jnp.stack([val, parent[..., 1], parent[..., 2]], axis=-1)
    return _xgb_gain(lam), xleaf, (lambda s: s[..., 2])


def colsample_mtry(colsample: float, d: int) -> int:
    """XGBoost -colsample_bytree fraction -> the builder's mtry count
    (0 = all features)."""
    return max(1, int(round(colsample * d))) if colsample < 1.0 else 0


def _make_builder(n_channels: int, stat_fn: Callable, gain_fn: Callable,
                  leaf_fn: Callable, count_fn: Callable, depth: int,
                  n_bins: int, mtry: int, min_split: float, min_leaf: float,
                  min_gain: float, use_pallas: bool = False,
                  hist_fast: bool = False, return_nodes: bool = False):
    """Single-tree level-wise builder; vmap over (w, rng) for an ensemble.

    bins: uint8 [n, d]; aux: per-row stat payload (labels / grads);
    w: [n] sample weights (bootstrap counts; 0 = out-of-bag).

    ``return_nodes=True`` also returns each row's final node id — the
    boosting loop reads the new tree's leaf value per row straight from it
    (value[node]), so no separate predict pass re-routes the rows.
    """

    def build(bins, aux, w, rng):
        n, d = bins.shape
        Nn = 2 ** (depth + 1) - 1
        if use_pallas:
            # dense-channel kernel input: transposed, padded bin codes —
            # invariant across levels (and across vmapped trees)
            np_ = -(-n // 1024) * 1024
            dp = -(-d // 8) * 8
            bins_t = jnp.pad(bins.astype(jnp.int32),
                             ((0, np_ - n), (0, dp - d)),
                             constant_values=-1).T
        feat = jnp.full(Nn, -1, jnp.int32)
        thr = jnp.zeros(Nn, jnp.uint8)
        value = jnp.zeros((Nn, n_channels), jnp.float32)
        settled = jnp.zeros(Nn, bool)           # node finished (is a leaf)
        node = jnp.zeros(n, jnp.int32)          # row -> current node id
        stats = stat_fn(aux)                    # [n, S] per-row channels
        ws = stats * w[:, None]                 # weighted channels

        for t in range(depth + 1):
            M = 2 ** t
            base = M - 1
            local = node - base
            # rows at settled nodes never route deeper, so their node id
            # stays behind the frontier and local < 0 already excludes
            # them — no per-row settled[] gather needed (per-row gathers
            # at ~26 ns each were the build's dominant cost, round 3)
            active = (local >= 0) & (local < M)
            # ---- histogram: one pass for the whole level ----
            loc = jnp.where(active, local, 0)
            if use_pallas:
                # dense-channel MXU kernel (ops/pallas_hist.py): node x
                # stat channels ride the matmul lane axis — no sorting,
                # no spill, no per-row index ops (round 3; the round-2
                # flat/sorted kernels remain for tests/fallback)
                loc_m = jnp.where(active, local, -1)
                hist = level_histogram_dense(bins_t, loc_m, ws, M,
                                             n_bins,
                                             fast=hist_fast)[:, :d]
            else:
                # CPU fallback: flat scatter-add ((local*d + f)*B + bin)
                fidx = (loc[:, None] * d + jnp.arange(d)[None, :]) * n_bins \
                    + bins.astype(jnp.int32)                   # [n, d]
                contrib = jnp.where(active[:, None, None],
                                    ws[:, None, :], 0.0)
                contrib = jnp.broadcast_to(contrib, (n, d, n_channels))
                hist = jnp.zeros((M * d * n_bins, n_channels), jnp.float32)
                hist = hist.at[fidx.ravel()].add(
                    contrib.reshape(n * d, n_channels))
                hist = hist.reshape(M, d, n_bins, n_channels)
            # ---- split statistics ----
            parent = hist.sum(2).max(1)  # [M, S] (identical across f; max ok)
            cum = jnp.cumsum(hist, axis=2)                     # left stats
            left = cum[:, :, :-1, :]                           # thr bin b
            right = parent[:, None, None, :] - left
            gains = gain_fn(left, right, parent, min_leaf)     # [M,d,B-1]
            if t == depth:
                best_gain = jnp.full(M, -jnp.inf)
                bf = jnp.zeros(M, jnp.int32)
                bb = jnp.zeros(M, jnp.uint8)
            else:
                if mtry and mtry < d:
                    rng, sub = jax.random.split(rng)
                    # per-node random feature subset (smile's -vars / mtry)
                    scores = jax.random.uniform(sub, (M, d))
                    kth = jnp.sort(scores, axis=1)[:, mtry - 1][:, None]
                    mask = scores <= kth
                    gains = jnp.where(mask[:, :, None], gains, -jnp.inf)
                flat_g = gains.reshape(M, -1)
                arg = jnp.argmax(flat_g, axis=1)
                best_gain = jnp.take_along_axis(flat_g, arg[:, None],
                                                axis=1)[:, 0]
                bf = (arg // (n_bins - 1)).astype(jnp.int32)
                bb = (arg % (n_bins - 1)).astype(jnp.uint8)
            cnt = count_fn(parent)
            # leaf decision per frontier node
            do_split = (best_gain > min_gain) & (cnt >= min_split)
            ids = base + jnp.arange(M)
            feat = feat.at[ids].set(jnp.where(do_split, bf, -1))
            thr = thr.at[ids].set(jnp.where(do_split, bb, 0))
            value = value.at[ids].set(leaf_fn(parent))
            newly_settled = ~do_split & ~settled[ids]
            settled = settled.at[ids].set(settled[ids] | ~do_split)
            # ---- route rows ----
            # per-row (do_split, bf, bb) lookups as ONE-HOT MATVECS, not
            # gathers: a [n]-indexed gather costs ~26 ns/row regardless of
            # table size (16 trees x 9 levels x n of them dominated the 1M
            # build), while onehot(loc) @ vals is n*M exact-in-bf16 MACs on
            # the MXU. bf16 represents integers exactly only up to 256, so
            # the matvec decode is used only when every carried value fits
            # (feature ids < d <= 256, bin ids < n_bins <= 256); wider
            # configs take the exact gather path.
            if d <= 256 and n_bins <= 256:
                vals = jnp.stack([do_split.astype(jnp.float32),
                                  bf.astype(jnp.float32),
                                  bb.astype(jnp.float32)], 1)   # [M, 3]
                ohn = (loc[:, None]
                       == jnp.arange(M, dtype=jnp.int32)[None, :])
                out3 = jax.lax.dot_general(
                    ohn.astype(jnp.bfloat16), vals.astype(jnp.bfloat16),
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)          # [n, 3]
                split_here = active & (out3[:, 0] > 0.5)
                fsel = out3[:, 1].astype(jnp.int32)
                bsel = out3[:, 2]
            else:
                sel = jnp.stack([do_split.astype(jnp.float32),
                                 bf.astype(jnp.float32),
                                 bb.astype(jnp.float32)], 1)[loc]  # [n, 3]
                split_here = active & (sel[:, 0] > 0.5)
                fsel = sel[:, 1].astype(jnp.int32)
                bsel = sel[:, 2]
            ohf = fsel[:, None] == jnp.arange(d, dtype=jnp.int32)[None, :]
            bval = jnp.where(ohf, bins, jnp.uint8(0)).max(1)
            go_right = bval.astype(jnp.float32) > bsel
            node = jnp.where(split_here,
                             2 * node + 1 + go_right.astype(jnp.int32),
                             node)
        if return_nodes:
            return feat, thr, value, node
        return feat, thr, value

    return build


# --- per-task front ends (jitted builders cached per config) ---------------

def _reg_leaf(parent):     # mean in channel 0 slot; keep stats for ensembling
    mean = parent[..., 1] / jnp.maximum(parent[..., 0], 1e-12)
    return jnp.stack([mean, parent[..., 0], parent[..., 2]], axis=-1)


def make_forest_builder_sharded(build, mesh):
    """Ensemble parallelism (SURVEY.md §3.17 row 4): per-device bootstrap
    tree builds over a dp mesh. Trees are embarrassingly parallel — the
    tree axis (weights, rng keys) shards over 'dp', bins replicate, and
    shard_map runs each device's sub-forest with the Pallas histogram
    kernel on local shapes (pallas_call cannot be GSPMD-partitioned, so
    the explicit shard_map IS the supported multi-chip path). The vote
    gather happens on the host over the [E]-sharded outputs."""
    import jax
    from jax.sharding import PartitionSpec as P
    return jax.jit(jax.shard_map(
        build, mesh=mesh,
        in_specs=(P(), P(), P("dp"), P("dp")),
        out_specs=(P("dp"), P("dp"), P("dp")),
        check_vma=False))


@lru_cache(maxsize=128)
def _cached_builder(task: str, n_channels: int, depth: int, n_bins: int,
                    mtry: int, min_split: float, min_leaf: float,
                    lam: float, vmapped: bool, use_pallas: bool,
                    return_nodes: bool = False):
    if task == "gini":
        gain, leaf, count = _gini_gain, (lambda p: p), (lambda s: s.sum(-1))
    elif task == "var":
        gain, leaf, count = _var_gain, _reg_leaf, (lambda s: s[..., 0])
    elif task == "xgb":
        gain, leaf, count = _xgb_task(lam)
    else:
        raise ValueError(task)
    # classification stat channels are class-indicator x bootstrap-count —
    # small integers, exact in bf16 — so the histogram matmul can run
    # single-pass (fast) without rounding anything; var/xgb channels carry
    # arbitrary floats and keep the f32-equivalent passes
    build = _make_builder(n_channels, lambda aux: aux, gain, leaf, count,
                          depth, n_bins, mtry, min_split, min_leaf,
                          min_gain=1e-7, use_pallas=use_pallas,
                          hist_fast=(task == "gini"),
                          return_nodes=return_nodes)
    if vmapped:
        build = jax.vmap(build, in_axes=(None, None, 0, 0))
    return jax.jit(build)


def build_tree_classifier(bins: np.ndarray, labels: np.ndarray,
                          weights: np.ndarray, edges: np.ndarray,
                          n_classes: int, *, depth: int = 8,
                          n_bins: int = 64, mtry: int = 0,
                          min_split: float = 2.0, min_leaf: float = 1.0,
                          seed: int = 42, n_trees: int = 1,
                          mesh=None, return_nodes: bool = False):
    """Gini trees; weights [E, n] give per-tree bootstrap counts. With
    ``mesh`` (a dp-axis jax Mesh), trees shard over devices.

    ``return_nodes=True`` (single-device only) additionally returns the
    [E, n] DEVICE array of each row's final node id — the builder routes
    every row (bootstrap weight plays no part in routing), so OOB error
    needs no separate predict pass over the forest."""
    onehot = jax.nn.one_hot(labels, n_classes)
    build = _cached_builder("gini", n_classes, depth, n_bins, mtry,
                            float(min_split), float(min_leaf), 0.0, True,
                            use_pallas_default(),
                            return_nodes=return_nodes and mesh is None)
    keys = jax.random.split(jax.random.PRNGKey(seed), n_trees)
    if mesh is not None:
        dp = mesh.shape["dp"]
        if n_trees % dp:
            raise ValueError(f"-trees {n_trees} must divide by dp={dp}")
        build = make_forest_builder_sharded(build.__wrapped__
                                            if hasattr(build, "__wrapped__")
                                            else build, mesh)
    out = build(jnp.asarray(bins), onehot, jnp.asarray(weights), keys)
    if return_nodes and mesh is None:
        f, t, v, node = out
        # v stays DEVICE-resident for the OOB lookup (re-uploading the
        # just-fetched host copy would re-pay the round trip _fetch_tree
        # exists to avoid)
        return _fetch_tree(f, t, v, edges), node, v
    f, t, v = out
    tree = _fetch_tree(f, t, v, edges)
    return (tree, None, None) if return_nodes else tree


def _fetch_tree(f, t, v, edges) -> Tree:
    """ONE device->host fetch for (feat, thr, value): a fetch pays a
    fixed latency regardless of size, so three separate np.asarray calls
    taxed every forest fit ~2 extra round trips."""
    E, Nn = f.shape
    packed = np.asarray(jnp.concatenate(
        [f.astype(jnp.float32).reshape(E, Nn, 1),
         t.astype(jnp.float32).reshape(E, Nn, 1),
         v.astype(jnp.float32)], axis=-1))
    return Tree(packed[..., 0].astype(np.int32),
                packed[..., 1].astype(np.uint8),
                np.ascontiguousarray(packed[..., 2:]), edges)


def build_tree_regressor(bins: np.ndarray, targets: np.ndarray,
                         weights: np.ndarray, edges: np.ndarray, *,
                         depth: int = 8, n_bins: int = 64, mtry: int = 0,
                         min_split: float = 2.0, min_leaf: float = 1.0,
                         seed: int = 42, n_trees: int = 1,
                         return_nodes: bool = False):
    """Variance-split trees; leaf value = weighted mean target."""
    y = jnp.asarray(targets, jnp.float32)
    aux = jnp.stack([jnp.ones_like(y), y, y * y], axis=1)
    build = _cached_builder("var", 3, depth, n_bins, mtry, float(min_split),
                            float(min_leaf), 0.0, True, use_pallas_default(),
                            return_nodes=return_nodes)
    keys = jax.random.split(jax.random.PRNGKey(seed), n_trees)
    out = build(jnp.asarray(bins), aux, jnp.asarray(weights), keys)
    if return_nodes:
        f, t, v, node = out
        return _fetch_tree(f, t, v, edges), node, v
    f, t, v = out
    return _fetch_tree(f, t, v, edges)


def build_tree_xgb(bins: np.ndarray, grads: np.ndarray, hess: np.ndarray,
                   edges: np.ndarray, *, depth: int = 6, n_bins: int = 64,
                   lam: float = 1.0, min_split: float = 2.0,
                   min_leaf: float = 1.0, colsample: float = 1.0,
                   seed: int = 42) -> Tree:
    """One boosting tree on (g, h); leaf value = -G/(H+lam) in channel 0."""
    g = jnp.asarray(grads, jnp.float32)
    h = jnp.asarray(hess, jnp.float32)
    aux = jnp.stack([g, h, jnp.ones_like(g)], axis=1)
    d = bins.shape[1]
    mtry = colsample_mtry(colsample, d)
    build = _cached_builder("xgb", 3, depth, n_bins, mtry, float(min_split),
                            float(min_leaf), float(lam), False,
                            use_pallas_default())
    f, t, v = build(jnp.asarray(bins), aux,
                    jnp.ones(bins.shape[0], jnp.float32),
                    jax.random.PRNGKey(seed))
    return Tree(np.asarray(f)[None], np.asarray(t)[None],
                np.asarray(v)[None], edges)


@lru_cache(maxsize=64)
def boost_loop_xgb(objective: str, n_rounds: int, depth: int, n_bins: int,
                   mtry: int, min_child_weight: float, lam: float,
                   eta: float, subsample: float, use_pallas: bool,
                   n_class: int = 0):
    """The WHOLE boosting run as one jitted lax.scan over rounds.

    Round 3 measured GBT at ~26k rows/s while RF built trees 10x bigger at
    117k rows/s: the boosting chain was round-SERIAL, paying a host-synced
    dispatch several times per round. Here a
    round is one scan iteration — grad/hess from the carried margin, the
    level-wise build, and the margin update from the builder's own row
    node ids (value[node, 0]; no separate predict re-walk) — so R rounds
    cost ONE dispatch. Matches the reference XGBoostUDTF training loop
    semantics (SURVEY.md §3.9) with jax.random round keys for subsample.

    With ``n_class > 0`` (multi:softmax) each round vmaps the builder over
    the per-class (g, h) stacks, carrying a [n, C] margin — the one-vs-rest
    round structure XGBoost uses for softmax.
    """
    gain, leaf, count = _xgb_task(lam)
    build = _make_builder(3, lambda aux: aux, gain, leaf, count,
                          depth, n_bins, mtry,
                          2.0, min_child_weight, 1e-7,
                          use_pallas=use_pallas, return_nodes=True)

    def grad_hess(y, margin):
        if objective == "binary:logistic":
            p = 1.0 / (1.0 + jnp.exp(-margin))
            return p - y, p * (1 - p)
        if objective == "reg:squarederror":
            return margin - y, jnp.ones_like(margin)
        if objective == "multi:softmax":
            e = jnp.exp(margin - margin.max(1, keepdims=True))
            p = e / e.sum(1, keepdims=True)
            onehot = jax.nn.one_hot(y.astype(jnp.int32), n_class)
            return p - onehot, jnp.maximum(p * (1 - p), 1e-6)
        raise ValueError(f"unknown objective {objective!r}")

    def subsampled(g, h, key):
        if subsample >= 1.0:
            return g, h
        keep = jax.random.bernoulli(key, subsample, (g.shape[0],))
        km = keep.astype(jnp.float32)
        km = km if g.ndim == 1 else km[:, None]
        return g * km, h * km

    def loop(bins, y, base_score, key):
        n = bins.shape[0]
        ones = jnp.ones(n, jnp.float32)

        def round_fn(margin, key_r):
            g, h = grad_hess(y, margin)
            g, h = subsampled(g, h, jax.random.fold_in(key_r, 1))
            if n_class:
                aux = jnp.stack([g, h, jnp.ones_like(g)], -1)   # [n, C, 3]
                aux = jnp.swapaxes(aux, 0, 1)                   # [C, n, 3]
                keys = jax.random.split(key_r, n_class)
                f, t, v, node = jax.vmap(
                    build, in_axes=(None, 0, None, 0))(bins, aux, ones,
                                                       keys)
                # [C, n] leaf values -> margin [n, C]
                leaf = jnp.take_along_axis(v[..., 0], node,
                                           axis=1)              # [C, n]
                margin = margin + eta * leaf.T
            else:
                aux = jnp.stack([g, h, jnp.ones_like(g)], 1)
                f, t, v, node = build(bins, aux, ones, key_r)
                margin = margin + eta * v[node, 0]
            return margin, (f, t, v)

        keys = jax.random.split(key, n_rounds)
        m0 = (jnp.full((n, n_class), base_score, jnp.float32) if n_class
              else jnp.full(n, base_score, jnp.float32))
        margin, (fs, ts, vs) = jax.lax.scan(round_fn, m0, keys)
        # ONE packed f32 tensor [..., Nn, 5] = (value[3], feat, thr): one
        # d2h fetch instead of three, each of which pays a fixed latency
        # regardless of size — feat (small ints) and thr (uint8) are
        # exact in f32
        packed = jnp.concatenate(
            [vs, fs.astype(jnp.float32)[..., None],
             ts.astype(jnp.float32)[..., None]], axis=-1)
        return packed, margin

    return jax.jit(loop)


# --- prediction: vectorized gather-walk (the StackMachine VM rebuild) ------

def _walk(feat, thr, value, bins, depth):
    n = bins.shape[0]
    node = jnp.zeros(n, jnp.int32)

    def body(_, node):
        f = feat[node]
        is_leaf = f < 0
        fsel = jnp.maximum(f, 0)
        go_right = bins[jnp.arange(n), fsel] > thr[node]
        nxt = 2 * node + 1 + go_right.astype(jnp.int32)
        return jnp.where(is_leaf, node, nxt)

    node = jax.lax.fori_loop(0, depth, body, node)
    return value[node]


@partial(jax.jit, static_argnums=(4,))
def _walk_ensemble(feat, thr, value, bins, depth):
    """vmapped gather-walk: all E trees in ONE device dispatch."""
    return jax.vmap(_walk, in_axes=(0, 0, 0, None, None)
                    )(feat, thr, value, bins, depth)


def _sweep_one(feat, thr, value, bins, depth):
    """Gather-free predict for one tree: per-level 0/1 membership sweep.

    The gather walk pays 3 per-row index ops per level (~26 ns each on
    v5e — 10 s for 1M rows x 16 trees x depth 8). Here membership mass
    flows down level by level with pure elementwise ops on [n, 2^t]
    slabs: P[r, nd] (the node's predicate) comes from ONE exact-in-bf16
    one-hot matmul, leaves emit value through a tiny [2^t, C] matmul, and
    nothing indexes per row.
    """
    n, d = bins.shape
    Nn = feat.shape[0]
    C = value.shape[1]
    ohf = jax.nn.one_hot(jnp.maximum(feat, 0), d, dtype=jnp.bfloat16)
    proj = jax.lax.dot_general(
        bins.astype(jnp.bfloat16), ohf,
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)          # [n, Nn] bin values
    P = (proj > thr[None, :].astype(jnp.float32)).astype(jnp.float32)
    is_leaf = (feat < 0).astype(jnp.float32)
    out = jnp.zeros((n, C), jnp.float32)
    match = jnp.ones((n, 1), jnp.float32)
    # `depth` here is the LEVEL COUNT (callers pass tree.depth + 1, the
    # same convention as _walk's routing-step count)
    for t in range(depth):
        base, M = 2 ** t - 1, 2 ** t
        leaf_t = is_leaf[base:base + M]
        # depth-t frontier: emit settled leaves (the deepest level is all
        # leaves by construction: feat stays -1 there)
        lv = value[base:base + M] * leaf_t[:, None]
        out = out + jax.lax.dot_general(
            match, lv, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)
        if t == depth - 1:
            break
        keep = match * (1.0 - leaf_t)[None, :]
        pt = P[:, base:base + M]
        left, right = keep * (1.0 - pt), keep * pt
        match = jnp.stack([left, right], 2).reshape(n, 2 * M)
    return out


@partial(jax.jit, static_argnums=(4,))
def _sweep_ensemble(feat, thr, value, bins, depth):
    return jax.vmap(_sweep_one, in_axes=(0, 0, 0, None, None)
                    )(feat, thr, value, bins, depth)


def predict_bins_device(tree: Tree, bins) -> jnp.ndarray:
    """Device-resident predict (no host sync) — the boosting round loop
    uses this so the margin chain never leaves the chip. Uses the
    gather-free level sweep up to depth 9 (cost grows with 2^depth slabs),
    row-chunked so the [E, chunk, Nn] predicate slab stays ~1 GB; deeper
    trees fall back to the gather walk."""
    depth = tree.depth + 1
    f = jnp.asarray(tree.feat)
    t = jnp.asarray(tree.thr)
    v = jnp.asarray(tree.value)
    bins = jnp.asarray(bins)
    if depth > 9:
        return _walk_ensemble(f, t, v, bins, depth)
    n = bins.shape[0]
    chunk = 32768
    if n <= chunk:
        return _sweep_ensemble(f, t, v, bins, depth)
    outs = [_sweep_ensemble(f, t, v, bins[s:s + chunk], depth)
            for s in range(0, n, chunk)]
    return jnp.concatenate(outs, axis=1)


def predict_bins(tree: Tree, bins: np.ndarray) -> np.ndarray:
    """Predict leaf payload per row for every tree: returns [E, n, C].
    The reference's per-row StackMachine opcode interpreter (SURVEY.md §3.9
    row 3) becomes this data-parallel gather walk, vmapped over the
    ensemble (one device call for the whole forest, not one per tree)."""
    return np.asarray(predict_bins_device(tree, bins))


def bin_raw(X: np.ndarray, edges: np.ndarray) -> np.ndarray:
    """Quantize raw features with a trained tree's edges.

    Searches the FULL inf-padded edge row — the same rule quantize_bins /
    bin_columns_native apply at fit time — so NaN codes as n_bins-1 on both
    sides even when duplicate quantile edges shorten the finite edge list
    (stripping non-finite edges here coded NaN as len(finite_edges), which
    silently routed missing values to a different branch at predict time)."""
    X = np.asarray(X, np.float32)
    edges = np.asarray(edges, np.float32)
    return _bin_columns(X, edges)


def predict_raw(tree: Tree, X: np.ndarray) -> np.ndarray:
    return predict_bins(tree, bin_raw(X, tree.edges))
