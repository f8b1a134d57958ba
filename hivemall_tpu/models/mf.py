"""Matrix factorization — train_mf_sgd / train_mf_adagrad / train_bprmf
(BASELINE config #3), on the normal path: ``catalog ->
LearnerBase.fit_stream -> _dispatch_mega``.

Reference (SURVEY.md §3.7): hivemall.mf.OnlineMatrixFactorizationUDTF (base:
streaming (user, item, rating) SGD over rank-k P/Q tables with biases and
global mean -mu), MatrixFactorizationSGDUDTF / MatrixFactorizationAdaGradUDTF,
BPRMatrixFactorizationUDTF (implicit feedback (u, pos, neg) ranking), and the
MFPredictUDF / BPRMFPredictUDF scorers.

What streams in is a batch of ratings: ``SparseBatch.idx`` ``[B, 2]``
(user, item), ``label`` the rating (BPR: ``idx`` ``[B, 3]`` (user, pos,
neg)). A warehouse table of scalar columns streams through
``io.arrow.ParquetStream(path, id_cols=("user", "item"),
label_col="rating")``; ``process`` / ``close`` and ``fit(users, items,
ratings)`` feed the same step.

TPU shape: two tables, users ``U`` and items ``I``, a row stored 128 lanes
(``row_lanes``): the k factors, then the bias, then zeros that stay zero
(their gradient is a product with them). The model is ``P[U,k]``, ``bu[U]``,
``Q[I,k]``, ``bi[I]`` (``serving_tables``). One step a minibatch: the
squared error of ``mu + p_u.q_i + b_u + b_i`` and L2 ``-lambda`` on the rows
the batch reads, per occurrence; the gradients of a row's duplicates are
summed and the optimizer (SGD, or per-element AdaGrad ``w -= eta0 * g /
(sqrt(gg) + 1e-6)``) sees the sum. Each table goes through ops/fm.py's
distinct-row machinery on its own: ``rank_rows`` -> ``gather_rows`` ->
forward and backward -> ``rows_update``, so a step reads and writes the
rows its batch touched and no others; the user table's capacity comes from
``tail_cap``, the item table's (small against the batch) from
``whole_table_cap``. Under ``-mesh`` both take the dense tail, GSPMD's cut
of it. ``train_bprmf`` keeps a dense step over the same two tables.

What the step counts about itself (folded with the loss, the registry's
``train`` section): ``mf_user_rows`` / ``mf_item_rows``, each table's
distinct rows (0 for a step that did not rank them), and the tail's
counters, a step counted distinct only where both tables took that tail.
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..io.sparse import SparseBatch
from ..obs.devprof import instrument_factory as _instrument
from ..ops.fm import (gather_rows, rank_rows, rows_update, tail_cap,
                      whole_table_cap)
from ..ops.optimizers import make_optimizer_cached
from ..ops.scan import scannable
from ..utils.options import OptionSpec
from .base import LearnerBase, learner_option_spec

__all__ = ["MFTrainer", "MFAdaGradTrainer", "BPRMFTrainer", "mf_predict",
           "bprmf_predict", "row_lanes"]

LANES = 128
TABLES = ("U", "I")


def row_lanes(k: int) -> int:
    """Lanes a table row is stored with: the k factors and the bias,
    rounded up to whole 128-lane tiles (the row kernel's rows,
    ops/rows_pallas.py)."""
    return -(-(int(k) + 1) // LANES) * LANES


def _mf_spec(name: str) -> OptionSpec:
    s = OptionSpec(name)
    s.add("factors", "factor", type=int, default=10, help="rank k")
    s.add("mu", "mean_rating", type=float, default=0.0, help="global mean")
    s.add("eta0", "eta", type=float, default=0.01, help="learning rate")
    s.add("lambda", type=float, default=0.03, help="L2 regularization")
    s.add("users", "max_users", type=int, default=1 << 20,
          help="user table size")
    s.add("items", "max_items", type=int, default=1 << 20,
          help="item table size")
    s.add("sigma", type=float, default=0.1, help="factor init stddev")
    s.flag("disable_bias", help="drop user/item bias terms")
    s.flag("halffloat", help="bf16 factor tables (the bias lane too)")
    return s


# -- the steps ---------------------------------------------------------------

def _lanes_upto(W: int, n: int):
    return jnp.arange(W) < n


def _tail_caps(n: int, params, opt_state, optimizer, distinct: bool):
    """Each table's ranking capacity (static): 0 for the dense tail."""
    caps = {}
    for name in TABLES:
        R, W = params[name].shape
        if not distinct:
            caps[name] = 0
            continue
        leaves = jax.tree_util.tree_leaves(opt_state[name])
        itemsize = min(a.dtype.itemsize for a in (params[name], *leaves))
        caps[name] = (tail_cap(n, R, W, itemsize)
                      or whole_table_cap(n, R, W))
    return caps


def _both_tables(stats: Dict[str, dict], compact: Dict[str, jax.Array]):
    """TAIL_STATS of a step over two tables from each table's own: a
    branch counted only where both took it, the distinct rows summed;
    and each table's distinct rows under its own name."""
    u, i = stats["U"], stats["I"]
    both = {k: jnp.minimum(u[k], i[k])
            for k in ("tail_distinct_steps", "scatter_compact_steps")}
    return {**both, "tail_dense_steps": 1 - both["tail_distinct_steps"],
            "distinct_rows": u["distinct_rows"] + i["distinct_rows"],
            "gather_compact_steps": jnp.minimum(compact["U"], compact["I"]),
            "mf_user_rows": u["distinct_rows"],
            "mf_item_rows": i["distinct_rows"]}


@_instrument("mf", "step")
@lru_cache(maxsize=64)
def _step_cached(k: int, mu: float, lam: float, use_bias: bool, optimizer,
                 distinct: bool):
    """The MF step, `ops.scan`'s contract: `(params, opt_state, t, idx,
    val, label, mask) -> (params, opt_state, loss, stats)` with `idx`
    [B, 2] (user, item) and `label` the ratings. Cached on its scalars,
    so trainers of one configuration share one compile."""

    def batch_loss(pu, qi, r, mask):
        W = pu.shape[-1]
        pred = mu + jnp.where(_lanes_upto(W, k), pu * qi, 0.0).sum(-1)
        if use_bias:
            pred = pred + pu[:, k] + qi[:, k]
        err = (r - pred) * mask
        read = _lanes_upto(W, k + use_bias)
        reg = (mask[:, None] * jnp.where(read, pu * pu + qi * qi, 0.0)).sum()
        return 0.5 * (err * err).sum() + lam * reg

    def core(params, opt_state, t, idx, val, label, mask):
        rows = {"U": idx[:, 0], "I": idx[:, 1]}
        caps = _tail_caps(idx.shape[0], params, opt_state, optimizer,
                          distinct)
        ranks, slab, compact = {}, {}, {}
        for name in TABLES:
            ranks[name] = rank_rows(rows[name], params[name],
                                    opt_state[name], optimizer, caps[name])
            slab[name], compact[name] = gather_rows(params[name], rows[name],
                                                    ranks[name])
        with jax.named_scope("hm.grad"):
            loss, grads = jax.value_and_grad(batch_loss, argnums=(0, 1))(
                slab["U"].astype(jnp.float32), slab["I"].astype(jnp.float32),
                label, mask)
        new_p, new_s, stats = {}, {}, {}
        for name, g in zip(TABLES, grads):
            new_p[name], new_s[name], stats[name] = rows_update(
                params[name], opt_state[name], rows[name], g, optimizer, t,
                ranks[name])
        return new_p, new_s, loss, _both_tables(stats, compact)

    return scannable(partial(jax.jit, donate_argnums=(0, 1))(core), core)


@_instrument("mf", "bpr_step")
@lru_cache(maxsize=64)
def _bpr_step_cached(k: int, lam: float, optimizer):
    """train_bprmf's step: `idx` [B, 3] (user, pos, neg), loss -log
    sigmoid(x_upos - x_uneg), x_ui = p_u.q_i + b_i, L2 on the rows read;
    the gradient of the whole tables and the optimizer's update of every
    row (the dense step)."""

    def core(params, opt_state, t, idx, val, label, mask):
        u, i, j = idx[:, 0], idx[:, 1], idx[:, 2]

        def batch_loss(p):
            pu, qi, qj = p["U"][u], p["I"][i], p["I"][j]
            W = pu.shape[-1]
            fac, item = _lanes_upto(W, k), _lanes_upto(W, k + 1)
            x = (jnp.where(fac, pu * (qi - qj), 0.0).sum(-1)
                 + qi[:, k] - qj[:, k])
            reg = mask[:, None] * (jnp.where(fac, pu * pu, 0.0)
                                   + jnp.where(item, qi * qi + qj * qj, 0.0))
            return (jax.nn.softplus(-x) * mask).sum() + lam * reg.sum()

        f32 = {n: params[n].astype(jnp.float32) for n in TABLES}
        with jax.named_scope("hm.grad"):
            loss, grads = jax.value_and_grad(batch_loss)(f32)
        new_p, new_s = {}, {}
        with jax.named_scope("hm.update"):
            for n in TABLES:
                w, new_s[n] = optimizer.update(f32[n], grads[n],
                                               opt_state[n], t)
                new_p[n] = w.astype(params[n].dtype)
        return new_p, new_s, loss

    return scannable(partial(jax.jit, donate_argnums=(0, 1))(core), core)


@lru_cache(maxsize=64)
def _state_init(U: int, I: int, k: int, sigma: float, dtype: str, optimizer):
    """The initial tables as ONE function of the key, the same object for
    the same shape (`LearnerBase._make_state` jits it: the tables are born
    on the chip): the factors normal(0, sigma) (the key split in two, the
    user table's half first), bias and the other lanes zero; the
    optimizer's state of each table."""
    W = row_lanes(k)

    def init(key):
        params = {}
        for name, rows, part in zip(TABLES, (U, I), jax.random.split(key)):
            f = jax.random.normal(part, (rows, k)) * sigma
            params[name] = jnp.pad(f, ((0, 0), (0, W - k))).astype(dtype)
        return params, {n: optimizer.init(params[n].shape) for n in TABLES}
    return init


# -- the trainers ------------------------------------------------------------

class MFTrainer(LearnerBase):
    """SQL: train_mf_sgd — reference hivemall.mf.MatrixFactorizationSGDUDTF."""

    NAME = "train_mf_sgd"
    CLASSIFICATION = False
    OPTIMIZER = "sgd"
    #: the id columns of a row, as a warehouse table names them (the CLI
    #: streams a Parquet directory of them, the label column the rating)
    ID_COLUMNS = ("user", "item")

    _KEEP = ("iters", "mini_batch", "ingest_workers", "ingest_pool",
             "steps_per_dispatch", "seed", "mesh", "loadmodel",
             "checkpoint_dir", "checkpoint_every", "checkpoint_keep",
             "telemetry_every", "obs_port")

    @classmethod
    def spec(cls) -> OptionSpec:
        s = _mf_spec(cls.NAME)
        base = learner_option_spec(cls.NAME, classification=False,
                                   default_loss="squaredloss")
        for o in base.options:
            if o.name in cls._KEEP:
                s.options.append(o)
        for o in s.options:             # Hivemall's own defaults
            if o.name == "mini_batch":
                o.default = 1024
            elif o.name == "seed":
                o.default, o.help = 31, "init seed"
        s.add("dims", type=int, default=0,
              help="unused: the tables have -users and -items rows")
        return s

    # -- state ---------------------------------------------------------------
    def _init_state(self) -> None:
        o = self.opts
        if o.loadmodel:
            raise ValueError(f"{self.NAME} has no -loadmodel: its model is "
                             "two factor tables, not a weight table")
        self.k = int(o.factors)
        # bracket access: "items" would hit dict.items on the Parsed namespace
        self.U, self.I = int(o["users"]), int(o["items"])
        if self.mesh is not None:        # table rows over 'tp'
            self._tp_sizes |= {self.U, self.I}
        self._opt = make_optimizer_cached(self.OPTIMIZER, "fixed",
                                          float(o.eta0), 10_000, 0.1)
        dtype = "bfloat16" if o.halffloat else "float32"
        self.params, self.opt_state = self._make_state(
            _state_init(self.U, self.I, self.k, float(o.sigma), dtype,
                        self._opt),
            jax.random.PRNGKey(int(o.seed)))
        self._step = self._make_step()
        self._buf: List[Tuple[int, int, float]] = []
        self._all: List[Tuple[int, int, float]] = []

    def _make_step(self):
        o = self.opts
        return _step_cached(self.k, float(o.mu), float(o["lambda"]),
                            not o.disable_bias, self._opt,
                            self.mesh is None)

    def _finalized_weights(self) -> np.ndarray:
        raise NotImplementedError(f"{self.NAME}'s model is two tables")

    def _table(self, name: str, cols) -> np.ndarray:
        """Columns ``cols`` of every row of table ``name``, float32."""
        return np.asarray(self.params[name][:, cols].astype(jnp.float32))

    def _rows(self, name: str, ids) -> np.ndarray:
        """Rows ``ids`` of table ``name``, float32, gathered on the
        device."""
        at = jnp.asarray(np.asarray(ids, np.int32))
        return np.asarray(self.params[name][at].astype(jnp.float32))

    @property
    def cum_loss(self) -> float:
        """The loss summed over every step so far."""
        self._fold_loss()
        return self._loss_sum

    # -- the feed: a batch of ratings is its id columns and the ratings ------
    def _preprocess_batch(self, batch: SparseBatch) -> SparseBatch:
        return SparseBatch(np.asarray(batch.idx, np.int32), None,
                           batch.label, n_valid=batch.n_valid)

    def _train_batch(self, batch: SparseBatch):
        self.params, self.opt_state, loss, *stats = self._step(
            self.params, self.opt_state, float(self._t),
            jnp.asarray(batch.idx), None, jnp.asarray(batch.label),
            batch.row_mask)
        self._stats_pending += stats
        return loss

    def _batch_of(self, rows: np.ndarray) -> SparseBatch:
        """A padded batch of ``rows`` [n, 3]: the ids, then the rating
        (BPR: three ids, the negative item in the rating's place)."""
        B, n = int(self.opts.mini_batch), len(rows)
        idx = np.zeros((B, len(self.ID_COLUMNS)), np.int32)
        label = np.zeros(B, np.float32)
        if len(self.ID_COLUMNS) == 2:
            idx[:n] = rows[:, :2]
            label[:n] = rows[:, 2]
        else:
            idx[:n] = rows
        return SparseBatch(idx, None, label, n_valid=n if n < B else None)

    # -- UDTF lifecycle ------------------------------------------------------
    def process(self, user: int, item: int, rating: float) -> None:
        self._buf.append((int(user), int(item), float(rating)))
        if len(self._buf) >= int(self.opts.mini_batch):
            self._flush()

    def _flush(self) -> None:
        if not self._buf:
            return
        chunk, self._buf = self._buf, []
        if int(self.opts.iters) > 1:
            self._all.extend(chunk)
        self._dispatch(self._batch_of(np.asarray(chunk, np.float64)))

    def close(self) -> Iterator[Tuple]:
        self._flush()
        iters, bs = int(self.opts.iters), int(self.opts.mini_batch)
        if iters > 1 and self._all:
            rng = np.random.default_rng(42)
            rows = np.asarray(self._all, np.float64)
            for _ in range(1, iters):
                order = rng.permutation(len(rows))
                for s in range(0, len(order), bs):
                    self._dispatch(self._batch_of(rows[order[s:s + bs]]))
        self._emit_train_done()
        yield from self.model_rows()

    def save_bundle(self, path: str) -> None:
        self._flush()                  # buffered rows train before we snapshot
        super().save_bundle(path)

    def fit(self, users, items, ratings, *, epochs: Optional[int] = None,
            shuffle: bool = True) -> "MFTrainer":
        """Columns of ratings (BPR: the negative items in ``ratings``'
        place), ``epochs`` passes (default -iters), each in a fresh random
        order unless ``shuffle`` is False: the same batches `process`
        would make, through `fit_stream`."""
        epochs = int(self.opts.iters) if epochs is None else epochs
        bs = int(self.opts.mini_batch)
        # a caller's device columns are fetched once, before any step
        rows = np.stack([np.asarray(users, np.float64),
                         np.asarray(items, np.float64),
                         np.asarray(ratings, np.float64)], 1)
        rng = np.random.default_rng(42)

        def batches():
            for _ in range(epochs):
                order = rng.permutation(len(rows)) if shuffle \
                    else np.arange(len(rows))
                for s in range(0, len(order), bs):
                    yield self._batch_of(rows[order[s:s + bs]])
        return self.fit_stream(batches(), convert_labels=False)

    # -- scoring / emission --------------------------------------------------
    def predict(self, users, items) -> np.ndarray:
        k = self.k
        pu, qi = self._rows("U", users), self._rows("I", items)
        out = float(self.opts.mu) + (pu[:, :k] * qi[:, :k]).sum(-1)
        if not self.opts.disable_bias:
            out = out + pu[:, k] + qi[:, k]
        return out.astype(np.float32)

    # -- weight-arena publishing (io.weight_arena "factor" family) -----------
    def serving_tables(self) -> Tuple[dict, Dict[str, np.ndarray]]:
        """Arena serving surface: the finalized f32 factor tables and the
        score recipe ``mu + P[u].Q[i] (+ bu[u] + bi[i])``, consumed by the
        retrieval plane (serve/retrieve.py) rather than the SparseBatch
        margin kernels — factor scoring gathers TWO embedding rows per
        pair instead of one weight row per feature."""
        self._flush()                  # buffered rows train before export
        k = self.k
        use_bias = not self.opts.disable_bias
        meta = {"family": "factor", "k": k,
                "mu": float(self.opts.mu),
                "user_bias": use_bias, "item_bias": use_bias,
                "classification": False}
        tables = {"P": self._table("U", slice(0, k)),
                  "Q": self._table("I", slice(0, k))}
        if use_bias:
            tables["bu"] = self._table("U", k)
            tables["bi"] = self._table("I", k)
        return meta, tables

    def save_model(self, path: str) -> None:
        """The model table `close` emits, a row a line, tab-separated."""
        with open(path, "w") as f:
            for row in self.model_rows():
                f.write("\t".join(str(x) for x in row) + "\n")

    def model_rows(self) -> Iterator[Tuple]:
        """(idx, Pu|None, Qi|None, bu, bi) rows, users then items, only
        touched ids (nonzero factors)."""
        k = self.k
        Ut, It = self._table("U", slice(0, k + 1)), \
            self._table("I", slice(0, k + 1))
        for uid in np.nonzero(np.abs(Ut[:, :k]).sum(-1) > 0)[0]:
            yield (int(uid), Ut[uid, :k].tolist(), None, float(Ut[uid, k]),
                   None)
        for iid in np.nonzero(np.abs(It[:, :k]).sum(-1) > 0)[0]:
            yield (int(iid), None, It[iid, :k].tolist(), None,
                   float(It[iid, k]))


class MFAdaGradTrainer(MFTrainer):
    """SQL: train_mf_adagrad — reference hivemall.mf.MatrixFactorizationAdaGradUDTF."""
    NAME = "train_mf_adagrad"
    OPTIMIZER = "adagrad"


class BPRMFTrainer(MFTrainer):
    """SQL: train_bprmf — reference hivemall.mf.BPRMatrixFactorizationUDTF.

    Implicit feedback: rows are (user, pos_item, neg_item); loss is
    -log sigmoid(x_upos - x_uneg) with x_ui = p_u.q_i + b_i (item bias only).
    """
    NAME = "train_bprmf"
    ID_COLUMNS = ("user", "pos_item", "neg_item")

    def _make_step(self):
        return _bpr_step_cached(self.k, float(self.opts["lambda"]),
                                self._opt)

    def process(self, user: int, pos_item: int, neg_item: int) -> None:
        # third slot carries the negative item id (int), not a rating
        super().process(user, pos_item, float(neg_item))

    def predict(self, users, items) -> np.ndarray:
        k = self.k
        pu, qi = self._rows("U", users), self._rows("I", items)
        return ((pu[:, :k] * qi[:, :k]).sum(-1) + qi[:, k]).astype(np.float32)

    def serving_tables(self) -> Tuple[dict, Dict[str, np.ndarray]]:
        """BPR's score has no global mean and no user bias — the pairwise
        ranking loss cancels both; only the item bias survives."""
        self._flush()
        k = self.k
        meta = {"family": "factor", "k": k, "mu": 0.0,
                "user_bias": False, "item_bias": True,
                "classification": False}
        return meta, {"P": self._table("U", slice(0, k)),
                      "Q": self._table("I", slice(0, k)),
                      "bi": self._table("I", k)}


# --- predict UDFs (join-side reassembly, SURVEY.md §3.7 row 5) -------------

def mf_predict(pu: Optional[List[float]], qi: Optional[List[float]],
               bu: Optional[float] = None, bi: Optional[float] = None,
               mu: float = 0.0) -> float:
    """SQL: mf_predict(Pu, Qi, Bu, Bi, mu) — reference hivemall.mf.MFPredictUDF.
    Missing user/item rows fall back to the known parts (cold start)."""
    out = float(mu)
    if bu is not None:
        out += float(bu)
    if bi is not None:
        out += float(bi)
    if pu is not None and qi is not None:
        out += float(np.dot(np.asarray(pu, np.float64),
                            np.asarray(qi, np.float64)))
    return out


def bprmf_predict(pu: Optional[List[float]], qi: Optional[List[float]],
                  bi: Optional[float] = None) -> float:
    """SQL: bprmf_predict — reference hivemall.mf.BPRMFPredictUDF."""
    out = 0.0 if bi is None else float(bi)
    if pu is not None and qi is not None:
        out += float(np.dot(np.asarray(pu, np.float64),
                            np.asarray(qi, np.float64)))
    return out
