"""train_word2vec — SkipGram/CBOW with negative sampling (BASELINE config #4).

Reference (SURVEY.md §3.8): the late-incubator hivemall embedding package's
Word2VecUDTF: consume tokenized documents, build a vocabulary + unigram^0.75
negative-sampling table, and train SkipGram (default) or CBOW embeddings.

TPU shape: training pairs are generated host-side into fixed-shape arrays
(center[B], context[B], negatives[B, neg]); one jitted step does the
logistic pos/neg dot products and scatter-adds into the in/out embedding
tables — the whole O(B * neg * dim) update is a handful of fused einsums,
instead of the reference's per-pair scalar loops. Linear LR decay matches
word2vec.c / the reference.

Pair generation is fully vectorized (numpy, no per-token Python): dynamic
windows draw one width per position, then each window offset delta becomes
two array-slice selections (left/right context) over the whole document —
2*win vector ops per doc instead of O(tokens * window) scalar work. This
keeps the host side >=10M pairs/sec so text8-scale training is TPU-bound,
not input-bound.
"""

from __future__ import annotations

from collections import Counter
from functools import lru_cache, partial
from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.devprof import instrument_factory as _instrument
from ..utils.options import OptionSpec

__all__ = ["Word2VecTrainer"]


class Word2VecTrainer:
    """SQL: train_word2vec(words[, options]) — UDTF over tokenized docs."""

    NAME = "train_word2vec"

    @classmethod
    def spec(cls) -> OptionSpec:
        s = OptionSpec(cls.NAME)
        s.add("dim", "size", type=int, default=100, help="embedding dim")
        s.add("window", "win", type=int, default=5, help="context window")
        s.add("neg", "negative", type=int, default=5,
              help="negative samples per pair")
        s.add("iters", "iterations", type=int, default=1, help="epochs")
        s.add("min_count", type=int, default=5, help="vocab frequency floor")
        s.add("alpha", "lr", type=float, default=0.025,
              help="initial learning rate, linearly decayed. With the "
                   "default -pacing pair this is word2vec.c's per-pair "
                   "step size (0.025 means 0.025)")
        s.add("pacing", default="pair",
              help="pair (default): per-pair-SUM loss — each pair moves "
                   "its rows by O(alpha), word2vec.c-compatible option "
                   "values | mean: round-2 batch-MEAN loss (alpha must "
                   "scale with mini_batch; kept for compatibility)")
        s.add("sample", type=float, default=1e-4,
              help="frequent-word subsampling threshold (0 = off)")
        s.add("neg_sharing", default="pair",
              help="pair (default): word2vec.c per-pair negative draws | "
                   "batch: ONE negative set shared by the whole minibatch "
                   "(candidate-sampling style). Sharing turns the "
                   "negative path into a [B,D]x[D,neg] MXU matmul and a "
                   "neg-row scatter instead of B*neg gather/scatter rows "
                   "— ~3x step throughput; raise -neg (e.g. 16-64) to "
                   "compensate the shared draw")
        s.add("mini_batch", type=int, default=16384,
              help="pairs per step. Under -pacing pair each pair "
                   "contributes its own O(alpha) step regardless of batch "
                   "size (hogwild-style minibatch of word2vec.c's "
                   "sequential updates), so bigger batches only reduce "
                   "dispatch overhead")
        s.add("seed", type=int, default=11, help="rng seed")
        s.add("pair_gen", default="auto",
              help="where SkipGram (center, context) pairs are generated: "
                   "host (vectorized numpy, pairs cross h2d — 4 bytes per "
                   "pair) | device (token stream crosses h2d ONCE — ~2 "
                   "bytes per token, pairs come from shifted views on "
                   "device; needs -neg_sharing batch, SkipGram, no -mesh "
                   "— rejected otherwise) | auto (device on accelerators "
                   "when those hold, else host)")
        s.add("window_policy", default="sample",
              help="device pair-gen window policy: sample (word2vec.c "
                   "dynamic windows — each position draws w in [1,win], "
                   "pairs beyond w masked) | weighted (every pair trains, "
                   "weighted (win-delta+1)/win — the EXPECTATION of "
                   "sample's draw; zero masked slots, lower variance)")
        s.flag("cbow", help="CBOW instead of SkipGram")
        s.add("mesh", default=None,
              help="shard training over a device mesh, e.g. 'dp=2,tp=4' "
                   "(pair batches over dp, embedding tables over tp)")
        return s

    def __init__(self, options: str = ""):
        self.opts = self.spec().parse(options)
        self._docs: List[List[str]] = []
        self.vocab: Dict[str, int] = {}
        self.inv_vocab: List[str] = []
        self.in_emb: Optional[jnp.ndarray] = None
        self.out_emb: Optional[jnp.ndarray] = None
        self.mesh = None
        if self.opts.mesh:
            from ..parallel.mesh import make_mesh, parse_mesh_spec
            dp, tp = parse_mesh_spec(str(self.opts.mesh))
            if int(self.opts.mini_batch) % dp:
                raise ValueError(
                    f"-mini_batch {self.opts.mini_batch} must be divisible "
                    f"by the dp axis ({dp})")
            self.mesh = make_mesh(dp=dp, tp=tp)

    # -- UDTF lifecycle ------------------------------------------------------
    def process(self, words: Sequence[str]) -> None:
        self._docs.append([str(w) for w in words if w])

    def close(self) -> Iterator[Tuple[str, List[float]]]:
        self.train(self._docs)
        yield from self.model_rows()

    # -- training ------------------------------------------------------------
    def _build_vocab(self, docs: Sequence[Sequence[str]]) -> np.ndarray:
        # vectorized: ONE np.unique pass over the corpus replaces the
        # Counter + two per-token dict walks (~1.2 s of the text8-scale
        # bench was host string work); per-doc id arrays are cached for
        # train() via the same inverse
        # host string arrays from Python token lists — no device sync
        parts = [np.asarray(d, dtype=np.str_) for d in docs if len(d)]  # graftcheck: disable=GC07
        flat = np.concatenate(parts) if parts else np.asarray([], np.str_)
        uniq, inverse, counts = np.unique(
            flat, return_inverse=True, return_counts=True)
        keep = counts >= int(self.opts.min_count)
        order = np.argsort(-counts[keep], kind="stable")
        kept_words = uniq[keep][order]
        kept_counts = counts[keep][order]
        remap = np.full(len(uniq), -1, np.int64)
        remap[np.nonzero(keep)[0][order]] = np.arange(order.size)
        ids_flat = remap[inverse]
        self.vocab = {w: i for i, w in enumerate(kept_words.tolist())}
        self.inv_vocab = kept_words.tolist()
        # cache per-doc id arrays (dropping out-of-vocab tokens)
        self._ids_docs_cache = []
        off = 0
        for d in docs:
            ids = ids_flat[off:off + len(d)]
            off += len(d)
            self._ids_docs_cache.append(
                ids[ids >= 0].astype(np.int32))
        return np.asarray(kept_counts, np.float64)

    def _neg_table(self, freqs: np.ndarray, size: int = 0) -> np.ndarray:
        """Unigram^0.75 sampling table (word2vec.c style). Sized ~16 slots
        per word (capped [2^16, 2^20]) and stored uint16 when the vocab
        fits — the table crosses h2d once per trainer and a fixed 2^20
        int32 table cost ~4 MB of every e2e run for no sampling-fidelity
        gain at text8-scale vocabularies."""
        V = len(freqs)
        if not size:
            size = max(1 << 16, min(1 << 20, 16 * V))
        p = freqs ** 0.75
        p /= p.sum()
        dt = np.uint16 if V < 65536 else np.int32
        return np.repeat(np.arange(len(freqs)),
                         np.maximum(1, np.round(p * size).astype(np.int64))
                         ).astype(dt)

    def _make_step(self, cbow: bool, vocab_size: int, dim: int):
        neg = int(self.opts.neg)
        pair_pacing = str(getattr(self.opts, "pacing", "pair")) == "pair"
        share_neg = str(getattr(self.opts, "neg_sharing",
                                "pair")) == "batch"
        # Two update variants, chosen by table size (measured on v5e):
        #   dense  — autodiff over the whole (in, out) tables; the SGD
        #            update is two fused elementwise passes. Fastest while
        #            V*D stays a few MB (text8-class vocabularies).
        #   sparse — slab-level autodiff + scatter-add of touched rows
        #            only (the ops.fm.make_ffm_step_fused principle). At
        #            enwiki scale (V ~ 1M) the dense variant would move
        #            100s of MB of table per step for a few thousand
        #            touched rows.
        # Both variants draw NEGATIVES ON DEVICE from the staged unigram^.75
        # table (word2vec.c's table sampling, jax PRNG keyed by the step
        # counter) and rebuild the pair mask from the valid-count scalar:
        # per-step h2d drops from 4 arrays (~520 KB at B=16k) to the two
        # id arrays — the dispatch link is the e2e bottleneck here.
        if vocab_size * dim <= (1 << 23) and not share_neg:
            # NOTE: with -neg_sharing batch the sparse slab step wins at
            # every vocab size (measured 5 ms vs 20 ms at V=16k, B=32k —
            # the dense autodiff materializes several [V,D] passes while
            # shared negatives already removed the sparse path's per-pair
            # neg rows)
            return self._make_step_dense(cbow)

        seed = int(self.opts.seed)

        @partial(jax.jit, donate_argnums=(0, 1))
        def step(in_emb, out_emb, ntab, center, context, nvalid, t, lr):
            # SkipGram: v_in = in[center]; target = context
            # CBOW: v_in = mean(in[context window]) handled by caller passing
            #       the window in `center` as [B, 2w] with -1 padding
            # ids may arrive uint16 (halved h2d bytes); widen on device
            center = center.astype(jnp.int32)
            context = context.astype(jnp.int32)
            B = context.shape[0]
            key = jax.random.fold_in(jax.random.PRNGKey(seed), t)
            nshape = (neg,) if share_neg else (B, neg)
            negs = ntab[jax.random.randint(key, nshape, 0,
                                           ntab.shape[0])].astype(
                jnp.int32)
            row_mask = (jnp.arange(B) < nvalid).astype(jnp.float32)
            if cbow:
                cmask = (center >= 0).astype(jnp.float32)
                cids = jnp.maximum(center, 0)
                vin_slab = in_emb[cids]                      # [B, 2w, D]
            else:
                vin_slab = in_emb[center]                    # [B, D]
            pos_slab = out_emb[context]                      # [B, D]
            neg_slab = out_emb[negs]            # [neg, D] or [B, neg, D]

            def batch_loss(vin, op, on):
                if cbow:
                    v = (vin * cmask[..., None]).sum(1) / jnp.maximum(
                        cmask.sum(1, keepdims=True), 1.0)
                else:
                    v = vin
                pos = (v * op).sum(-1)
                if share_neg:
                    negd = jnp.einsum("bd,nd->bn", v, on)    # MXU
                else:
                    negd = jnp.einsum("bd,bnd->bn", v, on)
                per_pair = (jax.nn.softplus(-pos)
                            + jax.nn.softplus(negd).sum(-1)) * row_mask
                if pair_pacing:
                    # per-pair SUM: every pair moves its rows by O(lr) —
                    # word2vec.c's pacing, batched hogwild-style
                    return per_pair.sum()
                # batch MEAN (round-2 semantics): effective per-pair step
                # is lr / n_valid
                return per_pair.sum() / jnp.maximum(row_mask.sum(), 1.0)

            loss, (gv, gp, gn) = jax.value_and_grad(
                batch_loss, argnums=(0, 1, 2))(vin_slab, pos_slab, neg_slab)
            D = in_emb.shape[1]
            if cbow:
                ie = in_emb.at[cids.reshape(-1)].add(
                    (-lr * gv).reshape(-1, D))
            else:
                ie = in_emb.at[center].add(-lr * gv)
            oe = out_emb.at[context].add(-lr * gp)
            oe = oe.at[negs.reshape(-1)].add((-lr * gn).reshape(-1, D))
            return ie, oe, loss

        return step

    def _make_step_dense(self, cbow: bool):
        neg = int(self.opts.neg)
        pair_pacing = str(getattr(self.opts, "pacing", "pair")) == "pair"
        share_neg = str(getattr(self.opts, "neg_sharing",
                                "pair")) == "batch"

        seed = int(self.opts.seed)

        @partial(jax.jit, donate_argnums=(0, 1))
        def step(in_emb, out_emb, ntab, center, context, nvalid, t, lr):
            center = center.astype(jnp.int32)
            context = context.astype(jnp.int32)
            B = context.shape[0]
            key = jax.random.fold_in(jax.random.PRNGKey(seed), t)
            nshape = (neg,) if share_neg else (B, neg)
            negs = ntab[jax.random.randint(key, nshape, 0,
                                           ntab.shape[0])].astype(
                jnp.int32)
            row_mask = (jnp.arange(B) < nvalid).astype(jnp.float32)

            def batch_loss(tables):
                ie, oe = tables
                if cbow:
                    mask = (center >= 0).astype(jnp.float32)
                    v = (ie[jnp.maximum(center, 0)] *
                         mask[..., None]).sum(1) / jnp.maximum(
                             mask.sum(1, keepdims=True), 1.0)
                else:
                    v = ie[center]
                pos = (v * oe[context]).sum(-1)
                if share_neg:
                    negd = jnp.einsum("bd,nd->bn", v, oe[negs])
                else:
                    negd = jnp.einsum("bd,bnd->bn", v, oe[negs])
                per_pair = (jax.nn.softplus(-pos)
                            + jax.nn.softplus(negd).sum(-1)) * row_mask
                if pair_pacing:
                    # per-pair SUM — word2vec.c pacing (see _make_step)
                    return per_pair.sum()
                return per_pair.sum() / jnp.maximum(row_mask.sum(), 1.0)

            loss, grads = jax.value_and_grad(batch_loss)((in_emb, out_emb))
            return (in_emb - lr * grads[0], out_emb - lr * grads[1], loss)

        return step

    def _make_pairgen(self, Nc: int, win: int, sep_id: int, policy: str,
                      seed: int, wire_dt):
        # module-level lru_cache: a fresh jitted closure per TRAINER would
        # re-trace/compile on every instance (measured: recompilation cost
        # dominated the device-windowing e2e run — each bench repeat paid
        # seconds of compile for identical configs)
        return _pairgen_cached(Nc, win, sep_id, policy, seed,
                               np.dtype(wire_dt).name)

    def _make_chunk_trainer(self, W2: int, Bc: int, n_steps: int):
        return _chunk_trainer_cached(
            W2, Bc, n_steps, int(self.opts.neg),
            str(getattr(self.opts, "pacing", "pair")) == "pair",
            int(self.opts.seed))

    def _train_device_windowing(self, ids_docs, keep_p,
                                table) -> None:
        """SkipGram training with on-device pair windowing (-pair_gen
        device): the token stream crosses h2d once per epoch (~2
        bytes/token vs ~4 bytes/PAIR x ~5 pairs/token on the host path);
        per-chunk, one jitted pair-gen builds the center-major [.., 2*win]
        grid and the grid step consumes row-block device slices."""
        o = self.opts
        rng = np.random.default_rng(int(o.seed))
        win = int(o.window)
        W2 = 2 * win
        B = int(o.mini_batch)
        Bc = max(128, B // W2)          # centers per step (~B pair slots)
        alpha = float(o.alpha)
        epochs = int(o.iters)
        V = len(self.vocab)
        sep = V                         # out-of-vocab sentinel id
        wire_dt = np.uint16 if V < 65535 else np.int32
        policy = str(o.window_policy)
        if policy not in ("sample", "weighted"):
            raise ValueError(f"-window_policy must be sample|weighted, got "
                             f"{policy!r}")
        gen = None                      # built once the stream size is known
        runner = None
        nstep = 0
        for ep in range(epochs):
            parts = []
            for d in ids_docs:
                if float(o.sample) > 0 and len(d):
                    d = d[rng.random(len(d)) < keep_p[d]]
                if len(d):
                    parts.append(d)
                    parts.append(np.full(win, sep, np.int32))
            if not parts:
                continue
            stream = np.concatenate(parts).astype(wire_dt)
            n = len(stream)
            if gen is None:
                # chunk tokens: power-of-two sized to the corpus, capped at
                # 512k (pair grid ~5.2M slots) — ONE compile per corpus
                # scale instead of a fixed grid that buries small corpora
                # in masked slots
                CH = min(1 << 19, 1 << max(10, (n - 1).bit_length()))
                Nc = CH + 2 * win
                gen = self._make_pairgen(Nc, win, sep, policy,
                                         int(o.seed), wire_dt)
            epd = jnp.uint32(ep)
            for s0 in range(0, n, CH):
                # win-token halo each side; SEP-pad the stream edges
                lo, hi = s0 - win, s0 + CH + win
                chunk = np.full(Nc, sep, wire_dt)
                src_lo, src_hi = max(0, lo), min(n, hi)
                chunk[src_lo - lo:src_hi - lo] = stream[src_lo:src_hi]
                c_all, x_all, m_all, _ = gen(jnp.asarray(chunk),
                                             jnp.int32(s0), epd)
                R = c_all.shape[0]               # grid rows (= Nc centers)
                ck_tokens = min(CH, n - s0)
                n_steps = -(-R // Bc)
                if runner is None:
                    runner = self._make_chunk_trainer(W2, Bc, n_steps)
                pad = n_steps * Bc - R
                if pad:
                    c_all = jnp.pad(c_all, (0, pad))
                    x_all = jnp.pad(x_all, ((0, pad), (0, 0)))
                    m_all = jnp.pad(m_all, ((0, pad), (0, 0)))

                # word2vec.c decays alpha continuously per word; progress
                # is PER-EPOCH NORMALIZED ((ep + within-epoch)/epochs) so
                # subsampling's per-epoch stream-length jitter can't push
                # it past 1.0 (which would clamp the tail at lr_min) or
                # leave it short of the floor; within a chunk it
                # interpolates per STEP so a single-chunk corpus still
                # sweeps alpha -> ~0
                def lr_at(si: float) -> float:
                    prog = (ep + (s0 + ck_tokens * (si / n_steps)) / n) \
                        / epochs
                    return alpha * (1.0 - prog)

                lr0 = lr_at(0.0)
                dlr = (lr0 - lr_at(float(n_steps))) / max(1, n_steps)
                self.in_emb, self.out_emb = runner(
                    self.in_emb, self.out_emb, table, c_all, x_all, m_all,
                    jnp.int32(nstep), jnp.float32(lr0), jnp.float32(dlr),
                    jnp.float32(alpha * 1e-4))
                nstep += n_steps

    @staticmethod
    def _skipgram_pairs(d: np.ndarray, win: int, rng) -> Tuple[np.ndarray,
                                                               np.ndarray]:
        """Vectorized SkipGram (center, context) pairs for one doc.

        Dynamic windows as in word2vec.c: each position draws a width
        w in [1, win]; (pos, pos±delta) is a pair iff delta <= w[pos].
        2*win slice-selections replace the per-token Python loop."""
        n = len(d)
        if n < 2:
            return (np.zeros(0, np.int32),) * 2
        w = rng.integers(1, win + 1, n, dtype=np.uint8)
        cs: List[np.ndarray] = []
        xs: List[np.ndarray] = []
        for delta in range(1, win + 1):
            pos = np.flatnonzero(w >= delta)   # centers wide enough for delta
            right = pos[pos < n - delta]       # (pos, pos+delta)
            cs.append(d[right])
            xs.append(d[right + delta])
            left = pos[pos >= delta]           # (pos, pos-delta)
            cs.append(d[left])
            xs.append(d[left - delta])
        return np.concatenate(cs), np.concatenate(xs)

    @staticmethod
    def _cbow_windows(d: np.ndarray, win: int, rng) -> Tuple[np.ndarray,
                                                             np.ndarray]:
        """Vectorized CBOW windows: rows [n, 2*win] of context ids (-1 pad)
        plus the center target, dynamic widths per position."""
        n = len(d)
        if n < 2:
            return np.zeros((0, 2 * win), np.int32), np.zeros(0, np.int32)
        w = rng.integers(1, win + 1, n)
        ctx = np.full((n, 2 * win), -1, np.int32)
        for delta in range(1, win + 1):
            keep = w >= delta
            col_r, col_l = 2 * (delta - 1), 2 * (delta - 1) + 1
            # right neighbor pos+delta feeds center pos
            sel = keep[:n - delta]
            ctx[:n - delta, col_r] = np.where(sel, d[delta:], -1)
            # left neighbor pos-delta feeds center pos
            sel = keep[delta:]
            ctx[delta:, col_l] = np.where(sel, d[:n - delta], -1)
        has_ctx = (ctx >= 0).any(1)
        return ctx[has_ctx], d[has_ctx]

    def train(self, docs: Sequence[Sequence[str]]) -> "Word2VecTrainer":
        o = self.opts
        freqs = self._build_vocab(docs)
        V, D = len(self.vocab), int(o.dim)
        if V == 0:
            raise ValueError("empty vocabulary (check -min_count)")
        rng = np.random.default_rng(int(o.seed))
        key = jax.random.PRNGKey(int(o.seed))
        Vp = V
        if self.mesh is not None:     # pad vocab rows to the tp axis size
            tp = self.mesh.shape["tp"]
            Vp = -(-V // tp) * tp     # extra rows are never gathered
        self.in_emb = (jax.random.uniform(key, (Vp, D)) - 0.5) / D
        self.out_emb = jnp.zeros((Vp, D))
        table = jnp.asarray(self._neg_table(freqs))   # staged on device once
        if self.mesh is not None:
            # vocab rows over tp, negative table replicated, batches over dp
            from jax.sharding import NamedSharding, PartitionSpec as P
            sh = NamedSharding(self.mesh, P("tp", None))
            self.in_emb = jax.device_put(self.in_emb, sh)
            self.out_emb = jax.device_put(self.out_emb, sh)
            table = jax.device_put(table, NamedSharding(self.mesh, P()))
        ids_docs = getattr(self, "_ids_docs_cache", None) or \
            [np.asarray([self.vocab[w] for w in d if w in self.vocab],  # graftcheck: disable=GC07
                               np.int32) for d in docs]  # host id arrays, no sync
        total = sum(len(d) for d in ids_docs)
        # frequent-word subsampling probabilities (word2vec.c formula)
        sample = float(o.sample)
        if sample > 0:
            f = freqs / max(1, total)
            keep_p = np.minimum(1.0, np.sqrt(sample / f) + sample / f)
        else:
            keep_p = np.ones(V)

        cbow = bool(o.cbow)
        pg = str(o.pair_gen)
        if pg not in ("auto", "host", "device"):
            raise ValueError(f"-pair_gen must be auto|host|device, got "
                             f"{pg!r}")
        share_neg = str(getattr(o, "neg_sharing", "pair")) == "batch"
        dev_ok = not cbow and self.mesh is None and share_neg
        if pg == "device" and not dev_ok:
            # never SILENTLY train with different semantics than asked: the
            # grid path needs batch-shared negatives (the per-center
            # negative term is the savings), SkipGram, and no mesh
            raise ValueError(
                "-pair_gen device requires -neg_sharing batch, SkipGram "
                "(no -cbow), and no -mesh; use -pair_gen auto to fall "
                "back automatically")
        if dev_ok and (pg == "device"
                       or (pg == "auto"
                           and jax.default_backend() != "cpu")):
            self._train_device_windowing(ids_docs, keep_p, table)
            return self

        step = self._make_step(cbow, V, D)
        win = int(o.window)
        B = int(o.mini_batch)
        neg = int(o.neg)
        alpha = float(o.alpha)
        epochs = int(o.iters)

        # pending vectorized pair chunks awaiting dispatch
        pend_c: List[np.ndarray] = []
        pend_x: List[np.ndarray] = []
        pending = 0

        nstep = 0

        wire_dt = np.uint16 if (not cbow and V < 65536) else np.int32
        K = 8               # steps shipped per h2d block (per-transfer
                            # latency dominates small blocks; one [K*B]
                            # block transfer feeds K pipelined steps)

        def dispatch_block(c: np.ndarray, x: np.ndarray, progress: float
                           ) -> None:
            """Ship up to K steps' pair ids in ONE h2d each, then step on
            device-resident slices; short tails pad to B and mask."""
            nonlocal nstep
            n = len(x)
            if n == 0:
                return
            nfull = -(-n // B) * B
            if nfull != n:
                padn = nfull - n
                c = np.concatenate(
                    [c, np.full((padn,) + c.shape[1:],
                                -1 if cbow else 0, c.dtype)])
                x = np.concatenate([x, np.zeros(padn, x.dtype)])
            lr = max(alpha * (1.0 - progress), alpha * 1e-4)
            cd_all = jnp.asarray(c.astype(wire_dt, copy=False))
            xd_all = jnp.asarray(x.astype(wire_dt, copy=False))
            if self.mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec as P
                cd_all = jax.device_put(cd_all, NamedSharding(
                    self.mesh, P(None, *([None] * (cd_all.ndim - 1)))))
                xd_all = jax.device_put(xd_all,
                                        NamedSharding(self.mesh, P(None)))
            for s0 in range(0, nfull, B):
                nb = min(B, n - s0)
                if nb <= 0:
                    break
                nstep += 1
                cd = cd_all[s0:s0 + B]
                xd = xd_all[s0:s0 + B]
                if self.mesh is not None:
                    from jax.sharding import NamedSharding, \
                        PartitionSpec as P
                    cd = jax.device_put(cd, NamedSharding(
                        self.mesh, P("dp", *([None] * (cd.ndim - 1)))))
                    xd = jax.device_put(xd,
                                        NamedSharding(self.mesh, P("dp")))
                self.in_emb, self.out_emb, _ = step(
                    self.in_emb, self.out_emb, table, cd, xd, nb, nstep,
                    lr)

        def drain(progress: float, final: bool = False) -> None:
            nonlocal pend_c, pend_x, pending
            if pending >= K * B or (final and pending):
                c = np.concatenate(pend_c)
                x = np.concatenate(pend_x)
                nfull = (len(x) // B) * B
                if final:
                    dispatch_block(c, x, progress)
                    pend_c, pend_x, pending = [], [], 0
                else:
                    dispatch_block(c[:nfull], x[:nfull], progress)
                    pend_c = [c[nfull:]]
                    pend_x = [x[nfull:]]
                    pending = len(x) - nfull

        tokens_done = 0
        for ep in range(epochs):
            for d in ids_docs:
                if sample > 0 and len(d):
                    d = d[rng.random(len(d)) < keep_p[d]]
                if cbow:
                    c, x = self._cbow_windows(d, win, rng)
                else:
                    c, x = self._skipgram_pairs(d, win, rng)
                if len(x):
                    if str(o.pacing) == "mean":
                        # mean pacing needs in-chunk shuffling: the
                        # per-delta grouping feeds same-offset runs that
                        # skew the batch mean. Pair pacing processes pairs
                        # in corpus order — word2vec.c's own order — and
                        # skips the ~1s host permutation+gather per 10M+
                        # pair chunk.
                        perm = rng.permutation(len(x))
                        c, x = c[perm], x[perm]
                    pend_c.append(c)
                    pend_x.append(x)
                    pending += len(x)
                tokens_done += len(d)
                drain(tokens_done / max(1, total * epochs))
        drain(1.0, final=True)
        return self

    # -- output --------------------------------------------------------------
    def model_rows(self) -> Iterator[Tuple[str, List[float]]]:
        emb = np.asarray(self.in_emb)
        for w, i in self.vocab.items():
            yield (w, emb[i].tolist())

    def vectors(self) -> Dict[str, np.ndarray]:
        emb = np.asarray(self.in_emb)
        return {w: emb[i] for w, i in self.vocab.items()}

    def similarity(self, a: str, b: str) -> float:
        va, vb = self.vectors()[a], self.vectors()[b]
        return float(va @ vb / (np.linalg.norm(va) * np.linalg.norm(vb)
                                + 1e-12))

    def serving_tables(self) -> Tuple[dict, Dict[str, np.ndarray]]:
        """Arena "factor" family (io.weight_arena): both the query table
        and the candidate table are the input embeddings — word2vec's
        retrieval shape is word→nearest-words over ONE vector space, so
        ``P is Q`` and cosine neighbor queries are the meaningful tier.
        Only the trained vocab rows export (the table may be padded to a
        tp mesh axis); the vocab itself rides in the header so the
        retrieval plane can translate ids back to words."""
        if self.in_emb is None:
            raise ValueError("serving_tables() before train(): "
                             "no embeddings yet")
        V = len(self.vocab)
        emb = np.asarray(self.in_emb, np.float32)[:V]
        meta = {"family": "factor", "k": int(emb.shape[1]), "mu": 0.0,
                "user_bias": False, "item_bias": False,
                "classification": False, "vocab": list(self.inv_vocab)}
        return meta, {"P": emb, "Q": emb}


@_instrument("word2vec", "pairgen")
@lru_cache(maxsize=64)
def _pairgen_cached(Nc: int, win: int, sep_id: int, policy: str, seed: int,
                    wire_name: str):
    """Jitted device-side SkipGram pair generator over a token chunk
    (cached per static config so trainer instances share one compile).

    The round-3 e2e wall was the h2d link moving PAIRS (~4 bytes/pair
    x ~5 pairs/token); here the TOKEN STREAM crosses once (~2
    bytes/token) and pairs come from 2*win shifted views (jnp.roll —
    no per-element index ops, the round-3 trap). Slot (i, j) of the
    [Nc, 2*win] grid is (T[i], T[i +/- delta]); validity/weight rides
    a per-slot mask consumed by the grid step (invalid slots train with
    weight 0 — masking beats device compaction, whose argsort/scatter
    would cost ~26 ns per pair, more than the step).

    policy='sample': word2vec.c dynamic windows — w[i] drawn in [1, win]
    by an integer hash of the global position (stateless, so chunks and
    epochs stay reproducible), pairs with delta > w[i] masked.
    policy='weighted': every pair trains with weight (win - delta + 1)/win
    — exactly the expectation of sample's draw, zero masked slots, lower
    gradient variance (documented delta). Chunks arrive with a win-token
    halo on both sides; centers in the halo are masked (their pairs belong
    to neighbour chunks)."""
    wire_dt = np.dtype(wire_name)

    @jax.jit
    def gen(T, offset, ep):
        Tw = T.astype(jnp.int32)
        i = jnp.arange(Nc, dtype=jnp.int32)
        if policy == "sample":
            h = (i + offset).astype(jnp.uint32)
            h = h * jnp.uint32(0x9E3779B1) + jnp.uint32(seed)
            h = h ^ (h >> 15)
            h = (h + ep.astype(jnp.uint32)) * jnp.uint32(0xC2B2AE35)
            h = h ^ (h >> 13)
            w = (1 + h % jnp.uint32(win)).astype(jnp.int32)
        ms, xs = [], []
        is_sep = Tw == sep_id
        center_ok = (~is_sep) & (i >= win) & (i < Nc - win)
        for delta in range(1, win + 1):
            for sgn in (1, -1):
                ctx = jnp.roll(Tw, -sgn * delta)
                ok = center_ok & (ctx != sep_id)
                if policy == "sample":
                    wt = (ok & (w >= delta)).astype(jnp.float32)
                else:
                    wt = ok.astype(jnp.float32) * ((win - delta + 1) / win)
                xs.append(ctx)
                ms.append(wt)
        x = jnp.stack(xs, 1).astype(wire_dt)      # [Nc, 2*win]
        m = jnp.stack(ms, 1)                      # [Nc, 2*win]
        return Tw.astype(wire_dt), x, m, m.sum()

    return gen


@_instrument("word2vec", "chunk_trainer")
@lru_cache(maxsize=64)
def _chunk_trainer_cached(W2: int, Bc: int, n_steps: int, neg: int,
                          pair_pacing: bool, seed: int):
    """The WHOLE chunk's step loop as one jitted lax.fori_loop (cached per
    static config — a fresh closure per trainer re-compiled every run).

    A per-step python loop pays one dispatch per slice/step, which capped
    the device pair-gen path below the host path; here a chunk is ONE
    dispatch. Each iteration consumes a [Bc] center
    block of the center-major grid via dynamic_slice, draws that step's
    shared negatives from the staged table, and applies the grid-step
    update: the flat pair step pays (gather + scatter) on BOTH endpoints
    of every slot (~4 index ops/pair at ~26 ns, the measured per-row
    floor); the grid gathers/scatters each center ONCE per W2 slots and
    computes the shared-negative term — which depends only on the center
    vector — per CENTER, weighted by the row's total pair weight (equal
    to summing it per pair). Index ops per slot drop from ~4 to
    ~2 + 2/W2. lr decays linearly across the chunk (word2vec.c per-word
    decay)."""
    @partial(jax.jit, donate_argnums=(0, 1))
    def run(in_emb, out_emb, ntab, c_all, x_all, m_all, t0, lr0, dlr,
            lr_min):
        D = in_emb.shape[1]

        def body(si, carry):
            ie, oe = carry
            r0 = si * Bc
            centers = jax.lax.dynamic_slice(
                c_all, (r0,), (Bc,)).astype(jnp.int32)
            ctx = jax.lax.dynamic_slice(
                x_all, (r0, 0), (Bc, W2)).astype(jnp.int32)
            wts = jax.lax.dynamic_slice(m_all, (r0, 0), (Bc, W2))
            lr = jnp.maximum(lr0 - dlr * si.astype(jnp.float32), lr_min)
            key = jax.random.fold_in(jax.random.PRNGKey(seed), t0 + si)
            negs = ntab[jax.random.randint(
                key, (neg,), 0, ntab.shape[0])].astype(jnp.int32)
            vin = ie[centers]                        # [Bc, D]
            pos_slab = oe[ctx.reshape(-1)].reshape(Bc, W2, D)
            neg_slab = oe[negs]                      # [neg, D]
            wrow = wts.sum(1)

            def batch_loss(v, po, on):
                posd = jnp.einsum("bd,bwd->bw", v, po)
                negd = jnp.einsum("bd,nd->bn", v, on)
                data = (jax.nn.softplus(-posd) * wts).sum() \
                    + (jax.nn.softplus(negd).sum(-1) * wrow).sum()
                if pair_pacing:
                    return data
                return data / jnp.maximum(wrow.sum(), 1.0)

            _, (gv, gp, gn) = jax.value_and_grad(
                batch_loss, argnums=(0, 1, 2))(vin, pos_slab, neg_slab)
            ie = ie.at[centers].add(-lr * gv)
            oe = oe.at[ctx.reshape(-1)].add((-lr * gp).reshape(-1, D))
            oe = oe.at[negs].add(-lr * gn)
            return (ie, oe)

        return jax.lax.fori_loop(0, n_steps, body, (in_emb, out_emb))

    return run
