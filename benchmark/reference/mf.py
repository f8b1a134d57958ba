"""train_mf_adagrad as the configuration states it: biased matrix
factorization (Funk; Koren et al. 2009), rank k, one rating an example.

    pred(u, i) = mu + p_u . q_i + b_u + b_i
    loss       = sum over a step's ratings of 1/2 (r - pred)^2
                 + lambda (|p_u|^2 + |q_i|^2 + b_u^2 + b_i^2)

L2 on the rows read, once for every rating that reads them; the gradient of
a row's ratings summed over the step; per-element AdaGrad with a fixed rate:
gg += g^2, x -= eta0 g / (sqrt(gg) + 1e-6). The initial factors are
normal(0, sigma), the user table's drawn with the first half of
split(PRNGKey(seed)) as one [users, k] draw, the item table's with the
second; biases and accumulators start at zero. Float32 throughout, the
products under `jax.default_matmul_precision("highest")`.

Like the FM reference it holds only the rows the given steps touch
("compact" tables): AdaGrad leaves a row with a zero gradient as it was."""

from __future__ import annotations

from functools import partial

import numpy as np

from . import common

LEAVES = ("P", "bu", "Q", "bi")
TABLES = {"U": ("P", "bu", "users"), "I": ("Q", "bi", "items")}


def table_keys(cfg: dict, ids: np.ndarray) -> dict:
    """The rows a batch of (user, item) ids [..., 2] touches, per table."""
    return {"U": np.unique(ids[..., 0]), "I": np.unique(ids[..., 1])}


def initial_rows(cfg: dict, seed: int, keys: dict) -> dict:
    """The seed's initial factor rows at `keys`, float32 [n, k] a table.
    Each table is drawn whole, as the statement says, and the rows are
    taken where it lies (on the chip: 2.5 GB for the cell's users)."""
    import jax
    import jax.numpy as jnp
    o = cfg["model"]
    k, sigma = int(o["factors"]), float(o["sigma"])
    parts = dict(zip(TABLES, jax.random.split(jax.random.PRNGKey(int(seed)))))

    @partial(jax.jit, static_argnums=(1,))
    def draw(part, rows, at):
        return (jax.random.normal(part, (rows, k)) * sigma)[at]

    out = {}
    for t, (_, _, size) in TABLES.items():
        at = np.asarray(keys[t], np.int64)
        # padded to a power of two: every seed's gather is one program
        padded = np.zeros(max(1024, 1 << (len(at) - 1).bit_length()),
                          np.int32)
        padded[:len(at)] = at
        out[t] = np.asarray(draw(parts[t], int(o[size]),
                                 jnp.asarray(padded)))[:len(at)]
    return out


def run(cfg: dict, seed: int, ids: np.ndarray, ratings: np.ndarray, *,
        precision: str = "", fault: str = "", init=None) -> dict:
    """Follow `ids` [S, B, 2] (user, item), `ratings` [S, B] for S steps
    from the seed's initial tables. Returns per-step loss sums and, for
    each leaf, the state before, after and the AdaGrad accumulator, at
    `keys`. `fault` "half_ratings": the second half of every step never
    arrives, and the first half's loss and gradients are doubled."""
    import time

    import jax
    import jax.numpy as jnp
    o = cfg["model"]
    k, mu, lam, eta0 = int(o["factors"]), float(o["mu"]), \
        float(o["lambda"]), float(o["eta0"])
    sdt, cdt = common.PRECISIONS[precision or "float32"]
    clock = [time.perf_counter()]
    keys = table_keys(cfg, ids)
    if init is None:
        init = initial_rows(cfg, seed, keys)
    at = {t: np.searchsorted(keys[t], ids[..., j]).astype(np.int32)
          for j, t in enumerate(TABLES)}                      # [S, B]
    state, n_keys = {}, {}
    for t, (fac, bias, _) in TABLES.items():
        n_keys[t], rows = len(keys[t]), common.padded(keys[t])
        f0 = np.zeros((rows, k), np.float32)     # padded: one shape for
        f0[:n_keys[t]] = init[t]                 # every seed's compile
        state[fac] = common.store(jnp.asarray(f0), sdt)
        state[bias] = jnp.zeros(rows)
    gg = {n: jnp.zeros_like(v) for n, v in state.items()}

    def batch_loss(s, iu, ii, r):
        pu, qi = s["P"][iu].astype(cdt), s["Q"][ii].astype(cdt)
        bu, bi = s["bu"][iu].astype(cdt), s["bi"][ii].astype(cdt)
        pred = (mu + (pu * qi).sum(-1) + bu + bi).astype(jnp.float32)
        err = r - pred
        reg = ((pu * pu).sum() + (qi * qi).sum() + (bu * bu).sum()
               + (bi * bi).sum()).astype(jnp.float32)
        return 0.5 * (err * err).sum() + lam * reg

    @jax.jit
    def step(s, gg, iu, ii, r):
        if fault == "half_ratings":
            h = iu.shape[0] // 2
            iu, ii, r = iu[:h], ii[:h], r[:h]
        loss, g = jax.value_and_grad(batch_loss)(s, iu, ii, r)
        if fault == "half_ratings":
            loss, g = 2 * loss, jax.tree_util.tree_map(lambda x: 2 * x, g)
        new, ngg = {}, {}
        for n in LEAVES:
            new[n], ngg[n] = common.adagrad(s[n], gg[n],
                                            g[n].astype(jnp.float32), eta0,
                                            sdt)
        return new, ngg, loss

    def rows(tree):                      # without the padding rows
        return {n: np.asarray(tree[n])[:n_keys[t]]
                for t, names in TABLES.items() for n in names[:2]}

    first = rows(state)
    clock.append(time.perf_counter())
    losses = []
    with jax.default_matmul_precision("highest"):
        for s in range(ids.shape[0]):
            state, gg, loss = step(state, gg, jnp.asarray(at["U"][s]),
                                   jnp.asarray(at["I"][s]),
                                   jnp.asarray(ratings[s], jnp.float32))
            losses.append(float(loss))
    clock.append(time.perf_counter())
    return {"keys": keys, "losses": losses, "before": first,
            "seconds": {"init": clock[1] - clock[0],
                        "steps": clock[2] - clock[1]},
            "after": rows(state), "gg": rows(gg)}
