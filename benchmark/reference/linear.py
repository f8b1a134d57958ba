"""train_classifier -loss logloss -opt adagrad as the configuration states
it: hashed logistic regression on unit-valued features, logloss on +-1
labels, and AdaGrad-RDA (Hivemall's own default `-reg rda` turns `-opt
adagrad` into it): Xiao's l1 regularised dual averaging (2010) with
AdaGrad's per-coordinate scaling (Duchi, Hazan and Singer 2011), float32
throughout.

    phi(x) = sum_i w_i                       (one unit-valued slot an id)
    g      = sum over the batch of dloss/dphi, per slot (SUMMED)
    u += g;  gg += g * g
    w  = -sign(u) * eta(t) * (t+1) * max(0, |u|/(t+1) - lambda)
                  / (sqrt(gg) + eps)         at every slot, every step
    eta(t) = eta0 / (1 + t)^power_t          (the `inverse` scheme)

The departure from Hivemall, which the configuration lists under `assumed`:
Hivemall updates a row at a time and touches only that row's features; here
a minibatch's gradients are summed per slot before `u` and `gg` see them,
and every slot's weight is re-materialised from `u`, `gg` and `t` at every
step. A slot no batch touched has u = 0, so its weight is 0 at every t: the
compact tables below (the slots the given batches touch) are the whole
table read at those slots. The initial table is zeros, so the seed changes
the data and nothing of the model, and the first step's loss is
B * ln 2 on every side."""

from __future__ import annotations

import numpy as np

from . import common

LEAVES = ("w",)


def table_keys(cfg: dict, ids: np.ndarray) -> np.ndarray:
    """The table slots a batch of feature ids touches: the ids themselves."""
    return np.unique(ids)


def initial_rows(cfg: dict, seed: int, keys: np.ndarray) -> np.ndarray:
    """The initial weights at `keys`: zeros, whatever the seed."""
    return np.zeros(len(keys), np.float32)


def run(cfg: dict, seed: int, ids: np.ndarray, labels: np.ndarray, *,
        precision: str = "", fault: str = "", extra_ids=None,
        init=None) -> dict:
    """Follow `ids` [S, B, F], `labels` [S, B] for S steps from the zero
    table. Returns per-step loss sums and the weight before and after and
    AdaGrad's sum of squares, at `keys`. The arithmetic runs on the host's
    CPU backend, so it is the same beside any device."""
    import contextlib
    import time

    import jax
    import jax.numpy as jnp
    o = cfg["model"]
    lam, eta0, power_t = (float(o[n]) for n in ("lambda", "eta0", "power_t"))
    sdt, cdt = common.PRECISIONS[precision or "float32"]
    clock = [time.perf_counter()]
    keys = table_keys(cfg, ids if extra_ids is None else np.concatenate(
        [ids.reshape(-1), np.asarray(extra_ids).reshape(-1)]))
    inv = np.searchsorted(keys, ids).astype(np.int32)        # [S, B, F]
    n_keys, U = len(keys), common.padded(keys)
    w0 = np.zeros(U, np.float32)
    w0[:n_keys] = initial_rows(cfg, seed, keys) if init is None else init

    def batch_loss(wg, y):
        phi = wg.astype(cdt).sum(1)
        return jax.nn.softplus(-phi.astype(jnp.float32) * y).sum()

    @jax.jit
    def step(w, u, gg, t, ix, y):
        if fault == "half_batch":          # the second half never arrives
            ix, y = ix[: ix.shape[0] // 2], y[: y.shape[0] // 2]
        loss, gw = jax.value_and_grad(batch_loss)(w[ix], y)
        if fault == "half_batch":          # ... and the mean is rescaled
            loss, gw = 2 * loss, 2 * gw
        g = jax.ops.segment_sum(gw.astype(jnp.float32).reshape(-1),
                                ix.reshape(-1), U)
        u, gg, tt = u + g, gg + g * g, t + 1.0
        shrunk = jnp.maximum(0.0, jnp.abs(u) / tt - lam)
        w = -jnp.sign(u) * common.eta(t, eta0, power_t) * tt * shrunk \
            / (jnp.sqrt(gg) + common.EPS)
        return common.store(w, sdt), u, gg, loss

    def rows(x):                         # without the padding slots
        return {"w": np.asarray(x)[:n_keys]}

    where = (contextlib.nullcontext() if jax.default_backend() == "cpu"
             else jax.default_device(jax.devices("cpu")[0]))
    with where, jax.default_matmul_precision("highest"):
        w = common.store(jnp.asarray(w0), sdt)
        u, gg = jnp.zeros(U), jnp.zeros(U)
        first = rows(w)
        clock.append(time.perf_counter())
        losses = []
        for s in range(ids.shape[0]):
            w, u, gg, loss = step(w, u, gg, float(s), jnp.asarray(inv[s]),
                                  jnp.asarray(labels[s]))
            losses.append(float(loss))
        after, sums = rows(w), rows(gg)
    clock.append(time.perf_counter())
    return {"keys": keys, "losses": losses, "before": first,
            "seconds": {"init": clock[1] - clock[0],
                        "steps": clock[2] - clock[1]},
            "after": after, "gg": sums}


def score(cfg: dict, ref: dict, ids: np.ndarray) -> np.ndarray:
    """P(y = +1) of rows `ids` [N, F] under the state `run` returned (its
    `extra_ids` must have covered them)."""
    at = np.searchsorted(ref["keys"], ids)
    phi = ref["after"]["w"][at].astype(np.float64).sum(1)
    return 1.0 / (1.0 + np.exp(-phi))
