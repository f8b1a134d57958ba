"""train_fm as the configuration states it: a degree-2 factorization
machine (Rendle 2010) on unit-valued features, logloss on +-1 labels,
minibatch AdaGrad (accumulators see the summed batch gradient), L2 added
per occurrence of a feature, float32 throughout.

    phi(x) = w0 + sum_i w_i + 1/2 sum_f [(sum_i v_if)^2 - sum_i v_if^2]
"""

from __future__ import annotations

import numpy as np

from . import common

LEAVES = ("V", "w", "w0")


def table_keys(cfg: dict, ids: np.ndarray) -> np.ndarray:
    """The table rows a batch of feature ids touches: the ids themselves."""
    return np.unique(ids)


def initial_rows(cfg: dict, seed: int, keys: np.ndarray) -> np.ndarray:
    """The seed's initial latent rows at `keys`, float32 [U, k]."""
    o = cfg["model"]
    return common.init_rows(seed, int(o["dims"]), int(o["factors"]),
                            float(o["sigma"]), keys)


def run(cfg: dict, seed: int, ids: np.ndarray, labels: np.ndarray, *,
        precision: str = "", fault: str = "", extra_ids=None, init=None,
        _on_host: bool = False) -> dict:
    """Follow `ids` [S, B, F], `labels` [S, B] for S steps from the seed's
    initial table. Returns per-step loss sums and, for each leaf, the
    state before, after and the AdaGrad accumulator, at `keys`."""
    import jax
    import jax.numpy as jnp
    o = cfg["model"]
    k, dims = int(o["factors"]), int(o["dims"])
    lam0, lam_w, lam_v = (float(o[n]) for n in
                          ("lambda0", "lambda_w", "lambda_v"))
    sdt, cdt = common.PRECISIONS[precision or "float32"]
    if jax.default_backend() != "cpu" and not _on_host:
        # rows of 5 floats are the worst shape a TPU can be given (each
        # pads to 128 lanes and is gathered element by element): the
        # initial rows are drawn on the chip, the arithmetic runs on the
        # host's CPU backend
        if init is None:
            init = initial_rows(cfg, seed, table_keys(
                cfg, ids if extra_ids is None else np.concatenate(
                    [ids.reshape(-1), np.asarray(extra_ids).reshape(-1)])))
        with jax.default_device(jax.devices("cpu")[0]):
            return run(cfg, seed, ids, labels, precision=precision,
                       fault=fault, extra_ids=extra_ids, init=init,
                       _on_host=True)
    import time
    clock = [time.perf_counter()]
    keys = table_keys(cfg, ids if extra_ids is None else np.concatenate(
        [ids.reshape(-1), np.asarray(extra_ids).reshape(-1)]))
    inv = np.searchsorted(keys, ids).astype(np.int32)        # [S, B, F]
    n_keys, U = len(keys), common.padded(keys)
    V0 = np.zeros((U, k), np.float32)       # on the host: no shape of
    V0[:n_keys] = initial_rows(cfg, seed, keys) if init is None else init
    V0 = common.store(jnp.asarray(V0), sdt)  # this seed's own to compile
    state = {"V": V0, "w": jnp.zeros(U), "w0": jnp.zeros(())}
    gg = {n: jnp.zeros_like(v) for n, v in state.items()}

    def batch_loss(w0, wg, Vg, y):
        wg, Vg = wg.astype(cdt), Vg.astype(cdt)
        s = Vg.sum(1)
        phi = (w0.astype(cdt) + wg.sum(1)
               + 0.5 * (s * s - (Vg * Vg).sum(1)).sum(-1))
        return jax.nn.softplus(-phi.astype(jnp.float32) * y).sum()

    @jax.jit
    def step(state, gg, t, ix, y):
        if fault == "half_batch":          # the second half never arrives
            ix, y = ix[: ix.shape[0] // 2], y[: y.shape[0] // 2]
        wg, Vg = state["w"][ix], state["V"][ix]
        loss, (g0, gw, gV) = jax.value_and_grad(batch_loss, (0, 1, 2))(
            state["w0"], wg, Vg, y)
        if fault == "half_batch":          # ... and the mean is rescaled
            loss, g0, gw, gV = 2 * loss, 2 * g0, 2 * gw, 2 * gV
        g0 = g0 + lam0 * state["w0"]
        gw = gw.astype(jnp.float32) + lam_w * wg
        gV = gV.astype(jnp.float32) + lam_v * Vg
        G = {"w0": g0,
             "w": jax.ops.segment_sum(gw.reshape(-1), ix.reshape(-1), U),
             "V": jax.ops.segment_sum(gV.reshape(-1, k), ix.reshape(-1), U)}
        lr = common.eta(t, float(o["eta0"]), float(o["power_t"]))
        new, ngg = {}, {}
        for n in LEAVES:
            new[n], ngg[n] = common.adagrad(state[n], gg[n], G[n], lr, sdt)
        return new, ngg, loss

    def rows(tree):                      # without the padding rows
        return {n: np.asarray(v)[:n_keys] if np.ndim(v) else np.asarray(v)
                for n, v in tree.items()}

    first = rows(state)
    clock.append(time.perf_counter())
    losses = []
    with jax.default_matmul_precision("highest"):
        for s in range(ids.shape[0]):
            state, gg, loss = step(state, gg, float(s), jnp.asarray(inv[s]),
                                   jnp.asarray(labels[s]))
            losses.append(float(loss))
    clock.append(time.perf_counter())
    return {"keys": keys, "losses": losses, "before": first,
            "seconds": {"init": clock[1] - clock[0],
                        "steps": clock[2] - clock[1]},
            "after": rows(state), "gg": rows(gg)}


def score(cfg: dict, ref: dict, ids: np.ndarray) -> np.ndarray:
    """P(y = +1) of rows `ids` [N, F] under the state `run` returned (its
    `extra_ids` must have covered them)."""
    at = np.searchsorted(ref["keys"], ids)
    a = ref["after"]
    Vg = a["V"][at].astype(np.float64)
    s = Vg.sum(1)
    phi = (float(a["w0"]) + a["w"][at].astype(np.float64).sum(1)
           + 0.5 * (s * s - (Vg * Vg).sum(1)).sum(-1))
    return 1.0 / (1.0 + np.exp(-phi))
