"""train_ffm as the configuration states it: a field-aware factorization
machine (Juan et al., RecSys 2016) on unit-valued rows with one feature
per field, logloss on +-1 labels, minibatch AdaGrad, tables stored in
bfloat16 under -halffloat, arithmetic in float32.

    phi(x) = w0 + sum_i w_i + sum_{i<j} <v_{i, f_j}, v_{j, f_i}>

Every feature id owns one table row, found by the 32-bit mix below (ids
that collide share a row, as hashed features do); a row holds the
feature's latent vector for every field and its linear weight. L2 is
added per occurrence over the whole row, own-field vector included: the
system's documented semantics (upstream regularises only the pair
entries it updates)."""

from __future__ import annotations

import numpy as np

from . import common

LEAVES = ("V", "w", "w0")
_J1, _J3 = 0x9E3779B1, 0xC2B2AE35


def row_of(ids: np.ndarray, n_rows: int) -> np.ndarray:
    h = ids.astype(np.uint32) * np.uint32(_J1)
    h = h ^ (h >> np.uint32(15))
    h = h * np.uint32(_J3)
    h = h ^ (h >> np.uint32(13))
    return (h & np.uint32(n_rows - 1)).astype(np.int64)


def n_rows(cfg: dict) -> int:
    o = cfg["model"]
    f_pow2 = 1
    while f_pow2 < int(o["fields"]):
        f_pow2 <<= 1
    return max(1 << 10, int(o["dims"]) // f_pow2)


def table_keys(cfg: dict, ids: np.ndarray) -> np.ndarray:
    return np.unique(row_of(ids, n_rows(cfg)))


def initial_rows(cfg: dict, seed: int, keys: np.ndarray) -> np.ndarray:
    """The seed's initial latent rows at `keys`, float32 [U, F * K]."""
    o = cfg["model"]
    return common.init_rows(seed, n_rows(cfg),
                            int(o["fields"]) * int(o["factors"]),
                            float(o["sigma"]), keys)


def run(cfg: dict, seed: int, ids: np.ndarray, labels: np.ndarray, *,
        precision: str = "", fault: str = "", extra_ids=None, init=None) -> dict:
    """`ids` [S, B, F] with slot j holding field j's feature."""
    import jax
    import jax.numpy as jnp
    o = cfg["model"]
    K, F = int(o["factors"]), int(o["fields"])
    if ids.shape[-1] != F:
        raise ValueError(f"rows have {ids.shape[-1]} features, not one for "
                         f"each of {F} fields")
    lam0, lam_w, lam_v = (float(o[n]) for n in
                          ("lambda0", "lambda_w", "lambda_v"))
    precision = precision or ("bfloat16_store" if o.get("halffloat")
                              else "float32")
    sdt, cdt = common.PRECISIONS[precision]
    Mr = n_rows(cfg)
    import time
    clock = [time.perf_counter()]
    keys = table_keys(cfg, ids if extra_ids is None else np.concatenate(
        [ids.reshape(-1), np.asarray(extra_ids).reshape(-1)]))
    inv = np.searchsorted(keys, row_of(ids, Mr)).astype(np.int32)
    n_keys, U = len(keys), common.padded(keys)
    V0 = np.zeros((U, F * K), np.float32)  # on the host: no shape of
    V0[:n_keys] = initial_rows(cfg, seed, keys) if init is None else init
    V0 = common.store(jnp.asarray(V0), sdt)  # this seed's own to compile
    state = {"V": V0, "w": jnp.zeros(U), "w0": jnp.zeros(())}
    gg = {n: jnp.zeros_like(v) for n, v in state.items()}

    def batch_loss(w0, wg, Vg, y):              # Vg [B, slot, F * K]
        wg = wg.astype(cdt)
        Vg = Vg.astype(cdt).reshape(Vg.shape[0], F, F, K)
        full = jnp.einsum("bgfk,bfgk->b", Vg, Vg)
        own = jnp.einsum("bggk->bgk", Vg)
        phi = (w0.astype(cdt) + wg.sum(1)
               + 0.5 * (full - (own * own).sum((1, 2))))
        return jax.nn.softplus(-phi.astype(jnp.float32) * y).sum()

    @jax.jit
    def step(state, gg, t, ix, y):
        if fault == "half_batch":
            ix, y = ix[: ix.shape[0] // 2], y[: y.shape[0] // 2]
        wg, Vg = state["w"][ix], state["V"][ix]
        loss, (g0, gw, gV) = jax.value_and_grad(batch_loss, (0, 1, 2))(
            state["w0"], wg, Vg, y)
        if fault == "half_batch":
            loss, g0, gw, gV = 2 * loss, 2 * g0, 2 * gw, 2 * gV
        g0 = g0 + lam0 * state["w0"]
        gw = gw.astype(jnp.float32) + lam_w * wg
        gV = gV.astype(jnp.float32) + lam_v * Vg
        G = {"w0": g0,
             "w": jax.ops.segment_sum(gw.reshape(-1), ix.reshape(-1), U),
             "V": jax.ops.segment_sum(gV.reshape(-1, F * K),
                                      ix.reshape(-1), U)}
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
    """P(y = +1) of rows `ids` [N, F] (slot j = field j) under the state
    `run` returned (its `extra_ids` must have covered them)."""
    at = np.searchsorted(ref["keys"], row_of(ids, n_rows(cfg)))
    a = ref["after"]
    F, K = int(cfg["model"]["fields"]), int(cfg["model"]["factors"])
    Vg = a["V"][at].astype(np.float64).reshape(len(at), F, F, K)
    full = np.einsum("bgfk,bfgk->b", Vg, Vg)
    own = np.einsum("bggk->bgk", Vg)
    phi = (float(a["w0"]) + a["w"][at].astype(np.float64).sum(1)
           + 0.5 * (full - (own * own).sum((1, 2))))
    return 1.0 / (1.0 + np.exp(-phi))
