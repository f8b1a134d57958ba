"""MIX on-mesh: parameter mixing as XLA collectives over ICI.

Reference: the MixServer subsystem (SURVEY.md §3.16) — asynchronous
parameter averaging over a custom Netty TCP protocol, with two combine ops:
  - average:    plain update-count-weighted mean of weights
  - argmin-KLD: precision-weighted mean for covariance-carrying models
    (CW/AROW/SCW) — the KL-minimizing merge of Gaussian weight posteriors.

TPU-native mapping [B]: within a slice, replicas live one-per-device on the
``dp`` mesh axis and mix by ``lax.pmean``/``psum`` at ``-mix_threshold``-step
cadence inside the jitted train loop (sync collectives over ICI at the same
cadence the reference would hit the mix server). Cross-slice/host async mixing
is parallel.mix_service (DCN path).
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P
from ..ops.losses import Loss
from ..ops.optimizers import Optimizer

__all__ = ["mix_average", "argmin_kld_mix", "make_replica_train_step",
           "make_covariance_replica_step"]


def mix_average(w: jnp.ndarray, axis: str = "dp") -> jnp.ndarray:
    """The MixServer 'average' event: plain mean across replicas."""
    return lax.pmean(w, axis)


def argmin_kld_mix(w: jnp.ndarray, covar: jnp.ndarray, axis: str = "dp",
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The 'argminKLD' event (reference: PartialArgminKLD): precision-weighted
    mean — the argmin-KL merge of per-replica Gaussian posteriors
    N(w_i, covar_i). Returns (w_mixed, covar_mixed) where covar_mixed is the
    product-of-Gaussians posterior variance 1/sum(1/covar_i)."""
    prec = 1.0 / covar
    prec_sum = lax.psum(prec, axis)
    w_mixed = lax.psum(w * prec, axis) / prec_sum
    return w_mixed, 1.0 / prec_sum


def make_replica_train_step(mesh: Mesh, loss: Loss, optimizer: Optimizer,
                            mix_every: int = 16) -> Callable:
    """Per-device independent replicas + cadence mixing — the closest TPU
    analog of the reference's map-task replicas attached to a MixServer.

    w: [dp, N] (one replica per device, spec P('dp', None)); the batch is
    sharded over dp. Every ``mix_every`` steps the replicas pmean their
    weights (reference: clock-threshold mix exchange, SURVEY.md §4.3);
    optimizer state stays local, as MixServer never mixed it either.
    """

    def local_step(w, opt_state, t, idx, val, label):
        w = w[0]                                    # [N] local replica
        st = jax.tree_util.tree_map(lambda a: a[0], opt_state)
        margin = (w[idx] * val).sum(-1)
        d = loss.dloss(margin, label)
        g = jnp.zeros_like(w).at[idx.ravel()].add((d[:, None] * val).ravel())
        w2, st = optimizer.update(w, g, st, t)
        do_mix = (t + 1.0) % mix_every == 0.0
        w2 = lax.cond(do_mix, lambda x: lax.pmean(x, "dp"), lambda x: x, w2)
        loss_sum = lax.psum(loss.loss(margin, label).sum(), "dp")
        return (w2[None],
                jax.tree_util.tree_map(lambda a: a[None], st), loss_sum)

    # opt_state entries are [dp, N]-replicated per device as well
    pspec_state = jax.tree_util.tree_map(lambda _: P("dp", None),
                                         optimizer.init(1))

    # check_vma off: the mix branch of lax.cond returns a pmean-replicated
    # value while the skip branch stays device-varying; that asymmetry is
    # exactly the cadence semantics we want.
    return jax.jit(jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(P("dp", None), pspec_state, P(), P("dp", None),
                  P("dp", None), P("dp")),
        out_specs=(P("dp", None), pspec_state, P()),
        check_vma=False))


def make_covariance_replica_step(mesh: Mesh, rates: Callable,
                                 mix_every: int = 16) -> Callable:
    """Covariance-family (CW/AROW/SCW) replicas under a dp mesh with
    argmin-KLD mixing — the MixServer 'argminKLD' event as an ICI
    collective (reference: PartialArgminKLD folded by the server; SURVEY
    §3.16/§3.17). Each device trains a local (w, sigma) on its batch shard
    with the closed-form aggregate update (models.classifier._make_step
    math); every ``mix_every`` steps the replicas merge by precision
    weighting.

    w, sigma: [dp, N]; rates(margin_y, v) -> (alpha, beta) is the
    trainer's closed-form rate fn (e.g. AROWTrainer()._rates()).
    """

    def local_step(w, sigma, t, idx, val, label):
        w, sigma = w[0], sigma[0]
        wg = w[idx]
        m = (wg * val).sum(-1) * label
        sg = sigma[idx]
        v = (sg * val * val).sum(-1)
        alpha, beta = rates(m, v)
        dw = jnp.zeros_like(w).at[idx.ravel()].add(
            ((alpha * label)[:, None] * sg * val).ravel())
        ds = jnp.zeros_like(sigma).at[idx.ravel()].add(
            (beta[:, None] * (sg * val) ** 2).ravel())
        w2 = w + dw
        sig2 = jnp.maximum(sigma - ds, 1e-8)
        do_mix = (t + 1.0) % mix_every == 0.0

        def mix(args):
            return argmin_kld_mix(args[0], args[1], "dp")

        w2, sig2 = lax.cond(do_mix, mix, lambda a: a, (w2, sig2))
        loss_sum = lax.psum(
            jnp.maximum(0.0, 1.0 - m).sum(), "dp")
        return w2[None], sig2[None], loss_sum

    return jax.jit(jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(P("dp", None), P("dp", None), P(), P("dp", None),
                  P("dp", None), P("dp")),
        out_specs=(P("dp", None), P("dp", None), P()),
        check_vma=False))
