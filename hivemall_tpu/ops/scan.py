"""Fused multi-step dispatch — K optimizer steps in ONE jitted lax.scan.

Why: with host prep off the critical path (io/pipeline.py), what a training
loop still pays per minibatch is DISPATCH: one Python->jit call, one h2d
transfer, and (absent donation across calls) an XLA copy of the dims-sized
tables per step. The reference amortizes per-ROW overhead by
buffering rows into minibatches (LearnerBaseUDTF's miniBatchSize); the
TPU-native analog amortizes per-BATCH overhead by buffering minibatches
into device-resident megasteps — the step-fusion idiom pjit training loops
use to hide dispatch latency.

Contract: every trainer step is a pure function

    (state1, state2, t, *batch_args) -> (state1, state2, loss_sum[, stats])

with ``state1`` the model params (or weight table), ``state2`` the
optimizer state, ``t`` the float global step, and batch args in canonical
order ``idx, [val,] label, row_mask[, field | lams]``. ``stats``, where a
step returns it, is a dict of int32 scalars the step counts about itself
(ops/fm.py ``TAIL_STATS``); the megastep stacks it [K] beside the losses.
The jitted K=1 wrapper and the K>1 scan body run the SAME function —
:func:`scannable` attaches the unjitted core to its jitted wrapper, and
:func:`make_megastep` scans that core over a stacked [K, ...] window with
the state threaded through the scan carry and ``donate_argnums`` on the
megastep itself, so XLA updates the tables in place across all K steps
instead of copying them per step.

Row-validity travels as an ``nv`` [K] int32 vector; the float row mask the
K=1 path transfers per batch is rebuilt on device (``arange(B) < nv`` —
identical values, 4*B fewer bytes per step on the link).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

__all__ = ["scannable", "make_megastep", "megastep_for", "SCOPES"]

# Phase scopes (jax.named_scope) name the step's device time from inside:
# hm.gather, hm.grad, hm.scatter, hm.update in the step bodies (ops/fm.py,
# ops/linear.py) and hm.scan around the scan here. A scope is metadata, and
# the persistent compile cache's key leaves metadata out: a program compiled
# before its scopes changed is served from the cache with the OLD names in
# its trace. So the vocabulary's version is part of the megastep's name,
# which the key does hold. Bump it in a change that alters scopes and
# nothing else of the program (a change to the program itself gets a new
# key anyway).
SCOPES = "hm2"


def scopes_in_name(fn):
    """Decorator, applied UNDER ``jax.jit``: the jitted module is named
    ``<fn>_<SCOPES>``."""
    fn.__name__ = f"{fn.__name__}_{SCOPES}"
    return fn


def scannable(step, core):
    """Attach the pure ``(state, batch) -> (state, loss)`` core to its
    jitted K=1 wrapper so the K>1 scan path runs the SAME function the
    K=1 path compiled (``jit``-of-core inlines under the scan trace)."""
    step.core = core
    return step


def make_megastep(core, *, none_val: bool = False, finish=None):
    """Build the jitted K-step megastep around one scannable core.

    Signature: ``megastep(s1, s2, t0, nv, idx, val, label, field, lams)``
    with ``idx`` [K, B, L], ``label`` [K, B], ``nv`` [K] int32, and
    ``val``/``field`` either stacked [K, B, L] arrays or None (None is
    static under jit — each presence pattern is its own compiled variant,
    exactly like the K=1 steps' unit-value elision). ``lams`` is a
    non-scanned broadcast extra (train_fm's -adareg runtime lambdas).
    ``none_val=True`` marks cores whose signature keeps a ``val``
    parameter that receives None under unit-value elision (linear/FM);
    False marks cores with no val parameter at all (the dedicated
    unit-val FFM variants).

    Returns ``(s1, s2, losses[K])`` — per-step loss sums, accumulated on
    device; the caller folds them at its existing cadence so no step ever
    blocks the host — and the core's ``stats``, stacked [K], where it
    returns any.

    ``finish(s1, s2, t) -> (s1, s2)``, where the step has one (ops/linear.py
    ``step.finish``), runs once after the scan with the last step's t: what
    a core leaves stale inside a dispatch is rebuilt there.
    """

    @partial(jax.jit, donate_argnums=(0, 1))
    @scopes_in_name
    def megastep(s1, s2, t0, nv, idx, val, label, field, lams):
        B = label.shape[1]
        xs = {"nv": nv, "idx": idx, "label": label}
        if val is not None:
            xs["val"] = val
        if field is not None:
            xs["field"] = field

        def body(carry, x):
            p, s, t = carry
            mask = (jnp.arange(B) < x["nv"]).astype(jnp.float32)
            args = [x["idx"]]
            if val is not None:
                args.append(x["val"])
            elif none_val:
                args.append(None)
            args += [x["label"], mask]
            if field is not None:
                args.append(x["field"])
            if lams is not None:
                args.append(lams)
            p, s, *out = core(p, s, t, *args)
            return (p, s, t + 1.0), tuple(out)

        # the slicing and stacking the scan adds is named too
        with jax.named_scope("hm.scan"):
            (s1, s2, _), out = jax.lax.scan(body, (s1, s2, t0), xs)
        if finish is not None:
            s1, s2 = finish(s1, s2, t0 + (label.shape[0] - 1.0))
        return (s1, s2, *out)

    return megastep


# keyed on the STEP OBJECT: the per-trainer steps are config-cached
# (models/fm.py lru_caches, models/base.shared_step), so same-config
# trainer instances converge on one compiled megastep exactly as they
# share one compiled K=1 step. Bounded like those caches.
_MEGASTEP_CACHE: dict = {}


def _profiled_megastep(mega):
    """Wrap one jitted megastep with the devprof dispatch boundary: after
    each fused dispatch, track the device allocator's peak-bytes
    high-water mark (obs.devprof — docs/OBSERVABILITY.md "Training
    profiling"). One attribute check per dispatch when profiling is
    inactive; the jitted fn (and its donate_argnums) is untouched."""
    from functools import wraps

    from ..obs.devprof import get_devprof
    dp = get_devprof()

    @wraps(mega)
    def wrapped(*args):
        out = mega(*args)
        dp.note_megastep()
        return out

    return wrapped


def megastep_for(step, *, none_val: bool = False):
    """Shared megastep for a (config-cached) trainer step."""
    key = (step, none_val)
    fn = _MEGASTEP_CACHE.get(key)
    if fn is None:
        if len(_MEGASTEP_CACHE) >= 128:
            _MEGASTEP_CACHE.pop(next(iter(_MEGASTEP_CACHE)))
        import time

        from ..obs.devprof import get_devprof
        t0 = time.perf_counter()
        fn = _profiled_megastep(
            make_megastep(getattr(step, "core", step), none_val=none_val,
                          finish=getattr(step, "finish", None)))
        _MEGASTEP_CACHE[key] = fn
        get_devprof().record_build("scan", "megastep",
                                   time.perf_counter() - t0)
    return fn
