"""What the FM and FFM references share: minibatch AdaGrad over compact
tables, the learning-rate schedule, storage precisions.

A reference holds only the table rows the given batches touch ("compact"
tables): a row no batch touches has a zero summed gradient, and AdaGrad
with a zero gradient leaves weight and accumulator as they were, so the
compact run is the whole-table run read at those rows."""

from __future__ import annotations

import numpy as np

EPS = 1e-6                       # AdaGrad's denominator offset

# name -> (storage dtype, compute dtype); "float32" and "bfloat16" are what
# configurations state, the rest are the controls' lower precisions
PRECISIONS = {
    "float32": ("float32", "float32"),
    "bfloat16_store": ("bfloat16", "float32"),   # -halffloat: bf16 tables
    "bfloat16": ("bfloat16", "bfloat16"),
    "fp8_store": ("float8_e4m3fn", "float32"),
}


def eta(t, eta0: float, power_t: float):
    """Hivemall's default `inverse` schedule: eta0 / (1 + t)^power_t."""
    import jax.numpy as jnp
    return eta0 / jnp.power(1.0 + t, power_t)


def store(x, dtype: str):
    """Round to the storage precision, carry on in float32."""
    import jax.numpy as jnp
    if dtype == "float32":
        return x
    return x.astype(getattr(jnp, dtype)).astype(jnp.float32)


def adagrad(param, gg, g, lr, dtype: str):
    gg = gg + g * g
    import jax.numpy as jnp
    return store(param - lr * g / (jnp.sqrt(gg) + EPS), dtype), gg


LANES = 128


def init_rows(seed: int, n_rows: int, width: int, sigma: float, keys):
    """The rows `keys` of normal(PRNGKey(seed), [n_rows, width]) * sigma:
    the initial latent table both sides draw from the seed. JAX's
    generator is not addressable by row, so the whole table is drawn and
    dropped again once the rows are taken. It is drawn FLAT and viewed as
    rows of 128: the flat draw gives the same numbers as the
    two-dimensional one (the generator counts elements in row-major
    order), a TPU neither pads it ([2^26, 5] floats would take 34 GB
    there) nor gathers it element by element, and the wanted elements are
    picked out of the fetched 128-wide rows on the host. A table of 2^31
    elements or more is drawn on the host's CPU backend."""
    import contextlib
    import jax
    import jax.numpy as jnp
    n, w = int(n_rows), int(width)
    total = n * w
    small = total < 2 ** 31 and total % LANES == 0
    where = (contextlib.nullcontext() if small
             else jax.default_device(jax.devices("cpu")[0]))
    with where:
        key = jax.random.PRNGKey(int(seed))
        if not small:
            full = jax.random.normal(key, (n, w)) * float(sigma)
            return np.asarray(full[jnp.asarray(keys)])
        full = (jax.random.normal(key, (total,)) * float(sigma)).reshape(
            total // LANES, LANES)
        first = np.asarray(keys, np.int64) * w          # flat start of a row
        span = (w + LANES - 2) // LANES + 1             # 128-rows it can touch
        need = np.unique((first // LANES)[:, None] + np.arange(span))
        need = need[need < total // LANES]
        # how many rows are needed differs from seed to seed and a gather
        # compiles per shape: the index is padded to a power of two, so
        # that every seed's gather is one program in the compile cache
        at = np.zeros(max(1024, 1 << (len(need) - 1).bit_length()), np.int32)
        at[:len(need)] = need
        got = np.asarray(full[jnp.asarray(at)])[:len(need)]
    del full
    # a row's elements lie in consecutive 128-rows, which are consecutive in
    # `need` too: one search per row, then offsets
    base = np.searchsorted(need, first // LANES)
    off = (first % LANES)[:, None] + np.arange(w)
    return got.reshape(-1)[(base[:, None] * LANES + off)]


def padded(keys: np.ndarray, multiple: int = 1 << 16) -> int:
    """Table rows to allocate for `keys`: rounded up, so that runs on
    different seeds share shapes and the compile cache serves them."""
    return -(-len(keys) // multiple) * multiple


def leaf_norm(x) -> float:
    return float(np.sqrt(np.sum(np.square(np.asarray(x, np.float64)))))
