"""What every family's reader shares."""

from __future__ import annotations

import numpy as np


def host(x) -> np.ndarray:
    import jax.numpy as jnp
    return np.asarray(x.astype(jnp.float32))


def gather_rows(table, at: np.ndarray) -> np.ndarray:
    """`table[at]` as float32 on the host. How many rows a dispatch touches
    differs from seed to seed, and a gather compiles per shape: the index
    is padded to the next power of two (with row 0, dropped again), so
    that every seed's gather is the one program the compile cache holds."""
    import jax.numpy as jnp
    n = len(at)
    padded = np.zeros(max(1024, 1 << (n - 1).bit_length()), np.int32)
    padded[:n] = at
    return host(table[jnp.asarray(padded)])[:n]
