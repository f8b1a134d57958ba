"""train_mf_adagrad's two tables: `params["U"]` (users) and `params["I"]`
(items), a row stored 128 lanes, the k factors and then the bias; AdaGrad's
sums in `opt_state[table]["gg"]`, co-shaped."""

from __future__ import annotations

import numpy as np

from .common import gather_rows

LEAVES = {"U": ("P", "bu"), "I": ("Q", "bi")}


def read_rows(trainer, keys: dict) -> dict:
    """{"value": {leaf: rows}, "gg": {leaf: AdaGrad's sums}} at keys["U"]
    (users) and keys["I"] (items)."""
    k = trainer.k
    out = {"value": {}, "gg": {}}
    for table, (fac, bias) in LEAVES.items():
        at = np.asarray(keys[table], np.int64)
        for part, src in (("value", trainer.params[table]),
                          ("gg", trainer.opt_state[table]["gg"])):
            rows = gather_rows(src, at)
            out[part][fac], out[part][bias] = rows[:, :k], rows[:, k]
    return out
