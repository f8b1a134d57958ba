"""train_fm's fused table: `params["T"]` packs P logical rows of width W
(k factors, then the linear weight) into one 128-lane row."""

from __future__ import annotations

import numpy as np

from .common import gather_rows, host


def read_rows(trainer, keys: np.ndarray) -> dict:
    """{"value": {leaf: rows}, "gg": {leaf: AdaGrad's sums}} at `keys`."""
    keys = np.asarray(keys, np.int64)
    k, W, P = trainer.k, trainer.W, trainer.P
    phys, sub = keys // P, keys % P
    take = np.arange(len(keys))

    def rows(table):                       # packed [Np, P*W] -> [U, W]
        return gather_rows(table, phys).reshape(len(keys), P, W)[take, sub]

    T = rows(trainer.params["T"])
    G = rows(trainer.opt_state["T"]["gg"])
    return {"value": {"V": T[:, :k], "w": T[:, k],
                      "w0": host(trainer.params["w0"])},
            "gg": {"V": G[:, :k], "w": G[:, k],
                   "w0": host(trainer.opt_state["w0"]["gg"])}}
