"""train_ffm's joint table: one row per hashed slot, F*K field-aware
factors and then the linear weight."""

from __future__ import annotations

import numpy as np

from .common import gather_rows, host


def read_rows(trainer, keys: np.ndarray) -> dict:
    """{"value": {leaf: rows}, "gg": {leaf: AdaGrad's sums}} at `keys`."""
    F, K = trainer.F, trainer.k
    at = np.asarray(keys, np.int64)
    T = gather_rows(trainer.params["T"], at)
    G = gather_rows(trainer.opt_state["T"]["gg"], at)
    return {"value": {"V": T[:, :F * K], "w": T[:, F * K],
                      "w0": host(trainer.params["w0"])},
            "gg": {"V": G[:, :F * K], "w": G[:, F * K],
                   "w0": host(trainer.opt_state["w0"]["gg"])}}
