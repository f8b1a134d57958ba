"""train_classifier and its linear kin: `params` is the [dims] weight table
itself, `opt_state` the optimizer's co-shaped arrays (`gg`: AdaGrad's sum
of squared gradients, which AdaGrad-RDA keeps beside its gradient sum `u`)."""

from __future__ import annotations

import numpy as np

from .common import gather_rows


def read_rows(trainer, keys: np.ndarray) -> dict:
    """{"value": {"w": weights}, "gg": {"w": AdaGrad's sums}} at `keys`."""
    keys = np.asarray(keys, np.int64)
    return {"value": {"w": gather_rows(trainer.params, keys)},
            "gg": {"w": gather_rows(trainer.opt_state["gg"], keys)}}
