"""Degree-2 FM (Rendle 2010), unit-valued rows of F features, K factors.

Row width is the logical one: K latent values and the linear weight."""

from __future__ import annotations

from .common import BYTES


def _shape(cfg: dict):
    m = cfg["model"]
    return int(m["fields"]), int(m["factors"]), BYTES[m["table_dtype"]], \
        BYTES[m["state_dtype"]]


def table_elements(cfg: dict) -> int:
    m = cfg["model"]
    return int(m["dims"]) * (int(m["factors"]) + 1)


def forward_flops(F: int, K: int) -> int:
    # linear term: F adds. Per factor: F adds for the sum, F multiplies and
    # F adds for the sum of squares, a square, a subtract, an add; then the
    # halving and the bias.
    return F + K * (3 * F + 3) + 2


def train_step(cfg: dict, rows: int) -> dict:
    """One minibatch-AdaGrad step over `rows` rows: every one of the
    rows x F slots reads and writes one table row and one accumulator row;
    the batch's ids and labels are read once."""
    F, K, tb, sb = _shape(cfg)
    slots = rows * F
    width = K + 1
    return {"bytes": slots * width * 2 * (tb + sb) + rows * (F * 4 + 4),
            # backward costs about twice the forward; AdaGrad: square, add,
            # sqrt, add, divide, multiply, subtract per element
            "flops": rows * 3 * forward_flops(F, K) + slots * width * 7}


def score(cfg: dict, rows: int) -> dict:
    F, K, tb, _ = _shape(cfg)
    return {"bytes": rows * F * (K + 1) * tb + rows * F * 4,
            "flops": rows * forward_flops(F, K)}
