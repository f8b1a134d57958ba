"""Field-aware FM (Juan et al. 2016), unit-valued rows with one feature in
each of F fields, K factors per (feature, field).

Row width is the logical one: F x K latent values and the linear weight."""

from __future__ import annotations

from .common import BYTES


def _shape(cfg: dict):
    m = cfg["model"]
    return int(m["fields"]), int(m["factors"]), BYTES[m["table_dtype"]], \
        BYTES[m["state_dtype"]]


def table_elements(cfg: dict) -> int:
    m = cfg["model"]
    f_pow2 = 1
    while f_pow2 < int(m["fields"]):
        f_pow2 <<= 1
    return (int(m["dims"]) // f_pow2) * (int(m["fields"])
                                         * int(m["factors"]) + 1)


def forward_flops(F: int, K: int) -> int:
    # F(F-1)/2 pairs, each a K-long dot product (K multiplies, K adds
    # counting the add into the running sum); F adds for the linear term
    # and one for the bias.
    return F * (F - 1) // 2 * 2 * K + F + 1


def train_step(cfg: dict, rows: int) -> dict:
    F, K, tb, sb = _shape(cfg)
    slots = rows * F
    width = F * K + 1
    return {"bytes": slots * width * 2 * (tb + sb) + rows * (F * 4 + 4),
            "flops": rows * 3 * forward_flops(F, K) + slots * width * 7}


def score(cfg: dict, rows: int) -> dict:
    F, K, tb, _ = _shape(cfg)
    return {"bytes": rows * F * (F * K + 1) * tb + rows * F * 4,
            "flops": rows * forward_flops(F, K)}
