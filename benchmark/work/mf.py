"""Biased matrix factorization (Koren et al. 2009), rank k, one rating a
row, minibatch AdaGrad.

A row of either table is counted at its logical width, the k factors and
the bias (k + 1), not at the lanes it is stored with. What a rating needs
is the row-at-a-time algorithm's own traffic: its user's and its item's
rows and their accumulators each read and written once. How many of a
step's rows are distinct follows the data's skew, not the shapes, so every
slot is counted as a row read and written (as work/linear.py and
work/skipgram.py count); no pass over a whole table is counted."""

from __future__ import annotations

from .common import BYTES

TABLES = 2                      # a rating reads a user row and an item row


def _shape(cfg: dict):
    m = cfg["model"]
    return int(m["factors"]), BYTES[m["table_dtype"]], BYTES[m["state_dtype"]]


def table_elements(cfg: dict) -> int:
    m = cfg["model"]
    return (int(m["users"]) + int(m["items"])) * (int(m["factors"]) + 1)


def forward_flops(k: int) -> int:
    # the k-long dot (k multiplies, k adds), the mean and two biases (3
    # adds), the error (1); its square and the half (2)
    return 2 * k + 6


def train_step(cfg: dict, rows: int) -> dict:
    """One minibatch-AdaGrad step over `rows` ratings: each of the
    rows x 2 slots reads and writes one table row and one accumulator row
    of k + 1 numbers; the ratings' two ids and the rating are read once."""
    k, tb, sb = _shape(cfg)
    slots = rows * TABLES
    width = k + 1
    return {"bytes": slots * width * 2 * (tb + sb) + rows * 3 * 4,
            # backward about twice the forward; per element of a slot the
            # L2 term (square, scale, add) and AdaGrad (square, add, sqrt,
            # add, divide, multiply, subtract)
            "flops": rows * 3 * forward_flops(k) + slots * width * (3 + 7)}
