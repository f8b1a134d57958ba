"""Hashed linear model under AdaGrad-RDA (Xiao 2010; Duchi et al. 2011),
unit-valued rows of F features, one slot an id: a "row" of the table is one
number, and so is a row of each of the optimizer's two sums, `u` and `gg`.

What a step needs is the row-at-a-time algorithm's own traffic: every slot
reads its weight for the margin, and the slots a batch touches read and
write `w`, `u` and `gg` once. How many of a batch's slots are distinct
follows the data's skew, not the shapes, so every slot is counted as
distinct (what uniformly hashed ids give). No pass over the whole table is
counted: re-materialising every weight at every step is the
implementation's choice (a slot nobody touched stays at zero)."""

from __future__ import annotations

from .common import BYTES

# per row beyond the dot: margin times label, exp, add, reciprocal and
# multiply for dloss; log1p (2) and the sum for the loss
ROW_FLOPS = 8
# per slot of the backward: dloss times value, and the add into the slot's
# summed gradient
SLOT_FLOPS = 2
# per updated slot: u += g; gg += g * g (2); |u|; / (t+1); - lambda; max;
# sign; sqrt; + eps; * eta * (t+1) (2); divide; negate
RDA_FLOPS = 15


def _shape(cfg: dict):
    m = cfg["model"]
    return int(m["fields"]), BYTES[m["table_dtype"]], BYTES[m["state_dtype"]]


def table_elements(cfg: dict) -> int:
    return int(cfg["model"]["dims"])


def forward_flops(F: int) -> int:
    # the F-term dot (F multiplies by the unit values, F adds), then the
    # sigmoid and the loss
    return 2 * F + ROW_FLOPS


def train_step(cfg: dict, rows: int) -> dict:
    """One minibatch step over `rows` rows: rows x F slots, each one read
    of `w`, then one read and one write of `w`, `u` and `gg`; the batch's
    ids and labels are read once."""
    F, tb, sb = _shape(cfg)
    slots = rows * F
    return {"bytes": slots * (tb + 2 * (tb + 2 * sb)) + rows * (F * 4 + 4),
            "flops": rows * forward_flops(F)
            + slots * (SLOT_FLOPS + RDA_FLOPS)}


def score(cfg: dict, rows: int) -> dict:
    F, tb, _ = _shape(cfg)
    return {"bytes": rows * F * tb + rows * F * 4,
            "flops": rows * forward_flops(F)}
