"""The work functions against counts made by hand at a toy shape, and the
rule that no term follows an implementation choice."""

import pytest

from work import common, ffm, fm


def cfg(fields, factors, table="float32", state="float32", dims=1 << 10):
    return {"model": {"fields": fields, "factors": factors, "dims": dims,
                      "table_dtype": table, "state_dtype": state}}


def test_fm_hand_count():
    # 2 rows of 3 features, 2 factors, float32 table and state
    w = fm.train_step(cfg(3, 2), rows=2)
    slots, width = 2 * 3, 2 + 1
    assert w["bytes"] == slots * width * (4 + 4 + 4 + 4) + 2 * (3 * 4 + 4)
    fwd = 3 + 2 * (3 * 3 + 3) + 2            # 29
    assert fm.forward_flops(3, 2) == fwd == 29
    assert w["flops"] == 2 * 3 * fwd + slots * width * 7
    s = fm.score(cfg(3, 2), rows=2)
    assert s == {"bytes": slots * width * 4 + 2 * 3 * 4, "flops": 2 * fwd}


def test_ffm_hand_count():
    # 2 rows, 3 fields, 2 factors, bfloat16 table, float32 state
    c = cfg(3, 2, table="bfloat16")
    w = ffm.train_step(c, rows=2)
    slots, width = 2 * 3, 3 * 2 + 1
    assert w["bytes"] == slots * width * (2 + 2 + 4 + 4) + 2 * (3 * 4 + 4)
    fwd = 3 * 2 // 2 * 2 * 2 + 3 + 1         # 3 pairs x 4 + 4 = 16
    assert ffm.forward_flops(3, 2) == fwd == 16
    assert w["flops"] == 2 * 3 * fwd + slots * width * 7
    assert ffm.score(c, rows=2)["bytes"] == slots * width * 2 + 2 * 3 * 4


def test_table_elements_are_logical():
    assert fm.table_elements(cfg(39, 5, dims=1 << 26)) == (1 << 26) * 6
    assert ffm.table_elements(cfg(39, 4, dims=1 << 28)) == \
        (1 << 22) * (39 * 4 + 1)


def test_work_does_not_grow_with_the_table():
    """A dense pass over the table is the implementation's choice: the
    step's work may not depend on `dims`."""
    for mod, c1, c2 in ((fm, cfg(39, 5, dims=1 << 20), cfg(39, 5, dims=1 << 26)),
                        (ffm, cfg(39, 4, dims=1 << 20), cfg(39, 4, dims=1 << 28))):
        assert mod.train_step(c1, 32768) == mod.train_step(c2, 32768)


def test_least_seconds_takes_the_binding_roof():
    peaks = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert common.least_seconds({"bytes": 50, "flops": 100}, peaks) == 5.0
    assert common.bound_by({"bytes": 50, "flops": 100}, peaks) == "hbm"
    assert common.least_seconds({"bytes": 5, "flops": 900}, peaks) == 9.0
    assert common.bound_by({"bytes": 5, "flops": 900}, peaks) == "flops"


def test_criteo_step_is_hbm_bound_and_small():
    from harness.common import load_json, BENCH_DIR, peaks_for
    import os
    peaks = peaks_for("TPU v5 lite")
    for name, mod in (("fm_criteo", fm), ("ffm_criteo_joint", ffm)):
        c = load_json(os.path.join(BENCH_DIR, "configs", f"{name}.json"))
        w = mod.train_step(c, 32768)
        assert common.bound_by(w, peaks) == "hbm"
        assert 1e-4 < common.least_seconds(w, peaks) < 5e-3
    with pytest.raises(KeyError):
        peaks_for("TPU v99")
