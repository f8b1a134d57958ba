"""Every Pallas kernel compiles for a TPU v5e with ``interpret=False``.

The tier-1 suite runs kernels in interpret mode on the CPU, which checks
their arithmetic and nothing about Mosaic: the multi-chip parts step ran
"ok" for five rounds on virtual CPU devices while its accumulate kernel
needed 18 MiB of a 16 MiB scoped-VMEM limit. jax can compile for a
device-less ``v5e:2x2`` topology in this sandbox, so this is the check that
catches such a refusal without chip time (tests/tpu_aot_worker.py holds
the cases).
"""

import json
import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_WORKER = os.path.join(_ROOT, "tests", "tpu_aot_worker.py")


def _compile(*cases, timeout):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=_ROOT)
    env.pop("XLA_FLAGS", None)          # no virtual devices needed here
    r = subprocess.run([sys.executable, _WORKER, *cases], env=env,
                       capture_output=True, text=True, timeout=timeout)
    done = [json.loads(l) for l in r.stdout.splitlines()
            if l.startswith("{")]
    assert r.returncode == 0 and [d["case"] for d in done] == list(cases), \
        f"compiled {done}; stderr tail:\n{r.stderr[-3000:]}"


def test_parts_and_histogram_kernels_compile_for_v5e():
    """Single-chip parts step (B=32768, F=40, K=4, MRF=8192), the sharded
    step's accumulate kernel on the 2x2 mesh (the one Mosaic refused),
    flat and dense histograms (n=2^20, d=28, 64 bins)."""
    _compile("parts_step", "parts_accum_kernel_2x2", "hist_flat",
             "hist_dense", timeout=600)


def test_fm_minibatch_step_compiles_without_a_loop_for_v5e():
    """One un-scanned packed FM minibatch step at the benchmark cell's
    geometry (-dims 2^26 -factors 5, B=32768, L=39, float32): the worker
    asserts the compiled text holds no `while(` of the compiler's making
    (PR 24's parent had two, one per direction of a reshape nobody saw;
    ~40 s of XLA compile) and, since PR 28, one `conditional(` between the
    distinct-row tail and the dense one, the ONE row kernel of
    ops/rows_pallas.py (PR 30; four before) compiled by Mosaic, no copy of
    a whole table, and temporaries under 2.85 GB; since PR 34 a second
    `conditional(` in front, whose distinct branch gathers the slots out
    of the compact [cap, 128] table (filled by the program's one `while`)
    and never out of the 2 GiB one, the slab leaving it without a copy."""
    _compile("fm_minibatch_step", timeout=600)


def test_ffm_joint_megastep_compiles_for_v5e_with_the_distinct_tail():
    """The flagship's megastep at its cell's geometry ([4194304, 164]
    bfloat16 table, float32 AdaGrad state, B=32768, L=39, fieldmajor, unit
    values; PR 32), its tail through rows_update: `tail_cap` offers the
    tail, one `conditional`, the scan and the blocks' loop, NO Mosaic
    kernel (the compiler's verdict on 164-lane and half-word row copies,
    learned here), no whole-table copy inside the scan, temporaries no
    more than the dense tail's (~45 s of XLA compile); since PR 34 the
    gather's `conditional` and the compact table's fill, a third loop."""
    _compile("ffm_joint_megastep", timeout=600)


def test_ffm_joint_megastep_compiles_for_four_v5e_chips_as_the_one_chips():
    """The same megastep under `-mesh dp=1,tp=4` at the four-chip cell's
    geometry (`-dims 2^30`: [4194304, 164] a chip; PR 36), two
    `shard_map`s over tp: the worker asserts that a chip's program is the one chip's
    (three `conditional`, three `while`, the compact table of the chip's
    own distinct rows in fast memory, no Mosaic kernel), that a batch
    within the capacities runs no zero fill of a table-sized gradient and
    no table-sized AdaGrad pass (the parent's GSPMD program ran both every
    step), and that the one collective of any size is the slab's
    all-reduce, bf16[32768,39,164], made and NAMED by the partitioner
    (the benchmark reads collectives by name) (~40 s of XLA compile)."""
    _compile("ffm_joint_megastep_tp4", timeout=600)


def test_state_initialiser_compiles_for_v5e_within_a_chip():
    """The fused tables' jitted initialiser at the size no one chip holds
    (PR 31): `train_ffm -dims 2^30 -halffloat` over tp=4, every output
    born in its row sharding, a quarter of the 16.5 GB a chip, no
    collective."""
    _compile("state_init", timeout=600)


def test_linear_megastep_compiles_for_v5e_within_a_chip():
    """`train_classifier -loss logloss -opt adagrad -dims 2^28` (the cell
    logreg_criteo.stream, PR 33): the state's jitted initialiser (three
    float32 [2^28] arrays, 3.22 GB) and the megastep (B=32768, L=39, unit
    values elided), every phase scope in the compiled text, state and
    temporaries inside one chip (~20 s of XLA compile)."""
    _compile("linear_megastep", timeout=600)


@pytest.mark.slow
def test_whole_sharded_step_and_sorted_histogram_compile_for_v5e():
    """The whole make_parts_step_sharded program (~85 s of XLA compile)
    and the sorted histogram (20-46 s per shape): outside the tier-1
    budget."""
    _compile("parts_step_sharded", "hist_sorted", timeout=1200)
