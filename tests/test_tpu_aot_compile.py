"""Every Pallas kernel compiles for a TPU v5e with ``interpret=False``.

The tier-1 suite runs kernels in interpret mode on the CPU, which checks
their arithmetic and nothing about Mosaic: a multi-chip step once ran "ok"
for five rounds on virtual CPU devices while its accumulate kernel needed
18 MiB of a 16 MiB scoped-VMEM limit. jax can compile for a
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


def test_flat_and_dense_histogram_kernels_compile_for_v5e():
    """The flat and dense histograms (n=2^20, d=28, 64 bins)."""
    _compile("hist_flat", "hist_dense", timeout=600)


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
    and never out of the 2 GiB one, the slab leaving it without a copy;
    since PR 38 the tail's compact sum by `scatter_rows` (the sum held in
    fast memory, no slab row read through the permutation) and the row
    kernel in a loop of passes, a second `while` (two Mosaic kernels)."""
    _compile("fm_minibatch_step", timeout=600)


def test_ffm_joint_megastep_compiles_for_v5e_with_the_distinct_tail():
    """The flagship's megastep at its cell's geometry ([4194304, 164]
    bfloat16 table, float32 AdaGrad state, B=32768, L=39, fieldmajor, unit
    values; PR 32), its tail through rows_update: `tail_cap` offers the
    tail, one `conditional`, the scan and the blocks' loop, NO Mosaic
    kernel (the compiler's verdict on 164-lane and half-word row copies,
    learned here), no whole-table copy inside the scan, temporaries no
    more than the dense tail's (~45 s of XLA compile); since PR 34 the
    gather's `conditional` and the compact table's fill, a third loop;
    since PR 38 the compact sum by `scatter_rows` in fast memory (the one
    Mosaic kernel) in a loop of passes, a fourth loop."""
    _compile("ffm_joint_megastep", timeout=600)


def test_ffm_joint_megastep_compiles_for_four_v5e_chips_as_the_one_chips():
    """The same megastep under `-mesh dp=1,tp=4` at the four-chip cell's
    geometry (`-dims 2^30`: [4194304, 164] a chip), `shard_map`s
    over tp: the worker asserts that a chip's program is the one chip's
    and the exchange (four `conditional`, five `while`, the compact
    tables of the chip's own distinct rows and its compact gradient in
    fast memory, the one `scatter_rows` kernel), that a batch
    within the capacities runs no zero fill of a table-sized gradient and
    no table-sized AdaGrad pass (the parent's GSPMD program ran both every
    step), and that the collectives of any size are, on that batch's
    path, the all-gather of the blocks' compact tables, bf16[196608,164],
    and the all-reduce of the slots' rows in them, s32[1277952], the
    slab's all-reduce, bf16[32768,39,164], left to the other branch,
    all made and NAMED by the partitioner (the benchmark reads
    collectives by name) (~40 s of XLA compile)."""
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
    temporaries inside one chip; the distinct-slot step: one
    `conditional`, the `fold_runs` kernel, the state updated in place, no
    whole array copied, and no pass over a [2^28] array for a batch within
    the capacity but the rebuild of `w` after the scan (~45 s of XLA
    compile)."""
    _compile("linear_megastep", timeout=600)


def test_skipgram_megastep_compiles_for_v5e_within_a_chip():
    """`train_word2vec -dim 300` on 2,519,370 words (the cell
    skipgram_enwiki.stream_text, PR 37): the jitted initialiser and the
    megastep of 16,384 pair slots; both tables row-major at the program's
    ends (rows stored 384 lanes: no whole-table relayout), state, noise
    table and temporaries inside one chip (~10 s of XLA compile)."""
    _compile("skipgram_megastep", timeout=600)


def test_mf_megastep_compiles_for_v5e_within_a_chip():
    """`train_mf_adagrad -factors 100 -users 6260326 -items 39780` (the
    cell mf_hugewiki.stream_ratings, 131,072 ratings a step): the jitted
    initialiser and the megastep, both tables through the distinct-row
    tail (four Mosaic kernels, both compact tables in fast memory), no
    whole table copied, state and temporaries inside one chip (~25 s of
    XLA compile)."""
    _compile("mf_megastep", timeout=600)


@pytest.mark.slow
def test_sorted_histogram_kernel_compiles_for_v5e():
    """The sorted histogram (20-46 s per shape): outside the tier-1
    budget."""
    _compile("hist_sorted", timeout=1200)
