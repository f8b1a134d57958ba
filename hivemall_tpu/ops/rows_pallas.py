"""Read, update and write back a list of rows of co-shaped tables in HBM:
one Pallas TPU kernel, one DMA a row a table a direction.

Why not XLA's own: its gather reads a 128-lane row in ~10 ns, but its
scatter into a table-sized operand, `add` or `set`, with or without the
sorted / unique promises, costs ~70 ns a row on a v5e (5.6 ms for 79,872
rows of a 2 GiB float32 table; with `indices_are_sorted` a pass over the
whole table instead, 6.4 ms + 4 ns a row), and both cost the same for a
list's out-of-range padding as for its rows (PERF.md section 6, PR 28).
The kernel takes the COUNT of live rows as a runtime scalar and does no
work past it, so a caller sizes its list for the worst batch and pays for
the rows the batch holds: a block of the list past the count starts no
DMA, fetches no gradient block (the index maps clamp to the last live
block) and runs no update, and no list-sized array is written to HBM.

`update_rows` walks the list in blocks of TB rows. A block's rows of every
table are DMAed from HBM into a VMEM slot, `fn` maps the slot's blocks and
the gradient block to new blocks, and those are DMAed back onto the same
table rows; the tables are aliased to the outputs, so rows the list does
not name are never touched. Each table has two slots: block i+1's copy-in
is started before block i's is waited for, and block i's write-back is
waited for only when block i+2 needs the slot (or the list ends). That is
legal because the live rows of a list must be DISTINCT (two copies onto
one row race): no block reads a row that another block writes. A full
block is waited for with one descriptor of the block's whole extent (a DMA
semaphore counts bytes), so the scalar core issues starts and no per-row
waits; only the list's one partial block waits row by row. What is left is
the DMA engine's rate, 15.5 ns a row copy on a v5e: 62 ns a distinct row of
two tables, 0.25 ms for the kernel alone on a list with no live row
(PERF.md section 6, PR 30).

What Mosaic compiles of this is rows of exactly one lane tile of 32-bit
words (`kernel_takes`), which lie contiguous in HBM. Asked for the
flagship's tables, `[4194304, 164]` bfloat16 with a float32 state, it
refuses (jax 0.9 for a v5e; tests/tpu_aot_worker.py `ffm_joint_megastep`): any
slice of an array whose rows are not whole lane tiles ("Slice shape along
dimension 1 must be aligned to tiling (128), but is 164", float32 too, 8-
and 16-row groups too, through a `[R/8, 8, 164]` view too), and fewer rows
than a tile's 8 of any other array (a bfloat16 pair, which shares its
sublane words: "dimension 0 must be aligned to tiling (8), but is 2", at
128 and 256 lanes; a float32 row of 256 lanes likewise).

For those tables, and off a TPU (`use_kernels_default`: training that
runs anywhere must not start to depend on the interpreter), `update_rows`
is XLA's gather, `fn` and scatter on the same list, in blocks of at most
XLA_BLOCK_ROWS up to the count: a loop whose trips follow the live rows,
because the scatter's 70 ns a row would otherwise be paid for the whole
capacity. That is why a list pads with out-of-range ids. `interpret=True`
runs the kernel off a TPU, for the tests.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["update_rows", "scatter_rows", "scatter_rows_takes", "fold_runs",
           "scatter_rows_cap", "use_kernels_default", "kernel_takes",
           "LIST_MULTIPLE", "BLOCK_ROWS", "XLA_BLOCK_ROWS",
           "SCATTER_VMEM_BYTES"]

#: a row list's length must be a multiple of this (one SMEM tile of ids)
LIST_MULTIPLE = 128
#: and is best a multiple of this: the rows of a block (1 MiB of VMEM a
#: slot at 128 float32 lanes), which must divide the list
BLOCK_ROWS = 2048
#: the most rows one trip of the XLA path gathers, updates and scatters
XLA_BLOCK_ROWS = 8192
_UNROLL = 4
#: the most VMEM `scatter_rows`' sum may hold: a v5e's 128 MiB less the
#: slab's blocks and what Mosaic keeps for itself. The one budget of the
#: tail's compact sum: `scatter_rows_cap` sizes its passes by it (FM
#: 196,608 rows of 128 lanes, the flagship 98,304 of 164 padded to 256)
SCATTER_VMEM_BYTES = 96 << 20
#: slots one trip of `scatter_rows`' loop adds, in whole rows of the slab
#: (at least one): ns a slot on a v5e at 8 / 16 slots of 128 lanes 7.15 /
#: 6.80, a row of 39 slots of 164 lanes 10.29 (PR 38)
SCATTER_UNROLL = 16
_IN, _OUT = 0, 1


def use_kernels_default() -> bool:
    """The kernel where it compiles (a TPU); XLA's gather and scatter on
    any other backend."""
    return jax.default_backend() == "tpu"


def kernel_takes(tables) -> bool:
    """Whether Mosaic compiles the kernel's row copies for these tables:
    rows of one lane tile of 32-bit words (the module's docstring has what
    it says to the others)."""
    return all(a.dtype.itemsize == 4 and a.shape[1] == 128 for a in tables)


def _block_rows(cap: int, most: int = BLOCK_ROWS) -> int:
    """Rows a block updates: `most` halved until it divides `cap`."""
    if cap % LIST_MULTIPLE:
        raise ValueError(f"a row list of {cap} ids is not a multiple of "
                         f"{LIST_MULTIPLE}")
    tb = most
    while cap % tb:
        tb //= 2
    return tb


def _update_rows_xla(tables, rows, n, g, t, fn):
    """update_rows by XLA's gather and scatter, a block of the list a trip,
    ceil(n / block) trips."""
    tb = _block_rows(rows.shape[0], XLA_BLOCK_ROWS)

    def block(i, tables):
        ids = jax.lax.dynamic_slice_in_dim(rows, i * tb, tb)
        new = fn(tuple(a.at[ids].get(mode="clip") for a in tables),
                 jax.lax.dynamic_slice_in_dim(g, i * tb, tb), t)
        return tuple(a.at[ids].set(u.astype(a.dtype), mode="drop")
                     for a, u in zip(tables, new))
    return jax.lax.fori_loop(0, (n.astype(jnp.int32) + tb - 1) // tb, block,
                             tables)


def _each(m, body):
    """body(j) for j in [0, m), m a runtime scalar: groups of _UNROLL
    unrolled, then the rest. A loop of two DMA starts a trip is bound by
    the scalar core's loop (21 ns a DMA; unrolled by 2, 16.4); from 4 up by
    the DMA engine's 15.5 ns a descriptor, on either priority (a v5e, PR
    30), and every unrolled copy is traced in every process's set-up."""
    def group(q, c):
        for u in range(_UNROLL):
            body(q * _UNROLL + u)
        return c

    def one(j, c):
        body(j)
        return c
    jax.lax.fori_loop(0, m // _UNROLL, group, 0)
    jax.lax.fori_loop((m // _UNROLL) * _UNROLL, m, one, 0)


def update_rows(tables, rows, n, g, t, fn, *, interpret=None):
    """`tables` (a tuple of co-shaped [R, W] arrays, each of its own
    dtype) with rows ``rows[:n]`` of every table replaced, in place
    (donate them), by ``fn(blocks, g_block, t)`` cast to the table's
    dtype: ``blocks`` the tables' rows at a block of the list, ``g_block``
    the same block of ``g`` [len(rows), W], ``t`` the scalar ``t`` as a
    float32 [1, W] (data, not a constant of the program). ``fn`` is
    elementwise over rows: it is also handed rows past ``n``, holding
    anything, whose results are dropped. rows[:n] distinct and in range,
    rows[n:] out of range; len(rows) at most R. The kernel
    (``interpret=True`` asks for it by name) takes what `kernel_takes`
    says."""
    tables = tuple(tables)
    cap, (R, W) = rows.shape[0], tables[0].shape
    tb = _block_rows(cap)
    if any(a.shape != (R, W) for a in tables):
        raise ValueError("tables must be co-shaped")
    if cap > R:
        raise ValueError(f"a list of {cap} rows into a table of {R}")
    t = jnp.full((1, W), t, jnp.float32)
    takes = kernel_takes(tables)
    if interpret is None and not (takes and use_kernels_default()):
        return _update_rows_xla(tables, rows, n, g, t, fn)
    if not takes:
        raise ValueError("the kernel copies rows of 128 lanes of 32-bit "
                         "words")
    nt = len(tables)

    def kernel(n_ref, ids_ref, next_ids_ref, t_ref, g_ref, *refs):
        outs, bufs, sem = refs[nt:2 * nt], refs[2 * nt:3 * nt], refs[3 * nt]
        i, n = pl.program_id(0), n_ref[0]
        base = i * tb
        slot = i % 2

        def copies(way, s, r, j, count=1):
            """Table rows [r, r + count) to (_OUT) or from (_IN) rows
            [j, j + count) of slot s, a descriptor a table."""
            for out, buf in zip(outs, bufs):
                hbm, vmem = out.at[pl.ds(r, count)], buf.at[s, pl.ds(j, count)]
                src, dst = (hbm, vmem) if way == _IN else (vmem, hbm)
                yield pltpu.make_async_copy(src, dst, sem.at[way, s])

        def start(way, ids, s, m):
            def body(j):
                for c in copies(way, s, ids[0, j >> 7, j & 127], j):
                    c.start()
            _each(m, body)

        def wait_whole(way, s):
            for c in copies(way, s, 0, 0, tb):
                c.wait()

        def wait(way, s, m):
            pl.when(m == tb)(lambda: wait_whole(way, s))

            @pl.when(m < tb)
            def _():
                def body(j):
                    for c in copies(way, s, 0, j):
                        c.wait()
                _each(m, body)

        @pl.when(base < n)
        def _():
            m = jnp.minimum(tb, n - base)
            more = base + tb < n

            @pl.when(i == 0)
            def _():
                start(_IN, ids_ref, slot, m)

            @pl.when(i > 0)
            def _():                    # block i-1 was full: block i lives
                wait_whole(_OUT, 1 - slot)

            @pl.when(more)
            def _():
                start(_IN, next_ids_ref, 1 - slot,
                      jnp.minimum(tb, n - base - tb))
            wait(_IN, slot, m)
            new = fn(tuple(buf[slot] for buf in bufs), g_ref[...],
                     t_ref[...])
            for buf, u in zip(bufs, new):
                buf[slot] = u.astype(buf.dtype)
            start(_OUT, ids_ref, slot, m)

            @pl.when(jnp.logical_not(more))
            def _():
                wait(_OUT, slot, m)

    def live(i, n):                     # no block past the last live one
        return jnp.minimum(i, jnp.maximum(n[0] - 1, 0) // tb)
    ids = [pl.BlockSpec((1, tb // 128, 128),
                        lambda i, n, k=k: (live(i + k, n), 0, 0),
                        memory_space=pltpu.SMEM) for k in (0, 1)]
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    rows = rows.reshape(cap // tb, tb // 128, 128)
    return tuple(pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(cap // tb,),
            in_specs=[*ids,
                      pl.BlockSpec((1, W), lambda i, n: (0, 0),
                                   memory_space=pltpu.VMEM),
                      pl.BlockSpec((tb, W), lambda i, n: (live(i, n), 0),
                                   memory_space=pltpu.VMEM),
                      *[hbm] * nt],
            out_specs=[hbm] * nt,
            scratch_shapes=[*[pltpu.VMEM((2, tb, W), a.dtype)
                              for a in tables],
                            pltpu.SemaphoreType.DMA((2, 2))]),
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in tables],
        input_output_aliases={5 + k: k for k in range(nt)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=bool(interpret), name="update_rows",
    )(n.astype(jnp.int32).reshape(1), rows, rows, t, g, *tables))


# --- the compact gradient, summed in fast memory -----------------------------
# XLA writes every scatter-add whose indices are not sorted as a sort of
# (indices, iota), a gather of the updates through the permutation and a
# sorted scatter: a slab of 1,277,952 gradient rows is then read at random
# out of HBM, 13.6-16.7 ns a row (the compiled text for a v5e, PR 38). This
# kernel reads the slab in its own order and adds each row into a compact
# gradient that stays in VMEM for the whole slab, at the row's rank: the
# random side lands in fast memory.


def _slab_view(shape):
    """The slab [..., W] as `scatter_rows` reads it, [M, S, W], slot
    m * S + s: a [B, L, W] slab as it is (a reshape of L not whole 8-row
    tiles would retile it, a pass over the slab), a [n, W] one 8 rows a
    tile (a view: its tiles ARE 8 rows)."""
    if len(shape) == 3:
        return shape
    return (shape[0] // 8, 8, shape[1]) if shape[0] % 8 == 0 else None


def _slab_unit(S: int) -> int:
    """Fewest slab rows of S slots that fill whole 128-id tiles of ranks."""
    u = 128
    while u > 1 and S % 2 == 0:
        u, S = u // 2, S // 2
    return u


def scatter_rows_cap(W: int) -> int:
    """Most rows of a [., W] float32 sum the kernel holds in
    SCATTER_VMEM_BYTES, in whole id tiles (0: not one tile)."""
    return (SCATTER_VMEM_BYTES // (-(-W // 128) * 128 * 4)
            // LIST_MULTIPLE * LIST_MULTIPLE)


def scatter_rows_takes(shape, cap: int) -> bool:
    """Whether `scatter_rows` runs as the kernel for a slab of this shape
    into ``cap`` rows: it reads the slab as [M, S, W] and its ranks in
    whole SMEM id tiles of slab rows, and the sum fits SCATTER_VMEM_BYTES."""
    view = _slab_view(shape) if len(shape) in (2, 3) else None
    return (view is not None and view[0] % _slab_unit(view[1]) == 0
            and cap % 8 == 0
            and cap * -(-shape[-1] // 128) * 128 * 4 <= SCATTER_VMEM_BYTES)


def scatter_rows(g, rank, cap: int, *, interpret=None):
    """zeros([cap, W], float32).at[rank].add(g, mode="drop"): the slots of
    the slab ``g`` [..., W] (float32; [n, W], or [B, L, W] as a step's
    backward leaves it) summed by ``rank`` [n] (int32, the slots in g's
    order), a slot ranked at or past ``cap`` (a multiple of 8) dropped.
    The kernel (a TPU, a slab `scatter_rows_takes`; or
    ``interpret=True``) holds the sum in a VMEM scratch of ``cap`` rows
    for the whole slab: the caller sizes ``cap`` so that it fits the
    chip's fast memory. Each grid step brings a block of the slab into
    VMEM and its ranks into SMEM, and adds every slot's row into its
    rank's 8-row tile, the other 7 sublanes masked (all 8 for a dropped
    slot); the last step copies the sum to HBM whole (Mosaic slices no
    array of 164 lanes). Duplicates meet in slot order. Anywhere else,
    XLA's scatter-add."""
    W = g.shape[-1]
    if interpret is None and not (scatter_rows_takes(g.shape, cap)
                                  and use_kernels_default()):
        return jnp.zeros((cap, W), jnp.float32).at[rank].add(
            g.reshape(-1, W), mode="drop")
    if not scatter_rows_takes(g.shape, cap):
        raise ValueError(f"a slab {g.shape} into {cap} rows")
    M, S, _ = _slab_view(g.shape)
    bm = _slab_unit(S)                  # slab rows a grid step
    while M % (2 * bm) == 0 and 2 * bm * S <= 4096:
        bm *= 2
    per_trip = 1 << max(0, (SCATTER_UNROLL // S).bit_length() - 1)
    tiles = cap // 8

    def kernel(rank_ref, g_ref, out_ref, acc_ref, sem):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
        sub = jax.lax.broadcasted_iota(jnp.int32, (8, W), 0)

        def body(i, c):
            # whole slab rows a trip, every slot's sublane static: a
            # dynamic sublane index costs twice as much a slot (PR 38)
            for k in range(per_trip):
                m = i * per_trip + k
                for s in range(S):
                    j = m * S + s
                    r = rank_ref[0, j >> 7, j & 127]
                    row = jnp.broadcast_to(g_ref[m, pl.ds(s, 1), :], (8, W))
                    acc_ref[jnp.minimum(r, cap - 1) >> 3] += jnp.where(
                        (sub == (r & 7)) & (r < cap), row, 0.0)
            return c
        jax.lax.fori_loop(0, bm // per_trip, body, 0)

        @pl.when(i == pl.num_programs(0) - 1)
        def _():
            copy = pltpu.make_async_copy(acc_ref, out_ref, sem.at[0])
            copy.start()
            copy.wait()
    lanes = -(-W // 128) * 128
    acc_bytes = cap * lanes * 4
    block_bytes = bm * -(-S // 8) * 8 * lanes * 4
    out = pl.pallas_call(
        kernel,
        grid=(M // bm,),
        in_specs=[pl.BlockSpec((1, bm * S // 128, 128),
                               lambda i: (i, 0, 0), memory_space=pltpu.SMEM),
                  pl.BlockSpec((bm, S, W), lambda i: (i, 0, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.VMEM((tiles, 8, W), jnp.float32),
                        pltpu.SemaphoreType.DMA((1,))],
        out_shape=jax.ShapeDtypeStruct((tiles, 8, W), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=acc_bytes + 2 * block_bytes + (4 << 20)),
        interpret=bool(interpret), name="scatter_rows",
    )(rank.reshape(M // bm, bm * S // 128, 128), g.reshape(M, S, W))
    return out.reshape(cap, W)


# --- runs summed one addend after another ------------------------------------
# A sum of a run of equal keys, in order, as XLA's scatter-add and a float32
# reference make it (fl(fl(a + b) + c) ...), costs XLA 8.7 ns an addend on a
# v5e (its scatter-add serialises duplicates), 11 ms for a batch of the
# linear cell. The order matters: thousands of near-equal addends round the
# same way at each add, and a tree's sum of a hot slot differs from the
# ordered one by 1e-4 of it. This kernel keeps the order and runs 1024
# stretches of the sequence side by side, one per word of a vector register.


#: stretches of the sequence a pass folds side by side: one [8, 128] tile
FOLD_LANES = 8 * 128


def fold_runs(vals, first, longest, *, interpret=None):
    """[n] float32: at each j, the sum of ``vals`` [n] from the start of
    j's run (the last i <= j with ``first[i]``; first[0] must be set) to
    j, added in order: fl(fl(v_s + v_s+1) + ...). The sequence is cut into
    FOLD_LANES stretches, folded side by side a step of each per vector
    op; a run that crosses into a stretch is right there once the pass
    before has folded the stretch it comes from, so a run over k stretches
    is right after k passes: ceil((``longest`` - 1) / stretch) + 1 passes,
    ``longest`` the longest run (a runtime scalar). Pallas on a TPU (or
    ``interpret=True``); None elsewhere: the caller sums by XLA's
    scatter-add, which adds in the same order."""
    if interpret is None and not use_kernels_default():
        return None
    n = vals.shape[0]
    per = -(-n // (FOLD_LANES * 8)) * 8          # a stretch, whole 8s
    pad = per * FOLD_LANES - n
    # [per, 8, 128]: step i of stretch (a, b) at [i, a, b]
    v = jnp.concatenate([vals.astype(jnp.float32),
                         jnp.zeros((pad,), jnp.float32)]
                        ).reshape(8, 128, per).transpose(2, 0, 1)
    f = jnp.concatenate([first, jnp.ones((pad,), bool)]
                        ).astype(jnp.int32).reshape(8, 128, per
                                                    ).transpose(2, 0, 1)
    bi = 8
    while per % (2 * bi) == 0 and 2 * bi <= 256:
        bi *= 2

    def kernel(c_ref, v_ref, f_ref, o_ref, acc_ref):
        @pl.when(pl.program_id(0) == 0)
        def _():
            acc_ref[...] = c_ref[...]

        def step(i, acc):
            x = v_ref[i]
            acc = jnp.where(f_ref[i] != 0, x, acc + x)
            o_ref[i] = acc
            return acc
        acc_ref[...] = jax.lax.fori_loop(0, bi, step, acc_ref[...])

    block = pl.BlockSpec((bi, 8, 128), lambda i: (i, 0, 0))
    one_pass = pl.pallas_call(
        kernel, grid=(per // bi,),
        in_specs=[pl.BlockSpec((8, 128), lambda i: (0, 0)), block, block],
        out_specs=block,
        scratch_shapes=[pltpu.VMEM((8, 128), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((per, 8, 128), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=bool(interpret), name="fold_runs")

    def again(carry):
        p, out = carry
        # each stretch starts from where the one before it ended
        last = out[per - 1].reshape(-1)
        c = jnp.concatenate([jnp.zeros((1,), jnp.float32), last[:-1]])
        return p + 1, one_pass(c.reshape(8, 128), v, f)

    passes = (jnp.maximum(longest, 1) - 1 + per - 1) // per + 1
    _, out = jax.lax.while_loop(
        lambda carry: carry[0] < passes, again,
        again((jnp.zeros((), jnp.int32), jnp.zeros((per, 8, 128),
                                                    jnp.float32))))
    return out.transpose(1, 2, 0).reshape(-1)[:n]
