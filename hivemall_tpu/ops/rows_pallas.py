"""Copy a list of table rows out of, and back into, a table in HBM: two
Pallas TPU kernels, one DMA a row.

Why not XLA's own: its gather reads a 128-lane row in ~10 ns, but its
scatter into a table-sized operand, `add` or `set`, with or without the
sorted / unique promises, costs ~70 ns a row on a v5e (5.6 ms for 79,872
rows of a 2 GiB float32 table; with `indices_are_sorted` a pass over the
whole table instead, 6.4 ms + 4 ns a row), and both cost the same for a
list's out-of-range padding as for its rows (PERF.md section 6, PR 28).
These kernels take the COUNT of live rows as a runtime scalar and issue no
DMA past it, so a caller sizes its list for the worst batch and pays for
the rows the batch holds.

`take_rows` DMAs row `rows[j]` of the table into row j of a block of the
output; `put_rows` DMAs row j of a block of the values onto row `rows[j]`
of the table, which is aliased to the output: rows the list does not name
are never touched. The row ids of a block are staged in SMEM; a block
starts all its copies, then waits for them, so TB copies are in flight.
The live rows of a list must be distinct (two copies onto one row race).
Both are jitted, so that a caller's tables of one shape (a table and its
optimizer state) trace and lower ONE kernel each: a kernel's trace costs
a quarter of a second of every process's set-up.

Off a TPU `take_rows` and `put_rows` ARE the XLA gather and scatter they
replace (`use_kernels_default`: training that runs anywhere must not start
to depend on the interpreter), which is why a list pads with out-of-range
ids; `interpret=True` runs the kernels there, for the tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["take_rows", "put_rows", "use_kernels_default", "LIST_MULTIPLE"]

#: a row list's length must be a multiple of this (one SMEM tile of ids)
LIST_MULTIPLE = 128
_UNROLL = 8


def use_kernels_default() -> bool:
    """The kernels where they compile (a TPU); XLA's gather and scatter on
    any other backend."""
    return jax.default_backend() == "tpu"


def _xla(interpret) -> bool:
    return interpret is None and not use_kernels_default()


def _block_rows(cap: int) -> int:
    """Rows a grid step copies: the largest of 2048..128 dividing `cap`
    (2048 rows of 128 float32 lanes are 1 MiB of VMEM a buffer)."""
    if cap % LIST_MULTIPLE:
        raise ValueError(f"a row list of {cap} ids is not a multiple of "
                         f"{LIST_MULTIPLE}")
    return next(tb for tb in (2048, 1024, 512, 256, 128) if cap % tb == 0)


def _each(m, body):
    """body(j) for j in [0, m), m a runtime scalar: groups of _UNROLL
    unrolled (the loop is scalar-issue bound), then the rest."""
    def group(q, c):
        for u in range(_UNROLL):
            body(q * _UNROLL + u)
        return c

    def one(j, c):
        body(j)
        return c
    jax.lax.fori_loop(0, m // _UNROLL, group, 0)
    jax.lax.fori_loop((m // _UNROLL) * _UNROLL, m, one, 0)


def _call(kernel, rows, n, operands, in_specs, out_spec, out_shape, tb,
          aliases, interpret):
    cap = rows.shape[0]
    ids = pl.BlockSpec((1, tb // 128, 128), lambda i, n: (i, 0, 0),
                       memory_space=pltpu.SMEM)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(cap // tb,),
            in_specs=[ids, *in_specs], out_specs=out_spec,
            scratch_shapes=[pltpu.SemaphoreType.DMA((1,))]),
        out_shape=out_shape, input_output_aliases=aliases,
        interpret=bool(interpret),
    )(n.astype(jnp.int32).reshape(1),
      rows.reshape(cap // tb, tb // 128, 128), *operands)


@functools.partial(jax.jit, static_argnames="interpret")
def take_rows(table, rows, n, *, interpret=None):
    """[len(rows), W] holding table[rows[j]] in row j for j < n. Rows from
    n on hold whatever the buffer held: never read them."""
    if _xla(interpret):
        return table.at[rows].get(mode="clip")
    tb = _block_rows(rows.shape[0])

    def kernel(n_ref, rows_ref, t_ref, o_ref, sem):
        base = pl.program_id(0) * tb

        @pl.when(base < n_ref[0])
        def _():
            m = jnp.minimum(tb, n_ref[0] - base)
            _each(m, lambda j: pltpu.make_async_copy(
                t_ref.at[pl.ds(rows_ref[0, j >> 7, j & 127], 1)],
                o_ref.at[pl.ds(j, 1)], sem.at[0]).start())
            _each(m, lambda j: pltpu.make_async_copy(
                t_ref.at[pl.ds(0, 1)], o_ref.at[pl.ds(j, 1)],
                sem.at[0]).wait())

    w = table.shape[1]
    return _call(
        kernel, rows, n, (table,), [pl.BlockSpec(memory_space=pl.ANY)],
        pl.BlockSpec((tb, w), lambda i, n: (i, 0), memory_space=pltpu.VMEM),
        jax.ShapeDtypeStruct((rows.shape[0], w), table.dtype), tb, {},
        interpret)


@functools.partial(jax.jit, static_argnames="interpret")
def put_rows(table, rows, n, values, *, interpret=None):
    """`table` with values[j] on row rows[j] for j < n, in place (donate
    the table); rows[:n] distinct and in range, rows[n:] out of range."""
    if _xla(interpret):
        return table.at[rows].set(values.astype(table.dtype), mode="drop")
    tb = _block_rows(rows.shape[0])

    def kernel(n_ref, rows_ref, v_ref, t_ref, o_ref, sem):
        del t_ref                                    # aliased to o_ref
        base = pl.program_id(0) * tb

        @pl.when(base < n_ref[0])
        def _():
            m = jnp.minimum(tb, n_ref[0] - base)
            _each(m, lambda j: pltpu.make_async_copy(
                v_ref.at[pl.ds(j, 1)],
                o_ref.at[pl.ds(rows_ref[0, j >> 7, j & 127], 1)],
                sem.at[0]).start())
            _each(m, lambda j: pltpu.make_async_copy(
                v_ref.at[pl.ds(j, 1)], o_ref.at[pl.ds(0, 1)],
                sem.at[0]).wait())

    w = table.shape[1]
    return _call(
        kernel, rows, n, (values.astype(table.dtype), table),
        [pl.BlockSpec((tb, w), lambda i, n: (i, 0),
                      memory_space=pltpu.VMEM),
         pl.BlockSpec(memory_space=pl.ANY)],
        pl.BlockSpec(memory_space=pl.ANY),
        jax.ShapeDtypeStruct(table.shape, table.dtype), tb, {3: 0},
        interpret)
