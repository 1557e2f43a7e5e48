"""Row write: put given rows into tables in place, one DMA per row.

``write_rows(rows, values, tables)`` writes ``values[t][j]`` into
row ``rows[j]`` of ``tables[t]`` for every ``j`` with ``rows[j]`` inside the
table, and leaves every other row as it was.  The ids of the rows written
must be distinct; ids past the table are skipped.  The tables are aliased
to the outputs, so nothing but the written rows moves.

The row ids are scalar-prefetched into SMEM; the kernel walks them once
and starts one row DMA from the values into every table for each id inside
the table, with at most ``IN_FLIGHT`` rows outstanding.  XLA's
own scatter on the TPU reads, adds and writes one update row at a time,
duplicates or not; this kernel only streams DMAs.

A row DMA on the TPU moves whole 128-lane tiles, so the kernel takes the
tables whose width is a multiple of ``LANES``.  A narrower table (a bias
column) is written by XLA's scatter of the same distinct rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128      # a row DMA's width must be a multiple of the lane tile
IN_FLIGHT = 16   # rows outstanding before the kernel waits for one
UNROLL = 8       # ids handled per loop iteration


def _write_rows_kernel(rows_ref, *refs, num_tables: int, in_flight: int):
    values = refs[:num_tables]
    outs = refs[2 * num_tables:3 * num_tables]
    sem = refs[3 * num_tables]
    num_rows = outs[0].shape[0]

    def copies(j, row):
        return [
            pltpu.make_async_copy(v.at[pl.ds(j, 1)], o.at[pl.ds(row, 1)], sem)
            for v, o in zip(values, outs)
        ]

    def wait_one():
        # every copy moves one row of its table: a wait for a copy of the
        # same shape retires one of them, whichever it was
        for c in copies(0, 0):
            c.wait()

    def one(j, issued):
        row = rows_ref[j]
        inside = row < num_rows

        @pl.when(inside & (issued >= in_flight))
        def _():
            wait_one()

        @pl.when(inside)
        def _():
            for c in copies(j, row):
                c.start()

        return issued + inside.astype(jnp.int32)

    def body(step, issued):
        # a few ids an iteration: the loop's own overhead is per iteration
        for u in range(UNROLL):
            issued = one(step * UNROLL + u, issued)
        return issued

    size = rows_ref.shape[0]
    issued = jax.lax.fori_loop(0, size // UNROLL, body, jnp.int32(0))
    for j in range(size - size % UNROLL, size):
        issued = one(j, issued)

    def drain(_, carry):
        wait_one()
        return carry

    jax.lax.fori_loop(0, jnp.minimum(issued, in_flight), drain, jnp.int32(0))


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(2,))
def write_rows(
    rows: jax.Array,                  # (B,) int32, distinct inside the table
    values: tuple,                    # per table, (B, width)
    tables: tuple,                    # per table, (num_rows, width)
    *,
    interpret: bool | None = None,    # None: interpret off the TPU
) -> tuple:
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    wide = [t for t, table in enumerate(tables) if table.shape[-1] % LANES == 0]
    out = [table if t in wide
           else table.at[rows].set(values[t], mode="drop", unique_indices=True)
           for t, table in enumerate(tables)]
    if wide:
        written = _dma_rows(
            rows, tuple(values[t] for t in wide), tuple(tables[t] for t in wide),
            interpret=interpret,
        )
        for t, table in zip(wide, written):
            out[t] = table
    return tuple(out)


def _dma_rows(rows, values: tuple, tables: tuple, *, interpret: bool) -> tuple:
    num_tables = len(tables)
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(
            _write_rows_kernel, num_tables=num_tables, in_flight=IN_FLIGHT
        ),
        out_shape=tuple(jax.ShapeDtypeStruct(t.shape, t.dtype) for t in tables),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[anywhere] * (2 * num_tables),
            out_specs=tuple([anywhere] * num_tables),
            scratch_shapes=[pltpu.SemaphoreType.DMA(())],
        ),
        input_output_aliases={1 + num_tables + t: t for t in range(num_tables)},
        interpret=interpret,
        name="write_rows",
    )(rows, *values, *tables)
