"""Interaction pairs for implicit ALS, laid out on the device once.

Hu et al.'s r_ui is an aggregate, so a user's repeated ratings of one
item are merged into one pair whose strength is their sum, and each pair
carries the confidence c = 1 + alpha * r (``workloads.implicit``).  The
merged pairs are then laid out twice, grouped by user and by item, for the
two half-steps of an ALS epoch (``core/als.py``).

A side's layout cuts each row's pairs into fixed-size chunks of
``chunk`` pairs, in row order: a row of n pairs takes ceil(n / chunk)
chunks, the last one padded with confidence 0.  Padding every row to the
longest one would pad 62 k item rows to the 162 k pairs of the most
popular item; chunks pad a row by less than one chunk, and a hot row is
summed over its chunks.  Rows are solved in blocks of ``rows_per_block``
consecutive rows, whose chunks are a contiguous range read
``chunks_per_launch`` at a time.

Everything is built with device sorts and scatters; the host reads back a
handful of integers (the pair and chunk counts, and each row block's
chunk range) to fix the layout's static shapes.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# pairs a chunk holds, rows a solve takes, chunks a Gramian launch reads
CHUNK = 64
ROWS_PER_BLOCK = 2048
CHUNKS_PER_LAUNCH = 256


class Block(NamedTuple):
    """One solve's rows and the chunks it reads (host integers).

    The block solves rows ``[lo + first, lo + rows_per_block)``; ``lo`` is
    pulled back so that the block never runs past the table, and the rows
    below ``lo + first`` belong to the block before it.  Its chunks start at
    ``start`` and take ``launches`` reads of ``chunks_per_launch``."""

    lo: int
    first: int
    start: int
    launches: int


class SideLayout(NamedTuple):
    """One side's pairs in chunks: the rows solved, the fixed side's ids.

    ``row``, ``col`` and ``conf`` carry ``chunks_per_launch`` padding chunks
    past the last real one (row ``num_rows``, confidence 0), so that every
    launch reads a whole window."""

    row: jax.Array      # (chunks + pad,) int32: the row each chunk belongs to
    col: jax.Array      # (chunks + pad, chunk) int32: fixed-side ids
    conf: jax.Array     # (chunks + pad, chunk) float32: c_ui; 0 where padded
    count: jax.Array    # (num_rows,) float32: the row's pairs, n_u
    num_rows: int
    chunks: int         # real chunks
    solved_rows: int    # rows with at least one pair
    split_rows: int     # rows whose pairs span more than one chunk
    rows_per_block: int
    chunks_per_launch: int
    blocks: Tuple[Block, ...]

    @property
    def chunk(self) -> int:
        return int(self.col.shape[1])


class PairLayouts(NamedTuple):
    user: SideLayout    # rows are users, ids are items
    item: SideLayout    # rows are items, ids are users
    pairs: int

    @property
    def pad_share(self) -> float:
        """Share of the pair slots of both layouts that are padding."""
        slots = sum(side.chunks * side.chunk for side in (self.user, self.item))
        return 1.0 - 2.0 * self.pairs / max(slots, 1)

    @property
    def split_rows(self) -> int:
        return self.user.split_rows + self.item.split_rows


@jax.jit
def _order(key, payload):
    """``payload`` stably sorted by ``key``.  Every sort of the layout is
    this one program at the log's length: the TPU compiler takes about a
    minute for each sort program of tens of millions of elements, and
    longer the more operands it carries."""
    return jax.lax.sort((key, payload), num_keys=1)[1]


@jax.jit
def _merge(user, item, rating, order):
    """The log in (user, item) order, each pair's first rating flagged,
    with the sum of the pair's ratings there."""
    user, item, rating = user[order], item[order], rating[order]
    head = jnp.concatenate([
        jnp.ones((1,), bool), (user[1:] != user[:-1]) | (item[1:] != item[:-1])])
    seg = jnp.cumsum(head.astype(jnp.int32)) - 1
    strength = jax.ops.segment_sum(rating, seg, num_segments=user.shape[0],
                                   indices_are_sorted=True)
    return user, item, strength[seg], head, seg[-1] + 1


@functools.partial(jax.jit, static_argnames=("num_rows", "chunk"))
def _chunk_offsets(row, valid, *, num_rows, chunk):
    """Pairs per row and each row's first chunk (exclusive cumsum of its
    chunk count), with the total at the end."""
    count = jax.ops.segment_sum(valid.astype(jnp.int32), row, num_segments=num_rows)
    chunks = (count + chunk - 1) // chunk
    first = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(chunks)])
    return count, first


@functools.partial(jax.jit, static_argnames=("num_rows", "chunk", "num_chunks"))
def _chunks(col, conf, count, first, *, num_rows, chunk, num_chunks):
    """Each chunk's row and its slots' ids and confidences, gathered from
    the pairs (``col``, ``conf``) sorted by row; ``first`` is each row's
    first chunk.  Slots past a row's pairs, and chunks past the last, are
    padding."""
    c = jnp.arange(num_chunks, dtype=jnp.int32)
    row = jnp.searchsorted(first, c, side="right").astype(jnp.int32) - 1
    live = c < first[-1]
    row = jnp.where(live, row, num_rows)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(count)[:-1]])
    r = jnp.minimum(row, num_rows - 1)[:, None]
    offset = (c[:, None] - first[r]) * chunk + jnp.arange(chunk, dtype=jnp.int32)
    valid = live[:, None] & (offset < count[r])
    src = jnp.where(valid, starts[r] + offset, 0)
    return row, jnp.where(valid, col[src], 0), jnp.where(valid, conf[src], 0.0)


def _side(row, col, conf, valid, index, *, num_rows, chunk, rows_per_block,
          chunks_per_launch) -> SideLayout:
    """One side's layout from the log's entries, the pairs among them marked
    ``valid``: a stable sort by row keeps each row's pairs in the order
    they come in."""
    count, first = _chunk_offsets(row, valid, num_rows=num_rows, chunk=chunk)
    total = int(first[-1])
    # the pairs first, by row
    order = _order(jnp.where(valid, row, num_rows), index)
    chunk_row, chunk_col, chunk_conf = _chunks(
        col[order], conf[order], count, first, num_rows=num_rows, chunk=chunk,
        num_chunks=total + chunks_per_launch)
    rows_per_block = min(rows_per_block, num_rows)
    bounds = np.arange(0, num_rows, rows_per_block)
    edges = np.asarray(first)[np.append(bounds, num_rows)]
    blocks = []
    for b, lo_row in enumerate(bounds):
        lo = min(int(lo_row), num_rows - rows_per_block)
        start, end = int(edges[b]), int(edges[b + 1])
        blocks.append(Block(lo=lo, first=int(lo_row) - lo, start=start,
                            launches=-(-(end - start) // chunks_per_launch)))
    count_h = np.asarray(count)
    return SideLayout(
        row=chunk_row, col=chunk_col, conf=chunk_conf,
        count=count.astype(jnp.float32), num_rows=num_rows, chunks=total,
        solved_rows=int(np.sum(count_h > 0)),
        split_rows=int(np.sum(count_h > chunk)), rows_per_block=rows_per_block,
        chunks_per_launch=chunks_per_launch, blocks=tuple(blocks))


def pair_layouts(user, item, rating, num_users: int, num_items: int, *,
                 alpha: float, chunk: int = CHUNK,
                 rows_per_block: int = ROWS_PER_BLOCK,
                 chunks_per_launch: int = CHUNKS_PER_LAUNCH) -> PairLayouts:
    """Merge a rating log into pairs and lay them out by user and by item.

    ``rating`` is read as interaction strength: a pair's r is the sum of
    its ratings and its confidence ``1 + alpha * r``."""
    from repro.workloads.implicit import confidence_weights

    user = jnp.asarray(user, jnp.int32)
    item = jnp.asarray(item, jnp.int32)
    index = jnp.arange(user.shape[0], dtype=jnp.int32)
    # (user, item) order by two stable sorts, the minor key first
    by_item = _order(item, index)
    u, i, strength, head, pairs = _merge(
        user, item, jnp.asarray(rating, jnp.float32), _order(user[by_item], by_item))
    pairs = int(pairs)
    conf = jnp.where(head, confidence_weights(strength, alpha), 0.0)
    sizes = dict(chunk=chunk, rows_per_block=rows_per_block,
                 chunks_per_launch=chunks_per_launch)
    by_user = _side(u, i, conf, head, index, num_rows=num_users, **sizes)
    by_item = _side(i, u, conf, head, index, num_rows=num_items, **sizes)
    return PairLayouts(user=by_user, item=by_item, pairs=pairs)
