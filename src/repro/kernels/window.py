"""Keyed event-time window aggregation: the kernels NEXmark Q5 ("hot items")
and Q7 ("highest bid") share (:mod:`repro.ops.nexmark`).

Everything here has fixed shapes, so a window task compiles once:

  * :func:`pane_counts` — how many times each key in ``[0, size)`` occurs in
    a batch of int32 keys (keys outside are not counted). The window tasks
    make their keys relative to the pane a row falls in, so the key indexes
    a fixed-size table. On a TPU this is the Pallas kernel ``window_count``:
    a one-hot compare of every key against a block of table slots, summed
    over the keys, with no scatter (XLA's scatter serialises on duplicate
    indices, and half of NEXmark's bids carry one key).
  * :func:`segment_max` — the largest value in each of the two segments of a
    batch (segment 0 or 1, -1 for rows in neither), -1 for an empty one: an
    int32 max, which XLA reduces in one pass with no duplicate-index cost.
  * :func:`first_positions` — the tie rule: the positions of the first
    ``capacity`` set entries of a mask in ascending order, and how many are
    set in all.
  * :func:`pack_rows` — emission into a fixed number of rows: blocks of
    rows, each with a row count, laid one after another from row 0; the rest
    zero.

``backend()`` (:mod:`repro.kernels.ops`) picks ``window_count`` on a TPU, the
jnp reference elsewhere, or the kernel body in interpret mode (tests).
Counts are integers: every path gives the same numbers.
"""
from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
SLOT_BLOCK = 128  # table slots compared per grid step of window_count


def _backend() -> str:
    from .ops import backend

    return backend()


def _lanes(a: jnp.ndarray, fill) -> jnp.ndarray:
    """A 1-D array padded with ``fill`` to whole (8, 128) tiles, (R, 128)."""
    pad = (-a.shape[0]) % (8 * LANES)
    if pad:
        a = jnp.concatenate([a, jnp.full((pad,), fill, a.dtype)])
    return a.reshape(-1, LANES)


# -- pane_counts ------------------------------------------------------------------

def _count_kernel(keys_ref, o_ref):
    """Counts of the slots of one block: slot ``t0 + r`` sits in sublane row
    ``r``; each row of 128 keys is compared against every slot of the block
    and the hits are summed across the key rows, then across the lanes."""
    t0 = pl.program_id(0) * SLOT_BLOCK
    slots = jax.lax.broadcasted_iota(jnp.int32, (SLOT_BLOCK, LANES), 0) + t0

    def body(g, acc):
        keys = keys_ref[pl.ds(pl.multiple_of(g * 8, 8), 8), :]  # 8 rows of 128 keys
        for r in range(8):
            acc = acc + (keys[r:r + 1, :] == slots).astype(jnp.float32)
        return acc

    acc = jax.lax.fori_loop(0, keys_ref.shape[0] // 8, body,
                            jnp.zeros((SLOT_BLOCK, LANES), jnp.float32))
    o_ref[...] = jnp.sum(acc, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("size", "interpret"))
def _pane_counts_pallas(keys: jnp.ndarray, size: int, interpret: bool = False) -> jnp.ndarray:
    if size % SLOT_BLOCK:
        raise ValueError(f"table size {size} is not a multiple of {SLOT_BLOCK}")
    rows = _lanes(keys.astype(jnp.int32), -1)
    counts = pl.pallas_call(
        _count_kernel,
        grid=(size // SLOT_BLOCK,),
        in_specs=[pl.BlockSpec(rows.shape, lambda i: (0, 0))],
        out_specs=pl.BlockSpec((SLOT_BLOCK, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((size, 1), jnp.float32),
        name="window_count",
        interpret=interpret,
    )(rows)
    return counts[:, 0].astype(jnp.int32)  # a count is at most the batch: exact


def pane_counts_ref(keys: jnp.ndarray, size: int) -> jnp.ndarray:
    keys = keys.astype(jnp.int32)
    inside = (keys >= 0) & (keys < size)
    return jnp.zeros((size,), jnp.int32).at[jnp.where(inside, keys, size)].add(1, mode="drop")


def pane_counts(keys: jnp.ndarray, size: int) -> jnp.ndarray:
    """(size,) int32: occurrences of each key in ``[0, size)`` among ``keys``."""
    be = _backend()
    if be == "ref":
        return pane_counts_ref(keys, size)
    return _pane_counts_pallas(keys, size, interpret=(be == "interpret"))


# -- segment_max ------------------------------------------------------------------

def segment_max(values: jnp.ndarray, seg: jnp.ndarray) -> jnp.ndarray:
    """(2,) int32: the largest of ``values`` (each at least 0) among the rows
    of segment 0 and of segment 1; -1 where a segment has no row."""
    values = values.astype(jnp.int32)
    return jnp.stack([jnp.max(jnp.where(seg == s, values, -1)) for s in (0, 1)])


# -- ties and emission ----------------------------------------------------------------

def first_positions(mask: jnp.ndarray, capacity: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The positions of the first ``capacity`` True entries of ``mask``, in
    ascending order ((capacity,) int32, 0 past the last), and the number of
    True entries in all (int32). One-hot by rank: no scatter, no sort."""
    rank = jnp.cumsum(mask.astype(jnp.int32)) - 1
    pos = jnp.arange(mask.shape[0], dtype=jnp.int32)
    hit = mask[None, :] & (rank[None, :] == jnp.arange(capacity, dtype=jnp.int32)[:, None])
    return jnp.sum(jnp.where(hit, pos[None, :], 0), axis=1), jnp.sum(mask.astype(jnp.int32))


def pick(values: jnp.ndarray, positions: jnp.ndarray) -> jnp.ndarray:
    """``values[positions]`` for a few positions, by one-hot comparison."""
    pos = jnp.arange(values.shape[0], dtype=jnp.int32)
    hit = positions[:, None] == pos[None, :]
    return jnp.sum(jnp.where(hit, values[None, :], 0), axis=1).astype(values.dtype)


def pack_rows(blocks: Sequence[Tuple[jnp.ndarray, jnp.ndarray]], rows: int) -> jnp.ndarray:
    """``rows`` rows holding each block's first ``n`` rows, block after block
    from row 0, and zeros after them; rows past ``rows`` are dropped.
    ``blocks`` is a sequence of ((cap, width) array, n)."""
    width = blocks[0][0].shape[1]
    room = rows + sum(b.shape[0] for b, _ in blocks)
    out = jnp.zeros((room, width), blocks[0][0].dtype)
    at = jnp.zeros((), jnp.int32)
    for block, n in blocks:
        live = jnp.arange(block.shape[0])[:, None] < n
        # a later block starts where this one's rows end, over its zero tail
        out = jax.lax.dynamic_update_slice(out, jnp.where(live, block, 0), (at, 0))
        at = at + n
    return out[:rows]
