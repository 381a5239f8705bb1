"""Row gathers by DMA into a double-buffered VMEM slab.

The chip cannot copy one row of a 2-D array in HBM: its tiles hold 8 rows
(a 16-bit row shares its words with the next). Viewed as ``(N, 1, W)``,
each row is a contiguous run of ``W`` words, so a one-row copy moves
exactly the row's bytes. A kernel that reads rows by indices in SMEM
passes that view with ``memory_space=pltpu.HBM``; block ``b`` is the rows
of ``ids[b·n … b·n + n − 1]``. Each grid step copies the next block's rows
into one half of a ``(2, n, 1, W)`` slab while it works on the current
block's, in the other half.

Used by the fused negative kernel (a token block's negative rows) and the
weighted table-gradient scatter (a block of sorted slots' source rows).
"""
from __future__ import annotations

import math

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def slab_scratch(n: int, width: int, dtype):
    """Scratch for :func:`gather_block`: the ``(2, n, 1, width)`` slab and
    one DMA semaphore a half."""
    return [pltpu.VMEM((2, n, 1, width), dtype),
            pltpu.SemaphoreType.DMA((2,))]


def start_rows(ids_ref, words_ref, slab, sem, block, slot, n):
    """Start the ``n`` row copies of block ``block`` into slab half
    ``slot``, all signalling that half's semaphore."""
    unroll = math.gcd(n, 8)

    def body(i, carry):
        for u in range(unroll):
            k = i * unroll + u
            pltpu.make_async_copy(words_ref.at[pl.ds(ids_ref[block * n + k],
                                                     1)],
                                  slab.at[slot, pl.ds(k, 1)],
                                  sem.at[slot]).start()
        return carry

    jax.lax.fori_loop(0, n // unroll, body, 0)


def gather_block(ids_ref, words_ref, slab, sem, step, last, block_of):
    """Double-buffered slab gather for grid step ``step`` (counted over the
    whole call) of a sweep whose block at step s is ``block_of(s)``:
    prime the first block, start the next one into the other half, wait
    for this one. Returns the slab half that holds this step's rows."""
    n = slab.shape[1]
    slot = step % 2

    @pl.when(step == 0)
    def _prime():
        start_rows(ids_ref, words_ref, slab, sem, block_of(step), slot, n)

    @pl.when(step < last)
    def _prefetch():
        start_rows(ids_ref, words_ref, slab, sem, block_of(step + 1),
                   1 - slot, n)

    # one wait for the whole half: its byte count is the sum of the rows'
    pltpu.make_async_copy(slab.at[slot], slab.at[slot], sem.at[slot]).wait()
    return slot
