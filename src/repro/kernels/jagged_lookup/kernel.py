"""Pallas TPU kernel: jagged embedding lookup (paper §4.1.2).

TPU layout rule for the gather's row-at-a-time BlockSpecs: a BlockSpec's
last two dims must be multiples of the chip's (8, 128) tile or the whole
array, and a table in HBM is stored in tiles of :func:`row_tile` rows
(8 for 32-bit dtypes, 16 for bf16/fp16). So a single row is never a block.
A row ``r`` is reached through the block that holds its tile
(``r // row_tile``) and picked out in VMEM by :func:`pick_row`, which is an
exact select. The table itself is never re-laid out.

Forward — scalar-prefetch gather: the packed *valid* indices are prefetched
into SMEM and drive the BlockSpec ``index_map`` directly, so each grid
step DMAs the tiles of ``rows`` live embedding rows HBM→VMEM (one
BlockSpec window per slot) and writes one ``(rows, D)`` output block.
Padding never enters the kernel (the paper's 'operate only on valid
indices'); there is no per-row zero-check or branch (the paper's KJT
complaint) because validity is resolved before launch.

Backward — sorted scatter-add: indices are sorted in the ops wrapper (the
paper's table-major batch regrouping, which also gives the L2-locality
win), so duplicate rows occupy *consecutive* positions and the kernels
accumulate them in order.

Two backward variants exist:

* :func:`runsum_pallas` — run-sums pre-materialized ``(n, D)`` grad rows
  (the two-pass oracle path: rows are built in HBM first), 8 rows per
  grid step, with the run starts computed outside the kernel;
* :func:`weighted_runsum_scatter` — the fused variant: each grad row is
  *generated inside the kernel* as ``w[slot] · (o[src] · scale)`` and
  added in VMEM to the copy of the tile that holds its destination row of
  the dense ``(V, D)`` gradient. The per-pair ``(n, D)`` grad-row buffer
  never exists in HBM — the last big negative-path temporary. A grid step
  walks a block of sorted slots. Their source rows are gathered as rows,
  one DMA each, into a double-buffered slab (:mod:`repro.kernels.row_dma`)
  while the previous block is walked. A destination tile is entered once,
  from zero (the sorted slots of a tile are contiguous, and the gradient
  starts at zero), and written back by an asynchronous DMA when the walk
  leaves it; a few tiles stay in flight in VMEM, and the call drains them.

SMEM holds 1 MiB, so scalar-prefetched index arrays are bounded: the
scatter walks its slots in chunks of :data:`SCATTER_SLOTS_PER_CALL`, one
``pallas_call`` each, threading the gradient buffer through the chunks;
a call reads back only its first tile, which the call before may have
begun.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import autotune
from repro.kernels.row_dma import gather_block, slab_scratch
from repro.obs.metrics import KERNEL_METRICS

# slots per weighted-scatter call: its three 32-bit prefetch arrays (ids,
# sources, weights) of this length take 384 KiB of the chip's 1 MiB SMEM
SCATTER_SLOTS_PER_CALL = 1 << 15


def row_tile(dtype) -> int:
    """Rows in one HBM tile of a 2-D array of ``dtype`` (8 per 32-bit
    sublane tile; half-width types pack two rows per sublane)."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def pick_row(tile: jax.Array, r) -> jax.Array:
    """Row ``r`` of a (rows, D) fp32 tile as (1, D): an exact select (one
    value plus zeros), legal for any dynamic ``r`` on the chip."""
    sel = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 0) == r
    return jnp.sum(jnp.where(sel, tile, 0.0), axis=0, keepdims=True)


# --------------------------------------------------------------------------
# forward gather
# --------------------------------------------------------------------------

def _gather_kernel(ids_ref, *refs, rows, sub):
    tbl_refs, out_ref = refs[:rows], refs[rows]
    i = pl.program_id(0)
    D = out_ref.shape[1]
    slot = jax.lax.broadcasted_iota(jnp.int32, (rows, D), 0)
    blk = jnp.zeros((rows, D), jnp.float32)
    for u in range(rows):
        row = pick_row(tbl_refs[u][...].astype(jnp.float32),
                       ids_ref[i * rows + u] % sub)
        blk = jnp.where(slot == u, row, blk)
    # one vectorized (rows, D) store per grid step
    out_ref[...] = blk.astype(out_ref.dtype)


def gather_pallas(table: jax.Array, ids: jax.Array, *,
                  rows_per_step: int = 1,
                  interpret: bool = False) -> jax.Array:
    """table (V, D), ids (n,) int32 (pre-clipped to [0, V)) → (n, D).

    ``rows_per_step`` batches the gather: each grid step issues that many
    tile DMAs (the table is passed once per slot — same HBM buffer, one
    BlockSpec window each) and lands them with a single block store. It is
    rounded up to a multiple of 8, the output block's sublane tile. Pure
    data movement (floats round-trip exactly through fp32), so every
    setting is bitwise identical.
    """
    n = ids.shape[0]
    V, D = table.shape
    rows = -(-max(int(rows_per_step), 1) // 8) * 8
    sub = row_tile(table.dtype)
    pad = (-n) % rows
    if pad:  # padded slots re-gather row 0; sliced off below
        ids = jnp.concatenate([ids, jnp.zeros((pad,), ids.dtype)])
    np_ = n + pad

    def _at_slot(u):
        return pl.BlockSpec(
            (sub, D), lambda i, ids_ref, u=u: (ids_ref[i * rows + u] // sub, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(np_ // rows,),
        in_specs=[_at_slot(u) for u in range(rows)],
        out_specs=pl.BlockSpec((rows, D), lambda i, ids_ref: (i, 0)),
    )
    cost = autotune.estimate_cost(
        "lookup_gather", {"n": np_, "D": D, "itemsize": table.dtype.itemsize},
        {"rows_per_step": rows})
    out = pl.pallas_call(
        functools.partial(_gather_kernel, rows=rows, sub=sub),
        name="lookup_gather",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((np_, D), table.dtype),
        interpret=interpret,
        **autotune.pallas_cost(bytes_accessed=cost["bytes_accessed"]),
    )(ids, *([table] * rows))
    return out[:n] if pad else out


# --------------------------------------------------------------------------
# backward run-sum (ids must be sorted ascending — table-major regrouping)
# --------------------------------------------------------------------------

def _runsum_kernel(first_ref, grows_ref, out_ref, acc_ref):
    """Running sum within each run of equal sorted ids, 8 rows per step.

    out[i] = Σ grad_rows[j..i] for the run containing i — the run TOTAL
    lands on the run's last element; the ops wrapper scatters exactly those
    (unique destinations, so the final XLA scatter is conflict-free).
    The accumulator lives in VMEM scratch and persists across the
    (sequential) grid, exploiting the same consecutive-duplicates locality
    the paper's table-level regrouping creates on Ascend L2.
    """
    for k in range(grows_ref.shape[0]):
        row = grows_ref[pl.ds(k, 1), :].astype(jnp.float32)
        starts = first_ref[pl.ds(k, 1), :] > 0                 # (1, 1)
        acc = jnp.where(starts, row, acc_ref[...] + row)
        acc_ref[...] = acc
        out_ref[pl.ds(k, 1), :] = acc.astype(out_ref.dtype)


def runsum_pallas(grad_rows: jax.Array, sorted_ids: jax.Array, *,
                  interpret: bool = False) -> jax.Array:
    """grad_rows (n, D) + sorted ids (n,) → per-run running sums (n, D)."""
    n, D = grad_rows.shape
    first = jnp.concatenate([jnp.ones((1,), bool),
                             sorted_ids[1:] != sorted_ids[:-1]])
    pad = (-n) % 8
    grows = grad_rows
    if pad:
        first = jnp.concatenate([first, jnp.ones((pad,), bool)])
        grows = jnp.concatenate([grows, jnp.zeros((pad, D), grows.dtype)])
    out = pl.pallas_call(
        _runsum_kernel,
        name="lookup_runsum",
        grid=((n + pad) // 8,),
        in_specs=[pl.BlockSpec((8, 1), lambda i: (i, 0)),
                  pl.BlockSpec((8, D), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((8, D), lambda i: (i, 0)),
        scratch_shapes=[pltpu.VMEM((1, D), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((n + pad, D), jnp.float32),
        interpret=interpret,
        **autotune.pallas_cost(flops=n * D, bytes_accessed=8 * n * D),
    )(first.astype(jnp.int32)[:, None], grows)
    return out[:n] if pad else out


# --------------------------------------------------------------------------
# fused weighted scatter — grad rows generated in sorted-id order
# --------------------------------------------------------------------------

# rows of an fp32 HBM tile: the unit the gradient is entered and written in
_TILE = row_tile(jnp.float32)


def _wscatter_kernel(sids_ref, src_ref, w_ref, o_ref, prev_ref, out_ref,
                     slab, sem, tiles, tile_sem, open_ref, *, vocab):
    """Walk one block of sorted slots: add ``w · (o[src] · scale)`` to its
    destination row in the VMEM copy of the destination's tile.

    ``sids`` (sorted destination ids), ``src`` (source token) and ``w``
    (weight) per slot are scalar-prefetched; ``o`` holds the rows already
    times ``scale``. The block's source rows are in slab half ``half``,
    copied row by row while the previous block was walked. They are
    weighted there in place, then added to their rows in slot order: two
    passes, so that no product and the sum it feeds can fuse into one
    multiply-add (an interpreter would, and round once, not twice).

    A tile's slots are contiguous in the sorted order, so it is entered
    once: from zero (the gradient starts at zero), except the call's first
    tile, which an earlier call may have begun and is read back. When the
    walk leaves a tile, its write-back starts and the walk goes on in the
    next of ``tiles``' buffers; a buffer is waited on only when the walk
    comes round to it again, and the last step drains them. ``open_ref``
    carries the open tile and the number of tiles entered before it from
    one grid step to the next.
    """
    b = pl.program_id(0)
    last = pl.num_programs(0) - 1
    S = slab.shape[1]
    ring = tiles.shape[0]
    half = gather_block(src_ref, o_ref, slab, sem, b, last, lambda s: s)

    def weigh(g, carry):             # the block's rows, w · (o · scale)
        for u in range(8):
            k = g * 8 + u
            slab[half, k] = w_ref[b * S + k] * slab[half, k]
        return carry

    jax.lax.fori_loop(0, S // 8, weigh, 0)

    def write(t, j):
        at = pl.multiple_of(t * _TILE, _TILE)
        return pltpu.make_async_copy(tiles.at[j], out_ref.at[pl.ds(at, _TILE)],
                                     tile_sem.at[j])

    @pl.when(b == 0)
    def _enter_first_tile():
        t = jnp.minimum(sids_ref[0], vocab) // _TILE
        at = pl.multiple_of(t * _TILE, _TILE)
        read = pltpu.make_async_copy(prev_ref.at[pl.ds(at, _TILE)],
                                     tiles.at[0], tile_sem.at[0])
        read.start()
        read.wait()
        open_ref[0] = t
        open_ref[1] = 0

    def add(k, carry):
        t_open, n = carry
        i = b * S + k
        dst = jnp.minimum(sids_ref[i], vocab)
        t = dst // _TILE
        leave = t != t_open

        @pl.when(leave)
        def _next_tile():
            write(t_open, n % ring).start()
            j = (n + 1) % ring

            @pl.when(n + 1 >= ring)
            def _free():                 # the tile entered ``ring`` ago
                write(t_open, j).wait()

            tiles[j] = jnp.zeros(tiles.shape[1:], jnp.float32)

        n = n + leave.astype(jnp.int32)
        j = n % ring
        r = dst % _TILE
        tiles[j, pl.ds(r, 1), :] = tiles[j, pl.ds(r, 1), :] + slab[half, k]
        return t, n

    def eight(g, carry):
        for u in range(8):
            carry = add(g * 8 + u, carry)
        return carry

    t_open, n = jax.lax.fori_loop(0, S // 8, eight, (open_ref[0], open_ref[1]))
    open_ref[0] = t_open
    open_ref[1] = n

    @pl.when(b == last)
    def _drain():
        write(t_open, n % ring).start()

        def wait(m, carry):
            write(t_open, m % ring).wait()
            return carry

        jax.lax.fori_loop(jnp.maximum(n + 1 - ring, 0), n + 1, wait, 0)


def weighted_runsum_scatter(o: jax.Array, weights: jax.Array,
                            sorted_ids: jax.Array, src: jax.Array,
                            vocab: int, *, scale: float = 1.0,
                            live: Optional[jax.Array] = None,
                            interpret: bool = False) -> jax.Array:
    """Σ over sorted slots of ``weights[i] · o[src[i]] · scale`` per id.

    o (T, D) fp32; weights (n,) fp32 (zeroed for dropped slots);
    sorted_ids (n,) int32 ascending with dropped slots keyed ≥ vocab; src
    (n,) int32 source row per slot. Returns (vocab + 1, D) fp32 where row
    ``vocab`` is the drop sink and rows never visited are zero. ``live``,
    a traced count of the leading slots that land (the rest dropped),
    stops the walk after the calls that hold them. Each row gets its
    additions in sorted-slot order, whatever the block and call sizes.
    """
    n = sorted_ids.shape[0]
    T, D = o.shape
    chunk = min(SCATTER_SLOTS_PER_CALL, -(-n // 8) * 8)
    S = min(autotune.wscatter_slots_per_block({"D": D, "itemsize": 4}),
            chunk)
    chunk = -(-chunk // S) * S
    pad = (-n) % chunk
    KERNEL_METRICS.gauge(
        "wscatter_src_bytes_per_slot",
        "HBM bytes each source-row DMA of the weighted scatter moves").set(
            4 * D)
    KERNEL_METRICS.gauge(
        "wscatter_slots_per_block",
        "sorted slots a grid step of the weighted scatter walks").set(S)
    sids = jnp.concatenate([sorted_ids.astype(jnp.int32),
                            jnp.full((pad,), vocab, jnp.int32)])
    srcs = jnp.concatenate([src.astype(jnp.int32),
                            jnp.zeros((pad,), jnp.int32)])
    ws = jnp.concatenate([weights.astype(jnp.float32),
                          jnp.zeros((pad,), jnp.float32)])
    rows = -(-(vocab + 1) // _TILE) * _TILE
    # tiles entered per call, for the cost hint: the walk's tiles spread
    # evenly over its calls
    entered = -(-min(n + pad, rows // _TILE) * chunk // (n + pad))
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    call = pl.pallas_call(
        functools.partial(_wscatter_kernel, vocab=vocab),
        name="lookup_wscatter",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(chunk // S,),
            in_specs=[hbm, hbm],
            out_specs=hbm,
            scratch_shapes=[
                *slab_scratch(S, D, jnp.float32),
                pltpu.VMEM((autotune.WSCATTER_TILES_IN_FLIGHT, _TILE, D),
                           jnp.float32),
                pltpu.SemaphoreType.DMA((autotune.WSCATTER_TILES_IN_FLIGHT,)),
                pltpu.SMEM((2,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((rows, D), jnp.float32),
        input_output_aliases={4: 0},
        interpret=interpret,
        # a source row a slot; each tile entered read (the zeros it
        # starts from, in effect) and written once
        **autotune.pallas_cost(
            flops=3 * chunk * D,
            bytes_accessed=4 * chunk * D + 2 * entered * _TILE * D * 4),
    )
    # o · scale as the two-pass path forms it, a row a contiguous run (one
    # DMA each)
    o_rows = (o.astype(jnp.float32) * scale).reshape(T, 1, D)

    def body(c, out):
        at = c * chunk
        return call(jax.lax.dynamic_slice_in_dim(sids, at, chunk),
                    jax.lax.dynamic_slice_in_dim(srcs, at, chunk),
                    jax.lax.dynamic_slice_in_dim(ws, at, chunk), o_rows, out)

    calls = (n + pad) // chunk
    if live is not None:
        calls = jnp.minimum((live + chunk - 1) // chunk, calls)
    out = jax.lax.fori_loop(0, calls, body,
                            jnp.zeros((rows, D), jnp.float32))
    return out[:vocab + 1]
