"""Pallas TPU kernel: jagged embedding lookup (paper §4.1.2).

TPU layout rule shared by every row-at-a-time kernel here: a BlockSpec's
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
  *generated inside the kernel* as ``w[slot] · (o[src] · scale)`` (the
  source row gathered by a scalar-prefetched index) and added in VMEM to
  the output tile that holds its destination row of the dense ``(V, D)``
  gradient. The per-pair ``(n, D)`` grad-row buffer never exists in HBM —
  the last big negative-path temporary. Because the output BlockSpec index
  is the destination *tile* (constant across a sorted run), Pallas only
  writes a tile back when the sorted ids leave it.

SMEM holds 1 MiB, so scalar-prefetched index arrays are bounded: the
scatter walks its slots in chunks of :data:`SCATTER_SLOTS_PER_CALL`, one
``pallas_call`` each, threading the gradient buffer through the chunks.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import autotune

# slots per weighted-scatter call: two int32 prefetch arrays of this length
# take 256 KiB of the chip's 1 MiB SMEM
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

def _wscatter_kernel(sids_ref, src_ref, w_ref, o_ref, prev_ref, out_ref,
                     acc_ref, *, scale, vocab, sub):
    """Generate grad row ``w · (o[src] · scale)`` and add it to its
    destination row in the VMEM copy of the destination's tile.

    ``sids`` (sorted destination ids) and ``src`` (source token per sorted
    slot) are scalar-prefetched: ``src`` drives the o-tile gather, ``sids``
    the *output* tile — so each tile is read once (from the gradient so far,
    ``prev``), updated in place while the sorted ids stay in it, and written
    back once.
    """
    i = pl.program_id(0)
    dst = jnp.minimum(sids_ref[i], vocab)
    prev_dst = jnp.minimum(sids_ref[jnp.maximum(i - 1, 0)], vocab)

    @pl.when((i == 0) | (dst // sub != prev_dst // sub))
    def _enter_tile():
        acc_ref[...] = prev_ref[...]

    w = w_ref[pl.ds(i % 8, 1), :]                                # (1, 1)
    # identical op order to the two-pass path: w · (o · scale)
    row = w * (pick_row(o_ref[...], src_ref[i] % sub) * scale)
    r = dst % sub
    acc_ref[pl.ds(r, 1), :] = acc_ref[pl.ds(r, 1), :] + row
    out_ref[...] = acc_ref[...]


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
    stops the walk after the calls that hold them.
    """
    n = sorted_ids.shape[0]
    T, D = o.shape
    sub = row_tile(o.dtype)
    chunk = min(SCATTER_SLOTS_PER_CALL, -(-n // 8) * 8)
    pad = (-n) % chunk
    sids = jnp.concatenate([sorted_ids.astype(jnp.int32),
                            jnp.full((pad,), vocab, jnp.int32)])
    srcs = jnp.concatenate([src.astype(jnp.int32),
                            jnp.zeros((pad,), jnp.int32)])
    ws = jnp.concatenate([weights.astype(jnp.float32),
                          jnp.zeros((pad,), jnp.float32)])[:, None]

    def dst_tile(i, sids_ref, src_ref):
        return (jnp.minimum(sids_ref[i], vocab) // sub, 0)

    call = pl.pallas_call(
        functools.partial(_wscatter_kernel, scale=scale, vocab=vocab,
                          sub=sub),
        name="lookup_wscatter",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(chunk,),
            in_specs=[
                pl.BlockSpec((8, 1), lambda i, sids, src: (i // 8, 0)),
                pl.BlockSpec((sub, D), lambda i, sids, src: (src[i] // sub,
                                                             0)),
                pl.BlockSpec((sub, D), dst_tile),
            ],
            out_specs=pl.BlockSpec((sub, D), dst_tile),
            scratch_shapes=[pltpu.VMEM((sub, D), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((vocab + 1, D), jnp.float32),
        input_output_aliases={4: 0},
        interpret=interpret,
        **autotune.pallas_cost(flops=3 * chunk * D,
                               bytes_accessed=12 * chunk * D),
    )

    def body(c, out):
        at = c * chunk
        return call(jax.lax.dynamic_slice_in_dim(sids, at, chunk),
                    jax.lax.dynamic_slice_in_dim(srcs, at, chunk),
                    jax.lax.dynamic_slice_in_dim(ws, at, chunk), o, out)

    calls = (n + pad) // chunk
    if live is not None:
        calls = jnp.minimum((live + chunk - 1) // chunk, calls)
    return jax.lax.fori_loop(0, calls, body,
                             jnp.zeros((vocab + 1, D), jnp.float32))
