"""jit'd wrappers for the jagged lookup kernel (paper §4.1.2).

``jagged_lookup`` is a differentiable embedding gather over *packed valid
indices*: forward is the scalar-prefetch Pallas gather; backward sorts the
(id, grad-row) pairs — the table-major regrouping — feeds them through the
run-sum kernel, and scatter-adds the per-run totals (unique destinations).

``multi_table_lookup`` concatenates per-table id streams table-major into
one fused kernel launch over a stacked table — the §4.1.2 'group all data
per table across the batch' strategy, which makes consecutive grid steps
hit the same table region.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.core.sharding import current_ctx
from repro.kernels import autotune
from repro.kernels.jagged_lookup import kernel as K


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


_DROP_KEY = jnp.int32(2 ** 30)


def _segment_totals(srows: jax.Array, sids: jax.Array) -> jax.Array:
    """Per-run totals of sorted rows, broadcast to every slot of the run.

    XLA twin of the run-sum kernel for non-TPU backends: emulating the
    Pallas kernel in interpret mode walks the grid step-by-step in the
    interpreter (O(n) dispatches — ~12 s for 16k rows on CPU), while a
    segment-sum is one scatter-add. Consumers only read run-*end* slots,
    where both produce the in-order accumulation of the run.
    """
    is_start = jnp.concatenate([jnp.ones((1,), bool), sids[1:] != sids[:-1]])
    run = jnp.cumsum(is_start.astype(jnp.int32)) - 1
    totals = jax.ops.segment_sum(srows, run, num_segments=srows.shape[0])
    return totals[run]


def _replicated(kernel):
    """A Pallas kernel cannot be partitioned by the compiler: under a
    multi-device mesh context (``core.sharding.shard_ctx``) it runs whole
    on every device over replicated operands (a run of equal sorted ids
    must not be cut at a shard boundary)."""
    ctx = current_ctx()
    if ctx is None or ctx.mesh.size == 1:
        return kernel
    return jax.shard_map(kernel, mesh=ctx.mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)


def dedup_rows(grad_rows: jax.Array, ids: jax.Array, *,
               interpret: Optional[bool] = None):
    """Sorted-runsum deduplication of (id, row) pairs.

    Sorts the pairs table-major (the §4.1.2 regrouping), run-sums rows of
    equal id — the Pallas run-sum kernel on TPU, the segment-sum twin
    elsewhere — and returns ``(uids, sums)`` of the input length where
    ``uids[i]`` is the id at each run *end* (−1 elsewhere and for dropped
    ids) and ``sums[i]`` the run total. ids < 0 are dropped. Consumers
    index only the ``uids >= 0`` slots — this is the unique-(id, grad-row)
    stream the sparse optimizer and the dense scatter share.
    """
    interpret = default_interpret() if interpret is None else interpret
    valid = ids >= 0
    skey = jnp.where(valid, ids, _DROP_KEY)
    order = jnp.argsort(skey)
    sids = skey[order]
    srows = grad_rows[order] * valid[order][:, None].astype(grad_rows.dtype)
    if interpret:
        sums = _segment_totals(srows, sids)
    else:
        sums = _replicated(K.runsum_pallas)(srows, sids)
    is_end = jnp.concatenate([sids[:-1] != sids[1:],
                              jnp.ones((1,), bool)])
    uids = jnp.where(is_end & (sids < _DROP_KEY), sids, -1)
    return uids, sums


def scatter_add_rows(grad_rows: jax.Array, ids: jax.Array, vocab: int, *,
                     interpret: Optional[bool] = None) -> jax.Array:
    """Σ grad_rows per id → dense (V, D). ids < 0 are dropped."""
    n, D = grad_rows.shape
    uids, sums = dedup_rows(grad_rows, ids, interpret=interpret)
    keep = (uids >= 0) & (uids < vocab)
    dest = jnp.where(keep, uids, vocab)
    out = jnp.zeros((vocab, D), jnp.float32)
    out = out.at[dest].add(jnp.where(keep[:, None], sums, 0.0),
                           mode="drop")
    return out


def scatter_add_weighted_rows(weights: jax.Array, o: jax.Array,
                              ids: jax.Array, vocab: int, *,
                              scale: float = 1.0,
                              impl: Optional[str] = None,
                              chunk: int = 128, sparse: bool = False,
                              interpret: Optional[bool] = None) -> jax.Array:
    """Σ over (t, r) of ``weights[t, r] · o[t] · scale`` per id → (V, D).

    The factored form of a sparse embedding gradient: ``weights`` (T, R)
    per-(token, slot) scalars, ``o`` (T, D) source rows, ``ids`` (T·R,)
    destinations flattened t-major; ids outside [0, vocab) are dropped.

    ``impl="fused"`` (default) generates each grad row *inside* the
    sorted-runsum scatter — the (T·R, D) row buffer never materializes in
    HBM (kernel on TPU; a token-chunked scan twin elsewhere whose live
    temporary is (chunk·R, D)). ``impl="two_pass"`` is the oracle: build
    all rows, then :func:`scatter_add_rows`.

    ``sparse``: many slots are dropped (a chip's share of a group's
    slots), so the kernel walks only the sorted slots that land, in as
    many of its calls as they fill.
    """
    interpret_ = default_interpret() if interpret is None else interpret
    T, R = weights.shape
    D = o.shape[1]
    if impl is None:
        impl = autotune.resolve("neg_fused", {"segment": T, "R": R, "D": D},
                                "scatter_impl", default="fused")
    if impl == "two_pass":
        rows = (weights.astype(jnp.float32)[:, :, None]
                * (o.astype(jnp.float32) * scale)[:, None, :]
                ).reshape(T * R, D)
        return scatter_add_rows(rows, ids, vocab, interpret=interpret_)
    if impl != "fused":
        raise ValueError(f"unknown scatter impl {impl!r}")
    valid = (ids >= 0) & (ids < vocab)
    if interpret_:
        # XLA twin: chunk the token axis so the live row buffer is
        # (chunk·R, D), never (T·R, D) — same reduction, scan-ordered.
        o32 = o.astype(jnp.float32) * scale
        w32 = weights.astype(jnp.float32)
        pad = (-T) % chunk
        if pad:
            o32 = jnp.concatenate([o32, jnp.zeros((pad, D), jnp.float32)])
            w32 = jnp.concatenate([w32, jnp.zeros((pad, R), jnp.float32)])
        idp = jnp.concatenate(
            [jnp.where(valid, ids, vocab).astype(jnp.int32),
             jnp.full((pad * R,), vocab, jnp.int32)])
        nc = (T + pad) // chunk

        def body(acc, args):
            wb, ob, idb = args
            rows = (wb[:, :, None] * ob[:, None, :]).reshape(chunk * R, D)
            return acc.at[idb].add(rows, mode="drop"), None

        acc, _ = jax.lax.scan(
            body, jnp.zeros((vocab, D), jnp.float32),
            (w32.reshape(nc, chunk, R), o32.reshape(nc, chunk, D),
             idp.reshape(nc, chunk * R)))
        return acc
    # TPU: sort (id, slot) pairs table-major and generate rows in-kernel
    skey = jnp.where(valid, ids, _DROP_KEY).astype(jnp.int32)
    order = jnp.argsort(skey)
    sids = skey[order]
    src = (order // R).astype(jnp.int32)
    ws = (weights.reshape(-1)[order].astype(jnp.float32)
          * valid[order].astype(jnp.float32))
    out = K.weighted_runsum_scatter(
        o.astype(jnp.float32), ws, sids, src, vocab, scale=scale,
        live=jnp.sum(valid, dtype=jnp.int32) if sparse else None,
        interpret=False)
    return out[:vocab]


def jagged_lookup(table: jax.Array, ids: jax.Array, *,
                  compute_dtype=jnp.bfloat16,
                  rows_per_step: Optional[int] = None,
                  interpret: Optional[bool] = None) -> jax.Array:
    """Differentiable packed-index gather. ids (n,) int32, ids < 0 → zeros."""
    interpret_ = default_interpret() if interpret is None else interpret
    V, D = table.shape
    if rows_per_step is None:
        rows_per_step = autotune.resolve(
            "lookup_gather",
            {"n": ids.shape[0], "D": D, "itemsize": table.dtype.itemsize},
            "rows_per_step", default=1)

    @jax.custom_vjp
    def _lookup(table):
        valid = ids >= 0
        safe = jnp.clip(ids, 0, V - 1)
        rows = K.gather_pallas(table, safe, rows_per_step=rows_per_step,
                               interpret=interpret_)
        return (rows * valid[:, None].astype(table.dtype)).astype(compute_dtype)

    def fwd(table):
        return _lookup(table), None

    def bwd(_, g):
        return (scatter_add_rows(g.astype(jnp.float32), ids, V,
                                 interpret=interpret_).astype(table.dtype),)

    _lookup.defvjp(fwd, bwd)
    return _lookup(table)


def multi_table_lookup(tables: Sequence[jax.Array],
                       ids_per_table: Sequence[jax.Array], *,
                       compute_dtype=jnp.bfloat16,
                       interpret: Optional[bool] = None
                       ) -> Tuple[jax.Array, ...]:
    """Fused table-major lookup: one kernel launch over stacked tables.

    All tables must share D. ids are offset into the stacked row space and
    concatenated table-major (all of table 0's ids, then table 1's, ...),
    matching Fig. 3's batch restructuring.
    """
    D = tables[0].shape[1]
    assert all(t.shape[1] == D for t in tables)
    offs = [0]
    for t in tables:
        offs.append(offs[-1] + t.shape[0])
    stacked = jnp.concatenate(tables, axis=0)
    shifted = [jnp.where(i >= 0, i + off, -1)
               for i, off in zip(ids_per_table, offs[:-1])]
    flat = jnp.concatenate(shifted)
    out = jagged_lookup(stacked, flat, compute_dtype=compute_dtype,
                        interpret=interpret)
    splits = jnp.cumsum(jnp.asarray([i.shape[0] for i in ids_per_table]))[:-1]
    return tuple(jnp.split(out, splits))
