"""Sharded quantized top-k retrieval over the item embedding table.

Serving never needs the (B, V) logit matrix or an fp32 copy of the table:
scoring streams the table through VMEM-sized vocab shards, keeps a running
(B, k) partial top-k, and merges per shard —

    for each vocab shard s:                         (block_v, D) rows
        scores_s = emb @ dequant(shard_s).T         (B, block_v) fp32
        carry    = top_k(concat(carry, top_k(scores_s)))

so peak live memory is O(B·block_v + B·k) and the table is read **once**
per micro-batch. Pointing the scan at the §4.3.2 FP16 shadow
(``ShadowedTable.shadow``) halves the bytes the scan reads — the serving
twin of the training-time negative-fetch win (rows dequantize after the
gather, exactly like ``lookup_quantized``). The dense fp32 full-scoring
path (:func:`topk_dense`) is kept as the parity oracle.

Shards are vocab blocks of one table here; on a multi-device serving mesh
the same loop runs per vocab partition with the (B, k) merge as the only
cross-device exchange (k ≪ block_v — the merge is the cheap part).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.embedding.tables import (ShadowedTable, live_shadow, shadow_values,
                                   stored_row_bytes)


def topk_dense(emb: jax.Array, table: jax.Array, k: int
               ) -> Tuple[jax.Array, jax.Array]:
    """Parity oracle: full (B, V) fp32 scoring + one global top-k."""
    scores = emb.astype(jnp.float32) @ table.astype(jnp.float32).T
    return jax.lax.top_k(scores, k)


def topk_blocked(emb: jax.Array, table: jax.Array, *, k: int,
                 block_v: int = 4096) -> Tuple[jax.Array, jax.Array]:
    """Blocked-scan top-k: per-shard partial top-k → running merge.

    emb (B, d) any float dtype; table (V, D) fp32 master or a stored
    fp16/bf16 shadow (rows are unpacked and cast to fp32 *after* the shard
    gather, so a half-precision table is fetched at half the bytes and
    never copied to fp32 wholesale). Returns fp32 (B, k) scores + int32
    (B, k) item ids, score-descending. The last shard is handled by re-sliding the window
    to V − block_v and masking re-scored ids, so no padded table copy is
    ever materialized.
    """
    B, d = emb.shape
    V = table.shape[0]
    if k > V:
        raise ValueError(f"k={k} exceeds vocab {V}")
    block_v = min(block_v, V)
    kb = min(k, block_v)
    nblk = -(-V // block_v)
    ef = emb.astype(jnp.float32)

    def body(i, carry):
        vals, idx = carry
        start = jnp.minimum(i * block_v, V - block_v)
        blk = shadow_values(jax.lax.dynamic_slice_in_dim(table, start,
                                                         block_v))
        s = ef @ blk.astype(jnp.float32).T                 # (B, block_v)
        gidx = start + jnp.arange(block_v, dtype=jnp.int32)
        # the re-slid last window overlaps the previous shard; score each
        # id exactly once by masking ids below this shard's nominal start
        s = jnp.where(gidx[None, :] >= i * block_v, s, -jnp.inf)
        bv, bi = jax.lax.top_k(s, kb)
        cand_v = jnp.concatenate([vals, bv], axis=1)
        cand_i = jnp.concatenate([idx, jnp.take(gidx, bi)], axis=1)
        mv, sel = jax.lax.top_k(cand_v, k)
        return mv, jnp.take_along_axis(cand_i, sel, axis=1)

    init = (jnp.full((B, k), -jnp.inf, jnp.float32),
            jnp.full((B, k), -1, jnp.int32))
    vals, idx = jax.lax.fori_loop(0, nblk, body, init)
    return vals, idx


def topk_from_slots(emb_buffer: jax.Array, rows: jax.Array,
                    table: jax.Array, *, k: int, block_v: int = 4096
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Rank straight from the slot-resident embedding buffer: gather the
    requested slot rows on device and run the blocked scan — user
    embeddings never stage through the host (the continuous engine's
    retrieval entry). Pad lanes index the scratch row; callers slice them
    off. Returns (scores, item ids, gathered query rows) — the query rows
    ride along so the engine's single device→host copy also covers the
    ``user_emb`` field of the results."""
    q = jnp.take(emb_buffer, rows, axis=0)
    vals, idx = topk_blocked(q, table, k=k, block_v=block_v)
    return vals, idx, q


# --------------------------------------------------------------------------
# byte accounting (what bench_serving reports)
# --------------------------------------------------------------------------

def table_scan_bytes(table: jax.Array,
                     block_v: Optional[int] = None) -> int:
    """HBM bytes one retrieval pass reads from ``table``. With
    ``block_v`` set, counts what :func:`topk_blocked` actually fetches:
    ceil(V/block_v) windows of block_v rows — the re-slid last window
    re-reads up to block_v − (V mod block_v) rows when block_v does not
    divide V. Without ``block_v`` (dense full scoring), exactly V rows."""
    V = int(table.shape[0])
    rows = V
    if block_v is not None:
        bv = min(block_v, V)
        rows = -(-V // bv) * bv
    return rows * stored_row_bytes(table)


def bytes_per_query(table: jax.Array, batch: int,
                    block_v: Optional[int] = None) -> float:
    """Table bytes per ranked request at micro-batch size ``batch``."""
    return table_scan_bytes(table, block_v) / max(int(batch), 1)


class ShardedTopK:
    """Configured retrieval entry: picks the scan table (shadow when
    available, unless ``use_shadow=False``) and jits the blocked scan.

    The jit is keyed on (B, table identity) shapes only; ``k`` and
    ``block_v`` are frozen at construction.
    """

    def __init__(self, k: int, *, block_v: int = 4096,
                 use_shadow: bool = True):
        self.k = k
        self.block_v = block_v
        self.use_shadow = use_shadow
        self._blocked = jax.jit(
            lambda e, t: topk_blocked(e, t, k=k, block_v=block_v))
        self._dense = jax.jit(lambda e, t: topk_dense(e, t, k))

    def scan_table(self, table: ShadowedTable) -> jax.Array:
        shadow = live_shadow(table) if self.use_shadow else None
        return table.master if shadow is None else shadow

    def __call__(self, table: ShadowedTable, emb: jax.Array
                 ) -> Tuple[jax.Array, jax.Array]:
        return self._blocked(emb, self.scan_table(table))

    def oracle(self, table: ShadowedTable, emb: jax.Array
               ) -> Tuple[jax.Array, jax.Array]:
        """fp32 full-scoring parity reference (dense matmul + top-k)."""
        return self._dense(emb, table.master)

    def bytes_per_query(self, table: ShadowedTable, batch: int) -> float:
        return bytes_per_query(self.scan_table(table), batch, self.block_v)
