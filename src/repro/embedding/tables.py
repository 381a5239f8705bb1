"""Sparse embedding tables — the GR system's sparse substrate.

The master table is fp32 (AdaGrad-friendly); lookups return the compute
dtype. ``lookup_quantized`` is the paper's §4.3.2 half-precision path:
rows are *stored/fetched* in half precision for negative samples while
the rest of the pipeline is unchanged. The half type is
:data:`SHADOW_DTYPE` (bfloat16) on every backend: the paper uses fp16, but
bf16 is the TPU's native half type and a v5e kernel cannot load an fp16
tile at all.

Multi-table (KJT-style) batches: a dict of feature name → jagged ids; the
table-major reorganization of §4.1.2 (group all data per table, then spread
each table across cores) corresponds here to looking tables up one at a
time over their packed valid indices only — no padded zeros enter the
gather. The TPU hot-path kernel is ``repro.kernels.jagged_lookup``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.jagged import JaggedBatch

# the one half-width type of the shadow table and of the negative fetch
SHADOW_DTYPE = jnp.bfloat16


@dataclass(frozen=True)
class TableSpec:
    name: str
    vocab: int
    dim: int
    init_scale: float = 0.02


def init_table(key, spec: TableSpec, dtype=jnp.float32) -> jax.Array:
    return (jax.random.normal(key, (spec.vocab, spec.dim), jnp.float32)
            * spec.init_scale).astype(dtype)


def lookup(table: jax.Array, ids: jax.Array,
           dtype=jnp.bfloat16) -> jax.Array:
    """Plain (dense-grad) lookup; GSPMD turns this into the vocab-parallel
    masked-gather+psum when `table` is sharded on dim 0."""
    return jnp.take(table, ids, axis=0).astype(dtype)


def lookup_quantized(table: jax.Array, ids: jax.Array,
                     qdtype=SHADOW_DTYPE) -> jax.Array:
    """§4.3.2: fetch rows in half precision (the paper's fp16 becomes the
    TPU-native bf16 by default). Quantization happens at the *fetch* — only
    the gathered rows are cast (casting ``table`` first would copy the
    whole (V, D) array per call), so the live negative tensor is half the
    bytes. The fused TPU hot path (``repro.kernels.neg_logits``) applies
    the same rounding in VMEM and never materializes the rows at all."""
    return jnp.take(table, ids, axis=0).astype(qdtype)


# --------------------------------------------------------------------------
# §4.3.2 persistent half-precision shadow table
# --------------------------------------------------------------------------

class ShadowedTable(NamedTuple):
    """fp32 master + persistent half-precision shadow + AdaGrad accumulator.

    The shadow realizes the §4.3.2 bandwidth win end to end: the fused
    negative-sampling kernel gathers half-width rows from ``shadow``
    (HBM→VMEM DMA at half the bytes, dequant in VMEM) instead of fetching
    fp32 master rows and rounding them in VMEM. The invariant

        shadow == shadow_of(master, qdtype)

    is maintained by :func:`repro.training.optim.adagrad_sparse_update`,
    which lands the touched rows from the landed master (or makes the
    whole shadow anew where a step touches a large share of it). The
    shadow is stored in the layout the kernel gathers from
    (:func:`shadow_of`): a bf16 table of a width that :func:`packs` is held
    as ``(V, D/256, 128)`` uint32 words, two elements each, so one row is
    one contiguous DMA and no step re-lays the table out; any other is
    ``master.astype(qdtype)``, which the kernel cannot copy a row of, so it
    reads the master's rows and rounds them in VMEM (the same values).
    :func:`shadow_values` reads either back as (…, D) half-precision rows.
    ``shadow=None`` disables the shadow (the fused path falls back to the
    fp32-round emulation); checkpoints store a 0-row shadow placeholder
    (dtype kept, bytes dropped) and restore rebuilds it from the master —
    see :func:`strip_shadow` / :func:`rebuild_shadow`.
    """
    master: jax.Array               # (V, D) fp32
    shadow: Optional[jax.Array]     # see shadow_of, or None
    accum: jax.Array                # (V, D) fp32 AdaGrad S (paper Eq. 1)


# The stored layout. A bf16 shadow whose row halves are lane-aligned (D a
# multiple of 256) is held packed: (V, D/256, 128) uint32 words, word j of a
# row holding element j in its low half and element j + D/2 in its high
# half. XLA lays a (k, 128) minor block out row-major, so each row is one
# contiguous run of D/2 words: the fused negative kernel copies one row with
# one DMA (a 16-bit row shares its HBM words with the next row, and the chip
# cannot copy a slice narrower than its 128-lane tile, so unaligned halves
# are not packed). Every other shadow is ``master.astype(qdtype)``. Code
# outside this section reads the layout only through these functions.

PACKED_DTYPE = jnp.uint32


def packs(qdtype, D: int) -> bool:
    """Whether a shadow of ``qdtype`` rows of width ``D`` is stored packed."""
    return jnp.dtype(qdtype) == jnp.bfloat16 and D % 256 == 0


def is_packed(x: jax.Array) -> bool:
    """Whether ``x`` (a stored shadow, a gather of its rows, or a VMEM copy
    of them) holds packed words."""
    return x.dtype == PACKED_DTYPE


def pack_rows(x: jax.Array) -> jax.Array:
    """bf16 rows (..., D) → packed words (..., D/256, 128)."""
    W = x.shape[-1] // 2
    bits = lambda h: jax.lax.bitcast_convert_type(h, jnp.uint16).astype(
        PACKED_DTYPE)
    words = bits(x[..., :W]) | (bits(x[..., W:]) << 16)
    return words.reshape(*x.shape[:-1], W // 128, 128)


def unpack_halves(words: jax.Array):
    """Packed words (..., W), however viewed → the rows' two halves as fp32
    (..., W) each, first half first: a bf16 value is the top half of its
    fp32 widening, so each half is exact. Pure elementwise ops, so a
    kernel can unpack rows it holds in VMEM."""
    lo = jax.lax.bitcast_convert_type(words << 16, jnp.float32)
    hi = jax.lax.bitcast_convert_type(words & PACKED_DTYPE(0xFFFF0000),
                                      jnp.float32)
    return lo, hi


def unpack_rows(words: jax.Array) -> jax.Array:
    """Inverse of :func:`pack_rows`: (..., k, 128) words → (..., 256k)
    bf16."""
    w = words.reshape(*words.shape[:-2], -1)
    return jnp.concatenate(unpack_halves(w), axis=-1).astype(jnp.bfloat16)


def packed_row_words(shadow: jax.Array) -> jax.Array:
    """A packed shadow (V, D/256, 128) viewed as (V, 1, D/2) words, one row
    a contiguous run (free: the same bytes)."""
    return shadow.reshape(shadow.shape[0], 1, -1)


def shadow_of(rows: jax.Array, qdtype) -> jax.Array:
    """The stored shadow of master ``rows`` (..., D): packed words where
    :func:`packs`, else the rows cast to ``qdtype``."""
    half = rows.astype(qdtype)
    return pack_rows(half) if packs(qdtype, rows.shape[-1]) else half


def shadow_dtype(shadow: jax.Array):
    """The half-precision type a stored shadow holds."""
    return jnp.dtype(jnp.bfloat16) if is_packed(shadow) else shadow.dtype


def shadow_values(shadow: jax.Array) -> jax.Array:
    """Stored shadow rows (the whole shadow or a gather of it) as (..., D)
    half-precision values."""
    return unpack_rows(shadow) if is_packed(shadow) else shadow


def stored_row_bytes(table: jax.Array) -> int:
    """HBM bytes one stored row of ``table`` (a master, or a shadow in
    either layout) occupies."""
    return math.prod(table.shape[1:]) * jnp.dtype(table.dtype).itemsize


def make_shadowed(master: jax.Array, qdtype=SHADOW_DTYPE,
                  accum: Optional[jax.Array] = None) -> ShadowedTable:
    """Build a ShadowedTable from an fp32 master. ``qdtype=None`` → no
    shadow (fp32-round emulation path)."""
    shadow = None if qdtype is None else shadow_of(master, qdtype)
    if accum is None:
        accum = jnp.zeros_like(master, jnp.float32)
    return ShadowedTable(master=master, shadow=shadow, accum=accum)


def strip_shadow(t: ShadowedTable) -> ShadowedTable:
    """Replace the shadow with a 0-row placeholder of the same dtype and
    row shape, so a checkpoint stores the master once (the shadow is
    derivable). The pytree structure (leaf count) is unchanged."""
    if t.shadow is None:
        return t
    return t._replace(shadow=jnp.zeros((0, *t.shadow.shape[1:]),
                                       t.shadow.dtype))


def rebuild_shadow(t: ShadowedTable) -> ShadowedTable:
    """Recompute the shadow from the master (restore path, or after any
    out-of-band master edit)."""
    if t.shadow is None:
        return t
    return t._replace(shadow=shadow_of(t.master, shadow_dtype(t.shadow)))


def live_shadow(t: ShadowedTable) -> Optional[jax.Array]:
    """The shadow iff it is usable as a gather/scan source: present and
    full-size (a checkpoint-stripped 0-row placeholder is not). Callers
    that can run on either table (the fused negative gather, the serving
    retrieval scan) use this instead of re-deriving the check."""
    if t.shadow is not None and t.shadow.shape[0] == t.master.shape[0]:
        return t.shadow
    return None


def shadow_consistent(t: ShadowedTable) -> jax.Array:
    """True iff the shadow invariant holds exactly (debug/test helper)."""
    if t.shadow is None:
        return jnp.bool_(True)
    return jnp.all(shadow_of(t.master, shadow_dtype(t.shadow)) == t.shadow)


def multi_table_lookup(tables: Dict[str, jax.Array],
                       feats: Dict[str, JaggedBatch],
                       dtype=jnp.bfloat16) -> Dict[str, JaggedBatch]:
    """KJT-style lookup: per-table packed gather over valid indices only.

    Invalid (padding) slots contribute a zero row — matching the paper's
    'operate only on valid indices' semantics (§4.1.2 step 1).
    """
    out: Dict[str, JaggedBatch] = {}
    for name, jb in feats.items():
        t = tables[name]
        emb = jnp.take(t, jb.values, axis=0).astype(dtype)
        emb = emb * jb.valid_mask()[:, None].astype(dtype)
        out[name] = JaggedBatch(values=emb, offsets=jb.offsets)
    return out
