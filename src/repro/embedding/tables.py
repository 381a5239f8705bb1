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

        shadow == master.astype(shadow.dtype)   (rows V, dims D)

    is maintained by :func:`repro.training.optim.adagrad_sparse_update`,
    which rewrites only the rows a step actually touched. ``shadow=None``
    disables the shadow (the fused path falls back to the fp32-round
    emulation); checkpoints store a 0-row shadow placeholder (dtype kept,
    bytes dropped) and restore rebuilds it from the master — see
    :func:`strip_shadow` / :func:`rebuild_shadow`.
    """
    master: jax.Array               # (V, D) fp32
    shadow: Optional[jax.Array]     # (V, D) bf16 (or fp16), or None
    accum: jax.Array                # (V, D) fp32 AdaGrad S (paper Eq. 1)


def make_shadowed(master: jax.Array, qdtype=SHADOW_DTYPE,
                  accum: Optional[jax.Array] = None) -> ShadowedTable:
    """Build a ShadowedTable from an fp32 master. ``qdtype=None`` → no
    shadow (fp32-round emulation path)."""
    shadow = None if qdtype is None else master.astype(qdtype)
    if accum is None:
        accum = jnp.zeros_like(master, jnp.float32)
    return ShadowedTable(master=master, shadow=shadow, accum=accum)


def strip_shadow(t: ShadowedTable) -> ShadowedTable:
    """Replace the shadow with a 0-row placeholder of the same dtype, so a
    checkpoint stores the master once (the shadow is derivable). The pytree
    structure (leaf count) is unchanged."""
    if t.shadow is None:
        return t
    return t._replace(shadow=jnp.zeros((0, t.shadow.shape[-1])
                                       if t.shadow.ndim == 2 else (0,),
                                       t.shadow.dtype))


def rebuild_shadow(t: ShadowedTable) -> ShadowedTable:
    """Recompute ``shadow = master.astype(qdtype)`` (restore path, or after
    any out-of-band master edit)."""
    if t.shadow is None:
        return t
    return t._replace(shadow=t.master.astype(t.shadow.dtype))


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
    return jnp.all(t.master.astype(t.shadow.dtype) == t.shadow)


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
