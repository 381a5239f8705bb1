"""Negative-sampling optimization (paper §4.3).

Recall training pairs every valid position with R sampled negatives. The
naive path materializes the (T, R, D) negative-embedding tensor (~34 GB at
the paper's example sizes) — §4.3 removes it three ways:

  * :func:`neg_logits_segmented` — §4.3.1: the logit at position t depends
    only on that position's slice, so we scan over fixed-size segments and
    never materialize (T, R, D). On TPU, Pallas double-buffers the HBM→VMEM
    segment fetches (``repro.kernels.neg_logits``); the ``jax.lax.scan``
    here is the XLA-path equivalent whose peak-memory drop shows directly
    in ``compiled.memory_analysis()``.
  * quantized lookups — §4.3.2: negatives fetched in half precision
    (bf16, ``tables.SHADOW_DTYPE``).
  * :func:`share_logits` — §4.3.3: intra-batch logit sharing with a
    token-level shuffle expands the effective negative set k× without any
    additional embedding lookups (Eq. 2's Δ term).

``sampled_softmax_loss`` is Eq. 2. :func:`fused_sampled_softmax_loss` is
the production entry point: it dispatches to the fused ID-driven Pallas
megakernel (``repro.kernels.neg_logits.fused_recall_lse``) on TPU — gather
+ dequant + logit sharing + logsumexp in one pass, no (T, R, D) embeddings
or (T, R·k) logits in HBM — and to :func:`fused_recall_lse_xla` (a
remat'd segmented scan with identical numerics) elsewhere.
"""
from __future__ import annotations

import logging
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hsp as H
from repro.embedding import tables as ET
from repro.kernels.neg_logits import fused_recall_lse
from repro.kernels.neg_logits.fused import NEG_POOL
from repro.kernels.neg_logits.ops import prepare_fused_inputs

logger = logging.getLogger(__name__)


# --------------------------------------------------------------------------
# negative id sampling (jaggedness-aware: §4.3.2 figure 11)
# --------------------------------------------------------------------------

def sample_negative_ids(key, *, num_tokens: int, num_negatives: int,
                        vocab_size: int) -> jax.Array:
    """Uniform negative ids (T, R). Jaggedness-awareness = the caller only
    passes *valid* token slots (packed layout); padded positions never get
    negatives sampled, unlike the dense (B, L, R) baseline."""
    return jax.random.randint(key, (num_tokens, num_negatives), 0,
                              vocab_size, dtype=jnp.int32)


# --------------------------------------------------------------------------
# logits
# --------------------------------------------------------------------------

def neg_logits_baseline(out_emb: jax.Array, neg_emb: jax.Array,
                        tau: float = 1.0) -> jax.Array:
    """Materialized path: out (T, D) × neg (T, R, D) → (T, R).

    The (T, R, D) input is the HBM hog the paper offloads; kept as the
    faithful baseline for Table 7."""
    return jnp.einsum("td,trd->tr", out_emb.astype(jnp.float32),
                      neg_emb.astype(jnp.float32)) / tau


def neg_logits_segmented(out_emb: jax.Array, table: jax.Array,
                         neg_ids: jax.Array, *, segment: int = 128,
                         tau: float = 1.0,
                         fetch_dtype=ET.SHADOW_DTYPE) -> jax.Array:
    """§4.3.1 'CPU offloading + segmented fetching', XLA form.

    The negatives live as *ids* (T, R); embeddings are fetched from
    ``table`` (which may be host-offloaded) one segment of valid positions
    at a time and reduced to logits immediately, so the live footprint is
    (segment, R, D) instead of (T, R, D). ``fetch_dtype`` applies the
    §4.3.2 quantization at the fetch.
    """
    T, R = neg_ids.shape
    D = out_emb.shape[-1]
    assert T % segment == 0, (T, segment)
    n_seg = T // segment

    def body(_, si):
        o = jax.lax.dynamic_slice_in_dim(out_emb, si * segment, segment, 0)
        idsb = jax.lax.dynamic_slice_in_dim(neg_ids, si * segment, segment, 0)
        # quantize the gathered rows only — casting `table` here would copy
        # the whole (V, D) array every call.
        nb = jnp.take(table, idsb.reshape(-1), axis=0).astype(fetch_dtype)
        nb = nb.reshape(segment, R, D)
        lg = jnp.einsum("td,trd->tr", o.astype(jnp.float32),
                        nb.astype(jnp.float32)) / tau
        return None, lg

    _, logits = jax.lax.scan(body, None, jnp.arange(n_seg, dtype=jnp.int32))
    return logits.reshape(T, R)


def offload_negatives(neg_emb: jax.Array) -> jax.Array:
    """Host-offload the negative tensor (TPU: pinned host memory; the
    double-buffered fetch is then driven by the segmented consumer).
    Falls back to a no-op where the platform has no pinned-host memory
    space — real sharding/transfer errors propagate instead of being
    swallowed."""
    if not hasattr(neg_emb, "devices"):
        return neg_emb                      # tracer/ShapeDtypeStruct
    devs = neg_emb.devices()
    if not devs:
        return neg_emb
    dev = next(iter(devs))
    try:
        dev.memory("pinned_host")           # capability probe only
    except (ValueError, KeyError, AttributeError,
            jax.errors.JaxRuntimeError) as e:
        logger.debug("offload_negatives: no pinned_host memory on %s (%s); "
                     "keeping negatives on-device", dev, e)
        return neg_emb
    import jax.sharding as jsh
    sharding = jsh.SingleDeviceSharding(dev, memory_kind="pinned_host")
    return jax.device_put(neg_emb, sharding)


# --------------------------------------------------------------------------
# §4.3.3 — intra-batch logit sharing (Eq. 2)
# --------------------------------------------------------------------------

def share_logits(key, neg_logits: jax.Array, expansion: int,
                 valid: Optional[jax.Array] = None) -> jax.Array:
    """Expand (T, R) → (T, R·k) by reusing other tokens' negative logits.

    For each token, (k−1)·R auxiliary logits are drawn from the flattened
    pool of all tokens' logits with a per-token shuffle (mitigates the
    fixed-concatenation redundancy the paper describes). No additional
    embedding lookups happen — the defining property of §4.3.3.
    """
    T, R = neg_logits.shape
    if expansion <= 1:
        return neg_logits
    n_aux = (expansion - 1) * R
    pool = neg_logits.reshape(T * R)
    if valid is not None:
        # invalid (padded) tokens' logits must not leak into the pool:
        # their slots are masked to a large-negative sentinel so a drawn
        # slot contributes exp(NEG_POOL) ≈ 0 to the consumer's softmax —
        # same convention as the fused kernel's in-VMEM pool mask.
        pool = jnp.where(jnp.repeat(valid, R), pool, NEG_POOL)
    # per-token shuffled draw from the pool, excluding the token's own rows
    keys = jax.random.split(key, T)

    def draw(k, t):
        idx = jax.random.randint(k, (n_aux,), 0, (T - 1) * R)
        # skip over this token's own block [t·R, (t+1)·R)
        idx = jnp.where(idx >= t * R, idx + R, idx)
        return pool[idx]

    aux = jax.vmap(draw)(keys, jnp.arange(T, dtype=jnp.int32))
    return jnp.concatenate([neg_logits, aux], axis=-1)


# --------------------------------------------------------------------------
# Eq. 2 — sampled-softmax contrastive loss
# --------------------------------------------------------------------------

def sampled_softmax_loss(pos_logit: jax.Array, neg_logits: jax.Array,
                         valid: Optional[jax.Array] = None) -> jax.Array:
    """Loss = −log( e^{l⁺} / (e^{l⁺} + Σ_j e^{l⁻_j} + Δ) )  (paper Eq. 2).

    pos_logit: (T,) fp32; neg_logits: (T, R′) fp32 (R′ includes any shared
    auxiliary logits = the Δ term); valid: (T,) bool mask of real tokens.
    """
    all_logits = jnp.concatenate([pos_logit[:, None], neg_logits], axis=-1)
    lse = jax.nn.logsumexp(all_logits.astype(jnp.float32), axis=-1)
    nll = lse - pos_logit.astype(jnp.float32)
    if valid is not None:
        v = valid.astype(jnp.float32)
        return jnp.sum(nll * v) / jnp.maximum(jnp.sum(v), 1.0)
    return jnp.mean(nll)


def recall_loss(out_emb: jax.Array, pos_emb: jax.Array,
                neg_logits: jax.Array, *, tau: float = 1.0,
                valid: Optional[jax.Array] = None) -> jax.Array:
    """Full recall objective: positive logit from the next-item embedding,
    negatives precomputed by one of the paths above."""
    pos = jnp.sum(out_emb.astype(jnp.float32) * pos_emb.astype(jnp.float32),
                  axis=-1) / tau
    return sampled_softmax_loss(pos, neg_logits, valid)


# --------------------------------------------------------------------------
# fused ID-driven recall path (tentpole): one pass from ids to Eq.-2 lse
# --------------------------------------------------------------------------

def shadow_gather(table: jax.Array, shadow: jax.Array,
                  ids: jax.Array) -> jax.Array:
    """Straight-through shadow fetch for the XLA fused twin.

    Forward reads ONLY the half-precision ``shadow`` rows (half the fetch
    bytes, visible in ``cost_analysis``); backward routes the cotangent to
    ``table`` (the fp32 master) as the plain gather-grad scatter — the
    same straight-through estimator the Pallas custom VJP implements
    (logits are linear in the rows, so d logit/d row = out/τ regardless of
    the rounding). ``ids`` travels through the VJP as an argument (float0
    cotangent): capturing it by closure would leak scan-body tracers into
    the backward pass.
    """
    V, D = table.shape
    tdtype = table.dtype

    @jax.custom_vjp
    def _fetch(tbl, ids_):
        return ET.shadow_values(jnp.take(shadow, ids_, axis=0))

    def fwd(tbl, ids_):
        return _fetch(tbl, ids_), ids_

    def bwd(ids_, g):
        dtbl = jnp.zeros((V, D), tdtype).at[ids_].add(
            g.astype(tdtype), mode="drop")
        return dtbl, np.zeros(ids_.shape, jax.dtypes.float0)

    _fetch.defvjp(fwd, bwd)
    return _fetch(table, ids)


def fused_recall_lse_xla(out_emb: jax.Array, pos_logit: jax.Array,
                         table: jax.Array, neg_ids: jax.Array, *,
                         segment: int = 128, tau: float = 1.0,
                         expansion: int = 1,
                         key: Optional[jax.Array] = None,
                         valid: Optional[jax.Array] = None,
                         fetch_dtype=None,
                         gather_table: Optional[jax.Array] = None,
                         owned: Optional[jax.Array] = None
                         ) -> jax.Array:
    """XLA twin of the fused megakernel (identical numerics, same
    per-segment shuffle): a remat'd segmented scan, so neither the forward
    nor the backward ever holds (T, R, D) gathered rows or (T, R·k)
    expanded logits — the backward re-gathers per segment exactly like the
    Pallas custom VJP. ``gather_table`` fetches rows from the persistent
    half-precision shadow (straight-through grad to ``table``), matching
    the Pallas path's shadow gather; ``owned`` masks the slots a chip
    does not hold, as the kernel's does."""
    T, R = neg_ids.shape
    D = table.shape[1]
    inv_tau = 1.0 / tau
    o_p, pos_p, ids_p, valid_p, perms, n_seg = prepare_fused_inputs(
        out_emb, pos_logit, table, neg_ids, segment=segment,
        expansion=expansion, key=key, valid=valid)
    own_p = (jnp.ones(ids_p.shape, bool) if owned is None else
             jnp.concatenate([owned, jnp.zeros((ids_p.shape[0] - T, R),
                                               bool)]))

    @partial(jax.checkpoint,
             policy=jax.checkpoint_policies.nothing_saveable)
    def body(_, si):
        o = jax.lax.dynamic_slice_in_dim(o_p, si * segment, segment, 0)
        idsb = jax.lax.dynamic_slice_in_dim(ids_p, si * segment, segment, 0)
        posb = jax.lax.dynamic_slice_in_dim(pos_p, si * segment, segment, 0)
        vb = jax.lax.dynamic_slice_in_dim(valid_p, si * segment, segment, 0)
        ob = jax.lax.dynamic_slice_in_dim(own_p, si * segment, segment, 0)
        if gather_table is not None:
            rows = shadow_gather(table, gather_table, idsb.reshape(-1))
        else:
            rows = jnp.take(table, idsb.reshape(-1), axis=0)
            if fetch_dtype is not None:
                rows = rows.astype(fetch_dtype)
        logits = jnp.einsum("td,trd->tr", o.astype(jnp.float32),
                            rows.reshape(segment, R, D).astype(jnp.float32)
                            ) * inv_tau
        if owned is not None:
            logits = jnp.where(ob, logits, NEG_POOL)
        cols = [posb[:, None], logits]
        if expansion > 1:
            masked = jnp.where(vb[:, None] > 0.0, logits, NEG_POOL)
            pseg = jax.lax.dynamic_index_in_dim(perms, si, 0,
                                                keepdims=False)
            for e in range(expansion - 1):
                cols.append(jnp.take(masked, pseg[e], axis=0))
        alls = jnp.concatenate(cols, axis=1)
        m = jnp.max(alls, axis=1, keepdims=True)
        lse = m[:, 0] + jnp.log(jnp.sum(jnp.exp(alls - m), axis=1))
        return None, lse

    _, lses = jax.lax.scan(body, None, jnp.arange(n_seg, dtype=jnp.int32))
    return lses.reshape(-1)[:T]


def fused_sampled_softmax_loss(out_emb: jax.Array, pos_emb: jax.Array,
                               table: jax.Array, neg_ids: jax.Array, *,
                               key: Optional[jax.Array] = None,
                               tau: float = 1.0,
                               valid: Optional[jax.Array] = None,
                               segment: int = 128, expansion: int = 1,
                               fetch_dtype=ET.SHADOW_DTYPE,
                               shadow: Optional[jax.Array] = None,
                               impl: Optional[str] = None,
                               scatter_impl: Optional[str] = None,
                               interpret: Optional[bool] = None,
                               hsp=None) -> jax.Array:
    """Eq. 2 straight from ids: the production recall loss.

    ``impl``: "pallas" (fused megakernel; default on TPU), "xla" (remat'd
    segmented scan; default elsewhere), or None for backend dispatch. Both
    implementations share numerics and the deterministic per-segment
    sharing shuffle, so they are interchangeable mid-training.

    ``shadow``: persistent half-precision table (§4.3.2 end to end) — the
    negative rows are fetched from it at half the bytes; gradients flow to
    ``table``. When None, ``fetch_dtype`` rounds fp32 master rows at the
    fetch instead (same numerics under the shadow invariant, full
    bandwidth).

    ``scatter_impl`` tunes the Pallas megakernel's backward-scatter
    schedule (kernels/autotune.py resolves the tuned.json default when
    None; ignored by the XLA impl).

    ``hsp``: the loss runs on one chip of the mesh of this
    ``repro.core.hsp.HSPLookup``, inside a ``shard_map``: ``table`` and
    ``shadow`` are the chip's rows, the rest its shard of the batch (see
    :func:`_owner_lse`). It returns the chip's part of the mean over every
    chip's valid tokens.
    """
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    pos = jnp.sum(out_emb.astype(jnp.float32) * pos_emb.astype(jnp.float32),
                  axis=-1) / tau
    kw = dict(segment=segment, tau=tau, expansion=expansion, key=key,
              fetch_dtype=fetch_dtype, gather_table=shadow)

    def lse_of(o, pos, ids, valid, owned=None):
        if impl == "pallas":
            return fused_recall_lse(o, pos, table, ids, valid=valid,
                                    scatter_impl=scatter_impl, owned=owned,
                                    interpret=interpret, **kw)
        if impl == "xla":
            return fused_recall_lse_xla(o, pos, table, ids, valid=valid,
                                        owned=owned, **kw)
        raise ValueError(f"unknown fused impl {impl!r}")

    if hsp is not None:
        lse = _owner_lse(hsp, lse_of, out_emb, pos, table.shape[0], neg_ids,
                         valid)
    else:
        lse = lse_of(out_emb, pos, neg_ids, valid)
    nll = lse - pos
    if hsp is not None:
        # the mean over every chip's tokens
        v = (jnp.ones(nll.shape, jnp.float32) if valid is None
             else valid.astype(jnp.float32))
        return jnp.sum(nll * v) / jnp.maximum(
            H.psum(jnp.sum(v), hsp.batch_axes, "neg"), 1.0)
    if valid is not None:
        v = valid.astype(jnp.float32)
        return jnp.sum(nll * v) / jnp.maximum(jnp.sum(v), 1.0)
    return jnp.mean(nll)


def _owner_lse(hsp, lse_of, out_emb, pos, rows, neg_ids, valid):
    """Each token's Eq.-2 logsumexp on one chip of an HSP group, computed
    by the owners of its negatives' rows.

    The chip gathers its group's token vectors, ids and validity (the
    vectors in fp32, so that the backward's reduce-scatter sums fp32
    gradients), runs the fused kernel over every group token's slots with
    the slots of rows it holds marked owned (ids made local to its rows),
    and gathers the group's partial logsumexps, each an exact logsumexp
    over the slots one chip holds. A token's logsumexp is that of its
    positive logit and its group's partials. The backward returns each
    token vector's gradient to its chip with a reduce-scatter (the
    transpose of the gather), and the kernel's scatter leaves each row's
    gradient on its owner."""
    axes = hsp.group_axes
    T = out_emb.shape[0]
    o_g = H.all_gather(out_emb.astype(jnp.float32), axes, "neg",
                       transposed=True)
    ids_g = H.all_gather(neg_ids, axes, "neg")
    valid_g = (None if valid is None else
               H.all_gather(valid.astype(jnp.float32), axes, "neg"))
    rel = ids_g - hsp.row_offset(rows)
    owned = (rel >= 0) & (rel < rows)
    none = jnp.full(o_g.shape[:1], NEG_POOL, jnp.float32)   # no positive
    part = lse_of(o_g, none, jnp.where(owned, rel, 0), valid_g, owned)
    parts = H.all_gather(part, axes, "neg", tiled=False, transposed=True)
    mine = jax.lax.dynamic_slice_in_dim(parts, hsp.row_offset(T), T, axis=1)
    return jax.nn.logsumexp(jnp.concatenate([pos[None], mine]), axis=0)
