"""jit'd wrappers for the negative-logits kernels.

* :func:`neg_logits` — the original segmented kernel over a materialized
  (T, R, D) tensor. Kept as the faithful §4.3.1 baseline for Table 7.
* :func:`fused_recall_lse` — the fused ID-driven megakernel: consumes
  (out_emb, neg_ids, table) directly and returns the per-token logsumexp
  of Eq. 2, with a custom VJP whose table gradient is reduced through the
  sorted run-sum scatter from ``jagged_lookup`` as sparse (id, row) pairs.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.embedding import tables as ET
from repro.kernels import autotune
from repro.kernels.jagged_lookup.ops import scatter_add_weighted_rows
from repro.kernels.neg_logits import fused as F
from repro.kernels.neg_logits import kernel as K
from repro.obs.metrics import KERNEL_METRICS


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def neg_logits(out_emb: jax.Array, neg_emb: jax.Array, *,
               segment: int = 128, tau: float = 1.0,
               interpret: Optional[bool] = None) -> jax.Array:
    """(T, D) × (T, R, D) → (T, R) logits, segment-pipelined.

    ``neg_emb`` may be fp16/bf16 (the §4.3.2 quantized fetch path) — the
    kernel dequantizes in VMEM. Differentiable via the segmented backward
    kernel; dn is produced in neg_emb's (possibly half-precision) dtype.
    """
    interpret_ = default_interpret() if interpret is None else interpret
    T = out_emb.shape[0]
    pad = (-T) % segment
    if pad:
        out_emb = jnp.concatenate(
            [out_emb, jnp.zeros((pad, *out_emb.shape[1:]), out_emb.dtype)])
        neg_emb = jnp.concatenate(
            [neg_emb, jnp.zeros((pad, *neg_emb.shape[1:]), neg_emb.dtype)])

    @jax.custom_vjp
    def _logits(o, n):
        return K.fwd_pallas(o, n, segment=segment, tau=tau,
                            interpret=interpret_)

    def fwd(o, n):
        return _logits(o, n), (o, n)

    def bwd(res, g):
        o, n = res
        do, dn = K.bwd_pallas(o, n, g, segment=segment, tau=tau,
                              interpret=interpret_)
        return do.astype(o.dtype), dn

    _logits.defvjp(fwd, bwd)
    out = _logits(out_emb, neg_emb)
    return out[:T] if pad else out


# --------------------------------------------------------------------------
# fused ID-driven recall path (§4.3.1 + §4.3.2 + §4.3.3 in one kernel)
# --------------------------------------------------------------------------

def make_share_perms(key, n_seg: int, segment: int,
                     expansion: int) -> jax.Array:
    """Deterministic per-segment shuffle for §4.3.3 logit sharing.

    Returns (n_seg, max(expansion-1, 1), segment) int32; entry [s, e, t] is
    the segment-local source token whose R logits consumer t borrows for
    expansion slot e — a random cyclic shift (never the identity, so a
    token can't borrow its own rows). For expansion ≤ 1 a zero dummy with
    the same rank is returned so kernel arity stays fixed.
    """
    if expansion <= 1:
        return jnp.zeros((n_seg, 1, segment), jnp.int32)
    shifts = jax.random.randint(key, (n_seg, expansion - 1), 1, segment,
                                dtype=jnp.int32)
    base = jnp.arange(segment, dtype=jnp.int32)
    return (base[None, None, :] + shifts[:, :, None]) % segment


def _pad_rows(x: jax.Array, pad: int) -> jax.Array:
    if not pad:
        return x
    return jnp.concatenate([x, jnp.zeros((pad, *x.shape[1:]), x.dtype)])


def prepare_fused_inputs(out_emb: jax.Array, pos_logit: jax.Array,
                         table: jax.Array, neg_ids: jax.Array, *,
                         segment: int, expansion: int,
                         key: Optional[jax.Array],
                         valid: Optional[jax.Array]):
    """Shared pad/clip/mask/shuffle prep for the Pallas megakernel, its XLA
    twin, and the materialized oracle — a single copy backs their
    'identical numerics, interchangeable mid-training' contract.

    Returns (o_p, pos_p, ids_p, valid_p, perms, n_seg) with all row arrays
    zero-padded to a multiple of ``segment`` (padded tokens are invalid,
    their ids clipped to row 0).
    """
    T, R = neg_ids.shape
    V = table.shape[0]
    assert 1 <= expansion <= segment, (expansion, segment)
    pad = (-T) % segment
    n_seg = (T + pad) // segment
    valid_p = _pad_rows(jnp.ones((T,), jnp.float32) if valid is None
                        else valid.astype(jnp.float32), pad)
    pos_p = _pad_rows(pos_logit.astype(jnp.float32), pad)
    ids_p = _pad_rows(jnp.clip(neg_ids, 0, V - 1).astype(jnp.int32), pad)
    o_p = _pad_rows(out_emb, pad)
    perms = make_share_perms(key if key is not None else jax.random.PRNGKey(0),
                             n_seg, segment, expansion)
    return o_p, pos_p, ids_p, valid_p, perms, n_seg


def fused_recall_lse(out_emb: jax.Array, pos_logit: jax.Array,
                     table: jax.Array, neg_ids: jax.Array, *,
                     segment: int = 128, tau: float = 1.0,
                     expansion: int = 1, key: Optional[jax.Array] = None,
                     valid: Optional[jax.Array] = None, fetch_dtype=None,
                     gather_table: Optional[jax.Array] = None,
                     scatter_impl: Optional[str] = None,
                     owned: Optional[jax.Array] = None,
                     interpret: Optional[bool] = None) -> jax.Array:
    """Per-token logsumexp over [pos | R negatives | (k−1)·R shared] (Eq. 2).

    out_emb (T, D), pos_logit (T,), table (V, D) — possibly stored
    fp16/bf16 — neg_ids (T, R) int32. Neither the (T, R, D) negative
    embeddings nor the (T, R·k) expanded logits ever exist in HBM: the
    kernels DMA each negative's row straight from the table into a
    double-buffered VMEM slab, a block of tokens at a time (the block sized
    from the shapes and the VMEM budget), and sharing shuffles
    VMEM-resident logits. Differentiable in (out_emb, pos_logit, table);
    the table gradient is reduced from sparse (id, w·out_row) pairs through
    the sorted run-sum kernel.

    ``gather_table``, when given, is the §4.3.2 persistent half-precision
    shadow as ``embedding.tables.shadow_of`` stores it, while the gradient
    still flows to ``table`` (the fp32 master). A packed shadow's rows are
    DMA'd as they are stored, two bf16 elements per 32-bit word (real
    half-bandwidth HBM→VMEM traffic), and dequantized in VMEM; an unpacked
    one (a width whose halves are not lane-aligned) holds exactly the
    master rounded to its dtype, so the kernels read the master's fp32 rows
    and round them in VMEM instead (:func:`fused.gather_source`) — under
    the shadow invariant the numerics are the same either way. Without a
    shadow, ``fetch_dtype`` emulates the rounding on fp32 master rows
    (numerics-faithful, not bandwidth-faithful). The bytes each row DMA
    moves, and whether the rows were packed, are recorded at trace time as
    the ``neg_gather_bytes_per_row`` gauge of
    :data:`repro.obs.KERNEL_METRICS`.

    ``scatter_impl`` (``"fused"`` in-kernel grad-row generation vs the
    ``"two_pass"`` materialized oracle) defaults to the tuned.json entry,
    else ``"fused"``.

    ``owned`` (T, R) bool, for one chip of an HSP group (``table`` its
    rows, ``neg_ids`` local to them): the slots whose rows the chip holds.
    The others count as absent — a logit of −∞, no gradient — so the lse
    is this chip's part of each token's sum over its negatives, and the
    scatter walks only the owned slots.
    """
    interpret_ = default_interpret() if interpret is None else interpret
    T, R = neg_ids.shape
    V, D = table.shape
    inv_tau = 1.0 / tau
    if scatter_impl is None:
        tune_dims = {"segment": segment, "R": R, "D": D, "T": T,
                     "expansion": expansion}
        scatter_impl = autotune.resolve("neg_fused", tune_dims,
                                        "scatter_impl", default="fused")
    packed = gather_table is not None and ET.is_packed(gather_table)
    KERNEL_METRICS.gauge(
        "neg_gather_bytes_per_row",
        "HBM bytes each negative-row DMA of the fused kernel moves",
        labels={"path": "packed_row" if packed else "row"}).set(
            ET.stored_row_bytes(gather_table) if packed else 4 * D)

    def _gather_src(tbl):
        # a packed shadow rides in by closure (non-differentiable state,
        # like ids_flat/valid2/perms); any other source must be the
        # custom_vjp *argument* — closing over `table` would leak the
        # caller's JVPTracer into the primal.
        return F.gather_source(tbl, gather_table, fetch_dtype)

    o_p, pos_p, ids_p, valid_p, perms, n_seg = prepare_fused_inputs(
        out_emb, pos_logit, table, neg_ids, segment=segment,
        expansion=expansion, key=key, valid=valid)
    Tp = n_seg * segment
    valid2 = valid_p.reshape(n_seg, segment)
    pos2 = pos_p.reshape(n_seg, segment)
    ids_flat = ids_p.reshape(-1)
    mask = None
    if owned is not None:
        mask = _pad_rows(owned, Tp - T)
        # the scatter drops what the chip does not hold
        scatter_ids = jnp.where(mask.reshape(-1), ids_flat, -1)
    else:
        scatter_ids = ids_flat

    @jax.custom_vjp
    def _lse(o, pos2d, tbl):
        words, fdt = _gather_src(tbl)
        return F.fwd_pallas(o, pos2d, words, ids_flat, valid2,
                            perms, segment=segment, R=R,
                            expansion=expansion, tau=tau, fetch_dtype=fdt,
                            owned=mask, interpret=interpret_)

    def fwd(o, pos2d, tbl):
        lse = _lse(o, pos2d, tbl)
        return lse, (o, pos2d, tbl, lse)

    def bwd(res, g):
        o, pos2d, tbl, lse = res
        words, fdt = _gather_src(tbl)
        w, dout, dpos = F.bwd_pallas(
            o, pos2d, words, ids_flat, valid2, perms, lse,
            g.astype(jnp.float32), segment=segment, R=R,
            expansion=expansion, tau=tau, fetch_dtype=fdt,
            owned=mask, interpret=interpret_)
        # sparse per-(token, slot) weights → weighted runsum-scatter; the
        # "fused" impl generates each w·o·τ⁻¹ grad row in sorted-run order
        # inside the kernel, so the (T·R, D) row buffer never exists.
        dtbl = scatter_add_weighted_rows(
            w.reshape(Tp, R), o.astype(jnp.float32), scatter_ids, V,
            scale=inv_tau, impl=scatter_impl, sparse=owned is not None,
            interpret=interpret_).astype(tbl.dtype)
        return dout.astype(o.dtype), dpos, dtbl

    _lse.defvjp(fwd, bwd)
    return _lse(o_p, pos2, table).reshape(-1)[:T]
