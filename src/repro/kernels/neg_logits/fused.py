"""Pallas TPU megakernel: fused ID-driven negative-sampling recall path.

One pass fuses the four stages the paper keeps separate (§4.3.1-§4.3.3):

  gather    — the kernel DMAs each negative's table row from HBM into a
              VMEM slab itself, one row copy per (token, slot), driven by
              the scalar-prefetched ids; the (T, R, D) negative tensor
              never exists anywhere.
  dequant   — rows stored (or emulated-fetched) bf16/fp16 are widened to
              fp32 in VMEM right before the dot (§4.3.2).
  sharing   — intra-batch logit sharing (§4.3.3) is a deterministic
              per-segment shuffle of the already-VMEM-resident segment
              logits (a one-hot permutation matmul), so the expanded
              (T, R·k) logit tensor never exists either.
  reduce    — the per-token logsumexp of Eq. 2 over
              [pos | own negatives | shared negatives] is produced directly;
              HBM output is just (T,) plus the tiny per-segment blocks.

The gather source is a ``(V, 1, W)`` array of 32-bit words in HBM
(``memory_space=pltpu.HBM``: the kernel copies from it itself), chosen by
:func:`gather_source`: the persistent bf16 shadow where it is stored packed
two elements to a word (``W = D/2``, the layout of ``embedding.tables``),
else the fp32 master's rows (``W = D``) rounded in VMEM. The chip cannot
copy one row of a ``(V, D)`` array — its HBM tiles hold 8 rows, and a
16-bit row shares its words with the next — but with a unit second-minor
dim each row is a contiguous ``W``-word run, so a one-row copy moves
exactly the row's bytes (2 KiB for a packed bf16 row at D = 1024).

Grid layout: ``(n_seg, segment / tb)`` — the outer dim walks fixed-size
segments of packed valid positions, the inner dim walks that segment in
blocks of ``tb`` tokens (``tokens_per_step``: by default the largest block
whose slab fits the VMEM budget, ``autotune.neg_tokens_per_step``). Each
grid step starts the ``tb·R`` row copies of the *next* block into the
other half of a double-buffered ``(2, tb·R, 1, W)`` slab (one DMA
semaphore per half, ``row_dma``), waits on the current half, computes each
token's ``(R, D)`` rows against its broadcast ``(1, D)`` vector, reduced
over lanes to an ``(R, 1)`` logit column. Columns land in a slot-major
``(R, segment)`` logit scratch that is transposed once per segment.
A token's arithmetic does not depend on ``tb``, so every legal value is
bitwise-identical. Output blocks are indexed by the outer dim only, so
they stay VMEM-resident across the inner sweep and are flushed once per
segment. Per-token vectors (positive logit, validity, lse) travel as
(seg, 1) columns so they line up with the token-major logit rows.

Backward is the same sweep twice inside one kernel (grid
``(n_seg, 2·segment / tb)``), both sweeps through the same slab gather:
phase 0 rebuilds the segment logits, the phase boundary turns them into
softmax weights (folding the shared-logit contributions back onto their
source rows with the transposed permutation), phase 1 forms each token's
``d_out = Σ_r w[t, r]·row_r / τ`` as one broadcast-multiply and sublane
reduction over its ``(R, D)`` rows. The table gradient leaves the kernel
as per-(token, slot) *weights* only — the ops wrapper reduces them
through the fused weighted scatter (grad rows generated in sorted-id
order inside that kernel), never a dense (T·R, D) row buffer.

The negative ids are scalar-prefetched into SMEM, which holds 1 MiB, so a
batch is processed in groups of whole segments of at most
:data:`IDS_PER_CALL` ids, one ``pallas_call`` per group.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.embedding import tables as ET
from repro.kernels import autotune
from repro.kernels.row_dma import gather_block, slab_scratch

# Sentinel for masked (invalid-token) pool logits: large-negative instead of
# -inf so logsumexp arithmetic stays NaN-free even if a whole row masks out.
NEG_POOL = -1e30

# negative ids per kernel call: 256 KiB of the chip's 1 MiB SMEM
IDS_PER_CALL = 1 << 16

_HIGHEST = jax.lax.Precision.HIGHEST


def gather_source(table: jax.Array, shadow, fetch_dtype):
    """What the kernels DMA rows from: ``(words, fetch_dtype)``.

    ``words`` is a ``(V, 1, W)`` view of 32-bit words, one row a contiguous
    run. A packed shadow (``embedding.tables``) is viewed as it is stored,
    two bf16 elements a word (``W = D/2``), and needs no rounding. Any other
    source is the master's fp32 rows (``W = D``, a free reshape of an fp32
    master), rounded in VMEM to ``fetch_dtype``; an unpacked shadow holds
    exactly ``master.astype(its dtype)``, so its rounding stands in for it
    and no step copies the table to another width.
    """
    if shadow is not None and ET.is_packed(shadow):
        return ET.packed_row_words(shadow), None
    if shadow is not None:
        fetch_dtype = shadow.dtype
    words = table.astype(jnp.float32).reshape(table.shape[0], 1, -1)
    return words, fetch_dtype


def _row_parts(words, fetch_dtype):
    """(R, W) words of a token's rows → [(lane offset, (R, w) fp32 rows)]:
    the two halves of packed bf16 rows, or whole fp32 rows."""
    if ET.is_packed(words):
        lo, hi = ET.unpack_halves(words)
        return [(0, lo), (words.shape[1], hi)]
    if fetch_dtype is not None:
        # fp32 master rows with a bf16/fp16 *fetch*: round in VMEM so
        # numerics match a half-stored table (§4.3.2) without ever casting
        # the (V, D) table in HBM.
        words = words.astype(fetch_dtype).astype(jnp.float32)
    return [(0, words)]


def _share_terms(logits, valid_col, perm_ref, expansion, segment):
    """Per-segment §4.3.3 sharing terms: yields (P_eᵀ, aux_e) per expansion
    slot, where P_e is the one-hot matrix of the deterministic shuffle
    (P_e[t, s] = [s == perm_e[t]]) and aux_e = P_e @ masked_logits (seg, R).
    Single source of truth for the masking sentinel and permutation layout
    used by forward AND backward."""
    if expansion <= 1:
        return
    masked = jnp.where(valid_col > 0.0, logits, NEG_POOL)
    iota = jax.lax.broadcasted_iota(jnp.int32, (segment, segment), 0)
    for e in range(expansion - 1):
        pe = perm_ref[0, pl.ds(e, 1), :]                    # (1, segment)
        p_t = (iota == pe).astype(jnp.float32)              # P_eᵀ
        yield p_t, jax.lax.dot(p_t.T, masked, precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def check_tokens_per_step(tokens_per_step: Optional[int], segment: int,
                          R: int, D: int, words: jax.Array) -> int:
    """Tokens a grid step covers: ``tokens_per_step``, which must divide the
    segment, or by default the largest block whose slab fits the VMEM
    budget. Every legal value gives bitwise the same results."""
    if tokens_per_step is None:
        return autotune.neg_tokens_per_step(
            {"segment": segment, "R": R, "D": D,
             "itemsize": words.shape[2] * 4 // D})
    tb = int(tokens_per_step)
    if not (1 <= tb <= segment and segment % tb == 0):
        raise ValueError(
            f"tokens_per_step={tb} invalid for segment={segment}")
    return tb


def _token_parts(slab, slot, g, R, fetch_dtype):
    words = slab[slot, pl.ds(g * R, R)]                      # (R, 1, W)
    return _row_parts(words.reshape(R, words.shape[-1]), fetch_dtype)


def _block_logits(o_ref, slab, slot, lt_ref, b, *, tb, R, inv_tau,
                  fetch_dtype):
    """Logit columns of token block ``b`` into the (R, seg) scratch: each
    token's rows times its broadcast vector, reduced over lanes."""
    lane = jax.lax.broadcasted_iota(jnp.int32, lt_ref.shape, 1)

    def token(g, lt):
        t = b * tb + g
        prod = None
        for off, rows in _token_parts(slab, slot, g, R, fetch_dtype):
            p = rows * o_ref[pl.ds(t, 1), pl.ds(off, rows.shape[1])]
            prod = p if prod is None else prod + p
        col = jnp.sum(prod, axis=1, keepdims=True) * inv_tau   # (R, 1)
        return jnp.where(lane == t, col, lt)

    lt_ref[...] = jax.lax.fori_loop(0, tb, token, lt_ref[...])


def _lse_cols(cols):
    """Row-wise logsumexp over a list of (seg, k) column groups → (seg, 1)."""
    m = functools.reduce(jnp.maximum,
                         [jnp.max(c, axis=1, keepdims=True) for c in cols])
    s = functools.reduce(
        jnp.add, [jnp.sum(jnp.exp(c - m), axis=1, keepdims=True)
                  for c in cols])
    return m + jnp.log(s)


def _segment_groups(n_seg: int, seg_r: int) -> int:
    """Segments per kernel call: the largest divisor of n_seg whose ids fit
    :data:`IDS_PER_CALL` (at least 1)."""
    cap = max(IDS_PER_CALL // seg_r, 1)
    return max(d for d in range(1, min(cap, n_seg) + 1) if n_seg % d == 0)


def _map_groups(call, n_seg: int, seg_r: int, args):
    """Run ``call`` on groups of whole segments. Every array in ``args``
    has a leading dim proportional to n_seg; outputs are concatenated back
    along it."""
    spc = _segment_groups(n_seg, seg_r)
    if spc == n_seg:
        return call(*args)
    ng = n_seg // spc
    outs = jax.lax.map(lambda xs: call(*xs),
                       tuple(a.reshape(ng, a.shape[0] // ng, *a.shape[1:])
                             for a in args))
    return jax.tree.map(lambda y: y.reshape(-1, *y.shape[2:]), outs)


def _cost(segment, R, D, Tp, expansion, tb, words):
    return autotune.estimate_cost(
        "neg_fused",
        {"segment": segment, "R": R, "D": D, "T": Tp, "expansion": expansion,
         "itemsize": words.shape[2] * 4 // D},
        {"tokens_per_step": tb})


# --------------------------------------------------------------------------
# forward: gather + dequant + share + logsumexp
# --------------------------------------------------------------------------

def _owned_logits(lt_ref, owned_refs):
    """The segment's (seg, R) logits, the slots a chip does not hold (an
    owned-slot mask given) set to the masking sentinel: their rows were
    copied from a stand-in row, and they weigh nothing."""
    logits = lt_ref[...].T
    if owned_refs:
        logits = jnp.where(owned_refs[0][...] > 0.0, logits, NEG_POOL)
    return logits


def _fwd_kernel(ids_ref, o_ref, words_ref, pos_ref, valid_ref, perm_ref,
                *refs, segment, R, tb, expansion, inv_tau, fetch_dtype,
                masked):
    owned_refs, (lse_ref, lt_ref, slab, sem) = refs[:masked], refs[masked:]
    si, b = pl.program_id(0), pl.program_id(1)
    nb = segment // tb
    step = si * nb + b
    slot = gather_block(ids_ref, words_ref, slab, sem, step,
                        pl.num_programs(0) * nb - 1, lambda s: s)
    _block_logits(o_ref, slab, slot, lt_ref, b, tb=tb, R=R, inv_tau=inv_tau,
                  fetch_dtype=fetch_dtype)

    @pl.when(b == nb - 1)
    def _finalize():
        logits = _owned_logits(lt_ref, owned_refs)          # (seg, R)
        cols = [pos_ref[0], logits]                         # (seg, 1) pos
        cols += [aux for _, aux in _share_terms(logits, valid_ref[0],
                                                perm_ref, expansion,
                                                segment)]
        lse_ref[0] = _lse_cols(cols)


def fwd_pallas(out_emb: jax.Array, pos_logit2d: jax.Array, words: jax.Array,
               ids_flat: jax.Array, valid2d: jax.Array, perms: jax.Array, *,
               segment: int, R: int, expansion: int, tau: float,
               fetch_dtype=None, tokens_per_step: Optional[int] = None,
               owned: Optional[jax.Array] = None,
               interpret: bool = False) -> jax.Array:
    """out_emb (Tp, D) · ids_flat (Tp·R,) → per-token lse (n_seg, segment).
    ``words`` and ``fetch_dtype`` are as :func:`gather_source` gives them.
    ``owned`` (Tp, R), 1 or 0: the slots whose rows this chip holds; the
    others (their ids any row of ``words``) count as absent."""
    Tp, D = out_emb.shape
    n_seg = Tp // segment
    tb = check_tokens_per_step(tokens_per_step, segment, R, D, words)
    col = pl.BlockSpec((1, segment, 1), lambda si, b, ids: (si, 0, 0))
    cost = _cost(segment, R, D, Tp, expansion, tb, words)
    extra = () if owned is None else (owned.astype(jnp.float32),)
    owned_spec = [pl.BlockSpec((segment, R), lambda si, b, ids: (si, 0))]

    def call(o, pos3, ids, valid3, pm, *own):
        ns = o.shape[0] // segment
        return pl.pallas_call(
            functools.partial(_fwd_kernel, segment=segment, R=R, tb=tb,
                              expansion=expansion, inv_tau=1.0 / tau,
                              fetch_dtype=fetch_dtype, masked=len(own)),
            name="neg_fused_fwd",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(ns, segment // tb),
                in_specs=[
                    pl.BlockSpec((segment, D), lambda si, b, ids: (si, 0)),
                    pl.BlockSpec(memory_space=pltpu.HBM),
                    col, col,
                    pl.BlockSpec((1, pm.shape[1], segment),
                                 lambda si, b, ids: (si, 0, 0)),
                ] + owned_spec[:len(own)],
                out_specs=col,
                scratch_shapes=[pltpu.VMEM((R, segment), jnp.float32),
                                *slab_scratch(tb * R, words.shape[2],
                                              words.dtype)],
            ),
            out_shape=jax.ShapeDtypeStruct((ns, segment, 1), jnp.float32),
            interpret=interpret,
            **autotune.pallas_cost(**{k: cost[k] for k in
                                      ("flops", "bytes_accessed",
                                       "transcendentals")}),
        )(ids, o, words, pos3, valid3, pm, *own)

    lse = _map_groups(call, n_seg, segment * R,
                      (out_emb.astype(jnp.float32),
                       pos_logit2d.reshape(n_seg, segment, 1), ids_flat,
                       valid2d.reshape(n_seg, segment, 1), perms) + extra)
    return lse.reshape(n_seg, segment)


# --------------------------------------------------------------------------
# backward: two-phase sweep in one kernel
#   phase 0 (b < nb)    gather → rebuild segment logits
#   boundary (b == nb)  logits → softmax weights w (sharing transposed
#                       back onto source rows), d_pos
#   phase 1 (b ≥ nb)    gather → d_out of each token from its weights
# --------------------------------------------------------------------------

def _bwd_kernel(ids_ref, o_ref, words_ref, pos_ref, valid_ref, lse_ref,
                g_ref, perm_ref, *refs, segment, R, tb, expansion, inv_tau,
                fetch_dtype, masked):
    owned_refs = refs[:masked]
    w_ref, dout_ref, dpos_ref, lt_ref, wt_ref, slab, sem = refs[masked:]
    si, j = pl.program_id(0), pl.program_id(1)
    nb = segment // tb
    step = si * 2 * nb + j
    slot = gather_block(
        ids_ref, words_ref, slab, sem, step, pl.num_programs(0) * 2 * nb - 1,
        lambda s: (s // (2 * nb)) * nb + s % nb)
    b = j % nb

    @pl.when(j < nb)
    def _rebuild():
        _block_logits(o_ref, slab, slot, lt_ref, b, tb=tb, R=R,
                      inv_tau=inv_tau, fetch_dtype=fetch_dtype)

    @pl.when(j == nb)
    def _weights():
        logits = _owned_logits(lt_ref, owned_refs)          # (seg, R)
        pos, lse, g = pos_ref[0], lse_ref[0], g_ref[0]      # (seg, 1) each
        # d lse / d logit = softmax prob; scale by upstream g per consumer.
        w = g * jnp.exp(logits - lse)
        for p_t, aux in _share_terms(logits, valid_ref[0], perm_ref,
                                     expansion, segment):
            p_aux = g * jnp.exp(aux - lse)
            # consumer t borrowed source perm_e[t]'s rows → P_eᵀ routes
            # each consumer's prob mass back to its source row.
            w = w + jax.lax.dot(p_t, p_aux, precision=_HIGHEST,
                                preferred_element_type=jnp.float32)
        w_ref[0] = w
        wt_ref[...] = w.T
        dpos_ref[0] = g * jnp.exp(pos - lse)

    @pl.when(j >= nb)
    def _dout():
        wt = wt_ref[...]                                    # (R, seg)
        lane = jax.lax.broadcasted_iota(jnp.int32, wt.shape, 1)

        def token(gi, carry):
            t = b * tb + gi
            w_col = jnp.sum(jnp.where(lane == t, wt, 0.0), axis=1,
                            keepdims=True)                  # (R, 1)
            for off, rows in _token_parts(slab, slot, gi, R,
                                          fetch_dtype):
                dout_ref[pl.ds(t, 1), pl.ds(off, rows.shape[1])] = (
                    jnp.sum(rows * w_col, axis=0, keepdims=True) * inv_tau)
            return carry

        jax.lax.fori_loop(0, tb, token, 0)


def bwd_pallas(out_emb: jax.Array, pos_logit2d: jax.Array, words: jax.Array,
               ids_flat: jax.Array, valid2d: jax.Array, perms: jax.Array,
               lse2d: jax.Array, g2d: jax.Array, *, segment: int, R: int,
               expansion: int, tau: float, fetch_dtype=None,
               tokens_per_step: Optional[int] = None,
               owned: Optional[jax.Array] = None,
               interpret: bool = False):
    """→ (w (n_seg, seg, R) softmax weights·g, d_out (Tp, D) fp32,
         d_pos (n_seg, seg) fp32). Table grads are finished by the caller
    via the fused weighted scatter (sparse (id, w·o) pairs). ``owned`` as
    for :func:`fwd_pallas`: an absent slot's weight is zero."""
    Tp, D = out_emb.shape
    n_seg = Tp // segment
    tb = check_tokens_per_step(tokens_per_step, segment, R, D, words)
    col = pl.BlockSpec((1, segment, 1), lambda si, j, ids: (si, 0, 0))
    rowblk = pl.BlockSpec((segment, D), lambda si, j, ids: (si, 0))
    cost = _cost(segment, R, D, Tp, expansion, tb, words)
    extra = () if owned is None else (owned.astype(jnp.float32),)
    owned_spec = [pl.BlockSpec((segment, R), lambda si, j, ids: (si, 0))]

    def call(o, pos3, ids, valid3, lse3, g3, pm, *own):
        ns = o.shape[0] // segment
        return pl.pallas_call(
            functools.partial(_bwd_kernel, segment=segment, R=R, tb=tb,
                              expansion=expansion, inv_tau=1.0 / tau,
                              fetch_dtype=fetch_dtype, masked=len(own)),
            name="neg_fused_bwd",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(ns, 2 * (segment // tb)),
                in_specs=[
                    rowblk,
                    pl.BlockSpec(memory_space=pltpu.HBM),
                    col, col, col, col,
                    pl.BlockSpec((1, pm.shape[1], segment),
                                 lambda si, j, ids: (si, 0, 0)),
                ] + owned_spec[:len(own)],
                out_specs=[
                    pl.BlockSpec((1, segment, R),
                                 lambda si, j, ids: (si, 0, 0)),
                    rowblk,
                    col,
                ],
                scratch_shapes=[pltpu.VMEM((R, segment), jnp.float32),
                                pltpu.VMEM((R, segment), jnp.float32),
                                *slab_scratch(tb * R, words.shape[2],
                                              words.dtype)],
            ),
            out_shape=[jax.ShapeDtypeStruct((ns, segment, R), jnp.float32),
                       jax.ShapeDtypeStruct((ns * segment, D), jnp.float32),
                       jax.ShapeDtypeStruct((ns, segment, 1), jnp.float32)],
            interpret=interpret,
            # two row sweeps
            **autotune.pallas_cost(
                flops=2 * cost["flops"],
                bytes_accessed=2 * cost["bytes_accessed"],
                transcendentals=2 * cost["transcendentals"]),
        )(ids, o, words, pos3, valid3, lse3, g3, pm, *own)

    col3 = lambda x: x.reshape(n_seg, segment, 1)
    w, dout, dpos = _map_groups(
        call, n_seg, segment * R,
        (out_emb.astype(jnp.float32), col3(pos_logit2d), ids_flat,
         col3(valid2d), col3(lse2d), col3(g2d), perms) + extra)
    return w, dout, dpos.reshape(n_seg, segment)
