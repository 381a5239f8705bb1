"""Pallas TPU megakernel: fused ID-driven negative-sampling recall path.

One pass fuses the four stages the paper keeps separate (§4.3.1-§4.3.3):

  gather    — scalar-prefetched negative ids drive the table BlockSpec
              ``index_map`` (the ``jagged_lookup`` technique), so each grid
              step DMAs the HBM tiles of ``rows_per_step`` *live* embedding
              rows into VMEM; the (T, R, D) negative tensor never exists
              anywhere.
  dequant   — rows stored (or emulated-fetched) bf16/fp16 are widened to
              fp32 in VMEM right before the dot (§4.3.2).
  sharing   — intra-batch logit sharing (§4.3.3) is a deterministic
              per-segment shuffle of the already-VMEM-resident segment
              logits (a one-hot permutation matmul), so the expanded
              (T, R·k) logit tensor never exists either.
  reduce    — the per-token logsumexp of Eq. 2 over
              [pos | own negatives | shared negatives] is produced directly;
              HBM output is just (T,) plus the tiny per-segment blocks.

Grid layout: ``(n_seg, segment·R / rows_per_step)`` — the outer dim walks
fixed-size segments of packed valid positions, the inner dim walks that
segment's (token, slot) pairs ``rows_per_step`` gathered rows at a time
(the autotunable knob; the table rides in once per slot with its own
window). A table row is reached through the block of its HBM tile
(``jagged_lookup.kernel.row_tile`` rows, the chip's tiling unit) and picked
out in VMEM. Per-slot logits are placed into the token's (1, R) logit row
with lane selects and stored once per step. Per-slot arithmetic keeps the
exact rps=1 op order (each slot's dot is its own reduction), so every
legal rows_per_step is bitwise-identical. Output blocks are indexed by the
outer dim only, so they stay VMEM-resident across the inner sweep and are
flushed once per segment (the standard inner-accumulation pattern).
Per-token vectors (positive logit, validity, lse) travel as (seg, 1)
columns so they line up with the token-major logit rows.

Backward is the same sweep twice inside one kernel (grid
``(n_seg, 2·segment·R / rows_per_step)``): phase 0 re-gathers and rebuilds
the segment logits, the phase boundary turns them into softmax weights
(folding the shared-logit contributions back onto their source rows with
the transposed permutation), phase 1 re-gathers to accumulate d_out — one
weight-row load per step, slot accumulation kept sequential for
bitwise-stable grads. The table gradient leaves the kernel as
per-(token, slot) *weights* only — the ops wrapper reduces them through
the fused weighted scatter (grad rows generated in sorted-id order inside
that kernel), never a dense (T·R, D) row buffer.

The negative ids are scalar-prefetched into SMEM, which holds 1 MiB, so a
batch is processed in groups of whole segments of at most
:data:`IDS_PER_CALL` ids, one ``pallas_call`` per group.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import autotune
from repro.kernels.jagged_lookup.kernel import pick_row, row_tile

# Sentinel for masked (invalid-token) pool logits: large-negative instead of
# -inf so logsumexp arithmetic stays NaN-free even if a whole row masks out.
NEG_POOL = -1e30

# negative ids per kernel call: 256 KiB of the chip's 1 MiB SMEM
IDS_PER_CALL = 1 << 16

_HIGHEST = jax.lax.Precision.HIGHEST


def _dequant(tile, fetch_dtype):
    if fetch_dtype is not None and tile.dtype != jnp.dtype(fetch_dtype):
        # fp32-stored master table with a bf16/fp16 *fetch*: round in VMEM
        # so numerics match a half-stored table (§4.3.2) without ever
        # casting the (V, D) table in HBM.
        tile = tile.astype(fetch_dtype)
    return tile.astype(jnp.float32)


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


def check_rows_per_step(rows_per_step: int, segment: int, R: int) -> int:
    """Legal rows_per_step: divides segment·R and aligns to token rows
    (divides R, or is a whole multiple of R). Returns it validated."""
    rps = int(rows_per_step)
    seg_r = segment * R
    if not (1 <= rps <= seg_r and seg_r % rps == 0
            and (R % rps == 0 or rps % R == 0)):
        raise ValueError(
            f"rows_per_step={rps} invalid for segment={segment}, R={R}")
    return rps


def _slot_rows(ids_ref, tbl_refs, base, sub, fetch_dtype):
    """The (1, D) fp32 rows of this step's slots, picked from their tiles."""
    return [pick_row(_dequant(t[...], fetch_dtype), ids_ref[base + u] % sub)
            for u, t in enumerate(tbl_refs)]


def _store_logits(acc_ref, o_ref, rows, jj, *, R, inv_tau):
    """Per-slot logits for inner step jj into the (seg, R) logit scratch.
    Each slot's dot is its own (1, D) reduction — the exact rps=1 op
    order — placed into its token's logit row by a lane select."""
    rps = len(rows)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, R), 1)

    def logit(o_t, row):
        return jnp.sum(o_t * row, axis=1, keepdims=True) * inv_tau  # (1, 1)

    if rps <= R:                        # rps slots inside one token row
        t = (jj * rps) // R
        r0 = (jj * rps) % R
        o_t = o_ref[pl.ds(t, 1), :]
        blk = acc_ref[pl.ds(t, 1), :]
        for u in range(rps):
            blk = jnp.where(lane == r0 + u, logit(o_t, rows[u]), blk)
        acc_ref[pl.ds(t, 1), :] = blk
        return
    m = rps // R                        # whole tokens per step
    for g in range(m):
        t = jj * m + g
        o_t = o_ref[pl.ds(t, 1), :]
        blk = jnp.zeros((1, R), jnp.float32)
        for s in range(R):
            blk = jnp.where(lane == s, logit(o_t, rows[g * R + s]), blk)
        acc_ref[pl.ds(t, 1), :] = blk


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


def _tbl_specs(table, rps, seg_r, sub, inner):
    """One (sub, D) table window per slot, at the tile of its id. ``inner``
    maps the grid's inner index to the sweep step."""
    return [pl.BlockSpec(
        (sub, table.shape[1]),
        lambda si, j, ids, u=u: (ids[si * seg_r + inner(j) * rps + u] // sub,
                                 0))
        for u in range(rps)]


# --------------------------------------------------------------------------
# forward: gather + dequant + share + logsumexp
# --------------------------------------------------------------------------

def _fwd_kernel(ids_ref, o_ref, *refs, segment, R, rps, sub, expansion,
                inv_tau, fetch_dtype):
    tbl_refs = refs[:rps]
    pos_ref, valid_ref, perm_ref, lse_ref, acc_ref = refs[rps:rps + 5]
    si, j = pl.program_id(0), pl.program_id(1)
    G = segment * R // rps

    rows = _slot_rows(ids_ref, tbl_refs, si * segment * R + j * rps, sub,
                      fetch_dtype)
    _store_logits(acc_ref, o_ref, rows, j, R=R, inv_tau=inv_tau)

    @pl.when(j == G - 1)
    def _finalize():
        logits = acc_ref[...]                               # (seg, R)
        cols = [pos_ref[0], logits]                         # (seg, 1) pos
        cols += [aux for _, aux in _share_terms(logits, valid_ref[0],
                                                perm_ref, expansion,
                                                segment)]
        lse_ref[0] = _lse_cols(cols)


def fwd_pallas(out_emb: jax.Array, pos_logit2d: jax.Array, table: jax.Array,
               ids_flat: jax.Array, valid2d: jax.Array, perms: jax.Array, *,
               segment: int, R: int, expansion: int, tau: float,
               fetch_dtype=None, rows_per_step: int = 1,
               interpret: bool = False) -> jax.Array:
    """out_emb (Tp, D) · ids_flat (Tp·R,) → per-token lse (n_seg, segment)."""
    Tp, D = out_emb.shape
    n_seg = Tp // segment
    seg_r = segment * R
    rps = check_rows_per_step(rows_per_step, segment, R)
    G = seg_r // rps
    sub = row_tile(table.dtype)
    col = pl.BlockSpec((1, segment, 1), lambda si, j, ids: (si, 0, 0))
    cost = autotune.estimate_cost(
        "neg_fused",
        {"segment": segment, "R": R, "D": D, "T": Tp, "expansion": expansion},
        {"rows_per_step": rps})

    def call(o, pos3, ids, valid3, pm):
        ns = o.shape[0] // segment
        return pl.pallas_call(
            functools.partial(_fwd_kernel, segment=segment, R=R, rps=rps,
                              sub=sub, expansion=expansion,
                              inv_tau=1.0 / tau, fetch_dtype=fetch_dtype),
            name="neg_fused_fwd",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(ns, G),
                in_specs=[
                    pl.BlockSpec((segment, D), lambda si, j, ids: (si, 0)),
                    *_tbl_specs(table, rps, seg_r, sub, lambda j: j),
                    col, col,
                    pl.BlockSpec((1, pm.shape[1], segment),
                                 lambda si, j, ids: (si, 0, 0)),
                ],
                out_specs=col,
                scratch_shapes=[pltpu.VMEM((segment, R), jnp.float32)],
            ),
            out_shape=jax.ShapeDtypeStruct((ns, segment, 1), jnp.float32),
            interpret=interpret,
            **autotune.pallas_cost(**{k: cost[k] for k in
                                      ("flops", "bytes_accessed",
                                       "transcendentals")}),
        )(ids, o, *([table] * rps), pos3, valid3, pm)

    lse = _map_groups(call, n_seg, seg_r,
                      (out_emb.astype(jnp.float32),
                       pos_logit2d.reshape(n_seg, segment, 1), ids_flat,
                       valid2d.reshape(n_seg, segment, 1), perms))
    return lse.reshape(n_seg, segment)


# --------------------------------------------------------------------------
# backward: two-phase sweep in one kernel
#   phase 0 (j < G)    re-gather → rebuild segment logits
#   boundary (j == G)  logits → softmax weights w (sharing transposed
#                      back onto source rows), d_pos
#   phase 1 (j ≥ G)    re-gather → accumulate d_out from w (one weight-row
#                      load per step, sequential slot accumulation)
# --------------------------------------------------------------------------

def _bwd_kernel(ids_ref, o_ref, *refs, segment, R, rps, sub, expansion,
                inv_tau, fetch_dtype):
    tbl_refs = refs[:rps]
    pos_ref, valid_ref, lse_ref, g_ref, perm_ref = refs[rps:rps + 5]
    w_ref, dout_ref, dpos_ref = refs[rps + 5:rps + 8]
    acc_ref, w_acc, do_acc = refs[rps + 8:rps + 11]
    si, j = pl.program_id(0), pl.program_id(1)
    G = segment * R // rps
    jj = j % G
    rows = _slot_rows(ids_ref, tbl_refs, si * segment * R + jj * rps, sub,
                      fetch_dtype)

    @pl.when(j < G)
    def _rebuild():
        _store_logits(acc_ref, o_ref, rows, jj, R=R, inv_tau=inv_tau)

    @pl.when(j == G)
    def _weights():
        logits = acc_ref[...]                               # (seg, R)
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
        w_acc[...] = w
        do_acc[...] = jnp.zeros_like(do_acc)
        dpos_ref[0] = g * jnp.exp(pos - lse)

    @pl.when(j >= G)
    def _accum_dout():
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, R), 1)

        def accum(t, r0, slot_rows):
            wv = w_acc[pl.ds(t, 1), :]                      # (1, R)
            cur = do_acc[pl.ds(t, 1), :]
            for u, row in enumerate(slot_rows):
                w_u = jnp.sum(jnp.where(lane == r0 + u, wv, 0.0), axis=1,
                              keepdims=True)                # (1, 1)
                cur = cur + w_u * row * inv_tau
            do_acc[pl.ds(t, 1), :] = cur

        if rps <= R:
            accum((jj * rps) // R, (jj * rps) % R, rows)
        else:
            m = rps // R
            for g_ in range(m):
                accum(jj * m + g_, 0, rows[g_ * R:(g_ + 1) * R])

    @pl.when(j == 2 * G - 1)
    def _flush():
        w_ref[0] = w_acc[...]
        dout_ref[...] = do_acc[...].astype(dout_ref.dtype)


def bwd_pallas(out_emb: jax.Array, pos_logit2d: jax.Array, table: jax.Array,
               ids_flat: jax.Array, valid2d: jax.Array, perms: jax.Array,
               lse2d: jax.Array, g2d: jax.Array, *, segment: int, R: int,
               expansion: int, tau: float, fetch_dtype=None,
               rows_per_step: int = 1, interpret: bool = False):
    """→ (w (n_seg, seg, R) softmax weights·g, d_out (Tp, D) fp32,
         d_pos (n_seg, seg) fp32). Table grads are finished by the caller
    via the fused weighted scatter (sparse (id, w·o) pairs)."""
    Tp, D = out_emb.shape
    n_seg = Tp // segment
    seg_r = segment * R
    rps = check_rows_per_step(rows_per_step, segment, R)
    G = seg_r // rps
    sub = row_tile(table.dtype)
    col = pl.BlockSpec((1, segment, 1), lambda si, j, ids: (si, 0, 0))
    rowblk = pl.BlockSpec((segment, D), lambda si, j, ids: (si, 0))
    cost = autotune.estimate_cost(
        "neg_fused",
        {"segment": segment, "R": R, "D": D, "T": Tp, "expansion": expansion},
        {"rows_per_step": rps})

    def call(o, pos3, ids, valid3, lse3, g3, pm):
        ns = o.shape[0] // segment
        return pl.pallas_call(
            functools.partial(_bwd_kernel, segment=segment, R=R, rps=rps,
                              sub=sub, expansion=expansion,
                              inv_tau=1.0 / tau, fetch_dtype=fetch_dtype),
            name="neg_fused_bwd",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(ns, 2 * G),
                in_specs=[
                    rowblk,
                    *_tbl_specs(table, rps, seg_r, sub, lambda j: j % G),
                    col, col, col, col,
                    pl.BlockSpec((1, pm.shape[1], segment),
                                 lambda si, j, ids: (si, 0, 0)),
                ],
                out_specs=[
                    pl.BlockSpec((1, segment, R),
                                 lambda si, j, ids: (si, 0, 0)),
                    rowblk,
                    col,
                ],
                scratch_shapes=[pltpu.VMEM((segment, R), jnp.float32),
                                pltpu.VMEM((segment, R), jnp.float32),
                                pltpu.VMEM((segment, D), jnp.float32)],
            ),
            out_shape=[jax.ShapeDtypeStruct((ns, segment, R), jnp.float32),
                       jax.ShapeDtypeStruct((ns * segment, D), jnp.float32),
                       jax.ShapeDtypeStruct((ns, segment, 1), jnp.float32)],
            interpret=interpret,
            **autotune.pallas_cost(
                flops=2 * cost["flops"],
                bytes_accessed=2 * cost["bytes_accessed"],
                transcendentals=2 * cost["transcendentals"]),
        )(ids, o, *([table] * rps), pos3, valid3, lse3, g3, pm)

    col3 = lambda x: x.reshape(n_seg, segment, 1)
    w, dout, dpos = _map_groups(
        call, n_seg, seg_r,
        (out_emb.astype(jnp.float32), col3(pos_logit2d), ids_flat,
         col3(valid2d), col3(lse2d), col3(g2d), perms))
    return w, dout, dpos.reshape(n_seg, segment)
