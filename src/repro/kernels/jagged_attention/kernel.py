"""Pallas TPU kernel: fused jagged pointwise attention + RAB (paper §4.1.1).

The paper's Ascend fusion operator eliminates (a) padding redundancy,
(b) dense↔jagged conversions at operator boundaries, and (c) separate
attention/RAB kernels. The TPU adaptation:

  * tokens stay in the packed layout end-to-end; the jagged structure
    enters as per-token metadata (segment id, in-row position, timestamp,
    1/row-length) blocked alongside q/k/v — no dense conversion;
  * the RAB (relative-position buckets + bucketized relative-time) is
    computed *inside* the kernel from VMEM/SMEM-resident bias tables — the
    positional part via an anti-diagonal decomposition: a (qb, kb) block
    touches only bq+bk−1 distinct relative distances, so one tiny one-hot
    matmul fetches all of them per head and a strided lane roll expands
    them to the (bq, bk) Toeplitz block — never a (bq·bk × npb) one-hot;
  * fully-masked (cross-row or acausal) blocks never cost MXU work or DMA
    traffic — the analogue of the paper's "operate only on valid data";
  * HSTU attention is softmax-free (SiLU(qkᵀ+rab)/n) → a single pass with
    fp32 VMEM accumulation, no running-max rescaling;
  * Pallas pipelines the HBM→VMEM block copies (the paper's asynchronous
    data copying) automatically.

Chip layout. Inside the kernels q/k/v/dy are head-major ``(H, cap, D)``
so every head's block is a clean (b, D) tile; the wrappers transpose the
model's ``(cap, H, D)`` activations in and out. Per-token metadata comes
in twice: ``(cap, 3)`` for the query side (read as (b, 1) columns) and
its transpose ``(3, cap)`` for the key side (read as (1, b) rows), so the
(bq, bk) masks and time buckets are plain broadcasts. The positional
table is held transposed ``(H, npb)`` in VMEM; the time table (or the
FuXi-γ (3, H) parameters) sits in SMEM and is read as scalars. The
RAB-table gradients accumulate transposed, ``(H, npb)`` and ``(H, ntb)``.

Backward follows the flash pattern: one k-major kernel for (dk, dv), one
q-major kernel for dq + both RAB-table gradients (accumulated into
constant-index outputs, safe because the TPU grid is sequential).

Two schedules exist for each of the three kernels:

``dense`` — grid (nb, nb): every q/k block pair is a grid step; dead pairs
are suppressed with ``pl.when`` on per-block segment ranges in SMEM, but
their HBM→VMEM copies are still issued, so DMA traffic and grid length are
O(nb²) regardless of jaggedness. Kept as the on-device oracle / fallback.

``worklist`` (default) — grid (P,): a 1-D grid over a *compacted work-list*
of live (qb, kb) pairs built in traced code from ``offsets`` (see
``ops.build_attn_plan``). The pair ids are scalar-prefetched to SMEM and
every BlockSpec index map reads them data-dependently, so grid length, DMA
traffic, and MXU work all scale with the number of *live* blocks, not
capacity². Work-list layout and visit-flag protocol:

  * the list is destination-ordered: q-block-major for the forward and dq
    kernels, k-block-major for the dk/dv kernel, so each destination block
    owns one contiguous (variable-length) run of grid steps;
  * entries past the live count ``n_live`` (the list is padded to a static
    bound) replicate the *last* live pair — consecutive identical block
    ids cost no new DMA, the per-entry live mask skips their compute, and
    the destination run simply extends through the tail;
  * with ``pairs_per_step`` (pps) > 1 each grid step consumes pps
    consecutive list entries: every run is padded to a pps multiple with
    dead entries replicating the run's last live pair (ops.py), the
    varying-side blocks ride in as pps separate BlockSpec windows (one
    per slot, each indexed by its own scalar-prefetched pair id — a
    repeated id is the same block index, so Pallas elides the copy), and
    slots accumulate sequentially in list order — bitwise identical to
    pps=1 for every setting;
  * per-step ``(first, last)`` visit flags — computed over the padded list
    by comparing neighbouring destinations — replace the dense grid's
    ``j == 0`` accumulator reset and ``j == nb−1`` flush: the accumulator
    zeroes on ``first`` and writes out on ``last``, which holds even for
    the all-padding batch (the tail run writes zeros to block 0);
  * destination blocks visited by no pair keep whatever was in the output
    HBM buffer — callers mask outputs by the valid-token mask (pad slots
    are defined to be zero, matching the oracles).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.jagged import NEG_SEG  # padding segment id; masks test < 0
from repro.kernels import autotune

_HIGHEST = jax.lax.Precision.HIGHEST


def _attn_cost(block, H, D, num_pairs, nb, pps, *, factor=1.0):
    """pl.CostEstimate kwargs for an attention kernel launch (honest
    FLOPs/bytes for XLA's scheduler; ``factor`` ~doubles the backward)."""
    c = autotune.estimate_cost(
        "attn_worklist",
        {"block": block, "H": H, "D": D, "num_pairs": num_pairs,
         "num_blocks": nb},
        {"pairs_per_step": pps})
    return autotune.pallas_cost(
        flops=factor * c["flops"],
        bytes_accessed=factor * c["bytes_accessed"],
        transcendentals=factor * c["transcendentals"])


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _dsilu(x):
    s = jax.nn.sigmoid(x)
    return s * (1.0 + x * (1.0 - s))


def _dot(a, b, contract):
    """fp32-accumulated matmul; fp32 operands keep full precision."""
    if a.dtype != b.dtype:
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return jax.lax.dot_general(
        a, b, (contract, ((), ())),
        precision=_HIGHEST if a.dtype == jnp.float32 else None,
        preferred_element_type=jnp.float32)


_NN = ((1,), (0,))      # a @ b
_NT = ((1,), (1,))      # a @ bᵀ
_TN = ((0,), (0,))      # aᵀ @ b


# --------------------------------------------------------------------------
# in-kernel RAB helpers
# --------------------------------------------------------------------------

def _diag_width(bq, bk):
    """Lane width holding the bq+bk−1 anti-diagonals (a multiple of 128)."""
    return -(-(bq + bk - 1) // 128) * 128


def _diag_onehot(i0, j0, bq, bk, npb):
    """(npb, W) one-hot: column t selects bucket clip(i0−j0+(bq−1)−t)."""
    W = _diag_width(bq, bk)
    t = jax.lax.broadcasted_iota(jnp.int32, (npb, W), 1)
    b = jax.lax.broadcasted_iota(jnp.int32, (npb, W), 0)
    d = jnp.clip(i0 - j0 + (bq - 1) - t, 0, npb - 1)
    return (d == b).astype(jnp.float32)


def _expand_diag(r, bq, bk):
    """r (1, W) anti-diagonal values → (bq, bk) bias with
    bias[ii, jj] = r[bq−1−ii+jj]: row ii is r rolled left by bq−1−ii."""
    W = r.shape[1]
    x = jnp.broadcast_to(r, (bq, W))
    return pltpu.roll(x, W - (bq - 1), 1, stride=1, stride_axis=0)[:, :bk]


def _collapse_diag(ds, W):
    """Adjoint of _expand_diag: ds (bq, bk) → (1, W) anti-diagonal sums.

    The adjoint rolls row ii right by bq−1−ii, a shift that falls with ii;
    the chip's strided roll only climbs, so the rows are reversed first
    (an exact one-hot matmul) and row k is then rolled right by k."""
    bq, bk = ds.shape
    rev = (jax.lax.broadcasted_iota(jnp.int32, (bq, bq), 0)
           + jax.lax.broadcasted_iota(jnp.int32, (bq, bq), 1) == bq - 1)
    y = _dot(rev.astype(jnp.float32), ds, _NN)
    y = jnp.concatenate([y, jnp.zeros((bq, W - bk), jnp.float32)], axis=1)
    z = pltpu.roll(y, 0, 1, stride=1, stride_axis=0)
    return jnp.sum(z, axis=0, keepdims=True)


def _time_buckets(qts, kts, ntb, tb_scale):
    """(bq, 1), (1, bk) int32 → (bq, bk) int32 time-bucket ids."""
    dt = jnp.abs(qts - kts).astype(jnp.float32)
    b = jnp.floor(jnp.log(1.0 + dt) / (jnp.log(10.0) * tb_scale))
    return jnp.clip(b.astype(jnp.int32), 0, ntb - 1)


def _functional_terms(tt_ref, h, qts, kts):
    """FuXi-γ exponential-power temporal encoder for head h, in-kernel
    (elementwise — no gather at all): bias_h = amp_h·exp(−((Δt+ε)/σ_h)^ρ_h).

    tt_ref packs (3, H) = [amp; sigma; rho] fp32 in SMEM (transforms from
    the raw parameters happen in traced code outside the custom_vjp, so the
    chain rule composes). Returns (amp, sigma, rho, ln z, z^ρ, E)."""
    amp, sigma, rho = tt_ref[0, h], tt_ref[1, h], tt_ref[2, h]
    dt = jnp.abs(qts - kts).astype(jnp.float32)
    z = (dt + 1e-6) / sigma                               # (bq, bk)
    lnz = jnp.log(z)
    zr = jnp.exp(rho * lnz)                               # z^ρ (z > 0)
    return amp, sigma, rho, lnz, zr, jnp.exp(-zr)


def _rab_blocks(ptT_ref, tt_ref, i0, j0, qts, kts, bq, bk, H,
                npb, ntb, tb_scale, use_pos, use_time,
                time_functional=False):
    """Per-head (bq, bk) RAB biases (list of H), or None without RAB."""
    if not (use_pos or use_time):
        return None
    bias = [jnp.zeros((bq, bk), jnp.float32) for _ in range(H)]
    if use_pos:
        rows = _dot(ptT_ref[...], _diag_onehot(i0, j0, bq, bk, npb), _NN)
        bias = [bias[h] + _expand_diag(rows[h:h + 1], bq, bk)
                for h in range(H)]                       # rows: (H, W)
    if use_time:
        if time_functional:
            for h in range(H):
                amp, *_, e = _functional_terms(tt_ref, h, qts, kts)
                bias[h] = bias[h] + amp * e
        else:
            tb = _time_buckets(qts, kts, ntb, tb_scale)
            tbias = [jnp.zeros((bq, bk), jnp.float32) for _ in range(H)]
            for b in range(ntb):
                hit = tb == b
                tbias = [jnp.where(hit, tt_ref[b, h], tbias[h])
                         for h in range(H)]
            bias = [bias[h] + tbias[h] for h in range(H)]
    return bias


def _mask_block(qseg, kseg, i0, j0, bq, bk, causal):
    m = (qseg == kseg) & (qseg >= 0)                       # (bq, bk)
    if causal:
        qslot = i0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kslot = j0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        m &= qslot >= kslot
    return m


def _block_live(seg_rng_ref, i, j, bq, bk, causal):
    """Cheap SMEM check: does block pair (i, j) contain any live pair?"""
    qlo, qhi = seg_rng_ref[i, 0], seg_rng_ref[i, 1]
    klo, khi = seg_rng_ref[j, 0], seg_rng_ref[j, 1]
    live = (qlo <= khi) & (klo <= qhi) & (qhi >= 0) & (khi >= 0)
    if causal:
        live &= (i + 1) * bq - 1 >= j * bk
    return live


def _pair_setup(qm_ref, qmf_ref, km_ref, pt_ref, tt_ref, i0, j0, *,
                bq, bk, H, npb, ntb, tb_scale, use_pos, use_time, causal,
                time_functional):
    """RAB biases and the masked 1/n weights of one (qb, kb) pair."""
    qm = qm_ref[...]                                       # (bq, 3)
    km = km_ref[...]                                       # (3, bk)
    qseg, qts = qm[:, 0:1], qm[:, 2:3]
    kseg, kts = km[0:1, :], km[2:3, :]
    bias = _rab_blocks(pt_ref, tt_ref, i0, j0, qts, kts, bq, bk, H,
                       npb, ntb, tb_scale, use_pos, use_time,
                       time_functional)
    mask = _mask_block(qseg, kseg, i0, j0, bq, bk, causal)
    mw = mask.astype(jnp.float32) * qmf_ref[...]           # (bq, bk)
    return bias, mw, qts, kts


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _fwd_block_compute(i0, j0, qm_ref, qmf_ref, km_ref,
                       q_ref, k_ref, v_ref, pt_ref, tt_ref, acc_ref, *,
                       bq, bk, H, scale, npb, ntb, tb_scale,
                       use_pos, use_time, causal, time_functional):
    """Accumulate one (qb, kb) pair's contribution into acc_ref — shared by
    the dense-grid and work-list forward kernels."""
    bias, mw, _, _ = _pair_setup(
        qm_ref, qmf_ref, km_ref, pt_ref, tt_ref, i0, j0, bq=bq, bk=bk, H=H,
        npb=npb, ntb=ntb, tb_scale=tb_scale, use_pos=use_pos,
        use_time=use_time, causal=causal, time_functional=time_functional)
    for h in range(H):
        s = _dot(q_ref[h], k_ref[h], _NT) * scale
        if bias is not None:
            s = s + bias[h]
        a = _silu(s) * mw
        acc_ref[h] += _dot(a.astype(v_ref.dtype), v_ref[h], _NN)


def _fwd_kernel(seg_rng_ref,                      # scalar prefetch (nb, 2)
                qm_ref, qmf_ref, km_ref, q_ref, k_ref, v_ref, pt_ref,
                tt_ref, out_ref, acc_ref, *,
                bq, bk, nkb, H, scale, npb, ntb, tb_scale,
                use_pos, use_time, causal, time_functional=False):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(_block_live(seg_rng_ref, i, j, bq, bk, causal))
    def _compute():
        _fwd_block_compute(i * bq, j * bk, qm_ref, qmf_ref, km_ref,
                           q_ref, k_ref, v_ref, pt_ref, tt_ref, acc_ref,
                           bq=bq, bk=bk, H=H, scale=scale, npb=npb,
                           ntb=ntb, tb_scale=tb_scale, use_pos=use_pos,
                           use_time=use_time, causal=causal,
                           time_functional=time_functional)

    @pl.when(j == nkb - 1)
    def _write():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _fwd_kernel_wl(wq_ref, wk_ref, flg_ref, live_ref, nlive_ref,  # prefetch
                   *refs,
                   bq, bk, pps, H, scale, npb, ntb, tb_scale,
                   use_pos, use_time, causal, time_functional=False):
    """Work-list forward: grid (S,), ``pps`` live (qb, kb) pairs per step,
    q-major. The k-side blocks arrive as pps per-slot windows; slots
    accumulate sequentially in list order (bitwise-equal to pps=1)."""
    qm_ref, qmf_ref = refs[0], refs[1]
    km_refs = refs[2:2 + pps]
    q_ref = refs[2 + pps]
    k_refs = refs[3 + pps:3 + 2 * pps]
    v_refs = refs[3 + 2 * pps:3 + 3 * pps]
    pt_ref, tt_ref = refs[3 + 3 * pps], refs[4 + 3 * pps]
    out_ref, acc_ref = refs[5 + 3 * pps], refs[6 + 3 * pps]
    p = pl.program_id(0)

    @pl.when(flg_ref[p, 0] == 1)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    i0 = wq_ref[p * pps] * bq     # destination: constant across the step
    for u in range(pps):
        @pl.when(live_ref[p * pps + u] == 1)
        def _compute(u=u):
            _fwd_block_compute(i0, wk_ref[p * pps + u] * bk,
                               qm_ref, qmf_ref, km_refs[u],
                               q_ref, k_refs[u], v_refs[u], pt_ref, tt_ref,
                               acc_ref, bq=bq, bk=bk, H=H, scale=scale,
                               npb=npb, ntb=ntb, tb_scale=tb_scale,
                               use_pos=use_pos, use_time=use_time,
                               causal=causal,
                               time_functional=time_functional)

    @pl.when(flg_ref[p, 1] == 1)
    def _write():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


# --------------------------------------------------------------------------
# layout plumbing shared by the four launchers
# --------------------------------------------------------------------------

def _heads_major(*xs):
    return tuple(x.transpose(1, 0, 2) for x in xs)


def _tables(pos_table, time_table):
    """(ptT (H, npb) fp32 for VMEM, tt (ntb, H) fp32 for SMEM)."""
    return (pos_table.astype(jnp.float32).T,
            time_table.astype(jnp.float32))


def _smem_spec():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _static_kw(pos_table, time_table, H, **kw):
    return dict(H=H, npb=pos_table.shape[0], ntb=time_table.shape[0], **kw)


def fwd_pallas(q, k, v, pos_table, time_table, meta_i32, meta_f32, seg_rng,
               *, block: int, scale: float, tb_scale: float,
               use_pos: bool, use_time: bool, causal: bool = True,
               time_functional: bool = False, interpret: bool = False):
    cap, H, D = q.shape
    assert cap % block == 0
    nb = cap // block
    bq = bk = block
    ptT, tt = _tables(pos_table, time_table)
    qh, kh, vh = _heads_major(q, k, v)

    kern = functools.partial(
        _fwd_kernel, bq=bq, bk=bk, nkb=nb, scale=scale, tb_scale=tb_scale,
        use_pos=use_pos, use_time=use_time, causal=causal,
        time_functional=time_functional,
        **_static_kw(pos_table, time_table, H))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb, nb),
        in_specs=[
            pl.BlockSpec((bq, 3), lambda i, j, *_: (i, 0)),    # q meta
            pl.BlockSpec((bq, 1), lambda i, j, *_: (i, 0)),    # q 1/n
            pl.BlockSpec((3, bk), lambda i, j, *_: (0, j)),    # k meta
            pl.BlockSpec((H, bq, D), lambda i, j, *_: (0, i, 0)),
            pl.BlockSpec((H, bk, D), lambda i, j, *_: (0, j, 0)),
            pl.BlockSpec((H, bk, D), lambda i, j, *_: (0, j, 0)),
            pl.BlockSpec(ptT.shape, lambda i, j, *_: (0, 0)),
            _smem_spec(),
        ],
        out_specs=pl.BlockSpec((H, bq, D), lambda i, j, *_: (0, i, 0)),
        scratch_shapes=[pltpu.VMEM((H, bq, D), jnp.float32)],
    )
    out = pl.pallas_call(
        kern, grid_spec=grid_spec, name="attn_fwd_dense",
        out_shape=jax.ShapeDtypeStruct((H, cap, D), v.dtype),
        interpret=interpret,
        **_attn_cost(block, H, D, nb * nb, nb, 1),
    )(seg_rng, meta_i32, meta_f32, meta_i32.T, qh, kh, vh, ptT, tt)
    return out.transpose(1, 0, 2)


def _wl_shape(wq, flags):
    """(L, S, pps) of a grouped work-list; pps is static from the shapes."""
    L, S = wq.shape[0], flags.shape[0]
    pps = L // S
    assert S * pps == L, (L, S)
    return L, S, pps


def fwd_pallas_wl(q, k, v, pos_table, time_table, meta_i32, meta_f32,
                  wq, wk, flags, live, n_live,
                  *, block: int, scale: float, tb_scale: float,
                  use_pos: bool, use_time: bool, causal: bool = True,
                  time_functional: bool = False, interpret: bool = False):
    """Forward over a compacted work-list (wq, wk): (L,) int32 pair ids,
    flags (S, 2) int32 first/last-step markers, live (L,) int32 per-entry
    mask, n_live (1,) int32. pps = L // S entries per grid step."""
    cap, H, D = q.shape
    assert cap % block == 0
    bq = bk = block
    nb = cap // block
    L, S, pps = _wl_shape(wq, flags)
    ptT, tt = _tables(pos_table, time_table)
    qh, kh, vh = _heads_major(q, k, v)

    kern = functools.partial(
        _fwd_kernel_wl, bq=bq, bk=bk, pps=pps, scale=scale,
        tb_scale=tb_scale, use_pos=use_pos, use_time=use_time,
        causal=causal, time_functional=time_functional,
        **_static_kw(pos_table, time_table, H))

    def at_q(p, wq, wk, flg, live, nl):
        return (wq[p * pps], 0)

    def at_q3(p, wq, wk, flg, live, nl):
        return (0, wq[p * pps], 0)

    def at_kt(u):
        return lambda p, wq, wk, flg, live, nl, u=u: (0, wk[p * pps + u])

    def at_k3(u):
        return lambda p, wq, wk, flg, live, nl, u=u: (0, wk[p * pps + u], 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(S,),
        in_specs=[
            pl.BlockSpec((bq, 3), at_q),                       # q meta
            pl.BlockSpec((bq, 1), at_q),                       # q 1/n
            *[pl.BlockSpec((3, bk), at_kt(u)) for u in range(pps)],
            pl.BlockSpec((H, bq, D), at_q3),
            *[pl.BlockSpec((H, bk, D), at_k3(u)) for u in range(pps)],
            *[pl.BlockSpec((H, bk, D), at_k3(u)) for u in range(pps)],
            pl.BlockSpec(ptT.shape, lambda p, *_: (0, 0)),
            _smem_spec(),
        ],
        out_specs=pl.BlockSpec((H, bq, D), at_q3),
        scratch_shapes=[pltpu.VMEM((H, bq, D), jnp.float32)],
    )
    out = pl.pallas_call(
        kern, grid_spec=grid_spec, name="attn_fwd",
        out_shape=jax.ShapeDtypeStruct((H, cap, D), v.dtype),
        interpret=interpret,
        **_attn_cost(block, H, D, L, nb, pps),
    )(wq, wk, flags, live, n_live, meta_i32, meta_f32,
      *([meta_i32.T] * pps), qh, *([kh] * pps), *([vh] * pps), ptT, tt)
    return out.transpose(1, 0, 2)


# --------------------------------------------------------------------------
# backward — shared ds recompute
# --------------------------------------------------------------------------

def _recompute_block(q_ref, k_ref, v_ref, dy_ref, bias, mw, H, scale):
    """Recompute (a, ds) for a block pair, per head: (bq, bk) fp32 each.

    a  = SiLU(s)·maskw — the attention weights;
    ds = ∂L/∂(pre-SiLU s) = (dy·vᵀ)·SiLU′(s)·maskw.
    """
    a_all = []
    ds_all = []
    for h in range(H):
        s = _dot(q_ref[h], k_ref[h], _NT) * scale
        if bias is not None:
            s = s + bias[h]
        da = _dot(dy_ref[h], v_ref[h], _NT)
        a_all.append(_silu(s) * mw)
        ds_all.append(da * _dsilu(s) * mw)
    return a_all, ds_all


def _kv_block_compute(i0, j0, qm_ref, qmf_ref, km_ref,
                      k_ref, v_ref, q_ref, dy_ref, pt_ref, tt_ref,
                      dk_acc, dv_acc, *,
                      bq, bk, H, scale, npb, ntb, tb_scale,
                      use_pos, use_time, causal, time_functional):
    """Accumulate one pair's (dk, dv) contribution. i0/j0: q/k origins."""
    bias, mw, _, _ = _pair_setup(
        qm_ref, qmf_ref, km_ref, pt_ref, tt_ref, i0, j0, bq=bq, bk=bk, H=H,
        npb=npb, ntb=ntb, tb_scale=tb_scale, use_pos=use_pos,
        use_time=use_time, causal=causal, time_functional=time_functional)
    a_all, ds_all = _recompute_block(q_ref, k_ref, v_ref, dy_ref, bias, mw,
                                     H, scale)
    for h in range(H):
        dv_acc[h] += _dot(a_all[h], dy_ref[h], _TN)
        dk_acc[h] += _dot(ds_all[h], q_ref[h], _TN) * scale


def _bwd_kv_kernel(seg_rng_ref,
                   km_ref, qm_ref, qmf_ref,
                   k_ref, v_ref, q_ref, dy_ref, pt_ref, tt_ref,
                   dk_ref, dv_ref, dk_acc, dv_acc, *,
                   bq, bk, nqb, H, scale, npb, ntb, tb_scale,
                   use_pos, use_time, causal, time_functional=False):
    """Grid (kb, qb) — q inner; accumulates dk, dv for this k block."""
    i, j = pl.program_id(0), pl.program_id(1)   # i = kb, j = qb

    @pl.when(j == 0)
    def _zero():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(_block_live(seg_rng_ref, j, i, bq, bk, causal))
    def _compute():
        _kv_block_compute(j * bq, i * bk, qm_ref, qmf_ref, km_ref,
                          k_ref, v_ref, q_ref, dy_ref, pt_ref, tt_ref,
                          dk_acc, dv_acc, bq=bq, bk=bk, H=H, scale=scale,
                          npb=npb, ntb=ntb, tb_scale=tb_scale,
                          use_pos=use_pos, use_time=use_time, causal=causal,
                          time_functional=time_functional)

    @pl.when(j == nqb - 1)
    def _write():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_kv_kernel_wl(wq_ref, wk_ref, flg_ref, live_ref, nlive_ref,
                      *refs,
                      bq, bk, pps, H, scale, npb, ntb, tb_scale,
                      use_pos, use_time, causal, time_functional=False):
    """Work-list (dk, dv): grid (S,), ``pps`` pairs per step, sorted
    k-block-major; flags mark the first/last step of each k-block run.
    The q-side (varying) blocks arrive as pps per-slot windows."""
    km_ref = refs[0]
    qm_refs = refs[1:1 + pps]
    qmf_refs = refs[1 + pps:1 + 2 * pps]
    k_ref, v_ref = refs[1 + 2 * pps], refs[2 + 2 * pps]
    q_refs = refs[3 + 2 * pps:3 + 3 * pps]
    dy_refs = refs[3 + 3 * pps:3 + 4 * pps]
    pt_ref, tt_ref = refs[3 + 4 * pps], refs[4 + 4 * pps]
    dk_ref, dv_ref, dk_acc, dv_acc = refs[5 + 4 * pps:9 + 4 * pps]
    p = pl.program_id(0)

    @pl.when(flg_ref[p, 0] == 1)
    def _zero():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    j0 = wk_ref[p * pps] * bk     # destination: constant across the step
    for u in range(pps):
        @pl.when(live_ref[p * pps + u] == 1)
        def _compute(u=u):
            _kv_block_compute(wq_ref[p * pps + u] * bq, j0,
                              qm_refs[u], qmf_refs[u], km_ref,
                              k_ref, v_ref, q_refs[u], dy_refs[u],
                              pt_ref, tt_ref, dk_acc, dv_acc,
                              bq=bq, bk=bk, H=H, scale=scale,
                              npb=npb, ntb=ntb, tb_scale=tb_scale,
                              use_pos=use_pos, use_time=use_time,
                              causal=causal,
                              time_functional=time_functional)

    @pl.when(flg_ref[p, 1] == 1)
    def _write():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _q_block_compute(i0, j0, qm_ref, qmf_ref, km_ref,
                     q_ref, k_ref, v_ref, dy_ref, pt_ref, tt_ref,
                     dq_acc, dpt_ref, dtt_ref, *,
                     bq, bk, H, scale, npb, ntb, tb_scale,
                     use_pos, use_time, causal, time_functional):
    """Accumulate one pair's dq + RAB-table grad contributions (the table
    grads transposed: dpt_ref (H, npb), dtt_ref (H, ntb))."""
    bias, mw, qts, kts = _pair_setup(
        qm_ref, qmf_ref, km_ref, pt_ref, tt_ref, i0, j0, bq=bq, bk=bk, H=H,
        npb=npb, ntb=ntb, tb_scale=tb_scale, use_pos=use_pos,
        use_time=use_time, causal=causal, time_functional=time_functional)
    _, ds_all = _recompute_block(q_ref, k_ref, v_ref, dy_ref, bias, mw,
                                 H, scale)
    for h in range(H):
        dq_acc[h] += _dot(ds_all[h], k_ref[h], _NN) * scale
    head = jax.lax.broadcasted_iota(jnp.int32, (H, 1), 0)
    if use_pos:
        W = _diag_width(bq, bk)
        dsdiag = jnp.zeros((H, W), jnp.float32)
        for h in range(H):
            dsdiag = jnp.where(head == h, _collapse_diag(ds_all[h], W),
                               dsdiag)
        dpt_ref[...] += _dot(dsdiag, _diag_onehot(i0, j0, bq, bk, npb), _NT)
    if use_time:
        ncol = dtt_ref.shape[1]
        cell = jax.lax.broadcasted_iota(jnp.int32, (H, ncol), 1)
        upd = jnp.zeros((H, ncol), jnp.float32)

        def put(h, c, x):
            total = jnp.sum(x, keepdims=True)              # (1, 1)
            return jnp.where((head == h) & (cell == c), total, upd)

        if time_functional:
            for h in range(H):
                amp, sigma, rho, lnz, zr, e = _functional_terms(
                    tt_ref, h, qts, kts)
                ds = ds_all[h]
                upd = put(h, 0, ds * e)
                # ∂bias/∂σ = amp·E·ρ·z^ρ/σ   (dz/dσ = −z/σ; d(−z^ρ)/dz = −ρz^{ρ−1})
                upd = put(h, 1, ds * (amp * e * rho * zr / sigma))
                # ∂bias/∂ρ = −amp·E·z^ρ·ln z
                upd = put(h, 2, ds * (-amp * e * zr * lnz))
        else:
            tb = _time_buckets(qts, kts, ntb, tb_scale)
            for b in range(ntb):
                hit = tb == b
                for h in range(H):
                    upd = put(h, b, jnp.where(hit, ds_all[h], 0.0))
        dtt_ref[...] += upd


def _bwd_q_kernel(seg_rng_ref,
                  qm_ref, qmf_ref, km_ref,
                  q_ref, k_ref, v_ref, dy_ref, pt_ref, tt_ref,
                  dq_ref, dpt_ref, dtt_ref, dq_acc, *,
                  bq, bk, nkb, H, scale, npb, ntb, tb_scale,
                  use_pos, use_time, causal, time_functional=False):
    """Grid (qb, kb) — k inner; accumulates dq + both RAB table grads."""
    i, j = pl.program_id(0), pl.program_id(1)   # i = qb, j = kb

    @pl.when(j == 0)
    def _zero_dq():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when((i == 0) & (j == 0))
    def _zero_tables():
        dpt_ref[...] = jnp.zeros_like(dpt_ref)
        dtt_ref[...] = jnp.zeros_like(dtt_ref)

    @pl.when(_block_live(seg_rng_ref, i, j, bq, bk, causal))
    def _compute():
        _q_block_compute(i * bq, j * bk, qm_ref, qmf_ref, km_ref,
                         q_ref, k_ref, v_ref, dy_ref, pt_ref, tt_ref,
                         dq_acc, dpt_ref, dtt_ref, bq=bq, bk=bk, H=H,
                         scale=scale, npb=npb, ntb=ntb, tb_scale=tb_scale,
                         use_pos=use_pos, use_time=use_time, causal=causal,
                         time_functional=time_functional)

    @pl.when(j == nkb - 1)
    def _write():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_q_kernel_wl(wq_ref, wk_ref, flg_ref, live_ref, nlive_ref,
                     *refs,
                     bq, bk, pps, H, scale, npb, ntb, tb_scale,
                     use_pos, use_time, causal, time_functional=False):
    """Work-list dq + RAB-table grads: grid (S,), ``pps`` pairs per step,
    q-block-major (the same list as the forward). The RAB-table outputs
    have constant index maps, so their VMEM windows persist across the
    whole grid — zero at p == 0, flush once at the end."""
    qm_ref, qmf_ref = refs[0], refs[1]
    km_refs = refs[2:2 + pps]
    q_ref, dy_ref = refs[2 + pps], refs[3 + pps]
    k_refs = refs[4 + pps:4 + 2 * pps]
    v_refs = refs[4 + 2 * pps:4 + 3 * pps]
    pt_ref, tt_ref = refs[4 + 3 * pps], refs[5 + 3 * pps]
    dq_ref, dpt_ref, dtt_ref, dq_acc = refs[6 + 3 * pps:10 + 3 * pps]
    p = pl.program_id(0)

    @pl.when(flg_ref[p, 0] == 1)
    def _zero_dq():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(p == 0)
    def _zero_tables():
        dpt_ref[...] = jnp.zeros_like(dpt_ref)
        dtt_ref[...] = jnp.zeros_like(dtt_ref)

    i0 = wq_ref[p * pps] * bq     # destination: constant across the step
    for u in range(pps):
        @pl.when(live_ref[p * pps + u] == 1)
        def _compute(u=u):
            _q_block_compute(i0, wk_ref[p * pps + u] * bk,
                             qm_ref, qmf_ref, km_refs[u],
                             q_ref, k_refs[u], v_refs[u], dy_ref,
                             pt_ref, tt_ref, dq_acc, dpt_ref, dtt_ref,
                             bq=bq, bk=bk, H=H, scale=scale,
                             npb=npb, ntb=ntb, tb_scale=tb_scale,
                             use_pos=use_pos, use_time=use_time,
                             causal=causal,
                             time_functional=time_functional)

    @pl.when(flg_ref[p, 1] == 1)
    def _write():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


def bwd_pallas(q, k, v, dy, pos_table, time_table, meta_i32, meta_f32,
               seg_rng, *, block: int, scale: float, tb_scale: float,
               use_pos: bool, use_time: bool, causal: bool = True,
               time_functional: bool = False, interpret: bool = False):
    cap, H, D = q.shape
    nb = cap // block
    bq = bk = block
    ptT, tt = _tables(pos_table, time_table)
    qh, kh, vh, dyh = _heads_major(q, k, v, dy)
    skw = dict(scale=scale, tb_scale=tb_scale, use_pos=use_pos,
               use_time=use_time, causal=causal,
               time_functional=time_functional,
               **_static_kw(pos_table, time_table, H))
    blk3 = lambda sel: pl.BlockSpec((H, block, D),
                                    lambda i, j, *_: (0, sel(i, j), 0))
    first = lambda i, j: i
    second = lambda i, j: j
    tables = [pl.BlockSpec(ptT.shape, lambda i, j, *_: (0, 0)),
              _smem_spec()]

    kv_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb, nb),
        in_specs=[
            pl.BlockSpec((3, bk), lambda i, j, *_: (0, i)),    # k meta
            pl.BlockSpec((bq, 3), lambda i, j, *_: (j, 0)),    # q meta
            pl.BlockSpec((bq, 1), lambda i, j, *_: (j, 0)),    # q 1/n
            blk3(first), blk3(first),                          # k, v
            blk3(second), blk3(second),                        # q, dy
            *tables,
        ],
        out_specs=[blk3(first), blk3(first)],                  # dk, dv
        scratch_shapes=[pltpu.VMEM((H, bk, D), jnp.float32),
                        pltpu.VMEM((H, bk, D), jnp.float32)],
    )
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_kv_kernel, bq=bq, bk=bk, nqb=nb, **skw),
        name="attn_bwd_kv_dense",
        grid_spec=kv_spec,
        out_shape=[jax.ShapeDtypeStruct((H, cap, D), k.dtype),
                   jax.ShapeDtypeStruct((H, cap, D), v.dtype)],
        interpret=interpret,
        **_attn_cost(block, H, D, nb * nb, nb, 1, factor=2.0),
    )(seg_rng, meta_i32.T, meta_i32, meta_f32, kh, vh, qh, dyh, ptT, tt)

    q_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb, nb),
        in_specs=[
            pl.BlockSpec((bq, 3), lambda i, j, *_: (i, 0)),    # q meta
            pl.BlockSpec((bq, 1), lambda i, j, *_: (i, 0)),    # q 1/n
            pl.BlockSpec((3, bk), lambda i, j, *_: (0, j)),    # k meta
            blk3(first), blk3(second), blk3(second),           # q, k, v
            blk3(first),                                       # dy
            *tables,
        ],
        out_specs=[
            blk3(first),                                       # dq
            pl.BlockSpec(ptT.shape, lambda i, j, *_: (0, 0)),
            pl.BlockSpec((H, tt.shape[0]), lambda i, j, *_: (0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((H, bq, D), jnp.float32)],
    )
    dq, dptT, dttT = pl.pallas_call(
        functools.partial(_bwd_q_kernel, bq=bq, bk=bk, nkb=nb, **skw),
        name="attn_bwd_q_dense",
        grid_spec=q_spec,
        out_shape=[jax.ShapeDtypeStruct((H, cap, D), q.dtype),
                   jax.ShapeDtypeStruct(ptT.shape, jnp.float32),
                   jax.ShapeDtypeStruct((H, tt.shape[0]), jnp.float32)],
        interpret=interpret,
        **_attn_cost(block, H, D, nb * nb, nb, 1, factor=2.0),
    )(seg_rng, meta_i32, meta_f32, meta_i32.T, qh, kh, vh, dyh, ptT, tt)
    dq, dk, dv = _heads_major(dq, dk, dv)
    return dq, dk, dv, dptT.T, dttT.T


def bwd_pallas_wl(q, k, v, dy, pos_table, time_table, meta_i32, meta_f32,
                  q_wl, q_flags, q_live, kv_wl, kv_flags, kv_live, n_live,
                  *, block: int, scale: float, tb_scale: float,
                  use_pos: bool, use_time: bool, causal: bool = True,
                  time_functional: bool = False, interpret: bool = False):
    """Backward over compacted work-lists.

    q_wl (L, 2): live pairs (qb, kb) in q-block-major order (the forward
    list) with q_flags (S, 2) first/last per qb run and q_live (L,) entry
    mask — drives the dq kernel. kv_wl (L, 2): the same pairs in
    k-block-major order with kv_flags/kv_live per kb run — drives the
    dk/dv kernel. n_live: (1,) int32. pps = L // S entries per step.
    """
    cap, H, D = q.shape
    bq = bk = block
    nb = cap // block
    L, S, pps = _wl_shape(q_wl[:, 0], q_flags)
    qi, qj = q_wl[:, 0], q_wl[:, 1]
    kvi, kvj = kv_wl[:, 0], kv_wl[:, 1]
    ptT, tt = _tables(pos_table, time_table)
    qh, kh, vh, dyh = _heads_major(q, k, v, dy)
    skw = dict(scale=scale, tb_scale=tb_scale, use_pos=use_pos,
               use_time=use_time, causal=causal,
               time_functional=time_functional,
               **_static_kw(pos_table, time_table, H))

    # first prefetch arg = qb ids, second = kb ids in BOTH kernels; the
    # destination side is whichever is constant per run (kb for dk/dv)
    def at_q(u):
        return lambda p, wq, wk, flg, live, nl, u=u: (wq[p * pps + u], 0)

    def at_q3(u):
        return lambda p, wq, wk, flg, live, nl, u=u: (0, wq[p * pps + u], 0)

    def at_kt(u):
        return lambda p, wq, wk, flg, live, nl, u=u: (0, wk[p * pps + u])

    def at_k3(u):
        return lambda p, wq, wk, flg, live, nl, u=u: (0, wk[p * pps + u], 0)

    tables = [pl.BlockSpec(ptT.shape, lambda p, *_: (0, 0)), _smem_spec()]
    kv_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(S,),
        in_specs=[
            pl.BlockSpec((3, bk), at_kt(0)),                    # k meta
            *[pl.BlockSpec((bq, 3), at_q(u)) for u in range(pps)],
            *[pl.BlockSpec((bq, 1), at_q(u)) for u in range(pps)],
            pl.BlockSpec((H, bk, D), at_k3(0)),                 # k
            pl.BlockSpec((H, bk, D), at_k3(0)),                 # v
            *[pl.BlockSpec((H, bq, D), at_q3(u)) for u in range(pps)],
            *[pl.BlockSpec((H, bq, D), at_q3(u)) for u in range(pps)],
            *tables,
        ],
        out_specs=[
            pl.BlockSpec((H, bk, D), at_k3(0)),
            pl.BlockSpec((H, bk, D), at_k3(0)),
        ],
        scratch_shapes=[pltpu.VMEM((H, bk, D), jnp.float32),
                        pltpu.VMEM((H, bk, D), jnp.float32)],
    )
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_kv_kernel_wl, bq=bq, bk=bk, pps=pps, **skw),
        name="attn_bwd_kv",
        grid_spec=kv_spec,
        out_shape=[jax.ShapeDtypeStruct((H, cap, D), k.dtype),
                   jax.ShapeDtypeStruct((H, cap, D), v.dtype)],
        interpret=interpret,
        **_attn_cost(block, H, D, L, nb, pps, factor=2.0),
    )(kvi, kvj, kv_flags, kv_live, n_live, meta_i32.T,
      *([meta_i32] * pps), *([meta_f32] * pps), kh, vh,
      *([qh] * pps), *([dyh] * pps), ptT, tt)

    q_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(S,),
        in_specs=[
            pl.BlockSpec((bq, 3), at_q(0)),                     # q meta
            pl.BlockSpec((bq, 1), at_q(0)),                     # q 1/n
            *[pl.BlockSpec((3, bk), at_kt(u)) for u in range(pps)],
            pl.BlockSpec((H, bq, D), at_q3(0)),                 # q
            pl.BlockSpec((H, bq, D), at_q3(0)),                 # dy
            *[pl.BlockSpec((H, bk, D), at_k3(u)) for u in range(pps)],
            *[pl.BlockSpec((H, bk, D), at_k3(u)) for u in range(pps)],
            *tables,
        ],
        out_specs=[
            pl.BlockSpec((H, bq, D), at_q3(0)),
            pl.BlockSpec(ptT.shape, lambda p, *_: (0, 0)),
            pl.BlockSpec((H, tt.shape[0]), lambda p, *_: (0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((H, bq, D), jnp.float32)],
    )
    dq, dptT, dttT = pl.pallas_call(
        functools.partial(_bwd_q_kernel_wl, bq=bq, bk=bk, pps=pps, **skw),
        name="attn_bwd_q",
        grid_spec=q_spec,
        out_shape=[jax.ShapeDtypeStruct((H, cap, D), q.dtype),
                   jax.ShapeDtypeStruct(ptT.shape, jnp.float32),
                   jax.ShapeDtypeStruct((H, tt.shape[0]), jnp.float32)],
        interpret=interpret,
        **_attn_cost(block, H, D, L, nb, pps, factor=2.0),
    )(qi, qj, q_flags, q_live, n_live, meta_i32, meta_f32,
      *([meta_i32.T] * pps), qh, dyh, *([kh] * pps), *([vh] * pps),
      ptT, tt)
    dq, dk, dv = _heads_major(dq, dk, dv)
    return dq, dk, dv, dptT.T, dttT.T
