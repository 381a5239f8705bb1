"""Tuning-knob parity sweeps: every tuned schedule must be bitwise
interchangeable with the default (knob=1) schedule — the autotuner only
reorders work, it never changes the reduction order — and the grouped
work-list/scatter paths must keep their structural invariants.

Everything runs in interpret mode (tiny shapes: the interpreter pays
O(grid) dispatch)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.jagged_attention import ops as attn_ops
from repro.kernels.jagged_lookup.kernel import gather_pallas
from repro.kernels.jagged_lookup.ops import scatter_add_weighted_rows
from repro.embedding.tables import shadow_of
from repro.kernels.neg_logits import fused as NF
from repro.kernels.neg_logits.ops import (fused_recall_lse,
                                         prepare_fused_inputs)
from repro.kernels.neg_logits.ref import fused_recall_lse_ref


def _bitwise(a, b):
    assert a.dtype == b.dtype and a.shape == b.shape
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# lookup gather: rows_per_step
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rps", [2, 4, 8])
@pytest.mark.parametrize("n", [24, 37])          # odd tail: 37 % rps != 0
def test_gather_rows_per_step_bitwise(rps, n):
    table = jax.random.normal(jax.random.PRNGKey(0), (64, 16), jnp.float32)
    ids = jax.random.randint(jax.random.PRNGKey(1), (n,), 0, 64)
    base = gather_pallas(table, ids, rows_per_step=1, interpret=True)
    got = gather_pallas(table, ids, rows_per_step=rps, interpret=True)
    _bitwise(base, got)


# ---------------------------------------------------------------------------
# fused negative sampling: the token block + padding rows. The block is no
# knob of the wrappers (it is sized from the shapes), so these drive the
# kernels' entry points at each block; the first three tests keep the names
# they had under the rows_per_step knob the block replaced.
# ---------------------------------------------------------------------------

NEG_SHAPES = dict(T=44, R=4, V=256, D=16, seg=16)


def _neg_kernels(out, pos, table, ids, tb, *, segment, tau=1.0,
                 expansion=1, key=None, valid=None, shadow=None):
    """lse (T,) and the backward's (w, d_out, d_pos, table grad) at
    ``tokens_per_step=tb``, for an upstream gradient of ones."""
    T, R = ids.shape
    o, p, i, v, perms, n_seg = prepare_fused_inputs(
        out, pos, table, ids, segment=segment, expansion=expansion,
        key=key, valid=valid)
    words, fdt = NF.gather_source(table, shadow, None)
    col = lambda x: x.reshape(n_seg, segment)
    kw = dict(segment=segment, R=R, expansion=expansion, tau=tau,
              fetch_dtype=fdt, tokens_per_step=tb, interpret=True)
    lse = NF.fwd_pallas(o, col(p), words, i.reshape(-1), col(v), perms,
                        **kw)
    w, dout, dpos = NF.bwd_pallas(o, col(p), words, i.reshape(-1), col(v),
                                  perms, lse, jnp.ones_like(lse), **kw)
    dtbl = scatter_add_weighted_rows(w.reshape(-1, R), o, i.reshape(-1),
                                     table.shape[0], scale=1.0 / tau,
                                     interpret=True)
    return lse.reshape(-1)[:T], (w, dout, dpos, dtbl)


@pytest.mark.parametrize("rps", [2, 4, 8])       # T=44 is no multiple of 8
@pytest.mark.parametrize("expansion", [1, 2])
def test_fused_neg_rows_per_step_bitwise(rps, expansion):
    T, R, V, D, seg = (NEG_SHAPES[k] for k in ("T", "R", "V", "D", "seg"))
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    out = jax.random.normal(ks[0], (T, D), jnp.float32)
    pos = jax.random.normal(ks[1], (T,), jnp.float32)
    table = jax.random.normal(ks[2], (V, D), jnp.float32)
    ids = jax.random.randint(ks[3], (T, R), 0, V)
    valid = jnp.arange(T) < T - 7                # T=44 pads to 48: dead tail
    kw = dict(segment=seg, tau=0.8, expansion=expansion,
              key=ks[4] if expansion > 1 else None, valid=valid)
    base, _ = _neg_kernels(out, pos, table, ids, 1, **kw)
    got, _ = _neg_kernels(out, pos, table, ids, rps, **kw)
    _bitwise(base, got)
    # the wrapper's own block, sized from the shapes
    _bitwise(base, fused_recall_lse(out, pos, table, ids, interpret=True,
                                    **kw))
    ref = fused_recall_lse_ref(out, pos, table, ids, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_fused_neg_all_padding_segment():
    # whole trailing segments of invalid tokens must not disturb the
    # block gather (their clipped ids still index row 0 safely)
    T, R, V, D, seg = 40, 4, 128, 8, 8
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    out = jax.random.normal(ks[0], (T, D), jnp.float32)
    pos = jax.random.normal(ks[1], (T,), jnp.float32)
    table = jax.random.normal(ks[2], (V, D), jnp.float32)
    ids = jax.random.randint(ks[3], (T, R), 0, V)
    valid = jnp.arange(T) < 2 * seg              # segments 3..5 fully dead
    kw = dict(segment=seg, tau=1.0, valid=valid)
    base, gb = _neg_kernels(out, pos, table, ids, 1, **kw)
    got, gg = _neg_kernels(out, pos, table, ids, 8, **kw)
    for a, b in zip((base, *gb), (got, *gg)):
        _bitwise(a, b)


def test_fused_neg_grads_match_across_rps():
    T, R, V, D, seg = 32, 4, 128, 8, 8
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    out = jax.random.normal(ks[0], (T, D), jnp.float32)
    pos = jax.random.normal(ks[1], (T,), jnp.float32)
    table = jax.random.normal(ks[2], (V, D), jnp.float32)
    ids = jax.random.randint(ks[3], (T, R), 0, V)
    _, g1 = _neg_kernels(out, pos, table, ids, 1, segment=seg)
    _, g4 = _neg_kernels(out, pos, table, ids, 4, segment=seg)
    for a, b in zip(g1, g4):
        _bitwise(a, b)


@pytest.mark.parametrize("tb", [2, 8])
@pytest.mark.parametrize("expansion", [1, 4])
def test_fused_neg_packed_shadow_tokens_per_step_bitwise(tb, expansion):
    """The packed bf16-shadow path (D a multiple of 256): lse and every
    backward output bit-identical across token blocks."""
    T, R, V, D, seg = 36, 4, 64, 256, 16
    ks = jax.random.split(jax.random.PRNGKey(8), 5)
    out = jax.random.normal(ks[0], (T, D), jnp.float32)
    pos = jax.random.normal(ks[1], (T,), jnp.float32)
    master = jax.random.normal(ks[2], (V, D), jnp.float32)
    shadow = shadow_of(master, jnp.bfloat16)
    ids = jax.random.randint(ks[3], (T, R), 0, V)
    kw = dict(segment=seg, expansion=expansion, key=ks[4],
              valid=jnp.arange(T) < T - 5, shadow=shadow)
    lse1, g1 = _neg_kernels(out, pos, master, ids, 1, **kw)
    lse_t, g_t = _neg_kernels(out, pos, master, ids, tb, **kw)
    for a, b in zip((lse1, *g1), (lse_t, *g_t)):
        _bitwise(a, b)


# ---------------------------------------------------------------------------
# backward scatter: fused in-kernel row generation vs two-pass oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("T,R,D,V", [(64, 4, 16, 100), (33, 3, 8, 50)])
def test_scatter_fused_matches_two_pass(T, R, D, V):
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    w = jax.random.normal(ks[0], (T, R), jnp.float32)
    o = jax.random.normal(ks[1], (T, D), jnp.float32)
    # include out-of-range ids (dropped) among the destinations
    ids = jax.random.randint(ks[2], (T * R,), -2, V + 3).astype(jnp.int32)
    a = scatter_add_weighted_rows(w, o, ids, V, scale=0.7, impl="fused",
                                  interpret=True)
    b = scatter_add_weighted_rows(w, o, ids, V, scale=0.7, impl="two_pass",
                                  interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-6)
    assert a.shape == (V, D)


# ---------------------------------------------------------------------------
# attention work-list: pairs_per_step plan invariants + bitwise parity
# ---------------------------------------------------------------------------

def _mk_attn(lens, H=2, D=16, extra=4, seed=0):
    lens = np.asarray(lens, np.int64)
    cap = int(lens.sum()) + extra
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    offsets = jnp.asarray(np.concatenate([[0], np.cumsum(lens)]), jnp.int32)
    q = jax.random.normal(ks[0], (cap, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (cap, H, D), jnp.float32)
    v = jax.random.normal(ks[2], (cap, H, D), jnp.float32)
    ts = jnp.cumsum(jax.random.randint(ks[3], (cap,), 1, 500)).astype(
        jnp.int32)
    return q, k, v, offsets, ts, cap


@pytest.mark.parametrize("pps", [2, 4])
@pytest.mark.parametrize("kv_major", [False, True])
def test_plan_grouping_invariants(pps, kv_major):
    lens = [5, 13, 3, 21, 1, 9]
    block = 8
    _, _, _, offsets, ts, cap = _mk_attn(lens)
    plan = attn_ops.build_attn_plan(offsets, ts, cap, block=block,
                                    max_row_len=max(lens),
                                    pairs_per_step=pps)
    wl = np.asarray(plan.kv_wl if kv_major else plan.q_wl)
    flags = np.asarray(plan.kv_flags if kv_major else plan.q_flags)
    live = np.asarray(plan.kv_live if kv_major else plan.q_live)
    L = wl.shape[0]
    assert L % pps == 0 and flags.shape[0] == L // pps
    assert plan.pairs_per_step == pps
    dest = wl[:, 1] if kv_major else wl[:, 0]
    # every grid step covers ONE destination block: dest is constant
    # within each pps-group (runs start on pps boundaries by padding)
    assert (dest.reshape(-1, pps) == dest.reshape(-1, pps)[:, :1]).all()
    # destination order is nondecreasing step to step
    assert (np.diff(dest.reshape(-1, pps)[:, 0]) >= 0).all()
    # dead fill entries replicate a live entry of the same run: the live
    # mask marks exactly n_live entries
    assert int(live.sum()) == int(plan.n_live[0])
    # flags mark first/last step of each destination run
    sd = dest.reshape(-1, pps)[:, 0]
    first = np.concatenate([[1], (sd[1:] != sd[:-1]).astype(np.int64)])
    last = np.concatenate([(sd[1:] != sd[:-1]).astype(np.int64), [1]])
    assert (flags[:, 0] == first).all() and (flags[:, 1] == last).all()


def test_plan_pps1_matches_default_bitwise():
    lens = [5, 13, 3, 21]
    _, _, _, offsets, ts, cap = _mk_attn(lens)
    a = attn_ops.build_attn_plan(offsets, ts, cap, block=8,
                                 max_row_len=max(lens), pairs_per_step=1)
    b = attn_ops.build_attn_plan(offsets, ts, cap, block=8,
                                 max_row_len=max(lens))
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("pps", [2, 4])
def test_attention_pairs_per_step_bitwise(pps):
    lens = [5, 13, 3, 21, 1, 9]      # odd tails + singleton row
    block = 8
    q, k, v, offsets, ts, cap = _mk_attn(lens)

    def run(pps_):
        plan = attn_ops.build_attn_plan(offsets, ts, cap, block=block,
                                        max_row_len=max(lens),
                                        pairs_per_step=pps_)

        def loss(q, k, v):
            out = attn_ops.jagged_attention(
                q, k, v, offsets, ts, {}, None, block=block, plan=plan,
                max_row_len=max(lens), interpret=True)
            return jnp.sum(out * out), out

        (l, out), g = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                         has_aux=True)(q, k, v)
        return l, out, g

    l1, o1, g1 = run(1)
    lp, op, gp = run(pps)
    _bitwise(o1, op)
    _bitwise(l1, lp)
    for a, b in zip(g1, gp):
        _bitwise(a, b)
    # grouping strictly shrinks the grid on this jagged regime
    p1 = attn_ops.build_attn_plan(offsets, ts, cap, block=block,
                                  max_row_len=max(lens), pairs_per_step=1)
    pg = attn_ops.build_attn_plan(offsets, ts, cap, block=block,
                                  max_row_len=max(lens), pairs_per_step=pps)
    assert pg.num_steps < p1.num_steps


def test_attention_all_padding_rows():
    # zero-length rows only: the plan has no live pairs and the kernel
    # must still produce a well-formed (zero) output at any pps
    lens = [0, 0, 0]
    block = 8
    q, k, v, offsets, ts, cap = _mk_attn(lens, extra=16)
    outs = []
    for pps in (1, 4):
        plan = attn_ops.build_attn_plan(offsets, ts, cap, block=block,
                                        max_row_len=8, pairs_per_step=pps)
        out = attn_ops.jagged_attention(q, k, v, offsets, ts, {}, None,
                                        block=block, plan=plan,
                                        max_row_len=8, interpret=True)
        outs.append(out)
        assert bool(jnp.all(out == 0.0))
    _bitwise(outs[0], outs[1])
