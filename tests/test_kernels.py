"""Per-kernel shape/dtype sweeps asserting allclose vs each ref.py oracle
(interpret=True executes the Pallas kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import RABConfig
from repro.kernels import autotune
from repro.kernels.jagged_attention import (jagged_attention,
                                            jagged_attention_ref)
from repro.kernels.jagged_lookup import (jagged_lookup, jagged_lookup_ref,
                                         multi_table_lookup,
                                         scatter_add_rows, scatter_add_ref)
from repro.kernels.jagged_lookup import kernel as LK
from repro.kernels.jagged_lookup.ops import _segment_totals
from repro.kernels.neg_logits import (fused_recall_lse, neg_logits,
                                      neg_logits_ref)
from repro.kernels.neg_logits import fused as NF
from repro.models.hstu import init_rab


def _mk_jagged(key, cap, lens, H, D, dtype):
    ks = jax.random.split(key, 4)
    offsets = jnp.asarray(np.concatenate([[0], np.cumsum(lens)]), jnp.int32)
    q = jax.random.normal(ks[0], (cap, H, D), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (cap, H, D), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (cap, H, D), jnp.float32).astype(dtype)
    ts = jnp.cumsum(jax.random.randint(ks[3], (cap,), 0, 500)).astype(jnp.int32)
    return q, k, v, offsets, ts


RAB = RABConfig(num_pos_buckets=64, num_time_buckets=16)


@pytest.mark.parametrize("cap,lens,H,D,block", [
    (256, [100, 60, 0, 40], 4, 32, 64),
    (256, [256], 2, 16, 128),            # one full row
    (128, [1, 1, 1, 1], 1, 8, 64),       # singleton rows
    (300, [120, 77], 4, 32, 64),         # cap not multiple of block (pad)
    (512, [200, 56, 128, 100], 8, 64, 128),
])
def test_jagged_attention_fwd_sweep(cap, lens, H, D, block):
    q, k, v, offsets, ts = _mk_jagged(jax.random.PRNGKey(0), cap, lens, H, D,
                                      jnp.float32)
    rp = init_rab(jax.random.PRNGKey(1), RAB, H)
    out = jagged_attention(q, k, v, offsets, ts, rp, RAB, block=block,
                           interpret=True)
    ref = jagged_attention_ref(q, k, v, offsets, ts, rp, RAB)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 0.05)])
def test_jagged_attention_dtypes(dtype, tol):
    q, k, v, offsets, ts = _mk_jagged(jax.random.PRNGKey(2), 256,
                                      [90, 70, 30], 4, 32, dtype)
    rp = init_rab(jax.random.PRNGKey(3), RAB, 4)
    out = jagged_attention(q, k, v, offsets, ts, rp, RAB, block=64,
                           interpret=True).astype(jnp.float32)
    ref = jagged_attention_ref(q, k, v, offsets, ts, rp,
                               RAB).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=tol, atol=tol)


def test_jagged_attention_grads_match_oracle():
    cap, H, D = 256, 4, 32
    q, k, v, offsets, ts = _mk_jagged(jax.random.PRNGKey(4), cap,
                                      [100, 60, 40], H, D, jnp.float32)
    rp = init_rab(jax.random.PRNGKey(5), RAB, H)

    def loss(fn):
        def inner(q, k, v, pt, tt):
            r = {"pos_table": pt, "time_table": tt}
            return jnp.sum(jnp.sin(fn(q, k, v, offsets, ts, r, RAB)))
        return inner

    ker = lambda *a, **kw: jagged_attention(*a, block=64, interpret=True, **kw)
    gk = jax.grad(loss(ker), argnums=(0, 1, 2, 3, 4))(
        q, k, v, rp["pos_table"], rp["time_table"])
    gr = jax.grad(loss(jagged_attention_ref), argnums=(0, 1, 2, 3, 4))(
        q, k, v, rp["pos_table"], rp["time_table"])
    for name, a, b in zip("q k v pos_table time_table".split(), gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4, err_msg=name)


def test_jagged_attention_block_skip_equivalence():
    """Different block sizes (different skip patterns) give identical out."""
    q, k, v, offsets, ts = _mk_jagged(jax.random.PRNGKey(6), 512,
                                      [64, 64, 64, 64, 128], 2, 16,
                                      jnp.float32)
    rp = init_rab(jax.random.PRNGKey(7), RAB, 2)
    o64 = jagged_attention(q, k, v, offsets, ts, rp, RAB, block=64,
                           interpret=True)
    o128 = jagged_attention(q, k, v, offsets, ts, rp, RAB, block=128,
                            interpret=True)
    np.testing.assert_allclose(np.asarray(o64), np.asarray(o128),
                               rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------------------
# jagged lookup
# --------------------------------------------------------------------------

@pytest.mark.parametrize("V,D,n", [(64, 8, 32), (100, 16, 64),
                                   (37, 128, 200), (1000, 64, 17)])
def test_lookup_fwd_sweep(V, D, n):
    table = jax.random.normal(jax.random.PRNGKey(0), (V, D), jnp.float32)
    ids = jax.random.randint(jax.random.PRNGKey(1), (n,), -3, V)
    out = jagged_lookup(table, ids, compute_dtype=jnp.float32,
                        interpret=True)
    ref = jagged_lookup_ref(table, ids, compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref))


def test_lookup_bwd_with_duplicates():
    V, D, n = 16, 8, 128   # heavy duplication — exercises run-sum kernel
    table = jax.random.normal(jax.random.PRNGKey(0), (V, D), jnp.float32)
    ids = jax.random.randint(jax.random.PRNGKey(1), (n,), -1, V)
    g = jax.grad(lambda t: jnp.sum(
        jnp.cos(jagged_lookup(t, ids, compute_dtype=jnp.float32,
                              interpret=True))))(table)
    gr = jax.grad(lambda t: jnp.sum(
        jnp.cos(jagged_lookup_ref(t, ids, compute_dtype=jnp.float32))))(table)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr), rtol=1e-5,
                               atol=1e-5)


def test_scatter_add_matches_ref():
    rng = np.random.default_rng(0)
    rows = jnp.asarray(rng.normal(size=(64, 8)).astype(np.float32))
    ids = jnp.asarray(rng.integers(-1, 20, 64).astype(np.int32))
    out = scatter_add_rows(rows, ids, 20, interpret=True)
    ref = scatter_add_ref(rows, ids, 20)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.float16])
def test_gather_kernel_half_width_tiles_bitwise(dtype):
    # rows are picked out of their 8/16-row HBM tile in VMEM: exact for
    # every dtype, including a table whose rows are not a tile multiple
    table = jax.random.normal(jax.random.PRNGKey(0), (100, 16)).astype(dtype)
    ids = jax.random.randint(jax.random.PRNGKey(1), (37,), 0, 100)
    out = LK.gather_pallas(table, ids, interpret=True)
    assert out.dtype == table.dtype
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(table[ids], np.float32))


def test_runsum_kernel_matches_segment_totals():
    rows = jax.random.normal(jax.random.PRNGKey(2), (37, 16))
    sids = jnp.sort(jax.random.randint(jax.random.PRNGKey(3), (37,), 0, 10))
    got = LK.runsum_pallas(rows, sids, interpret=True)
    want = _segment_totals(rows, sids)
    end = np.r_[np.asarray(sids[:-1] != sids[1:]), True]
    np.testing.assert_allclose(np.asarray(got)[end], np.asarray(want)[end],
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("slots_per_call", [1 << 15, 16, 8])
def test_weighted_scatter_kernel_matches_scatter_add(monkeypatch,
                                                     slots_per_call):
    # small chunks force runs and tiles to straddle kernel calls
    monkeypatch.setattr(LK, "SCATTER_SLOTS_PER_CALL", slots_per_call)
    T, R, D, V = 33, 3, 16, 50
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    w = jax.random.normal(ks[0], (T * R,))
    o = jax.random.normal(ks[1], (T, D))
    ids = jax.random.randint(ks[2], (T * R,), -2, V + 3)
    valid = (ids >= 0) & (ids < V)
    skey = jnp.where(valid, ids, 2 ** 30)
    order = jnp.argsort(skey)
    got = LK.weighted_runsum_scatter(o, w[order] * valid[order], skey[order],
                                     order // R, V, scale=0.7,
                                     interpret=True)
    want = jnp.zeros((V, D)).at[jnp.where(valid, ids, V)].add(
        w[:, None] * (o[jnp.arange(T * R) // R] * 0.7), mode="drop")
    np.testing.assert_allclose(np.asarray(got[:V]), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert not np.asarray(got[V]).any()       # the drop sink stays zero


def _sequential_scatter(o, w, sids, src, vocab, scale, walked):
    """float32 loop: ``w · (o[src] · scale)`` added to each row (row
    ``vocab`` the drop sink) in sorted-slot order, over ``walked`` slots."""
    acc = np.zeros((vocab + 1, o.shape[1]), np.float32)
    scale = np.float32(scale)
    for i in range(min(walked, len(sids))):
        d = min(int(sids[i]), vocab)
        acc[d] = acc[d] + w[i] * (o[src[i]] * scale)
    return acc


@pytest.mark.parametrize("slots_per_call,max_slots,in_flight,case", [
    (8, 8, 16, "random"),            # every block its own call
    (16, 8, 16, "random"),           # runs and tiles straddle blocks, calls
    (1 << 15, 8, 16, "random"),      # many blocks in one call
    (1 << 15, 8, 2, "random"),       # tile buffers reused two tiles on
    (1 << 15, 32, 16, "top_id"),     # rows V−1 and the sink share a tile
    (1 << 15, 8, 16, "dropped_block"),   # blocks of dropped slots only
    (1 << 15, 32, 16, "ragged"),     # 185 slots: not a multiple of S
    (16, 8, 16, "live"),             # the walk stops after live's calls
])
def test_weighted_scatter_bitwise_matches_sequential_loop(
        monkeypatch, slots_per_call, max_slots, in_flight, case):
    monkeypatch.setattr(LK, "SCATTER_SLOTS_PER_CALL", slots_per_call)
    monkeypatch.setattr(autotune, "WSCATTER_MAX_SLOTS", max_slots)
    monkeypatch.setattr(autotune, "WSCATTER_TILES_IN_FLIGHT", in_flight)
    T, R, D, V = 37, 5, 16, 203          # 26 destination tiles
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    w = np.asarray(jax.random.normal(ks[0], (T * R,)))
    o = np.asarray(jax.random.normal(ks[1], (T, D)))
    ids = np.array(jax.random.randint(ks[2], (T * R,), -2, V + 3))
    if case == "top_id":
        ids[::7] = V - 1
    elif case in ("dropped_block", "live"):
        ids[::5] = V
        ids[1::5] = 2 ** 30
        ids[2::5] = -1
    # dropped slots keyed at or above V, and weighted, so that the sink
    # shows which of them the walk visited
    key = np.where(ids < 0, 2 ** 30, ids).astype(np.int32)
    order = np.argsort(key, kind="stable")
    sids, src, ws = key[order], (order // R).astype(np.int32), w[order]
    live, walked = None, T * R
    if case == "live":
        n_live = int(np.sum(sids < V))
        live = jnp.int32(n_live)
        walked = -(-n_live // slots_per_call) * slots_per_call
        assert walked < T * R              # the walk stops short
    got = LK.weighted_runsum_scatter(jnp.asarray(o), jnp.asarray(ws),
                                     jnp.asarray(sids), jnp.asarray(src), V,
                                     scale=0.7, live=live, interpret=True)
    want = _sequential_scatter(o, ws, sids, src, V, 0.7, walked)
    assert np.array_equal(np.asarray(got), want)


def test_fused_neg_segment_groups_bitwise(monkeypatch):
    # SMEM bounds the ids per kernel call: groups of whole segments run as
    # separate calls, which must not change a bit of lse or its grads
    T, R, V, D, seg = 64, 4, 100, 16, 16
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    out = jax.random.normal(ks[0], (T, D))
    pos = jax.random.normal(ks[1], (T,))
    table = jax.random.normal(ks[2], (V, D))
    ids = jax.random.randint(ks[3], (T, R), 0, V)

    def run():
        return jax.value_and_grad(
            lambda o, t: jnp.sum(fused_recall_lse(o, pos, t, ids, segment=seg,
                                                  interpret=True)),
            argnums=(0, 1))(out, table)

    one = run()
    monkeypatch.setattr(NF, "IDS_PER_CALL", seg * R)     # one segment/call
    for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(run())):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_multi_table_lookup_table_major():
    k = jax.random.PRNGKey(0)
    t1 = jax.random.normal(k, (50, 16), jnp.float32)
    t2 = jax.random.normal(jax.random.PRNGKey(1), (30, 16), jnp.float32)
    i1 = jax.random.randint(jax.random.PRNGKey(2), (40,), 0, 50)
    i2 = jax.random.randint(jax.random.PRNGKey(3), (25,), 0, 30)
    o1, o2 = multi_table_lookup([t1, t2], [i1, i2],
                                compute_dtype=jnp.float32, interpret=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(t1)[np.asarray(i1)])
    np.testing.assert_allclose(np.asarray(o2), np.asarray(t2)[np.asarray(i2)])


# --------------------------------------------------------------------------
# negative logits
# --------------------------------------------------------------------------

@pytest.mark.parametrize("T,R,D,seg,dtype", [
    (96, 8, 16, 32, jnp.float32),
    (100, 4, 32, 32, jnp.float16),      # pad T to segment
    (128, 16, 64, 64, jnp.bfloat16),
    (64, 1, 8, 16, jnp.float32),
])
def test_neg_logits_sweep(T, R, D, seg, dtype):
    o = jax.random.normal(jax.random.PRNGKey(0), (T, D), jnp.float32)
    n = jax.random.normal(jax.random.PRNGKey(1), (T, R, D),
                          jnp.float32).astype(dtype)
    out = neg_logits(o, n, segment=seg, interpret=True)
    ref = neg_logits_ref(o, n)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_neg_logits_grads():
    T, R, D = 64, 8, 16
    o = jax.random.normal(jax.random.PRNGKey(0), (T, D), jnp.float32)
    n = jax.random.normal(jax.random.PRNGKey(1), (T, R, D), jnp.float32)
    f_k = lambda o_, n_: jnp.sum(jnp.sin(neg_logits(o_, n_, segment=16,
                                                    interpret=True)))
    f_r = lambda o_, n_: jnp.sum(jnp.sin(neg_logits_ref(o_, n_)))
    gk = jax.grad(f_k, argnums=(0, 1))(o, n)
    gr = jax.grad(f_r, argnums=(0, 1))(o, n)
    np.testing.assert_allclose(np.asarray(gk[0]), np.asarray(gr[0]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gk[1]), np.asarray(gr[1]),
                               rtol=1e-5, atol=1e-5)


def test_jagged_attention_functional_time_mode():
    """FuXi-γ exponential-power temporal encoder in-kernel (fwd + grads
    through the amp/σ/ρ transforms) vs the oracle."""
    rabf = RABConfig(num_pos_buckets=64, num_time_buckets=32)
    H, D, cap = 4, 32, 256
    offsets = jnp.asarray([0, 100, 160, 200], jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    q = jax.random.normal(ks[0], (cap, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (cap, H, D), jnp.float32)
    v = jax.random.normal(ks[2], (cap, H, D), jnp.float32)
    ts = jnp.cumsum(jax.random.randint(ks[3], (cap,), 1, 500)).astype(jnp.int32)
    rp = {"pos_table": jax.random.normal(ks[4], (64, H), jnp.float32) * 0.02,
          "time_amp": jnp.full((H,), 0.05, jnp.float32),
          "time_log_sigma": jnp.linspace(2.0, 8.0, H).astype(jnp.float32),
          "time_rho": jnp.linspace(-0.5, 0.5, H).astype(jnp.float32)}

    out_k = jagged_attention(q, k, v, offsets, ts, rp, rabf,
                             time_mode="functional", block=64,
                             interpret=True)
    out_r = jagged_attention_ref(q, k, v, offsets, ts, rp, rabf,
                                 time_mode="functional")
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=2e-5, atol=2e-5)

    def loss(fn):
        def inner(amp, ls, rho):
            r2 = {**rp, "time_amp": amp, "time_log_sigma": ls,
                  "time_rho": rho}
            return jnp.sum(jnp.sin(fn(q, k, v, offsets, ts, r2, rabf)))
        return inner

    ker = lambda *a, **kw: jagged_attention(*a, time_mode="functional",
                                            block=64, interpret=True, **kw)
    ref = lambda *a, **kw: jagged_attention_ref(*a, time_mode="functional",
                                                **kw)
    gk = jax.grad(loss(ker), argnums=(0, 1, 2))(
        rp["time_amp"], rp["time_log_sigma"], rp["time_rho"])
    gr = jax.grad(loss(ref), argnums=(0, 1, 2))(
        rp["time_amp"], rp["time_log_sigma"], rp["time_rho"])
    for name, a, b in zip("amp log_sigma rho".split(), gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6, err_msg=name)
