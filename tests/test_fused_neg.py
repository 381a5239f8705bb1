"""Fused ID-driven negative-sampling megakernel: parity + property tests.

The Pallas kernel runs in interpret mode (kernel bodies execute on CPU);
the XLA twin must match it bit-for-bit so the two are interchangeable
mid-training. The materialized oracle (`fused_recall_lse_ref`) and the
composed baseline (`neg_logits_baseline` + `sampled_softmax_loss`) anchor
the numerics to the pre-fusion paths.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import negative_sampling as NS
from repro.kernels.neg_logits import (fused_recall_lse,
                                      fused_recall_lse_ref,
                                      make_share_perms)


def _setup(T=64, R=8, D=16, V=100, seed=0, table_dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    out = jax.random.normal(ks[0], (T, D), jnp.float32)
    table = jax.random.normal(ks[1], (V, D), jnp.float32).astype(table_dtype)
    ids = jax.random.randint(ks[2], (T, R), 0, V)
    pos = jax.random.normal(ks[3], (T,), jnp.float32)
    return out, table, ids, pos


KEY = jax.random.PRNGKey(11)


@pytest.mark.parametrize("T,R,D,seg,expansion,table_dtype,fetch", [
    (64, 8, 16, 16, 1, jnp.float32, None),
    (50, 4, 16, 16, 1, jnp.float32, None),          # odd segment tail
    (64, 8, 16, 16, 2, jnp.float32, None),          # logit sharing k=2
    (70, 4, 32, 32, 3, jnp.float32, None),          # k=3 + odd tail
    (64, 8, 16, 16, 2, jnp.float16, None),          # fp16-STORED table
    (64, 8, 16, 16, 2, jnp.bfloat16, None),         # bf16-stored table
    (64, 8, 16, 16, 1, jnp.float32, jnp.float16),   # fp16 fetch emulation
    (33, 2, 8, 16, 2, jnp.float32, jnp.float16),    # everything at once
])
def test_fused_fwd_matches_oracle(T, R, D, seg, expansion, table_dtype,
                                  fetch):
    out, table, ids, pos = _setup(T, R, D, table_dtype=table_dtype)
    valid = jnp.arange(T) < (T - 3)
    kw = dict(segment=seg, expansion=expansion, key=KEY, valid=valid,
              fetch_dtype=fetch)
    ker = fused_recall_lse(out, pos, table, ids, interpret=True, **kw)
    ref = fused_recall_lse_ref(out, pos, table, ids, **kw)
    xla = NS.fused_recall_lse_xla(out, pos, table, ids, **kw)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(xla), np.asarray(ker),
                               rtol=1e-6, atol=1e-6)


def _row_dma_case(T=44, R=4, D=256, V=96, seg=16, seed=5):
    """Ids that probe the row DMA: every offset within a 16-row tile, a
    repeated id inside one token block, id V−1; T=44 pads to 48 (no
    multiple of the 8-token block) and tokens 16.. are padding, so the
    last two segments are all padding."""
    out, master, ids, pos = _setup(T, R, D, V, seed=seed)
    ids = np.asarray(ids).copy()
    ids[:4] = (np.arange(16) + 32).reshape(4, R)     # offsets 0..15
    ids[4, 1] = ids[4, 0]                            # repeat within a token
    ids[5, 2] = ids[4, 0]                            # ... and a block
    ids[6, 3] = V - 1
    valid = jnp.arange(T) < seg
    return out, master, jnp.asarray(ids), pos, valid


@pytest.mark.parametrize("expansion", [1, 4])
@pytest.mark.parametrize("source", ["stored_shadow", "bf16_shadow",
                                    "fp32_master_fetch"])
def test_fused_row_dma_matches_oracle(expansion, source):
    """Forward, d_out, d_pos and the table gradient against the oracle,
    for the shadow as training stores it (packed words), an unpacked bf16
    shadow (read as master rows rounded in VMEM), and the fp32-master
    fetch path."""
    from repro.embedding.tables import shadow_of
    out, master, ids, pos, valid = _row_dma_case()
    shadow = master.astype(jnp.bfloat16)
    kw = dict(segment=16, expansion=expansion, key=KEY, valid=valid)
    if source == "stored_shadow":
        ker_kw = dict(gather_table=shadow_of(master, jnp.bfloat16),
                      fetch_dtype=jnp.bfloat16)
        xla = NS.fused_recall_lse_xla(out, pos, master, ids, **kw, **ker_kw)
    elif source == "bf16_shadow":
        ker_kw = dict(gather_table=shadow, fetch_dtype=jnp.bfloat16)
    else:
        ker_kw = dict(fetch_dtype=jnp.bfloat16)
    vf = valid.astype(jnp.float32)

    def loss_k(o, t, p):
        lse = fused_recall_lse(o, p, t, ids, interpret=True, **kw,
                               **ker_kw)
        return jnp.sum((lse - p) * vf), lse

    def loss_r(o, t, p):
        # the bf16 rows as an fp32 table: exact rows, fp32 cotangents
        lse = fused_recall_lse_ref(o, p, t, ids, **kw)
        return jnp.sum((lse - p) * vf), lse

    (_, lse_k), gk = jax.value_and_grad(loss_k, argnums=(0, 1, 2),
                                        has_aux=True)(out, master, pos)
    (_, lse_r), gr = jax.value_and_grad(loss_r, argnums=(0, 1, 2),
                                        has_aux=True)(
        out, shadow.astype(jnp.float32), pos)
    np.testing.assert_allclose(np.asarray(lse_k), np.asarray(lse_r),
                               rtol=1e-5, atol=1e-5)
    if source == "stored_shadow":
        np.testing.assert_allclose(np.asarray(xla), np.asarray(lse_k),
                                   rtol=1e-6, atol=1e-6)
    for name, a, b in zip(["d_out", "table", "d_pos"], gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5, err_msg=name)


@pytest.mark.parametrize("dtype,D,path,bytes_", [
    (jnp.bfloat16, 256, "packed_row", 512),   # two elements a word
    (jnp.bfloat16, 16, "row", 64),            # halves not lane-aligned
    (jnp.float32, 16, "row", 64),
    (jnp.float16, 256, "row", 1024),          # only bf16 is packed
    (None, 16, "row", 64),                    # no shadow: master rows
])
def test_gather_counter_reports_bytes_per_row(dtype, D, path, bytes_):
    from repro.embedding.tables import shadow_of
    from repro.obs import KERNEL_METRICS
    out, master, ids, pos = _setup(T=16, R=2, D=D)
    gauge = KERNEL_METRICS.gauge("neg_gather_bytes_per_row",
                                 labels={"path": path})
    gauge.set(0)
    jax.eval_shape(lambda o, t: fused_recall_lse(
        o, pos, t, ids, segment=8, interpret=True,
        gather_table=None if dtype is None else shadow_of(t, dtype)),
        out, master)
    assert gauge.value == bytes_


def test_fused_expansion1_equals_composed_baseline():
    """k=1 fused loss ≡ neg_logits_baseline + sampled_softmax_loss."""
    out, table, ids, _ = _setup(T=48, R=8, D=16)
    pos_ids = jax.random.randint(jax.random.PRNGKey(9), (48,), 0, 100)
    pos_emb = jnp.take(table, pos_ids, axis=0)
    valid = jnp.arange(48) < 40

    fused = NS.fused_sampled_softmax_loss(out, pos_emb, table, ids,
                                          valid=valid, segment=16,
                                          fetch_dtype=None, impl="pallas",
                                          interpret=True)
    neg = NS.neg_logits_baseline(out, jnp.take(table, ids, axis=0))
    composed = NS.recall_loss(out, pos_emb, neg, valid=valid)
    np.testing.assert_allclose(float(fused), float(composed),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("fetch,rtol", [(None, 1e-5), (jnp.float16, 1e-2)])
def test_fused_loss_vs_materialized_baseline_tolerance(fetch, rtol):
    """Acceptance bound: ≤1e-5 rel err at fp32 fetch, ≤1e-2 at fp16."""
    out, table, ids, _ = _setup(T=64, R=8, D=64)
    pos_emb = jnp.take(table, jax.random.randint(
        jax.random.PRNGKey(3), (64,), 0, 100), axis=0)
    fused = NS.fused_sampled_softmax_loss(out, pos_emb, table, ids,
                                          segment=16, fetch_dtype=fetch,
                                          impl="pallas", interpret=True)
    neg = NS.neg_logits_baseline(out, jnp.take(table, ids, axis=0))
    base = NS.recall_loss(out, pos_emb, neg)
    assert abs(float(fused) - float(base)) / abs(float(base)) < rtol


@pytest.mark.parametrize("expansion,table_dtype,fetch,tol", [
    (1, jnp.float32, None, 1e-5),
    (3, jnp.float32, None, 1e-5),
    # half-precision cases: the oracle's autodiff rounds per-row cotangents
    # through the fp16 cast while the kernel accumulates fp32 throughout,
    # so parity is fp16-ulp, not fp32-ulp.
    (2, jnp.float16, None, 2e-3),       # fp16-stored: grads vs same-store ref
    (2, jnp.float32, jnp.float16, 2e-3),
])
def test_fused_grads_match_oracle(expansion, table_dtype, fetch, tol):
    T, R, D, seg = 50, 4, 16, 16
    out, table, ids, pos = _setup(T, R, D, table_dtype=table_dtype)
    valid = jnp.arange(T) < 45
    vsum = float(valid.sum())
    kw = dict(segment=seg, expansion=expansion, key=KEY, valid=valid,
              fetch_dtype=fetch)

    def masked_nll(lse, p):
        return jnp.sum((lse - p) * valid.astype(jnp.float32)) / vsum

    def loss_k(o, t, p):
        return masked_nll(fused_recall_lse(o, p, t, ids, interpret=True,
                                           **kw), p)

    def loss_r(o, t, p):
        return masked_nll(fused_recall_lse_ref(o, p, t, ids, **kw), p)

    gk = jax.grad(loss_k, argnums=(0, 1, 2))(out, table, pos)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(out, table, pos)
    for name, a, b in zip("out table pos".split(), gk, gr):
        np.testing.assert_allclose(np.asarray(a, dtype=np.float32),
                                   np.asarray(b, dtype=np.float32),
                                   rtol=tol, atol=tol, err_msg=name)


def test_fused_grads_match_composed_baseline():
    """Full-path gradient parity vs baseline+sampled_softmax at k=1."""
    out, table, ids, _ = _setup(T=48, R=8, D=16)
    pos_ids = jax.random.randint(jax.random.PRNGKey(9), (48,), 0, 100)
    valid = jnp.arange(48) < 40

    def loss_fused(o, t):
        return NS.fused_sampled_softmax_loss(
            o, jnp.take(t, pos_ids, axis=0), t, ids, valid=valid,
            segment=16, fetch_dtype=None, impl="pallas", interpret=True)

    def loss_base(o, t):
        neg = NS.neg_logits_baseline(o, jnp.take(t, ids, axis=0))
        return NS.recall_loss(o, jnp.take(t, pos_ids, axis=0), neg,
                              valid=valid)

    gk = jax.grad(loss_fused, argnums=(0, 1))(out, table)
    gb = jax.grad(loss_base, argnums=(0, 1))(out, table)
    np.testing.assert_allclose(np.asarray(gk[0]), np.asarray(gb[0]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gk[1]), np.asarray(gb[1]),
                               rtol=1e-5, atol=1e-5)


def test_fused_xla_grads_match_pallas():
    out, table, ids, pos = _setup(T=50, R=4, D=16)
    valid = jnp.arange(50) < 45
    kw = dict(segment=16, expansion=2, key=KEY, valid=valid,
              fetch_dtype=jnp.float16)

    def nll(lse, p):
        v = valid.astype(jnp.float32)
        return jnp.sum((lse - p) * v) / jnp.sum(v)

    g_p = jax.grad(lambda o, t, p: nll(
        fused_recall_lse(o, p, t, ids, interpret=True, **kw), p),
        argnums=(0, 1, 2))(out, table, pos)
    g_x = jax.grad(lambda o, t, p: nll(
        NS.fused_recall_lse_xla(o, p, t, ids, **kw), p),
        argnums=(0, 1, 2))(out, table, pos)
    for name, a, b in zip("out table pos".split(), g_p, g_x):
        # fp16 fetch: XLA autodiff rounds row cotangents at the cast, the
        # kernel path stays fp32 — agreement is fp16-ulp.
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3, err_msg=name)


def test_fused_sharing_grows_lse():
    """Expansion slots add strictly positive mass to the softmax
    denominator on top of the k=1 terms, so lse_k ≥ lse_1 for every token.
    (Different k draw different shuffles, so only the k=1 set is nested.)"""
    out, table, ids, pos = _setup(T=32, R=4, D=16)
    base = fused_recall_lse(out, pos, table, ids, segment=16,
                            expansion=1, key=KEY, interpret=True)
    for k in (2, 4):
        lse = fused_recall_lse(out, pos, table, ids, segment=16,
                               expansion=k, key=KEY, interpret=True)
        assert bool(jnp.all(lse >= base - 1e-6))


def test_fused_invalid_tokens_never_pollute_pool():
    """Crank an invalid token's embedding to huge values: with the valid
    mask the shared pool must be unaffected."""
    out, table, ids, pos = _setup(T=32, R=4, D=16)
    valid = jnp.arange(32) < 30
    spiked = out.at[31].set(1e4)
    kw = dict(segment=16, expansion=2, key=KEY, valid=valid)
    clean = fused_recall_lse(out, pos, table, ids, interpret=True, **kw)
    dirty = fused_recall_lse(spiked, pos, table, ids, interpret=True, **kw)
    np.testing.assert_allclose(np.asarray(clean[:30]),
                               np.asarray(dirty[:30]), rtol=1e-6)


def test_make_share_perms_never_identity():
    perms = make_share_perms(jax.random.PRNGKey(0), n_seg=7, segment=32,
                             expansion=4)
    assert perms.shape == (7, 3, 32)
    t = np.arange(32)
    p = np.asarray(perms)
    assert (p != t[None, None, :]).all(), "a token must not borrow itself"
    for s in range(7):
        for e in range(3):
            assert sorted(p[s, e].tolist()) == list(t), "must be a permutation"


def test_fused_bundle_loss_smoke():
    """GRBundle.loss neg_mode='fused' end-to-end under jit + grad."""
    from repro.configs import ARCHS, reduced
    from repro.models.model_zoo import GRBundle

    cfg = reduced(ARCHS["fuxi-tiny"]).replace(vocab_size=200,
                                              num_negatives=4,
                                              max_seq_len=16)
    b = GRBundle(cfg)
    key = jax.random.PRNGKey(0)
    dense = b.init_dense(key)
    table = b.init_table(key)
    G, cap = 2, 32
    batch = {
        "ids": jax.random.randint(key, (G, cap), 0, 200),
        "labels": jax.random.randint(key, (G, cap), 0, 200),
        "timestamps": jnp.cumsum(jnp.ones((G, cap), jnp.int32), axis=1),
        "offsets": jnp.asarray([[0, 10, 24], [0, 16, 30]], jnp.int32),
        "neg_ids": jax.random.randint(key, (G, cap, 4), 0, 200),
        "rng": jnp.asarray([7, 0], jnp.uint32),
    }

    def loss(d, t):
        return b.loss(d, t, batch, neg_mode="fused", expansion=2,
                      neg_segment=16)

    l, (gd, gt) = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(
        dense, table)
    assert np.isfinite(float(l))
    assert float(jnp.abs(gt).sum()) > 0
    assert all(np.isfinite(np.asarray(x, np.float32)).all()
               for x in jax.tree.leaves(gd))
