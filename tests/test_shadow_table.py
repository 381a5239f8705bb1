"""§4.3.2 persistent FP16 shadow table + sparse row-wise AdaGrad.

Covers the four contracts the subsystem guarantees:
  * shadow == master.astype(qdtype) after any number of sparse updates;
  * the sparse (id, row)-pair AdaGrad matches the dense Eq.-1 update
    exactly on touched rows and leaves untouched rows bit-identical;
  * the fused negative path gathering from the shadow matches the
    fp32-round emulation (values AND table grads, both impls);
  * checkpoints store a 0-row shadow placeholder and restore rebuilds it.
"""
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.core.negative_sampling import fused_sampled_softmax_loss
from repro.embedding import tables as ET
from repro.models.model_zoo import get_bundle
from repro.training import checkpoint as CKPT
from repro.training import optim as O
from repro.training.trainer import (gr_pending_slots, gr_train_state,
                                    make_gr_train_step)

V, D = 64, 16


def _rand_pairs(key, n, dup=True):
    ki, kr = jax.random.split(key)
    hi = V if dup else n
    ids = jax.random.randint(ki, (n,), 0, hi, dtype=jnp.int32)
    rows = jax.random.normal(kr, (n, D), jnp.float32)
    return ids, rows


def _table(key, qdtype=jnp.float16):
    master = jax.random.normal(key, (V, D), jnp.float32) * 0.1
    return ET.make_shadowed(master, qdtype=qdtype)


# --------------------------------------------------------------------------
# invariant + sparse/dense parity
# --------------------------------------------------------------------------

@pytest.mark.parametrize("qdtype", [jnp.float16, jnp.bfloat16])
def test_shadow_invariant_after_n_sparse_updates(qdtype):
    tbl = _table(jax.random.PRNGKey(0), qdtype)
    for i in range(5):
        ids, rows = _rand_pairs(jax.random.PRNGKey(i), 40)
        # mix in empty (-1) slots like the trainer's dedup sentinel
        ids = jnp.where(jnp.arange(40) % 7 == 0, -1, ids)
        tbl = O.adagrad_sparse_update(tbl, ids, rows, lr=0.05)
    assert bool(ET.shadow_consistent(tbl))
    np.testing.assert_array_equal(
        np.asarray(tbl.master.astype(qdtype), np.float32),
        np.asarray(tbl.shadow, np.float32))


def test_sparse_matches_dense_adagrad_on_touched_rows():
    tbl = _table(jax.random.PRNGKey(1))
    ids, rows = _rand_pairs(jax.random.PRNGKey(2), 48)
    # dense reference: scatter the pairs into a (V, D) grad, Eq.-1 update
    gt = np.zeros((V, D), np.float32)
    np.add.at(gt, np.asarray(ids), np.asarray(rows))
    dense_p, dense_st = O.adagrad_update(
        {"t": jnp.asarray(gt)}, O.AdaGradState(accum={"t": tbl.accum}),
        {"t": tbl.master}, lr=0.05)

    new = O.adagrad_sparse_update(tbl, ids, rows, lr=0.05)
    touched = np.unique(np.asarray(ids))
    np.testing.assert_allclose(np.asarray(new.master)[touched],
                               np.asarray(dense_p["t"])[touched],
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(new.accum)[touched],
                               np.asarray(dense_st.accum["t"])[touched],
                               rtol=1e-6, atol=1e-7)
    # untouched rows: bit-identical, shadow included
    untouched = np.setdiff1d(np.arange(V), touched)
    np.testing.assert_array_equal(np.asarray(new.master)[untouched],
                                  np.asarray(tbl.master)[untouched])
    np.testing.assert_array_equal(np.asarray(new.shadow)[untouched],
                                  np.asarray(tbl.shadow)[untouched])
    assert bool(ET.shadow_consistent(new))


def test_sparse_update_empty_and_out_of_range_ids_are_noops():
    tbl = _table(jax.random.PRNGKey(3))
    ids = jnp.asarray([-1, -1, V + 5, 2 ** 29], jnp.int32)
    rows = jnp.ones((4, D), jnp.float32)
    new = O.adagrad_sparse_update(tbl, ids, rows, lr=0.05)
    np.testing.assert_array_equal(np.asarray(new.master),
                                  np.asarray(tbl.master))
    np.testing.assert_array_equal(np.asarray(new.accum),
                                  np.asarray(tbl.accum))
    zero = O.adagrad_sparse_update(tbl, jnp.zeros((0,), jnp.int32),
                                   jnp.zeros((0, D), jnp.float32))
    assert zero is tbl


def test_sparse_update_sums_duplicate_ids():
    tbl = _table(jax.random.PRNGKey(4))
    ids = jnp.asarray([3, 3, 3, 9], jnp.int32)
    rows = jnp.stack([jnp.full((D,), 1.0), jnp.full((D,), 2.0),
                      jnp.full((D,), -0.5), jnp.full((D,), 4.0)])
    new = O.adagrad_sparse_update(tbl, ids, rows, lr=0.05)
    g3, g9 = 2.5, 4.0
    for rid, g in ((3, g3), (9, g9)):
        s = np.asarray(tbl.accum)[rid] + g * g
        want = (np.asarray(tbl.master)[rid]
                - 0.05 * g / np.sqrt(s + 1e-10))
        np.testing.assert_allclose(np.asarray(new.master)[rid], want,
                                   rtol=1e-6)


# --------------------------------------------------------------------------
# fused-path parity: shadow gather vs fp32-round emulation
# --------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_fused_shadow_matches_round_emulation(impl):
    key = jax.random.PRNGKey(5)
    ko, kp, kn, kt = jax.random.split(key, 4)
    T, R = 24, 4
    out = jax.random.normal(ko, (T, D), jnp.float32)
    pos = jax.random.normal(kp, (T, D), jnp.float32)
    neg = jax.random.randint(kn, (T, R), 0, V, dtype=jnp.int32)
    tbl = _table(kt)
    valid = jnp.arange(T) < T - 3

    def loss(master, shadow, fdt):
        return fused_sampled_softmax_loss(
            out, pos, master, neg, valid=valid, segment=8,
            fetch_dtype=fdt, shadow=shadow, impl=impl, interpret=True)

    # emulation: fp32 master rows rounded to fp16 at the fetch
    l_emu, g_emu = jax.value_and_grad(
        lambda m: loss(m, None, jnp.float16))(tbl.master)
    # shadow: real fp16 rows (invariant holds by construction)
    l_sh, g_sh = jax.value_and_grad(
        lambda m: loss(m, tbl.shadow, jnp.float32))(tbl.master)

    np.testing.assert_allclose(float(l_emu), float(l_sh), rtol=1e-2)
    np.testing.assert_allclose(np.asarray(g_emu), np.asarray(g_sh),
                               rtol=1e-2, atol=1e-2)
    # under the invariant the forward values are the same rounded rows —
    # the two paths should agree far tighter than the fp16 tolerance
    assert abs(float(l_emu) - float(l_sh)) < 1e-5


def test_fused_shadow_xla_pallas_interchangeable():
    key = jax.random.PRNGKey(6)
    ko, kp, kn, kt = jax.random.split(key, 4)
    T, R = 16, 4
    out = jax.random.normal(ko, (T, D), jnp.float32)
    pos = jax.random.normal(kp, (T, D), jnp.float32)
    neg = jax.random.randint(kn, (T, R), 0, V, dtype=jnp.int32)
    tbl = _table(kt)

    def loss(master, impl):
        return fused_sampled_softmax_loss(
            out, pos, master, neg, segment=8, shadow=tbl.shadow,
            impl=impl, interpret=True)

    lx, gx = jax.value_and_grad(lambda m: loss(m, "xla"))(tbl.master)
    lp, gp = jax.value_and_grad(lambda m: loss(m, "pallas"))(tbl.master)
    np.testing.assert_allclose(float(lx), float(lp), rtol=1e-5)
    # grads reduce through different fp32 orders (dense scatter-add vs
    # sorted run-sum) — a few-ulp spread on top of the fp16-rounded values
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gp),
                               rtol=1e-3, atol=1e-4)


# --------------------------------------------------------------------------
# trainer end to end: invariant through fused train steps (sync + τ=1)
# --------------------------------------------------------------------------

def _gr_fused_setup(semi_async):
    cfg = reduced(ARCHS["hstu-tiny"]).replace(num_negatives=8,
                                              vocab_size=512)
    b = get_bundle(cfg)
    key = jax.random.PRNGKey(0)

    def batch(i):
        k = jax.random.PRNGKey(i)
        G, cap = 2, 128
        return {
            "ids": jax.random.randint(k, (G, cap), 0, 512),
            "labels": jax.random.randint(k, (G, cap), 1, 512),
            "timestamps": jnp.cumsum(jax.random.randint(k, (G, cap), 0, 60),
                                     1).astype(jnp.int32),
            "offsets": jnp.asarray([[0, 64, 128], [0, 100, 120]], jnp.int32),
            "neg_ids": jax.random.randint(k, (G, cap, 8), 0, 512),
            "rng": jnp.zeros((2,), jnp.uint32),
        }

    state = gr_train_state(b.init_dense(key), b.init_table(key),
                           pending_slots=gr_pending_slots(batch(0)))
    step = jax.jit(make_gr_train_step(
        lambda d, t, bt, **kw: b.loss(d, t, bt, neg_mode="fused",
                                      neg_segment=32, **kw),
        semi_async=semi_async))
    return state, step, batch


@pytest.mark.parametrize("semi_async", [False, True])
def test_trainer_fused_shadow_invariant_and_descent(semi_async):
    state, step, batch = _gr_fused_setup(semi_async)
    assert state.table.shadow.dtype == ET.SHADOW_DTYPE
    losses = []
    for i in range(6):
        state, m = step(state, batch(i % 2))
        losses.append(float(m["loss"]))
    assert bool(ET.shadow_consistent(state.table)), \
        "shadow drifted from master after fused train steps"
    assert losses[-1] < losses[0], losses
    assert all(np.isfinite(l) for l in losses)


# --------------------------------------------------------------------------
# checkpoint round-trip
# --------------------------------------------------------------------------

def test_checkpoint_roundtrip_rebuilds_shadow():
    state, step, batch = _gr_fused_setup(True)
    state, _ = step(state, batch(0))
    state, _ = step(state, batch(1))
    with tempfile.TemporaryDirectory() as d:
        CKPT.save(d, 2, state._asdict())
        # the shadow must not be double-stored: its manifest entry is the
        # 0-row placeholder (dtype marker kept, bytes dropped)
        import os

        import msgpack
        with open(os.path.join(d, "step_2", "manifest.msgpack"), "rb") as f:
            manifest = msgpack.unpackb(f.read())
        V_, D_ = state.table.master.shape
        half_shapes = [tuple(s) for s, dt in zip(manifest["shapes"],
                                                 manifest["dtypes"])
                       if dt == "bfloat16"]
        assert (0, D_) in half_shapes
        assert (V_, D_) not in half_shapes
        got = CKPT.restore(d, state._asdict())
        tbl = got["table"]
        assert tbl.shadow.shape == state.table.master.shape
        np.testing.assert_array_equal(
            np.asarray(tbl.shadow, np.float32),
            np.asarray(tbl.master.astype(ET.SHADOW_DTYPE), np.float32))
        np.testing.assert_allclose(np.asarray(tbl.master),
                                   np.asarray(state.table.master))


def test_checkpoint_strip_keeps_leaf_count():
    tbl = _table(jax.random.PRNGKey(7))
    tree = {"table": tbl, "x": jnp.ones((3,))}
    stripped = CKPT._strip_shadows(tree)
    assert (len(jax.tree_util.tree_leaves(stripped))
            == len(jax.tree_util.tree_leaves(tree)))
    assert stripped["table"].shadow.shape[0] == 0
    rebuilt = CKPT._rebuild_shadows(stripped)
    np.testing.assert_array_equal(
        np.asarray(rebuilt["table"].shadow, np.float32),
        np.asarray(tbl.shadow, np.float32))


# --------------------------------------------------------------------------
# the packed layout: bf16 at a width the fused kernel gathers as words
# --------------------------------------------------------------------------

DP = 512                      # halves lane-aligned: (V, DP/256, 128) words


def _packed_table(key):
    master = jax.random.normal(key, (V, DP), jnp.float32) * 0.1
    tbl = ET.make_shadowed(master)
    assert tbl.shadow.shape == (V, DP // 256, 128)
    assert tbl.shadow.dtype == jnp.uint32
    return tbl


def test_packed_shadow_invariant_after_sparse_updates():
    tbl = _packed_table(jax.random.PRNGKey(20))
    for i in range(3):
        ki, kr = jax.random.split(jax.random.PRNGKey(30 + i))
        ids = jax.random.randint(ki, (40,), -1, V + 2, dtype=jnp.int32)
        rows = jax.random.normal(kr, (40, DP), jnp.float32)
        tbl = O.adagrad_sparse_update(tbl, ids, rows, lr=0.05)
    assert bool(ET.shadow_consistent(tbl))
    np.testing.assert_array_equal(
        np.asarray(ET.shadow_values(tbl.shadow), np.float32),
        np.asarray(tbl.master.astype(jnp.bfloat16), np.float32))


@pytest.mark.parametrize("packed", [True, False])
def test_shadow_landing_scatter_equals_rebuild(packed, monkeypatch):
    """The landing's two ways, the touched rows' scatter and the whole
    shadow made anew, leave the same bits, in both layouts."""
    tbl = (_packed_table(jax.random.PRNGKey(25)) if packed
           else _table(jax.random.PRNGKey(25), jnp.bfloat16))
    width = tbl.master.shape[1]
    ki, kr = jax.random.split(jax.random.PRNGKey(26))
    ids = jax.random.randint(ki, (12,), -1, V + 2, dtype=jnp.int32)
    rows = jax.random.normal(kr, (12, width), jnp.float32)
    got = {}
    for elems in (0, V * width):      # always scatter / always rebuild
        monkeypatch.setattr(O, "SHADOW_SCATTER_ROW_ELEMS", elems)
        got[elems] = O.adagrad_sparse_update(tbl, ids, rows, lr=0.05)
        assert bool(ET.shadow_consistent(got[elems]))
    for a, b in zip(got[0], got[V * width]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("packed", [True, False])
def test_aligned_landing_equals_the_sparse_pairs(packed):
    """A gradient laid out like the rows (an HSP chip's aggregate: most
    rows zero, empty slots < 0) lands in one pass with the bits that the
    sorted run sum and scatter of its non-zero rows leave."""
    tbl = (_packed_table(jax.random.PRNGKey(27)) if packed
           else _table(jax.random.PRNGKey(27), jnp.bfloat16))
    width = tbl.master.shape[1]
    ki, kr = jax.random.split(jax.random.PRNGKey(28))
    touched = jax.random.bernoulli(ki, 0.3, (V,))
    grad = jax.random.normal(kr, (V, width), jnp.float32) * touched[:, None]
    ids = jnp.where(jnp.arange(V) % 5 == 4, -1, jnp.arange(V, dtype=jnp.int32))
    got = O.adagrad_sparse_update(tbl, ids, grad, lr=0.05, aligned=True)
    keep = np.flatnonzero((np.asarray(ids) >= 0) & np.asarray(touched))
    want = O.adagrad_sparse_update(tbl, jnp.asarray(keep, jnp.int32),
                                   grad[keep], lr=0.05)
    assert 0 < keep.size < V
    assert bool(ET.shadow_consistent(got))
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_packed_shadow_checkpoint_strip_rebuild():
    tbl = _packed_table(jax.random.PRNGKey(21))
    stripped = CKPT._strip_shadows({"table": tbl})
    assert stripped["table"].shadow.shape == (0, DP // 256, 128)
    rebuilt = CKPT._rebuild_shadows(stripped)["table"]
    np.testing.assert_array_equal(np.asarray(rebuilt.shadow),
                                  np.asarray(tbl.shadow))


def test_packed_shadow_serving_scan_reads_bf16_rows():
    from repro.serving.retrieval import bytes_per_query, topk_blocked
    tbl = _packed_table(jax.random.PRNGKey(22))
    emb = jax.random.normal(jax.random.PRNGKey(23), (3, DP), jnp.float32)
    got = topk_blocked(emb, tbl.shadow, k=5, block_v=16)
    want = topk_blocked(emb, ET.shadow_values(tbl.shadow), k=5, block_v=16)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert bytes_per_query(tbl.shadow, 8) == V * DP * 2 / 8


def test_packed_shadow_cache_window_and_splice():
    from repro.embedding.cache import CachedShadowedTable
    master = np.asarray(jax.random.normal(jax.random.PRNGKey(24), (V, DP)),
                        np.float32)
    c = CachedShadowedTable(master, capacity_chunks=4, chunk_rows=8)
    c.warm_up(None)
    win = c.init_window()
    ids = np.array([0, 9, 63, 40, 41])            # chunks 0, 1, 7, 5
    plan, _ = c.prepare(0, np.unique(ids))
    win = c.splice(win, plan)
    rows = np.asarray(ET.shadow_values(win.shadow)[c.translate(ids)],
                      np.float32)
    np.testing.assert_array_equal(
        rows, np.asarray(jnp.asarray(master[ids]).astype(jnp.bfloat16),
                         np.float32))
    c.release(0, dirty=False)
