"""Host-offloaded embedding cache: chunk-manager properties + engine
bit-identity (tentpole of the §4.3.1 HBM-ceiling work).

Deterministic unit + engine-level identity tests; the hypothesis
property tests over the chunk manager live in
tests/test_cache_properties.py (importorskip-guarded).
"""
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.data.freq import batch_id_histogram, stream_id_histogram
from repro.data.synthetic import synth_jagged_batch
from repro.embedding import tables as ET
from repro.embedding.cache import CachedShadowedTable, CacheThrash
from repro.models.model_zoo import get_bundle
from repro.training import checkpoint as CKPT
from repro.training.engine import GREngine, make_gr_step_fn
from repro.training.trainer import (gr_pending_slots, gr_train_state,
                                    host_unique_candidates)

def _mk_cache(vocab=96, dim=3, chunk_rows=8, capacity=4, seed=0,
              accum=False):
    rng = np.random.default_rng(seed)
    master = rng.normal(size=(vocab, dim)).astype(np.float32)
    acc = (rng.random((vocab, dim)).astype(np.float32) if accum else None)
    return CachedShadowedTable(master, capacity_chunks=capacity,
                               chunk_rows=chunk_rows, accum=acc), master


# -- satellite: counts out of the unique sort ------------------------------

def test_host_unique_candidates_counts_match_np_unique():
    rng = np.random.default_rng(3)
    batch = {"ids": rng.integers(-4, 40, (2, 16)),
             "labels": rng.integers(0, 40, (2, 16)),
             "neg_ids": rng.integers(0, 60, (2, 16, 4))}
    s, first, counts = host_unique_candidates(batch, 32)
    want_ids, want_counts = np.unique(
        np.clip(np.concatenate([batch["ids"].reshape(-1),
                                batch["labels"].reshape(-1),
                                batch["neg_ids"].reshape(-1)]), 0, 31),
        return_counts=True)
    np.testing.assert_array_equal(s[first], want_ids)
    np.testing.assert_array_equal(counts[first], want_counts)
    assert counts.sum() == s.size       # run lengths partition the sort
    assert (counts[~first] == 0).all()


def test_batch_id_histogram_counts_all_id_features():
    batch = {"ids": np.array([[0, 1, 1]]), "labels": np.array([[2, 9]]),
             "neg_ids": np.array([[-7, 3]]), "offsets": np.array([[0, 3]])}
    h = batch_id_histogram(batch, 8)
    np.testing.assert_array_equal(h, [2, 2, 1, 1, 0, 0, 0, 1])
    h2 = stream_id_histogram([batch, batch], 8)
    np.testing.assert_array_equal(h2, 2 * h)


# -- chunk-manager unit behaviour ------------------------------------------

def test_warm_up_admits_hottest_chunks():
    c, _ = _mk_cache(vocab=96, chunk_rows=8, capacity=4)   # 12 chunks
    hist = np.zeros(96, np.int64)
    for chunk, w in ((11, 50), (2, 40), (7, 30), (5, 20), (0, 10)):
        hist[chunk * 8] = w
    admitted = c.warm_up(hist)
    np.testing.assert_array_equal(admitted, [2, 5, 7, 11])
    np.testing.assert_array_equal(c.resident_chunks(), [2, 5, 7, 11])


def test_cache_thrash_when_batch_exceeds_capacity():
    c, _ = _mk_cache(vocab=96, chunk_rows=8, capacity=2)
    c.warm_up(None)
    c.init_window()
    with pytest.raises(CacheThrash):
        c.prepare(0, np.array([0, 8, 16]))   # 3 chunks, capacity 2


def test_defer_release_holds_single_pending_batch():
    c, _ = _mk_cache()
    c.warm_up(None)
    c.init_window()
    c.prepare(0, np.array([0, 1]))
    c.prepare(1, np.array([8]))
    c.defer_release(0)
    with pytest.raises(RuntimeError):
        c.defer_release(1)
    c.release_pending()                     # lands batch 0's pairs
    assert c.dirty[0]
    c.release(1, dirty=False)
    assert not c.dirty[1]
    assert (c.pins == 0).all()


def test_row_sparse_writeback_reduces_bytes_bit_identically():
    """Satellite: eviction writeback copies only the rows the batch's
    sparse updates touched — the D2H byte count drops with touch sparsity
    while the reassembled host master stays bit-identical."""
    c, master = _mk_cache(vocab=96, chunk_rows=8, capacity=2)
    c.warm_up(None)                         # chunks 0, 1 resident
    win = c.init_window()
    # batch touches 2 of chunk 0's 8 rows (+ 1 row of chunk 1)
    touched = np.array([1, 5, 9])
    c.prepare(0, touched)
    # simulate the sparse landing: mutate exactly the touched window rows
    rows = c.translate(touched)
    new_vals = np.arange(rows.size * c.dim, dtype=np.float32
                         ).reshape(rows.size, c.dim)
    win = ET.ShadowedTable(
        master=win.master.at[jnp.asarray(rows)].set(jnp.asarray(new_vals)),
        shadow=win.shadow, accum=win.accum)
    c.publish(win)
    c.release(0, dirty=True)
    # evict chunk 0 by preparing a batch needing both free-less slots
    before = dict(c.counters())
    c.prepare(1, np.array([16, 24]))        # chunks 2, 3 → evict 0 and 1
    after = dict(c.counters())
    # only the 3 touched rows crossed D2H, not 2 full chunks (16 rows)
    assert after["writeback_rows_total"] - before["writeback_rows_total"] == 16
    assert after["writeback_rows_dirty"] - before["writeback_rows_dirty"] == 3
    row_bytes = 2 * c.dim * 4               # master + accum fp32
    assert (after["swap_out_bytes"] - before["swap_out_bytes"]
            == 3 * row_bytes)
    # ...and the host master is exactly what a full-chunk writeback
    # would have produced: touched rows updated, the rest untouched
    want = master.copy()
    want[touched] = new_vals
    np.testing.assert_array_equal(c.host_master[:96], want)
    c.release(1, dirty=False)


def test_writeback_without_touch_record_is_whole_chunk():
    """A dirty chunk with no recorded touch set (crash recovery) falls
    back to conservative whole-chunk writeback."""
    c, _ = _mk_cache(vocab=96, chunk_rows=8, capacity=2)
    c.warm_up(None)
    c.init_window()
    c.prepare(0, np.array([1]))
    c.release(0, dirty=True)
    # keep chunk 1 hotter than chunk 0 so LFU picks the dirty chunk 0
    c.prepare(5, np.array([8, 9, 10]))
    c.release(5, dirty=False)
    c.dirty_rows.clear()                    # lose the touch record
    before = dict(c.counters())
    c.prepare(1, np.array([16]))            # forces one eviction
    after = dict(c.counters())
    assert after["writeback_rows_dirty"] - before["writeback_rows_dirty"] == 8
    c.release(1, dirty=False)


def test_checkpoint_save_materializes_cache_nodes():
    """training.checkpoint flushes a cache node to the full host master
    (stripped shadow placeholder) — cached and uncached trees save
    interchangeably."""
    c, master = _mk_cache(vocab=32, chunk_rows=8, capacity=2)
    c.warm_up(None)
    win = c.init_window()
    c.prepare(0, np.arange(8))
    new = win._replace(master=win.master.at[:8].add(1.0))
    c.publish(new)
    c.release(0, dirty=True)
    want = np.array(master)
    want[:8] += 1.0
    with tempfile.TemporaryDirectory() as d:
        CKPT.save(d, 1, {"t": c})
        got = CKPT.restore(d, {"t": c.materialize()})
    np.testing.assert_array_equal(np.asarray(got["t"].master), want)
    # the stored shadow is the stripped placeholder; restore rebuilds it
    np.testing.assert_array_equal(np.asarray(got["t"].shadow),
                                  want.astype(jnp.bfloat16))
    assert c.dirty[0]          # materialize (used by save) is non-mutating


# -- engine-level identity ---------------------------------------------------

def _engine_fixtures(vocab=512, num_negatives=8):
    cfg = reduced(ARCHS["hstu-tiny"]).replace(num_negatives=num_negatives,
                                              vocab_size=vocab)
    b = get_bundle(cfg)
    key = jax.random.PRNGKey(0)
    lk = dict(neg_mode="fused", neg_segment=32)
    return b, key, lk


def _banded_batch(i, vocab=512, band_chunks=2, chunk_rows=32, bands=8):
    """Batch i draws every id feature from one rotating narrow band of
    chunks, so a capacity-limited cache run stays under its pin budget
    while still evicting across bands."""
    lo = (i % bands) * band_chunks * chunk_rows
    hi = lo + band_chunks * chunk_rows
    k = jax.random.PRNGKey(1000 + i)
    ks = jax.random.split(k, 3)
    cap = 64
    return {
        "ids": jax.random.randint(ks[0], (2, cap), lo, hi),
        "labels": jax.random.randint(ks[1], (2, cap), lo, hi),
        "timestamps": jnp.cumsum(
            jnp.ones((2, cap), jnp.int32), 1),
        "offsets": jnp.tile(jnp.asarray([0, cap // 2, cap], jnp.int32),
                            (2, 1)),
        "neg_ids": jax.random.randint(ks[2], (2, cap, 8), lo, hi),
        "rng": jnp.zeros((2,), jnp.uint32),
    }


@pytest.mark.parametrize("semi_async", [False, True])
@pytest.mark.parametrize("sched", ["flat", "algorithm1"])
def test_engine_cached_all_resident_bit_identical(semi_async, sched):
    """With capacity >= num_chunks the warm-up admits every chunk at
    slot == chunk, the window IS the full table, and the cached engine
    must reproduce the uncached fused step bit-for-bit: losses, master,
    shadow, AdaGrad accum and pending τ=1 pairs."""
    b, key, lk = _engine_fixtures()
    N = 5

    def batch(i):
        return synth_jagged_batch(jax.random.PRNGKey(i), 2, 128, 512, 8)

    step = make_gr_step_fn(b, loss_kwargs=lk, semi_async=semi_async)
    st = gr_train_state(b.init_dense(key), b.init_table(key),
                        pending_slots=gr_pending_slots(batch(0)))
    losses = []
    for i in range(N):
        st, m = step(st, batch(i))
        losses.append(float(m["loss"]))

    cache = CachedShadowedTable(b.init_table(key), capacity_chunks=8,
                                chunk_rows=64)          # 512/64: resident
    cache.warm_up(None)
    eng = GREngine(b, batch, loss_kwargs=lk, semi_async=semi_async,
                   schedule=sched, cache=cache)
    recs = eng.run(N)
    assert [r["loss"] for r in recs] == losses
    assert cache.stats.hit_rate == 1.0      # all-resident: no misses
    assert cache.stats.evictions == 0
    full = eng.full_snapshot()
    np.testing.assert_array_equal(np.asarray(full.table.master),
                                  np.asarray(st.table.master))
    np.testing.assert_array_equal(np.asarray(full.table.accum),
                                  np.asarray(st.table.accum))
    np.testing.assert_array_equal(
        np.asarray(ET.rebuild_shadow(full.table).shadow),
        np.asarray(st.table.shadow))
    np.testing.assert_array_equal(np.asarray(full.pending_ids),
                                  np.asarray(st.pending_ids))
    np.testing.assert_array_equal(np.asarray(full.pending_rows),
                                  np.asarray(st.pending_rows))
    # the live window really is capacity-shaped, not vocab-shaped
    assert eng.state.table.master.shape[0] == cache.rows


@pytest.mark.parametrize("semi_async", [False, True])
def test_engine_cached_capacity_limited_matches_uncached(semi_async):
    """The real regime: resident rows < vocab, misses/evictions/dirty
    writebacks on every band rotation — training math still bit-identical
    to the uncached fused step."""
    b, key, lk = _engine_fixtures()
    N = 10
    step = make_gr_step_fn(b, loss_kwargs=lk, semi_async=semi_async)
    st = gr_train_state(b.init_dense(key), b.init_table(key),
                        pending_slots=gr_pending_slots(_banded_batch(0)))
    losses = []
    for i in range(N):
        st, m = step(st, _banded_batch(i))
        losses.append(float(m["loss"]))

    cache = CachedShadowedTable(b.init_table(key), capacity_chunks=6,
                                chunk_rows=32)          # 6 of 16 chunks
    cache.warm_up(None)
    eng = GREngine(b, _banded_batch, loss_kwargs=lk, semi_async=semi_async,
                   schedule="flat", cache=cache)
    recs = eng.run(N)
    assert [r["loss"] for r in recs] == losses
    assert cache.stats.misses > 0 and cache.stats.evictions > 0
    assert cache.stats.writebacks > 0       # dirty chunks crossed bands
    assert recs[1]["cache"]["hits"] + recs[1]["cache"]["misses"] > 0
    full = eng.full_snapshot()
    np.testing.assert_array_equal(np.asarray(full.table.master),
                                  np.asarray(st.table.master))
    np.testing.assert_array_equal(np.asarray(full.table.accum),
                                  np.asarray(st.table.accum))
    np.testing.assert_array_equal(np.asarray(full.pending_ids),
                                  np.asarray(st.pending_ids))
    np.testing.assert_array_equal(np.asarray(full.pending_rows),
                                  np.asarray(st.pending_rows))


def test_engine_cached_pipelined_capacity_limited():
    """Algorithm-1 schedule with a capacity-limited cache: the in-flight
    lookahead keeps several bands pinned at once; losses must match the
    cached flat run exactly and the counters must show real swapping."""
    b, key, lk = _engine_fixtures()
    N = 12

    def run(sched):
        cache = CachedShadowedTable(b.init_table(key), capacity_chunks=14,
                                    chunk_rows=32)
        cache.warm_up(None)
        eng = GREngine(b, _banded_batch, loss_kwargs=lk, semi_async=True,
                       schedule=sched, cache=cache)
        recs = eng.run(N)
        return [r["loss"] for r in recs], cache

    flat_losses, _ = run("flat")
    pipe_losses, cache = run("algorithm1")
    assert pipe_losses == flat_losses
    assert cache.stats.misses > 0 and cache.stats.evictions > 0
    assert 0.0 < cache.stats.hit_rate < 1.0


def test_engine_cached_checkpoint_roundtrip():
    """full_snapshot → save → restore → adopt_full_state continues the
    trajectory bit-identically (pending pairs globalized/slotized, dirty
    chunks flushed, residency rebuilt from frequency)."""
    b, key, lk = _engine_fixtures()
    N = 8
    step = make_gr_step_fn(b, loss_kwargs=lk, semi_async=True)
    st = gr_train_state(b.init_dense(key), b.init_table(key),
                        pending_slots=gr_pending_slots(_banded_batch(0)))
    losses = []
    for i in range(N):
        st, m = step(st, _banded_batch(i))
        losses.append(float(m["loss"]))

    def mk_engine(data_fn):
        cache = CachedShadowedTable(b.init_table(key), capacity_chunks=6,
                                    chunk_rows=32)
        cache.warm_up(None)
        return GREngine(b, data_fn, loss_kwargs=lk, semi_async=True,
                        schedule="flat", cache=cache)

    eng = mk_engine(_banded_batch)
    r1 = eng.run(4)
    full = eng.full_snapshot()
    assert bool((np.asarray(full.pending_ids) >= 0).any())
    assert full.table.master.shape[0] == 512    # vocab-sized, not window
    with tempfile.TemporaryDirectory() as d:
        CKPT.save(d, 4, full)
        eng2 = mk_engine(lambda i: _banded_batch(i + 4))
        restored = CKPT.restore(d, full)    # template = saved structure
    eng2.adopt_full_state(restored)
    r2 = eng2.run(4)
    assert [r["loss"] for r in r1 + r2] == losses
    full2 = eng2.full_snapshot()
    np.testing.assert_array_equal(np.asarray(full2.table.master),
                                  np.asarray(st.table.master))
    np.testing.assert_array_equal(np.asarray(full2.table.accum),
                                  np.asarray(st.table.accum))
