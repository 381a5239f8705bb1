"""Hypothesis property tests over the embedding-cache chunk manager.

Skipped wholesale without hypothesis (same guard as test_hsp /
test_jagged); the deterministic cache tests live in
tests/test_cache_embedding.py.
"""
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.embedding.cache import CachedShadowedTable


def _mk_cache(vocab=96, dim=3, chunk_rows=8, capacity=4, seed=0,
              accum=False):
    rng = np.random.default_rng(seed)
    master = rng.normal(size=(vocab, dim)).astype(np.float32)
    acc = (rng.random((vocab, dim)).astype(np.float32) if accum else None)
    return CachedShadowedTable(master, capacity_chunks=capacity,
                               chunk_rows=chunk_rows, accum=acc), master

# -- hypothesis properties --------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(ids=st.lists(st.one_of(st.integers(-8, 40), st.integers(90, 110)),
                    min_size=1, max_size=64))
def test_cached_lookup_bit_identical_to_full_table(ids):
    """Gathering any id stream (duplicates, negatives, out-of-range)
    through translate + the window is bit-identical to clip-mode gather
    from the full table. The draw spans chunks 0–5 and 11 (clipped ids
    land on 0 and 95) — at most 8 distinct chunks, so capacity 8 never
    thrashes but chunk 11 always swaps in."""
    c, master = _mk_cache(vocab=96, chunk_rows=8, capacity=8)
    c.warm_up(None)
    win = c.init_window()
    a = np.asarray(ids, np.int64)
    uids = np.unique(np.clip(a, 0, 95))
    plan, _ = c.prepare(0, uids)
    win = c.splice(win, plan)
    c.publish(win)
    rows = np.asarray(win.master)[c.translate(a)]
    want = master[np.clip(a, 0, 95)]
    np.testing.assert_array_equal(rows, want)
    shadow = np.asarray(win.shadow)[c.translate(a)]
    np.testing.assert_array_equal(shadow, want.astype(jnp.bfloat16))
    c.release(0, dirty=False)


@settings(max_examples=25, deadline=None)
@given(batches=st.lists(st.lists(st.integers(0, 95), min_size=1,
                                 max_size=20), min_size=1, max_size=12))
def test_cache_accounting_invariants(batches):
    """Residency maps stay a bijection, pins balance, the hit/miss split
    partitions the weighted id stream, and the eviction counter matches
    observed evictions — under any prepare/release interleaving."""
    c, _ = _mk_cache(vocab=96, chunk_rows=8, capacity=4)
    c.warm_up(None)
    c.init_window()
    total = 0
    for i, b in enumerate(batches):
        uids, counts = np.unique(np.asarray(b, np.int64),
                                 return_counts=True)
        if np.unique(uids // 8).size > 4:
            continue                       # would (correctly) thrash
        _, step = c.prepare(i, uids, counts)
        total += int(counts.sum())
        assert step["hits"] + step["misses"] == int(counts.sum())
        # bijection: every resident chunk's slot points back at it
        res = np.flatnonzero(c.chunk_slot >= 0)
        assert res.size <= 4
        np.testing.assert_array_equal(c.slot_chunk[c.chunk_slot[res]], res)
        assert (c.pins >= 0).all()
        c.release(i, dirty=False)
    assert c.stats.hits + c.stats.misses == total
    assert (c.pins == 0).all()
    assert c.stats.writebacks == 0         # nothing was ever dirty


@settings(max_examples=20, deadline=None)
@given(seq=st.lists(st.tuples(st.integers(0, 11), st.booleans()),
                    min_size=1, max_size=20))
def test_eviction_never_drops_dirty_chunks(seq):
    """Numpy mirror: random chunk touches, some dirtying the window; any
    interleaving of evictions must write dirty rows back, so the final
    materialized table equals the mirror exactly."""
    c, master = _mk_cache(vocab=96, chunk_rows=8, capacity=4, accum=True)
    mirror = master.copy()
    c.warm_up(None)
    win = c.init_window()
    for i, (chunk, make_dirty) in enumerate(seq):
        uids = np.arange(chunk * 8, chunk * 8 + 8)
        plan, _ = c.prepare(i, uids)
        win = c.splice(win, plan)
        if make_dirty:                     # emulate a sparse landing
            rows = c.translate(uids)
            win = win._replace(
                master=win.master.at[rows].add(float(i + 1)))
            mirror[uids] += float(i + 1)
        c.publish(win)
        c.release(i, dirty=make_dirty)
    got = c.materialize(win)
    np.testing.assert_array_equal(np.asarray(got.master), mirror)
    # flush writes the same rows into the host store and clears dirty
    c.flush(win)
    assert not c.dirty.any()
    np.testing.assert_array_equal(c.host_master[:96], mirror)


