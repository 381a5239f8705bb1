"""repro.serving — scheduler packing invariants, incremental user-state
cache correctness (cached-vs-cold parity), and sharded quantized top-k
parity against the fp32 full-scoring oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.embedding.tables import make_shadowed, strip_shadow
from repro.models.model_zoo import get_bundle
from repro.serving import (RecallEngine, RequestScheduler, ShardedTopK,
                           UserState, UserStateCache, bytes_per_query,
                           topk_blocked, topk_dense)


# --------------------------------------------------------------------------
# scheduler
# --------------------------------------------------------------------------

def _random_requests(rng, n, max_len, n_items=1000):
    reqs = []
    for u in range(n):
        m = int(rng.integers(1, max_len + 1))
        ids = rng.integers(0, n_items, m).astype(np.int32)
        ts = np.cumsum(rng.integers(1, 50, m)).astype(np.int32)
        reqs.append((u, ids, ts))
    return reqs


@pytest.mark.parametrize("G,S,L,n", [(1, 4, 16, 9), (4, 2, 32, 25),
                                     (3, 5, 8, 40)])
def test_scheduler_packing_invariants(G, S, L, n):
    rng = np.random.default_rng(G * 100 + n)
    sch = RequestScheduler(G, S, L, max_delay_ms=0.0)
    reqs = _random_requests(rng, n, L)
    rids = [sch.submit(u, ids, ts, now=0.0) for u, ids, ts in reqs]
    mbs = sch.flush(now=1.0)
    assert sch.pending == 0
    seen = []
    for mb in mbs:
        cap = S * L
        # capacity + row-count bounds per shard
        assert (mb.offsets[:, -1] <= cap).all()
        assert (np.diff(mb.offsets, axis=1) >= 0).all()
        rows_per_shard = np.zeros(G, int)
        for s in mb.slots:
            rows_per_shard[s.shard] += 1
            # request → slot mapping reproduces the history verbatim
            u, ids, ts = reqs[s.rid]
            assert s.user == u
            np.testing.assert_array_equal(mb.ids[s.shard, s.lo:s.hi], ids)
            np.testing.assert_array_equal(
                mb.timestamps[s.shard, s.lo:s.hi], ts - ts[0])
            assert mb.offsets[s.shard, s.row] == s.lo
            assert mb.offsets[s.shard, s.row + 1] == s.hi
            assert mb.last_pos[s.shard, s.row] == s.hi - 1
            seen.append(s.rid)
    # every request packed exactly once, none dropped
    assert sorted(seen) == sorted(rids)


def test_scheduler_truncates_to_max_seq_len():
    sch = RequestScheduler(1, 2, 8, max_delay_ms=0.0)
    ids = np.arange(30, dtype=np.int32)
    sch.submit(7, ids, ids, now=0.0)
    (mb,) = sch.flush(now=0.0)
    s = mb.slots[0]
    np.testing.assert_array_equal(mb.ids[s.shard, s.lo:s.hi], ids[-8:])


def test_scheduler_token_capacity_binds():
    """With tokens_per_shard below the padded worst case, the token bound
    (not just the row cap) forces spills, and the packed buffers shrink to
    the configured width."""
    sch = RequestScheduler(2, 4, 8, tokens_per_shard=16, max_delay_ms=0.0)
    for u in range(8):
        sch.submit(u, np.arange(8), np.arange(8), now=0.0)
    mbs = sch.flush(now=0.0)
    assert sum(mb.num_requests for mb in mbs) == 8
    assert len(mbs) == 2                      # 4 fit per pack, 4 spill
    for mb in mbs:
        assert mb.ids.shape == (2, 16)        # (G, tokens_per_shard)
        assert (mb.offsets[:, -1] <= 16).all()
    with pytest.raises(ValueError):           # one request must still fit
        RequestScheduler(1, 2, 8, tokens_per_shard=4)


def test_scheduler_rejects_mismatched_history():
    sch = RequestScheduler(1, 2, 8, max_delay_ms=0.0)
    with pytest.raises(ValueError):
        sch.submit(0, np.arange(5), np.arange(4), now=0.0)
    # mismatch must be caught even when both sides exceed max_seq_len
    # (truncation used to mask it and silently mispair events)
    with pytest.raises(ValueError):
        sch.submit(0, np.arange(20), np.arange(15), now=0.0)


def test_scheduler_flush_policy():
    sch = RequestScheduler(2, 2, 8, max_delay_ms=50.0)
    assert not sch.ready(now=0.0)
    sch.submit(0, [1], [1], now=0.0)
    assert not sch.ready(now=0.01)            # young + not full
    assert sch.ready(now=0.06)                # deadline passed
    for u in range(1, 4):
        sch.submit(u, [1], [1], now=0.01)
    assert sch.ready(now=0.02)                # full micro-batch


def test_scheduler_spills_overflow_to_next_microbatch():
    """More tokens than one micro-batch holds → multiple well-formed
    packs, nothing dropped."""
    sch = RequestScheduler(2, 2, 10, max_delay_ms=0.0)
    # six max-length requests into a 2-shard × 2-row × 10-token pack
    for u in range(6):
        sch.submit(u, np.arange(10), np.arange(10), now=0.0)
    mbs = sch.flush(now=0.0)
    assert len(mbs) >= 2
    assert sum(mb.num_requests for mb in mbs) == 6
    for mb in mbs:
        assert (mb.offsets[:, -1] <= 20).all()


def test_scheduler_latency_records():
    sch = RequestScheduler(1, 4, 8, max_delay_ms=0.0)
    r0 = sch.submit(0, [1, 2], [1, 2], now=10.0)
    r1 = sch.record_hit(1, now=10.0)
    sch.flush(now=10.5)
    sch.mark_done([r0, r1], now=11.0)
    st = sch.latency_stats()
    assert st["count"] == 2
    assert st["cache_hits"] == 1
    assert abs(st["p50_s"] - 1.0) < 1e-9
    assert st["queue_p50_s"] >= 0.0


# --------------------------------------------------------------------------
# user-state cache
# --------------------------------------------------------------------------

def test_ring_buffer_truncation():
    st = UserState(max_len=8)
    st.append(np.arange(5), np.arange(5))
    ids, ts = st.history()
    np.testing.assert_array_equal(ids, np.arange(5))
    # wrap: 5 + 6 events > 8 → keep the last 8 chronological
    st.append(np.arange(5, 11), np.arange(5, 11))
    ids, ts = st.history()
    np.testing.assert_array_equal(ids, np.arange(3, 11))
    np.testing.assert_array_equal(ts, np.arange(3, 11))
    # one giant append replaces the whole buffer
    st.append(np.arange(100), np.arange(100))
    ids, _ = st.history()
    np.testing.assert_array_equal(ids, np.arange(92, 100))


def test_ring_buffer_matches_from_scratch_tokenization():
    """Incremental appends == re-tokenizing the full log (the property the
    engine's cached-vs-cold parity rests on)."""
    rng = np.random.default_rng(3)
    full_ids = rng.integers(0, 500, 100).astype(np.int32)
    full_ts = np.cumsum(rng.integers(1, 9, 100)).astype(np.int32)
    st = UserState(max_len=24)
    cur = 0
    while cur < 100:
        n = min(int(rng.integers(1, 30)), 100 - cur)
        st.append(full_ids[cur:cur + n], full_ts[cur:cur + n])
        cur += n
        ids, ts = st.history()
        np.testing.assert_array_equal(ids, full_ids[max(0, cur - 24):cur])
        np.testing.assert_array_equal(ts, full_ts[max(0, cur - 24):cur])


def test_cache_hit_miss_and_versioning():
    c = UserStateCache(max_seq_len=16)
    st, enc = c.update(1, [1, 2], [1, 2])
    assert enc                                 # new user → encode
    c.store(1, np.ones(4, np.float32))
    st, enc = c.update(1)                      # no new events → hit
    assert not enc and c.hits == 1
    st, enc = c.update(1, [3], [3])            # new event invalidates
    assert enc
    assert st.fresh_embedding() is None
    assert 0.0 < c.hit_rate() < 1.0


def test_store_with_snapshot_version_never_marks_stale_fresh():
    """An embedding encoded from version v must not satisfy a hit at
    version v+1, and an out-of-order older store must not clobber a newer
    one (two same-user requests in one micro-batch)."""
    c = UserStateCache(max_seq_len=16)
    st, _ = c.update(1, [1, 2], [1, 2])
    v1 = st.version
    st, _ = c.update(1, [3], [3])
    v2 = st.version
    c.store(1, np.full(4, 2.0, np.float32), v2)    # newer encode lands
    c.store(1, np.full(4, 1.0, np.float32), v1)    # stale encode after
    emb = c.get(1).fresh_embedding()
    assert emb is not None and emb[0] == 2.0       # newest kept
    c.store(1, np.full(4, 1.0, np.float32), v1)
    st, enc = c.update(1)
    assert not enc                                  # still a valid hit


def test_engine_same_user_twice_in_one_batch_stays_consistent():
    """The cache must never serve a hit from an embedding that predates
    events already merged into the history."""
    cfg, dense, table = _tiny_setup(seed=5)
    rng = np.random.default_rng(23)
    hist = _histories(rng, 1, cfg.vocab_size, lo=10, hi=20)
    ids, ts = hist[0]
    eng = RecallEngine(cfg, dense, table, num_shards=2, users_per_shard=2,
                       k=10, retrieval_block=256, max_delay_ms=0.0)
    # two requests for user 0 in one pack: full history, then one event
    eng.submit(0, ids[:-1], ts[:-1])
    eng.submit(0, ids[-1:], ts[-1:])
    eng.step(force=True)
    # a follow-up no-event request must rank the FULL history's embedding
    res = eng.serve([(0, [], [])])
    cold = RecallEngine(cfg, dense, table, num_shards=2, users_per_shard=2,
                        k=10, retrieval_block=256, max_delay_ms=0.0)
    ref = cold.serve([(0, ids, ts)])
    np.testing.assert_array_equal(res[0].user_emb, ref[0].user_emb)


def test_latency_stats_keys_stable_before_first_completion():
    sch = RequestScheduler(1, 2, 4, max_delay_ms=0.0)
    st = sch.latency_stats()
    assert st["count"] == 0 and np.isnan(st["p50_s"])
    assert st["cache_hit_rate"] == 0.0


def test_cache_update_rejects_mismatched_delta_before_touch():
    """A malformed delta must fail before the LRU is touched: no phantom
    state inserted, no warm user evicted."""
    c = UserStateCache(max_seq_len=8, max_users=2)
    c.update(1, [1], [1])
    c.update(2, [2], [2])
    with pytest.raises(ValueError):
        c.update(3, [1, 2, 3], [1, 2])
    assert 3 not in c and 1 in c and 2 in c
    assert c.evictions == 0


def test_engine_rejects_empty_history_without_polluting_cache():
    """A no-history request for an unknown user must fail BEFORE the cache
    mutates — no phantom UserState, no skewed miss count, no LRU
    eviction of a warm user."""
    cfg, dense, table = _tiny_setup(seed=6)
    rng = np.random.default_rng(29)
    hist = _histories(rng, 2, cfg.vocab_size)
    eng = RecallEngine(cfg, dense, table, num_shards=1, users_per_shard=2,
                       k=10, retrieval_block=256, max_delay_ms=0.0,
                       cache_users=2)
    eng.serve([(u, *hist[u]) for u in hist])     # cache full with 0, 1
    misses = eng.cache.misses
    with pytest.raises(ValueError):
        eng.submit(99, [], [])
    assert 99 not in eng.cache
    assert 0 in eng.cache and 1 in eng.cache     # nobody evicted
    assert eng.cache.misses == misses


def test_scheduler_records_bounded():
    sch = RequestScheduler(1, 2, 4, max_delay_ms=0.0, max_records=50)
    for i in range(300):
        rid = sch.submit(0, [1], [1], now=float(i))
        sch.flush(now=float(i))
        sch.mark_done([rid], now=float(i))
    assert len(sch.records) <= 50
    assert sch.latency_stats()["count"] <= 50


def test_cache_lru_eviction():
    c = UserStateCache(max_seq_len=4, max_users=2)
    for u in (1, 2, 3):
        c.update(u, [u], [u])
    assert len(c) == 2 and c.evictions == 1
    assert 1 not in c and 3 in c


def test_cache_pinned_overshoot_drains_after_release():
    """A pinned batch may overshoot max_users, but the first insert after
    the pins release must drain the cache back to the bound."""
    c = UserStateCache(max_seq_len=4, max_users=3)
    with c.pinned(range(10, 16)):
        for u in range(10, 16):
            c.update(u, [u], [u])
        assert len(c) == 6                   # transient overshoot
    c.update(99, [1], [1])                   # pins released → drain
    assert len(c) <= 3
    assert 99 in c                           # the new insert survives


def test_ring_buffer_rejects_mismatched_delta_without_corruption():
    st = UserState(max_len=8)
    st.append([1, 2, 3], [10, 20, 30])
    v = st.version
    with pytest.raises(ValueError):
        st.append([4, 5, 6], [40, 50])
    assert st.version == v                      # nothing was written
    ids, ts = st.history()
    np.testing.assert_array_equal(ids, [1, 2, 3])
    np.testing.assert_array_equal(ts, [10, 20, 30])


# --------------------------------------------------------------------------
# retrieval
# --------------------------------------------------------------------------

def _sets_match_allowing_ties(scores_full, idx_a, idx_b, atol=0.0):
    """Top-k sets may differ only in items whose true score is within
    ``atol`` of the boundary (the k-th best score)."""
    k = idx_a.shape[0]
    kth = np.sort(scores_full)[::-1][k - 1]
    diff = set(idx_a.tolist()) ^ set(idx_b.tolist())
    return all(abs(scores_full[i] - kth) <= atol for i in diff)


@pytest.mark.parametrize("V,k,block", [(1000, 100, 256), (1000, 100, 1000),
                                       (777, 50, 128), (64, 64, 32)])
def test_topk_blocked_matches_dense_fp32(V, k, block):
    """Same table, same dtype → the blocked per-shard merge must equal the
    full-scoring top-k exactly (up to ties at the boundary)."""
    key = jax.random.PRNGKey(V + k)
    table = jax.random.normal(key, (V, 32), jnp.float32)
    emb = jax.random.normal(jax.random.PRNGKey(1), (5, 32), jnp.float32)
    bv, bi = topk_blocked(emb, table, k=k, block_v=block)
    dv, di = topk_dense(emb, table, k)
    np.testing.assert_allclose(np.asarray(bv), np.asarray(dv), atol=1e-6)
    scores = np.asarray(emb, np.float32) @ np.asarray(table, np.float32).T
    for q in range(emb.shape[0]):
        assert _sets_match_allowing_ties(scores[q], np.asarray(bi)[q],
                                         np.asarray(di)[q], atol=1e-6)


def test_topk_shadow_vs_fp32_oracle_within_quantization():
    """Shadow-table top-k vs the fp32 full-scoring oracle: any set
    difference must sit within the fp16 quantization margin of the k-th
    score — beyond that margin a swap is a real bug."""
    key = jax.random.PRNGKey(0)
    master = jax.random.normal(key, (4096, 64), jnp.float32) * 0.05
    t = make_shadowed(master, qdtype=jnp.float16)
    emb = jax.random.normal(jax.random.PRNGKey(2), (8, 64), jnp.float32)
    k = 100
    ret = ShardedTopK(k, block_v=512)
    sv, si = ret(t, emb)
    ov, oi = ret.oracle(t, emb)
    f32 = np.asarray(emb) @ np.asarray(master).T
    f16 = np.asarray(emb) @ np.asarray(t.shadow, np.float32).T
    for q in range(emb.shape[0]):
        margin = np.abs(f32[q] - f16[q]).max() + 1e-6
        assert _sets_match_allowing_ties(f32[q], np.asarray(si)[q],
                                         np.asarray(oi)[q], atol=margin)


def test_topk_stripped_shadow_falls_back_to_master():
    master = jax.random.normal(jax.random.PRNGKey(1), (256, 16), jnp.float32)
    t = strip_shadow(make_shadowed(master))
    ret = ShardedTopK(10, block_v=64)
    assert ret.scan_table(t) is t.master
    emb = jax.random.normal(jax.random.PRNGKey(3), (2, 16), jnp.float32)
    sv, si = ret(t, emb)
    dv, di = topk_dense(emb, master, 10)
    np.testing.assert_allclose(np.asarray(sv), np.asarray(dv), atol=1e-6)


def test_retrieval_bytes_accounting():
    master = jnp.zeros((1000, 32), jnp.float32)
    t = make_shadowed(master, qdtype=jnp.float16)
    assert bytes_per_query(t.master, 8) == 1000 * 32 * 4 / 8
    assert bytes_per_query(t.shadow, 8) == 1000 * 32 * 2 / 8
    # the §4.3.2 serving win: exactly 2× fewer bytes per query
    assert bytes_per_query(t.master, 8) / bytes_per_query(t.shadow, 8) == 2.0
    # blocked scan: the re-slid last window re-reads the tail when
    # block_v does not divide V (4 windows of 256 rows for V=1000)
    assert bytes_per_query(t.master, 8, block_v=256) == 1024 * 32 * 4 / 8
    assert bytes_per_query(t.master, 8, block_v=1000) == 1000 * 32 * 4 / 8


def test_engine_from_raw_master_skips_optimizer_accum():
    """Serving-only construction from a bare (V, D) master must not
    allocate the (V, D) fp32 AdaGrad accumulator."""
    cfg, dense, table = _tiny_setup(seed=8)
    eng = RecallEngine(cfg, dense, table.master, num_shards=1,
                       users_per_shard=2, k=10, retrieval_block=256)
    assert eng.table.accum.shape[0] == 0
    assert eng.table.shadow.dtype == jnp.bfloat16
    rng = np.random.default_rng(31)
    hist = _histories(rng, 2, cfg.vocab_size)
    res = eng.serve([(u, *hist[u]) for u in hist])
    assert len(res) == 2 and res[0].item_ids.shape == (10,)


# --------------------------------------------------------------------------
# engine — cached-vs-cold parity end to end
# --------------------------------------------------------------------------

def _tiny_setup(seed=0, n_items=600, max_seq_len=32):
    cfg = reduced(ARCHS["hstu-tiny"]).replace(vocab_size=n_items,
                                              max_seq_len=max_seq_len)
    b = get_bundle(cfg)
    key = jax.random.PRNGKey(seed)
    return cfg, b.init_dense(key), make_shadowed(b.init_table(key))


def _histories(rng, users, n_items, lo=4, hi=40):
    out = {}
    for u in range(users):
        n = int(rng.integers(lo, hi))
        out[u] = (rng.integers(0, n_items, n).astype(np.int32),
                  np.cumsum(rng.integers(1, 60, n)).astype(np.int32))
    return out


def test_engine_cached_vs_cold_hidden_state_parity():
    """Users built up incrementally through the cache must produce
    bit-identical embeddings (and therefore identical top-k) to a cold
    engine that sees each full history once."""
    cfg, dense, table = _tiny_setup()
    rng = np.random.default_rng(7)
    hist = _histories(rng, 10, cfg.vocab_size, lo=8, hi=60)
    kw = dict(num_shards=2, users_per_shard=4, k=20, retrieval_block=256,
              max_delay_ms=0.0)

    warm = RecallEngine(cfg, dense, table, **kw)
    # drip each history in as three increments (random split points)
    splits = {u: sorted(rng.choice(np.arange(1, len(ids)), size=2,
                                   replace=False).tolist())
              for u, (ids, _) in hist.items()}
    for part in range(3):
        reqs = []
        for u, (ids, ts) in hist.items():
            lo_, hi_ = ([0] + splits[u])[part], (splits[u] + [len(ids)])[part]
            reqs.append((u, ids[lo_:hi_], ts[lo_:hi_]))
        warm_res = warm.serve(reqs)
    assert not any(r.cache_hit for r in warm_res)

    cold = RecallEngine(cfg, dense, table, **kw)
    cold_res = cold.serve([(u, *hist[u]) for u in hist])

    wa = {r.user: r for r in warm_res}
    for r in cold_res:
        np.testing.assert_array_equal(wa[r.user].user_emb, r.user_emb)
        np.testing.assert_array_equal(wa[r.user].item_ids, r.item_ids)


def test_engine_cache_hit_skips_encode_and_is_bitwise_stable():
    cfg, dense, table = _tiny_setup(seed=1)
    rng = np.random.default_rng(11)
    hist = _histories(rng, 6, cfg.vocab_size)
    eng = RecallEngine(cfg, dense, table, num_shards=1, users_per_shard=6,
                       k=10, retrieval_block=256, max_delay_ms=0.0)
    first = eng.serve([(u, *hist[u]) for u in hist])
    n_batches = eng.encoded_batches
    n_scans = eng.retrieval_batches
    second = eng.serve([(u, [], []) for u in hist])
    assert eng.encoded_batches == n_batches      # no forward ran
    assert eng.retrieval_batches == n_scans      # no table scan either
    assert all(r.cache_hit for r in second)
    f = {r.user: r for r in first}
    for r in second:
        np.testing.assert_array_equal(f[r.user].user_emb, r.user_emb)
        np.testing.assert_array_equal(f[r.user].item_ids, r.item_ids)
    assert eng.cache.hit_rate() == 0.5


def test_engine_hit_only_step_does_not_starve():
    """Pure cache-hit traffic must be served by an unforced step(): hits
    need no encode, so they never wait on the batching policy."""
    cfg, dense, table = _tiny_setup(seed=3)
    rng = np.random.default_rng(13)
    hist = _histories(rng, 3, cfg.vocab_size)
    eng = RecallEngine(cfg, dense, table, num_shards=1, users_per_shard=4,
                       k=10, retrieval_block=256, max_delay_ms=1e6)
    eng.serve([(u, *hist[u]) for u in hist])
    for u in hist:
        eng.submit(u, [], [], now=0.0)
    res = eng.step(now=0.0)                     # not forced, deadline far
    assert len(res) == 3 and all(r.cache_hit for r in res)


def test_engine_hit_survives_lru_eviction():
    """A recorded hit snapshots its embedding at submit time — evicting
    the user's state before step() must not zero the ranking."""
    cfg, dense, table = _tiny_setup(seed=4)
    rng = np.random.default_rng(17)
    hist = _histories(rng, 4, cfg.vocab_size)
    eng = RecallEngine(cfg, dense, table, num_shards=1, users_per_shard=4,
                       k=10, retrieval_block=256, max_delay_ms=0.0,
                       cache_users=2)
    first = eng.serve([(0, *hist[0])])
    eng.submit(0, [], [])                       # hit for user 0
    eng.submit(1, *hist[1])                     # two new users evict 0
    eng.submit(2, *hist[2])
    assert eng.cache.get(0) is None             # really evicted
    res = {r.user: r for r in eng.step(force=True)}
    assert res[0].cache_hit
    np.testing.assert_array_equal(res[0].user_emb, first[0].user_emb)
    np.testing.assert_array_equal(res[0].item_ids, first[0].item_ids)


def test_engine_rejects_delta_after_eviction_then_accepts_full_history():
    """A delta-only request from an LRU-evicted user must not silently
    re-seed state from the delta (garbage recommendations); it raises,
    and the retry with the full history re-seeds normally."""
    cfg, dense, table = _tiny_setup(seed=9)
    rng = np.random.default_rng(37)
    hist = _histories(rng, 4, cfg.vocab_size)
    eng = RecallEngine(cfg, dense, table, num_shards=1, users_per_shard=4,
                       k=10, retrieval_block=256, max_delay_ms=0.0,
                       cache_users=2)
    eng.serve([(0, *hist[0])])
    eng.serve([(1, *hist[1]), (2, *hist[2])])    # evicts user 0
    assert eng.cache.get(0) is None
    with pytest.raises(KeyError):
        eng.submit(0, hist[0][0][-1:], hist[0][1][-1:])
    res = eng.serve([(0, *hist[0])])             # retry: full history OK
    assert len(res) == 1 and not res[0].cache_hit
    # and the re-seeded state must equal a cold encode of the history
    cold = RecallEngine(cfg, dense, table, num_shards=1, users_per_shard=4,
                        k=10, retrieval_block=256, max_delay_ms=0.0)
    ref = cold.serve([(0, *hist[0])])
    np.testing.assert_array_equal(res[0].user_emb, ref[0].user_emb)


def test_engine_serve_is_atomic_on_rejection():
    """A rejected batch must enqueue nothing — the retry returns exactly
    one result per request, so positional request↔result zipping holds."""
    cfg, dense, table = _tiny_setup(seed=10)
    rng = np.random.default_rng(41)
    hist = _histories(rng, 5, cfg.vocab_size)
    eng = RecallEngine(cfg, dense, table, num_shards=1, users_per_shard=4,
                       k=10, retrieval_block=256, max_delay_ms=0.0,
                       cache_users=2)
    eng.serve([(0, *hist[0])])
    eng.serve([(1, *hist[1]), (2, *hist[2])])    # evicts user 0
    # batch: valid user 3 first, then a delta for evicted user 0 → whole
    # batch rejected, user 3 NOT stranded in the queue
    with pytest.raises(KeyError):
        eng.serve([(3, *hist[3]), (0, hist[0][0][-1:], hist[0][1][-1:])])
    assert eng.scheduler.pending == 0
    res = eng.serve([(3, *hist[3]), (0, *hist[0])])
    assert [r.user for r in res] == [3, 0]       # one result per request


def test_engine_serve_batch_does_not_evict_its_own_members():
    """New users earlier in a batch must not LRU-evict later members of
    the same batch mid-flight — the batch pins its users, so a validated
    request can't turn into a KeyError after others were enqueued."""
    cfg, dense, table = _tiny_setup(seed=12)
    rng = np.random.default_rng(47)
    hist = _histories(rng, 8, cfg.vocab_size)
    eng = RecallEngine(cfg, dense, table, num_shards=1, users_per_shard=4,
                       k=10, retrieval_block=256, max_delay_ms=0.0,
                       cache_users=3)
    eng.serve([(u, *hist[u]) for u in (0, 1, 2)])    # cache full: 0,1,2
    # three new users would evict user 0 right before its own request
    res = eng.serve([(5, *hist[5]), (6, *hist[6]), (7, *hist[7]),
                     (0, [], [])])
    assert [r.user for r in res] == [5, 6, 7, 0]
    assert res[3].cache_hit                          # 0 stayed cached
    assert eng.scheduler.pending == 0
    assert len(eng.cache) <= 4                       # soft bound: batch size


def test_engine_serve_cold_same_user_pair_with_empty_delta():
    """A cold batch may seed a user and follow up with an empty delta in
    the same call — validation must judge the second request against the
    batch-seeded history, not the still-empty cache."""
    cfg, dense, table = _tiny_setup(seed=13)
    rng = np.random.default_rng(53)
    hist = _histories(rng, 1, cfg.vocab_size)
    eng = RecallEngine(cfg, dense, table, num_shards=1, users_per_shard=2,
                       k=10, retrieval_block=256, max_delay_ms=0.0)
    res = eng.serve([(0, *hist[0]), (0, [], [])])
    assert len(res) == 2 and all(r.user == 0 for r in res)
    follow = eng.serve([(0, [], [])])            # now a plain cache hit
    assert follow[0].cache_hit
    # a truly history-less user is still rejected
    with pytest.raises(ValueError):
        eng.serve([(99, [], [])])


def test_engine_result_mutation_does_not_corrupt_cache():
    """Results are caller-owned copies: sorting/mutating them in place
    must not change what the next cache hit serves."""
    cfg, dense, table = _tiny_setup(seed=11)
    rng = np.random.default_rng(43)
    hist = _histories(rng, 2, cfg.vocab_size)
    eng = RecallEngine(cfg, dense, table, num_shards=1, users_per_shard=2,
                       k=10, retrieval_block=256, max_delay_ms=0.0)
    first = eng.serve([(u, *hist[u]) for u in hist])
    keep = {r.user: (r.item_ids.copy(), r.user_emb.copy()) for r in first}
    # cold-path arrays are read-only numpy views of jax buffers — a
    # hostile write raises rather than corrupting anything
    with pytest.raises(ValueError):
        first[0].item_ids[:] = -1
    # hit-path arrays are writable caller-owned copies: mutate them all
    second = eng.serve([(u, [], []) for u in hist])
    assert all(r.cache_hit for r in second)
    for r in second:                             # hostile caller
        r.item_ids[:] = -1
        r.scores[:] = np.inf
        r.user_emb[:] = 0.0
    third = eng.serve([(u, [], []) for u in hist])
    assert all(r.cache_hit for r in third)
    for r in third:
        np.testing.assert_array_equal(r.item_ids, keep[r.user][0])
        np.testing.assert_array_equal(r.user_emb, keep[r.user][1])


def test_engine_results_in_submission_order_and_k_valid():
    cfg, dense, table = _tiny_setup(seed=2, n_items=300)
    rng = np.random.default_rng(5)
    hist = _histories(rng, 9, cfg.vocab_size)
    eng = RecallEngine(cfg, dense, table, num_shards=2, users_per_shard=2,
                       k=30, retrieval_block=128, max_delay_ms=0.0)
    res = eng.serve([(u, *hist[u]) for u in hist])
    assert [r.user for r in res] == list(hist)
    for r in res:
        assert r.item_ids.shape == (30,)
        assert (r.item_ids >= 0).all() and (r.item_ids < 300).all()
        assert len(set(r.item_ids.tolist())) == 30   # no duplicate items
        assert (np.diff(r.scores) <= 1e-6).all()     # score-descending
