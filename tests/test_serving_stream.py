"""Continuous-batching serving (PR 8): slot-buffer invariants, exact
incremental-vs-full encode parity (append / truncate / wraparound), the
streaming engine's bit-parity against the micro-batch RecallEngine on
identical traces, honest overload latency stats, and the serving
partition specs' compile verification on an 8-fake-device mesh.

Hypothesis property tests over the slot allocator are importorskip-
guarded (same policy as tests/test_cache_properties.py).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spmd_util import run_spmd

from repro.configs import ARCHS, reduced
from repro.embedding.tables import make_shadowed
from repro.models import gr as GR
from repro.models.model_zoo import get_bundle
from repro.serving import (Admission, BucketLadder, CompileCache,
                           ContinuousScheduler, RecallEngine,
                           SequenceBuffer, StreamingRecallEngine)


# --------------------------------------------------------------------------
# bucket ladder / compile cache
# --------------------------------------------------------------------------

def test_bucket_ladder_rounds_up_within_bound():
    lad = BucketLadder(48)
    assert lad.rungs == (1, 2, 4, 8, 16, 32, 48)
    assert lad.bucket(1) == 1 and lad.bucket(3) == 4
    assert lad.bucket(33) == 48 and lad.bucket(48) == 48
    with pytest.raises(ValueError):
        lad.bucket(49)
    assert BucketLadder(64, min_size=2).rungs == (2, 4, 8, 16, 32, 64)


def test_compile_cache_counts_distinct_shape_keys():
    cc = CompileCache()
    builds = []
    fn = lambda: builds.append(1) or (lambda: None)
    cc.get("cold", (8,), fn)
    cc.get("cold", (8,), fn)
    cc.get("cold", (16,), fn)
    cc.get("warm", (8, 4), fn)
    assert cc.compiles == 3 and cc.calls == 4 and len(builds) == 3
    assert cc.stats()["per_fn"] == {"cold": 2, "warm": 1}


# --------------------------------------------------------------------------
# slot buffer — deterministic invariants
# --------------------------------------------------------------------------

def _buf(n=4, s=8, d=4, kv=False):
    return SequenceBuffer(n, s, d, kv_shape=(2, 2, 3, 3) if kv else None)


def test_slot_alloc_free_partition_and_eviction_handshake():
    b = _buf(n=2)
    s0 = b.alloc(10)
    s1 = b.alloc(11)
    assert {s0, s1} == {0, 1} and b.slots_used == 2
    # full + eviction off → None
    assert b.alloc(12, evict=False) is None
    # LRU eviction: slot of user 10 (allocated first, never re-touched)
    b.touch(s1)
    s2 = b.alloc(12)
    assert s2 == s0 and b.slot_of(10) is None
    # the evicted user is reported exactly once
    assert b.take_evicted(10) and not b.take_evicted(10)
    # busy slots are skipped: only s1 remains, mark it busy → no slot
    assert b.alloc(13, busy={s1, s2}) is None
    b.release(12)
    assert b.slot_of(12) is None and not b.take_evicted(12)  # graceful
    assert b.slots_used == 1


def test_append_ring_semantics_and_version():
    b = _buf(n=1, s=4)
    s = b.alloc(7)
    b.seed(s, [1, 2], [10, 20])
    v0 = int(b.version[s])
    assert b.needs_cold[s] and int(b.length[s]) == 2
    b.mark_encoded(s)
    assert b.emb_fresh(s) and not b.needs_cold[s]
    # in-capacity append: warm-eligible state, version advances
    b.append(s, [3], [30])
    assert int(b.version[s]) == v0 + 1 and not b.needs_cold[s]
    assert b.pending_new(s) == 1 and not b.emb_fresh(s)
    # overflow append: ring keeps the newest 4, prefix invalidated
    b.append(s, [4, 5], [40, 50])
    np.testing.assert_array_equal(b.h_ids[s], [2, 3, 4, 5])
    np.testing.assert_array_equal(b.h_ts[s], [20, 30, 40, 50])
    assert b.needs_cold[s] and int(b.length[s]) == 4
    # giant append: full replace, still newest-last
    b.append(s, [6, 7, 8, 9, 10], [60, 70, 80, 90, 100])
    np.testing.assert_array_equal(b.h_ids[s], [7, 8, 9, 10])


def test_warm_eligibility_guards_window_overflow():
    b = _buf(n=1, s=8, kv=True)
    s = b.alloc(1)
    b.seed(s, [1, 2, 3], [1, 2, 3])
    assert not b.warm_eligible(s, 1)        # needs_cold after seed
    b.mark_encoded(s)
    assert b.warm_eligible(s, 4) and b.warm_eligible(s, 5)
    assert not b.warm_eligible(s, 6)        # 3 + 6 > 8 would clamp
    bn = _buf(n=1, s=8, kv=False)
    sn = bn.alloc(1)
    bn.seed(sn, [1], [1])
    bn.mark_encoded(sn)
    assert not bn.warm_eligible(sn, 1)      # no K/V cache → cold only


def test_topk_cache_is_version_stamped():
    b = _buf(n=1)
    s = b.alloc(1)
    b.seed(s, [1], [1])
    b.store_topk(s, np.arange(3), np.ones(3))
    assert b.topk(s) is not None
    b.append(s, [2], [2])
    assert b.topk(s) is None                # stale version → miss


# --------------------------------------------------------------------------
# slot buffer — hypothesis properties (importorskip-guarded)
# --------------------------------------------------------------------------

def test_slot_alloc_free_version_properties():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=40, deadline=None)
    @given(ops=st.lists(
        st.tuples(st.sampled_from(["alloc", "release", "seed",
                                   "append", "encode"]),
                  st.integers(0, 9), st.integers(1, 6)),
        min_size=1, max_size=60))
    def prop(ops):
        b = SequenceBuffer(3, 8, 4, kv_shape=(1, 1, 2, 2))
        last_version = {}
        for op, user, n in ops:
            slot = b.slot_of(user)
            if op == "alloc" and slot is None:
                b.take_evicted(user)
                s = b.alloc(user)
                if s is not None:
                    b.seed(s, np.arange(n) + 1, np.arange(n) + 1)
            elif op == "release" and slot is not None:
                b.release(user)
            elif op == "seed" and slot is not None:
                b.seed(slot, np.arange(n) + 1, np.arange(n) + 1)
            elif op == "append" and slot is not None:
                b.append(slot, np.arange(n) + 1, np.arange(n) + 1)
            elif op == "encode" and slot is not None:
                b.mark_encoded(slot)
            # invariants after every op:
            live = dict(b._slot_of)
            # one slot per user; free ∪ live partitions the slots
            assert len(set(live.values())) == len(live)
            assert (set(live.values()) | set(b._free)
                    == set(range(b.max_users)))
            assert not (set(live.values()) & set(b._free))
            for u, s in live.items():
                assert 0 < int(b.length[s]) <= b.max_seq_len
                # version never goes backwards while the user keeps
                # its slot, and a mutation always advances it
                if u in last_version and last_version[u][1] == s:
                    assert int(b.version[s]) >= last_version[u][0]
                last_version[u] = (int(b.version[s]), s)
                # fresh ⇒ encode matches the latest state exactly
                if b.emb_fresh(s):
                    assert int(b.enc_len[s]) == int(b.length[s])

    prop()


# --------------------------------------------------------------------------
# incremental encode — exact parity vs from-scratch
# --------------------------------------------------------------------------

def _tiny_model(seed=0, vocab=300, max_seq_len=24):
    cfg = reduced(ARCHS["hstu-tiny"]).replace(vocab_size=vocab,
                                              max_seq_len=max_seq_len)
    b = get_bundle(cfg)
    key = jax.random.PRNGKey(seed)
    return cfg, b.init_dense(key), b.init_table(key)


def _encode_full(cfg, dense, table, ids, ts):
    """From-scratch oracle on one padded row."""
    S = cfg.max_seq_len
    n = len(ids)
    row_ids = np.zeros(S, np.int32)
    row_ts = np.zeros(S, np.int32)
    row_ids[:n] = ids
    row_ts[:n] = ts
    x = jnp.take(table, jnp.asarray(row_ids), axis=0
                 ).astype(jnp.dtype(cfg.dtype))
    return GR.gr_serve_row_kv(dense, cfg, x, jnp.asarray(row_ts),
                              jnp.asarray(n, jnp.int32),
                              attn_block=GR.serve_attn_block(S))


def test_incremental_encode_bit_identical_across_appends():
    """Chained warm appends reproduce the from-scratch encode bitwise at
    every step — the tentpole's correctness claim."""
    cfg, dense, table = _tiny_model()
    S = cfg.max_seq_len
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, 20).astype(np.int32)
    ts = np.cumsum(rng.integers(1, 50, 20)).astype(np.int32)
    dt = jnp.dtype(cfg.dtype)

    # cold: first 8 events
    n0 = 8
    emb, k, v = _encode_full(cfg, dense, table, ids[:n0], ts[:n0])
    row_ts = np.zeros(S, np.int32)
    row_ts[:n0] = ts[:n0]
    pos = n0
    for step, q in enumerate([3, 1, 5, 3]):     # includes a 1-wide append
        new = slice(pos, pos + q)
        row_ts[pos:pos + q] = ts[new]
        x_new = jnp.take(table, jnp.asarray(ids[new]), axis=0).astype(dt)
        # warm windows are padded to the q-ladder bucket (min 2)
        q_cap = BucketLadder(S, min_size=2).bucket(q)
        xw = jnp.zeros((q_cap, cfg.d_model), dt).at[:q].set(x_new)
        emb, k, v = GR.gr_serve_row_append(
            dense, cfg, xw, jnp.asarray(row_ts), k, v,
            jnp.asarray(pos, jnp.int32), jnp.asarray(q, jnp.int32),
            kv_block=GR.serve_attn_block(S))
        pos += q
        femb, fk, fv = _encode_full(cfg, dense, table, ids[:pos], ts[:pos])
        np.testing.assert_array_equal(np.asarray(emb), np.asarray(femb))
        np.testing.assert_array_equal(np.asarray(k[:, :pos]),
                                      np.asarray(fk[:, :pos]))
        np.testing.assert_array_equal(np.asarray(v[:, :pos]),
                                      np.asarray(fv[:, :pos]))


def test_engine_parity_across_truncate_and_wraparound():
    """Streaming vs micro-batch engine on a trace that exercises seed,
    in-capacity appends (warm), ring wraparound and full replacement
    (cold fallback) — top-k ids, scores, and embeddings bit-identical."""
    cfg, dense, table_m = _tiny_model(max_seq_len=16)
    table = make_shadowed(table_m)
    rng = np.random.default_rng(3)
    users = list(range(6))
    hist = {u: (rng.integers(0, cfg.vocab_size, 40).astype(np.int32),
                np.cumsum(rng.integers(1, 40, 40)).astype(np.int32))
            for u in users}
    # rounds: seed 10 (cold), +3 (warm), +8 (wraparound → cold), +20
    # (full replace → cold), +2 (warm)
    cuts = [10, 13, 21, 41, 43]
    base = RecallEngine(cfg, dense, table, num_shards=2, users_per_shard=3,
                        k=15, retrieval_block=128, max_delay_ms=0.0)
    eng = StreamingRecallEngine(cfg, dense, table, max_users=8, k=15,
                                retrieval_block=128, max_rows_per_tick=4)
    prev = 0
    for cut in cuts:
        reqs = [(u, hist[u][0][prev:cut], hist[u][1][prev:cut])
                for u in users]
        br = {r.user: r for r in base.serve(reqs)}
        sr = {r.user: r for r in eng.serve(reqs)}
        for u in users:
            np.testing.assert_array_equal(br[u].item_ids, sr[u].item_ids)
            np.testing.assert_array_equal(br[u].scores, sr[u].scores)
            np.testing.assert_array_equal(
                np.asarray(br[u].user_emb, np.float32),
                np.asarray(sr[u].user_emb, np.float32))
        prev = cut
    st = eng.stats()
    assert st["encode"]["warm_rows"] > 0          # warm path exercised
    assert st["encode"]["cold_rows"] > 0
    assert st["compile"]["compiles"] > 0


def test_streaming_hit_skips_device_and_matches():
    cfg, dense, table_m = _tiny_model(max_seq_len=16)
    eng = StreamingRecallEngine(cfg, dense, make_shadowed(table_m),
                                max_users=4, k=10, retrieval_block=128,
                                max_rows_per_tick=4)
    ids = np.arange(1, 9, dtype=np.int32)
    ts = np.arange(1, 9, dtype=np.int32) * 10
    first = eng.serve([(0, ids, ts)])[0]
    rank0 = eng.rank_batches
    hit = eng.serve([(0, [], [])])[0]
    assert hit.cache_hit and eng.rank_batches == rank0   # no table scan
    np.testing.assert_array_equal(first.item_ids, hit.item_ids)
    np.testing.assert_array_equal(first.scores, hit.scores)


# --------------------------------------------------------------------------
# admission / scheduler honesty
# --------------------------------------------------------------------------

def test_admission_typed_outcomes():
    cfg, dense, table_m = _tiny_model(max_seq_len=16)
    eng = StreamingRecallEngine(cfg, dense, make_shadowed(table_m),
                                max_users=2, k=5, retrieval_block=128,
                                max_rows_per_tick=2, queue_limit=3,
                                admission="shed")
    ids = np.arange(1, 5, dtype=np.int32)
    ts = ids * 10
    a0 = eng.submit(0, ids, ts, now=0.0)
    a1 = eng.submit(1, ids, ts, now=0.0)
    assert a0.accepted and a1.accepted
    # slots full, shedding admission → shed_slots
    a2 = eng.submit(2, ids, ts, now=0.0)
    assert a2.outcome == "shed_slots" and not a2.accepted
    # queue_limit binds on in-flight work → shed_queue
    a3 = eng.submit(0, ids + 10, ts + 100, now=0.0)
    assert a3.accepted
    a4 = eng.submit(1, ids + 20, ts + 200, now=0.0)
    assert a4.outcome == "shed_queue"
    st = eng.stats()["admission"]
    assert st["shed_slots"] == 1 and st["shed_queue"] == 1
    eng.tick(now=1.0)

    # evicting engine: user 2 displaces someone; the displaced user's
    # next delta gets the one-shot resend_full handshake
    ev = StreamingRecallEngine(cfg, dense, make_shadowed(table_m),
                               max_users=1, k=5, retrieval_block=128,
                               max_rows_per_tick=2)
    ev.serve([(0, ids, ts)])
    ev.serve([(1, ids, ts)])                 # evicts user 0
    a = ev.submit(0, ids + 1, ts + 1, now=0.0)
    assert a.outcome == "resend_full" and not a.accepted
    a = ev.submit(0, ids, ts, now=0.0)       # full resend re-seeds
    assert a.accepted


def test_same_user_burst_coalesces_into_one_encode():
    cfg, dense, table_m = _tiny_model(max_seq_len=16)
    eng = StreamingRecallEngine(cfg, dense, make_shadowed(table_m),
                                max_users=4, k=5, retrieval_block=128,
                                max_rows_per_tick=4)
    rids = []
    for i in range(3):
        a = eng.submit(0, [i + 1], [10 * (i + 1)], now=0.0)
        rids.append(a.rid)
    res = eng.tick(now=1.0)
    # one encode row served all three requests, identical answers
    assert [r.rid for r in res] == rids
    assert eng.cold_rows == 1
    for r in res[1:]:
        np.testing.assert_array_equal(res[0].item_ids, r.item_ids)


def test_latency_stats_honest_under_overload():
    """p99 over completed requests must come with queue_depth and
    oldest-in-flight age, so an overloaded engine cannot look healthy."""
    s = ContinuousScheduler(max_rows_per_tick=1, queue_limit=100)
    for i in range(5):
        rid = s.admit(i, now=float(i))
        s.enqueue(i, rid)
    plan = s.form_tick(now=10.0, cost_of=lambda slot: ("cold", 1))
    assert plan.rows == 1                   # budget admits one
    done = [r for _, rids in plan.cold for r in rids]
    s.mark_done(done, now=10.5)
    st = s.latency_stats(now=20.0)
    assert st["count"] == 1
    assert st["queue_depth"] == 4           # admitted, not finished
    assert st["oldest_inflight_age_s"] == pytest.approx(19.0)
    occ = s.occupancy()
    assert occ["ticks"] == 1 and occ["row_utilization"] == 1.0


def test_form_tick_token_budget_never_deadlocks():
    s = ContinuousScheduler(max_rows_per_tick=4, max_tokens_per_tick=10)
    r0 = s.admit(0, 0.0)
    s.enqueue(0, r0)
    r1 = s.admit(1, 0.0)
    s.enqueue(1, r1)
    costs = {0: 25, 1: 3}                   # slot 0 alone exceeds budget
    plan = s.form_tick(0.0, lambda sl: ("cold", costs[sl]))
    # the over-budget first slot is force-admitted; the next spills
    assert [sl for sl, _ in plan.cold] == [0]
    plan2 = s.form_tick(0.0, lambda sl: ("cold", costs[sl]))
    assert [sl for sl, _ in plan2.cold] == [1]


# --------------------------------------------------------------------------
# serving partition specs — 8-fake-device compile verification
# --------------------------------------------------------------------------

@pytest.mark.slow_spmd
def test_gr_serve_specs_compile_on_8_device_mesh():
    out = run_spmd("""
        import json, jax
        assert len(jax.devices()) == 8
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        from repro.launch.dryrun import build_serve_cell
        rec = build_serve_cell("hstu-tiny", max_users=15, rows_per_tick=4,
                               append_window=4, mesh=mesh)
        print(json.dumps({"ok": rec["ok"], "specs": rec["specs"]}))
    """)
    assert out["ok"]
    # the layout is real, not a replicated fallback
    assert "data" in out["specs"]["tokens"]
    assert "model" in out["specs"]["kv_k"]
    assert "model" in out["specs"]["scan_table"]
