"""Batched recall-serving engine: scheduler → cached jagged encode →
sharded quantized top-k.

One :class:`RecallEngine` owns the full serving path for a trained GR
model:

  1. ``submit`` merges a request's new events into the incremental user-
     state cache (``state_cache.UserStateCache``). Unchanged users with a
     version-current embedding are **cache hits** — they skip packing and
     encoding entirely. Changed/new users enqueue their (ring-buffer-
     truncated) history with the request scheduler.
  2. ``step`` flushes the scheduler into capacity-bounded jagged micro-
     batches (LPT over the G serving shards) and runs the jitted serving
     forward — embedding lookup + ``gr_user_embeddings_sharded`` — once
     per micro-batch. The attention plan (``build_attn_plan``) is built
     once per micro-batch inside the forward and shared by every layer,
     exactly as in training. Encoded embeddings are written back to the
     cache.
  3. Requests needing a ranking are scored together by the sharded top-k
     scan over the FP16 shadow table (``retrieval.ShardedTopK``); cache
     hits whose top-k is version-current skip even that (the model and
     table are static, so the cached ranking is bit-identical) — a pure
     hit never touches the table. Results come back in submission order
     with per-request latency stamped into the scheduler's records.

Shapes are static per engine: (G, cap) packs and bucketed retrieval batch
sizes, so steady-state serving runs two compiled programs (encode,
retrieve) regardless of traffic mix.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.embedding import tables as ET
from repro.models import gr as GR
from repro.obs import Obs
from repro.obs.trace import NULL_SPAN
from repro.serving import retrieval as RT
from repro.serving.retrieval import ShardedTopK
from repro.serving.scheduler import (Admission, ContinuousScheduler,
                                     RequestScheduler)
from repro.serving.slot_buffer import (BucketLadder, CompileCache,
                                       SequenceBuffer)
from repro.serving.state_cache import UserStateCache


def _null_span(*args: Any, **kwargs: Any):
    return NULL_SPAN


def _obs_hooks(obs: Optional[Obs]):
    """(span_fn, registry) for an engine: both no-ops when obs is absent
    or disabled, so the uninstrumented path stays a constant lookup."""
    if obs is not None and obs.enabled:
        return obs.tracer.span, obs.metrics
    return _null_span, None


@dataclass
class ServeResult:
    rid: int
    user: int
    item_ids: np.ndarray      # (k,) int32, score-descending
    scores: np.ndarray        # (k,) fp32
    user_emb: np.ndarray      # (d,) the representation that was ranked
    cache_hit: bool


def _bucket(n: int) -> int:
    """Next power-of-two ≥ n: bounds retrieval recompiles to log₂ sizes."""
    b = 1
    while b < n:
        b <<= 1
    return b


class RecallEngine:
    """Serving engine over a trained (dense params, ShadowedTable) pair."""

    def __init__(self, cfg: ArchConfig, dense: Any, table: Any, *,
                 num_shards: int = 1, users_per_shard: int = 8,
                 tokens_per_shard: Optional[int] = None,
                 k: int = 100, retrieval_block: int = 4096,
                 use_shadow: bool = True, max_delay_ms: float = 10.0,
                 attn_fn: Optional[Callable] = None,
                 cache_users: Optional[int] = None,
                 obs: Optional[Obs] = None):
        self.cfg = cfg
        self.dense = dense
        self.obs = obs
        self._span, self._mx = _obs_hooks(obs)
        if isinstance(table, ET.ShadowedTable):
            self.table = table
        else:
            # serving-only construction from a raw master: no (V, D) fp32
            # AdaGrad accumulator (only the training optimizer reads it),
            # and the bf16 shadow only if retrieval will scan it — dead
            # state at production vocab sizes otherwise
            self.table = ET.ShadowedTable(
                master=table,
                shadow=(ET.shadow_of(table, ET.SHADOW_DTYPE) if use_shadow
                        else None),
                accum=jnp.zeros((0, table.shape[-1]), jnp.float32))
        self.k = k
        self.num_shards = num_shards
        self.users_per_shard = users_per_shard
        self.scheduler = RequestScheduler(
            num_shards, users_per_shard, cfg.max_seq_len,
            tokens_per_shard=tokens_per_shard, max_delay_ms=max_delay_ms)
        self.cache = UserStateCache(cfg.max_seq_len, max_users=cache_users)
        self.retriever = ShardedTopK(
            k, block_v=min(retrieval_block, self.table.master.shape[0]),
            use_shadow=use_shadow)
        # (rid, user, embedding, cached top-k or None, version) — all
        # snapshotted at submit time so a later LRU eviction (or a
        # same-user append) between submit and step cannot corrupt a
        # recorded hit
        self._hits: List[Tuple[int, int, np.ndarray,
                               Optional[Tuple[np.ndarray, np.ndarray]],
                               int]] = []
        # rid → history version the request's encode was snapshotted at;
        # store() stamps this so events that arrive while an encode is in
        # flight (or a same-user request later in the pack) can never be
        # masked by a stale embedding marked fresh
        self._snap_version: Dict[int, int] = {}
        self.encoded_batches = 0
        self.retrieval_batches = 0

        if attn_fn is None:
            attn_fn = GR.default_attn_fn(cfg)
        dtype = jnp.dtype(cfg.dtype)

        def encode(dense_p, master, ids, offsets, ts, last_pos):
            x = ET.lookup(master, ids, dtype=dtype)           # (G, cap, d)
            return GR.gr_user_embeddings_sharded(
                dense_p, cfg, x, offsets, ts, last_pos, attn_fn=attn_fn)

        self._encode = jax.jit(encode)

    # -- request side ------------------------------------------------------
    def submit(self, user: int, new_ids: Sequence[int] = (),
               new_ts: Sequence[int] = (), *,
               now: Optional[float] = None) -> int:
        """Merge new events for ``user`` and enqueue if re-encoding is
        needed; returns the request id.

        Raises KeyError for a user whose cached state was LRU-evicted:
        a delta cannot reconstruct their history, and silently re-seeding
        from the delta would serve garbage recommendations. The flag
        clears on the rejection, so the client's retry with the full
        history re-seeds normally."""
        if self.cache.get(user) is None:
            # reject before touching the cache: a failed insert would
            # still create a UserState (skewing the miss count and, with
            # an LRU bound, possibly evicting a warm user)
            if self.cache.take_evicted(user):
                raise KeyError(
                    f"user {user}: cached state was evicted — resend the "
                    f"full history")
            if np.asarray(new_ids).size == 0:
                raise ValueError(f"user {user}: request with no history")
        st, needs_encode = self.cache.update(user, new_ids, new_ts)
        if not needs_encode:
            rid = self.scheduler.record_hit(user, now=now)
            self._hits.append((rid, user, st.fresh_embedding(),
                               st.fresh_topk(), st.version))
            return rid
        ids, ts = st.history()
        if ids.size == 0:
            raise ValueError(f"user {user}: request with no history")
        rid = self.scheduler.submit(user, ids, ts, now=now)
        self._snap_version[rid] = st.version
        return rid

    # -- serving step ------------------------------------------------------
    def step(self, *, force: bool = False,
             now: Optional[float] = None) -> List[ServeResult]:
        """Encode + rank everything currently servable. The encode queue
        packs only when the flush policy fires (or ``force=True``); cache
        hits need no encode, so they are always servable and never wait on
        the batching policy. Returns results in submission (rid) order."""
        run_flush = force or self.scheduler.ready(now)
        if not (run_flush or self._hits):
            return []
        # pending: (rid, user, hit, emb, snap_version) → needs the table
        # scan; done: finished ServeResults (hits with a version-current
        # cached top-k skip retrieval entirely — with a static model and
        # table their ranking is bit-identical to recomputing it)
        pending: List[Tuple[int, int, bool, np.ndarray, Optional[int]]] = []
        results: List[ServeResult] = []
        if run_flush:
            # dispatch every micro-batch before the first device→host
            # copy: jax dispatch is async, so encode k+1 overlaps the
            # transfer of k instead of serializing behind it
            mbs = self.scheduler.flush(now)
            with self._span("encode", "serve_encode", batches=len(mbs)):
                outs = []
                for mb in mbs:
                    outs.append(self._encode(
                        self.dense, self.table.master,
                        jnp.asarray(mb.ids), jnp.asarray(mb.offsets),
                        jnp.asarray(mb.timestamps),
                        jnp.asarray(mb.last_pos)))
                    self.encoded_batches += 1
                for mb, out in zip(mbs, outs):
                    out = np.asarray(out)
                    for s in mb.slots:
                        # copy, not view: caching a view would pin the
                        # whole (G, S, d) batch buffer for as long as any
                        # one of its users stays cached
                        e = out[s.shard, s.row].copy()
                        ver = self._snap_version.pop(s.rid, None)
                        self.cache.store(s.user, e, ver)
                        pending.append((s.rid, s.user, False, e, ver))
        for rid, user, emb, topk, ver in self._hits:
            if topk is not None:
                # hand the caller copies — these arrays live in the cache,
                # and a caller sorting/mutating its result in place must
                # not corrupt the next hit's "bit-identical" ranking
                results.append(ServeResult(rid=rid, user=user,
                                           item_ids=topk[0].copy(),
                                           scores=topk[1].copy(),
                                           user_emb=emb.copy(),
                                           cache_hit=True))
            else:
                pending.append((rid, user, True, emb, ver))
        self._hits = []
        if not (pending or results):
            return []

        if pending:
            B = len(pending)
            with self._span("retrieval", "serve_rank", batch=B):
                d = pending[0][3].shape[-1]
                E = np.zeros((_bucket(B), d), np.float32)
                E[:B] = np.stack([p[3] for p in pending]).astype(np.float32)
                vals, idx = self.retriever(self.table, jnp.asarray(E))
                self.retrieval_batches += 1
                vals = np.asarray(vals[:B])
                idx = np.asarray(idx[:B])
            for i, (rid, user, hit, emb, ver) in enumerate(pending):
                self.cache.store_topk(user, idx[i], vals[i], ver)
                # emb is the cached object — results get their own copy
                results.append(ServeResult(rid=rid, user=user,
                                           item_ids=idx[i], scores=vals[i],
                                           user_emb=emb.copy(),
                                           cache_hit=hit))

        done = time.monotonic() if now is None else now
        self.scheduler.mark_done([r.rid for r in results], now=done)
        results.sort(key=lambda r: r.rid)
        return results

    def serve(self, requests: Sequence[Tuple[int, Sequence[int],
                                             Sequence[int]]], *,
              now: Optional[float] = None) -> List[ServeResult]:
        """Synchronous convenience: submit ``(user, new_ids, new_ts)``
        triples, force one step, return results in request order.

        Atomic with respect to bad input: every request is validated
        before any is enqueued, so a rejected batch strands nothing in
        the queue and a later serve() returns exactly one result per
        request (zipping requests to results positionally stays safe)."""
        evicted: List[int] = []
        seeded: set = set()     # users given history EARLIER in this batch
        for user, ids, ts in requests:
            n_ids = np.asarray(ids, np.int32).size
            n_ts = np.asarray(ts, np.int32).size
            if n_ids != n_ts:
                raise ValueError(f"user {user}: event delta mismatch: "
                                 f"{n_ids} ids, {n_ts} ts")
            if self.cache.get(user) is None and user not in seeded:
                if self.cache.is_evicted(user):
                    evicted.append(user)
                elif n_ids == 0:
                    raise ValueError(
                        f"user {user}: request with no history")
            if n_ids or self.cache.get(user) is not None:
                seeded.add(user)
        if evicted:
            # consume the one-rejection handshake only for the users this
            # batch is actually rejected over — their retry re-seeds
            for u in evicted:
                self.cache.take_evicted(u)
            raise KeyError(f"users {evicted}: cached state was evicted — "
                           f"resend the full histories")
        # pin the batch against LRU eviction: new users inserted by
        # earlier submits must not evict later members of the same batch
        # (which would turn their validated state into a mid-batch
        # KeyError and strand the earlier requests in the queue)
        with self.cache.pinned(u for u, _, _ in requests):
            for user, ids, ts in requests:
                self.submit(user, ids, ts, now=now)
            return self.step(force=True, now=now)

    # -- accounting --------------------------------------------------------
    def stats(self) -> Dict[str, Any]:
        out = {"latency": self.scheduler.latency_stats(),
               "cache": self.cache.stats(),
               "encoded_batches": self.encoded_batches,
               "retrieval_table_dtype":
                   str(self.retriever.scan_table(self.table).dtype)}
        if self._mx is not None:
            # mirror into the registry; the dict itself is returned
            # unchanged (thin-view contract for existing callers)
            self._mx.publish("serve", out)
        return out


# --------------------------------------------------------------------------
# continuous-batching engine
# --------------------------------------------------------------------------

class StreamingRecallEngine:
    """Continuous-batching serving over a persistent device-resident
    :class:`SequenceBuffer`.

    Where :class:`RecallEngine` re-packs every changed user's full history
    into transient jagged micro-batches, this engine keeps user sequences
    *on device* in slot rows and moves only deltas:

      * ``submit`` is open-loop admission — it never blocks and returns a
        typed :class:`Admission` (accepted / shed_queue / shed_slots /
        resend_full) instead of raising on overload. New events are merged
        into the user's slot (host mirror + version bump); the encode work
        is attached to the *slot*, so a burst of same-user requests
        coalesces into one encode.
      * ``tick`` forms one budget-bounded batch (``ContinuousScheduler.
        form_tick``), runs the cold path (full re-encode of seeded /
        truncated slots, seeding the K/V prefix caches) and the warm path
        (``gr_append_slots``: encode only the appended window against the
        cached prefix — bit-identical to the full re-encode by the per-
        query-count attention normalization), then ranks every finished
        slot straight from the device embedding buffer
        (``retrieval.topk_from_slots`` — user embeddings never stage
        through the host).

    All device steps run at bucketed shapes from a shared
    :class:`BucketLadder` and are counted by an explicit
    :class:`CompileCache`, so the open-loop benchmark can report the
    recompile count. Persistent buffers are donated to the jitted steps —
    XLA updates them in place instead of copying the (N, S) state each
    tick.

    On identical traces the results are bit-identical to
    :class:`RecallEngine` (tests/test_serving_stream.py): same lookup, same
    blocked attention order, same blocked top-k over the same scan table.
    """

    def __init__(self, cfg: ArchConfig, dense: Any, table: Any, *,
                 max_users: int = 256, k: int = 100,
                 retrieval_block: int = 4096, use_shadow: bool = True,
                 max_rows_per_tick: int = 32,
                 max_tokens_per_tick: Optional[int] = None,
                 queue_limit: Optional[int] = None,
                 admission: str = "evict",
                 prefix_reuse: bool = True,
                 attn_fn: Optional[Callable] = None,
                 obs: Optional[Obs] = None):
        if admission not in ("evict", "shed"):
            raise ValueError(f"admission policy {admission!r}")
        self.cfg = cfg
        self.dense = dense
        self.obs = obs
        self._span, self._mx = _obs_hooks(obs)
        if isinstance(table, ET.ShadowedTable):
            self.table = table
        else:
            self.table = ET.ShadowedTable(
                master=table,
                shadow=(ET.shadow_of(table, ET.SHADOW_DTYPE) if use_shadow
                        else None),
                accum=jnp.zeros((0, table.shape[-1]), jnp.float32))
        self.k = k
        self.admission = admission
        # the warm path needs per-layer K/V projections, which only the
        # HSTU block exposes — other blocks fall back to cold-only serving
        self.prefix_reuse = bool(prefix_reuse) and (cfg.gr_block == "hstu")
        S = cfg.max_seq_len
        dqk = cfg.qkv_dim or cfg.resolved_head_dim
        kv_shape = ((cfg.num_layers, cfg.num_heads, dqk, dqk)
                    if self.prefix_reuse else None)
        self.buffer = SequenceBuffer(max_users, S, cfg.d_model,
                                     dtype=cfg.dtype, kv_shape=kv_shape)
        self.sched = ContinuousScheduler(
            max_rows_per_tick=max_rows_per_tick,
            max_tokens_per_tick=max_tokens_per_tick,
            queue_limit=(queue_limit if queue_limit is not None
                         else max(4 * max_users, 64)))
        # one ladder shared by the encode row axis and the retrieval batch
        # axis; a separate ladder for the warm append window (token axis).
        # min_size=2 on the append window: a 1-wide einsum takes a
        # different XLA contraction path whose bits differ from the full
        # computation, so warm windows are padded to ≥ 2 queries.
        self.row_ladder = BucketLadder(max_rows_per_tick)
        self.q_ladder = BucketLadder(S, min_size=min(2, S))
        self.compile_cache = CompileCache()
        self.retriever = ShardedTopK(
            k, block_v=min(retrieval_block, self.table.master.shape[0]),
            use_shadow=use_shadow)
        self._block_v = self.retriever.block_v
        # host mirror of the embedding rows, filled at rank time — what
        # cache-hit ServeResults carry without touching the device
        self._h_emb: Dict[int, np.ndarray] = {}
        # (rid, user, slot, (ids, scores)) answered from the top-k cache
        self._ready: List[Tuple[int, int, int,
                                Tuple[np.ndarray, np.ndarray]]] = []
        self.warm_rows = self.cold_rows = 0
        self.warm_tokens = self.cold_tokens = 0
        self.rank_batches = 0

        dtype = jnp.dtype(cfg.dtype)
        eff = GR.serve_attn_block(S)

        if self.prefix_reuse:
            def cold_step(dense_p, master, tokens, ts_buf, emb, kv_k, kv_v,
                          rows, row_ids, row_ts, lengths):
                tokens = tokens.at[rows].set(row_ids)
                ts_buf = ts_buf.at[rows].set(row_ts)
                x = ET.lookup(master, row_ids, dtype=dtype)
                e, kr, vr = GR.gr_encode_slots(dense_p, cfg, x, row_ts,
                                               lengths, attn_block=eff)
                return (tokens, ts_buf, emb.at[rows].set(e),
                        kv_k.at[rows].set(kr), kv_v.at[rows].set(vr))

            def warm_step(dense_p, master, tokens, ts_buf, emb, kv_k, kv_v,
                          rows, new_ids, new_ts, pref, nnew):
                # scatter the append window into the slot token/ts rows,
                # then encode only that window against the cached prefix
                upd = jax.vmap(lambda r, u, p:
                               jax.lax.dynamic_update_slice(r, u, (p,)))
                tok_rows = upd(tokens[rows], new_ids, pref)
                ts_rows = upd(ts_buf[rows], new_ts, pref)
                x_new = ET.lookup(master, new_ids, dtype=dtype)
                e, kr, vr = GR.gr_append_slots(
                    dense_p, cfg, x_new, ts_rows, kv_k[rows], kv_v[rows],
                    pref, nnew, kv_block=eff)
                return (tokens.at[rows].set(tok_rows),
                        ts_buf.at[rows].set(ts_rows),
                        emb.at[rows].set(e),
                        kv_k.at[rows].set(kr), kv_v.at[rows].set(vr))

            self._cold_fn = jax.jit(cold_step, donate_argnums=(2, 3, 4, 5, 6))
            self._warm_fn = jax.jit(warm_step, donate_argnums=(2, 3, 4, 5, 6))
        else:
            def cold_flat(dense_p, master, tokens, ts_buf, emb,
                          rows, row_ids, row_ts, lengths):
                tokens = tokens.at[rows].set(row_ids)
                ts_buf = ts_buf.at[rows].set(row_ts)
                x = ET.lookup(master, row_ids, dtype=dtype)
                e = GR.gr_encode_slots_flat(dense_p, cfg, x, row_ts, lengths,
                                            attn_fn=attn_fn)
                return tokens, ts_buf, emb.at[rows].set(e)

            self._cold_fn = jax.jit(cold_flat, donate_argnums=(2, 3, 4))
            self._warm_fn = None

        def rank_step(emb_buf, rows, scan_table):
            return RT.topk_from_slots(emb_buf, rows, scan_table,
                                      k=k, block_v=self._block_v)

        self._rank_fn = jax.jit(rank_step)

    def warmup(self, q_caps: Sequence[int] = ()) -> int:
        """Precompile the bucket ladder — cold encode and rank at every
        row rung, plus (with prefix reuse) each warm append-window bucket
        in ``q_caps`` — by running the jitted steps against the scratch
        row. A long-running engine calls this once at startup so
        steady-state traffic never stalls on an XLA compile (a mid-tick
        compile is a multi-hundred-ms admission-control event: arrivals
        keep landing while the engine is stuck in the compiler). Returns
        the number of programs compiled."""
        b = self.buffer
        S = b.max_seq_len
        before = self.compile_cache.compiles
        scan = self.retriever.scan_table(self.table)
        qs = sorted({self.q_ladder.bucket(q) for q in q_caps})
        for R in self.row_ladder.rungs:
            rows = jnp.full((R,), b.pad_row, jnp.int32)
            ids = jnp.zeros((R, S), jnp.int32)
            ts = jnp.zeros((R, S), jnp.int32)
            ones = jnp.ones((R,), jnp.int32)
            fn = self.compile_cache.get("cold", (R,), lambda: self._cold_fn)
            if self.prefix_reuse:
                (b.tokens, b.timestamps, b.emb, b.kv_k, b.kv_v) = fn(
                    self.dense, self.table.master, b.tokens, b.timestamps,
                    b.emb, b.kv_k, b.kv_v, rows, ids, ts, ones)
                for q in qs:
                    wfn = self.compile_cache.get("warm", (R, q),
                                                 lambda: self._warm_fn)
                    (b.tokens, b.timestamps, b.emb, b.kv_k, b.kv_v) = wfn(
                        self.dense, self.table.master, b.tokens,
                        b.timestamps, b.emb, b.kv_k, b.kv_v, rows,
                        jnp.zeros((R, q), jnp.int32),
                        jnp.zeros((R, q), jnp.int32), ones, ones)
            else:
                (b.tokens, b.timestamps, b.emb) = fn(
                    self.dense, self.table.master, b.tokens, b.timestamps,
                    b.emb, rows, ids, ts, ones)
            rfn = self.compile_cache.get("rank", (R,), lambda: self._rank_fn)
            rfn(b.emb, rows, scan)
        return self.compile_cache.compiles - before

    # -- request side ------------------------------------------------------

    def submit(self, user: int, new_ids: Sequence[int] = (),
               new_ts: Sequence[int] = (), *,
               now: Optional[float] = None) -> Admission:
        """Open-loop admission of one request. Never blocks, never raises
        on overload — returns a typed :class:`Admission`. Malformed input
        (mismatched delta, unknown user with no history) still raises:
        that is a caller bug, not traffic."""
        now = time.monotonic() if now is None else now
        ids = np.asarray(new_ids, np.int32)
        ts = np.asarray(new_ts, np.int32)
        if ids.size != ts.size:
            raise ValueError(f"user {user}: event delta mismatch: "
                             f"{ids.size} ids, {ts.size} ts")
        slot = self.buffer.slot_of(user)
        if slot is None:
            if self.buffer.take_evicted(user):
                # the delta cannot rebuild an evicted history — typed
                # outcome (reported once per eviction), not an exception
                self.sched.shed("resend_full")
                return Admission(None, "resend_full", user)
            if ids.size == 0:
                raise ValueError(f"user {user}: request with no history")
            if not self.sched.has_capacity():
                self.sched.shed("shed_queue")
                return Admission(None, "shed_queue", user)
            slot = self.buffer.alloc(user, evict=(self.admission == "evict"),
                                     busy=self.sched.busy_slots())
            if slot is None:
                self.sched.shed("shed_slots")
                return Admission(None, "shed_slots", user)
            self.buffer.seed(slot, ids, ts)
            rid = self.sched.admit(user, now)
            self.sched.enqueue(slot, rid)
            return Admission(rid, "accepted", user)
        if not self.sched.has_capacity():
            self.sched.shed("shed_queue")
            return Admission(None, "shed_queue", user)
        self.buffer.touch(slot)
        if ids.size:
            self.buffer.append(slot, ids, ts)
            rid = self.sched.admit(user, now)
            self.sched.enqueue(slot, rid)
            return Admission(rid, "accepted", user)
        if self.buffer.emb_fresh(slot):
            rid = self.sched.admit(user, now, hit=True)
            cached = self.buffer.topk(slot)
            if cached is not None:
                # pure hit: version-current top-k — never touches the
                # device; dispatched the instant it was admitted
                self.sched.records[rid]["t_dispatch"] = now
                self._ready.append((rid, user, slot, cached))
            else:
                self.sched.enqueue_rank(slot, rid)
            return Admission(rid, "accepted", user, hit=True)
        # no new events but the embedding is stale (events arrived earlier
        # and the slot has not ticked yet) — join the slot's encode work
        rid = self.sched.admit(user, now)
        self.sched.enqueue(slot, rid)
        return Admission(rid, "accepted", user)

    # -- tick --------------------------------------------------------------

    def _cost_of(self, slot: int) -> Tuple[str, int]:
        pend = self.buffer.pending_new(slot)
        if (self.prefix_reuse and pend > 0
                and self.buffer.warm_eligible(
                    slot, self.q_ladder.bucket(min(pend,
                                                  self.buffer.max_seq_len)))):
            return "warm", pend
        return "cold", max(int(self.buffer.length[slot]), 1)

    def tick(self, *, now: Optional[float] = None) -> List[ServeResult]:
        """Run one continuous-batching step: form a budget-bounded tick,
        encode its cold and warm rows, rank every finished slot from the
        device embedding buffer, and return results in rid order."""
        with self._span("tick", "serve"):
            return self._tick(now=now)

    def _tick(self, *, now: Optional[float] = None) -> List[ServeResult]:
        now = time.monotonic() if now is None else now
        results: List[ServeResult] = []
        for rid, user, slot, (tids, tscores) in self._ready:
            results.append(ServeResult(
                rid=rid, user=user, item_ids=tids.copy(),
                scores=tscores.copy(), user_emb=self._h_emb[slot].copy(),
                cache_hit=True))
        self._ready = []
        plan = self.sched.form_tick(now, self._cost_of)
        rank_items: List[Tuple[int, List[int], bool]] = []
        if not plan.empty:
            warm, cold = plan.warm, list(plan.cold)
            q_cap = 0
            if warm:
                q_cap = self.q_ladder.bucket(
                    max(max(self.buffer.pending_new(s) for s, _ in warm), 1))
                # demote rows the *bucketed* window no longer fits (the
                # per-slot eligibility probe used a smaller bucket)
                keep = []
                for slot, rids in warm:
                    if self.buffer.warm_eligible(slot, q_cap):
                        keep.append((slot, rids))
                    else:
                        cold.append((slot, rids))
                warm = keep
            if cold:
                self._run_cold(cold)
            if warm:
                self._run_warm(warm, q_cap)
            for slot, rids in cold + warm:
                hit = False
                rank_items.append((slot, rids, hit))
        for slot, rids in plan.rank_only:
            rank_items.append((slot, rids, True))
        if rank_items:
            results.extend(self._rank(rank_items))
        self.sched.mark_done([r.rid for r in results], now=now)
        results.sort(key=lambda r: r.rid)
        return results

    def _run_cold(self, items: List[Tuple[int, List[int]]]) -> None:
        with self._span("encode_cold", "serve_encode", rows=len(items)):
            self._run_cold_impl(items)

    def _run_cold_impl(self, items: List[Tuple[int, List[int]]]) -> None:
        slots = [s for s, _ in items]
        R = self.row_ladder.bucket(len(slots))
        S = self.buffer.max_seq_len
        rows = np.full(R, self.buffer.pad_row, np.int32)
        rows[:len(slots)] = slots
        row_ids = np.zeros((R, S), np.int32)
        row_ts = np.zeros((R, S), np.int32)
        lengths = np.zeros(R, np.int32)
        for i, s in enumerate(slots):
            row_ids[i] = self.buffer.h_ids[s]
            row_ts[i] = self.buffer.h_ts[s]
            lengths[i] = self.buffer.length[s]
        fn = self.compile_cache.get("cold", (R,), lambda: self._cold_fn)
        b = self.buffer
        if self.prefix_reuse:
            (b.tokens, b.timestamps, b.emb, b.kv_k, b.kv_v) = fn(
                self.dense, self.table.master, b.tokens, b.timestamps,
                b.emb, b.kv_k, b.kv_v, jnp.asarray(rows),
                jnp.asarray(row_ids), jnp.asarray(row_ts),
                jnp.asarray(lengths))
        else:
            (b.tokens, b.timestamps, b.emb) = fn(
                self.dense, self.table.master, b.tokens, b.timestamps,
                b.emb, jnp.asarray(rows), jnp.asarray(row_ids),
                jnp.asarray(row_ts), jnp.asarray(lengths))
        for s in slots:
            b.mark_encoded(s)
        self.cold_rows += len(slots)
        self.cold_tokens += int(lengths.sum())

    def _run_warm(self, items: List[Tuple[int, List[int]]],
                  q_cap: int) -> None:
        with self._span("encode_warm", "serve_encode",
                        rows=len(items), q_cap=q_cap):
            self._run_warm_impl(items, q_cap)

    def _run_warm_impl(self, items: List[Tuple[int, List[int]]],
                       q_cap: int) -> None:
        slots = [s for s, _ in items]
        R = self.row_ladder.bucket(len(slots))
        rows = np.full(R, self.buffer.pad_row, np.int32)
        rows[:len(slots)] = slots
        new_ids = np.zeros((R, q_cap), np.int32)
        new_ts = np.zeros((R, q_cap), np.int32)
        pref = np.zeros(R, np.int32)
        nnew = np.zeros(R, np.int32)
        b = self.buffer
        for i, s in enumerate(slots):
            el = int(b.enc_len[s])
            L = int(b.length[s])
            n = L - el
            new_ids[i, :n] = b.h_ids[s, el:L]
            new_ts[i, :n] = b.h_ts[s, el:L]
            pref[i] = el
            nnew[i] = n
            self.warm_tokens += n
        fn = self.compile_cache.get("warm", (R, q_cap),
                                    lambda: self._warm_fn)
        (b.tokens, b.timestamps, b.emb, b.kv_k, b.kv_v) = fn(
            self.dense, self.table.master, b.tokens, b.timestamps, b.emb,
            b.kv_k, b.kv_v, jnp.asarray(rows), jnp.asarray(new_ids),
            jnp.asarray(new_ts), jnp.asarray(pref), jnp.asarray(nnew))
        for s in slots:
            b.mark_encoded(s)
        self.warm_rows += len(slots)

    def _rank(self, items: List[Tuple[int, List[int], bool]]
              ) -> List[ServeResult]:
        """Rank finished slots straight from the device embedding buffer,
        in row-ladder-bounded bucketed chunks."""
        with self._span("rank", "serve_rank", slots=len(items)):
            return self._rank_impl(items)

    def _rank_impl(self, items: List[Tuple[int, List[int], bool]]
                   ) -> List[ServeResult]:
        results: List[ServeResult] = []
        scan = self.retriever.scan_table(self.table)
        cap = self.row_ladder.max_size
        for lo in range(0, len(items), cap):
            chunk = items[lo:lo + cap]
            slots = [s for s, _, _ in chunk]
            B = self.row_ladder.bucket(len(slots))
            rows = np.full(B, self.buffer.pad_row, np.int32)
            rows[:len(slots)] = slots
            fn = self.compile_cache.get("rank", (B,), lambda: self._rank_fn)
            vals, idx, q = fn(self.buffer.emb, jnp.asarray(rows), scan)
            self.rank_batches += 1
            vals = np.asarray(vals[:len(slots)])
            idx = np.asarray(idx[:len(slots)])
            q = np.asarray(q[:len(slots)])
            for i, (slot, rids, hit) in enumerate(chunk):
                self.buffer.store_topk(slot, idx[i], vals[i])
                self._h_emb[slot] = q[i]
                user = int(self.buffer.user[slot])
                for rid in rids:
                    results.append(ServeResult(
                        rid=rid, user=user, item_ids=idx[i].copy(),
                        scores=vals[i].copy(), user_emb=q[i].copy(),
                        cache_hit=hit))
        return results

    # -- convenience / accounting ------------------------------------------

    @property
    def pending(self) -> bool:
        return bool(self._ready or self.sched.queued_slots
                    or self.sched._rank_only)

    def serve(self, requests: Sequence[Tuple[int, Sequence[int],
                                             Sequence[int]]], *,
              now: Optional[float] = None) -> List[ServeResult]:
        """Closed-loop convenience (the parity-test entry): submit every
        ``(user, new_ids, new_ts)`` triple, tick until drained, return
        results in rid order. Raises if any request is shed — parity
        traces must size capacity so nothing sheds."""
        admissions = [self.submit(u, i, t, now=now) for u, i, t in requests]
        rejected = [a for a in admissions if not a.accepted]
        if rejected:
            raise RuntimeError(
                f"closed-loop serve shed {len(rejected)} requests: "
                f"{[(a.user, a.outcome) for a in rejected]}")
        out: List[ServeResult] = []
        while self.pending:
            out.extend(self.tick(now=now))
        out.sort(key=lambda r: r.rid)
        return out

    def stats(self) -> Dict[str, Any]:
        out = {
            "latency": self.sched.latency_stats(),
            "admission": dict(self.sched.outcomes),
            "occupancy": {**self.sched.occupancy(), **self.buffer.stats()},
            "compile": self.compile_cache.stats(),
            "encode": {"warm_rows": self.warm_rows,
                       "cold_rows": self.cold_rows,
                       "warm_tokens": self.warm_tokens,
                       "cold_tokens": self.cold_tokens,
                       "rank_batches": self.rank_batches,
                       "prefix_reuse": self.prefix_reuse},
            "retrieval_table_dtype":
                str(self.retriever.scan_table(self.table).dtype),
        }
        if self._mx is not None:
            # mirror into the registry; the dict itself is returned
            # unchanged (thin-view contract for existing callers)
            self._mx.publish("serve", out)
        return out
