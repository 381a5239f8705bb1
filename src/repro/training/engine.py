"""Staged GR execution engine — Algorithm 1 (§4.2.3) on real work.

:class:`GREngine` is the single training entrypoint for the GR workload:
it wires the jagged loader, the host unique stage and the staged train
step (:func:`repro.training.trainer.make_gr_stages`) into the six-stage
pipeline executor, so the model actually executes Algorithm 1 — host
stages (dataload, candidate unique) on the executor's thread pool,
device stages (emb_fwd, dense fwd/bwd, emb_bwd) async-dispatched on the
main thread — and every :class:`repro.core.pipeline.StageEvent` comes
from real work, which is what lets ``timeline_report`` reproduce
Table 6's computing / comm / not-overlapped / free breakdown on the real
workload instead of a sleep simulator.

Stage mapping (single-process JAX; hook names are Algorithm 1's):

    dataload   GRLoader / data_fn → numpy jagged batch       (host pool)
    a2a        host→device feature transfer of the batch      (host pool)
    unique     candidate-id dedup sort (host_unique_candidates,
               the per-shard dedup hsp.unique_accumulate runs
               before the sparse gradient exchange)           (host pool)
    emb_fwd    input-side table gather — the τ=1-stale
               prefetched read (§4.2.2)                       (device)
    dense_fwd  jagged model fwd + fused sampled-softmax loss
               + grads, async-dispatched                      (device)
    dense_bwd  realization of the dispatched fwd+bwd          (device)
    emb_bwd    _table_grad_pairs + AdamW + row-sparse AdaGrad (device)

The τ=1 carry is an explicit cross-batch artifact: ``dense_bwd(i)``'s
sparse (id, row) pairs land on the table in ``emb_bwd(i)`` (Algorithm 1
line 3), one statement before ``dense_fwd(i+1)`` — while ``emb_fwd(i+1)``
already gathered its input rows a step earlier, which is exactly the
one-step-stale input read of the semi-async schedule. With
``schedule="flat"`` the same stage functions run serially one batch at a
time (the pre-engine loop); both schedules are bit-identical to the
fused single-jit :func:`make_gr_train_step` — losses and the final
:class:`GRTrainState` (master, shadow, AdaGrad accum, pending pairs)
match exactly, sync and τ=1.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from jax.profiler import TraceAnnotation

from repro.core.hsp import HSPLookup
from repro.core.pipeline import (PipelineHooks, STAGES, SixStagePipeline,
                                 StageEvent,
                                 timeline_report as _timeline_report)
from repro.embedding import cache as EC
from repro.embedding import tables as ET
from repro.launch.roofline import device_peak, gr_model_flops
from repro.obs import Obs
from repro.obs.derived import measured_mfu, token_imbalance
from repro.obs.trace import stage_span
from repro.training import resilience as R
from repro.training.trainer import (GRTrainState, gr_pending_slots,
                                    gr_train_state, host_unique_candidates,
                                    make_gr_stages, make_gr_train_step)

SCHEDULES = ("algorithm1", "flat")


def _bundle_loss_fn(bundle, loss_kwargs: Optional[Dict[str, Any]]):
    lk = dict(loss_kwargs or {})
    return lambda d, t, b, **kw: bundle.loss(d, t, b, **{**lk, **kw})


def _hsp_for(loss_kwargs: Optional[Dict[str, Any]]) -> Optional[HSPLookup]:
    """The HSP lookup whose mesh the stages run on chip by chip: an HSP
    ``lookup_fn`` over several chips with the fused negatives, which the
    owners of the rows compute. Any other loss keeps the whole-mesh
    program (the HSP lookup's own shard_map, the rest partitioned by the
    compiler), as one chip does. One rule for the flat oracle and the
    pipelined engine."""
    lk = dict(loss_kwargs or {})
    lookup = lk.get("lookup_fn")
    if (isinstance(lookup, HSPLookup) and lookup.spans_chips
            and lk.get("neg_mode", "fused") == "fused"):
        return lookup
    return None


def _input_gather_for(bundle, loss_kwargs: Optional[Dict[str, Any]]):
    """The staged input gather, or None when it must stay inline: a custom
    ``lookup_fn`` (e.g. the HSP sparse exchange) is a custom-vjp function
    the emb_bwd stage cannot linearly transpose, so its gather is
    differentiated inside the dense stage instead. One rule, shared by
    the flat oracle and the pipelined engine — they must never disagree
    on dataflow mode."""
    if dict(loss_kwargs or {}).get("lookup_fn") is not None:
        return None
    return lambda t, b: bundle.input_gather(t, b)


def make_gr_step_fn(bundle, *, loss_kwargs: Optional[Dict[str, Any]] = None,
                    lr_dense: float = 4e-3, lr_sparse: float = 4e-3,
                    semi_async: bool = True, jit: bool = True):
    """The engine's flat fused train step as a standalone
    ``(state, batch) -> (state, metrics)`` function.

    This is the single-jit composition of the Algorithm-1 stage functions
    — what ``GREngine(schedule="flat")`` computes and what the pipelined
    schedule is verified bit-identical against. Entrypoints that need a
    bare step (the elastic runner, the multi-pod dry-run) build it here
    so every trainer in the repo shares one staged implementation.
    """
    lk = dict(loss_kwargs or {})
    input_gather = _input_gather_for(bundle, lk)
    step = make_gr_train_step(_bundle_loss_fn(bundle, lk),
                              lr_dense=lr_dense, lr_sparse=lr_sparse,
                              semi_async=semi_async,
                              input_gather=input_gather, hsp=_hsp_for(lk))
    return jax.jit(step) if jit else step


class GREngine:
    """Unified staged training engine for the GR workload.

    Parameters
    ----------
    bundle: ``GRBundle`` (model + loss).
    data: a ``GRLoader`` (its ``batches(steps)`` iterator feeds the
        dataload stage) or a callable ``data_fn(i) -> batch`` producing
        deterministic per-step batches.
    state: optional pre-built :class:`GRTrainState`; default builds one
        from ``bundle`` on the first batch (presizing the τ=1 pair
        buffers via :func:`gr_pending_slots`).
    loss_kwargs: bound into ``bundle.loss`` (neg_mode, expansion,
        attn_fn, lookup_fn, ...).
    schedule: "algorithm1" (six-stage pipelined execution) or "flat"
        (same stages, serial per step).
    cache: optional :class:`repro.embedding.cache.CachedShadowedTable` —
        the host-offloaded embedding cache. The engine's ``state.table``
        is then the device-resident hot-chunk *window* and the full
        vocab lives in host RAM: the ``unique`` hook additionally runs
        the cache-prefetch path (pin + swap in the batch's missing
        chunks, translate ids to window slots — on a worker thread, so
        the H2D chunk transfer overlaps the previous batch's dense
        stages), ``emb_fwd`` lands the staged chunks with a cheap device
        splice before its gather, and eviction writes dirty chunks back
        to host RAM. Per-step hit/miss/evict counters ride in each
        record's ``"cache"`` entry; checkpoints go through
        :meth:`full_snapshot` / :meth:`adopt_full_state` (vocab-sized
        table, stripped shadow). Incompatible with a custom
        ``lookup_fn``.
    step_callback: optional ``fn(i, record, state)`` invoked after each
        ``emb_bwd`` (logging, checkpointing). ``state`` is always the
        carry-convention snapshot (τ=1 pairs pending, pre-landing table)
        — identical to what the fused step would hold after step ``i``,
        so a checkpoint taken from any schedule resumes bit-identically.

    ``run(steps)`` returns a list of per-step records
    ``{"step", "loss", "tokens"}``; ``events`` holds the run's
    :class:`StageEvent` trace and :meth:`timeline_report` reduces it to
    the Table-6 breakdown.
    """

    def __init__(self, bundle, data, *, state: Optional[GRTrainState] = None,
                 seed: int = 0, loss_kwargs: Optional[Dict[str, Any]] = None,
                 lr_dense: float = 4e-3, lr_sparse: float = 4e-3,
                 semi_async: bool = True, schedule: str = "algorithm1",
                 qdtype=ET.SHADOW_DTYPE, workers: int = 3,
                 cache: Optional[EC.CachedShadowedTable] = None,
                 step_callback: Optional[Callable] = None,
                 fault_policy: Optional[R.FaultPolicy] = None,
                 fault_injector: Optional[R.FaultInjector] = None,
                 obs: Optional[Obs] = None):
        if schedule not in SCHEDULES:
            raise ValueError(f"schedule must be one of {SCHEDULES}")
        if cache is not None and \
                dict(loss_kwargs or {}).get("lookup_fn") is not None:
            raise ValueError("the embedding cache translates ids to window "
                             "slots on the host; a custom lookup_fn (HSP "
                             "sparse exchange) expects global ids — the two "
                             "cannot be combined")
        self.cache = cache
        self.bundle = bundle
        self.loader = None if callable(data) else data
        self._data_fn = data if callable(data) else None
        self.state = state
        self.seed = seed
        self.semi_async = semi_async
        self.schedule = schedule
        self.qdtype = qdtype
        self.workers = workers
        self.step_callback = step_callback
        self.events: List[StageEvent] = []
        # -- fault tolerance (training/resilience.py) ----------------------
        self._policy = fault_policy
        self._injector = fault_injector
        self._resume_base = 0            # global step of this run's batch 0
        self._skips_used = 0
        self.fault_events: List[tuple] = []   # typed (kind, stage, step)
        self.recoveries: List[R.RecoveryEvent] = []
        # -- observability (obs/) ------------------------------------------
        # _mx/_tr are None unless obs is live, so every instrumentation
        # site is a single attribute test on the hot path
        self.obs = obs
        live = obs is not None and obs.enabled
        self._mx = obs.metrics if live else None
        self._tr = obs.tracer if live else None
        # measured MFU: the step's model FLOPs (launch/roofline.
        # gr_model_flops) against the device's published peak (none on a
        # kind without one)
        peak = device_peak(jax.devices()[0].device_kind)
        self._peak_flops = peak["flops"] if peak else None
        self._last_step_end: Optional[float] = None
        self._run_t0 = 0.0

        lk = dict(loss_kwargs or {})
        input_gather = _input_gather_for(bundle, lk)
        self._x_mode = semi_async and input_gather is not None
        # on a multi-chip HSP mesh each chip finds the rows it lands from
        # its own gradient: the host sorts no candidates
        self._hsp = _hsp_for(lk)
        stages = make_gr_stages(_bundle_loss_fn(bundle, lk),
                                lr_dense=lr_dense, lr_sparse=lr_sparse,
                                semi_async=semi_async,
                                input_gather=input_gather, hsp=self._hsp)
        self.stages = stages
        self._j_emb_fwd = jax.jit(stages.emb_fwd)
        self._j_dense = jax.jit(stages.dense_fwd_bwd)
        self._j_emb_bwd = jax.jit(stages.emb_bwd,
                                  static_argnames=("apply_sparse", "slots"))
        self._j_sparse_apply = jax.jit(stages.sparse_apply)
        self._dlock = threading.Lock()

    # -- data --------------------------------------------------------------
    def _batch(self, i: int):
        """Deterministic index → batch mapping, safe under the executor's
        thread pool (dataload futures may run out of order)."""
        with self._dlock:
            while i >= len(self._bcache):
                j = len(self._bcache)
                self._bcache.append(self._data_fn(j)
                                    if self._data_fn is not None
                                    else next(self._batch_iter))
            return self._bcache[i]

    # -- per-run setup -----------------------------------------------------
    def _prepare_run(self, steps: int):
        self._batch_iter = (self.loader.batches(steps)
                            if self.loader is not None else None)
        self._bcache: List[Any] = []
        self._arts: Dict[int, Dict[str, Any]] = {}
        self.events = []
        self._run_last = steps - 1
        self._last_step_end = None
        self._run_t0 = time.perf_counter()
        first = self._batch(0)
        if self.state is None:
            key = jax.random.PRNGKey(self.seed)
            table = (self.cache.init_window() if self.cache is not None
                     else self.bundle.init_table(key))
            self.state = gr_train_state(
                self.bundle.init_dense(key), table,
                qdtype=self.qdtype, pending_slots=gr_pending_slots(first),
                vocab=self.cache.vocab if self.cache is not None else None)
        if self.cache is not None:
            # the run's starting table is the latest landed window — the
            # reference dirty-chunk writebacks read from
            self.cache.publish(self.state.table)
        # τ=1 pairs left pending by a previous run (or restored from a
        # checkpoint) land mid-prologue: after emb_fwd(0) — whose input
        # read is one step stale, exactly as the fused step orders it —
        # and before emb_fwd(1) / dense_fwd(0).
        self._leftover = (self.semi_async
                          and self.state.pending_ids.shape[0] > 0
                          and bool((np.asarray(self.state.pending_ids)
                                    >= 0).any()))
        # stage hooks, wrapped with fault injection / retry / watchdog
        # when a policy or injector is attached (run_resilient sets them);
        # the unwrapped fast path is byte-for-byte the pre-resilience
        # engine, so plain runs stay untouched
        base_fns = {s: getattr(self, f"_hk_{s}") for s in STAGES}
        if self._policy is not None or self._injector is not None:
            self._stage_fns = {
                s: R.wrap_stage_fn(
                    s, fn, policy=self._policy, injector=self._injector,
                    global_step=lambda i: self._resume_base + i,
                    fault_events=self.fault_events,
                    poison=self._poison_dout if s == "dense_fwd" else None)
                for s, fn in base_fns.items()}
        else:
            self._stage_fns = base_fns

    def _land_pending(self):
        st = self.state
        table = self._j_sparse_apply(st.table, st.pending_ids,
                                     st.pending_rows)
        self.state = st._replace(
            table=table,
            pending_ids=jnp.full_like(st.pending_ids, -1),
            pending_rows=jnp.zeros_like(st.pending_rows))
        if self.cache is not None:
            self.cache.publish(table)
            self.cache.release_pending()

    def _maybe_land_leftover(self, i: int, stage: str):
        if not self._leftover:
            return
        if stage == "emb_fwd" and i == 0:
            return                      # batch 0's input read stays stale
        self._land_pending()
        self._leftover = False

    # -- Algorithm-1 hooks -------------------------------------------------
    def _hk_dataload(self, i: int):
        return self._batch(i)

    def _hk_a2a(self, i: int, nb):
        # feature exchange: the host→device transfer of the jagged batch.
        # Under the cache the id features stay on host — the unique hook
        # uploads them after the id→slot translation.
        skip = (("weights",) if self.cache is None
                else ("weights", "ids", "labels", "neg_ids"))
        dev = {k: jnp.asarray(v) for k, v in nb.items() if k not in skip}
        with TraceAnnotation("h2d", step=i):
            jax.block_until_ready(dev)
        return {"np": nb, "dev": dev}

    def _hk_unique(self, i: int, art):
        if self.cache is not None:
            with TraceAnnotation("cache_prefetch", step=i):
                return self._cache_prefetch(i, art)
        if self._hsp is not None:
            return {**art, "cand": (None, None)}
        vocab = self.bundle.cfg.vocab_size
        if self.state is not None:
            vocab = self.state.table.master.shape[0]
        s, first, _ = host_unique_candidates(art["np"], vocab)
        return {**art, "cand": (jnp.asarray(s), jnp.asarray(first))}

    def _cache_prefetch(self, i: int, art):
        """Cache path of the unique hook (worker thread): candidate dedup
        feeds the chunk manager — pin this batch's chunks, stage the
        missing ones host→device (the transfer dispatches here, under the
        previous batch's dense stages), then translate the batch's id
        features and the candidate sort into window-slot space. The
        translated candidate list re-sorts bit-identically (translation
        is a per-chunk-monotonic bijection on the candidate multiset, so
        run structure is preserved) and the device stages consume it
        unchanged."""
        C, nb = self.cache, art["np"]
        s, first, counts = host_unique_candidates(nb, C.vocab)
        plan, cstats = C.prepare(i, s[first], counts[first])
        dev = dict(art["dev"])
        for k in ("ids", "labels", "neg_ids"):
            dev[k] = jnp.asarray(C.translate(np.asarray(nb[k])))
        ts = np.sort(C.translate(s))
        tf = np.concatenate([np.ones((1,), bool), ts[1:] != ts[:-1]])
        cand = (jnp.asarray(ts), jnp.asarray(tf))
        jax.block_until_ready(dev)
        return {**art, "dev": dev, "cand": cand, "plan": plan,
                "cache": cstats}

    def _hk_emb_fwd(self, i: int, art):
        if self.cache is not None:
            plan = art.get("plan")
            if plan is not None:
                # land the prefetched chunks: a cheap async-dispatched
                # chunk-slot scatter, disjoint from every in-flight
                # batch's rows (those chunks are pinned)
                self.state = self.state._replace(
                    table=self.cache.splice(self.state.table, plan))
            self.cache.publish(self.state.table)
        self._maybe_land_leftover(i, "emb_fwd")
        st = self.state
        if self._x_mode:
            x = self._j_emb_fwd(st.table.master, art["dev"])
            return {**art, "x": x}
        if self.semi_async:
            # custom lookup_fn (e.g. HSP): the gather stays in the dense
            # stage; prefetching = capturing the stale master reference
            return {**art, "stale_master": st.table.master}
        return art

    def _hk_dense_fwd(self, i: int, art):
        self._maybe_land_leftover(i, "dense_fwd")
        st = self.state
        dout = self._j_dense(st.dense, st.table, art["dev"],
                             art.get("x"), art.get("stale_master"))
        self._arts[i] = {**art, "dout": dout}
        return {"i": i}

    def _poison_dout(self, i: int):
        """FaultInjector 'nan' mutator: NaN the dense_fwd artifact (the GR
        batch is all integer ids, so a poisoned batch manifests exactly
        here — a non-finite loss out of the dense stage)."""
        full = self._arts[i]
        full["dout"] = full["dout"]._replace(
            loss=jnp.full_like(full["dout"].loss, jnp.nan))

    def _hk_dense_bwd(self, i: int, art):
        full = self._arts[i]
        dout = full["dout"]
        with TraceAnnotation("loss_sync", step=i):
            if dout.tokens is None:
                loss = float(dout.loss)   # realize the dispatched fwd+bwd
            else:
                loss, tokens = jax.device_get((dout.loss, dout.tokens))
                loss, tokens = float(loss), int(tokens)
        if dout.tokens is None:
            tokens = int(np.asarray(full["np"]["offsets"])[:, -1].sum())
        rec = {"step": i, "loss": loss, "tokens": tokens}
        if self.cache is not None:
            # per-step cache counters ride the record into the timeline
            rec["cache"] = full.get("cache")
        if self._mx is not None:
            # dense_bwd realizes the dispatched loss on the main thread in
            # both schedules, so step-boundary timestamps need no lock
            self._obs_step(i, rec, full)
        pol = self._policy
        if pol is not None and pol.guard_nonfinite:
            bad = not np.isfinite(loss)
            if not bad and pol.guard_grads:
                bad = not R.all_finite(full["dout"].grads_dense)
            if bad:
                g = self._resume_base + i
                if (pol.nonfinite_action == "skip"
                        and self._skips_used < pol.max_skips):
                    self._skips_used += 1
                    self.fault_events.append(
                        ("skip_nonfinite", "dense_bwd", g))
                    rec["skipped"] = True
                else:
                    raise R.NonFiniteLossError(
                        f"non-finite loss at step {g} "
                        f"(skip budget {pol.max_skips} exhausted)"
                        if pol.nonfinite_action == "skip" else
                        f"non-finite loss at step {g}")
        return rec

    def _hk_emb_bwd(self, i: int, rec, *, defer_sparse: bool = False):
        full = self._arts.pop(i)
        st = self.state
        if rec.get("skipped"):
            # non-finite guard dropped this batch: no optimizer step, no
            # pairs — the state is untouched and the current state is its
            # own carry-convention snapshot
            if self.cache is not None:
                self.cache.release(i, dirty=False)
            self._bcache[i] = None
            self._callback(i, rec, st)
            return rec
        cand_s, cand_f = full["cand"]
        release_dirty = False   # unpin AFTER the callback (see below)
        if self.semi_async:
            # checkpoints/callbacks always see the carry-convention
            # snapshot (pending pairs + pre-landing table — what the
            # fused step leaves in state, and the only resume-equivalent
            # form), regardless of schedule
            if defer_sparse or i == self._run_last:
                # flat schedule / end of run: live state IS the snapshot;
                # the pairs land at the next step's (or run's) landing
                dense, opt, _, p_ids, p_rows = self._j_emb_bwd(
                    st.dense, st.dense_opt, st.table, full["dout"],
                    full["dev"], cand_s, cand_f, apply_sparse=False,
                    slots=st.pending_ids.shape[0] or None)
                self.state = snapshot = GRTrainState(
                    dense, opt, st.table, p_ids, p_rows, st.step + 1)
                if self.cache is not None:
                    # pairs pending: the batch's chunks stay pinned until
                    # the deferred landing marks them dirty
                    self.cache.defer_release(i)
            else:
                # pipelined steady state: land now — dense_fwd(i+1) is
                # the next statement and must see the fresh rows; the
                # pre-landing st.table reference still backs the snapshot
                dense, opt, table, p_ids, p_rows = self._j_emb_bwd(
                    st.dense, st.dense_opt, st.table, full["dout"],
                    full["dev"], cand_s, cand_f, apply_sparse=True,
                    slots=st.pending_ids.shape[0] or None)
                snapshot = GRTrainState(dense, opt, st.table, p_ids,
                                        p_rows, st.step + 1)
                self.state = GRTrainState(
                    dense, opt, table, jnp.full_like(p_ids, -1),
                    jnp.zeros_like(p_rows), st.step + 1)
                if self.cache is not None:
                    self.cache.publish(table)
                    release_dirty = True
        else:
            dense, opt, table, p_ids, p_rows = self._j_emb_bwd(
                st.dense, st.dense_opt, st.table, full["dout"],
                full["dev"], cand_s, cand_f, apply_sparse=True,
                slots=st.pending_ids.shape[0] or None)
            self.state = snapshot = GRTrainState(
                dense, opt, table, jnp.full_like(p_ids, -1),
                jnp.zeros_like(p_rows), st.step + 1)
            if self.cache is not None:
                self.cache.publish(table)
                release_dirty = True
        self._bcache[i] = None            # free the consumed numpy batch
        self._callback(i, rec, snapshot)
        if self.cache is not None and release_dirty:
            # unpin only now: the callback may checkpoint the pre-landing
            # snapshot, and a concurrent worker-thread prepare() must not
            # evict+write back a chunk this landing just dirtied (the host
            # copy would turn post-landing while the snapshot still
            # carries the pairs — a double-apply on restore)
            self.cache.release(i, dirty=True)
        return rec

    def _callback(self, i: int, rec: Dict[str, Any], snapshot) -> None:
        if self.step_callback:
            with TraceAnnotation("step_callback", step=i):
                self.step_callback(i, rec, snapshot)

    def _make_hooks(self) -> PipelineHooks:
        return PipelineHooks(**self._stage_fns)

    # -- observability ------------------------------------------------------
    def _obs_step(self, i: int, rec: Dict[str, Any],
                  full: Dict[str, Any]) -> None:
        """Per-step derived gauges: measured step wall time, measured MFU
        (against the device's published peak; None and no gauge on a
        device kind without one), and the
        per-device token-load imbalance — the paper's 54.71%-MFU and
        47%→2.4%-imbalance axes, live per step. The derived values also
        ride the record so callers see them without a registry read."""
        now = time.perf_counter()
        prev = (self._last_step_end if self._last_step_end is not None
                else self._run_t0)
        self._last_step_end = now
        wall = now - prev
        loads = np.asarray(full["np"]["offsets"])[:, -1]
        rec["step_wall_s"] = wall
        lengths = np.diff(np.asarray(full["np"]["offsets"]), axis=-1)
        rec["mfu"] = measured_mfu(gr_model_flops(self.bundle.cfg, lengths),
                                  wall, self._peak_flops)
        rec["imbalance"] = token_imbalance(loads)
        mx = self._mx
        mx.counter("train_steps_total", "training steps completed").inc()
        mx.counter("train_tokens_total", "tokens trained").inc(rec["tokens"])
        mx.gauge("train_step", "last completed global step").set(
            self._resume_base + i)
        mx.gauge("train_loss", "last step loss").set(rec["loss"])
        mx.gauge("train_step_wall_s", "last step wall time").set(wall)
        if rec["mfu"] is not None:
            mx.gauge("train_mfu_measured",
                     "measured model-FLOPs utilization").set(rec["mfu"])
        mx.gauge("train_token_imbalance",
                 "per-device token-load imbalance").set(rec["imbalance"])
        if wall > 0.0:
            mx.gauge("train_tokens_per_s", "training throughput").set(
                rec["tokens"] / wall)
        mx.histogram("train_step_s", "step wall time").observe(wall)
        cstats = rec.get("cache")
        if cstats:
            mx.publish("cache_step", cstats)

    def _obs_finalize(self, results: List[Dict[str, Any]]) -> None:
        """End-of-run observability: ingest the stage-event trace (one
        Perfetto track per merged stage), publish the Table-6 timeline
        breakdown and the cache's cumulative counters."""
        if self._mx is None:
            return
        recs = {r["step"]: r for r in results}
        self._tr.ingest_stage_events(self.events, records=recs)
        tl = self.timeline_report()
        if tl:
            self._mx.publish("train_timeline", tl)
        if self.cache is not None:
            self._mx.publish("cache", self.cache.counters())

    # -- cache ↔ full-table state conversion --------------------------------
    def full_snapshot(self, state: Optional[GRTrainState] = None
                      ) -> GRTrainState:
        """The vocab-sized carry-convention state of a cached run: dirty
        chunks are flushed from the given window snapshot into a full
        ``(V, D)`` master/accum (shadow stays a stripped placeholder) and
        the τ=1 pending ids are globalized. No-op without a cache — this
        is the one state form checkpoints store, so cached and uncached
        runs save interchangeably."""
        st = state if state is not None else self.state
        if self.cache is None or st is None:
            return st
        table = self.cache.materialize(st.table)
        p_ids, p_rows = self.cache.globalize_pending_pairs(
            np.asarray(st.pending_ids), np.asarray(st.pending_rows))
        return st._replace(table=table, pending_ids=jnp.asarray(p_ids),
                           pending_rows=jnp.asarray(p_rows))

    def adopt_full_state(self, full: GRTrainState) -> GRTrainState:
        """Load a vocab-sized (restored) state into the cache: host
        master/accum are overwritten, residency is rebuilt from the
        accumulated frequency counters (pending-pair chunks force-
        admitted and pinned), and ``engine.state`` becomes the window
        form with slot-space pending ids."""
        if self.cache is None:
            self.state = full
            return full
        window, p_slots = self.cache.adopt(full.table,
                                           np.asarray(full.pending_ids))
        self.state = full._replace(table=window,
                                   pending_ids=jnp.asarray(p_slots))
        return self.state

    # -- run ---------------------------------------------------------------
    def run(self, steps: int) -> List[Dict[str, Any]]:
        """Train ``steps`` batches; returns per-step records."""
        if steps <= 0:
            return []
        with TraceAnnotation("prepare_run"):
            self._prepare_run(steps)
        if self.schedule == "algorithm1":
            pipe = SixStagePipeline(self._make_hooks(), workers=self.workers)
            results = pipe.run(steps)
            self.events = list(pipe.events)
        else:
            results = self._run_flat(steps)
        self._obs_finalize(results)
        return results

    def _run_flat(self, steps: int) -> List[Dict[str, Any]]:
        """Serial per-step execution of the same stages (no pipelining) —
        the pre-engine training loop, with the same τ=1 dataflow: batch
        i−1's pairs land *after* batch i's prefetched input gather."""
        results = []

        def stage(name, i, *a, **kw):
            with stage_span(self.events, name, i):
                return self._stage_fns[name](i, *a, **kw)

        self._leftover = False            # flat lands pending every step
        for i in range(steps):
            nb = stage("dataload", i)
            art = stage("a2a", i, nb)
            art = stage("unique", i, art)
            art = stage("emb_fwd", i, art)
            if self.semi_async:
                # the sparse half of emb_bwd(i−1): the delayed landing
                # (at i = 0, pairs a previous run left pending)
                if i > 0:
                    with stage_span(self.events, "emb_bwd", i - 1):
                        self._land_pending()
                else:
                    self._land_pending()
            small = stage("dense_fwd", i, art)
            rec = stage("dense_bwd", i, small)
            stage("emb_bwd", i, rec, defer_sparse=True)
            results.append(rec)
        return results

    # -- supervised recovery ----------------------------------------------
    def _global_fetch(self) -> Callable[[int], Any]:
        """Deterministic global-step → batch mapping that survives
        recovery replays. ``data_fn`` engines re-fetch on demand; loader
        engines pull from one persistent iterator into a cache, because
        ``GRLoader.batches`` is RNG-stateful and restarting it would
        change the replayed batches (the cache is bounded by the run
        length — resilient runs hold their batch window like the
        pipelined schedule holds its lookahead)."""
        cache: Dict[int, Any] = {}
        if self._data_fn is not None:
            src = self._data_fn

            def fetch(g: int):
                if g not in cache:
                    cache[g] = src(g)
                return cache[g]
            return fetch
        loader, it = self.loader, None

        def fetch_loader(g: int):
            nonlocal it
            if it is None:
                it = loader.batches(self._resilient_steps)
            while len(cache) <= g:
                cache[len(cache)] = next(it)
            return cache[g]
        return fetch_loader

    def _write_ckpt(self, saver, ckpt_dir: str, step_num: int, snapshot,
                    keep_last_n) -> None:
        """One checkpoint write inside a resilient run: the snapshot is
        always the carry-convention state (τ=1 pairs pending + pre-landing
        table). A torn-save injection site for this step crashes the write
        exactly as a real mid-save failure would (wreckage on disk, then
        the process dies) — recovery must fall back to the previous
        intact step."""
        spec = (self._injector.take(R.SAVE_SITE, step_num)
                if self._injector else None)
        if spec is not None and spec.kind == "torn_save":
            if saver is not None:
                try:
                    saver.wait()          # serialize with in-flight save
                except Exception:
                    pass
            self.fault_events.append(("torn_save", R.SAVE_SITE, step_num))
            R.simulate_torn_save(ckpt_dir, step_num, snapshot,
                                 tear=spec.tear)
            raise R.InjectedFault(
                f"crash mid-save of step {step_num} ({spec.tear})")
        if saver is not None:
            saver.save_async(step_num, snapshot)
        else:
            from repro.training import checkpoint as CKPT
            CKPT.save(ckpt_dir, step_num, snapshot,
                      keep_last_n=keep_last_n, registry=self._mx)

    def run_resilient(self, steps: int, *, ckpt_dir: str,
                      ckpt_every: int = 10,
                      policy: Optional[R.FaultPolicy] = None,
                      injector: Optional[R.FaultInjector] = None,
                      keep_last_n: Optional[int] = None,
                      async_save: bool = True, final_save: bool = True,
                      start_step: Optional[int] = None
                      ) -> List[Dict[str, Any]]:
        """Train to global step ``steps`` under supervision: periodic
        crash-consistent checkpoints every ``ckpt_every`` steps, per-stage
        retry/watchdog/non-finite handling per ``policy``, and on any
        escalated stage failure a full recovery cycle — the pipeline
        drains deterministically (every in-flight hook joins), the newest
        *intact* checkpoint is restored (falling back past torn saves; the
        run's initial state if none exists yet), and the remaining steps
        replay. Checkpoints hold the carry-convention snapshot (τ=1
        pending pairs + pre-landing table — the only resume-equivalent
        form), so a failed-and-recovered run is bit-identical to an
        uninterrupted one for both schedules, sync and τ=1
        (tests/test_resilience.py).

        Returns the per-step records for global steps ``[start, steps)``
        in order (``start`` defaults to ``state.step``; records replayed
        after a recovery overwrite their first, identical, incarnation).
        ``engine.fault_events`` collects typed ``(kind, stage, step)``
        events and ``engine.recoveries`` one :class:`RecoveryEvent` per
        restore cycle.
        """
        from repro.training import checkpoint as CKPT
        pol = policy if policy is not None else R.FaultPolicy()
        prev_pol, prev_inj = self._policy, self._injector
        prev_cb, prev_data = self.step_callback, self._data_fn
        self._policy, self._injector = pol, injector
        self.fault_events = []
        self.recoveries = []
        self._skips_used = 0
        self._resilient_steps = steps
        base0 = (start_step if start_step is not None
                 else (int(self.state.step) if self.state is not None
                       else 0))
        if base0 >= steps:
            return []
        fetch = self._global_fetch()
        records: Dict[int, Dict[str, Any]] = {}
        saver = (CKPT.AsyncCheckpointer(ckpt_dir, keep_last_n=keep_last_n,
                                        registry=self._mx)
                 if async_save else None)
        # replay-from-scratch anchor; cached runs anchor the *full* state
        # (host rows mutate under writeback, so the window alone cannot
        # reconstruct step 0)
        initial = (self.full_snapshot(self.state)
                   if self.cache is not None and self.state is not None
                   else self.state)

        def on_step(i: int, rec: Dict[str, Any], snapshot) -> None:
            g = self._resume_base + i
            grec = dict(rec, step=g)
            records[g] = grec
            if prev_cb:
                prev_cb(g, grec, snapshot)
            done = g + 1
            if (ckpt_every and done % ckpt_every == 0) or \
                    (final_save and done == steps):
                self._write_ckpt(saver, ckpt_dir, done,
                                 self.full_snapshot(snapshot),
                                 keep_last_n)

        self.step_callback = on_step
        prev_loader, self.loader = self.loader, None
        self._data_fn = lambda i: fetch(self._resume_base + i)
        base = base0
        try:
            while True:
                self._resume_base = base
                try:
                    self.run(steps - base)
                    break
                except Exception as err:
                    t0 = time.perf_counter()
                    if saver is not None:
                        try:
                            saver.wait()   # surface/serialize async saves
                        except Exception:
                            pass           # a torn async save is recovered
                    if len(self.recoveries) >= pol.max_recoveries:
                        raise
                    failed = max(records, default=base - 1) + 1
                    if self.cache is not None:
                        self.cache.reset_pins()   # the crashed run's pins
                    try:
                        tmpl = self.full_snapshot(self.state)
                        full, used = CKPT.restore_with_step(
                            ckpt_dir, tmpl, registry=self._mx)
                        self.adopt_full_state(full)
                    except (FileNotFoundError, CKPT.CheckpointCorrupt):
                        # no intact checkpoint yet: replay from scratch —
                        # the initial state (or its seed-deterministic
                        # re-init when the run built it) anchors step 0
                        if initial is None and self.cache is not None:
                            raise   # cache host rows already mutated
                        if self.cache is not None:
                            self.adopt_full_state(initial)
                        else:
                            self.state = initial
                        used = base0
                    for g in [g for g in records if g >= used]:
                        del records[g]
                    base = used
                    ev = R.RecoveryEvent(
                        failed_step=failed, restored_step=used,
                        error=repr(err),
                        wall_s=time.perf_counter() - t0)
                    self.recoveries.append(ev)
                    self.fault_events.append(
                        ("recovered", "engine", used))
                    if self._mx is not None:
                        self._mx.counter(
                            "train_recoveries_total",
                            "recovery cycles completed").inc()
                        self._mx.counter(
                            "train_steps_replayed_total",
                            "steps lost to recoveries").inc(ev.steps_lost)
                        self._mx.gauge(
                            "train_last_recovery_wall_s",
                            "wall time of the last recovery").set(ev.wall_s)
                        self._mx.histogram(
                            "train_recovery_s",
                            "recovery wall time").observe(ev.wall_s)
                    if self._tr is not None:
                        # live span with real timestamps — t0 was captured
                        # at recovery entry, so (t0, t0 + wall_s) is the
                        # actual restore window on the run's timeline
                        self._tr.record(
                            "recovery", "recovery", t0, t0 + ev.wall_s,
                            {"failed_step": failed, "restored_step": used,
                             "steps_lost": ev.steps_lost,
                             "error": repr(err)})
        finally:
            self.step_callback = prev_cb
            self._policy, self._injector = prev_pol, prev_inj
            self._data_fn, self.loader = prev_data, prev_loader
            self._resume_base = 0
            if saver is not None:
                saver.wait()
        return [records[g] for g in sorted(records)]

    # -- reporting ---------------------------------------------------------
    def timeline_report(self) -> Dict[str, float]:
        """Table-6 breakdown of the last run's real stage events."""
        return _timeline_report(self.events)
