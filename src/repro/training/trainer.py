"""Train-step builders.

* :func:`make_lm_train_step` — the step the multi-pod dry-run lowers for
  the 10 assigned LM architectures: gradient accumulation over
  microbatches (scan-of-grads, so activation memory is one microbatch) +
  AdamW. Grad-accumulation dtype and optimizer-moment dtype come from the
  partition plan (398B uses bf16 for both).

* :func:`make_gr_stages` — the paper's training step decomposed into the
  Algorithm-1 (§4.2.3) device-stage functions: ``emb_fwd`` (input-side
  table gather, the τ=1-stale prefetched read), ``dense_fwd_bwd`` (jagged
  dense model + fused sampled-softmax recall loss + grads w.r.t. dense
  params / fresh master / prefetched rows), ``emb_bwd``
  (candidate-dedup'd sparse (id, row) pairs + AdamW + row-sparse Eq.-1
  AdaGrad on the ShadowedTable) and ``sparse_apply`` (the deferred τ=1
  landing). ``repro.training.engine.GREngine`` dispatches these as real
  pipeline stages.

* :func:`make_gr_train_step` — the flat fused step: the same stage
  functions composed inside one jit (sparse lookup via HSP
  sparse-exchange or dense baseline, §4.3 neg-sampling modes — default
  the fused ID-driven megakernel path whose custom VJP delivers the table
  gradient through the sorted run-sum scatter), optionally τ=1 semi-async
  sparse updates (§4.2.2). The engine's pipelined schedule is verified
  bit-identical against this composition.

Semi-async staleness accounting (§4.2.2, Fig. 8): the sparse gradient of
batch t is exchanged/applied during batch t+1's dense stream. The only
table read that predates it landing is the *prefetched input-side lookup*
(issued before the update completes — that read is one step stale); the
loss-stage reads (labels, negatives, gathered at the tail of the dense
forward) see the updated rows. Delaying those too — the previous
behaviour — widened the staleness window by a step and over-penalized the
τ=1 trajectory.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core import hsp as H
from repro.core import semi_async as SA
from repro.embedding import tables as ET
from repro.training import optim as O

Params = Any
Batch = Dict[str, jax.Array]


# --------------------------------------------------------------------------
# LM trainer
# --------------------------------------------------------------------------

class LMTrainState(NamedTuple):
    params: Params
    opt: O.AdamWState
    step: jax.Array


def lm_train_state(params: Params, opt_dtype=jnp.float32) -> LMTrainState:
    return LMTrainState(params=params, opt=O.adamw_init(params, opt_dtype),
                        step=jnp.zeros((), jnp.int32))


def make_lm_train_step(loss_fn: Callable[[Params, Batch], jax.Array], *,
                       num_microbatches: int = 1,
                       accum_dtype=jnp.float32,
                       lr: float = 3e-4, weight_decay: float = 0.1,
                       b1: float = 0.9, b2: float = 0.95):
    """loss_fn(params, microbatch) → scalar. Returns train_step."""

    def train_step(state: LMTrainState, batch: Batch):
        params = state.params

        if num_microbatches <= 1:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        else:
            B = jax.tree_util.tree_leaves(batch)[0].shape[0]
            assert B % num_microbatches == 0, (B, num_microbatches)
            mb = B // num_microbatches
            stacked = jax.tree.map(
                lambda a: a.reshape(num_microbatches, mb, *a.shape[1:]),
                batch)
            zero = jax.tree.map(
                lambda p: jnp.zeros(p.shape, accum_dtype), params)

            def mb_step(carry, mbatch):
                g_acc, l_acc = carry
                loss, g = jax.value_and_grad(loss_fn)(params, mbatch)
                g_acc = jax.tree.map(
                    lambda a, b: a + b.astype(accum_dtype), g_acc, g)
                return (g_acc, l_acc + loss), None

            (grads, loss), _ = jax.lax.scan(
                mb_step, (zero, jnp.float32(0.0)), stacked)
            inv = 1.0 / num_microbatches
            grads = jax.tree.map(lambda g: g * inv, grads)
            loss = loss * inv

        new_params, new_opt = O.adamw_update(
            grads, state.opt, params, lr=lr, b1=b1, b2=b2,
            weight_decay=weight_decay)
        return (LMTrainState(new_params, new_opt, state.step + 1),
                {"loss": loss})

    return train_step


# --------------------------------------------------------------------------
# GR trainer (the paper's system)
# --------------------------------------------------------------------------

class GRTrainState(NamedTuple):
    dense: Params
    dense_opt: O.AdamWState
    table: ET.ShadowedTable         # fp32 master + bf16 shadow + AdaGrad S
    pending_ids: jax.Array          # (N,) int32, −1 = empty (τ=1, §4.2.2)
    pending_rows: jax.Array         # (N, D) fp32 delayed sparse grad rows
    step: jax.Array


def gr_train_state(dense: Params, table: jax.Array,
                   opt_dtype=jnp.float32, *, qdtype=ET.SHADOW_DTYPE,
                   pending_slots: int = 0,
                   vocab: Optional[int] = None) -> GRTrainState:
    """``table`` is the fp32 master; a ``qdtype`` shadow (None = disabled)
    is derived from it. ``pending_slots`` presizes the τ=1 delayed-grad
    pair buffers — 0 lets the first train step size them from the batch
    (one extra jit compile in a steady-shape loop). The pairs carry unique
    ids, so the buffers never hold more slots than the id space has rows:
    ``vocab``, or the table's row count (a cache window passes its full
    vocab, so cached and uncached states keep one shape)."""
    tbl = table.master if isinstance(table, ET.ShadowedTable) else table
    pending_slots = min(pending_slots, vocab or tbl.shape[0])
    st = (table if isinstance(table, ET.ShadowedTable)
          else ET.make_shadowed(tbl, qdtype=qdtype))
    return GRTrainState(
        dense=dense, dense_opt=O.adamw_init(dense, opt_dtype),
        table=st,
        pending_ids=jnp.full((pending_slots,), -1, jnp.int32),
        pending_rows=jnp.zeros((pending_slots, tbl.shape[1]), jnp.float32),
        step=jnp.zeros((), jnp.int32))


def gr_pending_slots(batch: Batch, vocab: Optional[int] = None) -> int:
    """Static size of the τ=1 pending (id, row) pair buffers for a batch:
    one candidate per table read (input ids + labels + negatives), capped
    at ``vocab`` rows when given (the pairs carry unique ids). Pass to
    :func:`gr_train_state` to presize the state (required for AOT-compiled
    steps, avoids one recompile for jitted loops)."""
    n = int(batch["ids"].size + batch["labels"].size + batch["neg_ids"].size)
    return n if vocab is None else min(n, int(vocab))


def host_unique_candidates(batch, vocab: int):
    """Host-side realization of the pipeline's "unique" stage.

    Numpy mirror of the candidate dedup :func:`_table_grad_pairs`
    performs in-graph (concat → clip → sort → first-occurrence mask), so
    the sort runs on a worker thread overlapped with device compute
    (Algorithm 1 line 9) and the device stages consume the precomputed
    (sorted, first) arrays bit-identically — integer sorts agree exactly
    between numpy and XLA. This is the same dedup
    :func:`repro.core.hsp.unique_accumulate` runs per-shard before the
    sparse gradient exchange; here it covers the whole candidate list of
    a batch (input ids + labels + negatives).

    Returns ``(sorted, first, counts)``: the sort's run boundaries give
    per-id multiplicities for free, so ``counts`` holds each run's
    length at its first position (0 elsewhere) — ``sorted[first]`` are
    the unique ids and ``counts[first]`` their per-batch frequencies,
    the admission/eviction weight of the host-offloaded embedding cache
    (:class:`repro.embedding.cache.CachedShadowedTable`).
    """
    cand = np.concatenate([
        np.asarray(batch["ids"]).reshape(-1),
        np.asarray(batch["labels"]).reshape(-1),
        np.asarray(batch["neg_ids"]).reshape(-1)]).astype(np.int32)
    cand = np.clip(cand, 0, vocab - 1)
    s = np.sort(cand)
    first = np.concatenate([np.ones((1,), bool), s[1:] != s[:-1]])
    starts = np.flatnonzero(first)
    counts = np.zeros(s.shape, np.int64)
    counts[starts] = np.diff(np.append(starts, s.size))
    return s, first, counts


def _table_grad_pairs(gt: jax.Array, batch: Batch, vocab: int,
                      cand_sorted: Optional[jax.Array] = None,
                      cand_first: Optional[jax.Array] = None,
                      slots: Optional[int] = None):
    """Dense table grad → deduplicated sparse (id, grad-row) pairs.

    Every table read happens at the batch's candidate ids (input ids,
    labels, negative ids), so those rows cover the grad's support exactly.
    Duplicates are collapsed by a first-occurrence mask over the sorted
    candidate list, giving unique ids whose gathered rows are the
    already-aggregated per-row gradients. The unique ids fill the first
    ``slots`` entries in ascending order (default: one per candidate),
    −1 / zero rows after them.

    ``cand_sorted``/``cand_first`` accept the host "unique" stage's
    precomputed sort (:func:`host_unique_candidates`) so the pipeline can
    overlap the candidate dedup with device compute; when absent the sort
    runs in-graph (the flat fused step).

    A batch reads far more candidates than the table has rows (T·R
    negatives alone), so the state sizes ``slots`` to at most the table's
    rows (:func:`gr_train_state`): the pair buffers then cost (V, D)
    rather than (T·(R+2), D).
    """
    if cand_sorted is None:
        cand = jnp.concatenate([
            batch["ids"].reshape(-1), batch["labels"].reshape(-1),
            batch["neg_ids"].reshape(-1)]).astype(jnp.int32)
        cand = jnp.clip(cand, 0, vocab - 1)
        s = jnp.sort(cand)
        first = jnp.concatenate([jnp.ones((1,), bool), s[1:] != s[:-1]])
    else:
        s, first = cand_sorted, cand_first
    n = s.shape[0]
    (at,) = jnp.nonzero(first, size=slots or n, fill_value=n)
    hit = at < n
    uids = jnp.where(hit, s[jnp.minimum(at, n - 1)], -1)
    rows = gt[jnp.maximum(uids, 0)] * hit[:, None]
    return uids, rows.astype(jnp.float32)


# -- Algorithm-1 stage functions -------------------------------------------
#
# The train step is not a monolith: it is the composition of the three
# device stages of the paper's six-stage pipeline (§4.2.3), factored here
# as separately-jittable functions so the execution engine
# (repro.training.engine.GREngine) can dispatch them as pipeline stages
# while the flat fused step below composes the *same* functions inside one
# jit — both paths therefore produce bit-identical losses and states.

class GRDenseOut(NamedTuple):
    """Artifact flowing dense_fwd/bwd → emb_bwd (one batch)."""
    loss: jax.Array
    grads_dense: Params                  # AdamW input
    grad_table: jax.Array                # (V, D) grad w.r.t. the fresh master
    grad_x: Optional[jax.Array]          # cotangent w.r.t. prefetched rows
    grad_stale: Optional[jax.Array]      # (V, D) stale-master grad (inline)
    # the step's valid tokens, counted on the chips (multi-chip HSP stages,
    # whose batch is not read back to the host)
    tokens: Optional[jax.Array] = None


class GRStages(NamedTuple):
    """The staged GR train step (Algorithm 1 device-stage vocabulary).

    emb_fwd(stale_master, batch) -> x | None
        Input-side table gather. In the pipeline this runs *before* the
        previous batch's sparse update lands — the τ=1 stale read
        (§4.2.2). Returns None when the gather is inlined into the dense
        stage (sync training, or no ``input_gather`` provided).
    dense_fwd_bwd(dense, table, batch, x, stale_master) -> GRDenseOut
        Jagged dense model + fused sampled-softmax loss + grads w.r.t.
        dense params, the fresh master (labels/negatives) and the
        prefetched input rows.
    emb_bwd(dense, dense_opt, table, dout, batch, cand_sorted, cand_first,
            *, apply_sparse, slots) -> (dense', opt', table', p_ids, p_rows)
        _table_grad_pairs + AdamW + (optionally deferred) row-sparse
        Eq.-1 AdaGrad. ``apply_sparse=False`` returns the pairs as the
        τ=1 pending cross-batch artifact instead of applying them;
        ``slots`` is the pending buffers' length.
    sparse_apply(table, p_ids, p_rows) -> table'
        The deferred landing of pending pairs (Algorithm 1 line 3).
    """
    emb_fwd: Callable
    dense_fwd_bwd: Callable
    emb_bwd: Callable
    sparse_apply: Callable


def make_gr_stages(loss_fn: Callable[..., jax.Array], *,
                   lr_dense: float = 4e-3, lr_sparse: float = 4e-3,
                   semi_async: bool = True,
                   input_gather: Optional[Callable] = None,
                   hsp: Optional[H.HSPLookup] = None) -> GRStages:
    """Decompose the GR train step into Algorithm-1 stage functions.

    ``input_gather(master, batch) -> x`` is the standalone input-side
    lookup (``GRBundle.input_gather``). When provided (and
    ``semi_async``), the emb_fwd stage performs the gather as its own
    dispatch and emb_bwd recovers the input-side table grad by linearly
    transposing it — the gather must therefore be built from transposable
    linear primitives (plain take + cast; not a custom-vjp lookup). When
    None, the input lookup stays inside the dense stage, differentiated
    against the stale master via ``input_table=`` (the pre-staging
    behaviour, and the only mode that supports custom ``lookup_fn``s).

    Cache-slot transparency: every stage is shape-generic over
    ``table.master.shape[0]`` and ids are used only as gather/scatter
    row indices, so the stages run unchanged on a
    :class:`repro.embedding.cache.CachedShadowedTable` window — the
    engine translates the batch's ids (and the precomputed candidate
    sort) from global id space to window-slot space on the host, and
    emb_fwd / the fused neg-kernel gather / the row-sparse AdaGrad in
    emb_bwd all operate on cache slots; writeback to the host-resident
    full table is chunk-sparse and deferred to eviction.

    ``hsp``, an HSP lookup over several chips: the stages run each chip's
    part of the step inside a ``shard_map`` over its mesh
    (:func:`_hsp_stages`).
    """
    if hsp is not None:
        return _hsp_stages(loss_fn, hsp, lr_dense=lr_dense,
                           lr_sparse=lr_sparse, semi_async=semi_async)
    x_mode = semi_async and input_gather is not None

    # Named scopes mark each layer's ops in the HLO metadata (the op_name
    # path a profiler trace carries, e.g. ``transpose(jvp(loss))``), so a
    # trace reader can split a stage's device time by layer; they change
    # no value.
    def emb_fwd(stale_master, batch):
        if not x_mode:
            return None
        with jax.named_scope("input_gather"):
            return input_gather(stale_master, batch)

    def dense_fwd_bwd(dense, table: ET.ShadowedTable, batch,
                      x=None, stale_master=None) -> GRDenseOut:
        shadow = table.shadow
        if semi_async and x is not None:
            (loss, _), (gd, gt, gx) = jax.value_and_grad(
                lambda d, tf, xx: (loss_fn(d, tf, batch, x_emb=xx,
                                           shadow=shadow), 0.0),
                argnums=(0, 1, 2), has_aux=True)(dense, table.master, x)
            return GRDenseOut(loss, gd, gt, gx, None)
        if semi_async:
            (loss, _), (gd, g_stale, g_fresh) = jax.value_and_grad(
                lambda d, ts, tf: (loss_fn(d, tf, batch, input_table=ts,
                                           shadow=shadow), 0.0),
                argnums=(0, 1, 2), has_aux=True)(
                    dense, stale_master, table.master)
            return GRDenseOut(loss, gd, g_fresh, None, g_stale)
        (loss, _), (gd, gt) = jax.value_and_grad(
            lambda d, t: (loss_fn(d, t, batch, input_table=None,
                                  shadow=shadow), 0.0),
            argnums=(0, 1), has_aux=True)(dense, table.master)
        return GRDenseOut(loss, gd, gt, None, None)

    def emb_bwd(dense, dense_opt, table: ET.ShadowedTable,
                dout: GRDenseOut, batch,
                cand_sorted=None, cand_first=None, *,
                apply_sparse: bool = True, slots: Optional[int] = None):
        vocab = table.master.shape[0]
        with jax.named_scope("table_grad"):
            if semi_async:
                if dout.grad_x is not None:
                    # transpose of the emb_fwd gather: the input-side
                    # scatter the fused step's autodiff emits for
                    # input_table
                    tsd = jax.ShapeDtypeStruct(table.master.shape,
                                               table.master.dtype)
                    g_stale = jax.linear_transpose(
                        lambda t: input_gather(t, batch), tsd)(
                            dout.grad_x)[0]
                else:
                    g_stale = dout.grad_stale
                # the barrier pins the summation order: fused into one jit
                # with the scatter that built g_stale, XLA may fold the add
                # into it and round differently than the staged engine
                g_stale, g_fresh = jax.lax.optimization_barrier(
                    (g_stale, dout.grad_table))
                gt = (g_stale + g_fresh).astype(jnp.float32)
            else:
                gt = dout.grad_table.astype(jnp.float32)
            p_ids, p_rows = _table_grad_pairs(gt, batch, vocab,
                                              cand_sorted, cand_first, slots)
        with jax.named_scope("adamw"):
            new_dense, new_opt = O.adamw_update(
                dout.grads_dense, dense_opt, dense, lr=lr_dense,
                weight_decay=0.0)
        new_table = (sparse_apply(table, p_ids, p_rows)
                     if apply_sparse else table)
        return new_dense, new_opt, new_table, p_ids, p_rows

    def sparse_apply(table: ET.ShadowedTable, p_ids, p_rows):
        with jax.named_scope("adagrad"):
            return O.adagrad_sparse_update(table, p_ids, p_rows,
                                           lr=lr_sparse)

    return GRStages(emb_fwd, dense_fwd_bwd, emb_bwd, sparse_apply)


def _replicated(x) -> P:
    """The spec of an array every chip holds whole, as the partition plan
    writes it (``repro.launch.partition.gr_param_specs``)."""
    return P(*([None] * jnp.ndim(x)))


def _hsp_stages(loss_fn: Callable[..., jax.Array], hsp: H.HSPLookup, *,
                lr_dense: float, lr_sparse: float,
                semi_async: bool) -> GRStages:
    """The Algorithm-1 stages of a step on an HSP mesh of several chips.

    Each stage is a ``shard_map`` whose body is one chip's work: every
    kernel runs on the chip's shard of the batch and its rows of the
    table, and every exchange is an explicit collective (``core.hsp``):

    dense_fwd_bwd  the loss over the chip's shard (the HSP lookups and
                   the owner-computes negatives exchange within its group)
                   and its gradients; then the step's gradient exchange:
                   the dense gradients and the loss summed over every
                   chip, the table gradient of the chip's rows over the
                   groups, so that every group holds the same aggregate
                   (paper Eq. 1). The step's valid tokens ride out with
                   the loss.
    emb_bwd        AdamW, the τ=1 pending pairs and optionally the
                   landing. A chip's pairs are its rows in order with its
                   aggregated gradient (``pending_rows`` laid out like the
                   rows, ``pending_ids`` their global ids), whatever the
                   pending buffers' ``slots``.
    sparse_apply   AdaGrad over the chip's rows, dense: a row the step did
                   not touch has a zero gradient and stays bit-unchanged.

    The input lookup stays in the dense stage (the HSP lookup is a
    custom-VJP exchange); ``x`` is never given. The candidate sort of the
    host ``unique`` stage is not used, so the host reads nothing of a
    placed batch.
    """
    mesh, chip_lookup = hsp.mesh, hsp.on_chip()
    rows_spec = hsp.table_spec
    ids_spec = P(*hsp.table_spec[:1])
    on_chips = partial(jax.shard_map, mesh=mesh, check_vma=False)

    def batch_specs(batch):
        return {k: P() if k == "rng" else hsp.ids_spec for k in batch}

    def table_specs(table):
        return jax.tree.map(lambda _: rows_spec, table)

    def dense_fwd_bwd(dense, table: ET.ShadowedTable, batch,
                      x=None, stale_master=None) -> GRDenseOut:
        if x is not None:
            raise ValueError("the HSP lookup keeps the input gather in the "
                             "dense stage")

        def chip(dense, master, shadow, stale, b):
            with H.exchange_meter():
                if semi_async:
                    loss, (gd, gs, gf) = jax.value_and_grad(
                        lambda d, ts, tf: loss_fn(
                            d, tf, b, input_table=ts, shadow=shadow,
                            lookup_fn=chip_lookup),
                        argnums=(0, 1, 2))(dense, stale, master)
                    gt = gs + gf
                else:
                    loss, (gd, gt) = jax.value_and_grad(
                        lambda d, t: loss_fn(d, t, b, input_table=None,
                                             shadow=shadow,
                                             lookup_fn=chip_lookup),
                        argnums=(0, 1))(dense, master)
                gd = jax.tree.map(lambda g: g.astype(jnp.float32), gd)
                loss, gd, tokens = H.psum(
                    (loss, gd, jnp.sum(b["offsets"][:, -1])),
                    hsp.batch_axes, "grad_exchange")
                if hsp.dp_axes:
                    gt = H.psum(gt.astype(jnp.float32), hsp.dp_axes,
                                "grad_exchange")
            return loss, gd, gt, tokens

        stale = table.master if stale_master is None else stale_master
        gd_specs = jax.tree.map(_replicated, dense)
        loss, gd, gt, tokens = on_chips(
            chip, in_specs=(gd_specs, rows_spec, rows_spec, rows_spec,
                            batch_specs(batch)),
            out_specs=(P(), gd_specs, rows_spec, P()))(
                dense, table.master, table.shadow, stale, batch)
        return GRDenseOut(loss, gd, gt, None, None, tokens)

    def land(tbl: ET.ShadowedTable, ids, rows):
        with jax.named_scope("adagrad"):
            return O.adagrad_sparse_update(tbl, ids, rows, lr=lr_sparse,
                                           aligned=True)

    def emb_bwd(dense, dense_opt, table: ET.ShadowedTable,
                dout: GRDenseOut, batch,
                cand_sorted=None, cand_first=None, *,
                apply_sparse: bool = True, slots: Optional[int] = None):

        def chip(dense, dense_opt, tbl, gt, gd):
            rows = gt.shape[0]
            p_ids = hsp.row_offset(rows) + jnp.arange(rows, dtype=jnp.int32)
            with jax.named_scope("adamw"):
                new_dense, new_opt = O.adamw_update(
                    gd, dense_opt, dense, lr=lr_dense, weight_decay=0.0)
            new_table = land(tbl, p_ids, gt) if apply_sparse else tbl
            return new_dense, new_opt, new_table, p_ids, gt

        d_specs = jax.tree.map(_replicated, dense)
        o_specs = jax.tree.map(_replicated, dense_opt)
        t_specs = table_specs(table)
        return on_chips(
            chip, in_specs=(d_specs, o_specs, t_specs, rows_spec,
                            jax.tree.map(_replicated, dout.grads_dense)),
            out_specs=(d_specs, o_specs, t_specs, ids_spec, rows_spec))(
                dense, dense_opt, table, dout.grad_table, dout.grads_dense)

    def sparse_apply(table: ET.ShadowedTable, p_ids, p_rows):
        t_specs = table_specs(table)
        return on_chips(land, in_specs=(t_specs, ids_spec, rows_spec),
                        out_specs=t_specs)(table, p_ids, p_rows)

    def emb_fwd(stale_master, batch):
        return None

    return GRStages(emb_fwd, dense_fwd_bwd, emb_bwd, sparse_apply)


def make_gr_train_step(loss_fn: Callable[..., jax.Array], *,
                       lr_dense: float = 4e-3, lr_sparse: float = 4e-3,
                       semi_async: bool = True,
                       input_gather: Optional[Callable] = None,
                       hsp: Optional[H.HSPLookup] = None):
    """loss_fn(dense_params, table, batch, *, input_table=None,
    shadow=None) → scalar (built from GRBundle.loss with the
    lookup/neg-sampling modes already bound; the default "fused" mode
    keeps the whole negative path out of HBM, gathers negatives from the
    half-precision ``shadow``, and its table grad arrives pre-reduced from
    sparse (id, row) pairs).

    The step is the flat composition of the :func:`make_gr_stages` stage
    functions inside one jit — the oracle the pipelined execution engine
    (``GREngine(schedule="algorithm1")``) is verified bit-identical
    against. ``input_gather`` opts the composition into the staged
    input-gather dataflow (x as an explicit artifact); entrypoints go
    through :class:`repro.training.engine.GREngine`, which always passes
    it for the plain-gather path.

    semi_async=True is the τ=1 schedule: last step's sparse (id, row)
    pairs land first (their exchange overlapped this step's dense
    stream), then the forward runs with the stale master feeding only the
    prefetched input lookup. The sparse optimizer is
    :func:`repro.training.optim.adagrad_sparse_update` — master, shadow
    and accumulator are rewritten at touched rows only.
    """
    st = make_gr_stages(loss_fn, lr_dense=lr_dense, lr_sparse=lr_sparse,
                        semi_async=semi_async, input_gather=input_gather,
                        hsp=hsp)

    def train_step(state: GRTrainState, batch: Batch):
        tbl = state.table
        slots = state.pending_ids.shape[0] or None

        if semi_async:
            # emb_fwd for this batch reads the stale master (the pipeline
            # prefetched it before the delayed update landed)...
            stale = tbl.master
            x = st.emb_fwd(stale, batch)
            # ...then the τ=1 pending pairs land (line 3 of Algorithm 1;
            # their exchange overlapped this step's dense stream)
            fresh = st.sparse_apply(tbl, state.pending_ids,
                                    state.pending_rows)
            dout = st.dense_fwd_bwd(state.dense, fresh, batch, x, stale)
            new_dense, new_opt, new_table, p_ids, p_rows = st.emb_bwd(
                state.dense, state.dense_opt, fresh, dout, batch,
                apply_sparse=False,   # pairs become the next step's carry
                slots=slots)
        else:
            dout = st.dense_fwd_bwd(state.dense, tbl, batch)
            new_dense, new_opt, new_table, uids, rows = st.emb_bwd(
                state.dense, state.dense_opt, tbl, dout, batch,
                apply_sparse=True, slots=slots)
            p_ids = jnp.full_like(uids, -1)
            p_rows = jnp.zeros_like(rows)

        return (GRTrainState(new_dense, new_opt, new_table,
                             p_ids, p_rows, state.step + 1),
                {"loss": dout.loss})

    return train_step
