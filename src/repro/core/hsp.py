"""Hierarchical Sparse Parallelism (paper §4.2.1).

Topology (mesh axes): the embedding table is vocab-sharded over the
``model`` axis *within* a group and replicated across the ``data``/``pod``
axes — each (pod, data) index is one HSP group of I = |model| devices.

  * lookup — two-phase intra-group exchange: all-gather ids over ``model``,
    masked partial gather from the local vocab shard, reduce(-scatter) back.
    Communication scale O(I), not O(N): the paper's 75.9% all-to-all claim.
  * sparse gradient exchange (custom VJP) — intra-group all-gather of
    (ids, grad rows), local unique-accumulate, then inter-group all-gather
    over ``data``/``pod`` and owner scatter-add. Every group ends with the
    identical aggregate gradient G_t, so AdaGrad states evolve identically
    (Eq. 1) — verified by tests/test_hsp.py::test_adagrad_state_identity.
  * baseline — table sharded over *all* axes (TorchRec-style global
    two-phase all-to-all): same lookup code, group = whole cluster; grads
    sync via the dense-allreduce autodiff path. Table 4 compares the two
    by HLO collective bytes.

All collectives are explicit ``shard_map`` + ``jax.lax`` ops, so the HLO
contains exactly the communication pattern we claim. The helpers
:func:`all_gather`, :func:`psum_scatter` and :func:`psum` wrap each in the
named scope of its phase (``hsp_lookup``, ``hsp_neg``, ``hsp_grad_exchange``)
and, while :func:`exchange_meter` is open, count the bytes it moves per
chip; the meter publishes them as the gauge
``hsp_exchange_bytes_per_step{phase}`` of :data:`repro.obs.KERNEL_METRICS`.

:class:`HSPLookup` is the lookup over the whole mesh; its
:meth:`HSPLookup.on_chip` is the lookup one chip runs inside a
``shard_map`` over that mesh, which is how the training stages run a
multi-chip step (``repro.training.trainer.make_gr_stages``).
"""
from __future__ import annotations

import contextlib
import contextvars
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.obs.metrics import KERNEL_METRICS


# --------------------------------------------------------------------------
# the exchange's collectives, by phase, and the bytes they move
# --------------------------------------------------------------------------

PHASES = ("lookup", "neg", "grad_exchange")
_METER: contextvars.ContextVar[Optional[Dict[str, float]]] = \
    contextvars.ContextVar("repro_hsp_meter", default=None)


@contextlib.contextmanager
def exchange_meter():
    """Count the bytes the collectives traced inside the block move per
    chip, by phase, and publish them as ``hsp_exchange_bytes_per_step``.

    Open it around the trace of one chip's step: the counts are then the
    step's, since a compiled step runs each traced collective once. A
    chip's bytes are those a ring moves through it: ``(n - 1) B`` for an
    all-gather of ``B`` bytes a chip over ``n`` chips, ``(n - 1) / n B``
    for a reduce-scatter of ``B``, twice that for an all-reduce."""
    counts = dict.fromkeys(PHASES, 0.0)
    tok = _METER.set(counts)
    try:
        yield counts
    finally:
        _METER.reset(tok)
    for phase, n in counts.items():
        KERNEL_METRICS.gauge(
            "hsp_exchange_bytes_per_step",
            "bytes the HSP exchange's collectives move per chip in a step",
            labels={"phase": phase}).set(n)


def _count(phase: str, nbytes: float) -> None:
    counts = _METER.get()
    if counts is not None:
        counts[phase] += nbytes


def _axes(axes):
    return axes[0] if isinstance(axes, tuple) and len(axes) == 1 else axes


def _nbytes(x) -> int:
    return x.size * jnp.dtype(x.dtype).itemsize


def all_gather(x, axes, phase: str, *, tiled: bool = True,
               transposed: bool = False):
    """``jax.lax.all_gather`` over ``axes`` in phase ``phase``'s scope.
    ``transposed``: the gather is differentiated, and its transpose (a
    reduce-scatter of the gathered array) moves as many bytes again."""
    n = jax.lax.axis_size(axes)
    _count(phase, (n - 1) * _nbytes(x) * (2 if transposed else 1))
    with jax.named_scope(f"hsp_{phase}"):
        return jax.lax.all_gather(x, _axes(axes), tiled=tiled)


def psum_scatter(x, axes, phase: str):
    """``jax.lax.psum_scatter`` over ``axes`` along the leading dim."""
    n = jax.lax.axis_size(axes)
    _count(phase, (n - 1) / n * _nbytes(x))
    with jax.named_scope(f"hsp_{phase}"):
        return jax.lax.psum_scatter(x, _axes(axes), scatter_dimension=0,
                                    tiled=True)


def psum(tree, axes, phase: str):
    """``jax.lax.psum`` of every leaf of ``tree`` over ``axes``."""
    n = jax.lax.axis_size(axes)
    _count(phase, 2 * (n - 1) / n * sum(_nbytes(x)
                                        for x in jax.tree.leaves(tree)))
    with jax.named_scope(f"hsp_{phase}"):
        return jax.lax.psum(tree, _axes(axes))


# --------------------------------------------------------------------------
# fixed-capacity unique + accumulate (the pipeline's "unique" stage)
# --------------------------------------------------------------------------

def unique_accumulate(ids: jax.Array, rows: jax.Array,
                      num_out: Optional[int] = None
                      ) -> Tuple[jax.Array, jax.Array]:
    """Deduplicate ids, summing their rows. JIT-safe fixed capacity.

    ids: (n,) int32 (negative = invalid), rows: (n, d).
    Returns (uids (num_out,) int32 with -1 fill, urows (num_out, d)).
    """
    n, d = rows.shape
    num_out = num_out or n
    valid = ids >= 0
    skey = jnp.where(valid, ids, jnp.iinfo(jnp.int32).max)
    order = jnp.argsort(skey)
    sids = skey[order]
    srows = rows[order] * valid[order][:, None].astype(rows.dtype)
    is_new = jnp.concatenate(
        [jnp.ones((1,), bool), sids[1:] != sids[:-1]])
    uslot = jnp.cumsum(is_new) - 1                       # (n,) slot per elem
    uslot = jnp.where(valid[order], uslot, num_out)      # invalid → dropped
    uids = jnp.full((num_out,), -1, jnp.int32)
    uids = uids.at[uslot].set(jnp.where(valid[order], sids, -1), mode="drop")
    urows = jnp.zeros((num_out, d), rows.dtype)
    urows = urows.at[uslot].add(srows, mode="drop")
    return uids, urows


def scatter_add_rows(table: jax.Array, ids: jax.Array,
                     rows: jax.Array) -> jax.Array:
    """table.at[ids] += rows, dropping ids < 0 / out-of-range."""
    ids = jnp.where(ids >= 0, ids, table.shape[0])
    return table.at[ids].add(rows.astype(table.dtype), mode="drop")


# --------------------------------------------------------------------------
# HSP lookup with sparse-exchange backward
# --------------------------------------------------------------------------

class HSPLookup:
    """An HSP lookup bound to a mesh (build it with :func:`make_hsp_lookup`).

    Called as ``lookup(table, ids)`` on the global arrays — table (V, d)
    sharded ``P(group_axes, None)``, ids (G, cap) sharded over the batch
    axes (``dp_axes + group_axes``, flat) — it returns emb (G, cap, d)
    with the ids' sharding, through a ``shard_map`` of its own, and its
    backward ends with every group holding the same aggregate gradient.

    :meth:`on_chip` gives the lookup that one chip runs inside a
    ``shard_map`` over the same mesh (:class:`HSPShard`).
    """

    def __init__(self, mesh: Mesh, group_axes: Tuple[str, ...],
                 dp_axes: Tuple[str, ...], compute_dtype,
                 unique_capacity: Optional[int], grad_wire_dtype):
        self.mesh = mesh
        self.group_axes, self.dp_axes = tuple(group_axes), tuple(dp_axes)
        self.batch_axes = self.dp_axes + self.group_axes
        self.compute_dtype = compute_dtype
        self.unique_capacity = unique_capacity
        self.grad_wire_dtype = grad_wire_dtype
        self.ids_spec = P(_axes(self.batch_axes))
        self.table_spec = P(_axes(self.group_axes), None)
        self.emb_spec = P(_axes(self.batch_axes), None, None)
        self.group_size = functools.reduce(
            lambda a, b: a * b, [mesh.shape[a] for a in self.group_axes], 1)

    @property
    def spans_chips(self) -> bool:
        return self.mesh.size > 1

    def row_offset(self, rows: int):
        """Inside a shard_map: the first row of this chip's vocab shard of
        ``rows`` rows within the group."""
        idx = jnp.int32(0)
        for a in self.group_axes:
            idx = idx * self.mesh.shape[a] + jax.lax.axis_index(a)
        return idx * rows

    def gather(self, tbl: jax.Array, ids: jax.Array) -> jax.Array:
        """One chip's forward: tbl (V/I, d) its shard, ids (Gl, cap) its
        shard of the ids → (Gl, cap, d)."""
        rows, d = tbl.shape
        rel = all_gather(ids, self.group_axes, "lookup") \
            - self.row_offset(rows)                      # (Gl·I, cap)
        owned = (rel >= 0) & (rel < rows)
        part = jnp.take(tbl, jnp.clip(rel, 0, rows - 1).reshape(-1), axis=0)
        part = part.reshape(*rel.shape, d).astype(self.compute_dtype)
        part = part * owned[..., None].astype(self.compute_dtype)
        # each row has exactly one owner, so a low-precision sum is exact
        return psum_scatter(part, self.group_axes, "lookup")

    def group_grad(self, rows: int, ids: jax.Array,
                   g: jax.Array) -> jax.Array:
        """One chip's backward within its group: the fp32 gradient of the
        ``rows`` rows it holds, from every chip of the group (and, with a
        ``unique_capacity``, of every group: the sparse inter-group
        exchange)."""
        d = g.shape[-1]
        lo = self.row_offset(rows)
        # local dedup before any exchange (the "unique" stage)
        uids, urows = unique_accumulate(
            ids.reshape(-1), g.reshape(-1, d).astype(jnp.float32),
            self.unique_capacity)
        # wire compression (DESIGN.md §7): bf16 halves, int8 quarters the
        # exchanged gradient bytes. int8 uses a per-row max-abs scale
        # shipped alongside (fp32, d× smaller)
        wire = jnp.dtype(self.grad_wire_dtype)
        scale = None
        if wire == jnp.int8:
            amax = jnp.max(jnp.abs(urows), axis=1, keepdims=True)
            scale = jnp.where(amax > 0, amax / 127.0, 1.0)
            urows = jnp.clip(jnp.round(urows / scale), -127, 127
                             ).astype(jnp.int8)
            scale = all_gather(scale, self.group_axes, "lookup")
        else:
            urows = urows.astype(wire)
        # phase 1 (intra-group): all-gather sparse (ids, rows) over `model`
        # — the embedding-gradient all-to-all — and scatter-add the rows
        # this member owns into its shard
        all_ids = all_gather(uids, self.group_axes, "lookup")
        all_rows = all_gather(urows, self.group_axes, "lookup")
        if scale is not None:
            all_rows = all_rows.astype(jnp.float32) * scale
        if self.dp_axes and self.unique_capacity is not None:
            # paper-faithful sparse inter-group exchange: ship (ids, rows)
            # across replicas. Buffer is bounded by the explicit
            # unique_capacity; without a bound the dense shard-psum of the
            # caller is cheaper and memory-safe.
            all_ids = all_gather(all_ids, self.dp_axes, "lookup")
            all_rows = all_gather(all_rows, self.dp_axes, "lookup")
        rel = all_ids - lo
        owned = (all_ids >= 0) & (rel >= 0) & (rel < rows)
        dtbl = jnp.zeros((rows, d), jnp.float32)
        return scatter_add_rows(dtbl, jnp.where(owned, rel, -1),
                                all_rows.astype(jnp.float32))

    def __call__(self, table: jax.Array, ids: jax.Array) -> jax.Array:
        rows = table.shape[0] // self.group_size
        tdtype = table.dtype

        def fwd_impl(table, ids):
            return jax.shard_map(self.gather, mesh=self.mesh,
                                 in_specs=(self.table_spec, self.ids_spec),
                                 out_specs=self.emb_spec,
                                 check_vma=False)(table, ids)

        def bwd_local(idsl, gl):
            dtbl = self.group_grad(rows, idsl, gl)
            # phase 2 (inter-group): reduce the OWNED shard across the
            # data/pod replicas — every group ends with the identical
            # aggregate G_t (Eq. 1).
            if self.dp_axes and self.unique_capacity is None:
                # psum accumulates — int8 would overflow; cap at bf16
                wire = jnp.dtype(self.grad_wire_dtype)
                pdt = jnp.bfloat16 if wire == jnp.int8 else wire
                dtbl = psum(dtbl.astype(pdt), self.dp_axes,
                            "grad_exchange").astype(jnp.float32)
            return dtbl.astype(tdtype)

        @jax.custom_vjp
        def _lookup(table, ids):
            return fwd_impl(table, ids)

        def fwd(table, ids):
            return fwd_impl(table, ids), ids

        def bwd(ids, g):
            dtable = jax.shard_map(bwd_local, mesh=self.mesh,
                                   in_specs=(self.ids_spec, self.emb_spec),
                                   out_specs=self.table_spec,
                                   check_vma=False)(ids, g)
            return dtable, None

        _lookup.defvjp(fwd, bwd)
        return _lookup(table, ids)

    def on_chip(self) -> "HSPShard":
        return HSPShard(self)


class HSPShard:
    """The HSP lookup one chip runs inside a ``shard_map`` over the HSP
    mesh: ``(tbl, ids)`` are the chip's table rows and its shard of the
    ids. Its backward gives the fp32 gradient of the rows the chip holds
    from every chip of its group; summing it over the groups is the
    caller's, once for the whole table gradient of a step. ``hsp`` is the
    mesh-wide lookup it belongs to."""

    def __init__(self, hsp: HSPLookup):
        if hsp.unique_capacity is not None:
            raise ValueError("a per-chip HSP step sums the table gradient "
                             "over the groups densely; unique_capacity "
                             "bounds the sparse inter-group exchange of "
                             "the mesh-wide lookup only")
        self.hsp = hsp

    def __call__(self, tbl: jax.Array, ids: jax.Array) -> jax.Array:
        hsp, rows = self.hsp, tbl.shape[0]

        @jax.custom_vjp
        def _lookup(tbl, ids):
            return hsp.gather(tbl, ids)

        def fwd(tbl, ids):
            return hsp.gather(tbl, ids), ids

        def bwd(ids, g):
            return hsp.group_grad(rows, ids, g).astype(tbl.dtype), None

        _lookup.defvjp(fwd, bwd)
        return _lookup(tbl, ids)


def make_hsp_lookup(mesh: Mesh, *, group_axes: Tuple[str, ...] = ("model",),
                    dp_axes: Tuple[str, ...] = ("data",),
                    compute_dtype=jnp.bfloat16,
                    unique_capacity: Optional[int] = None,
                    grad_wire_dtype=jnp.float32) -> HSPLookup:
    """Build an HSP lookup bound to ``mesh``.

    Returned fn: (table (V, d) sharded P(group_axes, None),
                  ids (G, cap) sharded P(dp_axes+group_axes (flat), ...))
                 → emb (G, cap, d), same batch sharding, replicated d.

    Grouping: vocab sharded over ``group_axes``; replicas over ``dp_axes``.
    The baseline (global sharding) is the same function with
    group_axes=("data","model") and dp_axes=() — the intra-"group" exchange
    then spans the whole cluster.

    ``unique_capacity`` bounds the per-device sparse-gradient message to
    that many unique rows (None = lossless, one slot per token).
    ``grad_wire_dtype`` is the on-the-wire dtype for exchanged gradient
    rows (bf16 halves inter-group bytes — beyond-paper compression knob).
    """
    return HSPLookup(mesh, group_axes, dp_axes, compute_dtype,
                     unique_capacity, grad_wire_dtype)


# --------------------------------------------------------------------------
# dense-grad baseline lookup (autodiff path; GSPMD dense allreduce)
# --------------------------------------------------------------------------

def dense_lookup(table: jax.Array, ids: jax.Array,
                 compute_dtype=jnp.bfloat16) -> jax.Array:
    """Plain differentiable gather. With table sharded P('model', None) and
    replicated over data, autodiff emits the *dense* (V/I, d) all-reduce
    over the data axes — the paper's baseline cost that the sparse exchange
    above eliminates."""
    return jnp.take(table, ids, axis=0).astype(compute_dtype)


# --------------------------------------------------------------------------
# Eq. 1 — grouped AdaGrad whose states stay identical across groups
# --------------------------------------------------------------------------

def adagrad_update(table: jax.Array, accum: jax.Array, grad: jax.Array,
                   lr: float, eps: float = 1e-10
                   ) -> Tuple[jax.Array, jax.Array]:
    """S_t = S_{t-1} + G_t²;  W_{t+1} = W_t − η·G_t/√(S_t+ε)  (paper Eq. 1).

    Because every group receives the identical aggregate G_t from the
    sparse exchange, per-group states S_{i,t} stay bitwise identical —
    centralized-equivalent training without learning-rate rescaling.
    """
    g = grad.astype(jnp.float32)
    accum = accum + g * g
    table = table - lr * g * jax.lax.rsqrt(accum + eps)
    return table, accum
