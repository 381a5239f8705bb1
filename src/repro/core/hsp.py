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
contains exactly the communication pattern we claim.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


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

def make_hsp_lookup(mesh: Mesh, *, group_axes: Tuple[str, ...] = ("model",),
                    dp_axes: Tuple[str, ...] = ("data",),
                    compute_dtype=jnp.bfloat16,
                    unique_capacity: Optional[int] = None,
                    grad_wire_dtype=jnp.float32):
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
    batch_axes = dp_axes + group_axes
    ids_spec = P(batch_axes if len(batch_axes) > 1 else batch_axes[0])
    table_spec = P(group_axes if len(group_axes) > 1 else group_axes[0], None)
    emb_spec = P(batch_axes if len(batch_axes) > 1 else batch_axes[0],
                 None, None)
    group_sz = functools.reduce(
        lambda a, b: a * b, [mesh.shape[a] for a in group_axes], 1)

    def _shard_lo(V_shard: int):
        """Row offset of this device's vocab shard within the group."""
        idx = jnp.int32(0)
        for a in group_axes:
            # axis sizes are static mesh facts (jax.lax.axis_size is not
            # available on older jax)
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        return idx * V_shard

    def _fwd_impl(table, ids):
        def fwd_local(tbl, idsl):
            # tbl: (V/I, d) local shard; idsl: (Gl, cap) local ids
            V_shard, d = tbl.shape
            lo = _shard_lo(V_shard)
            # phase 1: all-gather ids within the group (feature all-to-all)
            ids_g = jax.lax.all_gather(idsl, group_axes, tiled=True)  # (Gl*I, cap)
            rel = ids_g - lo
            owned = (rel >= 0) & (rel < V_shard)
            rel = jnp.clip(rel, 0, V_shard - 1)
            part = jnp.take(tbl, rel.reshape(-1), axis=0)
            part = part.reshape(*ids_g.shape, d).astype(compute_dtype)
            part = part * owned[..., None].astype(compute_dtype)
            # phase 2: reduce-scatter embeddings back to their requester
            # (each row has exactly one owner, so low-precision psum is exact)
            emb = jax.lax.psum_scatter(
                part, group_axes if len(group_axes) > 1 else group_axes[0],
                scatter_dimension=0, tiled=True)
            return emb

        return jax.shard_map(fwd_local, mesh=mesh,
                         in_specs=(table_spec, ids_spec),
                         out_specs=emb_spec, check_vma=False)(table, ids)

    def lookup_fn(table: jax.Array, ids: jax.Array) -> jax.Array:
        V, d = table.shape
        tdtype = table.dtype
        V_shard = V // group_sz

        @jax.custom_vjp
        def _lookup(table, ids):
            return _fwd_impl(table, ids)

        def fwd(table, ids):
            return _fwd_impl(table, ids), ids

        def bwd(ids, g):
            def bwd_local(idsl, gl):
                lo = _shard_lo(V_shard)
                gl2 = gl.reshape(-1, d).astype(jnp.float32)
                idsf = idsl.reshape(-1)
                # local dedup before any exchange (the "unique" stage)
                uids, urows = unique_accumulate(idsf, gl2, unique_capacity)
                # wire compression (DESIGN.md §7): bf16 halves, int8
                # quarters the exchanged gradient bytes. int8 uses a
                # per-row max-abs scale shipped alongside (fp32, d× smaller)
                if jnp.dtype(grad_wire_dtype) == jnp.int8:
                    amax = jnp.max(jnp.abs(urows), axis=1, keepdims=True)
                    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
                    urows_w = jnp.clip(jnp.round(urows / scale), -127, 127
                                       ).astype(jnp.int8)
                    all_scale = jax.lax.all_gather(scale, group_axes,
                                                   tiled=True)
                else:
                    urows_w = urows.astype(grad_wire_dtype)
                    all_scale = None
                # phase 1 (intra-group): all-gather sparse (ids, rows) over
                # `model` — the embedding-gradient all-to-all — and
                # scatter-add the rows this member owns into its shard
                all_ids = jax.lax.all_gather(uids, group_axes, tiled=True)
                all_rows = jax.lax.all_gather(urows_w, group_axes, tiled=True)
                if all_scale is not None:
                    all_rows = all_rows.astype(jnp.float32) * all_scale
                if dp_axes and unique_capacity is not None:
                    # paper-faithful sparse inter-group exchange: ship
                    # (ids, rows) across replicas. Buffer is bounded by the
                    # explicit unique_capacity; without a bound the dense
                    # shard-psum below is cheaper and memory-safe.
                    dpa = dp_axes if len(dp_axes) > 1 else dp_axes[0]
                    all_ids = jax.lax.all_gather(all_ids, dpa, tiled=True)
                    all_rows = jax.lax.all_gather(all_rows, dpa, tiled=True)
                rel = all_ids - lo
                owned = (all_ids >= 0) & (rel >= 0) & (rel < V_shard)
                rel = jnp.where(owned, rel, -1)
                dtbl = jnp.zeros((V_shard, d), jnp.float32)
                dtbl = scatter_add_rows(dtbl, rel, all_rows.astype(jnp.float32))
                # phase 2 (inter-group): reduce the OWNED shard across the
                # data/pod replicas — every group ends with the identical
                # aggregate G_t (Eq. 1).
                if dp_axes and unique_capacity is None:
                    # psum accumulates — int8 would overflow; cap at bf16
                    pdt = (jnp.bfloat16
                           if jnp.dtype(grad_wire_dtype) == jnp.int8
                           else grad_wire_dtype)
                    dtbl = jax.lax.psum(
                        dtbl.astype(pdt),
                        dp_axes if len(dp_axes) > 1 else dp_axes[0]
                    ).astype(jnp.float32)
                return dtbl.astype(tdtype)

            dtable = jax.shard_map(bwd_local, mesh=mesh,
                               in_specs=(ids_spec, emb_spec),
                               out_specs=table_spec, check_vma=False)(ids, g)
            return dtable, None

        _lookup.defvjp(fwd, bwd)
        return _lookup(table, ids)

    return lookup_fn


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
