"""Pure-JAX optimizers.

AdamW for the dense backbone (paper Appendix A: lr 4e-3, no weight decay
for GR; the LM plans use standard wd) and AdaGrad for the sparse embedding
table (paper Eq. 1). Optimizer-state dtype is configurable — the 398B
assigned config uses bf16 moments to fit the single-pod HBM budget
(DESIGN.md §7; the dry-run's memory_analysis is the check).
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.embedding import tables as ET
from repro.embedding.tables import ShadowedTable


class AdamWState(NamedTuple):
    mu: Any
    nu: Any
    count: jax.Array


def adamw_init(params: Any, dtype=jnp.float32) -> AdamWState:
    z = lambda p: jnp.zeros(p.shape, dtype)
    return AdamWState(mu=jax.tree.map(z, params),
                      nu=jax.tree.map(z, params),
                      count=jnp.zeros((), jnp.int32))


def adamw_update(grads: Any, state: AdamWState, params: Any, *,
                 lr: float = 4e-3, b1: float = 0.9, b2: float = 0.999,
                 eps: float = 1e-8, weight_decay: float = 0.0):
    c = state.count + 1
    bc1 = 1.0 - b1 ** c.astype(jnp.float32)
    bc2 = 1.0 - b2 ** c.astype(jnp.float32)

    def upd(p, g, m, v):
        g = g.astype(jnp.float32)
        m32 = m.astype(jnp.float32) * b1 + (1 - b1) * g
        v32 = v.astype(jnp.float32) * b2 + (1 - b2) * g * g
        step = lr * (m32 / bc1) / (jnp.sqrt(v32 / bc2) + eps)
        if weight_decay:
            step = step + lr * weight_decay * p.astype(jnp.float32)
        return ((p.astype(jnp.float32) - step).astype(p.dtype),
                m32.astype(m.dtype), v32.astype(v.dtype))

    out = jax.tree.map(upd, params, grads, state.mu, state.nu)
    new_params = jax.tree.map(lambda t: t[0], out,
                              is_leaf=lambda x: isinstance(x, tuple))
    new_mu = jax.tree.map(lambda t: t[1], out,
                          is_leaf=lambda x: isinstance(x, tuple))
    new_nu = jax.tree.map(lambda t: t[2], out,
                          is_leaf=lambda x: isinstance(x, tuple))
    return new_params, AdamWState(mu=new_mu, nu=new_nu, count=c)


class AdaGradState(NamedTuple):
    accum: Any


def adagrad_init(params: Any, init: float = 0.0,
                 dtype=jnp.float32) -> AdaGradState:
    return AdaGradState(accum=jax.tree.map(
        lambda p: jnp.full(p.shape, init, dtype), params))


def adagrad_update(grads: Any, state: AdaGradState, params: Any, *,
                   lr: float = 4e-3, eps: float = 1e-10):
    """Paper Eq. 1 — identical-aggregate-gradient AdaGrad."""
    def upd(p, g, s):
        g = g.astype(jnp.float32)
        s32 = s.astype(jnp.float32) + g * g
        newp = (p.astype(jnp.float32)
                - lr * g * jax.lax.rsqrt(s32 + eps)).astype(p.dtype)
        return newp, s32.astype(s.dtype)

    out = jax.tree.map(upd, params, grads, state.accum)
    new_params = jax.tree.map(lambda t: t[0], out,
                              is_leaf=lambda x: isinstance(x, tuple))
    new_accum = jax.tree.map(lambda t: t[1], out,
                             is_leaf=lambda x: isinstance(x, tuple))
    return new_params, AdaGradState(accum=new_accum)


# Table elements that making the shadow anew converts in the time a scatter
# lands one row: the landing makes it anew once the step's slots times this
# reach the table's size. On a v5e a scatter took 80-100 ns a row and a
# rebuild 0.024 ns an element at 2^18 x 1024 (packed: 4,200 elements a row;
# the scatter wins below a share of 0.22) and 0.010 at 2^22 x 128 (bf16:
# 7,700; below 1/64).
SHADOW_SCATTER_ROW_ELEMS = 6144


def adagrad_sparse_update(table: ShadowedTable, ids: jax.Array,
                          grad_rows: jax.Array, *, lr: float = 4e-3,
                          eps: float = 1e-10,
                          interpret: Optional[bool] = None,
                          aligned: bool = False) -> ShadowedTable:
    """Row-sparse Eq.-1 AdaGrad over (id, grad-row) pairs.

    ``ids`` (n,) int32 (< 0 = empty slot, duplicates allowed) and
    ``grad_rows`` (n, D) are deduplicated through the jagged_lookup
    sorted-runsum (table-major sort + run-sum, unique ids at run ends),
    then master and accumulator are rewritten at *only the touched rows* —
    the dense (V, D) update this replaces rewrote every row just to change
    the ones a batch references. A live shadow is landed from the landed
    master rows, in one of two ways chosen from the shapes: a scatter of
    the touched rows, or, where the step's slots are many for the table's
    size (:data:`SHADOW_SCATTER_ROW_ELEMS`), the whole shadow made anew
    from the master in one elementwise pass. Since ``shadow ==
    shadow_of(master, qdtype)`` held for every row, both leave the same
    bits. A stripped 0-row placeholder stays as it is.

    Numerics are identical to :func:`adagrad_update` on the touched rows
    (same fp32 ops in the same order); untouched rows are bit-unchanged.

    ``aligned``: the pairs are the table's rows in order (``ids[i]`` is
    ``i``, or < 0 for an empty slot; ``grad_rows`` has the table's shape),
    as a chip's aggregated gradient of its HSP shard is. They land in one
    elementwise pass, with no sort, run sum or scatter: a zero row adds 0
    to its accumulator and moves by −0, so it stays bit-unchanged.
    """
    if aligned:
        g = jnp.where((ids >= 0)[:, None], grad_rows.astype(jnp.float32), 0.0)
        accum = table.accum + g * g
        master = table.master + (-lr * g * jax.lax.rsqrt(accum + eps))
        shadow = table.shadow
        if ET.live_shadow(table) is not None:
            shadow = ET.shadow_of(master, ET.shadow_dtype(shadow))
        return ShadowedTable(master=master, shadow=shadow, accum=accum)
    if ids.shape[0] == 0:
        return table
    from repro.kernels.jagged_lookup.ops import dedup_rows
    uids, sums = dedup_rows(grad_rows.astype(jnp.float32), ids,
                            interpret=interpret)
    V = table.master.shape[0]
    keep = (uids >= 0) & (uids < V)
    safe = jnp.where(keep, uids, 0)
    g = sums * keep[:, None]
    s_new = table.accum[safe] + g * g
    delta = -lr * g * jax.lax.rsqrt(s_new + eps)
    dest = jnp.where(keep, uids, V)                     # V = dropped
    master = table.master.at[dest].add(
        jnp.where(keep[:, None], delta, 0.0), mode="drop")
    accum = table.accum.at[dest].add(
        jnp.where(keep[:, None], g * g, 0.0), mode="drop")
    shadow = table.shadow
    if ET.live_shadow(table) is not None:
        # from the rows the scatter actually wrote: recomputing
        # master[safe] + delta here can differ by an ulp when XLA fuses
        # the two delta uses differently, silently breaking the bitwise
        # invariant
        qdtype = ET.shadow_dtype(shadow)
        if ids.shape[0] * SHADOW_SCATTER_ROW_ELEMS >= table.master.size:
            shadow = ET.shadow_of(master, qdtype)
        else:
            shadow = shadow.at[dest].set(ET.shadow_of(master[safe], qdtype),
                                         mode="drop")
    return ShadowedTable(master=master, shadow=shadow, accum=accum)
