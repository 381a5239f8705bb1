"""Plain reference of a training cell on a mesh of chips, in float32.

The maths is ``reference.py``'s, taken from it: the same weights from the
seed, the same HSTU and FuXi-alpha blocks, the same sampled softmax
averaged over the valid tokens of every shard of a step, AdamW on the dense
weights and row-wise AdaGrad on the item table one step late (tau = 1),
at ``default_matmul_precision("highest")``. Only where the work runs
differs, so that a step of G shards takes about one shard's time and a
chip holds a share of the table's state:

* shard ``g`` of a step runs its forward and backward on chip ``g``, over
  the whole table gathered there, and the loss's sums, the dense gradients
  and the table gradient are summed over the chips, the table's split by
  rows as it is summed;
* the table, its AdaGrad accumulator and its pending gradient are stored
  split by rows over the chips.

It needs as many devices as a step has shards, and imports nothing of the
system under test.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import reference as R

F32 = jnp.float32


def run(model: Dict, seed: int, batches: List[Dict[str, np.ndarray]], *,
        low=None, half_batch: bool = False, steps: int = 3) -> Dict:
    """As ``reference.run``: each step's loss, each leaf's first gradient
    norm and each leaf's change after the steps (``table`` is the item
    table)."""
    tr = model["training"]
    b1, b2, eps = tr["adam_b1"], tr["adam_b2"], tr["adam_eps"]
    lr_d, lr_s, eps_s = tr["lr_dense"], tr["lr_sparse"], tr["adagrad_eps"]
    key = jax.random.PRNGKey(seed % (1 << 32))
    shards = batches[0]["ids"].shape[0]
    devs = jax.devices()
    if len(devs) < shards:
        raise SystemExit(f"reference: {shards} shards a step need as many "
                         f"devices; JAX sees {len(devs)}")
    mesh = Mesh(np.array(devs[:shards]), ("shard",))
    every = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, P("shard", None))

    def place(b):
        return {k: jax.device_put(x, every if k == "rng" else
                                  NamedSharding(mesh, P("shard")))
                for k, x in b.items()}

    def grads(dense, stale, fresh, batch):
        """On each chip: its shard's loss sum and gradients over the whole
        table; then the sums over the chips."""
        full = lambda t: jax.lax.all_gather(t, "shard", tiled=True)
        one = {k: batch[k][0] for k in R.SHARD_KEYS}
        (s, n), (gd, gs, gf) = jax.value_and_grad(
            lambda d, st, fr: R.shard_loss(d, st, fr, one, model, low,
                                           half_batch),
            argnums=(0, 1, 2), has_aux=True)(dense, full(stale), full(fresh))
        n = jnp.maximum(jax.lax.psum(n, "shard"), 1.0)
        gd = jax.tree.map(lambda g: jax.lax.psum(g, "shard") / n, gd)
        gt = jax.lax.psum_scatter(gs + gf, "shard", scatter_dimension=0,
                                  tiled=True) / n
        return jax.lax.psum(s, "shard") / n, gd, gt

    spec = lambda b: {k: P() if k == "rng" else P("shard") for k in b}
    with jax.default_matmul_precision("highest"):
        dense0, table0 = jax.jit(lambda k: R.init_weights(model, k),
                                 out_shardings=(every, rows))(key)
        names = R.leaf_names(dense0) + ["table"]
        step_grads = jax.jit(lambda d, s, f, b: jax.shard_map(
            grads, mesh=mesh, in_specs=(P(), P("shard", None),
                                        P("shard", None), spec(b)),
            out_specs=(P(), P(), P("shard", None)), check_vma=False)(
                d, s, f, b))

        def _store(path, x):
            rab = any(getattr(k, "key", None) == "rab" for k in path)
            dt = jnp.dtype(model["rab_dtype"] if rab else model["dtype"])
            return x.astype(dt).astype(F32)

        @jax.jit
        def adam(d, m, v, g, c):
            m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
            v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_,
                             v, g)
            bc1, bc2 = 1 - b1 ** c, 1 - b2 ** c
            d = jax.tree_util.tree_map_with_path(
                lambda path, p, m_, v_: _store(path, p - lr_d * (m_ / bc1) / (
                    jnp.sqrt(v_ / bc2) + eps)), d, m, v)
            return d, m, v

        @jax.jit
        def adagrad(t, s, g):
            s = s + g * g
            return t - lr_s * g * jax.lax.rsqrt(s + eps_s), s

        norms = jax.jit(R._norms)
        dense = dense0
        m = jax.tree.map(jnp.zeros_like, dense0)
        v = jax.tree.map(jnp.zeros_like, dense0)
        prev = cur = table0
        acc = jnp.zeros_like(table0)
        pending = None
        losses, first = [], None
        for j, b in enumerate(batches[:steps]):
            if pending is not None:
                new, acc = adagrad(cur, acc, pending)
                prev, cur = cur, new
            lv, gd, pending = step_grads(dense, prev, cur, place(b))
            losses.append(float(lv))
            if j == 0:
                first = [float(x) for x in norms(gd)] + \
                    [float(norms(pending)[0])]
            dense, m, v = adam(dense, m, v, gd, float(j + 1))
        diff = jax.tree.map(lambda a, b_: a - b_, dense, dense0)
        change = [float(x) for x in norms(diff)] + \
            [float(norms(cur - table0)[0])]
    return {"losses": losses, "grad_norms": dict(zip(names, first)),
            "change_norms": dict(zip(names, change))}
