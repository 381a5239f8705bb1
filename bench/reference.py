"""Plain reference of HSTU and FuXi-alpha recall training, in float32.

It follows the published blocks and the training the configuration
states, in straightforward ``jax.numpy`` at
``default_matmul_precision("highest")``, with no kernels, packing tricks or
staging, and imports nothing of the system under test:

* weights from the seed by the configuration's initialisation (normal
  draws scaled as in the HSTU and FuXi-alpha descriptions), stored at the
  configuration's parameter dtypes (``dtype``, and ``rab_dtype`` for the
  relative attention bias) after the initialisation and after every
  update, and computed with in float32;
* HSTU block: ``U, V, Q, K = SiLU(LN(x) W1)``,
  ``A = SiLU(Q K^T / sqrt(d) + rab) * causal_same_sequence / (pos + 1)``,
  ``x + (LN0(A V) * U) W2``; the relative attention bias is a position
  bucket table plus a log2-bucketed time table (HSTU) or the exponential
  power time function ``amp * exp(-(dt / sigma)^rho)`` (FuXi-alpha, which
  also adds a gated FFN ``x + (SiLU(LN(x) Wg) * LN(x) Wi) Wo``);
* the sampled softmax of each valid token's next item against its negative
  ids, averaged over valid tokens;
* AdamW on the dense weights and row-wise AdaGrad on the item table, with
  the table's sparse gradient landing one step late (tau = 1): step ``j``
  reads its input rows from the table before step ``j - 1``'s rows landed
  and its labels and negatives after.

``low`` rounds every matmul operand to a lower precision under a
per-tensor scale, gradients passing straight through (the control);
``half_batch`` takes the mean over the first half of each step's valid
tokens only (a planted fault).
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def _r(x, low):
    """``x`` rounded to ``low`` under a per-tensor scale to its largest
    value, with the gradient passed straight through."""
    if low is None:
        return x
    s = jnp.max(jnp.abs(x)) / float(jnp.finfo(low).max) + 1e-30
    q = (x / s).astype(low).astype(F32) * s
    return x + jax.lax.stop_gradient(q - x)


def _mm(spec, a, b, low):
    return jnp.einsum(spec, _r(a, low), _r(b, low), precision=HI)


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _ln(x, w=None, b=None, eps=1e-5):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return y if w is None else y * w + b


# -- weights ------------------------------------------------------------------

def init_weights(model: Dict, key):
    """(dense, table) in float32 from the seed's key."""
    d, L, H = model["d_model"], model["num_layers"], model["num_heads"]
    dq = model["qkv_dim"]
    rab = model["rab"]
    fuxi = model["block"] == "fuxi"
    pdt = jnp.dtype(model["dtype"])
    store = lambda x: x.astype(pdt).astype(F32)

    def layer(k):
        if fuxi:
            k, k2, k3, k4, _ = jax.random.split(k, 5)
        a1, a2, a3 = jax.random.split(k, 3)
        p = {"ln_w": jnp.ones((d,), F32), "ln_b": jnp.zeros((d,), F32),
             "w_uvqk": store(jax.random.normal(a1, (d, 4 * H * dq), F32)
                             / math.sqrt(d)),
             "w_o": store(jax.random.normal(a2, (H * dq, d), F32)
                          / math.sqrt(H * dq * 2 * L))}
        kp, kt = jax.random.split(a3)
        r = {}
        if rab["use_pos"]:
            r["pos_table"] = 0.02 * jax.random.normal(
                kp, (rab["num_pos_buckets"], H), F32)
        if rab["use_time"] and not fuxi:
            r["time_table"] = 0.02 * jax.random.normal(
                kt, (rab["num_time_buckets"], H), F32)
        if rab["use_time"] and fuxi:
            r["time_amp"] = jnp.full((H,), 0.02, F32)
            r["time_log_sigma"] = jnp.linspace(2.0, 12.0, H).astype(F32)
            r["time_rho"] = jnp.zeros((H,), F32)
        p["rab"] = r
        if fuxi:
            f = model["d_ff"]
            p["ffn_ln_w"] = jnp.ones((d,), F32)
            p["ffn_ln_b"] = jnp.zeros((d,), F32)
            p["ffn_w_in"] = store(jax.random.normal(k2, (d, f), F32)
                                  / math.sqrt(d))
            p["ffn_w_gate"] = store(jax.random.normal(k3, (d, f), F32)
                                    / math.sqrt(d))
            p["ffn_w_out"] = store(jax.random.normal(k4, (f, d), F32)
                                   / math.sqrt(f * 2 * L))
        return p

    blocks = jax.vmap(layer)(jax.random.split(key, L))
    dense = {"blocks": blocks, "out_ln_w": jnp.ones((d,), F32),
             "out_ln_b": jnp.zeros((d,), F32)}
    table = model["training"]["init_scale_table"] * jax.random.normal(
        key, (model["vocab_size"], d), F32)
    return dense, table


# -- model --------------------------------------------------------------------

def _bias(r, model, qpos, kpos, qt, kt):
    rab = model["rab"]
    b = 0.0
    if "pos_table" in r:
        bucket = jnp.clip(qpos[:, None] - kpos[None, :], 0,
                          rab["num_pos_buckets"] - 1)
        b = b + r["pos_table"][bucket]
    dt = jnp.abs(qt[:, None] - kt[None, :]).astype(F32)
    if "time_table" in r:
        tb = jnp.floor(jnp.log10(1.0 + dt) / rab["time_bucket_scale"])
        tb = jnp.clip(tb.astype(jnp.int32), 0, rab["num_time_buckets"] - 1)
        b = b + r["time_table"][tb]
    if "time_amp" in r:
        sigma = jnp.exp(r["time_log_sigma"])
        rho = jax.nn.sigmoid(r["time_rho"]) * 1.5 + 0.25
        z = (dt[..., None] + 1e-6) / sigma
        b = b + r["time_amp"] * jnp.exp(-jnp.power(z, rho))
    return b


def attention(q, k, v, seg, pos, ts, r, model, low, qblock=256):
    """Causal same-sequence pointwise attention, one block of queries at a
    time against the ``max_seq_len + qblock`` keys that can reach it."""
    T, H, dq = q.shape
    L = model["max_seq_len"]
    qb = min(qblock, T)
    assert T % qb == 0, (T, qb)
    W = L + qb
    pad = lambda a, fill: jnp.concatenate(
        [jnp.full((L,) + a.shape[1:], fill, a.dtype), a])
    kp, vp = pad(k, 0.0), pad(v, 0.0)
    segp, posp, tsp = pad(seg, -2), pad(pos, 0), pad(ts, 0)
    slot = jnp.arange(T, dtype=jnp.int32)
    slotp = pad(slot, -1)
    scale = 1.0 / math.sqrt(dq)

    @jax.checkpoint
    def one(b):
        s0 = b * qb
        sl = lambda a, n, at: jax.lax.dynamic_slice_in_dim(a, at, n, 0)
        qq, qs, qp, qt, qi = (sl(a, qb, s0) for a in (q, seg, pos, ts, slot))
        kk, vv, ks, kpp, kt, ki = (sl(a, W, s0) for a in
                                   (kp, vp, segp, posp, tsp, slotp))
        s = _mm("qhd,khd->qkh", qq, kk, low) * scale
        a = _silu(s + _bias(r, model, qp, kpp, qt, kt))
        m = (qs[:, None] == ks[None, :]) & (qs[:, None] >= 0) & \
            (qi[:, None] >= ki[None, :])
        a = jnp.where(m[..., None], a, 0.0) / (qp + 1)[:, None, None]
        return _mm("qkh,khd->qhd", a, vv, low)

    out = jax.lax.map(one, jnp.arange(T // qb))
    return out.reshape(T, H, dq)


def hidden(dense, model, x, offsets, ts, low):
    T = x.shape[0]
    H, dq, eps = model["num_heads"], model["qkv_dim"], model["norm_eps"]
    slot = jnp.arange(T, dtype=jnp.int32)
    seg = jnp.searchsorted(offsets, slot, side="right").astype(jnp.int32) - 1
    seg = jnp.where(slot < offsets[-1], seg, -1)
    pos = slot - offsets[jnp.clip(seg, 0, offsets.shape[0] - 2)]

    @jax.checkpoint
    def layer(x, p):
        h = _ln(x, p["ln_w"], p["ln_b"], eps)
        uvqk = _silu(_mm("td,de->te", h, p["w_uvqk"], low))
        u, v, q, k = jnp.split(uvqk, 4, axis=-1)
        y = attention(q.reshape(T, H, dq), k.reshape(T, H, dq),
                      v.reshape(T, H, dq), seg, pos, ts, p["rab"], model, low)
        y = _ln(y.reshape(T, H * dq), eps=eps)
        x = x + _mm("te,ed->td", y * u, p["w_o"], low)
        if "ffn_w_in" in p:
            h = _ln(x, p["ffn_ln_w"], p["ffn_ln_b"], eps)
            g = _silu(_mm("td,df->tf", h, p["ffn_w_gate"], low))
            i = _mm("td,df->tf", h, p["ffn_w_in"], low)
            x = x + _mm("tf,fd->td", g * i, p["ffn_w_out"], low)
        return x, None

    x, _ = jax.lax.scan(layer, x, dense["blocks"])
    return _ln(x, dense["out_ln_w"], dense["out_ln_b"], eps)


def shard_loss(dense, stale, fresh, batch, model, low, half_batch,
               chunk=512):
    """Sum of ``lse - pos`` over one shard's valid tokens (``(T,)`` ids,
    ``(S + 1,)`` offsets, ``(T, R)`` negatives), and their count."""
    ids, labels = batch["ids"], batch["labels"]
    offsets, ts = batch["offsets"], batch["timestamps"]
    neg = batch["neg_ids"]
    T = ids.shape[0]
    h = hidden(dense, model, stale[ids], offsets, ts, low)
    total = offsets[-1]
    valid = jnp.arange(T) < (total // 2 if half_batch else total)
    pos = jnp.sum(_r(h, low) * _r(fresh[labels], low), -1)
    c = min(chunk, T)

    @jax.checkpoint
    def neg_lse(args):
        hh, nn, pp = args
        lg = _mm("td,trd->tr", hh, fresh[nn], low)
        return jax.nn.logsumexp(jnp.concatenate([pp[:, None], lg], 1), -1)

    lse = jax.lax.map(neg_lse, (h.reshape(T // c, c, -1),
                                neg.reshape(T // c, c, -1),
                                pos.reshape(T // c, c))).reshape(T)
    v = valid.astype(F32)
    return jnp.sum((lse - pos) * v), jnp.sum(v)


SHARD_KEYS = ("ids", "labels", "offsets", "timestamps", "neg_ids")


def loss(dense, stale, fresh, batch, model, low, half_batch):
    """Mean of ``lse - pos`` over the valid tokens of every shard of the
    step: the global step that data-parallel training computes, since
    attention never crosses shards. Several shards go one at a time, each
    recomputed in the backward pass, so that the memory stays one shard's;
    one shard needs no recomputation."""
    shards = {k: batch[k] for k in SHARD_KEYS}
    if batch["ids"].shape[0] == 1:
        s, n = shard_loss(dense, stale, fresh,
                          {k: x[0] for k, x in shards.items()}, model, low,
                          half_batch)
    else:
        one = jax.checkpoint(lambda b: shard_loss(dense, stale, fresh, b,
                                                  model, low, half_batch))
        s, n = jax.lax.map(one, shards)
        s, n = jnp.sum(s), jnp.sum(n)
    return s / jnp.maximum(n, 1.0)


# -- training -----------------------------------------------------------------

def leaf_names(tree) -> List[str]:
    paths = jax.tree_util.tree_flatten_with_path(tree)[0]
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in paths]


def _norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))
            for x in jax.tree.leaves(tree)]


def run(model: Dict, seed: int, batches: List[Dict[str, np.ndarray]], *,
        low=None, half_batch: bool = False, steps: int = 3) -> Dict:
    """Train ``steps`` steps from the seed; returns each step's loss, each
    leaf's first gradient norm and each leaf's change after the steps
    (``table`` is the item table)."""
    tr = model["training"]
    b1, b2, eps = tr["adam_b1"], tr["adam_b2"], tr["adam_eps"]
    lr_d, lr_s, eps_s = tr["lr_dense"], tr["lr_sparse"], tr["adagrad_eps"]
    key = jax.random.PRNGKey(seed % (1 << 32))
    with jax.default_matmul_precision("highest"):
        dense0, table0 = jax.jit(lambda k: init_weights(model, k))(key)
        names = leaf_names(dense0) + ["table"]
        grad = jax.jit(jax.value_and_grad(
            lambda d, s, f, b: loss(d, s, f, b, model, low, half_batch),
            argnums=(0, 1, 2)))

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

        norms = jax.jit(_norms)
        dense = dense0
        m = jax.tree.map(jnp.zeros_like, dense0)
        v = jax.tree.map(jnp.zeros_like, dense0)
        prev = cur = table0
        acc = jnp.zeros_like(table0)
        pending = None
        losses, first = [], None
        for j, b in enumerate(batches[:steps]):
            b = {k: jnp.asarray(x) for k, x in b.items()}
            if pending is not None:
                new, acc = adagrad(cur, acc, pending)
                prev, cur = cur, new
            lv, (gd, gs, gf) = grad(dense, prev, cur, b)
            pending = gs + gf
            del gs, gf
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
