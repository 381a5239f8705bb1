"""GR model = stack of HSTU/FuXi blocks over a *packed* jagged token buffer.

The sparse stage (embedding lookup / HSP) happens OUTSIDE this module — the
dense model consumes already-looked-up embeddings ``(cap, d)`` plus the
jagged structure (offsets, timestamps). This sparse/dense split is exactly
the paper's execution model (§4.2.2 semi-async: sparse and dense are
separate pipeline stages/streams).

Multi-device layout: the global batch is ``(G, cap, ...)`` with G = number
of data shards (one jagged pack per device, built by the load balancer
§4.1.3) and the per-shard model vmapped over G.

Attention planning: when the attn_fn is plan-aware (exposes ``make_plan``,
e.g. the Pallas work-list kernel's PlannedAttention), :func:`gr_hidden`
builds one ``JaggedAttnPlan`` per step — token metadata + compacted live
block-pair work-lists — and threads the same plan through every layer,
instead of each layer recomputing it. On TPU the Pallas kernel is the
default attn_fn; elsewhere the XLA blocked scan remains the default.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels.jagged_attention import ops as attn_ops
from repro.models.fuxi import fuxi_block, init_fuxi_block
from repro.models.hstu import (hstu_block, hstu_block_append, hstu_block_kv,
                               init_hstu_block,
                               jagged_pointwise_attention_blocked)
from repro.models.sasrec import init_sasrec_block, sasrec_block

Params = Dict[str, Any]

_BLOCKS = {
    "hstu": (init_hstu_block, hstu_block),
    "fuxi": (init_fuxi_block, fuxi_block),
    "sasrec": (init_sasrec_block, sasrec_block),
}


def default_attn_fn(cfg: ArchConfig) -> Optional[Callable]:
    """TPU → the Pallas work-list kernel (max_row_len = cfg.max_seq_len
    bounds the work-list); elsewhere None (the blocks fall back to the XLA
    blocked scan). SASRec inlines its own softmax attention."""
    if cfg.gr_block == "sasrec":
        return None
    if jax.default_backend() == "tpu":
        # pairs_per_step=None: the plan builder reads the tuned.json entry
        # for this (block, nb) regime via kernels.autotune (default 1)
        return attn_ops.PlannedAttention(block=128,
                                         max_row_len=cfg.max_seq_len,
                                         pairs_per_step=None)
    return None


def init_gr(key, cfg: ArchConfig, dtype=None) -> Params:
    dtype = dtype or jnp.dtype(cfg.dtype)
    init_fn = _BLOCKS[cfg.gr_block or "hstu"][0]
    keys = jax.random.split(key, cfg.num_layers)
    blocks = jax.vmap(lambda k: init_fn(k, cfg, dtype))(keys)
    return {"blocks": blocks,
            "out_ln_w": jnp.ones((cfg.d_model,), dtype),
            "out_ln_b": jnp.zeros((cfg.d_model,), dtype)}


def gr_hidden(params: Params, cfg: ArchConfig, x: jax.Array,
              offsets: jax.Array, timestamps: jax.Array,
              *, attn_fn: Optional[Callable] = None,
              remat: bool = True) -> jax.Array:
    """x: (cap, d) packed embeddings → (cap, d) hidden states."""
    block_fn = _BLOCKS[cfg.gr_block or "hstu"][1]
    if attn_fn is None:
        attn_fn = default_attn_fn(cfg)

    # one-per-step attention planning: build the jagged metadata +
    # work-lists once, outside the layer scan, and reuse across layers
    plan = None
    if attn_fn is not None and hasattr(attn_fn, "make_plan"):
        plan = attn_fn.make_plan(offsets, timestamps, x.shape[0])

    def body(x, bp):
        f = lambda x_: block_fn(bp, cfg, x_, offsets, timestamps,
                                attn_fn=attn_fn, plan=plan)
        if remat:
            f = jax.checkpoint(f, policy=jax.checkpoint_policies.nothing_saveable)
        return f(x), None

    with jax.named_scope("blocks"):
        x, _ = jax.lax.scan(body, x, params["blocks"])
    return _final_norm(params, cfg, x)


def _final_norm(params: Params, cfg: ArchConfig, x: jax.Array) -> jax.Array:
    """Final affine layernorm over the hidden stream — row-local, shared by
    the packed forward and the serving row/append entries so all paths end
    in bitwise-identical ops."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
    y = y * params["out_ln_w"].astype(jnp.float32) + params["out_ln_b"].astype(jnp.float32)
    return y.astype(x.dtype)


def gr_hidden_sharded(params: Params, cfg: ArchConfig, x: jax.Array,
                      offsets: jax.Array, timestamps: jax.Array,
                      *, attn_fn: Optional[Callable] = None,
                      remat: bool = True) -> jax.Array:
    """Batched over shards: x (G, cap, d), offsets (G, B+1), ts (G, cap)."""
    fn = partial(gr_hidden, params, cfg, attn_fn=attn_fn, remat=remat)
    return jax.vmap(fn)(x, offsets, timestamps)


# --------------------------------------------------------------------------
# serving-mode entry points (repro.serving)
# --------------------------------------------------------------------------

def gr_serve_hidden(params: Params, cfg: ArchConfig, x: jax.Array,
                    offsets: jax.Array, timestamps: jax.Array,
                    *, attn_fn: Optional[Callable] = None) -> jax.Array:
    """Inference-mode hidden states over one jagged pack: same forward as
    training but without activation rematerialization (nothing is
    differentiated at serving time, so checkpointing would only re-run the
    blocks). The attention plan is still built once per micro-batch and
    shared by every layer."""
    return gr_hidden(params, cfg, x, offsets, timestamps,
                     attn_fn=attn_fn, remat=False)


def gr_user_embeddings(params: Params, cfg: ArchConfig, x: jax.Array,
                       offsets: jax.Array, timestamps: jax.Array,
                       last_pos: jax.Array,
                       *, attn_fn: Optional[Callable] = None) -> jax.Array:
    """Recall-serving user representations: the hidden state at each
    sequence's last token. x (cap, d), last_pos (S,) → (S, d). Rows past a
    pack's live sequences gather slot ``last_pos[j]`` verbatim — callers
    (the serving engine's slot map) ignore them."""
    h = gr_serve_hidden(params, cfg, x, offsets, timestamps, attn_fn=attn_fn)
    return jnp.take(h, last_pos, axis=0)


def gr_user_embeddings_sharded(params: Params, cfg: ArchConfig,
                               x: jax.Array, offsets: jax.Array,
                               timestamps: jax.Array, last_pos: jax.Array,
                               *, attn_fn: Optional[Callable] = None
                               ) -> jax.Array:
    """Batched over serving shards: x (G, cap, d), last_pos (G, S) →
    (G, S, d)."""
    fn = lambda xx, oo, tt, lp: gr_user_embeddings(
        params, cfg, xx, oo, tt, lp, attn_fn=attn_fn)
    return jax.vmap(fn)(x, offsets, timestamps, last_pos)


# --------------------------------------------------------------------------
# slot-buffer serving entries — one user per row, incremental prefix reuse
# --------------------------------------------------------------------------

def serve_attn_block(seq_len: int) -> int:
    """Effective kv-block the XLA blocked attention uses on one slot row:
    ``min(512, S)`` when it divides S (the training default after its
    internal ``block = min(block, cap)`` clamp), else the largest divisor
    of S ≤ 512. The warm append path must scan the key axis in the same
    block order to stay bitwise-equal to the cold full encode."""
    if seq_len <= 512:
        return seq_len
    for b in range(512, 0, -1):
        if seq_len % b == 0:
            return b
    return seq_len


def gr_serve_row_kv(params: Params, cfg: ArchConfig, x: jax.Array,
                    timestamps: jax.Array, length: jax.Array,
                    *, attn_block: Optional[int] = None):
    """Cold path of the slot-buffer engine: full encode of one slot row
    x (S, d) / timestamps (S,), also collecting every layer's K/V
    projections to seed the slot's prefix cache.

    Returns (emb (d,), k (L, S, H, dqk), v (L, S, H, dv)). Slots past
    ``length`` may hold arbitrary finite values — masked attention
    contributes exact zeros, so emb is bitwise-equal to the packed
    :func:`gr_user_embeddings` on the same live tokens. HSTU-only (the
    K/V-cache contract is the HSTU block's)."""
    if (cfg.gr_block or "hstu") != "hstu":
        raise ValueError("prefix reuse requires gr_block='hstu', got "
                         f"{cfg.gr_block!r}")
    S = x.shape[0]
    blk = attn_block or serve_attn_block(S)
    attn_fn = partial(jagged_pointwise_attention_blocked, block=blk)
    offsets = jnp.stack([jnp.zeros((), jnp.int32), length.astype(jnp.int32)])

    def body(x, bp):
        out, k, v = hstu_block_kv(bp, cfg, x, offsets, timestamps,
                                  attn_fn=attn_fn)
        return out, (k, v)

    h, (ks, vs) = jax.lax.scan(body, x, params["blocks"])
    h = _final_norm(params, cfg, h)
    emb = jnp.take(h, jnp.maximum(length - 1, 0), axis=0)
    return emb, ks, vs


def gr_serve_row_append(params: Params, cfg: ArchConfig, x_new: jax.Array,
                        timestamps: jax.Array,
                        k_cache: jax.Array, v_cache: jax.Array,
                        prefix_len: jax.Array, n_new: jax.Array,
                        *, kv_block: Optional[int] = None):
    """Warm path: encode only the appended tokens x_new (Q, d) of one slot
    row against the cached prefix K/V (L, S, H, ·), updating the caches in
    place at [prefix_len, prefix_len+Q).

    Returns (emb (d,), k_cache, v_cache) with emb the hidden state of the
    last live appended token — bitwise-equal to a from-scratch encode of
    the full row (causality keeps prefix hidden states unchanged; the
    append attention mirrors the blocked kernel's accumulation order)."""
    S = timestamps.shape[0]
    blk = kv_block or serve_attn_block(S)

    def body(x, layer):
        bp, kc, vc = layer
        out, kc, vc = hstu_block_append(bp, cfg, x, timestamps, kc, vc,
                                        prefix_len, n_new, kv_block=blk)
        return out, (kc, vc)

    h, (ks, vs) = jax.lax.scan(
        body, x_new, (params["blocks"], k_cache, v_cache))
    h = _final_norm(params, cfg, h)
    emb = jnp.take(h, jnp.maximum(n_new - 1, 0), axis=0)
    return emb, ks, vs


def gr_encode_slots(params: Params, cfg: ArchConfig, x: jax.Array,
                    timestamps: jax.Array, lengths: jax.Array,
                    *, attn_block: Optional[int] = None):
    """Cold tick over R slot rows: x (R, S, d), ts (R, S), lengths (R,) →
    (emb (R, d), k (R, L, S, H, dqk), v (R, L, S, H, dv))."""
    fn = lambda xx, tt, ll: gr_serve_row_kv(params, cfg, xx, tt, ll,
                                            attn_block=attn_block)
    return jax.vmap(fn)(x, timestamps, lengths)


def gr_append_slots(params: Params, cfg: ArchConfig, x_new: jax.Array,
                    timestamps: jax.Array,
                    k_cache: jax.Array, v_cache: jax.Array,
                    prefix_len: jax.Array, n_new: jax.Array,
                    *, kv_block: Optional[int] = None):
    """Warm tick over R slot rows: x_new (R, Q, d), ts (R, S), caches
    (R, L, S, H, ·), prefix_len/n_new (R,) → (emb (R, d), k, v)."""
    fn = lambda xx, tt, kk, vv, pp, nn: gr_serve_row_append(
        params, cfg, xx, tt, kk, vv, pp, nn, kv_block=kv_block)
    return jax.vmap(fn)(x_new, timestamps, k_cache, v_cache,
                        prefix_len, n_new)


def gr_encode_slots_flat(params: Params, cfg: ArchConfig, x: jax.Array,
                         timestamps: jax.Array, lengths: jax.Array,
                         *, attn_fn: Optional[Callable] = None) -> jax.Array:
    """Cold tick without K/V collection (any gr_block): row-per-user full
    encode, (R, S, d) → (R, d). The no-prefix-reuse fallback of the
    streaming engine (SASRec/FuXi, or kv_cache=False)."""
    def one(xx, tt, ll):
        offsets = jnp.stack([jnp.zeros((), jnp.int32), ll.astype(jnp.int32)])
        h = gr_hidden(params, cfg, xx, offsets, tt, attn_fn=attn_fn,
                      remat=False)
        return jnp.take(h, jnp.maximum(ll - 1, 0), axis=0)
    return jax.vmap(one)(x, timestamps, lengths)
