"""ArchConfig → model functions + dry-run input specs.

Two families:
  * LM bundles (the 10 assigned architectures): init / loss / prefill /
    decode over (tokens|embeds, labels) batches.
  * GR bundles (HSTU/FuXi — the paper's models): dense init + jagged batch
    loss with sparse-table lookups and sampled-softmax recall training.

``input_specs`` returns ShapeDtypeStruct stand-ins for every model input —
weak-type-correct, shardable, no device allocation — which is what the
multi-pod dry-run lowers against.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.configs.shapes import ShapeConfig
from repro.core import negative_sampling as NS
from repro.core.hsp import HSPShard
from repro.embedding import tables as ET
from repro.models import gr as GR
from repro.models import transformer as TF

Params = Dict[str, Any]
Batch = Dict[str, jax.Array]

I32 = jnp.int32


def sds(shape, dtype) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


# --------------------------------------------------------------------------
# LM bundle
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class LMBundle:
    cfg: ArchConfig

    def init(self, key) -> Params:
        return TF.init_lm(key, self.cfg)

    def loss(self, params: Params, batch: Batch, *, q_block: int = 1024,
             remat: bool = True) -> jax.Array:
        return TF.lm_loss(params, self.cfg, batch, q_block=q_block,
                          remat=remat)

    def prefill(self, params: Params, batch: Batch, *, q_block: int = 1024,
                max_len: Optional[int] = None):
        return TF.lm_prefill(params, self.cfg, batch, q_block=q_block,
                             max_len=max_len)

    def decode(self, params: Params, token, cache, cache_index,
               *, embeds=None):
        return TF.lm_decode_step(params, self.cfg, token, cache, cache_index,
                                 embeds=embeds)

    def init_cache(self, batch: int, max_len: int):
        return TF.init_cache(self.cfg, batch, max_len)

    # ---- dry-run specs ----------------------------------------------------
    def input_specs(self, shape: ShapeConfig) -> Dict[str, Any]:
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        stub = cfg.frontend == "stub_embed"
        if shape.kind == "train":
            batch: Dict[str, Any] = {"labels": sds((B, S), I32)}
            if stub:
                batch["embeds"] = sds((B, S, cfg.d_model), cfg.dtype)
            else:
                batch["tokens"] = sds((B, S), I32)
            return {"batch": batch}
        if shape.kind == "prefill":
            batch = ({"embeds": sds((B, S, cfg.d_model), cfg.dtype)} if stub
                     else {"tokens": sds((B, S), I32)})
            return {"batch": batch}
        # decode: one new token against a cache of seq_len
        cache = jax.eval_shape(lambda: TF.init_cache(cfg, B, S))
        out: Dict[str, Any] = {"cache": cache,
                               "cache_index": sds((), I32)}
        if stub:
            out["embeds"] = sds((B, 1, cfg.d_model), cfg.dtype)
            out["token"] = sds((B, 1), I32)
        else:
            out["token"] = sds((B, 1), I32)
        return out


# --------------------------------------------------------------------------
# GR bundle (the paper's workload)
# --------------------------------------------------------------------------

def gr_capacity(shape: ShapeConfig, num_shards: int) -> Tuple[int, int]:
    """(tokens capacity, max samples) per device shard. The load balancer
    (§4.1.3) packs users to a per-shard token budget; worst case is
    users_per_shard full-length sequences, with 2× sample-count slack for
    token-aware dynamic batch scaling of short sequences."""
    users = max(1, shape.global_batch // num_shards)
    cap = users * shape.seq_len
    return cap, 2 * users


@dataclass(frozen=True)
class GRBundle:
    cfg: ArchConfig

    def init_dense(self, key) -> Params:
        return GR.init_gr(key, self.cfg)

    def init_table(self, key) -> jax.Array:
        return (jax.random.normal(key, (self.cfg.vocab_size,
                                        self.cfg.d_model), jnp.float32)
                * 0.02)

    def input_gather(self, table: jax.Array, batch: Batch, *,
                     lookup_fn: Optional[Callable] = None) -> jax.Array:
        """The input-side lookup as a standalone pipeline stage (the
        ``emb_fwd`` stage of Algorithm 1): exactly the gather :meth:`loss`
        would perform for ``batch["ids"]``, so a precomputed result can be
        passed back via ``x_emb=`` without changing a single bit. Without
        ``lookup_fn`` this is a plain take + cast, which is linear — the
        staged trainer transposes it to recover the input-side table grad."""
        lookup = lookup_fn or (lambda t, i: jnp.take(t, i, axis=0)
                               .astype(jnp.dtype(self.cfg.dtype)))
        return lookup(table, batch["ids"])

    def loss(self, dense_params: Params, table: jax.Array, batch: Batch, *,
             lookup_fn: Optional[Callable] = None,
             neg_mode: str = "fused", expansion: int = 1,
             neg_segment: int = 128, fetch_dtype=ET.SHADOW_DTYPE,
             neg_impl: Optional[str] = None,
             neg_scatter_impl: Optional[str] = None, attn_fn=None,
             input_table: Optional[jax.Array] = None,
             x_emb: Optional[jax.Array] = None,
             shadow: Optional[jax.Array] = None,
             remat: bool = True) -> jax.Array:
        """Sampled-softmax recall loss over a sharded jagged batch.

        batch: ids/timestamps/labels (G, cap), offsets (G, B+1),
               neg_ids (G, cap, R), rng (2,) uint32.
        neg_mode: "fused" (default) runs the ID-driven megakernel path —
                  gather + dequant + §4.3.3 sharing + Eq.-2 logsumexp in
                  one pass, no (T, R, d) or (T, R·k) HBM buffers
                  (``neg_impl`` picks pallas/xla, None = backend dispatch;
                  ``neg_scatter_impl`` forwards the kernel's tuning
                  knob — None reads tuned.json via kernels.autotune);
                  "baseline" materializes (G, cap, R, d) (§4.3 challenge,
                  the Table 7 reference);
                  "segmented" scans fixed-size segments with quantized
                  fetches (§4.3.1 + §4.3.2, logit tensors still in HBM).
        expansion: §4.3.3 intra-batch logit sharing factor k.
        attn_fn: None dispatches per backend (models.gr.default_attn_fn):
                 the Pallas work-list jagged-attention kernel on TPU with
                 a JaggedAttnPlan built once per step and shared by all
                 layers, the XLA blocked scan elsewhere.
        input_table: table for the *input-side* lookup only (the sparse
                 forward the §4.2.2 pipeline prefetches before the delayed
                 sparse update lands — the trainer passes the one-step-
                 stale master here). Loss-stage reads (labels, negatives)
                 always use ``table``. Defaults to ``table``.
        x_emb: precomputed input-side embeddings (the ``emb_fwd`` pipeline
                 stage's output, from :meth:`input_gather`). When given,
                 the input lookup is skipped entirely and the input-side
                 table gradient is delivered by the caller transposing the
                 gather — this is how the staged execution engine threads
                 the prefetched (one-step-stale) rows into the dense
                 stage. Mutually exclusive with ``input_table``.
        shadow: persistent half-precision shadow for the fused negative
                 gather (§4.3.2 end to end); gradients flow to ``table``.
        """
        cfg = self.cfg
        lookup = lookup_fn or (lambda t, i: jnp.take(t, i, axis=0)
                               .astype(jnp.dtype(cfg.dtype)))
        # one chip of an HSP mesh (inside the per-chip stages' shard_map):
        # ``table`` and ``shadow`` hold the chip's rows
        hsp = lookup_fn.hsp if isinstance(lookup_fn, HSPShard) else None
        if hsp is not None and neg_mode != "fused":
            raise ValueError(f"neg_mode {neg_mode!r} reads every negative "
                             "row on the chip; one chip of an HSP mesh "
                             "holds its share of the rows (use 'fused')")
        if x_emb is not None:
            assert input_table is None, "x_emb replaces the input lookup"
            x = x_emb                                        # (G, cap, d)
        else:
            in_table = table if input_table is None else input_table
            x = lookup(in_table, batch["ids"])               # (G, cap, d)
        h = GR.gr_hidden_sharded(dense_params, cfg, x, batch["offsets"],
                                 batch["timestamps"], attn_fn=attn_fn,
                                 remat=remat)
        # the loss layer: positive logits and the negative path
        with jax.named_scope("loss"):
            pos_emb = lookup(table, batch["labels"])             # (G, cap, d)

            G, cap = batch["ids"].shape
            valid = (jnp.arange(cap, dtype=I32)[None, :]
                     < batch["offsets"][:, -1][:, None])         # (G, cap)

            tau = 1.0
            if neg_mode == "fused":
                # tokens are independent in the negative path: flatten
                # the shard axis so one kernel launch covers the global
                # batch (and §4.3.3 sharing mixes tokens across shards —
                # intra-*batch*).
                R = batch["neg_ids"].shape[-1]
                return NS.fused_sampled_softmax_loss(
                    h.reshape(G * cap, -1), pos_emb.reshape(G * cap, -1),
                    table, batch["neg_ids"].reshape(G * cap, R),
                    key=jax.random.PRNGKey(batch["rng"][0]), tau=tau,
                    valid=valid.reshape(-1), segment=neg_segment,
                    expansion=expansion, fetch_dtype=fetch_dtype,
                    shadow=shadow, impl=neg_impl,
                    scatter_impl=neg_scatter_impl, hsp=hsp)
            if neg_mode == "baseline":
                # (G, cap, R, d)
                neg_emb = jnp.take(table, batch["neg_ids"], axis=0)
                logits = jax.vmap(partial(NS.neg_logits_baseline, tau=tau))(
                    h, neg_emb.astype(h.dtype))
            else:
                logits = jax.vmap(
                    lambda hh, nn: NS.neg_logits_segmented(
                        hh, table, nn, segment=neg_segment, tau=tau,
                        fetch_dtype=fetch_dtype))(h, batch["neg_ids"])
            if expansion > 1:
                key = jax.random.PRNGKey(batch["rng"][0])
                keys = jax.random.split(key, G)
                logits = jax.vmap(
                    lambda k, lg, vv: NS.share_logits(k, lg, expansion, vv)
                )(keys, logits, valid)

            pos = jnp.sum(h.astype(jnp.float32)
                          * pos_emb.astype(jnp.float32), axis=-1) / tau
            return NS.sampled_softmax_loss(
                pos.reshape(-1), logits.reshape(G * cap, -1),
                valid.reshape(-1))

    # ---- dry-run specs ----------------------------------------------------
    def input_specs(self, shape: ShapeConfig,
                    num_shards: int = 256) -> Dict[str, Any]:
        cfg = self.cfg
        cap, max_samples = gr_capacity(shape, num_shards)
        G = num_shards
        batch = {
            "ids": sds((G, cap), I32),
            "labels": sds((G, cap), I32),
            "timestamps": sds((G, cap), I32),
            "offsets": sds((G, max_samples + 1), I32),
            "neg_ids": sds((G, cap, cfg.num_negatives), I32),
            "rng": sds((2,), jnp.uint32),
        }
        return {"batch": batch}


def get_bundle(cfg: ArchConfig):
    return GRBundle(cfg) if cfg.gr else LMBundle(cfg)
