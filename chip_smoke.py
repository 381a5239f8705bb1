#!/usr/bin/env python3
"""Bring-up check: HSTU-large GR training on a TPU, through the normal path.

    python3 chip_smoke.py             # one chip: kernel parity, then training
    python3 chip_smoke.py --chips 4   # four chips: the HSP step vs one chip

One chip. A kernel-parity phase first runs each Pallas kernel family of the
training step (jagged attention, fused negatives, weighted scatter and
run-sum, row gather) compiled for the chip at HSTU-large width and compares
it with its reference on the same inputs, computed at
``default_matmul_precision("highest")``. Then the trainer starts as
``launch/train.py`` wires it — synthetic KuaiRand surrogate from ``--seed``
→ 5-core filter → ``GRLoader`` → ``GREngine(schedule="algorithm1")`` —
for HSTU-large (d_model 1024, 16 layers, 8 heads × 128, RAB) at
``max_seq_len`` 2048, R = 128 negatives and 4 users per step, takes a few
steps, checks the losses, and checks that the compiled step runs the
kernels (``tpu_custom_call``).

Four chips (``--chips 4``). Only the hierarchical-sparse-parallel step runs:
the item table sharded over ``model``, replicas over ``data`` on a
(data=2, model=2) mesh, built as ``launch/dryrun.py`` builds it. Its losses
and updated table are compared with the same steps on one chip using the
plain ``take`` lookup, and per-device bytes in use show the table spread.

It exits non-zero, printing no result, when JAX finds no TPU or any check
fails. The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
JAX's compilation cache lives in ``$JAX_COMPILATION_CACHE_DIR`` or else
``<repo>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.launch.compile_cache import use_repo_compile_cache  # noqa: E402


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(count: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{devs[0].platform!r})")
    if len(devs) < count:
        sys.exit(f"chip_smoke: needs {count} chips, JAX sees {len(devs)}")
    log(f"[device] {devs[0].device_kind} x{len(devs)} "
        f"(platform {devs[0].platform})")
    return devs


def rel_err(got, want) -> float:
    """‖got − want‖ / ‖want‖ in fp32 (Frobenius); a zero reference is a
    broken check, not a pass."""
    import numpy as np
    g = np.asarray(got, np.float32)
    w = np.asarray(want, np.float32)
    ref = float(np.linalg.norm(w))
    if not ref > 0.0:
        sys.exit("chip_smoke: a reference is all zeros")
    return float(np.linalg.norm(g - w) / ref)


def check(name: str, err: float, tol: float, why: str) -> None:
    log(f"[parity] {name}: rel err {err:.3e} (tol {tol:g}: {why})")
    if not err <= tol:
        sys.exit(f"chip_smoke: {name} misses its tolerance "
                 f"({err:.3e} > {tol:g})")


# --------------------------------------------------------------------------
# one chip: kernel parity at full width
# --------------------------------------------------------------------------

BF16_TOL = 1e-2
BF16_WHY = "bf16 operands/outputs round at 2^-9"
F32_TOL = 1e-4
F32_WHY = "fp32 accumulation order"


def attention_parity(seed: int, cap: int = 2048, H: int = 8,
                     D: int = 128) -> None:
    import jax
    import jax.numpy as jnp
    from repro.configs.base import RABConfig
    from repro.kernels.jagged_attention.ops import jagged_attention
    from repro.kernels.jagged_attention.ref import jagged_attention_ref

    rab = RABConfig(num_pos_buckets=256, num_time_buckets=32)
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    q, k, v = (jax.random.normal(ks[i], (cap, H, D), jnp.bfloat16)
               for i in range(3))
    rp = {"pos_table": 0.1 * jax.random.normal(ks[3], (256, H)),
          "time_table": 0.1 * jax.random.normal(ks[4], (32, H))}
    offsets = jnp.asarray([0, cap // 3, cap * 15 // 16, cap - 48], jnp.int32)
    ts = jnp.cumsum(jax.random.randint(ks[5], (cap,), 0, 4000)).astype(
        jnp.int32)
    # bf16-exact cotangent, so both sides see the same dy
    w = jax.random.normal(ks[6], (cap, H, D)).astype(jnp.bfloat16).astype(
        jnp.float32)

    def kern(q, k, v, rp):
        out = jagged_attention(q, k, v, offsets, ts, rp, rab,
                               max_row_len=cap, interpret=False)
        return jnp.sum(out.astype(jnp.float32) * w), out

    def ref(q, k, v, rp):
        with jax.default_matmul_precision("highest"):
            out = jagged_attention_ref(q, k, v, offsets, ts, rp, rab)
        return jnp.sum(out * w), out

    up = lambda x: x.astype(jnp.float32)
    gk = jax.jit(jax.grad(kern, argnums=(0, 1, 2, 3), has_aux=True))
    gr = jax.jit(jax.grad(ref, argnums=(0, 1, 2, 3), has_aux=True))
    (dk, ok) = gk(q, k, v, rp)
    (dr, orf) = gr(up(q), up(k), up(v), rp)
    check("attention fwd", rel_err(ok, orf), BF16_TOL, BF16_WHY)
    for name, a, b in zip(("dq", "dk", "dv"), dk[:3], dr[:3]):
        check(f"attention {name}", rel_err(a, b), BF16_TOL, BF16_WHY)
    for t in ("pos_table", "time_table"):
        check(f"attention d{t}", rel_err(dk[3][t], dr[3][t]), BF16_TOL,
              "sums of bf16-input score gradients")


def negative_parity(seed: int, vocab: int, T: int = 1024, R: int = 128,
                    D: int = 1024) -> None:
    import jax
    import jax.numpy as jnp
    from repro.embedding.tables import shadow_of, shadow_values
    from repro.kernels.neg_logits import fused_recall_lse, fused_recall_lse_ref

    ks = jax.random.split(jax.random.PRNGKey(seed + 1), 4)
    out = jax.random.normal(ks[0], (T, D), jnp.bfloat16)
    pos = jax.random.normal(ks[1], (T,))
    master = 0.02 * jax.random.normal(ks[2], (vocab, D))
    shadow = shadow_of(master, jnp.bfloat16)      # stored as training does
    ids = jax.random.randint(ks[3], (T, R), 0, vocab)
    valid = jnp.arange(T) < T * 9 // 10

    # tables and ids are arguments: closed over, they would be baked into
    # the executable as constants
    def kern(o, p, t, sh, ids):
        lse = fused_recall_lse(o, p, t, ids, valid=valid, gather_table=sh,
                               interpret=False)
        return jnp.sum(jnp.where(valid, lse - p, 0.0)), lse

    # the reference reads the shadow's values as an fp32 table, so its
    # autodiff does not round row cotangents to bf16 at a cast
    def ref(o, p, t, ids):
        with jax.default_matmul_precision("highest"):
            lse = fused_recall_lse_ref(o, p, t, ids, valid=valid)
        return jnp.sum(jnp.where(valid, lse - p, 0.0)), lse

    gk, lk = jax.jit(jax.grad(kern, argnums=(0, 1, 2), has_aux=True))(
        out, pos, master, shadow, ids)
    gr, lr = jax.jit(jax.grad(ref, argnums=(0, 1, 2), has_aux=True))(
        out.astype(jnp.float32), pos,
        shadow_values(shadow).astype(jnp.float32), ids)
    check("negatives lse", rel_err(lk, lr), F32_TOL, F32_WHY)
    check("negatives d_out", rel_err(gk[0], gr[0]), BF16_TOL,
          "d_out comes back in out_emb's bf16")
    for name, a, b in zip(("d_pos", "d_table"), gk[1:], gr[1:]):
        check(f"negatives {name}", rel_err(a, b), F32_TOL, F32_WHY)


def lookup_parity(seed: int, vocab: int, T: int = 1024, R: int = 128,
                  D: int = 1024) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.jagged_lookup import kernel as K
    from repro.kernels.jagged_lookup.ops import (_segment_totals, dedup_rows,
                                                 scatter_add_weighted_rows)

    ks = jax.random.split(jax.random.PRNGKey(seed + 2), 5)
    table = (0.02 * jax.random.normal(ks[0], (vocab, D))).astype(jnp.bfloat16)
    ids = jax.random.randint(ks[1], (T * R,), 0, vocab)
    got = jax.jit(lambda t, i: K.gather_pallas(t, i, interpret=False))(
        table, ids)
    exact = bool(np.array_equal(np.asarray(got, np.float32),
                                np.asarray(jnp.take(table, ids, axis=0),
                                           np.float32)))
    log(f"[parity] gather: bitwise equal to take: {exact} (pure data "
        f"movement)")
    if not exact:
        sys.exit("chip_smoke: gather differs from take")

    w = jax.random.normal(ks[2], (T, R))
    o = jax.random.normal(ks[3], (T, D))
    sc = jax.jit(lambda w, o, ids: scatter_add_weighted_rows(
        w, o, ids, vocab, scale=0.5, impl="fused", interpret=False))(w, o, ids)
    with jax.default_matmul_precision("highest"):
        want = jnp.zeros((vocab, D)).at[ids].add(
            (w[:, :, None] * (o * 0.5)[:, None, :]).reshape(T * R, D))
    check("weighted scatter", rel_err(sc, want), F32_TOL,
          "fp32 summation order of duplicate ids")

    rows = jax.random.normal(ks[4], (T * R // 4, D))
    rid = ids[:T * R // 4]
    uids, sums = jax.jit(lambda r, i: dedup_rows(r, i, interpret=False))(
        rows, rid)
    order = jnp.argsort(rid)
    want = _segment_totals(rows[order], rid[order])
    end = np.asarray(uids) >= 0
    check("run-sum", rel_err(np.asarray(sums)[end], np.asarray(want)[end]),
          F32_TOL, "fp32 summation order within a run")


# --------------------------------------------------------------------------
# one chip: training through the normal path
# --------------------------------------------------------------------------

KERNELS_IN_STEP = {
    "attention": ("attn_fwd", "attn_bwd_kv", "attn_bwd_q"),
    "negatives": ("neg_fused_fwd", "neg_fused_bwd"),
    "scatter": ("lookup_wscatter",),
}


def train_one_chip(args, arch: str = "hstu-large", L: int = 2048,
                   R: int = 128, upd: int = 4) -> None:
    import re

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_arch
    from repro.data.kuairand import preprocess_log
    from repro.data.loader import GRLoader
    from repro.data.synthetic import SyntheticKuaiRand
    from repro.models.model_zoo import GRBundle
    from repro.training.engine import GREngine

    t0 = time.perf_counter()
    gen = SyntheticKuaiRand(num_users=args.users, num_items=200_000,
                            max_len=L + 1, seed=args.seed)
    train_seqs, _, remap = preprocess_log(gen.log(args.users))
    n_items = len(remap)
    log(f"[data] {len(train_seqs)} users, {n_items} table rows survive the "
        f"5-core filter (of 200000 item ids), "
        f"{time.perf_counter() - t0:.1f}s on the host")

    cfg = get_arch(arch).replace(max_seq_len=L, num_negatives=R,
                                 vocab_size=n_items)
    loader = GRLoader(train_seqs, num_devices=1, users_per_device=upd,
                      max_seq_len=L, num_negatives=R, num_items=n_items,
                      strategy="token_realloc", seed=args.seed)
    bundle = GRBundle(cfg)
    dense_sds = jax.eval_shape(bundle.init_dense, jax.random.PRNGKey(0))
    n_dense = sum(math.prod(x.shape) for x in jax.tree.leaves(dense_sds))
    log(f"[model] {cfg.name}: d_model {cfg.d_model}, {cfg.num_layers} "
        f"layers, {cfg.num_heads} heads x {cfg.qkv_dim}, RAB "
        f"{cfg.rab.num_pos_buckets} pos / {cfg.rab.num_time_buckets} time "
        f"buckets, {n_dense / 1e6:.2f}M dense params, R={R}, "
        f"capacity {upd * L} tokens/step")

    marks = []

    def on_step(i, rec, state):
        marks.append(time.perf_counter())
        log(f"[train] step {i}: loss {rec['loss']:.6f}, "
            f"{rec['tokens']} tokens")

    engine = GREngine(bundle, loader,
                      loss_kwargs=dict(neg_mode="fused", expansion=1),
                      semi_async=True, schedule="algorithm1",
                      seed=args.seed, step_callback=on_step)
    t_run = time.perf_counter()
    recs = engine.run(args.steps)
    losses = [r["loss"] for r in recs]
    first = marks[0] - t_run
    # the last step compiles the end-of-run emb_bwd variant (pairs left
    # pending), so the steady window is step 1 .. the second-to-last step
    steady = (marks[-2] - marks[1]) / max(len(marks) - 3, 1)
    log(f"[train] compile + first step {first:.1f}s, steady step "
        f"{steady:.2f}s (set-up information, not a metric)")
    if not all(np.isfinite(losses)):
        sys.exit(f"chip_smoke: non-finite loss {losses}")
    want = math.log(R + 1)
    log(f"[train] first loss {losses[0]:.4f} vs log(R+1) = {want:.4f}")
    # random init: logits ~ N(0, (0.02·√d)²) ≈ N(0, 0.64²), so the first
    # loss sits near log(R+1) + σ²/2
    if abs(losses[0] - want) > 1.0:
        sys.exit("chip_smoke: first loss is not near log(R+1)")

    # the compiled step runs the kernels, not an XLA twin
    st = engine.state
    nb = next(iter(loader.batches(1)))
    dev = {k: jnp.asarray(v) for k, v in nb.items() if k != "weights"}
    x = engine._j_emb_fwd(st.table.master, dev)
    lowered = engine._j_dense.lower(st.dense, st.table, dev, x, None)
    names = set(re.findall(r'kernel_name = "([^"]+)"', lowered.as_text()))
    compiled = lowered.compile().as_text()
    n_calls = compiled.count("tpu_custom_call")
    for family, kernels in KERNELS_IN_STEP.items():
        found = [k for k in kernels if k in names]
        log(f"[hlo] {family}: {found}")
        if len(found) != len(kernels) or not n_calls:
            sys.exit(f"chip_smoke: the train step lacks the {family} "
                     f"kernels")
    log(f"[hlo] dense stage: {n_calls} tpu_custom_call sites")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"[memory] peak_bytes_in_use {stats.get('peak_bytes_in_use')}")


# --------------------------------------------------------------------------
# four chips: the HSP step against one chip
# --------------------------------------------------------------------------

def hsp_four_chips(args, arch: str = "hstu-large", V: int = 200_000,
                   R: int = 128, L: int = 2048, layers: int = 4,
                   steps: int = 2) -> None:
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from repro.configs import get_arch
    from repro.configs.shapes import ShapeConfig
    from repro.core.hsp import make_hsp_lookup
    from repro.core.sharding import shard_ctx
    from repro.data.synthetic import synth_jagged_batch
    from repro.launch import partition as PT
    from repro.models.hstu import jagged_pointwise_attention_blocked
    from repro.models.model_zoo import GRBundle
    from repro.training.engine import make_gr_step_fn
    from repro.training.trainer import gr_pending_slots, gr_train_state

    devs = jax.devices()[:4]
    mesh = Mesh(np.array(devs).reshape(2, 2), ("data", "model"))
    cfg = get_arch(arch).replace(max_seq_len=L, num_negatives=R,
                                 vocab_size=V, num_layers=layers)
    log(f"[hsp] {cfg.name} d_model {cfg.d_model}, {layers} layers, "
        f"table {V}x{cfg.d_model}, mesh data=2 x model=2")
    bundle = GRBundle(cfg)
    plan = PT.make_plan(cfg, ShapeConfig("hsp", L, 4, "train"), mesh)
    batches = [synth_jagged_batch(jax.random.PRNGKey(args.seed + i), 4, L, V,
                                  R, offsets=[[0, L // 2 - 100, L - 48]] * 4)
               for i in range(steps)]
    key = jax.random.PRNGKey(args.seed)
    n_pend = gr_pending_slots(batches[0], V)
    attn_fn = partial(jagged_pointwise_attention_blocked, block=plan.q_block,
                      score_dtype=jnp.dtype(plan.gr_score_dtype))
    common = dict(neg_mode="segmented", neg_segment=plan.neg_segment,
                  expansion=1, attn_fn=attn_fn, remat=plan.remat)

    def fresh():
        return gr_train_state(bundle.init_dense(key), bundle.init_table(key),
                              pending_slots=n_pend)

    # one chip, plain take lookup
    step1 = make_gr_step_fn(bundle, loss_kwargs=common, semi_async=True)
    st, ref_losses = fresh(), []
    for b in batches:
        st, m = step1(st, b)
        ref_losses.append(float(m["loss"]))
    init = np.asarray(bundle.init_table(key))
    ref_delta = np.asarray(st.table.master) - init
    ref_accum = np.asarray(st.table.accum)
    del st

    # four chips, HSP lookup, shardings from launch/partition
    lookup = make_hsp_lookup(mesh, group_axes=("model",), dp_axes=("data",),
                             compute_dtype=jnp.dtype(cfg.dtype))
    step4 = make_gr_step_fn(bundle, loss_kwargs=dict(common,
                                                     lookup_fn=lookup),
                            semi_async=True, jit=False)
    st_sds = jax.eval_shape(fresh)
    dspecs = PT.gr_param_specs(st_sds.dense, mesh, plan)
    sspecs = PT.gr_state_specs(dspecs, PT.gr_table_spec(mesh, plan),
                               pend_spec=PT.gr_pend_spec(mesh, n_pend))
    inputs = {"batch": {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                        for k, v in batches[0].items()}}
    bspecs = PT.batch_specs(cfg, ShapeConfig("hsp", L, 4, "train"), mesh,
                            plan, inputs)["batch"]
    s_sh, b_sh = PT.to_named(mesh, sspecs), PT.to_named(mesh, bspecs)
    jitted = jax.jit(step4, in_shardings=(s_sh, b_sh),
                     out_shardings=(s_sh, None))
    st = jax.device_put(fresh(), s_sh)
    losses = []
    with shard_ctx(mesh, plan.rules):
        for b in batches:
            st, m = jitted(st, jax.device_put(b, b_sh))
            losses.append(float(m["loss"]))
    log(f"[hsp] losses 4 chips {losses}")
    log(f"[hsp] losses 1 chip  {ref_losses}")
    for d in devs:
        log(f"[hsp] {d}: bytes_in_use "
            f"{(d.memory_stats() or {}).get('bytes_in_use')}")
    log(f"[hsp] master sharding {st.table.master.sharding.spec}")
    rel = [abs(a - b) / abs(b) for a, b in zip(losses, ref_losses)]
    check("hsp step-0 loss vs one chip", rel[0], 1e-5,
          "the same math, fp32 reduction order")
    # later steps follow AdamW steps, whose first update is sign-like
    # (m/√v): gradients that are zero up to rounding flip sign with the
    # reduction order and move their parameter by ±lr
    check("hsp later losses vs one chip", max(rel[1:]), 1e-3,
          "sign-like first AdamW update of rounding-level gradients")
    # After two τ=1 steps the table holds step 0's landed sparse update.
    # The forward is the same on both sides (step-0 loss), but the
    # model-sharded backward sums partial bf16 products across chips: one
    # more rounding (2^-9) per sharded contraction, k ≈ 16 of them over 4
    # layers, so the step-0 table gradient g differs by ~√k·2^-9 ≈ 8e-3.
    # A dropped or doubled replica's share differs by O(1).
    # The AdaGrad accumulator is Σ g², sign-free: the exchange itself.
    check("hsp table accum (g^2) vs one chip",
          rel_err(st.table.accum, ref_accum), 5e-2,
          "bf16 partial sums of the sharded backward, squared")
    # the master's first AdaGrad step is sign-like (eps 1e-10: ±lr wherever
    # |g| >> 1e-5), so its error is 2·√(share of flipped signs)
    check("hsp table update vs one chip",
          rel_err(np.asarray(st.table.master) - init, ref_delta), 5e-2,
          "sign flips of near-zero gradients in the first AdaGrad step")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--users", type=int, default=8000,
                    help="synthetic KuaiRand users to generate")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args.steps < 4:
        ap.error("--steps must be at least 4 (first, steady, last)")
    use_repo_compile_cache()
    devs = require_tpu(args.chips)
    if args.chips == 4:
        hsp_four_chips(args)
    else:
        attention_parity(args.seed)
        negative_parity(args.seed, vocab=200_000)
        lookup_parity(args.seed, vocab=200_000)
        train_one_chip(args)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
