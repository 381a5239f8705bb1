"""Kernel autotune harness gates: tuned fast paths vs safe defaults.

Four sections, each a hard gate (raises on regression) plus measured
rows recorded into ``BENCH_kernels.json``:

  attn      work-list jagged attention on a long-tail regime: the tuned
            ``pairs_per_step`` plan must take STRICTLY FEWER grid steps
            than the default (pps=1) plan while producing bit-identical
            forward output and q/k/v grads; also records the
            consecutive-duplicate block-index fractions (the DMA-skip
            opportunity the multi-operand gather exploits).
  neg       fused negative-sampling megakernel: the token block sized from
            the shapes must cut grid steps vs one token a step at a
            bit-identical lse (and match the materialized oracle).
  scatter   backward embedding grad: the fused sorted-runsum path must
            lower WITHOUT the (T·R, D) row buffer the two-pass oracle
            materializes — checked against compiled memory_analysis()
            and the lowered HLO text (``no_TRD_grad_buffer`` gate, same
            PASS/FAIL/HLO_ONLY_ idiom as bench_table7).
  autotune  end-to-end sweep round trip through a temp tuned.json:
            cost-ranked candidates, obs-layer timing, persisted winner
            read back by ``resolve``.

Everything runs in interpret mode on CPU — shapes are deliberately tiny
where the interpreter pays O(grid) dispatch.
"""
from __future__ import annotations

import functools
import json
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import (emit, jagged_inputs, longtail_lengths,
                               time_fn, write_bench_json)
from benchmarks.bench_table7_offload import compile_once, no_materialization
from repro.kernels import autotune
from repro.kernels.jagged_attention import ops as attn_ops
from repro.kernels.jagged_lookup.kernel import gather_pallas
from repro.kernels.jagged_lookup.ops import scatter_add_weighted_rows
from repro.kernels.neg_logits.fused import fwd_pallas, gather_source
from repro.kernels.neg_logits.ops import (fused_recall_lse,
                                         prepare_fused_inputs)
from repro.kernels.neg_logits.ref import fused_recall_lse_ref
from repro.obs import MetricsRegistry, Tracer


def _gate(name: str, ok: bool, detail: str = "") -> str:
    status = "PASS" if ok else "FAIL"
    emit(f"kernels/gate/{name}", 0.0, f"{status} {detail}".strip())
    if not ok:
        raise RuntimeError(f"bench_kernels gate failed: {name} {detail}")
    return status


def _bitwise(a, b) -> bool:
    return bool(jnp.array_equal(jnp.asarray(a), jnp.asarray(b),
                                equal_nan=True))


def _reuse_frac(idx: np.ndarray) -> float:
    """Fraction of consecutive grid steps whose block index repeats —
    each repeat is a DMA the pipeline can elide for that operand slot."""
    if idx.size <= 1:
        return 0.0
    return float(np.mean(idx[1:] == idx[:-1]))


# ---------------------------------------------------------------------------
# section 1: work-list attention, tuned pairs_per_step
# ---------------------------------------------------------------------------

def bench_attn():
    block, H, D = 8, 2, 16
    lens = longtail_lengths(10, mean=12.0, sigma=1.1, max_len=32, seed=3)
    cap = int(np.sum(lens)) + 6
    q, k, v, offsets, ts = jagged_inputs(jax.random.PRNGKey(0), lens, H, D,
                                         cap)
    nb = -(-cap // block)
    dims = {"block": block, "nb": nb, "causal": True}
    tuned = rank0 = autotune.rank_candidates("attn_worklist", dims)[0]
    pps_t = int(rank0["pairs_per_step"])
    if pps_t == 1:  # model must prefer a grouped schedule on a long tail
        pps_t = 4

    def plan_for(pps):
        return attn_ops.build_attn_plan(offsets, ts, cap, block=block,
                                        max_row_len=int(lens.max()),
                                        pairs_per_step=pps)

    plan_d, plan_t = plan_for(1), plan_for(pps_t)

    def loss(q, k, v, plan):
        out = attn_ops.jagged_attention(
            q, k, v, offsets, ts, {}, None, block=block, plan=plan,
            max_row_len=int(lens.max()), interpret=True)
        return jnp.sum(out * out), out

    run = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True),
                  static_argnums=())
    (l_d, out_d), g_d = run(q, k, v, plan_d)
    (l_t, out_t), g_t = run(q, k, v, plan_t)

    bit_ok = (_bitwise(out_d, out_t) and _bitwise(l_d, l_t)
              and all(_bitwise(a, b) for a, b in zip(g_d, g_t)))
    steps_d, steps_t = int(plan_d.num_steps), int(plan_t.num_steps)
    _gate("attn_bitwise_pps", bit_ok, f"pps={pps_t} vs 1")
    _gate("attn_fewer_grid_steps", steps_t < steps_d,
          f"{steps_t} < {steps_d} (pps={pps_t})")

    us_d = time_fn(run, q, k, v, plan_d)
    us_t = time_fn(run, q, k, v, plan_t)
    q_idx = np.asarray(plan_t.q_wl[::pps_t, 0])
    kv_reuse = [
        _reuse_frac(np.asarray(plan_t.q_wl[u::pps_t, 1]))
        for u in range(pps_t)
    ]
    emit("kernels/attn/longtail", us_t,
         f"default={us_d:.1f}us steps {steps_d}->{steps_t}")
    return {
        "regime": "longtail", "block": block, "nb": nb,
        "rows": int(lens.size), "capacity": cap,
        "config_default": {"pairs_per_step": 1},
        "config_tuned": {"pairs_per_step": pps_t},
        "model_ranked_best": dict(tuned),
        "grid_steps_default": steps_d, "grid_steps_tuned": steps_t,
        "latency_us_default": us_d, "latency_us_tuned": us_t,
        "bitwise_identical": bit_ok,
        "q_block_dma_reuse_frac": _reuse_frac(q_idx),
        "kv_slot_dma_reuse_frac": kv_reuse,
        "n_live_pairs": int(plan_t.n_live[0]),
    }


# ---------------------------------------------------------------------------
# section 2: fused negative sampling, the shape-sized token block
# ---------------------------------------------------------------------------

def bench_neg():
    T, R, D, V, seg, exp = 60, 8, 16, 512, 16, 2
    key = jax.random.PRNGKey(7)
    ks = jax.random.split(key, 5)
    out = jax.random.normal(ks[0], (T, D), jnp.float32)
    pos = jax.random.normal(ks[1], (T,), jnp.float32)
    table = jax.random.normal(ks[2], (V, D), jnp.float32)
    ids = jax.random.randint(ks[3], (T, R), 0, V)
    valid = jnp.arange(T) < T - 5
    dims = {"segment": seg, "R": R, "D": D, "T": T, "expansion": exp}
    tb = autotune.neg_tokens_per_step(dims)
    kw = dict(segment=seg, tau=0.9, expansion=exp, key=ks[4], valid=valid)
    o_p, pos_p, ids_p, valid_p, perms, n_seg = prepare_fused_inputs(
        out, pos, table, ids, segment=seg, expansion=exp, key=ks[4],
        valid=valid)
    words, _ = gather_source(table, None, None)

    def lse(tps):
        return fwd_pallas(o_p, pos_p.reshape(n_seg, seg), words,
                          ids_p.reshape(-1), valid_p.reshape(n_seg, seg),
                          perms, segment=seg, R=R, expansion=exp, tau=0.9,
                          tokens_per_step=tps,
                          interpret=True).reshape(-1)[:T]

    lse_1 = lse(1)
    lse_b = fused_recall_lse(out, pos, table, ids, interpret=True, **kw)
    ref = fused_recall_lse_ref(out, pos, table, ids, **kw)
    bit_ok = _bitwise(lse_1, lse_b)
    _gate("neg_bitwise_tps", bit_ok, f"tokens_per_step={tb} vs 1")
    oracle_ok = bool(np.allclose(np.asarray(lse_b), np.asarray(ref),
                                 rtol=2e-5, atol=2e-5))
    _gate("neg_matches_oracle", oracle_ok, "vs fused_recall_lse_ref")
    steps_1 = int(autotune.estimate_cost(
        "neg_fused", dims, {"tokens_per_step": 1})["grid_steps"])
    steps_b = int(autotune.estimate_cost("neg_fused", dims)["grid_steps"])
    _gate("neg_fewer_grid_steps", steps_b < steps_1,
          f"{steps_b} < {steps_1} (tokens_per_step={tb})")
    us_1 = time_fn(lambda: lse(1))
    us_b = time_fn(lambda: lse(tb))
    emit("kernels/neg/fused_lse", us_b,
         f"one_token={us_1:.1f}us steps {steps_1}->{steps_b}")
    return {
        "regime": "longtail", "T": T, "R": R, "D": D, "segment": seg,
        "expansion": exp,
        "config_one_token": {"tokens_per_step": 1},
        "config_default": {"tokens_per_step": tb},
        "grid_steps_one_token": steps_1, "grid_steps_default": steps_b,
        "latency_us_one_token": us_1, "latency_us_default": us_b,
        "bitwise_identical": bit_ok, "oracle_allclose": oracle_ok,
    }


# ---------------------------------------------------------------------------
# section 3: backward scatter — no (T·R, D) grad-row buffer
# ---------------------------------------------------------------------------

def bench_scatter():
    T, R, D, V = 2048, 32, 128, 5000
    key = jax.random.PRNGKey(11)
    ks = jax.random.split(key, 3)
    w = jax.random.normal(ks[0], (T, R), jnp.float32)
    o = jax.random.normal(ks[1], (T, D), jnp.float32)
    ids = jax.random.randint(ks[2], (T * R,), 0, V).astype(jnp.int32)
    forbidden = [f"{T * R}x{D}"]           # the (T·R, D) row buffer

    def fused(w, o, ids):
        return scatter_add_weighted_rows(w, o, ids, V, scale=0.5,
                                         impl="fused")

    def two_pass(w, o, ids):
        return scatter_add_weighted_rows(w, o, ids, V, scale=0.5,
                                         impl="two_pass")

    cf, temp_f, txt_f = compile_once(fused, w, o, ids)
    ct, temp_t, txt_t = compile_once(two_pass, w, o, ids)
    clean = no_materialization(txt_f, forbidden)
    oracle_dirty = not no_materialization(txt_t, forbidden)
    if temp_f >= 0 and temp_t >= 0:
        mem_ok = "PASS" if clean and temp_f < temp_t else "FAIL"
    else:
        mem_ok = f"HLO_ONLY_{'PASS' if clean else 'FAIL'}"
    _gate("no_TRD_grad_buffer", "FAIL" not in mem_ok,
          f"{mem_ok} forbidden={forbidden}")
    # identical reductions: fused vs the materializing oracle
    gf = cf(w, o, ids)[0] if isinstance(cf(w, o, ids), tuple) else cf(w, o, ids)
    gt = ct(w, o, ids)[0] if isinstance(ct(w, o, ids), tuple) else ct(w, o, ids)
    parity = bool(np.allclose(np.asarray(gf), np.asarray(gt),
                              rtol=1e-5, atol=1e-5))
    _gate("scatter_matches_two_pass", parity, f"T={T} R={R} D={D}")
    us_f = time_fn(cf, w, o, ids)
    us_t = time_fn(ct, w, o, ids)
    emit("kernels/scatter/fused", us_f,
         f"two_pass={us_t:.1f}us temp {temp_f} vs {temp_t}")
    return {
        "T": T, "R": R, "D": D, "vocab": V,
        "forbidden_shapes": forbidden,
        "no_TRD_grad_buffer": mem_ok,
        "oracle_materializes": oracle_dirty,
        "peak_temp_bytes_fused": temp_f,
        "peak_temp_bytes_two_pass": temp_t,
        "latency_us_fused": us_f, "latency_us_two_pass": us_t,
        "parity_vs_two_pass": parity,
    }


# ---------------------------------------------------------------------------
# section 4: sweep + tuned.json round trip
# ---------------------------------------------------------------------------

def bench_autotune_roundtrip():
    n, D = 48, 16
    table = jax.random.normal(jax.random.PRNGKey(1), (96, D), jnp.float32)
    ids = jax.random.randint(jax.random.PRNGKey(2), (n,), 0, 96)
    dims = {"n": n, "D": D, "itemsize": 4}

    def run_fn(cfg):
        fn = jax.jit(functools.partial(
            gather_pallas, rows_per_step=int(cfg["rows_per_step"]),
            interpret=True))
        return lambda: fn(table, ids)

    tmp = tempfile.mkdtemp(prefix="tuned_")
    path = os.path.join(tmp, "tuned.json")
    old = os.environ.get("REPRO_TUNED_JSON")
    os.environ["REPRO_TUNED_JSON"] = path
    try:
        tracer = Tracer(enabled=True)
        metrics = MetricsRegistry()
        result = autotune.sweep("lookup_gather", dims, run_fn,
                                top_k=3, iters=2, warmup=1, tracer=tracer,
                                metrics=metrics)
        best = result["best"]["config"]
        resolved = autotune.resolve("lookup_gather", dims, "rows_per_step")
        round_trip = (os.path.exists(path)
                      and resolved == best["rows_per_step"])
        _gate("autotune_roundtrip", round_trip,
              f"resolved={resolved} best={best}")
        spans = [s for s in tracer.spans() if s.track == "autotune"]
        _gate("autotune_obs_spans", len(spans) >= 2 * len(result["trials"]) - 2,
              f"{len(spans)} spans / {len(result['trials'])} trials")
        with open(path) as f:
            stored = json.load(f)
        return {
            "dims": dims, "key": result["key"],
            "best": result["best"],
            "trials": len(result["trials"]),
            "tracer_spans": len(spans),
            "stored_entries": len(stored.get("entries", {})),
        }
    finally:
        if old is None:
            os.environ.pop("REPRO_TUNED_JSON", None)
        else:
            os.environ["REPRO_TUNED_JSON"] = old


def main():
    payload = {
        "bench": "kernel_autotune_gates",
        "backend": jax.default_backend(),
        "attn": bench_attn(),
        "neg": bench_neg(),
        "scatter": bench_scatter(),
        "autotune": bench_autotune_roundtrip(),
    }
    write_bench_json("kernels", payload)


if __name__ == "__main__":
    main()
