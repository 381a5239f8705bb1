"""Table 7: negative-embedding offloading — HBM savings.

Paper (FuXi-large): HBM 22.2→17.4 GB @32 negs, 31.6→23.4 @64,
50.4→34.3 @128 (−24.59%). Three compiled recall-loss programs are
compared on *measured* peak temp memory (``compiled.memory_analysis()``):

  baseline   materializes the (T, R, D) negative tensor;
  segmented  §4.3.1 scan, logits still (T, R) with per-segment fetches;
  fused      the ID-driven megakernel path (XLA twin off-TPU): ids →
             Eq.-2 logsumexp, no (T, R, D) embeddings and no (T, R·k)
             logits anywhere — verified against the lowered HLO.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_fn, write_bench_json
from repro.core import negative_sampling as NS
from repro.kernels import autotune


def compile_once(fn, *args):
    """(compiled executable, peak temp bytes or -1, lowered HLO text) —
    one lower + one compile per variant (``lower().compile()`` does not
    seed the jit cache, so going through ``jax.jit`` again would pay a
    second compilation)."""
    lowered = jax.jit(fn).lower(*args)
    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    temp = -1 if ma is None else int(ma.temp_size_in_bytes)
    return compiled, temp, lowered.as_text()


def no_materialization(txt: str, shapes) -> bool:
    """True iff none of the forbidden `AxBxC` tensor shapes appear in the
    lowered program."""
    return not any(s in txt for s in shapes)


def main():
    T, D, V = 4096, 256, 100_000
    seg = 128
    key = jax.random.PRNGKey(0)
    out = jax.random.normal(key, (T, D), jnp.float32)
    table = jax.random.normal(jax.random.PRNGKey(1), (V, D), jnp.float32)
    pos_ids = jax.random.randint(jax.random.PRNGKey(2), (T,), 0, V)

    json_rows = {}
    for R in (32, 64, 128):
        ids = jax.random.randint(jax.random.PRNGKey(R), (T, R), 0, V)

        def base(tbl):
            neg = jnp.take(tbl, ids, axis=0)           # (T,R,D) lives
            lg = NS.neg_logits_baseline(out, neg)
            return NS.recall_loss(out, jnp.take(tbl, pos_ids, axis=0), lg)

        def segd(tbl):
            lg = NS.neg_logits_segmented(out, tbl, ids, segment=seg,
                                         fetch_dtype=jnp.bfloat16)
            return NS.recall_loss(out, jnp.take(tbl, pos_ids, axis=0), lg)

        def fused(tbl):
            return NS.fused_sampled_softmax_loss(
                out, jnp.take(tbl, pos_ids, axis=0), tbl, ids,
                segment=seg, fetch_dtype=jnp.bfloat16)

        (jb, m_b, _), (js, m_s, _), (jf, m_f, txt_f) = (
            compile_once(f, table) for f in (base, segd, fused))
        t_b, t_s, t_f = (time_fn(f, table) for f in (jb, js, jf))
        v_b, v_s, v_f = (float(f(table)) for f in (jb, js, jf))
        neg_bytes = T * R * D * 4
        forbidden = [f"{T}x{R}x{D}", f"{T * R}x{D}", f"{R}x{T}x{D}"]
        clean = no_materialization(txt_f, forbidden)
        mem_ok = "PASS" if clean and 0 <= m_f < neg_bytes else "FAIL"
        if m_f < 0:                     # backend without memory stats:
            mem_ok = f"HLO_ONLY_{'PASS' if clean else 'FAIL'}"
        saving = f"{1 - m_f / m_b:.1%}" if m_b > 0 and m_f >= 0 else "n/a"
        emit(f"table7_offload.R{R}.baseline", t_b,
             f"peak_temp_bytes={m_b} trd_bytes={neg_bytes}")
        emit(f"table7_offload.R{R}.segmented", t_s,
             f"peak_temp_bytes={m_s} "
             f"loss_drift={abs(v_s - v_b) / abs(v_b):.2e}")
        emit(f"table7_offload.R{R}.fused", t_f,
             f"peak_temp_bytes={m_f} "
             f"saving_vs_baseline={saving} "
             f"no_TRD_or_TRk_buffer={mem_ok} "
             f"loss_drift={abs(v_f - v_b) / abs(v_b):.2e}")
        # active kernel tuning config for the fused path's shape regime
        tdims = {"segment": seg, "R": R, "D": D, "T": T, "expansion": 1}
        json_rows[f"R{R}"] = {
            "latency_us": {"baseline": t_b, "segmented": t_s, "fused": t_f},
            "peak_temp_bytes": {"baseline": m_b, "segmented": m_s,
                                "fused": m_f},
            "no_TRD_or_TRk_buffer": mem_ok,
            "tuning_config": {
                "bucket": autotune.shape_bucket(tdims),
                "tokens_per_step": autotune.neg_tokens_per_step(tdims),
                "scatter_impl": autotune.resolve(
                    "neg_fused", tdims, "scatter_impl"),
            },
        }
    write_bench_json("table7_offload", {
        "bench": "neg_offload_hbm", "T": T, "D": D, "segment": seg,
        "rows": json_rows})
    emit("table7_offload.paper", 0.0,
         "paper: -7.3%@32 -12.5%@64 -24.6%@128 of TOTAL HBM "
         "(neg tensor eliminated ~100%, as here)")


if __name__ == "__main__":
    main()
