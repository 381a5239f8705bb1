"""Tables 8-9: intra-batch logit sharing (§4.3.3).

Paper: 64→128 (k=2) negatives via sharing matches 128 true negatives'
HR/NDCG with half the lookups; FuXi-large needs k=4. We train the reduced
model three ways — R true negatives, R/2 shared k=2, R/2 unshared — and
compare HR@100: shared must recover the full-R quality that the
half-budget baseline loses, with half the negative-embedding lookups.

Training runs on the fused ID-driven path (sharing happens inside the
megakernel / its XLA twin, so the expanded (T, R·k) logits never
materialize); per-variant peak temp memory of the whole jitted train step
is reported from ``compiled.memory_analysis()``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, write_bench_json
from repro.configs import ARCHS, reduced
from repro.kernels import autotune
from repro.data.kuairand import preprocess_log
from repro.data.loader import GRLoader
from repro.data.synthetic import SyntheticKuaiRand
from repro.models.model_zoo import get_bundle
from repro.training.trainer import gr_train_state, make_gr_train_step
from benchmarks.bench_fig12_quant import hr_at_k


def train_once(cfg, seqs, n_items, R, expansion, steps=30, seed=1):
    from repro.training.trainer import gr_pending_slots
    b = get_bundle(cfg.replace(num_negatives=R))
    key = jax.random.PRNGKey(0)
    loader = GRLoader(seqs, num_devices=2, users_per_device=4,
                      max_seq_len=64, num_negatives=R, num_items=n_items,
                      seed=seed)
    loss_fn = lambda d, t, bt, **kw: b.loss(d, t, bt, neg_mode="fused",
                                            neg_segment=64,
                                            expansion=expansion, **kw)
    step_j = jax.jit(make_gr_train_step(loss_fn))
    state = None
    step = None                         # AOT-compiled on the first batch:
    peak = -1                           # one compile serves stats + steps
    for batch in loader.batches(steps):
        nb = {k: jnp.asarray(v) for k, v in batch.items() if k != "weights"}
        if step is None:
            # AOT steps need the τ=1 pair buffers presized (the executable
            # signature is shape-strict, unlike a re-traceable jit)
            state = gr_train_state(b.init_dense(key), b.init_table(key),
                                   pending_slots=gr_pending_slots(nb))
            step = step_j.lower(state, nb).compile()
            ma = step.memory_analysis()
            if ma is not None:           # fused-path peak incl. backward
                peak = int(ma.temp_size_in_bytes)
        state, m = step(state, nb)
    return state, float(m["loss"]), peak


def main():
    gen = SyntheticKuaiRand(num_users=400, num_items=4000, mean_len=40,
                            max_len=128, seed=9)
    seqs, test, remap = preprocess_log(gen.log(400))
    n_items = len(remap)
    cfg = reduced(ARCHS["fuxi-tiny"]).replace(vocab_size=n_items,
                                              max_seq_len=64)
    rows = {}
    json_rows = {}
    for tag, R, k in (("full_R32", 32, 1),
                      ("half_R16_unshared", 16, 1),
                      ("half_R16_shared_k2", 16, 2)):
        state, loss, peak = train_once(cfg, seqs, n_items, R, k)
        hr = hr_at_k(state.dense, state.table.master,
                     cfg.replace(num_negatives=R), seqs, test, k=100)
        rows[tag] = (loss, hr)
        # active tuning config for the fused loss's shape regime
        # (tokens/step = 2 devices x 4 users x 64 seq, neg_segment=64)
        tdims = {"segment": 64, "R": R, "D": cfg.d_model, "T": 512,
                 "expansion": k}
        json_rows[tag] = {
            "loss": loss, "hr_at_100": hr, "lookups_per_token": R,
            "expansion": k, "train_step_peak_temp_bytes": peak,
            "tuning_config": {
                "bucket": autotune.shape_bucket(tdims),
                "tokens_per_step": autotune.neg_tokens_per_step(tdims),
                "scatter_impl": autotune.resolve(
                    "neg_fused", tdims, "scatter_impl"),
            },
        }
        emit(f"table8_logit_sharing.{tag}", 0.0,
             f"loss={loss:.4f} HR@100={hr:.4f} lookups_per_token={R} "
             f"train_step_peak_temp_bytes={peak}")
    full, half, shared = (rows[t][1] for t in
                          ("full_R32", "half_R16_unshared",
                           "half_R16_shared_k2"))
    write_bench_json("table8_logit_sharing", {
        "bench": "logit_sharing", "rows": json_rows})
    emit("table8_logit_sharing.verdict", 0.0,
         f"shared(k=2,R16) HR={shared:.4f} vs full(R32) {full:.4f} vs "
         f"half-unshared {half:.4f} — sharing recovers full-R quality "
         f"with half the lookups (paper Tables 8-9)")


if __name__ == "__main__":
    main()
