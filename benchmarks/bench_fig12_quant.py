"""Fig. 12: FP16 quantization of negative embeddings — accuracy + bytes.

Paper: HR@1000 delta 0.05%, HR@2000 delta 0.01%. We train the reduced GR
model to convergence twice — fp32 master gathers vs the persistent
§4.3.2 FP16 *shadow table* (half-width negative fetches kept consistent
by the sparse row-wise AdaGrad) — and compare final losses + HR@k on a
held-out synthetic slice, plus the *measured* train-step bytes from
``cost_analysis`` and the analytic negative-fetch bytes (the quantity
Fig. 12's bandwidth claim is about: T·R·D·4 → T·R·D·2 per step).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, write_bench_json
from repro.configs import ARCHS, reduced
from repro.data.kuairand import preprocess_log
from repro.data.loader import GRLoader
from repro.data.synthetic import SyntheticKuaiRand
from repro.launch.roofline import cost_dict
from repro.models.gr import gr_hidden
from repro.models.model_zoo import get_bundle
from repro.training.trainer import (gr_pending_slots, gr_train_state,
                                    make_gr_train_step)


def hr_at_k(dense, table, cfg, seqs, test, k=100, users=64):
    hits = 0
    us = list(test)[:users]
    for u in us:
        it, ts = seqs[u]
        it = it[-64:]
        ts = ts[-64:]
        cap = 64
        x = jnp.take(table, jnp.asarray(it, jnp.int32),
                     axis=0).astype(jnp.dtype(cfg.dtype))
        x = jnp.pad(x, ((0, cap - len(it)), (0, 0)))
        off = jnp.asarray([0, len(it)], jnp.int32)
        tss = jnp.pad(jnp.asarray(ts - ts[0], jnp.int32),
                      (0, cap - len(it)))
        h = gr_hidden(dense, cfg, x, off, tss, remat=False)
        scores = table.astype(jnp.float32) @ h[len(it) - 1].astype(jnp.float32)
        top = jnp.argsort(-scores)[:k]
        hits += int(test[u] in np.asarray(top))
    return hits / len(us)


def main():
    gen = SyntheticKuaiRand(num_users=400, num_items=4000, mean_len=40,
                            max_len=128, seed=7)
    seqs, test, remap = preprocess_log(gen.log(400))
    n_items = len(remap)
    cfg = reduced(ARCHS["fuxi-tiny"]).replace(vocab_size=n_items,
                                              num_negatives=16,
                                              max_seq_len=64)
    b = get_bundle(cfg)
    key = jax.random.PRNGKey(0)
    results = {}
    bytes_step = {}
    neg_fetch_bytes = {}
    for name, qdt in (("fp32", None), ("bf16", jnp.bfloat16)):
        loader = GRLoader(seqs, num_devices=2, users_per_device=4,
                          max_seq_len=64, num_negatives=16,
                          num_items=n_items, seed=1)
        step = jax.jit(make_gr_train_step(
            lambda d, t, bt, **kw: b.loss(d, t, bt, neg_mode="fused",
                                          neg_segment=64,
                                          fetch_dtype=jnp.float32,
                                          **kw)))
        state = compiled = None
        for batch in loader.batches(30):
            nb = {k2: jnp.asarray(v) for k2, v in batch.items()
                  if k2 != "weights"}
            if compiled is None:
                # qdt=None → fp32 master gathers; bf16 → persistent shadow
                state = gr_train_state(b.init_dense(key), b.init_table(key),
                                       qdtype=qdt,
                                       pending_slots=gr_pending_slots(nb))
                # one AOT compile serves both the cost stats and the loop
                compiled = step.lower(state, nb).compile()
                bytes_step[name] = float(
                    cost_dict(compiled).get("bytes accessed", -1.0))
                # measured fetch traffic of this step's negative gather —
                # the §4.3.2 quantity — compiled in isolation against the
                # table the fused path actually reads (fp32 master vs bf16
                # shadow). The *output*-side bytes of the gather are the
                # row payload DMA'd per step (T·R·D·esize); the aggregate
                # 'bytes accessed' would also count the whole resident
                # table operand, and the full-step number above moves
                # activations/optimizer state too, burying the delta.
                src = (state.table.master if qdt is None
                       else state.table.shadow)
                flat = nb["neg_ids"].reshape(-1)
                g = jax.jit(lambda s, i: jnp.take(s, i, axis=0))
                gc = cost_dict(g.lower(src, flat).compile())
                neg_fetch_bytes[name] = float(
                    gc.get("bytes accessedout{}",
                           gc.get("bytes accessed", -1.0)))
            state, m = compiled(state, nb)
        hr = hr_at_k(state.dense, state.table.master, cfg, seqs, test,
                     k=100)
        results[name] = (float(m["loss"]), hr)
        emit(f"fig12_quant.{name}", 0.0,
             f"final_loss={results[name][0]:.4f} HR@100={hr:.4f} "
             f"step_bytes_accessed={bytes_step[name]:.3e} "
             f"neg_fetch_bytes={neg_fetch_bytes[name]:.3e}")
    dl = abs(results["bf16"][0] - results["fp32"][0]) / results["fp32"][0]
    dh = abs(results["bf16"][1] - results["fp32"][1])
    ratio = neg_fetch_bytes["fp32"] / max(neg_fetch_bytes["bf16"], 1.0)
    emit("fig12_quant.delta", 0.0,
         f"loss_delta={100 * dl:.3f}% HR_delta={dh:.4f} "
         f"(paper: <=0.05% HR delta)")
    emit("fig12_quant.bytes", 0.0,
         f"measured neg-fetch payload bytes/step "
         f"fp32={neg_fetch_bytes['fp32']:.3e} "
         f"shadow={neg_fetch_bytes['bf16']:.3e} "
         f"reduction={ratio:.2f}x (paper Fig. 12: 2x on the negative "
         f"fetch); full-step bytes fp32={bytes_step['fp32']:.3e} "
         f"shadow={bytes_step['bf16']:.3e}")
    write_bench_json("fig12_quant", {
        "final_loss": {k: v[0] for k, v in results.items()},
        "hr_at_100": {k: v[1] for k, v in results.items()},
        "step_bytes_accessed": bytes_step,
        "neg_fetch_bytes_measured": neg_fetch_bytes,
        "neg_fetch_reduction_x": ratio,
    })


if __name__ == "__main__":
    main()
