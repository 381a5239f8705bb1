"""Quickstart: train a tiny HSTU generative recommender on synthetic
KuaiRand-style data, on whatever device this machine has (~1 min on CPU).

    PYTHONPATH=src python examples/quickstart.py

Shows the public API end to end: config → synthetic data → Appendix-A
preprocessing → load-balanced jagged loader → GRBundle loss (fused
ID-driven negatives: gather + bf16 fetch + logit sharing + Eq.-2 reduce in
one pass) → the staged execution engine running §4.2.3 Algorithm 1 (host
dataload/unique overlapped with async-dispatched device stages, τ=1
semi-async sparse updates).
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

from repro.configs import ARCHS, reduced
from repro.data.kuairand import preprocess_log
from repro.data.loader import GRLoader
from repro.data.synthetic import SyntheticKuaiRand
from repro.models.model_zoo import get_bundle
from repro.training.engine import GREngine


def main():
    # 1. data: synthetic KuaiRand surrogate + the paper's preprocessing
    gen = SyntheticKuaiRand(num_users=400, num_items=5000, mean_len=40,
                            max_len=256, seed=0)
    seqs, test, remap = preprocess_log(gen.log(400))
    print(f"data: {len(seqs)} users / {len(remap)} items after 5-core + "
          f"leave-one-out")

    # 2. model: reduced HSTU (same family as the paper's hstu-* variants)
    cfg = reduced(ARCHS["hstu-tiny"]).replace(
        vocab_size=max(len(remap), 16), num_negatives=16, max_seq_len=128)
    bundle = get_bundle(cfg)

    # 3. loader with §4.1.3 global token reallocation
    loader = GRLoader(seqs, num_devices=jax.device_count(),
                      users_per_device=4, max_seq_len=128,
                      num_negatives=16, num_items=len(remap),
                      strategy="token_realloc")

    # 4. the staged engine: §4.3 fused negative path (megakernel on TPU,
    #    remat'd scan elsewhere) + bf16 fetch + logit sharing, executed as
    #    the §4.2.3 six-stage pipeline with §4.2.2 τ=1 semi-async updates
    engine = GREngine(
        bundle, loader,
        loss_kwargs=dict(neg_mode="fused", neg_segment=64, expansion=2),
        semi_async=True, schedule="algorithm1",
        step_callback=lambda i, rec, state:
            (i + 1) % 5 == 0 and print(f"step {i + 1:3d}  "
                                       f"loss {rec['loss']:.4f}"))
    engine.run(20)
    r = engine.timeline_report()
    print(f"pipeline: computing {100 * r['computing_ratio']:.1f}% of wall, "
          f"free {100 * r['free_ratio']:.1f}% (Table 6's breakdown, "
          f"measured on this run)")
    print("done — see examples/recall_training_kuairand.py for the full "
          "scenario with HR@k evaluation")


if __name__ == "__main__":
    main()
