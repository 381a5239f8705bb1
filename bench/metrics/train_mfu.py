"""Model FLOP/s utilization of the window, in %: the forward and backward
operations that the window's valid tokens require (bench/flops.py:
dense weights, causal attention pairs, positive and negative logits;
recomputation not counted) over window x chips x peak bf16."""
import flops


def read(run):
    ops = sum(flops.model_flops(run.model, s["lengths"]) for s in run.steps)
    return 100.0 * ops / (run.window_s * run.chips * run.peak["bf16_flops"])
