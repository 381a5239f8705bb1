"""Share of the jagged attention kernels' time that the chip's roofline
would need for the same work, in %. A step's work is one forward (Q K^T
and A V over the step's causal pairs) and one backward (dV, dA, dQ and
dK) per layer (bench/flops.py), at the larger of operations over peak
bf16 and bytes over HBM bandwidth. The count of layers comes from the
configuration, not from the kernels' calls, so that recomputation or a
layer split over several calls reads as more time for the same work. The
whole step's work is set against the sum of the cell's chips' kernel
time."""
import _chips
import flops
import xplane
from _kernels import ATTENTION


def read(run):
    ns = _chips.total(run, lambda p: xplane.kernel_ns(p, run.trace_window,
                                                       ATTENTION))
    if ns <= 0:
        return None
    layers = run.model["num_layers"]
    need = layers * sum(
        flops.roofline_s(flops.attention_fwd(run.model, s["lengths"]), run.peak)
        + flops.roofline_s(flops.attention_bwd(run.model, s["lengths"]), run.peak)
        for s in run.steps)
    return 100.0 * need / (ns * 1e-9)
