"""Device time of the fused negative kernels (kernels/neg_logits: forward
and backward) per step, in ms, the mean over the cell's chips."""
import _chips
import xplane
from _kernels import NEGATIVES


def read(run):
    ns = _chips.mean(run, lambda p: xplane.kernel_ns(p, run.trace_window,
                                                      NEGATIVES))
    return ns * 1e-6 / len(run.steps) if ns > 0 else None
