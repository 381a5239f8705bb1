"""Device time of the fused negative kernels (kernels/neg_logits: forward
and backward) per step, in ms."""
import xplane
from _kernels import NEGATIVES


def read(run):
    ns = xplane.kernel_ns(run.plane, run.trace_window, NEGATIVES)
    return ns * 1e-6 / len(run.steps) if ns > 0 else None
