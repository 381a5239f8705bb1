"""Device time of the jagged attention kernels (kernels/jagged_attention:
forward, backward dK/dV and backward dQ) per step, in ms, the mean over the
cell's chips."""
import _chips
import xplane
from _kernels import ATTENTION


def read(run):
    ns = _chips.mean(run, lambda p: xplane.kernel_ns(p, run.trace_window,
                                                      ATTENTION))
    return ns * 1e-6 / len(run.steps) if ns > 0 else None
