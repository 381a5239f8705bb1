"""Device time of the dense forward and backward program outside the named
kernels, per step, in ms: the blocks' projections, norms and the loss
around the attention, negative and lookup kernels."""
import xplane
from _kernels import DENSE_PROGRAM, NAMED


def read(run):
    ops = xplane.module_ops(run.plane, run.trace_window, DENSE_PROGRAM)
    ns = sum(e[2] for e in ops if not xplane.matches(e[0], NAMED))
    return ns * 1e-6 / len(run.steps) if ns > 0 else None
