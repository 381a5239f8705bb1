"""Device time of the dense forward and backward program outside the named
kernels, per step, in ms: the blocks' projections, norms and the loss
around the attention, negative and lookup kernels; the mean over the cell's
chips."""
import _chips
import xplane
from _kernels import DENSE_PROGRAM, NAMED


def dense_ns(plane, win):
    ops = xplane.module_ops(plane, win, DENSE_PROGRAM)
    return sum(e[2] for e in ops if not xplane.matches(e[0], NAMED))


def read(run):
    ns = _chips.mean(run, lambda p: dense_ns(p, run.trace_window))
    return ns * 1e-6 / len(run.steps) if ns > 0 else None
