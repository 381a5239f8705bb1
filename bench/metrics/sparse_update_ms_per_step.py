"""Device time of the sparse update per step, in ms: the weighted scatter
and the run-sum kernels (kernels/jagged_lookup) wherever they run, and every
op of the programs that land the table's rows (the engine's emb_bwd and
sparse_apply: row gradients, row-wise AdaGrad, and the dense AdamW update
that shares emb_bwd); the mean over the cell's chips."""
import _chips
import xplane
from _kernels import SPARSE, SPARSE_PROGRAMS


def sparse_ns(p, win):
    ns = xplane.kernel_ns(p, win, SPARSE)
    for prog in SPARSE_PROGRAMS:
        ns += sum(e[2] for e in xplane.module_ops(p, win, prog)
                  if not xplane.matches(e[0], SPARSE))
    return ns


def read(run):
    ns = _chips.mean(run, lambda p: sparse_ns(p, run.trace_window))
    return ns * 1e-6 / len(run.steps) if ns > 0 else None
