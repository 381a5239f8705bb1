"""Share of the fused negative kernels' time that the chip's roofline
would need for the step's negative logits and their backward, in %: at
least R bf16 rows per valid token read forward and read again backward,
plus the token vectors (bench/flops.py). Memory bound. The whole step's
work is set against the sum of the cell's chips' kernel time."""
import _chips
import flops
import xplane
from _kernels import NEGATIVES


def read(run):
    ns = _chips.total(run, lambda p: xplane.kernel_ns(p, run.trace_window,
                                                       NEGATIVES))
    if ns <= 0:
        return None
    need = sum(flops.roofline_s(flops.negatives_fwd(run.model, s["lengths"]), run.peak)
               + flops.roofline_s(flops.negatives_bwd(run.model, s["lengths"]), run.peak)
               for s in run.steps)
    return 100.0 * need / (ns * 1e-9)
