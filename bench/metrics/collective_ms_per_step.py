"""Device time of the collective ops (all-gather, all-reduce,
reduce-scatter, all-to-all, collective-permute, under the compiler's names
or JAX's, and the start and done of each asynchronous one) per step, in
ms: the union of their intervals on a chip's ``XLA Ops`` and ``Async XLA
Ops`` lines, the mean over the chips whose planes hold the most steps.
None where no chip ran a collective."""
import _collectives
import xplane


def read(run):
    win = run.trace_window
    ms = _collectives.ms_per_step(run, lambda p: xplane.total(
        _collectives.intervals(p, win)))
    return ms if ms else None
