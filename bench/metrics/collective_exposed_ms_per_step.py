"""The part of the collectives' device time (``collective_ms_per_step``) in
which no other op runs on the chip's ``XLA Ops`` line, per step, in ms:
what the exchange adds to a step that compute does not hide. The mean over
the chips whose planes hold the most steps; None where no chip ran a
collective."""
import _chips
import _collectives
import xplane


def read(run):
    win = run.trace_window
    if _chips.total(run, lambda p: xplane.total(
            _collectives.intervals(p, win))) <= 0:
        return None
    return _collectives.ms_per_step(run, lambda p: xplane.total(
        _collectives.exposed(p, win)))
