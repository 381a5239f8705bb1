"""Share of the traced window in which no operation ran on a chip: 1 -
(union of its op intervals) / window, in %, the mean over the cell's chips
(the same number as ``device.busy_s / device.window_s``)."""
import _chips
import xplane


def read(run):
    win = run.trace_window
    busy = _chips.mean(run, lambda p: xplane.busy_ns(p, win))
    return 100.0 * (1.0 - busy / (win[1] - win[0]))
