"""Share of the traced window in which no operation ran on the first
chip: 1 - (union of its op intervals) / window, in %."""
import xplane


def read(run):
    win = run.trace_window
    busy = xplane.busy_ns(run.plane, win)
    return 100.0 * (1.0 - busy / (win[1] - win[0]))
