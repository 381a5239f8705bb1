"""Time the engine's main thread spent blocked on a host stage's future
(the ``wait_<stage>`` spans of ``core/pipeline.py``: dataload, feature
exchange, candidate unique) inside the window, per window step, in ms.
Zero would mean Algorithm 1 hides the host stages entirely; the window's
first steps also wait for the lookahead that the warm steps left empty."""
import _spans


def read(run):
    events = _spans.main_thread(run.trace)
    waits = {e[0] for e in events if e[0].startswith("wait_")}
    if not waits:
        return None
    ns = _spans.span_ns(events, run.trace_window, waits)
    return ns * 1e-6 / len(run.steps)
