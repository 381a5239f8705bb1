"""Host time of the train step's device stages on the engine's main thread
(the ``emb_fwd``, ``dense_fwd``, ``dense_bwd`` and ``emb_bwd`` stage spans)
less the parts inside them that wait for the device (``loss_sync``, the
loss read) or run the caller's ``step_callback``, inside the window, per
window step, in ms: what it costs the host to enqueue a step's programs."""
import _spans

EXCLUDED = ("loss_sync", "step_callback")


def read(run):
    events = _spans.main_thread(run.trace)
    win = run.trace_window
    if not _spans.count(events, win, _spans.DEVICE_STAGES):
        return None
    ns = (_spans.span_ns(events, win, _spans.DEVICE_STAGES)
          - _spans.span_ns(events, win, EXCLUDED))
    return ns * 1e-6 / len(run.steps)
