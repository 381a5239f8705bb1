"""The program's own spans in a profiler trace: ``jax.profiler.
TraceAnnotation`` intervals that the engine opens on its host threads
(``obs.trace.stage_span`` for each stage call, ``wait_<stage>`` where the
main thread joins a host stage's future, ``loss_sync``, ``step_callback``,
``h2d``, ``prepare_run``). They sit on the host plane, one line per thread,
on the device trace's clock. The main thread is the line that holds the
harness's window marker; Python-tracer events there (``$file:line fn``)
never share a span's name."""
import xplane

DEVICE_STAGES = ("emb_fwd", "dense_fwd", "dense_bwd", "emb_bwd")


def main_thread(tr):
    """Events of the host line that holds the window marker, or []."""
    for p in tr["planes"]:
        if p["name"].startswith("/device:"):
            continue
        for ln in p["lines"]:
            if any(e[0] == xplane.MARKER for e in ln["events"]):
                return ln["events"]
    return []


def span_ns(events, win, names):
    """Total time of the spans named in ``names`` inside the window."""
    return xplane.total(xplane.clip((e for e in events if e[0] in names),
                                    win))


def count(events, win, names):
    """How many spans named in ``names`` start inside the window."""
    a, b = win
    return sum(1 for e in events if e[0] in names and a <= e[1] < b)
