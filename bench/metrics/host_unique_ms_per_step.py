"""Mean host span of the engine's ``unique`` stage (the candidate-id sort
on a worker thread) over the window's steps, in ms, from the engine's
stage events."""


def read(run):
    spans = [e.end - e.start for e in run.events if e.stage == "unique"]
    return 1e3 * sum(spans) / len(spans) if spans else None
