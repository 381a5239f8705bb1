"""Stage spans on two clocks, and a span tracer with Chrome/Perfetto
``trace_event`` export.

:func:`stage_span` times one pipeline stage call once and writes it to two
sinks: a ``StageEvent`` on ``time.perf_counter`` (what ``timeline_report``
and :meth:`Tracer.ingest_stage_events` read) and a
``jax.profiler.TraceAnnotation`` named by the stage with the step index as
its ``step`` argument, which lands in a profiler trace on the device
trace's clock whenever a profiler session is open (and costs about a
microsecond when none is).

The tracer is deliberately dumb: a thread-safe append-only list of
closed ``Span`` records on a monotonic clock.  Everything clever —
per-track busy-time union, the Chrome JSON layout —
is computed at export/report time from the immutable span list, so
recording stays cheap enough to leave on during benchmarks.

Clocks: spans carry ``time.perf_counter()`` timestamps (seconds,
monotonic, same clock as ``StageEvent``), so spans
recorded live and spans ingested from a ``SixStagePipeline`` event
stream land on a common timeline.  Tests inject explicit ``now=``
values instead of patching the clock.
"""
from __future__ import annotations

import json
import threading
import time
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterable, List, Mapping, Optional, Sequence, Tuple

from jax.profiler import TraceAnnotation

__all__ = [
    "Span",
    "StageEvent",
    "Tracer",
    "NULL_SPAN",
    "busy_from_intervals",
    "stage_span",
    "trace_busy_by_track",
]


@dataclass
class StageEvent:
    """One call of a pipeline stage for batch ``batch``, on
    ``perf_counter`` seconds."""
    stage: str
    batch: int
    start: float
    end: float


@contextmanager
def stage_span(events: List[StageEvent], stage: str, i: int,
               lock: Optional[threading.Lock] = None):
    """Time one call of ``stage`` for batch ``i``: a profiler-trace span
    ``stage`` with argument ``step=i`` around the body, and, when the body
    returns, the same interval appended to ``events`` (under ``lock``
    where other threads append too). A body that raises records no
    event."""
    t0 = time.perf_counter()
    with TraceAnnotation(stage, step=i):
        yield
    t1 = time.perf_counter()
    with lock or nullcontext():
        events.append(StageEvent(stage, i, t0, t1))


@dataclass(frozen=True)
class Span:
    """One closed interval on a named track.

    ``track`` groups spans into horizontal rows in the Perfetto UI (one
    per pipeline stage / worker thread); ``name`` labels the individual
    slice.  ``start``/``end`` are ``perf_counter`` seconds.
    """

    name: str
    track: str
    start: float
    end: float
    args: Mapping[str, Any] = field(default_factory=dict)

    @property
    def dur(self) -> float:
        return self.end - self.start


class _NullSpanCtx:
    """Shared no-op context manager handed out by a disabled tracer."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpanCtx":
        return self

    def __exit__(self, *exc: object) -> None:
        return None


_NULL_SPAN = _NullSpanCtx()
#: shared no-op span context for call sites instrumenting optionally
NULL_SPAN = _NULL_SPAN


def busy_from_intervals(intervals: Iterable[Tuple[float, float]]) -> float:
    """Total covered time of a set of (start, end) intervals (union)."""
    ivs = sorted((s, e) for s, e in intervals if e > s)
    busy = 0.0
    cur_s: Optional[float] = None
    cur_e = 0.0
    for s, e in ivs:
        if cur_s is None:
            cur_s, cur_e = s, e
        elif s <= cur_e:
            cur_e = max(cur_e, e)
        else:
            busy += cur_e - cur_s
            cur_s, cur_e = s, e
    if cur_s is not None:
        busy += cur_e - cur_s
    return busy


class Tracer:
    """Thread-safe span recorder.

    ``enabled=False`` makes every recording entry point a constant-time
    no-op (``span()`` returns one shared null context manager; nothing
    allocates), which is what ``Obs.noop()`` relies on for the
    zero-overhead acceptance criterion.
    """

    def __init__(self, enabled: bool = True,
                 clock: Callable[[], float] = time.perf_counter) -> None:
        self.enabled = enabled
        self.clock = clock
        self._lock = threading.Lock()
        self._spans: List[Span] = []

    # ---- recording ---------------------------------------------------
    @contextmanager
    def _span_cm(self, name: str, track: str, args: Optional[Mapping[str, Any]]):
        start = self.clock()
        try:
            yield self
        finally:
            end = self.clock()
            with self._lock:
                self._spans.append(Span(name, track, start, end, args or {}))

    def span(self, name: str, track: Optional[str] = None,
             **args: Any):
        """Context manager recording one span; no-op when disabled."""
        if not self.enabled:
            return _NULL_SPAN
        return self._span_cm(name, track or name, args or None)

    def record(self, name: str, track: str, start: float, end: float,
               args: Optional[Mapping[str, Any]] = None) -> None:
        """Record a span with explicit timestamps (``now=`` injection)."""
        if not self.enabled:
            return
        with self._lock:
            self._spans.append(Span(name, track, start, end, args or {}))

    # ---- adapters ----------------------------------------------------
    def ingest_stage_events(self, events: Sequence[StageEvent],
                            records: Optional[Mapping[int, Mapping[str, Any]]] = None,
                            merge: Optional[Mapping[str, str]] = None) -> int:
        """Ingest a ``SixStagePipeline`` event stream as spans.

        One track per (merged) stage name, matching ``timeline_report``'s
        ``stage_s`` accounting so exported busy times can be compared
        against it directly.  ``records`` (step -> per-step record dict)
        decorates each span's args with step/tokens/loss/cache hit rate.
        """
        if not self.enabled:
            return 0
        if merge is None:
            from repro.core.pipeline import REPORT_MERGED as merge
        n = 0
        for ev in events:
            track = merge.get(ev.stage, ev.stage)
            args: Dict[str, Any] = {"stage": ev.stage, "step": ev.batch}
            rec = records.get(ev.batch) if records else None
            if rec is not None:
                for k in ("tokens", "loss", "step_wall_s", "mfu", "imbalance"):
                    if rec.get(k) is not None:
                        args[k] = rec[k]
                cache = rec.get("cache")
                if isinstance(cache, Mapping) and "hit_rate" in cache:
                    args["cache_hit_rate"] = cache["hit_rate"]
            self.record(ev.stage, track, ev.start, ev.end, args)
            n += 1
        return n

    # ---- views -------------------------------------------------------
    def spans(self) -> List[Span]:
        with self._lock:
            return list(self._spans)

    def __len__(self) -> int:
        with self._lock:
            return len(self._spans)

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()

    def busy_by_track(self) -> Dict[str, float]:
        """Per-track busy seconds (interval union of that track's spans)."""
        by_track: Dict[str, List[Tuple[float, float]]] = {}
        for sp in self.spans():
            by_track.setdefault(sp.track, []).append((sp.start, sp.end))
        return {t: busy_from_intervals(ivs) for t, ivs in sorted(by_track.items())}

    def wall_span(self) -> Tuple[float, float]:
        """(min start, max end) over all spans; (0, 0) when empty."""
        spans = self.spans()
        if not spans:
            return (0.0, 0.0)
        return (min(s.start for s in spans), max(s.end for s in spans))

    # ---- export ------------------------------------------------------
    def to_chrome_trace(self, process_name: str = "repro") -> Dict[str, Any]:
        """Chrome/Perfetto ``trace_event`` JSON object.

        One thread (track) per pipeline stage / worker, named via ``M``
        metadata events; spans become ``X`` complete events with float-µs
        timestamps so round-tripped busy times match to <1 ns.
        """
        spans = self.spans()
        tracks = sorted({s.track for s in spans})
        tid_of = {t: i + 1 for i, t in enumerate(tracks)}
        events: List[Dict[str, Any]] = [{
            "name": "process_name", "ph": "M", "pid": 1, "tid": 0,
            "args": {"name": process_name},
        }]
        for track, tid in tid_of.items():
            events.append({"name": "thread_name", "ph": "M", "pid": 1,
                           "tid": tid, "args": {"name": track}})
        for sp in spans:
            events.append({
                "name": sp.name, "ph": "X", "pid": 1, "tid": tid_of[sp.track],
                "ts": sp.start * 1e6, "dur": sp.dur * 1e6,
                "cat": sp.track, "args": dict(sp.args),
            })
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def export(self, path: str, process_name: str = "repro") -> Dict[str, Any]:
        trace = self.to_chrome_trace(process_name)
        with open(path, "w") as f:
            json.dump(trace, f)
        return trace


def trace_busy_by_track(trace: Mapping[str, Any]) -> Dict[str, float]:
    """Per-track busy seconds recomputed from an exported Chrome trace.

    Used by tests/benchmarks to verify the exported JSON — not the
    in-memory tracer — agrees with ``timeline_report``'s ``stage_s``.
    """
    names: Dict[Tuple[int, int], str] = {}
    for ev in trace.get("traceEvents", []):
        if ev.get("ph") == "M" and ev.get("name") == "thread_name":
            names[(ev["pid"], ev["tid"])] = ev["args"]["name"]
    ivs: Dict[str, List[Tuple[float, float]]] = {}
    for ev in trace.get("traceEvents", []):
        if ev.get("ph") != "X":
            continue
        track = names.get((ev["pid"], ev["tid"]), str(ev["tid"]))
        start = ev["ts"] / 1e6
        ivs.setdefault(track, []).append((start, start + ev["dur"] / 1e6))
    return {t: busy_from_intervals(v) for t, v in sorted(ivs.items())}
