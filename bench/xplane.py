"""Reduction of a profiler trace to what the per-layer metrics read.

``load`` turns the ``.xplane.pb`` that ``jax.profiler.trace`` writes into a
plain dict (planes → lines → events ``[name, start_ns, duration_ns]``),
which is also the format of the small recorded trace the tests read. The
rest works on that dict:

* the window is the host span named ``MARKER`` that the harness records
  around the measured steps;
* a device's busy time is the union of its op intervals inside the window,
  and its idle gaps the rest of the window;
* a kernel's time is the sum of the durations of the ops that carry its
  name (device ops are named as the HLO names them, ``attn_fwd.16``);
  ops other than control flow are attributed to the program (jitted
  stage) whose module span contains them.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

MARKER = "bench_window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# control flow whose span holds the ops of its body
CONTAINERS = ("while", "conditional", "call")

Interval = Tuple[float, float]


def find(trace_dir: str) -> str:
    hits = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(hits, key=os.path.getmtime)


def load(path: str) -> Dict:
    """Plain-dict form of a trace: a ``.xplane.pb`` or a recorded
    ``.json``/``.json.gz`` of the same form."""
    if path.endswith(".json.gz"):
        with gzip.open(path, "rt") as f:
            return json.load(f)
    if path.endswith(".json"):
        with open(path) as f:
            return json.load(f)
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    planes = []
    for pl in pd.planes:
        dev = pl.name.startswith("/device:")
        lines = []
        for ln in pl.lines:
            evs = [[op_name(e.name) if dev else e.name, float(e.start_ns),
                    float(e.duration_ns)] for e in ln.events]
            lines.append({"name": ln.name, "events": evs})
        planes.append({"name": pl.name, "lines": lines})
    return {"planes": planes}


def op_name(hlo: str) -> str:
    """``attn_fwd.16`` from the HLO text that names a device op
    (``%attn_fwd.16 = bf16[...] custom-call(...)``)."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def base(name: str) -> str:
    """An op's kind or kernel: its name without the HLO's ``.<n>``."""
    return name.split(".")[0]


def device_planes(tr: Dict) -> List[Dict]:
    """The planes of the devices that ran ops, in device order
    (``/device:TPU:<n>``)."""
    planes = [p for p in tr["planes"]
              if p["name"].startswith("/device:") and
              any(ln["name"] == OPS_LINE for ln in p["lines"])]
    return sorted(planes, key=lambda p: _ordinal(p["name"]))


def _ordinal(name: str) -> float:
    n = name.rsplit(":", 1)[-1]
    return int(n) if n.isdigit() else float("inf")


def line(plane: Dict, name: str) -> List[List]:
    for ln in plane["lines"]:
        if ln["name"] == name:
            return ln["events"]
    return []


def window(tr: Dict, marker: str = MARKER) -> Optional[Interval]:
    for p in tr["planes"]:
        if p["name"].startswith("/device:"):
            continue
        for ln in p["lines"]:
            for name, start, dur in ln["events"]:
                if name == marker:
                    return (start, start + dur)
    return None


def clip(events: Iterable[Sequence], win: Interval) -> List[Interval]:
    a, b = win
    out = []
    for e in events:
        s, t = max(e[1], a), min(e[1] + e[2], b)
        if t > s:
            out.append((s, t))
    return out


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, t in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], t))
        else:
            out.append((s, t))
    return out


def total(intervals: Iterable[Interval]) -> float:
    return sum(t - s for s, t in intervals)


def busy_ns(plane: Dict, win: Interval) -> float:
    return total(union(clip(line(plane, OPS_LINE), win)))


def idle_gaps(plane: Dict, win: Interval) -> List[Interval]:
    gaps, cur = [], win[0]
    for s, t in union(clip(line(plane, OPS_LINE), win)):
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, t)
    if cur < win[1]:
        gaps.append((cur, win[1]))
    return gaps


def ops_in(plane: Dict, win: Interval) -> List[List]:
    a, b = win
    return [e for e in line(plane, OPS_LINE) if a <= e[1] < b]


def matches(name: str, kernels: Sequence[str]) -> bool:
    return base(name) in kernels


def leaf_ops(plane: Dict, win: Interval) -> List[List]:
    """Ops in the window other than control flow, whose spans hold the
    ops of their bodies."""
    return [e for e in ops_in(plane, win) if base(e[0]) not in CONTAINERS]


def kernel_ns(plane: Dict, win: Interval, kernels: Sequence[str]) -> float:
    return sum(e[2] for e in ops_in(plane, win) if matches(e[0], kernels))


def module_ops(plane: Dict, win: Interval, module: str) -> List[List]:
    """Ops inside the spans of the programs whose module name starts with
    ``module`` (``jit_<stage>`` for a jitted stage function)."""
    spans = [(s, s + d) for n, s, d in line(plane, MODULES_LINE)
             if n.startswith(module)]
    spans.sort()
    out, j = [], 0
    for e in sorted(leaf_ops(plane, win), key=lambda e: e[1]):
        while j < len(spans) and spans[j][1] <= e[1]:
            j += 1
        if j < len(spans) and spans[j][0] <= e[1] < spans[j][1]:
            out.append(e)
    return out


def top_ops(plane: Dict, win: Interval, n: int = 10) -> List[List]:
    """The ``n`` op kinds (kernels by name) that took the most device
    time in the window, in seconds."""
    by: Dict[str, float] = {}
    for name, _, dur in leaf_ops(plane, win):
        by[base(name)] = by.get(base(name), 0.0) + dur
    return [[k, v * 1e-9] for k, v in
            sorted(by.items(), key=lambda kv: -kv[1])[:n]]


def attribute_gaps(gaps: List[Interval], spans: List[Tuple[str, float, float]],
                   n: int = 10) -> List[List]:
    """The ``n`` longest gaps, each named by the host span (stage) that
    overlaps it most; ``unattributed`` where none does."""
    out = []
    for s, t in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        best, over = "unattributed", 0.0
        for name, a, b in spans:
            o = min(b, t) - max(a, s)
            if o > over:
                best, over = name, o
        out.append([best, (t - s) * 1e-9])
    return out
