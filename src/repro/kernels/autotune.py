"""Per-shape-regime autotuner for the repo's Pallas kernels.

The three megakernels (work-list jagged attention, fused negative
sampling, sorted-runsum scatter) expose schedule knobs —
``rows_per_step`` for the lookup gather, ``pairs_per_step`` for the
attention work-list, the backward-scatter ``scatter_impl`` — and this
module owns everything around picking their values (the negative kernel's
token block and the weighted scatter's slot block are no knobs:
:func:`neg_tokens_per_step` and :func:`wscatter_slots_per_block` size them
from the shapes):

* **candidate enumeration** from divisibility/alignment constraints and a
  coarse VMEM budget (``enumerate_candidates``);
* **cost-model ranking** so interpret-mode CPU runs can order candidates
  without a TPU (``estimate_cost`` / ``rank_candidates``) — the same
  numbers feed ``pl.CostEstimate`` so XLA's scheduler sees honest
  FLOPs/bytes even on the untuned default path (``pallas_cost``);
* **measured sweeps** timed through the PR-9 ``obs`` layer
  (``measure``/``sweep`` record spans on a ``Tracer`` and publish results
  into a ``MetricsRegistry`` — no private timing scaffolding);
* a **persistent store** (``tuned.json``, keyed by
  ``kernel|shape-bucket|backend``) that the ``ops.py`` wrappers consult
  via :func:`resolve` with a safe default fallback: a missing, corrupt,
  or stale-invalid entry silently degrades to the default schedule.

Shape keys are *buckets*, not exact shapes: large dims (> 256) round up
to a power of two so one real-hardware sweep covers a regime, small dims
(block sizes, R, segment) stay exact because the knob constraints depend
on them. Sweeps on real hardware write back through ``TunedStore.save``;
``REPRO_TUNED_JSON`` overrides the store path (tests point it at a tmp
file).
"""
from __future__ import annotations

import itertools
import json
import math
import os
import statistics
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
from jax.experimental import pallas as pl

__all__ = [
    "DEFAULTS", "CANDIDATES", "shape_bucket", "knob_valid",
    "enumerate_candidates", "neg_tokens_per_step",
    "wscatter_slots_per_block", "estimate_cost",
    "rank_candidates", "pallas_cost", "TunedStore", "default_path", "resolve",
    "measure", "sweep",
]

# ---------------------------------------------------------------------------
# machine model — only needs to ORDER candidates sensibly, not be exact
# ---------------------------------------------------------------------------

PEAK_FLOPS = 200e12         # MXU fp32-accumulate peak, one core (order of)
PEAK_BW = 1.0e12            # HBM bytes/s, one core (order of)
STEP_OVERHEAD_S = 2e-6      # per-grid-step dispatch + DMA-issue overhead
VMEM_BUDGET = 12 * 2 ** 20  # usable VMEM per kernel (conservative)

# ---------------------------------------------------------------------------
# knob spaces
# ---------------------------------------------------------------------------

DEFAULTS: Dict[str, Dict[str, Any]] = {
    # fused negative-sampling megakernel (kernels/neg_logits/fused.py)
    "neg_fused": {"scatter_impl": "fused"},
    # work-list jagged attention (kernels/jagged_attention)
    "attn_worklist": {"pairs_per_step": 1},
    # packed-index embedding gather (kernels/jagged_lookup)
    "lookup_gather": {"rows_per_step": 1},
}

CANDIDATES: Dict[str, Dict[str, Tuple[Any, ...]]] = {
    "neg_fused": {"scatter_impl": ("fused", "two_pass")},
    "attn_worklist": {"pairs_per_step": (1, 2, 4)},
    "lookup_gather": {"rows_per_step": (1, 2, 4, 8)},
}


def shape_bucket(dims: Mapping[str, Any]) -> str:
    """Canonical bucket key for a dims dict.

    Large extents (> 256: token counts, vocab, pair counts) round up to a
    power of two — tuning transfers within a regime; small extents (R,
    segment, block, D, H) stay exact because knob validity depends on
    them. Non-int values (dtype names, flags) pass through as-is.
    """
    parts = []
    for k in sorted(dims):
        v = dims[k]
        if isinstance(v, bool) or not isinstance(v, int):
            parts.append(f"{k}={v}")
        elif v > 256:
            parts.append(f"{k}=2^{max(v - 1, 1).bit_length()}")
        else:
            parts.append(f"{k}={v}")
    return ",".join(parts)


def knob_valid(kernel: str, dims: Mapping[str, Any], knob: str,
               value: Any) -> bool:
    """Is ``value`` a legal setting of ``knob`` for these dims?

    This is the stale-entry guard: ``resolve`` re-validates every stored
    value against the *current* shapes, so a tuned.json written for other
    shapes can never produce an invalid kernel configuration.
    """
    if kernel == "neg_fused":
        if knob == "scatter_impl":
            return value in ("fused", "two_pass")
    elif kernel == "attn_worklist":
        if knob == "pairs_per_step":
            return (isinstance(value, int) and not isinstance(value, bool)
                    and 1 <= value <= 64)
    elif kernel == "lookup_gather":
        if knob == "rows_per_step":
            return (isinstance(value, int) and not isinstance(value, bool)
                    and 1 <= value <= 64)
    return False


def _vmem_bytes(kernel: str, dims: Mapping[str, Any],
                config: Mapping[str, Any]) -> int:
    """Coarse per-step VMEM footprint of a candidate (double-buffered)."""
    D = int(dims.get("D", 128))
    if kernel == "neg_fused":
        seg = int(dims.get("segment", 128))
        R = int(dims.get("R", 1))
        tb = int(config.get("tokens_per_step", 1))
        row_bytes = D * int(dims.get("itemsize", 4))
        # the backward, the larger of the two: the double-buffered row slab
        # + o and d_out blocks (×2 pipeline) + logit/weight scratch and the
        # w block (×2) + one token's rows widened to fp32 and their product
        return (2 * tb * R * row_bytes
                + 4 * (4 * seg * D + 4 * seg * R + 2 * R * D))
    if kernel == "attn_worklist":
        blk = int(dims.get("block", 128))
        H = int(dims.get("H", 1))
        pps = int(config.get("pairs_per_step", 1))
        # q-side block + pps (k, v) blocks (×2 pipeline) + fp32 accumulator
        return 4 * (blk * H * D) * (2 + 4 * pps)
    if kernel == "lookup_gather":
        rps = int(config.get("rows_per_step", 1))
        return 4 * (4 * rps * D)
    if kernel == "lookup_wscatter":
        slots = int(config.get("slots_per_block", 8))
        # the double-buffered source-row slab + the fp32 destination tiles
        # whose write-backs are in flight
        return (2 * slots * D * int(dims.get("itemsize", 4))
                + WSCATTER_TILES_IN_FLIGHT * 8 * D * 4)
    return 0


def enumerate_candidates(kernel: str,
                         dims: Mapping[str, Any]) -> List[Dict[str, Any]]:
    """All valid knob combinations for this kernel/shape, VMEM-filtered."""
    space = CANDIDATES.get(kernel, {})
    knobs = sorted(space)
    out: List[Dict[str, Any]] = []
    for combo in itertools.product(*(space[k] for k in knobs)):
        cfg = dict(zip(knobs, combo))
        if not all(knob_valid(kernel, dims, k, v) for k, v in cfg.items()):
            continue
        if _vmem_bytes(kernel, dims, cfg) > VMEM_BUDGET:
            continue
        out.append(cfg)
    if not out:
        out.append(dict(DEFAULTS.get(kernel, {})))
    return out


# token blocks of the fused negative kernel; its time follows how fast the
# row DMAs are started, not the block (a v5e read the same time at 4, 8 and
# 16 tokens)
NEG_TOKEN_BLOCKS = (1, 2, 4, 8, 16)


def neg_tokens_per_step(dims: Mapping[str, Any]) -> int:
    """Tokens a grid step of the fused negative kernel covers: the largest
    of :data:`NEG_TOKEN_BLOCKS` that divides the segment and whose row
    slab, with the rest of the backward's buffers, fits
    :data:`VMEM_BUDGET`."""
    seg = int(dims.get("segment", 128))
    fits = [tb for tb in NEG_TOKEN_BLOCKS
            if seg % tb == 0 and _vmem_bytes(
                "neg_fused", dims, {"tokens_per_step": tb}) <= VMEM_BUDGET]
    return max(fits, default=1)


# the weighted table-gradient scatter: at most this many sorted slots a grid
# step, and this many 8-row destination tiles held in VMEM while their
# write-backs are in flight
WSCATTER_MAX_SLOTS = 512
WSCATTER_TILES_IN_FLIGHT = 16


def wscatter_slots_per_block(dims: Mapping[str, Any]) -> int:
    """Sorted slots a grid step of the weighted scatter walks: the largest
    power of two from 8 to :data:`WSCATTER_MAX_SLOTS` whose double-buffered
    source-row slab, with the tiles in flight, fits :data:`VMEM_BUDGET`."""
    slots = WSCATTER_MAX_SLOTS
    while slots > 8 and _vmem_bytes(
            "lookup_wscatter", dims, {"slots_per_block": slots}) > VMEM_BUDGET:
        slots //= 2
    return slots


# ---------------------------------------------------------------------------
# cost model — shared by candidate ranking and pl.CostEstimate wiring
# ---------------------------------------------------------------------------

def estimate_cost(kernel: str, dims: Mapping[str, Any],
                  config: Optional[Mapping[str, Any]] = None
                  ) -> Dict[str, float]:
    """(flops, bytes_accessed, transcendentals, grid_steps) for one config.

    Covers the *forward* pass of each kernel — enough for ranking (the
    backward scales all candidates by the same factor) and for honest
    ``pl.CostEstimate`` hints at every call site.
    """
    config = dict(DEFAULTS.get(kernel, {}), **(config or {}))
    D = int(dims.get("D", 128))
    if kernel == "neg_fused":
        seg = int(dims.get("segment", 128))
        R = int(dims.get("R", 1))
        T = int(dims.get("T", seg))
        k_exp = int(dims.get("expansion", 1))
        n_seg = -(-T // seg)
        tb = int(config.get("tokens_per_step") or neg_tokens_per_step(dims))
        itemsize = int(dims.get("itemsize", 4))
        pairs = n_seg * seg * R
        flops = 2.0 * pairs * D                       # per-slot dot
        flops += 2.0 * n_seg * (k_exp - 1) * seg * seg * R  # sharing matmuls
        flops += 3.0 * n_seg * seg * (1 + k_exp * R)  # logsumexp adds
        transc = 1.0 * n_seg * seg * (1 + k_exp * R)  # exp in logsumexp
        bytes_ = (itemsize * pairs * D    # gathered table rows, one DMA each
                  + 4.0 * (n_seg * seg * D   # o blocks
                           + n_seg * seg * 3))  # pos/valid/lse blocks
        steps = n_seg * (seg // max(tb, 1))
    elif kernel == "attn_worklist":
        blk = int(dims.get("block", 128))
        H = int(dims.get("H", 1))
        P = int(dims.get("num_pairs", 1))
        nb = int(dims.get("num_blocks", 1))
        pps = int(config.get("pairs_per_step", 1))
        flops = 4.0 * P * blk * blk * D * H           # qk^T and a@v
        transc = 1.0 * P * blk * blk * H              # sigmoid in SiLU
        bytes_ = 4.0 * P * (3 * blk * H * D) + 4.0 * nb * blk * H * D
        steps = -(-(P + nb * (pps - 1)) // pps)
    elif kernel == "lookup_gather":
        n = int(dims.get("n", 1))
        itemsize = int(dims.get("itemsize", 4))
        rps = int(config.get("rows_per_step", 1))
        flops = 0.0
        transc = 0.0
        bytes_ = 2.0 * n * D * itemsize
        steps = -(-n // max(rps, 1))
    else:
        flops = transc = bytes_ = 0.0
        steps = 1
    return {"flops": flops, "bytes_accessed": bytes_,
            "transcendentals": transc, "grid_steps": float(steps)}


def _score(cost: Mapping[str, float]) -> float:
    """Roofline seconds + per-step overhead: the ranking objective."""
    return (max(cost["flops"] / PEAK_FLOPS,
                cost["bytes_accessed"] / PEAK_BW)
            + cost["grid_steps"] * STEP_OVERHEAD_S)


def rank_candidates(kernel: str, dims: Mapping[str, Any],
                    candidates: Optional[Sequence[Mapping[str, Any]]] = None
                    ) -> List[Dict[str, Any]]:
    """Candidates sorted best-first by the cost model (stable)."""
    cands = (list(candidates) if candidates is not None
             else enumerate_candidates(kernel, dims))
    return sorted((dict(c) for c in cands),
                  key=lambda c: _score(estimate_cost(kernel, dims, c)))


def pallas_cost(flops: float = 0, bytes_accessed: float = 0,
                transcendentals: float = 0) -> Dict[str, Any]:
    """kwargs splat carrying a ``pl.CostEstimate`` for ``pl.pallas_call``.

    Returns ``{}`` on toolchains without ``CostEstimate`` so call sites
    can unconditionally ``**pallas_cost(...)``.
    """
    ce = getattr(pl, "CostEstimate", None)
    if ce is None:
        return {}
    try:
        return {"cost_estimate": ce(
            flops=max(int(flops), 0),
            bytes_accessed=max(int(bytes_accessed), 0),
            transcendentals=max(int(transcendentals), 0))}
    except Exception:  # pragma: no cover — API drift safety net
        return {}


# ---------------------------------------------------------------------------
# persistent tuned.json store
# ---------------------------------------------------------------------------

def default_path() -> str:
    return (os.environ.get("REPRO_TUNED_JSON")
            or os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "tuned.json"))


# path -> (mtime, entries); resolve() runs at trace time on the hot
# training path, so re-reading the file every compile is cached away
_ENTRY_CACHE: Dict[str, Tuple[float, Dict[str, Any]]] = {}


def _load_entries(path: str) -> Dict[str, Any]:
    try:
        mtime = os.path.getmtime(path)
    except OSError:
        return {}
    cached = _ENTRY_CACHE.get(path)
    if cached is not None and cached[0] == mtime:
        return cached[1]
    entries: Dict[str, Any] = {}
    try:
        with open(path) as f:
            data = json.load(f)
        if isinstance(data, dict) and isinstance(data.get("entries"), dict):
            entries = data["entries"]
    except (OSError, json.JSONDecodeError, UnicodeDecodeError):
        entries = {}          # corrupt file → defaults, never an error
    _ENTRY_CACHE[path] = (mtime, entries)
    return entries


class TunedStore:
    """Read/write view of one ``tuned.json``.

    Layout::

        {"version": 1,
         "entries": {"<kernel>|<shape-bucket>|<backend>":
                     {"config": {...}, "stats": {...}}}}

    Reads tolerate a missing or corrupt file (empty store); writes go
    through :meth:`save` (atomic tmp+rename).
    """

    def __init__(self, path: Optional[str] = None) -> None:
        self.path = path or default_path()
        self.entries: Dict[str, Any] = dict(_load_entries(self.path))

    @staticmethod
    def key(kernel: str, dims: Mapping[str, Any],
            backend: Optional[str] = None) -> str:
        return f"{kernel}|{shape_bucket(dims)}|{backend or jax.default_backend()}"

    def get(self, kernel: str, dims: Mapping[str, Any],
            backend: Optional[str] = None) -> Dict[str, Any]:
        entry = self.entries.get(self.key(kernel, dims, backend))
        if isinstance(entry, dict) and isinstance(entry.get("config"), dict):
            return entry["config"]
        return {}

    def put(self, kernel: str, dims: Mapping[str, Any],
            config: Mapping[str, Any], *, backend: Optional[str] = None,
            stats: Optional[Mapping[str, Any]] = None) -> str:
        key = self.key(kernel, dims, backend)
        self.entries[key] = {"config": dict(config),
                             "stats": dict(stats or {})}
        return key

    def save(self, path: Optional[str] = None) -> str:
        path = path or self.path
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"version": 1, "entries": self.entries}, f,
                      indent=1, sort_keys=True)
        os.replace(tmp, path)
        _ENTRY_CACHE.pop(path, None)
        return path


def resolve(kernel: str, dims: Mapping[str, Any], knob: str,
            default: Optional[Any] = None,
            backend: Optional[str] = None) -> Any:
    """Tuned value of ``knob`` for this shape, or the safe default.

    The single entry point the ``ops.py`` wrappers call: reads the
    (cached) tuned.json, re-validates the stored value against the
    current dims, and falls back to ``default`` (or the kernel's
    ``DEFAULTS``) on any miss, corruption, or constraint violation.
    """
    if default is None:
        default = DEFAULTS.get(kernel, {}).get(knob)
    entries = _load_entries(default_path())
    entry = entries.get(TunedStore.key(kernel, dims, backend))
    if not (isinstance(entry, dict) and isinstance(entry.get("config"), dict)):
        return default
    value = entry["config"].get(knob, default)
    return value if knob_valid(kernel, dims, knob, value) else default


# ---------------------------------------------------------------------------
# measured sweeps — timing via the PR-9 obs layer
# ---------------------------------------------------------------------------

def measure(fn: Callable[[], Any], *, iters: int = 3, warmup: int = 1,
            tracer=None, label: str = "autotune") -> float:
    """Median wall seconds of ``fn()`` over ``iters`` timed runs.

    Timing is recorded as ``Tracer`` spans (track ``"autotune"``) so a
    sweep leaves a Perfetto-visible trail; the median is read back from
    the recorded spans — the obs layer IS the timing scaffolding.
    """
    if tracer is None:
        from repro.obs import Tracer
        tracer = Tracer(enabled=True)
    for _ in range(max(warmup, 0)):
        jax.block_until_ready(fn())
    for i in range(max(iters, 1)):
        with tracer.span(label, track="autotune", rep=i):
            jax.block_until_ready(fn())
    spans = [s for s in tracer.spans()
             if s.track == "autotune" and s.name == label]
    return statistics.median(s.dur for s in spans[-max(iters, 1):])


def sweep(kernel: str, dims: Mapping[str, Any],
          run_fn: Callable[[Mapping[str, Any]], Callable[[], Any]], *,
          candidates: Optional[Sequence[Mapping[str, Any]]] = None,
          top_k: Optional[int] = None, iters: int = 3, warmup: int = 1,
          tracer=None, metrics=None, store: Optional[TunedStore] = None,
          backend: Optional[str] = None, save: bool = True
          ) -> Dict[str, Any]:
    """Measure candidates for one kernel/shape and persist the winner.

    ``run_fn(config)`` returns a zero-arg callable executing that
    variant (typically a jitted closure). Candidates are cost-model
    ranked first; ``top_k`` prunes the measured set to the model's best
    few — the ``pl.CostEstimate``-based pruning that makes CPU sweeps
    cheap. Results publish into ``metrics`` (when given) as
    ``autotune_*`` gauges/histograms and the winner lands in ``store``
    (skipped when ``save=False``).
    """
    ranked = rank_candidates(kernel, dims, candidates)
    if top_k is not None:
        ranked = ranked[:max(top_k, 1)]
    bucket = shape_bucket(dims)
    trials: List[Dict[str, Any]] = []
    for cfg in ranked:
        secs = measure(run_fn(cfg), iters=iters, warmup=warmup,
                       tracer=tracer, label=f"{kernel}:{bucket}")
        cost = estimate_cost(kernel, dims, cfg)
        trials.append({"config": dict(cfg), "seconds": secs,
                       "grid_steps": int(cost["grid_steps"]),
                       "model_score": _score(cost)})
        if metrics is not None:
            labels = {"kernel": kernel, "bucket": bucket,
                      **{k: v for k, v in cfg.items()}}
            metrics.histogram("autotune_trial_seconds",
                              "measured kernel-variant wall time",
                              labels=labels).observe(secs)
    trials.sort(key=lambda t: t["seconds"])
    best = trials[0]
    if metrics is not None:
        metrics.publish(f"autotune_{kernel}",
                        {"best_seconds": best["seconds"],
                         "best_grid_steps": best["grid_steps"],
                         "trials": len(trials)},
                        labels={"bucket": bucket})
    if store is None:
        store = TunedStore()
    key = store.put(kernel, dims, best["config"], backend=backend,
                    stats={"seconds": best["seconds"],
                           "grid_steps": best["grid_steps"],
                           "trials": len(trials)})
    if save:
        store.save()
    return {"kernel": kernel, "bucket": bucket, "key": key,
            "best": best, "trials": trials}
