"""Roofline-term extraction from compiled dry-run artifacts.

Per (arch × shape × mesh) we derive, from the *per-device* SPMD module:

    compute term    = HLO_FLOPs / peak_FLOP/s          (197 TF/s bf16, v5e)
    memory term     = HLO_bytes / HBM_bw               (819 GB/s, v5e)
    collective term = collective_bytes / link_bw       (~50 GB/s/link ICI)

HLO_FLOPs / HLO_bytes come from ``compiled.cost_analysis()`` (already
per-device after partitioning). collective_bytes is parsed from the
post-optimization HLO text: the sum of operand sizes of every all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute (+ their
async -start forms) — a ring collective moves ≈ its operand bytes through
each link.

MODEL_FLOPS is the analytic 6·N_active·D (train) / 2·N·D (inference),
N excluding embeddings; the ratio MODEL_FLOPS/HLO_FLOPs exposes remat or
redundancy waste (ratio ≪ 1/3 under full remat means pathological
recompute).
"""
from __future__ import annotations

import re
from dataclasses import asdict, dataclass
from typing import Dict, Mapping, Optional, Tuple

import numpy as np

from repro.configs.base import (ArchConfig, count_active_params, count_params)
from repro.configs.shapes import ShapeConfig

# Published per-chip peaks, keyed by ``jax.Device.device_kind``. Source:
# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM.
# A device kind that is not here has no peak: nothing is divided by a
# guess (a CPU run records no MFU).
DEVICE_PEAKS: Mapping[str, Mapping[str, float]] = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9},
}


def device_peak(device_kind: str) -> Optional[Mapping[str, float]]:
    """The peak-rate entry for a device kind, or None when not in the
    table."""
    return DEVICE_PEAKS.get(device_kind)


# the dry-run roofline targets a v5e pod (launch/dryrun.py)
PEAK_FLOPS = DEVICE_PEAKS["TPU v5 lite"]["flops"]     # bf16
HBM_BW = DEVICE_PEAKS["TPU v5 lite"]["hbm_bw"]        # bytes/s
LINK_BW = 50e9               # bytes/s per ICI link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter",
                  "all-to-all", "collective-permute")

_SHAPE_RE = re.compile(r"\b([a-z]+\d*(?:e\d+m\d+(?:fn)?)?)\[([\d,]*)\]")
# definition line:  %name = <type(s)> opcode(...operands...)
_DEF_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(\([^)]*\)|[^\s(]+)\s+([\w\-]+)\((.*)",
    re.M)
_OPERAND_RE = re.compile(r"%([\w.\-]+)")


def _shape_bytes(dtype: str, dims: str) -> int:
    b = _DTYPE_BYTES.get(dtype)
    if b is None:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * b


def _type_bytes(type_str: str) -> int:
    return sum(_shape_bytes(m.group(1), m.group(2))
               for m in _SHAPE_RE.finditer(type_str))


def cost_dict(compiled) -> Dict[str, float]:
    """``compiled.cost_analysis()`` as a dict (empty when unavailable)."""
    return dict(compiled.cost_analysis() or {})


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum *operand* bytes per collective kind from post-SPMD HLO text.

    Two passes: (1) map every instruction name → its result size; (2) for
    each collective (incl. async -start forms; -done excluded to avoid
    double counting), sum its operands' result sizes.
    """
    sizes: Dict[str, int] = {}
    colls = []  # (kind, operand names)
    for m in _DEF_RE.finditer(hlo_text):
        name, type_str, opcode, rest = m.groups()
        sizes[name] = _type_bytes(type_str)
        base = opcode[:-6] if opcode.endswith("-start") else opcode
        if base in COLLECTIVE_OPS and not opcode.endswith("-done"):
            # operands live before the first '),' — cut at the metadata
            args = rest.split("), ")[0] if "), " in rest else rest
            args = args.split(")")[0]
            ops = _OPERAND_RE.findall(args)
            colls.append((base, ops))
    out: Dict[str, int] = {k: 0 for k in COLLECTIVE_OPS}
    for kind, ops in colls:
        out[kind] += sum(sizes.get(o, 0) for o in ops)
    return out


@dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    # per-device quantities
    hlo_flops: float
    hlo_bytes: float
    coll_bytes: float
    coll_by_kind: Dict[str, int]
    # derived terms (seconds)
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    # usefulness
    model_flops: float               # per-device analytic
    useful_ratio: float              # model_flops / hlo_flops
    roofline_frac: float             # model_flops/peak / max(term)
    step_tokens: int
    notes: str = ""

    def to_dict(self):
        return asdict(self)


def gr_dense_params(cfg: ArchConfig) -> int:
    """Analytic dense-backbone params for HSTU/FuXi (matches Table 1)."""
    d, L = cfg.d_model, cfg.num_layers
    H = cfg.num_heads
    dqk = cfg.qkv_dim or cfg.resolved_head_dim
    per = d * H * 4 * dqk + H * dqk * d          # f1 (d→4d) + f2 (d→d)
    if cfg.gr_block == "fuxi":
        d_ff = cfg.d_ff
        per += 3 * d * d_ff                      # gated interaction FFN
    return L * per


def gr_model_flops(cfg: ArchConfig, lengths) -> float:
    """Forward and backward operations one GR training step requires, from
    its jagged sequence lengths (any shape; padding rows are 0),
    recomputation not counted: 6 per dense weight per token, 12·H·d_qkv
    per causal query-key pair per layer, and 6·d·(R + 1) per token for the
    positive and negative logits. The benchmark's ``bench/flops.
    model_flops`` counts the same."""
    lengths = np.asarray(lengths, np.int64).reshape(-1)
    t = int(lengths.sum())
    pairs = int((lengths * (lengths + 1) // 2).sum())
    dqk = cfg.qkv_dim or cfg.resolved_head_dim
    dense = 6.0 * gr_dense_params(cfg) * t
    attn = 12.0 * cfg.num_heads * dqk * pairs * cfg.num_layers
    logits = 6.0 * t * (cfg.num_negatives + 1) * cfg.d_model
    return dense + attn + logits


def model_flops_per_step(cfg: ArchConfig, shape: ShapeConfig) -> Tuple[float, int]:
    """(global analytic FLOPs per step, tokens per step)."""
    if cfg.gr:
        n = gr_dense_params(cfg)
        # jagged: valid tokens ≈ mean fill of the packed capacity
        tokens = int(shape.global_batch * shape.seq_len * 0.6)
        return 6.0 * n * tokens, tokens
    n_act = count_active_params(cfg)
    emb = cfg.vocab_size * cfg.d_model
    if not cfg.tie_embeddings:
        emb *= 2
    n = max(n_act - emb, 1)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens, tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens, tokens
    tokens = shape.global_batch          # decode: one token per sequence
    return 2.0 * n * tokens, tokens


def analyze(cfg: ArchConfig, shape: ShapeConfig, mesh_name: str, chips: int,
            cost: Dict[str, float], hlo_text: str,
            notes: str = "") -> Roofline:
    # trip-count-aware totals (XLA's cost_analysis counts scan bodies once —
    # see hlo_analysis.py); xla_* kept in notes for cross-checking.
    from repro.launch.hlo_analysis import analyze_text
    totals = analyze_text(hlo_text)
    flops = float(totals.flops)
    byts = float(totals.bytes)
    coll = {k: int(v) for k, v in totals.coll_bytes.items()}
    coll_total = float(sum(coll.values()))
    notes = (notes + f" | xla_once: flops={cost.get('flops', 0):.3e} "
             f"bytes={cost.get('bytes accessed', 0):.3e}")

    compute_s = flops / PEAK_FLOPS
    memory_s = byts / HBM_BW
    collective_s = coll_total / LINK_BW
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    dominant = max(terms, key=terms.get)

    gflops, tokens = model_flops_per_step(cfg, shape)
    mflops_dev = gflops / chips
    useful = mflops_dev / flops if flops else 0.0
    ideal_s = mflops_dev / PEAK_FLOPS
    bound_s = max(terms.values())
    frac = ideal_s / bound_s if bound_s else 0.0

    return Roofline(
        arch=cfg.name, shape=shape.name, mesh=mesh_name, chips=chips,
        hlo_flops=flops, hlo_bytes=byts, coll_bytes=coll_total,
        coll_by_kind=coll, compute_s=compute_s, memory_s=memory_s,
        collective_s=collective_s, dominant=dominant,
        model_flops=mflops_dev, useful_ratio=useful, roofline_frac=frac,
        step_tokens=tokens, notes=notes)
