"""Operations and bytes that the work of a step requires, from the batch's
jagged lengths and the configuration alone: never from block sizes, grids
or what an implementation happens to compute, so that the same work reads
the same whatever implements it.

``lengths`` is the list of a step's sequence lengths (tokens); a sequence
of ``l`` tokens has ``l (l + 1) / 2`` causal query-key pairs.
"""
from __future__ import annotations

import os
from typing import Dict, Sequence

import traffic

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))

BF16 = 2
F32 = 4
I32 = 4


def tokens(lengths: Sequence[int]) -> int:
    return int(sum(lengths))


def causal_pairs(lengths: Sequence[int]) -> int:
    return int(sum(l * (l + 1) // 2 for l in lengths))


def dense_matmul_params(model: Dict) -> int:
    """Weights of the dense model's matrix products (the non-embedding
    parameters that cost 2 operations per token forward). HSTU and
    FuXi-alpha blocks are counted here; a configuration with a block of its
    own names the module that counts it, ``"counts": "<module>"``
    (``<bench>/<module>.py`` with a ``dense_matmul_params(model)``)."""
    block = model["block"]
    if block not in ("hstu", "fuxi"):
        if "counts" not in model:
            raise ValueError(f"no count of the dense weights of a {block!r} "
                             f"block: the configuration names no 'counts' "
                             f"module")
        name = model["counts"]
        return traffic.load_module(os.path.join(BENCH_DIR, f"{name}.py"),
                                   f"bench_counts_{name}"
                                   ).dense_matmul_params(model)
    d, H, dq = model["d_model"], model["num_heads"], model["qkv_dim"]
    per = d * 4 * H * dq + H * dq * d
    if block == "fuxi":
        per += 3 * d * model["d_ff"]
    return model["num_layers"] * per


def model_flops(model: Dict, lengths: Sequence[int]) -> float:
    """Forward and backward operations of one step, recomputation not
    counted: 6 per dense weight per token, 12 H d_qkv per causal pair per
    layer, and 6 D (R + 1) per token for the positive and negative
    logits."""
    t = tokens(lengths)
    dense = 6.0 * dense_matmul_params(model) * t
    attn = (12.0 * model["num_heads"] * model["qkv_dim"]
            * causal_pairs(lengths) * model["num_layers"])
    neg = 6.0 * t * (model["num_negatives"] + 1) * model["d_model"]
    return dense + attn + neg


def attention_fwd(model: Dict, lengths: Sequence[int]) -> Dict[str, float]:
    """One layer's attention forward: Q K^T and A V over the causal pairs;
    reads q, k, v and writes the output once, in bf16."""
    H, dq = model["num_heads"], model["qkv_dim"]
    return {"flops": 4.0 * H * dq * causal_pairs(lengths),
            "bytes": 4.0 * tokens(lengths) * H * dq * BF16}


def attention_bwd(model: Dict, lengths: Sequence[int]) -> Dict[str, float]:
    """One layer's attention backward: dV, dA, dQ and dK over the causal
    pairs; reads q, k, v and dy and writes dq, dk and dv once, in bf16."""
    H, dq = model["num_heads"], model["qkv_dim"]
    return {"flops": 8.0 * H * dq * causal_pairs(lengths),
            "bytes": 7.0 * tokens(lengths) * H * dq * BF16}


def negatives_fwd(model: Dict, lengths: Sequence[int]) -> Dict[str, float]:
    """The negative logits of a step: R bf16 rows per valid token read
    once, the token vectors read once, R ids per token."""
    t, R, D = tokens(lengths), model["num_negatives"], model["d_model"]
    return {"flops": 2.0 * t * R * D,
            "bytes": t * (R * D * BF16 + D * BF16 + R * I32)}


def negatives_bwd(model: Dict, lengths: Sequence[int]) -> Dict[str, float]:
    """Their backward: the rows read again for the token gradient, the
    token vectors read and their gradient written."""
    t, R, D = tokens(lengths), model["num_negatives"], model["d_model"]
    return {"flops": 2.0 * t * R * D,
            "bytes": t * (R * D * BF16 + 2 * D * BF16 + R * I32)}


def roofline_s(work: Dict[str, float], peak: Dict[str, float]) -> float:
    """Least time the chip could take: the larger of operations over peak
    and bytes over HBM bandwidth."""
    return max(work["flops"] / peak["bf16_flops"],
               work["bytes"] / peak["hbm_bytes_per_s"])

