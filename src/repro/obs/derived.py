"""Derived gauges: measured MFU and token-load imbalance.

These close the loop between the static roofline estimates in
``launch/roofline.py`` and what a run actually did:

- ``measured_mfu`` — model FLOPs per step (``launch/roofline.
  gr_model_flops``: dense weights, causal attention pairs, positive and
  negative logits) over *measured* step wall time against the device's
  peak from ``launch/roofline.DEVICE_PEAKS`` (paper's 54.71% MFU axis);
  None ("not measured") for a device kind with no published peak.
- ``token_imbalance`` — makespan-relative imbalance of per-device
  token loads (paper's 47% -> 2.4% axis), delegating to
  ``core/load_balance.imbalance_ratio``.

All guards: zero wall time / empty loads return zeros, never
divide-by-zero.
"""
from __future__ import annotations

from typing import Optional, Sequence

from repro.core import load_balance as LB

__all__ = ["measured_mfu", "token_imbalance"]


def measured_mfu(model_flops: float, wall_s: float,
                 peak_flops: Optional[float]) -> Optional[float]:
    """Measured model-FLOPs utilization for one step.

    ``model_flops`` comes from ``roofline.model_flops_per_step`` (or
    ``roofline.gr_model_flops`` for GR); ``wall_s`` is the measured
    step wall time; ``peak_flops`` the device's entry in
    ``roofline.DEVICE_PEAKS``. Returns None without a peak (not
    measured) and 0.0 when a count is non-positive.
    """
    if peak_flops is None:
        return None
    if wall_s <= 0.0 or model_flops <= 0.0 or peak_flops <= 0.0:
        return 0.0
    return float(model_flops) / (float(wall_s) * float(peak_flops))


def token_imbalance(loads: Sequence[float]) -> float:
    """Makespan-relative token-load imbalance across devices.

    ``(max - mean) / max`` over per-device token loads (e.g.
    ``offsets[:, -1]`` from a jagged batch, i.e.
    ``core/load_balance.assignment_token_loads`` output).  0.0 for
    empty/zero loads or a single device.
    """
    loads = [float(x) for x in loads]
    if len(loads) < 2 or max(loads) <= 0.0:
        return 0.0
    return float(LB.imbalance_ratio((), (), loads=loads))

