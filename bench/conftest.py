"""Test set-up for the benchmark's own tests: the program's ``src/`` and
this directory on the path, a checkout-shaped scratch root holding a tiny
cell (``tiny_cell.py``), and a harness that runs on the CPU."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), BENCH, os.path.join(BENCH, "metrics")):
    if p not in sys.path:
        sys.path.insert(0, p)

from tiny_cell import make_root  # noqa: E402


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path)


@pytest.fixture
def on_cpu(monkeypatch):
    """The harness on the CPU's devices: no look for a chip, no compile
    cache, and a made-up peak in place of the chip's."""
    import jax

    import harness
    monkeypatch.setattr(harness, "require_chips",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(harness, "use_compile_cache", lambda root: None)
    monkeypatch.setattr(harness, "device_peak", lambda bench_dir, kind: {
        "bf16_flops": 1e12, "hbm_bytes_per_s": 1e11})
