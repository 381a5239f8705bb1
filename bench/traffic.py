"""Traffic mixes: a mix is ``<bench>/traffic/<name>.json``, a file of
parameters. Its ``generator`` names a module ``<bench>/generators/<g>.py``
whose ``batches(mix, model, seed, start, count, shards)`` returns the steps
``start .. start + count - 1`` of a cell of ``shards`` chips as ``(batch,
sequence_lengths)`` pairs. The same seed gives the same steps."""
from __future__ import annotations

import importlib.util
import json
import os
from typing import Dict, List, Tuple

import numpy as np


def load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no such file: {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_mix(bench_dir: str, name: str) -> Dict:
    path = os.path.join(bench_dir, "traffic", f"{name}.json")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no traffic mix {name!r}: {path}")
    with open(path) as f:
        return json.load(f)


def make_batches(bench_dir: str, mix: Dict, model: Dict, seed: int,
                 start: int, count: int, shards: int = 1
                 ) -> List[Tuple[Dict[str, np.ndarray], List[int]]]:
    gen = load_module(
        os.path.join(bench_dir, "generators", f"{mix['generator']}.py"),
        f"bench_generator_{mix['generator']}")
    return gen.batches(mix, model, seed, start, count, shards)
