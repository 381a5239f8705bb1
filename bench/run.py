#!/usr/bin/env python3
"""Run one benchmark cell once and print its result.

    python3 bench/run.py --workload hstu-large.long-hist --seed 7 \\
        --seconds 30 --trace 0

From the root of a checkout that holds the program (``src/``) and
``BENCHMARK.json``. It measures ``--seconds`` of whole training steps on
the chips the cell asks for and prints, as the last line of stdout, one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer metrics read
from a profiler trace), ``device`` and, traced, ``breakdown``; its last key
``checks`` holds each number that decided ``correct`` beside its limit,
which also end standard error. Without a TPU, or with fewer chips than the
cell needs, it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.getcwd()


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    # libtpu writes its logs to a fixed /tmp path unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        sys.exit(f"bench: no program under {src}; no result")
    sys.path.insert(0, src)
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import harness
    out = harness.execute(ROOT, args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=T_START)
    harness.print_checks(out["checks"])
    print(harness.result_line(out), flush=True)


if __name__ == "__main__":
    main()
