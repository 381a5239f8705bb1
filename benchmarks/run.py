"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only tableN]

Prints ``name,us_per_call,derived`` CSV rows (benchmarks/common.emit).
"""
import argparse
import glob
import importlib
import json
import os
import subprocess
import sys
import traceback

# preferred (paper) order; discovered bench_*.py modules not listed here
# are appended alphabetically so new benchmarks are picked up automatically
_ORDERED = [
    "benchmarks.bench_table1_e2e",
    "benchmarks.bench_fig2_jagged_fusion",
    "benchmarks.bench_table2_lookup",
    "benchmarks.bench_table3_load_balance",
    "benchmarks.bench_table4_hsp",
    "benchmarks.bench_table5_semi_async",
    "benchmarks.bench_table6_pipeline",
    "benchmarks.bench_table7_offload",
    "benchmarks.bench_fig12_quant",
    "benchmarks.bench_table8_logit_sharing",
    "benchmarks.bench_recovery",
    "benchmarks.bench_cache_embedding",
    "benchmarks.bench_serving",
    "benchmarks.bench_serving_stream",
    "benchmarks.bench_observability",
    "benchmarks.bench_kernels",
]


def discover_modules():
    # _ORDERED entries are kept even if their file went missing — the
    # import then fails loudly in main()'s per-module handler instead of
    # a stale rename silently dropping a row from the sweep
    here = os.path.dirname(os.path.abspath(__file__))
    found = sorted(f"benchmarks.{f[:-3]}" for f in os.listdir(here)
                   if f.startswith("bench_") and f.endswith(".py"))
    return _ORDERED + [m for m in found if m not in _ORDERED]


MODULES = discover_modules()


def _git_rev() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        ).stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def _headline(payload, prefix: str = "", limit: int = 64) -> dict:
    """Flatten a bench payload's numeric leaves (dot-joined paths) —
    the machine-readable headline numbers; capped so a pathological
    payload cannot bloat the summary."""
    out = {}

    def walk(node, path):
        if len(out) >= limit:
            return
        if isinstance(node, dict):
            for k in node:
                walk(node[k], f"{path}.{k}" if path else str(k))
        elif isinstance(node, bool):
            out[path] = int(node)
        elif isinstance(node, (int, float)):
            out[path] = node
    walk(payload, prefix)
    return out


def write_summary(out_dir: str = "") -> str:
    """Aggregate every ``BENCH_*.json`` in ``out_dir`` (default:
    $BENCH_JSON_DIR or cwd) into one ``BENCH_summary.json`` trajectory
    file: bench name → headline numbers, plus the git rev. Returns the
    summary path."""
    out_dir = out_dir or os.environ.get("BENCH_JSON_DIR", "") or os.getcwd()
    summary = {"git_rev": _git_rev(), "benches": {}}
    for path in sorted(glob.glob(os.path.join(out_dir, "BENCH_*.json"))):
        name = os.path.basename(path)[len("BENCH_"):-len(".json")]
        if name == "summary":
            continue
        try:
            with open(path) as f:
                payload = json.load(f)
        except (OSError, json.JSONDecodeError):
            continue
        summary["benches"][name] = _headline(payload)
    out_path = os.path.join(out_dir, "BENCH_summary.json")
    with open(out_path, "w") as f:
        json.dump(summary, f, indent=1, sort_keys=True)
    return out_path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    from repro.launch.compile_cache import use_repo_compile_cache
    use_repo_compile_cache()
    print("name,us_per_call,derived")
    failed = []
    for mod in MODULES:
        if args.only and args.only not in mod:
            continue
        print(f"# --- {mod} ---", flush=True)
        try:
            importlib.import_module(mod).main()
        except Exception:
            failed.append(mod)
            traceback.print_exc()
    # aggregate whatever BENCH_*.json exist so far (also under --only:
    # sequential CI bench steps accumulate into one trajectory file)
    print(f"# summary: {write_summary()}", flush=True)
    if failed:
        print(f"# FAILED: {failed}", file=sys.stderr)
        sys.exit(1)
    print("# all benchmarks complete")


if __name__ == "__main__":
    main()
