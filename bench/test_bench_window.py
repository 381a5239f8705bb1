"""The window loop and the correctness comparison on a tiny configuration
on the CPU, and the refusal to print a result without a TPU."""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

import harness
from tiny_cell import BENCH, ROOT, make_root


@pytest.mark.parametrize("block", ["hstu", "fuxi"])
def test_window_and_check_on_a_tiny_cell(tmp_path, on_cpu, block):
    root = make_root(tmp_path, block=block)
    out = harness.execute(root, "tiny.mix", 2 ** 31 + 11, 0.5, False,
                          t_start=time.perf_counter())
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] >= 2 and out["failed"] == 0
    assert out["compiles_in_window"] == 0
    m = out["metrics"]
    assert list(m) == ["train_tokens_per_s", "setup_s"]
    assert m["train_tokens_per_s"]["value"] > 0
    assert m["setup_s"]["unit"] == "s"
    line = json.loads(harness.result_line(out))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["device"]["platform"] == "cpu"


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "bench", "run.py"), "--workload",
         "hstu-large.long-hist", "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_run_refuses_without_a_tpu():
    p = _run_py(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def test_run_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, os.path.join(tmp_path, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(str(tmp_path))
    assert p.returncode != 0
    assert not p.stdout.strip()
