"""A tiny cell for the benchmark's own tests on the CPU: a small HSTU or
FuXi configuration, a small traffic mix, and a checkout-shaped root that
holds them beside a copy of the benchmark's files."""
import json
import os
import shutil

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

TINY = {
    "source": "small test configuration", "block": "hstu", "d_model": 128,
    "num_layers": 2, "num_heads": 4, "qkv_dim": 32, "d_ff": 0,
    "max_seq_len": 32, "num_negatives": 8,
    "rab": {"num_pos_buckets": 16, "num_time_buckets": 8,
            "time_bucket_scale": 0.301, "use_time": True, "use_pos": True},
    "norm_eps": 1e-5, "dtype": "bfloat16", "rab_dtype": "float32",
    "vocab_size": 512, "reduced": [],
    "training": {"schedule": "algorithm1",
                 "semi_async": True, "neg_mode": "fused", "expansion": 1,
                 "lr_dense": 4e-3, "lr_sparse": 4e-3, "adam_b1": 0.9,
                 "adam_b2": 0.999, "adam_eps": 1e-8, "adagrad_eps": 1e-10,
                 "init_scale_table": 0.02}}
TINY_MIX = {"generator": "packed_histories", "token_budget": 128,
            "max_seqs": 16, "history_mean": 20, "history_sigma": 1.0,
            "history_min": 2, "zipf_a": 1.1,
            "time_span_s": 2592000}


def make_root(path, model=None, block="hstu", chips=1):
    """A checkout-shaped directory: BENCHMARK.json with one tiny cell
    ``tiny.mix`` of ``chips`` chips and the benchmark's files."""
    shutil.copytree(BENCH, os.path.join(path, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "test_*",
                                                  "data"))
    m = dict(model or TINY, block=block)
    if block == "fuxi":
        m.update(d_ff=96)
    with open(os.path.join(path, "bench", "configs", "tiny.json"), "w") as f:
        json.dump(m, f)
    with open(os.path.join(path, "bench", "traffic", "mix.json"), "w") as f:
        json.dump(TINY_MIX, f)
    with open(os.path.join(path, "bench", "limits", "tiny.mix.json"),
              "w") as f:
        json.dump({"loss_gap": 5e-4, "grad_gap": 0.03, "change_gap": 0.1,
                   "table_change_gap": 0.02}, f)
    spec = {"command": ["python3", "bench/run.py"], "paths": ["bench"],
            "run_seconds": 1,
            "configs": [{"name": "tiny", "source": "test",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "test"}],
            "workloads": [{"name": "tiny.mix", "config": "tiny",
                           "traffic": "mix", "chips": chips, "why": "test"}],
            "end_to_end": [
                {"name": "train_tokens_per_s", "unit": "tokens/s",
                 "better": "higher", "bound": 0.05, "source": "host_clock"},
                {"name": "setup_s", "unit": "s", "better": "lower",
                 "bound": 0.25, "source": "host_clock"}],
            "per_layer": [
                {"name": "host_unique_ms_per_step", "unit": "ms",
                 "better": "lower", "source": "program_span",
                 "layer": "engine host stages",
                 "moves": "train_tokens_per_s"}]}
    with open(os.path.join(path, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return str(path)
