"""The harness finds a cell's configuration, traffic mix and per-layer
metrics by the names in BENCHMARK.json, from files alone, including ones
that exist only in a scratch checkout."""
import json
import os

import pytest

import compare
import harness


def test_cell_found_by_name(tiny_root):
    cell = harness.load_cell(tiny_root, "tiny.mix")
    assert cell.model["d_model"] == 128 and cell.chips == 1
    assert cell.mix["token_budget"] == 128
    assert [m["name"] for m in cell.end_to_end] == ["train_tokens_per_s",
                                                    "setup_s"]
    assert set(cell.limits) == set(compare.NUMBERS)


def test_new_config_mix_and_metric_are_files_and_entries(tiny_root):
    bench = os.path.join(tiny_root, "bench")
    with open(os.path.join(bench, "traffic", "mix.json")) as f:
        mix = json.load(f)
    with open(os.path.join(bench, "traffic", "other.json"), "w") as f:
        json.dump(dict(mix, history_mean=5), f)
    with open(os.path.join(bench, "limits", "tiny.other.json"), "w") as f:
        json.dump({"loss_gap": 1, "grad_gap": 1, "change_gap": 1}, f)
    with open(os.path.join(bench, "metrics", "steps_seen.py"), "w") as f:
        f.write("def read(run):\n    return float(len(run.steps))\n")
    spec_path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(spec_path) as f:
        spec = json.load(f)
    with open(os.path.join(bench, "configs", "tiny.json")) as f:
        model = json.load(f)
    with open(os.path.join(bench, "configs", "tiny-wide.json"), "w") as f:
        json.dump(dict(model, d_model=256), f)
    spec["configs"].append({"name": "tiny-wide", "source": "test",
                            "file": "bench/configs/tiny-wide.json",
                            "reduced": [], "why": "t"})
    spec["workloads"].append({"name": "tiny.other", "config": "tiny-wide",
                              "traffic": "other", "chips": 1, "why": "t"})
    spec["per_layer"].append({"name": "steps_seen", "unit": "steps",
                              "better": "higher", "source": "host_clock",
                              "layer": "engine host stages",
                              "moves": "train_tokens_per_s",
                              "workloads": ["tiny.other"]})
    with open(spec_path, "w") as f:
        json.dump(spec, f)
    cell = harness.load_cell(tiny_root, "tiny.other")
    assert cell.mix["history_mean"] == 5 and cell.model["d_model"] == 256
    assert [m["name"] for m in cell.per_layer] == ["host_unique_ms_per_step",
                                                   "steps_seen"]
    assert "steps_seen" not in [m["name"] for m in
                                harness.load_cell(tiny_root,
                                                  "tiny.mix").per_layer]
    reader = harness.metric_reader(bench, "steps_seen")
    run = harness.Run(model=cell.model, mix=cell.mix, chips=1, peak={},
                      steps=[{}, {}])
    assert reader.read(run) == 2.0


def test_unknown_names_are_errors(tiny_root):
    with pytest.raises(SystemExit):
        harness.load_cell(tiny_root, "tiny.nothing")
    with pytest.raises(FileNotFoundError):
        harness.metric_reader(os.path.join(tiny_root, "bench"), "nothing")


def test_peaks_table_and_unknown_device_kind(tiny_root):
    bench = os.path.join(tiny_root, "bench")
    peak = harness.device_peak(bench, "TPU v5 lite")
    assert peak["bf16_flops"] == 197e12 and peak["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.device_peak(bench, "cpu")
