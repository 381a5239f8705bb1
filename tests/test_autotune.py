"""Autotuner unit tests: candidate enumeration/ranking, the tuned.json
store (round trip, corrupt-file fallback, stale-entry guard), resolve()
semantics, and a tiny measured sweep through the obs layer."""
import json
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import autotune
from repro.kernels.autotune import (CANDIDATES, DEFAULTS, TunedStore,
                                    enumerate_candidates, estimate_cost,
                                    knob_valid, rank_candidates,
                                    shape_bucket)
from repro.obs import MetricsRegistry, Tracer


@pytest.fixture()
def tuned_path(tmp_path, monkeypatch):
    p = str(tmp_path / "tuned.json")
    monkeypatch.setenv("REPRO_TUNED_JSON", p)
    return p


NEG_DIMS = {"segment": 16, "R": 8, "D": 16, "T": 64, "expansion": 2}
ATTN_DIMS = {"block": 8, "nb": 12, "causal": True}
LOOKUP_DIMS = {"n": 48, "D": 16, "itemsize": 4}


# ---------------------------------------------------------------------------
# buckets / candidates / cost model
# ---------------------------------------------------------------------------

def test_shape_bucket_rounds_large_dims():
    assert shape_bucket({"T": 4096}) == "T=2^12"
    assert shape_bucket({"T": 4097}) == "T=2^13"
    assert shape_bucket({"R": 32}) == "R=32"            # small: exact
    assert shape_bucket({"causal": True}) == "causal=True"
    # order-insensitive canonical key
    assert (shape_bucket({"a": 1, "b": 2})
            == shape_bucket({"b": 2, "a": 1}))


@pytest.mark.parametrize("kernel,dims", [
    ("neg_fused", NEG_DIMS),
    ("attn_worklist", ATTN_DIMS),
    ("lookup_gather", LOOKUP_DIMS),
])
def test_enumerate_only_valid(kernel, dims):
    cands = enumerate_candidates(kernel, dims)
    assert cands, "must propose at least the default"
    for cfg in cands:
        for knob, value in cfg.items():
            assert knob_valid(kernel, dims, knob, value), (cfg, knob)
    assert DEFAULTS[kernel] in cands or any(
        all(cfg.get(k) == v for k, v in DEFAULTS[kernel].items()
            if k in cfg) for cfg in cands)


def test_rank_candidates_sorted_by_model():
    ranked = rank_candidates("lookup_gather", LOOKUP_DIMS)
    scores = [autotune._score(estimate_cost("lookup_gather", LOOKUP_DIMS, c))
              for c in ranked]
    assert len(ranked) > 1 and scores == sorted(scores)


def test_grid_steps_shrink_with_grouping():
    s1 = estimate_cost("neg_fused", NEG_DIMS, {"tokens_per_step": 1})
    s8 = estimate_cost("neg_fused", NEG_DIMS, {"tokens_per_step": 8})
    assert s8["grid_steps"] * 8 == s1["grid_steps"]
    # without a block, the cost describes the one the kernel compiles
    tb = autotune.neg_tokens_per_step(NEG_DIMS)
    assert (estimate_cost("neg_fused", NEG_DIMS)["grid_steps"] * tb
            == s1["grid_steps"])
    adims = {"block": 8, "H": 2, "D": 16, "num_pairs": 36, "num_blocks": 12}
    a1 = estimate_cost("attn_worklist", adims, {"pairs_per_step": 1})
    a4 = estimate_cost("attn_worklist", adims, {"pairs_per_step": 4})
    assert a4["grid_steps"] < a1["grid_steps"]


def test_knob_valid_rejects_bad_values():
    assert not knob_valid("lookup_gather", LOOKUP_DIMS, "rows_per_step", 0)
    assert not knob_valid("lookup_gather", LOOKUP_DIMS, "rows_per_step",
                          True)
    assert not knob_valid("lookup_gather", LOOKUP_DIMS, "rows_per_step", 65)
    assert not knob_valid("neg_fused", NEG_DIMS, "scatter_impl", "magic")
    assert knob_valid("neg_fused", NEG_DIMS, "scatter_impl", "two_pass")
    # the negative kernel's token block is sized from the shapes, no knob
    assert not knob_valid("neg_fused", NEG_DIMS, "tokens_per_step", 16)
    assert "tokens_per_step" not in CANDIDATES["neg_fused"]
    assert not knob_valid("attn_worklist", ATTN_DIMS, "pairs_per_step", 0)


@pytest.mark.parametrize("itemsize,tb", [(2, 16), (4, 8)])
def test_neg_tokens_per_step_fills_the_vmem_budget(itemsize, tb):
    # the cells' shapes: D=1024, R=128, segment 128; bf16 rows packed two
    # to a word (2 bytes an element) or fp32 rows (4)
    dims = {"segment": 128, "R": 128, "D": 1024, "T": 8192,
            "expansion": 1, "itemsize": itemsize}
    assert autotune.neg_tokens_per_step(dims) == tb
    assert autotune._vmem_bytes("neg_fused", dims, {"tokens_per_step": tb}
                                ) <= autotune.VMEM_BUDGET
    assert autotune._vmem_bytes("neg_fused", dims,
                                {"tokens_per_step": 2 * tb}
                                ) > autotune.VMEM_BUDGET


def test_pallas_cost_shape():
    kw = autotune.pallas_cost(flops=1e6, bytes_accessed=1e5,
                              transcendentals=10)
    # either a real CostEstimate kwarg or cleanly absent on old jax
    assert kw == {} or "cost_estimate" in kw


# ---------------------------------------------------------------------------
# store + resolve
# ---------------------------------------------------------------------------

def test_store_round_trip(tuned_path):
    store = TunedStore()
    assert store.path == tuned_path
    store.put("neg_fused", NEG_DIMS, {"scatter_impl": "two_pass"},
              stats={"seconds": 1e-3})
    store.save()
    assert autotune.resolve("neg_fused", NEG_DIMS,
                            "scatter_impl") == "two_pass"
    # fresh store object re-reads the file
    again = TunedStore()
    assert again.get("neg_fused", NEG_DIMS) == {"scatter_impl": "two_pass"}


def test_resolve_defaults_on_missing(tuned_path):
    assert autotune.resolve("lookup_gather", LOOKUP_DIMS,
                            "rows_per_step") == 1
    assert autotune.resolve("neg_fused", NEG_DIMS, "scatter_impl") == "fused"
    assert autotune.resolve("attn_worklist", ATTN_DIMS, "pairs_per_step",
                            default=2) == 2


def test_resolve_corrupt_file_falls_back(tuned_path):
    with open(tuned_path, "w") as f:
        f.write("{not json")
    assert autotune.resolve("neg_fused", NEG_DIMS, "scatter_impl") == "fused"
    with open(tuned_path, "w") as f:
        json.dump({"version": 1, "entries": "nope"}, f)
    assert autotune.resolve("neg_fused", NEG_DIMS, "scatter_impl") == "fused"


def test_resolve_stale_entry_guard(tuned_path):
    # a stored value that no longer satisfies the current dims degrades
    # to the default instead of configuring an invalid kernel
    store = TunedStore()
    store.put("neg_fused", NEG_DIMS, {"scatter_impl": "three_pass"})
    store.put("lookup_gather", LOOKUP_DIMS, {"rows_per_step": 0})
    store.save()
    assert autotune.resolve("neg_fused", NEG_DIMS, "scatter_impl") == "fused"
    assert autotune.resolve("lookup_gather", LOOKUP_DIMS,
                            "rows_per_step") == 1


def test_cache_invalidated_on_rewrite(tuned_path):
    store = TunedStore()
    store.put("lookup_gather", LOOKUP_DIMS, {"rows_per_step": 2})
    store.save()
    assert autotune.resolve("lookup_gather", LOOKUP_DIMS,
                            "rows_per_step") == 2
    store.put("lookup_gather", LOOKUP_DIMS, {"rows_per_step": 8})
    store.save()
    assert autotune.resolve("lookup_gather", LOOKUP_DIMS,
                            "rows_per_step") == 8


# ---------------------------------------------------------------------------
# measured sweep through the obs layer
# ---------------------------------------------------------------------------

def test_sweep_records_and_persists(tuned_path):
    x = jnp.ones((32, 8), jnp.float32)

    def run_fn(cfg):
        f = jax.jit(lambda x: x * float(cfg["rows_per_step"]))
        return lambda: f(x)

    tracer = Tracer(enabled=True)
    metrics = MetricsRegistry()
    res = autotune.sweep("lookup_gather", {"n": 32, "D": 8, "itemsize": 4},
                         run_fn, top_k=2, iters=2, warmup=0,
                         tracer=tracer, metrics=metrics)
    assert len(res["trials"]) == 2
    assert res["best"]["seconds"] <= res["trials"][-1]["seconds"]
    assert os.path.exists(tuned_path)
    assert any(s.track == "autotune" for s in tracer.spans())
    stored = json.load(open(tuned_path))
    assert res["key"] in stored["entries"]
    # resolve() reads the winner straight back
    assert autotune.resolve(
        "lookup_gather", {"n": 32, "D": 8, "itemsize": 4}, "rows_per_step"
    ) == res["best"]["config"]["rows_per_step"]
