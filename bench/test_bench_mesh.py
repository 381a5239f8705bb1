"""A cell on a mesh of chips: the configuration's ``mesh`` checked against
the cell's chips, steps of several shards made from the one-shard stream,
the reference over every shard, the trace readers over every chip, a
configuration's own reference and FLOP count as new files, and the tiny
cell on a (data 2, model 2) mesh of four host devices through the harness's
own run. At one shard every step, reference reading and trace reading is
the one of the single-chip harness, pinned here."""
import hashlib
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import compare
import flops
import harness
import reference
import traffic
import xplane
from tiny_cell import BENCH, ROOT, TINY, TINY_MIX, make_root

SEED = 2 ** 31 + 11
MESH = {"data": 2, "model": 2}


def _steps(count, shards=1, seed=SEED):
    return traffic.make_batches(BENCH, TINY_MIX, TINY, seed, 0, count, shards)


@pytest.mark.parametrize("mesh,chips", [(MESH, 1), ({"data": 1, "model": 2},
                                                    4), ({"data": 2}, 2)])
def test_load_cell_refuses_a_mesh_that_is_not_the_cells(tmp_path, mesh,
                                                        chips):
    root = make_root(tmp_path, dict(TINY, mesh=mesh), chips=chips)
    with pytest.raises(SystemExit) as e:
        harness.load_cell(root, "tiny.mix")
    if len(mesh) == 2:
        n = mesh["data"] * mesh["model"]
        assert f"= {n} chips" in str(e.value)
        assert f"asks for {chips}" in str(e.value)


def test_load_cell_takes_a_mesh_of_the_cells_chips(tmp_path):
    cell = harness.load_cell(make_root(tmp_path, dict(TINY, mesh=MESH),
                                       chips=4), "tiny.mix")
    assert (cell.chips, cell.shards) == (4, 4)
    assert harness.load_cell(make_root(tmp_path / "one"),
                             "tiny.mix").shards == 1


def test_shard_g_of_step_b_is_the_one_shard_streams_step_gb_plus_g():
    one, four = _steps(8), _steps(2, shards=4)
    for b, (batch, lengths) in enumerate(four):
        assert batch["ids"].shape == (4, TINY_MIX["token_budget"])
        assert batch["offsets"].shape == (4, TINY_MIX["max_seqs"] + 1)
        assert batch["neg_ids"].shape == (4, TINY_MIX["token_budget"],
                                          TINY["num_negatives"])
        np.testing.assert_array_equal(batch["rng"], one[4 * b][0]["rng"])
        assert lengths == [n for g in range(4) for n in one[4 * b + g][1]]
        for g in range(4):
            for k in ("ids", "labels", "timestamps", "offsets", "neg_ids"):
                np.testing.assert_array_equal(batch[k][g],
                                              one[4 * b + g][0][k][0])
    # a window's later steps are the stream's too
    np.testing.assert_array_equal(
        traffic.make_batches(BENCH, TINY_MIX, TINY, SEED, 1, 1, 4)[0][0]
        ["neg_ids"], four[1][0]["neg_ids"])


def test_one_shard_steps_are_the_single_chip_harness_steps():
    # digest of the tiny mix's first six steps, taken before the shard
    # count existed
    h = hashlib.sha256()
    for batch, lengths in _steps(6):
        for k in sorted(batch):
            a = np.ascontiguousarray(batch[k])
            h.update(k.encode())
            h.update(str(a.dtype).encode())
            h.update(str(a.shape).encode())
            h.update(a.tobytes())
        h.update(json.dumps([int(x) for x in lengths]).encode())
    assert h.hexdigest() == ("44af69f905fae2c6affaddcfa8c2994e"
                             "c23397d1d80c6f603c5beea019e13f52")


def test_reference_loss_is_the_token_weighted_mean_over_shards():
    dense, table = jax.jit(lambda k: reference.init_weights(TINY, k))(
        jax.random.PRNGKey(3))
    batch = {k: jnp.asarray(v) for k, v in _steps(1, shards=2)[0][0].items()}
    loss = lambda b: float(reference.loss(dense, table, table, b, TINY,
                                          None, False))
    parts = [loss({k: (v if k == "rng" else v[g:g + 1])
                   for k, v in batch.items()}) for g in range(2)]
    n = [int(batch["offsets"][g, -1]) for g in range(2)]
    want = (parts[0] * n[0] + parts[1] * n[1]) / (n[0] + n[1])
    assert loss(batch) == pytest.approx(want, rel=1e-6, abs=0)
    assert parts[0] != pytest.approx(parts[1], rel=1e-3)


def test_reference_at_one_shard_reads_what_it_read_before():
    ref = reference.run(TINY, SEED, [s[0] for s in _steps(3)])
    # float32 readings of the tiny cell, taken before the shard count
    # existed: the same bits
    assert ref["losses"] == [2.1968939304351807, 2.2372982501983643,
                             2.203159809112549]
    assert ref["grad_norms"]["table"] == 3.0289080142974854
    assert ref["grad_norms"]["blocks/w_o"] == 0.7696152925491333
    assert ref["change_norms"]["table"] == 1.2089539766311646
    assert ref["change_norms"]["blocks/w_o"] == 1.4115873575210571


# -- trace readers over every chip ----------------------------------------

RECORDED = os.path.join(BENCH, "data", "trace_hstu_long_2steps.json.gz")
# each reader on the recorded two-step trace of one v5e chip, taken with
# the single-chip readers
BEFORE = {"device_idle_share": 0.37722058667916114,
          "attn_ms_per_step": 148.281521,
          "attn_roofline": 5.648717210768799,
          "neg_ms_per_step": 815.4271285,
          "neg_roofline": 0.6519108367940222,
          "sparse_update_ms_per_step": 341.161146,
          "dense_ms_per_step": 95.730716,
          "train_mfu": 2.0836431932382133}
STEP = [2048] * 4


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(BENCH, "configs", "hstu-large.json")) as f:
        model = json.load(f)
    tr = xplane.load(RECORDED)
    return model, tr, xplane.window(tr)


def _run(recorded, chips):
    model, tr, win = recorded
    plane = xplane.device_planes(tr)[0]
    return harness.Run(model=model, mix={}, chips=chips,
                       peak=harness.device_peak(BENCH, "TPU v5 lite"),
                       steps=[{"lengths": STEP * chips,
                               "tokens": 8192 * chips}] * 2,
                       window_s=(win[1] - win[0]) * 1e-9,
                       planes=[plane] * chips, trace_window=win)


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_readers_on_one_chip_read_what_they_read_before(recorded, name):
    run = _run(recorded, 1)
    assert harness.metric_reader(BENCH, name).read(run) == BEFORE[name]


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_a_second_chip_doing_the_same_leaves_every_reading(recorded, name):
    # the same plane as a second chip, every step with a second shard of
    # the same lengths: per-chip times, shares and utilization unchanged
    run = _run(recorded, 2)
    assert len(run.planes) == 2
    got = harness.metric_reader(BENCH, name).read(run)
    assert got == pytest.approx(BEFORE[name], rel=1e-12)


def test_attach_trace_takes_each_chips_plane_in_device_order(recorded):
    _, tr, _ = recorded
    dev = [p for p in tr["planes"] if p["name"].startswith("/device:")][0]
    other = dict(dev, name="/device:TPU:1")
    two = dict(tr, planes=[tr["planes"][0], other, dev])
    run = harness.Run(model={}, mix={}, chips=2, peak={})
    harness.attach_trace(run, two, 0.0)
    assert [p["name"] for p in run.planes] == ["/device:TPU:0",
                                               "/device:TPU:1"]
    assert run.plane is run.planes[0]
    with pytest.raises(RuntimeError):
        harness.attach_trace(harness.Run(model={}, mix={}, chips=3, peak={}),
                             two, 0.0)


# -- a configuration's own reference and counts ----------------------------

def test_configuration_names_its_own_reference(tmp_path, on_cpu):
    root = make_root(tmp_path, dict(TINY, reference="ref_copy"))
    with open(os.path.join(BENCH, "reference.py")) as f:
        src = f.read()
    # the copy marks that it ran
    src += ("\n\n_run = run\n\n\ndef run(*a, **k):\n"
            "    import os\n"
            "    open(os.path.join(os.path.dirname(__file__), 'used'), 'w')"
            ".close()\n"
            "    return _run(*a, **k)\n")
    with open(os.path.join(root, "bench", "ref_copy.py"), "w") as f:
        f.write(src)
    cell = harness.load_cell(root, "tiny.mix")
    assert harness.reference_module(cell).__file__.endswith("ref_copy.py")
    out = harness.execute(root, "tiny.mix", SEED, 0.3, False,
                          t_start=time.perf_counter())
    assert out["correct"] is True, out["checks"]
    assert os.path.exists(os.path.join(root, "bench", "used"))
    with pytest.raises(FileNotFoundError):
        harness.reference_module(harness.load_cell(
            make_root(tmp_path / "none", dict(TINY, reference="nothing")),
            "tiny.mix"))


def test_configuration_names_its_own_counts(tmp_path, monkeypatch):
    monkeypatch.setattr(flops, "BENCH_DIR", str(tmp_path))
    with open(tmp_path / "my_counts.py", "w") as f:
        f.write("def dense_matmul_params(model):\n"
                "    return 7 * model['d_model']\n")
    other = dict(TINY, block="other", counts="my_counts")
    assert flops.dense_matmul_params(other) == 7 * 128
    assert flops.model_flops(other, [4]) == (
        6.0 * 7 * 128 * 4 + 12.0 * 4 * 32 * 10 * 2 + 6.0 * 4 * 9 * 128)
    with pytest.raises(ValueError, match="counts"):
        flops.dense_matmul_params(dict(TINY, block="other"))
    # the two blocks the benchmark knows need no module
    assert flops.dense_matmul_params(dict(TINY, counts="nothing")) == \
        flops.dense_matmul_params(TINY)


# -- the tiny cell on a (data 2, model 2) mesh -----------------------------

MESH_RUN = """
import json, os, sys, time
sys.path[:0] = [{src!r}, {bench!r}, {metrics!r}]
import jax
from jax.sharding import NamedSharding, PartitionSpec as P
import harness
harness.require_chips = lambda chips: jax.devices()[:chips]
harness.use_compile_cache = lambda root: None
harness.device_peak = lambda b, k: {{"bf16_flops": 1e12,
                                     "hbm_bytes_per_s": 1e11}}
seen = {{}}
checked = harness.Program.checked_steps


def checked_and_seen(self):
    out = checked(self)
    m = self.engine.state.table.master
    seen["table_sharded_over_model"] = m.sharding.is_equivalent_to(
        NamedSharding(self.mesh, P("model", None)), m.ndim)
    seen["devices"] = len(m.sharding.device_set)
    seen["dense_replicated"] = all(
        x.sharding.is_fully_replicated
        for x in jax.tree.leaves(self.engine.state.dense))
    return out


harness.Program.checked_steps = checked_and_seen
out = harness.execute({root!r}, "tiny.mix", {seed}, 0.3, False,
                      t_start=time.perf_counter())
print(json.dumps(dict(seen, correct=out["correct"], checks=out["checks"],
                      attempted=out["attempted"], failed=out["failed"],
                      count=out["device"]["count"],
                      compiles=out["compiles_in_window"])))
"""


def test_tiny_cell_on_a_mesh_of_four_runs_through_the_harness(tmp_path):
    # four host devices exist only in a process that asks for them first
    root = make_root(tmp_path, dict(TINY, mesh=MESH), chips=4)
    body = MESH_RUN.format(src=os.path.join(ROOT, "src"), bench=BENCH,
                           metrics=os.path.join(BENCH, "metrics"), root=root,
                           seed=SEED)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", body], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-4000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["correct"] is True, got["checks"]
    ok, _ = compare.judge({k: c["value"] for k, c in got["checks"].items()},
                          harness.load_cell(root, "tiny.mix").limits)
    assert ok
    assert got["table_sharded_over_model"] and got["devices"] == 4
    assert got["dense_replicated"]
    assert got["count"] == 4 and got["failed"] == 0
    assert got["attempted"] >= 2 and got["compiles"] == 0
