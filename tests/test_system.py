"""End-to-end system tests: the full GR training stack (data → loader →
model → trainer → checkpoint) and the train.py driver."""
import os
import subprocess
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_full_gr_stack_loss_decreases():
    from repro.configs import ARCHS, reduced
    from repro.data.kuairand import preprocess_log
    from repro.data.loader import GRLoader
    from repro.data.synthetic import SyntheticKuaiRand
    from repro.models.model_zoo import get_bundle
    from repro.training.trainer import gr_train_state, make_gr_train_step

    gen = SyntheticKuaiRand(num_users=300, num_items=5000, mean_len=40,
                            max_len=256, seed=1)
    train, test, remap = preprocess_log(gen.log(300))
    assert len(train) > 100 and len(test) == len(train)

    cfg = reduced(ARCHS["fuxi-tiny"]).replace(
        vocab_size=max(len(remap), 16), num_negatives=8, max_seq_len=128)
    b = get_bundle(cfg)
    loader = GRLoader(train, num_devices=2, users_per_device=4,
                      max_seq_len=128, num_negatives=8,
                      num_items=len(remap), strategy="token_realloc")
    key = jax.random.PRNGKey(0)
    state = gr_train_state(b.init_dense(key), b.init_table(key))
    step = jax.jit(make_gr_train_step(
        lambda d, t, bt, **kw: b.loss(d, t, bt, neg_mode="segmented",
                                      neg_segment=64, expansion=2, **kw)))
    losses = []
    for batch in loader.batches(6):
        nb = {k: jnp.asarray(v) for k, v in batch.items() if k != "weights"}
        state, m = step(state, nb)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]
    assert all(np.isfinite(l) for l in losses)


def test_train_driver_cli():
    """launch/train.py runs end to end on CPU (tiny budget)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    with tempfile.TemporaryDirectory() as d:
        cmd = [sys.executable, "-m", "repro.launch.train",
               "--arch", "hstu-tiny", "--steps", "4",
               "--synthetic-users", "200", "--num-items", "3000",
               "--max-seq-len", "64", "--users-per-device", "2",
               "--num-negatives", "8", "--log-every", "2",
               "--ckpt-dir", d, "--ckpt-every", "2"]
        env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(d, "jax_cache")
        proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                              timeout=600)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "[done]" in proc.stdout
        assert os.path.exists(os.path.join(d, "LATEST"))


@pytest.mark.parametrize("from_env", [False, True])
def test_compile_cache_placement(from_env):
    """Entry points keep JAX's compilation cache at one fixed path in the
    checkout, unless JAX_COMPILATION_CACHE_DIR places it; then JAX's own
    reading of the variable governs and no code overrides it."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    with tempfile.TemporaryDirectory() as d:
        if from_env:
            env["JAX_COMPILATION_CACHE_DIR"] = d
        prog = ("import jax\n"
                "from repro.launch.compile_cache import (CACHE_DIR,\n"
                "    use_repo_compile_cache)\n"
                "used = use_repo_compile_cache()\n"
                "print(used, jax.config.jax_compilation_cache_dir, CACHE_DIR)")
        proc = subprocess.run([sys.executable, "-c", prog], env=env,
                              capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr
        used, configured, repo_dir = proc.stdout.split()
        want = d if from_env else repo_dir
        assert used == configured == want
        assert repo_dir == os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".jax_cache")


@pytest.mark.slow_spmd
def test_dryrun_single_cell_small_mesh():
    """The dry-run machinery itself (build → lower → compile → roofline) on
    an 8-device mesh via subprocess."""
    from spmd_util import run_spmd
    out = run_spmd("""
        import json
        import jax, jax.numpy as jnp
        from repro.configs import ARCHS, reduced, get_arch
        from repro.configs.shapes import ShapeConfig
        from repro.core.sharding import shard_ctx
        from repro.launch import partition as PT
        from repro.launch import roofline as RL
        from repro.models.model_zoo import get_bundle
        from repro.training.trainer import lm_train_state, make_lm_train_step

        cfg = reduced(ARCHS["internlm2-20b"])
        shape = ShapeConfig("t", 64, 8, "train")
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        plan = PT.make_plan(cfg, shape, mesh)
        b = get_bundle(cfg)
        key = jax.random.PRNGKey(0)
        state_sds = jax.eval_shape(lambda: lm_train_state(b.init(key)))
        pspecs = PT.lm_param_specs(state_sds.params, mesh, plan)
        sspecs = PT.state_specs(pspecs, mesh)
        batch = {"tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((8, 64), jnp.int32)}
        step = make_lm_train_step(lambda p, bt: b.loss(p, bt, q_block=32),
                                  num_microbatches=plan.num_microbatches)
        from jax.sharding import PartitionSpec as P
        bspecs = {"tokens": P("data", None), "labels": P("data", None)}
        with shard_ctx(mesh, plan.rules):
            j = jax.jit(step, in_shardings=(PT.to_named(mesh, sspecs),
                                            PT.to_named(mesh, bspecs)))
            compiled = j.lower(state_sds, batch).compile()
        cost = RL.cost_dict(compiled)
        rl = RL.analyze(cfg, shape, "test2x4", mesh.size, cost,
                        compiled.as_text())
        print(json.dumps({"flops": rl.hlo_flops, "bytes": rl.hlo_bytes,
                          "dominant": rl.dominant,
                          "mem": int(compiled.memory_analysis()
                                     .temp_size_in_bytes)}))
    """, devices=8, timeout=900)
    assert out["flops"] > 0 and out["bytes"] > 0
    assert out["dominant"] in ("compute", "memory", "collective")
