"""SPMD numerical parity: the sharded model == the single-device model.

The strongest distributed-correctness check we can run without hardware:
an 8-device (2 data × 4 model) mesh with the full partition plan must
produce the same loss and the same updated parameters as one device.
"""
import pytest

from spmd_util import run_spmd


@pytest.mark.slow_spmd
def test_lm_train_step_parity_sharded_vs_single():
    out = run_spmd("""
        import json, jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.configs import ARCHS, reduced
        from repro.configs.shapes import ShapeConfig
        from repro.models.model_zoo import get_bundle
        from repro.models.transformer import init_lm
        from repro.launch import partition as PT
        from repro.core.sharding import shard_ctx
        from repro.training.trainer import lm_train_state, make_lm_train_step

        cfg = reduced(ARCHS["glm4-9b"])
        b = get_bundle(cfg)
        key = jax.random.PRNGKey(0)
        params = init_lm(key, cfg, jnp.float32)
        toks = jax.random.randint(key, (8, 64), 0, cfg.vocab_size)
        batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
        loss_fn = lambda p, bt: b.loss(p, bt, q_block=32)
        step = make_lm_train_step(loss_fn, num_microbatches=2,
                                  weight_decay=0.0)

        # single device
        s0 = lm_train_state(params)
        s0, m0 = jax.jit(step)(s0, batch)

        # sharded
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        shape = ShapeConfig("t", 64, 8, "train")
        plan = PT.make_plan(cfg, shape, mesh)
        pspecs = PT.lm_param_specs(jax.eval_shape(lambda: params), mesh, plan)
        sspecs = PT.state_specs(pspecs, mesh)
        bspecs = {"tokens": P("data", None), "labels": P("data", None)}
        s1 = lm_train_state(params)
        with shard_ctx(mesh, plan.rules):
            jstep = jax.jit(step, in_shardings=(
                PT.to_named(mesh, sspecs), PT.to_named(mesh, bspecs)))
            s1, m1 = jstep(s1, batch)

        dloss = abs(float(m0["loss"]) - float(m1["loss"]))
        dp = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) -
                                       c.astype(jnp.float32))))
                 for a, c in zip(jax.tree.leaves(s0.params),
                                 jax.tree.leaves(s1.params)))
        print(json.dumps({"dloss": dloss, "dparams": dp,
                          "loss": float(m0["loss"])}))
    """, devices=8, timeout=900)
    assert out["dloss"] < 1e-4, out
    assert out["dparams"] < 1e-3, out


@pytest.mark.slow_spmd
def test_moe_arch_parity_sharded_vs_single():
    out = run_spmd("""
        import json, jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.configs import ARCHS, reduced
        from repro.configs.shapes import ShapeConfig
        from repro.models.model_zoo import get_bundle
        from repro.models.transformer import init_lm
        from repro.launch import partition as PT
        from repro.core.sharding import shard_ctx

        cfg = reduced(ARCHS["olmoe-1b-7b"])
        b = get_bundle(cfg)
        key = jax.random.PRNGKey(0)
        params = init_lm(key, cfg, jnp.float32)
        toks = jax.random.randint(key, (4, 64), 0, cfg.vocab_size)
        batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
        loss_fn = lambda p: b.loss(p, batch, q_block=32)
        l0 = float(jax.jit(loss_fn)(params))

        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        shape = ShapeConfig("t", 64, 4, "train")
        plan = PT.make_plan(cfg, shape, mesh)
        pspecs = PT.lm_param_specs(jax.eval_shape(lambda: params), mesh, plan)
        with shard_ctx(mesh, plan.rules):
            l1 = float(jax.jit(loss_fn,
                               in_shardings=(PT.to_named(mesh, pspecs),)
                               )(params))
        print(json.dumps({"l0": l0, "l1": l1}))
    """, devices=8, timeout=900)
    assert abs(out["l0"] - out["l1"]) < 1e-4, out
