"""§4.2.1 HSP — sparse exchange correctness + Eq. 1 AdaGrad state identity.

Multi-device parts run in subprocesses (8 fake host devices); the pure
unique-accumulate parts are hypothesis property tests in-process.
"""
import numpy as np
import pytest
pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from spmd_util import run_spmd


@settings(max_examples=30, deadline=None)
@given(ids=st.lists(st.integers(-1, 20), min_size=1, max_size=64))
def test_unique_accumulate_property(ids):
    import jax.numpy as jnp
    from repro.core.hsp import unique_accumulate
    rng = np.random.default_rng(0)
    rows = rng.normal(size=(len(ids), 3)).astype(np.float32)
    uids, urows = unique_accumulate(jnp.asarray(ids, jnp.int32),
                                    jnp.asarray(rows))
    uids, urows = np.asarray(uids), np.asarray(urows)
    want = {}
    for i, r in zip(ids, rows):
        if i >= 0:
            want[i] = want.get(i, 0) + r
    got = {int(i): urows[k] for k, i in enumerate(uids) if i >= 0}
    assert set(got) == set(want)
    for i in want:
        np.testing.assert_allclose(got[i], want[i], rtol=1e-5, atol=1e-5)


def test_hsp_lookup_fwd_bwd_vs_dense():
    out = run_spmd("""
        import json, jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.core.hsp import make_hsp_lookup
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        V, d = 64, 8
        table = jax.random.normal(jax.random.PRNGKey(0), (V, d), jnp.float32)
        ids = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, V)
        lookup = make_hsp_lookup(mesh, group_axes=("model",),
                                 dp_axes=("data",),
                                 compute_dtype=jnp.float32)
        ts = jax.device_put(table, NamedSharding(mesh, P("model", None)))
        is_ = jax.device_put(ids, NamedSharding(mesh, P(("data", "model"))))
        emb = jax.jit(lookup)(ts, is_)
        ref = jnp.take(table, ids, axis=0)
        fwd_ok = bool(np.allclose(np.asarray(emb), np.asarray(ref), atol=1e-5))
        g = jax.jit(jax.grad(lambda t, i: jnp.sum(jnp.sin(lookup(t, i)))))(ts, is_)
        gr = jax.grad(lambda t: jnp.sum(jnp.sin(jnp.take(t, ids, axis=0))))(table)
        bwd_ok = bool(np.allclose(np.asarray(g), np.asarray(gr), atol=1e-4))
        print(json.dumps({"fwd_ok": fwd_ok, "bwd_ok": bwd_ok}))
    """)
    assert out["fwd_ok"] and out["bwd_ok"]


def test_hsp_global_baseline_lookup():
    """Baseline = table sharded over ALL axes; lookup must still be exact."""
    out = run_spmd("""
        import json, jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.core.hsp import make_hsp_lookup
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        V, d = 64, 8
        table = jax.random.normal(jax.random.PRNGKey(0), (V, d), jnp.float32)
        ids = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, V)
        lookup = make_hsp_lookup(mesh, group_axes=("data", "model"),
                                 dp_axes=(), compute_dtype=jnp.float32)
        ts = jax.device_put(table, NamedSharding(mesh, P(("data","model"), None)))
        is_ = jax.device_put(ids, NamedSharding(mesh, P(("data", "model"))))
        emb = jax.jit(lookup)(ts, is_)
        ref = jnp.take(table, ids, axis=0)
        print(json.dumps({"ok": bool(np.allclose(np.asarray(emb),
                                                 np.asarray(ref), atol=1e-5))}))
    """)
    assert out["ok"]


def test_adagrad_state_identity_across_groups():
    """Eq. 1: with the sparse exchange every group receives the identical
    aggregate G_t, so per-group AdaGrad accumulators stay bitwise equal and
    match centralized training."""
    out = run_spmd("""
        import json, jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.core.hsp import make_hsp_lookup, adagrad_update
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        V, d, lr = 32, 4, 0.1
        table0 = jax.random.normal(jax.random.PRNGKey(0), (V, d), jnp.float32)
        lookup = make_hsp_lookup(mesh, group_axes=("model",),
                                 dp_axes=("data",), compute_dtype=jnp.float32)

        def step(table, accum, ids, target):
            def loss(t):
                e = lookup(t, ids)
                return jnp.mean((e - target) ** 2)
            g = jax.grad(loss)(table)
            return adagrad_update(table, accum, g, lr)

        def step_ref(table, accum, ids, target):
            def loss(t):
                e = jnp.take(t, ids, axis=0)
                return jnp.mean((e - target) ** 2)
            g = jax.grad(loss)(table)
            return adagrad_update(table, accum, g, lr)

        ts = jax.device_put(table0, NamedSharding(mesh, P("model", None)))
        acc = jnp.zeros_like(table0)
        acc_s = jax.device_put(acc, NamedSharding(mesh, P("model", None)))
        tr, ar = table0, acc
        jstep = jax.jit(step)
        for t in range(4):
            ids = jax.random.randint(jax.random.PRNGKey(t), (8, 16), 0, V)
            tgt = jax.random.normal(jax.random.PRNGKey(100 + t),
                                    (8, 16, d), jnp.float32)
            ids_s = jax.device_put(ids, NamedSharding(mesh, P(("data","model"))))
            ts, acc_s = jstep(ts, acc_s, ids_s, tgt)
            tr, ar = step_ref(tr, ar, ids, tgt)
        w_ok = bool(np.allclose(np.asarray(ts), np.asarray(tr), atol=1e-5))
        s_ok = bool(np.allclose(np.asarray(acc_s), np.asarray(ar), atol=1e-5))
        print(json.dumps({"w_ok": w_ok, "s_ok": s_ok}))
    """)
    assert out["w_ok"], "HSP weights diverged from centralized training"
    assert out["s_ok"], "AdaGrad states diverged (Eq. 1 violated)"


def test_hsp_collective_scale_reduction():
    """HSP confines the lookup exchange to the model axis: its HLO must
    contain strictly fewer collective bytes than the global baseline."""
    out = run_spmd("""
        import json, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.core.hsp import make_hsp_lookup
        from repro.launch.hlo_analysis import analyze_text
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        V, d = 1024, 64
        ids_sds = jax.ShapeDtypeStruct((8, 128), jnp.int32)
        tbl_sds = jax.ShapeDtypeStruct((V, d), jnp.float32)

        def bytes_for(group_axes, dp_axes, tspec):
            lookup = make_hsp_lookup(mesh, group_axes=group_axes,
                                     dp_axes=dp_axes,
                                     compute_dtype=jnp.float32)
            f = lambda t, i: jnp.sum(lookup(t, i) ** 2)
            j = jax.jit(jax.grad(f), in_shardings=(
                NamedSharding(mesh, tspec),
                NamedSharding(mesh, P(("data", "model")))))
            c = analyze_text(j.lower(tbl_sds, ids_sds).compile().as_text())
            return sum(c.coll_bytes.values())

        hsp = bytes_for(("model",), ("data",), P("model", None))
        glob = bytes_for(("data", "model"), (), P(("data", "model"), None))
        print(json.dumps({"hsp": hsp, "glob": glob}))
    """)
    assert out["hsp"] < out["glob"], out


def test_grad_wire_compression_dtypes():
    """bf16/int8 wire compression (DESIGN §7): grads stay close to exact
    at 2×/4× fewer exchanged bytes."""
    out = run_spmd("""
        import json, jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.core.hsp import make_hsp_lookup
        mesh = jax.make_mesh((2, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        V, d = 64, 16
        table = jax.random.normal(jax.random.PRNGKey(0), (V, d), jnp.float32)
        ids = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, V)
        ts = jax.device_put(table, NamedSharding(mesh, P("model", None)))
        is_ = jax.device_put(ids, NamedSharding(mesh, P(("data","model"))))
        gref = jax.grad(lambda t: jnp.sum(jnp.sin(jnp.take(t, ids, axis=0))))(table)
        errs = {}
        for wire in (jnp.float32, jnp.bfloat16, jnp.int8):
            lk = make_hsp_lookup(mesh, compute_dtype=jnp.float32,
                                 grad_wire_dtype=wire)
            g = jax.jit(jax.grad(lambda t, i: jnp.sum(jnp.sin(lk(t, i)))))(ts, is_)
            errs[wire.__name__] = float(jnp.max(jnp.abs(g - gref))
                                        / (jnp.max(jnp.abs(gref)) + 1e-9))
        print(json.dumps(errs))
    """, devices=4)
    assert out["float32"] < 1e-6
    assert out["bfloat16"] < 0.02
    assert out["int8"] < 0.05
