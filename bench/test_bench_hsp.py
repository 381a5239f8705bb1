"""The HSP cell's program on a (data 2, model 2) mesh of four host devices:
the engine's per-chip stages against the cell's reference
(``reference_hsp.py``), the table kept sharded in the compiled programs,
the groups' tables equal, the exchange's byte counter against the
collectives the programs hold, no read-back of a placed batch, and a
planted fault (the exchange between groups left out) that the comparison
catches. And the collective readers on a made-up trace."""
import json
import os
import subprocess
import sys

import pytest

import _collectives
import compare
import harness
import xplane
from tiny_cell import BENCH, ROOT, TINY, make_root

SEED = 2 ** 31 + 23
MESH = {"data": 2, "model": 2}

PRELUDE = """
import json, os, sys, time
sys.path[:0] = @PATHS@
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
import harness
harness.require_chips = lambda chips: jax.devices()[:chips]
harness.use_compile_cache = lambda root: None
harness.device_peak = lambda b, k: {"bf16_flops": 1e12,
                                     "hbm_bytes_per_s": 1e11}
ROOT, SEED = @ROOT@, @SEED@
"""

ENGINE = PRELUDE + """
from jax._src.array import ArrayImpl
from repro.core import hsp as H
from repro.obs import KERNEL_METRICS
from repro.training import optim as O
from repro.training import trainer as TR

# every array the feed places, kept alive so that ids stay theirs, and
# every read of one back to the host
placed, reads = [], []
feed_call = harness.Feed.__call__


def feed(self, i):
    batch = feed_call(self, i)
    if self.place is not None:
        placed.extend(batch.values())
    return batch


def watch(name, real):
    def read(self, *a, **k):
        if any(self is x for x in placed):
            reads.append(name)
        return real(self, *a, **k)
    return read


harness.Feed.__call__ = feed
ArrayImpl.__array__ = watch("__array__", ArrayImpl.__array__)
ArrayImpl.copy_to_host_async = watch("copy_to_host_async",
                                     ArrayImpl.copy_to_host_async)
ArrayImpl._value = property(watch("_value", ArrayImpl._value.fget))

COLLECTIVES = {"all_gather", "reduce_scatter", "psum", "psum_invariant"}


def jaxprs(params):
    for v in params.values():
        for x in (v if isinstance(v, (list, tuple)) else [v]):
            if isinstance(x, jax.extend.core.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, jax.extend.core.Jaxpr):
                yield x


def exchange_bytes(jaxpr, mesh, out):
    # each collective's bytes a chip, by the phase its scope names
    for e in jaxpr.eqns:
        name = e.primitive.name
        if name in COLLECTIVES:
            axes = e.params.get("axis_name", e.params.get("axes"))
            axes = axes if isinstance(axes, tuple) else (axes,)
            n = int(np.prod([mesh.shape[a] for a in axes]))
            size = sum(int(np.prod(v.aval.shape)) * v.aval.dtype.itemsize
                       for v in e.invars)
            share = {"all_gather": n - 1, "reduce_scatter": (n - 1) / n
                     }.get(name, 2 * (n - 1) / n)
            stack = str(e.source_info.name_stack)
            for p in H.PHASES:
                if f"hsp_{p}" in stack:
                    out[p] += share * size
        for sub in jaxprs(e.params):
            exchange_bytes(sub, mesh, out)


seen = {}
checked = harness.Program.checked_steps


def checked_and_seen(self):
    got = checked(self)
    eng, mesh = self.engine, self.mesh
    st = eng.state
    # the groups' tables: model shard m of data group 0 against group 1
    same = True
    for leaf in jax.tree.leaves(st.table):
        by = {}
        for s in leaf.addressable_shards:
            by.setdefault(s.index, []).append(np.asarray(s.data))
        same &= all(len(v) == 2 and np.array_equal(v[0], v[1])
                    for v in by.values())
    seen["groups_equal"] = bool(same)
    # the compiled programs on the mesh
    host = self.feed.steps[0][0]
    batch = self.feed.place(host)
    V, D = st.table.master.shape
    slots = st.pending_ids.shape[0]
    whole = [f"[{V},{D}]", f"[{V},{D // 256},128]"]
    texts = {}
    c = eng._j_dense.lower(st.dense, st.table, batch, None,
                           st.table.master).compile()
    texts["dense_fwd_bwd"] = c.as_text()
    dout = eng._j_dense(st.dense, st.table, batch, None, st.table.master)
    c = eng._j_emb_bwd.lower(st.dense, st.dense_opt, st.table, dout, batch,
                             None, None, apply_sparse=True,
                             slots=slots).compile()
    texts["emb_bwd"] = c.as_text()
    seen["table_out_over_model"] = all(
        s.is_equivalent_to(NamedSharding(mesh, P("model", None)), 2)
        for s in jax.tree.leaves(c.output_shardings[2]))
    c = eng._j_sparse_apply.lower(st.table, st.pending_ids,
                                  st.pending_rows).compile()
    texts["sparse_apply"] = c.as_text()
    seen["table_out_over_model"] &= all(
        s.is_equivalent_to(NamedSharding(mesh, P("model", None)), 2)
        for s in jax.tree.leaves(c.output_shardings))
    # the landing on each chip's rows against the same landing on one
    # device, and against the sorted run sum and scatter of the same pairs
    one = jax.devices()[0]
    on_one = lambda t: jax.tree.map(
        lambda x: jax.device_put(np.asarray(x), one), t)
    lr = self.model["training"]["lr_sparse"]
    pairs = on_one((st.table, st.pending_ids, st.pending_rows))
    aligned = jax.jit(lambda t, i, r: O.adagrad_sparse_update(
        t, i, r, lr=lr, aligned=True))(*pairs)
    want = O.adagrad_sparse_update(*pairs, lr=lr)
    landed = eng._j_sparse_apply(st.table, st.pending_ids, st.pending_rows)
    seen["landing_bits"] = [bool(np.array_equal(np.asarray(a), np.asarray(b)))
                            for a, b in zip(landed, aligned)]
    # the compiler may fuse the fp32 steps differently (an fma): against
    # the step's own size, rounding and not a missed pair
    seen["landing_gaps"] = [float(
        np.linalg.norm(np.asarray(a) - np.asarray(b))
        / np.linalg.norm(np.asarray(b) - np.asarray(c)))
        for a, b, c in ((landed.master, want.master, st.table.master),
                        (landed.accum, want.accum, st.table.accum))]
    seen["rows_pending"] = int(np.any(np.asarray(st.pending_rows) != 0,
                                      axis=1).sum())
    seen["whole_table"] = {k: [w for w in whole if w in t]
                            for k, t in texts.items()}
    seen["collectives"] = {k: sum(t.count(op) for op in
                                   ("all-gather", "reduce-scatter",
                                    "all-reduce"))
                            for k, t in texts.items()}
    # the counter against the collectives of the dense stage's trace
    counted = {p: 0.0 for p in H.PHASES}
    exchange_bytes(jax.make_jaxpr(eng.stages.dense_fwd_bwd)(
        st.dense, st.table, batch, None, st.table.master).jaxpr, mesh,
        counted)
    seen["counted"] = counted
    seen["gauge"] = {p: KERNEL_METRICS.gauge(
        "hsp_exchange_bytes_per_step", labels={"phase": p}).value
        for p in H.PHASES}
    # the rows each chip lands (those with a gradient) against the
    # candidates of the host's sort
    _, _, _, p_ids, p_rows = eng._j_emb_bwd(
        st.dense, st.dense_opt, st.table, dout, batch, None, None,
        apply_sparse=False, slots=slots)
    s, first, _ = TR.host_unique_candidates(host, V)
    gt = np.asarray(dout.grad_table)
    c_ids, c_rows = TR._table_grad_pairs(jnp.asarray(gt), None, V,
                                         jnp.asarray(s), jnp.asarray(first),
                                         slots)
    c_ids, c_rows = np.asarray(c_ids), np.asarray(c_rows)
    keep = (c_ids >= 0) & np.any(c_rows != 0, axis=1)
    p_ids, p_rows = np.asarray(p_ids), np.asarray(p_rows)
    mine = (p_ids >= 0) & np.any(p_rows != 0, axis=1)
    order = np.argsort(p_ids[mine])
    seen["pairs_ids_equal"] = bool(np.array_equal(
        p_ids[mine][order], c_ids[keep]))
    seen["pairs_rows_equal"] = bool(np.array_equal(
        p_rows[mine][order], c_rows[keep]))
    seen["candidates_without_gradient"] = int(
        ((c_ids >= 0) & ~np.any(c_rows != 0, axis=1)).sum())
    return got


harness.Program.checked_steps = checked_and_seen
out = harness.execute(ROOT, "tiny.mix", SEED, 0.3, False,
                      t_start=time.perf_counter())
print(json.dumps(dict(seen, correct=out["correct"], checks=out["checks"],
                      reads=reads, placed=len(placed))))
"""

NO_DATA_EXCHANGE = PRELUDE + """
from repro.core import hsp as H
real = H.psum


def group_only(tree, axes, phase):
    # the planted fault: the table gradient is not summed over the groups
    return tree if tuple(axes) == ("data",) else real(tree, axes, phase)


H.psum = group_only
seen = {}
checked = harness.Program.checked_steps


def checked_and_seen(self):
    got = checked(self)
    by = {}
    for s in self.engine.state.table.master.addressable_shards:
        by.setdefault(s.index, []).append(np.asarray(s.data))
    seen["groups_equal"] = all(np.array_equal(*v) for v in by.values())
    return got


harness.Program.checked_steps = checked_and_seen
out = harness.execute(ROOT, "tiny.mix", SEED, 0.3, False,
                      t_start=time.perf_counter())
print(json.dumps(dict(seen, correct=out["correct"], checks=out["checks"])))
"""


def _run(body, root):
    paths = [os.path.join(ROOT, "src"), BENCH, os.path.join(BENCH, "metrics")]
    src = body.replace("@PATHS@", repr(paths)).replace(
        "@ROOT@", repr(str(root))).replace("@SEED@", str(SEED))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", src], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def _root(path):
    # 2048 rows: no other array of the tiny step has the table's shape
    return make_root(path, dict(TINY, mesh=MESH, reference="reference_hsp",
                                vocab_size=2048), chips=4)


@pytest.fixture(scope="module")
def engine(tmp_path_factory):
    root = _root(tmp_path_factory.mktemp("hsp"))
    return root, _run(ENGINE, root)


def test_engine_on_the_mesh_matches_the_hsp_reference(engine):
    root, got = engine
    assert got["correct"] is True, got["checks"]
    ok, _ = compare.judge({k: c["value"] for k, c in got["checks"].items()},
                          harness.load_cell(root, "tiny.mix").limits)
    assert ok


def test_both_groups_hold_the_same_table_after_the_checked_steps(engine):
    assert engine[1]["groups_equal"]


@pytest.mark.parametrize("program", ["dense_fwd_bwd", "emb_bwd",
                                     "sparse_apply"])
def test_no_program_holds_the_whole_table_on_one_device(engine, program):
    got = engine[1]
    assert got["whole_table"][program] == [], got["whole_table"]
    assert got["table_out_over_model"]
    # the exchange happens in the dense stage only
    assert (got["collectives"][program] > 0) == (program == "dense_fwd_bwd")


@pytest.mark.parametrize("phase", ["lookup", "neg", "grad_exchange"])
def test_exchange_counter_is_the_collectives_bytes(engine, phase):
    got = engine[1]
    assert got["gauge"][phase] > 0
    assert got["gauge"][phase] == pytest.approx(got["counted"][phase],
                                                rel=1e-9)


def test_landing_on_each_chips_rows_matches_one_device(engine):
    # AdaGrad over each chip's rows: the bits of the same landing on one
    # device, and within rounding of the sorted run sum and scatter of the
    # same pairs (bitwise where nothing is fused: test_shadow_table.py)
    got = engine[1]
    assert got["rows_pending"] > 0
    assert got["landing_bits"] == [True, True, True]
    assert max(got["landing_gaps"]) < 1e-5, got["landing_gaps"]


def test_engine_on_the_mesh_reads_no_placed_batch_back(engine):
    got = engine[1]
    assert got["placed"] > 0
    assert got["reads"] == []


def test_chip_pairs_are_the_host_candidates_that_have_a_gradient(engine):
    got = engine[1]
    assert got["pairs_ids_equal"] and got["pairs_rows_equal"]


def test_leaving_out_the_exchange_between_groups_fails_the_cell(tmp_path):
    root = _root(tmp_path)
    got = _run(NO_DATA_EXCHANGE, root)
    assert not got["groups_equal"]
    ok, _ = compare.judge({k: c["value"] for k, c in got["checks"].items()},
                          harness.load_cell(root, "tiny.mix").limits)
    assert not ok and got["correct"] is False


# -- the collective readers ------------------------------------------------

def _plane(ops, async_ops=(), steps=((0, 50), (50, 50))):
    return {"name": "/device:TPU:0",
            "lines": [{"name": "XLA Modules",
                       "events": [[f"jit_dense_fwd_bwd({i})", s, d]
                                  for i, (s, d) in enumerate(steps)]},
                      {"name": "XLA Ops", "events": [list(e) for e in ops]},
                      {"name": "Async XLA Ops",
                       "events": [list(e) for e in async_ops]}]}


def test_collective_readers_on_a_made_up_trace():
    # window [0, 100); two steps. An all-reduce hidden under a fusion, an
    # all-gather with nothing beside it, an asynchronous reduce-scatter
    # half under compute, and a while loop whose span holds them all.
    ops = [("while.1", 0, 100), ("fusion.3", 0, 30),
           ("psum.2", 10, 10), ("all_gather.7", 40, 10),
           ("reduce-scatter-start.1", 60, 1), ("fusion.4", 60, 15),
           ("reduce-scatter-done.1", 79, 1)]
    plane = _plane(ops, [("reduce-scatter-start.1", 60, 20)])
    # chip 1: an all-reduce in each step; chip 2 holds the ops of one
    # step only (the profiler dropped the rest) and is not read
    other = _plane([("fusion.5", 0, 10), ("all-reduce.1", 10, 10),
                    ("fusion.6", 50, 10), ("all-reduce.2", 60, 10)])
    short = _plane([("all-reduce.3", 0, 40)])
    run = harness.Run(model={}, mix={}, chips=3, peak={},
                      steps=[{}, {}], planes=[plane, other, short],
                      trace_window=(0.0, 100.0))
    read = lambda name: harness.metric_reader(BENCH, name).read(run)
    assert [_collectives.steps_held(p, run.trace_window)
            for p in run.planes] == [2, 2, 1]
    # chip 0: 10 + 10 + 20 ns of collectives, 10 + 5 of them exposed;
    # chip 1: 20 ns, all exposed: the mean over two chips, per step of two
    assert read("collective_ms_per_step") == pytest.approx(
        (40 + 20) / 2 / 2 * 1e-6)
    assert read("collective_exposed_ms_per_step") == pytest.approx(
        (15 + 20) / 2 / 2 * 1e-6)
    quiet = harness.Run(model={}, mix={}, chips=1, peak={}, steps=[{}],
                        planes=[_plane([("fusion.1", 0, 50)])],
                        trace_window=(0.0, 100.0))
    assert harness.metric_reader(BENCH, "collective_ms_per_step").read(
        quiet) is None
    assert harness.metric_reader(
        BENCH, "collective_exposed_ms_per_step").read(quiet) is None
    assert xplane.base("all-gather-start.3") == "all-gather-start"
    assert [n for n in ("all-reduce-done.1", "reduce_scatter.4", "psum.9",
                        "ppermute.2", "all-to-all.1", "fusion.5",
                        "copy-start.2", "region.1")
            if _collectives.is_collective(n)] == [
        "all-reduce-done.1", "reduce_scatter.4", "psum.9", "ppermute.2",
        "all-to-all.1"]
