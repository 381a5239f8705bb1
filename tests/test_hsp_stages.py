"""The kernels of a multi-chip HSP step, each on its chip's shard, against
the same kernels over every shard on one device.

Each check runs on a (data 2, model 2) mesh of four host devices (a
subprocess: the device count is fixed when JAX starts) through the
``shard_map`` a chip compiles: the Pallas kernels in interpret mode where
the CPU can run them, else their XLA twins under the same ``shard_map``.
"""
import pytest

from spmd_util import run_spmd

KERNELS = """
import json
import jax, jax.numpy as jnp, numpy as np
from functools import partial
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.configs.base import ArchConfig, RABConfig
from repro.core import hsp as H
from repro.core import negative_sampling as NS
from repro.embedding import tables as ET
from repro.kernels.jagged_attention import ops as A
from repro.kernels.jagged_lookup import kernel as LK
from repro.models import gr as GR

mesh = Mesh(np.array(jax.devices()).reshape(2, 2), ("data", "model"))
hsp = H.make_hsp_lookup(mesh, compute_dtype=jnp.float32)
ALL, BATCH, ROWS = ("data", "model"), P(("data", "model")), P("model", None)
on_chips = partial(jax.shard_map, mesh=mesh, check_vma=False)
err = lambda a, b: float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30))
out = {}

# -- attention: the dense model of each shard on its chip ------------------
cfg = ArchConfig(name="t", family="gr", num_layers=2, d_model=64,
                 num_heads=2, num_kv_heads=2, head_dim=16, qkv_dim=16,
                 d_ff=0, vocab_size=64, gr=True, gr_block="hstu",
                 rab=RABConfig(num_pos_buckets=16, num_time_buckets=8,
                               time_bucket_scale=0.301),
                 max_seq_len=32, dtype="float32")
G, T = 4, 64
k = jax.random.split(jax.random.PRNGKey(0), 4)
params = GR.init_gr(k[0], cfg)
x = jax.random.normal(k[1], (G, T, 64), jnp.float32)
offsets = jnp.asarray([[0, 20, 52, 60, 60], [0, 32, 64, 64, 64],
                       [0, 5, 10, 40, 50], [0, 31, 33, 33, 33]], jnp.int32)
ts = jnp.cumsum(jax.random.randint(k[2], (G, T), 0, 100), axis=1)
w = jax.random.normal(k[3], (G, T, 64), jnp.float32)
attn = A.make_attn_fn(block=16, max_row_len=32, interpret=True)


def hidden_loss(p, x, o, t, w):
    return jnp.sum(GR.gr_hidden_sharded(p, cfg, x, o, t, attn_fn=attn) * w)


def chip(p, x, o, t, w):
    l, (gp, gx) = jax.value_and_grad(hidden_loss, (0, 1))(p, x, o, t, w)
    return jax.lax.psum(l, ALL), jax.lax.psum(gp, ALL), gx


rep = jax.tree.map(lambda _: P(), params)
l4, gp4, gx4 = jax.jit(on_chips(chip, in_specs=(rep, BATCH, BATCH, BATCH,
                                                BATCH),
                                out_specs=(P(), rep, BATCH)))(
    params, x, offsets, ts, w)
l1, (gp1, gx1) = jax.jit(jax.value_and_grad(hidden_loss, (0, 1)))(
    params, x, offsets, ts, w)
out["attention"] = max([err(l4, l1), err(gx4, gx1)] +
                       [err(a, b) for a, b in zip(jax.tree.leaves(gp4),
                                                  jax.tree.leaves(gp1))])

# -- negatives: owner-computes on each chip against the whole batch ---------
V, D, R, TL = 64, 256, 8, 32
k = jax.random.split(jax.random.PRNGKey(1), 5)
h = jax.random.normal(k[0], (G * TL, D), jnp.float32)
pe = jax.random.normal(k[1], (G * TL, D), jnp.float32)
table = 0.3 * jax.random.normal(k[2], (V, D), jnp.float32)
shadow = ET.shadow_of(table, jnp.bfloat16)
ids = jax.random.randint(k[3], (G * TL, R), 0, V)
valid = jax.random.uniform(k[4], (G * TL,)) < 0.8


def neg_loss(h, pe, t, ids, valid, shadow, impl, hsp=None):
    return NS.fused_sampled_softmax_loss(
        h, pe, t, ids, valid=valid, segment=16, shadow=shadow, impl=impl,
        interpret=True, hsp=hsp)


for impl in ("pallas", "xla"):
    def chip(h, pe, t, ids, valid, shadow):
        l, g = jax.value_and_grad(neg_loss, (0, 1, 2))(
            h, pe, t, ids, valid, shadow, impl, hsp)
        return (jax.lax.psum(l, ALL), g[0], g[1],
                jax.lax.psum(g[2], "data"))

    got = jax.jit(on_chips(chip, in_specs=(BATCH, BATCH, ROWS, BATCH, BATCH,
                                           ROWS),
                           out_specs=(P(), BATCH, BATCH, ROWS)))(
        h, pe, table, ids, valid, shadow)
    l1, g1 = jax.jit(jax.value_and_grad(neg_loss, (0, 1, 2)),
                     static_argnums=6)(h, pe, table, ids, valid, shadow, impl)
    out[f"negatives_{impl}"] = {
        "loss": err(got[0], l1), "query": err(got[1], g1[0]),
        "positive": err(got[2], g1[1]), "table": err(got[3], g1[2])}

# -- the sorted scatter: the walk stops after the slots that land -----------
LK.SCATTER_SLOTS_PER_CALL = 64
n, vocab, live = 256, 40, 100
k = jax.random.split(jax.random.PRNGKey(2), 3)
o = jax.random.normal(k[0], (16, 128), jnp.float32)
sids = jnp.concatenate([jnp.sort(jax.random.randint(k[1], (live,), 0, vocab)),
                        jnp.full((n - live,), vocab, jnp.int32)])
# dropped slots keep a weight here, so that the drop sink (row vocab) shows
# which of them the walk visited: the calls up to slot 128, not all 256
ws = jax.random.normal(k[2], (n,), jnp.float32)
src = jnp.arange(n, dtype=jnp.int32) % 16
whole = LK.weighted_runsum_scatter(o, ws, sids, src, vocab, interpret=True)
part = LK.weighted_runsum_scatter(o, ws, sids, src, vocab,
                                  live=jnp.int32(live), interpret=True)
sink = jnp.sum(ws[live:128, None] * o[src[live:128]], axis=0)
out["scatter"] = err(part[:vocab], whole[:vocab])
out["scatter_sink"] = err(part[vocab], sink)
out["scatter_sink_whole"] = err(whole[vocab], sink)
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def kernels():
    return run_spmd(KERNELS, devices=4, timeout=600)


def test_attention_on_each_chips_shard_matches_one_device(kernels):
    # fp32 throughout; the dense gradients sum over chips in another order
    assert kernels["attention"] < 5e-5, kernels


@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("part", ["loss", "query", "positive", "table"])
def test_owner_computed_negatives_match_one_device(kernels, impl, part):
    # each chip scores the group's tokens against the rows it holds; the
    # partial logsumexps, the query gradients and the rows' gradients sum
    # to the one-device result
    assert kernels[f"negatives_{impl}"][part] < 1e-5, kernels


def test_sparse_scatter_walks_only_the_slots_that_land(kernels):
    assert kernels["scatter"] == 0.0, kernels
    assert kernels["scatter_sink"] < 1e-5, kernels
    assert kernels["scatter_sink_whole"] > 0.1, kernels
