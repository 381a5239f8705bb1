"""The training step's Pallas kernels compile for a TPU v5e chip.

Interpret mode accepts layouts the chip's compiler refuses, so these tests
compile each kernel family of the TPU training path — forward and backward
where it has one — for a described ``v5e:2x2`` topology (no chip needed)
at HSTU-large widths (d_model 1024, 8 heads × 128, R = 128, with the
hstu-tiny widths as a second case), and assert that the compiled HLO holds
the kernel (``tpu_custom_call``). The kernels are compiled alone: the XLA
sorts around them take the chip's compiler seconds more. Nothing runs.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.embedding import tables as ET
from repro.kernels.jagged_attention import kernel as AK
from repro.kernels.jagged_attention import ops as AO
from repro.kernels.jagged_lookup import kernel as LK
from repro.kernels.neg_logits import fused as NF

D_MODEL, HEADS, QKV, R, V = 1024, 8, 128, 128, 200_000


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("H,Dh", [(HEADS, QKV), (HEADS, 16)])  # large, tiny
def test_attention_fwd_bwd_compiles(one_chip, H, Dh):
    cap, block = 2048, 128
    plan = jax.eval_shape(
        lambda o, t: AO.build_attn_plan(o, t, cap, block=block,
                                        max_row_len=cap),
        jax.ShapeDtypeStruct((5,), jnp.int32),
        jax.ShapeDtypeStruct((cap,), jnp.int32))
    kw = dict(block=block, scale=Dh ** -0.5, tb_scale=0.301, use_pos=True,
              use_time=True, interpret=False)

    def step(q, k, v, dy, pt, tt, *fields):
        p = AO.JaggedAttnPlan(*fields)
        out = AK.fwd_pallas_wl(q, k, v, pt, tt, p.meta_i32, p.meta_f32,
                               p.q_wl[:, 0], p.q_wl[:, 1], p.q_flags,
                               p.q_live, p.n_live, **kw)
        grads = AK.bwd_pallas_wl(q, k, v, dy, pt, tt, p.meta_i32,
                                 p.meta_f32, p.q_wl, p.q_flags, p.q_live,
                                 p.kv_wl, p.kv_flags, p.kv_live, p.n_live,
                                 **kw)
        return out, grads

    qkv = ((cap, H, Dh), jnp.bfloat16)
    _compile(one_chip, step, qkv, qkv, qkv, qkv, ((256, H), jnp.float32),
             ((32, H), jnp.float32), *[(f.shape, f.dtype) for f in plan])


def _compile_negatives(one_chip, D, shadow_dtype):
    """The kernels on the gather source training gives them: the shadow as
    ``shadow_of`` stores it (packed words at D_MODEL, an unpacked shadow
    read as master rows at 128), or the fp32 master alone."""
    T, seg = 1024, 128
    n_seg = T // seg
    kw = dict(segment=seg, R=R, expansion=1, tau=1.0, interpret=False)

    def step(out, pos, master, ids, valid, perms, g):
        shadow = (None if shadow_dtype is None
                  else ET.shadow_of(master, shadow_dtype))
        words, fdt = NF.gather_source(master, shadow, jnp.bfloat16)
        lse = NF.fwd_pallas(out, pos, words, ids, valid, perms,
                            fetch_dtype=fdt, **kw)
        return lse, NF.bwd_pallas(out, pos, words, ids, valid, perms, lse,
                                  g, fetch_dtype=fdt, **kw)

    per_seg = ((n_seg, seg), jnp.float32)
    _compile(one_chip, step, ((T, D), jnp.bfloat16), per_seg,
             ((V, D), jnp.float32), ((T * R,), jnp.int32), per_seg,
             ((n_seg, 1, seg), jnp.int32), per_seg)


@pytest.mark.parametrize("D", [D_MODEL, 128])                # large, tiny
def test_fused_negatives_fwd_bwd_on_bf16_shadow_compiles(one_chip, D):
    # D_MODEL gathers packed bf16 rows, 128 fp32 master rows
    _compile_negatives(one_chip, D, jnp.bfloat16)


def test_fused_negatives_fwd_bwd_on_fp32_master_compiles(one_chip):
    _compile_negatives(one_chip, D_MODEL, None)


@pytest.mark.parametrize("T,sparse", [(1024, False),
                                      (16_384, True)])  # one chip, the mesh
def test_weighted_scatter_compiles(one_chip, T, sparse):
    # the mesh's chip walks its group's 16,384 tokens' slots up to ``live``
    _compile(one_chip,
             lambda o, w, s, src, live: LK.weighted_runsum_scatter(
                 o, w, s, src, V, scale=0.5,
                 live=live if sparse else None, interpret=False),
             ((T, D_MODEL), jnp.float32), ((T * R,), jnp.float32),
             ((T * R,), jnp.int32), ((T * R,), jnp.int32), ((), jnp.int32))


def test_weighted_scatter_counters_after_fused_negatives_backward(one_chip):
    from repro.kernels import autotune
    from repro.kernels.neg_logits import fused_recall_lse
    from repro.obs import KERNEL_METRICS
    src_bytes = KERNEL_METRICS.gauge("wscatter_src_bytes_per_slot")
    block = KERNEL_METRICS.gauge("wscatter_slots_per_block")
    src_bytes.set(0)
    block.set(0)
    T = 1024

    def loss(o, pos, table, ids):
        return jnp.sum(fused_recall_lse(o, pos, table, ids, segment=128,
                                        interpret=False))

    jax.jit(jax.grad(loss, argnums=(0, 2))).lower(
        *[jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in
          (((T, D_MODEL), jnp.float32), ((T,), jnp.float32),
           ((V, D_MODEL), jnp.float32), ((T, R), jnp.int32))])
    slots = autotune.wscatter_slots_per_block({"D": D_MODEL, "itemsize": 4})
    assert src_bytes.value == 4 * D_MODEL          # a row, not its tile
    assert block.value == slots and 128 <= slots <= 512


def test_runsum_compiles(one_chip):
    n = 1024 * (R + 2)
    _compile(one_chip, lambda g, i: LK.runsum_pallas(g, i, interpret=False),
             ((n, D_MODEL), jnp.float32), ((n,), jnp.int32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gather_compiles(one_chip, dtype):
    _compile(one_chip, lambda t, i: LK.gather_pallas(t, i, interpret=False),
             ((V, D_MODEL), dtype), ((8192,), jnp.int32))
