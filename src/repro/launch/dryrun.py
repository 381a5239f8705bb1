import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"     # the 512 devices are fake host ones

"""Multi-pod dry-run: .lower().compile() every (arch × shape × mesh) cell.

For each cell this builds the production mesh, the partition plan, the
train/prefill/decode step with full in_shardings, lowers against
ShapeDtypeStruct inputs (no allocation), compiles, and records
``memory_analysis()`` / ``cost_analysis()`` + the roofline terms parsed
from the partitioned HLO.

The environment lines above MUST stay the first statements — jax locks
the platform and device count at first init, and the 512 placeholder
host devices are what lets ``make_production_mesh`` build the 16×16 /
2×16×16 grids.

Usage:
    python -m repro.launch.dryrun --arch glm4-9b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all --mesh both --out results/
"""
import argparse
import gzip
import json
import time
import traceback
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, get_arch
from repro.configs.shapes import SHAPES_BY_NAME, cells_for, shapes_for
from repro.core.hsp import make_hsp_lookup
from repro.core.sharding import shard_ctx
from repro.launch import partition as PT
from repro.launch import roofline as RL
from repro.launch.mesh import make_production_mesh
from repro.models.model_zoo import get_bundle
from repro.training.engine import make_gr_step_fn
from repro.training.trainer import (gr_pending_slots, gr_train_state,
                                    lm_train_state, make_lm_train_step)


def _sharded_bytes(sds_tree: Any, spec_tree: Any, mesh) -> int:
    """Analytic per-device bytes of a sharded pytree."""
    total = 0
    flat_s, _ = jax.tree_util.tree_flatten(
        spec_tree, is_leaf=lambda x: isinstance(x, P))
    flat_t = jax.tree_util.tree_leaves(sds_tree)
    for t, s in zip(flat_t, flat_s):
        n = t.size * jnp.dtype(t.dtype).itemsize
        denom = 1
        for ax in (s or ()):  # each entry: None | str | tuple
            if ax is None:
                continue
            axes = (ax,) if isinstance(ax, str) else ax
            for a in axes:
                denom *= mesh.shape[a]
        total += n // max(denom, 1)
    return total


def build_cell(arch: str, shape_name: str, multi_pod: bool):
    """Returns (jitted_fn, example_args (SDS), state_specs, plan, mesh)."""
    cfg = get_arch(arch)
    shape = SHAPES_BY_NAME[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    plan = PT.make_plan(cfg, shape, mesh)
    bundle = get_bundle(cfg)
    key = jax.random.PRNGKey(0)

    if cfg.gr:
        if plan.neg_expansion > 1:
            # §4.3.3: fetch R/k negatives, recover the full set by sharing
            cfg = cfg.replace(
                num_negatives=cfg.num_negatives // plan.neg_expansion)
            bundle = get_bundle(cfg)
        # layout: "pack" = one big jagged buffer per device; "rows" =
        # row-major padded (one user per shard row) — the XLA-path attention
        # then only computes within-row pairs (§Perf H1)
        num_shards = (mesh.size if plan.gr_layout == "pack"
                      else shape.global_batch)
        inputs = bundle.input_specs(shape, num_shards=num_shards)
        # presize the τ=1 pending pair buffers from the batch spec: with
        # the default 0 slots the sparse-update stage would be statically
        # compiled out and the cost/memory analysis would miss it
        n_pend = gr_pending_slots(inputs["batch"], cfg.vocab_size)
        state_sds = jax.eval_shape(
            lambda: gr_train_state(bundle.init_dense(key),
                                   bundle.init_table(key),
                                   pending_slots=n_pend))
        dspecs = PT.gr_param_specs(state_sds.dense, mesh, plan)
        tspec = PT.gr_table_spec(mesh, plan)
        # shard the τ=1 pending (id, row-grad) pair buffers over the data
        # axes (batch-derived, ROADMAP item) instead of the replicated
        # default; run_cell asserts the spec landed in the report
        sspecs = PT.gr_state_specs(dspecs, tspec,
                                   pend_spec=PT.gr_pend_spec(mesh, n_pend))
        bspecs = PT.batch_specs(cfg, shape, mesh, plan, inputs)["batch"]
        dp = tuple(a for a in ("pod", "data") if a in mesh.shape)
        lookup = make_hsp_lookup(
            mesh, group_axes=("model",) if plan.hsp
            else tuple(mesh.shape.keys()),
            dp_axes=dp if plan.hsp else (),
            compute_dtype=jnp.dtype(cfg.dtype),
            grad_wire_dtype=jnp.dtype(plan.grad_wire_dtype))
        from functools import partial as _partial
        from repro.models.hstu import jagged_pointwise_attention_blocked
        attn_fn = _partial(jagged_pointwise_attention_blocked,
                           block=plan.q_block,
                           score_dtype=jnp.dtype(plan.gr_score_dtype))
        # the engine's staged step: lookup_fn (HSP sparse exchange) keeps
        # the input gather inside the dense stage, so the lowered HLO
        # carries exactly the collectives the plan claims
        step = make_gr_step_fn(
            bundle,
            loss_kwargs=dict(lookup_fn=lookup, neg_mode="segmented",
                             neg_segment=plan.neg_segment,
                             expansion=plan.neg_expansion,
                             attn_fn=attn_fn, remat=plan.remat),
            semi_async=True, jit=False)
        jitted = jax.jit(step, in_shardings=(
            PT.to_named(mesh, sspecs), PT.to_named(mesh, bspecs)))
        args = (state_sds, inputs["batch"])
        arg_specs = (sspecs, bspecs)
        return jitted, args, arg_specs, plan, mesh

    if shape.kind == "train":
        state_sds = jax.eval_shape(
            lambda: lm_train_state(bundle.init(key),
                                   jnp.dtype(plan.opt_dtype)))
        pspecs = PT.lm_param_specs(state_sds.params, mesh, plan)
        sspecs = PT.state_specs(pspecs, mesh)
        inputs = bundle.input_specs(shape)
        bspecs = PT.batch_specs(cfg, shape, mesh, plan, inputs)["batch"]
        loss_fn = lambda p, b: bundle.loss(p, b, q_block=plan.q_block,
                                           remat=plan.remat)
        step = make_lm_train_step(
            loss_fn, num_microbatches=plan.num_microbatches,
            accum_dtype=jnp.dtype(plan.accum_dtype))
        jitted = jax.jit(step, in_shardings=(
            PT.to_named(mesh, sspecs), PT.to_named(mesh, bspecs)))
        return jitted, (state_sds, inputs["batch"]), (sspecs, bspecs), plan, mesh

    params_sds = jax.eval_shape(bundle.init, key)
    pspecs = PT.lm_param_specs(params_sds, mesh, plan)
    inputs = bundle.input_specs(shape)
    ispecs = PT.batch_specs(cfg, shape, mesh, plan, inputs)

    if shape.kind == "prefill":
        fn = lambda p, b: bundle.prefill(p, b, q_block=plan.q_block)
        jitted = jax.jit(fn, in_shardings=(
            PT.to_named(mesh, pspecs), PT.to_named(mesh, ispecs["batch"])))
        return (jitted, (params_sds, inputs["batch"]),
                (pspecs, ispecs["batch"]), plan, mesh)

    # decode
    def fn(p, inp):
        return bundle.decode(p, inp.get("token"), inp["cache"],
                             inp["cache_index"],
                             embeds=inp.get("embeds"))
    jitted = jax.jit(fn, in_shardings=(
        PT.to_named(mesh, pspecs), PT.to_named(mesh, ispecs)))
    return jitted, (params_sds, inputs), (pspecs, ispecs), plan, mesh


def build_serve_cell(arch: str, *, max_users: int = 63,
                     rows_per_tick: int = 8, append_window: int = 4,
                     mesh: Any = None, multi_pod: bool = False,
                     reduce_arch: bool = True) -> Dict[str, Any]:
    """Compile-verify the continuous-serving layout (PR 8): the cold slot
    encode (``gr_encode_slots``), the warm append (``gr_append_slots``),
    and the slot-resident retrieval (``topk_from_slots``) each
    .lower().compile() with the ``partition.gr_serve_specs`` shardings on
    ``mesh`` (default: the production mesh; tests pass a fake 8-device
    mesh). No arrays are allocated — everything lowers against
    ShapeDtypeStructs. Returns the per-program spec strings + memory
    analysis for the report."""
    from repro.configs import get_arch, reduced
    from repro.models import gr as GRM
    from repro.serving.retrieval import topk_from_slots

    cfg = get_arch(arch)
    if not cfg.gr:
        raise ValueError(f"{arch} is not a GR arch")
    if reduce_arch:
        cfg = reduced(cfg)
    if mesh is None:
        mesh = make_production_mesh(multi_pod=multi_pod)
    bundle = get_bundle(cfg)
    key = jax.random.PRNGKey(0)
    dense_sds = jax.eval_shape(bundle.init_dense, key)
    table_sds = jax.eval_shape(bundle.init_table, key)
    S, d = cfg.max_seq_len, cfg.d_model
    dqk = cfg.qkv_dim or cfg.resolved_head_dim
    kv_shape = (cfg.num_layers, cfg.num_heads, dqk, dqk)
    specs = PT.gr_serve_specs(mesh, max_users=max_users, max_seq_len=S,
                              d_model=d, kv_shape=kv_shape,
                              vocab=int(table_sds.shape[0]))
    dspecs = jax.tree.map(lambda l: P(*([None] * len(l.shape))), dense_sds)
    dt = jnp.dtype(cfg.dtype)
    eff = GRM.serve_attn_block(S)
    N1, R, Q = max_users + 1, rows_per_tick, append_window
    sds = jax.ShapeDtypeStruct
    bufs = {
        "tokens": sds((N1, S), jnp.int32),
        "timestamps": sds((N1, S), jnp.int32),
        "emb": sds((N1, d), dt),
        "kv_k": sds((N1,) + (kv_shape[0], S, kv_shape[1], kv_shape[2]), dt),
        "kv_v": sds((N1,) + (kv_shape[0], S, kv_shape[1], kv_shape[3]), dt),
    }
    ns = lambda s: NamedSharding(mesh, s)
    buf_shard = tuple(ns(specs[k]) for k in
                      ("tokens", "timestamps", "emb", "kv_k", "kv_v"))

    def cold(dense_p, master, tokens, ts_buf, emb, kv_k, kv_v,
             rows, row_ids, row_ts, lengths):
        tokens = tokens.at[rows].set(row_ids)
        ts_buf = ts_buf.at[rows].set(row_ts)
        x = jnp.take(master, row_ids, axis=0).astype(dt)
        e, kr, vr = GRM.gr_encode_slots(dense_p, cfg, x, row_ts, lengths,
                                        attn_block=eff)
        return (tokens, ts_buf, emb.at[rows].set(e),
                kv_k.at[rows].set(kr), kv_v.at[rows].set(vr))

    def warm(dense_p, master, tokens, ts_buf, emb, kv_k, kv_v,
             rows, new_ids, new_ts, pref, nnew):
        upd = jax.vmap(lambda r, u, p:
                       jax.lax.dynamic_update_slice(r, u, (p,)))
        tok_rows = upd(tokens[rows], new_ids, pref)
        ts_rows = upd(ts_buf[rows], new_ts, pref)
        x_new = jnp.take(master, new_ids, axis=0).astype(dt)
        e, kr, vr = GRM.gr_append_slots(dense_p, cfg, x_new, ts_rows,
                                        kv_k[rows], kv_v[rows], pref, nnew,
                                        kv_block=eff)
        return (tokens.at[rows].set(tok_rows), ts_buf.at[rows].set(ts_rows),
                emb.at[rows].set(e), kv_k.at[rows].set(kr),
                kv_v.at[rows].set(vr))

    def rank(emb_buf, rows, scan):
        return topk_from_slots(emb_buf, rows, scan, k=16,
                               block_v=min(4096, int(table_sds.shape[0])))

    out: Dict[str, Any] = {"arch": arch, "mesh_shape": dict(mesh.shape),
                           "specs": {k: str(v) for k, v in specs.items()},
                           "ok": True}
    cold_j = jax.jit(cold, in_shardings=(
        PT.to_named(mesh, dspecs), ns(specs["scan_table"]), *buf_shard,
        ns(P()), ns(P()), ns(P()), ns(P())))
    warm_j = jax.jit(warm, in_shardings=(
        PT.to_named(mesh, dspecs), ns(specs["scan_table"]), *buf_shard,
        ns(P()), ns(P()), ns(P()), ns(P()), ns(P())))
    rank_j = jax.jit(rank, in_shardings=(
        ns(specs["emb"]), ns(specs["rows"]), ns(specs["scan_table"])))

    compiled = {}
    compiled["cold"] = cold_j.lower(
        dense_sds, table_sds, *(bufs[k] for k in bufs),
        sds((R,), jnp.int32), sds((R, S), jnp.int32),
        sds((R, S), jnp.int32), sds((R,), jnp.int32)).compile()
    compiled["warm"] = warm_j.lower(
        dense_sds, table_sds, *(bufs[k] for k in bufs),
        sds((R,), jnp.int32), sds((R, Q), jnp.int32),
        sds((R, Q), jnp.int32), sds((R,), jnp.int32),
        sds((R,), jnp.int32)).compile()
    compiled["rank"] = rank_j.lower(
        bufs["emb"], sds((R,), jnp.int32), table_sds).compile()
    for name, c in compiled.items():
        ma = c.memory_analysis()
        out[name] = {"argument_bytes": int(getattr(
            ma, "argument_size_in_bytes", 0)) if ma is not None else 0}
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             hlo_dir: str = "") -> Dict[str, Any]:
    cfg = get_arch(arch)
    shape = SHAPES_BY_NAME[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    t0 = time.perf_counter()
    jitted, args, arg_specs, plan, mesh = build_cell(arch, shape_name,
                                                     multi_pod)
    with shard_ctx(mesh, plan.rules):
        lowered = jitted.lower(*args)
    t_lower = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0

    ma = compiled.memory_analysis()
    mem = {}
    if ma is not None:
        for f in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
            mem[f] = int(getattr(ma, f, 0))
    # analytic per-device state bytes (CPU memory_analysis counts the
    # whole host platform; the sharded estimate is the per-chip check)
    state_bytes = _sharded_bytes(args[0], arg_specs[0], mesh)
    cost = RL.cost_dict(compiled)
    hlo = compiled.as_text()
    if hlo_dir:
        os.makedirs(hlo_dir, exist_ok=True)
        tag = f"{arch}__{shape_name}__{mesh_name}"
        with gzip.open(os.path.join(hlo_dir, tag + ".hlo.gz"), "wt") as f:
            f.write(hlo)
    rl = RL.analyze(cfg, shape, mesh_name, mesh.size,
                    cost, hlo, notes=plan.notes)
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "chips": mesh.size, "ok": True,
    }
    if cfg.gr:
        pend = arg_specs[0].pending_ids
        # a replicated fallback renders as P() or P(None) — both must trip
        assert any(ax is not None for ax in tuple(pend)), \
            "GR τ=1 pending buffers must be sharded over the data axes"
        rec["pend_spec"] = str(pend)
    rec |= {
        "t_lower_s": round(t_lower, 1), "t_compile_s": round(t_compile, 1),
        "plan": plan.notes, "num_microbatches": plan.num_microbatches,
        "memory_analysis": mem,
        "state_bytes_per_device": state_bytes,
        "cost": {k: cost.get(k) for k in ("flops", "bytes accessed")},
        "roofline": rl.to_dict(),
        "hlo_bytes_len": len(hlo),
    }
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    cells = []
    if args.all:
        for name, cfg in ARCHS.items():
            for s, ok, why in cells_for(cfg):
                if ok:
                    cells.append((name, s.name))
    else:
        cells = [(args.arch, args.shape)]

    for arch, shape in cells:
        for mp in meshes:
            mesh_name = "pod2x16x16" if mp else "pod16x16"
            tag = f"{arch}__{shape}__{mesh_name}"
            path = os.path.join(args.out, tag + ".json")
            if os.path.exists(path):
                print(f"[skip] {tag} (exists)")
                continue
            print(f"[dryrun] {tag} ...", flush=True)
            try:
                rec = run_cell(arch, shape, mp,
                               hlo_dir=os.path.join(args.out, "hlo"))
                print(f"  ok: compile {rec['t_compile_s']}s, "
                      f"flops {rec['cost']['flops']:.3e}, "
                      f"dominant {rec['roofline']['dominant']}")
            except Exception as e:
                rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
                       "ok": False, "error": str(e)[:2000],
                       "traceback": traceback.format_exc()[-4000:]}
                print(f"  FAIL: {str(e)[:200]}")
            with open(path, "w") as f:
                json.dump(rec, f, indent=1, default=str)


if __name__ == "__main__":
    main()
