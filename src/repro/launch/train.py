"""End-to-end GR training driver (the paper's workload).

Runs the full stack on whatever devices exist: synthetic-KuaiRand data →
Appendix-A preprocessing → load-balanced jagged loader → HSTU/FuXi dense
backbone + embedding table → sampled-softmax recall loss (§4.3 modes) →
AdamW + Eq.-1 AdaGrad (optionally τ=1 semi-async) → async checkpoints,
all executed by the staged engine (§4.2.3 Algorithm 1 by default;
``--schedule flat`` runs the same stages serially with identical
numerics).

CPU example (a ~100M-dense-param model, a few hundred steps):
    PYTHONPATH=src python -m repro.launch.train --arch hstu-large \
        --steps 200 --users-per-device 2 --max-seq-len 512 \
        --num-items 200000 --synthetic-users 2000

On a TPU the same command runs the Pallas kernels (attention, fused
negatives, scatter) in place of the XLA paths. The engine drives one
device: on a host with several, the loader is sized for that one and the
rest stay idle (the multi-chip HSP step is ``launch/dryrun.py``'s and
``chip_smoke.py --chips 4``'s). JAX's compilation cache lives in
``$JAX_COMPILATION_CACHE_DIR`` or else ``<repo>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import math
import time

import jax

from repro.configs import get_arch
from repro.data.kuairand import preprocess_log
from repro.data.loader import GRLoader
from repro.data.synthetic import SyntheticKuaiRand
from repro.launch.compile_cache import use_repo_compile_cache
from repro.models.model_zoo import GRBundle
from repro.training import checkpoint as CKPT
from repro.training.engine import GREngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="hstu-large")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--synthetic-users", type=int, default=2000)
    ap.add_argument("--num-items", type=int, default=200_000)
    ap.add_argument("--max-seq-len", type=int, default=512)
    ap.add_argument("--users-per-device", type=int, default=2)
    ap.add_argument("--num-negatives", type=int, default=32)
    ap.add_argument("--strategy", default="token_realloc",
                    choices=["fixed", "token_scaling", "token_realloc"])
    ap.add_argument("--neg-mode", default="fused",
                    choices=["baseline", "segmented", "fused"])
    ap.add_argument("--schedule", default="algorithm1",
                    choices=["algorithm1", "flat"],
                    help="staged pipeline (Algorithm 1) vs serial stages")
    ap.add_argument("--expansion", type=int, default=1)
    ap.add_argument("--no-semi-async", action="store_true")
    ap.add_argument("--use-kernel", action="store_true",
                    help="Pallas jagged attention (interpret on CPU)")
    ap.add_argument("--ckpt-dir", default="",
                    help="enables the supervised resilient loop: "
                         "crash-consistent async checkpoints, per-stage "
                         "retry, non-finite guard, recovery on failure")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--keep-last-n", type=int, default=0,
                    help="retain only the newest N checkpoints (0 = all)")
    ap.add_argument("--resume", action="store_true",
                    help="restore the newest intact checkpoint in "
                         "--ckpt-dir and continue to --steps")
    ap.add_argument("--stage-retries", type=int, default=2,
                    help="retry budget for the host stages "
                         "(dataload/a2a/unique)")
    ap.add_argument("--max-skips", type=int, default=0,
                    help="non-finite-loss batches to skip before "
                         "escalating to recovery")
    ap.add_argument("--stage-timeout", type=float, default=0.0,
                    help="per-stage straggler watchdog in seconds "
                         "(0 = off; stragglers are recorded, not failed)")
    ap.add_argument("--lr", type=float, default=4e-3)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace-out", default="",
                    help="write a Chrome/Perfetto trace_event JSON of the "
                         "run (open in ui.perfetto.dev)")
    ap.add_argument("--metrics-out", default="",
                    help="write the final MetricsRegistry snapshot: "
                         "*.prom gets Prometheus text exposition, "
                         "anything else the nested-JSON snapshot()")
    ap.add_argument("--metrics-every", type=int, default=0,
                    help="print measured MFU / token imbalance / step "
                         "wall time every N steps (0 = off; implies obs)")
    args = ap.parse_args()
    use_repo_compile_cache()

    cfg = get_arch(args.arch)
    if not cfg.gr:
        raise SystemExit("train.py drives GR models; LM archs are exercised "
                         "via launch/dryrun.py and examples/")
    cfg = cfg.replace(max_seq_len=args.max_seq_len,
                      num_negatives=args.num_negatives,
                      vocab_size=args.num_items)

    print(f"[data] synthesizing KuaiRand surrogate "
          f"({args.synthetic_users} users)...")
    gen = SyntheticKuaiRand(num_users=args.synthetic_users,
                            num_items=args.num_items,
                            max_len=args.max_seq_len + 1, seed=args.seed)
    train_seqs, test, remap = preprocess_log(gen.log(args.synthetic_users))
    n_items = max(len(remap), 16)
    cfg = cfg.replace(vocab_size=n_items)
    print(f"[data] {len(train_seqs)} users, {n_items} items after 5-core "
          f"filter + leave-one-out")

    # GREngine jits without shardings: it runs on one device, so the
    # loader packs one device's batch
    ndev = 1
    if jax.device_count() > ndev:
        print(f"[devices] {jax.device_count()} visible; the engine uses "
              f"{ndev} ({jax.devices()[0].device_kind})")
    loader = GRLoader(train_seqs, num_devices=ndev,
                      users_per_device=args.users_per_device,
                      max_seq_len=args.max_seq_len,
                      num_negatives=args.num_negatives,
                      num_items=n_items, strategy=args.strategy,
                      seed=args.seed)

    bundle = GRBundle(cfg)
    key = jax.random.PRNGKey(args.seed)
    # count params from shapes only — the engine materializes the state
    dense_sds = jax.eval_shape(bundle.init_dense, key)
    n_dense = sum(math.prod(x.shape) for x in jax.tree.leaves(dense_sds))
    print(f"[model] {cfg.name}: {n_dense/1e6:.2f}M dense params, "
          f"table {n_items}x{cfg.d_model}")

    attn_fn = None
    if args.use_kernel:
        from repro.kernels.jagged_attention import make_attn_fn
        # max_row_len bounds the work-list grid: rows come from the loader
        # capped at max_seq_len, so live pairs scale with rows, not cap².
        attn_fn = make_attn_fn(block=128, max_row_len=args.max_seq_len)

    # observability: any telemetry flag turns the obs layer on
    obs = None
    if args.trace_out or args.metrics_out or args.metrics_every:
        from repro.obs import Obs
        obs = Obs()

    t0 = time.perf_counter()
    tally = {"tokens": 0}

    def on_step(i, rec, state):
        tally["tokens"] += rec["tokens"]
        if (i + 1) % args.log_every == 0:
            dt = time.perf_counter() - t0
            print(f"step {i+1:5d}  loss {rec['loss']:.4f}  "
                  f"{tally['tokens']/dt:,.0f} tok/s  "
                  f"{(i+1)/dt:.2f} steps/s", flush=True)
        if args.metrics_every and (i + 1) % args.metrics_every == 0:
            # per-step derived gauges ride the record when obs is live
            mfu = rec.get("mfu")
            print(f"[obs] step {i+1:5d}  "
                  f"mfu {'not measured' if mfu is None else f'{100*mfu:.2f}%'}  "
                  f"imbalance {100*rec.get('imbalance', 0):.2f}%  "
                  f"step_wall {rec.get('step_wall_s', 0)*1e3:.1f}ms",
                  flush=True)

    engine = GREngine(
        bundle, loader,
        loss_kwargs=dict(neg_mode=args.neg_mode, expansion=args.expansion,
                         attn_fn=attn_fn),
        lr_dense=args.lr, lr_sparse=args.lr,
        semi_async=not args.no_semi_async, schedule=args.schedule,
        seed=args.seed, step_callback=on_step, obs=obs)
    if args.ckpt_dir:
        # supervised loop: crash-consistent checkpoints + recovery
        # (training/resilience.py); a failed stage drains the pipeline,
        # restores the newest intact checkpoint and replays
        from repro.training.resilience import FaultPolicy
        host_r = args.stage_retries
        policy = FaultPolicy(
            retries={"dataload": host_r, "a2a": host_r, "unique": host_r},
            stage_timeout_s=({s: args.stage_timeout for s in
                              ("dataload", "a2a", "unique", "dense_bwd")}
                             if args.stage_timeout else {}),
            max_skips=args.max_skips,
            nonfinite_action="skip" if args.max_skips else "recover")
        if args.resume:
            used = CKPT.latest_step(args.ckpt_dir)
            if used is not None:
                # template built exactly as the engine would on step 0 (a
                # twin loader peeks the first batch without advancing the
                # training loader's RNG)
                from repro.training.trainer import (gr_pending_slots,
                                                    gr_train_state)
                peek = GRLoader(train_seqs, num_devices=ndev,
                                users_per_device=args.users_per_device,
                                max_seq_len=args.max_seq_len,
                                num_negatives=args.num_negatives,
                                num_items=n_items, strategy=args.strategy,
                                seed=args.seed)
                first = next(iter(peek.batches(1)))
                template = gr_train_state(
                    bundle.init_dense(key), bundle.init_table(key),
                    pending_slots=gr_pending_slots(first))
                engine.state, used = CKPT.restore_with_step(
                    args.ckpt_dir, template)
                print(f"[resume] restored intact checkpoint step {used}")
        results = engine.run_resilient(
            args.steps, ckpt_dir=args.ckpt_dir,
            ckpt_every=args.ckpt_every, policy=policy,
            keep_last_n=args.keep_last_n or None)
        for ev in engine.recoveries:
            print(f"[recovery] failed near step {ev.failed_step}, "
                  f"restored step {ev.restored_step} "
                  f"({ev.steps_lost} steps replayed)")
    else:
        results = engine.run(args.steps)
    r = engine.timeline_report()
    print(f"[timeline] computing {100*r.get('computing_ratio', 0):.1f}%  "
          f"comm-not-overlapped "
          f"{100*r.get('comm_not_overlapped_ratio', 0):.2f}%  "
          f"free {100*r.get('free_ratio', 0):.1f}%")
    if obs is not None:
        if args.trace_out:
            obs.export_trace(args.trace_out)
            print(f"[obs] wrote Perfetto trace to {args.trace_out} "
                  f"({len(obs.tracer)} spans)")
        if args.metrics_out:
            if args.metrics_out.endswith(".prom"):
                with open(args.metrics_out, "w") as f:
                    f.write(obs.to_prometheus())
            else:
                import json
                with open(args.metrics_out, "w") as f:
                    json.dump(obs.snapshot(), f, indent=1)
            print(f"[obs] wrote metrics snapshot to {args.metrics_out}")
    final = f"final loss {results[-1]['loss']:.4f}" if results else "no steps"
    print(f"[done] {args.steps} steps in "
          f"{time.perf_counter()-t0:.1f}s, {final}")


if __name__ == "__main__":
    main()
