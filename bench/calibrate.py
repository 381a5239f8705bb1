#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/calibrate.py --workload hstu-large.long-hist \\
        --seeds 11,12,13 --control 3 --faults 3

For each seed it drives the program's checked steps exactly as a run does
(the engine from the seed's state through its own call and feed), frees the
program's state, runs the plain reference, and prints one JSON line with
the compared numbers (``side: program``). For the first ``--control`` seeds
it also reads the control, the reference computed with every matmul
operand in float8_e4m3 in the program's place (``side: control``), and the
planted fault of half of each step's tokens left out of the loss, the mean
taken over the rest (``side: half_batch``). For the first ``--faults``
seeds it reads two faults of the sparse update planted in the program:
the table's rows landed at once instead of one step late (``side: tau0``)
and AdaGrad's accumulator forgotten at every landing (``side: no_accum``).
A step that leaves the state unchanged reads 1 on ``change_gap`` by
construction and needs no run.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.getcwd()
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def forget_accumulator():
    """Plant the ``no_accum`` fault: every landing starts AdaGrad's
    accumulator of the touched rows from zero."""
    import jax.numpy as jnp
    from repro.training import optim
    real = optim.adagrad_sparse_update

    def broken(table, ids, rows, **kw):
        return real(table._replace(accum=jnp.zeros_like(table.accum)),
                    ids, rows, **kw)

    optim.adagrad_sparse_update = broken


def readings(prog, cell, seed):
    """The program's checked steps from ``seed``, its state freed after."""
    import harness
    import traffic
    steps = traffic.make_batches(cell.bench_dir, cell.mix, cell.model, seed,
                                 0, harness.CHECK_STEPS, cell.shards)
    prog.seed = seed
    prog.feed.steps = steps
    prog.init_state()
    mine = prog.checked_steps()
    prog.engine.state = None
    gc.collect()
    return mine, [s[0] for s in steps]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--faults", type=int, default=0)
    args = ap.parse_args()
    import jax.numpy as jnp

    import compare
    import harness

    cell = harness.load_cell(ROOT, args.workload)
    reference = harness.reference_module(cell)
    devs = harness.require_chips(cell.chips)
    harness.use_compile_cache(ROOT)
    seeds = [int(s) for s in args.seeds.split(",")]

    def show(seed, side, got, ref):
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "side": side, **compare.gaps(got, ref),
                          "losses": got["losses"],
                          "ref_losses": ref["losses"],
                          "grad_norms": got["grad_norms"],
                          "ref_grad_norms": ref["grad_norms"],
                          "change_norms": got["change_norms"],
                          "ref_change_norms": ref["change_norms"],
                          "device": devs[0].device_kind}), flush=True)

    prog = harness.Program(cell.model, seeds[0], devs)
    refs = {}
    for i, seed in enumerate(seeds):
        mine, batches = readings(prog, cell, seed)
        ref = reference.run(cell.model, seed, batches)
        show(seed, "program", mine, ref)
        if i < args.control:
            show(seed, "control", reference.run(
                cell.model, seed, batches, low=jnp.float8_e4m3fn), ref)
            show(seed, "half_batch", reference.run(
                cell.model, seed, batches, half_batch=True), ref)
        if i < args.faults:
            refs[seed] = ref
    prog.close()

    tau0 = dict(cell.model, training=dict(cell.model["training"],
                                          semi_async=False))
    for side, model, plant in (("tau0", tau0, None),
                               ("no_accum", cell.model, forget_accumulator)):
        if not refs:
            break
        if plant is not None:
            plant()
        prog = harness.Program(model, seeds[0], devs)
        for seed, ref in refs.items():
            show(seed, side, readings(prog, cell, seed)[0], ref)
        prog.close()


if __name__ == "__main__":
    main()
