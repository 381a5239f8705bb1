#!/usr/bin/env python3
"""Compile a cell's training programs for a described TPU v5e, without a
chip, and print what each needs of the chip's memory.

    JAX_PLATFORMS=cpu PYTHONPATH=src python3 bench/rehearse.py \\
        --workload hstu-large.long-hist [--vocab 262144]

It builds the cell's engine stages exactly as a run does, lowers each
program the window drives (input gather, dense forward and backward, the
embedding backward with and without the sparse landing, the landing of
leftover rows) at the cell's shapes for one chip of a described ``v5e``,
and prints ``memory_analysis()`` of each, with the state the engine keeps
beside them. ``--vocab`` overrides the table's rows, to size it. The
backend checks of the program see a TPU, so the Pallas kernels are the ones
compiled.
"""
from __future__ import annotations

import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.getcwd()
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--vocab", type=int, default=None)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import harness
    import traffic

    jax.default_backend = lambda: "tpu"      # take the program's TPU paths
    cell = harness.load_cell(ROOT, args.workload)
    model = dict(cell.model)
    if args.vocab:
        model["vocab_size"] = args.vocab
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip)

    prog = harness.Program(model, 0)
    batch, _ = traffic.make_batches(cell.bench_dir, cell.mix, model, 0, 0,
                                    1)[0]
    from repro.training.trainer import gr_pending_slots
    slots = gr_pending_slots(batch, model["vocab_size"])
    state = jax.eval_shape(prog._make_state, harness.seed_key(0), slots)
    state = jax.tree.map(sds, state)
    eng = prog.engine
    dev = {k: sds(jnp.asarray(v)) for k, v in batch.items()}
    n = dev["ids"].size + dev["labels"].size + dev["neg_ids"].size
    cand = (jax.ShapeDtypeStruct((n,), jnp.int32, sharding=chip),
            jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=chip))

    def report(name, lowered):
        c = lowered.compile()
        m = c.memory_analysis()
        kern = c.as_text().count("tpu_custom_call")
        print(f"{name:24s} args {m.argument_size_in_bytes / 2**30:7.3f} GiB"
              f"  out {m.output_size_in_bytes / 2**30:7.3f} GiB"
              f"  temp {m.temp_size_in_bytes / 2**30:7.3f} GiB"
              f"  alias {m.alias_size_in_bytes / 2**30:7.3f} GiB"
              f"  tpu_custom_call {kern}", flush=True)
        return m

    nbytes = lambda t: sum(x.size * x.dtype.itemsize
                           for x in jax.tree.leaves(t))
    print(f"{args.workload}: V={model['vocab_size']} T="
          f"{cell.mix['token_budget']} R={model['num_negatives']} "
          f"pending slots {slots}; train state "
          f"{nbytes(state) / 2**30:.3f} GiB", flush=True)
    x = jax.eval_shape(eng._j_emb_fwd, state.table.master, dev)
    report("emb_fwd", eng._j_emb_fwd.lower(state.table.master, dev))
    x = sds(x)
    md = report("dense_fwd_bwd", eng._j_dense.lower(state.dense, state.table,
                                                     dev, x, None))
    dout = jax.tree.map(sds, jax.eval_shape(eng._j_dense, state.dense,
                                            state.table, dev, x, None))
    print(f"{'dense output':24s} {nbytes(dout) / 2**30:.3f} GiB")
    for apply in (True, False):
        report(f"emb_bwd apply={apply}",
               eng._j_emb_bwd.lower(state.dense, state.dense_opt,
                                    state.table, dout, dev, *cand,
                                    apply_sparse=apply, slots=slots))
    report("sparse_apply", eng._j_sparse_apply.lower(
        state.table, state.pending_ids, state.pending_rows))
    report("check: change norms", prog._changes.lower(
        state.dense, state.table.master,
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=chip)))


if __name__ == "__main__":
    main()
