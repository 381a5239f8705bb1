#!/usr/bin/env python3
"""Compile a cell's training programs for a described TPU v5e, without a
chip, and print what each needs of a chip's memory.

    JAX_PLATFORMS=cpu PYTHONPATH=src python3 bench/rehearse.py \\
        --workload hstu-large.long-hist [--vocab 262144] [--mesh 2,2]

It builds the cell's engine stages exactly as a run does and lowers each
program the window drives (on one chip: the input gather, dense forward and
backward, the embedding backward with and without the sparse landing, the
landing of leftover rows; on a mesh the input gather stays inside the dense
program, as the HSP lookup needs) at the cell's shapes, with the state
placed as a run places it, and prints ``memory_analysis()`` of each: the
bytes on one chip, with the state's own bytes on one chip beside them. A
program the compiler refuses is printed with the refusal, and the next one
is tried. ``--vocab`` overrides the table's rows, to size it; ``--mesh
data,model`` puts the configuration on a mesh of that shape over the chips
of a described ``v5e:2x2``. The backend checks of the program see a TPU, so
the Pallas kernels are the ones compiled.
"""
from __future__ import annotations

import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.getcwd()
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def chip_bytes(tree) -> int:
    """Bytes of a tree of placed shapes on the first chip."""
    import jax
    import numpy as np
    return sum(int(np.prod(x.sharding.shard_shape(x.shape)))
               * x.dtype.itemsize for x in jax.tree.leaves(tree))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--vocab", type=int, default=None)
    ap.add_argument("--mesh", default=None,
                    help="data,model: the mesh to rehearse the cell on")
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
    if args.mesh:
        d, m = (int(x) for x in args.mesh.split(","))
        model["mesh"] = {"data": d, "model": m}
    shards = model["mesh"]["data"] * model["mesh"]["model"] \
        if "mesh" in model else 1
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    prog = harness.Program(model, 0, topo.devices[:shards])
    batch, _ = traffic.make_batches(cell.bench_dir, cell.mix, model, 0, 0, 1,
                                    shards)[0]
    from repro.training.trainer import gr_pending_slots
    slots = gr_pending_slots(batch, model["vocab_size"])
    state = jax.eval_shape(prog._make_state, harness.seed_key(0), slots)
    if prog.mesh is None:
        chip = SingleDeviceSharding(topo.devices[0])
        state_sh = jax.tree.map(lambda _: chip, state)
        batch_sh = {k: chip for k in batch}
    else:
        state_sh = prog.state_shardings(slots)
        batch_sh = prog.batch_shardings(batch)
    place = lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh)
    state = jax.tree.map(place, state, state_sh)
    dev = {k: place(jax.ShapeDtypeStruct(v.shape, v.dtype), batch_sh[k])
           for k, v in batch.items()}
    eng = prog.engine
    n = batch["ids"].size + batch["labels"].size + batch["neg_ids"].size
    cand_sh = batch_sh["rng"]                # one chip, or replicated
    cand = (jax.ShapeDtypeStruct((n,), jnp.int32, sharding=cand_sh),
            jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=cand_sh))

    def report(name, lower):
        try:
            c = lower().compile()
        except Exception as e:      # the compiler's refusal is the finding
            msg = " ".join(str(e).split())[:600]
            print(f"{name:24s} refused: {type(e).__name__}: {msg}",
                  flush=True)
            return None
        m = c.memory_analysis()
        kern = c.as_text().count("tpu_custom_call")
        print(f"{name:24s} args {m.argument_size_in_bytes / 2**30:7.3f} GiB"
              f"  out {m.output_size_in_bytes / 2**30:7.3f} GiB"
              f"  temp {m.temp_size_in_bytes / 2**30:7.3f} GiB"
              f"  alias {m.alias_size_in_bytes / 2**30:7.3f} GiB"
              f"  tpu_custom_call {kern}", flush=True)
        return c

    mesh = ("one chip" if prog.mesh is None else
            f"mesh {dict(prog.mesh.shape)} over {shards} chips")
    print(f"{args.workload}: {mesh}; V={model['vocab_size']} T="
          f"{cell.mix['token_budget']} a chip, R={model['num_negatives']}, "
          f"pending slots {slots}; train state on one chip "
          f"{chip_bytes(state) / 2**30:.3f} GiB", flush=True)
    if prog.mesh is None:
        report("emb_fwd", lambda: eng._j_emb_fwd.lower(state.table.master,
                                                        dev))
        x = place(jax.eval_shape(eng._j_emb_fwd, state.table.master, dev),
                  chip)
        stale = None
    else:
        x, stale = None, state.table.master
    args_dense = (state.dense, state.table, dev, x, stale)
    c = report("dense_fwd_bwd", lambda: eng._j_dense.lower(*args_dense))
    if c is not None:        # the embedding backward takes what it gave
        dout = jax.tree.map(place, jax.eval_shape(eng._j_dense, *args_dense),
                            c.output_shardings)
        print(f"{'dense output':24s} {chip_bytes(dout) / 2**30:.3f} GiB on "
              f"one chip", flush=True)
        for apply in (True, False):
            report(f"emb_bwd apply={apply}", lambda: eng._j_emb_bwd.lower(
                state.dense, state.dense_opt, state.table, dout, dev, *cand,
                apply_sparse=apply, slots=slots))
    report("sparse_apply", lambda: eng._j_sparse_apply.lower(
        state.table, state.pending_ids, state.pending_rows))
    report("check: change norms", lambda: prog._changes.lower(
        state.dense, state.table.master,
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=cand_sh)))


if __name__ == "__main__":
    main()
