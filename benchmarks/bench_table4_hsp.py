"""Table 4: hierarchical sparse parallelism vs global sharding baseline.

Paper: all-to-all delay 498→120 ms (−75.9%), overall comm 613→373 ms.
Without NPUs we compare the *compiled communication volume*: per-device
collective bytes of one embedding fwd+bwd under (a) TorchRec-style global
vocab sharding and (b) HSP — on an 8-device (2 groups × 4) mesh subprocess.
The intra-group exchange scales O(I) vs O(N), which is the paper's claim.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

from benchmarks.common import emit

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

BODY = """
import json, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.core.hsp import make_hsp_lookup
from repro.launch.hlo_analysis import analyze_text
mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
V, d = 65536, 256
ids_sds = jax.ShapeDtypeStruct((8, 1024), jnp.int32)
tbl_sds = jax.ShapeDtypeStruct((V, d), jnp.float32)

def coll(group_axes, dp_axes, tspec):
    lookup = make_hsp_lookup(mesh, group_axes=group_axes, dp_axes=dp_axes,
                             compute_dtype=jnp.float32)
    f = lambda t, i: jnp.sum(lookup(t, i) ** 2)
    j = jax.jit(jax.grad(f), in_shardings=(
        NamedSharding(mesh, tspec), NamedSharding(mesh, P(("data","model")))))
    c = analyze_text(j.lower(tbl_sds, ids_sds).compile().as_text())
    return {k: int(v) for k, v in c.coll_bytes.items()}

glob = coll(("data", "model"), (), P(("data", "model"), None))
hsp = coll(("model",), ("data",), P("model", None))
print(json.dumps({"global": glob, "hsp": hsp}))
"""


def main():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"     # fake host devices, never the chip
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(BODY)],
                          env=env, capture_output=True, text=True,
                          timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads([l for l in proc.stdout.splitlines()
                      if l.startswith("{")][-1])
    g = sum(out["global"].values())
    h = sum(out["hsp"].values())
    # the all-to-all-analogue = gather/scatter collectives of the lookup
    g_a2a = out["global"]["all-gather"] + out["global"]["reduce-scatter"]
    h_a2a = out["hsp"]["all-gather"] + out["hsp"]["reduce-scatter"]
    emit("table4_hsp.global_baseline_bytes", 0.0,
         f"total={g} a2a={g_a2a} {out['global']}")
    emit("table4_hsp.hsp_bytes", 0.0, f"total={h} a2a={h_a2a} {out['hsp']}")
    # scale law: the lookup exchange shrinks O(N)→O(I). At this 8-device
    # test mesh I/N = 1/2 (≈50% cut); at the production pod N=256, I=16
    # the same law gives a 93.75% cut — bracketing the paper's 75.9%
    # latency reduction on their 32-128 NPU cluster. The added inter-group
    # all-reduce is the trade the paper itself documents ("despite
    # introducing additional all-reduce communication...").
    cut = 1 - h_a2a / max(g_a2a, 1)
    emit("table4_hsp.reduction", 0.0,
         f"a2a_bytes_cut={cut:.1%} at I/N=1/2 (law: 1-I/N); production "
         f"I=16,N=256 -> 93.8% (paper 75.9% latency on 32-128 NPUs)")


if __name__ == "__main__":
    main()
