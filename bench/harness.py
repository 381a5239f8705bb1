"""One run of one benchmark cell.

A cell of ``BENCHMARK.json`` names a configuration (its ``file``) and a
traffic mix (``<bench>/traffic/<mix>.json``); each per-layer metric is read
by ``<bench>/metrics/<name>.py``, whose ``read(run)`` returns a number or
None. A new cell, configuration, mix or metric is new files and entries.

A configuration may name a mesh, ``"mesh": {"data": D, "model": M}``, of
as many chips as its cells ask for: the item table is then sharded over
``model`` by Hierarchical Sparse Parallelism (the HSP lookup of
``repro.core.hsp``), the dense weights replicated, and each chip trains one
shard of every step, so a step holds D M shards of the mix's token budget;
without it the cell runs on one chip and a step is one shard. It may also
name the module of its plain reference, ``"reference": "<module>"``
(``<bench>/<module>.py``, default ``reference``).

A run: find the chips; make the steps from the seed; build the training
engine (``GREngine``, Algorithm 1, tau = 1) with its state made on the
device in one jitted call; drive its first three steps, which the
correctness check compares, then three more, so that every program the
window runs is compiled; measure whole steps for about ``seconds``, ending
on ``block_until_ready`` of the state; read the peak memory; free the
program; run the plain reference over the first three steps; print the
result.
"""
from __future__ import annotations

import gc
import json
import math
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)

import compare  # noqa: E402
import traffic  # noqa: E402

CHECK_STEPS = 3
WARM_STEPS = 3


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# -- the benchmark's files -----------------------------------------------------

def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    model: Dict
    mix: Dict
    limits: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    bench_dir: str
    shards: int = 1         # shards of a step: one a chip of the mesh


def _named(entries: List[Dict], name: str, what: str) -> Dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"bench: no {what} named {name!r} in BENCHMARK.json")


def _for_cell(metrics: List[Dict], cell: str) -> List[Dict]:
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def load_cell(root: str, name: str) -> Cell:
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    w = _named(spec["workloads"], name, "workload")
    c = _named(spec["configs"], w["config"], "config")
    bench_dir = os.path.join(root, spec["paths"][0])
    model = load_json(os.path.join(root, c["file"]))
    chips, shards = int(w["chips"]), 1
    if "mesh" in model:
        mesh = model["mesh"]
        if sorted(mesh) != ["data", "model"] or not all(
                isinstance(v, int) and v > 0 for v in mesh.values()):
            raise SystemExit(f"bench: the mesh of {c['file']} is {mesh!r}, "
                             f"not {{\"data\": D, \"model\": M}}")
        shards = mesh["data"] * mesh["model"]
        if shards != chips:
            raise SystemExit(
                f"bench: the mesh of {c['file']} holds {mesh['data']} x "
                f"{mesh['model']} = {shards} chips; the cell {name} asks "
                f"for {chips}")
    return Cell(name=name, chips=chips, model=model,
                mix=traffic.load_mix(bench_dir, w["traffic"]),
                limits=load_json(os.path.join(bench_dir, "limits",
                                              f"{name}.json")),
                end_to_end=_for_cell(spec["end_to_end"], name),
                per_layer=_for_cell(spec["per_layer"], name),
                bench_dir=bench_dir, shards=shards)


def reference_module(cell: Cell):
    """The configuration's plain reference: ``<bench>/<module>.py``."""
    name = cell.model.get("reference", "reference")
    return traffic.load_module(os.path.join(cell.bench_dir, f"{name}.py"),
                               f"bench_reference_{name}")


def metric_reader(bench_dir: str, name: str):
    if os.path.join(bench_dir, "metrics") not in sys.path:
        sys.path.insert(0, os.path.join(bench_dir, "metrics"))
    return traffic.load_module(os.path.join(bench_dir, "metrics",
                                            f"{name}.py"),
                               f"bench_metric_{name.replace('.', '_')}")


def device_peak(bench_dir: str, kind: str) -> Dict[str, float]:
    peaks = load_json(os.path.join(bench_dir, "peaks.json"))["devices"]
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    return peaks[kind]


# -- the chip -------------------------------------------------------------------

def require_chips(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: JAX found no TPU (platform "
                         f"{devs[0].platform!r}); no result")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}; no result")
    return devs[:chips]


def use_compile_cache(root: str) -> None:
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts backend compilations while ``on``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.on, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event: str, *_a, **_k) -> None:
        if self.on and event == self.EVENT:
            self.count += 1


# -- the program ------------------------------------------------------------------

def arch_config(model: Dict):
    from repro.configs.base import ArchConfig, RABConfig
    return ArchConfig(
        name=f"bench-{model['block']}", family="gr",
        num_layers=model["num_layers"], d_model=model["d_model"],
        num_heads=model["num_heads"], num_kv_heads=model["num_heads"],
        head_dim=model["qkv_dim"], qkv_dim=model["qkv_dim"],
        d_ff=model["d_ff"], vocab_size=model["vocab_size"], gr=True,
        gr_block=model["block"], rab=RABConfig(**model["rab"]),
        num_negatives=model["num_negatives"],
        max_seq_len=model["max_seq_len"], dtype=model["dtype"],
        norm_eps=model["norm_eps"], rope_theta=0.0)


def seed_key(seed: int):
    import jax
    return jax.random.PRNGKey(seed % (1 << 32))


class Feed:
    """``data_fn`` of the engine: step ``i`` of the current run is
    ``steps[base + i]``, placed by ``place`` (on a mesh: each shard on its
    chip)."""

    def __init__(self):
        self.steps: List = []
        self.base = 0
        self.place = None

    def __call__(self, i: int):
        batch = self.steps[self.base + i][0]
        return batch if self.place is None else self.place(batch)


class Program:
    """The system under test: ``GREngine`` on the cell's configuration,
    and the program-side readings of the checked steps. With a ``mesh`` in
    the configuration, the engine runs over ``devices`` with the HSP
    lookup, and the state and each step's batch are placed by the
    program's own partition rules (``repro.launch.partition``)."""

    def __init__(self, model: Dict, seed: int, devices=None):
        import jax
        import jax.numpy as jnp
        from repro.models.model_zoo import GRBundle
        from repro.training.engine import GREngine
        from repro.training.trainer import gr_train_state

        tr = model["training"]
        self.model, self.seed = model, seed
        self.bundle = GRBundle(arch_config(model))
        self.feed = Feed()
        loss_kwargs = dict(neg_mode=tr["neg_mode"],
                           expansion=tr["expansion"])
        self.mesh = None
        if "mesh" in model:
            self._on_mesh(devices, loss_kwargs)
        self.engine = GREngine(
            self.bundle, self.feed, seed=seed % (1 << 32),
            loss_kwargs=loss_kwargs,
            lr_dense=tr["lr_dense"], lr_sparse=tr["lr_sparse"],
            semi_async=tr["semi_async"], schedule=tr["schedule"],
            step_callback=self._on_step)
        self._capture: Optional[Dict] = None
        self.marks: List[float] = []
        b1 = tr["adam_b1"]
        bundle = self.bundle
        sqnorm = lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))

        def make_state(key, slots):
            return gr_train_state(bundle.init_dense(key),
                                  bundle.init_table(key),
                                  pending_slots=slots)

        def first_grads(mu, rows):
            return [sqnorm(m) / (1.0 - b1) for m in jax.tree.leaves(mu)] + \
                [sqnorm(rows)]

        def changes(dense, master, key):
            d0 = bundle.init_dense(key)
            out = [sqnorm(a.astype(jnp.float32) - b.astype(jnp.float32))
                   for a, b in zip(jax.tree.leaves(dense),
                                   jax.tree.leaves(d0))]
            return out + [sqnorm(master - bundle.init_table(key))]

        self._state_fn = make_state
        self._make_state = jax.jit(make_state, static_argnums=1)
        self._first_grads = jax.jit(first_grads)
        self._changes = jax.jit(changes)
        dense_sds = jax.eval_shape(bundle.init_dense, seed_key(0))
        paths = jax.tree_util.tree_flatten_with_path(dense_sds)[0]
        self.leaves = ["/".join(str(getattr(k, "key", k)) for k in p)
                       for p, _ in paths] + ["table"]

    def _on_mesh(self, devices, loss_kwargs: Dict) -> None:
        """A ``("data", "model")`` mesh over ``devices``, the HSP lookup
        bound into the loss, the plan behind the state's and the batches'
        shardings, and the feed placing each step over the mesh."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import AxisType, Mesh
        from repro.configs.shapes import ShapeConfig
        from repro.core.hsp import make_hsp_lookup
        from repro.launch import partition as PT

        shape = (self.model["mesh"]["data"], self.model["mesh"]["model"])
        self.mesh = Mesh(np.array(devices).reshape(shape), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
        loss_kwargs["lookup_fn"] = make_hsp_lookup(
            self.mesh, group_axes=("model",), dp_axes=("data",),
            compute_dtype=jnp.dtype(self.model["dtype"]))
        self._shape = ShapeConfig("bench", self.model["max_seq_len"],
                                  self.mesh.size, "train")
        self._plan = PT.make_plan(self.bundle.cfg, self._shape, self.mesh)
        self.feed.place = lambda batch: jax.device_put(
            batch, self.batch_shardings(batch))

    def batch_shardings(self, batch: Dict):
        """Each array of a step placed over the mesh on its shard axis."""
        import jax
        from repro.launch import partition as PT
        sds = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
               for k, v in batch.items()}
        specs = PT.batch_specs(self.bundle.cfg, self._shape, self.mesh,
                               self._plan, {"batch": sds})["batch"]
        return PT.to_named(self.mesh, specs)

    def state_shardings(self, slots: int):
        """The state's shardings: the table over ``model``, the dense
        weights replicated, the tau = 1 pending rows over the data axes."""
        import jax
        from repro.launch import partition as PT
        sds = jax.eval_shape(self._make_state, seed_key(0), slots)
        dense = PT.gr_param_specs(sds.dense, self.mesh, self._plan)
        specs = PT.gr_state_specs(dense, PT.gr_table_spec(self.mesh,
                                                          self._plan),
                                  pend_spec=PT.gr_pend_spec(self.mesh, slots))
        return PT.to_named(self.mesh, specs)

    def init_state(self) -> None:
        import jax
        from repro.training.trainer import gr_pending_slots
        slots = gr_pending_slots(self.feed.steps[0][0],
                                 self.model["vocab_size"])
        make = self._make_state
        if self.mesh is not None:
            make = jax.jit(self._state_fn, static_argnums=1,
                           out_shardings=self.state_shardings(slots))
        self.engine.state = make(seed_key(self.seed), slots)

    def _on_step(self, i: int, rec: Dict, snap) -> None:
        self.marks.append(time.perf_counter())
        cap = self._capture
        if cap is None:
            return
        cap["losses"].append(rec["loss"])
        if i == 0:
            cap["grad"] = self._first_grads(snap.dense_opt.mu,
                                            snap.pending_rows)
        if i == CHECK_STEPS - 1:
            cap["change"] = self._changes(snap.dense, snap.table.master,
                                          seed_key(self.seed))

    def checked_steps(self) -> Dict:
        """Drive the first steps from the seed through the window's own
        call and feed; returns the program's readings."""
        self._capture = {"losses": []}
        self.feed.base = 0
        self.engine.run(CHECK_STEPS)
        cap, self._capture = self._capture, None
        return {"losses": [float(x) for x in cap["losses"]],
                "grad_norms": dict(zip(self.leaves,
                                       (float(x) for x in cap["grad"]))),
                "change_norms": dict(zip(self.leaves,
                                         (float(x) for x in cap["change"])))}

    def run(self, base: int, steps: int) -> List[Dict]:
        import jax
        self.feed.base = base
        recs = self.engine.run(steps)
        jax.block_until_ready(self.engine.state)
        return recs

    def close(self) -> None:
        self.engine.state = None
        self.engine = None
        gc.collect()


# -- a run ------------------------------------------------------------------

@dataclass
class Run:
    """What a per-layer metric reads."""
    model: Dict
    mix: Dict
    chips: int
    peak: Dict[str, float]
    steps: List[Dict] = field(default_factory=list)   # window steps
    window_s: float = 0.0
    events: List = field(default_factory=list)        # host stage spans
    host_offset_ns: float = 0.0     # trace clock = perf_counter ns + this
    trace: Optional[Dict] = None
    plane: Optional[Dict] = None    # the first chip's trace plane
    planes: List[Dict] = field(default_factory=list)  # every chip's, in order
    trace_window: Optional[tuple] = None

    def __post_init__(self):
        if not self.planes and self.plane is not None:
            self.planes = [self.plane]


def window_steps(seconds: float, step_s: float) -> int:
    return max(2, int(math.ceil(seconds / max(step_s, 1e-3))))


def execute(root: str, workload: str, seed: int, seconds: float,
            trace: bool, *, t_start: float) -> Dict:
    """One run of one cell; returns the result's fields."""
    cell = load_cell(root, workload)
    import jax
    devs = require_chips(cell.chips)
    use_compile_cache(root)
    peak = device_peak(cell.bench_dir, devs[0].device_kind)
    counter = CompileCounter()
    phases = [("start to chip", time.perf_counter())]

    prog = Program(cell.model, seed, devs)
    first = CHECK_STEPS + WARM_STEPS
    prog.feed.steps = traffic.make_batches(cell.bench_dir, cell.mix,
                                           cell.model, seed, 0, first,
                                           cell.shards)
    phases.append(("engine and steps", time.perf_counter()))
    budget = cell.shards * cell.mix["token_budget"]
    fill = [sum(s[1]) / budget for s in prog.feed.steps]
    prog.init_state()
    phases.append(("state", time.perf_counter()))
    mine = prog.checked_steps()
    phases.append(("checked steps", time.perf_counter()))
    prog.marks = []
    prog.run(CHECK_STEPS, WARM_STEPS)
    phases.append(("warm steps", time.perf_counter()))
    step_s = (prog.marks[-1] - prog.marks[0]) / (len(prog.marks) - 1)
    n = window_steps(seconds, step_s)
    prog.feed.steps += traffic.make_batches(cell.bench_dir, cell.mix,
                                            cell.model, seed, first, n,
                                            cell.shards)
    phases.append(("window steps", time.perf_counter()))
    log(f"[setup] {cell.name}: {n} window steps at ~{step_s:.3f} s a step "
        f"(warm); fill of the checked and warm steps "
        f"{[round(f, 4) for f in fill]}")
    t = [t_start] + [p[1] for p in phases]
    log("[setup] seconds: " + ", ".join(
        f"{name} {b - a:.2f}" for (name, _), a, b in zip(phases, t, t[1:])))
    run = Run(model=cell.model, mix=cell.mix, chips=cell.chips, peak=peak)
    run.steps = [{"lengths": s[1], "tokens": int(sum(s[1]))}
                 for s in prog.feed.steps[first:first + n]]
    trace_dir = os.path.join(root, ".bench_trace", workload)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    setup_s = time.perf_counter() - t_start

    counter.on = True
    with jax.profiler.TraceAnnotation("bench_window"):
        w0 = time.perf_counter()
        recs = prog.run(first, n)
        w1 = time.perf_counter()
    counter.on = False
    if trace:
        jax.profiler.stop_trace()
    run.window_s = w1 - w0
    run.events = list(prog.engine.events)
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devs)
    tokens = sum(r["tokens"] for r in recs)
    failed = sum(1 for r in recs if not math.isfinite(r["loss"]))
    log(f"[window] {n} steps, {tokens} tokens in {run.window_s:.4f} s; "
        f"fill {tokens / (n * budget):.4f}; "
        f"{counter.count} compilations inside the window")
    prog.close()
    del prog
    gc.collect()

    out: Dict[str, Any] = {"attempted": n, "failed": failed,
                           "compiles_in_window": counter.count}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(mem)}
    if trace:
        import xplane
        tr = xplane.load(xplane.find(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        attach_trace(run, tr, w0)
        metrics = {}
        for m in cell.per_layer:
            v = metric_reader(cell.bench_dir, m["name"]).read(run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        busy = [xplane.busy_ns(p, run.trace_window) for p in run.planes]
        device["busy_s"] = sum(busy) / len(busy) * 1e-9
        device["window_s"] = (run.trace_window[1] - run.trace_window[0]) * 1e-9
        out["breakdown"] = breakdown(run)
    else:
        metrics = {"train_tokens_per_s": {"value": tokens / run.window_s,
                                          "unit": "tokens/s"},
                   "setup_s": {"value": setup_s, "unit": "s"}}
        metrics = {m["name"]: metrics[m["name"]] for m in cell.end_to_end}
    out["metrics"] = metrics
    out["device"] = device

    ref = reference_module(cell).run(
        cell.model, seed, [s[0] for s in traffic.make_batches(
            cell.bench_dir, cell.mix, cell.model, seed, 0, CHECK_STEPS,
            cell.shards)])
    found = compare.gaps(mine, ref)
    ok, checks = compare.judge(found, cell.limits)
    out["correct"] = bool(ok and failed == 0)
    out["checks"] = checks
    log(f"[check] losses program {mine['losses']} reference {ref['losses']}")
    return out


def attach_trace(run: Run, tr: Dict, w0: float) -> None:
    import xplane
    run.trace = tr
    run.trace_window = xplane.window(tr)
    planes = xplane.device_planes(tr)[:run.chips]
    if run.trace_window is None or len(planes) < run.chips:
        raise RuntimeError(f"the trace holds no window marker or fewer "
                           f"than {run.chips} devices")
    run.planes, run.plane = planes, planes[0]
    run.host_offset_ns = run.trace_window[0] - w0 * 1e9


def breakdown(run: Run) -> Dict:
    import xplane
    spans = [(e.stage, e.start * 1e9 + run.host_offset_ns,
              e.end * 1e9 + run.host_offset_ns) for e in run.events]
    gaps = xplane.idle_gaps(run.plane, run.trace_window)
    return {"device_ops": xplane.top_ops(run.plane, run.trace_window),
            "idle_gaps": xplane.attribute_gaps(gaps, spans)}


def result_line(out: Dict) -> str:
    """The last stdout line; ``checks`` comes last."""
    keys = ["correct", "attempted", "failed", "metrics", "device"]
    if "breakdown" in out:
        keys.append("breakdown")
    line = {k: out[k] for k in keys}
    line["checks"] = out["checks"]
    return json.dumps(line)


def print_checks(checks: Dict) -> None:
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
