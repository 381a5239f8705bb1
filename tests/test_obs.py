"""Observability layer: tracer, exporter, registry, derived gauges, the
engine's spans and layer scopes in a profiler trace, and regression tests
that migrated stats surfaces stay bit-unchanged."""
import glob
import importlib.util
import json
import os
import tempfile
import threading
from collections import Counter

import jax
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.core.pipeline import STAGES, StageEvent, timeline_report
from repro.launch.roofline import device_peak, gr_model_flops
from repro.data.synthetic import synth_jagged_batch
from repro.models.model_zoo import get_bundle
from repro.obs import (Obs, MetricsRegistry, Tracer, busy_from_intervals,
                       measured_mfu, token_imbalance, trace_busy_by_track)
from repro.training.engine import GREngine
from repro.training.trainer import host_unique_candidates

BENCH = os.path.join(os.path.dirname(__file__), "..", "bench")


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

def test_overlapping_and_nested_spans_union():
    t = Tracer()
    # overlapping on one track: [0,2] ∪ [1,3] = 3s busy
    t.record("a", "s", 0.0, 2.0)
    t.record("b", "s", 1.0, 3.0)
    # nested: [10,14] contains [11,12] — still 4s
    t.record("outer", "n", 10.0, 14.0)
    t.record("inner", "n", 11.0, 12.0)
    busy = t.busy_by_track()
    assert busy == {"n": 4.0, "s": 3.0}
    assert t.wall_span() == (0.0, 14.0)


def test_busy_from_intervals_edge_cases():
    assert busy_from_intervals([]) == 0.0
    assert busy_from_intervals([(1.0, 1.0)]) == 0.0          # zero width
    assert busy_from_intervals([(0, 1), (1, 2)]) == 2.0      # touching
    assert busy_from_intervals([(0, 5), (1, 2), (6, 7)]) == 6.0


def test_span_context_manager_and_injected_clock():
    clock = iter([1.0, 2.5, 3.0, 3.25])
    t = Tracer(clock=lambda: next(clock))
    with t.span("work", "main", step=7):
        pass
    with t.span("more"):                       # track defaults to name
        pass
    spans = t.spans()
    assert (spans[0].start, spans[0].end) == (1.0, 2.5)
    assert spans[0].args == {"step": 7}
    assert spans[1].track == "more" and spans[1].dur == 0.25


def test_disabled_tracer_records_nothing():
    t = Tracer(enabled=False)
    with t.span("x", "y"):
        pass
    t.record("a", "b", 0.0, 1.0)
    assert len(t) == 0
    assert t.busy_by_track() == {}
    # shared null context: span() must not allocate per call
    assert t.span("p") is t.span("q")


def test_cross_thread_span_recording():
    t = Tracer()
    barrier = threading.Barrier(4)

    def worker(k):
        barrier.wait()
        for i in range(50):
            t.record(f"op{i}", f"thread{k}", float(i), float(i) + 0.5)

    threads = [threading.Thread(target=worker, args=(k,)) for k in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert len(t) == 200
    busy = t.busy_by_track()
    assert set(busy) == {f"thread{k}" for k in range(4)}
    assert all(abs(v - 25.0) < 1e-9 for v in busy.values())


def test_chrome_trace_schema():
    t = Tracer()
    t.record("a", "s1", 0.0, 1.0, {"step": 0})
    t.record("b", "s2", 0.5, 2.0)
    trace = t.to_chrome_trace(process_name="proc")
    # JSON round-trip must be clean (Perfetto loads the file as-is)
    trace = json.loads(json.dumps(trace))
    assert trace["displayTimeUnit"] == "ms"
    evs = trace["traceEvents"]
    assert all(ev["ph"] in ("X", "M") for ev in evs)
    meta = [ev for ev in evs if ev["ph"] == "M"]
    assert any(ev["name"] == "process_name" and
               ev["args"]["name"] == "proc" for ev in meta)
    names = {ev["args"]["name"] for ev in meta if ev["name"] == "thread_name"}
    assert names == {"s1", "s2"}
    for ev in evs:
        if ev["ph"] == "X":
            assert ev["ts"] >= 0 and ev["dur"] >= 0
            assert isinstance(ev["args"], dict)
    # one distinct tid per track
    tids = {ev["tid"] for ev in evs if ev["ph"] == "X"}
    assert len(tids) == 2


def test_zero_event_export_and_ratios():
    t = Tracer()
    trace = t.to_chrome_trace()
    assert trace["traceEvents"][0]["name"] == "process_name"
    assert trace_busy_by_track(trace) == {}
    assert t.busy_by_track() == {}
    assert token_imbalance([]) == 0.0
    assert measured_mfu(0.0, 0.0, 197e12) == 0.0
    assert MetricsRegistry().snapshot() == {}


def test_ingest_stage_events_merges_and_decorates():
    t = Tracer()
    events = [StageEvent("dense_fwd", 0, 0.0, 1.0),
              StageEvent("dense_bwd", 0, 1.0, 2.0),
              StageEvent("dataload", 1, 0.5, 0.75)]
    recs = {0: {"loss": 1.5, "tokens": 64,
                "cache": {"hit_rate": 0.9, "hits": 9}}}
    n = t.ingest_stage_events(events, records=recs)
    assert n == 3
    busy = t.busy_by_track()
    # dense fwd/bwd merge onto one track, as in timeline_report
    assert busy["dense_fwd_bwd"] == 2.0 and busy["dataload"] == 0.25
    sp = [s for s in t.spans() if s.name == "dense_fwd"][0]
    assert sp.args["loss"] == 1.5 and sp.args["cache_hit_rate"] == 0.9


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_registry_counter_gauge_histogram():
    r = MetricsRegistry()
    r.counter("steps_total", "steps").inc()
    r.counter("steps_total").inc(2)
    r.gauge("loss").set(1.25)
    h = r.histogram("step_s", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    snap = r.snapshot()
    assert snap["steps_total"]["values"][""] == 3.0
    assert snap["loss"]["values"][""] == 1.25
    hs = snap["step_s"]["values"][""]
    assert hs["count"] == 3 and hs["sum"] == pytest.approx(5.55)
    assert hs["buckets"] == {"0.1": 1, "1.0": 2, "+Inf": 3}
    with pytest.raises(ValueError):
        r.counter("steps_total").inc(-1)
    with pytest.raises(ValueError):
        r.gauge("steps_total")                  # kind conflict


def test_registry_labels_and_stable_snapshot():
    r = MetricsRegistry()
    r.gauge("busy_s", labels={"stage": "a2a"}).set(1.0)
    r.gauge("busy_s", labels={"stage": "dataload"}).set(2.0)
    r.counter("zz").inc()
    r.counter("aa").inc()
    snap = r.snapshot()
    assert list(snap) == sorted(snap)           # sorted family names
    assert set(snap["busy_s"]["values"]) == {"stage=a2a", "stage=dataload"}
    # identical key set on a second snapshot (stability contract)
    assert list(snap) == list(r.snapshot())


def test_registry_prometheus_text():
    r = MetricsRegistry()
    r.counter("train_steps_total", "steps done").inc(4)
    r.gauge("serve_p50_s", labels={"engine": "stream"}).set(0.002)
    r.histogram("ckpt_save_s", buckets=(1.0,)).observe(0.5)
    text = r.to_prometheus()
    assert "# HELP train_steps_total steps done" in text
    assert "# TYPE train_steps_total counter" in text
    assert "train_steps_total 4.0" in text
    assert 'serve_p50_s{engine="stream"} 0.002' in text
    assert 'ckpt_save_s_bucket{le="1.0"} 1' in text
    assert "ckpt_save_s_count 1" in text


def test_registry_publish_flattens_nested_stats():
    r = MetricsRegistry()
    n = r.publish("serve", {"latency": {"p50_s": 0.001, "count": 3},
                            "mode": "warm",        # string: skipped
                            "hit": True,           # bool -> 1.0
                            "occupancy": {"rows": 4}})
    assert n == 4
    snap = r.snapshot()
    assert snap["serve_latency_p50_s"]["values"][""] == 0.001
    assert snap["serve_hit"]["values"][""] == 1.0
    assert snap["serve_occupancy_rows"]["values"][""] == 4.0
    assert "serve_mode" not in snap


# ---------------------------------------------------------------------------
# derived gauges
# ---------------------------------------------------------------------------

def test_measured_mfu():
    # 1 TFLOP in 0.01 s on a 197 TFLOP/s part
    peak = device_peak("TPU v5 lite")["flops"]
    assert measured_mfu(1e12, 0.01, peak) == pytest.approx(
        1e12 / (0.01 * 197e12))
    assert measured_mfu(1e12, 0.01, peak_flops=1e14) == pytest.approx(1.0)
    assert measured_mfu(1e12, 0.0, peak) == 0.0
    # a device kind without a published peak gets no MFU, not a v5e guess
    assert device_peak("cpu") is None
    assert measured_mfu(1e12, 0.01, None) is None


def test_token_imbalance():
    # loads (100, 50, 50): makespan 100, mean ~66.7 → (100-66.7)/100
    assert token_imbalance([100, 50, 50]) == pytest.approx(1 / 3)
    assert token_imbalance([64, 64, 64, 64]) == 0.0
    assert token_imbalance([5]) == 0.0
    assert token_imbalance([0, 0]) == 0.0


# ---------------------------------------------------------------------------
# engine integration + migration regression
# ---------------------------------------------------------------------------

def _tiny_gr(obs=None, vocab=512):
    cfg = reduced(ARCHS["hstu-tiny"]).replace(num_negatives=8,
                                              vocab_size=vocab)
    b = get_bundle(cfg)

    def data_fn(i):
        return synth_jagged_batch(jax.random.PRNGKey(i), 2, 96, vocab, 8)

    return GREngine(b, data_fn, obs=obs, workers=2)


def test_engine_obs_losses_bit_identical():
    res_obs = _tiny_gr(obs=Obs()).run(4)
    res_plain = _tiny_gr(obs=None).run(4)
    assert [r["loss"] for r in res_obs] == [r["loss"] for r in res_plain]
    # records stay lean without obs (migration keeps old surface exact)
    assert sorted(res_plain[0]) == ["loss", "step", "tokens"]
    assert {"mfu", "imbalance", "step_wall_s"} <= set(res_obs[0])


def test_engine_noop_obs_adds_nothing():
    obs = Obs.noop()
    res = _tiny_gr(obs=obs).run(3)
    assert sorted(res[0]) == ["loss", "step", "tokens"]
    assert len(obs.tracer) == 0
    assert obs.snapshot() == {}


def test_engine_trace_matches_timeline_report():
    obs = Obs()
    eng = _tiny_gr(obs=obs)
    eng.run(5)
    stage_s = eng.timeline_report()["stage_s"]
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "trace.json")
        obs.export_trace(path)
        with open(path) as f:
            busy = trace_busy_by_track(json.load(f))
    for stage, ref in stage_s.items():
        assert busy[stage] == pytest.approx(ref, rel=0.01), stage


def test_engine_metrics_namespace():
    obs = Obs()
    eng = _tiny_gr(obs=obs)
    eng.run(3)
    snap = obs.snapshot()
    for fam in ("train_steps_total", "train_tokens_total", "train_loss",
                "train_token_imbalance",
                "train_step_wall_s", "train_step_s",
                "train_timeline_wall_s"):
        assert fam in snap, fam
    assert not [f for f in snap if "goodput" in f or "bubble" in f]
    assert snap["train_steps_total"]["values"][""] == 3.0
    if device_peak(jax.devices()[0].device_kind) is None:
        # no published peak for this device: MFU is not measured
        assert "train_mfu_measured" not in snap
    else:
        mfu = snap["train_mfu_measured"]["values"][""]
        assert 0.0 < mfu < 1.0
    assert snap["train_step_s"]["values"][""]["count"] == 3
    # prometheus rendering of the full engine namespace stays well-formed
    text = obs.to_prometheus()
    assert "# TYPE train_step_s histogram" in text


# ---------------------------------------------------------------------------
# the engine's spans and layer scopes in a profiler trace
# ---------------------------------------------------------------------------

SPAN_STEPS = 4
SUB_SPANS = ("loss_sync", "step_callback", "h2d", "prepare_run")
WAITS = tuple(f"wait_{s}" for s in STAGES)


def _span_engine(schedule):
    cfg = reduced(ARCHS["hstu-tiny"]).replace(num_negatives=8,
                                              vocab_size=512)

    def data_fn(i):
        return synth_jagged_batch(jax.random.PRNGKey(i), 2, 96, 512, 8)

    return GREngine(get_bundle(cfg), data_fn, schedule=schedule, workers=2,
                    step_callback=lambda i, rec, state: None)


def _trace_spans(trace_dir):
    """The program's spans in a profiler trace, one list per host thread
    that has any: ``(name, start_ns, end_ns, step)``."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    names = set(STAGES) | set(SUB_SPANS) | set(WAITS)
    threads = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            spans = []
            for e in line.events:
                if e.name in names:
                    step = dict(e.stats).get("step")
                    spans.append((e.name, e.start_ns,
                                  e.start_ns + e.duration_ns,
                                  None if step is None else int(step)))
            if spans:
                threads.append(spans)
    return threads


@pytest.fixture(scope="module", params=("algorithm1", "flat"))
def profiled(request):
    """The same tiny run from one seed, plainly and inside a profiler
    session (Python tracer off)."""
    plain = _span_engine(request.param)
    plain_recs = plain.run(SPAN_STEPS)
    eng = _span_engine(request.param)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            recs = eng.run(SPAN_STEPS)
        finally:
            jax.profiler.stop_trace()
        threads = _trace_spans(d)
    return request.param, eng, recs, plain, plain_recs, threads


def _nested(line, sub, stage):
    """Every ``sub`` span of a thread lies inside a ``stage`` span of the
    same step; returns how many there are."""
    outer = [(a, b, st) for n, a, b, st in line if n == stage]
    subs = [(a, b, st) for n, a, b, st in line if n == sub]
    for a, b, st in subs:
        assert any(oa <= a and b <= ob and ost == st
                   for oa, ob, ost in outer), (sub, stage, st)
    return len(subs)


def test_stage_spans_in_profiler_trace(profiled):
    schedule, eng, _, _, _, threads = profiled
    main, = [t for t in threads if any(n == "prepare_run" for n, *_ in t)]
    spans = [sp for t in threads for sp in t]
    stages = Counter((n, st) for n, _, _, st in spans if n in STAGES)
    # one interval, two sinks: the trace holds exactly the engine's events
    assert stages == Counter((e.stage, e.batch) for e in eng.events)
    if schedule == "algorithm1":
        assert stages == Counter({(s, i): 1 for s in STAGES
                                  for i in range(SPAN_STEPS)})
    device = ("emb_fwd", "dense_fwd", "dense_bwd", "emb_bwd")
    assert {n for n, *_ in main if n in STAGES} >= set(device)
    (_, p0, p1, _), = [sp for sp in main if sp[0] == "prepare_run"]
    assert p1 <= min(a for n, a, _, _ in spans if n in STAGES)
    assert _nested(main, "loss_sync", "dense_bwd") == SPAN_STEPS
    assert _nested(main, "step_callback", "emb_bwd") == SPAN_STEPS
    assert sum(_nested(t, "h2d", "a2a") for t in threads) == SPAN_STEPS
    waits = [n for n, *_ in main if n in WAITS]
    assert not [n for t in threads if t is not main
                for n, *_ in t if n in WAITS]
    if schedule == "algorithm1":
        # the main thread joins every host future it consumes
        assert set(waits) == {"wait_dataload", "wait_a2a", "wait_unique"}
    else:
        assert waits == []


def test_profiler_session_leaves_math_bit_identical(profiled):
    _, eng, recs, plain, plain_recs, _ = profiled
    assert [r["loss"] for r in recs] == [r["loss"] for r in plain_recs]
    for a, b in zip(jax.tree.leaves(eng.state), jax.tree.leaves(plain.state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def scope_names(op_name):
    """The scopes of an HLO op's ``op_name`` path, each component with
    its transform wrappers stripped (``transpose(jvp(loss))`` -> loss)."""
    out = set()
    for part in op_name.split("/"):
        while part.endswith(")") and "(" in part:
            part = part[part.index("(") + 1:-1]
        out.add(part)
    return out


@pytest.fixture(scope="module")
def stage_scopes():
    """Scope names in the compiled HLO metadata of each jitted stage."""
    import re

    import jax.numpy as jnp
    eng = _span_engine("algorithm1")
    eng.run(1)
    st, nb = eng.state, eng._data_fn(0)
    dev = {k: jnp.asarray(v) for k, v in nb.items() if k != "weights"}
    s, first, _ = host_unique_candidates(nb, st.table.master.shape[0])
    x = eng._j_emb_fwd(st.table.master, dev)
    dout = eng._j_dense(st.dense, st.table, dev, x, None)
    lowered = {
        "emb_fwd": eng._j_emb_fwd.lower(st.table.master, dev),
        "dense_fwd_bwd": eng._j_dense.lower(st.dense, st.table, dev, x,
                                            None),
        "emb_bwd": eng._j_emb_bwd.lower(
            st.dense, st.dense_opt, st.table, dout, dev, jnp.asarray(s),
            jnp.asarray(first), apply_sparse=True,
            slots=st.pending_ids.shape[0] or None),
        "sparse_apply": eng._j_sparse_apply.lower(
            st.table, st.pending_ids, st.pending_rows)}
    out = {}
    for stage, low in lowered.items():
        text = low.compile().as_text()
        out[stage] = set().union(*map(scope_names,
                                      re.findall(r'op_name="([^"]*)"', text)))
    return out


@pytest.mark.parametrize("scope,stages", [
    ("input_gather", ("emb_fwd",)),
    ("blocks", ("dense_fwd_bwd",)),
    ("loss", ("dense_fwd_bwd",)),
    ("table_grad", ("emb_bwd",)),
    ("adamw", ("emb_bwd",)),
    ("adagrad", ("emb_bwd", "sparse_apply"))])
def test_layer_scopes_in_stage_metadata(stage_scopes, scope, stages):
    for stage in stages:
        assert scope in stage_scopes[stage], (scope, stage)
    others = set(stage_scopes) - set(stages)
    assert not [o for o in others if scope in stage_scopes[o]]


def _bench_flops():
    spec = importlib.util.spec_from_file_location(
        "bench_flops", os.path.join(BENCH, "flops.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("arch", ["hstu-tiny", "hstu-large", "fuxi-large"])
def test_gr_model_flops_matches_bench_count(arch):
    cfg = ARCHS[arch]
    if arch == "hstu-tiny":
        cfg = reduced(cfg)
    model = {"block": cfg.gr_block, "d_model": cfg.d_model,
             "num_heads": cfg.num_heads, "qkv_dim": cfg.qkv_dim,
             "d_ff": cfg.d_ff, "num_layers": cfg.num_layers,
             "num_negatives": cfg.num_negatives}
    lengths = [[2048, 2048, 1500, 7, 0], [1, 300, 0, 0, 0]]
    flat = [l for row in lengths for l in row]
    assert gr_model_flops(cfg, lengths) == \
        _bench_flops().model_flops(model, flat)


def test_engine_mfu_counts_the_step_work(monkeypatch):
    from repro.training import engine as engine_mod
    monkeypatch.setattr(engine_mod, "device_peak", lambda kind: {"flops": 1.0})
    eng = _tiny_gr(obs=Obs())
    for rec in eng.run(3):
        lengths = np.diff(np.asarray(eng._data_fn(rec["step"])["offsets"]),
                          axis=-1)
        assert rec["mfu"] == pytest.approx(
            gr_model_flops(eng.bundle.cfg, lengths) / rec["step_wall_s"])


def test_timeline_report_pure_function_regression():
    """timeline_report must be untouched by the obs migration: known
    event stream -> exact breakdown."""
    evs = [StageEvent("dataload", 0, 0.0, 1.0),
           StageEvent("dense_fwd", 0, 1.0, 2.0),
           StageEvent("dense_bwd", 0, 2.0, 4.0)]
    rep = timeline_report(evs)
    assert rep["wall_s"] == 4.0
    assert rep["stage_s"] == {"dataload": 1.0, "dense_fwd_bwd": 3.0}
    assert timeline_report([]) == {}


def test_resilient_run_checkpoint_metrics():
    obs = Obs()
    eng = _tiny_gr(obs=obs)
    with tempfile.TemporaryDirectory() as d:
        res = eng.run_resilient(4, ckpt_dir=d, ckpt_every=2,
                                async_save=False)
    assert len(res) == 4
    snap = obs.snapshot()
    assert snap["ckpt_save_s"]["values"][""]["count"] >= 2
    assert snap["ckpt_saves_total"]["values"][""] >= 2.0


def test_checkpoint_registry_direct():
    from repro.training import checkpoint as CKPT
    r = MetricsRegistry()
    tree = {"w": np.arange(6, dtype=np.float32).reshape(2, 3)}
    with tempfile.TemporaryDirectory() as d:
        CKPT.save(d, 1, tree, registry=r)
        out, used = CKPT.restore_with_step(d, tree, registry=r)
    assert used == 1
    np.testing.assert_array_equal(np.asarray(out["w"]), tree["w"])
    snap = r.snapshot()
    assert snap["ckpt_save_s"]["values"][""]["count"] == 1
    assert snap["ckpt_restore_s"]["values"][""]["count"] == 1
    assert snap["ckpt_restores_total"]["values"][""] == 1.0


# ---------------------------------------------------------------------------
# serving migration regression
# ---------------------------------------------------------------------------

def _tiny_serving():
    cfg = reduced(ARCHS["hstu-tiny"]).replace(vocab_size=300,
                                              max_seq_len=24)
    b = get_bundle(cfg)
    key = jax.random.PRNGKey(0)
    return cfg, b.init_dense(key), b.init_table(key)


def test_streaming_stats_unchanged_by_obs():
    from repro.serving.engine import StreamingRecallEngine
    cfg, dense, table = _tiny_serving()
    reqs = [(u, list(range(1, 6 + u)), list(range(10, 15 + u)))
            for u in range(4)]

    def run(obs):
        eng = StreamingRecallEngine(cfg, dense, table, max_users=8, k=15,
                                    retrieval_block=128,
                                    max_rows_per_tick=4, obs=obs)
        # injected now: latency stats become deterministic, so the dicts
        # compare exactly across the two engines
        results = eng.serve(reqs, now=5.0)
        return results, eng.stats()

    obs = Obs()
    r1, s1 = run(obs)
    r2, s2 = run(None)
    assert s1 == s2                      # bit-unchanged return value
    for a, b in zip(r1, r2):
        assert np.array_equal(a.item_ids, b.item_ids)
        assert np.array_equal(a.scores, b.scores)
    snap = obs.snapshot()
    assert snap["serve_latency_count"]["values"][""] == s1["latency"]["count"]
    assert "serve_occupancy_row_utilization" in snap
    assert "serve_compile_compiles" in snap
    tracks = {s.track for s in obs.tracer.spans()}
    assert "serve" in tracks and "serve_encode" in tracks


def test_recall_engine_stats_unchanged_by_obs():
    from repro.serving.engine import RecallEngine
    cfg, dense, table = _tiny_serving()
    reqs = [(u, list(range(1, 8)), list(range(10, 17))) for u in range(3)]

    def run(obs):
        eng = RecallEngine(cfg, dense, table, num_shards=1,
                           users_per_shard=4, k=15, retrieval_block=128,
                           obs=obs)
        results = eng.serve(reqs, now=2.0)
        return results, eng.stats()

    obs = Obs()
    r1, s1 = run(obs)
    r2, s2 = run(None)
    assert s1 == s2
    for a, b in zip(r1, r2):
        assert np.array_equal(a.item_ids, b.item_ids)
    snap = obs.snapshot()
    assert snap["serve_encoded_batches"]["values"][""] == \
        s1["encoded_batches"]
    assert {s.track for s in obs.tracer.spans()} == \
        {"serve_encode", "serve_rank"}


# ---------------------------------------------------------------------------
# benchmark summary aggregation
# ---------------------------------------------------------------------------

def test_bench_summary_aggregation(tmp_path, monkeypatch):
    from benchmarks.run import write_summary
    (tmp_path / "BENCH_alpha.json").write_text(json.dumps(
        {"us_per_call": 12.5, "nested": {"ratio": 0.5, "name": "x"}}))
    (tmp_path / "BENCH_beta.json").write_text(json.dumps({"ok": True}))
    (tmp_path / "BENCH_broken.json").write_text("{not json")
    path = write_summary(str(tmp_path))
    s = json.loads((tmp_path / "BENCH_summary.json").read_text())
    assert path.endswith("BENCH_summary.json")
    assert s["benches"]["alpha"] == {"us_per_call": 12.5,
                                     "nested.ratio": 0.5}
    assert s["benches"]["beta"] == {"ok": 1}
    assert "broken" not in s["benches"]
    assert "git_rev" in s
    # re-running includes the existing summary's siblings, never itself
    path2 = write_summary(str(tmp_path))
    s2 = json.loads((tmp_path / "BENCH_summary.json").read_text())
    assert "summary" not in s2["benches"]
