"""Observability layer: tracer, exporter, registry, derived gauges, and
regression tests that migrated stats surfaces stay bit-unchanged."""
import json
import os
import tempfile
import threading

import jax
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.core.pipeline import StageEvent, timeline_report
from repro.launch.roofline import device_peak
from repro.data.synthetic import synth_jagged_batch
from repro.models.model_zoo import get_bundle
from repro.obs import (Obs, MetricsRegistry, Tracer, busy_from_intervals,
                       measured_mfu, pipeline_goodput, token_imbalance,
                       trace_busy_by_track)
from repro.training.engine import GREngine


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
    t.instant("i")
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
    t.instant("marker", "s1", now=0.75)
    trace = t.to_chrome_trace(process_name="proc")
    # JSON round-trip must be clean (Perfetto loads the file as-is)
    trace = json.loads(json.dumps(trace))
    assert trace["displayTimeUnit"] == "ms"
    evs = trace["traceEvents"]
    assert all(ev["ph"] in ("X", "M", "i") for ev in evs)
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
    assert pipeline_goodput([]) == {"wall_s": 0.0, "busy_s": 0.0,
                                    "goodput": 0.0, "bubble_ratio": 0.0}
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


def test_ingest_recovery_events_lays_spans_cumulatively():
    class Ev:
        failed_step, restored_step, steps_lost = 7, 5, 2
        error, wall_s = "boom", 0.5

    t = Tracer()
    assert t.ingest_recovery_events([Ev(), Ev()], t0=1.0) == 2
    spans = t.spans()
    assert (spans[0].start, spans[0].end) == (1.0, 1.5)
    assert (spans[1].start, spans[1].end) == (1.5, 2.0)
    assert spans[0].args["failed_step"] == 7


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


def test_pipeline_goodput():
    evs = [StageEvent("dataload", 0, 0.0, 1.0),
           StageEvent("dense_fwd", 0, 0.5, 2.0),
           StageEvent("emb_bwd", 0, 3.0, 4.0)]
    gp = pipeline_goodput(evs)
    assert gp["wall_s"] == 4.0 and gp["busy_s"] == 3.0
    assert gp["goodput"] == pytest.approx(0.75)
    assert gp["bubble_ratio"] == pytest.approx(0.25)


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
                "train_pipeline_goodput", "train_pipeline_bubble_ratio",
                "train_timeline_wall_s"):
        assert fam in snap, fam
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
