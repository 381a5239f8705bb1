"""The trace reduction: busy and idle time as a union of op intervals,
kernel time by name, ops attributed to their program, idle gaps attributed
to the host stage, on a hand-made trace and on a small recorded one: two
steps of ``hstu-large.long-hist`` on one TPU v5e chip, device ops and
programs only, names as the HLO gives them."""
import gzip
import json
import os

import pytest

import xplane
from tiny_cell import BENCH

# window 1000..2000 ns; ops overlap at 1100..1300; a gap 1500..1800
HAND = {"planes": [
    {"name": "/host:CPU", "lines": [
        {"name": "python", "events": [["bench_window", 1000.0, 1000.0]]}]},
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ["jit_dense_fwd_bwd(1)", 1000.0, 500.0],
            ["jit_emb_bwd(2)", 1800.0, 300.0]]},
        {"name": "XLA Ops", "events": [
            ["attn_fwd", 900.0, 300.0],          # clipped to 1000..1200
            ["fusion.3", 1100.0, 200.0],
            ["neg_fused_fwd", 1300.0, 200.0],
            ["lookup_wscatter.1", 1800.0, 100.0],
            ["fusion.9", 1900.0, 200.0]]}]}]}


def test_window_busy_and_gaps():
    win = xplane.window(HAND)
    assert win == (1000.0, 2000.0)
    plane = xplane.device_planes(HAND)[0]
    assert xplane.busy_ns(plane, win) == 200 + 100 + 200 + 100 + 100
    assert xplane.idle_gaps(plane, win) == [(1500.0, 1800.0)]


def test_kernels_and_programs():
    plane = xplane.device_planes(HAND)[0]
    win = (0.0, 3000.0)
    assert xplane.kernel_ns(plane, win, ("attn_fwd",)) == 300.0
    assert xplane.kernel_ns(plane, win, ("lookup_wscatter",)) == 100.0
    dense = xplane.module_ops(plane, win, "jit_dense_fwd_bwd")
    assert [e[0] for e in dense] == ["fusion.3", "neg_fused_fwd"]
    emb = xplane.module_ops(plane, win, "jit_emb_bwd")
    assert [e[0] for e in emb] == ["lookup_wscatter.1", "fusion.9"]
    top = xplane.top_ops(plane, win, 2)
    assert [t[0] for t in top] == ["fusion", "attn_fwd"]
    assert top[0][1] == pytest.approx(400e-9)


def test_gap_attribution():
    gaps = [(1500.0, 1800.0), (1950.0, 1960.0)]
    spans = [("emb_bwd", 1400.0, 1700.0), ("a2a", 1650.0, 1700.0)]
    got = xplane.attribute_gaps(gaps, spans)
    assert [g[0] for g in got] == ["emb_bwd", "unattributed"]
    assert [g[1] for g in got] == pytest.approx([300e-9, 10e-9])


RECORDED = os.path.join(BENCH, "data", "trace_hstu_long_2steps.json.gz")


@pytest.fixture(scope="module")
def recorded():
    tr = xplane.load(RECORDED)
    return tr, xplane.device_planes(tr)[0], xplane.window(tr)


def test_recorded_trace_kernels(recorded):
    _, plane, win = recorded
    # every kernel the readers name ran in the two steps
    for k in ("attn_fwd", "attn_bwd_kv", "attn_bwd_q", "neg_fused_fwd",
              "neg_fused_bwd", "lookup_wscatter", "lookup_runsum"):
        assert xplane.kernel_ns(plane, win, (k,)) > 0, k
    busy = xplane.busy_ns(plane, win) / (win[1] - win[0])
    assert 0.9 < busy <= 1.0
    assert xplane.top_ops(plane, win, 1)[0][0] == "neg_fused_bwd"


def test_recorded_trace_programs(recorded):
    _, plane, win = recorded
    dense = {xplane.base(e[0]) for e in
             xplane.module_ops(plane, win, "jit_dense_fwd_bwd")}
    assert {"attn_fwd", "neg_fused_bwd", "lookup_wscatter"} <= dense
    emb = {xplane.base(e[0]) for e in
           xplane.module_ops(plane, win, "jit_emb_bwd")}
    assert "lookup_runsum" in emb and "attn_fwd" not in emb
    assert not any(xplane.base(e[0]) in xplane.CONTAINERS
                   for e in xplane.module_ops(plane, win,
                                              "jit_dense_fwd_bwd"))


def test_metric_readers_on_the_recorded_trace(recorded):
    import harness
    tr, plane, win = recorded
    with open(os.path.join(BENCH, "configs", "hstu-large.json")) as f:
        model = json.load(f)
    peak = harness.device_peak(BENCH, "TPU v5 lite")
    run = harness.Run(model=model, mix={}, chips=1, peak=peak,
                      steps=[{"lengths": [2048] * 4, "tokens": 8192}] * 2,
                      window_s=(win[1] - win[0]) * 1e-9, plane=plane,
                      trace_window=win)
    read = lambda n: harness.metric_reader(BENCH, n).read(run)
    assert 0.0 <= read("device_idle_share") < 10.0
    for n in ("attn_roofline", "neg_roofline", "train_mfu"):
        assert 0.0 < read(n) < 100.0
    steps = {n: read(n) for n in ("attn_ms_per_step", "neg_ms_per_step",
                                  "sparse_update_ms_per_step",
                                  "dense_ms_per_step")}
    assert all(v > 0 for v in steps.values())
    # the layers do not add up to more than the window
    assert sum(steps.values()) * 2 < (win[1] - win[0]) * 1e-6
