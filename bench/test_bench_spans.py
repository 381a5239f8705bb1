"""The readers of the program's own spans (``metrics/_spans.py``,
``host_wait_ms_per_step``, ``host_dispatch_ms_per_step``) on a hand-made
trace; on a small recorded one, two steps of ``hstu-large.long-hist`` on
one TPU v5e chip (program spans on their threads, device ops and
programs); and on a trace of a program without those spans, where each
reads nothing."""
import gzip
import json
import os
from types import SimpleNamespace

import pytest

import _spans
import harness
import xplane
from harness import metric_reader
from tiny_cell import BENCH

# window 1000..3000 ns, two steps. Main thread: per step emb_fwd, dense_fwd,
# dense_bwd around loss_sync, emb_bwd around step_callback, then a wait on
# the unique future; Python-tracer events interleave. A worker thread
# runs the host stages, whose spans the readers must not count.
MAIN = [["bench_window", 1000.0, 2000.0],
        ["prepare_run", 900.0, 50.0],
        ["$engine.py:375 _hk_dense_bwd", 1150.0, 250.0]]
for base in (1000.0, 2000.0):
    MAIN += [["emb_fwd", base, 100.0],
             ["dense_fwd", base + 100, 50.0],
             ["dense_bwd", base + 150, 250.0],
             ["loss_sync", base + 200, 190.0],
             ["emb_bwd", base + 400, 200.0],
             ["step_callback", base + 550, 30.0],
             ["wait_unique", base + 600, 50.0]]
MAIN.append(["wait_dataload", 2950.0, 100.0])       # clipped to 50 ns
WORKER = [["unique", 1000.0, 1500.0], ["h2d", 1600.0, 100.0],
          ["a2a", 1550.0, 200.0], ["wait_unique", 1800.0, 10.0]]
HAND = {"planes": [
    {"name": "/host:CPU", "lines": [
        {"name": "python3", "events": WORKER},
        {"name": "python3", "events": MAIN}]},
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [["fusion.1", 1000.0, 2000.0]]}]}]}


def hand_run(trace=HAND):
    return SimpleNamespace(trace=trace, trace_window=xplane.window(trace),
                           steps=[{}, {}])


def read(name, run):
    return metric_reader(BENCH, name).read(run)


def test_main_thread_is_the_line_with_the_marker():
    assert _spans.main_thread(HAND) is MAIN
    assert _spans.main_thread({"planes": []}) == []
    win = xplane.window(HAND)
    assert _spans.count(MAIN, win, _spans.DEVICE_STAGES) == 8
    assert _spans.span_ns(MAIN, win, ("wait_dataload",)) == 50.0


def test_host_wait_reads_the_main_thread_waits():
    # 2 x 50 ns wait_unique + 50 ns of wait_dataload inside the window;
    # the worker's wait_unique is not the main thread's
    assert read("host_wait_ms_per_step", hand_run()) == pytest.approx(
        150e-6 / 2)


def test_host_dispatch_leaves_out_the_loss_read_and_callback():
    # per step (100 + 50 + 250 + 200) - (190 + 30) = 380 ns
    assert read("host_dispatch_ms_per_step", hand_run()) == pytest.approx(
        380e-6)


@pytest.mark.parametrize("name", ["host_wait_ms_per_step",
                                  "host_dispatch_ms_per_step"])
def test_program_without_spans_reads_nothing(name):
    # the recorded trace of a program that opened no spans of its own
    path = os.path.join(BENCH, "data", "trace_hstu_long_2steps.json.gz")
    with gzip.open(path, "rt") as f:
        tr = json.load(f)
    assert read(name, hand_run(tr)) is None


RECORDED = os.path.join(BENCH, "data", "trace_hstu_long_spans_2steps.json.gz")


@pytest.fixture(scope="module")
def recorded():
    run = harness.Run(model={}, mix={}, chips=1, peak={})
    harness.attach_trace(run, xplane.load(RECORDED), 0.0)
    run.steps = [{}, {}]
    return run


def test_recorded_trace_one_span_per_stage_per_step(recorded):
    main = _spans.main_thread(recorded.trace)
    win = recorded.trace_window
    for stage in _spans.DEVICE_STAGES + ("loss_sync", "step_callback"):
        assert _spans.count(main, win, (stage,)) == 2, stage
    for sub, stage in (("loss_sync", "dense_bwd"),
                       ("step_callback", "emb_bwd")):
        outer = [(e[1], e[1] + e[2]) for e in main if e[0] == stage]
        for e in main:
            if e[0] == sub:
                assert any(a <= e[1] and e[1] + e[2] <= b
                           for a, b in outer), sub
    # the host stages run on worker threads, never on the main one
    assert not _spans.count(main, win, ("dataload", "a2a", "unique", "h2d"))


def test_recorded_trace_readers(recorded):
    # the loss read holds nearly all of a step's host time on the main
    # thread; the rest, the dispatch, is a few ms of a 1.4 s step
    wait = read("host_wait_ms_per_step", recorded)
    dispatch = read("host_dispatch_ms_per_step", recorded)
    assert wait == pytest.approx(9.5588395)
    assert dispatch == pytest.approx(6.314296)
    step_ms = (recorded.trace_window[1] - recorded.trace_window[0]) * 1e-6 / 2
    assert 1300 < step_ms < 1500
    assert dispatch < 0.01 * step_ms
