"""``correct`` comes out false for what it has to catch, on a tiny
configuration on the CPU: the control (the plain reference computed with
float8 matmul operands, in the program's place), and the run with its timed
path broken underneath: a step that returns its state unchanged, half of
each step's tokens left out of the loss with the mean taken over the rest,
and a sparse update of the item table that does not land, forgets AdaGrad's
accumulator, or lands the rows at once instead of one step late."""
import time

import jax
import jax.numpy as jnp
import pytest

import compare
import harness
import reference
import traffic
from tiny_cell import TINY, make_root

SEED = 2 ** 31 + 11


@pytest.mark.parametrize("block", ["hstu", "fuxi"])
def test_control_fails_the_limits(tmp_path, block):
    cell = harness.load_cell(make_root(tmp_path, block=block), "tiny.mix")
    steps = [s[0] for s in traffic.make_batches(
        cell.bench_dir, cell.mix, cell.model, SEED, 0, harness.CHECK_STEPS)]
    ref = reference.run(cell.model, SEED, steps)
    low = reference.run(cell.model, SEED, steps, low=jnp.float8_e4m3fn)
    ok, checks = compare.judge(compare.gaps(low, ref), cell.limits)
    assert not ok, checks


def _broken_run(tmp_path, model=None):
    out = harness.execute(make_root(tmp_path, model), "tiny.mix", SEED, 0.3,
                          False, t_start=time.perf_counter())
    return out["correct"], out["checks"]


def test_state_left_unchanged_is_caught(tmp_path, on_cpu, monkeypatch):
    from repro.training import optim
    monkeypatch.setattr(optim, "adamw_update",
                        lambda g, st, p, **kw: (p, st))
    monkeypatch.setattr(optim, "adagrad_sparse_update",
                        lambda t, i, r, **kw: t)
    ok, checks = _broken_run(tmp_path)
    assert not ok
    assert checks["change_gap"]["value"] == pytest.approx(1.0)
    assert checks["table_change_gap"]["value"] == pytest.approx(1.0)


def test_half_batch_is_caught(tmp_path, on_cpu, monkeypatch):
    from repro.core import negative_sampling as NS
    real = NS.fused_sampled_softmax_loss

    def half(*a, valid=None, **kw):
        keep = jnp.cumsum(valid.astype(jnp.int32)) <= jnp.sum(valid) // 2
        return real(*a, valid=valid & keep, **kw)

    monkeypatch.setattr(NS, "fused_sampled_softmax_loss", half)
    ok, checks = _broken_run(tmp_path)
    assert not ok, checks


@pytest.mark.parametrize("fault", ["no_landing", "no_accum", "tau0"])
def test_sparse_update_fault_is_caught(tmp_path, on_cpu, monkeypatch, fault):
    """Only the item table's landing is broken; the table's own change
    number catches it."""
    from repro.training import optim
    real = optim.adagrad_sparse_update
    model = None
    if fault == "no_landing":
        monkeypatch.setattr(optim, "adagrad_sparse_update",
                            lambda t, i, r, **kw: t)
    elif fault == "no_accum":
        monkeypatch.setattr(
            optim, "adagrad_sparse_update", lambda t, i, r, **kw: real(
                t._replace(accum=jnp.zeros_like(t.accum)), i, r, **kw))
    else:
        model = dict(TINY, training=dict(TINY["training"], semi_async=False))
    ok, checks = _broken_run(tmp_path, model)
    assert not ok
    c = checks["table_change_gap"]
    assert c["value"] > c["limit"], checks
