"""The traffic generator: deterministic from the seed, the token budget
and the sequence bound respected, the same steps however they are
requested."""
import json

import numpy as np
import pytest

import traffic
from tiny_cell import BENCH, TINY, TINY_MIX

SEEDS = [0, 7, 2 ** 31 + 11]


def _steps(seed, start, count, mix=TINY_MIX):
    return traffic.make_batches(BENCH, mix, TINY, seed, start, count)


def _same(a, b):
    for (ba, la), (bb, lb) in zip(a, b):
        assert la == lb
        for k in ba:
            np.testing.assert_array_equal(ba[k], bb[k])


@pytest.mark.parametrize("seed", SEEDS)
def test_same_seed_same_steps(seed):
    _same(_steps(seed, 0, 4), _steps(seed, 0, 4))
    _same(_steps(seed, 2, 2), _steps(seed, 0, 4)[2:])


def test_seeds_change_content():
    a, b = _steps(1, 0, 1)[0][0], _steps(2, 0, 1)[0][0]
    assert not np.array_equal(a["neg_ids"], b["neg_ids"])


@pytest.mark.parametrize("seed", SEEDS)
def test_budget_bound_and_layout(seed):
    T, S, L = TINY_MIX["token_budget"], TINY_MIX["max_seqs"], \
        TINY["max_seq_len"]
    for batch, lengths in _steps(seed, 0, 6):
        off = batch["offsets"][0]
        assert off.shape == (S + 1,)
        assert len(lengths) <= S and sum(lengths) <= T
        assert int(off[-1]) == sum(lengths)
        assert np.all(np.diff(off) >= 0)
        assert max(lengths) <= L and min(lengths) >= 1
        for k in ("ids", "labels", "neg_ids"):
            assert batch[k].min() >= 0 and batch[k].max() < TINY["vocab_size"]
        assert batch["neg_ids"].shape == (1, T, TINY["num_negatives"])
        ts = batch["timestamps"][0]
        for s, e in zip(off[:-1], off[1:]):
            assert np.all(np.diff(ts[s:e]) > 0)
            # a history is shifted by one for its labels
            np.testing.assert_array_equal(batch["ids"][0, s + 1:e],
                                          batch["labels"][0, s:e - 1])
        assert sum(lengths) / T > 0.5


@pytest.mark.parametrize("mix", ["long-hist", "short-hist"])
def test_every_seed_gets_the_same_work(mix):
    # the same sequence lengths in every step, in another order
    with open(f"{BENCH}/configs/hstu-large.json") as f:
        model = json.load(f)
    mix = traffic.load_mix(BENCH, mix)
    a, b = (traffic.make_batches(BENCH, mix, model, s, 0, 4)
            for s in SEEDS[1:])
    assert [sorted(x[1]) for x in a] == [sorted(x[1]) for x in b]
    # every step full: a window's tokens follow from its number of steps
    assert all(sum(x[1]) == mix["token_budget"] for x in a)
    assert not np.array_equal(a[0][0]["ids"], b[0][0]["ids"])


def test_lengths_follow_the_mix():
    gen = traffic.load_module(f"{BENCH}/generators/packed_histories.py",
                              "ph")
    ev = gen.user_events(TINY_MIX, 4000)
    assert ev.min() >= TINY_MIX["history_min"]
    # a lognormal of the mix's mean, within the spread of 4000 draws
    assert abs(np.mean(ev) / TINY_MIX["history_mean"] - 1.0) < 0.05


def test_zipf_ids_stay_in_the_table_and_are_skewed():
    gen = traffic.load_module(f"{BENCH}/generators/packed_histories.py",
                              "ph")
    ids = gen.zipf_ids(np.random.default_rng(0), 100_000, 1 << 12, 1.1)
    assert ids.min() >= 0 and ids.max() < 1 << 12
    counts = np.bincount(ids, minlength=1 << 12)
    # skewed, yet no single id holds a large share of the draws
    assert counts.max() > 20 * np.median(counts)
    assert counts.max() < 0.2 * ids.size
