"""Training steps packed from KuaiRand-like user histories.

Every user has a history whose length is lognormal (``history_mean``,
``history_sigma``), at least ``history_min`` events. A history keeps its
latest ``max_seq_len + 1`` events, which give ``len - 1`` next-item training
pairs. Users are packed in order into steps of ``token_budget`` tokens and
at most ``max_seqs`` sequences: the user that does not fit whole is cut to
the tokens left, keeping its latest events, and closes the step. So every
step is full, and a window's tokens do not depend on which steps it holds.

The seed sets the order and the content, not the work. User ``k``'s length
is the lognormal quantile at ``frac((k + 1) * phi)``, a golden-ratio
sequence, so any run of consecutive users covers the distribution evenly,
and every seed packs the same lengths into the same steps; the seed orders
the sequences inside each step and draws their items, timestamps and
negatives. So two seeds give the window the same work. Item ids are Zipf(``zipf_a``) over the table's rows, drawn from a
power law truncated to the table (the inverse-CDF form; ranks are then
spread over ids by a fixed odd multiplier, a bijection on a power-of-two
table). Timestamps rise monotonically over ``time_span_s`` with exponential
gaps, and a sequence's timestamps count from its first kept event, as the
repo's loader does. Negatives are ``num_negatives`` uniform ids per token.

A cell on a mesh of G chips trains G shards a step, one a chip: its step
``b`` stacks the stream's packed steps ``G b .. G b + G - 1`` as ``(G, T)``
arrays, so ``token_budget`` and ``max_seqs`` are per chip, and the step's
lengths are every shard's.

The arithmetic of the lengths, the Zipf ranks and the timestamps follows
``repro.data.synthetic.SyntheticKuaiRand``; the truncation replaces its
clip, which put about 29% of all draws (at a = 1.1 over 2^18 rows) on the
last rank.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Dict, List, Tuple

import numpy as np

PHI = (math.sqrt(5.0) - 1.0) / 2.0
SCATTER = 2654435761


def _rng(seed: int, *keys: int) -> np.random.Generator:
    return np.random.default_rng([seed % (1 << 63), *keys])


def user_events(mix: Dict, count: int) -> np.ndarray:
    """History lengths (events) of users 0..count-1."""
    sigma = float(mix["history_sigma"])
    mu = math.log(float(mix["history_mean"])) - sigma * sigma / 2.0
    nd = NormalDist()
    u = np.mod(PHI * np.arange(1, count + 1), 1.0)
    u = np.clip(u, 1e-12, 1.0 - 1e-12)
    z = np.array([nd.inv_cdf(float(x)) for x in u])
    return np.maximum(np.exp(mu + sigma * z).astype(np.int64),
                      int(mix["history_min"]))


def pack(lengths: np.ndarray, budget: int,
         max_seqs: int) -> List[List[Tuple[int, int]]]:
    """Users (by index) packed in order into steps of ``budget`` tokens,
    as ``(user, tokens)`` pairs; the user that does not fit whole is cut
    to the tokens left."""
    steps, cur, left = [], [], budget
    for k, n in enumerate(lengths):
        n = min(int(n), left)
        if n <= 0:
            continue
        cur.append((k, n))
        left -= n
        if left == 0 or len(cur) == max_seqs:
            steps.append(cur)
            cur, left = [], budget
    if cur:
        steps.append(cur)
    return steps


def zipf_ids(rng: np.random.Generator, n: int, vocab: int,
             a: float) -> np.ndarray:
    u = rng.random(n)
    top = (vocab + 1.0) ** (1.0 - a)
    ranks = np.floor((1.0 - u * (1.0 - top)) ** (1.0 / (1.0 - a))) - 1.0
    ranks = np.clip(ranks, 0, vocab - 1).astype(np.int64)
    return ((ranks * SCATTER + 12345) % vocab).astype(np.int32)


def _user(mix: Dict, seed: int, k: int, n: int, full_events: int,
          vocab: int) -> Tuple[np.ndarray, np.ndarray]:
    rng = _rng(seed, 1, k)
    items = zipf_ids(rng, n + 1, vocab, float(mix["zipf_a"]))
    gap = float(mix["time_span_s"]) / max(full_events, 1)
    gaps = np.maximum(rng.exponential(gap, n), 1.0).astype(np.int64)
    ts = np.concatenate([[0], np.cumsum(gaps)[:-1]]).astype(np.int32)
    return items, ts


def _packed(mix: Dict, seed: int, b: int, step: List[Tuple[int, int]],
            events: np.ndarray, T: int, S: int, R: int, V: int
            ) -> Tuple[Dict[str, np.ndarray], List[int]]:
    """Packed step ``b`` of the stream as one shard, ``(1, T)`` arrays."""
    ids = np.zeros((1, T), np.int32)
    labels = np.zeros((1, T), np.int32)
    ts = np.zeros((1, T), np.int32)
    offsets = np.zeros((1, S + 1), np.int32)
    cur, seq = 0, []
    order = _rng(seed, 3, b).permutation(len(step))
    for j, (k, n) in enumerate(step[i] for i in order):
        items, t = _user(mix, seed, k, n, int(events[k]), V)
        ids[0, cur:cur + n] = items[:-1]
        labels[0, cur:cur + n] = items[1:]
        ts[0, cur:cur + n] = t
        cur += n
        offsets[0, j + 1] = cur
        seq.append(n)
    offsets[0, len(seq) + 1:] = cur
    rng = _rng(seed, 2, b)
    neg = rng.integers(0, V, (1, T, R), dtype=np.int32)
    key = rng.integers(0, 2 ** 31, (2,)).astype(np.uint32)
    return ({"ids": ids, "labels": labels, "timestamps": ts,
             "offsets": offsets, "neg_ids": neg, "rng": key}, seq)


def batches(mix: Dict, model: Dict, seed: int, start: int, count: int,
            shards: int = 1
            ) -> List[Tuple[Dict[str, np.ndarray], List[int]]]:
    """Steps ``start .. start + count - 1`` of a cell of ``shards`` chips:
    each a ``(batch, sequence_lengths)`` pair in the engine's batch layout,
    step ``b`` the packed steps ``shards * b ..`` stacked on the shard
    axis, ``rng`` the first one's key, the lengths every shard's."""
    L = int(model["max_seq_len"])
    R = int(model["num_negatives"])
    V = int(model["vocab_size"])
    T = int(mix["token_budget"])
    S = int(mix["max_seqs"])
    need = (start + count) * shards
    users = max(64, need * max(1, T // max(L, 1)) * 2)
    while True:
        events = user_events(mix, users)
        lengths = np.minimum(events, L + 1) - 1
        steps = pack(lengths, T, S)
        if len(steps) > need:       # the last step may still be filling
            break
        users *= 2
    out = []
    for b in range(start, start + count):
        parts = [_packed(mix, seed, p, steps[p], events, T, S, R, V)
                 for p in range(b * shards, (b + 1) * shards)]
        batch = {k: np.concatenate([p[0][k] for p in parts])
                 for k in parts[0][0] if k != "rng"}
        batch["rng"] = parts[0][0]["rng"]
        out.append((batch, [n for p in parts for n in p[1]]))
    return out
