"""The traffic generator: deterministic per seed, stratified sizes."""
from collections import Counter

import numpy as np
import pytest

import bench_path  # noqa: F401
from harness import traffic

TP = {"requests_per_call": 128, "order_seed": 14,
      "tier_shares": [[8, 0.25], [4, 0.25], [2, 0.25], [1, 0.25]],
      "prompt_lens": [512, 1024, 2048], "prompt_len_probs": [0.3, 0.5, 0.2],
      "new_tokens": [128, 256, 512], "new_tokens_probs": [0.5, 0.3, 0.2],
      "greedy_share": 0.125}
BIG = 2 ** 31 + 12345


def key(r):
    return (r.rid, r.prompt.tobytes(), r.max_new_tokens, r.k, r.greedy)


@pytest.mark.parametrize("seed", [0, 7, BIG, 2 ** 40 + 3])
def test_same_seed_same_trace(seed):
    a = traffic.make_trace(TP, 50304, seed)
    b = traffic.make_trace(TP, 50304, seed)
    assert [key(r) for r in a] == [key(r) for r in b]


def sizes(trace):
    return [(len(r.prompt), r.max_new_tokens, r.k, r.greedy)
            for r in trace]


def test_other_seed_same_work_other_tokens():
    a = traffic.make_trace(TP, 50304, 1)
    b = traffic.make_trace(TP, 50304, 2)
    assert sizes(a) == sizes(b)
    assert all(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    c = traffic.make_trace(dict(TP, order_seed=15), 50304, 1)
    assert sizes(c) != sizes(a)
    for f in (lambda r: len(r.prompt), lambda r: r.max_new_tokens,
              lambda r: (r.k, r.greedy)):
        assert Counter(map(f, a)) == Counter(map(f, c))


def test_stratified_counts():
    tr = traffic.make_trace(TP, 50304, 3)
    assert Counter(len(r.prompt) for r in tr) == {512: 38, 1024: 64,
                                                  2048: 26}
    assert Counter(r.max_new_tokens for r in tr) == {128: 64, 256: 38,
                                                     512: 26}
    assert Counter(r.k for r in tr) == {8: 32, 4: 32, 2: 32, 1: 32}
    assert Counter(r.k for r in tr if r.greedy) == {8: 4, 4: 4, 2: 4, 1: 4}
    assert all(0 <= r.prompt.min() and r.prompt.max() < 50304 for r in tr)


def test_slot_k():
    assert traffic.slot_k({"slots": [[8, 2], [1, 3]]}) == (8, 8, 1, 1, 1)
