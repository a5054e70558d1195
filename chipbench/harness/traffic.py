"""Seeded request traces for the serving cells, from a traffic file's
parameters alone.

Closed batches: every request of a call is due at t=0.  Sizes are drawn
as ``repro.serving.workload.make_trace`` draws them, with two
differences, both for steadiness from seed to seed:

* sizes are stratified: of ``n`` requests, the count at each prompt
  length, output length, tier and greedy flag is ``n`` times its share,
  rounded by largest remainder;
* their order comes from the traffic file's ``order_seed``, the token ids
  from the run's seed.  Every seed then sends the same work (the same
  requests in the same order, so the engine forms the same batches and
  runs the same programs) and differs in what the tokens and weights are.

Parameters (all from the traffic file):

``requests_per_call``  requests handed to one ``ServingEngine.run`` call
``order_seed``         seed of the sizes' order
``prompt_lens``, ``prompt_len_probs``                  prompt lengths
``new_tokens``, ``new_tokens_probs``                   output lengths
``tier_shares``        ``[[k, share], ...]``
``greedy_share``       share of requests (per tier) decoded greedily
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Sequence

import numpy as np


@dataclass
class Req:
    rid: int
    prompt: np.ndarray
    max_new_tokens: int
    k: int
    greedy: bool


def stratified(n: int, values: Sequence, probs: Sequence[float] | None,
               rng: np.random.Generator) -> list:
    """``n`` draws holding each value ``n * p`` times (largest remainder),
    in an order taken from ``rng``."""
    p = (np.full(len(values), 1.0 / len(values)) if probs is None
         else np.asarray(probs, np.float64) / np.sum(probs))
    raw = n * p
    counts = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - counts), kind="stable")[: n - counts.sum()]:
        counts[i] += 1
    out = [v for v, c in zip(values, counts) for _ in range(c)]
    order = rng.permutation(n)
    return [out[i] for i in order]


def make_trace(tp: Dict, vocab_size: int, seed: int,
               rid0: int = 0) -> List[Req]:
    """One call's requests.  Equal (tp, vocab_size, seed) give equal
    traces; every seed gives the same lengths, tiers and flags, in the
    same order."""
    n = int(tp["requests_per_call"])
    rng = np.random.default_rng(int(tp["order_seed"]))
    tok_rng = np.random.default_rng(seed)
    tiers = [int(k) for k, _ in tp["tier_shares"]]
    ks = stratified(n, tiers, [s for _, s in tp["tier_shares"]], rng)
    lens = stratified(n, tp["prompt_lens"], tp.get("prompt_len_probs"), rng)
    outs = stratified(n, tp["new_tokens"], tp.get("new_tokens_probs"), rng)
    greedy = np.zeros(n, bool)
    share = float(tp.get("greedy_share", 0.0))
    for k in tiers:
        idx = [i for i in range(n) if ks[i] == k]
        flags = stratified(len(idx), [True, False], [share, 1.0 - share],
                           rng)
        greedy[idx] = flags
    out: List[Req] = []
    for i in range(n):
        prompt = tok_rng.integers(0, vocab_size, (int(lens[i]),),
                                  dtype=np.int64).astype(np.int32)
        out.append(Req(rid=rid0 + i, prompt=prompt,
                       max_new_tokens=int(outs[i]), k=int(ks[i]),
                       greedy=bool(greedy[i])))
    return out


def slot_k(tp: Dict) -> tuple:
    """The engine's static per-slot budgets: ``[[k, n_slots], ...]``."""
    return tuple(int(k) for k, n in tp["slots"] for _ in range(int(n)))
