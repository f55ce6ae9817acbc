"""Inputs made from the seed: training batches and serving arrivals.

``train_batch`` is the specification of the token stream the trainer's
seeded pipeline yields (a Zipf unigram draw with an induced bigram chain, a
pure function of ``(seed, step)``). The reference reads its batches from
here; if the program's pipeline ever yields other tokens, the losses part
and the run is not correct.

``serve_schedule`` fixes the multiset of request sizes and of inter-arrival
gaps for a mix and lets the seed choose only their order and the token ids,
so that two seeds offer the same work.
"""
from __future__ import annotations

import itertools
import math
from dataclasses import dataclass
from typing import Dict, List, Sequence

import numpy as np


def train_batch(seed: int, step: int, batch: int, seq: int,
                vocab: int) -> Dict[str, np.ndarray]:
    rng = np.random.default_rng(np.random.SeedSequence([seed, step]))
    ranks = rng.zipf(1.3, size=(batch, seq + 1)).astype(np.int64)
    tokens = np.minimum(ranks, vocab - 1).astype(np.int32)
    chain = (31 * tokens[:, :-1] + 17) % vocab
    odd = np.arange(seq) % 2 == 1
    tokens[:, 1:][:, odd] = chain[:, odd].astype(np.int32)
    return {"tokens": tokens[:, :seq], "labels": tokens[:, 1:seq + 1]}


@dataclass
class Arrival:
    rid: int
    due: float            # seconds after the window opens
    prompt: np.ndarray    # int32 token ids
    max_new: int


def serve_schedule(seed: int, seconds: float, rate: float,
                   prompt_lens: Sequence[int], output_lens: Sequence[int],
                   vocab: int) -> List[Arrival]:
    """Poisson arrivals at ``rate`` per second over ``seconds``.

    The gaps are the exponential distribution's quantiles at (i + 1/2)/n,
    shuffled; the sizes cycle through every (prompt, output) pair the same
    number of times, shuffled.
    """
    n = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng(np.random.SeedSequence([seed, 7]))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    gaps = gaps * (seconds / gaps.sum())
    rng.shuffle(gaps)
    pairs = list(itertools.product(prompt_lens, output_lens))
    sizes = [pairs[i % len(pairs)] for i in range(n)]
    order = rng.permutation(n)
    sizes = [sizes[i] for i in order]
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    out = []
    for i in range(n):
        p, o = sizes[i]
        out.append(Arrival(i, float(due[i]),
                           rng.integers(0, vocab, p, dtype=np.int32), int(o)))
    return out


def percentile(values: Sequence[float], p: float) -> float:
    """The nearest-rank percentile (the value below which ``p`` % lie)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    k = max(0, math.ceil(p / 100.0 * len(xs)) - 1)
    return float(xs[k])
