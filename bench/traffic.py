"""The one generator of the benchmark's inputs, driven by a traffic file.

A traffic file (`bench/traffic/<name>.json`) names its job and holds only
parameters. Every seed gets the same multiset of sizes and arrival gaps,
drawn at fixed quantiles of the stated distributions, in an order the
seed shuffles, and a serving stream is drawn span by span (warm-up,
window, tail), so each window holds the same work; token ids are
uniform from the seed. So two seeds do the same work in a different
order, and the spread between runs is the system's, not the draw's.
A traffic file that fixes `order_seed` fixes the order too: every seed
then sends one schedule, and only the token ids follow the seed.
"""
from __future__ import annotations

from statistics import NormalDist
from typing import List, Sequence, Tuple

import numpy as np

SEED_MOD = 2 ** 31 - 1


def seed32(seed: int) -> int:
    """A seed for APIs that take 32 bits, derived from any whole number."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0]
               % SEED_MOD)


def quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(spec: dict, n: int) -> np.ndarray:
    """n lengths at fixed quantiles of a lognormal with the given median
    and sigma, clipped to [min, max]."""
    z = np.array([NormalDist().inv_cdf(u) for u in quantiles(n)])
    x = np.rint(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(x, spec["min"], spec["max"]).astype(np.int32)


def poisson_offsets(duration_s: float, n: int, rng: np.random.Generator
                    ) -> np.ndarray:
    """Arrival offsets of n requests spread over [0, duration_s):
    exponential gaps at fixed quantiles, shuffled, scaled to the span."""
    gaps = rng.permutation(-np.log1p(-quantiles(n)))
    c = np.cumsum(gaps)
    return duration_s * (c - gaps[0]) / c[-1]


def chat_requests(traffic: dict, vocab: int, spans_s: Sequence[float],
                  seed: int) -> List[Tuple[float, np.ndarray, int]]:
    """(offset_s, prompt ids, output budget) of an open-loop Poisson
    stream over consecutive spans (warm-up, window, ...). Each span gets
    rate x its length requests whose lengths and gaps are the same
    multiset for every seed, so every window holds the same work. Their
    order comes from `traffic["order_seed"]` where the file sets one,
    else from `seed`."""
    rng = np.random.default_rng(seed)
    order = (np.random.default_rng(traffic["order_seed"])
             if "order_seed" in traffic else rng)
    rate = traffic["rate_rps"]
    reqs, t0 = [], 0.0
    for span in spans_s:
        n = max(1, int(round(rate * span)))
        t = t0 + poisson_offsets(span, n, order)
        prompts = order.permutation(lognormal_lengths(traffic["prompt"], n))
        outputs = order.permutation(lognormal_lengths(traffic["output"], n))
        for i in range(n):
            ids = rng.integers(0, vocab, size=int(prompts[i]),
                               dtype=np.int32)
            reqs.append((float(t[i]), ids, int(outputs[i])))
        t0 += span
    return reqs


def token_ring(traffic: dict, n_nodes: int, h: int, vocab: int,
               seed: int) -> List[dict]:
    """`ring` superstep batches of uniform token ids, each
    {"tokens", "targets"}: int32 [n_nodes, h, local_batch, seq_len], the
    targets the next token of each row. Every row of every batch differs."""
    rng = np.random.default_rng(seed)
    b, s = traffic["local_batch"], traffic["seq_len"]
    ring = []
    for _ in range(traffic["ring"]):
        ids = rng.integers(0, vocab, size=(n_nodes, h, b, s + 1),
                           dtype=np.int32)
        ring.append({"tokens": np.ascontiguousarray(ids[..., :-1]),
                     "targets": np.ascontiguousarray(ids[..., 1:])})
    return ring
