"""The one traffic generator: reads a mix file of parameters.

A mix fixes a pool of (prompt length, output length) pairs, drawn once
from the mix's own ``pool.seed``.  A run's ``--seed`` only orders that
pool and draws the prompt tokens, so every seed serves the same set of
sizes in another order.  The closed loop cycles through the ordered
pool for as long as the run asks for requests.
"""
from __future__ import annotations

from typing import Iterator, Tuple

import numpy as np


def _draw(rng, spec: dict, n: int) -> np.ndarray:
    if spec["dist"] == "lognormal":
        x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    elif spec["dist"] == "uniform":
        x = rng.uniform(spec["min"], spec["max"] + 1, n)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.floor(x) if spec["dist"] == "uniform" else np.round(x),
                   spec.get("min", 1), spec.get("max", None)).astype(np.int64)


def length_pool(mix: dict) -> np.ndarray:
    """(size, 2) int64: prompt and output length of each pooled request."""
    pool = mix["pool"]
    rng = np.random.default_rng(pool["seed"])
    n = pool["size"]
    return np.stack([_draw(rng, mix["prompt_len"], n),
                     _draw(rng, mix["output_len"], n)], axis=1)


class Traffic:
    """Request stream of one run: ``next()`` gives (prompt ids, max new
    tokens).  Token ids are drawn from [2, vocab) so no prompt holds the
    engine's pad (0) or end-of-sequence (1) ids."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        if mix.get("loop") != "closed":
            raise ValueError(f"traffic {mix.get('name')!r}: only a closed "
                             "loop is generated")
        self.mix = mix
        # the queue is topped up to this many slot pools before each tick
        self.queue_pools = int(mix.get("queue_slots", 1))
        self.pool = length_pool(mix)
        self.rng = np.random.default_rng(seed)
        self.order = self.rng.permutation(len(self.pool))
        self.vocab = vocab
        self.i = 0

    def __iter__(self) -> Iterator[Tuple[np.ndarray, int]]:
        return self

    def __next__(self) -> Tuple[np.ndarray, int]:
        p, o = self.pool[self.order[self.i % len(self.pool)]]
        self.i += 1
        return (self.rng.integers(2, self.vocab, int(p)).astype(np.int32),
                int(o))

    def prompt_buckets(self, bucket: int, cap: int) -> list:
        """Every padded prefill width the pool can ask for."""
        return sorted({min(-(-int(p) // bucket) * bucket, cap)
                       for p in self.pool[:, 0]})
