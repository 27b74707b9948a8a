"""Traffic source ``zipf_lanes``: many independent Zipf streams, one per lane.

Parameters (the mix's JSON): ``chunk`` = ``[lanes, T]``, ``ring`` (requests
pre-generated per lane and cycled), ``alphas`` (lane ``i`` draws with
exponent ``alphas[i % len(alphas)]``).  The configuration gives
``ids_per_lane`` and, optionally, a constant ``record_bytes`` (unit sizes
otherwise).
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from chipbench.generator import rng_for, zipf_cdf, zipf_keys


class LaneRing:
    """Every chunk is ``[lanes, T]`` keys.  Blocks of ``BLOCK`` lanes of one
    exponent are drawn in threads, each from a generator of its own."""

    BLOCK = 32

    def __init__(self, config: dict, mix: dict, seed: int):
        self.lanes, self.T = mix["chunk"]
        self.ring = mix["ring"]
        if self.ring % self.T:
            raise ValueError("ring must be a whole number of chunks")
        alphas = mix["alphas"]
        self.strata = len(alphas)
        self.keys = np.empty((self.lanes, self.ring), np.int32)
        cdfs = [zipf_cdf(config["ids_per_lane"], a) for a in alphas]

        def draw(task):
            a, b, rows = task
            self.keys[rows] = zipf_keys(rng_for(seed, 0, a, b), cdfs[a],
                                        (len(rows), self.ring))

        tasks = []
        for a in range(len(alphas)):
            rows = np.arange(a, self.lanes, len(alphas))
            for b in range(0, len(rows), self.BLOCK):
                tasks.append((a, b // self.BLOCK, rows[b:b + self.BLOCK]))
        with ThreadPoolExecutor(min(8, len(tasks))) as pool:
            list(pool.map(draw, tasks))
        size = config.get("record_bytes")
        self.size = None if size is None else np.full(
            (self.lanes, self.T), size, np.int64)
        self.pos = 0

    def next(self):
        lo = self.pos
        self.pos = (lo + self.T) % self.ring
        keys = self.keys[:, lo:lo + self.T]
        return keys if self.size is None else (keys, self.size, None)

    def lane_chunks(self, lane: int, n_chunks: int):
        for c in range(n_chunks):
            lo = (c * self.T) % self.ring
            yield (self.keys[lane, lo:lo + self.T],
                   None if self.size is None else self.size[lane])

    def close(self):
        pass


def make(config: dict, mix: dict, seed: int) -> LaneRing:
    return LaneRing(config, mix, seed)


def shrink(config: dict, mix: dict) -> tuple:
    """The same code paths and key space at a size a CPU test holds: two
    lanes of each exponent, short chunks and rings, small caches."""
    return (dict(config, K=512),
            dict(mix, chunk=[2 * len(mix["alphas"]), 16], ring=4096))
