"""Plain DynamicAdaptiveClimb: the reference the benchmark holds the engine to.

A copy of ``OracleDynamicAdaptiveClimb`` from ``src/repro/core/oracle.py``
(Algorithm 2 of arXiv 2511.21235 on an ordered Python list, index 0 = top),
with two changes that keep it fast enough for the benchmark's sizes and
change no decision:

* a set of the keys held answers "is it cached?" in O(1); the list is
  searched only on a hit, from the top;
* ranks at the tail of the list that were never written are left out of
  the list instead of being stored as ``EMPTY``: a list shorter than the
  active size ``k`` means its missing ranks are empty.

It imports nothing of the program under test.

Besides its totals, a lane reports the ranks that Algorithm 2 works on in
each chunk: per request ``lane_pad(k)``, the active size ``k`` padded to
the chip's 128 lanes (``ranks``).  ``metrics/row_roofline_pct.py`` counts
the row traffic the algorithm needs from it.
"""
from __future__ import annotations

import math

import numpy as np

EMPTY = -1
LANE = 128


def lane_pad(n: int) -> int:
    return max(LANE, -(-n // LANE) * LANE)


class DAC:
    """One cache lane.  ``step(key) -> hit``; keys are ints >= 0."""

    def __init__(self, K: int, eps: float = 0.5, growth: int = 4,
                 k_min: int = 2):
        self.K_max = K * growth
        self.k = K
        self.eps = eps
        self.k_min = k_min
        self.row: list = []        # ranks 0..len-1; ranks >= len are empty
        self.held: set = set()
        self.jump = K
        self.jump2 = 0
        self.t = 0                 # requests decided
        self.k_log = [(0, K)]      # (from request t on, active size k)

    def _insert(self, t: int, key: int) -> None:
        row = self.row
        if t > len(row):
            row.extend([EMPTY] * (t - len(row)))
        row.insert(t, key)

    def step(self, key: int) -> bool:
        k = self.k
        half = k // 2
        hit = key in self.held
        if hit:
            i = self.row.index(key)
            if self.jump > -half:
                self.jump -= 1
            if i < half:
                if self.jump2 > -half:
                    self.jump2 -= 1
            else:
                if self.jump2 < 0:
                    self.jump2 += 1
            actual = max(1, min(self.jump, i))
            if i > 0:
                self.row.pop(i)
                self.row.insert(i - actual, key)
        else:
            self.jump = min(self.jump + 1, 2 * k)
            if self.jump2 < 0:
                self.jump2 += 1
            actual = max(1, min(k - 1, self.jump))
            if len(self.row) == k:             # evict rank k-1
                gone = self.row.pop()
                if gone != EMPTY:
                    self.held.discard(gone)
            self._insert(k - actual, key)
            self.held.add(key)

        # resize checks, after every request
        if self.jump == 0:
            self.jump2 = 0
        half = self.k // 2
        shrink_thresh = -math.ceil(self.eps * half)
        if self.jump >= 2 * self.k and 2 * self.k <= self.K_max:
            self.k = 2 * self.k                # new ranks start empty
            self.jump = max(min(self.jump, 2 * self.k), -(self.k // 2))
            self.jump2 = 0
        elif (self.jump <= -half and self.jump2 <= shrink_thresh
              and half >= self.k_min):
            for gone in self.row[half:]:
                if gone != EMPTY:
                    self.held.discard(gone)
            del self.row[half:]
            self.k = half
            self.jump = 0
            self.jump2 = 0
        self.t += 1
        if self.k != self.k_log[-1][1]:
            self.k_log.append((self.t, self.k))
        return hit


class Lane:
    """One lane's cache and its totals as the engine defines them:
    requests and hits as integers; bytes summed per chunk in float32, in
    request order, and over chunks in float64.

    ``key_bits < 32`` keeps only that many low bits of each key: the
    control, which must come out as not correct.
    """

    def __init__(self, config: dict, key_bits: int = 32):
        self.cache = DAC(config["K"], **config["policy_params"])
        self.mask = (1 << key_bits) - 1 if key_bits < 32 else None
        self.requests = self.hits = 0
        self.bytes_total = self.bytes_missed = 0.0
        self.ranks: list = []      # per chunk: sum of lane_pad(k) per request
        self._at = 0               # entry of cache.k_log in force at chunk start

    def _ranks(self, t0: int, t1: int) -> int:
        """Sum over requests ``[t0, t1)`` of ``lane_pad(k)``."""
        log = self.cache.k_log
        while self._at + 1 < len(log) and log[self._at + 1][0] <= t0:
            self._at += 1
        total = 0
        for i in range(self._at, len(log)):
            t, k = log[i]
            end = log[i + 1][0] if i + 1 < len(log) else t1
            lo, hi = max(t, t0), min(end, t1)
            if hi > lo:
                total += (hi - lo) * lane_pad(k)
        return total

    def chunk(self, keys, sizes=None) -> None:
        """One chunk: ``sizes`` an int array, or ``None`` for unit sizes."""
        keys = np.asarray(keys, np.int64)
        if self.mask is not None:
            keys = keys & self.mask
        step = self.cache.step
        hit = np.fromiter((step(k) for k in keys.tolist()), bool, len(keys))
        size = (np.ones(len(keys), np.float32) if sizes is None
                else np.asarray(sizes).astype(np.float32))
        missed = np.where(hit, np.float32(0), size)
        self.ranks.append(self._ranks(self.requests, self.requests + len(keys)))
        self.requests += len(keys)
        self.hits += int(hit.sum())
        if len(keys):
            self.bytes_total += float(np.add.accumulate(size)[-1])
            self.bytes_missed += float(np.add.accumulate(missed)[-1])

    def totals(self) -> dict:
        return {"requests": self.requests, "hits": self.hits,
                "bytes_total": self.bytes_total,
                "bytes_missed": self.bytes_missed, "ranks": self.ranks}


def replay_chunks(chunks, config: dict, key_bits: int = 32) -> dict:
    """Replay one lane's ``(keys, sizes)`` chunks through a fresh cache."""
    lane = Lane(config, key_bits)
    for keys, sizes in chunks:
        lane.chunk(keys, sizes)
    return lane.totals()
