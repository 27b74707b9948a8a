"""Traffic source ``oracle_file``: one libCacheSim oracleGeneral trace file.

Parameters (the mix's JSON): ``requests`` (records in the file), ``alpha``
(Zipf exponent over the configuration's ``ids_per_lane`` random 64-bit
object ids), ``size_model`` (log-normal object sizes), ``chunk`` (requests
per chunk).  The file is written in set-up; the window streams it through
the program's own reader, ``repro.data.ingest.iter_chunks``, and re-opens
it when the window outruns it.
"""
from __future__ import annotations

import os
import shutil
import tempfile

import numpy as np

from chipbench.generator import (object_sizes, rng_for, write_oracle_general,
                                 zipf_cdf, zipf_keys)
from chipbench.reference import oracle_general


class OracleFile:
    def __init__(self, config: dict, mix: dict, seed: int):
        self.lanes, self.T = 1, mix["chunk"]
        self.requests = mix["requests"]
        if self.requests % self.T:
            raise ValueError("the file must be a whole number of chunks")
        self.strata = 1
        rng = rng_for(seed, 0)
        ids = config["ids_per_lane"]
        raw = rng.integers(0, np.iinfo(np.uint64).max, size=ids,
                           dtype=np.uint64, endpoint=True)
        sizes = object_sizes(rng, ids, **mix["size_model"])
        rank = zipf_keys(rng, zipf_cdf(ids, mix["alpha"]), self.requests)
        self.dir = tempfile.mkdtemp(prefix="chipbench-")
        self.path = os.path.join(self.dir, "trace.oracleGeneral.bin")
        write_oracle_general(self.path, raw[rank], sizes[rank])
        self._it = None

    def _open(self):
        from repro.data import ingest
        return ingest.iter_chunks(self.path, chunk=self.T)

    def next(self):
        if self._it is not None:
            item = next(self._it, None)
            if item is not None:
                return item
        self._it = self._open()
        return next(self._it)

    def lane_chunks(self, lane: int, n_chunks: int):
        n = min(n_chunks * self.T, self.requests)
        keys, sizes = oracle_general.read(self.path, n)
        for c in range(n_chunks):
            lo = (c * self.T) % self.requests
            yield keys[lo:lo + self.T], sizes[lo:lo + self.T]

    def close(self):
        self._it = None
        shutil.rmtree(self.dir, ignore_errors=True)


def make(config: dict, mix: dict, seed: int) -> OracleFile:
    return OracleFile(config, mix, seed)


def shrink(config: dict, mix: dict) -> tuple:
    """The same code paths and key space at a size a CPU test holds: a
    shorter file, small caches."""
    return dict(config, K=512), dict(mix, requests=1 << 18)
