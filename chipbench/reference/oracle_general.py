"""Plain decoder of libCacheSim's oracleGeneral trace format.

A record is 24 packed little-endian bytes: ``u32 clock_time, u64 obj_id,
u32 obj_size, i64 next_access_vtime``.  Object ids are mapped to dense ids
in first-appearance order: the i-th distinct id seen gets ``i``, the
convention of ``repro.data.ingest.DenseRemap``.  Written from the format's
description; it imports nothing of the program under test.
"""
from __future__ import annotations

import numpy as np

RECORD = np.dtype([("time", "<u4"), ("obj", "<u8"), ("size", "<u4"),
                   ("next", "<i8")])


def read(path, n: int):
    """The first ``n`` records as ``(dense_ids int64, sizes int64)``."""
    rec = np.fromfile(path, dtype=RECORD, count=n)
    if len(rec) != n:
        raise ValueError(f"{path}: {len(rec)} records, {n} wanted")
    return dense_ids(rec["obj"]), rec["size"].astype(np.int64)


def dense_ids(raw: np.ndarray) -> np.ndarray:
    """Dense ids in first-appearance order."""
    uniq, first, inv = np.unique(raw, return_index=True, return_inverse=True)
    rank = np.empty(len(uniq), np.int64)
    rank[np.argsort(first, kind="stable")] = np.arange(len(uniq))
    return rank[inv.reshape(-1)]
