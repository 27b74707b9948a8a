"""Traffic: a mix's parameters in, a chunk source out.

A traffic mix is a JSON file under ``chipbench/traffic/``; its
``"generator"`` key names a source under ``chipbench/generators/`` (loaded
by path), and the rest are that source's parameters.  A source has
``lanes`` and ``T`` (requests a lane a chunk), gives the engine one chunk
per ``next()``, and can hand the plain reference any lane's chunks again
afterwards (``lane_chunks``).

A source whose lanes idle (a pool's lane with no tenant carries key ``-1``
for that step) defines the hook ``live(lo, hi)``: per lane, the requests
it handed out in chunks ``[lo, hi)``, idle slots not counted.  The harness
reads it through :func:`live`, which gives ``T`` a lane a chunk for a
source without the hook.

The Zipf sampler, the log-normal size model and the oracleGeneral writer
are copies, so that later changes to the program do not move the
benchmark's inputs:

* ``zipf_cdf`` / ``zipf_keys``: ``zipf_trace`` of ``src/repro/data/traces.py``
  (inverse-CDF sampling, as ``Generator.choice`` with ``p=`` does it);
* ``object_sizes``: ``object_sizes`` of ``src/repro/data/traces.py``;
* ``write_oracle_general``: ``write_oracle_general`` of
  ``src/repro/data/ingest.py``, with ``next_access_vtime`` computed by a
  sort instead of a Python loop.
"""
from __future__ import annotations

import numpy as np

from chipbench.reference import oracle_general


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A generator per (seed, purpose); any integer seed is accepted."""
    return np.random.default_rng([seed % (1 << 64), *stream])


def zipf_cdf(n: int, alpha: float) -> np.ndarray:
    w = np.arange(1, n + 1, dtype=np.float64) ** -alpha
    cdf = np.cumsum(w / w.sum())
    cdf /= cdf[-1]
    return cdf


def zipf_keys(rng, cdf: np.ndarray, shape) -> np.ndarray:
    """IID Zipf draws (0 = most popular id) as int32."""
    return np.searchsorted(cdf, rng.random(shape), side="right").astype(
        np.int32)


def object_sizes(rng, n_objects: int, median_kb: float,
                 sigma: float) -> np.ndarray:
    """Log-normal object sizes in bytes (wiki-like heavy tail)."""
    kb = rng.lognormal(mean=np.log(median_kb), sigma=sigma, size=n_objects)
    return np.maximum(1, (kb * 1024).astype(np.int64))


def next_access(keys: np.ndarray) -> np.ndarray:
    """For each position, the index of the key's next occurrence, or -1."""
    order = np.argsort(keys, kind="stable")
    same = keys[order[1:]] == keys[order[:-1]]
    nxt = np.full(len(keys), -1, np.int64)
    nxt[order[:-1][same]] = order[1:][same]
    return nxt


def write_oracle_general(path, raw_ids: np.ndarray, sizes: np.ndarray):
    rec = np.empty(len(raw_ids), dtype=oracle_general.RECORD)
    rec["time"] = np.arange(len(raw_ids), dtype=np.uint32)
    rec["obj"] = raw_ids
    rec["size"] = sizes.astype(np.uint32)
    rec["next"] = next_access(raw_ids)
    rec.tofile(path)


def live(source, lo: int, hi: int) -> np.ndarray:
    """Requests per lane (``int64[lanes]``) that ``source`` handed out in
    chunks ``[lo, hi)``: its ``live`` hook, or ``T`` a lane a chunk."""
    if hasattr(source, "live"):
        return np.asarray(source.live(lo, hi), np.int64).reshape(source.lanes)
    return np.full(source.lanes, (hi - lo) * source.T, np.int64)


def build(config: dict, mix: dict, seed: int):
    from chipbench import plugins
    return plugins.load("generators", mix["generator"]).make(config, mix,
                                                             seed)
