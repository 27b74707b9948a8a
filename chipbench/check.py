"""The comparison that decides ``correct``.

The engine's per-lane totals of the run (``replay_stream``'s own result,
warm-up and window together) are held against what the harness fed it and
against the plain reference (``chipbench/reference``) replaying the same
chunks:

* ``requests_gap``: every lane, requests counted against requests fed, as
  the source counts them (``generator.live``: its ``live`` hook, where its
  lanes idle, else ``T`` a lane a chunk);
* ``hits_gap``, ``bytes_missed_gap``, ``bytes_total_gap``: the lanes of a
  sample drawn from the seed, against the reference.

Each number is the largest absolute gap over the lanes it covers.  All
four are exact: the limit is 0.

The configuration names its reference (``"reference"``), a module under
``chipbench/reference/`` with ``replay_chunks(chunks, config, key_bits)``,
which replays one lane alone.  A reference whose lanes share state (a pool:
admission, arbitration and budgets couple them) defines the hook
``replay_lanes(source, lanes, n_chunks, config, key_bits)`` instead, which
replays the source's first ``n_chunks`` chunks once and returns
``{lane: totals}`` for the lanes asked; the check then calls it once.
"""
from __future__ import annotations

import numpy as np

from chipbench import plugins
from chipbench.generator import live, rng_for

LIMITS = {"requests_gap": 0, "hits_gap": 0, "bytes_missed_gap": 0,
          "bytes_total_gap": 0}


def sample_lanes(lanes: int, n: int, strata: int, seed: int) -> list:
    """``n`` lanes drawn from the seed, spread evenly over the lane classes
    ``lane % strata`` (the traffic's exponents)."""
    rng = rng_for(seed, 1)
    n = min(n, lanes)
    picked = []
    for s in range(strata):
        pool = np.arange(s, lanes, strata)
        take = n // strata + (s < n % strata)
        picked += rng.choice(pool, size=min(take, len(pool)),
                             replace=False).tolist()
    return sorted(picked)


def reference_totals(config: dict, source, lanes: list, n_chunks: int,
                     key_bits: int = 32) -> dict:
    ref = plugins.load("reference", config["reference"])
    if not hasattr(ref, "replay_lanes"):
        return {lane: ref.replay_chunks(source.lane_chunks(lane, n_chunks),
                                        config, key_bits)
                for lane in lanes}
    got = ref.replay_lanes(source, lanes, n_chunks, config, key_bits)
    if sorted(got) != sorted(lanes):
        raise ValueError(f"replay_lanes gave lanes {sorted(got)}, "
                         f"{sorted(lanes)} asked")
    return got


def compare(metrics, config: dict, source, n_chunks: int,
            lanes: list) -> dict:
    """``metrics``: per-lane ``requests``/``hits``/``bytes_*`` arrays (or
    scalars for one lane).  Returns ``{name: (value, limit)}``, the lanes
    that failed, and the reference's per-chunk ``ranks`` of each sampled
    lane (where the reference reports them)."""
    got = {f: np.atleast_1d(np.asarray(getattr(metrics, f), np.float64))
           for f in ("requests", "hits", "bytes_total", "bytes_missed")}
    fed = live(source, 0, n_chunks)
    requests_gap = float(np.max(np.abs(got["requests"] - fed)))
    bad = set(np.flatnonzero(got["requests"] != fed).tolist())
    ref = reference_totals(config, source, lanes, n_chunks)
    gaps = {"hits_gap": 0.0, "bytes_missed_gap": 0.0, "bytes_total_gap": 0.0}
    for lane, want in ref.items():
        for name in gaps:
            field = name[:-len("_gap")]
            gap = abs(float(got[field][lane]) - float(want[field]))
            gaps[name] = max(gaps[name], gap)
            if gap > LIMITS[name]:
                bad.add(lane)
    values = {"requests_gap": requests_gap, **gaps}
    return {"checks": {k: (v, LIMITS[k]) for k, v in values.items()},
            "failed_lanes": sorted(bad),
            "ranks": {lane: want["ranks"] for lane, want in ref.items()
                      if "ranks" in want}}
