"""Runs in which ``correct`` has to come out false, and the readings that
set the limits of ``check.py``.

    python3 chipbench/selfcheck.py --workload <cell> --what program|control|faults \
        --seeds 1 2 3 --seconds 10

All seeds run in one process, through ``run.run_cell``, on the chip:

* ``program``: the benchmark's own run (the lower readings);
* ``control``: the plain reference in the program's place, with its keys
  cut to their 16 low bits (the upper readings);
* ``faults``: the program with a fault planted under the harness, one run
  per fault: a step that returns its state unchanged (``frozen``), half of
  the lanes (or, on one lane, half of each chunk) left out and the rest's
  mean put in their place (``half_batch``), and the answer of every
  request to a key divisible by 97 reported as a miss (``altered``).
  There is no exchange between chips on the timed path of the cells so
  far, so that fault has no run.  A pool cell on four chips (the fleet's
  ``psum`` re-deal of the slot budget every ``rebalance`` steps) will
  need one, the exchange left out, which the PR that adds the cell must
  add here, with a control that replays the pool's coupled lanes together
  (a reference's ``replay_lanes``, ``check.py``).

``tests/test_selfcheck.py`` makes the same runs at a size a test can hold,
on the CPU, with the look for a chip skipped.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from types import SimpleNamespace

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from chipbench import plugins  # noqa: E402

FIELDS = ("requests", "hits", "bytes_total", "bytes_missed")


def split(item):
    """A chunk as ``(keys, sizes or None)``."""
    if isinstance(item, tuple):
        return np.asarray(item[0]), item[1]
    return np.asarray(item), None


def result(per_lane: dict):
    return SimpleNamespace(metrics=SimpleNamespace(
        **{f: np.asarray(v, np.float64) for f, v in per_lane.items()}))


class ControlEngine:
    """The reference in the program's place, keys cut to ``key_bits``."""

    def __init__(self, config: dict, key_bits: int = 16):
        self.config, self.key_bits = config, key_bits
        self.ref = plugins.load("reference", config["reference"])

    def replay_stream(self, policy, chunks, K):
        lanes = None
        for item in chunks:
            keys, sizes = split(item)
            keys = np.atleast_2d(keys)
            sizes = None if sizes is None else np.atleast_2d(sizes)
            if lanes is None:
                lanes = [self.ref.Lane(self.config, self.key_bits)
                         for _ in range(len(keys))]
            for i, lane in enumerate(lanes):
                lane.chunk(keys[i], None if sizes is None else sizes[i])
        tot = [lane.totals() for lane in lanes]
        return result({f: [t[f] for t in tot] for f in FIELDS})


def _wrapped(kind: str):
    """A policy class that plants fault ``kind`` in a base policy's step."""
    import jax.numpy as jnp
    from repro.core.policy import Policy

    class Faulty(Policy):
        name = f"faulty_{kind}"

        def __init__(self, base):
            self.base = base

        def init(self, K):
            return self.base.init(K)

        def step(self, state, req):
            new, info = self.base.step(state, req)
            if kind == "frozen":
                return state, info
            hit = info.hit & (req.key % 97 != 0)
            return new, info._replace(
                hit=hit, bytes_missed=jnp.where(hit, 0, req.size),
                penalty=jnp.where(hit, jnp.float32(0), req.cost))

    return Faulty


class PolicyFault:
    """The program, with its policy's step broken."""

    def __init__(self, kind: str):
        self.kind = kind

    def replay_stream(self, policy, chunks, K):
        from repro.core import Engine, make_policy
        return Engine().replay_stream(_wrapped(self.kind)(make_policy(policy)),
                                      chunks, K)


class HalfBatch:
    """The program on half the batch; the rest get the mean of that half."""

    def replay_stream(self, policy, chunks, K):
        from repro.core import Engine
        shape = []

        def halves():
            for item in chunks:
                keys, sizes = split(item)
                shape[:] = keys.shape
                cut = ((slice(0, len(keys) // 2),) if keys.ndim == 2
                       else (slice(0, keys.shape[-1] // 2),))
                yield (keys[cut] if sizes is None
                       else (keys[cut], np.asarray(sizes)[cut], None))

        m = Engine().replay_stream(policy, halves(), K).metrics
        out = {}
        for f in FIELDS:
            got = np.atleast_1d(np.asarray(getattr(m, f), np.float64))
            if len(shape) == 2:
                rest = np.full(shape[0] - len(got), got.mean())
                out[f] = np.concatenate([got, rest])
            else:
                out[f] = 2 * got
        return result(out)


def fault_engines() -> dict:
    return {"frozen": PolicyFault("frozen"), "half_batch": HalfBatch(),
            "altered": PolicyFault("altered")}


def shrink(config: dict, mix: dict) -> tuple:
    """The cell at a size a CPU test holds: same code paths and key space,
    small rows, few lanes, short rings."""
    config = dict(config, K=512)
    if mix["generator"] == "zipf_lanes":
        mix = dict(mix, chunk=[2 * len(mix["alphas"]), 16], ring=4096)
    else:
        mix = dict(mix, requests=1 << 18)
    return config, mix


def main(argv=None) -> int:
    from chipbench import run

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--what", choices=("program", "control", "faults"),
                    required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    cell, config, mix, _, _ = run.load_cell(args.workload)
    devices = run.find_chips(cell["chips"])
    engines = ({"program": None} if args.what == "program" else
               {"control": ControlEngine(config)} if args.what == "control"
               else fault_engines())
    for seed in args.seeds:
        for name, engine in engines.items():
            out = run.run_cell(config, mix, seed, args.seconds, False, [],
                               devices=devices, engine=engine)
            print(json.dumps({
                "workload": args.workload, "what": name, "seed": seed,
                "correct": out["correct"],
                "window_chunks": out["window_chunks"],
                "checks": {k: c["value"] for k, c in out["checks"].items()},
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
