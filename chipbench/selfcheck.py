"""Runs in which ``correct`` has to come out false, and the readings that
set the limits of ``check.py``.

    python3 chipbench/selfcheck.py --workload <cell> --what program|control|faults \
        --seeds 1 2 3 --seconds 10

All seeds run in one process, through ``run.run_cell``, on the chip:

* ``program``: the benchmark's own run (the lower readings);
* ``control``: the plain reference in the program's place, with its keys
  cut to their 16 low bits (the upper readings).  Where the reference
  replays lanes that share state (its ``replay_lanes`` hook, ``check.py``),
  the control records the chunks it is fed and, after each, replays every
  lane over all of them, so the reference sets the window's pace;
  otherwise it replays each lane alone, chunk by chunk, as they come;
* ``faults``: the program with a fault planted under the harness, one run
  per fault (:func:`faults`).  The generic ones, each planted in the
  policy's step (and in its budgeted step, where a pool steps it so) or in
  the batch under ``Engine().replay_stream``: a step that returns its
  state unchanged (``frozen``), half of the lanes (or, on one lane, half
  of each chunk) left out and the rest's mean put in their place
  (``half_batch``), and the answer of every request to a key divisible by
  97 reported as a miss (``altered``).  A configuration's entry may add
  faults of its own beside them with the hook ``faults(config) -> {name:
  engine}`` (a pool's exchange between chips left out, say); it can
  neither replace nor take away a generic one.

Every engine here takes ``replay_stream(policy, chunks, K, **kw)``: the
generic faults pass the keywords an entry gives on to
``Engine().replay_stream``, and the control ignores them.

``shrink`` gives the cell at a size a CPU test holds, by its traffic
source's own ``shrink(config, mix)``.  ``tests/test_selfcheck.py`` makes
the same runs for every cell of ``BENCHMARK.json`` at that size, on the
CPU, with the look for a chip skipped, and a cell of more than one chip on
as many host devices.
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

from chipbench import check, plugins  # noqa: E402

FIELDS = ("requests", "hits", "bytes_total", "bytes_missed")


def split(item):
    """A chunk as ``(keys, sizes or None)``."""
    if isinstance(item, tuple):
        return np.asarray(item[0]), item[1]
    return np.asarray(item), None


def result(per_lane: dict):
    return SimpleNamespace(metrics=SimpleNamespace(
        **{f: np.asarray(v, np.float64) for f, v in per_lane.items()}))


class Fed:
    """The chunks a control was fed, as a source that a reference's
    ``replay_lanes`` reads again: ``lanes``, ``T``, ``lane_chunks`` and
    ``live`` (keys >= 0; a pool's idle slot carries -1)."""

    def __init__(self):
        self.keys: list = []       # per chunk, [lanes, T]
        self.sizes: list = []      # per chunk, [lanes, T] or None

    def add(self, keys, sizes) -> None:
        """Copies: a source may hand out the same buffer again."""
        self.keys.append(np.atleast_2d(np.array(keys)))
        self.sizes.append(None if sizes is None
                          else np.atleast_2d(np.array(sizes)))

    @property
    def lanes(self) -> int:
        return self.keys[0].shape[0]

    @property
    def T(self) -> int:
        return self.keys[0].shape[1]

    def lane_chunks(self, lane: int, n_chunks: int):
        for keys, sizes in zip(self.keys[:n_chunks], self.sizes[:n_chunks]):
            yield keys[lane], None if sizes is None else sizes[lane]

    def live(self, lo: int, hi: int) -> np.ndarray:
        return sum((np.count_nonzero(k >= 0, axis=1)
                    for k in self.keys[lo:hi]),
                   np.zeros(self.lanes, np.int64))


class ControlEngine:
    """The reference in the program's place, keys cut to ``key_bits``."""

    def __init__(self, config: dict, key_bits: int = 16):
        self.config, self.key_bits = config, key_bits
        self.ref = plugins.load("reference", config["reference"])

    def replay_stream(self, policy, chunks, K, **kw):
        if hasattr(self.ref, "replay_lanes"):
            return self._coupled(chunks)
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

    def _coupled(self, chunks):
        """Every lane in one ``replay_lanes`` call over all chunks fed so
        far, again after each chunk: the window is paced by the reference,
        so it records no more chunks than the reference replays in it."""
        fed, tot = Fed(), None
        for item in chunks:
            fed.add(*split(item))
            lanes = list(range(fed.lanes))
            tot = check.reference_totals(self.config, fed, lanes,
                                         len(fed.keys), self.key_bits)
        return result({f: [tot[lane][f] for lane in lanes] for f in FIELDS})


def _faulty(kind: str, base):
    """``base`` with fault ``kind`` planted in its step, and in its budgeted
    step where it has one (a pool's fleet steps a resizable policy so).  Of
    a subclass of ``base``'s own class, so that whatever takes ``base``
    takes it too."""
    import jax.numpy as jnp

    def plant(state, req, new, info):
        if kind == "frozen":
            return state, info
        hit = info.hit & (req.key % 97 != 0)
        return new, info._replace(
            hit=hit, bytes_missed=jnp.where(hit, 0, req.size),
            penalty=jnp.where(hit, jnp.float32(0), req.cost))

    cls = type(base)
    body = {"name": f"faulty_{kind}",
            "step": lambda self, state, req: plant(
                state, req, *cls.step(self, state, req))}
    if hasattr(cls, "step_budgeted"):
        body["step_budgeted"] = lambda self, state, req: plant(
            state, req, *cls.step_budgeted(self, state, req))
    faulty = object.__new__(type(f"Faulty{cls.__name__}", (cls,), body))
    faulty.__dict__.update(vars(base))
    return faulty


class PolicyFault:
    """The program, with its policy's step broken."""

    def __init__(self, kind: str):
        self.kind = kind

    def replay_stream(self, policy, chunks, K, **kw):
        from repro.core import Engine, make_policy
        return Engine().replay_stream(_faulty(self.kind, make_policy(policy)),
                                      chunks, K, **kw)


class HalfBatch:
    """The program on half the batch; the rest get the mean of that half."""

    def replay_stream(self, policy, chunks, K, **kw):
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

        m = Engine().replay_stream(policy, halves(), K, **kw).metrics
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
    """The generic faults, planted under ``Engine().replay_stream``."""
    return {"frozen": PolicyFault("frozen"), "half_batch": HalfBatch(),
            "altered": PolicyFault("altered")}


def faults(config: dict) -> dict:
    """The configuration's faults: the generic ones, and beside them its
    entry's own, ``faults(config)``, none of which may take a generic
    fault's name."""
    entry = plugins.load("entries", config["entry"])
    own = entry.faults(config) if hasattr(entry, "faults") else {}
    generic = fault_engines()
    if set(own) & set(generic):
        raise ValueError(f"entry {config['entry']!r}: faults "
                         f"{sorted(set(own) & set(generic))} would replace "
                         "generic ones")
    return {**generic, **own}


def engines(config: dict, what: str) -> dict:
    """``{name: engine}`` for ``--what``; the program's own is ``None``."""
    if what == "program":
        return {"program": None}
    if what == "control":
        return {"control": ControlEngine(config)}
    return faults(config)


def shrink(config: dict, mix: dict) -> tuple:
    """The cell at a size a CPU test holds, by its traffic source's
    ``shrink(config, mix)``."""
    return plugins.load("generators", mix["generator"]).shrink(config, mix)


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
    for seed in args.seeds:
        for name, engine in engines(config, args.what).items():
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
