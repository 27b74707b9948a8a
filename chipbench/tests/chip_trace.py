"""Record ``Engine.replay_stream`` under the profiler on the chip, and split
the idle between chunks by the engine's ``engine.*`` spans.

    python3 chipbench/tests/chip_trace.py fixture [--out DIR]
    python3 chipbench/tests/chip_trace.py cell --workload <cell> --seed <n> --seconds <s>

``fixture`` drives the harness's own pieces (``window.Window`` and
``Tracer`` inside a ``replay_stream`` span, as ``run.py`` does) over a few
``[1024, 16]`` chunks of the ``skew1024`` mix, and writes to ``--out``
(``chipbench/tests/data/`` by default): the trace (``replay_stream.xplane.pb``) and
``replay_stream.json``, the numbers ``tracereduce.reduce_trace`` gives for
it and its :func:`engine_split`.  It also prints what a span costs with
the profiler off and on.

``cell`` makes a cell's traced run through ``run.run_cell`` and prints one
JSON line: the per-layer metrics, the breakdown, the window's
:func:`engine_split`, and the median of each ``engine.*`` span over the
window's chunks from ``repro.core.tracing.last_stream()``.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import numpy as np  # noqa: E402

from chipbench import tracereduce  # noqa: E402

DATA = os.path.join(HERE, "data")
FIXTURE = dict(chunk=[1024, 16], ring=4096, warmup=4, skip=2, n=3, seed=7)


def engine_spans(path) -> dict:
    """Host events named ``engine.*``: name -> ``[n, 2]`` (start, end) ns."""
    from jax.profiler import ProfileData
    out: dict = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("engine."):
                        out.setdefault(ev.name, []).append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns))
    return {k: np.asarray(v, np.float64) for k, v in out.items()}


def overlap(a: np.ndarray, b: np.ndarray) -> float:
    """Length of the intersection of two sets of disjoint intervals."""
    if len(a) == 0 or len(b) == 0:
        return 0.0
    lo = np.maximum(a[:, None, 0], b[None, :, 0])
    hi = np.minimum(a[:, None, 1], b[None, :, 1])
    return float(np.clip(hi - lo, 0, None).sum())


def engine_split(path) -> dict:
    """The idle between chunks in the traced window (device idle outside
    any device program), in seconds, by the ``engine.*`` span it falls in
    (interval intersection), the rest under ``none``; and what share of
    ``tracereduce``'s ``replay_stream`` idle gaps the spans cover."""
    host, devices = tracereduce.load(path)
    (lo, hi), = host["traced_window"]
    spans = engine_spans(path)
    every = tracereduce._union(np.concatenate(list(spans.values())))
    feed = tracereduce._union(tracereduce._clip(
        host.get("feed", np.zeros((0, 2))), lo, hi))
    split = dict.fromkeys(sorted(spans), 0.0)
    between = rs = rs_covered = 0.0
    n_dev = 0
    for dev in devices:
        ops = dev["ops"]
        if not ((ops[:, 0] >= lo) & (ops[:, 0] < hi)).any():
            continue
        n_dev += 1
        u = tracereduce._union(tracereduce._clip(ops, lo, hi))
        gaps = np.concatenate([[lo], u.ravel(), [hi]]).reshape(-1, 2)
        gaps = gaps[gaps[:, 1] > gaps[:, 0]]
        mods = tracereduce._union(tracereduce._clip(dev["modules"], lo, hi))
        mid = gaps.mean(axis=1)
        in_prog = tracereduce._inside(mid, mods)
        in_feed = tracereduce._inside(mid, feed) & ~in_prog
        out = gaps[~in_prog]
        between += float((out[:, 1] - out[:, 0]).sum())
        for name, iv in spans.items():
            split[name] += overlap(out, iv)
        g = gaps[~in_prog & ~in_feed]
        rs += float((g[:, 1] - g[:, 0]).sum())
        rs_covered += overlap(g, every)
    split["none"] = between - sum(split.values())
    return {"idle_between_chunks_s": between / n_dev * 1e-9,
            "by_span_s": {k: v / n_dev * 1e-9 for k, v in split.items()},
            "replay_stream_idle_s": rs / n_dev * 1e-9,
            "replay_stream_idle_in_engine_spans": rs_covered / rs if rs else
            None}


def spans_inside_window(path) -> bool:
    host, _ = tracereduce.load(path)
    (lo, hi), = host["traced_window"]
    return all(((iv[:, 0] >= lo) & (iv[:, 1] <= hi)).all()
               for iv in engine_spans(path).values())


def span_cost_us(n: int = 20000) -> float:
    """Mean cost of one ``engine.*`` span with its record, in us."""
    from repro.core import tracing
    with tracing.stream() as rec:
        t0 = time.perf_counter()
        for i in range(n):
            with tracing.span("engine.put", i):
                pass
        dt = time.perf_counter() - t0
        rec.end_chunk(0)
    return dt / n * 1e6


class Counted:
    """A source that ends after ``n`` chunks."""

    def __init__(self, source, n):
        self.source, self.left = source, n

    def next(self):
        if self.left == 0:
            raise StopIteration
        self.left -= 1
        return self.source.next()


def fixture(out_dir: str = DATA) -> dict:
    import jax

    from chipbench import generator, plugins, run
    from chipbench.window import Tracer, Window
    run.find_chips(1)
    _, config, mix, _, _ = run.load_cell("paper_s.skew1024")
    f = FIXTURE
    mix = dict(mix, chunk=f["chunk"], ring=f["ring"])
    source = generator.build(config, mix, f["seed"])
    entry = plugins.load("entries", config["entry"])
    log_dir = tempfile.mkdtemp(prefix="chip-trace-")
    try:
        tracer = Tracer(log_dir, f["skip"], f["n"])
        win = Window(Counted(source, f["warmup"] + f["skip"] + f["n"] + 1),
                     f["warmup"], 1e9, tracer)
        with jax.profiler.TraceAnnotation("replay_stream"):
            entry.drive(config, win)
        path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                          recursive=True)
        os.makedirs(out_dir, exist_ok=True)
        shutil.copy(path, os.path.join(out_dir, "replay_stream.xplane.pb"))
        cost_off = span_cost_us()
        with jax.profiler.trace(os.path.join(log_dir, "cost")):
            cost_on = span_cost_us()
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    path = os.path.join(out_dir, "replay_stream.xplane.pb")
    out = {"recorded_with": dict(f, device=jax.devices()[0].device_kind),
           "reduce_trace": tracereduce.reduce_trace(path),
           "engine_split": engine_split(path)}
    with open(os.path.join(out_dir, "replay_stream.json"), "w") as fh:
        json.dump(out, fh, indent=1)
    print(json.dumps({"fixture": out, "spans_inside_window":
                      spans_inside_window(path),
                      "span_cost_us": {"profiler_off": cost_off,
                                       "profiler_on": cost_on},
                      "bytes": os.path.getsize(path)}))
    return out


def cell(workload: str, seed: int, seconds: float) -> dict:
    from chipbench import run
    from repro.core import tracing
    spec, config, mix, _, layer = run.load_cell(workload)
    devices = run.find_chips(spec["chips"])
    split = {}
    reduce_trace = run.reduce_trace

    def reduce_and_split(path):
        split.update(engine_split(path), inside=spans_inside_window(path))
        return reduce_trace(path)

    run.reduce_trace = reduce_and_split
    try:
        out = run.run_cell(config, mix, seed, seconds, True, layer,
                           devices=devices, peak=run.peak_of(devices[0]))
    finally:
        run.reduce_trace = reduce_trace
    rec = tracing.last_stream()
    n = out["window_chunks"]
    medians = {c: 1e6 * float(np.median(rec.rows[c][-n:]))
               for c in tracing.COLUMNS if c.endswith("_s")}
    line = {"workload": workload, "seed": seed, "correct": out["correct"],
            "metrics": out["metrics"], "breakdown": out["breakdown"],
            "device": out["device"], "engine_split": split,
            "window_median_us": medians,
            "window_compiles": float(rec.rows["compiles"][-n:].sum()),
            "totals": rec.totals,
            "compiled": {k: v for k, v in rec.compiled.items() if v[0]}}
    print(json.dumps(line))
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="what", required=True)
    f = sub.add_parser("fixture")
    f.add_argument("--out", default=DATA)
    c = sub.add_parser("cell")
    c.add_argument("--workload", required=True)
    c.add_argument("--seed", type=int, required=True)
    c.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    if args.what == "fixture":
        fixture(args.out)
    else:
        cell(args.workload, args.seed, args.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
