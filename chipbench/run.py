"""Chip benchmark of the cache-replay engine: one cell per run.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration (``configs/<name>.json``:
the program's entry it drives, ``entries/<entry>.py``, and its plain
reference, ``reference/<reference>.py``) and a traffic mix
(``traffic/<name>.json``: parameters of a source under ``generators/``).
Each metric is a reader of its own, ``metrics/<name>.py``.  All are found
by name (``plugins.py``).  A run:

1. needs a TPU: with no accelerator, or fewer chips than the cell asks
   for, it exits non-zero and prints no result;
2. makes the cell's traffic from ``--seed`` on the host;
3. drives the configuration's entry once (``Engine().replay_stream``,
   as a user calls it, with no lowering chosen) over ``window.Window``:
   warm-up chunks (set-up: the compile or cache load, and the caches
   filled to their steady size), then ``--seconds`` of whole chunks.  An
   entry whose ``drive`` declares a ``devices`` parameter is handed the
   chips the cell holds there (to build its mesh on); any other is called
   without it;
4. with ``--trace 1``, profiles a few chunks of the window and reduces the
   trace (``tracereduce.py``);
5. compares the engine's totals with what was fed and with the plain
   reference (``check.py``), and prints each number compared beside its
   limit, last on standard error.  What was fed is what the source
   counts (its ``live`` hook, where its lanes idle: ``generator.py``); a
   reference whose lanes share state replays them in one call (its
   ``replay_lanes`` hook: ``check.py``);
6. prints one JSON line last on standard output: ``correct``,
   ``attempted`` (the requests the source handed out in the window, idle
   slots not counted: the numerator of ``requests_per_s``), ``failed``
   (those of them on the lanes that failed a check), ``metrics`` (the
   cell's end-to-end metrics, or with ``--trace 1`` its per-layer ones),
   ``device``, ``breakdown`` (traced runs) and ``checks``.  A traced run
   in which a per-layer metric of the cell finds nothing to read fails
   instead.

JAX's compilation cache is kept in ``.jax_cache`` at the root of the
checkout (or in ``$JAX_COMPILATION_CACHE_DIR``).
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import inspect  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402

from chipbench import check, generator, plugins  # noqa: E402
from chipbench.tracereduce import reduce_trace  # noqa: E402
from chipbench.window import Tracer, Window  # noqa: E402


class NoChip(RuntimeError):
    pass


def load_json(path):
    with open(path) as f:
        return json.load(f)


def applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str):
    """The cell, its configuration, its traffic mix and its metrics (end
    to end, per layer), all found by name from ``BENCHMARK.json``."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(os.path.join(ROOT, cfg["file"]))
    mix = load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    e2e = [m for m in bench["end_to_end"] if applies(m, name)]
    layer = [m for m in bench["per_layer"] if applies(m, name)]
    return cell, config, mix, e2e, layer


def find_chips(n: int):
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {devices[0].platform!r}")
    if len(devices) < n:
        raise NoChip(f"{n} chips asked, {len(devices)} found")
    return devices[:n]


def peak_of(device) -> dict:
    table = load_json(os.path.join(HERE, "peaks.json"))["devices"]
    if device.device_kind not in table:
        raise KeyError(f"no peaks for device kind {device.device_kind!r} in "
                       "chipbench/peaks.json")
    return table[device.device_kind]


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def run_cell(config: dict, mix: dict, seed: int, seconds: float, trace: bool,
             metrics: list, *, devices, engine=None, peak=None,
             t_process: float = T_PROCESS) -> dict:
    """Drive one run and return its result line (a dict).  ``devices``
    are the chips the cell holds (``[]`` skips the device readings), handed
    on to an entry whose ``drive`` declares ``devices``; ``engine`` stands
    in for the program's own (controls, faults)."""
    enable_compile_cache()
    entry = plugins.load("entries", config["entry"])
    chips = ({"devices": devices}
             if "devices" in inspect.signature(entry.drive).parameters
             else {})
    source = generator.build(config, mix, seed)
    log_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    tracer = (Tracer(log_dir, mix["trace_skip"], mix["trace_chunks"])
              if trace else None)
    win = Window(source, mix["warmup_chunks"], seconds, tracer)
    try:
        try:
            with jax.profiler.TraceAnnotation("replay_stream"):
                res = entry.drive(config, win, engine, **chips)
            t_end = time.perf_counter()
        finally:
            if tracer is not None:
                tracer.stop(len(win.calls))
        mem = memory_peak(devices) if devices else 0
        traced = None
        if trace and len(tracer.chunks):
            path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                              recursive=True)
            traced = reduce_trace(path)
            traced["steps"] = len(tracer.chunks) * source.T
            traced["chunks"] = [mix["warmup_chunks"] + c
                                for c in tracer.chunks]
        lanes = check.sample_lanes(source.lanes, mix["check_lanes"],
                                   source.strata, seed)
        verdict = check.compare(res.metrics, config, source, win.fed, lanes)
        window_live = generator.live(source, win.warmup, win.fed)
    finally:
        source.close()
        if log_dir:
            shutil.rmtree(log_dir, ignore_errors=True)

    window_requests = int(window_live.sum())
    ctx = {"config": config, "traffic": mix, "lanes": source.lanes,
           "setup_s": win.start - t_process, "window_s": t_end - win.start,
           "window_requests": window_requests, "chunk_s": win.chunk_s,
           "traced": traced, "peak": peak, "ranks": verdict["ranks"]}
    values = {}
    for m in metrics:
        v = plugins.load("metrics", m["name"]).read(ctx)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    checks = verdict["checks"]
    correct = all(v <= lim for v, lim in checks.values())
    failed_lanes = verdict["failed_lanes"]
    out = {
        "correct": correct,
        "attempted": window_requests,
        "failed": int(window_live[failed_lanes].sum()),
        "metrics": values,
        "device": {
            "platform": devices[0].platform if devices else "none",
            "kind": devices[0].device_kind if devices else "none",
            "count": len(devices),
            "memory_peak_bytes": mem,
        },
    }
    if traced is not None:
        out["device"]["busy_s"] = traced["busy_s"]
        out["device"]["window_s"] = traced["window_s"]
        out["breakdown"] = {"device_ops": traced["device_ops"],
                            "idle_gaps": traced["idle_gaps"]}
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    out["checked_lanes"] = lanes
    out["window_chunks"] = len(win.calls)
    return out


def report(out: dict) -> None:
    """The compared numbers last on standard error, the result line last
    on standard output (``checks`` as its last key)."""
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    line = {k: v for k, v in out.items() if k != "checks"}
    line["checks"] = out["checks"]
    print(json.dumps(line), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell, config, mix, e2e, layer = load_cell(args.workload)
    try:
        devices = find_chips(cell["chips"])
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    wanted = layer if args.trace else e2e
    out = run_cell(config, mix, args.seed, args.seconds, bool(args.trace),
                   wanted, devices=devices, peak=peak_of(devices[0]))
    missing = [m["name"] for m in wanted if m["name"] not in out["metrics"]]
    if missing:
        print(f"chipbench: nothing to read for {missing} in this run; "
              f"checks {out['checks']}", file=sys.stderr)
        return 4
    report(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
