"""Benchmark driver: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--fast] [--list] [--only NAME ...]

Every table runs through the declarative Sweep API (repro.bench) and
writes a schema-validated JSON result to experiments/bench/ (or
``--out-dir DIR``, so ad-hoc runs and campaign stores never interleave
JSONs into the same directory).  ``--only`` takes *exact* job names
(repeatable, comma-separable; see ``--list``) and exits non-zero when a
requested name doesn't exist — no silent no-op runs.
"""
from __future__ import annotations

import argparse
import sys
import time

from repro.bench import results
from repro.compile_cache import enable_compile_cache


def _jobs():
    from . import (ablation_eps, byte_miss, curve_cachesize, fleet_sweep,
                   kv_bounded, mrr_table, ops_per_request, real_traces,
                   robustness, skew_sweep, tenant_sweep, throughput)

    # name -> (description, fn(fast) -> validated payload)
    return {
        "mrr_table": (
            "Table III / Fig 5-6",
            lambda fast: mrr_table.run(T=20_000 if fast else 60_000,
                                       n_traces=2 if fast else 3)),
        "curve_cachesize": (
            "Fig 8",
            lambda fast: curve_cachesize.run(T=30_000 if fast else 80_000)),
        "skew_sweep": (
            "Fig 11",
            lambda fast: skew_sweep.run(T=20_000 if fast else 60_000)),
        "byte_miss": (
            "Fig 10",
            lambda fast: byte_miss.run(T=20_000 if fast else 60_000)),
        "ops_per_request": (
            "Fig 9", lambda fast: ops_per_request.run()),
        "throughput": (
            "Tables IV/V, Fig 7",
            lambda fast: throughput.run(T=10_000 if fast else 30_000)),
        "kv_bounded": (
            "beyond-paper",
            lambda fast: kv_bounded.run(gen=16 if fast else 32)),
        "real_traces": (
            "paper's real-trace grid (miniature corpus, streaming path, "
            "v2 schema)",
            lambda fast: real_traces.run(T=2000 if fast else None)),
        "tenant_sweep": (
            "beyond-paper (multi-tenant tier, v2 schema)",
            lambda fast: tenant_sweep.run(
                T=24_000 if fast else 60_000,
                seeds=(0, 1) if fast else (0, 1, 2))),
        "fleet_sweep": (
            "beyond-paper (dynamic fleet + SLO telemetry, v2 schema)",
            lambda fast: fleet_sweep.run(
                T=16_000 if fast else 40_000,
                seeds=(0, 1) if fast else (0, 1, 2))),
        "ablation_eps": (
            "beyond-paper",
            lambda fast: ablation_eps.run(T=20_000 if fast else 60_000)),
        "robustness": (
            "beyond-paper (size-aware admission vs hostile grid, "
            "v2 schema)",
            lambda fast: robustness.run(
                N=1024 if fast else 4096,
                T=6000 if fast else 40_000,
                seeds=(0,) if fast else (0, 1))),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--fast", action="store_true",
                    help="smaller T for a quick pass")
    ap.add_argument("--list", action="store_true",
                    help="print the exact job names and exit")
    ap.add_argument("--only", action="append", default=[], metavar="NAME",
                    help="run only these jobs (exact names; repeatable or "
                         "comma-separated)")
    ap.add_argument("--out-dir", default=None, metavar="DIR",
                    help="write result JSONs here instead of "
                         f"{results.RESULTS_DIR} (BENCH_OUT)")
    args = ap.parse_args(argv)
    if args.out_dir:
        results.set_results_dir(args.out_dir)

    jobs = _jobs()
    if args.list:
        for name, (desc, _) in jobs.items():
            print(f"{name:18s} {desc}")
        return 0

    selected = [n.strip() for arg in args.only for n in arg.split(",")
                if n.strip()]
    unknown = [n for n in selected if n not in jobs]
    if unknown:
        print(f"error: unknown job name(s) {unknown}; "
              f"known: {list(jobs)}", file=sys.stderr)
        return 2
    if args.only and not selected:
        print("error: --only matched nothing", file=sys.stderr)
        return 2
    to_run = selected or list(jobs)
    enable_compile_cache()

    for name in to_run:
        desc, fn = jobs[name]
        print(f"\n{'='*72}\n{name} ({desc})\n{'='*72}")
        t0 = time.perf_counter()
        payload = fn(args.fast)
        results.validate(payload)
        print(f"[{name}] {time.perf_counter()-t0:.1f}s "
              f"(schema {payload['schema']} OK, "
              f"{len(payload['records'])} records)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
