"""Smoke run of the cache-replay engine on a TPU, through its entry points.

    python chip_smoke.py [--seed S]      # one chip: phases a-e
    python chip_smoke.py --chips 4       # the 4-chip mesh phase only

One process drives the chip; all data is generated on the host from
``--seed``.  Phases (one chip):

  a. device check: the first device is a TPU and the Pallas interpreter is
     not forced (``REPRO_PALLAS_INTERPRET`` empty or ``auto``);
  b. node scale: DAC at K=65,536 over 256 independent Zipf(0.99) streams
     of 2^20 ids, compiled kernel vs the jnp lowering, lane for lane; the
     compiled program must contain the kernel (``tpu_custom_call``);
  c. one lane at K=4,096 over 2^15 requests against the plain-Python
     reference (``repro.core.oracle``): CLIMB, AdaptiveClimb and DAC
     compiled, LRU in jnp;
  d. the streaming path over a file trace equals a materialized replay;
  e. a DAC fleet under the auction arbiter keeps its slot budget at
     every step.

``--chips 4`` replays the phase-b lanes over a 4-chip ``data`` mesh and on
one of those chips (bit-identical, lanes on all four devices) and runs the
sharded fleet (budget conserved at every step).

Earlier lines report each phase's wall times on the host clock, with
compile time as set-up, and its result counts.  The last line is a JSON
object ``{"ok": true, "device": {...}}``, printed only when every phase
passed; any failure exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import Engine, make_policy  # noqa: E402
from repro.core.oracle import oracle_replay  # noqa: E402
from repro.core.policy import Request  # noqa: E402
from repro.core.simulator import _replay_batched  # noqa: E402
from repro.data import ingest  # noqa: E402
from repro.data.traces import fleet_trace, zipf_trace  # noqa: E402
from repro.fleet import FleetTier  # noqa: E402
from repro.kernels.policy_step import INTERPRET_ENV  # noqa: E402

N_IDS = 1 << 20         # key space of each shard
ALPHA = 0.99            # YCSB's zipfian constant
LANES = 256             # independent cache shards
K_NODE = 65536          # per-shard capacity; DAC rows hold 4*K
T_NODE = 4096           # requests per shard
K_REF, T_REF = 4096, 1 << 15
K_STREAM, STREAM_CHUNK = 128, 1024
CORPUS = os.path.join(ROOT, "benchmarks", "corpus", "mix.oracleGeneral.bin.gz")
FLEET_LANES, FLEET_SHARE, T_FLEET = 64, 1024, 4096


class SmokeFailure(RuntimeError):
    pass


def check(cond, what):
    if not cond:
        raise SmokeFailure(what)


def say(phase, **kv):
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


def timed(fn):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def metrics_equal(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(a, b))


def device_check(n_chips):
    dev = jax.devices()
    check(dev[0].platform == "tpu",
          f"first device is {dev[0].platform!r}, not a TPU")
    env = os.environ.get(INTERPRET_ENV, "").strip().lower()
    check(env in ("", "auto"),
          f"{INTERPRET_ENV}={env!r}: the smoke runs the compiled kernel only")
    check(len(dev) >= n_chips, f"{n_chips} chips asked, {len(dev)} found")
    say("a device", platform=dev[0].platform, kind=repr(dev[0].device_kind),
        count=len(dev), cache=enable_compile_cache())
    return dev


def node_keys(seed):
    return zipf_trace(N_IDS, LANES * T_NODE, ALPHA,
                      seed=seed).reshape(LANES, T_NODE)


def phase_node(keys):
    """(b) DAC at node scale: compiled kernel == jnp lowering."""
    pol = make_policy("dac")
    reqs = Request.of(keys)
    t0 = time.perf_counter()
    lowered = _replay_batched.lower(pol, reqs, K_NODE, False, False,
                                    "compiled", None, "data")
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t_lower, t_compile = t1 - t0, time.perf_counter() - t1
    check("tpu_custom_call" in compiled.as_text(),
          "compiled DAC replay has no tpu_custom_call: the kernel did not "
          "compile in")
    mem = compiled.memory_analysis()
    say("b node", lanes=LANES, K=K_NODE, T=T_NODE,
        setup_lower_s=round(t_lower, 3), setup_compile_s=round(t_compile, 3),
        temp_bytes=mem.temp_size_in_bytes)
    out = {}
    for mode in ("compiled", False):
        def run():
            return Engine().replay(pol, reqs, K_NODE, collect_info=False,
                                   use_pallas=mode).metrics
        _, first = timed(run)
        res, warm = timed(run)
        out[mode] = res
        say("b node", lowering=mode or "jnp",
            first_call_s=round(first, 3), warm_call_s=round(warm, 3),
            requests=int(np.sum(res.requests)), hits=int(np.sum(res.hits)))
    check(metrics_equal(out["compiled"], out[False]),
          "compiled and jnp lowerings disagree on some lane")
    say("b node", lanes_equal=LANES)
    return out["compiled"]


def phase_reference(seed):
    """(c) one lane against the plain-Python reference."""
    keys = zipf_trace(N_IDS, T_REF, ALPHA, seed=seed + 1)
    for pol, mode in (("climb", "compiled"), ("adaptiveclimb", "compiled"),
                      ("dynamicadaptiveclimb", "compiled"), ("lru", False)):
        res, wall = timed(lambda: Engine().replay(pol, keys, K_REF,
                                                  use_pallas=mode).info.hit)
        hits = np.asarray(res)
        ref = oracle_replay(pol, keys, K_REF)["hits"]
        check(np.array_equal(hits, ref),
              f"{pol}: {int(np.sum(hits != ref))} of {T_REF} requests "
              "differ from the reference")
        say("c reference", policy=pol, lowering=mode or "jnp", K=K_REF,
            T=T_REF, hits=int(hits.sum()), equal=True,
            first_call_s=round(wall, 3))


def phase_stream():
    """(d) streaming a file trace == replaying it materialized."""
    (stream, t_stream) = timed(lambda: Engine().replay_stream(
        "dac", ingest.iter_chunks(CORPUS, chunk=STREAM_CHUNK), K_STREAM,
        use_pallas="compiled").metrics)
    full = ingest.load_trace(CORPUS)
    (whole, t_whole) = timed(lambda: Engine().replay(
        "dac", Request.of(full.keys, full.sizes, full.costs), K_STREAM,
        collect_info=False, use_pallas="compiled").metrics)
    for f in ("requests", "hits", "bytes_total", "bytes_missed"):
        check(float(getattr(stream, f)) == float(getattr(whole, f)),
              f"stream {f}={getattr(stream, f)} != replay "
              f"{getattr(whole, f)}")
    say("d stream", requests=int(stream.requests), hits=int(stream.hits),
        bytes_missed=float(stream.bytes_missed), chunk=STREAM_CHUNK,
        K=K_STREAM, equal=True, stream_first_call_s=round(t_stream, 3),
        replay_first_call_s=round(t_whole, 3))


def fleet_inputs(seed):
    tier = FleetTier("dac", n_lanes=FLEET_LANES,
                     budget=FLEET_LANES * FLEET_SHARE, arbiter="auction")
    keys = fleet_trace(N=1 << 16, T=T_FLEET, n_lanes=FLEET_LANES, rate=0.05,
                       mean_session=1500, seed=seed + 2)
    return tier, keys


def conserved(tier, res):
    """``sum(k over live lanes) + k_min * idle lanes <= budget`` at every
    step; returns the step count checked."""
    k = np.asarray(res.obs["k"]).astype(np.int64)
    alive = np.asarray(res.obs["alive"])
    held = np.where(alive, k, 0).sum(axis=1) + tier.k_min * (~alive).sum(1)
    worst = int(held.max())
    check(worst <= tier.budget,
          f"fleet holds {worst} slots at some step, budget {tier.budget}")
    return k.shape[0], worst


def phase_fleet(seed):
    """(e) one-chip fleet under the auction arbiter."""
    tier, keys = fleet_inputs(seed)
    res, wall = timed(lambda: Engine().replay_fleet(
        tier, keys, observe=True, use_pallas="compiled"))
    steps, worst = conserved(tier, res)
    say("e fleet", lanes=FLEET_LANES, budget=tier.budget, steps=steps,
        max_held=worst, conserved=True,
        hits=int(np.sum(res.metrics.hits)),
        requests=int(np.sum(res.metrics.requests)),
        first_call_s=round(wall, 3))


def phase_mesh(keys, seed, devices):
    """--chips 4: lanes over the mesh == lanes on one chip; sharded fleet
    conserves its budget."""
    mesh = Mesh(np.array(devices), ("data",))
    reqs = Request.of(keys)
    sharded = jax.device_put(reqs, NamedSharding(mesh, P("data", None)))
    check(len(sharded.key.sharding.device_set) == len(devices),
          "mesh input does not span every device")
    one, t_one = timed(lambda: Engine().replay(
        "dac", jax.device_put(reqs, devices[0]), K_NODE,
        collect_info=False, use_pallas="compiled").metrics)
    many, t_many = timed(lambda: Engine().replay(
        "dac", sharded, K_NODE, collect_info=False, use_pallas="compiled",
        mesh=mesh).metrics)
    spans = len(many.hits.sharding.device_set)
    check(spans == len(devices), f"mesh output spans {spans} devices")
    check(metrics_equal(one, many), "mesh replay differs from one chip")
    say("mesh replay", lanes=LANES, K=K_NODE, T=T_NODE, devices=spans,
        hits=int(np.sum(many.hits)), equal=True,
        one_chip_first_call_s=round(t_one, 3),
        mesh_first_call_s=round(t_many, 3))

    tier, fkeys = fleet_inputs(seed)
    res, wall = timed(lambda: Engine().replay_fleet(
        tier, fkeys, observe=True, mesh=mesh, use_pallas="compiled"))
    spans = len(res.metrics.hits.sharding.device_set)
    check(spans == len(devices), f"fleet output spans {spans} devices")
    steps, worst = conserved(tier, res)
    say("mesh fleet", lanes=FLEET_LANES, budget=tier.budget, steps=steps,
        devices=spans, max_held=worst, conserved=True,
        first_call_s=round(wall, 3))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    dev = device_check(args.chips)
    t0 = time.perf_counter()
    keys = node_keys(args.seed)
    say("data", node_keys=keys.shape, setup_s=round(time.perf_counter() - t0,
                                                    3))
    if args.chips == 4:
        phase_mesh(keys, args.seed, dev[:4])
    else:
        phase_node(keys)
        phase_reference(args.seed)
        phase_stream()
        phase_fleet(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev[0].platform, "kind": dev[0].device_kind,
        "count": len(dev)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
