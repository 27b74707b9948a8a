"""The harness's optional hooks, on the CPU at a small size: a source
whose lanes idle counts what it fed (``live``), and a reference replays
lanes that share state in one call (``replay_lanes``); and, at the end, a
pool over four host devices plugged in with new parts only (an entry's
``devices`` and ``faults``, a source's ``shrink``, a reference with only
``replay_lanes``) and ``repro.core.Engine`` given the pool's path, run
through ``test_selfcheck.outcomes``.

The stand-ins are plugged in through ``plugins.load``'s ``sys.modules``
lookup:

* ``pool_lanes``: a shared pool of 8 lanes that tenants arrive on and
  leave (``repro.data.traces.fleet_trace``): key ``-1`` where a lane has
  no tenant, about a third of the lane-steps;
* ``FleetEngine``: the pool replayed by ``repro.fleet.replay_fleet`` (DAC
  lanes, auction arbiter) over every chunk the window fed, in one call,
  since the fleet has no streamed form;
* ``pool_ref``: ``replay_lanes`` replaying every lane of the source
  together through the same fleet.  It stands in for a plain reference of
  the pool, which does not exist yet: it shows the hook's plumbing, and
  what it compares, not the fleet's correctness.

A clock that moves a fixed step at every reading makes the window the
same number of chunks in every run.
"""
import sys
import types

import numpy as np
import pytest

from chipbench import check, generator, plugins, run, selfcheck, window

CONFIG = {"entry": "replay_stream", "policy": "dac",
          "policy_params": {"eps": 0.5, "growth": 4, "k_min": 2},
          "reference": "pool_ref", "K": 64, "ids_per_lane": 256}
MIX = {"generator": "pool_lanes", "chunk": [8, 16], "ring": 1024,
       "rate": 0.15, "mean_session": 40, "warmup_chunks": 4,
       "check_lanes": 3, "trace_skip": 0, "trace_chunks": 0}
E2E = [{"name": "requests_per_s", "unit": "requests/s"},
       {"name": "chunk_p95_ms", "unit": "ms"},
       {"name": "setup_s", "unit": "s"}]
SEED = 2**31 + 11
SECONDS = 0.5


class Clock:
    """A host clock that moves ``tick`` seconds at every reading."""

    def __init__(self, tick: float = 0.01):
        self.t, self.tick = 0.0, tick

    def perf_counter(self) -> float:
        self.t += self.tick
        return self.t


class PoolLanes:
    """``[lanes, T]`` chunks cut from a ring of a pool's lane streams."""

    def __init__(self, config: dict, mix: dict, seed: int):
        from repro.data.traces import fleet_trace
        self.lanes, self.T = mix["chunk"]
        self.ring, self.strata = mix["ring"], 1
        self.keys = np.ascontiguousarray(fleet_trace(
            N=config["ids_per_lane"], T=self.ring, n_lanes=self.lanes,
            rate=mix["rate"], mean_session=mix["mean_session"], seed=seed).T)
        self.pos = 0

    def next(self):
        lo = self.pos
        self.pos = (lo + self.T) % self.ring
        return self.keys[:, lo:lo + self.T]

    def lane_chunks(self, lane: int, n_chunks: int):
        for c in range(n_chunks):
            lo = (c * self.T) % self.ring
            yield self.keys[lane, lo:lo + self.T], None

    def live(self, lo: int, hi: int) -> np.ndarray:
        per_chunk = (self.keys >= 0).reshape(self.lanes, -1, self.T).sum(2)
        return per_chunk[:, np.arange(lo, hi) % per_chunk.shape[1]].sum(1)

    def close(self):
        pass


def fleet_totals(policy, K: int, keys: np.ndarray, **kw) -> dict:
    """``[lanes, steps]`` keys through one fleet of ``policy`` (a spec or a
    policy), ``kw`` for ``replay_fleet`` (``mesh``, ``rebalance``):
    per-lane totals."""
    from repro.fleet import FleetTier, replay_fleet
    tier = FleetTier(policy, n_lanes=len(keys), budget=K, arbiter="auction")
    m = replay_fleet(tier, np.ascontiguousarray(keys.T), **kw).metrics
    return {f: np.asarray(getattr(m, f), np.float64)
            for f in selfcheck.FIELDS}


class FleetEngine:
    """The pool replayed through the program's fleet; ``fault`` plants
    ``idle_counted`` (every slot counted as a request) or ``dropped`` (one
    live request of a lane left out of the stream)."""

    def __init__(self, fault: str | None = None):
        self.fault = fault

    def replay_stream(self, policy, chunks, K):
        keys = np.concatenate([np.asarray(c) for c in chunks], axis=1)
        if self.fault == "dropped":
            lane = int(np.argmax((keys >= 0).sum(1)))
            keys = keys.copy()
            keys[lane, np.flatnonzero(keys[lane] >= 0)[-1]] = -1
        tot = fleet_totals(policy, K, keys)
        if self.fault == "idle_counted":
            tot["requests"] = np.full(len(keys), float(keys.shape[1]))
        return selfcheck.result(tot)


def pool_ref_module(hits_off: int = 0):
    """``replay_lanes`` over the whole pool; records each call's lanes and
    reports ``hits_off`` hits too many on every lane asked."""
    mod = types.ModuleType("chipbench.reference.pool_ref")
    mod.calls = []

    def replay_lanes(source, lanes, n_chunks, config, key_bits=32):
        mod.calls.append(list(lanes))
        keys = np.stack([np.concatenate([k for k, _ in source.lane_chunks(
            lane, n_chunks)]) for lane in range(source.lanes)])
        spec = plugins.load("entries", "replay_stream").policy_spec(config)
        tot = fleet_totals(spec, config["K"], keys)
        tot["hits"] = tot["hits"] + hits_off
        return {lane: {f: v[lane] for f, v in tot.items()} for lane in lanes}

    mod.replay_lanes = replay_lanes
    return mod


def generator_module(name: str, make):
    mod = types.ModuleType(f"chipbench.generators.{name}")
    mod.make = make
    return mod


@pytest.fixture
def plugged(monkeypatch):
    """The stand-ins in ``sys.modules``, the fixed-step clock in the
    window and the harness; returns the reference stand-in."""
    ref = pool_ref_module()
    monkeypatch.setitem(sys.modules, "chipbench.generators.pool_lanes",
                        generator_module("pool_lanes", PoolLanes))
    monkeypatch.setitem(sys.modules, "chipbench.reference.pool_ref", ref)
    clock = Clock()
    monkeypatch.setattr(window, "time", clock)
    monkeypatch.setattr(run, "time", clock)
    return ref


def pool_run(engine, metrics=E2E):
    return run.run_cell(CONFIG, MIX, SEED, SECONDS, False, metrics,
                        devices=[], engine=engine, t_process=0.0)


def window_live(out) -> np.ndarray:
    source = PoolLanes(CONFIG, MIX, SEED)
    lo = MIX["warmup_chunks"]
    return source.live(lo, lo + out["window_chunks"])


def test_idling_source_reads_correct_with_every_gap_0(plugged):
    out = pool_run(FleetEngine())
    n = MIX["warmup_chunks"] + out["window_chunks"]
    source = PoolLanes(CONFIG, MIX, SEED)
    fed = generator.live(source, 0, n)
    assert out["window_chunks"] > 0
    assert (fed < n * source.T).all() and fed.sum() < 0.8 * n * 8 * 16
    assert out["correct"], out["checks"]
    assert {k: c["value"] for k, c in out["checks"].items()} == dict.fromkeys(
        check.LIMITS, 0.0)
    assert out["failed"] == 0


def test_requests_per_s_counts_the_live_requests_of_the_window(
        plugged, monkeypatch):
    seen = {}
    probe = types.ModuleType("chipbench.metrics.probe")
    probe.read = lambda ctx: seen.update(ctx) or 1.0
    monkeypatch.setitem(sys.modules, "chipbench.metrics.probe", probe)
    out = pool_run(FleetEngine(), E2E + [{"name": "probe", "unit": "1"}])
    live = int(window_live(out).sum())
    assert out["attempted"] == seen["window_requests"] == live
    assert live < out["window_chunks"] * 8 * 16
    rate = out["metrics"]["requests_per_s"]["value"]
    assert rate == live / seen["window_s"]


def test_idle_slots_counted_as_requests_fail(plugged):
    out = pool_run(FleetEngine("idle_counted"))
    assert not out["correct"]
    assert out["checks"]["requests_gap"]["value"] >= 1
    assert out["failed"] == out["attempted"]   # every lane idled a while


def test_a_dropped_live_request_fails(plugged):
    out = pool_run(FleetEngine("dropped"))
    assert not out["correct"]
    assert out["checks"]["requests_gap"]["value"] >= 1
    assert out["failed"] > 0


def test_replay_lanes_is_called_once_and_its_lanes_are_compared(
        plugged, monkeypatch):
    out = pool_run(FleetEngine())
    assert plugged.calls == [out["checked_lanes"]]
    assert len(out["checked_lanes"]) == MIX["check_lanes"]

    off = pool_ref_module(hits_off=1)
    monkeypatch.setitem(sys.modules, "chipbench.reference.pool_ref", off)
    out = pool_run(FleetEngine())
    assert len(off.calls) == 1
    assert not out["correct"]
    assert out["checks"]["hits_gap"]["value"] == 1
    assert out["checks"]["requests_gap"]["value"] == 0
    live = window_live(out)
    assert out["failed"] == int(live[out["checked_lanes"]].sum())


class Counting:
    """A source with a ``live`` hook that counts the keys it hands out."""

    def __init__(self, base):
        self.base = base

    def __getattr__(self, name):
        return getattr(self.base, name)

    def live(self, lo, hi):
        out = np.zeros(self.lanes, np.int64)
        for lane in range(self.lanes):
            for c, (keys, _) in enumerate(self.base.lane_chunks(lane, hi)):
                if c >= lo:
                    out[lane] += np.count_nonzero(np.asarray(keys) >= 0)
        return out


@pytest.mark.parametrize("engine", [None, "half_batch"])
@pytest.mark.parametrize("traffic", ["skew1024", "file1"])
def test_live_hook_absent_and_present_read_alike(monkeypatch, traffic,
                                                 engine):
    """The accepted cells' sources, as they are and with a ``live`` hook:
    the same ``attempted``, ``failed`` and ``checks``, which are today's
    ``T`` a lane a chunk."""
    config = run.load_json(f"{run.HERE}/configs/dac_paper_s.json")
    mix = run.load_json(f"{run.HERE}/traffic/{traffic}.json")
    config, mix = selfcheck.shrink(config, mix)
    base = mix["generator"]
    monkeypatch.setitem(sys.modules, "chipbench.generators.counting",
                        generator_module("counting", lambda *a: Counting(
                            plugins.load("generators", base).make(*a))))
    outs = []
    for m in (mix, dict(mix, generator="counting")):
        clock = Clock()
        monkeypatch.setattr(window, "time", clock)
        monkeypatch.setattr(run, "time", clock)
        eng = None if engine is None else selfcheck.fault_engines()[engine]
        outs.append(run.run_cell(config, m, SEED, SECONDS, False, E2E,
                                 devices=[], engine=eng, t_process=0.0))
    plain, hooked = outs
    keys = ("window_chunks", "attempted", "failed", "correct", "checks")
    assert {k: plain[k] for k in keys} == {k: hooked[k] for k in keys}
    lanes, T = (mix["chunk"] if traffic == "skew1024" else (1, mix["chunk"]))
    assert plain["attempted"] == plain["window_chunks"] * lanes * T
    assert plain["correct"] == (engine is None)
    if engine is not None:
        assert plain["failed"] > 0


# A pool over four chips, plugged in with new parts only: an entry that
# takes the cell's chips (``drive(devices=)``) and adds a fault of its own
# (``faults``), a reference with only ``replay_lanes``, and a source with
# ``live`` and ``shrink``.  The program's engine is extended as the pool's
# PR would extend it: ``Engine().replay_stream`` takes ``mesh=`` and
# ``rebalance=`` and then replays the fleet, so the generic faults, planted
# under it, reach the pool.  It runs through ``test_selfcheck.outcomes``,
# in a subprocess on four host devices.

POOL4_CONFIG = {"entry": "pool4", "policy": "dac",
                "policy_params": {"eps": 0.5, "growth": 4, "k_min": 2},
                "reference": "pool4_ref", "K": 2048, "shards": 4,
                "rebalance": 16, "ids_per_lane": 1 << 18}
POOL4_MIX = {"generator": "pool4_lanes", "chunk": [128, 512],
             "ring": 1 << 15, "rate": 0.15, "mean_session": 400,
             "id_shift": 13, "warmup_chunks": 4, "check_lanes": 3,
             "trace_skip": 0, "trace_chunks": 0}
POOL4_WHATS = ["program", "control", "frozen", "half_batch", "altered",
               "no_exchange"]


def pool4_shrink(config: dict, mix: dict) -> tuple:
    """8 of the 128 lanes, with their share of the budget."""
    lanes = 8
    K = config["K"] * lanes // mix["chunk"][0]
    return dict(config, K=K), dict(mix, chunk=[lanes, 16], ring=1024)


class SpacedPoolLanes(PoolLanes):
    """``PoolLanes`` with its ids spaced ``2**id_shift`` apart in the int32
    key space, so that they differ above bit 16 and the control's cut to 16
    bits folds them onto a few keys; each even id is then moved down onto
    the multiple of 97 below it, so that the keys whose answers the generic
    ``altered`` fault reports as misses are half the ids, not one in 97."""

    def __init__(self, config: dict, mix: dict, seed: int):
        super().__init__(config, mix, seed)
        spaced = self.keys.astype(np.int64) << mix["id_shift"]
        spaced -= (spaced % 97) * (self.keys % 2 == 0)
        self.keys = np.where(self.keys >= 0, spaced, -1).astype(np.int32)


def pool_engine():
    """``repro.core.Engine`` with the pool's path: given ``mesh=``, every
    chunk fed goes through the fleet of ``policy`` sharded over the mesh,
    in one call, since the fleet has no streamed form."""
    from repro.core import Engine, make_policy

    class PoolEngine(Engine):
        def replay_stream(self, policy, chunks, K, *, mesh=None,
                          rebalance=256, **kw):
            if mesh is None:
                return super().replay_stream(policy, chunks, K, **kw)
            keys = np.concatenate([np.asarray(c) for c in chunks], axis=1)
            return selfcheck.result(fleet_totals(
                make_policy(policy), K, keys, mesh=mesh,
                rebalance=rebalance))

    return PoolEngine


def pool4_drive(config: dict, chunks, engine=None, devices=None):
    from jax.sharding import Mesh

    from repro.core import Engine
    engine = Engine() if engine is None else engine
    spec = plugins.load("entries", "replay_stream").policy_spec(config)
    return engine.replay_stream(spec, chunks, config["K"],
                                mesh=Mesh(np.array(devices), ("data",)),
                                rebalance=config["rebalance"])


class NoExchange:
    """The pool with the re-deal of the budget between chips left out after
    the first step: a ``rebalance`` period longer than any run."""

    def replay_stream(self, policy, chunks, K, *, rebalance, **kw):
        from repro.core import Engine
        return Engine().replay_stream(policy, chunks, K, rebalance=1 << 30,
                                      **kw)


def pool4_replay_lanes(source, lanes, n_chunks, config, key_bits=32):
    import jax
    from jax.sharding import Mesh
    keys = np.stack([np.concatenate([k for k, _ in source.lane_chunks(
        lane, n_chunks)]) for lane in range(source.lanes)])
    if key_bits < 32:
        keys = np.where(keys >= 0, keys & ((1 << key_bits) - 1), keys)
    spec = plugins.load("entries", "replay_stream").policy_spec(config)
    mesh = Mesh(np.array(jax.devices()[:config["shards"]]), ("data",))
    tot = fleet_totals(spec, config["K"], keys, mesh=mesh,
                       rebalance=config["rebalance"])
    return {lane: {f: v[lane] for f, v in tot.items()} for lane in lanes}


def paced_replay_lanes(clock: Clock, seconds_a_chunk: float = 0.05):
    """``pool4_replay_lanes`` that moves the fixed-step clock
    ``seconds_a_chunk`` for each chunk it replays, as a reference's own
    time would move the host clock."""
    def replay_lanes(source, lanes, n_chunks, config, key_bits=32):
        clock.t += seconds_a_chunk * n_chunks
        return pool4_replay_lanes(source, lanes, n_chunks, config, key_bits)
    return replay_lanes


def install():
    """The four-chip pool's parts in ``sys.modules``, its path in
    ``repro.core.Engine``, and the fixed-step clock in the window and the
    harness (in a subprocess of its own)."""
    import repro.core
    clock = Clock()
    entry = types.ModuleType("chipbench.entries.pool4")
    entry.drive = pool4_drive
    entry.faults = lambda config: {"no_exchange": NoExchange()}
    ref = types.ModuleType("chipbench.reference.pool4_ref")
    ref.replay_lanes = paced_replay_lanes(clock)
    source = generator_module("pool4_lanes", SpacedPoolLanes)
    source.shrink = pool4_shrink
    for mod in (entry, ref, source):
        sys.modules[mod.__name__] = mod
    repro.core.Engine = pool_engine()
    window.time = clock
    run.time = clock


def test_a_four_chip_pool_needs_no_edit_of_the_harness():
    from chipbench.tests.test_selfcheck import outcomes
    outs = outcomes(POOL4_CONFIG, POOL4_MIX, 4, POOL4_WHATS,
                    plug="chipbench.tests.test_pool_hooks")
    program = outs["program"]
    assert program["correct"], program["checks"]
    assert {k: c["value"] for k, c in program["checks"].items()} == (
        dict.fromkeys(check.LIMITS, 0.0))
    assert program["failed"] == 0 and program["device"]["count"] == 4
    # shrunk by the source's own hook to [8, 16] chunks, idle slots not
    # counted
    assert 0 < program["attempted"] < program["window_chunks"] * 8 * 16
    for what in POOL4_WHATS[1:]:
        assert not outs[what]["correct"], (what, outs[what]["checks"])
    # the coupled control is paced by its reference: a window four times
    # as long holds fewer chunks than the program's
    assert outs["control"]["window_chunks"] < program["window_chunks"]
