"""Every cell of ``BENCHMARK.json``, at a size a test holds
(``selfcheck.shrink``), on the CPU with the look for a chip skipped: the
program comes out correct; the control and every fault of the cell
(``selfcheck.faults``) come out not correct.  A cell of more than one chip
runs in a subprocess on as many host devices."""
import importlib
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from chipbench import run, selfcheck

BENCH = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in BENCH["workloads"]]
SEED = 2**31 + 11


def outcomes(config: dict, mix: dict, chips: int, whats: list,
             plug: str | None = None, seed: int = SEED) -> dict:
    """``{what: result line}``, one run each of ``whats``: ``"program"``,
    ``"control"`` or a fault's name.  On more than one chip, or with
    stand-ins to plug in, the runs go to a subprocess with ``chips`` host
    devices, which first calls ``install()`` of the module ``plug``."""
    if chips == 1 and plug is None:
        return here(config, mix, chips, whats, seed)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([os.path.join(run.ROOT, "src"),
                                           run.ROOT]))
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count={chips}")
    code = ("import json, sys; from chipbench.tests import test_selfcheck "
            "as t; print(json.dumps(t.child(*json.loads(sys.argv[1]))))")
    args = json.dumps([config, mix, chips, whats, plug, seed])
    proc = subprocess.run([sys.executable, "-c", code, args], cwd=run.ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=900)
    assert proc.returncode == 0, proc.stderr[-6000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def child(config, mix, chips, whats, plug, seed) -> dict:
    if plug is not None:
        importlib.import_module(plug).install()
    return here(config, mix, chips, whats, seed)


def here(config, mix, chips, whats, seed) -> dict:
    import jax
    devices = jax.devices()[:chips]
    assert len(devices) == chips, devices
    config, mix = selfcheck.shrink(config, mix)
    engines = {**selfcheck.engines(config, "program"),
               **selfcheck.engines(config, "control"),
               **selfcheck.faults(config)}
    outs = {}
    for what in whats:
        # the control is the plain reference, slow in Python: it needs a
        # longer window before keys beyond 16 bits come up in the stream
        seconds = 4.0 if what == "control" else 1.0
        outs[what] = run.run_cell(config, mix, seed, seconds, False,
                                  BENCH["end_to_end"], devices=devices,
                                  engine=engines[what])
    return outs


def cell_outcomes(name: str, whats: list) -> dict:
    cell, config, mix, _, _ = run.load_cell(name)
    return outcomes(config, mix, cell["chips"], whats)


def control_and_faults() -> list:
    cases = []
    for name in CELLS:
        _, config, _, _, _ = run.load_cell(name)
        cases += [(name, w) for w in ["control", *selfcheck.faults(config)]]
    return cases


@pytest.mark.parametrize("cell", CELLS)
def test_program_is_correct(cell):
    out = cell_outcomes(cell, ["program"])["program"]
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"requests_per_s", "chunk_p95_ms",
                                   "setup_s"}


@pytest.mark.parametrize("cell,what", control_and_faults())
def test_control_and_faults_are_not_correct(cell, what):
    out = cell_outcomes(cell, [what])[what]
    assert not out["correct"], out["checks"]


def entry_module(monkeypatch, own: dict | None):
    mod = types.ModuleType("chipbench.entries.hooked")
    if own is not None:
        mod.faults = lambda config: own
    monkeypatch.setitem(sys.modules, "chipbench.entries.hooked", mod)
    return {"entry": "hooked"}


@pytest.mark.parametrize("own", [None, {}, {"no_exchange": "x"},
                                 {"no_exchange": "x", "stale": "y"}])
def test_an_entry_adds_faults_beside_the_generic_ones(monkeypatch, own):
    got = selfcheck.faults(entry_module(monkeypatch, own))
    generic = selfcheck.fault_engines()
    assert list(got)[:len(generic)] == list(generic)
    assert set(got) == set(generic) | set(own or {})
    for name, engine in got.items():
        if own and name in own:
            assert engine == own[name]
        else:
            assert type(engine) is type(generic[name])


def test_an_entry_cannot_replace_a_generic_fault(monkeypatch):
    config = entry_module(monkeypatch, {"frozen": "x", "no_exchange": "y"})
    with pytest.raises(ValueError, match="frozen"):
        selfcheck.faults(config)


class Endless:
    """A source that hands out ``[4, 8]`` chunks of keys 0-31 for ever."""

    lanes, T = 4, 8

    def next(self):
        return np.arange(32, dtype=np.int32).reshape(4, 8)


def test_the_coupled_control_is_paced_by_its_reference(monkeypatch):
    """With ``replay_lanes``, the control replays every lane over all the
    chunks fed after each one, so a reference that takes 10 ms a chunk
    replayed holds the window to the chunks it replays in it (12, warm-up
    included); the clock's own readings alone let 36 through."""
    from chipbench import window
    from chipbench.tests.test_pool_hooks import Clock
    clock = Clock()
    monkeypatch.setattr(window, "time", clock)
    ref = types.ModuleType("chipbench.reference.paced")
    calls = []

    def replay_lanes(source, lanes, n_chunks, config, key_bits=32):
        calls.append((n_chunks, key_bits))
        clock.t += 0.01 * n_chunks
        live = source.live(0, n_chunks)
        return {lane: {"requests": live[lane], "hits": 0,
                       "bytes_total": live[lane],
                       "bytes_missed": live[lane]} for lane in lanes}

    ref.replay_lanes = replay_lanes
    monkeypatch.setitem(sys.modules, "chipbench.reference.paced", ref)
    win = window.Window(Endless(), warmup=2, seconds=1.0)
    m = selfcheck.ControlEngine({"reference": "paced"}).replay_stream(
        "dac", win, 8).metrics
    assert calls == [(n, 16) for n in range(1, win.fed + 1)]
    assert 2 < win.fed < 16
    assert list(m.requests) == [8 * win.fed] * 4


class Recorder:
    """Stands in for ``repro.core.Engine``: records each call's keywords
    and returns per-lane totals of the keys it was fed."""

    calls: list = []

    def replay_stream(self, policy, chunks, K, **kw):
        keys = np.concatenate([np.atleast_2d(c) for c in chunks], axis=1)
        Recorder.calls.append(kw)
        n = np.count_nonzero(keys >= 0, axis=1)
        return selfcheck.result({"requests": n, "hits": 0 * n,
                                 "bytes_total": n, "bytes_missed": n})


@pytest.mark.parametrize("name", ["frozen", "half_batch", "altered",
                                  "control"])
def test_stand_ins_take_an_entrys_keywords(monkeypatch, name):
    import repro.core
    monkeypatch.setattr(repro.core, "Engine", Recorder)
    monkeypatch.setattr(Recorder, "calls", [])
    config = run.load_json(os.path.join(run.HERE, "configs",
                                        "dac_paper_s.json"))
    engine = (selfcheck.ControlEngine(config) if name == "control"
              else selfcheck.fault_engines()[name])
    chunks = iter([np.arange(32, dtype=np.int32).reshape(4, 8)] * 2)
    m = engine.replay_stream("dac", chunks, 8, mesh="m", rebalance=16).metrics
    assert list(m.requests) == [16] * 4
    want = [] if name == "control" else [{"mesh": "m", "rebalance": 16}]
    assert Recorder.calls == want
