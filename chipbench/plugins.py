"""Find a cell's parts by name: ``chipbench/<kind>/<name>.py``, loaded by path.

The kinds are ``generators`` (traffic sources, named by a mix's
``"generator"``), ``entries`` (the program's entry a configuration drives,
its ``"entry"``), ``reference`` (its plain reference, its ``"reference"``)
and ``metrics`` (one reader per metric of ``BENCHMARK.json``).  A later
cell, mix or metric adds a file here; nothing that exists is edited.

A part needs its main function (a source's ``make``, an entry's
``drive``, a reference's ``replay_chunks`` or ``replay_lanes``, a reader's
``read``), and a source's module its ``shrink(config, mix) -> (config,
mix)``: the cell at a size a CPU test holds (``selfcheck.shrink``).  The
hooks below are optional, and the harness behaves as without them where a
part leaves them out:

* a source's ``live(lo, hi)``: the requests per lane it handed out in
  chunks ``[lo, hi)``, where its lanes idle (``generator.py``);
* an entry's ``drive(config, chunks, engine, devices=)``: declaring
  ``devices`` hands it the chips the cell holds (``run.run_cell``);
* an entry module's ``faults(config) -> {name: engine}``: faults of its
  own, added beside the generic ones, whose names they may not take
  (``selfcheck.faults``);
* a reference's ``replay_lanes(source, lanes, n_chunks, config,
  key_bits)`` in place of ``replay_chunks``: one replay of lanes that share
  state, for the check (``check.py``) and the control
  (``selfcheck.ControlEngine``) alike.

A module already in ``sys.modules`` under ``chipbench.<kind>.<name>`` is
taken as it is (the tests' stand-ins).
"""
from __future__ import annotations

import importlib.util
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def load(kind: str, name: str):
    mod_name = f"chipbench.{kind}.{name}"
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod
