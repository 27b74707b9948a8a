"""Each configuration and traffic mix under ``chipbench/``, at a size a
test holds, on the CPU with the look for a chip skipped: the program comes
out correct; the control and every planted fault come out not correct."""
import os

import pytest

from chipbench import run, selfcheck

CELLS = [("dac_paper_s", "skew1024"), ("dac_paper_s", "file1")]


def _run(cell, engine, seed=2**31 + 11):
    config = run.load_json(os.path.join(run.HERE, "configs", cell[0] + ".json"))
    mix = run.load_json(os.path.join(run.HERE, "traffic", cell[1] + ".json"))
    e2e = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))["end_to_end"]
    config, mix = selfcheck.shrink(config, mix)
    seconds = 1.0
    if engine == "control":
        # the control is the plain reference, slow in Python: it needs a
        # longer window before keys beyond 16 bits come up in the stream
        engine, seconds = selfcheck.ControlEngine(config), 4.0
    elif engine is not None:
        engine = selfcheck.fault_engines()[engine]
    return run.run_cell(config, mix, seed, seconds, False, e2e, devices=[],
                        engine=engine)


@pytest.mark.parametrize("cell", CELLS)
def test_program_is_correct(cell):
    out = _run(cell, None)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"requests_per_s", "chunk_p95_ms",
                                   "setup_s"}


@pytest.mark.parametrize("what", ["control", "frozen", "half_batch",
                                  "altered"])
@pytest.mark.parametrize("cell", CELLS)
def test_control_and_faults_are_not_correct(cell, what):
    out = _run(cell, what)
    assert not out["correct"], out["checks"]
