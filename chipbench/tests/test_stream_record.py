"""A harness run leaves the engine's own record of the call
(``repro.core.tracing.last_stream()``) with a row for every window chunk:
at a small size on the CPU."""
import os

import jax
import numpy as np
import pytest

from chipbench import run, selfcheck
from repro.core import tracing


@pytest.fixture(scope="module")
def harness_run():
    config = run.load_json(os.path.join(run.HERE, "configs",
                                        "dac_paper_s.json"))
    mix = run.load_json(os.path.join(run.HERE, "traffic", "skew1024.json"))
    config, mix = selfcheck.shrink(config, mix)
    jax.clear_caches()         # so that the warm-up compiles, as in a process
    out = run.run_cell(config, mix, 2**31 + 3, 1.0, False, [], devices=[])
    return out, tracing.last_stream()


def test_the_record_covers_the_window(harness_run):
    out, rec = harness_run
    assert out["correct"], out["checks"]
    n = out["window_chunks"]
    assert n > 0 and rec is not None
    assert rec.chunks == len(rec.rows["chunk"]) >= n
    window = {c: v[-n:] for c, v in rec.rows.items()}
    # the engine's own loop: put + dispatch + fetch + sum, each timed
    for c in ("put_s", "dispatch_s", "fetch_s", "sum_s"):
        assert (window[c] > 0).all(), c
    # every program the window runs was compiled in the warm-up
    assert not window["compiles"].any()


def test_compile_time_is_counted_in_the_warm_up(harness_run):
    _, rec = harness_run
    assert rec.rows["compiles"][0] > 0
    assert rec.totals["compile_s"] == pytest.approx(
        float(np.sum(rec.rows["compile_s"])))
    assert rec.totals["compile_s"] > 0
