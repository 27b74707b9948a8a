"""A trace of ``Engine.replay_stream`` recorded on a TPU v5e
(``data/replay_stream.xplane.pb``, made by ``chip_trace.py fixture``):
``tracereduce.reduce_trace`` gives the numbers recorded with it, and every
``engine.*`` span lies inside ``traced_window``."""
import json
import os

import pytest

from chipbench import tracereduce
from chipbench.tests import chip_trace

PATH = os.path.join(chip_trace.DATA, "replay_stream.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(chip_trace.DATA, "replay_stream.json")) as f:
        return json.load(f)


def test_reduce_trace_gives_the_recorded_numbers(recorded):
    got = tracereduce.reduce_trace(PATH)
    want = recorded["reduce_trace"]
    assert set(got) == set(want)
    for key in ("devices", "ops"):
        assert got[key] == want[key], key
    for key in ("window_s", "busy_s", "feed_s"):
        assert got[key] == pytest.approx(want[key], rel=1e-12), key
    for key in ("device_ops", "idle_gaps"):
        assert [n for n, _ in got[key]] == [n for n, _ in want[key]], key
        assert [t for _, t in got[key]] == pytest.approx(
            [t for _, t in want[key]], rel=1e-12), key


def test_engine_spans_lie_inside_the_traced_window(recorded):
    from jax.profiler import ProfileData
    spans = chip_trace.engine_spans(PATH)
    # engine.init runs once, in chunk 0, before the window's profiler starts
    assert set(spans) == {f"engine.{p}" for p in
                          ("source", "put", "dispatch", "wait", "fetch",
                           "sum")}
    assert chip_trace.spans_inside_window(PATH)
    for plane in ProfileData.from_file(PATH).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("engine."):
                    assert "chunk" in dict(ev.stats), ev.name
    split = chip_trace.engine_split(PATH)
    want = recorded["engine_split"]
    assert split["by_span_s"] == pytest.approx(want["by_span_s"], rel=1e-12)
    for key in ("idle_between_chunks_s", "replay_stream_idle_s",
                "replay_stream_idle_in_engine_spans"):
        assert split[key] == pytest.approx(want[key], rel=1e-12), key
    share = split["replay_stream_idle_in_engine_spans"]
    print(f"engine.* spans cover {100 * share:.1f}% of the replay_stream "
          f"idle gaps ({split['replay_stream_idle_s'] * 1e3:.3f} ms)")
    assert 0 < share <= 1
