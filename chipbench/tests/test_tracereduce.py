"""The trace reduction's interval arithmetic, and its counts per chip, on
hand-made cases and on the recorded trace."""
import os

import numpy as np

from chipbench import tracereduce


def test_union_merges_overlaps_and_keeps_gaps():
    iv = np.array([[5, 7], [0, 2], [1, 3], [6, 9], [9, 10]], float)
    np.testing.assert_array_equal(tracereduce._union(iv),
                                  [[0, 3], [5, 10]])


def test_inside_finds_the_span_of_each_point():
    spans = np.array([[0, 2], [5, 7]], float)
    got = tracereduce._inside(np.array([-1, 1, 3, 5, 7, 6.5]), spans)
    assert got.tolist() == [False, True, False, True, False, True]



def _device(plane, ops):
    ops = np.asarray(ops, float).reshape(-1, 2)
    return {"plane": plane, "names": [f"op{i}" for i in range(len(ops))],
            "ops": ops, "modules": np.zeros((0, 2))}


def test_ops_and_busy_are_per_chip():
    host = {"traced_window": np.array([[0.0, 100.0]])}
    devices = [
        _device("/device:TPU:0", [[0, 10], [20, 30], [40, 50]]),
        _device("/device:TPU:1", [[0, 5], [10, 15], [20, 25], [30, 35],
                                  [90, 120]]),
        # ran nothing inside the window: not a chip of the step
        _device("/device:TPU:2", [[200, 210]]),
    ]
    got = tracereduce.reduce_planes(host, devices)
    assert got["devices"] == 2
    assert got["ops"] == (3 + 5) / 2
    assert got["busy_s"] == (30 + 30) / 2 * 1e-9


def test_two_chips_running_the_recorded_ops_read_one_chips_count():
    """The recorded one-chip trace, its device twice: per chip, the same
    ops and busy time as the one chip, not twice them."""
    from chipbench.tests import chip_trace
    path = os.path.join(chip_trace.DATA, "replay_stream.xplane.pb")
    host, devices = tracereduce.load(path)
    one = tracereduce.reduce_planes(host, devices)
    two = tracereduce.reduce_planes(host, devices + devices)
    assert (one["devices"], two["devices"]) == (1, 2)
    assert two["ops"] == one["ops"] > 0
    assert two["busy_s"] == one["busy_s"]
