"""The trace reduction's interval arithmetic on hand-made cases."""
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

