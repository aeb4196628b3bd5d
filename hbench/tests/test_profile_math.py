"""Interval arithmetic of the trace reduction, on intervals worked out by
hand."""
import numpy as np

from hbench import profile


def test_union_and_gaps_by_hand():
    # [0,2) [1,3) [5,6) [5.5,5.8) [7,9): covered 3 + 1 + 2 = 6.
    s = np.array([5.0, 0.0, 1.0, 5.5, 7.0])
    e = np.array([6.0, 2.0, 3.0, 5.8, 9.0])
    assert profile.union_length(s, e) == 6.0
    assert profile.gaps(s, e, -1.0, 10.0) == [(-1.0, 0.0), (3.0, 5.0),
                                              (6.0, 7.0), (9.0, 10.0)]


def test_reduce_by_hand():
    # One device; window [10, 20); ops cover [9, 12) (clipped to [10, 12)),
    # [13, 14) and [14, 15): busy 4 s, idle share 0.6. The host is in
    # engine.run over [11, 14) and in window.block over [14, 20).
    ops = {0: (["fusion", "sort", "fusion"], np.array([9.0, 13.0, 14.0]),
               np.array([12.0, 14.0, 15.0]))}
    spans = [("window", 10.0, 20.0), ("engine.run", 11.0, 14.0),
             ("window.block", 14.0, 20.0)]
    r = profile.reduce(ops, spans)
    assert r.window_s == 10.0
    assert r.busy_s == [4.0]
    assert r.idle_share == [0.6]
    assert r.top_ops == [["fusion", 3.0], ["sort", 1.0]]
    assert r.idle_gaps == [["window.block", 5.0], ["engine.run", 1.0]]
    assert r.spans == {"engine.run": [3.0], "window.block": [6.0]}
