"""The trace reduction: the union of device intervals, and the gaps and
what names them."""
import numpy as np

from bench import trace_reduce as tr


def test_union_and_gaps():
    starts = np.array([10, 12, 30, 31, 50])
    ends = np.array([20, 15, 35, 40, 70])
    busy, gaps = tr.union_length(starts, ends, 0, 60)
    # [10, 20) + [30, 40) + [50, 60) inside [0, 60)
    assert busy == 30
    assert gaps == [(0, 10), (20, 30), (40, 50)]


def test_union_empty_window():
    busy, gaps = tr.union_length(np.array([]), np.array([]), 5, 9)
    assert busy == 0 and gaps == [(5, 9)]


def test_gaps_named_by_innermost_host_event():
    host = [("bench.window", 0, 100), ("bench.create", 0, 60),
            ("from_edge_list", 5, 15)]
    got = tr.name_gaps([(0, 10), (40, 50), (70, 90)], host, "bench.window")
    # longest first: 20 ns, then 10 ns gaps (ties keep their order)
    assert got == [("no host event", 20e-9), ("from_edge_list", 10e-9),
                   ("bench.create", 10e-9)]
