"""The control (the reference one precision below the configuration's,
in the program's place) fails the comparison of every cell."""
import pytest

from bench import control

CELLS = ["livejournal-s18.sweep", "graph500-s18.build"]


@pytest.mark.parametrize("workload", CELLS)
def test_control_fails_a_limit(workload):
    got = control.readings(workload, seed=2**31 + 3, rehearse=True)
    assert got["graph_bad"] == 0
    assert got["sigma_bad"] > 0
    assert any(v > 0 for v in got.values())
