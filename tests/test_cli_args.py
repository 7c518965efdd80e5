"""``scan_serve`` argument handling that runs before any device work: the
``--mus``/``--epss`` value parser, the refusals of flag combinations that
cannot work together, and the ``--lane`` export."""
import os

import pytest

from repro.launch.scan_serve import parse_values

# the lane this process was started with, before any test ran
_LANE_AT_START = os.environ.get("REPRO_LANE")


def test_parse_values_comma_list():
    assert parse_values("2,4,8", int) == [2, 4, 8]


def test_parse_values_range_is_a_linspace():
    assert parse_values("0.1:0.9:5", float) == pytest.approx(
        [0.1, 0.3, 0.5, 0.7, 0.9])


def test_parse_values_two_field_range_raises():
    with pytest.raises(ValueError):
        parse_values("0.1:0.9", float)


@pytest.mark.parametrize("argv,message", [
    (("serve", "--load", "{d}", "--indexes", 2),
     "cannot be combined with --load"),
    (("serve", "--approx", "simhash:64", "--load", "{d}"),
     "--approx builds a fresh LSH-sketched index"),
    (("serve", "--approx", "minhash:64"), "pass --measure jaccard"),
    (("update", "--save", "{d}"), "use --root DIR instead of --save"),
    (("fleet", "--load", "{d}"), "the fleet serves its own catalog root"),
    (("fleet", "--save", "{d}"), "the fleet serves its own catalog root"),
], ids=["serve-load-indexes", "serve-approx-load", "serve-approx-measure",
        "update-save", "fleet-load", "fleet-save"])
def test_refused_flag_combinations(scan_cli, tmp_path, argv, message):
    argv = [str(a).format(d=tmp_path / "idx") for a in argv]
    with pytest.raises(SystemExit, match=message):
        scan_cli(*argv)
    # refused before anything was built or written
    assert not (tmp_path / "idx").exists()


def test_lane_flag_exports_repro_lane(scan_cli, monkeypatch, tmp_path):
    """``--lane`` reaches every dispatch site through ``REPRO_LANE``, set
    before the subcommand runs (here one that is then refused). The
    fixture's teardown takes the export back, so later tests in this
    process resolve their lanes as they would have."""
    with pytest.raises(SystemExit):
        scan_cli("update", "--save", tmp_path / "idx", "--lane", "ref")
    assert os.environ["REPRO_LANE"] == "ref"
    monkeypatch.undo()                      # what the teardown runs
    assert os.environ.get("REPRO_LANE") == _LANE_AT_START
