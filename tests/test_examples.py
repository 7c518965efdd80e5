"""The library examples run to their end at their own sizes."""
import os
import runpy
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_quickstart_runs(capsys):
    runpy.run_path(os.path.join(_REPO, "examples", "quickstart.py"),
                   run_name="__main__")
    out = capsys.readouterr().out
    assert "figure-1 labels :" in out
    assert "LSH(simhash,k=256) vs exact at best params: ARI=" in out


def test_scan_service_runs():
    """In a subprocess: the example forces 8 host devices before JAX
    starts, which this process cannot do any more."""
    r = subprocess.run(
        [sys.executable, os.path.join("examples", "scan_service.py")],
        capture_output=True, text=True, cwd=_REPO, timeout=600,
        env={**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-3000:]
    for line in ("consistency with direct query: OK",
                 "sharded sweep over 8 devices: bit-exact match = True",
                 "incremental == rebuild (bit-identical): OK"):
        assert line in r.stdout
    assert r.stdout.rstrip().endswith("matches: OK")
