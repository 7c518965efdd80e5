import os

# Tests see the real (single) CPU device; multi-device cases force host
# devices in subprocesses of their own.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pytest

try:
    from hypothesis import HealthCheck, settings

    # seed-pinned fast-lane profile: derandomize makes every run replay
    # the same examples, so tier-1/CI can't flake on a rare draw; the
    # "thorough" profile re-enables exploration for local soak runs
    # (HYPOTHESIS_PROFILE=thorough pytest ...).
    settings.register_profile(
        "fast", derandomize=True, deadline=None,
        suppress_health_check=[HealthCheck.too_slow])
    settings.register_profile("thorough", deadline=None, max_examples=100)
    settings.load_profile(os.environ.get("HYPOTHESIS_PROFILE", "fast"))
except ImportError:                                    # pragma: no cover
    pass


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def scan_cli(monkeypatch, tmp_path, capsys):
    """Drive the ``scan_serve`` CLI in-process: ``run(*argv)`` → stdout.

    ``JAX_COMPILATION_CACHE_DIR`` is set, so ``enable_compile_cache``
    leaves this process's JAX config as it is; ``REPRO_LANE`` (which
    ``--lane`` exports) and the temp directory the subcommands fall back
    to are restored after the test. ``REPRO_LANE`` is set to the empty
    string, which the backend reads as unset, rather than deleted: a
    delete of an absent variable records nothing to undo, so a lane the
    CLI exported would outlive the test."""
    import tempfile

    from repro.launch import scan_serve

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))
    monkeypatch.setenv("REPRO_LANE", "")
    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))

    def run(*argv):
        capsys.readouterr()
        scan_serve.main([str(a) for a in argv])
        return capsys.readouterr().out

    return run
