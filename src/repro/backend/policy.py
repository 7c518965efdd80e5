"""The :class:`ExecutionPolicy`: one dispatch decision for every hot op.

Before this subsystem, backend choice was frozen at import time
(``_ON_TPU``/``_INTERPRET`` module constants in ``kernels/ops.py``) and
lane decisions (dense gram vs CSR searchsorted vs Pallas probe) were
hard-coded at each call site. The policy centralizes all of it:

* **platform detection per call** — ``platform()`` queries
  ``jax.default_backend()`` every time, so ``JAX_PLATFORMS`` set after
  import (as the subprocess mesh tests do) is honored, and importing this
  module never initializes the jax backend;
* **a kernel registry** — each hot op (``bucket_probe``, ``simhash``,
  ``hamming``, ``triangle_count``, and the pure-jnp ``query`` path) maps
  to its available lanes: ``ref`` (pure-jnp oracle),
  ``pallas-interpret`` (kernel body emulated on host), and
  ``pallas-compiled`` (real accelerator dispatch);
* **calibrated shapes** — an :class:`~repro.backend.profile
  .AutotuneProfile` of block shapes and tile widths (default = the
  legacy constants);
* **a forced-lane override** — the ``REPRO_LANE`` environment variable
  (read per call, so tests and subprocesses can pin a lane) or an
  explicit ``forced_lane=`` (``EngineConfig(lane=...)`` / ``scan_serve
  --lane``). A forced lane clamps to each op's available lanes (ops with
  only a ``ref`` lane stay on it). ``pallas-interpret`` is a host lane:
  forcing it on a TPU raises instead of emulating the kernels there.

The **bit-identity contract** makes lane choice safe: every lane of every
hot op reproduces the ``ref`` lane bit-for-bit on unweighted σ (to ULP on
weighted), enforced by the lane-matrix oracle test in
``tests/test_backend.py`` — swapping lanes can never change an index
fingerprint.

Every decision is observable: ``note()`` bumps a
``backend.lane.<op>.<lane>`` counter on the policy's registry, and
``describe()`` returns the block ``LiveIndexService.status()`` exposes.
"""
from __future__ import annotations

import os
from typing import Optional

from repro.backend.profile import AutotuneProfile, DEFAULT_PROFILE

LANE_REF = "ref"
LANE_INTERPRET = "pallas-interpret"
LANE_COMPILED = "pallas-compiled"
LANES = (LANE_REF, LANE_INTERPRET, LANE_COMPILED)

ENV_LANE = "REPRO_LANE"

# the kernel registry: hot op → lanes that can answer it
OPS = {
    "bucket_probe": LANES,
    "simhash": LANES,
    "hamming": LANES,
    "triangle_count": LANES,
    "query": (LANE_REF,),       # (μ, ε) sweep path is pure jnp today
}


def _check_lane(lane: str) -> str:
    if lane not in LANES:
        raise ValueError(f"unknown lane {lane!r}; expected one of {LANES}")
    return lane


class ExecutionPolicy:
    """Per-call lane resolution + profile shapes + lane counters.

    ``registry`` is an optional :class:`repro.obs.MetricsRegistry`; when
    present every resolved dispatch counts under
    ``backend.lane.<op>.<lane>``.
    """

    def __init__(self, profile: Optional[AutotuneProfile] = None,
                 forced_lane: Optional[str] = None, registry=None) -> None:
        self.profile = profile if profile is not None else DEFAULT_PROFILE
        self._forced = _check_lane(forced_lane) if forced_lane else None
        self.registry = registry

    # -- per-call resolution (never cached) ---------------------------------
    def platform(self) -> str:
        """The jax backend *right now* — resolved per call, never frozen."""
        import jax
        return jax.default_backend()

    def forced_lane(self) -> Optional[str]:
        """The pinned lane, if any: ``REPRO_LANE`` env (read per call)
        beats the constructor/``EngineConfig`` override.

        Pinning ``pallas-interpret`` on a TPU raises: the interpreter would
        emulate every kernel on the host while the chip sits idle, a
        silent fallback no caller on the chip wants."""
        env = os.environ.get(ENV_LANE)
        forced = _check_lane(env) if env else self._forced
        if forced == LANE_INTERPRET and self.platform() == "tpu":
            raise RuntimeError(
                f"lane {LANE_INTERPRET!r} is forced on a TPU; it would run "
                "every kernel in the host interpreter. Unset REPRO_LANE / "
                "EngineConfig.lane, or force 'ref' or 'pallas-compiled'.")
        return forced

    def pallas_lane(self) -> str:
        """Which Pallas flavor this platform runs: compiled on TPU,
        interpret (host emulation of the same kernel body) elsewhere."""
        return LANE_COMPILED if self.platform() == "tpu" else LANE_INTERPRET

    def lane(self, op: str) -> str:
        """Routing-site decision: which lane answers ``op``.

        Forced lane wins (clamped to the op's registered lanes). Otherwise
        a TPU runs the compiled kernel and every other platform the jnp
        reference engine.
        """
        avail = OPS.get(op, (LANE_REF,))
        forced = self.forced_lane()
        if forced is not None:
            return forced if forced in avail else LANE_REF
        if self.platform() == "tpu" and LANE_COMPILED in avail:
            return LANE_COMPILED
        return LANE_REF

    def kernel_lane(self, op: str) -> str:
        """Entry-point decision for the explicit kernel wrappers in
        ``kernels/ops.py``: callers who reached a wrapper asked for the
        Pallas path, so the default is the platform's Pallas flavor; a
        forced lane (clamped to the op's lanes) still wins."""
        avail = OPS.get(op, (LANE_REF,))
        forced = self.forced_lane()
        if forced is not None:
            return forced if forced in avail else LANE_REF
        return self.pallas_lane() if LANE_INTERPRET in avail else LANE_REF

    @staticmethod
    def interpret(lane: str) -> bool:
        """The ``interpret=`` flag a Pallas call needs under ``lane``."""
        return lane != LANE_COMPILED

    # -- observability ------------------------------------------------------
    def note(self, op: str, lane: str, count: int = 1) -> None:
        """Record one (or ``count``) dispatch decisions."""
        if self.registry is not None and count:
            self.registry.inc(f"backend.lane.{op}.{lane}", count)

    def describe(self) -> dict:
        """The ``backend`` status block: platform, forced lane, the lane
        each op resolves to right now, and the active profile."""
        import dataclasses
        return {
            "platform": self.platform(),
            "forced_lane": self.forced_lane(),
            "lanes": {op: self.lane(op) for op in OPS},
            "profile": dataclasses.asdict(self.profile),
        }


_DEFAULT: Optional[ExecutionPolicy] = None


def default_policy() -> ExecutionPolicy:
    """The process-wide policy used when a call site is given none. Holds
    its own registry so ``backend.lane.*`` counters always land somewhere
    inspectable (``default_policy().registry.snapshot()``)."""
    global _DEFAULT
    if _DEFAULT is None:
        from repro.obs import MetricsRegistry
        _DEFAULT = ExecutionPolicy(registry=MetricsRegistry())
    return _DEFAULT


def set_default_policy(policy: Optional[ExecutionPolicy]) -> None:
    """Replace (or with ``None``, reset) the process-wide default policy."""
    global _DEFAULT
    _DEFAULT = policy
