"""Backend execution layer: one dispatch policy for every kernel.

* :mod:`repro.backend.policy` — :class:`ExecutionPolicy`: per-call
  platform detection, the op → lane registry (``ref`` /
  ``pallas-interpret`` / ``pallas-compiled``), forced-lane override
  (``REPRO_LANE`` env, ``EngineConfig(lane=...)``, ``scan_serve --lane``),
  ``backend.lane.*`` counters.
* :mod:`repro.backend.profile` — :class:`AutotuneProfile` calibrated
  shapes (default = the legacy constants) + the one-shot
  :func:`autotune` microbenchmark; profiles persist as a manifest leaf in
  ``IndexStore`` snapshots.
* :mod:`repro.backend.padding` — the shared padding / pow2 shape helpers.
"""
from repro.backend.padding import (  # noqa: F401
    np_log2, np_pow2ceil, pad1, pad_to, pow2_bucket, pow2ceil,
)
from repro.backend.policy import (  # noqa: F401
    ENV_LANE, LANE_COMPILED, LANE_INTERPRET, LANE_REF, LANES, OPS,
    ExecutionPolicy, default_policy, set_default_policy,
)
from repro.backend.profile import (  # noqa: F401
    DEFAULT_PROFILE, PROFILE_VERSION, AutotuneProfile, autotune,
)
