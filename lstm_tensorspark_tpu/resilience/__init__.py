"""Resilience plane: shared exit-code contract + deterministic fault injection.

Two halves, deliberately dependency-light (no jax at import time — the
supervisor and shell tooling import from here without paying backend init):

- :mod:`.exit_codes` — the ONE table of process exit codes used by the
  training loop, the supervisor and the verification gates. Replaces the
  magic numbers that used to be scattered (and once collided).
- :mod:`.faults` — a seeded, deterministic fault-injection plane
  (``LSTM_TSP_FAULTS`` / ``--faults``) that provokes the failure modes the
  self-healing code claims to survive: process crash at step N, NaN/Inf
  gradient bursts, checkpoint truncation after write, data-batch
  exceptions, serve-engine exceptions mid-decode. Chaos tests
  (tests/test_chaos*.py, tools/chaos_smoke.py) arm it and assert the
  crash→restart→resume cycle completes the full step budget.
"""

from .exit_codes import (  # noqa: F401
    ANOMALY_RC,
    FAULT_CRASH_RC,
    LIVENESS_RC,
    POISON_RC,
    REGRESSION_RC,
    RETRYABLE_RCS,
    USAGE_RC,
)
from .faults import (  # noqa: F401
    FaultPlane,
    InjectedFault,
    active,
    arm,
    arm_from_flag_or_env,
    disarm,
)
