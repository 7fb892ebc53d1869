"""Fault-tolerance layer (round 12): deterministic chaos injection,
checksummed last-K checkpoint chains, shape-portable resume images,
and the supervised retry/backoff runner.

- ``chaos`` — a seeded, deterministic fault schedule (``--chaos``)
  that injects failures at named engine sites (dispatch, checkpoint
  publish, archive writes, host-table sweeps, batch waves) so every
  recovery path is testable on CPU in tier-1.
- ``ckpt_chain`` — sha256-sidecar integrity for every checkpoint plus
  last-K rotation with atomic publish; a torn/corrupt head reads as
  "fall back to the previous valid checkpoint" with a named warning.
- ``portable`` — engine-agnostic resume images extracted from a
  classic, pjit or spill checkpoint: the visited key set + the
  frontier rows in gid order, re-partitioned on load so a pjit-mesh
  checkpoint resumes on a different device count or on the spill
  engine.
- ``supervisor`` — catch → backend-reinit → resume-from-latest-valid
  with bounded exponential backoff + jitter; every attempt stamped
  into the run ledger and heartbeat.
"""

from .chaos import (ChaosSchedule, ChaosSpecError, InjectedFault,
                    chaos_fire, chaos_point, get_schedule, install,
                    uninstall)
from .ckpt_chain import ChainWarning

__all__ = [
    "ChaosSchedule", "ChaosSpecError", "InjectedFault", "chaos_fire",
    "chaos_point", "get_schedule", "install", "uninstall",
    "ChainWarning",
]
