"""ctypes binding for the native C++ checker (native/raft_checker.cc).

Builds the shared object on demand with g++ -O3 (no pip deps) and
exposes ``check(cfg, ...)`` with the same counting semantics as the
Python oracle and the TPU engine — the framework's CPU runtime and the
machine-measured stand-in for the reference's "TLC -workers N" baseline
(BASELINE.md).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from dataclasses import dataclass, field
from pathlib import Path
from typing import List, Optional

import numpy as np

from ..config import (NEXT_ASYNC, NEXT_ASYNC_CRASH, NEXT_DYNAMIC,
                      NEXT_FULL, ModelConfig)
from ..models.explore import symmetry_perms
from ..ops.layout import Layout

# keep in sync with raft_checker.cc ConBit / InvBit
CONSTRAINT_ORDER = (
    "BoundedInFlightMessages", "BoundedRequestVote", "BoundedLogSize",
    "BoundedRestarts", "BoundedTimeouts", "BoundedTerms",
    "BoundedClientRequests", "BoundedTriedMembershipChanges",
    "BoundedMembershipChanges", "ElectionsUncontested",
    "CleanStartUntilFirstRequest", "CleanStartUntilTwoLeaders",
    "CleanFirstLeaderElection",
)
INVARIANT_ORDER = (
    "LeaderVotesQuorum", "CandidateTermNotInLog", "ElectionSafety",
    "LogMatching", "VotesGrantedInv", "VotesGrantedInv_false",
    "QuorumLogInv", "MoreUpToDateCorrect", "LeaderCompleteness",
    "LeaderCompleteness_false", "OneAtATimeMembershipChangeOK",
)
_FAMILY = {NEXT_ASYNC: 0, NEXT_ASYNC_CRASH: 1, NEXT_FULL: 2,
           NEXT_DYNAMIC: 3}

# keep in sync with raft_checker.cc MAX_LEVELS
_MAX_LEVELS = 256

_lock = threading.Lock()
_lib = None


def _build() -> Path:
    """Compile the checker into a cache path keyed on a content hash of
    the source (never committed; a stale or foreign-built object can
    never be picked up).  -march=native is attempted first for speed and
    dropped automatically on toolchains/microarchitectures that reject
    it."""
    import hashlib
    src = Path(__file__).parent / "raft_checker.cc"
    digest = hashlib.sha256(src.read_bytes()).hexdigest()[:16]
    so = Path(__file__).parent / f"raft_checker.{digest}.so"
    if so.exists():
        return so
    # build into a process-unique temp and rename atomically so
    # concurrent builders (e.g. parallel pytest workers) never unlink or
    # half-overwrite an object another process is about to CDLL
    tmp = so.with_suffix(f".tmp{os.getpid()}")
    base = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17",
            "-o", str(tmp), str(src), "-lpthread"]
    try:
        subprocess.run(base[:2] + ["-march=native"] + base[2:],
                       check=True, capture_output=True, text=True)
    except subprocess.CalledProcessError:
        subprocess.run(base, check=True, capture_output=True, text=True)
    os.replace(tmp, so)
    for stale in so.parent.glob("raft_checker*.so"):
        if stale != so:
            stale.unlink(missing_ok=True)
    return so


def load():
    """Build (once) and load the shared object."""
    global _lib
    with _lock:
        if _lib is None:
            lib = ctypes.CDLL(str(_build()))
            lib.raft_check.restype = ctypes.c_int64
            lib.raft_check.argtypes = [
                ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_int64)]
            _lib = lib
    return _lib


@dataclass
class NativeResult:
    distinct_states: int
    generated_states: int
    depth: int
    violations: List[str]
    overflow_faults: int
    seconds: float = 0.0
    # post-constraint frontier size after each level (the oracle's
    # level_sizes); levels past _MAX_LEVELS are not recorded
    level_sizes: List[int] = field(default_factory=list)

    @property
    def states_per_sec(self):
        return self.distinct_states / max(self.seconds, 1e-9)


def _pack_cfg(cfg: ModelConfig, threads: int, max_depth: int,
              max_states: int, stop_on_violation: bool) -> np.ndarray:
    lay = Layout(cfg)
    for nm in cfg.invariants:
        if nm not in INVARIANT_ORDER:
            raise ValueError(
                f"invariant {nm!r} is python-side only (scenario "
                f"properties run on the oracle/TPU engines)")
    for nm in cfg.constraints:
        if nm not in CONSTRAINT_ORDER:
            raise ValueError(f"constraint {nm!r} unsupported natively")
    con_mask = sum(1 << CONSTRAINT_ORDER.index(nm)
                   for nm in cfg.constraints)
    inv_mask = 0
    for nm in cfg.invariants:
        if cfg.apalache_variant and nm in ("VotesGrantedInv",
                                           "LeaderCompleteness"):
            nm = nm + "_false"
        inv_mask |= 1 << INVARIANT_ORDER.index(nm)
    perms = (symmetry_perms(cfg) if cfg.symmetry
             else [tuple(range(cfg.n_servers))])
    b = cfg.bounds
    head = [
        cfg.n_servers, len(cfg.values),
        *list(cfg.values) + [0] * (8 - len(cfg.values)),
        cfg.init_mask, cfg.num_rounds, _FAMILY[cfg.next_family],
        b.max_log_length, cfg.log_capacity, cfg.bag_capacity,
        b.max_restarts, b.max_timeouts, b.max_terms,
        b.max_client_requests, b.max_membership_changes,
        b.max_tried_membership_changes, cfg.max_inflight, b.max_trace,
        con_mask, inv_mask, int(cfg.symmetry), threads,
        max_depth, max_states, int(stop_on_violation), lay.value_bits,
        len(perms),
    ]
    flat = [x for p in perms for x in p]
    return np.array(head + flat, dtype=np.int64)


def check(cfg: ModelConfig, threads: int = os.cpu_count() or 8,
          max_depth: int = 2 ** 60, max_states: int = 2 ** 60,
          stop_on_violation: bool = False) -> NativeResult:
    """``max_states`` is a level-granular budget, matching the TPU
    engine's semantics: expansion stops at the first level boundary at
    or past the cap, so the returned count may exceed it by up to one
    level's worth of states."""
    import time
    lib = load()
    arr = _pack_cfg(cfg, threads, max_depth, max_states,
                    stop_on_violation)
    out = np.zeros(8 + _MAX_LEVELS, dtype=np.int64)
    t0 = time.time()
    rc = lib.raft_check(
        arr.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
    secs = time.time() - t0
    if rc != 0:
        raise RuntimeError(
            f"native checker rejected the model dims (rc={rc}): "
            f"S<=6, K<=72, Lcap<=16, Lmax<=8, |values|<=8 required")
    violations = [nm for k, nm in enumerate(INVARIANT_ORDER)
                  if out[3] >> k & 1]
    return NativeResult(
        distinct_states=int(out[0]), generated_states=int(out[1]),
        depth=int(out[2]), violations=violations,
        overflow_faults=int(out[4]), seconds=secs,
        level_sizes=[int(x) for x in
                     out[8:8 + min(int(out[2]), _MAX_LEVELS)]])
