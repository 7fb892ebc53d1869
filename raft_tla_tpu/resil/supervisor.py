"""Supervised retry/backoff runner: catch → backend reinit → resume
from the latest valid checkpoint, with bounded exponential backoff.

The drive loop of a long run on a preemptible remote TPU dies to
transient causes (lost connection, device OOM race, host I/O blips) far
more often than to engine bugs — rounds 4-5 lost multi-hour runs
exactly that way.  ``supervised_check`` wraps any engine family's
``check()``:

- retryable failures (``InjectedFault``, ``RuntimeError`` — the XLA
  runtime's error class — and ``OSError``) trigger a bounded
  exponential backoff with deterministic jitter, a fresh engine from
  ``make_engine()`` (the backend-reinit hook: jit caches cleared, new
  executables, new device buffers), and a resume from the newest VALID
  member of the checkpoint chain (``resil.ckpt_chain``) — falling back
  to the original resume source, or a fresh start, when no checkpoint
  was written yet;
- non-retryable failures (``CheckpointError`` and other
  ``ValueError``s, assertion failures) propagate immediately — they
  mean misconfiguration, not weather;
- every attempt is stamped into the run ledger (``kind="retry"``) and
  the heartbeat (``status="backoff"``), so ``tools/watch.py`` shows a
  retrying run instead of a silent gap.

Because every engine resumes bit-exact from level-boundary
checkpoints, a supervised run's final counts are identical to an
unfaulted run — the chaos differentials in tests/test_resil.py pin
exactly that with faults injected at every level boundary.
"""

from __future__ import annotations

import time
from typing import Callable, Optional

from .chaos import InjectedFault
from .ckpt_chain import latest_valid

#: failures the supervisor treats as transient weather
RETRYABLE = (InjectedFault, RuntimeError, OSError)


class RetryExhausted(RuntimeError):
    """The supervised run failed on its final permitted attempt."""

    def __init__(self, attempts: int, last: BaseException):
        super().__init__(
            f"supervised run failed after {attempts} attempt(s); "
            f"last error: {last}")
        self.attempts = attempts
        self.last = last


def _jitter(attempt: int) -> float:
    """Deterministic jitter in [0, 1): decorrelates fleet retries
    without breaking replayability (no wall-clock entropy)."""
    return ((attempt + 1) * 2654435761 % (1 << 20)) / float(1 << 20)


def backoff_delay(attempt: int, backoff: float, backoff_max: float,
                  jitter_frac: float = 0.25) -> float:
    """Bounded exponential backoff + deterministic jitter for the
    k-th retry (0-based)."""
    base = min(backoff * (2.0 ** attempt), backoff_max)
    return base * (1.0 + jitter_frac * _jitter(attempt))


def _reinit_backend():
    """Best-effort backend reinit between attempts: drop every traced
    executable and live compilation cache so the fresh engine rebuilds
    them (on a remote runtime this is where a reconnect happens; the
    persistent on-disk compile cache keeps the rebuild cheap)."""
    try:
        import jax
        jax.clear_caches()
    except Exception:
        pass


def supervised_check(make_engine: Callable[[], object],
                     retries: int = 0,
                     backoff: float = 1.0,
                     backoff_max: float = 60.0,
                     obs=None,
                     checkpoint_path: Optional[str] = None,
                     resume_from: Optional[str] = None,
                     resume_image=None,
                     sleep: Callable[[float], None] = time.sleep,
                     reinit: bool = True,
                     **check_kw):
    """Run ``make_engine().check(...)`` under supervision.  Returns
    ``(res, engine, attempts_used)``; raises ``RetryExhausted`` when
    the last permitted attempt also fails.

    ``make_engine`` is called once per attempt — the backend-reinit
    contract (a fresh engine re-traces against a reconnected backend).
    ``checkpoint_path`` doubles as the recovery source: each retry
    resumes from the newest valid chain member; without one, retries
    fall back to the original ``resume_from``/``resume_image`` (or a
    fresh start).  ``reinit=False`` skips the jit-cache drop between
    attempts (the chaos differentials retry dozens of times on one
    CPU engine instance — re-tracing every executable there tests
    nothing and costs seconds per attempt; real runtime recoveries
    keep the default).  Remaining kwargs pass through to
    ``check()``."""
    from ..obs import NULL_OBS
    obs = obs if obs is not None else NULL_OBS
    # the caller's resume source: retries fall back to it (or to a
    # fresh start) whenever the checkpoint chain has no valid member —
    # never to a stale chain path from an earlier attempt
    orig_from, orig_image = resume_from, resume_image
    attempt = 0
    while True:
        try:
            eng = make_engine()
            kw = dict(check_kw)
            if resume_image is not None:
                kw["resume_image"] = resume_image
            res = eng.check(checkpoint_path=checkpoint_path,
                            resume_from=resume_from, obs=obs, **kw)
            return res, eng, attempt + 1
        except NotImplementedError:
            # a RuntimeError subclass, but NEVER weather: it names a
            # capability the engine lacks (e.g. multi-controller
            # checkpointing) — retrying cannot help
            raise
        except RETRYABLE as e:
            if attempt >= retries:
                if retries:
                    raise RetryExhausted(attempt + 1, e) from e
                raise
            wait = backoff_delay(attempt, backoff, backoff_max)
            obs.retry(attempt=attempt + 1, max_attempts=retries + 1,
                      wait_s=wait, error=e)
            sleep(wait)
            if reinit:
                _reinit_backend()
            # recovery source for the next attempt: newest valid
            # checkpoint > the original resume source > fresh start
            lv = (latest_valid(checkpoint_path)
                  if checkpoint_path else None)
            if lv is not None:
                resume_from, resume_image = lv, None
            else:
                resume_from, resume_image = orig_from, orig_image
            attempt += 1
