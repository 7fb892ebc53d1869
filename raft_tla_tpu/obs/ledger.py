"""JSONL run ledger: one record per dispatch, written incrementally.

Rounds 4-5 lost multi-hour remote-TPU runs with nothing to show for
them: the stats existed only as in-process counters, so a dropped
connection destroyed the whole run's telemetry.  The ledger appends
one JSON line per dispatch (burst device call, per-level round trip,
or sim dispatch) and flushes it immediately, so a killed run leaves a
complete record up to the last dispatch — depth, frontier size,
cumulative registry counters, throughput, host RSS and device memory
(``jax.local_devices()[0].memory_stats()`` where the backend reports
it).  ``tools/watch.py`` tails it for live progress.
"""

from __future__ import annotations

import itertools
import json
import os
import time
from typing import Dict, Optional

# monotonic per-PROCESS record sequence (ISSUE 17 satellite): shared
# across every RunLedger in the process, so records of interleaved
# runs (or one run appending after a resume) order deterministically
# even when two ledgers target the same file; readers pair it with
# the per-run ``run_id`` stamp to demultiplex.  Old rows without the
# keys still parse — readers use .get().
_SEQ = itertools.count(1)


def rss_bytes() -> int:
    """Current process resident set size (bytes); 0 if unknowable."""
    try:
        with open("/proc/self/status") as fh:
            for line in fh:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) * 1024
    except OSError:
        pass
    try:
        import resource
        import sys
        ru = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        # ru_maxrss units are platform-defined: bytes on macOS,
        # KiB everywhere else that matters here
        return int(ru) * (1 if sys.platform == "darwin" else 1024)
    except Exception:
        return 0


def device_memory_stats() -> Optional[Dict[str, int]]:
    """``memory_stats()`` of device 0, trimmed to the interesting
    gauges; None where the backend (e.g. XLA:CPU) reports nothing."""
    try:
        import jax
        stats = jax.local_devices()[0].memory_stats()
    except Exception:
        return None
    if not stats:
        return None
    keep = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
    out = {k: int(stats[k]) for k in keep if k in stats}
    return out or None


class RunLedger:
    """Append-only JSONL writer; every record carries a wall-clock
    timestamp (for correlating with external logs) and a monotonic
    one (for durations)."""

    def __init__(self, path: str):
        self.path = path
        # run-constant keys applied to EVERY record via setdefault
        # (Obs installs {"run_id": ...} here, so rows recorded
        # directly by the serving layer carry it too)
        self.stamp: Dict = {}
        # append, never truncate: a resumed run (--resume after a
        # lost connection) must extend the pre-crash telemetry, which is
        # exactly the record the ledger exists to preserve
        self._fh = open(path, "a")
        self._t0 = time.perf_counter()

    def record(self, rec: Dict):
        rec = dict(rec)
        for k, v in self.stamp.items():
            rec.setdefault(k, v)
        rec.setdefault("seq", next(_SEQ))
        rec.setdefault("ts", round(time.time(), 3))
        rec.setdefault("t_mono", round(time.perf_counter() - self._t0, 6))
        self._fh.write(json.dumps(rec) + "\n")
        # flush per record: the OS has the line even if the process is
        # killed mid-run (the whole point of the ledger)
        self._fh.flush()

    def close(self):
        if self._fh is not None:
            self._fh.close()
            self._fh = None
