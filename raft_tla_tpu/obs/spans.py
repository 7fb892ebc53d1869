"""Span/timeline recorder: nested named phases on monotonic clocks.

Every engine driver brackets its phases — ``compile``,
``burst_dispatch``, ``level_dispatch``, ``host_sweep``, ``harvest``,
``archive_io``, ``checkpoint`` — with ``SpanRecorder.span(name)``.
Round 9 adds the MXU-path micro-phase names ``guard_matmul`` /
``guard_lanes``; round 11 adds
``delta_apply`` / ``delta_kernels`` (the group scatter-as-matmul vs
the per-family successor kernels): inside a fused engine step these
exist as ``jax.named_scope`` annotations (visible in an XLA
``--profile-dir`` trace), and bench.py times them as standalone host
spans in the BENCH_r09/r11 A/Bs so the win attributes per phase.
Clocks are ``time.perf_counter()`` (monotonic: NTP steps on long
runs corrupted the old ``time.time()`` deltas), and completed
spans are emitted as Chrome-trace "complete" events (``ph": "X"`` with
``ts``/``dur`` in microseconds), so a ``--trace-timeline`` file loads
directly in Perfetto / chrome://tracing next to an XLA device trace
captured with matching ``jax.profiler.TraceAnnotation`` names
(``--profile-dir``).

The on-disk format is the catapult JSON *array* form, streamed: the
file is valid the moment each span closes (the trailing ``]`` is
optional per the trace-event spec and appended on a clean close), so a
killed run still leaves a loadable timeline up to its last dispatch.
"""

from __future__ import annotations

import contextlib
import json
import os
import time
from typing import Dict, List, Optional, Tuple


class SpanRecorder:
    """Nested span timer + Chrome-trace-event emitter.

    path     — optional trace file, streamed incrementally (see module
               docstring); ``close()`` finishes the JSON array.
    annotate — mirror every span as a ``jax.profiler.TraceAnnotation``
               so XLA device traces (``--profile-dir``) line up with
               the host timeline by name.
    """

    def __init__(self, path: Optional[str] = None,
                 annotate: bool = False):
        self.path = path
        self.annotate = annotate
        self._t0 = time.perf_counter()
        self._pid = os.getpid()
        self._stack: List[Tuple[str, float]] = []
        self._totals: Dict[str, List[float]] = {}   # name -> [n, secs]
        self.events: List[dict] = []
        self._fh = None
        self._n_written = 0
        if path:
            self._fh = open(path, "w")
            self._fh.write("[")
            self._fh.flush()

    # -- recording -----------------------------------------------------

    @contextlib.contextmanager
    def span(self, name: str):
        ann = None
        if self.annotate:
            try:
                import jax
                ann = jax.profiler.TraceAnnotation(name)
                ann.__enter__()
            except Exception:
                ann = None
        t0 = time.perf_counter()
        self._stack.append((name, t0))
        try:
            yield self
        finally:
            t1 = time.perf_counter()
            self._stack.pop()
            if ann is not None:
                ann.__exit__(None, None, None)
            self._emit(name, t0, t1)

    def _emit(self, name: str, t0: float, t1: float):
        tot = self._totals.setdefault(name, [0, 0.0])
        tot[0] += 1
        tot[1] += t1 - t0
        ev = {
            "name": name, "cat": "obs", "ph": "X",
            "ts": round((t0 - self._t0) * 1e6, 3),
            "dur": round((t1 - t0) * 1e6, 3),
            "pid": self._pid, "tid": 0,
        }
        if self._fh is None:
            # in-memory mode only: when streaming, the file IS the
            # record — retaining a second copy would grow RAM without
            # bound on days-scale runs (totals() reads _totals)
            self.events.append(ev)
        else:
            # never a trailing comma: a killed run's file stays
            # parseable (only the closing ] is missing, which the
            # trace-event spec makes optional)
            prefix = "\n" if self._n_written == 0 else ",\n"
            self._fh.write(prefix + json.dumps(ev))
            self._fh.flush()
            self._n_written += 1

    # -- reading back --------------------------------------------------

    def totals(self) -> Dict[str, Dict[str, float]]:
        """Per-span-name inclusive totals:
        ``{name: {count, seconds}}`` — bench.py records these per phase
        so A/B deltas attribute to dispatch vs compute vs harvest
        instead of one end-to-end number."""
        return {nm: {"count": n, "seconds": round(s, 6)}
                for nm, (n, s) in sorted(self._totals.items())}

    # -- lifecycle -----------------------------------------------------

    def close(self):
        if self._fh is not None:
            self._fh.write("\n]\n")
            self._fh.close()
            self._fh = None
