"""Span/timeline recorder: nested named phases on monotonic clocks.

Every engine driver brackets its phases with
``SpanRecorder.span(name)``: the single-device engine emits
``check_setup`` (everything before its driver loop, with ``compile``,
the per-level prewarm, inside it), ``burst_dispatch``,
``level_dispatch``, ``harvest``, ``archive_io`` and ``checkpoint``;
the spill engine adds ``host_sweep``, ``h2d_stage`` and
``sweep_overlap``, the serving layer its ``job_*``/``bucket_*`` and
``batched_dispatch`` spans, the simulator ``sim_dispatch``.  Spans
are host phases only: work inside one device program (the chunk
step's expansion, dedup, predicates) has no span, and its share shows
in a device trace or in the program's counters (``obs.metrics``).
The single-device engine also records each check's dedup counters as
one Chrome-trace counter event (``counters()``), so the timeline and
``totals()`` carry them beside the spans.
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
        # counter name -> [samples, sum, min, max]
        self._counters: Dict[str, List[int]] = {}
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

    def counters(self, values: Dict[str, int]):
        """One sample of named counters (a check's dedup counts): a
        Chrome-trace counter event ("ph": "C") on the timeline, and
        per-name totals that ``totals()`` reports beside the spans."""
        values = {nm: int(v) for nm, v in values.items()}
        for nm, v in values.items():
            tot = self._counters.setdefault(nm, [0, 0, v, v])
            tot[0] += 1
            tot[1] += v
            tot[2] = min(tot[2], v)
            tot[3] = max(tot[3], v)
        self._write({
            "name": "counters", "cat": "obs", "ph": "C",
            "ts": round((time.perf_counter() - self._t0) * 1e6, 3),
            "pid": self._pid, "tid": 0,
            "args": values,
        })

    def _emit(self, name: str, t0: float, t1: float):
        tot = self._totals.setdefault(name, [0, 0.0])
        tot[0] += 1
        tot[1] += t1 - t0
        self._write({
            "name": name, "cat": "obs", "ph": "X",
            "ts": round((t0 - self._t0) * 1e6, 3),
            "dur": round((t1 - t0) * 1e6, 3),
            "pid": self._pid, "tid": 0,
        })

    def _write(self, ev: dict):
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
        instead of one end-to-end number.  Each counter recorded by
        ``counters()`` adds ``{name: {count, seconds: 0.0, sum, min,
        max}}`` over its samples."""
        out = {nm: {"count": n, "seconds": round(s, 6)}
               for nm, (n, s) in self._totals.items()}
        for nm, (n, s, lo, hi) in self._counters.items():
            out[nm] = {"count": n, "seconds": 0.0, "sum": s, "min": lo,
                       "max": hi}
        return dict(sorted(out.items()))

    # -- lifecycle -----------------------------------------------------

    def close(self):
        if self._fh is not None:
            self._fh.write("\n]\n")
            self._fh.close()
            self._fh = None
