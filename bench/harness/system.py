"""The system under test: the program's checking engine, built once in
set-up as `python -m raft_tla_tpu check <cfg> <flags>` would build it,
and driven one whole check at a time.

This is the only module of the benchmark that imports the program.  It
takes from it the engine, its spans and its counters; nothing else.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Dict, List


@dataclass
class CheckRecord:
    """What one check answered: the numbers the comparison reads."""
    distinct: int
    generated: int
    depth: int
    level_sizes: List[int]
    violated: List[str] = field(default_factory=list)
    seconds: float = 0.0


class System:
    """One engine, built from a configuration's JSON (``conf``) and
    the cfg copy beside it.  ``spans=True`` hands every check an obs
    bundle with a span recorder (the `--registry`/`--trace-timeline`
    path): the per-layer metrics read its totals.  ``spans=False`` is a
    plain `check` with no obs flag."""

    def __init__(self, conf: dict, conf_dir: str, spans: bool = False):
        from raft_tla_tpu.cfg.parser import load_model
        from raft_tla_tpu.config import Bounds
        from raft_tla_tpu.engine.bfs import Engine
        cfg = load_model(os.path.join(conf_dir, conf["cfg"]))
        flags = conf.get("bound_flags", {})
        if flags:
            # the CLI's bound overrides (cli._apply_overrides): flags win,
            # the spec's other bounds stay, MaxTerm re-derives
            b = cfg.bounds
            cfg = cfg.with_(bounds=Bounds.make(
                max_log_length=flags.get("max_log_length",
                                         b.max_log_length),
                max_restarts=flags.get("max_restarts", b.max_restarts),
                max_timeouts=flags.get("max_timeouts", b.max_timeouts),
                max_client_requests=flags.get("max_client_requests",
                                              b.max_client_requests),
                max_membership_changes=b.max_membership_changes,
                max_terms=flags.get("max_terms"),
                max_trace=b.max_trace))
        self.cfg = cfg
        self.max_depth = int(conf["max_depth"])
        self.stop_on_violation = bool(conf["stop_on_violation"])
        self.engine = Engine(cfg, **conf["engine"])
        self.spans = spans
        self.obs = None
        self.reset_spans()

    def reset_spans(self):
        from raft_tla_tpu.obs import NULL_OBS, Obs, SpanRecorder
        # annotate: each span also opens a profiler TraceAnnotation, so
        # the device trace's idle gaps can be labelled by host span
        self.obs = (Obs(spans=SpanRecorder(annotate=True)) if self.spans
                    else NULL_OBS)

    def span_totals(self) -> Dict[str, Dict[str, float]]:
        return self.obs.spans.totals() if self.spans else {}

    def check(self) -> CheckRecord:
        r = self.engine.check(max_depth=self.max_depth,
                              stop_on_violation=self.stop_on_violation,
                              obs=self.obs)
        return CheckRecord(
            distinct=int(r.distinct_states),
            generated=int(r.generated_states), depth=int(r.depth),
            level_sizes=[int(x) for x in r.level_sizes],
            violated=sorted({v.invariant for v in r.violations}),
            seconds=float(r.seconds))

    def capacities(self) -> Dict[str, int]:
        """The engine's buffer capacities now: a warm-up that grew them
        shows here."""
        e = self.engine
        return {"lcap": e.LCAP, "vcap": e.VCAP, "ocap": e.OCAP}

    def close(self):
        """Drop every device buffer the engine holds."""
        self.engine = None
        self.obs = None

