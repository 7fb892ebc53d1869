"""The readers of the program's counters and spans (CPU, small sizes):

- on hand-built records they agree with the records, and read None
  where the window's checks disagree or the program lacks the counter;
- on a traced engine at micro bounds, every check leaves one sample of
  its dedup counters in the span totals, the samples agree, and the
  readers read them.
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "bench"))

import run  # noqa: E402
from harness import manifest  # noqa: E402
from harness.system import CheckRecord, System  # noqa: E402
from test_benchmark import micro  # noqa: E402

GENERATED = 89337
COUNTS = {"dedup_walk_iters": 275, "dedup_probe_steps": 13195}


def _ctx(samples, spans=None, generated=(GENERATED,)):
    """A window of one check per sample: each sample is one check's
    counters, totalled as the span recorder totals them."""
    recs = [CheckRecord(distinct=1, generated=generated[i % len(generated)],
                        depth=1, level_sizes=[1])
            for i in range(len(samples))]
    spans = dict(spans or {})
    for nm in {k for s in samples for k in s}:
        vals = [s[nm] for s in samples if nm in s]
        spans[nm] = {"count": len(vals), "seconds": 0.0, "sum": sum(vals),
                     "min": min(vals), "max": max(vals)}
    return run.Context(records=recs, window_s=1.0, setup_s=0.0,
                       peak_bytes=0, peaks={}, spans=spans)


def _read(name, ctx):
    return manifest.load_module(manifest.metric_file(name)).read(ctx)


def test_counter_and_span_readers_agree_with_their_records():
    ctx = _ctx([COUNTS, dict(COUNTS)],
               spans={"check_setup": {"count": 2, "seconds": 0.25}})
    for nm in ("dedup_walk_iters_per_check",
               "dedup_walk_iters_per_check.small"):
        assert _read(nm, ctx) == 275
    assert _read("dedup_probe_steps_per_candidate", ctx) == \
        pytest.approx(13195 / GENERATED)
    for nm in ("check_setup_s_per_check", "check_setup_s_per_check.small"):
        assert _read(nm, ctx) == pytest.approx(0.125)


def test_counter_readers_return_none_when_checks_disagree():
    names = ("dedup_walk_iters_per_check",
             "dedup_probe_steps_per_candidate")
    other = dict(COUNTS, dedup_walk_iters=276, dedup_probe_steps=13196)
    for nm in names:
        assert _read(nm, _ctx([COUNTS, other])) is None
        # a check that left no sample
        assert _read(nm, _ctx([COUNTS, {}])) is None
        # a program without the counters (an older parent) reads None
        assert _read(nm, _ctx([{}, {}])) is None
    # checks that generated different numbers of candidates
    assert _read("dedup_probe_steps_per_candidate",
                 _ctx([COUNTS] * 2, generated=(1, 2))) is None
    assert _read("check_setup_s_per_check", _ctx([COUNTS])) is None


@pytest.mark.parametrize("name", ["raft-tlc-s3-l3",
                                  "raft-apalache-s2-k10"])
def test_traced_checks_leave_identical_counter_samples(name):
    c = micro(name)
    system = System(c, os.path.dirname(c["cfg"]), spans=True)
    recs = [system.check(), system.check()]
    spans = system.span_totals()
    walk = spans["dedup_walk_iters"]
    assert walk["count"] == 2 and walk["min"] == walk["max"] > 0
    assert spans["dedup_rounds"]["max"] > 0
    assert walk["min"] >= spans["dedup_rounds"]["max"]
    assert spans["check_setup"]["count"] == 2
    ctx = run.Context(records=recs, window_s=1.0, setup_s=0.0,
                      peak_bytes=0, peaks={}, spans=spans)
    assert _read("dedup_walk_iters_per_check", ctx) == walk["min"]
    assert _read("dedup_probe_steps_per_candidate", ctx) == \
        spans["dedup_probe_steps"]["min"] / recs[0].generated
    assert _read("check_setup_s_per_check", ctx) > 0
