"""The reader of the program's ``harvest_transfers`` counter (CPU, no
engine): it reads the window checks' common count, and None where the
checks disagree, a check left no sample, or the program has no such
counter (a parent that predates it)."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "bench"))

import run  # noqa: E402
from harness import manifest  # noqa: E402
from harness.system import CheckRecord  # noqa: E402

NAME = "harvest_transfers_per_check"


def _read(samples):
    recs = [CheckRecord(distinct=1, generated=1, depth=1, level_sizes=[1])
            for _ in samples]
    vals = [s["harvest_transfers"] for s in samples
            if "harvest_transfers" in s]
    spans = ({"harvest_transfers": {
        "count": len(vals), "seconds": 0.0, "sum": sum(vals),
        "min": min(vals), "max": max(vals)}} if vals else {})
    ctx = run.Context(records=recs, window_s=1.0, setup_s=0.0,
                      peak_bytes=0, peaks={}, spans=spans)
    return manifest.load_module(manifest.metric_file(NAME)).read(ctx)


def test_reads_the_checks_common_count():
    assert _read([{"harvest_transfers": 5}] * 3) == 5


def test_reads_none_where_it_cannot_tell():
    assert _read([{"harvest_transfers": 5}, {"harvest_transfers": 6}]) \
        is None
    assert _read([{"harvest_transfers": 5}, {}]) is None
    assert _read([{}, {}]) is None


def test_manifest_lists_it_for_the_small_cell():
    (m,) = [m for m in manifest.load()["per_layer"] if m["name"] == NAME]
    assert m["layer"] == "level driver" and m["moves"] == "check_s"
    assert m["workloads"] == ["apalache-s2-k10"]
