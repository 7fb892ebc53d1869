"""bench.py perf-regression floor (VERDICT r3 #5): a deliberate
slowdown trips the warn tier, a collapse below the measured noise band
zeroes the score, non-headline runs and foreign platforms skip, and a
new best ratchets the floor file."""

import importlib.util
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

spec = importlib.util.spec_from_file_location(
    "bench", os.path.join(REPO, "bench.py"))
bench = importlib.util.module_from_spec(spec)
sys.modules["bench"] = bench
spec.loader.exec_module(bench)


def floor_file(tmp_path, best=100000.0):
    p = tmp_path / "floor.json"
    p.write_text(json.dumps({"tlc_membership_S3_T3_L3": {
        "platform_prefix": "TPU", "machine": "test",
        "best_states_per_sec": best, "source": "test",
        "warn_frac": 0.6, "hard_frac": 0.3}}))
    return str(p)


def test_floor_trips_on_slowdown(tmp_path):
    fp = floor_file(tmp_path)
    # healthy rate: ok, not zeroed
    info, zero = bench.perf_floor(90000.0, bench.MAX_DEPTH, "TPU v5", fp)
    assert info["status"] == "ok" and not zero
    # deliberate slowdown (e.g. --chunk 64): warn tier trips
    info, zero = bench.perf_floor(45000.0, bench.MAX_DEPTH, "TPU v5", fp)
    assert info["status"] == "warn" and not zero
    # collapse below the noise band: score is zeroed
    info, zero = bench.perf_floor(10000.0, bench.MAX_DEPTH, "TPU v5", fp)
    assert info["status"] == "hard" and zero


def test_floor_skips_nonheadline_and_foreign_platform(tmp_path):
    fp = floor_file(tmp_path)
    info, zero = bench.perf_floor(10.0, 5, "TPU v5", fp)
    assert "skipped" in info["status"] and not zero
    info, zero = bench.perf_floor(10.0, bench.MAX_DEPTH, "cpu", fp)
    assert "skipped" in info["status"] and not zero
    # missing floor file: floor disabled, never zeroes
    info, zero = bench.perf_floor(10.0, bench.MAX_DEPTH, "TPU v5",
                                  str(tmp_path / "absent.json"))
    assert info is None and not zero


def test_floor_ratchets_on_new_best(tmp_path):
    fp = floor_file(tmp_path, best=50000.0)
    info, zero = bench.perf_floor(60000.0, bench.MAX_DEPTH, "TPU v5", fp)
    assert info["status"] == "ok" and not zero
    assert json.load(open(fp))["tlc_membership_S3_T3_L3"][
        "best_states_per_sec"] == 60000.0
    # a failing correctness gate must NOT ratchet the floor
    bench.perf_floor(99000.0, bench.MAX_DEPTH, "TPU v5", fp,
                     gate_ok=False)
    assert json.load(open(fp))["tlc_membership_S3_T3_L3"][
        "best_states_per_sec"] == 60000.0


import pytest

# the run shapes tools/measure_baseline.py and tools/deep_run.py key
# their floor rows by, beside bench.py's headline
_FLOOR_KEYS = ("tlc_membership_S3_T3_L3", "config1_budgeted",
               "config2_budgeted", "config3_budgeted", "config4_budgeted",
               "config5_budgeted", "spill_config2_depth19")


@pytest.mark.parametrize("key", _FLOOR_KEYS)
def test_floor_machinery_per_row(key, tmp_path):
    """Every row works through the same warn/hard/ratchet machinery."""
    p = tmp_path / "floor.json"
    p.write_text(json.dumps({key: {
        "platform_prefix": "TPU", "machine": "test",
        "best_states_per_sec": 100000.0, "source": "test",
        "warn_frac": 0.6, "hard_frac": 0.3}}))
    fp = str(p)
    info, zero = bench.perf_floor(45000.0, 0, "TPU v5", fp, key=key,
                                  headline_depth=0)
    assert info["status"] == "warn" and not zero
    info, zero = bench.perf_floor(10000.0, 0, "TPU v5", fp, key=key,
                                  headline_depth=0)
    assert info["status"] == "hard" and zero
    info, zero = bench.perf_floor(103000.0, 0, "TPU v5", fp, key=key,
                                  headline_depth=0, bump_source="t")
    assert info["status"] == "ok"
    assert json.load(open(fp))[key]["best_states_per_sec"] == 103000.0
