"""The comparison that decides ``correct``: every check's answer against
the reference's, layer by layer.  Each number is an exact gap, so each
limit is 0 (an exact comparison has the limit 0)."""

from __future__ import annotations

from typing import Dict, List

# name -> limit; the order is the order printed
LIMITS = {
    "depth_gap": 0,          # level driver: depth reached
    "level_size_gap": 0,     # level driver: worst |size| gap over levels
    "distinct_gap": 0,       # dedup: distinct states
    "generated_gap": 0,      # dedup: generated successors
    "verdict_gap": 0,        # predicates: invariants judged differently
}


def gaps(rec, ref) -> Dict[str, int]:
    """The five gaps of one check (``rec``) against the reference."""
    n = max(len(rec.level_sizes), len(ref.level_sizes))
    a = list(rec.level_sizes) + [0] * (n - len(rec.level_sizes))
    b = list(ref.level_sizes) + [0] * (n - len(ref.level_sizes))
    return {
        "depth_gap": abs(rec.depth - ref.depth),
        "level_size_gap": max((abs(x - y) for x, y in zip(a, b)),
                              default=0),
        "distinct_gap": abs(rec.distinct - ref.distinct),
        "generated_gap": abs(rec.generated - ref.generated),
        "verdict_gap": len(set(rec.violated) ^ set(ref.violated)),
    }


def compare(records: List, ref) -> Dict[str, Dict[str, int]]:
    """Worst gap of each kind over every check: ``{name: {value,
    limit}}``.  No check at all compares as a failure."""
    worst = {nm: 0 for nm in LIMITS}
    for rec in records:
        for nm, v in gaps(rec, ref).items():
            worst[nm] = max(worst[nm], v)
    out = {nm: {"value": worst[nm], "limit": lim}
           for nm, lim in LIMITS.items()}
    out["checks_compared"] = {"value": len(records), "limit": 1}
    return out


def ok(compared: Dict[str, Dict[str, int]]) -> bool:
    return (compared["checks_compared"]["value"] >= 1 and
            all(compared[nm]["value"] <= lim for nm, lim in LIMITS.items()))


def failed_checks(records: List, ref) -> int:
    return sum(1 for rec in records
               if any(v > LIMITS[nm] for nm, v in gaps(rec, ref).items()))
