"""Metrics registry: the ONE store for a run's scalar counters.

Before this module the counters lived four lives — mutated as ad-hoc
``CheckResult`` fields by each engine's harvest loop, hand-copied into
the CLI's ``--stats-json`` dict, re-copied into checkpoint meta, and
re-derived by bench/deep_run — and the copies drifted (the
``levels_fused`` pseudo-level bug needed three review passes to fix in
every copy).  Now:

- ``MetricsRegistry`` holds the counters; ``engine.bfs.CheckResult``
  exposes them as write-through attribute views, so a driver mutating
  ``res.levels_fused`` IS updating the registry — there is no second
  store to fall out of sync;
- ``check_stats`` / ``sim_stats`` are the single assemblers of the
  ``--stats-json`` payloads (cli, the run ledger and the tests all call
  them), with the pre-registry key order pinned by
  ``tests/test_obs.py`` for byte-compatibility.

Keys are registered once (``register``) and unknown-key writes raise —
a typo'd counter fails loudly instead of forking a new silent copy.
"""

from __future__ import annotations

from typing import Dict, Mapping, Optional

# the dedup claim walk's device-side work counts (engine/bfs
# _probe_insert), in the order the device packs them: inner walk
# iterations (vector-wide gather rounds), probe advances summed over
# lanes, outer claim/insert/reset scatter rounds, and claims lost to a
# lower rank (retries).  Counted by the single-device engine's chunk
# step and burst (and the pjit engine, which inherits them); the spill
# engine reports 0.
DEDUP_COUNTER_KEYS = ("dedup_walk_iters", "dedup_probe_steps",
                      "dedup_rounds", "dedup_claim_losses")

# the canonical counter set every exhaustive-check engine accumulates
# (bfs / spill / pjit_mesh all share CheckResult, so the set is
# structurally identical across them — tests/test_obs.py pins it)
CHECK_COUNTER_KEYS = (
    "distinct_states", "generated_states", "depth", "overflow_faults",
    "violations_global", "levels_fused", "burst_dispatches",
    "burst_bailouts", "pin_interior_states", "guard_matmul",
    "delta_matmul", "sym_canon") + DEDUP_COUNTER_KEYS

# the MXU-path mode flags (0/1): which expansion program this
# run executed — BENCH rounds 9/11 read these next to the
# guard_matmul / delta_apply span totals so the A/B
# attributes per phase AND records which mode produced each row.
# Stamped LIVE by every engine's _stamp_mode (never serialized into
# checkpoints — a resumed run reports the resuming engine's modes).
MXU_COUNTER_KEYS = ("guard_matmul", "delta_matmul",
                    # 1 = orbit-sort canonical fingerprints (round 15),
                    # 0 = min-over-perms; the resolved --sym-canon mode
                    "sym_canon")

# the burst telemetry triple that must agree between the ledger,
# --stats-json and checkpoint meta (the PR-5 drift class)
BURST_COUNTER_KEYS = ("levels_fused", "burst_dispatches",
                      "burst_bailouts")

# the sim engine's canonical counter set (SimResult fields surfaced by
# sim_stats and the simulate ledger's final record)
SIM_COUNTER_KEYS = (
    "walkers", "steps_dispatched", "walker_steps", "sampled_steps",
    "restarts", "deadlocks", "promotions", "hits",
    "est_distinct_states", "bloom_saturated", "bloom_canonical")

# the per-dispatch subset knowable without a device bloom fetch
# (sim/walker.dispatch_counters emits exactly these)
SIM_DISPATCH_KEYS = (
    "walkers", "steps_dispatched", "walker_steps", "sampled_steps",
    "restarts", "deadlocks", "promotions", "hits")


class MetricsRegistry:
    """A named-counter store with explicit registration.

    ``register`` declares a counter once; ``set``/``inc`` update it and
    raise ``KeyError`` on undeclared names, so every counter any code
    path reports must appear in the declared set — new telemetry is
    added in exactly one place and shows up in every consumer
    (ledger, stats JSON, checkpoint meta) automatically.
    """

    __slots__ = ("_vals",)

    def __init__(self, initial: Optional[Mapping] = None):
        self._vals: Dict[str, object] = {}
        if initial:
            for k, v in initial.items():
                self.register(k, v)

    def register(self, name: str, value=0):
        if name in self._vals:
            raise ValueError(f"metric {name!r} already registered")
        self._vals[name] = value

    def set(self, name: str, value):
        if name not in self._vals:
            raise KeyError(
                f"metric {name!r} not registered (known: "
                f"{', '.join(sorted(self._vals))})")
        self._vals[name] = value

    def inc(self, name: str, delta=1):
        self.set(name, self._vals[name] + delta)

    def get(self, name: str):
        return self._vals[name]

    def __contains__(self, name: str) -> bool:
        return name in self._vals

    def keys(self):
        return tuple(self._vals.keys())

    def as_dict(self) -> Dict[str, object]:
        """Snapshot in registration order (dict order is insertion
        order, so consumers emit a stable key sequence)."""
        return dict(self._vals)


def check_stats(counters: Mapping, seconds: float, n_violations: int,
                fp_bits: Optional[int] = None,
                spec: Optional[str] = None,
                ir_fp: Optional[str] = None,
                lanes_enabled: Optional[Mapping] = None
                ) -> Dict[str, object]:
    """The ``check`` stats payload (stdout line and ``--stats-json``),
    assembled from a counter mapping (``CheckResult.metrics.as_dict()``
    for the engines; a hand-built dict for the oracle, which has no
    registry).  ONE definition — cli, the run ledger's final record and
    the tests all call this, so the key set cannot drift per caller.

    Key order and presence match the pre-registry CLI output exactly
    (tests/test_obs.py pins both): the fingerprint/burst telemetry
    keys appear only when ``fp_bits`` is given (the oracle has no
    notion of them), ``pin_interior_states`` only when nonzero.
    ``lanes_enabled`` (the engine's enabled lanes per action family)
    comes last, when a nonempty mapping is given.
    """
    distinct = int(counters["distinct_states"])
    gen = int(counters["generated_states"])
    out = {
        "distinct_states": distinct,
        "generated_states": gen,
        "depth": int(counters["depth"]),
        "seconds": round(float(seconds), 3),
        "states_per_sec": round(distinct / max(seconds, 1e-9), 1),
        "dedup_hit_rate": round(1.0 - distinct / max(gen, 1), 4),
        "violations": int(n_violations),
    }
    if int(counters.get("pin_interior_states", 0) or 0):
        out["pin_interior_states"] = int(counters["pin_interior_states"])
    if fp_bits is not None:
        # dedup is fingerprint-based (TLC semantics): surface the
        # expected-collision bound the exhaustiveness claim rests on
        # (ADVICE r1; SURVEY §7.4 pt 4).  E[collisions] <= n^2/2^(b+1)
        out["fp_bits"] = int(fp_bits)
        out["expected_fp_collisions"] = float(
            distinct * distinct / 2.0 ** (fp_bits + 1))
        # fused-dispatch telemetry: proves the multi-level burst
        # engaged (levels_fused > 0) instead of silently bailing every
        # level (burst_bailouts ~ depth with levels_fused 0)
        for k in BURST_COUNTER_KEYS:
            out[k] = int(counters[k])
        # MXU-path mode flags (guard-matmul / delta-matmul expansion)
        # — .get: pre-round-9 counter dicts lack them
        for k in MXU_COUNTER_KEYS:
            out[k] = int(counters.get(k, 0) or 0)
        # dedup work counts (appended after the pinned prefix)
        for k in DEDUP_COUNTER_KEYS:
            out[k] = int(counters.get(k, 0) or 0)
    if spec is not None:
        # the active SpecIR name + structure fingerprint (spec/
        # package) — appended last so the pre-IR key prefix stays
        # byte-identical; present for the oracle engine too (the spec
        # is a frontend property, not an engine one)
        out["spec"] = spec
        if ir_fp is not None:
            out["ir_fingerprint"] = ir_fp
    if lanes_enabled:
        out["lanes_enabled"] = {k: int(v) for k, v in lanes_enabled.items()}
    return out


def sim_counters(res) -> Dict[str, object]:
    """A SimResult's canonical counter snapshot (SIM_COUNTER_KEYS
    order) — the simulate ledger records and sim_stats share it."""
    return {
        "walkers": int(res.walkers),
        "steps_dispatched": int(res.steps_dispatched),
        "walker_steps": int(res.walker_steps),
        "sampled_steps": int(res.sampled_steps),
        "restarts": int(res.restarts),
        "deadlocks": int(res.deadlocks),
        "promotions": int(res.promotions),
        "hits": len(res.hits),
        "est_distinct_states": round(float(res.est_distinct_states), 1),
        "bloom_saturated": bool(res.bloom_saturated),
        "bloom_canonical": bool(res.bloom_canonical),
    }


def sim_stats(res, target: str, policy: str, seed: int,
              platform: str) -> Dict[str, object]:
    """The ``simulate`` stats payload — same single-assembler contract
    as check_stats (key order matches the pre-registry CLI output)."""
    c = sim_counters(res)
    return {
        "target": target,
        "policy": policy,
        "walkers": c["walkers"],
        "steps_dispatched": c["steps_dispatched"],
        "walker_steps": c["walker_steps"],
        "sampled_steps": c["sampled_steps"],
        "walker_steps_per_sec": round(res.walker_steps_per_sec, 1),
        "restarts": c["restarts"],
        "deadlocks": c["deadlocks"],
        "promotions": c["promotions"],
        "seconds": round(float(res.seconds), 3),
        "est_distinct_states": c["est_distinct_states"],
        "bloom_saturated": c["bloom_saturated"],
        "bloom_canonical": c["bloom_canonical"],
        "hits": c["hits"],
        "platform": platform,
        "seed": seed,
    }
