"""Measure BASELINE.md configs #1-#5: native CPU checker (the machine-
measured TLC stand-in) vs the TPU engine, same counting semantics.

Usage:  python tools/measure_baseline.py [config_no ...]

Writes one JSON file per config under baseline_runs/ so the BASELINE.md
table can be filled incrementally; reruns overwrite.  Budgets keep every
run minutes-scale: configs whose spaces exceed the budget are recorded
with exhausted=false and the rate still holds (level-granular budget,
identical on both engines).
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

OUT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "baseline_runs")
os.makedirs(OUT, exist_ok=True)

TLC_CFG = "/root/reference/tlc_membership/raft.cfg"
APA_CFG = "/root/reference/apalache_no_membership/raft.cfg"


def build_cfg(n):
    from raft_tla_tpu.cfg.parser import load_model
    from raft_tla_tpu.config import NEXT_DYNAMIC, Bounds
    if n == 1:
        # Server=3, MaxTerm=2, MaxLogLen=2 (BASELINE.json config #1)
        return load_model(TLC_CFG, bounds=Bounds.make(
            max_log_length=2, max_timeouts=1, max_client_requests=3))
    if n == 2:
        # headline metric config (bench.py)
        cfg = load_model(TLC_CFG, bounds=Bounds.make(
            max_log_length=3, max_timeouts=2, max_client_requests=3))
        return cfg.with_(invariants=("ElectionSafety",))
    if n == 3:
        # membership workload: Server=4 ⊋ InitServer=3, NextDynamic,
        # + the invariant BASELINE.json names (authored by us — the
        # reference has no such operator, SURVEY preamble)
        cfg = load_model(TLC_CFG, bounds=Bounds.make(
            max_log_length=2, max_timeouts=1, max_client_requests=2,
            max_membership_changes=1))
        return cfg.with_(
            n_servers=4, init_servers=(0, 1, 2),
            next_family=NEXT_DYNAMIC,
            invariants=tuple(cfg.invariants) +
            ("OneAtATimeMembershipChangeOK",))
    if n == 4:
        # apalache_no_membership variant, bounded k=10 as BFS depth
        return load_model(APA_CFG)
    if n == 5:
        # Server=5, MaxTerm=4, MaxLogLen=4, scenario property hunt
        cfg = load_model(TLC_CFG, bounds=Bounds.make(
            max_log_length=4, max_timeouts=3, max_client_requests=3))
        return cfg.with_(n_servers=5, init_servers=(0, 1, 2, 3, 4),
                         invariants=("ConcurrentLeaders",))
    raise SystemExit(f"unknown config {n}")


# budgets keep runs minutes-scale and inside single-chip HBM for the
# engine's level buffers (levels near the budget must fit LCAP without
# growth: a growth's transient old+new buffers are what OOM a chip);
# equal budgets on both engines keep the differential count check
# meaningful even when not exhaustive
BUDGET = {1: 2_000_000, 2: 2_400_000, 3: 1_500_000, 4: 10**9,
          5: 600_000}
DEPTH = {4: 10}
ENGINE_KW = {
    # ocap=2^14 on the S=3 configs: the early nearly-all-fresh levels
    # outgrow the chunk*4 fresh-row default (growth = replay the level)
    1: dict(chunk=2048, lcap=1 << 21, vcap=1 << 24, ocap=1 << 14),
    2: dict(chunk=2048, lcap=1 << 21, vcap=1 << 24, ocap=1 << 14),
    # fcap/ocap/fam_caps pre-sized from measured per-family enabled
    # maxima (tools/tune_config3.py famx_max + 25% headroom): the
    # membership model averages ~20 enabled lanes/parent and its early
    # levels are nearly all-fresh, so the density-table defaults both
    # under-size (mid-run growth = ~100s replay+recompile) and
    # over-size (every phase pays the buffer width) — measured
    # 18.2k -> 31.2k states/s round-over-round on this config
    # lcap=2^23 pre-sizes for depth 17's 2.14M-state level: at 2^21 the
    # first rep pays a grow+recompile+replay (~200s) that the median
    # then hides — measured 12.3k/s rep-1 vs 85.6k/s steady-state
    3: dict(chunk=2048, lcap=1 << 23, vcap=1 << 24, fcap=45056,
            ocap=1 << 14,
            fam_caps=(3584, 512, 3584, 2048, 3072, 2560, 1024, 8192,
                      4608, 8192, 7680, 7680, 2048, 3072)),
    4: dict(chunk=1024, lcap=1 << 17, vcap=1 << 20),
    5: dict(chunk=512, lcap=1 << 20, vcap=1 << 23),
}


from statistics import median as _median


def measure(n, reps=3):
    """Interleaved A/B protocol (VERDICT r4 #7): the recorded ratio is
    median(native)/median(engine) over `reps` alternating same-process
    runs (native, TPU, native, TPU, ...) — the shared single-vCPU host
    measured the SAME native binary at 24k-150k/s across different
    days, so single runs hours apart are not comparable."""
    from raft_tla_tpu import native
    from raft_tla_tpu.engine.bfs import Engine
    cfg = build_cfg(n)
    budget = BUDGET[n]
    depth = DEPTH.get(n, 10**9)
    out = {"config": n, "budget": budget, "max_depth": depth,
           "protocol": f"interleaved median-of-{reps} (same process)"}

    # config 5's target is a scenario property (negated reachability);
    # the native runtime checks safety invariants only, so its rate is
    # measured on the bare state space there
    nat_cfg = cfg.with_(invariants=()) if n == 5 else cfg
    kw = dict(ENGINE_KW[n])
    fam_caps = kw.pop("fam_caps", None)
    eng = Engine(cfg, store_states=False, **kw)
    if fam_caps is not None:
        eng.FAM_CAPS = tuple(fam_caps)
    t0 = time.time()
    eng.check(max_depth=min(2, depth))          # warm the jit caches
    compile_s = time.time() - t0

    nat_rates, eng_rates = [], []
    nat = r = None
    for rep in range(max(1, int(reps))):
        nat = native.check(nat_cfg, threads=os.cpu_count() or 1,
                           max_states=budget, max_depth=depth)
        nat_rates.append(round(nat.states_per_sec, 1))
        t0 = time.time()
        r = eng.check(max_states=budget, max_depth=depth)
        secs = time.time() - t0
        eng_rates.append(round(r.distinct_states / max(secs, 1e-9), 1))
        print(f"config {n} rep {rep}: native {nat_rates[-1]}/s  "
              f"engine {eng_rates[-1]}/s", flush=True)
        # identical counts EVERY rep, not just the last
        assert (r.distinct_states == nat.distinct_states
                or n == 5), (r.distinct_states, nat.distinct_states)

    # both `seconds` fields are MEDIAN-DERIVED (distinct/median rate)
    # so they stay comparable to each other and to states_per_sec; the
    # raw per-rep rates ride in rates[]
    out["native"] = {
        "distinct": int(nat.distinct_states), "depth": int(nat.depth),
        "seconds": round(nat.distinct_states /
                         max(_median(nat_rates), 1e-9), 2),
        "states_per_sec": _median(nat_rates),
        "rates": nat_rates,
        "violations": len(nat.violations),
        "exhausted": bool(nat.distinct_states < budget),
    }
    out["engine"] = {
        "distinct": int(r.distinct_states), "depth": int(r.depth),
        "seconds": round(r.distinct_states / max(_median(eng_rates),
                                                 1e-9), 2),
        "states_per_sec": _median(eng_rates),
        "rates": eng_rates,
        "compile_seconds": round(compile_s, 1),
        "violations": len(r.violations),
        "overflow_faults": int(r.overflow_faults),
        "exhausted": bool(r.distinct_states < budget),
    }
    out["counts_match"] = (
        out["native"]["distinct"] == out["engine"]["distinct"]
        and out["native"]["depth"] == out["engine"]["depth"])
    out["speedup"] = round(out["engine"]["states_per_sec"] /
                           max(out["native"]["states_per_sec"], 1e-9), 2)
    # per-config perf floor (VERDICT r4 #6): the canonical budgeted run
    # checks + ratchets its BENCH_FLOOR row like bench.py's headline
    import jax

    from bench import perf_floor
    floor_info, _zero = perf_floor(
        out["engine"]["states_per_sec"], 0,
        str(jax.devices()[0].device_kind),
        os.path.join(os.path.dirname(OUT), "BENCH_FLOOR.json"),
        gate_ok=out["counts_match"], allow_bump=True,
        key=f"config{n}_budgeted", headline_depth=0,
        bump_source=f"measure_baseline.py config {n} auto-bump")
    out["engine"]["perf_floor"] = floor_info
    print(f"config {n} engine: {out['engine']} "
          f"match={out['counts_match']} speedup={out['speedup']}",
          flush=True)
    with open(os.path.join(OUT, f"config{n}.json"), "w") as f:
        json.dump(out, f, indent=1)
    return out


if __name__ == "__main__":
    from raft_tla_tpu.utils import enable_compilation_cache
    enable_compilation_cache()
    args = sys.argv[1:]
    reps = 3
    if "--reps" in args:
        i = args.index("--reps")
        reps = int(args[i + 1])
        del args[i:i + 2]
    if len(args) == 1:
        try:
            measure(int(args[0]), reps=reps)
        except Exception as e:
            print(f"config {args[0]} FAILED: {type(e).__name__}: {e}",
                  flush=True)
            raise SystemExit(1)
    else:
        # one subprocess per config: a failed/OOM'd engine run must not
        # pin HBM (exception tracebacks keep carry buffers alive) or
        # poison later configs
        import subprocess
        for n in [int(a) for a in args] or [1, 2, 3, 4, 5]:
            subprocess.run([sys.executable, os.path.abspath(__file__),
                            str(n), "--reps", str(reps)])
