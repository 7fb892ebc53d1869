"""Beyond-the-wall depth-exact runs with the host-spill engine
(VERDICT r3 #1 "Break the exhaustion wall").

Round 3 measured the wall: depth 19 (config #2) / depth 21 (config #1)
are the deepest level-exact runs whose buffers fit single-chip HBM, and
the native CPU checker OOMs the 125 GB host (~650 B/state) even
earlier, so NO checker in this environment can verify deeper counts.
The SpillEngine streams levels through host RAM (engine/spill), so its
depth wall is the visited table (12 B/key fp64, 20 B/key fp128)
instead of the level buffers.

Usage: python tools/deep_run.py CONFIG DEPTH [--spec raft|paxos]
       [--fp128] [--chunk N]
       [--seg N] [--vcap N] [--tag NAME] [--classic] [--lcap N]
       [--fcap N] [--native] [--budget N] [--ckpt FILE]
       [--resume FILE] [--ckpt-every N] [--ckpt-keep K]
       [--retries N] [--backoff S] [--chaos SPEC] [--host-table]
       [--partitions P] [--part-cap N] [--ledger FILE]
       [--heartbeat FILE] [--trace-timeline FILE] [--profile-dir DIR]
       [--registry DIR]

Fault tolerance (round 12, resil/): --retries N wraps the drive loop
in the supervised runner — a lost runtime triggers backend reinit +
resume from the newest valid member of the --ckpt chain (last
--ckpt-keep checkpoints, sha256 sidecars) with bounded exponential
backoff; attempts land in the ledger/heartbeat and tools/watch.py
shows the backoff state.  --chaos injects deterministic faults at the
named engine sites for recovery drills.

Observability (obs/): --ledger appends one JSONL record per dispatch
(flushed, so a lost connection keeps the telemetry up to the last
dispatch), --heartbeat atomically rewrites a watchdog file every
dispatch (tools/watch.py tails both), --trace-timeline writes the
host span timeline as Perfetto-loadable Chrome-trace JSON, and
--profile-dir captures an XLA device trace with matching
TraceAnnotation names.  --registry DIR appends one queryable record
per run (counters, span rollups, resource peaks, backend fingerprint)
that ``cli obs ls/show/diff/regress`` reads — the ROADMAP validation
rounds should attach --ledger/--heartbeat/--registry to every TPU run.

--host-table moves the visited set to fingerprint-prefix partitions in
host RAM (engine/host_table), streamed through HBM per level — the
depth wall becomes host RAM instead of the ~2^29-slot HBM table.
Checkpoints then carry the partition images (sparse, exact-image
restore) and --resume must repeat the same --host-table/--partitions;
the engine refuses a mismatched resume rather than drift.

--spec paxos runs the Paxos frontend instead of Raft: CONFIG then
selects a ladder of Paxos models (1 = N3/B2/V2/I1 stock, 2 = N3/B3/V2,
3 = N5/B2/V2, 4 = N3/B2/V2/I2) and --native is unavailable (the native
C++ checker is Raft-only).

--classic uses the in-HBM Engine instead of SpillEngine (for
depth-exact head-to-heads at depths that still fit); --native also
runs the native C++ checker at the same depth/budget and records the
speedup; --budget caps distinct states (level-granular, both engines)
for budget-exact rather than depth-exact comparisons.

Writes/merges baseline_runs/round4_deep.json:
  {"config2_depth21": {...}, "config2_depth21_fp128": {...}, ...}

Honesty note (BASELINE.md): counts at these depths cannot be checked
against the native checker or TLC on this machine — corroboration is a
second run with independent 128-bit fingerprints (--fp128), the same
cross-check round 3 recorded for the depth-19 row.
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

OUT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "baseline_runs", "round4_deep.json")


def main():
    from raft_tla_tpu.utils import enable_compilation_cache
    enable_compilation_cache()
    from raft_tla_tpu.engine.bfs import Engine
    from raft_tla_tpu.engine.spill import SpillEngine
    from tools.measure_baseline import build_cfg

    args = sys.argv[1:]
    conf_no = int(args.pop(0))
    depth = int(args.pop(0))
    flags = {f: f in args for f in ("--fp128", "--classic", "--native",
                                    "--host-table", "--no-burst",
                                    "--no-guard-matmul",
                                    "--no-delta-matmul")}
    for f, on in flags.items():
        if on:
            args.remove(f)
    fp128 = flags["--fp128"]
    host_table = flags["--host-table"]
    if host_table and flags["--classic"]:
        raise SystemExit("--host-table composes with the spill engine; "
                         "drop --classic")
    opts = dict(zip(args[::2], args[1::2]))
    known = {"--chunk", "--seg", "--vcap", "--budget", "--tag", "--lcap",
             "--fcap", "--ckpt", "--resume", "--ckpt-every",
             "--ckpt-keep", "--retries", "--backoff", "--chaos",
             "--partitions", "--part-cap", "--burst-levels",
             "--ledger", "--heartbeat", "--trace-timeline",
             "--profile-dir", "--registry",
             "--fam-cap-density", "--spec"}
    bad = set(opts) - known
    if bad or len(args) % 2:
        # fail loud: these depths cannot be cross-checked by any other
        # checker here, so a silently-ignored typo'd flag would record
        # an unverifiable row under the wrong parameters
        raise SystemExit(f"unknown/incomplete options: "
                         f"{sorted(bad) or args[-1:]} (known: "
                         f"{sorted(known)})")
    chunk = int(opts.get("--chunk", 4096))
    seg = int(opts.get("--seg", 1 << 22))
    vcap = int(opts.get("--vcap", 1 << 26))
    burst = not flags["--no-burst"]
    burst_levels = (int(opts["--burst-levels"])
                    if "--burst-levels" in opts else None)
    if burst_levels is not None and burst_levels <= 0:
        raise SystemExit(f"--burst-levels must be positive "
                         f"(got {burst_levels}); use --no-burst to "
                         "disable the fused-level path")
    budget = int(opts.get("--budget", 10 ** 9))
    partitions = int(opts.get("--partitions", 4))
    part_cap = int(opts.get("--part-cap", 1 << 16))
    guard_matmul = not flags["--no-guard-matmul"]
    spec = opts.get("--spec", "raft")
    if spec not in ("raft", "paxos"):
        raise SystemExit(f"--spec must be raft|paxos (got {spec})")
    fam_density = None
    if "--fam-cap-density" in opts:
        from raft_tla_tpu.engine.expand import parse_fam_density
        from raft_tla_tpu.spec import get_spec
        try:
            fam_density = parse_fam_density(opts["--fam-cap-density"],
                                            get_spec(spec))
        except ValueError as e:
            raise SystemExit(f"--fam-cap-density: {e}") from None
    mxu_kw = dict(guard_matmul=guard_matmul,
                  delta_matmul=not flags["--no-delta-matmul"],
                  fam_density=fam_density)
    tag = opts.get("--tag",
                   ("paxos_" if spec == "paxos" else "")
                   + f"config{conf_no}_depth{depth}"
                   + ("_fp128" if fp128 else "")
                   + ("_hosttable" if host_table else ""))

    if spec == "paxos":
        from raft_tla_tpu.spec.paxos.config import PaxosConfig
        ladder = {1: PaxosConfig(),
                  2: PaxosConfig(n_ballots=3),
                  3: PaxosConfig(n_servers=5),
                  4: PaxosConfig(n_instances=2)}
        if conf_no not in ladder:
            raise SystemExit(
                f"--spec paxos CONFIG must be one of "
                f"{sorted(ladder)} (got {conf_no})")
        if flags["--native"]:
            raise SystemExit("--native is raft-only (the native C++ "
                             "checker has no Paxos frontend)")
        cfg = ladder[conf_no]
    else:
        cfg = build_cfg(conf_no)
    if fp128:
        cfg = cfg.with_(fp128=True)
    nat_rec = None
    if flags["--native"]:
        from raft_tla_tpu import native
        nat_cfg = cfg.with_(invariants=()) if conf_no == 5 else cfg
        nat = native.check(nat_cfg, threads=os.cpu_count() or 1,
                           max_depth=depth, max_states=budget)
        nat_rec = {
            "distinct": int(nat.distinct_states),
            "depth": int(nat.depth),
            "seconds": round(nat.seconds, 2),
            "states_per_sec": round(nat.states_per_sec, 1)}
        print(json.dumps({"native": nat_rec}), flush=True)
    retries = int(opts.get("--retries", 0))
    backoff_s = float(opts.get("--backoff", 2.0))
    ckpt_keep = int(opts.get("--ckpt-keep", 2))
    if retries < 0 or backoff_s <= 0 or ckpt_keep < 1:
        raise SystemExit("--retries must be >= 0, --backoff > 0, "
                         "--ckpt-keep >= 1")
    if "--chaos" in opts:
        from raft_tla_tpu.resil.chaos import ChaosSpecError, install
        try:
            install(opts["--chaos"])
        except ChaosSpecError as e:
            raise SystemExit(str(e))

    def build_engine():
        if flags["--classic"]:
            eng = Engine(cfg, chunk=chunk, store_states=False,
                         vcap=vcap,
                         lcap=int(opts.get("--lcap", 1 << 21)),
                         fcap=int(opts["--fcap"]) if "--fcap" in opts
                         else None,
                         burst=burst, burst_levels=burst_levels,
                         **mxu_kw)
        else:
            eng = SpillEngine(cfg, chunk=chunk, store_states=False,
                              seg=seg, vcap=vcap,
                              host_table=host_table,
                              partitions=partitions,
                              part_cap=part_cap,
                              burst=burst, burst_levels=burst_levels,
                              **mxu_kw)
        eng.ckpt_keep = ckpt_keep
        return eng
    eng = build_engine()
    from raft_tla_tpu.obs import from_flags
    obs = from_flags(ledger=opts.get("--ledger"),
                     heartbeat=opts.get("--heartbeat"),
                     timeline=opts.get("--trace-timeline"),
                     profile_dir=opts.get("--profile-dir"),
                     registry=opts.get("--registry"),
                     run_info={"cmd": "deep_run", "cfg": repr(cfg)},
                     meta={"spec": eng.ir.name,
                           "ir_fingerprint": eng.ir.fingerprint()})
    obs.start()
    t0 = time.perf_counter()
    with obs.span("compile"):
        eng.check(max_depth=2)                   # warm the jit caches
    compile_s = time.perf_counter() - t0
    # checkpointing (VERDICT r4 #2): hours-scale runs on a remote
    # TPU die to dropped connections, not engine faults — a level-
    # boundary checkpoint + --resume makes the depth-21 fp128
    # corroboration protocol survivable
    ckpt = opts.get("--ckpt")
    resume = opts.get("--resume")
    resume_start = 0
    if resume:
        # the checkpoint's distinct count: post-resume throughput is
        # (delta states)/secs — cumulative/partial would inflate the
        # recorded rate ~10x on a late resume.  Read the same chain
        # member the engine will (a torn head falls back to FILE.1,
        # resil/ckpt_chain) — a bare head read here would traceback on
        # exactly the torn-write case the chain exists for
        from raft_tla_tpu.resil.ckpt_chain import latest_valid
        src = latest_valid(resume) or resume
        meta = json.loads(str(np.load(src)["meta"]))
        resume_start = int(meta["distinct"])
    t0 = time.perf_counter()
    # supervised drive loop (resil/supervisor): the first attempt uses
    # the already-warmed engine; retries rebuild it (backend reinit)
    # and resume from the newest valid member of the --ckpt chain
    from raft_tla_tpu.resil.supervisor import supervised_check
    _warm = [eng]

    def make_engine():
        return _warm.pop() if _warm else build_engine()
    try:
        r, eng, attempts = supervised_check(
            make_engine, retries=retries, backoff=backoff_s, obs=obs,
            checkpoint_path=ckpt, resume_from=resume,
            max_depth=depth, max_states=budget, verbose=True,
            checkpoint_every=int(opts.get("--ckpt-every", 1)))
    except BaseException:
        obs.finish(status="failed")
        raise
    secs = time.perf_counter() - t0
    obs.finish(depth=int(r.depth), states=int(r.distinct_states),
               counters=r.metrics.as_dict(),
               level_sizes=[int(x) for x in r.level_sizes])
    rec = {
        "engine": type(eng).__name__,
        "spec": eng.ir.name,
        "ir_fingerprint": eng.ir.fingerprint(),
        "config": conf_no, "max_depth": depth,
        "fp_bits": 128 if fp128 else 64,
        "distinct": int(r.distinct_states), "depth": int(r.depth),
        "depth_exact": budget >= 10 ** 9,
        # on a resumed run the wall/rate fields cover the POST-RESUME
        # portion only (counts stay cumulative); the row is labeled by
        # resumed_from_checkpoint below so it cannot pass for a
        # single-session wall measurement
        "seconds": round(secs, 2),
        "states_per_sec": round(
            (r.distinct_states - resume_start) / max(secs, 1e-9), 1),
        "compile_seconds": round(compile_s, 1),
        "level_sizes": [int(x) for x in r.level_sizes],
        "violations": len(r.violations),
        "overflow_faults": int(r.overflow_faults),
        "chunk": chunk, "seg": seg, "final_vcap": int(eng.VCAP),
        "host_table": host_table,
        # fused-dispatch telemetry: levels_fused > 0 proves the burst
        # engaged on the tiny early levels instead of silently bailing
        "burst": burst,
        "levels_fused": int(r.levels_fused),
        "burst_dispatches": int(r.burst_dispatches),
        "burst_bailouts": int(r.burst_bailouts),
        # MXU-path mode flags (round 9): which expansion program
        # produced this row
        "guard_matmul": int(r.guard_matmul),
        "delta_matmul": int(r.delta_matmul),
        "resumed_from_checkpoint": bool(resume),
        # supervised-retry provenance (round 12): a row produced over
        # several attempts is labeled; its wall/rate fields cover the
        # whole supervised session including backoff waits
        "retry_attempts": int(attempts),
        "expected_fp_collisions": float(
            r.distinct_states ** 2 /
            2.0 ** ((128 if fp128 else 64) + 1)),
    }
    # spill perf floor (VERDICT r4 #6): the canonical spill probe shape
    # (config #2, depth-exact 19, SpillEngine, single session) guards
    # the spill engine's rate the way bench.py guards the classic one
    if host_table:
        rec["partitions"] = partitions
        rec["host_table_keys"] = int(eng.hpt.n_keys)
        rec["host_table_bytes"] = int(eng.hpt.nbytes)
    # (host-table runs are rate-recorded but never floor-gate: the
    # canonical spill probe guards the default in-HBM-table path)
    if (spec == "raft" and not flags["--classic"] and conf_no == 2
            and depth == 19
            and rec["depth_exact"] and not fp128 and not resume
            and not host_table and attempts == 1):
        import jax

        from bench import perf_floor
        floor_info, _zero = perf_floor(
            rec["states_per_sec"], 0,
            str(jax.devices()[0].device_kind),
            os.path.join(os.path.dirname(os.path.dirname(OUT)),
                         "BENCH_FLOOR.json"),
            gate_ok=rec["violations"] == 0, allow_bump=True,
            key="spill_config2_depth19", headline_depth=0,
            bump_source="deep_run.py spill probe auto-bump")
        rec["perf_floor"] = floor_info
    if nat_rec is not None:
        rec["native"] = nat_rec
        rec["counts_match"] = (
            nat_rec["distinct"] == rec["distinct"]
            and nat_rec["depth"] == rec["depth"])
        rec["speedup"] = round(rec["states_per_sec"] /
                               max(nat_rec["states_per_sec"], 1e-9), 2)
    else:
        rec["note"] = ("no CPU checker on this host can reach this "
                       "depth (native OOMs ~65GB RSS; round3 "
                       "exhaustion probes)")
    data = {}
    if os.path.exists(OUT):
        data = json.load(open(OUT))
    data[tag] = rec
    # write-then-rename: an interrupted dump must not destroy earlier
    # recorded rows (these depths are unreproducible by other checkers)
    tmp = OUT + ".tmp"
    with open(tmp, "w") as f:
        json.dump(data, f, indent=1)
    os.replace(tmp, OUT)
    print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
