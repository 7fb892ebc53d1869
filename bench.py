"""Headline benchmark: distinct states/sec on the BASELINE.md metric
config (configs/config2/raft.cfg: the tlc_membership model at Server=3,
MaxTerm=3, MaxLogLen=3, ElectionSafety checked — BASELINE.json config
#2), on a TPU only.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "states/sec", "vs_baseline": N}

``vs_baseline`` compares the TPU engine against the repo's native C++
checker (native/raft_checker.cc) measured on this machine over the
SAME depth-exact run — the machine-measured stand-in for the
reference's "TLC -workers N" baseline (the reference publishes no
numbers — BASELINE.md).  Both engines run level-exact to depth 19
(7,619,299 states) and must land on the identical distinct-state
count.  The micro gate's model is /root/reference's tlc_membership
when present, else the repo-local twin under configs/.

Correctness gate: before timing, the engine is differentially checked
against the Python oracle on a micro config; a mismatch zeroes the
score (guards against accelerator-path miscompiles).

``perf_floor`` is the warn/hard regression-floor check over a floor
file (tools/measure_baseline.py and tools/deep_run.py pass one); no
floor file is kept in the repo until a chip run records one.
"""

import json
import os
import sys
import time

# Depth-exact headline: both engines run the full space to depth 19.
# Level-20 frontiers (~25M rows) exceed single-chip HBM — BASELINE.md.
MAX_DEPTH = 19
LCAP = 3 << 21            # ≥ the 5.18M-row depth-19 level, no growth
VCAP = 1 << 25            # 7.62M keys at a 23% load factor
CFG2 = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "configs", "config2", "raft.cfg")


def perf_floor(rate, max_depth, plat, floor_path, gate_ok=True,
               allow_bump=True, key="tlc_membership_S3_T3_L3",
               headline_depth=None, bump_source="bench.py auto-bump"):
    """Perf regression floor (VERDICT r3 #5, extended to per-config
    rows in r5 — VERDICT r4 #6; tests/test_bench.py).

    Returns (floor_info dict or None, zero_score bool).  Only applies
    to the recorded run shape on the recorded machine class — a
    shallower run pays proportionally more per-level dispatch/compile
    and its rate isn't comparable.  A new best (gate passing, >2% up)
    rewrites the floor file so the floor ratchets with the engine."""
    if headline_depth is None:
        headline_depth = MAX_DEPTH
    try:
        fl = json.load(open(floor_path))[key]
    except (OSError, KeyError, ValueError):
        return None, False
    if not str(plat).upper().startswith(fl["platform_prefix"].upper()):
        return {"status": f"skipped (platform {plat!r})"}, False
    if max_depth != headline_depth:
        return {"status": "skipped (non-headline depth)"}, False
    best = float(fl["best_states_per_sec"])
    warn, hard = best * fl["warn_frac"], best * fl["hard_frac"]
    status = ("ok" if rate >= warn else
              "warn" if rate >= hard else "hard")
    info = {"best_states_per_sec": best, "warn_below": round(warn, 1),
            "hard_below": round(hard, 1), "status": status}
    if allow_bump and gate_ok and rate > best * 1.02:
        data = json.load(open(floor_path))
        data[key]["best_states_per_sec"] = round(rate, 1)
        data[key]["source"] = bump_source
        # write-then-rename: a floor file truncated by a mid-dump kill
        # would silently DISABLE the regression gate on every later run
        # (the loader treats unreadable as no-floor)
        tmp = floor_path + ".tmp"
        with open(tmp, "w") as fh:
            json.dump(data, fh, indent=2)
        os.replace(tmp, floor_path)
    return info, status == "hard"


def _burst_ab(out_path):
    """Fused-dispatch A/B (tools/bench_sim.py idiom): the same micro
    space checked with the multi-level burst ON vs OFF, recording a
    dispatches-per-level metric — host level-sync round trips per BFS
    level, counting each burst device call (burst_dispatches counts
    every call, committing or bailing, as exactly one round trip) plus
    one per level the per-level driver ran.  This is the
    dispatch-floor metric the burst exists to cut (ROADMAP open items
    #3/#4: every sync has a fixed host cost).  Counts are
    correctness-gated: a mismatch labels the file failed.  On this
    CPU-only container the rows are an honest CPU fallback, exactly as
    BENCH_r06.json labels the sim figures — the dispatch COUNTS are
    platform-independent; only the seconds are not.

    Round 8: each run carries an obs SpanRecorder, and the row records
    ``phase_seconds`` (per-span totals: burst_dispatch /
    level_dispatch / harvest / archive_io / compile) so the A/B delta
    attributes to dispatch vs compute vs harvest instead of one
    end-to-end number — the file is the BENCH_r08 round."""
    import jax

    from raft_tla_tpu.config import Bounds, ModelConfig, NEXT_ASYNC
    from raft_tla_tpu.engine.bfs import Engine
    from raft_tla_tpu.obs import Obs, SpanRecorder

    micro = ModelConfig(
        n_servers=2, init_servers=(0, 1), values=(1,),
        next_family=NEXT_ASYNC, symmetry=True, max_inflight_override=4,
        bounds=Bounds.make(max_log_length=1, max_timeouts=1,
                           max_client_requests=1))
    rows, counts = {}, {}
    for label, burst in (("burst_off", False), ("burst_on", True)):
        eng = Engine(micro, chunk=256, store_states=False, burst=burst)
        rec = SpanRecorder()
        obs = Obs(spans=rec)
        with obs.span("compile"):
            eng.check(max_depth=2)               # warm the jit caches
        t0 = time.perf_counter()
        r = eng.check(obs=obs)
        secs = time.perf_counter() - t0
        level_syncs = r.burst_dispatches + (r.depth - r.levels_fused)
        rows[label] = {
            "distinct_states": int(r.distinct_states),
            "depth": int(r.depth),
            "levels_fused": int(r.levels_fused),
            "burst_dispatches": int(r.burst_dispatches),
            "burst_bailouts": int(r.burst_bailouts),
            "level_syncs": int(level_syncs),
            "dispatches_per_level": round(
                level_syncs / max(r.depth, 1), 3),
            "seconds": round(secs, 2),
            "states_per_sec": round(
                r.distinct_states / max(secs, 1e-9), 1),
            # per-phase span totals (obs/spans): the A/B delta
            # attributes to dispatch vs compute vs harvest
            "phase_seconds": {nm: t["seconds"]
                              for nm, t in rec.totals().items()},
            "phase_counts": {nm: t["count"]
                             for nm, t in rec.totals().items()},
        }
        counts[label] = (r.distinct_states, r.depth,
                         tuple(r.level_sizes))
    identical = counts["burst_on"] == counts["burst_off"]
    out = {
        "bench": "fused multi-level dispatch A/B with per-phase span "
                 "totals (bench.py, BENCH_r08 round)",
        "platform": jax.default_backend(),
        "honest_label": (
            "CPU-only fallback: this container has no TPU; the "
            "dispatch/level counts are platform-independent, the "
            "seconds are XLA:CPU" if jax.default_backend() == "cpu"
            else "TPU-measured"),
        "status": ("ok" if identical else
                   "FAILED: burst counts diverge from the per-level "
                   "driver — the perf rows are meaningless"),
        "counts_identical": identical,
        "rows": rows,
    }
    tmp = out_path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(out, fh, indent=1)
    os.replace(tmp, out_path)
    return out


def _matmul_ab(out_path):
    """MXU-native expansion A/B (BENCH round 9): the same micro space
    checked with guard_matmul ON (guard grid as int8 matmul + one-hot
    successor einsum) vs OFF (the historical vmapped lane sweep),
    counts correctness-gated identical, each run carrying the PR-7
    span recorder so the end-to-end delta attributes per phase.

    On top of the end-to-end rows, a STANDALONE micro-phase times the
    replaced primitive directly (the engine fuses it inside one jit,
    so per-phase wall-clock needs standalone dispatch): ``guard_matmul``
    vs ``guard_lanes`` spans — the [B, A] guard grid via the packed
    int8 matmul vs the vmapped per-lane sweep, jitted, on a batch of
    reachable states.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from raft_tla_tpu.config import Bounds, ModelConfig
    from raft_tla_tpu.engine.bfs import Engine
    from raft_tla_tpu.obs import Obs, SpanRecorder

    micro = ModelConfig(
        n_servers=2, init_servers=(0, 1), values=(1,),
        symmetry=True, max_inflight_override=4,
        bounds=Bounds.make(max_log_length=1, max_timeouts=1,
                           max_client_requests=1))
    rows, counts = {}, {}
    engines = {}
    for label, gm in (("guard_matmul_off", False),
                      ("guard_matmul_on", True)):
        eng = engines[label] = Engine(micro, chunk=256,
                                      store_states=False,
                                      guard_matmul=gm)
        rec = SpanRecorder()
        obs = Obs(spans=rec)
        with obs.span("compile"):
            eng.check(max_depth=2)               # warm the jit caches
        t0 = time.perf_counter()
        r = eng.check(obs=obs)
        secs = time.perf_counter() - t0
        rows[label] = {
            "distinct_states": int(r.distinct_states),
            "depth": int(r.depth),
            "guard_matmul": int(r.guard_matmul),
            "levels_fused": int(r.levels_fused),
            "seconds": round(secs, 2),
            "states_per_sec": round(
                r.distinct_states / max(secs, 1e-9), 1),
            "phase_seconds": {nm: t["seconds"]
                              for nm, t in rec.totals().items()},
            "phase_counts": {nm: t["count"]
                             for nm, t in rec.totals().items()},
        }
        counts[label] = (r.distinct_states, r.depth,
                         tuple(r.level_sizes))
    identical = counts["guard_matmul_on"] == counts["guard_matmul_off"]

    # ---- standalone guard-pass micro-phase ---------------------------
    from raft_tla_tpu.models.explore import explore
    from raft_tla_tpu.ops.codec import encode, widen
    from raft_tla_tpu.ops.layout import Layout
    lay = Layout(micro)
    st = list(explore(micro, max_states=1024,
                      keep_states=True).states.values())[:256]
    batch = widen({k: np.stack([encode(lay, sv, h)[k]
                                for sv, h in st])
                   for k in encode(lay, *st[0])})
    svT = {k: jnp.moveaxis(jnp.asarray(v), 0, -1)
           for k, v in batch.items()}
    ex_on = engines["guard_matmul_on"].expander
    ex_off = engines["guard_matmul_off"].expander
    derT = jax.jit(ex_on.derived_batch_T)(svT)
    f_on = jax.jit(ex_on.guards_T_matmul)
    f_off = jax.jit(lambda s, d: ex_off.guards_T(s, d))
    ok_a = np.asarray(f_on(svT, derT))           # warm + correctness
    ok_b = np.asarray(f_off(svT, derT))
    guards_identical = bool((ok_a == ok_b).all())
    rec2 = SpanRecorder()
    REPS = 20
    with rec2.span("guard_matmul"):
        for _ in range(REPS):
            f_on(svT, derT)[0].block_until_ready()
    with rec2.span("guard_lanes"):
        for _ in range(REPS):
            f_off(svT, derT)[0].block_until_ready()

    micro_phase = {nm: {"seconds": t["seconds"], "count": t["count"]}
                   for nm, t in rec2.totals().items()}

    plat = jax.default_backend()
    out = {
        "bench": "MXU-native expansion A/B with per-phase span totals "
                 "(bench.py, BENCH_r09 round)",
        "platform": plat,
        "honest_label": (
            "CPU-only fallback: this container has no TPU — the count/"
            "outcome identities are platform-independent; the seconds "
            "are XLA:CPU" if plat == "cpu" else "TPU-measured"),
        "status": ("ok" if identical and guards_identical else
                   "FAILED: guard-matmul path diverges from the lane "
                   "path — the perf rows are meaningless"),
        "counts_identical": identical,
        "guard_grid_identical": guards_identical,
        "rows": rows,
        "micro_phase_spans": micro_phase,
        "micro_phase_note": (
            "guard_matmul/guard_lanes: 20 jitted dispatches of the "
            "[256-state x lane-grid] guard pass each"),
    }
    tmp = out_path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(out, fh, indent=1)
    os.replace(tmp, out_path)
    return out


def _delta_ab(out_path):
    """Delta-matmul successor-generation A/B (BENCH round 11, ROADMAP
    item 3): the same micro space checked with delta_matmul ON (every
    declared family applies as ONE batched scatter-as-matmul per
    family group) vs OFF (the per-family vmapped kernels), counts
    correctness-gated identical for raft AND paxos — the paxos pair
    doubles as the zero-new-kernels proof (all four families run from
    declarations alone).

    On top of the end-to-end rows, a STANDALONE expansion-phase
    micro-pair times the replaced primitive directly on config #2's
    lane mix (the engines fuse materialize inside one jit, so
    per-phase wall-clock needs standalone dispatch):

    - ``delta_apply`` — jitted ``Expander.materialize`` with the group
      delta matmul compiled (int32 einsum blocks);
    - ``delta_kernels`` — the identical call with the per-family
      kernel path.

    Off-TPU the einsum blocks run on XLA:CPU — the seconds measure the
    fallback, not the matrix unit; the row is labeled honestly and the
    candidate-buffer identity is the platform-independent part.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from raft_tla_tpu.config import Bounds, ModelConfig
    from raft_tla_tpu.cfg.parser import load_model
    from raft_tla_tpu.engine.bfs import Engine
    from raft_tla_tpu.engine.expand import Expander
    from raft_tla_tpu.obs import Obs, SpanRecorder
    from raft_tla_tpu.spec import get_spec
    from raft_tla_tpu.spec.paxos.config import PaxosConfig

    micro = ModelConfig(
        n_servers=2, init_servers=(0, 1), values=(1,),
        symmetry=True, max_inflight_override=4,
        bounds=Bounds.make(max_log_length=1, max_timeouts=1,
                           max_client_requests=1))
    rows, counts = {}, {}
    for label, dm in (("delta_matmul_off", False),
                      ("delta_matmul_on", True)):
        eng = Engine(micro, chunk=256, store_states=False,
                     delta_matmul=dm)
        rec = SpanRecorder()
        obs = Obs(spans=rec)
        with obs.span("compile"):
            eng.check(max_depth=2)               # warm the jit caches
        t0 = time.perf_counter()
        r = eng.check(obs=obs)
        secs = time.perf_counter() - t0
        rows[label] = {
            "distinct_states": int(r.distinct_states),
            "depth": int(r.depth),
            "delta_matmul": int(r.delta_matmul),
            "seconds": round(secs, 2),
            "states_per_sec": round(
                r.distinct_states / max(secs, 1e-9), 1),
            "phase_seconds": {nm: t["seconds"]
                              for nm, t in rec.totals().items()},
        }
        counts[label] = (r.distinct_states, r.depth,
                         tuple(r.level_sizes))
    identical = counts["delta_matmul_on"] == counts["delta_matmul_off"]

    # paxos end-to-end pair: declarations-only expansion, full space
    pax_counts = {}
    for label, dm in (("off", False), ("on", True)):
        r = Engine(PaxosConfig(), chunk=128, store_states=False,
                   delta_matmul=dm).check()
        pax_counts[label] = (r.distinct_states, r.depth,
                            tuple(r.level_sizes))
    pax_identical = pax_counts["on"] == pax_counts["off"]

    # ---- standalone expansion-phase micro-pair (config #2 lane mix) --
    # the repo-local cfg twin + config #2's bounds reproduce the
    # headline config's LANE GRID exactly; the batch is depth-limited
    # reachable states (the phase timing needs the mix, not the space)
    cfg2 = load_model(CFG2, bounds=Bounds.make(
        max_log_length=3, max_timeouts=2, max_client_requests=3))
    ir = get_spec("raft")
    lay = ir.make_layout(cfg2)
    st = list(ir.oracle_explore(cfg2, max_states=1024,
                                keep_states=True).states.values())[:256]
    enc = [ir.encode(lay, sv, h) for sv, h in st]    # encode each ONCE
    batch = ir.widen({k: np.stack([e[k] for e in enc])
                      for k in enc[0]})
    svT = {k: jnp.moveaxis(jnp.asarray(v), 0, -1)
           for k, v in batch.items()}
    ex_on = Expander(cfg2, delta_matmul=True)
    ex_off = Expander(cfg2, delta_matmul=False)
    derT = jax.jit(ex_on.derived_batch_T)(svT)
    ok = np.asarray(jax.jit(ex_on.guards_T)(svT, derT))
    B = ok.shape[0]
    okf = jnp.asarray(ok.reshape(-1))
    FCAP = int(ok.sum()) + 8
    epos = jnp.where(okf, jnp.cumsum(okf.astype(jnp.int32)) - 1, FCAP)
    caps = ex_on.default_fam_caps(B)
    f_on = jax.jit(lambda s, d: ex_on.materialize(
        s, d, okf, epos, FCAP, caps))
    f_off = jax.jit(lambda s, d: ex_off.materialize(
        s, d, okf, epos, FCAP, caps))
    c_on, _x1 = f_on(svT, derT)                  # warm + correctness
    c_off, _x2 = f_off(svT, derT)
    n_e = int(ok.sum())
    cands_identical = all(
        np.array_equal(np.asarray(c_on[k])[..., :n_e],
                       np.asarray(c_off[k])[..., :n_e])
        for k in c_on)
    rec2 = SpanRecorder()
    REPS = 10
    with rec2.span("delta_apply"):
        for _ in range(REPS):
            f_on(svT, derT)[0]["ctr"].block_until_ready()
    with rec2.span("delta_kernels"):
        for _ in range(REPS):
            f_off(svT, derT)[0]["ctr"].block_until_ready()
    micro_phase = {nm: {"seconds": t["seconds"], "count": t["count"]}
                   for nm, t in rec2.totals().items()}

    plat = jax.default_backend()
    ok_all = identical and pax_identical and cands_identical
    out = {
        "bench": "delta-matmul successor generation A/B with "
                 "expansion-phase span totals (bench.py, BENCH_r11 "
                 "round)",
        "platform": plat,
        "honest_label": (
            "CPU-only fallback: this container has no TPU — the "
            "count/candidate identities are platform-independent; the "
            "delta_apply seconds time the off-TPU lowering (static "
            "gathers + segment scatter-add, bit-identical buffers), "
            "NOT the MXU einsum blocks a TPU runs"
            if plat == "cpu" else "TPU-measured"),
        "status": ("ok" if ok_all else
                   "FAILED: delta-matmul path diverges from the "
                   "kernel path — the perf rows are meaningless"),
        "counts_identical": identical,
        "paxos_counts_identical": pax_identical,
        "paxos_zero_new_kernels": True,
        "candidates_identical": cands_identical,
        "delta_families_raft": list(ex_on.delta_family_names),
        "rows": rows,
        "expansion_phase_spans": micro_phase,
        "expansion_phase_note": (
            f"delta_apply/delta_kernels: {REPS} jitted materialize "
            f"dispatches each over a 256-state reachable batch on "
            f"config #2's lane mix ({ex_on.n_lanes} lanes, "
            f"{n_e} enabled)"),
    }
    tmp = out_path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(out, fh, indent=1)
    os.replace(tmp, out_path)
    return out


def _batch_ab(out_path):
    """Multi-tenant batch A/B (BENCH round 10, ROADMAP 2b): K=4 small
    jobs — the same micro config under four different depth gates, the
    serving layer's bread-and-butter repeat-tenant shape — run
    sequentially (one engine per job: K compiles, K dispatch chains)
    vs batched (ONE bucket engine, ONE job-vmapped device program,
    per-job state on a leading [J] axis).  Records compile count,
    dispatch count and wall-clock per job for both modes under the
    shared correctness gate: every per-job result must be identical
    across modes or the file is labeled FAILED and the headline gate
    trips.  On this CPU-only container the rows are an honest CPU
    fallback (the compile/dispatch COUNTS are platform-independent;
    the seconds are XLA:CPU), as in BENCH_r05-r09."""
    import jax

    from raft_tla_tpu.config import Bounds, ModelConfig, NEXT_ASYNC
    from raft_tla_tpu.obs import Obs, SpanRecorder
    from raft_tla_tpu.serve import Job, run_jobs

    micro = ModelConfig(
        n_servers=2, init_servers=(0, 1), values=(1,),
        next_family=NEXT_ASYNC, symmetry=True, max_inflight_override=4,
        bounds=Bounds.make(max_log_length=1, max_timeouts=1,
                           max_client_requests=1))
    DEPTHS = (3, 4, 5, 6)
    K = len(DEPTHS)

    def mk_jobs():
        return [Job(micro, max_depth=d, label=f"d{d}") for d in DEPTHS]

    rows, per_job, raw_secs = {}, {}, {}
    for label, seq in (("sequential", True), ("batched", False)):
        rec = SpanRecorder()
        t0 = time.perf_counter()
        rep = run_jobs(mk_jobs(), obs=Obs(spans=rec), sequential=seq)
        secs = raw_secs[label] = time.perf_counter() - t0
        per_job[label] = {
            o.job.label: (int(o.res.distinct_states),
                          int(o.res.generated_states),
                          int(o.res.depth),
                          tuple(int(x) for x in o.res.level_sizes))
            for o in rep.outcomes}
        device_dispatches = sum(
            int(o.res.burst_dispatches) +
            (int(o.res.depth) - int(o.res.levels_fused))
            for o in rep.outcomes) if seq else \
            rep.meta["batch_dispatches"]
        rows[label] = {
            "jobs": K,
            "engines_compiled": rep.meta["engines_compiled"],
            "device_dispatches": int(device_dispatches),
            "seconds": round(secs, 2),
            "seconds_per_job": round(secs / K, 2),
            "statuses": [o.status for o in rep.outcomes],
            "phase_seconds": {nm: t["seconds"]
                              for nm, t in rec.totals().items()},
            "phase_counts": {nm: t["count"]
                             for nm, t in rec.totals().items()},
        }
    identical = per_job["sequential"] == per_job["batched"]
    all_batched = all(s == "done"
                      for s in rows["batched"]["statuses"])
    # raw timings, not the 2-decimal display rounding in the rows
    speedup = raw_secs["sequential"] / max(raw_secs["batched"], 1e-9)
    out = {
        "bench": "multi-tenant batch A/B: K=4 small jobs sequential "
                 "vs one job-vmapped device program (bench.py, "
                 "BENCH_r10 round)",
        "platform": jax.default_backend(),
        "honest_label": (
            "CPU-only fallback: this container has no TPU; the "
            "compile/dispatch counts and result identities are "
            "platform-independent, the seconds are XLA:CPU"
            if jax.default_backend() == "cpu" else "TPU-measured"),
        "status": ("ok" if identical and all_batched else
                   "FAILED: batched per-job results diverge from the "
                   "sequential engines (or jobs fell back) — the perf "
                   "rows are meaningless"),
        "results_identical": identical,
        "all_jobs_batched": all_batched,
        "per_job_speedup": round(speedup, 2),
        "rows": rows,
        "per_job_counts": {lbl: list(v) for lbl, v in
                           per_job["batched"].items()},
    }
    tmp = out_path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(out, fh, indent=1)
    os.replace(tmp, out_path)
    return out


def _ceiling_ab(out_path):
    """Constant-ceiling serving A/B (BENCH round 12, ROADMAP item 1):
    K=4 raft jobs with DISTINCT value bounds (max_timeouts ×
    max_log_length at depth 13 — each job's reachable count differs,
    so the runtime-bounds machinery is provably live, not coincidence)
    run sequentially (K engines, K compiles) vs through ONE padded
    bucket ceiling (one engine, ONE ``bucket_compile``, per-job guard
    thresholds/lane masks/bounds as vmapped device data).  Before
    round 13 this exact job list compiled K separate buckets — the
    heterogeneous traffic missed the bucket cache entirely.

    Correctness gate: every job's (counts, level sizes) must be
    identical across modes AND the four jobs' counts must be four
    DIFFERENT numbers; otherwise the file is labeled FAILED and the
    headline gate trips.  CPU fallback labeling as in BENCH_r05+."""
    import jax

    from raft_tla_tpu.config import Bounds, ModelConfig, NEXT_ASYNC
    from raft_tla_tpu.obs import Obs, SpanRecorder
    from raft_tla_tpu.serve import Job, run_jobs
    from raft_tla_tpu.spec import spec_of

    BOUNDS = ((1, 1), (1, 2), (2, 1), (2, 2))
    K = len(BOUNDS)
    cfgs = [ModelConfig(
        n_servers=2, init_servers=(0, 1), values=(1,),
        next_family=NEXT_ASYNC, symmetry=True,
        max_inflight_override=4,
        bounds=Bounds.make(max_log_length=m, max_timeouts=t,
                           max_client_requests=2))
        for m, t in BOUNDS]
    n_ceilings = len({repr(spec_of(c).serve_bucket(c)[0])
                      for c in cfgs})

    def mk_jobs():
        return [Job(c, max_depth=13, label=f"b{m}x{t}")
                for c, (m, t) in zip(cfgs, BOUNDS)]

    rows, per_job, raw_secs = {}, {}, {}
    for label, seq in (("sequential", True), ("bucketed", False)):
        rec = SpanRecorder()
        t0 = time.perf_counter()
        rep = run_jobs(mk_jobs(), obs=Obs(spans=rec), sequential=seq)
        secs = raw_secs[label] = time.perf_counter() - t0
        per_job[label] = {
            o.job.label: (int(o.res.distinct_states),
                          int(o.res.generated_states),
                          int(o.res.depth),
                          tuple(int(x) for x in o.res.level_sizes))
            for o in rep.outcomes}
        rows[label] = {
            "jobs": K,
            "engines_compiled": rep.meta["engines_compiled"],
            "buckets": rep.meta.get("buckets", 0),
            "seconds": round(secs, 2),
            "seconds_per_job": round(secs / K, 2),
            "statuses": [o.status for o in rep.outcomes],
            "phase_seconds": {nm: t["seconds"]
                              for nm, t in rec.totals().items()},
            "phase_counts": {nm: t["count"]
                             for nm, t in rec.totals().items()},
        }
    identical = per_job["sequential"] == per_job["bucketed"]
    counts = [v[0] for v in per_job["bucketed"].values()]
    discriminated = len(set(counts)) == K
    all_bucketed = all(s == "done"
                       for s in rows["bucketed"]["statuses"])
    one_compile = (n_ceilings == 1 and
                   rows["bucketed"]["engines_compiled"] == 1 and
                   rows["bucketed"]["phase_counts"].get(
                       "bucket_compile", 0) == 1)
    ok = identical and discriminated and all_bucketed and one_compile
    speedup = raw_secs["sequential"] / max(raw_secs["bucketed"], 1e-9)
    out = {
        "bench": "constant-ceiling serving A/B: K=4 heterogeneous-"
                 "bounds jobs sequential vs ONE padded bucket ceiling "
                 "(bench.py, BENCH_r12 round)",
        "platform": jax.default_backend(),
        "honest_label": (
            "CPU-only fallback: this container has no TPU; the "
            "compile counts, bucket-hit behavior and result "
            "identities are platform-independent, the seconds are "
            "XLA:CPU"
            if jax.default_backend() == "cpu" else "TPU-measured"),
        "status": ("ok" if ok else
                   "FAILED: padded-ceiling per-job results diverge "
                   "from the sequential engines, do not discriminate "
                   "by bounds, or compiled more than once — the perf "
                   "rows are meaningless"),
        "results_identical": identical,
        "bounds_discriminate": discriminated,
        "all_jobs_bucketed": all_bucketed,
        "one_bucket_one_compile": one_compile,
        "engines_compiled": {lbl: rows[lbl]["engines_compiled"]
                             for lbl in rows},
        "per_job_speedup": round(speedup, 2),
        "rows": rows,
        "per_job_counts": {lbl: list(v) for lbl, v in
                           per_job["bucketed"].items()},
    }
    tmp = out_path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(out, fh, indent=1)
    os.replace(tmp, out_path)
    return out


def _canon_ab(out_path):
    """Orbit-sort canonicalization A/B (BENCH round 14 file, repo
    round 15): the config-#5 SHAPE — S=5 all-init, full S_5 symmetry,
    P=120 — checked depth-capped with ``--sym-canon sort`` (ONE
    argsorted canonical relabeling hashed per state, adjacent-
    transposition certificates, rare min-over-perms fallback) vs
    ``minperm`` (the historical P-fold min).  Counts must be
    bit-identical — the orbit partitions are provably equal, so any
    divergence is a miscompile and the file is FAILED.

    On top of the end-to-end rows, a STANDALONE fingerprint-phase
    micro-pair times the replaced primitive directly (the engine fuses
    hashing inside one jit, so per-phase wall-clock needs standalone
    dispatch): ``canon_sort`` vs ``canon_minperm`` — jitted
    ``fingerprint_batch_T`` over the same reachable 256-state batch.
    The partition induced by the two modes' values must be identical
    (the VALUES themselves differ by design: the sort hash is salted
    into a disjoint codomain so cross-mode tables can never alias).
    At P=120 the sort path does ~1 hash + 1 argsort + S-1 certificate
    probes where minperm does 120 masked hashes; the round claims
    >=3x on this phase and the file records whether the claim held.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from raft_tla_tpu.config import Bounds, ModelConfig, NEXT_ASYNC
    from raft_tla_tpu.engine.bfs import Engine
    from raft_tla_tpu.engine.fingerprint import Fingerprinter
    from raft_tla_tpu.models.explore import explore
    from raft_tla_tpu.obs import Obs, SpanRecorder
    from raft_tla_tpu.ops.codec import encode, widen
    from raft_tla_tpu.ops.layout import Layout

    cfg5 = ModelConfig(
        n_servers=5, init_servers=(0, 1, 2, 3, 4), values=(1,),
        next_family=NEXT_ASYNC, symmetry=True, max_inflight_override=4,
        bounds=Bounds.make(max_log_length=2, max_timeouts=1,
                           max_client_requests=1))
    DEPTH = 4
    rows, counts = {}, {}
    for label, mode in (("minperm", "minperm"), ("sort", "sort")):
        eng = Engine(cfg5, chunk=256, store_states=False,
                     sym_canon=mode)
        rec = SpanRecorder()
        obs = Obs(spans=rec)
        with obs.span("compile"):
            eng.check(max_depth=2)               # warm the jit caches
        t0 = time.perf_counter()
        r = eng.check(max_depth=DEPTH, obs=obs)
        secs = time.perf_counter() - t0
        rows[label] = {
            "distinct_states": int(r.distinct_states),
            "depth": int(r.depth),
            "sym_canon": int(r.sym_canon),
            "seconds": round(secs, 2),
            "states_per_sec": round(
                r.distinct_states / max(secs, 1e-9), 1),
            "phase_seconds": {nm: t["seconds"]
                              for nm, t in rec.totals().items()},
        }
        counts[label] = (r.distinct_states, r.generated_states,
                         r.depth, tuple(r.level_sizes))
    identical = counts["sort"] == counts["minperm"]
    flags_ok = (rows["sort"]["sym_canon"] == 1 and
                rows["minperm"]["sym_canon"] == 0)

    # ---- standalone fingerprint-phase micro-pair ---------------------
    lay = Layout(cfg5)
    st = list(explore(cfg5, max_states=2048,
                      keep_states=True).states.values())[:512]
    batch = widen({k: np.stack([encode(lay, sv, h)[k]
                                for sv, h in st])
                   for k in encode(lay, *st[0])})
    svT = {k: jnp.moveaxis(jnp.asarray(v), 0, -1)
           for k, v in batch.items()}
    fprs = {m: Fingerprinter(cfg5, sym_canon=m)
            for m in ("sort", "minperm")}
    fns = {m: jax.jit(f.fingerprint_batch_T) for m, f in fprs.items()}
    fp = {m: np.asarray(fn(svT)) for m, fn in fns.items()}   # warm

    def gids(a):
        """[n_streams, B] values -> first-occurrence group ids: the
        induced partition, comparable across disjoint codomains."""
        seen = {}
        return [seen.setdefault(tuple(int(a[t, b])
                                      for t in range(a.shape[0])), b)
                for b in range(a.shape[1])]

    partition_identical = gids(fp["sort"]) == gids(fp["minperm"])
    hard = fprs["sort"].sort_debug(batch)["hard"]
    rec2 = SpanRecorder()
    REPS = 20
    phase_secs = {}
    for m in ("sort", "minperm"):
        with rec2.span(f"canon_{m}"):
            for _ in range(REPS):
                fns[m](svT)[0].block_until_ready()
        phase_secs[m] = rec2.totals()[f"canon_{m}"]["seconds"]
    speedup = phase_secs["minperm"] / max(phase_secs["sort"], 1e-9)
    speedup_3x = speedup >= 3.0

    plat = jax.default_backend()
    ok = identical and flags_ok and partition_identical and speedup_3x
    out = {
        "bench": "orbit-sort canonicalization A/B: one argsorted "
                 "canonical hash vs the P=120 min-over-perms "
                 "(bench.py, BENCH_r14 round)",
        "platform": plat,
        "honest_label": (
            "CPU-only fallback: this container has no TPU — the "
            "count/partition identities are platform-independent; the "
            "canon_sort seconds time XLA:CPU's argsort+gather "
            "lowering, NOT the TPU sort/gather units, so the phase "
            "ratio is the fallback's, measured against the same "
            "fallback's 120 masked hashes"
            if plat == "cpu" else "TPU-measured"),
        "status": ("ok" if ok else
                   "FAILED: sort-mode counts/partition diverge from "
                   "min-over-perms (or the claimed fingerprint-phase "
                   "speedup did not hold) — the perf rows are "
                   "meaningless"),
        "counts_identical": identical,
        "mode_flags_stamped": flags_ok,
        "partition_identical": partition_identical,
        "perm_group_size": len(fprs["minperm"].sigmas),
        "hard_fallback_rate": round(float(np.mean(hard)), 4),
        "fingerprint_phase_seconds": {
            m: round(s, 4) for m, s in phase_secs.items()},
        "fingerprint_phase_speedup": round(speedup, 2),
        "speedup_at_least_3x": speedup_3x,
        "fingerprint_phase_note": (
            f"canon_sort/canon_minperm: {REPS} jitted "
            "fingerprint_batch_T dispatches each over the same "
            "512-state reachable batch at S=5, P=120"),
        "rows": rows,
    }
    tmp = out_path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(out, fh, indent=1)
    os.replace(tmp, out_path)
    return out


def _wave_mesh_ab(out_path):
    """Mesh-sharded serving wave A/B (BENCH_r15, round 16): the SAME
    6-job raft wave through ``cli batch`` on ONE device vs a 4-virtual-
    device job mesh (``--wave-mesh 4``), under the shared correctness
    gate (per-job counts/level sizes bit-identical across modes, or
    the file is FAILED).

    Subprocess runs, not in-process: the job mesh needs >1 local
    device and this process's jax initialized with the default 1 —
    both runs force ``--xla_force_host_platform_device_count=4`` so
    the device count itself is identical and only ``--wave-mesh``
    differs.  Both record into one ``--registry``, so the A/B is an
    ``obs diff`` verdict (clean = identical counts) and the rows carry
    the records' ``batched_dispatch`` span totals plus per-job wall
    seconds from ``--stats-json``.

    Honest CPU-fallback label: 4 virtual CPU devices share the SAME
    physical cores, so the mesh row's seconds measure sharding
    overhead, not speedup — the throughput claim (D devices x 8 lanes
    per dispatch) is a TPU-slice measurement; what this file pins on
    every container is bit-exactness, occupancy accounting and the
    dispatch-count invariance."""
    import shutil
    import subprocess
    import tempfile

    import jax

    from raft_tla_tpu.obs.registry import RunRegistry
    from raft_tla_tpu.obs.report import diff_runs

    repo = os.path.dirname(os.path.abspath(__file__))
    tmp = tempfile.mkdtemp(prefix="wave_mesh_ab_")
    jobs_path = os.path.join(tmp, "jobs.jsonl")
    with open(jobs_path, "w") as fh:
        for d in (3, 4, 5, 6, 7, 8):
            fh.write(json.dumps({
                "spec": "raft",
                "config": "configs/tlc_membership/raft.cfg",
                "overrides": {
                    "servers": 2, "values": [1], "max_inflight": 4,
                    "next": "NextAsync",
                    "bounds": {"max_log_length": 1, "max_timeouts": 1,
                               "max_client_requests": 1}},
                "max_depth": d, "label": f"r{d}"}) + "\n")
    registry = os.path.join(tmp, "registry")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                          " --xla_force_host_platform_device_count=4"
                          ).strip())
    rows, keys, run_ids = {}, {}, {}
    try:
        for label, mesh in (("single_device", "off"),
                            ("mesh_4dev", "4")):
            stats = os.path.join(tmp, label + ".json")
            t0 = time.perf_counter()
            p = subprocess.run(
                [sys.executable, "-m", "raft_tla_tpu", "batch",
                 "--jobs", jobs_path, "--wave-mesh", mesh,
                 "--stats-json", stats, "--registry", registry],
                capture_output=True, text=True, cwd=repo, env=env,
                timeout=900)
            wall = time.perf_counter() - t0
            if p.returncode != 0:
                out = {"bench": "mesh-sharded serving wave A/B "
                                "(bench.py, BENCH_r15 round)",
                       "status": f"FAILED: cli batch --wave-mesh "
                                 f"{mesh} exited {p.returncode}: "
                                 f"{p.stderr[-500:]}"}
                tmpf = out_path + ".tmp"
                with open(tmpf, "w") as fh:
                    json.dump(out, fh, indent=1)
                os.replace(tmpf, out_path)
                return out
            with open(stats) as fh:
                payload = json.load(fh)
            summary, jrows = payload["summary"], payload["jobs"]
            keys[label] = tuple(
                (r["label"], r["distinct_states"],
                 r["generated_states"], r["depth"],
                 tuple(r["level_sizes"])) for r in jrows)
            reg = RunRegistry(registry)
            fresh = [i for i in reg.run_ids()
                     if i not in run_ids.values()]
            run_ids[label] = fresh[-1]
            rec = reg.load(run_ids[label])
            spans = rec.get("spans") or {}
            disp = spans.get("batched_dispatch") or {}
            rows[label] = {
                "run_id": run_ids[label],
                "wall_seconds": round(wall, 2),
                "wave_devices": int(summary.get("wave_devices", 0)),
                "wave_lanes": int(summary.get("wave_lanes", 0)),
                "batch_dispatches":
                    int(summary.get("batch_dispatches", 0)),
                "batched_dispatch_span": {
                    "count": int(disp.get("count", 0)),
                    "seconds": round(float(disp.get("seconds", 0.0)),
                                     4)},
                "bucket_compile_seconds": round(float(
                    (spans.get("bucket_compile") or {})
                    .get("seconds", 0.0)), 4),
                "per_job_seconds": {
                    r["label"]: round(float(r.get("seconds", 0.0)), 4)
                    for r in jrows},
            }
        reg = RunRegistry(registry)
        diff = diff_runs(reg.load(run_ids["single_device"]),
                         reg.load(run_ids["mesh_4dev"]))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    identical = len(set(keys.values())) == 1
    occupancy_ok = (rows["mesh_4dev"]["wave_devices"] == 4 and
                    rows["single_device"]["wave_devices"] == 1 and
                    rows["mesh_4dev"]["batch_dispatches"] ==
                    rows["single_device"]["batch_dispatches"])
    diff_ok = diff["verdict"] in ("clean", "mode_drift")
    ok = identical and occupancy_ok and diff_ok
    out = {
        "bench": "mesh-sharded serving wave A/B: one 6-job raft wave, "
                 "--wave-mesh off vs 4 virtual devices (bench.py, "
                 "BENCH_r15 round)",
        "platform": jax.default_backend(),
        "honest_label": (
            "CPU-only fallback: the 4 'devices' are virtual XLA:CPU "
            "devices on the SAME physical cores, so the mesh row's "
            "seconds measure GSPMD sharding overhead, not speedup — "
            "the D-devices-x-8-lanes throughput multiplier is a TPU-"
            "slice measurement; bit-exactness, wave occupancy "
            "accounting and dispatch-count invariance are the "
            "platform-independent content"
            if jax.default_backend() == "cpu" else "TPU-measured"),
        "status": ("ok" if ok else
                   "FAILED: mesh-wave counts diverge from the single-"
                   "device wave (or the occupancy/diff verdict is "
                   "wrong) — the perf rows are meaningless"),
        "counts_identical": identical,
        "occupancy_ok": occupancy_ok,
        "obs_diff_verdict": diff["verdict"],
        "registry_run_ids": run_ids,
        "rows": rows,
    }
    tmpf = out_path + ".tmp"
    with open(tmpf, "w") as fh:
        json.dump(out, fh, indent=1)
    os.replace(tmpf, out_path)
    return out


def _wave_mesh2d_ab(out_path):
    """2-D wave-mesh A/B (BENCH_r16, round 17): one OVERSIZED tenant
    (a full-space micro raft job) plus three small fills through
    ``cli batch`` on one device vs the ``--wave-mesh 2x2`` jobs x
    state grid on 4 virtual devices, under the shared correctness
    gate (per-job counts/level sizes bit-identical across modes, or
    the file is FAILED).

    The 2x2 grid is the round-17 claim: the big tenant's visited
    slots/frontier rings split across the state axis while the fills
    pack the job axis — same wave, no eviction of the small jobs.
    Both runs record into one ``--registry`` so the A/B is an ``obs
    diff`` verdict (clean = identical counts), and the grid row must
    stamp ``wave_state_shards=2`` next to ``wave_devices=4``.

    Honest CPU-fallback label: 4 virtual CPU devices share the SAME
    physical cores, so the grid row's seconds measure GSPMD resharding
    overhead, not speedup — the per-device memory-ceiling relief
    (VCAP/S slots per device) is a TPU-slice claim; what this file
    pins on every container is bit-exactness, the state-shard
    occupancy accounting and the dispatch-count invariance."""
    import shutil
    import subprocess
    import tempfile

    import jax

    from raft_tla_tpu.obs.registry import RunRegistry
    from raft_tla_tpu.obs.report import diff_runs

    repo = os.path.dirname(os.path.abspath(__file__))
    tmp = tempfile.mkdtemp(prefix="wave_mesh2d_ab_")
    jobs_path = os.path.join(tmp, "jobs.jsonl")
    ovr = {"servers": 2, "values": [1], "max_inflight": 4,
           "next": "NextAsync",
           "bounds": {"max_log_length": 1, "max_timeouts": 1,
                      "max_client_requests": 1}}
    with open(jobs_path, "w") as fh:
        # the oversized tenant: the full micro space, deepest job in
        # the wave by far...
        fh.write(json.dumps({
            "spec": "raft",
            "config": "configs/tlc_membership/raft.cfg",
            "overrides": ovr, "max_depth": 13,
            "label": "big"}) + "\n")
        # ...plus small fills sharing its bucket's job axis
        for d in (2, 3, 4):
            fh.write(json.dumps({
                "spec": "raft",
                "config": "configs/tlc_membership/raft.cfg",
                "overrides": ovr, "max_depth": d,
                "label": f"fill{d}"}) + "\n")
    registry = os.path.join(tmp, "registry")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                          " --xla_force_host_platform_device_count=4"
                          ).strip())
    rows, keys, run_ids = {}, {}, {}
    try:
        for label, mesh in (("single_device", "off"),
                            ("grid_2x2", "2x2")):
            stats = os.path.join(tmp, label + ".json")
            t0 = time.perf_counter()
            p = subprocess.run(
                [sys.executable, "-m", "raft_tla_tpu", "batch",
                 "--jobs", jobs_path, "--wave-mesh", mesh,
                 "--stats-json", stats, "--registry", registry],
                capture_output=True, text=True, cwd=repo, env=env,
                timeout=900)
            wall = time.perf_counter() - t0
            if p.returncode != 0:
                out = {"bench": "2-D wave-mesh A/B (bench.py, "
                                "BENCH_r16 round)",
                       "status": f"FAILED: cli batch --wave-mesh "
                                 f"{mesh} exited {p.returncode}: "
                                 f"{p.stderr[-500:]}"}
                tmpf = out_path + ".tmp"
                with open(tmpf, "w") as fh:
                    json.dump(out, fh, indent=1)
                os.replace(tmpf, out_path)
                return out
            with open(stats) as fh:
                payload = json.load(fh)
            summary, jrows = payload["summary"], payload["jobs"]
            keys[label] = tuple(
                (r["label"], r["distinct_states"],
                 r["generated_states"], r["depth"],
                 tuple(r["level_sizes"])) for r in jrows)
            reg = RunRegistry(registry)
            fresh = [i for i in reg.run_ids()
                     if i not in run_ids.values()]
            run_ids[label] = fresh[-1]
            rec = reg.load(run_ids[label])
            spans = rec.get("spans") or {}
            disp = spans.get("batched_dispatch") or {}
            rows[label] = {
                "run_id": run_ids[label],
                "wall_seconds": round(wall, 2),
                "wave_devices": int(summary.get("wave_devices", 0)),
                "wave_state_shards":
                    int(summary.get("wave_state_shards", 0)),
                "wave_lanes": int(summary.get("wave_lanes", 0)),
                "batch_dispatches":
                    int(summary.get("batch_dispatches", 0)),
                "batched_dispatch_span": {
                    "count": int(disp.get("count", 0)),
                    "seconds": round(float(disp.get("seconds", 0.0)),
                                     4)},
                "bucket_compile_seconds": round(float(
                    (spans.get("bucket_compile") or {})
                    .get("seconds", 0.0)), 4),
                "per_job_seconds": {
                    r["label"]: round(float(r.get("seconds", 0.0)), 4)
                    for r in jrows},
            }
        reg = RunRegistry(registry)
        diff = diff_runs(reg.load(run_ids["single_device"]),
                         reg.load(run_ids["grid_2x2"]))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    identical = len(set(keys.values())) == 1
    occupancy_ok = (rows["grid_2x2"]["wave_devices"] == 4 and
                    rows["grid_2x2"]["wave_state_shards"] == 2 and
                    rows["single_device"]["wave_devices"] == 1 and
                    rows["grid_2x2"]["batch_dispatches"] ==
                    rows["single_device"]["batch_dispatches"])
    diff_ok = diff["verdict"] in ("clean", "mode_drift")
    ok = identical and occupancy_ok and diff_ok
    out = {
        "bench": "2-D wave-mesh A/B: one oversized micro-raft tenant "
                 "+ 3 fills, --wave-mesh off vs the 2x2 jobs x state "
                 "grid on 4 virtual devices (bench.py, BENCH_r16 "
                 "round)",
        "platform": jax.default_backend(),
        "honest_label": (
            "CPU-only fallback: the 4 'devices' are virtual XLA:CPU "
            "devices on the SAME physical cores, so the grid row's "
            "seconds measure GSPMD resharding overhead, not speedup — "
            "the per-device ceiling relief (VCAP/S visited slots per "
            "device) is a TPU-slice claim; bit-exactness, state-shard "
            "occupancy accounting and dispatch-count invariance are "
            "the platform-independent content"
            if jax.default_backend() == "cpu" else "TPU-measured"),
        "status": ("ok" if ok else
                   "FAILED: 2x2 grid counts diverge from the single-"
                   "device wave (or the occupancy/diff verdict is "
                   "wrong) — the perf rows are meaningless"),
        "correctness_gate": bool(ok),
        "counts_identical": identical,
        "occupancy_ok": occupancy_ok,
        "obs_diff_verdict": diff["verdict"],
        "registry_run_ids": run_ids,
        "rows": rows,
    }
    tmpf = out_path + ".tmp"
    with open(tmpf, "w") as fh:
        json.dump(out, fh, indent=1)
    os.replace(tmpf, out_path)
    return out


def _bench_registry_record(registry_dir, headline):
    """Append one ``cmd="bench"`` record to a run registry (ISSUE 17)
    so ``cli obs ls/diff/regress`` can query bench results next to
    check runs — the headline detail's numeric fields become the
    record's counters (the parity keys obs/report.py compares)."""
    if not registry_dir:
        return
    import time as _time

    from raft_tla_tpu.obs.registry import RunRegistry, new_run_id
    from raft_tla_tpu.obs.resources import backend_fingerprint
    detail = headline.get("detail") or {}
    counters = {k: v for k, v in detail.items()
                if isinstance(v, (int, float))
                and not isinstance(v, bool)}
    RunRegistry(registry_dir).append({
        "run_id": new_run_id(), "cmd": "bench", "status": "finished",
        "finished_ts": round(_time.time(), 3),
        "metric": headline.get("metric"),
        "value": headline.get("value"),
        "counters": counters,
        "backend": backend_fingerprint(),
        "headline": headline})


def main():
    from raft_tla_tpu import native
    from raft_tla_tpu.cfg.parser import load_model
    from raft_tla_tpu.config import Bounds
    from raft_tla_tpu.engine.bfs import Engine
    from raft_tla_tpu.models.explore import explore

    # --registry parses before the reference check: the fallback path
    # (this container) records a queryable cmd="bench" row too
    argv = sys.argv[1:]
    registry = None
    if "--registry" in argv:
        i = argv.index("--registry")
        if i + 1 >= len(argv):
            raise SystemExit("--registry needs a DIR argument")
        registry = argv[i + 1]
        del argv[i:i + 2]

    import jax
    from raft_tla_tpu.utils import enable_compilation_cache, ref_or_local
    enable_compilation_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"bench.py measures the TPU engine; JAX found "
                         f"{dev.platform} ({dev.device_kind})")
    cfg_path = ref_or_local("/root/reference/tlc_membership/raft.cfg")

    # -- correctness gate (micro config, fast) --------------------------
    micro = load_model(cfg_path,
                       bounds=Bounds.make(max_log_length=1, max_timeouts=1,
                                          max_client_requests=1))
    micro = micro.with_(n_servers=2, init_servers=(0, 1), values=(1,),
                        max_inflight_override=4)
    eng_micro = Engine(micro, chunk=256, store_states=False)
    got = eng_micro.check()
    want = explore(micro)
    gate_ok = (got.distinct_states == want.distinct_states and
               got.depth == want.depth and
               got.generated_states == want.generated_states and
               len(got.violations) == len(want.violations))
    if not gate_ok:
        print(json.dumps({
            "metric": "distinct_states_per_sec_tlc_membership_S3_T3_L3",
            "value": 0.0, "unit": "states/sec", "vs_baseline": 0.0,
            "detail": {"correctness_gate": False,
                       "micro_engine": int(got.distinct_states),
                       "micro_oracle": int(want.distinct_states)}}))
        return

    # -- metric config #2 ----------------------------------------------
    # MaxTerm=3 <=> max_timeouts=2 (MaxTerms = MaxTimeouts+1, raft.tla:27)
    cfg = load_model(CFG2, bounds=Bounds.make(
        max_log_length=3, max_timeouts=2, max_client_requests=3))

    # optional overrides: `python bench.py [--max-depth N] [--chunk C]`
    # (NOTE: the round-2 positional arg was a STATE BUDGET; the metric
    # is now depth-exact, so a bare positional number is rejected to
    # avoid silently reinterpreting old invocations).  --chunk exists
    # to let the perf-floor trip be exercised deliberately.
    max_depth, chunk = MAX_DEPTH, 2048
    while argv:
        if len(argv) >= 2 and argv[0] == "--max-depth":
            max_depth = int(argv[1])
            if not 1 <= max_depth <= 64:
                raise SystemExit(f"--max-depth {max_depth}: BFS depths "
                                 "are small (the round-2 budget arg is "
                                 "gone)")
            argv = argv[2:]
        elif len(argv) >= 2 and argv[0] == "--chunk":
            chunk = int(argv[1])
            argv = argv[2:]
        else:
            raise SystemExit("usage: python bench.py [--max-depth N] "
                             "[--chunk C] [--registry DIR]   (the "
                             "metric is depth-exact now; the old "
                             "positional state budget was removed)")

    # -- CPU baseline: the native checker, same depth-exact run ---------
    threads = os.cpu_count() or 8
    nat = native.check(cfg, threads=threads, max_depth=max_depth)
    nat_rate = nat.states_per_sec

    # -- TPU engine, same depth ----------------------------------------
    # ocap pre-sized: the early nearly-all-fresh levels outgrow the
    # default chunk*4 fresh-row buffer, and the growth replay would
    # re-run a level inside the timed window
    eng = Engine(cfg, chunk=chunk, store_states=False, lcap=LCAP,
                 vcap=VCAP, ocap=1 << 14)
    t_compile = time.time()
    eng.check(max_depth=2)                      # warm the jit caches
    t_compile = time.time() - t_compile
    t0 = time.time()
    r = eng.check(max_depth=max_depth)
    secs = time.time() - t0
    rate = r.distinct_states / max(secs, 1e-9)

    count_ok = (r.distinct_states == nat.distinct_states and
                r.depth == nat.depth)
    gate_ok = gate_ok and count_ok

    # fused-dispatch A/B rides along (file only — the stdout contract
    # stays ONE JSON line); a burst≡per-level mismatch fails the
    # headline gate
    burst_ab = _burst_ab(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_r08.json"))
    gate_ok = gate_ok and burst_ab["counts_identical"]
    matmul_ab = _matmul_ab(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_r09.json"))
    gate_ok = gate_ok and matmul_ab["status"] == "ok"
    batch_ab = _batch_ab(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_r10.json"))
    gate_ok = gate_ok and batch_ab["status"] == "ok"
    delta_ab = _delta_ab(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_r11.json"))
    gate_ok = gate_ok and delta_ab["status"] == "ok"
    ceiling_ab = _ceiling_ab(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_r12.json"))
    gate_ok = gate_ok and ceiling_ab["status"] == "ok"
    canon_ab = _canon_ab(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_r14.json"))
    gate_ok = gate_ok and canon_ab["status"] == "ok"
    wave_mesh_ab = _wave_mesh_ab(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_r15.json"))
    gate_ok = gate_ok and wave_mesh_ab["status"] == "ok"
    wave_mesh2d_ab = _wave_mesh2d_ab(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_r16.json"))
    gate_ok = gate_ok and wave_mesh2d_ab["status"] == "ok"

    scored = gate_ok
    out = {
        "metric": "distinct_states_per_sec_tlc_membership_S3_T3_L3",
        "value": round(rate if scored else 0.0, 1),
        "unit": "states/sec",
        "vs_baseline": round((rate / nat_rate) if scored else 0.0, 2),
        "detail": {
            "distinct_states": int(r.distinct_states),
            "depth": int(r.depth),
            "device": f"{dev.device_kind} x{len(jax.devices())}",
            "depth_exact": True,      # no budget cap: full space to depth
            "seconds": round(secs, 2),
            "compile_seconds": round(t_compile, 1),
            "violations": len(r.violations),
            "overflow_faults": int(r.overflow_faults),
            "baseline_native_states_per_sec": round(nat_rate, 1),
            "baseline_native_seconds": round(nat.seconds, 2),
            "baseline_native_threads": threads,
            "correctness_gate": bool(gate_ok),
            "counts_match_native": bool(count_ok),
            # the full space exceeds ~1e8 states (BASELINE.md round-3
            # exhaustion-wall measurements); depth 19 is the deepest
            # single-chip level-exact run
            "exhausted": False,
            # the dedup-exhaustiveness claim's collision bound
            # (64-bit fingerprints)
            "expected_fp_collisions": float(
                r.distinct_states ** 2 / 2.0 ** 65),
        },
    }
    out["detail"]["burst_ab_counts_identical"] = \
        bool(burst_ab["counts_identical"])
    out["detail"]["matmul_ab_status"] = matmul_ab["status"]
    out["detail"]["batch_ab_status"] = batch_ab["status"]
    out["detail"]["delta_ab_status"] = delta_ab["status"]
    out["detail"]["ceiling_ab_status"] = ceiling_ab["status"]
    out["detail"]["canon_ab_status"] = canon_ab["status"]
    out["detail"]["wave_mesh_ab_status"] = wave_mesh_ab["status"]
    out["detail"]["wave_mesh2d_ab_status"] = wave_mesh2d_ab["status"]
    print(json.dumps(out))
    _bench_registry_record(registry, out)


if __name__ == "__main__":
    main()
