"""Pre-warm the persistent compilation cache for the measurement
capacity ladder (VERDICT r3 #4 "kill the compile tax").

tools/compile_probe.py splits warm-start time into tracing+lowering,
the COLD backend compile of the fused step, a WARM disk-cache load,
and the many small root-path programs (the round-4 figures came from
an older runtime; not measured on the current code).  So the compile
tax has two parts:

1. cold compiles after a code or capacity-shape change — REMOVABLE by
   running this tool once per code change: it constructs each ladder
   engine and runs a depth-2 check, which exercises every executable
   (step, finalize, root fingerprint/phase2, and the small eager ops)
   and writes them all to the persistent cache (min_compile_time is 0
   since round 4);
2. per-process executable *loads* (~10 executables) — the floor that
   the cache cannot remove.

Usage: python tools/prewarm.py [config_no ...]   (default: the bench
config #2 ladder + configs 1-5 at their measure_baseline capacities)
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def warm(tag, cfg, **kw):
    """A depth-2 check per (burst, guard-matmul, delta-matmul) mode:
    the default (burst=True) pass compiles the fused multi-level
    executable the tiny levels run on; the burst=False pass compiles
    the per-level step/finalize pair the engine falls back to the
    moment a level outgrows the burst ring — BOTH are hit by every
    real run, so both land in the persistent cache here.  Round 9:
    each burst mode warms under BOTH matmul modes (the default MXU
    guard-matmul path and the --no-guard-matmul lane sweep); round 11
    adds the delta-matmul successor modes — matmul modes pair with
    their matching delta mode plus the two cross-mode A/B programs
    (gm ON × delta OFF and gm OFF × delta ON), so any
    --[no-]guard-matmul/--[no-]delta-matmul session pays no cold
    compiles."""
    from raft_tla_tpu.engine.bfs import Engine
    t0 = time.time()
    for gm, dm in ((True, True), (True, False),
                   (False, True), (False, False)):
        for burst in (True, False):
            eng = Engine(cfg, store_states=False, burst=burst,
                         guard_matmul=gm, delta_matmul=dm, **kw)
            eng.check(max_depth=2)
    print(f"{tag}: warmed in {time.time() - t0:.1f}s "
          f"(chunk={eng.chunk} LCAP={eng.LCAP} VCAP={eng.VCAP} "
          f"FCAP={eng.FCAP})", flush=True)
    del eng


def warm_spill(tag, cfg, **kw):
    """Spill-engine twin of warm(); with host_table=True the depth-2
    check additionally exercises the partitioned-table executables
    (the sweep membership probe, the cache-reseed insert, and the
    lfp-carrying spill slice), so a post-change deep_run/bench with
    --host-table doesn't pay their cold compiles mid-run.  Like
    warm(), both burst modes run — host-table mode keeps the per-level
    path (the sweep is due every level), so the burst pass is skipped
    there."""
    from raft_tla_tpu.engine.spill import SpillEngine
    t0 = time.time()
    modes = (True, False) if not kw.get("host_table") else (False,)
    # both matmul modes (round 9) × both delta modes (round 11; the
    # cross-mode combinations matter only for the classic engine's
    # A/B sessions — spill warms the two default-paired programs)
    for gm, dm in ((True, True), (False, False)):
        for burst in modes:
            eng = SpillEngine(cfg, store_states=False, burst=burst,
                              guard_matmul=gm, delta_matmul=dm, **kw)
            eng.check(max_depth=2)
    print(f"{tag}: warmed in {time.time() - t0:.1f}s "
          f"(chunk={eng.chunk} SEGL={eng.SEGL} VCAP={eng.VCAP} "
          f"host_table={eng.host_table})", flush=True)
    del eng


def warm_pjit(tag, cfg, **kw):
    """Pjit-engine warm (round 14): the whole-state-sharded program's
    step/finalize/burst executables trace with NamedSharding
    out_shardings, so they are DISTINCT cache entries from the classic
    engine's — one depth-2 check per burst mode lands them (plus the
    sharded fresh-carry builders) in the persistent cache before a
    pod-scale session pays them cold."""
    from raft_tla_tpu.parallel.pjit_mesh import PjitShardedEngine
    t0 = time.time()
    for burst in (True, False):
        eng = PjitShardedEngine(cfg, store_states=False, burst=burst,
                                **kw)
        eng.check(max_depth=2)
    print(f"{tag}: pjit warmed in {time.time() - t0:.1f}s "
          f"(D={eng.D} chunk={eng.chunk} LCAP={eng.LCAP} "
          f"VCAP={eng.VCAP})", flush=True)
    del eng


def warm_sym(tag, cfg, **kw):
    """Canonicalization-mode warm (round 15): a symmetric config
    compiles DISTINCT fingerprint programs under --sym-canon sort
    (argsort canonicalization + transposition certificates + the
    cond-gated min-over-perms fallback) vs minperm (the P-fold min) —
    auto picks exactly one, so a bench _canon_ab or deep_run A/B
    session would pay the other's cold compile mid-run.  One depth-2
    check per mode lands both in the persistent cache."""
    from raft_tla_tpu.engine.bfs import Engine
    t0 = time.time()
    for mode in ("sort", "minperm"):
        eng = Engine(cfg, store_states=False, sym_canon=mode, **kw)
        eng.check(max_depth=2)
    print(f"{tag}: sym-canon modes warmed in {time.time() - t0:.1f}s "
          f"(chunk={eng.chunk} P={len(eng.fpr.sigmas)})", flush=True)
    del eng


def warm_resume(tag, cfg, **kw):
    """Resume-repartition warm (round 12): checkpoint a depth-2 run,
    load the portable image and resume it on the spill engine — this
    exercises the resume-side executables a supervised recovery pays
    mid-incident (the fresh-carry build, the table-image upload, the
    repartitioned first level) so they land in the persistent cache
    before the run ever fails."""
    import tempfile

    from raft_tla_tpu.engine.bfs import Engine
    from raft_tla_tpu.engine.spill import SpillEngine
    from raft_tla_tpu.resil.portable import load_portable_image
    t0 = time.time()
    ck = os.path.join(tempfile.mkdtemp(prefix="prewarm_resil_"),
                      "warm.ckpt")
    eng = Engine(cfg, store_states=False, **kw)
    eng.check(max_depth=2, checkpoint_path=ck, checkpoint_every=1)
    eng.check(max_depth=3, resume_from=ck)           # native resume
    img = load_portable_image(ck)
    sp = SpillEngine(cfg, store_states=False, seg=1 << 14,
                     chunk=kw.get("chunk", 256))
    sp.check(max_depth=3, resume_image=img)          # repartition
    print(f"{tag}: resume/repartition warmed in "
          f"{time.time() - t0:.1f}s", flush=True)


def main():
    from raft_tla_tpu.utils import enable_compilation_cache
    enable_compilation_cache()
    from tools.measure_baseline import ENGINE_KW, build_cfg

    # per-spec warming (SpecIR frontends compile distinct programs):
    # "paxos" warms the stock Paxos model's executables — both matmul
    # and burst modes, plus a spill pass — alongside the raft ladder
    raw = sys.argv[1:]
    if "paxos" in raw:
        raw = [a for a in raw if a != "paxos"]
        from raft_tla_tpu.spec.paxos.config import PaxosConfig
        pcfg = PaxosConfig()
        warm("paxos default", pcfg, chunk=256)
        warm_spill("paxos spill", pcfg, chunk=256, seg=1 << 14)
        if not raw:
            return
    args = [int(a) for a in raw]
    # bench.py's shapes first: its micro correctness-gate engine
    # (chunk=256) AND its headline capacities both differ from
    # measure_baseline's budgeted ones — without them a post-prewarm
    # bench run would still pay cold compiles inside its timed session
    if not args:
        import bench
        from raft_tla_tpu.cfg.parser import load_model
        from raft_tla_tpu.config import Bounds
        micro = load_model(
            "/root/reference/tlc_membership/raft.cfg",
            bounds=Bounds.make(max_log_length=1, max_timeouts=1,
                               max_client_requests=1))
        micro = micro.with_(n_servers=2, init_servers=(0, 1),
                            values=(1,), max_inflight_override=4)
        warm("bench micro gate", micro, chunk=256)
        # the supervised-recovery path's executables (round 12)
        warm_resume("resume repartition", micro, chunk=256)
        # the pod-scale sharded program (round 14) — its executables
        # are distinct cache entries from the classic engine's
        warm_pjit("pjit micro", micro, chunk=256)
        # both canonicalization modes (round 15) at bench _canon_ab's
        # exact shape: the config-#5 S=5/P=120 space where auto picks
        # sort — without this the forced-minperm A/B twin compiles cold
        from raft_tla_tpu.config import Bounds as _B, ModelConfig, \
            NEXT_ASYNC
        warm_sym("canon A/B config-5 shape", ModelConfig(
            n_servers=5, init_servers=(0, 1, 2, 3, 4), values=(1,),
            next_family=NEXT_ASYNC, symmetry=True,
            max_inflight_override=4,
            bounds=_B.make(max_log_length=2, max_timeouts=1,
                           max_client_requests=1)), chunk=256)
        warm("bench headline", build_cfg(2), chunk=2048,
             lcap=bench.LCAP, vcap=bench.VCAP)
        # deep_run's spill probe shape, host table OFF and ON: the ON
        # pass compiles the sweep/reseed executables at the ladder's
        # quantized key-block shapes
        warm_spill("spill config 2", build_cfg(2), chunk=4096,
                   seg=1 << 22, vcap=1 << 26)
        warm_spill("spill config 2 +host-table", build_cfg(2),
                   chunk=4096, seg=1 << 22, vcap=1 << 26,
                   host_table=True, partitions=4, part_cap=1 << 16)
    for n in args or [1, 2, 3, 4, 5]:
        warm(f"config {n}", build_cfg(n), **ENGINE_KW[n])


if __name__ == "__main__":
    main()
