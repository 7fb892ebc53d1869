"""Config #3 (membership) step-shape tuning harness (VERDICT r4 #1).

Runs the budgeted config-3 workload under candidate engine shapes and
reports rate + the measured per-family enabled maxima (Engine.famx_max)
so FAM_CAPS/FCAP/OCAP can be pre-sized from data instead of the
conservative density table.

Usage: python tools/tune_config3.py VARIANT [budget]
  VARIANT: base | nofp | tightcaps | tight-nofp | chunk4096
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from tools.measure_baseline import build_cfg, ENGINE_KW
from raft_tla_tpu.engine.bfs import Engine


def main():
    from raft_tla_tpu.utils import enable_compilation_cache
    enable_compilation_cache()
    variant = sys.argv[1] if len(sys.argv) > 1 else "base"
    budget = int(sys.argv[2]) if len(sys.argv) > 2 else 1_500_000
    cfg = build_cfg(3)
    kw = dict(ENGINE_KW[3])
    inc = True
    if variant == "nofp":
        inc = False
    elif variant == "chunk4096":
        kw["chunk"] = 4096
        kw["fcap"] = 1 << 17
    # ENGINE_KW[3] carries the production fam_caps (a post-construction
    # assignment, not a constructor kwarg — see measure_baseline)
    kw_fam_caps = kw.pop("fam_caps", None)
    if variant.startswith("tight"):
        if TIGHT.get(kw.get("chunk", 2048)) is None:
            raise SystemExit("record famx_max with `base` first")
        # Σ tight caps bounds any chunk's enabled total, so FCAP can
        # shrink with them (fp/probe phases scale with FCAP)
        kw["fcap"] = TIGHT_FCAP[kw.get("chunk", 2048)]
        if variant == "tight-nofp":
            inc = False
    eng = Engine(cfg, store_states=False, incremental_fp=inc, **kw)
    if variant.startswith("tight"):
        # caps measured by a prior `base` run (famx_max + 25% headroom,
        # rounded up to 512); overflow just replays, so tight is safe
        eng.FAM_CAPS = tuple(TIGHT[eng.chunk])
    elif kw_fam_caps is not None and variant != "base":
        eng.FAM_CAPS = tuple(kw_fam_caps)
    t0 = time.time()
    eng.check(max_depth=2)
    compile_s = time.time() - t0
    t0 = time.time()
    r = eng.check(max_states=budget)
    secs = time.time() - t0
    fams = [f.name for f in eng.expander.families]
    print({
        "variant": variant, "budget": budget,
        "distinct": r.distinct_states, "depth": r.depth,
        "seconds": round(secs, 2),
        "states_per_sec": round(r.distinct_states / secs, 1),
        "compile_seconds": round(compile_s, 1),
        "chunk": eng.chunk, "FCAP": eng.FCAP, "OCAP": eng.OCAP,
        "fam_caps": dict(zip(fams, eng.FAM_CAPS)),
        "famx_max": dict(zip(fams, getattr(eng, "famx_max", []))),
    }, flush=True)


# per-chunk tight caps, from the recorded `base` run's famx_max
# (2026-07-31: RequestVote 2650, BecomeLeader 87, ClientRequest 2492,
# AdvanceCommitIndex 1246, AppendEntries 2394, UpdateTerm 1655,
# CocDiscard 689, Receive 6145, Timeout 3431, Restart 6204,
# Duplicate 5767, Drop 5767, AddNewServer 1366, DeleteServer 2394)
TIGHT = {2048: [3584, 512, 3584, 2048, 3072, 2560, 1024, 8192, 4608,
                8192, 7680, 7680, 2048, 3072]}
# Σ famx_max = 42287 bounds any single chunk's enabled total
TIGHT_FCAP = {2048: 45056}

if __name__ == "__main__":
    main()
