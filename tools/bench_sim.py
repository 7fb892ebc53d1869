"""Walker-throughput bench for the random-walk sim engine (sim/walker).

Measures steady-state walker-steps/sec on two workloads:

  small — the 3-server membership scenario shape (NextDynamic,
          InitServer ⊊ Server) the differential tests use;
  cfg5  — the BASELINE config #5 shape (Server=5, MaxTerm=4,
          MaxLogLen=4, NextDynamic) the sim engine exists for.

Both run HIT-FREE (no target invariant) so the number is pure
transition throughput — sampling, step fusion, predicates, fingerprint,
Bloom — not witness luck.  The platform is recorded verbatim: on this
CPU-only container the figures are an honest CPU fallback, not TPU
numbers (BASELINE.md round 7 carries the same label).

Usage:  python tools/bench_sim.py [out.json]
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

OUT = sys.argv[1] if len(sys.argv) > 1 else os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "BENCH_r06.json")


def build(name):
    from raft_tla_tpu.cfg.parser import load_model
    from raft_tla_tpu.config import Bounds, NEXT_DYNAMIC
    cfg = load_model("configs/tlc_membership/raft.cfg")
    if name == "small":
        return cfg.with_(
            n_servers=3, init_servers=(0, 1), next_family=NEXT_DYNAMIC,
            max_inflight_override=6, invariants=(),
            bounds=Bounds.make(max_log_length=2, max_timeouts=1,
                               max_client_requests=1,
                               max_membership_changes=1))
    if name == "cfg5":
        return cfg.with_(
            n_servers=5, init_servers=(0, 1, 2, 3, 4),
            next_family=NEXT_DYNAMIC, max_inflight_override=50,
            invariants=(),
            bounds=Bounds.make(max_log_length=4, max_timeouts=3,
                               max_client_requests=3, max_terms=4))
    raise SystemExit(name)


def measure(name, walkers, steps, warm=16):
    import jax
    from raft_tla_tpu.sim import SimEngine
    eng = SimEngine(build(name), walkers=walkers, max_depth=48, seed=0,
                    bloom_bits=20)
    t0 = time.time()
    eng.run(steps=warm, steps_per_dispatch=warm)     # compile + warm
    compile_s = time.time() - t0
    st = eng.fresh_carry()
    t0 = time.time()
    st = eng._dispatch(st, steps)
    sdone = int(st["stats"][0])                      # blocks on device
    secs = time.time() - t0
    return {
        "workload": name, "walkers": walkers, "fleet_steps": steps,
        "walker_steps": sdone,
        "walker_steps_per_sec": round(sdone / max(secs, 1e-9), 1),
        "sampled_steps": int(st["stats"][5]),
        "seconds": round(secs, 3),
        "compile_seconds": round(compile_s, 1),
        "platform": jax.default_backend(),
    }


def main():
    from raft_tla_tpu.utils import enable_compilation_cache
    enable_compilation_cache()
    import jax
    rows = [measure("small", walkers=64, steps=256),
            measure("cfg5", walkers=64, steps=128)]
    out = {
        "bench": "sim walker throughput (tools/bench_sim.py)",
        "platform": jax.default_backend(),
        "honest_label": (
            "CPU-only fallback: this container has no TPU; figures "
            "measure the same device program XLA:CPU-compiled"
            if jax.default_backend() == "cpu" else
            "TPU-measured"),
        "rows": rows,
    }
    with open(OUT, "w") as fh:
        json.dump(out, fh, indent=1)
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
