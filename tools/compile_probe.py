"""Break down the engine's warm-start cost on the TPU
(VERDICT r3 #4 "kill the compile tax"): how much of the measured
36-205 s `compile_seconds` is (a) Python tracing + MLIR lowering on the
1-vCPU host, (b) backend compile / persistent-cache load, (c) the first
real dispatch round trips.

Usage: python tools/compile_probe.py [config_no] [--chunk N] [--lcap N]
       [--vcap N]

The split decides the fix: (a) dominates -> cache at the jaxpr level /
slim the traced program; (b) dominates -> prewarm the persistent cache
(tools/prewarm.py ladder); (c) dominates -> nothing to win below the
runtime's round-trip floor.
"""
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])


def main():
    from raft_tla_tpu.utils import enable_compilation_cache
    enable_compilation_cache()
    import jax

    from raft_tla_tpu.engine.bfs import Engine
    from tools.measure_baseline import ENGINE_KW, build_cfg

    args = sys.argv[1:]
    conf_no = int(args.pop(0)) if args and not args[0].startswith("-") \
        else 2
    opts = dict(zip(args[::2], args[1::2]))
    kw = dict(ENGINE_KW[conf_no])
    for k in ("chunk", "lcap", "vcap"):
        if f"--{k}" in opts:
            kw[k] = int(opts[f"--{k}"])

    cfg = build_cfg(conf_no)
    t0 = time.time()
    eng = Engine(cfg, store_states=False, **kw)
    t_init = time.time() - t0
    print(f"engine init (incl. salt tables): {t_init:.1f}s", flush=True)

    # build a real carry the way check() does, then time each stage of
    # the step executable explicitly
    carry = eng._fresh_carry(eng.LCAP, eng.VCAP, eng.FCAP)
    t0 = time.time()
    lowered = eng._step_jit.lower(carry, eng.FAM_CAPS)
    t_lower = time.time() - t0
    print(f"step trace+lower: {t_lower:.1f}s", flush=True)
    t0 = time.time()
    lowered.compile()
    t_compile = time.time() - t0
    print(f"step backend compile (or cache load): {t_compile:.1f}s",
          flush=True)
    # a plain dispatch through the normal jit path (its own cache)
    t0 = time.time()
    carry = eng._step_jit(carry, eng.FAM_CAPS)
    jax.block_until_ready(carry["n_lvl"])
    t_disp = time.time() - t0
    print(f"first jit dispatch (trace+compile+run on top of AOT "
          f"warmth): {t_disp:.1f}s", flush=True)

    t0 = time.time()
    lowered_f = eng._fin_jit.lower(carry)
    print(f"finalize trace+lower: {time.time() - t0:.1f}s", flush=True)
    t0 = time.time()
    lowered_f.compile()
    print(f"finalize compile/load: {time.time() - t0:.1f}s", flush=True)

    t0 = time.time()
    r = eng.check(max_depth=2)
    print(f"check(max_depth=2) after all of the above: "
          f"{time.time() - t0:.1f}s  ({r.distinct_states} states)",
          flush=True)


if __name__ == "__main__":
    main()
