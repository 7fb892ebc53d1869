"""Unified engine profiler, built on the obs span recorder.

Consolidates the three one-off scripts it replaces
(profile_engine.py — compile vs steady-state; profile_config3.py /
profile_config3b.py — per-phase attribution of the fused chunk step on
a captured mid-depth frontier) into two modes sharing one harness:

  steady — jit-compile cost, steady-state chunk-step and finalize
           latency, then a bounded full run with growth logging:
             python tools/profile.py steady [--config N] [--chunk C]
                 [--lcap N] [--vcap N] [--budget N]
  phases — capture a real frontier at --depth via the finalize hook,
           then time the step's phases separately (guard pass,
           expand+materialize+fingerprint, +probe-insert dedup,
           +phase2, full fused step) and print the attribution:
             python tools/profile.py phases [--config N] [--depth D]
                 [--chunk C]

Both modes record every measured region as an obs span, so
``--timeline FILE`` emits the whole profiling session as
Perfetto-loadable Chrome-trace JSON — the same format and span names
the engines' ``--trace-timeline`` uses.

``--config N`` picks the BASELINE config (tools/measure_baseline
.build_cfg; default 2 for steady, 3 for phases).  Containers without
/root/reference fall back to the repo-local configs/ twin at micro
bounds (honestly labeled), so the tool runs anywhere.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax                                               # noqa: E402
import jax.numpy as jnp                                  # noqa: E402
import numpy as np                                       # noqa: E402
from jax import lax                                      # noqa: E402

from raft_tla_tpu.engine.bfs import Engine               # noqa: E402
from raft_tla_tpu.obs import SpanRecorder                # noqa: E402
from raft_tla_tpu.ops.codec import widen                 # noqa: E402

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_cfg(n: int):
    """build_cfg(n) when the reference tree exists; otherwise the
    repo-local twin at micro bounds (labeled — the twin parses
    identically, tests/test_sim.py pins that)."""
    if os.path.exists("/root/reference/tlc_membership/raft.cfg"):
        from tools.measure_baseline import ENGINE_KW, build_cfg
        return build_cfg(n), dict(ENGINE_KW.get(n, {}))
    from raft_tla_tpu.cfg.parser import load_model
    from raft_tla_tpu.config import Bounds
    print("NOTE: /root/reference absent — profiling the repo-local "
          "configs/ twin at micro bounds (relative attribution is "
          "meaningful; absolute rates are not the BASELINE shape)",
          flush=True)
    cfg = load_model(
        os.path.join(_REPO, "configs", "tlc_membership", "raft.cfg"),
        bounds=Bounds.make(max_log_length=1, max_timeouts=1,
                           max_client_requests=1))
    return cfg.with_(n_servers=2, init_servers=(0, 1), values=(1,),
                     max_inflight_override=4), dict(chunk=256)


def _bench(rec, name, fn, iters):
    """Compile + steady-state timing of one component, each region a
    span (compile once, then `name` per steady iteration)."""
    with rec.span("compile"):
        v = fn(0)
        jax.tree_util.tree_map(
            lambda x: np.asarray(x) if hasattr(x, "shape") else x, v)
    t0 = time.perf_counter()
    for i in range(iters):
        with rec.span(name):
            v = fn(i)
    jax.tree_util.tree_map(
        lambda x: np.asarray(x) if hasattr(x, "shape") else x, v)
    dt = (time.perf_counter() - t0) / iters
    tc = rec.totals()["compile"]["seconds"]
    print(f"{name:30s} compile {tc:6.1f}s   steady "
          f"{dt * 1000:8.2f} ms", flush=True)
    return dt


def mode_steady(opts, rec):
    conf_no = int(opts.get("--config", 2))
    cfg, kw = load_cfg(conf_no)
    if "--chunk" in opts:
        kw["chunk"] = int(opts["--chunk"])
    if "--lcap" in opts:
        kw["lcap"] = int(opts["--lcap"])
    if "--vcap" in opts:
        kw["vcap"] = int(opts["--vcap"])
    kw.pop("fam_caps", None)
    eng = Engine(cfg, store_states=False, **kw)
    print(f"config #{conf_no}: lanes={eng.A} chunk={eng.chunk} "
          f"LCAP={eng.LCAP} VCAP={eng.VCAP}", flush=True)

    carry = eng._fresh_carry(eng.LCAP, eng.VCAP)
    with rec.span("compile"):
        carry = eng._step_jit(carry, eng.FAM_CAPS)
        jax.block_until_ready(carry["n_lvl"])
    print(f"step compile+run1: "
          f"{rec.totals()['compile']['seconds']:.1f}s", flush=True)
    with rec.span("compile"):
        carry, out = eng._fin_jit(carry)
        jax.block_until_ready(out["scal"])

    # steady state: sync with a real transfer (a device-to-host read
    # cannot return before the work it depends on)
    t0 = time.perf_counter()
    for _ in range(10):
        with rec.span("level_dispatch"):
            carry = eng._step_jit(carry, eng.FAM_CAPS)
    _ = int(np.asarray(carry["n_lvl"]))
    dt = (time.perf_counter() - t0) / 10
    print(f"steady chunk step: {dt * 1000:.1f} ms -> "
          f"{eng.chunk / dt:.0f} parent-states/s "
          f"({eng.chunk * eng.A / dt:.0f} cand/s)", flush=True)
    t0 = time.perf_counter()
    with rec.span("level_dispatch"):
        carry, out = eng._fin_jit(carry)
        _ = np.asarray(out["scal"])
    print(f"steady finalize: "
          f"{(time.perf_counter() - t0) * 1000:.1f} ms", flush=True)

    # full bounded run with growth logging (fresh engine: the probe
    # carry above dirtied the first one's table); the engine-internal
    # spans (burst_dispatch / level_dispatch / harvest) land on the
    # same recorder, so --timeline shows the whole run's phases
    from raft_tla_tpu.obs import Obs
    eng2 = Engine(cfg, store_states=False, **kw)
    budget = int(opts.get("--budget", 150_000))
    t0 = time.perf_counter()
    with rec.span("check"):
        r = eng2.check(max_states=budget, verbose=True,
                       obs=Obs(spans=rec))
    print(f"full: {r.distinct_states} states depth {r.depth} in "
          f"{time.perf_counter() - t0:.1f}s -> "
          f"{r.states_per_sec:.0f}/s  "
          f"final LCAP={eng2.LCAP} VCAP={eng2.VCAP}", flush=True)


def mode_phases(opts, rec):
    conf_no = int(opts.get("--config", 3))
    cap_depth = int(opts.get("--depth", 13))
    cfg, kw = load_cfg(conf_no)
    if "--chunk" in opts:
        kw["chunk"] = int(opts["--chunk"])
    eng = Engine(cfg, store_states=False, **kw)
    B, A, FCAP = eng.chunk, eng.A, eng.FCAP
    print(f"config #{conf_no}: lanes={A} chunk={B} FCAP={FCAP} "
          f"W={eng.W}", flush=True)

    # ---- capture the carry entering the finalize at cap_depth ----
    snap = {}
    real_fin = eng._fin_jit
    lvl = [0]

    def fin_hook(carry):
        lvl[0] += 1
        if lvl[0] == cap_depth and "c" not in snap:
            # snapshot to host BEFORE donation invalidates the buffers
            snap["c"] = jax.tree_util.tree_map(np.asarray, carry)
        return real_fin(carry)

    eng._fin_jit = fin_hook
    with rec.span("capture"):
        # burst off for the capture: the fused path never calls the
        # finalize hook on the early levels
        eng.burst = False
        r = eng.check(max_depth=cap_depth, max_states=1_500_000)
    eng._fin_jit = real_fin
    if "c" not in snap:
        raise SystemExit(f"space exhausted at depth {r.depth} before "
                         f"--depth {cap_depth}; pass a smaller depth")
    carry = jax.tree_util.tree_map(jnp.asarray, snap["c"])
    carry, out = eng._fin_jit(carry)
    n_front = int(np.asarray(out["scal"])[3])
    print(f"captured frontier: {n_front} rows at depth {cap_depth} "
          f"({r.distinct_states} states explored)", flush=True)

    def chunk_front(carry, base):
        sv = widen({k: lax.dynamic_slice_in_dim(v, base, B,
                                                axis=v.ndim - 1)
                    for k, v in carry["front"].items()})
        fmask = lax.dynamic_slice_in_dim(carry["fmask"], base, B)
        valid = ((base + jnp.arange(B, dtype=jnp.int32)) <
                 carry["n_front"]) & fmask
        return sv, valid

    # ---- component jits (everything consumed so nothing DCEs) ----
    @jax.jit
    def guard_only(carry, base):
        sv, valid = chunk_front(carry, base)
        derb = eng.expander.derived_batch_T(sv)
        ok = eng.expander.guards_T(sv, derb)
        return (ok & valid[:, None]).sum()

    @jax.jit
    def expand_fp(carry, base):
        sv, valid = chunk_front(carry, base)
        cand_c, elive, fp, take, famx, n_e = eng._expand_fp_chunk(
            sv, valid, eng.FAM_CAPS, FCAP)
        s = sum(jnp.sum(v.astype(jnp.int32)) for v in cand_c.values())
        return s + fp.astype(jnp.int32).sum() + n_e + elive.sum()

    @jax.jit
    def expand_fp_probe(carry, base):
        sv, valid = chunk_front(carry, base)
        cand_c, elive, fp, take, famx, n_e = eng._expand_fp_chunk(
            sv, valid, eng.FAM_CAPS, FCAP)
        keys = tuple(jnp.where(elive, fp[w], jnp.uint32(0xFFFFFFFF))
                     for w in range(eng.W))
        ranks = jnp.arange(FCAP, dtype=jnp.uint32)
        table, claims, fresh, pos, hv = eng._probe_insert(
            carry["vis"], carry["claims"], keys, elive, ranks)
        return fresh.sum() + table[0].astype(jnp.int32).sum()

    @jax.jit
    def expand_fp_phase2(carry, base):
        sv, valid = chunk_front(carry, base)
        cand_c, elive, fp, take, famx, n_e = eng._expand_fp_chunk(
            sv, valid, eng.FAM_CAPS, FCAP)
        inv, con = eng._phase2_T(cand_c)
        return inv.sum() + con.sum()

    n_chunks = max(1, n_front // B)
    iters = min(10, max(2, n_chunks))

    def comp(fn):
        return lambda i: fn(carry, jnp.int32((i % n_chunks) * B))

    t_g = _bench(rec, "guard_pass", comp(guard_only), iters)
    t_e = _bench(rec, "expand_materialize_fp", comp(expand_fp), iters)
    t_p = _bench(rec, "probe_insert_dedup", comp(expand_fp_probe),
                 iters)
    t_2 = _bench(rec, "phase2_predicates", comp(expand_fp_phase2),
                 iters)

    # full fused step: donated carry — run on a copy stream
    c2 = jax.tree_util.tree_map(jnp.asarray, snap["c"])
    with rec.span("compile"):
        c2 = eng._step_jit(c2, eng.FAM_CAPS)
        _ = int(np.asarray(c2["n_lvl"]))
    t0 = time.perf_counter()
    for _ in range(iters):
        with rec.span("level_dispatch"):
            c2 = eng._step_jit(c2, eng.FAM_CAPS)
    _ = int(np.asarray(c2["n_lvl"]))
    dt = (time.perf_counter() - t0) / iters
    print(f"{'FULL fused step':30s} steady {dt * 1000:8.2f} ms/chunk"
          f"   {B / dt:9.0f} parents/s", flush=True)
    print(f"attribution (ms/chunk): guard={t_g * 1000:.1f}  "
          f"mat+fp={1000 * (t_e - t_g):.1f}  "
          f"probe={1000 * (t_p - t_e):.1f}  "
          f"phase2={1000 * (t_2 - t_e):.1f}  "
          f"append+rest={1000 * (dt - t_p - (t_2 - t_e)):.1f}",
          flush=True)


def main():
    from raft_tla_tpu.utils import enable_compilation_cache
    enable_compilation_cache()
    args = sys.argv[1:]
    if not args or args[0] in ("-h", "--help"):
        print(__doc__)
        return 0 if args else 2
    mode = args.pop(0)
    opts = dict(zip(args[::2], args[1::2]))
    known = {"--config", "--chunk", "--depth", "--lcap", "--vcap",
             "--budget", "--timeline"}
    bad = set(opts) - known
    if bad or len(args) % 2 or mode not in ("steady", "phases"):
        raise SystemExit(
            f"usage: profile.py steady|phases [opts]; unknown: "
            f"{sorted(bad) or [mode]} (known: {sorted(known)})")
    rec = SpanRecorder(opts.get("--timeline"))
    try:
        (mode_steady if mode == "steady" else mode_phases)(opts, rec)
    finally:
        rec.close()
    # the ONE span-rollup rendering lives in obs/report.py (ISSUE 17);
    # `cli obs show/diff` print the same shape
    from raft_tla_tpu.obs.report import format_span_totals
    print("span totals: " + format_span_totals(rec.totals()),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
