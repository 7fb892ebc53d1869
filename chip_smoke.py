#!/usr/bin/env python3
"""Chip smoke: BASELINE config #2 checked on a TPU through the CLI.

    python chip_smoke.py                # one chip: classic engine
    python chip_smoke.py --four-chips   # four chips: `check --pjit`

One process, one chip owner: the native checker's shared object is
built (a g++ child) before JAX is imported, and nothing is started
after.  Phases, each fatal on failure:

1. (one chip) the micro correctness gate bench.py runs: the TPU engine
   against the Python oracle on a 2-server model;
2. the native C++ checker (an independent implementation) on config #2
   to depth 19, in this process;
3. ``raft_tla_tpu.cli.main(["check", ...])`` on config #2 to the same
   depth at default capacities (so the buffers grow as in a plain
   run); with ``--four-chips`` the same command with ``--pjit`` on a
   4-device mesh, pre-sized.  Its distinct count, depth and per-level
   sizes must equal the native checker's.

The last stdout line is ``{"ok": true, "device": {...}}``; any failure
exits non-zero before it is printed, and so does a non-TPU device.
"""

import argparse
import json
import os
import shutil
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
CFG2 = os.path.join(REPO, "configs", "config2", "raft.cfg")
# MaxTerm = MaxTimeouts + 1 = 3 (raft.tla:27)
BOUNDS = ["--max-log-length", "3", "--max-timeouts", "2",
          "--max-client-requests", "3"]
# the depth-19 level adds ~5.2M states into a 2^25-slot table
# (BASELINE.md).  One chip runs the default capacities, so the growth
# path a plain `check` takes runs too; the pjit run is pre-sized
CAPS = ["--lcap", str(3 << 21), "--vcap", str(1 << 25),
        "--ocap", str(1 << 14)]
OUT_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")


def fail(msg):
    print(f"chip_smoke: FAIL — {msg}", file=sys.stderr)
    sys.exit(1)


def micro_gate():
    """bench.py's gate: engine ≡ oracle on the 2-server micro model."""
    from raft_tla_tpu.cfg.parser import load_model
    from raft_tla_tpu.config import Bounds
    from raft_tla_tpu.engine.bfs import Engine
    from raft_tla_tpu.models.explore import explore
    micro = load_model(
        os.path.join(REPO, "configs", "tlc_membership", "raft.cfg"),
        bounds=Bounds.make(max_log_length=1, max_timeouts=1,
                           max_client_requests=1))
    micro = micro.with_(n_servers=2, init_servers=(0, 1), values=(1,),
                        max_inflight_override=4)
    got = Engine(micro, chunk=256, store_states=False).check()
    want = explore(micro)
    g = (got.distinct_states, got.generated_states, got.depth,
         list(got.level_sizes), len(got.violations))
    w = (want.distinct_states, want.generated_states, want.depth,
         list(want.level_sizes), len(want.violations))
    if g != w:
        fail(f"micro gate: engine {g} != oracle {w}")
    print(f"micro gate: engine == oracle, {g[0]} distinct states, "
          f"depth {g[2]}")


def cli_check(depth, pjit, caps=()):
    """Config #2 through the CLI; returns (registry record, wall s,
    compile s).  Compile time is JAX's own trace + lower + backend
    compile (or cache load) durations, summed over the run."""
    import jax
    from raft_tla_tpu import cli
    compile_s = [0.0]

    def on_event(name, secs, **_kw):
        if name.startswith("/jax/core/compile/"):
            compile_s[0] += secs

    jax.monitoring.register_event_duration_secs_listener(on_event)
    reg = os.path.join(OUT_DIR, "registry")
    shutil.rmtree(reg, ignore_errors=True)
    argv = ["check", CFG2, *BOUNDS, "--max-depth", str(depth),
            "--no-store", "--chunk", "2048", *caps, "--registry", reg]
    if pjit:
        argv.append("--pjit")
    print("cli: python -m raft_tla_tpu " + " ".join(
        os.path.relpath(a, REPO) if a.startswith(REPO) else a
        for a in argv), flush=True)
    t0 = time.perf_counter()
    rc = cli.main(argv)
    wall = time.perf_counter() - t0
    if rc != 0:
        fail(f"cli check exited {rc}")
    recs = [os.path.join(reg, f) for f in os.listdir(reg)]
    if len(recs) != 1:
        fail(f"expected one registry record, found {len(recs)}")
    with open(recs[0]) as fh:
        return json.load(fh), wall, compile_s[0]


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run `check --pjit` on a 4-device mesh (and "
                         "its native comparison) instead")
    ap.add_argument("--max-depth", type=int, default=19)
    args = ap.parse_args()
    n_chips = 4 if args.four_chips else 1

    from raft_tla_tpu import native
    native.load()                  # g++ build: the only child process

    import jax
    from raft_tla_tpu.utils import enable_compilation_cache
    cache_dir = enable_compilation_cache()
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        fail(f"needs a TPU; JAX found {dev.platform} ({dev.device_kind})")
    if len(devs) != n_chips:
        fail(f"needs exactly {n_chips} TPU device(s), found {len(devs)}")
    where = f"{dev.device_kind} x{len(devs)}"
    print(f"device: {where}; compile cache: {cache_dir}", flush=True)
    os.makedirs(OUT_DIR, exist_ok=True)

    if not args.four_chips:
        micro_gate()

    from raft_tla_tpu.cfg.parser import load_model
    from raft_tla_tpu.config import Bounds
    cfg = load_model(CFG2, bounds=Bounds.make(
        max_log_length=3, max_timeouts=2, max_client_requests=3))
    threads = os.cpu_count() or 8
    nat = native.check(cfg, threads=threads, max_depth=args.max_depth)
    print(f"native: {nat.distinct_states} distinct, depth {nat.depth}, "
          f"{nat.seconds:.3f} s on {threads} host threads", flush=True)

    rec, wall, compile_s = cli_check(args.max_depth, args.four_chips,
                                     CAPS if args.four_chips else ())
    got = (rec["distinct_states"], rec["depth"], rec["level_sizes"])
    want = (nat.distinct_states, nat.depth, nat.level_sizes)
    print(f"check: {got[0]} distinct, depth {got[1]}")
    print(f"level sizes: {got[2]}")
    if got != want:
        fail(f"check {got} != native {want}")
    print("counts and level sizes match the native checker")
    print(f"wall {wall:.3f} s = compile {compile_s:.3f} s + run "
          f"{wall - compile_s:.3f} s, measured on {where}")
    print(f"engine rate over the run part: "
          f"{got[0] / max(wall - compile_s, 1e-9):.1f} states/s on "
          f"{where}; native {nat.states_per_sec:.1f} states/s on "
          f"{threads} host threads")
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devs]
    print(f"device peak_bytes_in_use: {peaks} on {where}")
    print("dedup program: lax claim-insert (engine/bfs._probe_insert)")
    with open(os.path.join(OUT_DIR, f"chips{n_chips}.json"), "w") as fh:
        json.dump({"device": where, "wall_s": wall,
                   "compile_s": compile_s, "peak_bytes_in_use": peaks,
                   "native_s": nat.seconds, "native_threads": threads,
                   "registry": rec}, fh, indent=1)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
