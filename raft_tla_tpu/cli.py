"""Command-line front-end: ``check`` and ``trace`` (SURVEY §7.2 L5).

Mirrors the two ways the reference drives TLC (SURVEY §3.1, §3.5):

  check  — exhaustive bounded model check: BFS to fixpoint, report
           distinct states / depth / states/sec and any invariant
           violations (with traces).
  trace  — scenario-trace generation: enable ONE negated-reachability
           property (raft.cfg "Test cases", §2.9) and print the witness
           trace TLC would emit as a "violation".

Engine selection: --engine tpu (default; the JAX BFS) or --engine oracle
(the plain-Python reference implementation, for cross-checking).

Spec selection: --spec raft (default; the cfg positional is a TLC .cfg
path) or --spec paxos (the cfg positional is optional — omitted or
"default" builds the stock small PaxosConfig, else a JSON file of
constants).  Every engine/oracle path below routes through the
``SpecIR`` handle, so the two specs share the whole command surface.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from .cfg.parser import load_model
from .config import Bounds


def _apply_overrides(cfg, args):
    kw = {}
    if args.servers is not None:
        kw["n_servers"] = args.servers
        init = args.init_servers if args.init_servers is not None \
            else args.servers
        kw["init_servers"] = tuple(range(init))
        # MaxInFlightMessages is a FORMULA over Server in the spec
        # (2·|S|² tlc / 4·|S|² apalache, raft.tla:30); the parser lifts
        # its value at the cfg's |Server|, so a --servers override must
        # recompute it — otherwise a shrunk model keeps the big model's
        # bag capacity (e.g. K=19 at S=2, a shape the remote TPU
        # compiler chokes on for >15 min)
        old_n, new_n = cfg.n_servers, args.servers
        ov = cfg.max_inflight_override
        if ov == 2 * old_n * old_n:
            kw["max_inflight_override"] = 2 * new_n * new_n
        elif ov == 4 * old_n * old_n:
            kw["max_inflight_override"] = 4 * new_n * new_n
    elif args.init_servers is not None:
        kw["init_servers"] = tuple(range(args.init_servers))
    if args.symmetry is not None:
        kw["symmetry"] = args.symmetry
    if getattr(args, "next_family", None):
        # next-relation family override (the CLI analog of editing the
        # cfg's NEXT line — e.g. NextDynamic enables the membership
        # actions the MembershipChange* scenario targets need)
        kw["next_family"] = args.next_family
    b = cfg.bounds
    bkw = {}
    if args.max_terms is not None:
        bkw["max_terms"] = args.max_terms
    if args.max_log_length is not None:
        bkw["max_log_length"] = args.max_log_length
    if args.max_timeouts is not None:
        bkw["max_timeouts"] = args.max_timeouts
    if args.max_client_requests is not None:
        bkw["max_client_requests"] = args.max_client_requests
    if args.max_restarts is not None:
        bkw["max_restarts"] = args.max_restarts
    if bkw:
        kw["bounds"] = Bounds.make(
            max_log_length=bkw.get("max_log_length", b.max_log_length),
            max_restarts=bkw.get("max_restarts", b.max_restarts),
            max_timeouts=bkw.get("max_timeouts", b.max_timeouts),
            max_client_requests=bkw.get("max_client_requests",
                                        b.max_client_requests),
            max_membership_changes=b.max_membership_changes,
            max_terms=bkw.get("max_terms"),
            max_trace=b.max_trace)
    if args.fp128:
        kw["fp128"] = True
    # cfg-surgery equivalents of TLC's comment-toggling (raft.cfg:51-76).
    # ADDITIVE, like TLC's repeated CONSTRAINTS/INVARIANTS blocks: the
    # cfg's general bounding constraints stay in force.
    from .models import predicates as OP

    def _add(base, extra, known, what):
        for nm in extra:
            if nm not in known:
                raise SystemExit(
                    f"unknown {what} {nm!r}; known: "
                    f"{', '.join(sorted(known))}")
        return tuple(dict.fromkeys(base + tuple(extra)))
    if getattr(args, "invariants", None):
        kw["invariants"] = _add(cfg.invariants, args.invariants,
                                OP.INVARIANTS, "invariant")
    if getattr(args, "constraint_overrides", None):
        kw["constraints"] = _add(cfg.constraints, args.constraint_overrides,
                                 OP.CONSTRAINTS, "constraint")
    if getattr(args, "action_constraints", None):
        kw["action_constraints"] = _add(cfg.action_constraints,
                                        args.action_constraints,
                                        OP.ACTION_CONSTRAINTS,
                                        "action constraint")
    return cfg.with_(**kw) if kw else cfg


def _load_paxos_model(args):
    """--spec paxos config assembly: the cfg positional is optional
    (None/"default" -> the stock small model; a ``.cfg`` path -> the
    TLC CONSTANTS front-end, cfg/parser.load_paxos_model; anything
    else -> a JSON file of constants), then the generic CLI overrides
    apply (--servers = acceptors, --ballots/--paxos-values/
    --instances, --symmetry, --fp128, --invariant)."""
    import json as _json
    from .cfg.parser import (CfgError, load_paxos_model,
                             paxos_config_from_obj)
    from .spec import get_spec
    from .spec.paxos.config import PaxosConfig
    raft_only = [flag for flag, attr in (
        ("--next", "next_family"), ("--max-terms", "max_terms"),
        ("--max-log-length", "max_log_length"),
        ("--max-timeouts", "max_timeouts"),
        ("--max-client-requests", "max_client_requests"),
        ("--max-restarts", "max_restarts"),
        ("--init-servers", "init_servers"))
        if getattr(args, attr, None) is not None]
    if raft_only:
        raise SystemExit(
            f"{', '.join(raft_only)} are raft-only bounds/toggles — "
            "spec 'paxos' is bounded by --ballots/--paxos-values/"
            "--instances/--servers instead")
    if args.cfg and args.cfg != "default":
        try:
            if args.cfg.endswith(".cfg"):
                cfg = load_paxos_model(args.cfg)
            else:
                with open(args.cfg) as fh:
                    raw = _json.load(fh)
                cfg = paxos_config_from_obj(raw, where=args.cfg)
        except CfgError as e:
            raise SystemExit(str(e))
    else:
        cfg = PaxosConfig()
    kw = {}
    if args.servers is not None:
        kw["n_servers"] = args.servers
    if getattr(args, "ballots", None) is not None:
        kw["n_ballots"] = args.ballots
    if getattr(args, "paxos_values", None) is not None:
        kw["n_values"] = args.paxos_values
    if getattr(args, "instances", None) is not None:
        kw["n_instances"] = args.instances
    if args.symmetry is not None:
        kw["symmetry"] = args.symmetry
    if args.fp128:
        kw["fp128"] = True
    try:
        if kw:
            cfg = cfg.with_(**kw)
    except ValueError as e:
        raise SystemExit(f"paxos config: {e}")
    if getattr(args, "invariants", None):
        ir = get_spec("paxos")
        for nm in args.invariants:
            if nm not in ir.known_invariants:
                raise SystemExit(
                    f"unknown invariant {nm!r} for spec 'paxos'; "
                    f"known: {', '.join(sorted(ir.known_invariants))}")
        cfg = cfg.with_(invariants=tuple(dict.fromkeys(
            cfg.invariants + tuple(args.invariants))))
    if getattr(args, "constraint_overrides", None) or \
            getattr(args, "action_constraints", None):
        raise SystemExit(
            "spec 'paxos' declares no constraints / action "
            "constraints (the bounded space is finite without them)")
    return cfg


def _load_cfg(args):
    """(SpecIR handle, model config) for the selected --spec."""
    from .spec import get_spec
    ir = get_spec(args.spec)
    if args.spec == "paxos":
        return ir, _load_paxos_model(args)
    if not args.cfg:
        raise SystemExit(
            "a TLC .cfg path is required for --spec raft "
            "(only --spec paxos has a built-in default model)")
    cfg = load_model(args.cfg, bounds=None)
    return ir, _apply_overrides(cfg, args)


def _print_violation(idx, name, trace):
    print(f"\nViolation {idx}: invariant {name}")
    if trace:
        for step, (label, sv) in enumerate(trace):
            print(f"  {step:3d}  {label}")
            print(f"       {sv}")


def _load_seeds(path, ir):
    """Seed-trace file -> list of seeds (punctuated search: BFS
    explores only extensions of the pinned prefix).  Entries carry the
    active spec's oracle state/hist plus the exact non-VIEW lanes when
    emitted by the engine."""
    import json as _json
    with open(path) as fh:
        data = _json.load(fh)
    if isinstance(data, dict):
        data = [data]
    oracle_seeds, engine_seeds = [], []
    for obj in data:
        # seed files are spec-tagged (paxos state_to_obj writes a
        # "paxos" marker; untagged files are raft-era) — refuse a
        # cross-spec seed with the same clarity as checkpoint resume
        got_spec = "paxos" if obj.get("paxos") else "raft"
        if got_spec != ir.name:
            raise SystemExit(
                f"{path}: seed was emitted for spec {got_spec!r}; "
                f"this run is --spec {ir.name} — re-emit the seed "
                f"with the matching --spec")
        sv, h = ir.state_from_obj(obj)
        oracle_seeds.append((sv, h))
        engine_seeds.append((sv, h, obj.get("nonview")))
    return oracle_seeds, engine_seeds


def _engine_seed_arrays(cfg, ir, engine_seeds):
    import numpy as np
    lay = ir.make_layout(cfg)
    out = []
    for sv, h, nonview in engine_seeds:
        arrs = ir.encode(lay, sv, h)
        if nonview:
            for k, v in nonview.items():
                arrs[k] = np.asarray(v, dtype=arrs[k].dtype)
        out.append(arrs)
    return out


_OBS_ARGS = ("ledger", "heartbeat", "trace_timeline", "profile_dir",
             "registry")


def _obs_flags_set(args) -> bool:
    """Flag presence WITHOUT constructing the bundle (building it
    opens/truncates the ledger and timeline files)."""
    return any(getattr(args, nm, None) for nm in _OBS_ARGS)


def _build_obs(args, ir=None, cfg=None, cmd=None):
    """The observability bundle the flags describe (obs package);
    NULL_OBS when no flag is set.  ``ir`` stamps the active spec name
    + IR fingerprint into every ledger record; ``cfg``/``cmd`` ride
    the run-level context (ledger meta row + registry record only —
    a cfg repr is too bulky for every dispatch row)."""
    from .obs import from_flags
    meta = ({"spec": ir.name, "ir_fingerprint": ir.fingerprint()}
            if ir is not None else None)
    run_info = {}
    if cmd is not None:
        run_info["cmd"] = cmd
    if cfg is not None:
        run_info["cfg"] = repr(cfg)
    return from_flags(ledger=getattr(args, "ledger", None),
                      heartbeat=getattr(args, "heartbeat", None),
                      timeline=getattr(args, "trace_timeline", None),
                      profile_dir=getattr(args, "profile_dir", None),
                      meta=meta,
                      registry=getattr(args, "registry", None),
                      run_info=run_info or None)


def _add_obs_flags(sp):
    """--ledger/--heartbeat/--trace-timeline/--profile-dir/--registry,
    shared by check, simulate and batch (tools/deep_run.py exposes the
    same set)."""
    sp.add_argument("--ledger", default=None, metavar="FILE",
                    help="append one JSONL record per dispatch (depth, "
                         "frontier, registry counters, states/sec, "
                         "RSS, device memory) — flushed per record, so "
                         "a killed run keeps its telemetry; tail with "
                         "tools/watch.py")
    sp.add_argument("--heartbeat", default=None, metavar="FILE",
                    help="atomically rewrite a small JSON (pid, depth, "
                         "last-dispatch timestamp, states enqueued) "
                         "every dispatch so an external watchdog can "
                         "distinguish a slow level from a dead run")
    sp.add_argument("--trace-timeline", default=None, metavar="FILE",
                    help="write the host span timeline (compile / "
                         "burst_dispatch / harvest / host_sweep / "
                         "archive_io / checkpoint) as Chrome-trace "
                         "JSON — load it in Perfetto "
                         "(https://ui.perfetto.dev)")
    sp.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="capture an XLA device trace via "
                         "jax.profiler.trace into DIR; span names ride "
                         "along as TraceAnnotations so the device "
                         "trace lines up with --trace-timeline")
    sp.add_argument("--registry", default=None, metavar="DIR",
                    help="append one atomic schema-versioned run "
                         "record (counters, span rollups, resource "
                         "peaks, backend fingerprint, exit status, "
                         "artifact paths) under DIR at run end; query "
                         "with `cli obs ls/show/diff/regress`")


def _install_chaos(args):
    """--chaos SPEC -> the process-global schedule (resil/chaos);
    returns an error string on a malformed spec."""
    if not getattr(args, "chaos", None):
        return None
    from .resil.chaos import ChaosSpecError, install
    try:
        install(args.chaos)
    except ChaosSpecError as e:
        return str(e)
    return None


def _check_retry_flags(args):
    if getattr(args, "retries", 0) < 0:
        return f"--retries must be >= 0 (got {args.retries})"
    if getattr(args, "backoff", 1.0) <= 0:
        return f"--backoff must be positive (got {args.backoff})"
    if getattr(args, "ckpt_keep", 1) is not None and \
            getattr(args, "ckpt_keep", 1) < 1:
        return f"--ckpt-keep must be >= 1 (got {args.ckpt_keep})"
    return None


def cmd_check(args):
    ir, cfg = _load_cfg(args)
    if args.engine == "oracle" and (args.resume or args.checkpoint):
        print("--checkpoint/--resume are tpu-engine features",
              file=sys.stderr)
        return 2
    if args.resume and args.seed_trace:
        print("--resume and --seed-trace are mutually exclusive",
              file=sys.stderr)
        return 2
    if args.resume_portable and not args.resume:
        print("--resume-portable qualifies --resume: pass the "
              "checkpoint with --resume FILE", file=sys.stderr)
        return 2
    if args.resume_portable and not (args.spill or args.pjit):
        print("--resume-portable re-partitions any engine family's "
              "checkpoint onto the spill or pjit engine: add --spill "
              "or --pjit", file=sys.stderr)
        return 2
    if args.pjit and args.spill:
        print("--pjit and --spill are different engines; pick one",
              file=sys.stderr)
        return 2
    err = _check_retry_flags(args) or _install_chaos(args)
    if err:
        print(err, file=sys.stderr)
        return 2
    oracle_seeds = engine_seeds = None
    if args.seed_trace:
        oracle_seeds, raw = _load_seeds(args.seed_trace, ir)
        if args.engine == "oracle":
            # engine-emitted seeds (nonview lanes, no glob records)
            # cannot feed the oracle's record-scanning predicates: they
            # would silently evaluate against an empty history.
            needs_glob = ir.glob_dependent & (
                set(cfg.invariants) | set(cfg.constraints) |
                set(cfg.action_constraints))
            for _sv, h, nonview in raw:
                if nonview and not h.glob and needs_glob:
                    print(f"seed was emitted by the tpu engine (nonview "
                          f"lanes, no history records); the oracle "
                          f"cannot evaluate {sorted(needs_glob)} on it — "
                          f"re-emit the seed with `trace --engine oracle "
                          f"--emit-seed`", file=sys.stderr)
                    return 2
        else:
            engine_seeds = _engine_seed_arrays(cfg, ir, raw)
    if args.engine == "oracle":
        explore = ir.oracle_explore
        import time
        if _obs_flags_set(args):
            # the oracle has no dispatches to ledger/heartbeat; say so
            # instead of silently writing nothing (and do NOT build
            # the bundle — that would touch the files)
            print("--ledger/--heartbeat/--trace-timeline/--profile-dir"
                  "/--registry instrument the tpu engines; ignored "
                  "for --engine oracle", file=sys.stderr)
        t0 = time.perf_counter()
        r = explore(cfg, max_depth=args.max_depth,
                    max_states=args.max_states,
                    stop_on_violation=not args.keep_going,
                    trace_violations=True, seed_states=oracle_seeds)
        secs = time.perf_counter() - t0
        viol = [(v.invariant, v.trace) for v in r.violations]
        distinct, depth, gen = r.distinct_states, r.depth, \
            r.generated_states
    else:
        from .engine.bfs import CheckpointError, Engine
        if args.host_table and not args.spill:
            print("--host-table composes with the spill engine: add "
                  "--spill", file=sys.stderr)
            return 2
        if args.burst_levels is not None and args.burst_levels <= 0:
            # a clear error beats the jit-time shape traceback a zero
            # ring would produce
            print(f"--burst-levels must be positive (got "
                  f"{args.burst_levels}); use --no-burst to disable "
                  "the fused-level path", file=sys.stderr)
            return 2
        fam_density = None
        if args.fam_cap_density:
            from .engine.expand import parse_fam_density
            try:
                fam_density = parse_fam_density(args.fam_cap_density,
                                                ir)
            except ValueError as e:
                print(f"--fam-cap-density: {e}", file=sys.stderr)
                return 2
        burst_kw = dict(burst=args.burst, burst_levels=args.burst_levels,
                        guard_matmul=args.guard_matmul,
                        delta_matmul=args.delta_matmul,
                        fam_density=fam_density,
                        sym_canon=args.sym_canon)
        if args.lcap is not None and args.spill:
            print("--spill sizes its level segment with --seg, not "
                  "--lcap", file=sys.stderr)
            return 2
        # pre-sized capacities: no growth replay, no recompile
        burst_kw.update({k: getattr(args, k) for k in
                         ("lcap", "vcap", "ocap", "fcap")
                         if getattr(args, k) is not None})

        def make_engine():
            # one fresh engine per supervised attempt — the backend-
            # reinit contract (resil/supervisor): a retry re-traces
            # against a reconnected backend instead of reusing
            # executables that may hold dead runtime handles
            if args.spill:
                # host-spill engine: levels stream through host RAM,
                # for depths whose level buffers exceed HBM
                # (engine/spill); --host-table additionally moves the
                # visited set to fingerprint-prefix partitions in host
                # RAM, streamed through HBM per level
                # (engine/host_table) — the ceiling becomes host RAM,
                # not the chip
                from .engine.spill import SpillEngine
                eng = SpillEngine(cfg, chunk=args.chunk,
                                  store_states=not args.no_store,
                                  seg=args.seg,
                                  host_table=args.host_table,
                                  partitions=args.partitions,
                                  part_cap=args.part_cap,
                                  sweep_stage=args.sweep_stage,
                                  archive_dir=args.archive_dir,
                                  **burst_kw)
            elif args.pjit:
                # pod-scale pjit engine: the classic program under
                # named shardings spanning every host's devices
                # (parallel/pjit_mesh) — bit-identical counts/traces
                from .parallel.pjit_mesh import PjitShardedEngine
                eng = PjitShardedEngine(cfg, chunk=args.chunk,
                                        store_states=not args.no_store,
                                        archive_dir=args.archive_dir,
                                        **burst_kw)
            else:
                eng = Engine(cfg, chunk=args.chunk,
                             store_states=not args.no_store,
                             archive_dir=args.archive_dir,
                             **burst_kw)
            eng.ckpt_keep = args.ckpt_keep
            return eng
        from .resil.supervisor import RetryExhausted, supervised_check
        obs = _build_obs(args, ir, cfg=cfg, cmd="check")
        obs.start()
        done = False
        try:
            resume_image = None
            if args.resume_portable:
                from .resil.portable import load_portable_image
                resume_image = load_portable_image(args.resume)
            r, eng, _attempts = supervised_check(
                make_engine, retries=args.retries,
                backoff=args.backoff, obs=obs,
                checkpoint_path=args.checkpoint,
                resume_from=(None if args.resume_portable
                             else args.resume),
                resume_image=resume_image,
                max_depth=args.max_depth,
                max_states=args.max_states,
                stop_on_violation=not args.keep_going,
                verbose=args.verbose, seed_states=engine_seeds,
                checkpoint_every=args.checkpoint_every)
            done = True
        except (CheckpointError, FileNotFoundError) as e:
            # only checkpoint load/format problems — a mid-run error
            # after a successful resume propagates with its real trace
            if not args.resume:
                raise
            print(f"cannot resume from {args.resume}: {e}",
                  file=sys.stderr)
            return 2
        except RetryExhausted as e:
            print(str(e), file=sys.stderr)
            return 3
        finally:
            # the final heartbeat carries the run's reported depth (so
            # a watchdog sees "finished" with depth == the stats line)
            if done:
                obs.finish(depth=int(r.depth),
                           states=int(r.distinct_states),
                           counters=r.metrics.as_dict(),
                           level_sizes=list(r.level_sizes))
            else:
                obs.finish(status="failed")
        secs = r.seconds
        viol = []
        for v in r.violations[:args.max_violations]:
            if v.state_id < 0:
                # pinned-prefix interior state (models/golden): checked
                # at seed time, never entered BFS — no parent chain
                trace = [("(pinned-prefix interior state — precedes "
                          "the seeded witness end)", v.state)]
            elif not args.no_store:
                trace = eng.trace(v.state_id)
            elif v.state is not None:
                # no parent archive, but the violating state itself was
                # decoded at detection time — always show it (TLC always
                # reports at least the bad state)
                trace = [("(violating state; run without --no-store "
                          "for the full trace)", v.state)]
            else:
                trace = None
            viol.append((v.invariant, trace))
        distinct, depth, gen = r.distinct_states, r.depth, \
            r.generated_states
        if r.overflow_faults:
            print(f"FAULT: {r.overflow_faults} un-representable states "
                  f"(bounds too small for the disabled-constraint space)",
                  file=sys.stderr)
    # ONE stats assembler (obs.metrics.check_stats) generates the
    # stdout line and --stats-json from the metrics registry — same
    # keys as the historical hand-built dict (pinned by
    # tests/test_obs.py), incl. pin_interior_states only when nonzero
    # and the fingerprint/burst telemetry only for the tpu engines
    from .obs.metrics import check_stats
    if args.engine == "oracle":
        counters = dict(
            distinct_states=int(distinct), generated_states=int(gen),
            depth=int(depth),
            pin_interior_states=int(
                getattr(r, "pin_interior_states", 0) or 0))
        out = check_stats(counters, secs, len(viol),
                          spec=ir.name, ir_fp=ir.fingerprint())
    else:
        out = check_stats(r.metrics.as_dict(), secs, len(viol),
                          fp_bits=128 if args.fp128 else 64,
                          spec=ir.name, ir_fp=ir.fingerprint(),
                          lanes_enabled=getattr(r, "lanes_enabled", None))
    print(json.dumps(out))
    if args.stats_json:
        # oracle runs write the same stats file (minus the
        # fingerprint/burst telemetry keys the oracle has no notion of)
        with open(args.stats_json, "w") as fh:
            json.dump(out, fh)
    for k, (name, trace) in enumerate(viol):
        if args.engine == "oracle":
            print(f"\nViolation {k}: {name}")
            if trace:
                print("  " + " -> ".join(trace))
            elif trace is None:
                # pinned-prefix interior state (models/golden): outside
                # the BFS parent map, so there is no action trace.
                # (A ROOT violation has an EMPTY trace, not None.)
                print("  (pinned-prefix interior state — precedes the "
                      "seeded witness end)")
            else:
                print("  (violation at a root state — empty trace)")
        else:
            _print_violation(k, name, trace)
    return 1 if viol else 0


def _write_seed(path, obj):
    with open(path, "w") as fh:
        json.dump(obj, fh)
    print(f"seed written to {path}", file=sys.stderr)


def _seed_obj(ir, sv, hist, arrs):
    """Witness end state -> the seed-file object `check --seed-trace`
    accepts: the active spec's oracle view (state_to_obj) plus the raw
    non-VIEW lanes, so a seeded engine resumes with identical
    constraint / scenario-predicate inputs.  ONE definition — trace
    and simulate both emit through it, so seed files cannot drift."""
    import numpy as np
    obj = ir.state_to_obj(sv, hist)
    obj["nonview"] = {k: np.asarray(arrs[k]).tolist()
                      for k in ir.nonview_keys}
    return obj


def _check_target(name, ir) -> bool:
    """Validate a --target against the active spec's scenario registry
    (SpecIR.scenario_properties — the ONE table trace, simulate and
    the help text all read, so new sim-reachable targets cannot drift
    out of the CLI).  Safety invariants are also accepted (hunting a
    real violation is a legitimate target)."""
    if name in ir.known_invariants:
        return True
    others = sorted(set(ir.known_invariants) -
                    set(ir.scenario_properties))
    print(f"unknown scenario property {name!r} for spec "
          f"{ir.name!r}; known scenario properties: "
          f"{', '.join(ir.scenario_properties)}\n"
          f"(safety invariants are accepted too: "
          f"{', '.join(others)})",
          file=sys.stderr)
    return False


def cmd_trace(args):
    ir, cfg = _load_cfg(args)
    if not _check_target(args.target, ir):
        return 2
    cfg = cfg.with_(invariants=(args.target,))
    if args.engine == "oracle":
        import time
        explore = ir.oracle_explore
        t0 = time.perf_counter()
        r = explore(cfg, max_depth=args.max_depth,
                    max_states=args.max_states, stop_on_violation=True,
                    trace_violations=True)
        if not r.violations:
            print(f"no witness found for {args.target} within bounds "
                  f"({r.distinct_states} states, depth {r.depth})")
            return 1
        print(f"witness for {args.target} at depth {r.depth} "
              f"({r.distinct_states} states explored, "
              f"{time.perf_counter() - t0:.1f}s):")
        for step, label in enumerate(r.violations[0].trace):
            print(f"  {step + 1:3d}  {label}")
        if args.emit_seed:
            v = r.violations[0]
            _write_seed(args.emit_seed,
                        ir.state_to_obj(v.state, v.hist))
        return 0
    from .engine.bfs import Engine
    eng = Engine(cfg, chunk=args.chunk, store_states=True,
                 guard_matmul=args.guard_matmul,
                 delta_matmul=args.delta_matmul,
                 sym_canon=args.sym_canon)
    r = eng.check(max_depth=args.max_depth, max_states=args.max_states,
                  stop_on_violation=True, verbose=args.verbose)
    if not r.violations:
        print(f"no witness found for {args.target} within bounds "
              f"({r.distinct_states} states, depth {r.depth})")
        return 1
    v = r.violations[0]
    print(f"witness for {args.target} at depth {r.depth} "
          f"({r.distinct_states} states explored, "
          f"{r.seconds:.1f}s):")
    for step, (label, sv) in enumerate(eng.trace(v.state_id)):
        print(f"  {step:3d}  {label}")
        if args.verbose:
            print(f"       {sv}")
    if args.emit_seed:
        arrs = eng.get_state_arrays(v.state_id)
        sv, h = ir.decode(eng.lay, arrs)
        _write_seed(args.emit_seed, _seed_obj(ir, sv, h, arrs))
    return 0


def cmd_simulate(args):
    """TLC ``-simulate`` analogue: W vmapped random walkers hunt a
    scenario property beyond the exhaustive stack's reach (sim/walker
    design notes).  Exit 0 on a witness, 1 on none within the step
    budget."""
    import time
    # a clear bounds error beats the jit-time shape traceback a
    # non-positive loop length would produce (ROADMAP sim follow-ups)
    for nm, val in (("--steps-per-dispatch", args.steps_per_dispatch),
                    ("--walkers", args.walkers),
                    ("--steps", args.steps)):
        if val <= 0:
            print(f"{nm} must be positive (got {val})",
                  file=sys.stderr)
            return 2
    ir, cfg = _load_cfg(args)
    if not _check_target(args.target, ir):
        return 2
    cfg = cfg.with_(invariants=(args.target,))
    # --max-depth doubles as the walk restart bound; the check-style
    # "unbounded" default maps to a walk-sized one
    depth = args.max_depth if args.max_depth < 10 ** 6 else 64
    import jax
    from .sim import SimEngine
    kw = dict(max_depth=depth, seed=args.seed, policy=args.policy,
              bloom_bits=args.bloom_bits,
              guard_matmul=args.guard_matmul,
              delta_matmul=args.delta_matmul,
              sym_canon=args.sym_canon)
    if args.mesh and len(jax.local_devices()) > 1:
        from .parallel.sim_mesh import ShardedSimEngine
        eng = ShardedSimEngine(cfg, walkers=args.walkers, **kw)
    else:
        eng = SimEngine(cfg, walkers=args.walkers, **kw)
    obs = _build_obs(args, ir, cfg=cfg, cmd="simulate")
    obs.start()
    t0 = time.perf_counter()
    done = False
    try:
        r = eng.run(steps=args.steps,
                    steps_per_dispatch=args.steps_per_dispatch,
                    verbose=args.verbose, obs=obs)
        done = True
    finally:
        if done:
            from .obs.metrics import sim_counters
            obs.finish(depth=int(r.steps_dispatched),
                       states=int(r.walker_steps),
                       counters=sim_counters(r))
        else:
            obs.finish(status="failed")
    # the ONE simulate stats assembler (obs.metrics.sim_stats) — same
    # keys as the historical hand-built dict
    from .obs.metrics import sim_stats
    out = sim_stats(r, target=args.target, policy=args.policy,
                    seed=args.seed, platform=jax.default_backend())
    # the active SpecIR stamp, appended last (same contract as
    # check_stats' spec/ir_fingerprint tail keys)
    out["spec"] = ir.name
    out["ir_fingerprint"] = ir.fingerprint()
    print(json.dumps(out))
    if args.stats_json:
        with open(args.stats_json, "w") as fh:
            json.dump(out, fh)
    if not r.hits:
        print(f"no witness found for {args.target} within "
              f"{r.walker_steps} walker-steps", file=sys.stderr)
        return 1
    h = eng.decode_hit(r.hits[0])
    print(f"witness for {args.target} at depth {h.depth} "
          f"(walker {h.walker}, {r.walker_steps} walker-steps, "
          f"{time.perf_counter() - t0:.1f}s):")
    for step, (label, sv) in enumerate(h.trace):
        print(f"  {step:3d}  {label}")
        if args.verbose:
            print(f"       {sv}")
    if args.trace_out:
        with open(args.trace_out, "w") as fh:
            json.dump({"target": args.target, "depth": h.depth,
                       "walker": h.walker, "seed": args.seed,
                       "labels": [label for label, _sv in h.trace]},
                      fh)
        print(f"witness trace written to {args.trace_out}",
              file=sys.stderr)
    if args.emit_seed:
        _write_seed(args.emit_seed,
                    _seed_obj(ir, h.trace[-1][1], h.hist,
                              h.state_arrs))
    return 0


def cmd_batch(args):
    """Multi-tenant batched checking (serve/): a job list from a JSONL
    file and/or repeated --job flags, grouped into shape buckets and
    run as one device program per bucket, with fingerprint-keyed
    result caching.  Prints one summary JSON line, then one report
    line per job (submission order).  Exit 0 = all clean, 1 = some job
    found violations, 2 = usage error."""
    from .cfg.parser import CfgError
    from .serve import (ResultCache, job_from_dict, load_jobs,
                        run_jobs)
    jobs = []
    if args.jobs:
        try:
            jobs.extend(load_jobs(args.jobs))
        except (OSError, ValueError, CfgError) as e:
            print(str(e), file=sys.stderr)
            return 2
    for k, text in enumerate(args.job or []):
        where = f"--job #{k + 1}"
        try:
            jobs.append(job_from_dict(json.loads(text), where=where))
        except (OSError, ValueError) as e:
            # OSError too: a missing config path is a usage error
            # (exit 2), not a violation-style exit 1
            msg = str(e) if str(e).startswith(where) \
                else f"{where}: {e}"
            print(msg, file=sys.stderr)
            return 2
    if not jobs:
        print("no jobs: pass --jobs FILE.jsonl and/or --job JSON",
              file=sys.stderr)
        return 2
    if args.cache_max_bytes is not None and args.cache_max_bytes <= 0:
        print(f"--cache-max-bytes must be positive (got "
              f"{args.cache_max_bytes}); omit it for an unbounded "
              "cache", file=sys.stderr)
        return 2
    if args.cache_max_bytes is not None and not args.cache_dir:
        print("--cache-max-bytes bounds the on-disk result cache: "
              "add --cache-dir", file=sys.stderr)
        return 2
    if args.wave_yield is not None and args.wave_yield < 1:
        print(f"--wave-yield must be >= 1 (got {args.wave_yield})",
              file=sys.stderr)
        return 2
    if args.max_wave is not None and args.max_wave < 1:
        print(f"--max-wave must be >= 1 (got {args.max_wave})",
              file=sys.stderr)
        return 2
    try:
        from .serve.batch import resolve_wave_mesh
        resolve_wave_mesh(args.wave_mesh)
    except ValueError as e:
        print(str(e), file=sys.stderr)
        return 2
    if args.executable_cache_max_bytes is not None:
        if args.executable_cache_max_bytes <= 0:
            print(f"--executable-cache-max-bytes must be positive "
                  f"(got {args.executable_cache_max_bytes}); omit it "
                  "for an unbounded cache", file=sys.stderr)
            return 2
        if not args.executable_cache:
            print("--executable-cache-max-bytes bounds the on-disk "
                  "executable cache: add --executable-cache",
                  file=sys.stderr)
            return 2
    err = _check_retry_flags(args) or _install_chaos(args)
    if err:
        print(err, file=sys.stderr)
        return 2
    cache = ResultCache(args.cache_dir,
                        max_bytes=args.cache_max_bytes) \
        if args.cache_dir else None
    exec_cache = None
    if args.executable_cache:
        from .serve.exec_cache import ExecCache
        exec_cache = ExecCache(
            args.executable_cache,
            max_bytes=args.executable_cache_max_bytes)
    obs = _build_obs(args, cmd="batch")
    obs.start()
    done = False
    rep = None
    import time as _time
    from .resil.supervisor import RETRYABLE, backoff_delay
    attempt = 0
    try:
        while True:
            try:
                rep = run_jobs(jobs, cache=cache, obs=obs,
                               sequential=args.sequential,
                               verbose=args.verbose,
                               wave_state=args.wave_state,
                               wave_yield=args.wave_yield,
                               max_wave=args.max_wave,
                               wave_mesh=args.wave_mesh,
                               bucket_overrides=(
                                   {"sym_canon": args.sym_canon}
                                   if args.sym_canon != "auto"
                                   else None),
                               exec_cache=exec_cache)
                done = True
                break
            except RETRYABLE as e:
                # a retried batch is incremental: finished jobs answer
                # from the result cache, stragglers resume mid-BFS
                # from --wave-state
                if attempt >= args.retries:
                    print(f"batch run failed: {e}", file=sys.stderr)
                    return 3
                wait = backoff_delay(attempt, args.backoff, 60.0)
                obs.retry(attempt=attempt + 1,
                          max_attempts=args.retries + 1,
                          wait_s=wait, error=e)
                _time.sleep(wait)
                attempt += 1
    finally:
        if done:
            obs.finish(
                depth=max((int(o.report.get("depth", 0))
                           for o in rep.outcomes), default=0),
                states=sum(int(o.report.get("distinct_states", 0))
                           for o in rep.outcomes),
                # the batch summary's scalar counters (jobs, buckets,
                # cache hits, dispatches) are the run's registry record
                counters={k: v for k, v in rep.summary.items()
                          if isinstance(v, (int, float))
                          and not isinstance(v, bool)})
        else:
            obs.finish(status="failed")
    print(json.dumps(rep.summary))
    for o in rep.outcomes:
        print(json.dumps(o.report))
    if args.stats_json:
        with open(args.stats_json, "w") as fh:
            json.dump({"summary": rep.summary,
                       "jobs": [o.report for o in rep.outcomes]}, fh)
    n_viol = sum(int(o.report.get("violations", 0))
                 for o in rep.outcomes)
    return 1 if n_viol else 0


def cmd_serve(args):
    """The persistent checking daemon (serve/daemon): watch a spool
    directory (and/or tail a JSONL stream) for job submissions, drain
    claimed jobs through the shared wave scheduler, and write one
    atomic result JSON + done/ marker per submission.  Runs until
    SIGTERM/SIGINT (graceful drain, exit 0) or --max-idle-polls.
    Exit 0 = drained cleanly, 2 = usage error, 3 = a serve cycle
    exhausted its retries (the supervisor's restart signal)."""
    from .serve import Daemon, ResultCache
    if args.poll <= 0:
        print(f"--poll must be positive (got {args.poll})",
              file=sys.stderr)
        return 2
    if args.grace < 0:
        print(f"--grace must be >= 0 (got {args.grace})",
              file=sys.stderr)
        return 2
    if args.max_idle_polls is not None and args.max_idle_polls < 1:
        print(f"--max-idle-polls must be >= 1 "
              f"(got {args.max_idle_polls})", file=sys.stderr)
        return 2
    if args.wave_yield is not None and args.wave_yield < 1:
        print(f"--wave-yield must be >= 1 (got {args.wave_yield})",
              file=sys.stderr)
        return 2
    if args.max_wave is not None and args.max_wave < 1:
        print(f"--max-wave must be >= 1 (got {args.max_wave})",
              file=sys.stderr)
        return 2
    try:
        from .serve.batch import resolve_wave_mesh
        resolve_wave_mesh(args.wave_mesh)
    except ValueError as e:
        print(str(e), file=sys.stderr)
        return 2
    if args.cache_max_bytes is not None and args.cache_max_bytes <= 0:
        print(f"--cache-max-bytes must be positive (got "
              f"{args.cache_max_bytes}); omit it for an unbounded "
              "cache", file=sys.stderr)
        return 2
    if args.executable_cache_max_bytes is not None:
        if args.executable_cache_max_bytes <= 0:
            print(f"--executable-cache-max-bytes must be positive "
                  f"(got {args.executable_cache_max_bytes}); omit it "
                  "for an unbounded cache", file=sys.stderr)
            return 2
        if not args.executable_cache:
            print("--executable-cache-max-bytes bounds the on-disk "
                  "executable cache: add --executable-cache",
                  file=sys.stderr)
            return 2
    err = _check_retry_flags(args) or _install_chaos(args)
    if err:
        print(err, file=sys.stderr)
        return 2
    # the daemon is restart-proof BY DEFAULT: result cache and wave
    # state live under the spool unless pointed elsewhere
    cache_dir = args.cache_dir or os.path.join(args.spool, "cache")
    wave_dir = args.wave_state or os.path.join(args.spool, "waves")
    cache = ResultCache(cache_dir, max_bytes=args.cache_max_bytes)
    exec_cache = None
    if args.executable_cache:
        from .serve.exec_cache import ExecCache
        exec_cache = ExecCache(
            args.executable_cache,
            max_bytes=args.executable_cache_max_bytes)
    obs = _build_obs(args, cmd="serve")
    obs.start()
    daemon = Daemon(
        args.spool, cache=cache, wave_state=wave_dir,
        exec_cache=exec_cache, obs=obs, poll_s=args.poll,
        wave_yield=args.wave_yield,
        max_wave=args.max_wave, wave_mesh=args.wave_mesh,
        bucket_overrides=({"sym_canon": args.sym_canon}
                          if args.sym_canon != "auto" else None),
        retries=args.retries, backoff=args.backoff,
        max_idle_polls=args.max_idle_polls, stream=args.stream,
        grace_s=args.grace, verbose=args.verbose)
    daemon.install_signals()
    # daemon.run owns obs.finish (the drain epilogue must run on
    # every exit path, with the daemon's own counters)
    return daemon.run()


def _load_baseline_file(path, row):
    """A committed baseline for ``obs regress``: a --stats-json
    payload, a bench headline object, a registry record, or a BENCH
    A/B file with a ``rows`` map (then --baseline-row picks one)."""
    with open(path) as fh:
        obj = json.load(fh)
    if isinstance(obj, dict) and isinstance(obj.get("rows"), dict):
        if not row:
            raise SystemExit(
                f"{path} holds multiple A/B rows; pick one with "
                f"--baseline-row (known: "
                f"{', '.join(sorted(obj['rows']))})")
        if row not in obj["rows"]:
            raise SystemExit(
                f"--baseline-row {row!r} not in {path} (known: "
                f"{', '.join(sorted(obj['rows']))})")
        return obj["rows"][row]
    if row:
        raise SystemExit(f"--baseline-row given but {path} has no "
                         "'rows' map")
    return obj


def cmd_obs(args):
    """``cli obs`` — the registry's query surface (obs/report.py).

    ls      — filterable run table (newest last).
    show    — one run's full record (counters, span rollups,
              resource peaks, artifacts) as indented JSON.
    diff    — machine-readable parity verdict + per-phase span deltas
              between two runs; exit 1 on count mismatch.
    regress — a run against a prior run (--against) or a committed
              baseline file (--baseline); exit 1 on count mismatch or
              a tripped --max-span-ratio bound, 2 on usage errors.

    Run tokens: a full run id, a unique id prefix, or ``last``."""
    from .obs.registry import RunRegistry
    from .obs.report import diff_runs, regress
    reg = RunRegistry(args.registry)

    def resolve(token):
        rid = reg.resolve(token)
        if rid is None:
            ids = reg.run_ids()
            print(f"no unique run matches {token!r} in "
                  f"{args.registry} ({len(ids)} records"
                  + (f"; newest {ids[-1]}" if ids else "")
                  + ")", file=sys.stderr)
        return rid

    if args.obs_cmd == "ls":
        rows = []
        for rid, rec in reg.records():
            if args.spec and rec.get("spec") != args.spec:
                continue
            if args.cmd_filter and rec.get("cmd") != args.cmd_filter:
                continue
            if args.status and rec.get("status") != args.status:
                continue
            rows.append(rec)
        print(f"{'run_id':34s} {'cmd':9s} {'spec':6s} {'status':9s} "
              f"{'depth':>6s} {'states':>10s} {'seconds':>8s}")
        for rec in rows:
            print(f"{str(rec.get('run_id', '?')):34s} "
                  f"{str(rec.get('cmd', '?')):9s} "
                  f"{str(rec.get('spec', '-')):6s} "
                  f"{str(rec.get('status', '?')):9s} "
                  f"{str(rec.get('depth', '-')):>6s} "
                  f"{str(rec.get('distinct_states', '-')):>10s} "
                  f"{str(rec.get('seconds', '-')):>8s}")
        return 0
    if args.obs_cmd == "show":
        rid = resolve(args.run)
        if rid is None:
            return 2
        print(json.dumps(reg.load(rid), indent=1))
        return 0
    if args.obs_cmd == "diff":
        ra, rb = resolve(args.run_a), resolve(args.run_b)
        if ra is None or rb is None:
            return 2
        rep = diff_runs(reg.load(ra), reg.load(rb))
        print(json.dumps(rep))
        return 1 if rep["verdict"] == "mismatch" else 0
    if args.obs_cmd == "regress":
        if bool(args.against) == bool(args.baseline):
            print("obs regress needs exactly one of --against RUN / "
                  "--baseline FILE", file=sys.stderr)
            return 2
        rid = resolve(args.run)
        if rid is None:
            return 2
        if args.against:
            bid = resolve(args.against)
            if bid is None:
                return 2
            baseline = reg.load(bid)
        else:
            baseline = _load_baseline_file(args.baseline,
                                           args.baseline_row)
        rep, code = regress(reg.load(rid), baseline,
                            max_span_ratio=args.max_span_ratio,
                            min_seconds=args.min_seconds)
        print(json.dumps(rep))
        return code
    raise SystemExit(f"unknown obs subcommand {args.obs_cmd!r}")


def main(argv=None):
    p = argparse.ArgumentParser(
        prog="raft_tla_tpu",
        description="TPU-native explicit-state model checker for the "
                    "Raft spec family")
    sub = p.add_subparsers(dest="cmd", required=True)

    def common(sp):
        sp.add_argument("cfg", nargs="?", default=None,
                        help="model file: a TLC .cfg path (--spec "
                             "raft; required) or a TLC .cfg / JSON "
                             "constants file / 'default' (--spec "
                             "paxos; optional)")
        sp.add_argument("--spec", choices=("raft", "paxos"),
                        default="raft",
                        help="which spec frontend (SpecIR) to check: "
                             "the Raft membership-change spec "
                             "(default) or bounded single-decree/"
                             "multi-instance Paxos — same engines, "
                             "same flags, same oracle-differential "
                             "guarantees")
        sp.add_argument("--engine", choices=("tpu", "oracle"),
                        default="tpu")
        sp.add_argument("--chunk", type=int, default=512)
        sp.add_argument("--max-depth", type=int, default=10 ** 9)
        sp.add_argument("--max-states", type=int, default=10 ** 9)
        sp.add_argument("--servers", type=int, default=None,
                        help="override |Server|")
        sp.add_argument("--init-servers", type=int, default=None,
                        help="override |InitServer| (first K servers)")
        sp.add_argument("--symmetry", action=argparse.BooleanOptionalAction,
                        default=None)
        sp.add_argument("--next", dest="next_family", default=None,
                        choices=("NextAsync", "NextAsyncCrash", "Next",
                                 "NextDynamic"),
                        help="override the cfg's NEXT family (e.g. "
                             "NextDynamic enables the membership "
                             "actions the MembershipChange* scenario "
                             "targets need)")
        sp.add_argument("--max-terms", type=int, default=None)
        sp.add_argument("--max-log-length", type=int, default=None)
        sp.add_argument("--max-timeouts", type=int, default=None)
        sp.add_argument("--max-client-requests", type=int, default=None)
        sp.add_argument("--max-restarts", type=int, default=None)
        sp.add_argument("--fp128", action="store_true")
        # --spec paxos constants (ignored for raft)
        sp.add_argument("--ballots", type=int, default=None,
                        help="paxos: ballots 0..N-1 (--spec paxos)")
        sp.add_argument("--paxos-values", type=int, default=None,
                        help="paxos: values 0..N-1 (--spec paxos)")
        sp.add_argument("--instances", type=int, default=None,
                        help="paxos: independent consensus instances "
                             "(--spec paxos)")
        sp.add_argument("--guard-matmul",
                        action=argparse.BooleanOptionalAction,
                        default=True,
                        help="MXU-native expansion (default ON, "
                             "bit-exact): the guard grid runs as one "
                             "int8 matmul against the packed guard "
                             "matrix and enabled-lane materialization "
                             "as one-hot einsum blocks; --no-guard-"
                             "matmul restores the vmapped per-lane "
                             "sweep exactly")
        sp.add_argument("--delta-matmul",
                        action=argparse.BooleanOptionalAction,
                        default=True,
                        help="delta-matmul successor generation "
                             "(default ON, bit-exact): families with "
                             "declared delta algebras apply as ONE "
                             "batched scatter-as-matmul per family "
                             "group (int32 einsum blocks on the MXU); "
                             "declaration-less families keep the "
                             "per-family kernel path either way, and "
                             "--no-delta-matmul restores it for all")
        sp.add_argument("--sym-canon",
                        choices=("auto", "sort", "minperm"),
                        default="auto",
                        help="symmetry canonicalization (round 15): "
                             "'sort' hashes ONE orbit-sorted canonical "
                             "relabeling per state (equivariant "
                             "signatures + argsort; signature ties "
                             "fall back to min-over-residual-perms, "
                             "so the state partition is IDENTICAL); "
                             "'minperm' keeps the P-fold "
                             "min-over-perms; 'auto' (default) picks "
                             "sort past 6 perms.  Fingerprint VALUES "
                             "are mode-specific — checkpoints refuse "
                             "cross-mode resume")
        sp.add_argument("--verbose", "-v", action="store_true")

    pc = sub.add_parser("check", help="exhaustive bounded check")
    common(pc)
    pc.add_argument("--keep-going", action="store_true",
                    help="do not stop at the first violation")
    pc.add_argument("--spill", action="store_true",
                    help="host-spill engine: stream levels through "
                         "host RAM (TLC's disk-spill counterpart) — "
                         "required past the single-chip HBM depth wall")
    pc.add_argument("--pjit", action="store_true",
                    help="pod-scale pjit engine (parallel/pjit_mesh): "
                         "the whole BFS state lives under named "
                         "shardings on a mesh spanning every host's "
                         "devices (multi-controller runs span hosts "
                         "after jax.distributed.initialize), with the "
                         "hash-ownership dedup exchange compiled as "
                         "in-program collectives; counts/gids/traces "
                         "are bit-identical to the default engine")
    for flag, what in (("--lcap", "level-buffer rows"),
                       ("--vcap", "visited-table slots"),
                       ("--ocap", "post-dedup fresh rows per chunk"),
                       ("--fcap", "enabled candidate lanes per chunk")):
        pc.add_argument(flag, type=int, default=None, metavar="N",
                        help=f"initial {what}: pre-size it for a known "
                             "space, so the run never pays a growth "
                             "replay and its recompile")
    pc.add_argument("--seg", type=int, default=1 << 21,
                    help="spill segment capacity in states (--spill)")
    pc.add_argument("--host-table", action="store_true",
                    help="host-partitioned visited table (needs "
                         "--spill): the authoritative fingerprint set "
                         "lives in host RAM as fingerprint-prefix "
                         "partitions streamed through HBM per level; "
                         "the device table becomes a bounded cache — "
                         "breaks the ~2^29-slot HBM dedup ceiling "
                         "(TLC's disk-spillable fingerprint set "
                         "counterpart)")
    pc.add_argument("--sweep-stage",
                    action=argparse.BooleanOptionalAction,
                    default=True,
                    help="double-buffered pre-sweep H2D staging "
                         "(--host-table): issue the next sweep's "
                         "partition-image uploads at level start so "
                         "the DMA overlaps the level's compute "
                         "instead of serializing inside the sweep "
                         "(h2d_stage/sweep_overlap spans on the "
                         "ledger/timeline; counts are identical "
                         "either way — --no-sweep-stage is the A/B "
                         "reference)")
    pc.add_argument("--partitions", type=int, default=4, metavar="P",
                    help="host-table partition count, a power of two "
                         "(counts are P-invariant; P sizes the "
                         "largest image HBM must hold at once)")
    pc.add_argument("--part-cap", type=int, default=1 << 16,
                    metavar="N",
                    help="initial slots per host-table partition "
                         "(grows 4x on the 0.40 load bound)")
    pc.add_argument("--archive-dir", default=None, metavar="DIR",
                    help="disk-backed trace archives: stream each "
                         "level's parent/lane/state rows to memmap'd "
                         "files under DIR instead of growing host "
                         "arrays (store_states runs stay RAM-bounded; "
                         "traces replay from the memmaps)")
    pc.add_argument("--burst", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="fused multi-level dispatch: run whole runs "
                         "of small BFS levels inside one device "
                         "program instead of one dispatch+sync per "
                         "level (--no-burst restores the pure "
                         "per-level driver; counts are bit-identical "
                         "either way)")
    pc.add_argument("--burst-levels", type=int, default=None,
                    metavar="K",
                    help="max levels fused per burst device call "
                         "(default 16)")
    pc.add_argument("--fam-cap-density", default=None, metavar="SPEC",
                    help="override per-family enabled-lane density "
                         "caps as fam=k,fam2=k2 (e.g. "
                         "Receive=8,Timeout=2): cap_f = chunk * "
                         "min(lanes_f, k).  Tunes cap-overflow "
                         "replays without editing engine/expand.py; "
                         "unknown families / non-positive k are "
                         "rejected with a clear error")
    pc.add_argument("--stats-json", default=None, metavar="FILE",
                    help="write the run stats JSON (incl. "
                         "levels_fused/burst_bailouts) to FILE")
    _add_obs_flags(pc)
    pc.add_argument("--no-store", action="store_true",
                    help="do not retain states (no traces; less memory)")
    pc.add_argument("--max-violations", type=int, default=5)
    pc.add_argument("--checkpoint", default=None, metavar="FILE",
                    help="write a resumable checkpoint every "
                         "--checkpoint-every levels (tpu engine; TLC's "
                         "states/ dir counterpart)")
    pc.add_argument("--checkpoint-every", type=int, default=5,
                    metavar="N",
                    help="levels between checkpoints (each checkpoint "
                         "is a full snapshot incl. the visited set and "
                         "any trace archives — frequent checkpoints on "
                         "deep store_states runs are I/O-heavy)")
    pc.add_argument("--resume", default=None, metavar="FILE",
                    help="resume a checkpointed run (final counts are "
                         "identical to an uninterrupted run).  A torn "
                         "or corrupt head falls back to the previous "
                         "valid checkpoint in the last-K chain with a "
                         "named warning")
    pc.add_argument("--ckpt-keep", type=int, default=2, metavar="K",
                    help="checkpoint-chain depth: keep the last K "
                         "checkpoints (FILE, FILE.1, ...), each with "
                         "a sha256 integrity sidecar, so a crash "
                         "mid-write never strands the run (default 2; "
                         "1 = the historical single file)")
    pc.add_argument("--resume-portable", action="store_true",
                    help="shape-portable resume (needs --spill): "
                         "re-partition ANY engine family's checkpoint "
                         "— classic, spill, or a mesh of any device "
                         "count — onto this engine by re-inserting "
                         "the visited key set and re-routing the "
                         "frontier (resil/portable)")
    pc.add_argument("--retries", type=int, default=0, metavar="N",
                    help="supervised retry/backoff (resil/supervisor): "
                         "on a transient failure (lost runtime, "
                         "device error), reinit the backend and "
                         "resume from the newest valid checkpoint, up "
                         "to N times with bounded exponential backoff "
                         "+ jitter; attempts are stamped into the "
                         "ledger and heartbeat")
    pc.add_argument("--backoff", type=float, default=2.0, metavar="S",
                    help="base backoff seconds for --retries "
                         "(doubles per attempt, capped at 60s, "
                         "deterministic jitter)")
    pc.add_argument("--chaos", default=None, metavar="SPEC",
                    help="deterministic fault injection "
                         "(resil/chaos): e.g. "
                         "'dispatch:every=2;ckpt_torn:at=1' — seeded "
                         "schedule firing at named engine sites "
                         "(dispatch, ckpt_torn, ckpt_corrupt, "
                         "archive, host_table, wave_kill), so every "
                         "recovery path is testable on CPU")
    pc.add_argument("--seed-trace", default=None, metavar="FILE",
                    help="punctuated search: explore only extensions of "
                         "the seed state(s) in FILE (emitted by `trace "
                         "--emit-seed`; the engine analog of the spec's "
                         "hard-coded prefix pins, raft.tla:1198-1234)")
    # cfg toggles, check-only (trace derives its invariant from --target):
    # ADD to the cfg's lists, mirroring TLC's additive repeated blocks
    pc.add_argument("--invariant", dest="invariants",
                    action="append", default=None, metavar="NAME",
                    help="enable an extra invariant (repeatable) — the "
                         "CLI analog of uncommenting the cfg's "
                         "Test-cases block")
    pc.add_argument("--constraint", dest="constraint_overrides",
                    action="append", default=None, metavar="NAME",
                    help="enable an extra CONSTRAINT (repeatable)")
    pc.add_argument("--action-constraint", dest="action_constraints",
                    action="append", default=None, metavar="NAME",
                    help="enable an extra ACTION_CONSTRAINT (repeatable)")
    pc.set_defaults(fn=cmd_check)

    # --target help comes from the per-spec scenario registries
    # (SpecIR.scenario_properties) so new sim-reachable targets cannot
    # drift out of the help text
    from .spec import get_spec
    target_help = ("scenario property of the active --spec (raft: " +
                   ", ".join(get_spec("raft").scenario_properties) +
                   "; paxos: " +
                   ", ".join(get_spec("paxos").scenario_properties) +
                   ")")

    pt = sub.add_parser("trace", help="generate a scenario witness trace")
    common(pt)
    pt.add_argument("--target", required=True, help=target_help)
    pt.add_argument("--emit-seed", default=None, metavar="FILE",
                    help="write the witness end state to FILE as a seed "
                         "for `check --seed-trace` (punctuated search)")
    pt.set_defaults(fn=cmd_trace)

    ps = sub.add_parser(
        "simulate",
        help="random-walk scenario hunt (TLC -simulate analogue): W "
             "vmapped walkers sample enabled actions uniformly — for "
             "configs beyond the exhaustive stack's reach")
    common(ps)
    ps.add_argument("--target", required=True, help=target_help)
    ps.add_argument("--walkers", type=int, default=256,
                    help="fleet width W (one vmapped lane per walker)")
    ps.add_argument("--steps", type=int, default=10000,
                    help="synchronous fleet steps before giving up")
    ps.add_argument("--steps-per-dispatch", type=int, default=256,
                    help="walker steps fused into one device program "
                         "(the persistent-kernel loop length)")
    ps.add_argument("--seed", type=int, default=0,
                    help="PRNG seed; fixed seeds replay bit-identical "
                         "trajectories across runs and --walkers "
                         "shardings")
    ps.add_argument("--policy", choices=("punctuated", "tlc"),
                    default="punctuated",
                    help="restart policy: 'punctuated' (default) "
                         "resamples pruned successors and restarts "
                         "from per-walker scenario-ladder bases; "
                         "'tlc' is exact TLC -simulate shape (abandon "
                         "the walk on any pruned successor)")
    ps.add_argument("--bloom-bits", type=int, default=24,
                    help="log2 bits of the novelty Bloom filter behind "
                         "est_distinct_states")
    ps.add_argument("--mesh", action="store_true",
                    help="shard the fleet across all local devices "
                         "(pmapped per-device cohorts)")
    ps.add_argument("--trace-out", default=None, metavar="FILE",
                    help="write the witness trace (labels) as JSON")
    ps.add_argument("--emit-seed", default=None, metavar="FILE",
                    help="write the witness end state as a seed for "
                         "`check --seed-trace` (simulation feeds "
                         "punctuated exhaustive search)")
    ps.add_argument("--stats-json", default=None, metavar="FILE",
                    help="write the run stats JSON to FILE")
    _add_obs_flags(ps)
    ps.set_defaults(fn=cmd_simulate)

    pb = sub.add_parser(
        "batch",
        help="multi-tenant batched checking: many (spec, config) jobs "
             "packed into one device program per shape bucket, with "
             "fingerprint-keyed result caching (README 'Batch / "
             "serving' documents the JSONL job format)")
    pb.add_argument("--jobs", default=None, metavar="FILE",
                    help="JSONL job file: one job object per line "
                         "(blank lines and #-comments skipped)")
    pb.add_argument("--job", action="append", default=None,
                    metavar="JSON",
                    help="inline job object (repeatable), same schema "
                         "as a --jobs line")
    pb.add_argument("--cache-dir", default=None, metavar="DIR",
                    help="result cache: jobs whose (spec, config, "
                         "engine-options) fingerprints match a cached "
                         "result are answered with zero device "
                         "dispatches; results persist across "
                         "invocations")
    pb.add_argument("--cache-max-bytes", type=int, default=None,
                    metavar="N",
                    help="LRU-by-bytes cache bound: every completed "
                         "job's put trims the --cache-dir back under "
                         "N bytes, least-recently-used payloads "
                         "first (default: unbounded, the historical "
                         "behavior)")
    pb.add_argument("--executable-cache", default=None, metavar="DIR",
                    help="persistent AOT executable cache (serve/"
                         "exec_cache): bucket executables serialize "
                         "to DIR around .lower().compile(), so a "
                         "service restart re-loads them instead of "
                         "re-paying the 30-50s TPU compiles; on a "
                         "backend that cannot serialize executables "
                         "every entry reads as a labeled miss "
                         "(counted in the summary/ledger), never a "
                         "crash")
    pb.add_argument("--executable-cache-max-bytes", type=int,
                    default=None, metavar="N",
                    help="LRU-by-bytes bound on the executable cache "
                         "directory (entries are MBs each on TPU): "
                         "every store trims --executable-cache back "
                         "under N bytes, least-recently-USED entries "
                         "first (recency = mtime, refreshed on warm "
                         "loads; the just-stored entry is never the "
                         "victim; default: unbounded)")
    pb.add_argument("--sequential", action="store_true",
                    help="run each job on its own engine instead of "
                         "the batched path (the honest A/B reference "
                         "— N jobs pay N compiles)")
    pb.add_argument("--wave-state", default=None, metavar="DIR",
                    help="preemptible waves (serve/wavestate): "
                         "persist every live job's carry slice at "
                         "each wave boundary, so a killed run "
                         "resumes finished jobs from --cache-dir and "
                         "stragglers mid-BFS — bit-exact per job")
    pb.add_argument("--wave-yield", type=int, default=None,
                    metavar="N",
                    help="preemption: a wave yields its lanes after "
                         "N batched device calls while other jobs "
                         "wait (higher Job priority runs first); "
                         "parked jobs continue in a later wave")
    pb.add_argument("--max-wave", type=int, default=None, metavar="N",
                    help="jobs-per-wave ceiling (default: 8 per mesh "
                         "device); shrink it to force parking or to "
                         "bound wave memory")
    pb.add_argument("--wave-mesh", default="auto",
                    metavar="auto|N|JxS|off",
                    help="shard each batched wave across a 2-D "
                         "(jobs, state) mesh of local devices: 'auto' "
                         "(default) = all local devices on the job "
                         "axis (state shards kick in when a bucket's "
                         "ceiling exceeds the per-device budget), "
                         "'off' = the single-device wave, N = the "
                         "first N devices on the job axis, JxS (e.g. "
                         "4x2) = J job rows x S state shards so one "
                         "huge job's visited table/rings span S "
                         "devices; per-job results are bit-exact in "
                         "every mode")
    pb.add_argument("--retries", type=int, default=0, metavar="N",
                    help="re-run the job list up to N times on a "
                         "transient failure, with bounded exponential "
                         "backoff — incremental via --cache-dir + "
                         "--wave-state")
    pb.add_argument("--backoff", type=float, default=2.0, metavar="S",
                    help="base backoff seconds for --retries")
    pb.add_argument("--chaos", default=None, metavar="SPEC",
                    help="deterministic fault injection (resil/"
                         "chaos); 'wave_kill:at=1' is the "
                         "deterministic SIGKILL stand-in the CI "
                         "chaos smoke uses")
    pb.add_argument("--sym-canon",
                    choices=("auto", "sort", "minperm"),
                    default="auto",
                    help="symmetry canonicalization for every bucket "
                         "engine and solo fallback (see check "
                         "--sym-canon); part of the executable cache "
                         "key — sort and minperm never share a "
                         "compiled bucket")
    pb.add_argument("--stats-json", default=None, metavar="FILE",
                    help="write the batch summary + per-job reports "
                         "as one JSON file")
    pb.add_argument("--verbose", "-v", action="store_true")
    _add_obs_flags(pb)
    pb.set_defaults(fn=cmd_batch)

    pd = sub.add_parser(
        "serve",
        help="persistent checking daemon: watch a spool directory "
             "(and/or tail a JSONL stream) for job files, claim them "
             "atomically, drain them through the shared wave "
             "scheduler, and write one atomic result JSON + done/ "
             "marker per job; SIGTERM drains gracefully (README "
             "'Daemon service' documents the spool protocol)")
    pd.add_argument("--spool", required=True, metavar="DIR",
                    help="spool root: incoming/ claimed/ rejected/ "
                         "results/ done/ are created under it; "
                         "clients write-then-rename one JSON job "
                         "object per file (trailing newline) into "
                         "incoming/")
    pd.add_argument("--stream", default=None, metavar="FILE",
                    help="also tail this append-only JSONL job "
                         "stream: each complete appended line "
                         "materializes as a spool submission "
                         "(stream-<n>); the consumed offset persists "
                         "across restarts")
    pd.add_argument("--poll", type=float, default=0.5, metavar="SEC",
                    help="spool poll interval while idle "
                         "(default 0.5)")
    pd.add_argument("--grace", type=float, default=5.0, metavar="SEC",
                    help="seconds an incomplete submission (no "
                         "trailing newline — a writer mid-write) may "
                         "sit in incoming/ before it quarantines as "
                         "torn (default 5)")
    pd.add_argument("--max-idle-polls", type=int, default=None,
                    metavar="N",
                    help="drain and exit 0 after N consecutive empty "
                         "polls (default: run until SIGTERM; CI "
                         "smokes use this for bounded runs)")
    pd.add_argument("--cache-dir", default=None, metavar="DIR",
                    help="result cache directory (default: "
                         "SPOOL/cache) — duplicate submissions are "
                         "answered from it with zero device "
                         "dispatches")
    pd.add_argument("--cache-max-bytes", type=int, default=None,
                    metavar="N",
                    help="LRU-by-bytes result-cache bound (see "
                         "batch --cache-max-bytes)")
    pd.add_argument("--executable-cache", default=None, metavar="DIR",
                    help="persistent AOT executable cache: a warm "
                         "daemon restart performs ZERO bucket "
                         "compiles (see batch --executable-cache)")
    pd.add_argument("--executable-cache-max-bytes", type=int,
                    default=None, metavar="N",
                    help="LRU-by-bytes bound on the executable cache "
                         "(see batch --executable-cache-max-bytes)")
    pd.add_argument("--wave-state", default=None, metavar="DIR",
                    help="wave-state directory (default: SPOOL/waves) "
                         "— live jobs persist their carry at every "
                         "wave boundary, so a killed daemon resumes "
                         "stragglers mid-BFS bit-exact on restart")
    pd.add_argument("--wave-yield", type=int, default=None,
                    metavar="N",
                    help="fairness: a wave yields its lanes after N "
                         "batched device calls while other claimed "
                         "jobs wait (higher Job priority runs first)")
    pd.add_argument("--max-wave", type=int, default=None, metavar="N",
                    help="jobs-per-wave ceiling (default: 8 per mesh "
                         "device; see batch --max-wave)")
    pd.add_argument("--wave-mesh", default="auto",
                    metavar="auto|N|JxS|off",
                    help="2-D (jobs, state) mesh sharding for every "
                         "wave (see batch --wave-mesh); the daemon "
                         "restart matrix is portable — a restart "
                         "under ANY mesh shape (2-D included) "
                         "resumes the parked wave state bit-exact")
    pd.add_argument("--retries", type=int, default=0, metavar="N",
                    help="re-run a failed serve cycle up to N times "
                         "with bounded exponential backoff "
                         "(incremental via the result cache + wave "
                         "state); exhaustion exits 3")
    pd.add_argument("--backoff", type=float, default=2.0, metavar="S",
                    help="base backoff seconds for --retries")
    pd.add_argument("--chaos", default=None, metavar="SPEC",
                    help="deterministic fault injection (resil/"
                         "chaos); 'intake' faults the spool scan, "
                         "'wave_kill:at=1' is the deterministic "
                         "SIGKILL stand-in the daemon smoke uses")
    pd.add_argument("--sym-canon",
                    choices=("auto", "sort", "minperm"),
                    default="auto",
                    help="symmetry canonicalization for every bucket "
                         "engine (see batch --sym-canon)")
    pd.add_argument("--verbose", "-v", action="store_true")
    _add_obs_flags(pd)
    pd.set_defaults(fn=cmd_serve)

    po = sub.add_parser(
        "obs",
        help="query the run registry: ls (run table), show RUN, "
             "diff A B (parity verdict + span deltas), regress "
             "(verdict vs a prior run or committed baseline; exit "
             "nonzero on count mismatch / span-ratio regression)")
    osub = po.add_subparsers(dest="obs_cmd", required=True)

    def _reg_flag(sp):
        sp.add_argument("--registry", required=True, metavar="DIR",
                        help="the registry directory earlier runs "
                             "recorded into")

    ols = osub.add_parser("ls", help="list recorded runs (newest last)")
    _reg_flag(ols)
    ols.add_argument("--spec", default=None,
                     help="only runs of this spec frontend")
    ols.add_argument("--cmd", dest="cmd_filter", default=None,
                     help="only runs of this command (check/simulate/"
                          "batch/serve/deep_run/bench)")
    ols.add_argument("--status", default=None,
                     help="only runs with this exit status "
                          "(finished/failed, or a daemon's "
                          "done/draining)")

    oshow = osub.add_parser(
        "show", help="one run's full record (counters, span rollups, "
                     "resource peaks, artifact paths) as JSON")
    _reg_flag(oshow)
    oshow.add_argument("run", help="run id, unique prefix, or 'last'")

    odiff = osub.add_parser(
        "diff", help="machine-readable diff of two runs: count/"
                     "level-size parity verdict, per-phase span "
                     "deltas, mode-flag drift by name; exit 1 on "
                     "count mismatch")
    _reg_flag(odiff)
    odiff.add_argument("run_a", help="run id, unique prefix, or 'last'")
    odiff.add_argument("run_b", help="run id, unique prefix, or 'last'")

    oreg = osub.add_parser(
        "regress", help="regression verdict of RUN against a prior "
                        "registry run or a committed baseline file; "
                        "exit 1 on regression, 2 on usage error")
    _reg_flag(oreg)
    oreg.add_argument("run", help="run id, unique prefix, or 'last'")
    oreg.add_argument("--against", default=None, metavar="RUN",
                      help="baseline = this prior registry run")
    oreg.add_argument("--baseline", default=None, metavar="FILE",
                      help="baseline = a committed JSON file: a "
                           "--stats-json payload, a bench headline "
                           "object, or a BENCH_*.json A/B file "
                           "(then --baseline-row picks the row)")
    oreg.add_argument("--baseline-row", default=None, metavar="KEY",
                      help="row key inside a BENCH file's 'rows' map")
    oreg.add_argument("--max-span-ratio", type=float, default=None,
                      metavar="R",
                      help="also fail when a shared phase's span time "
                           "exceeds R x the baseline's (phases under "
                           "--min-seconds in the baseline are exempt "
                           "— CI wall-clock noise)")
    oreg.add_argument("--min-seconds", type=float, default=0.05,
                      metavar="S",
                      help="span-ratio floor: baseline phases shorter "
                           "than S seconds never trip (default 0.05)")
    po.set_defaults(fn=cmd_obs)

    args = p.parse_args(argv)
    from .utils import enable_compilation_cache
    enable_compilation_cache()
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
