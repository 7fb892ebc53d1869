"""Unified observability layer (raft_tla_tpu/obs): span recorder,
metrics registry, ledger, heartbeat — and the cross-engine telemetry
parity the registry exists to guarantee.

The parity test is the structural guard against the PR-5 drift class
(`levels_fused` counted differently per harvest loop): all five
engines run the same tiny config and must emit the identical registry
key set, with the burst counters byte-equal between the ledger's final
record, the --stats-json payload and the checkpoint meta.
"""

import json
import os

import numpy as np
import pytest

from raft_tla_tpu.config import Bounds, ModelConfig, NEXT_ASYNC
from raft_tla_tpu.obs import (CHECK_COUNTER_KEYS, BURST_COUNTER_KEYS,
                              DEDUP_COUNTER_KEYS, SIM_DISPATCH_KEYS,
                              Heartbeat,
                              MetricsRegistry, Obs, RunLedger,
                              SpanRecorder, check_stats)
from raft_tla_tpu.obs.heartbeat import read_heartbeat

# the same tiny config for every engine (test_sharded's micro: VIEW-
# only constraints so count parity is representative-insensitive)
TINY = ModelConfig(
    n_servers=2, init_servers=(0, 1), values=(1,),
    max_inflight_override=2, next_family=NEXT_ASYNC, symmetry=False,
    constraints=("BoundedInFlightMessages", "BoundedRequestVote",
                 "BoundedLogSize", "BoundedTerms"),
    invariants=("ElectionSafety", "LogMatching"),
    bounds=Bounds.make(max_log_length=1, max_timeouts=1,
                       max_client_requests=1))


# ---------------------------------------------------------------------
# unit tests (smoke tier: no device programs beyond import)
# ---------------------------------------------------------------------


@pytest.mark.smoke
def test_metrics_registry_is_strict():
    m = MetricsRegistry()
    m.register("a", 1)
    m.inc("a", 2)
    assert m.get("a") == 3
    with pytest.raises(ValueError):
        m.register("a")            # double registration
    with pytest.raises(KeyError):
        m.set("typo", 1)           # undeclared counter fails loudly
    assert m.as_dict() == {"a": 3}


@pytest.mark.smoke
def test_check_result_counters_are_registry_views():
    from raft_tla_tpu.engine.bfs import CheckResult
    r = CheckResult(distinct_states=7, generated_states=9)
    r.levels_fused += 2
    r.depth = 5
    # the attribute IS the registry entry — one store, no copies
    assert r.metrics.get("levels_fused") == 2
    assert r.metrics.get("depth") == 5
    assert tuple(r.metrics.keys()) == CHECK_COUNTER_KEYS


@pytest.mark.smoke
def test_check_stats_keys_byte_compatible():
    """--stats-json keys must match the pre-registry CLI output
    exactly (acceptance: byte-compatible in keys)."""
    from raft_tla_tpu.engine.bfs import CheckResult
    r = CheckResult(distinct_states=10, generated_states=20, depth=3)
    # engine payload (fp_bits given)
    out = check_stats(r.metrics.as_dict(), 1.5, 0, fp_bits=64)
    assert tuple(out.keys()) == (
        "distinct_states", "generated_states", "depth", "seconds",
        "states_per_sec", "dedup_hit_rate", "violations", "fp_bits",
        "expected_fp_collisions", "levels_fused", "burst_dispatches",
        "burst_bailouts", "guard_matmul", "delta_matmul",
        "sym_canon", "dedup_walk_iters", "dedup_probe_steps",
        "dedup_rounds", "dedup_claim_losses")
    # oracle payload (no engine telemetry)
    out = check_stats(r.metrics.as_dict(), 1.5, 2)
    assert tuple(out.keys()) == (
        "distinct_states", "generated_states", "depth", "seconds",
        "states_per_sec", "dedup_hit_rate", "violations")
    # pin_interior_states appears only when nonzero, after violations
    r.pin_interior_states = 4
    out = check_stats(r.metrics.as_dict(), 1.5, 0, fp_bits=64)
    keys = list(out.keys())
    assert keys.index("pin_interior_states") == \
        keys.index("violations") + 1


@pytest.mark.smoke
def test_span_recorder_nesting_and_file(tmp_path):
    path = str(tmp_path / "tl.json")
    rec = SpanRecorder(path)
    with rec.span("outer"):
        with rec.span("inner"):
            pass
        with rec.span("inner"):
            pass
    rec.close()
    events = json.load(open(path))
    assert [e["name"] for e in events] == ["inner", "inner", "outer"]
    for e in events:
        assert e["ph"] == "X" and e["ts"] >= 0 and e["dur"] >= 0
    outer = events[-1]
    for inner in events[:2]:
        # proper nesting: inner spans inside the outer interval
        assert inner["ts"] >= outer["ts"]
        assert inner["ts"] + inner["dur"] <= \
            outer["ts"] + outer["dur"] + 1.0
    tot = rec.totals()
    assert tot["inner"]["count"] == 2 and tot["outer"]["count"] == 1


@pytest.mark.smoke
def test_span_recorder_killed_run_file_parses(tmp_path):
    """A run killed mid-span-stream must leave a loadable timeline
    (missing ] only — the trace-event spec makes it optional)."""
    path = str(tmp_path / "tl.json")
    rec = SpanRecorder(path)
    with rec.span("a"):
        pass
    with rec.span("b"):
        pass
    # no close(): simulate the kill; repair exactly as Perfetto does
    text = open(path).read()
    assert not text.rstrip().endswith("]")
    events = json.loads(text.rstrip().rstrip(",") + "]")
    assert [e["name"] for e in events] == ["a", "b"]


@pytest.mark.smoke
def test_heartbeat_and_ledger(tmp_path):
    hb_path = str(tmp_path / "hb.json")
    hb = Heartbeat(hb_path)
    hb.beat(depth=3, states=42)
    obj = read_heartbeat(hb_path)
    assert obj["depth"] == 3 and obj["states_enqueued"] == 42
    assert obj["pid"] == os.getpid() and obj["status"] == "running"
    hb.beat(depth=4, states=50, status="finished")
    assert read_heartbeat(hb_path)["status"] == "finished"
    # no .tmp leftover (write-then-rename)
    assert not os.path.exists(hb_path + ".tmp")

    led_path = str(tmp_path / "run.jsonl")
    led = RunLedger(led_path)
    led.record({"kind": "level", "depth": 1})
    led.record({"kind": "burst", "depth": 4})
    # readable BEFORE close: the killed-run contract
    lines = [json.loads(x) for x in open(led_path)]
    assert [x["kind"] for x in lines] == ["level", "burst"]
    assert all("ts" in x and "t_mono" in x for x in lines)
    led.close()


@pytest.mark.smoke
def test_obs_dispatch_record_shape(tmp_path):
    led_path = str(tmp_path / "run.jsonl")
    obs = Obs(ledger=RunLedger(led_path),
              heartbeat=Heartbeat(str(tmp_path / "hb.json")))
    obs.start()
    # the dispatch-passed depth must win over the registry's stale
    # `depth` counter (finalized only at run end)
    obs.dispatch(kind="level", depth=9, frontier=5,
                 metrics={"distinct_states": 100,
                          "generated_states": 200, "depth": 0})
    obs.finish(depth=9, states=100)
    recs = [json.loads(x) for x in open(led_path)]
    # ISSUE 17: start() writes a kind="meta" row (run identity) and
    # the resource sampler a kind="resource" row — the dispatch record
    # itself is the single kind="level" row
    (rec,) = [x for x in recs if x["kind"] == "level"]
    assert rec["depth"] == 9
    assert rec["frontier"] == 5 and rec["rss_bytes"] > 0
    assert rec["dedup_hit_rate"] == 0.5
    hb = read_heartbeat(str(tmp_path / "hb.json"))
    assert hb["depth"] == 9 and hb["status"] == "finished"


# ---------------------------------------------------------------------
# cross-engine telemetry parity (the acceptance test): all five
# engines, same tiny config, identical registry key sets; burst
# counters consistent between ledger, --stats-json payload and
# checkpoint meta
# ---------------------------------------------------------------------


def _run_with_obs(name, make_engine, tmp_path, checkpoint=True):
    led_path = str(tmp_path / f"{name}.jsonl")
    hb_path = str(tmp_path / f"{name}.hb.json")
    ckpt_path = str(tmp_path / f"{name}.ckpt")
    obs = Obs(ledger=RunLedger(led_path), heartbeat=Heartbeat(hb_path))
    obs.start()
    eng = make_engine()
    kw = dict(checkpoint_path=ckpt_path, checkpoint_every=1) \
        if checkpoint else {}
    r = eng.check(obs=obs, **kw)
    obs.finish(depth=int(r.depth), states=int(r.distinct_states))
    recs = [json.loads(x) for x in open(led_path)]
    assert recs, f"{name}: no ledger records"
    stats = check_stats(r.metrics.as_dict(), r.seconds,
                        len(r.violations), fp_bits=64)
    meta = None
    if checkpoint:
        z = np.load(ckpt_path, allow_pickle=False)
        meta = json.loads(str(z["meta"]))
        z.close()
    return r, recs, stats, meta, read_heartbeat(hb_path)


def _engine_cases():
    from raft_tla_tpu.engine.bfs import Engine
    from raft_tla_tpu.engine.spill import SpillEngine
    from raft_tla_tpu.parallel.mesh import ShardedEngine
    from raft_tla_tpu.parallel.spill_mesh import SpilledShardedEngine

    return {
        "bfs": (lambda: Engine(TINY, chunk=64, store_states=False),
                True),
        "spill": (lambda: SpillEngine(
            TINY, chunk=64, store_states=False, seg=1 << 10,
            vcap=1 << 12, sync_every=2), True),
        "mesh": (lambda: ShardedEngine(TINY, chunk=64,
                                       store_states=False), True),
        # SpilledShardedEngine does not checkpoint yet (its check
        # raises) — ledger/stats parity only
        "spill_mesh": (lambda: SpilledShardedEngine(
            TINY, chunk=64, store_states=False, lcap=1 << 11), False),
    }


def _telemetry_parity(name, tmp_path):
    """One engine family on the tiny config: registry key set,
    ledger/stats/checkpoint-meta burst-counter agreement, heartbeat
    parity."""
    make, ckpt = _engine_cases()[name]
    r, recs, stats, meta, hb = _run_with_obs(
        name, make, tmp_path, checkpoint=ckpt)
    # 1. the registry key set — structural identity across engines
    assert tuple(r.metrics.keys()) == CHECK_COUNTER_KEYS, name
    # 2. every DISPATCH record carries every registry key (the
    #    kind="meta"/"resource" rows ISSUE 17 added are bookkeeping,
    #    not dispatches)
    drecs = [x for x in recs if x.get("kind") in ("level", "burst")]
    assert drecs, f"{name}: no dispatch records"
    for rec in drecs:
        missing = set(CHECK_COUNTER_KEYS) - set(rec)
        assert not missing, f"{name}: ledger record lacks {missing}"
    # 3. burst counters: ledger final record == stats payload
    last = recs[-1]
    for k in BURST_COUNTER_KEYS:
        assert last[k] == stats[k], (name, k)
    # ... == checkpoint meta (the third historical copy)
    if meta is not None:
        for k in BURST_COUNTER_KEYS:
            assert meta[k] == stats[k], (name, k)
        assert meta["distinct"] == stats["distinct_states"], name
    # 4. heartbeat final depth == the run's reported depth
    assert hb["depth"] == r.depth == stats["depth"], name
    assert hb["states_enqueued"] == r.distinct_states, name
    assert hb["status"] == "finished", name
    # the fused path engaged (so the burst counters are live, not
    # trivially zero) — every engine's default burst must fire on
    # this tiny space
    assert r.levels_fused > 0, name
    # cross-engine count identity, anchored to the shared ORACLE
    # reference (conftest session cache) so every parametrized variant
    # asserts it independently — no ordering or selection dependence
    from conftest import cached_explore
    w = cached_explore(TINY)
    assert (r.distinct_states, r.depth, tuple(r.level_sizes)) == \
        (w.distinct_states, w.depth, tuple(w.level_sizes)), name


@pytest.mark.parametrize("name", ["bfs", "spill"])
def test_telemetry_parity_engine(name, tmp_path):
    """Fast representatives (tier-1 budget, round-13 suite diet): the
    single-device families.  The mesh variants below run the same body
    slow-marked — the MetricsRegistry single-source design plus the
    mesh count differentials elsewhere keep the fast signal."""
    _telemetry_parity(name, tmp_path)


@pytest.mark.slow
@pytest.mark.parametrize("name", ["mesh", "spill_mesh"])
def test_telemetry_parity_engine_mesh_slow(name, tmp_path):
    _telemetry_parity(name, tmp_path)


def test_burst_bailout_reuses_warmed_per_level_executable():
    """The BENCH_r08 recompile leak (round-9 satellite): in burst mode
    the per-level path runs only when a burst BAILS, and its cold
    compile used to land mid-run inside a level_dispatch span (11.6 s
    over 9 dispatches vs 1.65 s over 30 in per-level mode).  Pin the
    fix: the per-level executables warm at run start inside ONE
    compile span per mode, and the post-bail dispatches reuse the
    warmed executable — the step jit compiles exactly once (the
    density override maxes every family cap so no growth retrace can
    blur the count)."""
    from raft_tla_tpu.engine.bfs import Engine
    from raft_tla_tpu.engine.expand import _FAMILY_DENSITY
    dens = {nm: 1 << 10 for nm in _FAMILY_DENSITY}
    for mode, burst in (("burst", True), ("per_level", False)):
        rec = SpanRecorder()
        obs = Obs(spans=rec)
        # chunk=16 -> burst ring of 64 states: TINY's mid-run levels
        # outgrow it, so bursts engage on the tiny levels AND bail
        # mid-run, exercising the post-bail per-level path
        eng = Engine(TINY, chunk=16, store_states=False, burst=burst,
                     fam_density=dens)
        r = eng.check(obs=obs)
        tot = rec.totals()
        assert tot["compile"]["count"] == 1, (mode, tot)
        assert eng._step_jit._cache_size() == 1, mode
        assert eng._fin_jit._cache_size() == 1, mode
        if burst:
            # the leak path actually engaged: bursts committed levels,
            # bailed, and the per-level driver ran dispatches after
            assert r.levels_fused > 0
            assert r.burst_bailouts >= 1
            assert tot["level_dispatch"]["count"] >= 1
            assert r.depth - r.levels_fused >= 1


def test_setup_program_compiles_in_compile_span():
    """The fresh-start set-up program (Engine._setup_carry) compiles
    inside the traced check's ONE compile span, beside the step and
    finalize; a second check at the same capacities adds no cache
    entry.  The program is keyed on the exact root count: a 3-seed
    check adds one entry, which a second 3-seed check reuses."""
    from raft_tla_tpu.engine.bfs import Engine
    from raft_tla_tpu.models.explore import explore
    rec = SpanRecorder()
    obs = Obs(spans=rec)
    eng = Engine(TINY, chunk=64, store_states=False)
    warmed = []
    prewarm = eng._prewarm_perlevel

    def spy(*a):
        prewarm(*a)
        warmed.append(set(eng._setup_jit_cache))

    eng._prewarm_perlevel = spy
    eng.check(obs=obs)
    caps = (eng.LCAP, eng.VCAP, eng.FCAP, eng.OCAP)
    assert warmed == [{caps + (1,)}]
    assert rec.totals()["compile"]["count"] == 1
    eng.check(obs=obs)
    assert rec.totals()["compile"]["count"] == 1
    assert set(eng._setup_jit_cache) == {caps + (1,)}
    seeds = list(explore(TINY, max_depth=2, keep_states=True)
                 .states.values())
    for _ in range(2):
        eng.check(obs=obs, seed_states=seeds[:3], max_depth=2)
    assert set(eng._setup_jit_cache) == {caps + (1,), caps + (3,)}
    assert all(fn._cache_size() == 1
               for fn in eng._setup_jit_cache.values())
    assert eng._step_jit._cache_size() == 1
    assert eng._fin_jit._cache_size() == 1


# ---------------------------------------------------------------------
# dedup work counters and the check_setup span (engine/bfs): integer
# counts of a deterministic program, so exact equalities hold on CPU
# ---------------------------------------------------------------------


def _dedup(r):
    return {k: r.metrics.get(k) for k in DEDUP_COUNTER_KEYS}


@pytest.fixture(scope="module")
def dedup_runs():
    """Two traced checks on one burst engine (span totals after each),
    and one check each of two per-level engines whose tables differ
    16x in size (2^17 and 2^21 slots)."""
    from raft_tla_tpu.engine.bfs import Engine
    rec = SpanRecorder()
    obs = Obs(spans=rec)
    eng = Engine(TINY, chunk=64, store_states=False)
    first = eng.check(obs=obs)
    tot1 = rec.totals()
    second = eng.check(obs=obs)
    out = dict(eng=eng, first=first, second=second, tot1=tot1,
               tot2=rec.totals())
    for nm, vcap in (("small", 1 << 17), ("big", 1 << 21)):
        e = Engine(TINY, chunk=64, store_states=False, burst=False,
                   vcap=vcap)
        out[nm] = e.check()
        assert e.VCAP == vcap, nm      # no growth blurs the sizes
    return out


def test_dedup_counters_repeat_exactly_on_one_engine(dedup_runs):
    a, b = dedup_runs["first"], dedup_runs["second"]
    assert _dedup(a) == _dedup(b)
    assert a.distinct_states == b.distinct_states


def test_dedup_probe_steps_fall_on_a_16x_larger_table(dedup_runs):
    small, big = dedup_runs["small"], dedup_runs["big"]
    assert small.distinct_states == big.distinct_states
    # the same candidates meet a 16x emptier table: shorter chains
    assert 0 < big.dedup_probe_steps < small.dedup_probe_steps


@pytest.mark.parametrize("path", ["burst", "per_level"])
def test_dedup_counters_count_on_both_driver_paths(dedup_runs, path):
    r = dedup_runs["first" if path == "burst" else "small"]
    assert (r.levels_fused > 0) is (path == "burst")
    d = _dedup(r)
    assert all(v > 0 for v in d.values()), d
    # every outer round runs at least one walk iteration
    assert d["dedup_walk_iters"] >= d["dedup_rounds"]
    # the counters reach the --stats-json payload after the pinned keys
    stats = check_stats(r.metrics.as_dict(), 1.0, 0, fp_bits=64)
    assert list(stats)[-4:] == list(DEDUP_COUNTER_KEYS)
    assert {k: stats[k] for k in DEDUP_COUNTER_KEYS} == d


def test_check_setup_span_opens_once_per_check(dedup_runs):
    assert dedup_runs["tot1"]["check_setup"]["count"] == 1
    assert dedup_runs["tot2"]["check_setup"]["count"] == 2


def test_each_check_leaves_one_dedup_counter_sample(dedup_runs):
    """A traced check records its dedup counters once, on the span
    recorder: the totals after two equal checks hold two equal
    samples."""
    d = _dedup(dedup_runs["first"])
    for k, v in d.items():
        assert dedup_runs["tot1"][k] == {"count": 1, "seconds": 0.0,
                                         "sum": v, "min": v, "max": v}
        assert dedup_runs["tot2"][k] == {"count": 2, "seconds": 0.0,
                                         "sum": 2 * v, "min": v, "max": v}


@pytest.fixture(scope="module")
def replayed_run():
    """A per-level check from capacities too small for TINY: levels
    overflow, grow and replay."""
    from raft_tla_tpu.engine.bfs import Engine
    e = Engine(TINY, chunk=64, store_states=False, burst=False,
               lcap=256, fcap=64, ocap=64)
    r = e.check()
    assert (e.LCAP, e.FCAP) != (256, 64)     # something grew and replayed
    return r


@pytest.mark.parametrize("path", ["burst", "per_level", "replayed"])
def test_lane_counters_sum_to_the_generated_successors(
        dedup_runs, replayed_run, path):
    """Each family's enabled lanes over the committed levels: their sum
    is every generated successor (the roots aside), whichever level
    path ran the levels and however often a level replayed."""
    r = {"burst": dedup_runs["first"], "per_level": dedup_runs["small"],
         "replayed": replayed_run}[path]
    assert (r.levels_fused > 0) is (path == "burst")
    lanes = r.lanes_enabled
    assert set(lanes) == {"RequestVote", "BecomeLeader", "ClientRequest",
                          "AdvanceCommitIndex", "AppendEntries",
                          "UpdateTerm", "CocDiscard", "Receive", "Timeout"}
    assert sum(lanes.values()) == r.generated_states - 1
    assert lanes == dedup_runs["first"].lanes_enabled
    # and reach the --stats-json payload, last
    stats = check_stats(r.metrics.as_dict(), 1.0, 0, fp_bits=64,
                        lanes_enabled=lanes)
    assert list(stats)[-1] == "lanes_enabled"
    assert stats["lanes_enabled"] == lanes


def test_each_check_leaves_one_lane_counter_sample(dedup_runs):
    """A traced check records ``lanes_enabled.<Family>`` and
    ``lanes_kernel_path`` once: two equal checks leave two equal
    samples."""
    r, eng = dedup_runs["first"], dedup_runs["eng"]
    delta = set(eng.expander.delta_family_names)
    want = {f"lanes_enabled.{nm}": v for nm, v in r.lanes_enabled.items()}
    want["lanes_kernel_path"] = sum(
        v for nm, v in r.lanes_enabled.items() if nm not in delta)
    assert 0 < want["lanes_kernel_path"] < r.generated_states
    for k, v in want.items():
        assert dedup_runs["tot1"][k] == {"count": 1, "seconds": 0.0,
                                         "sum": v, "min": v, "max": v}, k
        assert dedup_runs["tot2"][k] == {"count": 2, "seconds": 0.0,
                                         "sum": 2 * v, "min": v, "max": v}


@pytest.mark.smoke
def test_span_recorder_counter_samples(tmp_path):
    from raft_tla_tpu.obs.report import format_span_totals
    path = str(tmp_path / "tl.json")
    rec = SpanRecorder(path)
    with rec.span("a"):
        rec.counters({"walk": 3, "steps": 7})
    rec.counters({"walk": 5, "steps": 7})
    rec.close()
    events = json.load(open(path))
    assert [(e["name"], e["ph"]) for e in events] == [
        ("counters", "C"), ("a", "X"), ("counters", "C")]
    assert events[2]["args"] == {"walk": 5, "steps": 7}
    tot = rec.totals()
    assert tot["walk"] == {"count": 2, "seconds": 0.0, "sum": 8,
                           "min": 3, "max": 5}
    assert tot["steps"]["min"] == tot["steps"]["max"] == 7
    # span rollups render the spans alone
    assert format_span_totals(tot) == f"a={tot['a']['seconds']:.2f}s/1"


def test_second_traced_check_runs_no_prewarm(dedup_runs):
    """The prewarm runs once per capacity set: the second traced check
    on one engine opens no compile span, so it dispatches the same
    programs an untraced check does."""
    eng = dedup_runs["eng"]
    assert dedup_runs["tot1"]["compile"]["count"] == 1
    assert dedup_runs["tot2"]["compile"]["count"] == 1
    assert eng._step_jit._cache_size() == 1
    assert eng._fin_jit._cache_size() == 1


def test_telemetry_parity_sim_engine(tmp_path):
    """The fifth engine family: the sim ledger's per-dispatch records
    carry exactly the canonical SIM_DISPATCH_KEYS, consistent with the
    SimResult the run returns."""
    from raft_tla_tpu.sim.walker import SimEngine

    cfg = TINY.with_(invariants=("ElectionSafety",))
    led_path = str(tmp_path / "sim.jsonl")
    hb_path = str(tmp_path / "sim.hb.json")
    obs = Obs(ledger=RunLedger(led_path), heartbeat=Heartbeat(hb_path))
    obs.start()
    eng = SimEngine(cfg, walkers=8, max_depth=8, seed=0,
                    bloom_bits=12)
    r = eng.run(steps=24, steps_per_dispatch=8, stop_on_hit=False)
    # rerun through run(obs=...) — separate engine so the jit caches
    # stay warm from the first run
    r = SimEngine(cfg, walkers=8, max_depth=8, seed=0,
                  bloom_bits=12).run(steps=24, steps_per_dispatch=8,
                                     stop_on_hit=False, obs=obs)
    obs.finish(depth=int(r.steps_dispatched),
               states=int(r.walker_steps))
    recs = [json.loads(x) for x in open(led_path)]
    drecs = [x for x in recs if x.get("kind") == "sim"]
    assert drecs, "sim wrote no dispatch records"
    for rec in drecs:
        missing = set(SIM_DISPATCH_KEYS) - set(rec)
        assert not missing, f"sim ledger record lacks {missing}"
    last = recs[-1]
    # final record consistent with the returned SimResult
    assert last["steps_dispatched"] == r.steps_dispatched
    assert last["walker_steps"] == r.walker_steps
    assert last["restarts"] == r.restarts
    hb = read_heartbeat(hb_path)
    assert hb["depth"] == r.steps_dispatched
    assert hb["states_enqueued"] == r.walker_steps
