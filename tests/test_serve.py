"""Batched multi-tenant serving layer (serve/): batched ≡ sequential
bit-exactness, cache hit/miss paths, fallbacks, job parsing, the
multi-job observability surface, and (round 13) the constant-padding
bucket ceilings + persistent AOT executable cache.

One fast representative of each contract runs in tier-1; the
full-space duplicates are slow-marked (tier-1 budget, ROADMAP
standing constraint).
"""

import importlib.util
import json
import os
import pickle

import pytest

import jax

from raft_tla_tpu.config import Bounds, ModelConfig, NEXT_ASYNC
from raft_tla_tpu.engine.bfs import Engine
from raft_tla_tpu.serve import (ExecCache, Job, ResultCache,
                                job_from_dict, load_jobs, run_jobs)
from raft_tla_tpu.spec.paxos.config import PaxosConfig

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MICRO = ModelConfig(
    n_servers=2, init_servers=(0, 1), values=(1,),
    next_family=NEXT_ASYNC, symmetry=True, max_inflight_override=4,
    bounds=Bounds.make(max_log_length=1, max_timeouts=1,
                       max_client_requests=1))
PAX = PaxosConfig(n_servers=2, n_ballots=2, n_values=1)


def _het_raft(mll, mt):
    """A MICRO variant whose (max_log_length, max_timeouts) pair makes
    its depth-13 reachable count DISTINCT from its siblings — the
    heterogeneous-ceiling fixtures (each pair's count is pinned in
    test_heterogeneous_*; bench._ceiling_ab uses the same grid)."""
    return MICRO.with_(bounds=Bounds.make(
        max_log_length=mll, max_timeouts=mt, max_client_requests=2))


def _same(res, ref):
    assert (res.distinct_states, res.generated_states, res.depth) == \
        (ref.distinct_states, ref.generated_states, ref.depth)
    assert res.level_sizes == ref.level_sizes
    assert [(v.invariant, v.state_id) for v in res.violations] == \
        [(v.invariant, v.state_id) for v in ref.violations]


def _trace_key(trace):
    return [(label, repr(sv)) for label, sv in trace]


@pytest.mark.slow  # tier-1 budget (round 14): ~43s; batched ≡ solo
# parity (counts, violation ids, witness traces) stays fast via
# test_batched_violation_states_and_witness_parity, and
# tools/serve_smoke.py batches a mixed raft+paxos wave through the
# real CLI every CI run.
def test_batched_mixed_specs_bit_exact():
    """The tier-1 representative: a mixed raft+paxos job list through
    the batched path lands bit-exact against per-job sequential
    engines — counts, level sizes, violation ids AND witness traces —
    while compiling exactly one engine per (spec, bucket)."""
    jobs = [Job(MICRO, max_depth=4, label="r4"),
            Job(MICRO, max_depth=6, label="r6"),
            Job(PAX, max_depth=3, label="p3"),
            Job(PAX, label="pfull")]
    rep = run_jobs(jobs)
    assert rep.meta["buckets"] == 2
    assert rep.meta["engines_compiled"] == 2
    assert rep.meta["fallback_jobs"] == 0
    assert all(o.status == "done" for o in rep.outcomes)
    re_r, re_p = Engine(MICRO), Engine(PAX)
    _same(rep.outcomes[0].res, re_r.check(max_depth=4))
    _same(rep.outcomes[2].res, re_p.check(max_depth=3))
    ref6 = re_r.check(max_depth=6)
    _same(rep.outcomes[1].res, ref6)
    # witness-trace parity: the deepest raft state replays identically
    # from the per-job batched archives and the solo engine's
    last = ref6.distinct_states - 1
    assert _trace_key(rep.outcomes[1].trace(last)) == \
        _trace_key(re_r.trace(last))
    refp = re_p.check()
    _same(rep.outcomes[3].res, refp)
    lastp = refp.distinct_states - 1
    assert _trace_key(rep.outcomes[3].trace(lastp)) == \
        _trace_key(re_p.trace(lastp))
    # the stats stamps every job row carries
    row = rep.outcomes[3].report
    assert row["spec"] == "paxos" and row["status"] == "done"
    assert row["cache_key"].startswith("paxos-")


def test_batched_violation_states_and_witness_parity():
    """A job that FINDS a violation (ValueChosen as invariant, the
    trace-command idiom): the batched run reports the same violating
    state ids and replays the same witness trace as the sequential
    engine, and stop_on_violation gates identically."""
    vcfg = PAX.with_(invariants=("ValueChosen",))
    rep = run_jobs([Job(vcfg, label="vc")])
    o = rep.outcomes[0]
    assert o.status == "done"
    ref_eng = Engine(vcfg)
    ref = ref_eng.check(stop_on_violation=True)
    _same(o.res, ref)
    assert o.res.violations, "expected a ValueChosen witness"
    sid = o.res.violations[0].state_id
    assert _trace_key(o.trace(sid)) == _trace_key(ref_eng.trace(sid))
    det = o.report["violations_detail"]
    assert det and det[0]["invariant"] == "ValueChosen"
    assert det[0]["trace"] == [lbl for lbl, _ in ref_eng.trace(sid)]


def test_result_cache_hit_and_fingerprint_misses(tmp_path):
    """Cache round-trip: an identical job is served with ZERO device
    work; any changed fingerprint component (engine options, config)
    misses."""
    cache = ResultCache(str(tmp_path))
    rep1 = run_jobs([Job(PAX, max_depth=2, label="a")], cache=cache)
    assert rep1.meta["cache_hits"] == 0
    assert rep1.meta["batch_dispatches"] >= 1
    # identical (cfg, options) under a different label: a hit, no
    # engine, no dispatch
    rep2 = run_jobs([Job(PAX, max_depth=2, label="b")], cache=cache)
    assert rep2.meta["cache_hits"] == 1
    assert rep2.meta["batch_dispatches"] == 0
    assert rep2.meta["engines_compiled"] == 0
    o = rep2.outcomes[0]
    assert o.status == "cache_hit" and o.cache_hit
    assert o.report["distinct_states"] == \
        rep1.outcomes[0].report["distinct_states"]
    assert o.report["level_sizes"] == \
        rep1.outcomes[0].report["level_sizes"]
    # options-fingerprint misses: depth gate, stop-on-violation,
    # store toggle all key separately
    assert cache.get(Job(PAX, max_depth=3).cache_key()) is None
    assert cache.get(Job(PAX, max_depth=2,
                         stop_on_violation=False).cache_key()) is None
    assert cache.get(Job(PAX, max_depth=2,
                         store_states=False).cache_key()) is None
    # config-fingerprint miss
    assert cache.get(Job(PAX.with_(n_ballots=1),
                         max_depth=2).cache_key()) is None
    # the payload survives a fresh cache handle (disk round-trip)
    fresh = ResultCache(str(tmp_path))
    key = Job(PAX, max_depth=2).cache_key()
    assert fresh.get(key)["distinct_states"] == \
        rep1.outcomes[0].report["distinct_states"]


def test_ring_overflow_falls_back_sequential_exact():
    """A job whose frontier outgrows the per-job ring bails out of the
    batched program and re-runs solo — results stay exact and the
    fallback is reported honestly.  (Depth-capped: the tiny 16-chunk
    ring overflows by depth ~13 already, and the full 20k-state solo
    reference was most of this test's cost — tier-1 budget.)"""
    rep = run_jobs([Job(MICRO, max_depth=16, label="big")],
                   bucket_overrides=dict(chunk=16, vcap=1 << 10))
    assert rep.meta["fallback_jobs"] == 1
    o = rep.outcomes[0]
    assert o.status == "fallback"
    assert "re-run sequentially" in o.report["status_reason"]
    _same(o.res, Engine(MICRO).check(max_depth=16))


def test_job_from_dict_format_and_errors(tmp_path):
    cfg_path = os.path.join(_REPO, "configs", "tlc_membership",
                            "raft.cfg")
    job = job_from_dict({
        "spec": "raft", "config": cfg_path,
        "overrides": {"servers": 2, "values": [1], "max_inflight": 4,
                      "next": "NextAsync",
                      "bounds": {"max_log_length": 1,
                                 "max_timeouts": 1,
                                 "max_client_requests": 1}},
        "max_depth": 3, "label": "r"})
    assert job.cfg.n_servers == 2 and job.cfg.values == (1,)
    assert job.cfg.max_inflight == 4
    assert job.cfg.bounds.max_terms == 2       # derived: timeouts + 1
    assert job.max_depth == 3 and job.stop_on_violation
    pj = job_from_dict({"spec": "paxos",
                        "config": {"acceptors": 2, "ballots": 2,
                                   "values": 1},
                        "keep_going": True})
    assert pj.cfg == PAX.with_() and not pj.stop_on_violation
    # errors name the offending key
    with pytest.raises(ValueError, match="unknown job key.*'frobnicate'"):
        job_from_dict({"spec": "paxos", "frobnicate": 1})
    with pytest.raises(ValueError, match="unknown raft override.*'speed'"):
        job_from_dict({"spec": "raft", "config": cfg_path,
                       "overrides": {"speed": 11}})
    with pytest.raises(ValueError, match="unknown paxos config key 'qs'"):
        job_from_dict({"spec": "paxos", "config": {"qs": 3}})
    with pytest.raises(ValueError, match="raft-only"):
        job_from_dict({"spec": "paxos", "overrides": {"servers": 2}})
    with pytest.raises(ValueError, match="max_depth"):
        job_from_dict({"spec": "paxos", "max_depth": -1})
    # JSONL loader: comments/blank lines skipped, line numbers in errors
    p = tmp_path / "jobs.jsonl"
    p.write_text('# comment\n\n{"spec": "paxos", "max_depth": 2}\n')
    assert len(load_jobs(str(p))) == 1
    p.write_text('{"spec": "nope"}\n')
    with pytest.raises(ValueError, match="jobs.jsonl:1.*unknown spec"):
        load_jobs(str(p))


def test_cache_keys_are_spec_and_ir_scoped():
    """Same options, different specs/configs never collide: the key
    embeds the spec name, IR structure fingerprint and cfg repr."""
    k1 = Job(PAX, max_depth=4).cache_key()
    k2 = Job(MICRO, max_depth=4).cache_key()
    k3 = Job(PAX, max_depth=4).cache_key()
    assert k1 != k2 and k1 == k3
    assert k1.startswith("paxos-") and k2.startswith("raft-")


def test_watch_renders_multi_job_heartbeat(tmp_path):
    """tools/watch.py multi-job mode: a batch heartbeat's per-job map
    renders one status line per job."""
    from raft_tla_tpu.obs.heartbeat import Heartbeat
    spec = importlib.util.spec_from_file_location(
        "watch", os.path.join(_REPO, "tools", "watch.py"))
    watch = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(watch)
    hb_path = str(tmp_path / "hb.json")
    hb = Heartbeat(hb_path)
    hb.beat(depth=4, states=34, extra={"jobs": {
        "r4": {"depth": 4, "distinct": 29, "status": "done"},
        "p3": {"depth": 3, "distinct": 5, "status": "running"}}})
    line, code = watch.status_line(hb_path, None, stale_s=300)
    assert code == 0
    assert "job r4: depth 4  29 states  done" in line
    assert "job p3: depth 3  5 states  running" in line
    # single-run heartbeats render exactly as before
    hb2 = Heartbeat(str(tmp_path / "hb2.json"))
    hb2.beat(depth=2, states=9)
    line2, _ = watch.status_line(str(tmp_path / "hb2.json"), None, 300)
    assert "job " not in line2 and "\n" not in line2


def test_batch_obs_ledger_rows_and_heartbeat(tmp_path):
    """The obs threading: one kind='batch' ledger record per batched
    device call, one kind='job' row per job, per-job heartbeat map,
    and span timelines attributing bucket_compile vs batched_dispatch
    vs job_harvest."""
    from raft_tla_tpu.obs import Obs
    from raft_tla_tpu.obs.heartbeat import Heartbeat
    from raft_tla_tpu.obs.ledger import RunLedger
    from raft_tla_tpu.obs.spans import SpanRecorder
    ledger_path = str(tmp_path / "ledger.jsonl")
    rec = SpanRecorder()
    obs = Obs(spans=rec, ledger=RunLedger(ledger_path),
              heartbeat=Heartbeat(str(tmp_path / "hb.json")))
    obs.start()
    rep = run_jobs([Job(PAX, max_depth=2, label="p")], obs=obs)
    obs.finish(depth=2, states=int(
        rep.outcomes[0].res.distinct_states))
    recs = [json.loads(ln) for ln in open(ledger_path)]
    kinds = [r.get("kind") for r in recs]
    assert "batch" in kinds and "job" in kinds
    batch_rec = next(r for r in recs if r["kind"] == "batch")
    assert batch_rec["jobs_total"] == 1
    job_rec = next(r for r in recs if r["kind"] == "job")
    assert job_rec["label"] == "p" and job_rec["status"] == "done"
    hb = json.load(open(tmp_path / "hb.json"))
    assert hb["status"] == "finished" and "p" in hb["jobs"]
    totals = rec.totals()
    for nm in ("bucket_compile", "batched_dispatch", "job_harvest"):
        assert nm in totals and totals[nm]["count"] >= 1, (nm, totals)


# ---------------------------------------------------------------------------
# Constant-padding bucket ceilings (round 13): heterogeneous value
# bounds through ONE compiled bucket, bit-exact per job vs solo.
# ---------------------------------------------------------------------------


@pytest.mark.slow  # tier-1 budget (round 14): ~50s; the paxos hetero
# rep below stays fast and tools/serve_smoke.py runs a 4-distinct-
# bounds raft hetero wave on the real CLI every CI run.
def test_heterogeneous_raft_bounds_one_bucket_bit_exact():
    """Two raft jobs with DIFFERENT search bounds (so their reachable
    sets genuinely differ at the test depth) land in ONE padded bucket
    ceiling, compile one engine, and each result is bit-exact vs its
    own solo engine — counts, level sizes, violation ids, witness
    traces.  (The K=4 grid incl. paxos is the slow duplicate below;
    bench._ceiling_ab and tools/serve_smoke.py pin the K=4
    compile-once contract every run.)"""
    from raft_tla_tpu.spec import spec_of
    cfgs = [_het_raft(1, 1), _het_raft(2, 2)]
    assert len({repr(spec_of(c).serve_bucket(c)[0])
                for c in cfgs}) == 1
    rep = run_jobs([Job(c, max_depth=13, label=f"h{k}")
                    for k, c in enumerate(cfgs)])
    assert rep.meta["buckets"] == 1
    assert rep.meta["engines_compiled"] == 1
    assert rep.meta["fallback_jobs"] == 0
    counts = []
    for o, c in zip(rep.outcomes, cfgs):
        ref_eng = Engine(c)
        ref = ref_eng.check(max_depth=13)
        assert o.status == "done"
        _same(o.res, ref)
        last = ref.distinct_states - 1
        assert _trace_key(o.trace(last)) == \
            _trace_key(ref_eng.trace(last))
        counts.append(int(o.res.distinct_states))
    # the jobs' answers DIFFER — the per-job runtime bounds are live,
    # not a coincidence of equal spaces under a shared ceiling
    assert counts == [616, 743], counts


def test_heterogeneous_paxos_bounds_one_bucket_bit_exact():
    """Paxos twin: differing (ballots, values) pad to one ceiling;
    padded lanes are masked per job, so each job's reachable set,
    level sizes and witness labels match its solo engine exactly."""
    from raft_tla_tpu.spec import spec_of
    cfgs = [PaxosConfig(n_servers=2, n_ballots=3, n_values=3),
            PaxosConfig(n_servers=2, n_ballots=4, n_values=4)]
    assert len({repr(spec_of(c).serve_bucket(c)[0])
                for c in cfgs}) == 1
    rep = run_jobs([Job(c, max_depth=4, label=f"p{k}")
                    for k, c in enumerate(cfgs)])
    assert rep.meta["buckets"] == 1
    assert rep.meta["engines_compiled"] == 1
    assert rep.meta["fallback_jobs"] == 0
    counts = []
    for o, c in zip(rep.outcomes, cfgs):
        ref_eng = Engine(c)
        ref = ref_eng.check(max_depth=4)
        assert o.status == "done"
        _same(o.res, ref)
        last = ref.distinct_states - 1
        # padded layouts decode wider state rows, so trace parity is
        # on the action-label chain (the state identity is already
        # pinned by counts/level sizes/violation ids above)
        assert [lbl for lbl, _ in o.trace(last)] == \
            [lbl for lbl, _ in ref_eng.trace(last)]
        counts.append(int(o.res.distinct_states))
    assert counts == [44, 88], counts


# ---------------------------------------------------------------------------
# Persistent AOT executable cache (serve/exec_cache, round 13)
# ---------------------------------------------------------------------------


class _FakeSerializer:
    """Deterministic stand-in: 'serializes' to a token and keeps the
    live executable in a registry — simulates a serializable backend
    without depending on runtime support, so the keying/round-trip/
    corrupt-entry contracts pin on every platform."""

    name = "fake"
    registry = {}

    def serialize(self, compiled):
        token = f"tok{id(compiled)}".encode()
        _FakeSerializer.registry[token] = compiled
        return token

    def deserialize(self, blob):
        return _FakeSerializer.registry[blob]


class _BrokenSerializer:
    name = "broken"

    def serialize(self, compiled):
        raise RuntimeError("this backend cannot serialize executables")

    def deserialize(self, blob):
        raise RuntimeError("this backend cannot serialize executables")


@pytest.mark.smoke
def test_exec_cache_key_stability_and_parts(tmp_path):
    """Key = sha of the canonical parts: stable across repeats,
    different for ANY changed part (JP, ceiling, mode flags,
    backend)."""
    from raft_tla_tpu.serve.exec_cache import backend_fingerprint, \
        exec_key
    base = dict(backend=backend_fingerprint(), spec="raft",
                ceiling_cfg="cfgA", JP=2, chunk=128,
                guard_matmul=True)
    assert exec_key(base) == exec_key(dict(base))
    assert exec_key(base) == exec_key(
        dict(reversed(list(base.items()))))     # order-independent
    for change in (dict(JP=4), dict(ceiling_cfg="cfgB"),
                   dict(guard_matmul=False), dict(spec="paxos"),
                   dict(backend={"platform": "other"})):
        assert exec_key({**base, **change}) != exec_key(base), change


@pytest.mark.smoke
def test_exec_cache_roundtrip_corrupt_and_foreign_miss(tmp_path):
    """Disk round-trip through an injected serializer; a corrupt
    entry, a foreign (renamed) entry, and a serializer mismatch all
    read as labeled misses — never an exception, never a wrong
    load."""
    cache = ExecCache(str(tmp_path), serializer=_FakeSerializer())
    sentinel = object()
    assert cache.store("k1", sentinel)
    ex, why = cache.load("k1")
    assert ex is sentinel and why == "hit"
    # cold key
    ex, why = cache.load("k2")
    assert ex is None and "cold" in why
    # corrupt entry: truncated pickle
    with open(tmp_path / "k3.exec", "wb") as fh:
        fh.write(b"\x80\x04 garbage")
    ex, why = cache.load("k3")
    assert ex is None and "corrupt" in why
    # foreign entry: a valid container copied under the wrong name
    os.replace(tmp_path / "k1.exec", tmp_path / "k4.exec")
    ex, why = cache.load("k4")
    assert ex is None and "foreign" in why
    # serializer mismatch reads as a miss, not a wrong deserialize
    cache2 = ExecCache(str(tmp_path), serializer=_BrokenSerializer())
    cache2.store("k5", sentinel)        # records a named failure
    assert cache2.store_failures == 1
    assert "cannot serialize" in cache2.store_fail_reasons[-1]
    with open(tmp_path / "k6.exec", "wb") as fh:
        pickle.dump({"format": 1, "key": "k6", "parts": {},
                     "serializer": "fake", "blob": b"x"}, fh)
    ex, why = cache2.load("k6")
    assert ex is None and "serializer mismatch" in why
    stats = cache.stats()
    assert stats["exec_cache_hits"] == 1
    assert stats["exec_cache_misses"] >= 3


@pytest.mark.smoke
def test_exec_cache_lru_bytes_eviction(tmp_path):
    """LRU-by-bytes bound (round 14 — the eviction half ROADMAP item 1
    left open, mirroring serve/cache.ResultCache): every store trims
    the directory back under max_bytes, oldest-mtime first; a warm
    LOAD refreshes recency so a hot bucket survives; the just-written
    entry is never the victim; None keeps the historical unbounded
    behavior."""
    import time as _t

    def entry_bytes(key):
        cache = ExecCache(str(tmp_path), serializer=_FakeSerializer())
        cache.store(key, object())
        return os.path.getsize(tmp_path / f"{key}.exec")

    one = entry_bytes("probe")
    os.remove(tmp_path / "probe.exec")
    with pytest.raises(ValueError, match="must be positive"):
        ExecCache(str(tmp_path), max_bytes=0)
    cache = ExecCache(str(tmp_path), serializer=_FakeSerializer(),
                      max_bytes=int(2.5 * one))
    assert cache.store("a", object())
    _t.sleep(0.05)
    assert cache.store("b", object())
    _t.sleep(0.05)
    # a warm load refreshes "a"'s mtime: it becomes the NEWEST
    ex, why = cache.load("a")
    assert why == "hit"
    _t.sleep(0.05)
    # third entry overflows the bound: the LRU victim is now "b"
    assert cache.store("c", object())
    assert cache.evictions == 1
    assert sorted(p.name for p in tmp_path.glob("*.exec")) == \
        ["a.exec", "c.exec"]
    # the just-written entry is never the victim, even when a single
    # oversized store exceeds the bound on its own
    tiny = ExecCache(str(tmp_path / "tiny"),
                     serializer=_FakeSerializer(), max_bytes=1)
    assert tiny.store("big", object())
    assert os.path.exists(tmp_path / "tiny" / "big.exec")
    assert tiny.evictions == 0
    # ... and the NEXT store retires it like any other cold entry
    assert tiny.store("big2", object())
    assert not os.path.exists(tmp_path / "tiny" / "big.exec")
    # unbounded default: no eviction ever, loads stay write-free
    unb = ExecCache(str(tmp_path / "unb"),
                    serializer=_FakeSerializer())
    for i in range(4):
        unb.store(f"k{i}", object())
    assert unb.evictions == 0
    assert len(list((tmp_path / "unb").glob("*.exec"))) == 4
    assert unb.stats()["exec_cache_evictions"] == 0


def test_exec_cache_refuses_to_store_under_cpu_jax_cache(tmp_path):
    """jax 0.9's XLA:CPU re-serializes an executable that JAX's
    persistent cache loaded into a blob that fails at run time, so
    with that cache on the real serializer stores nothing: a named
    store failure, never an entry a restart would crash on."""
    import jax.numpy as jnp
    assert jax.config.jax_enable_compilation_cache   # conftest: on
    assert jax.config.jax_compilation_cache_dir
    cache = ExecCache(str(tmp_path))
    compiled = jax.jit(lambda x: x + 1).lower(jnp.zeros(4)).compile()
    assert not cache.store("k", compiled, {"p": 1})
    assert cache.stores == 0 and cache.store_failures == 1
    assert "persistent compilation cache" in cache.store_fail_reasons[0]
    assert cache.load("k", {"p": 1})[0] is None


def test_exec_cache_max_bytes_cli_validation():
    """batch --executable-cache-max-bytes is a usage error (exit 2,
    named message) without --executable-cache or with a non-positive
    bound — never a traceback."""
    from raft_tla_tpu.cli import main
    assert main(["batch", "--job", '{"spec": "paxos"}',
                 "--executable-cache-max-bytes", "100"]) == 2
    assert main(["batch", "--job", '{"spec": "paxos"}',
                 "--executable-cache", "/tmp/nope",
                 "--executable-cache-max-bytes", "-5"]) == 2


def test_exec_cache_warm_restart_zero_compiles_and_slo_obs(tmp_path,
                                                          no_jax_cache):
    """End-to-end acceptance: a warm ``exec_cache`` restart (fresh
    BucketEngine, fresh run_jobs) performs ZERO .compile() calls —
    no bucket_compile span — and serves bit-identical results.  Uses
    the REAL jax serializer (this backend round-trips); a backend
    that cannot serialize is covered by the _BrokenSerializer test
    above (honest labeled miss).  The same runs pin the round-13 SLO
    surface: wait_s/service_s on every report row, the heartbeat SLO
    snapshot (queue depth + histograms + exec-cache counters), and
    the per-tenant ledger rollups."""
    from raft_tla_tpu.obs import Obs
    from raft_tla_tpu.obs.heartbeat import Heartbeat
    from raft_tla_tpu.obs.ledger import RunLedger
    from raft_tla_tpu.obs.spans import SpanRecorder
    exec_dir = str(tmp_path / "exec")
    rec1 = SpanRecorder()
    rep1 = run_jobs([Job(PAX, max_depth=3, label="a")],
                    obs=Obs(spans=rec1), exec_cache=exec_dir)
    assert rec1.totals()["bucket_compile"]["count"] == 1
    assert rep1.meta["exec_cache_misses"] == 1
    assert rep1.meta["exec_cache_stores"] == 1

    rec2 = SpanRecorder()
    ledger_path = str(tmp_path / "ledger.jsonl")
    hb_path = str(tmp_path / "hb.json")
    cache2 = ExecCache(exec_dir)
    obs2 = Obs(spans=rec2, ledger=RunLedger(ledger_path),
               heartbeat=Heartbeat(hb_path))
    obs2.start()
    rep2 = run_jobs([Job(PAX, max_depth=3, label="b")], obs=obs2,
                    exec_cache=cache2)
    obs2.finish(depth=3, states=1)
    assert rec2.totals().get("bucket_compile",
                             {}).get("count", 0) == 0
    assert cache2.hits == 1
    assert rep2.meta["exec_cache_hits"] == 1
    assert rep1.outcomes[0].res.level_sizes == \
        rep2.outcomes[0].res.level_sizes
    # SLO surface: report rows, heartbeat snapshot, tenant rollups
    row = rep2.outcomes[0].report
    assert "wait_s" in row and "service_s" in row
    hb = json.load(open(hb_path))
    slo = hb["slo"]
    assert slo["queue_depth"] == 0 and slo["jobs_done"] == 1
    assert sum(slo["service_hist"].values()) == 1
    assert slo["exec_cache"]["exec_cache_hits"] == 1
    recs = [json.loads(ln) for ln in open(ledger_path)]
    tenant = [r for r in recs if r.get("kind") == "tenant"]
    assert len(tenant) == 1 and tenant[0]["spec"] == "paxos"
    assert tenant[0]["jobs"] == 1 and tenant[0]["service_s"] >= 0
    assert any(r.get("kind") == "exec_cache" for r in recs)
    batch_rec = next(r for r in recs if r.get("kind") == "batch")
    assert "queue_depth" in batch_rec
    # watch renders the SLO lines
    spec = importlib.util.spec_from_file_location(
        "watch_slo", os.path.join(_REPO, "tools", "watch.py"))
    watch = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(watch)
    line, code = watch.status_line(hb_path, None, stale_s=300)
    assert code == 0
    assert "queue: 0 waiting, 1 done" in line
    assert "exec-cache: 1 hits" in line


# ---------------------------------------------------------------------------
# slow duplicates: bigger spaces, bigger waves
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_heterogeneous_k4_grid_bit_exact_slow():
    """The full K=4 acceptance grid, raft AND paxos: four distinct
    value-bound configs per spec, each spec ONE bucket and ONE
    compile, every job bit-exact vs its solo engine (the fast 2-job
    representatives above keep tier-1 lean)."""
    from raft_tla_tpu.spec import spec_of
    rcfgs = [_het_raft(m, t) for m, t in
             ((1, 1), (1, 2), (2, 1), (2, 2))]
    pcfgs = [PaxosConfig(n_servers=2, n_ballots=b, n_values=v)
             for b, v in ((3, 3), (3, 4), (4, 3), (4, 4))]
    assert len({repr(spec_of(c).serve_bucket(c)[0])
                for c in rcfgs}) == 1
    assert len({repr(spec_of(c).serve_bucket(c)[0])
                for c in pcfgs}) == 1
    jobs = [Job(c, max_depth=13, label=f"r{k}")
            for k, c in enumerate(rcfgs)] + \
           [Job(c, max_depth=4, label=f"p{k}")
            for k, c in enumerate(pcfgs)]
    rep = run_jobs(jobs)
    assert rep.meta["buckets"] == 2
    assert rep.meta["engines_compiled"] == 2
    assert rep.meta["fallback_jobs"] == 0
    counts = {}
    for o, c, d in zip(rep.outcomes, rcfgs + pcfgs,
                       [13] * 4 + [4] * 4):
        ref_eng = Engine(c)
        ref = ref_eng.check(max_depth=d)
        assert o.status == "done"
        _same(o.res, ref)
        last = ref.distinct_states - 1
        assert [lbl for lbl, _ in o.trace(last)] == \
            [lbl for lbl, _ in ref_eng.trace(last)]
        counts[o.job.label] = int(o.res.distinct_states)
    assert len({counts[f"r{k}"] for k in range(4)}) == 4, counts
    assert len({counts[f"p{k}"] for k in range(4)}) >= 3, counts

@pytest.mark.slow
def test_batched_stock_paxos_and_deep_raft_parity_slow():
    """Full-space duplicates of the fast representative: the stock
    paxos model (857 distinct symmetric, fully batched) mixed with the
    raft micro space to exhaustion (20,438 distinct, peak level 740 —
    deliberately NOT a small job: it must overflow the per-job burst
    ring, fall back to a solo engine, and still land exact with an
    honest status).  The ring/table are widened (4*256 rows, 2^17
    slots) so the fallback is the burst's own bail, not the root
    admission check."""
    stock = PaxosConfig()
    jobs = [Job(stock, label="stock"),
            Job(MICRO, label="micro-full"),
            Job(MICRO, max_depth=5, label="micro-d5")]
    rep = run_jobs(jobs, bucket_overrides=dict(chunk=256,
                                               vcap=1 << 17))
    assert rep.meta["buckets"] == 2
    refs = [Engine(stock).check(), Engine(MICRO).check(),
            Engine(MICRO).check(max_depth=5)]
    statuses = [o.status for o in rep.outcomes]
    assert statuses == ["done", "fallback", "done"], statuses
    assert rep.meta["fallback_jobs"] == 1
    for o, ref in zip(rep.outcomes, refs):
        _same(o.res, ref)


@pytest.mark.slow
def test_batched_wave_of_identical_options_slow():
    """A wave wider than a power of two boundary (5 jobs -> padded to
    8) with mixed depth gates, all one bucket — stragglers keep
    stepping while short jobs freeze."""
    jobs = [Job(MICRO, max_depth=d, label=f"d{d}")
            for d in (2, 3, 4, 5, 6)]
    rep = run_jobs(jobs)
    assert rep.meta["buckets"] == 1
    assert rep.meta["engines_compiled"] == 1
    eng = Engine(MICRO)
    for o, d in zip(rep.outcomes, (2, 3, 4, 5, 6)):
        _same(o.res, eng.check(max_depth=d))


# ---------------------------------------------------------------------
# LRU-by-bytes eviction (round 11, ROADMAP 1: --cache-max-bytes)
# ---------------------------------------------------------------------


@pytest.mark.smoke
def test_result_cache_lru_eviction_by_bytes(tmp_path):
    """With max_bytes set, put trims the directory back under the
    bound, least-recently-USED first; a get refreshes recency, and the
    just-written payload is never the victim."""
    pad = "x" * 200                      # ~220 B/payload on disk
    cache = ResultCache(str(tmp_path), max_bytes=3 * 260)
    t = 1_000_000_000
    for i, key in enumerate(("k0", "k1", "k2")):
        cache.put(key, {"n": i, "pad": pad})
        t += 10
        os.utime(os.path.join(str(tmp_path), key + ".json"),
                 (t, t))                 # deterministic recency order
    assert len(cache) == 3
    # touch k0: now k1 is the least recently used
    fresh = ResultCache(str(tmp_path), max_bytes=3 * 260)
    assert fresh.get("k0")["n"] == 0
    t += 10
    os.utime(os.path.join(str(tmp_path), "k0.json"), (t, t))
    fresh.put("k3", {"n": 3, "pad": pad})
    names = sorted(nm for nm in os.listdir(str(tmp_path))
                   if nm.endswith(".json"))
    assert "k3.json" in names            # never evicts its own put
    assert "k0.json" in names            # refreshed by the get
    assert "k1.json" not in names        # the LRU victim
    # evicted keys miss even through the in-process dict
    assert fresh.get("k1") is None


@pytest.mark.smoke
def test_result_cache_unbounded_and_bad_bound(tmp_path):
    """max_bytes=None preserves the historical unbounded behavior;
    a non-positive bound errors at construction, not mid-batch."""
    cache = ResultCache(str(tmp_path / "c"))
    for i in range(8):
        cache.put(f"k{i}", {"n": i, "pad": "y" * 500})
    assert len(cache) == 8
    with pytest.raises(ValueError, match="max_bytes"):
        ResultCache(str(tmp_path / "d"), max_bytes=0)


def test_result_cache_eviction_serves_survivors(tmp_path):
    """End-to-end: a bounded cache under run_jobs still serves the
    surviving key with zero dispatches after eviction pressure."""
    cache = ResultCache(str(tmp_path), max_bytes=1 << 20)
    run_jobs([Job(PAX, max_depth=2, label="a")], cache=cache)
    rep = run_jobs([Job(PAX, max_depth=2, label="b")], cache=cache)
    assert rep.meta["cache_hits"] == 1
    assert rep.meta["batch_dispatches"] == 0


# ---------------------------------------------------------------------
# Mesh-sharded waves (round 16): the job axis across every local
# device.  conftest forces 8 virtual CPU devices for the whole test
# session (the test_pjit pattern), so wave_mesh=4 shards across a
# device subset in-process.
# ---------------------------------------------------------------------


def test_mesh_wave_bit_exact_vs_single_device():
    """The tier-1 mesh representative: a K=8 mixed raft+paxos wave
    under a 4-device job mesh is bit-exact per job vs the
    single-device wave (counts, level sizes, violation ids, witness
    traces) — and the single-device wave is itself pinned against
    solo engines by the tests above, so mesh ≡ solo transitively
    (the slow duplicate below checks solo directly).  One
    bucket_compile per bucket, one batched_dispatch per burst round,
    and the wave occupancy lands in the meta, the ledger rows and the
    heartbeat."""
    from raft_tla_tpu.obs import Obs
    from raft_tla_tpu.obs.heartbeat import Heartbeat
    from raft_tla_tpu.obs.ledger import RunLedger
    from raft_tla_tpu.obs.spans import SpanRecorder
    import tempfile

    def jobs():
        return ([Job(MICRO, max_depth=d, label=f"r{d}")
                 for d in (3, 4, 5, 6, 7, 8)] +
                [Job(PAX, max_depth=3, label="p3"),
                 Job(PAX, label="pfull")])

    with tempfile.TemporaryDirectory() as td:
        rec = SpanRecorder()
        led_path = os.path.join(td, "ledger.jsonl")
        hb_path = os.path.join(td, "hb.json")
        obs = Obs(spans=rec, ledger=RunLedger(led_path),
                  heartbeat=Heartbeat(hb_path))
        obs.start()
        rep_m = run_jobs(jobs(), wave_mesh=4, obs=obs)
        obs.finish(depth=8, states=1)
        rep_s = run_jobs(jobs(), wave_mesh="off")
        hb = json.load(open(hb_path))
        recs = [json.loads(ln) for ln in open(led_path)]
    assert rep_m.meta["buckets"] == 2
    assert rep_m.meta["fallback_jobs"] == 0
    assert rep_m.meta["wave_devices"] == 4
    # 6 raft jobs -> mesh multiple 4 * pow2(ceil(6/4)) = 8 lanes
    assert rep_m.meta["wave_lanes"] == 8
    assert rep_s.meta["wave_devices"] == 1
    for om, osd in zip(rep_m.outcomes, rep_s.outcomes):
        assert om.status == "done" and osd.status == "done"
        _same(om.res, osd.res)
    # witness-trace parity through the mesh harvest path (r6's
    # deepest state replays identically in both modes)
    last = rep_s.outcomes[3].res.distinct_states - 1
    assert _trace_key(rep_m.outcomes[3].trace(last)) == \
        _trace_key(rep_s.outcomes[3].trace(last))
    # ONE bucket_compile per bucket, ONE batched_dispatch per round
    totals = rec.totals()
    assert totals["bucket_compile"]["count"] == 2
    assert totals["batched_dispatch"]["count"] == \
        rep_m.meta["batch_dispatches"]
    # same round count in both modes: the mesh changes placement,
    # never the per-job trajectory
    assert rep_m.meta["batch_dispatches"] == \
        rep_s.meta["batch_dispatches"]
    # occupancy on the obs surface: every kind=batch ledger row and
    # the final heartbeat carry the wave block
    batch_rows = [r for r in recs if r.get("kind") == "batch"]
    assert batch_rows and all(r["wave_devices"] == 4
                              for r in batch_rows)
    assert any(r["wave_lanes"] == 8 for r in batch_rows)
    assert hb["wave"]["devices"] == 4
    assert hb["wave"]["jobs_per_device"] * 4 == hb["wave"]["lanes"]
    assert hb["wave"]["state_shards"] == 1

    # the 2-D grid: the same 4 devices as a 2x2 jobs x state mesh.
    # Identical per-job results, still ONE bucket_compile per bucket,
    # and the state axis surfaces in meta, ledger and heartbeat.
    with tempfile.TemporaryDirectory() as td:
        rec2 = SpanRecorder()
        led2 = os.path.join(td, "ledger.jsonl")
        hb2p = os.path.join(td, "hb.json")
        obs2 = Obs(spans=rec2, ledger=RunLedger(led2),
                   heartbeat=Heartbeat(hb2p))
        obs2.start()
        rep_2 = run_jobs(jobs(), wave_mesh="2x2", obs=obs2)
        obs2.finish(depth=8, states=1)
        hb2 = json.load(open(hb2p))
        recs2 = [json.loads(ln) for ln in open(led2)]
    assert rep_2.meta["wave_devices"] == 4
    assert rep_2.meta["wave_state_shards"] == 2
    # J=2 axis: 6 raft jobs -> 2 * pow2(ceil(6/2)) = 8 lanes again
    assert rep_2.meta["wave_lanes"] == 8
    assert rep_2.meta["fallback_jobs"] == 0
    for o2, osd in zip(rep_2.outcomes, rep_s.outcomes):
        assert o2.status == "done"
        _same(o2.res, osd.res)
    assert _trace_key(rep_2.outcomes[3].trace(last)) == \
        _trace_key(rep_s.outcomes[3].trace(last))
    totals2 = rec2.totals()
    assert totals2["bucket_compile"]["count"] == 2
    assert totals2["batched_dispatch"]["count"] == \
        rep_2.meta["batch_dispatches"]
    assert rep_2.meta["batch_dispatches"] == \
        rep_s.meta["batch_dispatches"]
    rows2 = [r for r in recs2 if r.get("kind") == "batch"]
    assert rows2 and all(r["wave_state_shards"] == 2 for r in rows2)
    assert hb2["wave"]["devices"] == 4
    assert hb2["wave"]["state_shards"] == 2


@pytest.mark.slow  # tier-1 budget: the fast reps pin mesh ≡
# single-device (itself pinned vs solo); this is the direct
# full-space mesh ≡ solo duplicate, 1-D and 2-D
def test_mesh_wave_vs_solo_engines_slow():
    def jobs():
        return ([Job(MICRO, max_depth=d, label=f"r{d}")
                 for d in (4, 6, 13)] +
                [Job(_het_raft(1, 2), max_depth=6, label="h6"),
                 Job(MICRO, max_depth=5, label="r5b"),
                 Job(MICRO, max_depth=3, label="r3b"),
                 Job(PAX, max_depth=3, label="p3"),
                 Job(PAX, label="pfull")])
    rep = run_jobs(jobs(), wave_mesh=4)
    assert rep.meta["wave_devices"] == 4
    assert rep.meta["fallback_jobs"] == 0
    solos = []
    for o in rep.outcomes:
        eng = Engine(o.job.cfg)
        solos.append(eng.check(max_depth=o.job.max_depth))
        _same(o.res, solos[-1])
    # the 2-D grid against the same solo results
    rep2 = run_jobs(jobs(), wave_mesh="2x2")
    assert rep2.meta["wave_state_shards"] == 2
    assert rep2.meta["fallback_jobs"] == 0
    for o, want in zip(rep2.outcomes, solos):
        _same(o.res, want)


def test_exec_cache_key_discriminates_mesh_shapes_and_padding():
    """A mesh-shape change is a NAMED miss, never a wrong load: the
    4x1, 2x2 and single-device bucket executables' keys all differ at
    the same padded width, because the [J, S] grid joins the key
    parts — and they differ in wave_mesh ONLY, so the discrimination
    is exactly the mesh shape.  Also pins the padding rule the width
    half of the key rides on: J-axis multiples, the state axis never
    eats lanes."""
    from raft_tla_tpu.serve.batch import BucketEngine
    from raft_tla_tpu.serve.exec_cache import exec_key
    be_off = BucketEngine(MICRO)
    be_mesh = BucketEngine(MICRO, wave_mesh=4)
    be_2d = BucketEngine(MICRO, wave_mesh=(2, 2))
    p_off, p_mesh, p_2d = (be_off._exec_key_parts(8),
                           be_mesh._exec_key_parts(8),
                           be_2d._exec_key_parts(8))
    assert p_off["wave_mesh"] == 0 and p_mesh["wave_mesh"] == [4, 1] \
        and p_2d["wave_mesh"] == [2, 2]
    for a, b in ((p_off, p_mesh), (p_off, p_2d), (p_mesh, p_2d)):
        assert {k for k in a if a[k] != b[k]} == {"wave_mesh"}
    assert len({exec_key(p) for p in (p_off, p_mesh, p_2d)}) == 3
    # padding: single-device pads to pow2, mesh to a J-axis multiple
    # with equal per-row lane counts (4x1 and 2x2 use the same 4
    # devices but round to different lane widths — J=4 vs J=2)
    assert [be_off._pad_jp(n) for n in (1, 2, 5, 8)] == [1, 2, 8, 8]
    assert [be_mesh._pad_jp(n) for n in (1, 4, 5, 8, 9)] == \
        [4, 4, 8, 8, 16]
    assert [be_2d._pad_jp(n) for n in (1, 2, 3, 5)] == [2, 2, 4, 8]


def test_wave_mesh_resolution_and_scheduler_ceiling():
    """resolve_wave_mesh normalizes auto/off/N/JxS to the (J, S) grid
    with named errors, and the scheduler's default wave ceiling
    scales to J x 8 lanes (the state axis never widens the wave)
    unless --max-wave pins it."""
    from raft_tla_tpu.serve import WaveScheduler
    from raft_tla_tpu.serve.batch import resolve_wave_mesh
    assert resolve_wave_mesh("auto") == (8, 1)  # conftest's 8 devices
    assert resolve_wave_mesh(None) == (8, 1)
    assert resolve_wave_mesh("off") == (0, 1)
    assert resolve_wave_mesh(1) == (0, 1)      # 1 device = no mesh
    assert resolve_wave_mesh("4") == (4, 1)
    assert resolve_wave_mesh("2x2") == (2, 2)
    assert resolve_wave_mesh("4x2") == (4, 2)
    assert resolve_wave_mesh("1x2") == (1, 2)  # state-only split
    assert resolve_wave_mesh("1x1") == (0, 1)  # 1 device = no mesh
    with pytest.raises(ValueError, match="banana"):
        resolve_wave_mesh("banana")
    with pytest.raises(ValueError, match="exceeds the 8"):
        resolve_wave_mesh(64)
    with pytest.raises(ValueError, match="exceeds the 8"):
        resolve_wave_mesh("3x3")
    with pytest.raises(ValueError, match=">= 1"):
        resolve_wave_mesh("0x2")
    with pytest.raises(ValueError, match=">= 0"):
        resolve_wave_mesh(-2)
    assert WaveScheduler(wave_mesh=4).wave_cap == 32
    assert WaveScheduler(wave_mesh="2x2").wave_cap == 16
    assert WaveScheduler(wave_mesh="off").wave_cap == 8
    assert WaveScheduler(wave_mesh=4, max_wave=5).wave_cap == 5
    with pytest.raises(ValueError, match="max_wave"):
        WaveScheduler(max_wave=0)


@pytest.mark.smoke
def test_wave_mesh_and_max_wave_cli_validation():
    """batch --max-wave/--wave-mesh usage errors are exit 2 with a
    named message, never a traceback (serve shares the checks)."""
    from raft_tla_tpu.cli import main
    base = ["batch", "--job", '{"spec": "paxos"}']
    assert main(base + ["--max-wave", "0"]) == 2
    assert main(base + ["--wave-mesh", "banana"]) == 2
    assert main(base + ["--wave-mesh", "64"]) == 2


def test_parked_carry_restores_across_mesh_modes(tmp_path):
    """The portable restart matrix: a carry parked under a 4-device
    mesh resumes bit-exact on a single-device scheduler, and a
    single-device carry resumes under the mesh — the .wave.npz slices
    are host numpy, re-placed by whichever mode restores them."""
    from raft_tla_tpu.serve import WaveScheduler
    from conftest import cached_explore
    waves = tmp_path / "waves"
    cache = ResultCache(str(tmp_path / "cache"))
    ovr = {"burst_levels": 1}   # several step boundaries per job
    mesh = WaveScheduler(cache=cache, wave_state=str(waves),
                         wave_mesh=4, bucket_overrides=ovr)
    single = WaveScheduler(cache=cache, wave_state=str(waves),
                           wave_mesh="off", bucket_overrides=ovr)

    def stop_after_persist():
        return waves.is_dir() and any(
            fn.endswith(".wave.npz") for fn in os.listdir(waves))

    # mesh park -> single-device resume
    rep1 = mesh.serve([Job(MICRO, max_depth=6, label="m6")],
                      stop=stop_after_persist)
    assert rep1.outcomes == [None] and rep1.meta["deferred_jobs"] == 1
    assert stop_after_persist(), "the mesh carry must survive"
    rep2 = single.serve([Job(MICRO, max_depth=6, label="m6")])
    o = rep2.outcomes[0]
    assert o.status == "done" and rep2.meta["resumed_jobs"] == 1
    want = cached_explore(MICRO, max_depth=6)
    _same(o.res, want)
    assert not stop_after_persist()

    # single-device park -> mesh resume (both engines already
    # compiled: zero new compiles either side)
    rep3 = single.serve([Job(MICRO, max_depth=5, label="m5")],
                        stop=stop_after_persist)
    assert rep3.outcomes == [None]
    assert rep3.meta["engines_compiled"] == 0
    rep4 = mesh.serve([Job(MICRO, max_depth=5, label="m5")])
    o4 = rep4.outcomes[0]
    assert o4.status == "done" and rep4.meta["resumed_jobs"] == 1
    assert rep4.meta["engines_compiled"] == 0
    assert rep4.meta["wave_devices"] == 4
    _same(o4.res, cached_explore(MICRO, max_depth=5))


def test_parked_carry_restores_across_mesh_shapes(tmp_path):
    """The 2-D restart matrix: a carry parked under the 2x2 grid
    resumes bit-exact under 4x1, 1x1 and plain single-device
    schedulers and back again — the .wave.npz slices are host numpy,
    so the grid shape at park time never leaks into the file.  Every
    scheduler keeps a warm exec cache; the second leg of each
    direction compiles nothing."""
    from raft_tla_tpu.serve import WaveScheduler
    from conftest import cached_explore
    waves = tmp_path / "waves"
    cache = ResultCache(str(tmp_path / "cache"))
    ovr = {"burst_levels": 1}   # several step boundaries per job

    def sched(mesh):
        return WaveScheduler(cache=cache, wave_state=str(waves),
                             wave_mesh=mesh, bucket_overrides=ovr,
                             exec_cache=str(tmp_path / "exec"))

    s22, s41, s11 = sched("2x2"), sched("4x1"), sched("1x1")

    def parked():
        return waves.is_dir() and any(
            fn.endswith(".wave.npz") for fn in os.listdir(waves))

    # 2x2 park -> 4x1 resume (same 4 devices, different grid)
    rep1 = s22.serve([Job(MICRO, max_depth=6, label="m6")],
                     stop=parked)
    assert rep1.outcomes == [None] and rep1.meta["deferred_jobs"] == 1
    assert rep1.meta["wave_state_shards"] == 2
    assert parked(), "the 2x2 carry must survive"
    rep2 = s41.serve([Job(MICRO, max_depth=6, label="m6")])
    o2 = rep2.outcomes[0]
    assert o2.status == "done" and rep2.meta["resumed_jobs"] == 1
    assert rep2.meta["wave_devices"] == 4
    assert rep2.meta["wave_state_shards"] == 1
    _same(o2.res, cached_explore(MICRO, max_depth=6))
    assert not parked()

    # 4x1 park -> 2x2 resume: both engines warm, zero new compiles
    # on either side (the second leg of the matrix)
    rep3 = s41.serve([Job(MICRO, max_depth=5, label="m5")],
                     stop=parked)
    assert rep3.outcomes == [None]
    assert rep3.meta["engines_compiled"] == 0
    rep4 = s22.serve([Job(MICRO, max_depth=5, label="m5")])
    o4 = rep4.outcomes[0]
    assert o4.status == "done" and rep4.meta["resumed_jobs"] == 1
    assert rep4.meta["engines_compiled"] == 0
    assert rep4.meta["wave_state_shards"] == 2
    _same(o4.res, cached_explore(MICRO, max_depth=5))

    # 2x2 park -> single-device resume ("1x1" resolves to no mesh)
    rep5 = s22.serve([Job(MICRO, max_depth=4, label="m4")],
                     stop=parked)
    assert rep5.outcomes == [None]
    assert rep5.meta["engines_compiled"] == 0
    rep6 = s11.serve([Job(MICRO, max_depth=4, label="m4")])
    o6 = rep6.outcomes[0]
    assert o6.status == "done" and rep6.meta["resumed_jobs"] == 1
    assert rep6.meta["wave_devices"] == 1
    _same(o6.res, cached_explore(MICRO, max_depth=4))


@pytest.mark.smoke
def test_watch_renders_wave_occupancy(tmp_path):
    """tools/watch.py renders the wave block as devices x lanes with
    the idle-lane waste as pad N/M, in any view that carries it."""
    from raft_tla_tpu.obs.heartbeat import Heartbeat
    spec = importlib.util.spec_from_file_location(
        "watch_wave", os.path.join(_REPO, "tools", "watch.py"))
    watch = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(watch)
    hb_path = str(tmp_path / "hb.json")
    Heartbeat(hb_path).beat(depth=4, states=100, extra={
        "jobs": {"r4": {"depth": 4, "distinct": 29,
                        "status": "running"}},
        "wave": {"devices": 4, "lanes": 8, "filled": 6, "pad": 2,
                 "jobs_per_device": 2}})
    line, code = watch.status_line(hb_path, None, stale_s=300)
    assert code == 0
    assert "wave: 4 devices x 2 lanes/device  6 jobs  pad 2/8" in line
    # daemon view: the same block renders next to the daemon lines
    hb2 = str(tmp_path / "hb2.json")
    Heartbeat(hb2).beat(depth=2, states=9, status="serving", extra={
        "daemon": {"status": "serving", "cycles": 1},
        "wave": {"devices": 2, "lanes": 16, "filled": 16, "pad": 0,
                 "jobs_per_device": 8}})
    line2, _ = watch.status_line(hb2, None, 300)
    assert "wave: 2 devices x 8 lanes/device  16 jobs  pad 0/16" \
        in line2
    assert "daemon serving" in line2
    # 2-D grid: devices/state_shards = the J axis, rendered as a grid
    hb4 = str(tmp_path / "hb4.json")
    Heartbeat(hb4).beat(depth=4, states=50, extra={
        "wave": {"devices": 4, "lanes": 8, "filled": 6, "pad": 2,
                 "jobs_per_device": 2, "state_shards": 2}})
    line4, _ = watch.status_line(hb4, None, 300)
    assert "wave: 2x2 grid  6 jobs  pad 2/8  state shards 2" in line4
    # heartbeats without a wave block render exactly as before
    hb3 = str(tmp_path / "hb3.json")
    Heartbeat(hb3).beat(depth=2, states=9)
    line3, _ = watch.status_line(hb3, None, 300)
    assert "wave:" not in line3
