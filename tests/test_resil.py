"""Fault-tolerance layer (resil/): chaos-driven recovery
differentials — for each injection site, a faulted-then-recovered run
must equal the unfaulted run bit-for-bit (counts, level sizes, gids,
witness traces) — plus the checkpoint-chain integrity contract,
shape-portable resume, and preemptible batch waves.

One fast representative per engine family runs in tier-1; full-space
and cross-shape duplicates are slow-marked (tier-1 budget, ROADMAP
standing constraint).
"""

import importlib.util
import json
import os
import warnings

import numpy as np
import pytest

from raft_tla_tpu.config import Bounds, ModelConfig, NEXT_ASYNC
from raft_tla_tpu.engine.bfs import CheckpointError, Engine
from raft_tla_tpu.resil import chaos
from raft_tla_tpu.resil.chaos import (ChaosSchedule, ChaosSpecError,
                                      InjectedFault)
from raft_tla_tpu.resil.ckpt_chain import (ChainWarning,
                                           chain_candidates,
                                           latest_valid, verify)
from raft_tla_tpu.resil.portable import load_portable_image
from raft_tla_tpu.resil.supervisor import (RetryExhausted,
                                           backoff_delay,
                                           supervised_check)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MICRO = ModelConfig(
    n_servers=2, init_servers=(0, 1), values=(1,),
    next_family=NEXT_ASYNC, symmetry=True, max_inflight_override=4,
    bounds=Bounds.make(max_log_length=1, max_timeouts=1,
                       max_client_requests=1))


def _same(res, ref):
    assert (res.distinct_states, res.generated_states, res.depth) == \
        (ref.distinct_states, ref.generated_states, ref.depth)
    assert res.level_sizes == ref.level_sizes
    assert [(v.invariant, v.state_id) for v in res.violations] == \
        [(v.invariant, v.state_id) for v in ref.violations]


def _labels(trace):
    return [label for label, _sv in trace]


@pytest.fixture(autouse=True)
def _chaos_clean():
    """Every test leaves the process-global schedule uninstalled."""
    yield
    chaos.uninstall()


@pytest.fixture(scope="module")
def classic():
    # burst_levels=2 so checkpoint chains actually build up (one
    # 16-level burst would cover the whole micro prefix in one save)
    return Engine(MICRO, chunk=64, burst_levels=2)


@pytest.fixture(scope="module")
def classic_ref(classic):
    """ONE unfaulted depth-8 reference run (counts + witness trace)
    shared by every classic-engine differential below — the engine's
    archives are reset by later runs, so the trace is captured here."""
    ref = classic.check(max_depth=8)
    return ref, _labels(classic.trace(ref.distinct_states - 1))


@pytest.fixture(scope="module")
def pjit2():
    import jax

    from raft_tla_tpu.parallel.pjit_mesh import PjitShardedEngine
    return PjitShardedEngine(MICRO, devices=jax.devices()[:2], chunk=16,
                             store_states=True, burst_levels=2)


# ---------------------------------------------------------------------------
# chaos schedule: parsing, determinism, sites
# ---------------------------------------------------------------------------

@pytest.mark.smoke
def test_chaos_spec_parse_and_determinism():
    s = ChaosSchedule("seed=3;dispatch:at=2,4;archive:every=3;"
                      "host_table:p=0.5")
    assert [s.fire("dispatch") for _ in range(5)] == \
        [False, True, False, True, False]
    assert [s.fire("archive") for _ in range(6)] == \
        [False, False, True, False, False, True]
    # p= clauses are a pure function of (seed, site, hit): replays
    # are identical
    a = [ChaosSchedule("seed=7;host_table:p=0.5").fire("host_table")
         for _ in range(8)]
    b = [ChaosSchedule("seed=7;host_table:p=0.5").fire("host_table")
         for _ in range(8)]
    del a, b  # schedules above are single-hit; compare multi-hit:
    s1 = ChaosSchedule("seed=7;host_table:p=0.5")
    s2 = ChaosSchedule("seed=7;host_table:p=0.5")
    assert [s1.fire("host_table") for _ in range(32)] == \
        [s2.fire("host_table") for _ in range(32)]
    # unknown sites/rules/values error by name
    for bad, msg in [("nope:at=1", "unknown site"),
                     ("dispatch:often=2", "unknown rule"),
                     ("dispatch:at=0", "bad at= value"),
                     ("dispatch", "not 'site:rule'"),
                     ("seed=x;dispatch:at=1", "bad seed"),
                     ("seed=4", "declares no sites")]:
        with pytest.raises(ChaosSpecError, match=msg):
            ChaosSchedule(bad)
    # point() raises InjectedFault with site + hit attribution
    s3 = ChaosSchedule("dispatch:at=2")
    s3.point("dispatch")
    with pytest.raises(InjectedFault) as ei:
        s3.point("dispatch")
    assert ei.value.site == "dispatch" and ei.value.hit == 2
    assert s3.fired == [("dispatch", 2)]
    # uninstalled global points are no-ops
    chaos.uninstall()
    chaos.chaos_point("dispatch")
    assert chaos.chaos_fire("ckpt_torn") is False


@pytest.mark.smoke
def test_backoff_delay_bounded_and_deterministic():
    d = [backoff_delay(k, 1.0, 8.0) for k in range(6)]
    assert d == [backoff_delay(k, 1.0, 8.0) for k in range(6)]
    base = [min(1.0 * 2.0 ** k, 8.0) for k in range(6)]
    for got, b in zip(d, base):
        assert b <= got <= b * 1.25


# ---------------------------------------------------------------------------
# checkpoint chain: rotation, integrity sidecars, torn-head fallback
# ---------------------------------------------------------------------------

def test_ckpt_chain_rotation_and_torn_head_fallback(classic,
                                                    classic_ref,
                                                    tmp_path):
    ref, _ref_trace = classic_ref
    ck = str(tmp_path / "run.ckpt")
    classic.ckpt_keep = 3
    classic.check(max_depth=6, checkpoint_path=ck, checkpoint_every=1)
    names = sorted(os.listdir(tmp_path))
    assert "run.ckpt" in names and "run.ckpt.1" in names
    assert "run.ckpt.sum" in names and "run.ckpt.1.sum" in names
    assert verify(ck) == (True, "ok")
    assert latest_valid(ck) == ck
    assert chain_candidates(ck)[0] == ck
    # tear the head: resume falls back to .1 with a NAMED warning and
    # still lands bit-exact
    with open(ck, "r+b") as fh:
        fh.truncate(os.path.getsize(ck) // 2)
    assert verify(ck)[0] is False
    assert latest_valid(ck) == ck + ".1"
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        resumed = classic.check(max_depth=8, resume_from=ck)
    assert any(issubclass(x.category, ChainWarning) and
               "integrity" in str(x.message) for x in w)
    _same(resumed, ref)
    assert sum(len(p) for p in classic._parents) == ref.distinct_states
    # corrupt BYTES (same length) are caught by the sha256, not size
    with open(ck + ".1", "r+b") as fh:
        size = os.path.getsize(ck + ".1")
        fh.seek(size // 2)
        fh.write(b"\xff" * 32)
    assert verify(ck + ".1") == (False, "sha256 mismatch "
                                 "(corrupt bytes)")


def test_ckpt_read_truncated_yields_clear_error(classic, tmp_path):
    """Satellite: payload integrity validates BEFORE the cfg-repr
    compare — a truncated file (with or without its sidecar) is a
    clear CheckpointError, never a numpy/zipfile traceback."""
    ck = str(tmp_path / "solo.ckpt")
    classic.ckpt_keep = 1            # no chain: nothing to fall back to
    classic.check(max_depth=4, checkpoint_path=ck)
    with open(ck, "r+b") as fh:
        fh.truncate(os.path.getsize(ck) // 3)
    with pytest.raises(CheckpointError, match="no valid checkpoint"):
        classic.check(resume_from=ck)
    # legacy file (no sidecar): the structural load catches the torn
    # zip container with the same named error
    os.remove(ck + ".sum")
    with pytest.raises(CheckpointError, match="no valid checkpoint"):
        classic.check(resume_from=ck)
    with pytest.raises(CheckpointError, match="no such checkpoint"):
        classic.check(resume_from=str(tmp_path / "missing.ckpt"))
    classic.ckpt_keep = 2


# ---------------------------------------------------------------------------
# supervised chaos differentials: one fast rep per engine family
# ---------------------------------------------------------------------------

def test_supervised_chaos_classic_every_boundary(classic,
                                                 classic_ref,
                                                 tmp_path):
    """The acceptance rep: dispatch faults at every level boundary
    (every 2nd loop hit — the alternating hits are the post-resume
    re-entries) plus one torn and one corrupt checkpoint head, all
    recovered by the supervised runner, bit-exact vs unfaulted."""
    ck = str(tmp_path / "sup.ckpt")
    ref, ref_trace = classic_ref
    sched = chaos.install(
        "dispatch:every=2;ckpt_torn:at=2;ckpt_corrupt:at=3")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", ChainWarning)
        res, eng, attempts = supervised_check(
            lambda: classic, retries=50, backoff=0.01,
            checkpoint_path=ck, checkpoint_every=1, max_depth=8,
            sleep=lambda s: None, reinit=False)
    assert attempts > 1
    assert any(site == "dispatch" for site, _ in sched.fired)
    assert any(site == "ckpt_torn" for site, _ in sched.fired)
    _same(res, ref)
    assert _labels(eng.trace(res.distinct_states - 1)) == ref_trace
    chaos.uninstall()
    # exhaustion is a named error, not an infinite loop (no
    # checkpoint: every-dispatch faults allow no progress at all)
    chaos.install("dispatch:every=1")
    with pytest.raises(RetryExhausted, match="after 3 attempt"):
        supervised_check(lambda: classic, retries=2, backoff=0.01,
                         max_depth=8, sleep=lambda s: None,
                         reinit=False)


def test_supervised_chaos_spill_dispatch_and_archive(tmp_path):
    """Spill-family rep, with the trace archives on DISK: dispatch
    faults AND an archive-write fault both recover via resume
    (reattach + truncate), bit-exact including the memmap'd trace."""
    from raft_tla_tpu.engine.spill import SpillEngine
    arch = str(tmp_path / "arch")
    ck = str(tmp_path / "spill.ckpt")
    eng = SpillEngine(MICRO, chunk=64, seg=1 << 12, store_states=True,
                      archive_dir=arch, burst_levels=2)
    ref = eng.check(max_depth=7)
    ref_trace = _labels(eng.trace(ref.distinct_states - 1))
    sched = chaos.install("dispatch:at=2;archive:at=5")
    res, eng2, attempts = supervised_check(
        lambda: eng, retries=4, backoff=0.01, checkpoint_path=ck,
        checkpoint_every=1, max_depth=7, sleep=lambda s: None,
        reinit=False)
    assert attempts > 1
    assert {site for site, _ in sched.fired} == {"dispatch",
                                                 "archive"}
    _same(res, ref)
    assert _labels(eng2.trace(res.distinct_states - 1)) == ref_trace


def test_supervised_chaos_pjit(pjit2, tmp_path):
    """Multi-device rep: the pjit engine on a 2-device mesh recovers a
    dispatch fault through its (classic-format) checkpoint,
    bit-exact including the witness trace."""
    eng = pjit2
    ck = str(tmp_path / "pjit.ckpt")
    ref = eng.check(max_depth=6)
    ref_trace = _labels(eng.trace(ref.distinct_states - 1))
    chaos.install("dispatch:at=2")
    res, eng2, attempts = supervised_check(
        lambda: eng, retries=1, backoff=0.01, checkpoint_path=ck,
        checkpoint_every=1, max_depth=6, sleep=lambda s: None,
        reinit=False)
    assert attempts == 2
    _same(res, ref)
    assert _labels(eng2.trace(res.distinct_states - 1)) == ref_trace


# ---------------------------------------------------------------------------
# shape-portable resume (resil/portable)
# ---------------------------------------------------------------------------

def test_portable_resume_classic_and_spill_cross_family(classic,
                                                        classic_ref,
                                                        pjit2,
                                                        tmp_path):
    """The elastic-resume contract, fast reps: a classic-Engine
    checkpoint resumes on the 2-device pjit mesh and on the spill
    engine by re-partitioning the visited image and frontier on load —
    final counts/level sizes/depth equal the uninterrupted run (the
    cross-device-count targets run in the slow duplicate)."""
    from raft_tla_tpu.engine.spill import SpillEngine
    ref, _ref_trace = classic_ref
    ck = str(tmp_path / "classic.ckpt")
    classic.check(max_depth=5, checkpoint_path=ck)
    img = load_portable_image(ck)
    assert img.source_format == "engine" and img.depth == 5
    res = pjit2.check(max_depth=8, resume_image=img)
    _same(res, ref)
    assert sum(len(p) for p in pjit2._parents) == ref.distinct_states
    sp = SpillEngine(MICRO, chunk=64, seg=1 << 12, store_states=True)
    res_sp = sp.check(max_depth=8, resume_image=load_portable_image(ck))
    _same(res_sp, ref)
    assert sum(len(p) for p in sp._parents) == ref.distinct_states
    # target gates: wrong config refuses by name
    img_bad = load_portable_image(ck)
    img_bad.cfg_repr = "nope"
    with pytest.raises(CheckpointError, match="different model "
                                              "config"):
        pjit2.check(resume_image=img_bad)


@pytest.mark.slow
def test_portable_resume_mesh_to_other_mesh_sizes(tmp_path):
    """A 2-device pjit checkpoint re-partitions onto a 4-device pjit
    mesh (natively — the file is classic format — and through the
    portable image) AND onto the single-chip spill engine: counts and
    witness traces do not depend on the mesh size."""
    import jax

    from raft_tla_tpu.engine.spill import SpillEngine
    from raft_tla_tpu.parallel.pjit_mesh import PjitShardedEngine
    devs = jax.devices()
    e2 = PjitShardedEngine(MICRO, devices=devs[:2], chunk=16,
                           store_states=True)
    full = e2.check(max_depth=12)
    gid = full.distinct_states - 1
    full_trace = _labels(e2.trace(gid))
    ck = str(tmp_path / "pjit2.ckpt")
    e2.check(max_depth=6, checkpoint_path=ck)
    e4 = PjitShardedEngine(MICRO, devices=devs[:4], chunk=16,
                           store_states=True)
    _same(e4.check(max_depth=12, resume_from=ck), full)
    assert _labels(e4.trace(gid)) == full_trace
    res4 = e4.check(max_depth=12, resume_image=load_portable_image(ck))
    _same(res4, full)
    assert _labels(e4.trace(gid)) == full_trace
    sp = SpillEngine(MICRO, chunk=64, seg=1 << 12, store_states=True)
    res_sp = sp.check(max_depth=12, resume_image=load_portable_image(ck))
    _same(res_sp, full)


@pytest.mark.slow
def test_supervised_chaos_host_table_partition_loss(tmp_path):
    """host_table site: a lost host partition mid-run recovers via
    checkpoint resume (exact sparse partition images), bit-exact."""
    from raft_tla_tpu.engine.spill import SpillEngine
    kw = dict(chunk=64, seg=1 << 12, store_states=False,
              host_table=True, partitions=2, part_cap=1 << 8,
              dev_keys=64)
    eng = SpillEngine(MICRO, **kw)
    ref = eng.check(max_depth=10)
    ck = str(tmp_path / "ht.ckpt")
    chaos.install("host_table:at=4")
    res, _eng, attempts = supervised_check(
        lambda: eng, retries=2, backoff=0.01, checkpoint_path=ck,
        checkpoint_every=1, max_depth=10, sleep=lambda s: None, reinit=False)
    assert attempts > 1
    _same(res, ref)


@pytest.mark.slow
def test_supervised_chaos_classic_full_space(tmp_path):
    """Full-space duplicate of the acceptance rep: the whole micro
    model to exhaustion under every-boundary dispatch faults."""
    eng = Engine(MICRO, chunk=64, burst_levels=4)
    ref = eng.check()
    ck = str(tmp_path / "full.ckpt")
    chaos.install("dispatch:every=2;ckpt_torn:at=3")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", ChainWarning)
        res, eng2, attempts = supervised_check(
            lambda: eng, retries=64, backoff=0.01,
            checkpoint_path=ck, checkpoint_every=1,
            sleep=lambda s: None, reinit=False)
    assert attempts > 2
    _same(res, ref)
    gid = ref.distinct_states - 1
    assert _labels(eng2.trace(gid)) == _labels(eng.trace(gid))


# ---------------------------------------------------------------------------
# preemptible batch waves (serve/)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_wave_state_kill_resume_and_preemption_bit_exact(classic,
                                                         classic_ref,
                                                         tmp_path):
    """The batch acceptance rep: a run killed at a wave boundary (the
    deterministic SIGKILL stand-in, firing AFTER the wave-state
    persist) resumes to bit-exact per-job results — finished jobs from
    the cache, stragglers mid-BFS from their carry — and the long job
    parks (yields its lane) when another job waits on the single
    lane.  References are solo-engine runs: batched ≡ solo is the
    PR-10 pinned contract, so the classic engine is the exact
    per-job answer."""
    from raft_tla_tpu.serve import Job, ResultCache, run_jobs
    ws = str(tmp_path / "waves")
    cache = ResultCache(str(tmp_path / "cache"))
    bo = {"burst_levels": 2}
    ref8, _tr = classic_ref
    ref3 = classic.check(max_depth=3)

    def mk():
        return [Job(MICRO, max_depth=8, label="long"),
                Job(MICRO, max_depth=3, label="hi", priority=5)]
    # killed mid-run: single lane + 1-step yield budget; hi (priority
    # 5) takes boundaries 1-2, the kill fires at boundary 3 — the
    # long job's first step, right after its carry persisted
    chaos.install("wave_kill:at=3")
    with pytest.raises(InjectedFault):
        run_jobs(mk(), cache=cache, wave_state=ws, max_wave=1,
                 wave_yield=1, bucket_overrides=bo)
    chaos.uninstall()
    assert any(nm.endswith(".wave.npz") for nm in os.listdir(ws))
    rep = run_jobs(mk(), cache=cache, wave_state=ws, max_wave=1,
                   wave_yield=1, bucket_overrides=bo)
    assert rep.meta["resumed_jobs"] >= 1
    assert rep.meta["fallback_jobs"] == 0
    long_o, hi_o = rep.outcomes
    assert long_o.report["status_reason"] == "resumed from wave state"
    _same(long_o.res, ref8)
    _same(hi_o.res, ref3)
    # wave state retired at completion; a re-run is all cache hits
    assert not [nm for nm in os.listdir(ws)
                if nm.endswith(".wave.npz")]
    rep2 = run_jobs(mk(), cache=cache, wave_state=ws,
                    bucket_overrides=bo)
    assert all(o.status == "cache_hit" for o in rep2.outcomes)
    with pytest.raises(ValueError, match="wave_yield"):
        run_jobs(mk(), wave_yield=0)


@pytest.mark.slow
def test_wave_kill_park_priority_full(tmp_path):
    """Full-surface duplicate: 3 jobs, parking + priority scheduling +
    witness-trace parity against a clean batched reference."""
    from raft_tla_tpu.serve import Job, ResultCache, run_jobs
    ws = str(tmp_path / "waves")
    cache = ResultCache(str(tmp_path / "cache"))
    bo = {"burst_levels": 2}

    def mk():
        return [Job(MICRO, max_depth=12, label="long"),
                Job(MICRO, max_depth=3, label="hi", priority=5),
                Job(MICRO, max_depth=4, label="mid")]
    ref = run_jobs(mk(), bucket_overrides=bo)
    assert ref.meta["fallback_jobs"] == 0
    chaos.install("wave_kill:at=3")
    with pytest.raises(InjectedFault):
        run_jobs(mk(), cache=cache, wave_state=ws, max_wave=1,
                 wave_yield=1, bucket_overrides=bo)
    chaos.uninstall()
    rep = run_jobs(mk(), cache=cache, wave_state=ws, max_wave=1,
                   wave_yield=1, bucket_overrides=bo)
    assert rep.meta["resumed_jobs"] >= 1
    assert rep.meta["parked_waves"] >= 1
    for got, want in zip(rep.outcomes, ref.outcomes):
        _same(got.res, want.res)
        gid = want.res.distinct_states - 1
        assert _labels(got.trace(gid)) == _labels(want.trace(gid))


def test_wave_state_store_corruption_is_a_miss(tmp_path):
    from raft_tla_tpu.serve.wavestate import WaveStateStore
    ws = WaveStateStore(str(tmp_path))
    ws.save("k1", {"fm": np.ones((4,), bool)},
            {"cache_key": "k1", "depth": 3})
    arrays, book = ws.load("k1")
    assert book["depth"] == 3 and arrays["fm"].all()
    # torn file -> miss with a warning, never an error
    path = ws._file("k1")
    with open(path, "r+b") as fh:
        fh.truncate(os.path.getsize(path) // 2)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert ws.load("k1") is None
    assert any("integrity" in str(x.message) for x in w)
    # foreign key -> miss
    ws.save("k2", {}, {"cache_key": "OTHER"})
    assert ws.load("k2") is None
    ws.drop("k1")
    assert ws.load("k1") is None


# ---------------------------------------------------------------------------
# obs / watch: retry stamps
# ---------------------------------------------------------------------------

def test_obs_retry_ledger_heartbeat_and_watch(tmp_path):
    from raft_tla_tpu.obs import Obs
    from raft_tla_tpu.obs.heartbeat import Heartbeat
    from raft_tla_tpu.obs.ledger import RunLedger
    spec = importlib.util.spec_from_file_location(
        "watch", os.path.join(_REPO, "tools", "watch.py"))
    watch = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(watch)
    ledger_path = str(tmp_path / "ledger.jsonl")
    hb_path = str(tmp_path / "hb.json")
    obs = Obs(ledger=RunLedger(ledger_path),
              heartbeat=Heartbeat(hb_path),
              meta={"spec": "raft"})
    obs.start()
    obs.dispatch(kind="level", depth=3,
                 metrics={"distinct_states": 42})
    obs.retry(attempt=2, max_attempts=4, wait_s=1.5,
              error=RuntimeError("device lost"))
    recs = [json.loads(ln) for ln in open(ledger_path)]
    rr = next(r for r in recs if r["kind"] == "retry")
    assert rr["attempt"] == 2 and rr["max_attempts"] == 4
    assert "device lost" in rr["error"] and rr["spec"] == "raft"
    hb = json.load(open(hb_path))
    assert hb["status"] == "backoff" and \
        hb["retry"]["attempt"] == 2
    # watch renders RETRYING (healthy, not stalled) even when the
    # last dispatch is old
    line, code = watch.status_line(hb_path, ledger_path, stale_s=0.0)
    assert code == 0 and "RETRYING attempt 2/4" in line
    obs.finish(depth=3, states=42)


def test_cli_chaos_and_retry_flag_validation():
    from raft_tla_tpu.cli import main
    # malformed chaos spec is a usage error (exit 2), not a traceback
    rc = main(["check", os.path.join(_REPO, "configs",
                                     "tlc_membership", "raft.cfg"),
               "--chaos", "bogus_site:at=1", "--max-depth", "1"])
    assert rc == 2
    rc = main(["check", os.path.join(_REPO, "configs",
                                     "tlc_membership", "raft.cfg"),
               "--retries", "-1", "--max-depth", "1"])
    assert rc == 2
    rc = main(["check", os.path.join(_REPO, "configs",
                                     "tlc_membership", "raft.cfg"),
               "--resume-portable", "--max-depth", "1"])
    assert rc == 2


@pytest.mark.slow
def test_wave_kill_with_retries_self_heals(tmp_path):
    """--retries on batch absorbs the kill: one invocation, the retry
    re-runs the job list and the wave state makes it incremental."""
    import subprocess
    import sys
    cfg = os.path.join(_REPO, "configs", "tlc_membership", "raft.cfg")
    job = json.dumps({
        "spec": "raft", "config": cfg, "label": "j",
        "max_depth": 12,
        "overrides": {"servers": 2, "next": "NextAsync",
                      "bounds": {"max_log_length": 1,
                                 "max_timeouts": 1,
                                 "max_client_requests": 1}}})
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "raft_tla_tpu", "batch", "--job", job,
         "--cache-dir", str(tmp_path / "cache"),
         "--wave-state", str(tmp_path / "waves"),
         "--chaos", "wave_kill:at=1", "--retries", "1",
         "--backoff", "0.01"],
        capture_output=True, text=True, cwd=_REPO, env=env,
        timeout=600)
    assert p.returncode == 0, (p.stdout, p.stderr)
    rows = [json.loads(ln) for ln in p.stdout.splitlines() if ln]
    assert rows[0]["resumed_jobs"] == 1
    assert rows[1]["status"] == "done"
