"""Pod-scale pjit engine (parallel/pjit_mesh) differentials.

The engine puts the WHOLE BFS state under NamedShardings on a device
mesh (here: conftest's 8 virtual CPU devices in-process, plus 2
controller processes x 2 virtual devices with gloo collectives as the
DCN stand-in) and must stay bit-identical to the classic engine — same
program, different partitioning — and therefore to the oracle: counts,
level sizes, global ids, archives, witness traces, checkpoints.

Budget: the classic reference and the pjit engine are module-shared
(one depth-capped run each — every engine instance costs ~6-10s of
XLA:CPU compile); the 2-controller rep is depth-capped; full-space
duplicates are slow-marked.
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

from raft_tla_tpu.config import Bounds, ModelConfig, NEXT_ASYNC
from raft_tla_tpu.engine.bfs import Engine
from raft_tla_tpu.parallel.pjit_mesh import (
    CARRY_RULES, PjitShardedEngine, match_partition_rules)

from conftest import cached_explore

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tools", "pjit_worker.py")

MICRO = ModelConfig(
    n_servers=2, init_servers=(0, 1), values=(1,),
    next_family=NEXT_ASYNC, symmetry=True, max_inflight_override=4,
    bounds=Bounds.make(max_log_length=1, max_timeouts=1,
                       max_client_requests=1))
DEPTH = 10


@pytest.fixture(scope="module")
def ref():
    eng = Engine(MICRO, chunk=64, lcap=1 << 12, vcap=1 << 15,
                 store_states=True)
    res = eng.check(max_depth=DEPTH)
    return eng, res


@pytest.fixture(scope="module")
def pj():
    eng = PjitShardedEngine(MICRO, chunk=64, lcap=1 << 12,
                            vcap=1 << 15, store_states=True)
    res = eng.check(max_depth=DEPTH)
    return eng, res


# ---------------------------------------------------------------------------
# rule-matched PartitionSpec trees (the SNIPPETS.md exemplar shape)
# ---------------------------------------------------------------------------

def test_partition_rules_axis_kinds():
    from jax.sharding import PartitionSpec as P
    tree = {"vis": (np.zeros((8, 2)),), "claims": np.zeros((8,)),
            "front": {"x": np.zeros((3, 4, 16))},
            "lpar": np.zeros((16,)), "fmask": np.zeros((16,)),
            "n_front": np.zeros(())}
    specs = match_partition_rules(CARRY_RULES, tree)
    assert specs["vis"][0] == P("d", None)       # slot axis = dim 0
    assert specs["claims"] == P("d")
    assert specs["front"]["x"] == P(None, None, "d")   # batch-last
    assert specs["lpar"] == P("d")
    assert specs["n_front"] == P()               # scalars replicate


def test_pjit_mesh_spans_all_devices(pj):
    eng, _res = pj
    assert eng.D == 8                            # conftest's 8-dev CPU


def test_pjit_cli_flag_validation():
    from raft_tla_tpu.cli import main
    cfg_path = os.path.join(REPO, "configs", "tlc_membership",
                            "raft.cfg")
    # --pjit and --spill are different engines: usage error, exit 2
    assert main(["check", cfg_path, "--pjit", "--spill",
                 "--max-depth", "1"]) == 2


# ---------------------------------------------------------------------------
# parity vs the oracle and the classic engine (counts / level sizes /
# gids / archives / witness traces)
# ---------------------------------------------------------------------------

def test_pjit_parity_counts(pj):
    _eng, res = pj
    want = cached_explore(MICRO, max_depth=DEPTH)
    assert res.distinct_states == want.distinct_states
    assert res.depth == want.depth
    assert res.generated_states == want.generated_states
    assert list(res.level_sizes) == list(want.level_sizes)
    assert res.overflow_faults == 0


def test_pjit_gids_and_traces_match_classic(ref, pj):
    e1, r1 = ref
    e2, r2 = pj
    assert r2.distinct_states == r1.distinct_states
    # global ids are bit-identical (same program, different
    # partitioning): spot-check states and full witness chains across
    # the id range
    for gid in (0, 1, 7, 50, r1.distinct_states - 1):
        assert e1.get_state(gid) == e2.get_state(gid), gid
        t1 = [lbl for lbl, _ in e1.trace(gid)]
        t2 = [lbl for lbl, _ in e2.trace(gid)]
        assert t1 == t2, (gid, t1, t2)


def test_pjit_checkpoint_is_classic_format_and_archives_resume(
        pj, ref, tmp_path):
    """The pjit engine writes CLASSIC-format checkpoints (gathered to
    host), so (a) the classic engine resumes them directly, and (b)
    store_states x checkpoint works from day one: archives ride the
    checkpoint and a resumed run's gids/traces equal an uninterrupted
    run's."""
    eng, _res = pj
    e1, r1 = ref
    ck = str(tmp_path / "pjit.ckpt")
    part = eng.check(max_depth=6, checkpoint_path=ck)
    assert part.distinct_states < r1.distinct_states
    assert os.path.exists(ck)
    # (b) resume on the SAME pjit engine: archives restored, final
    # state bit-equal to the uninterrupted reference
    full = eng.check(max_depth=DEPTH, resume_from=ck)
    assert full.distinct_states == r1.distinct_states
    assert list(full.level_sizes) == list(r1.level_sizes)
    for gid in (3, 80, r1.distinct_states - 1):
        assert [l for l, _ in eng.trace(gid)] == \
            [l for l, _ in e1.trace(gid)], gid
    # (a) the classic engine resumes the pjit checkpoint directly
    got = e1.check(max_depth=DEPTH, resume_from=ck)
    assert got.distinct_states == r1.distinct_states
    assert list(got.level_sizes) == list(r1.level_sizes)
    # leave the module-shared engines on their canonical full-run
    # state for any later test (cheap: programs are already compiled)
    eng.check(max_depth=DEPTH)
    e1.check(max_depth=DEPTH)


def test_pjit_violation_matches_classic():
    """A violation found on the mesh decodes to the classic engine's
    state id and state, and its witness trace replays identically
    (the violation rows come back through the replicated harvest)."""
    import jax
    cfg = MICRO.with_(invariants=MICRO.invariants +
                      ("FirstBecomeLeader",))
    got = {}
    for name, eng in (
            ("classic", Engine(cfg, chunk=64, store_states=True)),
            ("pjit", PjitShardedEngine(cfg, devices=jax.devices()[:2],
                                       chunk=64, store_states=True))):
        r = eng.check(stop_on_violation=True)
        assert r.violations, name
        v = r.violations[0]
        got[name] = (v.invariant, v.state_id, v.state,
                     [(lbl, sv) for lbl, sv in eng.trace(v.state_id)])
    assert got["pjit"][0] == "FirstBecomeLeader"
    assert got["pjit"] == got["classic"]


def test_dryrun_multichip_on_virtual_devices(capsys):
    """The multi-chip dry-run entry point on 2 of conftest's virtual
    CPU devices: the pjit engine's phase-1 step on the flagship model
    and a micro model to fixpoint at the oracle's exact counts."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "graft_entry", os.path.join(REPO, "__graft_entry__.py"))
    entry = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(entry)
    entry.dryrun_multichip(2)
    assert "== oracle" in capsys.readouterr().out


def test_pjit_portable_resume_from_spill_checkpoint(tmp_path, pj):
    """Round-12 portable contract at pod shape: a spill-engine
    checkpoint — archives included — re-partitions onto the pjit mesh
    via resume_image and lands on the exact counts, archives and
    witness traces of an uninterrupted run (the acceptance rep).  The
    spill engine drops constraint-pruned frontier rows, which the
    pjit (classic) frontier layout needs: MICRO's frontier is whole
    only up to depth 2, and a later checkpoint is refused by name.
    Resumes onto the module-shared pjit engine: its compiled programs
    are capacity-compatible, so the rep costs no extra pjit compile."""
    from raft_tla_tpu.engine.bfs import CheckpointError
    from raft_tla_tpu.engine.spill import SpillEngine
    from raft_tla_tpu.resil.portable import load_portable_image
    eng, r_full = pj
    sp = SpillEngine(MICRO, chunk=64, seg=1 << 12, store_states=True)
    pruned = str(tmp_path / "spill6.ckpt")
    sp.check(max_depth=6, checkpoint_path=pruned)
    with pytest.raises(CheckpointError, match="not contiguous"):
        eng.check(max_depth=DEPTH,
                  resume_image=load_portable_image(pruned))
    ck = str(tmp_path / "spill2.ckpt")
    sp.check(max_depth=2, checkpoint_path=ck)
    img = load_portable_image(ck)
    assert img.source_format == "spill" and img.depth == 2
    res = eng.check(max_depth=DEPTH, resume_image=img)
    assert res.distinct_states == r_full.distinct_states
    assert res.depth == r_full.depth
    assert list(res.level_sizes) == list(r_full.level_sizes)
    assert res.generated_states == r_full.generated_states
    # archives ported whole: every state has its row (pre-checkpoint
    # rows keep the SPILL engine's gid order — portable archives
    # preserve the source engine's id assignment, so label-for-label
    # equality with the classic engine is only defined for counts,
    # not row order) and a witness chain replays root-first
    assert sum(len(p) for p in eng._parents) == res.distinct_states
    labels = [l for l, _ in eng.trace(res.distinct_states - 1)]
    assert labels[0] == "Init" and len(labels) == DEPTH + 1
    # NOTE: eng (the module-shared pjit engine) now holds spill-ordered
    # archives; keep this test LAST among the fast per-gid users of
    # the fixture (e1 stays untouched)


# ---------------------------------------------------------------------------
# 2 controller processes x 2 virtual CPU devices, gloo (DCN stand-in)
# ---------------------------------------------------------------------------

def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _run_pair(opts):
    port = _free_port()
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    procs = [subprocess.Popen(
        [sys.executable, WORKER, str(pid), "2", str(port),
         json.dumps(opts)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env, cwd=REPO) for pid in range(2)]
    outs = []
    for p in procs:
        out, err = p.communicate(timeout=600)
        assert p.returncode == 0, f"worker failed:\n{out}\n{err}"
        line = [ln for ln in out.splitlines()
                if ln.startswith("RESULT ")]
        assert line, f"no RESULT line:\n{out}\n{err}"
        outs.append(json.loads(line[-1][len("RESULT "):]))
    return outs


def test_pjit_two_controllers_depth_capped():
    """The fast multi-controller rep: the whole BFS state under
    NamedShardings spanning 2 processes (4 devices total), hash-
    ownership exchange as in-program collectives — both controllers
    land on the oracle's exact counts AND replay the same witness
    chain (archives are controller-replicated under the gather
    fns)."""
    want = cached_explore(MICRO, max_depth=8)
    outs = _run_pair({"max_depth": 8, "trace_gid": 50})
    for r in outs:
        assert r["n_devices"] == 4          # 2 procs x 2 devices
        assert r["distinct"] == want.distinct_states
        assert r["depth"] == want.depth
        assert r["generated"] == want.generated_states
        assert r["level_sizes"] == list(want.level_sizes)
        assert r["violations"] == 0
    assert outs[0]["trace"] == outs[1]["trace"]
    assert outs[0]["trace"][0] == "Init"
    assert outs[0] == dict(outs[1], pid=0)


@pytest.mark.slow
def test_pjit_two_controllers_full_space():
    want = cached_explore(MICRO)
    outs = _run_pair({"trace_gid": 5000})
    for r in outs:
        assert r["distinct"] == want.distinct_states
        assert r["depth"] == want.depth
        assert r["generated"] == want.generated_states
        assert r["level_sizes"] == list(want.level_sizes)
    assert outs[0]["trace"] == outs[1]["trace"]


@pytest.mark.slow
def test_pjit_two_controllers_portable_resume(tmp_path):
    """Multi-controller resume through the portable contract: a
    2-controller pjit run checkpoints (classic format, proc-0
    publish), and a fresh 2-controller run resumes it via
    resume_portable, finishing on the oracle's counts."""
    want = cached_explore(MICRO)
    ck = str(tmp_path / "pjit2.ckpt")
    part = _run_pair({"max_depth": 6, "checkpoint": ck})
    assert all(r["distinct"] < want.distinct_states for r in part)
    assert os.path.exists(ck)
    outs = _run_pair({"resume_portable": ck})
    for r in outs:
        assert r["distinct"] == want.distinct_states
        assert r["depth"] == want.depth


@pytest.mark.slow
def test_pjit_full_space_parity():
    want = cached_explore(MICRO)
    eng = PjitShardedEngine(MICRO, chunk=64, lcap=1 << 13,
                            vcap=1 << 17, store_states=False)
    res = eng.check()
    assert res.distinct_states == want.distinct_states
    assert res.depth == want.depth
    assert res.generated_states == want.generated_states
    assert list(res.level_sizes) == list(want.level_sizes)
