"""Checkpoint/resume: interrupt at level k, resume, land on counts
identical to an uninterrupted run (TLC's states/ checkpointing —
/root/reference/.gitignore:4; SURVEY §5)."""

import json
import subprocess
import sys

import pytest

from raft_tla_tpu.config import Bounds, ModelConfig
from raft_tla_tpu.engine.bfs import Engine

MICRO = ModelConfig(
    n_servers=2, init_servers=(0, 1), values=(1,),
    max_inflight_override=4, symmetry=True,
    bounds=Bounds.make(max_log_length=1, max_timeouts=1,
                       max_client_requests=1))


@pytest.mark.slow
def test_checkpoint_resume_identical(tmp_path):
    full = Engine(MICRO, chunk=64, store_states=True).check()

    ckpt = str(tmp_path / "run.ckpt")
    e1 = Engine(MICRO, chunk=64, store_states=True)
    part = e1.check(max_depth=12, checkpoint_path=ckpt)
    assert part.depth == 12
    assert part.distinct_states < full.distinct_states

    e2 = Engine(MICRO, chunk=64, store_states=True)
    resumed = e2.check(resume_from=ckpt)
    assert resumed.distinct_states == full.distinct_states
    assert resumed.depth == full.depth
    assert resumed.generated_states == full.generated_states
    assert resumed.level_sizes == full.level_sizes
    # the parent/lane archives survive the resume: every state of the
    # full run is reconstructible
    assert sum(len(p) for p in e2._parents) == full.distinct_states


def test_checkpoint_config_mismatch(tmp_path):
    ckpt = str(tmp_path / "run.ckpt")
    Engine(MICRO, chunk=64, store_states=False).check(
        max_depth=6, checkpoint_path=ckpt)
    other = Engine(MICRO.with_(symmetry=False), chunk=64,
                   store_states=False)
    with pytest.raises(ValueError, match="different model config"):
        other.check(resume_from=ckpt)


@pytest.mark.slow
def test_cli_checkpoint_resume(tmp_path):
    ckpt = str(tmp_path / "cli.ckpt")
    base = [sys.executable, "-m", "raft_tla_tpu", "check",
            __import__("conftest").ref_or_local(
                "/root/reference/tlc_membership/raft.cfg"),
            "--servers", "2", "--init-servers", "2",
            "--max-log-length", "1", "--max-timeouts", "1",
            "--max-client-requests", "1", "--chunk", "64",
            "--no-store", "--keep-going"]
    r1 = subprocess.run(base + ["--max-depth", "8",
                                "--checkpoint", ckpt],
                        capture_output=True, text=True, timeout=600)
    assert r1.returncode == 0, r1.stderr
    r2 = subprocess.run(base + ["--resume", ckpt, "--max-depth", "12"],
                        capture_output=True, text=True, timeout=600)
    assert r2.returncode == 0, r2.stderr
    full = subprocess.run(base + ["--max-depth", "12"],
                          capture_output=True, text=True, timeout=600)
    assert full.returncode == 0, full.stderr
    got = json.loads(r2.stdout.splitlines()[0])
    want = json.loads(full.stdout.splitlines()[0])
    assert got["distinct_states"] == want["distinct_states"]
    assert got["depth"] == want["depth"]


def test_resume_keeps_lane_counts_and_refuses_arrival_order_bags(
        tmp_path):
    """A resumed check reports the enabled lanes an uninterrupted one
    does; a checkpoint written before bags were kept in message order
    (its meta lacks ``bag_order``) is refused, natively and portably —
    and so is one of the removed shard_map mesh engines (meta
    ``sharded``, alone or with ``spill``), by name, never read as the
    classic or spill format it resembles."""
    import numpy as np
    from raft_tla_tpu.engine.bfs import CheckpointError, ckpt_read
    from raft_tla_tpu.resil.ckpt_chain import publish
    from raft_tla_tpu.resil.portable import load_portable_image
    full = Engine(MICRO, chunk=64, store_states=False).check(max_depth=8)
    ckpt = str(tmp_path / "run.ckpt")
    Engine(MICRO, chunk=64, store_states=False).check(
        max_depth=5, checkpoint_path=ckpt)
    resumed = Engine(MICRO, chunk=64, store_states=False).check(
        max_depth=8, resume_from=ckpt)
    assert resumed.level_sizes == full.level_sizes
    assert resumed.lanes_enabled == full.lanes_enabled
    assert sum(full.lanes_enabled.values()) == full.generated_states - 1

    def rewritten(name, edit):
        with np.load(ckpt) as z:
            data = dict(z)
        meta = json.loads(str(data["meta"]))
        edit(meta)
        data["meta"] = np.array(json.dumps(meta))
        tmp = str(tmp_path / f"{name}.tmp.npz")
        np.savez(tmp, **data)
        out = str(tmp_path / f"{name}.ckpt")
        publish(tmp, out)
        return out

    def drop_bag_order(meta):
        assert meta.pop("bag_order") == 1

    removed = "removed shard_map mesh engine format"
    for name, edit, match in [
            ("old", drop_bag_order, "message order"),
            ("mesh", lambda m: m.update(sharded=True, D=2), removed),
            ("spill_sharded",
             lambda m: m.update(sharded=True, spill=True, D=2),
             removed)]:
        bad = rewritten(name, edit)
        with pytest.raises(CheckpointError, match=match):
            Engine(MICRO, chunk=64, store_states=False).check(
                max_depth=8, resume_from=bad)
        with pytest.raises(CheckpointError, match=match):
            load_portable_image(bad)
        if match == removed:
            # the spill engine's reader refuses it too
            with pytest.raises(CheckpointError, match=match):
                ckpt_read(bad, repr(MICRO), 64, (), spill=True)
