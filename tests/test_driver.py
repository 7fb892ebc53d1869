"""The shared level-harvest/driver core (engine/driver — ROADMAP item
5): unit reps for the extracted loop's exact semantics (depth gate,
id guard, burst checkpoint crossing, callback ordering) plus the
routing reps pinning that every engine's harvest loop (bfs, spill,
the pjit engine's inherited bfs loop, batched serve) actually calls
it — the bit-exactness of the call sites themselves is pinned by
every engine differential (test_engine / test_spill / test_pjit /
test_serve).
"""

import inspect
import sys

import numpy as np
import pytest

from raft_tla_tpu.engine import driver
from raft_tla_tpu.engine.bfs import CheckResult


def _res():
    return CheckResult()


# ---------------------------------------------------------------------------
# unit reps: the extracted semantics, exactly
# ---------------------------------------------------------------------------

def test_ckpt_due_after_burst_crosses_any_multiple():
    # a multi-level jump over a multiple fires even when the landing
    # depth is not an exact multiple (the exact-modulo test would skip
    # every checkpoint with checkpoint_every > 1)
    assert driver.ckpt_due_after_burst(7, 3, 5)        # crossed 5
    assert not driver.ckpt_due_after_burst(4, 3, 5)    # no multiple
    assert driver.ckpt_due_after_burst(10, 9, 5)       # exact landing
    assert driver.ckpt_due_after_burst(23, 4, 5)       # several crossed
    # checkpoint_every <= 1 clamps to every level
    assert driver.ckpt_due_after_burst(2, 1, 0)


def test_ckpt_due_at_level_plain_modulo():
    assert driver.ckpt_due_at_level(10, 5)
    assert not driver.ckpt_due_at_level(9, 5)
    assert driver.ckpt_due_at_level(3, 1)
    assert driver.ckpt_due_at_level(3, 0)        # clamped to 1


def test_guard_id_space():
    driver.guard_id_space(2 ** 31 - 2)           # fine
    with pytest.raises(RuntimeError, match="state-id space exhausted"):
        driver.guard_id_space(2 ** 31 - 1)


def test_gate_level_depth():
    res = _res()
    # all-pruned pseudo-level: depth rolls back, no level size
    assert driver.gate_level_depth(res, 5, 0, 0, 17) == 4
    assert res.level_sizes == []
    # all-duplicates level (n_gen > 0) DOES count
    assert driver.gate_level_depth(res, 5, 0, 3, 17) == 5
    assert res.level_sizes == [17]
    assert driver.gate_level_depth(res, 6, 2, 9, 11) == 6
    assert res.level_sizes == [17, 11]


def test_harvest_fused_levels_accumulation_and_gating():
    res = _res()
    # levels: (n_lvl, n_viol, faults, n_expand, n_gen)
    stats = [(3, 1, 0, 7, 9),       # real level with a violation
             (0, 0, 0, 5, 0),       # all-pruned pseudo-level
             (0, 0, 1, 4, 2),       # all-duplicates level: counts
             (2, 0, 0, 6, 8)]
    calls = []
    depth, n_states = driver.harvest_fused_levels(
        res, len(stats), lambda li: stats[li], 10, 100,
        archive=lambda li, n: calls.append(("arch", li, n)),
        violations=lambda li, n, base: calls.append(("viol", li, n,
                                                     base)),
        visited=lambda li, n: calls.append(("vis", li, n)))
    assert depth == 13                  # 3 real levels of 4
    assert n_states == 105
    assert res.distinct_states == 5
    assert res.generated_states == 19
    assert res.overflow_faults == 1
    assert res.violations_global == 1
    assert res.levels_fused == 3        # ≡ depth advanced
    assert res.level_sizes == [7, 4, 6]
    # archive runs for EVERY level (the callback owns its own
    # empty-level policy); violations only where seen, with the
    # PRE-increment gid base; visited after the gid advance, per level
    assert calls == [("arch", 0, 3), ("viol", 0, 3, 100),
                     ("vis", 0, 3),
                     ("arch", 1, 0), ("vis", 1, 0),
                     ("arch", 2, 0), ("vis", 2, 0),
                     ("arch", 3, 2), ("vis", 3, 2)]


def test_harvest_fused_levels_id_guard_flag():
    near = 2 ** 31 - 3
    stats = [(2, 0, 0, 2, 2)]
    with pytest.raises(RuntimeError, match="state-id space"):
        driver.harvest_fused_levels(_res(), 1, lambda li: stats[li],
                                    0, near)
    # id_guard=False preserves the batched-serve semantics (per-job
    # ids never approach 2^31; the historical serve harvest carried
    # no guard)
    depth, n = driver.harvest_fused_levels(
        _res(), 1, lambda li: stats[li], 0, near, id_guard=False)
    assert (depth, n) == (1, near + 2)


def test_burst_archive_slice_copies_out_of_ring():
    L, KB = 3, 4
    par = np.arange(L * KB, dtype=np.int32).reshape(L, KB)
    lane = par + 100
    st = {"x": np.arange(2 * 5 * L * KB, dtype=np.int32)
          .reshape(2, 5, L, KB)}
    p, ln, rows = driver.burst_archive_slice(par, lane, st, 1, 2)
    assert p.tolist() == [4, 5] and ln.tolist() == [104, 105]
    assert rows["x"].shape == (2, 2, 5)     # batch-major
    assert np.array_equal(rows["x"][0], st["x"][:, :, 1, 0])
    # the slices are COPIES (the ring buffer is reused next burst)
    p[0] = -1
    assert par[1, 0] == 4


# ---------------------------------------------------------------------------
# routing reps: every harvest loop calls the shared core (the point of
# ROADMAP item 5 — control-flow duplication is dead, so a drift class
# can no longer exist)
# ---------------------------------------------------------------------------

CALL_SITES = [
    ("raft_tla_tpu.engine.bfs", "Engine"),
    ("raft_tla_tpu.engine.spill", "SpillEngine"),
    ("raft_tla_tpu.parallel.pjit_mesh", "PjitShardedEngine"),
    ("raft_tla_tpu.serve.batch", "BucketEngine"),
]
# the method that holds each class's harvest loop (default: check)
_LOOP = {"BucketEngine": "_harvest"}


@pytest.mark.parametrize("modname,_cls", CALL_SITES)
def test_harvest_routes_through_driver(modname, _cls):
    import importlib
    cls = getattr(importlib.import_module(modname), _cls)
    # read the module that defines the loop: an engine that inherits
    # its loop (the pjit engine runs Engine.check) is checked there,
    # and one that grows a loop of its own is checked in its module
    loop = _LOOP.get(_cls, "check")
    owner = next(k for k in cls.__mro__ if loop in vars(k))
    src = inspect.getsource(sys.modules[owner.__module__])
    assert "harvest_fused_levels" in src, \
        f"{owner.__module__}: fused harvest no longer routes through " \
        "engine/driver"
    # the tell-tale of a re-inlined copy: the pseudo-level counter
    # bump next to a local depth increment (levels_fused is accounted
    # INSIDE driver.harvest_fused_levels / the per-level drivers'
    # shared gate only)
    assert "res.levels_fused += 1" not in src, \
        f"{owner.__module__}: a local harvest copy re-appeared"


def test_per_level_drivers_share_the_gate():
    import importlib
    for modname in ("raft_tla_tpu.engine.bfs",
                    "raft_tla_tpu.engine.spill"):
        src = inspect.getsource(importlib.import_module(modname))
        assert ("gate_level_depth" in src
                or "harvest_fused_levels" in src), modname
        # checkpoint crossing decisions live in driver too
        assert ("ckpt_due_at_level" in src
                or "ckpt_due_after_burst" in src), modname
