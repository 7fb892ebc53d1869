"""The benchmark's own tests (CPU, small sizes), collected by tier-1:

    JAX_PLATFORMS=cpu python -m pytest tests/benchmark -q

- every manifest entry resolves to its files by name, within the
  contract's limits;
- the harness on a CPU exits non-zero and prints no result;
- two back-to-back checks on one engine answer identically, and equal
  the reference, at micro bounds of both configurations and of config
  #3 (the membership fixture in configs/, which has no cell yet);
- the plain reference agrees with the program's native checker, a
  second witness, at micro bounds of all three and at config #3's own
  bounds to depth 15; the membership actions add states; the two
  configurations' answers are pinned; the reference refuses what it
  was not written for;
- a corrupted level size fails the comparison, the control (a narrow
  dedup key) fails it at the cells' own sizes, and each fault planted
  under the timed path makes a whole run report ``correct`` false;
- the trace reduction's arithmetic on a trace recorded on the CPU.
"""

import argparse
import copy
import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
sys.path.insert(0, BENCH)

from harness import compare, manifest, reference  # noqa: E402
from harness import trace as tr  # noqa: E402
from harness.system import System  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CACHE = os.path.join(ROOT, ".bench_cache", "reference")
# BASELINE.json config #3: Server 4 beyond InitServer 3, NextDynamic
CONFIG3 = "raft-tlc-s4-membership"
MICRO = {"max_log_length": 1, "max_timeouts": 1, "max_client_requests": 1}


@pytest.fixture(scope="module")
def man():
    return manifest.load()


@pytest.fixture(scope="module")
def ref_exe():
    return reference.build(CACHE)


def _conf_dir(name):
    if name == CONFIG3:
        return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "configs")
    return os.path.join(BENCH, "configs")


def _conf(name):
    with open(os.path.join(_conf_dir(name), name + ".json")) as fh:
        return json.load(fh)


def micro(name):
    """The configuration at micro bounds (a CPU-sized space)."""
    c = copy.deepcopy(_conf(name))
    c["cfg"] = os.path.join(_conf_dir(name), c["cfg"])
    if name in ("raft-tlc-s3-l3", CONFIG3):
        c["bound_flags"] = dict(MICRO)
        c["model"]["bounds"].update(MICRO, max_terms=2)
        # config #3's membership actions first fire at depth 10
        c["max_depth"] = 9 if name == "raft-tlc-s3-l3" else 12
        c["engine"] = {"chunk": 256, "store_states": False}
    else:
        c["max_depth"] = 5
    return c


# -- the manifest -------------------------------------------------------

def test_manifest_entries_resolve_by_name(man):
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert man["paths"] == ["bench", "tests/benchmark"]
    n_cells = len(man["workloads"])
    # a full check: 2 + 14 runs a cell, each run_seconds + 60 s, 180 s
    # a cell to compile, 1200 s spare, all of it for 24 cells
    assert (2 + 14 * 24) * (man["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    configs = {c["name"]: c for c in man["configs"]}
    for c in man["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["source"]) <= 200
        conf = _conf(c["name"])
        assert os.path.join(ROOT, c["file"]) == os.path.join(
            BENCH, "configs", c["name"] + ".json")
        assert conf["source"] == c["source"]
        assert conf["reduced"] == c["reduced"]
        for k in ("cfg",):
            assert os.path.exists(os.path.join(BENCH, "configs", conf[k]))
        assert os.path.exists(os.path.join(
            BENCH, "configs", conf["cfg"][:-4] + ".tla"))
    metrics = man["end_to_end"] + man["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert NAME.match(m["name"]) and m["better"] in ("lower",
                                                         "higher")
        mod = manifest.load_module(manifest.metric_file(m["name"]))
        assert callable(mod.read)
    for m in man["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert len({w["name"] for w in man["workloads"]}) == n_cells
    for w in man["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] == 1
        cell = manifest.cell(w["name"], man)
        assert os.path.exists(manifest.driver_file(cell.traffic["driver"]))
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in e2e


def test_harness_without_a_chip_exits_nonzero():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "apalache-s2-k10",
         "--seed", "3000000019", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"metrics"' not in p.stdout and "needs a TPU" in p.stderr


# -- the engine against the reference -----------------------------------

@pytest.mark.parametrize("name", ["raft-tlc-s3-l3",
                                  "raft-apalache-s2-k10", CONFIG3])
def test_back_to_back_checks_answer_identically(name, ref_exe):
    c = micro(name)
    system = System(c, os.path.dirname(c["cfg"]))
    a, b = system.check(), system.check()
    assert (a.distinct, a.generated, a.depth, a.level_sizes,
            a.violated) == (b.distinct, b.generated, b.depth,
                            b.level_sizes, b.violated)
    ref = reference.check(ref_exe, c["model"], c["max_depth"])
    assert compare.ok(compare.compare([a, b], ref))


def _agrees_with_native(c, ref):
    from raft_tla_tpu import native
    nat = native.check(_program_cfg(c), threads=2,
                       max_depth=c["max_depth"])
    assert (ref.distinct, ref.generated, ref.depth, ref.level_sizes) == (
        nat.distinct_states, nat.generated_states, nat.depth,
        nat.level_sizes)
    forms = c["model"].get("invariant_forms", {})
    assert sorted(forms.get(nm, nm) for nm in ref.violated) == sorted(
        set(nat.violations))


@pytest.mark.parametrize("name", ["raft-tlc-s3-l3",
                                  "raft-apalache-s2-k10", CONFIG3])
def test_reference_agrees_with_the_programs_native_checker(name, ref_exe):
    """A second witness: the program's native C++ checker (a 64-bit
    fingerprint set, multithreaded) reads what the plain reference
    reads, at micro bounds."""
    c = micro(name)
    _agrees_with_native(c, reference.check(ref_exe, c["model"],
                                           c["max_depth"]))


def test_reference_agrees_with_native_at_config3_bounds(ref_exe):
    """Config #3 at its own bounds (MaxLogLength 2, MaxTimeouts 1,
    MaxClientRequests 2) to depth 15, the deepest at which the two
    agree: from depth 16 the program keeps another member of a VIEW
    class whose members' histories differ, as the order of its bag's
    slots falls (PERF.md, section 7)."""
    c = copy.deepcopy(_conf(CONFIG3))
    c["cfg"] = os.path.join(_conf_dir(CONFIG3), c["cfg"])
    c["max_depth"] = 15
    ref = reference.check(ref_exe, c["model"], 15)
    assert (ref.distinct, ref.generated, ref.violated) == (180685, 544816,
                                                           [])
    assert ref.level_sizes == [1, 2, 4, 10, 20, 35, 56, 91, 141, 213, 382,
                               1117, 4566, 19757, 80652]
    _agrees_with_native(c, ref)


def test_membership_actions_add_states(ref_exe):
    """AddNewServer, DeleteServer and their messages do real work: the
    fixture under NextDynamic reaches more states than under Next."""
    c = micro(CONFIG3)
    dyn = reference.check(ref_exe, c["model"], c["max_depth"])
    stat = reference.check(ref_exe, dict(c["model"], next="Next"),
                           c["max_depth"])
    assert dyn.distinct > stat.distinct
    assert dyn.generated > stat.generated


@pytest.mark.parametrize("name,answer", [
    ("raft-apalache-s2-k10",
     (35279, 89337, [2, 6, 18, 56, 150, 370, 878, 1982, 4258, 8782], [])),
    ("raft-tlc-s3-l3",
     (738319, 1818497, [1, 2, 4, 7, 12, 19, 28, 40, 57, 85, 167, 507, 1942,
                        7579, 27966, 96189, 309574], [])),
])
def test_reference_keeps_the_cells_answers(name, answer, ref_exe):
    """The two cells' configurations at their own sizes: the reference's
    answers, pinned as they were before it learnt the membership spec."""
    c = _conf(name)
    ref = reference.check(ref_exe, c["model"], c["max_depth"])
    assert (ref.distinct, ref.generated, ref.level_sizes,
            ref.violated) == answer


def test_reference_refuses_what_it_was_not_written_for(ref_exe):
    """Scenario properties, the prefix pins, unknown families and
    names: exit 2, not a guess."""
    c = micro(CONFIG3)
    pins = ["CommitWhenConcurrentLeaders_constraint",
            "CommitWhenConcurrentLeaders_unique",
            "MajorityOfClusterRestarts_constraint"]
    for key, value in (("next", "NextUnreliable"),
                       ("invariants", ["MembershipChangeCommits"]),
                       ("invariants", ["FirstCommit"]),
                       ("invariants", ["OneLeader"]),
                       *(("constraints", c["model"]["constraints"] + [p])
                         for p in pins)):
        model = dict(c["model"], **{key: value})
        with pytest.raises(subprocess.CalledProcessError) as e:
            reference.check(ref_exe, model, 3)
        assert e.value.returncode == 2


def _program_cfg(c):
    from raft_tla_tpu.cfg.parser import load_model
    from raft_tla_tpu.config import Bounds
    cfg = load_model(c["cfg"])
    if c["bound_flags"]:
        b = cfg.bounds
        cfg = cfg.with_(bounds=Bounds.make(
            max_restarts=b.max_restarts,
            max_membership_changes=b.max_membership_changes,
            max_trace=b.max_trace, **c["bound_flags"]))
    return cfg


# -- the comparison and its control -------------------------------------

def test_corrupted_level_size_fails_the_comparison(ref_exe):
    c = micro("raft-apalache-s2-k10")
    ref = reference.check(ref_exe, c["model"], c["max_depth"])
    good = reference.check(ref_exe, c["model"], c["max_depth"])
    assert compare.ok(compare.compare([good], ref))
    good.level_sizes[2] += 1
    out = compare.compare([good], ref)
    assert not compare.ok(out) and out["level_size_gap"]["value"] == 1


@pytest.mark.parametrize("name", ["raft-apalache-s2-k10",
                                  "raft-tlc-s3-l3"])
def test_control_fails_at_the_cells_own_size(name, ref_exe):
    """The control: the reference in the program's place with a
    narrower dedup key (the configuration's ``control.fp_bits``)."""
    c = _conf(name)
    exact = reference.check(ref_exe, c["model"], c["max_depth"])
    ctl = reference.check(ref_exe, c["model"], c["max_depth"],
                          fp_bits=c["control"]["fp_bits"])
    out = compare.compare([ctl], exact)
    assert not compare.ok(out), out
    assert out["distinct_gap"]["value"] > 0


def _micro_root(tmp_path, name, cell):
    man = manifest.load()
    c = micro(name)
    with open(tmp_path / "micro.json", "w") as fh:
        json.dump(c, fh)
    man["configs"] = [{"name": "micro", "source": "test",
                       "file": "micro.json", "reduced": [],
                       "why": "test"}]
    entry = next(w for w in man["workloads"] if w["name"] == cell)
    man["workloads"] = [dict(entry, config="micro")]
    with open(tmp_path / "BENCHMARK.json", "w") as fh:
        json.dump(man, fh)
    return str(tmp_path)


def _plant(kind):
    def fault(system):
        eng = system.engine
        if kind in ("unchanged", "half"):
            eng.burst = False          # every level through the step
            step, calls = eng._step_jit, [0]

            def broken(carry, caps):
                calls[0] += 1
                if kind == "unchanged" or calls[0] % 2 == 0:
                    return carry
                return step(carry, caps)
            eng._step_jit = broken
            return
        orig = system.check

        def altered():
            rec = orig()
            if kind == "answer":
                rec.level_sizes[-1] += 1
            else:
                rec.violated = sorted(set(rec.violated) | {"LogMatching"})
            return rec
        system.check = altered
    return fault


@pytest.mark.parametrize("kind", [None, "unchanged", "half", "answer",
                                  "verdict"])
def test_a_run_with_a_planted_fault_is_not_correct(kind, tmp_path,
                                                   monkeypatch):
    import jax
    import run
    from harness import device
    monkeypatch.setattr(run, "_settings", lambda jax: None)
    monkeypatch.setattr(device, "peaks",
                        lambda kind: {"name": "test", "hbm_bytes": 16e9})
    root = _micro_root(tmp_path, "raft-tlc-s3-l3", "tlc-s3-d17")
    args = argparse.Namespace(workload="tlc-s3-d17", seed=3000000007,
                              seconds=0.5, trace=0)
    res, compared = run.run(args, fault=kind and _plant(kind),
                            require=lambda n: jax.devices()[:n],
                            root=root)
    assert res["correct"] is (kind is None), compared
    assert list(res)[-1] == "compared"
    if kind is not None:
        assert res["failed"] == res["attempted"] >= 1


# -- the trace reduction --------------------------------------------------

def test_trace_reduction_arithmetic_on_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(tr.WINDOW):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("harvest"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    # on the CPU the XLA client thread stands in for a device's op line
    ev = tr.load(tr.find_xplane(str(tmp_path)), {tr.WINDOW, "harvest"},
                 device_plane=re.compile(r"^/host:CPU$"),
                 ops_line=re.compile(r"^tf_XLAPjRtCpuClient"),
                 modules_line=None)
    lo, hi = tr.window(ev)
    s = tr.reduce(ev, lo, hi)
    ops = next(iter(ev.ops.values()))
    # busy is the union of the op intervals inside the window
    pts = sorted((max(a, lo), min(b, hi)) for a, b, _ in ops
                 if min(b, hi) > max(a, lo))
    busy, end = 0.0, lo
    for a, b in pts:
        if b > end:
            busy += b - max(a, end)
            end = b
    assert s.busy_s == pytest.approx(busy / 1e9)
    assert 0 < s.busy_s <= s.window_s == pytest.approx((hi - lo) / 1e9)
    assert sum(s.idle_by_label.values()) == pytest.approx(
        s.window_s - s.busy_s)
    assert set(s.idle_by_label) <= {tr.WINDOW, "harvest"}
    assert len(s.idle_gaps) <= 10 and len(s.top_ops) <= 10
    assert s.idle_gaps == sorted(s.idle_gaps, key=lambda g: -g[1])


def test_interval_arithmetic():
    spans = [(0, 10, "a"), (5, 20, "b"), (30, 40, "c"), (35, 36, "d")]
    merged = tr.union(spans, 2, 38)
    assert merged == [[2, 20], [30, 38]]
    assert tr.gaps(merged, 0, 50) == [(0, 2), (20, 30), (38, 50)]
    host = [(0, 100, "bench.window"), (15, 32, "harvest")]
    assert tr.label_at(25, host) == "harvest"
    assert tr.label_at(40, host) == "bench.window"
    assert tr.label_at(200, host) == "no span"
    assert tr.program("jit__chunk_step_impl(1234)") == "jit__chunk_step_impl"
    assert tr.op_name("%while.44 = (u32[8]{0}, s32[]) while(%t)") == \
        "while.44"
    assert tr.op_name("fusion.3") == "fusion.3"
