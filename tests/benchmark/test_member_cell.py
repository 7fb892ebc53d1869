"""The config #3 cell, ``tlc-s4-member-d16`` (CPU, small sizes where the
cell's own size does not fit a test):

- its configuration is the membership fixture with only the cell's keys
  changed, and its ``cli`` line builds the engine the harness builds;
- the engine, the plain reference and the program's native checker
  agree level by level where the arrival order of the bag used to
  change the answer (config #3's servers under ``Next`` at micro
  bounds, depth 16), and the engine answers as the reference does under
  ``NextDynamic`` at micro bounds;
- the control (a 32-bit dedup key) fails at the cell's own size;
- ``kernel_path_lane_share`` reads the engine's lane counters on
  hand-built totals and on a traced engine, and reads None where the
  checks disagree or the program has no such counters.
"""

import copy
import json
import os
import shlex
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "bench")
FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "configs")
sys.path.insert(0, BENCH)

import run  # noqa: E402
from harness import compare, manifest, reference  # noqa: E402
from harness.system import CheckRecord, System  # noqa: E402

NAME = "raft-tlc-s4-membership"
CELL = "tlc-s4-member-d16"
CACHE = os.path.join(ROOT, ".bench_cache", "reference")
MICRO = {"max_log_length": 1, "max_timeouts": 1, "max_client_requests": 1}
CELL_KEYS = {"max_depth", "engine", "assumed", "cli", "deployment",
             "control"}


@pytest.fixture(scope="module")
def ref_exe():
    return reference.build(CACHE)


def _load(d, ext="json"):
    with open(os.path.join(d, f"{NAME}.{ext}")) as fh:
        return json.load(fh) if ext == "json" else fh.read()


def _micro(next_family=None, depth=12):
    c = copy.deepcopy(_load(os.path.join(BENCH, "configs")))
    c["cfg"] = os.path.join(BENCH, "configs", c["cfg"])
    c["bound_flags"] = dict(MICRO)
    c["model"]["bounds"].update(MICRO, max_terms=2)
    c["max_depth"] = depth
    c["engine"] = {"chunk": 1024, "store_states": False}
    if next_family:
        c["model"]["next"] = next_family
    return c


def _program_cfg(c):
    from raft_tla_tpu.config import NEXT_FULL
    system = System(c, os.path.dirname(c["cfg"]))
    cfg = system.cfg
    if c["model"]["next"] == "Next":
        cfg = cfg.with_(next_family=NEXT_FULL)
        system = None
    return cfg, system


# -- the configuration ---------------------------------------------------

def test_cell_configuration_is_the_fixture_with_the_cells_keys():
    bench = _load(os.path.join(BENCH, "configs"))
    fixture = _load(FIXTURE)
    assert set(bench) - set(fixture) <= CELL_KEYS
    for k in set(bench) | set(fixture):
        if k not in CELL_KEYS:
            assert bench[k] == fixture[k], k
    assert bench["max_depth"] == 16
    assert bench["engine"] == {"chunk": 2048, "store_states": False,
                               "lcap": 688128, "vcap": 4194304,
                               "ocap": 16384, "fcap": 65536}
    for ext in ("cfg", "tla"):
        assert _load(os.path.join(BENCH, "configs"), ext) == \
            _load(FIXTURE, ext)
    man = manifest.load()
    cell = manifest.cell(CELL, man)
    assert cell.entry["chips"] == 1 and cell.conf["name"] == NAME
    assert {m["name"] for m in cell.end_to_end} == {
        "states_per_s", "peak_hbm_bytes", "setup_s"}
    assert [m["name"] for m in cell.per_layer] == ["kernel_path_lane_share"]


def test_cli_line_builds_the_cells_engine(monkeypatch):
    """``check --fcap`` reaches Engine.FCAP: the configuration's cli
    line builds the engine the harness builds (capacities and all)."""
    from raft_tla_tpu import cli
    from raft_tla_tpu.engine import bfs

    class Built(Exception):
        pass

    built = []

    class Recording(bfs.Engine):
        def check(self, *a, **kw):
            built.append(self)
            raise Built()

    monkeypatch.setattr(bfs, "Engine", Recording)
    conf = _load(os.path.join(BENCH, "configs"))
    argv = shlex.split(conf["cli"])
    assert argv[:4] == ["python", "-m", "raft_tla_tpu", "check"]
    monkeypatch.chdir(ROOT)
    with pytest.raises(Built):
        cli.main(argv[3:])
    eng = built[0]
    want = System(conf, os.path.join(BENCH, "configs")).engine
    assert eng.FCAP == 65536
    assert (eng.LCAP, eng.VCAP, eng.OCAP, eng.FCAP, eng.chunk,
            eng.store_states, repr(eng.cfg)) == (
        want.LCAP, want.VCAP, want.OCAP, want.FCAP, want.chunk,
        want.store_states, repr(want.cfg))


# -- the answers -----------------------------------------------------------

def test_engine_reference_and_native_agree_where_bag_order_mattered(
        ref_exe):
    """Config #3's servers under ``Next`` at micro bounds, depth 16: in
    arrival order the engine and the native checker kept 51,402 states
    in level 16, where the reference keeps 51,401."""
    from raft_tla_tpu import native
    from raft_tla_tpu.engine.bfs import Engine
    c = _micro("Next", depth=16)
    cfg, _ = _program_cfg(c)
    ref = reference.check(ref_exe, c["model"], 16)
    assert ref.level_sizes[-1] == 51401
    r = Engine(cfg, **c["engine"]).check(max_depth=16)
    nat = native.check(cfg, threads=2, max_depth=16)
    assert (r.distinct_states, r.generated_states, r.depth,
            list(r.level_sizes)) == (ref.distinct, ref.generated,
                                     ref.depth, ref.level_sizes)
    assert (nat.distinct_states, nat.generated_states, nat.depth,
            list(nat.level_sizes)) == (ref.distinct, ref.generated,
                                       ref.depth, ref.level_sizes)


def test_system_at_micro_bounds_answers_as_the_reference(ref_exe):
    c = _micro()
    _, system = _program_cfg(c)
    rec = system.check()
    ref = reference.check(ref_exe, c["model"], c["max_depth"])
    assert compare.ok(compare.compare([rec], ref))
    assert rec.generated > rec.distinct > 5000


def test_control_fails_at_the_cells_own_size(ref_exe):
    """The reference in the program's place with the configuration's
    narrower dedup key, at depth 16, against the exact reference."""
    c = _load(os.path.join(BENCH, "configs"))
    exact = reference.check(ref_exe, c["model"], c["max_depth"])
    assert (exact.distinct, exact.generated) == (739634, 2343529)
    assert exact.level_sizes[-1] == 305682
    ctl = reference.check(ref_exe, c["model"], c["max_depth"],
                          fp_bits=c["control"]["fp_bits"])
    out = compare.compare([ctl], exact)
    assert not compare.ok(out), out
    assert out["distinct_gap"]["value"] > 0


# -- the reader --------------------------------------------------------------

LANES = {"lanes_enabled.Receive": 30, "lanes_enabled.Timeout": 50,
         "lanes_enabled.Duplicate": 20, "lanes_kernel_path": 30}


def _ctx(samples):
    recs = [CheckRecord(distinct=1, generated=101, depth=1, level_sizes=[1])
            for _ in samples]
    spans = {}
    for nm in {k for s in samples for k in s}:
        vals = [s[nm] for s in samples if nm in s]
        spans[nm] = {"count": len(vals), "seconds": 0.0, "sum": sum(vals),
                     "min": min(vals), "max": max(vals)}
    return run.Context(records=recs, window_s=1.0, setup_s=0.0,
                       peak_bytes=0, peaks={}, spans=spans)


def _share(ctx):
    return manifest.load_module(
        manifest.metric_file("kernel_path_lane_share")).read(ctx)


def test_lane_share_reader_on_hand_built_totals():
    assert _share(_ctx([LANES, dict(LANES)])) == pytest.approx(30.0)
    # checks that disagree
    other = dict(LANES, **{"lanes_enabled.Timeout": 51})
    assert _share(_ctx([LANES, other])) is None
    # a check that left no sample
    assert _share(_ctx([LANES, {}])) is None
    # a program without the counters (the parent of this cell)
    assert _share(_ctx([{}, {}])) is None
    no_kernel = {k: v for k, v in LANES.items() if k != "lanes_kernel_path"}
    assert _share(_ctx([no_kernel])) is None


def test_lane_share_reader_on_a_traced_engine():
    c = _micro()
    system = System(c, os.path.dirname(c["cfg"]), spans=True)
    recs = [system.check(), system.check()]
    spans = system.span_totals()
    fams = {k: v for k, v in spans.items()
            if k.startswith("lanes_enabled.")}
    assert len(fams) == 14
    assert all(v["count"] == 2 and v["min"] == v["max"]
               for v in fams.values())
    assert sum(v["min"] for v in fams.values()) == recs[0].generated - 1
    delta = {"BecomeLeader", "ClientRequest", "Timeout", "Restart",
             "UpdateTerm", "Duplicate"}
    kernel = sum(v["min"] for k, v in fams.items()
                 if k.split(".", 1)[1] not in delta)
    assert spans["lanes_kernel_path"]["min"] == kernel
    ctx = run.Context(records=recs, window_s=1.0, setup_s=0.0,
                      peak_bytes=0, peaks={}, spans=spans)
    total = sum(v["min"] for v in fams.values())
    assert _share(ctx) == pytest.approx(100 * kernel / total)
