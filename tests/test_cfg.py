"""cfg front-end tests: parse the actual reference model files.

The reference tree (/root/reference) is not shipped in every container;
its parse tests skip when absent.  The CLI end-to-end tests run against
the repo-local twin (configs/tlc_membership — tests/test_sim.py pins
that it parses identically to the reference expectations), so they
exercise the CLI everywhere.
"""

import subprocess
import sys
import json
import os

import pytest

from raft_tla_tpu.cfg.parser import load_model, read_bounds_from_spec
from raft_tla_tpu.config import (NEXT_ASYNC_CRASH, NEXT_FULL)

TLC_CFG = "/root/reference/tlc_membership/raft.cfg"
APA_CFG = "/root/reference/apalache_no_membership/raft.cfg"
LOCAL_CFG = "configs/tlc_membership/raft.cfg"

needs_reference = pytest.mark.skipif(
    not os.path.exists(TLC_CFG),
    reason="reference spec tree not present in this container")


def test_config2_is_tlc_membership_with_election_safety_only():
    """configs/config2 (BASELINE config #2, what bench.py and
    chip_smoke.py check) is the tlc_membership twin with its invariant
    block cut to ElectionSafety: nothing else may drift between them."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    base = load_model(os.path.join(repo, LOCAL_CFG))
    cfg2 = load_model(os.path.join(repo, "configs", "config2", "raft.cfg"))
    assert cfg2.invariants == ("ElectionSafety",)
    assert cfg2 == base.with_(invariants=("ElectionSafety",))


@needs_reference
def test_parse_tlc_membership():
    cfg = load_model(TLC_CFG)
    assert cfg.n_servers == 3
    assert cfg.init_servers == (0, 1, 2)
    assert cfg.values == (1, 2)
    assert cfg.num_rounds == 1
    assert cfg.next_family == NEXT_ASYNC_CRASH
    assert cfg.symmetry is True
    assert not cfg.apalache_variant
    # the 12 enabled constraints and 8 enabled invariants (raft.cfg:37-87)
    assert len(cfg.constraints) == 12
    assert cfg.invariants == (
        "LeaderVotesQuorum", "CandidateTermNotInLog", "ElectionSafety",
        "LogMatching", "VotesGrantedInv", "QuorumLogInv",
        "MoreUpToDateCorrect", "LeaderCompleteness")
    # in-spec bounds lifted from raft.tla:22-30
    b = cfg.bounds
    assert (b.max_log_length, b.max_restarts, b.max_timeouts,
            b.max_client_requests, b.max_terms,
            b.max_membership_changes) == (5, 2, 3, 3, 4, 3)
    assert b.max_trace == 24
    assert cfg.max_inflight == 2 * 9  # 2 * S^2 (raft.tla:30)


@needs_reference
def test_parse_apalache_no_membership():
    cfg = load_model(APA_CFG)
    assert cfg.n_servers == 2
    assert cfg.init_servers == (0, 1)
    assert cfg.values == (1, 2, 3)
    assert cfg.next_family == NEXT_FULL
    assert cfg.symmetry is False
    assert cfg.apalache_variant
    assert "CleanFirstLeaderElection" in cfg.constraints
    b = cfg.bounds
    assert (b.max_log_length, b.max_restarts, b.max_timeouts) == (5, 2, 2)
    assert b.max_trace == 12
    assert cfg.max_inflight == 16  # (2*S)^2 (apalache raft.tla:22)


def run_cli(*argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "raft_tla_tpu"] + list(argv),
        capture_output=True, text=True, cwd="/root/repo", env=env,
        timeout=900)


def test_cli_check_micro():
    """End-to-end CLI on the tlc cfg with micro bounds, both
    engines must agree."""
    common = [LOCAL_CFG, "--servers", "2", "--max-timeouts", "1",
              "--max-log-length", "1", "--max-client-requests", "1",
              "--max-depth", "12"]
    outs = {}
    for engine in ("tpu", "oracle"):
        r = run_cli("check", *common, "--engine", engine)
        assert r.returncode == 0, r.stderr
        outs[engine] = json.loads(r.stdout.splitlines()[0])
    assert outs["tpu"]["distinct_states"] == \
        outs["oracle"]["distinct_states"]
    assert outs["tpu"]["depth"] == outs["oracle"]["depth"]
    assert outs["tpu"]["violations"] == outs["oracle"]["violations"] == 0


@pytest.mark.slow
def test_cli_trace_first_commit():
    r = run_cli("trace", LOCAL_CFG, "--servers", "2", "--max-timeouts", "1",
                "--max-log-length", "1", "--max-client-requests", "1",
                "--target", "FirstCommit")
    assert r.returncode == 0, r.stderr
    assert "witness for FirstCommit" in r.stdout
    assert "AdvanceCommitIndex" in r.stdout


# ---------------------------------------------------------------------------
# TLC .cfg front-end for paxos constants (ROADMAP 2a leftover):
# `--spec paxos model.cfg` parses CONSTANTS into PaxosConfig, with
# clear errors naming unsupported keys, round-tripping against the
# JSON constants path.
# ---------------------------------------------------------------------------

PAXOS_CFG_TEXT = """\
\\* small paxos model
CONSTANTS
  a1 = 1
  a2 = 2
  a3 = 3
  Acceptor = {a1, a2, a3}
  Ballot = {0, 1}
  Value = {0, 1}
  Instances = 2
SYMMETRY perms
INIT Init
NEXT Next
INVARIANTS
  Agreement
  Validity
"""


def test_paxos_cfg_roundtrips_with_json_path(tmp_path):
    from raft_tla_tpu.cfg.parser import (load_paxos_model,
                                         paxos_config_from_obj)
    p = tmp_path / "paxos.cfg"
    p.write_text(PAXOS_CFG_TEXT)
    cfg = load_paxos_model(str(p))
    assert (cfg.n_servers, cfg.n_ballots, cfg.n_values,
            cfg.n_instances) == (3, 2, 2, 2)
    assert cfg.symmetry is True
    assert cfg.invariants == ("Agreement", "Validity")
    # round-trip: the JSON constants path builds the identical config
    via_json = paxos_config_from_obj(
        {"acceptors": 3, "ballots": 2, "values": 2, "instances": 2,
         "symmetry": True, "invariants": ["Agreement", "Validity"]},
        where="json")
    assert cfg == via_json
    # no SYMMETRY line -> symmetry off (TLC semantics); no INVARIANT
    # lines -> the spec defaults
    p2 = tmp_path / "plain.cfg"
    p2.write_text("CONSTANTS\n  a1 = 1\n  Acceptor = {a1}\n"
                  "  Ballot = {0}\n  Value = {0}\n")
    cfg2 = load_paxos_model(str(p2))
    assert cfg2.symmetry is False and cfg2.n_servers == 1
    assert cfg2 == paxos_config_from_obj(
        {"acceptors": 1, "ballots": 1, "values": 1, "symmetry": False},
        where="json")


def test_paxos_cfg_clear_errors(tmp_path):
    from raft_tla_tpu.cfg.parser import CfgError, load_paxos_model

    def expect(text, pattern):
        p = tmp_path / "bad.cfg"
        p.write_text(text)
        with pytest.raises(CfgError, match=pattern):
            load_paxos_model(str(p))

    base = "CONSTANTS\n  a1 = 1\n  Acceptor = {a1}\n"
    # unsupported constant, by name
    expect(base + "  Frob = {a1}\n", "unsupported paxos CONSTANT 'Frob'")
    # Quorum is derived
    expect(base + "  Quorum = {a1}\n", "Quorum is not cfg-settable")
    # non-dense ballot set
    expect(base + "  Ballot = {1, 3}\n", "contiguous set 0..N-1")
    # unknown invariant names the spec (the shared JSON-path message)
    expect(base + "INVARIANT NotAThing\n",
           r"unknown invariant\(s\) 'NotAThing' for spec 'paxos'")
    # paxos declares no constraints
    expect(base + "CONSTRAINT Bounded\n", "declares no constraints")
    # unsupported NEXT family
    expect(base + "NEXT NextAsync\n", "unsupported NEXT")


def test_cli_check_paxos_cfg_matches_json(tmp_path):
    """`--spec paxos model.cfg` end-to-end: the .cfg and the JSON
    constants path land on identical counts."""
    cfg_p = tmp_path / "m.cfg"
    cfg_p.write_text("CONSTANTS\n  a1 = 1\n  a2 = 2\n"
                     "  Acceptor = {a1, a2}\n  Ballot = {0}\n"
                     "  Value = {0}\n")
    json_p = tmp_path / "m.json"
    json_p.write_text(json.dumps(
        {"acceptors": 2, "ballots": 1, "values": 1,
         "symmetry": False}))
    outs = {}
    for name, path in (("cfg", cfg_p), ("json", json_p)):
        r = run_cli("check", str(path), "--spec", "paxos",
                    "--engine", "oracle", "--max-depth", "4")
        assert r.returncode == 0, r.stderr
        outs[name] = json.loads(r.stdout.splitlines()[0])
    assert outs["cfg"]["distinct_states"] == \
        outs["json"]["distinct_states"]
    assert outs["cfg"]["depth"] == outs["json"]["depth"]
