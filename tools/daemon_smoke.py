"""CI daemon smoke: the persistent ``cli serve`` loop end-to-end —
spool intake, graceful drain, kill-mid-wave restart (ISSUE 18).

Three daemon invocations over the real CLI (subprocesses, CPU-only),
pinned against a clean ``cli batch`` reference of the same jobs:

1. SERVICE — a daemon watches a spool; a deep raft job and a paxos
   job arrive via the client protocol (write-then-rename, trailing
   newline); both results land in results/ with done/ markers,
   bit-exact vs the batch reference; the ledger holds the
   ``kind="intake"`` claim rows and a ``kind="daemon"`` cycle row.
   SIGTERM then drains it: exit 0, final heartbeat ``status="done"``,
   one registry record ``cmd="serve"`` listed by ``obs ls``.
2. KILL — a fresh spool, ``--chaos wave_kill:at=1``: the
   deterministic SIGKILL stand-in fires at the first wave boundary,
   AFTER the job's wave state persisted.  The cycle fails, retries
   are exhausted (0), the daemon exits 3 — and the claimed file plus
   the ``.wave.npz`` carry survive on disk, exactly the crash
   contract a real ``kill -9`` leaves behind.
3. RESTART — a new daemon on the same spool re-claims the leftover
   (``recover``), the scheduler resumes the straggler MID-BFS from
   its wave state (``kind="wave_resume"`` ledger row), the result is
   bit-exact vs the reference, and — executable cache warm from run
   1 — the span timeline holds ZERO ``bucket_compile`` events.  On a
   backend whose runtime cannot serialize executables the
   zero-compile assertion SKIPS with a named reason (the honest-miss
   contract) — never a crash.
"""

import json
import os
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PAXOS_CFG = """\\* tiny paxos model (daemon smoke)
CONSTANTS
  a1 = 1
  a2 = 2
  Acceptor = {a1, a2}
  Ballot = {0}
  Value = {0}
INIT Init
NEXT Next
INVARIANT Agreement
"""

DEEP_RAFT = {
    "spec": "raft",
    "config": os.path.join(REPO, "configs", "tlc_membership",
                           "raft.cfg"),
    "label": "deep", "max_depth": 14,
    "overrides": {"servers": 2, "next": "NextAsync",
                  "bounds": {"max_log_length": 1, "max_timeouts": 1,
                             "max_client_requests": 1}},
}

COMPARE_KEYS = ("distinct_states", "generated_states", "depth",
                "level_sizes", "violations")


def submit(spool, name, obj):
    """The client protocol: write the complete JSON (trailing
    newline) to a dot-tmp name, then rename into incoming/.  Clients
    may create incoming/ themselves — the daemon's intake does the
    same idempotently, so whoever arrives first wins."""
    os.makedirs(os.path.join(spool, "incoming"), exist_ok=True)
    tmp = os.path.join(spool, "incoming", f".{name}.tmp")
    with open(tmp, "w") as fh:
        fh.write(json.dumps(obj) + "\n")
    os.rename(tmp, os.path.join(spool, "incoming", name + ".json"))


def start_daemon(spool, tmp, exec_dir, extra=()):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "-m", "raft_tla_tpu", "serve",
           "--spool", spool, "--poll", "0.1",
           "--executable-cache", exec_dir,
           "--ledger", os.path.join(tmp, "ledger.jsonl"),
           "--heartbeat", os.path.join(tmp, "hb.json"),
           "--registry", os.path.join(tmp, "reg"), *extra]
    # the daemon writes to files, not pipes: nothing here drains a
    # pipe while the daemon runs, and XLA:CPU's log lines (one per
    # executable the compile cache loads) fill one and block it
    logs = []
    for suffix in (".out", ".err"):
        fd, path = tempfile.mkstemp(prefix="daemon.", suffix=suffix,
                                    dir=tmp)
        logs.append((fd, path))
    proc = subprocess.Popen(cmd, cwd=REPO, env=env, stdout=logs[0][0],
                            stderr=logs[1][0])
    for fd, _path in logs:
        os.close(fd)              # the child holds its own copies
    proc.log_paths = [path for _fd, path in logs]
    return proc


def wait_for(pred, what, timeout_s=420):
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        if pred():
            return
        time.sleep(0.2)
    raise AssertionError(f"timed out waiting for {what}")


def wait_exit(proc, what, timeout_s=420):
    try:
        proc.wait(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        proc.kill()
        raise AssertionError(f"daemon did not exit: {what}")
    logs = []
    for path in proc.log_paths:
        with open(path) as fh:
            logs.append(fh.read())
    return (proc.returncode, *logs)


def ledger_records(tmp):
    recs = []
    with open(os.path.join(tmp, "ledger.jsonl")) as fh:
        for line in fh:
            recs.append(json.loads(line))
    return recs


def read_result(spool, name):
    with open(os.path.join(spool, "results", name + ".json")) as fh:
        return json.load(fh)


def obs_ls(tmp, *filters):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "raft_tla_tpu", "obs", "ls",
         "--registry", os.path.join(tmp, "reg"), *filters],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=120)
    assert p.returncode == 0, (p.returncode, p.stdout, p.stderr)
    return p.stdout.splitlines()[1:]          # drop the header


def main():
    top = tempfile.mkdtemp(prefix="daemon_smoke_")
    pax_cfg = os.path.join(top, "paxos.cfg")
    with open(pax_cfg, "w") as fh:
        fh.write(PAXOS_CFG)
    pax_job = {"spec": "paxos", "config": pax_cfg, "max_depth": 3,
               "label": "pax"}
    exec_dir = os.path.join(top, "exec")

    # 0. clean `cli batch` reference — the ground truth both the
    # service path and the restart path must match bit-for-bit
    ref_tmp = os.path.join(top, "ref")
    os.makedirs(ref_tmp)
    jobs_path = os.path.join(ref_tmp, "jobs.jsonl")
    with open(jobs_path, "w") as fh:
        fh.write(json.dumps(DEEP_RAFT) + "\n")
        fh.write(json.dumps(pax_job) + "\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "raft_tla_tpu", "batch",
         "--jobs", jobs_path,
         "--cache-dir", os.path.join(ref_tmp, "cache")],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=600)
    assert p.returncode == 0, (p.returncode, p.stdout, p.stderr)
    ref = {r["label"]: r for r in
           (json.loads(ln) for ln in p.stdout.splitlines() if ln)
           if r.get("kind") != "batch_summary"}
    assert set(ref) == {"deep", "pax"}, sorted(ref)

    # 1. SERVICE — daemon up, two tenants submit, results land,
    # SIGTERM drains it
    t1 = os.path.join(top, "t1")
    spool1 = os.path.join(t1, "spool")
    os.makedirs(spool1)
    d1 = start_daemon(spool1, t1, exec_dir)
    try:
        submit(spool1, "deep", DEEP_RAFT)
        submit(spool1, "pax", pax_job)
        done = os.path.join(spool1, "done")
        wait_for(lambda: os.path.exists(os.path.join(done,
                                                     "deep.json"))
                 and os.path.exists(os.path.join(done, "pax.json")),
                 "done/ markers for both submissions")
    finally:
        d1.send_signal(signal.SIGTERM)
    rc, out, err = wait_exit(d1, "SIGTERM drain")
    assert rc == 0, (rc, out, err)
    for name in ("deep", "pax"):
        got = read_result(spool1, name)
        for key in COMPARE_KEYS:
            assert got[key] == ref[name][key], \
                (name, key, got[key], ref[name][key])
    recs = ledger_records(t1)
    claimed = [r for r in recs if r.get("kind") == "intake"
               and r.get("action") == "claimed"]
    assert {r["name"] for r in claimed} == {"deep", "pax"}, claimed
    assert any(r.get("kind") == "daemon" for r in recs), \
        sorted({r.get("kind") for r in recs})
    with open(os.path.join(t1, "hb.json")) as fh:
        hb = json.load(fh)
    assert hb.get("status") == "done", hb.get("status")
    assert hb.get("daemon", {}).get("jobs_done") == 2, hb.get("daemon")
    rows = obs_ls(t1, "--cmd", "serve", "--status", "done")
    assert len(rows) == 1 and " serve " in rows[0], rows
    print("daemon_smoke: OK (2 tenants served bit-exact; SIGTERM "
          "drain: exit 0, heartbeat done, registry cmd=serve)")

    # 2. KILL — chaos fires mid-wave AFTER the wave-state persist;
    # the daemon exits 3 leaving the claimed file + carry on disk
    t2 = os.path.join(top, "t2")
    spool2 = os.path.join(t2, "spool")
    os.makedirs(spool2)
    d2 = start_daemon(spool2, t2, exec_dir,
                      extra=("--chaos", "wave_kill:at=1"))
    submit(spool2, "deep", DEEP_RAFT)
    rc, out, err = wait_exit(d2, "chaos kill")
    assert rc == 3, (rc, out, err)
    waves = os.listdir(os.path.join(spool2, "waves"))
    assert any(nm.endswith(".wave.npz") for nm in waves), \
        f"no wave state persisted before the kill: {waves}"
    assert os.path.exists(os.path.join(spool2, "claimed",
                                       "deep.json")), \
        "claimed file must survive the crash"
    assert not os.listdir(os.path.join(spool2, "done"))
    rows = obs_ls(t2, "--cmd", "serve", "--status", "failed")
    assert len(rows) == 1, rows

    # 3. RESTART — recover the leftover claim, resume mid-BFS from
    # the wave state, finish bit-exact; exec cache warm from run 1
    tl = os.path.join(t2, "tl.json")
    d3 = start_daemon(spool2, t2, exec_dir,
                      extra=("--max-idle-polls", "20",
                             "--trace-timeline", tl))
    rc, out, err = wait_exit(d3, "restart drain")
    assert rc == 0, (rc, out, err)
    got = read_result(spool2, "deep")
    for key in COMPARE_KEYS:
        assert got[key] == ref["deep"][key], \
            (key, got[key], ref["deep"][key])
    recs = ledger_records(t2)
    assert any(r.get("kind") == "intake"
               and r.get("action") == "recovered" for r in recs), \
        sorted({(r.get("kind"), r.get("action")) for r in recs})
    assert any(r.get("kind") == "wave_resume" for r in recs), \
        sorted({r.get("kind") for r in recs})
    stored = [nm for nm in os.listdir(exec_dir)
              if nm.endswith(".exec")] if os.path.isdir(exec_dir) \
        else []
    if not stored:
        print("daemon_smoke: OK (killed mid-wave, restart resumed "
              "bit-exact); SKIPPING zero-compile check — backend "
              "cannot serialize executables (empty exec cache)")
        return
    with open(tl) as fh:
        ncomp = fh.read().count('"name": "bucket_compile"')
    assert ncomp == 0, \
        f"warm restart must compile NOTHING, saw {ncomp} spans"
    print("daemon_smoke: OK (killed mid-wave, restart resumed "
          "bit-exact, 0 bucket compiles on the warm path)")


if __name__ == "__main__":
    main()
