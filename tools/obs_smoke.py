"""Ledger/heartbeat/timeline smoke validation (tools/ci_smoke.sh step).

Runs one tiny CLI check with the full observability surface on —
``--ledger --heartbeat --trace-timeline --stats-json`` — then
validates the artifacts against the contracts the obs layer promises:

- the JSONL ledger parses line-by-line, has >= 1 record per burst
  dispatch (every committing burst writes one; a first-level bail is
  immediately followed by a per-level record, so total records >=
  burst_dispatches), and its final record's burst counters equal the
  --stats-json ones (the registry is the single source — any split
  would be the levels_fused drift class);
- the Chrome-trace timeline satisfies the catapult trace_event schema
  Perfetto validates: every span event has ph/ts/dur/name, ph == "X",
  no negative timestamps or durations, and events on one (pid, tid)
  nest properly (no partial overlap — every inner span closed inside
  its enclosing span); a counter event (ph == "C", the check's
  program counters) has a name, a non-negative ts and integer args;
- the heartbeat's final depth equals the run's reported depth and its
  status is "finished".

Exits 0 on success, 1 with a message on any violation.  CPU-only and
reference-free (uses the repo-local configs/ twin), so it runs in
every container ci_smoke.sh runs in.
"""

import json
import os
import subprocess
import sys
import tempfile

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def fail(msg):
    print(f"obs_smoke: FAIL — {msg}", file=sys.stderr)
    sys.exit(1)


def load_trace_events(path):
    """Parse a (possibly unclosed — killed-run) trace-event array."""
    text = open(path).read().strip()
    if not text.startswith("["):
        fail(f"{path}: not a JSON array")
    if not text.endswith("]"):
        text = text.rstrip().rstrip(",") + "\n]"
    try:
        return json.loads(text)
    except ValueError as e:
        fail(f"{path}: trace JSON does not parse: {e}")


def validate_spans(events):
    """catapult trace_event schema + proper nesting."""
    counters = [ev for ev in events if ev.get("ph") == "C"]
    events = [ev for ev in events if ev.get("ph") != "C"]
    for ev in counters:
        if "name" not in ev or not ev.get("ts", -1) >= 0 or not all(
                isinstance(v, int) for v in ev.get("args", {}).values()):
            fail(f"malformed counter event: {ev}")
    if not events:
        fail("timeline has no span events")
    for ev in events:
        for key in ("ph", "ts", "dur", "name"):
            if key not in ev:
                fail(f"trace event missing {key!r}: {ev}")
        if ev["ph"] != "X":
            fail(f"unexpected phase {ev['ph']!r} (complete events "
                 f"only): {ev}")
        if ev["ts"] < 0 or ev["dur"] < 0:
            fail(f"negative ts/dur (non-monotonic clock?): {ev}")
    # nesting: on each (pid, tid) track, sorted by start (ties: longer
    # first — the enclosing span), every span must close before the
    # enclosing one does; a partial overlap means an unmatched
    # begin/end pair
    by_track = {}
    for ev in events:
        by_track.setdefault((ev.get("pid"), ev.get("tid")),
                            []).append(ev)
    eps = 1.0   # us — perf_counter rounding slack
    for track, evs in by_track.items():
        evs.sort(key=lambda e: (e["ts"], -e["dur"]))
        stack = []
        for ev in evs:
            end = ev["ts"] + ev["dur"]
            while stack and ev["ts"] >= stack[-1] - eps:
                stack.pop()
            if stack and end > stack[-1] + eps:
                fail(f"span {ev['name']!r} [{ev['ts']}, {end}] "
                     f"overlaps its enclosing span's end "
                     f"{stack[-1]} on track {track} — unmatched "
                     f"start/end")
            stack.append(end)


def main():
    td = tempfile.mkdtemp(prefix="obs_smoke_")
    ledger = os.path.join(td, "run.jsonl")
    hb = os.path.join(td, "hb.json")
    tl = os.path.join(td, "timeline.json")
    stats = os.path.join(td, "stats.json")
    cmd = [
        sys.executable, "-m", "raft_tla_tpu", "check",
        os.path.join(_REPO, "configs", "tlc_membership", "raft.cfg"),
        "--servers", "2", "--init-servers", "2",
        "--max-log-length", "1", "--max-timeouts", "1",
        "--max-client-requests", "1", "--max-depth", "6",
        "--ledger", ledger, "--heartbeat", hb,
        "--trace-timeline", tl, "--stats-json", stats,
    ]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(cmd, env=env, cwd=_REPO,
                          capture_output=True, text=True)
    if proc.returncode != 0:
        fail(f"check run failed rc={proc.returncode}:\n{proc.stderr}")

    st = json.load(open(stats))

    # -- ledger ---------------------------------------------------------
    recs = []
    for i, line in enumerate(open(ledger)):
        try:
            recs.append(json.loads(line))
        except ValueError as e:
            fail(f"ledger line {i + 1} does not parse: {e}")
    if not recs:
        fail("ledger is empty")
    if len(recs) < st["burst_dispatches"]:
        fail(f"{len(recs)} ledger records < {st['burst_dispatches']} "
             "burst dispatches — a dispatch wrote no record")
    last = recs[-1]
    for key in ("levels_fused", "burst_dispatches", "burst_bailouts",
                "distinct_states", "generated_states"):
        if last.get(key) != st[key]:
            fail(f"ledger final record {key}={last.get(key)} != "
                 f"--stats-json {key}={st[key]} — the registry split")
    for key in ("kind", "depth", "frontier", "rss_bytes", "ts"):
        if key not in last:
            fail(f"ledger record missing {key!r}: {last}")

    # -- timeline -------------------------------------------------------
    validate_spans(load_trace_events(tl))

    # -- heartbeat ------------------------------------------------------
    hb_obj = json.load(open(hb))
    if hb_obj.get("depth") != st["depth"]:
        fail(f"heartbeat depth {hb_obj.get('depth')} != run depth "
             f"{st['depth']}")
    if hb_obj.get("status") != "finished":
        fail(f"heartbeat status {hb_obj.get('status')!r} != "
             "'finished'")
    if hb_obj.get("states_enqueued") != st["distinct_states"]:
        fail(f"heartbeat states {hb_obj.get('states_enqueued')} != "
             f"{st['distinct_states']}")

    print(f"obs_smoke: ok — {len(recs)} ledger records, depth "
          f"{st['depth']}, {st['distinct_states']} states, "
          f"heartbeat+timeline consistent ({td})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
