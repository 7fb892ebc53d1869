"""Tail a run's heartbeat (and optionally its ledger) and render live
progress — the watchdog half of the obs layer.

A long remote-TPU run used to be a black box: rounds 4-5 lost
multi-hour runs to dropped connections that looked exactly like big
levels.  The engines now rewrite ``--heartbeat FILE`` atomically every
dispatch; this tool reads it (plus the last ``--ledger`` records for
throughput) and prints one status line per interval:

  depth 17  1,642,844 states  5,120/s  last dispatch 4s ago  pid 3406 alive

A heartbeat older than ``--stale`` seconds (default 300 — a slow level
can legitimately take minutes) or a dead pid
flags the run STALLED/DEAD.  Stall detection is also CADENCE-AWARE
(ISSUE 17): once a run has beaten enough times to establish its own
rhythm (>= 5 beats), a heartbeat older than ``--cadence-factor`` times
the observed inter-beat cadence flags ``STALLED?`` even before the
absolute ``--stale`` bound — a lost TPU connection on a fast-beating
run no longer looks identical to one long level.  A supervised run
(``--retries``) in its backoff window renders RETRYING with the
attempt counters instead — alive, not stalled — and a parked batch
job shows status ``parked``.

Multi-job mode: a batch heartbeat (``cli batch`` — the serving layer)
carries a per-job status map; one extra line renders per job:

  job raft-micro: depth 4  29 states  done
  job paxos-micro: depth 3  44 states  running

A batch heartbeat also carries the SLO snapshot (round 13): queue
depth, per-job wait/service-seconds histograms and the executable-
cache counters render as dashboard lines after the job map:

  queue: 3 waiting, 5 done
  wait:    <=0.25s:4 <=1s:1
  service: <=1s:3 <=5s:2
  exec-cache: 2 hits, 1 misses, 1 stored

Daemon mode (``cli serve`` — ISSUE 18): a daemon heartbeat carries a
``daemon`` block (cycle counter, spool queue depths, cumulative
done/rejected, per-tenant rollups); the daemon view renders after the
job/SLO lines:

  daemon serving  cycle 3  incoming 2 claimed 4 done 11 rejected 1
  served 11 jobs (3 cache hits, 0 violations), 1 recovered
  tenant raft: 7 done, 2 cache hits
  tenant paxos: 4 done, 1 cache hit

Two daemon-specific rules: a terminal ``status="done"`` heartbeat (a
graceful drain) renders FINISHED exactly like a batch run's
``finished`` — never a stall — and CADENCE-based stall detection is
skipped while the daemon block says idle|serving|draining, because an
idle daemon legitimately beats at its ``--poll`` rhythm however fast
its serving cadence once was (the absolute ``--stale`` bound still
applies; a dead pid still flags DEAD).

Usage:
  python tools/watch.py HEARTBEAT [--ledger FILE] [--interval SEC]
                        [--stale SEC] [--cadence-factor N] [--once]

``--once`` prints a single line and exits 0 (healthy), 1 (stalled or
dead), 2 (no heartbeat yet) — the shape a cron watchdog wants.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from raft_tla_tpu.obs.heartbeat import read_heartbeat  # noqa: E402


def pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except PermissionError:
        return True
    return True


# the per-dispatch record kinds a throughput estimate may difference
# (meta/resource/retry/job/... rows carry no cumulative state counts)
_DISPATCH_KINDS = ("level", "burst", "sim", "batch")


def last_ledger_records(path, n=2):
    """The last n parseable DISPATCH records of a JSONL ledger (the
    final line can be mid-write — skip anything that does not parse).

    Interleaved/resumed runs demultiplex by the run-id + seq keys
    (ISSUE 17): only records of the newest run id are considered, in
    seq order, so a ledger a resumed run appended to never yields a
    rate computed across two different runs.  Pre-ISSUE-17 rows carry
    neither key and still parse (one unkeyed stream)."""
    recs = []
    try:
        with open(path) as fh:
            for line in fh:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if rec.get("kind") in _DISPATCH_KINDS:
                    recs.append(rec)
    except OSError:
        return []
    if not recs:
        return []
    live = recs[-1].get("run_id")
    recs = [r for r in recs if r.get("run_id") == live]
    recs.sort(key=lambda r: r.get("seq", 0))
    return recs[-n:]


def job_lines(hb):
    """One rendered status line per job of a batch heartbeat (the
    serving layer's per-job map); [] for single-run heartbeats."""
    out = []
    for name, j in (hb.get("jobs") or {}).items():
        out.append(f"  job {name}: depth {int(j.get('depth', 0))}  "
                   f"{int(j.get('distinct', 0)):,} states  "
                   f"{j.get('status', '?')}")
    return out


def wave_lines(hb):
    """The batched wave's occupancy line (rounds 16-17 mesh waves):
    the device grid, how many lanes hold real jobs, and the idle-lane
    waste as ``pad N/M``; [] when the heartbeat carries no wave block
    (solo runs, cache-only batches).  Renders in the batch AND the
    daemon views — the block rides every batched dispatch beat either
    way.  Under a 2-D (jobs, state) mesh the grid and the state-shard
    count render explicitly:

      wave: 4 devices x 2 lanes/device  6 jobs  pad 2/8
      wave: 2x2 grid  6 jobs  pad 2/8  state shards 2
    """
    w = hb.get("wave")
    if not w:
        return []
    dev = int(w.get("devices", 1))
    lanes = int(w.get("lanes", 0))
    ss = int(w.get("state_shards", 1))
    filled = int(w.get("filled", 0))
    pad = int(w.get("pad", 0))
    if ss > 1:
        return [f"  wave: {dev // ss}x{ss} grid  {filled} jobs  "
                f"pad {pad}/{lanes}  state shards {ss}"]
    return [f"  wave: {dev} device{'s' if dev != 1 else ''} x "
            f"{int(w.get('jobs_per_device', lanes))} lanes/device  "
            f"{filled} jobs  pad {pad}/{lanes}"]


def _hist_summary(hist):
    """'<=0.25s:3 <=1s:2 >120s:1' — only the occupied buckets, in
    edge order (the heartbeat keeps the full fixed-bucket histogram;
    'inf' is the catch-all above the largest edge)."""
    out = []
    last_edge = "?"
    for k, v in (hist or {}).items():
        if k.startswith("le_"):
            last_edge = k[3:]
            if v:
                out.append(f"<={last_edge}s:{v}")
        elif k == "inf" and v:
            out.append(f">{last_edge}s:{v}")
    return " ".join(out)


def slo_lines(hb):
    """The serving layer's SLO snapshot (queue depth, wait/service
    histograms, exec-cache counters) as rendered dashboard lines; []
    when the heartbeat carries none."""
    slo = hb.get("slo")
    if not slo:
        return []
    out = [f"  queue: {int(slo.get('queue_depth', 0))} waiting, "
           f"{int(slo.get('jobs_done', 0))} done"]
    w = _hist_summary(slo.get("wait_hist"))
    s = _hist_summary(slo.get("service_hist"))
    if w:
        out.append(f"  wait:    {w}")
    if s:
        out.append(f"  service: {s}")
    ec = slo.get("exec_cache")
    if ec:
        out.append(
            f"  exec-cache: {int(ec.get('exec_cache_hits', 0))} hits, "
            f"{int(ec.get('exec_cache_misses', 0))} misses, "
            f"{int(ec.get('exec_cache_stores', 0))} stored"
            + (f", {int(ec['exec_cache_store_failures'])} store "
               f"failures (backend cannot serialize?)"
               if ec.get("exec_cache_store_failures") else ""))
    return out


def daemon_lines(hb):
    """The daemon view (``cli serve`` heartbeats): queue depths,
    cumulative serve counters, per-tenant rollups and the drain
    reason; [] for non-daemon heartbeats."""
    d = hb.get("daemon")
    if not d:
        return []
    out = [f"  daemon {d.get('status', '?')}  "
           f"cycle {int(d.get('cycles', 0))}  "
           f"incoming {int(d.get('incoming', 0))} "
           f"claimed {int(d.get('claimed', 0))} "
           f"done {int(d.get('done', 0))} "
           f"rejected {int(d.get('rejected', 0))}"]
    served = (f"  served {int(d.get('jobs_done', 0))} jobs "
              f"({int(d.get('cache_hits', 0))} cache hits, "
              f"{int(d.get('violations', 0))} violations)")
    if d.get("jobs_recovered"):
        served += f", {int(d['jobs_recovered'])} recovered"
    out.append(served)
    for name, t in (d.get("tenants") or {}).items():
        out.append(f"  tenant {name}: {int(t.get('jobs_done', 0))} "
                   f"done, {int(t.get('cache_hits', 0))} cache hits"
                   + (f", {int(t['violations'])} violations"
                      if t.get("violations") else ""))
    if d.get("drain_reason"):
        out.append(f"  draining: {d['drain_reason']}")
    return out


# a run must beat this many times before its own cadence is trusted
# for stall detection (too few samples and one slow early level —
# compile included — would poison the estimate)
MIN_CADENCE_BEATS = 5
# never flag on cadence alone under this age: sub-second-cadence
# micro runs would flap on ordinary scheduler hiccups
CADENCE_FLOOR_S = 30.0


def observed_cadence(hb):
    """Mean inter-beat seconds of this heartbeat's own history, or
    None before MIN_CADENCE_BEATS (the heartbeat carries started_ts /
    last_dispatch_ts / beats, so the cadence needs no extra state)."""
    beats = int(hb.get("beats", 0))
    if beats < MIN_CADENCE_BEATS:
        return None
    span = hb["last_dispatch_ts"] - hb.get("started_ts",
                                           hb["last_dispatch_ts"])
    if span <= 0:
        return None
    return span / (beats - 1)


def status_line(hb_path, ledger_path, stale_s, cadence_factor=8.0):
    """(line, exit_code): 0 healthy, 1 stalled/dead, 2 unreadable.
    Batch heartbeats append one line per job (job_lines)."""
    try:
        hb = read_heartbeat(hb_path)
    except (OSError, ValueError) as e:
        return f"no heartbeat yet ({e})", 2
    age = time.time() - hb["last_dispatch_ts"]
    alive = pid_alive(int(hb["pid"]))
    # "finished" is a run's terminal beat; "done" is a daemon's
    # graceful drain — both terminal, both render FINISHED so the
    # watch loop exits 0 instead of flagging a stall on a process
    # that exited exactly as asked
    finished = hb.get("status") in ("finished", "done")
    backoff = hb.get("status") == "backoff"
    # a live daemon (idle|serving|draining) beats at its --poll
    # rhythm while idle: its historical serving cadence says nothing
    # about the gaps between idle beats, so cadence-based stall
    # detection is meaningless — the absolute --stale bound and the
    # pid check still guard a daemon that truly wedged
    daemonish = hb.get("daemon") is not None and \
        hb.get("status") in ("idle", "serving", "draining")
    parts = [f"depth {hb['depth']}",
             f"{hb['states_enqueued']:,} states"]
    rate = None
    if ledger_path:
        recs = last_ledger_records(ledger_path)
        if len(recs) == 2:
            ds = (recs[1].get("distinct_states",
                              recs[1].get("walker_steps", 0)) -
                  recs[0].get("distinct_states",
                              recs[0].get("walker_steps", 0)))
            dt = recs[1].get("seconds", 0) - recs[0].get("seconds", 0)
            if dt > 0:
                rate = ds / dt
        elif len(recs) == 1:
            rate = recs[0].get("states_per_sec")
    if rate is not None:
        parts.append(f"{rate:,.0f}/s")
    parts.append(f"last dispatch {age:.0f}s ago")
    cadence = observed_cadence(hb)
    cadence_limit = None
    if cadence is not None and cadence_factor and not daemonish:
        cadence_limit = max(cadence * cadence_factor, CADENCE_FLOOR_S)
    code = 0
    if finished:
        parts.append("FINISHED")
    elif backoff and alive:
        # supervised retry (resil/supervisor): the run hit a transient
        # failure and is waiting out its backoff — alive and healthy,
        # not stalled, however old the last dispatch is.  A DEAD pid
        # still wins below: a run killed during its backoff window
        # must flag DEAD, not an eternal RETRYING.
        r = hb.get("retry") or {}
        parts.append(
            f"RETRYING attempt {r.get('attempt', '?')}/"
            f"{r.get('max_attempts', '?')}, backoff "
            f"{r.get('wait_s', '?')}s")
    elif not alive:
        parts.append(f"pid {hb['pid']} DEAD")
        code = 1
    elif age > stale_s:
        parts.append(f"pid {hb['pid']} alive but STALLED? "
                     f"(> {stale_s:.0f}s since last dispatch)")
        code = 1
    elif cadence_limit is not None and age > cadence_limit:
        # the run's own rhythm says this gap is abnormal even though
        # the absolute --stale bound has not yet tripped: a dropped
        # connection on a fast-beating run surfaces in minutes, not hours
        parts.append(
            f"pid {hb['pid']} alive but STALLED? ({age:.0f}s "
            f"> {cadence_factor:.0f}x observed cadence "
            f"{cadence:.1f}s/beat over {hb.get('beats', 0)} beats)")
        code = 1
    else:
        parts.append(f"pid {hb['pid']} alive")
    line = "  ".join(parts)
    jl = (job_lines(hb) + wave_lines(hb) + slo_lines(hb) +
          daemon_lines(hb))
    if jl:
        line = "\n".join([line] + jl)
    return line, code


def main(argv=None):
    args = list(sys.argv[1:] if argv is None else argv)
    if not args or args[0] in ("-h", "--help"):
        print(__doc__)
        return 0 if args else 2
    hb_path = args.pop(0)
    once = "--once" in args
    if once:
        args.remove("--once")
    opts = dict(zip(args[::2], args[1::2]))
    bad = set(opts) - {"--ledger", "--interval", "--stale",
                       "--cadence-factor"}
    if bad or len(args) % 2:
        raise SystemExit(f"unknown/incomplete options: "
                         f"{sorted(bad) or args[-1:]}")
    ledger = opts.get("--ledger")
    interval = float(opts.get("--interval", 5))
    stale = float(opts.get("--stale", 300))
    factor = float(opts.get("--cadence-factor", 8))
    if once:
        line, code = status_line(hb_path, ledger, stale, factor)
        print(line)
        return code
    while True:
        line, code = status_line(hb_path, ledger, stale, factor)
        print(time.strftime("%H:%M:%S") + "  " + line, flush=True)
        if "FINISHED" in line:
            return 0
        time.sleep(interval)


if __name__ == "__main__":
    sys.exit(main())
