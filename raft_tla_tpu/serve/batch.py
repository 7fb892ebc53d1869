"""Multi-tenant batched checking: many (spec, config) jobs, ONE device
program per bucket (ROADMAP 2b — the serving half of the north star).

Every solo ``check`` pays its own compile (~6 s per engine instance on
XLA:CPU; on the TPU not measured on the current code) and its own
dispatch chain, so N small jobs cost N× everything.  This layer amortizes both across
tenants, the same move PR 5 made across levels:

- **Buckets** — jobs group by their spec's ``serve_bucket`` hook:
  (spec, ceiling config, bucket params).  One ``BucketEngine`` per
  bucket compiles ONE job-vmapped burst program
  (``engine/bfs.Engine.burst_batched_fn``) and serves every job in the
  bucket through it, in waves of up to ``_MAX_WAVE`` jobs per device
  padded to a power of two (so the wave-size compile cache stays
  tiny).
- **Mesh waves** (rounds 16-17) — with more than one local device (a
  TPU slice, or CPU via ``--xla_force_host_platform_device_count``),
  the wave shards across a two-axis ``jax.make_mesh(("jobs",
  "state"))`` (``--wave-mesh JxS``): per-job scalars/cursors stay on
  ``P("jobs")`` while the big per-job arrays — visited-table slots,
  frontier rings, level buffers, archive staging — also shard
  ``P("jobs", "state")``, so ONE huge tenant's dedup state spans the
  pod inside a batched wave (the round-14 pjit substrate under the
  bucket program; the probe/claim scatter lowers to state-axis
  GSPMD collectives only — jobs stay collective-free).  ``S=1``
  degenerates to the round-16 job-axis mesh with a single
  pytree-prefix sharding; ``auto`` promotes spare devices to state
  shards when a bucket's ceiling VCAP exceeds the per-device budget.
  Waves pad to a J-axis multiple and the ceiling scales to J x 8
  lanes.  The per-job harvest, park/resume slices and wave-state
  files stay host-side numpy, so the same ``.wave.npz`` restores
  under ANY mesh shape, 2-D included (the portable restart matrix).
- **Job axis** — per-job frontier rings, visited tables, global-id
  cursors, depth gates and invariant verdicts all ride a leading
  ``[J, ...]`` axis.  JAX batches the burst's while_loops as
  run-until-all-jobs-done with per-job select masking: finished jobs
  freeze (their lanes contribute no work to the result) while
  stragglers keep stepping.  Each job's trajectory is bit-identical to
  a solo run — pinned by tests/test_serve.py on counts, level sizes,
  violation states and witness traces.
- **Fallback** — a job the batched path cannot hold (root set or a
  frontier outgrowing the per-job ring, a table overflow, seeded /
  prefix-pinned configs) is re-run solo from scratch on an ordinary
  ``Engine``; its batched partial progress is discarded, so fallback
  results are trivially exact.  Fallbacks are counted and labeled
  honestly in the report and the ledger.
- **Result cache** — (spec, IR, config, options)-fingerprint keyed
  (serve/cache): a repeat job is answered with zero device dispatches.
- **Observability** — spans attribute wall-clock to ``bucket_compile``
  vs ``batched_dispatch`` vs ``job_harvest`` (vs ``sequential_job``
  for fallbacks); the ledger gets one ``kind="batch"`` record per
  batched device call and one ``kind="job"`` record per finished job;
  the heartbeat carries a per-job status map ``tools/watch.py``
  renders one line per job.
"""

from __future__ import annotations

import os
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..obs.metrics import check_stats
from ..resil.chaos import chaos_point
from ..spec import C_OVERFLOW, spec_of
from ..utils import take_arrays as _take
from .jobs import Job
from .wavestate import WaveStateStore

U32MAX_NP = np.uint32(0xFFFFFFFF)

# jobs per batched device program; a bucket with more runs extra waves
_MAX_WAVE = 8

# "auto" state-split budget (round 17): bytes of ONE job's dedup state
# (W visited-table words + the claims word, u32 each) a single device
# is allowed to hold before auto promotes spare job-axis devices into
# state shards (S > 1).  Sized for a ~16 GB HBM part with headroom for
# rings/levels/archives; override for tests and small-HBM parts.
_AUTO_STATE_BUDGET = int(os.environ.get(
    "RAFT_TPU_WAVE_STATE_BUDGET", str(256 << 20)))

# rule-matched partition specs for the batched wave carry/outputs under
# the 2-D ("jobs", "state") mesh (parallel/pjit_mesh's exemplar rules,
# serve-side tables).  Per-job cursors and runtime thresholds stay
# P("jobs") — collective-free; the per-job BIG arrays also shard the
# "state" axis: visited-table slots + claims on dim 1 (the probe/claim
# scatter lowers to state-axis GSPMD collectives), frontier rings /
# depth gates / level buffers / archive staging on their batch-last
# ring axis.
WAVE_CARRY_RULES = [
    (r"^vis\|", "jobs_slots"),
    (r"^claims$", "jobs_slots"),
    (r"^(fr\||fm$|gd$)", "jobs_rows"),
    (r".*", "jobs"),
]
WAVE_OUT_RULES = [
    (r"^(par$|lane$|inv$|st\|)", "jobs_rows"),
    (r".*", "jobs"),
]

# the serve_bucket contract's fallback when a spec declares no hook
DEFAULT_BUCKET_PARAMS = dict(chunk=128, vcap=1 << 15, burst_levels=8)


def _default_serve_bucket(cfg):
    return cfg, dict(DEFAULT_BUCKET_PARAMS)


def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


def resolve_wave_mesh(value) -> Tuple[int, int]:
    """Normalize a ``--wave-mesh`` spec to a (J, S) mesh shape.

    J is the job-axis device count, S the state-shard count — the
    two axes of the serving wave's ``("jobs", "state")`` mesh.
    ``(0, 1)`` means mesh off (the historical single-device wave).

    ``"auto"``/None -> all local devices on the job axis when more
    than one is visible, else off; ``BucketEngine`` may re-split an
    auto shape to S > 1 when the bucket ceiling's per-job dedup state
    exceeds the per-device budget (``_AUTO_STATE_BUDGET``).
    ``"off"``/0/1 -> off.  An integer N -> ``(N, 1)``, the round-16
    job-axis mesh.  ``"JxS"`` (e.g. ``4x2``) -> J job rows x S state
    shards; J*S must fit the backend.  Anything else is a ValueError
    with the offending value named (the CLI turns it into exit 2,
    never a traceback)."""
    import jax
    avail = jax.local_device_count()
    if value is None or value == "auto":
        return (avail, 1) if avail > 1 else (0, 1)
    if value == "off":
        return (0, 1)
    if isinstance(value, tuple):
        j, s = int(value[0]), int(value[1])
        if j < 0 or s < 1:
            raise ValueError(f"--wave-mesh shape must have J >= 0 and "
                             f"S >= 1, got {value!r}")
    elif isinstance(value, str) and "x" in value:
        try:
            j_txt, s_txt = value.split("x", 1)
            j, s = int(j_txt), int(s_txt)
        except ValueError:
            raise ValueError(
                f"--wave-mesh must be 'auto', 'off', a device count "
                f"or JxS (e.g. 4x2), got {value!r}")
        if j < 1 or s < 1:
            raise ValueError(
                f"--wave-mesh {value!r}: both the J (jobs) and S "
                f"(state) axes must be >= 1")
    else:
        try:
            n = int(value)
        except (TypeError, ValueError):
            raise ValueError(
                f"--wave-mesh must be 'auto', 'off', a device count "
                f"or JxS (e.g. 4x2), got {value!r}")
        if n < 0:
            raise ValueError(f"--wave-mesh device count must be >= 0, "
                             f"got {n}")
        j, s = n, 1
    if j * s > avail:
        raise ValueError(
            f"--wave-mesh {value!r} needs {j * s} device(s) and "
            f"exceeds the {avail} visible local device(s)")
    return (j, s) if j * s > 1 else (0, 1)


# ---------------------------------------------------------------------------
# per-job bookkeeping
# ---------------------------------------------------------------------------

class _JobRun:
    """One job's in-flight state inside a batched wave: the CheckResult
    under construction, the BFS cursors the harvest loop advances, and
    the per-level trace archives (host RAM lists, the in-RAM Engine
    archive format)."""

    def __init__(self, job: Job):
        from ..engine.bfs import CheckResult
        self.job = job
        self.res = CheckResult()
        # per-job wall clock starts when the job enters its wave, so
        # a job's reported seconds never absorb OTHER buckets' compile
        # or runtime (it still shares its own wave's wall, honestly)
        self._t0 = time.perf_counter()
        self.depth = 0
        self.n_states = 0
        self.n_front = 0
        self.parents: List[np.ndarray] = []
        self.lanes: List[np.ndarray] = []
        self.states: List[Dict[str, np.ndarray]] = []
        self.live = True
        self.fallback = False
        self.fallback_reason: Optional[str] = None
        # preemption / resume (round 12): a carry slice to enter the
        # next wave with instead of root admission — set by a wave
        # yield (parked) or a wave-state restore (resumed)
        self.preinit: Optional[Dict] = None
        self.parked = False
        self.resumed = False
        # SLO accounting (round 13): submission -> wave-entry seconds,
        # stamped by the driver's _SloTracker
        self.wait_s = 0.0

    def finish(self):
        self.live = False
        self.res.depth = self.depth
        self.res.seconds = time.perf_counter() - self._t0

    def mark_fallback(self, reason: str):
        self.live = False
        self.fallback = True
        self.fallback_reason = reason

    @property
    def status(self) -> str:
        if self.live:
            return "parked" if self.parked else "running"
        return "fallback" if self.fallback else "done"

    # -- wave-state (de)hydration (serve/wavestate) --------------------

    def book(self) -> Dict:
        res = self.res
        return dict(
            cache_key=self.job.cache_key(), label=self.job.label,
            depth=int(self.depth), n_states=int(self.n_states),
            n_front=int(self.n_front),
            distinct=int(res.distinct_states),
            generated=int(res.generated_states),
            faults=int(res.overflow_faults),
            viol_global=int(res.violations_global),
            levels_fused=int(res.levels_fused),
            burst_dispatches=int(res.burst_dispatches),
            burst_bailouts=int(res.burst_bailouts),
            level_sizes=[int(x) for x in res.level_sizes],
            violations=[[v.invariant, int(v.state_id)]
                        for v in res.violations],
            n_arch=len(self.parents))

    def wave_arrays(self) -> Dict[str, np.ndarray]:
        out = {}
        for nm in ("fm", "gd", "vis"):
            out[nm] = self.preinit[nm]
        for k, v in self.preinit["fr"].items():
            out[f"fr|{k}"] = v
        out["cursors"] = np.array(
            [self.preinit["nf"], self.preinit["g"],
             self.preinit["pg"]], np.int64)
        for i, (p, ln) in enumerate(zip(self.parents, self.lanes)):
            out[f"par|{i}"] = p
            out[f"lane|{i}"] = ln
            for k, v in self.states[i].items():
                out[f"st|{i}|{k}"] = v
        return out

    @classmethod
    def from_wave_state(cls, job: Job, arrays: Dict, book: Dict
                        ) -> "_JobRun":
        from ..engine.bfs import Violation
        run = cls(job)
        run.resumed = True
        run.depth = int(book["depth"])
        run.n_states = int(book["n_states"])
        run.n_front = int(book["n_front"])
        res = run.res
        res.distinct_states = int(book["distinct"])
        res.generated_states = int(book["generated"])
        res.overflow_faults = int(book["faults"])
        res.violations_global = int(book["viol_global"])
        res.levels_fused = int(book["levels_fused"])
        res.burst_dispatches = int(book["burst_dispatches"])
        res.burst_bailouts = int(book["burst_bailouts"])
        res.level_sizes = [int(x) for x in book["level_sizes"]]
        for inv, sid in book["violations"]:
            res.violations.append(Violation(str(inv), int(sid)))
        fr = {nm.split("|", 1)[1]: arrays[nm] for nm in arrays
              if nm.startswith("fr|")}
        cur = arrays["cursors"]
        run.preinit = dict(fr=fr, fm=arrays["fm"], vis=arrays["vis"],
                           gd=arrays["gd"], nf=int(cur[0]),
                           g=int(cur[1]), pg=int(cur[2]))
        n_arch = int(book.get("n_arch", 0))
        st_keys = sorted({nm.split("|", 2)[2] for nm in arrays
                          if nm.startswith("st|0|")})
        for i in range(n_arch):
            run.parents.append(arrays[f"par|{i}"])
            run.lanes.append(arrays[f"lane|{i}"])
            run.states.append({k: arrays[f"st|{i}|{k}"]
                               for k in st_keys})
        return run


class JobOutcome:
    """One job's final answer: status, the CheckResult (None for cache
    hits), the JSON-able report row, and — when trace archives exist —
    ``trace(gid)``/``get_state(gid)`` in the Engine format."""

    def __init__(self, job: Job, status: str, res=None, report=None,
                 archives=None, engine=None, reason=None):
        self.job = job
        self.status = status
        self.res = res
        self.report = report or {}
        self._archives = archives      # (parents, lanes, states, labels)
        self._engine = engine          # solo engine (fallback path)
        self.reason = reason

    @property
    def cache_hit(self) -> bool:
        return self.status == "cache_hit"

    def get_state(self, gid: int):
        if self._engine is not None:
            return self._engine.get_state(gid)
        if self._archives is None:
            raise ValueError(f"job {self.job.label!r}: no trace "
                             "archives (store_states off or cache hit)")
        ir, lay = self.job.ir, self._archives[4]
        _parents, _lanes, states, _labels = self._archives[:4]
        off = 0
        for blk in states:
            n = next(iter(blk.values())).shape[0]
            if gid < off + n:
                return ir.decode(lay, _take(blk, gid - off))
            off += n
        raise IndexError(gid)

    def trace(self, gid: int) -> List[Tuple]:
        """Witness trace (label, state) chain — the Engine.trace
        contract, replayed from the per-job archives."""
        if self._engine is not None:
            return self._engine.trace(gid)
        if self._archives is None:
            raise ValueError(f"job {self.job.label!r}: no trace "
                             "archives (store_states off or cache hit)")
        parents_l, lanes_l, _states, labels, _lay = self._archives
        parents = np.concatenate(parents_l)
        lanes = np.concatenate(lanes_l)
        chain = []
        g = gid
        while g >= 0:
            lane = int(lanes[g])
            label = labels[lane] if lane >= 0 else "Init"
            chain.append((label, self.get_state(g)[0]))
            g = int(parents[g])
        return list(reversed(chain))

    def cache_payload(self) -> Dict:
        return dict(self.report)

    @classmethod
    def _from_cache(cls, job: Job, payload: Dict) -> "JobOutcome":
        report = dict(payload)
        report["status"] = "cache_hit"
        report["label"] = job.label
        return cls(job, "cache_hit", report=report)


class BatchReport:
    """run_jobs' return value: outcomes in submission order + the
    batch-level meta counters (buckets, compiles, dispatches, cache
    hits, fallbacks)."""

    def __init__(self, outcomes: List[JobOutcome], meta: Dict,
                 seconds: float):
        self.outcomes = outcomes
        self.meta = dict(meta)
        self.meta["seconds"] = round(seconds, 3)

    @property
    def summary(self) -> Dict:
        # a drained serve() round leaves deferred outcomes as None —
        # they carry no violations yet, by definition
        return {"kind": "batch_summary", **self.meta,
                "violations": sum(int(o.report.get("violations", 0))
                                  for o in self.outcomes
                                  if o is not None)}


# ---------------------------------------------------------------------------
# report assembly
# ---------------------------------------------------------------------------

def _build_report(job: Job, res, status: str, reason=None,
                  tracer=None) -> Dict:
    ir = spec_of(job.cfg)
    out = check_stats(res.metrics.as_dict(), res.seconds,
                      len(res.violations),
                      fp_bits=128 if getattr(job.cfg, "fp128", False)
                      else 64,
                      spec=ir.name, ir_fp=ir.fingerprint())
    out["label"] = job.label
    out["status"] = status
    if reason:
        out["status_reason"] = reason
    out["cfg_fingerprint"] = job.cfg_fingerprint()
    out["opts_fingerprint"] = job.opts_fingerprint()
    out["cache_key"] = job.cache_key()
    out["level_sizes"] = [int(x) for x in res.level_sizes]
    det = []
    for v in res.violations[:8]:
        d = {"invariant": v.invariant, "state_id": int(v.state_id)}
        if tracer is not None and v.state_id >= 0:
            d["trace"] = [lbl for lbl, _sv in tracer(v.state_id)]
        det.append(d)
    out["violations_detail"] = det
    return out


def _job_row(obs, outcome: JobOutcome):
    if obs.ledger is None:
        return
    rec = dict(outcome.report)
    rec["kind"] = "job"
    obs.ledger.record(rec)


def _jobs_map(runs: List[_JobRun]) -> Dict[str, Dict]:
    return {run.job.label: {"depth": int(run.depth),
                            "distinct": int(run.res.distinct_states),
                            "status": run.status}
            for run in runs}


# ---------------------------------------------------------------------------
# SLO accounting (ROADMAP item 1, round 13): per-job wait (submission ->
# first wave entry) and service (wave entry -> answer) seconds, folded
# into fixed-bucket histograms the heartbeat carries live and the
# per-tenant ledger rollups summarize at batch end.
# ---------------------------------------------------------------------------

_SLO_EDGES = (0.01, 0.05, 0.25, 1.0, 5.0, 30.0, 120.0)


def slo_histogram(seconds: List[float]) -> Dict[str, int]:
    """Fixed log-ish latency buckets (cumulative-friendly: each key is
    the bucket's inclusive upper edge, 'inf' catches the tail)."""
    hist = {f"le_{e:g}": 0 for e in _SLO_EDGES}
    hist["inf"] = 0
    for s in seconds:
        for e in _SLO_EDGES:
            if s <= e:
                hist[f"le_{e:g}"] += 1
                break
        else:
            hist["inf"] += 1
    return hist


class _SloTracker:
    """The batch-global SLO state ``run_jobs`` maintains: submission
    timestamps, finished jobs' wait/service samples, and the live
    snapshot dict (mutated in place — run_wave's dispatches carry it
    into every heartbeat)."""

    def __init__(self, n_jobs: int):
        self.t_submit = time.perf_counter()
        self.waits: List[float] = []
        self.services: List[float] = []
        self.snapshot: Dict = {"queue_depth": n_jobs,
                               "jobs_done": 0,
                               "wait_hist": slo_histogram([]),
                               "service_hist": slo_histogram([])}

    def job_entered(self, run: "_JobRun"):
        run.wait_s = run._t0 - self.t_submit

    def job_done(self, wait_s: float, service_s: float):
        self.waits.append(max(0.0, float(wait_s)))
        self.services.append(max(0.0, float(service_s)))
        self.snapshot["jobs_done"] = len(self.services)
        self.snapshot["wait_hist"] = slo_histogram(self.waits)
        self.snapshot["service_hist"] = slo_histogram(self.services)

    def set_queue_depth(self, n: int):
        self.snapshot["queue_depth"] = max(0, int(n))


# ---------------------------------------------------------------------------
# the bucket engine
# ---------------------------------------------------------------------------

class BucketEngine:
    """One compiled batched checker per (spec, ceiling cfg, params)
    bucket.  Wraps an ordinary ``Engine`` for the ceiling config and
    drives its job-vmapped burst core; never calls ``Engine.check``,
    so the solo executables are never traced or compiled here."""

    def __init__(self, cfg, chunk: int = 128, vcap: int = 1 << 15,
                 burst_levels: int = 8, delta_matmul: bool = True,
                 sym_canon: str = "auto", exec_cache=None,
                 wave_mesh=0, wave_mesh_auto: bool = False):
        from ..engine.bfs import Engine
        # store_states stays off on the engine — serve harvests its own per-job
        # archives straight from the burst outputs.  delta_matmul
        # vmaps cleanly (pure einsum blocks), so the batched program
        # keeps the group delta path; the kwarg exists for A/B tests
        # (bucket_overrides={"delta_matmul": False}).
        self.eng = Engine(cfg, chunk=chunk, store_states=False,
                          vcap=vcap,
                          burst_levels=burst_levels,
                          delta_matmul=delta_matmul,
                          sym_canon=sym_canon)
        self.KB = self.eng._burst_width()
        self.VCAP = self.eng.VCAP
        # Donation-free program whenever a persistent executable cache
        # is in play: carry donation bakes input->output aliasing into
        # the executable, and a serialize_executable round-trip loaded
        # in a DIFFERENT process silently corrupts the donated carry
        # outputs (stats stay right, the re-fed wave and the persisted
        # wave state go wrong — daemon_smoke's warm-restart phase
        # caught it).  The stored, loaded, and freshly-compiled
        # programs must be the SAME program, so the choice is made
        # once here and recorded in _exec_key_parts.
        self._donate = exec_cache is None
        # constant-padding ceilings flag first: the carry template the
        # 2-D spec trees match on needs to know whether rt rides along
        self.rt_mode = self.eng.ir.serve_runtime is not None
        self._rt_cache: Dict[str, Dict] = {}
        # mesh mode (rounds 16-17): shard the wave across a 2-D
        # (J, S) = ("jobs", "state") mesh of local devices.  With
        # S == 1 every leaf of the batched carry leads with [J], so
        # ONE job-axis NamedSharding is the pytree-prefix spec for the
        # whole program — GSPMD splits the wave with no data
        # collectives (lanes are independent).  With S > 1 the big
        # per-job arrays ALSO shard the "state" axis under per-leaf
        # rule-matched spec trees (WAVE_CARRY_RULES/WAVE_OUT_RULES —
        # the parallel/pjit_mesh substrate), so one huge tenant's
        # visited table and rings span J*S devices while the dedup
        # probe/claim scatter stays an in-program state-axis
        # collective.  Either way the per-job harvest slicing below
        # stays host-side and mode-blind.
        if isinstance(wave_mesh, tuple):
            mj, ms = int(wave_mesh[0]), int(wave_mesh[1])
        else:
            mj, ms = int(wave_mesh or 0), 1
        if wave_mesh_auto and mj > 1 and ms == 1:
            mj, ms = self._auto_split(mj)
        if mj * ms <= 1:
            mj, ms = 0, 1
        self.mesh_jobs = mj
        self.mesh_state = ms
        self.mesh_devices = mj * ms
        self._spec_trees = None
        if self.mesh_devices > 1:
            import jax
            from jax.sharding import NamedSharding, PartitionSpec
            mesh = jax.make_mesh(
                (mj, ms), ("jobs", "state"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2,
                devices=jax.devices()[:self.mesh_devices])
            self._sharding = NamedSharding(mesh, PartitionSpec("jobs"))
            if ms > 1:
                self._spec_trees = self._wave_spec_trees(mesh)
        else:
            self._sharding = None
        self._fn = self.eng.burst_batched_fn(
            donate=self._donate,
            sharding=(self._spec_trees if self._spec_trees is not None
                      else self._sharding))
        self._compiled = {}            # padded J -> AOT executable
        # constant-padding ceilings (round 13): with a serve_runtime
        # hook (rt_mode above), every job's guard thresholds / family
        # lane mask / search-bounds vector enter the batched program as
        # per-job device data (jst["rt"]) — cfg here is the bucket's
        # CEILING, which may sit strictly above any member job's config
        # (the rt memo cache itself is initialized next to rt_mode,
        # before the mesh build that may template rt arrays).
        # persistent AOT executable cache (serve/exec_cache): None =
        # the historical always-compile behavior
        self.exec_cache = exec_cache

    def _auto_split(self, D: int) -> Tuple[int, int]:
        """The ``auto`` 2-D heuristic (round 17): given D auto-resolved
        devices on the job axis, move power-of-two factors of D onto
        the state axis while ONE job's dedup state (W visited words +
        the claims word per table slot, u32 each) exceeds the
        per-device budget — a huge ceiling spans the mesh instead of
        pinning one device at its HBM wall.  S stays a divisor of D so
        the (J, S) grid is always full."""
        per_job = (self.eng.W + 1) * self.VCAP * 4
        s = 1
        while s * 2 <= D and D % (s * 2) == 0 and \
                per_job // s > _AUTO_STATE_BUDGET:
            s *= 2
        return D // s, s

    def _carry_template(self):
        """The batched carry as a [J=1] ShapeDtypeStruct pytree: the
        structure + leaf ranks the 2-D sharding rules match on
        (shardings are shape-free, so one template serves every wave
        width)."""
        import jax
        eng = self.eng
        one = eng.ir.narrow(eng.lay, eng.ir.encode(
            eng.lay, *eng.ir.init_state(eng.cfg)))
        sds = jax.ShapeDtypeStruct
        tpl = dict(
            vis=tuple(sds((1, self.VCAP), np.uint32)
                      for _ in range(eng.W)),
            claims=sds((1, self.VCAP), np.uint32),
            fr={k: sds((1,) + np.asarray(v).shape + (self.KB,),
                       np.asarray(v).dtype)
                for k, v in one.items()},
            fm=sds((1, self.KB), np.bool_),
            gd=sds((1, self.KB), np.int32),
            nf=sds((1,), np.int32),
            g=sds((1,), np.int32),
            pg=sds((1,), np.int32))
        if self.rt_mode:
            tpl["rt"] = {nm: sds((1,) + np.asarray(v).shape,
                                 np.asarray(v).dtype)
                         for nm, v in self._rt_of(eng.cfg).items()
                         if nm in ("thr", "mask", "bounds")}
        return tpl

    def _wave_spec_trees(self, mesh) -> Dict:
        """Per-leaf NamedSharding trees for the 2-D wave program:
        rule-matched PartitionSpecs (parallel/pjit_mesh's
        ``match_partition_rules``) over the carry template and the
        burst's output structure (via ``jax.eval_shape`` on the
        UNCHANGED ``_batched_burst_impl``), plus the job-axis gate
        sharding for the lv/cap vectors."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec
        from ..parallel.pjit_mesh import match_partition_rules
        tpl = self._carry_template()
        gate = jax.ShapeDtypeStruct((1,), np.int32)
        out_tpl = jax.eval_shape(self.eng._batched_burst_impl,
                                 tpl, gate, gate)[1]

        def named(tree, rules):
            specs = match_partition_rules(rules, tree)
            return jax.tree_util.tree_map(
                lambda spec: NamedSharding(mesh, spec), specs,
                is_leaf=lambda x: isinstance(x, PartitionSpec))

        return {"carry": named(tpl, WAVE_CARRY_RULES),
                "gate": NamedSharding(mesh, PartitionSpec("jobs")),
                "out": named(out_tpl, WAVE_OUT_RULES)}

    def _rt_of(self, cfg) -> Dict[str, np.ndarray]:
        """One job's runtime-thresholds arrays under this bucket's
        ceiling expander, memoized per config repr (every wave of a
        resumed/parked job re-enters with identical arrays)."""
        key = repr(cfg)
        rt = self._rt_cache.get(key)
        if rt is None:
            rt = self._rt_cache[key] = \
                self.eng.ir.serve_runtime(self.eng.expander, cfg)
        return rt

    def _exec_key_parts(self, JP: int) -> Dict:
        """Every compile-relevant identity of the (bucket, JP)
        executable — serve/exec_cache docstring.  The ceiling cfg repr
        covers the predicate name lists, symmetry and fp128; the
        engine fields cover the program's static shapes and modes."""
        from ..obs.resources import backend_fingerprint
        from .exec_cache import code_fingerprint
        eng = self.eng
        return {
            "backend": backend_fingerprint(),
            # source identity: any package code change is a miss (a
            # stale executable must never answer for new semantics)
            "code": code_fingerprint(),
            "spec": eng.ir.name,
            "ir_fingerprint": eng.ir.fingerprint(),
            "ceiling_cfg": repr(eng.cfg),
            "JP": JP,
            "chunk": eng.chunk, "KB": self.KB, "VCAP": self.VCAP,
            "FCAP": eng.FCAP, "OCAP": eng.OCAP,
            "burst_levels": eng.burst_levels,
            "fam_caps": list(eng.FAM_CAPS),
            "W": eng.W,
            "guard_matmul": eng.guard_matmul,
            "delta_matmul": eng.expander.delta_active,
            # the RESOLVED canonicalization mode: sort and minperm
            # compile different fingerprint programs AND produce
            # different table values — never share an executable
            "sym_canon": eng.fpr.sym_canon,
            "incremental_fp": bool(eng.incremental_fp and
                                   eng.fpr.supports_incremental()),
            "rt_mode": self.rt_mode,
            # donation mode is program identity: a donated executable
            # must never be revived cross-process (see __init__)
            "donate": self._donate,
            # mesh shape is program identity too: the [J, S] grid (0
            # when off).  A 4x1 sharded executable must read as a
            # NAMED miss on a 2x2 or 1-device process (and vice
            # versa), never a wrong load — resharding changes the
            # GSPMD program, not just placement.  JP above already
            # covers the wave-lane width the mesh multiple changes.
            "wave_mesh": ([self.mesh_jobs, self.mesh_state]
                          if self.mesh_devices else 0),
        }

    # -- root admission ------------------------------------------------

    def _admit(self, run: _JobRun):
        """Level-0 admission for one job — the host-side twin of
        Engine.check's fresh-start path (roots dedup, invariant/
        constraint eval, archive, table placement).  Returns the
        per-job init arrays, or None when the root set cannot enter
        the batched path."""
        import jax.numpy as jnp

        from ..engine.bfs import Violation
        eng = self.eng
        roots, rk, _pins = eng._dedup_roots(run.job.seed_states)
        n = len(rk)
        if n > min(self.KB, int(eng._LOAD_MAX * self.VCAP)):
            run.mark_fallback(
                f"{n} root states exceed the bucket ring/table")
            return None
        narrow_mj = {k: np.asarray(v) for k, v in
                     eng.ir.narrow(eng.lay, eng.ir.widen(roots)).items()}
        rootsj = {k: jnp.asarray(v) for k, v in roots.items()}
        if self.rt_mode:
            # root constraints gate level-0 expansion: they must read
            # the JOB's bounds, not the ceiling's
            inv_r, con_r = eng._phase2_rt(
                rootsj,
                jnp.asarray(self._rt_of(run.job.cfg)["bounds"]))
        else:
            inv_r, con_r = eng._phase2(rootsj)
        inv_r, con_r = np.asarray(inv_r), np.asarray(con_r)
        res = run.res
        res.distinct_states = n
        res.generated_states = n
        res.overflow_faults = int(
            (np.asarray(roots["ctr"])[:, C_OVERFLOW] > 0).sum())
        res.violations_global = int((~inv_r).sum())
        eng._stamp_mode(res)
        if run.job.store_states:
            run.parents.append(np.full((n,), -1, np.int32))
            run.lanes.append(np.full((n,), -1, np.int32))
            run.states.append({k: v.copy()
                               for k, v in narrow_mj.items()})
        for jx, nm in enumerate(eng.inv_names):
            for s in np.nonzero(~inv_r[:, jx])[0]:
                vsv, vh = eng.ir.decode(eng.lay, _take(narrow_mj,
                                                       int(s)))
                res.violations.append(
                    Violation(nm, int(s), state=vsv, hist=vh))
        run.n_states = n
        run.n_front = n
        # the job is born finished when its gates already close
        if run.job.max_depth <= 0 or \
                res.distinct_states >= run.job.max_states or \
                (run.job.stop_on_violation and res.violations):
            run.finish()
        fr = {k: np.zeros(v.shape[1:] + (self.KB,), v.dtype)
              for k, v in narrow_mj.items()}
        for k in fr:
            fr[k][..., :n] = np.moveaxis(narrow_mj[k], 0, -1)
        fm = np.zeros((self.KB,), bool)
        fm[:n] = con_r
        vis = np.full((eng.W, self.VCAP), U32MAX_NP, np.uint32)
        slots = eng._host_probe_assign(rk, vcap=self.VCAP)
        for w in range(eng.W):
            vis[w][slots] = rk[:, w]
        return dict(fr=fr, fm=fm, vis=vis, nf=n, g=n)

    def _pad_init(self):
        """A frozen placeholder job (nf=0): pads a wave to its
        power-of-two width without contributing any work."""
        eng = self.eng
        one = eng.ir.narrow(eng.lay, eng.ir.encode(
            eng.lay, *eng.ir.init_state(eng.cfg)))
        fr = {k: np.zeros(v.shape + (self.KB,), v.dtype)
              for k, v in one.items()}
        fm = np.zeros((self.KB,), bool)
        vis = np.full((eng.W, self.VCAP), U32MAX_NP, np.uint32)
        out = dict(fr=fr, fm=fm, vis=vis, nf=0, g=0)
        if self.rt_mode:
            # a pad job still needs rt arrays of the stacked shape;
            # the ceiling's own (all-enabled) data is the natural
            # no-op — the pad lane is frozen (nf=0) regardless
            out["rt"] = self._rt_of(eng.cfg)
        return out

    def _pad_jp(self, n: int) -> int:
        """Wave width for n admitted jobs.  Single-device: the next
        power of two (tiny compile cache).  Mesh mode: a J-axis
        multiple J * pow2(ceil(n/J)) — the state axis never eats wave
        lanes — so every job row holds the same lane count and the pad
        lanes (frozen, nf=0) are the only idle-lane waste — surfaced
        as ``pad N/M`` by tools/watch."""
        J = self.mesh_jobs
        if J > 1:
            return J * _next_pow2(max(1, -(-n // J)))
        return _next_pow2(n)

    def _place(self, x):
        """Device placement for a job-axis wave input (the lv/cap gate
        vectors and, with S == 1, the whole carry): under the job mesh
        when sharding, else jax's default (single device).  Host numpy
        in (the _stack/_job_slice format is host-side and mode-blind)
        -> committed device arrays out, so a parked or restored carry
        re-enters ANY mesh shape — the wave.npz restart matrix is
        portable by construction."""
        if self._sharding is None:
            return x
        import jax
        return jax.device_put(x, self._sharding)

    def _place_carry(self, jst):
        """Carry placement: leaf-by-leaf under the 2-D per-leaf spec
        trees when the state axis is on, the single job-axis prefix
        otherwise (same _place portability contract either way)."""
        if self._spec_trees is not None:
            import jax
            return jax.tree_util.tree_map(jax.device_put, jst,
                                          self._spec_trees["carry"])
        return self._place(jst)

    def _stack(self, inits):
        import jax.numpy as jnp
        eng = self.eng
        JP = len(inits)
        # gd/pg default to the fresh-start values (root gids are the
        # ring prefix; no previous level); a restored/parked init
        # carries its real cursors (wave-state resume, round 12)
        gd0 = np.arange(self.KB, dtype=np.int32)
        rt = {}
        if self.rt_mode:
            # per-job runtime thresholds / lane masks / bounds on the
            # leading [J] axis (engine/bfs._batched_burst_impl)
            rt = dict(rt={
                nm: jnp.asarray(np.stack(
                    [np.asarray(it["rt"][nm]) for it in inits]))
                for nm in ("thr", "mask", "bounds")})
        return self._place_carry(dict(
            **rt,
            vis=tuple(jnp.asarray(np.stack([it["vis"][w]
                                            for it in inits]))
                      for w in range(eng.W)),
            claims=jnp.full((JP, self.VCAP), np.uint32(U32MAX_NP)),
            fr={k: jnp.asarray(np.stack([it["fr"][k] for it in inits]))
                for k in inits[0]["fr"]},
            fm=jnp.asarray(np.stack([it["fm"] for it in inits])),
            gd=jnp.asarray(np.stack([
                np.asarray(it.get("gd", gd0), np.int32)
                for it in inits])),
            nf=jnp.asarray(np.array([it["nf"] for it in inits],
                                    np.int32)),
            g=jnp.asarray(np.array([it["g"] for it in inits],
                                   np.int32)),
            pg=jnp.asarray(np.array([int(it.get("pg", 0))
                                     for it in inits], np.int32)),
        ))

    def _job_slice(self, jst, k: int) -> Dict:
        """One job's lane of the batched carry -> a host init dict
        (the _stack/_admit format plus gd/pg) — the parkable/
        persistable per-job wave state."""
        eng = self.eng
        return dict(
            fr={nm: np.asarray(v[k]) for nm, v in jst["fr"].items()},
            fm=np.asarray(jst["fm"][k]),
            vis=np.stack([np.asarray(jst["vis"][w][k])
                          for w in range(eng.W)]),
            gd=np.asarray(jst["gd"][k]),
            nf=int(np.asarray(jst["nf"][k])),
            g=int(np.asarray(jst["g"][k])),
            pg=int(np.asarray(jst["pg"][k])))

    # -- the wave driver -----------------------------------------------

    def run_wave(self, runs: List[_JobRun], obs, meta: Dict,
                 jobs_ctx: Optional[Dict] = None,
                 verbose: bool = False,
                 max_steps: Optional[int] = None,
                 wave_state: Optional[WaveStateStore] = None,
                 slo_ctx: Optional[Dict] = None,
                 stop=None):
        """Run up to a wave of jobs through the batched burst.
        Mutates the runs in place; jobs that bail are marked for the
        sequential fallback.  ``jobs_ctx`` is the batch-global per-job
        status map (heartbeat payload) this wave merges its own
        statuses into.

        ``max_steps`` — preemption (round 12): after that many batched
        device calls, still-live jobs PARK (their carry slice moves to
        ``run.preinit``) and the wave returns, yielding the lanes to
        waiting jobs; the driver re-enters parked runs in a later
        wave.  ``wave_state`` persists every live job's slice at each
        wave boundary, so a killed process resumes stragglers
        mid-BFS.

        ``stop`` — graceful drain (serve/scheduler): a callable
        checked at every wave step boundary, AFTER the wave-state
        persist; when it returns true, still-live jobs park exactly as
        a ``max_steps`` yield would, so the scheduler can defer them
        with their carries safely on disk."""
        import jax.numpy as jnp
        eng = self.eng
        with obs.span("job_admit"):
            admitted = []
            for run in runs:
                if run.preinit is not None:
                    # parked/restored job: enter with its carry slice,
                    # not root admission (counters already accrued)
                    init, run.preinit = run.preinit, None
                    eng._stamp_mode(run.res)
                else:
                    init = self._admit(run)
                if init is not None:
                    if self.rt_mode:
                        # rt is derived from the job's config, never
                        # persisted: parked/restored carries re-attach
                        # it here (bit-identical arrays by construction)
                        init["rt"] = self._rt_of(run.job.cfg)
                    admitted.append((run, init))
        if not any(run.live for run, _ in admitted):
            for run, _ in admitted:
                if not run.fallback:
                    run.finish()
            return
        JP = self._pad_jp(len(admitted))
        inits = [init for _run, init in admitted]
        inits += [self._pad_init()] * (JP - len(admitted))
        jst = self._stack(inits)
        # wave occupancy (rounds 16-17): the J x S grid, lanes and the
        # pad waste, for the heartbeat/ledger and the registry counters
        wave_dev = max(1, self.mesh_devices)
        wave_ss = max(1, self.mesh_state)
        wave_occ = {"devices": wave_dev, "lanes": JP,
                    "filled": len(admitted),
                    "pad": JP - len(admitted),
                    "jobs_per_device": JP // max(1, self.mesh_jobs),
                    "state_shards": wave_ss}
        meta["wave_devices"] = max(meta.get("wave_devices", 0),
                                   wave_dev)
        meta["wave_lanes"] = max(meta.get("wave_lanes", 0), JP)
        meta["wave_state_shards"] = max(
            meta.get("wave_state_shards", 0), wave_ss)
        steps = 0
        while any(run.live for run, _ in admitted):
            # chaos site: dispatch-time device/runtime error on the
            # batched program (the batch-level --retries re-runs the
            # job list; cache + wave state make the retry incremental)
            chaos_point("dispatch")
            lv = np.zeros((JP,), np.int32)
            cap = np.ones((JP,), np.int32)
            for k, (run, _) in enumerate(admitted):
                if run.live:
                    lv[k] = min(eng.burst_levels,
                                run.job.max_depth - run.depth)
                    cap[k] = max(1, min(
                        run.job.max_states - run.res.distinct_states,
                        2 ** 31 - 1))
            lvj = self._place(jnp.asarray(lv))
            capj = self._place(jnp.asarray(cap))
            ex = self._compiled.get(JP)
            key = parts = None
            if ex is None and self.exec_cache is not None:
                # persistent AOT executable cache (serve/exec_cache):
                # a warm restart loads the serialized executable and
                # performs ZERO .compile() calls; any failure is a
                # labeled miss and falls through to the compile below
                from .exec_cache import exec_key
                parts = self._exec_key_parts(JP)
                key = exec_key(parts)
                with obs.span("bucket_exec_load"):
                    ex, _why = self.exec_cache.load(key, parts)
                if ex is not None:
                    self._compiled[JP] = ex
            if ex is None:
                # AOT compile, in its own span: the bench and the
                # ledger attribute bucket-compile seconds exactly
                with obs.span("bucket_compile"):
                    ex = self._fn.lower(jst, lvj, capj).compile()
                self._compiled[JP] = ex
                if self.exec_cache is not None:
                    # store failures are counted + named (a backend
                    # without serialization support), never raised
                    with obs.span("bucket_exec_store"):
                        self.exec_cache.store(key, ex, parts)
            with obs.span("batched_dispatch"):
                jst, out = ex(jst, lvj, capj)
                stats = np.asarray(out["stats"])   # the ONE sync
            meta["batch_dispatches"] += 1
            with obs.span("job_harvest"):
                for k, (run, _) in enumerate(admitted):
                    if not run.live:
                        continue
                    # archives transfer PER JOB, and only for jobs
                    # that keep traces or hit a violation — a wave
                    # where one job stores never pays the whole
                    # [J, levels, ...] stack's device-to-host cost
                    need = run.job.store_states or stats[k, -1, 3]
                    self._harvest(
                        run, stats[k],
                        np.asarray(out["par"][k]) if need else None,
                        np.asarray(out["lane"][k]) if need else None,
                        np.asarray(out["inv"][k]) if need else None,
                        {nm: np.asarray(v[k])
                         for nm, v in out["st"].items()}
                        if need else None)
            steps += 1
            if wave_state is not None:
                # wave boundary: persist every still-live job's carry
                # slice + bookkeeping, so a kill between here and the
                # next boundary resumes mid-BFS (finished jobs are
                # covered by the result cache instead)
                with obs.span("wave_persist"):
                    for k, (run, _) in enumerate(admitted):
                        if run.live:
                            run.preinit = self._job_slice(jst, k)
                            wave_state.save(run.job.cache_key(),
                                            run.wave_arrays(),
                                            run.book())
                            run.preinit = None
            # chaos site: the deterministic SIGKILL stand-in — fires
            # AFTER the persist, exactly like a kill at the boundary
            chaos_point("wave_kill")
            if ((max_steps is not None and steps >= max_steps) or
                    (stop is not None and stop())) and \
                    any(run.live for run, _ in admitted):
                # preemption: park the stragglers' carry slices and
                # yield the lanes to waiting jobs; the driver requeues
                # parked runs into a later wave
                for k, (run, _) in enumerate(admitted):
                    if run.live:
                        run.preinit = self._job_slice(jst, k)
                        run.parked = True
            live_runs = [run for run, _ in admitted]
            jobs_map = dict(jobs_ctx or {})
            jobs_map.update(_jobs_map(live_runs))
            if jobs_ctx is not None:
                jobs_ctx.update(jobs_map)
            obs.dispatch(
                kind="batch",
                depth=max((r.depth for r in live_runs), default=0),
                frontier=sum(r.n_front for r in live_runs if r.live),
                metrics={
                    "distinct_states": sum(
                        int(r.res.distinct_states) for r in live_runs),
                    "generated_states": sum(
                        int(r.res.generated_states)
                        for r in live_runs)},
                jobs=jobs_map, slo=slo_ctx, wave=wave_occ)
            if verbose:
                done = sum(1 for r in live_runs if not r.live)
                print(f"batch wave: {done}/{len(live_runs)} jobs done, "
                      f"max depth "
                      f"{max((r.depth for r in live_runs), default=0)}")
            if any(run.parked for run, _ in admitted):
                break

    def _harvest(self, run: _JobRun, sj, par_j, lane_j, inv_j, st_j):
        """One job's slice of a batched call — the solo burst harvest
        (the SHARED engine/driver core, so the serve copy can never
        drift from the engine drivers again; depth gating, pseudo-level
        skip, archive rows, violation decode all run in
        driver.harvest_fused_levels)."""
        from ..engine import driver
        eng = self.eng
        res = run.res
        nlev = int(sj[-1, 0])
        bailed = bool(sj[-1, 1])
        res.burst_dispatches += 1
        res.burst_bailouts += int(bailed)
        if bailed:
            # the job outgrew its per-job ring / table / family caps:
            # discard the batched progress and re-run it solo (the solo
            # engine owns every growth path).  Exact by construction.
            run.mark_fallback("burst bailed (per-job ring or table "
                              "overflow) — re-run sequentially")
            return

        def _arch(li, n_lvl):
            if not run.job.store_states:
                return
            # zero-row levels still occupy an archive slot so gid
            # arithmetic matches the solo archives
            par, lane, states = driver.burst_archive_slice(
                par_j, lane_j, st_j, li, n_lvl)
            run.parents.append(par)
            run.lanes.append(lane)
            run.states.append(states)

        def _viol(li, n_lvl, gid_base):
            driver.burst_decode_violations(
                res, eng.ir, eng.lay, eng.inv_names, inv_j, st_j,
                li, n_lvl, gid_base)

        # no id guard: per-job ids never approach 2^31 (the historical
        # serve harvest carried none — bit-exact re-homing)
        run.depth, run.n_states = driver.harvest_fused_levels(
            res, nlev, lambda li: sj[li, :5], run.depth, run.n_states,
            archive=_arch, violations=_viol, id_guard=False)
        run.n_front = int(sj[-1, 2])
        if run.n_front == 0 or run.depth >= run.job.max_depth or \
                res.distinct_states >= run.job.max_states or \
                (run.job.stop_on_violation and res.violations):
            run.finish()
        elif nlev == 0:
            # defensive: a live job that neither committed a level nor
            # bailed would spin this driver forever — route it to the
            # exact sequential path instead
            run.mark_fallback("batched call made no progress")


# ---------------------------------------------------------------------------
# the driver
# ---------------------------------------------------------------------------

def _run_solo(job: Job, obs, meta: Dict, status: str,
              reason: Optional[str],
              sym_canon: str = "auto") -> JobOutcome:
    """One job on its own Engine (the sequential reference path):
    used for --sequential runs, batched-path fallbacks, and seeded/
    pinned jobs.  Engine dispatches ride the same obs bundle, so the
    ledger records the solo device traffic honestly.  sym_canon
    follows any bucket override so a fallback job dedups with the
    same canonicalization program its bucket would have."""
    from ..engine.bfs import Engine
    with obs.span("sequential_job"):
        eng = Engine(job.cfg, store_states=job.store_states,
                     sym_canon=sym_canon)
        meta["engines_compiled"] += 1
        res = eng.check(max_depth=job.max_depth,
                        max_states=job.max_states,
                        stop_on_violation=job.stop_on_violation,
                        seed_states=job.seed_states, obs=obs)
    tracer = eng.trace if job.store_states else None
    report = _build_report(job, res, status, reason=reason,
                           tracer=tracer)
    return JobOutcome(job, status, res=res, report=report, engine=eng,
                      reason=reason)


def run_jobs(jobs: List[Job], cache=None, obs=None,
             sequential: bool = False, bucket_overrides=None,
             verbose: bool = False, wave_state=None,
             wave_yield: Optional[int] = None,
             max_wave: Optional[int] = None,
             exec_cache=None, wave_mesh=None) -> BatchReport:
    """Serve a job list: cache lookups, shape-bucket grouping, batched
    waves, sequential fallbacks, cache fill.  Returns a BatchReport
    with outcomes in submission order.

    sequential=True skips the batched path entirely (one solo Engine
    per job) — the honest A/B reference bench.py records.
    bucket_overrides overrides the per-spec bucket params (tests force
    tiny rings with it to exercise the fallback).

    exec_cache (round 13) — a serve/exec_cache.ExecCache or a
    directory path: bucket executables are serialized around their
    ``.lower().compile()`` so a process restart re-loads them instead
    of re-paying the 30-50 s TPU compiles; hit/miss/store counters
    (incl. named miss reasons on backends that cannot serialize) land
    in the batch meta, the ledger and the heartbeat SLO snapshot.

    Round 12 (preemptible waves): jobs schedule by descending
    ``Job.priority`` (stable on submission order); ``wave_yield=N``
    makes a wave yield its lanes after N batched device calls while
    other jobs wait — stragglers PARK their carry and continue in a
    later wave.  ``wave_state`` (a WaveStateStore or directory path)
    persists every live job's carry at wave boundaries and resumes
    jobs from it on the next invocation, so a killed run continues
    finished jobs from the result cache and stragglers mid-BFS —
    bit-exact per job.  ``max_wave`` overrides the jobs-per-wave
    ceiling (default 8 per device; tests shrink it to force parking).

    ``wave_mesh`` (rounds 16-17) — ``"auto"`` (default), ``"off"``, a
    device count, or a ``JxS`` grid (e.g. ``"4x2"``): shard every
    batched wave across a 2-D ("jobs", "state") mesh of local devices
    (``resolve_wave_mesh``); S > 1 also shards each job's visited
    table / rings / level buffers so one huge tenant spans the mesh,
    and ``auto`` promotes state shards when the bucket ceiling
    exceeds the per-device budget.  Per-job results stay bit-exact in
    every mode; the wave ceiling scales to J x 8 lanes unless
    ``max_wave`` pins it.

    This function is the one-shot wrapper over the shared
    ``serve/scheduler.WaveScheduler`` core — the SAME driver loop the
    persistent daemon (``cli serve``) runs every intake cycle.  All
    scheduling logic (priority, yield/park, dedup, restore, fallback,
    rollups) lives there; this module keeps the per-wave machinery
    (``BucketEngine``) and the per-job bookkeeping it drives."""
    from .scheduler import WaveScheduler
    return WaveScheduler(
        cache=cache, wave_state=wave_state, exec_cache=exec_cache,
        bucket_overrides=bucket_overrides, wave_yield=wave_yield,
        max_wave=max_wave, wave_mesh=wave_mesh).serve(
        jobs, obs=obs, sequential=sequential, verbose=verbose)
