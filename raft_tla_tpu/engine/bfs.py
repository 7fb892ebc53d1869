"""Level-synchronous BFS engine: TLC's worker loop, TPU-shaped.

Replaces the reference's external checker (SURVEY §2.13: TLC's BFS +
fingerprint set + invariant eval) with a **device-resident** pipeline:
the frontier, the candidate expansion, the fingerprint set (an
open-addressing hash table in HBM), the dedup, the invariant /
constraint evaluation and the next-frontier compaction all live on
device.  Per frontier chunk the host issues ONE fused jit call
(expand + fingerprint + action constraints + claim-insert dedup +
invariant/constraint eval on the fresh rows + scatter into the level
buffer) with a donated carry, so chunk steps pipeline asynchronously;
the only per-level synchronization is reading back a handful of
scalars (new-state count, violation count, next-frontier size).

State identity follows TLC's semantics: the visited table stores the
symmetry-canonical VIEW fingerprints (engine/fingerprint) as
``n_streams`` u32 words; first-seen survivor order matches the Python
oracle (chunk-sequential, candidate-index order within a chunk —
SURVEY §7.4 pt 5) via rank-tie-broken claims.  CONSTRAINT semantics
are prune-not-reject: violating states are counted and checked but not
expanded (§2.8).  Parent pointers (state-id, lane-id) stream to the
host per level for trace reconstruction (SURVEY §7.2 L5).

Dedup design (the hot path): a
membership query against the table costs ~1-3 dependent gathers
(quadratic probing at load factor <= _LOAD_MAX), versus the ~22-24
gather rounds per query of the sorted-array binary search this
replaced; inserts happen inside the same probe walk via a scatter-min
claim round, so there is no per-chunk sort and no per-level key merge
at all.  Each level journals its inserted slots; a level abandoned for
buffer overflow rolls the table back by clearing exactly those slots
(safe: a cleared cohort postdates every surviving key, so it cannot
sit on a surviving key's probe path — see _probe_insert).

Capacity model: the table (VCAP slots, power of two) and the per-level
buffer (LCAP states) are fixed-shape device arrays; when a level
outgrows LCAP (or the table's load bound trips) the engine grows the
cap, recompiles (one extra jit cache entry per growth), rolls back and
replays the level from the intact frontier.  The table grows by
rehashing into a larger table on device.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..config import ModelConfig
from ..obs import NULL_OBS
from ..obs.metrics import CHECK_COUNTER_KEYS, DEDUP_COUNTER_KEYS
from ..ops.codec import C_OVERFLOW
from ..spec import spec_of
from ..utils import HOME_SALT
from ..resil.chaos import chaos_point
from ..utils import cat_arrays as _cat
from ..utils import fmix32_int as _fmix32_int
from ..utils import fp_key
from ..utils import take_arrays as _take
from . import driver, pack
from .expand import Expander
from .fingerprint import Fingerprinter, fmix32

U32MAX = jnp.uint32(0xFFFFFFFF)

# historical name; the canonical definition lives in utils.HOME_SALT
# (shared with the host-partition images — see utils docstring)
_HOME_SALT = HOME_SALT


class CheckpointError(ValueError):
    """Checkpoint missing, malformed, or written by an incompatible
    engine version/config.  The CLI catches exactly this for its
    'cannot resume' message; unrelated mid-run ValueErrors propagate."""

@dataclass
class Violation:
    invariant: str
    state_id: int
    # the spec oracle's (state, hist) pair — raft State/Hist or the
    # paxos twins, depending on the engine's SpecIR
    state: Optional[object] = None
    hist: Optional[object] = None
    trace: Optional[List[str]] = None


class CheckResult:
    """Run result whose scalar counters live in ONE
    ``obs.metrics.MetricsRegistry`` (``self.metrics``); the named
    attributes below are write-through views, so a harvest loop
    mutating ``res.levels_fused`` IS updating the registry — the
    ledger, ``--stats-json`` and checkpoint meta all read the same
    store and cannot drift apart per consumer (the PR-5
    ``levels_fused`` bug class).

    Counter notes (the registry keys, obs.metrics.CHECK_COUNTER_KEYS):

    - ``violations_global`` — every violation the run found, counted
      from the per-level scalars (replicated under the pjit engine, so
      every controller reads the same count).
    - ``levels_fused`` / ``burst_dispatches`` / ``burst_bailouts`` —
      fused-dispatch telemetry (the multi-level burst fast path):
      levels committed inside bursts, burst device calls (each is
      exactly one host round trip, whether it committed levels or
      not), and calls that ended in a bail back to the per-level path
      (a call can both commit levels AND bail) — bench/progress lines
      read these to prove the burst engaged instead of silently
      bailing every level.
    - ``pin_interior_states`` — punctuated search from cfg prefix pins
      seeds BFS at the witness END state (models/golden docstring);
      TLC also counts the prefix interior states.  This is the number
      of distinct interior states the engine invariant-checked but did
      NOT count — the upper bound on the distinct_states divergence
      from TLC for pinned cfgs.
    - ``dedup_walk_iters`` / ``dedup_probe_steps`` / ``dedup_rounds`` /
      ``dedup_claim_losses`` — the dedup claim walk's work on the
      device (obs.metrics.DEDUP_COUNTER_KEYS), summed over every
      finalize (overflow replays included) and every committed burst
      level.  Integer work of a deterministic program: a check's counts
      are the same on every run and every backend.

    ``lanes_enabled`` (a dict beside the registry: its keys are the
    spec's action families) — each family's enabled lanes over the
    check's committed levels, a replayed level counted once.  Without
    action constraints its sum is ``generated_states`` less the roots.

    ``harvest_transfers`` (beside the registry: a count of this check
    alone, never checkpointed) — the device-to-host reads of level rows
    the check's harvests made: one packed read (engine/pack) per
    harvested level or burst that needed rows (one per block of a level
    wider than ``Engine._PACK_BYTES``), none where states are not
    stored and no invariant broke.
    """

    # the ONE canonical key tuple lives in obs.metrics — aliasing it
    # (not copying) is what makes a future counter addition a
    # single-site change
    _COUNTERS = CHECK_COUNTER_KEYS

    def __init__(self, distinct_states: int = 0,
                 generated_states: int = 0, depth: int = 0,
                 violations: Optional[List[Violation]] = None,
                 level_sizes: Optional[List[int]] = None,
                 seconds: float = 0.0, overflow_faults: int = 0,
                 phase_seconds: Optional[Dict[str, float]] = None,
                 violations_global: int = 0, levels_fused: int = 0,
                 burst_dispatches: int = 0, burst_bailouts: int = 0,
                 pin_interior_states: int = 0, guard_matmul: int = 0,
                 delta_matmul: int = 0,
                 sym_canon: int = 0, dedup_walk_iters: int = 0,
                 dedup_probe_steps: int = 0, dedup_rounds: int = 0,
                 dedup_claim_losses: int = 0):
        from ..obs.metrics import MetricsRegistry
        init = locals()
        self.metrics = MetricsRegistry()
        for nm in self._COUNTERS:
            self.metrics.register(nm, int(init[nm]))
        self.violations: List[Violation] = list(violations or [])
        self.level_sizes: List[int] = list(level_sizes or [])
        self.lanes_enabled: Dict[str, int] = {}
        self.harvest_transfers = 0
        self.seconds = float(seconds)
        self.phase_seconds: Dict[str, float] = dict(phase_seconds or {})

    def __repr__(self):
        body = ", ".join(f"{k}={v}"
                         for k, v in self.metrics.as_dict().items())
        return (f"CheckResult({body}, seconds={self.seconds:.3f}, "
                f"violations={len(self.violations)})")

    @property
    def states_per_sec(self):
        return self.distinct_states / max(self.seconds, 1e-9)

    @property
    def dedup_hit_rate(self):
        """Fraction of generated successors that were duplicates —
        TLC's 'distinct vs generated' engine metric (SURVEY §5)."""
        return 1.0 - self.distinct_states / max(self.generated_states, 1)


def _metric_view(nm: str) -> property:
    return property(lambda self: self.metrics.get(nm),
                    lambda self, v: self.metrics.set(nm, int(v)))


for _nm in CheckResult._COUNTERS:
    setattr(CheckResult, _nm, _metric_view(_nm))


def _add_dedup(res: CheckResult, counts) -> None:
    """Add one dedup count vector (DEDUP_COUNTER_KEYS order, as the
    device packs it) to a result's counters."""
    for nm, v in zip(DEDUP_COUNTER_KEYS, counts):
        res.metrics.inc(nm, int(v))


def _dedup_counts(res: CheckResult) -> Dict[str, int]:
    """A result's dedup counters by name (DEDUP_COUNTER_KEYS)."""
    return {k: res.metrics.get(k) for k in DEDUP_COUNTER_KEYS}


def _add_lanes(res: CheckResult, names, counts) -> None:
    """Add one committed level's (or burst's) enabled lanes per family."""
    for nm, v in zip(names, counts):
        res.lanes_enabled[nm] = res.lanes_enabled.get(nm, 0) + int(v)


def _check_counters(res: CheckResult, delta_names) -> Dict[str, int]:
    """The counter sample a check records on its span recorder: the
    dedup counts, ``lanes_enabled.<Family>``, ``lanes_kernel_path``,
    the enabled lanes of the families whose successors the expander
    built with their kernels rather than the delta matmul
    (``delta_names``), and ``harvest_transfers``."""
    return {**_dedup_counts(res),
            **{f"lanes_enabled.{nm}": v
               for nm, v in res.lanes_enabled.items()},
            "lanes_kernel_path": sum(
                v for nm, v in res.lanes_enabled.items()
                if nm not in delta_names),
            "harvest_transfers": res.harvest_transfers}


def _ceil_log2(n: int) -> int:
    return max(1, int(np.ceil(np.log2(max(n, 2)))))


def _leaf_name(key_path) -> str:
    """Stable archive name for a carry pytree leaf (shared by
    checkpoint save and load — must stay in lockstep)."""
    return "carry|" + "|".join(
        str(getattr(p, "key", getattr(p, "idx", p))) for p in key_path)


# ---------------------------------------------------------------------------
# checkpoint serializer, shared by Engine and SpillEngine (TLC
# checkpoints to states/ — /root/reference/.gitignore:4; SURVEY §5).
# A checkpoint is the full BFS wavefront: {carry pytree leaves (by
# _leaf_name), level counters, result-so-far, and (when store_states)
# the parent/lane/state archives for trace reconstruction}.  Written at
# level boundaries, so a resumed run replays nothing and lands on
# bit-identical counts.  Engine-specific capacity fields ride in the
# meta dict the callers supply.
# ---------------------------------------------------------------------------

# Raft states written since the bag was kept in message order
# (ops/layout.py); a checkpoint without it holds bags in arrival order,
# which would walk differently from a fresh run and change the answer.
BAG_ORDER = 1


def ckpt_refuse_removed_format(path, meta) -> None:
    """Refuse a checkpoint of the removed shard_map mesh engines
    (single-host, spill-composed and multi-host): their per-device
    carry layout is read by no engine now, and its meta must not pass
    for the classic or spill format it resembles."""
    if meta.get("sharded"):
        raise CheckpointError(
            f"{path}: checkpoint of the removed shard_map mesh engine "
            "format (meta 'sharded'); no engine reads it — re-run "
            "without --resume")


def ckpt_check_bag_order(path, meta) -> None:
    """Refuse a Raft checkpoint written before BAG_ORDER."""
    if meta.get("spec", "raft") == "raft" and \
            meta.get("bag_order") != BAG_ORDER:
        raise CheckpointError(
            f"{path}: checkpoint written before message bags were kept "
            "in message order; its states would be walked in another "
            "order than a fresh run's — re-run without --resume")


_CKPT_BASE_KEYS = ("cfg", "chunk", "store_states", "n_levels",
                   "distinct", "generated", "depth", "level_sizes",
                   "faults", "viol_global", "n_states", "n_vis",
                   "n_front")


def ckpt_write(path, carry, store_states, parents, lanes, states, res,
               meta, keep: int = 1):
    """``keep`` > 1 keeps a last-K chain (path, path.1, ..) with the
    previous heads rotated down before the atomic publish; every
    member carries a sha256 sidecar (resil/ckpt_chain) so a torn or
    corrupt head is detected BEFORE any array is read and resume
    falls back to the newest valid predecessor."""
    import json
    import os
    data = {}
    for kp, leaf in jax.tree_util.tree_flatten_with_path(carry)[0]:
        data[_leaf_name(kp)] = np.asarray(leaf)
    if store_states:
        for i, arr in enumerate(parents):
            data[f"parents|{i}"] = arr
        for i, arr in enumerate(lanes):
            data[f"lanes|{i}"] = arr
        for i, blk in enumerate(states):
            for k, v in blk.items():
                data[f"states|{i}|{k}"] = v
    data["viol_names"] = np.array([v.invariant for v in res.violations])
    data["viol_ids"] = np.array([v.state_id for v in res.violations],
                                dtype=np.int64)
    base = dict(distinct=res.distinct_states,
                generated=res.generated_states,
                faults=res.overflow_faults,
                level_sizes=res.level_sizes,
                viol_global=res.violations_global,
                pin_interior=res.pin_interior_states,
                levels_fused=res.levels_fused,
                burst_dispatches=res.burst_dispatches,
                burst_bailouts=res.burst_bailouts,
                **_dedup_counts(res),
                lanes_enabled=res.lanes_enabled,
                n_levels=len(parents), store_states=store_states,
                bag_order=BAG_ORDER)
    data["meta"] = np.array(json.dumps({**base, **meta}))
    tmp = path + ".tmp.npz"           # .npz suffix: savez won't append
    np.savez(tmp, **data)
    # rotate + publish + checksum sidecar (+ the ckpt_torn/ckpt_corrupt
    # chaos sites, applied to the fresh head only)
    from ..resil.ckpt_chain import publish
    publish(tmp, path, keep=keep)


def ckpt_read(path, cfg_repr, chunk, extra_keys, spill=False,
              expected_format=None, spec_name=None, sym_canon=None):
    """np.load + the meta validation every engine shares.  Returns
    (npz, meta) or raises CheckpointError.

    expected_format — optional (meta_key, want_value, why) triple: the
    engine's checkpoint-format gate, checked here so every engine
    versions its files one way (meta lacking the key reads as format 1
    — the pre-versioning era).

    spec_name — the resuming engine's SpecIR name: resume refuses on a
    spec mismatch (same pattern as the config-mismatch refusal below;
    meta lacking the key reads as "raft" — every pre-IR checkpoint is
    a Raft one).

    sym_canon — the resuming engine's RESOLVED canonicalization mode
    ("sort" | "minperm"): the visited table stores fingerprint VALUES,
    and orbit-sort values are a bijective remix of min-over-perms
    values (fingerprint._core_sort), so resuming across modes would
    silently re-visit every known state.  Refused by name; meta
    lacking the key reads as "minperm" — every round-14 checkpoint
    predates the sort path.

    Integrity (round 12, resil/ckpt_chain): the file's sha256 sidecar
    is verified BEFORE any array is touched — a truncated or corrupt
    file is a clear named condition, never a numpy/zipfile traceback —
    and a bad head falls back (with a ChainWarning) to the newest
    valid predecessor in the last-K chain ``path, path.1, ...``."""
    import json
    from ..resil.ckpt_chain import (IntegrityError, load_engine_npz,
                                    open_validated)
    # payload-integrity validation before ANY meta compare: the digest
    # check runs first; the structural loader catches legacy
    # no-sidecar files whose zip container or meta record is torn
    try:
        z, path = open_validated(path, load_engine_npz)
    except IntegrityError as e:
        raise CheckpointError(str(e)) from e
    meta = json.loads(str(z["meta"]))
    if spec_name is not None:
        got_spec = meta.get("spec", "raft")
        if got_spec != spec_name:
            raise CheckpointError(
                f"{path}: checkpoint was written for spec "
                f"{got_spec!r}; engine is running spec {spec_name!r} "
                f"— resume with --spec {got_spec}")
    if sym_canon is not None:
        got_mode = meta.get("sym_canon", "minperm")
        if got_mode != sym_canon:
            raise CheckpointError(
                f"{path}: checkpoint fingerprints were canonicalized "
                f"with --sym-canon {got_mode}; engine resolved "
                f"{sym_canon} — fingerprint values are mode-specific "
                f"(the visited table would miss every known state) — "
                f"resume with --sym-canon {got_mode}")
    ckpt_refuse_removed_format(path, meta)
    if bool(meta.get("spill")) != spill:
        raise CheckpointError(
            f"{path}: host-spill checkpoint — resume it with "
            "SpillEngine" if meta.get("spill")
            else f"{path}: not a SpillEngine checkpoint — resume it "
            "with the engine that wrote it")
    if expected_format is not None:
        fkey, want, why = expected_format
        got = meta.get(fkey, 1)
        if got != want:
            raise CheckpointError(
                f"{path}: checkpoint format {got!r} != {want} ({why}) "
                "— re-run without --resume")
    for key in _CKPT_BASE_KEYS + tuple(extra_keys):
        if key not in meta:
            raise CheckpointError(
                f"{path}: checkpoint written by an older engine "
                f"version (meta lacks {key!r}) — re-run without "
                "--resume")
    ckpt_check_bag_order(path, meta)
    if meta["cfg"] != cfg_repr:
        raise CheckpointError(
            "checkpoint was written for a different model config:\n"
            f"  checkpoint: {meta['cfg']}\n"
            f"  engine:     {cfg_repr}")
    if meta["chunk"] != chunk:
        raise CheckpointError(
            f"checkpoint was written with chunk={meta['chunk']}; "
            f"resume with the same chunk (engine has {chunk} — "
            "capacities are rounded to the chunk size)")
    return z, meta


def ckpt_carry(path, z, template):
    """Rebuild the carry pytree (device arrays) from archived
    leaves."""
    leaves, _ = jax.tree_util.tree_flatten_with_path(template)
    missing = [_leaf_name(kp) for kp, _ in leaves
               if _leaf_name(kp) not in z]
    if missing:
        raise CheckpointError(
            f"{path}: checkpoint carry layout is from an "
            f"incompatible engine version (missing {missing[:3]}"
            f"{'…' if len(missing) > 3 else ''}) — re-run without "
            "--resume")
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(template),
        [jnp.asarray(z[_leaf_name(kp)]) for kp, _ in leaves])


def ckpt_archives(z, meta, template, store_states):
    """(parents, lanes, states) trace archives; empty when the store is
    off."""
    if store_states and not meta["store_states"]:
        raise CheckpointError(
            "checkpoint was written with store_states=False; "
            "resume with store_states=False (CLI: --no-store) — "
            "trace archives cannot be reconstructed")
    if not (store_states and meta["store_states"]):
        return [], [], []
    parents = [z[f"parents|{i}"] for i in range(meta["n_levels"])]
    lanes = [z[f"lanes|{i}"] for i in range(meta["n_levels"])]
    keys = list(template["lvl"].keys())
    states = [{k: z[f"states|{i}|{k}"] for k in keys}
              for i in range(meta["n_levels"])]
    return parents, lanes, states


def ckpt_result(z, meta) -> "CheckResult":
    res = CheckResult(
        distinct_states=meta["distinct"],
        generated_states=meta["generated"], depth=meta["depth"],
        level_sizes=list(meta["level_sizes"]),
        overflow_faults=meta["faults"],
        violations_global=meta["viol_global"],
        # .get: round-3 checkpoints predate the field
        pin_interior_states=meta.get("pin_interior", 0),
        # .get: round-7 checkpoints predate the burst telemetry — a
        # resumed run's stats must stay cumulative, like every other
        # counter here
        levels_fused=meta.get("levels_fused", 0),
        burst_dispatches=meta.get("burst_dispatches", 0),
        burst_bailouts=meta.get("burst_bailouts", 0),
        **{k: meta.get(k, 0) for k in DEDUP_COUNTER_KEYS})
    res.lanes_enabled = dict(meta.get("lanes_enabled", {}))
    for nm, sid in zip(z["viol_names"], z["viol_ids"]):
        res.violations.append(Violation(str(nm), int(sid)))
    return res


class Engine:
    """One compiled checker instance per (ModelConfig, chunk size).

    chunk    — frontier states expanded per fused device call.
    lcap     — initial per-level buffer capacity (states); doubles on
               overflow (the level is replayed from the intact frontier).
    vcap     — initial visited-set capacity (fingerprint keys).
    """

    def __init__(self, cfg: ModelConfig, chunk: int = 512,
                 store_states: bool = True,
                 lcap: int = 1 << 14, vcap: int = 1 << 17,
                 fcap: Optional[int] = None,
                 ocap: Optional[int] = None,
                 incremental_fp: bool = True,
                 burst: bool = True,
                 burst_levels: Optional[int] = None,
                 archive_dir: Optional[str] = None,
                 guard_matmul: bool = True,
                 delta_matmul: bool = True,
                 delta_chunk_skip: Optional[bool] = None,
                 fam_density: Optional[Dict[str, int]] = None,
                 sym_canon: str = "auto"):
        self.cfg = cfg
        # the active spec's compiled operator surface (SpecIR): layout,
        # codec, kernels, families, predicates, fingerprints, oracle —
        # every model-specific hook below routes through this handle
        self.ir = spec_of(cfg)
        # observability bundle (obs/): check() rebinds it per run; the
        # archive/checkpoint helpers read it so their spans land on the
        # active run's timeline
        self._obs = NULL_OBS
        self.chunk = max(16, int(chunk))
        self.store_states = store_states
        # disk-backed per-level trace archives (engine/archive): with
        # store_states, parents/lanes/state rows stream to memmap'd
        # files under this run directory instead of growing host
        # arrays, so trace reconstruction is RAM-bounded.  None keeps
        # the historical in-RAM archive.
        self.archive_dir = archive_dir
        self._arch = None
        self._states: List[Dict[str, np.ndarray]] = []
        self._parents: List[np.ndarray] = []
        self._lanes: List[np.ndarray] = []
        # incremental per-action fingerprints (auto-off for big
        # symmetry groups — fingerprint.supports_incremental)
        self.incremental_fp = incremental_fp
        self.lay = self.ir.make_layout(cfg)
        self.kern = self.ir.make_kernels(self.lay)
        # MXU-native expansion (guard grid as int8 matmul + one-hot
        # einsum selection — expand.Expander docstring): default ON,
        # bit-exact by construction; guard_matmul=False restores the
        # historical vmapped-sweep program exactly
        self.guard_matmul = bool(guard_matmul)
        # delta-matmul successor generation (expand.Expander docstring):
        # families with declared delta algebras apply as ONE batched
        # scatter-as-matmul per family group; default ON, bit-exact by
        # construction, delta_matmul=False restores the per-family
        # kernel path for every family
        self.delta_matmul = bool(delta_matmul)
        # delta_chunk_skip: per-family lax.cond blocks that skip a
        # family's whole delta-group slice when a chunk enables none of
        # its lanes (None = follow the backend default: ON under the
        # TPU MXU lowering, OFF under the CPU scatter-add — see the
        # Expander docstring; bit-exact either way)
        self.expander = Expander(cfg, guard_matmul=self.guard_matmul,
                                 delta_matmul=self.delta_matmul,
                                 delta_chunk_skip=delta_chunk_skip)
        # symmetry canonicalization mode (fingerprint.resolve_sym_canon):
        # "sort" hashes ONE argsorted canonical relabeling per state,
        # "minperm" keeps the historical P-fold min-over-perms; "auto"
        # picks sort past 6 perms.  Fingerprint VALUES are mode-specific
        # (checkpoints refuse cross-mode resume) but the induced state
        # partition is identical — bench._canon_ab pins the A/B.
        self.fpr = Fingerprinter(cfg, sym_canon=sym_canon)
        self.preds = self.ir.make_predicates(self.lay)
        self.inv_names = list(cfg.invariants)
        self.con_names = list(cfg.constraints)
        self.act_names = list(cfg.action_constraints)
        self.labels = self.expander.lane_labels()
        self.A = self.expander.n_lanes
        self.W = self.fpr.n_streams           # u32 words per dedup key
        # capacities (LCAP always a multiple of chunk).  FCAP bounds the
        # fresh-per-chunk compaction buffer; LCAP reserves an FCAP-sized
        # append margin (usable level capacity is LCAP - FCAP).
        # FCAP: measured enabled-lane density on the metric config is
        # ~4 lanes/state on the widest levels but spikes past 8/state
        # on mid-depth chunks; chunk*16 avoids the fovf growth path
        # and its mid-run step recompile
        self.FCAP = int(fcap) if fcap else min(
            self.chunk * self.A, max(self.chunk * 16, 1 << 13))
        # OCAP bounds the POST-DEDUP fresh-row buffer: phase2 +
        # narrow + level append run at this width, not FCAP.  Fresh
        # rows per chunk are enabled * (1 - dedup hit rate) — typically
        # ~1-4x chunk where enabled can exceed 20x chunk on the
        # membership config, so the second compaction cuts the
        # append-side work ~8x (measured 17+21 ms -> 8+11 ms per chunk
        # at FCAP=2^16 vs 2^13).  A chunk whose fresh count exceeds
        # OCAP trips oovf and the level replays with OCAP grown (same
        # discipline as FCAP/fam caps).
        self.OCAP = self._round_cap(min(self.FCAP, int(ocap) if ocap
                                        else max(4 * self.chunk,
                                                 1 << 11)))
        self.LCAP = self._round_cap(
            max(lcap, 4 * self.chunk, 4 * self.FCAP))
        # open-addressing table: power-of-two capacity (mask indexing)
        self.VCAP = 1 << _ceil_log2(int(vcap))
        if self.VCAP != int(vcap):
            import warnings
            warnings.warn(
                f"vcap {vcap} rounded up to the next power of two "
                f"({self.VCAP}) for mask indexing — the visited table "
                f"allocates {self.VCAP * self.W * 4} bytes",
                stacklevel=2)
        # per-family materialization caps (guard-first expansion);
        # static jit args so growth retraces the step.  fam_density
        # overrides the measured per-family densities (validated —
        # a bad entry raises here, not as a jit traceback)
        self.fam_density = dict(fam_density or {})
        self.FAM_CAPS = tuple(self.expander.default_fam_caps(
            self.chunk, self.fam_density))
        self._rehash_cache = {}
        # jitted carry builders: the empty carry per (LCAP, VCAP, FCAP,
        # OCAP), and the fresh-start set-up program per capacity set and
        # root count (_fresh_carry, _setup_carry).  _carry_sh is
        # the carry's sharding tree where an engine has one
        # (parallel/pjit_mesh): both programs' outputs are born under it
        self._fresh_jit_cache = {}
        self._setup_jit_cache = {}
        self._carry_sh = None
        # the (LCAP, VCAP, FCAP, OCAP, FAM_CAPS) the per-level
        # executables were last prewarmed at (check()'s prewarm)
        self._warm_caps = None
        self._phase1 = jax.jit(self._phase1_impl)
        self._phase2 = jax.jit(self._phase2_impl)
        # runtime-bounds twin (traced only by the padded-ceiling
        # serving path — solo checks never touch it)
        self._phase2_rt = jax.jit(self._phase2_rt_impl)
        # NOTE: a multi-chunk dispatch (K chunk steps per device call
        # via fori_loop) was tried and measured slower on an older
        # v5e setup (not measured on the current code): XLA copies the
        # loop-carried level/table buffers at the loop boundary
        # instead of aliasing them, which outweighed the per-dispatch
        # cost it saves.
        self._step_jit = jax.jit(self._chunk_step_impl, donate_argnums=0,
                                 static_argnums=1)
        self._fin_jit = jax.jit(self._finalize_impl, donate_argnums=0)
        self._rootfp_jit = jax.jit(self.fpr.fingerprint_batch)
        # small-level burst (see _burst_core): on by default; burst=False
        # restores the pure per-level driver (the A/B is pinned by
        # tests/test_burst.py).  burst_levels caps the levels fused per
        # device call; the ring width is _burst_chunks frontier chunks.
        self.burst = burst
        if burst_levels is not None and int(burst_levels) <= 0:
            raise ValueError(
                f"burst_levels must be positive, got {burst_levels} "
                "(use burst=False to disable the fused-level path)")
        self.burst_levels = (int(burst_levels) if burst_levels
                             else self._BURST_LEVELS)
        self._burst_jit = jax.jit(self._burst_impl, donate_argnums=0,
                                  static_argnums=1)
        # job-axis batched burst (serve/batch) — built lazily by
        # burst_batched_fn, so solo checks never trace it
        self._bat_jit = None
        # checkpoint-chain depth (resil/ckpt_chain): keep the last K
        # checkpoints (path, path.1, ...) so a torn head falls back to
        # a valid predecessor; 1 restores the historical single file.
        # An attribute (not a ctor kwarg) so all four engine families
        # inherit it and the CLI sets it in one place (--ckpt-keep).
        self.ckpt_keep = 2

    def _round_cap(self, n: int) -> int:
        c = self.chunk
        return ((int(n) + c - 1) // c) * c

    def _fetch(self, x) -> np.ndarray:
        """Device array -> host numpy, engine-overridable: the harvest
        paths route every device read through here so an engine whose
        state lives under multi-host shardings (parallel/pjit_mesh) can
        gather to a replicated (every-controller-addressable) array
        first.  The base engines' arrays are process-local already."""
        return np.asarray(x)

    # the most bytes one harvest read packs.  A pack's output and its
    # program's code (resident while its bucket is cached, and growing
    # with its rows) are held on the chip where the per-leaf reads held
    # one leaf; at config #4's level 10 one 6.4 MB read raised the
    # check's device peak 1.8% (TPU v5e), so a wider level reads in
    # blocks of this size.
    _PACK_BYTES = 2 << 20

    def _fetch_rows(self, res, leaves, n_rows: int,
                    levels: Optional[int] = None) -> List[np.ndarray]:
        """The leaves' first ``n_rows`` rows (last axis; with ``levels``
        the first ``levels`` ring levels too) on the host, batch-last:
        ONE packed transfer (engine/pack) whose rows round up to a
        bucket (``pack.row_bucket``), or for a level of more than
        ``_PACK_BYTES`` one per block of that size."""
        cap = leaves[0].shape[-1]
        block = self._pack_block(leaves)
        if levels is not None or n_rows <= block:
            return self._read_pack(
                res, leaves, 0, pack.row_bucket(n_rows, self.chunk, cap),
                levels)
        parts = []
        for s in range(0, n_rows, block):
            at = min(s, cap - block)          # the block stays in bounds
            got = self._read_pack(res, leaves, at, block)
            parts.append([g[..., s - at:min(n_rows, s + block) - at]
                          for g in got])
        return [np.concatenate(p, axis=-1) for p in zip(*parts)]

    def _pack_block(self, leaves) -> int:
        """Rows of these batch-last leaves one read packs at most: whole
        chunks within ``_PACK_BYTES``, at least one, at most them all."""
        row_bytes = sum(x.dtype.itemsize * int(np.prod(x.shape[:-1]))
                        for x in leaves)
        return min(leaves[0].shape[-1],
                   max(1, self._PACK_BYTES // row_bytes // self.chunk)
                   * self.chunk)

    def _read_pack(self, res, leaves, start: int, rows: int,
                   levels: Optional[int] = None) -> List[np.ndarray]:
        """One packed transfer, read through ``_fetch`` and counted in
        ``res.harvest_transfers``."""
        buf = pack.pack(list(leaves), np.int32(start), rows=rows,
                        levels=levels)
        buf.copy_to_host_async()
        host = self._fetch(buf)
        del buf                   # the device copy goes with its bytes
        res.harvest_transfers += 1
        return pack.unpack(host, pack.layout(leaves, rows, levels), rows)

    # ------------------------------------------------------------------
    # phase 1: expand + action constraints + fingerprint (also used by
    # the driver entry point)
    # ------------------------------------------------------------------

    def _act_ok(self, parent_sv, cand_sv):
        """ACTION_CONSTRAINTS (TLC semantics): evaluated on the
        (unprimed, primed) pair; violating transitions are not taken.
        The name registry is part of the spec surface
        (preds.action_fn) — an unknown name errors naming the spec."""
        ok = jnp.bool_(True)
        for nm in self.act_names:
            ok = ok & self.preds.action_fn(nm)(parent_sv, cand_sv)
        return ok

    def _phase1_impl(self, svb):
        ok, cand = self.expander._expand_impl(svb)          # [B,A], [B,A,…]

        def per_state(parent, cand_row, ok_row):
            def per_lane(c, o):
                fp = self.fpr.fingerprint(c)
                act = self._act_ok(parent, c)
                return fp, act
            return jax.vmap(per_lane)(cand_row, ok_row)

        fp, act = jax.vmap(per_state)(svb, cand, ok)
        return ok & act, cand, fp

    def _phase2_one(self, sv, rtb=None):
        der = self.kern.derived(sv)
        inv = jnp.stack([self.preds.invariant_fn(nm)(sv, der)
                         for nm in self.inv_names]) \
            if self.inv_names else jnp.ones((0,), bool)
        con = jnp.bool_(True)
        for nm in self.con_names:
            con = con & self.preds.constraint_fn(nm)(sv, der, rtb)
        return inv, con

    def _phase2_impl(self, svb):
        """Batch-major ([B, ...]) public API: inv [B, n_inv], con [B]."""
        inv, con = self._phase2_T(
            {k: jnp.moveaxis(v, 0, -1) for k, v in svb.items()})
        return jnp.moveaxis(inv, -1, 0), con

    def _phase2_rt_impl(self, svb, rtb):
        """Batch-major twin taking a runtime-bounds vector (the padded-
        ceiling serving path's root admission — serve/batch._admit)."""
        inv, con = self._phase2_T(
            {k: jnp.moveaxis(v, 0, -1) for k, v in svb.items()}, rtb)
        return jnp.moveaxis(inv, -1, 0), con

    def _phase2_T(self, svT, rtb=None):
        """Batch-LAST hot-path twin: inv [n_inv, B], con [B] (rows
        vmapped at -1 — the tiny per-state minor dims waste TPU vector
        tiles batch-major, expand.materialize docstring).  ``rtb`` is
        an optional per-JOB runtime search-bounds vector
        (ops/vpredicates.runtime_bounds): constant across the state
        batch, so it broadcasts (in_axes=None) — under the serving
        layer's job-axis vmap it varies per job."""
        return jax.vmap(self._phase2_one, in_axes=(-1, None),
                        out_axes=-1)(svT, rtb)

    # ------------------------------------------------------------------
    # device-resident dedup primitives
    # ------------------------------------------------------------------

    # ------------------------------------------------------------------
    # device-resident open-addressing visited table.  Empty slot =
    # all-ones key (an all-ones fingerprint aliases "empty" with
    # probability 2^-64 — the same accepted-risk class as TLC's
    # fingerprint collisions; fp128 shrinks it to 2^-128).
    # ------------------------------------------------------------------

    _MAX_PROBE_ROUNDS = 4096
    _LOAD_MAX = 0.40

    def _home(self, keys, vcap: int):
        h = jnp.full(keys[0].shape, _HOME_SALT, jnp.uint32)
        for w in range(self.W):
            h = fmix32(h ^ keys[w])
        return (h & jnp.uint32(vcap - 1)).astype(jnp.int32)

    def _probe_insert(self, table, claims, keys, live, ranks,
                      counts=False):
        """Parallel claim-insert of `keys` (W × u32[M]; lanes with
        live=False are ignored) into the open-addressing `table`
        (W × u32[VCAP]; `claims` u32[VCAP] all-U32MAX between calls).
        Returns (table', claims', fresh, pos, hovf): fresh marks lanes
        whose key was NOT already present and won its slot; pos is each
        lane's final table slot.  ``counts=True`` appends int32[4], the
        call's work in obs.metrics.DEDUP_COUNTER_KEYS order: inner walk
        iterations summed over outer rounds (each one vector-wide gather
        round), probe advances summed over lanes, outer rounds, and
        lanes that claimed an empty slot and lost it to a lower rank.

        Two-phase structure, shaped by TPU op costs (scatters are an
        order of magnitude slower than gathers at these widths):

        - WALK (inner while_loop, gathers only): every active lane
          quadratic-probes (pos += ++t, full-cycle for power-of-2
          capacity) until its current slot holds its key (duplicate)
          or is empty (insertion candidate).
        - RESOLVE (one scatter round per outer iteration): insertion
          candidates claim their empty slot by scatter-min of the lane
          rank (first-seen tie-break = the oracle's enumeration order,
          since ranks ascend in candidate order); winners scatter
          their keys into the table; claims are reset by a scatter of
          the sentinel.  Losers — and duplicates of a key that just
          won — stay active and re-walk from their current position in
          the next outer iteration (equal keys walk identical probe
          paths, so a duplicate always finds its winner).

        The outer loop runs until every lane resolves: `dedup_rounds`
        per call read 2.67 on config #4's whole space (104 rounds over
        39 chunk steps) and 2.83 on config #2 to depth 17 (331 over
        117), each round 2 + W scatters, versus one such round per
        probe *step* in the naive formulation.  `hovf` reports a
        blown round budget (table too full — caller grows, rehashes,
        replays the level).

        Rollback safety (used by _finalize_impl's abandon): every slot
        on an inserted key's probe path was occupied by an *earlier*
        insert at walk time, so clearing a whole trailing cohort of
        inserts can never punch an empty hole into a surviving key's
        path — lookups after rollback still terminate correctly.
        """
        VCAP = table[0].shape[0]
        M = keys[0].shape[0]
        pos0 = self._home(keys, VCAP)

        def classify(table, pos):
            cur = [table[w][pos] for w in range(self.W)]
            iskey = jnp.ones((M,), bool)
            isempty = jnp.ones((M,), bool)
            for w in range(self.W):
                iskey &= cur[w] == keys[w]
                isempty &= cur[w] == U32MAX
            return iskey, isempty

        def outer_cond(st):
            active, rounds = st[4], st[6]
            return active.any() & (rounds < self._MAX_PROBE_ROUNDS)

        def outer_body(st):
            table, claims, pos, t, active, fresh, rounds, walks, lost = st

            # ---- walk: gathers only, no table writes ----
            def walk_cond(ws):
                _p, _t, moving, steps = ws
                return moving.any() & (steps < self._MAX_PROBE_ROUNDS)

            def walk_body(ws):
                pos, t, moving, steps = ws
                iskey, isempty = classify(table, pos)
                adv = moving & ~(iskey | isempty)
                t = jnp.where(adv, t + 1, t)
                pos = jnp.where(adv, (pos + t) & (VCAP - 1), pos)
                return pos, t, adv, steps + 1

            pos, t, _moving, n_walk = lax.while_loop(
                walk_cond, walk_body, (pos, t, active, jnp.int32(0)))
            iskey, isempty = classify(table, pos)
            active = active & ~iskey               # duplicate: lane dies

            # ---- resolve: one claim/insert/reset scatter round ----
            claimers = active & isempty
            cidx = jnp.where(claimers, pos, VCAP)
            claims = claims.at[cidx].min(ranks, mode="drop")
            won = claimers & (claims[pos] == ranks)
            widx = jnp.where(won, pos, VCAP)
            table = tuple(table[w].at[widx].set(keys[w], mode="drop")
                          for w in range(self.W))
            claims = claims.at[cidx].set(U32MAX, mode="drop")
            fresh = fresh | won
            active = active & ~won
            lost = lost + (claimers & ~won).sum(dtype=jnp.int32)
            return (table, claims, pos, t, active, fresh, rounds + 1,
                    walks + n_walk, lost)

        zero = jnp.int32(0)
        state0 = (table, claims, pos0, jnp.zeros((M,), jnp.int32),
                  live, jnp.zeros((M,), bool), zero, zero, zero)
        table, claims, pos, t, active, fresh, rounds, walks, lost = \
            lax.while_loop(outer_cond, outer_body, state0)
        out = (table, claims, fresh, pos, active.any())
        if not counts:
            return out
        # t only advances on live lanes: its sum is the probe steps
        return out + (jnp.stack([walks, t.sum(dtype=jnp.int32), rounds,
                                 lost]),)

    def _host_probe_assign(self, keys: np.ndarray,
                           vcap: Optional[int] = None) -> np.ndarray:
        """Sequential host twin of _probe_insert against an EMPTY table
        (root/punctuated-seed placement): same home hash and quadratic
        advance, so the device continues the table consistently.  keys
        are pre-deduped [N, W] u32."""
        vcap = vcap if vcap is not None else self.VCAP
        occupied = set()
        out = np.zeros(len(keys), np.int32)
        for i, kw in enumerate(keys):
            h = _HOME_SALT
            for w in range(self.W):
                h = _fmix32_int(h ^ int(kw[w]))
            pos, t = h & (vcap - 1), 0
            while pos in occupied:
                t += 1
                pos = (pos + t) & (vcap - 1)
            occupied.add(pos)
            out[i] = pos
        return out

    def _rehash_fn(self, old_vcap: int, new_vcap: int):
        """The jitted rehash program for one (old, new) capacity pair:
        table -> (new table, new claims, hv)."""
        fn = self._rehash_cache.get((old_vcap, new_vcap))
        if fn is None:
            def impl(table):
                allones = jnp.ones((old_vcap,), bool)
                for w in range(self.W):
                    allones &= table[w] == U32MAX
                new = tuple(jnp.full((new_vcap,), U32MAX)
                            for _ in range(self.W))
                ncl = jnp.full((new_vcap,), U32MAX)
                ranks = jnp.arange(old_vcap, dtype=jnp.uint32)
                new, ncl, _fresh, _pos, hv = self._probe_insert(
                    new, ncl, table, ~allones, ranks)
                return new, ncl, hv
            fn = self._rehash_cache[(old_vcap, new_vcap)] = jax.jit(impl)
        return fn

    def _rehash_tables(self, table, new_vcap: int):
        """Grow the visited table: device-side rehash of every occupied
        slot into a fresh table (and fresh claims array) of `new_vcap`
        slots (one jit cache entry per (old, new) capacity pair)."""
        new, ncl, hv = self._rehash_fn(table[0].shape[0], new_vcap)(table)
        if bool(np.asarray(hv)):
            raise RuntimeError("rehash did not converge — table "
                               "pathologically full; raise vcap")
        return new, ncl

    # ------------------------------------------------------------------
    # fused per-chunk step (ONE device call per frontier chunk)
    # ------------------------------------------------------------------

    def _expand_fp_chunk(self, sv, valid, fam_caps, FCAP, rt=None):
        """Shared front half of a chunk step (this engine's fused step
        and engine/spill's streamed step): guard-first expansion over
        the [B, A] lane grid, compaction of enabled lanes into the FCAP
        buffer, successor materialization, ACTION_CONSTRAINTS, and the
        symmetry-canonical fingerprint of the compacted candidates.

        Returns (cand_c [..., FCAP] batch-last, elive [FCAP], fp
        [W, FCAP], take [FCAP] flat lane ids, famx_chunk [n_fams]
        per-family enabled counts, n_e enabled total).  Callers fold
        famx/fovf into their carries.

        Fingerprints run INCREMENTALLY when the config supports it
        (fingerprint.py "Incremental per-action fingerprints"): one
        full per-term hash per PARENT, per-candidate deltas over the
        action family's touched positions — bit-identical to the
        direct path (tests/test_codec.py) at a fraction of the work on
        wide-expansion configs.

        ``rt`` — the per-job runtime-thresholds dict (guard thresholds
        + family lane mask as device data; expand.Expander docstring),
        None outside the padded-ceiling serving path."""
        B, A = valid.shape[0], self.A      # B from the caller's batch:
        # the level burst expands a whole (small) frontier as one chunk
        N = B * A
        derb = self.expander.derived_batch_T(sv)
        ok = lax.optimization_barrier(
            self.expander.guards_T(sv, derb, rt))
        okf = (ok & valid[:, None]).reshape(N)

        # compact enabled lanes into FCAP (ascending lane index =
        # the oracle's successor enumeration order)
        idx = jnp.arange(N, dtype=jnp.int32)
        epos = jnp.where(okf, jnp.cumsum(okf.astype(jnp.int32)) - 1,
                         FCAP)                           # OOB drops
        n_e = okf.sum(dtype=jnp.int32)
        incr = self.incremental_fp and self.fpr.supports_incremental()
        if incr:
            tables = lax.optimization_barrier(
                self.fpr.parent_tables(sv))
            cand_c, famx, fp = self.expander.materialize(
                sv, derb, okf, epos, FCAP, fam_caps,
                delta_fp=(self.fpr, tables))
        else:
            cand_c, famx = self.expander.materialize(
                sv, derb, okf, epos, FCAP, fam_caps)
        cand_c = lax.optimization_barrier(cand_c)        # [..., FCAP]
        elive = jnp.arange(FCAP, dtype=jnp.int32) < n_e
        eidx = lax.optimization_barrier(
            jnp.full((FCAP,), N, jnp.int32).at[epos].set(
                idx, mode="drop"))                       # slot -> lane
        take = jnp.clip(eidx, 0, N - 1)
        if self.act_names:
            # ACTION_CONSTRAINTS on the compacted (parent, successor)
            # pairs: violating transitions are killed before dedup
            par_c = {k: v[..., take // A] for k, v in sv.items()}
            act = jax.vmap(self._act_ok, in_axes=-1)(par_c, cand_c)
            elive = elive & act
        if not incr:
            # direct path: full min-over-perms hash per candidate
            fp = self.fpr.fingerprint_batch_T(cand_c)    # [W, FCAP]
        fp = lax.optimization_barrier(fp)
        return cand_c, elive, fp, take, famx, n_e

    def _chunk_step_impl(self, carry, fam_caps):
        """Expand frontier[base:base+chunk], fingerprint, dedup via the
        visited hash table (claim-insert: intra-chunk first-seen,
        cross-chunk and cross-level membership in ONE probe walk),
        evaluate invariants/constraints on the fresh rows, and append
        them to the level buffer.  Everything stays on device; `carry`
        is donated so buffers are reused.

        Shaped for the TPU's strengths (profiled on hardware):

        - enabled lanes are compacted to the FCAP buffer *before*
          fingerprinting, so the expensive min-over-perms hash runs on
          ~enabled candidates instead of the full B×A lane grid
          (typically ~10× fewer — the fingerprint dominated phase 1);
        - dedup is the open-addressing claim walk (_probe_insert):
          ~1-3 dependent gathers per candidate instead of the 60+
          binary-search gather rounds of the sorted-set design, and no
          sorts anywhere in the step;
        - the level write is gather + contiguous dynamic_update_slice
          instead of a full-width scatter (TPU scatters are an order of
          magnitude slower than gathers at these shapes);
        - invariants/constraints run here on the FCAP fresh rows, not
          on the LCAP-wide level buffer at finalize — total predicate
          work is O(distinct states), and finalize does no heavy work;
        - every phase boundary carries an optimization_barrier: without
          them XLA rematerializes the huge expansion graph into each
          consumer (measured 140ms/chunk vs ~20ms with barriers)."""
        B, A, W = self.chunk, self.A, self.W
        LCAP = carry["lpar"].shape[0]
        FCAP = carry["cidx"].shape[0]
        OCAP = carry["oidx"].shape[0]
        VCAP = carry["vis"][0].shape[0]
        N = B * A
        base = carry["base"]        # device-resident chunk cursor: a
        # host-passed scalar would cost a blocking host->device
        # transfer per chunk
        # Frontier rows are stored narrow (codec.narrow_dtypes) and
        # BATCH-LAST ([..., LCAP]): the tiny per-state dims (S, Lcap,
        # K) are far smaller than the TPU's (8, 128) vector tiles, so
        # keeping them off the lane axis is worth ~5x on the successor
        # materialization (expand.Expander.materialize docstring).
        sv = self.ir.widen({k: lax.dynamic_slice_in_dim(v, base, B,
                                                axis=v.ndim - 1)
                    for k, v in carry["front"].items()})
        fmask = lax.dynamic_slice_in_dim(carry["fmask"], base, B)
        # guard-first expansion + compaction + fingerprint: the shared
        # front half (_expand_fp_chunk).  fmask carries both the
        # live-row bound and the CONSTRAINT prune-not-expand mask
        # (SURVEY §2.8)
        valid = ((base + jnp.arange(B, dtype=jnp.int32)) <
                 carry["n_front"]) & fmask
        cand_c, elive, fp, take, famx_c, n_e = self._expand_fp_chunk(
            sv, valid, fam_caps, FCAP)
        famx = jnp.maximum(carry["famx"], famx_c)
        lanes = carry["lanes"] + famx_c
        fovf = carry["fovf"] | (n_e > FCAP) | \
            jnp.any(famx > jnp.asarray(fam_caps, jnp.int32))
        n_gen = carry["n_gen"] + elive.sum(dtype=jnp.int32)
        keys = tuple(jnp.where(elive, fp[w], U32MAX)
                     for w in range(W))
        # any overflow means this level replays — stop inserting so the
        # journal stays the exact record of this level's table writes
        gate = ~(carry["ovf"] | fovf | carry["hovf"] | carry["oovf"])
        ranks = jnp.arange(FCAP, dtype=jnp.uint32)
        table, claims, fresh, pos, hv, dd = self._probe_insert(
            carry["vis"], carry["claims"], keys, elive & gate, ranks,
            counts=True)
        hovf = carry["hovf"] | hv
        n_fresh = fresh.sum(dtype=jnp.int32)
        # two chunk-local overflows share the revert path: level buffer
        # full (ovf — margin is OCAP, the most one chunk can append)
        # and fresh-compaction buffer blown (oovf)
        ovf_now = carry["n_lvl"] + n_fresh > LCAP - OCAP
        oovf_now = n_fresh > OCAP
        bad_now = ovf_now | oovf_now
        # revert THIS chunk's inserts on the spot (earlier chunks' stay
        # until finalize's abandon clears them via the journal), then
        # skip the append
        ridx = jnp.where(fresh & bad_now, pos, VCAP)
        table = tuple(table[w].at[ridx].set(U32MAX, mode="drop")
                      for w in range(W))
        fresh = fresh & ~bad_now
        n_fresh = jnp.where(bad_now, 0, n_fresh)
        ovf = carry["ovf"] | ovf_now
        oovf = carry["oovf"] | oovf_now

        # second compaction: FCAP candidate slots -> OCAP fresh rows.
        # Everything downstream (phase2, narrow, the level append) runs
        # at OCAP width — fresh rows are the dedup survivors, typically
        # ~8x fewer than enabled candidates on wide-grid configs
        # (measured: the width halves the append+phase2 cost even at
        # 8x).
        slot = jnp.arange(FCAP, dtype=jnp.int32)
        lpos = jnp.where(fresh,
                         jnp.cumsum(fresh.astype(jnp.int32)) - 1, OCAP)
        lidx = lax.optimization_barrier(
            jnp.zeros((OCAP,), jnp.int32).at[lpos].set(
                slot, mode="drop"))                # out slot -> FCAP slot

        # contiguous append at n_lvl: gather OCAP rows, one
        # dynamic_update_slice per array.  Rows past n_fresh are
        # garbage but live beyond the new n_lvl, so later chunks
        # overwrite them and finalize masks them by n_lvl.  The start
        # clamp only engages when the level has overflowed, in which
        # case ovf forces a replay anyway.
        start = jnp.minimum(carry["n_lvl"], LCAP - OCAP)
        lane = take[lidx]                                # original lane id
        rows = lax.optimization_barrier(
            {k: cand_c[k][..., lidx] for k in cand_c})   # batch-last
        # invariants + constraints on the fresh rows (garbage rows are
        # masked by n_lvl at finalize)
        inv, con = lax.optimization_barrier(self._phase2_T(rows))
        rows_n = self.ir.narrow(self.lay, rows)   # storage dtypes
        lvl = {k: lax.dynamic_update_slice_in_dim(
                   v, rows_n[k], start, v.ndim - 1)
               for k, v in carry["lvl"].items()}
        # parent global ids are arithmetic: frontier row r has id
        # pg_off + r (the frontier IS the previous level, uncompacted)
        lpar = lax.dynamic_update_slice_in_dim(
            carry["lpar"], carry["pg_off"] + base + lane // A, start, 0)
        llane = lax.dynamic_update_slice_in_dim(
            carry["llane"], lane % A, start, 0)
        jslot = lax.dynamic_update_slice_in_dim(
            carry["jslot"], pos[lidx], start, 0)
        linv = lax.dynamic_update_slice_in_dim(carry["linv"], inv,
                                               start, 1)
        lcon = lax.dynamic_update_slice_in_dim(
            carry["lcon"], con, start, 0)
        return dict(carry, vis=table, claims=claims, lvl=lvl, lpar=lpar,
                    llane=llane, jslot=jslot, linv=linv, lcon=lcon,
                    n_lvl=jnp.minimum(carry["n_lvl"] + n_fresh,
                                      LCAP - OCAP),
                    n_gen=n_gen, ovf=ovf, fovf=fovf, hovf=hovf,
                    oovf=oovf, famx=famx, lanes=lanes,
                    ofx=jnp.maximum(carry["ofx"], n_fresh),
                    dedup=carry["dedup"] + dd,
                    base=base + B)

    # ------------------------------------------------------------------
    # per-level finalize: scalar aggregation, next-frontier swap,
    # journal rollback on overflow — one cheap device call.
    #
    # (A whole-level while_loop driver was tried and reverted for the
    # single-device engine: XLA materializes padded-layout copies of
    # the loop-carried [LCAP, S, S]-shaped buffers — (3,3) minor dims
    # tile to (4,128), a 57x blowup that OOMs HBM at LCAP=2^21 — and
    # measured host dispatch is only ~0.5 ms/chunk, so per-chunk
    # dispatch costs nothing.)
    # ------------------------------------------------------------------

    def _finalize_impl(self, carry):
        """Level finalize.  Returns (carry', outputs) where
        outputs["scal"] packs every per-level scalar the host needs —
        [n_lvl, n_viol, faults, n_front, ovf, fovf, n_gen, n_expand,
        hovf, oovf, ofx], the per-family enabled maxima, the per-family
        enabled-lane sums, then the level's four dedup counts — into ONE
        int32 array so the level
        costs a single device→host round trip.  Invariants/constraints
        were already evaluated per chunk (linv/lcon rows); finalize
        only aggregates, swaps the
        level buffer into the frontier, and — when a chunk overflowed a
        buffer (ovf/fovf/hovf) — rolls the visited table back via the
        journal instead of committing, so the host can grow capacities
        and replay the level exactly."""
        LCAP = carry["lpar"].shape[0]
        VCAP = carry["vis"][0].shape[0]
        n_lvl = carry["n_lvl"]
        g_off = carry["g_off"]
        bad = carry["ovf"] | carry["fovf"] | carry["hovf"] | \
            carry["oovf"]
        validrow = jnp.arange(LCAP, dtype=jnp.int32) < n_lvl
        inv_ok = (carry["linv"] | ~validrow[None, :]
                  if self.inv_names else carry["linv"])   # [n_inv, LCAP]
        con = carry["lcon"]
        n_viol = (~inv_ok).sum(dtype=jnp.int32)
        faults = ((carry["lvl"]["ctr"][C_OVERFLOW] > 0) &
                  validrow).sum(dtype=jnp.int32)

        def commit(carry):
            # the level buffer BECOMES the frontier (pointer swap, free
            # under donation); constraint-pruned rows stay in place and
            # are masked out of expansion by fmask (prune-not-expand,
            # SURVEY §2.8) so no LCAP-wide compaction gather is needed.
            # The level's keys are already in the visited table.
            fmask = con & validrow
            return (carry["lvl"], carry["front"], fmask, n_lvl,
                    carry["vis"], g_off, g_off + n_lvl)

        def abandon(carry):
            # overflow: roll the visited table back to the level start
            # by clearing exactly the journaled inserts (safe — see
            # _probe_insert rollback note), leave the frontier intact
            cidx = jnp.where(validrow, carry["jslot"], VCAP)
            vis = tuple(carry["vis"][w].at[cidx].set(U32MAX, mode="drop")
                        for w in range(self.W))
            return (carry["front"], carry["lvl"], carry["fmask"],
                    carry["n_front"], vis, carry["pg_off"], g_off)

        front, lvl, fmask, n_front, vis, pg_off, g_next = lax.cond(
            bad, abandon, commit, carry)
        n_expand = (con & validrow).sum(dtype=jnp.int32)
        # scal tail carries the per-family enabled-count maxima so the
        # host can grow exactly the overflowing family caps (still ONE
        # device→host transfer per level)
        scal = jnp.concatenate([jnp.stack([
            n_lvl, n_viol, faults, n_front,
            carry["ovf"].astype(jnp.int32), carry["fovf"].astype(jnp.int32),
            carry["n_gen"], n_expand, carry["hovf"].astype(jnp.int32),
            carry["oovf"].astype(jnp.int32), carry["ofx"]]),
            carry["famx"], carry["lanes"], carry["dedup"]])
        new_carry = dict(carry, vis=vis, front=front, lvl=lvl,
                         fmask=fmask, n_front=n_front,
                         n_lvl=jnp.int32(0), n_gen=jnp.int32(0),
                         ovf=jnp.bool_(False), fovf=jnp.bool_(False),
                         hovf=jnp.bool_(False), oovf=jnp.bool_(False),
                         famx=jnp.zeros_like(carry["famx"]),
                         lanes=jnp.zeros_like(carry["lanes"]),
                         ofx=jnp.int32(0),
                         dedup=jnp.zeros_like(carry["dedup"]),
                         base=jnp.int32(0), pg_off=pg_off, g_off=g_next)
        return new_carry, dict(inv_ok=inv_ok, scal=scal)

    # ------------------------------------------------------------------
    # small-level burst: run up to burst_levels whole BFS levels in ONE
    # device call while the frontier fits the burst ring
    # (_burst_chunks frontier chunks).
    #
    # Motivation: every synchronous dispatch+readback has a fixed host
    # cost, so a tiny level (one chunk step + finalize + scalar sync)
    # is mostly latency — and every config #3 run pays 12 sub-chunk
    # levels before the space widens (the round-5 latency figures
    # came from an older runtime; not measured on the current code).  The burst folds those levels into one jit: a
    # lax.while_loop whose body is the SAME pipeline as a chunk step
    # (guard-first expand + fingerprint + claim-insert dedup + phase2)
    # plus the finalize's commit; each iteration processes one frontier
    # CHUNK and commits a level whenever the chunk cursor drains the
    # frontier, so levels up to _burst_chunks * chunk states still fuse
    # (round 5's one-chunk burst capped at `chunk`, which left config
    # #3's 2-5k-state early levels on the per-level path).  The host
    # reads back ONE stats array for the whole burst.
    #
    # The while carry holds only ring-width (_burst_chunks * chunk)
    # buffers + the visited table; the big LCAP buffers pass through
    # OUTSIDE the loop (the reverted whole-level while_loop driver died
    # on XLA padding the loop-carried [.., S, S, LCAP] buffers — see
    # the note above _finalize_impl; the burst's loop-carried state
    # stays orders of magnitude smaller).
    #
    # Overflow discipline: any overflow (enabled > FCAP, a family cap,
    # level outgrowing the ring, probe budget) BAILS: the tripping
    # chunk's table inserts are cleared on the spot and the level's
    # earlier chunks' via the in-ring journal, the pre-level frontier
    # is kept, and the host replays that level through the ordinary
    # per-level path.  Archives (parents/lanes/state rows/inv bits) are
    # recorded per level on device and fetched only when needed
    # (store_states or a violation), so a clean burst costs one small
    # D2H transfer.
    #
    # Parent ids ride an explicit per-row gid array (gd) instead of the
    # chunk step's pg_off arithmetic: the spill engine feeds this same
    # core (engine/spill) with host-compacted frontiers whose gids are
    # NOT contiguous; levels born inside the burst refresh gd
    # arithmetically, which is exactly the per-level id assignment.
    # ------------------------------------------------------------------

    _BURST_LEVELS = 16
    _BURST_CHUNKS = 4           # ring width, in frontier chunks

    @property
    def _BS_N(self) -> int:
        """Stats columns (see _burst_core): five per-level counts, the
        dedup counts, the enabled lanes per family."""
        return 5 + len(DEDUP_COUNTER_KEYS) + len(self.expander.families)

    @property
    def _burst_chunks(self) -> int:
        return self._BURST_CHUNKS

    def _burst_width(self) -> int:
        """Ring width (states): the largest frontier/level the fused
        path handles before falling back to the per-level driver."""
        return self._burst_chunks * self.chunk

    def _burst_core(self, vis, claims, fr, fm, gd, nf, g0, pg0,
                    fam_caps, levels_left, states_cap, fcap=None,
                    ocap=None, rt=None):
        """The fused multi-level loop, over standalone ring-width
        buffers (no engine carry): fr/fm/gd are [..., KB]/[KB]/[KB]
        frontier rows (narrow, batch-last), membership mask and global
        ids; g0 is the next global id to assign.  Returns (stf, out):
        stf the final while state (vis/claims/fr/fm/gd/nf/g/pg), out
        the stats + per-level archives.

        out["stats"] is int32 [burst_levels + 1, _BS_N]: per-level rows
        [n_lvl, n_viol, faults, n_expand, n_gen] followed by the level's
        four dedup counts (DEDUP_COUNTER_KEYS order) and its enabled
        lanes per family, and a meta row at index burst_levels:
        [n_levels_done, bail, n_front_out, viol_any, states_done].
        out["par"]/out["lane"] are [L_MAX, KB] int32, out["st"] the
        narrow state rows [..., L_MAX, KB], out["inv"] bool
        [n_inv, L_MAX, KB] — the per-level archives."""
        B, A, W = self.chunk, self.A, self.W
        FCAP = int(fcap) if fcap is not None else self.FCAP
        KB = fm.shape[0]
        VCAP = vis[0].shape[0]
        L_MAX = self.burst_levels
        n_inv = len(self.inv_names)
        # post-dedup compaction width (capped by the ring: a chunk can
        # never append more than KB rows anyway) — see the OCAP note in
        # the chunk step; the burst body used to run narrow/phase2 at
        # FCAP width per chunk where the per-level path compacts to
        # OCAP first, a measured ~2x per-chunk saving
        OC = min(int(ocap) if ocap is not None else self.OCAP, KB)

        st = dict(
            vis=vis, claims=claims, fr=fr, fm=fm, gd=gd, nf=nf,
            base=jnp.int32(0), nl=jnp.int32(0), gl=jnp.int32(0),
            dd=jnp.zeros((len(DEDUP_COUNTER_KEYS),), jnp.int32),
            fl=jnp.zeros((len(self.expander.families),), jnp.int32),
            lv={k: jnp.zeros_like(v) for k, v in fr.items()},
            lvp=jnp.full((KB,), -1, jnp.int32),
            lvlane=jnp.full((KB,), -1, jnp.int32),
            lin=jnp.ones((n_inv, KB), bool),
            lco=jnp.ones((KB,), bool),
            jsl=jnp.zeros((KB,), jnp.int32),
            li=jnp.int32(0), done=jnp.int32(0),
            g=g0, pg=pg0,
            bail=jnp.bool_(False), viol=jnp.bool_(False),
            stats=jnp.zeros((L_MAX, self._BS_N), jnp.int32),
            opar=jnp.full((L_MAX, KB), -1, jnp.int32),
            olane=jnp.full((L_MAX, KB), -1, jnp.int32),
            ost={k: jnp.zeros(v.shape[:-1] + (L_MAX, KB), v.dtype)
                 for k, v in fr.items()},
            oinv=jnp.ones((n_inv, L_MAX, KB), bool),
        )

        def cond(st):
            return (~st["bail"] & ~st["viol"] & (st["li"] < levels_left)
                    & (st["nf"] > 0) & (st["done"] < states_cap))

        def body(st):
            base, nl = st["base"], st["nl"]
            sv = self.ir.widen({k: lax.dynamic_slice_in_dim(v, base, B,
                                                    axis=v.ndim - 1)
                        for k, v in st["fr"].items()})
            fm_c = lax.dynamic_slice_in_dim(st["fm"], base, B)
            valid = ((base + jnp.arange(B, dtype=jnp.int32)) <
                     st["nf"]) & fm_c
            cand_c, elive, fp, take, famx_c, n_e = \
                self._expand_fp_chunk(sv, valid, fam_caps, FCAP, rt)
            bail = (n_e > FCAP) | jnp.any(
                famx_c > jnp.asarray(fam_caps, jnp.int32))
            keys = tuple(jnp.where(elive, fp[w], U32MAX)
                         for w in range(W))
            ranks = jnp.arange(FCAP, dtype=jnp.uint32)
            vis, claims, fresh, pos, hv, dd = self._probe_insert(
                st["vis"], st["claims"], keys, elive & ~bail, ranks,
                counts=True)
            dd2 = st["dd"] + dd
            bail = bail | hv
            n_fresh = fresh.sum(dtype=jnp.int32)
            bail = bail | (nl + n_fresh > KB)
            # one chunk's fresh rows outran the post-dedup compaction
            # buffer: bail to the per-level path, whose oovf growth
            # machinery owns this case
            bail = bail | (n_fresh > OC)
            # bail => this level never happened: clear THIS chunk's
            # inserts on the spot and the level's earlier chunks' via
            # the ring journal (rollback-safe — _probe_insert note)
            ridx = jnp.where(fresh & bail, pos, VCAP)
            vis = tuple(vis[w].at[ridx].set(U32MAX, mode="drop")
                        for w in range(W))
            jb = jnp.where((jnp.arange(KB, dtype=jnp.int32) < nl) & bail,
                           st["jsl"], VCAP)
            vis = tuple(vis[w].at[jb].set(U32MAX, mode="drop")
                        for w in range(W))
            fresh = fresh & ~bail
            n_fresh = jnp.where(bail, 0, n_fresh)
            n_genl = jnp.where(bail, 0, elive.sum(dtype=jnp.int32))
            gl2 = st["gl"] + n_genl
            fl2 = st["fl"] + jnp.where(bail, 0, famx_c)
            nl2 = nl + n_fresh

            # second compaction (the chunk step's OCAP discipline,
            # folded in round 9): fresh FCAP slots compact to OC rows
            # BEFORE narrow/phase2/ring-append, so the burst body never
            # pays padded FCAP width for the append-side work.  Row
            # order is candidate-slot ascending = parent-major, lane
            # ascending — the per-level order, bit-identical appends.
            slot = jnp.arange(FCAP, dtype=jnp.int32)
            opos = jnp.where(fresh,
                             jnp.cumsum(fresh.astype(jnp.int32)) - 1,
                             OC)
            oidx = lax.optimization_barrier(
                jnp.zeros((OC,), jnp.int32).at[opos].set(
                    slot, mode="drop"))          # out row -> FCAP slot
            rows = lax.optimization_barrier(
                {k: cand_c[k][..., oidx] for k in cand_c})
            inv, con = self._phase2_T(
                rows, None if rt is None else rt["bounds"])
            rows_n = self.ir.narrow(self.lay, rows)
            # ring positions for the compacted rows: nl + row index
            oar = jnp.arange(OC, dtype=jnp.int32)
            rpos = jnp.where(oar < n_fresh, nl + oar, KB)
            lv = {k: st["lv"][k].at[..., rpos].set(rows_n[k],
                                                   mode="drop")
                  for k in st["lv"]}
            take_o = take[oidx]
            par_row = jnp.clip(base + take_o // A, 0, KB - 1)
            pgid = st["gd"][par_row]
            lvp = st["lvp"].at[rpos].set(pgid, mode="drop")
            lvlane = st["lvlane"].at[rpos].set(take_o % A, mode="drop")
            jsl = st["jsl"].at[rpos].set(pos[oidx], mode="drop")
            lin = (st["lin"].at[:, rpos].set(inv, mode="drop")
                   if n_inv else st["lin"])
            lco = st["lco"].at[rpos].set(con, mode="drop")

            new_base = base + B
            level_done = ~bail & (new_base >= st["nf"])

            # level commit (predicated — a mid-level chunk leaves the
            # frontier and archives untouched)
            validrow = jnp.arange(KB, dtype=jnp.int32) < nl2
            inv_ok = ((lin | ~validrow[None, :]) if n_inv
                      else jnp.ones((0, KB), bool))
            n_viol = (~inv_ok).sum(dtype=jnp.int32)
            faults = ((lv["ctr"][C_OVERFLOW] > 0) &
                      validrow).sum(dtype=jnp.int32)
            n_expand = (lco & validrow).sum(dtype=jnp.int32)
            li = st["li"]
            row = jnp.concatenate([
                jnp.stack([nl2, n_viol, faults, n_expand, gl2]), dd2, fl2])

            new = dict(st)
            new["vis"], new["claims"] = vis, claims
            new["lv"], new["lvp"], new["lvlane"] = lv, lvp, lvlane
            new["lin"], new["lco"], new["jsl"] = lin, lco, jsl
            new["stats"] = jnp.where(
                level_done,
                lax.dynamic_update_slice(st["stats"], row[None],
                                         (li, 0)),
                st["stats"])
            new["opar"] = jnp.where(
                level_done,
                lax.dynamic_update_slice(st["opar"], lvp[None],
                                         (li, 0)),
                st["opar"])
            new["olane"] = jnp.where(
                level_done,
                lax.dynamic_update_slice(st["olane"], lvlane[None],
                                         (li, 0)),
                st["olane"])
            new["ost"] = {
                k: jnp.where(
                    level_done,
                    lax.dynamic_update_slice(
                        v, lv[k][..., None, :],
                        (0,) * (v.ndim - 2) + (li, 0)),
                    v)
                for k, v in st["ost"].items()}
            if n_inv:
                new["oinv"] = jnp.where(
                    level_done,
                    lax.dynamic_update_slice(st["oinv"],
                                             inv_ok[:, None, :],
                                             (0, li, 0)),
                    st["oinv"])
            # frontier swap only at a level boundary (bail keeps the
            # pre-level frontier so the host can replay it exactly);
            # rows past nl2 are stale but masked by nf/fm downstream
            new["fr"] = {k: jnp.where(level_done, lv[k], st["fr"][k])
                         for k in st["fr"]}
            new["fm"] = jnp.where(level_done, lco & validrow, st["fm"])
            new["nf"] = jnp.where(level_done, nl2, st["nf"])
            new["gd"] = jnp.where(
                level_done, st["g"] + jnp.arange(KB, dtype=jnp.int32),
                st["gd"])
            new["pg"] = jnp.where(level_done, st["g"], st["pg"])
            new["g"] = st["g"] + jnp.where(level_done, nl2, 0)
            new["done"] = st["done"] + jnp.where(level_done, nl2, 0)
            new["li"] = li + level_done.astype(jnp.int32)
            new["base"] = jnp.where(level_done, 0, new_base)
            new["nl"] = jnp.where(level_done, 0, nl2)
            new["gl"] = jnp.where(level_done, 0, gl2)
            new["dd"] = jnp.where(level_done, 0, dd2)
            new["fl"] = jnp.where(level_done, 0, fl2)
            new["bail"] = bail
            new["viol"] = st["viol"] | (level_done & (n_viol > 0))
            return new

        st = lax.while_loop(cond, body, st)

        meta = jnp.zeros((self._BS_N,), jnp.int32)
        meta = meta.at[0].set(st["li"])
        meta = meta.at[1].set(st["bail"].astype(jnp.int32))
        meta = meta.at[2].set(st["nf"])
        meta = meta.at[3].set(st["viol"].astype(jnp.int32))
        meta = meta.at[4].set(st["done"])
        stats = jnp.concatenate([st["stats"], meta[None]], axis=0)
        return st, dict(stats=stats, par=st["opar"], lane=st["olane"],
                        st=st["ost"], inv=st["oinv"])

    # ------------------------------------------------------------------
    # job-axis batched burst (serve/batch): _burst_core with every
    # per-job buffer riding a leading [J] axis — the multi-tenant
    # serving layer packs many small (spec, config) jobs into ONE
    # device program this way, amortizing compile and dispatch across
    # tenants exactly as the burst amortizes them across levels.
    # ------------------------------------------------------------------

    def _batched_burst_impl(self, jst, lv_left, st_cap):
        """Job-vmapped burst core.  ``jst`` stacks per-job state on a
        leading job axis: vis (W-tuple of u32[J, VCAP] tables), claims
        u32[J, VCAP], fr (narrow batch-last frontier rows [J, ..., KB]),
        fm bool[J, KB], gd int32[J, KB], nf/g/pg int32[J]; ``lv_left``
        and ``st_cap`` are per-job int32[J] depth/state gates (a
        finished job passes lv_left=0 and never re-enters the loop).

        Constant-padding ceilings (round 13): an optional ``jst["rt"]``
        carries per-job runtime data — guard thresholds int32[J, A],
        family lane masks bool[J, A], and the search-bounds vector
        int32[J, NB] — so heterogeneous small configs (differing
        MaxTerm-style bounds, paxos ballot/value/instance counts) ride
        ONE compiled ceiling program: the int8 guard matrix and delta
        matrices stay shared per shape ceiling while each job's
        thresholds/masks/bounds vmap as device data.  Absent, the
        program is the historical baked-constant one, bit-identical.

        Under vmap the burst's while_loops run until EVERY job's cond
        is false, with per-job select masking: a finished job's state
        freezes (its lanes contribute no further table writes or
        appends) while stragglers keep stepping.  Each job's trajectory
        is bit-identical to a solo burst — every op in the body is
        per-lane-independent integer/boolean work, and the select only
        ever replaces a finished job's next state with its frozen one
        (tests/test_serve.py pins batched ≡ sequential on counts, level
        sizes, violations and witness traces).

        Returns (jst', out) with out's stats matrix and per-level
        archives carrying the same leading [J] axis."""
        def one(st, lvl, cap):
            rt = st.get("rt")
            stf, out = self._burst_core(
                st["vis"], st["claims"], st["fr"], st["fm"], st["gd"],
                st["nf"], st["g"], st["pg"], self.FAM_CAPS, lvl, cap,
                rt=rt)
            nst = dict(vis=stf["vis"], claims=stf["claims"],
                       fr=stf["fr"], fm=stf["fm"], gd=stf["gd"],
                       nf=stf["nf"], g=stf["g"], pg=stf["pg"])
            if rt is not None:
                # rt is job-constant: pass it through the carry so the
                # AOT executable's output tree matches its input tree
                # (the serving layer re-feeds jst every device call)
                nst["rt"] = rt
            return nst, out
        return jax.vmap(one)(jst, lv_left, st_cap)

    def burst_batched_fn(self, donate: bool = True, sharding=None):
        """The jitted job-axis burst entry point (lazy: solo checks
        never pay for it).  The serving layer AOT-compiles it per
        (bucket, padded job count) via ``.lower(...).compile()`` so the
        compile lands in one attributable span.

        ``donate=False`` compiles WITHOUT donating the carry.  Carry
        donation bakes input->output buffer aliasing into the XLA
        executable, and an older jax lost the jax-side half of that
        contract for an executable deserialized in a DIFFERENT
        process: the re-fed carry came back silently corrupted.  On
        the current jax the CPU round trip keeps it
        (tools/daemon_smoke.py passes with donation forced on, PR 21),
        but no TPU run has checked it, so the serving layer still
        compiles the donation-free variant whenever a persistent
        executable cache is in play, trading one carry's worth of
        device memory for a program that round-trips serialization
        exactly (tools/daemon_smoke.py pins the kill->restart path
        warm).

        ``sharding`` is either None, a single job-axis
        ``NamedSharding`` (the round-16 1-D job mesh), or a dict
        ``{"carry": <tree>, "gate": <sharding>, "out": <tree>}`` of
        per-leaf sharding pytrees (the round-17 2-D jobs × state
        mesh).

        The single-sharding form applies as a pytree-prefix
        ``in_shardings``/``out_shardings`` over the whole carry: every
        leaf of ``jst`` and ``out`` leads with the [J] job axis, so
        ONE spec splits the wave across devices and GSPMD partitions
        the body with no data collectives (each lane is independent;
        only the vmapped while-loop condition reduces across jobs).

        The dict form carries full per-leaf trees because under a 2-D
        mesh the leaves shard DIFFERENTLY: per-job scalars/cursors
        stay on P("jobs") while the visited-table slots, frontier
        rings, level buffers and archive staging also shard their
        big per-job axis over "state" (serve/batch builds the trees
        from parallel/pjit_mesh's rule-matched partition specs).
        ``"carry"`` must match ``jst``'s structure, ``"gate"`` covers
        the two int32[J] gate args, ``"out"`` the stats/archive tree.
        Either way the body is UNCHANGED — the same program serves
        one device, a 1-D job mesh, or a 2-D pod slice; the dedup
        probe/claim scatter lowers to in-program GSPMD collectives
        along the state axis only."""
        if self._bat_jit is None:
            self._bat_jit = {}
        if isinstance(sharding, dict):
            # spec trees are unhashable pytrees: key the jit-variant
            # cache on (treedef, leaves) — NamedShardings hash fine
            leaves, treedef = jax.tree_util.tree_flatten(sharding)
            key = (bool(donate), treedef, tuple(leaves))
        else:
            key = (bool(donate), sharding)
        fn = self._bat_jit.get(key)
        if fn is None:
            kwargs = {}
            if donate:
                kwargs["donate_argnums"] = 0
            if isinstance(sharding, dict):
                gate = sharding["gate"]
                kwargs["in_shardings"] = (sharding["carry"], gate,
                                          gate)
                kwargs["out_shardings"] = (sharding["carry"],
                                           sharding["out"])
            elif sharding is not None:
                kwargs["in_shardings"] = (sharding, sharding, sharding)
                kwargs["out_shardings"] = sharding
            fn = jax.jit(self._batched_burst_impl, **kwargs)
            self._bat_jit[key] = fn
        return fn

    def _burst_impl(self, carry, fam_caps, levels_left, states_cap):
        """Classic-carry wrapper around _burst_core: slice the ring out
        of the LCAP buffers, run the fused loop, paste the surviving
        frontier back.  Returns (carry', out) — out as in
        _burst_core."""
        KB = self._burst_width()
        front0 = {k: lax.dynamic_slice_in_dim(v, 0, KB, axis=v.ndim - 1)
                  for k, v in carry["front"].items()}
        # classic frontiers are contiguous: row r has id pg_off + r
        gd0 = carry["pg_off"] + jnp.arange(KB, dtype=jnp.int32)
        stf, out = self._burst_core(
            carry["vis"], carry["claims"], front0,
            carry["fmask"][:KB], gd0, carry["n_front"], carry["g_off"],
            carry["pg_off"], fam_caps, levels_left, states_cap,
            fcap=carry["cidx"].shape[0], ocap=carry["oidx"].shape[0])
        fmask = jnp.zeros_like(carry["fmask"]).at[:KB].set(stf["fm"])
        front = {k: lax.dynamic_update_slice_in_dim(
                     v, stf["fr"][k], 0, axis=v.ndim - 1)
                 for k, v in carry["front"].items()}
        new_carry = dict(carry, vis=stf["vis"], claims=stf["claims"],
                         front=front, fmask=fmask, n_front=stf["nf"],
                         g_off=stf["g"], pg_off=stf["pg"])
        return new_carry, out

    # ------------------------------------------------------------------

    def _carry_jit(self, fn):
        """jax.jit for a program that returns a carry: born under the
        carry's named shardings where the engine has them."""
        if self._carry_sh is None:
            return jax.jit(fn)
        return jax.jit(fn, out_shardings=self._carry_sh)

    def _fresh_carry(self, lcap: int, vcap: int, fcap: Optional[int] = None,
                     ocap: Optional[int] = None):
        """An empty carry at the given capacities, built by one jitted
        program per capacity set (every buffer filled on the device,
        none uploaded)."""
        key = (lcap, vcap, fcap if fcap is not None else self.FCAP,
               ocap if ocap is not None else self.OCAP)
        fn = self._fresh_jit_cache.get(key)
        if fn is None:
            def fresh_carry():           # the program's name in traces
                return self._fresh_carry_impl(*key)
            fn = self._fresh_jit_cache[key] = self._carry_jit(fresh_carry)
        return fn()

    def _fresh_carry_impl(self, lcap: int, vcap: int, fcap: int,
                          ocap: int):
        one = self.ir.narrow(self.lay, self.ir.encode(
            self.lay, *self.ir.init_state(self.cfg)))
        # frontier/level state buffers are BATCH-LAST ([..., lcap]) —
        # see the chunk step's layout note
        zeros = {k: jnp.zeros(v.shape + (lcap,), dtype=v.dtype)
                 for k, v in one.items()}
        n_inv = len(self.inv_names)
        return dict(
            # the open-addressing visited table + its transient claims
            vis=tuple(jnp.full((vcap,), U32MAX) for _ in range(self.W)),
            claims=jnp.full((vcap,), U32MAX),
            jslot=jnp.full((lcap,), -1, jnp.int32),  # level insert journal
            linv=jnp.ones((n_inv, lcap), bool),      # per-row invariants
            lcon=jnp.ones((lcap,), bool),            # per-row constraints
            lvl=zeros,
            lpar=jnp.full((lcap,), -1, jnp.int32),
            llane=jnp.full((lcap,), -1, jnp.int32),
            cidx=jnp.zeros((fcap,), jnp.int32),   # FCAP shape anchor
            oidx=jnp.zeros((ocap,), jnp.int32),   # OCAP shape anchor
            n_lvl=jnp.int32(0),
            n_gen=jnp.int32(0),
            famx=jnp.zeros((len(self.expander.families),), jnp.int32),
            # the level's enabled lanes per family (lanes_enabled)
            lanes=jnp.zeros((len(self.expander.families),), jnp.int32),
            ofx=jnp.int32(0),       # max fresh rows in any chunk
            # the level's dedup work counts (DEDUP_COUNTER_KEYS)
            dedup=jnp.zeros((len(DEDUP_COUNTER_KEYS),), jnp.int32),
            base=jnp.int32(0),      # chunk cursor within the frontier
            g_off=jnp.int32(0),     # global state-id offset (this level)
            pg_off=jnp.int32(0),    # global state-id offset (frontier)
            ovf=jnp.bool_(False),
            fovf=jnp.bool_(False),
            hovf=jnp.bool_(False),  # probe-round budget blown
            oovf=jnp.bool_(False),  # fresh-compaction buffer blown
            front={k: jnp.zeros_like(v) for k, v in zeros.items()},
            fmask=jnp.zeros((lcap,), bool),
            n_front=jnp.int32(0),
        )

    def _place_roots(self, carry, roots_n, slots, rk, inv_r, con_r):
        """Write the n root rows into a fresh carry: level-buffer rows
        (narrow, batch-last), their host-placed visited-table slots
        and keys (u32 [n, W]), the insert journal and the invariant /
        constraint bits.  Traced inside the set-up program
        (_setup_impl), on whatever sharding the carry has
        (parallel/pjit_mesh: slot- and row-sharded); the row writes
        update the leading n rows of each output buffer in place."""
        n = slots.shape[0]
        carry = dict(carry)
        carry["lvl"] = {k: v.at[..., :n].set(roots_n[k])
                        for k, v in carry["lvl"].items()}
        carry["vis"] = tuple(carry["vis"][w].at[slots].set(rk[:, w])
                             for w in range(self.W))
        carry["jslot"] = carry["jslot"].at[:n].set(slots)
        carry["n_lvl"] = jnp.int32(n)
        carry["linv"] = carry["linv"].at[:, :n].set(inv_r.T)
        carry["lcon"] = carry["lcon"].at[:n].set(con_r)
        return carry

    def _setup_impl(self, lcap, vcap, fcap, ocap, roots, slots, rk):
        """The fresh-start set-up program: allocate the carry, narrow
        the widened root rows (int32 SoA [n, ...]) to storage dtypes in
        batch-last layout, evaluate the root cohort's invariants and
        constraints (levels get theirs inside the chunk step; roots
        bypass it) and place the roots.  The fills and the root writes
        land in the same output buffers: one carry."""
        carry = self._fresh_carry_impl(lcap, vcap, fcap, ocap)
        roots_n = {k: jnp.moveaxis(v, 0, -1)
                   for k, v in self.ir.narrow(self.lay, roots).items()}
        inv_r, con_r = self._phase2_impl(roots)
        return self._place_roots(carry, roots_n, slots, rk, inv_r, con_r)

    def _setup_carry(self, roots, rk):
        """A fresh start's carry with the deduplicated roots placed, in
        ONE device call, compiled once per capacity set and root count.
        roots: widened SoA [n, ...] host rows; rk: their u32 [n, W]
        keys.  The table is empty, so the host's sequential probe
        placement (_host_probe_assign) is exact."""
        key = (self.LCAP, self.VCAP, self.FCAP, self.OCAP, len(rk))
        fn = self._setup_jit_cache.get(key)
        if fn is None:
            def setup_carry(*args):      # the program's name in traces
                return self._setup_impl(*key[:4], *args)
            fn = self._setup_jit_cache[key] = self._carry_jit(setup_carry)
        return fn(roots, self._host_probe_assign(rk), rk)

    def _grow(self, carry, lcap: int, vcap: int):
        """Re-home a carry into bigger capacity buffers (the visited
        table and the frontier survive; the level buffer is reset —
        callers replay the level).  The table must already have `vcap`
        slots (_rehash_tables handles table growth)."""
        old_lcap = carry["lpar"].shape[0]
        assert carry["vis"][0].shape[0] == vcap, \
            "grow the table via _rehash_tables first"
        new = self._fresh_carry(lcap, vcap, self.FCAP, self.OCAP)
        new["vis"] = carry["vis"]
        new["claims"] = carry["claims"]
        pad = lcap - old_lcap
        new["front"] = {k: jnp.concatenate(
            [carry["front"][k],
             jnp.zeros(v.shape[:-1] + (pad,), v.dtype)], axis=-1)
            for k, v in carry["front"].items()}
        new["fmask"] = jnp.concatenate(
            [carry["fmask"], jnp.zeros((pad,), bool)])
        new["n_front"] = carry["n_front"]
        new["g_off"] = carry["g_off"]
        new["pg_off"] = carry["pg_off"]
        # n_gen stays 0: the caller replays the whole level from the
        # intact frontier, so keeping the partial count would double it
        return new

    # ------------------------------------------------------------------

    def _stamp_mode(self, res: "CheckResult") -> "CheckResult":
        """Record which expansion/dedup program this run executed (the
        MXU-path mode flags in the metrics registry).  Stamped from the
        LIVE engine config — never serialized into checkpoints — so a
        resumed run reports the resuming engine's modes."""
        res.guard_matmul = int(self.guard_matmul)
        # 1 only when the delta program actually compiled (flag ON and
        # the spec declares at least one affine family)
        res.delta_matmul = int(self.expander.delta_active)
        # 1 = orbit-sort canonical fingerprints, 0 = min-over-perms
        # (fingerprint.resolve_sym_canon — the RESOLVED mode, so "auto"
        # runs report what they actually executed)
        res.sym_canon = int(self.fpr.sym_canon == "sort")
        return res

    def _prewarm_perlevel(self, roots=None, rk=None):
        """Warm the per-level step/finalize executables with one dummy
        dispatch each BEFORE the driver loop (the BENCH_r08 recompile
        leak: with burst ON the first per-level dispatch otherwise
        happens only when a burst BAILS, so its cold compile landed
        mid-run inside a level_dispatch span — 11.6 s over 9 dispatches
        vs 1.65 s over 30 in per-level mode).  Given a fresh start's
        roots, the set-up program builds the dummy, so it warms too;
        otherwise the dummy is an empty carry.  Either way n_front = 0
        (every lane invalid: the step inserts nothing) and the calls
        donate the dummy away, so the cost is one transient carry + two
        no-op dispatches; post-bail dispatches then reuse the warmed
        executable (tests/test_obs.py pins the compile-span/cache
        counts).  Capacity growth retraces, as ever."""
        dummy = (self._setup_carry(roots, rk) if roots is not None
                 else self._fresh_carry(self.LCAP, self.VCAP))
        dummy = self._step_jit(dummy, self.FAM_CAPS)
        # wait for the last warm dispatch, so the dummy is freed before
        # the real carry's buffers are allocated, not beside them
        jax.block_until_ready(self._fin_jit(dummy))

    def _dedup_roots(self, seed_states):
        """Shared root-admission front half (this engine and
        SpillEngine): cfg prefix pins compile to seeds
        (raft.tla:1198-1234; models/golden docstring), seeds encode to
        SoA rows, and first-seen fingerprint dedup picks the root set.
        Returns (roots int32 SoA [n, ...] batch-major, rk u32 [n, W]
        canonical fingerprints, pin_interiors or None)."""
        pin_interiors = None
        if seed_states is None and self.cfg.prefix_pins:
            if self.ir.prefix_pin_seeds is None:
                raise ValueError(
                    f"spec {self.ir.name!r} has no prefix-pin support")
            seed_states, pin_interiors = self.ir.prefix_pin_seeds(
                self.cfg, with_interior=True)
        init_list = (seed_states if seed_states is not None
                     else [self.ir.init_state(self.cfg)])
        init_arrs = self.ir.widen(_cat([
            {k: np.asarray(v)[None] for k, v in s.items()}
            if isinstance(s, dict) else
            {k: v[None] for k, v in self.ir.encode(self.lay, *s).items()}
            for s in init_list]))
        root_fp = np.asarray(self._rootfp_jit(init_arrs)).astype(np.uint32)
        _uniq, first_idx = np.unique(fp_key(root_fp),
                                     return_index=True)
        first_idx.sort()
        return _take(init_arrs, first_idx), root_fp[first_idx], \
            pin_interiors

    # ------------------------------------------------------------------
    # trace-archive plumbing (engine/archive): every engine family
    # stores per-level parent/lane/state arrays either in host RAM (the
    # historical lists) or streamed to memmap'd per-level files under
    # ``archive_dir`` — one dispatch point so check loops, checkpoints
    # and trace reconstruction stay backing-agnostic.
    # ------------------------------------------------------------------

    def _init_store(self):
        self._states, self._parents, self._lanes = [], [], []
        self._arch = None
        if self.store_states and self.archive_dir:
            from .archive import DiskArchive
            self._arch = DiskArchive(self.archive_dir)

    def _archive_level(self, parents, lanes, states_major):
        with self._obs.span("archive_io"):
            if self._arch is not None:
                self._arch.append_level(parents, lanes, states_major)
            else:
                self._parents.append(parents)
                self._lanes.append(lanes)
                self._states.append(states_major)

    def _ckpt_store_args(self):
        """(parents, lanes, states, extra-meta) for ckpt_write: a disk
        archive already persists itself level-by-level, so checkpoints
        record only its level count instead of re-embedding rows."""
        if self._arch is not None:
            return [], [], [], dict(disk_archive=True,
                                    arch_levels=self._arch.n_levels)
        return self._parents, self._lanes, self._states, {}

    def _load_archives(self, path, z, meta, template):
        """Resume-side twin of _ckpt_store_args: reattach the disk
        archive (truncating levels past the checkpoint, so a resumed
        run re-appends them bit-identically) or unpack the embedded
        in-RAM archives."""
        from .archive import ArchiveError, DiskArchive
        if meta.get("disk_archive"):
            if not (self.store_states and self.archive_dir):
                raise CheckpointError(
                    f"{path}: checkpoint archives live in a disk "
                    "archive directory — resume with the same "
                    "archive_dir (CLI: --archive-dir)")
            try:
                self._arch = DiskArchive(self.archive_dir, attach=True)
                self._arch.truncate(meta["arch_levels"])
            except ArchiveError as e:
                raise CheckpointError(str(e)) from e
            self._parents, self._lanes, self._states = [], [], []
            return
        if self.store_states and self.archive_dir:
            raise CheckpointError(
                f"{path}: checkpoint holds in-RAM archives; resume "
                "without archive_dir")
        self._arch = None
        self._parents, self._lanes, self._states = ckpt_archives(
            z, meta, template, self.store_states)

    def _restore_portable_archives(self, img):
        """Shape-portable twin of _load_archives: attach the archives a
        ``resil.portable.PortableImage`` carries (the in-RAM per-level
        lists, or a disk-archive reattach+truncate).  The archive
        format is engine-agnostic — parents/lanes/state rows in global
        id order — so archives port across engine families unchanged."""
        from .archive import ArchiveError, DiskArchive
        self._arch = None
        self._parents, self._lanes, self._states = [], [], []
        if not self.store_states:
            return
        if not img.store_states:
            raise CheckpointError(
                "portable image was written with store_states=False; "
                "resume with store_states=False (CLI: --no-store) — "
                "trace archives cannot be reconstructed")
        if img.disk_archive_levels is not None:
            if not self.archive_dir:
                raise CheckpointError(
                    f"{img.source_path}: image archives live in a "
                    "disk archive directory — resume with the same "
                    "archive_dir (CLI: --archive-dir)")
            try:
                self._arch = DiskArchive(self.archive_dir, attach=True)
                self._arch.truncate(img.disk_archive_levels)
            except ArchiveError as e:
                raise CheckpointError(str(e)) from e
            return
        if self.archive_dir:
            raise CheckpointError(
                f"{img.source_path}: image holds in-RAM archives; "
                "resume without archive_dir")
        self._parents = list(img.parents)
        self._lanes = list(img.lanes)
        self._states = [dict(s) for s in img.states]

    def check(self, max_depth: int = 10 ** 9, max_states: int = 10 ** 9,
              stop_on_violation: bool = False,
              seed_states: Optional[List] = None,
              checkpoint_path: Optional[str] = None,
              checkpoint_every: int = 1,
              resume_from: Optional[str] = None,
              resume_image=None,
              verbose: bool = False, obs=None) -> CheckResult:
        """seed_states entries are (State, Hist) pairs or raw SoA dicts
        (the latter preserve feature lanes exactly — engine-emitted
        seeds; punctuated search, SURVEY §2.9).

        checkpoint_path — write a checkpoint there every
        ``checkpoint_every`` levels; resume_from — continue a prior
        checkpointed run (final counts are identical to an
        uninterrupted run; levels are never half-resumed).

        resume_image — a ``resil.portable.PortableImage`` from ANY
        engine family's checkpoint (round 12 contract): the visited
        key SET rebuilds this engine's table image and the gid-ordered
        frontier rows re-home into the level-buffer layout, so a spill
        checkpoint resumes here (and, via
        parallel/pjit_mesh's inherited override, onto a pod-spanning
        pjit mesh) landing on the exact counts of an uninterrupted
        run.

        obs — an ``obs.Obs`` bundle (spans / JSONL ledger / heartbeat /
        profiler hooks); every dispatch writes one ledger record and
        one heartbeat rewrite, so a killed run keeps its telemetry."""
        obs = self._obs = obs if obs is not None else NULL_OBS
        t0 = time.perf_counter()
        if resume_from is not None and resume_image is not None:
            raise ValueError(
                "resume_from and resume_image are mutually exclusive")
        fam_names = [f.name for f in self.expander.families]

        def prewarm(obs, roots=None, rk=None):
            # per-level executables warm at run start, inside a compile
            # span — never mid-run inside a level_dispatch span (the
            # BENCH_r08 burst-bailout leak).  Gated on span
            # instrumentation: every real perf/TPU run carries the obs
            # surface (ROADMAP carry-over; bench/deep_run/obs_smoke all
            # pass spans), while uninstrumented unit-test checks skip
            # the two extra dummy dispatches — on XLA:CPU the
            # persistent compile cache cannot absorb them, and tier-1
            # runs ~100 check() calls.  Once per capacity set: a later
            # check at the same capacities finds the executables warm,
            # so a traced check then dispatches what an untraced one
            # does.  Called BEFORE the real carry materializes where
            # possible: the dummy carry is donated away by the warm
            # dispatches, so sequencing it first keeps peak device
            # memory at ONE carry.  A fresh start's set-up program warms
            # with them.
            caps = (self.LCAP, self.VCAP, self.FCAP, self.OCAP,
                    self.FAM_CAPS)
            if obs.spans is not None and caps != self._warm_caps:
                with obs.span("compile"):
                    self._prewarm_perlevel(roots, rk)
                self._warm_caps = caps

        def run_finalize(carry):
            carry, out = self._fin_jit(carry)
            # the ONE per-level device->host sync
            scal = [int(x) for x in np.asarray(out["scal"])]
            # every finalize's dedup counts, replays included (a
            # replayed level's probe walk is device work too)
            _add_dedup(res, scal[-len(DEDUP_COUNTER_KEYS):])
            return carry, out, scal

        def grow_table_if_needed(carry, min_add=0):
            # pessimistic load bound: a level can add at most
            # LCAP - OCAP keys (a burst up to min_add), so checking
            # before the level needs no mid-level sync
            need = n_vis + max(self.LCAP - self.OCAP, min_add)
            if need > self._LOAD_MAX * self.VCAP:
                while need > self._LOAD_MAX * self.VCAP:
                    self.VCAP *= 4
                vis, claims = self._rehash_tables(carry["vis"], self.VCAP)
                carry = dict(carry, vis=vis, claims=claims)
            return carry

        def harvest(carry, out, scal):
            """Per-level host bookkeeping: counts, parents/lanes,
            violations, optional state store."""
            nonlocal n_states, n_vis
            n_lvl, n_viol, faults, n_front = scal[:4]
            n_genl = scal[6]
            nf = len(fam_names)
            _add_lanes(res, fam_names, scal[11 + nf:11 + 2 * nf])
            res.distinct_states += n_lvl
            res.overflow_faults += faults
            res.generated_states += n_genl
            res.violations_global += n_viol
            if self.store_states or n_viol:
                # after finalize the level's rows live in front (the
                # buffers swap); they are only overwritten by the
                # next-next level's chunk steps.  One packed read
                # brings parents, lanes, state rows and (on a
                # violation) the invariant bits.
                front = carry["front"]
                par, lane, *got = self._fetch_rows(
                    res, [carry["lpar"], carry["llane"], *front.values(),
                          *([out["inv_ok"]] if n_viol else [])], n_lvl)
                inv_ok = got.pop()[:, :n_lvl] if n_viol else None
                # batch-last state rows, cut to the level
                rows = {k: v[..., :n_lvl] for k, v in zip(front, got)}
            if self.store_states:
                # archives are stored batch-major numpy (host layout) —
                # decode/trace/_take row-index them; copies of the
                # level's rows alone, so the archive keeps no padding
                self._archive_level(
                    par[:n_lvl].copy(), lane[:n_lvl].copy(),
                    {k: np.moveaxis(v.copy(), -1, 0)
                     for k, v in rows.items()})
            if n_viol:
                rows = {k: np.moveaxis(v, -1, 0) for k, v in rows.items()}
                for j, nm in enumerate(self.inv_names):
                    for s in np.nonzero(~inv_ok[j])[0]:
                        vsv, vh = self.ir.decode(self.lay,
                                                 _take(rows, s))
                        res.violations.append(
                            Violation(nm, n_states + int(s),
                                      state=vsv, hist=vh))
            n_states += n_lvl
            n_vis += n_lvl
            # global state ids are device int32 (gids/lpar); fail loud
            # rather than wrap if a run ever approaches that scale
            driver.guard_id_space(n_states)
            return n_front

        # everything before the driver loop is one span: store init,
        # root dedup, the prewarm, the set-up program (or a resume's
        # carry load) and the root level's finalize + harvest
        with obs.span("check_setup"):
            if resume_from is not None:
                carry, res, meta = self._load_checkpoint(resume_from)
                # resume: the checkpointed carry is already device-resident
                # before the capacities are known, so this prewarm runs
                # beside it — a transient second carry allocation (resumes
                # are rare; a fresh start never pays it)
                prewarm(obs)
                n_states = meta["n_states"]
                n_vis = meta["n_vis"]
                depth = meta["depth"]
                n_front = meta["n_front"]
                resumed = True
            elif resume_image is not None:
                (carry, res, depth, n_states, n_vis,
                 n_front) = self._resume_portable(resume_image)
                prewarm(obs)
                resumed = True
            else:
                self._init_store()
                roots, rk, pin_interiors = self._dedup_roots(seed_states)
                n_roots = len(rk)

                res = CheckResult(distinct_states=0,
                                  generated_states=n_roots, depth=0)
                res.lanes_enabled = dict.fromkeys(fam_names, 0)
                self._check_pin_interiors(pin_interiors, res)
                while self.LCAP - self.OCAP < 2 * n_roots:
                    self.LCAP *= 2
                while n_roots + self.LCAP - self.OCAP > \
                        self._LOAD_MAX * self.VCAP:
                    self.VCAP *= 4
                # capacities final; warm BEFORE the real carry allocates
                prewarm(obs, roots, rk)
                # roots enter through the same admit path as every level:
                # placed in the level buffer + visited table, then
                # finalized.  One jitted set-up program allocates the
                # carry on the device, narrows the roots, evaluates their
                # invariants/constraints and places them (_setup_carry);
                # only the root rows, slots and keys cross to the device,
                # as that call's arguments.
                carry = self._setup_carry(roots, rk)
                n_states = 0
                n_vis = 0
                depth = 0
                resumed = False
            self._stamp_mode(res)
            if not resumed:
                carry, out, scal = run_finalize(carry)
                n_front = harvest(carry, out, scal)
        if stop_on_violation and res.violations:
            res.seconds = time.perf_counter() - t0
            obs.counters(_check_counters(
                res, self.expander.delta_family_names))
            return res

        # burst_ok gates the speculative burst entry: a burst that
        # committed levels and THEN bailed leaves the bailing level's
        # pre-level frontier intact, so re-entering the burst would
        # deterministically replay the same chunks and bail again — one
        # wasted round trip (the exact cost the burst cuts).  Skip the
        # burst for that one level; the per-level path re-arms it.
        burst_ok = True
        while n_front and depth < max_depth and \
                res.distinct_states < max_states:
            # chaos site: a dispatch-time device/runtime error at the
            # level boundary (resil/chaos).  Raised BEFORE any device
            # work, so the last checkpoint/archives stay consistent
            # and the supervised runner resumes bit-exact.
            chaos_point("dispatch")
            if self.burst and burst_ok and \
                    n_front <= self._burst_width():
                # small-level burst: run up to burst_levels levels in
                # one device call (see _burst_core).  nlev == 0 means
                # the very first level bailed on an overflow — fall
                # through and let the per-level path (with its growth
                # machinery) run that level.
                t1 = time.perf_counter()
                with obs.span("burst_dispatch"):
                    carry = grow_table_if_needed(
                        carry,
                        min_add=self.burst_levels * self._burst_width())
                    lv_left = min(self.burst_levels, max_depth - depth)
                    st_cap = max(1,
                                 min(max_states - res.distinct_states,
                                     2 ** 31 - 1))
                    carry, bout = self._burst_jit(
                        carry, self.FAM_CAPS, jnp.int32(lv_left),
                        jnp.int32(st_cap))
                    stats = np.asarray(bout["stats"])  # the ONE burst
                    # sync
                nlev = int(stats[-1, 0])
                bailed = bool(stats[-1, 1])
                res.burst_dispatches += 1
                res.burst_bailouts += int(bailed)
                viol_any = bool(stats[-1, 3])
                # the ring archives the harvest reads; the rest of the
                # burst's output is dropped now, not at the next burst
                ring = ([bout["par"], bout["lane"], *bout["st"].values(),
                         *([bout["inv"]] if viol_any else [])]
                        if nlev and (self.store_states or viol_any)
                        else None)
                st_keys = list(bout["st"])
                del bout
                if nlev:
                    burst_ok = not bailed
                    d0 = depth
                    n_front = int(stats[-1, 2])
                    with obs.span("harvest"):
                        par_h = lane_h = st_h = inv_h = None
                        if ring is not None:
                            # one packed read of the committed levels,
                            # cut to the widest level's rows
                            par_h, lane_h, *got = self._fetch_rows(
                                res, ring, int(stats[:nlev, 0].max()),
                                levels=nlev)
                            ring = None
                            if viol_any:
                                inv_h = got.pop()
                            st_h = dict(zip(st_keys, got))

                        def _arch(li, n_lvl):
                            if self.store_states:
                                self._archive_level(
                                    *driver.burst_archive_slice(
                                        par_h, lane_h, st_h, li,
                                        n_lvl))

                        def _viol(li, n_lvl, gid_base):
                            driver.burst_decode_violations(
                                res, self.ir, self.lay,
                                self.inv_names, inv_h, st_h, li,
                                n_lvl, gid_base)

                        def _vis(li, n_lvl):
                            nonlocal n_vis
                            n_vis += n_lvl

                        depth, n_states = driver.harvest_fused_levels(
                            res, nlev, lambda li: stats[li, :5],
                            depth, n_states, archive=_arch,
                            violations=_viol, visited=_vis)
                        # the committed levels' dedup counts and
                        # enabled lanes (columns 5+)
                        nd = 5 + len(DEDUP_COUNTER_KEYS)
                        _add_dedup(res, stats[:nlev, 5:nd].sum(axis=0))
                        _add_lanes(res, fam_names,
                                   stats[:nlev, nd:].sum(axis=0))
                    if checkpoint_path is not None and \
                            driver.ckpt_due_after_burst(
                                depth, d0, checkpoint_every):
                        self._save_checkpoint(checkpoint_path, carry,
                                              res, depth, n_states,
                                              n_vis, n_front)
                    obs.dispatch(kind="burst", depth=depth,
                                 frontier=n_front,
                                 metrics=res.metrics.as_dict())
                    if stop_on_violation and res.violations:
                        break
                    if verbose:
                        print(f"burst: {nlev} levels to depth {depth} "
                              f"(total {res.distinct_states}), "
                              f"frontier {n_front}, "
                              f"{time.perf_counter() - t1:.2f}s")
                    continue
            burst_ok = True        # re-arm after a per-level level
            depth += 1
            t1 = time.perf_counter()
            _lvl_span = obs.span("level_dispatch")
            _lvl_span.__enter__()
            carry = grow_table_if_needed(carry)
            while True:
                n_chunks = (n_front + self.chunk - 1) // self.chunk
                for _ in range(n_chunks):
                    carry = self._step_jit(carry, self.FAM_CAPS)
                carry, out, scal = run_finalize(carry)
                ovf, fovf, hovf, oovf = (bool(scal[4]), bool(scal[5]),
                                         bool(scal[8]), bool(scal[9]))
                if not (ovf or fovf or hovf or oovf):
                    break
                # buffer overflow: the finalize rolled the table back
                # and skipped its commit on device (frontier intact),
                # so grow and replay the level exactly.  Growth is 4x —
                # each growth step recompiles the fused kernels, so
                # fewer, larger steps.
                old_caps = (self.LCAP, self.FCAP, self.OCAP)
                if oovf:
                    # a chunk's FRESH rows outran the post-dedup
                    # compaction buffer; the true need is unknown (the
                    # revert fired first), so double toward FCAP
                    self.OCAP = self._round_cap(
                        min(self.FCAP, 2 * self.OCAP))
                if fovf:
                    # grow exactly the overflowing family caps (famx in
                    # the scal tail); grow FCAP only if the TOTAL
                    # enabled count blew the compaction buffer
                    famx = scal[11:11 + len(self.FAM_CAPS)]
                    caps = list(self.FAM_CAPS)
                    fam_over = False
                    for fi, fam in enumerate(self.expander.families):
                        hard = fam.n_lanes * self.chunk
                        while caps[fi] < hard and famx[fi] > caps[fi]:
                            caps[fi] = min(2 * caps[fi], hard)
                            fam_over = True
                    self.FAM_CAPS = tuple(caps)
                    if not fam_over:
                        # the TOTAL enabled count blew the compaction
                        # buffer.  Grow to what the measured per-family
                        # maxima need (Σfamx bounds any chunk's n_e),
                        # not a blind 4x: an oversized FCAP widens the
                        # fingerprint/dedup/append work of EVERY later
                        # chunk (a 4x overshoot measured ~4x slower
                        # steady-state on the membership config)
                        self.FCAP = self._round_cap(min(
                            self.chunk * self.A,
                            max(2 * self.FCAP,
                                (5 * int(sum(famx))) // 4)))
                if ovf or self.LCAP < 4 * self.OCAP:
                    # the append margin is OCAP now, so the LCAP floor
                    # couples to OCAP (an FCAP growth alone no longer
                    # forces a level-buffer rebuild)
                    self.LCAP = self._round_cap(
                        max((4 * self.LCAP) if ovf else self.LCAP,
                            4 * self.OCAP))
                if hovf:
                    # probe walk blew its round budget: table too full
                    self.VCAP *= 4
                    vis, claims = self._rehash_tables(carry["vis"],
                                                      self.VCAP)
                    carry = dict(carry, vis=vis, claims=claims)
                if verbose:
                    print(f"level {depth}: buffer overflow "
                          f"(ovf={ovf} fovf={fovf} hovf={hovf} "
                          f"oovf={oovf}), LCAP={self.LCAP} "
                          f"FCAP={self.FCAP} OCAP={self.OCAP} "
                          f"VCAP={self.VCAP}")
                if (self.LCAP, self.FCAP, self.OCAP) != old_caps:
                    carry = self._grow(carry, self.LCAP, self.VCAP)
                    # the replayed level can now add up to the NEW
                    # LCAP - FCAP keys: re-check the table load bound
                    # before replaying (a full table would spin the
                    # probe walk to its round budget)
                    carry = grow_table_if_needed(carry)
            _lvl_span.__exit__(None, None, None)
            with obs.span("harvest"):
                n_front = harvest(carry, out, scal)
            # per-family enabled maxima ride the scal tail every level;
            # keep the run-wide max as cap-sizing diagnostics
            # (tools/tune_config3.py reads this to pre-size FAM_CAPS)
            self.famx_max = [max(a, b) for a, b in zip(
                getattr(self, "famx_max", [0] * len(self.FAM_CAPS)),
                scal[11:11 + len(self.FAM_CAPS)])]
            # the shared depth gate (engine/driver docstring): an
            # all-pruned pseudo-level advances no depth; a real level
            # appends the post-constraint frontier size (the oracle's
            # metric)
            depth = driver.gate_level_depth(res, depth, scal[0],
                                            scal[6], scal[7])
            if checkpoint_path is not None and \
                    driver.ckpt_due_at_level(depth, checkpoint_every):
                self._save_checkpoint(checkpoint_path, carry, res,
                                      depth, n_states, n_vis, n_front)
            obs.dispatch(kind="level", depth=depth, frontier=n_front,
                         metrics=res.metrics.as_dict())
            if stop_on_violation and res.violations:
                break
            if verbose:
                print(f"depth {depth}: +{scal[0]} states "
                      f"(total {res.distinct_states}), "
                      f"frontier {n_front}, {n_chunks} chunks in "
                      f"{time.perf_counter() - t1:.2f}s")
        res.depth = depth
        res.seconds = time.perf_counter() - t0
        obs.counters(_check_counters(res,
                                     self.expander.delta_family_names))
        return res

    def _check_pin_interiors(self, interiors, res: CheckResult):
        """Invariant-check the replayed pinned-prefix interior states.

        TLC counts and invariant-checks every prefix state; seeding at
        the witness end skips them (models/golden docstring).  The
        interiors are already materialized by replay(), so check them
        here — a violation inside the pinned prefix gets reported with
        state_id=-1 (it has no BFS id) — and record the distinct count
        in CheckResult.pin_interior_states as the divergence bound."""
        if not interiors:
            return
        arrs = self.ir.widen(_cat([
            {k: v[None] for k, v in self.ir.encode(self.lay,
                                                   *s).items()}
            for s in interiors]))
        b = {k: jnp.asarray(v) for k, v in arrs.items()}
        keys = fp_key(np.asarray(self._rootfp_jit(b)))
        _uniq, first = np.unique(keys, return_index=True)
        first.sort()
        res.pin_interior_states = len(first)
        if not self.inv_names:
            return
        inv = np.asarray(self._phase2(b)[0])       # [B, n_inv]
        for j, nm in enumerate(self.inv_names):
            for s in np.nonzero(~inv[first, j])[0]:
                sv, h = interiors[int(first[s])]
                res.violations.append(
                    Violation(nm, -1, state=sv, hist=h))
                res.violations_global += 1

    # ------------------------------------------------------------------
    # checkpoint / resume (see the module-level ckpt_* serializer)
    # ------------------------------------------------------------------

    def _save_checkpoint(self, path, carry, res, depth, n_states,
                         n_vis, n_front):
        with self._obs.span("checkpoint"):
            parents, lanes, states, arch_meta = self._ckpt_store_args()
            ckpt_write(path, carry, self.store_states, parents,
                       lanes, states, res, dict(
                           depth=depth, n_states=n_states, n_vis=n_vis,
                           n_front=n_front, LCAP=self.LCAP,
                           VCAP=self.VCAP, FCAP=self.FCAP,
                           OCAP=self.OCAP,
                           fam_caps=list(self.FAM_CAPS), **arch_meta,
                           layout=2, chunk=self.chunk,
                           spec=self.ir.name,
                           sym_canon=self.fpr.sym_canon,
                           ir_fingerprint=self.ir.fingerprint(),
                           cfg=repr(self.cfg)),
                       keep=self.ckpt_keep)

    def _load_checkpoint(self, path):
        z, meta = ckpt_read(path, repr(self.cfg), self.chunk,
                            ("LCAP", "VCAP", "FCAP", "OCAP",
                             "fam_caps"),
                            expected_format=(
                                "layout", 2, "this engine's batch-last/"
                                "narrow-dtype storage layout"),
                            spec_name=self.ir.name,
                            sym_canon=self.fpr.sym_canon)
        self.LCAP, self.VCAP, self.FCAP, self.OCAP = (
            meta["LCAP"], meta["VCAP"], meta["FCAP"], meta["OCAP"])
        self.FAM_CAPS = tuple(int(c) for c in meta["fam_caps"])
        # eval_shape: the template is only read for structure/key paths,
        # never materialized (a real _fresh_carry would transiently
        # double device memory at resume)
        template = jax.eval_shape(
            lambda: self._fresh_carry(self.LCAP, self.VCAP, self.FCAP,
                                      self.OCAP))
        carry = ckpt_carry(path, z, template)
        self._load_archives(path, z, meta, template)
        res = ckpt_result(z, meta)
        z.close()             # all arrays extracted; don't leak the fd
        return carry, res, meta

    # ------------------------------------------------------------------
    # shape-portable resume (resil/portable round-12 contract): any
    # engine family's checkpoint re-homes into this engine's layout —
    # the key SET rebuilds the table image (membership is a set
    # property, slot layout never matters), the gid-ordered frontier
    # rows land in the level-buffer positions their contiguous ids
    # dictate, and archives/counters attach unchanged.  The pjit mesh
    # engine inherits this wholesale and re-partitions via
    # _commit_carry.
    # ------------------------------------------------------------------

    def _commit_carry(self, carry):
        """Final placement hook for host-assembled carries: identity
        here; parallel/pjit_mesh re-partitions onto its named
        shardings."""
        return carry

    def _seed_table_from_keys(self, keys_np: np.ndarray):
        """[N, W] u32 visited keys -> a fresh (vis, claims) pair at the
        CURRENT self.VCAP via the bulk lax claim walk (the reseed
        discipline of engine/spill: whole-cohort inserts stay on the
        lax path; dedup needs membership, not the original slot
        layout)."""
        n = int(keys_np.shape[0])
        nq = 1 << max(10, _ceil_log2(max(n, 2)))
        kq = np.full((self.W, nq), np.uint32(0xFFFFFFFF), np.uint32)
        if n:
            kq[:, :n] = keys_np.T
        VCAP, W = self.VCAP, self.W
        fn = getattr(self, "_seed_table_cache", None)
        if fn is None:
            fn = self._seed_table_cache = {}
        impl = fn.get((VCAP, nq))
        if impl is None:
            def build(keys, n):
                table = tuple(jnp.full((VCAP,), U32MAX)
                              for _ in range(W))
                claims = jnp.full((VCAP,), U32MAX)
                live = jnp.arange(nq, dtype=jnp.int32) < n
                ks = tuple(keys[w] for w in range(W))
                ranks = jnp.arange(nq, dtype=jnp.uint32)
                table, claims, _f, _p, hv = self._probe_insert(
                    table, claims, ks, live, ranks)
                return table, claims, hv
            impl = fn[(VCAP, nq)] = jax.jit(build)
        vis, claims, hv = impl(jnp.asarray(kq), jnp.int32(n))
        if bool(np.asarray(hv)):
            raise RuntimeError(
                "portable-resume table seed probe overflow — raise "
                "vcap")
        return vis, claims

    def _resume_portable(self, img):
        """PortableImage -> (carry, res, depth, n_states, n_vis,
        n_front).  Refuses images whose frontier gids are not
        contiguous (spill-family images drop pruned rows; this
        engine's frontier layout is the full last level under fmask)
        with a message naming the engine that can host them."""
        from ..resil.portable import validate_image
        validate_image(img, self.ir.name, repr(self.cfg), self.W)
        n_front = img.n_front
        if n_front:
            gids = np.asarray(img.gids, np.int64)
            pg_off = int(gids[0])
            if not np.array_equal(
                    gids, pg_off + np.arange(n_front, dtype=np.int64)):
                raise CheckpointError(
                    f"{img.source_path}: portable image's frontier "
                    "gids are not contiguous (a spill-family image "
                    "drops constraint-pruned rows); this engine's "
                    "frontier layout needs the full last level — "
                    "resume it with the spill engine "
                    "(check --spill --resume F --resume-portable)")
        else:
            pg_off = img.n_states
        # capacity sizing follows the fresh-start discipline
        # (capacities shape overflow replays, never counts)
        while self.LCAP - self.OCAP < 2 * max(n_front, 1):
            self.LCAP *= 2
        while img.n_vis + self.LCAP - self.OCAP > \
                self._LOAD_MAX * self.VCAP:
            self.VCAP *= 4
        self._restore_portable_archives(img)
        carry = self._fresh_carry(self.LCAP, self.VCAP)
        carry["vis"], carry["claims"] = self._seed_table_from_keys(
            img.keys)
        if n_front:
            rows_T = {k: np.moveaxis(np.asarray(v), 0, -1)
                      for k, v in img.rows.items()}
            carry["front"] = {
                k: v.at[..., :n_front].set(jnp.asarray(rows_T[k]))
                for k, v in carry["front"].items()}
            carry["fmask"] = carry["fmask"].at[:n_front].set(
                jnp.asarray(np.asarray(img.con, bool)))
        carry["n_front"] = jnp.int32(n_front)
        carry["pg_off"] = jnp.int32(pg_off)
        carry["g_off"] = jnp.int32(img.n_states)
        carry = self._commit_carry(carry)
        return (carry, img.fresh_result(), img.depth, img.n_states,
                img.n_vis, n_front)

    # ------------------------------------------------------------------

    def get_state(self, gid: int) -> Tuple:
        return self.ir.decode(self.lay, self.get_state_arrays(gid))

    def get_state_arrays(self, gid: int) -> Dict[str, np.ndarray]:
        assert self.store_states, "state store disabled"
        if self._arch is not None:
            return self._arch.state_row(gid)
        off = 0
        for blk in self._states:
            # any leaf's row count — key sets are spec-defined, so no
            # named key can be assumed here
            n = len(next(iter(blk.values())))
            if gid < off + n:
                return _take(blk, gid - off)
            off += n
        raise IndexError(gid)

    def trace(self, gid: int) -> List[Tuple]:
        if self._arch is not None:
            # memmap'd walk: each hop reads one parent/lane pair and
            # one state row — no level is ever loaded whole
            chain = []
            g = gid
            while g >= 0:
                par, lane = self._arch.parent_lane(g)
                label = self.labels[lane] if lane >= 0 else "Init"
                chain.append((label, self.get_state(g)[0]))
                g = par
            return list(reversed(chain))
        parents = np.concatenate(self._parents)
        lanes = np.concatenate(self._lanes)
        chain = []
        g = gid
        while g >= 0:
            lane = lanes[g]
            label = self.labels[lane] if lane >= 0 else "Init"
            chain.append((label, self.get_state(g)[0]))
            g = parents[g]
        return list(reversed(chain))
