"""TPU-native random-walk simulation engine: TLC ``-simulate``, vmapped.

BASELINE config #5-class spaces (Server=5, MaxTerm=4, MaxLogLen=4 with
scenario-property targets) sit orders of magnitude past the exhaustive
BFS stack even with the host-partitioned visited table, and the repo
had no analogue of TLC's ``-simulate`` mode.  This engine runs W
independent random walkers as ONE device program:

- per-walker ``jax.random`` key streams, keyed by GLOBAL walker id so a
  fixed ``seed`` replays bit-identical trajectories across runs AND
  across ``--walkers`` shardings (walker w's stream never depends on W
  or on the mesh shape — tests/test_sim.py pins this);
- uniform enabled-action sampling over the existing guard grid
  (engine/expand.guards_T + ops/kernels.select_enabled): the walker
  draws u ~ U[0, n_enabled) and takes the u-th enabled (action, server,
  param) lane — TLC ``-simulate``'s uniform successor choice on the
  same operator surface;
- per-walker step fusion (expand.Expander.step_lanes): one kernel
  application per FAMILY per walker instead of the full [B, A]
  candidate materialization;
- in-device invariant + scenario-predicate evaluation on every sampled
  successor (ops/vpredicates) — pruned states are checked then
  discarded, TLC's CONSTRAINT semantics;
- on-device trajectory recording: each walker's root-to-here lane ids
  live in a [traj_cap, W] buffer, so a scenario-hitting walker is
  decoded host-side into the same witness-trace format ``cli.py trace``
  emits (and into ``--seed-trace`` files — simulation FEEDS punctuated
  exhaustive search);
- a best-effort novelty Bloom filter over the fingerprints the
  exhaustive engines dedup on (engine/fingerprint.bloom_positions)
  reporting estimated distinct-state coverage.

Restart policies (the knob that decides what the fleet can reach):

``tlc``        — exact TLC ``-simulate`` shape: one uniform draw per
                 step; a pruned (CONSTRAINT-violating) successor, a
                 deadlock, or the depth bound abandons the walk and
                 restarts from the root.  Measured on config #5 this
                 finds nothing: under the Clean-start constraints the
                 mean walk dies in ~1.5 steps.
``punctuated`` — (default) two refinements, both preserving the
                 uniform per-step choice:
                 (a) prune-resampling: a pruned successor is checked,
                     then its lane is masked out and the walker redraws
                     uniformly among the REMAINING enabled lanes
                     (rejection sampling = uniform over the extendable
                     subset; measured 5 hits / 76 walks vs 0 / 209k
                     walks on a small membership scenario);
                 (b) per-walker progress bases: a walker restarts not
                     from the root but from its own best state on a
                     monotone scenario ladder (leader elected <
                     membership changes appended < latest-ConfigEntry
                     replication count), the in-engine analogue of the
                     spec's punctuated-search prefix pins
                     (raft.tla:1198-1234).  Measured on config #5 this
                     turns MembershipChangeCommits from unreachable
                     into a ~30k-step find.

The walker loop is a single ``lax.while_loop`` program running hundreds
of steps per dispatch — the persistent-kernel level-loop shape the
config #3/#4 dispatch-floor items call for: per dispatch the host syncs
one small stats vector, nothing else.

Differential anchor: models/explore.random_walk is the plain-Python
oracle twin; tests/test_sim.py replays engine trajectories through the
oracle transition relation step-for-step and pins the per-step enabled
counts (the sampling surface) against the oracle's successor counts.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..config import ModelConfig
from ..ops.kernels import select_enabled
from ..spec import spec_of
from ..engine.expand import Expander
from ..engine.fingerprint import (Fingerprinter, bloom_estimate,
                                  bloom_positions, resolve_sym_canon)

BLOOM_K = 2
# under FORCED min-over-perms (--sym-canon minperm), symmetry groups
# past this size pay more in per-step P-fold hashing than the novelty
# estimate is worth (the same threshold
# fingerprint.supports_incremental uses); the Bloom falls back to
# identity-permutation fingerprints, honestly labeled in the result.
# The orbit-sort canonicalizer (--sym-canon sort/auto, round 15)
# hashes ONE relabeling per state, so it keeps the Bloom canonical at
# ANY group size — this cap only gates the minperm path.
_BLOOM_CANONICAL_MAX_PERMS = 24


@dataclass
class WalkerHit:
    """One walker's scenario / invariant hit, decoded host-side."""
    invariant: str
    walker: int                  # global walker id
    depth: int                   # steps from the root (witness length)
    lanes: List[int]             # flat lane ids root -> hit state
    # (label, oracle-state) chain — the active spec's state type
    trace: List[Tuple] = field(default_factory=list)
    state_arrs: Optional[Dict[str, np.ndarray]] = None
    hist: Optional[object] = None


@dataclass
class SimResult:
    walkers: int
    steps_dispatched: int        # fleet-synchronous loop iterations
    walker_steps: int            # transitions taken (Σ accepted steps)
    sampled_steps: int           # successors sampled (incl. pruned)
    restarts: int
    deadlocks: int
    promotions: int              # progress-base advances (punctuated)
    seconds: float = 0.0
    hits: List[WalkerHit] = field(default_factory=list)
    bloom_bits_set: int = 0
    bloom_m_bits: int = 0
    bloom_saturated: bool = False
    bloom_canonical: bool = True  # False = identity-perm fingerprints
    est_distinct_states: float = 0.0

    @property
    def walker_steps_per_sec(self) -> float:
        return self.walker_steps / max(self.seconds, 1e-9)


# stats vector layout (int32 on device)
(ST_STEPS, ST_RESTARTS, ST_DEADLOCKS, ST_ITERS, ST_HIT, ST_SAMPLED,
 ST_PROMOS, ST_LEN) = range(8)


def dispatch_counters(stats2d: np.ndarray, walkers: int):
    """Per-dispatch ledger counters off the raw [n_shards, ST_LEN]
    stats matrix — the single stats→names mapping the sim ledger
    records use (key set pinned as obs.metrics.SIM_DISPATCH_KEYS, the
    subset of the SimResult counters knowable without a bloom fetch).
    Both sim engines (single-device and the pmapped fleet) call it, so
    their ledger schemas cannot drift."""
    return {
        "walkers": int(walkers),
        "steps_dispatched": int(stats2d[:, ST_ITERS].max()),
        "walker_steps": int(stats2d[:, ST_STEPS].sum()),
        "sampled_steps": int(stats2d[:, ST_SAMPLED].sum()),
        "restarts": int(stats2d[:, ST_RESTARTS].sum()),
        "deadlocks": int(stats2d[:, ST_DEADLOCKS].sum()),
        "promotions": int(stats2d[:, ST_PROMOS].sum()),
        "hits": int(stats2d[:, ST_HIT].sum()),
    }

class SimEngine:
    """W-walker random-walk explorer bound to one ModelConfig.

    walkers   — fleet width W (one vmapped lane per walker).
    max_depth — per-segment step budget: a walk restarts (to the root,
                or to its progress base under ``punctuated``) after
                this many steps beyond its base.
    traj_cap  — on-device trajectory buffer rows ([traj_cap, W] int32
                lanes from the ROOT); bounds the total witness depth.
    seed      — base PRNG seed; walker w uses fold_in(PRNGKey(seed), w)
                with w the GLOBAL walker id (see wid_base).
    policy    — 'punctuated' (default) or 'tlc' (see module docstring).
    bloom_bits— log2 of the novelty Bloom filter size in bits.
    wid_base  — global id of this engine's walker 0 (mesh shards pass
                d * walkers so streams are sharding-invariant).
    """

    _MAX_TRIES = 8               # prune-resampling rounds per step

    def __init__(self, cfg: ModelConfig, walkers: int = 256,
                 max_depth: int = 48, seed: int = 0,
                 policy: str = "punctuated",
                 traj_cap: Optional[int] = None,
                 bloom_bits: int = 22, wid_base: int = 0,
                 guard_matmul: bool = True,
                 delta_matmul: bool = True,
                 sym_canon: str = "auto"):
        if policy not in ("punctuated", "tlc"):
            raise ValueError(f"unknown restart policy {policy!r}")
        self.cfg = cfg
        self.W = int(walkers)
        self.budget = max(2, int(max_depth))
        self.R = int(traj_cap) if traj_cap else max(4 * self.budget, 64)
        self.seed = int(seed)
        self.policy = policy
        self.bloom_bits = int(bloom_bits)
        self.wid_base = int(wid_base)
        self.ir = spec_of(cfg)
        self.lay = self.ir.make_layout(cfg)
        self.kern = self.ir.make_kernels(self.lay)
        # the sim engine reuses select_enabled over the SAME guard grid
        # the exhaustive engines dispatch on, so the MXU guard-matrix
        # path (engine/expand docstring) drops in here unchanged:
        # guards_T becomes the int8 matmul, step_lanes' per-walker
        # param selection the one-hot einsum — trajectories are
        # bit-identical either way (tests/test_guard_matmul.py)
        # the delta-matmul successor path drops into step_lanes the
        # same way: affine-family walkers step through ONE group delta
        # matmul; trajectories are bit-identical either way
        # (tests/test_delta_matmul.py)
        self.guard_matmul = bool(guard_matmul)
        self.delta_matmul = bool(delta_matmul)
        self.expander = Expander(cfg, guard_matmul=self.guard_matmul,
                                 delta_matmul=self.delta_matmul)
        fp_cfg = cfg
        self.bloom_canonical = True
        mode = resolve_sym_canon(cfg, sym_canon)
        if cfg.symmetry and mode == "minperm":
            if len(self.ir.symmetry_perms(cfg)) > \
                    _BLOOM_CANONICAL_MAX_PERMS:
                import warnings
                warnings.warn(
                    f"--sym-canon minperm with "
                    f"{len(self.ir.symmetry_perms(cfg))} perms: the "
                    "novelty Bloom falls back to identity-permutation "
                    "fingerprints (bloom_canonical=false) — use "
                    "--sym-canon sort (or auto) to keep it canonical",
                    stacklevel=2)
                fp_cfg = cfg.with_(symmetry=False)
                self.bloom_canonical = False
        self.fpr = Fingerprinter(fp_cfg, sym_canon=mode)
        self.preds = self.ir.make_predicates(self.lay)
        # punctuated-restart progress ladder: a SpecIR hook (the raft
        # scenario ladder lives in spec/raft_ir.sim_progress); a spec
        # without one degrades punctuated to budget-only restarts
        self._progress_fn = (self.ir.sim_progress(self.kern, self.lay)
                             if self.ir.sim_progress else None)
        self.inv_names = list(cfg.invariants)
        self.con_names = list(cfg.constraints)
        self.act_names = list(cfg.action_constraints)
        self.labels = self.expander.lane_labels()
        self.A = self.expander.n_lanes
        self._root = self.ir.encode(self.lay,
                                    *self.ir.init_state(cfg))
        self._dispatch = jax.jit(self._dispatch_impl, donate_argnums=0,
                                 static_argnums=(1, 2))

    # ------------------------------------------------------------------
    # carry construction
    # ------------------------------------------------------------------

    def fresh_carry(self) -> Dict:
        W = self.W
        rootT = {k: jnp.asarray(np.repeat(
            np.asarray(v)[..., None], W, axis=-1))
            for k, v in self._root.items()}
        base = jax.random.PRNGKey(self.seed)
        wids = jnp.arange(self.wid_base, self.wid_base + W)
        keys = jax.vmap(lambda w: jax.random.fold_in(base, w))(wids)
        return dict(
            sv=rootT,                                   # [..., W] int32
            depth=jnp.zeros((W,), jnp.int32),           # from the ROOT
            key=keys,                                   # [W, 2] u32
            traj=jnp.full((self.R, W), -1, jnp.int32),
            # distinct buffers from sv: the dispatch donates the carry,
            # and aliased leaves would be donated twice
            base={k: v.copy() for k, v in rootT.items()},
            base_depth=jnp.zeros((W,), jnp.int32),
            score=jnp.zeros((W,), jnp.int32),
            hit=jnp.zeros((W,), bool),
            hit_inv=jnp.full((W,), -1, jnp.int32),
            hit_depth=jnp.full((W,), -1, jnp.int32),
            bloom=jnp.zeros((1 << self.bloom_bits,), bool),
            stats=jnp.zeros((ST_LEN,), jnp.int32),
        )

    # ------------------------------------------------------------------
    # predicates on batch-last rows (the engines' batch-minor shape)
    # ------------------------------------------------------------------

    def _phase2_T(self, svT):
        def one(sv):
            der = self.kern.derived(sv)
            inv = jnp.stack([self.preds.invariant_fn(nm)(sv, der)
                             for nm in self.inv_names]) \
                if self.inv_names else jnp.ones((0,), bool)
            con = jnp.bool_(True)
            for nm in self.con_names:
                con = con & self.preds.constraint_fn(nm)(sv, der)
            return inv, con
        return jax.vmap(one, in_axes=-1, out_axes=-1)(svT)

    def _progress_T(self, svT) -> jnp.ndarray:
        """Monotone scenario-ladder score [W] (the SpecIR sim_progress
        hook — raft: leader elected < membership changes appended <
        ConfigEntry replication; paxos: phase ladder).  Drives the
        ``punctuated`` restart bases; never consulted under ``tlc``.
        A spec without the hook scores every state 0 (punctuated
        degrades to budget-only restarts from the root)."""
        if self._progress_fn is None:
            return jnp.zeros((self.W,), jnp.int32)
        return self._progress_fn(svT)

    # ------------------------------------------------------------------
    # the fused step (shared by the single-device dispatch and the
    # pmapped fleet in parallel/sim_mesh.py)
    # ------------------------------------------------------------------

    def step(self, st: Dict) -> Dict:
        """One synchronous step of every walker; pure (jit/pmap-safe)."""
        W, A = self.W, self.A
        svT = st["sv"]
        frozen = st["hit"]
        derT = self.expander.derived_batch_T(svT)
        ok0 = self.expander.guards_T(svT, derT)             # [W, A]
        n_tries = self._MAX_TRIES if self.policy == "punctuated" else 1
        n_inv = len(self.inv_names)

        # ---- rejection-sampling rounds: draw a lane uniformly from
        # the remaining enabled set; a pruned successor is checked,
        # masked out, and redrawn (punctuated) or abandons the walk
        # (tlc).  All walkers run rounds in lockstep; each round costs
        # one fused step_lanes + predicate pass.
        def rcond(c):
            return (~c["done"]).any() & (c["tries"] < n_tries)

        def rbody(c):
            okm = c["okm"]
            n_en = okm.sum(axis=1, dtype=jnp.int32)
            active = ~c["done"] & (n_en > 0)
            splits = jax.vmap(jax.random.split)(c["key"])
            # a walker's key advances ONLY on its own draws — otherwise
            # the fleet-global resampling round count would leak into
            # every walker's stream and trajectories would depend on
            # the fleet width (tests pin sharding invariance)
            keys2 = jnp.where(active[:, None], splits[:, 0], c["key"])
            subs = splits[:, 1]
            u = jax.vmap(lambda k, n: jax.random.randint(
                k, (), 0, jnp.maximum(n, 1)))(subs, n_en)
            lane = jax.vmap(select_enabled)(okm, u)
            lane = jnp.where(active, lane, -1)
            cand = self.expander.step_lanes(svT, derT, lane)
            inv, con = self._phase2_T(cand)
            if n_inv:
                inv = inv | ~active[None]
                hitrow = ~inv.all(axis=0)
                hinv = jnp.argmax(~inv, axis=0).astype(jnp.int32)
            else:
                hitrow = jnp.zeros((W,), bool)
                hinv = jnp.full((W,), -1, jnp.int32)
            accept = active & con & ~hitrow
            reject = active & ~con & ~hitrow
            # mask the rejected lane out of the walker's enabled set
            li = jnp.clip(lane, 0, A - 1)
            okm = okm.at[jnp.arange(W), li].set(
                jnp.where(reject, False, okm[jnp.arange(W), li]))
            take = (accept | hitrow) & ~c["acc"]
            out = {k: jnp.where(take, cand[k], c["cand"][k])
                   for k in cand}
            lane_out = jnp.where(take, lane, c["lane"])
            return dict(
                okm=okm, key=keys2, cand=out, lane=lane_out,
                acc=c["acc"] | accept,
                hitrow=c["hitrow"] | hitrow,
                hinv=jnp.where(hitrow & (c["hinv"] < 0), hinv,
                               c["hinv"]),
                sampled=c["sampled"] + active.sum(dtype=jnp.int32),
                done=c["done"] | accept | hitrow | (n_en == 0),
                tries=c["tries"] + 1)

        c0 = dict(okm=ok0 & ~frozen[:, None], key=st["key"],
                  cand={k: v for k, v in svT.items()},
                  lane=jnp.full((W,), -1, jnp.int32),
                  acc=jnp.zeros((W,), bool),
                  hitrow=jnp.zeros((W,), bool),
                  hinv=jnp.full((W,), -1, jnp.int32),
                  sampled=jnp.int32(0),
                  done=frozen | (ok0.sum(axis=1) == 0),
                  tries=jnp.int32(0))
        c = lax.while_loop(rcond, rbody, c0)
        cand, lane = c["cand"], c["lane"]
        accepted = c["acc"]
        hit_now = c["hitrow"] & ~frozen
        took = accepted | hit_now                  # a lane was recorded
        deadlock = ~frozen & (ok0.sum(axis=1) == 0)
        # stuck = every enabled lane tried and pruned (or tries blown)
        stuck = ~frozen & ~took & ~deadlock

        # ---- trajectory record at the pre-step depth
        traj = st["traj"].at[st["depth"], jnp.arange(W)].set(
            jnp.where(took, lane, st["traj"][st["depth"],
                                            jnp.arange(W)]))

        # ---- novelty Bloom over the accepted rows' fingerprints
        fp = self.fpr.fingerprint_batch_T(cand)             # [T, W]
        pos = bloom_positions(fp, self.bloom_bits, BLOOM_K)  # [k, W]
        upd = jnp.where(accepted[None], pos,
                        jnp.int32(1 << self.bloom_bits)).reshape(-1)
        bloom = st["bloom"].at[upd].set(True, mode="drop")

        depth2 = jnp.where(took, st["depth"] + 1, st["depth"])
        hit_all = st["hit"] | hit_now

        # ---- punctuated progress bases
        if self.policy == "punctuated":
            score2 = self._progress_T(cand)
            promote = accepted & (score2 > st["score"]) & \
                (depth2 <= self.R - self.budget)
            base = {k: jnp.where(promote, cand[k], st["base"][k])
                    for k in cand}
            base_depth = jnp.where(promote, depth2, st["base_depth"])
            score = jnp.where(promote, score2, st["score"])
        else:
            promote = jnp.zeros((W,), bool)
            base, base_depth, score = (st["base"], st["base_depth"],
                                       st["score"])

        # ---- restart policy: segment budget blown, stuck, deadlock
        over = depth2 - base_depth >= self.budget
        restart = ~frozen & ~hit_now & \
            (deadlock | stuck | (accepted & over & ~promote))
        # stuck AT the base: demote the base to the root so the walker
        # cannot spin forever on an unextendable base
        demote = (stuck | deadlock) & (st["depth"] == base_depth)
        rootT = {k: jnp.asarray(np.asarray(v))[..., None]
                 for k, v in self._root.items()}
        base = {k: jnp.where(demote, rootT[k], base[k]) for k in base}
        base_depth = jnp.where(demote, 0, base_depth)
        score = jnp.where(demote, 0, score)

        sv_next = {k: jnp.where(restart, base[k],
                                jnp.where(accepted, cand[k], svT[k]))
                   for k in svT}
        depth3 = jnp.where(restart, base_depth, depth2)

        stats = st["stats"]
        stats = stats.at[ST_STEPS].add(accepted.sum(dtype=jnp.int32))
        stats = stats.at[ST_SAMPLED].add(c["sampled"])
        stats = stats.at[ST_RESTARTS].add(restart.sum(dtype=jnp.int32))
        stats = stats.at[ST_DEADLOCKS].add(
            deadlock.sum(dtype=jnp.int32))
        stats = stats.at[ST_PROMOS].add(promote.sum(dtype=jnp.int32))
        stats = stats.at[ST_ITERS].add(1)
        stats = stats.at[ST_HIT].set(hit_all.any().astype(jnp.int32))
        return dict(st, sv=sv_next, depth=depth3, key=c["key"],
                    traj=traj, base=base, base_depth=base_depth,
                    score=score, hit=hit_all,
                    hit_inv=jnp.where(hit_now & (st["hit_inv"] < 0),
                                      c["hinv"], st["hit_inv"]),
                    hit_depth=jnp.where(hit_now & (st["hit_depth"] < 0),
                                        depth2, st["hit_depth"]),
                    bloom=bloom, stats=stats)

    def _dispatch_impl(self, st: Dict, steps: int,
                       stop_on_hit: bool = True) -> Dict:
        """``steps`` walker steps in ONE device program (lax.while_loop
        — the persistent-kernel pattern: the host syncs only the stats
        vector per dispatch), exiting early on the first hit when
        stop_on_hit (hit walkers freeze either way)."""
        start = st["stats"][ST_ITERS]

        def cond(st):
            go = st["stats"][ST_ITERS] - start < steps
            if stop_on_hit:
                go = go & (st["stats"][ST_HIT] == 0)
            return go

        return lax.while_loop(cond, self.step, st)

    # ------------------------------------------------------------------
    # driver
    # ------------------------------------------------------------------

    def run(self, steps: int, steps_per_dispatch: int = 256,
            stop_on_hit: bool = True, verbose: bool = False,
            obs=None) -> SimResult:
        """Walk for up to ``steps`` synchronous fleet steps (early exit
        on the first scenario/invariant hit when stop_on_hit).

        obs — an ``obs.Obs`` bundle: one ledger record + heartbeat
        rewrite per device dispatch (the heartbeat's ``depth`` is the
        fleet-synchronous iteration count — a random walk has no BFS
        depth)."""
        from ..obs import NULL_OBS
        obs = obs if obs is not None else NULL_OBS
        t0 = time.perf_counter()
        # the step loop checks sampled SUCCESSORS; the root itself must
        # be checked once up front (a safety-invariant target can be
        # violated at depth 0 — check/trace report it there too)
        root_hit = self._check_root()
        if root_hit is not None and stop_on_hit:
            res = self._harvest(self.fresh_carry(),
                                time.perf_counter() - t0)
            res.hits.insert(0, root_hit)
            return res
        st = self.fresh_carry()
        done = 0
        while done < steps:
            k = min(steps_per_dispatch, steps - done)
            with obs.span("sim_dispatch"):
                st = self._dispatch(st, int(k), bool(stop_on_hit))
                stats = np.asarray(st["stats"])   # the ONE per-dispatch
                # sync
            done = int(stats[ST_ITERS])
            if obs.enabled:
                # light per-dispatch counters straight off the stats
                # vector (no bloom fetch mid-run); key set pinned by
                # obs.metrics.SIM_DISPATCH_KEYS
                obs.dispatch(
                    kind="sim", depth=done, frontier=self.W,
                    states=int(stats[ST_STEPS]),
                    metrics=dispatch_counters(stats[None], self.W))
            if verbose:
                print(f"sim: {done} iters, {int(stats[ST_STEPS])} "
                      f"walker-steps, {int(stats[ST_RESTARTS])} "
                      f"restarts, {int(stats[ST_PROMOS])} promotions",
                      flush=True)
            if stop_on_hit and stats[ST_HIT]:
                break
        res = self._harvest(st, time.perf_counter() - t0)
        if root_hit is not None:
            res.hits.insert(0, root_hit)
        return res

    def _check_root(self) -> Optional[WalkerHit]:
        """Evaluate the target invariants on the root state; a depth-0
        violation decodes like any other hit (empty lane list)."""
        if not self.inv_names:
            return None
        rootT = {k: jnp.asarray(np.asarray(v))[..., None]
                 for k, v in self._root.items()}
        inv, _con = self._phase2_T(rootT)
        inv = np.asarray(inv)[:, 0]
        if inv.all():
            return None
        return WalkerHit(
            invariant=self.inv_names[int(np.argmax(~inv))],
            walker=self.wid_base, depth=0, lanes=[])

    def build_result(self, stats2d: np.ndarray, union_bits: int,
                     walkers: int, seconds: float) -> SimResult:
        """Shared stats->SimResult assembly (this engine and the
        pmapped fleet): stats2d is [n_shards, ST_LEN]; iteration count
        is the max across shards (a hit exits one shard's loop early),
        everything else sums."""
        m = self.bloom_bits
        return SimResult(
            walkers=walkers,
            steps_dispatched=int(stats2d[:, ST_ITERS].max()),
            walker_steps=int(stats2d[:, ST_STEPS].sum()),
            sampled_steps=int(stats2d[:, ST_SAMPLED].sum()),
            restarts=int(stats2d[:, ST_RESTARTS].sum()),
            deadlocks=int(stats2d[:, ST_DEADLOCKS].sum()),
            promotions=int(stats2d[:, ST_PROMOS].sum()),
            seconds=seconds,
            bloom_bits_set=union_bits, bloom_m_bits=m,
            bloom_saturated=union_bits >= (1 << m) - 1,
            bloom_canonical=self.bloom_canonical,
            est_distinct_states=bloom_estimate(union_bits, m, BLOOM_K))

    def harvest_hits(self, res: SimResult, hit, traj, hdep, hinv,
                     wid_base: int):
        """Decode one shard's hit flags into WalkerHit entries (traj is
        [R, W] for that shard; global ids offset by wid_base)."""
        for w in np.nonzero(hit)[0]:
            d = int(hdep[w])
            res.hits.append(WalkerHit(
                invariant=self.inv_names[int(hinv[w])]
                if 0 <= int(hinv[w]) < len(self.inv_names) else "?",
                walker=wid_base + int(w), depth=d,
                lanes=[int(x) for x in traj[:d, w]]))

    def _harvest(self, st: Dict, seconds: float) -> SimResult:
        stats = np.asarray(st["stats"])
        bits = int(np.asarray(st["bloom"]).sum())
        res = self.build_result(stats[None], bits, self.W, seconds)
        hit = np.asarray(st["hit"])
        if hit.any():
            self.harvest_hits(res, hit, np.asarray(st["traj"]),
                              np.asarray(st["hit_depth"]),
                              np.asarray(st["hit_inv"]), self.wid_base)
        return res

    # ------------------------------------------------------------------
    # host-side witness decoding: replay the recorded lanes from the
    # root through the single-state expander (bit-identical to the
    # device step — same kernels, same params), producing the
    # (label, State) chain cli.py trace prints and the exact SoA arrays
    # --emit-seed needs.
    # ------------------------------------------------------------------

    def decode_hit(self, h: WalkerHit) -> WalkerHit:
        arrs = {k: np.asarray(v) for k, v in self._root.items()}
        chain: List[Tuple] = [
            ("Init", self.ir.decode(self.lay, arrs)[0])]
        for lane in h.lanes:
            enabled = self.expander.expand_one(arrs)
            match = [sv2 for (lbl, sv2) in enabled
                     if lbl == self.labels[lane]]
            if not match:
                raise RuntimeError(
                    f"sim replay divergence: lane {lane} "
                    f"({self.labels[lane]}) not enabled at depth "
                    f"{len(chain) - 1}")
            arrs = match[0]
            chain.append((self.labels[lane],
                          self.ir.decode(self.lay, arrs)[0]))
        h.trace = chain
        h.state_arrs = arrs
        h.hist = self.ir.decode(self.lay, arrs)[1]
        return h
