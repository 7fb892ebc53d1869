"""Host-spill BFS engine: levels stream through host RAM, breaking the
single-chip HBM exhaustion wall (SURVEY §7.2 L4 "spill/compact to host";
VERDICT r3 #1).

The classic Engine (engine/bfs) keeps the frontier and the level buffer
device-resident, which caps level-exact runs at the deepest level whose
~340 B/state buffers fit HBM next to the visited table (measured:
depth 19 on BASELINE config #2, depth 21 on #1 — BASELINE.md
"exhaustion wall").  TLC never has this wall: its fingerprint set and
state queue spill to disk (`states/`, /root/reference/.gitignore:4).

This engine is the TPU counterpart, shaped by host<->device transfer
economics (big transfers amortize the fixed round-trip cost;
per-chunk scalar syncs do not):

- HBM holds ONLY the visited table (12 B/key at fp64 — the one
  structure whose random-access probes need device residency) plus two
  SEGMENT buffers: a frontier segment being expanded and a level
  segment being filled.
- The frontier lives in host RAM as a list of narrow batch-last
  blocks; segments upload whole (one big H2D per ~SEG states).
- Fresh states append to the level segment on device; when it fills
  (or the level ends) it spills whole to the host (one big D2H),
  becoming both the next-frontier source and the trace archive.
- The host syncs ONE small summary vector every `sync_every` chunks
  (not per chunk): JAX only transfers what is forced, so the
  intermediate summaries are never fetched.

Overflow recovery is CHUNK-local (the classic engine's whole-level
journal replay is impossible once earlier segments have spilled): a
chunk that trips any overflow — level segment full (ovf), family/
compaction caps (fovf), probe-round budget (hovf) — reverts its own
table inserts in-step and leaves no trace; every later chunk in the
sync window sees the sticky flag and does nothing.  The host then
fixes the cause (spill the segment / grow caps / grow+rehash the
table), resets the flags, and resumes from the recorded trip chunk —
enumeration order is exactly preserved, so counts and first-seen
survivors match the classic engine and the oracle bit-for-bit.

Constraint semantics stay prune-not-expand (SURVEY §2.8): pruned rows
are counted, invariant-checked and archived, then dropped on host when
the next frontier is assembled (the classic engine keeps them device-
side under an fmask instead — same reachable set, differentially
tested in tests/test_spill.py).

What this buys: the depth wall moves from "level buffers fit HBM"
(~8.5 GB at depth 20 on config #2) to "visited table fits HBM" —
~12 B/key lets ~400M distinct states on a 16 GB chip, with level
buffers bounded by the 125 GB host.  The native C++ checker OOMs the
same host at ~65 GB RSS (~650 B/state) long before that — BASELINE.md
round-4 records the beyond-the-wall rows this engine produced.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..config import ModelConfig
from ..ops.codec import C_OVERFLOW
from ..obs import NULL_OBS
from . import driver
from .bfs import (CheckResult, CheckpointError, Engine, U32MAX,
                  _HOME_SALT, Violation, ckpt_read, ckpt_result,
                  ckpt_write)
from .fingerprint import fmix32
from .host_table import HostPartitionedTable, insert_np
from ..resil.chaos import chaos_point

# summary vector layout (int32): the per-window device->host sync
(S_NLVL, S_NGEN, S_OVF, S_FOVF, S_HOVF, S_OOVF, S_TRIP, S_OFX,
 S_LEN) = range(9)


class SpillEngine(Engine):
    """Engine whose frontier/level buffers stream through host RAM.

    chunk      — frontier states expanded per fused device call.
    seg        — level/frontier segment capacity (states); HBM holds
                 ~2 segments x ~340 B/state next to the visited table.
    vcap       — initial visited-table slots (grows by device rehash).
    sync_every — chunks between summary syncs (each sync costs one
                 host round trip; a trip replays at most this many
                 chunks).
    """

    def __init__(self, cfg: ModelConfig, chunk: int = 2048,
                 store_states: bool = False, seg: int = 1 << 21,
                 vcap: int = 1 << 22, fcap: Optional[int] = None,
                 ocap: Optional[int] = None, sync_every: int = 8,
                 host_table: bool = False, partitions: int = 4,
                 part_cap: int = 1 << 12,
                 dev_keys: Optional[int] = None,
                 sweep_stage: bool = True,
                 burst: bool = True,
                 burst_levels: Optional[int] = None,
                 archive_dir: Optional[str] = None,
                 guard_matmul: bool = True,
                 delta_matmul: bool = True,
                 fam_density: Optional[Dict[str, int]] = None,
                 sym_canon: str = "auto"):
        # burst (fused multi-level dispatch) is ON by default since
        # round 8 — the tiny early levels of a deep spill run pay the
        # same dispatch floor as the classic engine's; pass
        # burst=False to force the pure per-level/segment driver
        # (tests/test_burst.py pins the A/B)
        super().__init__(cfg, chunk=chunk, store_states=store_states,
                         lcap=seg, vcap=vcap, fcap=fcap, ocap=ocap,
                         burst=burst, burst_levels=burst_levels,
                         archive_dir=archive_dir,
                         guard_matmul=guard_matmul,
                         delta_matmul=delta_matmul,
                         fam_density=fam_density,
                         sym_canon=sym_canon)
        self.SEGL = self.LCAP          # level segment rows (can grow)
        self.SEGF = self.LCAP          # frontier segment rows (fixed)
        self.sync_every = max(1, int(sync_every))
        # host-partitioned visited table (VERDICT r4 missing #1;
        # engine/host_table module docstring): the authoritative
        # visited set lives in host RAM as P fingerprint-prefix
        # partitions, swept through HBM partition-by-partition at level
        # boundaries; the HBM table degrades to a bounded CACHE of
        # recent levels' keys.  The cache is complete over the running
        # level (it grows mid-level if it must), so level keys reach
        # the sweep already unique and in enumeration order; the cache
        # can only err fresh-ward (an evicted key re-admitted), never
        # suppress a truly-new state, so the sweep's membership verdict
        # keeps counts EXACT — no collision class is added beyond the
        # fingerprints themselves.  The exhaustive ceiling moves from
        # "total distinct keys fit the HBM table" (~214M fp64 on
        # 16 GB) to "one partition image + one level's keys fit it",
        # bounded by host RAM at 20-80 B/key fp64 (8 B/slot images
        # between the 0.40 load bound and a fresh 4x growth).
        # TLC's disk-spillable fingerprint set is the reference
        # behavior (SURVEY §5).
        self.host_table = bool(host_table)
        self.partitions = int(partitions)
        self.part_cap = int(part_cap)
        self.VCAP0 = self.VCAP         # reseed resets the cache here
        # cache budget: past this occupancy at a level boundary the
        # device table resets and reseeds with the frontier's keys
        # (the only keys the next level's expansion re-generates at
        # high rate); everything older answers from the host sweep
        self.dev_keys = (int(dev_keys) if dev_keys
                         else int(self._LOAD_MAX * self.VCAP))
        self.hpt = None                # built per check()/resume
        # double-buffered pre-sweep H2D staging (round 14): the next
        # level's partition-image uploads are ISSUED at level start, so
        # the DMA rides the host link while the level's chunks compute
        # instead of serializing after them inside the sweep
        # (_stage_sweep_images; h2d_stage/sweep_overlap spans make the
        # overlap visible in the PR-7 ledger/timeline).  At most
        # _SWEEP_STAGE_DEPTH images are in flight (double-buffering —
        # HBM holds the staged image next to the sweep's own working
        # set); a staged image serves a sweep only when its partition's
        # mutation version still matches (host_table.vers), so growth
        # or commit can never hand the device a stale membership image.
        self.sweep_stage = bool(sweep_stage)
        self._sweep_staged = {}        # partition -> (dev_img, version)
        self.sweep_stage_hits = 0      # sweeps served from a prestage
        self.sweep_stage_misses = 0    # inline (serialized) uploads
        self._paste_cache = {}         # upload-paste jit per block size
        self._slice_cache = {}         # spill-slice jit per block size
        self._ckpt_sparse_cache = {}   # sparse-table jit per size
        self._seed_cache = {}          # table-reseed jit per size
        self._member_cache = {}        # sweep-membership jit per shape
        self._sstep_jit = jax.jit(self._spill_step_impl,
                                  donate_argnums=0, static_argnums=1)
        # spill-aware fused multi-level burst (engine/bfs._burst_core
        # over standalone ring buffers — the spill carry's segment
        # shapes never enter the loop).  fcap rides as a static arg:
        # unlike the classic wrapper there is no carry-shape anchor, so
        # an FCAP growth must force a retrace explicitly.
        self._spill_burst_jit = jax.jit(self._spill_burst_call,
                                        donate_argnums=(0, 1),
                                        static_argnums=(7, 8, 9))

    # ------------------------------------------------------------------
    # fused per-chunk step (spill twin of Engine._chunk_step_impl)
    # ------------------------------------------------------------------

    def _spill_step_impl(self, carry, fam_caps):
        """One frontier chunk: expand + fingerprint (shared front half
        _expand_fp_chunk) + claim-insert dedup + invariant/constraint
        eval + append to the level segment.  Returns (carry', summary).

        Chunk-local overflow discipline (module docstring): a chunk
        that trips ovf/fovf/hovf reverts its own inserts and commits
        nothing; `trip_base` records the first tripping chunk's frontier
        cursor so the host can resume exactly there after fixing."""
        B, A, W = self.chunk, self.A, self.W
        SEGL = carry["lpar"].shape[0]
        FCAP = carry["cidx"].shape[0]
        OCAP = carry["oidx"].shape[0]
        VCAP = carry["vis"][0].shape[0]
        base = carry["base"]
        sv = self.ir.widen({k: lax.dynamic_slice_in_dim(v, base, B,
                                                axis=v.ndim - 1)
                    for k, v in carry["front"].items()})
        # no fmask: constraint-pruned rows never enter the frontier
        # (host compacts them away — prune-not-expand is host-side)
        valid = (base + jnp.arange(B, dtype=jnp.int32)) < carry["n_front"]
        cand_c, elive, fp, take, famx_c, n_e = self._expand_fp_chunk(
            sv, valid, fam_caps, FCAP)
        famx = jnp.maximum(carry["famx"], famx_c)
        fovf_now = (n_e > FCAP) | \
            jnp.any(famx_c > jnp.asarray(fam_caps, jnp.int32))
        gate = ~(carry["ovf"] | carry["fovf"] | carry["hovf"] |
                 carry["oovf"])
        live = elive & gate & ~fovf_now

        keys = tuple(jnp.where(live, fp[w], U32MAX) for w in range(W))
        ranks = jnp.arange(FCAP, dtype=jnp.uint32)
        table, claims, fresh, pos, hovf_now = self._probe_insert(
            carry["vis"], carry["claims"], keys, live, ranks)
        n_fresh = fresh.sum(dtype=jnp.int32)
        ovf_now = gate & (carry["n_lvl"] + n_fresh > SEGL - OCAP)
        oovf_now = gate & (n_fresh > OCAP)
        bad_now = gate & (fovf_now | hovf_now | ovf_now | oovf_now)
        # revert THIS chunk's inserts on any trip — the chunk leaves no
        # trace, so the host replay re-runs it bit-identically
        ridx = jnp.where(fresh & bad_now, pos, VCAP)
        table = tuple(table[w].at[ridx].set(U32MAX, mode="drop")
                      for w in range(W))
        fresh = fresh & ~bad_now
        n_fresh = jnp.where(bad_now, 0, n_fresh)
        commit = gate & ~bad_now
        n_gen = carry["n_gen"] + \
            jnp.where(commit, elive.sum(dtype=jnp.int32), 0)
        trip_base = jnp.where(gate & bad_now, base, carry["trip_base"])

        # contiguous append of the fresh rows, post-dedup-compacted to
        # OCAP width (engine/bfs layout + second-compaction notes)
        slot = jnp.arange(FCAP, dtype=jnp.int32)
        lpos = jnp.where(fresh,
                         jnp.cumsum(fresh.astype(jnp.int32)) - 1, OCAP)
        lidx = lax.optimization_barrier(
            jnp.zeros((OCAP,), jnp.int32).at[lpos].set(
                slot, mode="drop"))
        start = jnp.minimum(carry["n_lvl"], SEGL - OCAP)
        lane = take[lidx]
        rows = lax.optimization_barrier(
            {k: cand_c[k][..., lidx] for k in cand_c})
        inv, con = lax.optimization_barrier(self._phase2_T(rows))
        rows_n = self.ir.narrow(self.lay, rows)
        lvl = {k: lax.dynamic_update_slice_in_dim(
                   v, rows_n[k], start, v.ndim - 1)
               for k, v in carry["lvl"].items()}
        # parent ids come from the uploaded per-row global ids (the
        # host-compacted frontier breaks the classic engine's
        # pg_off+row arithmetic)
        lpar = lax.dynamic_update_slice_in_dim(
            carry["lpar"], carry["gids"][base + lane // A], start, 0)
        llane = lax.dynamic_update_slice_in_dim(
            carry["llane"], lane % A, start, 0)
        linv = lax.dynamic_update_slice_in_dim(carry["linv"], inv,
                                               start, 1)
        lcon = lax.dynamic_update_slice_in_dim(
            carry["lcon"], con, start, 0)
        extra = {}
        if self.host_table:
            # the appended rows' fingerprints ride the spill (8 B/state
            # fp64): they feed the host archive check and the device-
            # table reseed at level boundaries
            extra["lfp"] = lax.dynamic_update_slice(
                carry["lfp"], fp[:, lidx], (0, start))
        n_lvl = jnp.minimum(carry["n_lvl"] + n_fresh, SEGL - OCAP)
        ovf = carry["ovf"] | ovf_now
        fovf = carry["fovf"] | (gate & fovf_now)
        hovf = carry["hovf"] | (gate & hovf_now)
        oovf = carry["oovf"] | oovf_now
        ofx = jnp.maximum(carry["ofx"], n_fresh)
        summary = jnp.concatenate([jnp.stack([
            n_lvl, n_gen, ovf.astype(jnp.int32), fovf.astype(jnp.int32),
            hovf.astype(jnp.int32), oovf.astype(jnp.int32),
            trip_base, ofx]), famx])
        new_carry = dict(carry, vis=table, claims=claims, lvl=lvl,
                         lpar=lpar, llane=llane, linv=linv, lcon=lcon,
                         n_lvl=n_lvl, n_gen=n_gen, famx=famx, ovf=ovf,
                         fovf=fovf, hovf=hovf, oovf=oovf, ofx=ofx,
                         trip_base=trip_base, base=base + B, **extra)
        return new_carry, summary

    # ------------------------------------------------------------------

    def _fresh_spill_carry(self):
        one = self.ir.narrow(self.lay, self.ir.encode(
            self.lay, *self.ir.init_state(self.cfg)))
        lvl = {k: jnp.zeros(v.shape + (self.SEGL,), dtype=v.dtype)
               for k, v in one.items()}
        front = {k: jnp.zeros(v.shape + (self.SEGF,), dtype=v.dtype)
                 for k, v in one.items()}
        n_inv = len(self.inv_names)
        extra = {}
        if self.host_table:
            extra["lfp"] = jnp.full((self.W, self.SEGL), U32MAX)
        return dict(
            vis=tuple(jnp.full((self.VCAP,), U32MAX)
                      for _ in range(self.W)),
            claims=jnp.full((self.VCAP,), U32MAX),
            lvl=lvl,
            **extra,
            lpar=jnp.full((self.SEGL,), -1, jnp.int32),
            llane=jnp.full((self.SEGL,), -1, jnp.int32),
            linv=jnp.ones((n_inv, self.SEGL), bool),
            lcon=jnp.ones((self.SEGL,), bool),
            front=front,
            gids=jnp.full((self.SEGF,), -1, jnp.int32),
            cidx=jnp.zeros((self.FCAP,), jnp.int32),  # FCAP anchor
            oidx=jnp.zeros((self.OCAP,), jnp.int32),  # OCAP anchor
            n_front=jnp.int32(0),
            base=jnp.int32(0),
            n_lvl=jnp.int32(0),
            n_gen=jnp.int32(0),
            famx=jnp.zeros((len(self.expander.families),), jnp.int32),
            ovf=jnp.bool_(False),
            fovf=jnp.bool_(False),
            hovf=jnp.bool_(False),
            oovf=jnp.bool_(False),
            ofx=jnp.int32(0),       # max fresh rows in any chunk
            trip_base=jnp.int32(-1),
        )

    def _reset_lvl_buffers(self, carry):
        """Fresh level-segment buffers at the CURRENT self.SEGL/FCAP
        (used after a cap growth changed shapes; plain n_lvl reset
        suffices otherwise)."""
        one = self.ir.narrow(self.lay, self.ir.encode(
            self.lay, *self.ir.init_state(self.cfg)))
        carry["lvl"] = {k: jnp.zeros(v.shape + (self.SEGL,),
                                     dtype=v.dtype)
                        for k, v in one.items()}
        carry["lpar"] = jnp.full((self.SEGL,), -1, jnp.int32)
        carry["llane"] = jnp.full((self.SEGL,), -1, jnp.int32)
        carry["linv"] = jnp.ones((len(self.inv_names), self.SEGL), bool)
        carry["lcon"] = jnp.ones((self.SEGL,), bool)
        carry["cidx"] = jnp.zeros((self.FCAP,), jnp.int32)
        carry["oidx"] = jnp.zeros((self.OCAP,), jnp.int32)
        carry["n_lvl"] = jnp.int32(0)
        if self.host_table:
            carry["lfp"] = jnp.full((self.W, self.SEGL), U32MAX)
        return carry

    def _prewarm_perlevel(self):
        """Spill twin of Engine._prewarm_perlevel: one dummy streamed
        chunk step on an empty spill carry warms the executable the
        segment driver falls back to when a burst bails."""
        dummy = self._fresh_spill_carry()
        dummy, _s = self._sstep_jit(dummy, self.FAM_CAPS)
        del dummy

    # ------------------------------------------------------------------
    # host-side level plumbing
    # ------------------------------------------------------------------

    def _spill_segment(self, carry, n_lvl: int):
        """Start an ASYNC fetch of the filled rows of the level segment
        and reset the device cursor.  Returns (carry, blk) where blk is
        a PENDING block: its arrays are device-side copies with
        copy_to_host_async in flight — the device keeps crunching the
        next chunks while the DMA drains; _materialize_blk turns it
        into host numpy (cheap once the DMA lands).

        Slice lengths quantize up to _spill_quantum multiples: a
        python-int slice compiles one executable per distinct length,
        and each compile costs seconds — quantizing
        bounds the shape set to ~8 per SEGL.  The device-side slice is
        a real copy op sequenced BEFORE later donated steps overwrite
        the segment buffer, so the async host copy reads stable data."""
        blk = None
        if n_lvl:
            nq = self._quantize(n_lvl, self.SEGL)
            fn = self._slice_cache.get(nq)
            if fn is None:
                # a jit'd slice (not donated) ALWAYS yields fresh
                # buffers — a bare v[..., :nq] at nq == SEGL is an
                # identity view of the live segment buffer, which the
                # next donated step would delete out from under the
                # pending async copy
                def impl(lvl, lpar, llane, linv, lcon, lfp=None,
                         nq=nq):
                    out = dict(
                        rows={k: lax.slice_in_dim(v, 0, nq, axis=v.ndim - 1)
                              for k, v in lvl.items()},
                        lpar=lax.slice_in_dim(lpar, 0, nq, axis=0),
                        llane=lax.slice_in_dim(llane, 0, nq, axis=0),
                        linv=lax.slice_in_dim(linv, 0, nq, axis=1),
                        lcon=lax.slice_in_dim(lcon, 0, nq, axis=0))
                    if lfp is not None:
                        # the rows' fingerprints ride the spill: they
                        # feed the host-partition sweep and the cache
                        # reseed (host-table mode only)
                        out["lfp"] = lax.slice_in_dim(lfp, 0, nq,
                                                      axis=1)
                    return out
                fn = self._slice_cache[nq] = jax.jit(impl)
            dev = fn(carry["lvl"], carry["lpar"], carry["llane"],
                     carry["linv"], carry["lcon"],
                     carry["lfp"] if self.host_table else None)
            for leaf in jax.tree_util.tree_leaves(dev):
                leaf.copy_to_host_async()
            blk = dict(_dev=dev, n=n_lvl)
        carry["n_lvl"] = jnp.int32(0)
        return carry, blk

    @staticmethod
    def _quantize(n: int, cap: int, floor: int = 1 << 12) -> int:
        """Round a row count up to a power of two in [floor, cap]:
        transfer/slice programs compile once per SIZE, and host<->
        device bandwidth is finite — a 7-row early-level segment must not ship (or
        slice) the full multi-GB buffer (measured 30-70 s per tiny
        level when it did)."""
        q = floor
        while q < n:
            q *= 2
        return min(q, cap)

    @staticmethod
    def _materialize_blk(blk):
        """Resolve a pending spill block to host numpy, trimming the
        quantization padding with real copies — a view would pin the
        up-to-2x-padded base arrays in host RAM for as long as the
        block lives in the next frontier (the deep runs this engine
        exists for are host-RAM bound); idempotent."""
        if blk is None or "_dev" not in blk:
            return blk
        dev = blk.pop("_dev")
        n = blk["n"]

        def trim(v, axis):
            a = np.asarray(v)
            if a.shape[axis] == n:
                return a
            return np.ascontiguousarray(
                a[(slice(None),) * axis + (slice(0, n),)])
        blk["rows"] = {k: trim(v, v.ndim - 1)
                       for k, v in dev["rows"].items()}
        blk["lpar"] = trim(dev["lpar"], 0)
        blk["llane"] = trim(dev["llane"], 0)
        blk["linv"] = trim(dev["linv"], 1)
        blk["lcon"] = trim(dev["lcon"], 0)
        if "lfp" in dev:
            blk["lfp"] = trim(dev["lfp"], 1)
        return blk

    def _stage_segment(self, seg_rows: Dict[str, np.ndarray],
                       seg_gids: np.ndarray):
        """Issue the H2D transfers for a frontier segment NOW (padded
        to the next size QUANTUM, not to SEGF — a tiny early-level
        segment must not ship the full multi-GB buffer to the
        device) without touching the carry: called one segment
        AHEAD, so the DMA overlaps while the device crunches
        the current segment (the double-buffering half of VERDICT r4
        #4)."""
        n = int(seg_gids.shape[0])
        nq = self._quantize(n, self.SEGF)
        pad = nq - n
        blocks = {}
        for k, v in seg_rows.items():
            if pad:
                v = np.concatenate(
                    [v, np.zeros(v.shape[:-1] + (pad,), v.dtype)],
                    axis=-1)
            blocks[k] = jax.device_put(v)
        gids = np.full((nq,), -1, np.int32)
        gids[:n] = seg_gids
        return dict(blocks=blocks, gids=jax.device_put(gids), n=n,
                    nq=nq)

    def _swap_in_segment(self, carry, staged):
        """Paste the staged (already device-resident) quantized block
        into the persistent SEGF-shaped frontier buffers — one small
        donated-DUS program per block size, cached.  Rows past n_front
        are stale garbage from earlier segments; the step's valid mask
        bounds them."""
        nq = staged["nq"]
        fn = self._paste_cache.get(nq)
        if fn is None:
            def impl(front, gids, blocks, bg):
                front = {k: lax.dynamic_update_slice_in_dim(
                    v, blocks[k], 0, v.ndim - 1)
                    for k, v in front.items()}
                return front, lax.dynamic_update_slice_in_dim(
                    gids, bg, 0, 0)
            fn = self._paste_cache[nq] = jax.jit(
                impl, donate_argnums=(0, 1))
        carry["front"], carry["gids"] = fn(
            carry["front"], carry["gids"], staged["blocks"],
            staged["gids"])
        carry["n_front"] = jnp.int32(staged["n"])
        carry["base"] = jnp.int32(0)
        return carry, staged["n"]

    @staticmethod
    def _resegment(blocks: List, seg: int):
        """Yield (rows, gids) segments of <= seg rows from frontier
        blocks [(rows dict batch-last, gids)], concatenating across
        block boundaries."""
        buf_rows, buf_gids, have = [], [], 0
        for rows, gids in blocks:
            n = int(gids.shape[0])
            off = 0
            while off < n:
                take_n = min(seg - have, n - off)
                buf_rows.append({k: v[..., off:off + take_n]
                                 for k, v in rows.items()})
                buf_gids.append(gids[off:off + take_n])
                have += take_n
                off += take_n
                if have == seg:
                    yield SpillEngine._cat_seg(buf_rows, buf_gids)
                    buf_rows, buf_gids, have = [], [], 0
        if have:
            yield SpillEngine._cat_seg(buf_rows, buf_gids)

    @staticmethod
    def _cat_seg(buf_rows, buf_gids):
        if len(buf_rows) == 1:
            return buf_rows[0], buf_gids[0]
        keys = buf_rows[0].keys()
        return ({k: np.concatenate([b[k] for b in buf_rows], axis=-1)
                 for k in keys}, np.concatenate(buf_gids))

    # ------------------------------------------------------------------
    # host-partitioned table: the per-level partition sweep and the
    # device-cache reseed (engine/host_table module docstring)
    # ------------------------------------------------------------------

    def _member_fn(self, cap: int, nq: int):
        """Jit'd gathers-only membership probe of nq keys against a
        cap-slot partition image (one cache entry per shape pair):
        the device half of the sweep — same home hash and quadratic
        walk as _probe_insert, no writes."""
        fn = self._member_cache.get((cap, nq))
        if fn is None:
            W = self.W
            MAXR = self._MAX_PROBE_ROUNDS

            def impl(img, keys, n):
                live = jnp.arange(nq, dtype=jnp.int32) < n
                h = jnp.full((nq,), _HOME_SALT, jnp.uint32)
                for w in range(W):
                    h = fmix32(h ^ keys[w])
                pos = (h & jnp.uint32(cap - 1)).astype(jnp.int32)

                def classify(pos):
                    iskey = jnp.ones((nq,), bool)
                    isempty = jnp.ones((nq,), bool)
                    for w in range(W):
                        cur = img[w, pos]
                        iskey &= cur == keys[w]
                        isempty &= cur == U32MAX
                    return iskey, isempty

                def cond(st):
                    _p, _t, act, _f, r = st
                    return act.any() & (r < MAXR)

                def body(st):
                    pos, t, act, found, r = st
                    iskey, isempty = classify(pos)
                    found = found | (act & iskey)
                    act = act & ~(iskey | isempty)
                    t = jnp.where(act, t + 1, t)
                    pos = jnp.where(act, (pos + t) & (cap - 1), pos)
                    return pos, t, act, found, r + 1

                st = (pos, jnp.zeros((nq,), jnp.int32), live,
                      jnp.zeros((nq,), bool), jnp.int32(0))
                _p, _t, act, found, _r = lax.while_loop(cond, body, st)
                return found, act.any()
            fn = self._member_cache[(cap, nq)] = jax.jit(impl)
        return fn

    def _sweep_level_keys(self, keys: np.ndarray) -> np.ndarray:
        """One level's partition sweep: bucket the level's keys (u32
        [N, W], unique within the level, enumeration order) by
        fingerprint prefix, stream each partition's image through the
        device for the membership probe — partition p+1's H2D staging
        is issued before p's verdict is forced, so the upload rides the
        host link while the device probes (the spill engine's
        double-buffering discipline) — then commit the fresh keys into
        the host partitions.  Returns keep = not-seen-before [N]."""
        # chaos site: host-partition loss (this device-streamed sweep
        # is the twin of HostPartitionedTable.sweep, which carries the
        # same site for the portable-resume re-sweep)
        chaos_point("host_table")
        with self._obs.span("host_sweep"):
            return self._sweep_level_keys_impl(keys)

    _SWEEP_STAGE_DEPTH = 2

    def _stage_sweep_images(self):
        """Issue async H2D uploads of the NEXT sweep's first partition
        images (ascending partition order — the sweep's plan order) up
        to the double-buffer depth.  Called at level start inside the
        level_dispatch window: the ``h2d_stage`` span then visibly
        overlaps the level's compute spans on the timeline, which is
        the point — the upload cost leaves the sweep's critical path.
        device_put returns immediately (the transfer drains in the
        background); the version tag recorded here is what lets the
        sweep trust (or discard) the image later."""
        if not (self.sweep_stage and self.host_table
                and self.hpt is not None):
            return
        if getattr(self, "_staged_for", None) is not self.hpt:
            # a fresh/resumed check rebuilt the partitions: any staged
            # images belong to the OLD table object — drop them (the
            # version counters of a new table restart at 0 and could
            # alias)
            self._sweep_staged = {}
            self._staged_for = self.hpt
        todo = [p for p in range(self.hpt.P)
                if p not in self._sweep_staged]
        room = self._SWEEP_STAGE_DEPTH - len(self._sweep_staged)
        if room <= 0 or not todo:
            return
        with self._obs.span("h2d_stage"):
            for p in todo[:room]:
                self._sweep_staged[p] = (
                    jax.device_put(self.hpt.imgs[p]),
                    self.hpt.vers[p])

    def _sweep_level_keys_impl(self, keys: np.ndarray) -> np.ndarray:
        n_all = keys.shape[0]
        keep = np.ones(n_all, bool)
        if n_all == 0:
            return keep
        hpt = self.hpt
        pids = hpt.partition_ids(keys)
        plan = []
        for p in range(hpt.P):
            idx = np.nonzero(pids == p)[0]
            if idx.size:
                plan.append((p, idx))
        staged = {}

        def stage(j):
            if j < len(plan):
                p, idx = plan[j]
                # grow BEFORE the upload so the device image honors the
                # probe-budget load bound even after this level commits
                grew = hpt.reserve(p, int(idx.size))
                pre = self._sweep_staged.pop(p, None)
                if pre is not None and not grew and \
                        pre[1] == hpt.vers[p]:
                    # the image was prestaged during the level's
                    # compute (and is provably current): its H2D
                    # already rode the link — the sweep_overlap span
                    # marks the serialized upload this sweep skipped
                    with self._obs.span("sweep_overlap"):
                        staged[j] = pre[0]
                    self.sweep_stage_hits += 1
                else:
                    staged[j] = jax.device_put(hpt.imgs[p])
                    if self.sweep_stage:
                        self.sweep_stage_misses += 1

        stage(0)
        pending = []
        for j, (p, idx) in enumerate(plan):
            img = staged.pop(j)
            n = int(idx.size)
            nq = self._quantize(n, 1 << 30, floor=1 << 8)
            kq = np.full((self.W, nq), np.uint32(0xFFFFFFFF),
                         np.uint32)
            kq[:, :n] = keys[idx].T
            fn = self._member_fn(int(img.shape[1]), nq)
            found, hovf = fn(img, jax.device_put(kq), jnp.int32(n))
            stage(j + 1)        # next partition's H2D rides now
            pending.append((idx, found, hovf))
        for idx, found, hovf in pending:
            if bool(np.asarray(hovf)):
                raise RuntimeError(
                    "host-partition sweep probe walk did not converge "
                    "— partition image pathologically full")
            keep[idx] = ~np.asarray(found)[:idx.size]
        hpt.commit(keys, keep)
        return keep

    def _reseed_dev_table(self, carry, fkeys: np.ndarray):
        """Reset the device cache to the frontier's keys at (near) the
        initial capacity: the frontier cohort is what the next level
        re-generates at high rate; everything older answers from the
        host sweep.  Only ever called at a level boundary — the cache
        must stay complete over a running level."""
        n = int(fkeys.shape[0])
        self.VCAP = self.VCAP0
        while n + self.SEGL - self.OCAP > self._LOAD_MAX * self.VCAP:
            self.VCAP *= 4
        nq = self._quantize(max(n, 1), 1 << 30, floor=1 << 8)
        kq = np.full((self.W, nq), np.uint32(0xFFFFFFFF), np.uint32)
        if n:
            kq[:, :n] = fkeys.T
        fn = self._seed_cache.get((self.VCAP, nq))
        if fn is None:
            VCAP = self.VCAP

            def impl(keys, n):
                table = tuple(jnp.full((VCAP,), U32MAX)
                              for _ in range(self.W))
                claims = jnp.full((VCAP,), U32MAX)
                live = jnp.arange(nq, dtype=jnp.int32) < n
                ks = tuple(keys[w] for w in range(self.W))
                ranks = jnp.arange(nq, dtype=jnp.uint32)
                table, claims, _f, _p, hv = self._probe_insert(
                    table, claims, ks, live, ranks)
                return table, claims, hv
            fn = self._seed_cache[(self.VCAP, nq)] = jax.jit(impl)
        vis, claims, hv = fn(jnp.asarray(kq), jnp.int32(n))
        if bool(np.asarray(hv)):
            raise RuntimeError(
                "cache reseed probe overflow — raise vcap")
        return dict(carry, vis=vis, claims=claims), n

    # ------------------------------------------------------------------
    # spill-aware fused multi-level burst: while the whole frontier
    # fits the burst ring (engine/bfs burst notes) and no host-table
    # sweep is due (host_table mode sweeps EVERY level, so it keeps the
    # per-level path), run whole levels on device — one dispatch + one
    # small stats readback per burst instead of the
    # upload/window/spill round trips of the segment driver.  The
    # moment a level outgrows the ring, any cap trips, or the space
    # widens past the ring, the burst bails with the pre-level frontier
    # intact and the segment driver takes over — a spill flush or
    # segment boundary can therefore never be needed INSIDE a burst
    # (the ring is far smaller than a segment).
    # ------------------------------------------------------------------

    def _spill_burst_call(self, vis, claims, fr, fm, gd, nf, g0,
                          fam_caps, fcap, ocap, levels_left,
                          states_cap):
        stf, out = self._burst_core(vis, claims, fr, fm, gd, nf, g0,
                                    g0, fam_caps, levels_left,
                                    states_cap, fcap=fcap, ocap=ocap)
        return (stf["vis"], stf["claims"], stf["fr"], stf["fm"],
                stf["gd"], stf["nf"], out)

    def _burst_spill_levels(self, carry, frontier_blocks, res, depth,
                            n_states, n_vis, max_depth, max_states,
                            verbose):
        """One fused multi-level device call on a tiny frontier.
        Harvests every committed level (counts, archives, violations)
        and rebuilds the host frontier blocks from the surviving ring.
        Returns (carry, frontier_blocks, depth, n_states, n_vis,
        fused, bailed) — fused=False means the first level bailed
        (caps/ring overflow) and the segment driver must run it
        instead; bailed=True means the call ended in a bail (even
        after committing levels), so re-entering the burst on the
        unchanged frontier would deterministically bail again."""
        t1 = time.perf_counter()
        lay = self.lay
        with self._obs.span("burst_dispatch"):
            KB = self._burst_width()
            n_front = sum(int(g.shape[0]) for _r, g in frontier_blocks)
            rows_cat, gids_cat = self._cat_seg(
                [r for r, _g in frontier_blocks],
                [g for _r, g in frontier_blocks])
            one = self.ir.narrow(lay, self.ir.encode(
                lay, *self.ir.init_state(self.cfg)))
            fr_np = {k: np.zeros(v.shape + (KB,), v.dtype)
                     for k, v in one.items()}
            for k in fr_np:
                fr_np[k][..., :n_front] = rows_cat[k]
            gd_np = np.full((KB,), -1, np.int32)
            gd_np[:n_front] = gids_cat
            fm_np = np.zeros((KB,), bool)
            fm_np[:n_front] = True
            carry = self._grow_table_if_needed(
                carry, n_vis, min_add=self.burst_levels * KB)
            lv_left = min(self.burst_levels, max_depth - depth)
            st_cap = max(1, min(max_states - res.distinct_states,
                                2 ** 31 - 1))
            vis, claims, frd, fmd, gdd, _nfd, out = \
                self._spill_burst_jit(
                    carry["vis"], carry["claims"],
                    {k: jnp.asarray(v) for k, v in fr_np.items()},
                    jnp.asarray(fm_np), jnp.asarray(gd_np),
                    jnp.int32(n_front), jnp.int32(n_states),
                    self.FAM_CAPS, self.FCAP, self.OCAP,
                    jnp.int32(lv_left), jnp.int32(st_cap))
            carry = dict(carry, vis=vis, claims=claims)
            stats = np.asarray(out["stats"])      # the ONE burst sync
        nlev = int(stats[-1, 0])
        bailed = bool(stats[-1, 1])
        res.burst_dispatches += 1
        res.burst_bailouts += int(bailed)
        if nlev == 0:
            return (carry, frontier_blocks, depth, n_states, n_vis,
                    False, bailed)
        viol_any = bool(stats[-1, 3])
        with self._obs.span("harvest"):
            par_h = lane_h = st_h = inv_h = None
            if self.store_states or viol_any:
                par_h = np.asarray(out["par"])
                lane_h = np.asarray(out["lane"])
                st_h = {k: np.asarray(v) for k, v in out["st"].items()}
                inv_h = np.asarray(out["inv"])

            def _arch(li, n_lvl):
                if self.store_states and n_lvl:
                    # n_lvl == 0 appends nothing: the spill archive's
                    # gid->row mapping is cumulative, not per-level
                    # (flush_archives skips empty levels the same way)
                    self._archive_level(*driver.burst_archive_slice(
                        par_h, lane_h, st_h, li, n_lvl))

            def _viol(li, n_lvl, gid_base):
                driver.burst_decode_violations(
                    res, self.ir, lay, self.inv_names, inv_h, st_h,
                    li, n_lvl, gid_base)

            def _vis(li, n_lvl):
                nonlocal n_vis
                n_vis += n_lvl

            depth, n_states = driver.harvest_fused_levels(
                res, nlev, lambda li: stats[li, :5], depth, n_states,
                archive=_arch, violations=_viol, visited=_vis)
        # rebuild the host frontier from the surviving ring: pruned
        # rows drop here (prune-not-expand stays host-side outside the
        # burst, exactly as if the level had spilled)
        nf = int(stats[-1, 2])
        frontier_blocks = []
        if nf:
            keep = np.nonzero(np.asarray(fmd)[:nf])[0]
            if len(keep):
                fr_h = {k: np.ascontiguousarray(
                            np.asarray(v)[..., keep])
                        for k, v in frd.items()}
                frontier_blocks = [
                    (fr_h, np.asarray(gdd)[keep].astype(np.int32))]
        self._obs.dispatch(kind="burst", depth=depth, frontier=nf,
                           metrics=res.metrics.as_dict())
        if verbose:
            print(f"burst: {nlev} levels to depth {depth} "
                  f"(total {res.distinct_states}), frontier "
                  f"{sum(int(g.shape[0]) for _r, g in frontier_blocks)}, "
                  f"{time.perf_counter() - t1:.2f}s", flush=True)
        return (carry, frontier_blocks, depth, n_states, n_vis, True,
                bailed)

    # ------------------------------------------------------------------

    def check(self, max_depth: int = 10 ** 9, max_states: int = 10 ** 9,
              stop_on_violation: bool = False,
              seed_states: Optional[List] = None,
              checkpoint_path: Optional[str] = None,
              checkpoint_every: int = 1,
              resume_from: Optional[str] = None,
              resume_image=None,
              verbose: bool = False, obs=None) -> CheckResult:
        """``resume_image`` — a ``resil.portable.PortableImage`` from
        a classic, pjit or spill checkpoint: the visited key set
        rebuilds this engine's table image (and host partitions) and
        the frontier rows become one spill block, so a pjit-mesh or
        classic checkpoint resumes here after a shape change (ROADMAP
        item-2 elastic resume)."""
        obs = self._obs = obs if obs is not None else NULL_OBS
        t0 = time.perf_counter()
        lay = self.lay
        frontier_keys: List[np.ndarray] = []   # host-table mode only
        if resume_from is not None and resume_image is not None:
            raise ValueError(
                "resume_from and resume_image are mutually exclusive")

        def prewarm():
            # the segment driver's streamed step warms at run start so
            # a burst BAIL never pays its cold compile mid-run inside a
            # dispatch span (the BENCH_r08 leak — engine/bfs check()'s
            # prewarm note for the span gate and the peak-memory
            # sequencing)
            if obs.spans is not None:
                with obs.span("compile"):
                    self._prewarm_perlevel()

        if resume_from is not None:
            (carry, res, frontier_blocks, frontier_keys, n_states,
             n_vis, depth) = self._load_spill_checkpoint(resume_from)
            prewarm()        # beside the loaded carry (resume-only)
            root_blk = None
        elif resume_image is not None:
            (carry, res, frontier_blocks, frontier_keys, n_states,
             n_vis, depth) = self._resume_portable(resume_image)
            prewarm()
            root_blk = None
        else:
            self._init_store()
            if self.host_table:
                self.hpt = HostPartitionedTable(
                    self.W, partitions=self.partitions,
                    part_cap=self.part_cap)
                self._sweep_staged = {}
            # ---- roots (shared admit path: engine/bfs._dedup_roots) --
            roots, rk, pin_interiors = self._dedup_roots(seed_states)
            n_roots = len(rk)

            res = CheckResult(distinct_states=0,
                              generated_states=n_roots, depth=0)
            self._check_pin_interiors(pin_interiors, res)

            # warm BEFORE the real carry allocates (the dummy is
            # donated away, so peak device memory stays ONE carry)
            prewarm()
            carry = self._fresh_spill_carry()
            slots = self._host_probe_assign(rk, vcap=self.VCAP)
            sl = jnp.asarray(slots)
            carry["vis"] = tuple(
                carry["vis"][w].at[sl].set(jnp.asarray(rk[:, w]))
                for w in range(self.W))
            inv_r, con_r = (np.asarray(a) for a in self._phase2(
                {k: jnp.asarray(v) for k, v in roots.items()}))
            roots_T = {k: np.moveaxis(v, 0, -1)
                       for k, v in self.ir.narrow(lay,
                                                  roots).items()}
            root_blk = dict(rows=roots_T,
                            lpar=np.full((n_roots,), -1, np.int32),
                            llane=np.full((n_roots,), -1, np.int32),
                            linv=inv_r.T, lcon=con_r, n=n_roots)
            if self.host_table:
                root_blk["lfp"] = np.ascontiguousarray(
                    rk.T.astype(np.uint32))

            n_states = 0       # running global id offset
            n_vis = n_roots
            depth = 0
            frontier_blocks = []

        self._stamp_mode(res)

        def harvest_block(blk, keep=None):
            """Counts, violations, archives, next-frontier rows for one
            spilled block; returns (rows, gids, fkeys) for the frontier
            (fkeys None outside host-table mode).  ``keep`` is the
            host-partition sweep's verdict: False rows were seen in an
            earlier level (the device cache only errs fresh-ward) and
            are dropped before any counting — exactly the rows the
            in-HBM engine would never have admitted."""
            nonlocal n_states
            if keep is not None and not keep.all():
                kidx = np.nonzero(keep)[0]
                sub = dict(
                    rows={k: np.ascontiguousarray(v[..., kidx])
                          for k, v in blk["rows"].items()},
                    lpar=blk["lpar"][kidx], llane=blk["llane"][kidx],
                    linv=blk["linv"][:, kidx], lcon=blk["lcon"][kidx],
                    n=len(kidx))
                if "lfp" in blk:
                    sub["lfp"] = np.ascontiguousarray(
                        blk["lfp"][:, kidx])
                blk = sub
            n = blk["n"]
            res.distinct_states += n
            # C_OVERFLOW representability faults (engine/bfs finalize
            # counts the same lane per level)
            res.overflow_faults += int(
                (blk["rows"]["ctr"][C_OVERFLOW] > 0).sum())
            gids = np.arange(n_states, n_states + n, dtype=np.int32)
            inv_ok = blk["linv"]
            if inv_ok.size and not inv_ok.all():
                bad = np.nonzero(~inv_ok)
                res.violations_global += len(bad[0])
                for j, s in zip(*bad):
                    vsv, vh = self.ir.decode(
                        lay, _take_last(blk["rows"], s))
                    res.violations.append(Violation(
                        self.inv_names[j], int(gids[s]),
                        state=vsv, hist=vh))
            if self.store_states:
                self._lvl_parts[-1].append(blk)
            n_states += n
            driver.guard_id_space(n_states)
            con = blk["lcon"].astype(bool)
            if con.all():
                fk = (np.ascontiguousarray(blk["lfp"].T)
                      if "lfp" in blk else None)
                return blk["rows"], gids, fk
            cidx = np.nonzero(con)[0]
            if not len(cidx):
                return None
            fk = (np.ascontiguousarray(blk["lfp"][:, cidx].T)
                  if "lfp" in blk else None)
            return ({k: v[..., cidx] for k, v in blk["rows"].items()},
                    gids[cidx], fk)

        def _take_last(rows, i):
            return {k: np.asarray(v[..., i]) for k, v in rows.items()}

        def flush_archives():
            """store_states: merge this level's spilled parts into the
            per-level archive — streamed to the disk archive's memmaps
            under ``archive_dir`` (host RSS stays level-bounded), or
            concatenated into the classic in-RAM batch-major arrays
            otherwise (trace()/get_state are inherited unchanged)."""
            if not self.store_states:
                return
            parts = self._lvl_parts[-1]
            if not parts:
                return
            with obs.span("archive_io"):
                if self._arch is not None:
                    self._arch.append_level_parts(parts)
                else:
                    self._parents.append(np.concatenate(
                        [p["lpar"] for p in parts]))
                    self._lanes.append(np.concatenate(
                        [p["llane"] for p in parts]))
                    keys = parts[0]["rows"].keys()
                    self._states.append(
                        {k: np.moveaxis(np.concatenate(
                            [p["rows"][k] for p in parts], axis=-1),
                            -1, 0)
                         for k in keys})
            # the archive holds its own copies/files now; dropping the
            # part refs keeps host RSS frontier-bounded
            self._lvl_parts[-1] = []

        self._lvl_parts: List[List] = [[]]
        if root_blk is not None:
            rkeep = None
            if self.host_table:
                # roots enter the host partitions through the same
                # sweep as every level (all fresh by construction)
                rkeep = self._sweep_level_keys(
                    np.ascontiguousarray(root_blk["lfp"].T))
            out = harvest_block(root_blk, rkeep)
            flush_archives()
            if out is not None:
                rows_r, gids_r, fk_r = out
                frontier_blocks.append((rows_r, gids_r))
                if fk_r is not None:
                    frontier_keys.append(fk_r)
            res.generated_states = n_roots
        if stop_on_violation and res.violations:
            res.seconds = time.perf_counter() - t0
            return res

        # ---- level loop ---------------------------------------------
        # Double-buffered (VERDICT r4 #4): the next frontier segment's
        # H2D transfers are issued while the device crunches the
        # current one; level spills ride D2H asynchronously (pending
        # blocks, harvested in FIFO later); and window summaries are
        # fetched ONE WINDOW LATE so the device always has a dispatched
        # window in flight instead of idling on the summary round
        # trip.  Late detection is safe: a trip gates
        # every later chunk into a no-op (sticky flags), and the spill
        # floor reserves margin for the extra in-flight window.
        # burst_ok: a burst that committed levels then bailed keeps the
        # bailing level's frontier intact — re-entering would replay
        # the identical chunks and bail again (one wasted round trip),
        # so skip the burst for that level; the segment driver re-arms
        burst_ok = True
        while frontier_blocks and depth < max_depth and \
                res.distinct_states < max_states:
            # chaos site: dispatch-time device/runtime error at the
            # level boundary (resil/chaos) — before any device work,
            # so the last checkpoint stays the exact resume point
            chaos_point("dispatch")
            if (self.burst and burst_ok and not self.host_table and
                    sum(int(g.shape[0]) for _r, g in frontier_blocks)
                    <= self._burst_width()):
                d0 = depth
                (carry, frontier_blocks, depth, n_states, n_vis,
                 fused, bailed) = self._burst_spill_levels(
                    carry, frontier_blocks, res, depth, n_states,
                    n_vis, max_depth, max_states, verbose)
                if fused:
                    burst_ok = not bailed
                    if checkpoint_path is not None and \
                            driver.ckpt_due_after_burst(
                                depth, d0, checkpoint_every):
                        self._save_spill_checkpoint(
                            checkpoint_path, carry, res,
                            frontier_blocks, frontier_keys, depth,
                            n_states, n_vis)
                    if stop_on_violation and res.violations:
                        break
                    continue
                # first level bailed: the segment driver (with its
                # growth machinery) runs it below
            burst_ok = True        # re-arm after a per-level level
            depth += 1
            t1 = time.perf_counter()
            self._lvl_parts.append([])
            level_new = 0
            level_gen = 0
            next_blocks: List = []
            next_keys: List = []
            level_blks: List = []      # host-table: sweep at level end
            pending_blks: List = []

            def drain_gen():
                # drain the device generated-counter into the host's
                # Python ints each segment: it is an int32, and a whole
                # beyond-the-wall run generates ~4e9 successors — kept
                # monotone on device it would wrap negative
                nonlocal level_gen, carry
                g = int(np.asarray(carry["n_gen"]))
                res.generated_states += g
                level_gen += g
                carry = dict(carry, n_gen=jnp.int32(0))

            def settle_blk(blk):
                """Immediate int bookkeeping for a fresh pending spill
                block; the numpy materialization + harvest run later
                (FIFO) so the D2H DMA overlaps further chunk work.
                n_vis tracks DEVICE-table occupancy either way; under
                the host table, level_new waits for the sweep verdict
                (a device-fresh row may be an older level's key)."""
                nonlocal n_vis, level_new
                if blk is not None:
                    n_vis += blk["n"]
                    if not self.host_table:
                        level_new += blk["n"]
                    pending_blks.append(blk)

            def drain_blks():
                nonlocal pending_blks
                if not pending_blks:
                    return
                with obs.span("harvest"):
                    for blk in pending_blks:
                        blk = self._materialize_blk(blk)
                        if self.host_table:
                            # harvest defers to the level-end sweep:
                            # the host partitions judge the whole
                            # level's keys at once, in enumeration
                            # order
                            level_blks.append(blk)
                            continue
                        out = harvest_block(blk)
                        if out is not None:
                            next_blocks.append(out[:2])
                    pending_blks = []

            _lvl_span = obs.span("level_dispatch")
            _lvl_span.__enter__()
            if self.host_table:
                # issue the level-end sweep's first partition uploads
                # NOW: the H2D DMA overlaps this level's chunk compute
                # (tentpole-c double-buffering; h2d_stage span nested
                # inside this level_dispatch span = the visible
                # overlap)
                self._stage_sweep_images()
            seg_iter = self._resegment(frontier_blocks, self.SEGF)
            staged = next(seg_iter, None)
            staged_dev = (self._stage_segment(*staged)
                          if staged is not None else None)
            while staged_dev is not None:
                carry = self._grow_table_if_needed(carry, n_vis)
                carry, n_seg = self._swap_in_segment(carry, staged_dev)
                staged = next(seg_iter, None)
                # prefetch the NEXT segment now: its H2D DMA overlaps
                # this segment's windows
                staged_dev = (self._stage_segment(*staged)
                              if staged is not None else None)
                n_chunks = (n_seg + self.chunk - 1) // self.chunk
                k = 0
                inflight = None
                while k < n_chunks or inflight is not None:
                    cur = None
                    if k < n_chunks:
                        win_end = min(k + self.sync_every, n_chunks)
                        while k < win_end:
                            carry, cur = self._sstep_jit(carry,
                                                         self.FAM_CAPS)
                            k += 1
                    if inflight is not None:
                        s = np.asarray(inflight)    # one window stale
                        # floor margin covers the in-flight window
                        # dispatched above (2x sync_every, not 1x)
                        spill_floor = self.SEGL - self.OCAP * (
                            2 * self.sync_every + 3)
                        tripped = s[S_OVF] or s[S_FOVF] or \
                            s[S_HOVF] or s[S_OOVF]
                        if tripped or int(s[S_NLVL]) >= spill_floor:
                            if cur is not None:
                                # sync the in-flight window too: its
                                # summary is the freshest view of the
                                # sticky flags / famx / n_lvl (trip
                                # chunks are gated no-ops, so nothing
                                # was committed past the trip)
                                s = np.asarray(cur)
                                cur = None
                            if s[S_OVF] or s[S_FOVF] or s[S_HOVF] or \
                                    s[S_OOVF]:
                                # a fresh pending block may be created
                                # inside; older ones harvest first
                                drain_blks()
                                carry, blk, k = self._handle_trip(
                                    carry, s, n_vis, verbose)
                                settle_blk(blk)
                            else:
                                drain_blks()
                                carry, blk = self._spill_segment(
                                    carry, int(s[S_NLVL]))
                                settle_blk(blk)
                            # re-check the load bound now that n_vis
                            # moved: a dense segment can spill several
                            # SEGL's worth of fresh keys before the
                            # next segment-boundary check
                            carry = self._grow_table_if_needed(carry,
                                                               n_vis)
                    inflight = cur
                drain_gen()
                # final spill for this segment epoch happens lazily —
                # rows stay on device and keep accumulating across
                # frontier segments until the floor trips or the level
                # ends (fewer, larger transfers)

            # level end: spill the remainder
            n_rem = int(np.asarray(carry["n_lvl"]))
            carry, blk = self._spill_segment(carry, n_rem)
            settle_blk(blk)
            drain_gen()
            _lvl_span.__exit__(None, None, None)
            drain_blks()
            if self.host_table and level_blks:
                # the level's keys — unique (device cache is complete
                # over the level) and in enumeration order — meet the
                # host partitions: rows whose key an earlier level
                # archived are dropped everywhere at once
                lkeys = np.concatenate(
                    [np.ascontiguousarray(b["lfp"].T)
                     for b in level_blks])
                lkeep = self._sweep_level_keys(lkeys)
                with obs.span("harvest"):
                    off = 0
                    for b in level_blks:
                        nb = b["n"]
                        kb = lkeep[off:off + nb]
                        off += nb
                        level_new += int(kb.sum())
                        out = harvest_block(b, kb)
                        if out is not None:
                            rows_b, gids_b, fk_b = out
                            next_blocks.append((rows_b, gids_b))
                            next_keys.append(fk_b)
            flush_archives()
            # shared depth gate (engine/driver): a pruned-only frontier
            # cannot occur here (host drops pruned rows), but the
            # empty-frontier guard keeps depth semantics aligned
            depth = driver.gate_level_depth(
                res, depth, level_new, level_gen,
                sum(int(g.shape[0]) for _r, g in next_blocks))
            frontier_blocks = next_blocks   # the expanded level's
            # blocks are freed here (rebind) unless archived
            frontier_keys = next_keys
            if self.host_table and n_vis > self.dev_keys:
                # level boundary: the cache outgrew its HBM budget —
                # reseed it with just the frontier's keys (the host
                # partitions already hold everything archived)
                fkeys = (np.concatenate(frontier_keys) if frontier_keys
                         else np.zeros((0, self.W), np.uint32))
                carry, n_vis = self._reseed_dev_table(carry, fkeys)
            if checkpoint_path is not None and \
                    driver.ckpt_due_at_level(depth, checkpoint_every):
                self._save_spill_checkpoint(
                    checkpoint_path, carry, res, frontier_blocks,
                    frontier_keys, depth, n_states, n_vis)
            obs.dispatch(
                kind="level", depth=depth,
                frontier=sum(int(g.shape[0])
                             for _r, g in frontier_blocks),
                metrics=res.metrics.as_dict())
            if stop_on_violation and res.violations:
                break
            if verbose:
                print(f"depth {depth}: +{level_new} states "
                      f"(total {res.distinct_states}), "
                      f"frontier {sum(int(g.shape[0]) for _r, g in frontier_blocks)}, "
                      f"{time.perf_counter() - t1:.2f}s", flush=True)
        res.depth = depth
        res.seconds = time.perf_counter() - t0
        return res

    # ------------------------------------------------------------------
    # checkpoint / resume (VERDICT r4 #2): at a level boundary the whole
    # wavefront is host-reachable — the visited table is the ONLY device
    # state that matters (level segment empty, frontier segment stale:
    # both rebuild from the host frontier blocks at resume), and the
    # frontier blocks + counters + archives are already host numpy.
    # Reuses the engine-family ckpt_* serializer (engine/bfs), with the
    # frontier blocks riding inside the carry pytree; ckpt_read's
    # spill=True flag keeps the classic and pjit engines from resuming
    # these files and vice versa.  TLC checkpoints its disk queue +
    # fingerprint set the same way (/root/reference/.gitignore:4).
    #
    # Each checkpoint is a full (not incremental) snapshot; under
    # store_states=True the cumulative archives rewrite every time, so
    # long trace-hunting runs should raise checkpoint_every.  The deep
    # beyond-the-wall runs this exists for run store_states=False, where
    # a snapshot is the sparse table + the current frontier only.
    # ------------------------------------------------------------------

    _SPILL_EXTRA_KEYS = ("SEGL", "SEGF", "VCAP", "FCAP", "OCAP",
                         "fam_caps", "n_fblk")

    def _save_spill_checkpoint(self, path, carry, res, frontier_blocks,
                               frontier_keys, depth, n_states, n_vis):
        with self._obs.span("checkpoint"):
            return self._save_spill_checkpoint_impl(
                path, carry, res, frontier_blocks, frontier_keys,
                depth, n_states, n_vis)

    def _save_spill_checkpoint_impl(self, path, carry, res,
                                    frontier_blocks, frontier_keys,
                                    depth, n_states, n_vis):
        # the table serializes SPARSE (occupied slot indices + keys),
        # and the sparsification runs ON DEVICE: deep runs pre-allocate
        # VCAP for the final level (2^28 slots = 4 GB of streams at
        # fp128), and fetching the dense table to the host costs
        # tens of seconds per checkpoint (measured on an older runtime;
        # not measured on the current code).  The device
        # compacts occupied slots into a buffer quantized to the
        # host-tracked occupancy (n_vis counts exactly the admitted
        # keys), so the transfer is O(occupied).  An all-ones key
        # aliases "empty" and would drop out — the same 2^-64/2^-128
        # accepted-risk class as the probe walk (engine/bfs table
        # docstring).
        VCAP = self.VCAP
        nq = self._quantize(max(n_vis, 1), VCAP)
        fn = self._ckpt_sparse_cache.get((nq, VCAP))
        if fn is None:
            def impl(vis, nq=nq, VCAP=VCAP):
                empty = vis[0] == U32MAX
                for t in vis[1:]:
                    empty &= t == U32MAX
                idx = jnp.nonzero(~empty, size=nq,
                                  fill_value=VCAP)[0]
                safe = jnp.clip(idx, 0, VCAP - 1)
                keys = jnp.stack([
                    jnp.where(idx < VCAP, t[safe], U32MAX)
                    for t in vis])
                return idx.astype(jnp.int64), keys
            fn = self._ckpt_sparse_cache[(nq, VCAP)] = jax.jit(impl)
        idx_np, keys_np = (np.asarray(a) for a in fn(carry["vis"]))
        live = idx_np < VCAP
        occ_idx = idx_np[live]
        ckpt = dict(
            vis_idx=occ_idx,
            vis_keys=np.ascontiguousarray(keys_np[:, live]),
            fblk=[dict(g=np.asarray(g),
                       r={k: np.asarray(v) for k, v in rows.items()})
                  for rows, g in frontier_blocks])
        if self.host_table:
            # the authoritative visited set: sparse per-partition
            # images (exact-image restore — no rehash drift) plus the
            # frontier key blocks the reseed path needs
            ckpt.update(self.hpt.state_dict())
            ckpt["fkey"] = [np.asarray(fk) for fk in frontier_keys]
        n_front = sum(int(g.shape[0]) for _r, g in frontier_blocks)
        parents, lanes, states, arch_meta = self._ckpt_store_args()
        ckpt_write(path, ckpt, self.store_states, parents,
                   lanes, states, res, dict(
                       spill=True, depth=depth, n_states=n_states,
                       n_vis=n_vis, n_front=n_front,
                       n_fblk=len(frontier_blocks),
                       SEGL=self.SEGL, SEGF=self.SEGF, VCAP=self.VCAP,
                       FCAP=self.FCAP, OCAP=self.OCAP,
                       fam_caps=list(self.FAM_CAPS),
                       host_table=self.host_table,
                       partitions=self.partitions, **arch_meta,
                       layout=2, chunk=self.chunk,
                       spec=self.ir.name,
                       sym_canon=self.fpr.sym_canon,
                       ir_fingerprint=self.ir.fingerprint(),
                       cfg=repr(self.cfg)),
                   keep=self.ckpt_keep)

    def _resume_portable(self, img):
        """Rebuild this engine's level-boundary state from a
        ``resil.portable.PortableImage`` (any source engine family /
        shape): the visited key set re-inserts into a fresh table
        image via the host claim-insert twin (engine/host_table
        ``insert_np`` — same home hash and probe walk as the device),
        the frontier rows become one spill block, and under
        ``host_table`` the host partitions rebuild by re-sweeping the
        whole key set (a re-partition: ANY --partitions works)."""
        from ..resil.portable import validate_image
        validate_image(img, self.ir.name, repr(self.cfg), self.W)
        self._restore_portable_archives(img)
        keys = img.keys.astype(np.uint32)
        rows, gids = img.expandable()
        frontier_blocks = []
        if gids.shape[0]:
            frontier_blocks.append((
                {k: np.ascontiguousarray(np.moveaxis(v, 0, -1))
                 for k, v in rows.items()}, gids))
        frontier_keys: List[np.ndarray] = []
        if self.host_table:
            # the authoritative set re-partitions into fresh host
            # images (chunked sweeps — every key is fresh by
            # construction); the device table reseeds with just the
            # frontier's keys, exactly the reseed-at-boundary state
            self.hpt = HostPartitionedTable(
                self.W, partitions=self.partitions,
                part_cap=self.part_cap)
            step = 1 << 16
            for i in range(0, keys.shape[0], step):
                self.hpt.sweep(np.ascontiguousarray(keys[i:i + step]))
            if gids.shape[0]:
                b = {k: jnp.asarray(v)
                     for k, v in self.ir.widen(rows).items()}
                fkeys = np.asarray(self._rootfp_jit(b)).astype(
                    np.uint32)
                frontier_keys.append(fkeys)
            else:
                fkeys = np.zeros((0, self.W), np.uint32)
            self.VCAP = self.VCAP0
            while fkeys.shape[0] + self.SEGL > \
                    self._LOAD_MAX * self.VCAP:
                self.VCAP *= 4
            tbl = np.full((self.W, self.VCAP), np.uint32(0xFFFFFFFF),
                          np.uint32)
            insert_np(tbl, fkeys)
            n_vis = int(fkeys.shape[0])
        else:
            while keys.shape[0] + self.SEGL > \
                    self._LOAD_MAX * self.VCAP:
                self.VCAP *= 4
            tbl = np.full((self.W, self.VCAP), np.uint32(0xFFFFFFFF),
                          np.uint32)
            insert_np(tbl, keys)
            n_vis = int(keys.shape[0])
        carry = self._fresh_spill_carry()
        carry["vis"] = tuple(jnp.asarray(tbl[w])
                             for w in range(self.W))
        return (carry, img.fresh_result(), frontier_blocks, frontier_keys,
                img.n_states, n_vis, img.depth)

    def _load_spill_checkpoint(self, path):
        z, meta = ckpt_read(path, repr(self.cfg), self.chunk,
                            self._SPILL_EXTRA_KEYS,
                            spill=True, expected_format=(
                                "layout", 2, "this engine's batch-last/"
                                "narrow-dtype storage layout"),
                            spec_name=self.ir.name,
                            sym_canon=self.fpr.sym_canon)
        if meta["SEGF"] != self.SEGF:
            # frontier re-segmentation is count-preserving (first-seen
            # is parent-order invariant), but a resumed run should be
            # bit-identical in every observable — including archive
            # block boundaries — so hold the segment shape fixed
            raise CheckpointError(
                f"checkpoint was written with seg={meta['SEGF']}; "
                f"resume with the same seg (engine has {self.SEGF})")
        self.SEGL, self.VCAP, self.FCAP, self.OCAP = (
            meta["SEGL"], meta["VCAP"], meta["FCAP"], meta["OCAP"])
        self.FAM_CAPS = tuple(int(c) for c in meta["fam_caps"])
        carry = self._fresh_spill_carry()
        if "carry|vis_idx" not in z or "carry|vis_keys" not in z:
            raise CheckpointError(
                f"{path}: checkpoint lacks the sparse visited-table "
                "records — written by an incompatible engine version; "
                "re-run without --resume")
        occ_idx = jnp.asarray(z["carry|vis_idx"])
        keys = z["carry|vis_keys"]
        if keys.shape[0] != self.W:
            raise CheckpointError(
                f"{path}: checkpoint has {keys.shape[0]} fingerprint "
                f"streams; engine expects {self.W} (fp64 vs fp128 "
                "mismatch)")
        carry["vis"] = tuple(
            carry["vis"][w].at[occ_idx].set(jnp.asarray(keys[w]))
            for w in range(self.W))
        row_keys = list(carry["lvl"].keys())
        frontier_blocks = []
        for i in range(meta["n_fblk"]):
            gids = z[f"carry|fblk|{i}|g"]
            rows = {k: z[f"carry|fblk|{i}|r|{k}"] for k in row_keys}
            frontier_blocks.append((rows, gids))
        if bool(meta.get("host_table")) != self.host_table:
            raise CheckpointError(
                f"{path}: checkpoint was written with host_table="
                f"{bool(meta.get('host_table'))}; resume with the "
                "same setting")
        frontier_keys = []
        if self.host_table:
            if meta.get("partitions") != self.partitions:
                raise CheckpointError(
                    f"{path}: checkpoint has {meta.get('partitions')} "
                    f"host-table partitions; engine has "
                    f"{self.partitions} — resume with the same "
                    "--partitions (counts are P-invariant, but the "
                    "serialized images are not)")
            self.hpt = HostPartitionedTable.from_state(
                lambda nm: z["carry|" + nm])
            frontier_keys = [np.asarray(z[f"carry|fkey|{i}"])
                             for i in range(meta["n_fblk"])]
        template = {"lvl": carry["lvl"]}       # archive key template
        self._load_archives(path, z, meta, template)
        res = ckpt_result(z, meta)
        z.close()             # all arrays extracted; don't leak the fd
        return (carry, res, frontier_blocks, frontier_keys,
                meta["n_states"], meta["n_vis"], meta["depth"])

    # ------------------------------------------------------------------

    def _grow_table_if_needed(self, carry, n_vis: int, min_add: int = 0):
        """Proactive load check, run at segment boundaries AND after
        every mid-segment spill/trip (n_vis moves there too): the table
        can take at most SEGL - FCAP more keys before the next check
        (``min_add`` raises that bound — the fused burst can admit up
        to burst_levels ring-widths before its next host sync).
        A rehash here is safe mid-segment — the cursor and frontier
        segment ride in the carry untouched — and far cheaper than the
        reactive hovf trip+replay it preempts."""
        need = n_vis + max(self.SEGL - self.OCAP, min_add)
        if need > self._LOAD_MAX * self.VCAP:
            while need > self._LOAD_MAX * self.VCAP:
                self.VCAP *= 4
            vis, claims = self._rehash_tables(carry["vis"], self.VCAP)
            carry = dict(carry, vis=vis, claims=claims)
        return carry

    def _handle_trip(self, carry, s, n_vis: int, verbose: bool):
        """Fix whatever tripped (segment full / caps / table), reset
        the sticky flags, and point the cursor back at the tripped
        chunk.  The tripped chunk left no trace (step docstring), so
        resuming there preserves enumeration order exactly."""
        trip_base = int(s[S_TRIP])
        assert trip_base >= 0, "trip flags set but no trip_base"
        blk = None
        old_shapes = (self.FCAP, self.OCAP, self.SEGL)
        if s[S_OVF]:
            carry, blk = self._spill_segment(carry, int(s[S_NLVL]))
        if s[S_OOVF]:
            # a chunk's fresh rows outran the post-dedup compaction
            # buffer (engine/bfs second-compaction note): double toward
            # FCAP, the hard bound on fresh per chunk
            self.OCAP = self._round_cap(min(self.FCAP, 2 * self.OCAP))
        if s[S_FOVF]:
            famx = [int(x) for x in s[S_LEN:S_LEN + len(self.FAM_CAPS)]]
            caps = list(self.FAM_CAPS)
            fam_over = False
            for fi, fam in enumerate(self.expander.families):
                hard = fam.n_lanes * self.chunk
                while caps[fi] < hard and famx[fi] > caps[fi]:
                    caps[fi] = min(2 * caps[fi], hard)
                    fam_over = True
            self.FAM_CAPS = tuple(caps)
            if not fam_over:
                self.FCAP = self._round_cap(min(
                    self.chunk * self.A,
                    max(2 * self.FCAP, (5 * int(sum(famx))) // 4)))
        if self.SEGL < 4 * self.OCAP:
            # the level segment keeps an OCAP-sized append margin
            self.SEGL = self._round_cap(4 * self.OCAP)
        if (self.FCAP, self.OCAP, self.SEGL) != old_shapes:
            # buffer shapes change: spill the committed rows FIRST
            # (a reset would drop them), then rebuild
            if blk is None:
                carry, blk = self._spill_segment(carry,
                                                 int(s[S_NLVL]))
            carry = self._reset_lvl_buffers(dict(carry))
        # FAM_CAPS-only growth retraces via the static jit arg —
        # no buffer rebuild needed
        if s[S_HOVF]:
            self.VCAP *= 4
            vis, claims = self._rehash_tables(carry["vis"], self.VCAP)
            carry = dict(carry, vis=vis, claims=claims)
        if verbose:
            print(f"trip at base {trip_base}: ovf={int(s[S_OVF])} "
                  f"fovf={int(s[S_FOVF])} hovf={int(s[S_HOVF])} "
                  f"oovf={int(s[S_OOVF])} "
                  f"-> FCAP={self.FCAP} OCAP={self.OCAP} "
                  f"SEGL={self.SEGL} "
                  f"VCAP={self.VCAP} fam_caps={self.FAM_CAPS}",
                  flush=True)
        carry["ovf"] = jnp.bool_(False)
        carry["fovf"] = jnp.bool_(False)
        carry["hovf"] = jnp.bool_(False)
        carry["oovf"] = jnp.bool_(False)
        carry["trip_base"] = jnp.int32(-1)
        carry["famx"] = jnp.zeros((len(self.expander.families),),
                                  jnp.int32)
        carry["base"] = jnp.int32(trip_base)
        return carry, blk, trip_base // self.chunk
