"""Frontier expansion: the Next-relation as one vmapped/jitted step.

The action grid mirrors the ∃-quantification TLC performs (SURVEY §3.1):
each *family* (RequestVote, Phase2a, …) is vmapped over its parameter
grid (server pairs, values, bag slots) and over the frontier batch
axis, then families concatenate into a [B, A] candidate block with
validity masks.

SPEC-AGNOSTIC since round 10: the family registry, the guard-algebra
declarations behind the int8 guard matmul, and the per-family density
caps all come from the active ``SpecIR`` (``spec/`` — raft and paxos
today).  Family order follows each spec's oracle successor enumeration
so candidate streams are comparable; a family without a declared guard
algebra fails loudly at construction, naming the spec.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..spec import spec_of


@dataclass
class Family:
    name: str
    fn: Callable            # (sv, der, *params) -> (ok, sv2)
    params: Tuple[np.ndarray, ...]   # one array per param, equal length
    labeler: Callable        # (*param_values) -> str
    # guard-algebra declaration for the MXU guard-matrix path:
    # (feature-offset table, layout, *lane params) ->
    # ([(feature_index, weight)], threshold) over the spec kernels'
    # guard_features vector.  Part of the SpecIR contract: a family
    # without one fails at Expander construction (the int8 guard
    # matmul cannot silently fall back without forking the two paths).
    guard: Optional[Callable] = None
    # delta-algebra declaration for the MXU successor path (the
    # BLEST-style scatter-as-matmul; round 11): (offset table, layout,
    # *lane params) -> [(slot, source, weight), ...] triples over the
    # packed int32 state view, meaning
    #
    #     x'[slot] = x[slot] + sum over triples of weight * psi[source]
    #
    # where x is the flat int32 view of the state (u32 lanes bitcast)
    # and psi = concat([1], x, kernels.delta_features(sv, der)).  A
    # "set" is (slot, const, v) + (slot, old-slot source, -1); u32 bit
    # sends ride a bit-clear/one-hot feature so integer add == set-OR.
    # UNLIKE guard, delta is OPTIONAL: a family without one (genuinely
    # nonlinear actions — bag inserts, log reshuffles) transparently
    # keeps the per-family kernel path; declared families are compiled
    # into ONE batched delta matmul per family group.
    delta: Optional[Callable] = None

    @property
    def n_lanes(self):
        return len(self.params[0]) if self.params else 1


def d_set(off, slot: int, value: int):
    """Delta-declaration helper: the two triples of ``x'[slot] = value``
    for a lane-constant value — the constant in, the old slot value
    out.  (State- or feature-sourced sets are spelled directly as
    triples; see the spec IRs.)"""
    return [(slot, off["_const"], int(value)),
            (slot, off["_src_x"] + slot, -1)]


# Per-family enabled-lane density caps are part of the SpecIR contract
# (cap_f = chunk * min(n_lanes_f, density); overflow trips fovf, the
# engine grows the cap and replays the level — throughput tuning, not
# correctness bounds).  Each spec owns its measured table
# (spec/raft_ir.FAMILY_DENSITY, spec/paxos/ir.FAMILY_DENSITY); the
# historical module-level name stays as the raft alias for existing
# imports.
from ..spec.raft_ir import FAMILY_DENSITY as _FAMILY_DENSITY  # noqa: E402


def validate_fam_density(density, ir=None) -> Dict[str, int]:
    """Bounds-validate a per-family density override mapping (the
    engines' ``fam_density`` kwarg / CLI ``--fam-cap-density``): known
    family name OF THE ACTIVE SPEC, integer k >= 1.  Raises ValueError
    with a message fit for the CLI — never a jit traceback.  ``ir``
    defaults to the raft frontend (the historical global table)."""
    if ir is None:
        from ..spec import get_spec
        ir = get_spec("raft")
    known = dict(ir.family_density)
    out = {}
    for name, k in dict(density or {}).items():
        if name not in known:
            raise ValueError(
                f"unknown action family {name!r} in fam-cap-density "
                f"for spec {ir.name!r}; known families: "
                f"{', '.join(sorted(known))}")
        if isinstance(k, bool) or not isinstance(k, int):
            raise ValueError(
                f"fam-cap-density {name}: k must be an integer "
                f"(got {k!r})")
        if k < 1:
            raise ValueError(
                f"fam-cap-density {name}: k must be >= 1 (got {k}) — "
                "a zero cap would drop every enabled lane of the "
                "family")
        out[name] = k
    return out


def parse_fam_density(text: str, ir=None) -> Dict[str, int]:
    """Parse the CLI form ``fam=k,fam2=k2`` (``--fam-cap-density``)
    into a validated override dict against the active spec's family
    table (``ir``; raft when omitted)."""
    out = {}
    for item in (text or "").split(","):
        item = item.strip()
        if not item:
            continue
        name, sep, val = item.partition("=")
        if not sep:
            raise ValueError(
                f"fam-cap-density entry {item!r} is not of the form "
                "fam=k (e.g. Receive=8,Timeout=2)")
        try:
            k = int(val.strip())
        except ValueError:
            raise ValueError(
                f"fam-cap-density {name.strip()}: k must be an "
                f"integer, got {val.strip()!r}") from None
        out[name.strip()] = k
    return validate_fam_density(out, ir)


class Expander:
    """Compiled expansion over a frontier batch.

    guard_matmul — the MXU-native expansion path (default ON, bit-exact
    by construction): the [states × lanes] guard grid is computed as
    one int8 matmul of per-state guard features against a packed
    signed-weight matrix (``guards_T_matmul``) instead of the vmapped
    per-lane kernel sweep, and the compacted (row, lane) selections in
    ``materialize``/``step_lanes`` become one-hot einsum blocks (the
    BLEST/tensor-core-BFS formulation: frontier expansion as low-
    precision matrix products).  OFF restores the exact historical
    gather/vmap program — tests/test_guard_matmul.py pins ON ≡ OFF.

    delta_matmul — the successor-GENERATION half of the same
    reformulation (round 11): every family whose ``Family.delta``
    algebra is declared compiles into shared one-hot delta matrices,
    and ``materialize``/``step_lanes`` apply the whole affine family
    group as ONE batched scatter-as-matmul (int32 einsum blocks:
    S' = S + P^T((L Q) ⊙ (Ψ V)) over the packed int32 state view)
    instead of one vmapped kernel per family.  Declaration-less
    families transparently keep the kernel path; OFF restores it for
    every family — tests/test_delta_matmul.py pins ON ≡ OFF."""

    def __init__(self, cfg, guard_matmul: bool = True,
                 delta_matmul: bool = True,
                 delta_chunk_skip: Optional[bool] = None):
        self.cfg = cfg
        self.ir = spec_of(cfg)
        self.lay = self.ir.make_layout(cfg)
        self.kern = self.ir.make_kernels(self.lay)
        self.families = self.ir.build_families(self.lay)
        self.keys = self.ir.all_keys
        self.n_lanes = sum(f.n_lanes for f in self.families)
        self.guard_matmul = bool(guard_matmul)
        self.delta_matmul = bool(delta_matmul)
        # P-contraction lowering: the MXU matmul on TPU, the
        # bit-identical static scatter-add off-TPU (see _delta_of)
        self._delta_mxu = jax.default_backend() == "tpu"
        # chunk skip (the ROADMAP item-3 leftover): apply the delta
        # group as per-family blocks, each under a lax.cond on the
        # chunk's enabled count, so a chunk that enables NONE of a
        # family's lanes skips that family's whole cap-wide block —
        # today's group width is the sum of declared fam caps even
        # then.  Bit-exact: an enabled family's block runs the same
        # gathers and int32 adds as the fused group, and a skipped
        # family's columns hold only compaction garbage no consumer
        # ever reads (the same garbage-unobservability the ON≡OFF
        # differentials already rest on).  Default follows the MXU
        # lowering — the cond buys back dense matmul width on TPU,
        # while off-TPU the always-apply single block keeps the
        # cheaper-to-compile graph; tests force it ON under CPU to pin
        # exactness.
        self.delta_chunk_skip = (self._delta_mxu
                                 if delta_chunk_skip is None
                                 else bool(delta_chunk_skip))
        self._gW, self._gT = self._build_guard_matrix()
        self._dgroup = self._build_delta_group() if self.delta_matmul \
            else None
        self._expand = jax.jit(self._expand_impl)

    @property
    def delta_active(self) -> bool:
        """True when the delta-matmul successor path is compiled (the
        flag is ON and at least one family declares its delta algebra)
        — what the engines stamp into the ``delta_matmul`` counter."""
        return self._dgroup is not None

    @property
    def delta_family_names(self):
        if self._dgroup is None:
            return ()
        return tuple(self.families[fi].name
                     for fi in self._dgroup["fam_idx"])

    # ---- packed guard matrix (the guard grid as int8 matmul) -------------

    def _build_guard_matrix(self):
        """(W int8 [n_features, A], T int32 [A]): lane a's enabling
        guard is exactly ``φ(s) · W[:, a] == T[a]`` over the feature
        vector of the spec kernels' ``guard_features``.

        Guards that are pure conjunctions of features select them with
        +1 weights and threshold = the conjunct count; a negated
        conjunct (raft AddNewServer's ``j ∉ config``) enters with
        weight -1 and no threshold contribution — integer arithmetic,
        so the compare is exact, never approximate.  The rows come
        from each family's ``guard`` declaration (the SpecIR contract);
        a family without one fails loudly here: new actions must
        declare their guard algebra, silently falling back would fork
        the two paths."""
        OFF = self.kern.guard_feature_offsets()
        Wm = np.zeros((OFF["total"], self.n_lanes), np.int8)
        T = np.zeros((self.n_lanes,), np.int32)
        lane = 0
        for fam in self.families:
            if fam.guard is None:
                raise KeyError(
                    f"no guard algebra declared for action family "
                    f"{fam.name!r} of spec {self.ir.name!r} — set the "
                    f"Family.guard declaration in the spec's "
                    f"build_families (spec/{self.ir.name}*)")
            for vals in zip(*fam.params) if fam.params else [()]:
                vals = tuple(int(v) for v in vals)
                pairs, thresh = fam.guard(OFF, self.lay, *vals)
                for idx, w in pairs:
                    Wm[idx, lane] = w
                T[lane] = thresh
                lane += 1
        assert lane == self.n_lanes
        return Wm, T

    # ---- packed delta matrices (successor generation as matmul) ----------
    #
    # The affine family group compiles into three shared matrices over
    # the flat int32 state view x (all state arrays in self.keys order,
    # u32 lanes bitcast, row-major) and the extended source vector
    # psi = concat([1], x, kernels.delta_features(sv, der)):
    #
    #   Q [A_g, T] int8  — triple-ownership: Q[a, t] = 1 iff triple t
    #                      belongs to group lane a (kept as the
    #                      documented matrix; _delta_of applies it as
    #                      the equivalent static gather t_lane)
    #   t_srcu/t_w [T]   — per-triple source row (into the pruned
    #                      `used` psi subset) and int32 weight (u32
    #                      bit weights wrap through two's complement,
    #                      exact under the bit-clear sourcing
    #                      contract) — the single-nonzero V matrix in
    #                      gather form
    #   P [T,   D] int8  — slot placement: P[t, slot_t] = 1
    #
    # so a compacted (row, lane) block with row one-hot R and lane
    # one-hot L applies ALL its lanes' deltas as int32 einsum blocks:
    #
    #   x'_rows = R x + P^T ((L Q) ⊙ (w · psi[src]))
    #
    # — one batched scatter-as-matmul for the whole family group
    # instead of one vmapped kernel per family (ROADMAP item 3, the
    # BLEST formulation; arXiv:2512.21967 / 2606.05081).

    def _build_delta_group(self):
        fams = [(fi, fam) for fi, fam in enumerate(self.families)
                if fam.delta is not None]
        if not fams:
            return None
        # flat state-view layout from the spec's canonical (widened)
        # encoding of the init state — shapes/dtypes only
        proto = {k: np.asarray(v) for k, v in self.ir.widen(
            self.ir.encode(self.lay,
                           *self.ir.init_state(self.cfg))).items()}
        slots, shapes, dtypes = {}, {}, {}
        D = 0
        for k in self.keys:
            a = proto[k]
            slots[k], shapes[k], dtypes[k] = D, a.shape, a.dtype
            D += int(a.size)
        foff = self.kern.delta_feature_offsets()
        nF = int(foff["total"])
        E = 1 + D + nF
        OFF = dict(slots)
        OFF["_const"] = 0            # source index of the literal 1
        OFF["_src_x"] = 1            # + flat slot -> old-value source
        OFF["_src_f"] = 1 + D        # + feature index -> feature source
        OFF["_feat"] = dict(foff)    # the spec's feature offset table
        t_lane, t_slot, t_src, t_w = [], [], [], []
        fam_idx, lane_base = [], {}
        fam_trng = {}                # fi -> the family's triple range
        lane_to_aff = np.full((self.n_lanes,), -1, np.int32)
        A_g = 0
        goff = 0                     # global lane offset
        for fi, fam in enumerate(self.families):
            nf = fam.n_lanes
            if fam.delta is not None:
                fam_idx.append(fi)
                lane_base[fi] = A_g
                t_lo = len(t_w)
                lane_to_aff[goff:goff + nf] = \
                    A_g + np.arange(nf, dtype=np.int32)
                for li, vals in enumerate(
                        zip(*fam.params) if fam.params else [()]):
                    vals = tuple(int(v) for v in vals)
                    for slot, src, w in fam.delta(OFF, self.lay, *vals):
                        if not 0 <= slot < D:
                            raise KeyError(
                                f"delta declaration of family "
                                f"{fam.name!r} (spec {self.ir.name!r}) "
                                f"writes slot {slot} outside the "
                                f"[0, {D}) state view")
                        if not 0 <= src < E:
                            raise KeyError(
                                f"delta declaration of family "
                                f"{fam.name!r} (spec {self.ir.name!r}) "
                                f"reads source {src} outside the "
                                f"[0, {E}) psi vector")
                        if not -(1 << 31) <= int(w) < (1 << 32):
                            # the deliberate wrap below covers u32 bit
                            # weights; anything wider would silently
                            # truncate — fail at build time instead
                            raise KeyError(
                                f"delta declaration of family "
                                f"{fam.name!r} (spec {self.ir.name!r}) "
                                f"uses weight {w} outside the 32-bit "
                                f"range")
                        t_lane.append(A_g + li)
                        t_slot.append(slot)
                        t_src.append(src)
                        t_w.append(int(w))
                fam_trng[fi] = (t_lo, len(t_w))
                A_g += nf
            goff += nf
        T = len(t_w)
        Q = np.zeros((A_g, T), np.int8)
        Q[np.asarray(t_lane), np.arange(T)] = 1
        # prune the source axis to the USED psi rows only: V holds one
        # nonzero per column, so restricting to the distinct sources
        # (typically tens, vs E = 1 + D + n_features in the hundreds)
        # shrinks both the traced graph and the matmul FLOPs several-
        # fold with zero semantic change — `used` gathers the rows out
        # of the full psi vector with static indices
        used = np.unique(np.asarray(t_src, np.int64))
        src_of = {int(s): u for u, s in enumerate(used)}
        # u32-bit weights (1 << 31) wrap to INT_MIN: two's-complement
        # add still sets exactly that bit when the source proves it
        # clear, so the wrap is the intended exact arithmetic
        t_wi = (np.asarray(t_w, np.int64) &
                0xFFFFFFFF).astype(np.uint32).view(np.int32)
        P = np.zeros((T, D), np.int8)
        P[np.arange(T), np.asarray(t_slot)] = 1
        return dict(fam_idx=fam_idx, lane_base=lane_base, n_lanes=A_g,
                    n_triples=T, Q=Q, P=P, slots=slots,
                    shapes=shapes, dtypes=dtypes, D=D,
                    used=used.astype(np.int32), n_feats=nF,
                    t_lane=np.asarray(t_lane, np.int32),
                    t_srcu=np.asarray([src_of[s] for s in t_src],
                                      np.int32),
                    t_slot=np.asarray(t_slot, np.int32),
                    t_w=t_wi, lane_to_aff=lane_to_aff,
                    fam_trng=fam_trng)

    def _flatten_T(self, svT) -> jnp.ndarray:
        """Batch-last state dict [..., B] -> flat int32 view [D, B]
        (u32 lanes bitcast; key order = self.keys, row-major)."""
        parts = []
        for k in self.keys:
            v = svT[k]
            if v.dtype == jnp.uint32:
                v = jax.lax.bitcast_convert_type(v, jnp.int32)
            parts.append(v.reshape((-1,) + v.shape[-1:]))
        return jnp.concatenate(parts, axis=0)

    def _unflatten_T(self, flat):
        """[D, B] flat view -> the state dict, original shapes/dtypes."""
        dg = self._dgroup
        out, pos = {}, 0
        for k in self.keys:
            shape = dg["shapes"][k]
            n = int(np.prod(shape, dtype=np.int64)) if shape else 1
            v = flat[pos:pos + n].reshape(tuple(shape) + flat.shape[-1:])
            if dg["dtypes"][k] == np.uint32:
                v = jax.lax.bitcast_convert_type(v, jnp.uint32)
            out[k] = v
            pos += n
        return out

    def _delta_of(self, psi_c, selL):
        """The group delta [D, cap] for per-row sources psi_c [U, cap]
        and group-lane one-hots selL [cap, A_g]: per-triple terms
        ``own ⊙ (w · psi[src])`` contract against the slot-placement
        matrix P — the scatter-as-matmul (an all-zero selL row applies
        no delta, so the row passes through unchanged).

        The per-triple source/ownership selections are single-nonzero
        matrices, so they apply as STATIC-index gathers (free to
        compile, and on TPU they vectorize as row broadcasts).  P's
        contraction is the one genuine summation: on TPU it is the
        int32 matmul that rides the MXU; off-TPU it lowers to the
        bit-identical static segment scatter-add (int32 addition is
        commutative/associative even under wrap, so the two lowerings
        produce equal buffers) — ANY dot embedded in the fused engine
        step costs ~1.3s of XLA:CPU compile per traced program, which
        tier-1 pays per engine instance)."""
        dg = self._dgroup
        tv = psi_c[jnp.asarray(dg["t_srcu"])] * \
            jnp.asarray(dg["t_w"])[:, None]               # [T, cap]
        own = jnp.transpose(selL)[jnp.asarray(dg["t_lane"])]
        x = own * tv
        if self._delta_mxu:
            return jnp.einsum("td,tc->dc", jnp.asarray(dg["P"]), x,
                              preferred_element_type=jnp.int32)
        slots = jnp.asarray(dg["t_slot"])
        return jnp.zeros((dg["D"], x.shape[-1]),
                         jnp.int32).at[slots].add(x)

    def _delta_of_fam(self, psi_c, selL, fi: int):
        """_delta_of restricted to ONE family's triple range — the
        chunk-skip path's per-family block (delta_chunk_skip; selL is
        the family-LOCAL lane one-hot [cap, nf]).  Same sources, same
        weights, same int32 adds as the fused group, so an enabled
        family's columns are bit-identical to the single-block path."""
        dg = self._dgroup
        lo, hi = dg["fam_trng"][fi]
        tv = psi_c[jnp.asarray(dg["t_srcu"][lo:hi])] * \
            jnp.asarray(dg["t_w"][lo:hi])[:, None]        # [Tf, cap]
        own = jnp.transpose(selL)[
            jnp.asarray(dg["t_lane"][lo:hi]
                        - dg["lane_base"][fi])]
        x = own * tv
        if self._delta_mxu:
            return jnp.einsum("td,tc->dc",
                              jnp.asarray(dg["P"][lo:hi]), x,
                              preferred_element_type=jnp.int32)
        slots = jnp.asarray(dg["t_slot"][lo:hi])
        return jnp.zeros((dg["D"], x.shape[-1]),
                         jnp.int32).at[slots].add(x)

    def _psi_T(self, svT, derT, xflat):
        """The USED rows of the extended source vector
        psi = [1; x; features], in `used` (ascending-source) order —
        [U, B].  Regions are gathered with static indices; the feature
        pass is skipped entirely when no declaration sources it."""
        dg = self._dgroup
        used, D = dg["used"], dg["D"]
        B = xflat.shape[-1]
        u_x = used[(used >= 1) & (used < 1 + D)] - 1
        u_f = used[used >= 1 + D] - (1 + D)
        parts = []
        if (used < 1).any():
            parts.append(jnp.ones((1, B), jnp.int32))
        if len(u_x):
            parts.append(xflat[jnp.asarray(u_x)])
        if len(u_f):
            feats = jax.vmap(self.kern.delta_features,
                             in_axes=-1, out_axes=-1)(svT, derT)
            parts.append(feats.astype(jnp.int32)[jnp.asarray(u_f)])
        return jnp.concatenate(parts, axis=0)

    def lane_labels(self) -> List[str]:
        out = []
        for f in self.families:
            cols = [p for p in f.params]
            for vals in zip(*cols):
                out.append(f.labeler(*[int(v) for v in vals]))
        return out

    def _expand_impl(self, svb: Dict[str, jnp.ndarray]):
        """[B, ...] frontier -> (ok [B, A], cand dict of [B, A, ...])."""
        kern = self.kern

        def one_state(sv):
            der = kern.derived(sv)
            oks, cands = [], []
            for fam in self.families:
                lane = jax.vmap(fam.fn,
                                in_axes=(None, None) + (0,) * len(fam.params))
                ok, sv2 = lane(sv, der,
                               *[jnp.asarray(p) for p in fam.params])
                oks.append(ok)
                cands.append(sv2)
            ok = jnp.concatenate([o.reshape(-1) for o in oks])
            cand = {k: jnp.concatenate([c[k] for c in cands], axis=0)
                    for k in self.keys}
            return ok, cand

        return jax.vmap(one_state)(svb)

    def expand(self, svb):
        return self._expand(svb)

    # ---- guard-first expansion (the engine hot path) ---------------------
    #
    # The full [B, A] candidate materialization of _expand_impl writes
    # ~A× more successor state than survives compaction (typically ~4-8
    # of A≈90 lanes are enabled per parent).  The engines instead run a
    # cheap guard pass over the whole lane grid (XLA dead-code-eliminates
    # the successor arithmetic since only `ok` is consumed), then
    # materialize successors ONLY for enabled lanes: per family, enabled
    # (parent, lane) pairs compact into a statically-capped buffer, the
    # family kernel runs on those rows, and an index map reassembles the
    # global FCAP candidate buffer in the oracle's enumeration order.

    def default_fam_caps(self, chunk: int,
                         density=None) -> Tuple[int, ...]:
        """Per-family materialization caps: chunk × min(lanes, density).
        ``density`` overrides the spec's family_density table per
        family (the engines' ``fam_density`` kwarg /
        ``--fam-cap-density`` — validated by validate_fam_density, so
        cap-overflow replays are tunable without editing any spec
        module)."""
        d = dict(self.ir.family_density)
        d.update(validate_fam_density(density, self.ir))
        return tuple(
            chunk * min(f.n_lanes, d.get(f.name, 2))
            for f in self.families)

    def derived_batch_T(self, svT):
        """Batch-LAST derived quantities (the engines' batch-minor hot
        path — see materialize's layout note)."""
        return jax.vmap(self.kern.derived, in_axes=-1, out_axes=-1)(svT)

    def _guard_one(self, sv, der):
        oks = []
        for fam in self.families:
            lane = jax.vmap(fam.fn,
                            in_axes=(None, None) + (0,) * len(fam.params))
            ok, _sv2 = lane(sv, der,
                            *[jnp.asarray(p) for p in fam.params])
            oks.append(ok.reshape(-1))
        return jnp.concatenate(oks)

    # ---- runtime thresholds (the serving layer's constant-padding
    # bucket ceilings, round 13).  The int8 guard matrix W is shared
    # per SHAPE CEILING; what varies per job is runtime data:
    #
    #   rt["thr"]  int32 [A] — the per-lane threshold the matmul
    #              accumulator compares against (today every job's
    #              vector equals the ceiling's baked _gT — thresholds
    #              are conjunct counts — but the compare consumes it as
    #              DEVICE DATA, so a [J]-leading axis vmaps it per job
    #              with zero retrace);
    #   rt["mask"] bool [A] — the job's family lane mask: a padded
    #              ceiling enumerates MORE lanes than a small job's
    #              grid (paxos ballots/values/instances); masked lanes
    #              read disabled before compaction, so the surviving
    #              candidate stream is exactly the job's own
    #              enumeration order.
    #
    # rt=None keeps the historical baked-constant trace bit-identical.

    def runtime_thresholds(self):
        """The ceiling's (thresholds, all-enabled mask) pair as host
        arrays — the template a spec's ``serve_runtime`` hook starts
        from when building a job's rt data."""
        return (np.asarray(self._gT, np.int32).copy(),
                np.ones((self.n_lanes,), bool))

    def guards_T(self, svT, derT, rt=None) -> jnp.ndarray:
        """Batch-LAST frontier [..., B] -> ok [B, A]: every lane's
        enabling guard.  Dispatches to the MXU guard-matrix path
        (``guards_T_matmul``, default) or the historical vmapped
        per-lane sweep with the successor construction
        dead-code-eliminated (``guard_matmul=False``).  ``rt`` is the
        per-job runtime-thresholds dict above (None = baked
        constants)."""
        if self.guard_matmul:
            return self.guards_T_matmul(svT, derT, rt)
        ok = jax.vmap(self._guard_one, in_axes=-1, out_axes=-1)(svT, derT)
        ok = jnp.moveaxis(ok, -1, 0)
        if rt is not None:
            # the sweep computes guards directly (no threshold
            # compare), so only the lane mask applies here
            ok = ok & rt["mask"][None, :]
        return ok

    def guards_T_matmul(self, svT, derT, rt=None) -> jnp.ndarray:
        """The guard grid as ONE int8 matmul: φ [F, B] features (one
        elementwise extraction pass per state — the per-slot receive
        guards run once per SLOT, not once per lane) contracted against
        the packed weight matrix on the MXU with int32 accumulation,
        then the exact per-lane threshold compare.  Bit-identical to
        the lane sweep by construction (integer arithmetic, 0/±1
        weights).  With ``rt``, the thresholds are device data and the
        job's lane mask ANDs in after the compare (see the
        runtime-thresholds note above)."""
        with jax.named_scope("guard_matmul"):
            phi = jax.vmap(self.kern.guard_features,
                           in_axes=-1, out_axes=-1)(svT, derT)  # [F, B]
            acc = jax.lax.dot_general(
                phi, jnp.asarray(self._gW),
                dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)               # [B, A]
            if rt is None:
                return acc == jnp.asarray(self._gT)[None, :]
            return (acc == rt["thr"][None, :]) & rt["mask"][None, :]

    # ---- one-hot einsum selection (the successor-generation half of
    # the MXU path): a compacted (row, lane) index block becomes an
    # int one-hot matrix contracted against the batch — a single-1-per-
    # row matmul is EXACTLY the gather (one nonzero product per output
    # element, int32 accumulation), but it rides the MXU instead of the
    # scalar gather units.  uint32 payloads bitcast through int32.

    def _sel_rows(self, arrs, b_idx, B: int):
        sel = (b_idx[:, None] ==
               jnp.arange(B, dtype=jnp.int32)[None, :]) \
            .astype(jnp.int32)                            # [cap, B]
        out = {}
        for k, v in arrs.items():
            isu = v.dtype == jnp.uint32
            vi = jax.lax.bitcast_convert_type(v, jnp.int32) if isu else v
            r = jnp.einsum("...b,cb->...c", vi, sel,
                           preferred_element_type=jnp.int32)
            out[k] = jax.lax.bitcast_convert_type(r, jnp.uint32) \
                if isu else r
        return out

    def _sel_params(self, params, l_idx, nf: int):
        sel = (l_idx[:, None] ==
               jnp.arange(nf, dtype=jnp.int32)[None, :]) \
            .astype(jnp.int32)                            # [cap, nf]
        return [jnp.einsum("cn,n->c", sel, jnp.asarray(p, jnp.int32),
                           preferred_element_type=jnp.int32)
                for p in params]

    def materialize(self, svT, derT, okf, epos, fcap: int,
                    fam_caps, delta_fp=None) \
            -> Tuple[Dict[str, jnp.ndarray], jnp.ndarray]:
        """Build the compacted candidate buffer [..., fcap] from the
        guard mask.  svT/derT are BATCH-LAST ([..., B]); okf is the
        flat [B*A] enabled mask in b-major lane order, epos the global
        compaction position per flat lane (fcap = dropped).  Returns
        (cand rows batch-last in enumeration order, per-family enabled
        counts — the host grows any family whose count exceeded its cap
        and replays the level).

        delta_fp — optional (Fingerprinter, parent_tables) pair: each
        family also computes its candidates' per-permutation hashes
        incrementally from the parent tables (fingerprint.family_delta)
        and a third return value fp [n_streams, fcap] carries the
        sealed canonical fingerprints.

        Everything runs BATCH-MINOR (the row axis vmapped at -1): the
        per-state arrays have tiny minor dims (S, Lcap, K ≈ 3-20) which
        waste the TPU's (8,128) vector tiles when the batch is major —
        measured 5.6x slower than this layout on v5e."""
        B = okf.shape[0] // self.n_lanes
        A = self.n_lanes
        totc = sum(fam_caps)

        # ---- one fused compaction for ALL families -------------------
        # The per-family cumsum+scatter chains were ~2x13 serialized
        # kernel launches; instead rearrange the lane grid family-major
        # once (static permutation), run ONE cumsum, and derive every
        # family's buffer positions from it with static lookup tables.
        n_fams = len(self.families)
        perm = np.empty((B * A,), np.int64)          # grouped -> flat
        f_of = np.empty((B * A,), np.int32)
        blk_start = np.empty((n_fams,), np.int64)    # grouped offsets
        caps_np = np.asarray(fam_caps, np.int32)
        coff_np = np.concatenate([[0], np.cumsum(caps_np)[:-1]])
        fam_off = []                  # global lane offset per family
        g = 0
        off = 0
        for fi, fam in enumerate(self.families):
            nf = fam.n_lanes
            blk_start[fi] = g
            fam_off.append(off)
            bl = (np.arange(B)[:, None] * A + off +
                  np.arange(nf)[None, :]).reshape(-1)
            perm[g:g + B * nf] = bl
            f_of[g:g + B * nf] = fi
            g += B * nf
            off += nf
        okg = okf[perm]                              # [N] family-major
        cum = jnp.cumsum(okg.astype(jnp.int32))      # ONE scan
        # enabled-count per family = cum at block ends minus starts
        ends = jnp.asarray(np.concatenate([blk_start[1:], [B * A]]) - 1)
        cum_end = cum[ends]
        cum_start = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32), cum_end[:-1]])
        counts = cum_end - cum_start                 # [n_fams] = famx
        # per grouped lane: position within its family's cap buffer
        wpos = cum - 1 - cum_start[jnp.asarray(f_of)]
        cap_p = jnp.asarray(caps_np)[jnp.asarray(f_of)]
        coff_p = jnp.asarray(coff_np, jnp.int32)[jnp.asarray(f_of)]
        fits = okg & (wpos < cap_p)
        target = jnp.where(fits, coff_p + wpos, totc)
        # src: concat slot -> flat lane id (ONE scatter)
        src = jnp.full((totc,), B * A, jnp.int32).at[target].set(
            jnp.asarray(perm, jnp.int32), mode="drop")
        srcc = jnp.clip(src, 0, B * A - 1)
        b_all, l_all = srcc // A, srcc % A
        # mapidx: global FCAP slot -> concat slot (ONE scatter).  Only
        # fitting lanes may write (a clip-garbage src could alias an
        # enabled lane's epos).
        epos_g = epos[perm]
        mapidx = jnp.full((fcap,), totc, jnp.int32).at[
            jnp.where(fits, epos_g, fcap)].set(
            target, mode="drop")

        # ---- affine family group: ONE batched scatter-as-matmul ------
        # Every delta-declared family's buffer slice concatenates into
        # a single (row, group-lane) block; parent-row selection, the
        # source gather and the slot scatter all run as int32 einsum
        # blocks over the flat state view (the BLEST reformulation —
        # see the delta-matrix comment above).  Declaration-less
        # families fall through to the per-family kernel loop below.
        dg = self._dgroup
        g_cand = None
        if dg is not None:
            with jax.named_scope("delta_apply"):
                # barrier the block's inputs as well as its output:
                # the compaction indices and the flat/psi views
                # otherwise fuse into the one-hot einsums and the
                # fusion search dominates compile time (~1.3s per
                # traced program on XLA:CPU) — identity ops, bit-exact
                xflat = jax.lax.optimization_barrier(
                    self._flatten_T(svT))
                psi = jax.lax.optimization_barrier(
                    self._psi_T(svT, derT, xflat))
                if self.delta_chunk_skip:
                    # chunk skip (the ROADMAP item-3 leftover): one
                    # block per family, each under a cond on the
                    # chunk's enabled count — a chunk enabling none of
                    # a family's lanes skips its whole cap-wide block
                    # instead of paying the full group width.  An
                    # enabled family's block runs the identical
                    # gathers/adds as the fused group (bit-exact); a
                    # skipped family's columns were compaction garbage
                    # no consumer reads either way.
                    out_parts, par_parts = [], []
                    for fi in dg["fam_idx"]:
                        nf = self.families[fi].n_lanes
                        lo = int(coff_np[fi])
                        cap = fam_caps[fi]
                        gb_f, gl_f = jax.lax.optimization_barrier(
                            (b_all[lo:lo + cap],
                             jnp.clip(l_all[lo:lo + cap]
                                      - fam_off[fi], 0, nf - 1)))

                        def _apply(ops, fi=fi, nf=nf):
                            xf, ps, gb, gl = ops
                            selL = (gl[:, None] ==
                                    jnp.arange(nf, dtype=jnp.int32)
                                    [None, :]).astype(jnp.int32)
                            if self._delta_mxu:
                                selB = (gb[:, None] ==
                                        jnp.arange(B, dtype=jnp.int32)
                                        [None, :]).astype(jnp.int32)
                                rows = jnp.einsum(
                                    "db,cb->dc", xf, selB,
                                    preferred_element_type=jnp.int32)
                                vals = jnp.einsum(
                                    "eb,cb->ec", ps, selB,
                                    preferred_element_type=jnp.int32)
                            else:
                                rows = xf[:, gb]
                                vals = ps[:, gb]
                            return rows, rows + self._delta_of_fam(
                                vals, selL, fi)

                        def _skip(ops, cap=cap):
                            z = jnp.zeros((dg["D"], cap), jnp.int32)
                            return z, z

                        par_f, out_f = jax.lax.cond(
                            counts[fi] > 0, _apply, _skip,
                            (xflat, psi, gb_f, gl_f))
                        par_parts.append(par_f)
                        out_parts.append(out_f)
                    out_flat = jax.lax.optimization_barrier(
                        jnp.concatenate(out_parts, axis=-1))
                    rows_flat = jnp.concatenate(par_parts, axis=-1)
                else:
                    gb_parts, gl_parts = [], []
                    for fi in dg["fam_idx"]:
                        nf = self.families[fi].n_lanes
                        lo = int(coff_np[fi])
                        cap = fam_caps[fi]
                        gb_parts.append(b_all[lo:lo + cap])
                        gl_parts.append(jnp.clip(
                            l_all[lo:lo + cap] - fam_off[fi],
                            0, nf - 1) + dg["lane_base"][fi])
                    gb, gl = jax.lax.optimization_barrier(
                        (jnp.concatenate(gb_parts),
                         jnp.concatenate(gl_parts)))
                    selL = (gl[:, None] ==
                            jnp.arange(dg["n_lanes"],
                                       dtype=jnp.int32)[None, :]) \
                        .astype(jnp.int32)                # [gcap, A_g]
                    if self._delta_mxu:
                        # row selection as one-hot matmuls (the PR-8
                        # _sel_rows trick, whole group at once)
                        selB = (gb[:, None] ==
                                jnp.arange(B, dtype=jnp.int32)
                                [None, :]).astype(jnp.int32)
                        rows_flat = jnp.einsum(
                            "db,cb->dc", xflat, selB,
                            preferred_element_type=jnp.int32)
                        vals = jnp.einsum(
                            "eb,cb->ec", psi, selB,
                            preferred_element_type=jnp.int32)
                    else:
                        # off-TPU: the bit-identical column gather
                        # (each embedded dot costs ~1s of XLA:CPU
                        # compile)
                        rows_flat = xflat[:, gb]
                        vals = psi[:, gb]
                    # the barrier stops XLA fusing the delta matmul
                    # into its ~n_keys × n_families unflatten/concat
                    # consumers — without it the fusion search costs
                    # ~1.3s of compile per traced program (same class
                    # as the phase barriers in
                    # engine/bfs._chunk_step_impl); identity, so the
                    # bit-exactness contract is untouched
                    out_flat = jax.lax.optimization_barrier(
                        rows_flat + self._delta_of(vals, selL))
                # ONE unflatten for the whole group buffer; families
                # slice their column ranges out of the shaped arrays
                # (slices are far cheaper to trace than per-family
                # reshape+bitcast cascades)
                g_all = self._unflatten_T(out_flat)
                g_par = (self._unflatten_T(rows_flat)
                         if delta_fp is not None else None)
                g_pos = {}
                pos = 0
                for fi in dg["fam_idx"]:
                    g_pos[fi] = pos
                    pos += fam_caps[fi]
                g_cand = g_pos            # membership + slice offset

        # ---- per-family successor kernels on their buffer slices -----
        outs = []
        fp_outs = []
        off = 0
        for fi, (fam, cap) in enumerate(zip(self.families, fam_caps)):
            nf = fam.n_lanes
            lo = int(coff_np[fi])
            b_idx = b_all[lo:lo + cap]
            l_idx = jnp.clip(l_all[lo:lo + cap] - off, 0, nf - 1)
            if g_cand is not None and fi in g_cand:
                # affine family: its successors came out of the group
                # delta matmul above; only the incremental-fp hook
                # still needs the per-family row/param views
                gp = g_cand[fi]
                sv2 = {k: v[..., gp:gp + cap]
                       for k, v in g_all.items()}
                outs.append(sv2)
                if delta_fp is not None:
                    prm_rows = (self._sel_params(fam.params, l_idx, nf)
                                if self.guard_matmul else
                                [jnp.asarray(p)[l_idx]
                                 for p in fam.params])
                    fpr, tables = delta_fp
                    fp_outs.append(fpr.family_delta(
                        fam.name, tables, b_idx,
                        {k: v[..., gp:gp + cap]
                         for k, v in g_par.items()}, sv2, prm_rows))
                off += nf
                continue
            if self.guard_matmul:
                # batched successor einsum: the family's compacted
                # (row, lane) block selects parent rows and lane params
                # via one-hot matmuls (exact — see _sel_rows)
                sv_rows = self._sel_rows(svT, b_idx, B)
                der_rows = self._sel_rows(derT, b_idx, B)
                prm_rows = self._sel_params(fam.params, l_idx, nf)
            else:
                sv_rows = {k: v[..., b_idx] for k, v in svT.items()}
                der_rows = {k: v[..., b_idx] for k, v in derT.items()}
                prm_rows = [jnp.asarray(p)[l_idx] for p in fam.params]
            _ok, sv2 = jax.vmap(
                fam.fn, in_axes=(-1, -1) + (0,) * len(fam.params),
                out_axes=(0, -1))(sv_rows, der_rows, *prm_rows)
            outs.append(sv2)
            if delta_fp is not None:
                fpr, tables = delta_fp
                fp_outs.append(fpr.family_delta(
                    fam.name, tables, b_idx, sv_rows, sv2, prm_rows))
            off += nf
        concat = {k: jnp.concatenate([o[k] for o in outs], axis=-1)
                  for k in self.keys}
        take = jnp.clip(mapidx, 0, totc - 1)
        cand = {k: v[..., take] for k, v in concat.items()}
        if delta_fp is None:
            return cand, counts
        h_all = jnp.concatenate(fp_outs, axis=-1)[..., take]
        return cand, counts, delta_fp[0].finish_min(h_all)

    # ---- per-walker step fusion (the sim engine's hot path) --------------
    #
    # A random walker takes ONE lane per state per step, so the full
    # [B, A] candidate materialization (or even the FCAP compaction) is
    # ~A× too much successor construction.  step_lanes instead applies
    # each family's kernel ONCE per walker with that walker's chosen
    # params (clipped to the family's grid when the walker chose another
    # family — the result is discarded by the select), then merges the
    # n_families results by lane-range selects.  Cost per step is
    # n_families (~10-14) kernel applications per walker versus
    # A (~90-370) lanes of a full expansion; the guard pass stays the
    # dead-code-eliminated guards_T grid.

    def step_lanes(self, svT, derT, lane) -> Dict[str, jnp.ndarray]:
        """Batch-last walker states [..., B] + flat lane ids [B] ->
        successor rows [..., B].  lane must be an enabled lane of its
        state (sim samples from guards_T via ops.kernels.select_enabled);
        rows whose lane is out of range (e.g. -1 = no enabled lane)
        return the state unchanged — callers mask on enabled-count.

        With the delta path compiled, every walker whose lane belongs
        to an affine family steps through ONE group delta matmul (a
        walker outside the group gets an all-zero lane one-hot, so its
        delta is exactly zero and the row passes through); only the
        declaration-less families still apply their kernels."""
        dg = self._dgroup
        if dg is not None:
            with jax.named_scope("delta_apply"):
                aff = jnp.asarray(dg["lane_to_aff"])[
                    jnp.clip(lane, 0, self.n_lanes - 1)]
                aff = jnp.where(lane >= 0, aff, jnp.int32(-1))
                selL = (aff[:, None] ==
                        jnp.arange(dg["n_lanes"],
                                   dtype=jnp.int32)[None, :]) \
                    .astype(jnp.int32)                    # [B, A_g]
                xflat = self._flatten_T(svT)
                psi = self._psi_T(svT, derT, xflat)
                out = self._unflatten_T(
                    xflat + self._delta_of(psi, selL))
        else:
            out = {k: v for k, v in svT.items()}
        off = 0
        for fam in self.families:
            nf = fam.n_lanes
            if dg is not None and fam.delta is not None:
                off += nf
                continue
            li = jnp.clip(lane - off, 0, nf - 1)
            prm = (self._sel_params(fam.params, li, nf)
                   if self.guard_matmul
                   else [jnp.asarray(p)[li] for p in fam.params])
            _ok, sv2 = jax.vmap(
                fam.fn, in_axes=(-1, -1) + (0,) * len(fam.params),
                out_axes=(0, -1))(svT, derT, *prm)
            sel = (lane >= off) & (lane < off + nf)
            out = {k: jnp.where(sel, sv2[k], out[k]) for k in out}
            off += nf
        return out

    # ---- test/debug path -------------------------------------------------
    def expand_one(self, arrs: Dict[str, np.ndarray]):
        """Single state -> [(label, sv2_arrays)] for enabled lanes."""
        svb = {k: jnp.asarray(v)[None] for k, v in arrs.items()}
        ok, cand = self.expand(svb)
        ok = np.asarray(ok)[0]
        labels = self.lane_labels()
        out = []
        for lane in np.nonzero(ok)[0]:
            sv2 = {k: np.asarray(cand[k])[0, lane] for k in self.keys}
            out.append((labels[lane], sv2))
        return out
