"""Symmetry-aware state fingerprints (VIEW + SYMMETRY semantics).

State identity follows the reference model's TLC declarations
(tlc_membership/raft.cfg:29-30): the fingerprint covers only the 10
semantic variables (``VIEW vars`` — history/features excluded, SURVEY
§2.2) and is canonical under server relabeling (``SYMMETRY perms``,
raft.tla:1281) by taking the minimum over the permutation group of a
64-bit hash of the relabeled view:

  fp(s) = min_{σ ∈ G} H(relabel(s, σ))

G is the subgroup of Permutations(Server) fixing InitServer setwise —
Permutations(Server) as the reference declares would be unsound when
InitServer ⊊ Server (models/explore.py symmetry_perms is the oracle twin).

H hashes positional fields with per-position salts and the message bag
**commutatively** (Σ over slots of count · mix(slot)), so bag slot order
— or a message split across slots — never affects identity and no
canonical bag sort exists anywhere in the engine (ops/layout.py).

Hot-path formulation (the engine fingerprints every fresh candidate, so
this dominated profiles): because the positional hash is a commutative
sum Σ_t fmix(relabeled[t] ^ salt[t]), relabeling the *state* is
equivalent to permuting the *salts*:

  Σ_t fmix(view(σ(s))[t] ^ salt[t])  =  Σ_p fmix(content_σ(s)[p] ^ salt[σ(p)])

so instead of gathering every state array through the inverse
permutation per σ (the old formulation — P gathers of the whole state
per candidate), the engine precomputes P statically-permuted salt
tables at init and hashes the state IN PLACE.  Only fields whose
*values* carry server labels still need per-σ work: votedFor, the
vote bitmasks, ConfigEntry payloads, and message src/dst/mserver.
Message slots are unpacked ONCE (perm-independent) and per σ only the
three label fields are re-packed into the header word.  The resulting
fingerprints are bit-identical to the naive relabel-then-hash form
(tests/test_codec.py asserts batch/per-state identity; the engine's
differential suites pin the semantics).

64-bit fingerprints are two independent 32-bit murmur-finalizer streams
(no jax x64 dependency); ``fp128`` doubles the streams (SURVEY §7.4
hard part 4: TLC-style collision odds vs exhaustiveness claims).
"""

from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from ..config import CONFIG_ENTRY, MT_COC, NIL, ModelConfig
from ..ops.kernels import RaftKernels
from ..ops.layout import Layout, get_field, put_field

U32 = jnp.uint32


def fmix32(x):
    """murmur3 finalizer on uint32 arrays (wrapping arithmetic)."""
    x = x ^ (x >> 16)
    x = x * U32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * U32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


def _salts(n: int, stream: int) -> np.ndarray:
    rng = np.random.RandomState(0xC0FFEE + 7919 * stream)
    return rng.randint(0, 1 << 32, size=n, dtype=np.uint32)


SYM_CANON_MODES = ("auto", "sort", "minperm")
# auto → orbit-sort once the group outgrows the trivial-cost regime.
# P ≤ 6 (S ≤ 3 full-symmetry) keeps the static min-over-perms path —
# it is already cheap there AND keeps the incremental-fp delta tables.
_AUTO_SORT_MIN_PERMS = 6


def resolve_sym_canon(cfg, sym_canon: str = "auto") -> str:
    """CLI/engine mode -> the concrete canonicalizer ("sort" or
    "minperm").  Symmetry off always resolves to minperm (the identity
    permutation; nothing to sort); "auto" picks sort when the group
    has more than ``_AUTO_SORT_MIN_PERMS`` permutations."""
    if sym_canon not in SYM_CANON_MODES:
        raise ValueError(
            f"sym_canon must be one of {SYM_CANON_MODES}, "
            f"got {sym_canon!r}")
    if not cfg.symmetry:
        return "minperm"
    if sym_canon == "auto":
        from ..spec import spec_of
        n_perms = len(spec_of(cfg).symmetry_perms(cfg))
        return "sort" if n_perms > _AUTO_SORT_MIN_PERMS else "minperm"
    return sym_canon


def Fingerprinter(cfg, sym_canon: str = "auto"):
    """Factory: the active spec's symmetry-canonical fingerprinter
    (``spec_of(cfg).make_fingerprinter`` — RaftFingerprinter below for
    raft, spec/paxos/fingerprint.PaxosFingerprinter for paxos).  Kept
    under the historical class name so every engine/tool call site
    reads unchanged.  ``sym_canon`` selects the canonicalizer (round
    15): "minperm" is the classic P-fold min-over-perms, "sort" the
    orbit-sort signature path, "auto" resolves per the group size —
    the spec hook always receives the RESOLVED mode."""
    from ..spec import spec_of
    return spec_of(cfg).make_fingerprinter(
        cfg, sym_canon=resolve_sym_canon(cfg, sym_canon))


class RaftFingerprinter:
    def __init__(self, cfg: ModelConfig, sym_canon: str = "minperm"):
        assert sym_canon in ("sort", "minperm"), sym_canon
        self.sym_canon = sym_canon
        self.cfg = cfg
        self.lay = Layout(cfg)
        self.kern = RaftKernels(self.lay)
        S, Lcap = self.lay.S, self.lay.Lcap
        self.n_streams = 4 if cfg.fp128 else 2
        # positional salt layout: ct,st,vf,ci,llen | log | vr,vg | ni,mi
        self.n_pos = 5 * S + S * Lcap + 2 * S + 2 * S * S
        self.pos_salts = [_salts(self.n_pos, t) for t in
                          range(self.n_streams)]
        self.bag_salts = [_salts(self.lay.msg_words + 1, 16 + t)
                          for t in range(self.n_streams)]
        if cfg.symmetry:
            # the spec's symmetry group (SpecIR handle — the oracle
            # twin models/explore.symmetry_perms for raft)
            from ..spec import spec_of
            perms = spec_of(cfg).symmetry_perms(cfg)
        else:
            perms = [tuple(range(S))]
        self.sigmas = np.array(perms, dtype=np.int32)           # [P, S]
        # statically permuted salt tables: psalts[p, t, i] is the salt a
        # value at original flat position i hashes against under σ_p —
        # i.e. pos_salts[t][σ_p(position i)]; per-server blocks permute
        # by σ(i), log by (σ(i), l), ni/mi by (σ(i), σ(j)).
        idx = np.empty((len(perms), self.n_pos), dtype=np.int64)
        ar = np.arange(S)
        for p, sig in enumerate(np.asarray(self.sigmas)):
            off = 0
            for _blk in range(5):                        # ct st vf ci llen
                idx[p, off:off + S] = off + sig[ar]
                off += S
            blk = (sig[ar][:, None] * Lcap +
                   np.arange(Lcap)[None, :]).reshape(-1)  # log
            idx[p, off:off + S * Lcap] = off + blk
            off += S * Lcap
            for _blk in range(2):                        # vr vg
                idx[p, off:off + S] = off + sig[ar]
                off += S
            blk2 = (sig[ar][:, None] * S + sig[ar][None, :]).reshape(-1)
            for _blk in range(2):                        # ni mi
                idx[p, off:off + S * S] = off + blk2
                off += S * S
            assert off == self.n_pos
        self.psalts = np.stack(
            [np.stack([self.pos_salts[t][idx[p]]
                       for t in range(self.n_streams)])
             for p in range(len(perms))])          # [P, n_streams, n_pos]
        if sym_canon == "sort":
            # orbit-sort precompute (round 15): static per-block server
            # index lists (every σ in the group fixes InitServer
            # setwise, so the sort must never move a server across the
            # inside/outside boundary), a per-block salt folded into
            # the signature so equal-looking servers in DIFFERENT
            # blocks never tie, per-log-slot signature salts, and a
            # per-stream salt for the final bijection that keeps
            # sort-mode fingerprint VALUES disjoint from min-perm mode
            # (the checkpoint cross-mode refusal guards a real
            # incompatibility, not a convention).
            inside = [i for i in range(S) if cfg.init_mask >> i & 1]
            outside = [i for i in range(S)
                       if not (cfg.init_mask >> i & 1)]
            self._blocks = [np.array(b, np.int32)
                            for b in (inside, outside) if b]
            bsalt = _salts(len(self._blocks), 41)
            blk = np.zeros(S, np.uint32)
            for bi, b in enumerate(self._blocks):
                blk[b] = bsalt[bi]
            self._blk_salt = blk
            self._log_sig_salts = _salts(Lcap, 42)
            self._sort_salt = _salts(self.n_streams, 49)
            from ..spec import spec_of
            self._sig_fn = spec_of(cfg).server_signature

    # ------------------------------------------------------------------

    def _perm_mask(self, m, sigma):
        out = jnp.zeros_like(m)
        for i in range(self.lay.S):
            out = out | (((m >> i) & 1) << sigma[i])
        return out

    # ------------------------------------------------------------------
    # shared hashing core.  svT holds the VIEW arrays with their
    # canonical leading axes ([S], [S,Lcap], [K,MW], [K]) and `nb`
    # trailing batch axes (0 for the per-state path, 1 for the batched
    # engine path — batch axis LAST so position reductions stay major).
    # ------------------------------------------------------------------

    def _prep(self, svT: Dict, nb: int) -> Dict:
        """Perm-independent hashing precompute (hoisted out of every
        per-σ / per-lane hash evaluation): bag header fields unpacked
        once, log/entry ConfigEntry payloads split once."""
        lay, kern = self.lay, self.kern
        K = lay.K
        hs = lay.header_shifts
        bag = svT["bag"]                                  # [K, MW, ...]
        w0 = bag[:, 0]
        mtype = get_field(w0, hs["mtype"]).astype(jnp.int32)
        src = get_field(w0, hs["msrc"]).astype(jnp.int32)
        dst = get_field(w0, hs["mdst"]).astype(jnp.int32)
        braw = get_field(w0, hs["b"]).astype(jnp.int32)   # stored +1
        clear = U32(0xFFFFFFFF) ^ U32(
            put_field(0xFFFFFFFF, hs["msrc"]) |
            put_field(0xFFFFFFFF, hs["mdst"]) |
            put_field(0xFFFFFFFF, hs["b"]))
        w0_base = w0 & clear
        empty = mtype == 0
        is_coc = mtype == MT_COC
        ebits, epw = lay.entry_bits, lay.entries_per_word
        emask = (1 << ebits) - 1
        ent = jnp.stack([
            ((bag[:, 1 + k // epw] >> (ebits * (k % epw))) & emask)
            .astype(jnp.int32)
            for k in range(lay.Lmax)], axis=1) if lay.msg_words > 1 \
            else jnp.zeros((K, 0) + w0.shape[1:], jnp.int32)  # [K,Lmax,...]
        vmask = (1 << lay.value_bits) - 1

        def split_cfg(e):
            """entry -> (is_cfg, payload-cleared base, payload)."""
            is_cfg = (kern.entry_type(e) == CONFIG_ENTRY) & (e != 0)
            return is_cfg, e & ~jnp.int32(vmask), e & vmask

        ent_cfg, ent_base, ent_pay = split_cfg(ent)
        log = svT["log"]                                  # [S, Lcap, ...]
        log_cfg, log_base, log_pay = split_cfg(log)
        const_flat = [svT["ct"], svT["st"], None, svT["ci"], svT["llen"],
                      None, None, None, svT["ni"], svT["mi"]]
        return dict(bag=bag, w0=w0, src=src, dst=dst, braw=braw,
                    w0_base=w0_base, empty=empty, is_coc=is_coc,
                    ent=ent, ent_cfg=ent_cfg, ent_base=ent_base,
                    ent_pay=ent_pay, log=log, log_cfg=log_cfg,
                    log_base=log_base, log_pay=log_pay,
                    vf=svT["vf"], cnt=svT["cnt"].astype(U32),
                    const_flat=const_flat)

    def _hash_under(self, prep: Dict, svT: Dict, nb: int,
                    sigma, psalt) -> jnp.ndarray:
        """One salted hash of the state under σ -> u32[n_streams, ...].

        σ is either a single static permutation [S] (the min-over-perms
        path vmaps this over ``sigmas``/``psalts``) or a PER-LANE
        permutation [S, B] with per-lane gathered salts ([T, n_pos, B],
        the orbit-sort path).  Value rewrites and salt lookups pick the
        gather flavor by ndim; the hash algebra is identical, so the
        two paths agree bit-for-bit whenever the permutations do."""
        lay = self.lay
        S = lay.S
        hs = lay.header_shifts
        tail = (1,) * nb

        def sub(idx):
            return (jnp.take_along_axis(sigma, idx, axis=0)
                    if sigma.ndim > 1 else sigma[idx])

        # ---- label-carrying content, relabeled under σ ----
        vf = prep["vf"]
        vfp = jnp.where(vf >= 0, sub(jnp.clip(vf, 0, S - 1)), NIL)
        vrp = self._perm_mask(svT["vr"], sigma)
        vgp = self._perm_mask(svT["vg"], sigma)
        logp = jnp.where(prep["log_cfg"],
                         prep["log_base"] |
                         self._perm_mask(prep["log_pay"], sigma),
                         prep["log"])
        pieces = list(prep["const_flat"])
        pieces[2], pieces[5], pieces[6], pieces[7] = vfp, logp, vrp, vgp
        flat = jnp.concatenate(
            [p.reshape((-1,) + p.shape[p.ndim - nb:]).astype(U32)
             for p in pieces])                            # [n_pos, ...]

        # ---- bag header/entry repack (only label fields change) --
        srcp = sub(jnp.clip(prep["src"], 0, S - 1))
        dstp = sub(jnp.clip(prep["dst"], 0, S - 1))
        bp = jnp.where(prep["is_coc"],
                       sub(jnp.clip(prep["braw"] - 1, 0, S - 1)) + 1,
                       prep["braw"])
        w0p = (prep["w0_base"] |
               put_field(srcp.astype(U32), hs["msrc"]) |
               put_field(dstp.astype(U32), hs["mdst"]) |
               put_field(bp.astype(U32), hs["b"]))
        w0p = jnp.where(prep["empty"], prep["w0"], w0p)
        entp = jnp.where(prep["ent_cfg"],
                         prep["ent_base"] |
                         self._perm_mask(prep["ent_pay"], sigma),
                         prep["ent"])
        ebits, epw = lay.entry_bits, lay.entries_per_word
        words = [w0p]
        for w in range(1, lay.msg_words):
            acc = jnp.zeros_like(prep["w0"])
            for k in range((w - 1) * epw, min(w * epw, lay.Lmax)):
                acc = acc | (entp[:, k].astype(U32)
                             << (ebits * (k % epw)))
            words.append(jnp.where(prep["empty"], prep["bag"][:, w],
                                   acc))

        # ---- per-stream reduction ----
        out = []
        for t in range(self.n_streams):
            p_t = psalt[t]
            if p_t.ndim == 1:
                p_t = p_t.reshape((self.n_pos,) + tail)
            h = jnp.sum(fmix32(flat ^ p_t), axis=0)
            bs = jnp.asarray(self.bag_salts[t])
            slot = jnp.zeros_like(prep["w0"])
            for w in range(lay.msg_words):
                slot = slot + fmix32(words[w] ^ bs[w])
            h = h + jnp.sum(prep["cnt"] * fmix32(slot ^ bs[-1]),
                            axis=0)
            out.append(h)
        return jnp.stack(out)                     # [n_streams, ...]

    def _core(self, svT: Dict, nb: int) -> jnp.ndarray:
        prep = self._prep(svT, nb)
        if self.sym_canon == "sort" and len(self.sigmas) > 1:
            assert nb == 1          # fingerprint() wraps with B=1
            return self._core_sort(prep, svT)
        hs_all = jax.vmap(
            lambda s, p: self._hash_under(prep, svT, nb, s, p))(
            jnp.asarray(self.sigmas),
            jnp.asarray(self.psalts))             # [P, n_streams, ...]
        return self._seal(self._lex_min(hs_all))

    # ------------------------------------------------------------------
    # Orbit-sort canonicalization (round 15).  Instead of hashing under
    # EVERY σ and minning (×P work per candidate, P = S! on config #5),
    # compute a permutation-EQUIVARIANT per-server signature (the
    # SpecIR ``server_signature`` hook — vectorized 1-WL color
    # refinement), stable-argsort it within each symmetry block, and
    # hash ONCE under the sorting permutation π.  Soundness:
    #   * if the sorted signatures are strictly increasing inside every
    #     block, π is the UNIQUE canonicalizing permutation up to the
    #     stabilizer of the state, and H(relabel(s, π)) is an orbit
    #     invariant outright;
    #   * signature ties leave a residual subgroup generated by the
    #     adjacent transpositions of tie runs.  For each tied adjacent
    #     pair the CERTIFICATE hashes under τ∘π (swap the two canonical
    #     slots — S-1 extra dynamic hashes worst case): if every tied
    #     transposition leaves the hash fixed, the whole residual
    #     subgroup stabilizes the canonical representative (a product
    #     of symmetric groups is generated by adjacent transpositions)
    #     and the single hash is again orbit-invariant ("soft" lane);
    #   * otherwise the lane is "hard": the signature could not
    #     separate genuinely distinct servers (1-WL-hard cases, e.g.
    #     votedFor functional-graph cycles), and the lane falls back to
    #     the exact min-over-perms value — same orbit ⟹ same min, so
    #     the partition equals min-over-perms EXACTLY (modulo the same
    #     2^-64-per-pair hash-collision class as minperm itself; a
    #     certificate-hash collision can additionally SPLIT an orbit
    #     where minperm could only merge — same odds class).
    # Hard/soft classification is itself orbit-invariant (signatures
    # are equivariant, so relabeled states sort to the SAME canonical
    # representative and tie pattern), hence lanes of one orbit never
    # disagree on which value they use.  The fallback is lax.cond-gated
    # per chunk: a chunk with zero hard lanes never pays the P-fold
    # pass.  Finally a per-stream fmix bijection over the selected
    # value keeps sort-mode fingerprints value-disjoint from min-perm
    # mode (cross-mode resume is refused, not silently corrupted).
    # ------------------------------------------------------------------

    def _sort_perm(self, sig):
        """Per-lane canonicalizing permutation π (old id -> canonical
        slot) from the signature: stable argsort WITHIN each symmetry
        block.  Returns (π [S, B] i32, ties) where ties is the static
        list of (slot_a, slot_b, eq [B]) adjacent-pair certificates —
        block boundaries never generate a tie entry."""
        S = self.lay.S
        nB = sig.shape[1]
        col = jnp.arange(nB)[None, :]
        pi = jnp.zeros((S, nB), jnp.int32)
        ties = []
        for blk in self._blocks:
            bj = jnp.asarray(blk)
            sigb = sig[blk]                       # [m, B] static gather
            order = jnp.argsort(sigb, axis=0, stable=True)
            src = bj[order]               # old ids in canonical order
            pi = pi.at[src, col].set(
                jnp.broadcast_to(bj[:, None], src.shape))
            ss = jnp.take_along_axis(sigb, order, axis=0)
            for r in range(len(blk) - 1):
                ties.append((int(blk[r]), int(blk[r + 1]),
                             ss[r] == ss[r + 1]))
        return pi, ties

    def _dyn_psalts(self, pi):
        """pos_salts gathered under a PER-LANE permutation — the jnp
        mirror of __init__'s static psalts index construction.
        pi [S, B] -> [n_streams, n_pos, B]."""
        S, Lcap = self.lay.S, self.lay.Lcap
        B = pi.shape[1:]
        parts, off = [], 0
        for _blk in range(5):                        # ct st vf ci llen
            parts.append(off + pi)
            off += S
        lg = off + pi[:, None] * Lcap + \
            jnp.arange(Lcap, dtype=jnp.int32)[None, :, None]
        parts.append(lg.reshape((S * Lcap,) + B))    # log
        off += S * Lcap
        for _blk in range(2):                        # vr vg
            parts.append(off + pi)
            off += S
        for _blk in range(2):                        # ni mi
            sq = off + pi[:, None] * S + pi[None, :]
            parts.append(sq.reshape((S * S,) + B))
            off += S * S
        idx = jnp.concatenate(parts)                 # [n_pos, B]
        return jnp.stack([jnp.asarray(self.pos_salts[t])[idx]
                          for t in range(self.n_streams)])

    def _sort_hashes(self, prep: Dict, svT: Dict):
        """Shared sort-path body: (h0 [T, B], hard [B], tie [B])."""
        sig = self._sig_fn(self, svT, prep)          # [S, B] u32
        pi, ties = self._sort_perm(sig)
        h0 = self._hash_under(prep, svT, 1, pi, self._dyn_psalts(pi))
        hard = jnp.zeros(h0.shape[1:], bool)
        tie = jnp.zeros(h0.shape[1:], bool)
        for a, b, eq in ties:
            tie = tie | eq
            pit = jnp.where(pi == a, b, jnp.where(pi == b, a, pi))
            ht = self._hash_under(prep, svT, 1, pit,
                                  self._dyn_psalts(pit))
            same = jnp.ones_like(hard)
            for t in range(self.n_streams):
                same = same & (ht[t] == h0[t])
            hard = hard | (eq & ~same)
        return h0, hard, tie

    def _core_sort(self, prep: Dict, svT: Dict) -> jnp.ndarray:
        h0, hard, _tie = self._sort_hashes(prep, svT)

        def _fallback(_):
            hs_all = jax.vmap(
                lambda s, p: self._hash_under(prep, svT, 1, s, p))(
                jnp.asarray(self.sigmas), jnp.asarray(self.psalts))
            return self._lex_min(hs_all)

        fp_min = jax.lax.cond(jnp.any(hard), _fallback,
                              lambda _: jnp.zeros_like(h0), None)
        fp = jnp.where(hard[None], fp_min, h0)
        fp = fmix32(fp ^ jnp.asarray(self._sort_salt)[:, None])
        return self._seal(fp)

    def sort_debug(self, svb: Dict) -> Dict:
        """Test/bench hook: per-state (hard, tie) masks for a batch-
        FIRST [B, ...] state dict under the sort canonicalizer."""
        assert self.sym_canon == "sort"
        svT = {k: jnp.moveaxis(jnp.asarray(v), 0, -1)
               for k, v in svb.items()}
        prep = self._prep(svT, 1)
        _h0, hard, tie = self._sort_hashes(prep, svT)
        return dict(hard=np.asarray(hard), tie=np.asarray(tie))

    def _seal(self, best):
        """The engines' visited tables use the all-ones key as the
        empty-slot sentinel; an all-ones fingerprint would alias it
        and be re-admitted as fresh on EVERY regeneration (unlike an
        ordinary fp collision, which miscounts once).  Remap it to a
        fixed alternate so the sentinel is unreachable by real keys."""
        allones = jnp.ones(best.shape[1:], bool)
        for t in range(self.n_streams):
            allones = allones & (best[t] == U32(0xFFFFFFFF))
        return best.at[self.n_streams - 1].set(
            jnp.where(allones, U32(0xFFFFFFFE), best[self.n_streams - 1]))

    def fingerprint(self, sv: Dict) -> jnp.ndarray:
        """Single state -> u32[n_streams]: the canonical hash (min over
        the symmetry group in minperm mode, the orbit-sort hash in sort
        mode — same partition either way)."""
        if self.sym_canon == "sort" and len(self.sigmas) > 1:
            svT = {k: jnp.asarray(v)[..., None] for k, v in sv.items()}
            return self._core(svT, nb=1)[..., 0]
        return self._core(sv, nb=0)

    def fingerprint_batch(self, svb: Dict) -> jnp.ndarray:
        """[B, ...] batch -> u32[B, n_streams]; bit-identical to
        vmap(fingerprint) (tests/test_codec.py asserts this) but with
        the batch axis minor so the position reduction vectorizes."""
        svT = {k: jnp.moveaxis(v, 0, -1) for k, v in svb.items()}
        return self._core(svT, nb=1).T            # [B, n_streams]

    def fingerprint_batch_T(self, svT: Dict) -> jnp.ndarray:
        """Batch-LAST twin for the engines' batch-minor hot path:
        [..., B] arrays -> u32[n_streams, B] (no transposes)."""
        return self._core(svT, nb=1)

    def _lex_min(self, hs) -> jnp.ndarray:
        """[P, n_streams, ...] -> [n_streams, ...]: lexicographic min
        over the permutation axis via iterative select (P is small).
        Shared by the per-state and batched entry points so the
        tie-break order can never diverge between them."""
        best = hs[0]
        for p in range(1, hs.shape[0]):
            cand = hs[p]
            less = jnp.zeros(best.shape[1:], bool)
            eq = jnp.ones(best.shape[1:], bool)
            for t in range(self.n_streams):
                less = less | (eq & (cand[t] < best[t]))
                eq = eq & (cand[t] == best[t])
            best = jnp.where(less, cand, best)
        return best

    # ==================================================================
    # Incremental per-action fingerprints (VERDICT r3 #2/#3).
    #
    # Because every stream is a COMMUTATIVE u32 sum of per-position /
    # per-bag-slot terms, a successor's per-permutation hash is exactly
    #
    #   h_p(s') = h_p(s) + Σ_{touched pos i} [term_p(new_i) − term_p(old_i)]
    #           + Σ_{changed slot k} [bagterm_p(new_k) − bagterm_p(old_k)]
    #
    # (u32 modular addition is associative/commutative, so this is
    # BIT-IDENTICAL to the direct sum — tests/test_codec.py pins it).
    # The engine therefore computes, ONCE per frontier chunk, a table
    # of every parent's per-position terms (one full hash per PARENT),
    # and each candidate only evaluates terms at its action family's
    # statically-known touched-position superset (unchanged positions
    # cancel exactly, so supersets are sound).  At ~4-20 enabled lanes
    # per parent this collapses the per-candidate fingerprint work —
    # the measured dominant phase on the wide membership config
    # (BASELINE.md config #3) — by ~6-10x.
    #
    # Per-family touch supersets are derived from ops/kernels.py (each
    # kernel's masked writes); the bag side is a generic <=2-changed-
    # slot diff (every action sends and/or consumes at most one
    # message each — SURVEY §2.4/§2.5).
    # ==================================================================

    # families whose kernels touch the message bag (ops/kernels.py)
    _BAG_FAMILIES = frozenset((
        "RequestVote", "AppendEntries", "CocDiscard", "Receive",
        "Duplicate", "Drop", "AddNewServer", "DeleteServer"))

    def supports_incremental(self) -> bool:
        """Parent-table memory is O(P * n_pos * B); the big-symmetry
        configs (S=5 -> P=120) blow past the win, and their direct
        salt-permutation path already measured >=1.0x vs native.  The
        orbit-sort path has no per-perm delta algebra at all (π is
        data-dependent, so a parent's terms say nothing about its
        successors'), so sort mode always takes the direct path — the
        engines' ``incremental_fp and supports_incremental()`` gate
        handles every call site."""
        if self.sym_canon == "sort":
            return False
        return len(self.sigmas) <= 24

    def _offsets(self):
        S, Lcap = self.lay.S, self.lay.Lcap
        return dict(ct=0, st=S, vf=2 * S, ci=3 * S, llen=4 * S,
                    log=5 * S, vr=5 * S + S * Lcap,
                    vg=6 * S + S * Lcap, ni=7 * S + S * Lcap,
                    mi=7 * S + S * Lcap + S * S)

    def _perm_mask_P(self, m, sig):
        """m [cap] -> [P, cap]: perm_mask under every sigma at once."""
        out = jnp.zeros((sig.shape[0],) + m.shape, jnp.int32)
        for i in range(self.lay.S):
            out = out | (((m >> i) & 1)[None] << sig[:, i][:, None])
        return out

    def parent_tables(self, svT: Dict) -> Dict:
        """Batch-last parent rows [..., B] -> per-term tables:
        posterm [P,T,n_pos,B], bagterm [P,T,K,B], h [P,T,B].  The same
        arithmetic as _core, with the per-term sums retained."""
        lay, kern = self.lay, self.kern
        S, Lcap, K = lay.S, lay.Lcap, lay.K
        hs = lay.header_shifts
        bag = svT["bag"]                                  # [K, MW, B]
        w0 = bag[:, 0]
        mtype = get_field(w0, hs["mtype"]).astype(jnp.int32)
        src = get_field(w0, hs["msrc"]).astype(jnp.int32)
        dst = get_field(w0, hs["mdst"]).astype(jnp.int32)
        braw = get_field(w0, hs["b"]).astype(jnp.int32)
        clear = U32(0xFFFFFFFF) ^ U32(
            put_field(0xFFFFFFFF, hs["msrc"]) |
            put_field(0xFFFFFFFF, hs["mdst"]) |
            put_field(0xFFFFFFFF, hs["b"]))
        w0_base = w0 & clear
        empty = mtype == 0
        is_coc = mtype == MT_COC
        ebits, epw = lay.entry_bits, lay.entries_per_word
        emask = (1 << ebits) - 1
        ent = jnp.stack([
            ((bag[:, 1 + k // epw] >> (ebits * (k % epw))) & emask)
            .astype(jnp.int32)
            for k in range(lay.Lmax)], axis=1) if lay.msg_words > 1 \
            else jnp.zeros((K, 0) + w0.shape[1:], jnp.int32)
        vmask = (1 << lay.value_bits) - 1

        def split_cfg(e):
            is_cfg = (kern.entry_type(e) == CONFIG_ENTRY) & (e != 0)
            return is_cfg, e & ~jnp.int32(vmask), e & vmask

        ent_cfg, ent_base, ent_pay = split_cfg(ent)
        log = svT["log"]
        log_cfg, log_base, log_pay = split_cfg(log)
        vf = svT["vf"]
        cnt = svT["cnt"].astype(U32)
        const_flat = [svT["ct"], svT["st"], None, svT["ci"], svT["llen"],
                      None, None, None, svT["ni"], svT["mi"]]

        def one_perm(sigma, psalt):
            vfp = jnp.where(vf >= 0,
                            sigma[jnp.clip(vf, 0, S - 1)], NIL)
            vrp = self._perm_mask(svT["vr"], sigma)
            vgp = self._perm_mask(svT["vg"], sigma)
            logp = jnp.where(log_cfg,
                             log_base | self._perm_mask(log_pay, sigma),
                             log)
            pieces = list(const_flat)
            pieces[2], pieces[5], pieces[6], pieces[7] = vfp, logp, vrp, vgp
            flat = jnp.concatenate(
                [p.reshape((-1,) + p.shape[p.ndim - 1:]).astype(U32)
                 for p in pieces])                        # [n_pos, B]
            srcp = sigma[jnp.clip(src, 0, S - 1)]
            dstp = sigma[jnp.clip(dst, 0, S - 1)]
            bp = jnp.where(is_coc,
                           sigma[jnp.clip(braw - 1, 0, S - 1)] + 1, braw)
            w0p = (w0_base |
                   put_field(srcp.astype(U32), hs["msrc"]) |
                   put_field(dstp.astype(U32), hs["mdst"]) |
                   put_field(bp.astype(U32), hs["b"]))
            w0p = jnp.where(empty, w0, w0p)
            entp = jnp.where(ent_cfg,
                             ent_base | self._perm_mask(ent_pay, sigma),
                             ent)
            words = [w0p]
            for w in range(1, lay.msg_words):
                acc = jnp.zeros_like(w0)
                for k in range((w - 1) * epw, min(w * epw, lay.Lmax)):
                    acc = acc | (entp[:, k].astype(U32)
                                 << (ebits * (k % epw)))
                words.append(jnp.where(empty, bag[:, w], acc))
            posterm, bagterm, hsum = [], [], []
            for t in range(self.n_streams):
                pt = fmix32(flat ^ psalt[t][:, None])     # [n_pos, B]
                bs = jnp.asarray(self.bag_salts[t])
                slot = jnp.zeros_like(w0)
                for w in range(lay.msg_words):
                    slot = slot + fmix32(words[w] ^ bs[w])
                bt = cnt * fmix32(slot ^ bs[-1])          # [K, B]
                posterm.append(pt)
                bagterm.append(bt)
                hsum.append(pt.sum(axis=0) + bt.sum(axis=0))
            return (jnp.stack(posterm), jnp.stack(bagterm),
                    jnp.stack(hsum))

        posterm, bagterm, h = jax.vmap(one_perm)(
            jnp.asarray(self.sigmas), jnp.asarray(self.psalts))
        return dict(posterm=posterm, bagterm=bagterm, h=h)

    def _slot_terms(self, words, cnt, sig):
        """One bag slot per candidate (words [MW, cap] u32, cnt [cap])
        -> its per-(perm, stream) bag term [P, T, cap]: the single-slot
        twin of parent_tables' bag reduction."""
        lay = self.lay
        hs = lay.header_shifts
        S = lay.S
        w0 = words[0]
        mtype = get_field(w0, hs["mtype"]).astype(jnp.int32)
        src = get_field(w0, hs["msrc"]).astype(jnp.int32)
        dst = get_field(w0, hs["mdst"]).astype(jnp.int32)
        braw = get_field(w0, hs["b"]).astype(jnp.int32)
        clear = U32(0xFFFFFFFF) ^ U32(
            put_field(0xFFFFFFFF, hs["msrc"]) |
            put_field(0xFFFFFFFF, hs["mdst"]) |
            put_field(0xFFFFFFFF, hs["b"]))
        w0_base = w0 & clear
        empty = mtype == 0
        is_coc = mtype == MT_COC
        ebits, epw = lay.entry_bits, lay.entries_per_word
        emask = (1 << ebits) - 1
        vmask = (1 << lay.value_bits) - 1
        srcp = sig[:, jnp.clip(src, 0, S - 1)]            # [P, cap]
        dstp = sig[:, jnp.clip(dst, 0, S - 1)]
        bp = jnp.where(is_coc[None],
                       sig[:, jnp.clip(braw - 1, 0, S - 1)] + 1,
                       braw[None])
        w0p = (w0_base[None] |
               put_field(srcp.astype(U32), hs["msrc"]) |
               put_field(dstp.astype(U32), hs["mdst"]) |
               put_field(bp.astype(U32), hs["b"]))
        w0p = jnp.where(empty[None], w0[None], w0p)       # [P, cap]
        wordsp = [w0p]
        if lay.msg_words > 1:
            ent = [((words[1 + k // epw] >> (ebits * (k % epw))) & emask)
                   .astype(jnp.int32) for k in range(lay.Lmax)]
            for w in range(1, lay.msg_words):
                acc = jnp.zeros_like(w0p)
                for k in range((w - 1) * epw, min(w * epw, lay.Lmax)):
                    e = ent[k]
                    is_cfg = (self.kern.entry_type(e) == CONFIG_ENTRY) \
                        & (e != 0)
                    ep = jnp.where(is_cfg[None],
                                   (e & ~jnp.int32(vmask))[None] |
                                   self._perm_mask_P(e & vmask, sig),
                                   e[None])
                    acc = acc | (ep.astype(U32) << (ebits * (k % epw)))
                wordsp.append(jnp.where(empty[None], words[w][None],
                                        acc))
        out = []
        cntu = cnt.astype(U32)
        for t in range(self.n_streams):
            bs = jnp.asarray(self.bag_salts[t])
            slot = jnp.zeros_like(w0p)
            for w in range(lay.msg_words):
                slot = slot + fmix32(wordsp[w] ^ bs[w])
            out.append(cntu[None] * fmix32(slot ^ bs[-1]))
        return jnp.stack(out, axis=1)                     # [P, T, cap]

    def family_delta(self, name: str, tables: Dict, b_idx, parT: Dict,
                     candT: Dict, params) -> jnp.ndarray:
        """Per-candidate per-permutation hashes [P, T, cap] for one
        action family's buffer rows: parent hash + touched-term deltas.
        parT/candT are batch-last [..., cap]; b_idx maps rows to the
        chunk's parent index (tables' B axis).  Touch supersets follow
        ops/kernels.py's masked writes; unchanged positions cancel."""
        lay = self.lay
        S, Lcap, K = lay.S, lay.Lcap, lay.K
        hs = lay.header_shifts
        OFF = self._offsets()
        cap = b_idx.shape[0]
        r = jnp.arange(cap)
        sig = jnp.asarray(self.sigmas)                    # [P, S]
        psal = jnp.asarray(self.psalts)                   # [P, T, n_pos]

        if name in ("UpdateTerm", "CocDiscard", "Receive",
                    "Duplicate", "Drop"):
            k = params[0]
            w0 = parT["bag"][k, 0, r]
            i = get_field(w0, hs["mdst"]).astype(jnp.int32)
            j = get_field(w0, hs["msrc"]).astype(jnp.int32)
        else:
            i = params[0]
            j = params[1] if len(params) > 1 else None

        touches = []                   # (kind, pos [cap], newval [cap])

        def t_plain(key, a, pos):
            touches.append(("plain", pos, candT[key][a, r]))

        def t_mask(key, a, pos):
            touches.append(("mask", pos, candT[key][a, r]))

        if name == "Restart":
            t_plain("st", i, OFF["st"] + i)
            t_mask("vr", i, OFF["vr"] + i)
            t_mask("vg", i, OFF["vg"] + i)
            t_plain("ci", i, OFF["ci"] + i)
            for jj in range(S):
                touches.append(("plain", OFF["ni"] + i * S + jj,
                                candT["ni"][i, jj, r]))
                touches.append(("plain", OFF["mi"] + i * S + jj,
                                candT["mi"][i, jj, r]))
        elif name == "Timeout":
            t_plain("ct", i, OFF["ct"] + i)
            t_plain("st", i, OFF["st"] + i)
            touches.append(("vf", OFF["vf"] + i, candT["vf"][i, r]))
            t_mask("vr", i, OFF["vr"] + i)
            t_mask("vg", i, OFF["vg"] + i)
        elif name == "BecomeLeader":
            t_plain("st", i, OFF["st"] + i)
            for jj in range(S):
                touches.append(("plain", OFF["ni"] + i * S + jj,
                                candT["ni"][i, jj, r]))
                touches.append(("plain", OFF["mi"] + i * S + jj,
                                candT["mi"][i, jj, r]))
        elif name == "ClientRequest":
            t_plain("llen", i, OFF["llen"] + i)
            lpos = jnp.clip(parT["llen"][i, r], 0, Lcap - 1)
            touches.append(("logent", OFF["log"] + i * Lcap + lpos,
                            candT["log"][i, lpos, r]))
        elif name == "AdvanceCommitIndex":
            t_plain("ci", i, OFF["ci"] + i)
        elif name == "AddNewServer":
            t_plain("ct", j, OFF["ct"] + j)
            touches.append(("vf", OFF["vf"] + j, candT["vf"][j, r]))
        elif name == "UpdateTerm":
            t_plain("ct", i, OFF["ct"] + i)
            t_plain("st", i, OFF["st"] + i)
            touches.append(("vf", OFF["vf"] + i, candT["vf"][i, r]))
        elif name == "Receive":
            t_plain("ct", i, OFF["ct"] + i)
            t_plain("st", i, OFF["st"] + i)
            touches.append(("vf", OFF["vf"] + i, candT["vf"][i, r]))
            t_plain("ci", i, OFF["ci"] + i)
            t_plain("llen", i, OFF["llen"] + i)
            t_mask("vr", i, OFF["vr"] + i)
            t_mask("vg", i, OFF["vg"] + i)
            jc = jnp.clip(j, 0, S - 1)
            touches.append(("plain", OFF["ni"] + i * S + jc,
                            candT["ni"][i, jc, r]))
            touches.append(("plain", OFF["mi"] + i * S + jc,
                            candT["mi"][i, jc, r]))
            for ll in range(Lcap):
                touches.append(("logent", OFF["log"] + i * Lcap + ll,
                                candT["log"][i, ll, r]))
        # RequestVote / AppendEntries / DeleteServer / CocDiscard /
        # Duplicate / Drop: bag-only

        vmask = (1 << lay.value_bits) - 1
        delta = jnp.zeros((len(self.sigmas), self.n_streams, cap), U32)
        for kind, pos, val in touches:
            pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (cap,))
            old = tables["posterm"][:, :, pos, b_idx]     # [P, T, cap]
            saltv = psal[:, :, pos]                       # [P, T, cap]
            if kind == "plain":
                newv = jnp.broadcast_to(val.astype(U32)[None],
                                        (len(self.sigmas), cap))
            elif kind == "vf":
                newv = jnp.where(val[None] >= 0,
                                 sig[:, jnp.clip(val, 0, S - 1)],
                                 NIL).astype(U32)
            elif kind == "mask":
                newv = self._perm_mask_P(val, sig).astype(U32)
            else:                                         # logent
                is_cfg = (self.kern.entry_type(val) == CONFIG_ENTRY) \
                    & (val != 0)
                newv = jnp.where(
                    is_cfg[None],
                    (val & ~jnp.int32(vmask))[None] |
                    self._perm_mask_P(val & vmask, sig),
                    val[None]).astype(U32)
            delta = delta + (fmix32(newv[:, None] ^ saltv) - old)

        if name in self._BAG_FAMILIES:
            bagp = parT["bag"]                            # [K, MW, cap]
            bagc = candT["bag"]
            diff = jnp.any(bagp != bagc, axis=1) | \
                (parT["cnt"] != candT["cnt"])             # [K, cap]
            k0 = jnp.argmax(diff, axis=0)
            d0 = diff[k0, r]
            diff2 = diff & (jnp.arange(K)[:, None] != k0[None])
            k1 = jnp.argmax(diff2, axis=0)
            d1 = diff2[k1, r]
            bag_t = jnp.moveaxis(bagc, 1, 0)              # [MW, K, cap]
            for km, dm in ((k0, d0), (k1, d1)):
                old = tables["bagterm"][:, :, km, b_idx]
                new = self._slot_terms(bag_t[:, km, r],
                                       candT["cnt"][km, r], sig)
                delta = delta + jnp.where(dm[None, None], new - old, 0)

        return tables["h"][:, :, b_idx] + delta

    def finish_min(self, h_all) -> jnp.ndarray:
        """[P, T, ...] per-perm hashes -> sealed canonical fingerprint
        [T, ...] (same lexmin + sentinel remap as the direct path)."""
        return self._seal(self._lex_min(h_all))


# ---------------------------------------------------------------------------
# Per-server signature kernel (SpecIR ``server_signature`` hook, raft
# implementation; spec/paxos/fingerprint.paxos_acceptor_signature is
# the paxos twin).  The contract: sig[S, B] u32, permutation-
# EQUIVARIANT — sig(relabel(s, σ))[σ(i)] == sig(s)[i] for every σ in
# the symmetry group — so sorting by signature commutes with
# relabeling and the sorted representative is orbit-canonical.  Every
# component below is a per-server invariant: own scalar row state,
# self/NIL classes of votedFor, popcount+own-bit of the vote masks and
# ConfigEntry payloads (the full bit pattern is NOT equivariant — bit
# j moves under σ), row/column value multisets of nextIndex /
# matchIndex, and the multiset of label-blanked message contents that
# reference the server as src / dst / CoC-subject.  Two rounds of
# 1-WL color refinement then fold NEIGHBOR colors over the label
# relations (votedFor edges both directions, vote-mask bits both
# directions, ni/mi cells keyed by value), separating servers that
# agree on local counts but differ in who they point at.  Signature
# strength is a PERFORMANCE knob only — correctness never depends on
# it (the certificate + min-over-perms fallback in _core_sort is what
# pins the partition).
# ---------------------------------------------------------------------------


def _popc(m, nbits: int):
    """Population count over the low ``nbits`` bits (static loop)."""
    pc = jnp.zeros_like(m)
    for i in range(nbits):
        pc = pc + ((m >> i) & 1)
    return pc


def _refine_colors(fpr, svT: Dict, c, rnd: int):
    """One 1-WL round: fold each server's neighbors' colors over the
    label-carrying relations, keyed by relation and direction."""
    S = fpr.lay.S
    ar0 = jnp.arange(S, dtype=jnp.int32)
    agg = fmix32(c * U32(0x9E3779B1) + U32(0x7FEB352D + 0x45D9F3B * rnd))
    vf = svT["vf"]
    tgt = jnp.take_along_axis(c, jnp.clip(vf, 0, S - 1), axis=0)
    agg = agg + jnp.where(vf >= 0, fmix32(tgt ^ U32(0x2C1B3C6D)),
                          U32(0x297A2D39))
    inm = vf[None, :, :] == ar0[:, None, None]          # [S_i, S_j, B]
    agg = agg + jnp.sum(inm.astype(U32)
                        * fmix32(c ^ U32(0xD35A2D97))[None], axis=1)
    for key, so, si in (("vr", 0x9F3B5389, 0x6F68F2CD),
                        ("vg", 0xB92E5B2B, 0x186A3C6B)):
        m = svT[key]
        bits = ((m[:, None, :] >> ar0[None, :, None]) & 1)  # bit j of m[i]
        agg = agg + jnp.sum(bits.astype(U32)
                            * fmix32(c ^ U32(so))[None], axis=1)
        agg = agg + jnp.sum(jnp.swapaxes(bits, 0, 1).astype(U32)
                            * fmix32(c ^ U32(si))[None], axis=1)
    for key, s1, s2 in (("ni", 0x8DA6B343, 0xD8163841),
                        ("mi", 0xCB1AB31F, 0x41C64E6D)):
        M = svT[key].astype(U32)
        agg = agg + jnp.sum(fmix32(c[None] ^ fmix32(M ^ U32(s1))),
                            axis=1)
        agg = agg + jnp.sum(
            fmix32(c[None] ^ fmix32(jnp.swapaxes(M, 0, 1) ^ U32(s2))),
            axis=1)
    return fmix32(agg)


def raft_server_signature(fpr, svT: Dict, prep: Dict) -> jnp.ndarray:
    """Raft ``server_signature`` hook body (docstring above): batch-
    last views + the fingerprinter's _prep dict -> sig u32[S, B]."""
    lay = fpr.lay
    S = lay.S

    def U(x):
        return x.astype(U32)

    ar1 = jnp.arange(S, dtype=jnp.int32)[:, None]        # [S, 1]
    c = fmix32(U(svT["ct"]) ^ U32(0x6B79D8A5))
    c = fmix32(c + U(svT["st"]) * U32(0x9E3779B1))
    c = fmix32(c + U(svT["ci"]) * U32(0x85EBCA77))
    c = fmix32(c + U(svT["llen"]) * U32(0xC2B2AE3D))
    vf = svT["vf"]
    c = fmix32(c + U(vf == ar1) * U32(0x27D4EB2F)
               + U(vf < 0) * U32(0x165667B1))
    for key, k1, k2 in (("vr", 0x94D049BB, 0xBF58476D),
                        ("vg", 0x2545F491, 0xD6E8FEB8)):
        m = svT[key]
        c = fmix32(c + U(_popc(m, S)) * U32(k1)
                   + U((m >> ar1) & 1) * U32(k2))
    # log: order-preserving entry fold; ConfigEntry payloads (server-
    # set bitmasks) reduce to their invariants (popcount + own bit)
    ar2 = ar1[:, None]                                   # [S, 1, 1]
    entc = jnp.where(
        prep["log_cfg"],
        U(prep["log_base"])
        + U(_popc(prep["log_pay"], S)) * U32(0xFF51AFD7)
        + U((prep["log_pay"] >> ar2) & 1) * U32(0xC4CEB9FE),
        U(prep["log"]))
    lsalt = jnp.asarray(fpr._log_sig_salts)[None, :, None]
    c = fmix32(c + jnp.sum(fmix32(entc ^ lsalt), axis=1))
    # ni/mi: row/column value multisets + the diagonal
    ar0 = jnp.arange(S)
    for key, s1, s2, s3 in (("ni", 0x0AF63B71, 0x9C06FAF1, 0x4B7F1897),
                            ("mi", 0x71D67FFF, 0xFD7046C5, 0xABA98398)):
        M = U(svT[key])                                  # [S, S, B]
        c = fmix32(c + jnp.sum(fmix32(M ^ U32(s1)), axis=1))
        c = fmix32(c + jnp.sum(fmix32(M ^ U32(s2)), axis=0))
        c = fmix32(c ^ fmix32(M[ar0, ar0] * U32(s3)))
    # message bag: each live slot's label-blanked content hash, counted
    # into the multisets of the servers it references (src / dst /
    # CoC subject).  Entry-payload MEMBERSHIP is deliberately not
    # folded — states differing only there tie and ride the fallback.
    slot = fmix32(U(prep["w0_base"]) ^ U32(0xE6546B64))
    for k in range(lay.Lmax):
        ek = jnp.where(
            prep["ent_cfg"][:, k],
            U(prep["ent_base"][:, k])
            + U(_popc(prep["ent_pay"][:, k], S)) * U32(0x5BD1E995),
            U(prep["ent"][:, k]))
        slot = fmix32(slot + ek * U32(0x38B34AE5 + 2 * k))
    term = prep["cnt"] * U(~prep["empty"])               # [K, B]
    ark = jnp.arange(S, dtype=jnp.int32)[:, None, None]  # [S, 1, 1]
    for fld, ks in ((prep["src"], 0x632BE5AB),
                    (prep["dst"], 0x85157AF5)):
        w = term * fmix32(slot ^ U32(ks))
        msk = fld[None] == ark                           # [S, K, B]
        c = fmix32(c + jnp.sum(U(msk) * w[None], axis=1))
    wb = term * U(prep["is_coc"]) * fmix32(slot ^ U32(0x3C6EF372))
    mskb = (prep["braw"] - 1)[None] == ark
    c = fmix32(c + jnp.sum(U(mskb) * wb[None], axis=1))
    # per-block salt: σ fixes the InitServer blocks, so equal-looking
    # servers in different blocks must never tie
    c = c ^ jnp.asarray(fpr._blk_salt)[:, None]
    for rnd in range(2):
        c = _refine_colors(fpr, svT, c, rnd)
    return c


# ---------------------------------------------------------------------------
# Best-effort novelty Bloom filter (sim/walker.py): the random-walk
# engine cannot afford an authoritative visited set (walkers revisit
# states by design), but a Bloom filter over the SAME symmetry-canonical
# fingerprints the exhaustive engines dedup on gives an estimated
# distinct-state coverage for ~1 bit/slot.  The k probe positions come
# straight from the fingerprint's independent u32 streams (remixed when
# k exceeds the stream count), so sim and BFS agree on state identity.
# ---------------------------------------------------------------------------

def bloom_positions(fp, m_bits: int, k: int = 2) -> jnp.ndarray:
    """Canonical fingerprints [n_streams, B] u32 -> [k, B] int32 bit
    positions into a 2^m_bits Bloom array."""
    T = fp.shape[0]
    out = []
    for j in range(k):
        h = fp[j % T]
        if j >= T:            # remix re-used streams with a round salt
            h = fmix32(h ^ U32((0x9E3779B9 * (j // T)) & 0xFFFFFFFF))
        out.append((h & U32((1 << m_bits) - 1)).astype(jnp.int32))
    return jnp.stack(out)


def bloom_estimate(bits_set: int, m_bits: int, k: int = 2) -> float:
    """Standard Bloom cardinality estimate n̂ = -(m/k)·ln(1 - X/m).
    A saturated filter (X == m) clamps to X = m-1, i.e. (m/k)·ln m —
    an arbitrary ceiling, not an estimate; callers must surface the
    saturation flag (SimResult.bloom_saturated) instead of trusting
    the number there."""
    m = float(1 << m_bits)
    x = float(min(bits_set, (1 << m_bits) - 1))
    return -(m / k) * float(np.log1p(-x / m))


# canonical dedup-key bit layout lives in utils (host helpers);
# re-exported here for back-compat with older imports
from ..utils import combine_u64  # noqa: E402,F401
