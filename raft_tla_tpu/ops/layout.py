"""Packed-state layout: field widths and packing spec, computed from bounds.

Device representation (SURVEY.md §7.1, revised for SoA):  a state is a
struct-of-arrays pytree rather than one bit-packed word vector — XLA
vectorizes per-field int32 arrays well and the kernels stay readable —
with bit-packing used exactly where it is load-bearing:

  * **log entries** pack to one small int each (``entry_bits`` ≤ 16):
    ``term | etype | payload`` — so entry equality (LogMatching, the
    AppendEntries conflict test) is a single integer compare
    (reference entry schema: tlc_membership/raft.tla:115, 153-155).
  * **messages** pack to ``msg_words`` uint32 words per bag slot: a
    header word (type/term/src/dst/3 generic fields/entry-count) plus
    entry words.  Field-set identity (the follow-up CatchupRequest's
    *absent* mcommitIndex, raft.tla:762-771) is preserved by storing an
    absent generic field (-1) as the field's all-ones value.

State *identity* (VIEW semantics, raft.cfg:30) is established by a
64/128-bit fingerprint, not by canonical bytes:  the message bag is
hashed **commutatively** (sum over slots of ``count * mix(words)``), so
slot order never affects identity (the TypedBags (+)/(-) semantics of
raft.tla:226-231).  Symmetry (raft.cfg:29) is the min of the
fingerprint over server relabelings.

Slot order is NOT free, though: it is the order in which Receive,
UpdateTerm, CocDiscard, Duplicate and Drop walk the bag, and so which
member of a VIEW class BFS meets first when the members' histories
(outside the VIEW) differ.  The occupied slots are therefore kept as a
prefix of the bag in one total order of messages, the plain reference's
(bench/reference/plain_bfs.cc: type, term, src, dst, a, b, c, entry
count, then each entry as term, payload, type; an absent field last).
The packing is laid out so that this order is the lexicographic order
of ``msg_key(words)``: the header's fields sit most significant first,
an absent field is all-ones, message entries sit earliest-first in each
word, and ``msg_key`` only moves each entry's type bit below its
payload.

All widths derive from ModelConfig bounds; tests assert round-trip
identity against the oracle representation.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import cached_property

import numpy as np

from ..config import (MT_AEREQ, MT_AERESP, MT_CATREQ, MT_CATRESP, MT_COC,
                      MT_RVREQ, MT_RVRESP, ModelConfig)


def bits_for(maxval: int) -> int:
    """Bits needed to store values 0..maxval."""
    b = 1
    while (1 << b) <= maxval:
        b += 1
    return b


# Generic message-field mapping (type tag -> which oracle tuple positions
# land in generic fields a, b, c).  src/dst positions come from the oracle's
# own table (models/raft.py _SRC_DST) so there is one source of truth.
#   RVREQ   (t, term, lastLogTerm, lastLogIndex, src, dst)       a=llt b=lli
#   RVRESP  (t, term, granted, mlog, src, dst)                   a=granted
#   AEREQ   (t, term, prevIdx, prevTerm, entries, mcommit, s, d) a=pi b=pt c=mc
#   AERESP  (t, term, success, matchIdx, src, dst)               a=succ b=mi
#   CATREQ  (t, term, logLen, entries, mcommit, src, dst, rnds)  a=ll b=mc c=r
#   CATRESP (t, term, success, matchIdx, src, dst, roundsLeft)   a=s b=mi c=rl
#   COC     (t, term, madd, mserver, src, dst)                   a=madd b=msrv
_ABC_ENT = {
    MT_RVREQ:   dict(a=2, b=3, c=None, ent=None),
    MT_RVRESP:  dict(a=2, b=None, c=None, ent=3),
    MT_AEREQ:   dict(a=2, b=3, c=5, ent=4),
    MT_AERESP:  dict(a=2, b=3, c=None, ent=None),
    MT_CATREQ:  dict(a=2, b=4, c=7, ent=3),
    MT_CATRESP: dict(a=2, b=3, c=6, ent=None),
    MT_COC:     dict(a=2, b=3, c=None, ent=None),
}


def _msg_fields():
    from ..models.raft import _SRC_DST
    return {mt: dict(src=_SRC_DST[mt][0], dst=_SRC_DST[mt][1], **abc)
            for mt, abc in _ABC_ENT.items()}


MSG_FIELDS = _msg_fields()


@dataclass(frozen=True)
class Layout:
    cfg: ModelConfig

    # ---- dimensions -----------------------------------------------------
    @cached_property
    def S(self):
        return self.cfg.n_servers

    @cached_property
    def Lmax(self):
        """Max entries carried in one message (mentries/mlog ≤ one log:
        raft.tla:444 comment limits AE to ≤1; catchup sends SubSeq of a
        frontier log, ≤ MaxLogLength; RVResp mlog likewise)."""
        return self.cfg.bounds.max_log_length

    @cached_property
    def Lcap(self):
        """Max representable per-server log: catchup splice of a ≤L prefix
        with ≤L entries (HandleCatchupRequest raft.tla:734-736) = 2L; such
        states are generated+checked but never expanded (CONSTRAINT
        semantics, SURVEY §2.8)."""
        return self.cfg.log_capacity

    @cached_property
    def K(self):
        """Bag slots: distinct messages ≤ BagCardinality ≤ MaxInFlight,
        +1 headroom for the Send that overruns the bound before pruning."""
        return self.cfg.bag_capacity

    # ---- scalar field widths -------------------------------------------
    @cached_property
    def term_bits(self):
        # terms reach max_terms + 1 (Timeout from a max_terms state is
        # generated, then pruned by BoundedTerms)
        return bits_for(self.cfg.bounds.max_terms + 1)

    @cached_property
    def server_bits(self):
        return bits_for(max(self.S - 1, 1))

    @cached_property
    def value_bits(self):
        # entry payload: raw client value (raft.cfg:11 binds small ints)
        # or a config bitmask (S bits)
        return max(bits_for(max(self.cfg.values)), self.S)

    @cached_property
    def entry_bits(self):
        # term | etype(1) | payload ; 0 == "no entry" (real terms ≥ 1)
        return self.term_bits + 1 + self.value_bits

    @cached_property
    def field_bits(self):
        # generic message fields a/b/c (absent=-1 → all-ones, above every
        # value): values span log indices (≤ Lcap+1), terms, server ids,
        # rounds
        fmax = max(self.Lcap + 1, self.cfg.bounds.max_terms + 1, self.S,
                   self.cfg.num_rounds)
        return bits_for(fmax + 1)

    @cached_property
    def entlen_bits(self):
        return bits_for(self.Lmax)

    # ---- message word packing ------------------------------------------
    # word0 (header), most significant field first so that unsigned
    # word order is message order:
    #   mtype | mterm | msrc | mdst | a | b | c | entlen
    # word1..: packed entries, entries_per_word per word, earliest entry
    # most significant
    @cached_property
    def header_shifts(self):
        fields = (("mtype", 3), ("mterm", self.term_bits),
                  ("msrc", self.server_bits), ("mdst", self.server_bits),
                  ("a", self.field_bits), ("b", self.field_bits),
                  ("c", self.field_bits), ("entlen", self.entlen_bits))
        cur = sum(width for _, width in fields)
        if cur > 32:
            raise ValueError(
                f"message header needs {cur} bits > 32; bounds too large "
                f"for the single-header-word packing (split packing TBD)")
        shifts = {}
        for name, width in fields:
            cur -= width
            shifts[name] = (cur, width)
        return shifts

    @cached_property
    def entries_per_word(self):
        return 32 // self.entry_bits

    @cached_property
    def msg_words(self):
        return 1 + (self.Lmax + self.entries_per_word - 1) \
            // self.entries_per_word

    def entry_shift(self, k: int) -> int:
        """Bit offset of message entry ``k`` in its word (word
        ``1 + k // entries_per_word``): earliest entry most significant."""
        epw = self.entries_per_word
        return self.entry_bits * (epw - 1 - k % epw)

    @cached_property
    def _key_masks(self):
        """Per entry word: the term bits, the payload bits and the unit
        bit of every entry slot (msg_key)."""
        vb, eb = self.value_bits, self.entry_bits
        term = pay = unit = 0
        for k in range(self.entries_per_word):
            s = eb * k
            term |= ((1 << eb) - (1 << (vb + 1))) << s
            pay |= ((1 << vb) - 1) << s
            unit |= 1 << s
        return term, pay, unit

    def msg_key(self, words):
        """Packed message words [..., msg_words] (u32, numpy or jnp) ->
        sort-key words whose lexicographic unsigned order is the plain
        reference's message order.  The header word is its own key; in
        each entry word every entry ``term | type | payload`` becomes
        ``term | payload | type`` (three masks and two shifts for the
        whole word)."""
        term, pay, unit = (np.uint32(m) for m in self._key_masks)
        vb = self.value_bits
        out = [words[..., 0]]
        for w in range(1, self.msg_words):
            x = words[..., w]
            out.append((x & term) | ((x & pay) << 1) | ((x >> vb) & unit))
        return out

    # ---- fingerprint salts ---------------------------------------------
    @cached_property
    def n_hash_streams(self):
        return 2 if self.cfg.fp128 else 1

    def describe(self) -> str:
        return (f"Layout(S={self.S}, Lmax={self.Lmax}, Lcap={self.Lcap}, "
                f"K={self.K}, entry_bits={self.entry_bits}, "
                f"msg_words={self.msg_words})")

    def __post_init__(self):
        # packed entries live in int32 log lanes: 31 usable bits
        if self.entry_bits > 31:
            raise ValueError(
                f"entry_bits={self.entry_bits} exceeds the int32 log lane")
        _ = self.header_shifts  # validate eagerly


# ---------------------------------------------------------------------------
# Generic (numpy / jnp polymorphic) bit-field helpers.  All shift amounts
# and masks are static Python ints, so these trace cleanly under jit.
# ---------------------------------------------------------------------------

def get_field(word, shift_width):
    shift, width = shift_width
    return (word >> shift) & ((1 << width) - 1)


def put_field(val, shift_width):
    shift, width = shift_width
    return (val & ((1 << width) - 1)) << shift


def put_field_checked(val, shift_width, name="field"):
    """Host-side fail-loud variant: a value outside the field width means
    the state is un-representable under the configured bounds (possible if
    a user disables the stock constraints) — fault, don't alias."""
    shift, width = shift_width
    if not 0 <= val < (1 << width):
        raise OverflowError(
            f"message {name}={val} exceeds {width}-bit packing; state is "
            f"un-representable under the configured bounds")
    return val << shift


def pack_entry(lay: Layout, term, etype, payload):
    vb = lay.value_bits
    return (term << (1 + vb)) | (etype << vb) | payload


def unpack_entry(lay: Layout, e):
    vb = lay.value_bits
    return e >> (1 + vb), (e >> vb) & 1, e & ((1 << vb) - 1)


def entry_term(lay: Layout, e):
    return e >> (1 + lay.value_bits)


def entry_type(lay: Layout, e):
    return (e >> lay.value_bits) & 1


def entry_payload(lay: Layout, e):
    return e & ((1 << lay.value_bits) - 1)


def hash_salts(lay: Layout, n_words: int, stream: int = 0) -> np.ndarray:
    """Deterministic per-position 64-bit salts for the fingerprint mix."""
    rng = np.random.RandomState(0xC0FFEE + 7919 * stream)
    lo = rng.randint(0, 1 << 32, size=n_words, dtype=np.uint64)
    hi = rng.randint(0, 1 << 32, size=n_words, dtype=np.uint64)
    return (hi << np.uint64(32)) | lo
