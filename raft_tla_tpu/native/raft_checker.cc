// Native multi-threaded explicit-state checker for the Raft spec family.
//
// This is the framework's CPU runtime: a C++ twin of the Python oracle
// (raft_tla_tpu/models/raft.py, which cites tlc_membership/raft.tla
// line-by-line) running a level-synchronous multi-worker BFS — the role
// TLC's Java engine plays for the reference (SURVEY §2.13), and the
// machine-local baseline the TPU engine is benchmarked against
// (BASELINE.md: "TLC -workers 8 on CPU", measured here by us).
//
// Semantics notes mirrored from the oracle:
//   * state identity = the 10 semantic vars (VIEW vars, raft.cfg:30),
//     canonical under server relabeling (SYMMETRY, raft.cfg:29) via
//     min-over-permutations of a 64-bit field-stream hash; history
//     counters ride along but are excluded from identity.
//   * the message bag hashes commutatively (sum over slots of
//     count * mix(msg)), so slot order never affects identity; it is
//     the order the bag is walked in (bag ops below).
//   * CONSTRAINT = don't-expand (state still checked); first-seen
//     survivor per level in frontier order.
//   * UpdateTerm / ReturnToFollowerState / Conflict / NoConflict do not
//     consume the message; HandleCheckOldConfig's discard and process
//     branches overlap for a Leader at the message term.
//
// Exposed C ABI (ctypes): raft_build_config-free — the Python side
// passes a flat int64 config array; see native/__init__.py.

#include <algorithm>
#include <atomic>
#include <cstdint>
#include <cstring>
#include <mutex>
#include <thread>
#include <unordered_map>
#include <vector>

namespace {

constexpr int SMAX = 6;     // servers
constexpr int LMAX = 8;     // max entries in one message
constexpr int LCAPMAX = 16; // max representable log (2 * MaxLogLength)
constexpr int KMAX = 72;    // bag slots
constexpr int VMAX = 8;     // client values
constexpr int PMAX = 720;   // symmetry permutations (<= 6!)

enum Role { FOLLOWER = 0, CANDIDATE = 1, LEADER = 2 };
enum EType { VALUE_ENTRY = 0, CONFIG_ENTRY = 1 };
enum MType {
  MT_NONE = 0, MT_RVREQ, MT_RVRESP, MT_AEREQ, MT_AERESP,
  MT_CATREQ, MT_CATRESP, MT_COC
};
enum Family { FAM_ASYNC = 0, FAM_ASYNC_CRASH, FAM_FULL, FAM_DYNAMIC };
constexpr int8_t NIL = -1;

// Constraint bit order — must match native/__init__.py CONSTRAINT_ORDER.
enum ConBit {
  CB_INFLIGHT = 0, CB_RVREQ, CB_LOGSIZE, CB_RESTARTS, CB_TIMEOUTS,
  CB_TERMS, CB_CLIENTREQ, CB_TRIEDMC, CB_MC, CB_UNCONTESTED,
  CB_CLEANFIRSTREQ, CB_CLEANTWOLEADERS, CB_CLEANFIRSTELECTION,
  CB_COUNT
};
// Invariant bit order — must match native/__init__.py INVARIANT_ORDER.
enum InvBit {
  IB_LEADERVOTESQUORUM = 0, IB_CANDTERMNOTINLOG, IB_ELECTIONSAFETY,
  IB_LOGMATCHING, IB_VOTESGRANTED, IB_VOTESGRANTED_FALSE, IB_QUORUMLOG,
  IB_MOREUPTODATE, IB_LEADERCOMPLETE, IB_LEADERCOMPLETE_FALSE,
  IB_ONEATATIME, IB_COUNT
};

struct Cfg {
  int S, nvals, init_mask, num_rounds, family;
  int vals[VMAX];
  int L, Lcap, K;
  int max_restarts, max_timeouts, max_terms, max_client_requests;
  int max_mc, max_tried, max_inflight, max_trace;
  uint32_t con_mask, inv_mask;
  int symmetry, threads;
  int64_t max_depth, max_states;
  int stop_on_violation;
  // derived
  int value_bits, entry_bits;
  int n_perms;
  int8_t perms[PMAX][SMAX];   // sigma: old -> new
};

struct Msg {
  uint8_t type;
  int16_t term, src, dst, a, b, c;
  uint8_t entlen;
  uint16_t ent[LMAX];
  // memset-based init so struct PADDING is zeroed: operator== compares
  // raw bytes, and indeterminate padding would stop equal messages
  // merging in bag_put (splitting slots breaks the count==1 guards of
  // Duplicate/Drop, raft.tla:926-932).
  Msg() {
    std::memset(this, 0, sizeof(Msg));
    a = b = c = -1;
  }
  bool operator==(const Msg &o) const {
    return std::memcmp(this, &o, sizeof(Msg)) == 0;
  }
};

struct State {
  // VIEW (identity)
  int16_t ct[SMAX];
  int8_t st[SMAX], vf[SMAX];
  int16_t ci[SMAX], llen[SMAX];
  uint16_t log[SMAX][LCAPMAX];
  uint8_t vr[SMAX], vg[SMAX];
  int16_t ni[SMAX][SMAX], mi[SMAX][SMAX];
  Msg bag[KMAX];
  uint8_t cnt[KMAX];
  // non-VIEW (history counters; constraint inputs)
  uint8_t restarted[SMAX], timeoutc[SMAX];
  int16_t nleaders, nreq, ntried, nmc;
  int32_t globlen;
  uint8_t overflow;
};

inline uint16_t pack_entry(const Cfg &c, int term, int etype, int payload) {
  return (uint16_t)((term << (1 + c.value_bits)) |
                    (etype << c.value_bits) | payload);
}
inline int entry_term(const Cfg &c, uint16_t e) {
  return e >> (1 + c.value_bits);
}
inline int entry_type(const Cfg &c, uint16_t e) {
  return (e >> c.value_bits) & 1;
}
inline int entry_payload(const Cfg &c, uint16_t e) {
  return e & ((1 << c.value_bits) - 1);
}

inline int popcount(uint32_t x) { return __builtin_popcount(x); }

// GetConfig (raft.tla:354-360): latest ConfigEntry else InitServer.
inline int get_config(const Cfg &c, const State &s, int i) {
  for (int k = s.llen[i] - 1; k >= 0; --k)
    if (entry_type(c, s.log[i][k]) == CONFIG_ENTRY)
      return entry_payload(c, s.log[i][k]);
  return c.init_mask;
}
// GetMaxConfigIndex (raft.tla:346-351), 1-based.
inline int max_config_index(const Cfg &c, const State &s, int i) {
  for (int k = s.llen[i] - 1; k >= 0; --k)
    if (entry_type(c, s.log[i][k]) == CONFIG_ENTRY) return k + 1;
  return 0;
}
inline int last_term(const Cfg &c, const State &s, int i) {
  return s.llen[i] ? entry_term(c, s.log[i][s.llen[i] - 1]) : 0;
}
// set ∈ Quorum(config) (raft.tla:217): subset + strict majority.
inline bool in_quorum(uint32_t votes, uint32_t config) {
  if (votes & ~config) return false;
  return 2 * popcount(votes) > popcount(config);
}

// ---------------------------------------------------------------------
// Bag ops (TypedBags (+)/(-), raft.tla:226-231).  The occupied slots
// are a prefix of the bag in one total order of messages, the plain
// reference's (type, term, src, dst, a, b, c, entry count, then each
// entry as term, payload, type; an absent field last): the receive,
// duplicate and drop loops walk the slots in that order, so BFS keeps
// the reference's member of a VIEW class (ops/layout.py).
// ---------------------------------------------------------------------

inline int msg_cmp(const Cfg &c, const Msg &x, const Msg &y) {
  const int hx[8] = {x.type, (uint16_t)x.term, x.src, x.dst,
                     (uint16_t)x.a, (uint16_t)x.b, (uint16_t)x.c, x.entlen};
  const int hy[8] = {y.type, (uint16_t)y.term, y.src, y.dst,
                     (uint16_t)y.a, (uint16_t)y.b, (uint16_t)y.c, y.entlen};
  for (int f = 0; f < 8; ++f)
    if (hx[f] != hy[f]) return hx[f] < hy[f] ? -1 : 1;
  for (int k = 0; k < x.entlen; ++k) {
    // term, payload, type: the reference's entry bytes
    auto key = [&](uint16_t e) {
      return (entry_term(c, e) << (1 + c.value_bits)) |
             (entry_payload(c, e) << 1) | entry_type(c, e);
    };
    int kx = key(x.ent[k]), ky = key(y.ent[k]);
    if (kx != ky) return kx < ky ? -1 : 1;
  }
  return 0;
}

inline void bag_put(const Cfg &c, State &s, const Msg &m) {
  int k = 0;
  int cmp = 1;
  while (k < c.K && s.cnt[k] && (cmp = msg_cmp(c, s.bag[k], m)) < 0) ++k;
  if (k < c.K && s.cnt[k] && cmp == 0) { s.cnt[k]++; return; }
  if (s.cnt[c.K - 1]) { s.overflow = 1; return; }
  for (int q = c.K - 1; q > k; --q) {
    s.bag[q] = s.bag[q - 1];
    s.cnt[q] = s.cnt[q - 1];
  }
  s.bag[k] = m;
  s.cnt[k] = 1;
}

inline void bag_del(State &s, int k) {
  if (--s.cnt[k]) return;
  for (; k + 1 < KMAX && s.cnt[k + 1]; ++k) {
    s.bag[k] = s.bag[k + 1];
    s.cnt[k] = s.cnt[k + 1];
  }
  s.bag[k] = Msg{};
  s.cnt[k] = 0;
}

// ---------------------------------------------------------------------
// Hashing: canonical under symmetry, commutative over the bag
// ---------------------------------------------------------------------

inline uint64_t mix64(uint64_t x) {
  x += 0x9E3779B97F4A7C15ull;
  x = (x ^ (x >> 30)) * 0xBF58476D1CE4E5B9ull;
  x = (x ^ (x >> 27)) * 0x94D049BB133111EBull;
  return x ^ (x >> 31);
}

inline uint32_t perm_mask(uint32_t m, const int8_t *sigma, int S) {
  uint32_t out = 0;
  for (int i = 0; i < S; ++i)
    if (m >> i & 1) out |= 1u << sigma[i];
  return out;
}

inline uint16_t perm_entry(const Cfg &c, uint16_t e, const int8_t *sigma) {
  if (!e || entry_type(c, e) != CONFIG_ENTRY) return e;
  return pack_entry(c, entry_term(c, e), CONFIG_ENTRY,
                    perm_mask(entry_payload(c, e), sigma, c.S));
}

inline uint64_t hash_msg(const Cfg &c, const Msg &m, const int8_t *sigma) {
  uint64_t h = 0x51ED270B0B0B0B0Bull;
  h = mix64(h ^ m.type);
  h = mix64(h ^ (uint64_t)(uint16_t)m.term);
  h = mix64(h ^ (uint64_t)sigma[m.src]);
  h = mix64(h ^ (uint64_t)sigma[m.dst]);
  h = mix64(h ^ (uint64_t)(uint16_t)(m.a + 1));
  int b = (m.type == MT_COC) ? sigma[m.b] : m.b;
  h = mix64(h ^ (uint64_t)(uint16_t)(b + 1));
  h = mix64(h ^ (uint64_t)(uint16_t)(m.c + 1));
  h = mix64(h ^ m.entlen);
  for (int k = 0; k < m.entlen; ++k)
    h = mix64(h ^ perm_entry(c, m.ent[k], sigma));
  return h;
}

inline uint64_t hash_perm(const Cfg &c, const State &s, const int8_t *sigma) {
  int S = c.S;
  int8_t inv[SMAX];
  for (int i = 0; i < S; ++i) inv[sigma[i]] = (int8_t)i;
  uint64_t h = 0;
  uint64_t pos = 1;
  auto put = [&](uint64_t v) { h += mix64(v + 0x1000003 * (pos++)); };
  for (int k = 0; k < S; ++k) {
    int i = inv[k];
    put(s.ct[i]);
    put(s.st[i]);
    put(s.vf[i] == NIL ? (uint64_t)S : (uint64_t)sigma[(int)s.vf[i]]);
    put(s.ci[i]);
    put(s.llen[i]);
    for (int p = 0; p < c.Lcap; ++p) put(perm_entry(c, s.log[i][p], sigma));
    put(perm_mask(s.vr[i], sigma, S));
    put(perm_mask(s.vg[i], sigma, S));
    for (int l = 0; l < S; ++l) put(s.ni[i][inv[l]]);
    for (int l = 0; l < S; ++l) put(s.mi[i][inv[l]]);
  }
  uint64_t bag = 0;
  for (int k = 0; k < c.K; ++k)
    if (s.cnt[k]) bag += (uint64_t)s.cnt[k] * hash_msg(c, s.bag[k], sigma);
  return h + mix64(bag);
}

inline uint64_t fingerprint(const Cfg &c, const State &s) {
  uint64_t best = ~0ull;
  for (int p = 0; p < c.n_perms; ++p)
    best = std::min(best, hash_perm(c, s, c.perms[p]));
  return best;
}

// ---------------------------------------------------------------------
// Actions (oracle: models/raft.py; spec: tlc_membership/raft.tla §2.4-2.5)
// ---------------------------------------------------------------------

using Emit = void (*)(void *, const State &);

struct Ctx {
  const Cfg *c;
  void *sink;
  Emit emit;
};

inline void restart(Ctx &x, const State &s, int i) {  // raft.tla:401-411
  const Cfg &c = *x.c;
  State t = s;
  t.st[i] = FOLLOWER;
  t.vr[i] = t.vg[i] = 0;
  for (int j = 0; j < c.S; ++j) { t.ni[i][j] = 1; t.mi[i][j] = 0; }
  t.ci[i] = 0;
  t.restarted[i]++;
  t.globlen++;
  x.emit(x.sink, t);
}

inline void timeout(Ctx &x, const State &s, int i) {  // raft.tla:415-427
  const Cfg &c = *x.c;
  if (s.st[i] == LEADER) return;
  if (!(get_config(c, s, i) >> i & 1)) return;
  State t = s;
  t.st[i] = CANDIDATE;
  if (t.ct[i] + 1 > c.max_terms + 1) t.overflow = 1; else t.ct[i]++;
  t.vf[i] = NIL;
  t.vr[i] = t.vg[i] = 0;
  t.timeoutc[i]++;
  t.globlen++;
  x.emit(x.sink, t);
}

inline void request_vote(Ctx &x, const State &s, int i, int j) {  // :431-440
  const Cfg &c = *x.c;
  if (s.st[i] != CANDIDATE) return;
  if (!((get_config(c, s, i) & ~s.vr[i]) >> j & 1)) return;
  State t = s;
  Msg m;
  m.type = MT_RVREQ; m.term = s.ct[i]; m.src = (int16_t)i; m.dst = (int16_t)j;
  m.a = (int16_t)last_term(c, s, i); m.b = s.llen[i];
  bag_put(c, t, m);
  t.globlen++;
  x.emit(x.sink, t);
}

inline void append_entries(Ctx &x, const State &s, int i, int j) { // :446-468
  const Cfg &c = *x.c;
  if (i == j || s.st[i] != LEADER) return;
  if (!(get_config(c, s, i) >> j & 1)) return;
  int nij = s.ni[i][j];
  int prev_idx = nij - 1;
  int prev_term = (prev_idx > 0 && prev_idx <= s.llen[i])
                      ? entry_term(c, s.log[i][prev_idx - 1]) : 0;
  int last_entry = std::min<int>(s.llen[i], nij);
  State t = s;
  Msg m;
  m.type = MT_AEREQ; m.term = s.ct[i]; m.src = (int16_t)i;
  m.dst = (int16_t)j;
  m.a = (int16_t)prev_idx; m.b = (int16_t)prev_term;
  m.c = (int16_t)std::min<int>(s.ci[i], last_entry);
  if (nij <= last_entry) { m.entlen = 1; m.ent[0] = s.log[i][nij - 1]; }
  bag_put(c, t, m);
  t.globlen++;
  x.emit(x.sink, t);
}

inline void become_leader(Ctx &x, const State &s, int i) {  // :472-484
  const Cfg &c = *x.c;
  if (s.st[i] != CANDIDATE) return;
  if (!in_quorum(s.vg[i], get_config(c, s, i))) return;
  State t = s;
  t.st[i] = LEADER;
  for (int j = 0; j < c.S; ++j) {
    t.ni[i][j] = (int16_t)(s.llen[i] + 1);
    t.mi[i][j] = 0;
  }
  t.nleaders++;
  t.globlen++;
  x.emit(x.sink, t);
}

inline void client_request(Ctx &x, const State &s, int i, int v) { // :488-497
  const Cfg &c = *x.c;
  if (s.st[i] != LEADER) return;
  State t = s;
  if (s.llen[i] >= c.Lcap) t.overflow = 1;
  else {
    t.log[i][s.llen[i]] = pack_entry(c, s.ct[i], VALUE_ENTRY, v);
    t.llen[i]++;
  }
  t.nreq++;   // no global record (raft.tla:488-497)
  x.emit(x.sink, t);
}

inline void advance_commit_index(Ctx &x, const State &s, int i) { // :504-539
  const Cfg &c = *x.c;
  if (s.st[i] != LEADER) return;
  uint32_t config = get_config(c, s, i);
  int max_agree = 0;
  for (int idx = 1; idx <= s.llen[i]; ++idx) {
    uint32_t agree = 1u << i;
    for (int k = 0; k < c.S; ++k)
      if ((config >> k & 1) && s.mi[i][k] >= idx) agree |= 1u << k;
    if (in_quorum(agree, config)) max_agree = idx;
  }
  State t = s;
  int new_ci = s.ci[i];
  if (max_agree > 0 &&
      entry_term(c, s.log[i][max_agree - 1]) == s.ct[i])
    new_ci = max_agree;
  t.ci[i] = (int16_t)new_ci;
  // CommitEntry vs CommitMembershipChange (raft.tla:522-538) both append
  // one record; the distinction feeds feature lanes (python-side only).
  if (new_ci > s.ci[i]) t.globlen++;
  x.emit(x.sink, t);
}

inline void add_new_server(Ctx &x, const State &s, int i, int j) { // :542-555
  const Cfg &c = *x.c;
  if (s.st[i] != LEADER) return;
  if (get_config(c, s, i) >> j & 1) return;
  State t = s;
  t.ct[j] = 1;
  t.vf[j] = NIL;
  Msg m;
  m.type = MT_CATREQ; m.term = s.ct[i]; m.src = (int16_t)i;
  m.dst = (int16_t)j;
  m.a = s.mi[i][j];                       // mlogLen (raft.tla:549)
  m.b = s.ci[i];                          // mcommitIndex
  m.c = (int16_t)c.num_rounds;
  int nij = s.ni[i][j];
  int n = std::max(0, std::min<int>(s.ci[i] - nij + 1, LMAX));
  if (s.ci[i] - nij + 1 > LMAX) t.overflow = 1;
  for (int k = 0; k < n; ++k) m.ent[k] = s.log[i][nij - 1 + k];
  m.entlen = (uint8_t)n;
  bag_put(c, t, m);
  t.ntried++;
  t.globlen += 2;                         // TryAddServer + Send
  x.emit(x.sink, t);
}

inline void delete_server(Ctx &x, const State &s, int i, int j) { // :558-569
  const Cfg &c = *x.c;
  if (s.st[i] != LEADER || s.st[j] == LEADER || i == j) return;
  if (!(get_config(c, s, i) >> j & 1)) return;
  State t = s;
  Msg m;
  m.type = MT_COC; m.term = s.ct[i]; m.src = (int16_t)i; m.dst = (int16_t)i;
  m.a = 0; m.b = (int16_t)j;
  bag_put(c, t, m);
  t.ntried++;
  t.globlen += 2;                         // TryRemoveServer + Send
  x.emit(x.sink, t);
}

inline void duplicate_message(Ctx &x, const State &s, int k) {  // :892-896
  if (s.cnt[k] != 1) return;
  State t = s;
  t.cnt[k]++;
  x.emit(x.sink, t);
}

inline void drop_message(Ctx &x, const State &s, int k) {       // :900-904
  if (s.cnt[k] != 1) return;
  State t = s;
  bag_del(t, k);
  x.emit(x.sink, t);
}

// Receive (raft.tla:842-863): UpdateTerm lane + per-type handlers.
inline void receive(Ctx &x, const State &s, int k) {
  const Cfg &c = *x.c;
  if (!s.cnt[k]) return;
  const Msg &m = s.bag[k];
  int i = m.dst, j = m.src;

  // UpdateTerm (raft.tla:826-832): msg NOT consumed.
  if (m.term > s.ct[i]) {
    State t = s;
    t.ct[i] = m.term;
    t.st[i] = FOLLOWER;
    t.vf[i] = NIL;
    x.emit(x.sink, t);
  }

  switch (m.type) {
    case MT_RVREQ: {                      // raft.tla:578-597
      if (m.term > s.ct[i]) break;
      int lt = last_term(c, s, i);
      bool log_ok = m.a > lt || (m.a == lt && m.b >= s.llen[i]);
      bool grant = m.term == s.ct[i] && log_ok &&
                   (s.vf[i] == NIL || s.vf[i] == j);
      State t = s;
      if (grant) t.vf[i] = (int8_t)j;
      Msg r;
      r.type = MT_RVRESP; r.term = s.ct[i]; r.src = (int16_t)i;
      r.dst = (int16_t)j;
      r.a = grant ? 1 : 0;
      r.entlen = (uint8_t)std::min<int>(s.llen[i], LMAX);  // mlog :591-593
      for (int p = 0; p < r.entlen; ++p) r.ent[p] = s.log[i][p];
      if (s.llen[i] > LMAX) t.overflow = 1;
      bag_del(t, k);
      bag_put(c, t, r);
      t.globlen += 2;
      x.emit(x.sink, t);
      break;
    }
    case MT_RVRESP: {                     // raft.tla:836-839, 602-614
      if (m.term > s.ct[i]) break;
      State t = s;
      if (m.term == s.ct[i]) {
        t.vr[i] |= 1u << j;
        if (m.a == 1) t.vg[i] |= 1u << j;
      }
      bag_del(t, k);
      t.globlen++;
      x.emit(x.sink, t);
      break;
    }
    case MT_AEREQ: {                      // raft.tla:617-700
      if (m.term > s.ct[i]) break;
      bool eq = m.term == s.ct[i];
      int prev_idx = m.a;
      bool log_ok = prev_idx == 0 ||
                    (prev_idx > 0 && prev_idx <= s.llen[i] &&
                     m.b == entry_term(c, s.log[i][prev_idx - 1]));
      if (m.term < s.ct[i] || (eq && s.st[i] == FOLLOWER && !log_ok)) {
        State t = s;                      // Reject :617-629
        Msg r;
        r.type = MT_AERESP; r.term = s.ct[i]; r.src = (int16_t)i;
        r.dst = (int16_t)j; r.a = 0; r.b = 0;
        bag_del(t, k);
        bag_put(c, t, r);
        t.globlen += 2;
        x.emit(x.sink, t);
      } else if (eq && s.st[i] == CANDIDATE) {
        State t = s;                      // ReturnToFollower :632-636
        t.st[i] = FOLLOWER;               // msg NOT consumed
        x.emit(x.sink, t);
      } else if (eq && s.st[i] == FOLLOWER && log_ok) {
        int index = prev_idx + 1;
        bool have_at = s.llen[i] >= index;
        bool term_match =
            have_at && m.entlen &&
            entry_term(c, s.log[i][index - 1]) == entry_term(c, m.ent[0]);
        if (m.entlen == 0 || (have_at && term_match)) {
          State t = s;                    // AlreadyDone :639-655
          t.ci[i] = m.c;                  // can DECREASE (comment :644)
          Msg r;
          r.type = MT_AERESP; r.term = s.ct[i]; r.src = (int16_t)i;
          r.dst = (int16_t)j; r.a = 1;
          r.b = (int16_t)(prev_idx + m.entlen);
          bag_del(t, k);
          bag_put(c, t, r);
          t.globlen += 2;
          x.emit(x.sink, t);
        } else if (m.entlen && have_at && !term_match) {
          State t = s;                    // Conflict :658-665 (no reply)
          t.log[i][s.llen[i] - 1] = 0;
          t.llen[i]--;
          x.emit(x.sink, t);
        } else if (m.entlen && s.llen[i] == prev_idx) {
          State t = s;                    // NoConflict :668-672 (no reply)
          if (s.llen[i] >= c.Lcap) t.overflow = 1;
          else { t.log[i][s.llen[i]] = m.ent[0]; t.llen[i]++; }
          x.emit(x.sink, t);
        }
      }
      break;
    }
    case MT_AERESP: {                     // raft.tla:705-715
      if (m.term > s.ct[i]) break;
      State t = s;
      if (m.term == s.ct[i]) {
        if (m.a == 1) {
          t.ni[i][j] = (int16_t)(m.b + 1);
          t.mi[i][j] = m.b;
        } else {
          t.ni[i][j] = (int16_t)std::max(s.ni[i][j] - 1, 1);
        }
      }
      bag_del(t, k);
      t.globlen++;
      x.emit(x.sink, t);
      break;
    }
    case MT_CATREQ: {                     // raft.tla:718-745
      if (m.term < s.ct[i]) {
        State t = s;
        Msg r;
        r.type = MT_CATRESP; r.term = s.ct[i]; r.src = (int16_t)i;
        r.dst = (int16_t)j; r.a = 0; r.b = 0; r.c = 0;
        bag_del(t, k);
        bag_put(c, t, r);
        t.globlen += 2;
        x.emit(x.sink, t);
      } else {
        State t = s;
        int old_len = s.llen[i];
        int prefix = std::min<int>(m.a, old_len);
        int new_len = prefix + m.entlen;
        if (new_len > c.Lcap) t.overflow = 1;
        else {
          for (int p = 0; p < m.entlen; ++p)
            t.log[i][prefix + p] = m.ent[p];
          for (int p = new_len; p < old_len; ++p) t.log[i][p] = 0;
          t.llen[i] = (int16_t)new_len;
        }
        t.ct[i] = m.term;                 // adopt (raft.tla:737)
        Msg r;                            // mmatchIndex = PRE-splice len
        r.type = MT_CATRESP; r.term = m.term; r.src = (int16_t)i;
        r.dst = (int16_t)j; r.a = 1; r.b = (int16_t)old_len;
        r.c = (int16_t)(m.c - 1);
        bag_del(t, k);
        bag_put(c, t, r);
        t.globlen += 2;
        x.emit(x.sink, t);
      }
      break;
    }
    case MT_CATRESP: {                    // raft.tla:748-792
      bool progress = (m.b != s.ci[i] && m.b != s.mi[i][j]) ||
                      m.b == s.ci[i];
      bool accept = m.a == 1 && progress && s.st[i] == LEADER &&
                    m.term == s.ct[i] &&
                    !(get_config(c, s, i) >> j & 1);
      State t = s;
      if (accept) {
        int old_nij = s.ni[i][j];
        t.ni[i][j] = (int16_t)(m.b + 1);
        t.mi[i][j] = m.b;
        Msg r;
        if (m.c != 0) {                   // follow-up CatchupRequest
          r.type = MT_CATREQ; r.term = s.ct[i]; r.src = (int16_t)i;
          r.dst = (int16_t)j;
          r.a = (int16_t)(old_nij - 1);   // unprimed nextIndex :764-767
          r.b = -1;                       // mcommitIndex ABSENT :762-771
          r.c = m.c;
          int n = std::max(0, std::min<int>(s.ci[i] - old_nij + 1, LMAX));
          if (s.ci[i] - old_nij + 1 > LMAX) t.overflow = 1;
          for (int p = 0; p < n; ++p) r.ent[p] = s.log[i][old_nij - 1 + p];
          r.entlen = (uint8_t)n;
        } else {                          // CheckOldConfig to self
          r.type = MT_COC; r.term = s.ct[i]; r.src = (int16_t)i;
          r.dst = (int16_t)i; r.a = 1; r.b = (int16_t)j;
        }
        bag_del(t, k);
        bag_put(c, t, r);
        t.globlen += 2;
      } else {
        bag_del(t, k);
        t.globlen++;
      }
      x.emit(x.sink, t);
      break;
    }
    case MT_COC: {                        // raft.tla:795-822
      // discard branch (guard :796 — OVERLAPS the process branch)
      if (s.st[i] != LEADER || m.term == s.ct[i]) {
        State t = s;
        bag_del(t, k);
        t.globlen++;
        x.emit(x.sink, t);
      }
      if (s.st[i] == LEADER && m.term == s.ct[i]) {
        if (max_config_index(c, s, i) <= s.ci[i]) {
          uint32_t config = get_config(c, s, i);
          uint32_t nc = m.a ? (config | 1u << m.b)
                            : (config & ~(1u << m.b));
          State t = s;
          if (nc != config) {
            if (s.llen[i] >= c.Lcap) t.overflow = 1;
            else {
              t.log[i][s.llen[i]] =
                  pack_entry(c, s.ct[i], CONFIG_ENTRY, (int)nc);
              t.llen[i]++;
            }
            t.nmc++;
            bag_del(t, k);
            t.globlen += 2;               // Receive + Add/RemoveServer
          } else {
            bag_del(t, k);
            t.globlen++;
          }
          x.emit(x.sink, t);
        } else {                          // retry loop :813-821
          State t = s;
          Msg r = m;                      // re-send same COC to self
          bag_del(t, k);
          bag_put(c, t, r);
          t.globlen += 2;
          x.emit(x.sink, t);
        }
      }
      break;
    }
    default:
      break;
  }
}

// Successor enumeration in the oracle's order (models/raft.py
// successors(); raft.tla:909-943).
inline void successors(Ctx &x, const State &s) {
  const Cfg &c = *x.c;
  for (int i = 0; i < c.S; ++i)
    for (int j = 0; j < c.S; ++j) request_vote(x, s, i, j);
  for (int i = 0; i < c.S; ++i) become_leader(x, s, i);
  for (int i = 0; i < c.S; ++i)
    for (int v = 0; v < c.nvals; ++v) client_request(x, s, i, c.vals[v]);
  for (int i = 0; i < c.S; ++i) advance_commit_index(x, s, i);
  for (int i = 0; i < c.S; ++i)
    for (int j = 0; j < c.S; ++j) append_entries(x, s, i, j);
  for (int k = 0; k < c.K; ++k) receive(x, s, k);
  for (int i = 0; i < c.S; ++i) timeout(x, s, i);
  if (c.family >= FAM_ASYNC_CRASH)
    for (int i = 0; i < c.S; ++i) restart(x, s, i);
  if (c.family >= FAM_FULL) {
    for (int k = 0; k < c.K; ++k) duplicate_message(x, s, k);
    for (int k = 0; k < c.K; ++k) drop_message(x, s, k);
  }
  if (c.family == FAM_DYNAMIC) {
    for (int i = 0; i < c.S; ++i)
      for (int j = 0; j < c.S; ++j) add_new_server(x, s, i, j);
    for (int i = 0; i < c.S; ++i)
      for (int j = 0; j < c.S; ++j) delete_server(x, s, i, j);
  }
}

// ---------------------------------------------------------------------
// Constraints (raft.tla:1105-1137) and invariants (:988-1099)
// ---------------------------------------------------------------------

inline bool constraints_ok(const Cfg &c, const State &s) {
  uint32_t m = c.con_mask;
  if (m >> CB_INFLIGHT & 1) {
    int total = 0;
    for (int k = 0; k < c.K; ++k) total += s.cnt[k];
    if (total > c.max_inflight) return false;
  }
  if (m >> CB_RVREQ & 1)
    for (int k = 0; k < c.K; ++k)
      if (s.bag[k].type == MT_RVREQ && s.cnt[k] > 1) return false;
  if (m >> CB_LOGSIZE & 1)
    for (int i = 0; i < c.S; ++i)
      if (s.llen[i] > c.L) return false;
  if (m >> CB_RESTARTS & 1)
    for (int i = 0; i < c.S; ++i)
      if (s.restarted[i] > c.max_restarts) return false;
  if (m >> CB_TIMEOUTS & 1)
    for (int i = 0; i < c.S; ++i)
      if (s.timeoutc[i] > c.max_timeouts) return false;
  if (m >> CB_TERMS & 1)
    for (int i = 0; i < c.S; ++i)
      if (s.ct[i] > c.max_terms) return false;
  if (m >> CB_CLIENTREQ & 1 && s.nreq > c.max_client_requests) return false;
  if (m >> CB_TRIEDMC & 1 && s.ntried > c.max_tried) return false;
  if (m >> CB_MC & 1 && s.nmc > c.max_mc) return false;
  int ncand = 0, sum_to = 0, sum_rs = 0;
  bool any_restart = false;
  for (int i = 0; i < c.S; ++i) {
    ncand += s.st[i] == CANDIDATE;
    sum_to += s.timeoutc[i];
    sum_rs += s.restarted[i];
    any_restart |= s.restarted[i] != 0;
  }
  if (m >> CB_UNCONTESTED & 1 && ncand > 1) return false;
  if (m >> CB_CLEANFIRSTREQ & 1 && s.nleaders < 1 && s.nreq < 1)
    if (any_restart || sum_to > 1 || ncand > 1) return false;
  if (m >> CB_CLEANTWOLEADERS & 1 && s.nleaders < 2)
    if (sum_rs > 1 || sum_to > 2) return false;
  if (m >> CB_CLEANFIRSTELECTION & 1 && s.nleaders < 1)
    if (any_restart || ncand > 1) return false;
  return true;
}

// IsPrefix(Committed(i), log[j])  (raft.tla:969; committed clamps)
inline bool prefix_ok(const Cfg &c, const State &s, int i, int j) {
  int n = std::min<int>(s.ci[i], s.llen[i]);
  if (n > s.llen[j]) return false;
  for (int k = 0; k < n; ++k)
    if (s.log[i][k] != s.log[j][k]) return false;
  return true;
}

// Returns a bitmask of VIOLATED invariants.
inline uint32_t check_invariants(const Cfg &c, const State &s) {
  uint32_t viol = 0;
  uint32_t m = c.inv_mask;
  int S = c.S;

  if (m >> IB_LEADERVOTESQUORUM & 1 && s.nmc == 0) {  // :988-993
    for (int i = 0; i < S; ++i) {
      if (s.st[i] != LEADER) continue;
      uint32_t voters = 0;
      for (int j = 0; j < S; ++j)
        if (s.ct[j] > s.ct[i] || (s.ct[j] == s.ct[i] && s.vf[j] == i))
          voters |= 1u << j;
      if (!in_quorum(voters, get_config(c, s, i)))
        viol |= 1u << IB_LEADERVOTESQUORUM;
    }
  }
  if (m >> IB_CANDTERMNOTINLOG & 1 && s.nmc == 0) {   // :997-1004
    for (int i = 0; i < S; ++i) {
      if (s.st[i] != CANDIDATE) continue;
      uint32_t voters = 0;
      for (int j = 0; j < S; ++j)
        if (s.ct[j] == s.ct[i] && (s.vf[j] == i || s.vf[j] == NIL))
          voters |= 1u << j;
      if (!in_quorum(voters, get_config(c, s, i))) continue;
      for (int j = 0; j < S; ++j)
        for (int k = 0; k < s.llen[j]; ++k)
          if (entry_term(c, s.log[j][k]) == s.ct[i])
            viol |= 1u << IB_CANDTERMNOTINLOG;
    }
  }
  if (m >> IB_ELECTIONSAFETY & 1) {                   // :1009-1014
    for (int i = 0; i < S; ++i) {
      if (s.st[i] != LEADER) continue;
      int mine = 0;
      for (int k = 0; k < s.llen[i]; ++k)
        if (entry_term(c, s.log[i][k]) == s.ct[i]) mine = k + 1;
      for (int j = 0; j < S; ++j) {
        int other = 0;
        for (int k = 0; k < s.llen[j]; ++k)
          if (entry_term(c, s.log[j][k]) == s.ct[i]) other = k + 1;
        if (other > mine) viol |= 1u << IB_ELECTIONSAFETY;
      }
    }
  }
  if (m >> IB_LOGMATCHING & 1) {                      // :1017-1021
    for (int i = 0; i < S; ++i)
      for (int j = 0; j < S; ++j) {
        int upto = std::min<int>(s.llen[i], s.llen[j]);
        bool pref_eq = true;
        for (int k = 0; k < upto; ++k) {
          pref_eq = pref_eq && s.log[i][k] == s.log[j][k];
          if (entry_term(c, s.log[i][k]) == entry_term(c, s.log[j][k]) &&
              !pref_eq)
            viol |= 1u << IB_LOGMATCHING;
        }
      }
  }
  if (m >> IB_VOTESGRANTED & 1) {                     // :1048-1052
    for (int i = 0; i < S; ++i)
      if (s.vf[i] != NIL && !prefix_ok(c, s, i, s.vf[i]))
        viol |= 1u << IB_VOTESGRANTED;
  }
  if (m >> IB_VOTESGRANTED_FALSE & 1) {               // :1038-1046
    for (int i = 0; i < S; ++i)
      for (int j = 0; j < S; ++j)
        if ((s.vg[i] >> j & 1) && s.ct[i] == s.ct[j] &&
            !prefix_ok(c, s, j, i))
          viol |= 1u << IB_VOTESGRANTED_FALSE;
  }
  if (m >> IB_QUORUMLOG & 1) {                        // :1056-1060
    for (int i = 0; i < S; ++i) {
      uint32_t config = get_config(c, s, i), good = 0;
      for (int j = 0; j < S; ++j)
        if (prefix_ok(c, s, i, j)) good |= 1u << j;
      uint32_t bad = config & ~good;
      if (2 * popcount(bad) > popcount(config))
        viol |= 1u << IB_QUORUMLOG;
    }
  }
  if (m >> IB_MOREUPTODATE & 1) {                     // :1066-1071
    for (int i = 0; i < S; ++i)
      for (int j = 0; j < S; ++j) {
        int li = last_term(c, s, i), lj = last_term(c, s, j);
        bool more = li > lj || (li == lj && s.llen[i] >= s.llen[j]);
        if (more && !prefix_ok(c, s, j, i))
          viol |= 1u << IB_MOREUPTODATE;
      }
  }
  if (m >> IB_LEADERCOMPLETE & 1) {                   // :1089-1099
    for (int i = 0; i < S; ++i) {
      int n = std::min<int>(s.ci[i], s.llen[i]);
      for (int k = 0; k < n; ++k)
        for (int l = 0; l < S; ++l)
          if (s.st[l] == LEADER &&
              s.ct[l] > entry_term(c, s.log[i][k]) &&
              (s.llen[l] <= k || s.log[l][k] != s.log[i][k]))
            viol |= 1u << IB_LEADERCOMPLETE;
    }
  }
  if (m >> IB_LEADERCOMPLETE_FALSE & 1) {             // :1079-1083
    for (int i = 0; i < S; ++i)
      if (s.st[i] == LEADER)
        for (int j = 0; j < S; ++j)
          if (!prefix_ok(c, s, j, i))
            viol |= 1u << IB_LEADERCOMPLETE_FALSE;
  }
  if (m >> IB_ONEATATIME & 1) {                       // ours (SURVEY)
    for (int i = 0; i < S; ++i) {
      int n = 0;
      for (int k = s.ci[i]; k < s.llen[i]; ++k)
        n += entry_type(c, s.log[i][k]) == CONFIG_ENTRY;
      if (n > 1) viol |= 1u << IB_ONEATATIME;
    }
  }
  return viol;
}

// ---------------------------------------------------------------------
// Multi-threaded level-synchronous BFS
// ---------------------------------------------------------------------

constexpr int NSHARD = 64;

// Deterministic first-seen under any thread count.  A VIEW fingerprint
// drops the history counters, so WHICH of several VIEW-equal states
// joins the frontier decides the constraint inputs downstream; TLC,
// the oracle and the TPU engine keep the first one in BFS order.  Each
// candidate therefore carries its level-local rank (frontier index,
// then successor order), and the smallest rank per fingerprint wins.
struct VisitedSet {
  // fp -> 0 for a state of an earlier level, else 1 + the smallest
  // rank offered for it in the current level so far
  std::unordered_map<uint64_t, uint64_t> shard[NSHARD];
  std::mutex mu[NSHARD];
  // true if (fp, rank) is, for now, the current level's best candidate
  bool offer(uint64_t fp, uint64_t rank) {
    int sh = fp & (NSHARD - 1);
    std::lock_guard<std::mutex> g(mu[sh]);
    auto it = shard[sh].find(fp);
    if (it == shard[sh].end()) {
      shard[sh].emplace(fp, rank + 1);
      return true;
    }
    if (it->second == 0 || it->second <= rank + 1) return false;
    it->second = rank + 1;
    return true;
  }
  // after the level's workers joined (no lock needed)
  bool won(uint64_t fp, uint64_t rank) {
    return shard[fp & (NSHARD - 1)].find(fp)->second == rank + 1;
  }
  void settle(uint64_t fp) { shard[fp & (NSHARD - 1)][fp] = 0; }
};

struct Stats {
  int64_t distinct = 0, generated = 0, depth = 0, overflow = 0;
  uint32_t violated = 0;   // union of violated invariant bits
};

// a candidate that was the best for its fingerprint when offered;
// the State itself is kept only when it passes the constraints
struct Cand {
  uint64_t fp, rank;
  uint32_t violated;
  uint8_t overflow;
  int64_t keep;            // index into WorkerSink::keep, or -1
};

struct WorkerSink {
  const Cfg *c;
  VisitedSet *visited;
  uint64_t rank = 0;       // next candidate's level-local rank
  std::vector<Cand> cand;
  std::vector<State> keep;
  int64_t generated = 0;
};

void worker_emit(void *sink_, const State &t) {
  auto *w = static_cast<WorkerSink *>(sink_);
  w->generated++;
  uint64_t rank = w->rank++;
  uint64_t fp = fingerprint(*w->c, t);
  if (!w->visited->offer(fp, rank)) return;
  int64_t k = -1;
  if (constraints_ok(*w->c, t)) {
    k = (int64_t)w->keep.size();
    w->keep.push_back(t);
  }
  w->cand.push_back(Cand{fp, rank, check_invariants(*w->c, t),
                         t.overflow, k});
}

}  // namespace

extern "C" {

// cfg_arr layout — keep in sync with native/__init__.py _pack_cfg():
//  [0]=S [1]=nvals [2..9]=vals [10]=init_mask [11]=num_rounds [12]=family
//  [13]=L [14]=Lcap [15]=K [16]=max_restarts [17]=max_timeouts
//  [18]=max_terms [19]=max_client_requests [20]=max_mc [21]=max_tried
//  [22]=max_inflight [23]=max_trace [24]=con_mask [25]=inv_mask
//  [26]=symmetry [27]=threads [28]=max_depth [29]=max_states
//  [30]=stop_on_violation [31]=value_bits
//  [32]=n_perms [33...]=perms flattened (n_perms * S entries)
// out: [0]=distinct [1]=generated [2]=depth [3]=violated_mask [4]=overflow
//      [8 + d - 1]=post-constraint frontier size after level d, for
//      d <= MAX_LEVELS (the oracle's level_sizes)
constexpr int64_t MAX_LEVELS = 256;
int64_t raft_check(const int64_t *a, int64_t *out) {
  Cfg c{};
  c.S = (int)a[0];
  c.nvals = (int)a[1];
  for (int v = 0; v < c.nvals; ++v) c.vals[v] = (int)a[2 + v];
  c.init_mask = (int)a[10];
  c.num_rounds = (int)a[11];
  c.family = (int)a[12];
  c.L = (int)a[13];
  c.Lcap = (int)a[14];
  c.K = (int)a[15];
  c.max_restarts = (int)a[16];
  c.max_timeouts = (int)a[17];
  c.max_terms = (int)a[18];
  c.max_client_requests = (int)a[19];
  c.max_mc = (int)a[20];
  c.max_tried = (int)a[21];
  c.max_inflight = (int)a[22];
  c.max_trace = (int)a[23];
  c.con_mask = (uint32_t)a[24];
  c.inv_mask = (uint32_t)a[25];
  c.symmetry = (int)a[26];
  c.threads = (int)a[27];
  int64_t max_depth = a[28];
  int64_t max_states = a[29];
  // a[30] stop_on_violation: BFS stops at the level a violation appears
  c.value_bits = (int)a[31];
  c.entry_bits = 0;
  c.n_perms = (int)a[32];
  if (c.S > SMAX || c.K > KMAX || c.Lcap > LCAPMAX ||
      c.nvals > VMAX || c.n_perms > PMAX || c.L > LMAX)
    return -1;
  for (int p = 0; p < c.n_perms; ++p)
    for (int i = 0; i < c.S; ++i)
      c.perms[p][i] = (int8_t)a[33 + p * c.S + i];

  // Init (raft.tla:367-393)
  State init{};
  for (int i = 0; i < c.S; ++i) {
    init.ct[i] = 1;
    init.st[i] = FOLLOWER;
    init.vf[i] = NIL;
    for (int j = 0; j < c.S; ++j) init.ni[i][j] = 1;
  }

  Stats st;
  VisitedSet visited;
  visited.offer(fingerprint(c, init), 0);
  visited.settle(fingerprint(c, init));
  st.distinct = 1;
  st.generated = 1;
  st.violated |= check_invariants(c, init);
  std::vector<State> frontier;
  if (constraints_ok(c, init)) frontier.push_back(init);

  int nthreads = std::max(1, c.threads);
  while (!frontier.empty() && st.depth < max_depth &&
         st.distinct < max_states) {
    st.depth++;
    std::vector<WorkerSink> sinks(nthreads);
    std::vector<std::thread> threads;
    std::atomic<size_t> cursor{0};
    const size_t grain = 64;
    for (int t = 0; t < nthreads; ++t) {
      sinks[t].c = &c;
      sinks[t].visited = &visited;
      threads.emplace_back([&, t]() {
        Ctx x{&c, &sinks[t], worker_emit};
        for (;;) {
          size_t base = cursor.fetch_add(grain);
          if (base >= frontier.size()) break;
          size_t end = std::min(frontier.size(), base + grain);
          for (size_t q = base; q < end; ++q) {
            sinks[t].rank = (uint64_t)q << 24;   // < 2^24 successors
            successors(x, frontier[q]);
          }
        }
      });
    }
    for (auto &t : threads) t.join();
    // the winners, in rank order: the sequential BFS's first-seen order
    struct Win { uint64_t rank; const Cand *cd; State *s; };
    std::vector<Win> win;
    for (auto &w : sinks) {
      st.generated += w.generated;
      for (auto &cd : w.cand)
        if (visited.won(cd.fp, cd.rank))
          win.push_back({cd.rank, &cd,
                         cd.keep >= 0 ? &w.keep[cd.keep] : nullptr});
    }
    std::sort(win.begin(), win.end(),
              [](const Win &a, const Win &b) { return a.rank < b.rank; });
    std::vector<State> next;
    for (auto &wn : win) {
      visited.settle(wn.cd->fp);
      st.distinct++;
      st.overflow += wn.cd->overflow;
      st.violated |= wn.cd->violated;
      if (wn.s) next.push_back(*wn.s);
    }
    if (st.depth <= MAX_LEVELS) out[8 + st.depth - 1] = next.size();
    frontier.swap(next);
    if (a[30] && st.violated) break;
  }

  out[0] = st.distinct;
  out[1] = st.generated;
  out[2] = st.depth;
  out[3] = (int64_t)st.violated;
  out[4] = st.overflow;
  return 0;
}

}  // extern "C"
