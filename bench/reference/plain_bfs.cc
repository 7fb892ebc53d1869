// The benchmark's plain reference: a single-threaded breadth-first
// search of the Raft spec's state graph, written from the spec's own
// definitions (SURVEY.md §2, which cites tlc_membership/raft.tla and
// apalache_no_membership/raft.tla line by line) and from nothing of the
// program under test.
//
// What makes it plain:
//   * a state is a record of the spec's variables, compared whole: the
//     visited set holds the complete canonical VIEW of every state seen
//     (std::unordered_set of byte strings), so no two distinct states
//     can ever merge;
//   * SYMMETRY is the lexicographically least byte string over every
//     relabeling of Server that maps InitServer onto itself; a relabeling
//     renames servers everywhere they appear: votedFor, vote sets, the
//     server sets of config entries, message ends and CheckOldConfig's
//     mserver;
//   * VIEW vars (raft.cfg) leaves `history` out of a state's identity;
//     the first state of a VIEW class met in BFS order is the one kept
//     and expanded (frontier order, then the order of Next's disjuncts);
//   * CONSTRAINT means "not expanded": such a state is still counted,
//     and every invariant is evaluated on it;
//   * the message bag is a function message -> count kept sorted by the
//     message's bytes; Receive, Duplicate and Drop walk it in that order.
//
// The families NextAsync, NextAsyncCrash, Next and NextDynamic (Next with
// AddNewServer and DeleteServer, SURVEY §2.6): typed log entries,
// GetConfig from the log, catch-up and CheckOldConfig messages.  The
// safety invariants of raft.cfg, the two `_false` forms, the authored
// OneAtATimeMembershipChangeOK, and the constraints of §2.8.  Anything
// else (scenario properties, the prefix pins, unknown names) is refused
// (exit 2) rather than guessed, and so is a state that outgrows the
// fixed capacities below.
//
// The control: fp_bits=N (1..63) replaces the exact set by a set of the
// canonical strings' 64-bit hashes cut to N bits, a lossy dedup key that
// merges distinct states.  fp_bits=0 is the exact reference.
//
// Usage: plain_bfs key=value ...   (bench/harness/reference.py writes
// the arguments from a configuration's JSON).  Prints one JSON line.

#include <algorithm>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <string>
#include <unordered_set>
#include <vector>

namespace {

constexpr int SMAX = 5;    // servers
constexpr int LMAX = 8;    // entries in one log or one message
constexpr int KMAX = 40;   // distinct messages in the bag: at four servers a
                           // state holds at most MaxInFlightMessages + 1 = 33
constexpr uint8_t NIL = 255;
constexpr uint8_t ABSENT = 255;   // a field the message record lacks

enum Role : uint8_t { FOLLOWER = 0, CANDIDATE = 1, LEADER = 2 };
enum MType : uint8_t {
  RVREQ = 1, RVRESP = 2, AEREQ = 3, AERESP = 4,
  CATREQ = 5, CATRESP = 6, CHECKOLD = 7
};
enum EType : uint8_t { VALUE_ENTRY = 0, CONFIG_ENTRY = 1 };

[[noreturn]] void die(const char *what) {
  std::fprintf(stderr, "plain_bfs: %s\n", what);
  std::exit(2);
}

// A log entry (SURVEY §2.1): a ValueEntry [term, value] or a ConfigEntry
// [term, server set]; `value` holds the value or the set's bits.
struct Entry {
  uint8_t term, value, type;
  bool operator==(const Entry &o) const {
    return term == o.term && value == o.value && type == o.type;
  }
  bool operator!=(const Entry &o) const { return !(*this == o); }
};

// A message record, laid out as bytes so that equality and order are
// those of the whole record.  Fields by type:
//   RVREQ    a=mlastLogTerm b=mlastLogIndex
//   RVRESP   a=mvoteGranted, ents=mlog
//   AEREQ    a=mprevLogIndex b=mprevLogTerm c=mcommitIndex, ents=mentries
//   AERESP   a=msuccess b=mmatchIndex
//   CATREQ   a=mlogLen b=mcommitIndex (ABSENT in a follow-up round)
//            c=mrounds, ents=mentries
//   CATRESP  a=msuccess b=mmatchIndex c=mroundsLeft
//   CHECKOLD a=madd b=mserver (src = dst: sent to itself)
struct Msg {
  uint8_t type, term, src, dst, a, b, c, n;
  Entry ents[LMAX];
};
static_assert(sizeof(Msg) == 8 + 3 * LMAX, "Msg has no padding");

inline int msg_cmp(const Msg &x, const Msg &y) {
  return std::memcmp(&x, &y, sizeof(Msg));
}

struct State {
  // VIEW: the spec's `vars`
  uint8_t currentTerm[SMAX], state[SMAX], votedFor[SMAX];
  uint8_t commitIndex[SMAX], len[SMAX];
  Entry log[SMAX][LMAX];
  uint8_t votesResponded[SMAX], votesGranted[SMAX];   // bit sets
  uint8_t nextIndex[SMAX][SMAX], matchIndex[SMAX][SMAX];
  uint8_t nmsg;
  Msg msg[KMAX];          // sorted by bytes, each with count >= 1
  uint8_t cnt[KMAX];
  // history: outside the VIEW, read by the constraints and by
  // LeaderVotesQuorum and CandidateTermNotInLog
  uint8_t restarted[SMAX], timeouts[SMAX];
  uint16_t hadNumLeaders, hadNumClientRequests;
  uint16_t hadNumTriedMembershipChanges, hadNumMembershipChanges;
};

struct Cfg {
  int S = 0, nvals = 0, vals[8] = {0};
  uint8_t init_mask = 0;
  int family = -1;    // 0 NextAsync, 1 NextAsyncCrash, 2 Next, 3 NextDynamic
  bool symmetry = false;
  int max_log = 0, max_restarts = 0, max_timeouts = 0, max_terms = 0;
  int max_client_requests = 0, max_inflight = 0;
  int max_membership_changes = 0, max_tried_membership_changes = 0;
  int num_rounds = 1;
  std::vector<std::string> constraints, invariants;
  int max_depth = 0, fp_bits = 0;
  std::vector<std::vector<uint8_t>> perms;   // sigma: old -> new
};

// ---------------------------------------------------------------- bag

// WithMessage: count + 1, keeping the bag sorted.
void with_message(State &t, const Msg &m) {
  int k = 0;
  while (k < t.nmsg && msg_cmp(t.msg[k], m) < 0) ++k;
  if (k < t.nmsg && msg_cmp(t.msg[k], m) == 0) { t.cnt[k]++; return; }
  if (t.nmsg == KMAX) die("bag holds more distinct messages than KMAX");
  for (int q = t.nmsg; q > k; --q) {
    t.msg[q] = t.msg[q - 1];
    t.cnt[q] = t.cnt[q - 1];
  }
  t.msg[k] = m;
  t.cnt[k] = 1;
  t.nmsg++;
}

// WithoutMessage: count - 1; a count of 0 leaves the bag's domain.
void without_message(State &t, const Msg &m) {
  for (int k = 0; k < t.nmsg; ++k) {
    if (msg_cmp(t.msg[k], m) != 0) continue;
    if (--t.cnt[k] == 0) {
      for (int q = k; q + 1 < t.nmsg; ++q) {
        t.msg[q] = t.msg[q + 1];
        t.cnt[q] = t.cnt[q + 1];
      }
      t.nmsg--;
      std::memset(&t.msg[t.nmsg], 0, sizeof(Msg));
      t.cnt[t.nmsg] = 0;
    }
    return;
  }
  die("discarding a message that is not in the bag");
}

Msg make_msg(uint8_t type, int term, int src, int dst) {
  Msg m;
  std::memset(&m, 0, sizeof m);
  m.type = type;
  m.term = (uint8_t)term;
  m.src = (uint8_t)src;
  m.dst = (uint8_t)dst;
  return m;
}

// ------------------------------------------------------------ helpers

int last_term(const State &s, int i) {
  return s.len[i] ? s.log[i][s.len[i] - 1].term : 0;
}

bool is_quorum(uint8_t set, uint8_t config) {       // set \in Quorum(config)
  if (set & ~config) return false;
  return 2 * __builtin_popcount(set) > __builtin_popcount(config);
}

// GetMaxConfigIndex(i) (H7, :346-351): the index of the last ConfigEntry
// in log[i], 0 if there is none.
int max_config_index(const State &s, int i) {
  for (int p = s.len[i]; p > 0; --p)
    if (s.log[i][p - 1].type == CONFIG_ENTRY) return p;
  return 0;
}

// GetConfig(i) (H8, :354-360): the server set of that entry, committed
// or not; InitServer when the log holds none.
uint8_t get_config(const Cfg &c, const State &s, int i) {
  int p = max_config_index(s, i);
  return p ? s.log[i][p - 1].value : c.init_mask;
}

void append(State &t, int i, const Entry &e) {
  if (t.len[i] >= LMAX) die("log longer than LMAX");
  t.log[i][t.len[i]++] = e;
}

// SubSeq(log[i], from, commitIndex[i]) as a catch-up message's mentries.
void catchup_entries(const State &s, int i, int from, Msg &m) {
  if (s.commitIndex[i] > s.len[i]) die("a commitIndex past the log");
  for (int k = from; k <= s.commitIndex[i]; ++k)
    m.ents[m.n++] = s.log[i][k - 1];
}

// ------------------------------------------------------------ actions

struct Out {
  std::vector<State> *succ;
  void emit(const State &t) { succ->push_back(t); }
};

void restart(const Cfg &c, const State &s, int i, Out &o) {
  State t = s;
  t.state[i] = FOLLOWER;
  t.votesResponded[i] = t.votesGranted[i] = 0;
  for (int j = 0; j < c.S; ++j) {
    t.nextIndex[i][j] = 1;
    t.matchIndex[i][j] = 0;
  }
  t.commitIndex[i] = 0;
  t.restarted[i]++;
  o.emit(t);
}

void timeout(const Cfg &c, const State &s, int i, Out &o) {
  if (s.state[i] != FOLLOWER && s.state[i] != CANDIDATE) return;
  if (!(get_config(c, s, i) >> i & 1)) return;
  State t = s;
  t.state[i] = CANDIDATE;
  t.currentTerm[i]++;
  t.votedFor[i] = NIL;
  t.votesResponded[i] = t.votesGranted[i] = 0;
  t.timeouts[i]++;
  o.emit(t);
}

void request_vote(const Cfg &c, const State &s, int i, int j, Out &o) {
  if (s.state[i] != CANDIDATE) return;
  uint8_t allowed = get_config(c, s, i) & ~s.votesResponded[i];
  if (!(allowed >> j & 1)) return;
  State t = s;
  Msg m = make_msg(RVREQ, s.currentTerm[i], i, j);
  m.a = (uint8_t)last_term(s, i);
  m.b = s.len[i];
  with_message(t, m);
  o.emit(t);
}

void append_entries(const Cfg &c, const State &s, int i, int j, Out &o) {
  if (i == j || s.state[i] != LEADER) return;
  if (!(get_config(c, s, i) >> j & 1)) return;
  int next = s.nextIndex[i][j];
  int prev_index = next - 1;
  if (prev_index > s.len[i]) die("AppendEntries reads past the log");
  int prev_term = prev_index > 0 ? s.log[i][prev_index - 1].term : 0;
  int last_entry = std::min<int>(s.len[i], next);
  State t = s;
  Msg m = make_msg(AEREQ, s.currentTerm[i], i, j);
  m.a = (uint8_t)prev_index;
  m.b = (uint8_t)prev_term;
  m.c = (uint8_t)std::min<int>(s.commitIndex[i], last_entry);
  for (int k = next; k <= last_entry; ++k) m.ents[m.n++] = s.log[i][k - 1];
  with_message(t, m);
  o.emit(t);
}

void become_leader(const Cfg &c, const State &s, int i, Out &o) {
  if (s.state[i] != CANDIDATE) return;
  if (!is_quorum(s.votesGranted[i], get_config(c, s, i))) return;
  State t = s;
  t.state[i] = LEADER;
  for (int j = 0; j < c.S; ++j) {
    t.nextIndex[i][j] = (uint8_t)(s.len[i] + 1);
    t.matchIndex[i][j] = 0;
  }
  t.hadNumLeaders++;
  o.emit(t);
}

void client_request(const Cfg &, const State &s, int i, int v, Out &o) {
  if (s.state[i] != LEADER) return;
  State t = s;
  append(t, i, Entry{s.currentTerm[i], (uint8_t)v, VALUE_ENTRY});
  t.hadNumClientRequests++;
  o.emit(t);
}

void advance_commit_index(const Cfg &c, const State &s, int i, Out &o) {
  if (s.state[i] != LEADER) return;
  uint8_t config = get_config(c, s, i);
  int max_agree = 0;                       // Max(agreeIndexes), 0 if empty
  for (int n = 1; n <= s.len[i]; ++n) {
    uint8_t agree = (uint8_t)(1u << i);
    for (int k = 0; k < c.S; ++k)
      if ((config >> k & 1) && s.matchIndex[i][k] >= n) agree |= 1u << k;
    if (is_quorum(agree, config)) max_agree = n;
  }
  State t = s;
  if (max_agree > 0 && s.log[i][max_agree - 1].term == s.currentTerm[i])
    t.commitIndex[i] = (uint8_t)max_agree;
  o.emit(t);
}

// A8 AddNewServer(i, j) (:542-555): the leader resets j's term and vote
// (it writes another server's variables, a modelling shortcut) and sends
// the first CatchupRequest.  SendDirect counts the try (:249-254).
void add_new_server(const Cfg &c, const State &s, int i, int j, Out &o) {
  if (s.state[i] != LEADER) return;
  if (get_config(c, s, i) >> j & 1) return;
  State t = s;
  t.currentTerm[j] = 1;
  t.votedFor[j] = NIL;
  Msg m = make_msg(CATREQ, s.currentTerm[i], i, j);
  m.a = s.matchIndex[i][j];
  m.b = s.commitIndex[i];
  m.c = (uint8_t)c.num_rounds;
  catchup_entries(s, i, s.nextIndex[i][j], m);
  with_message(t, m);
  t.hadNumTriedMembershipChanges++;
  o.emit(t);
}

// A9 DeleteServer(i, j) (:558-569): a CheckOldConfig(madd = FALSE) the
// leader sends to itself; SendDirect counts the try (:249-254).
void delete_server(const Cfg &c, const State &s, int i, int j, Out &o) {
  if (s.state[i] != LEADER || i == j) return;
  if (s.state[j] != FOLLOWER && s.state[j] != CANDIDATE) return;
  if (!(get_config(c, s, i) >> j & 1)) return;
  State t = s;
  Msg m = make_msg(CHECKOLD, s.currentTerm[i], i, i);
  m.a = 0;
  m.b = (uint8_t)j;
  with_message(t, m);
  t.hadNumTriedMembershipChanges++;
  o.emit(t);
}

// Reply(response, request): discard the request, send the response.
void reply(State &t, const Msg &resp, const Msg &req) {
  without_message(t, req);
  with_message(t, resp);
}

void receive(const Cfg &c, const State &s, int k, Out &o) {
  const Msg m = s.msg[k];
  int i = m.dst, j = m.src;
  int ct = s.currentTerm[i];
  // UpdateTerm: the message stays in the bag.
  if (m.term > ct) {
    State t = s;
    t.currentTerm[i] = m.term;
    t.state[i] = FOLLOWER;
    t.votedFor[i] = NIL;
    o.emit(t);
  }
  switch (m.type) {
    case RVREQ: {
      if (m.term > ct) break;
      int lt = last_term(s, i);
      bool log_ok = m.a > lt || (m.a == lt && m.b >= s.len[i]);
      bool grant = m.term == ct && log_ok &&
                   (s.votedFor[i] == NIL || s.votedFor[i] == j);
      State t = s;
      if (grant) t.votedFor[i] = (uint8_t)j;
      Msg r = make_msg(RVRESP, ct, i, j);
      r.a = grant;
      for (int p = 0; p < s.len[i]; ++p) r.ents[r.n++] = s.log[i][p];
      reply(t, r, m);
      o.emit(t);
      break;
    }
    case RVRESP: {
      if (m.term > ct) break;
      State t = s;                 // DropStaleResponse, or the handler
      if (m.term == ct) {
        t.votesResponded[i] |= 1u << j;
        if (m.a) t.votesGranted[i] |= 1u << j;
      }
      without_message(t, m);
      o.emit(t);
      break;
    }
    case AEREQ: {
      if (m.term > ct) break;
      int prev = m.a;
      bool log_ok = prev == 0 ||
                    (prev <= s.len[i] && m.b == s.log[i][prev - 1].term);
      if (m.term < ct || (m.term == ct && s.state[i] == FOLLOWER &&
                          !log_ok)) {
        State t = s;               // reject
        Msg r = make_msg(AERESP, ct, i, j);
        r.a = 0;
        r.b = 0;
        reply(t, r, m);
        o.emit(t);
      } else if (m.term == ct && s.state[i] == CANDIDATE) {
        State t = s;               // return to follower; not consumed
        t.state[i] = FOLLOWER;
        o.emit(t);
      } else if (m.term == ct && s.state[i] == FOLLOWER && log_ok) {
        int index = prev + 1;
        if (m.n == 0 ||
            (s.len[i] >= index && s.log[i][index - 1].term == m.ents[0].term)) {
          State t = s;             // already done
          t.commitIndex[i] = m.c;
          Msg r = make_msg(AERESP, ct, i, j);
          r.a = 1;
          r.b = (uint8_t)(prev + m.n);
          reply(t, r, m);
          o.emit(t);
        } else if (s.len[i] >= index) {
          State t = s;             // conflict: drop the last entry
          t.len[i]--;
          t.log[i][t.len[i]] = Entry{0, 0, 0};
          o.emit(t);
        } else if (s.len[i] == prev) {
          State t = s;             // no conflict: append the entry
          append(t, i, m.ents[0]);
          o.emit(t);
        }
      }
      break;
    }
    case AERESP: {
      if (m.term > ct) break;
      State t = s;                 // DropStaleResponse, or the handler
      if (m.term == ct) {
        if (m.a) {
          t.nextIndex[i][j] = (uint8_t)(m.b + 1);
          t.matchIndex[i][j] = m.b;
        } else {
          t.nextIndex[i][j] =
              (uint8_t)std::max(s.nextIndex[i][j] - 1, 1);
        }
      }
      without_message(t, m);
      o.emit(t);
      break;
    }
    case CATREQ: {                 // R7 HandleCatchupRequest (:718-745)
      State t = s;
      if (m.term < ct) {           // stale: refuse
        Msg r = make_msg(CATRESP, ct, i, j);
        r.a = 0;
        r.b = 0;
        r.c = 0;
        reply(t, r, m);
        o.emit(t);
        break;
      }
      // adopt the term; log[i] := SubSeq(log[i], 1, Min({mlogLen,
      // Len(log[i])})) \o mentries, the spec's splice (not the older
      // one its comment keeps); state and votedFor stay
      t.currentTerm[i] = m.term;
      int keep = std::min<int>(m.a, s.len[i]);
      if (keep + m.n > LMAX) die("log longer than LMAX");
      for (int p = 0; p < m.n; ++p) t.log[i][keep + p] = m.ents[p];
      for (int p = keep + m.n; p < LMAX; ++p) t.log[i][p] = Entry{0, 0, 0};
      t.len[i] = (uint8_t)(keep + m.n);
      Msg r = make_msg(CATRESP, m.term, i, j);
      r.a = 1;
      r.b = s.len[i];              // Len(log[i]) before the splice (:740)
      r.c = (uint8_t)(m.c - 1);
      reply(t, r, m);
      o.emit(t);
      break;
    }
    case CATRESP: {                // R8 HandleCatchupResponse (:748-792)
      bool progress = m.b == s.commitIndex[i] || m.b != s.matchIndex[i][j];
      bool accept = m.a && progress && s.state[i] == LEADER &&
                    m.term == ct && !(get_config(c, s, i) >> j & 1);
      State t = s;
      if (!accept) {               // the five discard disjuncts
        without_message(t, m);
        o.emit(t);
        break;
      }
      int next = s.nextIndex[i][j];
      t.nextIndex[i][j] = (uint8_t)(m.b + 1);
      t.matchIndex[i][j] = m.b;
      Msg r;
      if (m.c != 0) {              // another round (:761-771): from the
        r = make_msg(CATREQ, ct, i, j);   // old nextIndex, no mcommitIndex
        r.a = (uint8_t)(next - 1);
        r.b = ABSENT;
        r.c = m.c;
        catchup_entries(s, i, next, r);
      } else {                     // caught up (:772-782)
        r = make_msg(CHECKOLD, ct, i, i);
        r.a = 1;
        r.b = (uint8_t)j;
      }
      reply(t, r, m);
      o.emit(t);
      break;
    }
    case CHECKOLD: {               // R9 HandleCheckOldConfig (:795-822)
      // discard (:796): a current leader may take this branch too
      if (s.state[i] != LEADER || m.term == ct) {
        State t = s;
        without_message(t, m);
        o.emit(t);
      }
      if (s.state[i] != LEADER || m.term != ct) break;
      State t = s;
      if (max_config_index(s, i) <= s.commitIndex[i]) {
        // the previous config is committed: one change at a time (:800)
        uint8_t config = get_config(c, s, i);
        uint8_t next = m.a ? (uint8_t)(config | 1u << m.b)
                           : (uint8_t)(config & ~(1u << m.b));
        if (next != config) {      // DiscardDirectWithMembershipChange
          append(t, i, Entry{s.currentTerm[i], next, CONFIG_ENTRY});
          t.hadNumMembershipChanges++;
        }
        without_message(t, m);
      }
      // else the same message again to itself (:813-821): Reply(m, m)
      // leaves the bag as it was
      o.emit(t);
      break;
    }
    default:
      die("a message type this reference does not know");
  }
}

void duplicate_message(const State &s, int k, Out &o) {
  if (s.cnt[k] != 1) return;
  State t = s;
  t.cnt[k]++;
  o.emit(t);
}

void drop_message(const State &s, int k, Out &o) {
  if (s.cnt[k] != 1) return;
  State t = s;
  without_message(t, s.msg[k]);
  o.emit(t);
}

// Next, disjunct by disjunct in the order the spec writes them.
void successors(const Cfg &c, const State &s, Out &o) {
  for (int i = 0; i < c.S; ++i)
    for (int j = 0; j < c.S; ++j) request_vote(c, s, i, j, o);
  for (int i = 0; i < c.S; ++i) become_leader(c, s, i, o);
  for (int i = 0; i < c.S; ++i)
    for (int v = 0; v < c.nvals; ++v) client_request(c, s, i, c.vals[v], o);
  for (int i = 0; i < c.S; ++i) advance_commit_index(c, s, i, o);
  for (int i = 0; i < c.S; ++i)
    for (int j = 0; j < c.S; ++j) append_entries(c, s, i, j, o);
  for (int k = 0; k < s.nmsg; ++k) receive(c, s, k, o);
  for (int i = 0; i < c.S; ++i) timeout(c, s, i, o);
  if (c.family >= 1)
    for (int i = 0; i < c.S; ++i) restart(c, s, i, o);
  if (c.family >= 2) {
    for (int k = 0; k < s.nmsg; ++k) duplicate_message(s, k, o);
    for (int k = 0; k < s.nmsg; ++k) drop_message(s, k, o);
  }
  if (c.family >= 3) {             // NextDynamic (:940-943)
    for (int i = 0; i < c.S; ++i)
      for (int j = 0; j < c.S; ++j) add_new_server(c, s, i, j, o);
    for (int i = 0; i < c.S; ++i)
      for (int j = 0; j < c.S; ++j) delete_server(c, s, i, j, o);
  }
}

// -------------------------------------------------------- constraints

bool has(const std::vector<std::string> &v, const char *name) {
  return std::find(v.begin(), v.end(), name) != v.end();
}

bool in_model(const Cfg &c, const State &s) {
  const auto &k = c.constraints;
  int S = c.S, inflight = 0, candidates = 0, sum_restarts = 0,
      sum_timeouts = 0;
  for (int q = 0; q < s.nmsg; ++q) inflight += s.cnt[q];
  for (int i = 0; i < S; ++i) {
    candidates += s.state[i] == CANDIDATE;
    sum_restarts += s.restarted[i];
    sum_timeouts += s.timeouts[i];
  }
  if (has(k, "BoundedInFlightMessages") && inflight > c.max_inflight)
    return false;
  if (has(k, "BoundedRequestVote"))
    for (int q = 0; q < s.nmsg; ++q)
      if (s.msg[q].type == RVREQ && s.cnt[q] > 1) return false;
  for (int i = 0; i < S; ++i) {
    if (has(k, "BoundedLogSize") && s.len[i] > c.max_log) return false;
    if (has(k, "BoundedRestarts") && s.restarted[i] > c.max_restarts)
      return false;
    if (has(k, "BoundedTimeouts") && s.timeouts[i] > c.max_timeouts)
      return false;
    if (has(k, "BoundedTerms") && s.currentTerm[i] > c.max_terms)
      return false;
  }
  if (has(k, "BoundedClientRequests") &&
      s.hadNumClientRequests > c.max_client_requests)
    return false;
  if (has(k, "BoundedTriedMembershipChanges") &&
      s.hadNumTriedMembershipChanges > c.max_tried_membership_changes)
    return false;
  if (has(k, "BoundedMembershipChanges") &&
      s.hadNumMembershipChanges > c.max_membership_changes)
    return false;
  if (has(k, "ElectionsUncontested") && candidates > 1) return false;
  if (has(k, "CleanStartUntilFirstRequest") && s.hadNumLeaders < 1 &&
      s.hadNumClientRequests < 1 &&
      (sum_restarts > 0 || sum_timeouts > 1 || candidates > 1))
    return false;
  if (has(k, "CleanStartUntilTwoLeaders") && s.hadNumLeaders < 2 &&
      (sum_restarts > 1 || sum_timeouts > 2))
    return false;
  if (has(k, "CleanFirstLeaderElection") && s.hadNumLeaders < 1 &&
      (sum_restarts > 0 || candidates > 1))
    return false;
  return true;
}

// --------------------------------------------------------- invariants

// Committed(i) == SubSeq(log[i], 1, commitIndex[i]) (:969), read as the
// first min(commitIndex, Len) entries: its length.
int committed_len(const State &s, int i) {
  return std::min(s.commitIndex[i], s.len[i]);
}

// IsPrefix(Committed(i), log[j]).
bool committed_is_prefix(const State &s, int i, int j) {
  int n = committed_len(s, i);
  if (n > s.len[j]) return false;
  for (int p = 0; p < n; ++p)
    if (s.log[i][p] != s.log[j][p]) return false;
  return true;
}

int max_index_of_term(const State &s, int i, int term) {   // MaxOrZero
  int best = 0;
  for (int p = 0; p < s.len[i]; ++p)
    if (s.log[i][p].term == term) best = p + 1;
  return best;
}

bool holds(const Cfg &c, const State &s, const std::string &inv) {
  int S = c.S;
  if (inv == "ElectionSafety") {
    for (int i = 0; i < S; ++i) {
      if (s.state[i] != LEADER) continue;
      int mine = max_index_of_term(s, i, s.currentTerm[i]);
      for (int j = 0; j < S; ++j)
        if (max_index_of_term(s, j, s.currentTerm[i]) > mine) return false;
    }
    return true;
  }
  if (inv == "LogMatching") {
    for (int i = 0; i < S; ++i)
      for (int j = 0; j < S; ++j)
        for (int n = 1; n <= std::min(s.len[i], s.len[j]); ++n) {
          if (s.log[i][n - 1].term != s.log[j][n - 1].term) continue;
          for (int p = 0; p < n; ++p)
            if (s.log[i][p] != s.log[j][p]) return false;
        }
    return true;
  }
  if (inv == "VotesGrantedInv_false") {
    for (int i = 0; i < S; ++i)
      for (int j = 0; j < S; ++j)
        if ((s.votesGranted[i] >> j & 1) &&
            s.currentTerm[i] == s.currentTerm[j] &&
            !committed_is_prefix(s, j, i))
          return false;
    return true;
  }
  if (inv == "LeaderCompleteness_false") {
    for (int i = 0; i < S; ++i)
      if (s.state[i] == LEADER)
        for (int j = 0; j < S; ++j)
          if (!committed_is_prefix(s, j, i)) return false;
    return true;
  }
  // LeaderVotesQuorum (:988-993): a leader's voters, those at a higher
  // term or that voted for it in its term, are a quorum of its config;
  // stated while no membership change has happened.
  if (inv == "LeaderVotesQuorum") {
    if (s.hadNumMembershipChanges != 0) return true;
    for (int i = 0; i < S; ++i) {
      if (s.state[i] != LEADER) continue;
      uint8_t voters = 0;
      for (int j = 0; j < S; ++j)
        if (s.currentTerm[j] > s.currentTerm[i] ||
            (s.currentTerm[j] == s.currentTerm[i] && s.votedFor[j] == i))
          voters |= 1u << j;
      if (!is_quorum(voters, get_config(c, s, i))) return false;
    }
    return true;
  }
  // CandidateTermNotInLog (:997-1004): a candidate that could still win
  // (a quorum of its config at its term voted for it or for no one) has
  // its term in no log; the same guard.
  if (inv == "CandidateTermNotInLog") {
    if (s.hadNumMembershipChanges != 0) return true;
    for (int i = 0; i < S; ++i) {
      if (s.state[i] != CANDIDATE) continue;
      uint8_t voters = 0;
      for (int j = 0; j < S; ++j)
        if (s.currentTerm[j] == s.currentTerm[i] &&
            (s.votedFor[j] == i || s.votedFor[j] == NIL))
          voters |= 1u << j;
      if (!is_quorum(voters, get_config(c, s, i))) continue;
      for (int j = 0; j < S; ++j)
        for (int p = 0; p < s.len[j]; ++p)
          if (s.log[j][p].term == s.currentTerm[i]) return false;
    }
    return true;
  }
  // VotesGrantedInv (:1048-1052): votedFor[i] = j => IsPrefix(
  // Committed(i), log[j]).
  if (inv == "VotesGrantedInv") {
    for (int i = 0; i < S; ++i)
      if (s.votedFor[i] != NIL && !committed_is_prefix(s, i, s.votedFor[i]))
        return false;
    return true;
  }
  // QuorumLogInv (:1056-1060): every quorum of GetConfig(i) holds a
  // server whose log begins with Committed(i); so the config's servers
  // whose logs do not are no quorum.
  if (inv == "QuorumLogInv") {
    for (int i = 0; i < S; ++i) {
      uint8_t config = get_config(c, s, i), lacking = 0;
      for (int j = 0; j < S; ++j)
        if ((config >> j & 1) && !committed_is_prefix(s, i, j))
          lacking |= 1u << j;
      if (is_quorum(lacking, config)) return false;
    }
    return true;
  }
  // MoreUpToDateCorrect (:1066-1071): a log at least as up to date as
  // log[j] begins with Committed(j).
  if (inv == "MoreUpToDateCorrect") {
    for (int i = 0; i < S; ++i)
      for (int j = 0; j < S; ++j) {
        int ti = last_term(s, i), tj = last_term(s, j);
        bool more = ti > tj || (ti == tj && s.len[i] >= s.len[j]);
        if (more && !committed_is_prefix(s, j, i)) return false;
      }
    return true;
  }
  // LeaderCompleteness (:1089-1099): a committed entry is at the same
  // index in the log of every leader whose term is above the entry's.
  if (inv == "LeaderCompleteness") {
    for (int i = 0; i < S; ++i)
      for (int p = 0; p < committed_len(s, i); ++p) {
        const Entry &e = s.log[i][p];
        for (int l = 0; l < S; ++l)
          if (s.state[l] == LEADER && s.currentTerm[l] > e.term &&
              (s.len[l] <= p || s.log[l][p] != e))
            return false;
      }
    return true;
  }
  // OneAtATimeMembershipChangeOK, the authored invariant (SURVEY's
  // preamble): at most one ConfigEntry in each log beyond commitIndex.
  if (inv == "OneAtATimeMembershipChangeOK") {
    for (int i = 0; i < S; ++i) {
      int pending = 0;
      for (int p = s.commitIndex[i]; p < s.len[i]; ++p)
        pending += s.log[i][p].type == CONFIG_ENTRY;
      if (pending > 1) return false;
    }
    return true;
  }
  die("an invariant this reference does not know");
}

// ---------------------------------------------------- canonical VIEW

// The VIEW of `s` with every server renamed by sigma, as bytes.  An
// entry is two bytes: its term with the type in the top bit, then its
// value or its renamed server set.
void view_bytes(const Cfg &c, const State &s, const uint8_t *sigma,
                std::string &out) {
  int S = c.S;
  uint8_t inv[SMAX];
  for (int i = 0; i < S; ++i) inv[sigma[i]] = (uint8_t)i;
  auto ren = [&](uint8_t set) {
    uint8_t r = 0;
    for (int i = 0; i < S; ++i)
      if (set >> i & 1) r |= 1u << sigma[i];
    return r;
  };
  auto ren_entry = [&](Entry e) {
    if (e.type == CONFIG_ENTRY) e.value = ren(e.value);
    return e;
  };
  auto put_entry = [&](const Entry &e) {
    out.push_back((char)(e.term | e.type << 7));
    out.push_back((char)e.value);
  };
  out.clear();
  for (int k = 0; k < S; ++k) {       // the server now named k
    int i = inv[k];
    out.push_back((char)s.currentTerm[i]);
    out.push_back((char)s.state[i]);
    out.push_back((char)(s.votedFor[i] == NIL ? NIL : sigma[s.votedFor[i]]));
    out.push_back((char)s.commitIndex[i]);
    out.push_back((char)s.len[i]);
    for (int p = 0; p < s.len[i]; ++p) put_entry(ren_entry(s.log[i][p]));
    out.push_back((char)ren(s.votesResponded[i]));
    out.push_back((char)ren(s.votesGranted[i]));
    for (int l = 0; l < S; ++l) out.push_back((char)s.nextIndex[i][inv[l]]);
    for (int l = 0; l < S; ++l) out.push_back((char)s.matchIndex[i][inv[l]]);
  }
  // the bag: renamed messages, sorted, each with its count
  Msg ms[KMAX];
  int order[KMAX];
  for (int q = 0; q < s.nmsg; ++q) {
    Msg &m = ms[q];
    m = s.msg[q];
    m.src = sigma[m.src];
    m.dst = sigma[m.dst];
    if (m.type == CHECKOLD) m.b = sigma[m.b];
    for (int p = 0; p < m.n; ++p) m.ents[p] = ren_entry(m.ents[p]);
    order[q] = q;
  }
  std::sort(order, order + s.nmsg, [&](int x, int y) {
    return msg_cmp(ms[x], ms[y]) < 0;
  });
  out.push_back((char)s.nmsg);
  for (int q = 0; q < s.nmsg; ++q) {
    const Msg &m = ms[order[q]];
    out.append(reinterpret_cast<const char *>(&m), 8);
    for (int p = 0; p < m.n; ++p) put_entry(m.ents[p]);
    out.push_back((char)s.cnt[order[q]]);
  }
}

void canonical(const Cfg &c, const State &s, std::string &best,
               std::string &scratch) {
  view_bytes(c, s, c.perms[0].data(), best);
  for (size_t p = 1; p < c.perms.size(); ++p) {
    view_bytes(c, s, c.perms[p].data(), scratch);
    if (scratch < best) best.swap(scratch);
  }
}

uint64_t hash_bytes(const std::string &b) {       // FNV-1a, then a mix
  uint64_t h = 1469598103934665603ull;
  for (unsigned char ch : b) h = (h ^ ch) * 1099511628211ull;
  h ^= h >> 33;
  h *= 0xff51afd7ed558ccdull;
  return h ^ (h >> 33);
}

// ------------------------------------------------------------- config

std::vector<int> ints(const std::string &v) {
  std::vector<int> r;
  size_t p = 0;
  while (p < v.size()) {
    size_t q = v.find(',', p);
    if (q == std::string::npos) q = v.size();
    if (q > p) r.push_back(std::atoi(v.substr(p, q - p).c_str()));
    p = q + 1;
  }
  return r;
}

std::vector<std::string> words(const std::string &v) {
  std::vector<std::string> r;
  size_t p = 0;
  while (p < v.size()) {
    size_t q = v.find(',', p);
    if (q == std::string::npos) q = v.size();
    if (q > p) r.push_back(v.substr(p, q - p));
    p = q + 1;
  }
  return r;
}

bool known(const std::vector<const char *> &names, const std::string &k) {
  return std::find_if(names.begin(), names.end(), [&](const char *n) {
           return k == n;
         }) != names.end();
}

Cfg parse(int argc, char **argv) {
  Cfg c;
  std::vector<int> init;
  for (int a = 1; a < argc; ++a) {
    std::string kv = argv[a];
    size_t eq = kv.find('=');
    if (eq == std::string::npos) die("arguments are key=value");
    std::string k = kv.substr(0, eq), v = kv.substr(eq + 1);
    int n = std::atoi(v.c_str());
    if (k == "servers") c.S = n;
    else if (k == "init_servers") init = ints(v);
    else if (k == "values") {
      auto vs = ints(v);
      if (vs.size() > 8) die("more than 8 values");
      c.nvals = (int)vs.size();
      for (int q = 0; q < c.nvals; ++q) c.vals[q] = vs[q];
    } else if (k == "next") {
      if (v == "NextAsync") c.family = 0;
      else if (v == "NextAsyncCrash") c.family = 1;
      else if (v == "Next") c.family = 2;
      else if (v == "NextDynamic") c.family = 3;
      else die("a Next family this reference does not know");
    } else if (k == "symmetry") c.symmetry = v == "1";
    else if (k == "max_log_length") c.max_log = n;
    else if (k == "max_restarts") c.max_restarts = n;
    else if (k == "max_timeouts") c.max_timeouts = n;
    else if (k == "max_terms") c.max_terms = n;
    else if (k == "max_client_requests") c.max_client_requests = n;
    else if (k == "max_membership_changes") c.max_membership_changes = n;
    else if (k == "max_tried_membership_changes")
      c.max_tried_membership_changes = n;
    else if (k == "num_rounds") c.num_rounds = n;
    else if (k == "max_inflight_messages") c.max_inflight = n;
    else if (k == "constraints") c.constraints = words(v);
    else if (k == "invariants") c.invariants = words(v);
    else if (k == "max_depth") c.max_depth = n;
    else if (k == "fp_bits") c.fp_bits = n;
    else die("an unknown argument");
  }
  if (c.S < 1 || c.S > SMAX) die("servers out of range");
  if (c.family < 0) die("no next");
  if (c.max_log + 1 > LMAX) die("max_log_length too large for LMAX");
  if (c.num_rounds < 1 || c.num_rounds > 255) die("num_rounds out of range");
  if (c.fp_bits < 0 || c.fp_bits > 63) die("fp_bits out of range");
  static const std::vector<const char *> constraints = {
      "BoundedInFlightMessages", "BoundedRequestVote", "BoundedLogSize",
      "BoundedRestarts", "BoundedTimeouts", "BoundedTerms",
      "BoundedClientRequests", "BoundedTriedMembershipChanges",
      "BoundedMembershipChanges", "ElectionsUncontested",
      "CleanStartUntilFirstRequest", "CleanStartUntilTwoLeaders",
      "CleanFirstLeaderElection"};
  static const std::vector<const char *> invariants = {
      "LeaderVotesQuorum", "CandidateTermNotInLog", "ElectionSafety",
      "LogMatching", "VotesGrantedInv", "VotesGrantedInv_false",
      "QuorumLogInv", "MoreUpToDateCorrect", "LeaderCompleteness",
      "LeaderCompleteness_false", "OneAtATimeMembershipChangeOK"};
  for (auto &k : c.constraints)
    if (!known(constraints, k)) die("a constraint this reference does not know");
  for (auto &k : c.invariants)
    if (!known(invariants, k)) die("an invariant this reference does not know");
  for (int i : init) {
    if (i < 0 || i >= c.S) die("an init server out of range");
    c.init_mask |= 1u << i;
  }
  // relabelings of Server that map InitServer onto itself
  std::vector<uint8_t> sigma(c.S);
  for (int i = 0; i < c.S; ++i) sigma[i] = (uint8_t)i;
  do {
    bool keeps = true;
    for (int i = 0; i < c.S; ++i)
      if ((c.init_mask >> i & 1) != (c.init_mask >> sigma[i] & 1))
        keeps = false;
    if (keeps && (c.symmetry || c.perms.empty())) c.perms.push_back(sigma);
    if (!c.symmetry) break;
  } while (std::next_permutation(sigma.begin(), sigma.end()));
  return c;
}

}  // namespace

int main(int argc, char **argv) {
  Cfg c = parse(argc, argv);

  State init;
  std::memset(&init, 0, sizeof init);
  for (int i = 0; i < c.S; ++i) {
    init.currentTerm[i] = 1;
    init.state[i] = FOLLOWER;
    init.votedFor[i] = NIL;
    for (int j = 0; j < c.S; ++j) init.nextIndex[i][j] = 1;
  }

  std::unordered_set<std::string> seen;       // exact reference
  std::unordered_set<uint64_t> seen_fp;       // control only
  std::string key, scratch;
  auto first_time = [&](const State &s) {
    canonical(c, s, key, scratch);
    if (c.fp_bits == 0) return seen.insert(key).second;
    uint64_t fp = hash_bytes(key) & ((1ull << c.fp_bits) - 1);
    return seen_fp.insert(fp).second;
  };

  std::vector<std::string> violated;
  auto check = [&](const State &s) {
    for (auto &inv : c.invariants)
      if (std::find(violated.begin(), violated.end(), inv) ==
              violated.end() &&
          !holds(c, s, inv))
        violated.push_back(inv);
  };

  long long distinct = 1, generated = 1;
  std::vector<long long> level_new, level_kept;
  first_time(init);
  check(init);
  std::vector<State> frontier, next, succ;
  if (in_model(c, init)) frontier.push_back(init);
  int depth = 0;
  while (!frontier.empty() && depth < c.max_depth) {
    depth++;
    next.clear();
    long long fresh = 0;
    for (const State &s : frontier) {
      succ.clear();
      Out o{&succ};
      successors(c, s, o);
      generated += (long long)succ.size();
      for (const State &t : succ) {
        if (!first_time(t)) continue;
        fresh++;
        check(t);
        if (in_model(c, t)) next.push_back(t);
      }
    }
    distinct += fresh;
    level_new.push_back(fresh);
    level_kept.push_back((long long)next.size());
    frontier.swap(next);
  }

  std::sort(violated.begin(), violated.end());
  std::printf("{\"distinct\": %lld, \"generated\": %lld, \"depth\": %d, "
              "\"level_sizes\": [", distinct, generated, depth);
  for (size_t d = 0; d < level_kept.size(); ++d)
    std::printf("%s%lld", d ? ", " : "", level_kept[d]);
  std::printf("], \"level_new\": [");
  for (size_t d = 0; d < level_new.size(); ++d)
    std::printf("%s%lld", d ? ", " : "", level_new[d]);
  std::printf("], \"violated\": [");
  for (size_t q = 0; q < violated.size(); ++q)
    std::printf("%s\"%s\"", q ? ", " : "", violated[q].c_str());
  std::printf("], \"fp_bits\": %d}\n", c.fp_bits);
  return 0;
}
