// planexec.cc — native executor for frozen wire plans.
//
// coll/plan.py freezes a spanning collective's wire schedule into a
// WirePlan (per-round peer lists, FrameTemplates, expected recvs).
// Until now every compiled fire still re-entered Python once per
// round: generator next() per fragment in WireRouter._stripe, a reap
// callback per arrival, a fresh dict of reassembly buffers per round.
// This file lowers the WHOLE plan below the interpreter: Python
// compiles the plan once into a flat descriptor blob (rounds, peers,
// precomposed SGH2 header bytes, scatter-gather payload maps,
// expected-recv headers and pool placements), binds the live
// endpoint/ring handles, and then a steady-state fire is one
// fire_begin + a fire_step loop that walks every round C-side.
//
// Live rounds: a round whose send bytes the provenance probe could
// not locate (a schedule that folds what arrives and sends the fold
// on) carries no scatter-gather map. The walk pauses in front of it
// (RC_PAUSE), Python hands over the arrays the schedule body has just
// made (fire_supply) and the same fire walks on — round 0's "inputs
// come from the caller" at a later round. The fire, its xfer ids, its
// slab and the locks Python holds around it stay one.
//
// Wire parity is structural, not aspirational: headers are composed
// from the SAME precomposed pre/mid byte strings FrameTemplate uses
// (pre + int64rec(xfer) + mid + int64rec(crc)), fragments carry the
// same "SGC2"+xfer+idx prefix, and they travel through the SAME
// shmring_writev / wire_sendv legs as the interpreted path — a
// receiver cannot tell which executor sent a frame.
//
// Receives land in a per-plan reassembly pool: one slab sized at
// compile time from the frozen recv metadata, each (round, src, msg)
// assigned a fixed offset, reused across fires (the mpool/rcache
// analogue — zero steady-state allocation).
//
// Blocking discipline: fire_step(slice_ms) returns RC_AGAIN at safe
// points when the slice expires so Python can run the ULFM failure
// detector between slices (the same ~100 ms cadence as the
// interpreted _sliced_recv); a per-comm fault word (set by Python
// from FtState) is polled inside the wait loops so death/revoke
// aborts the fire within the detection interval even mid-slice.
// Foreign frames met on the coll channel (stale fragments are
// dropped exactly like the portable resync; anything else) are
// stashed verbatim for Python to re-inject into the btl stashes
// after the run — the executor never eats another channel's bytes.

#include <cstdint>
#include <cstring>
#include <ctime>
#include <deque>
#include <mutex>
#include <vector>

#include "crc32.h"
#include "oob_endpoint.h"

using ompitpu::crc32_update;
using ompitpu::Endpoint;
using ompitpu::Frame;

// Datapath legs from btl_shm.cc / btl_tcp.cc / oob.cc — same .so,
// linked together; declared here instead of a shared header because
// the extern "C" ABI *is* the contract (ctypes loads these too).
extern "C" {
int oob_send(void* h, int32_t dst, int32_t tag, const uint8_t* data,
             int32_t len);
int wire_sendv(void* h, int32_t dst, int32_t tag, const uint8_t** parts,
               const int64_t* lens, int32_t nparts);
int64_t wire_recv_frag(void* h, int32_t src, int32_t tag, int64_t xfer,
                       int64_t nchunks, int64_t chunk, uint8_t* base,
                       int64_t nbytes, int timeout_ms);
int shmring_writev(void* vr, int32_t tag, const uint8_t** parts,
                   const int64_t* lens, int32_t nparts, int timeout_ms);
int64_t shmring_read_frag(void* vr, int32_t tag, int64_t xfer,
                          int64_t nchunks, int64_t chunk, uint8_t* base,
                          int64_t nbytes, int timeout_ms);
int64_t shmring_read_into(void* vr, int32_t* tag, uint8_t* out,
                          int64_t maxlen, int timeout_ms);
}

namespace {

// ---- return codes (mirrored in native/bindings.py PlanExec) ----
constexpr int RC_DONE = 0;
constexpr int RC_AGAIN = 1;        // slice expired; call fire_step again
constexpr int RC_FTSTOP = 2;       // fault word set; Python runs check_wait
constexpr int RC_PAUSE = 3;        // live round ahead: fire_supply, then step
constexpr int RC_BADARG = -1;
constexpr int RC_PEERDEAD = -2;    // err_peer() names the pidx
constexpr int RC_TIMEOUT = -3;     // plan timeout exhausted
constexpr int RC_DIVERGED = -4;    // inbound header != frozen expectation
constexpr int RC_TRUNCATED = -5;   // reassembled payload failed CRC
constexpr int RC_WOULDBLOCK = -100;  // internal: ring full, try later

constexpr uint64_t kBlobMagic = 0x314345584C504FULL;  // "OPLXEC1"
constexpr int64_t kBlobVersion = 2;

// DSS int64 single-value record marker: type tag DSS_INT64 (1) +
// u32 LE count 1 — the 5 bytes btl/components._int64_rec prepends.
constexpr uint8_t kI64Marker[5] = {0x01, 0x01, 0x00, 0x00, 0x00};
constexpr int64_t kI64Rec = 13;    // marker + 8-byte LE value

double mono_s() {
  timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return static_cast<double>(ts.tv_sec) + 1e-9 * ts.tv_nsec;
}

void nap_us(long us) {
  timespec ts{0, us * 1000L};
  nanosleep(&ts, nullptr);
}

// ---- frozen descriptor (parsed once from the Python-built blob) ----

struct Seg {          // one scatter-gather span of a composed payload
  int64_t kind;       // 0 = input region (live pointer), 1 = pool,
                      // 2 = message idx of a live round (fire_supply)
  int64_t idx;        // region index within its kind
  int64_t off;
  int64_t len;
};

struct SendMsg {
  std::vector<uint8_t> pre, mid;   // FrameTemplate header constants
  int64_t nbytes, nchunks, chunk;
  std::vector<Seg> segs;
};

struct Stream {       // one peer's message sequence within a round
  int64_t peer;       // index into PlanExec::peers
  std::vector<SendMsg> msgs;
};

struct RecvMsg {
  int64_t pool_idx;
  int64_t nbytes, nchunks, chunk;
  std::vector<uint8_t> pre, mid;   // expected header constants
};

struct RecvSrc {
  int64_t peer;
  std::vector<RecvMsg> msgs;
};

struct Round {
  int64_t depth;
  bool live = false;  // sends come from Python, at the pause before it
  std::vector<Stream> streams;
  std::vector<RecvSrc> rsrcs;
};

struct PoolBuf {
  int64_t off, nbytes;
};

struct PeerBind {
  int64_t pidx;
  int32_t nid = -1;
  void* tx_ring = nullptr;   // null → vectored-socket leg
  void* rx_ring = nullptr;   // null → endpoint-queue leg
};

struct StashFrame {   // foreign bytes met on the coll channel
  int64_t kind;       // 0 = endpoint queue frame, 1 = ring record
  int64_t peer;       // pidx it arrived from
  int64_t tag;
  std::vector<uint8_t> bytes;
};

// ---- per-fire resumable state ----

struct StreamState {
  size_t msg = 0;
  int64_t frame = 0;   // 0 = header, 1..nchunks = fragments
  int64_t xfer = 0;
  uint32_t crc = 0;
  bool blocked = false;  // current fragment has met a full ring
  bool done = false;
};

struct SrcState {
  size_t msg = 0;
  int mode = 0;        // 0 = want header, 1 = want fragments
  int64_t xfer = 0;
  uint32_t crc_exp = 0;
  int64_t got = 0;
  bool done = false;
};

struct PlanExec {
  // frozen
  int32_t tag = 0;
  std::vector<int64_t> input_lens;
  std::vector<PoolBuf> pool;
  int64_t pool_total = 0;
  std::vector<PeerBind> peers;
  std::vector<Round> rounds;
  std::vector<uint8_t> slab;

  // bound
  Endpoint* ep = nullptr;
  int32_t my_nid = -1;
  const volatile int64_t* ftword = nullptr;

  // fire state
  bool firing = false;
  std::vector<const uint8_t*> inputs;
  std::vector<const uint8_t*> supplied;  // the current live round's sends
  bool supplied_ok = false;
  int64_t timeout_ms = 0;
  int64_t xfer_next = 0;
  double deadline_total = 0.0;
  size_t cur_round = 0;
  std::vector<StreamState> sst;
  std::vector<SrcState> rst;
  std::vector<double> ts;          // per-round end stamps
  std::vector<StashFrame> stash;
  int64_t ring_yields = 0;         // fragments of this fire that met a
                                   // full ring and yielded to a drain
  int64_t err_peer = -1;
  int64_t err_round = -1;
  double slice_deadline = 0.0;
};

// ---- blob parsing ----

struct Cursor {
  const uint8_t* p;
  const uint8_t* end;
  bool ok = true;

  int64_t i64() {
    if (!ok || end - p < 8) { ok = false; return 0; }
    int64_t v;
    std::memcpy(&v, p, 8);
    p += 8;
    return v;
  }
  bool bytes(std::vector<uint8_t>* out) {
    int64_t n = i64();
    if (!ok || n < 0 || end - p < n) { ok = false; return false; }
    out->assign(p, p + n);
    p += n;
    return true;
  }
};

PlanExec* parse_blob(const uint8_t* blob, int64_t len) {
  Cursor c{blob, blob + len};
  if (static_cast<uint64_t>(c.i64()) != kBlobMagic) return nullptr;
  if (c.i64() != kBlobVersion) return nullptr;
  auto x = new PlanExec();
  x->tag = static_cast<int32_t>(c.i64());
  int64_t n_inputs = c.i64();
  for (int64_t i = 0; c.ok && i < n_inputs; ++i)
    x->input_lens.push_back(c.i64());
  int64_t n_pool = c.i64();
  for (int64_t i = 0; c.ok && i < n_pool; ++i) {
    PoolBuf b;
    b.off = c.i64();
    b.nbytes = c.i64();
    x->pool.push_back(b);
  }
  x->pool_total = c.i64();
  int64_t n_peers = c.i64();
  for (int64_t i = 0; c.ok && i < n_peers; ++i) {
    PeerBind pb;
    pb.pidx = c.i64();
    x->peers.push_back(pb);
  }
  int64_t n_rounds = c.i64();
  for (int64_t r = 0; c.ok && r < n_rounds; ++r) {
    Round rd;
    rd.depth = c.i64();
    rd.live = c.i64() != 0;
    int64_t n_streams = c.i64();
    for (int64_t s = 0; c.ok && s < n_streams; ++s) {
      Stream st;
      st.peer = c.i64();
      int64_t n_msgs = c.i64();
      for (int64_t m = 0; c.ok && m < n_msgs; ++m) {
        SendMsg sm;
        c.bytes(&sm.pre);
        c.bytes(&sm.mid);
        sm.nbytes = c.i64();
        sm.nchunks = c.i64();
        sm.chunk = c.i64();
        int64_t n_segs = c.i64();
        for (int64_t g = 0; c.ok && g < n_segs; ++g) {
          Seg sg;
          sg.kind = c.i64();
          sg.idx = c.i64();
          sg.off = c.i64();
          sg.len = c.i64();
          sm.segs.push_back(sg);
        }
        st.msgs.push_back(std::move(sm));
      }
      rd.streams.push_back(std::move(st));
    }
    int64_t n_rsrcs = c.i64();
    for (int64_t s = 0; c.ok && s < n_rsrcs; ++s) {
      RecvSrc rs;
      rs.peer = c.i64();
      int64_t n_msgs = c.i64();
      for (int64_t m = 0; c.ok && m < n_msgs; ++m) {
        RecvMsg rm;
        rm.pool_idx = c.i64();
        rm.nbytes = c.i64();
        rm.nchunks = c.i64();
        rm.chunk = c.i64();
        c.bytes(&rm.pre);
        c.bytes(&rm.mid);
        rs.msgs.push_back(std::move(rm));
      }
      rd.rsrcs.push_back(std::move(rs));
    }
    x->rounds.push_back(std::move(rd));
  }
  // structural sanity: every index in range, sizes consistent
  if (c.ok) {
    for (auto& rd : x->rounds) {
      int64_t flat = 0;  // message index within the round, stream order
      for (auto& st : rd.streams) {
        if (st.peer < 0 ||
            st.peer >= static_cast<int64_t>(x->peers.size()))
          c.ok = false;
        for (auto& sm : st.msgs) {
          int64_t tot = 0;
          // a live round's message is one supplied array, whole
          if (rd.live && (sm.segs.size() != 1 || sm.segs[0].kind != 2 ||
                          sm.segs[0].idx != flat))
            c.ok = false;
          ++flat;
          for (auto& sg : sm.segs) {
            tot += sg.len;
            if (sg.kind == 2) {
              if (!rd.live || sg.off != 0 || sg.len != sm.nbytes)
                c.ok = false;
            } else if (sg.kind == 0) {
              if (sg.idx < 0 ||
                  sg.idx >= static_cast<int64_t>(x->input_lens.size()) ||
                  sg.off < 0 || sg.off + sg.len > x->input_lens[sg.idx])
                c.ok = false;
            } else if (sg.kind == 1) {
              if (sg.idx < 0 ||
                  sg.idx >= static_cast<int64_t>(x->pool.size()) ||
                  sg.off < 0 ||
                  sg.off + sg.len > x->pool[sg.idx].nbytes)
                c.ok = false;
            } else {
              c.ok = false;
            }
          }
          if (tot != sm.nbytes) c.ok = false;
        }
      }
      for (auto& rs : rd.rsrcs) {
        if (rs.peer < 0 ||
            rs.peer >= static_cast<int64_t>(x->peers.size()))
          c.ok = false;
        for (auto& rm : rs.msgs) {
          if (rm.pool_idx < 0 ||
              rm.pool_idx >= static_cast<int64_t>(x->pool.size()) ||
              x->pool[rm.pool_idx].nbytes != rm.nbytes)
            c.ok = false;
        }
      }
    }
    for (auto& b : x->pool)
      if (b.off < 0 || b.nbytes < 0 || b.off + b.nbytes > x->pool_total)
        c.ok = false;
  }
  if (!c.ok) {
    delete x;
    return nullptr;
  }
  x->slab.resize(static_cast<size_t>(x->pool_total));
  x->ts.assign(x->rounds.size(), 0.0);
  return x;
}

// ---- send side ----

// Compose and send one message header: pre + int64rec(xfer) + mid +
// int64rec(crc) — byte-identical to FrameTemplate.header().
int send_header(PlanExec* x, const PeerBind& pb, const SendMsg& m,
                int64_t xfer, uint32_t crc) {
  std::vector<uint8_t> h;
  h.reserve(m.pre.size() + m.mid.size() + 2 * kI64Rec);
  h.insert(h.end(), m.pre.begin(), m.pre.end());
  h.insert(h.end(), kI64Marker, kI64Marker + 5);
  int64_t xv = xfer;
  uint8_t tmp[8];
  std::memcpy(tmp, &xv, 8);
  h.insert(h.end(), tmp, tmp + 8);
  h.insert(h.end(), m.mid.begin(), m.mid.end());
  h.insert(h.end(), kI64Marker, kI64Marker + 5);
  int64_t cv = static_cast<int64_t>(crc);
  std::memcpy(tmp, &cv, 8);
  h.insert(h.end(), tmp, tmp + 8);
  return oob_send(x->ep, pb.nid, x->tag,
                  h.data(), static_cast<int32_t>(h.size()));
}

// Where a span's bytes live right now: a caller's array (fire_begin's
// or, in a live round, fire_supply's) or the reassembly slab.
const uint8_t* seg_base(PlanExec* x, const Seg& sg) {
  size_t i = static_cast<size_t>(sg.idx);
  if (sg.kind == 0) return x->inputs[i];
  if (sg.kind == 2) return x->supplied[i];
  return x->slab.data() + x->pool[i].off;
}

uint32_t crc_of_msg(PlanExec* x, const SendMsg& m) {
  uint32_t crc = 0;
  for (auto& sg : m.segs) {
    crc = crc32_update(crc, seg_base(x, sg) + sg.off,
                       static_cast<size_t>(sg.len));
  }
  return crc;
}

// Build the scatter-gather part list for fragment `ci` of msg `m`:
// ["SGC2"+xfer(8B BE), idx(8B BE), payload sub-spans...] — the same
// frame FrameTemplate.sg_lists yields, except composed payloads go
// to the wire straight from their source regions (the interpreted
// path joins them into a staging array first).
int send_frag(PlanExec* x, const PeerBind& pb, const SendMsg& m,
              int64_t xfer, int64_t ci, int* rc_out) {
  uint8_t pre12[12];
  std::memcpy(pre12, "SGC2", 4);
  for (int i = 0; i < 8; ++i)
    pre12[4 + i] = static_cast<uint8_t>((xfer >> (8 * (7 - i))) & 0xFF);
  uint8_t idx8[8];
  for (int i = 0; i < 8; ++i)
    idx8[i] = static_cast<uint8_t>((ci >> (8 * (7 - i))) & 0xFF);

  int64_t lo = ci * m.chunk;
  int64_t hi = lo + m.chunk;
  if (hi > m.nbytes) hi = m.nbytes;

  const uint8_t* parts[2 + 64];
  int64_t lens[2 + 64];
  std::vector<const uint8_t*> pvec;
  std::vector<int64_t> lvec;
  const uint8_t** pp = parts;
  int64_t* pl = lens;
  int32_t np = 0;
  auto push = [&](const uint8_t* ptr, int64_t n) {
    if (np >= 2 + 64 && pvec.empty()) {   // spill: rare, deep SG maps
      pvec.assign(parts, parts + np);
      lvec.assign(lens, lens + np);
    }
    if (!pvec.empty()) {
      pvec.push_back(ptr);
      lvec.push_back(n);
    } else {
      pp[np] = ptr;
      pl[np] = n;
    }
    ++np;
  };
  push(pre12, 12);
  push(idx8, 8);
  int64_t pos = 0;
  for (auto& sg : m.segs) {
    int64_t s0 = pos, s1 = pos + sg.len;
    pos = s1;
    if (s1 <= lo || s0 >= hi) continue;
    int64_t a = lo > s0 ? lo : s0;
    int64_t b = hi < s1 ? hi : s1;
    push(seg_base(x, sg) + sg.off + (a - s0), b - a);
  }
  const uint8_t** P = pvec.empty() ? parts : pvec.data();
  int64_t* L = lvec.empty() ? lens : lvec.data();

  if (pb.tx_ring != nullptr) {
    // never-fits falls back to the vectored socket, dead consumer
    // is a typed error, and a full ring is not waited for in here:
    // the caller drains our own arrivals (the peer may be parked on
    // OUR full ring) and retries. The ring keeps one stall open per
    // blocked record across those retries (native/btl_shm.cc), so
    // w_stalls and w_stall_ns read as if the wait had been inside.
    int rc = shmring_writev(pb.tx_ring, x->tag, P, L, np, 0);
    if (rc == 0) return 0;
    if (rc == -3) { *rc_out = RC_PEERDEAD; return -1; }
    if (rc == -1) { *rc_out = RC_WOULDBLOCK; return -1; }
    // rc == -2: frame can never fit → socket leg below
  }
  if (wire_sendv(x->ep, pb.nid, x->tag, P, L, np) != 0) {
    *rc_out = RC_PEERDEAD;
    return -1;
  }
  return 0;
}

// ---- receive side ----

bool header_matches(const RecvMsg& rm, const std::vector<uint8_t>& pay,
                    int64_t* xfer, uint32_t* crc) {
  size_t want = rm.pre.size() + rm.mid.size() + 2 * kI64Rec;
  if (pay.size() != want) return false;
  const uint8_t* p = pay.data();
  if (std::memcmp(p, rm.pre.data(), rm.pre.size()) != 0) return false;
  p += rm.pre.size();
  if (std::memcmp(p, kI64Marker, 5) != 0) return false;
  int64_t xv;
  std::memcpy(&xv, p + 5, 8);
  p += kI64Rec;
  if (std::memcmp(p, rm.mid.data(), rm.mid.size()) != 0) return false;
  p += rm.mid.size();
  if (std::memcmp(p, kI64Marker, 5) != 0) return false;
  int64_t cv;
  std::memcpy(&cv, p + 5, 8);
  *xfer = xv;
  *crc = static_cast<uint32_t>(cv);
  return true;
}

bool is_sgh2_pre(const RecvMsg& rm, const std::vector<uint8_t>& pay) {
  return pay.size() >= rm.pre.size() &&
         std::memcmp(pay.data(), rm.pre.data(), rm.pre.size()) == 0;
}

// Pop the first queued frame from (nid, tag) off the endpoint.
// Returns false when none is queued. No waiting — the reap sweep is
// a poll; blocking happens via the sweep's nap.
bool pop_queue_frame(PlanExec* x, int32_t nid,
                     std::vector<uint8_t>* out) {
  std::lock_guard<std::mutex> l(x->ep->mu);
  for (auto it = x->ep->queue.begin(); it != x->ep->queue.end(); ++it) {
    if (it->src == nid && it->tag == x->tag) {
      *out = std::move(it->payload);
      x->ep->queue.erase(it);
      return true;
    }
  }
  return false;
}

// Drain one foreign record off an rx ring into the stash (ring head
// is blocked on a record for another channel — a cross-tag p2p
// transfer sharing this slot). Python re-injects it post-run.
bool stash_ring_head(PlanExec* x, const PeerBind& pb) {
  std::vector<uint8_t> buf(4096);
  int32_t tag = 0;
  for (;;) {
    int64_t rc = shmring_read_into(pb.rx_ring, &tag, buf.data(),
                                   static_cast<int64_t>(buf.size()), 0);
    if (rc >= 0) {
      buf.resize(static_cast<size_t>(rc));
      x->stash.push_back({1, pb.pidx, tag, std::move(buf)});
      return true;
    }
    if (rc == -2) {                  // record larger than buf: grow
      buf.resize(buf.size() * 2);
      continue;
    }
    return false;                    // empty or producer dead: no-op
  }
}

// One reap sweep over the current round's pending sources. Returns
// >0 on progress, 0 on none, <0 (via rc_out) on typed error.
int reap_sweep(PlanExec* x, int* rc_out) {
  Round& rd = x->rounds[x->cur_round];
  int progress = 0;
  for (size_t si = 0; si < rd.rsrcs.size(); ++si) {
    RecvSrc& rs = rd.rsrcs[si];
    SrcState& st = x->rst[si];
    if (st.done) continue;
    PeerBind& pb = x->peers[static_cast<size_t>(rs.peer)];
    RecvMsg& rm = rs.msgs[st.msg];
    uint8_t* dst = x->slab.data() +
                   x->pool[static_cast<size_t>(rm.pool_idx)].off;

    if (st.mode == 0) {
      // headers always ride the endpoint queue
      std::vector<uint8_t> pay;
      if (!pop_queue_frame(x, pb.nid, &pay)) continue;
      progress = 1;
      int64_t xfer;
      uint32_t crc;
      if (header_matches(rm, pay, &xfer, &crc)) {
        st.mode = 1;
        st.xfer = xfer;
        st.crc_exp = crc;
        st.got = 0;
      } else if (pay.size() >= 4 &&
                 std::memcmp(pay.data(), "SGC2", 4) == 0) {
        // stale fragment from an abandoned transfer: drop, exactly
        // like the portable receiver's resync-to-next-header
        continue;
      } else if (is_sgh2_pre(rm, pay)) {
        // a real header whose dtype/shape/chunking differs from the
        // frozen expectation: the schedule diverged
        x->err_peer = pb.pidx;
        x->err_round = static_cast<int64_t>(x->cur_round);
        *rc_out = RC_DIVERGED;
        return -1;
      } else {
        // not ours — preserve for Python's stash re-injection
        x->stash.push_back({0, pb.pidx, x->tag, std::move(pay)});
      }
      continue;
    }

    // fragment mode
    int64_t rc;
    if (pb.rx_ring != nullptr) {
      rc = shmring_read_frag(pb.rx_ring, x->tag, st.xfer, rm.nchunks,
                             rm.chunk, dst, rm.nbytes, 0);
      if (rc == -5) {                // foreign tag parked at ring head
        if (stash_ring_head(x, pb)) progress = 1;
        continue;
      }
      if (rc == -3) {
        x->err_peer = pb.pidx;
        x->err_round = static_cast<int64_t>(x->cur_round);
        *rc_out = RC_PEERDEAD;
        return -1;
      }
      if (rc == -4) { progress = 1; continue; }  // stale, consumed
      if (rc == -2) { progress = 1; continue; }  // malformed, consumed
    } else {
      rc = wire_recv_frag(x->ep, pb.nid, x->tag, st.xfer, rm.nchunks,
                          rm.chunk, dst, rm.nbytes, 0);
      if (rc == -4) {
        // head frame for (src, tag) is not our fragment: either a
        // stale fragment (drop) or something foreign (stash)
        std::vector<uint8_t> pay;
        if (pop_queue_frame(x, pb.nid, &pay)) {
          progress = 1;
          if (!(pay.size() >= 4 &&
                std::memcmp(pay.data(), "SGC2", 4) == 0))
            x->stash.push_back({0, pb.pidx, x->tag, std::move(pay)});
        }
        continue;
      }
      if (rc == -2) { progress = 1; continue; }
    }
    if (rc < 0) continue;            // timeout: no fragment queued

    progress = 1;
    if (++st.got < rm.nchunks) continue;

    // message complete: end-to-end integrity before it becomes a
    // source region for later rounds
    uint32_t crc = crc32_update(0, dst, static_cast<size_t>(rm.nbytes));
    if (crc != st.crc_exp) {
      x->err_peer = pb.pidx;
      x->err_round = static_cast<int64_t>(x->cur_round);
      *rc_out = RC_TRUNCATED;
      return -1;
    }
    st.mode = 0;
    if (++st.msg >= rs.msgs.size()) st.done = true;
  }
  return progress;
}

void enter_round(PlanExec* x) {
  Round& rd = x->rounds[x->cur_round];
  x->sst.assign(rd.streams.size(), StreamState());
  for (size_t i = 0; i < rd.streams.size(); ++i)
    if (rd.streams[i].msgs.empty()) x->sst[i].done = true;
  x->rst.assign(rd.rsrcs.size(), SrcState());
  for (size_t i = 0; i < rd.rsrcs.size(); ++i)
    if (rd.rsrcs[i].msgs.empty()) x->rst[i].done = true;
  x->supplied_ok = false;
}

}  // namespace

extern "C" {

void* planexec_create(const uint8_t* blob, int64_t len) {
  if (blob == nullptr || len < 16) return nullptr;
  return parse_blob(blob, len);
}

void planexec_destroy(void* h) { delete static_cast<PlanExec*>(h); }

int planexec_bind(void* h, void* ep, int64_t my_nid,
                  const int64_t* peer_nids, void** tx_rings,
                  void** rx_rings, int64_t n_peers) {
  auto* x = static_cast<PlanExec*>(h);
  if (ep == nullptr ||
      n_peers != static_cast<int64_t>(x->peers.size()))
    return RC_BADARG;
  x->ep = static_cast<Endpoint*>(ep);
  x->my_nid = static_cast<int32_t>(my_nid);
  for (int64_t i = 0; i < n_peers; ++i) {
    x->peers[static_cast<size_t>(i)].nid =
        static_cast<int32_t>(peer_nids[i]);
    x->peers[static_cast<size_t>(i)].tx_ring = tx_rings[i];
    x->peers[static_cast<size_t>(i)].rx_ring = rx_rings[i];
  }
  return 0;
}

void planexec_set_ftword(void* h, const int64_t* word) {
  static_cast<PlanExec*>(h)->ftword =
      static_cast<const volatile int64_t*>(word);
}

int planexec_fire_begin(void* h, const uint8_t** inputs,
                        const int64_t* lens, int64_t n,
                        int64_t xfer_base, int64_t timeout_ms) {
  auto* x = static_cast<PlanExec*>(h);
  if (x->ep == nullptr ||
      n != static_cast<int64_t>(x->input_lens.size()))
    return RC_BADARG;
  for (int64_t i = 0; i < n; ++i)
    if (lens[i] != x->input_lens[static_cast<size_t>(i)])
      return RC_BADARG;
  x->inputs.assign(inputs, inputs + n);
  x->xfer_next = xfer_base;
  x->timeout_ms = timeout_ms;
  x->deadline_total = mono_s() + 1e-3 * static_cast<double>(timeout_ms);
  x->cur_round = 0;
  x->ts.assign(x->rounds.size(), 0.0);
  x->ring_yields = 0;
  x->err_peer = -1;
  x->err_round = -1;
  x->firing = true;
  if (!x->rounds.empty()) enter_round(x);
  return 0;
}

// The sends of the live round the walk has paused at, one pointer per
// message in stream order (sorted peer, then the peer's message list):
// the arrays must stay where they are until the next return that is
// not RC_AGAIN / RC_FTSTOP. The wait bound starts anew, as a replayed
// round's does: the time Python took between two segments is not the
// wire's.
int planexec_fire_supply(void* h, const uint8_t** ptrs,
                         const int64_t* lens, int64_t n) {
  auto* x = static_cast<PlanExec*>(h);
  if (!x->firing || x->cur_round >= x->rounds.size()) return RC_BADARG;
  Round& rd = x->rounds[x->cur_round];
  if (!rd.live || x->supplied_ok) return RC_BADARG;
  int64_t flat = 0;
  for (auto& st : rd.streams)
    for (auto& sm : st.msgs) {
      if (flat >= n || lens[flat] != sm.nbytes) return RC_BADARG;
      ++flat;
    }
  if (flat != n) return RC_BADARG;
  x->supplied.assign(ptrs, ptrs + n);
  x->supplied_ok = true;
  x->deadline_total = mono_s() + 1e-3 * static_cast<double>(x->timeout_ms);
  return 0;
}

// The fire is over for Python: walked to its end, or left where it
// stands (its schedule raised between two segments). What the reap
// set aside stays in the stash for drain, the next fire_begin starts
// clean.
void planexec_fire_end(void* h) {
  static_cast<PlanExec*>(h)->firing = false;
}

// Walk rounds until done, error, fault-word stop, slice expiry, or a
// live round whose sends have not been supplied.
// Send legs stripe round-robin across peer streams in depth-sized
// bursts (the _stripe discipline). A ring write never waits: on a
// full ring the send phase drains its own arrivals until a sweep
// finds nothing, retries the fragment, and naps 50 us only when
// neither the write nor the drain moved anything — so two opposing
// senders past one ring of bytes each run at copy speed instead of
// one fragment per wait slice, and cannot deadlock.
int planexec_fire_step(void* h, int64_t slice_ms) {
  auto* x = static_cast<PlanExec*>(h);
  if (!x->firing) return RC_BADARG;
  x->slice_deadline = mono_s() + 1e-3 * static_cast<double>(slice_ms);

  while (x->cur_round < x->rounds.size()) {
    Round& rd = x->rounds[x->cur_round];
    if (rd.live && !x->supplied_ok) return RC_PAUSE;

    // ---- send phase: striped depth bursts over open streams ----
    bool sends_left = false;
    for (auto& ss : x->sst) sends_left |= !ss.done;
    while (sends_left) {
      sends_left = false;
      for (size_t si = 0; si < rd.streams.size(); ++si) {
        StreamState& ss = x->sst[si];
        if (ss.done) continue;
        Stream& stm = rd.streams[si];
        PeerBind& pb = x->peers[static_cast<size_t>(stm.peer)];
        int64_t b = 0;
        while (b < rd.depth && !ss.done) {
          SendMsg& m = stm.msgs[ss.msg];
          int rc = 0;
          if (ss.frame == 0) {
            ss.xfer = x->xfer_next++;
            ss.crc = crc_of_msg(x, m);
            if (send_header(x, pb, m, ss.xfer, ss.crc) != 0) {
              x->err_peer = pb.pidx;
              x->err_round = static_cast<int64_t>(x->cur_round);
              x->firing = false;
              return RC_PEERDEAD;
            }
            ss.frame = 1;
            ++b;
            continue;
          }
          if (send_frag(x, pb, m, ss.xfer, ss.frame - 1, &rc) != 0) {
            if (rc == RC_WOULDBLOCK) {
              // peer's ring is full: take everything that is queued
              // for us (the peer may be parked on OUR full ring, and
              // one sweep takes one fragment per source), check
              // fault / deadlines, then retry this same fragment;
              // nap only when neither side moved
              if (!ss.blocked) {
                ss.blocked = true;
                ++x->ring_yields;
              }
              int rc2 = 0, prog;
              bool drained = false;
              while ((prog = reap_sweep(x, &rc2)) > 0) drained = true;
              if (prog < 0) {
                x->firing = false;
                return rc2;
              }
              if (x->ftword != nullptr && *x->ftword != 0)
                return RC_FTSTOP;
              double now = mono_s();
              if (now >= x->deadline_total) {
                x->err_peer = pb.pidx;
                x->err_round = static_cast<int64_t>(x->cur_round);
                x->firing = false;
                return RC_TIMEOUT;
              }
              if (now >= x->slice_deadline) return RC_AGAIN;
              if (!drained) nap_us(50);
              continue;
            }
            x->err_peer = pb.pidx;
            x->err_round = static_cast<int64_t>(x->cur_round);
            x->firing = false;
            return rc;
          }
          ss.blocked = false;
          ++ss.frame;
          ++b;
          if (ss.frame > m.nchunks) {
            ss.frame = 0;
            if (++ss.msg >= stm.msgs.size()) ss.done = true;
          }
        }
        if (!ss.done) sends_left = true;
      }
      if (x->ftword != nullptr && *x->ftword != 0) return RC_FTSTOP;
      if (mono_s() >= x->slice_deadline && sends_left) return RC_AGAIN;
    }

    // ---- reap phase: poll + nap until the round's recvs land ----
    for (;;) {
      bool pending = false;
      for (auto& st : x->rst) pending |= !st.done;
      if (!pending) break;
      int rc = 0;
      int prog = reap_sweep(x, &rc);
      if (prog < 0) {
        x->firing = false;
        return rc;
      }
      if (x->ftword != nullptr && *x->ftword != 0) return RC_FTSTOP;
      double now = mono_s();
      if (now >= x->deadline_total) {
        x->err_round = static_cast<int64_t>(x->cur_round);
        x->firing = false;
        return RC_TIMEOUT;
      }
      if (now >= x->slice_deadline) return RC_AGAIN;
      if (prog == 0) nap_us(100);
    }

    x->ts[x->cur_round] = mono_s();
    if (++x->cur_round < x->rounds.size()) enter_round(x);
  }

  x->firing = false;
  return RC_DONE;
}

const uint8_t* planexec_pool_ptr(void* h) {
  return static_cast<PlanExec*>(h)->slab.data();
}

int64_t planexec_pool_total(void* h) {
  return static_cast<PlanExec*>(h)->pool_total;
}

int64_t planexec_pool_count(void* h) {
  return static_cast<int64_t>(static_cast<PlanExec*>(h)->pool.size());
}

int64_t planexec_round_count(void* h) {
  return static_cast<int64_t>(static_cast<PlanExec*>(h)->rounds.size());
}

int64_t planexec_input_count(void* h) {
  return static_cast<int64_t>(
      static_cast<PlanExec*>(h)->input_lens.size());
}

const double* planexec_ts_ptr(void* h) {
  return static_cast<PlanExec*>(h)->ts.data();
}

// fragments of the last fire that met a full tx ring (0: the rings
// never filled)
int64_t planexec_ring_yields(void* h) {
  return static_cast<PlanExec*>(h)->ring_yields;
}

// the library's CRC (crc32.h) for Python: btl/nativewire computes a
// message's header checksum with it and verifies whatever the ring's
// one-call read did not checksum inside its copy (the socket leg,
// frames that came through a stash); tests hold it against
// zlib.crc32. tables_only != 0 takes the portable path whatever the
// CPU has
uint32_t planexec_crc32(uint32_t crc, const uint8_t* p, int64_t n,
                        int32_t tables_only) {
  return crc32_update(crc, p, static_cast<size_t>(n), tables_only != 0);
}

int64_t planexec_err_peer(void* h) {
  return static_cast<PlanExec*>(h)->err_peer;
}

int64_t planexec_err_round(void* h) {
  return static_cast<PlanExec*>(h)->err_round;
}

int64_t planexec_stash_count(void* h) {
  return static_cast<int64_t>(static_cast<PlanExec*>(h)->stash.size());
}

// len of stash entry i; kind 0 = endpoint frame, 1 = ring record
int64_t planexec_stash_info(void* h, int64_t i, int64_t* kind,
                            int64_t* peer, int64_t* tag) {
  auto* x = static_cast<PlanExec*>(h);
  if (i < 0 || i >= static_cast<int64_t>(x->stash.size())) return -1;
  auto& s = x->stash[static_cast<size_t>(i)];
  *kind = s.kind;
  *peer = s.peer;
  *tag = s.tag;
  return static_cast<int64_t>(s.bytes.size());
}

const uint8_t* planexec_stash_data(void* h, int64_t i) {
  auto* x = static_cast<PlanExec*>(h);
  if (i < 0 || i >= static_cast<int64_t>(x->stash.size()))
    return nullptr;
  return x->stash[static_cast<size_t>(i)].bytes.data();
}

void planexec_stash_clear(void* h) {
  static_cast<PlanExec*>(h)->stash.clear();
}

}  // extern "C"
