// nativewire shared-memory datapath — single-producer single-consumer
// byte rings over POSIX shm for co-hosted ranks.
//
// The reference's btl/sm moves eager fragments through per-peer FIFOs
// in a mapped segment instead of the loopback TCP stack; this is that
// idea for the TPU framework's tpurun worker processes. Each DIRECTED
// (producer -> consumer) pair gets its own ring, and a peer pair
// stripes lanes across a small slot set (slot = tag % nslots), so one
// bulk lane can never head-of-line-block another lane's ring — the
// shm analogue of the QoS lane striping the TCP path already does.
//
// Ring layout (one shm object):
//   [128-byte header][capacity bytes of ring data]
//   header: u64 magic, u64 capacity, u64 widx, u64 ridx,
//           i64 producer_pid, i64 consumer_pid,
//           then the telemetry block (see RingHdr)
// widx/ridx are MONOTONIC byte counters (offset = idx % capacity);
// they are only ever written by their owning side, with release
// stores paired against acquire loads on the other side — the
// classic SPSC discipline, no locks in the byte path.
//
// Records: [u32 payload_len][i32 tag][payload], byte-wrapped (no
// padding); the payload of a fragment record is EXACTLY the frame
// payload the TCP path would carry (SGC2 prefix + bytes), so the
// byte-identity contract holds across both native transports.
//
// Fault model: same-host liveness is authoritative — kill(pid, 0)
// answering ESRCH means the peer is GONE, not slow. Both blocking
// entry points poll the counterpart pid and return -3 so Python can
// raise the PR 9 typed error (ERR_PROC_FAILED) instead of wedging on
// a ring that will never drain.

#include <errno.h>
#include <fcntl.h>
#include <signal.h>
#include <sys/mman.h>
#include <sys/stat.h>
#include <unistd.h>

#include <atomic>
#include <chrono>
#include <cstdint>
#include <cstring>
#include <thread>

#include "crc32.h"
#include "nativeev.h"

namespace {

// v2: the header grew a telemetry block, which moves the data offset
// — a v1 peer interpreting v2 bytes would corrupt frames, so the
// magic changes with the layout. Safe across the fleet because every
// rank builds the .so from the same sources (bindings stamp-check).
constexpr uint64_t kRingMagic = 0x6f6d707473687232ULL;  // "omptshr2"
constexpr size_t kHdrSize = 128;
constexpr size_t kRecHdr = 8;  // u32 len + i32 tag
constexpr size_t kSgPrefix = 4 + 8 + 8;  // "SGC2" + xfer + idx

struct RingHdr {
  uint64_t magic;
  uint64_t capacity;
  uint64_t widx;
  uint64_t ridx;
  int64_t producer_pid;
  int64_t consumer_pid;
  // telemetry block — always-on relaxed counters, each field written
  // by exactly one side (SPSC carries over), read by anyone. w_* and
  // hwm belong to the producer, r_* to the consumer. Bytes count
  // record payloads (the fragment bytes Python used to count), hwm is
  // the occupancy high-water mark in ring bytes, stall_ns accumulates
  // time spent blocked in the Deadline wait loops.
  uint64_t w_frames;
  uint64_t w_bytes;
  uint64_t w_stalls;
  uint64_t w_stall_ns;
  uint64_t hwm;
  uint64_t r_frames;
  uint64_t r_bytes;
  uint64_t r_stalls;
  uint64_t r_stall_ns;
};
static_assert(sizeof(RingHdr) <= kHdrSize, "ring header grew");

struct ShmRing {
  uint8_t* map = nullptr;
  uint64_t cap = 0;
  bool creator = false;
  // Producer-side OPEN stall (process-local, not in the shared
  // header). Both callers of shmring_writev wait on a full ring in
  // short slices — between them they take their own arrivals off
  // their inbound rings so opposing full-ring senders cannot deadlock
  // — and then retry the SAME record (the ring is SPSC and in order:
  // nothing else can be written first). A -1 return therefore leaves
  // the stall open and the retry resumes it: one blocked record is
  // one w_stalls count however many slices it took, and w_stall_ns
  // and the event record's waited-ns run from the first full-ring
  // sighting to the write, the caller's time between slices included.
  bool w_stall_open = false;
  std::chrono::steady_clock::time_point w_stall_t0;    // first sighting
  std::chrono::steady_clock::time_point w_stall_mark;  // credited up to
};

inline RingHdr* hdr(ShmRing* r) {
  return reinterpret_cast<RingHdr*>(r->map);
}
inline uint8_t* data(ShmRing* r) { return r->map + kHdrSize; }

inline uint64_t load_acq(uint64_t* p) {
  return __atomic_load_n(p, __ATOMIC_ACQUIRE);
}
inline void store_rel(uint64_t* p, uint64_t v) {
  __atomic_store_n(p, v, __ATOMIC_RELEASE);
}

// telemetry: each counter has a single writer, so load+store relaxed
// is enough — no RMW, no fence, unmeasurable next to the memcpy
inline uint64_t load_rlx(uint64_t* p) {
  return __atomic_load_n(p, __ATOMIC_RELAXED);
}
inline void bump_rlx(uint64_t* p, uint64_t v) {
  __atomic_store_n(p, __atomic_load_n(p, __ATOMIC_RELAXED) + v,
                   __ATOMIC_RELAXED);
}
inline void max_rlx(uint64_t* p, uint64_t v) {
  if (v > __atomic_load_n(p, __ATOMIC_RELAXED))
    __atomic_store_n(p, v, __ATOMIC_RELAXED);
}

inline uint64_t ns_between(std::chrono::steady_clock::time_point a,
                           std::chrono::steady_clock::time_point b) {
  return static_cast<uint64_t>(
      std::chrono::duration_cast<std::chrono::nanoseconds>(b - a).count());
}

// one blocked wait = one stall; construct when the fast check fails,
// settle() once on the way out (every exit path, including errors)
struct StallTimer {
  uint64_t* count;
  uint64_t* ns;
  std::chrono::steady_clock::time_point t0;
  bool armed = false;
  StallTimer(uint64_t* c, uint64_t* n) : count(c), ns(n) {}
  void arm() {
    if (armed) return;
    armed = true;
    t0 = std::chrono::steady_clock::now();
    bump_rlx(count, 1);
  }
  uint64_t settle() {
    if (!armed) return 0;
    armed = false;
    uint64_t w = ns_between(t0, std::chrono::steady_clock::now());
    bump_rlx(ns, w);
    return w;
  }
};

inline bool pid_dead(int64_t pid) {
  // pid 0 = counterpart not attached yet: still coming up, not dead
  return pid > 0 && ::kill(static_cast<pid_t>(pid), 0) != 0 &&
         errno == ESRCH;
}

// modular copies between the ring and linear buffers
void ring_put(ShmRing* r, uint64_t pos, const uint8_t* src, size_t n) {
  uint64_t off = pos % r->cap;
  size_t first = static_cast<size_t>(
      n < r->cap - off ? n : r->cap - off);
  std::memcpy(data(r) + off, src, first);
  if (n > first) std::memcpy(data(r), src + first, n - first);
}

void ring_get(ShmRing* r, uint64_t pos, uint8_t* dst, size_t n) {
  uint64_t off = pos % r->cap;
  size_t first = static_cast<size_t>(
      n < r->cap - off ? n : r->cap - off);
  std::memcpy(dst, data(r) + off, first);
  if (n > first) std::memcpy(dst + first, data(r), n - first);
}

inline uint64_t be64(const uint8_t* p) {
  uint64_t v = 0;
  for (int i = 0; i < 8; ++i) v = (v << 8) | p[i];
  return v;
}

inline void put_be64(uint8_t* p, uint64_t v) {
  for (int i = 7; i >= 0; --i, v >>= 8) p[i] = static_cast<uint8_t>(v);
}

// Peek the SGC2 prefix out of a scatter-gather list (the event ring
// wants xfer/idx and the producer only has the iovec). True iff the
// payload starts with a full prefix.
bool sg_peek(const uint8_t** parts, const int64_t* lens,
             int32_t nparts, uint64_t* xfer, uint64_t* idx) {
  uint8_t pre[kSgPrefix];
  size_t got = 0;
  for (int32_t i = 0; i < nparts && got < kSgPrefix; ++i) {
    size_t take = static_cast<size_t>(lens[i]);
    if (take > kSgPrefix - got) take = kSgPrefix - got;
    std::memcpy(pre + got, parts[i], take);
    got += take;
  }
  if (got < kSgPrefix || std::memcmp(pre, "SGC2", 4) != 0)
    return false;
  *xfer = be64(pre + 4);
  *idx = be64(pre + 12);
  return true;
}

struct Deadline {
  std::chrono::steady_clock::time_point t;
  explicit Deadline(int timeout_ms)
      : t(std::chrono::steady_clock::now() +
          std::chrono::milliseconds(timeout_ms)) {}
  bool expired() const { return std::chrono::steady_clock::now() >= t; }
};

inline void ring_nap() {
  // short sleep, not sched_yield: rings pair with device work, a
  // spinning consumer would steal the XLA threads' cores
  std::this_thread::sleep_for(std::chrono::microseconds(50));
}

ShmRing* map_ring(int fd, uint64_t total, bool creator) {
  void* m = ::mmap(nullptr, total, PROT_READ | PROT_WRITE, MAP_SHARED,
                   fd, 0);
  ::close(fd);  // mapping keeps the object alive
  if (m == MAP_FAILED) return nullptr;
  auto* r = new ShmRing();
  r->map = static_cast<uint8_t*>(m);
  r->cap = total - kHdrSize;
  r->creator = creator;
  return r;
}

// Producer core of shmring_writev and shmring_write_msg: append one
// record whose payload is the concatenation of the scatter-gather
// parts, waiting on a full ring until `dl`. Return codes as
// shmring_writev's.
int put_record(ShmRing* r, int32_t tag, const uint8_t** parts,
               const int64_t* lens, int32_t nparts, const Deadline& dl) {
  RingHdr* h = hdr(r);
  uint64_t plen = 0;
  for (int32_t i = 0; i < nparts; ++i)
    plen += static_cast<uint64_t>(lens[i]);
  uint64_t total = kRecHdr + plen;
  if (total > r->cap) return -2;
  // credit the open stall's time since the last credit to w_stall_ns
  auto credit = [r, h] {
    auto now = std::chrono::steady_clock::now();
    bump_rlx(&h->w_stall_ns, ns_between(r->w_stall_mark, now));
    r->w_stall_mark = now;
  };
  uint64_t w = h->widx;  // we are the only writer
  for (;;) {
    uint64_t used = w - load_acq(&h->ridx);
    if (r->cap - used >= total) break;
    if (!r->w_stall_open) {  // ring full: this record is one stall
      r->w_stall_open = true;  // until it is written (see ShmRing)
      r->w_stall_t0 = r->w_stall_mark = std::chrono::steady_clock::now();
      bump_rlx(&h->w_stalls, 1);
    }
    if (pid_dead(h->consumer_pid)) {
      credit();
      r->w_stall_open = false;
      return -3;
    }
    if (dl.expired()) {
      credit();
      return -1;  // the stall stays open: the caller retries
    }
    ring_nap();
  }
  uint64_t waited = 0;
  if (r->w_stall_open) {
    credit();
    r->w_stall_open = false;
    waited = ns_between(r->w_stall_t0, r->w_stall_mark);
  }
  uint8_t rec[kRecHdr];
  uint32_t l32 = static_cast<uint32_t>(plen);
  std::memcpy(rec, &l32, 4);
  std::memcpy(rec + 4, &tag, 4);
  ring_put(r, w, rec, kRecHdr);
  uint64_t pos = w + kRecHdr;
  for (int32_t i = 0; i < nparts; ++i) {
    ring_put(r, pos, parts[i], static_cast<size_t>(lens[i]));
    pos += static_cast<uint64_t>(lens[i]);
  }
  store_rel(&h->widx, w + total);
  bump_rlx(&h->w_frames, 1);
  bump_rlx(&h->w_bytes, plen);
  max_rlx(&h->hwm, (w + total) - load_acq(&h->ridx));
  uint64_t xfer, idx;
  if (sg_peek(parts, lens, nparts, &xfer, &idx))
    ompitpu::nativeev_emit(
        tag, xfer,
        static_cast<uint32_t>(plen - kSgPrefix),
        static_cast<uint32_t>(idx), /*recv_side=*/false, waited);
  return 0;
}

// Copy out of the ring and checksum what was copied, block by block,
// so the CRC reads each byte while the copy has it in cache.
uint32_t ring_get_crc(ShmRing* r, uint64_t pos, uint8_t* dst, size_t n,
                      uint32_t crc) {
  constexpr size_t kBlock = 32 * 1024;
  while (n) {
    size_t b = n < kBlock ? n : kBlock;
    ring_get(r, pos, dst, b);
    crc = ompitpu::crc32_update(crc, dst, b);
    pos += b;
    dst += b;
    n -= b;
  }
  return crc;
}

// Consumer core of shmring_read_frag and shmring_read_msg: pop the
// head record IF it is an SGC2 fragment of transfer `xfer` on `tag`,
// copying its payload straight into the reassembly buffer, waiting on
// an empty ring until `dl`. Return codes as shmring_read_frag's.
// `crc` (may be null) is the transfer's running checksum: crc[0] the
// CRC-32 of fragments 0 .. crc[1]-1; a fragment that arrives in that
// order is checksummed inside its copy, any other sets crc[1] to -1
// (the caller then checks the whole buffer at the end).
int64_t take_frag(ShmRing* r, int32_t tag, int64_t xfer, int64_t nchunks,
                  int64_t chunk, uint8_t* base, int64_t nbytes,
                  const Deadline& dl, int64_t* crc) {
  RingHdr* h = hdr(r);
  StallTimer stall(&h->r_stalls, &h->r_stall_ns);
  uint64_t rd = h->ridx;  // we are the only reader
  for (;;) {
    if (load_acq(&h->widx) != rd) break;
    stall.arm();  // ring empty: this read is a stall until data lands
    if (pid_dead(h->producer_pid)) {
      stall.settle();
      return -3;
    }
    if (dl.expired()) {
      stall.settle();
      return -1;
    }
    ring_nap();
  }
  uint64_t waited = stall.settle();
  uint8_t rec[kRecHdr];
  ring_get(r, rd, rec, kRecHdr);
  uint32_t plen;
  int32_t rtag;
  std::memcpy(&plen, rec, 4);
  std::memcpy(&rtag, rec + 4, 4);
  if (rtag != tag) return -5;
  uint64_t next = rd + kRecHdr + plen;
  auto consume = [h, next, plen] {
    store_rel(&h->ridx, next);
    bump_rlx(&h->r_frames, 1);
    bump_rlx(&h->r_bytes, plen);
  };
  uint8_t pre[kSgPrefix];
  if (plen >= kSgPrefix) ring_get(r, rd + kRecHdr, pre, kSgPrefix);
  if (plen < kSgPrefix || std::memcmp(pre, "SGC2", 4) != 0 ||
      be64(pre + 4) != static_cast<uint64_t>(xfer)) {
    consume();
    return -4;
  }
  int64_t idx = static_cast<int64_t>(be64(pre + 12));
  int64_t flen = static_cast<int64_t>(plen - kSgPrefix);
  if (idx < 0 || idx >= nchunks || idx * chunk + flen > nbytes) {
    consume();
    return -2;
  }
  bool chained = crc && crc[1] == idx;
  uint64_t from = rd + kRecHdr + kSgPrefix;
  if (chained) {
    crc[0] = ring_get_crc(r, from, base + idx * chunk,
                          static_cast<size_t>(flen),
                          static_cast<uint32_t>(crc[0]));
    crc[1] = idx + 1;
  } else {
    if (flen)
      ring_get(r, from, base + idx * chunk, static_cast<size_t>(flen));
    if (crc) crc[1] = -1;
  }
  consume();
  ompitpu::nativeev_emit(tag, static_cast<uint64_t>(xfer),
                         static_cast<uint32_t>(flen),
                         static_cast<uint32_t>(idx),
                         /*recv_side=*/true, waited);
  return idx;
}

}  // namespace

extern "C" {

// Create (O_CREAT|O_EXCL) a ring named `name` (leading '/', per
// shm_open) with `capacity` data bytes and stamp ourselves producer.
// NULL when the name exists already or the mapping failed.
void* shmring_create(const char* name, int64_t capacity,
                     int64_t producer_pid) {
  if (capacity < static_cast<int64_t>(kRecHdr) * 2) return nullptr;
  int fd = ::shm_open(name, O_CREAT | O_EXCL | O_RDWR, 0600);
  if (fd < 0) return nullptr;
  uint64_t total = kHdrSize + static_cast<uint64_t>(capacity);
  if (::ftruncate(fd, static_cast<off_t>(total)) != 0) {
    ::close(fd);
    ::shm_unlink(name);
    return nullptr;
  }
  ShmRing* r = map_ring(fd, total, true);
  if (!r) {
    ::shm_unlink(name);
    return nullptr;
  }
  RingHdr* h = hdr(r);
  h->capacity = static_cast<uint64_t>(capacity);
  h->widx = 0;
  h->ridx = 0;
  h->producer_pid = producer_pid;
  h->consumer_pid = 0;
  // telemetry block starts zeroed (ftruncate guarantees it; be
  // explicit so a future re-create-in-place stays correct)
  h->w_frames = h->w_bytes = h->w_stalls = h->w_stall_ns = 0;
  h->hwm = 0;
  h->r_frames = h->r_bytes = h->r_stalls = h->r_stall_ns = 0;
  // magic LAST (release): an attacher seeing the magic sees a fully
  // initialized header
  __atomic_store_n(&h->magic, kRingMagic, __ATOMIC_RELEASE);
  return r;
}

// Attach an existing ring; stamp ourselves consumer when
// consumer_pid > 0. NULL when absent / not yet initialized.
void* shmring_attach(const char* name, int64_t consumer_pid) {
  int fd = ::shm_open(name, O_RDWR, 0600);
  if (fd < 0) return nullptr;
  struct stat st{};
  if (::fstat(fd, &st) != 0 ||
      static_cast<size_t>(st.st_size) <= kHdrSize) {
    ::close(fd);
    return nullptr;
  }
  ShmRing* r = map_ring(fd, static_cast<uint64_t>(st.st_size), false);
  if (!r) return nullptr;
  RingHdr* h = hdr(r);
  if (__atomic_load_n(&h->magic, __ATOMIC_ACQUIRE) != kRingMagic ||
      h->capacity != r->cap) {
    ::munmap(r->map, r->cap + kHdrSize);
    delete r;
    return nullptr;
  }
  if (consumer_pid > 0) h->consumer_pid = consumer_pid;
  return r;
}

int shmring_unlink(const char* name) { return ::shm_unlink(name); }

void shmring_close(void* vr) {
  auto* r = static_cast<ShmRing*>(vr);
  ::munmap(r->map, r->cap + kHdrSize);
  delete r;
}

int64_t shmring_capacity(void* vr) {
  return static_cast<int64_t>(static_cast<ShmRing*>(vr)->cap);
}

int64_t shmring_producer_pid(void* vr) {
  return hdr(static_cast<ShmRing*>(vr))->producer_pid;
}

int64_t shmring_consumer_pid(void* vr) {
  return hdr(static_cast<ShmRing*>(vr))->consumer_pid;
}

// Bytes currently queued (tests/observability).
int64_t shmring_pending(void* vr) {
  auto* r = static_cast<ShmRing*>(vr);
  RingHdr* h = hdr(r);
  return static_cast<int64_t>(load_acq(&h->widx) - load_acq(&h->ridx));
}

// Telemetry block reader. Indices:
//   0 w_frames  1 w_bytes  2 w_stalls  3 w_stall_ns  4 hwm (bytes)
//   5 r_frames  6 r_bytes  7 r_stalls  8 r_stall_ns
// -1 for an unknown index. Reads are relaxed — the block is
// monotonic diagnostics, not synchronization.
int64_t shmring_stat(void* vr, int32_t which) {
  RingHdr* h = hdr(static_cast<ShmRing*>(vr));
  uint64_t* fields[] = {&h->w_frames, &h->w_bytes,   &h->w_stalls,
                        &h->w_stall_ns, &h->hwm,     &h->r_frames,
                        &h->r_bytes,  &h->r_stalls,  &h->r_stall_ns};
  if (which < 0 || which >= static_cast<int32_t>(
                                sizeof(fields) / sizeof(fields[0])))
    return -1;
  return static_cast<int64_t>(load_rlx(fields[which]));
}

// Producer side: append one record whose payload is the concatenation
// of the scatter-gather parts. 0 on success, -1 timeout (ring full;
// the stall stays open for the caller's retry of this record, see
// ShmRing — a caller that gives the record up instead leaves it open
// until the next write), -2 record can never fit (caller must route
// via TCP), -3 consumer process is gone.
int shmring_writev(void* vr, int32_t tag, const uint8_t** parts,
                   const int64_t* lens, int32_t nparts,
                   int timeout_ms) {
  return put_record(static_cast<ShmRing*>(vr), tag, parts, lens, nparts,
                    Deadline(timeout_ms));
}

// Producer side, a message's payload in one call: append the SGC2
// fragment records `first` .. nchunks-1 of transfer `xfer` (fragment
// i = "SGC2" + xfer + i + payload[i*chunk : (i+1)*chunk], the records
// btl/components.FrameTemplate.sg_lists composes), all inside one
// wait of `timeout_ms`. Returns how many fragments went in; *status
// says why it stopped: 0 the last one is in, else shmring_writev's
// code for the fragment it stopped at (-1: the ring stayed full for
// the rest of the slice — the caller looks at its own inbound rings
// and calls again from that fragment, its stall still open).
int64_t shmring_write_msg(void* vr, int32_t tag, int64_t xfer,
                          const uint8_t* payload, int64_t nbytes,
                          int64_t chunk, int64_t first, int64_t nchunks,
                          int timeout_ms, int32_t* status) {
  auto* r = static_cast<ShmRing*>(vr);
  Deadline dl(timeout_ms);
  uint8_t pre[kSgPrefix];
  std::memcpy(pre, "SGC2", 4);
  put_be64(pre + 4, static_cast<uint64_t>(xfer));
  int64_t n = 0;
  *status = 0;
  for (int64_t idx = first; idx < nchunks; ++idx, ++n) {
    put_be64(pre + 12, static_cast<uint64_t>(idx));
    int64_t off = idx * chunk;
    int64_t flen = nbytes - off < chunk ? nbytes - off : chunk;
    if (flen < 0) flen = 0;
    const uint8_t* parts[2] = {pre, payload + off};
    const int64_t lens[2] = {static_cast<int64_t>(kSgPrefix), flen};
    int rc = put_record(r, tag, parts, lens, 2, dl);
    if (rc != 0) {
      *status = rc;
      break;
    }
  }
  return n;
}

// Consumer side, fragment fast path: pop the head record IF it is an
// SGC2 fragment of transfer `xfer` on `tag`, copying its payload
// straight into the reassembly buffer. Returns the fragment index, or
//   -1 timeout   -2 malformed/overrun (consumed)   -3 producer dead
//   -4 same-tag stale fragment (consumed + dropped, like the portable
//      path's want-prefix filter)
//   -5 head record carries a DIFFERENT tag (left; pop via
//      shmring_read_into and stash it)
int64_t shmring_read_frag(void* vr, int32_t tag, int64_t xfer,
                          int64_t nchunks, int64_t chunk, uint8_t* base,
                          int64_t nbytes, int timeout_ms) {
  return take_frag(static_cast<ShmRing*>(vr), tag, xfer, nchunks, chunk,
                   base, nbytes, Deadline(timeout_ms), nullptr);
}

// Consumer side, a message's payload in one call: land up to `want`
// fragments of (tag, xfer) at base + idx * chunk, all inside one wait
// of `timeout_ms`; stale same-tag fragments are dropped on the way.
// Returns how many landed; *status says why it stopped: 0 all `want`
// are in, else shmring_read_frag's code for what the caller has to
// handle (-1 nothing more came in this slice, -2, -3, -5). `crc` is
// the transfer's running checksum across calls (see take_frag): the
// caller starts it at {0, 0} and, once every fragment is in, holds
// crc[0] against the header's CRC if crc[1] == nchunks.
int64_t shmring_read_msg(void* vr, int32_t tag, int64_t xfer,
                         int64_t nchunks, int64_t chunk, uint8_t* base,
                         int64_t nbytes, int64_t want, int timeout_ms,
                         int64_t* crc, int32_t* status) {
  auto* r = static_cast<ShmRing*>(vr);
  Deadline dl(timeout_ms);
  int64_t n = 0;
  *status = 0;
  while (n < want) {
    int64_t rc = take_frag(r, tag, xfer, nchunks, chunk, base, nbytes,
                           dl, crc);
    if (rc >= 0) {
      ++n;
    } else if (rc != -4) {
      *status = static_cast<int32_t>(rc);
      break;
    }
  }
  return n;
}

// Consumer side, generic pop: copy the head record's payload into
// `out` and report its tag. Returns payload length, -1 timeout,
// -2 out buffer too small (record stays), -3 producer dead.
int64_t shmring_read_into(void* vr, int32_t* tag, uint8_t* out,
                          int64_t maxlen, int timeout_ms) {
  auto* r = static_cast<ShmRing*>(vr);
  RingHdr* h = hdr(r);
  Deadline dl(timeout_ms);
  StallTimer stall(&h->r_stalls, &h->r_stall_ns);
  uint64_t rd = h->ridx;
  for (;;) {
    if (load_acq(&h->widx) != rd) break;
    stall.arm();
    if (pid_dead(h->producer_pid)) {
      stall.settle();
      return -3;
    }
    if (dl.expired()) {
      stall.settle();
      return -1;
    }
    ring_nap();
  }
  stall.settle();
  uint8_t rec[kRecHdr];
  ring_get(r, rd, rec, kRecHdr);
  uint32_t plen;
  std::memcpy(&plen, rec, 4);
  std::memcpy(tag, rec + 4, 4);
  if (static_cast<int64_t>(plen) > maxlen) return -2;
  if (plen) ring_get(r, rd + kRecHdr, out, plen);
  store_rel(&h->ridx, rd + kRecHdr + plen);
  bump_rlx(&h->r_frames, 1);
  bump_rlx(&h->r_bytes, plen);
  return static_cast<int64_t>(plen);
}

}  // extern "C"
