// OOB endpoint internals, extracted from oob.cc so the datapath BTL
// translation units (btl_tcp.cc, btl_shm.cc) can speak the SAME frame
// format over the SAME sockets — a nativewire fragment is an ordinary
// OOB frame, byte-identical to one built in Python, it just never
// transits a Python bytes object on the sending side.
//
// Everything here is header-only (inline) and lives in namespace
// ompitpu; oob.cc keeps the extern "C" control-plane ABI, the BTL
// files add the extern "C" datapath ABI on top of the same Endpoint.
#ifndef OMPITPU_OOB_ENDPOINT_H_
#define OMPITPU_OOB_ENDPOINT_H_

#include <arpa/inet.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <poll.h>
#include <sys/socket.h>
#include <unistd.h>

#include <atomic>
#include <chrono>
#include <condition_variable>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <deque>
#include <map>
#include <mutex>
#include <random>
#include <set>
#include <string>
#include <thread>
#include <vector>

namespace ompitpu {

constexpr uint32_t kMagic = 0x4f4d5054;  // "OMPT"
// Hop budget: a mis-set routing table (two default routes pointing at
// each other) would otherwise relay a frame in a cycle forever.
constexpr int32_t kMaxTtl = 32;

// Control-plane authentication (the opal/mca/sec credential framework
// analogue, sec.h:79-91 `authenticate`): when a per-job secret is set,
// every INBOUND connection must answer a fresh-nonce challenge with
// SipHash-2-4(secret, nonce) before any frame it sends is accepted —
// without this, any local user could inject TAG_DIE/TAG_MIGRATE frames
// into a running job's control plane.
constexpr int32_t kTagChallenge = -998;
constexpr int32_t kTagAuth = -997;
constexpr int kNonceLen = 16;

inline uint64_t rotl64(uint64_t x, int b) {
  return (x << b) | (x >> (64 - b));
}

// SipHash-2-4 (Aumasson & Bernstein; public-domain reference
// algorithm): a keyed PRF designed for exactly this short-input
// authentication job — no crypto library dependency needed.
inline uint64_t siphash24(const uint8_t key[16], const uint8_t* in,
                          size_t inlen) {
  uint64_t k0, k1;
  std::memcpy(&k0, key, 8);
  std::memcpy(&k1, key + 8, 8);
  uint64_t v0 = 0x736f6d6570736575ULL ^ k0;
  uint64_t v1 = 0x646f72616e646f6dULL ^ k1;
  uint64_t v2 = 0x6c7967656e657261ULL ^ k0;
  uint64_t v3 = 0x7465646279746573ULL ^ k1;
  auto sipround = [&] {
    v0 += v1; v1 = rotl64(v1, 13); v1 ^= v0; v0 = rotl64(v0, 32);
    v2 += v3; v3 = rotl64(v3, 16); v3 ^= v2;
    v0 += v3; v3 = rotl64(v3, 21); v3 ^= v0;
    v2 += v1; v1 = rotl64(v1, 17); v1 ^= v2; v2 = rotl64(v2, 32);
  };
  const uint8_t* end = in + (inlen & ~size_t{7});
  for (; in != end; in += 8) {
    uint64_t m;
    std::memcpy(&m, in, 8);
    v3 ^= m;
    sipround();
    sipround();
    v0 ^= m;
  }
  uint64_t b = static_cast<uint64_t>(inlen) << 56;
  for (size_t i = 0; i < (inlen & 7); ++i)
    b |= static_cast<uint64_t>(in[i]) << (8 * i);
  v3 ^= b;
  sipround();
  sipround();
  v0 ^= b;
  v2 ^= 0xff;
  sipround();
  sipround();
  sipround();
  sipround();
  return v0 ^ v1 ^ v2 ^ v3;
}

inline bool read_full_timeout(int fd, void* buf, size_t n,
                              int timeout_ms) {
  uint8_t* p = static_cast<uint8_t*>(buf);
  while (n) {
    pollfd pfd{fd, POLLIN, 0};
    int pr = ::poll(&pfd, 1, timeout_ms);
    if (pr <= 0) return false;
    ssize_t r = ::read(fd, p, n);
    if (r <= 0) return false;
    p += r;
    n -= static_cast<size_t>(r);
  }
  return true;
}

struct Frame {
  int32_t src;
  int32_t dst;
  int32_t tag;
  int32_t ttl = kMaxTtl;
  std::vector<uint8_t> payload;
};

struct Header {
  uint32_t magic;
  int32_t src;
  int32_t dst;
  int32_t tag;
  int32_t ttl;
  uint32_t len;
} __attribute__((packed));

inline bool read_full(int fd, void* buf, size_t n) {
  uint8_t* p = static_cast<uint8_t*>(buf);
  while (n) {
    ssize_t r = ::read(fd, p, n);
    if (r <= 0) return false;
    p += r;
    n -= static_cast<size_t>(r);
  }
  return true;
}

inline bool write_full(int fd, const void* buf, size_t n) {
  const uint8_t* p = static_cast<const uint8_t*>(buf);
  while (n) {
    ssize_t r = ::write(fd, p, n);
    if (r <= 0) return false;
    p += r;
    n -= static_cast<size_t>(r);
  }
  return true;
}

struct Endpoint {
  int32_t id = -1;
  int listen_fd = -1;
  int port = 0;
  std::atomic<bool> stopping{false};
  bool has_secret = false;
  uint8_t secret[16] = {0};
  std::atomic<int> auth_rejected{0};  // refused inbound connections

  std::mutex mu;                     // guards peers/routes/queue
  std::mutex wmu;                    // serializes frame writes
  std::map<int32_t, int> peer_fd;    // directly connected peers
  std::set<int> open_fds;            // EVERY live connection fd (incl.
                                     // inbound ones not yet announced)
  std::map<int32_t, int32_t> route;  // dst -> next-hop peer
  std::deque<Frame> queue;
  std::deque<Frame> undeliverable;   // forwards awaiting a peer/route
  std::atomic<int> ttl_dropped{0};   // frames dropped at ttl 0

  // native-wire telemetry block (the tcp analogue of the shm ring
  // header counters): relaxed, always-on, bumped by wire_sendv /
  // wire_recv_frag in btl_tcp.cc. tx_* counts vectored sends (bytes =
  // payload, header excluded); rx_* counts fragments copied into a
  // reassembly buffer; rx_stalls/rx_stall_ns accumulate time
  // wire_recv_frag spent parked on the queue cv with nothing to match.
  std::atomic<uint64_t> tx_frames{0};
  std::atomic<uint64_t> tx_bytes{0};
  std::atomic<uint64_t> rx_frames{0};
  std::atomic<uint64_t> rx_bytes{0};
  std::atomic<uint64_t> rx_stalls{0};
  std::atomic<uint64_t> rx_stall_ns{0};
  std::condition_variable cv;
  std::vector<std::thread> threads;
  std::thread acceptor;

  // Liveness beats from a native thread. The reference's beat timer
  // (sensor_heartbeat.c:61) lives in the daemon's event loop, not in
  // the application; a beat sent by a Python thread needs the GIL for
  // every line it runs, and a main thread inside back-to-back
  // GIL-holding calls (bulk bytes/numpy copies, bytes.find over
  // hundreds of MiB in the native-plan provenance probe) held it off
  // past the HNP's miss window — a healthy job torn down as
  // HEARTBEAT_FAILED. This thread never touches the interpreter.
  std::thread beater;
  std::mutex beat_mu;       // guards `beating`
  std::mutex beat_join_mu;  // guards `beater` (taken before beat_mu)
  std::condition_variable beat_cv;
  bool beating = false;

  ~Endpoint() { stop(); }

  // One beat's payload: the resusage sample the HNP keeps per worker
  // (sensor_resusage feeding orte-ps), as the JSON its monitor parses.
  static std::string beat_payload() {
    unsigned long long vm_pages = 0, rss_pages = 0;
    if (FILE* f = std::fopen("/proc/self/statm", "r")) {
      if (std::fscanf(f, "%llu %llu", &vm_pages, &rss_pages) != 2)
        vm_pages = rss_pages = 0;
      std::fclose(f);
    }
    unsigned long long page =
        static_cast<unsigned long long>(::sysconf(_SC_PAGESIZE));
    char buf[128];
    int n = std::snprintf(buf, sizeof buf,
                          "{\"vmsize\": %llu, \"rss\": %llu, \"pid\": %d}",
                          vm_pages * page, rss_pages * page,
                          static_cast<int>(::getpid()));
    return std::string(buf, static_cast<size_t>(n));
  }

  // Send one (dst, tag) beat every interval_ms until stop_beats(), the
  // endpoint stops, or the link to dst is gone (a run that ended that
  // way is not restarted: there is nobody left to beat to).
  void start_beats(int32_t dst, int32_t tag, int interval_ms) {
    std::lock_guard<std::mutex> j(beat_join_mu);
    std::lock_guard<std::mutex> l(beat_mu);
    if (beating || beater.joinable() || stopping) return;
    beating = true;
    beater = std::thread([this, dst, tag, interval_ms] {
      std::unique_lock<std::mutex> bl(beat_mu);
      while (beating) {
        beat_cv.wait_for(bl, std::chrono::milliseconds(interval_ms));
        if (!beating) break;
        bl.unlock();
        Frame f;
        f.src = id;
        f.dst = dst;
        f.tag = tag;
        std::string p = beat_payload();
        f.payload.assign(p.begin(), p.end());
        bool sent = send_frame(f);
        bl.lock();
        if (!sent) beating = false;  // lifeline gone: teardown follows
      }
    });
  }

  void stop_beats() {
    std::lock_guard<std::mutex> j(beat_join_mu);
    {
      std::lock_guard<std::mutex> l(beat_mu);
      beating = false;
    }
    beat_cv.notify_all();
    if (beater.joinable()) beater.join();
  }

  void stop() {
    if (stopping.exchange(true)) return;
    if (listen_fd >= 0) {
      ::shutdown(listen_fd, SHUT_RDWR);
      ::close(listen_fd);
    }
    {
      // shutdown (not close) every connection fd — including inbound
      // ones whose announce frame never arrived; each reader_loop
      // unblocks, deregisters, and closes its own fd, so no fd is
      // closed twice and no reader blocks forever in read()
      std::lock_guard<std::mutex> l(mu);
      for (int fd : open_fds) ::shutdown(fd, SHUT_RDWR);
    }
    stop_beats();  // after the shutdown: a beat parked in write() fails
    cv.notify_all();
    if (acceptor.joinable()) acceptor.join();
    for (auto& t : threads)
      if (t.joinable()) t.join();
  }

  int next_hop_fd(int32_t dst) {
    std::lock_guard<std::mutex> l(mu);
    auto it = peer_fd.find(dst);
    if (it != peer_fd.end()) return it->second;
    auto r = route.find(dst);
    if (r != route.end()) {
      auto h = peer_fd.find(r->second);
      if (h != peer_fd.end()) return h->second;
    }
    auto d = route.find(-1);  // default route (toward the root)
    if (d != route.end()) {
      auto h = peer_fd.find(d->second);
      if (h != peer_fd.end()) return h->second;
    }
    return -1;
  }

  bool send_frame(const Frame& f) {
    int fd = next_hop_fd(f.dst);
    if (fd < 0) return false;
    Header h{kMagic, f.src, f.dst, f.tag, f.ttl,
             static_cast<uint32_t>(f.payload.size())};
    std::lock_guard<std::mutex> l(wmu);  // serialize frame writes
    if (!write_full(fd, &h, sizeof h)) return false;
    return f.payload.empty() ||
           write_full(fd, f.payload.data(), f.payload.size());
  }

  void deliver_or_forward(Frame&& f, bool spend_ttl = true) {
    if (f.dst == id || f.dst == -1) {
      std::lock_guard<std::mutex> l(mu);
      queue.push_back(std::move(f));
      cv.notify_all();
      return;
    }
    // relay hop: spend one ttl unit; at zero the frame dies here
    // (cycle guard — see kMaxTtl). Retries from the undeliverable
    // queue already paid for this hop (spend_ttl=false).
    if (spend_ttl && --f.ttl <= 0) {
      ttl_dropped.fetch_add(1);
      return;
    }
    if (!send_frame(f)) {
      // tree relay (routed analogue); a frame can arrive before the
      // next hop has announced itself — hold it until a peer registers
      std::lock_guard<std::mutex> l(mu);
      undeliverable.push_back(std::move(f));
    }
  }

  void flush_undeliverable() {
    std::deque<Frame> retry;
    {
      std::lock_guard<std::mutex> l(mu);
      retry.swap(undeliverable);
    }
    for (auto& f : retry) deliver_or_forward(std::move(f), false);
  }

  // Pre-auth gate for an inbound connection: the FIRST frame must be
  // the 8-byte SipHash of the challenge nonce. Header and MAC are
  // read with a deadline and a hard length bound — an attacker must
  // not be able to park a reader thread forever or make it allocate
  // an arbitrary h.len before proving knowledge of the secret.
  bool authenticate_inbound(int fd, const std::vector<uint8_t>& nonce) {
    Header h;
    if (!read_full_timeout(fd, &h, sizeof h, 10'000) ||
        h.magic != kMagic || h.tag != kTagAuth || h.len != 8) {
      auth_rejected.fetch_add(1);
      return false;
    }
    uint64_t got;
    if (!read_full_timeout(fd, &got, 8, 10'000)) {
      auth_rejected.fetch_add(1);
      return false;
    }
    uint64_t want = siphash24(secret, nonce.data(), nonce.size());
    if (got != want) {
      auth_rejected.fetch_add(1);
      return false;
    }
    return true;
  }

  // nonce non-empty = inbound connection that must authenticate
  // before any frame it sends is processed — a well-formed
  // announce/data frame from an unauthenticated peer is refused,
  // never queued.
  void reader_loop(int fd, std::vector<uint8_t> nonce = {}) {
    bool authed = nonce.empty() || authenticate_inbound(fd, nonce);
    while (authed) {
      Header h;
      if (!read_full(fd, &h, sizeof h) || h.magic != kMagic) break;
      Frame f;
      f.src = h.src;
      f.dst = h.dst;
      f.tag = h.tag;
      f.ttl = h.ttl;
      f.payload.resize(h.len);
      if (h.len && !read_full(fd, f.payload.data(), h.len)) break;
      // first frame on an inbound connection announces the peer id
      if (h.tag == -999) {
        {
          std::lock_guard<std::mutex> l(mu);
          peer_fd[h.src] = fd;
        }
        flush_undeliverable();
        continue;
      }
      deliver_or_forward(std::move(f));
    }
    // connection over: deregister and close OUR fd exactly once (a
    // disconnected peer must not linger in peer_fd, and stop() must
    // not double-close it)
    {
      std::lock_guard<std::mutex> l(mu);
      open_fds.erase(fd);
      for (auto it = peer_fd.begin(); it != peer_fd.end();) {
        if (it->second == fd)
          it = peer_fd.erase(it);
        else
          ++it;
      }
    }
    ::close(fd);
  }

  void accept_loop() {
    std::random_device rd;
    for (;;) {
      int fd = ::accept(listen_fd, nullptr, nullptr);
      if (fd < 0) return;  // listener closed
      int one = 1;
      setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof one);
      std::vector<uint8_t> nonce;
      if (has_secret) {
        // fresh per-connection nonce: replaying a captured response
        // cannot authenticate a new connection
        nonce.resize(kNonceLen);
        for (int i = 0; i < kNonceLen; i += 4) {
          uint32_t r = rd();
          std::memcpy(nonce.data() + i, &r, 4);
        }
        Header ch{kMagic, id, -1, kTagChallenge, kMaxTtl,
                  static_cast<uint32_t>(nonce.size())};
        if (!write_full(fd, &ch, sizeof ch) ||
            !write_full(fd, nonce.data(), nonce.size())) {
          ::close(fd);
          continue;
        }
      }
      std::lock_guard<std::mutex> l(mu);
      if (stopping) {
        // stop() already swept open_fds; registering now would leave
        // a reader blocked forever — drop the connection instead
        ::close(fd);
        return;
      }
      open_fds.insert(fd);
      threads.emplace_back(
          [this, fd, nonce] { reader_loop(fd, nonce); });
    }
  }
};

}  // namespace ompitpu

#endif  // OMPITPU_OOB_ENDPOINT_H_
