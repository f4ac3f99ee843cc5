// OOB/RML — tagged, tree-routable TCP messaging for the control plane.
//
// The reference's out-of-band stack: oob/tcp moves framed bytes over
// sockets with a connection state machine, rml adds tagged send/recv
// on top, routed supplies the overlay tree so daemons relay messages
// they are not the destination of (SURVEY §2.2 oob/rml/routed). This
// is that stack rebuilt small and native for the TPU framework's
// multi-host coordinator: every endpoint has a listener, frames carry
// (src, dst, tag), a routing table forwards frames not addressed to
// this node (tree routing), and received frames land in a
// condition-variable-guarded queue that Python drains.
//
// The Endpoint itself lives in oob_endpoint.h (shared with the
// nativewire datapath BTLs); this file is the extern "C" control
// surface ctypes binds to.
//
// C ABI for ctypes; threads: one acceptor + one reader per connection.

#include "oob_endpoint.h"

using ompitpu::Endpoint;
using ompitpu::Frame;
using ompitpu::Header;
using ompitpu::kMagic;
using ompitpu::kMaxTtl;
using ompitpu::kNonceLen;
using ompitpu::kTagAuth;
using ompitpu::kTagChallenge;
using ompitpu::read_full_timeout;
using ompitpu::siphash24;
using ompitpu::write_full;

extern "C" {

namespace {
void fold_secret(Endpoint* ep, const uint8_t* key, int32_t len) {
  std::memset(ep->secret, 0, sizeof ep->secret);
  for (int32_t i = 0; i < len; ++i)
    ep->secret[i % 16] ^= key[i];
  ep->has_secret = len > 0;
}
}  // namespace

// Create an endpoint listening on bind_addr:port (0 = ephemeral).
// bind_addr "0.0.0.0" listens on every interface — required for the
// multi-host PLM (plm_rsh analogue) where tree peers connect across
// machines; the default remains loopback for single-host jobs.
// The secret (optional; len 0 = auth disabled) is installed BEFORE
// the listener starts accepting: installing it afterwards would leave
// a window in which connections are accepted — and trusted forever —
// without a challenge.
void* oob_create_auth(int32_t id, int port, const char* bind_addr,
                      const uint8_t* key, int32_t keylen) {
  auto* ep = new Endpoint();
  ep->id = id;
  if (key != nullptr && keylen > 0) fold_secret(ep, key, keylen);
  ep->listen_fd = ::socket(AF_INET, SOCK_STREAM, 0);
  int one = 1;
  setsockopt(ep->listen_fd, SOL_SOCKET, SO_REUSEADDR, &one, sizeof one);
  sockaddr_in addr{};
  addr.sin_family = AF_INET;
  if (bind_addr == nullptr || *bind_addr == '\0') {
    addr.sin_addr.s_addr = htonl(INADDR_LOOPBACK);
  } else if (inet_pton(AF_INET, bind_addr, &addr.sin_addr) != 1) {
    // an unparseable address must fail loudly, not silently bind
    // loopback and leave remote peers' connects refused far from
    // the cause
    ::close(ep->listen_fd);
    delete ep;
    return nullptr;
  }
  addr.sin_port = htons(static_cast<uint16_t>(port));
  if (bind(ep->listen_fd, reinterpret_cast<sockaddr*>(&addr),
           sizeof addr) != 0 ||
      listen(ep->listen_fd, 64) != 0) {
    delete ep;
    return nullptr;
  }
  socklen_t alen = sizeof addr;
  getsockname(ep->listen_fd, reinterpret_cast<sockaddr*>(&addr), &alen);
  ep->port = ntohs(addr.sin_port);
  ep->acceptor = std::thread([ep] { ep->accept_loop(); });
  return ep;
}

void* oob_create_bound(int32_t id, int port, const char* bind_addr) {
  return oob_create_auth(id, port, bind_addr, nullptr, 0);
}

// Back-compat loopback-only entry point.
void* oob_create(int32_t id, int port) {
  return oob_create_bound(id, port, "127.0.0.1");
}

int oob_port(void* h) { return static_cast<Endpoint*>(h)->port; }

// Inbound connections refused by the challenge (observability/tests).
int oob_auth_rejected(void* h) {
  return static_cast<Endpoint*>(h)->auth_rejected.load();
}

// Outbound connection to a peer's listener; answers the listener's
// auth challenge when a secret is installed, then announces our id.
int oob_connect(void* h, int32_t peer_id, const char* host, int port) {
  auto* ep = static_cast<Endpoint*>(h);
  int fd = ::socket(AF_INET, SOCK_STREAM, 0);
  sockaddr_in addr{};
  addr.sin_family = AF_INET;
  addr.sin_port = htons(static_cast<uint16_t>(port));
  inet_pton(AF_INET, host, &addr.sin_addr);
  if (connect(fd, reinterpret_cast<sockaddr*>(&addr), sizeof addr) != 0) {
    ::close(fd);
    return -1;
  }
  int one = 1;
  setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof one);
  if (ep->has_secret) {
    // the listener speaks first: challenge nonce, bounded wait (a
    // secretless listener never sends one — mismatched configs fail
    // here loudly instead of hanging)
    Header ch;
    if (!read_full_timeout(fd, &ch, sizeof ch, 10'000) ||
        ch.magic != kMagic || ch.tag != kTagChallenge ||
        ch.len != kNonceLen) {
      ::close(fd);
      return -1;
    }
    uint8_t nonce[kNonceLen];
    if (!read_full_timeout(fd, nonce, kNonceLen, 10'000)) {
      ::close(fd);
      return -1;
    }
    uint64_t mac = siphash24(ep->secret, nonce, kNonceLen);
    Header auth{kMagic, ep->id, peer_id, kTagAuth, kMaxTtl, 8};
    if (!write_full(fd, &auth, sizeof auth) ||
        !write_full(fd, &mac, 8)) {
      ::close(fd);
      return -1;
    }
  }
  Header hello{kMagic, ep->id, peer_id, -999, kMaxTtl, 0};
  if (!write_full(fd, &hello, sizeof hello)) {
    ::close(fd);
    return -1;
  }
  std::lock_guard<std::mutex> l(ep->mu);
  ep->peer_fd[peer_id] = fd;
  ep->open_fds.insert(fd);
  ep->threads.emplace_back([ep, fd] { ep->reader_loop(fd); });
  return 0;
}

// Static route: frames for dst leave via directly-connected peer `via`.
// dst == -1 installs the default route (toward the tree root).
void oob_add_route(void* h, int32_t dst, int32_t via) {
  auto* ep = static_cast<Endpoint*>(h);
  std::lock_guard<std::mutex> l(ep->mu);
  ep->route[dst] = via;
}

int oob_send(void* h, int32_t dst, int32_t tag, const uint8_t* data,
             int32_t len) {
  auto* ep = static_cast<Endpoint*>(h);
  Frame f;
  f.src = ep->id;
  f.dst = dst;
  f.tag = tag;
  f.payload.assign(data, data + len);
  if (dst == ep->id) {  // self-send: straight to the queue
    ep->deliver_or_forward(std::move(f));
    return 0;
  }
  return ep->send_frame(f) ? 0 : -1;
}

// Pop the next frame matching tag (-1 = any). Returns payload length,
// -1 on timeout, -2 if the output buffer is too small (frame stays).
int oob_recv(void* h, int32_t* src, int32_t* tag, uint8_t* out,
             int32_t maxlen, int timeout_ms) {
  auto* ep = static_cast<Endpoint*>(h);
  std::unique_lock<std::mutex> l(ep->mu);
  auto deadline = std::chrono::steady_clock::now() +
                  std::chrono::milliseconds(timeout_ms);
  for (;;) {
    for (auto it = ep->queue.begin(); it != ep->queue.end(); ++it) {
      if (*tag == -1 || it->tag == *tag) {
        if (static_cast<int32_t>(it->payload.size()) > maxlen) return -2;
        *src = it->src;
        *tag = it->tag;
        int n = static_cast<int>(it->payload.size());
        if (n) std::memcpy(out, it->payload.data(), n);
        ep->queue.erase(it);
        return n;
      }
    }
    if (ep->stopping ||
        ep->cv.wait_until(l, deadline) == std::cv_status::timeout)
      return -1;
  }
}

int oob_pending(void* h) {
  auto* ep = static_cast<Endpoint*>(h);
  std::lock_guard<std::mutex> l(ep->mu);
  return static_cast<int>(ep->queue.size());
}

// Frames dropped by the ttl cycle guard (observability for tests).
int oob_ttl_dropped(void* h) {
  return static_cast<Endpoint*>(h)->ttl_dropped.load();
}

// Wait until a frame matching tag (-1 = any) is queued; return its
// payload length without consuming it (-1 on timeout). Lets callers
// size the recv buffer exactly instead of allocating a worst case.
int oob_next_len(void* h, int32_t tag, int timeout_ms) {
  auto* ep = static_cast<Endpoint*>(h);
  std::unique_lock<std::mutex> l(ep->mu);
  auto deadline = std::chrono::steady_clock::now() +
                  std::chrono::milliseconds(timeout_ms);
  for (;;) {
    for (auto& f : ep->queue)
      if (tag == -1 || f.tag == tag)
        return static_cast<int>(f.payload.size());
    if (ep->stopping ||
        ep->cv.wait_until(l, deadline) == std::cv_status::timeout)
      return -1;
  }
}

// Liveness beats toward dst from a native thread (no interpreter, no
// GIL): one (dst, tag) frame every interval_ms carrying this
// process's resusage sample. See Endpoint::start_beats.
void oob_beats_start(void* h, int32_t dst, int32_t tag, int interval_ms) {
  static_cast<Endpoint*>(h)->start_beats(dst, tag, interval_ms);
}

void oob_beats_stop(void* h) { static_cast<Endpoint*>(h)->stop_beats(); }

void oob_destroy(void* h) { delete static_cast<Endpoint*>(h); }

}  // extern "C"
