// dfupload — native HTTP upload server: the serving end of the piece hop.
//
// The reference's upload server is compiled-native Go
// (client/daemon/upload/upload_manager.go:149-196 — GET
// /download/{prefix}/{task_id} with Range or pieceNum). This is our C++
// equivalent: worker threads accept keep-alive connections, parse the
// request line + Range header, look the piece window up in a registry fed
// by Python as pieces land, and sendfile() the bytes straight from the
// page cache — zero Python on the serving path, pairing with dfhttp.cc on
// the receiving end so a piece hop never surfaces into either daemon's
// interpreter.
//
// Python keeps everything policy-shaped: TLS/mTLS and rate-limited serving
// stay on the aiohttp implementation (daemon/upload.py), which also
// documents the HTTP contract this server mirrors: pieceNum → 200,
// Range → 206, unknown task/piece → 404, uncovered range → 416, over
// concurrency cap → 429.

#include <atomic>
#include <condition_variable>
#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <deque>
#include <mutex>
#include <string>
#include <thread>
#include <unordered_map>
#include <unordered_set>
#include <vector>

#include <errno.h>
#include <fcntl.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <poll.h>
#include <sys/sendfile.h>
#include <sys/socket.h>
#include <sys/stat.h>
#include <time.h>
#include <unistd.h>
#include <arpa/inet.h>

namespace {

constexpr size_t HEAD_MAX = 16 << 10;

long env_seconds(const char* name, long dflt) {
  const char* v = getenv(name);
  if (!v || !*v) return dflt;
  errno = 0;
  char* end = nullptr;
  long n = strtol(v, &end, 10);
  return (errno || *end || n <= 0) ? dflt : n;
}

struct PieceEnt {
  uint64_t offset;
  uint64_t size;
};

struct TaskEnt {
  std::string data_path;
  int64_t content_length = -1;
  uint64_t piece_size = 0;
  std::unordered_map<uint32_t, PieceEnt> pieces;
};

// One finished send, for the daemon's flight ring: Python drains these
// (df_upload_drain) and stamps an upload_serve event each. Times are
// CLOCK_MONOTONIC, which is Python's time.perf_counter() on Linux.
struct ServeRec {
  std::string task_id;
  int64_t piece;      // -1: a Range request
  uint64_t bytes;
  int64_t end_ns;     // the send's last byte written
  int64_t send_us;    // header out -> last byte written
  int64_t wait_us;    // the connection's wait for a worker (its first request)
};

constexpr size_t SERVE_LOG_MAX = 8192;

int64_t mono_ns() {
  struct timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return (int64_t)ts.tv_sec * 1000000000LL + ts.tv_nsec;
}

struct Pending {
  int fd;
  int64_t accepted_ns;
};

struct Server {
  int listen_fd = -1;
  int port = 0;
  std::atomic<bool> stopping{false};
  std::vector<std::thread> workers;
  std::thread acceptor;

  std::mutex queue_mu;
  std::condition_variable queue_cv;
  std::deque<Pending> pending;  // accepted fds awaiting a worker
  size_t max_queue = 128;

  int concurrent_limit = 0;  // 0 = unlimited; over → 429
  std::atomic<int> active{0};

  std::mutex conns_mu;
  std::unordered_set<int> conns;  // live connection fds, for fast shutdown

  std::mutex reg_mu;
  std::unordered_map<std::string, TaskEnt> tasks;

  std::atomic<uint64_t> bytes_served{0};
  std::atomic<uint64_t> ok{0};
  std::atomic<uint64_t> not_found{0};     // unknown task / route / data gone
  std::atomic<uint64_t> piece_missing{0}; // known task, absent piece / 416
  std::atomic<uint64_t> throttled{0};
  std::atomic<uint64_t> bad_request{0};

  std::mutex log_mu;
  std::deque<ServeRec> serve_log;  // newest SERVE_LOG_MAX finished sends
  uint64_t serve_log_dropped = 0;
};

std::mutex g_srv_mu;
std::unordered_map<int64_t, Server*> g_servers;
int64_t g_next_srv = 1;

Server* get_srv(int64_t h) {
  std::lock_guard<std::mutex> lk(g_srv_mu);
  auto it = g_servers.find(h);
  return it == g_servers.end() ? nullptr : it->second;
}

bool send_all(int fd, const char* p, size_t n) {
  size_t off = 0;
  while (off < n) {
    ssize_t r = send(fd, p + off, n - off, MSG_NOSIGNAL);
    if (r < 0) {
      if (errno == EINTR) continue;
      return false;
    }
    off += (size_t)r;
  }
  return true;
}

bool send_simple(int fd, int status, const char* reason, const char* body) {
  char buf[256];
  size_t blen = strlen(body);
  int n = snprintf(buf, sizeof(buf),
                   "HTTP/1.1 %d %s\r\nContent-Length: %zu\r\n"
                   "Connection: keep-alive\r\n\r\n%s",
                   status, reason, blen, body);
  return send_all(fd, buf, (size_t)n);
}

// Parse "bytes=a-b" / "bytes=a-" / "bytes=-n" against total (may be -1:
// only the explicit a-b form is then valid). Returns false on failure.
bool parse_range(const std::string& v, int64_t total, uint64_t* start,
                 uint64_t* length) {
  if (v.compare(0, 6, "bytes=") != 0) return false;
  std::string spec = v.substr(6);
  size_t dash = spec.find('-');
  if (dash == std::string::npos) return false;
  std::string a = spec.substr(0, dash), b = spec.substr(dash + 1);
  errno = 0;
  if (a.empty()) {  // suffix: last N bytes
    if (b.empty() || total < 0) return false;
    char* end = nullptr;
    int64_t n = strtoll(b.c_str(), &end, 10);
    if (errno || *end || n <= 0) return false;
    if (n > total) n = total;
    *start = (uint64_t)(total - n);
    *length = (uint64_t)n;
    return true;
  }
  char* end = nullptr;
  int64_t s = strtoll(a.c_str(), &end, 10);
  if (errno || *end || s < 0) return false;
  int64_t e;
  if (b.empty()) {
    if (total < 0) return false;
    e = total - 1;
  } else {
    errno = 0;
    e = strtoll(b.c_str(), &end, 10);
    if (errno || *end || e < s) return false;
    if (total >= 0 && e >= total) e = total - 1;
  }
  if (total >= 0 && s >= total) return false;
  *start = (uint64_t)s;
  *length = (uint64_t)(e - s + 1);
  return *length > 0;
}

// All pieces covering [start, start+length) present? (mirror of
// LocalTaskStore.covers_range used by the Python server for 416s)
bool covers_range(const TaskEnt& t, uint64_t start, uint64_t length) {
  if (t.piece_size == 0) return false;
  uint64_t end = start + length;
  for (uint64_t n = start / t.piece_size; n * t.piece_size < end; n++) {
    auto it = t.pieces.find((uint32_t)n);
    if (it == t.pieces.end()) return false;
    uint64_t p0 = it->second.offset, p1 = p0 + it->second.size;
    uint64_t need0 = std::max(start, n * t.piece_size);
    uint64_t need1 = std::min(end, (n + 1) * t.piece_size);
    if (need0 < p0 || need1 > p1) return false;
  }
  return true;
}

void handle_request(Server* srv, int fd, const std::string& head,
                    bool* keep_alive, int64_t* wait_us) {
  // Request line: "GET <path> HTTP/1.1"
  size_t eol = head.find("\r\n");
  std::string line = head.substr(0, eol == std::string::npos ? head.size() : eol);
  if (line.compare(0, 4, "GET ") != 0) {
    srv->bad_request++;
    send_simple(fd, 405, "Method Not Allowed", "GET only");
    return;
  }
  size_t sp = line.find(' ', 4);
  std::string target = line.substr(4, sp == std::string::npos ? std::string::npos : sp - 4);

  // Headers we care about: Range, Connection.
  std::string range_hdr;
  *keep_alive = true;
  size_t pos = eol == std::string::npos ? head.size() : eol + 2;
  while (pos < head.size()) {
    size_t e = head.find("\r\n", pos);
    std::string h = head.substr(pos, (e == std::string::npos ? head.size() : e) - pos);
    pos = e == std::string::npos ? head.size() : e + 2;
    size_t colon = h.find(':');
    if (colon == std::string::npos) continue;
    std::string name = h.substr(0, colon);
    for (auto& c : name) c = (char)tolower((unsigned char)c);
    size_t vs = colon + 1;
    while (vs < h.size() && (h[vs] == ' ' || h[vs] == '\t')) vs++;
    std::string value = h.substr(vs);
    if (name == "range") range_hdr = value;
    else if (name == "connection") {
      for (auto& c : value) c = (char)tolower((unsigned char)c);
      if (value == "close") *keep_alive = false;
    }
  }

  std::string path = target, query;
  size_t q = target.find('?');
  if (q != std::string::npos) {
    path = target.substr(0, q);
    query = target.substr(q + 1);
  }

  if (path == "/healthy") {
    // Not counted as `ok`: that counter means pieces served (the aiohttp
    // server's label semantics), and health probes must not inflate it.
    send_simple(fd, 200, "OK", "ok");
    return;
  }
  if (path == "/metrics") {
    // Built as a string, not a fixed buffer: adding a counter must never
    // silently truncate the exposition. The daemon's real metrics
    // endpoint is the Python metrics server, which merges these counters
    // into the full label families (upload.py native_counters); this
    // endpoint is the raw native view for direct scrapes.
    std::string body;
    char scratch[128];
    auto add = [&](const char* fmt, uint64_t v) {
      int w = snprintf(scratch, sizeof(scratch), fmt, (unsigned long long)v);
      if (w > 0)
        body.append(scratch,
                    std::min((size_t)w, sizeof(scratch) - 1));
    };
    add("upload_bytes_total %llu\n", srv->bytes_served.load());
    add("upload_requests_total{result=\"ok\"} %llu\n", srv->ok.load());
    add("upload_requests_total{result=\"not_found\"} %llu\n",
        srv->not_found.load());
    add("upload_requests_total{result=\"piece_missing\"} %llu\n",
        srv->piece_missing.load());
    add("upload_requests_total{result=\"throttled\"} %llu\n",
        srv->throttled.load());
    add("upload_requests_total{result=\"bad_request\"} %llu\n",
        srv->bad_request.load());
    add("upload_active_transfers %llu\n", (uint64_t)srv->active.load());
    {
      std::lock_guard<std::mutex> lk(srv->reg_mu);
      add("upload_registered_tasks %llu\n", (uint64_t)srv->tasks.size());
    }
    const char* buf = body.c_str();
    int n = (int)body.size();
    char hdr[160];
    int hn = snprintf(hdr, sizeof(hdr),
                      "HTTP/1.1 200 OK\r\nContent-Length: %d\r\n"
                      "Connection: keep-alive\r\n\r\n", n);
    send_all(fd, hdr, (size_t)hn) && send_all(fd, buf, (size_t)n);
    return;
  }

  // /download/<prefix>/<task_id>
  if (path.compare(0, 10, "/download/") != 0) {
    srv->not_found++;
    send_simple(fd, 404, "Not Found", "no such route");
    return;
  }
  size_t last = path.rfind('/');
  std::string task_id = path.substr(last + 1);

  // query: pieceNum=N among &-separated pairs
  int64_t piece_num = -1;
  size_t p = 0;
  while (p < query.size()) {
    size_t amp = query.find('&', p);
    std::string kv = query.substr(p, (amp == std::string::npos ? query.size() : amp) - p);
    p = amp == std::string::npos ? query.size() : amp + 1;
    if (kv.compare(0, 9, "pieceNum=") == 0) {
      errno = 0;
      char* end = nullptr;
      piece_num = strtoll(kv.c_str() + 9, &end, 10);
      if (errno || *end || piece_num < 0) {
        srv->bad_request++;
        send_simple(fd, 400, "Bad Request", "bad pieceNum");
        return;
      }
    }
  }

  uint64_t start = 0, length = 0;
  std::string data_path;
  {
    std::lock_guard<std::mutex> lk(srv->reg_mu);
    auto it = srv->tasks.find(task_id);
    if (it == srv->tasks.end()) {
      srv->not_found++;
      send_simple(fd, 404, "Not Found", "task not found");
      return;
    }
    TaskEnt& t = it->second;
    if (piece_num >= 0) {
      auto pit = t.pieces.find((uint32_t)piece_num);
      if (pit == t.pieces.end()) {
        srv->piece_missing++;
        send_simple(fd, 404, "Not Found", "piece not found");
        return;
      }
      start = pit->second.offset;
      length = pit->second.size;
    } else if (!range_hdr.empty()) {
      if (!parse_range(range_hdr, t.content_length, &start, &length)) {
        srv->bad_request++;
        send_simple(fd, 400, "Bad Request", "bad range");
        return;
      }
      if (!covers_range(t, start, length)) {
        srv->piece_missing++;
        send_simple(fd, 416, "Range Not Satisfiable", "range not covered");
        return;
      }
    } else {
      srv->bad_request++;
      send_simple(fd, 400, "Bad Request", "Range or pieceNum required");
      return;
    }
    data_path = t.data_path;
  }

  // Reserve-then-check: a load-before-increment gate races across worker
  // threads (N requests all observe active<limit); fetch_add makes the
  // reservation itself the check.
  if (srv->concurrent_limit > 0) {
    int reserved = srv->active.fetch_add(1, std::memory_order_relaxed);
    if (reserved >= srv->concurrent_limit) {
      srv->active.fetch_sub(1, std::memory_order_relaxed);
      srv->throttled++;
      send_simple(fd, 429, "Too Many Requests", "throttled");
      return;
    }
  } else {
    srv->active.fetch_add(1, std::memory_order_relaxed);
  }

  // Open per request: an unlinked-but-open data file stays readable, so GC
  // reclaiming the store mid-send cannot corrupt the response (the Python
  // server pins the store for the same reason).
  int in_fd = open(data_path.c_str(), O_RDONLY);
  if (in_fd < 0) {
    srv->active.fetch_sub(1, std::memory_order_relaxed);
    srv->not_found++;
    send_simple(fd, 404, "Not Found", "data gone");
    return;
  }
  char hdr[256];
  int hn;
  if (piece_num >= 0) {
    hn = snprintf(hdr, sizeof(hdr),
                  "HTTP/1.1 200 OK\r\nContent-Length: %llu\r\n"
                  "Accept-Ranges: bytes\r\nConnection: keep-alive\r\n\r\n",
                  (unsigned long long)length);
  } else {
    hn = snprintf(hdr, sizeof(hdr),
                  "HTTP/1.1 206 Partial Content\r\nContent-Length: %llu\r\n"
                  "Content-Range: bytes %llu-%llu/*\r\n"
                  "Accept-Ranges: bytes\r\nConnection: keep-alive\r\n\r\n",
                  (unsigned long long)length, (unsigned long long)start,
                  (unsigned long long)(start + length - 1));
  }
  int64_t send_start_ns = mono_ns();
  bool ok = send_all(fd, hdr, (size_t)hn);
  off_t off = (off_t)start;
  uint64_t left = length;
  // SO_SNDTIMEO is NOT honored by sendfile on a blocking socket (measured:
  // a zero-window peer parks the call indefinitely — the exact stalled-
  // client worker exhaustion the timeout was meant to prevent). Bound the
  // stall explicitly: non-blocking sendfile + poll(POLLOUT) with the
  // timeout; a peer that stays unwritable past it loses the transfer.
  long timeout_s = env_seconds("DF_UPLOAD_SEND_TIMEOUT_S", 60);
  if (timeout_s > 2000000) timeout_s = 2000000;  // keep ms in int range
  const int send_timeout_ms = (int)(timeout_s * 1000);
  int fl = fcntl(fd, F_GETFL, 0);
  if (fl >= 0) fcntl(fd, F_SETFL, fl | O_NONBLOCK);
  while (ok && left > 0) {
    ssize_t r = sendfile(fd, in_fd, &off, left);
    if (r < 0) {
      if (errno == EINTR) continue;
      if (errno == EAGAIN || errno == EWOULDBLOCK) {
        struct pollfd pfd;
        pfd.fd = fd;
        pfd.events = POLLOUT;
        pfd.revents = 0;
        int pr = poll(&pfd, 1, send_timeout_ms);
        if (pr < 0 && errno == EINTR) continue;  // signal, not a stall
        if (pr > 0 && !(pfd.revents & (POLLERR | POLLHUP))) continue;
        ok = false;  // stalled past the send timeout, or dead socket
        break;
      }
      ok = false;
      break;
    }
    if (r == 0) {  // short file (sparse/truncated): stop, poison keep-alive
      ok = false;
      break;
    }
    left -= (uint64_t)r;
  }
  if (fl >= 0) fcntl(fd, F_SETFL, fl);
  close(in_fd);
  srv->active.fetch_sub(1, std::memory_order_relaxed);
  if (ok) {
    srv->bytes_served += length;
    srv->ok++;
    int64_t end_ns = mono_ns();
    std::lock_guard<std::mutex> lk(srv->log_mu);
    if (srv->serve_log.size() >= SERVE_LOG_MAX) {
      srv->serve_log.pop_front();
      srv->serve_log_dropped++;
    }
    srv->serve_log.push_back(ServeRec{task_id, piece_num, length, end_ns,
                                      (end_ns - send_start_ns) / 1000,
                                      *wait_us});
    *wait_us = 0;  // the wait was this connection's first request's
  } else {
    *keep_alive = false;  // response possibly truncated: desynced stream
  }
}

void conn_loop(Server* srv, int fd, int64_t wait_us) {
  {
    std::lock_guard<std::mutex> lk(srv->conns_mu);
    if (srv->stopping.load()) { close(fd); return; }
    srv->conns.insert(fd);
  }
  // Thread-per-connection + keep-alive means an IDLE connection parks a
  // worker inside recv. A short receive timeout bounds that parking (the
  // pull side's pool probes liveness and retries on a fresh connection, so
  // idle-close is client-transparent); sends keep a long timeout for slow
  // readers mid-transfer. Both are env-tunable for abuse tests.
  struct timeval tv;
  tv.tv_sec = 10;
  tv.tv_usec = 0;
  setsockopt(fd, SOL_SOCKET, SO_RCVTIMEO, &tv, sizeof(tv));
  tv.tv_sec = env_seconds("DF_UPLOAD_SEND_TIMEOUT_S", 60);
  setsockopt(fd, SOL_SOCKET, SO_SNDTIMEO, &tv, sizeof(tv));
  int one = 1;
  setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof(one));
  // The per-recv timeout alone does not bound a slow-loris head (a byte
  // every few seconds resets it forever, parking this worker; enough such
  // connections exhaust the pool). A whole-head deadline does.
  const long head_deadline_s = env_seconds("DF_UPLOAD_HEAD_DEADLINE_S", 30);

  std::string buf;
  char chunk[4096];
  while (!srv->stopping.load(std::memory_order_relaxed)) {
    // Read one request head (requests have no bodies on this server).
    size_t mark;
    time_t head_start = time(nullptr);
    while ((mark = buf.find("\r\n\r\n")) == std::string::npos) {
      if (buf.size() > HEAD_MAX) { close(fd); return; }
      ssize_t r = recv(fd, chunk, sizeof(chunk), 0);
      if (r <= 0) { close(fd); return; }
      if (time(nullptr) - head_start > head_deadline_s) { close(fd); return; }
      buf.append(chunk, (size_t)r);
    }
    std::string head = buf.substr(0, mark);
    buf.erase(0, mark + 4);
    bool keep = true;
    handle_request(srv, fd, head, &keep, &wait_us);
    if (!keep) break;
    {
      // Accepted connections are waiting for a worker: yield this one
      // rather than parking on an idle keep-alive while they starve (a
      // queued connection's request would stall toward the client's
      // timeout and read as a dead parent).
      std::lock_guard<std::mutex> lk(srv->queue_mu);
      if (!srv->pending.empty()) break;
    }
  }
  {
    std::lock_guard<std::mutex> lk(srv->conns_mu);
    srv->conns.erase(fd);
  }
  close(fd);
}

void worker_loop(Server* srv) {
  for (;;) {
    Pending next;
    {
      std::unique_lock<std::mutex> lk(srv->queue_mu);
      srv->queue_cv.wait(lk, [&] {
        return srv->stopping.load() || !srv->pending.empty();
      });
      if (srv->pending.empty()) return;  // stopping
      next = srv->pending.front();
      srv->pending.pop_front();
    }
    if (next.fd < 0) return;  // sentinel
    conn_loop(srv, next.fd, (mono_ns() - next.accepted_ns) / 1000);
  }
}

void accept_loop(Server* srv) {
  for (;;) {
    int fd = accept(srv->listen_fd, nullptr, nullptr);
    if (fd < 0) {
      if (errno == EINTR) continue;
      return;  // listener closed (stop) or fatal
    }
    std::lock_guard<std::mutex> lk(srv->queue_mu);
    if (srv->stopping.load() || srv->pending.size() >= srv->max_queue) {
      close(fd);
      continue;
    }
    srv->pending.push_back(Pending{fd, mono_ns()});
    srv->queue_cv.notify_one();
  }
}

}  // namespace

extern "C" {

// Start the server on ip:port (port 0 = ephemeral; read back with
// df_upload_port). workers = serving threads; concurrent_limit mirrors the
// Python server's 429 gate (0 = unlimited). Returns a handle or -errno.
int64_t df_upload_start(const char* ip, int port, int workers,
                        int concurrent_limit) {
  int fd = socket(AF_INET, SOCK_STREAM, 0);
  if (fd < 0) return -(int64_t)errno;
  int one = 1;
  setsockopt(fd, SOL_SOCKET, SO_REUSEADDR, &one, sizeof(one));
  struct sockaddr_in addr;
  memset(&addr, 0, sizeof(addr));
  addr.sin_family = AF_INET;
  addr.sin_port = htons((uint16_t)port);
  if (inet_pton(AF_INET, ip, &addr.sin_addr) != 1) {
    close(fd);
    return -(int64_t)EINVAL;
  }
  if (bind(fd, (struct sockaddr*)&addr, sizeof(addr)) < 0 ||
      listen(fd, 256) < 0) {
    int64_t e = -(int64_t)errno;
    close(fd);
    return e;
  }
  socklen_t alen = sizeof(addr);
  getsockname(fd, (struct sockaddr*)&addr, &alen);

  Server* srv = new Server();
  srv->listen_fd = fd;
  srv->port = ntohs(addr.sin_port);
  srv->concurrent_limit = concurrent_limit;
  if (workers <= 0) workers = 32;
  for (int i = 0; i < workers; i++)
    srv->workers.emplace_back(worker_loop, srv);
  srv->acceptor = std::thread(accept_loop, srv);

  std::lock_guard<std::mutex> lk(g_srv_mu);
  int64_t h = g_next_srv++;
  g_servers[h] = srv;
  return h;
}

int df_upload_port(int64_t h) {
  Server* srv = get_srv(h);
  return srv ? srv->port : -1;
}

// Upsert a task's serving entry; piece records survive re-registration
// (content_length/piece_size are often learned after the first pieces).
int df_upload_register_task(int64_t h, const char* task_id,
                            const char* data_path, int64_t content_length,
                            uint64_t piece_size) {
  Server* srv = get_srv(h);
  if (srv == nullptr) return -1;
  std::lock_guard<std::mutex> lk(srv->reg_mu);
  TaskEnt& t = srv->tasks[task_id];
  t.data_path = data_path;
  t.content_length = content_length;
  t.piece_size = piece_size;
  return 0;
}

int df_upload_register_piece(int64_t h, const char* task_id, uint32_t num,
                             uint64_t offset, uint64_t size) {
  Server* srv = get_srv(h);
  if (srv == nullptr) return -1;
  std::lock_guard<std::mutex> lk(srv->reg_mu);
  auto it = srv->tasks.find(task_id);
  if (it == srv->tasks.end()) return -2;
  it->second.pieces[num] = PieceEnt{offset, size};
  return 0;
}

int df_upload_unregister_task(int64_t h, const char* task_id) {
  Server* srv = get_srv(h);
  if (srv == nullptr) return -1;
  std::lock_guard<std::mutex> lk(srv->reg_mu);
  srv->tasks.erase(task_id);
  return 0;
}

// out[6] = {bytes_served, ok, not_found, piece_missing, throttled,
// bad_request} — label parity with the aiohttp server's metrics.
void df_upload_counters(int64_t h, uint64_t* out) {
  Server* srv = get_srv(h);
  if (srv == nullptr) {
    memset(out, 0, 6 * sizeof(uint64_t));
    return;
  }
  out[0] = srv->bytes_served.load();
  out[1] = srv->ok.load();
  out[2] = srv->not_found.load();
  out[3] = srv->piece_missing.load();
  out[4] = srv->throttled.load();
  out[5] = srv->bad_request.load();
}

// Pop finished sends, oldest first, as text lines
// "<task_id> <piece> <bytes> <end_ns> <send_us> <wait_us>\n" into out[cap];
// only whole lines, as many as fit. Returns the bytes written (0: none
// left, or cap too small for the next line).
int64_t df_upload_drain(int64_t h, char* out, int64_t cap) {
  Server* srv = get_srv(h);
  if (srv == nullptr) return 0;
  std::lock_guard<std::mutex> lk(srv->log_mu);
  int64_t used = 0;
  while (!srv->serve_log.empty()) {
    const ServeRec& r = srv->serve_log.front();
    char line[512];
    int n = snprintf(line, sizeof(line), "%s %lld %llu %lld %lld %lld\n",
                     r.task_id.c_str(), (long long)r.piece,
                     (unsigned long long)r.bytes, (long long)r.end_ns,
                     (long long)r.send_us, (long long)r.wait_us);
    if (n <= 0 || (size_t)n >= sizeof(line)) {  // an absurd task id: skip
      srv->serve_log.pop_front();
      continue;
    }
    if (used + n > cap) break;
    memcpy(out + used, line, (size_t)n);
    used += n;
    srv->serve_log.pop_front();
  }
  return used;
}

void df_upload_stop(int64_t h) {
  Server* srv;
  {
    std::lock_guard<std::mutex> lk(g_srv_mu);
    auto it = g_servers.find(h);
    if (it == g_servers.end()) return;
    srv = it->second;
    g_servers.erase(it);
  }
  srv->stopping.store(true);
  shutdown(srv->listen_fd, SHUT_RDWR);
  close(srv->listen_fd);
  {
    std::lock_guard<std::mutex> lk(srv->queue_mu);
    for (const Pending& p : srv->pending) close(p.fd);
    srv->pending.clear();
  }
  srv->queue_cv.notify_all();
  srv->acceptor.join();
  // Kick in-flight keep-alive connections out of recv/sendfile immediately
  // (don't close here: the worker owns the close; shutdown just unblocks).
  {
    std::lock_guard<std::mutex> lk(srv->conns_mu);
    for (int fd : srv->conns) shutdown(fd, SHUT_RDWR);
  }
  for (auto& w : srv->workers) w.join();
  delete srv;
}

}  // extern "C"
