// Native WAL fast path — the C++ runtime piece of the storage layer.
//
// The reference's durability layer is vendored etcd/wal (Go) feeding an
// fsync before peer sends (reference raft.go:227-235).  At 100k groups per
// tick the record-framing CPU cost lands on the host hot loop, so the
// framing + CRC + buffered write path lives here; Python (storage/wal.py)
// keeps the cold paths (open/replay) and falls back to a pure-Python
// writer when this library is unavailable.
//
// Byte format is identical to storage/wal.py:
//   u32 crc32(body) | u32 body_len | body          (little endian)
//   body := u8 type | fields
//     type 1 ENTRY:     u32 group | u64 index | u64 term | bytes data
//     type 2 HARDSTATE: u32 group | u64 term  | i64 vote | u64 commit
//     type 3 SNAPSHOT:  u32 group | u64 index | u64 term
//     type 4 COMPACT:   u32 group | u64 index | u64 term
//     type 5 RANGE:     u32 group | u64 start | u64 term | u32 count
//                       | u32 lens[count] | payload bytes
//
// Build: g++ -O2 -shared -fPIC -o _native_wal.so wal.cc
// ABI: plain C, consumed via ctypes (no pybind11 in this environment).

#include <cerrno>
#include <cstdint>
#include <cstring>
#include <fcntl.h>
#include <mutex>
#include <unistd.h>
#include <vector>

namespace {

uint32_t kCrcTable[256];
struct CrcInit {
  CrcInit() {
    for (uint32_t i = 0; i < 256; ++i) {
      uint32_t c = i;
      for (int k = 0; k < 8; ++k)
        c = (c & 1) ? 0xEDB88320u ^ (c >> 1) : c >> 1;
      kCrcTable[i] = c;
    }
  }
} crc_init;

uint32_t crc32z_update(uint32_t c, const uint8_t* p, size_t n) {
  for (size_t i = 0; i < n; ++i) c = kCrcTable[(c ^ p[i]) & 0xFF] ^ (c >> 8);
  return c;
}

uint32_t crc32z(const uint8_t* p, size_t n) {  // zlib-compatible
  return crc32z_update(0xFFFFFFFFu, p, n) ^ 0xFFFFFFFFu;
}

struct Wal {
  int fd = -1;
  std::vector<uint8_t> buf;  // framed records pending write+fsync
  std::mutex mu;
};

void put_u32(std::vector<uint8_t>& b, uint32_t v) {
  for (int i = 0; i < 4; ++i) b.push_back(uint8_t(v >> (8 * i)));
}
void put_u64(std::vector<uint8_t>& b, uint64_t v) {
  for (int i = 0; i < 8; ++i) b.push_back(uint8_t(v >> (8 * i)));
}

// Frame `body` (already assembled past the header) into w->buf.
void frame(Wal* w, const std::vector<uint8_t>& body) {
  put_u32(w->buf, crc32z(body.data(), body.size()));
  put_u32(w->buf, uint32_t(body.size()));
  w->buf.insert(w->buf.end(), body.begin(), body.end());
}

int flush_locked(Wal* w) {
  size_t off = 0;
  while (off < w->buf.size()) {
    ssize_t n = ::write(w->fd, w->buf.data() + off, w->buf.size() - off);
    if (n < 0) {
      if (errno == EINTR) continue;
      // Drop the consumed prefix so a retry/close cannot re-write bytes
      // already on disk (which would garble the tail with duplicates).
      w->buf.erase(w->buf.begin(), w->buf.begin() + off);
      return -1;
    }
    off += size_t(n);
  }
  w->buf.clear();
  return 0;
}

}  // namespace

extern "C" {

void* wal_open(const char* path) {
  int fd = ::open(path, O_WRONLY | O_CREAT | O_APPEND, 0644);
  if (fd < 0) return nullptr;
  Wal* w = new Wal();
  w->fd = fd;
  w->buf.reserve(1 << 20);
  return w;
}

int wal_append_entry(void* h, uint32_t group, uint64_t index, uint64_t term,
                     const uint8_t* data, uint32_t len) {
  Wal* w = static_cast<Wal*>(h);
  std::vector<uint8_t> body;
  body.reserve(21 + len);
  body.push_back(1);
  put_u32(body, group);
  put_u64(body, index);
  put_u64(body, term);
  if (len) body.insert(body.end(), data, data + len);
  std::lock_guard<std::mutex> lk(w->mu);
  frame(w, body);
  return 0;
}

// Batched append: n records whose data blobs are concatenated in `datas`
// with per-record lengths in `lens`.  One ctypes call per tick, not per
// record.
int wal_append_entries(void* h, uint32_t n, const uint32_t* groups,
                       const uint64_t* indexes, const uint64_t* terms,
                       const uint8_t* datas, const uint32_t* lens) {
  Wal* w = static_cast<Wal*>(h);
  std::lock_guard<std::mutex> lk(w->mu);
  size_t off = 0;
  std::vector<uint8_t> body;
  for (uint32_t i = 0; i < n; ++i) {
    body.clear();
    body.reserve(21 + lens[i]);
    body.push_back(1);
    put_u32(body, groups[i]);
    put_u64(body, indexes[i]);
    put_u64(body, terms[i]);
    if (lens[i]) body.insert(body.end(), datas + off, datas + off + lens[i]);
    off += lens[i];
    frame(w, body);
  }
  return 0;
}

// Range append: one type-5 record per (group, start, term, count) range
// of consecutive entries — the header+CRC amortizes over the whole
// range (the per-entry framing was the durable tick's byte bottleneck).
// Body: u8 5 | u32 group | u64 start | u64 term | u32 count
//       | u32 lens[count] | payload bytes (concatenated).
int wal_append_ranges(void* h, uint32_t n_ranges, const uint32_t* groups,
                      const uint64_t* starts, const uint64_t* terms,
                      const uint32_t* counts, const uint8_t* blob,
                      const uint32_t* lens) {
  Wal* w = static_cast<Wal*>(h);
  std::lock_guard<std::mutex> lk(w->mu);
  size_t blob_off = 0, len_off = 0;
  std::vector<uint8_t> body;
  for (uint32_t r = 0; r < n_ranges; ++r) {
    uint32_t cnt = counts[r];
    size_t bytes = 0;
    for (uint32_t i = 0; i < cnt; ++i) bytes += lens[len_off + i];
    body.clear();
    body.reserve(25 + 4 * size_t(cnt) + bytes);
    body.push_back(5);
    put_u32(body, groups[r]);
    put_u64(body, starts[r]);
    put_u64(body, terms[r]);
    put_u32(body, cnt);
    for (uint32_t i = 0; i < cnt; ++i) put_u32(body, lens[len_off + i]);
    if (bytes)
      body.insert(body.end(), blob + blob_off, blob + blob_off + bytes);
    blob_off += bytes;
    len_off += cnt;
    frame(w, body);
  }
  return 0;
}

int wal_set_snapshot(void* h, uint32_t group, uint64_t index,
                     uint64_t term) {
  Wal* w = static_cast<Wal*>(h);
  std::vector<uint8_t> body;
  body.reserve(21);
  body.push_back(3);
  put_u32(body, group);
  put_u64(body, index);
  put_u64(body, term);
  std::lock_guard<std::mutex> lk(w->mu);
  frame(w, body);
  return 0;
}

// Compaction floor marker (type 4): on replay, entries of `group` at or
// below `index` are dropped while the retained suffix SURVIVES — unlike
// the snapshot marker (type 3), which also clears the suffix because an
// installed state's history may conflict with it.
// Type 6 EPOCH: u8 kind (0 BEGIN / 1 END) | u64 epoch number — the
// multi-step dispatch frame marker (runtime/fused.py); replay ignores
// it, repair_epochs() truncates at an uncommitted BEGIN.
int wal_epoch(void* h, uint64_t no, uint8_t kind) {
  Wal* w = static_cast<Wal*>(h);
  std::vector<uint8_t> body;
  body.reserve(10);
  body.push_back(6);
  body.push_back(kind);
  put_u64(body, no);
  std::lock_guard<std::mutex> lk(w->mu);
  frame(w, body);
  return 0;
}

int wal_set_compact(void* h, uint32_t group, uint64_t index,
                    uint64_t term) {
  Wal* w = static_cast<Wal*>(h);
  std::vector<uint8_t> body;
  body.reserve(21);
  body.push_back(4);
  put_u32(body, group);
  put_u64(body, index);
  put_u64(body, term);
  std::lock_guard<std::mutex> lk(w->mu);
  frame(w, body);
  return 0;
}

// A sweep's floor markers in one call (storage/wal.py
// WAL._write_compact_recs): a call from Python lets go of the interpreter and
// has to win it back, which in a served engine costs far more than the
// record; a sweep writes hundreds, the one that drops the first
// segment tens of thousands.
int wal_set_compacts(void* h, uint32_t n, const uint32_t* groups,
                     const uint64_t* indexes, const uint64_t* terms) {
  Wal* w = static_cast<Wal*>(h);
  std::lock_guard<std::mutex> lk(w->mu);
  std::vector<uint8_t> body;
  for (uint32_t i = 0; i < n; ++i) {
    body.clear();
    body.reserve(21);
    body.push_back(4);
    put_u32(body, groups[i]);
    put_u64(body, indexes[i]);
    put_u64(body, terms[i]);
    frame(w, body);
  }
  return 0;
}

int wal_set_hardstate(void* h, uint32_t group, uint64_t term, int64_t vote,
                      uint64_t commit) {
  Wal* w = static_cast<Wal*>(h);
  std::vector<uint8_t> body;
  body.reserve(29);
  body.push_back(2);
  put_u32(body, group);
  put_u64(body, term);
  put_u64(body, uint64_t(vote));
  put_u64(body, commit);
  std::lock_guard<std::mutex> lk(w->mu);
  frame(w, body);
  return 0;
}

// Batched hard states — one call per tick for every group whose
// (term, vote, commit) changed; under saturation that is ALL groups, so
// the per-record Python/ctypes round trip must not be per group.
int wal_set_hardstates(void* h, uint32_t n, const uint32_t* groups,
                       const uint64_t* terms, const int64_t* votes,
                       const uint64_t* commits) {
  Wal* w = static_cast<Wal*>(h);
  std::lock_guard<std::mutex> lk(w->mu);
  std::vector<uint8_t> body;
  for (uint32_t i = 0; i < n; ++i) {
    body.clear();
    body.reserve(29);
    body.push_back(2);
    put_u32(body, groups[i]);
    put_u64(body, terms[i]);
    put_u64(body, uint64_t(votes[i]));
    put_u64(body, commits[i]);
    frame(w, body);
  }
  return 0;
}

// Durable point: write all pending frames, then fdatasync.  Returns 0 on
// success, -1 on error (caller must treat as fatal — the ordering
// invariant is broken if we proceed).
int wal_sync(void* h) {
  Wal* w = static_cast<Wal*>(h);
  std::lock_guard<std::mutex> lk(w->mu);
  if (w->buf.empty()) return 0;
  if (flush_locked(w) != 0) return -1;
  return ::fdatasync(w->fd) == 0 ? 0 : -1;
}

int wal_close(void* h) {
  Wal* w = static_cast<Wal*>(h);
  int rc = 0;
  {
    std::lock_guard<std::mutex> lk(w->mu);
    if (!w->buf.empty() && flush_locked(w) == 0) ::fdatasync(w->fd);
    rc = ::close(w->fd);
  }
  delete w;
  return rc;
}

}  // extern "C"
