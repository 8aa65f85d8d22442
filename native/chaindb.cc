// chaindb: segmented append-only record store — the native storage engine
// under celestia_app_tpu/chain/storage.py (ctypes-bound as libchaindb.so).
//
// Reference parity: the durable plane the reference gets from tm-db
// (LevelDB) + celestia-core's block store/WAL files — a log-structured
// store whose records are (stream, height) -> payload, with crash-safe
// framing and prune/rollback tombstones. The Python layer keeps the commit
// semantics (delta chains, snapshot cadence, prune windows); this engine
// owns the byte plane: framing, CRC, fsync batching, torn-tail recovery,
// segment rotation and dead-segment GC.
//
// Format: directory of seg-<n>.log files. Each record:
//   u32 magic | u32 kind | u32 stream | u64 height | u32 len | u32 crc | bytes
// crc covers kind..len, then the payload: CRC-32 (IEEE, reflected polynomial
// 0xEDB88320, init and xor-out 0xFFFFFFFF — the value of zlib.crc32),
// computed eight bytes a step (slicing-by-8). A record is written in one
// vectored write: the 28-byte header from the stack, the payload straight
// from the caller's buffer (no staging copy). Records are replayed in
// segment order on open; the in-memory index maps (stream, height) ->
// (segment, offset, len).
// Recovery rule: a torn/corrupt record in the LAST segment truncates the
// log there (a crash mid-append loses only that append, like a WAL); a
// corrupt record in an earlier segment is a hard open error (real data
// loss must be loud, not silently skipped).
//
// Kinds: PUT adds/overwrites one key. TOMB_AT deletes one key. TOMB_ABOVE
// deletes every key with height > h in ALL streams (rollback: the abandoned
// fork's state, blocks and LATEST markers all die together). A sealed
// segment whose live-record count reaches zero is unlinked (GC).
//
// Concurrency: a read-write open takes an exclusive flock on LOCK (two
// writers on one validator home would double-sign; fail loudly instead). A
// read-only open (tools scanning a LIVE home: blockscan/blocktime) takes no
// lock, never truncates, and simply stops at the first torn record — a
// concurrent writer mid-append must not have its tail chopped by a reader.

#include <algorithm>
#include <cerrno>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <dirent.h>
#include <fcntl.h>
#include <sys/file.h>
#include <map>
#include <set>
#include <string>
#include <sys/stat.h>
#include <sys/uio.h>
#include <unistd.h>
#include <vector>

namespace {

constexpr uint32_t MAGIC = 0xCE1E57DAu;
constexpr uint32_t KIND_PUT = 0;
constexpr uint32_t KIND_TOMB_AT = 1;
constexpr uint32_t KIND_TOMB_ABOVE = 2;
constexpr size_t HDR_SIZE = 4 + 4 + 4 + 8 + 4 + 4;

// crc_table[0] is the byte-at-a-time table; crc_table[k][b] is the CRC of
// byte b followed by k zero bytes, so eight lookups advance eight bytes.
uint32_t crc_table[8][256];
struct CrcInit {
  CrcInit() {
    for (uint32_t i = 0; i < 256; i++) {
      uint32_t c = i;
      for (int k = 0; k < 8; k++) c = (c & 1) ? 0xEDB88320u ^ (c >> 1) : c >> 1;
      crc_table[0][i] = c;
    }
    for (int k = 1; k < 8; k++)
      for (uint32_t i = 0; i < 256; i++) {
        uint32_t c = crc_table[k - 1][i];
        crc_table[k][i] = crc_table[0][c & 0xFF] ^ (c >> 8);
      }
  }
} crc_init;

uint32_t load_le32(const uint8_t* p) {
  return (uint32_t)p[0] | (uint32_t)p[1] << 8 | (uint32_t)p[2] << 16 |
         (uint32_t)p[3] << 24;
}

uint32_t crc32(const uint8_t* p, size_t n, uint32_t c = 0) {
  const auto& t = crc_table;
  c = ~c;
  for (; n >= 8; p += 8, n -= 8) {
    uint32_t a = load_le32(p) ^ c, b = load_le32(p + 4);
    c = t[7][a & 0xFF] ^ t[6][(a >> 8) & 0xFF] ^ t[5][(a >> 16) & 0xFF] ^
        t[4][a >> 24] ^ t[3][b & 0xFF] ^ t[2][(b >> 8) & 0xFF] ^
        t[1][(b >> 16) & 0xFF] ^ t[0][b >> 24];
  }
  for (; n; p++, n--) c = t[0][(c ^ *p) & 0xFF] ^ (c >> 8);
  return ~c;
}

void put_u32(uint8_t* p, uint32_t v) { memcpy(p, &v, 4); }
void put_u64(uint8_t* p, uint64_t v) { memcpy(p, &v, 8); }
uint32_t get_u32(const uint8_t* p) { uint32_t v; memcpy(&v, p, 4); return v; }
uint64_t get_u64(const uint8_t* p) { uint64_t v; memcpy(&v, p, 8); return v; }

struct Loc {
  uint64_t seg;
  uint64_t off;   // offset of payload
  uint32_t len;
};

struct Tomb {
  uint32_t kind;    // KIND_TOMB_AT or KIND_TOMB_ABOVE
  uint32_t stream;  // meaningful for TOMB_AT only
  uint64_t height;
};

struct DB {
  std::string dir;
  std::map<uint64_t, int> seg_fds;              // open segments (read)
  std::map<uint64_t, int64_t> live;             // seg -> live record count
  std::map<std::pair<uint32_t, uint64_t>, Loc> index;
  // Physical PUT keys per segment (indexed or not) and the tombstones each
  // segment carries: GC must not lose a tomb that still masks physical
  // bytes in a surviving segment, or those records resurrect on replay.
  std::map<uint64_t, std::vector<std::pair<uint32_t, uint64_t>>> seg_phys;
  std::map<uint64_t, std::vector<Tomb>> seg_tombs;
  uint64_t active_seg = 0;
  int active_fd = -1;
  uint64_t active_size = 0;
  uint64_t seg_max;
  bool dirty = false;                           // unsynced appends
  bool read_only = false;
  bool replaying = false;                       // defer GC during open
  int lock_fd = -1;
  std::string err;
};

std::string seg_path(const DB& db, uint64_t n) {
  char buf[32];
  snprintf(buf, sizeof buf, "seg-%08llu.log", (unsigned long long)n);
  return db.dir + "/" + buf;
}

int append_record(DB& db, uint32_t kind, uint32_t stream, uint64_t height,
                  const uint8_t* data, uint32_t len);

// Does any surviving segment (≠ dying) physically hold PUT bytes for a key
// that is NOT currently indexed? Such bytes would resurrect on replay
// unless a tombstone later in the log keeps masking them.
bool needs_masking_at(DB& db, uint64_t dying, uint32_t stream,
                      uint64_t height) {
  if (db.index.count({stream, height})) return false;  // re-put: replay
  for (auto& kv : db.seg_phys) {                       // order re-masks it
    if (kv.first == dying) continue;
    for (auto& k : kv.second)
      if (k.first == stream && k.second == height) return true;
  }
  return false;
}

void gc_segment(DB& db, uint64_t seg) {
  // A tombstone's scope is POSITIONAL: it masks only records earlier in
  // the log. Forwarding must preserve that scope from the log tail, so a
  // dying TOMB_ABOVE(h) is converted to precise per-key TOMB_ATs for
  // exactly the unindexed physical keys it still masks — re-appending the
  // TOMB_ABOVE itself would re-apply it to records committed AFTER the
  // rollback (live post-rollback commits) and destroy them. A tail
  // TOMB_AT on a currently-dead key is always safe: any future re-put
  // lands later in the log and wins on replay.
  std::set<std::pair<uint32_t, uint64_t>> fwd;
  for (auto& t : db.seg_tombs[seg]) {
    if (t.kind == KIND_TOMB_AT) {
      if (needs_masking_at(db, seg, t.stream, t.height))
        fwd.insert({t.stream, t.height});
    } else {  // TOMB_ABOVE
      for (auto& kv : db.seg_phys) {
        if (kv.first == seg) continue;
        for (auto& k : kv.second)
          if (k.second > t.height && !db.index.count(k)) fwd.insert(k);
      }
    }
  }
  // Append the forwards BEFORE destroying anything: if an append fails
  // (ENOSPC, rotate failure) the dying segment — and the tombstones it
  // carries — stay on disk, so no mask is ever silently lost. The
  // forwards must also be DURABLE before the unlink: a journaled FS can
  // commit the directory-entry removal ahead of the appended data, and a
  // crash in that window would replay the old fork with no tombstone
  // anywhere in the log.
  for (auto& k : fwd)
    if (append_record(db, KIND_TOMB_AT, k.first, k.second, nullptr, 0) != 0)
      return;
  if (!fwd.empty()) {
    if (fsync(db.active_fd) != 0) return;
    db.dirty = false;
  }
  db.seg_tombs.erase(seg);
  db.seg_phys.erase(seg);
  ::unlink(seg_path(db, seg).c_str());
  auto fd = db.seg_fds.find(seg);
  if (fd != db.seg_fds.end()) { ::close(fd->second); db.seg_fds.erase(fd); }
  db.live.erase(seg);
}

void drop_key(DB& db, uint32_t stream, uint64_t height) {
  auto it = db.index.find({stream, height});
  if (it == db.index.end()) return;
  uint64_t seg = it->second.seg;
  db.index.erase(it);
  if (--db.live[seg] == 0 && seg != db.active_seg && !db.replaying)
    gc_segment(db, seg);
}

void apply_tomb_above(DB& db, uint64_t height) {
  std::vector<std::pair<uint32_t, uint64_t>> dead;
  for (auto& kv : db.index)
    if (kv.first.second > height) dead.push_back(kv.first);
  for (auto& k : dead) drop_key(db, k.first, k.second);
}

// Replay one segment into the index. Returns false on a hard error (db.err
// set); `last` enables torn-tail truncation.
bool replay_segment(DB& db, uint64_t seg, int fd, bool last) {
  struct stat st;
  if (fstat(fd, &st) != 0) { db.err = "fstat failed"; return false; }
  uint64_t size = (uint64_t)st.st_size, off = 0;
  std::vector<uint8_t> buf;
  db.live[seg];  // materialize at 0
  while (off + HDR_SIZE <= size) {
    uint8_t hdr[HDR_SIZE];
    if (pread(fd, hdr, HDR_SIZE, off) != (ssize_t)HDR_SIZE) break;
    uint32_t magic = get_u32(hdr), kind = get_u32(hdr + 4),
             stream = get_u32(hdr + 8), len = get_u32(hdr + 20),
             crc = get_u32(hdr + 24);
    uint64_t height = get_u64(hdr + 12);
    if (magic != MAGIC || off + HDR_SIZE + len > size) break;
    buf.resize(len);
    if (len && pread(fd, buf.data(), len, off + HDR_SIZE) != (ssize_t)len)
      break;
    uint32_t want = crc32(hdr + 4, HDR_SIZE - 8);
    if (len) want = crc32(buf.data(), len, want);
    if (want != crc) break;
    if (kind == KIND_PUT) {
      drop_key(db, stream, height);
      db.index[{stream, height}] = {seg, off + HDR_SIZE, len};
      db.live[seg]++;
      db.seg_phys[seg].push_back({stream, height});
    } else if (kind == KIND_TOMB_AT) {
      drop_key(db, stream, height);
      db.seg_tombs[seg].push_back({kind, stream, height});
    } else if (kind == KIND_TOMB_ABOVE) {
      apply_tomb_above(db, height);
      db.seg_tombs[seg].push_back({kind, 0, height});
    }  // unknown kinds: skip (forward compat)
    off += HDR_SIZE + len;
  }
  if (off != size) {
    if (!last) {
      char m[128];
      snprintf(m, sizeof m,
               "corrupt record in sealed segment %llu at offset %llu",
               (unsigned long long)seg, (unsigned long long)off);
      db.err = m;
      return false;
    }
    if (!db.read_only) {  // a reader must never chop a live writer's tail
      if (ftruncate(fd, (off_t)off) != 0) {
        db.err = "truncate failed";
        return false;
      }
      fsync(fd);
    }
  }
  if (last) db.active_size = off;
  return true;
}

int sync_dir(const DB& db) {
  int dfd = ::open(db.dir.c_str(), O_RDONLY);
  if (dfd < 0) return -1;
  int rc = fsync(dfd);
  ::close(dfd);
  return rc;
}

int rotate(DB& db) {
  if (fsync(db.active_fd) != 0) return -1;
  // open the new segment BEFORE committing any state change: a failed
  // open (EMFILE/ENOSPC) must leave the old segment active, or later
  // appends would index under a segment number with no fd
  std::string p = seg_path(db, db.active_seg + 1);
  int fd = ::open(p.c_str(), O_RDWR | O_CREAT | O_TRUNC, 0644);
  if (fd < 0) return -1;
  db.active_seg += 1;
  db.active_fd = fd;
  db.active_size = 0;
  db.seg_fds[db.active_seg] = fd;
  db.live[db.active_seg] = 0;
  return sync_dir(db);
}

int append_record(DB& db, uint32_t kind, uint32_t stream, uint64_t height,
                  const uint8_t* data, uint32_t len) {
  if (db.read_only || db.active_fd < 0) return -4;
  if (db.active_size >= db.seg_max && rotate(db) != 0) return -1;
  uint8_t hdr[HDR_SIZE];
  put_u32(hdr, MAGIC);
  put_u32(hdr + 4, kind);
  put_u32(hdr + 8, stream);
  put_u64(hdr + 12, height);
  put_u32(hdr + 20, len);
  uint32_t crc = crc32(hdr + 4, HDR_SIZE - 8);
  if (len) crc = crc32(data, len, crc);
  put_u32(hdr + 24, crc);
  // header and payload in one vectored write, resumed after a short write;
  // a failure leaves a torn tail that the next open truncates
  uint64_t off = db.active_size, total = HDR_SIZE + (uint64_t)len, done = 0;
  iovec iov[2] = {{hdr, HDR_SIZE}, {const_cast<uint8_t*>(data), len}};
  iovec* v = iov;
  int nv = len ? 2 : 1;
  while (done < total) {
    ssize_t n = pwritev(db.active_fd, v, nv, (off_t)(off + done));
    if (n < 0 && errno == EINTR) continue;
    if (n <= 0) return -1;
    done += (uint64_t)n;
    for (size_t left = (size_t)n; left;) {  // skip what was written
      size_t step = std::min(left, v->iov_len);
      v->iov_base = (uint8_t*)v->iov_base + step;
      v->iov_len -= step;
      left -= step;
      if (v->iov_len == 0) { v++; nv--; }
    }
  }
  db.active_size += total;
  db.dirty = true;
  if (kind == KIND_PUT) {
    drop_key(db, stream, height);
    db.index[{stream, height}] = {db.active_seg, off + HDR_SIZE, len};
    db.live[db.active_seg]++;
    db.seg_phys[db.active_seg].push_back({stream, height});
  } else if (kind == KIND_TOMB_AT) {
    db.seg_tombs[db.active_seg].push_back({kind, stream, height});
    drop_key(db, stream, height);
  } else if (kind == KIND_TOMB_ABOVE) {
    db.seg_tombs[db.active_seg].push_back({kind, 0, height});
    apply_tomb_above(db, height);
  }
  return 0;
}

}  // namespace

extern "C" {

void* cdb_open(const char* dir, int read_only, char* errbuf, int errlen) {
  DB* db = new DB;
  db->dir = dir;
  db->read_only = read_only != 0;
  const char* sm = getenv("CELESTIA_CDB_SEGBYTES");
  db->seg_max = sm ? strtoull(sm, nullptr, 10) : (64ull << 20);
  if (db->seg_max < 1) db->seg_max = 1;
  if (!db->read_only) mkdir(dir, 0755);  // EEXIST ok
  if (!db->read_only) {
    std::string lp = db->dir + "/LOCK";
    db->lock_fd = ::open(lp.c_str(), O_RDWR | O_CREAT, 0644);
    if (db->lock_fd < 0 || flock(db->lock_fd, LOCK_EX | LOCK_NB) != 0) {
      snprintf(errbuf, errlen,
               "chaindb %s is locked by another process (flock: %s)", dir,
               strerror(errno));
      if (db->lock_fd >= 0) ::close(db->lock_fd);
      delete db;
      return nullptr;
    }
  }
  std::vector<uint64_t> segs;
  if (DIR* d = opendir(dir)) {
    while (dirent* e = readdir(d)) {
      unsigned long long n;
      if (sscanf(e->d_name, "seg-%llu.log", &n) == 1) segs.push_back(n);
    }
    closedir(d);
  } else {
    snprintf(errbuf, errlen, "cannot open dir %s: %s", dir, strerror(errno));
    if (db->lock_fd >= 0) ::close(db->lock_fd);
    delete db;
    return nullptr;
  }
  std::sort(segs.begin(), segs.end());
  db->replaying = true;  // GC during replay would write mid-open; defer
  for (size_t i = 0; i < segs.size(); i++) {
    std::string p = seg_path(*db, segs[i]);
    int fd = ::open(p.c_str(), db->read_only ? O_RDONLY : O_RDWR);
    if (fd < 0) {
      snprintf(errbuf, errlen, "cannot open %s: %s", p.c_str(), strerror(errno));
      for (auto& kv : db->seg_fds) ::close(kv.second);
      if (db->lock_fd >= 0) ::close(db->lock_fd);
      delete db;
      return nullptr;
    }
    db->seg_fds[segs[i]] = fd;
    db->active_seg = segs[i];
    db->active_fd = fd;
    if (!replay_segment(*db, segs[i], fd, i + 1 == segs.size())) {
      snprintf(errbuf, errlen, "%s", db->err.c_str());
      for (auto& kv : db->seg_fds) ::close(kv.second);
      if (db->lock_fd >= 0) ::close(db->lock_fd);
      delete db;
      return nullptr;
    }
  }
  db->replaying = false;
  if (!db->read_only) {  // deferred GC: sealed segments fully dead on disk
    std::vector<uint64_t> dead;
    for (auto& kv : db->live)
      if (kv.second == 0 && kv.first != db->active_seg)
        dead.push_back(kv.first);
    for (uint64_t s : dead) gc_segment(*db, s);
  }
  if (segs.empty() && !db->read_only) {
    std::string p = seg_path(*db, 0);
    int fd = ::open(p.c_str(), O_RDWR | O_CREAT | O_TRUNC, 0644);
    if (fd < 0) {
      snprintf(errbuf, errlen, "cannot create %s: %s", p.c_str(),
               strerror(errno));
      if (db->lock_fd >= 0) ::close(db->lock_fd);
      delete db;
      return nullptr;
    }
    db->seg_fds[0] = fd;
    db->live[0] = 0;
    db->active_seg = 0;
    db->active_fd = fd;
    db->active_size = 0;
    sync_dir(*db);
  }
  return db;
}

int cdb_put(void* h, uint32_t stream, uint64_t height, const void* data,
            uint32_t len) {
  DB* db = (DB*)h;
  return append_record(*db, KIND_PUT, stream, height, (const uint8_t*)data,
                       len);
}

int cdb_tomb_at(void* h, uint32_t stream, uint64_t height) {
  return append_record(*(DB*)h, KIND_TOMB_AT, stream, height, nullptr, 0);
}

int cdb_tomb_above(void* h, uint64_t height) {
  return append_record(*(DB*)h, KIND_TOMB_ABOVE, 0, height, nullptr, 0);
}

int cdb_sync(void* h) {
  DB* db = (DB*)h;
  if (!db->dirty) return 0;
  if (fsync(db->active_fd) != 0) return -1;
  db->dirty = false;
  return 0;
}

int64_t cdb_get_len(void* h, uint32_t stream, uint64_t height) {
  DB* db = (DB*)h;
  auto it = db->index.find({stream, height});
  return it == db->index.end() ? -1 : (int64_t)it->second.len;
}

int cdb_get(void* h, uint32_t stream, uint64_t height, void* out,
            uint32_t cap) {
  DB* db = (DB*)h;
  auto it = db->index.find({stream, height});
  if (it == db->index.end()) return -1;
  const Loc& loc = it->second;
  if (cap < loc.len) return -2;
  int fd = db->seg_fds.at(loc.seg);
  if (loc.len &&
      pread(fd, out, loc.len, (off_t)loc.off) != (ssize_t)loc.len)
    return -3;
  return (int)loc.len;
}

int64_t cdb_latest(void* h, uint32_t stream) {
  DB* db = (DB*)h;
  auto it = db->index.upper_bound({stream, UINT64_MAX});
  if (it == db->index.begin()) return -1;
  --it;
  if (it->first.first != stream) return -1;
  return (int64_t)it->first.second;
}

uint64_t cdb_count(void* h, uint32_t stream) {
  DB* db = (DB*)h;
  uint64_t n = 0;
  for (auto it = db->index.lower_bound({stream, 0});
       it != db->index.end() && it->first.first == stream; ++it)
    n++;
  return n;
}

int64_t cdb_heights(void* h, uint32_t stream, uint64_t* out, uint64_t cap) {
  DB* db = (DB*)h;
  uint64_t n = 0;
  for (auto it = db->index.lower_bound({stream, 0});
       it != db->index.end() && it->first.first == stream; ++it) {
    if (n < cap) out[n] = it->first.second;
    n++;
  }
  return n <= cap ? (int64_t)n : -(int64_t)n;
}

uint64_t cdb_segments(void* h) { return ((DB*)h)->seg_fds.size(); }

void cdb_close(void* h) {
  DB* db = (DB*)h;
  if (!db->read_only) cdb_sync(h);
  for (auto& kv : db->seg_fds) ::close(kv.second);
  if (db->lock_fd >= 0) ::close(db->lock_fd);  // releases the flock
  delete db;
}

}  // extern "C"
