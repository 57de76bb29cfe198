// pathway_tpu host-runtime native core.
//
// TPU-era equivalent of the reference's Rust hot paths: 128-bit key
// derivation (src/engine/value.rs Key::for_values — SipHash there,
// BLAKE2b-128 here to match the Python hashlib fallback bit-for-bit) and
// the hashing tokenizer's batch encode (models/tokenizer.py), which
// dominates host time in the embedding ingest path.
//
// And the watched-directory poll of io/fs (pw_fs_list, pw_fs_stat,
// pw_fs_read): pass 1 lists the tree under a directory, matches base names
// against a `*`/`?` pattern the way glob.glob("<dir>/**/<pattern>",
// recursive=True) does and stats only the files the caller does not know
// yet; pass 2 stats every file the caller knew; pw_fs_read reads the files
// the caller names; all with the interpreter lock released.  The Python
// lister of io/fs is the fallback, and is also what io/fs takes when the
// path is a single file or a glob, or the pattern holds a path separator or
// a bracket expression.
//
// Built by pathway_tpu/_native/__init__.py with g++ -O3 -shared -fPIC;
// every exported function has a pure-Python fallback with identical
// semantics, so the library is an accelerator, never a requirement.

#include <dirent.h>
#include <fcntl.h>
#include <sys/stat.h>
#include <unistd.h>

#include <algorithm>
#include <cerrno>
#include <cstdint>
#include <cstring>
#include <string>
#include <string_view>
#include <unordered_map>
#include <vector>

// ---------------------------------------------------------------------------
// BLAKE2b (RFC 7693), fixed 16-byte digest, no key — matches
// hashlib.blake2b(data, digest_size=16).
// ---------------------------------------------------------------------------

namespace {

constexpr uint64_t kIV[8] = {
    0x6a09e667f3bcc908ULL, 0xbb67ae8584caa73bULL, 0x3c6ef372fe94f82bULL,
    0xa54ff53a5f1d36f1ULL, 0x510e527fade682d1ULL, 0x9b05688c2b3e6c1fULL,
    0x1f83d9abfb41bd6bULL, 0x5be0cd19137e2179ULL,
};

constexpr uint8_t kSigma[12][16] = {
    {0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15},
    {14, 10, 4, 8, 9, 15, 13, 6, 1, 12, 0, 2, 11, 7, 5, 3},
    {11, 8, 12, 0, 5, 2, 15, 13, 10, 14, 3, 6, 7, 1, 9, 4},
    {7, 9, 3, 1, 13, 12, 11, 14, 2, 6, 5, 10, 4, 0, 15, 8},
    {9, 0, 5, 7, 2, 4, 10, 15, 14, 1, 11, 12, 6, 8, 3, 13},
    {2, 12, 6, 10, 0, 11, 8, 3, 4, 13, 7, 5, 15, 14, 1, 9},
    {12, 5, 1, 15, 14, 13, 4, 10, 0, 7, 6, 3, 9, 2, 8, 11},
    {13, 11, 7, 14, 12, 1, 3, 9, 5, 0, 15, 4, 8, 6, 2, 10},
    {6, 15, 14, 9, 11, 3, 0, 8, 12, 2, 13, 7, 1, 4, 10, 5},
    {10, 2, 8, 4, 7, 6, 1, 5, 15, 11, 9, 14, 3, 12, 13, 0},
    {0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15},
    {14, 10, 4, 8, 9, 15, 13, 6, 1, 12, 0, 2, 11, 7, 5, 3},
};

inline uint64_t rotr64(uint64_t x, int n) { return (x >> n) | (x << (64 - n)); }

inline uint64_t load64(const uint8_t* p) {
  uint64_t v;
  std::memcpy(&v, p, 8);  // little-endian hosts only (x86_64/aarch64)
  return v;
}

struct Blake2bState {
  uint64_t h[8];
  uint64_t t[2];
  uint8_t buf[128];
  size_t buflen;
};

void g(uint64_t* v, int a, int b, int c, int d, uint64_t x, uint64_t y) {
  v[a] = v[a] + v[b] + x;
  v[d] = rotr64(v[d] ^ v[a], 32);
  v[c] = v[c] + v[d];
  v[b] = rotr64(v[b] ^ v[c], 24);
  v[a] = v[a] + v[b] + y;
  v[d] = rotr64(v[d] ^ v[a], 16);
  v[c] = v[c] + v[d];
  v[b] = rotr64(v[b] ^ v[c], 63);
}

void compress(Blake2bState* s, const uint8_t block[128], bool last) {
  uint64_t m[16];
  for (int i = 0; i < 16; i++) m[i] = load64(block + 8 * i);
  uint64_t v[16];
  for (int i = 0; i < 8; i++) v[i] = s->h[i];
  for (int i = 0; i < 8; i++) v[i + 8] = kIV[i];
  v[12] ^= s->t[0];
  v[13] ^= s->t[1];
  if (last) v[14] = ~v[14];
  for (int r = 0; r < 12; r++) {
    const uint8_t* sg = kSigma[r];
    g(v, 0, 4, 8, 12, m[sg[0]], m[sg[1]]);
    g(v, 1, 5, 9, 13, m[sg[2]], m[sg[3]]);
    g(v, 2, 6, 10, 14, m[sg[4]], m[sg[5]]);
    g(v, 3, 7, 11, 15, m[sg[6]], m[sg[7]]);
    g(v, 0, 5, 10, 15, m[sg[8]], m[sg[9]]);
    g(v, 1, 6, 11, 12, m[sg[10]], m[sg[11]]);
    g(v, 2, 7, 8, 13, m[sg[12]], m[sg[13]]);
    g(v, 3, 4, 9, 14, m[sg[14]], m[sg[15]]);
  }
  for (int i = 0; i < 8; i++) s->h[i] ^= v[i] ^ v[i + 8];
}

}  // namespace

extern "C" void pw_blake2b128(const uint8_t* data, uint64_t len,
                              uint8_t out[16]) {
  Blake2bState s;
  for (int i = 0; i < 8; i++) s.h[i] = kIV[i];
  s.h[0] ^= 0x01010000ULL ^ 16ULL;  // digest_length=16, fanout=depth=1
  s.t[0] = s.t[1] = 0;
  s.buflen = 0;

  // full blocks (keep the final block, even if full, for the last-flag pass)
  while (len > 128) {
    std::memcpy(s.buf, data, 128);
    s.t[0] += 128;
    if (s.t[0] < 128) s.t[1]++;
    compress(&s, s.buf, false);
    data += 128;
    len -= 128;
  }
  std::memset(s.buf, 0, 128);
  if (len > 0) std::memcpy(s.buf, data, len);
  s.t[0] += len;
  if (s.t[0] < len) s.t[1]++;
  compress(&s, s.buf, true);
  std::memcpy(out, s.h, 16);
}

// ---------------------------------------------------------------------------
// Hashing tokenizer batch encode — byte-level, exact mirror of
// models/tokenizer.HashTokenizer:
//   word bytes: [A-Za-z0-9_] or >= 0x80; whitespace splits; any other
//   byte is a single punctuation token.  Token id =
//   N_SPECIAL + fnv1a64(bytes) % (vocab - N_SPECIAL).
// ---------------------------------------------------------------------------

namespace {

constexpr int32_t kPad = 0, kCls = 1, kSep = 2, kNSpecial = 4;

inline bool is_ws(uint8_t c) {
  return c == ' ' || c == '\t' || c == '\n' || c == '\r' || c == '\f' ||
         c == '\v';
}

inline bool is_word(uint8_t c) {
  return (c >= 'a' && c <= 'z') || (c >= 'A' && c <= 'Z') ||
         (c >= '0' && c <= '9') || c == '_' || c >= 0x80;
}

inline uint64_t fnv1a64(const uint8_t* p, size_t n, bool lowercase) {
  uint64_t h = 1469598103934665603ULL;
  for (size_t i = 0; i < n; i++) {
    uint8_t c = p[i];
    if (lowercase && c >= 'A' && c <= 'Z') c += 32;
    h ^= c;
    h *= 1099511628211ULL;
  }
  return h;
}

// emits up to max_out token ids, returns count
int64_t tokenize(const uint8_t* text, int64_t len, int64_t vocab_size,
                 bool lowercase, int32_t* out, int64_t max_out) {
  const uint64_t mod = (uint64_t)(vocab_size - kNSpecial);
  int64_t n_out = 0;
  int64_t i = 0;
  while (i < len && n_out < max_out) {
    uint8_t c = text[i];
    if (is_ws(c)) {
      i++;
      continue;
    }
    int64_t start = i;
    if (is_word(c)) {
      while (i < len && is_word(text[i])) i++;
    } else {
      i++;  // single punctuation byte
    }
    uint64_t h = fnv1a64(text + start, (size_t)(i - start), lowercase);
    out[n_out++] = (int32_t)(kNSpecial + (int64_t)(h % mod));
  }
  return n_out;
}

}  // namespace

extern "C" void pw_tokenize_batch(
    const uint8_t** texts, const int64_t* text_lens, int64_t n,
    const uint8_t** pairs, const int64_t* pair_lens,  // nullable
    int64_t max_length, int64_t vocab_size, int lowercase,
    int32_t* out_ids, int32_t* out_mask) {
  for (int64_t row = 0; row < n; row++) {
    int32_t* ids = out_ids + row * max_length;
    int32_t* mask = out_mask + row * max_length;
    std::memset(ids, 0, sizeof(int32_t) * (size_t)max_length);
    std::memset(mask, 0, sizeof(int32_t) * (size_t)max_length);

    int64_t pos = 0;
    ids[pos++] = kCls;
    pos += tokenize(texts[row], text_lens[row], vocab_size, lowercase,
                    ids + pos, max_length - 2 - (pos - 1));
    ids[pos++] = kSep;
    if (pairs != nullptr) {
      if (pos > max_length / 2) {
        // truncating the first segment leaves its stale ids beyond the new
        // pos; re-zero so a shorter pair text matches the Python fallback
        // bit-for-bit even for consumers that ignore the mask
        pos = max_length / 2;
        std::memset(ids + pos, 0, sizeof(int32_t) * (size_t)(max_length - pos));
      }
      pos += tokenize(pairs[row], pair_lens[row], vocab_size, lowercase,
                      ids + pos, max_length - pos - 1);
      if (pos < max_length) ids[pos++] = kSep;
    }
    for (int64_t j = 0; j < pos; j++) mask[j] = 1;
  }
}

// ---------------------------------------------------------------------------
// Watched-directory poll (io/fs), in two passes with the caller's commit
// between them.
//
// Pass 1, pw_fs_list: mirrors, for a directory `root` and a base-name pattern
// without `/` or `[`,
//   sorted(f for f in glob.glob(root + "/**/" + pattern, recursive=True)
//          if os.path.isfile(f))
// and tells the files the caller does not know yet (with os.stat's st_mtime
// and st_size of each) from the known ones, which it only marks as listed:
// readdir gives the names, and a name the caller holds costs no system call
// here, so the pass costs the entries that are there plus one fstatat a NEW
// file.  `**` enters every directory whose name does not start with `.`,
// symlinked ones too; a pattern with `*` or `?` skips names that start with
// `.` unless it starts with `.` itself, and a pattern without either is
// compared as it is; a new name is kept when stat (links followed) says
// regular file.  A directory is opened by its whole path, as os.scandir opens
// it, so a loop of symlinks ends where the kernel ends it for Python (ELOOP,
// ENAMETOOLONG).
//
// Pass 2, pw_fs_stat: one fstatat of every file the caller names, relative to
// its directory opened once (a stat by path walks the path again for every
// file), for the caller to compare with the (mtime, size) it holds.
// ---------------------------------------------------------------------------

namespace {

// Bytes of the UTF-8 sequence at p: what a Python str holds as one
// character.  An undecodable byte counts as one, as os.fsdecode's
// surrogateescape makes it.
inline size_t unit_len(const uint8_t* p, size_t n) {
  uint8_t c = p[0];
  if (c < 0x80) return 1;
  size_t more;
  uint8_t lo = 0x80, hi = 0xBF;
  if (c >= 0xC2 && c <= 0xDF) {
    more = 1;
  } else if (c >= 0xE0 && c <= 0xEF) {
    more = 2;
    if (c == 0xE0) lo = 0xA0;
    if (c == 0xED) hi = 0x9F;
  } else if (c >= 0xF0 && c <= 0xF4) {
    more = 3;
    if (c == 0xF0) lo = 0x90;
    if (c == 0xF4) hi = 0x8F;
  } else {
    return 1;
  }
  if (n <= more || p[1] < lo || p[1] > hi) return 1;
  for (size_t i = 2; i <= more; i++)
    if ((p[i] & 0xC0) != 0x80) return 1;
  return more + 1;
}

// fnmatch.fnmatchcase for a pattern without `[`: `*` any run of characters,
// `?` one character, anything else itself; the whole name has to match.
bool name_matches(const uint8_t* pat, size_t np, const uint8_t* s, size_t ns) {
  size_t pi = 0, si = 0, star_pi = SIZE_MAX, star_si = 0;
  while (si < ns) {
    if (pi < np && pat[pi] == '*') {
      star_pi = ++pi;
      star_si = si;
      continue;
    }
    size_t sl = unit_len(s + si, ns - si);
    if (pi < np) {
      if (pat[pi] == '?') {
        pi++;
        si += sl;
        continue;
      }
      size_t pl = unit_len(pat + pi, np - pi);
      if (pl == sl && std::memcmp(pat + pi, s + si, sl) == 0) {
        pi += pl;
        si += sl;
        continue;
      }
    }
    if (star_pi == SIZE_MAX) return false;
    star_si += unit_len(s + star_si, ns - star_si);  // the star takes one more
    si = star_si;
    pi = star_pi;
  }
  while (pi < np && pat[pi] == '*') pi++;
  return pi == np;
}

struct FsFile {
  std::string path;
  double mtime;
  int64_t size;
};

// the float os.stat gives: st_mtime = sec + 1e-9 * nsec
inline double mtime_of(const struct stat& st) {
  return (double)st.st_mtim.tv_sec + 1e-9 * (double)st.st_mtim.tv_nsec;
}

struct FsPattern {
  const uint8_t* bytes;
  size_t len;
  bool magic;   // holds `*` or `?`: matched, and hidden names skipped
  bool hidden;  // starts with `.`: hidden names are not skipped
};

struct FsListing {
  FsPattern pat;
  std::unordered_map<std::string_view, int64_t> known;  // path -> its index
  std::vector<uint8_t> listed;                          // per known path
  std::vector<FsFile> files;                            // the new ones
  int64_t entries = 0;
  int64_t stats = 0;
};

// false when `dir` (which ends with '/') cannot be listed
bool list_dir(const std::string& dir, FsListing* out) {
  DIR* d = opendir(dir.c_str());
  if (d == nullptr) return false;
  const int fd = dirfd(d);
  const FsPattern& pat = out->pat;
  std::vector<std::string> subdirs;
  std::string path;
  while (struct dirent* e = readdir(d)) {
    const char* name = e->d_name;
    if (name[0] == '.' && (name[1] == 0 || (name[1] == '.' && name[2] == 0)))
      continue;
    ++out->entries;
    const size_t len = std::strlen(name);
    const bool hidden = name[0] == '.';
    const bool wanted =
        pat.magic ? ((!hidden || pat.hidden) &&
                     name_matches(pat.bytes, pat.len, (const uint8_t*)name, len))
                  : (len == pat.len && std::memcmp(name, pat.bytes, len) == 0);
    const unsigned char type = e->d_type;
    if (wanted && type != DT_DIR) {
      path.assign(dir).append(name, len);
      const auto hit = out->known.find(path);
      if (hit != out->known.end()) {
        // a known file: pass 2 asks for its time and size, and says so if it
        // is a directory now (the caller then lists again)
        out->listed[hit->second] = 1;
        continue;
      }
    }
    // a wanted new name is stat'ed for its time and size; any other only
    // where the directory entry does not say whether `**` enters it
    const bool ask = wanted ? type != DT_DIR
                            : (!hidden && (type == DT_LNK || type == DT_UNKNOWN));
    struct stat st;
    if (ask) ++out->stats;
    const bool answered = ask && fstatat(fd, name, &st, 0) == 0;
    if (type == DT_DIR || (answered && S_ISDIR(st.st_mode))) {
      if (!hidden) subdirs.emplace_back(name, len);  // `**` enters no hidden one
    } else if (wanted && answered && S_ISREG(st.st_mode)) {
      out->files.push_back({dir + name, mtime_of(st), (int64_t)st.st_size});
    }
  }
  closedir(d);  // before the children: one descriptor however deep the tree
  for (const std::string& sub : subdirs)
    list_dir(dir + sub + "/", out);  // as glob: unlistable is empty
  return true;
}

}  // namespace

// What the caller sees of pass 1: `n` NEW regular files sorted by path (byte
// order, which is str order for UTF-8), their paths joined by NUL, and per
// file st_mtime and st_size; `missing` holds, ascending, the indices of the
// `n_missing` known paths that the listing does not hold; `entries` counts
// the directory entries read and is -1 when `root` itself cannot be listed (a
// single file, or nothing); `stats` counts the fstatat calls made.
struct PwFsList {
  int64_t n;
  int64_t entries;
  int64_t stats;
  int64_t paths_len;
  const char* paths;
  const double* mtimes;
  const int64_t* sizes;
  int64_t n_missing;
  const int64_t* missing;
};

namespace {
struct FsListOwner {
  PwFsList view;  // first: the pointer handed out is the owner's
  std::string paths;
  std::vector<double> mtimes;
  std::vector<int64_t> sizes;
  std::vector<int64_t> missing;
};
}  // namespace

// `root` ends with '/'; `known` holds `n_known` paths, each ended by NUL, and
// outlives the call.  Returns nullptr only when memory ran out.
extern "C" PwFsList* pw_fs_list(const char* root, const char* pattern,
                                const char* known, int64_t n_known) {
  try {
    FsListing listing;
    listing.pat = {(const uint8_t*)pattern, std::strlen(pattern),
                   std::strpbrk(pattern, "*?") != nullptr, pattern[0] == '.'};
    listing.known.reserve((size_t)n_known);
    listing.listed.assign((size_t)n_known, 0);
    for (int64_t i = 0; i < n_known; i++) {
      const std::string_view path(known);
      listing.known.emplace(path, i);
      known += path.size() + 1;
    }
    if (!list_dir(root, &listing)) listing.entries = -1;
    std::vector<FsFile>& files = listing.files;
    std::sort(files.begin(), files.end(),
              [](const FsFile& a, const FsFile& b) { return a.path < b.path; });
    auto* out = new FsListOwner();
    out->mtimes.reserve(files.size());
    out->sizes.reserve(files.size());
    for (const FsFile& f : files) {
      if (!out->paths.empty()) out->paths.push_back('\0');
      out->paths += f.path;
      out->mtimes.push_back(f.mtime);
      out->sizes.push_back(f.size);
    }
    for (int64_t i = 0; i < n_known; i++)
      if (!listing.listed[(size_t)i]) out->missing.push_back(i);
    out->view = {(int64_t)files.size(), listing.entries, listing.stats,
                 (int64_t)out->paths.size(), out->paths.data(),
                 out->mtimes.data(), out->sizes.data(),
                 (int64_t)out->missing.size(), out->missing.data()};
    return &out->view;
  } catch (...) {
    return nullptr;
  }
}

extern "C" void pw_fs_list_free(PwFsList* listing) {
  delete reinterpret_cast<FsListOwner*>(listing);
}

// What the caller sees of pass 2, per path it named: `kinds` (0 a regular
// file, whose st_mtime and st_size follow; 1 a directory; 2 anything else,
// or nothing there).
struct PwFsStat {
  int64_t n;
  const double* mtimes;
  const int64_t* sizes;
  const uint8_t* kinds;
};

namespace {
struct FsStatOwner {
  PwFsStat view;
  std::vector<double> mtimes;
  std::vector<int64_t> sizes;
  std::vector<uint8_t> kinds;
};

struct Fd {
  int fd;
  ~Fd() {
    if (fd >= 0) close(fd);
  }
};
}  // namespace

// `paths` holds `n` paths, each ended by NUL.  One fstatat a path (links
// followed), files of one directory one after the other so that the
// directory is opened once.  Returns nullptr only when memory ran out.
extern "C" PwFsStat* pw_fs_stat(const char* paths, int64_t n) {
  try {
    struct Named {
      std::string_view dir;  // up to and with the last '/'
      const char* name;
      int64_t at;
    };
    std::vector<Named> order;
    order.reserve((size_t)n);
    for (int64_t i = 0; i < n; i++) {
      const std::string_view path(paths);
      const size_t cut = path.rfind('/') + 1;  // 0 where there is none
      order.push_back({path.substr(0, cut), paths + cut, i});
      paths += path.size() + 1;
    }
    std::stable_sort(order.begin(), order.end(),
                     [](const Named& a, const Named& b) { return a.dir < b.dir; });
    auto* out = new FsStatOwner();
    out->mtimes.assign((size_t)n, 0.0);
    out->sizes.assign((size_t)n, 0);
    out->kinds.assign((size_t)n, 2);
    std::string dir;
    for (size_t i = 0; i < order.size();) {
      dir.assign(order[i].dir.empty() ? std::string_view("./") : order[i].dir);
      const Fd d{open(dir.c_str(), O_RDONLY | O_DIRECTORY | O_CLOEXEC)};
      for (const std::string_view of = order[i].dir;
           i < order.size() && order[i].dir == of; i++) {
        struct stat st;
        if (d.fd < 0 || fstatat(d.fd, order[i].name, &st, 0) != 0) continue;
        const size_t at = (size_t)order[i].at;
        if (S_ISREG(st.st_mode)) {
          out->kinds[at] = 0;
          out->mtimes[at] = mtime_of(st);
          out->sizes[at] = (int64_t)st.st_size;
        } else if (S_ISDIR(st.st_mode)) {
          out->kinds[at] = 1;
        }
      }
    }
    out->view = {n, out->mtimes.data(), out->sizes.data(), out->kinds.data()};
    return &out->view;
  } catch (...) {
    return nullptr;
  }
}

extern "C" void pw_fs_stat_free(PwFsStat* stats) {
  delete reinterpret_cast<FsStatOwner*>(stats);
}

// The bytes of the first `n_read` of `n` files (paths joined by NUL), one
// after the other: file i ends at `ends[i]` and failed with `errs[i]` (an
// errno, 0 for none; a failed file holds no bytes).  Reading stops after the
// file that brings the total to `budget` bytes, so that a first poll of a
// large directory is not held in memory twice.
struct PwFsRead {
  int64_t n_read;
  int64_t data_len;
  const uint8_t* data;
  const int64_t* ends;
  const int32_t* errs;
};

namespace {
struct FsReadOwner {
  PwFsRead view;
  std::vector<uint8_t> data;
  std::vector<int64_t> ends;
  std::vector<int32_t> errs;
};

// Appends the file's bytes to `data`; an errno (and no bytes) if it failed.
int read_whole(const char* path, std::vector<uint8_t>* data) {
  const Fd f{open(path, O_RDONLY | O_CLOEXEC)};
  if (f.fd < 0) return errno;
  const size_t start = data->size();
  struct stat st;
  size_t room = (fstat(f.fd, &st) == 0 && st.st_size > 0) ? (size_t)st.st_size + 1 : 4096;
  for (size_t at = start;;) {
    data->resize(at + room);
    const ssize_t got = read(f.fd, data->data() + at, room);
    if (got < 0 && errno == EINTR) continue;
    if (got < 0) {
      const int err = errno;
      data->resize(start);
      return err;
    }
    at += (size_t)got;
    if (got == 0) {
      data->resize(at);
      return 0;
    }
    // a short read is most likely the end; a full one outgrew what fstat said
    room = (size_t)got < room ? 4096 : std::max<size_t>(4096, at - start);
  }
}
}  // namespace

extern "C" PwFsRead* pw_fs_read(const char* paths, int64_t n, int64_t budget) {
  try {
    auto* out = new FsReadOwner();
    const char* path = paths;
    for (int64_t i = 0; i < n; i++) {
      out->errs.push_back(read_whole(path, &out->data));
      out->ends.push_back((int64_t)out->data.size());
      path += std::strlen(path) + 1;
      if ((int64_t)out->data.size() >= budget) break;
    }
    out->view = {(int64_t)out->ends.size(), (int64_t)out->data.size(),
                 out->data.data(), out->ends.data(), out->errs.data()};
    return &out->view;
  } catch (...) {
    return nullptr;
  }
}

extern "C" void pw_fs_read_free(PwFsRead* r) {
  delete reinterpret_cast<FsReadOwner*>(r);
}

// ---------------------------------------------------------------------------
// version stamp so the loader can invalidate stale cached builds
// ---------------------------------------------------------------------------

extern "C" int pw_native_abi_version() { return 3; }
