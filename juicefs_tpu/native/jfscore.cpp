// jfscore: native data-plane primitives for juicefs_tpu.
//
// The reference implements its block data plane's hot paths natively via
// cgo (C zstd/lz4, pkg/compress/compress.go:71-120; CRC32C via Go's
// hardware-accelerated hash/crc32). This library is the rebuild's
// equivalent: hardware CRC32C and the JTH-256 content hash in C++,
// exposed through a plain C ABI consumed with ctypes (and reusable from
// any language, like the reference's libjfs C ABI in sdk/java). It also
// opens the reference's object envelope (RSA-OAEP data key, AES-256-GCM
// body) through the host's OpenSSL, found at run time: no OpenSSL headers
// or link flags are needed to build it.
//
// JTH-256 here MUST stay byte-identical to the normative numpy spec in
// juicefs_tpu/tpu/jth256.py (BASELINE.md acceptance bar); the test suite
// cross-checks all implementations. Little-endian hosts assumed (x86-64,
// aarch64) — the word view and digest serialization are uint32-LE.
//
// Build: g++ -O3 -shared -fPIC -std=c++17 -pthread jfscore.cpp -o libjfscore.so -ldl

#include <dlfcn.h>
#include <time.h>

#include <atomic>
#include <climits>
#include <cstdint>
#include <cstring>
#include <mutex>
#include <thread>
#include <vector>

#if defined(__x86_64__)
#include <cpuid.h>
#include <nmmintrin.h>
#endif

extern "C" {
uint32_t jfs_crc32c(const uint8_t *data, size_t n, uint32_t crc);
void jfs_jth256(const uint8_t *data, size_t n, uint8_t out[32]);
void jfs_jth256_batch(const uint8_t *const *blocks, const size_t *lens,
                      size_t count, uint8_t *outs, int threads);
void jfs_pack_rows(const uint8_t *const *blocks, const size_t *lens,
                   size_t count, uint8_t *rows, size_t row_bytes);
size_t jfs_touch_pages(uint8_t *p, size_t n, size_t page,
                       const volatile int *stop);
int jfs_crypto_available(const char *soname);
void *jfs_rsa_key_load(const uint8_t *der, size_t n);
void jfs_rsa_key_free(void *key);
int jfs_envelope_open(void *key, const uint8_t *blob, size_t n, uint8_t *out,
                      size_t out_len, uint64_t *unwrap_ns);
int jfs_abi_version();
}

int jfs_abi_version() { return 2; }

// ---------------------------------------------------------------- CRC32C --

static uint32_t crc32c_table[8][256];
static std::atomic<bool> table_ready{false};

static void init_table() {
  if (table_ready.load(std::memory_order_acquire)) return;
  const uint32_t poly = 0x82F63B78u;  // Castagnoli, reflected
  for (int n = 0; n < 256; n++) {
    uint32_t c = (uint32_t)n;
    for (int k = 0; k < 8; k++) c = (c & 1) ? (c >> 1) ^ poly : c >> 1;
    crc32c_table[0][n] = c;
  }
  for (int n = 0; n < 256; n++) {
    uint32_t c = crc32c_table[0][n];
    for (int k = 1; k < 8; k++) {
      c = crc32c_table[0][c & 0xFF] ^ (c >> 8);
      crc32c_table[k][n] = c;
    }
  }
  table_ready.store(true, std::memory_order_release);
}

static uint32_t crc32c_sw(const uint8_t *p, size_t n, uint32_t c) {
  init_table();
  // slicing-by-8
  while (n >= 8) {
    uint64_t word;
    memcpy(&word, p, 8);
    word ^= c;
    c = crc32c_table[7][word & 0xFF] ^ crc32c_table[6][(word >> 8) & 0xFF] ^
        crc32c_table[5][(word >> 16) & 0xFF] ^
        crc32c_table[4][(word >> 24) & 0xFF] ^
        crc32c_table[3][(word >> 32) & 0xFF] ^
        crc32c_table[2][(word >> 40) & 0xFF] ^
        crc32c_table[1][(word >> 48) & 0xFF] ^
        crc32c_table[0][(word >> 56) & 0xFF];
    p += 8;
    n -= 8;
  }
  while (n--) c = crc32c_table[0][(c ^ *p++) & 0xFF] ^ (c >> 8);
  return c;
}

#if defined(__x86_64__)
__attribute__((target("sse4.2"))) static uint32_t crc32c_hw(const uint8_t *p,
                                                            size_t n,
                                                            uint32_t c) {
  uint64_t c64 = c;
  while (n >= 8) {
    uint64_t word;
    memcpy(&word, p, 8);
    c64 = _mm_crc32_u64(c64, word);
    p += 8;
    n -= 8;
  }
  c = (uint32_t)c64;
  while (n--) c = _mm_crc32_u8(c, *p++);
  return c;
}

static bool have_sse42() {
  unsigned a, b, c, d;
  if (!__get_cpuid(1, &a, &b, &c, &d)) return false;
  return (c & bit_SSE4_2) != 0;
}
#endif

uint32_t jfs_crc32c(const uint8_t *data, size_t n, uint32_t crc) {
  uint32_t c = crc ^ 0xFFFFFFFFu;
#if defined(__x86_64__)
  static const bool hw = have_sse42();
  c = hw ? crc32c_hw(data, n, c) : crc32c_sw(data, n, c);
#else
  c = crc32c_sw(data, n, c);
#endif
  return c ^ 0xFFFFFFFFu;
}

// ---------------------------------------------------------------- JTH-256 --

static const uint32_t P1 = 0x9E3779B1u, P2 = 0x85EBCA77u, P3 = 0xC2B2AE3Du,
                      P4 = 0x27D4EB2Fu, P5 = 0x165667B1u;
static const uint32_t FM1 = 0x85EBCA6Bu, FM2 = 0xC2B2AE35u;
static const uint32_t IV[8] = {0x6A09E667u, 0xBB67AE85u, 0x3C6EF372u,
                               0xA54FF53Au, 0x510E527Fu, 0x9B05688Cu,
                               0x1F83D9ABu, 0x5BE0CD19u};

static inline uint32_t rotl32(uint32_t x, int k) {
  return (x << k) | (x >> (32 - k));
}

// One 64 KiB lane (16384 LE words as 128x128) -> 8-word lane digest.
static void lane_compress(const uint32_t *W, uint32_t lane, uint32_t out[8]) {
  uint32_t s[128];
  const uint32_t lp3 = lane * P3;
  for (uint32_t j = 0; j < 128; j++) s[j] = P5 ^ (j * P1) ^ lp3;
  for (int r = 0; r < 128; r++) {
    const uint32_t *row = W + (size_t)r * 128;
    for (int j = 0; j < 128; j++) {  // auto-vectorizes (no cross-lane deps)
      uint32_t v = (s[j] ^ row[j]) * P1;
      v = rotl32(v, 13) * P2;
      s[j] = v ^ (v >> 15);
    }
  }
  uint32_t acc[8];
  const uint32_t lp2 = lane * P2;
  for (uint32_t k = 0; k < 8; k++) acc[k] = P4 ^ lp2 ^ (k * P1);
  for (uint32_t g = 0; g < 16; g++) {
    const uint32_t gp5 = g * P5;
    for (int k = 0; k < 8; k++) {
      uint32_t v = (acc[k] ^ s[g * 8 + k]) * P3;
      acc[k] = rotl32(v, 11) + gp5;
    }
  }
  memcpy(out, acc, 32);
}

void jfs_jth256(const uint8_t *data, size_t n, uint8_t out[32]) {
  const size_t m = n ? (n + 65535) / 65536 : 1;
  uint32_t h[8];
  memcpy(h, IV, 32);
  alignas(64) uint32_t lane_buf[16384];
  for (size_t i = 0; i < m; i++) {
    const size_t off = i * 65536;
    const size_t take = n > off ? (n - off < 65536 ? n - off : 65536) : 0;
    const uint32_t *W;
    if (take == 65536 && ((uintptr_t)(data + off) % 4 == 0)) {
      W = (const uint32_t *)(data + off);  // full aligned lane: zero-copy
    } else {
      memcpy(lane_buf, data + off, take);
      memset((uint8_t *)lane_buf + take, 0, 65536 - take);
      W = lane_buf;
    }
    uint32_t acc[8];
    lane_compress(W, (uint32_t)i, acc);
    const uint32_t ip1 = (uint32_t)i * P1;
    for (int k = 0; k < 8; k++) {
      uint32_t v = (h[k] ^ acc[k]) * P2;
      h[k] = rotl32(v, 17) + ip1;
    }
  }
  for (uint32_t k = 0; k < 8; k++) {
    uint32_t v = h[k] ^ ((uint32_t)n + k * P4);
    v ^= v >> 16;
    v *= FM1;
    v ^= v >> 13;
    v *= FM2;
    v ^= v >> 16;
    h[k] = v;
  }
  memcpy(out, h, 32);  // LE host: matches uint32-LE serialization
}

void jfs_jth256_batch(const uint8_t *const *blocks, const size_t *lens,
                      size_t count, uint8_t *outs, int threads) {
  if (threads <= 1 || count <= 1) {
    for (size_t i = 0; i < count; i++)
      jfs_jth256(blocks[i], lens[i], outs + i * 32);
    return;
  }
  std::atomic<size_t> next{0};
  auto worker = [&]() {
    for (;;) {
      size_t i = next.fetch_add(1);
      if (i >= count) return;
      jfs_jth256(blocks[i], lens[i], outs + i * 32);
    }
  };
  unsigned nt = std::min<size_t>(threads, count);
  std::vector<std::thread> pool;
  for (unsigned t = 0; t < nt; t++) pool.emplace_back(worker);
  for (auto &t : pool) t.join();
}

// ------------------------------------------------------------- pack rows --

// One hash batch's host pack: block i to the start of row i, zeros to the
// end of the row. One call for the whole batch, so that the caller leaves
// and retakes the interpreter lock once and not once a block: beside ten
// GET threads each retaking costs the packing thread a queue for the lock.
// The caller has checked lens[i] <= row_bytes.
void jfs_pack_rows(const uint8_t *const *blocks, const size_t *lens,
                   size_t count, uint8_t *rows, size_t row_bytes) {
  for (size_t i = 0; i < count; i++) {
    uint8_t *row = rows + i * row_bytes;
    memcpy(row, blocks[i], lens[i]);
    memset(row + lens[i], 0, row_bytes - lens[i]);
  }
}

// Make [p, p + n) resident before somebody packs into it: one store a
// page, in one call outside the interpreter lock (a helper thread that came
// back for the lock between slices would queue 5 ms behind a busy Python
// thread every time). The bytes are the caller's to overwrite (pack_rows
// writes every row whole), so what is stored is moot. `stop`, where given,
// is looked at once a MiB: set, the call returns early. Returns how many
// bytes from p on are resident now.
size_t jfs_touch_pages(uint8_t *p, size_t n, size_t page,
                       const volatile int *stop) {
  for (size_t i = 0; i < n; i += page) {
    if (stop && (i & ((1u << 20) - 1)) == 0 && *stop) return i;
    p[i] = 0;
  }
  return n;
}

// --------------------------------------------------------------- envelope --

// The reference's envelope (pkg/object/encrypt.go): be32 len || RSA-OAEP
// (SHA-256, MGF1-SHA-256) of a 32-byte data key || 12-byte nonce ||
// AES-256-GCM body || 16-byte tag. Opened here in one call that holds no
// interpreter lock, so GET threads open side by side. OpenSSL 3's libcrypto
// is found with dlopen (first the copy the process already has) and the
// few entry points are declared by hand with opaque pointers.

namespace {

struct Crypto {
  const void *(*aes_256_gcm)();
  const void *(*sha256)();
  void *(*cipher_ctx_new)();
  void (*cipher_ctx_free)(void *);
  int (*cipher_ctx_ctrl)(void *, int, int, void *);
  int (*decrypt_init)(void *, const void *, void *, const uint8_t *,
                      const uint8_t *);
  int (*decrypt_update)(void *, uint8_t *, int *, const uint8_t *, int);
  int (*decrypt_final)(void *, uint8_t *, int *);
  void *(*d2i_auto_private_key)(void **, const uint8_t **, long);
  void (*pkey_free)(void *);
  int (*pkey_get_size)(const void *);
  void *(*pkey_ctx_new)(void *, void *);
  void (*pkey_ctx_free)(void *);
  int (*pkey_decrypt_init)(void *);
  int (*pkey_decrypt)(void *, uint8_t *, size_t *, const uint8_t *, size_t);
  int (*set_rsa_padding)(void *, int);
  int (*set_rsa_oaep_md)(void *, const void *);
  int (*set_rsa_mgf1_md)(void *, const void *);
  void (*err_clear)();
};

const int kRsaOaepPadding = 4;     // RSA_PKCS1_OAEP_PADDING
const int kCtrlGcmSetTag = 0x11;   // EVP_CTRL_GCM_SET_TAG
const size_t kNonce = 12, kTag = 16, kDataKey = 32;
const size_t kMaxModulus = 2048;   // bytes: RSA-16384; a larger key stays out

std::mutex crypto_mu;
std::atomic<const Crypto *> crypto{nullptr};

template <typename F>
bool resolve(void *lib, const char *name, F &fn) {
  fn = reinterpret_cast<F>(dlsym(lib, name));
  return fn != nullptr;
}

bool resolve_all(void *lib, Crypto &c) {
  return resolve(lib, "EVP_aes_256_gcm", c.aes_256_gcm) &&
         resolve(lib, "EVP_sha256", c.sha256) &&
         resolve(lib, "EVP_CIPHER_CTX_new", c.cipher_ctx_new) &&
         resolve(lib, "EVP_CIPHER_CTX_free", c.cipher_ctx_free) &&
         resolve(lib, "EVP_CIPHER_CTX_ctrl", c.cipher_ctx_ctrl) &&
         resolve(lib, "EVP_DecryptInit_ex", c.decrypt_init) &&
         resolve(lib, "EVP_DecryptUpdate", c.decrypt_update) &&
         resolve(lib, "EVP_DecryptFinal_ex", c.decrypt_final) &&
         resolve(lib, "d2i_AutoPrivateKey", c.d2i_auto_private_key) &&
         resolve(lib, "EVP_PKEY_free", c.pkey_free) &&
         resolve(lib, "EVP_PKEY_get_size", c.pkey_get_size) &&
         resolve(lib, "EVP_PKEY_CTX_new", c.pkey_ctx_new) &&
         resolve(lib, "EVP_PKEY_CTX_free", c.pkey_ctx_free) &&
         resolve(lib, "EVP_PKEY_decrypt_init", c.pkey_decrypt_init) &&
         resolve(lib, "EVP_PKEY_decrypt", c.pkey_decrypt) &&
         resolve(lib, "EVP_PKEY_CTX_set_rsa_padding", c.set_rsa_padding) &&
         resolve(lib, "EVP_PKEY_CTX_set_rsa_oaep_md", c.set_rsa_oaep_md) &&
         resolve(lib, "EVP_PKEY_CTX_set_rsa_mgf1_md", c.set_rsa_mgf1_md) &&
         resolve(lib, "ERR_clear_error", c.err_clear);
}

struct RsaKey {
  void *pkey;
  size_t size;  // the modulus in bytes: the wrapped key's length
};

// Overwrites what the compiler may not prove dead.
void wipe(uint8_t *p, size_t n) {
  volatile uint8_t *v = p;
  while (n--) *v++ = 0;
}

uint64_t now_ns() {
  timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return (uint64_t)ts.tv_sec * 1000000000u + (uint64_t)ts.tv_nsec;
}

// The data key of an envelope into dk; false where it does not unwrap.
// One EVP_PKEY_CTX a call: the EVP_PKEY itself is only read, which OpenSSL
// 3 allows from many threads at once.
bool unwrap(const Crypto &c, const RsaKey &k, const uint8_t *wrapped,
            uint8_t *dk, size_t dk_cap, size_t *dk_len) {
  void *ctx = c.pkey_ctx_new(k.pkey, nullptr);
  if (!ctx) return false;
  const void *md = c.sha256();
  bool ok = c.pkey_decrypt_init(ctx) > 0 &&
            c.set_rsa_padding(ctx, kRsaOaepPadding) > 0 &&
            c.set_rsa_oaep_md(ctx, md) > 0 && c.set_rsa_mgf1_md(ctx, md) > 0;
  *dk_len = dk_cap;
  ok = ok && c.pkey_decrypt(ctx, dk, dk_len, wrapped, k.size) > 0;
  c.pkey_ctx_free(ctx);
  return ok;
}

// The body opened into out (len bytes) and the tag checked by
// EVP_DecryptFinal_ex; false where anything fails.
bool open_body(const Crypto &c, const uint8_t *dk, const uint8_t *nonce,
               const uint8_t *body, size_t len, const uint8_t *tag,
               uint8_t *out) {
  void *ctx = c.cipher_ctx_new();
  if (!ctx) return false;
  bool ok = c.decrypt_init(ctx, c.aes_256_gcm(), nullptr, dk, nonce) > 0;
  const size_t step = (size_t)1 << 30;  // EVP lengths are ints
  for (size_t off = 0; ok && off < len; off += step) {
    int n = (int)(len - off < step ? len - off : step), got = 0;
    ok = c.decrypt_update(ctx, out + off, &got, body + off, n) > 0 && got == n;
  }
  int tail = 0;
  ok = ok &&
       c.cipher_ctx_ctrl(ctx, kCtrlGcmSetTag, (int)kTag,
                         const_cast<uint8_t *>(tag)) > 0 &&
       c.decrypt_final(ctx, out + len, &tail) > 0 && tail == 0;
  c.cipher_ctx_free(ctx);  // cleanses the key schedule
  return ok;
}

}  // namespace

// 1 where libcrypto (soname: e.g. "libcrypto.so.3") has every entry point
// the envelope needs; the first such library found serves every later
// open. 0, and nothing changed, otherwise.
int jfs_crypto_available(const char *soname) {
  void *lib = dlopen(soname, RTLD_NOW | RTLD_NOLOAD);
  if (!lib) lib = dlopen(soname, RTLD_NOW);
  if (!lib) return 0;
  Crypto c;
  if (!resolve_all(lib, c)) {
    dlclose(lib);
    return 0;
  }
  std::lock_guard<std::mutex> hold(crypto_mu);
  if (!crypto.load(std::memory_order_acquire))
    crypto.store(new Crypto(c), std::memory_order_release);  // never freed
  else
    dlclose(lib);  // one reference is enough
  return 1;
}

// A private RSA key from its DER (PKCS#8 or PKCS#1) -> handle, or null.
void *jfs_rsa_key_load(const uint8_t *der, size_t n) {
  const Crypto *c = crypto.load(std::memory_order_acquire);
  if (!c || n > LONG_MAX) return nullptr;
  const uint8_t *p = der;
  void *pkey = c->d2i_auto_private_key(nullptr, &p, (long)n);
  if (!pkey) {
    c->err_clear();
    return nullptr;
  }
  int size = c->pkey_get_size(pkey);
  if (size <= 0 || (size_t)size > kMaxModulus) {
    c->pkey_free(pkey);
    return nullptr;
  }
  return new RsaKey{pkey, (size_t)size};
}

void jfs_rsa_key_free(void *key) {
  const Crypto *c = crypto.load(std::memory_order_acquire);
  RsaKey *k = static_cast<RsaKey *>(key);
  if (!k) return;
  if (c) c->pkey_free(k->pkey);
  delete k;
}

// Opens blob[0, n) (untrusted: every length is checked before it is read)
// into out[0, out_len), out_len being n less the envelope's overhead.
// 0 opened; 1 `header` (the length does not fit the key or the object),
// 2 `key` (the data key does not unwrap to 32 bytes), 3 `tag` (the body
// fails its tag); -1 the call itself is wrong (no libcrypto, out_len).
// unwrap_ns gets the RSA unwrap's time alone.
int jfs_envelope_open(void *key, const uint8_t *blob, size_t n, uint8_t *out,
                      size_t out_len, uint64_t *unwrap_ns) {
  const Crypto *c = crypto.load(std::memory_order_acquire);
  const RsaKey *k = static_cast<const RsaKey *>(key);
  *unwrap_ns = 0;
  if (!c || !k) return -1;
  const size_t head = 4 + k->size + kNonce;
  if (n < head + kTag) return 1;
  const uint32_t klen = (uint32_t)blob[0] << 24 | (uint32_t)blob[1] << 16 |
                        (uint32_t)blob[2] << 8 | (uint32_t)blob[3];
  if (klen != k->size) return 1;
  if (out_len != n - head - kTag) return -1;
  uint8_t dk[kMaxModulus];  // an unwrap yields less than the modulus
  size_t dk_len = 0;
  const uint64_t t = now_ns();
  bool ok = unwrap(*c, *k, blob + 4, dk, sizeof dk, &dk_len) &&
            dk_len == kDataKey;
  *unwrap_ns = now_ns() - t;
  int rc = 2;
  if (ok) {
    rc = open_body(*c, dk, blob + 4 + k->size, blob + head, out_len,
                   blob + n - kTag, out)
             ? 0
             : 3;
  }
  wipe(dk, sizeof dk);
  if (rc) c->err_clear();
  return rc;
}
