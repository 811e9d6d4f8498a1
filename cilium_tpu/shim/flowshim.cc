// flowshim implementation. See flowshim.h for the component map.

#include "flowshim.h"

#include <errno.h>
#include <string.h>

#include <algorithm>
#include <array>
#include <cstdio>
#include <deque>
#include <thread>
#include <vector>

#if defined(__linux__) && __has_include(<linux/if_xdp.h>)
#define FLOWSHIM_HAVE_AFXDP 1
#include <linux/if_xdp.h>
#include <net/if.h>
#include <sys/mman.h>
#include <sys/socket.h>
#include <unistd.h>
#else
#define FLOWSHIM_HAVE_AFXDP 0
#endif

namespace {

// ---------------------------------------------------------------------------
// Hash — bit-identical to cilium_tpu/kernels/hashing.py (murmur3-style
// accumulate + fmix32). The steering contract depends on this equality.
// ---------------------------------------------------------------------------
constexpr uint32_t kC1 = 0xCC9E2D51u;
constexpr uint32_t kC2 = 0x1B873593u;
constexpr uint32_t kSeed = 0x9747B28Cu;

inline uint32_t rotl32(uint32_t x, int r) {
  return (x << r) | (x >> (32 - r));
}

uint32_t hash_words(const uint32_t* w, int n) {
  uint32_t h = kSeed;
  for (int i = 0; i < n; i++) {
    uint32_t k = w[i] * kC1;
    k = rotl32(k, 15);
    k = k * kC2;
    h ^= k;
    h = rotl32(h, 13);
    h = h * 5u + 0xE6546B64u;
  }
  h ^= h >> 16;
  h *= 0x85EBCA6Bu;
  h ^= h >> 13;
  h *= 0xC2B2AE35u;
  h ^= h >> 16;
  return h;
}

void ct_key_words(const ShimRecord& r, bool reverse, uint32_t out[10]) {
  uint32_t src[4], dst[4];  // copy out of the packed struct (alignment-safe)
  memcpy(src, reverse ? r.dst : r.src, 16);
  memcpy(dst, reverse ? r.src : r.dst, 16);
  uint32_t sport = reverse ? r.dport : r.sport;
  uint32_t dport = reverse ? r.sport : r.dport;
  uint32_t dir = reverse ? (1u - r.direction) : r.direction;
  for (int i = 0; i < 4; i++) out[i] = src[i];
  for (int i = 0; i < 4; i++) out[4 + i] = dst[i];
  out[8] = (sport << 16) | dport;
  out[9] = (uint32_t(r.proto) << 8) | dir;
}

inline uint32_t be32(const uint8_t* p) {
  return (uint32_t(p[0]) << 24) | (uint32_t(p[1]) << 16) |
         (uint32_t(p[2]) << 8) | uint32_t(p[3]);
}

// HTTP methods — ids must match utils/constants.py HTTP_METHOD_IDS.
const char* kMethods[] = {"GET",     "POST",  "PUT",   "DELETE", "HEAD",
                          "OPTIONS", "PATCH", "TRACE", "CONNECT"};

// Frame provenance of a record: where the bytes live so the verdict can be
// enforced on them (umem frames recycle to fill on drop, forward via tx on
// pass; mock-driver records have no frame to enforce on).
struct FrameRef {
  uint64_t addr = 0;
  uint32_t len = 0;
  bool umem = false;
};

struct PendingRecord {
  ShimRecord rec;
  ShimTokens tok;
  FrameRef frame;
};

// One single-producer/single-consumer AF_XDP ring view. The kernel maps
// producer/consumer indices and the descriptor array at fixed offsets; the
// mock backs them with heap memory. Index arithmetic is free-running uint32
// (entries = prod - cons), acquire/release on the shared indices — the same
// contract the kernel's xsk rings use.
struct Ring {
  volatile uint32_t* producer = nullptr;
  volatile uint32_t* consumer = nullptr;
  void* desc = nullptr;
  uint32_t size = 0;  // entries, power of two
};

static inline uint32_t ring_load_prod(const Ring& r) {
  return __atomic_load_n(r.producer, __ATOMIC_ACQUIRE);
}
static inline uint32_t ring_load_cons(const Ring& r) {
  return __atomic_load_n(r.consumer, __ATOMIC_ACQUIRE);
}
static inline uint32_t ring_entries(const Ring& r) {
  return ring_load_prod(r) - ring_load_cons(r);
}
static inline uint32_t ring_free(const Ring& r) {
  return r.size - ring_entries(r);
}

// fill/completion rings carry bare umem addresses (uint64)
static bool ring_push_addr(Ring& r, uint64_t addr) {
  if (ring_free(r) == 0) return false;
  uint32_t prod = *r.producer;
  static_cast<uint64_t*>(r.desc)[prod & (r.size - 1)] = addr;
  __atomic_store_n(r.producer, prod + 1, __ATOMIC_RELEASE);
  return true;
}
static bool ring_pop_addr(Ring& r, uint64_t* addr) {
  if (ring_entries(r) == 0) return false;
  uint32_t cons = *r.consumer;
  *addr = static_cast<const uint64_t*>(r.desc)[cons & (r.size - 1)];
  __atomic_store_n(r.consumer, cons + 1, __ATOMIC_RELEASE);
  return true;
}

// rx/tx rings carry descriptors
static bool ring_push_desc(Ring& r, const ShimXdpDesc& d) {
  if (ring_free(r) == 0) return false;
  uint32_t prod = *r.producer;
  static_cast<ShimXdpDesc*>(r.desc)[prod & (r.size - 1)] = d;
  __atomic_store_n(r.producer, prod + 1, __ATOMIC_RELEASE);
  return true;
}
static bool ring_pop_desc(Ring& r, ShimXdpDesc* d) {
  if (ring_entries(r) == 0) return false;
  uint32_t cons = *r.consumer;
  *d = static_cast<const ShimXdpDesc*>(r.desc)[cons & (r.size - 1)];
  __atomic_store_n(r.consumer, cons + 1, __ATOMIC_RELEASE);
  return true;
}

}  // namespace

struct Shim {
  uint32_t batch_size;
  uint64_t timeout_us;
  std::deque<PendingRecord> pending;
  uint64_t first_pending_ts = 0;
  std::vector<std::pair<std::array<uint8_t, 16>, uint32_t>> endpoints;
  ShimStats stats{};
  uint32_t next_frame_idx = 0;
  // frames of emitted-but-unverdicted batches, in emission order —
  // shim_apply_verdicts consumes the oldest batch (FIFO matches the
  // poll_batch → classify → verdict pipeline, including when several
  // batches are in flight). Bounded: harvest-only consumers (tap mode,
  // pcap replay) never apply verdicts, so old batches age out (frames
  // recycled, counted in verdict_expired). The Python binding mirrors
  // kMaxUnverdictedBatches for its per-batch count FIFO.
  std::deque<std::vector<FrameRef>> emitted_batches;
  bool enforcing = false;  // a verdict was applied → never age batches out
  // service LB steering state (see shim_set_lb)
  std::vector<uint32_t> lb_tab_keys;  // [cap*6]
  std::vector<int32_t> lb_tab_val;    // [cap]
  uint32_t lb_cap = 0;
  uint32_t lb_probe_depth = 0;
  std::vector<int32_t> lb_fe_service;  // [F]
  std::vector<int32_t> lb_maglev;      // [S*M]
  uint32_t lb_maglev_m = 0;
  std::vector<uint32_t> lb_be_addr;    // [B*4]
  std::vector<int32_t> lb_be_port;     // [B]
  // umem + rings (kernel-mapped after afxdp_bind, heap-backed after
  // mock_rings_init)
  int xsk_fd = -1;
  uint8_t* umem_area = nullptr;
  size_t umem_size = 0;
  uint32_t frame_size = 0;
  bool rings_ready = false;
  bool rings_mock = false;
  Ring fill, comp, rx, tx;
  // mock-mode backing storage
  std::vector<uint64_t> mock_addr_mem;   // fill+comp descriptor arrays
  std::vector<ShimXdpDesc> mock_desc_mem;  // rx+tx descriptor arrays
  std::vector<uint32_t> mock_idx_mem;    // producer/consumer indices
  std::vector<uint8_t> mock_umem;
#if FLOWSHIM_HAVE_AFXDP
  void* ring_maps[4] = {nullptr, nullptr, nullptr, nullptr};
  size_t ring_map_lens[4] = {0, 0, 0, 0};
#endif
};

extern "C" {

Shim* shim_create(uint32_t batch_size, uint64_t timeout_us) {
  Shim* s = new Shim();
  s->batch_size = batch_size ? batch_size : 1;
  s->timeout_us = timeout_us;
  return s;
}

void shim_destroy(Shim* s) {
#if FLOWSHIM_HAVE_AFXDP
  if (s->xsk_fd >= 0) close(s->xsk_fd);
  for (int i = 0; i < 4; i++)
    if (s->ring_maps[i]) munmap(s->ring_maps[i], s->ring_map_lens[i]);
  if (s->umem_area && !s->rings_mock) munmap(s->umem_area, s->umem_size);
#endif
  delete s;
}

int shim_register_endpoint(Shim* s, const uint8_t ip16[16], uint32_t ep_id) {
  std::array<uint8_t, 16> a;
  memcpy(a.data(), ip16, 16);
  s->endpoints.emplace_back(a, ep_id);
  return 0;
}

// ---------------------------------------------------------------------------
// Parser
// ---------------------------------------------------------------------------
static const uint8_t kV4Mapped[12] = {0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0xFF, 0xFF};

static bool parse_frame(Shim* s, const uint8_t* f, uint32_t len,
                        PendingRecord* out) {
  if (len < 14) return false;
  uint16_t ethertype = (uint16_t(f[12]) << 8) | f[13];
  uint32_t off = 14;
  if (ethertype == 0x8100 || ethertype == 0x88A8) {  // VLAN
    if (len < 18) return false;
    ethertype = (uint16_t(f[16]) << 8) | f[17];
    off = 18;
  }

  ShimRecord& r = out->rec;
  memset(&r, 0, sizeof(r));
  ShimTokens& t = out->tok;
  memset(&t, 0, sizeof(t));
  t.method = 255;

  uint8_t src16[16], dst16[16];
  uint32_t l4off;
  uint8_t proto;

  if (ethertype == 0x0800) {  // IPv4
    if (len < off + 20) return false;
    const uint8_t* ip = f + off;
    uint32_t ihl = (ip[0] & 0x0F) * 4;
    if ((ip[0] >> 4) != 4 || ihl < 20 || len < off + ihl) return false;
    // fragments with nonzero offset carry no L4 header — refuse (the
    // classifier treats them as untrackable; upstream has a fragmap)
    uint16_t frag = ((uint16_t(ip[6]) << 8) | ip[7]) & 0x1FFF;
    if (frag != 0) return false;
    proto = ip[9];
    memcpy(src16, kV4Mapped, 12);
    memcpy(src16 + 12, ip + 12, 4);
    memcpy(dst16, kV4Mapped, 12);
    memcpy(dst16 + 12, ip + 16, 4);
    l4off = off + ihl;
    r.is_v6 = 0;
  } else if (ethertype == 0x86DD) {  // IPv6 (no extension headers in v1)
    if (len < off + 40) return false;
    const uint8_t* ip = f + off;
    if ((ip[0] >> 4) != 6) return false;
    proto = ip[6];
    memcpy(src16, ip + 8, 16);
    memcpy(dst16, ip + 24, 16);
    l4off = off + 40;
    r.is_v6 = 1;
  } else {
    return false;
  }

  for (int i = 0; i < 4; i++) {
    r.src[i] = be32(src16 + 4 * i);
    r.dst[i] = be32(dst16 + 4 * i);
  }
  r.proto = proto;

  if (proto == 6) {  // TCP
    if (len < l4off + 20) return false;
    const uint8_t* tcp = f + l4off;
    r.sport = (uint16_t(tcp[0]) << 8) | tcp[1];
    r.dport = (uint16_t(tcp[2]) << 8) | tcp[3];
    r.tcp_flags = tcp[13];
    uint32_t doff = (tcp[12] >> 4) * 4;
    uint32_t payload = l4off + doff;
    if (doff >= 20 && len > payload) {
      // HTTP request-line tokenizer
      const uint8_t* p = f + payload;
      uint32_t plen = len - payload;
      for (uint32_t m = 0; m < sizeof(kMethods) / sizeof(kMethods[0]); m++) {
        size_t mlen = strlen(kMethods[m]);
        if (plen > mlen + 1 && memcmp(p, kMethods[m], mlen) == 0 &&
            p[mlen] == ' ') {
          t.has_tokens = 1;
          t.method = uint8_t(m);
          uint32_t start = mlen + 1;
          uint32_t end = start;
          while (end < plen && end - start < 64 && p[end] != ' ' &&
                 p[end] != '\r' && p[end] != '\n')
            end++;
          t.path_len = uint16_t(end - start);
          memcpy(t.path, p + start, t.path_len);
          break;
        }
      }
    }
  } else if (proto == 17 || proto == 132) {  // UDP / SCTP
    if (len < l4off + 8) return false;
    const uint8_t* l4 = f + l4off;
    r.sport = (uint16_t(l4[0]) << 8) | l4[1];
    r.dport = (uint16_t(l4[2]) << 8) | l4[3];
  } else if (proto == 1 || proto == 58) {  // ICMP / ICMPv6: type in dport
    if (len < l4off + 4) return false;
    r.dport = f[l4off];
  }

  // direction + endpoint classification: src match → egress, dst → ingress
  r.ep_id = 0;
  r.direction = 1;
  for (const auto& ep : s->endpoints) {
    if (memcmp(ep.first.data(), src16, 16) == 0) {
      r.ep_id = ep.second;
      r.direction = 0;
      break;
    }
    if (memcmp(ep.first.data(), dst16, 16) == 0) {
      r.ep_id = ep.second;
      r.direction = 1;
      break;
    }
  }
  r.orig_len = len;
  return true;
}

int shim_feed_frame(Shim* s, const uint8_t* frame, uint32_t len,
                    uint64_t now_us) {
  s->stats.frames_seen++;
  PendingRecord pr;
  if (!parse_frame(s, frame, len, &pr)) {
    s->stats.parse_errors++;
    return -1;
  }
  pr.rec.frame_idx = s->next_frame_idx++;
  if (s->pending.empty()) s->first_pending_ts = now_us;
  s->pending.push_back(pr);
  s->stats.frames_parsed++;
  return 0;
}

static constexpr size_t kMaxUnverdictedBatches = 64;

uint32_t shim_poll_batch(Shim* s, uint64_t now_us, int force,
                         ShimRecord* out_records, ShimTokens* out_tokens) {
  if (s->pending.empty()) return 0;
  bool full = s->pending.size() >= s->batch_size;
  bool timed_out = now_us - s->first_pending_ts >= s->timeout_us;
  if (!full && !timed_out && !force) return 0;
  uint32_t n = std::min<size_t>(s->pending.size(), s->batch_size);
  std::vector<FrameRef> frames;
  frames.reserve(n);
  for (uint32_t i = 0; i < n; i++) {
    out_records[i] = s->pending.front().rec;
    out_tokens[i] = s->pending.front().tok;
    frames.push_back(s->pending.front().frame);
    s->pending.pop_front();
  }
  s->emitted_batches.push_back(std::move(frames));
  // age out ONLY for harvest-only consumers: once a verdict has ever been
  // applied the consumer is enforcing, and evicting would desync every
  // later verdict onto the wrong batch's frames (off-by-one enforcement —
  // worse than unbounded growth, which backpressure bounds in practice)
  while (!s->enforcing &&
         s->emitted_batches.size() > kMaxUnverdictedBatches) {
    for (const FrameRef& fr : s->emitted_batches.front()) {
      s->stats.verdict_expired++;
      if (fr.umem && s->rings_ready) ring_push_addr(s->fill, fr.addr);
    }
    s->emitted_batches.pop_front();
  }
  if (!s->pending.empty()) s->first_pending_ts = now_us;
  s->stats.batches_emitted++;
  s->stats.records_emitted += n;
  return n;
}

static void kick_tx(Shim* s) {
#if FLOWSHIM_HAVE_AFXDP
  if (s->xsk_fd >= 0)
    sendto(s->xsk_fd, nullptr, 0, MSG_DONTWAIT, nullptr, 0);
#else
  (void)s;
#endif
}

void shim_apply_verdicts(Shim* s, const uint8_t* allow, uint32_t n) {
  s->enforcing = true;
  bool sent = false;
  std::vector<FrameRef> frames;
  if (!s->emitted_batches.empty()) {
    frames = std::move(s->emitted_batches.front());
    s->emitted_batches.pop_front();
  }
  for (uint32_t i = 0; i < n; i++) {
    FrameRef fr;
    if (i < frames.size()) fr = frames[i];
    if (allow[i]) {
      if (fr.umem && s->rings_ready) {
        // forward: hand the frame to the tx ring; the frame returns to the
        // fill ring via the completion ring once the NIC is done with it
        ShimXdpDesc d{fr.addr, fr.len, 0};
        if (ring_push_desc(s->tx, d)) {
          sent = true;
          s->stats.verdict_passes++;
        } else {
          // tx ring full → drop rather than leak the frame; counted apart
          // from policy drops so NIC backpressure loss is visible
          s->stats.tx_full_drops++;
          ring_push_addr(s->fill, fr.addr);
        }
      } else {
        s->stats.verdict_passes++;
      }
    } else {
      s->stats.verdict_drops++;
      if (fr.umem && s->rings_ready) ring_push_addr(s->fill, fr.addr);
    }
  }
  // verdicts short of the batch's record count: the rest fail closed
  // (dropped + recycled) rather than leaking frames
  for (size_t i = n; i < frames.size(); i++) {
    s->stats.verdict_drops++;
    if (frames[i].umem && s->rings_ready)
      ring_push_addr(s->fill, frames[i].addr);
  }
  if (sent) kick_tx(s);
}

void shim_get_stats(const Shim* s, ShimStats* out) { *out = s->stats; }

uint32_t shim_flow_shard(const ShimRecord* rec, uint32_t n_shards) {
  uint32_t fwd[10], rev[10];
  ct_key_words(*rec, false, fwd);
  ct_key_words(*rec, true, rev);
  return (hash_words(fwd, 10) ^ hash_words(rev, 10)) % n_shards;
}

int shim_set_lb(Shim* s, const uint32_t* tab_keys, const int32_t* tab_val,
                uint32_t cap, uint32_t probe_depth, const int32_t* fe_service,
                uint32_t n_fe, const int32_t* maglev, uint32_t n_svc,
                uint32_t maglev_m, const uint32_t* be_addr,
                const int32_t* be_port, uint32_t n_be) {
  if (cap == 0) {
    s->lb_cap = 0;
    return 0;
  }
  if (cap & (cap - 1)) return -1;  // capacity must be a power of two
  s->lb_tab_keys.assign(tab_keys, tab_keys + size_t(cap) * 6);
  s->lb_tab_val.assign(tab_val, tab_val + cap);
  s->lb_cap = cap;
  s->lb_probe_depth = probe_depth;
  s->lb_fe_service.assign(fe_service, fe_service + n_fe);
  s->lb_maglev.assign(maglev, maglev + size_t(n_svc) * maglev_m);
  s->lb_maglev_m = maglev_m;
  s->lb_be_addr.assign(be_addr, be_addr + size_t(n_be) * 4);
  s->lb_be_port.assign(be_port, be_port + n_be);
  return 0;
}

// Mirror of compile/lb.lb_translate_np for one record: frontend probe →
// Maglev backend select → DNAT of (dst, dport). no-backend frontends stay
// untranslated (those packets drop; any shard is correct and this matches
// the host mirror).
static bool lb_translate(const Shim* s, const ShimRecord& r,
                         uint32_t new_dst[4], uint16_t* new_dport) {
  if (s->lb_cap == 0) return false;
  uint32_t dst[4];
  memcpy(dst, r.dst, 16);
  uint32_t key[6] = {dst[0], dst[1], dst[2], dst[3], uint32_t(r.dport),
                     uint32_t(r.proto)};
  uint32_t mask = s->lb_cap - 1;
  uint32_t base = hash_words(key, 6) & mask;
  int32_t fe = -1;
  for (uint32_t d = 0; d < s->lb_probe_depth && fe < 0; d++) {
    uint32_t slot = (base + d) & mask;
    if (s->lb_tab_val[slot] < 0) continue;
    if (memcmp(&s->lb_tab_keys[size_t(slot) * 6], key, 24) == 0)
      fe = s->lb_tab_val[slot];
  }
  if (fe < 0) return false;
  uint32_t src[4];
  memcpy(src, r.src, 16);
  uint32_t sel[10] = {src[0], src[1], src[2], src[3],
                      dst[0], dst[1], dst[2], dst[3],
                      (uint32_t(r.sport) << 16) | uint32_t(r.dport),
                      uint32_t(r.proto) << 8};
  uint32_t slot = hash_words(sel, 10) % s->lb_maglev_m;
  int32_t be =
      s->lb_maglev[size_t(s->lb_fe_service[fe]) * s->lb_maglev_m + slot];
  if (be < 0) return false;
  memcpy(new_dst, &s->lb_be_addr[size_t(be) * 4], 16);
  *new_dport = uint16_t(s->lb_be_port[be]);
  return true;
}

uint32_t shim_flow_shard2(const Shim* s, const ShimRecord* rec,
                          uint32_t n_shards) {
  ShimRecord r = *rec;
  uint32_t new_dst[4];
  uint16_t new_dport;
  if (lb_translate(s, r, new_dst, &new_dport)) {
    memcpy(r.dst, new_dst, 16);
    r.dport = new_dport;
  }
  return shim_flow_shard(&r, n_shards);
}

static void maglev_fill_row(const int64_t* offsets, const int64_t* skips,
                            const int32_t* weights, int64_t n, int64_t m,
                            int32_t* row) {
  std::fill(row, row + m, int32_t(-1));
  if (n == 0) return;
  std::vector<int64_t> at(offsets, offsets + n);  // next permutation slot
  for (int64_t filled = 0; filled < m;) {
    for (int64_t i = 0; i < n && filled < m; i++) {
      for (int32_t w = 0; w < weights[i] && filled < m; w++) {
        int64_t c = at[i];
        while (row[c] >= 0) {
          c += skips[i];
          if (c >= m) c -= m;
        }
        row[c] = int32_t(i);
        filled++;
        c += skips[i];
        at[i] = c >= m ? c - m : c;
      }
    }
  }
}

void shim_maglev_fill(const int64_t* offsets, const int64_t* skips,
                      const int32_t* weights, const int64_t* row_start,
                      uint32_t n_rows, uint32_t m, int32_t* out,
                      uint32_t threads) {
  auto rows = [&](uint32_t from, uint32_t step) {
    for (uint32_t r = from; r < n_rows; r += step) {
      int64_t b = row_start[r];
      maglev_fill_row(offsets + b, skips + b, weights + b,
                      row_start[r + 1] - b, m, out + size_t(r) * m);
    }
  };
  uint32_t k = std::max(1u, std::min(threads, n_rows));
  std::vector<std::thread> pool;
  for (uint32_t t = 1; t < k; t++) pool.emplace_back(rows, t, k);
  rows(0, k);
  for (auto& th : pool) th.join();
}

// ---------------------------------------------------------------------------
// The ring-draining packet path (shared by kernel-mapped and mocked rings):
//   1. completion → fill: frames the NIC finished transmitting recycle;
//   2. rx walk: each descriptor's umem frame goes through the parser into
//      the batcher, carrying its FrameRef for verdict enforcement;
//      unparseable frames recycle to the fill ring immediately (they never
//      reach the classifier — the upstream analog is an XDP_DROP before the
//      tc layer).
// ---------------------------------------------------------------------------
int shim_afxdp_poll(Shim* s, uint32_t budget, uint64_t now_us) {
  if (!s->rings_ready) return s->xsk_fd < 0 ? -EBADF : -EINVAL;
  uint64_t addr;
  while (ring_pop_addr(s->comp, &addr)) ring_push_addr(s->fill, addr);

  uint32_t drained = 0;
  ShimXdpDesc d;
  while (drained < budget && ring_entries(s->rx) > 0) {
    if (!ring_pop_desc(s->rx, &d)) break;
    drained++;
    s->stats.frames_seen++;
    const uint8_t* frame = s->umem_area + d.addr;
    PendingRecord pr;
    if (!parse_frame(s, frame, d.len, &pr)) {
      s->stats.parse_errors++;
      ring_push_addr(s->fill, d.addr);
      continue;
    }
    pr.rec.frame_idx = s->next_frame_idx++;
    pr.frame = FrameRef{d.addr, d.len, true};
    if (s->pending.empty()) s->first_pending_ts = now_us;
    s->pending.push_back(pr);
    s->stats.frames_parsed++;
  }
  return int(drained);
}

// ---------------------------------------------------------------------------
// Memory-mocked rings (unprivileged testbench for the path above)
// ---------------------------------------------------------------------------
int shim_mock_rings_init(Shim* s, uint32_t ring_size, uint32_t frame_size,
                         uint32_t n_frames) {
  if (s->rings_ready) return -EBUSY;
  if (!ring_size || (ring_size & (ring_size - 1))) return -EINVAL;
  if (!frame_size || !n_frames) return -EINVAL;
  s->mock_umem.assign(size_t(frame_size) * n_frames, 0);
  s->umem_area = s->mock_umem.data();
  s->umem_size = s->mock_umem.size();
  s->frame_size = frame_size;
  s->mock_addr_mem.assign(size_t(ring_size) * 2, 0);
  s->mock_desc_mem.assign(size_t(ring_size) * 2, ShimXdpDesc{});
  s->mock_idx_mem.assign(8, 0);
  s->fill = Ring{&s->mock_idx_mem[0], &s->mock_idx_mem[1],
                 s->mock_addr_mem.data(), ring_size};
  s->comp = Ring{&s->mock_idx_mem[2], &s->mock_idx_mem[3],
                 s->mock_addr_mem.data() + ring_size, ring_size};
  s->rx = Ring{&s->mock_idx_mem[4], &s->mock_idx_mem[5],
               s->mock_desc_mem.data(), ring_size};
  s->tx = Ring{&s->mock_idx_mem[6], &s->mock_idx_mem[7],
               s->mock_desc_mem.data() + ring_size, ring_size};
  for (uint32_t i = 0; i < n_frames && ring_free(s->fill); i++)
    ring_push_addr(s->fill, uint64_t(i) * frame_size);
  s->rings_ready = true;
  s->rings_mock = true;
  return 0;
}

int shim_mock_rx_inject(Shim* s, const uint8_t* frame, uint32_t len) {
  if (!s->rings_mock) return -EINVAL;
  if (len > s->frame_size) return -EMSGSIZE;
  if (ring_free(s->rx) == 0) return -ENOSPC;
  uint64_t addr;
  if (!ring_pop_addr(s->fill, &addr)) return -ENOSPC;
  memcpy(s->umem_area + addr, frame, len);
  ring_push_desc(s->rx, ShimXdpDesc{addr, len, 0});
  return 0;
}

uint32_t shim_mock_tx_drain(Shim* s, uint64_t* addrs, uint32_t* lens,
                            uint32_t max) {
  if (!s->rings_mock) return 0;
  uint32_t n = 0;
  ShimXdpDesc d;
  while (n < max && ring_pop_desc(s->tx, &d)) {
    if (addrs) addrs[n] = d.addr;
    if (lens) lens[n] = d.len;
    ring_push_addr(s->comp, d.addr);  // "transmitted" → completion
    n++;
  }
  return n;
}

uint32_t shim_ring_fill_level(const Shim* s) {
  return s->rings_ready ? ring_entries(s->fill) : 0;
}

// ---------------------------------------------------------------------------
// AF_XDP socket setup (privileged; graceful -errno in unprivileged
// containers — callers fall back to mock rings or the mock driver)
// ---------------------------------------------------------------------------
#if FLOWSHIM_HAVE_AFXDP
static constexpr uint32_t kFrameSize = 2048;
static constexpr uint32_t kNumFrames = 4096;

int shim_afxdp_bind(Shim* s, const char* ifname, uint32_t queue_id) {
  if (s->rings_ready) return -EBUSY;
  unsigned ifindex = if_nametoindex(ifname);
  if (!ifindex) return -ENODEV;
  int fd = socket(AF_XDP, SOCK_RAW, 0);
  if (fd < 0) return -errno;

  size_t umem_size = size_t(kFrameSize) * kNumFrames;
  void* area = mmap(nullptr, umem_size, PROT_READ | PROT_WRITE,
                    MAP_PRIVATE | MAP_ANONYMOUS | MAP_POPULATE, -1, 0);
  if (area == MAP_FAILED) {
    close(fd);
    return -errno;
  }
  struct xdp_umem_reg umem_reg = {};
  umem_reg.addr = reinterpret_cast<uint64_t>(area);
  umem_reg.len = umem_size;
  umem_reg.chunk_size = kFrameSize;
  if (setsockopt(fd, SOL_XDP, XDP_UMEM_REG, &umem_reg, sizeof(umem_reg)) < 0) {
    int err = -errno;
    munmap(area, umem_size);
    close(fd);
    return err;
  }
  uint32_t ring_sz = kNumFrames;
  setsockopt(fd, SOL_XDP, XDP_UMEM_FILL_RING, &ring_sz, sizeof(ring_sz));
  setsockopt(fd, SOL_XDP, XDP_UMEM_COMPLETION_RING, &ring_sz, sizeof(ring_sz));
  setsockopt(fd, SOL_XDP, XDP_RX_RING, &ring_sz, sizeof(ring_sz));
  setsockopt(fd, SOL_XDP, XDP_TX_RING, &ring_sz, sizeof(ring_sz));

  // map the four rings at the kernel-reported offsets
  struct xdp_mmap_offsets off = {};
  socklen_t optlen = sizeof(off);
  if (getsockopt(fd, SOL_XDP, XDP_MMAP_OFFSETS, &off, &optlen) < 0) {
    int err = -errno;
    munmap(area, umem_size);
    close(fd);
    return err;
  }
  struct MapSpec {
    uint64_t pgoff;
    uint64_t prod_off, cons_off, desc_off;
    uint32_t entries;
    size_t desc_bytes;
    Ring* ring;
  } specs[4] = {
      {XDP_UMEM_PGOFF_FILL_RING, off.fr.producer, off.fr.consumer,
       off.fr.desc, ring_sz, sizeof(uint64_t), &s->fill},
      {XDP_UMEM_PGOFF_COMPLETION_RING, off.cr.producer, off.cr.consumer,
       off.cr.desc, ring_sz, sizeof(uint64_t), &s->comp},
      {XDP_PGOFF_RX_RING, off.rx.producer, off.rx.consumer, off.rx.desc,
       ring_sz, sizeof(struct xdp_desc), &s->rx},
      {XDP_PGOFF_TX_RING, off.tx.producer, off.tx.consumer, off.tx.desc,
       ring_sz, sizeof(struct xdp_desc), &s->tx},
  };
  for (int i = 0; i < 4; i++) {
    size_t len = specs[i].desc_off + size_t(specs[i].entries) *
                                         specs[i].desc_bytes;
    void* m = mmap(nullptr, len, PROT_READ | PROT_WRITE,
                   MAP_SHARED | MAP_POPULATE, fd, specs[i].pgoff);
    if (m == MAP_FAILED) {
      int err = -errno;
      for (int j = 0; j < i; j++) {
        munmap(s->ring_maps[j], s->ring_map_lens[j]);
        s->ring_maps[j] = nullptr;
        s->ring_map_lens[j] = 0;
      }
      munmap(area, umem_size);
      close(fd);
      return err;
    }
    s->ring_maps[i] = m;
    s->ring_map_lens[i] = len;
    uint8_t* base = static_cast<uint8_t*>(m);
    *specs[i].ring = Ring{
        reinterpret_cast<volatile uint32_t*>(base + specs[i].prod_off),
        reinterpret_cast<volatile uint32_t*>(base + specs[i].cons_off),
        base + specs[i].desc_off, specs[i].entries};
  }

  struct sockaddr_xdp sxdp = {};
  sxdp.sxdp_family = AF_XDP;
  sxdp.sxdp_ifindex = ifindex;
  sxdp.sxdp_queue_id = queue_id;
  sxdp.sxdp_flags = XDP_COPY;  // portable; zerocopy negotiated by drivers
  if (bind(fd, reinterpret_cast<struct sockaddr*>(&sxdp), sizeof(sxdp)) < 0) {
    int err = -errno;
    for (int j = 0; j < 4; j++) {
      munmap(s->ring_maps[j], s->ring_map_lens[j]);
      s->ring_maps[j] = nullptr;
      s->ring_map_lens[j] = 0;
    }
    munmap(area, umem_size);
    close(fd);
    return err;
  }
  s->xsk_fd = fd;
  s->umem_area = static_cast<uint8_t*>(area);
  s->umem_size = umem_size;
  s->frame_size = kFrameSize;
  // prime the fill ring: hand every frame to the NIC for rx
  for (uint32_t i = 0; i < kNumFrames && ring_free(s->fill); i++)
    ring_push_addr(s->fill, uint64_t(i) * kFrameSize);
  s->rings_ready = true;
  return 0;
}
#else   // !FLOWSHIM_HAVE_AFXDP
int shim_afxdp_bind(Shim*, const char*, uint32_t) { return -38; /*ENOSYS*/ }
#endif  // FLOWSHIM_HAVE_AFXDP

}  // extern "C"
