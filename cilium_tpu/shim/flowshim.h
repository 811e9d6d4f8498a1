// flowshim: the native ingress front end (SURVEY.md §2 native checklist
// item 2 — the userspace replacement for the XDP hook: "C++ AF_XDP shim
// (umem/fill/completion rings, batch header extraction → pinned host
// buffers → TPU transfer). No Python in the packet path.").
//
// Components:
//  - parser: Ethernet/VLAN → IPv4/IPv6 → TCP/UDP/SCTP/ICMP header extraction
//    into the fixed 64-byte record the classifier consumes, plus an HTTP
//    request-line tokenizer filling a parallel 72-byte token record.
//  - batcher: lock-free-ish ring accumulating records until batch_size or an
//    adaptive deadline (p99-latency driven) elapses.
//  - afxdp: AF_XDP socket setup via raw syscalls (UMEM + fill/completion/rx/tx
//    rings). Compiles everywhere; at runtime it requires a privileged netns
//    and an XDP-capable driver, so the library also exposes a mock-driver
//    path (feed frames from memory) used by tests and pcap replay.
//
// C ABI only — consumed from Python via ctypes (no pybind11 in this image).

#ifndef CILIUM_TPU_FLOWSHIM_H_
#define CILIUM_TPU_FLOWSHIM_H_

#include <stddef.h>
#include <stdint.h>

#ifdef __cplusplus
extern "C" {
#endif

// ---------------------------------------------------------------------------
// Record layouts (must match cilium_tpu/kernels/records.py column order)
// ---------------------------------------------------------------------------
// 64-byte header record. Addresses are 16-byte normalized (IPv4 mapped to
// ::ffff:a.b.c.d) and stored as four BIG-ENDIAN u32 words, matching the
// device batch layout.
typedef struct __attribute__((packed)) ShimRecord {
  uint32_t src[4];     // big-endian words
  uint32_t dst[4];     // big-endian words
  uint16_t sport;      // host order
  uint16_t dport;      // host order; ICMP: type
  uint8_t proto;
  uint8_t tcp_flags;
  uint8_t is_v6;
  uint8_t direction;   // 0 egress / 1 ingress (relative to the endpoint)
  uint32_t ep_id;      // local endpoint id (0 = unclassified)
  uint32_t frame_idx;  // umem frame / mock buffer index for verdict return
  uint32_t orig_len;   // original frame length
  uint8_t pad[12];
} ShimRecord;  // == 64 bytes

// 72-byte L7 token record (parallel array; only meaningful when has_tokens).
typedef struct __attribute__((packed)) ShimTokens {
  uint8_t has_tokens;  // 1 when an HTTP request line was recognized
  uint8_t method;      // HTTP_METHOD id; 255 = none
  uint16_t path_len;
  uint8_t path[64];
  uint8_t pad[4];
} ShimTokens;  // == 72 bytes

typedef struct ShimStats {
  uint64_t frames_seen;
  uint64_t frames_parsed;
  uint64_t parse_errors;
  uint64_t batches_emitted;
  uint64_t records_emitted;
  uint64_t verdict_drops;
  uint64_t verdict_passes;
  // allowed frames lost because the tx ring was full (NIC backpressure) —
  // counted separately from verdict_passes so tx loss is diagnosable
  uint64_t tx_full_drops;
  // records whose batch aged out of the bounded unverdicted queue (a
  // harvest-only consumer — tap mode, pcap replay — never calls
  // shim_apply_verdicts; their umem frames recycle to the fill ring)
  uint64_t verdict_expired;
} ShimStats;

typedef struct Shim Shim;  // opaque

// ---------------------------------------------------------------------------
// Lifecycle
// ---------------------------------------------------------------------------
// Create a shim with a batching target: emit a batch when ``batch_size``
// records accumulated OR ``timeout_us`` elapsed since the first record of the
// batch (adaptive latency bound).
Shim* shim_create(uint32_t batch_size, uint64_t timeout_us);
void shim_destroy(Shim* s);

// Register an endpoint IP → (ep_id). The parser classifies each frame's
// direction by matching src/dst against registered endpoint addresses
// (ip16 = 16-byte normalized address).
int shim_register_endpoint(Shim* s, const uint8_t ip16[16], uint32_t ep_id);

// ---------------------------------------------------------------------------
// Mock-driver ingest (tests / pcap replay). Frames are raw Ethernet.
// Returns 0 on success, -1 on parse error (counted in stats).
// ---------------------------------------------------------------------------
int shim_feed_frame(Shim* s, const uint8_t* frame, uint32_t len,
                    uint64_t now_us);

// Harvest a ready batch (either full or timed out as of ``now_us``).
// Returns the number of records written into out_records/out_tokens
// (caller-allocated, capacity = batch_size), or 0 if no batch is ready.
// ``force`` flushes a partial batch regardless of deadline.
uint32_t shim_poll_batch(Shim* s, uint64_t now_us, int force,
                         ShimRecord* out_records, ShimTokens* out_tokens);

// Return verdicts for a previously harvested batch: allow[i] == 0 → drop.
// In AF_XDP mode this recycles/forwards umem frames; in mock mode it only
// updates stats (and the test inspects them).
void shim_apply_verdicts(Shim* s, const uint8_t* allow, uint32_t n);

void shim_get_stats(const Shim* s, ShimStats* out);

// ---------------------------------------------------------------------------
// AF_XDP mode (privileged; returns -errno on failure, e.g. in containers
// without NET_ADMIN — callers fall back to the mock driver)
// ---------------------------------------------------------------------------
int shim_afxdp_bind(Shim* s, const char* ifname, uint32_t queue_id);
// Drain up to ``budget`` frames from the rx ring into the batcher:
// completion ring → fill ring recycle, then an rx descriptor walk handing
// each umem frame to the parser. Works on the kernel-mapped rings after
// shim_afxdp_bind OR on memory-mocked rings after shim_mock_rings_init.
// Returns descriptors drained (>= 0) or -errno.
int shim_afxdp_poll(Shim* s, uint32_t budget, uint64_t now_us);

// XDP descriptor layout (mirror of the kernel's struct xdp_desc, declared
// here so the ring logic and its memory-mocked tests compile anywhere).
typedef struct ShimXdpDesc {
  uint64_t addr;
  uint32_t len;
  uint32_t options;
} ShimXdpDesc;

// ---------------------------------------------------------------------------
// Memory-mocked rings: the exact producer/consumer ring algebra of AF_XDP
// (fill/completion/rx/tx single-producer single-consumer rings over a umem
// frame pool) backed by heap memory, so the full frame lifecycle —
// fill → (mock driver) rx → parse/batch → verdict → tx or fill-recycle →
// completion → fill — is testable in an unprivileged container. The real
// shim_afxdp_bind wires the same Ring views at kernel-mapped offsets.
// ---------------------------------------------------------------------------
int shim_mock_rings_init(Shim* s, uint32_t ring_size, uint32_t frame_size,
                         uint32_t n_frames);
// Mock NIC RX: take a frame from the fill ring, copy ``frame`` into its umem
// slot, publish an rx descriptor. Returns 0, -ENOSPC (fill empty / rx full)
// or -EMSGSIZE (frame larger than the umem chunk).
int shim_mock_rx_inject(Shim* s, const uint8_t* frame, uint32_t len);
// Mock NIC TX: consume up to ``max`` tx descriptors (the frames the shim
// forwarded), then report them transmitted via the completion ring.
uint32_t shim_mock_tx_drain(Shim* s, uint64_t* addrs, uint32_t* lens,
                            uint32_t max);
// Frames currently available in the fill ring (leak/accounting checks).
uint32_t shim_ring_fill_level(const Shim* s);

// ---------------------------------------------------------------------------
// Service LB steering state (mirror of compile/lb.py's frontend hash table +
// Maglev + backend arrays). Steering must hash the TRANSLATED tuple: CT
// entries live under the DNAT'ed 5-tuple, so a service flow's forward and
// reply packets only land on the same CT shard if the shim applies the same
// deterministic translation the device kernel does.
// All arrays are copied; row-major. Pass cap=0 to clear.
// ---------------------------------------------------------------------------
int shim_set_lb(Shim* s, const uint32_t* tab_keys /*[cap*6]*/,
                const int32_t* tab_val /*[cap]*/, uint32_t cap,
                uint32_t probe_depth, const int32_t* fe_service /*[F]*/,
                uint32_t n_fe, const int32_t* maglev /*[S*M]*/, uint32_t n_svc,
                uint32_t maglev_m, const uint32_t* be_addr /*[B*4]*/,
                const int32_t* be_port /*[B]*/, uint32_t n_be);

// RSS-style flow-shard steering (must match
// cilium_tpu/parallel/mesh.flow_shard_of with the shim's LB state: service
// DNAT first, then XOR of fwd/rev murmur key hashes).
uint32_t shim_flow_shard2(const Shim* s, const ShimRecord* rec,
                          uint32_t n_shards);

// Legacy steering without LB translation (wrong for service traffic on a
// sharded mesh — kept for non-LB deployments).
uint32_t shim_flow_shard(const ShimRecord* rec, uint32_t n_shards);

// ---------------------------------------------------------------------------
// Maglev population for compile/lb.py (needs no Shim): row r has the
// backends [row_start[r], row_start[r+1]), each with the (offset, skip) of
// its permutation of [0, m) and a weight; the backends take turns in order,
// `weight` consecutive turns each, every turn claiming the backend's next
// unclaimed permutation slot, until the row's m slots are claimed. out[r*m +
// c] is the row-local index of slot c's backend, -1 throughout for a row
// without backends. The table compile/lb.py:_maglev_rows_py gives, element
// for element (tests/test_maglev_build.py). Rows are dealt over up to
// `threads` threads.
// ---------------------------------------------------------------------------
void shim_maglev_fill(const int64_t* offsets, const int64_t* skips,
                      const int32_t* weights, const int64_t* row_start,
                      uint32_t n_rows, uint32_t m, int32_t* out,
                      uint32_t threads);

#ifdef __cplusplus
}
#endif

#endif  // CILIUM_TPU_FLOWSHIM_H_
