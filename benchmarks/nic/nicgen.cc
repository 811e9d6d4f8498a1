// nicgen: the NIC's side of the shim's mock rings, for the benchmark.
//
// One blocking call, made from a thread that holds no interpreter lock
// (ctypes releases it for the call's duration), owns the three NIC-side
// operations of the single-producer/single-consumer mock rings:
//
//   shim_mock_rx_inject   fill ring (consumer) -> umem copy -> rx ring (producer)
//   shim_mock_tx_drain    tx ring (consumer) -> completion ring (producer)
//   shim_get_stats        plain read of the shim's counters
//
// The shim's own thread (the feeder) owns the other ends, so the rings'
// acquire/release indices are the only synchronisation needed. The three
// functions arrive as pointers taken from the already-loaded
// libflowshim.so; this file links against nothing of the program and takes
// only ShimStats' layout from its header.
//
// Two loops (the traffic file names one):
//   saturate  closed on ring space: inject whenever the rx and fill rings
//             have room. Nothing is lost; the rate delivered is the result.
//   open      each frame has a due time; a frame the ring refuses when the
//             loop offers it is lost (inject_t < 0), never retried. A
//             frame this loop comes to late (its own thread was off the
//             processor) is offered then, as a NIC would have put it in
//             the ring meanwhile: latency is counted from the due time, and
//             how late the loop ran is read from inject_t - due.
//
// Whose loss a refusal is (open loop). This loop spins and calls only
// lock-free ring functions, so it comes to a frame over 1 ms late only when
// its own thread did not run: the machine stopped it, not the server. The
// frames due in the stop then arrive in one burst, which a NIC would have
// spread over the stop. So coming to a frame late opens a *stop episode*:
//   - a late frame the ring refuses in it is the host's loss;
//   - so is an on-time frame refused in its aftermath, while the ring the
//     burst filled still drains, but no more of those than the ring took
//     late frames in the episode: each of them sits in a slot that an
//     on-time frame would have found free.
// The episode closes when the frames in flight (accepted - verdicts, as
// this loop reads them) are back to half the ring or less: the burst has
// been served. Or stop_cap_s after the loop was last late, whichever comes
// first: after that the program is behind by its own doing. Refusals
// inside an episode are the host's (inject_t = -2, n_refused_in_stop);
// every other refusal found the generator on time and the ring full by the
// program's doing (inject_t = -1, n_refused_on_time). Only this loop's own
// clock opens an episode: nothing the server reports enters that.
//
// The loop spins. A loop that slept 100 us between iterations woke late
// 850 times a run on the chip machine (PERF.md, PR 23).
//
// Every iteration also drains the tx ring and samples the verdict
// counters; each change is appended to a preallocated log of
// (time, drops, passes, tx_full). An entry is marked stable when the next
// sample read the same three numbers: the counters only grow, so two equal
// reads in a row mean the first was not torn by a verdict batch being
// applied under it.

#include <stdint.h>
#include <string.h>
#include <time.h>

#include "flowshim.h"

namespace {

inline double now_s() {
  timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);  // Python's time.monotonic()
  return double(ts.tv_sec) + double(ts.tv_nsec) * 1e-9;
}

inline void cpu_relax() {
#if defined(__x86_64__) || defined(__i386__)
  __builtin_ia32_pause();
#endif
}

}  // namespace

extern "C" {

typedef int (*inject_fn)(Shim*, const uint8_t*, uint32_t);
typedef uint32_t (*tx_drain_fn)(Shim*, uint64_t*, uint32_t*, uint32_t);
typedef void (*get_stats_fn)(const Shim*, ShimStats*);

// Field order and widths are mirrored by benchmarks/nic/nicgen.py.
struct NicgenRun {
  // the shim under test
  Shim* shim;
  inject_fn inject;
  tx_drain_fn tx_drain;
  get_stats_fn get_stats;
  // frames: one row per flow, schedule entry k sends flow sched_flow[k]
  const uint8_t* flow_frames;   // [n_flows, frame_stride]
  const uint16_t* flow_len;     // [n_flows]
  uint32_t frame_stride;
  uint32_t n_flows;
  const uint32_t* sched_flow;   // [n_sched]
  const double* due_s;          // [n_sched] absolute monotonic; NULL = saturate
  uint64_t n_sched;
  double t_stop_s;              // absolute: no frame is injected from here on
  double drain_deadline_s;      // absolute: give up waiting for verdicts
  // open loop: when a stop episode ends (see the head of this file)
  double stop_cap_s;            // seconds after the loop was last late
  uint64_t ring_frames;         // frames the rings can hold in flight
  // out: per schedule entry, the time it entered the ring; refused: -1 with
  // the generator on time, -2 inside a stop episode
  double* inject_t;             // [n_sched]
  // out: the verdict-counter log
  double* log_t;
  uint64_t* log_drops;
  uint64_t* log_passes;
  uint64_t* log_txfull;
  uint8_t* log_stable;
  uint64_t log_cap;
  uint64_t n_log;
  // out: totals
  uint64_t n_offered;           // schedule entries reached
  uint64_t n_accepted;
  uint64_t n_refused;           // open loop only: in_stop + on_time
  uint64_t n_refused_in_stop;   // the host's: inside a stop episode
  uint64_t n_refused_on_time;   // the program's: every other refusal
  uint64_t n_refused_aftermath; // of those in a stop, frames reached on time
  uint64_t n_stop_episodes;     // episodes in which the ring refused a frame
  double stop_s;                // this loop's gaps over 1 ms while injecting
  uint64_t n_tx_drained;
  uint64_t n_samples;
  uint64_t n_gaps_over_50us;    // sampling gaps longer than 50 us
  double max_gap_s;
  uint64_t base_verdicts;       // counter sum at entry
  uint32_t log_overflow;
  uint32_t drained;             // every accepted frame got its verdict
  volatile uint32_t stop;       // set from outside to abandon the run
  // out: the loop's own stalls. An iteration that took over 1 ms is kept
  // with the time each of its three parts took, so that a late generator
  // can be told from a slow server, and a slow call from a lost time slice.
  uint32_t n_stalls;            // all of them; the first 64 are kept
  double stall_t[64];
  double stall_inject_s[64];
  double stall_drain_s[64];
  double stall_stats_s[64];
};

static inline uint64_t verdicts_of(const ShimStats& st) {
  return st.verdict_drops + st.verdict_passes + st.tx_full_drops;
}

int nicgen_run(NicgenRun* r) {
  if (!r || !r->shim || !r->inject || !r->tx_drain || !r->get_stats ||
      !r->flow_frames || !r->flow_len || !r->sched_flow || !r->inject_t ||
      !r->log_t || !r->log_cap)
    return -1;
  if (r->due_s && (!r->ring_frames || !(r->stop_cap_s > 0.0))) return -1;
  ShimStats st, prev;
  r->get_stats(r->shim, &prev);
  r->base_verdicts = verdicts_of(prev);
  r->n_log = 0;
  r->n_offered = r->n_accepted = r->n_refused = 0;
  r->n_refused_in_stop = r->n_refused_on_time = r->n_refused_aftermath = 0;
  r->n_stop_episodes = 0;
  r->stop_s = 0.0;
  bool in_stop = false;         // a stop episode is open
  bool stop_counted = false;    // ... and is in n_stop_episodes
  uint64_t late_taken = 0;      // late frames the ring took in it, less the
                                // aftermath's refusals already set against them
  double t_last_late = 0.0;     // when the loop last came to a frame late
  r->n_tx_drained = r->n_samples = r->n_gaps_over_50us = 0;
  r->max_gap_s = 0.0;
  r->log_overflow = r->drained = 0;
  r->n_stalls = 0;
  bool prev_logged = false;     // is `prev` the newest log entry?
  uint64_t k = 0;
  double t_last = now_s();

  for (;;) {
    double now = now_s();
    double gap = now - t_last;
    t_last = now;
    if (gap > r->max_gap_s) r->max_gap_s = gap;
    if (gap > 50e-6) r->n_gaps_over_50us++;

    bool injecting = now < r->t_stop_s && k < r->n_sched && !r->stop;
    if (injecting) {
      if (gap > 1e-3) r->stop_s += gap;
      if (r->due_s) {
        // open loop: everything due by now goes in, or is lost
        uint32_t burst = 0;
        while (k < r->n_sched && r->due_s[k] <= now && burst < 256) {
          uint32_t f = r->sched_flow[k];
          int rc = f < r->n_flows
                       ? r->inject(r->shim,
                                   r->flow_frames + size_t(f) * r->frame_stride,
                                   r->flow_len[f])
                       : -1;
          bool late = now - r->due_s[k] > 1e-3;
          if (late) {
            if (!in_stop) {
              in_stop = true;
              stop_counted = false;
              late_taken = 0;
            }
            t_last_late = now;
          }
          if (rc == 0) {
            r->inject_t[k] = now;
            r->n_accepted++;
            if (late) late_taken++;
          } else if (in_stop && (late || late_taken > 0)) {
            if (!late) {
              late_taken--;
              r->n_refused_aftermath++;
            }
            if (!stop_counted) {
              stop_counted = true;
              r->n_stop_episodes++;
            }
            r->inject_t[k] = -2.0;
            r->n_refused_in_stop++;
          } else {
            r->inject_t[k] = -1.0;
            r->n_refused_on_time++;
          }
          k++;
          burst++;
        }
      } else {
        // closed on ring space
        uint32_t burst = 0;
        while (k < r->n_sched && burst < 64) {
          uint32_t f = r->sched_flow[k];
          if (f >= r->n_flows) { r->stop = 1; break; }
          int rc = r->inject(r->shim,
                             r->flow_frames + size_t(f) * r->frame_stride,
                             r->flow_len[f]);
          if (rc != 0) break;   // ring full: try again next iteration
          r->inject_t[k] = now;
          r->n_accepted++;
          k++;
          burst++;
        }
      }
      r->n_offered = k;
      r->n_refused = r->n_refused_in_stop + r->n_refused_on_time;
    }

    double t_injected = now_s();
    r->n_tx_drained += r->tx_drain(r->shim, nullptr, nullptr, 256);
    double t_drained = now_s();

    r->get_stats(r->shim, &st);
    r->n_samples++;
    double t_sampled = now_s();
    if (t_sampled - now > 1e-3 || gap > 1e-3) {
      if (r->n_stalls < 64) {
        uint32_t i = r->n_stalls;
        r->stall_t[i] = now;
        r->stall_inject_s[i] = t_injected - now;
        r->stall_drain_s[i] = t_drained - t_injected;
        r->stall_stats_s[i] = t_sampled - t_drained;
      }
      r->n_stalls++;
    }
    if (in_stop) {
      int64_t in_flight = int64_t(r->n_accepted) -
                          int64_t(verdicts_of(st) - r->base_verdicts);
      if (in_flight <= int64_t(r->ring_frames / 2) ||
          now - t_last_late > r->stop_cap_s)
        in_stop = false;
    }
    bool same = st.verdict_drops == prev.verdict_drops &&
                st.verdict_passes == prev.verdict_passes &&
                st.tx_full_drops == prev.tx_full_drops;
    if (same) {
      if (prev_logged) r->log_stable[r->n_log - 1] = 1;
    } else {
      if (r->n_log < r->log_cap) {
        uint64_t i = r->n_log++;
        r->log_t[i] = now;
        r->log_drops[i] = st.verdict_drops;
        r->log_passes[i] = st.verdict_passes;
        r->log_txfull[i] = st.tx_full_drops;
        r->log_stable[i] = 0;
        prev_logged = true;
      } else {
        r->log_overflow = 1;
        prev_logged = false;
      }
      prev = st;
    }

    if (!injecting) {
      if (verdicts_of(st) - r->base_verdicts >= r->n_accepted && same) {
        r->drained = 1;
        break;
      }
      if (now >= r->drain_deadline_s || r->stop) break;
    }
    cpu_relax();
  }
  // what the shim forwarded after the last look
  r->n_tx_drained += r->tx_drain(r->shim, nullptr, nullptr, 4096);
  return 0;
}

uint32_t nicgen_sizeof_run(void) { return uint32_t(sizeof(NicgenRun)); }

}  // extern "C"
