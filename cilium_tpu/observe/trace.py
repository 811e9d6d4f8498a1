"""Sampled span tracing for the serving path (the host-side half of
SURVEY.md §5 "tracing/profiling"; ``jax.profiler.trace`` covers the device
half, ``metrics.span`` keeps cheap aggregate timers).

Why another mechanism: SpanStat aggregates (count/total/max) cannot answer
"which *stage* made cfg4's p99 3.8x its p50" — that needs per-occurrence
records with a shared trace id across stages, and it must cost ~nothing on
the hot path. So:

- **Sampling is a counter, not an RNG.** ``maybe_sample()`` draws from an
  ``itertools.count`` (atomic under the GIL) and returns a trace id every
  Nth event (N = round(1/sample_rate)); every other event pays one
  ``next()`` + a modulo. Deterministic → tests can assert exact sample
  counts.
- **Fixed-capacity ring.** Spans land in a preallocated ring (drop-oldest);
  recording takes a lock only on the *sampled* path.
- **Trace context is a thread-local.** The pipeline worker (or classify
  caller) enters ``context(trace_id)``; downstream layers (the datapath's
  pack/transfer/compute split) attach spans to whatever trace is current
  without any signature changes across the DatapathBackend boundary.
- **A span says whose time it was.** Beside name, start and duration it
  carries the recording thread's name, the name of the span that was open
  on that thread when it opened (``parent``: self time is a span's
  duration less its children's) and its ``kind``: ``work``, or ``wait``
  for an interval in which the thread sleeps or that is recorded after
  the fact. All of it is taken on the sampled path only.
- **CPU time is the thread's, not the span's.** What a whole thread
  burns is :func:`thread_cpu_s`, the thread's own CPU clock read from
  whoever asks (``Pipeline.stats()``, ``ShimFeeder.stats()``), never on
  the hot path. No span reads a CPU clock: under gVisor, where the
  benchmark's machine runs the process, one ``time.thread_time()`` is a
  6 µs system call (0.3 µs on plain Linux), the clock advances in 10 ms
  ticks, and over millisecond spans that follow a sleep, wall less CPU
  read 0.4 of the wall with nobody else wanting the interpreter lock
  (``benchmarks/tests/host_facts.py --clock``): it is the host's time to
  put the thread back on a core, not the lock's wait, so a span's CPU
  time would say nothing there.
- **Totals outlive the ring.** ``record`` adds (count, wall) to a dict by
  name under the lock it already holds; the ring's wrap does not touch
  it, so a reader takes a window's sums from ``totals()`` at its two ends
  and the ring need not hold the run.

One process-wide instance (``TRACER``) mirrors the ``FAULTS`` singleton so
instrumentation points need no plumbing; independent ``Tracer`` objects
exist for unit tests.
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

#: span tuple layout: (trace_id, name, t0_monotonic, duration_s, attrs|None,
#: thread, parent|None, kind)
_Span = Tuple[int, str, float, float, Optional[dict], str, Optional[str],
              str]

#: a span's ``kind``: the thread ran (or wanted to) all through it ...
WORK = "work"
#: ... or it slept in it (a device wait, a held-back harvest), or the
#: interval was recorded after the fact from two stamps (a queue wait)
WAIT = "wait"

#: ``trace_id`` of a submission whose producer drew no sampling decision:
#: the pipeline draws it. (None is a decision: drawn and not sampled.)
UNDECIDED = -1

DEFAULT_CAPACITY = 4096

#: Live-state fast-path span names (ROADMAP item 3). PATCH_APPLY_SPAN
#: wraps the device-side scatter-apply of a sparse policy delta
#: (JITDatapath.place_patch — the "device-apply" half of a live rule
#: update; the host compile half rides the existing engine.regen.patch
#: span). CT_GC_SPAN wraps one overlapped chunk-sweep enqueue
#: (JITDatapath.sweep_step). Both show in the tracer summary beside the
#: pipeline's stage spans.
PATCH_APPLY_SPAN = "datapath.patch.apply"
CT_GC_SPAN = "datapath.ct.gc"
#: inside ``datapath.pack``, a batch on the L7 path-dictionary wire: the
#: dictionary's build (JITDatapath._pack_wire; attrs rows, distinct,
#: dict_rows, bytes)
L7_DICT_SPAN = "datapath.pack.l7dict"
#: inside a regeneration, full or incremental: the build of the two LPM
#: tries from the ipcache (compile/lpm.build_lpm; attrs nodes_v4, nodes_v6,
#: prefixes), as ``engine.regen.lb`` is the load-balancer tables'
LPM_BUILD_SPAN = "engine.regen.lpm"


class _NullSpan:
    """Shared no-op context for unsampled events (no allocation per call)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        pass


_NULL_SPAN = _NullSpan()


class _SpanCtx:
    """One sampled span: pushes its name on the thread's stack of open
    spans, which is what a span opened inside it records as its
    ``parent``."""

    __slots__ = ("_tracer", "_tid", "_name", "_attrs", "_kind", "_stack",
                 "_parent", "_t0")

    def __init__(self, tracer: "Tracer", tid: int, name: str, attrs,
                 kind: str):
        self._tracer = tracer
        self._tid = tid
        self._name = name
        self._attrs = attrs
        self._kind = kind

    def __enter__(self):
        stack = self._stack = _open_spans()
        self._parent = stack[-1] if stack else None
        stack.append(self._name)
        self._t0 = time.monotonic()
        return self

    def set(self, **attrs):
        """Attributes known only once the span's work is under way."""
        if self._attrs is None:
            self._attrs = attrs
        else:
            self._attrs.update(attrs)

    def __exit__(self, *exc):
        dur = time.monotonic() - self._t0
        self._stack.pop()
        self._tracer._put(self._tid, self._name, self._t0, dur,
                          self._attrs, self._parent, self._kind)
        return False


#: thread-local trace context: (tracer, trace_id) of the innermost
#: ``Tracer.context`` block, and ``stack``, the names of the sampled spans
#: open on the thread. Module-level (not per-Tracer) so downstream
#: layers attach spans to whichever tracer set the context — a Pipeline
#: constructed with an injected test tracer still gets its datapath spans.
_ACTIVE = threading.local()


def _open_spans() -> List[str]:
    stack = getattr(_ACTIVE, "stack", None)
    if stack is None:
        stack = _ACTIVE.stack = []
    return stack


def active() -> Tuple["Tracer", Optional[int]]:
    """The cross-layer read point: (tracer, trace_id) of the thread's
    current trace context, or (TRACER, None) when none is set."""
    entry = getattr(_ACTIVE, "entry", None)
    return entry if entry is not None else (TRACER, None)


def thread_cpu_s(thread: Optional[threading.Thread]) -> Optional[float]:
    """CPU seconds ``thread`` has burnt since it started, by its own CPU
    clock read from the calling thread. None where it does not run or the
    platform has no such clock."""
    if thread is None or not thread.is_alive():
        return None
    try:
        return time.clock_gettime(time.pthread_getcpuclockid(thread.ident))
    except (OSError, AttributeError):
        return None


class _TraceCtx:
    """Sets/restores the thread-local current trace context."""

    __slots__ = ("_tracer", "_tid", "_prev")

    def __init__(self, tracer: "Tracer", tid: Optional[int]):
        self._tracer = tracer
        self._tid = tid

    def __enter__(self):
        self._prev = getattr(_ACTIVE, "entry", None)
        _ACTIVE.entry = (self._tracer, self._tid)
        return self._tid

    def __exit__(self, *exc):
        _ACTIVE.entry = self._prev
        return False


class Tracer:
    def __init__(self, sample_rate: float = 0.0,
                 capacity: int = DEFAULT_CAPACITY):
        self._lock = threading.Lock()
        self._events = itertools.count()
        self._trace_ids = itertools.count(1)
        self._ring: List[Optional[_Span]] = []
        self._widx = 0
        self._filled = 0           # occupied ring slots (O(1) stats read)
        self.sampled_total = 0     # counter-sampled events (maybe_sample)
        self.forced_total = 0      # always-traced events (regen, autotune)
        # drop-oldest accounting (ISSUE 13): the ring used to wrap
        # silently — a span summary over a storm looked complete while
        # thousands of spans had been overwritten. Every overwritten slot
        # counts; wraps counts full ring cycles.
        self.spans_dropped_total = 0
        self.ring_wraps = 0
        # name -> [count, wall_s] of every span recorded since the start
        # (or reset()); the ring's wrap does not touch it
        self._totals: Dict[str, List[float]] = {}
        self.configure(sample_rate=sample_rate, capacity=capacity)

    # -- configuration -------------------------------------------------------
    def configure(self, sample_rate: Optional[float] = None,
                  capacity: Optional[int] = None) -> None:
        """Set the sampling rate (0 disables tracing entirely; 1.0 samples
        every event; 1/64 samples every 64th) and/or the ring capacity."""
        with self._lock:
            if sample_rate is not None:
                if sample_rate <= 0:
                    self._every = 0                  # disabled
                elif sample_rate >= 1.0:
                    self._every = 1
                else:
                    self._every = max(1, round(1.0 / sample_rate))
            if capacity is not None:
                if capacity < 1:
                    raise ValueError("trace capacity must be >= 1")
                # reallocate (discarding spans) only on an actual change —
                # re-stating the current capacity must not wipe the ring
                if capacity != len(self._ring):
                    self._ring = [None] * capacity
                    self._widx = 0
                    self._filled = 0

    @property
    def enabled(self) -> bool:
        return self._every > 0

    @property
    def sample_rate(self) -> float:
        return 0.0 if self._every == 0 else 1.0 / self._every

    def reset(self) -> None:
        with self._lock:
            self._ring = [None] * len(self._ring)
            self._widx = 0
            self._filled = 0
            self.sampled_total = 0
            self.forced_total = 0
            self.spans_dropped_total = 0
            self.ring_wraps = 0
            self._totals = {}
            self._events = itertools.count()
            self._trace_ids = itertools.count(1)

    # -- hot path ------------------------------------------------------------
    def maybe_sample(self) -> Optional[int]:
        """The per-event sampling decision. Unsampled cost: one atomic
        counter draw + a modulo — this is what the hot path pays."""
        every = self._every
        if every == 0:
            return None
        n = next(self._events)
        if every != 1 and n % every:
            return None
        # the sampled branch is the rare one — fine to take the lock here
        # (an unlocked += would lose increments across producer threads)
        with self._lock:
            self.sampled_total += 1
        return next(self._trace_ids)

    def force_sample(self) -> Optional[int]:
        """A trace id regardless of the sampling counter (rare events worth
        always recording — regenerations, autotune decisions). Still None
        when tracing is disabled outright."""
        if self._every == 0:
            return None
        with self._lock:
            # a separate counter: forced traces (regen, autotune decisions)
            # must not skew the sampled-submission count that coverage math
            # (sampled_total x 1/rate ~= submissions) relies on
            self.forced_total += 1
        return next(self._trace_ids)

    def span(self, trace_id: Optional[int], name: str, kind: str = WORK,
             **attrs):
        """Context manager recording one span when ``trace_id`` is not None
        (the no-op path allocates nothing, reads no clock and touches no
        thread-local)."""
        if trace_id is None:
            return _NULL_SPAN
        return _SpanCtx(self, trace_id, name, attrs or None, kind)

    def record(self, trace_id: Optional[int], name: str, t0: float,
               duration_s: float, attrs: Optional[dict] = None,
               kind: str = WORK) -> None:
        """One span recorded after the fact, from stamps the caller took:
        its thread is the calling one and its parent the span open on it
        now."""
        if trace_id is None:
            return
        stack = getattr(_ACTIVE, "stack", None)
        self._put(trace_id, name, t0, duration_s, attrs,
                  stack[-1] if stack else None, kind)

    def _put(self, trace_id: int, name: str, t0: float, duration_s: float,
             attrs: Optional[dict], parent: Optional[str],
             kind: str) -> None:
        span = (trace_id, name, t0, duration_s, attrs,
                threading.current_thread().name, parent, kind)
        with self._lock:
            ring = self._ring
            overwrote = ring[self._widx] is not None
            if not overwrote:
                self._filled += 1
            else:
                # drop-oldest: the evicted span is LOST to every later
                # summary/export — count it so /v1/trace can say how much
                # of the story the ring no longer holds
                self.spans_dropped_total += 1
            ring[self._widx] = span
            self._widx = (self._widx + 1) % len(ring)
            # a wrap is a completed cycle of LOSS, so the initial free
            # fill doesn't count — keeps drops == wraps * capacity (+
            # the partial cycle) mutually consistent
            if self._widx == 0 and overwrote:
                self.ring_wraps += 1
            tot = self._totals.get(name)
            if tot is None:
                tot = self._totals[name] = [0, 0.0]
            tot[0] += 1
            tot[1] += duration_s

    def event(self, name: str, **attrs) -> Optional[int]:
        """Record a zero-duration decision event (always, when enabled)."""
        tid = self.force_sample()
        if tid is not None:
            self.record(tid, name, time.monotonic(), 0.0, attrs or None)
        return tid

    # -- trace-context propagation -------------------------------------------
    def current(self) -> Optional[int]:
        """The thread's current trace id (whichever tracer set it)."""
        entry = getattr(_ACTIVE, "entry", None)
        return entry[1] if entry is not None else None

    def context(self, trace_id: Optional[int]) -> _TraceCtx:
        """Make ``trace_id`` the thread's current trace for the with-block
        (downstream spans attach via :func:`active` / :meth:`current`)."""
        return _TraceCtx(self, trace_id)

    # -- read side -----------------------------------------------------------
    def _snapshot(self) -> List[_Span]:
        """Ring contents oldest→newest."""
        with self._lock:
            ring, w = list(self._ring), self._widx
        ordered = ring[w:] + ring[:w]
        return [s for s in ordered if s is not None]

    def spans(self, limit: int = 100, name: Optional[str] = None,
              trace_id: Optional[int] = None) -> List[Dict]:
        out = []
        for tid, nm, t0, dur, attrs, thread, parent, kind \
                in self._snapshot():
            if name is not None and nm != name:
                continue
            if trace_id is not None and tid != trace_id:
                continue
            d = {"trace_id": tid, "name": nm, "start_mono": round(t0, 6),
                 "duration_ms": round(dur * 1e3, 6), "thread": thread,
                 "kind": kind}
            if parent is not None:
                d["parent"] = parent
            if attrs:
                d["attrs"] = attrs
            out.append(d)
        return out[-limit:]

    def totals(self) -> Dict[str, List[float]]:
        """name → ``[count, wall_s]`` over every span recorded since the
        start (or ``reset()``), whatever the ring still holds."""
        with self._lock:
            return {nm: list(t) for nm, t in self._totals.items()}

    def summary(self) -> Dict[str, Dict]:
        """Per-stage aggregate — the CLI surface. Over the spans currently
        in the ring: count, p50/p99/max/total (ms) and the thread that
        recorded the newest; ``since_start``: count and wall (ms) of every
        span of the name, wrapped out of the ring or not."""
        by_name: Dict[str, List[float]] = {}
        thread_of: Dict[str, str] = {}
        for _tid, nm, _t0, dur, _attrs, thread, _parent, _kind \
                in self._snapshot():
            by_name.setdefault(nm, []).append(dur)
            thread_of[nm] = thread
        totals = self.totals()
        out = {}
        for nm in sorted(by_name):
            v = np.asarray(by_name[nm], dtype=np.float64) * 1e3
            n, wall = totals.get(nm, (0, 0.0))
            out[nm] = {
                "count": int(v.size),
                "p50_ms": round(float(np.percentile(v, 50)), 4),
                "p99_ms": round(float(np.percentile(v, 99)), 4),
                "max_ms": round(float(v.max()), 4),
                "total_ms": round(float(v.sum()), 4),
                "thread": thread_of[nm],
                "since_start": {"count": int(n),
                                "total_ms": round(wall * 1e3, 4)},
            }
        return out

    def stats(self) -> Dict:
        # O(1) under the lock — this runs on every /v1/status scrape and
        # must not stall hot-path record() for a full-ring scan
        with self._lock:
            recorded = self._filled
            capacity = len(self._ring)
        return {
            "enabled": self.enabled,
            "sample_rate": self.sample_rate,
            "sampled_total": self.sampled_total,
            "forced_total": self.forced_total,
            "spans_in_ring": recorded,
            "capacity": capacity,
            # drop-oldest accounting (ISSUE 13): spans overwritten before
            # any export saw them + full ring cycles — the "how much of
            # the story is gone" fields the CLI prints
            "spans_dropped_total": self.spans_dropped_total,
            "ring_wraps": self.ring_wraps,
        }


#: process-wide tracer (the FAULTS-singleton idiom): instrumentation points
#: in pipeline/engine/datapath use this; DaemonConfig.trace_* configures it.
TRACER = Tracer()
