"""Pipeline guard layer: overload protection + self-healing for the hot path.

PR 1 gave the *control plane* supervised degradation (last-good snapshots,
backoff, OK/DEGRADED/STALE health) and the scheduler gave the pipeline
retry-on-fault — but until this layer the serving path still failed
unboundedly: a hung ``dispatch_fn``/``finalize`` (device stall) wedged the
worker forever with every ticket blocked, a worker crash closed the
pipeline permanently, admitted work had no deadline so a backlog served
arbitrarily stale submissions, and repeated dispatch errors kept hammering
a sick backend. This module holds the three mechanisms the scheduler wires
into its hot path to extend the supervised-degradation philosophy there:

- **Error classes** — every way a submission can fail is a distinct
  ``PipelineError`` subclass, so the serving surface (REST/CLI) can map
  overload shed (:class:`PipelineDrop`, :class:`PipelineDeadlineExceeded`
  → 429) apart from unavailability (:class:`PipelineUnavailable`,
  :class:`PipelineClosed` → 503).
- :class:`CircuitBreaker` — consecutive dispatch/finalize failures past a
  threshold open the breaker; submissions then fail fast with
  :class:`PipelineUnavailable` instead of burning per-submission retry
  budgets against a sick backend. After ``cooldown_s`` one *probe*
  submission is admitted (half-open); its dispatch succeeding closes the
  breaker, failing re-opens it. Transitions are traced
  (``pipeline.breaker`` events), counted
  (``pipeline_breaker_transitions_total{to=...}``) and gauged
  (``pipeline_breaker_state``).
- :class:`Watchdog` — a supervisor thread fed by worker heartbeats (armed
  around each blocking dispatch/finalize call). A heartbeat armed longer
  than ``stall_timeout_s`` means the worker is wedged in the device path;
  the watchdog then drives the scheduler's restart protocol: reject the
  wedged in-flight window, abandon the stuck thread behind a generation
  fence, and start a fresh worker on a fresh staging ring. Restarts are
  bounded with capped backoff; past the bound the pipeline goes
  *hard-failed* (every submission rejected fast) rather than flapping.

The scheduler (``pipeline/scheduler.py``) owns the wiring; everything here
is mechanism, deliberately free of scheduler imports so the error types
can be shared across layers (engine, API, CLI) without cycles.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Callable, Dict, Optional, Tuple

log = logging.getLogger("cilium_tpu.pipeline.guard")

#: pipeline serving states surfaced through stats()/health()/Prometheus
#: (gauge ``pipeline_state`` carries the numeric code)
PIPELINE_STATES: Dict[str, int] = {
    "ok": 0, "breaker-open": 1, "restarting": 2, "failed": 3, "closed": 4,
    "device-lost": 5,
}

#: breaker states → ``pipeline_breaker_state`` gauge codes
BREAKER_STATES: Dict[str, int] = {"closed": 0, "half-open": 1, "open": 2}

#: overload-ladder states (the supervised degradation ladder under
#: adversarial load — OverloadLadder below) → ``overload_state`` gauge
#: codes. Each rung arms one more shedding behavior; see the README
#: "Failure modes & degradation" table for the full contract.
OVERLOAD_OK = 0
OVERLOAD_PRESSURE = 1
OVERLOAD_OVERLOAD = 2
OVERLOAD_SHED_NEW = 3
OVERLOAD_STATES: Dict[str, int] = {
    "ok": OVERLOAD_OK, "pressure": OVERLOAD_PRESSURE,
    "overload": OVERLOAD_OVERLOAD, "shed-new": OVERLOAD_SHED_NEW,
}
OVERLOAD_STATE_NAMES: Dict[int, str] = {v: k for k, v in
                                        OVERLOAD_STATES.items()}

#: priority classes the shim feeder stamps into the ``_prio`` batch column
#: (lower = more important). Established-CT flows outrank new flows, which
#: outrank unknown-endpoint traffic — the shedding order under PRESSURE+.
PRIO_ESTABLISHED = 0
PRIO_NEW = 1
PRIO_UNKNOWN = 2


class PipelineError(RuntimeError):
    """Base error for pipeline submissions."""


class PipelineDrop(PipelineError):
    """Submission shed at admission (queue full, drop mode or block
    timeout exhausted). Overload shed → retryable (429 at the API)."""


class PipelineClosed(PipelineError):
    """submit() after close()/stop()."""


class PipelineDeadlineExceeded(PipelineError):
    """Submission shed because its deadline passed before the worker
    reached it (at ingest) or before its microbatch dispatched (at
    flush). The answer nobody is waiting for is never computed."""


class PipelineUnavailable(PipelineError):
    """Fail-fast rejection: the circuit breaker is open, or the pipeline
    hard-failed after exhausting its watchdog restart budget. 503 at the
    API — the backend is sick, not merely busy."""


class DeviceLost(RuntimeError):
    """A dispatch failed with a dead-accelerator signature — not the
    transient breaker/backoff territory every other dispatch error lands
    in, but a chip that left the mesh (runtime/datapath.dead_device_of is
    the classifier that tells the two apart). ``device`` is the ordinal
    into the datapath's CONFIGURED device list (-1 = a device died but
    the error named no ordinal; the engine probes to attribute it).

    Deliberately NOT a :class:`PipelineError`: the scheduler treats it as
    a mesh-health signal (park the worker, notify the engine's re-mesh
    path) rather than a per-submission failure, and only the wedged
    in-flight window is rejected — queued submissions survive the fenced
    re-mesh, exactly like a watchdog restart."""

    def __init__(self, message: str, device: int = -1):
        super().__init__(message)
        self.device = device


class PipelineTenantCap(PipelineDrop):
    """Per-tenant occupancy-cap shed (multi-tenant QoS): the submitter is
    at its OWN queue budget while the shared queue may still have room —
    isolation working as designed, not a cluster-wide overload. A
    :class:`PipelineDrop` subclass, so every existing retryable-429
    handler treats it correctly without knowing about tenants."""


class CircuitBreaker:
    """Consecutive-failure circuit breaker for the dispatch path.

    Thread-safe and self-contained: the scheduler calls
    :meth:`record_failure` / :meth:`record_success` from the worker and
    :meth:`admit` from producers; ``on_transition`` (if given) fires on
    every state change with ``(old, new)`` so the owner can fold the state
    into its own health surface."""

    def __init__(self, threshold: int = 20, cooldown_s: float = 5.0, *,
                 metrics=None, tracer=None, name: str = "pipeline",
                 on_transition: Optional[Callable[[str, str], None]] = None):
        if threshold < 1:
            raise ValueError("breaker threshold must be >= 1")
        if cooldown_s <= 0:
            raise ValueError("breaker cooldown must be > 0")
        self.threshold = threshold
        self.cooldown_s = cooldown_s
        self.metrics = metrics
        self.tracer = tracer
        self.name = name
        self._on_transition = on_transition
        self._lock = threading.Lock()
        self._state = "closed"
        self._consecutive = 0
        self._opened_mono = 0.0
        self._probe_at: Optional[float] = None   # a half-open probe is out
        self._transitions = 0

    # -- producer side -------------------------------------------------------
    def admit(self) -> bool:
        """One admission decision. ``True`` → let the submission in
        (normal serving, or the half-open probe); ``False`` → fail fast."""
        moved = None
        with self._lock:
            now = time.monotonic()
            if self._state == "closed":
                return True
            if self._state == "open":
                if now - self._opened_mono >= self.cooldown_s:
                    moved = self._transition_locked("half-open")
                    self._probe_at = now
                    verdict = True
                else:
                    verdict = False
            # half-open: one probe at a time; a probe that never reported
            # back (admission dropped it downstream) expires after a
            # cooldown so the breaker cannot wedge itself shut
            elif self._probe_at is None or now - self._probe_at >= \
                    self.cooldown_s:
                self._probe_at = now
                verdict = True
            else:
                verdict = False
        self._emit(moved)
        return verdict

    # -- worker side ---------------------------------------------------------
    def record_failure(self) -> bool:
        """One dispatch/finalize failure. Returns True when the breaker is
        now open (the caller should stop retrying and reject fast)."""
        moved = None
        with self._lock:
            self._consecutive += 1
            self._probe_at = None
            if self._state == "half-open":
                moved = self._transition_locked("open")   # the probe failed
                self._opened_mono = time.monotonic()
            elif self._state == "closed" and \
                    self._consecutive >= self.threshold:
                moved = self._transition_locked("open")
                self._opened_mono = time.monotonic()
            now_open = self._state == "open"
        self._emit(moved)
        return now_open

    def record_success(self) -> None:
        moved = None
        with self._lock:
            self._consecutive = 0
            self._probe_at = None
            if self._state != "closed":
                # the probe came back healthy
                moved = self._transition_locked("closed")
        self._emit(moved)

    # -- internals -----------------------------------------------------------
    def _transition_locked(self, to: str) -> Tuple[str, str, int]:
        """Lock held: flip the state; the observable side effects happen
        in :meth:`_emit` after the lock is released (``on_transition`` may
        take the owner's lock — holding ours across it would invert lock
        order against readers of :attr:`state`)."""
        old, self._state = self._state, to
        self._transitions += 1
        return (old, to, self._consecutive)

    def _emit(self, moved: Optional[Tuple[str, str, int]]) -> None:
        if moved is None:
            return
        old, to, consecutive = moved
        log.warning("%s circuit breaker %s -> %s (%d consecutive failures)",
                    self.name, old, to, consecutive)
        if self.metrics is not None:
            self.metrics.inc_counter(
                f'pipeline_breaker_transitions_total{{to="{to}"}}')
            self.metrics.set_gauge("pipeline_breaker_state",
                                   BREAKER_STATES[to])
        if self.tracer is not None:
            self.tracer.event("pipeline.breaker", frm=old, to=to,
                              consecutive=consecutive)
        if self._on_transition is not None:
            self._on_transition(old, to)

    # -- read side -----------------------------------------------------------
    @property
    def state(self) -> str:
        with self._lock:
            return self._state

    def stats(self) -> Dict:
        with self._lock:
            d = {
                "state": self._state,
                "consecutive_failures": self._consecutive,
                "threshold": self.threshold,
                "cooldown_s": self.cooldown_s,
                "transitions": self._transitions,
            }
            if self._state == "open":
                d["retry_in_s"] = round(max(
                    0.0, self.cooldown_s
                    - (time.monotonic() - self._opened_mono)), 3)
            return d


class OverloadLadder:
    """The explicit degradation state machine under adversarial load:
    OK → PRESSURE → OVERLOAD → SHED-NEW.

    Pure mechanism (no pipeline/engine imports): the owner feeds it one
    ``observe(queue_frac, shed_rate, ct_occupancy)`` per control interval
    — queue occupancy fraction, sheds+admission-drops per second, CT live
    fraction — and propagates the returned state to the shedding sites
    (``Pipeline.set_overload_state``, ``ShimFeeder.set_overload_state``).

    Mechanics: each input is a *latched* signal with per-signal hysteresis
    (lights at its high threshold, stays lit until it falls below its low
    threshold), and the lit count is the severity: one lit signal holds
    PRESSURE; two-or-more lit signals keep ESCALATING — one rung per
    ``up_ticks`` consecutive pressured intervals, all the way to SHED-NEW
    if the pressure survives each stronger shed (requiring all three
    would deadlock: fail-fast admission at OVERLOAD is precisely what
    keeps the CT signal from ever lighting in an ingest-bound storm).
    Descent is one rung per ``down_ticks`` calm intervals and
    deliberately slow — a storm pausing for one scrape must not whiplash
    the feeder back into full admission.

    Thread-safe; ``status()`` carries per-state dwell times (the
    ladder-residency surface) and the last observed inputs."""

    #: bounded transition trail for status()/debug bundles
    MAX_TRANSITIONS = 32

    def __init__(self, *, queue_high: float = 0.75, queue_low: float = 0.25,
                 shed_high: float = 50.0, shed_low: float = 5.0,
                 ct_high: float = 0.85, ct_low: float = 0.6,
                 resource_high: float = 0.9, resource_low: float = 0.7,
                 up_ticks: int = 2, down_ticks: int = 6):
        if not (0.0 <= queue_low < queue_high <= 1.0):
            raise ValueError("need 0 <= queue_low < queue_high <= 1")
        if not (0.0 <= shed_low < shed_high):
            raise ValueError("need 0 <= shed_low < shed_high")
        if not (0.0 <= ct_low < ct_high <= 1.0):
            raise ValueError("need 0 <= ct_low < ct_high <= 1")
        if not (0.0 <= resource_low < resource_high <= 1.0):
            raise ValueError("need 0 <= resource_low < resource_high <= 1")
        if up_ticks < 1 or down_ticks < 1:
            raise ValueError("up_ticks and down_ticks must be >= 1")
        self._hi = {"queue": queue_high, "shed": shed_high, "ct": ct_high,
                    "resource": resource_high}
        self._lo = {"queue": queue_low, "shed": shed_low, "ct": ct_low,
                    "resource": resource_low}
        self._up_ticks = up_ticks
        self._down_ticks = down_ticks
        self._lock = threading.Lock()
        self._lit = {"queue": False, "shed": False, "ct": False,
                     "resource": False}
        self._last: Dict[str, float] = {}
        self.state = 0
        self._up = 0
        self._down = 0
        self._entered_mono = time.monotonic()
        self._dwell = [0.0, 0.0, 0.0, 0.0]
        self.transitions = 0
        self._trail: list = []

    def _latch(self, name: str, value: float) -> bool:
        if value >= self._hi[name]:
            self._lit[name] = True
        elif value <= self._lo[name]:
            self._lit[name] = False
        return self._lit[name]

    def observe(self, queue_frac: float, shed_rate: float,
                ct_occupancy: float,
                resource_pressure: float = 0.0) -> Tuple[int, bool]:
        """One control interval. Returns (state, changed).
        ``resource_pressure`` (ISSUE 13) is the resource ledger's worst
        non-CT pressure fraction — a fourth latch, so a wire pool / patch
        budget / ring running hot counts toward severity exactly like the
        original three signals (default 0.0 keeps three-signal callers'
        behavior bit-identical)."""
        with self._lock:
            sev = sum((self._latch("queue", queue_frac),
                       self._latch("shed", shed_rate),
                       self._latch("ct", ct_occupancy),
                       self._latch("resource", resource_pressure)))
            self._last = {"queue_frac": round(queue_frac, 4),
                          "shed_rate": round(shed_rate, 2),
                          "ct_occupancy": round(ct_occupancy, 4),
                          "resource_pressure": round(resource_pressure, 4),
                          "severity": sev}
            old = self.state
            # SHED-NEW is the top rung: with four latchable signals the
            # severity can reach 4, and an unbounded climb would step past
            # the state table exactly when shedding matters most
            escalate = (self.state < OVERLOAD_SHED_NEW
                        and (sev > self.state or sev >= 2))
            calm = sev < self.state and sev < 2
            if escalate:
                self._up += 1
                self._down = 0
                if self._up >= self._up_ticks:
                    self._move_locked(self.state + 1)
                    self._up = 0
            elif calm:
                self._down += 1
                self._up = 0
                if self._down >= self._down_ticks:
                    self._move_locked(self.state - 1)
                    self._down = 0
            else:
                self._up = self._down = 0
            return self.state, self.state != old

    def _move_locked(self, to: int) -> None:
        now = time.monotonic()
        self._dwell[self.state] += now - self._entered_mono
        self._entered_mono = now
        self._trail.append({"t": time.time(),
                            "frm": OVERLOAD_STATE_NAMES[self.state],
                            "to": OVERLOAD_STATE_NAMES[to],
                            "inputs": dict(self._last)})
        del self._trail[:-self.MAX_TRANSITIONS]
        self.state = to
        self.transitions += 1
        log.warning("overload ladder %s -> %s (%s)",
                    self._trail[-1]["frm"], self._trail[-1]["to"],
                    self._last)

    def status(self) -> Dict:
        with self._lock:
            now = time.monotonic()
            dwell = list(self._dwell)
            dwell[self.state] += now - self._entered_mono
            return {
                "state": OVERLOAD_STATE_NAMES[self.state],
                "level": self.state,
                "since_s": round(now - self._entered_mono, 3),
                "dwell_s": {OVERLOAD_STATE_NAMES[i]: round(d, 3)
                            for i, d in enumerate(dwell)},
                "transitions": self.transitions,
                "trail": list(self._trail),
                "inputs": dict(self._last),
                "lit": dict(self._lit),
            }


class Watchdog:
    """Supervisor thread watching the worker's heartbeat.

    ``heartbeat()`` returns the worker's currently armed beat as
    ``(armed_mono, label, gen, grace)`` or None when the worker is not
    inside a blocking call (an idle worker parked on its condvar is
    healthy, not stalled). ``grace`` is a per-beat multiplier on the stall
    budget — a cold first dispatch (XLA compile) gets more rope than a
    warm one. When a beat stays armed past ``stall_timeout_s × grace``
    the watchdog calls ``on_stall(gen, reason)`` — the scheduler's
    restart protocol, which is generation-fenced so a double fire is a
    no-op.
    ``should_stop()`` True ends the thread (pipeline closed/hard-failed).

    ``stall_timeout_s`` is mutable at runtime (the chaos driver shrinks it
    after XLA warmup so a stall-storm scenario doesn't have to out-wait a
    production-sized timeout)."""

    def __init__(self, *, stall_timeout_s: float,
                 heartbeat: Callable[
                     [], Optional[Tuple[float, str, int, int]]],
                 on_stall: Callable[[int, str], None],
                 should_stop: Callable[[], bool],
                 name: str = "pipeline"):
        if stall_timeout_s <= 0:
            raise ValueError("stall_timeout_s must be > 0")
        self.stall_timeout_s = stall_timeout_s
        self._heartbeat = heartbeat
        self._on_stall = on_stall
        self._should_stop = should_stop
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name=f"{name}-watchdog")

    def start(self) -> None:
        self._thread.start()

    def is_alive(self) -> bool:
        return self._thread.is_alive()

    def join(self, timeout: Optional[float] = None) -> None:
        self._thread.join(timeout)

    def _run(self) -> None:
        while True:
            # re-derive each lap: stall_timeout_s is runtime-tunable
            time.sleep(max(0.005, min(self.stall_timeout_s / 4.0, 0.25)))
            if self._should_stop():
                return
            beat = self._heartbeat()
            if beat is None:
                continue
            armed_mono, label, gen, grace = beat
            budget = self.stall_timeout_s * max(1, grace)
            stalled_for = time.monotonic() - armed_mono
            if stalled_for > budget:
                try:
                    self._on_stall(gen, f"worker stalled in {label} for "
                                        f"{stalled_for:.2f}s (timeout "
                                        f"{budget}s)")
                except Exception:        # noqa: BLE001 — never kill the dog
                    log.exception("watchdog restart attempt failed")
