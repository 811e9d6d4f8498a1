"""Fault-injection registry (SURVEY-style chaos harness for the runtime).

A process-wide :data:`FAULTS` singleton owns a set of **named injection
points** wired into the serving/control paths:

======================  =====================================================
point                   fires in
======================  =====================================================
``regen.compile``       ``Engine.regenerate()`` — before snapshot compile
``shim.rx_ring``        ``FlowShim.poll_batch()`` / ``afxdp_poll()``
``clustermesh.peer_read``  ``ClusterMesh._read_peers()`` — per peer file
``checkpoint.write``    ``checkpoint.save()`` — between tmp write and rename
``api.handler``         REST dispatch (every method) in ``api._Handler``
``pipeline.dispatch``   per-microbatch dispatch in the ingestion pipeline
                        worker (``pipeline/scheduler.py``)
``pipeline.finalize``   per-batch finalize in the pipeline worker (trips
                        reject the batch; ``hang`` stalls for the watchdog)
``datapath.transfer``   host→device transfer enqueue inside
                        ``JITDatapath.classify_async``
``audit.corrupt``       shadow-audit capture (``observe/audit.py``): a trip
                        flips the captured allow bits so the parity auditor
                        must detect the divergence
``ct.gc``               one overlapped CT-GC tick (``Engine.sweep_step``) —
                        trips drill the ct-gc controller's supervised backoff
======================  =====================================================

Each point can be **armed** with one spec:

* ``fail`` (``times=N``): raise :class:`FaultInjected` on the first N fires
  (``times=None`` → every fire).
* ``prob`` (``prob=P, seed=S``): raise with probability P from a private
  seeded ``random.Random`` — fully deterministic, no wall clock.
* ``delay`` (``delay_s=T``): inject latency (sleep) instead of failing.
* ``hang`` (``delay_s=T, times=N``): a **cooperative stall** — the firing
  thread blocks inside the point (simulating a wedged device call) until
  the point is disarmed or the cap T (hard-clamped to
  :data:`HANG_HARD_CAP_S`) elapses, then returns normally. This is what
  drives the pipeline watchdog's stall detection without ever being able
  to deadlock a test.

Activation is either programmatic (the :meth:`FaultInjector.inject` context
manager, used by tests) or via the environment::

    CILIUM_TPU_FAULTS="regen.compile=fail:10;clustermesh.peer_read=prob:0.5:seed=7"

Grammar: ``point=mode[:arg][:key=val]...`` entries joined by ``;`` or ``,``.
``fail:N`` sets times, ``prob:P`` sets probability, ``delay:T`` sets seconds.
The agent process parses the variable at import of this module, so a chaos
scenario can target a real daemon with zero code changes.

Everything is thread-safe; ``fire()`` on an un-armed point is a dict lookup
plus a counter bump, cheap enough for control-path call sites (it is NOT in
the per-packet device path).
"""

from __future__ import annotations

import os
import random
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, Optional

ENV_VAR = "CILIUM_TPU_FAULTS"

# the registry of known points (arm() validates against this so a typo'd
# scenario fails loudly instead of silently injecting nothing)
POINTS: Dict[str, str] = {
    "regen.compile": "snapshot compile inside Engine.regenerate()",
    "shim.rx_ring": "rx-ring poll in shim bindings (poll_batch/afxdp_poll)",
    "clustermesh.peer_read": "per-peer store file read in ClusterMesh",
    "checkpoint.write": "pre-rename window of each atomic checkpoint file "
                        "write (tmp written, rename pending)",
    "api.handler": "REST request dispatch in the unix-socket API server",
    "pipeline.dispatch": "per-microbatch dispatch in the ingestion "
                         "pipeline worker (trips are retried — batches "
                         "delay, never drop — until the circuit breaker "
                         "opens; hang mode stalls for the watchdog)",
    "pipeline.finalize": "per-batch finalize in the ingestion pipeline "
                         "worker (trips reject the batch's tickets and "
                         "feed the circuit breaker; hang mode stalls for "
                         "the watchdog)",
    "datapath.transfer": "host→device transfer enqueue in "
                         "JITDatapath.classify_async (serial classify and "
                         "the pipeline both route through it)",
    "audit.corrupt": "shadow-audit capture in observe/audit.py: an armed "
                     "trip flips the CAPTURED allow bits (the copy, never "
                     "the live verdicts) — simulates a datapath parity bug "
                     "so chaos drills prove the auditor detects, health "
                     "degrades, and a flight-recorder bundle freezes",
    "resource.poll": "one resource-pressure ledger sweep "
                     "(Engine.resource_step): trips exercise the "
                     "resource-ledger controller's supervised backoff — "
                     "serving and the last exported pressure gauges must "
                     "be untouched by a wedged/failing poll",
    "ct.gc": "one tick of the overlapped device-side CT GC "
             "(Engine.sweep_step): trips exercise the ct-gc controller's "
             "supervised backoff — classify traffic and CT correctness "
             "must be untouched by a wedged/failing sweep",
    "ct.insert": "the CT insert phase of one classify dispatch "
                 "(JITDatapath.classify_async / FakeDatapath.classify): a "
                 "trip rejects the batch — tickets fail closed in FIFO "
                 "order, the breaker is fed — drilling verdict-FIFO "
                 "survival while a DDoS flood saturates the table",
    "overload.decide": "one tick of the overload-ladder controller "
                       "(Engine.overload_step): trips drill the supervised "
                       "backoff — the ladder state must HOLD (no flap to "
                       "OK, no spurious escalation) while the decider "
                       "itself is failing",
    "clustermesh.store_list": "the whole-store directory listing in "
                              "ClusterMesh._read_peers (a dead NFS mount — "
                              "the store PARTITION, vs peer_read's "
                              "single-file flake): trips make the mesh "
                              "serve last-good remote state and, past the "
                              "staleness budget, degrade health with the "
                              "MESH_STALE detail — never fail closed on "
                              "established remote flows",
    "fqdn.parse": "the DNS response decode inside the feeder's learning "
                  "tap (fqdn/proxy.observe_batch): a trip loses LEARNING "
                  "for that batch's DNS rows — counted in "
                  "fqdn_parse_errors_total — while the replies keep their "
                  "verdicts bit-identical (the fail-open contract a "
                  "broken parser must honor; chaos phase dns-poison)",
    "device.fail": "a dead accelerator in the flow-shard mesh: fired on "
                   "every sharded classify dispatch AND by "
                   "JITDatapath.probe_device. Arm with message=dev=K to "
                   "name the victim ordinal — the datapath's real-error "
                   "classifier (dead_device_of) recognizes the trip as a "
                   "dead-device signature (NOT breaker/backoff territory), "
                   "latches the per-device health record, and raises "
                   "DeviceLost so the engine fences and re-meshes onto the "
                   "survivors. A trip naming an ordinal already OUT of the "
                   "serving mesh is swallowed (a dead chip cannot hurt a "
                   "mesh it is not in) — that is what lets degraded serving "
                   "continue while the fault stays armed, and what makes "
                   "disarming it a drill's heal signal",
    "device.collective": "the host CT gather inside JITDatapath.remesh "
                         "(the salvage collective): a trip means the "
                         "surviving shards' tables could not be gathered — "
                         "salvage falls back to the ct-snapshot archive "
                         "floor (bounded staleness) or a cold table, "
                         "counted in ct_salvage_source_total",
}

#: hard clamp on ``hang`` stalls: whatever cap a scenario asks for, a
#: hung thread is always released — tests and chaos drills cannot deadlock
HANG_HARD_CAP_S = 30.0


class FaultInjected(RuntimeError):
    """Raised by an armed injection point. A plain RuntimeError subclass so
    every existing failure-isolation path (controllers, degraded regen,
    route handlers) treats it exactly like a real fault."""


def register_point(name: str, description: str) -> None:
    """Declare a new injection point (subsystems added later self-register)."""
    POINTS[name] = description


@dataclass
class FaultSpec:
    mode: str                      # fail | prob | delay
    times: Optional[int] = None    # fail: trip the first N fires (None=all)
    prob: float = 0.0              # prob: trip probability per fire
    delay_s: float = 0.0           # delay: injected latency
    seed: int = 0                  # prob: RNG seed (determinism)
    message: str = ""

    def __post_init__(self):
        if self.mode not in ("fail", "prob", "delay", "hang"):
            raise ValueError(f"bad fault mode {self.mode!r}")
        if self.mode == "prob" and not (0.0 <= self.prob <= 1.0):
            raise ValueError(f"bad fault probability {self.prob!r}")
        if not (0.0 <= self.delay_s):
            raise ValueError(f"bad fault delay {self.delay_s!r}")


@dataclass
class _Armed:
    spec: FaultSpec
    rng: random.Random = field(default_factory=random.Random)
    fires: int = 0                 # times the point was reached while armed
    trips: int = 0                 # times the fault actually triggered


class FaultInjector:
    """Process-wide injection-point registry; see module docstring."""

    def __init__(self, env: Optional[Dict[str, str]] = None):
        self._lock = threading.Lock()
        self._armed: Dict[str, _Armed] = {}
        self._fired: Dict[str, int] = {}    # total fires per point (always)
        env = os.environ if env is None else env
        if env.get(ENV_VAR):
            self.load_spec(env[ENV_VAR])

    # -- arming ------------------------------------------------------------
    def arm(self, point: str, mode: str = "fail", times: Optional[int] = None,
            prob: float = 0.0, delay_s: float = 0.0, seed: int = 0,
            message: str = "") -> None:
        if point not in POINTS:
            raise ValueError(f"unknown injection point {point!r}; known: "
                             f"{sorted(POINTS)}")
        spec = FaultSpec(mode=mode, times=times, prob=prob,
                         delay_s=delay_s, seed=seed, message=message)
        with self._lock:
            self._armed[point] = _Armed(spec, random.Random(seed))

    def disarm(self, point: Optional[str] = None) -> None:
        """Disarm one point (or everything with ``point=None``)."""
        with self._lock:
            if point is None:
                self._armed.clear()
            else:
                self._armed.pop(point, None)

    def inject(self, point: str, **kw) -> "_InjectCtx":
        """Context manager: arm on enter, restore the previous arming on
        exit — scenario steps nest safely."""
        return _InjectCtx(self, point, kw)

    def load_spec(self, text: str) -> int:
        """Parse a ``CILIUM_TPU_FAULTS`` string and arm every entry.
        Returns the number of points armed. All-or-nothing: every entry is
        parsed and validated before ANY point is armed, so a 400 on a bad
        multi-entry spec never leaves earlier entries live on the agent."""
        parsed = []
        for entry in text.replace(",", ";").split(";"):
            entry = entry.strip()
            if not entry:
                continue
            if "=" not in entry:
                raise ValueError(f"bad fault entry {entry!r} "
                                 "(want point=mode[:arg][:k=v]...)")
            point, _, rest = entry.partition("=")
            point = point.strip()
            parts = rest.split(":")
            mode, args = parts[0], parts[1:]
            kw: Dict = {"mode": mode}
            for a in args:
                if "=" in a:
                    k, _, v = a.partition("=")
                    kw[k] = v
                elif mode == "fail":
                    kw["times"] = a
                elif mode == "prob":
                    kw["prob"] = a
                elif mode in ("delay", "hang"):
                    kw["delay_s"] = a
            if "times" in kw:
                kw["times"] = int(kw["times"])
            if "prob" in kw:
                kw["prob"] = float(kw["prob"])
            if "delay_s" in kw:
                kw["delay_s"] = float(kw["delay_s"])
            if "seed" in kw:
                kw["seed"] = int(kw["seed"])
            if point not in POINTS:
                raise ValueError(f"unknown injection point {point!r}; "
                                 f"known: {sorted(POINTS)}")
            try:
                spec = FaultSpec(**kw)
            except TypeError as e:     # unknown k=v key
                raise ValueError(f"bad fault entry {entry!r}: {e}") from None
            parsed.append((point, spec))
        with self._lock:
            for point, spec in parsed:
                self._armed[point] = _Armed(spec, random.Random(spec.seed))
        return len(parsed)

    # -- firing ------------------------------------------------------------
    def fire(self, point: str) -> None:
        """Call at an injection site. Raises FaultInjected / sleeps /
        stalls when the point is armed and the spec trips; otherwise a
        cheap no-op."""
        delay = None
        hang_cap = None
        with self._lock:
            self._fired[point] = self._fired.get(point, 0) + 1
            armed = self._armed.get(point)
            if armed is None:
                return
            armed.fires += 1
            spec = armed.spec
            if spec.mode in ("fail", "delay", "hang"):
                if spec.times is not None and armed.trips >= spec.times:
                    return
            elif spec.mode == "prob":
                if armed.rng.random() >= spec.prob:
                    return
            armed.trips += 1
            if spec.mode == "delay":
                delay = spec.delay_s
            elif spec.mode == "hang":
                hang_cap = min(spec.delay_s or HANG_HARD_CAP_S,
                               HANG_HARD_CAP_S)
        if delay is not None:
            time.sleep(delay)
            return
        if hang_cap is not None:
            self._hang(point, hang_cap)
            return
        raise FaultInjected(
            f"injected fault at {point}"
            + (f": {spec.message}" if spec.message else ""))

    def _hang(self, point: str, cap_s: float) -> None:
        """The cooperative stall: block in small increments until the
        point is disarmed (a chaos driver releasing its victims) or the
        hard cap elapses, then return normally — the caller proceeds as if
        the device finally answered."""
        t0 = time.monotonic()
        while time.monotonic() - t0 < cap_s:
            time.sleep(min(0.02, cap_s))
            with self._lock:
                armed = self._armed.get(point)
                if armed is None or armed.spec.mode != "hang":
                    return

    # -- introspection -----------------------------------------------------
    def armed(self) -> Dict[str, FaultSpec]:
        with self._lock:
            return {p: a.spec for p, a in self._armed.items()}

    def stats(self) -> Dict[str, Dict]:
        """Per-point fire/trip counts (all known points, armed or not)."""
        with self._lock:
            out: Dict[str, Dict] = {}
            for point in sorted(set(POINTS) | set(self._fired)
                                | set(self._armed)):
                armed = self._armed.get(point)
                out[point] = {
                    "description": POINTS.get(point, ""),
                    "fired": self._fired.get(point, 0),
                    "armed": armed is not None,
                    "mode": armed.spec.mode if armed else None,
                    "trips": armed.trips if armed else 0,
                }
            return out

    def reset(self) -> None:
        """Disarm everything and zero the counters (test isolation)."""
        with self._lock:
            self._armed.clear()
            self._fired.clear()


class _InjectCtx:
    def __init__(self, injector: FaultInjector, point: str, kw: Dict):
        self._injector = injector
        self._point = point
        self._kw = kw
        self._prev: Optional[_Armed] = None

    def __enter__(self) -> FaultInjector:
        with self._injector._lock:
            self._prev = self._injector._armed.get(self._point)
        self._injector.arm(self._point, **self._kw)
        return self._injector

    def __exit__(self, *exc) -> None:
        with self._injector._lock:
            if self._prev is None:
                self._injector._armed.pop(self._point, None)
            else:
                self._injector._armed[self._point] = self._prev


#: the process-wide injector every instrumented site fires through
FAULTS = FaultInjector()
