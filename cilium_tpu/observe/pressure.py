"""Resource pressure ledger: one registry for every bounded structure.

Upstream Cilium exports ``cilium_bpf_map_pressure`` because the datapath's
failure modes are *capacity* failures — a full CT map or policy map drops
traffic long before CPU saturates, and the PR 10 DDoS work proved the same
holds here (CT_FULL, steer_overflow, admission sheds). Occupancy accounting
was ad-hoc before this module: ``ct_occupancy`` a fraction,
``pipeline_staging_free`` an absolute, trace/flowlog/blackbox rings wrapping
silently, wire pools and patch budgets reporting nothing. The ledger makes
"which bounded structure runs out first, and when" a first-class question:

- **Registration.** Engine-side *providers* (one callable per subsystem)
  return ``{resource: (capacity, occupancy[, pressure])}`` samples;
  :meth:`ResourceLedger.poll` sweeps them on the ``resource-ledger``
  controller's cadence (or a deterministic driver's logical clock). A
  provider that raises is counted and skipped — the ledger can observe a
  dying subsystem without joining it.
- **One labeled family.** Every resource exports
  ``ciliumtpu_resource_{occupancy,capacity,high_water,pressure}{resource=}``
  (+ ``resource_eta_seconds`` while a finite forecast exists), replacing
  the per-subsystem gauge zoo for capacity questions. Pressure is
  occupancy/capacity unless the provider supplies the canonical fraction
  itself — the CT provider hands through the ``ct_occupancy`` gauge
  verbatim, so the two surfaces can never disagree (tests/test_pressure.py
  asserts exact equality).
- **Time-to-exhaustion.** Per resource, a bounded window of (t, occupancy)
  samples yields a growth rate; ``eta_s = (capacity - occupancy) / rate``
  while the resource is growing. An ETA under ``eta_warn_s`` fires one
  ``resource-pressure`` flight-recorder event (latched — re-arms when the
  forecast clears); a resource that *then actually exhausts* (pressure ≥
  1.0) fires ``resource-exhaustion``, a strict-freeze kind — forecasted
  and ignored is the anomaly, commanded shedding is not.
- **Deregistration sweeps gauges.** A departed resource (pipeline closed,
  engine stopped, mesh resized) drops its whole label family via
  ``Metrics.drop_gauge`` — the same sweep departed clustermesh peers get —
  so a dead structure can never keep exporting a healthy-looking reading.

Consumers: ``Engine.health()`` folds pressured resources in as the
``RESOURCE_PRESSURE`` detail, the overload ladder takes ``max_pressure``
(CT excluded — it is already the ladder's own signal) as its fourth latch,
``GET /v1/resources`` + ``cilium-tpu top`` render the live table with
per-resource high-water and the HBM ledger.
"""

from __future__ import annotations

import logging
import threading
import time
from collections import deque
from typing import Callable, Dict, Iterable, List, Optional, Tuple

log = logging.getLogger("cilium_tpu.pressure")

#: resources excluded from the overload ladder's resource term: the CT
#: table and the admission queue are already the ladder's own signals
#: (double-lighting one cause would double its severity), and the audit
#: pool saturates by design under sampling-1.0 drills — the fourth latch
#: must mean "some OTHER bounded structure is about to fail".
LADDER_EXCLUDE = frozenset(("ct_table", "admission_queue", "audit_pool"))

#: gauge families every resource exports (the ``resource=`` label rides in
#: the name, runtime/metrics.py renders one TYPE line per base family)
GAUGE_FAMILIES = ("resource_occupancy", "resource_capacity",
                  "resource_high_water", "resource_pressure",
                  "resource_eta_seconds")

#: a provider returns {resource: (capacity, occupancy)} or
#: {resource: (capacity, occupancy, pressure)} — the 3-tuple form hands
#: through a canonical pressure fraction (the CT provider's ct_occupancy)
Sample = Tuple
Provider = Callable[[], Optional[Dict[str, Sample]]]


class _ResourceState:
    __slots__ = ("capacity", "occupancy", "pressure", "high_water",
                 "window", "eta_s", "forecast_latched", "exhaust_fired",
                 "provider", "last_poll")

    def __init__(self, provider: str, window: int):
        self.capacity = 0.0
        self.occupancy = 0.0
        self.pressure = 0.0
        self.high_water = 0.0
        self.window: deque = deque(maxlen=max(2, window))
        self.eta_s: Optional[float] = None
        self.forecast_latched = False      # resource-pressure event out
        self.exhaust_fired = False         # strict freeze already fired
        self.provider = provider
        self.last_poll = 0


class ResourceLedger:
    """Central (resource, capacity, occupancy, high_water) registry with
    windowed time-to-exhaustion forecasting. Thread-safe; ``poll`` is the
    only sampler (providers are swept, never push)."""

    def __init__(self, metrics=None, *, window: int = 16,
                 warn: float = 0.8, crit: float = 0.95,
                 eta_warn_s: float = 120.0,
                 event_sink: Optional[Callable] = None):
        if not 0.0 < warn < crit <= 1.0:
            raise ValueError("need 0 < warn < crit <= 1")
        if window < 2:
            raise ValueError("eta window must hold >= 2 samples")
        if eta_warn_s <= 0:
            raise ValueError("eta_warn_s must be > 0")
        self.metrics = metrics
        self.warn = warn
        self.crit = crit
        self.eta_warn_s = eta_warn_s
        self._window = window
        #: flight-recorder hook (Engine wires blackbox.record_event);
        #: called OUTSIDE the ledger lock, exceptions swallowed
        self._event_sink = event_sink
        self._lock = threading.Lock()
        self._providers: Dict[str, Provider] = {}
        self._state: Dict[str, _ResourceState] = {}
        self.polls_total = 0
        self.provider_errors_total = 0
        self.forecasts_total = 0
        self.exhaustions_total = 0

    # -- registration --------------------------------------------------------
    def register(self, name: str, provider: Provider) -> None:
        """Attach a provider. Re-registering a name replaces the callable
        (an engine-restarted pipeline keeps its resource history)."""
        with self._lock:
            self._providers[name] = provider

    def deregister(self, name: str) -> List[str]:
        """Detach a provider and sweep every resource it owned: state
        dropped AND the whole exported label family removed via
        ``Metrics.drop_gauge`` — a frozen last value would keep exporting
        a healthy-looking reading for a dead structure (the departed-
        clustermesh-peer lesson). Returns the swept resource names."""
        with self._lock:
            self._providers.pop(name, None)
            gone = [r for r, st in self._state.items()
                    if st.provider == name]
            for r in gone:
                del self._state[r]
            # drop the gauges INSIDE the ledger lock (metrics locks are
            # leaves): a concurrent _fold for the same resource serializes
            # against this — it can never re-pin a swept family, because
            # it re-checks provider registration under the same lock
            self._drop_families_locked(gone)
        return gone

    def _drop_families_locked(self, resources: Iterable[str]) -> None:
        if self.metrics is None:
            return
        for r in resources:
            for fam in GAUGE_FAMILIES:
                self.metrics.drop_gauge(f'{fam}{{resource="{r}"}}')

    def deregister_all(self) -> List[str]:
        """Engine shutdown: sweep everything (register/deregister symmetry
        under engine restart is what the tier-1 restart test pins)."""
        with self._lock:
            names = list(self._providers)
        gone: List[str] = []
        for n in names:
            gone.extend(self.deregister(n))
        return gone

    # -- sampling ------------------------------------------------------------
    def poll(self, now: Optional[float] = None) -> Dict:
        """One ledger sweep. ``now`` (seconds, any monotone clock) defaults
        to ``time.monotonic()``; deterministic drivers (the pressure soak
        of tests/test_pressure.py) pass their logical clock so ETA math is
        replayable.
        Returns the full report (the ``/v1/resources`` document)."""
        if now is None:
            now = time.monotonic()
        events: List[Tuple[str, Dict]] = []
        with self._lock:
            self.polls_total += 1
            tick = self.polls_total
            providers = list(self._providers.items())
        ok_providers = set()
        for pname, provider in providers:
            try:
                samples = provider()
            except Exception:   # noqa: BLE001 — observe, never join, a
                log.exception("resource provider %r failed", pname)  # dying
                with self._lock:                                     # subsys
                    self.provider_errors_total += 1
                continue
            ok_providers.add(pname)
            if not samples:
                continue
            for rname, sample in samples.items():
                events.extend(self._fold(pname, rname, sample, now))
        # staleness sweep: a resource its (healthy) provider stopped
        # reporting — the pipeline closed, the incremental compiler was
        # discarded — is DEPARTED, and its frozen last pressure must not
        # keep the health detail / ladder latch lit on a healthy engine.
        # A provider that ERRORED this poll sweeps nothing (a transient
        # failure is not a departure — its last good readings stand).
        with self._lock:
            stale = [r for r, st in self._state.items()
                     if st.provider in ok_providers and st.last_poll < tick]
            for r in stale:
                del self._state[r]
            self._drop_families_locked(stale)
        report = self.report()
        for kind, attrs in events:
            self._emit(kind, attrs)
        return report

    def _fold(self, pname: str, rname: str, sample: Sample,
              now: float) -> List[Tuple[str, Dict]]:
        capacity = float(sample[0])
        occupancy = float(sample[1])
        explicit_p = float(sample[2]) if len(sample) > 2 else None
        events: List[Tuple[str, Dict]] = []
        with self._lock:
            if pname not in self._providers:
                # the provider was deregistered between its sample call
                # and this fold: folding would resurrect a swept resource
                # no future poll could ever clean up again
                return events
            st = self._state.get(rname)
            if st is None:
                st = self._state[rname] = _ResourceState(pname,
                                                         self._window)
            st.provider = pname
            st.capacity = capacity
            st.occupancy = occupancy
            # the provider's canonical fraction wins (the CT provider hands
            # the ct_occupancy gauge through VERBATIM — tests hold the two
            # surfaces to never disagreeing); otherwise derive
            st.pressure = explicit_p if explicit_p is not None \
                else occupancy / capacity if capacity > 0 else 0.0
            st.high_water = max(st.high_water, occupancy)
            st.window.append((now, occupancy))
            st.eta_s = self._eta_locked(st)
            st.last_poll = self.polls_total
            # forecast latch: one resource-pressure event per excursion;
            # re-arm only once the forecast has genuinely cleared
            if st.eta_s is not None and st.eta_s <= self.eta_warn_s \
                    and st.pressure >= self.warn:
                if not st.forecast_latched:
                    st.forecast_latched = True
                    self.forecasts_total += 1
                    events.append(("resource-pressure", {
                        "resource": rname,
                        "eta_s": round(st.eta_s, 1),
                        "occupancy": round(occupancy, 2),
                        "capacity": capacity,
                        "pressure": round(st.pressure, 4)}))
            elif st.forecast_latched and st.pressure < self.warn:
                # pressure-based hysteresis: the excursion is over once the
                # resource is back under warn — a fresh climb is a fresh
                # forecast (stale window samples must not pin the latch)
                st.forecast_latched = False
                st.exhaust_fired = False
            # forecast-then-exhaustion is the strict-freeze anomaly: the
            # ledger SAID this would run out and then it did — commanded
            # shedding narrates, an ignored forecast freezes evidence
            if st.forecast_latched and not st.exhaust_fired \
                    and st.pressure >= 1.0:
                st.exhaust_fired = True
                self.exhaustions_total += 1
                events.append(("resource-exhaustion", {
                    "resource": rname,
                    "occupancy": round(occupancy, 2),
                    "capacity": capacity,
                    "high_water": round(st.high_water, 2)}))
            # export INSIDE the ledger lock (metrics locks are leaves):
            # a concurrent deregister's family sweep serializes against
            # this write instead of racing it, and the exported five
            # values are always one poll's consistent snapshot
            self._export_locked(rname, st)
        return events

    @staticmethod
    def _eta_locked(st: _ResourceState) -> Optional[float]:
        """Windowed growth rate → seconds until occupancy == capacity.
        None while the resource is flat/shrinking or already full (an
        exhausted resource has no *forecast* — its pressure says it all)."""
        if len(st.window) < 2:
            return None
        t0, o0 = st.window[0]
        t1, o1 = st.window[-1]
        if t1 <= t0:
            return None
        rate = (o1 - o0) / (t1 - t0)
        headroom = st.capacity - o1
        if rate <= 0 or headroom <= 0:
            return None
        return headroom / rate

    def _export_locked(self, rname: str, st: _ResourceState) -> None:
        if self.metrics is None:
            return
        lbl = f'{{resource="{rname}"}}'
        values = {
            f"resource_occupancy{lbl}": st.occupancy,
            f"resource_capacity{lbl}": st.capacity,
            f"resource_high_water{lbl}": st.high_water,
            f"resource_pressure{lbl}": round(st.pressure, 6),
        }
        if st.eta_s is not None:
            values[f"resource_eta_seconds{lbl}"] = round(st.eta_s, 1)
            drop = ()
        else:
            # a stale finite ETA is a false alarm pinned forever — sweep
            # the series the moment the forecast clears
            drop = (f"resource_eta_seconds{lbl}",)
        # one lock acquisition for the whole family (the <2% polling
        # attestation is the budget this spends)
        self.metrics.set_gauges(values, drop=drop)

    def _emit(self, kind: str, attrs: Dict) -> None:
        if self._event_sink is None:
            return
        try:
            self._event_sink(kind, **attrs)
        except Exception:   # noqa: BLE001
            log.exception("resource event sink failed")

    # -- read side -----------------------------------------------------------
    def resources(self) -> List[str]:
        with self._lock:
            return sorted(self._state)

    def max_pressure(self, exclude: Iterable[str] = ()) -> float:
        """The worst pressure fraction across registered resources (the
        overload ladder's fourth latch signal; CT is excluded there — it
        is already the ladder's own signal)."""
        ex = frozenset(exclude)
        with self._lock:
            return max((st.pressure for r, st in self._state.items()
                        if r not in ex), default=0.0)

    def pressured(self, threshold: Optional[float] = None) -> List[str]:
        thr = self.warn if threshold is None else threshold
        with self._lock:
            return sorted(r for r, st in self._state.items()
                          if st.pressure >= thr)

    def report(self) -> Dict:
        """The ``/v1/resources`` / ``cilium-tpu top`` document: one row per
        resource plus the ledger's own accounting."""
        with self._lock:
            rows = {
                r: {
                    "capacity": st.capacity,
                    "occupancy": st.occupancy,
                    "pressure": round(st.pressure, 6),
                    "high_water": st.high_water,
                    "eta_s": round(st.eta_s, 1)
                    if st.eta_s is not None else None,
                    "forecast": st.forecast_latched,
                    "provider": st.provider,
                } for r, st in sorted(self._state.items())
            }
            max_p = max((st.pressure for st in self._state.values()),
                        default=0.0)
        return {
            "resources": rows,
            "max_pressure": round(max_p, 6),
            "pressured": [r for r, d in rows.items()
                          if d["pressure"] >= self.warn],
            "thresholds": {"warn": self.warn, "crit": self.crit,
                           "eta_warn_s": self.eta_warn_s},
            "polls_total": self.polls_total,
            "provider_errors_total": self.provider_errors_total,
            "forecasts_total": self.forecasts_total,
            "exhaustions_total": self.exhaustions_total,
        }

    def status(self) -> Dict:
        """The small health-surface summary Engine.health() folds in."""
        with self._lock:
            pressured = sorted(
                (r for r, st in self._state.items()
                 if st.pressure >= self.warn),
                key=lambda r: -self._state[r].pressure)
            max_p = max((st.pressure for st in self._state.values()),
                        default=0.0)
            etas = [(r, st.eta_s) for r, st in self._state.items()
                    if st.eta_s is not None]
            crit = any(st.pressure >= self.crit
                       for st in self._state.values())
        min_eta = min(etas, key=lambda kv: kv[1]) if etas else None
        return {
            "pressured": pressured,
            "max_pressure": round(max_p, 6),
            "critical": crit,
            "min_eta": ({"resource": min_eta[0],
                         "eta_s": round(min_eta[1], 1)}
                        if min_eta is not None else None),
            "registered": len(self._state),
        }
