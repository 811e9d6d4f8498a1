"""Metrics registry + Prometheus text rendering (analog of upstream
``pkg/metrics`` for the agent and the ``metricsmap`` per-verdict datapath
counters tensor — SURVEY.md §5: "counters tensor accumulated in-kernel
(drops by reason × direction), scraped to Prometheus text format").
"""

from __future__ import annotations

import bisect
import threading
import time
from typing import Dict, List, Sequence, Tuple

import numpy as np

from cilium_tpu.utils import constants as C


class SpanStat:
    """Micro-span timing aggregate (upstream pkg/spanstat)."""

    def __init__(self):
        self.count = 0
        self.total_s = 0.0
        self.max_s = 0.0

    def observe(self, seconds: float) -> None:
        self.count += 1
        self.total_s += seconds
        self.max_s = max(self.max_s, seconds)

    class _Timer:
        def __init__(self, stat):
            self._stat = stat

        def __enter__(self):
            self._t0 = time.perf_counter()
            return self

        def __exit__(self, *exc):
            self._stat.observe(time.perf_counter() - self._t0)

    def timer(self) -> "_Timer":
        return SpanStat._Timer(self)


# Default latency buckets (seconds): sub-ms queue waits up to multi-second
# stalls — the range the pipeline's queue-wait and batch-latency spans cover.
DEFAULT_BUCKETS = (0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                   0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0)


class Histogram:
    """Prometheus histogram: cumulative ``_bucket`` counts + ``_sum`` /
    ``_count`` (the le-labelled exposition format). ``SpanStat`` stays the
    cheap count/total/max aggregate for existing spans; histograms are for
    distributions where percentiles matter (queue wait, batch latency)."""

    def __init__(self, buckets: Sequence[float] = DEFAULT_BUCKETS):
        self.buckets: Tuple[float, ...] = tuple(sorted(buckets))
        # counts[i] = observations <= buckets[i]; counts[-1] = +Inf bucket
        self.counts = [0] * (len(self.buckets) + 1)
        self.total = 0.0
        self.count = 0
        # own lock (not the Metrics one): observe() is the pipeline hot
        # path; an unsynchronized render could otherwise scrape a bucket
        # count ahead of +Inf — a non-monotonic histogram Prometheus rejects
        self._lock = threading.Lock()

    def observe(self, value: float) -> None:
        with self._lock:
            self.counts[bisect.bisect_left(self.buckets, value)] += 1
            self.total += value
            self.count += 1

    def snapshot(self) -> Tuple[Tuple[float, ...], List[int], float, int]:
        """Consistent (buckets, counts, sum, count) for rendering."""
        with self._lock:
            return self.buckets, list(self.counts), self.total, self.count

    def quantile(self, q: float) -> float:
        """Approximate quantile from the bucket counts (linear within the
        winning bucket). For observations past the last finite boundary the
        boundary itself is returned — a histogram cannot do better. An
        empty histogram reads 0.0 — this is a *display* surface (status
        docs), where "no observations yet" rendering as 0
        is the established convention; interval/delta consumers must use
        :func:`quantile_from` and test for :data:`EMPTY_QUANTILE`."""
        buckets, counts, _total, count = self.snapshot()
        if count == 0:
            return 0.0
        return quantile_from(buckets, counts, q)


#: the empty-window sentinel :func:`quantile_from` returns when the count
#: vector sums to zero. NaN, deliberately: every arithmetic comparison
#: against it is False, so a consumer that forgets to check cannot mistake
#: an idle interval for a zero-latency one (the pre-fix 0.0 return made a
#: scrape gap look like "queue wait collapsed" to the autotuner and would
#: read as "SLO met" to burn-rate math). Test with ``math.isnan`` /
#: :func:`quantile_is_empty`.
EMPTY_QUANTILE = float("nan")


def quantile_is_empty(value: float) -> bool:
    """True iff ``value`` is the :data:`EMPTY_QUANTILE` sentinel."""
    return value != value            # NaN is the only float unequal to itself


def quantile_from(buckets: Sequence[float], counts: Sequence[int],
                  q: float) -> float:
    """Quantile over a (buckets, counts) pair — shared by
    ``Histogram.quantile`` and consumers working on *delta* counts (the
    observe autotuner and the SLO burn math diff successive snapshots so
    each control interval is judged on its own distribution, not the
    process lifetime's). A zero-count window — two scrapes with no
    observations in between — returns :data:`EMPTY_QUANTILE` (NaN), never
    a fabricated 0.0."""
    count = sum(counts)
    if count == 0:
        return EMPTY_QUANTILE
    target = q * count
    acc = 0
    lo = 0.0
    for i, b in enumerate(buckets):
        if counts[i]:
            if acc + counts[i] >= target:
                frac = (target - acc) / counts[i]
                return lo + frac * (b - lo)
            acc += counts[i]
        lo = b
    return buckets[-1]


class Metrics:
    """Accumulates device counter outputs + host-side spans/gauges."""

    def __init__(self):
        self._lock = threading.Lock()
        # shape derived from the counter-tensor geometry in constants —
        # a DropReason added past the old hard-coded 512 can no longer
        # silently truncate (add_batch validates the incoming shape too)
        self.by_reason_dir = np.zeros((C.COUNTER_CELLS,), dtype=np.uint64)
        self.insert_fail = 0
        self.ct_evicted = 0
        # the pre-CT kernels' rows (kernels/classify.tally_pre_ct): DNAT'd
        # to a backend, sent to a frontend with none, and walked, by the
        # matched prefix length (last bin: no prefix, the world fallback)
        self.lb_translated = 0
        self.lb_no_backend = 0
        self.lpm_rows = np.zeros((C.LPM_PLEN_BINS,), dtype=np.uint64)
        # the L7 lane's rows (kernels/classify.tally_l7): requests held to
        # their cell's rule set, and those of them the set refused
        self.l7_checked = 0
        self.l7_refused = 0
        self.packets_total = 0
        self.batches_total = 0
        self.spans: Dict[str, SpanStat] = {}
        self.histograms: Dict[str, Histogram] = {}
        self.gauges: Dict[str, float] = {}
        self.counters: Dict[str, int] = {}

    def span(self, name: str) -> SpanStat:
        with self._lock:
            if name not in self.spans:
                self.spans[name] = SpanStat()
            return self.spans[name]

    def histogram(self, name: str,
                  buckets: Sequence[float] = DEFAULT_BUCKETS) -> Histogram:
        """Named histogram (created on first use; ``buckets`` only applies
        then). Name it like a Prometheus metric, e.g.
        ``pipeline_queue_wait_seconds``."""
        with self._lock:
            if name not in self.histograms:
                self.histograms[name] = Histogram(buckets)
            return self.histograms[name]

    def add_batch(self, counters: Dict, n_valid: int) -> None:
        arr = np.asarray(counters["by_reason_dir"])
        if arr.shape != self.by_reason_dir.shape:
            raise ValueError(
                f"by_reason_dir shape {arr.shape} != expected "
                f"{self.by_reason_dir.shape} (reasons x directions from "
                f"constants — kernel and metrics geometry diverged)")
        with self._lock:
            self.by_reason_dir += arr.astype(np.uint64)
            self.insert_fail += int(counters["insert_fail"])
            # optional: legacy counter dicts (older backends, tests)
            # predate the insert-when-full eviction accounting
            self.ct_evicted += int(counters.get("ct_evicted", 0))
            if "lpm_rows" in counters:
                self.lb_translated += int(counters["lb_translated"])
                self.lb_no_backend += int(counters["lb_no_backend"])
                self.lpm_rows += np.asarray(counters["lpm_rows"])
            if "l7_checked" in counters:
                self.l7_checked += int(counters["l7_checked"])
                self.l7_refused += int(counters["l7_refused"])
            self.packets_total += n_valid
            self.batches_total += 1

    def verdict_rows(self) -> Dict[str, int]:
        """Rows verdicted so far, and how many of them each pre-CT kernel
        and the L7 lane answered which way, read at one instant (a delta
        of two reads is exact: every batch folds all of them under the one
        lock)."""
        with self._lock:
            return {"total": self.packets_total,
                    "lb_translated": self.lb_translated,
                    "lb_no_backend": self.lb_no_backend,
                    "lpm_walked": int(self.lpm_rows.sum()),
                    "lpm_missed": int(self.lpm_rows[C.LPM_MISS_BIN]),
                    "l7_checked": self.l7_checked,
                    "l7_refused": self.l7_refused}

    def set_gauge(self, name: str, value: float) -> None:
        with self._lock:
            self.gauges[name] = value

    def set_gauges(self, values: Dict[str, float],
                   drop: Sequence[str] = ()) -> None:
        """Write a batch of gauges (and drop departed ones) under ONE lock
        acquisition — the resource ledger exports five families per
        resource per poll, and per-gauge locking was measurable against
        the <2% polling-overhead attestation."""
        with self._lock:
            self.gauges.update(values)
            for name in drop:
                self.gauges.pop(name, None)

    def drop_gauge(self, name: str) -> None:
        """Remove a labeled gauge whose subject is gone (e.g. a departed
        clustermesh peer) — a frozen last value would keep exporting a
        healthy-looking reading for a dead thing."""
        with self._lock:
            self.gauges.pop(name, None)

    def inc_counter(self, name: str, by: int = 1) -> None:
        """Named host-side counter (upstream: errors/warnings metrics —
        e.g. regeneration failures, sink drops)."""
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + by

    # -- rendering -----------------------------------------------------------
    def render_prometheus(self) -> str:
        """Prometheus text exposition format."""
        lines: List[str] = []
        with self._lock:
            lines.append("# HELP ciliumtpu_datapath_verdicts_total Verdicts "
                         "by drop reason and direction")
            lines.append("# TYPE ciliumtpu_datapath_verdicts_total counter")
            arr = self.by_reason_dir.reshape(C.DROP_REASON_BINS,
                                             C.N_DIRECTIONS)
            for reason in np.nonzero(arr.sum(axis=1))[0]:
                try:
                    rname = C.DropReason(int(reason)).name
                except ValueError:
                    rname = str(int(reason))
                for d in range(C.N_DIRECTIONS):
                    if arr[reason, d]:
                        lines.append(
                            f'ciliumtpu_datapath_verdicts_total{{reason="{rname}",'
                            f'direction="{C.DIR_NAMES[d]}"}} {int(arr[reason, d])}')
            lines.append("# TYPE ciliumtpu_ct_insert_fail_total counter")
            lines.append(f"ciliumtpu_ct_insert_fail_total {self.insert_fail}")
            lines.append("# TYPE ciliumtpu_ct_evicted_total counter")
            lines.append(f"ciliumtpu_ct_evicted_total {self.ct_evicted}")
            lines.append("# TYPE ciliumtpu_lb_translated_rows_total counter")
            lines.append(
                f"ciliumtpu_lb_translated_rows_total {self.lb_translated}")
            lines.append("# TYPE ciliumtpu_lb_no_backend_rows_total counter")
            lines.append(
                f"ciliumtpu_lb_no_backend_rows_total {self.lb_no_backend}")
            lines.append("# TYPE ciliumtpu_lpm_rows_total counter")
            for b in np.nonzero(self.lpm_rows)[0]:
                plen = "miss" if b == C.LPM_MISS_BIN else int(b)
                lines.append(f'ciliumtpu_lpm_rows_total{{plen="{plen}"}} '
                             f'{int(self.lpm_rows[b])}')
            lines.append("# TYPE ciliumtpu_l7_checked_rows_total counter")
            lines.append(
                f"ciliumtpu_l7_checked_rows_total {self.l7_checked}")
            lines.append("# TYPE ciliumtpu_l7_refused_rows_total counter")
            lines.append(
                f"ciliumtpu_l7_refused_rows_total {self.l7_refused}")
            lines.append("# TYPE ciliumtpu_packets_total counter")
            lines.append(f"ciliumtpu_packets_total {self.packets_total}")
            lines.append("# TYPE ciliumtpu_batches_total counter")
            lines.append(f"ciliumtpu_batches_total {self.batches_total}")
            # counters may carry a label set in the name (e.g.
            # ``pipeline_shed_total{reason="flush"}``); the TYPE line is
            # emitted once per base metric, not per label combination
            typed = set()
            for name, v in sorted(self.counters.items()):
                base = name.split("{", 1)[0]
                if base not in typed:
                    lines.append(f"# TYPE ciliumtpu_{base} counter")
                    typed.add(base)
                lines.append(f"ciliumtpu_{name} {v}")
            # gauges may carry labels too (``pipeline_staged_rows{shard=..}``)
            # — one TYPE line per base metric, like the counters above
            gtyped = set()
            for name, g in sorted(self.gauges.items()):
                base = name.split("{", 1)[0]
                if base not in gtyped:
                    lines.append(f"# TYPE ciliumtpu_{base} gauge")
                    gtyped.add(base)
                lines.append(f"ciliumtpu_{name} {g}")
            for name, s in sorted(self.spans.items()):
                lines.append(f"# TYPE ciliumtpu_{name}_seconds summary")
                lines.append(f"ciliumtpu_{name}_seconds_count {s.count}")
                lines.append(f"ciliumtpu_{name}_seconds_sum {s.total_s:.6f}")
                lines.append(f"ciliumtpu_{name}_seconds_max {s.max_s:.6f}")
            # histograms may carry a label set in the name too (the
            # per-shard ingest e2e families, ``..._seconds{shard="3"}``):
            # one TYPE line per base metric, labels merged into each
            # bucket's le label and suffixed onto _sum/_count
            htyped = set()
            for name, h in sorted(self.histograms.items()):
                buckets, counts, total, count = h.snapshot()
                base, _, labels = name.partition("{")
                labels = labels.rstrip("}")
                lbl_prefix = f"{labels}," if labels else ""
                lbl_suffix = f"{{{labels}}}" if labels else ""
                if base not in htyped:
                    lines.append(f"# TYPE ciliumtpu_{base} histogram")
                    htyped.add(base)
                acc = 0
                for le, n in zip(buckets, counts):
                    acc += n
                    lines.append(f'ciliumtpu_{base}_bucket'
                                 f'{{{lbl_prefix}le="{le}"}} {acc}')
                lines.append(f'ciliumtpu_{base}_bucket'
                             f'{{{lbl_prefix}le="+Inf"}} {count}')
                lines.append(f"ciliumtpu_{base}_sum{lbl_suffix} {total:.6f}")
                lines.append(f"ciliumtpu_{base}_count{lbl_suffix} {count}")
        return "\n".join(lines) + "\n"
