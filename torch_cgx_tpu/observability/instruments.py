"""Typed metric instruments + the process-wide registry.

The seed's ``Metrics`` was one flat ``Dict[str, float]`` — fine for test
assertions, useless for operating a quantized-collective stack: a
compression ratio and a bridge-timeout tally need different shapes (a
distribution vs a monotonic count), and an exporter needs to know which
is which. This module upgrades the registry to three instrument types
while keeping the seed's ``add/set/get/snapshot/reset`` call sites
working unchanged:

* :class:`Counter` — monotonic accumulator (``metrics.add``). Fault
  tallies, wire bytes, step counts.
* :class:`Gauge` — last-write-wins level (``metrics.set``). Arena bytes
  in flight, current bits/bucket.
* :class:`Histogram` — streaming distribution with exact count/sum/
  min/max and quantile estimates from a bounded reservoir of the most
  recent samples (``metrics.observe``). Phase durations, queue waits,
  quantization error.

Deliberately dependency-free (stdlib only, no package-internal imports):
``utils.logging`` re-exports the singleton, so this module sits below
everything else in the import graph.
"""

from __future__ import annotations

import threading
from collections import deque
from typing import Dict, Optional, Sequence, Tuple

# Reservoir depth per histogram: quantiles describe the *recent* window
# (the operationally interesting one — a 10-minute-old stall should not
# dilute this step's p99), exact count/sum/min/max cover all time.
RESERVOIR = 512

_QUANTILES = (0.5, 0.9, 0.99)


class Counter:
    """Monotonic accumulator."""

    __slots__ = ("value",)

    def __init__(self):
        self.value = 0.0

    def add(self, v: float) -> None:
        self.value += v


class Gauge:
    """Last-write-wins level."""

    __slots__ = ("value",)

    def __init__(self):
        self.value = 0.0

    def set(self, v: float) -> None:
        self.value = v


class Histogram:
    """Count/sum/min/max over all observations + quantiles over a bounded
    reservoir of the most recent :data:`RESERVOIR` samples."""

    __slots__ = ("count", "sum", "min", "max", "_recent")

    def __init__(self):
        self.count = 0
        self.sum = 0.0
        self.min = float("inf")
        self.max = float("-inf")
        self._recent: deque = deque(maxlen=RESERVOIR)

    def observe(self, v: float) -> None:
        v = float(v)
        self.count += 1
        self.sum += v
        if v < self.min:
            self.min = v
        if v > self.max:
            self.max = v
        self._recent.append(v)

    def quantile(self, q: float) -> float:
        """q-quantile of the recent reservoir (nearest-rank); 0.0 when
        empty."""
        if not self._recent:
            return 0.0
        s = sorted(self._recent)
        return s[min(int(q * len(s)), len(s) - 1)]

    def stats(self) -> Dict[str, float]:
        if self.count == 0:
            return {"count": 0, "sum": 0.0}
        out = {
            "count": float(self.count),
            "sum": self.sum,
            "min": self.min,
            "max": self.max,
            "mean": self.sum / self.count,
        }
        for q in _QUANTILES:
            out[f"p{int(q * 100)}"] = self.quantile(q)
        return out


class Metrics:
    """Process-wide instrument registry (thread-safe).

    Backward compatible with the seed's flat-counter API: ``add`` feeds a
    :class:`Counter`, ``set`` a :class:`Gauge`, the new ``observe`` a
    :class:`Histogram`; ``get``/``snapshot`` read all three (histograms
    flatten to ``<name>.count/.sum/.mean/.min/.max/.p50/.p90/.p99``).
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: Dict[str, Counter] = {}
        self._gauges: Dict[str, Gauge] = {}
        self._histograms: Dict[str, Histogram] = {}

    def add(self, name: str, value: float = 1.0) -> None:
        with self._lock:
            c = self._counters.get(name)
            if c is None:
                c = self._counters[name] = Counter()
            c.add(value)

    def set(self, name: str, value: float) -> None:
        with self._lock:
            g = self._gauges.get(name)
            if g is None:
                g = self._gauges[name] = Gauge()
            g.set(value)

    def observe(self, name: str, value: float) -> None:
        with self._lock:
            h = self._histograms.get(name)
            if h is None:
                h = self._histograms[name] = Histogram()
            h.observe(value)

    def get(self, name: str) -> float:
        """Counter/gauge value; for a histogram, its observation count;
        0.0 for an unknown name (seed semantics)."""
        with self._lock:
            c = self._counters.get(name)
            if c is not None:
                return c.value
            g = self._gauges.get(name)
            if g is not None:
                return g.value
            h = self._histograms.get(name)
            if h is not None:
                return float(h.count)
            return 0.0

    def histogram_stats(self, name: str) -> Optional[Dict[str, float]]:
        with self._lock:
            h = self._histograms.get(name)
            return h.stats() if h is not None else None

    def sums(self, names: Sequence[str]) -> Tuple[float, ...]:
        """The ``.sum`` of each named histogram (0.0 for one nothing has
        observed yet), under one lock: what a caller reads at the two ends
        of a stretch to say where its time went, where a ``snapshot`` would
        sort every reservoir of the registry."""
        with self._lock:
            found = map(self._histograms.get, names)
            return tuple(0.0 if h is None else h.sum for h in found)

    def snapshot(self, prefix: str = "") -> Dict[str, float]:
        """Flat view of every instrument, optionally filtered by name
        prefix — e.g. ``metrics.snapshot("cgx.faults.")`` for the
        fault-injection tally. Histograms flatten to dotted stat keys so
        existing dict consumers keep working."""
        with self._lock:
            out: Dict[str, float] = {
                k: c.value for k, c in self._counters.items()
            }
            out.update({k: g.value for k, g in self._gauges.items()})
            for k, h in self._histograms.items():
                for stat, v in h.stats().items():
                    out[f"{k}.{stat}"] = v
        if not prefix:
            return out
        return {k: v for k, v in out.items() if k.startswith(prefix)}

    def snapshot_typed(self) -> Dict[str, Dict]:
        """Structured view for the exporter/aggregator: instruments kept
        by type so a merge can sum counters but combine histograms by
        component (count/sum/min/max are mergeable; quantiles are not)."""
        with self._lock:
            return {
                "counters": {k: c.value for k, c in self._counters.items()},
                "gauges": {k: g.value for k, g in self._gauges.items()},
                "histograms": {
                    k: h.stats() for k, h in self._histograms.items()
                },
            }

    def reset(self) -> None:
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._histograms.clear()


metrics = Metrics()
