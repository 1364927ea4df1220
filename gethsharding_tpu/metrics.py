"""Metrics: counters / gauges / timers behind a registry.

Parity: the `metrics/` go-metrics fork (registry `metrics.go:22-39`,
process collectors :42, expvar/influx exporters) scoped to what the
sharding framework actually needs natively (SURVEY.md §7.8): the two
BASELINE metrics — aggregate signature verifications/sec and collation
validate latency percentiles — plus per-actor operation counters.

Like the reference's `metrics.Enabled` gate, collection is cheap enough
to leave on; the `--metrics` CLI flag controls *reporting*. Timers keep a
bounded sample reservoir for percentile snapshots (the go-metrics
ExpDecaySample analog, simplified to a ring buffer — recent-window
percentiles, which is what a validate-latency dashboard wants).
"""

from __future__ import annotations

import math
import threading
import time
from typing import Dict, List, Optional


class Counter:
    """Monotonic event count with a creation-time rate AND a windowed
    one (the go-metrics `Meter` EWMA analog).

    `rate()` (events/sec since creation) goes stale on a long-running
    node — an hour of silence barely moves it. `rate_1m()` is the
    1-minute exponentially-weighted moving average over 5-second ticks
    (go-metrics `meter.go` constants), advanced lazily on read so idle
    counters cost nothing between snapshots."""

    _TICK_S = 5.0
    _ALPHA_1M = 1.0 - math.exp(-_TICK_S / 60.0)

    def __init__(self) -> None:
        self._value = 0
        self._t0 = time.monotonic()
        self._lock = threading.Lock()
        # EWMA state: events since the last tick, the tick clock, and
        # the smoothed per-second rate (unset until the first tick)
        self._uncounted = 0
        self._last_tick = self._t0
        self._ewma: Optional[float] = None

    def inc(self, n: int = 1) -> None:
        with self._lock:
            self._value += n
            self._uncounted += n

    @property
    def value(self) -> int:
        return self._value

    def rate(self) -> float:
        """Events/sec since creation."""
        elapsed = time.monotonic() - self._t0
        return self._value / elapsed if elapsed > 0 else 0.0

    def rate_1m(self, now: Optional[float] = None) -> float:
        """Events/sec, 1-minute EWMA (0.0 until the first 5 s tick)."""
        now = time.monotonic() if now is None else now
        with self._lock:
            ticks = int((now - self._last_tick) / self._TICK_S)
            if ticks > 0:
                # lazy ticking must agree with a real periodic ticker:
                # spread the accumulated events evenly over the elapsed
                # ticks (crediting them all to one tick and then pure-
                # decaying would under-report steady rates on infrequent
                # reads). Constant per-tick rate makes the K-tick EWMA
                # update exact in closed form.
                instant = self._uncounted / (ticks * self._TICK_S)
                remaining = ticks
                if self._ewma is None:
                    self._ewma = instant  # go-metrics: first tick seeds
                    remaining -= 1
                self._ewma = instant + (self._ewma - instant) * (
                    (1.0 - self._ALPHA_1M) ** remaining)
                self._uncounted = 0
                self._last_tick += ticks * self._TICK_S
            return self._ewma or 0.0

    def snapshot(self) -> dict:
        return {"type": "counter", "count": self._value,
                "rate_per_s": round(self.rate(), 3),
                "rate_1m": round(self.rate_1m(), 3)}


class Gauge:
    """Last-written value."""

    def __init__(self) -> None:
        self._value: float = 0.0

    def set(self, value: float) -> None:
        self._value = value

    @property
    def value(self) -> float:
        return self._value

    def snapshot(self) -> dict:
        return {"type": "gauge", "value": self._value}


class Histogram:
    """Fixed-bucket distribution of discrete observations.

    The right shape for batch sizes (the serving layer's coalescing
    evidence): `Timer`'s reservoir percentiles interpolate between
    sample values, which is meaningless for discrete quantities that
    only ever take bucket-shaped values — a histogram reports how many
    observations fell at-or-below each bound, exactly.

    Snapshot fields are FLAT (``le_<bound>`` / ``le_inf`` counts next
    to ``count``/``mean``) so the influx exporter and the dashboard
    render them without nested-dict special cases.

    Bucket semantics are Prometheus's: ``le_*`` counts are CUMULATIVE
    (observations at-or-below the bound; ``le_inf`` == ``count``).
    The exact per-slot counts remain available under ``bucket_*`` keys
    (`slot_counts()`) — each observation lands in exactly one slot.
    """

    DEFAULT_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024)

    def __init__(self, buckets=DEFAULT_BUCKETS) -> None:
        if not buckets:
            raise ValueError("histogram needs at least one bucket bound")
        self._bounds = tuple(sorted(buckets))
        # one slot per bound + the overflow (> last bound) slot
        self._counts = [0] * (len(self._bounds) + 1)
        self._count = 0
        self._total = 0.0
        self._lock = threading.Lock()

    def observe(self, value: float) -> None:
        slot = len(self._bounds)
        for i, bound in enumerate(self._bounds):
            if value <= bound:
                slot = i
                break
        with self._lock:
            self._counts[slot] += 1
            self._count += 1
            self._total += value

    @property
    def count(self) -> int:
        return self._count

    def mean(self) -> float:
        return self._total / self._count if self._count else 0.0

    @property
    def bounds(self) -> tuple:
        return self._bounds

    @property
    def total(self) -> float:
        return self._total

    def read(self) -> tuple:
        """ONE consistent locked read: (per-slot counts, count, total).
        Every derived view builds from this so a scrape racing
        observe() can never emit ``le_inf != count`` (the Prometheus
        histogram invariant)."""
        with self._lock:
            return list(self._counts), self._count, self._total

    def _cumulative(self, counts) -> Dict[str, int]:
        out: Dict[str, int] = {}
        running = 0
        for i, bound in enumerate(self._bounds):
            running += counts[i]
            out[f"le_{bound:g}"] = running
        out["le_inf"] = running + counts[-1]
        return out

    def _per_slot(self, counts) -> Dict[str, int]:
        out = {f"bucket_{bound:g}": counts[i]
               for i, bound in enumerate(self._bounds)}
        out["bucket_inf"] = counts[-1]
        return out

    def bucket_counts(self) -> Dict[str, int]:
        """CUMULATIVE at-or-below counts under Prometheus ``le_*`` keys
        (what the name has always implied; ``le_inf`` == ``count``)."""
        return self._cumulative(self.read()[0])

    def quantile(self, q: float) -> float:
        """Estimate the q-quantile from the cumulative buckets, linear
        interpolation WITHIN the bucket the target rank falls in (the
        Prometheus `histogram_quantile` estimator): the first bucket
        interpolates from 0, the overflow bucket clamps to the largest
        finite bound — an estimator cannot invent an upper edge for
        +Inf. 0.0 with no observations."""
        if not 0.0 <= q <= 1.0:
            raise ValueError(f"quantile must be in [0, 1], got {q}")
        counts, count, _ = self.read()
        if count == 0:
            return 0.0
        target = q * count
        running = 0
        lower = 0.0
        for i, bound in enumerate(self._bounds):
            if running + counts[i] >= target:
                if counts[i] == 0:
                    return float(bound)
                frac = (target - running) / counts[i]
                return lower + (bound - lower) * frac
            running += counts[i]
            lower = float(bound)
        return float(self._bounds[-1])

    def slot_counts(self) -> Dict[str, int]:
        """EXACT per-slot counts under ``bucket_*`` keys (each
        observation in exactly one slot; ``bucket_inf`` is overflow)."""
        return self._per_slot(self.read()[0])

    def snapshot(self) -> dict:
        counts, count, total = self.read()
        return {"type": "histogram", "count": count,
                "mean": round(total / count if count else 0.0, 3),
                # bucket-interpolated percentiles next to the raw
                # buckets: /status renders snapshots verbatim, so the
                # serving/fleet sections show p50/p95/p99 directly
                "p50": round(self.quantile(0.50), 4),
                "p95": round(self.quantile(0.95), 4),
                "p99": round(self.quantile(0.99), 4),
                **self._cumulative(counts), **self._per_slot(counts)}


class Timer:
    """Duration observations with percentile snapshots over a recent
    window (ring buffer of the last `reservoir` observations)."""

    def __init__(self, reservoir: int = 1024) -> None:
        self._samples: List[float] = []
        self._reservoir = reservoir
        self._count = 0
        self._total = 0.0
        self._next = 0
        self._lock = threading.Lock()

    def observe(self, seconds: float) -> None:
        with self._lock:
            self._count += 1
            self._total += seconds
            if len(self._samples) < self._reservoir:
                self._samples.append(seconds)
            else:  # ring overwrite: recent-window percentiles
                self._samples[self._next] = seconds
                self._next = (self._next + 1) % self._reservoir

    def time(self) -> "_TimerContext":
        return _TimerContext(self)

    @property
    def count(self) -> int:
        return self._count

    def percentile(self, q: float) -> float:
        with self._lock:
            if not self._samples:
                return 0.0
            ordered = sorted(self._samples)
        idx = min(int(q * len(ordered)), len(ordered) - 1)
        return ordered[idx]

    def mean(self) -> float:
        return self._total / self._count if self._count else 0.0

    def snapshot(self) -> dict:
        return {
            "type": "timer", "count": self._count,
            "mean_s": round(self.mean(), 6),
            "p50_s": round(self.percentile(0.50), 6),
            "p95_s": round(self.percentile(0.95), 6),
            "p99_s": round(self.percentile(0.99), 6),
        }


class _TimerContext:
    def __init__(self, timer: Timer) -> None:
        self._timer = timer

    def __enter__(self) -> "_TimerContext":
        self._start = time.monotonic()
        return self

    def __exit__(self, *exc) -> None:
        self._timer.observe(time.monotonic() - self._start)


class Registry:
    """Named metric registry (metrics.Registry parity)."""

    def __init__(self) -> None:
        self._metrics: Dict[str, object] = {}
        self._lock = threading.Lock()

    def _get_or_register(self, name: str, factory):
        with self._lock:
            metric = self._metrics.get(name)
            if metric is None:
                metric = factory()
                self._metrics[name] = metric
            return metric

    def counter(self, name: str) -> Counter:
        return self._get_or_register(name, Counter)

    def gauge(self, name: str) -> Gauge:
        return self._get_or_register(name, Gauge)

    def timer(self, name: str) -> Timer:
        return self._get_or_register(name, Timer)

    def histogram(self, name: str, buckets=None) -> Histogram:
        """`buckets` applies only on first registration (like every
        metric here, the first caller defines the instrument)."""
        factory = (Histogram if buckets is None
                   else (lambda: Histogram(buckets)))
        return self._get_or_register(name, factory)

    def get(self, name: str) -> Optional[object]:
        return self._metrics.get(name)

    def snapshot(self, prefix: str = "") -> Dict[str, dict]:
        """Every metric's snapshot by name, or those of one namespace."""
        with self._lock:
            items = [item for item in self._metrics.items()
                     if item[0].startswith(prefix)]
        return {name: metric.snapshot() for name, metric in sorted(items)}


# the default registry (metrics.DefaultRegistry parity)
DEFAULT_REGISTRY = Registry()


def counter(name: str) -> Counter:
    return DEFAULT_REGISTRY.counter(name)


def gauge(name: str) -> Gauge:
    return DEFAULT_REGISTRY.gauge(name)


def timer(name: str) -> Timer:
    return DEFAULT_REGISTRY.timer(name)


def histogram(name: str, buckets=None) -> Histogram:
    return DEFAULT_REGISTRY.histogram(name, buckets=buckets)


class PeriodicReporter:
    """Logs a registry snapshot every `interval` seconds (the
    `CollectProcessMetrics` + exp exporter analog, to the log stream)."""

    def __init__(self, registry: Registry = DEFAULT_REGISTRY,
                 interval: float = 10.0, logger=None) -> None:
        import logging

        self.registry = registry
        self.interval = interval
        self.log = logger or logging.getLogger("metrics")
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="metrics-reporter")
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)

    def _run(self) -> None:
        while not self._stop.wait(self.interval):
            for name, snap in self.registry.snapshot().items():
                self.log.info("%s %s", name, snap)


class InfluxLineExporter:
    """Registry snapshots as InfluxDB line protocol (the
    `metrics/influxdb` exporter analog), pushed on an interval to a
    file (Telegraf `tail`) or a UDP endpoint (InfluxDB's classic
    zero-dependency ingestion listener).

    One line per metric: ``<namespace>.<name> f1=v1,f2=v2 <ns-epoch>``
    with metric path separators normalized and every field emitted as a
    float (a stable schema: influx rejects type flips per field).
    Histogram lines carry BOTH the cumulative ``le_*`` fields and the
    exact per-slot ``bucket_*`` fields of the snapshot."""

    def __init__(self, registry: Registry = DEFAULT_REGISTRY,
                 interval: float = 10.0, path: Optional[str] = None,
                 udp: Optional[tuple] = None,
                 namespace: str = "gethsharding") -> None:
        if (path is None) == (udp is None):
            raise ValueError("exactly one sink: path= or udp=(host, port)")
        self.registry = registry
        self.interval = interval
        self.path = path
        self.udp = udp
        self.namespace = namespace
        self.pushes = 0
        self._sock = None
        # push() runs on the reporter thread AND on stop()'s final
        # flush (whose join is bounded and may time out with the
        # reporter mid-push): the socket lazy-init and the pushes
        # counter need a real guard, not a single-writer convention.
        # _closed (set under the same lock after the final flush)
        # stops a timed-out straggler reporter from lazily RE-creating
        # the socket stop() just closed and leaking it.
        self._closed = False
        self._push_lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    @staticmethod
    def _escape(name: str) -> str:
        return (name.replace("/", ".").replace(" ", "_")
                .replace(",", "_").replace("=", "_"))

    def encode_snapshot(self, timestamp_ns: Optional[int] = None) -> bytes:
        ts = (time.time_ns() if timestamp_ns is None else timestamp_ns)
        lines = []
        for name, snap in self.registry.snapshot().items():
            fields = ",".join(
                f"{self._escape(k)}={float(v)}"
                for k, v in sorted(snap.items())
                if isinstance(v, (int, float)))
            if fields:
                lines.append(
                    f"{self.namespace}.{self._escape(name)} {fields} {ts}")
        return ("\n".join(lines) + "\n").encode() if lines else b""

    def push(self) -> None:
        payload = self.encode_snapshot()
        if not payload:
            return
        with self._push_lock:
            if self._closed:
                return  # stop() already final-flushed and closed
            if self.path is not None:
                with open(self.path, "ab") as fh:
                    fh.write(payload)
            else:
                import socket

                if self._sock is None:
                    self._sock = socket.socket(socket.AF_INET,
                                               socket.SOCK_DGRAM)
                self._sock.sendto(payload, self.udp)
            self.pushes += 1

    def start(self) -> None:
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="metrics-influx")
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
        try:
            self.push()  # final flush
        except OSError:
            pass
        with self._push_lock:
            self._closed = True
            if self._sock is not None:
                self._sock.close()
                self._sock = None

    def _run(self) -> None:
        while not self._stop.wait(self.interval):
            try:
                self.push()
            except OSError:
                pass  # sink unavailable: keep collecting, retry next tick


# -- Prometheus text exposition (scrape without Telegraf) -------------------


def _prom_name(name: str, namespace: str) -> str:
    """Metric path -> a legal Prometheus metric name."""
    import re

    flat = re.sub(r"[^a-zA-Z0-9_:]", "_", name)
    return f"{namespace}_{flat}" if namespace else flat


def prometheus_text(registry: Registry = DEFAULT_REGISTRY,
                    namespace: str = "gethsharding") -> str:
    """The registry as Prometheus text exposition format (0.0.4) — the
    ``GET /metrics?format=prom`` payload, so a node is scrapeable with
    no Telegraf/Influx hop:

    - Counter   -> ``<name>_total`` counter (+ ``<name>_rate_1m`` gauge)
    - Gauge     -> gauge
    - Timer     -> summary (quantiles 0.5/0.95/0.99, ``_count``/``_sum``)
    - Histogram -> histogram (cumulative ``_bucket{le=...}``,
      ``le="+Inf"`` == ``_count``, plus ``_sum``)
    """
    with registry._lock:
        items = sorted(registry._metrics.items())
    lines: List[str] = []
    for name, metric in items:
        prom = _prom_name(name, namespace)
        if isinstance(metric, Counter):
            lines += [f"# TYPE {prom}_total counter",
                      f"{prom}_total {metric.value}",
                      f"# TYPE {prom}_rate_1m gauge",
                      f"{prom}_rate_1m {metric.rate_1m():g}"]
        elif isinstance(metric, Gauge):
            lines += [f"# TYPE {prom} gauge", f"{prom} {metric.value:g}"]
        elif isinstance(metric, Timer):
            lines.append(f"# TYPE {prom} summary")
            for q in (0.5, 0.95, 0.99):
                lines.append(
                    f'{prom}{{quantile="{q:g}"}} {metric.percentile(q):g}')
            lines += [f"{prom}_count {metric.count}",
                      f"{prom}_sum {metric.mean() * metric.count:g}"]
        elif isinstance(metric, Histogram):
            lines.append(f"# TYPE {prom} histogram")
            # ONE locked read: +Inf bucket, _count and _sum must agree
            # even when a scrape races observe()
            counts, count, total = metric.read()
            cumulative = metric._cumulative(counts)
            for bound in metric.bounds:
                lines.append(f'{prom}_bucket{{le="{bound:g}"}} '
                             f'{cumulative[f"le_{bound:g}"]}')
            lines += [f'{prom}_bucket{{le="+Inf"}} {cumulative["le_inf"]}',
                      f"{prom}_count {count}",
                      f"{prom}_sum {total:g}"]
    # never empty: a scraper (or the observability smoke step) reading
    # zero bytes cannot tell "no metrics yet" from a broken endpoint
    return "\n".join(lines) + "\n" if lines else "# empty registry\n"
