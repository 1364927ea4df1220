"""Per-class service-level objectives: error budgets, burn rates, breaches.

A soak's gates (``scripts/serving_stress.py``'s per-class p99
assertions) are one-shot: they say whether a 12-second soak stayed
inside its SLO. A
production fleet needs the CONTINUOUS form — declarative objectives per
admission class, rolling multi-window burn-rate tracking (the SRE
fast-5m/slow-1h pattern), an error budget that depletes and recovers,
and a breach hook — so a router frontend, a hedging policy or an
operator pager can act on "interactive is burning 20x budget" instead
of re-running a benchmark.

- ``tracker.py`` — `Objective` (target availability + optional latency
  quantile target, env-overridable), `SLOTracker` (bucketed good/bad
  event rings, fast/slow burn rates, `slo/<class>/...` gauges and
  counters in the metrics registry, breach hooks), and the lazily
  built process default (`tracker()` / module-level `record()`).

Event sources: the serving tier records every request's outcome and
latency (serving/batcher.py), the fleet router records per-attempt and
per-call outcomes (fleet/router.py — a breaker trip shows up as burn
even when failover keeps callers whole), and the continuous soundness
audit feeds the ``integrity`` objective (resilience/soundness.py —
the 2G2T detection budget as a quantified SLO, not just a counter).
Surfaces: ``slo/<class>/{burn_rate,burn_rate_slow,budget_remaining,
good,bad,breaches}`` on /metrics (+ Prometheus exposition), the
``slo`` section on /status, and the federation rollups under
``fleet/replica/<name>/slo/...`` on a router.
"""

from gethsharding_tpu.slo.tracker import (
    DEFAULT_OBJECTIVES,
    INTEGRITY,
    Objective,
    SLOTracker,
    active,
    configure,
    default_objectives,
    record,
    tracker,
)

__all__ = [
    "DEFAULT_OBJECTIVES",
    "INTEGRITY",
    "Objective",
    "SLOTracker",
    "active",
    "configure",
    "default_objectives",
    "record",
    "tracker",
]
