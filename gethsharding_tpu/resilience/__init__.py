"""Fault-tolerance layer: retries, breaker failover, watchdog, journal,
chaos.

Four pillars wired through the serving tier, sigbackend, notary and
mainchain bridge (ISSUE 5):

- ``policy.py``   — composable deadline + capped-backoff-with-jitter
  retry executors with per-seam retry/giveup counters;
- ``breaker.py``  — `FailoverSigBackend`: the accelerated backend
  behind a circuit breaker over the scalar `PythonSigBackend`, with
  half-open differential spot-check re-promotion
  (``--sigbackend=failover-*``);
- ``watchdog.py`` — `DispatchWatchdog`: hung serving dispatches fail
  their batch's futures with `DeadlineExceeded` and the dispatcher
  restarts;
- ``journal.py``  — `VoteJournal`: crash-safe (shard, period) vote set
  + audit high-water mark through `db/kv`, replayed on notary start;
- ``chaos.py``    — seeded, deterministic failure schedules injectable
  at the backend-op, mainchain-call and dispatch seams (tests,
  ``--chaos`` on the node CLI), including the
  silent-corruption ``mode=corrupt`` rules;
- ``soundness.py`` — `SpotCheckSigBackend`: continuous statistically-
  sound integrity audit of the fast path — sampled random-row
  re-verification against the scalar reference plus an always-on
  verdict-plane invariant check; a detected disagreement raises
  `SoundnessViolation` into the breaker's fault path
  (``--soundness-rate``, ``GETHSHARDING_SOUNDNESS_RATE``).

Submodules are imported lazily (PEP 562): `errors`/`policy` are leaf
modules safe for the serving tier and mainchain client to import
directly; `breaker`/`chaos` pull in the sigbackend registry and only
load when failover or chaos is actually in play.
"""

from __future__ import annotations

from gethsharding_tpu.resilience.errors import (
    DeadlineExceeded,
    DispatcherClosed,
    FetchAborted,
    ResilienceError,
    SoundnessViolation,
    TransientError,
)

_LAZY = {
    "RetryPolicy": ("policy", "RetryPolicy"),
    "RetryExecutor": ("policy", "RetryExecutor"),
    "retry_call": ("policy", "retry_call"),
    "poll_probe": ("policy", "poll_probe"),
    "POLL_MISS": ("policy", "POLL_MISS"),
    "CircuitBreaker": ("breaker", "CircuitBreaker"),
    "FailoverSigBackend": ("breaker", "FailoverSigBackend"),
    "DispatchWatchdog": ("watchdog", "DispatchWatchdog"),
    "VoteJournal": ("journal", "VoteJournal"),
    "ChaosSchedule": ("chaos", "ChaosSchedule"),
    "ChaosSigBackend": ("chaos", "ChaosSigBackend"),
    "InjectedFault": ("chaos", "InjectedFault"),
    "parse_spec": ("chaos", "parse_spec"),
    "wrap": ("chaos", "wrap"),
    "SpotCheckSigBackend": ("soundness", "SpotCheckSigBackend"),
    "detection_probability": ("soundness", "detection_probability"),
    "dispatches_to_detect": ("soundness", "dispatches_to_detect"),
    "soundness_table": ("soundness", "soundness_table"),
}

__all__ = [
    "DeadlineExceeded", "DispatcherClosed", "FetchAborted",
    "ResilienceError", "SoundnessViolation", "TransientError",
    *sorted(_LAZY),
]


def __getattr__(name: str):
    try:
        module_name, attr = _LAZY[name]
    except KeyError:
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}") from None
    import importlib

    module = importlib.import_module(f"{__name__}.{module_name}")
    value = getattr(module, attr)
    globals()[name] = value  # cache: next access skips __getattr__
    return value
