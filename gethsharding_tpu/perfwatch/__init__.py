"""perfwatch: trustworthy device timing, a continuous benchmark
ledger with a noise-aware regression gate, and a black-box flight
recorder.

The measurement substrate every perf PR gates against:

- ``timer.py``    — `DeviceTimer` / `checked_pull` / `ensure_host`:
  every timing closes over a REAL device->host pull, with an always-on
  block-vs-pull self-check (`perfwatch/timer_suspect`);
- ``ledger.py``   — the append-only JSONL measurement history behind
  ONE writer (`record_bench`), one schema for every emitter;
- ``registry.py`` — the CPU-quick microbench suite the gate watches;
- ``gate.py``     — `python -m gethsharding_tpu.perfwatch --check`:
  rolling-median + MAD tolerance bands per (workload, backend,
  platform), exit 1 on regression;
- ``recorder.py`` — the flight recorder: bounded structured-event +
  wire-ledger rings, post-mortem bundles on breaker trips, watchdog
  fires and soundness violations.

Surfaces: the ``perf`` section on ``/status`` (`perf_status`),
``perfwatch/*`` counters on /metrics + the Prometheus exposition
(tests/test_perfwatch.py holds the acceptance assertions).
"""

from gethsharding_tpu.perfwatch.gate import (
    CheckResult,
    Verdict,
    check,
    direction_for,
    last_check_summary,
    report,
)
from gethsharding_tpu.perfwatch.ledger import (
    Ledger,
    default_path,
    env_fingerprint,
    record_bench,
)
from gethsharding_tpu.perfwatch.recorder import RECORDER, FlightRecorder
from gethsharding_tpu.perfwatch.registry import (
    MICROBENCHES,
    microbench,
    run_suite,
)
from gethsharding_tpu.perfwatch.timer import (
    DeviceTimer,
    checked_pull,
    ensure_host,
    suspect_count,
)

__all__ = [
    "CheckResult",
    "DeviceTimer",
    "FlightRecorder",
    "Ledger",
    "MICROBENCHES",
    "RECORDER",
    "Verdict",
    "check",
    "checked_pull",
    "default_path",
    "direction_for",
    "ensure_host",
    "env_fingerprint",
    "last_check_summary",
    "microbench",
    "perf_status",
    "record_bench",
    "report",
    "run_suite",
    "suspect_count",
]


def perf_status() -> dict:
    """The node /status ``perf`` section: last ledger record, the last
    in-process regression verdicts, the timer-suspect count and the
    flight-recorder state — performance trust at a glance."""
    ledger = Ledger()
    # last(): a tail-seek read — /status is scraped continuously and
    # must not re-parse a growing append-only file per request
    rec = ledger.last()
    last = None
    if rec is not None:
        last = {"workload": rec.get("workload"), "ts": rec.get("ts"),
                "value": rec.get("metrics", {}).get("value"),
                "platform": rec.get("platform"),
                "valid": rec.get("valid", True),
                "source": rec.get("source")}
    return {
        "timer_suspect": suspect_count(),
        "ledger": {"path": ledger.path, "last": last},
        "gate": last_check_summary(),
        "recorder": RECORDER.describe(),
    }
