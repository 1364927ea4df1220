"""The continuous benchmark ledger: append-only JSON lines, one writer.

Hand-edited tables and ad-hoc JSON artifacts give a regression gate
nothing to diff mechanically. The ledger is the one place every
measurement of the microbench suite and the soak script lands:

- **One schema.** Every record carries the workload name, the batch
  shape, the backend + platform it ran on, the active kernel knobs, an
  environment fingerprint (git revision, python, host), the per-stage
  timing/wire metrics as a flat numeric dict, and a validity verdict
  (a record taken while the device timer's block-vs-pull self-check
  fired is stamped ``valid: false`` — see perfwatch/timer.py).
- **One writer.** `Ledger.append` is the only code path that writes;
  `record_bench` adapts the one-line ``{metric, value, unit, extra}``
  shape onto it so every emitter (perfwatch/registry.py,
  scripts/serving_stress.py) shares the schema instead of each keeping
  its own drifting extras dict.
- **Append-only JSON lines.** History is never rewritten; the
  regression gate (perfwatch/gate.py) reads a rolling window backward.

The default path is ``perf_ledger.jsonl`` in the working directory,
overridable with ``GETHSHARDING_PERFWATCH_LEDGER``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional

from gethsharding_tpu import metrics

SCHEMA_VERSION = 1

# registered at import so the Prometheus exposition carries the row
# from the first scrape, not the first append
_M_RECORDS = metrics.counter("perfwatch/ledger/records")
_M_PARSE_ERRORS = metrics.counter("perfwatch/ledger/parse_errors")


def default_path() -> str:
    """The process ledger file: env override or ./perf_ledger.jsonl."""
    return os.environ.get("GETHSHARDING_PERFWATCH_LEDGER",
                          os.path.join(os.getcwd(), "perf_ledger.jsonl"))


_FINGERPRINT: Optional[dict] = None
_FP_LOCK = threading.Lock()


def env_fingerprint() -> dict:
    """The record's reproducibility stamp: enough to say WHERE a number
    came from without re-deriving it (git revision, interpreter, host).
    Computed once per process; jax's version is reported only when jax
    is already imported — fingerprinting must never initialize an
    accelerator backend."""
    global _FINGERPRINT
    with _FP_LOCK:
        if _FINGERPRINT is None:
            import platform as _platform

            fp = {
                "python": _platform.python_version(),
                "host": _platform.node(),
                "machine": _platform.machine(),
            }
            try:
                fp["git"] = subprocess.run(
                    ["git", "rev-parse", "--short", "HEAD"],
                    capture_output=True, text=True, timeout=10,
                    cwd=os.path.dirname(os.path.abspath(__file__)),
                ).stdout.strip() or None
            except (subprocess.SubprocessError, OSError):
                fp["git"] = None
            _FINGERPRINT = fp
        fp = dict(_FINGERPRINT)
    jax = sys.modules.get("jax")
    if jax is not None:
        fp["jax"] = getattr(jax, "__version__", None)
    return fp


def knob_snapshot() -> Dict[str, str]:
    """The `GETHSHARDING_TPU_*` variables of this process (records
    must be self-describing about the code paths they timed)."""
    return {key: val for key, val in os.environ.items()
            if key.startswith("GETHSHARDING_TPU_")}


class Ledger:
    """Append-only JSONL measurement history behind one lock."""

    def __init__(self, path: Optional[str] = None):
        self.path = path or default_path()
        self._lock = threading.Lock()

    # -- writing -----------------------------------------------------------

    def append(self, record: dict) -> dict:
        """Normalize + append one record; returns the completed record.
        Required: ``workload`` and a numeric ``metrics`` dict. Fills
        schema/ts/env/knobs when absent, never mutates history."""
        if not record.get("workload"):
            raise ValueError("ledger record needs a workload name")
        metrics_dict = record.get("metrics")
        if not isinstance(metrics_dict, dict) or not metrics_dict:
            raise ValueError("ledger record needs a non-empty metrics dict")
        for key, val in metrics_dict.items():
            if not isinstance(val, (int, float)) or isinstance(val, bool):
                raise ValueError(
                    f"metric {key!r} must be numeric, got {val!r}")
        out = dict(record)
        out.setdefault("schema", SCHEMA_VERSION)
        out.setdefault("ts_unix", time.time())
        out.setdefault("ts", time.strftime("%Y-%m-%d %H:%M:%S",
                                           time.localtime(out["ts_unix"])))
        out.setdefault("env", env_fingerprint())
        out.setdefault("knobs", knob_snapshot())
        out.setdefault("valid", True)
        out.setdefault("source", "bench")
        line = json.dumps(out, sort_keys=True)
        with self._lock:
            parent = os.path.dirname(self.path)
            if parent:
                os.makedirs(parent, exist_ok=True)
            with open(self.path, "a") as fh:
                fh.write(line + "\n")
        _M_RECORDS.inc()
        return out

    # -- reading -----------------------------------------------------------

    def records(self, workload: Optional[str] = None,
                valid_only: bool = False) -> List[dict]:
        """All parseable records, file order (oldest first). Corrupt
        lines are counted (`perfwatch/ledger/parse_errors`) and
        skipped — an interrupted append must not poison the gate."""
        out: List[dict] = []
        try:
            with open(self.path) as fh:
                lines = fh.readlines()
        except OSError:
            return out
        for line in lines:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                _M_PARSE_ERRORS.inc()
                continue
            if not isinstance(rec, dict) or "workload" not in rec:
                _M_PARSE_ERRORS.inc()
                continue
            if workload is not None and rec.get("workload") != workload:
                continue
            if valid_only and rec.get("valid") is False:
                continue
            out.append(rec)
        return out

    def tail(self, n: int = 32) -> List[dict]:
        """The newest `n` parseable records from a BOUNDED tail read
        (~16 KB per requested record, seek-from-end). The flight
        recorder calls this on its post-mortem dump path — incident
        moments must not pay a full-file parse on a ledger that has
        grown for months."""
        try:
            with open(self.path, "rb") as fh:
                fh.seek(0, os.SEEK_END)
                size = fh.tell()
                window = min(size, max(1, n) * 16384)
                fh.seek(size - window)
                chunk = fh.read().decode("utf-8", "replace")
        except OSError:
            return []
        out: List[dict] = []
        lines = chunk.strip().splitlines()
        if size > window and lines:
            lines = lines[1:]  # the window's first line may be torn
        for line in lines:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if isinstance(rec, dict) and "workload" in rec:
                out.append(rec)
        return out[-n:]

    def last(self) -> Optional[dict]:
        """The newest parseable record, read from the file TAIL — O(1)
        in ledger size. /status calls this on every scrape; a full
        `records()` parse would grow without bound on an append-only
        file."""
        try:
            with open(self.path, "rb") as fh:
                fh.seek(0, os.SEEK_END)
                size = fh.tell()
                fh.seek(max(0, size - 65536))
                chunk = fh.read().decode("utf-8", "replace")
        except OSError:
            return None
        for line in reversed(chunk.strip().splitlines()):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue  # a torn first line of the tail window
            if isinstance(rec, dict) and "workload" in rec:
                return rec
        return None

    def workloads(self) -> List[str]:
        seen: List[str] = []
        for rec in self.records():
            name = rec.get("workload")
            if name not in seen:
                seen.append(name)
        return seen


def build_record(metric: str, value: float, unit: Optional[str] = None,
                 vs_baseline: Optional[float] = None,
                 extra: Optional[dict] = None,
                 workload: Optional[str] = None,
                 source: str = "bench", valid: bool = True,
                 suspects: int = 0) -> dict:
    """THE adapter from the one-line ``{metric, value, unit,
    vs_baseline, extra}`` contract onto the ledger schema, so the
    extras-splitting rules live in one function. Numeric extras become
    gateable metrics; everything else rides in ``extra`` verbatim."""
    extra = dict(extra or {})
    mets: Dict[str, float] = {"value": float(value)}
    rest: Dict[str, object] = {}
    for key, val in extra.items():
        if isinstance(val, bool):
            rest[key] = val
        elif isinstance(val, (int, float)):
            mets[key] = float(val)
        else:
            rest[key] = val
    record = {
        "workload": workload or metric,
        "metric": metric,
        "unit": unit,
        "vs_baseline": vs_baseline,
        "backend": rest.get("backend") or rest.get("primary"),
        "platform": rest.get("platform", extra.get("platform")),
        "shape": {k: int(mets[k]) for k in ("rows", "clients", "replicas",
                                            "k_samples", "verify_rows")
                  if k in mets},
        "knobs": (extra.get("knobs") if isinstance(extra.get("knobs"), dict)
                  else knob_snapshot()),
        "metrics": mets,
        "extra": {k: v for k, v in rest.items() if k != "knobs"},
        "valid": bool(valid) and suspects == 0,
        "suspects": int(suspects),
        "source": source,
    }
    return record


# devscope stamp routing: the peak-HBM watermark is a GATED metric
# (memory creep flags like latency); the compile totals are
# process-cumulative — what they measure depends on every mode that
# ran earlier in the same process, so gating them would flag
# invocation composition, not compile growth. They ride in `extra`
# as attribution.
_DEVSCOPE_GATED = ("peak_hbm_bytes",)


def _devscope_fields() -> Dict[str, float]:
    """The device-introspection stamp every LIVE record carries: the
    observed peak-HBM watermark (gated — memory creep flags like
    latency) and the cumulative compile attribution (informational).
    Lazy + best-effort: a host with no devscope plane (or an
    import-order edge case) stamps nothing."""
    try:
        from gethsharding_tpu.devscope import ledger_fields

        return {k: v for k, v in ledger_fields().items()
                if isinstance(v, (int, float))}
    except Exception:  # noqa: BLE001 - the stamp is additive
        return {}


def record_bench(metric: str, value: float, unit: Optional[str] = None,
                 vs_baseline: Optional[float] = None,
                 extra: Optional[dict] = None,
                 workload: Optional[str] = None,
                 source: str = "bench", valid: bool = True,
                 suspects: int = 0,
                 ledger: Optional[Ledger] = None) -> dict:
    """Build (`build_record`) + append in one step — the live
    emitters' entry. LIVE records (source
    \"bench\") additionally carry the devscope stamp
    (`_devscope_fields`): peak-HBM into the gated metrics dict, compile
    attribution into `extra` — ONE schema, stamped by the one writer,
    never by per-mode extras. Any other source measured ANOTHER
    process's device, and stamping this host's peak into its group
    would poison the gated memory baseline."""
    record = build_record(
        metric, value, unit=unit, vs_baseline=vs_baseline, extra=extra,
        workload=workload, source=source, valid=valid, suspects=suspects)
    if source == "bench":
        for key, val in _devscope_fields().items():
            slot = (record["metrics"] if key in _DEVSCOPE_GATED
                    else record["extra"])
            slot.setdefault(key, float(val))
    return (ledger or Ledger()).append(record)
