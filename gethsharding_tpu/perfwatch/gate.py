"""Noise-aware regression gate over the benchmark ledger.

``python -m gethsharding_tpu.perfwatch --check`` compares each
workload's newest valid ledger record against a rolling baseline of
its own history and exits 1 on a regression — the automated form of
ROADMAP item 2's "every claim comparable across rounds", and the gate
a `sigbackend.py` split has to clear before it can silently cost 10%.

How a verdict is reached, per (workload, backend, platform) group —
grouping matters: a CPU-quick run must never be judged against TPU
history, or a run without the chip would read as a 50x regression:

- the **baseline** is the median of the previous `window` valid
  records' value for each gated metric;
- the **tolerance band** is noise-aware: ``max(rel_floor,
  z_mad * sigma_rel)`` capped at `tol_cap`, where ``sigma_rel =
  1.4826 * MAD/median`` (the stddev-equivalent of the history's
  median absolute deviation) — a naturally jittery metric earns a
  wider band from its own scatter, a stable one is held to the
  floor, and no amount of historical chaos inflates the band past
  the cap (a 1.3x slowdown must ALWAYS trip);
- **direction** comes from the metric name: timings/bytes regress
  upward, rates regress downward, everything else is informational;
- fewer than `min_baseline` prior records -> ``baseline_building``
  (never a failure: a new workload earns its gate by accumulating
  history, it does not start red).

Records stamped ``valid: false`` (the device-timer self-check fired
during the measurement) are excluded from both sides: a lying timing
neither fails the gate nor poisons the baseline.
"""

from __future__ import annotations

import statistics
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from gethsharding_tpu.perfwatch.ledger import Ledger

DEFAULT_WINDOW = 12
DEFAULT_REL_FLOOR = 0.15
DEFAULT_Z_MAD = 5.0
DEFAULT_TOL_CAP = 0.28
DEFAULT_MIN_BASELINE = 3

# metric-name suffixes -> gated direction ("lower"/"higher" is better)
_LOWER_SUFFIXES = ("_s", "_ms", "_us", "_bytes", "_pct")
_HIGHER_SUFFIXES = ("_per_s", "_per_sec", "_rate", "sig_rate",
                    "_availability", "speedup")
# names that look directional but are budgets/knobs, not measurements —
# plus cache-hit byte counters, where MORE bytes served from cache is
# the good direction and a "lower" verdict would flag improvements
_UNGATED = ("deadline", "budget", "timeout", "slo_ms", "reset", "hit")


def direction_for(metric: str) -> Optional[str]:
    """'lower' / 'higher' when the metric has a regression direction,
    None when it is informational only."""
    low = metric.lower()
    if any(tok in low for tok in _UNGATED):
        return None
    if low.endswith(_HIGHER_SUFFIXES):
        return "higher"
    if low.endswith(_LOWER_SUFFIXES):
        return "lower"
    if "bytes" in low:
        # byte WORKLOAD names (das_sampled_bytes_per_collation,
        # audit_warm_wire_bytes_per_dispatch) end in their denominator,
        # not in "_bytes" — wire bytes always regress upward
        return "lower"
    return None


@dataclass
class Verdict:
    workload: str
    metric: str
    status: str          # ok | regression | improvement | baseline_building
    latest: float
    baseline: Optional[float]
    tolerance: Optional[float]   # relative band actually applied
    n_baseline: int
    group: str = ""
    delta_pct: Optional[float] = None


@dataclass
class CheckResult:
    verdicts: List[Verdict] = field(default_factory=list)
    checked_groups: int = 0

    @property
    def regressions(self) -> List[Verdict]:
        return [v for v in self.verdicts if v.status == "regression"]

    @property
    def failed(self) -> bool:
        return bool(self.regressions)


# the last in-process check, surfaced on /status (node perf section)
LAST_CHECK: Optional[CheckResult] = None


def _group_key(rec: dict) -> Tuple[str, str, str]:
    return (str(rec.get("workload")), str(rec.get("backend")),
            str(rec.get("platform")))


def check(ledger: Optional[Ledger] = None,
          window: int = DEFAULT_WINDOW,
          rel_floor: float = DEFAULT_REL_FLOOR,
          z_mad: float = DEFAULT_Z_MAD,
          tol_cap: float = DEFAULT_TOL_CAP,
          min_baseline: int = DEFAULT_MIN_BASELINE,
          workloads: Optional[List[str]] = None) -> CheckResult:
    """Run the gate over every (workload, backend, platform) group's
    newest valid record. Stores the result in `LAST_CHECK`."""
    global LAST_CHECK
    ledger = ledger or Ledger()
    groups: Dict[Tuple[str, str, str], List[dict]] = {}
    for rec in ledger.records(valid_only=True):
        if workloads is not None and rec.get("workload") not in workloads:
            continue
        groups.setdefault(_group_key(rec), []).append(rec)
    result = CheckResult()
    for key in sorted(groups):
        history = groups[key]
        if not history:
            continue
        latest = history[-1]
        # labeled injection drills (registry.run's `injected` stamp)
        # are JUDGED when latest — that is the drill — but never join
        # a baseline: a few drills in the window would MAD-inflate the
        # band to its cap and let real regressions hide under it
        baseline_recs = [rec for rec in history[:-1]
                         if not (rec.get("extra") or {}).get("injected")
                         ][-window:]
        result.checked_groups += 1
        label = f"{key[0]} [{key[1]}/{key[2]}]"
        for metric, value in sorted(latest.get("metrics", {}).items()):
            # the headline number of a bench record lands under the
            # generic "value" key (ledger.record_bench): its direction
            # comes from the WORKLOAD name (notary_sig_..._per_sec ->
            # higher, das_sampled_bytes_... -> lower) — without this the
            # gate would never check the one number each mode is for
            direction = direction_for(key[0] if metric == "value"
                                      else metric)
            if direction is None:
                continue
            samples = [rec["metrics"][metric] for rec in baseline_recs
                       if isinstance(rec.get("metrics", {}).get(metric),
                                     (int, float))]
            if len(samples) < min_baseline:
                result.verdicts.append(Verdict(
                    workload=key[0], metric=metric,
                    status="baseline_building", latest=value,
                    baseline=None, tolerance=None,
                    n_baseline=len(samples), group=label))
                continue
            median = statistics.median(samples)
            if median == 0:
                continue  # a zero baseline has no relative band
            mad = statistics.median(abs(s - median) for s in samples)
            # 1.4826 scales MAD to a stddev-equivalent under normality
            sigma_rel = 1.4826 * mad / abs(median)
            tol = min(max(rel_floor, z_mad * sigma_rel), tol_cap)
            delta = (value - median) / abs(median)
            if direction == "lower":
                status = ("regression" if delta > tol
                          else "improvement" if delta < -tol else "ok")
            else:
                status = ("regression" if delta < -tol
                          else "improvement" if delta > tol else "ok")
            result.verdicts.append(Verdict(
                workload=key[0], metric=metric, status=status,
                latest=value, baseline=median, tolerance=round(tol, 4),
                n_baseline=len(samples), group=label,
                delta_pct=round(100.0 * delta, 2)))
    LAST_CHECK = result
    return result


def last_check_summary() -> Optional[dict]:
    """The /status-friendly condensation of the last in-process check."""
    if LAST_CHECK is None:
        return None
    return {
        "groups": LAST_CHECK.checked_groups,
        "metrics_checked": len(LAST_CHECK.verdicts),
        "regressions": [
            {"workload": v.workload, "metric": v.metric,
             "latest": v.latest, "baseline": v.baseline,
             "delta_pct": v.delta_pct, "tolerance": v.tolerance}
            for v in LAST_CHECK.regressions],
        "failed": LAST_CHECK.failed,
    }


# == reporting =============================================================


def verdict_table(result: CheckResult) -> str:
    """The check as a markdown table (regressions first)."""
    lines = ["| workload | metric | latest | baseline | Δ% | band | "
             "n | status |",
             "|---|---|---|---|---|---|---|---|"]
    order = {"regression": 0, "improvement": 1, "ok": 2,
             "baseline_building": 3}
    for v in sorted(result.verdicts,
                    key=lambda v: (order.get(v.status, 9), v.group,
                                   v.metric)):
        base = "—" if v.baseline is None else f"{v.baseline:g}"
        band = "—" if v.tolerance is None else f"±{100 * v.tolerance:g}%"
        delta = "—" if v.delta_pct is None else f"{v.delta_pct:+g}%"
        lines.append(f"| {v.group} | {v.metric} | {v.latest:g} | {base} "
                     f"| {delta} | {band} | {v.n_baseline} | {v.status} |")
    return "\n".join(lines)


def history_table(ledger: Optional[Ledger] = None,
                  workload: str = "notary_sig_verifications_per_sec",
                  limit: int = 40) -> str:
    """The measured-history twin of PERF.md's hand-kept table, emitted
    from ledger records (``--check --report``): every recorded run of
    the headline workload with its provenance."""
    ledger = ledger or Ledger()
    rows = ledger.records(workload=workload)[-limit:]
    lines = [f"| when | value | platform | backend | valid | source | "
             f"knobs |",
             "|---|---|---|---|---|---|---|"]
    for rec in rows:
        mets = rec.get("metrics", {})
        knobs = rec.get("knobs") or {}
        label = "/".join(
            f"{k.replace('GETHSHARDING_TPU_', '').lower()}={v}"
            for k, v in sorted(knobs.items())) or "defaults"
        lines.append(
            f"| {rec.get('ts', '?')} | {mets.get('value', 0):g} "
            f"| {rec.get('platform')} | {rec.get('backend')} "
            f"| {rec.get('valid', True)} | {rec.get('source')} "
            f"| {label} |")
    if not rows:
        lines.append(f"| (no {workload} records) | | | | | | |")
    return "\n".join(lines)


def report(ledger: Optional[Ledger] = None,
           result: Optional[CheckResult] = None) -> str:
    """The full --report payload: headline history + per-workload
    latest snapshot + the check's verdict table when one ran."""
    ledger = ledger or Ledger()
    parts = ["## Perfwatch measured history "
             "(machine-generated from the ledger)",
             "", history_table(ledger), ""]
    latest: Dict[str, dict] = {}
    for rec in ledger.records(valid_only=True):
        latest[str(rec.get("workload"))] = rec
    if latest:
        parts += ["## Latest per workload", "",
                  "| workload | value | platform | when | source |",
                  "|---|---|---|---|---|"]
        for name in sorted(latest):
            rec = latest[name]
            parts.append(
                f"| {name} | {rec.get('metrics', {}).get('value', 0):g} "
                f"| {rec.get('platform')} | {rec.get('ts')} "
                f"| {rec.get('source')} |")
        parts.append("")
    if result is not None:
        parts += ["## Regression check", "", verdict_table(result), ""]
    return "\n".join(parts)
