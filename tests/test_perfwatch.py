"""perfwatch: trustworthy timing, the benchmark ledger + regression
gate, and the black-box flight recorder.

The ISSUE-13 acceptance coverage:

- the regression detector passes 20 seeded-noise clean runs and flags
  an injected 1.3x slowdown (and recovers on the next clean run);
- the device-timer self-check detects a simulated no-op
  ``block_until_ready`` (a block that does not wait), increments
  ``perfwatch/timer_suspect`` and invalidates the enclosing record;
- a chaos-injected dispatch hang under the serving watchdog produces a
  COMPLETE flight-recorder bundle (event ring + span ring + metrics
  snapshot + wire ring + ledger tail);
- the resilience seams (breaker trip, soundness violation) feed the
  recorder; the single ledger writer normalizes every bench emission;
  /status's perf section renders.
"""

import json
import os
import random
import time

import numpy as np
import pytest

from gethsharding_tpu import metrics, perfwatch
from gethsharding_tpu.perfwatch import gate as pgate
from gethsharding_tpu.perfwatch import registry as pregistry
from gethsharding_tpu.perfwatch.ledger import Ledger, record_bench
from gethsharding_tpu.perfwatch.recorder import RECORDER, FlightRecorder
from gethsharding_tpu.perfwatch.timer import (DeviceTimer, checked_pull,
                                              ensure_host)


# == ledger ================================================================


def test_ledger_append_and_read_roundtrip(tmp_path):
    led = Ledger(str(tmp_path / "ledger.jsonl"))
    rec = led.append({"workload": "w", "metrics": {"wall_s": 0.5}})
    assert rec["schema"] == 1 and rec["valid"] is True
    assert rec["ts"] and rec["env"].get("python")
    got = led.records()
    assert len(got) == 1 and got[0]["workload"] == "w"
    assert got[0]["metrics"]["wall_s"] == 0.5


def test_ledger_rejects_malformed_records(tmp_path):
    led = Ledger(str(tmp_path / "ledger.jsonl"))
    with pytest.raises(ValueError):
        led.append({"metrics": {"wall_s": 1.0}})  # no workload
    with pytest.raises(ValueError):
        led.append({"workload": "w", "metrics": {}})  # empty metrics
    with pytest.raises(ValueError):
        led.append({"workload": "w", "metrics": {"x": "fast"}})  # non-num


def test_ledger_skips_corrupt_lines(tmp_path):
    path = tmp_path / "ledger.jsonl"
    led = Ledger(str(path))
    led.append({"workload": "w", "metrics": {"v_s": 1.0}})
    with open(path, "a") as fh:
        fh.write("{truncated-mid-append\n")
    led.append({"workload": "w", "metrics": {"v_s": 2.0}})
    assert [r["metrics"]["v_s"] for r in led.records()] == [1.0, 2.0]


def test_ledger_last_is_tail_read(tmp_path):
    """last() parses only the file tail (the /status scrape path) and
    agrees with records()[-1], skipping a torn trailing line."""
    path = tmp_path / "ledger.jsonl"
    led = Ledger(str(path))
    assert led.last() is None  # no file yet
    for i in range(5):
        led.append({"workload": f"w{i}", "metrics": {"v_s": float(i)}})
    assert led.last()["workload"] == "w4"
    with open(path, "a") as fh:
        fh.write('{"torn')  # interrupted append must not break /status
    assert led.last()["workload"] == "w4"


def test_record_bench_one_writer_schema(tmp_path):
    led = Ledger(str(tmp_path / "ledger.jsonl"))
    rec = record_bench(
        metric="das_sampled_bytes_per_collation", value=69760,
        unit="bytes", vs_baseline=0.266,
        extra={"platform": "cpu", "k_samples": 16, "bytes_ratio": 0.266,
               "verify_backend": "jax", "knobs": {"K": "V"}},
        ledger=led)
    assert rec["workload"] == "das_sampled_bytes_per_collation"
    assert rec["platform"] == "cpu"
    assert rec["metrics"]["value"] == 69760.0
    assert rec["metrics"]["bytes_ratio"] == 0.266  # numeric extra -> metric
    assert rec["extra"]["verify_backend"] == "jax"  # string stays extra
    assert rec["knobs"] == {"K": "V"}
    assert rec["shape"]["k_samples"] == 16


def test_record_bench_suspect_invalidates(tmp_path):
    led = Ledger(str(tmp_path / "ledger.jsonl"))
    rec = record_bench(metric="m", value=1.0, suspects=2, ledger=led)
    assert rec["valid"] is False and rec["suspects"] == 2


# == regression gate =======================================================


def _seeded_history(led, n, base=0.1, noise=0.03, seed=0,
                    workload="micro/demo"):
    rng = random.Random(seed)
    for _ in range(n):
        wall = base * (1.0 + rng.uniform(-noise, noise))
        led.append({"workload": workload, "backend": "host",
                    "platform": "host", "source": "micro",
                    "metrics": {"wall_s": round(wall, 9),
                                "rows_per_s": round(8 / wall, 6)}})


def test_gate_20_clean_seeded_runs_pass(tmp_path):
    """The ISSUE acceptance: 20 consecutive clean checks over seeded
    +/-3% noise must all pass."""
    led = Ledger(str(tmp_path / "ledger.jsonl"))
    _seeded_history(led, 10)  # baseline build-up
    rng = random.Random(99)
    for i in range(20):
        wall = 0.1 * (1.0 + rng.uniform(-0.03, 0.03))
        led.append({"workload": "micro/demo", "backend": "host",
                    "platform": "host", "source": "micro",
                    "metrics": {"wall_s": round(wall, 9),
                                "rows_per_s": round(8 / wall, 6)}})
        result = pgate.check(led)
        assert not result.failed, (i, [vars(v) for v in
                                       result.regressions])


def test_gate_flags_injected_13x_slowdown(tmp_path):
    led = Ledger(str(tmp_path / "ledger.jsonl"))
    _seeded_history(led, 10)
    led.append({"workload": "micro/demo", "backend": "host",
                "platform": "host", "source": "micro",
                "metrics": {"wall_s": 0.1 * 1.3,
                            "rows_per_s": 8 / (0.1 * 1.3)}})
    result = pgate.check(led)
    assert result.failed
    flagged = {(v.workload, v.metric) for v in result.regressions}
    assert ("micro/demo", "wall_s") in flagged
    # direction is honored: the rate metric regressed DOWNWARD
    assert ("micro/demo", "rows_per_s") in flagged
    # ... and the next clean run heals (the outlier cannot drag the
    # rolling median)
    _seeded_history(led, 1, seed=7)
    assert not pgate.check(led).failed


def test_gate_improvement_and_building_statuses(tmp_path):
    led = Ledger(str(tmp_path / "ledger.jsonl"))
    _seeded_history(led, 2)
    building = pgate.check(led)
    assert not building.failed
    assert all(v.status == "baseline_building" for v in building.verdicts)
    _seeded_history(led, 8)
    led.append({"workload": "micro/demo", "backend": "host",
                "platform": "host", "source": "micro",
                "metrics": {"wall_s": 0.05, "rows_per_s": 160.0}})
    result = pgate.check(led)
    assert not result.failed
    assert {v.status for v in result.verdicts} == {"improvement"}


def test_gate_excludes_injected_drills_from_baselines(tmp_path):
    """Labeled injection drills never join a baseline — repeated CI
    drills must not MAD-inflate the band until real regressions hide
    under the cap."""
    led = Ledger(str(tmp_path / "ledger.jsonl"))
    _seeded_history(led, 8)
    for _ in range(4):  # four drills against the same ledger
        led.append({"workload": "micro/demo", "backend": "host",
                    "platform": "host", "source": "micro",
                    "extra": {"injected": 1.5},
                    "metrics": {"wall_s": 0.15, "rows_per_s": 8 / 0.15}})
    # a real 22% regression must STILL trip (band stays at the floor,
    # not widened by the drills' scatter)
    led.append({"workload": "micro/demo", "backend": "host",
                "platform": "host", "source": "micro",
                "metrics": {"wall_s": 0.122, "rows_per_s": 8 / 0.122}})
    result = pgate.check(led)
    assert result.failed, [vars(v) for v in result.verdicts]


def test_gate_excludes_invalid_records(tmp_path):
    """A suspect (invalid) record neither fails the gate nor joins the
    baseline."""
    led = Ledger(str(tmp_path / "ledger.jsonl"))
    _seeded_history(led, 8)
    led.append({"workload": "micro/demo", "backend": "host",
                "platform": "host", "valid": False, "source": "micro",
                "metrics": {"wall_s": 50.0, "rows_per_s": 0.1}})
    assert not pgate.check(led).failed


def test_gate_groups_by_platform(tmp_path):
    """A CPU run is never judged against TPU history."""
    led = Ledger(str(tmp_path / "ledger.jsonl"))
    for _ in range(6):
        led.append({"workload": "w", "backend": "jax", "platform": "tpu",
                    "metrics": {"dispatch_s": 0.3}})
    led.append({"workload": "w", "backend": "jax", "platform": "cpu",
                "metrics": {"dispatch_s": 30.0}})  # 100x "slower": new group
    result = pgate.check(led)
    assert not result.failed


def test_gate_checks_the_headline_value_metric(tmp_path):
    """The bench record's primary number lands under metrics['value'];
    its direction comes from the WORKLOAD name — a 2x sig-rate drop
    must trip the gate, not pass as 'informational'."""
    led = Ledger(str(tmp_path / "ledger.jsonl"))
    for _ in range(6):
        led.append({"workload": "notary_sig_verifications_per_sec",
                    "backend": "jax", "platform": "tpu",
                    "metrics": {"value": 45000.0}})
    led.append({"workload": "notary_sig_verifications_per_sec",
                "backend": "jax", "platform": "tpu",
                "metrics": {"value": 20000.0}})
    result = pgate.check(led)
    assert result.failed
    assert any(v.metric == "value" for v in result.regressions)
    # ... and byte workloads gate upward (wire growth is a regression)
    led2 = Ledger(str(tmp_path / "ledger2.jsonl"))
    for _ in range(6):
        led2.append({"workload": "das_sampled_bytes_per_collation",
                     "backend": "jax", "platform": "cpu",
                     "metrics": {"value": 69760.0}})
    led2.append({"workload": "das_sampled_bytes_per_collation",
                 "backend": "jax", "platform": "cpu",
                 "metrics": {"value": 262144.0}})
    assert pgate.check(led2).failed


def test_gate_direction_inference():
    assert pgate.direction_for("dispatch_s") == "lower"
    assert pgate.direction_for("wire_bytes") == "lower"
    assert pgate.direction_for("overhead_pct") == "lower"
    assert pgate.direction_for("sig_rate") == "higher"
    assert pgate.direction_for("rows_per_s") == "higher"
    assert pgate.direction_for("chaos_availability") == "higher"
    assert pgate.direction_for("verify_speedup") == "higher"
    assert pgate.direction_for("watchdog_deadline_s") is None  # a knob
    assert pgate.direction_for("k_periods") is None  # no direction
    # workload-name forms of the headline metrics
    assert pgate.direction_for(
        "notary_sig_verifications_per_sec") == "higher"
    assert pgate.direction_for(
        "das_sampled_bytes_per_collation") == "lower"
    assert pgate.direction_for(
        "audit_warm_wire_bytes_per_dispatch") == "lower"
    # cache-HIT bytes: more saved is better — never gated lower
    assert pgate.direction_for("pk_hit_bytes_warm") is None


def test_gate_report_renders_tables(tmp_path):
    led = Ledger(str(tmp_path / "ledger.jsonl"))
    led.append({"workload": "notary_sig_verifications_per_sec",
                "platform": "tpu", "backend": "jax",
                "metrics": {"value": 45487.7, "dispatch_s": 0.2968}})
    result = pgate.check(led)
    text = pgate.report(led, result=result)
    assert "45487.7" in text and "measured history" in text
    assert "| workload |" in text


# == microbench registry ===================================================


def test_micro_suite_runs_and_records(tmp_path):
    led = Ledger(str(tmp_path / "ledger.jsonl"))
    records = pregistry.run_suite(ledger=led, quick=True, inject={},
                                  names=["bucket_policy_10k",
                                         "keccak_256x64"])
    assert len(records) == 2
    for rec in records:
        assert rec["workload"].startswith("micro/")
        assert rec["metrics"]["wall_s"] > 0
        assert rec["source"] == "micro" and rec["valid"] is True


def test_micro_injection_scales_and_labels(tmp_path):
    led = Ledger(str(tmp_path / "ledger.jsonl"))
    clean = pregistry.run(
        "bucket_policy_10k", ledger=led, inject={})["metrics"]["wall_s"]
    injected = pregistry.run("bucket_policy_10k", ledger=led,
                             inject={"bucket_policy_10k": 3.0})
    assert injected["extra"]["injected"] == 3.0
    assert injected["metrics"]["wall_s"] > clean * 1.5  # honestly scaled
    # rates scale the OPPOSITE way (a slowdown must never record as a
    # rate improvement — "_per_s" also ends with "_s")
    assert injected["metrics"]["calls_per_s"] < (10_000 / clean) / 1.5
    assert pregistry.parse_inject("a:1.3,b:2") == {"a": 1.3, "b": 2.0}
    with pytest.raises(ValueError):
        pregistry.parse_inject("garbage")


# == DeviceTimer self-check ================================================


class _NoopBlockValue:
    """block_until_ready no-ops; the real pull pays the latency — a
    hidden sub-second DISPATCH, above the 0.25 s suspect floor (a short
    verdict-plane pull stays below it on purpose)."""

    def __init__(self, pull_s=0.3):
        self.pull_s = pull_s

    def block_until_ready(self):
        return self

    def __array__(self, dtype=None, copy=None):
        time.sleep(self.pull_s)
        return np.zeros(4, dtype=dtype or np.int32)


class _HonestBlockValue:
    """block waits for the 'device'; the pull is then instant."""

    def block_until_ready(self):
        time.sleep(0.08)
        return self

    def __array__(self, dtype=None, copy=None):
        return np.zeros(4, dtype=dtype or np.int32)


def test_timer_detects_noop_block():
    before = perfwatch.suspect_count()
    dt = DeviceTimer("test_op")
    dt.dispatched()
    arr = dt.pull(_NoopBlockValue())
    dt.done()
    assert arr.shape == (4,)
    assert dt.suspect is True
    assert perfwatch.suspect_count() == before + 1
    # the event landed in the flight-recorder ring
    kinds = [e for e in RECORDER.events() if e["kind"] == "timer_suspect"
             and e["detail"].get("op") == "test_op"]
    assert kinds, "timer_suspect event missing from the recorder ring"


def test_timer_trusts_honest_block():
    before = perfwatch.suspect_count()
    dt = DeviceTimer("test_op_honest")
    dt.dispatched()
    dt.pull(_HonestBlockValue())
    dt.done()
    assert dt.suspect is False
    assert perfwatch.suspect_count() == before
    assert dt.device_s >= 0.08  # the block time counts as device time


def test_timer_fast_pull_never_suspect():
    """Sub-floor pulls (healthy fast dispatches, overlapped audits
    where the device finished early) are never suspect."""
    before = perfwatch.suspect_count()
    dt = DeviceTimer("test_op_fast")
    dt.dispatched()
    dt.pull(np.arange(8))
    dt.done()
    assert dt.suspect is False
    assert perfwatch.suspect_count() == before


def test_timer_rtt_scale_pull_not_suspect():
    """An overlapped audit: the device finished before the pull, so
    the block is near-instant and the pull pays only the verdict-plane
    transfer (~0.08 s here) — an HONEST reading below the 0.25 s floor,
    never flagged (only a block hiding a whole sub-second dispatch is
    the hazard)."""
    before = perfwatch.suspect_count()
    dt = DeviceTimer("test_op_rtt")
    dt.dispatched()
    dt.pull(_NoopBlockValue(pull_s=0.08))
    dt.done()
    assert dt.suspect is False
    assert perfwatch.suspect_count() == before


def test_timer_feeds_sig_rollups():
    t_m = metrics.timer("sig/marshal_time")
    t_d = metrics.timer("sig/device_time")
    before_m, before_d = t_m.count, t_d.count
    dt = DeviceTimer("rollup_probe")
    dt.dispatched()
    dt.pull(np.arange(4))
    dt.done()
    assert t_m.count == before_m + 1
    assert t_d.count == before_d + 1


def test_checked_pull_and_ensure_host():
    assert checked_pull(np.arange(3)).tolist() == [0, 1, 2]
    assert ensure_host([1, 2]) == [1, 2]  # host containers untouched
    assert ensure_host(None) is None
    out = ensure_host(_NoopBlockValue(pull_s=0.0), op="eh")
    assert isinstance(out, np.ndarray)


def test_jax_dispatch_goes_through_device_timer():
    """The adopted sigbackend path: a real (CPU) jax ecrecover dispatch
    must observe the rollup timers via DeviceTimer."""
    from gethsharding_tpu.crypto import secp256k1 as ecdsa
    from gethsharding_tpu.crypto.keccak import keccak256
    from gethsharding_tpu.sigbackend import get_backend

    t_d = metrics.timer("sig/device_time")
    before = t_d.count
    priv = int.from_bytes(keccak256(b"pw-jax"), "big") % ecdsa.N
    digest = keccak256(b"pw-jax-msg")
    backend = get_backend("jax")
    got = backend.ecrecover_addresses(
        [digest], [ecdsa.sign(digest, priv).to_bytes65()])
    assert got == [ecdsa.priv_to_address(priv)]
    assert t_d.count > before


# == flight recorder =======================================================


def test_recorder_ring_bounded_and_ordered():
    rec = FlightRecorder(ring=4)
    for i in range(10):
        rec.record("k", i=i)
    events = rec.events()
    assert len(events) == 4
    assert [e["detail"]["i"] for e in events] == [6, 7, 8, 9]


def test_recorder_wire_ring():
    rec = FlightRecorder(ring=8, wire_ring=2)
    rec.record_wire("op", {"wire_bytes": 1})
    rec.record_wire("op", {"wire_bytes": 2})
    rec.record_wire("op", {"wire_bytes": 3})
    assert [w["wire_bytes"] for w in rec.wires()] == [2, 3]
    rec.record_wire("op", None)  # empty ledgers are dropped, not stored
    assert len(rec.wires()) == 2


def test_recorder_dump_bundle_complete(tmp_path, monkeypatch):
    monkeypatch.setenv("GETHSHARDING_PERFWATCH_DIR", str(tmp_path))
    monkeypatch.setenv("GETHSHARDING_PERFWATCH_DUMP_S", "0")
    rec = FlightRecorder(ring=8)
    rec.record("something", x=1)
    rec.record_wire("op", {"wire_bytes": 7})
    path = rec.dump("unit_test")
    assert path is not None
    files = sorted(os.listdir(path))
    assert files == ["events.json", "ledger_tail.jsonl", "manifest.json",
                     "metrics.json", "spans.json", "wire.json"]
    manifest = json.load(open(os.path.join(path, "manifest.json")))
    assert manifest["reason"] == "unit_test"
    events = json.load(open(os.path.join(path, "events.json")))
    assert events and events[-1]["kind"] == "something"
    wires = json.load(open(os.path.join(path, "wire.json")))
    assert wires[0]["wire_bytes"] == 7


def test_recorder_rate_limit_and_prune(tmp_path, monkeypatch):
    monkeypatch.setenv("GETHSHARDING_PERFWATCH_DIR", str(tmp_path))
    monkeypatch.setenv("GETHSHARDING_PERFWATCH_DUMP_S", "3600")
    monkeypatch.setenv("GETHSHARDING_PERFWATCH_BUNDLES", "2")
    rec = FlightRecorder(ring=8)
    assert rec.dump("first") is not None
    assert rec.dump("suppressed") is None  # inside the min interval
    assert rec.dump("forced", force=True) is not None
    assert rec.dump("forced2", force=True) is not None
    assert len(os.listdir(tmp_path)) == 2  # pruned to the newest 2


def test_recorder_disabled_is_noop(tmp_path, monkeypatch):
    monkeypatch.setenv("GETHSHARDING_PERFWATCH_RECORDER", "0")
    monkeypatch.setenv("GETHSHARDING_PERFWATCH_DIR", str(tmp_path))
    rec = FlightRecorder(ring=8)
    rec.record("k")
    rec.trigger("k", dump=True)
    rec.flush()
    assert rec.events() == []
    assert os.listdir(tmp_path) == []


# == the resilience seams feed the recorder ================================


def test_breaker_trip_records_and_dumps(tmp_path, monkeypatch):
    from gethsharding_tpu.metrics import Registry
    from gethsharding_tpu.resilience.breaker import (OPEN, CircuitBreaker)

    monkeypatch.setenv("GETHSHARDING_PERFWATCH_DIR", str(tmp_path))
    monkeypatch.setenv("GETHSHARDING_PERFWATCH_DUMP_S", "0")
    breaker = CircuitBreaker(name="pw-test", fault_threshold=1,
                             reset_s=60.0, registry=Registry())
    breaker.record_fault(RuntimeError("boom"))
    assert breaker.state == OPEN
    trips = [e for e in RECORDER.events()
             if e["kind"] == "breaker_trip"
             and e["detail"].get("breaker") == "pw-test"]
    assert trips, "breaker trip missing from the recorder ring"
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline and not os.listdir(tmp_path):
        RECORDER.flush()
        time.sleep(0.02)
    assert os.listdir(tmp_path), "breaker trip produced no bundle"


def test_soundness_violation_records_event(monkeypatch, tmp_path):
    from gethsharding_tpu.metrics import Registry
    from gethsharding_tpu.resilience.errors import SoundnessViolation
    from gethsharding_tpu.resilience.soundness import SpotCheckSigBackend
    from gethsharding_tpu.sigbackend import PythonSigBackend

    monkeypatch.setenv("GETHSHARDING_PERFWATCH_DIR", str(tmp_path))

    class _Corrupt(PythonSigBackend):
        name = "corrupt"

        def ecrecover_addresses(self, digests, sigs65):
            out = super().ecrecover_addresses(digests, sigs65)
            return [None] * len(out)  # silently wrong

    spot = SpotCheckSigBackend(_Corrupt(), rate=1.0, registry=Registry())
    from gethsharding_tpu.crypto import secp256k1 as ecdsa
    from gethsharding_tpu.crypto.keccak import keccak256

    priv = int.from_bytes(keccak256(b"pw-sound"), "big") % ecdsa.N
    digest = keccak256(b"pw-sound-msg")
    with pytest.raises(SoundnessViolation):
        spot.ecrecover_addresses([digest],
                                 [ecdsa.sign(digest, priv).to_bytes65()])
    events = [e for e in RECORDER.events()
              if e["kind"] == "soundness_violation"]
    assert events and events[-1]["detail"]["op"] == "ecrecover_addresses"
    RECORDER.flush()


def test_chaos_hang_watchdog_bundle_complete(tmp_path, monkeypatch):
    """THE ISSUE acceptance: a chaos-injected dispatch hang must leave
    a complete black-box bundle (events + spans + metrics + wire +
    ledger tail), with the watchdog_timeout and chaos_decision events
    in the ring."""
    from gethsharding_tpu.resilience.chaos import (ChaosSchedule,
                                                   ChaosSigBackend)
    from gethsharding_tpu.resilience.errors import DeadlineExceeded
    from gethsharding_tpu.serving import ServingConfig, ServingSigBackend
    from gethsharding_tpu.sigbackend import PythonSigBackend

    monkeypatch.setenv("GETHSHARDING_PERFWATCH_DIR", str(tmp_path))
    monkeypatch.setenv("GETHSHARDING_PERFWATCH_DUMP_S", "0")
    schedule = ChaosSchedule(seed=7,
                             rules={"dispatch.ecrecover_addresses": 1})
    serving = ServingSigBackend(
        ChaosSigBackend(PythonSigBackend(), schedule, hang_s=2.0),
        ServingConfig(flush_us=200.0, watchdog_s=0.15))
    try:
        with pytest.raises(DeadlineExceeded):
            serving.ecrecover_addresses([b"\x11" * 32], [b"\x22" * 65])
        deadline = time.monotonic() + 10.0
        bundle = None
        while time.monotonic() < deadline:
            RECORDER.flush()
            dirs = sorted(os.listdir(tmp_path))
            if dirs:
                bundle = tmp_path / dirs[-1]
                break
            time.sleep(0.02)
        assert bundle is not None, "watchdog fired but no bundle appeared"
        files = sorted(os.listdir(bundle))
        for required in ("manifest.json", "events.json", "spans.json",
                         "metrics.json", "wire.json", "ledger_tail.jsonl"):
            assert required in files, (required, files)
        events = json.load(open(bundle / "events.json"))
        kinds = {e["kind"] for e in events}
        assert "watchdog_timeout" in kinds, kinds
        assert "chaos_decision" in kinds, kinds
        snapshot = json.load(open(bundle / "metrics.json"))
        assert "resilience/watchdog/timeouts" in snapshot
    finally:
        serving.close()


# == surfaces ==============================================================


def test_cli_check_exit_codes(tmp_path):
    import subprocess
    import sys as _sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = tmp_path / "ledger.jsonl"
    led = Ledger(str(path))
    _seeded_history(led, 8)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    ok = subprocess.run(
        [_sys.executable, "-m", "gethsharding_tpu.perfwatch", "--check",
         "--ledger", str(path)],
        capture_output=True, text=True, timeout=120, cwd=repo, env=env)
    assert ok.returncode == 0, ok.stderr
    led.append({"workload": "micro/demo", "backend": "host",
                "platform": "host", "metrics": {"wall_s": 0.2}})
    bad = subprocess.run(
        [_sys.executable, "-m", "gethsharding_tpu.perfwatch", "--check",
         "--json", "--ledger", str(path)],
        capture_output=True, text=True, timeout=120, cwd=repo, env=env)
    assert bad.returncode == 1, (bad.stdout, bad.stderr)
    verdicts = json.loads(bad.stdout.strip().splitlines()[-1])
    assert verdicts["failed"] is True


def test_perf_status_section(tmp_path, monkeypatch):
    path = tmp_path / "ledger.jsonl"
    monkeypatch.setenv("GETHSHARDING_PERFWATCH_LEDGER", str(path))
    led = Ledger(str(path))
    led.append({"workload": "w", "platform": "host",
                "metrics": {"value": 42.0}})
    pgate.check(led)
    status = perfwatch.perf_status()
    assert status["ledger"]["last"]["workload"] == "w"
    assert status["ledger"]["last"]["value"] == 42.0
    assert status["gate"] is not None and "failed" in status["gate"]
    assert "timer_suspect" in status
    assert "events" in status["recorder"]


def test_perfwatch_prometheus_rows():
    from gethsharding_tpu.metrics import prometheus_text

    text = prometheus_text()
    for needle in ("gethsharding_perfwatch_timer_suspect_total",
                   "gethsharding_perfwatch_pulls_total",
                   "gethsharding_perfwatch_events_total",
                   "gethsharding_perfwatch_bundles_total",
                   "gethsharding_perfwatch_ledger_records_total"):
        assert needle in text, needle
