"""Rehearsal checks of the benchmark's own files: `python -m pytest
benchmark/tests -q`. They run on the CPU and prove wiring and arithmetic,
never a speed."""

import json
import os
import re
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import reduce_trace  # noqa: E402
import run  # noqa: E402

with open(os.path.join(REPO, "BENCHMARK.json")) as _src:
    SPEC = json.load(_src)
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")


@pytest.mark.parametrize("cell", [c["name"] for c in SPEC["workloads"]])
def test_cell_resolves_to_files_that_parse(cell):
    got = run.resolve_cell(cell)
    config = got["config"]
    assert config["name"] == got["cell"]["config"]
    assert config["chips"] == got["cell"]["chips"]
    listed = {c["name"]: c for c in SPEC["configs"]}[config["name"]]
    assert listed["source"] == config["source"]
    assert listed["reduced"] == config["reduced"]
    assert listed["file"] == f"benchmark/configs/{config['name']}.json"
    for fn in ("build", "check", "requests"):
        assert callable(getattr(got["builder"], fn))
    names = {m["name"] for m in got["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert got["per_layer"]
    for spec in got["per_layer"]:
        listed = {m["name"]: m for m in SPEC["per_layer"]}[spec["name"]]
        assert (spec["layer"], spec["unit"], spec["moves"], spec["better"]) \
            == (listed["layer"], listed["unit"], listed["moves"],
                listed["better"])
        assert spec["moves"] in names


def test_names_and_units_are_of_the_allowed_characters():
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    names = ([m["name"] for m in metrics]
             + [c["name"] for c in SPEC["configs"]]
             + [k for c in SPEC["configs"] for k in c["reduced"]]
             + [x for w in SPEC["workloads"]
                for x in (w["name"], w["config"], w["traffic"])])
    for name in names:
        assert NAME.fullmatch(name), name
    for m in metrics:
        assert UNIT.fullmatch(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    assert len({m["name"] for m in metrics}) == len(metrics)
    for entry in SPEC["configs"] + SPEC["workloads"]:
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
        assert len(entry.get("source", "x")) <= 200


def test_trace_reducer_on_hand_made_intervals():
    ms = 1_000_000
    device = [("while.1", 0, 10 * ms),          # holds the two fusions
              ("fusion.a", 1 * ms, 4 * ms),
              ("fusion.b", 6 * ms, 2 * ms),
              ("copy.2", 30 * ms, 5 * ms),      # after a 20 ms gap
              ("fusion.a", 35 * ms, 5 * ms)]    # abuts: no gap
    host = [("main: json.loads", 11 * ms, 15 * ms),
            ("main: short", 27 * ms, 1 * ms)]
    out = reduce_trace.reduce_events([device], host)
    assert out["busy_s"] == pytest.approx(0.020)
    assert out["span_s"] == pytest.approx(0.040)
    assert 1 - out["busy_s"] / out["span_s"] == pytest.approx(0.5)
    assert out["device_ops"][0] == ["fusion.a", pytest.approx(0.009)]
    assert dict(map(tuple, out["device_ops"]))["while.1"] \
        == pytest.approx(0.004)
    assert sum(s for _, s in out["device_ops"]) == pytest.approx(0.020)
    assert out["idle_gaps"] == [["main: json.loads", pytest.approx(0.020)]]
    # two chips: busy time is the mean over them, operations the sum
    both = reduce_trace.reduce_events([device, device[3:]], host)
    assert both["busy_s"] == pytest.approx(0.015)
    assert reduce_trace.reduce_events([[]]) == {}
    assert reduce_trace.op_kind(
        "%broadcast_multiply_fusion.304 = (s32[2,112]{1,0}) fusion(...)") \
        == "broadcast_multiply_fusion"
    assert reduce_trace.op_kind("%cond.22.clone.2 = (s32[2]) cond") == "cond"


def test_percentile_and_snapshot_arithmetic():
    values = [0.4, 0.1, 0.3, 0.2, 0.5]
    assert run.percentile(values, 0.5) == pytest.approx(0.3)
    assert run.percentile(values, 0.9) == pytest.approx(0.46)
    assert run.percentile([7.0], 0.9) == 7.0
    records = [(0.1, True, 100), (0.3, True, 100), (0.2, False, 100)]
    assert run.end_to_end("sigs_per_s", records, 2.0, 9.0) == 100.0
    assert run.end_to_end("latency_p50_ms", records, 2.0, 9.0) \
        == pytest.approx(200.0)
    assert run.end_to_end("setup_s", records, 2.0, 9.0) == 9.0

    def timer(count, mean_s):
        return {"type": "timer", "count": count, "mean_s": mean_s}

    before = {"serving/x/wait_time": timer(2, 0.5),
              "serving/x/dispatch_latency": timer(2, 1.0),
              "serving/x/requests": {"type": "counter", "count": 2},
              "serving/x/request_rows": {"type": "counter", "count": 20},
              "serving/x/dispatches": {"type": "counter", "count": 2}}
    after = {"serving/x/wait_time": timer(12, 0.1),       # +0.2 s / 10
             "serving/x/dispatch_latency": timer(7, 0.5),   # +1.5 s / 5
             "serving/x/requests": {"type": "counter", "count": 12},
             "serving/x/request_rows": {"type": "counter", "count": 120},
             "serving/x/dispatches": {"type": "counter", "count": 7}}

    def metric(name, **kw):
        return run.layer_metric(run.read_json("layer_metrics", name + ".json"),
                                "x", before, after, **kw)

    assert metric("queue_wait_ms") == pytest.approx(20.0)
    assert metric("rows_per_dispatch") == pytest.approx(20.0)
    # 500 ms at the client, (0.2 + 1.5) s over 10 requests in the tier
    assert metric("rpc_codec_ms", client_mean_ms=500.0) \
        == pytest.approx(330.0)
    # nothing to read: the metric is left out, not reported as 0
    assert metric("marshal_ms") is None
    assert metric("device_busy_ms", trace=None) is None
    trace = {"busy_s": 1.5, "counts": {"serving/x/dispatches": 5}}
    assert metric("device_busy_ms", trace=trace) == pytest.approx(300.0)


def test_rehearsal_run_ends_in_the_contracts_line():
    cell = SPEC["workloads"][-1]["name"]   # the one with the most requests
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", str(2**31 + 11), "--seconds", "2", "--trace", "0",
         "--rehearsal"],
        cwd=REPO, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-2000:]
    lines = done.stdout.strip().splitlines()
    assert all(line.startswith("[rehearsal cpu] ") for line in lines[:-1])
    last = json.loads(lines[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(last)
    assert last["rehearsal"] is True and last["correct"] is True
    assert last["failed"] == 0 and last["attempted"] >= 1
    assert {"platform", "kind", "count", "memory_peak_bytes"} \
        <= set(last["device"])
    # no CPU number under a metric's name
    assert set(last["metrics"]) == {
        "rehearsal." + m["name"] for m in SPEC["end_to_end"]
        if cell in m.get("workloads", [cell])}
