#!/usr/bin/env python3
"""run.py: one run of one cell of BENCHMARK.json, measured from the client.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

client (this process) -> `chain_server --sigbackend jax` at module defaults
(`child.py`, the one process that holds the chip) -> serving queue ->
sigbackend dispatch -> device. This process never imports JAX: a parent
that touched JAX would hold the chip its child needs.

A cell is resolved to files found by name (README.md): its configuration
`configs/<config>.json`, its traffic `traffic/<traffic>.json`, the request
builder `builders/<builder>.py` the configuration names, and one
`layer_metrics/<name>.json` per per-layer metric the cell reports.

Set-up (counted as `setup_s`): start the child, make or load the data set
from `--seed`, hold it against the scalar reference, read the child's
banner, send the traffic's warm-up requests (the first compiles or reads
the compile cache). Then a closed loop for `--seconds`, every verdict
compared with the construction's. `--trace 0` prints the end-to-end
metrics; `--trace 1` the per-layer metrics: the counters over the same
window, then a device trace of a few requests after it.

It prints no result line, and exits non-zero, when the child is not on a
TPU, when a shape compiled inside the window, or when the program's timer
self-check fired. `--rehearsal` is the only way to run on a CPU: tiny
shapes, `[rehearsal cpu]` on every line, and every metric's name prefixed
with `rehearsal.`, so that no CPU number stands under a metric's name.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pickle  # noqa: E402
import select  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
STRIPPED_PREFIXES = ("GETHSHARDING_TPU_", "GETHSHARDING_MESH_")
BOOT_TIMEOUT_S = 300.0
RPC_TIMEOUT_S = 900.0    # a cold first request compiles for minutes
# The traced interval: at least one request and 0.15 s. It is this short
# because the TPU's trace holds one event per fused operation, 650,000 a
# period request and 285,000 a one-row request, and `stop_trace` takes
# 128 us an event (250 s for three period requests, PERF.md PR 24).
TRACE_REQUESTS, TRACE_MIN_S = 1, 0.15

_TAG = ""


def say(msg: str) -> None:
    print(f"{_TAG}{msg}", flush=True)


# == a cell, resolved to its files ==========================================


def read_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as src:
        return json.load(src)


def load_builder(name: str):
    """`builders/<name>.py`, imported as `builders.<name>` so that a
    signing pool's workers find it under the same name."""
    if HERE not in sys.path:
        sys.path.insert(0, HERE)
    return importlib.import_module(f"builders.{name}")


def resolve_cell(name: str, rehearsal: bool = False) -> dict:
    """The cell `name` of BENCHMARK.json with everything it names."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as src:
        bench = json.load(src)
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no cell {name!r} in BENCHMARK.json "
                         f"(cells: {sorted(cells)})")
    cell = cells[name]
    config = read_json("configs", cell["config"] + ".json")
    if rehearsal:
        config.update(config.get("rehearsal", {}))

    def reported(metric):
        return name in metric.get("workloads", cells)

    return {
        "cell": cell, "config": config,
        "traffic": read_json("traffic", cell["traffic"] + ".json"),
        "builder": load_builder(config["builder"]),
        "end_to_end": [m for m in bench["end_to_end"] if reported(m)],
        "per_layer": [read_json("layer_metrics", m["name"] + ".json")
                      for m in bench["per_layer"] if reported(m)],
    }


def dataset_of(config: dict, builder, seed: int, rehearsal: bool) -> dict:
    """The data set of (configuration, seed): loaded from `.data/` where
    an earlier run left it, else made, checked against the scalar
    reference and written there. The file's name carries the shapes, so a
    rehearsal's file is never loaded by a real run."""
    shape = "r{rows}x{committee}q{quorum}".format(**config)
    path = os.path.join(HERE, ".data",
                        f"{config['name']}-{shape}-seed{seed}.pkl")
    t0 = time.monotonic()
    if os.path.exists(path):
        with open(path, "rb") as src:
            data = pickle.load(src)
        say(f"set-up: data set loaded from {os.path.relpath(path, REPO)} "
            f"in {time.monotonic() - t0:.1f} s")
        return data
    workers = 1 if rehearsal else max(1, min(12, os.cpu_count() or 1))
    data = builder.build(config, seed, workers=workers)
    data["checked_rows"] = builder.check(config, data, seed)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path + ".part", "wb") as out:
        pickle.dump(data, out)
    os.replace(path + ".part", path)
    say(f"set-up: data set made from seed {seed} with {workers} signing "
        f"worker(s), scalar reference agreed on rows "
        f"{data['checked_rows']}: {time.monotonic() - t0:.1f} s (host "
        f"scalar crypto); kept as {os.path.relpath(path, REPO)}")
    return data


# == the child (copied from chip_smoke.py's Children) =======================


class Child:
    """The one chip-holding child of a run."""

    def __init__(self, cmd, env):
        self._buf = b""
        self.proc = subprocess.Popen(cmd, env=env, cwd=REPO,
                                     stdout=subprocess.PIPE, bufsize=0)

    def read_json_line(self, timeout_s: float) -> dict:
        """The next JSON object line of the child's stdout (its banner);
        raises if the child exits or the deadline passes first."""
        deadline = time.monotonic() + timeout_s
        while True:
            while b"\n" in self._buf:
                line, _, self._buf = self._buf.partition(b"\n")
                if line.lstrip().startswith(b"{"):
                    return json.loads(line)
            left = deadline - time.monotonic()
            if left <= 0:
                raise TimeoutError(
                    f"no line from the child within {timeout_s:.0f} s")
            ready, _, _ = select.select([self.proc.stdout], [], [], left)
            if ready:
                chunk = os.read(self.proc.stdout.fileno(), 65536)
                if not chunk:
                    raise RuntimeError(f"child exited (code "
                                       f"{self.proc.wait()}) before its line")
                self._buf += chunk

    def stop(self) -> int:
        """SIGINT (the server's clean shutdown), then SIGTERM, then wait:
        a SIGKILLed chip holder can leave the chip locked, so SIGKILL is
        the last resort and fails the run."""
        for sig, grace in ((signal.SIGINT, 60), (signal.SIGTERM, 30)):
            if self.proc.poll() is not None:
                break
            self.proc.send_signal(sig)
            try:
                self.proc.wait(timeout=grace)
            except subprocess.TimeoutExpired:
                continue
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
            raise RuntimeError("child ignored SIGINT and SIGTERM")
        return self.proc.returncode


def child_env(rehearsal: bool):
    """The child's environment and the knobs stripped from it: the cells
    run the module defaults, which is what a user's chain_server gets."""
    env = dict(os.environ)
    stripped = sorted(k for k in env if k.startswith(STRIPPED_PREFIXES))
    for key in stripped:
        del env[key]
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    if rehearsal:
        env["JAX_PLATFORMS"] = "cpu"
    return env, stripped


def check_device(device, chips: int, rehearsal: bool) -> None:
    if not device:
        raise RuntimeError("the child reported no device record")
    say(f"device: platform={device['platform']} "
        f"device_kind={device['device_kind']!r} count={device['count']} (as "
        f"the child reports) compile_cache_dir={device['compile_cache_dir']}")
    want = "cpu" if rehearsal else "tpu"
    if device["platform"] != want:
        raise RuntimeError(
            f"the child runs on platform {device['platform']!r}, not "
            f"{want!r}" + ("" if rehearsal
                           else " (off the chip only --rehearsal runs)"))
    if not rehearsal and device["count"] < chips:
        raise RuntimeError(f"{device['count']} chip(s), the cell asks {chips}")


def ask(child: Child, control: str, request: str, answer: str,
        timeout_s: float = 120.0) -> dict:
    """Create `request` in the control directory and wait for the child's
    `answer` (child.py's side thread)."""
    open(os.path.join(control, request), "w").close()
    deadline = time.monotonic() + timeout_s
    path, error = (os.path.join(control, n) for n in (answer, "error.json"))
    while not os.path.exists(path):
        if os.path.exists(error):
            with open(error) as src:
                raise RuntimeError(f"child, on {request}: {json.load(src)}")
        if child.proc.poll() is not None or time.monotonic() > deadline:
            raise RuntimeError(f"no {answer} from the child")
        time.sleep(0.02)
    with open(path) as src:
        body = json.load(src)
    os.remove(path)
    return body


# == arithmetic (checked in tests/test_harness.py) ==========================


def percentile(values, q: float) -> float:
    """The q-quantile (0..1) by linear interpolation between the sorted
    values, as numpy's default gives it."""
    ordered = sorted(values)
    pos = q * (len(ordered) - 1)
    low = int(pos)
    high = min(low + 1, len(ordered) - 1)
    return ordered[low] + (ordered[high] - ordered[low]) * (pos - low)


def count_of(snapshot: dict, name: str) -> float:
    row = snapshot.get(name) or {}
    return row.get("count", row.get("value", 0))


def total_s(snapshot: dict, name: str) -> float:
    """A registry timer's total seconds (it publishes count and mean)."""
    row = snapshot.get(name) or {}
    return row.get("mean_s", 0.0) * row.get("count", 0)


def layer_metric(spec: dict, op: str, before: dict, after: dict,
                 client_mean_ms=None, trace=None):
    """One per-layer metric from its file's `source`, over the window's
    two `shard_metrics` snapshots (or the reduced trace). None where
    there is nothing to read: the metric is then left out of the line."""
    src = spec["source"]
    names = [n.format(op=op) for n in src.get("names", [])]

    def delta(name, of=count_of):
        return of(after, name) - of(before, name)

    if src["kind"] == "trace":
        if not trace or not trace.get("busy_s"):
            return None
        per = trace["counts"].get(src["per"].format(op=op))
        return 1e3 * trace["busy_s"] / per if per else None
    if src["reduce"] == "delta":
        return delta(names[0])
    if src["reduce"] == "ratio":
        return (delta(names[0]) / delta(names[1])
                if delta(names[1]) else None)
    if src["reduce"] == "timer_mean_ms":
        n = delta(names[0])
        return 1e3 * delta(names[0], total_s) / n if n else None
    if src["reduce"] == "client_residual_ms":
        n = delta(src["per"].format(op=op))
        if not n or client_mean_ms is None:
            return None
        return client_mean_ms - 1e3 * sum(delta(name, total_s)
                                          for name in names) / n
    raise ValueError(f"{spec['name']}: unknown reduction {src['reduce']!r}")


def end_to_end(name: str, records: list, window_s: float, setup_s: float):
    """One end-to-end metric from the window's (latency_s, ok, n_sigs)
    records. `latency_p<q>_ms` is the q-th percentile of all requests."""
    if name == "setup_s":
        return setup_s
    if name == "sigs_per_s":
        return sum(n for _, ok, n in records if ok) / window_s
    if name.startswith("latency_p") and name.endswith("_ms"):
        q = int(name[len("latency_p"):-len("_ms")]) / 100.0
        return 1e3 * percentile([lat for lat, _, _ in records], q)
    raise ValueError(f"no arithmetic for end-to-end metric {name!r}")


# == the run ================================================================


class Loop:
    """One closed-loop client: the next request goes when the last has
    answered, and every verdict is compared with the construction's."""

    def __init__(self, backend, requests):
        self.backend, self.requests = backend, requests
        self.records = []   # (latency_s, ok, n_sigs)

    def send(self) -> float:
        method, args, want, n_sigs = next(self.requests)
        t0 = time.monotonic()
        try:
            ok = list(getattr(self.backend, method)(*args)) == list(want)
        except Exception as exc:  # noqa: BLE001 - an error is a failure
            say(f"request failed: {exc!r}")
            ok = False
        latency = time.monotonic() - t0
        self.records.append((latency, ok, n_sigs))
        return latency

    def send_for(self, seconds: float, at_least: int = 0) -> None:
        deadline, sent = time.monotonic() + seconds, 0
        while time.monotonic() < deadline or sent < at_least:
            self.send()
            sent += 1


def traced_interval(loop: Loop, child: Child, control: str, backend,
                    op: str) -> dict:
    """Trace the device over a few requests with none in flight at either
    edge: the reduced trace, the interval on the client's clock and the
    counts of the registry over it."""
    ask(child, control, "trace.start", "trace.started")
    before = backend.metrics()
    t0 = time.monotonic()
    loop.send_for(TRACE_MIN_S, at_least=TRACE_REQUESTS)
    window_s = time.monotonic() - t0
    after = backend.metrics()
    trace = ask(child, control, "trace.stop", "trace.json", timeout_s=200.0)
    trace["window_s"] = window_s
    trace["counts"] = {name: count_of(after, name) - count_of(before, name)
                       for name in after if name.startswith(f"serving/{op}/")}
    return trace


def main(argv=None) -> int:
    global _TAG
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearsal", action="store_true",
                        help="tiny shapes on JAX_PLATFORMS=cpu; the only "
                             "way to run off the chip")
    args = parser.parse_args(argv)
    _TAG = "[rehearsal cpu] " if args.rehearsal else ""
    signal.signal(signal.SIGTERM, signal.default_int_handler)

    resolved = resolve_cell(args.workload, args.rehearsal)
    cell, config, traffic = (resolved[k] for k in
                             ("cell", "config", "traffic"))
    if (traffic["loop"], traffic["clients"]) != ("closed", 1):
        raise SystemExit(f"traffic {cell['traffic']!r}: this generator "
                         f"drives one closed-loop client")
    op = config["op_label"]

    # built here, before the child starts, so no two processes race the
    # first-use build (gethsharding_tpu/native.py)
    sys.path.insert(0, REPO)
    from gethsharding_tpu import native

    say("native library: " + ("loaded" if native.available()
                              else "unavailable, pure-Python fallback"))
    from gethsharding_tpu.fleet.router import RpcReplicaBackend

    env, stripped = child_env(args.rehearsal)
    say(f"child environment: stripped {stripped or 'nothing'}; the cell "
        f"runs the module defaults")
    control = tempfile.mkdtemp(prefix="benchmark-control-")
    child = Child([sys.executable, os.path.join(HERE, "child.py"), control]
                  + config["server_args"], env)
    backend = None
    try:
        # the data set is made while the child boots
        data = dataset_of(config, resolved["builder"], args.seed,
                          args.rehearsal)
        banner = child.read_json_line(BOOT_TIMEOUT_S)
        boot_s = time.monotonic() - T_START
        device = banner.get("device")
        check_device(device, cell["chips"], args.rehearsal)
        backend = RpcReplicaBackend.dial(banner["host"], banner["port"],
                                         timeout=RPC_TIMEOUT_S)
        loop = Loop(backend, resolved["builder"].requests(config, data,
                                                          traffic))
        warm = [loop.send() for _ in range(traffic["warmup_requests"])]
        if not all(ok for _, ok, _ in loop.records):
            raise RuntimeError("a warm-up request failed or was wrong")
        setup_s = time.monotonic() - T_START
        say(f"set-up {setup_s:.1f} s: child and data ready at {boot_s:.1f} s,"
            f" warm-up requests " + ", ".join(f"{w:.3f} s" for w in warm)
            + " (the first traces, lowers and compiles or reads the cache)")

        # -- the window ------------------------------------------------
        loop.records.clear()
        before = backend.metrics()
        t0 = time.monotonic()
        loop.send_for(args.seconds)
        window_s = time.monotonic() - t0
        after = backend.metrics()
        records = list(loop.records)
        # the device trace comes after the window, so that the counters
        # and the client's latencies are read with the profiler off
        trace = (traced_interval(loop, child, control, backend, op)
                 if args.trace else None)
        memory = ask(child, control, "mem.req", "mem.json")
    finally:
        if backend is not None:
            backend.close()
        rc = child.stop()
        shutil.rmtree(control, ignore_errors=True)
    # -2: the SIGINT that stops a server whose handler did not yet run
    if rc not in (0, -signal.SIGINT):
        raise RuntimeError(f"chain_server exited {rc}")

    refuse(before, after)
    print(json.dumps(result_line(
        resolved, args, device, memory, records, loop.records, window_s,
        setup_s, before, after, trace)), flush=True)
    return 0


def refuse(before: dict, after: dict) -> None:
    """Raise, so that no result line is printed, on what makes a run's
    numbers worthless: a compile inside the window, a device timing the
    program itself distrusts, a parent that touched JAX."""
    compiled = (count_of(after, "jax/compile_cache/misses")
                - count_of(before, "jax/compile_cache/misses"))
    suspects = count_of(after, "perfwatch/timer_suspect")
    say(f"shapes compiled inside the window: {compiled}; "
        f"perfwatch/timer_suspect: {suspects}")
    if compiled:
        raise RuntimeError(f"{compiled} shape(s) compiled inside the window")
    if suspects:
        raise RuntimeError(f"perfwatch/timer_suspect = {suspects}: the "
                           f"program's device timings are not to be trusted")
    if "jax" in sys.modules:
        raise RuntimeError("the parent imported JAX: it would have held "
                           "the chip its child needs")


def result_line(resolved, args, device, memory, records, all_records,
                window_s, setup_s, before, after, trace) -> dict:
    """The contract's last line. `records` are the window's requests,
    `all_records` those and the traced ones: a wrong verdict fails the
    run wherever it came."""
    failed = sum(1 for _, ok, _ in all_records if not ok)
    latencies = [lat for lat, _, _ in records]
    say(f"window {window_s:.3f} s: {len(records)} requests, {failed} failed,"
        f" latency min {min(latencies):.4f} median "
        f"{percentile(latencies, 0.5):.4f} max {max(latencies):.4f} s "
        f"(request {latencies.index(max(latencies))})")
    out_device = {"platform": device["platform"],
                  "kind": device["device_kind"], "count": device["count"],
                  "memory_peak_bytes": memory["memory_peak_bytes"]}
    metrics, breakdown = {}, None
    if args.trace:
        if trace.get("busy_s"):
            out_device.update(busy_s=trace["busy_s"],
                              window_s=trace["window_s"])
            breakdown = {"device_ops": trace["device_ops"],
                         "idle_gaps": trace["idle_gaps"]}
            say(f"trace: {trace['window_s']:.3f} s on the client's clock, "
                f"device busy {trace['busy_s']:.3f} s; first to last device "
                f"operation {trace['span_s']:.3f} s; {trace['n_gaps']} gaps; "
                f"stop {trace['stop_s']:.1f} s, reduce "
                f"{trace['reduce_s']:.1f} s")
        client_mean_ms = 1e3 * sum(latencies) / len(latencies)
        for spec in resolved["per_layer"]:
            value = layer_metric(spec, resolved["config"]["op_label"],
                                 before, after, client_mean_ms, trace)
            if value is not None:
                metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
    else:
        for m in resolved["end_to_end"]:
            metrics[m["name"]] = {
                "value": end_to_end(m["name"], records, window_s, setup_s),
                "unit": m["unit"]}
    if args.rehearsal:
        metrics = {f"rehearsal.{k}": v for k, v in metrics.items()}
    result = {"correct": failed == 0, "attempted": len(all_records),
              "failed": failed, "metrics": metrics, "device": out_device}
    if breakdown:
        result["breakdown"] = breakdown
    if args.rehearsal:
        result["rehearsal"] = True
    return result


if __name__ == "__main__":
    sys.exit(main())
