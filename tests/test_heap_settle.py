"""The heap settles after a first compile (ISSUE 27): a serving process
moves what survives a collection into the collector's permanent
generation, so that later full collections walk only what was allocated
since. Held here: the compile watch's one listener fires once per fresh
(op, shape) and never on a hit; a settle leaves the collector as it was
and leaks nothing; only the two composition roots install it, once; the
counters read what happened; and the benchmark's per-layer metric reads
series a real `chain_server` registers.

Every test ends in `gc.unfreeze()`: this process holds thousands of
objects that other tests expect `gc.collect()` to free.
"""

import gc
import json
import os
import subprocess
import sys
import weakref

import pytest

from gethsharding_tpu import devscope, metrics, tracing
from gethsharding_tpu.crypto import bn256 as bls
from gethsharding_tpu.devscope import CompileWatch
from gethsharding_tpu.tracing.stage import GC_CLOCK, GcClock, _PauseCounter

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SERIES = (GcClock.COUNTER, GcClock.FULL_COUNTER, GcClock.FULL_COLLECTIONS,
          GcClock.SETTLES, GcClock.FROZEN)
# the three that the collector's callback writes
CALLBACK_COUNTERS = SERIES[:3]


@pytest.fixture(autouse=True)
def nothing_stays_frozen(monkeypatch):
    """The process's watch gets its listener back after the test, and
    whatever the test froze is handed back to the collector."""
    monkeypatch.setattr(devscope.COMPILES, "after_compile", None)
    try:
        yield
    finally:
        gc.unfreeze()


@pytest.fixture
def watch():
    """A watch of the test's own, wired to the process's clock as a
    composition root wires `devscope.COMPILES`."""
    watch = CompileWatch(registry=metrics.Registry())
    GC_CLOCK.install(settle_after=watch)
    return watch


def _read(name):
    return metrics.DEFAULT_REGISTRY.get(name).value


def _committees():
    """Two rows x two votes, one row with a vote on another message."""
    keys = [bls.bls_keygen(b"heap-settle-%d" % i) for i in range(2)]
    msgs = [b"settle-header-0", b"settle-header-1"]
    sig_rows = [[bls.bls_sign(m, sk) for sk, _ in keys] for m in msgs]
    sig_rows[1][0] = bls.bls_sign(b"another header", keys[0][0])
    pk_rows = [[pk for _, pk in keys] for _ in msgs]
    return msgs, sig_rows, pk_rows, [True, False]


# == the listener ===========================================================


@pytest.mark.parametrize("sightings, calls", [
    ([("a", (1,), True)], [("a", (1,))]),
    ([("a", (1,), True), ("a", (1,), False), ("a", (1,), False)],
     [("a", (1,))]),
    ([("a", (1,), True), ("a", (2,), True), ("b", (1,), True),
      ("a", (2,), False)],
     [("a", (1,)), ("a", (2,)), ("b", (1,))]),
    ([("a", (1,), False)], []),
])
def test_listener_fires_once_per_fresh_shape_and_never_on_a_hit(
        sightings, calls):
    watch = CompileWatch(registry=metrics.Registry())
    heard = []
    watch.after_compile = lambda op, shape: heard.append((op, shape))
    for op, shape, fresh in sightings:
        with watch.compile_span(op, shape, fresh):
            pass
    assert heard == calls
    assert watch.compiles == len(calls)


def test_listener_runs_outside_the_watchs_lock():
    watch = CompileWatch(registry=metrics.Registry())
    held = []
    watch.after_compile = lambda op, shape: held.append(
        watch._lock.locked())
    with watch.compile_span("a", (1,), True):
        pass
    assert held == [False]


def test_a_compile_that_raised_is_booked_and_not_heard():
    watch = CompileWatch(registry=metrics.Registry())
    heard = []
    watch.after_compile = lambda op, shape: heard.append((op, shape))
    with pytest.raises(ZeroDivisionError):
        with watch.compile_span("a", (1,), True):
            1 / 0
    assert watch.compiles == 1
    assert heard == []


def test_what_the_listener_raises_is_logged_and_not_passed_on(caplog):
    watch = CompileWatch(registry=metrics.Registry())

    def deaf(op, shape):
        raise RuntimeError("no settle today")

    watch.after_compile = deaf
    with caplog.at_level("ERROR", logger="devscope.compile"):
        with watch.compile_span("a", (1,), True):
            verdict = "reached"
    assert verdict == "reached"
    assert watch.compiles == 1
    assert "no settle today" in caplog.text


# == the settle =============================================================


@pytest.mark.parametrize("kept", ["enabled", "threshold", "callbacks"])
def test_a_settle_freezes_the_heap_and_leaves_the_collector_as_it_was(
        watch, kept):
    read = {"enabled": gc.isenabled, "threshold": gc.get_threshold,
            "callbacks": lambda: list(gc.callbacks)}[kept]
    before, frozen = read(), gc.get_freeze_count()
    ballast = [[i] for i in range(5000)]    # alive across the settle
    with watch.compile_span("op", (kept,), True):
        pass
    assert gc.get_freeze_count() >= frozen + len(ballast)
    assert read() == before
    assert gc.isenabled()


def test_a_cycle_made_after_the_settle_is_freed_by_the_next_collection(
        watch):
    class Node:
        pass

    with watch.compile_span("op", (1,), True):
        pass
    assert gc.get_freeze_count() > 0
    node = Node()
    node.me = node
    alive = weakref.ref(node)
    del node
    assert alive() is not None      # only the collector can free it
    gc.collect()
    assert alive() is None


def test_a_frozen_object_is_still_freed_with_its_last_reference(watch):
    class Leaf:
        pass

    leaf = Leaf()
    alive = weakref.ref(leaf)
    with watch.compile_span("op", (1,), True):
        pass
    del leaf
    assert alive() is None


def test_a_settle_is_a_span_under_the_span_that_compiled(watch):
    tracing.enable()
    tracing.TRACER.clear()
    try:
        with tracing.span("serving/op/dispatch") as dispatch:
            with watch.compile_span("op", (1, 4), True):
                pass
        spans = tracing.TRACER.recent_spans()
    finally:
        tracing.disable()
        tracing.TRACER.clear()
    settle, = [s for s in spans if s["name"] == "runtime/gc/settle"]
    assert (settle["trace"], settle["parent"]) \
        == (dispatch.trace_id, dispatch.span_id)
    assert settle["tags"]["op"] == "op"
    assert settle["tags"]["shape"] == [1, 4]
    assert settle["tags"]["frozen_objects"] > 0
    # its own full collection lies inside it
    inner = [s for s in spans
             if s["name"] == "runtime/gc" and s["parent"] == settle["span"]]
    assert inner and all(settle["start"] <= s["start"]
                         and s["end"] <= settle["end"] for s in inner)


# == the counters ===========================================================


def test_the_counters_read_what_happened(watch):
    before = {name: _read(name) for name in SERIES}
    gc.collect(0)
    gc.collect(1)
    assert _read(GcClock.FULL_COLLECTIONS) == before[GcClock.FULL_COLLECTIONS]
    assert _read(GcClock.FULL_COUNTER) == before[GcClock.FULL_COUNTER]
    gc.collect()
    assert _read(GcClock.FULL_COLLECTIONS) \
        == before[GcClock.FULL_COLLECTIONS] + 1
    for n in (1, 2):
        with watch.compile_span("op", (n,), True):
            pass
        with watch.compile_span("op", (n,), False):
            pass
    assert _read(GcClock.SETTLES) == before[GcClock.SETTLES] + 2
    assert _read(GcClock.SETTLES) - before[GcClock.SETTLES] \
        == watch.compiles
    # each settle is a full collection of its own
    assert _read(GcClock.FULL_COLLECTIONS) \
        == before[GcClock.FULL_COLLECTIONS] + 3
    assert 0 < _read(GcClock.FROZEN) <= gc.get_freeze_count() + 64
    full = _read(GcClock.FULL_COUNTER) - before[GcClock.FULL_COUNTER]
    every = _read(GcClock.COUNTER) - before[GcClock.COUNTER]
    assert 0 < full <= every
    assert _read(GcClock.FULL_COUNTER) <= _read(GcClock.COUNTER)


@pytest.mark.parametrize("name", CALLBACK_COUNTERS)
def test_the_collectors_callback_takes_no_lock(watch, name):
    counter = metrics.DEFAULT_REGISTRY.get(name)
    assert isinstance(counter, _PauseCounter)
    ballast = [[i] for i in range(20000)]   # a collection of some length
    # a snapshot may run the collector while it holds the counter's lock
    with counter._lock:
        before = counter.value
        gc.collect()
        assert counter.value > before
    del ballast
    assert metrics.DEFAULT_REGISTRY.snapshot()[name]["count"] \
        == counter.value


# == who installs it ========================================================


def test_a_library_dispatch_freezes_nothing(monkeypatch):
    """`JaxSigBackend` alone, as a test builds it: the
    compile is booked with the process's watch, which has no listener."""
    from gethsharding_tpu.sigbackend import JaxSigBackend

    GC_CLOCK.install()
    *args, want = _committees()
    freezes, freeze = [], gc.freeze
    monkeypatch.setattr(gc, "freeze", lambda: (freezes.append(1), freeze()))
    settles, compiles = _read(GcClock.SETTLES), devscope.COMPILES.compiles
    assert JaxSigBackend().bls_verify_committees(*args) == want
    assert devscope.COMPILES.compiles == compiles + 1
    assert devscope.COMPILES.after_compile is None
    assert freezes == []
    assert _read(GcClock.SETTLES) == settles
    # the same compile in a process that a composition root has wired
    GC_CLOCK.install(settle_after=devscope.COMPILES)
    assert JaxSigBackend().bls_verify_committees(*args) == want
    assert freezes == [1]
    assert _read(GcClock.SETTLES) == settles + 1


def _boot_chain_server(tmp_path):
    from gethsharding_tpu.rpc import chain_server

    return chain_server.main(["--runtime", "0.05", "--port", "0"])


def _boot_node_cli(tmp_path):
    from gethsharding_tpu.node.cli import run_cli

    return run_cli(["sharding", "--actor", "observer", "--runtime", "0.05",
                    "--blocktime", "0.01", "--datadir", str(tmp_path)])


@pytest.mark.parametrize("boot", [_boot_chain_server, _boot_node_cli])
def test_a_composition_root_installs_the_settle_exactly_once(
        boot, tmp_path, capsys):
    callbacks = list(gc.callbacks)
    for _ in range(2):
        assert boot(tmp_path) == 0
        assert devscope.COMPILES.after_compile == GC_CLOCK.settle
    assert gc.callbacks.count(GC_CLOCK._on_gc) == 1
    assert [c for c in gc.callbacks if c != GC_CLOCK._on_gc] \
        == [c for c in callbacks if c != GC_CLOCK._on_gc]
    frozen = gc.get_freeze_count()
    # what the root installed settles after a compile of the process's
    with devscope.COMPILES.compile_span("op", (1,), True):
        pass
    assert gc.get_freeze_count() > frozen


# == a real chain_server, and the benchmark's metric over it ================


@pytest.fixture(scope="module")
def served_process():
    """`python -m gethsharding_tpu.rpc.chain_server --sigbackend jax` on
    the CPU: `shard_metrics` before any request, after the first (which
    compiles) and after a second (which does not)."""
    from gethsharding_tpu.fleet.router import RpcReplicaBackend

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    proc = subprocess.Popen(
        [sys.executable, "-m", "gethsharding_tpu.rpc.chain_server",
         "--sigbackend", "jax", "--port", "0", "--runtime", "900"],
        stdout=subprocess.PIPE, env=env, cwd=REPO, text=True)
    try:
        banner = json.loads(proc.stdout.readline())
        client = RpcReplicaBackend.dial(banner["host"], banner["port"],
                                        timeout=600.0)
        *args, want = _committees()
        snaps = [client.metrics()]
        for _ in range(2):
            assert client.bls_verify_committees(*args) == want
            snaps.append(client.metrics())
        client.close()
        yield snaps
    finally:
        proc.terminate()
        proc.wait(timeout=30)


def _count(snapshot, name):
    row = snapshot[name]
    return row.get("count", row.get("value"))


def test_a_serving_process_settles_once_per_compile(served_process):
    boot, first, second = served_process
    for name in SERIES:
        assert _count(boot, name) is not None, name  # there from the boot
    assert _count(boot, GcClock.SETTLES) == 0
    assert _count(boot, GcClock.FROZEN) == 0
    compiles = _count(first, "devscope/compile/count")
    assert compiles >= 1
    assert _count(first, GcClock.SETTLES) == compiles
    assert _count(first, GcClock.FROZEN) > 100_000     # JAX's own heap
    assert _count(first, GcClock.FULL_COLLECTIONS) >= compiles
    # a hit settles nothing
    assert _count(second, "devscope/compile/count") == compiles
    assert _count(second, GcClock.SETTLES) == compiles
    assert _count(second, GcClock.FROZEN) == _count(first, GcClock.FROZEN)
    for snap in served_process:
        assert _count(snap, GcClock.FULL_COUNTER) \
            <= _count(snap, GcClock.COUNTER)


def test_the_per_layer_metric_reads_series_the_server_registers(
        served_process):
    with open(os.path.join(REPO, "benchmark", "layer_metrics",
                           "gc_full_pause_us.json")) as src:
        spec = json.load(src)
    with open(os.path.join(REPO, "BENCHMARK.json")) as src:
        bench = json.load(src)
    listed = {m["name"]: m for m in bench["per_layer"]}
    bench["end_to_end"] = {m["name"]: m for m in bench["end_to_end"]}
    assert spec["name"] == "gc_full_pause_us"
    entry = listed[spec["name"]]
    assert (entry["layer"], entry["unit"], entry["moves"], entry["better"]) \
        == (spec["layer"], spec["unit"], spec["moves"], spec["better"])
    assert (spec["layer"], spec["unit"], spec["better"]) \
        == ("runtime", "us", "lower")
    # it moves an end-to-end metric of every cell that reports it
    for cell in entry["workloads"]:
        assert cell in bench["end_to_end"][spec["moves"]].get(
            "workloads", [cell])
    assert spec["source"]["kind"] == "registry"
    assert spec["source"]["reduce"] == "ratio"
    names = spec["source"]["names"]
    assert names == [GcClock.FULL_COUNTER, "rpc/verifyCommittees/server_time"]
    _, first, second = served_process
    for name in names:
        assert name in second, name
    bench = os.path.join(REPO, "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    import run

    # the first request compiled and settled: a full collection in it
    value = run.layer_metric(spec, "bls_committee", served_process[0], first)
    assert value > 0
    assert run.layer_metric(spec, "bls_committee", first, second) >= 0
