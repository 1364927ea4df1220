"""Device introspection plane (gethsharding_tpu/devscope/).

Coverage map (the ISSUE 14 checklist):
- memory poller gauges on fake devices, totals, watermark ring bounds;
- buffer census: owner attribution, unattributed remainder, and the
  LRU-vs-census drift cross-check (agreeing books are silent, lying
  books count);
- the seeded recompile-storm detector: fires exactly once per episode,
  silent on steady state, re-arms after the window drains;
- compile-span wall-time booking + the sigbackend _note_shape feed;
- profiler start/stop idempotence, bounded+pruned session directory,
  sampler collapsed stacks + unique-stack budget + overhead guard;
- the RPC surface (shard_profileStart/Stop/Stacks/devscopeStatus), the
  StatusServer /profile routes + /status devscope section, Prometheus
  rows;
- near-OOM -> flight-recorder bundle containing the census;
- perfwatch ledger records carrying peak-HBM/compile-time fields;
- the log<->trace correlation filter.
"""

from __future__ import annotations

import json
import logging
import os
import time
import urllib.request

import pytest

from gethsharding_tpu import devscope, metrics, tracing
from gethsharding_tpu.devscope import (
    COMPILES,
    CompileWatch,
    MemoryPoller,
    PROFILER,
    ProfileManager,
    SamplingProfiler,
)
from gethsharding_tpu.devscope import memory as devscope_memory


class FakeDevice:
    def __init__(self, device_id=0, in_use=100 << 20, peak=150 << 20,
                 limit=16 << 30, platform="tpu"):
        self.id = device_id
        self.platform = platform
        self.in_use = in_use
        self.peak = peak
        self.limit = limit

    def memory_stats(self):
        return {"bytes_in_use": self.in_use,
                "peak_bytes_in_use": self.peak,
                "bytes_limit": self.limit}


class FakeBuffer:
    def __init__(self, nbytes, shape=(8, 8), dtype="int32"):
        self.nbytes = nbytes
        self.shape = shape
        self.dtype = dtype


@pytest.fixture(autouse=True)
def _clean_owners_and_profiler():
    yield
    for name in devscope.owners():
        if name.startswith("test_"):
            devscope.unregister_owner(name)
    PROFILER.stop()


# == memory poller =========================================================


def test_poller_gauges_on_fake_devices():
    devs = [FakeDevice(0, in_use=10, peak=20, limit=100),
            FakeDevice(3, in_use=30, peak=40, limit=200)]
    poller = MemoryPoller(interval_s=60, devices_fn=lambda: devs,
                          buffers_fn=lambda: [])
    readings = poller.poll_once()
    assert readings == {
        "d0": {"bytes_in_use": 10, "peak_bytes": 20, "limit": 100,
               "platform": "tpu"},
        "d3": {"bytes_in_use": 30, "peak_bytes": 40, "limit": 200,
               "platform": "tpu"},
    }
    reg = metrics.DEFAULT_REGISTRY
    assert reg.gauge("devscope/mem/d0/bytes_in_use").value == 10
    assert reg.gauge("devscope/mem/d3/peak_bytes").value == 40
    assert reg.gauge("devscope/mem/d3/limit").value == 200
    # process totals span the devices
    assert metrics.gauge("devscope/mem/bytes_in_use").value == 40
    assert metrics.gauge("devscope/mem/limit").value == 300
    assert poller.peak_bytes() == 40


def test_poller_devices_without_stats_are_skipped():
    class Bare:
        pass

    poller = MemoryPoller(interval_s=60, devices_fn=lambda: [Bare()],
                          buffers_fn=lambda: [])
    assert poller.poll_once() == {}


def test_poller_thread_start_stop_idempotent():
    poller = MemoryPoller(interval_s=0.01, devices_fn=lambda: [FakeDevice()],
                          buffers_fn=lambda: [])
    poller.start()
    poller.start()  # second start is a no-op, not a second thread
    deadline = time.monotonic() + 5.0
    while poller.polls == 0 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert poller.polls > 0
    poller.stop()
    assert not poller.running
    poller.stop()  # idempotent


def test_watermark_ring_records_and_bounds(monkeypatch):
    monkeypatch.setenv("GETHSHARDING_DEVSCOPE_WATERMARKS", "4")
    dev = FakeDevice(0, in_use=0, peak=0, limit=1000)
    poller = MemoryPoller(interval_s=60, devices_fn=lambda: [dev],
                          buffers_fn=lambda: [])
    for peak in range(1, 10):
        dev.peak = peak
        poller.poll_once()
    marks = poller.watermarks()
    assert len(marks) == 4  # bounded
    assert [m["bytes"] for m in marks] == [6, 7, 8, 9]  # newest kept
    dev.peak = 9  # no new high-watermark -> no new entry
    poller.poll_once()
    assert len(poller.watermarks()) == 4
    assert poller.watermarks()[-1]["bytes"] == 9


# == census + drift ========================================================


def test_census_attributes_owned_and_unattributed():
    owned = [FakeBuffer(100), FakeBuffer(50)]
    stray = [FakeBuffer(7, shape=(7,), dtype="uint8")]
    devscope.register_owner("test_plane",
                            claimed_fn=lambda: 150,
                            buffers_fn=lambda: list(owned))
    poller = MemoryPoller(interval_s=60, devices_fn=lambda: [],
                          buffers_fn=lambda: owned + stray)
    census = poller.census()
    assert census["live_buffers"] == 3
    assert census["live_bytes"] == 157
    assert census["by_owner"]["test_plane"] == {"buffers": 2, "bytes": 150}
    assert census["by_owner"]["unattributed"] == {"buffers": 1, "bytes": 7}
    assert census["owners"]["test_plane"]["drifted"] is False
    assert census["top_groups"][0]["bytes"] == 150  # (int32, (8,8)) group


def test_census_drift_detection():
    """An owner whose claimed bytes disagree with what the census sees
    beyond the tolerance is a drift count; honest books are silent."""
    bufs = [FakeBuffer(10 << 20)]
    claimed = {"v": 10 << 20}
    devscope.register_owner("test_lru",
                            claimed_fn=lambda: claimed["v"],
                            buffers_fn=lambda: list(bufs))
    poller = MemoryPoller(interval_s=60, devices_fn=lambda: [],
                          buffers_fn=lambda: list(bufs))
    before = metrics.counter("devscope/mem/drift").value
    census = poller.census()
    assert census["owners"]["test_lru"]["drifted"] is False
    assert metrics.counter("devscope/mem/drift").value == before
    claimed["v"] = 30 << 20  # the books now lie by 20 MiB
    census = poller.census()
    assert census["owners"]["test_lru"]["drifted"] is True
    assert census["owners"]["test_lru"]["drift_bytes"] == 20 << 20
    assert metrics.counter("devscope/mem/drift").value == before + 1
    # PERSISTENT drift is one episode, not one count per census
    poller.census()
    assert metrics.counter("devscope/mem/drift").value == before + 1
    claimed["v"] = 10 << 20  # books heal -> latch re-arms
    poller.census()
    claimed["v"] = 30 << 20  # a NEW drift episode counts again
    poller.census()
    assert metrics.counter("devscope/mem/drift").value == before + 2


def test_drift_detected_by_plain_polling():
    """The census (and its drift cross-check) runs on EVERY poll, not
    only when a near-OOM fires — a leak with a bookkeeper must not
    need the device to already be on fire to show up."""
    bufs = [FakeBuffer(10 << 20)]
    claimed = {"v": 10 << 20}
    devscope.register_owner("test_poll_drift",
                            claimed_fn=lambda: claimed["v"],
                            buffers_fn=lambda: list(bufs))
    reg = metrics.Registry()
    poller = MemoryPoller(interval_s=60,
                          devices_fn=lambda: [FakeDevice()],
                          buffers_fn=lambda: list(bufs), registry=reg)
    poller.poll_once()
    assert poller.describe()["last_census"] is not None
    assert poller.describe()["drift_events"] == 0
    claimed["v"] = 40 << 20  # the books start lying
    poller.poll_once()
    assert poller.describe()["drift_events"] == 1


def test_isolated_registry_poller_never_touches_process_rows():
    reg = metrics.Registry()
    poller = MemoryPoller(
        interval_s=60,
        devices_fn=lambda: [FakeDevice(in_use=990, peak=995, limit=1000)],
        buffers_fn=lambda: [], registry=reg)
    polls_before = metrics.counter("devscope/mem/polls").value
    oom_before = metrics.counter("devscope/mem/near_oom").value
    in_use_before = metrics.gauge("devscope/mem/bytes_in_use").value
    poller.poll_once()  # fake device at 99% utilization
    assert metrics.counter("devscope/mem/polls").value == polls_before
    assert metrics.counter("devscope/mem/near_oom").value == oom_before
    assert metrics.gauge(
        "devscope/mem/bytes_in_use").value == in_use_before
    assert reg.counter("devscope/mem/polls").value == 1
    assert reg.counter("devscope/mem/near_oom").value == 1


def test_observe_peaks_has_no_side_effects():
    """The ledger stamp's read path: peaks/watermarks advance, but no
    gauges publish, no census runs and no near-OOM dump can fire from
    inside the ledger writer."""
    reg = metrics.Registry()
    poller = MemoryPoller(
        interval_s=60,
        devices_fn=lambda: [FakeDevice(in_use=990, peak=995, limit=1000)],
        buffers_fn=lambda: [], registry=reg)
    assert poller.observe_peaks() == 995
    assert poller.watermarks()[-1]["bytes"] == 995
    assert reg.counter("devscope/mem/polls").value == 0
    assert reg.counter("devscope/mem/near_oom").value == 0  # 99% util!
    assert poller.describe()["last_census"] is None


def test_census_keyless_owner_never_drifts():
    """An owner with no buffers_fn cannot be censused — claimed bytes
    are reported but never cross-checked (no false drift)."""
    devscope.register_owner("test_blind", claimed_fn=lambda: 123)
    poller = MemoryPoller(interval_s=60, devices_fn=lambda: [],
                          buffers_fn=lambda: [FakeBuffer(1)])
    census = poller.census()
    assert census["owners"]["test_blind"]["claimed_bytes"] == 123
    assert census["owners"]["test_blind"]["drifted"] is False


def test_resident_lru_registers_as_owner():
    """The jax backend's resident pk-plane LRU registers at
    construction (no dispatch needed: the claimed/buffers callbacks
    read the cache state directly)."""
    pytest.importorskip("jax")
    from gethsharding_tpu.sigbackend import JaxSigBackend

    backend = object.__new__(JaxSigBackend)
    import threading
    from collections import OrderedDict

    backend._pk_dev_lock = threading.Lock()
    backend._pk_dev_cache = OrderedDict()
    backend._pk_dev_bytes = 0
    backend._pk_batch_memo = None
    backend._pk_zero_rows = {}
    devscope.register_owner("pk_plane_lru",
                            claimed_fn=backend._resident_claimed_bytes,
                            buffers_fn=backend._resident_buffers)
    assert "pk_plane_lru" in devscope.owners()
    assert backend._resident_claimed_bytes() == 0
    assert backend._resident_buffers() == []
    entry = (FakeBuffer(10), FakeBuffer(10), FakeBuffer(2), 22)
    backend._pk_dev_cache["k"] = entry
    backend._pk_dev_bytes = 22
    assert backend._resident_claimed_bytes() == 22
    assert len(backend._resident_buffers()) == 3
    devscope.unregister_owner("pk_plane_lru")  # stub backend, not the
    # process singleton — later censuses must not read it


# == near-OOM -> flight-recorder bundle ====================================


def test_near_oom_dumps_census_bundle(tmp_path, monkeypatch):
    monkeypatch.setenv("GETHSHARDING_PERFWATCH_DIR", str(tmp_path / "bb"))
    monkeypatch.setenv("GETHSHARDING_PERFWATCH_DUMP_S", "0")
    from gethsharding_tpu.perfwatch.recorder import RECORDER

    bufs = [FakeBuffer(48 << 20), FakeBuffer(4 << 20)]
    devscope.register_owner("test_oom_plane",
                            claimed_fn=lambda: sum(b.nbytes for b in bufs),
                            buffers_fn=lambda: list(bufs))
    dev = FakeDevice(0, in_use=950, peak=960, limit=1000)
    poller = MemoryPoller(interval_s=60, devices_fn=lambda: [dev],
                          buffers_fn=lambda: list(bufs))
    before = metrics.counter("devscope/mem/near_oom").value
    # an earlier test's dump may still be writing: a trigger that finds
    # one pending is suppressed, and this test would wait for nothing
    RECORDER.flush(timeout=30.0)
    poller.poll_once()
    assert metrics.counter("devscope/mem/near_oom").value == before + 1
    deadline = time.monotonic() + 10.0
    bundle = None
    while time.monotonic() < deadline:
        RECORDER.flush()
        base = str(tmp_path / "bb")
        dirs = sorted(os.listdir(base)) if os.path.isdir(base) else []
        if dirs:
            bundle = os.path.join(base, dirs[-1])
            break
        time.sleep(0.05)
    assert bundle is not None, "near-OOM produced no bundle"
    events = json.load(open(os.path.join(bundle, "events.json")))
    oom = [e for e in events if e["kind"] == "hbm_near_oom"]
    assert oom, sorted({e["kind"] for e in events})
    detail = oom[-1]["detail"]
    assert detail["device"] == "d0"
    assert detail["utilization"] == 0.95
    census = detail["census"]
    assert census["by_owner"]["test_oom_plane"]["bytes"] == 52 << 20
    assert detail["watermarks"], "watermark tail missing from the event"
    # the episode latch: same utilization again must not re-fire
    poller.poll_once()
    assert metrics.counter("devscope/mem/near_oom").value == before + 1
    # hysteresis: clear well below the line, then cross again -> refires
    dev.in_use = 100
    poller.poll_once()
    dev.in_use = 950
    poller.poll_once()
    assert metrics.counter("devscope/mem/near_oom").value == before + 2


# == compile watch =========================================================


def _seeded_watch(threshold=4, window=30.0):
    clock = {"t": 1000.0}
    watch = CompileWatch(storm_shapes=threshold, storm_window_s=window,
                         clock=lambda: clock["t"])
    return watch, clock


def test_storm_detector_fires_once_and_rearms():
    watch, clock = _seeded_watch(threshold=4, window=30.0)
    from gethsharding_tpu.perfwatch.recorder import RECORDER

    def storm_events():
        return sum(1 for e in RECORDER.events()
                   if e["kind"] == "recompile_storm")

    before = storm_events()
    # steady state: repeats of known shapes never storm
    for _ in range(100):
        watch.saw("op", (128,), False)
    assert watch.storms == 0
    # 3 fresh shapes spread over hours: under threshold, silent
    for i in range(3):
        clock["t"] += 3600
        watch.saw("op", (i,), True)
    assert watch.storms == 0 and storm_events() == before
    # the storm: threshold fresh shapes inside one window, fires ONCE
    for i in range(10, 20):
        clock["t"] += 0.1
        watch.saw("op", (i,), True)
    assert watch.storms == 1
    assert storm_events() == before + 1
    assert watch.storm_active() is True
    assert metrics.gauge("devscope/compile/storm").value == 1
    # the window drains -> verdict clears, gauge resets
    clock["t"] += 31.0
    assert watch.storm_active() is False
    assert metrics.gauge("devscope/compile/storm").value == 0
    # a SECOND storm is a new episode: fires exactly once again
    for i in range(30, 40):
        clock["t"] += 0.1
        watch.saw("op", (i,), True)
    assert watch.storms == 2
    assert storm_events() == before + 2


def test_compile_span_books_wall_per_shape():
    watch, _ = _seeded_watch()
    with watch.compile_span("ecrecover", (64,), True):
        time.sleep(0.02)
    with watch.compile_span("ecrecover", (64,), False):
        time.sleep(0.05)  # a HIT is never booked as compile time
    desc = watch.describe()
    assert desc["compiles"] == 1
    assert 0.015 < desc["total_s"] < 0.05
    top = desc["top_shapes"][0]
    assert top["op"] == "ecrecover" and top["shape"] == [64]
    assert top["compiles"] == 1


@pytest.mark.parametrize("source", ["store", "traced"])
def test_compile_span_books_where_the_executable_came_from(source):
    """A fresh shape's body is handed the booking: a load from the
    executable store writes `source="store"` and its seconds into it,
    a trace writes nothing and is booked as `traced`."""
    watch, _ = _seeded_watch()
    with watch.compile_span("bls_committee", (112, 144, "i32"),
                            True) as booking:
        assert booking == {"source": "traced"}
        if source == "store":
            booking.update(source="store", load_s=0.25)
    with watch.compile_span("bls_committee", (112, 144, "i32"),
                            False) as booking:
        assert booking is None  # a hit books nothing
    desc = watch.describe()
    assert desc["compiles"] == 1
    assert desc["from_store"] == (1 if source == "store" else 0)
    top = desc["top_shapes"][0]
    assert top["source"] == source
    assert top.get("load_s") == (0.25 if source == "store" else None)


def test_a_launch_through_the_store_is_booked_under_its_source(tmp_path):
    """The backend's own bracket: a miss is `traced`, the same shape in
    a second backend is `store` with its load time inside the wall, and
    `jax/compile_cache/misses` still counts a shape once a backend."""
    import jax
    import jax.numpy as jnp

    from gethsharding_tpu.sigbackend import JaxSigBackend
    from gethsharding_tpu.sigbackend.execstore import ExecutableStore

    op = "test_store_booking_op"
    fn = jax.jit(lambda x: (x + 1, x * 2))
    args = (jnp.arange(6, dtype=jnp.int32),)
    miss, hit = (metrics.counter(f"jax/compile_cache/{name}")
                 for name in ("misses", "hits"))
    seen = []
    watch, _ = _seeded_watch()  # not the process's: its window is shared
    for _ in range(2):
        backend = JaxSigBackend(
            exec_store=ExecutableStore(str(tmp_path / "exe")))
        backend._compiles = watch
        n_miss, n_hit = miss.value, hit.value
        for _ in range(3):
            backend._counted_launch(op, (6,), fn, *args)
        assert (miss.value - n_miss, hit.value - n_hit) == (1, 2)
        slot = watch.describe()["top_shapes"][0]
        seen.append((slot["source"], slot["compiles"]))
    assert seen == [("traced", 1), ("store", 2)]
    assert 0 < slot["load_s"] <= slot["wall_s"]
    assert watch.describe()["from_store"] == 1


def test_note_shape_feeds_process_compile_watch():
    """The sigbackend per-shape cache feeds the process COMPILES
    singleton (storm window + per-shape ledger) on fresh shapes."""
    import threading

    from gethsharding_tpu.sigbackend import JaxSigBackend

    backend = object.__new__(JaxSigBackend)
    backend._shape_seen = set()
    backend._shape_lock = threading.Lock()
    backend._m_shape_hit = metrics.counter("jax/compile_cache/hits")
    backend._m_shape_miss = metrics.counter("jax/compile_cache/misses")
    backend._compiles = COMPILES
    key = ("test_note_shape_op", time.monotonic())
    before = COMPILES.describe()["unique_shapes"]
    assert backend._note_shape(*key) is True
    assert backend._note_shape(*key) is False  # the hit path early-outs
    assert COMPILES.describe()["unique_shapes"] == before + 1


def test_ledger_records_carry_devscope_fields(tmp_path):
    from gethsharding_tpu.perfwatch import Ledger, record_bench

    COMPILES.note_compile("test_ledger_op", (1,), 0.5)
    ledger = Ledger(str(tmp_path / "ledger.jsonl"))
    rec = record_bench(metric="test_metric_per_sec", value=10.0,
                       extra={}, ledger=ledger)
    # peak-HBM is a GATED metric (memory creep flags like latency)...
    assert "peak_hbm_bytes" in rec["metrics"]
    from gethsharding_tpu.perfwatch import direction_for

    assert direction_for("peak_hbm_bytes") == "lower"
    # ...while the process-cumulative compile attribution rides in
    # extra: gating it would flag invocation composition, not growth
    assert rec["extra"]["compile_total_s"] > 0
    assert rec["extra"]["compile_count"] >= 1
    assert "compile_total_s" not in rec["metrics"]
    # replayed captures measured ANOTHER process's device: stamping
    # this host's peak (0) into their group would poison the baseline
    replay = record_bench(metric="test_metric_per_sec", value=10.0,
                          extra={"platform": "tpu"}, source="replay",
                          ledger=ledger)
    assert "peak_hbm_bytes" not in replay["metrics"]
    assert "compile_total_s" not in replay["extra"]


# == profiler ==============================================================


def test_profiler_start_stop_idempotent(tmp_path, monkeypatch):
    monkeypatch.setenv("GETHSHARDING_DEVSCOPE_PROFILE_DIR",
                       str(tmp_path / "prof"))
    manager = ProfileManager()
    out = manager.start(mode="sampler", hz=500)
    assert out["started"] is True
    again = manager.start(mode="sampler")
    assert again.get("already_running") is True
    assert manager.sessions == 1  # the double start opened ONE session
    stopped = manager.stop()
    assert stopped["stopped"] is True
    assert manager.stop() == {"stopped": False, "reason": "not running"}


def test_jax_only_stop_preserves_last_sampler_stacks(tmp_path,
                                                     monkeypatch):
    """A mode=jax session has no sampler; its stop() must not wipe the
    previous sampler session's downloadable stacks."""
    monkeypatch.setenv("GETHSHARDING_DEVSCOPE_PROFILE_DIR",
                       str(tmp_path / "prof"))
    monkeypatch.setattr(ProfileManager, "_start_jax_trace",
                        lambda self: (str(tmp_path / "prof" / "s1"), None))
    monkeypatch.setattr(ProfileManager, "_stop_jax_trace",
                        staticmethod(lambda: True))
    manager = ProfileManager()
    manager.start(mode="sampler", hz=500)
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline and not manager.stacks():
        time.sleep(0.01)
    manager.stop()
    stacks = manager.stacks()
    assert stacks
    manager.start(mode="jax")
    manager.stop()
    assert manager.stacks() == stacks  # the artifact survived


def test_storm_gauge_clears_via_booted_poller_heartbeat():
    """The booted poller's tick drains the storm verdict, so a
    prom-only scraper sees devscope/compile/storm reset without anyone
    hitting /status."""
    inst = devscope.boot(start_poller=False)
    try:
        inst._devices_fn = lambda: []
        inst._buffers_fn = lambda: []
        gauge = metrics.gauge("devscope/compile/storm")
        gauge.set(1)  # a storm latched earlier, window since drained
        inst.poll_once()
        assert gauge.value == 0
    finally:
        devscope.shutdown()


def test_profiler_bad_mode_rejected():
    with pytest.raises(ValueError):
        ProfileManager().start(mode="flamegraph")


def test_profiler_build_failure_does_not_wedge(monkeypatch):
    """A throw mid-build (bad sample-rate env) must roll the session
    claim back — the next corrected start works, no phantom
    already_running."""
    manager = ProfileManager()
    monkeypatch.setenv("GETHSHARDING_DEVSCOPE_SAMPLE_HZ", "abc")
    with pytest.raises(ValueError):
        manager.start(mode="sampler")  # hz=None reads the broken env
    assert manager.describe()["active"] is False
    monkeypatch.delenv("GETHSHARDING_DEVSCOPE_SAMPLE_HZ")
    out = manager.start(mode="sampler", hz=500)
    assert out["started"] is True
    manager.stop()


def test_default_devices_require_resolved_backend(monkeypatch):
    """The poller must never be the thing that initializes a jax
    backend (on a TPU host the first init takes every chip): with jax
    imported but no device record resolved by the program
    (ops/device.py), device/buffer enumeration reads as no devices —
    and once the program resolved its devices, the poller sees them."""
    from gethsharding_tpu.ops import device

    monkeypatch.setattr(device, "_resolved", None)
    assert devscope_memory._default_devices() == []
    assert devscope_memory._default_buffers() == []
    monkeypatch.undo()
    device.device_record()
    assert len(devscope_memory._default_devices()) == 8  # conftest's mesh


def test_profiler_session_dir_bounded(tmp_path, monkeypatch):
    base = tmp_path / "prof"
    base.mkdir()
    monkeypatch.setenv("GETHSHARDING_DEVSCOPE_PROFILE_DIR", str(base))
    monkeypatch.setenv("GETHSHARDING_DEVSCOPE_PROFILE_KEEP", "3")
    for i in range(7):
        (base / f"2026010{i}_000000_1").mkdir()
    ProfileManager._prune(str(base))
    kept = sorted(os.listdir(base))
    assert len(kept) == 3
    assert kept == ["20260104_000000_1", "20260105_000000_1",
                    "20260106_000000_1"]  # newest survive


def test_profiler_stacks_survive_stop(tmp_path, monkeypatch):
    monkeypatch.setenv("GETHSHARDING_DEVSCOPE_PROFILE_DIR",
                       str(tmp_path / "prof"))
    manager = ProfileManager()
    manager.start(mode="sampler", hz=500)
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline and not manager.stacks():
        time.sleep(0.01)
    manager.stop()
    assert manager.stacks(), "last session's stacks must stay downloadable"


def _with_sibling_thread(fn):
    """Run `fn` while one parked sibling thread exists — the sampler
    excludes its OWN thread, so a single-threaded test process would
    have nothing to sample."""
    import threading

    release = threading.Event()
    thread = threading.Thread(target=release.wait, daemon=True,
                              name="devscope-test-sleeper")
    thread.start()
    try:
        return fn()
    finally:
        release.set()
        thread.join(timeout=5.0)


def test_sampler_collapsed_stacks_and_budget():
    sampler = SamplingProfiler(hz=1000, max_stacks=1)

    def drive():
        for _ in range(20):
            sampler.sample_once()

    _with_sibling_thread(drive)
    text = sampler.collapsed()
    assert text, "a sibling thread's stack must be visible"
    head = text.splitlines()[0]
    stack, _, count = head.rpartition(" ")
    assert int(count) > 0 and stack  # "a;b;c N" shape
    desc = sampler.describe()
    assert desc["unique_stacks"] <= 1  # the budget held
    assert desc["samples"] == 20


def test_sampler_overhead_guard():
    """The duty cycle the sampler charges at its configured rate stays
    under the 2%-of-a-request budget (the bench closed loop asserts
    the same bound against a real serving request)."""
    sampler = SamplingProfiler()  # default hz
    sampler.sample_once()  # warm
    n = 200
    t0 = time.perf_counter()
    for _ in range(n):
        sampler.sample_once()
    tick_s = (time.perf_counter() - t0) / n
    duty_pct = 100.0 * sampler.hz * tick_s
    assert duty_pct < 2.0, (
        f"sampler duty cycle {duty_pct:.3f}% at {sampler.hz}Hz "
        f"({tick_s * 1e6:.1f}us/tick)")


def test_sampler_chrome_export_merges(tmp_path):
    sampler = SamplingProfiler(hz=100)
    _with_sibling_thread(lambda: [sampler.sample_once()
                                  for _ in range(5)])
    path = tmp_path / "samples.json"
    events = sampler.write_chrome_trace(str(path))
    assert events > 0
    payload = json.loads(path.read_text())
    assert "clock_offset_us" in payload["otherData"]  # the merge anchor
    assert payload["traceEvents"][0]["ph"] == "M"  # process_name lane
    # the span export and the sampler export fold into one view
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "trace_merge", os.path.join(os.path.dirname(__file__), "..",
                                    "scripts", "trace_merge.py"))
    trace_merge = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(trace_merge)
    merged = trace_merge.merge_traces([payload])
    assert sum(1 for e in merged["traceEvents"] if e["ph"] == "X") == events


# == surfaces: RPC, StatusServer, Prometheus ===============================


def test_rpc_profile_surface(tmp_path, monkeypatch):
    monkeypatch.setenv("GETHSHARDING_DEVSCOPE_PROFILE_DIR",
                       str(tmp_path / "prof"))
    from gethsharding_tpu.params import Config
    from gethsharding_tpu.rpc.client import RPCClient
    from gethsharding_tpu.rpc.server import RPCServer
    from gethsharding_tpu.smc.chain import SimulatedMainchain

    server = RPCServer(SimulatedMainchain(config=Config()))
    server.start()
    client = RPCClient(*server.address)
    try:
        out = client.call("shard_profileStart", "sampler", 500)
        assert out["started"] is True
        assert client.call("shard_profileStart")["already_running"] is True
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            client.call("shard_blockNumber")
            if client.call("shard_profileStacks"):
                break
        assert client.call("shard_profileStop")["stopped"] is True
        stacks = client.call("shard_profileStacks")
        assert stacks and "gethsharding" in stacks
        status = client.call("shard_devscopeStatus")
        assert status["profiler"]["sessions"] >= 1
        assert "compile" in status and "memory" in status
    finally:
        client.close()
        server.stop()


def test_status_server_devscope_surfaces(tmp_path, monkeypatch):
    monkeypatch.setenv("GETHSHARDING_DEVSCOPE_PROFILE_DIR",
                       str(tmp_path / "prof"))
    from gethsharding_tpu.node.backend import ShardNode
    from gethsharding_tpu.node.http_status import StatusServer

    node = ShardNode(actor="observer", txpool_interval=None, http_port=0)
    node.start()
    try:
        port = node.service(StatusServer).port

        def get(path):
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}{path}",
                        timeout=10) as resp:
                    return resp.read().decode()
            except urllib.error.HTTPError as exc:
                # degraded-but-answering routes return 500 + a JSON body
                return exc.read().decode()

        status = json.loads(get("/status"))
        assert "devscope" in status
        assert "compile" in status["devscope"]
        assert "profiler" in status["devscope"]
        out = json.loads(get("/profile?action=start&mode=sampler&hz=500"))
        assert out["started"] is True
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            get("/healthz")
            if get("/profile/stacks"):
                break
        out = json.loads(get("/profile?action=stop"))
        assert out["stopped"] is True
        assert get("/profile/stacks"), "stacks download empty"
        desc = json.loads(get("/profile"))
        assert desc["active"] is False and desc["sessions"] >= 1
        bad = json.loads(get("/profile?action=explode"))
        assert "error" in bad
        prom = get("/metrics?format=prom")
        for row in ("devscope_profiler_sessions",
                    "devscope_compile_count",
                    "devscope_mem_polls"):
            assert row in prom, f"{row} missing from prom exposition"
    finally:
        node.stop()


def test_devscope_status_shape():
    status = devscope.devscope_status()
    assert set(status) == {"memory", "compile", "profiler"}
    assert "storm_active" in status["compile"]
    assert "sessions" in status["profiler"]


def test_boot_disabled_by_env(monkeypatch):
    monkeypatch.setenv("GETHSHARDING_DEVSCOPE", "0")
    assert devscope.boot() is None


def test_boot_idempotent_and_shutdown():
    first = devscope.boot(start_poller=False)
    second = devscope.boot(start_poller=False)
    assert first is second
    assert devscope.poller() is first
    devscope.shutdown()
    assert devscope.poller() is None


# == log <-> trace correlation =============================================


def test_log_filter_stamps_trace_ids(caplog):
    logger = logging.getLogger("sharding.node.test_devscope")
    handler = logging.Handler()
    records = []
    handler.emit = records.append
    handler.addFilter(tracing.LOG_FILTER)
    logger.addHandler(handler)
    was_enabled = tracing.TRACER.enabled
    try:
        tracing.enable()
        with tracing.span("devscope/test") as span:
            logger.warning("inside a span")
        logger.warning("outside any span")
    finally:
        tracing.TRACER.enabled = was_enabled
        logger.removeHandler(handler)
    inside, outside = records
    assert inside.trace_id == str(span.trace_id)
    assert inside.span_id == str(span.span_id)
    assert outside.trace_id == "-"
    assert outside.span_id == "-"
    # the CLI format string renders against the stamped record
    fmt = logging.Formatter("%(levelname)s [%(trace_id)s] %(message)s")
    assert f"[{span.trace_id}]" in fmt.format(inside)
    assert "[-]" in fmt.format(outside)


def test_install_log_correlation_idempotent():
    root = logging.getLogger()
    handler = logging.NullHandler()
    root.addHandler(handler)
    try:
        tracing.install_log_correlation()
        tracing.install_log_correlation()
        assert handler.filters.count(tracing.LOG_FILTER) == 1
    finally:
        root.removeHandler(handler)
