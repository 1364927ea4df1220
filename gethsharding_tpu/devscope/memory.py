"""HBM memory accounting: the device's real memory state, observed.

The resident pk-plane LRU accounts its own bytes (`jax/pk_device_cache/
bytes`) — but that is the cache's OPINION of what it holds, not the
device's. Nothing in the stack reads `device.memory_stats()`, so HBM
creep from a leaked staging buffer, a forgotten DAS proof plane, or a
future mesh path's per-device shards would be invisible until the
allocator raises. This module is the always-on answer:

- **Poller.** A daemon thread samples every device's
  ``memory_stats()`` each ``GETHSHARDING_DEVSCOPE_POLL_S`` seconds and
  publishes per-device ``devscope/mem/d<id>/{bytes_in_use,peak_bytes,
  limit}`` gauges plus process totals — scrapeable rows, not a debug
  call an operator has to know about.
- **Attribution.** Components that hold device memory register as
  OWNERS (`register_owner`): a claimed-bytes callback plus an optional
  live-buffer callback. The census walks the live buffers
  (`jax.live_arrays()`), attributes each to the owner whose buffer
  list contains it, and sums the rest as ``unattributed``. The
  resident pk-plane LRU's census bytes are cross-checked against its
  OWN accounting; drift beyond ``GETHSHARDING_DEVSCOPE_DRIFT_PCT``
  (plus a fixed slack) increments ``devscope/mem/drift`` — a cache
  whose books disagree with the device is a leak with a bookkeeper.
- **High-watermark ring + near-OOM trigger.** Every poll that raises a
  device's observed peak lands in a bounded ring; utilization above
  ``GETHSHARDING_DEVSCOPE_OOM_PCT`` fires the perfwatch flight
  recorder's fatal-trigger path ONCE per episode, with the buffer
  census and the watermark tail in the event detail — so a near-OOM
  post-mortem bundle answers "what was on the device" without anyone
  attached.

Everything degrades to a no-op on a host with no accelerator: the
poller reads devices through an injectable ``devices_fn`` that never
initializes a backend (``sys.modules.get("jax")`` — the
env_fingerprint rule), and the tests drive every path with fake
device/buffer objects.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional

from gethsharding_tpu import metrics

# registered at import so the Prometheus exposition carries the rows
# from the first scrape, not the first poll. The poller itself resolves
# every row through ITS registry (an isolated-registry poller — tests,
# bench drills over fake devices — must not write the process rows);
# for the default-registry poller these registrations are the same
# instances.
metrics.counter("devscope/mem/polls")
metrics.counter("devscope/mem/drift")
metrics.counter("devscope/mem/near_oom")
metrics.gauge("devscope/mem/bytes_in_use")
metrics.gauge("devscope/mem/peak_bytes")
metrics.gauge("devscope/mem/limit")

DEFAULT_POLL_S = 5.0
DEFAULT_OOM_PCT = 0.92
DEFAULT_DRIFT_PCT = 0.05
DRIFT_SLACK_BYTES = 1 << 16  # absolute slack under the relative band
DEFAULT_WATERMARKS = 128
_CENSUS_TOP = 16  # (dtype, shape) groups reported per census


def _poll_interval_s() -> float:
    return float(os.environ.get("GETHSHARDING_DEVSCOPE_POLL_S",
                                str(DEFAULT_POLL_S)))


def _oom_pct() -> float:
    return float(os.environ.get("GETHSHARDING_DEVSCOPE_OOM_PCT",
                                str(DEFAULT_OOM_PCT)))


def _drift_pct() -> float:
    return float(os.environ.get("GETHSHARDING_DEVSCOPE_DRIFT_PCT",
                                str(DEFAULT_DRIFT_PCT)))


def _watermark_ring() -> int:
    return int(os.environ.get("GETHSHARDING_DEVSCOPE_WATERMARKS",
                              str(DEFAULT_WATERMARKS)))


def _jax_if_resolved():
    """The jax module IF this process already resolved its devices
    through `ops.device.device_record` (every accelerated entry point
    does, at backend construction), else None. The poller must observe
    the runtime someone else booted, never be the thing that boots it:
    `jax.devices()` on a merely-imported jax INITIALIZES the platform
    client, and on a TPU host that takes every chip — a control-plane
    process serving the scalar backend must not claim the device just
    to report on it."""
    device = sys.modules.get("gethsharding_tpu.ops.device")
    if device is None or device.resolved_record() is None:
        return None
    return sys.modules.get("jax")


def _default_devices() -> list:
    """The live devices of an ALREADY-resolved backend (see
    `_jax_if_resolved` — polling must never trigger the first backend
    init)."""
    jax = _jax_if_resolved()
    return [] if jax is None else list(jax.devices())


def _default_buffers() -> list:
    """Every live device array this process holds (`jax.live_arrays`).
    Same resolved-backend gate."""
    jax = _jax_if_resolved()
    return [] if jax is None else list(jax.live_arrays())


class _Owner:
    """One registered device-memory owner: a claimed-bytes callback
    (the component's OWN accounting) and an optional live-buffer
    callback (what it actually holds, for census attribution)."""

    __slots__ = ("name", "claimed_fn", "buffers_fn")

    def __init__(self, name: str, claimed_fn: Callable[[], int],
                 buffers_fn: Optional[Callable[[], list]] = None):
        self.name = name
        self.claimed_fn = claimed_fn
        self.buffers_fn = buffers_fn


# the process owner registry (module-level like metrics.DEFAULT_REGISTRY:
# owners register once at construction, the poller reads)
_OWNERS: Dict[str, _Owner] = {}
_OWNERS_LOCK = threading.Lock()


def register_owner(name: str, claimed_fn: Callable[[], int],
                   buffers_fn: Optional[Callable[[], list]] = None) -> None:
    """Register (or replace) a device-memory owner. `claimed_fn`
    returns the bytes the component believes it holds on device;
    `buffers_fn` (optional) returns the live device arrays backing that
    claim, so the census can attribute them and cross-check the two."""
    with _OWNERS_LOCK:
        _OWNERS[name] = _Owner(name, claimed_fn, buffers_fn)


def unregister_owner(name: str) -> None:
    with _OWNERS_LOCK:
        _OWNERS.pop(name, None)


def owners() -> List[str]:
    with _OWNERS_LOCK:
        return sorted(_OWNERS)


def _safe_int(value) -> int:
    try:
        return int(value)
    except (TypeError, ValueError):
        return 0


class MemoryPoller:
    """Background HBM gauge publisher + buffer census + near-OOM trap.

    `poll_once()` is the whole unit of work (the thread just repeats
    it), so tests and the bench closed loop drive every path —
    including the recorder trigger — synchronously with fake devices.
    """

    def __init__(self, interval_s: Optional[float] = None,
                 devices_fn: Callable[[], list] = _default_devices,
                 buffers_fn: Callable[[], list] = _default_buffers,
                 registry: metrics.Registry = metrics.DEFAULT_REGISTRY,
                 on_poll: Optional[Callable[[], None]] = None):
        self.interval_s = (_poll_interval_s() if interval_s is None
                          else float(interval_s))
        self.registry = registry
        self._devices_fn = devices_fn
        self._buffers_fn = buffers_fn
        # optional per-poll hook: boot() hangs the compile watch's
        # storm-verdict drain here, making the booted poller the
        # devscope heartbeat (a prom-only scraper then sees the storm
        # gauge clear without anyone hitting /status)
        self._on_poll = on_poll
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._peaks: Dict[str, int] = {}       # device label -> peak seen
        self._watermarks: deque = deque(maxlen=max(1, _watermark_ring()))
        self._near_oom: Dict[str, bool] = {}   # per-device episode latch
        self._drifted_owners: set = set()      # per-owner episode latch
        self._last_census: Optional[dict] = None
        self._last_poll_ts: Optional[float] = None
        self.polls = 0

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> "MemoryPoller":
        with self._lock:
            if self._thread is not None:
                return self
            self._stop.clear()
            thread = threading.Thread(target=self._loop,
                                      name="devscope-mem-poller",
                                      daemon=True)
            # started BEFORE publication, under the lock (the
            # recorder's idiom): a concurrent stop() must never join()
            # an unstarted thread (RuntimeError)
            thread.start()
            self._thread = thread
        return self

    def stop(self, timeout: float = 5.0) -> None:
        with self._lock:
            thread = self._thread
            self._thread = None
        if thread is not None:
            self._stop.set()
            thread.join(timeout=timeout)

    @property
    def running(self) -> bool:
        with self._lock:
            return self._thread is not None

    def _loop(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self.poll_once()
            except Exception:  # noqa: BLE001 - the poller is advisory:
                pass           # a bad stats read must not kill the loop

    # -- one poll ----------------------------------------------------------

    @staticmethod
    def _device_label(device, index: int) -> str:
        return f"d{getattr(device, 'id', index)}"

    @staticmethod
    def _read_stats(device) -> Optional[dict]:
        """One device's memory_stats as a normalized dict, or None
        (no stats surface / per-device read failure — never fatal)."""
        stats_fn = getattr(device, "memory_stats", None)
        if stats_fn is None:
            return None
        try:
            stats = stats_fn() or {}
        except Exception:  # noqa: BLE001
            return None
        in_use = _safe_int(stats.get("bytes_in_use"))
        return {"bytes_in_use": in_use,
                "peak_bytes": _safe_int(
                    stats.get("peak_bytes_in_use")) or in_use,
                "limit": _safe_int(stats.get("bytes_limit"))}

    def _advance_peak(self, label: str, reading: dict, now: float) -> None:
        """Fold one reading into the per-device peaks + the watermark
        ring (under the lock)."""
        with self._lock:
            prev_peak = self._peaks.get(label, 0)
            new_peak = max(prev_peak, reading["peak_bytes"],
                           reading["bytes_in_use"])
            self._peaks[label] = new_peak
            if new_peak > prev_peak:
                self._watermarks.append(
                    {"ts": now, "device": label, "bytes": new_peak,
                     "bytes_in_use": reading["bytes_in_use"],
                     "limit": reading["limit"]})

    def observe_peaks(self) -> int:
        """Advance the peak watermarks from a direct stats read — NO
        gauge publication, census or near-OOM trigger. The perfwatch
        ledger stamp calls this per append: writing a benchmark record
        must never fire a post-mortem dump or walk the live buffers as
        a side effect. Returns the highest observed peak."""
        now = time.time()
        for i, device in enumerate(self._devices_fn()):
            reading = self._read_stats(device)
            if reading is not None:
                self._advance_peak(self._device_label(device, i),
                                   reading, now)
        return self.peak_bytes()

    def poll_once(self) -> dict:
        """Sample every device, publish gauges, advance watermarks, run
        the buffer census (attribution + the owner drift cross-check —
        every poll, not only on fire), and trigger the near-OOM dump
        when a device crosses the threshold. Returns the per-device
        readings (tests assert on them)."""
        now = time.time()
        readings: Dict[str, dict] = {}
        total_use = total_peak = total_limit = 0
        fired: List[str] = []
        for i, device in enumerate(self._devices_fn()):
            reading = self._read_stats(device)
            if reading is None:
                continue
            label = self._device_label(device, i)
            in_use, limit = reading["bytes_in_use"], reading["limit"]
            readings[label] = {
                **reading, "platform": getattr(device, "platform", "?")}
            self.registry.gauge(
                f"devscope/mem/{label}/bytes_in_use").set(in_use)
            self.registry.gauge(
                f"devscope/mem/{label}/peak_bytes").set(
                reading["peak_bytes"])
            self.registry.gauge(f"devscope/mem/{label}/limit").set(limit)
            total_use += in_use
            total_peak += reading["peak_bytes"]
            total_limit += limit
            self._advance_peak(label, reading, now)
            if limit > 0 and in_use / limit >= _oom_pct():
                with self._lock:
                    latched = self._near_oom.get(label, False)
                    self._near_oom[label] = True
                if not latched:
                    fired.append(label)
            elif limit > 0 and in_use / limit < _oom_pct() - 0.05:
                # hysteresis: re-arm only once clearly below the line,
                # so a device hovering at the threshold dumps once per
                # episode, not once per poll
                with self._lock:
                    self._near_oom[label] = False
        self.registry.gauge("devscope/mem/bytes_in_use").set(total_use)
        self.registry.gauge("devscope/mem/peak_bytes").set(total_peak)
        self.registry.gauge("devscope/mem/limit").set(total_limit)
        self.registry.counter("devscope/mem/polls").inc()
        with self._lock:
            self.polls += 1
            self._last_poll_ts = now
        # the census runs EVERY poll: attribution and the owner drift
        # cross-check are the always-on detectors, not a post-mortem
        # extra — pure host arithmetic over buffer metadata
        census = self.census()
        for label in fired:
            self._fire_near_oom(label, readings[label], census)
        if self._on_poll is not None:
            try:
                self._on_poll()
            except Exception:  # noqa: BLE001 - the hook is advisory
                pass
        return readings

    def _fire_near_oom(self, label: str, reading: dict,
                       census: dict) -> None:
        self.registry.counter("devscope/mem/near_oom").inc()
        # lazy: the recorder is the perfwatch black box; a census-only
        # consumer (tests, scripts) never builds it
        from gethsharding_tpu.perfwatch.recorder import RECORDER

        with self._lock:
            tail = list(self._watermarks)[-8:]
        RECORDER.trigger(
            "hbm_near_oom", dump=True, device=label,
            bytes_in_use=reading["bytes_in_use"],
            limit=reading["limit"],
            utilization=round(
                reading["bytes_in_use"] / max(1, reading["limit"]), 4),
            census=census, watermarks=tail)

    # -- the buffer census -------------------------------------------------

    def census(self) -> dict:
        """Attribute every live device buffer to a registered owner (or
        ``unattributed``), cross-check each owner's census bytes against
        its own claimed accounting, and summarize the biggest
        (dtype, shape) groups. Pure host arithmetic over buffer
        metadata — no device sync, no transfers."""
        buffers = self._buffers_fn()
        with _OWNERS_LOCK:
            owner_list = list(_OWNERS.values())
        owned_ids: Dict[int, str] = {}
        owner_stats: Dict[str, dict] = {}
        for owner in owner_list:
            censused = 0
            count = 0
            if owner.buffers_fn is not None:
                try:
                    held = owner.buffers_fn()
                except Exception:  # noqa: BLE001 - an owner mid-teardown
                    held = []
                for buf in held:
                    owned_ids[id(buf)] = owner.name
                    censused += _safe_int(getattr(buf, "nbytes", 0))
                    count += 1
            try:
                claimed = _safe_int(owner.claimed_fn())
            except Exception:  # noqa: BLE001
                claimed = 0
            drift = abs(claimed - censused) if owner.buffers_fn else 0
            tolerance = int(max(claimed, censused) * _drift_pct()
                            + DRIFT_SLACK_BYTES)
            drifted = owner.buffers_fn is not None and drift > tolerance
            # episode latch (the near-OOM pattern): the counter ticks
            # at drift ONSET, not once per poll while the books stay
            # wrong — drift_events counts incidents, not duration
            with self._lock:
                was_drifted = owner.name in self._drifted_owners
                if drifted:
                    self._drifted_owners.add(owner.name)
                else:
                    self._drifted_owners.discard(owner.name)
            if drifted and not was_drifted:
                self.registry.counter("devscope/mem/drift").inc()
            owner_stats[owner.name] = {
                "claimed_bytes": claimed, "census_bytes": censused,
                "buffers": count, "drift_bytes": drift,
                "drifted": drifted}
        by_owner: Dict[str, dict] = {}
        groups: Dict[tuple, dict] = {}
        total = 0
        for buf in buffers:
            nbytes = _safe_int(getattr(buf, "nbytes", 0))
            total += nbytes
            name = owned_ids.get(id(buf), "unattributed")
            slot = by_owner.setdefault(name, {"buffers": 0, "bytes": 0})
            slot["buffers"] += 1
            slot["bytes"] += nbytes
            key = (str(getattr(buf, "dtype", "?")),
                   str(tuple(getattr(buf, "shape", ()))))
            grp = groups.setdefault(key, {"count": 0, "bytes": 0})
            grp["count"] += 1
            grp["bytes"] += nbytes
        top = sorted(groups.items(), key=lambda kv: -kv[1]["bytes"])
        census = {
            "ts": time.time(),
            "live_buffers": len(buffers),
            "live_bytes": total,
            "by_owner": by_owner,
            "owners": owner_stats,
            "top_groups": [{"dtype": k[0], "shape": k[1], **v}
                           for k, v in top[:_CENSUS_TOP]],
        }
        with self._lock:
            self._last_census = census
        return census

    # -- consumers ---------------------------------------------------------

    def peak_bytes(self) -> int:
        """The highest per-device HBM peak this poller has observed —
        the number the perfwatch ledger folds into every record."""
        with self._lock:
            return max(self._peaks.values(), default=0)

    def watermarks(self, limit: Optional[int] = None) -> List[dict]:
        with self._lock:
            out = list(self._watermarks)
        return out if limit is None else out[-limit:]

    def describe(self) -> dict:
        with self._lock:
            peaks = dict(self._peaks)
            last_census = self._last_census
            last_poll = self._last_poll_ts
            watermarks = len(self._watermarks)
        return {
            "running": self.running,
            "interval_s": self.interval_s,
            "polls": self.polls,
            "last_poll_ts": last_poll,
            "peaks": peaks,
            "watermarks": watermarks,
            "owners": owners(),
            "drift_events": self.registry.counter(
                "devscope/mem/drift").value,
            "near_oom_events": self.registry.counter(
                "devscope/mem/near_oom").value,
            "last_census": last_census,
        }
