"""On-demand continuous profiling: a jax.profiler session you can
toggle from an RPC, and a pure-Python sampler that answers "where does
host CPU go" with zero dependencies.

The only profiling hook before this was a whole-run
``jax.profiler.start_trace`` behind the CLI's ``--profile`` flag: to
profile a production incident you had to have predicted it at boot.
Here both profilers are runtime-toggled — ``shard_profileStart/Stop``
over RPC, ``/profile?action=start|stop`` on the StatusServer — and
bounded so leaving one on cannot fill a disk:

- **Device traces** (``jax.profiler``): each session writes into its
  own subdirectory of ``GETHSHARDING_DEVSCOPE_PROFILE_DIR``; old
  sessions are pruned to ``GETHSHARDING_DEVSCOPE_PROFILE_KEEP``.
  Degrades gracefully (reported, not raised) when jax is absent or the
  profiler backend refuses — a CPU control plane still gets the
  sampler.
- **Host sampler** (`SamplingProfiler`): a daemon thread walks
  ``sys._current_frames()`` at ``GETHSHARDING_DEVSCOPE_SAMPLE_HZ``,
  folding every thread's stack into flamegraph-style collapsed lines
  (``frame;frame;frame count``) under a bounded unique-stack budget.
  ``/profile/stacks`` serves the text (feed it to any flamegraph
  tool); a bounded ring of
  raw samples exports as Chrome trace events with the same
  ``clock_offset_us`` wall anchor as ``tracing.write_chrome_trace``,
  so ``scripts/trace_merge.py`` folds device spans and host samples
  into ONE Perfetto view.

Start/stop are idempotent by design (a second start reports
``already_running`` instead of leaking a session; a second stop is a
no-op) — RPC retries and impatient operators must not wedge the
profiler state machine.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from gethsharding_tpu import metrics
from gethsharding_tpu.tracing.export import clock_offset_us

# registered at import: prom rows from the first scrape. The session
# counters stay process-global (the PROFILER singleton is the only
# session manager); the per-sample counter resolves through the
# sampler's registry so probe instances (bench overhead drills) don't
# inflate the process row.
_M_SESSIONS = metrics.counter("devscope/profiler/sessions")
_G_ACTIVE = metrics.gauge("devscope/profiler/active")
metrics.counter("devscope/profiler/samples")

DEFAULT_SAMPLE_HZ = 67.0  # off the 50/60/100 Hz beat of periodic loops
DEFAULT_MAX_STACKS = 2000
DEFAULT_PROFILE_KEEP = 4
_RAW_RING = 4096  # raw samples kept for the Chrome export


def _sample_hz() -> float:
    return float(os.environ.get("GETHSHARDING_DEVSCOPE_SAMPLE_HZ",
                                str(DEFAULT_SAMPLE_HZ)))


def _max_stacks() -> int:
    return int(os.environ.get("GETHSHARDING_DEVSCOPE_SAMPLE_STACKS",
                              str(DEFAULT_MAX_STACKS)))


def _profile_dir() -> str:
    return os.environ.get("GETHSHARDING_DEVSCOPE_PROFILE_DIR",
                          os.path.join(os.getcwd(), "devscope_profile"))


def _profile_keep() -> int:
    return int(os.environ.get("GETHSHARDING_DEVSCOPE_PROFILE_KEEP",
                              str(DEFAULT_PROFILE_KEEP)))


def _default_mode() -> str:
    return os.environ.get("GETHSHARDING_DEVSCOPE_PROFILE_MODE", "both")


def _frame_label(frame) -> str:
    code = frame.f_code
    module = frame.f_globals.get("__name__", "?")
    return f"{module}.{code.co_name}:{frame.f_lineno}"


class SamplingProfiler:
    """Collapsed-stack wall sampler over ``sys._current_frames()``.

    One sample = one walk of every live thread's stack (its own
    excluded), folded root-first into ``a;b;c`` keys. Aggregation is
    bounded: past ``max_stacks`` unique keys, new stacks book under an
    overflow bucket instead of growing without limit.
    """

    def __init__(self, hz: Optional[float] = None,
                 max_stacks: Optional[int] = None,
                 registry: metrics.Registry = metrics.DEFAULT_REGISTRY):
        self.hz = _sample_hz() if hz is None else float(hz)
        self.max_stacks = (_max_stacks() if max_stacks is None
                           else int(max_stacks))
        self._m_samples = registry.counter("devscope/profiler/samples")
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._counts: Dict[str, int] = {}
        self._overflowed = 0
        self._raw: deque = deque(maxlen=_RAW_RING)
        self.samples = 0
        self.started_mono: Optional[float] = None
        self.stopped_mono: Optional[float] = None

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> "SamplingProfiler":
        with self._lock:
            if self._thread is not None:
                return self
            self._stop.clear()
            self.started_mono = time.monotonic()
            self.stopped_mono = None
            thread = threading.Thread(target=self._loop,
                                      name="devscope-sampler", daemon=True)
            # started before publication, under the lock — a racing
            # stop() must never join() an unstarted thread
            thread.start()
            self._thread = thread
        return self

    def stop(self, timeout: float = 5.0) -> None:
        with self._lock:
            thread = self._thread
            self._thread = None
            if thread is not None:
                self.stopped_mono = time.monotonic()
        if thread is not None:
            self._stop.set()
            thread.join(timeout=timeout)

    @property
    def running(self) -> bool:
        with self._lock:
            return self._thread is not None

    def _loop(self) -> None:
        period = 1.0 / max(self.hz, 0.1)
        while not self._stop.wait(period):
            try:
                self.sample_once()
            except Exception:  # noqa: BLE001 - sampling is advisory
                pass

    # -- one sample --------------------------------------------------------

    def sample_once(self) -> int:
        """Walk every other thread's stack once; returns the number of
        threads sampled. Public so the bench overhead probe can measure
        the EXACT per-tick cost it multiplies by hz."""
        me = threading.get_ident()
        now = time.monotonic()
        sampled = 0
        frames = sys._current_frames()
        entries = []
        for tid, frame in frames.items():
            if tid == me:
                continue
            stack: List[str] = []
            depth = 0
            while frame is not None and depth < 64:
                stack.append(_frame_label(frame))
                frame = frame.f_back
                depth += 1
            if not stack:
                continue
            stack.reverse()  # root first, flamegraph convention
            entries.append((tid, ";".join(stack), stack[-1]))
            sampled += 1
        with self._lock:
            for tid, key, leaf in entries:
                if key in self._counts:
                    self._counts[key] += 1
                elif len(self._counts) < self.max_stacks:
                    self._counts[key] = 1
                else:
                    self._overflowed += 1
                self._raw.append((now, tid, leaf))
            self.samples += 1
        self._m_samples.inc()
        return sampled

    # -- consumers ---------------------------------------------------------

    def collapsed(self) -> str:
        """The flamegraph collapsed-stack text: one ``stack count``
        line per unique stack, heaviest first."""
        with self._lock:
            items = sorted(self._counts.items(), key=lambda kv: -kv[1])
            overflow = self._overflowed
        lines = [f"{key} {count}" for key, count in items]
        if overflow:
            lines.append(f"[stacks-over-budget] {overflow}")
        return "\n".join(lines)

    def chrome_events(self, pid: Optional[int] = None) -> List[dict]:
        """Raw samples as Chrome trace events (one fixed-width "X" slab
        per sample, leaf frame as the name) — same clock base as
        tracing's span export, so the two files merge."""
        pid = os.getpid() if pid is None else pid
        dur = 1e6 / max(self.hz, 0.1)
        with self._lock:
            raw = list(self._raw)
        return [{
            "name": leaf, "cat": "sample", "ph": "X",
            "ts": round(ts * 1e6, 1), "dur": round(dur, 1),
            "pid": pid, "tid": tid, "args": {},
        } for ts, tid, leaf in raw]

    def write_chrome_trace(self, path: str,
                           label: Optional[str] = None) -> int:
        """Write the raw-sample ring in the exact file shape
        ``tracing.write_chrome_trace`` uses (pid lane metadata +
        ``clock_offset_us`` anchor), mergeable by trace_merge.py."""
        pid = os.getpid()
        events = self.chrome_events(pid=pid)
        meta = [{"name": "process_name", "ph": "M", "pid": pid, "ts": 0,
                 "args": {"name": label or f"sampler pid {pid}"}}]
        with open(path, "w") as fh:
            json.dump({
                "traceEvents": meta + events,
                "displayTimeUnit": "ms",
                "otherData": {"pid": pid,
                              "label": label or f"sampler pid {pid}",
                              "clock_offset_us": clock_offset_us()},
            }, fh)
        return len(events)

    def describe(self) -> dict:
        with self._lock:
            unique = len(self._counts)
            overflow = self._overflowed
            started = self.started_mono
            stopped = self.stopped_mono
        wall = None
        if started is not None:
            wall = round((stopped or time.monotonic()) - started, 3)
        return {"running": self.running, "hz": self.hz,
                "samples": self.samples, "unique_stacks": unique,
                "stacks_over_budget": overflow, "wall_s": wall}


class ProfileManager:
    """The process profiling state machine behind the RPC + HTTP
    toggles: at most one session (sampler and/or jax trace) at a time,
    idempotent start/stop, bounded on-disk footprint."""

    def __init__(self):
        self._lock = threading.Lock()
        self._sampler: Optional[SamplingProfiler] = None
        self._jax_dir: Optional[str] = None
        self._mode: Optional[str] = None
        self._jax_error: Optional[str] = None
        # identity of the start() currently building a session: stop()
        # clears it, and a build whose token is gone rolls back instead
        # of publishing over a successor session (mode alone is not
        # enough — stop-then-start during a build re-sets it)
        self._build_token: Optional[object] = None
        self.sessions = 0
        self.last_session: Optional[dict] = None

    # -- control -----------------------------------------------------------

    def start(self, mode: Optional[str] = None,
              hz: Optional[float] = None) -> dict:
        """Begin a session. `mode`: ``sampler`` (host only), ``jax``
        (device trace only) or ``both``. A session already running is
        REPORTED (``already_running``), never doubled — the jax
        profiler raises on nested traces and the sampler would leak a
        thread."""
        mode = (mode or _default_mode()).lower()
        if mode not in ("sampler", "jax", "both"):
            raise ValueError(
                f"unknown profile mode {mode!r}; pick sampler/jax/both")
        token = object()
        with self._lock:
            if self._mode is not None:
                return {"already_running": True, "mode": self._mode,
                        "jax_dir": self._jax_dir}
            self._mode = mode
            self._jax_error = None
            self._build_token = token
        jax_dir = None
        jax_error = None
        sampler = None
        try:
            if mode in ("jax", "both"):
                jax_dir, jax_error = self._start_jax_trace()
            if mode in ("sampler", "both"):
                sampler = SamplingProfiler(hz=hz)
                sampler.start()
        except BaseException:
            # a throw mid-build (bad GETHSHARDING_DEVSCOPE_SAMPLE_HZ,
            # thread creation failure) must not wedge the manager in a
            # phantom "already_running" session: roll the claim back,
            # stop whatever half started, re-raise to the caller
            with self._lock:
                if self._build_token is token:
                    self._mode = None
                    self._build_token = None
            if sampler is not None:
                sampler.stop()
            if jax_dir is not None:
                self._stop_jax_trace()
            raise
        published = False
        with self._lock:
            if self._build_token is token:
                self._sampler = sampler
                self._jax_dir = jax_dir
                self._jax_error = jax_error
                self.sessions += 1
                published = True
        if not published:
            # stop() (possibly followed by a fresh start()) raced this
            # build: roll OUR pieces back — never publish over, or
            # clear the gauge of, a successor session
            if sampler is not None:
                sampler.stop()
            if jax_dir is not None:
                self._stop_jax_trace()
            return {"started": False, "reason": "stopped during start"}
        _M_SESSIONS.inc()
        _G_ACTIVE.set(1)
        out = {"started": True, "mode": mode, "jax_dir": jax_dir}
        if jax_error:
            out["jax_error"] = jax_error
        return out

    def stop(self) -> dict:
        """End the session (both halves); a stop with nothing running
        is a reported no-op."""
        with self._lock:
            mode = self._mode
            sampler = self._sampler
            jax_dir = self._jax_dir
            self._mode = None
            self._sampler = None
            self._jax_dir = None
            self._build_token = None  # cancels an in-flight build
        if mode is None:
            return {"stopped": False, "reason": "not running"}
        _G_ACTIVE.set(0)
        if sampler is not None:
            sampler.stop()
        jax_stopped = False
        if jax_dir is not None:
            jax_stopped = self._stop_jax_trace()
        out = {"stopped": True, "mode": mode, "jax_dir": jax_dir,
               "jax_stopped": jax_stopped,
               "sampler": sampler.describe() if sampler else None}
        with self._lock:
            # keep the finished sampler so /profile/stacks serves the
            # LAST session's stacks after stop — the operator pulls the
            # artifact after toggling off, not during. A jax-only
            # session (sampler None) must not wipe the previous
            # sampler's artifact.
            if sampler is not None:
                self._last_sampler = sampler
            self.last_session = out
        return out

    # retained across stop() for post-session stack downloads
    _last_sampler: Optional[SamplingProfiler] = None

    def stacks(self) -> str:
        """Collapsed stacks of the RUNNING sampler, or the last
        finished one. Empty string when neither exists."""
        with self._lock:
            sampler = self._sampler or self._last_sampler
        return sampler.collapsed() if sampler is not None else ""

    def sampler(self) -> Optional[SamplingProfiler]:
        with self._lock:
            return self._sampler or self._last_sampler

    # -- the jax half ------------------------------------------------------

    def _start_jax_trace(self):
        """Open a jax.profiler trace into a fresh pruned session dir.
        Returns (dir, error): a missing/refusing profiler is an error
        STRING, never an exception — the sampler half must still
        start."""
        jax = sys.modules.get("jax")
        if jax is None:
            return None, "jax not imported in this process"
        base = _profile_dir()
        name = time.strftime("%Y%m%d_%H%M%S") + f"_{os.getpid()}"
        path = os.path.join(base, name)
        try:
            os.makedirs(path, exist_ok=True)
            jax.profiler.start_trace(path)
        except Exception as exc:  # noqa: BLE001 - profiler backends are
            return None, repr(exc)  # environment-fragile; report, go on
        self._prune(base)
        return path, None

    @staticmethod
    def _stop_jax_trace() -> bool:
        jax = sys.modules.get("jax")
        if jax is None:
            return False
        try:
            jax.profiler.stop_trace()
            return True
        except Exception:  # noqa: BLE001
            return False

    @staticmethod
    def _prune(base: str) -> None:
        """Keep only the newest ``GETHSHARDING_DEVSCOPE_PROFILE_KEEP``
        session directories (the flight recorder's shared pruner)."""
        from gethsharding_tpu.perfwatch.recorder import prune_dirs

        prune_dirs(base, _profile_keep())

    # -- consumers ---------------------------------------------------------

    def describe(self) -> dict:
        with self._lock:
            mode = self._mode
            sampler = self._sampler or self._last_sampler
            jax_dir = self._jax_dir
            jax_error = self._jax_error
        return {
            "active": mode is not None,
            "mode": mode,
            "jax_dir": jax_dir,
            "jax_error": jax_error,
            "sessions": self.sessions,
            "profile_dir": _profile_dir(),
            "sampler": sampler.describe() if sampler is not None else None,
        }


# THE process profiler (the RECORDER analog): the RPC methods and the
# StatusServer /profile routes drive this instance.
PROFILER = ProfileManager()
