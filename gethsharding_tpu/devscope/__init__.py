"""devscope: the device introspection plane.

perfwatch answers "how long did it take" and tracing answers "where in
the pipeline"; devscope answers the three questions neither can — what
is ON the device, what did compilation cost, and where does host CPU
go:

- ``memory.py``       — `MemoryPoller` over ``device.memory_stats()``:
  per-device ``devscope/mem/*`` gauges, live-buffer census attributed
  to registered owners (resident pk-plane LRU cross-checked against
  its own accounting — drift is a counter), an HBM high-watermark ring,
  and a near-OOM trigger that dumps the census into a perfwatch
  flight-recorder bundle.
- ``compilewatch.py`` — `CompileWatch`: per-(op, shape) compile
  wall-time captured at the sig backend's compile-cache miss sites, a
  sliding-window recompile-storm detector (``devscope/compile/storm``
  gauge + recorder event, once per episode), and the cumulative
  compile-time the benchmark ledger folds into every record.
- ``profiler.py``     — `ProfileManager` / `SamplingProfiler`:
  on-demand ``jax.profiler`` sessions in a bounded pruned directory
  plus a pure-Python collapsed-stack sampler, toggled at runtime via
  ``shard_profileStart/Stop`` RPC or ``/profile`` on the StatusServer,
  stacks downloadable from ``/profile/stacks``.

Surfaces: the ``devscope`` section on ``/status`` (`devscope_status`),
``devscope/*`` rows on /metrics + the Prometheus exposition
(tests/test_devscope.py holds the acceptance assertions). ``boot()`` is the
node/chain_server entry: start the background poller (off with
``GETHSHARDING_DEVSCOPE=0``) and return it.
"""

from __future__ import annotations

import os
import threading
from typing import Optional

from gethsharding_tpu.devscope.compilewatch import COMPILES, CompileWatch
from gethsharding_tpu.devscope.memory import (
    MemoryPoller,
    owners,
    register_owner,
    unregister_owner,
)
from gethsharding_tpu.devscope.profiler import (
    PROFILER,
    ProfileManager,
    SamplingProfiler,
)

__all__ = [
    "COMPILES",
    "CompileWatch",
    "MemoryPoller",
    "PROFILER",
    "ProfileManager",
    "SamplingProfiler",
    "boot",
    "devscope_status",
    "ledger_fields",
    "owners",
    "poller",
    "register_owner",
    "shutdown",
    "unregister_owner",
]

# THE process memory poller, built by boot() (None until a composition
# root boots the plane — library users poll their own instances)
_POLLER: Optional[MemoryPoller] = None
_POLLER_LOCK = threading.Lock()


def poller() -> Optional[MemoryPoller]:
    """The booted process poller, or None."""
    with _POLLER_LOCK:
        return _POLLER


def boot(start_poller: bool = True) -> Optional[MemoryPoller]:
    """Composition-root entry (node CLI, chain_server): build + start
    the process memory poller unless ``GETHSHARDING_DEVSCOPE=0``.
    Idempotent — a second boot returns the running poller."""
    global _POLLER
    if os.environ.get("GETHSHARDING_DEVSCOPE", "1") == "0":
        return None
    with _POLLER_LOCK:
        if _POLLER is None:
            # the booted poller is the devscope heartbeat: its tick
            # also drains the compile watch's storm verdict, so the
            # latched storm gauge clears for prom-only scrapers
            _POLLER = MemoryPoller(
                on_poll=lambda: COMPILES.storm_active())
        instance = _POLLER
    if start_poller:
        instance.start()
    return instance


def shutdown() -> None:
    """Stop the booted poller and any live profiling session (tests +
    process teardown)."""
    global _POLLER
    with _POLLER_LOCK:
        instance = _POLLER
        _POLLER = None
    if instance is not None:
        instance.stop()
    PROFILER.stop()


def devscope_status() -> dict:
    """The node /status ``devscope`` section: memory plane, compile
    plane, profiler state — device introspection at a glance."""
    mem = poller()
    return {
        "memory": mem.describe() if mem is not None else None,
        "compile": COMPILES.describe(),
        "profiler": PROFILER.describe(),
    }


def ledger_fields() -> dict:
    """The numeric fields the perfwatch ledger folds into every
    record's metrics: the observed peak-HBM high watermark and the
    cumulative compile cost — so the regression gate can flag memory
    creep and compile-time growth, not just latency. Zeros on a host
    with no booted poller / no compiles (the gate skips zero-median
    baselines). Reads the device stats on demand (`observe_peaks` — no
    census, no gauges, no near-OOM side effects from inside the ledger
    writer) so a record written between two background ticks (or in a
    process that booted with the thread off) still
    observes the device state it just measured."""
    mem = poller()
    peak = 0
    if mem is not None:
        try:
            peak = mem.observe_peaks()
        except Exception:  # noqa: BLE001 - the stamp is additive
            peak = mem.peak_bytes()
    return {
        "peak_hbm_bytes": float(peak),
        "compile_total_s": round(COMPILES.total_s, 4),
        "compile_count": float(COMPILES.compiles),
    }
