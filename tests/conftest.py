"""Test configuration: hermetic CPU-only JAX with an 8-device virtual mesh.

Multi-chip sharding paths (`gethsharding_tpu.parallel`) are exercised on a
virtual 8-device CPU mesh (XLA host-platform device count), mirroring how the
driver dry-runs `__graft_entry__.dryrun_multichip`. The forcing logic lives
in `gethsharding_tpu.parallel.virtual` (shared with the dryrun entry) and
must run before any backend init, hence at conftest import time.
"""

import os as _os
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

# The lock recorder must patch threading BEFORE any package module is
# imported: module-level singletons (metrics.DEFAULT_REGISTRY, the
# tracer) allocate their locks at import time, and a lock created
# before the patch is real, unlabeled and invisible — every write it
# guards would look lockless to the race sanitizer and the session
# gate would report false violations against the static model.
# analysis/lockcheck imports nothing from the runtime packages, so
# this is safe ahead of the virtual-device forcing below.
if _os.environ.get("GETHSHARDING_LOCKCHECK") == "1" or \
        _os.environ.get("GETHSHARDING_RACECHECK") == "1":
    from gethsharding_tpu.analysis import lockcheck as _lockcheck_early

    _lockcheck_early.install()

from gethsharding_tpu.parallel.virtual import force_virtual_cpu_devices

force_virtual_cpu_devices(8)

# perfwatch hermeticity: the flight recorder dumps post-mortem bundles
# on every breaker trip / watchdog fire / soundness violation — events
# the resilience suites trigger ON PURPOSE, hundreds of times. Point
# the bundle directory and the benchmark ledger at a session temp dir
# (unless the caller pinned them) so a test run never litters the repo
# with black-box bundles or appends test noise to the committed
# measurement history.
if "GETHSHARDING_PERFWATCH_DIR" not in _os.environ:
    import tempfile as _tempfile

    _os.environ["GETHSHARDING_PERFWATCH_DIR"] = _tempfile.mkdtemp(
        prefix="perfwatch_blackbox_")
if "GETHSHARDING_PERFWATCH_LEDGER" not in _os.environ:
    import tempfile as _tempfile

    _os.environ["GETHSHARDING_PERFWATCH_LEDGER"] = _os.path.join(
        _tempfile.mkdtemp(prefix="perfwatch_ledger_"), "ledger.jsonl")

# XLA:CPU deterministically segfaults once a process holds too many
# compiled programs (~150): faulthandler runs place the crash at the
# SAME test/program both inside the persistent-cache deserializer
# (compilation_cache.get_executable_and_time) AND, with the cache off,
# inside plain backend_compile_and_load — i.e. executable-COUNT pressure
# in XLA's loader, not the cache and not our programs (the same file
# runs green in a short-lived process). The fix is to keep the live
# executable count low: `jax.clear_caches()` after every test module
# (autouse fixture below). With pressure bounded, the persistent cache
# (force_virtual_cpu_devices placed it: JAX_COMPILATION_CACHE_DIR, else
# <checkout>/.jax_cache) is safe and stays ENABLED — one-process
# `pytest tests/` runs green AND takes cache hits.
# GETHSHARDING_CACHE_OFF=1 disables the cache for debugging;
# `scripts/run_suite.sh` (one process per file) remains an equivalent,
# maximally isolated entry.
import gc as _gc

if _os.environ.get("GETHSHARDING_CACHE_OFF") == "1":
    import jax as _jax

    _jax.config.update("jax_enable_compilation_cache", False)

# GETHSHARDING_LOCKCHECK=1: wrap threading.Lock/RLock with the runtime
# lock-order recorder (analysis/lockcheck.py) for the whole session and
# assert, at session end, that the OBSERVED acquisition orders are
# inversion-free and consistent with the static lock graph the
# lock-order lint derives — the race-detector-lite that keeps the
# static model honest. Install happens at conftest import so every
# lock a test creates is wrapped.
if _os.environ.get("GETHSHARDING_LOCKCHECK") == "1":
    from gethsharding_tpu.analysis import lockcheck as _lockcheck

    _lockcheck.install()  # idempotent: the early install above won

# GETHSHARDING_RACECHECK=1: instrument attribute writes on the
# registered component classes (analysis/racecheck.py) with the runtime
# access sanitizer — per-(instance, attr) Eraser lockset tracking over
# real threads. The session gate below cross-validates the observed
# write locksets against the static race-guard model: a shared write
# the static map calls guarded running with no lock is a violation;
# statically-flagged attrs the tests never drove shared are printed as
# honest coverage gaps. Installing implies the lock recorder (the
# sanitizer reads per-thread held locks from it).
if _os.environ.get("GETHSHARDING_RACECHECK") == "1":
    from gethsharding_tpu.analysis import racecheck as _racecheck

    _racecheck.install()


@pytest.fixture(scope="session", autouse=True)
def _lockcheck_gate():
    yield
    from gethsharding_tpu.analysis import lockcheck

    if not lockcheck.active():
        return
    verdict = lockcheck.verify_against_static()
    observed = len(lockcheck.report()["edges"])
    print(f"\nlockcheck: {observed} lock-order edge(s) observed, "
          f"{len(verdict.inversions)} inversion(s), "
          f"{len(verdict.static_violations)} static violation(s), "
          f"{len(verdict.coverage_gaps)} coverage gap(s)")
    assert not verdict.inversions, (
        "lockcheck: AB/BA lock-order inversion observed:\n" + "\n".join(
            f"  {inv.second[0]} -> {inv.second[1]} reverses "
            f"{inv.first[0]} -> {inv.first[1]} (first seen at "
            f"{inv.first_site})" for inv in verdict.inversions))
    assert not verdict.static_violations, (
        "lockcheck: observed order contradicts the static lock graph:\n"
        + "\n".join(f"  {v}" for v in verdict.static_violations))
    if verdict.coverage_gaps:  # informational: model under-approximates
        print("\nlockcheck coverage gaps (observed, not in static graph):")
        for gap in verdict.coverage_gaps:
            print(f"  {gap}")


@pytest.fixture(scope="session", autouse=True)
def _racecheck_gate():
    yield
    import json as _json

    from gethsharding_tpu.analysis import racecheck

    if not racecheck.active():
        return
    baseline_path = (Path(__file__).resolve().parents[1]
                     / "gethsharding_tpu/analysis/baseline.json")
    baselined = set()
    if baseline_path.is_file():
        data = _json.loads(baseline_path.read_text())
        baselined = {key.split("::", 1)[1]
                     for key in data.get("findings", {})
                     if key.startswith("race-guard::")}
    verdict = racecheck.verify_against_static(baseline_keys=baselined)
    stats = racecheck.stats()
    print(f"\nracecheck: {stats['writes_seen']} write(s) on "
          f"{stats['attrs_written']} attr(s) across "
          f"{stats['classes_instrumented']} instrumented class(es); "
          f"{stats['shared_attrs']} shared, "
          f"{stats['unguarded_shared']} unguarded-shared, "
          f"{len(verdict.violations)} violation(s), "
          f"{len(verdict.confirmations)} confirmation(s), "
          f"{len(verdict.coverage_gaps)} coverage gap(s)")
    assert not verdict.violations, (
        "racecheck: runtime write locksets contradict the static "
        "race-guard model:\n" + "\n".join(f"  {v}"
                                          for v in verdict.violations))
    if verdict.confirmations:
        print("racecheck confirmations (statically flagged AND observed "
              "racing — fix or baseline):")
        for line in verdict.confirmations:
            print(f"  {line}")
    if verdict.coverage_gaps:  # informational: tests never drove these
        print("racecheck coverage gaps (statically racy, never observed "
              "shared this run):")
        for gap in verdict.coverage_gaps:
            print(f"  {gap}")


@pytest.fixture(autouse=True, scope="module")
def _bound_xla_executable_pressure():
    """Drop compiled executables after each module (see header)."""
    yield
    import jax

    jax.clear_caches()
    _gc.collect()

# Test tiers: everything in these modules compiles the heavyweight batched
# kernels (pairing Miller loops, 256-step recovery ladders) — minutes of
# XLA:CPU compile when the persistent cache is cold. They are auto-marked
# `slow`; the fast tier (`pytest -m "not slow"`) holds ~105 s warm
# (the README promise is ≤120 s on this host class).
_SLOW_MODULES = {
    "test_bn256_jax",
    "test_secp256k1_jax",
    "test_sigbackend",
    "test_graft_entry",
    "test_period_pipeline",
    "test_end_to_end",
    "test_replay",
    "test_stress",
    "test_knob_combos",  # one cold kernel compile per subprocess
}
# test_pallas_finalexp stays in the FAST tier on purpose: its three
# cheap helper parity tests (normalize/conv/mul_xi) are the fast guard
# on the mega-kernel module (arity/import regressions); the heavier
# parity/oracle/interpret/miller differentials carry `@slow` marks.


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.module.__name__ in _SLOW_MODULES:
            item.add_marker(pytest.mark.slow)
