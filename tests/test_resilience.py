"""The resilience layer (gethsharding_tpu/resilience): retry policies,
circuit-breaker backend failover with differential half-open probes,
the dispatch watchdog, the crash-safe vote journal, and deterministic
chaos injection — plus the drain-and-fail dispatcher shutdown and the
SMCClient stop contract."""

import logging
import threading
import time

import pytest

from gethsharding_tpu import metrics
from gethsharding_tpu.actors.notary import Notary
from gethsharding_tpu.actors.proposer import create_collation
from gethsharding_tpu.core.shard import Shard
from gethsharding_tpu.core.types import Transaction
from gethsharding_tpu.db.kv import MemoryKV, SqliteKV
from gethsharding_tpu.mainchain.accounts import AccountManager
from gethsharding_tpu.mainchain.client import ClientStopped, SMCClient
from gethsharding_tpu.params import Config, ETHER
from gethsharding_tpu.resilience.breaker import (
    CLOSED, OPEN, CircuitBreaker, FailoverSigBackend)
from gethsharding_tpu.resilience.chaos import (
    ChaosSchedule, ChaosSigBackend, InjectedFault, parse_spec, wrap)
from gethsharding_tpu.resilience.errors import (
    DeadlineExceeded, DispatcherClosed)
from gethsharding_tpu.resilience.journal import VoteJournal
from gethsharding_tpu.resilience.policy import RetryExecutor, RetryPolicy
from gethsharding_tpu.serving import ServingConfig, ServingSigBackend
from gethsharding_tpu.serving.pipeline import PipelinedDispatcher
from gethsharding_tpu.sigbackend import PythonSigBackend, get_backend
from gethsharding_tpu.smc.chain import SimulatedMainchain
from gethsharding_tpu.utils.hexbytes import Hash32


def _garbage_rows(n):
    """n invalid ecrecover rows: both backends answer None for each, so
    results compare equal across primary and fallback."""
    return ([b"\x11" * 32] * n, [b"\x22" * 65] * n)


# -- retry policy ------------------------------------------------------------


def test_retry_then_succeed_counts_retries():
    registry = metrics.Registry()
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise ConnectionError("transient")
        return "ok"

    executor = RetryExecutor(
        "t1", RetryPolicy(attempts=5, base_s=0.0, jitter=0.0),
        registry=registry)
    assert executor.call(flaky) == "ok"
    assert len(calls) == 3
    assert registry.counter("resilience/retry/t1/retries").value == 2
    assert registry.counter("resilience/retry/t1/giveups").value == 0


def test_retry_exhausted_reraises_and_counts_giveup():
    registry = metrics.Registry()
    executor = RetryExecutor(
        "t2", RetryPolicy(attempts=3, base_s=0.0, jitter=0.0),
        registry=registry)

    def always():
        raise TimeoutError("down")

    with pytest.raises(TimeoutError):
        executor.call(always)
    assert registry.counter("resilience/retry/t2/retries").value == 2
    assert registry.counter("resilience/retry/t2/giveups").value == 1


def test_retry_only_transient_classes():
    executor = RetryExecutor(
        "t3", RetryPolicy(attempts=5, base_s=0.0),
        registry=metrics.Registry())
    calls = []

    def fatal():
        calls.append(1)
        raise ValueError("a bug, not weather")

    with pytest.raises(ValueError):
        executor.call(fatal)
    assert len(calls) == 1  # no retry on non-transient classes


def test_retry_non_retryable_oserror_children_raise_immediately():
    """FileNotFoundError/PermissionError are OSError, but they are
    deterministic misconfiguration, not weather — the ladder must not
    hammer them with backoff."""
    for exc_type in (FileNotFoundError, PermissionError):
        registry = metrics.Registry()
        executor = RetryExecutor(
            "t3b", RetryPolicy(attempts=5, base_s=0.0, jitter=0.0),
            registry=registry)
        calls = []

        def fatal():
            calls.append(1)
            raise exc_type("bad endpoint path")

        with pytest.raises(exc_type):
            executor.call(fatal)
        assert len(calls) == 1
        assert registry.counter("resilience/retry/t3b/retries").value == 0


def test_retry_deadline_bounds_attempts():
    executor = RetryExecutor(
        "t4",
        RetryPolicy(attempts=50, base_s=0.02, deadline_s=0.06, jitter=0.0),
        registry=metrics.Registry())
    calls = []

    def always():
        calls.append(1)
        raise ConnectionError("down")

    t0 = time.monotonic()
    with pytest.raises(ConnectionError):
        executor.call(always)
    assert time.monotonic() - t0 < 1.0
    assert len(calls) < 50  # the deadline cut the ladder short


def test_retry_jitter_deterministic_with_seed():
    a = RetryPolicy(attempts=6, seed=9)
    b = RetryPolicy(attempts=6, seed=9)
    assert [a.backoff_s(i) for i in range(5)] == \
        [b.backoff_s(i) for i in range(5)]


# -- circuit breaker + failover backend --------------------------------------


class _FaultyBackend(PythonSigBackend):
    """Scalar-correct backend that raises while `faults` is positive."""

    name = "faulty"

    def __init__(self):
        self.faults = 0
        self.calls = 0

    def ecrecover_addresses(self, digests, sigs65):
        self.calls += 1
        if self.faults > 0:
            self.faults -= 1
            raise RuntimeError("device on fire")
        return super().ecrecover_addresses(digests, sigs65)


def _failover(fault_threshold=2, reset_s=60.0):
    registry = metrics.Registry()
    primary = _FaultyBackend()
    breaker = CircuitBreaker(name="t", fault_threshold=fault_threshold,
                             reset_s=reset_s, registry=registry)
    backend = FailoverSigBackend(primary, PythonSigBackend(),
                                 breaker=breaker, registry=registry)
    return backend, primary, breaker, registry


def test_breaker_trips_after_consecutive_faults_and_serves_fallback():
    backend, primary, breaker, registry = _failover(fault_threshold=2)
    want = PythonSigBackend().ecrecover_addresses(*_garbage_rows(3))
    primary.faults = 2
    # each faulted call is served from the fallback — callers never see
    # the device error — and the second consecutive fault trips it open
    assert backend.ecrecover_addresses(*_garbage_rows(3)) == want
    assert breaker.state == CLOSED
    assert backend.ecrecover_addresses(*_garbage_rows(3)) == want
    assert breaker.state == OPEN
    assert registry.counter("resilience/breaker/t/trips").value == 1
    # while open the primary is not touched at all
    calls_before = primary.calls
    assert backend.ecrecover_addresses(*_garbage_rows(3)) == want
    assert primary.calls == calls_before
    assert registry.counter(
        "resilience/breaker/t/fallback_calls").value >= 3
    assert registry.gauge("resilience/breaker/t/state").value == OPEN


def test_breaker_success_between_faults_resets_the_run():
    backend, primary, breaker, _ = _failover(fault_threshold=2)
    primary.faults = 1
    backend.ecrecover_addresses(*_garbage_rows(1))  # fault 1
    backend.ecrecover_addresses(*_garbage_rows(1))  # success: run resets
    primary.faults = 1
    backend.ecrecover_addresses(*_garbage_rows(1))  # fault 1 again
    assert breaker.state == CLOSED  # never two CONSECUTIVE faults


def test_breaker_half_open_probe_match_recloses():
    backend, primary, breaker, registry = _failover(
        fault_threshold=1, reset_s=0.02)
    primary.faults = 1
    backend.ecrecover_addresses(*_garbage_rows(2))
    assert breaker.state == OPEN
    time.sleep(0.03)
    # cooldown elapsed: this call runs the differential spot-check —
    # primary healed and agrees with the fallback, so the breaker closes
    want = PythonSigBackend().ecrecover_addresses(*_garbage_rows(2))
    assert backend.ecrecover_addresses(*_garbage_rows(2)) == want
    assert breaker.state == CLOSED
    assert registry.counter("resilience/breaker/t/probes").value == 1
    assert registry.counter("resilience/breaker/t/closes").value == 1
    # closed again: the primary serves
    calls_before = primary.calls
    backend.ecrecover_addresses(*_garbage_rows(2))
    assert primary.calls == calls_before + 1


def test_breaker_probe_exception_reopens():
    backend, primary, breaker, registry = _failover(
        fault_threshold=1, reset_s=0.02)
    primary.faults = 5  # stays broken through the first probe
    backend.ecrecover_addresses(*_garbage_rows(1))
    assert breaker.state == OPEN
    time.sleep(0.03)
    backend.ecrecover_addresses(*_garbage_rows(1))  # probe raises
    assert breaker.state == OPEN
    assert registry.counter("resilience/breaker/t/probes").value == 1
    assert registry.counter("resilience/breaker/t/closes").value == 0


def test_breaker_probe_mismatch_reopens():
    class _WrongBackend(PythonSigBackend):
        name = "wrong"

        def ecrecover_addresses(self, digests, sigs65):
            return ["not-the-answer"] * len(digests)

    registry = metrics.Registry()
    breaker = CircuitBreaker(name="t", fault_threshold=1, reset_s=0.0,
                             registry=registry)
    backend = FailoverSigBackend(_WrongBackend(), PythonSigBackend(),
                                 breaker=breaker, registry=registry)
    breaker.record_fault(RuntimeError("seed fault"))
    assert breaker.state == OPEN
    # probe: the "recovered" primary answers — wrongly. The fallback's
    # answer is served and the breaker refuses to re-promote.
    want = PythonSigBackend().ecrecover_addresses(*_garbage_rows(2))
    assert backend.ecrecover_addresses(*_garbage_rows(2)) == want
    assert breaker.state == OPEN
    assert registry.counter(
        "resilience/breaker/t/probe_mismatches").value == 1


def test_breaker_probe_concludes_even_when_fallback_raises():
    """A raising FALLBACK during the differential probe must still
    conclude the probe (re-open) — a dangling probe flag would bench
    the primary forever with every later call routed to the fallback."""

    class _BrokenFallback(PythonSigBackend):
        name = "broken"

        def ecrecover_addresses(self, digests, sigs65):
            raise RuntimeError("fallback also on fire")

    registry = metrics.Registry()
    breaker = CircuitBreaker(name="t", fault_threshold=1, reset_s=0.0,
                             registry=registry)
    backend = FailoverSigBackend(PythonSigBackend(), _BrokenFallback(),
                                 breaker=breaker, registry=registry)
    breaker.record_fault(RuntimeError("seed"))
    assert breaker.state == OPEN
    with pytest.raises(RuntimeError, match="fallback also on fire"):
        backend.ecrecover_addresses(*_garbage_rows(1))  # the probe
    # the probe concluded: the NEXT eligible call probes again (it is
    # not starved by a stuck probe-in-flight flag)
    assert breaker.state == OPEN
    assert backend._call("bls_verify_aggregates", [], [], []) == []
    assert registry.counter("resilience/breaker/t/probes").value == 2
    # the fallback's failure is NOT a primary fault: only the seed
    # fault is on the counter
    assert registry.counter(
        "resilience/breaker/t/primary_faults").value == 1


def test_breaker_probe_abort_keeps_cooldown_timestamp():
    """probe_aborted (fallback raised, primary untested) re-opens
    WITHOUT restarting the cooldown: the next call re-probes
    immediately, unlike probe_failed which benches the primary for a
    fresh reset_s."""
    now = [0.0]
    breaker = CircuitBreaker(name="t", fault_threshold=1, reset_s=10.0,
                             registry=metrics.Registry(),
                             clock=lambda: now[0])
    breaker.record_fault(RuntimeError("seed"))
    assert breaker.state == OPEN
    now[0] = 10.0
    assert breaker.on_call() == "probe"
    breaker.probe_aborted("fallback raised")
    assert breaker.on_call() == "probe"  # no fresh cooldown
    breaker.probe_failed(mismatch=True)
    assert breaker.on_call() == "fallback"  # a REAL probe verdict does
    now[0] = 20.0
    assert breaker.on_call() == "probe"


def test_breaker_stale_deferred_faults_do_not_retrip():
    """A backlog of watchdog-failed futures submitted BEFORE a recovery
    must not re-trip the breaker against the recovered primary when the
    caller finally drains them: deferred outcomes carry the epoch of
    their submit, and a re-close bumps it."""
    breaker = CircuitBreaker(name="t", fault_threshold=2, reset_s=0.0,
                             registry=metrics.Registry())
    old = breaker.epoch
    breaker.record_fault(RuntimeError("f1"), epoch=old)
    breaker.record_fault(RuntimeError("f2"), epoch=old)
    assert breaker.state == OPEN
    assert breaker.on_call() == "probe"
    breaker.probe_matched()
    assert breaker.state == CLOSED
    for _ in range(5):  # the stale backlog drains after recovery
        breaker.record_fault(DeadlineExceeded("stale"), epoch=old)
    assert breaker.state == CLOSED
    # ... and a stale SUCCESS must not mask fresh faults
    new = breaker.epoch
    breaker.record_fault(RuntimeError("fresh1"), epoch=new)
    breaker.record_success(epoch=old)  # ignored: pre-recovery submit
    breaker.record_fault(RuntimeError("fresh2"), epoch=new)
    assert breaker.state == OPEN  # two FRESH consecutive faults trip


def test_failover_future_result_is_idempotent_on_failure():
    """Polling a failed serving future twice must not double-count the
    fault or recompute the fallback."""
    from concurrent.futures import Future

    from gethsharding_tpu.resilience.breaker import _FailoverFuture

    inner: Future = Future()
    inner.set_exception(RuntimeError("device fault"))
    recoveries = []

    def recover(exc):
        recoveries.append(exc)
        return ["fallback-answer"]

    future = _FailoverFuture(inner, recover, lambda: None)
    assert future.result() == ["fallback-answer"]
    assert future.result() == ["fallback-answer"]
    assert len(recoveries) == 1


def test_failover_backpressure_shed_is_not_a_device_fault():
    """A ServingOverloadError escaping the primary is the CALLER's
    backpressure signal: it must re-raise (the shed contract) and must
    not count toward tripping the breaker."""
    from gethsharding_tpu.serving.queue import ServingOverloadError

    class _SheddingBackend(PythonSigBackend):
        name = "shedding"

        def ecrecover_addresses(self, digests, sigs65):
            raise ServingOverloadError("queue at capacity")

    registry = metrics.Registry()
    breaker = CircuitBreaker(name="t", fault_threshold=1, reset_s=60,
                             registry=registry)
    backend = FailoverSigBackend(_SheddingBackend(), PythonSigBackend(),
                                 breaker=breaker, registry=registry)
    for _ in range(3):
        with pytest.raises(ServingOverloadError):
            backend.ecrecover_addresses(*_garbage_rows(1))
    assert breaker.state == CLOSED
    assert registry.counter("resilience/breaker/t/trips").value == 0
    assert registry.counter(
        "resilience/breaker/t/primary_faults").value == 0


def test_failover_probe_shed_is_not_a_probe_failure():
    """A backpressure shed at PROBE time gets the same exemption as the
    closed path: the probe concludes without a verdict — no fault
    count, no fresh cooldown — and the fallback's answer is served."""
    from gethsharding_tpu.serving.queue import ServingOverloadError

    class _SheddingBackend(PythonSigBackend):
        name = "shedding"

        def ecrecover_addresses(self, digests, sigs65):
            raise ServingOverloadError("queue at capacity")

    registry = metrics.Registry()
    now = [0.0]
    breaker = CircuitBreaker(name="t", fault_threshold=1, reset_s=10.0,
                             registry=registry, clock=lambda: now[0])
    backend = FailoverSigBackend(_SheddingBackend(), PythonSigBackend(),
                                 breaker=breaker, registry=registry)
    breaker.record_fault(RuntimeError("seed"))
    assert breaker.state == OPEN
    now[0] = 10.0
    want = PythonSigBackend().ecrecover_addresses(*_garbage_rows(2))
    assert backend.ecrecover_addresses(*_garbage_rows(2)) == want
    assert breaker.state == OPEN
    # no fault beyond the seed, and no cooldown restart: the very next
    # call is a probe again instead of 10 more seconds of fallback
    assert registry.counter(
        "resilience/breaker/t/primary_faults").value == 1
    assert breaker.on_call() == "probe"


def test_failover_future_caller_timeout_is_not_a_fault():
    """result(timeout) expiring on a still-pending batch re-raises the
    caller's TimeoutError; a later poll still gets the real answer."""
    from concurrent import futures
    from concurrent.futures import Future

    from gethsharding_tpu.resilience.breaker import _FailoverFuture

    inner: Future = Future()
    faults = []
    future = _FailoverFuture(inner, lambda exc: faults.append(exc),
                             lambda: None)
    with pytest.raises(futures.TimeoutError):
        future.result(timeout=0.01)
    assert not faults  # no fault recorded, no fallback recompute
    inner.set_result(["late-but-right"])
    assert future.result() == ["late-but-right"]


def test_failover_async_caller_error_at_pull_is_not_a_fault():
    """A ValueError surfacing at result() time on the primary-routed
    async committee path gets the same exemption as the sync path:
    re-raised to the caller, no fault counted, no fallback recompute —
    one buggy caller must not demote a healthy device for everyone."""
    from gethsharding_tpu.sigbackend import VerdictFuture

    class _RaggedBackend(PythonSigBackend):
        name = "ragged"

        def bls_verify_committees_async(self, messages, sig_rows,
                                        pk_rows, pk_row_keys=None):
            def finalize():
                raise ValueError("ragged rows")

            return VerdictFuture(finalize)

    registry = metrics.Registry()
    breaker = CircuitBreaker(name="t", fault_threshold=1, reset_s=60,
                             registry=registry)
    backend = FailoverSigBackend(_RaggedBackend(), PythonSigBackend(),
                                 breaker=breaker, registry=registry)
    future = backend.bls_verify_committees_async([b"\x01" * 32], [[]], [[]])
    with pytest.raises(ValueError):
        future.result()
    with pytest.raises(ValueError):
        future.result()  # cached, not re-derived
    assert breaker.state == CLOSED
    assert registry.counter(
        "resilience/breaker/t/primary_faults").value == 0
    assert registry.counter(
        "resilience/breaker/t/fallback_calls").value == 0


def test_failover_async_pull_fault_counts_once_when_fallback_raises():
    """`VerdictFuture.result()` re-runs finalize when it raised, so a
    caller polling a doubly-failed verification twice must still count
    exactly ONE primary fault (not one per poll) and re-raise the
    cached fallback failure instead of re-deriving it."""
    from gethsharding_tpu.sigbackend import VerdictFuture

    class _DeadBackend(PythonSigBackend):
        name = "dead"

        def bls_verify_committees_async(self, messages, sig_rows,
                                        pk_rows, pk_row_keys=None):
            def finalize():
                raise RuntimeError("device on fire")

            return VerdictFuture(finalize)

    class _BrokenFallback(PythonSigBackend):
        name = "broken"
        calls = 0

        def bls_verify_committees(self, messages, sig_rows, pk_rows,
                                  pk_row_keys=None):
            type(self).calls += 1
            raise RuntimeError("fallback also down")

    registry = metrics.Registry()
    breaker = CircuitBreaker(name="t", fault_threshold=3, reset_s=60,
                             registry=registry)
    backend = FailoverSigBackend(_DeadBackend(), _BrokenFallback(),
                                 breaker=breaker, registry=registry)
    future = backend.bls_verify_committees_async([b"\x01" * 32], [[]], [[]])
    with pytest.raises(RuntimeError, match="fallback also down"):
        future.result()
    with pytest.raises(RuntimeError, match="fallback also down"):
        future.result()
    assert registry.counter(
        "resilience/breaker/t/primary_faults").value == 1
    assert _BrokenFallback.calls == 1
    assert breaker.state == CLOSED  # one op, one fault — not two of three


def test_failover_submit_caller_error_is_not_a_fault():
    """The serving `submit` recover path: a deterministic caller error
    failing the batch's future re-raises without counting a device
    fault or recomputing on the fallback (sync-path parity)."""
    from concurrent.futures import Future

    class _ServingLike(PythonSigBackend):
        name = "servinglike"

        def submit(self, op, *args, **kwargs):
            future: Future = Future()
            future.set_exception(TypeError("bad G1 point"))
            return future

    registry = metrics.Registry()
    breaker = CircuitBreaker(name="t", fault_threshold=1, reset_s=60,
                             registry=registry)
    backend = FailoverSigBackend(_ServingLike(), PythonSigBackend(),
                                 breaker=breaker, registry=registry)
    future = backend.submit("ecrecover_addresses", *_garbage_rows(1))
    with pytest.raises(TypeError):
        future.result()
    with pytest.raises(TypeError):
        future.result()  # idempotent: cached, no second recover
    assert breaker.state == CLOSED
    assert registry.counter(
        "resilience/breaker/t/primary_faults").value == 0


def test_failover_matches_python_backend_differentially():
    backend, primary, _, _ = _failover()
    py = PythonSigBackend()
    digests, sigs = _garbage_rows(5)
    assert backend.ecrecover_addresses(digests, sigs) == \
        py.ecrecover_addresses(digests, sigs)
    # async committee face, fault at submit -> recovered on fallback
    primary.faults = 0
    future = backend.bls_verify_committees_async([], [], [])
    assert future.result() == []


def test_failover_open_logs_transitions(caplog):
    backend, primary, breaker, _ = _failover(fault_threshold=1)
    primary.faults = 1
    with caplog.at_level(logging.WARNING, logger="resilience.breaker"):
        backend.ecrecover_addresses(*_garbage_rows(1))
    assert breaker.state == OPEN
    assert any("breaker t open" in rec.message for rec in caplog.records)


# -- dispatch watchdog -------------------------------------------------------


class _HangBackend(PythonSigBackend):
    """First `hangs` calls block on the release event (a wedged device
    dispatch); later calls answer instantly."""

    name = "hang"

    def __init__(self, hangs=1):
        self.hangs = hangs
        self.release = threading.Event()

    def ecrecover_addresses(self, digests, sigs65):
        if self.hangs > 0:
            self.hangs -= 1
            self.release.wait(10.0)
        return super().ecrecover_addresses(digests, sigs65)


def test_watchdog_fails_hung_batch_and_restarts_dispatcher():
    hang = _HangBackend(hangs=1)
    serving = ServingSigBackend(
        hang, ServingConfig(flush_us=100.0, watchdog_s=0.15))
    try:
        t0 = time.monotonic()
        with pytest.raises(DeadlineExceeded):
            serving.ecrecover_addresses(*_garbage_rows(2))
        # failed within ~the deadline, not the 10s the device hung for
        assert time.monotonic() - t0 < 2.0
        hang.release.set()  # let the superseded thread die
        # the restarted dispatcher serves the next batch
        want = PythonSigBackend().ecrecover_addresses(*_garbage_rows(2))
        assert serving.ecrecover_addresses(*_garbage_rows(2)) == want
        assert metrics.DEFAULT_REGISTRY.counter(
            "resilience/watchdog/timeouts").value >= 1
    finally:
        serving.close()


def test_watchdog_timeout_feeds_failover_breaker():
    """A chaos-hung dispatch under serving surfaces as DeadlineExceeded;
    the failover face above counts it as a primary fault and answers
    from the scalar fallback — the caller sees a RESULT, not an error."""
    schedule = ChaosSchedule(seed=3, rules={"dispatch.ecrecover_addresses": 1})
    chaotic = ChaosSigBackend(PythonSigBackend(), schedule, hang_s=5.0)
    serving = ServingSigBackend(
        chaotic, ServingConfig(flush_us=100.0, watchdog_s=0.15))
    registry = metrics.Registry()
    breaker = CircuitBreaker(name="wd", fault_threshold=3, reset_s=60,
                             registry=registry)
    backend = FailoverSigBackend(serving, PythonSigBackend(),
                                 breaker=breaker, registry=registry)
    try:
        want = PythonSigBackend().ecrecover_addresses(*_garbage_rows(1))
        t0 = time.monotonic()
        assert backend.ecrecover_addresses(*_garbage_rows(1)) == want
        assert time.monotonic() - t0 < 3.0
        assert registry.counter(
            "resilience/breaker/wd/primary_faults").value == 1
        # healed: the next call rides the primary serving path again
        assert backend.ecrecover_addresses(*_garbage_rows(1)) == want
    finally:
        serving.close()


def test_fail_current_min_age_spares_a_fresh_batch():
    """The watchdog's observe-then-abandon is racy: the hung batch can
    complete and a FRESH batch start between the age read and the
    fail_current call. min_age_s re-checks under the lock so the fresh
    batch survives instead of being failed moments after it started."""
    dispatcher = PipelinedDispatcher(name="t-minage")
    started, release = threading.Event(), threading.Event()

    def batch():
        started.set()
        release.wait(5.0)

    failed = []
    try:
        dispatcher.submit(batch, fail=failed.append)
        assert started.wait(2.0)
        # the in-flight batch is fresh: a watchdog that observed an
        # OLDER batch hanging must not abandon this one
        assert dispatcher.fail_current(
            DeadlineExceeded("stale observation"), min_age_s=3.0) is False
        assert not failed
        # the unconditional path (shutdown) still abandons it
        assert dispatcher.fail_current(
            DeadlineExceeded("really hung")) is True
        assert len(failed) == 1
    finally:
        release.set()
        dispatcher.close(wait=True)


def test_failover_future_proxies_serving_request():
    """observe_future_wake attributes wake latency via the serving
    future's `_serving_wake`; the failover wrapper must pass it
    through or the future_wake span silently disappears under
    failover-* + --serving."""
    from concurrent.futures import Future

    from gethsharding_tpu.resilience.breaker import _FailoverFuture

    inner: Future = Future()
    inner._serving_wake = sentinel = object()
    wrapped = _FailoverFuture(inner, lambda exc: None, lambda: None)
    assert wrapped._serving_wake is sentinel
    bare = _FailoverFuture(Future(), lambda exc: None, lambda: None)
    assert bare._serving_wake is None


# -- drain-and-fail dispatcher shutdown --------------------------------------


def test_dispatcher_close_while_busy_fails_queued_work():
    dispatcher = PipelinedDispatcher(name="t-close")
    started, release = threading.Event(), threading.Event()

    def slow():
        started.set()
        release.wait(5.0)

    failed = []
    dispatcher.submit(slow, fail=failed.append)
    assert started.wait(2.0)
    # queued-but-undispatched behind the busy batch
    dispatcher.submit(lambda: pytest.fail("must never run"),
                      fail=failed.append)
    t0 = time.monotonic()
    dispatcher.close(wait=True, grace_s=0.2)
    assert time.monotonic() - t0 < 2.0  # deterministic, no 10s hang
    # both the wedged in-flight batch and the queued one were failed
    assert len(failed) == 2
    assert all(isinstance(exc, DispatcherClosed) for exc in failed)
    release.set()


def test_dispatcher_close_healthy_drains_by_running():
    dispatcher = PipelinedDispatcher(name="t-drain")
    ran, failed = [], []
    dispatcher.submit(lambda: ran.append(1), fail=failed.append)
    dispatcher.close(wait=True)
    assert ran == [1] and failed == []
    with pytest.raises(RuntimeError):
        dispatcher.submit(lambda: None)


def test_dispatcher_close_nowait_leaves_inflight_work_alone():
    """close(wait=False) keeps its fire-and-forget contract: a healthy
    in-flight batch completes instead of being failed."""
    dispatcher = PipelinedDispatcher(name="t-nowait")
    started, release = threading.Event(), threading.Event()
    done, failed = [], []

    def slow():
        started.set()
        release.wait(5.0)
        done.append(1)

    dispatcher.submit(slow, fail=failed.append)
    assert started.wait(2.0)
    # second batch fills the ready slot, so close's sentinel is dropped
    dispatcher.submit(lambda: done.append(2), fail=failed.append)
    dispatcher.close(wait=False)  # returns immediately, fails nothing
    assert failed == []
    release.set()
    deadline = time.monotonic() + 2.0
    while len(done) < 2 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert done == [1, 2]
    # ... and the dispatch thread still exits despite the lost sentinel
    dispatcher._thread.join(timeout=2.0)
    assert not dispatcher._thread.is_alive()


def test_serving_close_while_hung_fails_futures_not_hangs():
    """Regression: close-while-busy at the serving level — a queued
    request behind a wedged dispatch gets a shutdown error instead of
    hanging the closing thread or the caller forever."""
    hang = _HangBackend(hangs=1)
    serving = ServingSigBackend(hang, ServingConfig(flush_us=100.0))
    results = []

    def call():
        try:
            results.append(serving.ecrecover_addresses(*_garbage_rows(1)))
        except Exception as exc:  # noqa: BLE001 - recording, not hiding
            results.append(exc)

    threads = [threading.Thread(target=call) for _ in range(2)]
    for thread in threads:
        thread.start()
    time.sleep(0.3)  # both flushed; one executing (hung), one behind it
    serving.batcher._dispatcher.close(wait=True, grace_s=0.2)
    hang.release.set()
    for thread in threads:
        thread.join(timeout=5.0)
    assert not any(thread.is_alive() for thread in threads)
    assert len(results) == 2
    assert any(isinstance(r, DispatcherClosed) for r in results)
    serving.close()


# -- crash-safe vote journal -------------------------------------------------


def test_kv_prefix_key_scan_skips_values(tmp_path):
    """The journal's namespace scan is key-only: both engines serve
    keys(prefix) without touching the (potentially huge) values."""
    for kv in (MemoryKV(), SqliteKV(str(tmp_path / "kv.db"))):
        kv.put(b"vj/v/a", b"\x01")
        kv.put(b"vj/v/b", b"\x01")
        kv.put(b"vj/audit_hwm", b"\x02")
        kv.put(b"chunk/huge", b"\xff" * 4096)
        assert sorted(kv.keys(b"vj/v/")) == [b"vj/v/a", b"vj/v/b"]
        assert sorted(kv.keys(b"vj/")) == [b"vj/audit_hwm", b"vj/v/a",
                                           b"vj/v/b"]
        assert len(list(kv.keys())) == 4
        kv.close()


def test_vote_journal_period_zero_watermark_is_real():
    """'period 0 audited' and 'nothing audited' must not conflate: the
    watermark is None until set, and set(0) persists."""
    journal = VoteJournal(MemoryKV(), registry=metrics.Registry())
    assert journal.audit_high_water() is None
    journal.set_audit_high_water(0)
    assert journal.audit_high_water() == 0


def test_vote_journal_roundtrip_and_prune(tmp_path):
    kv = SqliteKV(str(tmp_path / "journal.db"))
    journal = VoteJournal(kv, registry=metrics.Registry())
    assert not journal.has_vote(3, 7)
    journal.record_vote(3, 7)
    journal.record_vote(4, 7)
    journal.record_vote(3, 9)
    assert journal.has_vote(3, 7)
    assert sorted(journal.votes()) == [(3, 7), (3, 9), (4, 7)]
    assert journal.prune_votes(before_period=9) == 2
    assert sorted(journal.votes()) == [(3, 9)]
    journal.set_audit_high_water(5)
    journal.set_audit_high_water(3)  # monotonic: cannot go back
    assert journal.audit_high_water() == 5
    kv.close()
    # durability: a fresh handle on the same file sees the same state
    kv2 = SqliteKV(str(tmp_path / "journal.db"))
    journal2 = VoteJournal(kv2, registry=metrics.Registry())
    assert journal2.audit_high_water() == 5
    assert sorted(journal2.votes()) == [(3, 9)]
    kv2.close()


def _drive_period_with_collation(backend, client, notary, config):
    """Create + register a collation for the CURRENT period, then mine
    heads until the period ends (the notary votes along the way).
    Returns the period driven."""
    period = backend.current_period()
    collation = create_collation(client, 0, period,
                                 [Transaction(nonce=period, payload=b"x")])
    notary.shard.save_collation(collation)
    client.add_header(0, period, collation.header.chunk_root,
                      collation.header.proposer_signature)
    while backend.current_period() == period:
        backend.commit()
    return period


def test_vote_journal_exactly_once_across_notary_restart():
    """Kill a notary mid-period and restart it over the SAME journal:
    the restarted instance must neither re-submit the period's vote nor
    re-audit already-finished periods — even when the chain's own
    has_voted view is unreachable."""
    config = Config(quorum_size=1, period_length=4)
    backend = SimulatedMainchain(config=config)
    accounts = AccountManager()
    account = accounts.new_account()
    backend.fund(account.address, 2000 * ETHER)
    journal_kv = MemoryKV()
    journal = VoteJournal(journal_kv, registry=metrics.Registry())
    shard_kv = MemoryKV()

    client1 = SMCClient(backend=backend, accounts=accounts,
                        account=account, config=config)
    notary1 = Notary(client=client1, shard=Shard(0, shard_kv),
                     config=config, deposit_flag=True, all_shards=False,
                     journal=journal)
    notary1.start()
    backend.fast_forward(1)  # off period 0: the high-water mark is real
    p1 = _drive_period_with_collation(backend, client1, notary1, config)
    # one head into the next period so notary1 audits p1 (hwm -> p1)
    p2 = backend.current_period()
    collation = create_collation(client1, 0, p2,
                                 [Transaction(nonce=99, payload=b"y")])
    notary1.shard.save_collation(collation)
    client1.add_header(0, p2, collation.header.chunk_root,
                       collation.header.proposer_signature)
    backend.commit()  # head mid-period: audit p1 + vote p2
    assert notary1.votes_submitted == 2, notary1.errors
    assert journal.has_vote(0, p1) and journal.has_vote(0, p2)
    assert journal.audit_high_water() == p1
    audits1 = notary1.audits_run
    assert audits1 >= 1
    notary1.stop()  # the mid-period crash

    # restart: same account + journal; the chain's has_voted view is
    # DOWN (always-faulting), so only the journal can prevent a
    # double-vote
    schedule = ChaosSchedule(rules={"mainchain.has_voted": True})
    client2 = SMCClient(backend=wrap(backend, schedule, "mainchain"),
                        accounts=accounts, account=account, config=config)
    notary2 = Notary(client=client2, shard=Shard(0, shard_kv),
                     config=config, deposit_flag=True, all_shards=False,
                     journal=journal)
    notary2.start()
    try:
        # journal replay: "p1 audited" recovers as watermark p1 + 1
        assert notary2._last_audited_period == p1 + 1
        # mine out the REST of p2 without crossing into p3 (staying
        # mid-period keeps the p1-re-audit temptation alive every head)
        plen = config.period_length
        while (backend.block_number + 1) // plen == p2:
            backend.commit()
        assert notary2.votes_submitted == 0  # exactly-once across restart
        assert notary2.audits_run == 0       # p1 NOT re-audited
        # p2's single on-chain vote stands, un-doubled
        assert backend.collation_record(0, p2).vote_count == 1
        assert not notary2.errors, notary2.errors
    finally:
        notary2.stop()


def test_vote_journal_cleared_when_ahead_of_chain():
    """A journal that outlived its chain (wiped devnet: old datadir,
    fresh chain at period 0) must be invalidated on recovery — replay
    would silently mute the notary until the new chain catches up to
    the stale watermark."""
    journal = VoteJournal(MemoryKV(), registry=metrics.Registry())
    journal.record_vote(0, 5)
    journal.record_vote(0, 7)
    journal.set_audit_high_water(6)
    # same-chain restart: nothing ahead of the chain, journal kept
    assert not journal.invalidate_if_reset(current_period=7)
    assert journal.audit_high_water() == 6
    # chain reset: watermark/votes are ahead — cleared
    assert journal.invalidate_if_reset(current_period=2)
    assert journal.audit_high_water() is None
    assert list(journal.votes()) == []

    # the notary-level path: the stale journal from a previous chain
    # lifetime is cleared on on_start, and the notary votes normally
    config = Config(quorum_size=1, period_length=4)
    backend = SimulatedMainchain(config=config)
    client = SMCClient(backend=backend, config=config)
    backend.fund(client.account(), 2000 * ETHER)
    stale = VoteJournal(MemoryKV(), registry=metrics.Registry())
    stale.record_vote(0, 1)          # "already voted" period 1...
    stale.set_audit_high_water(40)   # ...and audited far ahead
    notary = Notary(client=client, shard=Shard(0, MemoryKV()),
                    config=config, deposit_flag=True, all_shards=False,
                    journal=stale)
    notary.start()
    try:
        assert stale.audit_high_water() is None  # cleared on replay
        assert notary._last_audited_period == 0
        backend.fast_forward(1)
        period = _drive_period_with_collation(backend, client, notary,
                                              config)
        assert notary.votes_submitted == 1, notary.errors
        assert backend.collation_record(0, period).vote_count == 1
    finally:
        notary.stop()


# -- deterministic chaos -----------------------------------------------------


def test_chaos_schedule_deterministic_and_seeded():
    rules = {"backend.op": 0.5}
    a = ChaosSchedule(seed=11, rules=rules)
    b = ChaosSchedule(seed=11, rules=rules)
    verdicts_a = [a.should_fail("backend.op") for _ in range(64)]
    verdicts_b = [b.should_fail("backend.op") for _ in range(64)]
    assert verdicts_a == verdicts_b
    assert any(verdicts_a) and not all(verdicts_a)
    c = ChaosSchedule(seed=12, rules=rules)
    assert [c.should_fail("backend.op") for _ in range(64)] != verdicts_a


def test_chaos_first_n_heals_and_prefix_rules():
    schedule = ChaosSchedule(rules={"backend.x": 2, "mainchain": True})
    assert schedule.should_fail("backend.x")
    assert schedule.should_fail("backend.x")
    assert not schedule.should_fail("backend.x")  # healed after n
    assert schedule.should_fail("mainchain.anything")  # bare prefix rule
    assert not schedule.should_fail("backend.other")
    assert schedule.injected == {"backend.x": 2, "mainchain.anything": 1}


def test_parse_spec():
    schedule = parse_spec(
        "seed=42, backend.bls_verify_committees=2, "
        "mainchain.collation_record=0.25, client.sign=always")
    assert schedule.seed == 42
    assert schedule.rules == {"backend.bls_verify_committees": 2,
                              "mainchain.collation_record": 0.25,
                              "client.sign": True}
    with pytest.raises(ValueError):
        parse_spec("not-a-rule")


def test_unwired_seams_flags_rules_no_injector_routes():
    from gethsharding_tpu.resilience.chaos import unwired_seams

    schedule = parse_spec(
        "seed=1,backend.ecrecover_addresses=2,client.sign=always,"
        "mainchain=0.5,typo.op=always")
    assert unwired_seams(
        schedule, ("mainchain", "backend", "dispatch")) == \
        ["client.sign", "typo.op"]
    assert unwired_seams(
        schedule, ("mainchain", "backend", "dispatch", "client")) == \
        ["typo.op"]


def test_chaos_property_backed_attribute_seam_injects():
    """A rule NAMING a property-backed attribute (mainchain.block_number
    is a @property, not a method) must inject on the read — silently
    returning the value would make the experiment test less than the
    operator asked for. Un-ruled data attributes pass through without
    consuming schedule slots."""
    config = Config(quorum_size=1)
    backend = SimulatedMainchain(config=config)
    schedule = ChaosSchedule(rules={"mainchain.block_number": 2})
    proxy = wrap(backend, schedule, "mainchain")
    with pytest.raises(InjectedFault):
        proxy.block_number
    with pytest.raises(InjectedFault):
        proxy.block_number
    assert proxy.block_number == backend.block_number  # healed after n
    _ = proxy.config  # no rule names it: off the books
    assert schedule.calls("mainchain.config") == 0


def test_chaos_backend_seam_under_client_retry():
    """mainchain-seam injection sits UNDER the client's retry executor:
    a first-n schedule is absorbed by retries (retry-then-succeed)."""
    config = Config(quorum_size=1)
    backend = SimulatedMainchain(config=config)
    schedule = ChaosSchedule(rules={"mainchain.shard_count": 2})
    client = SMCClient(
        backend=wrap(backend, schedule, "mainchain"), config=config,
        retry_policy=RetryPolicy(attempts=4, base_s=0.0, jitter=0.0))
    assert client.shard_count() == config.shard_count
    assert schedule.calls("mainchain.shard_count") == 3  # 2 faults + 1 ok


# -- the acceptance chaos run ------------------------------------------------


def test_chaos_device_fault_mid_audit_full_breaker_cycle(tracer):
    """ISSUE 5 acceptance: an injected device fault mid-audit trips the
    breaker, the notary completes the same period's votes on the scalar
    fallback with ZERO missed (shard, period) votes, and the breaker is
    observed closed again (open -> half-open differential probe ->
    closed) in metrics and trace output."""
    config = Config(quorum_size=1, period_length=4)
    backend = SimulatedMainchain(config=config)
    client = SMCClient(backend=backend, config=config)
    backend.fund(client.account(), 2000 * ETHER)

    # the first two committee-audit dispatches on the primary fail (the
    # injected device fault); everything after is healed
    schedule = ChaosSchedule(seed=5,
                             rules={"backend.bls_verify_committees": 2})
    registry = metrics.Registry()
    breaker = CircuitBreaker(name="accept", fault_threshold=1,
                             reset_s=0.005, registry=registry)
    failover = FailoverSigBackend(
        ChaosSigBackend(PythonSigBackend(), schedule),
        PythonSigBackend(), breaker=breaker, registry=registry)

    notary = Notary(client=client, shard=Shard(0, MemoryKV()),
                    config=config, deposit_flag=True, all_shards=False,
                    sig_backend=failover)
    notary.start()
    backend.fast_forward(1)
    periods = []
    try:
        for _ in range(5):
            periods.append(_drive_period_with_collation(
                backend, client, notary, config))
            time.sleep(0.01)  # let the open-state cooldown elapse
    finally:
        notary.stop()

    # zero missed votes: every driven period's (shard 0, period) vote
    # landed — including the ones audited/verified on the fallback
    assert notary.votes_submitted == len(periods), notary.errors
    for period in periods:
        assert backend.collation_record(0, period).vote_count == 1
    assert backend.last_approved_collation(0) == periods[-1]
    assert notary.audits_run >= 3
    assert notary.audit_mismatches == 0

    # the breaker went through the whole cycle: tripped open on the
    # injected fault, probed half-open, re-closed on a matching
    # differential spot-check — and ended closed
    assert schedule.injected.get("backend.bls_verify_committees") == 2
    assert registry.counter("resilience/breaker/accept/trips").value >= 1
    assert registry.counter("resilience/breaker/accept/probes").value >= 1
    assert registry.counter("resilience/breaker/accept/closes").value >= 1
    assert registry.counter(
        "resilience/breaker/accept/fallback_calls").value >= 1
    assert breaker.state == CLOSED
    assert registry.gauge("resilience/breaker/accept/state").value == CLOSED

    # ... and in trace output: the transition events were recorded
    names = {span["name"] for span in tracer.recent_spans()}
    assert "resilience/breaker/trip" in names
    assert "resilience/breaker/probe" in names
    assert "resilience/breaker/close" in names


@pytest.fixture
def tracer():
    from gethsharding_tpu import tracing

    tracing.enable(ring_spans=65536)
    tracing.TRACER.clear()
    yield tracing.TRACER
    tracing.disable()
    tracing.TRACER.clear()


# -- SMCClient stop contract -------------------------------------------------


def test_client_stop_exits_wait_for_transaction_promptly():
    client = SMCClient(backend=SimulatedMainchain())
    client.start()
    outcome = []

    def waiter():
        try:
            client.wait_for_transaction(Hash32(b"\xaa" * 32), timeout_s=30.0)
        except Exception as exc:  # noqa: BLE001 - recording the outcome
            outcome.append(exc)

    thread = threading.Thread(target=waiter)
    t0 = time.monotonic()
    thread.start()
    time.sleep(0.05)
    client.stop()
    thread.join(timeout=5.0)
    assert not thread.is_alive()
    assert time.monotonic() - t0 < 5.0  # nowhere near the 30s timeout
    assert len(outcome) == 1 and isinstance(outcome[0], ClientStopped)


def test_client_post_stop_calls_raise_client_stopped():
    client = SMCClient(backend=SimulatedMainchain())
    client.start()
    assert client.current_period() == 0
    client.stop()
    with pytest.raises(ClientStopped):
        client.current_period()
    with pytest.raises(ClientStopped):
        client.sign(b"\x00" * 32)
    with pytest.raises(ClientStopped):
        client.submit_vote(0, 1, 0, Hash32(b"\x00" * 32))
    client.start()  # restartable: the gate clears
    assert client.current_period() == 0


def test_client_stop_interrupts_inflight_retry_backoff():
    """stop() during a retry ladder's backoff must wake the sleeper and
    end the ladder with ClientStopped — not run the rest of the backoff
    budget against a backend that is going away."""
    config = Config(quorum_size=1)
    backend = SimulatedMainchain(config=config)
    schedule = ChaosSchedule(rules={"mainchain.shard_count": True})
    client = SMCClient(
        backend=wrap(backend, schedule, "mainchain"), config=config,
        retry_policy=RetryPolicy(attempts=50, base_s=5.0, cap_s=5.0,
                                 jitter=0.0))
    client.start()
    outcome = []

    def reader():
        try:
            client.shard_count()
        except Exception as exc:  # noqa: BLE001 - recording the outcome
            outcome.append(exc)

    thread = threading.Thread(target=reader)
    t0 = time.monotonic()
    thread.start()
    time.sleep(0.05)  # let the ladder enter its first 5s backoff
    client.stop()
    thread.join(timeout=5.0)
    assert not thread.is_alive()
    assert time.monotonic() - t0 < 2.0  # nowhere near one backoff step
    assert len(outcome) == 1 and isinstance(outcome[0], ClientStopped)


# -- netstore retry seam -----------------------------------------------------


def test_netstore_fetch_retries_rebroadcast_and_give_up():
    from gethsharding_tpu.p2p.service import Hub, P2PServer
    from gethsharding_tpu.storage.chunker import ChunkStoreError
    from gethsharding_tpu.storage.netstore import NetStore

    retries = metrics.DEFAULT_REGISTRY.counter(
        "resilience/retry/netstore/retries")
    giveups = metrics.DEFAULT_REGISTRY.counter(
        "resilience/retry/netstore/giveups")
    retries_before, giveups_before = retries.value, giveups.value
    ns = NetStore(p2p=P2PServer(hub=Hub()), fetch_timeout=0.06,
                  fetch_attempts=2, poll_interval=0.01)
    ns.start()
    try:
        with pytest.raises(ChunkStoreError, match="unavailable"):
            ns.get_chunk(b"\x42" * 32)
    finally:
        ns.stop()
    assert retries.value == retries_before + 1
    assert giveups.value == giveups_before + 1


# -- the closed-breaker overhead budget --------------------------------------


def test_breaker_closed_overhead_on_serving_hot_path():
    """With the breaker closed and no faults injected, the failover
    guard work per call (on_call + record_success + a counter) must
    cost <2% of a serving request — the same instrumentation budget the
    observability tests pin for tracing."""
    serving = ServingSigBackend(PythonSigBackend(),
                                ServingConfig(flush_us=500.0))
    backend, _, breaker, _ = _failover()
    try:
        serving.ecrecover_addresses(*_garbage_rows(0))  # warm the threads
        n = 100
        t0 = time.perf_counter()
        for i in range(n):
            serving.ecrecover_addresses(*_garbage_rows(i % 97))
        per_request_s = (time.perf_counter() - t0) / n
    finally:
        serving.close()

    m = 50_000
    t0 = time.perf_counter()
    for _ in range(m):
        if breaker.on_call() == "primary":
            breaker.record_success()
    guard_s = (time.perf_counter() - t0) / m
    # charge 3 guard evaluations per request (3x the real count of 1)
    assert 3 * guard_s < 0.02 * per_request_s, (
        f"breaker-closed overhead {3 * guard_s * 1e6:.3f}us vs request "
        f"{per_request_s * 1e6:.1f}us")
