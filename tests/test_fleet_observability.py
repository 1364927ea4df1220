"""Fleet observability plane: cross-process trace stitching, metrics
federation, and per-class SLO burn-rate tracking (ISSUE 9).

Four contracts:

- STITCHING: a request issued through `RouterSigBackend` against an
  RPC replica produces ONE trace id spanning router route/attempt
  spans, the replica's RPC handler span (adopted from the wire trace
  envelope) and the serving request/dispatch spans — and the dispatch
  span carries `device_ms`/`wire_bytes` tags. The per-process Chrome
  exports merge into one Perfetto file (scripts/trace_merge.py).
- FEDERATION: after one health-sweep pass the router's registry (and
  its Prometheus exposition) contains `fleet/replica/<name>/` rollups
  scraped over the new `shard_metrics` RPC, plus the fleet aggregates.
- SLO: objectives window good/bad events into fast/slow burn rates
  with deterministic clocks; the serving tier and router record events;
  a seeded chaos breaker trip measurably moves the affected class's
  burn rate in the closed loop; soundness violations burn the
  integrity budget.
- RING: the bounded finished-span ring counts overwritten spans
  (`trace/dropped`).
"""

import json
import time

import pytest

from gethsharding_tpu import metrics, slo, tracing
from gethsharding_tpu.crypto import secp256k1 as ecdsa
from gethsharding_tpu.crypto.keccak import keccak256
from gethsharding_tpu.fleet import (
    FleetRouter,
    Replica,
    RouterSigBackend,
)
from gethsharding_tpu.fleet.router import RpcReplicaBackend
from gethsharding_tpu.rpc.server import RPCServer
from gethsharding_tpu.serving import ServingConfig, ServingSigBackend
from gethsharding_tpu.sigbackend import PythonSigBackend
from gethsharding_tpu.slo.tracker import BUCKET_S, Objective, SLOTracker
from gethsharding_tpu.smc.chain import SimulatedMainchain


@pytest.fixture
def tracer():
    tracing.enable(ring_spans=65536)
    tracing.TRACER.clear()
    yield tracing.TRACER
    tracing.disable()
    tracing.TRACER.clear()


@pytest.fixture
def fresh_slo():
    """A fresh process SLO tracker on a fresh registry, restored
    afterwards — burn state must not leak between tests."""
    import importlib

    # the package re-exports `tracker` (the accessor), shadowing the
    # submodule attribute — reach the module itself for the global
    tracker_mod = importlib.import_module("gethsharding_tpu.slo.tracker")
    saved = tracker_mod.TRACKER
    fresh = slo.configure(registry=metrics.Registry())
    yield fresh
    tracker_mod.TRACKER = saved


def _ecdsa_cases(n: int, tag: bytes = b"fleetobs"):
    cases = []
    for i in range(n):
        priv = int.from_bytes(keccak256(tag + b"-%d" % i), "big") % ecdsa.N
        digest = keccak256(tag + b"-msg-%d" % i)
        cases.append((digest, ecdsa.sign(digest, priv).to_bytes65(),
                      ecdsa.priv_to_address(priv)))
    return cases


def _rpc_fleet(n_replicas: int = 2, registry=None):
    """Router over `n_replicas` RPCServer replicas dialed through
    `RpcReplicaBackend` — the cross-process shape, in-process."""
    registry = registry or metrics.Registry()
    servers, replicas = [], []
    for i in range(n_replicas):
        serving = ServingSigBackend(PythonSigBackend(),
                                    ServingConfig(flush_us=200))
        server = RPCServer(SimulatedMainchain(), sig_backend=serving)
        server.start()
        servers.append((server, serving))
        backend = RpcReplicaBackend.dial(*server.address)
        replicas.append(Replica(f"r{i}", backend, health=backend.health,
                                probe=None, registry=registry))
    router = FleetRouter(replicas, health_interval_s=0.0,
                         registry=registry)
    return router, replicas, servers, registry


def _close_fleet(router, replicas, servers):
    for replica in replicas:
        replica.backend.close()
    for server, serving in servers:
        server.stop()
        serving.close()
    del router


# == cross-process trace stitching ==========================================


def test_routed_request_stitches_one_trace_end_to_end(tracer, fresh_slo):
    """THE acceptance path: RouterSigBackend -> RPC replica. One trace
    id covers fleet/route -> fleet/attempt -> rpc/client ->
    rpc/shard_ecrecover (adopted from the wire envelope) ->
    serving/ecrecover/request -> device_dispatch, and the dispatch
    span carries device_ms/wire_bytes tags."""
    router, replicas, servers, _ = _rpc_fleet(2)
    back = RouterSigBackend(router)
    try:
        digest, sig, want = _ecdsa_cases(1)[0]
        assert back.ecrecover_addresses([digest], [sig]) == [want]
    finally:
        _close_fleet(router, replicas, servers)

    # the replica closes its enclosing span after it has flushed
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        spans = tracer.recent_spans()
        if any(s["name"] == "rpc/ecrecover/server_time" for s in spans):
            break
        time.sleep(0.001)
    routes = [s for s in spans if s["name"] == "fleet/route"]
    assert len(routes) == 1
    trace_id = routes[0]["trace"]
    by_name = {}
    for s in spans:
        if s["trace"] == trace_id:
            by_name.setdefault(s["name"], []).append(s)
    # the whole ladder shares the route's trace id
    for name in ("fleet/attempt", "rpc/client/shard_ecrecover",
                 "rpc/shard_ecrecover", "serving/ecrecover/request",
                 "serving/ecrecover/device_dispatch"):
        assert name in by_name, (name, sorted(by_name))
    # parentage: attempt under route, client under attempt, handler
    # (cross-"process" via the trace envelope) under the client span
    attempt = by_name["fleet/attempt"][0]
    assert attempt["parent"] == routes[0]["span"]
    assert attempt["tags"]["replica"] in ("r0", "r1")
    assert attempt["tags"]["attempt"] == 1
    client = by_name["rpc/client/shard_ecrecover"][0]
    assert client["parent"] == attempt["span"]
    # ... through the client's roundtrip, whose span id the envelope
    # carries, and the server's enclosing server_time span
    handler = by_name["rpc/shard_ecrecover"][0]
    served = by_name["rpc/ecrecover/server_time"][0]
    roundtrip = by_name["rpc/client/roundtrip"][0]
    assert handler["parent"] == served["span"]
    assert served["parent"] == roundtrip["span"]
    assert roundtrip["parent"] == client["span"]
    # the client-side correlation tag points at the stitched trace
    assert client["tags"]["remote_trace"] == trace_id
    # the serving request hangs off the handler; its dispatch span
    # carries the device-time attribution tags
    request = by_name["serving/ecrecover/request"][0]
    assert request["parent"] == handler["span"]
    dispatch = by_name["serving/ecrecover/device_dispatch"][0]
    assert dispatch["parent"] == request["span"]
    assert dispatch["tags"]["device_ms"] >= 0.0
    assert dispatch["tags"]["wire_bytes"] >= 32 + 65  # digest + sig
    assert request["tags"]["device_ms"] >= 0.0


def test_trace_merge_tool_aligns_pid_lanes(tracer, tmp_path):
    """Two per-process exports (distinct pids, wall anchors) merge into
    one Perfetto file: both lanes present, stitched trace ids intact,
    timestamps on one common axis."""
    import os
    import sys

    scripts = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts")
    sys.path.insert(0, scripts)
    try:
        from trace_merge import merge_traces
    finally:
        sys.path.remove(scripts)

    with tracing.span("router/work"):
        pass
    path_a = str(tmp_path / "a.json")
    tracing.write_chrome_trace(path_a, pid=1001, label="router")
    tracer.clear()
    with tracing.span("replica/work"):
        pass
    path_b = str(tmp_path / "b.json")
    tracing.write_chrome_trace(path_b, pid=2002, label="replica-0")

    merged = merge_traces([json.load(open(path_a)),
                           json.load(open(path_b))])
    events = merged["traceEvents"]
    span_events = [e for e in events if e.get("ph") == "X"]
    assert {e["pid"] for e in span_events} == {1001, 2002}
    names = {e["name"] for e in span_events}
    assert {"router/work", "replica/work"} <= names
    # process_name metadata survives per lane
    lanes = {e["pid"]: e["args"]["name"] for e in events
             if e.get("ph") == "M" and e.get("name") == "process_name"}
    assert lanes[1001] == "router" and lanes[2002] == "replica-0"
    # one common, near-zero-based time axis
    assert all(e["ts"] >= 0 for e in span_events)


def test_rpc_client_surfaces_remote_trace_tag(tracer):
    """SATELLITE: the `trace` field the server has always returned on
    the response envelope (and the client silently discarded) is now
    surfaced as the client span's `remote_trace` tag, so caller logs
    correlate to replica traces even against a replica that does not
    stitch. Every RPC method gets this — not just the serving ops."""
    server = RPCServer(SimulatedMainchain())
    server.start()
    from gethsharding_tpu.rpc.client import RPCClient

    client = RPCClient(*server.address)
    try:
        assert isinstance(client.call("shard_blockNumber"), int)
    finally:
        client.close()
        server.stop()
    clients = [s for s in tracer.recent_spans()
               if s["name"] == "rpc/client/shard_blockNumber"]
    assert len(clients) == 1
    handler = [s for s in tracer.recent_spans()
               if s["name"] == "rpc/shard_blockNumber"]
    assert len(handler) == 1
    assert clients[0]["tags"]["remote_trace"] == handler[0]["trace"]
    # the client span itself was the outbound context, so the handler
    # adopted it: one trace id across the wire, both directions
    assert clients[0]["trace"] == handler[0]["trace"]


def test_dropped_span_counter_on_ring_overflow(tracer):
    """SATELLITE: ring overflow is counted, not silent."""
    registry = metrics.Registry()
    t = tracing.Tracer(ring_spans=4, registry=registry)
    t.enabled = True
    for i in range(10):
        t.record(f"s{i}", 0.0, 1.0)
    assert t.spans_dropped == 6
    assert registry.counter("trace/dropped").value == 6
    assert t.spans_recorded == 10


# == metrics federation =====================================================


def test_health_sweep_federates_replica_metrics(fresh_slo):
    """After one sweep, the router registry holds
    fleet/replica/<name>/ rollups scraped via shard_metrics, the
    fleet aggregates, and the Prometheus exposition carries them."""
    router, replicas, servers, registry = _rpc_fleet(2)
    back = RouterSigBackend(router)
    try:
        for digest, sig, want in _ecdsa_cases(4, b"fed"):
            assert back.ecrecover_addresses([digest], [sig]) == [want]
        router.refresh(force=True)  # ONE sweep pass: health + scrape
        # the replicas share this test process's DEFAULT_REGISTRY, so
        # the scrape sees the serving counters the traffic just moved
        gauge = registry.get("fleet/replica/r0/serving/ecrecover/"
                             "requests/count")
        assert gauge is not None and gauge.value >= 1
        lat = registry.get("fleet/replica/r0/serving/ecrecover/"
                           "dispatch_latency/p99_s")
        assert lat is not None and lat.value >= 0.0
        # fleet aggregates
        assert registry.get("fleet/total_inflight") is not None
        assert registry.get("fleet/worst_replica_p99_s").value >= 0.0
        for klass in ("interactive", "bulk_audit", "catchup_replay"):
            assert registry.get(f"fleet/class/{klass}/queue_depth") \
                is not None
        # and the exposition renders them
        prom = metrics.prometheus_text(registry)
        assert "gethsharding_fleet_replica_r0_serving_ecrecover_" \
            "requests_count" in prom
        assert "gethsharding_fleet_total_inflight" in prom
        assert replicas[0].last_metrics  # scrape retained for debugging
    finally:
        _close_fleet(router, replicas, servers)


def test_shard_metrics_rpc_serves_registry_snapshot():
    server = RPCServer(SimulatedMainchain())
    server.start()
    backend = RpcReplicaBackend.dial(*server.address)
    try:
        snap = backend.metrics()
        assert isinstance(snap, dict)
    finally:
        backend.close()
        server.stop()


# == the SLO layer ==========================================================


def _tracker(**kw) -> SLOTracker:
    objectives = kw.pop("objectives", None) or {
        "interactive": Objective("interactive", availability=0.999,
                                 latency_target_s=0.5),
        "integrity": Objective("integrity", availability=0.9999),
    }
    return SLOTracker(objectives=objectives,
                      registry=kw.pop("registry", metrics.Registry()),
                      **kw)


def test_burn_rate_windows_and_budget():
    """Deterministic clock: burn = error_ratio / budget per window;
    fast window forgets, slow window remembers; budget_remaining
    mirrors the slow burn."""
    t = _tracker()
    now = 1000.0
    # 10 events, 1 bad: error ratio 0.1, budget 0.001 -> burn 100x
    for i in range(9):
        t.record("interactive", ok=True, latency_s=0.01, now=now)
    t.record("interactive", ok=False, now=now)
    assert t.burn_rate("interactive", "fast", now=now) == \
        pytest.approx(100.0)
    assert t.burn_rate("interactive", "slow", now=now) == \
        pytest.approx(100.0)
    assert t.budget_remaining("interactive", now=now) == 0.0
    # after the fast window passes (good traffic meanwhile), the fast
    # burn recovers while the slow window still remembers the bad event
    later = now + t.fast_window_s + BUCKET_S
    for i in range(90):
        t.record("interactive", ok=True, latency_s=0.01, now=later)
    fast = t.burn_rate("interactive", "fast", now=later)
    slow = t.burn_rate("interactive", "slow", now=later)
    assert fast == 0.0
    assert slow == pytest.approx((1 / 100) / 0.001)  # 10x
    # ... and after the slow window rolls past, the budget recovers
    much_later = later + t.slow_window_s + BUCKET_S
    t.record("interactive", ok=True, latency_s=0.01, now=much_later)
    assert t.burn_rate("interactive", "slow", now=much_later) == 0.0
    assert t.budget_remaining("interactive", now=much_later) == 1.0


def test_latency_target_counts_slow_successes_as_bad():
    t = _tracker()
    now = 2000.0
    t.record("interactive", ok=True, latency_s=0.9, now=now)  # > 0.5s
    t.record("interactive", ok=True, latency_s=0.1, now=now)
    assert t.burn_rate("interactive", "fast", now=now) == \
        pytest.approx(0.5 / 0.001)


def test_breach_hook_fires_once_with_hysteresis():
    t = _tracker(min_events=5)
    fired = []
    t.on_breach(lambda name, fast, slow: fired.append((name, fast, slow)))
    now = 3000.0
    for i in range(20):
        t.record("interactive", ok=False, now=now + i * 0.01)
    t.sweep(now=now + 1.0)
    t.sweep(now=now + 2.0)  # still breached: must NOT re-fire
    assert len(fired) == 1
    name, fast, slow = fired[0]
    assert name == "interactive" and fast >= t.breach_fast
    assert t._series["interactive"].m_breaches.value == 1


def test_slo_gauges_reach_registry_and_prom():
    registry = metrics.Registry()
    t = _tracker(registry=registry)
    now = 4000.0
    t.record("interactive", ok=False, now=now)
    t.sweep(now=now)
    assert registry.get("slo/interactive/burn_rate").value > 0
    assert registry.get("slo/interactive/budget_remaining") is not None
    prom = metrics.prometheus_text(registry)
    assert "gethsharding_slo_interactive_burn_rate" in prom
    assert "gethsharding_slo_interactive_breaches_total" in prom


def test_objective_env_overrides(monkeypatch):
    monkeypatch.setenv("GETHSHARDING_SLO_INTERACTIVE_P99_MS", "250")
    monkeypatch.setenv("GETHSHARDING_SLO_INTERACTIVE_AVAILABILITY",
                       "0.95")
    objectives = slo.default_objectives()
    assert objectives["interactive"].latency_target_s == \
        pytest.approx(0.25)
    assert objectives["interactive"].availability == 0.95
    # all three admission classes + integrity + the light-client DAS
    # sampling tier exist
    assert set(objectives) == {"interactive", "bulk_audit",
                               "catchup_replay", "das_light",
                               "integrity"}


def test_serving_records_slo_events(fresh_slo):
    """The serving tier marks every completed request good with its
    end-to-end latency — visible as slo/<class> counters."""
    serving = ServingSigBackend(PythonSigBackend(),
                                ServingConfig(flush_us=200))
    try:
        digest, sig, want = _ecdsa_cases(1, b"slo-serving")[0]
        assert serving.ecrecover_addresses([digest], [sig]) == [want]
    finally:
        serving.close()
    assert fresh_slo._series["interactive"].m_good.value >= 1
    assert fresh_slo._series["interactive"].latency.count >= 1


def test_queue_shed_and_expiry_burn_victim_class_budget(fresh_slo):
    """Displacement and class-deadline expiry inside the admission
    queue charge the VICTIM class's error budget — overload is exactly
    what the burn-rate plane exists to see."""
    from gethsharding_tpu.crypto.keccak import keccak256
    from gethsharding_tpu.serving import (
        AdmissionQueue,
        ClassDeadlineExceeded,
        Request,
        ServingOverloadError,
    )
    from gethsharding_tpu.serving.classes import (
        CLASS_CATCHUP,
        ClassPolicy,
        default_policies,
    )

    def req(klass):
        return Request("ecrecover_addresses",
                       ((keccak256(b"q"),), (b"\x00" * 65,)), 1,
                       klass=klass)

    queue = AdmissionQueue(cap_rows=2, policy="shed", max_batch=2,
                           flush_us=1_000_000)
    victims = [req(CLASS_CATCHUP) for _ in range(2)]
    for request in victims:
        queue.put(request)
    queue.put(req("interactive"))  # displaces the newest catchup
    with pytest.raises(ServingOverloadError):
        victims[-1].future.result(timeout=1)
    assert fresh_slo._series[CLASS_CATCHUP].m_bad.value == 1
    assert fresh_slo.burn_rate(CLASS_CATCHUP, "fast") > 0

    policies = default_policies()
    policies[CLASS_CATCHUP] = ClassPolicy(
        CLASS_CATCHUP, priority=2, weight=1, flush_mult=8.0,
        deadline_s=0.01)
    expiring = AdmissionQueue(cap_rows=8, max_batch=8,
                              flush_us=1_000_000, policies=policies)
    stale = req(CLASS_CATCHUP)
    expiring.put(stale)
    time.sleep(0.05)
    done = []
    t = __import__("threading").Thread(
        target=lambda: done.append(expiring.take_batch()), daemon=True)
    t.start()
    with pytest.raises(ClassDeadlineExceeded):
        stale.future.result(timeout=5)
    assert fresh_slo._series[CLASS_CATCHUP].m_bad.value == 2
    expiring.close()
    t.join(timeout=5)


def test_soundness_violation_burns_integrity_budget(fresh_slo):
    from gethsharding_tpu.resilience import SoundnessViolation
    from gethsharding_tpu.resilience.soundness import SpotCheckSigBackend

    class LyingBackend(PythonSigBackend):
        name = "liar"

        def ecrecover_addresses(self, digests, sigs65):
            out = super().ecrecover_addresses(digests, sigs65)
            return [None] * len(out)  # silently wrong

    audited = SpotCheckSigBackend(LyingBackend(), rate=1.0, rows=1,
                                  registry=metrics.Registry())
    digest, sig, want = _ecdsa_cases(1, b"slo-integrity")[0]
    with pytest.raises(SoundnessViolation):
        audited.ecrecover_addresses([digest], [sig])
    series = fresh_slo._series["integrity"]
    assert series.m_bad.value == 1
    assert fresh_slo.burn_rate("integrity", "fast") > 0


# == the closed loop: breaker trip moves the burn rate ======================


def test_seeded_breaker_trip_moves_interactive_burn_rate(fresh_slo):
    """ACCEPTANCE: a seeded chaos schedule trips replica r0's breaker;
    the failed attempts burn the interactive class's error budget, so
    the burn-rate gauge measurably rises even though failover answers
    every caller correctly."""
    from gethsharding_tpu.resilience.breaker import (CircuitBreaker,
                                                     FailoverSigBackend)
    from gethsharding_tpu.resilience.chaos import (ChaosSchedule,
                                                   ChaosSigBackend)

    registry = metrics.Registry()
    schedule = ChaosSchedule(seed=7,
                             rules={"backend.ecrecover_addresses": 3})
    r0_serving = ServingSigBackend(
        ChaosSigBackend(PythonSigBackend(), schedule),
        ServingConfig(flush_us=200), registry=registry)
    r1_serving = ServingSigBackend(PythonSigBackend(),
                                   ServingConfig(flush_us=200),
                                   registry=registry)
    router = FleetRouter([
        Replica("c0", FailoverSigBackend(
            r0_serving, PythonSigBackend(),
            breaker=CircuitBreaker(name="slo-c0", fault_threshold=3,
                                   reset_s=60.0)), registry=registry),
        Replica("c1", FailoverSigBackend(
            r1_serving, PythonSigBackend(),
            breaker=CircuitBreaker(name="slo-c1")), registry=registry),
    ], health_interval_s=0.0, registry=registry)
    back = RouterSigBackend(router)
    try:
        before = fresh_slo.burn_rate("interactive", "fast")
        assert before == 0.0
        for digest, sig, want in _ecdsa_cases(8, b"slo-chaos"):
            # every answer stays correct (failover/fallback covers the
            # injected faults) — burn comes from the fleet's attempts
            assert back.ecrecover_addresses([digest], [sig]) == [want]
        assert schedule.injected.get("backend.ecrecover_addresses") == 3
        fresh_slo.sweep()
        after = fresh_slo.burn_rate("interactive", "fast")
        assert after > before
        assert fresh_slo._series["interactive"].m_bad.value >= 1
        gauge = fresh_slo._series["interactive"].g_fast
        assert gauge.value > 0
    finally:
        router.close()
        # router.close() closes the replica backends (and the serving
        # tiers under them)
