"""Fleet-scale serving: admission classes, tenant quotas, the shard-
aware router, and breaker-aware draining (gethsharding_tpu/fleet/ +
the reworked serving/queue.py).

Five contracts:

- CLASSES: the admission queue drains by weighted priority (bulk can
  never starve interactive, interactive can never fully starve bulk),
  sheds by class under overload (catchup first, interactive last),
  enforces per-tenant row quotas, and expires work past its class
  deadline — all with typed errors.
- LIFECYCLE: a closed queue fails fast (`QueueClosed`) for late and
  blocked putters alike; `chain_server`-style drain refuses new work
  with a typed "replica draining" error and strands no caller.
- ROUTING: consistent shard→replica affinity, least-loaded keyless
  routing, retry-on-next-replica on hang/trip/shed, the typed
  `AllReplicasDraining` when nothing accepts, and rebalance after a
  drained replica re-enters.
- DRAINING: a replica whose breaker trips (seeded chaos, no ad-hoc
  mocks) is marked draining, takes no new work, and re-enters only
  after its half-open differential probe re-promotes the primary.
- CLOSED LOOP (the acceptance bar): under a seeded chaos schedule that
  trips one replica's breaker mid-soak, zero requests are lost or
  mis-answered (every result verified against the known signer),
  interactive traffic stays within its latency SLO while catchup is
  shed first, and the replica re-enters the rotation.
"""

import threading
import time

import pytest

from gethsharding_tpu import metrics
from gethsharding_tpu.crypto import secp256k1 as ecdsa
from gethsharding_tpu.crypto.keccak import keccak256
from gethsharding_tpu.fleet import (
    AllReplicasDraining,
    FleetRouter,
    Replica,
    RouterSigBackend,
)
from gethsharding_tpu.serving.classes import (
    CLASS_BULK_AUDIT,
    CLASS_CATCHUP,
    CLASS_INTERACTIVE,
    ClassPolicy,
    admission_class,
    default_policies,
)
from gethsharding_tpu.resilience.breaker import (
    CircuitBreaker,
    FailoverSigBackend,
)
from gethsharding_tpu.resilience.chaos import ChaosSchedule, ChaosSigBackend
from gethsharding_tpu.serving import (
    AdmissionQueue,
    ClassDeadlineExceeded,
    QueueClosed,
    Request,
    ServingConfig,
    ServingOverloadError,
    ServingSigBackend,
    TenantQuotaExceeded,
)
from gethsharding_tpu.sigbackend import PythonSigBackend, SigBackend


def _registry() -> metrics.Registry:
    return metrics.Registry()


def _req(rows: int = 1, klass: str = CLASS_INTERACTIVE,
         tenant: str = "") -> Request:
    digests = tuple(keccak256(b"q-%d" % i) for i in range(rows))
    sigs = tuple(b"\x00" * 65 for _ in range(rows))
    return Request("ecrecover_addresses", (digests, sigs), rows,
                   klass=klass, tenant=tenant)


class SlowBackend(SigBackend):
    """Real results, controllable pace: every dispatch sleeps
    `delay_s` first (the load-shaping brake of the soak tests —
    results stay verifiable against the known signer)."""

    name = "slow"

    def __init__(self, inner, delay_s: float = 0.0):
        self.inner = inner
        self.delay_s = delay_s

    def _op(self, op, *args, **kwargs):
        if self.delay_s:
            time.sleep(self.delay_s)
        return getattr(self.inner, op)(*args, **kwargs)

    def ecrecover_addresses(self, digests, sigs65):
        return self._op("ecrecover_addresses", digests, sigs65)

    def bls_verify_aggregates(self, messages, agg_sigs, agg_pks):
        return self._op("bls_verify_aggregates", messages, agg_sigs,
                        agg_pks)

    def bls_verify_committees(self, messages, sig_rows, pk_rows,
                              pk_row_keys=None):
        return self._op("bls_verify_committees", messages, sig_rows,
                        pk_rows, pk_row_keys=pk_row_keys)

    def das_verify_samples(self, chunks, indices, proofs, roots):
        return self._op("das_verify_samples", chunks, indices, proofs,
                        roots)


def _ecdsa_cases(n: int):
    cases = []
    for i in range(n):
        priv = int.from_bytes(keccak256(b"fleet-%d" % i), "big") % ecdsa.N
        digest = keccak256(b"fleet-msg-%d" % i)
        cases.append((digest, ecdsa.sign(digest, priv).to_bytes65(),
                      ecdsa.priv_to_address(priv)))
    return cases


# == admission classes in the queue =========================================


def test_weighted_take_serves_every_class_its_share():
    """A 12-row batch over a 3-class backlog splits 8/3/1 by weight,
    interactive first — bulk cannot starve interactive AND interactive
    cannot fully starve bulk."""
    queue = AdmissionQueue(cap_rows=1024, max_batch=12, flush_us=0)
    for klass in (CLASS_CATCHUP, CLASS_BULK_AUDIT, CLASS_INTERACTIVE):
        for _ in range(20):
            queue.put(_req(1, klass=klass))
    batch, reason = queue.take_batch()
    assert reason == "full"
    counts = {}
    for request in batch:
        counts[request.klass] = counts.get(request.klass, 0) + 1
    assert counts == {CLASS_INTERACTIVE: 8, CLASS_BULK_AUDIT: 3,
                      CLASS_CATCHUP: 1}
    # priority order inside the batch: interactive rows lead
    assert batch[0].klass == CLASS_INTERACTIVE
    # a lone-class backlog takes the whole batch (weights only split
    # among NONEMPTY classes)
    queue2 = AdmissionQueue(cap_rows=1024, max_batch=8, flush_us=0)
    for _ in range(8):
        queue2.put(_req(1, klass=CLASS_CATCHUP))
    batch2, _ = queue2.take_batch()
    assert len(batch2) == 8


def test_shed_by_class_catchup_first_interactive_last():
    """At the cap, a higher-priority arrival displaces queued catchup
    (newest first) with a typed failure; same-or-lower priority is
    shed itself; interactive is never displaced."""
    queue = AdmissionQueue(cap_rows=8, policy="shed", max_batch=8,
                           flush_us=1_000_000)
    catchup = [_req(1, klass=CLASS_CATCHUP) for _ in range(8)]
    for request in catchup:
        queue.put(request)
    assert queue.depth_rows == 8

    interactive = _req(1)
    queue.put(interactive)  # displaces the NEWEST catchup request
    assert queue.depth_rows == 8
    with pytest.raises(ServingOverloadError, match="displaced by"):
        catchup[-1].future.result(timeout=1)
    assert not interactive.future.done()
    assert queue.shed_by_class[CLASS_CATCHUP] == 1

    with pytest.raises(ServingOverloadError, match="request shed"):
        queue.put(_req(1, klass=CLASS_CATCHUP))  # nothing lower: shed self
    assert queue.shed_by_class[CLASS_CATCHUP] == 2

    bulk = _req(1, klass=CLASS_BULK_AUDIT)
    queue.put(bulk)  # displaces catchup, not interactive
    assert queue.shed_by_class[CLASS_CATCHUP] == 3
    assert queue.shed_by_class[CLASS_INTERACTIVE] == 0
    assert not bulk.future.done()
    # drain: interactive + bulk survived, catchup thinned from the tail
    batch, _ = queue.take_batch()
    survivors = {request.klass for request in batch}
    assert CLASS_INTERACTIVE in survivors and CLASS_BULK_AUDIT in survivors


def test_tenant_quota_bounds_one_tenant():
    """A tenant at its quota is refused with `TenantQuotaExceeded`
    (counted); other tenants are unaffected; drain frees the quota."""
    queue = AdmissionQueue(cap_rows=64, max_batch=64, flush_us=0,
                           tenant_quota_rows=4)
    for _ in range(4):
        queue.put(_req(1, tenant="noisy"))
    with pytest.raises(TenantQuotaExceeded, match="noisy"):
        queue.put(_req(1, tenant="noisy"))
    queue.put(_req(1, tenant="quiet"))  # other tenants unaffected
    queue.put(_req(1))                  # untenanted traffic unaffected
    assert queue.quota_rejections == 1
    assert queue.tenant_rows("noisy") == 4
    queue.take_batch()
    assert queue.tenant_rows("noisy") == 0
    queue.put(_req(1, tenant="noisy"))  # drained: admitted again


def test_wfq_tenant_fairness_preserves_class_weighting():
    """The tenant-fair drain (deficit round-robin, PR 15) nests INSIDE
    the class-weighted drain: with two tenants queued in every class,
    the batch still splits 8/3/1 by class weight, and within the
    interactive share both tenants are served. (The starvation-bound
    and carried-deficit contracts live in test_fleet_frontend.py.)"""
    queue = AdmissionQueue(cap_rows=1024, max_batch=12, flush_us=0)
    for klass in (CLASS_CATCHUP, CLASS_BULK_AUDIT, CLASS_INTERACTIVE):
        for tenant in ("a", "b"):
            for _ in range(10):
                queue.put(_req(1, klass=klass, tenant=tenant))
    batch, reason = queue.take_batch()
    assert reason == "full"
    counts: dict = {}
    for request in batch:
        counts[request.klass] = counts.get(request.klass, 0) + 1
    assert counts == {CLASS_INTERACTIVE: 8, CLASS_BULK_AUDIT: 3,
                      CLASS_CATCHUP: 1}
    interactive_tenants = {r.tenant for r in batch
                           if r.klass == CLASS_INTERACTIVE}
    assert interactive_tenants == {"a", "b"}


def test_put_after_close_fails_fast():
    queue = AdmissionQueue(cap_rows=16, max_batch=16, flush_us=0)
    queue.close()
    with pytest.raises(QueueClosed):
        queue.put(_req(1))


def test_close_wakes_blocked_putter_with_queue_closed():
    """A putter blocked on a full queue must not hang across close():
    it fails fast with `QueueClosed`."""
    queue = AdmissionQueue(cap_rows=4, policy="block", max_batch=4,
                           flush_us=1_000_000)
    for _ in range(4):
        queue.put(_req(1))
    outcome: dict = {}

    def blocked_put():
        try:
            queue.put(_req(1))
            outcome["result"] = "enqueued"
        except QueueClosed:
            outcome["result"] = "closed"

    thread = threading.Thread(target=blocked_put)
    thread.start()
    time.sleep(0.1)
    assert thread.is_alive()  # genuinely blocked at the cap
    queue.close()
    thread.join(timeout=5)
    assert outcome["result"] == "closed"


def test_batcher_submit_after_close_is_queue_closed():
    serving = ServingSigBackend(PythonSigBackend(), registry=_registry())
    serving.close()
    with pytest.raises(QueueClosed):
        serving.submit("ecrecover_addresses", [keccak256(b"x")],
                       [b"\x00" * 65])


def test_class_deadline_expires_stale_requests():
    """A request past its class deadline fails with
    `ClassDeadlineExceeded` even when the queue then empties (the
    consumer must not strand it behind an indefinite wait)."""
    policies = default_policies()
    policies[CLASS_CATCHUP] = ClassPolicy(
        CLASS_CATCHUP, priority=2, weight=1, flush_mult=8.0,
        deadline_s=0.05)
    queue = AdmissionQueue(cap_rows=64, max_batch=64, flush_us=1_000_000,
                           policies=policies)
    stale = _req(1, klass=CLASS_CATCHUP)
    queue.put(stale)
    got: dict = {}

    def consume():
        got["batch"] = queue.take_batch()

    thread = threading.Thread(target=consume, daemon=True)
    thread.start()
    with pytest.raises(ClassDeadlineExceeded, match="expired"):
        stale.future.result(timeout=5)
    assert queue.expired_by_class[CLASS_CATCHUP] == 1
    assert queue.depth_rows == 0
    # the consumer is still serving: a fresh interactive request flows
    fresh = _req(1)
    queue.put(fresh)
    thread.join(timeout=5)
    assert not thread.is_alive()
    batch, _ = got["batch"]
    assert batch == [fresh]


def test_class_resolution_context_defaults_and_metrics():
    """Class resolution: explicit kwarg > thread context > per-op
    default; the per-class request counters attribute each."""
    registry = _registry()
    serving = ServingSigBackend(PythonSigBackend(),
                                ServingConfig(flush_us=500),
                                registry=registry)
    try:
        digest, sig = keccak256(b"cls"), b"\x00" * 65
        serving.ecrecover_addresses([digest], [sig])  # default interactive
        with admission_class(CLASS_CATCHUP, tenant="t9"):
            serving.ecrecover_addresses([digest], [sig])  # context
            serving.submit("ecrecover_addresses", [digest], [sig],
                           klass=CLASS_BULK_AUDIT).result(timeout=10)
        # das_verify defaults to bulk_audit by the per-op map
        assert serving.das_verify_samples([], [], [], []) == []
        base = "serving/ecrecover/class"
        assert registry.counter(
            f"{base}/{CLASS_INTERACTIVE}/requests").value == 1
        assert registry.counter(
            f"{base}/{CLASS_CATCHUP}/requests").value == 1
        assert registry.counter(
            f"{base}/{CLASS_BULK_AUDIT}/requests").value == 1
        assert registry.counter(
            f"serving/das_verify/class/{CLASS_BULK_AUDIT}/requests"
        ).value == 1
        # the classed() facade pins a class without the context
        classed = serving.classed(CLASS_CATCHUP, tenant="t10")
        classed.ecrecover_addresses([digest], [sig])
        assert registry.counter(
            f"{base}/{CLASS_CATCHUP}/requests").value == 2
    finally:
        serving.close()


# == the router =============================================================


def _plain_replicas(n: int, registry) -> list:
    return [Replica(f"r{i}", PythonSigBackend(), probe=None,
                    registry=registry)
            for i in range(n)]


def test_affinity_stable_and_rebalances_after_reentry():
    """The same key routes to the same replica order; draining the
    preferred replica moves ONLY its keys; re-entry moves them back."""
    registry = _registry()
    router = FleetRouter(_plain_replicas(3, registry),
                         health_interval_s=0.0, registry=registry)
    orders = {key: [r.name for r in router.route(key)]
              for key in ("shard-0", "shard-1", "shard-2", "shard-3")}
    for key, order in orders.items():
        assert [r.name for r in router.route(key)] == order  # stable
    victim = orders["shard-0"][0]
    router.drain(victim)
    assert router._replica(victim).state == "draining"
    moved = [r.name for r in router.route("shard-0")]
    assert moved == orders["shard-0"][1:]  # only the head drops out
    for key, order in orders.items():
        expect = [name for name in order if name != victim]
        assert [r.name for r in router.route(key)] == expect
    router.undrain(victim)
    assert [r.name for r in router.route("shard-0")] == orders["shard-0"]
    assert router._replica(victim).reentries == 1


def test_keyless_routing_prefers_least_in_flight():
    registry = _registry()
    replicas = _plain_replicas(2, registry)
    router = FleetRouter(replicas, health_interval_s=0.0,
                         registry=registry)
    replicas[0].in_flight = 5
    assert [r.name for r in router.route()] == ["r1", "r0"]


def test_replica_hang_watchdog_fires_router_retries_next():
    """A seeded dispatch hang wedges replica r0's serving dispatcher;
    the watchdog fails the batch with DeadlineExceeded and the router
    answers from r1 — the caller never sees the hang."""
    registry = _registry()
    schedule = ChaosSchedule(seed=7,
                             rules={"dispatch.ecrecover_addresses": 1})
    hung = ServingSigBackend(
        ChaosSigBackend(PythonSigBackend(), schedule, hang_s=1.5),
        ServingConfig(flush_us=200, watchdog_s=0.15),
        registry=registry)
    healthy = ServingSigBackend(PythonSigBackend(),
                                ServingConfig(flush_us=200),
                                registry=registry)
    router = FleetRouter(
        [Replica("r0", hung, probe=None, registry=registry),
         Replica("r1", healthy, probe=None, registry=registry)],
        health_interval_s=0.0, registry=registry)
    back = RouterSigBackend(router)
    (digest, sig, want), = _ecdsa_cases(1)
    try:
        t0 = time.monotonic()
        assert back.ecrecover_addresses([digest], [sig]) == [want]
        assert time.monotonic() - t0 < 1.2  # did not sit out the hang
        assert registry.counter("fleet/replica/r0/failures").value == 1
        assert registry.counter("fleet/router/failovers").value == 1
        assert schedule.injected.get("dispatch.ecrecover_addresses") == 1
    finally:
        hung.close()
        healthy.close()


def test_breaker_trip_drains_probe_repromotes_and_reenters():
    """Seeded chaos faults trip r0's breaker mid-traffic: every answer
    stays correct (fallback-served), the router marks r0 draining, and
    after the cooldown the router's probe runs the half-open
    differential — r0 re-enters only once the breaker re-closes."""
    registry = _registry()
    schedule = ChaosSchedule(seed=7,
                             rules={"backend.ecrecover_addresses": 3})
    serving0 = ServingSigBackend(
        ChaosSigBackend(PythonSigBackend(), schedule),
        ServingConfig(flush_us=200), registry=registry)
    serving1 = ServingSigBackend(PythonSigBackend(),
                                 ServingConfig(flush_us=200),
                                 registry=registry)
    breaker0 = CircuitBreaker(name="fleet-r0", fault_threshold=3,
                              reset_s=0.2, registry=registry)
    r0 = Replica("r0",
                 FailoverSigBackend(serving0, PythonSigBackend(),
                                    breaker=breaker0, registry=registry),
                 registry=registry)
    r1 = Replica("r1",
                 FailoverSigBackend(serving1, PythonSigBackend(),
                                    breaker=CircuitBreaker(
                                        name="fleet-r1",
                                        registry=registry),
                                    registry=registry),
                 registry=registry)
    router = FleetRouter([r0, r1], health_interval_s=0.0,
                         registry=registry)
    back = RouterSigBackend(router)
    cases = _ecdsa_cases(8)
    try:
        # keyless traffic prefers idle r0: the first three calls eat the
        # three seeded faults (each served correctly from the scalar
        # fallback), tripping the breaker
        for digest, sig, want in cases[:3]:
            assert back.ecrecover_addresses([digest], [sig]) == [want]
        assert breaker0.state_name == "open"
        router.refresh(force=True)
        assert r0.state == "draining"
        assert r0.drain_events == 1
        # drained: traffic lands on r1, still correct
        for digest, sig, want in cases[3:6]:
            assert back.ecrecover_addresses([digest], [sig]) == [want]
        assert r0.state == "draining"  # cooldown not elapsed
        # cooldown elapses; the router's refresh-side probe becomes the
        # half-open differential, matches, and re-promotes the primary
        time.sleep(0.25)
        deadline = time.monotonic() + 5
        while r0.state != "healthy" and time.monotonic() < deadline:
            router.refresh(force=True)
            time.sleep(0.02)
        assert r0.state == "healthy"
        assert breaker0.state_name == "closed"
        assert r0.reentries == 1
        for digest, sig, want in cases[6:]:
            assert back.ecrecover_addresses([digest], [sig]) == [want]
        assert schedule.injected.get("backend.ecrecover_addresses") == 3
    finally:
        serving0.close()
        serving1.close()


def test_all_replicas_draining_is_typed_and_fast():
    registry = _registry()
    router = FleetRouter(_plain_replicas(2, registry),
                         health_interval_s=0.0, registry=registry)
    router.drain("r0")
    router.drain("r1")
    with pytest.raises(AllReplicasDraining):
        router.call("ecrecover_addresses", [keccak256(b"x")],
                    [b"\x00" * 65])
    assert registry.counter("fleet/router/all_draining").value >= 1


def test_overloaded_replica_spills_to_next():
    """A shed on one replica's admission queue is routing information:
    the router retries the next replica instead of failing the caller."""
    registry = _registry()
    # r0's serving tier: zero-capacity-ish shed policy with a wedged
    # dispatcher brake so the queue stays full
    slow = SlowBackend(PythonSigBackend(), delay_s=0.2)
    serving0 = ServingSigBackend(
        slow, ServingConfig(max_batch=1, flush_us=0, queue_cap=1,
                            policy="shed"),
        registry=registry)
    serving1 = ServingSigBackend(PythonSigBackend(),
                                 ServingConfig(flush_us=200),
                                 registry=registry)
    router = FleetRouter(
        [Replica("r0", serving0, probe=None, registry=registry),
         Replica("r1", serving1, probe=None, registry=registry)],
        health_interval_s=0.0, registry=registry)
    back = RouterSigBackend(router)
    (digest, sig, want), = _ecdsa_cases(1)
    try:
        # wedge r0: fill the dispatcher, the double-buffer slot and the
        # queue until its shed policy fires — the flusher drains the
        # 1-row queue into the pipeline, so a few submits are needed
        # before admission actually refuses
        filler, wedged = [], False
        for i in range(8):
            try:
                filler.append(serving0.submit(
                    "ecrecover_addresses", [keccak256(b"fill-%d" % i)],
                    [b"\x00" * 65]))
            except ServingOverloadError:
                wedged = True
                break
        assert wedged, "r0 never reached its shed point"
        # route: r0 preferred (idle by in_flight), sheds, spills to r1
        assert back.ecrecover_addresses([digest], [sig]) == [want]
        assert registry.counter("serving/ecrecover/shed").value >= 2
        assert registry.counter("fleet/router/failovers").value >= 1
        for future in filler:
            future.result(timeout=10)
    finally:
        serving0.close()
        serving1.close()


# == chain_server drain lifecycle ===========================================


def test_rpc_server_drain_refuses_new_work_and_strands_no_caller():
    """`shard_drain` flips the replica to draining: health reports it,
    new verification RPCs fail with the typed 'replica draining' error,
    and already-queued serving futures resolve or fail cleanly."""
    from gethsharding_tpu.rpc.client import RPCClient, RPCError
    from gethsharding_tpu.rpc import codec
    from gethsharding_tpu.rpc.server import RPCServer
    from gethsharding_tpu.smc.chain import SimulatedMainchain

    serving = ServingSigBackend(PythonSigBackend(),
                                ServingConfig(flush_us=500),
                                registry=_registry())
    failover = FailoverSigBackend(serving, PythonSigBackend(),
                                  breaker=CircuitBreaker(
                                      name="drain-test",
                                      registry=_registry()),
                                  registry=_registry())
    server = RPCServer(SimulatedMainchain(), sig_backend=failover)
    server.start()
    client = RPCClient(*server.address)
    try:
        (digest, sig, want), = _ecdsa_cases(1)
        out = client.call("shard_ecrecover", [codec.enc_bytes(digest)],
                          [codec.enc_bytes(sig)])
        assert out == [codec.enc_bytes(want)]
        health = client.call("shard_health")
        assert health["draining"] is False
        assert health["breaker"] == "closed"
        assert health["serving"] is not None

        drained = client.call("shard_drain")
        assert drained["draining"] is True
        assert client.call("shard_health")["draining"] is True
        with pytest.raises(RPCError, match="replica draining"):
            client.call("shard_ecrecover", [codec.enc_bytes(digest)],
                        [codec.enc_bytes(sig)])
        # non-verification RPCs still answer during the drain
        assert isinstance(client.call("shard_blockNumber"), int)
    finally:
        client.close()
        server.stop()
        serving.close()


def test_rpc_tenant_only_param_still_charges_quota():
    """A caller passing `tenant` WITHOUT `klass` on shard_ecrecover
    must still be charged against its quota (regression: the tenant tag
    used to be dropped unless a class rode along)."""
    from gethsharding_tpu.rpc.client import RPCClient, RPCError
    from gethsharding_tpu.rpc import codec
    from gethsharding_tpu.rpc.server import RPCServer
    from gethsharding_tpu.smc.chain import SimulatedMainchain

    # a LONG flush deadline keeps the filler row sitting in the queue
    # (no full flush at max_batch 128, no deadline flush for ~1 s), so
    # the tenant's quota occupancy is deterministic — no pipeline race
    serving = ServingSigBackend(
        PythonSigBackend(),
        ServingConfig(max_batch=128, flush_us=1_000_000, queue_cap=64,
                      tenant_quota_rows=1),
        registry=_registry())
    server = RPCServer(SimulatedMainchain(), sig_backend=serving)
    server.start()
    client = RPCClient(*server.address)
    try:
        (digest, sig, _), = _ecdsa_cases(1)
        filler = serving.submit("ecrecover_addresses",
                                [keccak256(b"qf")], [b"\x00" * 65],
                                tenant="t9")
        queue = serving.batcher._queues["ecrecover_addresses"]
        assert queue.tenant_rows("t9") == 1
        with pytest.raises(RPCError, match="quota"):
            client.call("shard_ecrecover", [codec.enc_bytes(digest)],
                        [codec.enc_bytes(sig)], None, "t9")
        # a different tenant is admitted (and coalesces with the filler
        # once the deadline flush fires)
        out = client.call("shard_ecrecover", [codec.enc_bytes(digest)],
                          [codec.enc_bytes(sig)], None, "other")
        assert out is not None
        filler.result(timeout=10)
    finally:
        client.close()
        server.stop()
        serving.close()


def test_router_over_rpc_replicas_drains_and_fails_over():
    """Cross-process shape: two RPCServer replicas behind
    `RpcReplicaBackend`s; draining one routes traffic to the other
    (the typed draining refusal is retried, not surfaced)."""
    from gethsharding_tpu.fleet.router import RpcReplicaBackend
    from gethsharding_tpu.rpc.server import RPCServer
    from gethsharding_tpu.smc.chain import SimulatedMainchain

    registry = _registry()
    servers, replicas = [], []
    for i in range(2):
        serving = ServingSigBackend(PythonSigBackend(),
                                    ServingConfig(flush_us=500),
                                    registry=_registry())
        server = RPCServer(SimulatedMainchain(), sig_backend=serving)
        server.start()
        servers.append((server, serving))
        backend = RpcReplicaBackend.dial(*server.address)
        replicas.append(Replica(f"rpc{i}", backend,
                                health=backend.health, probe=None,
                                registry=registry))
    router = FleetRouter(replicas, health_interval_s=0.0,
                         registry=registry)
    back = RouterSigBackend(router)
    cases = _ecdsa_cases(4)
    try:
        for digest, sig, want in cases[:2]:
            assert back.ecrecover_addresses(
                [digest], [sig],) == [want]
        # drain replica 0 THROUGH the control plane
        replicas[0].backend.drain()
        router.refresh(force=True)
        assert replicas[0].state == "draining"
        for digest, sig, want in cases[2:]:
            assert back.ecrecover_addresses([digest], [sig]) == [want]
        # the drained replica took nothing new
        assert servers[0][0].draining is True
    finally:
        for replica in replicas:
            replica.backend.close()
        for server, serving in servers:
            server.stop()
            serving.close()


# == the long traffic-model soak (slow tier) ================================


@pytest.mark.slow  # ~25 s: the full diurnal/hot-shard/herd traffic model
def test_fleet_traffic_model_soak_slow():
    """The scripts/serving_stress.py traffic-model soak, end to end:
    diurnal load curve, hot-shard skew, a thundering-herd burst and a
    seeded mid-soak breaker trip — exit 0 means zero divergence, zero
    interactive sheds, SLOs held, and the tripped replica re-entered."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "scripts", "serving_stress.py"),
         "--replicas", "3", "--clients", "16", "--duration", "18",
         "--max-batch", "16", "--queue-cap", "16", "--policy", "shed",
         "--classes", "interactive=8,bulk_audit=4,catchup_replay=4",
         "--chaos-trip", "10", "--hot-shard", "0.9", "--diurnal-s", "8",
         "--herd-at", "6", "--slo-interactive-ms", "8000"],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    import json as _json

    summary = _json.loads(proc.stdout.strip().splitlines()[-1])
    assert summary["divergences"] == 0
    assert summary["drain_events"] >= 1 and summary["reentered"]


# == the closed-loop acceptance soak ========================================


def test_closed_loop_drain_soak_acceptance():
    """The ISSUE 8 acceptance bar, end to end: a 3-replica fleet under
    mixed-class traffic rides a seeded chaos schedule that trips one
    replica's breaker mid-soak. Asserts: the router marks it draining;
    ZERO requests are lost or mis-answered (every interactive result
    verified against the known signer); interactive p99 stays within
    its SLO and sees zero sheds while catchup_replay is shed first;
    and the replica re-enters after half-open re-promotion."""
    registry = _registry()
    n_replicas = 3
    # r0's chaos: a seeded run of consecutive device-dispatch faults a
    # little into the soak — each absorbed by the fallback (answers stay
    # correct), together tripping the breaker. The window is wider than
    # the fault threshold because caller-side outcomes interleave across
    # threads: a pre-window dispatch resolving late can reset the
    # consecutive count once, not eight times.
    schedule = ChaosSchedule(
        seed=11,
        rules={"backend.ecrecover_addresses":
               lambda idx: 10 <= idx < 18})
    servings, replicas = [], []
    for i in range(n_replicas):
        inner = SlowBackend(PythonSigBackend(), delay_s=0.002)
        if i == 0:
            inner = ChaosSigBackend(inner, schedule)
        serving = ServingSigBackend(
            inner,
            ServingConfig(max_batch=16, flush_us=300, queue_cap=16,
                          policy="shed"),
            registry=_registry())
        servings.append(serving)
        breaker = CircuitBreaker(name=f"soak-r{i}", fault_threshold=3,
                                 reset_s=0.3, registry=registry)
        replicas.append(Replica(
            f"r{i}",
            FailoverSigBackend(serving, PythonSigBackend(),
                               breaker=breaker, registry=registry),
            registry=registry))
    router = FleetRouter(replicas, health_interval_s=0.05,
                         registry=registry)
    back = RouterSigBackend(router)

    cases = _ecdsa_cases(64)
    divergences: list = []
    interactive_lat: list = []
    interactive_shed = [0]
    catchup_shed = [0]
    stop = threading.Event()

    def interactive_client(c: int) -> None:
        for r in range(30):
            digest, sig, want = cases[(c * 30 + r) % len(cases)]
            t0 = time.monotonic()
            try:
                got = back.ecrecover_addresses([digest], [sig])
            except ServingOverloadError:
                interactive_shed[0] += 1
                continue
            interactive_lat.append(time.monotonic() - t0)
            if got != [want]:
                divergences.append((c, r, got))
                stop.set()
                return
            time.sleep(0.002)

    def catchup_flood() -> None:
        # bursty backfill with hot-shard skew: 8-row requests, many
        # concurrent threads, all keyed to ONE affinity — the hot
        # replica's 16-row queue overflows and catchup sheds first (the
        # retry ladder spills survivors to the colder replicas)
        for r in range(12):
            if stop.is_set():
                return
            rows = [cases[(r + j) % len(cases)] for j in range(8)]
            try:
                router.call(
                    "ecrecover_addresses",
                    [c[0] for c in rows], [c[1] for c in rows],
                    affinity="hot-shard", klass=CLASS_CATCHUP)
            except (ServingOverloadError, AllReplicasDraining):
                catchup_shed[0] += 1

    threads = ([threading.Thread(target=interactive_client, args=(c,))
                for c in range(4)]
               + [threading.Thread(target=catchup_flood)
                  for _ in range(6)])
    try:
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join(timeout=120)
        assert not any(t.is_alive() for t in threads), "hung client"
        assert divergences == [], divergences

        r0 = replicas[0]
        # the seeded faults fired and tripped r0 into draining mid-soak
        assert schedule.injected.get("backend.ecrecover_addresses", 0) >= 3
        assert r0.drain_events >= 1, router.states()

        # r0 re-enters after its half-open differential re-promotion
        deadline = time.monotonic() + 10
        while r0.state != "healthy" and time.monotonic() < deadline:
            router.refresh(force=True)
            time.sleep(0.05)
        assert r0.state == "healthy", router.states()
        assert r0.reentries >= 1

        # deterministic overload coda: the organic flood's pressure is
        # timing-dependent — on a fast host (or when an early r0 trip
        # spaces arrivals behind retry backoffs) 12 rounds can drain
        # without ever overflowing a 16-row queue, and an overload
        # phase that never overloaded would flake the shed assertions
        # instead of testing them. If nothing shed organically, drive
        # one concentrated catchup burst at a single replica beyond its
        # queue + double-buffer capacity (16 queued + 16 slotted + 16
        # executing = 48 rows; 10x8 = 80 arriving at once MUST shed),
        # so shed-by-class is always exercised.
        if sum(s.batcher.shed_by_class()[CLASS_CATCHUP]
               for s in servings) + catchup_shed[0] == 0:
            def burst(k: int) -> None:
                rows = [cases[(k + j) % len(cases)] for j in range(8)]
                try:
                    servings[1].classed(CLASS_CATCHUP).ecrecover_addresses(
                        [c[0] for c in rows], [c[1] for c in rows])
                except ServingOverloadError:
                    catchup_shed[0] += 1
            burst_threads = [threading.Thread(target=burst, args=(k,))
                             for k in range(10)]
            for thread in burst_threads:
                thread.start()
            for thread in burst_threads:
                thread.join(timeout=60)

        # shed-by-class: interactive rode through untouched; the
        # catchup flood absorbed the overload
        replica_sheds = {
            klass: sum(s.batcher.shed_by_class()[klass]
                       for s in servings)
            for klass in (CLASS_INTERACTIVE, CLASS_BULK_AUDIT,
                          CLASS_CATCHUP)}
        assert interactive_shed[0] == 0
        assert replica_sheds[CLASS_INTERACTIVE] == 0, replica_sheds
        # the overload evidence can land replica-side (displacement /
        # arrival shed) or caller-side (the retry ladder exhausted) —
        # shed by either side counts
        assert replica_sheds[CLASS_CATCHUP] + catchup_shed[0] > 0, \
            (replica_sheds, catchup_shed)

        # interactive latency SLO (generous for hermetic CPU)
        interactive_lat.sort()
        p99 = interactive_lat[int(0.99 * (len(interactive_lat) - 1))]
        assert p99 < 2.0, f"interactive p99 {p99:.3f}s"

        # zero lost: every interactive request either verified or was
        # counted shed (and interactive sheds were zero)
        assert len(interactive_lat) == 4 * 30
    finally:
        stop.set()
        for serving in servings:
            serving.close()
