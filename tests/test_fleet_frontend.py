"""Fleet frontend hardening: full wire planes, hedged dispatch, the
standalone frontend process, and the hard interleavings
(ISSUE 15 / gethsharding_tpu/fleet/frontend.py + router hedging).

Contracts:

- WIRE PLANES: `RpcReplicaBackend` serves the FULL SigBackend surface
  over JSON-RPC — the committee plane (`shard_verifyCommittees`) and
  the DAS sample plane (`shard_dasVerify`) return verdicts
  bit-identical to the scalar reference, hostile rows included, and
  the plane codecs roundtrip.
- TRANSPORT CHAOS: seeded ``fleet.transport`` delay/partition modes
  stall or cut a replica's wire deterministically; invalid mode/seam
  combinations fail fast.
- HEDGING: an interactive call outliving its hedge delay is re-issued
  to the next affinity replica, first verdict wins, losers are
  discarded with accounting; bulk traffic never hedges; hedges ride
  untenanted (quota idempotence); a hedged pair detecting the same
  corruption charges the audit-fault path ONCE; a replica draining
  while its hedge is in flight finishes cleanly; a sustained wasted-
  rate storm latches and lands in the flight recorder.
- FRONTEND: the standalone server routes every plane, orchestrates
  drains, refuses typed while draining, and an actor dialing it
  RECOVERS through its retry policy after a frontend restart
  mid-request (typed error in between, redial after).
- WFQ: inside one admission class, a heavy tenant cannot starve a
  light one (deficit round-robin; see also test_fleet.py's queue
  suite).
"""

import threading
import time

import pytest

from gethsharding_tpu import metrics
from gethsharding_tpu.crypto import bn256 as bls
from gethsharding_tpu.crypto import secp256k1 as ecdsa
from gethsharding_tpu.crypto.keccak import keccak256
from gethsharding_tpu.fleet import (
    FleetRouter,
    FrontendServer,
    Replica,
    RouterSigBackend,
    build_frontend,
)
from gethsharding_tpu.fleet.router import RpcReplicaBackend
from gethsharding_tpu.resilience.chaos import (
    ChaosSchedule,
    ChaosSigBackend,
    InjectedFault,
    TransportChaos,
    parse_spec,
    transport_disturb,
)
from gethsharding_tpu.resilience.errors import SoundnessViolation
from gethsharding_tpu.resilience.soundness import SpotCheckSigBackend
from gethsharding_tpu.rpc import codec
from gethsharding_tpu.rpc.client import RPCClient, RPCError
from gethsharding_tpu.rpc.server import RPCServer
from gethsharding_tpu.serving import (
    AdmissionQueue,
    Request,
    ServingConfig,
    ServingSigBackend,
)
from gethsharding_tpu.serving.classes import CLASS_BULK_AUDIT
from gethsharding_tpu.sigbackend import PythonSigBackend
from gethsharding_tpu.smc.chain import SimulatedMainchain


def _registry() -> metrics.Registry:
    return metrics.Registry()


def _ecdsa_cases(n: int, tag: bytes = b"ff"):
    cases = []
    for i in range(n):
        priv = int.from_bytes(keccak256(tag + b"-%d" % i), "big") % ecdsa.N
        digest = keccak256(tag + b"-msg-%d" % i)
        cases.append((digest, ecdsa.sign(digest, priv).to_bytes65(),
                      ecdsa.priv_to_address(priv)))
    return cases


def _committee_rows(n: int = 3, tamper: int = 1):
    msgs, sig_rows, pk_rows, keys = [], [], [], []
    for i in range(n):
        tag = b"ffc-%d" % i
        ks = [bls.bls_keygen(tag + bytes([j])) for j in range(2)]
        sigs = [bls.bls_sign(tag, sk) for sk, _ in ks]
        if i == tamper:
            sigs[0] = bls.bls_sign(b"tampered", ks[0][0])
        msgs.append(tag)
        sig_rows.append(sigs)
        pk_rows.append([pk for _, pk in ks])
        keys.append((i, i * 7))
    return msgs, sig_rows, pk_rows, keys


def _das_rows():
    from gethsharding_tpu.das.erasure import extend_body
    from gethsharding_tpu.das.proofs import (chunk_leaf, merkle_levels,
                                             merkle_proof)

    xb = extend_body(b"\x07" * 9000, parity_ratio=0.5)
    levels = merkle_levels([chunk_leaf(c) for c in xb.chunks])
    root = levels[-1][0]
    good0, good1 = merkle_proof(levels, 0), merkle_proof(levels, 1)
    # valid, valid, withheld, truncated proof, wrong root
    chunks = [xb.chunks[0], xb.chunks[1], b"", xb.chunks[1],
              xb.chunks[0]]
    indices = [0, 1, 1, 1, 0]
    proofs = [good0, good1, (), good1[:-1], good0]
    roots = [root, root, root, root, b"\x02" * 32]
    return chunks, indices, proofs, roots


@pytest.fixture
def rpc_replica():
    """One chain_server-shaped RPC replica + its dialed backend."""
    serving = ServingSigBackend(PythonSigBackend(),
                                ServingConfig(flush_us=200),
                                registry=_registry())
    server = RPCServer(SimulatedMainchain(), sig_backend=serving)
    server.start()
    backend = RpcReplicaBackend.dial(*server.address)
    yield backend
    backend.close()
    server.stop()
    serving.close()


# == the wire planes ========================================================


def test_committee_plane_over_the_wire_bit_identical(rpc_replica):
    """`shard_verifyCommittees` through a real RPC replica returns the
    scalar reference's verdicts bit-for-bit — tampered and empty rows
    included — and the async face keeps the VerdictFuture contract."""
    msgs, sig_rows, pk_rows, keys = _committee_rows()
    want = PythonSigBackend().bls_verify_committees(msgs, sig_rows,
                                                    pk_rows)
    assert want == [True, False, True]
    got = rpc_replica.bls_verify_committees(msgs, sig_rows, pk_rows,
                                            pk_row_keys=keys)
    assert got == want
    # keyless + keyed agree; an empty committee row is a rejection
    assert rpc_replica.bls_verify_committees(msgs, sig_rows,
                                             pk_rows) == want
    assert rpc_replica.bls_verify_committees([b"m"], [[]], [[]]) == [False]
    future = rpc_replica.bls_verify_committees_async(
        msgs, sig_rows, pk_rows, pk_row_keys=keys)
    assert future.done() and future.result() == want


def test_das_plane_over_the_wire_bit_identical(rpc_replica):
    """`shard_dasVerify` verdicts equal the scalar reference — hostile
    rows (withheld chunk, truncated proof, wrong root) cost a False,
    never an error, exactly as in-process."""
    chunks, indices, proofs, roots = _das_rows()
    want = PythonSigBackend().das_verify_samples(chunks, indices,
                                                 proofs, roots)
    assert want == [True, True, False, False, False]
    got = rpc_replica.das_verify_samples(chunks, indices, proofs, roots)
    assert got == want
    assert rpc_replica.das_verify_samples([], [], [], []) == []


def test_plane_codecs_roundtrip():
    msgs, sig_rows, pk_rows, keys = _committee_rows()
    assert codec.dec_g1_rows(codec.enc_g1_rows(sig_rows)) == sig_rows
    assert codec.dec_g2_rows(codec.enc_g2_rows(pk_rows)) == pk_rows
    # pk-row keys ship as repr strings: injective for the int-tuple
    # keys the notary uses, None preserved, stable across processes
    wire = codec.enc_pk_row_keys([None, (1, 2), ("a", 3)])
    assert wire[0] is None and wire[1] != wire[2]
    assert codec.enc_pk_row_keys(None) is None
    chunks, indices, proofs, roots = _das_rows()
    enc = codec.enc_das_call(chunks, indices, proofs, roots)
    dec = codec.dec_das_call(*enc)
    assert dec == (list(chunks), list(indices),
                   [list(p) for p in proofs], list(roots))


def test_rpc_replica_maps_connection_loss_to_typed_transport_error():
    """A replica killed under a dialed backend surfaces
    `ConnectionError` (the router's retryable/trip class), and the
    backend REDIALS once the endpoint is back."""
    serving = ServingSigBackend(PythonSigBackend(),
                                ServingConfig(flush_us=200),
                                registry=_registry())
    server = RPCServer(SimulatedMainchain(), sig_backend=serving)
    server.start()
    host, port = server.address
    backend = RpcReplicaBackend.dial(host, port)
    (digest, sig, want), = _ecdsa_cases(1)
    assert backend.ecrecover_addresses([digest], [sig]) == [want]
    server.stop()
    serving.close()
    with pytest.raises(ConnectionError):
        backend.ecrecover_addresses([digest], [sig])
    # restart on the SAME endpoint: the next call redials and succeeds
    serving2 = ServingSigBackend(PythonSigBackend(),
                                 ServingConfig(flush_us=200),
                                 registry=_registry())
    server2 = RPCServer(SimulatedMainchain(), host=host, port=port,
                        sig_backend=serving2)
    server2.start()
    try:
        deadline = time.monotonic() + 5
        while True:
            try:
                assert backend.ecrecover_addresses([digest],
                                                   [sig]) == [want]
                break
            except ConnectionError:
                if time.monotonic() > deadline:
                    raise
                time.sleep(0.05)
    finally:
        backend.close()
        server2.stop()
        serving2.close()


# == transport chaos ========================================================


def test_transport_chaos_delay_and_partition_modes():
    delayed = ChaosSchedule(seed=3, rules={"fleet.transport": 1},
                            modes={"fleet.transport": "delay"},
                            delay_s=0.15)
    front = TransportChaos(PythonSigBackend(), delayed)
    (digest, sig, want), = _ecdsa_cases(1)
    t0 = time.monotonic()
    assert front.ecrecover_addresses([digest], [sig]) == [want]
    assert time.monotonic() - t0 >= 0.15  # first call stalled
    t0 = time.monotonic()
    assert front.ecrecover_addresses([digest], [sig]) == [want]
    assert time.monotonic() - t0 < 0.1  # rule healed (first-n)

    cut = ChaosSchedule(seed=3, rules={"fleet.transport": 1},
                        modes={"fleet.transport": "partition"})
    front = TransportChaos(PythonSigBackend(), cut)
    with pytest.raises(InjectedFault):
        front.ecrecover_addresses([digest], [sig])
    assert isinstance(InjectedFault("x"), ConnectionError)  # trip class
    assert front.ecrecover_addresses([digest], [sig]) == [want]
    # transport_disturb with no schedule / no rule is a no-op
    transport_disturb(None)
    transport_disturb(ChaosSchedule(seed=1))


def test_transport_mode_validation_fails_fast():
    with pytest.raises(ValueError, match="fleet.transport"):
        ChaosSchedule(modes={"backend.ecrecover_addresses": "delay"})
    with pytest.raises(ValueError, match="fleet.transport"):
        parse_spec("dispatch.ecrecover_addresses:mode=partition")
    schedule = parse_spec(
        "seed=5,fleet.transport=0.5,fleet.transport:mode=delay,"
        "delay_s=0.02")
    assert schedule.delay_s == 0.02
    assert schedule.mode_for("fleet.transport") == "delay"


# == hedged dispatch ========================================================


def _slow_fast_fleet(registry, delay_s=0.4, hedge_ms=30.0,
                     slow_backend=None, fast_backend=None):
    slow_sched = ChaosSchedule(seed=1, rules={"fleet.transport": True},
                               modes={"fleet.transport": "delay"},
                               delay_s=delay_s)
    r0 = Replica("r0", TransportChaos(slow_backend or PythonSigBackend(),
                                      slow_sched),
                 probe=None, registry=registry)
    r1 = Replica("r1", fast_backend or PythonSigBackend(), probe=None,
                 registry=registry)
    router = FleetRouter([r0, r1], health_interval_s=0.0,
                         hedge_ms=hedge_ms, registry=registry)
    return router, r0, r1


def _r0_key(router) -> str:
    return next(k for k in (f"shard-{i}" for i in range(64))
                if router.route(k)[0].name == "r0")


def test_hedge_first_verdict_wins_and_losses_are_accounted():
    """A slow primary's interactive call is answered by the hedge
    after the floor delay; the loser's verdict is discarded with
    accounting, and bulk traffic never hedges."""
    registry = _registry()
    router, r0, r1 = _slow_fast_fleet(registry)
    (digest, sig, want), = _ecdsa_cases(1)
    key = _r0_key(router)
    try:
        t0 = time.monotonic()
        got = router.call("ecrecover_addresses", [digest], [sig],
                          affinity=key)
        took = time.monotonic() - t0
        assert got == [want]
        assert took < 0.3, f"sat out the slow replica: {took:.3f}s"
        deadline = time.monotonic() + 3
        while router.hedge_stats()["wasted"] < 1 \
                and time.monotonic() < deadline:
            time.sleep(0.02)  # the loser finishes in the pool
        stats = router.hedge_stats()
        assert stats["issued"] == 1 and stats["won"] == 1
        assert stats["wasted"] == 1
        # bulk never hedges: the same slow-affinity call under
        # bulk_audit waits the primary out
        t0 = time.monotonic()
        got = router.call("ecrecover_addresses", [digest], [sig],
                          affinity=key, klass=CLASS_BULK_AUDIT)
        assert got == [want]
        assert time.monotonic() - t0 >= 0.35
        assert router.hedge_stats()["issued"] == 1
    finally:
        router.close()


def test_hedge_rides_untenanted_for_quota_idempotence():
    """The hedged duplicate must NOT charge the caller's tenant quota:
    a serving replica with a 1-row tenant quota still answers a hedged
    call whose primary is stalled ON that tenant's only quota slot."""
    registry = _registry()
    # r1 (the hedge target) enforces a 1-row quota for every tenant;
    # the hedge rides untenanted so it is admitted regardless
    serving1 = ServingSigBackend(
        PythonSigBackend(),
        ServingConfig(flush_us=200, tenant_quota_rows=1),
        registry=_registry())
    router, r0, r1 = _slow_fast_fleet(registry, fast_backend=serving1)
    (digest, sig, want), = _ecdsa_cases(1)
    key = _r0_key(router)
    try:
        got = router.call("ecrecover_addresses", [digest], [sig],
                          affinity=key, tenant="t-hedge")
        assert got == [want]
        assert router.hedge_stats()["won"] == 1
        # the quota bucket saw no queued rows from the hedge once the
        # dispatch drained — and crucially no TenantQuotaExceeded
        queue = serving1.batcher._queues["ecrecover_addresses"]
        assert queue.quota_rejections == 0
    finally:
        router.close()
        serving1.close()


def test_hedge_duplicate_suppression_fires_audit_once():
    """Both sides of a hedged pair detect the SAME silent corruption
    (soundness spot-check on two corrupt replicas): the audit-fault
    accounting charges ONCE per logical request, the ladder still
    recovers from the clean third replica."""
    registry = _registry()

    def corrupt_backend():
        schedule = ChaosSchedule(
            seed=9, rules={"backend.ecrecover_addresses": True},
            modes={"backend.ecrecover_addresses": "corrupt"})
        return SpotCheckSigBackend(
            ChaosSigBackend(PythonSigBackend(), schedule), rate=1.0)

    slow_sched = ChaosSchedule(seed=1, rules={"fleet.transport": True},
                               modes={"fleet.transport": "delay"},
                               delay_s=0.25)
    r0 = Replica("r0", TransportChaos(corrupt_backend(), slow_sched),
                 probe=None, registry=registry)
    r1 = Replica("r1", corrupt_backend(), probe=None, registry=registry)
    r2 = Replica("r2", PythonSigBackend(), probe=None, registry=registry)
    router = FleetRouter([r0, r1, r2], health_interval_s=0.0,
                         hedge_ms=30, registry=registry)
    cases = _ecdsa_cases(4, tag=b"aud")
    # an affinity whose preference order is exactly r0, r1, r2: the
    # hedged pair is corrupt+corrupt and the ladder lands on clean r2
    key = next(k for k in (f"shard-{i}" for i in range(256))
               if [r.name for r in router.route(k)] == ["r0", "r1", "r2"])
    mismatches = metrics.DEFAULT_REGISTRY.counter(
        "resilience/soundness/ecrecover_addresses/mismatches")
    mark = mismatches.value
    try:
        got = router.call("ecrecover_addresses",
                          [c[0] for c in cases], [c[1] for c in cases],
                          affinity=key)
        assert got == [c[2] for c in cases]  # the clean replica answered
        stats = router.hedge_stats()
        assert stats["issued"] == 1
        # BOTH duplicates raised SoundnessViolation; the audit-fault
        # path was charged exactly once for the logical request. A
        # both-failed pair discards no verdict: nothing is counted
        # wasted — the pair's failure drove the retry ladder instead
        assert stats["audit_faults"] == 1, stats
        assert stats["wasted"] == 0 and stats["loser_failures"] == 0, stats
        # each replica's audit really did fire (the spot-checker's
        # counters live in the default registry)
        assert mismatches.value - mark >= 2
    finally:
        router.close()


def test_hedge_loser_failing_before_verdict_is_counted_wasted():
    """A hedge duplicate that fails FAST (partitioned hedge target)
    while the slow primary eventually answers is still a wasted
    dispatch — it must feed the storm watch's wasted rate, not vanish
    into the race bookkeeping."""
    registry = _registry()
    slow_sched = ChaosSchedule(seed=4, rules={"fleet.transport": True},
                               modes={"fleet.transport": "delay"},
                               delay_s=0.3)
    cut_sched = ChaosSchedule(seed=4, rules={"fleet.transport": True},
                              modes={"fleet.transport": "partition"})
    r0 = Replica("r0", TransportChaos(PythonSigBackend(), slow_sched),
                 probe=None, registry=registry)
    r1 = Replica("r1", TransportChaos(PythonSigBackend(), cut_sched),
                 probe=None, registry=registry)
    router = FleetRouter([r0, r1], health_interval_s=0.0, hedge_ms=30,
                         registry=registry)
    (digest, sig, want), = _ecdsa_cases(1, tag=b"lf")
    key = _r0_key(router)
    try:
        got = router.call("ecrecover_addresses", [digest], [sig],
                          affinity=key)
        assert got == [want]  # the slow primary's verdict, waited out
        stats = router.hedge_stats()
        assert stats["issued"] == 1 and stats["won"] == 0
        assert stats["wasted"] == 1, stats   # the dead duplicate
        assert stats["loser_failures"] == 1, stats
    finally:
        router.close()


def test_hedge_vs_drain_interleaving():
    """The primary's replica is DRAINED while its hedge duplicate is
    still in flight: the caller's verdict is unaffected, the stale
    dispatch finishes inside the drain (flight accounting), and the
    replica reaches drained-empty state."""
    registry = _registry()
    router, r0, r1 = _slow_fast_fleet(registry, delay_s=0.4)
    (digest, sig, want), = _ecdsa_cases(1)
    key = _r0_key(router)
    try:
        got = router.call("ecrecover_addresses", [digest], [sig],
                          affinity=key)
        assert got == [want]  # hedge answered; r0's dispatch still live
        assert r0.in_flight == 1
        router.drain("r0")
        assert r0.state == "draining"
        assert not r0.drained  # the hedged loser is still in flight
        deadline = time.monotonic() + 3
        while not r0.drained and time.monotonic() < deadline:
            time.sleep(0.02)
        assert r0.drained  # in-flight loser finished inside the drain
        assert router.hedge_stats()["wasted"] == 1
        # traffic keeps flowing on the survivor
        assert router.call("ecrecover_addresses", [digest], [sig],
                           affinity=key) == [want]
    finally:
        router.close()


def test_hedge_storm_latches_and_lands_in_the_flight_recorder():
    """A sustained wasted-duplicate rate over the threshold is a
    fleet-health event: the storm latch sets (gauge + hedge_stats),
    and the flight recorder captures a hedge_storm event like a
    breaker trip."""
    from gethsharding_tpu.perfwatch import RECORDER

    registry = _registry()
    # every call hedges (sub-ms fuse against ~ms scalar calls) and the
    # primary usually wins -> near-100% wasted rate
    router, r0, r1 = _slow_fast_fleet(registry, delay_s=0.0,
                                      hedge_ms=0.01)
    cases = _ecdsa_cases(4, tag=b"storm")
    key = _r0_key(router)
    try:
        for i in range(24):
            digest, sig, want = cases[i % len(cases)]
            assert router.call("ecrecover_addresses", [digest], [sig],
                               affinity=key) == [want]
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            router.refresh(force=True)  # the sweep runs the storm watch
            if router.hedge_stats()["storm"]:
                break
            time.sleep(0.05)
        stats = router.hedge_stats()
        assert stats["storm"] == 1, stats
        assert registry.gauge("fleet/hedge/storm").value == 1
        assert any(e["kind"] == "hedge_storm"
                   for e in RECORDER.events()), "no recorder event"
    finally:
        router.close()


def test_hedged_spans_link_one_trace_and_attribute_wasted_work(monkeypatch):
    """Winner and loser of a hedged pair are linked on ONE logical
    trace: both fleet/attempt spans parent under the route span, the
    loser's discard records a fleet/hedge_wasted span (replica +
    winner + wasted tags) with the SAME trace id, the hedge flags that
    trace for the fleet collector's tail retention, and the critical-
    path analyzer reports the duplicate as the hedge_wasted segment
    OUTSIDE the wall-time identity."""
    from gethsharding_tpu import fleettrace, tracing
    from gethsharding_tpu.fleettrace.critical_path import attribute

    registry = _registry()
    tracing.enable(ring_spans=16384)
    tracing.TRACER.clear()
    collector = fleettrace.TraceCollector(registry, sample=0.0)
    monkeypatch.setattr(fleettrace, "COLLECTOR", collector)
    router, r0, r1 = _slow_fast_fleet(registry)
    (digest, sig, want), = _ecdsa_cases(1, tag=b"link")
    key = _r0_key(router)
    try:
        assert router.call("ecrecover_addresses", [digest], [sig],
                           affinity=key) == [want]
        deadline = time.monotonic() + 3
        while router.hedge_stats()["wasted"] < 1 \
                and time.monotonic() < deadline:
            time.sleep(0.02)  # the loser's discard records the span
        assert router.hedge_stats()["wasted"] == 1
        spans = tracing.TRACER.recent_spans()
        route = next(s for s in spans if s["name"] == "fleet/route")
        trace = [s for s in spans if s["trace"] == route["trace"]]
        attempts = [s for s in trace if s["name"] == "fleet/attempt"]
        # primary + hedge, both under the route span, one trace id
        assert len(attempts) == 2, [s["name"] for s in trace]
        assert {a["tags"]["replica"] for a in attempts} == {"r0", "r1"}
        assert {a["tags"]["hedged"] for a in attempts} == {False, True}
        assert all(a["parent"] == route["span"] for a in attempts)
        wasted = next(s for s in trace
                      if s["name"] == "fleet/hedge_wasted")
        assert wasted["parent"] == route["span"]
        assert wasted["tags"]["replica"] == "r0"
        assert wasted["tags"]["winner"] == "r1"
        assert wasted["tags"]["wasted"] is True
        # winner linkage is tagged on the logical request's span
        assert route["tags"]["hedge_winner"] == "r1"
        # ... and the hedge flagged the trace for tail retention (the
        # spans have not reached this collector, so the mark is staged)
        assert collector._marks.get(route["trace"]) == "hedged"
        # attribution: the duplicate is its own segment, outside the
        # telescoping identity (it ran CONCURRENTLY, it is not wall
        # time), and the tree walk reaches every span
        attr = attribute(trace)
        assert attr["root"] == "fleet/route"
        assert attr["orphan_spans"] == 0
        assert "hedge_wasted" not in attr["segments"]
        # the loser sat out the ~0.4 s transport delay after the ~30 ms
        # hedge verdict: its discarded interval dwarfs the route span
        assert attr["hedge_wasted_s"] > attr["total_s"]
    finally:
        router.close()
        tracing.TRACER.clear()
        tracing.disable()


# == WFQ: tenant fairness inside a class ====================================


def _req(rows: int, tenant: str) -> Request:
    digests = tuple(keccak256(b"w-%d" % i) for i in range(rows))
    sigs = tuple(b"\x00" * 65 for _ in range(rows))
    return Request("ecrecover_addresses", (digests, sigs), rows,
                   klass=CLASS_BULK_AUDIT, tenant=tenant)


def test_wfq_heavy_tenant_cannot_starve_light_tenant():
    """The starvation bound: with a heavy tenant's 100-request backlog
    queued FIRST, a light tenant's 4 requests still ride the very next
    batch (deficit round-robin share), and over the whole drain the
    light tenant's wait is bounded by its share, not the heavy
    backlog."""
    queue = AdmissionQueue(cap_rows=4096, max_batch=16, flush_us=0)
    for _ in range(100):
        queue.put(_req(1, "heavy"))
    for _ in range(4):
        queue.put(_req(1, "light"))
    batch, reason = queue.take_batch()
    assert reason == "full"
    counts: dict = {}
    for request in batch:
        counts[request.tenant] = counts.get(request.tenant, 0) + 1
    assert counts.get("light", 0) == 4, counts  # full share, batch ONE
    assert counts["heavy"] == len(batch) - 4


def test_wfq_big_requests_clear_via_carried_deficit():
    """A tenant whose requests are larger than one quantum is not
    starved by size: its deficit carries across batches until the big
    request clears."""
    queue = AdmissionQueue(cap_rows=4096, max_batch=8, flush_us=0)
    for _ in range(40):
        queue.put(_req(1, "small"))
    queue.put(_req(6, "big"))
    for i in range(4):
        batch, _ = queue.take_batch()
        if any(r.tenant == "big" for r in batch):
            break
    else:
        pytest.fail("the 6-row request never cleared in 4 batches")
    assert i <= 2, f"big request starved for {i} batches"


def test_wfq_single_tenant_drains_fifo():
    """Untenanted (or single-tenant) backlogs keep the exact pre-WFQ
    FIFO drain order."""
    queue = AdmissionQueue(cap_rows=4096, max_batch=8, flush_us=0)
    marks = []
    for i in range(12):
        request = _req(1, "")
        marks.append(request)
        queue.put(request)
    batch, _ = queue.take_batch()
    assert batch == marks[:8]


# == the standalone frontend ================================================


def _frontend_fixture(registry, n=2):
    servings, replicas = [], []
    for i in range(n):
        serving = ServingSigBackend(PythonSigBackend(),
                                    ServingConfig(flush_us=200),
                                    registry=_registry())
        servings.append(serving)
        replicas.append(Replica(f"r{i}", serving, probe=None,
                                registry=registry))
    router = FleetRouter(replicas, health_interval_s=0.05,
                         registry=registry)
    frontend = FrontendServer(router)
    frontend.start()
    return frontend, servings


def test_frontend_serves_all_planes_and_orchestrates_drains():
    registry = _registry()
    frontend, servings = _frontend_fixture(registry)
    client = RPCClient(*frontend.address)
    try:
        (digest, sig, want), = _ecdsa_cases(1)
        out = client.call("shard_ecrecover", [codec.enc_bytes(digest)],
                          [codec.enc_bytes(sig)])
        assert out == [codec.enc_bytes(want)]
        msgs, sig_rows, pk_rows, keys = _committee_rows()
        got = client.call("shard_verifyCommittees",
                          [codec.enc_bytes(m) for m in msgs],
                          codec.enc_g1_rows(sig_rows),
                          codec.enc_g2_rows(pk_rows),
                          codec.enc_pk_row_keys(keys))
        assert got == [True, False, True]
        chunks, indices, proofs, roots = _das_rows()
        got = client.call("shard_dasVerify",
                          *codec.enc_das_call(chunks, indices, proofs,
                                              roots))
        assert got == [True, True, False, False, False]
        # control plane: health, status, per-replica drain/undrain
        health = client.call("shard_health")
        assert health["draining"] is False
        assert health["accepting_replicas"] == 2
        client.call("shard_drainReplica", "r0")
        status = client.call("shard_fleetStatus")
        assert status["replicas"]["r0"]["state"] == "draining"
        out = client.call("shard_ecrecover", [codec.enc_bytes(digest)],
                          [codec.enc_bytes(sig)])
        assert out == [codec.enc_bytes(want)]  # survivor answers
        client.call("shard_undrainReplica", "r0")
        assert client.call(
            "shard_fleetStatus")["replicas"]["r0"]["state"] == "healthy"
        # frontend-level drain: typed refusal with the routing phrase
        client.call("shard_drain")
        with pytest.raises(RPCError, match="replica draining"):
            client.call("shard_ecrecover", [codec.enc_bytes(digest)],
                        [codec.enc_bytes(sig)])
        assert client.call("shard_health")["draining"] is True
    finally:
        client.close()
        frontend.stop()
        for serving in servings:
            serving.close()


def test_frontend_relays_a_packed_request_without_opening_a_point(
        monkeypatch):
    """Client -> frontend -> `RpcReplicaBackend` -> replica: every hop
    hands the packed rows on as their bytes. The replica's backend here
    answers without reading a row, so any point opened on the way would
    be the relay's doing; rows with an absent point travel listed."""
    from gethsharding_tpu.crypto import pointrows

    seen = {}

    class Recorder(PythonSigBackend):
        def bls_verify_committees(self, messages, sig_rows, pk_rows,
                                  pk_row_keys=None):
            seen.update(sig_rows=sig_rows, pk_rows=pk_rows)
            return [len(r) > 0 for r in sig_rows]

    registry = _registry()
    serving = ServingSigBackend(Recorder(), ServingConfig(flush_us=200),
                                registry=_registry())
    replica_server = RPCServer(SimulatedMainchain(), sig_backend=serving)
    replica_server.start()
    wire = RpcReplicaBackend.dial(*replica_server.address)
    frontend = FrontendServer(FleetRouter(
        [Replica("r0", wire, probe=None, metrics_read=None,
                 registry=registry)],
        health_interval_s=0.05, registry=registry))
    frontend.start()
    actor = RpcReplicaBackend.dial(*frontend.address)
    msgs, sig_rows, pk_rows, keys = _committee_rows(4)
    sig_rows[2], pk_rows[2] = [], []
    sig_rows[3][0] = pk_rows[3][0] = None    # an absent voter: listed
    opened = []
    points = pointrows.PackedRow._points
    try:
        # the actor packs its own Python points; from there on no hop
        # may turn a packed row back into points
        params = (codec.enc_g1_rows(sig_rows), codec.enc_g2_rows(pk_rows))
        monkeypatch.setattr(
            pointrows.PackedRow, "_points",
            lambda self, raw: opened.append(self) or points(self, raw))
        got = actor.bls_verify_committees(msgs, sig_rows, pk_rows,
                                          pk_row_keys=keys)
        monkeypatch.undo()
    finally:
        actor.close()
        frontend.stop()
        wire.close()
        replica_server.stop()
        serving.close()
    assert got == [True, True, False, True] and opened == []
    for sent, rows in zip(params, (seen["sig_rows"], seen["pk_rows"])):
        assert [isinstance(r, pointrows.PackedRow) for r in rows] \
            == [True, True, True, False]
        assert ["0x" + r.raw.hex() for r in rows[:3]] == sent[:3]
    assert [list(r) for r in seen["sig_rows"]] == sig_rows
    assert [list(r) for r in seen["pk_rows"]] == pk_rows


def test_frontend_restart_with_actor_mid_request_recovers():
    """An actor (an `RpcReplicaBackend` dialing the FRONTEND) whose
    in-flight request dies with the frontend gets a TYPED transport
    error, and its retry policy recovers once the frontend restarts on
    the same endpoint — no actor rebuild, no stranded future."""
    registry = _registry()
    # a slow replica keeps the actor's request in flight across the
    # frontend's shutdown window
    slow_sched = ChaosSchedule(seed=2, rules={"fleet.transport": 2},
                               modes={"fleet.transport": "delay"},
                               delay_s=0.6)
    replica_backend = TransportChaos(PythonSigBackend(), slow_sched)
    router = FleetRouter(
        [Replica("r0", replica_backend, probe=None, registry=registry)],
        health_interval_s=0.0, registry=registry)
    frontend = FrontendServer(router)
    frontend.start()
    host, port = frontend.address
    actor = RpcReplicaBackend.dial(host, port)
    (digest, sig, want), = _ecdsa_cases(1)
    outcome: dict = {}

    def mid_request() -> None:
        try:
            outcome["result"] = actor.ecrecover_addresses([digest], [sig])
        except ConnectionError as exc:
            outcome["typed"] = exc
        except Exception as exc:  # noqa: BLE001 - the assertion target
            outcome["untyped"] = exc

    thread = threading.Thread(target=mid_request)
    thread.start()
    time.sleep(0.15)  # the request is inside the 0.6 s replica stall
    frontend.stop(grace_s=0.1)
    thread.join(timeout=10)
    assert not thread.is_alive()
    assert "typed" in outcome, outcome  # ConnectionError, nothing else
    # restart on the SAME endpoint (fresh router over the same replica)
    router2 = FleetRouter(
        [Replica("r0", replica_backend, probe=None, registry=registry)],
        health_interval_s=0.0, registry=registry)
    frontend2 = FrontendServer(router2, host=host, port=port)
    frontend2.start()
    try:
        # the actor's ordinary retry shape: redial-and-retry on the
        # typed transport error recovers without rebuilding the actor
        from gethsharding_tpu.resilience.policy import (RetryExecutor,
                                                        RetryPolicy)

        executor = RetryExecutor(
            "test.frontend_recover",
            RetryPolicy(attempts=30, base_s=0.05, jitter=0.0,
                        retryable=(ConnectionError,)),
            registry=registry)
        got = executor.call(
            lambda: actor.ecrecover_addresses([digest], [sig]))
        assert got == [want]
    finally:
        actor.close()
        frontend2.stop()


def test_build_frontend_dials_real_replicas_end_to_end():
    """`build_frontend` (the CLI's constructor): two RPC replica
    processes-worth of servers, one frontend, verdicts bit-identical
    through the whole chain — and the frontend's shard_metrics carries
    the fleet/hedge counters for federation."""
    servers = []
    endpoints = []
    for _ in range(2):
        serving = ServingSigBackend(PythonSigBackend(),
                                    ServingConfig(flush_us=200),
                                    registry=_registry())
        server = RPCServer(SimulatedMainchain(), sig_backend=serving)
        server.start()
        servers.append((server, serving))
        endpoints.append("%s:%d" % server.address)
    frontend = build_frontend(endpoints, hedge_ms=0,
                              health_interval_s=0.05,
                              registry=metrics.DEFAULT_REGISTRY)
    frontend.start()
    client = RPCClient(*frontend.address)
    try:
        cases = _ecdsa_cases(4, tag=b"bf")
        for digest, sig, want in cases:
            out = client.call("shard_ecrecover",
                              [codec.enc_bytes(digest)],
                              [codec.enc_bytes(sig)])
            assert out == [codec.enc_bytes(want)]
        snapshot = client.call("shard_metrics")
        assert "fleet/hedge/issued" in snapshot
        assert "fleet/router/calls" in snapshot
    finally:
        client.close()
        frontend.stop()
        for server, serving in servers:
            server.stop()
            serving.close()
