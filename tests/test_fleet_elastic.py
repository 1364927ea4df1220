"""Elastic fleet: runtime membership, replicated frontends, and the
SLO-driven autoscaler (gethsharding_tpu/fleet/membership.py,
fleet/autoscaler.py, the frontend's membership RPC plane, and
rpc/client.py's FrontendPool).

The contracts:

- MEMBERSHIP: the registry mutates at runtime under the routing
  invariants — a new replica enters DRAINING and earns HEALTHY through
  the health sweep, a removal drains first and detaches only once
  nothing is in flight, duplicates/unknowns are typed errors, and the
  journal restores the last acked topology across a restart.
- SWEEP TOLERANCE (the regression): a replica removed while the sweep
  is blocked in another replica's health read gets NO stale probe and
  NO stale health fold — its backend is closed and never touched again.
- RENDEZVOUS-MINIMAL RESHUFFLE: admitting (or removing) a replica
  moves ONLY the keys whose rendezvous top choice is the new (gone)
  replica; every other key keeps its exact route.
- CHURN HAMMER: a seeded add/remove loop under concurrent traffic
  produces zero incorrect verdicts and zero non-typed errors.
- REPLICATED FRONTENDS: membership epochs gossip last-writer-wins
  (eager push on local mutations, pull convergence after divergence),
  and `FrontendPool` fails over on the typed draining refusal a
  stopping frontend serves during its drain-notice window — no retry
  burned on a bare connection reset.
- AUTOSCALER: scale-out on fast burn or sustained depth, scale-in only
  when calm is sustained, cooldowns hold (and count) repeat triggers,
  the boot topology is never scaled away, and retired processes are
  reaped once the router lets go.
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
    ReplicaState,
    RouterSigBackend,
)
from gethsharding_tpu.fleet.autoscaler import AutoscaleConfig, Autoscaler
from gethsharding_tpu.fleet.membership import (
    DuplicateReplicaError,
    FleetMembership,
    MembershipJournal,
    UnknownReplicaError,
)
from gethsharding_tpu.db.kv import MemoryKV
from gethsharding_tpu.serving.classes import CLASS_BULK_AUDIT
from gethsharding_tpu.sigbackend import PythonSigBackend


def _registry() -> metrics.Registry:
    return metrics.Registry()


def _ecdsa_cases(n: int):
    cases = []
    for i in range(n):
        priv = int.from_bytes(keccak256(b"elastic-%d" % i), "big") % ecdsa.N
        digest = keccak256(b"elastic-msg-%d" % i)
        cases.append((digest, ecdsa.sign(digest, priv).to_bytes65(),
                      ecdsa.priv_to_address(priv)))
    return cases


def _boot_fleet(registry, n: int = 2, health_interval_s: float = 0.0):
    """A router over `n` in-proc replicas plus its membership plane
    (make_replica builds in-proc replicas named by their endpoint)."""
    def make(endpoint: str) -> Replica:
        return Replica(endpoint, PythonSigBackend(), probe=None,
                       registry=registry)

    boot = [Replica(f"r{i}", PythonSigBackend(), probe=None,
                    registry=registry) for i in range(n)]
    router = FleetRouter(boot, health_interval_s=health_interval_s,
                         registry=registry)
    membership = FleetMembership(
        router, make, seed={f"r{i}": f"boot:{i}" for i in range(n)},
        registry=registry)
    return router, membership


# == runtime membership =====================================================


def test_admission_enters_draining_and_sweep_promotes():
    registry = _registry()
    router, membership = _boot_fleet(registry)
    try:
        out = membership.add("ep:new")
        assert out["epoch"] == 1
        assert out["state"] == ReplicaState.DRAINING
        # not offered work yet: route() only walks accepting replicas
        assert all(r.name != "ep:new"
                   for r in router.route(affinity="some-key"))
        router.refresh(force=True)  # the sweep reads real health
        states = router.states()
        assert states["ep:new"]["state"] == ReplicaState.HEALTHY
    finally:
        router.close()


def test_removal_drains_then_detaches_and_typed_errors():
    registry = _registry()
    router, membership = _boot_fleet(registry)
    try:
        membership.add("ep:new")
        with pytest.raises(DuplicateReplicaError):
            membership.add("ep:new")
        out = membership.remove("ep:new")
        assert out["detached"] is True  # idle: detached immediately
        assert "ep:new" not in membership.endpoints()
        with pytest.raises(UnknownReplicaError):
            membership.remove("ep:new")
        # the boot seed removes by NAME too (names predate endpoints)
        out = membership.remove("r1")
        assert out["detached"] is True
        assert len(router.members()) == 1
    finally:
        router.close()


def test_removal_waits_for_in_flight_work():
    """A busy replica drains (no new work) but detaches only once its
    in-flight call finishes — no live request sees the endpoint die."""
    registry = _registry()
    router, membership = _boot_fleet(registry, n=1)
    try:
        membership.add("ep:busy")
        router.refresh(force=True)
        busy = router._replica("ep:busy")
        with busy.flight():
            out = membership.remove("ep:busy")
            assert out["detached"] is False
            assert busy.state == ReplicaState.DRAINING
            assert not busy.detached
            router.refresh(force=True)  # sweep must NOT detach it yet
            assert not busy.detached
        router.refresh(force=True)  # flight done: the sweep completes it
        assert busy.detached
        assert all(r.name != "ep:busy" for r in router.members())
    finally:
        router.close()


def test_journal_restores_last_acked_topology():
    registry = _registry()
    kv = MemoryKV()
    router, _ = _boot_fleet(registry, n=1)
    journal = MembershipJournal(kv, registry=registry)
    try:
        membership = FleetMembership(
            router, lambda e: Replica(e, PythonSigBackend(), probe=None,
                                      registry=registry),
            journal=journal, seed={"r0": "boot:0"}, registry=registry)
        assert membership.restore() is False  # fresh journal: seed acked
        membership.add("ep:a")
        membership.add("ep:b")
        membership.remove("ep:a")
        epoch = membership.epoch
        assert epoch == 3
    finally:
        router.close()
    # "restart": a new process boots from the stale command line
    registry2 = _registry()
    router2, _ = _boot_fleet(registry2, n=1)
    try:
        membership2 = FleetMembership(
            router2, lambda e: Replica(e, PythonSigBackend(), probe=None,
                                       registry=registry2),
            journal=MembershipJournal(kv, registry=registry2),
            seed={"r0": "boot:0"}, registry=registry2)
        assert membership2.restore() is True
        assert membership2.epoch == epoch
        assert "ep:b" in membership2.endpoints()
        assert "ep:a" not in membership2.endpoints()
    finally:
        router2.close()


# == the sweep tolerates concurrent mutation (the regression) ===============


def test_mid_sweep_removal_skips_stale_replica():
    """Remove a replica while the sweep is BLOCKED in the previous
    replica's health read: the removed replica must get no stale health
    read and no stale probe, and its backend must be closed."""
    registry = _registry()
    entered = threading.Event()
    release = threading.Event()

    def blocking_health():
        entered.set()
        assert release.wait(5)
        return {"breaker": None, "draining": False}

    b_calls = {"health": 0, "probe": 0}

    class Closable(PythonSigBackend):
        closed = False

        def close(self):
            self.closed = True

    def b_health():
        b_calls["health"] += 1
        return {"breaker": "open", "draining": True}

    def b_probe():
        b_calls["probe"] += 1

    backend_b = Closable()
    replicas = [
        Replica("A", PythonSigBackend(), health=blocking_health,
                probe=None, registry=registry),
        Replica("B", backend_b, health=b_health, probe=b_probe,
                registry=registry),
    ]
    router = FleetRouter(replicas, health_interval_s=0.0,
                         registry=registry)
    try:
        sweep = threading.Thread(
            target=lambda: router.refresh(force=True))
        sweep.start()
        assert entered.wait(5)  # the sweep holds A's health read
        state = router.remove_replica("B")  # mid-sweep removal
        assert state["detached"] is True
        assert backend_b.closed
        release.set()
        sweep.join(timeout=5)
        assert not sweep.is_alive()
        # the regression: no stale health read, no probe-back-to-life
        assert b_calls == {"health": 0, "probe": 0}
        assert [r.name for r in router.members()] == ["A"]
    finally:
        release.set()
        router.close()


# == rendezvous-minimal reshuffle ===========================================


def test_admission_moves_only_rendezvous_minimal_keys():
    registry = _registry()
    router, membership = _boot_fleet(registry, n=3)
    keys = [f"shard-{i}" for i in range(64)]
    try:
        before = {k: router.route(affinity=k)[0].name for k in keys}
        membership.add("ep:new")
        router.refresh(force=True)  # promote the admission
        after = {k: router.route(affinity=k)[0].name for k in keys}
        moved = {k for k in keys if after[k] != before[k]}
        assert moved, "rendezvous should hand SOME keys to the new node"
        # minimality: every moved key moved TO the new replica, every
        # other key kept its exact first choice
        assert all(after[k] == "ep:new" for k in moved)
        # and removal restores the original assignment exactly
        membership.remove("ep:new")
        restored = {k: router.route(affinity=k)[0].name for k in keys}
        assert restored == before
    finally:
        router.close()


# == churn hammer ===========================================================


def test_membership_churn_hammer_zero_incorrect_verdicts():
    """Seeded add/remove churn under concurrent traffic: every verdict
    correct, every error typed (AllReplicasDraining only)."""
    import random

    registry = _registry()
    router, membership = _boot_fleet(registry, n=2,
                                     health_interval_s=0.02)
    back = RouterSigBackend(router)
    cases = _ecdsa_cases(8)
    stop = threading.Event()
    wrong: list = []
    untyped: list = []

    def traffic():
        i = 0
        while not stop.is_set():
            digest, sig, want = cases[i % len(cases)]
            i += 1
            try:
                out = back.ecrecover_addresses([digest], [sig])
                if out != [want]:
                    wrong.append((want, out))
            except AllReplicasDraining:
                pass  # typed fleet weather
            except Exception as exc:  # noqa: BLE001 - the assertion
                untyped.append(exc)

    threads = [threading.Thread(target=traffic) for _ in range(3)]
    for thread in threads:
        thread.start()
    rnd = random.Random(0x5EED)
    extra: list = []
    try:
        for step in range(40):
            if extra and rnd.random() < 0.45:
                membership.remove(extra.pop(rnd.randrange(len(extra))))
            else:
                endpoint = f"ep:{step}"
                membership.add(endpoint)
                extra.append(endpoint)
            if rnd.random() < 0.5:
                router.refresh(force=True)
            time.sleep(0.002)
    finally:
        stop.set()
        for thread in threads:
            thread.join(timeout=10)
        router.close()
    assert not wrong, f"incorrect verdicts under churn: {wrong[:3]}"
    assert not untyped, f"non-typed errors under churn: {untyped[:3]}"
    # the boot replicas never left
    assert membership.epoch == 40
    names = {r.name for r in router.members()}
    assert {"r0", "r1"} <= names


# == replicated frontends: gossip + FrontendPool ============================


def _frontend(registry, peers=None):
    from gethsharding_tpu.fleet.frontend import FrontendServer

    router, membership = _boot_fleet(registry, n=1,
                                     health_interval_s=0.05)
    server = FrontendServer(router, port=0, membership=membership,
                            peers=peers or [], gossip_interval_s=30.0)
    server.start()
    return server


def test_membership_epochs_gossip_last_writer_wins():
    from gethsharding_tpu.rpc.client import RPCClient, RPCError

    reg_a, reg_b = _registry(), _registry()
    server_b = _frontend(reg_b)
    server_a = _frontend(
        reg_a, peers=[f"127.0.0.1:{server_b.address[1]}"])
    client = RPCClient("127.0.0.1", server_a.address[1], timeout=10.0)
    try:
        # local mutation on A pushes eagerly to B
        out = client.call("shard_addReplica", "ep:pushed")
        assert out["epoch"] == 1
        assert "ep:pushed" in server_b.membership.endpoints()
        assert server_b.membership.epoch == 1
        # typed wire errors for operator mistakes
        with pytest.raises(RPCError) as excinfo:
            client.call("shard_addReplica", "ep:pushed")
        assert excinfo.value.code == -32011
        assert "DuplicateReplicaError" in excinfo.value.message
        with pytest.raises(RPCError) as excinfo:
            client.call("shard_removeReplica", "ep:never")
        assert excinfo.value.code == -32011
        assert "UnknownReplicaError" in excinfo.value.message
        # B diverges ahead (epoch 2); A's pull adopts the newer epoch
        server_b.membership.add("ep:pulled")
        assert server_a.gossip_once() == 1
        assert server_a.membership.epoch == 2
        assert "ep:pulled" in server_a.membership.endpoints()
        # stale gossip is a no-op: re-offering A's own epoch changes
        # nothing (no ping-pong between converged peers)
        snap = server_a.membership.snapshot()
        out = client.call("shard_fleetReconfigure", snap["endpoints"],
                          snap["epoch"])
        assert out["adopted"] is False
        # the control plane shows through shard_health/shard_fleetStatus
        assert client.call("shard_health")["epoch"] == 2
        status = client.call("shard_fleetStatus")
        assert status["membership"]["epoch"] == 2
    finally:
        client.close()
        server_a.stop(grace_s=1.0, notice_s=0.0)
        server_b.stop(grace_s=1.0, notice_s=0.0)


def test_frontend_pool_fails_over_on_drain_notice():
    """A stopping frontend answers its drain-notice window with the
    typed refusal: the pool fails over to the peer without burning a
    retry on a connection reset, and stays on the survivor."""
    from gethsharding_tpu.rpc.client import FrontendPool

    reg_a, reg_b = _registry(), _registry()
    server_a = _frontend(reg_a)
    server_b = _frontend(reg_b)
    pool = FrontendPool([f"127.0.0.1:{server_a.address[1]}",
                         f"127.0.0.1:{server_b.address[1]}"],
                        timeout=10.0)
    (digest, sig, want), = _ecdsa_cases(1)
    stopped = threading.Event()

    def stop_a():
        server_a.stop(grace_s=2.0, notice_s=0.6)
        stopped.set()

    try:
        assert pool.ecrecover_addresses([digest], [sig]) == [want]
        assert pool.failovers == 0
        stopper = threading.Thread(target=stop_a)
        stopper.start()
        time.sleep(0.15)  # inside A's drain-notice window
        assert pool.ecrecover_addresses([digest], [sig]) == [want]
        assert pool.failovers >= 1  # typed refusal, not a reset
        assert pool.primary().endswith(str(server_b.address[1]))
        assert stopped.wait(10)
        stopper.join(timeout=5)
        # A is fully gone now; the pool is sticky on B
        assert pool.ecrecover_addresses([digest], [sig]) == [want]
    finally:
        pool.close()
        if not stopped.is_set():
            server_a.stop(grace_s=1.0, notice_s=0.0)
        server_b.stop(grace_s=1.0, notice_s=0.0)


# == the autoscaler control law =============================================


class FakeSpawner:
    def __init__(self):
        self.count = 0
        self.retired: list = []

    def spawn(self) -> str:
        endpoint = f"spawn:{self.count}"
        self.count += 1
        return endpoint

    def retire(self, endpoint: str) -> None:
        self.retired.append(endpoint)

    def close(self) -> None:
        pass


def _scaler(registry, signals, **cfg_kwargs):
    router, membership = _boot_fleet(registry, n=1)
    base = dict(min_replicas=1, max_replicas=3, sustain_s=3.0,
                cooldown_s=10.0)
    base.update(cfg_kwargs)
    cfg = AutoscaleConfig(**base)
    spawner = FakeSpawner()
    scaler = Autoscaler(membership, spawner, config=cfg,
                        registry=registry, signals=lambda: dict(signals))
    return router, membership, spawner, scaler, signals


CALM = {"burn_fast": 0.0, "burn_slow": 0.0, "depth": 0.0, "p99": 0.0}


def test_autoscaler_out_on_fast_burn_then_in_when_calm():
    registry = _registry()
    signals = {"burn_fast": 5.0, "burn_slow": 3.0, "depth": 10.0,
               "p99": 0.5}
    router, membership, spawner, scaler, signals = _scaler(
        registry, signals)
    try:
        decision = scaler.tick(now=0.0)
        assert decision["action"] == "out"
        assert membership.endpoints() == ["boot:0", "spawn:0"]
        # still burning one second later: held by the cooldown
        decision = scaler.tick(now=1.0)
        assert decision["action"] == "held"
        assert "cooling down" in decision["reason"]
        # calm arrives; the in-gate needs calm SUSTAINED
        signals.update(CALM)
        assert scaler.tick(now=11.0)["action"] == "none"
        decision = scaler.tick(now=14.5)
        assert decision["action"] == "in"
        assert decision["candidate"] == "spawn:0"
        assert membership.endpoints() == ["boot:0"]
        # the drained removal is reaped on the next tick
        scaler.tick(now=15.5)
        assert spawner.retired == ["spawn:0"]
        assert registry.counter("fleet/autoscale/out").value == 1
        assert registry.counter("fleet/autoscale/in").value == 1
        assert registry.counter("fleet/autoscale/held").value >= 1
        assert scaler.status()["spawned"] == []
    finally:
        router.close()


def test_autoscaler_out_on_sustained_depth_only():
    """Queue depth must HOLD for sustain_s — a momentary spike does not
    scale; and the boot replica is never a scale-in candidate."""
    registry = _registry()
    signals = {"burn_fast": 0.0, "burn_slow": 0.0, "depth": 100.0,
               "p99": 0.0}
    router, membership, spawner, scaler, signals = _scaler(
        registry, signals, out_depth=64.0)
    try:
        assert scaler.tick(now=0.0)["action"] == "none"  # band started
        signals["depth"] = 0.0  # spike over before sustain_s
        assert scaler.tick(now=1.0)["action"] == "none"
        signals["depth"] = 100.0
        assert scaler.tick(now=2.0)["action"] == "none"  # band restarts
        decision = scaler.tick(now=5.5)
        assert decision["action"] == "out"
        assert "queue depth" in decision["reason"]
        # calm sustained at the floor: nothing to scale in (only the
        # boot replica would remain after reaping the spawned one)
        signals.update(CALM)
        scaler.tick(now=16.0)
        decision = scaler.tick(now=19.5)
        assert decision["action"] == "in"
        scaler.tick(now=20.5)  # reap
        signals.update(CALM)
        scaler.tick(now=31.0)
        decision = scaler.tick(now=34.5)
        assert decision["action"] == "none"
        assert "at floor" in decision["reason"]
        assert membership.endpoints() == ["boot:0"]
    finally:
        router.close()


def test_autoscaler_held_at_max():
    registry = _registry()
    signals = {"burn_fast": 9.0, "burn_slow": 9.0, "depth": 500.0,
               "p99": 2.0}
    router, membership, spawner, scaler, signals = _scaler(
        registry, signals, max_replicas=2, cooldown_s=0.0)
    try:
        assert scaler.tick(now=0.0)["action"] == "out"
        decision = scaler.tick(now=1.0)
        assert decision["action"] == "held"
        assert "at max" in decision["reason"]
        assert len(membership.endpoints()) == 2
    finally:
        router.close()


# == budget-aware bulk hedging ==============================================


def test_bulk_hedge_gated_on_slo_budget(monkeypatch):
    """Keyed bulk_audit planes hedge only while the class's SLO budget
    says the duplicate is free; a starved budget holds the hedge (and
    counts the hold). Default (0) keeps bulk hedging off entirely."""
    import importlib

    # a fresh process tracker, restored afterwards: bulk budget that an
    # earlier test of the same process burnt would hold the first hedge
    tracker_mod = importlib.import_module("gethsharding_tpu.slo.tracker")
    monkeypatch.setattr(tracker_mod, "TRACKER", tracker_mod.SLOTracker(
        registry=metrics.Registry()))
    registry = _registry()
    replica = Replica("r0", PythonSigBackend(), probe=None,
                      registry=registry)

    def build(min_budget):
        monkeypatch.setenv("GETHSHARDING_FLEET_HEDGE_BULK_MIN_BUDGET",
                           str(min_budget))
        return FleetRouter([replica], health_interval_s=0.0,
                           hedge_ms=5.0, registry=_registry())

    # a fresh tracker has its full budget (remaining 1.0): armed
    router = build(0.5)
    try:
        delay = router._hedge_delay_s(replica, CLASS_BULK_AUDIT,
                                      keyed=True)
        assert delay == pytest.approx(0.005)
        # unkeyed bulk work never hedges (no affinity, no second choice)
        assert router._hedge_delay_s(replica, CLASS_BULK_AUDIT,
                                     keyed=False) == 0.0
    finally:
        router.close()
    # an unattainable floor: the hedge is HELD and the hold is counted
    router = build(2.0)
    try:
        assert router._hedge_delay_s(replica, CLASS_BULK_AUDIT,
                                     keyed=True) == 0.0
        assert router.hedge_stats()["bulk_budget_held"] == 1
    finally:
        router.close()
    # default: bulk hedging stays off (pre-elastic behavior)
    monkeypatch.delenv("GETHSHARDING_FLEET_HEDGE_BULK_MIN_BUDGET")
    router = FleetRouter([replica], health_interval_s=0.0, hedge_ms=5.0,
                         registry=_registry())
    try:
        assert router._hedge_delay_s(replica, CLASS_BULK_AUDIT,
                                     keyed=True) == 0.0
        assert router.hedge_stats()["bulk_budget_held"] == 0
    finally:
        router.close()
